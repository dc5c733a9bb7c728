"""Compile the main-path kernels for a TPU v5e that is described, not
attached, and check how every Pallas entry picks its mode.

The compile tests lower each entry at the widths the chip smoke runs
(2048-row pages, a LiveJournal-sized target id space) for one chip of a
described ``v5e:2x2`` and assert that a Mosaic kernel
(``tpu_custom_call``) is in the compiled program: what the TPU compiler
refuses -- unaligned blocks, in-kernel gathers, too much VMEM -- fails
here instead of on the chip.  The topology is described inside a module
fixture (never at import), so every xdist worker collects the same tests
and only the worker running this file loads the TPU library.

The CPU tests check :func:`repro.kernels._pad.pallas_interpret` with the
backend steered inside the test, the named error a refused entry raises
off the CPU, and the compile-cache helper of the entry points.
"""
import os

import jax
import jax.numpy as jnp
import pytest
from jax.sharding import SingleDeviceSharding

from repro.core.labels import L, compile_cond
from repro.kernels import _pad
from repro.kernels.bitmap_select import kernel as BK
from repro.kernels.flash_attention import kernel as FK
from repro.kernels.label_filter import kernel as LK
from repro.kernels.pac_decode import kernel as K
from repro.kernels.rle_filter import kernel as RK
from repro.kernels.traversal import kernel as TK

PAGE = 2048                       # DEFAULT_PAGE_SIZE: the adjacency pages
N_MINI = PAGE // 32
PLAN_ROWS = 16384                 # pages of a 2**25-edge value column
N_WORDS = -(-4_847_571 // 32)     # soc-LiveJournal1's vertex id space
P_PAD = 64
T_PAD = 1024
U32, I32 = jnp.uint32, jnp.int32


@pytest.fixture(scope="module")
def topo():
    from jax.experimental import topologies
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    try:
        return topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:  # no TPU compiler here
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")


@pytest.fixture(scope="module")
def one_chip(topo):
    return SingleDeviceSharding(topo.devices[0])


@pytest.fixture
def no_compile_cache():
    """A compile for a described chip is written to the persistent cache
    but cannot be read back without the chip: keep the cache off."""
    from jax.experimental.compilation_cache import compilation_cache as cc
    was = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    cc.reset_cache()
    yield
    jax.config.update("jax_enable_compilation_cache", was)
    cc.reset_cache()


def _plan(sds):
    return (sds((PLAN_ROWS, 1), I32), sds((PLAN_ROWS, PAGE - 1), I32),
            sds((PLAN_ROWS, PAGE - 1), I32), sds((PLAN_ROWS, PAGE), U32))


def _pack_args(sds, n):
    return (sds((n, 1), I32), sds((n, N_MINI), I32), sds((n, N_MINI), I32),
            sds((n, N_MINI), I32), sds((n, PAGE), U32), sds((n, 1), I32))


def _label_plan(sds, k=3, n_pos=4096):
    return sds((k, n_pos), I32), sds((k, 2), I32)


_OPS = compile_cond((L("a") & ~L("b")) | L("c")).ops

COMPILES = {
    "gather_decode_pallas": lambda s: K.gather_decode_pallas.lower(
        *_plan(s), s((P_PAD,), I32), page_size=PAGE, interpret=False),
    "fused_gather_decode_bitmap_batch": lambda s:
        K.fused_gather_decode_bitmap_batch.lower(
            *_plan(s), s((P_PAD + T_PAD + 1,), I32), s((N_WORDS,), U32),
            page_size=PAGE, n_words=N_WORDS, p_pad=P_PAD, want_ids=False,
            interpret=False),
    "fused_gather_decode_bitmap_batch[want_ids]": lambda s:
        K.fused_gather_decode_bitmap_batch.lower(
            *_plan(s), s((P_PAD + T_PAD + 1,), I32), s((N_WORDS,), U32),
            page_size=PAGE, n_words=N_WORDS, p_pad=P_PAD, want_ids=True,
            interpret=False),
    "fused_gather_decode_filter_bitmap_batch": lambda s:
        LK.fused_gather_decode_filter_bitmap_batch.lower(
            *_plan(s), s((P_PAD + T_PAD + 1,), I32), s((N_WORDS,), U32),
            s((N_WORDS,), U32), page_size=PAGE, n_words=N_WORDS,
            p_pad=P_PAD, want_ids=False, interpret=False),
    "delta_decode_pallas": lambda s: K.delta_decode_pallas.lower(
        *_pack_args(s, P_PAD), page_size=PAGE, interpret=False),
    "fused_decode_bitmap_batch": lambda s: K.fused_decode_bitmap_batch.lower(
        *_pack_args(s, P_PAD), s((8, PAGE), I32), s((T_PAD,), I32),
        s((1, 1), I32), page_size=PAGE, n_words=N_WORDS, interpret=False),
    "fused_decode_filter_bitmap_batch": lambda s:
        LK.fused_decode_filter_bitmap_batch.lower(
            *_pack_args(s, P_PAD), s((8, PAGE), I32), s((T_PAD,), I32),
            s((1, 1), I32), *_label_plan(s), page_size=PAGE,
            n_words=N_WORDS, ops=_OPS, interpret=False),
    "cond_bitmap_pallas": lambda s: LK.cond_bitmap_pallas.lower(
        *_label_plan(s), n_words=N_WORDS, ops=_OPS, interpret=False),
    "flash_attention": lambda s: FK.flash_attention.lower(
        s((15, 512, 64), jnp.bfloat16), s((15, 512, 64), jnp.bfloat16),
        s((15, 512, 64), jnp.bfloat16), interpret=False),
}


@pytest.mark.parametrize("entry", sorted(COMPILES))
def test_entry_compiles_for_v5e(entry, one_chip, no_compile_cache):
    def sds(shape, dtype):
        return jax.ShapeDtypeStruct(shape, dtype, sharding=one_chip)

    compiled = COMPILES[entry](sds).compile()
    assert "tpu_custom_call" in compiled.as_text()


def test_sharded_fused_entry_compiles_for_v5e_2x2(topo, no_compile_cache,
                                                  monkeypatch):
    """The partition plane's shard_map dispatch over a 4-chip mesh, with
    the Pallas body lowered through Mosaic on every shard (the backend
    is steered to the TPU so the entry picks Mosaic, as it does there)."""
    import numpy as np
    from jax.sharding import Mesh, NamedSharding, PartitionSpec as P
    from repro.kernels.shard import sharded_fused_entry
    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
    mesh = Mesh(np.array(topo.devices), ("part",))
    g, rows = len(topo.devices), 1024

    def sds(shape, dtype):
        return jax.ShapeDtypeStruct(shape, dtype,
                                    sharding=NamedSharding(mesh, P("part")))

    plan = (sds((g * rows, 1), I32), sds((g * rows, PAGE - 1), I32),
            sds((g * rows, PAGE - 1), I32), sds((g * rows, PAGE), U32))
    fn = sharded_fused_entry(mesh, "pallas", PAGE, N_WORDS, P_PAD, False,
                             False)
    compiled = fn.lower(*plan, sds((g, P_PAD + T_PAD + 1), I32)).compile()
    assert "tpu_custom_call" in compiled.as_text()


# ------------------------------ CPU tests ---------------------------------

def _plain(shape, dtype):
    return jax.ShapeDtypeStruct(shape, dtype)


REFUSED = {
    "khop_scan_pallas": lambda interpret: TK.khop_scan_pallas.lower(
        _plain((4096,), I32), _plain((1025,), I32), _plain((64,), I32),
        _plain((2, 32), U32), n_out=1024, interpret=interpret),
    "_expand_pallas": lambda interpret: TK.two_hop_pallas.lower(
        _plain((4096,), I32), _plain((1025,), I32), _plain((4096,), I32),
        _plain((1025,), I32), _plain((64,), I32), _plain((32,), U32),
        n_key=1024, n_mid=1024, n_out=1024, n_words=32,
        interpret=interpret),
    "count_hop_pallas": lambda interpret: TK.count_hop_pallas.lower(
        _plain((4096,), I32), _plain((1025,), I32), _plain((8,), I32),
        _plain((8,), I32), n_key=1024, n_out=1024, interpret=interpret),
    "bitmap_pallas": lambda interpret: K.bitmap_pallas.lower(
        _plain((512,), I32), _plain((), I32), _plain((), I32), n_words=64,
        interpret=interpret),
    "fused_decode_bitmap": lambda interpret: K.fused_decode_bitmap.lower(
        *_pack_args(_plain, 4), _plain((), I32), page_size=PAGE,
        words_out=64, interpret=interpret),
    "rle_to_bitmap_pallas": lambda interpret: RK.rle_to_bitmap_pallas.lower(
        _plain((1, 128), I32), _plain((1, 3), I32), n_words=64,
        interpret=interpret),
    "bitmap_select_pallas": lambda interpret: BK.bitmap_select_pallas.lower(
        _plain((4, PAGE), jnp.float32), _plain((4, PAGE // 32), U32),
        page_size=PAGE, interpret=interpret),
}


@pytest.mark.parametrize("entry", sorted(REFUSED))
def test_refused_entry_raises_named_error(entry):
    """Lowering a refused entry for Mosaic raises before any compile,
    naming the entry and quoting the compiler's refusal."""
    with pytest.raises(_pad.KernelNotLowered, match=entry) as err:
        REFUSED[entry](False)
    assert "the compiler refused it with" in str(err.value)


def test_refused_entry_raises_off_the_cpu_by_default(monkeypatch):
    """With the backend steered to the TPU, the default mode of a refused
    entry is the named error -- never the interpreter."""
    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
    with pytest.raises(_pad.KernelNotLowered, match="count_hop_pallas"):
        TK.count_hop_pallas.lower(
            _plain((2048,), I32), _plain((1001,), I32), _plain((8,), I32),
            _plain((8,), I32), n_key=1000, n_out=1000)


@pytest.mark.parametrize("backend,want", [("cpu", True), ("tpu", False)])
def test_pallas_interpret_follows_backend(monkeypatch, backend, want):
    monkeypatch.setattr(jax, "default_backend", lambda: backend)
    assert _pad.pallas_interpret() is want
    # an explicit mode wins (compile tests lower for Mosaic from a CPU)
    assert _pad.pallas_interpret(not want) is (not want)


def test_pallas_interpret_refuses_other_backends(monkeypatch):
    monkeypatch.setattr(jax, "default_backend", lambda: "gpu")
    with pytest.raises(RuntimeError, match="'gpu'"):
        _pad.pallas_interpret()


def test_refuse_off_cpu_interprets_on_the_cpu(monkeypatch):
    monkeypatch.setattr(jax, "default_backend", lambda: "cpu")
    assert _pad.refuse_off_cpu("k", "no") is True
    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
    with pytest.raises(_pad.KernelNotLowered, match="'k'.*no"):
        _pad.refuse_off_cpu("k", "no")


@pytest.fixture
def cache_dir_restored():
    was = jax.config.jax_compilation_cache_dir
    yield
    jax.config.update("jax_compilation_cache_dir", was)


def test_compile_cache_defaults_to_repo_dir(monkeypatch, cache_dir_restored):
    from repro.launch import compile_cache as cc
    monkeypatch.delenv(cc.ENV_VAR, raising=False)
    root = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    want = os.path.join(root, ".jax_cache")
    assert cc.use_compile_cache() == want
    assert jax.config.jax_compilation_cache_dir == want


def test_compile_cache_leaves_env_dir_alone(monkeypatch, cache_dir_restored):
    from repro.launch import compile_cache as cc
    jax.config.update("jax_compilation_cache_dir", None)
    monkeypatch.setenv(cc.ENV_VAR, "/elsewhere/cache")
    assert cc.use_compile_cache() == "/elsewhere/cache"
    assert jax.config.jax_compilation_cache_dir is None
