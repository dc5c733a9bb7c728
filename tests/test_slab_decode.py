"""The resident decode's slab kernel against the jnp reference.

The Pallas engine's resident plan is laid out page by page
(``repro.core.encoding.slab_plan``) wherever a page makes whole
(8, 128) slabs; one kernel then DMAs each staged page by its index and
decodes it in VMEM.  Every case decodes the same pages through the
Pallas entry (interpret mode on the CPU) over the slab plan and through
the jnp engine's reference over the row plan, and asks for equal ids and
bitmap words, bit for bit.
"""
import json
import os
import subprocess
import sys
import textwrap
from pathlib import Path

import numpy as np
import pytest
import jax.numpy as jnp

from repro.core.encoding import (ALLOWED_WIDTHS, delta_encode_column,
                                 pack_column)
from repro.kernels.label_filter import kernel as LK
from repro.kernels.label_filter import ref as LR
from repro.kernels.pac_decode import kernel as K
from repro.kernels.pac_decode import ref as R

SRC = str(Path(__file__).resolve().parents[1] / "src")
MINI = 32


def _values(page_size, n_pages, widths, seed, short=0):
    """Sorted ids whose miniblocks take the given bit widths in turn.

    Miniblock ``m`` of each page gets the width ``widths[m %
    len(widths)]``: its residuals span 0 to ``2**(w - 1)`` exactly
    (``2**16`` for 32, which rounds up to it) over the page's base gap.
    ``short`` rows come off the last page."""
    rng = np.random.default_rng(seed)
    pages = []
    last = int(rng.integers(0, 1000))
    for k in range(n_pages):
        n = page_size - (short if k == n_pages - 1 else 0)
        deltas = np.empty(n - 1, np.int64)
        for m, i in enumerate(range(0, n - 1, MINI)):
            w = widths[m % len(widths)]
            top = 0 if w == 0 else (1 << 16 if w == 32 else 1 << (w - 1))
            r = rng.integers(0, top + 1, min(MINI, n - 1 - i))
            r[0], r[-1] = 0, top
            deltas[i:i + len(r)] = r
        page = last + int(rng.integers(1, 50)) + np.concatenate(
            [[0], (deltas + int(rng.integers(0, 4))).cumsum()])
        pages.append(page)
        last = int(page[-1])
    vals = np.concatenate(pages)
    assert vals[-1] < 2**31
    return vals


def _column(page_size=1024, n_pages=6, widths=ALLOWED_WIDTHS, seed=0,
            short=0):
    packed = pack_column(delta_encode_column(
        _values(page_size, n_pages, widths, seed, short), page_size))
    return packed, packed.device_plan("pallas"), packed.device_plan("jax")


def _idx(n_pages, p_pad, seed=1):
    return np.random.default_rng(seed).integers(0, n_pages, p_pad) \
        .astype(np.int32)


def _decode(packed, slab, rows, idx, kernel=True):
    assert K.decodes_in_kernel(slab) is kernel
    got = K.gather_decode_pallas(*slab, jnp.asarray(idx),
                                 page_size=packed.page_size)
    want = R.gather_decode_ref(*rows, jnp.asarray(idx),
                               page_size=packed.page_size)
    np.testing.assert_array_equal(np.asarray(got), np.asarray(want))


def _fused(packed, slab, rows, p_pad, filtered=False, seed=2):
    """The fused entry, words and ids, against its reference."""
    ps = packed.page_size
    rng = np.random.default_rng(seed)
    n_words = -(-int(packed.first.max() + 8 * ps * 2**16) // 32)
    n_words = min(n_words, 1 << 16)
    t = 4 * p_pad
    staged = np.zeros(p_pad + t + 1, np.int32)
    staged[:p_pad] = _idx(packed.n_pages, p_pad, seed)
    staged[p_pad:-1] = rng.integers(0, p_pad * ps, t)
    staged[-1] = t - 5
    winit = jnp.zeros(n_words, jnp.uint32)
    kw = dict(page_size=ps, n_words=n_words, p_pad=p_pad, want_ids=True)
    if filtered:
        fwords = jnp.asarray(rng.integers(0, 2**32, n_words, np.uint32))
        got = LK.fused_gather_decode_filter_bitmap_batch(
            *slab, jnp.asarray(staged), fwords, winit, **kw)
        want = LR.fused_gather_filter_batch_ref(
            *rows, jnp.asarray(staged), fwords, winit, **kw)
    else:
        got = K.fused_gather_decode_bitmap_batch(
            *slab, jnp.asarray(staged), winit, **kw)
        want = R.fused_gather_batch_ref(*rows, jnp.asarray(staged), winit,
                                        **kw)
    assert np.asarray(want[0]).any()
    for g, w in zip(got, want):
        np.testing.assert_array_equal(np.asarray(g), np.asarray(w))


def _one_width(w):
    def case():
        packed, slab, rows = _column(widths=(w,), seed=w)
        assert set(np.unique(packed.bit_widths)) == {w}
        _decode(packed, slab, rows, _idx(packed.n_pages, 16))
    return case


def _mixed_widths():
    packed, slab, rows = _column(page_size=2048, n_pages=3)
    # every width inside one page
    assert set(np.unique(packed.bit_widths[0])) == set(ALLOWED_WIDTHS)
    _decode(packed, slab, rows, _idx(packed.n_pages, 8))


def _short_last_page():
    packed, slab, rows = _column(short=700)
    assert packed.counts[-1, 0] == 1024 - 700
    _decode(packed, slab, rows, np.arange(packed.n_pages, dtype=np.int32))


def _clip_padded():
    packed, slab, rows = _column()
    n = packed.n_pages
    idx = np.zeros(32, np.int32)              # padded with page 0
    idx[:5] = [3, 1, n - 1, n + 4, 2**20]     # past the end: clipped
    _decode(packed, slab, rows, idx)


def _sharded():
    """The partition plane's sharded entries on four forced host
    devices, in a fresh process (the device count is fixed at start)."""
    code = textwrap.dedent(f"""
        import json, sys
        import numpy as np
        import jax, jax.numpy as jnp
        from jax.sharding import NamedSharding, PartitionSpec as P
        sys.path.insert(0, {os.path.dirname(__file__)!r})
        from test_slab_decode import _values
        from repro.core.encoding import delta_encode_column
        from repro.core.partition import partition_column
        from repro.kernels import shard
        from repro.kernels.pac_decode import kernel as K
        col = delta_encode_column(_values(1024, 10, (0, 2, 8, 32), 5), 1024)
        parts = partition_column(col, 4)
        (mesh, slab, pmax), (_, rows, _) = (parts.device_plan("pallas"),
                                            parts.device_plan("jax"))
        g, p_pad, t = 4, 8, 64
        rng = np.random.default_rng(3)
        staged = np.zeros((g, p_pad + t + 1), np.int32)
        staged[:, :p_pad] = rng.integers(0, pmax, (g, p_pad))
        staged[:, p_pad:-1] = rng.integers(0, p_pad * 1024, (g, t))
        staged[:, -1] = t
        staged = jax.device_put(staged, NamedSharding(mesh, P("part")))
        idx = jax.device_put(np.asarray(staged)[:, :p_pad],
                             NamedSharding(mesh, P("part")))
        out = {{"devices": len(mesh.devices.flat),
                "kernel": K.decodes_in_kernel(slab)}}
        for name, fn, args in (
                ("fused", lambda e: shard.sharded_fused_entry(
                    mesh, e, 1024, 4096, p_pad, True, False), (staged,)),
                ("decode", lambda e: shard.sharded_decode_entry(
                    mesh, e, 1024, p_pad), (idx,))):
            got = jax.tree.leaves(fn("pallas")(*slab, *args))
            want = jax.tree.leaves(fn("jax")(*rows, *args))
            out[name] = all(np.array_equal(a, b)
                            for a, b in zip(got, want))
            out[name + "_bits"] = int(np.asarray(want[0]).any())
        print(json.dumps(out))
    """)
    env = dict(os.environ, JAX_PLATFORMS="cpu",
               XLA_FLAGS="--xla_force_host_platform_device_count=4",
               PYTHONPATH=SRC + os.pathsep + os.environ.get("PYTHONPATH", ""))
    proc = subprocess.run([sys.executable, "-c", code], env=env,
                          capture_output=True, text=True, timeout=600)
    assert proc.returncode == 0, proc.stderr[-3000:]
    out = json.loads(proc.stdout.strip().splitlines()[-1])
    assert out == {"devices": 4, "kernel": True, "fused": True,
                   "fused_bits": 1, "decode": True, "decode_bits": 1}


def _small_page():
    """A page that makes no whole slab keeps the row plan and the XLA
    gathers."""
    packed, slab, rows = _column(page_size=256, n_pages=12)
    assert slab[1].shape == rows[1].shape == (12, 255)
    _decode(packed, slab, rows, _idx(12, 16), kernel=False)
    _fused(packed, slab, rows, 16)


CASES = {
    **{f"width-{w}": _one_width(w) for w in ALLOWED_WIDTHS},
    "mixed-widths": _mixed_widths,
    "short-last-page": _short_last_page,
    "clip-padded": _clip_padded,
    "p_pad-1024": lambda: _fused(*_column(), 1024),
    "p_pad-2048": lambda: _fused(*_column(), 2048),
    "filtered": lambda: _fused(*_column(), 64, filtered=True),
    "sharded": _sharded,
    "small-page-xla": _small_page,
}


@pytest.mark.parametrize("case", list(CASES))
def test_slab_decode_matches_reference(case):
    CASES[case]()
