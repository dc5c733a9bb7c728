"""Shared kernel-dispatch helpers: padding size classes + trace counters.

Every jitted kernel entry retraces once per distinct input-shape tuple, so
the dispatch layer pads variable-length inputs (page-index vectors, the
requested-row position vector, id lists) up to a small set of shared
**power-of-two size classes**.  The helpers here are the single home for
that policy (they were previously copy-pasted across the pac_decode and
label_filter op layers).

The module also keeps a lightweight **trace counter**: each jitted entry
calls :func:`note_trace` from inside its Python body, which only executes
when jax actually (re)traces -- a cache hit dispatches the compiled
executable without re-running the body.  Benchmarks and tests use
:func:`trace_count` to assert that steady-state serving dispatches hit
the jit cache (zero retraces).  The counts live in the program's counter
registry (:mod:`repro.obs`) under ``traces/<entry>``.

It also decides **how a Pallas kernel runs**: :func:`pallas_interpret`
picks the interpreter on the CPU backend and Mosaic lowering on the TPU,
so no kernel entry can quietly interpret on an accelerator.  Entries the
TPU compiler still refuses call :func:`refuse_off_cpu`, which raises
:class:`KernelNotLowered` instead of interpreting or handing the call to
the jnp reference.
"""
from __future__ import annotations

from typing import Dict, Optional

import jax

from repro import obs


def next_multiple(x: int, m: int) -> int:
    """Smallest multiple of ``m`` >= ``x``."""
    return -(-x // m) * m


def next_pow2(x: int) -> int:
    """Smallest power of two >= ``x`` (``next_pow2(0) == 1``)."""
    return 1 << max(x - 1, 0).bit_length()


def size_class(x: int, minimum: int = 1) -> int:
    """Shared pow2 padding class: smallest power of two >= max(x, minimum).

    The ``minimum`` floor collapses the long tail of tiny frontier shapes
    into one bucket, so steady-state serving dispatches stop retracing
    per distinct (small) batch shape.
    """
    return max(next_pow2(x), next_pow2(minimum))


# --------------------------------------------------------------------------
# kernel mode: interpreter on the CPU, Mosaic on the TPU
# --------------------------------------------------------------------------

class KernelNotLowered(NotImplementedError):
    """A Pallas entry the TPU compiler refuses was called off the CPU."""


def pallas_interpret(interpret: Optional[bool] = None) -> bool:
    """The ``interpret`` flag for a ``pallas_call`` in this process.

    An explicit ``interpret`` wins (a compile test passes ``False`` to
    lower for a described TPU from a CPU host).  Otherwise the default
    backend decides: the CPU runs kernels in the Pallas interpreter, the
    TPU lowers them through Mosaic, and any other backend is an error --
    there is no silent interpreter path on an accelerator.
    """
    if interpret is not None:
        return interpret
    backend = jax.default_backend()
    if backend == "cpu":
        return True
    if backend == "tpu":
        return False
    raise RuntimeError(f"Pallas kernels run on the TPU (Mosaic) or the "
                       f"CPU (interpreter), not on the {backend!r} backend")


def refuse_off_cpu(name: str, refusal: str,
                   interpret: Optional[bool] = None) -> bool:
    """Guard for an entry the TPU compiler refuses.

    Returns ``True`` (interpret) on the CPU; anywhere a Mosaic lowering
    would be needed it raises :class:`KernelNotLowered` naming the entry
    and quoting what the compiler said when the entry was compiled for a
    v5e.
    """
    if pallas_interpret(interpret):
        return True
    raise KernelNotLowered(f"Pallas entry {name!r} does not lower for the "
                           f"TPU; the compiler refused it with: {refusal}")


# --------------------------------------------------------------------------
# trace counting (retrace tripwire for steady-state dispatch benchmarks)
# --------------------------------------------------------------------------

_TRACES = "traces/"


def note_trace(name: str) -> None:
    """Record one (re)trace of the named jitted entry.

    Call from inside the jitted function's Python body: the body runs only
    on a jit-cache miss, so the counter equals the number of traces.
    """
    obs.count(_TRACES + name)


def trace_count(prefix: str = "") -> int:
    """Total traces recorded for entries whose name starts with ``prefix``."""
    return sum(v for k, v in trace_counts().items() if k.startswith(prefix))


def trace_counts() -> Dict[str, int]:
    """Per-entry trace counts (a copy)."""
    return {k[len(_TRACES):]: v for k, v in obs.counters().items()
            if k.startswith(_TRACES)}


def reset_trace_counts() -> None:
    obs.reset(_TRACES)
