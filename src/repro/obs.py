"""The program's spans and counters: one place for both.

**Spans** mark the layer boundaries of the retrieval path.  ``span``
returns a :class:`jax.profiler.TraceAnnotation`: inside a profiler
session each span lands in the profiler's host plane, on the same clock
as the device's XLA ops, so a reduction of the trace can put every
device-idle gap down to the innermost span the host was in.  Outside a
session an annotation is inactive and costs well under a microsecond.
:data:`SPANS` lists the names every retrieval opens and
:data:`ALL_SPANS` every name the program opens (the label plane's
``graphar.filter`` besides), so a reader never hard-codes them.

**Counters** are plain Python ints, always on: requests, rows, decoded
lanes, pages decoded in the resident slab kernel
(``decode.kernel_pages``), bytes moved across the host-device
boundary, and the label plane's retrievals that carry a filter
(``filter.requests``), predicate planes evaluated on the device
(``filter.planes``) and the run positions those planes walk
(``filter.plane_runs``), counted where the work happens.  ``counters``
returns a copy; a reader takes the change between two copies.  The
kernel layer's trace counter (:func:`repro.kernels._pad.note_trace`)
records here too, under ``traces/<entry>``.
"""
from __future__ import annotations

from typing import Dict

#: root of the program's spans for one batched retrieval
RETRIEVE = "graphar.retrieve"
#: the ``<offset>`` lookup: edge-row ranges of the batch
EDGE_RANGES = "graphar.edge_ranges"
#: host planning: page set, zone-map prune, cache split, charging, row
#: positions, the staged vectors
PLAN = "graphar.plan"
#: the host-to-device put of the staged vectors
UPLOAD = "graphar.upload"
#: the kernel call: enqueue, and a trace or compile when one is due
LAUNCH = "graphar.launch"
#: waiting for the device, then the device-to-host copy of its outputs
PULL = "graphar.pull"
#: the host PAC build from the pulled bitmap
ASSEMBLE = "graphar.assemble"
#: ids from a PAC
TO_IDS = "graphar.to_ids"
#: the label plane's host work for a retrieval that carries a filter:
#: the filter's kernel inputs, its qualifying hull, the put of its run
#: arrays and the enqueue of its predicate plane
FILTER = "graphar.filter"

#: the spans every batched retrieval opens
SPANS = (RETRIEVE, EDGE_RANGES, PLAN, UPLOAD, LAUNCH, PULL, ASSEMBLE, TO_IDS)
#: every span the program opens
ALL_SPANS = SPANS + (FILTER,)

_COUNTERS: Dict[str, int] = {}


def span(name: str):
    """A host span named ``name`` (one of :data:`ALL_SPANS`), as a context
    manager: a :class:`jax.profiler.TraceAnnotation`.  JAX is imported
    here, on first use, so the numpy storage plane stays free of it."""
    from jax.profiler import TraceAnnotation
    return TraceAnnotation(name)


def count(name: str, n: int = 1) -> None:
    """Add ``n`` to counter ``name``."""
    _COUNTERS[name] = _COUNTERS.get(name, 0) + int(n)


def counters() -> Dict[str, int]:
    """Every counter's value (a copy)."""
    return dict(_COUNTERS)


def reset(prefix: str = "") -> None:
    """Drop the counters whose names start with ``prefix``."""
    for k in [k for k in _COUNTERS if k.startswith(prefix)]:
        del _COUNTERS[k]
