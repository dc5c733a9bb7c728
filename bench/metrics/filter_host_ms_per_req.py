"""Device idle time whose innermost host span is the program's label
plane (``graphar.filter``, ``repro.obs.FILTER``) in the traced window,
per completed request: the host builds a predicate plane while the
device waits.  0 in a cell whose requests carry no filter; None without
a trace, or on a program without the span."""

SPAN = "graphar.filter"


def _program_opens_it() -> bool:
    try:
        from repro import obs
    except ImportError:
        return False
    return SPAN in getattr(obs, "ALL_SPANS", ())


def read(run):
    s = run.summary
    if s is None or run.completed == 0 or not _program_opens_it():
        return None
    return s.idle_ns.get(SPAN, 0.0) / 1e6 / run.completed
