"""Predicate planes the program evaluated on the device per filtered
retrieval: its ``filter.planes`` counter over its ``filter.requests``
(``repro.obs``), totals over every retrieval of the traced run, warm-up
included (``bench.program_trace.program_counters``).  1.0 where every
request builds its plane afresh, lower where planes are reused; 0 where
no retrieval carried a filter.  None without a trace, or on a program
without the label plane's span and counters."""
from bench import program_trace

SPAN = "graphar.filter"


def _program_counts_it() -> bool:
    from repro import obs
    return SPAN in getattr(obs, "ALL_SPANS", ())


def read(run):
    c = program_trace.program_counters(run)
    if c is None or not _program_counts_it():
        return None
    return c.get("filter.planes", 0) / max(c.get("filter.requests", 0), 1)
