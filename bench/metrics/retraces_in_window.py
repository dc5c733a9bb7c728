"""Traces of the program's jitted entries during the window (the change
of ``repro.kernels._pad.trace_count()``): each is a compile, or a load
from the compile cache, that a request paid."""


def read(run):
    before, after = run.counters_before, run.counters_after
    if "traces" not in before or "traces" not in after:
        return None
    return after["traces"] - before["traces"]
