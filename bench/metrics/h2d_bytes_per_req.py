"""Bytes the program put on the device per fused retrieval: its
``transfer.h2d_bytes`` counter over its ``retrieve.requests``
(``repro.obs``), over every retrieval of the traced run, warm-up
included (``bench.program_trace.program_counters``)."""
from bench import program_trace


def read(run):
    c = program_trace.program_counters(run) or {}
    n = c.get("retrieve.requests", 0)
    if n <= 0 or "transfer.h2d_bytes" not in c:
        return None
    return c["transfer.h2d_bytes"] / n
