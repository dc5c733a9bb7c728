"""Share of the delta lanes the fused retrievals decoded that a request
asked for: 100 x the program's ``retrieve.rows`` counter over its
``retrieve.lanes_decoded`` (every lane of every padded page), over every
retrieval of the traced run, warm-up included
(``bench.program_trace.program_counters``)."""
from bench import program_trace


def read(run):
    c = program_trace.program_counters(run) or {}
    lanes = c.get("retrieve.lanes_decoded", 0)
    if lanes <= 0:
        return None
    return 100.0 * c.get("retrieve.rows", 0) / lanes
