"""Device time of the predicate-plane program (``cond_bitmap_pallas``,
whose ops the trace labels ``jit_cond_bitmap_pallas/...``) in the traced
window, per completed request.  0 in a cell that builds no plane; None
without a trace."""

PROGRAM = "jit_cond_bitmap_pallas/"


def read(run):
    s = run.summary
    if s is None or run.completed == 0:
        return None
    ns = sum(t for name, t in s.op_ns.items() if name.startswith(PROGRAM))
    return ns / 1e6 / run.completed
