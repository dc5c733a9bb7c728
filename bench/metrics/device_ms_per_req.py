"""Device busy time in the traced window per completed request."""


def read(run):
    s = run.summary
    if s is None or run.completed == 0:
        return None
    return s.busy_ns / 1e6 / run.completed
