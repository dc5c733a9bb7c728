"""XLA program executions on the device (the trace's module events) in
the traced window, per completed request."""


def read(run):
    s = run.summary
    if s is None or run.completed == 0:
        return None
    return s.launches / run.completed
