"""Share of the traced window in which no op ran on the device:
1 - (union of device-op intervals) / window, averaged over the chips."""


def read(run):
    s = run.summary
    if s is None or s.window_ns <= 0:
        return None
    return 100.0 * (1.0 - s.busy_ns / s.window_ns)
