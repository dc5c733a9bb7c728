"""Per request: its span minus the device busy time inside it, averaged
over the requests of the traced window."""


def read(run):
    s = run.summary
    if s is None or len(s.request_ns) == 0:
        return None
    return float((s.request_ns - s.request_busy_ns).mean()) / 1e6
