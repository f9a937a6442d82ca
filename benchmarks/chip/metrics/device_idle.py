"""Share of the traced window in which no operation ran on the device
(1 - busy / window, the busy time being the union of the device's
operation intervals), from the profiler trace."""


def read(rec):
    tr = rec.get("trace")
    if tr is None or tr.window_s <= 0:
        return None
    return 100.0 * tr.idle_share
