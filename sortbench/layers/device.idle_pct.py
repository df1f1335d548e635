"""Share of the traced window in which no operation ran on the busiest
device: 1 - (union of its op intervals) / window (device trace)."""


def read(run):
    tr = run.trace
    if tr is None:
        return None
    return 100.0 * (1.0 - tr.busy_s(tr.busiest()) / tr.window_s)
