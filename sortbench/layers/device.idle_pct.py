"""Share of the traced window in which no operation ran on the busiest
device: 1 - (union of its op intervals) / window, where the window is
the union of the ``sortbench.call`` intervals, so the check between two
calls is neither busy nor idle (device trace)."""


def read(run):
    tr = run.trace
    if tr is None:
        return None
    return 100.0 * (1.0 - tr.busy_s(tr.busiest()) / tr.window_s)
