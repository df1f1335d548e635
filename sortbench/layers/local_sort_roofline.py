"""Share of its roofline that the local sort reaches on the busiest chip.

Floor: one read and one write of the keys each chip holds, at the HBM
peak (``peaks.json``): 2 x keys per chip x key bytes / HBM bytes/s per
call, the same whatever engine sorts.  Time: the device seconds of the
local sort's ops (:func:`sortbench.opclass.is_local_sort`) on the chip
that spent the most in them, over the traced calls.
"""

import numpy as np

from sortbench import opclass, spec


def read(run):
    tr = run.trace
    if tr is None or not tr.calls:
        return None
    cfg = run.cell.config
    peak = spec.peaks(run.device["kind"])["hbm_bytes_per_s"]
    per_chip = cfg["keys_per_call"] / run.cell.chips
    floor_s = 2 * per_chip * np.dtype(cfg["dtype"]).itemsize / peak * tr.calls
    spent = max(tr.op_seconds(d, opclass.is_local_sort) for d in tr.devices)
    return 100.0 * floor_s / spent if spent > 0 else None
