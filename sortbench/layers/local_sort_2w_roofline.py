"""Share of its roofline that the 2-word local sort reaches on the
busiest chip, whatever engine sorts.

Floor: one read and one write of the keys each chip holds, at the HBM
peak (``peaks.json``): 2 x keys per chip x 8 B (two uint32 words a key)
/ HBM bytes/s per traced call.  Time: the device seconds of the ops
tagged ``layer="local_sort"`` (every op of the local-sort programs: the
variadic ``lax.sort`` of the duplicate reroute or a Pallas pair kernel)
on the chip that spent the most in them.  None where no op carries the
tag.
"""

from sortbench import spec

KEY_BYTES = 8
TAG = 'layer="local_sort"'


def read(run):
    tr = run.trace
    if tr is None or not tr.calls:
        return None
    spent = max(tr.op_seconds(d, lambda hlo: TAG in hlo) for d in tr.devices)
    if spent <= 0:
        return None
    peak = spec.peaks(run.device["kind"])["hbm_bytes_per_s"]
    per_chip = run.cell.config["keys_per_call"] / run.cell.chips
    floor_s = 2 * per_chip * KEY_BYTES / peak * tr.calls
    return 100.0 * floor_s / spent
