"""Share of its roofline that the pair engine's run fix-up reaches on
the busiest chip.

Floor: one read of both words and one write of the low word of the keys
each chip holds, at the HBM peak (``peaks.json``): 3 x keys per chip x
4 B / HBM bytes/s per traced call.  Time: the device seconds of the ops
tagged ``stage="pair_fixup"`` (the in-VMEM fix-up kernel, the block
boundary strips, and the residual and high-word tie reduction) on the
chip that spent the most in them.  None where no op carries the tag.
"""

from sortbench import spec

WORD_BYTES = 4
#: two words read, the low word written
WORDS_MOVED = 3
TAG = 'stage="pair_fixup"'


def read(run):
    tr = run.trace
    if tr is None or not tr.calls:
        return None
    spent = max(tr.op_seconds(d, lambda hlo: TAG in hlo) for d in tr.devices)
    if spent <= 0:
        return None
    peak = spec.peaks(run.device["kind"])["hbm_bytes_per_s"]
    per_chip = run.cell.config["keys_per_call"] / run.cell.chips
    floor_s = WORDS_MOVED * per_chip * WORD_BYTES / peak * tr.calls
    return 100.0 * floor_s / spent
