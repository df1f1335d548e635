"""Share of the traced window in the program's ``pair.residual``
annotations: the readback of the pair engine's residual flag and
high-word tie count inside ``phase:sort`` (``models/local.py``), where
the host waits for the pair sort (program span on the trace's clock)."""

from sortbench import progspans


def read(run):
    return progspans.annotation_share_pct(run, "pair.residual")
