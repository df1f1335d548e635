"""Share of the traced window in the program's ``egress.fetch``
annotations: the device->host copy of the sorted words, inside
``phase:decode`` (``DistributedSortResult.to_numpy``; program span on the
trace's clock)."""

from sortbench import progspans


def read(run):
    return progspans.annotation_share_pct(run, "egress.fetch")
