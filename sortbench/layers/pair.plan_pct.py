"""Share of the traced window in the program's ``pair.plan``
annotations: the host min/max of both words and the hi-word duplicate
sniff of the one-device 64-bit route, inside ``phase:encode``
(``models/api.py``; program span on the trace's clock)."""

from sortbench import progspans


def read(run):
    return progspans.annotation_share_pct(run, "pair.plan")
