"""Share of the traced window in the program's ``encode.words``
annotations: the host codec encode of the keys into uint32 words, inside
``phase:encode`` (``models/api.py``; program span on the trace's clock)."""

from sortbench import progspans


def read(run):
    return progspans.annotation_share_pct(run, "encode.words")
