"""Share of the traced window in the program's ``verify.fingerprint``
annotations: the host folds of the input fingerprint, inside
``phase:verify`` (``models/api.py``; program span on the trace's clock)."""

from sortbench import progspans


def read(run):
    return progspans.annotation_share_pct(run, "verify.fingerprint")
