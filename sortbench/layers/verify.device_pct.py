"""Share of the traced window the busiest chip spent in ops tagged
``layer="verify"``: the on-device verifier programs (``models/verify.py``)
(device trace)."""

from sortbench import progspans


def read(run):
    return progspans.layer_share_pct(run, "verify")
