"""Share of the window in ``phase:verify`` (``models/verify.py``): the
host input fingerprint and the on-device check of the result.  The
check blocks on the device, so the device's sort time not yet finished
when it starts is billed here too."""


def read(run):
    return run.phase_share_pct("verify")
