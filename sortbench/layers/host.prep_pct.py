"""Share of the window the host spent preparing keys: the program's
``phase:encode``, ``phase:device_put`` and ``phase:ingest`` spans
(``models/api.py``, ``models/ingest.py``, ``ops/keys.py``)."""


def read(run):
    return run.phase_share_pct("encode", "device_put", "ingest")
