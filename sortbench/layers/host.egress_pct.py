"""Share of the window in ``phase:decode``: device->host fetch and
decode of the sorted keys (``DistributedSortResult.to_numpy``, whose
streamed ``egress.*`` spans nest inside it)."""


def read(run):
    return run.phase_share_pct("decode")
