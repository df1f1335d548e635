"""Process start to window start: JAX's start, the native build check,
key generation and the warm-up calls (host clock)."""


def read(run):
    return run.setup_s
