"""Process start to the warm-up call's return: JAX's start, the native
build check, key generation and the warm-up call (host clock).  The
references, computed after it and before the window, are in neither."""


def read(run):
    return run.setup_s
