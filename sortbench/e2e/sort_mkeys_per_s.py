"""Keys of the window's completed, correct calls over the window's
length (start to the last call's return), host keys in to host keys
out, in millions per second (host clock)."""


def read(run):
    return run.keys_ok / run.window_s / 1e6
