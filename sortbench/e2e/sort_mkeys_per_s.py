"""Keys of the window's completed, correct calls over the window's
length (the sum of the calls' durations; the check between calls lies
outside it), host keys in to host keys out, in millions per second
(host clock)."""


def read(run):
    return run.keys_ok / run.window_s / 1e6
