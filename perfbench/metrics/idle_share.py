"""idle_share.* (%, lower is better; layer: device). 1 minus the union of
the device's op intervals over the traced window; the fullest device where
there are several."""

from harness import readers, trace as tr


def read(run):
    t, win = readers.traced(run)
    return None if t is None else tr.idle_share(t, win)
