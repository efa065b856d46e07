"""Rooflines read by SCOPE (``jax.named_scope``: the ``tf_op`` stat of a
device op) and not by kernel name, so that the share reads the same work
whatever implements it: a Pallas kernel, XLA ops, or both."""

from . import costs, readers, spans


def seconds(run, pattern):
    """Device seconds of the traced window under the scope, or None."""
    _, win = readers.traced(run)
    if win is None:
        return None
    return spans.scope_seconds(spans.device_ops(run), pattern, win)


def roofline(run, pattern, flops, nbytes, name):
    """Least time for ``flops`` and ``nbytes`` over the device time of
    the ops under the scope (percent); None where nothing ran there."""
    spent = seconds(run, pattern)
    if not spent or not flops or run.peaks is None:
        return None
    least, bound = costs.least_seconds(flops, nbytes, run.peaks)
    run.note(metric=name, least_seconds=least, bound=bound,
             scope_seconds=spent, flops=flops, bytes=nbytes)
    return 100.0 * least / spent
