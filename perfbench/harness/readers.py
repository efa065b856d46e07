"""What the per-layer readers (``perfbench/metrics``) share. A reader
returns a number, or None where there is nothing to read (the harness
then leaves the metric out of the line; a share of a roofline or of a
peak is never reported as 0)."""

from . import costs, trace as tr


def traced(run):
    """(trace, traced window in ns), or (None, None) without a trace."""
    return run.facts.get("trace"), run.facts.get("window_ns")


def steps(run):
    """Executions of the step program inside the traced window."""
    t, win = traced(run)
    if t is None:
        return []
    return tr.module_events(t, run.facts["step_pattern"], win)


def window_s(run):
    _, win = traced(run)
    return None if win is None else (win[1] - win[0]) / 1e9


def serve_work(run):
    """FLOPs and bytes of the serving work the traced window did."""
    n = len(steps(run))
    if not n:
        return None
    return run.family.serve_work(run.cfg, n, run.facts["prefill"],
                                 run.facts["decode"])


def kernel_roofline(run, pattern, flops, nbytes, name):
    """Least time for ``flops`` and ``nbytes`` over the summed device
    time of the ops matching ``pattern`` (percent)."""
    t, win = traced(run)
    if t is None or run.peaks is None:
        return None
    spent = tr.op_seconds(t, pattern, win)
    if not spent or not flops:
        return None
    least, bound = costs.least_seconds(flops, nbytes, run.peaks)
    run.note(metric=name, least_seconds=least, bound=bound,
             kernel_seconds=spent, flops=flops, bytes=nbytes)
    return 100.0 * least / spent
