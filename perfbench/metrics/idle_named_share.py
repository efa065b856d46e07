"""idle_named_share.gap, .tok (%, higher is better; layer: device). Of
the first device's idle nanoseconds in the traced window (the complement
of the "XLA Ops" line, as idle_share takes them), the share lying under
some span of the program on the host planes of the same trace. Idle
under ``serving.wait`` (launch latency, the result's transfer) counts as
named; the printed table ``idle_seconds_by_span`` lists it on its own."""

from harness import spans


def read(run):
    sp = spans.loaded(run)
    if sp is None:
        return None
    idle = spans.idle_intervals(run.facts["trace"], run.facts["window_ns"])
    return spans.named_share(idle, sp["host"])
