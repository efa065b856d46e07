"""host_apply_ms.gap, .tok (ms, lower is better; layer: cluster and
scheduler). Median duration of the program's ``serving.apply`` span over
the dispatches of the traced window: from the next tokens on the host to
the dispatch's return (prefix insert, speculative verification, emission
and the streaming callbacks, deadlines, pool gauges)."""

from harness import spans


def read(run):
    sp = spans.loaded(run)
    return None if sp is None else spans.median_ms(sp["host"],
                                                   "serving.apply")
