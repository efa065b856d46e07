"""host_sched_ms.gap, .tok (ms, lower is better; layer: cluster and
scheduler). Median duration of the program's ``serving.schedule`` span
over the dispatches of the traced window: taking the dispatch locks,
expiring deadlines, pumping the requeue, scheduling the rows."""

from harness import spans


def read(run):
    sp = spans.loaded(run)
    return None if sp is None else spans.median_ms(sp["host"],
                                                   "serving.schedule")
