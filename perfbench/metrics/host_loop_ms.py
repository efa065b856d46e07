"""host_loop_ms.gap, .tok (ms, lower is better; layer: cluster and
scheduler). Median self time of the program's ``replica.tick`` span over
the traced window: one turn of the replica's loop less the
``serving.dispatch`` inside it (admission from the backlog, reaping)."""

from harness import spans


def read(run):
    sp = spans.loaded(run)
    return None if sp is None else spans.tick_self_ms(sp["host"])
