"""batch_occupancy.gap, .tok (%, higher is better; layer: cluster and
scheduler). Sum of ``tokens`` over sum of ``t_cap`` of the program's
``serving.dispatch`` spans in the traced window: how full the dispatched
programs' token slots were."""

from harness import spans


def read(run):
    sp = spans.loaded(run)
    return None if sp is None else spans.stat_ratio(sp["host"], "tokens",
                                                    "t_cap")
