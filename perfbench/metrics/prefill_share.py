"""prefill_share.gap, .tok (%, higher is better; layer: cluster and
scheduler). Sum of ``prefill_tokens`` over sum of ``tokens`` of the
program's ``serving.dispatch`` spans in the traced window."""

from harness import spans


def read(run):
    sp = spans.loaded(run)
    return None if sp is None else spans.stat_ratio(
        sp["host"], "prefill_tokens", "tokens")
