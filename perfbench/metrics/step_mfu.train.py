"""step_mfu.train (%, higher is better; layer: whole step against the
chip; moves train_tok_s). The usual MFU: 6 x matmul parameters plus causal
attention per trained token, recomputation not counted, over the peak, the
traced wall time and the chips (or the bytes of the training state, read
and written once a step, over the bandwidth, where that is larger)."""

from harness import costs, readers


def read(run):
    ws, n = readers.window_s(run), run.facts.get("steps_traced")
    if run.peaks is None or not ws or not n:
        return None
    fam = run.family
    flops = fam.train_flops_per_token(run.cfg, run.facts["seq_len"]) \
        * run.facts["tokens_per_step"] * n
    nbytes = 2 * 16 * fam.total_params(run.cfg) * n
    least, bound = costs.least_seconds(flops, nbytes, run.peaks)
    run.note(metric="step_mfu", least_seconds=least, bound=bound,
             window_s=ws, steps=n, flops=flops, bytes=nbytes)
    return 100.0 * least / (ws * run.chips)
