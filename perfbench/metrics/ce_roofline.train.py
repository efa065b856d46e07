"""ce_roofline.train (%, higher is better; layer: kernels; moves
train_tok_s). Least time for the head matmul and the cross-entropy of
the traced steps over the device time of the ops that carry the fused
cross-entropy's name: the forward kernel (``pallas_call(name=
"paddle_tpu.fused_ce")``) and the backward's XLA ops, traced under a
scope of the same name. The name is read from the op's ``tf_op`` stat
(PERF.md, section 3); nothing matching means nothing reported."""

from harness import costs, spans

SCOPE = r"paddle_tpu\.fused_ce\b"
ITEMSIZE = 4        # the head and the hidden states reach it in fp32


def ce_work(cfg, tokens):
    """FLOPs and bytes the head and its loss need for ``tokens`` trained
    tokens: the logits forward and both backward products (hidden-state
    and weight gradients), 2 FLOPs a multiply-add each, the backward's
    recomputation of the logits not counted; hidden states and head
    weight read once forward and once backward, their gradients written
    once."""
    h, v = cfg["hidden_size"], cfg["vocab_size"]
    return 6 * tokens * h * v, 3 * (tokens * h + h * v) * ITEMSIZE


def read(run):
    n = run.facts.get("steps_traced")
    if not n or run.peaks is None:
        return None
    spent = spans.scope_seconds(spans.device_ops(run), SCOPE,
                                run.facts["window_ns"])
    if not spent:
        return None
    flops, nbytes = ce_work(run.cfg, run.facts["tokens_per_step"] * n)
    least, bound = costs.least_seconds(flops, nbytes, run.peaks)
    run.note(metric="ce_roofline.train", least_seconds=least, bound=bound,
             kernel_seconds=spent, flops=flops, bytes=nbytes)
    return 100.0 * least / spent
