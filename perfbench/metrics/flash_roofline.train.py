"""flash_roofline.train (%, higher is better; layer: kernels; moves
train_tok_s). Least time for causal attention forward and backward of the
traced steps over the summed device time of the flash kernels' events
(forward, dQ, dK/dV). PATTERN was written after reading a trace by hand
(PERF.md, section 3)."""

from harness import readers

# No pallas_call of the program passes name=, so the profiler calls a
# kernel by its HLO text. The flash kernels are the custom calls to
# tpu_custom_call whose result is laid out [batch, heads, seq, head_dim]
# in bf16: forward (out, lse), dQ, dK/dV. The fused cross-entropy kernel
# returns f32[tokens, 1] and does not match.
PATTERN = r"custom-call tpu_custom_call \(?bf16\[{batch},\d+,{seq},{dim}\]"


def read(run):
    n = run.facts.get("steps_traced")
    if not n:
        return None
    seq, rows = run.facts["seq_len"], run.facts["sequences_per_step"]
    cfg, fam = run.cfg, run.family
    dim = cfg.get("head_dim") or \
        cfg["hidden_size"] // cfg["num_attention_heads"]
    pattern = PATTERN.format(batch=rows, seq=seq, dim=dim)
    return readers.kernel_roofline(
        run, pattern, fam.train_attn_flops(cfg, seq, rows) * n,
        fam.train_attn_bytes(cfg, seq, rows) * n, "flash_roofline.train")
