"""attn_roofline.gap, attn_roofline.tok (%, higher is better; layer:
kernels). Least time for the attention work of the traced window (FLOPs
and K/V bytes from the prompt lengths and output positions the benchmark
saw) over the summed device time of the ragged paged attention kernel's
events. PATTERN was written after reading a trace by hand (PERF.md,
section 3); nothing matching means nothing reported."""

from harness import readers

# No pallas_call of the program passes name=, so the profiler calls the
# kernel by its HLO text: a custom call to tpu_custom_call whose result
# holds the K and V page pools it writes in place (the only such call in
# the mixed step, one per layer).
PATTERN = r"custom-call tpu_custom_call .*\[{pages},{kv_heads},{page},{dim}\]"


def read(run):
    w = readers.serve_work(run)
    if w is None:
        return None
    e = run.mix["engine"]
    pattern = PATTERN.format(
        pages=e["num_pages"], kv_heads=run.cfg["num_key_value_heads"],
        page=e["page_size"], dim=run.cfg["head_dim"])
    return readers.kernel_roofline(run, pattern, w["attn_flops"],
                                   w["attn_bytes"], "attn_roofline")
