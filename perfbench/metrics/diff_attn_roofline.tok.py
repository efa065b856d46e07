"""diff_attn_roofline.tok (%, higher is better; layer: kernels; moves
serve_tok_s). Least time for the differential attention of the traced
window (the family's ``diff_attn_work``: min(context, window) keys a row
on window layers, the shared pool's context once for each of its
readers) over the device time under the scope ``paddle_tpu.diff_attn``
(projections, kernel, the pairs' difference and norm)."""

from harness import scopes

SCOPE = r"paddle_tpu\.diff_attn\b"


def read(run):
    work = getattr(run.family, "diff_attn_work", None)
    if work is None or "prefill" not in run.facts:
        return None
    flops, nbytes = work(run.cfg, run.facts["prefill"], run.facts["decode"])
    return scopes.roofline(run, SCOPE, flops, nbytes,
                           "diff_attn_roofline.tok")
