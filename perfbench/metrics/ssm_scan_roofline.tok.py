"""ssm_scan_roofline.tok (%, higher is better; layer: kernels; moves
serve_tok_s). Least time for the convolutions and scans of the traced
window (the family's ``scan_work``: the states once in and once out a
row a layer; x, step size, B, C, m once a token; the larger of bytes
over the bandwidth and FLOPs over the peak) over the device time under
the scope ``paddle_tpu.ssm_scan``: by scope, so that it reads the same
work whatever implements the scan."""

from harness import scopes

SCOPE = r"paddle_tpu\.ssm_scan\b"


def read(run):
    work = getattr(run.family, "scan_work", None)
    if work is None or "prefill" not in run.facts:
        return None
    flops, nbytes = work(run.cfg, run.facts["prefill"], run.facts["decode"])
    return scopes.roofline(run, SCOPE, flops, nbytes,
                           "ssm_scan_roofline.tok")
