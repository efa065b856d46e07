"""moe_gemm_roofline.tok (%, higher is better; layer: kernels; moves
serve_tok_s). Least time for the routed experts' three GEMMs of the
traced window (each token's rows through gate, up and down; the weights
of the experts a step touches once a step: the family's ``moe_flops`` and
``moe_bytes``) over the device time of the kernels whose name starts
``paddle_tpu.grouped_gemm``. Where none is on the trace (a fallback to
XLA) nothing is reported."""

from harness import readers

PATTERN = r"^%?paddle_tpu\.grouped_gemm"


def read(run):
    w = readers.serve_work(run)
    if w is None or not w.get("moe_flops"):
        return None
    return readers.kernel_roofline(run, PATTERN, w["moe_flops"],
                                   w["moe_bytes"], "moe_gemm_roofline.tok")
