"""mla_roofline.tok (%, higher is better; layer: kernels; moves
serve_tok_s). Least time for the latent attention of the traced window
(the absorbed form's FLOPs and the latent rows each context reads, from
the family's counts) over the device time of the kernel named
``paddle_tpu.ragged_mla_attn`` (a kernel's ``name=`` starts its op's
name on the trace). Where that kernel is not on the trace (a fallback to
XLA, a program without it) nothing is reported."""

from harness import readers

PATTERN = r"^%?paddle_tpu\.ragged_mla_attn\b"


def read(run):
    w = readers.serve_work(run)
    if w is None or not w.get("attn_flops"):
        return None
    return readers.kernel_roofline(run, PATTERN, w["attn_flops"],
                                   w["attn_bytes"], "mla_roofline.tok")
