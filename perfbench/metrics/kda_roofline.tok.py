"""kda_roofline.tok (%, higher is better; layer: kernels; moves
serve_tok_s). Least time for the convolutions and the gated delta rule
of the traced window (the family's ``kda_work``: the matrix states once
in and once out a row a layer, float32; the convolutions' inputs, q, k,
v, g, beta, o once a token; the recurrence's FLOPs a decoded token, the
chunkwise form's a prompt's token; the larger of bytes over the
bandwidth and FLOPs over the peak) over the device time under the scope
``paddle_tpu.kda_scan``: by scope, so that it reads the same work
whatever implements it (the Pallas step ``paddle_tpu.kda_step``, XLA
ops, or both). A family without ``kda_work`` or a trace with nothing
under the scope reports nothing."""

from harness import scopes

SCOPE = r"paddle_tpu\.kda_scan\b"


def read(run):
    work = getattr(run.family, "kda_work", None)
    if work is None or "prefill" not in run.facts:
        return None
    flops, nbytes = work(run.cfg, run.facts["prefill"], run.facts["decode"])
    return scopes.roofline(run, SCOPE, flops, nbytes, "kda_roofline.tok")
