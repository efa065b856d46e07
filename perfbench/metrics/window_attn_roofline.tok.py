"""window_attn_roofline.tok (%, higher is better; layer: kernels; moves
serve_tok_s). Least time for the ragged attention of the window layers
in the traced window (the family's ``attn_work`` for that kind, from
what the program counted on the window's ``serving.dispatch`` spans for
one such layer: ``window_keys``, the (query, key) pairs of each row's
queries against the keys it reads, ``window_reads``, those keys, and
the new tokens; FLOPs over the peak against K/V bytes over the
bandwidth) over the device time under the scope
``paddle_tpu.attn_window`` (the kernel call and its packing). A program
without the counters or the scope reports nothing."""

from harness import scopes, spans

KIND = "window"
SCOPE = r"paddle_tpu\.attn_window\b"


def read(run):
    work = getattr(run.family, "attn_work", None)
    sp = spans.loaded(run)
    if work is None or sp is None:
        return None
    got = [s[3] for s in spans.named(sp["host"], spans.DISPATCH)
           if KIND + "_keys" in s[3]]
    if not got:
        return None
    flops, nbytes = work(run.cfg, KIND,
                         sum(g[KIND + "_keys"] for g in got),
                         sum(g[KIND + "_reads"] for g in got),
                         sum(g["tokens"] for g in got))
    return scopes.roofline(run, SCOPE, flops, nbytes,
                           KIND + "_attn_roofline.tok")
