"""spec_accept_share.tok (%, higher is better; layer: cluster and
scheduler; moves serve_tok_s). Of the draft tokens the traced window's
dispatches verified, the share they accepted: the sums over the
``serving.dispatch`` spans of ``spec_accepted`` over ``spec_proposed``
(the program's counters, a speculative engine's). A program without the
counters, or a window that verified no draft, reports nothing."""

from harness import spans


def read(run):
    sp = spans.loaded(run)
    if sp is None:
        return None
    got = [s[3] for s in spans.named(sp["host"], spans.DISPATCH)
           if "spec_proposed" in s[3]]
    proposed = sum(g["spec_proposed"] for g in got)
    return 100.0 * sum(g["spec_accepted"] for g in got) / proposed \
        if proposed else None
