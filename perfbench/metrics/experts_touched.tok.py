"""experts_touched.tok (%, higher is better; layer: step program; moves
serve_tok_s). Of a layer's routed experts, the share a dispatch's tokens
reached: the mean over the traced window's ``serving.dispatch`` spans of
their ``experts_touched`` (the program's counter: the median over the
expert layers of the experts that got a row) over ``n_routed_experts``.
A program without the counter reports nothing."""

from harness import spans


def read(run):
    sp = spans.loaded(run)
    experts = run.cfg.get("n_routed_experts")
    if sp is None or not experts:
        return None
    got = [s[3]["experts_touched"] for s in spans.named(
        sp["host"], spans.DISPATCH) if "experts_touched" in s[3]]
    return 100.0 * sum(got) / len(got) / experts if got else None
