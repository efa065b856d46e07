"""opt_pass_ms.train (ms, lower is better; layer: step program; moves
train_tok_s). Device milliseconds a step in the ops traced under the
``optimizer`` scope (``Optimizer.step``), read from the op's ``tf_op``
stat. What XLA fuses into another phase's op (the AdamW update of a
matrix into its weight-gradient matmul) carries that op's scope and is
not counted here (PERF.md, section 5)."""

from harness import spans

SCOPE = r"(?:^|/)optimizer/"


def read(run):
    n = run.facts.get("steps_traced")
    if not n:
        return None
    spent = spans.scope_seconds(spans.device_ops(run), SCOPE,
                                run.facts["window_ns"])
    return None if spent is None else 1e3 * spent / n
