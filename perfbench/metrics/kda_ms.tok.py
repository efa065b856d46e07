"""kda_ms.tok (ms, lower is better; layer: step program; moves
serve_tok_s). Device time a step under the scope ``paddle_tpu.kda``: the
gated delta-rule mixers, from their projections to their output
projection (the convolutions, the recurrence and the state traffic are
inside it, under ``paddle_tpu.kda_scan``). Nothing under that scope on
the trace (a program without such layers) means nothing reported."""

from harness import readers, scopes

SCOPE = r"paddle_tpu\.kda\b"


def read(run):
    steps = len(readers.steps(run))
    under = scopes.seconds(run, SCOPE) if steps else None
    if not under:
        return None
    run.note(metric="kda_ms.tok", scope_seconds=under, steps=steps)
    return 1e3 * under / steps
