"""mtp_ms.tok (ms, lower is better; layer: step program; moves
serve_tok_s). Device time a step under the scope ``paddle_tpu.mtp``: the
drafting module a speculative engine runs in the step program on every
packed position (the embedding of the following token, the two norms and
the input projection, its layer with its own pool, its norm, the head and
the argmax that is the next dispatch's draft). Nothing under that scope
on the trace (a program without such a module, or an engine that does
not draft with it) means nothing reported."""

from harness import readers, scopes

SCOPE = r"paddle_tpu\.mtp\b"


def read(run):
    steps = len(readers.steps(run))
    under = scopes.seconds(run, SCOPE) if steps else None
    if not under:
        return None
    run.note(metric="mtp_ms.tok", scope_seconds=under, steps=steps)
    return 1e3 * under / steps
