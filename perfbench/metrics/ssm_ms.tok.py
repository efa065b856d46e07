"""ssm_ms.tok (ms, lower is better; layer: step program; moves
serve_tok_s). Device time a step under the scope ``paddle_tpu.ssm``: the
state-space mixers, from their in-projection to their gate (the
convolution, the scan and the state traffic are inside it, under
``paddle_tpu.ssm_scan``). Nothing under that scope on the trace means
nothing reported."""

from harness import readers, scopes

SCOPE = r"paddle_tpu\.ssm\b"


def read(run):
    steps = len(readers.steps(run))
    under = scopes.seconds(run, SCOPE) if steps else None
    if not under:
        return None
    run.note(metric="ssm_ms.tok", scope_seconds=under, steps=steps)
    return 1e3 * under / steps
