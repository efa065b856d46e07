"""moe_route_ms.tok (ms, lower is better; layer: step program; moves
serve_tok_s). Device time a step under the scope ``paddle_tpu.moe``
outside the grouped GEMM kernels: the router, the sort by expert, the
gather into packed rows and the weighted combine. Nothing under that
scope on the trace means nothing reported."""

from harness import readers, spans, trace as tr

SCOPE = r"paddle_tpu\.moe\b"
GEMM = r"^%?paddle_tpu\.grouped_gemm"


def read(run):
    steps = len(readers.steps(run))
    t, win = readers.traced(run)
    if not steps:
        return None
    under = spans.scope_seconds(spans.device_ops(run), SCOPE, win)
    if not under:
        return None
    gemms = tr.op_seconds(t, GEMM, win) or 0.0
    run.note(metric="moe_route_ms.tok", scope_seconds=under,
             gemm_seconds=gemms, steps=steps)
    return 1e3 * max(under - gemms, 0.0) / steps
