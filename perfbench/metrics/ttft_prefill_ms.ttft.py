"""ttft_prefill_ms.ttft (ms, lower is better; layer: step program; moves
ttft_p90_ms). Median of ``t_first_token - t_first_chunk`` over the
program's ``serving.first_token`` markers of the requests submitted in
the window: the request's own chunked prefill, from its first chunk's
dispatch to its first token. None where the ring has wrapped past the
window's open."""

from harness import spans


def read(run):
    return spans.ttft_part_ms(run, "t_first_token", "t_first_chunk")
