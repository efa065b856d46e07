"""ttft_prefill_wait_ms.ttft (ms, lower is better; layer: cluster and
scheduler; moves ttft_p90_ms). Median of ``t_first_chunk - t_admit`` over
the program's ``serving.first_token`` markers of the requests submitted
in the window: from admission to the first dispatch that carried one of
its prefill chunks (the wait for chunk budget behind other prompts).
None where the ring has wrapped past the window's open."""

from harness import spans


def read(run):
    return spans.ttft_part_ms(run, "t_first_chunk", "t_admit")
