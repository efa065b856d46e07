"""ttft_queue_ms.ttft (ms, lower is better; layer: cluster and scheduler;
moves ttft_p90_ms). Median of ``t_admit - t_submit`` over the program's
``serving.first_token`` markers of the requests submitted in the window:
from the cluster's ``submit()`` to admission into the continuous batch
(the wait for a batch slot and pages). None where the ring has wrapped
past the window's open."""

from harness import spans


def read(run):
    return spans.ttft_part_ms(run, "t_admit", "t_submit")
