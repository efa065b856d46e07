"""gap_p99_ms.gap (ms, lower is better; layer: cluster and scheduler;
moves gap_p95_ms). 99th percentile over every gap between consecutive
output tokens in the window. Every sequence a dispatch decodes gets its
token at once, so one slow dispatch is about 1% of a window's gaps: this
reads the window's second or third slowest dispatch, which in 5 of 24
runs of unchanged code was 10 to 55 ms late (PERF.md, section 2), and so
stands without a bound beside the bounded gap_p95_ms."""


def read(run):
    return run.facts.get("gap_p99_ms")
