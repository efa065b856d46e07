"""gen_late_p99_ms.gap (ms, lower is better; layer: load generator; moves
gap_p95_ms). 99th percentile of how late each request was sent against
its schedule, over the whole window."""


def read(run):
    return run.facts.get("gen_late_p99_ms")
