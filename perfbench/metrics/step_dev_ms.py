"""step_dev_ms.* (ms, lower is better; layer: step program). Median
device duration of one execution of the step program in the traced
window."""

from harness import readers, stats


def read(run):
    ev = readers.steps(run)
    return stats.median([d for _, _, d in ev]) / 1e6 if ev else None
