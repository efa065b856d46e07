"""step_period_ms.* (ms, lower is better; layer: cluster and scheduler).
Median time between the starts of consecutive executions of the engine's
mixed-step program on the device: host work plus device work per
dispatch."""

from harness import readers, stats


def read(run):
    starts = [s for _, s, _ in readers.steps(run)]
    if len(starts) < 2:
        return None
    return stats.median([b - a for a, b in zip(starts, starts[1:])]) / 1e6
