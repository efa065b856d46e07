"""Checks the benchmark makes of itself in every run (a few
milliseconds): the trace reduction against a small recorded trace whose
answers were worked by hand (``perfbench/data/sample_trace.json``). The
cost functions are held to hand figures by their family's own
``selfcheck``, which ``run.py`` calls beside this one."""

import json
import os

from . import costs, trace as tr

HERE = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def near(got, want, rel, what):
    if abs(got - want) > rel * abs(want):
        raise AssertionError(f"selfcheck: {what}: got {got}, hand "
                             f"figure {want}")


def check_trace():
    with open(os.path.join(HERE, "data", "sample_trace.json")) as f:
        doc = json.load(f)
    t, want = doc["trace"], doc["expected"]
    win = tuple(want["window_ns"])
    plane = tr.device_planes(t)[0]
    near(tr.busy_ns(plane, win), want["busy_ns"], 0, "busy union")
    near(tr.idle_share(t, win), want["idle_share"], 1e-9, "idle share")
    steps = tr.module_events(t, want["step_pattern"], win)
    near(len(steps), want["steps"], 0, "step count")
    starts = [s for _, s, _ in steps]
    periods = sorted(b - a for a, b in zip(starts, starts[1:]))
    near(periods[len(periods) // 2], want["median_period_ns"], 0,
         "step period")
    near(tr.op_seconds(t, want["kernel_pattern"], win) * 1e9,
         want["kernel_ns"], 1e-9, "kernel sum")
    if tr.op_seconds(t, "no-such-op", win) is not None:
        raise AssertionError("selfcheck: an absent kernel read as a number")
    gaps = dict(tr.idle_gaps(t, win, lambda s, e: "gap"))
    near(gaps["gap"] * 1e9, win[1] - win[0] - want["busy_ns"], 1e-9,
         "idle gaps")
    top = tr.top_ops(t, win)
    if top[0][0] != want["top_op"]:
        raise AssertionError(f"selfcheck: top op {top[0][0]}")
    if tr.mark_ns(t) != want["mark_ns"]:
        raise AssertionError("selfcheck: marker")


def check_recorded_trace():
    """Two real executions of the serving step program, as the chip's
    profiler wrote them."""
    with open(os.path.join(HERE, "data", "recorded_trace.json")) as f:
        doc = json.load(f)
    t, want = doc["trace"], doc["expected"]
    win = tuple(want["window_ns"])
    near(tr.busy_ns(tr.device_planes(t)[0], win), want["busy_ns"], 0,
         "recorded busy union")
    near(tr.idle_share(t, win), want["idle_share"], 1e-9,
         "recorded idle share")
    steps = tr.module_events(t, want["step_pattern"], win)
    near(len(steps), want["steps"], 0, "recorded step count")
    near(steps[1][1] - steps[0][1], want["period_ns"], 0,
         "recorded step period")
    near(tr.op_seconds(t, want["kernel_pattern"], win) * 1e9,
         want["kernel_ns"], 1e-9, "recorded kernel sum")


def run():
    near(costs.causal_pairs(4), 10, 0, "causal pairs")
    check_trace()
    check_recorded_trace()
