"""Checks the benchmark makes of itself in every run (a few
milliseconds): the cost functions against figures worked by hand, the
trace reduction against a small recorded trace whose answers were
worked by hand (``perfbench/data/sample_trace.json``)."""

import json
import os

from . import costs, trace as tr

HERE = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def _near(got, want, rel, what):
    if abs(got - want) > rel * abs(want):
        raise AssertionError(f"selfcheck: {what}: got {got}, hand "
                             f"figure {want}")


def _config(name):
    with open(os.path.join(HERE, "configs", name + ".json")) as f:
        return json.load(f)


def check_costs():
    m = _config("mistral-7b-v0.3-l16")
    i4 = _config("internlm2-1.8b-l4")
    i24 = dict(i4, num_hidden_layers=24)
    _near(costs.layer_params(m), 218.1e6, 1e-3, "Mistral layer params")
    _near(costs.total_params(m), 3.758e9, 1e-3, "Mistral L16 params")
    _near(costs.kv_bytes_per_token(m), 64 * 1024, 0, "K/V bytes a token")
    _near(costs.layer_params(i4), 62.9e6, 1e-3, "InternLM2 layer params")
    _near(costs.total_params(i4), 630.8e6, 1e-3, "InternLM2 L4 params")
    _near(costs.total_params(i24), 1.889e9, 1e-3, "InternLM2 params")
    _near(costs.train_flops_per_token(i4, 4096), 2.85e9, 2e-3,
          "InternLM2 L4 FLOPs a trained token")
    _near(costs.train_flops_per_token(i24, 4096), 11.4e9, 2e-3,
          "InternLM2 FLOPs a trained token")
    # one decode token at context 1000 on Mistral L16: 2 x 3.624e9
    # matmul FLOPs + 4 x 4096 x 16 x 1000 attention FLOPs
    w = costs.serve_work(m, 1, [], [1000])
    _near(w["flops"], 2 * 3.6239e9 + 262.1e6, 1e-3, "decode token FLOPs")
    _near(w["bytes"], 2 * 3.6239e9 + 1001 * 65536, 1e-3,
          "decode step bytes")
    _near(costs.causal_pairs(4), 10, 0, "causal pairs")


def check_trace():
    with open(os.path.join(HERE, "data", "sample_trace.json")) as f:
        doc = json.load(f)
    t, want = doc["trace"], doc["expected"]
    win = tuple(want["window_ns"])
    plane = tr.device_planes(t)[0]
    _near(tr.busy_ns(plane, win), want["busy_ns"], 0, "busy union")
    _near(tr.idle_share(t, win), want["idle_share"], 1e-9, "idle share")
    steps = tr.module_events(t, want["step_pattern"], win)
    _near(len(steps), want["steps"], 0, "step count")
    starts = [s for _, s, _ in steps]
    periods = sorted(b - a for a, b in zip(starts, starts[1:]))
    _near(periods[len(periods) // 2], want["median_period_ns"], 0,
          "step period")
    _near(tr.op_seconds(t, want["kernel_pattern"], win) * 1e9,
          want["kernel_ns"], 1e-9, "kernel sum")
    if tr.op_seconds(t, "no-such-op", win) is not None:
        raise AssertionError("selfcheck: an absent kernel read as a number")
    gaps = dict(tr.idle_gaps(t, win, lambda s, e: "gap"))
    _near(gaps["gap"] * 1e9, win[1] - win[0] - want["busy_ns"], 1e-9,
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
    _near(tr.busy_ns(tr.device_planes(t)[0], win), want["busy_ns"], 0,
          "recorded busy union")
    _near(tr.idle_share(t, win), want["idle_share"], 1e-9,
          "recorded idle share")
    steps = tr.module_events(t, want["step_pattern"], win)
    _near(len(steps), want["steps"], 0, "recorded step count")
    _near(steps[1][1] - steps[0][1], want["period_ns"], 0,
          "recorded step period")
    _near(tr.op_seconds(t, want["kernel_pattern"], win) * 1e9,
          want["kernel_ns"], 1e-9, "recorded kernel sum")


def run():
    check_costs()
    check_trace()
    check_recorded_trace()
