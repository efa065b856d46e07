"""The hybrid family's four readers (``ssm_ms.tok``,
``ssm_scan_roofline.tok``, ``diff_attn_roofline.tok``,
``window_held_share.tok``) on hand-made device ops and dispatch spans,
and its cost functions against figures worked by hand."""

import json
import os
import types

import pytest

import run as bench
from harness import family, peaks, spans, trace as tr

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
CELL = "phi-4-mini-flash-reasoning.reason-closed"
MS = 1_000_000
STEP = tr.STEP_MODULE.lstrip("^")


@pytest.fixture(scope="module")
def fam_cfg():
    cfg = bench.load_json("perfbench", "configs",
                          "phi-4-mini-flash-reasoning.json")
    return family.load(cfg, "phi-4-mini-flash-reasoning"), cfg


def ops_of(*rows):
    """``[text, start_ns, duration_ns, scope]``, back to back."""
    out, at = [], 0
    for text, ms, scope in rows:
        out.append([text, at, int(ms * MS), scope])
        at += int(ms * MS)
    return out


@pytest.fixture
def fake_run(fam_cfg, monkeypatch):
    fam, cfg = fam_cfg

    def make(ops, host=(), prefill=(), decode=(), steps=2):
        end = max(o[1] + o[2] for o in ops)
        trace = {"planes": [
            {"name": "/device:TPU:0", "lines": [
                {"name": tr.OPS_LINE, "events": [o[:3] for o in ops]},
                {"name": tr.MODULES_LINE, "events": [
                    [STEP + "(1)", i * end // steps, end // steps]
                    for i in range(steps)]}]}]}
        notes = []
        run = types.SimpleNamespace(
            facts=dict(trace=trace, window_ns=[0, end],
                       prefill=list(prefill), decode=list(decode),
                       step_pattern=tr.STEP_MODULE),
            trace_dir="unused", cfg=cfg, family=fam,
            peaks=peaks.peak("TPU v5 lite"), chips=1, notes=notes,
            note=lambda **kw: notes.append(kw))
        monkeypatch.setattr(spans, "load_device_ops", lambda d: ops)
        monkeypatch.setattr(spans, "ring_events", lambda: (
            [{"name": h[0], "ts": 0, "dur": 0, "args": h[3]}
             for h in host], lambda ts: ts / 1e6))
        monkeypatch.setattr(spans, "load_host_spans",
                            lambda d, names: [list(h) for h in host])
        monkeypatch.setattr(spans, "report", lambda run: None)
        return run
    return make


SSM = "jit(pure_step)/paddle_tpu.ssm/"
OPS = ops_of(
    ("%fusion.1 = fusion()", 2.0, SSM + "dot_general"),
    ("%while.3 = while()", 3.0, SSM + "paddle_tpu.ssm_scan/while"),
    ("%fusion.4 = fusion()", 1.0, SSM + "paddle_tpu.ssm_scan/scatter"),
    ("%paddle_tpu.ragged_attn_fused_rope.2 = custom-call()", 4.0,
     "jit(pure_step)/paddle_tpu.diff_attn/jit(call)/pallas_call"),
    ("%fusion.9 = fusion()", 1.0, "jit(pure_step)/paddle_tpu.diff_attn/mul"),
    ("%fusion.7 = fusion()", 6.0, "jit(pure_step)/paddle_tpu.gmu/dot"),
    ("%fusion.8 = fusion()", 3.0, "jit(pure_step)/dot_general"))


def read(name, run):
    return bench.load_reader(name).read(run)


def test_ssm_ms_is_the_scope_and_what_lies_inside_it(fake_run):
    # 2 + 3 + 1 ms under paddle_tpu.ssm over two steps
    assert read("ssm_ms.tok", fake_run(OPS)) == pytest.approx(3.0)


def test_scan_roofline_by_scope(fake_run, fam_cfg):
    fam, cfg = fam_cfg
    run = fake_run(OPS, prefill=[200], decode=[300, 900])
    flops, nbytes = fam.scan_work(cfg, [200], [300, 900])
    least = max(flops / 197e12, nbytes / 819e9)
    assert read("ssm_scan_roofline.tok", run) \
        == pytest.approx(100 * least / 4e-3)
    note = next(n for n in run.notes
                if n.get("metric") == "ssm_scan_roofline.tok")
    assert note["scope_seconds"] == pytest.approx(4e-3)
    assert note["bound"] == "memory"


def test_diff_attn_roofline_by_scope(fake_run, fam_cfg):
    fam, cfg = fam_cfg
    run = fake_run(OPS, prefill=[200], decode=[300, 900])
    flops, nbytes = fam.diff_attn_work(cfg, [200], [300, 900])
    least = max(flops / 197e12, nbytes / 819e9)
    assert read("diff_attn_roofline.tok", run) \
        == pytest.approx(100 * least / 5e-3)


def test_window_held_share_from_the_dispatch_counters(fake_run):
    host = [[spans.DISPATCH, 0, 10, {"window_pages": 8 * 33 * 2,
                                     "shared_kv_pages": 70 + 40}],
            [spans.DISPATCH, 20, 10, {"window_pages": 8 * 33 * 2,
                                      "shared_kv_pages": 90 + 60}]]
    run = fake_run(OPS, host=host)
    # eight window layers: 2 x 528 of 8 x (110 + 150) pages
    assert read("window_held_share.tok", run) \
        == pytest.approx(100 * 1056 / (8 * 260))


def test_a_program_without_the_scopes_or_counters_reads_nothing(fake_run):
    bare = ops_of(("%fusion.1 = fusion()", 2.0, "jit(pure_step)/dot"))
    host = [[spans.DISPATCH, 0, 10, {"rows": 3}]]
    run = fake_run(bare, host=host, prefill=[10], decode=[20])
    for name in ("ssm_ms.tok", "ssm_scan_roofline.tok",
                 "diff_attn_roofline.tok", "window_held_share.tok"):
        assert read(name, run) is None, name


def test_costs_by_hand(fam_cfg):
    fam, cfg = fam_cfg
    fam.selfcheck()
    # a 600-token prompt: 512 x 513 / 2 + 88 x 512 window pairs a window
    # layer, 600 x 601 / 2 causal pairs a reader of the shared pool
    flops, nbytes = fam.diff_attn_work(cfg, [600], [])
    assert flops == 2 * 40 * 192 * (8 * (131328 + 45056) + 8 * 180300)
    # keys written and read once a window layer, written once and read
    # by eight in the shared pool
    assert nbytes == 5120 * (8 * 2 * 600 + 9 * 600)
    flops, nbytes = fam.scan_work(cfg, [600], [])
    assert flops == 9 * 600 * (6 * 5120 * 16 + 2 * 5120 * 5)
    assert nbytes == 9 * (2 * 358400 + 600 * 41088)
    w = fam.serve_work(cfg, 3, [600], [700, 800])
    assert w["tokens"] == 602
    assert w["bytes"] == 3 * 2 * fam.total_params(cfg) \
        + w["attn_bytes"] + w["scan_bytes"]
    with pytest.raises(NotImplementedError):
        fam.train_flops_per_token(cfg, 4096)


def test_benchmark_lists_the_cell_for_every_shared_tok_metric():
    with open(os.path.join(ROOT, "BENCHMARK.json")) as f:
        spec = json.load(f)
    per = {m["name"]: m for m in spec["per_layer"]}
    for name in ("step_period_ms.tok", "step_dev_ms.tok", "step_mfu.tok",
                 "idle_share.tok", "host_sched_ms.tok", "host_build_ms.tok",
                 "host_apply_ms.tok", "host_loop_ms.tok",
                 "idle_named_share.tok", "batch_occupancy.tok",
                 "prefill_share.tok"):
        assert per[name]["workloads"][-1] == CELL
    for name in ("attn_roofline.tok", "mla_roofline.tok",
                 "moe_gemm_roofline.tok"):
        assert CELL not in per[name]["workloads"]
