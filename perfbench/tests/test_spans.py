"""The span helper and each reader built on it, against hand figures and
the recorded sample (``perfbench/data/sample_spans.json``: three
dispatches of chat-open on the chip, my chip run, PR 26)."""

import json
import os
import types

import pytest

import run as bench
from harness import peaks, spans, trace as tr

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
CHAT, TRAIN = "mistral-7b-v0.3-l16.chat-open", "internlm2-1.8b-l4.pretrain-4k"


def to_pc(ts_us):
    return ts_us / 1e6


@pytest.fixture(scope="module")
def sample():
    with open(os.path.join(ROOT, "perfbench", "data",
                           "sample_spans.json")) as f:
        return json.load(f)


def device_trace(ops):
    return {"planes": [{"name": "/device:TPU:0", "lines": [
        {"name": tr.OPS_LINE, "events": [o[:3] for o in ops]}]}]}


@pytest.fixture
def fake_run(sample, monkeypatch):
    """A run whose trace, host spans and ring are the sample's."""
    def make(cell, **facts):
        with open(os.path.join(ROOT, "BENCHMARK.json")) as f:
            spec = json.load(f)
        c = {c["name"]: c for c in spec["workloads"]}[cell]
        with open(os.path.join(ROOT, {x["name"]: x for x in spec[
                "configs"]}[c["config"]]["file"])) as f:
            cfg = json.load(f)
        notes = []
        run = types.SimpleNamespace(
            facts=dict(trace=device_trace(sample["ops"]),
                       window_ns=sample["window_ns"], **facts),
            trace_dir="unused", cfg=cfg, peaks=peaks.peak("TPU v5 lite"),
            chips=1, t0=0.0, setup_s=99.5, seconds=10.0, notes=notes,
            note=lambda **kw: notes.append(kw))
        return run
    monkeypatch.setattr(spans, "ring_events",
                        lambda: (sample["ring"], to_pc))
    monkeypatch.setattr(spans, "load_host_spans",
                        lambda d, names: [h for h in sample["host"]
                                          if h[0] in names])
    monkeypatch.setattr(spans, "load_device_ops",
                        lambda d: sample["ops"])
    return make


# ---------------------------------------------------------------------------
# hand figures
# ---------------------------------------------------------------------------
def test_idle_covered_and_uncovered():
    # device busy 0-10 and 40-90 of a window 0-100: idle 10-40 and 90-100
    t = device_trace([["%a", 0, 10, ""], ["%b", 40, 50, ""]])
    idle = spans.idle_intervals(t, [0, 100])
    assert idle == [[10, 40], [90, 100]]
    host = [["outer", 5, 30, {}],       # 5-35
            ["inner", 20, 10, {}],      # 20-30, inside outer
            ["late", 95, 20, {}]]       # 95-115, runs past the window
    by = spans.idle_by_span(idle, host)
    # 10-20 outer, 20-30 inner, 30-35 outer, 35-40 nobody; 90-95 nobody,
    # 95-100 late
    assert by == {"outer": 15, "inner": 10, None: 10, "late": 5}
    assert spans.named_share(idle, host) == pytest.approx(75.0)
    assert spans.named_share(idle, []) == pytest.approx(0.0)
    assert spans.named_share([], host) is None


def test_innermost_is_the_span_that_began_last():
    segs = spans.innermost([["a", 0, 100, {}], ["b", 10, 20, {}],
                            ["c", 15, 5, {}]])
    assert segs == [[0, 10, "a"], [10, 15, "b"], [15, 20, "c"],
                    [20, 30, "b"], [30, 100, "a"]]


def test_self_time_and_ratios():
    host = [["replica.tick", 0, 100, {}],
            ["serving.dispatch", 10, 70,
             {"tokens": 96, "t_cap": 128, "prefill_tokens": 90}],
            ["replica.tick", 200, 50, {}],
            ["serving.dispatch", 205, 40,
             {"tokens": 16, "t_cap": 32, "prefill_tokens": 0}]]
    # self times 30 and 10 ns: median 20 ns
    assert spans.tick_self_ms(host) == pytest.approx(20 / 1e6)
    assert spans.stat_ratio(host, "tokens", "t_cap") \
        == pytest.approx(100 * 112 / 160)
    assert spans.stat_ratio(host, "prefill_tokens", "tokens") \
        == pytest.approx(100 * 90 / 112)
    # a dispatch without the stat: no guess
    host[1][3].pop("t_cap")
    assert spans.stat_ratio(host, "tokens", "t_cap") is None
    assert spans.median_ms(host, "serving.build") is None


def ring_of(*events):
    return [dict(name=n, ts=ts, dur=dur, args=args)
            for n, ts, dur, args in events]


def test_a_wrapped_ring_reads_none():
    ring = ring_of(
        ("serving.dispatch", 5e6, 2e5, {"step": 1, "kind": "mixed"}),
        ("serving.first_token", 6e6, 1.0,
         dict(t_submit=6.0, t_admit=6.1, t_first_chunk=6.3,
              t_first_token=7.0)))
    # the ring still holds something older than 4.0 s: not wrapped
    ring.insert(0, dict(name="old", ts=3e6, dur=1.0))
    assert len(spans.dispatches(ring, to_pc, 4.0, 60.0)) == 1
    reqs = spans.requests(ring, to_pc, 4.0)
    assert spans.stamp_gap_ms(reqs, "t_first_chunk", "t_admit") \
        == pytest.approx(200.0)
    # its oldest event is younger than the window's open: wrapped
    assert spans.dispatches(ring[1:], to_pc, 4.0, 60.0) is None
    assert spans.requests(ring[1:], to_pc, 4.0) is None
    assert spans.requests([], to_pc, 4.0) is None
    # a request that never got a first token is left out, not guessed
    ring.append(dict(name="serving.first_token", ts=8e6, dur=1.0, args=dict(
        t_submit=8.0, t_admit=8.1, t_first_chunk=None,
        t_first_token=None)))
    assert len(spans.requests(ring, to_pc, 4.0)) == 1
    assert spans.stamp_gap_ms([], "t_admit", "t_submit") is None


def test_late_dispatches_names_the_phase():
    disp = [dict(step=i, start=i * 0.1, ms=100.0, kind="mixed", tokens=128,
                 build_ms=9.0, wait_ms=90.0) for i in range(10)]
    disp[6].update(ms=160.0, build_ms=69.0)
    # the device taking longer is not a late dispatch: host time is
    disp[3].update(ms=130.0, wait_ms=120.0)
    out = spans.late_dispatches(disp)
    assert out["late_count"] == 1 and out["late"][0]["step"] == 6
    assert out["late"][0]["over_ms"] == pytest.approx(60.0)
    assert out["late"][0]["build_ms"] == 69.0
    assert out["slowest"][0]["step"] == 6
    assert out["largest_gap"]["step"] == 6
    assert out["largest_gap"]["gap_ms"] == pytest.approx(160.0)


def test_kernels_and_scopes_by_name():
    ops = [["%jvp_paddle_tpu.flash_fwd_.7 = (bf16[2,16,4096,128]) "
            "custom-call(...)", 0, 10,
            "jit(pure_step)/jvp(paddle_tpu.flash_fwd)/pallas_call:"],
           ["%paddle_tpu.ragged_attn_fused_rope.21 = (bf16[32,8,4,128]) "
            "custom-call(...)", 10, 20, "jit(pure_step)/pallas_call:"],
           ["%paddle_tpu.ragged_attn_fused_rope.22 = ...", 30, 20, ""],
           ["%multiply_subtract_fusion.1 = fusion(...)", 50, 7,
            "jit(pure_step)/optimizer/sub:"],
           ["%fusion.9 = fusion(...)", 57, 5,
            "jit(pure_step)/backward/transpose(jvp(paddle_tpu.fused_ce))"
            "/dot_general:"],
           ["%fusion.3 = fusion(...)", 62, 100,
            "jit(pure_step)/backward/mul:"]]
    k = spans.kernels(ops, [0, 200])
    assert k == {"flash_fwd": {"seconds": 10 / 1e9, "events": 1},
                 "ragged_attn_fused_rope": {"seconds": 40 / 1e9,
                                            "events": 2}}
    # cut to the window: the second ragged event is half outside
    assert spans.kernels(ops, [0, 40])["ragged_attn_fused_rope"] \
        == {"seconds": 30 / 1e9, "events": 2}
    assert spans.scope_seconds(ops, r"(?:^|/)optimizer/", [0, 200]) \
        == pytest.approx(7 / 1e9)
    assert spans.scope_seconds(ops, r"paddle_tpu\.fused_ce\b", [0, 200]) \
        == pytest.approx(5 / 1e9)
    assert spans.scope_seconds(ops, r"no_such_scope", [0, 200]) is None


def _varint(n):
    out = b""
    while True:
        out += bytes([(n & 0x7F) | (0x80 if n > 0x7F else 0)])
        n >>= 7
        if not n:
            return out


def _ld(field, payload):
    return _varint(field << 3 | 2) + _varint(len(payload)) + payload


def _vi(field, n):
    return _varint(field << 3) + _varint(n)


def test_op_scopes_reads_the_metadata_stat_off_the_wire():
    """A hand-encoded XSpace: one host plane, one TPU plane with two
    event metadata (one ``tf_op`` as a string, one as a reference to a
    stat metadata's name) and a third without the stat."""
    stat_meta = b"".join(
        _ld(5, _vi(1, i) + _ld(2, _vi(1, i) + _ld(2, n.encode())))
        for i, n in ((1, "tf_op"), (2, "flops"),
                     (3, "jit(pure_step)/backward/mul:")))

    def meta(i, text, *stats):
        return _ld(4, _vi(1, i) + _ld(2, _vi(1, i) + _ld(2, text.encode())
                                      + b"".join(_ld(5, s) for s in stats)))
    tpu = _ld(2, b"/device:TPU:0") + stat_meta \
        + meta(7, "%a = fusion()", _vi(1, 2) + _vi(3, 99),
               _vi(1, 1) + _ld(5, b"jit(pure_step)/optimizer/sub:")) \
        + meta(8, "%b = fusion()", _vi(1, 1) + _vi(7, 3)) \
        + meta(9, "%c = fusion()", _vi(1, 2) + _vi(3, 5)) \
        + _ld(3, _ld(2, b"XLA Ops"))
    host = _ld(2, b"/host:CPU") + meta(1, "python3")
    xspace = _ld(1, host) + _ld(1, tpu)
    assert spans.op_scopes(xspace) == {
        "%a = fusion()": "jit(pure_step)/optimizer/sub:",
        "%b = fusion()": "jit(pure_step)/backward/mul:"}
    assert spans.op_scopes(_ld(1, host)) == {}


def test_ce_cost_at_the_internlm2_widths():
    with open(os.path.join(ROOT, "perfbench", "configs",
                           "internlm2-1.8b-l4.json")) as f:
        cfg = json.load(f)
    flops, nbytes = bench.load_reader("ce_roofline.train").ce_work(
        cfg, 2 * 4096)
    # 8192 tokens x 2048 x 92544 multiply-adds, three products
    assert flops == 6 * 8192 * 2048 * 92544 == 9315784065024
    # (8192 x 2048 + 2048 x 92544) fp32 elements, read twice, written once
    assert nbytes == 3 * (16777216 + 189530112) * 4 == 2475687936
    # compute bound on the v5e: 47.3 ms a step against 3.0 ms of traffic
    pk = peaks.peak("TPU v5 lite")
    assert flops / pk["flops_per_s"] == pytest.approx(0.047288, rel=1e-3)
    assert nbytes / pk["bytes_per_s"] == pytest.approx(0.003023, rel=1e-3)


# ---------------------------------------------------------------------------
# the recorded sample through every reader
# ---------------------------------------------------------------------------
def test_sample_is_what_the_program_records(sample):
    host = sample["host"]
    disp = spans.named(host, spans.DISPATCH)
    assert len(disp) == 3 and len(spans.named(host, spans.TICK)) == 3
    for d in disp:
        kids = [h for h in host if h[0] in spans.PHASES
                and h[3]["step"] == d[3]["step"]]
        assert [k[0] for k in kids] == list(spans.PHASES)
        for k, nxt in zip(kids, kids[1:] + [None]):
            assert d[1] <= k[1] and k[1] + k[2] <= d[1] + d[2]
            assert nxt is None or k[1] + k[2] <= nxt[1]
    assert set(spans.kernels(sample["ops"], sample["window_ns"])) \
        == {"ragged_attn_fused_rope"}


# the three dispatches' medians are their middle values (schedule 139200,
# 172270, 187460 ns; build 8369080, 9624569, 9997499; apply 156540,
# 194100, 622680; tick less dispatch 100180, 118710, 357480); every
# dispatch carried 128 tokens of 128, 114 of them prefill; the four
# requests waited 1.281, 23.814, 120.608, 217.178 ms for admission, 0.177,
# 0.653, 218.839, 3791.699 ms for their first chunk and prefilled for
# 425.655, 652.309, 735.235, 807.136 ms
@pytest.mark.parametrize("metric,value", [
    ("host_sched_ms.gap", 0.17227),
    ("host_build_ms.gap", 9.624569),
    ("host_apply_ms.gap", 0.1941),
    ("host_loop_ms.gap", 0.11871),
    ("batch_occupancy.gap", 100.0),
    ("prefill_share.gap", 100 * 342 / 384),
    ("idle_named_share.gap", 99.265794),
    ("ttft_queue_ms.ttft", (23.814 + 120.608) / 2),
    ("ttft_prefill_wait_ms.ttft", (0.653 + 218.839) / 2),
    ("ttft_prefill_ms.ttft", (652.309 + 735.235) / 2),
])
def test_reader_on_the_sample(fake_run, metric, value):
    run = fake_run(CHAT)
    got = bench.load_reader(metric).read(run)
    assert got == pytest.approx(value, rel=1e-4)


def test_span_readers_print_their_tables_once(fake_run):
    run = fake_run(CHAT)
    for m in ("host_sched_ms.gap", "host_build_ms.gap",
              "idle_named_share.gap", "ttft_queue_ms.ttft",
              "ttft_prefill_ms.ttft"):
        bench.load_reader(m).read(run)
    tables = [n["table"] for n in run.notes if "table" in n]
    assert sorted(tables) == ["dispatches", "idle_seconds_by_span",
                              "kernel_seconds_by_name", "ttft_anatomy"]
    idle = next(n for n in run.notes
                if n.get("table") == "idle_seconds_by_span")
    assert "serving.wait" in idle and "None" in idle
    disp = next(n for n in run.notes if n.get("table") == "dispatches")
    assert disp["count"] == 3
    assert {"schedule_ms", "build_ms", "wait_ms", "apply_ms"} \
        <= set(disp["slowest"][0])


@pytest.mark.parametrize("metric", [
    m["name"] for m in json.load(open(os.path.join(
        ROOT, "BENCHMARK.json")))["per_layer"]
    if m["source"] == "program_span"])
def test_span_readers_read_nothing_from_a_program_without_spans(
        fake_run, monkeypatch, metric):
    """The parent of the PR that added the spans: no ring map, no host
    spans. The reader returns None and does not raise."""
    run = fake_run(CHAT)
    monkeypatch.setattr(spans, "ring_events", lambda: (None, None))
    assert bench.load_reader(metric).read(run) is None
    # a ring (an older program's) but no such span on the host planes
    monkeypatch.setattr(spans, "ring_events", lambda: (
        [dict(name="rpc.call", ts=1.0, dur=1.0)], to_pc))
    run = fake_run(CHAT)
    assert bench.load_reader(metric).read(run) is None


def test_a_wrapped_ring_reads_none_through_the_reader(fake_run, sample,
                                                      monkeypatch):
    run = fake_run(CHAT)
    run.setup_s = 50.0          # the sample's ring begins at 100 s
    assert bench.load_reader("ttft_queue_ms.ttft").read(run) is None


def test_train_readers_on_scoped_ops(fake_run, monkeypatch):
    step_ns = 300_000_000
    ops = [["%jvp_paddle_tpu.fused_ce_.1 = custom-call()", 0, 64_000_000,
            "jit(pure_step)/jvp(paddle_tpu.fused_ce)/pallas_call:"],
           ["%fusion.1 = fusion()", 64_000_000, 94_000_000,
            "jit(pure_step)/backward/transpose(jvp(paddle_tpu.fused_ce))"
            "/dot_general:"],
           ["%multiply_subtract_fusion = fusion()", 158_000_000, 16_000_000,
            "jit(pure_step)/optimizer/sub:"],
           ["%fusion.2 = fusion()", 174_000_000, 100_000_000,
            "jit(pure_step)/backward/mul:"]]
    monkeypatch.setattr(spans, "load_device_ops", lambda d: ops)
    run = fake_run(TRAIN, steps_traced=1, tokens_per_step=8192)
    run.facts["window_ns"] = [0, step_ns]
    # 47.288 ms of least time against 64 + 94 ms under the name
    assert bench.load_reader("ce_roofline.train").read(run) \
        == pytest.approx(100 * 0.047288 / 0.158, rel=1e-3)
    assert bench.load_reader("opt_pass_ms.train").read(run) \
        == pytest.approx(16.0)
    # a program without the names (the parent): nothing, and no raise
    monkeypatch.setattr(spans, "load_device_ops", lambda d: [
        [o[0].replace("paddle_tpu.", ""), o[1], o[2], "jit(pure_step)/x:"]
        for o in ops])
    run = fake_run(TRAIN, steps_traced=1, tokens_per_step=8192)
    assert bench.load_reader("ce_roofline.train").read(run) is None
    assert bench.load_reader("opt_pass_ms.train").read(run) is None
