"""The costs of the linear-attention / latent-attention expert family
against figures worked by hand from Kimi-Linear-48B-A3B-Instruct's
``config.json`` as ``configs/kimi-linear-48b-a3b-ep16.json`` holds it (16
of 256 experts a layer: ``golden_kda_mla_moe.json``), and its two readers
(``kda_ms.tok``, ``kda_roofline.tok``) on hand-made device ops. The
arithmetic, so that a reader can follow each figure:

- a KDA mixer: q, k, v 3 x 2304 x 4096 + o 4096 x 2304 = 37,748,736; the
  gate's and the output gate's pairs 2 x (2304 x 128 + 128 x 4096) =
  1,638,400; beta 2304 x 32 = 73,728; three convolutions 3 x 4096 x 4 =
  49,152; A_log 32, dt_bias 4,096, the output norm 128: 39,514,272
- a latent mixer: q 2304 x 6144 + kv_a 2304 x 576 + its norm 512 + kv_b
  512 x 8192 + o 4096 x 2304 = 29,114,880; a layer's two norms 4,608
- the dense FFN 3 x 2304 x 9216 = 63,700,992; an expert 3 x 2304 x 1024
  = 7,077,888; an expert FFN here: 16 held + the shared one + the router
  2304 x 256 + its bias 256 = 120,914,176
- the model: 2 x 163,840 x 2304 + 20 x 39,514,272 + 7 x 29,114,880 + 27 x
  4,608 + 63,700,992 + 26 x 120,914,176 + 2,304 = 4,956,660,608
- streamed a step whatever it carries: all but the embedding
  (377,487,360), the held experts (26 x 113,246,208) and the final norm
  (2,304) = 1,634,769,536
- FLOPs a token outside the kernels and the head: a KDA layer 2 x
  (4 x 2304 x 4096 + 1,638,400 + 73,728); a latent layer (absorbed) 2 x
  (2304 x 6144 + 2304 x 576 + 2 x 32 x 128 x 512 + 4096 x 2304); the
  dense FFN; an expert layer 2 x (2304 x 256 + (8 x 16 / 256 + 1) x
  7,077,888): 2,696,183,808
- the window: prompts 384 and 100, decoded contexts 1,000, 500, 501 in 3
  steps: 487 tokens; pairs 384 x 385 / 2 + 100 x 101 / 2 + 2,001 =
  80,971; a pair 2 x (512 + 64 + 512) x 32 heads x 7 latent layers
- KDA: a decoded token 7 x 32 x 128 x 128 + the convolutions' 2 x 4 x
  12,288; a prompt's token 32 x (6 x 128 x 128 + 7 x 64 x 128) + the
  same; 5 rows move 2 x 2,170,880 bytes a layer; a token 3 x 4096 x 2 +
  5 x 4096 x 4 + 32 x 4 bytes a layer"""

import json
import os
import types

import pytest

import run as bench
from harness import family, peaks, spans, trace as tr

HERE = os.path.dirname(os.path.abspath(__file__))
NAME = "kimi-linear-48b-a3b-ep16"
MS = 1_000_000
STEP = tr.STEP_MODULE.lstrip("^")
with open(os.path.join(HERE, "golden_kda_mla_moe.json")) as f:
    GOLDEN = json.load(f)


@pytest.fixture(scope="module")
def fam_cfg():
    cfg = bench.load_json("perfbench", "configs", NAME + ".json")
    return family.load(cfg, NAME), cfg


def test_parameters_by_hand(fam_cfg):
    fam, cfg = fam_cfg
    g = GOLDEN
    norms = g["norm_params_a_layer"]
    assert fam.layer_params(cfg, 0) \
        == g["kda_mixer_params"] + norms + g["dense_ffn_params"]
    assert fam.layer_params(cfg, 1) == fam.layer_params(cfg, 25) \
        == g["kda_mixer_params"] + norms + g["expert_ffn_params"]
    assert fam.layer_params(cfg, 3) == fam.layer_params(cfg, 26) \
        == g["latent_mixer_params"] + norms + g["expert_ffn_params"]
    assert g["expert_ffn_params"] == 17 * 7077888 + 2304 * 256 + 256
    assert fam.expert_params(cfg) == g["held_experts_a_layer"]
    assert fam.total_params(cfg) == g["total_params"]
    assert fam.streamed_params(cfg) == g["streamed_params"]
    assert fam.latent_bytes_per_token(cfg) == g["latent_bytes_a_token"]
    assert fam.state_bytes(cfg) == g["state_bytes_a_layer"]
    assert fam.kinds(cfg) == (20, 7, 26) and fam.layer_count(cfg) == 27


def test_flops_a_token_by_hand(fam_cfg):
    fam, cfg = fam_cfg
    assert fam.token_flops(cfg) == GOLDEN["token_flops"]
    assert fam.attn_flops(cfg, 1) == 2 * 1088 * 32 * 7


def test_a_windows_work_by_hand(fam_cfg):
    fam, cfg = fam_cfg
    g = GOLDEN["work"]
    w = fam.serve_work(cfg, g["steps"], g["prefill"], g["decode"])
    assert w["tokens"] == g["tokens"] == 384 + 100 + 3
    assert g["pairs"] == 384 * 385 // 2 + 100 * 101 // 2 + 2001
    assert w["attn_flops"] == g["attn_flops"] == 2 * 1088 * 32 * 7 * g["pairs"]
    # every context's rows read once, every token's row written once
    assert w["attn_bytes"] == g["attn_bytes"] \
        == 8064 * (484 + 2001) + 8064 * 487
    assert g["head_flops"] == 2 * 2304 * 163840 * (2 + 3)
    assert w["kda_flops"] == g["kda_flops"]
    assert w["kda_bytes"] == g["kda_bytes"] \
        == 20 * (2 * 5 * 2170880 + 487 * 106624)
    assert (w["kda_flops"], w["kda_bytes"]) \
        == fam.kda_work(cfg, g["prefill"], g["decode"])
    assert w["flops"] == pytest.approx(
        GOLDEN["token_flops"] * 487 + g["head_flops"] + g["attn_flops"]
        + g["kda_flops"], rel=1e-12)
    # the held experts: a token's 8 rows fall on this chip's 16 of 256
    # one time in 16; a step of 487 / 3 tokens touches all 16 (16 x (1 -
    # (31/32)^162) = 15.908)
    rows = 487 * 8 * 16 / 256
    assert w["moe_flops"] == pytest.approx(2 * rows * 7077888 * 26)
    touched = 16 * (1 - (31 / 32) ** (487 / 3))
    assert touched == pytest.approx(15.908, abs=1e-3)
    expert_bytes = 3 * touched * 7077888 * 2 * 26
    rows_bytes = rows * 3 * (2304 + 1024) * 2 * 26
    assert w["moe_bytes"] == pytest.approx(expert_bytes + rows_bytes,
                                           rel=1e-12)
    assert w["bytes"] == pytest.approx(
        3 * GOLDEN["streamed_params"] * 2 + expert_bytes + g["attn_bytes"]
        + g["kda_bytes"], rel=1e-12)


def test_a_decode_step_touches_most_of_the_share(fam_cfg):
    fam, cfg = fam_cfg
    # 64 tokens: 16 x (1 - (31/32)^64) = 13.9 of the 16 held
    assert fam.experts_touched(cfg, 64) == pytest.approx(13.90, abs=0.01)
    assert fam.experts_touched(cfg, 1) == pytest.approx(0.5, rel=1e-12)
    assert fam.experts_touched(cfg, 10 ** 6) <= 16.0


def test_selfcheck_and_no_training_costs(fam_cfg):
    fam, cfg = fam_cfg
    fam.selfcheck()
    with pytest.raises(NotImplementedError):
        fam.train_flops_per_token(cfg, 4096)


# ---------------------------------------------------------------------------
# the two readers, on hand-made device ops
# ---------------------------------------------------------------------------
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

    def make(ops, prefill=(), decode=(), steps=2, family_=fam):
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
            trace_dir="unused", cfg=cfg, family=family_,
            peaks=peaks.peak("TPU v5 lite"), chips=1, notes=notes,
            note=lambda **kw: notes.append(kw))
        monkeypatch.setattr(spans, "load_device_ops", lambda d: ops)
        monkeypatch.setattr(spans, "report", lambda run: None)
        return run
    return make


KDA = "jit(pure_step)/paddle_tpu.kda/"
OPS = ops_of(
    ("%fusion.1 = fusion()", 2.0, KDA + "dot_general"),
    ("%paddle_tpu.kda_step.3 = custom-call()", 3.0,
     KDA + "paddle_tpu.kda_scan/jit(call)/pallas_call"),
    ("%while.4 = while()", 1.0, KDA + "paddle_tpu.kda_scan/while"),
    ("%fusion.5 = fusion()", 1.0, KDA + "paddle_tpu.kda_scan/scatter"),
    ("%fusion.7 = fusion()", 6.0, "jit(pure_step)/paddle_tpu.moe/dot"),
    ("%fusion.8 = fusion()", 3.0, "jit(pure_step)/paddle_tpu.kdax/dot"))


def read(name, run):
    return bench.load_reader(name).read(run)


def test_kda_ms_is_the_scope_and_what_lies_inside_it(fake_run):
    # 2 + 3 + 1 + 1 ms under paddle_tpu.kda over two steps
    assert read("kda_ms.tok", fake_run(OPS)) == pytest.approx(3.5)


def test_kda_roofline_by_scope(fake_run, fam_cfg):
    fam, cfg = fam_cfg
    run = fake_run(OPS, prefill=[200], decode=[300, 900])
    flops, nbytes = fam.kda_work(cfg, [200], [300, 900])
    least = max(flops / 197e12, nbytes / 819e9)
    assert read("kda_roofline.tok", run) \
        == pytest.approx(100 * least / 5e-3)
    note = next(n for n in run.notes
                if n.get("metric") == "kda_roofline.tok")
    assert note["scope_seconds"] == pytest.approx(5e-3)
    assert note["bound"] == "memory"


def test_a_program_or_a_family_without_kda_reads_nothing(fake_run):
    bare = ops_of(("%fusion.1 = fusion()", 2.0, "jit(pure_step)/dot"))
    run = fake_run(bare, prefill=[10], decode=[20])
    assert read("kda_ms.tok", run) is None
    assert read("kda_roofline.tok", run) is None
    other = types.SimpleNamespace()             # a family with no kda_work
    run = fake_run(OPS, prefill=[10], decode=[20], family_=other)
    assert read("kda_roofline.tok", run) is None


def test_benchmark_lists_the_cell_and_its_readers():
    spec = bench.load_json("BENCHMARK.json")
    cell = NAME + ".assist-closed"
    entry = next(c for c in spec["workloads"] if c["name"] == cell)
    assert entry["chips"] == 1 and entry["traffic"] == "assist-closed"
    per = {m["name"]: m for m in spec["per_layer"]}
    for name in ("kda_ms.tok", "kda_roofline.tok"):
        assert per[name]["workloads"] == [cell]
        assert per[name]["moves"] == "serve_tok_s"
        assert os.path.isfile(bench.reader_path(name))
    for name in ("step_period_ms.tok", "step_dev_ms.tok", "step_mfu.tok",
                 "idle_share.tok", "host_sched_ms.tok", "host_build_ms.tok",
                 "host_apply_ms.tok", "host_loop_ms.tok",
                 "idle_named_share.tok", "batch_occupancy.tok",
                 "prefill_share.tok", "mla_roofline.tok",
                 "moe_gemm_roofline.tok", "moe_route_ms.tok"):
        assert per[name]["workloads"][-1] == cell
    assert next(m for m in spec["end_to_end"]
                if m["name"] == "serve_tok_s")["workloads"][-1] == cell
