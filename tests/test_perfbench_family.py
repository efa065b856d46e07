"""The benchmark's cells rehearse end to end on the CPU, and a new model
family arrives as new files: every cell of ``BENCHMARK.json`` through
``perfbench/run.py --rehearse 1`` (the configuration's and the mix's
rehearsal sizes: whole control flow, reference comparison included, no
device number), and the latent-attention expert family's costs against
its hand figures. ``perfbench/tests`` holds the benchmark's own, finer
tests; these are the ones tier-1 counts."""

import json
import os
import sys

import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
for p in (ROOT, os.path.join(ROOT, "perfbench")):
    if p not in sys.path:
        sys.path.insert(0, p)

import run as bench                       # noqa: E402
from harness import family                # noqa: E402

with open(os.path.join(ROOT, "BENCHMARK.json")) as f:
    SPEC = json.load(f)
SECONDS = {"train_feed": "2", "open_poisson": "4", "closed_clients": "4"}


@pytest.mark.parametrize("cell", [c["name"] for c in SPEC["workloads"]])
def test_cell_rehearses(cell):
    mix = bench.load_json("perfbench", "mixes", next(
        c["traffic"] for c in SPEC["workloads"] if c["name"] == cell)
        + ".json")
    code, result = bench.execute([
        "--workload", cell, "--seed", "3000000013", "--seconds",
        SECONDS[mix["generator"]], "--rehearse", "1"])
    assert code == bench.REHEARSAL_EXIT
    assert result["correct"] and result["failed"] == 0, result
    assert result["attempted"] > 0
    assert "setup_s" in result["metric_names"]


def test_benchmark_names_the_new_cells_and_their_readers():
    cells = {c["name"]: c for c in SPEC["workloads"]}
    doc, sat = "joyai-llm-flash-l5.docqa-closed", \
        "mistral-7b-v0.3-l16.chat-sat"
    assert cells[doc]["chips"] == cells[sat]["chips"] == 1
    per = {m["name"]: m for m in SPEC["per_layer"]}
    # the linear-attention expert family's cell (PR 37) shares the latent
    # kernel and the packed GEMM: it joined three of the four lists
    assist = "kimi-linear-48b-a3b-ep16.assist-closed"
    for name in ("mla_roofline.tok", "moe_gemm_roofline.tok",
                 "moe_route_ms.tok", "experts_touched.tok"):
        assert per[name]["workloads"] == [doc] + [assist] * (
            name != "experts_touched.tok")
        assert per[name]["moves"] == "serve_tok_s"
        assert os.path.isfile(bench.reader_path(name))
    assert doc not in per["attn_roofline.tok"]["workloads"]
    assert sat in per["attn_roofline.tok"]["workloads"]
    # the hybrid family's cell (PR 34) and its four readers
    reason = "phi-4-mini-flash-reasoning.reason-closed"
    assert cells[reason]["chips"] == 1
    hybrid = ("ssm_ms.tok", "ssm_scan_roofline.tok",
              "diff_attn_roofline.tok", "window_held_share.tok")
    for name in hybrid:
        assert per[name]["workloads"] == [reason]
        assert per[name]["moves"] == "serve_tok_s"
        assert os.path.isfile(bench.reader_path(name))
    assert per["window_held_share.tok"]["better"] == "lower"
    assert cells[assist]["chips"] == 1
    kda = ("kda_ms.tok", "kda_roofline.tok")
    for name in kda:
        assert per[name]["workloads"] == [assist]
        assert per[name]["moves"] == "serve_tok_s"
        assert os.path.isfile(bench.reader_path(name))
    for m in SPEC["per_layer"]:
        if m["name"].endswith(".tok") and m["name"] not in hybrid + kda + (
                "mla_roofline.tok", "moe_gemm_roofline.tok",
                "moe_route_ms.tok", "experts_touched.tok",
                "attn_roofline.tok"):
            assert doc in m["workloads"] and sat in m["workloads"], m
            # every kernel-agnostic .tok metric is read in the new cells
            assert reason in m["workloads"], m
            assert m["workloads"][-1] == assist, m
    serve = next(m for m in SPEC["end_to_end"]
                 if m["name"] == "serve_tok_s")["workloads"]
    assert reason in serve and serve[-1] == assist


def test_configuration_keeps_every_published_width():
    cfg = bench.load_json("perfbench", "configs", "joyai-llm-flash-l5.json")
    published = {
        "hidden_size": 2048, "intermediate_size": 7168,
        "moe_intermediate_size": 768, "kv_lora_rank": 512,
        "q_lora_rank": 1536, "qk_nope_head_dim": 128,
        "qk_rope_head_dim": 64, "qk_head_dim": 192, "v_head_dim": 128,
        "head_dim": 64, "num_attention_heads": 32,
        "n_routed_experts": 256, "num_experts_per_tok": 8,
        "n_shared_experts": 1, "vocab_size": 129280,
        "routed_scaling_factor": 2.5, "rope_theta": 32000000,
        "first_k_dense_replace": 1, "max_position_embeddings": 131072}
    assert {k: cfg[k] for k in published} == published
    assert sorted(cfg["reduced"]) == ["num_hidden_layers",
                                      "num_nextn_predict_layers"]
    assert cfg["reduced"]["num_hidden_layers"]["published"] == 40
    assert cfg["num_hidden_layers"] == 5
    assert cfg["num_nextn_predict_layers"] == 0


def test_the_share_configuration_keeps_every_published_width():
    cfg = bench.load_json("perfbench", "configs",
                          "kimi-linear-48b-a3b-ep16.json")
    fam = family.load(cfg, "kimi-linear-48b-a3b-ep16")
    fam.selfcheck()
    assert cfg["num_hidden_layers"] == 27 and cfg["vocab_size"] == 163840
    assert sorted(cfg["reduced"]) == ["num_experts"]
    assert fam.dims(cfg)["e"] == 256 and fam.dims(cfg)["held"] == 16
    assert fam.total_params(cfg) == 4956660608
    # one decode row: 20 states of 2,170,880 bytes in and out
    assert fam.kda_work(cfg, [], [1000])[1] \
        == 20 * (2 * 2170880 + 3 * 4096 * 2 + 5 * 4096 * 4 + 128)


def test_family_costs_against_hand_figures():
    cfg = bench.load_json("perfbench", "configs", "joyai-llm-flash-l5.json")
    fam = family.load(cfg, "joyai-llm-flash-l5")
    fam.selfcheck()
    # 70,391,808 + 4 x 1,239,554,304 + 2 x 129,280 x 2,048 + 2,048
    assert fam.total_params(cfg) == 5558141952
    assert fam.latent_bytes_per_token(cfg) == 5 * 576 * 2
    w = fam.serve_work(cfg, 1, [], [1000])
    assert w["tokens"] == 1 and w["attn_flops"] == 2 * 1088 * 32 * 5 * 1000
    # nothing of the program is imported by the family's file
    with open(family.path_of("mla_moe")) as f:
        text = f.read()
    body = text.split("# the plain reference")[1]
    assert "paddle_tpu" not in body
