"""The costs of the latent-attention expert family against figures
worked by hand from JoyAI-LLM-Flash's ``config.json`` (``golden_mla_moe
.json``). The arithmetic, so that a reader can follow each figure:

- attention a layer: q_a 2048x1536 + q_b 1536x(32x192) + kv_a 2048x576 +
  kv_b 512x(32x256) + o (32x128)x2048 = 3,145,728 + 9,437,184 + 1,179,648
  + 4,194,304 + 8,388,608 = 26,345,472; its norms 2048 + 2048 + 1536 +
  512 = 6,144
- layer 0: + 3x2048x7168 = 44,040,192 -> 70,391,808
- an expert layer: + router 524,288 + bias 256 + shared 4,718,592 + 256 x
  4,718,592 = 1,207,959,552 -> 1,239,554,304
- the model: 70,391,808 + 4 x 1,239,554,304 + 2 x 129,280 x 2048 + 2048 =
  5,558,141,952
- streamed a step whatever it carries: all but the embedding
  (264,765,440), the routed experts (4 x 1,207,959,552) and the final
  norm (2,048) = 461,536,256
- FLOPs a token outside the attention kernel and the head (absorbed
  form): attention 2 x 26,345,472 a layer (absorbing the query and
  leaving the latent space, 2 x 32x128x512, cost what kv_b's 512 x 8192
  would); dense 2 x 44,040,192; an expert layer 2 x (524,288 + 9 x
  4,718,592) = 2 x 42,991,616: 2 x (5 x 26,345,472 + 44,040,192 + 4 x
  42,991,616) = 695,468,032
- attention a pair: 2 x (512 + 64 + 512) x 32 heads x 5 layers = 348,160
- the window: prompts 4,096 and 600, decoded contexts 5,000, 700, 701 in
  3 steps: 4,699 tokens; pairs 4096x4097/2 + 600x601/2 + 6,401 =
  8,390,656 + 180,300 + 6,401 = 8,577,357"""

import json
import os

import pytest

import run as bench
from harness import family

HERE = os.path.dirname(os.path.abspath(__file__))
with open(os.path.join(HERE, "golden_mla_moe.json")) as f:
    GOLDEN = json.load(f)


@pytest.fixture(scope="module")
def fam_cfg():
    cfg = bench.load_json("perfbench", "configs", "joyai-llm-flash-l5.json")
    return family.load(cfg, "joyai-llm-flash-l5"), cfg


def test_parameters_by_hand(fam_cfg):
    fam, cfg = fam_cfg
    g = GOLDEN
    assert fam.layer_params(cfg, 0) == g["dense_layer_params"] \
        == g["attention_params"] + g["norm_params_a_layer"] + 44040192
    assert fam.layer_params(cfg, 1) == fam.layer_params(cfg, 4) \
        == g["expert_layer_params"]
    assert fam.expert_params(cfg) == g["routed_experts_a_layer"]
    assert fam.total_params(cfg) == g["total_params"]
    assert fam.streamed_params(cfg) == g["streamed_params"]
    assert fam.latent_bytes_per_token(cfg) == g["latent_bytes_a_token"]
    assert fam.moe_layers(cfg) == 4 and fam.layer_count(cfg) == 5


def test_flops_a_token_by_hand(fam_cfg):
    fam, cfg = fam_cfg
    assert fam.token_flops(cfg) == GOLDEN["token_flops"] \
        == 2 * (5 * 26345472 + 44040192 + 4 * (524288 + 9 * 4718592))
    assert fam.attn_flops(cfg, 1) == 2 * 1088 * 32 * 5 == 348160


def test_a_windows_work_by_hand(fam_cfg):
    fam, cfg = fam_cfg
    g = GOLDEN["work"]
    w = fam.serve_work(cfg, g["steps"], g["prefill"], g["decode"])
    assert w["tokens"] == g["tokens"] == 4096 + 600 + 3
    assert g["pairs"] == 4096 * 4097 // 2 + 600 * 601 // 2 + 6401
    assert w["attn_flops"] == g["attn_flops"] == 348160 * g["pairs"]
    # every context's rows read once, every token's row written once
    assert w["attn_bytes"] == g["attn_bytes"] \
        == 5760 * (4696 + 6401) + 5760 * 4699
    assert g["head_flops"] == 2 * 2048 * 129280 * (2 + 3)
    assert w["flops"] == GOLDEN["token_flops"] * 4699 + g["head_flops"] \
        + g["attn_flops"]
    assert w["moe_flops"] == g["moe_flops"] \
        == 2 * g["moe_rows"] * 3 * 2048 * 768 * 4
    assert g["moe_rows"] == 4699 * 8
    # the experts' weights: a step of 4,699 / 3 tokens touches all 256
    # (256 x (1 - (31/32)^1566) = 256 to twelve digits)
    touched = 256 * (1 - (31 / 32) ** (4699 / 3))
    assert touched == pytest.approx(256.0, rel=1e-12)
    rows_bytes = g["moe_rows"] * 3 * (2048 + 768) * 2 * 4
    expert_bytes = 3 * touched * 4718592 * 2 * 4
    assert w["moe_bytes"] == pytest.approx(expert_bytes + rows_bytes,
                                           rel=1e-12)
    assert w["bytes"] == pytest.approx(
        3 * GOLDEN["streamed_params"] * 2 + expert_bytes + g["attn_bytes"],
        rel=1e-12)


def test_a_decode_only_step_touches_few_experts(fam_cfg):
    fam, cfg = fam_cfg
    # 48 tokens: 256 x (1 - (31/32)^48) = 200.2 experts a layer
    assert fam.experts_touched(cfg, 48) == pytest.approx(200.23, abs=0.01)
    assert fam.experts_touched(cfg, 1) == pytest.approx(8.0, rel=1e-12)
    assert fam.experts_touched(cfg, 10 ** 6) <= 256.0


def test_selfcheck_and_no_training_costs(fam_cfg):
    fam, cfg = fam_cfg
    fam.selfcheck()
    with pytest.raises(NotImplementedError):
        fam.train_flops_per_token(cfg, 4096)
