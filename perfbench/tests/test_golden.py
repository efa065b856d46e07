"""The move of the decoder family's code behind ``harness/family.py``
moved no number: golden values recorded from the parent's harness
(commit b5f8a5b, ``harness/weights.py``, ``costs.py``, ``reference.py``,
on the CPU) before the move, asserted of ``families/decoder.py`` after
it. Deterministic only: no timed window. ``golden_decoder.json`` holds
them; the inputs below are the ones they were recorded with."""

import hashlib
import json
import os

import numpy as np
import pytest

import run as bench
from harness import family

HERE = os.path.dirname(os.path.abspath(__file__))
with open(os.path.join(HERE, "golden_decoder.json")) as f:
    GOLDEN = json.load(f)
CONFIGS = sorted(GOLDEN)
SEEDS = (7, 3000000011)
HP = (3e-4, 0.9, 0.999, 1e-8, 0.1)


def published(name):
    cfg = bench.load_json("perfbench", "configs", name + ".json")
    return cfg, family.load(cfg, name)


def rehearsal(name):
    cfg, fam = published(name)
    bench.deep_update(cfg, cfg["rehearse"])
    return cfg, fam


def digest(tree):
    h = hashlib.sha256()
    for k in sorted(tree):
        a = np.asarray(tree[k])
        h.update(k.encode())
        h.update(str(a.dtype).encode())
        h.update(str(a.shape).encode())
        h.update(a.tobytes())
    return h.hexdigest()


@pytest.mark.parametrize("part", ["ends", "layer0", "layer_last"])
@pytest.mark.parametrize("seed", SEEDS)
@pytest.mark.parametrize("name", CONFIGS)
def test_weights_are_the_parents_bit_for_bit(name, seed, part):
    cfg, fam = rehearsal(name)
    got = {"ends": lambda: fam.ends(cfg, seed, "float32"),
           "layer0": lambda: fam.layer(cfg, seed, 0, "float32"),
           "layer_last": lambda: fam.layer(
               cfg, seed, fam.layer_count(cfg) - 1, "float32")}[part]()
    assert digest(got) == GOLDEN[name]["weights"][str(seed)][part]


@pytest.mark.parametrize("name", CONFIGS)
def test_weights_in_the_served_type(name):
    cfg, fam = rehearsal(name)
    g = GOLDEN[name]
    assert digest(fam.ends(cfg, 7, "bfloat16")) == g["weights_bf16_ends"]
    assert digest(fam.layer(cfg, 7, 0, "bfloat16")) \
        == g["weights_bf16_layer0"]


@pytest.mark.parametrize("name,params", [
    ("mistral-7b-v0.3-l16", 3758231552), ("internlm2-1.8b-l4", 630736896)])
def test_total_params_at_published_sizes(name, params):
    cfg, fam = published(name)
    assert fam.total_params(cfg) == params == GOLDEN[name]["total_params"]


@pytest.mark.parametrize("what", ["serve_work", "train_flops_per_token",
                                  "train_attn_flops", "train_attn_bytes"])
@pytest.mark.parametrize("name", CONFIGS)
def test_costs_at_published_widths(name, what):
    cfg, fam = published(name)
    got = {"serve_work": lambda: fam.serve_work(
               cfg, 7, [512, 33, 2048], [100, 1000, 513, 514]),
           "train_flops_per_token": lambda: fam.train_flops_per_token(
               cfg, 4096),
           "train_attn_flops": lambda: fam.train_attn_flops(cfg, 4096, 2),
           "train_attn_bytes": lambda: fam.train_attn_bytes(cfg, 4096, 2)
           }[what]()
    assert got == GOLDEN[name][what]


@pytest.mark.parametrize("quant", [None, "int8"])
@pytest.mark.parametrize("name", CONFIGS)
def test_served_logits(name, quant):
    cfg, fam = rehearsal(name)
    want = GOLDEN[name]["logits_" + str(quant)]
    ids = np.random.default_rng(5).integers(0, cfg["vocab_size"], (2, 24))
    rows = np.tile(np.arange(3, 23, 4), (2, 1)).astype(np.int32)
    ends = fam.ends(cfg, 7, "float32")
    lw = [fam.layer(cfg, 7, i, "float32")
          for i in range(fam.layer_count(cfg))]
    lg = fam.served_logits(cfg, ids, rows, lambda i: lw[i], ends, quant, 2)
    assert list(lg.shape) == want["shape"]
    assert lg.argmax(-1).tolist() == want["argmax"]
    np.testing.assert_allclose(lg[0, 0, :8], want["first"], rtol=1e-5,
                               atol=1e-7)
    np.testing.assert_allclose(lg[-1, -1, -8:], want["last"], rtol=1e-5,
                               atol=1e-7)
    assert np.abs(lg.astype(np.float64)).sum() \
        == pytest.approx(want["abs_sum"], rel=1e-6)


@pytest.mark.parametrize("quant", [None, "int8"])
@pytest.mark.parametrize("name", CONFIGS)
def test_train_readings(name, quant):
    cfg, fam = rehearsal(name)
    want = GOLDEN[name]["train_" + str(quant)]
    batches = [np.random.default_rng(9 + i).integers(
        0, cfg["vocab_size"], (2, 33)).astype(np.int32) for i in range(2)]
    got = fam.train_readings(cfg, 7, batches, HP, quant=quant)
    assert got["loss"] == pytest.approx(want["loss"], rel=1e-6)
    for key in ("grad", "delta"):
        assert got[key] == pytest.approx(want[key], rel=1e-4, abs=1e-9)
