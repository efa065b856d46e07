"""The latent-attention expert decoder (``models/mla_moe.py``) against
the plain reference of its benchmark family
(``perfbench/families/mla_moe.py``: float32, attention NOT absorbed, no
cache, nothing of the program imported), on seeded weights at the
configuration's rehearsal sizes; then the same model through
``LlamaServingEngine``: latent pages, chunked prefill, prefix hits.

Tolerances: program and reference are both float32 here and differ only
in the order of their sums (absorbed against rebuilt keys, packed rows
against gathered ones), so logits agree to 2e-4 of a logit range of a
few units; served tokens are held to the reference's logits (the served
token's logit at most 1e-3 under the reference's best: a near-tie may
fall either way), never to its tokens."""

import os
import sys

import jax
import jax.numpy as jnp
import numpy as np
import pytest

import paddle_tpu as paddle
from paddle_tpu.inference.serving import (LlamaServingEngine, Request,
                                          UnsupportedServingFeature)
from paddle_tpu.models import MlaMoeForCausalLM, tiny_mla_moe_config

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
for p in (ROOT, os.path.join(ROOT, "perfbench")):
    if p not in sys.path:
        sys.path.insert(0, p)

import run as bench                       # noqa: E402
from harness import family, program       # noqa: E402

SEED = 3000000019
LOGIT_TOL = 2e-4
GAP_TOL = 1e-3


@pytest.fixture(scope="module")
def setup():
    """(cfg, family, model with the seeded weights, the weights)."""
    cfg = bench.load_json("perfbench", "configs", "joyai-llm-flash-l5.json")
    bench.deep_update(cfg, cfg["rehearse"])
    fam = family.load(cfg, "joyai-llm-flash-l5")
    model = fam.build_model(cfg, "float32")
    model.eval()
    w, n = program.assign_weights(fam, model, cfg, SEED, "float32")
    assert n == fam.total_params(cfg) == model.num_params()
    return cfg, fam, model, w


def ref_logits(setup, ids, rows, quant=None):
    cfg, fam, _, w = setup
    return fam.served_logits(cfg, ids, rows, lambda i: w["layers"][i],
                             w["ends"], quant)


def test_logits_equal_the_reference(setup):
    cfg, _, model, _ = setup
    ids = np.random.default_rng(1).integers(0, cfg["vocab_size"], (2, 40))
    with paddle.no_grad():
        got = np.asarray(model(paddle.to_tensor(ids))._data)
    rows = np.tile(np.arange(40), (2, 1))
    want = ref_logits(setup, ids, rows)
    assert np.abs(got - want).max() < LOGIT_TOL
    # the int8 control is a different model, by far more than that
    assert np.abs(ref_logits(setup, ids, rows, "int8") - want).max() \
        > 10 * LOGIT_TOL


def test_layer_zero_is_dense_and_the_rest_route(setup):
    cfg, fam, model, _ = setup
    kinds = [layer.is_moe for layer in model.model.layers]
    assert kinds == [False] + [True] * (cfg["num_hidden_layers"] - 1)
    assert "layers.0.gate" in fam.leaves(model, cfg)
    assert "layers.1.experts_gate" in fam.leaves(model, cfg)
    assert model.model.layers[1].mlp.experts_gate.shape == [
        cfg["n_routed_experts"], cfg["hidden_size"],
        cfg["moe_intermediate_size"]]


def test_absorbed_attention_equals_the_published_form(setup):
    """One sequence through the absorbed operands and the XLA latent
    attention against ``MlaAttention.forward`` (per-head keys and values
    rebuilt from the latent)."""
    from paddle_tpu.models.mla_moe import rope_tables_interleaved
    from paddle_tpu.ops.ragged_mla_attention import (
        latent_row_width, ragged_mla_attention_xla)
    _, _, model, _ = setup
    att = model.model.layers[1].self_attn
    t, page = 24, 8
    x = paddle.to_tensor(np.random.default_rng(2).normal(
        size=(1, t, att.config.hidden_size)).astype(np.float32))
    with paddle.no_grad():
        want = np.asarray(att(x)._data)
        width = latent_row_width(att.kv_rank, att.rope)
        sin, cos = rope_tables_interleaved(jnp.arange(t), att.rope,
                                           float(att.config.rope_theta))
        qf, rows = att.absorbed(x, sin, cos, width)
        assert rows.shape == [t, width]
        # what is cached: the latent, the rotated shared key, zero lanes
        assert not np.asarray(rows._data)[:, att.kv_rank + att.rope:].any()
        one = lambda v: jnp.asarray([v], jnp.int32)      # noqa: E731
        out, _ = ragged_mla_attention_xla(
            qf, rows, jnp.zeros((4, page, width), jnp.float32),
            jnp.asarray([[0, 1, 2]], jnp.int32), one(t), one(0), one(t),
            one(0), one(0), one(t), att.kv_rank, att.scale, t)
        got = np.asarray(att.unabsorb(out.reshape(
            [t, att.num_heads, att.kv_rank]))._data)
    assert np.abs(got - want).max() < LOGIT_TOL


def _route(mlp, x, bias=None):
    wr = mlp.router._data
    br = mlp.router_bias._data if bias is None else bias
    idx, w = mlp.route(jnp.asarray(x), wr, br)
    return np.asarray(idx), np.asarray(w)


def test_router_bias_moves_the_choice_not_the_weights(setup):
    _, _, model, _ = setup
    mlp = model.model.layers[1].mlp
    x = np.random.default_rng(3).normal(size=(16, 64)).astype(np.float32)
    s = np.asarray(jax.nn.sigmoid(x @ np.asarray(mlp.router._data)))
    idx0, w0 = _route(mlp, x, jnp.zeros_like(mlp.router_bias._data))
    # without a bias the top k by score, weights s / sum(s) * scale
    top = np.sort(np.argsort(-s, axis=1)[:, :mlp.top_k], axis=1)
    assert (np.sort(idx0, axis=1) == top).all()
    picked = np.take_along_axis(s, idx0, axis=1)
    np.testing.assert_allclose(
        w0, picked / picked.sum(1, keepdims=True) * 2.5, rtol=1e-6)
    np.testing.assert_allclose(w0.sum(1), 2.5, rtol=1e-6)
    # a large bias on one expert puts it among every token's choice,
    # and its weight is still its own score's share
    bias = jnp.zeros_like(mlp.router_bias._data).at[5].set(10.0)
    idx1, w1 = _route(mlp, x, bias)
    assert (idx1 == 5).any(axis=1).all()
    picked = np.take_along_axis(s, idx1, axis=1)
    np.testing.assert_allclose(
        w1, picked / picked.sum(1, keepdims=True) * 2.5, rtol=1e-6)
    # the seeded bias is not zero, so the configuration tests this too
    assert np.abs(np.asarray(mlp.router_bias._data)).max() > 0


def test_shared_expert_is_added_once(setup):
    _, _, model, _ = setup
    mlp = model.model.layers[1].mlp
    x = paddle.to_tensor(np.random.default_rng(4).normal(
        size=(1, 6, 64)).astype(np.float32))
    with paddle.no_grad():
        whole = np.asarray(mlp(x)._data)
        shared = np.asarray(mlp.shared(x)._data)
        x2 = np.asarray(x._data).reshape(6, 64)
        idx, w = _route(mlp, x2)
        g = np.asarray(mlp.experts_gate._data)
        u = np.asarray(mlp.experts_up._data)
        d = np.asarray(mlp.experts_down._data)
        routed = np.zeros((6, 64), np.float32)
        for t in range(6):
            for e, wt in zip(idx[t], w[t]):
                h = np.asarray(jax.nn.silu(x2[t] @ g[e])) * (x2[t] @ u[e])
                routed[t] += wt * (h @ d[e])
    assert np.abs(whole[0] - routed - shared[0]).max() < 1e-5
    assert list(np.asarray(mlp.last_stats._data)) == [
        len(set(idx.reshape(-1))), np.bincount(idx.reshape(-1)).max()]


# ---------------------------------------------------------------------------
# through the engine
# ---------------------------------------------------------------------------
def _engine(model, **kw):
    kw = dict(dict(max_batch=4, page_size=8, num_pages=65,
                   max_pages_per_seq=16, chunk_budget=16, chunk_block=8),
              **kw)
    return LlamaServingEngine(model, **kw)


def _serve(engine, prompts, new):
    reqs = [Request(list(p), max_new_tokens=new) for p in prompts]
    for r in reqs:
        engine.add_request(r)
    for _ in range(400):
        if all(r.done for r in reqs):
            break
        engine.step()
    assert all(r.done and r.status == "completed" for r in reqs)
    return reqs


def _gaps(setup, prompts, outs):
    """How far each served token's reference logit lies under the
    reference's best."""
    pad = max(len(p) + len(o) for p, o in zip(prompts, outs)) + 1
    kmax = max(len(o) for o in outs)
    ids = np.zeros((len(prompts), pad), np.int64)
    rows = np.zeros((len(prompts), kmax), np.int32)
    for i, (p, o) in enumerate(zip(prompts, outs)):
        ids[i, :len(p)], ids[i, len(p):len(p) + len(o)] = p, o
        rows[i, :len(o)] = np.arange(len(p) - 1, len(p) - 1 + len(o))
    ref = ref_logits(setup, ids, rows)
    gaps = []
    for i, o in enumerate(outs):
        took = ref[i, np.arange(len(o)), np.asarray(o)]
        gaps += list(ref[i, :len(o)].max(-1) - took)
    return np.asarray(gaps)


def test_engine_serves_the_reference_through_latent_pages(setup):
    """Prompts of 5 to 45 tokens under a 16-token chunk budget (the long
    ones are prefilled over several dispatches beside the others' decode
    rows), then decode through the latent pages."""
    cfg, _, model, _ = setup
    rng = np.random.default_rng(5)
    prompts = [list(rng.integers(1, cfg["vocab_size"], n))
               for n in (5, 45, 23, 9, 30)]
    e = _engine(model)
    reqs = _serve(e, prompts, 7)
    outs = [r.output_ids for r in reqs]
    assert all(len(o) == 7 for o in outs)
    assert _gaps(setup, prompts, outs).max() < GAP_TOL
    # one pool a layer, no head axis, 576 useful lanes of 640... here
    # 32 + 8 of 128: the row is padded to whole lane tiles
    att = model.model.layers[0].self_attn
    assert len(e.k_pools) == cfg["num_hidden_layers"] and e.v_pools == []
    assert e.k_pools[0].shape == [65, 8, 128]
    assert e.kv_bytes_per_token == 128 * 4 * cfg["num_hidden_layers"]
    assert att.kv_rank + att.rope == 40
    # every page went back
    assert e.alloc.free_pages + (e.prefix.pages if e.prefix else 0) \
        == e.alloc.num_pages


def test_prefix_cache_hit_serves_the_same_tokens(setup):
    cfg, _, model, _ = setup
    rng = np.random.default_rng(6)
    shared = list(rng.integers(1, cfg["vocab_size"], 24))
    first = shared + list(rng.integers(1, cfg["vocab_size"], 9))
    second = shared + list(rng.integers(1, cfg["vocab_size"], 13))
    warm = _engine(model)
    _serve(warm, [first], 4)
    hit = _serve(warm, [second], 6)[0]
    assert hit._cached_tokens == 24 and warm.prefix.stats()["hits"] >= 1
    cold = _serve(_engine(model, prefix_cache=False), [second], 6)[0]
    assert hit.output_ids == cold.output_ids
    assert _gaps(setup, [second], [hit.output_ids]).max() < GAP_TOL


def test_decode_scan_equals_the_stepped_decode(setup):
    cfg, _, model, _ = setup
    rng = np.random.default_rng(7)
    prompts = [list(rng.integers(1, cfg["vocab_size"], n)) for n in (6, 19)]
    stepped = [r.output_ids for r in _serve(_engine(model), prompts, 9)]
    scanned = _engine(model).generate(prompts, max_new_tokens=9)
    assert [list(o) for o in scanned] == stepped


@pytest.mark.parametrize("kw,what", [
    (dict(kv_dtype="int8"), "kv_dtype=int8"),
    (dict(kv_tier=True), "kv_tier"),
    (dict(spec_k=2), "spec_k"),
])
def test_features_that_do_not_reach_latent_pages_are_refused(setup, kw,
                                                             what):
    with pytest.raises(UnsupportedServingFeature, match=what):
        _engine(setup[2], **kw)


def test_generate_matches_the_forward(setup):
    cfg, _, model, _ = setup
    ids = np.random.default_rng(8).integers(1, cfg["vocab_size"], (2, 11))
    out = np.asarray(model.generate(paddle.to_tensor(ids),
                                    max_new_tokens=4)._data)
    assert out.shape == (2, 15) and (out[:, :11] == ids).all()
    with paddle.no_grad():
        lg = np.asarray(model(paddle.to_tensor(out[:, :-1]))._data)
    assert (lg[:, 10:].argmax(-1) == out[:, 11:]).all()


def test_tiny_config_builds():
    paddle.seed(0)
    m = MlaMoeForCausalLM(tiny_mla_moe_config())
    assert m.num_params() == sum(int(np.prod(p.shape))
                                 for p in m.parameters())


def test_a_subprocess_replica_can_be_told_to_build_it():
    from paddle_tpu.inference.replica_worker import _build_model
    m = _build_model({"kind": "tiny_mla_moe", "seed": 0,
                      "config": {"num_hidden_layers": 2}})
    assert isinstance(m, MlaMoeForCausalLM) and not m.training
    assert len(m.model.layers) == 2
    with pytest.raises(ValueError, match="unknown model kind"):
        _build_model({"kind": "no_such_model"})
