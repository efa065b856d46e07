"""The decoder-hybrid-decoder model (``models/sambay.py``: state-space
layers, window and full differential attention, one shared K/V pool,
gated memory units) against the plain reference of its benchmark family
(``perfbench/families/sambay.py``: float32, a token-by-token scan, dense
masked attention with 64-wide heads and both softmaxes of a pair written
out, no cache, nothing of the program imported), on seeded weights at
the configuration's rehearsal sizes (8 layers: all six kinds; window 16);
then the same model through ``LlamaServingEngine``: states a sequence
slot, rings of window pages, the shared pool, a carry between layers.

Tolerances: program and reference are both float32 here and differ in
the order of their sums (blocked rows against one scan, padded 2d-lane
heads through the paged program against dense 64-lane heads), so logits
agree to 2e-4 of a logit range of a few units; states to 1e-4 of their
largest value; served tokens are held to the reference's logits (the
served token's logit at most 1e-3 under the reference's best: a near-tie
may fall either way), never to its tokens."""

import os
import sys

import jax.numpy as jnp
import numpy as np
import pytest

import paddle_tpu as paddle
from paddle_tpu.inference.serving import (LlamaServingEngine, Request,
                                          UnsupportedServingFeature)
from paddle_tpu.models import SambaYForCausalLM, tiny_sambay_config
from paddle_tpu.observability import trace

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
for p in (ROOT, os.path.join(ROOT, "perfbench")):
    if p not in sys.path:
        sys.path.insert(0, p)

import run as bench                       # noqa: E402
from harness import family, program       # noqa: E402

SEED = 3000000023
LOGIT_TOL = 2e-4
GAP_TOL = 1e-3
STATE_TOL = 1e-4
KINDS = ["mamba", "window", "mamba", "window", "mamba_memory", "full",
         "gmu", "cross"]
NAME = "phi-4-mini-flash-reasoning"


@pytest.fixture(scope="module")
def setup():
    """(cfg, family, model with the seeded weights, the weights)."""
    cfg = bench.load_json("perfbench", "configs", NAME + ".json")
    bench.deep_update(cfg, cfg["rehearse"])
    fam = family.load(cfg, NAME)
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
    """40 tokens: two and a half windows."""
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


def test_every_kind_of_layer_is_there(setup):
    cfg, fam, model, _ = setup
    assert [layer.kind for layer in model.model.layers] == KINDS
    assert [fam.kind(cfg, i) for i in range(8)] == KINDS
    assert cfg["sliding_window"] == 16


@pytest.mark.parametrize("index", range(8), ids=[
    f"{i}-{k}" for i, k in enumerate(KINDS)])
def test_each_layer_alone_equals_its_reference_layer(setup, index):
    """One layer of each kind over one random sequence of 40 tokens,
    handed a random scan output and random shared keys and values where
    it reads them."""
    cfg, fam, model, w = setup
    d = fam.dims(cfg)
    rng = np.random.default_rng(10 + index)
    t = 40
    x = rng.normal(size=(t, d["h"])).astype(np.float32)
    m = rng.normal(size=(t, d["c"])).astype(np.float32)
    k, v = (rng.normal(size=(t, d["hk"], d["d"])).astype(np.float32)
            for _ in range(2))
    want = np.asarray(fam.layer_forward(
        jnp.asarray(x), w["layers"][index], cfg, index,
        {"m": jnp.asarray(m), "kv": (jnp.asarray(k), jnp.asarray(v))},
        None))
    pairs = lambda a: paddle.to_tensor(                  # noqa: E731
        a.reshape(1, t, d["hk"] // 2, 2 * d["d"]))
    carry = {"m": paddle.to_tensor(m[None]), "kv": (pairs(k), pairs(v))}
    with paddle.no_grad():
        got = np.asarray(model.model.layers[index](
            paddle.to_tensor(x[None]), carry)._data)[0]
    assert np.abs(got - want).max() < LOGIT_TOL * max(1.0, np.abs(want).max())


# ---------------------------------------------------------------------------
# through the engine
# ---------------------------------------------------------------------------
def _engine(model, **kw):
    kw = dict(dict(max_batch=4, page_size=8, num_pages=4 * 16 + 1,
                   max_pages_per_seq=16, chunk_budget=32, chunk_block=8),
              **kw)
    return LlamaServingEngine(model, **kw)


def _serve(engine, prompts, new):
    reqs = [Request(list(p), max_new_tokens=new) for p in prompts]
    for r in reqs:
        engine.add_request(r)
    for _ in range(600):
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


def test_engine_serves_the_reference_past_the_window(setup):
    """Four prompts of 5 to 45 tokens in chunks of 8 (the 45-token one
    takes six dispatches, beside the others' decode rows: rows of 1 and
    of 8 tokens in one dispatch), then 30 decoded tokens: every context
    ends two to four windows long."""
    cfg, _, model, _ = setup
    rng = np.random.default_rng(5)
    prompts = [list(rng.integers(1, cfg["vocab_size"], n))
               for n in (5, 45, 23, 30)]
    e = _engine(model)
    reqs = _serve(e, prompts, 30)
    outs = [r.output_ids for r in reqs]
    assert all(len(o) == 30 for o in outs)
    assert _gaps(setup, prompts, outs).max() < GAP_TOL
    # 2 state pools a Mamba layer (3), 2 ring pools a window layer (2),
    # the shared pool's 2: the cross layer is handed layer 5's
    assert len(e.k_pools) == 3 * 2 + 2 * 2 + 2 and e.v_pools == []
    assert e._layer_pages[7] == e._layer_pages[5] and e._layer_pages[6] == []
    ring = e.ring_pages(16)
    assert ring == (16 + 8) // 8 + 1
    assert e.k_pools[2].shape == [(4 + 1) * ring, 2, 8, 16]
    assert e.k_pools[0].shape == [5, 3, 128] \
        and e.k_pools[1].shape == [5, 16, 128]
    # only the shared pool grows with the context
    assert e.kv_bytes_per_token == 2 * 2 * 16 * 4
    # every page and every slot went back
    assert e.alloc.free_pages == e.alloc.num_pages
    assert e.alloc.slots_held == 0


def test_window_pages_stay_bounded_while_the_context_grows(setup):
    """One sequence decoded to more than four windows: what the window
    layers hold stops growing at the ring, the ring wraps three times
    and the tokens still are the reference's."""
    cfg, _, model, _ = setup
    rng = np.random.default_rng(6)
    prompt = list(rng.integers(1, cfg["vocab_size"], 12))
    e = _engine(model)
    trace.clear()
    req = _serve(e, [prompt], 4 * 16 + 10)[0]
    # (the last dispatch retires the sequence: it holds nothing after)
    disp = [ev["args"] for ev in trace.get_events()
            if ev["name"] == "serving.dispatch"][:-1]
    assert len(disp) >= 4 * 16
    held = [d["window_pages"] for d in disp]
    ring, layers = e.ring_pages(16), 2
    assert max(held) <= layers * ring and max(held) == held[-1]
    # the window's pages: 16 keys lie on 2 or 3 pages of 8
    assert set(held[24:]) <= {layers * 2, layers * 3}
    assert sum(d["window_pages_freed"] for d in disp) \
        == layers * ((12 + 4 * 16 + 10 - 2 - 16) // 8)
    # the shared pool keeps every token
    assert disp[-1]["shared_kv_pages"] == -(-(12 + 4 * 16 + 10 - 2) // 8)
    assert all(d["state_slots"] == 1 for d in disp)
    assert _gaps(setup, [prompt], [req.output_ids]).max() < GAP_TOL


def test_state_after_chunked_prefill_equals_one_pass(setup):
    """A 37-token prompt in five chunks of 8 against one chunk of 64:
    the same scan states, conv inputs and first token."""
    cfg, _, model, _ = setup
    prompt = list(np.random.default_rng(7).integers(
        1, cfg["vocab_size"], 37))
    states = []
    for block in (8, 64):
        e = _engine(model, chunk_block=block, chunk_budget=64)
        r = Request(prompt, max_new_tokens=2)
        e.add_request(r)                   # prefills to the first token
        slot = e.alloc.slot_of(r.seq_id)
        states.append([np.asarray(e.k_pools[i]._data[slot])
                       for i in (0, 1, 4, 5, 8, 9)] + [r.output_ids[0]])
    for chunked, whole in zip(*states):
        assert np.abs(chunked - whole).max() \
            <= STATE_TOL * np.abs(whole).max()
    assert all(np.abs(a).max() > 0 for a in states[1])


@pytest.mark.parametrize("kw,what", [
    (dict(prefix_cache=True), "prefix_cache"),
    (dict(kv_dtype="int8"), "kv_dtype=int8"),
    (dict(kv_tier=True), "kv_tier"),
    (dict(spec_k=2), "spec_k"),
    (dict(weight_dtype="int8"), "weight_dtype=int8"),
])
def test_features_that_reach_no_state_or_window_are_refused(setup, kw,
                                                            what):
    with pytest.raises(UnsupportedServingFeature, match=what):
        _engine(setup[2], **kw)


def test_prefix_cache_is_off_where_no_layer_can_reuse_a_prefix(setup):
    e = _engine(setup[2])
    assert e.prefix is None
    with pytest.raises(UnsupportedServingFeature, match="decode scan"):
        e._ensure_scan_compiled(4)


def test_generate_steps_and_matches_the_forward(setup):
    """`engine.generate` (single steps: the scan carries no slots) and
    the model's own cache-free `generate` against the forward."""
    cfg, _, model, _ = setup
    ids = np.random.default_rng(8).integers(1, cfg["vocab_size"], (2, 21))
    out = np.asarray(model.generate(paddle.to_tensor(ids),
                                    max_new_tokens=4)._data)
    assert out.shape == (2, 25) and (out[:, :21] == ids).all()
    with paddle.no_grad():
        lg = np.asarray(model(paddle.to_tensor(out[:, :-1]))._data)
    assert (lg[:, 20:].argmax(-1) == out[:, 21:]).all()
    served = _engine(model).generate([list(r) for r in ids],
                                     max_new_tokens=4)
    assert _gaps(setup, [list(r) for r in ids],
                 [list(o) for o in served]).max() < GAP_TOL


def test_tiny_config_builds():
    paddle.seed(0)
    m = SambaYForCausalLM(tiny_sambay_config())
    assert m.num_params() == sum(int(np.prod(p.shape))
                                 for p in m.parameters())
    assert [layer.kind for layer in m.model.layers] == KINDS
    with pytest.raises(ValueError, match="multiple of 4"):
        tiny_sambay_config(num_hidden_layers=6)


def test_a_subprocess_replica_can_be_told_to_build_it():
    from paddle_tpu.inference.replica_worker import _build_model
    m = _build_model({"kind": "tiny_sambay", "seed": 0,
                      "config": {"sliding_window": 8}})
    assert isinstance(m, SambaYForCausalLM) and not m.training
    assert m.config.sliding_window == 8


def test_configuration_keeps_every_published_key():
    cfg = bench.load_json("perfbench", "configs", NAME + ".json")
    published = {
        "embd_pdrop": 0, "hidden_act": "silu", "hidden_size": 2560,
        "intermediate_size": 10240, "layer_norm_eps": 1e-05,
        "max_position_embeddings": 262144, "mb_per_layer": 2,
        "model_type": "phi4flash", "num_attention_heads": 40,
        "num_hidden_layers": 32, "num_key_value_heads": 20,
        "resid_pdrop": 0, "sliding_window": 512,
        "tie_word_embeddings": True, "mlp_bias": False,
        "lm_head_bias": False, "vocab_size": 200064}
    assert {k: cfg[k] for k in published} == published
    assert cfg["reduced"] == {} and cfg["family"] == "sambay"
    for key in ("mamba_d_state", "mamba_d_conv", "mamba_expand",
                "mamba_dt_rank", "layer_kinds", "window_edge",
                "positional_encoding", "attention", "weights"):
        assert key in cfg["assumed"], key
    fam = family.load(cfg, NAME)
    fam.selfcheck()
    assert fam.total_params(cfg) == 3852562944
    # nothing of the program is imported by the reference
    with open(family.path_of("sambay")) as f:
        body = f.read().split("# the plain reference")[1]
    assert "paddle_tpu" not in body


def test_scan_of_long_rows_alone_equals_the_scan_of_all():
    """A dispatch's scan with its promise that few rows are longer than
    one token against the plain scan over every row."""
    from paddle_tpu.ops.selective_scan import selective_scan_rows
    rng = np.random.default_rng(9)
    r, q, c, n = 7, 5, 12, 4
    x, dt = (rng.normal(size=(r, q, c)).astype(np.float32) for _ in "xd")
    b, cm = (rng.normal(size=(r, q, n)).astype(np.float32) for _ in "bc")
    a = -np.exp(rng.normal(size=(n, c))).astype(np.float32)
    d = rng.normal(size=(c,)).astype(np.float32)
    h0 = rng.normal(size=(r, n, c)).astype(np.float32)
    lens = np.asarray([1, 0, 5, 1, 3, 1, 0], np.int32)
    args = [jnp.asarray(v) for v in (x, np.abs(dt), b, cm, a, d, h0, lens)]
    m0, s0 = selective_scan_rows(*args)
    m1, s1 = selective_scan_rows(*args, long_rows=3)
    np.testing.assert_allclose(s1, s0, rtol=1e-6, atol=1e-6)
    for i, k in enumerate(lens):
        np.testing.assert_allclose(m1[i, :k], m0[i, :k], rtol=1e-6,
                                   atol=1e-6)
    # a row that is none keeps its state
    assert np.array_equal(np.asarray(s1)[[1, 6]], h0[[1, 6]])
