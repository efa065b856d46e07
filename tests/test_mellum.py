"""The window / full GQA expert model (``models/mellum.py``: window layers
that keep a ring of pages a sequence slot with the default rope, full
layers that keep the whole context with YaRN's rope, a softmax-routed
expert FFN in every layer on the packed path) against the plain reference
of its benchmark family (``perfbench/families/window_moe.py``: float32,
dense masked attention, its own YaRN frequencies, the experts a group at a
time, nothing of the program imported), on seeded weights at the
configuration's rehearsal sizes (4 layers: window, window, window, full;
window 16; 8 query heads over one kv head; 8 experts, 2 a token); then the
same model through ``LlamaServingEngine``: rings that wrap beside pages of
the whole context.

Tolerances: program and reference are both float32 here and differ in the
order of their sums (the paged program against dense heads, packed expert
rows against a group at a time), so logits agree to 2e-4 of a logit range
of a few units; served tokens are held to the reference's logits (the
served token's logit at most 1e-3 under the reference's best: a near-tie
may fall either way), never to its tokens. Each of full attention on a
window layer, the default rope on a full layer, sigmoid routing and a
forced shared expert moves logits by more than ten times that
(``test_the_tolerance_sees_each_mechanism``)."""

import dataclasses
import math
import os
import sys

import jax.numpy as jnp
import numpy as np
import pytest

import paddle_tpu as paddle
from paddle_tpu.framework.tensor import Tensor
from paddle_tpu.inference.layer_step import PagedKV
from paddle_tpu.inference.serving import (LlamaServingEngine, Request,
                                          UnsupportedServingFeature)
from paddle_tpu.models import (MellumForCausalLM, MlaMoeMLP,
                               tiny_mellum_config)
from paddle_tpu.models.llama import LlamaMoEMLP
from paddle_tpu.observability import trace
from paddle_tpu.ops.ragged_paged_attention import (rope_tables,
                                                  yarn_inv_freq)

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
for p in (ROOT, os.path.join(ROOT, "perfbench")):
    if p not in sys.path:
        sys.path.insert(0, p)

import run as bench                       # noqa: E402
from harness import family, program       # noqa: E402

SEED = 3000000041
LOGIT_TOL = 2e-4
GAP_TOL = 1e-3
W, F = "sliding_attention", "full_attention"
KINDS = [W, W, W, F]
NAME = "mellum2-12b-a2.5b-l8"


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


def _forward(model, ids):
    with paddle.no_grad():
        return np.asarray(model(paddle.to_tensor(ids))._data)


def test_logits_equal_the_reference(setup):
    """70 tokens: past the window of 16 and past the point where YaRN's
    interpolated frequencies part from the default rope's."""
    cfg, _, model, _ = setup
    ids = np.random.default_rng(1).integers(0, cfg["vocab_size"], (2, 70))
    got = _forward(model, ids)
    rows = np.tile(np.arange(70), (2, 1))
    want = ref_logits(setup, ids, rows)
    assert np.abs(got - want).max() < LOGIT_TOL
    # the int8 control is a different model, by far more than that
    assert np.abs(ref_logits(setup, ids, rows, "int8") - want).max() \
        > 10 * LOGIT_TOL


def test_every_kind_of_layer_is_there(setup):
    cfg, _, model, _ = setup
    layers = model.model.layers
    assert [layer.kind for layer in layers] == KINDS == cfg["layer_types"]
    assert [layer.self_attn.window for layer in layers] == [16] * 3 + [None]
    assert [layer.self_attn.yarn is not None for layer in layers] \
        == [False] * 3 + [True]
    caches = [layer.serving_cache() for layer in layers]
    assert all(isinstance(c, PagedKV) and (c.heads, c.width) == (1, 16)
               for c in caches)
    assert [c.window for c in caches] == [16] * 3 + [None]
    mlp = layers[0].mlp
    assert (mlp.scoring, mlp.num_experts, mlp.top_k) == ("softmax", 8, 2)
    assert mlp.shared is None and mlp.router_bias is None


def _yarn_by_hand(d, base, factor, original, fast, slow, scale, pos):
    """YaRN's equations, written out in numpy float64."""
    def dim_of(turns):
        return d * math.log(original / (turns * 2 * math.pi)) \
            / (2 * math.log(base))
    lo, hi = math.floor(dim_of(fast)), math.ceil(dim_of(slow))
    i = np.arange(d // 2, dtype=np.float64)
    f = base ** (-2 * i / d)
    r = np.clip((i - lo) / (hi - lo), 0, 1)
    inv = r * f / factor + (1 - r) * f
    ang = np.asarray(pos, np.float64)[:, None] * inv
    emb = np.concatenate([ang, ang], -1)
    return (lo, hi), inv, np.sin(emb) * scale, np.cos(emb) * scale, r


@pytest.mark.parametrize("d,base,original", [
    (128, 500000.0, 8192), (16, 10000.0, 2048)], ids=["published", "tiny"])
def test_yarn_tables_equal_the_formula(d, base, original):
    """Published: the ramp from 18 to 35, worked by hand from the
    published config; both sizes have frequencies at each end of the
    ramp and between. The
    frequencies to float32's precision; the tables to 1e-4 where the
    angle is small, and, at every position up to the cell's longest
    context, to the float32 angle's rounding."""
    y = (16.0, original, 32.0, 1.0, 1.2772588722239782)
    pos = np.array([0, 1, 7, 100, 1023, 8191, 17407], np.int32)
    ends, inv, sin, cos, r = _yarn_by_hand(d, base, *y, pos)
    if d == 128:
        assert ends == (18, 35)
    assert (r == 0).any() and (r == 1).any() and ((r > 0) & (r < 1)).any()
    got_inv, scale = yarn_inv_freq(d, base, y)
    assert scale == y[-1]
    assert np.abs(np.asarray(got_inv) / inv - 1).max() < 1e-6
    got_sin, got_cos = (np.asarray(t) for t in
                        rope_tables(jnp.asarray(pos), d, base, y))
    near = pos < 1024
    assert np.abs(got_sin[near] - sin[near]).max() < 1e-4
    assert np.abs(got_cos[near] - cos[near]).max() < 1e-4
    # the angle as float32 forms it, then sin and cos in float64
    ang = (pos[:, None].astype(np.float32) * np.asarray(got_inv)) \
        .astype(np.float64)
    emb = np.concatenate([ang, ang], -1)
    assert np.abs(got_sin - y[-1] * np.sin(emb)).max() < 1e-5
    assert np.abs(got_cos - y[-1] * np.cos(emb)).max() < 1e-5
    # without YaRN the tables are the default rope's, unscaled
    plain_sin, _ = rope_tables(jnp.asarray(pos), d, base)
    f = base ** (-2 * np.arange(d // 2) / d)
    assert np.abs(np.asarray(plain_sin)[:, :d // 2][near]
                  - np.sin(pos[near, None] * f)).max() < 1e-4


def _swap(obj, **kw):
    kept = {k: getattr(obj, k) for k in kw}
    for k, v in kw.items():
        setattr(obj, k, v)
    return kept


@pytest.mark.parametrize("what", ["full_on_window", "default_rope_on_full",
                                  "sigmoid_routing", "shared_expert"])
def test_the_tolerance_sees_each_mechanism(setup, what):
    """Each mechanism put back by its plainer neighbour moves the logits
    by more than ten times the tolerance the program meets."""
    cfg, _, model, _ = setup
    ids = np.random.default_rng(2).integers(0, cfg["vocab_size"], (1, 60))
    want = ref_logits(setup, ids, np.arange(60)[None])
    layers = model.model.layers
    undo = []
    if what == "full_on_window":
        undo += [(layers[i].self_attn, _swap(layers[i].self_attn,
                                             window=None)) for i in range(3)]
    elif what == "default_rope_on_full":
        undo.append((layers[3].self_attn, _swap(layers[3].self_attn,
                                                yarn=None)))
    elif what == "sigmoid_routing":
        zero = Tensor(jnp.zeros((8,), jnp.float32))
        undo += [(layer.mlp, _swap(layer.mlp, scoring="sigmoid",
                                   router_bias=zero)) for layer in layers]
    else:
        paddle.seed(7)
        conf = dataclasses.replace(model.config, n_shared_experts=1)
        for layer in layers:
            forced = MlaMoeMLP(conf, scoring="softmax")
            for name in ("router", "experts_gate", "experts_up",
                         "experts_down"):
                getattr(forced, name)._data = getattr(layer.mlp, name)._data
            undo.append((layer, _swap(layer, mlp=forced)))
    try:
        got = _forward(model, ids)
    finally:
        for obj, kept in undo:
            _swap(obj, **kept)
    assert np.abs(got - want).max() > 10 * LOGIT_TOL
    assert np.abs(_forward(model, ids) - want).max() < LOGIT_TOL


def test_the_packed_softmax_layer_equals_the_strided_one(setup):
    """`MlaMoeMLP(scoring="softmax")` with no shared expert against
    `LlamaMoEMLP`'s strided forward on the same weights."""
    cfg, _, model, _ = setup
    conf = model.config
    paddle.seed(11)
    packed = MlaMoeMLP(conf, scoring="softmax")
    strided = LlamaMoEMLP(conf)
    strided.gate._data = packed.router._data
    strided.gate_proj._data = packed.experts_gate._data
    strided.up_proj._data = packed.experts_up._data
    strided.down_proj._data = packed.experts_down._data
    x = np.random.default_rng(3).normal(
        size=(2, 19, conf.hidden_size)).astype(np.float32)
    with paddle.no_grad():
        a = np.asarray(packed(paddle.to_tensor(x))._data)
        b = np.asarray(strided(paddle.to_tensor(x))._data)
    assert np.abs(a).max() > 1e-3
    assert np.abs(a - b).max() < 1e-5


def test_no_shared_expert_is_built_where_the_config_has_none(setup):
    """``n_shared_experts`` 0 builds no shared expert (the parameters
    counted), 1 builds one of the expert width; `route` with softmax
    takes no bias."""
    conf = setup[2].config
    e, h, f = conf.n_routed_experts, conf.hidden_size, \
        conf.moe_intermediate_size
    none = MlaMoeMLP(conf, scoring="softmax")
    assert sum(int(np.prod(p.shape)) for p in none.parameters()) \
        == h * e + 3 * e * h * f
    one = MlaMoeMLP(dataclasses.replace(conf, n_shared_experts=1))
    assert sum(int(np.prod(p.shape)) for p in one.parameters()) \
        == h * e + e + 3 * e * h * f + 3 * h * f
    with pytest.raises(ValueError, match="scoring"):
        MlaMoeMLP(conf, scoring="tanh")


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


def test_engine_serves_the_reference_through_rings_and_pages(setup):
    """Prompts of 1 to 75 tokens (longer than the window and a chunk
    budget together, 16 + 32) in chunks of 8 beside decode rows, then 30
    decoded tokens: the rings of 7 pages wrap several times."""
    cfg, _, model, _ = setup
    rng = np.random.default_rng(5)
    prompts = [list(rng.integers(1, cfg["vocab_size"], n))
               for n in (1, 75, 23, 60)]
    e = _engine(model)
    reqs = _serve(e, prompts, 30)
    outs = [r.output_ids for r in reqs]
    assert all(len(o) == 30 for o in outs)
    assert _gaps(setup, prompts, outs).max() < GAP_TOL
    # a K and a V pool a layer: three rings, then the whole context
    assert e.ring_pages(16) == 7 and e.v_pools == []
    assert e._layer_pages == [[0, 1], [2, 3], [4, 5], [6, 7]]
    assert [p.shape for p in e.k_pools] == [[5 * 7, 1, 8, 16]] * 6 \
        + [[65, 1, 8, 16]] * 2
    # only the full layer's pools grow with the context
    assert e.kv_bytes_per_token == 2 * 16 * 4
    assert e.alloc.free_pages == e.alloc.num_pages
    assert e.alloc.slots_held == 0


def test_logits_through_the_engine_equal_the_forward(setup):
    """Prefill then decode through rings and whole-context pages against
    the model's own forward."""
    cfg, _, model, _ = setup
    rng = np.random.default_rng(6)
    prompts = [list(rng.integers(1, cfg["vocab_size"], n)) for n in (53, 9)]
    reqs = _serve(_engine(model), prompts, 20)
    for p, r in zip(prompts, reqs):
        ids = np.asarray([p + list(r.output_ids)])
        lg = _forward(model, ids)[0]
        at = np.arange(len(p) - 1, len(p) - 1 + len(r.output_ids))
        took = lg[at, np.asarray(r.output_ids)]
        assert (lg[at].max(-1) - took).max() < GAP_TOL


def test_dispatch_counters_follow_the_allocator(setup):
    """``window_pages`` and ``shared_kv_pages`` against the allocator
    after each dispatch; ``window_keys`` / ``full_keys`` (and the keys
    read, ``*_reads``) against the dispatch's own rows: a row of ``n``
    query tokens ending at context ``c`` reads ``c`` keys in a full layer
    and ``min(c, 15 + n)`` in a window layer of 16."""
    cfg, _, model, _ = setup
    e = _engine(model)
    seen, held = {}, []
    rows_of, counters = e._dispatch_rows, e._slot_counters

    def spy(rows, cow):
        seen[e._dispatch_count - 1] = [(start, n)
                                       for _, _, start, n, _, _ in rows]
        return rows_of(rows, cow)

    def spy_counters(rows):
        lens = [n for n in e.alloc._lens.values() if n > 0]
        held.append((lens, e.alloc.num_pages - e.alloc.free_pages))
        return counters(rows)

    e._dispatch_rows, e._slot_counters = spy, spy_counters
    rng = np.random.default_rng(9)
    reqs = [Request(list(rng.integers(1, cfg["vocab_size"], n)),
                    max_new_tokens=6) for n in (40, 5, 23)]
    trace.clear()
    for r in reqs:
        e.add_request(r)
    while any(not r.done for r in reqs):
        e.step()
    disp = [ev["args"] for ev in trace.get_events()
            if ev["name"] == "serving.dispatch"]
    assert len(disp) == len(held) > 3
    for a, (lens, used) in zip(disp, held):
        rows = seen[a["step"]]
        assert a["shared_kv_pages"] == used
        # pages of positions max(n - 16, 0) .. n - 1, three window layers
        assert a["window_pages"] == 3 * sum(
            (n - 1) // 8 - max(n - 16, 0) // 8 + 1 for n in lens)
        ends = [(s + n, n) for s, n in rows]
        assert a["full_keys"] == sum(c * n for c, n in ends)
        assert a["full_reads"] == sum(c for c, _ in ends)
        assert a["window_keys"] == sum(min(c, 15 + n) * n for c, n in ends)
        assert a["window_reads"] == sum(min(c, 15 + n) for c, n in ends)
        assert a["window_keys"] <= a["full_keys"]
        assert a["state_bytes"] == 0 and "tile_rows" in a
    assert any(a["window_keys"] < a["full_keys"] for a in disp)
    e.close()


class _TracedRun:
    """What the kind rooflines read of a traced run: the window's
    ``serving.dispatch`` args and its device ops with their scopes."""

    def __init__(self, fam, cfg, dispatches, ops):
        from harness import peaks
        self.family, self.cfg = fam, cfg
        self.peaks = peaks.peak("TPU v5 lite")
        self.facts = {"trace": {}, "window_ns": (0, 10 ** 9),
                      "device_ops": ops,
                      "spans": {"host": [("serving.dispatch", 1000 * i, 10,
                                          a) for i, a in
                                         enumerate(dispatches)]}}
        self.notes = []

    def note(self, **kw):
        self.notes.append(kw)


@pytest.mark.parametrize("kind", ["window", "full"])
def test_the_kind_rooflines_read_their_scopes_and_counters(setup, kind):
    """``<kind>_attn_roofline.tok``: the family's ``attn_work`` for the
    window's counters of ONE layer of that kind, at the published
    configuration, over the device time under ``paddle_tpu.attn_<kind>``
    (kernel and packing, not the projections); nothing from a program
    without the counters or the scope."""
    _, fam, _, _ = setup
    cfg = bench.load_json("perfbench", "configs", NAME + ".json")
    step = "jit(pure_step)/paddle_tpu.attn/"
    ops = [["%a = custom-call()", 0, 400_000, step + "paddle_tpu.attn_"
            + kind + "/jit(call)"],
           ["%b = copy()", 400_000, 100_000, step + "paddle_tpu.attn_"
            + kind + "/paddle_tpu.attn_pack/transpose"],
           ["%c = fusion()", 500_000, 300_000,
            step + "paddle_tpu.attn_proj/dot_general"]]
    disp = [{"tokens": 512, "window_keys": 600_000, "window_reads": 40_000,
             "full_keys": 900_000, "full_reads": 90_000}] * 2
    run = _TracedRun(fam, cfg, disp, ops)
    got = bench.load_reader(kind + "_attn_roofline.tok").read(run)
    flops, nbytes = fam.attn_work(cfg, kind, 2 * disp[0][kind + "_keys"],
                                  2 * disp[0][kind + "_reads"], 1024)
    least = max(flops / 197e12, nbytes / 819e9)
    assert got == pytest.approx(100 * least / 500e-6)
    (note,) = run.notes
    assert note["bound"] == "memory" and note["scope_seconds"] == 500e-6
    # the parent's program: no counters, or no scope
    bare = [dict(d) for d in disp]
    for d in bare:
        d.pop(kind + "_keys")
    assert bench.load_reader(kind + "_attn_roofline.tok").read(
        _TracedRun(fam, cfg, bare, ops)) is None
    other = "full" if kind == "window" else "window"
    assert bench.load_reader(other + "_attn_roofline.tok").read(
        _TracedRun(fam, cfg, disp, ops)) is None


@pytest.mark.parametrize("kw,what", [
    (dict(prefix_cache=True), "prefix_cache"),
    (dict(kv_dtype="int8"), "kv_dtype=int8"),
    (dict(kv_tier=True), "kv_tier"),
    (dict(weight_dtype="int8"), "weight_dtype=int8"),
])
def test_features_that_reach_no_ring_are_refused(setup, kw, what):
    with pytest.raises(UnsupportedServingFeature, match=what):
        _engine(setup[2], **kw)


def test_ngram_drafts_verify_through_the_rings(setup):
    """``spec_k`` reaches the rings: n-gram drafts of a repetitive prompt
    verified as rows of up to 3 tokens through rings of 7 pages that
    wrap, rejected keys rolled back; the greedy tokens are those of the
    engine without drafting and every page comes back."""
    cfg, _, model, _ = setup
    rng = np.random.default_rng(8)
    prompts = [list(rng.integers(1, cfg["vocab_size"], 10)) * 4,
               list(rng.integers(1, cfg["vocab_size"], 29))]
    plain = [r.output_ids for r in _serve(_engine(model), prompts, 40)]
    e = _engine(model, spec_k=2)
    assert [r.output_ids for r in _serve(e, prompts, 40)] == plain
    stats = e.spec_stats()
    assert stats["proposed"] > 0
    assert e.alloc.free_pages == e.alloc.num_pages
    assert e.alloc.double_free_count == 0


def test_prefix_cache_is_off_and_the_scan_refused(setup):
    e = _engine(setup[2])
    assert e.prefix is None and e.chunk_rows is None
    with pytest.raises(UnsupportedServingFeature, match="decode scan"):
        e._ensure_scan_compiled(4)


def test_tiny_config_builds_and_names_every_layer():
    paddle.seed(0)
    m = MellumForCausalLM(tiny_mellum_config())
    assert m.num_params() == sum(int(np.prod(p.shape))
                                 for p in m.parameters())
    assert [layer.kind for layer in m.model.layers] == KINDS
    with pytest.raises(ValueError, match="layer_types"):
        tiny_mellum_config(layer_types=(W, F))


def test_configuration_keeps_every_published_key():
    import json
    cfg = bench.load_json("perfbench", "configs", NAME + ".json")
    published = {
        "attention_bias": False, "head_dim": 128, "hidden_act": "silu",
        "hidden_size": 2304, "intermediate_size": 7168,
        "max_position_embeddings": 131072, "max_window_layers": 0,
        "model_type": "mellum", "moe_intermediate_size": 896,
        "norm_topk_prob": True, "num_attention_heads": 32,
        "num_experts": 64, "num_experts_per_tok": 8,
        "num_key_value_heads": 4, "rms_norm_eps": 1e-06,
        "sliding_window": 1024, "tie_word_embeddings": False,
        "use_sliding_window": True, "vocab_size": 98304,
        "torch_dtype": "bfloat16"}
    assert {k: cfg[k] for k in published} == published
    assert cfg["rope_parameters"] == {
        "full_attention": {
            "rope_type": "yarn", "rope_theta": 500000, "factor": 16,
            "original_max_position_embeddings": 8192, "beta_fast": 32,
            "beta_slow": 1, "attention_factor": 1.2772588722239782},
        "sliding_attention": {"rope_type": "default", "rope_theta": 500000}}
    assert cfg["num_hidden_layers"] == 8
    assert cfg["layer_types"] == ([W] * 3 + [F]) * 2
    assert cfg["mlp_layer_types"] == ["sparse"] * 8
    assert sorted(cfg["reduced"]) == ["layer_types", "mlp_layer_types",
                                      "num_hidden_layers"]
    assert cfg["reduced"]["num_hidden_layers"]["published"] == 28
    assert cfg["family"] == "window_moe" and "8, 8, 8 and 4" \
        in cfg["deployment"]
    for key in ("qk_norm", "mtp", "weights", "yarn", "router"):
        assert key in cfg["assumed"], key
    spec = bench.load_json("BENCHMARK.json")
    entry = next(c for c in spec["configs"] if c["name"] == NAME)
    assert entry["reduced"] == ["num_hidden_layers", "layer_types",
                                "mlp_layer_types"]
    fam = family.load(cfg, NAME)
    fam.selfcheck()
    assert fam.total_params(cfg) == 3794966784
    assert fam.kinds(cfg) == {"window": 6, "full": 2}
    # nothing of the program is imported by the reference
    with open(family.path_of("window_moe")) as f:
        body = f.read().split("# the plain reference")[1]
    assert "paddle_tpu" not in body
    assert json.dumps(cfg)          # plain data
