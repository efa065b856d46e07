"""The post-norm window / NoPE expert model with its MTP module
(``models/exaone_moe.py``: per-head q/k norms, window layers that keep a
ring of pages a sequence slot with the default rope, full layers with no
rope that keep the whole context, sigmoid-routed experts with a shared one
on the packed path, one chip's share of them, and a module that drafts the
next token but one) against the plain reference of its benchmark family
(``perfbench/families/exaone_moe.py``: float32, dense masked attention,
the held experts a group at a time, nothing of the program imported), on
seeded weights at the configuration's rehearsal sizes (4 layers: window,
window, window, full, the first dense; window 16; 8 query heads over 2 kv
heads; 4 experts held of the router's 8, 2 a token); then the same model
through ``LlamaServingEngine`` with ``spec_k`` 1, its module drafting in
the step program over a pool of its own.

Tolerances: program and reference are both float32 here and differ in the
order of their sums (the paged program against dense heads, packed expert
rows against a group at a time), so logits agree to 2e-4 of a logit range
of a few units; served tokens and drafts are held to the reference's
logits (the token's logit at most 1e-3 under the reference's best: a
near-tie may fall either way), never to its tokens. Each of the q/k norms,
the post-norms, the missing rope of the full layer, the routed scaling
and the correction bias, taken out, moves logits by more than ten times
that (``test_the_tolerance_sees_each_mechanism``)."""

import os
import sys

import jax.numpy as jnp
import numpy as np
import pytest

import paddle_tpu as paddle
from paddle_tpu import nn
from paddle_tpu.inference.layer_step import PagedKV
from paddle_tpu.inference.serving import (LlamaServingEngine, Request,
                                          UnsupportedServingFeature)
from paddle_tpu.models import (ExaoneMoeForCausalLM, MlaMoeMLP,
                               tiny_exaone_moe_config)
from paddle_tpu.observability import trace

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
for p in (ROOT, os.path.join(ROOT, "perfbench")):
    if p not in sys.path:
        sys.path.insert(0, p)

import run as bench                       # noqa: E402
from harness import family, program       # noqa: E402

SEED = 3000000043
LOGIT_TOL = 2e-4
GAP_TOL = 1e-3
W, F = "sliding_attention", "full_attention"
NAME = "k-exaone-236b-a23b-ep16-l8"
T = 64          # the tokens of a forward (one set of shapes for all)


@pytest.fixture(scope="module")
def setup(request):
    """(cfg, family, model with the seeded weights, the weights): the
    rehearsal's 8 query heads over 2 kv heads, or over the kv heads a
    test hands in (``GROUPS``)."""
    cfg = bench.load_json("perfbench", "configs", NAME + ".json")
    bench.deep_update(cfg, cfg["rehearse"])
    cfg["num_key_value_heads"] = getattr(request, "param",
                                         cfg["num_key_value_heads"])
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


def ref_mtp(setup, ids, rows):
    cfg, fam, _, w = setup
    return fam.mtp_logits(cfg, ids, rows, lambda i: w["layers"][i],
                          w["ends"])


def _forward(model, ids):
    with paddle.no_grad():
        return np.asarray(model(paddle.to_tensor(ids))._data)


def test_logits_equal_the_reference(setup):
    """64 tokens: past the window of 16; the int8 control is another
    model by far more than the tolerance."""
    cfg, _, model, _ = setup
    ids = np.random.default_rng(1).integers(0, cfg["vocab_size"], (2, T))
    got = _forward(model, ids)
    rows = np.tile(np.arange(T), (2, 1))
    want = ref_logits(setup, ids, rows)
    assert np.abs(got - want).max() < LOGIT_TOL
    assert np.abs(ref_logits(setup, ids, rows, "int8") - want).max() \
        > 10 * LOGIT_TOL


def test_mtp_logits_equal_the_reference(setup):
    """The module's own forward (row ``i`` predicts the token at ``i +
    2``) against the reference's."""
    cfg, _, model, _ = setup
    ids = np.random.default_rng(2).integers(0, cfg["vocab_size"], (2, T))
    with paddle.no_grad():
        got = np.asarray(model.mtp_logits(paddle.to_tensor(ids))._data)
    want = ref_mtp(setup, ids, np.tile(np.arange(T - 1), (2, 1)))
    assert got.shape == want.shape == (2, T - 1, cfg["vocab_size"])
    assert np.abs(got - want).max() < LOGIT_TOL
    assert np.abs(got - _forward(model, ids)[:, 1:]).max() > 0.1


def test_every_kind_of_layer_is_there(setup):
    cfg, _, model, _ = setup
    layers = model.model.layers
    assert [layer.kind for layer in layers] == cfg["layer_types"]
    assert [layer.self_attn.window for layer in layers] == [16] * 3 + [None]
    assert [layer.self_attn.rotary for layer in layers] \
        == [True] * 3 + [False]
    assert [layer.is_moe for layer in layers] == [False] + [True] * 3
    assert all(layer.post_norm for layer in layers)
    caches = [layer.serving_cache() for layer in layers]
    assert all(isinstance(c, PagedKV) and (c.heads, c.width) == (2, 16)
               for c in caches)
    assert [c.window for c in caches] == [16] * 3 + [None]
    mlp = layers[1].mlp
    assert (mlp.scoring, mlp.num_experts, mlp.held, mlp.first, mlp.top_k,
            mlp.scaling) == ("sigmoid", 8, 4, 4, 2, 2.5)
    assert mlp.shared is not None
    mtp = model.serving_drafter()
    assert mtp is model.mtp and mtp.layer.kind == F and mtp.layer.is_moe
    assert mtp.serving_cache().window is None


class _Identity(nn.Layer):
    def forward(self, x):
        return x


def _swap(obj, **kw):
    kept = {k: getattr(obj, k) for k in kw}
    for k, v in kw.items():
        setattr(obj, k, v)
    return kept


@pytest.mark.parametrize("what", ["qk_norm", "post_norm", "nope_full",
                                  "routed_scaling", "correction_bias"])
def test_the_tolerance_sees_each_mechanism(setup, what):
    """Each mechanism taken out of the program moves the logits by more
    than ten times the tolerance the program meets."""
    cfg, _, model, _ = setup
    ids = np.random.default_rng(3).integers(0, cfg["vocab_size"], (2, T))
    want = ref_logits(setup, ids, np.tile(np.arange(T), (2, 1)))
    layers = list(model.model.layers)
    undo = []
    for layer in layers:
        a, m = layer.self_attn, layer.mlp
        if what == "qk_norm":
            undo.append((a, _swap(a, q_norm=_Identity(),
                                  k_norm=_Identity())))
        elif what == "post_norm":
            undo.append((layer, _swap(
                layer, post_attention_layernorm=_Identity(),
                post_feedforward_layernorm=_Identity())))
        elif what == "nope_full" and a.window is None:
            undo.append((a, _swap(a, rotary=True)))
        elif what == "routed_scaling" and layer.is_moe:
            undo.append((m, _swap(m, scaling=1.0)))
        elif what == "correction_bias" and layer.is_moe:
            undo.append((m.router_bias, _swap(
                m.router_bias, _data=jnp.zeros_like(m.router_bias._data))))
    assert undo
    try:
        got = _forward(model, ids)
    finally:
        for obj, kept in undo:
            _swap(obj, **kept)
    assert np.abs(got - want).max() > 10 * LOGIT_TOL
    assert np.abs(_forward(model, ids) - want).max() < LOGIT_TOL


def test_sixteen_shares_of_eight_add_up_to_the_whole_layer():
    """The published split at the tiny width: a router of 128 outputs, 8
    a token; the routed parts of 16 shares of 8 experts plus the shared
    expert counted once equal the uncut layer, and the reference's whole
    layer."""
    conf = tiny_exaone_moe_config(n_routed_experts=128,
                                  num_experts_per_tok=8)
    rng = np.random.default_rng(4)
    paddle.seed(5)
    whole = MlaMoeMLP(conf)
    whole.router_bias._data = jnp.asarray(
        0.02 * rng.normal(size=(128,)), jnp.float32)
    shares = [MlaMoeMLP(conf, experts_held=8, first_expert=8 * r)
              for r in range(16)]
    for r, sh in enumerate(shares):
        for name in ("router", "router_bias"):
            getattr(sh, name)._data = getattr(whole, name)._data
        for name in ("experts_gate", "experts_up", "experts_down"):
            getattr(sh, name)._data = getattr(whole, name)._data[
                8 * r:8 * r + 8]
        for name in ("gate_proj", "up_proj", "down_proj"):
            getattr(sh.shared, name).weight._data = \
                getattr(whole.shared, name).weight._data
    x = rng.normal(size=(1, 37, conf.hidden_size)).astype(np.float32)
    xt = paddle.to_tensor(x)
    with paddle.no_grad():
        full = np.asarray(whole(xt)._data)
        shared = np.asarray(whole.shared(xt)._data)
        parts = [np.asarray(sh(xt)._data) - shared for sh in shares]
    assert np.abs(sum(parts) + shared - full).max() < 1e-5
    assert sum(np.abs(p).max() > 1e-4 for p in parts) >= 12
    # the reference family's FFN, told it holds all 128, gives the whole
    cfg = bench.load_json("perfbench", "configs", NAME + ".json")
    fam = family.load(cfg, NAME)
    c = dict(cfg, num_experts=128, ep_size=1, ep_rank=0)
    lw = {"router": whole.router._data,
          "router_bias": whole.router_bias._data,
          "shared_gate": whole.shared.gate_proj.weight._data,
          "shared_up": whole.shared.up_proj.weight._data,
          "shared_down": whole.shared.down_proj.weight._data,
          "experts_gate": whole.experts_gate._data,
          "experts_up": whole.experts_up._data,
          "experts_down": whole.experts_down._data}
    ref = fam.moe_ffn(jnp.asarray(x[0]), lw, c, jnp.ones((37,), bool),
                      None)
    assert np.abs(np.asarray(ref) - full[0]).max() < 1e-5


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


def _gaps(ref, rows_of, tokens_of):
    """How far each token's reference logit lies under the reference's
    best at its row."""
    out = []
    for i, (rows, toks) in enumerate(zip(rows_of, tokens_of)):
        lg = ref[i, np.arange(len(rows))]
        out += list(lg.max(-1) - lg[np.arange(len(rows)), toks])
    return np.asarray(out)


def _teacher(seqs, rows_of):
    pad = max(len(s) for s in seqs) + 1
    kmax = max(len(r) for r in rows_of)
    ids = np.zeros((len(seqs), pad), np.int64)
    rows = np.zeros((len(seqs), kmax), np.int32)
    for i, (s, r) in enumerate(zip(seqs, rows_of)):
        ids[i, :len(s)] = s
        rows[i, :len(r)] = r
    return ids, rows


def _record_drafts(engine):
    """Spy on every dispatch's apply: ``{request: [(position the draft
    was made at, draft)]}`` (the draft after the last committed token
    ``n - 1`` of ``n`` known tokens is the module's at ``n - 2``)."""
    seen = {}
    apply = engine._apply_rows

    def spy(rows, *a):
        out = apply(rows, *a)
        for r, *_ in rows:
            if not r.done and r._draft_tok is not None:
                n = len(r.prompt_ids) + len(r.output_ids)
                seen.setdefault(id(r), (r, []))[1].append(
                    (n - 2, r._draft_tok))
        return out

    engine._apply_rows = spy
    return seen


def _assert_drafts_follow_the_reference(setup, seen, least=10):
    reqs = [r for r, _ in seen.values()]
    seqs = [list(r.prompt_ids) + list(r.output_ids) for r in reqs]
    rows_of = [sorted(set(at for at, _ in d)) for _, d in seen.values()]
    drafts = [[dict(d)[at] for at in rows] for (_, d), rows
              in zip(seen.values(), rows_of)]
    ids, rows = _teacher(seqs, rows_of)
    gaps = _gaps(ref_mtp(setup, ids, rows), rows_of, drafts)
    assert len(gaps) >= least and gaps.max() < GAP_TOL


#: prompt lengths: 1 to 75 tokens (longer than the window and a chunk
#: budget together, 16 + 32), then a set for the forced drafter, then a
#: repetitive one for the n-gram drafter
LENGTHS = {"module": (1, 75, 23, 60), "forced": (9, 41, 30), "ngram": (12,)}
NEW = {"module": 30, "forced": 40, "ngram": 24}


def _prompts(cfg, which):
    rng = np.random.default_rng(5)
    sets = {k: [list(rng.integers(1, cfg["vocab_size"], n)) for n in ns]
            for k, ns in LENGTHS.items()}
    sets["ngram"] = [p * 3 for p in sets["ngram"]]
    return sets[which]


@pytest.fixture(scope="module")
def plain(setup):
    """Each prompt set's greedy tokens from ONE engine without drafting."""
    cfg, _, model, _ = setup
    e = _engine(model)
    out = {k: [r.output_ids for r in _serve(e, _prompts(cfg, k), NEW[k])]
           for k in LENGTHS}
    e.close()
    return out


#: the drafting engine's query heads a kv head: the rehearsal's 4 (a
#: verify row of two tokens is 8 softmax rows, one tile either way), and
#: the published 8 (64 over 8), where the row's 16 take the float
#: program's small tile only because it is sized for ``1 + spec_k``
GROUPS = pytest.mark.parametrize("setup", [2, 1], indirect=True,
                                 ids=["group4", "group8"])


@pytest.fixture(scope="module")
def drafting(setup):
    """ONE engine whose model's module drafts (``spec_k`` 1)."""
    e = _engine(setup[2], spec_k=1)
    yield e
    e.close()


@GROUPS
def test_engine_serves_the_reference_and_drafts_with_the_module(
        setup, plain, drafting):
    """Prompts in chunks of 8 beside verify rows, then 30 tokens: the
    rings of 7 pages wrap several times. Every served token follows the
    main reference, every draft the MTP reference's logits at the last
    committed position; the greedy tokens are those of the engine
    without drafting, and each dispatch says what it verified."""
    cfg, _, model, _ = setup
    e = drafting
    prompts = _prompts(cfg, "module")
    seen = _record_drafts(e)
    trace.clear()
    before = e.spec_stats()
    reqs = _serve(e, prompts, NEW["module"])
    del e._apply_rows
    outs = [list(r.output_ids) for r in reqs]
    assert outs == plain["module"]
    seqs = [p + o for p, o in zip(prompts, outs)]
    rows_of = [list(range(len(p) - 1, len(p) + 29)) for p in prompts]
    ids, rows = _teacher(seqs, rows_of)
    assert _gaps(ref_logits(setup, ids, rows), rows_of, outs).max() \
        < GAP_TOL
    _assert_drafts_follow_the_reference(setup, seen, least=60)
    # three rings and the whole context a layer, then the module's pool
    hk = cfg["num_key_value_heads"]
    assert e._drafter is model.mtp and e.ring_pages(16) == 7
    assert e._layer_pages == [[0, 1], [2, 3], [4, 5], [6, 7], [8, 9]]
    assert [p.shape for p in e.k_pools] == [[5 * 7, hk, 8, 16]] * 6 \
        + [[65, hk, 8, 16]] * 4
    assert e.kv_bytes_per_token == 2 * 2 * hk * 16 * 4
    assert e.alloc.free_pages == e.alloc.num_pages
    assert e.alloc.double_free_count == 0
    disp = [ev["args"] for ev in trace.get_events()
            if ev["name"] == "serving.dispatch"]
    proposed = e.spec_stats()["proposed"] - before["proposed"]
    accepted = e.spec_stats()["accepted"] - before["accepted"]
    assert proposed > 60
    assert sum(a["spec_proposed"] for a in disp) == proposed
    assert sum(a["spec_accepted"] for a in disp) == accepted
    # every verify row is one the attention kernel computes on its tile
    assert all(a["tile_rows"] >= a["decode_rows"] for a in disp)
    with pytest.raises(UnsupportedServingFeature, match="single steps"):
        e._ensure_scan_compiled(4)


@GROUPS
def test_accepted_and_rejected_drafts_keep_every_pool_exact(
        setup, plain, drafting):
    """A drafter that proposes the reference's own next token on two
    rows in three and a wrong one on the third, with the module still
    drafting in the program: accepted tokens and rolled-back keys through
    rings that wrap, the whole-context pools and the module's pool. The
    tokens are those of the engine without drafting, and the module's
    drafts still follow its reference, which they would not if its pool
    missed a committed position or kept a rejected one."""
    cfg, _, model, _ = setup
    e = drafting
    prompts = _prompts(cfg, "forced")
    oracle = {tuple(p): o for p, o in zip(prompts, plain["forced"])}
    calls = [0]

    def draft(r, kcap):
        if kcap < 1:
            return ()
        calls[0] += 1
        want = oracle[tuple(int(t) for t in r.prompt_ids)][
            len(r.output_ids)]
        return (want,) if calls[0] % 3 \
            else ((want + 1) % cfg["vocab_size"],)

    e._draft = draft
    seen = _record_drafts(e)
    before = e.spec_stats()
    try:
        reqs = _serve(e, prompts, NEW["forced"])
    finally:
        del e._draft, e._apply_rows
    assert [r.output_ids for r in reqs] == plain["forced"]
    accepted = e.spec_stats()["accepted"] - before["accepted"]
    proposed = e.spec_stats()["proposed"] - before["proposed"]
    assert 0 < accepted < proposed
    _assert_drafts_follow_the_reference(setup, seen)
    assert e.alloc.free_pages == e.alloc.num_pages
    assert e.alloc.double_free_count == 0


def test_an_ngram_drafter_given_wins_over_the_module(setup, plain):
    """A ``drafter_factory`` is used as given: no module pool is built,
    and the tokens are the plain engine's."""
    cfg, _, model, _ = setup
    from paddle_tpu.inference.speculative import NGramDrafter
    e = _engine(model, spec_k=2, drafter_factory=lambda: NGramDrafter(3))
    assert e._drafter is None and len(e.k_pools) == 8
    reqs = _serve(e, _prompts(cfg, "ngram"), NEW["ngram"])
    assert [r.output_ids for r in reqs] == plain["ngram"]
    assert e.spec_stats()["proposed"] > 0


@pytest.mark.parametrize("kw,what", [
    (dict(prefix_cache=True), "prefix_cache"),
    (dict(kv_dtype="int8"), "kv_dtype=int8"),
    (dict(kv_tier=True), "kv_tier"),
    (dict(weight_dtype="int8"), "weight_dtype=int8"),
])
def test_features_that_reach_no_ring_are_refused(setup, kw, what):
    with pytest.raises(UnsupportedServingFeature, match=what):
        _engine(setup[2], **kw)


def test_tiny_config_checks_its_lists():
    paddle.seed(0)
    m = ExaoneMoeForCausalLM(tiny_exaone_moe_config())
    assert m.num_params() == sum(int(np.prod(p.shape))
                                 for p in m.parameters())
    assert m.config.sliding_windows == (16, 16, 16, 0)
    with pytest.raises(ValueError, match="layer_types"):
        tiny_exaone_moe_config(layer_types=(W, F))
    with pytest.raises(ValueError, match="sliding_windows"):
        tiny_exaone_moe_config(sliding_windows=(16, 16, 16, 16))
    assert ExaoneMoeForCausalLM(tiny_exaone_moe_config(
        num_nextn_predict_layers=0)).serving_drafter() is None


def test_configuration_keeps_every_published_key():
    """Every number of the published config (the catalog's copy) under
    its own key, the cut ones listed in ``reduced`` with their published
    values; the family's hand figures hold."""
    import json
    cfg = bench.load_json("perfbench", "configs", NAME + ".json")
    published = {
        "first_k_dense_replace": 1, "head_dim": 128, "hidden_act": "silu",
        "hidden_size": 6144, "intermediate_size": 18432,
        "max_position_embeddings": 262144, "model_type": "exaone_moe",
        "moe_intermediate_size": 2048, "mtp_layer_types": [F],
        "mtp_sliding_windows": [0], "n_group": 1, "norm_topk_prob": True,
        "num_attention_heads": 64, "num_experts_per_tok": 8,
        "num_key_value_heads": 8, "num_nextn_predict_layers": 1,
        "num_shared_experts": 1, "rms_norm_eps": 1e-05,
        "rope_parameters": {"rope_theta": 1000000, "rope_type": "default"},
        "routed_scaling_factor": 2.5, "scoring_func": "sigmoid",
        "sliding_window": 128, "sliding_window_pattern": "LLLG",
        "tie_word_embeddings": False, "topk_group": 1}
    assert {k: cfg[k] for k in published} == published
    assert cfg["num_hidden_layers"] == 8
    assert cfg["layer_types"] == ([W] * 3 + [F]) * 2
    assert cfg["mlp_layer_types"] == ["dense"] + ["sparse"] * 7
    assert cfg["sliding_windows"] == [128, 128, 128, 0] * 2
    assert (cfg["num_experts"], cfg["ep_size"], cfg["ep_rank"],
            cfg["vocab_size"]) == (8, 16, 0, 19200)
    red = cfg["reduced"]
    assert sorted(red) == sorted(["num_hidden_layers", "layer_types",
                                  "mlp_layer_types", "sliding_windows",
                                  "num_experts", "vocab_size"])
    assert (red["num_hidden_layers"]["published"],
            red["num_experts"]["published"],
            red["vocab_size"]["published"]) == (48, 128, 153600)
    assert "16 chips share each layer" in cfg["deployment"]
    for key in ("qk_norm", "rope", "block", "router", "correction_bias",
                "mtp", "weights"):
        assert key in cfg["assumed"], key
    spec = bench.load_json("BENCHMARK.json")
    entry = next(c for c in spec["configs"] if c["name"] == NAME)
    assert sorted(entry["reduced"]) == sorted(red)
    fam = family.load(cfg, NAME)
    fam.selfcheck()
    assert fam.total_params(cfg) == 4394714368 + 6144
    assert fam.kinds(cfg) == {"window": 6, "full": 3}
    # nothing of the program is imported by the reference
    with open(family.path_of("exaone_moe")) as f:
        body = f.read().split("# the plain reference")[1]
    assert "paddle_tpu" not in body
    assert json.dumps(cfg)          # plain data


STEP = "jit(pure_step)/"


class _Run:
    """A traced run of two steps whose device ops are ``ops`` ([HLO
    text, start ns, duration ns, scope])."""

    def __init__(self, ops):
        mods = [["jit_pure_step(7)", i * 1000, 900] for i in range(2)]
        trace = {"planes": [{"name": "/device:TPU:0", "lines": [
            {"name": "XLA Modules", "events": mods},
            {"name": "XLA Ops", "events": [o[:3] for o in ops]}]}]}
        self.facts = {"trace": trace, "window_ns": (0, 2000),
                      "step_pattern": r"^jit_pure_step", "device_ops": ops}
        self.notes, self.trace_dir = [], None

    def note(self, **kw):
        self.notes.append(kw)


def test_the_new_readers_read_the_scope_and_the_counters():
    """``mtp_ms.tok``: device ms a step under ``paddle_tpu.mtp`` (the
    module's attention kernel among them); ``spec_accept_share.tok``:
    accepted over proposed on the window's dispatches; nothing from a
    program without the scope or the counters."""
    ops = [["%a = fusion()", 0, 300, STEP + "paddle_tpu.mtp/dot_general"],
           ["%b = custom-call()", 300, 200, STEP + "paddle_tpu.mtp/"
            "paddle_tpu.attn/paddle_tpu.attn_full/jit(call)"],
           ["%c = fusion()", 500, 400, STEP + "paddle_tpu.mlp/dot_general"]]
    mtp = bench.load_reader("mtp_ms.tok")
    assert mtp.read(_Run(ops)) == pytest.approx(1e3 * 500e-9 / 2)
    assert mtp.read(_Run(ops[2:])) is None

    class _Spans:
        def __init__(self, disp):
            self.facts = {"spans": {"host": [
                ("serving.dispatch", 10 * i, 5, a)
                for i, a in enumerate(disp)]}}

    share = bench.load_reader("spec_accept_share.tok")
    disp = [{"spec_proposed": 60, "spec_accepted": 3},
            {"spec_proposed": 40, "spec_accepted": 2}, {"rows": 4}]
    assert share.read(_Spans(disp)) == pytest.approx(5.0)
    assert share.read(_Spans([{"rows": 4}])) is None
    assert share.read(_Spans([{"spec_proposed": 0,
                               "spec_accepted": 0}])) is None
