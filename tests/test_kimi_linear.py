"""The linear-attention / latent-attention expert model
(``models/kimi_linear.py``: gated delta-rule layers that keep a matrix
state a sequence slot, NoPE latent-attention layers that keep pages,
expert layers that hold a share of their experts) against the plain
reference of its benchmark family (``perfbench/families/kda_mla_moe.py``:
float32, a token-by-token scan, dense attention with nothing absorbed,
the held experts one at a time, nothing of the program imported), on
seeded weights at the configuration's rehearsal sizes (5 layers: KDA with
the dense FFN, KDA, KDA, latent, KDA; 8 experts routed, 4 held); then the
same model through ``LlamaServingEngine``: states by slot, latent pages
behind the block tables, the expert share.

Tolerances: program and reference are both float32 here and differ in
the order of their sums (the chunkwise form against one scan, the
absorbed attention through the paged program against dense heads, packed
expert rows against one expert at a time), so logits agree to 2e-4 of a
logit range of a few units, a layer's output to 2e-4 of its largest
value, states to 1e-4 of their largest value; served tokens are held to
the reference's logits (the served token's logit at most 1e-3 under the
reference's best: a near-tie may fall either way), never to its tokens.
A state kept in bfloat16 or a gate left out moves logits by 1e-2 and
more (``test_a_dropped_gate_...``)."""

import os
import sys

import jax.numpy as jnp
import numpy as np
import pytest

import paddle_tpu as paddle
from paddle_tpu.inference.serving import (LlamaServingEngine, Request,
                                          UnsupportedServingFeature)
from paddle_tpu.models import (KimiLinearForCausalLM, MlaMoeMLP,
                               tiny_kimi_linear_config)
from paddle_tpu.observability import trace

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
for p in (ROOT, os.path.join(ROOT, "perfbench")):
    if p not in sys.path:
        sys.path.insert(0, p)

import run as bench                       # noqa: E402
from harness import family, program       # noqa: E402

SEED = 3000000029
LOGIT_TOL = 2e-4
GAP_TOL = 1e-3
STATE_TOL = 1e-4
KINDS = ["kda", "kda", "kda", "mla", "kda"]
NAME = "kimi-linear-48b-a3b-ep16"


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
    """70 tokens: more than one chunk of the chunkwise form."""
    cfg, _, model, _ = setup
    ids = np.random.default_rng(1).integers(0, cfg["vocab_size"], (2, 70))
    with paddle.no_grad():
        got = np.asarray(model(paddle.to_tensor(ids))._data)
    rows = np.tile(np.arange(70), (2, 1))
    want = ref_logits(setup, ids, rows)
    assert np.abs(got - want).max() < LOGIT_TOL
    # the int8 control is a different model, by far more than that
    assert np.abs(ref_logits(setup, ids, rows, "int8") - want).max() \
        > 10 * LOGIT_TOL


def test_every_kind_of_layer_is_there(setup):
    cfg, fam, model, _ = setup
    assert [layer.kind for layer in model.model.layers] == KINDS
    assert [("kda" if fam.is_kda(cfg, i) else "mla")
            for i in range(5)] == KINDS
    assert [layer.is_moe for layer in model.model.layers] \
        == [False, True, True, True, True]
    mlp = model.model.layers[1].mlp
    # ep_rank 1 of 2: experts 4..7 of the router's 8
    assert (mlp.num_experts, mlp.held, mlp.first) == (8, 4, 4)
    assert mlp.experts_gate.shape[0] == 4 and mlp.router.shape[1] == 8


@pytest.mark.parametrize("index", range(5), ids=[
    f"{i}-{k}" for i, k in enumerate(KINDS)])
def test_each_layer_alone_equals_its_reference_layer(setup, index):
    cfg, fam, model, w = setup
    rng = np.random.default_rng(10 + index)
    t = 40
    x = rng.normal(size=(t, cfg["hidden_size"])).astype(np.float32)
    want = np.asarray(fam.layer_forward(jnp.asarray(x), w["layers"][index],
                                        cfg, index, None))
    with paddle.no_grad():
        got = np.asarray(model.model.layers[index](
            paddle.to_tensor(x[None]))._data)[0]
    assert np.abs(got - want).max() < LOGIT_TOL * max(1.0, np.abs(want).max())


def test_a_dropped_gate_or_a_bf16_state_fails_the_tolerance(setup):
    """What the tolerances are tight enough to see: the KDA layers'
    decay left out (``A_log`` so low that ``g`` is 0), and the served
    state rounded to bfloat16 between a prompt's chunks."""
    cfg, _, model, w = setup
    ids = np.random.default_rng(2).integers(0, cfg["vocab_size"], (1, 48))
    rows = np.arange(48)[None]
    want = ref_logits(setup, ids, rows)
    mixer = model.model.layers[1].mixer
    kept = mixer.A_log._data
    mixer.A_log._data = jnp.full_like(kept, -40.0)
    try:
        with paddle.no_grad():
            got = np.asarray(model(paddle.to_tensor(ids))._data)
    finally:
        mixer.A_log._data = kept
    assert np.abs(got - want).max() > 10 * LOGIT_TOL
    # the state through bfloat16 after the first of two chunks
    e = _engine(model, chunk_block=32, chunk_budget=64)
    r = Request(list(ids[0]), max_new_tokens=2)
    real = e._run_mixed
    seen = []

    def rounding(buf):
        out = real(buf)
        if e._dispatch_count == 1:          # after the first chunk
            for i in (0, 2, 4, 7):          # the four state pools
                p = e.k_pools[i]
                p._data = p._data.astype(jnp.bfloat16).astype(jnp.float32)
        seen.append(1)
        return out

    e._run_mixed = rounding
    e.add_request(r)
    while not r.done:
        e.step()
    assert len(seen) >= 2
    rounded = [np.asarray(e.k_pools[i]._data[0]) for i in (0, 2, 4, 7)]
    clean = _engine(model, chunk_block=32, chunk_budget=64)
    r2 = Request(list(ids[0]), max_new_tokens=2)
    clean.add_request(r2)
    while not r2.done:
        clean.step()
    exact = [np.asarray(clean.k_pools[i]._data[0]) for i in (0, 2, 4, 7)]
    # (both sequences sat in slot 0 and left their last state there)
    assert max(np.abs(a - b).max() / np.abs(b).max()
               for a, b in zip(rounded, exact)) > 10 * STATE_TOL


# ---------------------------------------------------------------------------
# the expert share
# ---------------------------------------------------------------------------
def test_the_shares_add_up_to_the_whole_layer(setup):
    """The routed parts of every share (``ep_rank`` 0 .. ``ep_size`` -
    1) plus the shared expert counted once equal the uncut `MlaMoeMLP`,
    and equal the reference's whole layer."""
    cfg, fam, model, w = setup
    conf = model.config
    rng = np.random.default_rng(3)
    x = rng.normal(size=(1, 37, conf.hidden_size)).astype(np.float32)
    paddle.seed(5)
    whole = MlaMoeMLP(conf)
    whole.router_bias._data = jnp.asarray(
        0.02 * rng.normal(size=(conf.n_routed_experts,)), jnp.float32)
    shares = [MlaMoeMLP(conf, experts_held=4, first_expert=4 * r)
              for r in range(2)]
    for r, sh in enumerate(shares):
        for name in ("router", "router_bias"):
            getattr(sh, name)._data = getattr(whole, name)._data
        for name in ("experts_gate", "experts_up", "experts_down"):
            getattr(sh, name)._data = getattr(whole, name)._data[
                4 * r:4 * r + 4]
        for name in ("gate_proj", "up_proj", "down_proj"):
            getattr(sh.shared, name).weight._data = \
                getattr(whole.shared, name).weight._data
    xt = paddle.to_tensor(x)
    with paddle.no_grad():
        full = np.asarray(whole(xt)._data)
        shared = np.asarray(whole.shared(xt)._data)
        parts = [np.asarray(sh(xt)._data) - shared for sh in shares]
        stats = [np.asarray(sh.last_stats._data) for sh in shares]
    assert np.abs(sum(parts) + shared - full).max() < 1e-5
    assert all(np.abs(p).max() > 1e-3 for p in parts)
    # a share counts the experts it holds
    assert all(0 < s[0] <= 4 for s in stats)
    # ... and the reference, told it holds all eight, gives the whole
    lw = {"router": whole.router._data,
          "router_bias": whole.router_bias._data,
          "shared_gate": whole.shared.gate_proj.weight._data,
          "shared_up": whole.shared.up_proj.weight._data,
          "shared_down": whole.shared.down_proj.weight._data,
          "experts_gate": whole.experts_gate._data,
          "experts_up": whole.experts_up._data,
          "experts_down": whole.experts_down._data}
    uncut = dict(cfg, num_experts=8, ep_size=1, ep_rank=0)
    ref = np.asarray(fam.moe_ffn(jnp.asarray(x[0]), lw, uncut, None))
    assert np.abs(ref - full[0]).max() < 1e-5
    # and each share's reference is that share's program
    for r, sh in enumerate(shares):
        cut = dict(cfg, num_experts=4, ep_size=2, ep_rank=r)
        part = dict(lw, **{n: lw[n][4 * r:4 * r + 4] for n in (
            "experts_gate", "experts_up", "experts_down")})
        ref = np.asarray(fam.moe_ffn(jnp.asarray(x[0]), part, cut, None))
        assert np.abs(ref - (parts[r] + shared)[0]).max() < 1e-5
    with pytest.raises(ValueError, match="router"):
        MlaMoeMLP(conf, experts_held=4, first_expert=6)


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


def test_engine_serves_the_reference_through_slots_and_latent_pages(setup):
    """Four prompts of 1 to 45 tokens in chunks of 8 (the 45-token one
    takes six dispatches, beside the others' decode rows: rows of 1 and
    of 8 tokens in one dispatch, two sequences of unequal length sharing
    them), then 30 decoded tokens."""
    cfg, _, model, _ = setup
    rng = np.random.default_rng(5)
    prompts = [list(rng.integers(1, cfg["vocab_size"], n))
               for n in (1, 45, 23, 30)]
    e = _engine(model)
    reqs = _serve(e, prompts, 30)
    outs = [r.output_ids for r in reqs]
    assert all(len(o) == 30 for o in outs)
    assert _gaps(setup, prompts, outs).max() < GAP_TOL
    # two state pools a KDA layer (4), one latent pool
    assert len(e.k_pools) == 4 * 2 + 1 and e.v_pools == []
    assert e._layer_pages == [[0, 1], [2, 3], [4, 5], [6], [7, 8]]
    assert e.k_pools[0].shape == [5, 4, 16, 16] \
        and e.k_pools[1].shape == [5, 3, 3 * 64]
    assert e.k_pools[6].shape == [65, 8, 128]
    assert str(e.k_pools[0]._data.dtype) == "float32"
    # only the latent pool grows with the context: one padded row
    assert e.kv_bytes_per_token == 128 * 4
    # every page and every slot went back
    assert e.alloc.free_pages == e.alloc.num_pages
    assert e.alloc.slots_held == 0


def test_logits_through_the_engine_equal_the_forward(setup):
    """Prefill then decode through slot states and latent pages against
    the model's own forward: the served token is the forward's argmax
    wherever the forward's best two are further apart than the
    tolerance."""
    cfg, _, model, _ = setup
    rng = np.random.default_rng(6)
    prompts = [list(rng.integers(1, cfg["vocab_size"], n)) for n in (19, 7)]
    reqs = _serve(_engine(model), prompts, 20)
    for p, r in zip(prompts, reqs):
        ids = np.asarray([p + list(r.output_ids)])
        with paddle.no_grad():
            lg = np.asarray(model(paddle.to_tensor(ids))._data)[0]
        at = np.arange(len(p) - 1, len(p) - 1 + len(r.output_ids))
        took = lg[at, np.asarray(r.output_ids)]
        assert (lg[at].max(-1) - took).max() < GAP_TOL


def test_an_evicted_sequence_is_admitted_again_from_its_first_token(setup):
    """Evict a sequence mid-decode: its slot and pages go back, it is
    prefilled again from token 0 into a slot whose state another
    sequence may have left, and the tokens still are the reference's."""
    cfg, _, model, _ = setup
    rng = np.random.default_rng(7)
    prompts = [list(rng.integers(1, cfg["vocab_size"], n)) for n in (21, 12)]
    e = _engine(model)
    reqs = [Request(list(p), max_new_tokens=24) for p in prompts]
    for r in reqs:
        e.add_request(r)
    for _ in range(8):
        e.step()
    victim = reqs[0]
    assert not victim.done and len(victim.output_ids) > 4
    slot = e.alloc.slot_of(victim.seq_id)
    e._evict(victim)
    assert victim.status == "requeued" and e.alloc.slots_held == 1
    for _ in range(600):
        if all(r.done for r in reqs):
            break
        e.step()
    assert all(r.status == "completed" for r in reqs)
    assert slot in (0, 1) and len(reqs[0].output_ids) == 24
    assert _gaps(setup, prompts, [r.output_ids for r in reqs]).max() \
        < GAP_TOL
    assert e.alloc.slots_held == 0


def test_state_after_chunked_prefill_equals_one_pass(setup):
    """A 37-token prompt in five chunks of 8 against one chunk of 64:
    the same matrix states, conv inputs and first token."""
    cfg, _, model, _ = setup
    prompt = list(np.random.default_rng(8).integers(
        1, cfg["vocab_size"], 37))
    states = []
    for block in (8, 64):
        e = _engine(model, chunk_block=block, chunk_budget=64)
        r = Request(prompt, max_new_tokens=2)
        e.add_request(r)                   # prefills to the first token
        slot = e.alloc.slot_of(r.seq_id)
        states.append([np.asarray(e.k_pools[i]._data[slot])
                       for i in (0, 1, 2, 3, 4, 5, 7, 8)]
                      + [r.output_ids[0]])
    for chunked, whole in zip(*states):
        assert np.abs(chunked - whole).max() \
            <= STATE_TOL * np.abs(whole).max()
    assert all(np.abs(a).max() > 0 for a in states[1])


def test_dispatch_counters_follow_the_allocator_and_the_routing(setup):
    """``state_slots``, ``latent_rows``, ``state_bytes`` against the
    allocator and the rows, ``experts_touched`` / ``expert_rows_max`` (of
    the HELD experts) against what the family's plain reference routes
    the dispatch's own tokens to."""
    cfg, fam, model, w = setup
    d = fam.dims(cfg)
    e = _engine(model)
    seen = {}
    rows_of = e._dispatch_rows

    def spy(rows, cow):
        seen[e._dispatch_count - 1] = [(r, start, n)
                                       for r, _, start, n, _, _ in rows]
        return rows_of(rows, cow)

    held, counters = [], e._slot_counters

    def spy_slots(rows):
        held.append(e.alloc.slots_held)     # after the rows were applied
        return counters(rows)

    e._dispatch_rows, e._slot_counters = spy, spy_slots
    rng = np.random.default_rng(9)
    reqs = [Request(list(rng.integers(1, cfg["vocab_size"], n)),
                    max_new_tokens=6) for n in (40, 5, 23)]
    trace.clear()
    for r in reqs:
        e.add_request(r)
    while any(not r.done for r in reqs):
        e.step()

    def routing(ids):
        """[expert layers, T, k] of one sequence, by the reference."""
        x = jnp.take(w["ends"]["embed"], jnp.asarray(ids), axis=0) \
            .astype(jnp.float32)
        out = []
        for i in range(cfg["num_hidden_layers"]):
            lw = w["layers"][i]
            if not fam.is_dense(cfg, i):
                y = fam._normed(fam.after_mixer(x, lw, cfg, i, None),
                                lw["ln2"], float(cfg["rms_norm_eps"]))
                out.append(np.asarray(fam.route(
                    y, lw["router"], lw["router_bias"], d["k"],
                    float(cfg["routed_scaling_factor"]),
                    bool(cfg["moe_renormalize"]))[0]))
            x = fam.layer_forward(x, lw, cfg, i, None)
        return np.stack(out)

    routed = {id(r): routing(list(r.prompt_ids) + list(r.output_ids))
              for r in reqs}
    disp = [ev for ev in trace.get_events()
            if ev["name"] == "serving.dispatch"]
    assert {ev["args"]["kind"] for ev in disp} == {"mixed", "decode"}
    # 4 KDA layers x (4 x 16 x 16 float32 + 3 x 192 float32), in and out
    slot_bytes = 4 * (4 * 16 * 16 * 4 + 3 * 192 * 4)
    assert e._slot_bytes == slot_bytes
    assert len(held) == len(disp)
    for ev, slots in zip(disp, held):
        a = ev["args"]
        rows = seen[a["step"]]
        assert a["state_slots"] == slots <= 3
        assert a["state_bytes"] == 2 * len(rows) * slot_bytes
        assert a["latent_rows"] == sum(start + n for _, start, n in rows)
        per_layer = []
        for layer in range(4):
            ids = np.concatenate([
                routed[id(r)][layer, start:start + n].reshape(-1)
                for r, start, n in rows]) - d["first"]
            counts = np.bincount(ids[(ids >= 0) & (ids < d["held"])],
                                 minlength=d["held"])
            per_layer.append([(counts > 0).sum(), counts.max()])
        med = np.median(np.asarray(per_layer), axis=0)
        assert a["experts_touched"] == med[0] <= d["held"]
        assert a["expert_rows_max"] == med[1]
        assert "tile_rows" not in a and a["window_pages"] == 0
    e.close()


@pytest.mark.parametrize("kw,what", [
    (dict(prefix_cache=True), "prefix_cache"),
    (dict(kv_dtype="int8"), "kv_dtype=int8"),
    (dict(kv_tier=True), "kv_tier"),
    (dict(spec_k=2), "spec_k"),
    (dict(weight_dtype="int8"), "weight_dtype=int8"),
])
def test_features_that_reach_no_matrix_state_are_refused(setup, kw, what):
    with pytest.raises(UnsupportedServingFeature, match=what):
        _engine(setup[2], **kw)


def test_prefix_cache_is_off_and_the_scan_refused(setup):
    e = _engine(setup[2])
    assert e.prefix is None and e.chunk_rows == 4
    with pytest.raises(UnsupportedServingFeature, match="decode scan"):
        e._ensure_scan_compiled(4)


def test_generate_steps_and_matches_the_forward(setup):
    cfg, _, model, _ = setup
    ids = np.random.default_rng(11).integers(1, cfg["vocab_size"], (2, 21))
    out = np.asarray(model.generate(paddle.to_tensor(ids),
                                    max_new_tokens=3)._data)
    assert out.shape == (2, 24) and (out[:, :21] == ids).all()
    with paddle.no_grad():
        lg = np.asarray(model(paddle.to_tensor(out[:, :-1]))._data)
    assert (lg[:, 20:].argmax(-1) == out[:, 21:]).all()


def test_tiny_config_builds_and_the_lists_name_every_layer():
    paddle.seed(0)
    m = KimiLinearForCausalLM(tiny_kimi_linear_config())
    assert m.num_params() == sum(int(np.prod(p.shape))
                                 for p in m.parameters())
    assert [layer.kind for layer in m.model.layers] == KINDS
    with pytest.raises(ValueError, match="every layer once"):
        tiny_kimi_linear_config(full_attn_layers=(3, 4))


def test_a_subprocess_replica_can_be_told_to_build_it():
    from paddle_tpu.inference.replica_worker import _build_model
    m = _build_model({"kind": "tiny_kimi_linear", "seed": 0,
                      "config": {"experts_held": 4, "first_expert": 2}})
    assert isinstance(m, KimiLinearForCausalLM) and not m.training
    assert m.model.layers[1].mlp.first == 2


def test_configuration_keeps_every_published_key():
    import json
    cfg = bench.load_json("perfbench", "configs", NAME + ".json")
    published = {
        "first_k_dense_replace": 1, "head_dim": 72, "hidden_act": "silu",
        "hidden_size": 2304, "intermediate_size": 9216,
        "kv_lora_rank": 512, "mla_use_nope": True,
        "model_max_length": 1048576, "model_type": "kimi_linear",
        "moe_intermediate_size": 1024, "moe_layer_freq": 1,
        "moe_renormalize": True, "moe_router_activation_func": "sigmoid",
        "num_attention_heads": 32, "num_expert_group": 1,
        "num_experts_per_token": 8, "num_hidden_layers": 27,
        "num_key_value_heads": 32, "num_nextn_predict_layers": 0,
        "num_shared_experts": 1, "q_lora_rank": None,
        "qk_nope_head_dim": 128, "qk_rope_head_dim": 64,
        "rms_norm_eps": 1e-05, "rope_scaling": None, "rope_theta": 10000,
        "routed_scaling_factor": 2.446, "tie_word_embeddings": False,
        "topk_group": 1, "use_grouped_topk": True, "v_head_dim": 128,
        "vocab_size": 163840}
    assert {k: cfg[k] for k in published} == published
    lin = cfg["linear_attn_config"]
    assert (lin["head_dim"], lin["num_heads"],
            lin["short_conv_kernel_size"]) == (128, 32, 4)
    assert lin["full_attn_layers"] == [4, 8, 12, 16, 20, 24, 27]
    assert sorted(lin["kda_layers"] + lin["full_attn_layers"]) \
        == list(range(1, 28))
    assert sorted(cfg["reduced"]) == ["num_experts"]
    assert cfg["reduced"]["num_experts"]["published"] == 256
    assert (cfg["num_experts"], cfg["ep_size"], cfg["ep_rank"]) \
        == (16, 16, 0)
    assert cfg["family"] == "kda_mla_moe" and "16" in cfg["deployment"]
    for key in ("kda", "weights", "state_precision", "latent_attention",
                "router", "head_dim", "initializer_range"):
        assert key in cfg["assumed"], key
    spec = bench.load_json("BENCHMARK.json")
    entry = next(c for c in spec["configs"] if c["name"] == NAME)
    assert entry["reduced"] == ["num_experts"]
    fam = family.load(cfg, NAME)
    fam.selfcheck()
    assert fam.total_params(cfg) == 4956660608
    # nothing of the program is imported by the reference
    with open(family.path_of("kda_mla_moe")) as f:
        body = f.read().split("# the plain reference")[1]
    assert "paddle_tpu" not in body
    assert json.dumps(cfg)          # plain data
