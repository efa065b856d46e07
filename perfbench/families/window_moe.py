"""The window / full GQA expert family (the Mellum 2 layout, here
Mellum2-12B-A2.5B-Instruct): pre-norm RMSNorm; every layer GQA attention
(no bias, no q/k norm) over a window of ``sliding_window`` keys with the
default rope, or over the whole context with YaRN's rope, by the
configuration's ``layer_types``; every layer's FFN softmax-routed experts
(the top k of the softmax over all experts, weights divided by their sum)
and no shared expert; untied head. Everything the harness knows of it is
here, behind the interface that ``harness/family.py`` lists.

Leaves (matrices [in, out]): ``embed`` [V, H], ``head`` [H, V], ``norm``
[H]; a layer has ``ln1 ln2`` [H], ``q`` [H, Hq d], ``k v`` [H, Hk d],
``o`` [Hq d, H], ``router`` [H, E] and ``experts_gate experts_up`` [E, H,
F], ``experts_down`` [E, F, H].

The reference is the model's equations in straightforward ``jax.numpy``,
float32, matmuls at ``highest``: its own YaRN frequencies from the
configuration's ``rope_parameters``, dense masked attention a block of
queries at a time (keys repeated over the query heads they serve), the
experts a group at a time (each token gathered to the experts that chose
it), no cache, no batching; nothing of the program is imported.
``quant="int8"`` is the control: every linear layer through
``harness/reference.py:mm`` in int8 (the router, the rope and the
softmaxes stay float32). Departures from the published code: none known;
what the published config does not say is listed under ``assumed`` in the
configuration."""

import functools
import json
import math
import os

import jax
import jax.numpy as jnp
import numpy as np

from harness import weights
from harness.costs import causal_pairs
from harness.reference import HIGHEST, head_logits, mm, rmsnorm
from harness.selfcheck import near

HERE = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
WINDOW, FULL = "sliding_attention", "full_attention"


# ---------------------------------------------------------------------------
# the program's side
# ---------------------------------------------------------------------------
def yarn_of(cfg):
    """The full layers' YaRN as the program takes it: ``(factor,
    original_max_position_embeddings, beta_fast, beta_slow,
    attention_factor)``."""
    y = cfg["rope_parameters"][FULL]
    return (float(y["factor"]), int(y["original_max_position_embeddings"]),
            float(y["beta_fast"]), float(y["beta_slow"]),
            float(y["attention_factor"]))


def build_model(cfg, dtype):
    """The program's own constructor."""
    import paddle_tpu as paddle
    from paddle_tpu.models import MellumConfig, MellumForCausalLM
    only = {"hidden_act": "silu", "attention_bias": False,
            "tie_word_embeddings": False, "use_sliding_window": True,
            "max_window_layers": 0}
    for k, v in only.items():
        if cfg.get(k, v) != v:
            raise ValueError(f"family window_moe runs {k}={v!r} only, the "
                             f"configuration gives {cfg[k]!r}")
    rp = cfg["rope_parameters"]
    if rp[FULL]["rope_type"] != "yarn" or rp[WINDOW]["rope_type"] \
            != "default" or rp[FULL]["rope_theta"] != rp[WINDOW]["rope_theta"]:
        raise ValueError("family window_moe runs YaRN on the full layers "
                         "and the default rope on the window layers, one "
                         "theta")
    if set(cfg["mlp_layer_types"]) != {"sparse"}:
        raise ValueError("family window_moe runs an expert FFN in every "
                         "layer")
    paddle.set_default_dtype(dtype)
    try:
        model = MellumForCausalLM(MellumConfig(
            vocab_size=cfg["vocab_size"], hidden_size=cfg["hidden_size"],
            intermediate_size=cfg["intermediate_size"],
            moe_intermediate_size=cfg["moe_intermediate_size"],
            num_hidden_layers=cfg["num_hidden_layers"],
            num_attention_heads=cfg["num_attention_heads"],
            num_key_value_heads=cfg["num_key_value_heads"],
            head_dim=cfg["head_dim"], layer_types=tuple(cfg["layer_types"]),
            sliding_window=cfg["sliding_window"],
            n_routed_experts=cfg["num_experts"], n_shared_experts=0,
            num_experts_per_tok=cfg["num_experts_per_tok"],
            norm_topk_prob=bool(cfg["norm_topk_prob"]),
            max_position_embeddings=cfg["max_position_embeddings"],
            rms_norm_eps=float(cfg["rms_norm_eps"]),
            rope_theta=float(rp[WINDOW]["rope_theta"]), yarn=yarn_of(cfg)))
    finally:
        paddle.set_default_dtype("float32")
    return model


def leaves(model, cfg):
    """``{benchmark leaf name: the program's parameter}``."""
    out = {"embed": model.model.embed_tokens.weight,
           "head": model.lm_head.weight, "norm": model.model.norm.weight}
    for i, layer in enumerate(model.model.layers):
        a, m = layer.self_attn, layer.mlp
        own = {"ln1": layer.input_layernorm.weight,
               "ln2": layer.post_attention_layernorm.weight,
               "q": a.q_proj.weight, "k": a.k_proj.weight,
               "v": a.v_proj.weight, "o": a.o_proj.weight,
               "router": m.router, "experts_gate": m.experts_gate,
               "experts_up": m.experts_up, "experts_down": m.experts_down}
        out.update({f"layers.{i}.{k}": p for k, p in own.items()})
    return out


def engine(model, mix):
    """The serving engine as the mix sizes it, its two step shapes (the
    chunk budget and the decode batch) warm."""
    from paddle_tpu.inference.serving import LlamaServingEngine
    e = LlamaServingEngine(model, **mix["engine"])
    e.prewarm(mixed=[e.chunk_budget, e.max_batch])
    return e


def release(engine):
    """Free the rings and the whole-context pools: the reference runs
    beside the weights alone."""
    engine.k_pools = engine.v_pools = None


# ---------------------------------------------------------------------------
# seeded weights
# ---------------------------------------------------------------------------
def layer_count(cfg):
    return cfg["num_hidden_layers"]


def dims(cfg):
    return {"h": cfg["hidden_size"], "hq": cfg["num_attention_heads"],
            "hk": cfg["num_key_value_heads"], "d": cfg["head_dim"],
            "f": cfg["moe_intermediate_size"], "e": cfg["num_experts"],
            "k": cfg["num_experts_per_tok"], "w": cfg["sliding_window"],
            "v": cfg["vocab_size"]}


def layer_shapes(cfg, index):
    """``(normal leaves std 0.02, leaves that are 1)`` of layer
    ``index``: name -> shape (every layer alike)."""
    d = dims(cfg)
    h, qd, kd = d["h"], d["hq"] * d["d"], d["hk"] * d["d"]
    normal = {"q": (h, qd), "k": (h, kd), "v": (h, kd), "o": (qd, h),
              "router": (h, d["e"]), "experts_gate": (d["e"], h, d["f"]),
              "experts_up": (d["e"], h, d["f"]),
              "experts_down": (d["e"], d["f"], h)}
    return normal, {"ln1": (h,), "ln2": (h,)}


def _frozen(shapes):
    return tuple(sorted(shapes.items()))


@functools.partial(jax.jit, static_argnames=("normal", "ones", "dtype"))
def _layer(key, normal, ones, dtype):
    out = weights.normal_leaves(key, normal, dtype)
    for name, shape in ones:
        out[name] = jnp.ones(shape, dtype)
    return out


@functools.partial(jax.jit, static_argnames=("vocab", "hidden", "dtype"))
def _ends(key, vocab, hidden, dtype):
    k1, k2 = jax.random.split(key)
    return {"embed": weights.normal(k1, (vocab, hidden), dtype),
            "head": weights.normal(k2, (hidden, vocab), dtype),
            "norm": jnp.ones((hidden,), dtype)}


def layer(cfg, seed, index, dtype):
    return _layer(jax.random.fold_in(weights.key_of(seed), 1 + index),
                  *(_frozen(s) for s in layer_shapes(cfg, index)),
                  jnp.dtype(dtype))


def ends(cfg, seed, dtype):
    return _ends(jax.random.fold_in(weights.key_of(seed), 0),
                 cfg["vocab_size"], cfg["hidden_size"], jnp.dtype(dtype))


# ---------------------------------------------------------------------------
# operations and bytes
# ---------------------------------------------------------------------------
def layer_params(cfg, index):
    return sum(int(np.prod(s)) for group in layer_shapes(cfg, index)
               for s in group.values())


def total_params(cfg):
    return sum(layer_params(cfg, i) for i in range(cfg["num_hidden_layers"])) \
        + 2 * cfg["vocab_size"] * cfg["hidden_size"] + cfg["hidden_size"]


def kinds(cfg):
    """How many layers of each attention kind: ``{"window": .., "full":
    ..}``."""
    types = cfg["layer_types"]
    return {"window": types.count(WINDOW), "full": types.count(FULL)}


def expert_params(cfg):
    """One layer's routed experts."""
    d = dims(cfg)
    return d["e"] * 3 * d["h"] * d["f"]


def streamed_params(cfg):
    """Parameters every executed step reads whatever it carries: all but
    the embedding table (a gather), the routed experts (counted by the
    experts a step touches) and the final norm."""
    return total_params(cfg) - cfg["vocab_size"] * cfg["hidden_size"] \
        - cfg["num_hidden_layers"] * expert_params(cfg) - cfg["hidden_size"]


def experts_touched(cfg, tokens):
    """Expected distinct experts a layer reads for ``tokens`` tokens in a
    step under even routing (what seeded weights give)."""
    d = dims(cfg)
    return d["e"] * (1.0 - (1.0 - d["k"] / d["e"]) ** tokens)


def token_flops(cfg):
    """Matmul FLOPs a token outside attention's scores and the head:
    the projections, the router and a token's ``k`` experts, every
    layer, 2 a multiply-add."""
    d = dims(cfg)
    h = d["h"]
    per = h * (2 * d["hq"] + 2 * d["hk"]) * d["d"] + h * d["e"] \
        + d["k"] * 3 * h * d["f"]
    return 2 * per * cfg["num_hidden_layers"]


def kv_bytes(cfg, itemsize=2):
    """K and V of one token in one layer."""
    d = dims(cfg)
    return 2 * d["hk"] * d["d"] * itemsize


def window_pairs(cfg, prefill, decode):
    """Sum over the query tokens of the keys each sees in a window
    layer: a prompt's position ``i`` sees ``min(i + 1, w)``."""
    w = cfg["sliding_window"]

    def prompt(p):
        full = min(p, w)
        return full * (full + 1) // 2 + (p - full) * w

    return sum(prompt(p) for p in prefill) + sum(min(c, w) for c in decode)


def pair_flops(cfg):
    """A (query token, key) pair in one layer: every query head's score
    and value row, 2 FLOPs a multiply-add."""
    d = dims(cfg)
    return 4 * d["hq"] * d["d"]


def attn_work(cfg, kind, keys, reads, tokens, itemsize=2):
    """``(flops, bytes)`` of the ragged attention of all layers of one
    ``kind`` (``window`` or ``full``) from what the program counted for ONE
    layer of it (``serving.dispatch``'s ``<kind>_keys``, ``<kind>_reads``,
    ``tokens``): every (query, key) pair of a row's queries against the
    keys it reads, those keys read once a row and the new tokens' K and V
    written once."""
    n = kinds(cfg)[kind]
    return n * pair_flops(cfg) * keys, \
        n * kv_bytes(cfg, itemsize) * (reads + tokens)


def moe_work(cfg, steps, tokens, itemsize=2):
    """``(flops, weight bytes, row bytes)`` of the routed experts' three
    GEMMs: each token's ``k`` rows through gate, up and down; the
    weights of the experts a step touches once a step; each packed row
    read and written once a GEMM."""
    d = dims(cfg)
    m = cfg["num_hidden_layers"]
    rows = tokens * d["k"]
    per_step = experts_touched(cfg, tokens / max(steps, 1)) \
        * 3 * d["h"] * d["f"]
    return (2 * rows * 3 * d["h"] * d["f"] * m,
            steps * per_step * itemsize * m,
            rows * 3 * (d["h"] + d["f"]) * itemsize * m)


def serve_work(cfg, steps, prefill, decode, itemsize=2):
    """FLOPs and bytes of a serving window (see ``harness/family.py``).
    Logits at the last position of a prompt and at every decoded token;
    a full layer reads each context once, a window layer ``min(context,
    window)`` of it, every layer writes each new token's K and V once;
    the experts are priced at the expected distinct ones a step of the
    window's mean size touches."""
    tokens = sum(prefill) + len(decode)
    k = kinds(cfg)
    heads_flops = 2 * cfg["hidden_size"] * cfg["vocab_size"] \
        * (len(prefill) + len(decode))
    a_flops = pair_flops(cfg) * (
        k["window"] * window_pairs(cfg, prefill, decode)
        + k["full"] * (sum(causal_pairs(p) for p in prefill) + sum(decode)))
    w = cfg["sliding_window"]
    a_bytes = kv_bytes(cfg, itemsize) * (
        cfg["num_hidden_layers"] * (sum(prefill) + tokens)
        + k["full"] * sum(decode)
        + k["window"] * sum(min(c, w) for c in decode))
    m_flops, m_weights, m_rows = moe_work(cfg, steps, tokens, itemsize)
    return {"flops": token_flops(cfg) * tokens + heads_flops + a_flops,
            "tokens": tokens,
            "bytes": steps * streamed_params(cfg) * itemsize + m_weights
            + a_bytes,
            "attn_flops": a_flops, "attn_bytes": a_bytes,
            "moe_flops": m_flops, "moe_bytes": m_weights + m_rows}


def _no_training(*_a, **_k):
    raise NotImplementedError("family window_moe has no training cell: "
                              "its costs and reference cover serving")


train_flops_per_token = train_attn_flops = train_attn_bytes = \
    train_readings = _no_training


def _config(name):
    with open(os.path.join(HERE, "configs", name + ".json")) as f:
        return json.load(f)


def selfcheck():
    """The cost functions against figures worked by hand."""
    c = _config("mellum2-12b-a2.5b-l8")
    assert kinds(c) == {"window": 6, "full": 2}, kinds(c)
    # attention 21,233,664, router 147,456, 64 experts of 6,193,152,
    # two norms 4,608
    near(layer_params(c, 0), 21233664 + 147456 + 64 * 6193152 + 4608, 0,
         "a layer")
    near(layer_params(c, 0), 417747456, 0, "a layer, summed by hand")
    near(2 * c["vocab_size"] * c["hidden_size"], 452984832, 0,
         "embedding and head")
    near(total_params(c), 3794966784, 0, "Mellum2 8-layer params")
    near(kv_bytes(c), 2048, 0, "K/V bytes a token a layer")
    near(window_pairs(c, [], [100, 5000]), 100 + 1024, 0, "window keys")
    near(window_pairs(c, [1500], []), 1024 * 1025 // 2 + 476 * 1024, 0,
         "window keys of a prompt")
    near(experts_touched(c, 64), 64 * (1 - (56 / 64) ** 64), 1e-12,
         "experts 64 tokens touch")
    # one decode token at context 3000 in a step of its own: all the
    # streamed weights, the 8 experts it touches in each layer, two full
    # layers reading 3,000 keys, six window layers 1,024, every layer
    # writing the new key
    w = serve_work(c, 1, [], [3000])
    near(w["attn_flops"], 4 * 32 * 128 * (6 * 1024 + 2 * 3000), 0,
         "decode token attention FLOPs")
    near(w["attn_bytes"], 2048 * (8 + 2 * 3000 + 6 * 1024), 0,
         "decode token K/V bytes")
    near(w["moe_flops"], 2 * 8 * 3 * 2304 * 896 * 8, 0,
         "decode token expert FLOPs")
    near(streamed_params(c), 3794966784 - 98304 * 2304 - 8 * 396361728
         - 2304, 0, "streamed parameters")
    near(w["bytes"], 2 * (streamed_params(c) + 8 * 8 * 6193152)
         + w["attn_bytes"], 1e-9, "decode step bytes")
    # what the program counts for one layer of each kind: a decode row at
    # context 3,000 and a 512-token chunk ending at 2,048
    f, b = attn_work(c, "window", 1024 + 512 * 1535, 1024 + 1535, 513)
    near(f, 6 * 4 * 32 * 128 * (1024 + 512 * 1535), 0, "window work")
    near(b, 6 * 2048 * (1024 + 1535 + 513), 0, "window bytes")
    f, b = attn_work(c, "full", 3000 + 512 * 2048, 3000 + 2048, 513)
    near(f, 2 * 16384 * (3000 + 512 * 2048), 0, "full work")
    near(b, 2 * 2048 * (3000 + 2048 + 513), 0, "full bytes")
    # YaRN's ramp from frequency 18 = floor(18.08) to 35 = ceil(34.98)
    assert ramp_ends(c) == (18, 35), ramp_ends(c)


# ---------------------------------------------------------------------------
# the plain reference
# ---------------------------------------------------------------------------
Q_BLOCK = 256           # queries a block of the attention
EXPERT_GROUP = 16       # experts upcast at a time


def ramp_ends(cfg):
    """YaRN's correction range: the frequency indices whose wavelengths
    turn ``beta_fast`` and ``beta_slow`` times over the original
    context, rounded outward."""
    y, d = cfg["rope_parameters"][FULL], cfg["head_dim"]
    theta = float(y["rope_theta"])

    def dim_of(turns):
        return d * math.log(y["original_max_position_embeddings"]
                            / (turns * 2 * math.pi)) / (2 * math.log(theta))

    return max(math.floor(dim_of(y["beta_fast"])), 0), \
        min(math.ceil(dim_of(y["beta_slow"])), d - 1)


def rope_of(cfg, kind):
    """``(inverse frequencies [d/2], factor on sin and cos)`` of a layer
    kind, as the published rope_parameters define them."""
    y, d = cfg["rope_parameters"][kind], cfg["head_dim"]
    i = np.arange(d // 2, dtype=np.float64)
    f = float(y["rope_theta"]) ** (-2.0 * i / d)
    if y["rope_type"] == "default":
        return jnp.asarray(f, jnp.float32), 1.0
    lo, hi = ramp_ends(cfg)
    r = np.clip((i - lo) / (hi - lo), 0.0, 1.0)
    inv = r * f / float(y["factor"]) + (1.0 - r) * f
    return jnp.asarray(inv, jnp.float32), float(y["attention_factor"])


def rotate(x, inv, scale):
    """``x [T, heads, d]`` at positions 0..T-1, halves rotated."""
    t, d = x.shape[0], x.shape[-1]
    ang = jnp.arange(t, dtype=jnp.float32)[:, None] * inv
    emb = jnp.concatenate([ang, ang], -1)[:, None, :]
    x1, x2 = x[..., :d // 2], x[..., d // 2:]
    return scale * (x * jnp.cos(emb)
                    + jnp.concatenate([-x2, x1], -1) * jnp.sin(emb))


@functools.partial(jax.jit, static_argnames=("d", "eps", "scale", "quant"))
def attn_operands(x, w, inv, d, eps, scale, quant):
    """Rotated ``q [T, Hq, d]`` and ``k``, and ``v [T, Hk, d]``."""
    d = dict(d)
    t = x.shape[0]
    u = rmsnorm(x, w["ln1"], eps)
    q = mm(u, w["q"], quant).reshape(t, d["hq"], d["d"])
    k = mm(u, w["k"], quant).reshape(t, d["hk"], d["d"])
    v = mm(u, w["v"], quant).reshape(t, d["hk"], d["d"])
    return rotate(q, inv, scale), rotate(k, inv, scale), v


@functools.partial(jax.jit, static_argnames=("window",))
def attn_block(q, k, v, start, window):
    """Queries ``q [B, Hq, d]`` at positions ``start..`` over ``k, v [T,
    Hk, d]`` (each key head repeated over its query heads), causal, and
    within ``window`` keys where given."""
    g = q.shape[1] // k.shape[1]
    k, v = jnp.repeat(k, g, axis=1), jnp.repeat(v, g, axis=1)
    s = jnp.einsum("qhd,khd->hqk", q, k, precision=HIGHEST) \
        / np.sqrt(q.shape[-1])
    qpos = start + jnp.arange(q.shape[0])
    kpos = jnp.arange(k.shape[0])
    mask = kpos[None, :] <= qpos[:, None]
    if window is not None:
        mask &= kpos[None, :] > qpos[:, None] - window
    p = jax.nn.softmax(jnp.where(mask[None], s, -jnp.inf), axis=-1)
    return jnp.einsum("hqk,khd->qhd", p, v, precision=HIGHEST)


@functools.partial(jax.jit, static_argnames=("eps", "quant"))
def attn_out(x, a, w, eps, quant):
    """``x + a W_o`` and the FFN's normed input."""
    x = x + mm(a.reshape(a.shape[0], -1), w["o"], quant)
    return x, rmsnorm(x, w["ln2"], eps)


@functools.partial(jax.jit, static_argnames=("k",))
def route(y, router, valid, k):
    """Chosen experts ``[T, k]`` and their weights: the softmax over all
    experts in float32, the top k, divided by their sum. A position past
    the sequence's needed length chooses none."""
    p = jax.nn.softmax(jnp.matmul(y, router.astype(jnp.float32),
                                  precision=HIGHEST), axis=-1)
    wt, idx = jax.lax.top_k(p, k)
    wt = wt / jnp.sum(wt, axis=-1, keepdims=True)
    e = router.shape[1]
    return jnp.where(valid[:, None], idx, e), \
        jnp.where(valid[:, None], wt, 0.0)


@functools.partial(jax.jit, static_argnames=("experts", "cap"))
def expert_tables(idx, wt, experts, cap):
    """For each expert the tokens that chose it, ``cap`` slots each
    (token T = none, weight 0): ``[E, cap]`` ids and weights."""
    t, k = idx.shape
    flat = idx.reshape(-1)
    order = jnp.argsort(flat, stable=True)
    se = flat[order]
    first = jnp.searchsorted(se, jnp.arange(experts + 1), side="left")
    slot = jnp.arange(t * k) - first[jnp.clip(se, 0, experts)]
    ids = jnp.full((experts + 1, cap), t, jnp.int32) \
        .at[se, slot].set((order // k).astype(jnp.int32), mode="drop")
    wts = jnp.zeros((experts + 1, cap), jnp.float32) \
        .at[se, slot].set(wt.reshape(-1)[order], mode="drop")
    return ids[:experts], wts[:experts]


@functools.partial(jax.jit, static_argnames=("quant",))
def expert_group(acc, y, ids, wts, gate, up, down, quant):
    """Add a group of experts' weighted SwiGLU outputs for the tokens
    that chose them: ids/wts ``[G, cap]``, weights ``[G, in, out]``."""
    ypad = jnp.concatenate([y, jnp.zeros((1, y.shape[1]), y.dtype)])
    xg = ypad[ids]
    bmm = jax.vmap(lambda a, b: mm(a, b, quant))
    out = bmm(jax.nn.silu(bmm(xg, gate)) * bmm(xg, up), down)
    return acc.at[ids.reshape(-1)].add(
        (out * wts[..., None]).reshape(-1, out.shape[-1]), mode="drop")


def moe_ffn(y, w, cfg, valid, quant):
    d = dims(cfg)
    idx, wt = route(y, w["router"], valid, d["k"])
    counts = np.bincount(np.asarray(idx).reshape(-1),
                         minlength=d["e"] + 1)[:d["e"]]
    # slots an expert: 64 x a power of two, half again over the mean of
    # a full sequence, more if the fullest expert asks for it
    cap = 64
    while cap < max(1.5 * y.shape[0] * d["k"] / d["e"], counts.max()):
        cap *= 2
    ids, wts = expert_tables(idx, wt, d["e"], cap)
    acc = jnp.zeros(y.shape, jnp.float32)
    for g in range(0, d["e"], EXPERT_GROUP):
        sl = slice(g, g + EXPERT_GROUP)
        acc = expert_group(acc, y, ids[sl], wts[sl], w["experts_gate"][sl],
                           w["experts_up"][sl], w["experts_down"][sl],
                           quant)
    return acc


def layer_forward(x, w, cfg, index, valid, quant):
    """One layer over one sequence ``x [T, H]`` (positions 0..T-1)."""
    d, eps = dims(cfg), float(cfg["rms_norm_eps"])
    kind = cfg["layer_types"][index]
    inv, scale = rope_of(cfg, kind)
    q, k, v = attn_operands(x, w, inv, _frozen(d), eps, scale, quant)
    window = d["w"] if kind == WINDOW else None
    a = jnp.concatenate([attn_block(q[s:s + Q_BLOCK], k, v, s, window)
                         for s in range(0, x.shape[0], Q_BLOCK)])
    del q, k, v
    x, y = attn_out(x, a, w, eps, quant)
    return x + moe_ffn(y, w, cfg, valid, quant)


def served_logits(cfg, ids, rows, layer_weights, end_weights, quant=None,
                  block=1):
    """Teacher-forced logits (see ``harness/family.py``): ``ids`` [N, T]
    (prompt, served tokens, padding), ``rows`` [N, K] the positions whose
    next-token logits are wanted -> numpy [N, K, V] float32. A sequence
    at a time at the padded length (one set of shapes a run; a causal
    model's earlier positions never see the padding, which routes to no
    expert; ``block`` is not used)."""
    ids, rows = np.asarray(ids), np.asarray(rows)
    eps = float(cfg["rms_norm_eps"])
    out = []
    for n in range(len(ids)):
        t = ids.shape[1]
        valid = jnp.arange(t) < int(rows[n].max()) + 1
        x = jnp.take(end_weights["embed"], jnp.asarray(ids[n]),
                     axis=0).astype(jnp.float32)
        for i in range(cfg["num_hidden_layers"]):
            x = layer_forward(x, layer_weights(i), cfg, i, valid, quant)
        out.append(np.asarray(head_logits(
            x[None], jnp.asarray(rows[n:n + 1]), end_weights["norm"],
            end_weights["head"], eps=eps, quant=quant)))
    return np.concatenate(out)
