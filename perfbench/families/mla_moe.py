"""The latent-attention expert family (the DeepSeek-V3 layout, here
JoyAI-LLM-Flash): pre-norm RMSNorm, multi-head latent attention with
low-rank q and kv projections and interleaved rotary pairs, a leading run
of dense SwiGLU layers, then layers of sigmoid-routed experts (top k by
score plus a correction bias, weights without it, normalised and scaled)
beside one shared expert; untied head. Everything the harness knows of it
is here, behind the interface that ``harness/family.py`` lists.

Leaves: ``embed`` [V, H], ``head`` [H, V], ``norm`` [H]; a layer has
``ln1 ln2 q_a q_a_norm q_b kv_a kv_a_norm kv_b o`` and then, dense,
``gate up down``, or, expert, ``router router_bias shared_gate shared_up
shared_down experts_gate experts_up experts_down`` (matrices [in, out],
experts stacked [E, in, out]).

The reference is the published mathematics in straightforward
``jax.numpy``, float32, matmuls at ``highest``, the attention NOT
absorbed (per head keys and values rebuilt from the latent), no cache, no
kernel; nothing of the program is imported. It is computed in blocks that
fit beside the bf16 weights: attention a block of queries at a time, the
experts a group at a time (each token gathered to the experts that chose
it, up to the fullest expert's count). ``quant="int8"`` is the control:
every linear layer through ``harness/reference.py:mm`` in int8; the
router stays float32, as the published code computes it."""

import functools
import json
import os

import jax
import jax.numpy as jnp
import numpy as np

from harness import weights
from harness.costs import causal_pairs
from harness.reference import HIGHEST, head_logits, mm, rmsnorm
from harness.selfcheck import near

HERE = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


# ---------------------------------------------------------------------------
# the program's side
# ---------------------------------------------------------------------------
CONFIG_KEYS = (
    "vocab_size", "hidden_size", "intermediate_size",
    "moe_intermediate_size", "num_hidden_layers", "num_attention_heads",
    "q_lora_rank", "kv_lora_rank", "qk_nope_head_dim", "qk_rope_head_dim",
    "v_head_dim", "n_routed_experts", "n_shared_experts",
    "num_experts_per_tok", "first_k_dense_replace",
    "routed_scaling_factor", "norm_topk_prob", "max_position_embeddings",
    "rms_norm_eps", "rope_theta", "tie_word_embeddings")


def build_model(cfg, dtype):
    """The program's own constructor."""
    import paddle_tpu as paddle
    from paddle_tpu.models import MlaMoeConfig, MlaMoeForCausalLM
    unsupported = {"n_group": 1, "topk_group": 1, "scoring_func": "sigmoid",
                   "topk_method": "noaux_tc", "rope_scaling": None,
                   "rope_interleave": True, "attention_bias": False,
                   "hidden_act": "silu", "moe_layer_freq": 1}
    for k, v in unsupported.items():
        if cfg.get(k, v) != v:
            raise ValueError(f"family mla_moe runs {k}={v!r} only, the "
                             f"configuration gives {cfg[k]!r}")
    paddle.set_default_dtype(dtype)
    try:
        model = MlaMoeForCausalLM(MlaMoeConfig(
            **{k: cfg[k] for k in CONFIG_KEYS}))
    finally:
        paddle.set_default_dtype("float32")
    return model


def is_dense(cfg, index):
    return index < cfg["first_k_dense_replace"]


def leaves(model, cfg):
    """``{benchmark leaf name: the program's parameter}``."""
    out = {"embed": model.model.embed_tokens.weight,
           "head": model.lm_head.weight, "norm": model.model.norm.weight}
    for i, layer in enumerate(model.model.layers):
        a, m = layer.self_attn, layer.mlp
        own = {"ln1": layer.input_layernorm.weight,
               "ln2": layer.post_attention_layernorm.weight,
               "q_a": a.q_a.weight, "q_a_norm": a.q_a_norm.weight,
               "q_b": a.q_b.weight, "kv_a": a.kv_a.weight,
               "kv_a_norm": a.kv_a_norm.weight, "kv_b": a.kv_b.weight,
               "o": a.o.weight}
        if is_dense(cfg, i):
            own.update(gate=m.gate_proj.weight, up=m.up_proj.weight,
                       down=m.down_proj.weight)
        else:
            own.update(router=m.router, router_bias=m.router_bias,
                       shared_gate=m.shared.gate_proj.weight,
                       shared_up=m.shared.up_proj.weight,
                       shared_down=m.shared.down_proj.weight,
                       experts_gate=m.experts_gate,
                       experts_up=m.experts_up,
                       experts_down=m.experts_down)
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
    """Free the latent page pools: the reference runs beside the
    weights alone."""
    engine.k_pools = engine.v_pools = None


# ---------------------------------------------------------------------------
# seeded weights
# ---------------------------------------------------------------------------
def layer_count(cfg):
    return cfg["num_hidden_layers"]


def dims(cfg):
    return {"h": cfg["hidden_size"], "heads": cfg["num_attention_heads"],
            "qr": cfg["q_lora_rank"], "kvr": cfg["kv_lora_rank"],
            "nope": cfg["qk_nope_head_dim"], "rope": cfg["qk_rope_head_dim"],
            "v": cfg["v_head_dim"], "i": cfg["intermediate_size"],
            "f": cfg["moe_intermediate_size"], "e": cfg["n_routed_experts"],
            "k": cfg["num_experts_per_tok"], "ns": cfg["n_shared_experts"]}


def layer_shapes(cfg, index):
    """The seeded normal leaves of layer ``index`` (norm weights are 1)."""
    d = dims(cfg)
    h, heads = d["h"], d["heads"]
    out = {"q_a": (h, d["qr"]),
           "q_b": (d["qr"], heads * (d["nope"] + d["rope"])),
           "kv_a": (h, d["kvr"] + d["rope"]),
           "kv_b": (d["kvr"], heads * (d["nope"] + d["v"])),
           "o": (heads * d["v"], h)}
    if is_dense(cfg, index):
        out.update(gate=(h, d["i"]), up=(h, d["i"]), down=(d["i"], h))
    else:
        fs = d["f"] * d["ns"]
        out.update(router=(h, d["e"]), router_bias=(d["e"],),
                   shared_gate=(h, fs), shared_up=(h, fs),
                   shared_down=(fs, h), experts_gate=(d["e"], h, d["f"]),
                   experts_up=(d["e"], h, d["f"]),
                   experts_down=(d["e"], d["f"], h))
    return out


NORMS = (("ln1", "h"), ("ln2", "h"), ("q_a_norm", "qr"),
         ("kv_a_norm", "kvr"))


@functools.partial(jax.jit, static_argnames=("shapes", "norms", "dtype"))
def _layer(key, shapes, norms, dtype):
    out = weights.normal_leaves(key, shapes, dtype)
    for name, n in norms:
        out[name] = jnp.ones((n,), dtype)
    return out


@functools.partial(jax.jit, static_argnames=("vocab", "hidden", "dtype"))
def _ends(key, vocab, hidden, dtype):
    k1, k2 = jax.random.split(key)
    return {"embed": weights.normal(k1, (vocab, hidden), dtype),
            "head": weights.normal(k2, (hidden, vocab), dtype),
            "norm": jnp.ones((hidden,), dtype)}


def layer(cfg, seed, index, dtype):
    d = dims(cfg)
    shapes = tuple(sorted(layer_shapes(cfg, index).items()))
    return _layer(jax.random.fold_in(weights.key_of(seed), 1 + index),
                  shapes, tuple((n, d[k]) for n, k in NORMS),
                  jnp.dtype(dtype))


def ends(cfg, seed, dtype):
    return _ends(jax.random.fold_in(weights.key_of(seed), 0),
                 cfg["vocab_size"], cfg["hidden_size"], jnp.dtype(dtype))


# ---------------------------------------------------------------------------
# operations and bytes
# ---------------------------------------------------------------------------
def _size(shape):
    return int(np.prod(shape))


def layer_params(cfg, index):
    d = dims(cfg)
    return sum(_size(s) for s in layer_shapes(cfg, index).values()) \
        + sum(d[k] for _, k in NORMS)


def expert_params(cfg):
    """One layer's routed experts."""
    d = dims(cfg)
    return d["e"] * 3 * d["h"] * d["f"]


def moe_layers(cfg):
    return sum(not is_dense(cfg, i) for i in range(cfg["num_hidden_layers"]))


def total_params(cfg):
    return sum(layer_params(cfg, i) for i in range(cfg["num_hidden_layers"])) \
        + 2 * cfg["vocab_size"] * cfg["hidden_size"] + cfg["hidden_size"]


def streamed_params(cfg):
    """Parameters every executed step reads whatever it carries: all but
    the embedding table (a gather), the routed experts (counted by the
    experts a step touches) and the final norm."""
    return total_params(cfg) - cfg["vocab_size"] * cfg["hidden_size"] \
        - moe_layers(cfg) * expert_params(cfg) - cfg["hidden_size"]


def experts_touched(cfg, tokens):
    """Expected distinct experts a layer reads for ``tokens`` tokens in a
    step under even routing (what seeded weights give): never more than
    the layer has."""
    d = dims(cfg)
    return d["e"] * (1.0 - (1.0 - d["k"] / d["e"]) ** tokens)


def token_flops(cfg):
    """Matmul FLOPs a token outside the attention kernel and the head,
    by the ABSORBED form: the low-rank projections, the query's
    absorption into the latent space and the attended latent's way out
    of it, the output projection, then the FFN of each layer."""
    d = dims(cfg)
    h, heads = d["h"], d["heads"]
    attn = h * d["qr"] + d["qr"] * heads * (d["nope"] + d["rope"]) \
        + h * (d["kvr"] + d["rope"]) + heads * d["nope"] * d["kvr"] \
        + heads * d["kvr"] * d["v"] + heads * d["v"] * h
    dense = 3 * h * d["i"]
    moe = h * d["e"] + (d["k"] + d["ns"]) * 3 * h * d["f"]
    n, m = cfg["num_hidden_layers"], moe_layers(cfg)
    return 2 * (n * attn + (n - m) * dense + m * moe)


def latent_bytes_per_token(cfg, itemsize=2):
    """The cached row of one token over all layers: the latent and the
    shared rotary key, nothing per head (pad lanes are not work)."""
    d = dims(cfg)
    return (d["kvr"] + d["rope"]) * itemsize * cfg["num_hidden_layers"]


def attn_flops(cfg, pairs):
    """The absorbed attention of all layers for a sum of (query token x
    rows it attends to): scores over ``kv_rank + rope`` lanes and values
    over ``kv_rank``, 2 FLOPs a multiply-add, every head."""
    d = dims(cfg)
    return 2 * (2 * d["kvr"] + d["rope"]) * d["heads"] \
        * cfg["num_hidden_layers"] * pairs


def moe_work(cfg, steps, tokens, itemsize=2):
    """``(flops, weight bytes, row bytes)`` of the routed experts' three
    GEMMs: each token's ``k`` rows through gate, up and down; the
    weights of the experts a step touches once a step; each packed row
    read and written once a GEMM."""
    d = dims(cfg)
    m = moe_layers(cfg)
    rows = tokens * d["k"]
    per_step = experts_touched(cfg, tokens / max(steps, 1)) \
        * 3 * d["h"] * d["f"]
    return (2 * rows * 3 * d["h"] * d["f"] * m,
            steps * per_step * itemsize * m,
            rows * 3 * (d["h"] + d["f"]) * itemsize * m)


def serve_work(cfg, steps, prefill, decode, itemsize=2):
    """FLOPs and bytes of a serving window (see ``harness/family.py``).
    Logits are needed at the last position of a prompt and at every
    decoded token; the latent rows of each context are read once."""
    tokens = sum(prefill) + len(decode)
    pairs = sum(causal_pairs(p) for p in prefill) + sum(decode)
    heads_flops = 2 * cfg["hidden_size"] * cfg["vocab_size"] \
        * (len(prefill) + len(decode))
    a_flops = attn_flops(cfg, pairs)
    lat = latent_bytes_per_token(cfg, itemsize)
    a_bytes = lat * (sum(prefill) + sum(decode)) + lat * tokens
    m_flops, m_weights, m_rows = moe_work(cfg, steps, tokens, itemsize)
    return {"flops": token_flops(cfg) * tokens + heads_flops + a_flops,
            "tokens": tokens,
            "bytes": steps * streamed_params(cfg) * itemsize + m_weights
            + a_bytes,
            "attn_flops": a_flops, "attn_bytes": a_bytes,
            "moe_flops": m_flops, "moe_bytes": m_weights + m_rows}


def _no_training(*_a, **_k):
    raise NotImplementedError("family mla_moe has no training cell: its "
                              "costs and reference cover serving")


train_flops_per_token = train_attn_flops = train_attn_bytes = \
    train_readings = _no_training


def _config(name):
    with open(os.path.join(HERE, "configs", name + ".json")) as f:
        return json.load(f)


def selfcheck():
    """The cost functions against figures worked by hand (ISSUE 30's
    arithmetic; ``perfbench/tests/golden_mla_moe.json`` holds more)."""
    c = _config("joyai-llm-flash-l5")
    # attention 3.146 + 9.437 + 1.180 + 4.194 + 8.389 M and four norms
    near(layer_params(c, 0), 26.345e6 + 44.040e6, 1e-3, "dense layer")
    near(layer_params(c, 1), 1239.55e6, 1e-3, "expert layer")
    near(expert_params(c), 1207.96e6, 1e-4, "routed experts of a layer")
    near(total_params(c), 5558e6, 1e-3, "JoyAI L5 params")
    near(latent_bytes_per_token(c), 5 * 1152, 0, "latent bytes a token")
    near(experts_touched(c, 48), 256 * (1 - 0.96875 ** 48), 1e-12,
         "experts 48 tokens touch")
    near(experts_touched(c, 1024), 256.0, 1e-9, "experts a chunk touches")
    # one decode token at context 1000 in a step of its own: 8 experts
    # a layer x 4 layers x 4.719 M values beside the streamed 461.5 M
    # (5 x 26.345 attention + 44.040 dense + 4 x 5.243 router and
    # shared expert + 264.77 head)
    near(streamed_params(c), 461.5e6, 1e-3, "streamed parameters")
    w = serve_work(c, 1, [], [1000])
    near(w["bytes"], 2 * (461.5e6 + 4 * 8 * 4.7186e6) + 1001 * 5760,
         1e-3, "decode step bytes")
    near(w["attn_flops"], 2 * 1088 * 32 * 5 * 1000, 0,
         "decode token attention FLOPs")
    near(w["moe_flops"], 2 * 8 * 3 * 2048 * 768 * 4, 0,
         "decode token expert FLOPs")


# ---------------------------------------------------------------------------
# the plain reference
# ---------------------------------------------------------------------------
Q_BLOCK = 256           # queries a block of the attention
EXPERT_GROUP = 32       # experts upcast at a time


def rope_pairs(x, pos, theta):
    """x [T, heads, D], pos [T]: rotate the pairs (2i, 2i+1), as
    published (``rope_interleave``), the lanes left where they are."""
    d = x.shape[-1]
    inv = 1.0 / (theta ** (jnp.arange(0, d, 2, dtype=jnp.float32) / d))
    ang = pos.astype(jnp.float32)[:, None] * inv
    c, s = jnp.cos(ang)[:, None, :], jnp.sin(ang)[:, None, :]
    x1, x2 = x[..., 0::2], x[..., 1::2]
    return jnp.stack([x1 * c - x2 * s, x2 * c + x1 * s], -1) \
        .reshape(x.shape)


@functools.partial(jax.jit, static_argnames=("d", "eps", "theta", "quant"))
def attn_operands(x, w, d, eps, theta, quant):
    """Per head q, k [T, heads, nope + rope] and v [T, heads, v] of one
    sequence x [T, H] (positions 0..T-1): nothing absorbed."""
    d = dict(d)
    t, heads = x.shape[0], d["heads"]
    pos = jnp.arange(t)
    y = rmsnorm(x, w["ln1"], eps)
    q = mm(rmsnorm(mm(y, w["q_a"], quant), w["q_a_norm"], eps), w["q_b"],
           quant).reshape(t, heads, d["nope"] + d["rope"])
    lat = mm(y, w["kv_a"], quant)
    c = rmsnorm(lat[:, :d["kvr"]], w["kv_a_norm"], eps)
    kr = rope_pairs(lat[:, None, d["kvr"]:], pos, theta)
    kv = mm(c, w["kv_b"], quant).reshape(t, heads, d["nope"] + d["v"])
    q = jnp.concatenate([q[..., :d["nope"]],
                         rope_pairs(q[..., d["nope"]:], pos, theta)], -1)
    k = jnp.concatenate([kv[..., :d["nope"]],
                         jnp.broadcast_to(kr, (t, heads, d["rope"]))], -1)
    return q, k, kv[..., d["nope"]:]


@jax.jit
def attn_block(q, k, v, start):
    """Causal attention of the queries q [B, heads, D] at positions
    start.. over all of k, v [T, heads, .]."""
    s = jnp.einsum("qhd,khd->hqk", q, k, precision=HIGHEST) \
        / np.sqrt(q.shape[-1])
    qpos = start + jnp.arange(q.shape[0])
    mask = jnp.arange(k.shape[0])[None, :] <= qpos[:, None]
    p = jax.nn.softmax(jnp.where(mask[None], s, -jnp.inf), axis=-1)
    return jnp.einsum("hqk,khd->qhd", p, v, precision=HIGHEST)


@functools.partial(jax.jit, static_argnames=("eps", "quant"))
def attn_out(x, a, w, eps, quant):
    """Residual after attention, and the FFN's normed input."""
    x = x + mm(a.reshape(a.shape[0], -1), w["o"], quant)
    return x, rmsnorm(x, w["ln2"], eps)


@functools.partial(jax.jit, static_argnames=("quant",))
def swiglu(y, gate, up, down, quant):
    return mm(jax.nn.silu(mm(y, gate, quant)) * mm(y, up, quant), down,
              quant)


@functools.partial(jax.jit, static_argnames=("k", "scale", "norm"))
def route(y, router, bias, valid, k, scale, norm):
    """Chosen experts [T, k] and their weights: sigmoid scores, the top
    k by score + bias, weights WITHOUT the bias, normalised, scaled. A
    position past the sequence's needed length chooses none."""
    s = jax.nn.sigmoid(jnp.matmul(y, router.astype(jnp.float32),
                                  precision=HIGHEST))
    _, idx = jax.lax.top_k(s + bias.astype(jnp.float32), k)
    wt = jnp.take_along_axis(s, idx, axis=-1)
    if norm:
        wt = wt / (jnp.sum(wt, axis=-1, keepdims=True) + 1e-20)
    e = router.shape[1]
    return jnp.where(valid[:, None], idx, e), \
        jnp.where(valid[:, None], wt * scale, 0.0)


@functools.partial(jax.jit, static_argnames=("experts", "cap"))
def expert_tables(idx, wt, experts, cap):
    """For each expert the tokens that chose it, ``cap`` slots each
    (token T = none, weight 0): [E, cap] ids and weights."""
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
    """Add a group of experts' weighted outputs for the tokens that
    chose them: ids/wts [G, cap], weights [G, in, out]."""
    ypad = jnp.concatenate([y, jnp.zeros((1, y.shape[1]), y.dtype)])
    xg = ypad[ids]                                       # [G, cap, H]
    bmm = jax.vmap(lambda a, b: mm(a, b, quant))
    out = bmm(jax.nn.silu(bmm(xg, gate)) * bmm(xg, up), down)
    return acc.at[ids.reshape(-1)].add(
        (out * wts[..., None]).reshape(-1, out.shape[-1]), mode="drop")


def moe_ffn(y, w, cfg, valid, quant):
    d = dims(cfg)
    idx, wt = route(y, w["router"], w["router_bias"], valid, d["k"],
                    float(cfg["routed_scaling_factor"]),
                    bool(cfg["norm_topk_prob"]))
    counts = np.bincount(np.asarray(idx).reshape(-1),
                         minlength=d["e"] + 1)[:d["e"]]
    # slots an expert: 64 x a power of two, half again over the mean of
    # a full sequence (one shape whatever the sequence), more if the
    # fullest expert asks for it
    cap = 64
    while cap < max(1.5 * y.shape[0] * d["k"] / d["e"], counts.max()):
        cap *= 2
    ids, wts = expert_tables(idx, wt, d["e"], cap)
    acc = swiglu(y, w["shared_gate"], w["shared_up"], w["shared_down"],
                 quant)
    for g in range(0, d["e"], EXPERT_GROUP):
        sl = slice(g, g + EXPERT_GROUP)
        acc = expert_group(acc, y, ids[sl], wts[sl], w["experts_gate"][sl],
                           w["experts_up"][sl], w["experts_down"][sl],
                           quant)
    return acc


def layer_forward(x, w, cfg, index, valid, quant):
    """One layer over one sequence x [T, H] (positions 0..T-1)."""
    d, eps = dims(cfg), float(cfg["rms_norm_eps"])
    q, k, v = attn_operands(x, w, tuple(sorted(d.items())), eps,
                            float(cfg["rope_theta"]), quant)
    a = jnp.concatenate([attn_block(q[s:s + Q_BLOCK], k, v, s)
                         for s in range(0, x.shape[0], Q_BLOCK)])
    del q, k, v
    x, y = attn_out(x, a, w, eps, quant)
    if is_dense(cfg, index):
        return x + swiglu(y, w["gate"], w["up"], w["down"], quant)
    return x + moe_ffn(y, w, cfg, valid, quant)


def served_logits(cfg, ids, rows, layer_weights, end_weights, quant=None,
                  block=1):
    """Teacher-forced logits (see ``harness/family.py``): ``ids`` [N, T]
    (prompt, served tokens, padding), ``rows`` [N, K] the positions whose
    next-token logits are wanted -> numpy [N, K, V] float32. A sequence
    at a time (``block`` is not used: a sequence's blocks are its
    queries and its experts), cut to the positions its rows need, since
    a causal model's earlier positions never see the later ones."""
    ids, rows = np.asarray(ids), np.asarray(rows)
    eps = float(cfg["rms_norm_eps"])
    out = []
    for n in range(len(ids)):
        need = int(rows[n].max()) + 1
        t = ids.shape[1]
        valid = jnp.arange(t) < need
        x = jnp.take(end_weights["embed"], jnp.asarray(ids[n, :t]),
                     axis=0).astype(jnp.float32)
        for i in range(cfg["num_hidden_layers"]):
            x = layer_forward(x, layer_weights(i), cfg, i, valid, quant)
        out.append(np.asarray(head_logits(
            x[None], jnp.asarray(rows[n:n + 1]), end_weights["norm"],
            end_weights["head"], eps=eps, quant=quant)))
    return np.concatenate(out)
