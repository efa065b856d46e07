"""The decoder family: pre-norm RMSNorm, rotary embeddings on halves
(``rotate_half``), grouped-query causal attention, bias-free SwiGLU,
every layer alike, untied head (the Mistral-7B / InternLM2 / Llama
layout). Everything the harness knows of it is here, behind the
interface that ``harness/family.py`` lists: the program's constructor
and parameters, the seeded weights, the operations and bytes its
algorithm needs, and its plain reference.

Leaves: ``embed`` [V, H], ``head`` [H, V], ``norm`` [H] and per layer
``q k v o gate up down`` ([in, out]) and ``ln1 ln2`` [H].

The reference is the family's published mathematics in straightforward
``jax.numpy``, float32, matmuls at ``highest``: no kernel, no cache, no
batching tricks. It imports nothing of the program and takes nothing the
program made (weights come from the seed). ``quant="int8"`` is the
control: the same mathematics with every linear layer computed in int8
(``harness/reference.py:mm``)."""

import functools
import json
import os

import jax
import jax.numpy as jnp
import numpy as np

from harness import weights
from harness.costs import causal_pairs
from harness.reference import (attention, delta_norms, flat_leaves,
                               head_logits, mm, rmsnorm, rope)
from harness.selfcheck import near

HERE = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


# ---------------------------------------------------------------------------
# the program's side
# ---------------------------------------------------------------------------
def llama_config(cfg, **extra):
    from paddle_tpu.models import LlamaConfig
    return LlamaConfig(
        vocab_size=cfg["vocab_size"], hidden_size=cfg["hidden_size"],
        intermediate_size=cfg["intermediate_size"],
        num_hidden_layers=cfg["num_hidden_layers"],
        num_attention_heads=cfg["num_attention_heads"],
        num_key_value_heads=cfg["num_key_value_heads"],
        max_position_embeddings=cfg["max_position_embeddings"],
        rms_norm_eps=cfg["rms_norm_eps"], rope_theta=cfg["rope_theta"],
        tie_word_embeddings=cfg.get("tie_word_embeddings", False),
        **extra)


def build_model(cfg, dtype, **extra):
    """The program's own constructor (its eager per-parameter init is
    part of set-up until the program can skip it)."""
    import paddle_tpu as paddle
    from paddle_tpu.models import LlamaForCausalLM
    paddle.set_default_dtype(dtype)
    try:
        model = LlamaForCausalLM(llama_config(cfg, **extra))
    finally:
        paddle.set_default_dtype("float32")
    return model


def leaves(model, cfg):
    """``{benchmark leaf name: the program's parameter}``."""
    out = {"embed": model.model.embed_tokens.weight,
           "head": model.lm_head.weight, "norm": model.model.norm.weight}
    for i, layer in enumerate(model.model.layers):
        a, m = layer.self_attn, layer.mlp
        out.update({
            f"layers.{i}.q": a.q_proj.weight, f"layers.{i}.k": a.k_proj.weight,
            f"layers.{i}.v": a.v_proj.weight, f"layers.{i}.o": a.o_proj.weight,
            f"layers.{i}.gate": m.gate_proj.weight,
            f"layers.{i}.up": m.up_proj.weight,
            f"layers.{i}.down": m.down_proj.weight,
            f"layers.{i}.ln1": layer.input_layernorm.weight,
            f"layers.{i}.ln2": layer.post_attention_layernorm.weight})
    return out


def engine(model, mix):
    """The serving engine as the mix sizes it, its two step shapes (the
    chunk budget and the decode batch) warm."""
    from paddle_tpu.inference.serving import LlamaServingEngine
    e = LlamaServingEngine(model, **mix["engine"])
    e.prewarm(mixed=[e.chunk_budget, e.max_batch])
    return e


def release(engine):
    """Free the page pools: the reference runs beside nothing else."""
    engine.k_pools = engine.v_pools = None


# ---------------------------------------------------------------------------
# seeded weights
# ---------------------------------------------------------------------------
def layer_count(cfg):
    return cfg["num_hidden_layers"]


def layer_shapes(cfg):
    h, f, d = cfg["hidden_size"], cfg["intermediate_size"], head_dim(cfg)
    nq, nk = cfg["num_attention_heads"], cfg["num_key_value_heads"]
    return {"q": (h, nq * d), "k": (h, nk * d), "v": (h, nk * d),
            "o": (nq * d, h), "gate": (h, f), "up": (h, f),
            "down": (f, h)}


@functools.partial(jax.jit, static_argnames=("shapes", "hidden", "dtype"))
def _layer(key, shapes, hidden, dtype):
    out = weights.normal_leaves(key, shapes, dtype)
    out["ln1"] = jnp.ones((hidden,), dtype)
    out["ln2"] = jnp.ones((hidden,), dtype)
    return out


@functools.partial(jax.jit, static_argnames=("vocab", "hidden", "dtype"))
def _ends(key, vocab, hidden, dtype):
    k1, k2 = jax.random.split(key)
    return {"embed": weights.normal(k1, (vocab, hidden), dtype),
            "head": weights.normal(k2, (hidden, vocab), dtype),
            "norm": jnp.ones((hidden,), dtype)}


def layer(cfg, seed, index, dtype):
    shapes = tuple(sorted(layer_shapes(cfg).items()))
    return _layer(jax.random.fold_in(weights.key_of(seed), 1 + index),
                  shapes, cfg["hidden_size"], jnp.dtype(dtype))


def ends(cfg, seed, dtype):
    return _ends(jax.random.fold_in(weights.key_of(seed), 0),
                 cfg["vocab_size"], cfg["hidden_size"], jnp.dtype(dtype))


# ---------------------------------------------------------------------------
# operations and bytes
# ---------------------------------------------------------------------------
def head_dim(cfg):
    return cfg.get("head_dim") or \
        cfg["hidden_size"] // cfg["num_attention_heads"]


def layer_params(cfg):
    """Parameters of one decoder layer: q, k, v, o, gate, up, down and
    the two RMSNorm weights."""
    h, f, d = cfg["hidden_size"], cfg["intermediate_size"], head_dim(cfg)
    nq, nk = cfg["num_attention_heads"], cfg["num_key_value_heads"]
    return h * nq * d + 2 * h * nk * d + nq * d * h + 3 * h * f + 2 * h


def embed_params(cfg):
    return cfg["vocab_size"] * cfg["hidden_size"]


def total_params(cfg):
    n = cfg["num_hidden_layers"] * layer_params(cfg) + embed_params(cfg) \
        + cfg["hidden_size"]
    if not cfg.get("tie_word_embeddings", False):
        n += embed_params(cfg)
    return n


def matmul_params(cfg):
    """Parameters that a token multiplies through: all but the embedding
    table (a gather) and the norm weights."""
    return cfg["num_hidden_layers"] * (layer_params(cfg)
                                       - 2 * cfg["hidden_size"]) \
        + embed_params(cfg)


def kv_bytes_per_token(cfg, itemsize=2):
    """K and V of one token over all layers."""
    return 2 * cfg["num_key_value_heads"] * head_dim(cfg) * itemsize \
        * cfg["num_hidden_layers"]


def attn_flops(cfg, q_tokens_times_context):
    """QK^T and PV of all layers for a sum of (query token x keys it
    attends to): 2 matmuls x 2 FLOPs x heads x head_dim per pair."""
    return 4 * cfg["num_attention_heads"] * head_dim(cfg) \
        * cfg["num_hidden_layers"] * q_tokens_times_context


def train_flops_per_token(cfg, seq_len):
    """6 x matmul parameters plus causal attention forward and backward
    (3 x forward; the flash kernels' recomputation is not counted)."""
    pairs_per_token = (seq_len + 1) / 2.0
    return 6 * matmul_params(cfg) + 3 * attn_flops(cfg, pairs_per_token)


def train_attn_flops(cfg, seq_len, sequences):
    return 3 * attn_flops(cfg, causal_pairs(seq_len)) * sequences


def train_attn_bytes(cfg, seq_len, sequences, itemsize=2):
    """Least traffic of attention forward and backward: q, k, v, o read
    or written once forward; q, k, v, o, do read and dq, dk, dv written
    backward."""
    d = head_dim(cfg)
    q = cfg["num_attention_heads"] * d
    kv = cfg["num_key_value_heads"] * d
    per_token = (2 * q + 2 * kv) + (3 * q + 2 * kv) + (q + 2 * kv)
    return per_token * itemsize * seq_len * sequences \
        * cfg["num_hidden_layers"]


def weight_bytes(cfg, itemsize=2):
    """Bytes of the weights a serving step streams once: every matmul
    parameter but the embedding table (a gather of a few rows)."""
    return matmul_params(cfg) * itemsize


def serve_work(cfg, steps, prefill, decode, kv_itemsize=2,
               weight_itemsize=2):
    """FLOPs and bytes of a serving window.

    ``steps``: executions of the step program (each streams the weights
    once); ``prefill``: list of prompt lengths whose prefill fell in the
    window; ``decode``: list of context lengths (keys attended to) of the
    output tokens decoded in the window. Attention reads the K/V of each
    context once: a prompt once over its own length, a decoded token over
    its context."""
    tokens = sum(prefill) + len(decode)
    pairs = sum(causal_pairs(p) for p in prefill) + sum(decode)
    flops = 2 * matmul_params(cfg) * tokens + attn_flops(cfg, pairs)
    kvb = kv_bytes_per_token(cfg, kv_itemsize)
    kv_read = kvb * (sum(prefill) + sum(decode))
    kv_write = kvb * tokens
    return {"flops": flops, "tokens": tokens,
            "bytes": steps * weight_bytes(cfg, weight_itemsize)
            + kv_read + kv_write,
            "attn_flops": attn_flops(cfg, pairs),
            "attn_bytes": kv_read + kv_write}


def _config(name):
    with open(os.path.join(HERE, "configs", name + ".json")) as f:
        return json.load(f)


def selfcheck():
    """The cost functions against figures worked by hand."""
    m = _config("mistral-7b-v0.3-l16")
    i4 = _config("internlm2-1.8b-l4")
    i24 = dict(i4, num_hidden_layers=24)
    near(layer_params(m), 218.1e6, 1e-3, "Mistral layer params")
    near(total_params(m), 3.758e9, 1e-3, "Mistral L16 params")
    near(kv_bytes_per_token(m), 64 * 1024, 0, "K/V bytes a token")
    near(layer_params(i4), 62.9e6, 1e-3, "InternLM2 layer params")
    near(total_params(i4), 630.8e6, 1e-3, "InternLM2 L4 params")
    near(total_params(i24), 1.889e9, 1e-3, "InternLM2 params")
    near(train_flops_per_token(i4, 4096), 2.85e9, 2e-3,
          "InternLM2 L4 FLOPs a trained token")
    near(train_flops_per_token(i24, 4096), 11.4e9, 2e-3,
          "InternLM2 FLOPs a trained token")
    # one decode token at context 1000 on Mistral L16: 2 x 3.624e9
    # matmul FLOPs + 4 x 4096 x 16 x 1000 attention FLOPs
    w = serve_work(m, 1, [], [1000])
    near(w["flops"], 2 * 3.6239e9 + 262.1e6, 1e-3, "decode token FLOPs")
    near(w["bytes"], 2 * 3.6239e9 + 1001 * 65536, 1e-3,
          "decode step bytes")


# ---------------------------------------------------------------------------
# the plain reference
# ---------------------------------------------------------------------------
def layer_forward(x, w, dims, quant):
    """One decoder layer over x [B, T, H] (positions 0..T-1)."""
    nq, nk, d, eps, theta = dims
    b, t, h = x.shape
    pos = jnp.arange(t)
    y = rmsnorm(x, w["ln1"], eps)
    q = mm(y, w["q"], quant).reshape(b, t, nq, d)
    k = mm(y, w["k"], quant).reshape(b, t, nk, d)
    v = mm(y, w["v"], quant).reshape(b, t, nk, d)

    def one(qkv):
        q_, k_, v_ = qkv
        return attention(rope(q_, pos, theta), rope(k_, pos, theta), v_)

    a = jax.lax.map(one, (q, k, v)).reshape(b, t, nq * d)
    x = x + mm(a, w["o"], quant)
    y = rmsnorm(x, w["ln2"], eps)
    up = jax.nn.silu(mm(y, w["gate"], quant)) * mm(y, w["up"], quant)
    return x + mm(up, w["down"], quant)


def dims_of(cfg):
    return (cfg["num_attention_heads"], cfg["num_key_value_heads"],
            head_dim(cfg), float(cfg["rms_norm_eps"]),
            float(cfg["rope_theta"]))


_layer_jit = jax.jit(layer_forward, static_argnames=("dims", "quant"))


def served_logits(cfg, ids, rows, layer_weights, end_weights, quant=None,
                  block=4):
    """Teacher-forced logits. ``ids`` [N, T] int (prompt, then the served
    tokens, then padding: a causal model's earlier positions never see
    it), ``rows`` [N, K] the positions whose next-token logits are
    wanted. ``layer_weights(i)`` and ``end_weights`` give the seeded
    weights. Returns a numpy array [N, K, V] (float32), computed in
    blocks of ``block`` sequences so that it fits beside nothing else."""
    ids, rows = np.asarray(ids), np.asarray(rows)
    dims = dims_of(cfg)
    out = []
    for s in range(0, len(ids), block):
        x = jnp.take(end_weights["embed"], jnp.asarray(ids[s:s + block]),
                     axis=0).astype(jnp.float32)
        for i in range(cfg["num_hidden_layers"]):
            x = _layer_jit(x, layer_weights(i), dims=dims, quant=quant)
        out.append(np.asarray(head_logits(
            x, jnp.asarray(rows[s:s + block]), end_weights["norm"],
            end_weights["head"], eps=dims[3], quant=quant)))
    return np.concatenate(out)


def init_params(cfg, seed):
    """Float32 master weights from the seed, as the program is given."""
    p = dict(ends(cfg, seed, "float32"))
    p["layers"] = [layer(cfg, seed, i, "float32")
                   for i in range(cfg["num_hidden_layers"])]
    return p


def loss_fn(params, ids, labels, dims, quant, ce_chunk):
    """Mean next-token cross entropy over every position of ids [B, T]."""
    x = jnp.take(params["embed"], ids, axis=0).astype(jnp.float32)
    layer = jax.checkpoint(
        functools.partial(layer_forward, dims=dims, quant=quant))
    for w in params["layers"]:
        x = layer(x, w)
    y = rmsnorm(x, params["norm"], dims[3]).reshape(-1, x.shape[-1])
    lab = labels.reshape(-1)

    @jax.checkpoint
    def chunk_nll(args):
        yc, lc = args
        logits = mm(yc, params["head"], quant)
        lse = jax.nn.logsumexp(logits, axis=-1)
        return jnp.sum(lse - jnp.take_along_axis(
            logits, lc[:, None], axis=1)[:, 0])

    n = y.shape[0]
    yc = y.reshape(n // ce_chunk, ce_chunk, -1)
    lc = lab.reshape(n // ce_chunk, ce_chunk)
    return jnp.sum(jax.lax.map(chunk_nll, (yc, lc))) / n


@functools.partial(jax.jit, static_argnames=("dims", "quant", "ce_chunk",
                                             "hp"),
                   donate_argnums=(0, 1, 2))
def adamw_step(params, m, v, t, ids, labels, dims, quant, ce_chunk, hp):
    """One AdamW step as published (decoupled decay, bias-corrected
    moments). Returns the new state, the loss and each leaf's gradient
    norm."""
    lr, b1, b2, eps, wd = hp
    loss, g = jax.value_and_grad(loss_fn)(params, ids, labels, dims,
                                          quant, ce_chunk)
    gnorm = jax.tree_util.tree_map(
        lambda a: jnp.sqrt(jnp.sum(jnp.square(a))), g)
    m = jax.tree_util.tree_map(lambda a, b: b1 * a + (1 - b1) * b, m, g)
    v = jax.tree_util.tree_map(lambda a, b: b2 * a + (1 - b2) * b * b,
                               v, g)
    c1, c2 = 1 - b1 ** t, 1 - b2 ** t

    def upd(p, m_, v_):
        return p - lr * ((m_ / c1) / (jnp.sqrt(v_ / c2) + eps) + wd * p)

    return jax.tree_util.tree_map(upd, params, m, v), m, v, loss, gnorm


def train_readings(cfg, seed, batches, hp, quant=None, rows=None,
                   ce_chunk=1024):
    """Follow the first ``len(batches)`` steps from the seed.

    ``batches``: list of int arrays [B, T+1] (the rows the program was
    fed). ``rows`` keeps only those rows of every batch (the planted
    fault "half of the batch left out"). Returns ``{"loss": [...],
    "grad": {leaf: norm of the first gradient}, "delta": {leaf: norm of
    the parameters' change after the steps}}``."""
    dims = dims_of(cfg)
    params = init_params(cfg, seed)
    m = jax.tree_util.tree_map(jnp.zeros_like, params)
    v = jax.tree_util.tree_map(jnp.zeros_like, params)
    losses, grad = [], None
    for t, b in enumerate(batches, start=1):
        b = np.asarray(b)
        if rows is not None:
            b = b[list(rows)]
        chunk = min(ce_chunk, b.shape[0] * (b.shape[1] - 1))
        params, m, v, loss, gnorm = adamw_step(
            params, m, v, jnp.float32(t), jnp.asarray(b[:, :-1]),
            jnp.asarray(b[:, 1:]), dims=dims, quant=quant,
            ce_chunk=chunk, hp=tuple(float(h) for h in hp))
        losses.append(float(loss))
        if grad is None:
            grad = {k: float(a) for k, a in flat_leaves(gnorm).items()}
    del m, v
    delta = delta_norms(params, init_params(cfg, seed))
    return {"loss": losses, "grad": grad,
            "delta": {k: float(a) for k, a in flat_leaves(delta).items()}}
