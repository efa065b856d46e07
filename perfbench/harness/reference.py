"""The plain reference: the decoder family's published mathematics in
straightforward ``jax.numpy``, float32, matmuls at ``highest``. No
kernel, no cache, no batching tricks; it imports nothing of the program
and takes nothing the program made (weights come from
``harness.weights`` and the seed).

Architecture (Mistral-7B / InternLM2 / Llama layout): pre-norm RMSNorm,
rotary embeddings on halves (``rotate_half``), grouped-query causal
attention, bias-free SwiGLU, untied head.

``quant="int8"`` is the control: the same mathematics with every linear
layer computed in int8, the nearest precision below bf16 and the one the
v5e's matrix unit would tempt a later PR with. All three products of a
linear layer are rounded: forward ``x @ w``, and in training ``dy @ w.T``
and ``x.T @ dy``, each operand scaled along the dimension that is not
contracted (the finest scaling an int8 product allows)."""

import functools

import jax
import jax.numpy as jnp
import numpy as np

from . import costs, weights

HIGHEST = jax.lax.Precision.HIGHEST


def _int8(x, axis):
    """Round to 127 levels either side of 0, scaled by the largest
    magnitude along ``axis`` (the contracted dimension)."""
    scale = jnp.max(jnp.abs(x), axis=axis, keepdims=True) / 127.0
    scale = jnp.where(scale == 0, 1.0, scale)
    return jnp.clip(jnp.round(x / scale), -127, 127) * scale


@jax.custom_vjp
def _int8_mm(x, w):
    return jnp.matmul(_int8(x, -1), _int8(w, 0), precision=HIGHEST)


def _int8_mm_fwd(x, w):
    return _int8_mm(x, w), (x, w)


def _int8_mm_bwd(res, dy):
    x, w = res
    x2, dy2 = x.reshape(-1, x.shape[-1]), dy.reshape(-1, dy.shape[-1])
    dx = jnp.matmul(_int8(dy, -1), _int8(w, 1).T, precision=HIGHEST)
    dw = jnp.matmul(_int8(x2, 0).T, _int8(dy2, 0), precision=HIGHEST)
    return dx, dw


_int8_mm.defvjp(_int8_mm_fwd, _int8_mm_bwd)


def mm(x, w, quant):
    x, w = x.astype(jnp.float32), w.astype(jnp.float32)
    if quant == "int8":
        return _int8_mm(x, w)
    if quant is not None:
        raise ValueError(f"unknown control precision {quant!r}")
    return jnp.matmul(x, w, precision=HIGHEST)


def rmsnorm(x, w, eps):
    x = x.astype(jnp.float32)
    return x * jax.lax.rsqrt(jnp.mean(x * x, -1, keepdims=True) + eps) \
        * w.astype(jnp.float32)


def rope(x, pos, theta):
    """x [T, heads, D], pos [T]: rotate halves, as published."""
    d = x.shape[-1]
    inv = 1.0 / (theta ** (jnp.arange(0, d, 2, dtype=jnp.float32) / d))
    ang = pos.astype(jnp.float32)[:, None] * inv
    emb = jnp.concatenate([ang, ang], -1)[:, None, :]
    x1, x2 = x[..., :d // 2], x[..., d // 2:]
    return x * jnp.cos(emb) + jnp.concatenate([-x2, x1], -1) * jnp.sin(emb)


def attention(q, k, v):
    """Causal GQA over one sequence: q [T, Hq, D], k/v [T, Hk, D]."""
    t, hq, d = q.shape
    group = hq // k.shape[1]
    k = jnp.repeat(k, group, axis=1)
    v = jnp.repeat(v, group, axis=1)
    s = jnp.einsum("qhd,khd->hqk", q, k, precision=HIGHEST) / np.sqrt(d)
    mask = jnp.tril(jnp.ones((t, t), bool))
    p = jax.nn.softmax(jnp.where(mask[None], s, -jnp.inf), axis=-1)
    return jnp.einsum("hqk,khd->qhd", p, v, precision=HIGHEST)


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
            costs.head_dim(cfg), float(cfg["rms_norm_eps"]),
            float(cfg["rope_theta"]))


_layer_jit = jax.jit(layer_forward, static_argnames=("dims", "quant"))


@functools.partial(jax.jit, static_argnames=("eps", "quant"))
def _head_logits(x, rows, norm, head, eps, quant):
    """Logits at chosen positions: x [B, T, H], rows [B, K] -> [B, K, V]."""
    picked = jnp.take_along_axis(x, rows[:, :, None], axis=1)
    return mm(rmsnorm(picked, norm, eps), head, quant)


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
        out.append(np.asarray(_head_logits(
            x, jnp.asarray(rows[s:s + block]), end_weights["norm"],
            end_weights["head"], eps=dims[3], quant=quant)))
    return np.concatenate(out)


# ---------------------------------------------------------------------------
# training: loss, gradients, AdamW
# ---------------------------------------------------------------------------
def init_params(cfg, seed):
    """Float32 master weights from the seed, as the program is given."""
    p = dict(weights.ends(cfg, seed, "float32"))
    p["layers"] = [weights.layer(cfg, seed, i, "float32")
                   for i in range(cfg["num_hidden_layers"])]
    return p


def flat_leaves(params):
    out = {k: params[k] for k in ("embed", "head", "norm")}
    for i, lw in enumerate(params["layers"]):
        for k, a in lw.items():
            out[f"layers.{i}.{k}"] = a
    return out


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


@jax.jit
def _delta_norms(new, old):
    return jax.tree_util.tree_map(
        lambda a, b: jnp.sqrt(jnp.sum(jnp.square(a - b))), new, old)


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
    delta = _delta_norms(params, init_params(cfg, seed))
    return {"loss": losses, "grad": grad,
            "delta": {k: float(a) for k, a in flat_leaves(delta).items()}}
