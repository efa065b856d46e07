"""What every family's plain reference shares: straightforward
``jax.numpy``, float32, matmuls at ``highest``. No kernel, no cache, no
batching tricks; nothing here imports the program or takes what the
program made. A family's own mathematics (its layer, its loss, its
optimizer step) lies beside its costs in ``perfbench/families``.

``quant="int8"`` is the control, one implementation for every family:
the same mathematics with every linear layer computed in int8, the
nearest precision below bf16 and the one the v5e's matrix unit would
tempt a later PR with. All three products of a linear layer are rounded:
forward ``x @ w``, and in training ``dy @ w.T`` and ``x.T @ dy``, each
operand scaled along the dimension that is not contracted (the finest
scaling an int8 product allows)."""

import functools

import jax
import jax.numpy as jnp
import numpy as np

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


@functools.partial(jax.jit, static_argnames=("eps", "quant"))
def head_logits(x, rows, norm, head, eps, quant):
    """Logits at chosen positions: x [B, T, H], rows [B, K] -> [B, K, V]."""
    picked = jnp.take_along_axis(x, rows[:, :, None], axis=1)
    return mm(rmsnorm(picked, norm, eps), head, quant)


def flat_leaves(params):
    """``{leaf name: leaf}`` of a tree ``{end leaf: ..., "layers": [{leaf:
    ...}, ...]}``: the names the program's parameters go by."""
    out = {k: a for k, a in params.items() if k != "layers"}
    for i, lw in enumerate(params["layers"]):
        for k, a in lw.items():
            out[f"layers.{i}.{k}"] = a
    return out


@jax.jit
def delta_norms(new, old):
    return jax.tree_util.tree_map(
        lambda a, b: jnp.sqrt(jnp.sum(jnp.square(a - b))), new, old)

