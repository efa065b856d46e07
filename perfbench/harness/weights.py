"""Seeded weights, made on the device by jitted calls, for the program
and the reference alike. The benchmark makes them; the program is given
them; the reference makes its own copy from the same seed.

Names: ``embed`` [V, H], ``head`` [H, V], ``norm`` [H] and per layer
``q k v o gate up down`` ([in, out]) and ``ln1 ln2`` [H]."""

import functools

import jax
import jax.numpy as jnp

from . import costs

INIT_STD = 0.02      # the decoder family's published initializer range


def key_of(seed):
    """A PRNG key from any whole-number seed (more than 32 bits fold)."""
    seed = int(seed) % (1 << 62)
    return jax.random.fold_in(jax.random.PRNGKey(seed & 0x7FFFFFFF),
                              seed >> 31)


def layer_shapes(cfg):
    h, f, d = cfg["hidden_size"], cfg["intermediate_size"], \
        costs.head_dim(cfg)
    nq, nk = cfg["num_attention_heads"], cfg["num_key_value_heads"]
    return {"q": (h, nq * d), "k": (h, nk * d), "v": (h, nk * d),
            "o": (nq * d, h), "gate": (h, f), "up": (h, f),
            "down": (f, h)}


@functools.partial(jax.jit, static_argnames=("shapes", "hidden", "dtype"))
def _layer(key, shapes, hidden, dtype):
    out = {}
    for i, (name, shape) in enumerate(shapes):
        out[name] = (INIT_STD * jax.random.normal(
            jax.random.fold_in(key, i), shape, jnp.float32)).astype(dtype)
    out["ln1"] = jnp.ones((hidden,), dtype)
    out["ln2"] = jnp.ones((hidden,), dtype)
    return out


@functools.partial(jax.jit, static_argnames=("vocab", "hidden", "dtype"))
def _ends(key, vocab, hidden, dtype):
    k1, k2 = jax.random.split(key)
    return {"embed": (INIT_STD * jax.random.normal(
                k1, (vocab, hidden), jnp.float32)).astype(dtype),
            "head": (INIT_STD * jax.random.normal(
                k2, (hidden, vocab), jnp.float32)).astype(dtype),
            "norm": jnp.ones((hidden,), dtype)}


def layer(cfg, seed, index, dtype):
    shapes = tuple(sorted(layer_shapes(cfg).items()))
    return _layer(jax.random.fold_in(key_of(seed), 1 + index), shapes,
                  cfg["hidden_size"], jnp.dtype(dtype))


def ends(cfg, seed, dtype):
    return _ends(jax.random.fold_in(key_of(seed), 0), cfg["vocab_size"],
                 cfg["hidden_size"], jnp.dtype(dtype))
