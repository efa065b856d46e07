"""Seeded weights, made on the device by jitted calls, for the program
and the reference alike. The benchmark makes them; the program is given
them; the reference makes its own copy from the same seed. Which leaves
a model has, and of what shape, is its family's to say
(``perfbench/families``); the key, the range and the maker are shared."""

import functools

import jax
import jax.numpy as jnp

INIT_STD = 0.02      # the published initializer range of every family so far


def key_of(seed):
    """A PRNG key from any whole-number seed (more than 32 bits fold)."""
    seed = int(seed) % (1 << 62)
    return jax.random.fold_in(jax.random.PRNGKey(seed & 0x7FFFFFFF),
                              seed >> 31)


def normal(key, shape, dtype):
    """One seeded normal leaf, drawn in float32 and cast."""
    return (INIT_STD * jax.random.normal(key, shape, jnp.float32)) \
        .astype(dtype)


@functools.partial(jax.jit, static_argnames=("shapes", "dtype"))
def normal_leaves(key, shapes, dtype):
    """``{name: normal leaf}`` for a tuple of ``(name, shape)``, the key
    folded by each leaf's place in it: one call on the device."""
    return {name: normal(jax.random.fold_in(key, i), shape, dtype)
            for i, (name, shape) in enumerate(shapes)}
