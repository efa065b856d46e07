"""RNG state management.

Reference: `paddle/phi/core/generator.h` (global + per-device Generator) and
the model-parallel ``RNGStatesTracker`` (`fleet/layers/mpu/random.py:34`).

TPU-native design: state is a JAX PRNG key. Eager ops split the global key.
Under ``jit`` tracing, a traced key is installed with ``rng_guard`` so the
whole program stays functional (the key becomes an input of the compiled
step). Named-state tracking (``rng_state``) gives model-parallel-safe
dropout: each name folds a distinct constant into the key, the analog of the
reference's per-axis seeded states.
"""

from __future__ import annotations

import contextlib
import zlib

import jax
import numpy as np

__all__ = ["seed", "get_rng_state", "set_rng_state", "next_key", "rng_guard",
           "Generator", "default_generator", "rng_state", "fold_in_name"]


class Generator:
    """Stateful PRNG source backed by a JAX key. The key is made on
    first use, not at construction: building one initializes the JAX
    backend, and ``import paddle_tpu`` (which constructs
    ``default_generator``) must leave the chip to whichever process
    goes on to use it."""

    def __init__(self, seed_val: int = 0, key=None):
        self._seed = seed_val
        self._state = key

    @property
    def _key(self):
        if self._state is None:
            self._state = jax.random.key(self._seed)
        return self._state

    def manual_seed(self, seed_val: int):
        self._seed = seed_val
        self._state = None
        return self

    def initial_seed(self):
        return self._seed

    def get_state(self):
        return self._key

    def set_state(self, state):
        self._state = state

    def next(self):
        self._state, sub = jax.random.split(self._key)
        return sub


default_generator = Generator(np.random.randint(0, 2**31 - 1))

# stack of override generators (installed by rng_guard / rng_state)
_guard_stack: list[Generator] = []


def _current() -> Generator:
    return _guard_stack[-1] if _guard_stack else default_generator


def seed(seed_val: int):
    """``paddle.seed`` — reseed the global generator."""
    default_generator.manual_seed(int(seed_val))
    return default_generator


def get_rng_state():
    return _current().get_state()


def set_rng_state(state):
    _current().set_state(state)


def next_key():
    """Draw a fresh PRNG key from the active generator."""
    return _current().next()


@contextlib.contextmanager
def rng_guard(key):
    """Install ``key`` (possibly a tracer) as the RNG source.

    Used by ``paddle_tpu.jit`` so random ops inside a traced step consume a
    traced key instead of baking host randomness into the compiled program.
    """
    gen = Generator(key=key)
    _guard_stack.append(gen)
    try:
        yield gen
    finally:
        _guard_stack.pop()


def fold_in_name(key, name: str):
    """Deterministically derive a named subkey (stable across processes)."""
    return jax.random.fold_in(key, zlib.crc32(name.encode()) & 0x7FFFFFFF)


@contextlib.contextmanager
def rng_state(name: str = "global"):
    """Model-parallel RNG scope (reference: ``get_rng_state_tracker().rng_state``).

    Inside the scope, keys derive from the active key with ``name`` folded
    in — e.g. tensor-parallel dropout uses a different stream per name while
    staying reproducible.
    """
    base = _current()
    gen = Generator(key=fold_in_name(base.next(), name))
    _guard_stack.append(gen)
    try:
        yield gen
    finally:
        _guard_stack.pop()
