"""The gated delta rule over rows of a ragged batch (``ops/kda.py``)
against the token recurrence written out in numpy (float64): one-token
rows, chunk rows, rows that stop short, entry states, the promise that
few rows are long; the chunkwise form against the recurrence at strong
decay; the Pallas step (interpret mode here) against the XLA form, in
place on a pool.

Tolerances: both sides are float32 sums in different orders of numbers
of order 1, so outputs and states agree to 2e-5; a state kept in
bfloat16 (2^-9 a value) or a dropped gate is orders of magnitude off."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from paddle_tpu.ops import kda

TOL = 2e-5


def recurrence(q, k, v, g, beta, s0, lens):
    """ISSUE 37's three lines, a token and a head at a time."""
    rows, ql, heads, _ = q.shape
    o = np.zeros((rows, ql, heads, v.shape[-1]))
    s_all = s0.astype(np.float64).copy()
    for r in range(rows):
        for t in range(lens[r]):
            for h in range(heads):
                s = s_all[r, h] * np.exp(g[r, t, h])[:, None]
                u = beta[r, t, h] * (v[r, t, h] - s.T @ k[r, t, h])
                s = s + np.outer(k[r, t, h], u)
                s_all[r, h] = s
                o[r, t, h] = s.T @ q[r, t, h]
    return o, s_all


def operands(rng, rows, ql, heads, kd, vd, decay=0.3):
    q, k = (rng.normal(size=(rows, ql, heads, kd)) for _ in "qk")
    q /= np.linalg.norm(q, axis=-1, keepdims=True) * kd ** 0.5
    k /= np.linalg.norm(k, axis=-1, keepdims=True)
    v = rng.normal(size=(rows, ql, heads, vd))
    g = -decay * rng.uniform(size=(rows, ql, heads, kd))
    beta = rng.uniform(size=(rows, ql, heads))
    s0 = rng.normal(size=(rows, heads, kd, vd))
    return q, k, v, g, beta, s0


def run_rows(ops, lens, long_rows=None):
    fn = jax.jit(lambda *a: kda.kda_rows(*a, long_rows=long_rows))
    o, s = fn(*(jnp.asarray(a, jnp.float32) for a in ops),
              jnp.asarray(lens, jnp.int32))
    return np.asarray(o), np.asarray(s)


def assert_rows(ops, lens, long_rows=None):
    o, s = run_rows(ops, lens, long_rows)
    want_o, want_s = recurrence(*ops, lens)
    live = np.arange(o.shape[1])[None, :] < np.asarray(lens)[:, None]
    assert np.abs(o - want_o)[live].max() < TOL
    assert np.abs(s - want_s).max() < TOL
    return o, s


@pytest.mark.parametrize("ql,lens", [
    (1, [1, 1, 0]),                 # rows of one token, and a row that is none
    (8, [8, 5, 0]),                 # one sub-block; a row that stops short
    (40, [40, 17, 1]),              # three sub-blocks of 16, padded
    (64, [64, 33, 2]),              # a whole chunk
    (150, [150, 70, 64]),           # three chunks, the state carried
], ids=["one-token", "short", "padded", "chunk", "chunks"])
def test_rows_equal_the_recurrence(ql, lens):
    rng = np.random.default_rng(ql)
    ops = operands(rng, len(lens), ql, 2, 16, 8)
    _, s = assert_rows(ops, lens)
    # a row that is none hands its entry state back
    for r, n in enumerate(lens):
        if n == 0:
            assert np.array_equal(s[r], ops[5][r].astype(np.float32))


def test_entry_states_matter_and_zeros_are_a_first_token():
    rng = np.random.default_rng(1)
    ops = operands(rng, 2, 24, 2, 16, 16)
    o, _ = assert_rows(ops, [24, 24])
    zero = ops[:5] + (np.zeros_like(ops[5]),)
    o0, _ = assert_rows(zero, [24, 24])
    assert np.abs(o - o0).max() > 1e-2


def test_long_rows_alone_equal_all_rows():
    """A dispatch's promise (few rows are longer than one token) against
    every row at once, and against the recurrence."""
    rng = np.random.default_rng(2)
    lens = [1, 0, 64, 1, 30, 1, 0, 2]
    ops = operands(rng, len(lens), 64, 2, 16, 8)
    o1, s1 = assert_rows(ops, lens, long_rows=3)
    o0, s0 = run_rows(ops, lens)
    np.testing.assert_allclose(s1, s0, atol=TOL)
    for r, n in enumerate(lens):
        np.testing.assert_allclose(o1[r, :n], o0[r, :n], atol=TOL)
    assert np.array_equal(s1[[1, 6]], ops[5][[1, 6]].astype(np.float32))


def test_strong_decay_over_a_chunk_stays_finite():
    """Log-decays of up to -50 a token: 64 tokens decay by e^-3200, and
    the chunkwise form still takes only differences ``G_t - G_s``."""
    rng = np.random.default_rng(3)
    ops = operands(rng, 2, 64, 2, 16, 16, decay=50.0)
    o, s = assert_rows(ops, [64, 64])
    assert np.isfinite(o).all() and np.isfinite(s).all()


def test_a_dropped_gate_or_a_bf16_state_is_far_outside_the_tolerance():
    rng = np.random.default_rng(4)
    ops = operands(rng, 2, 48, 2, 16, 16)
    want_o, want_s = recurrence(*ops, [48, 48])
    no_gate = ops[:3] + (np.zeros_like(ops[3]),) + ops[4:]
    o, _ = run_rows(no_gate, [48, 48])
    assert np.abs(o - want_o).max() > 1000 * TOL
    bf16 = np.asarray(jnp.asarray(want_s, jnp.bfloat16)
                      .astype(jnp.float32))
    assert np.abs(bf16 - want_s).max() > 20 * TOL


def test_the_convolutions_and_their_new_states():
    rng = np.random.default_rng(5)
    rows, ql, c, width = 4, 6, 12, 4
    x = rng.normal(size=(rows, ql, c)).astype(np.float32)
    prev = rng.normal(size=(rows, width - 1, c)).astype(np.float32)
    w = rng.normal(size=(c, width)).astype(np.float32)
    lens = np.asarray([6, 1, 0, 3], np.int32)
    y, last = kda.kda_conv_rows(*(jnp.asarray(a) for a in (x, prev, w,
                                                           lens)))
    seq = np.concatenate([prev, x], axis=1)
    for r, n in enumerate(lens):
        for t in range(n):
            acc = sum(seq[r, t + j] * w[:, j] for j in range(width))
            np.testing.assert_allclose(y[r, t], acc / (1 + np.exp(-acc)),
                                       atol=1e-6)
        np.testing.assert_array_equal(last[r], seq[r, n:n + width - 1])


@pytest.mark.parametrize("heads", [4, 16], ids=["one-block", "two-blocks"])
def test_the_pallas_step_equals_the_xla_form_in_place(heads):
    """Rows of one token against the state POOL: the row's slot holds
    its new state, a fresh row starts from zeros whatever its slot held,
    every other slot is bitwise as it was."""
    rng = np.random.default_rng(6)
    rows, kd, vd, slots = 5, 16, 16, 8
    q, k, v, g, beta, _ = operands(rng, rows, 1, heads, kd, vd)
    pool = rng.normal(size=(slots, heads, kd, vd)).astype(np.float32)
    slot = np.asarray([3, 0, 6, 5, 2], np.int32)
    fresh = np.asarray([0, 1, 0, 0, 0], bool)
    f32 = lambda a: jnp.asarray(a, jnp.float32)          # noqa: E731
    o, new = jax.jit(kda.kda_step)(
        f32(q[:, 0]), f32(k[:, 0]), f32(v[:, 0]), f32(g[:, 0]),
        f32(beta[:, 0]), jnp.asarray(pool), jnp.asarray(slot),
        jnp.asarray(fresh))
    s0 = np.where(fresh[:, None, None, None], 0.0, pool[slot])
    want_o, want_s = run_rows((q, k, v, g, beta, s0), [1] * rows)
    np.testing.assert_allclose(o, want_o[:, 0], atol=TOL)
    np.testing.assert_allclose(np.asarray(new)[slot], want_s, atol=TOL)
    rest = [i for i in range(slots) if i not in slot]
    assert np.array_equal(np.asarray(new)[rest], pool[rest])
