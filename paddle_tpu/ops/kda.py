"""The gated delta rule (Kimi Delta Attention) over rows of a ragged batch.

A KDA mixer keeps, per sequence and head, a matrix state ``S [K, V]``
(float32) and the last ``W - 1`` inputs of its three short causal
convolutions. Over a row of ``Q`` tokens (a prefill chunk, a decode step
of one token, or a whole sequence), with per-token, per-head operands
``q, k [K]`` (L2-normalised, ``q`` scaled), ``v [V]``, the per-CHANNEL
log-decay ``g [K] <= 0`` and the write strength ``beta`` in (0, 1):

    S' = diag(exp(g_t)) S_{t-1}
    S_t = S' + beta_t k_t (v_t - S'^T k_t)^T
    o_t = S_t^T q_t

Rows are independent: each starts from the state it is handed (zeros at
a sequence's first token, else what the sequence's slot holds), stops at
its own length and hands its last state back. The state, ``g`` and
``beta`` are float32 whatever the model's dtype.

Two programs compute it, under the scope ``paddle_tpu.kda_scan``:

- `kda_rows`, XLA, the CHUNKWISE form on the matrix unit: with ``G_t =
  sum_{s<=t} g_s`` inside a chunk of ``C`` tokens and ``S_0`` its entry
  state, ``A[t, s] = sum_c k_t[c] k_s[c] exp(G_t[c] - G_s[c])`` (``s <
  t``), ``Q[t, s]`` likewise with ``q_t`` (``s <= t``); the unit
  lower-triangular system ``(I + diag(beta) tril(A, -1)) U = diag(beta)
  (V - (K * exp(G)) S_0)``; ``o_t = S_0^T (q_t * exp(G_t)) + sum_{s<=t}
  Q[t, s] u_s``; ``S_C = diag(exp(G_C)) S_0 + sum_s (k_s * exp(G_C -
  G_s)) u_s^T``. Every ``exp`` takes a difference ``G_t - G_s`` with ``t
  >= s`` (never ``1 / exp(G_s)`` alone), so the strongest decay
  underflows to 0 and nothing overflows: inside a sub-block of 16 tokens
  the differences are taken token by token, between sub-blocks through
  the later block's first ``G`` (both factors then decay).
- `kda_step`, Pallas (``paddle_tpu.kda_step``), a row of ONE token (every
  decode row of a dispatch): one pass over the row's state, picked out of
  the pool by its slot and written back in place.

Everything else here is traceable ``jax.numpy``; the serving step and
the model's plain forward call the same functions."""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp

try:
    from jax.experimental import pallas as pl
    from jax.experimental.pallas import tpu as pltpu
    _HAS_PLTPU = True
except Exception:                                   # pragma: no cover
    pl = pltpu = None
    _HAS_PLTPU = False

__all__ = ["kda_conv_rows", "kda_rows", "kda_step", "SCOPE", "CHUNK"]

SCOPE = "paddle_tpu.kda_scan"
CHUNK = 64          # tokens a chunk of the chunkwise form
SUB = 16            # tokens a sub-block whose decays are taken pairwise
HEADS_PER_BLOCK = 8     # heads of a row that one grid step of kda_step takes
_HI = jax.lax.Precision.HIGHEST


def _interpret():
    return jax.default_backend() != "tpu"


def kda_conv_rows(x, prev, w, q_lens):
    """Causal depthwise convolution (no bias) over each row, then SiLU,
    and the row's new convolution state.

    ``x [R, Q, C]`` the rows' inputs (the q, k and v streams side by
    side), ``prev [R, W-1, C]`` the ``W-1`` inputs before each row
    (zeros at a sequence's start), ``w [C, W]`` (``w[:, W-1]`` weighs the
    current input), ``q_lens [R]``. Returns ``(silu(conv) [R, Q, C]``
    float32, the last ``W-1`` inputs up to each row's length ``[R, W-1,
    C]`` in ``prev``'s dtype``)``."""
    with jax.named_scope(SCOPE):
        width, q = w.shape[1], x.shape[1]
        seq = jnp.concatenate([prev.astype(x.dtype), x], axis=1)
        wf = w.astype(jnp.float32)
        acc = jnp.zeros(x.shape, jnp.float32)
        for j in range(width):
            acc = acc + seq[:, j:j + q, :].astype(jnp.float32) \
                * wf[None, None, :, j]
        # inputs q_len-(W-1) .. q_len-1 of the row sit at seq[q_len ..]
        at = q_lens.astype(jnp.int32)[:, None] \
            + jnp.arange(width - 1, dtype=jnp.int32)[None, :]
        last = jnp.take_along_axis(seq, at[:, :, None], axis=1)
        return jax.nn.silu(acc), last.astype(prev.dtype)


# ---------------------------------------------------------------------------
# the chunkwise form (XLA)
# ---------------------------------------------------------------------------
def _chunk(q, k, v, g, beta, s0, sub):
    """One chunk of every row: ``q, k, g [N, H, C, K]``, ``v [N, H, C,
    V]``, ``beta [N, H, C]``, ``s0 [N, H, K, V]``, all float32; ``C`` a
    multiple of ``sub``. A token past its row's length comes with ``g =
    0`` and ``beta = 0`` and so leaves the state alone. Returns ``(o [N,
    H, C, V], the state after the chunk)``."""
    n, h, c, kd = q.shape
    nb = c // sub
    neg = -jnp.inf
    gc = jnp.cumsum(g, axis=2)                               # G_t
    gb = gc.reshape(n, h, nb, sub, kd)
    # the G before each sub-block's first token: what a pair of tokens
    # in different sub-blocks decays through
    ref = jnp.concatenate([jnp.zeros((n, h, 1, kd), jnp.float32),
                           gb[:, :, :-1, -1]], axis=2)       # [N,H,nb,K]
    dec = jnp.exp(gb - ref[:, :, :, None])                   # t's side
    kb, qb = k.reshape(gb.shape), q.reshape(gb.shape)
    earlier = jnp.arange(c)[None, :] < (jnp.arange(nb) * sub)[:, None]
    inc = jnp.exp(jnp.where(earlier[:, :, None],
                            ref[:, :, :, None] - gc[:, :, None], neg))
    k_inc = k[:, :, None] * inc                              # [N,H,nb,C,K]
    a_off = jnp.einsum("nhitk,nhisk->nhits", kb * dec, k_inc,
                       precision=_HI)
    q_off = jnp.einsum("nhitk,nhisk->nhits", qb * dec, k_inc,
                       precision=_HI)
    # inside a sub-block: exp(G_t - G_s), pair by pair
    tri = jnp.arange(sub)[:, None] >= jnp.arange(sub)[None, :]
    pair = jnp.exp(jnp.where(tri[:, :, None],
                             gb[:, :, :, :, None] - gb[:, :, :, None],
                             neg))                           # [.,t,s,K]
    ks = kb[:, :, :, None] * pair
    a_in = jnp.sum(kb[:, :, :, :, None] * ks, axis=-1)       # [N,H,nb,t,s]
    q_in = jnp.sum(qb[:, :, :, :, None] * ks, axis=-1)
    own = jnp.eye(nb, dtype=jnp.float32)[:, None, :, None]

    def whole(off, inside):
        full = off.reshape(n, h, nb, sub, nb, sub) \
            + own * inside[:, :, :, :, None, :]
        return full.reshape(n, h, c, c)

    amat, qmat = whole(a_off, a_in), whole(q_off, q_in)
    lower = jnp.tril(jnp.ones((c, c), bool), -1)
    lhs = jnp.where(lower, beta[..., None] * amat, 0.0) \
        + jnp.eye(c, dtype=jnp.float32)
    eg = jnp.exp(gc)
    rhs = beta[..., None] * (v - jnp.einsum(
        "nhck,nhkv->nhcv", k * eg, s0, precision=_HI))
    # (a chunk of one token has nothing under its diagonal)
    u = rhs if c == 1 else jax.scipy.linalg.solve_triangular(
        lhs, rhs, lower=True, unit_diagonal=True)
    o = jnp.einsum("nhck,nhkv->nhcv", q * eg, s0, precision=_HI) \
        + jnp.einsum("nhts,nhsv->nhtv",
                     jnp.where(lower | jnp.eye(c, dtype=bool), qmat, 0.0),
                     u, precision=_HI)
    g_end = gc[:, :, -1]
    s = jnp.exp(g_end)[..., None] * s0 + jnp.einsum(
        "nhsk,nhsv->nhkv", k * jnp.exp(g_end[:, :, None] - gc), u,
        precision=_HI)
    return o, s


def _rows(q, k, v, g, beta, s0, q_lens):
    """Every row at once, a chunk at a time: ``q, k, g [N, Q, H, K]``
    etc. as `kda_rows` takes them."""
    n, ql = q.shape[0], q.shape[1]
    f32 = jnp.float32
    live = (jnp.arange(ql, dtype=jnp.int32)[None, :]
            < q_lens.astype(jnp.int32)[:, None])             # [N, Q]
    g = jnp.where(live[:, :, None, None], g.astype(f32), 0.0)
    beta = jnp.where(live[:, :, None], beta.astype(f32), 0.0)
    if ql <= SUB:
        c = sub = ql
    else:
        c = min(CHUNK, -(-ql // SUB) * SUB)
        sub = SUB
    pad = -ql % c

    def lay(a):             # [N, Q, H, .] -> [chunks, N, H, C, .]
        a = jnp.pad(a.astype(f32), ((0, 0), (0, pad)) + ((0, 0),)
                    * (a.ndim - 2))
        a = a.reshape((n, (ql + pad) // c, c) + a.shape[2:])
        return jnp.moveaxis(jnp.moveaxis(a, 1, 0), 3, 2)

    ops = tuple(lay(a) for a in (q, k, v, g)) \
        + (lay(beta[..., None])[..., 0],)

    def tick(s, t):
        o, s = _chunk(*t, s, sub)
        return s, o

    if ops[0].shape[0] == 1:
        o, s = _chunk(*(a[0] for a in ops), s0.astype(f32), sub)
        o = o[None]
    else:
        s, o = jax.lax.scan(tick, s0.astype(f32), ops)
    # [chunks, N, H, C, V] -> [N, Q, H, V]
    o = jnp.moveaxis(jnp.moveaxis(o, 2, 3), 0, 1)
    return o.reshape((n, ql + pad) + o.shape[3:])[:, :ql], s


def kda_rows(q, k, v, g, beta, s0, q_lens, long_rows=None):
    """The gated delta rule of the module docstring over each row.

    ``q, k [R, Q, H, K]`` (normalised; ``q`` scaled), ``v [R, Q, H, V]``,
    ``g [R, Q, H, K]`` float32 (``<= 0``), ``beta [R, Q, H]`` float32,
    ``s0 [R, H, K, V]`` float32, ``q_lens [R]``. Returns ``(o [R, Q, H,
    V]`` float32, the state after each row's last token ``[R, H, K, V]``
    float32``)``; tokens at or past a row's length leave the state alone
    and their ``o`` is not meaningful.

    ``long_rows`` (static) promises that at most that many rows are
    longer than one token (a serving dispatch: a few prefill chunks
    beside a batch of decode rows). Token 0 of every row is then one
    step over all rows, and the long rows are computed whole, one row at
    a time, in a loop that runs once a long row that is THERE: a
    dispatch pays for the chunk rows it carries, not for the most it
    could."""
    with jax.named_scope(SCOPE):
        rows, ql = q.shape[0], q.shape[1]
        n = q_lens.astype(jnp.int32)
        if long_rows is None or ql == 1:
            return _rows(q, k, v, g, beta, s0, n)
        # (a dispatch whose one-token rows went through `kda_step`
        # hands over chunk rows alone: the first step is then skipped)
        first = tuple(a[:, :1] for a in (q, k, v, g, beta))
        o0, s = jax.lax.cond(
            jnp.any(n == 1),
            lambda: _rows(*first, s0, jnp.minimum(n, 1)),
            lambda: (jnp.zeros((rows, 1) + v.shape[2:], jnp.float32),
                     s0.astype(jnp.float32)))
        o = jnp.concatenate(
            [o0, jnp.zeros((rows, ql - 1) + o0.shape[2:], jnp.float32)],
            axis=1)
        at = jnp.nonzero(n > 1, size=long_rows, fill_value=0)[0]

        def one(i, carry):
            o, s = carry
            r = at[i]

            def take(a):
                return jax.lax.dynamic_index_in_dim(a, r, 0, keepdims=True)

            o_r, s_r = _rows(*(take(a) for a in (q, k, v, g, beta, s0)),
                             take(n))
            return (jax.lax.dynamic_update_index_in_dim(o, o_r[0], r, 0),
                    jax.lax.dynamic_update_index_in_dim(s, s_r[0], r, 0))

        count = jnp.minimum(jnp.sum(n > 1), long_rows).astype(jnp.int32)
        return jax.lax.fori_loop(0, count, one, (o, s))


# ---------------------------------------------------------------------------
# a row of one token (Pallas)
# ---------------------------------------------------------------------------
def _kda_step_kernel(slot_ref, fresh_ref, qkg_ref, v_ref, b_ref, s_ref,
                     o_ref, s_out_ref, *, heads):
    """One grid step: ``heads`` heads of one row. ``qkg_ref [1, 1, K,
    L]`` holds this block's ``q | k | g`` with the channels on the
    SUBLANES (a head a lane: a column of it scales the state's rows);
    ``v_ref, b_ref [1, heads, V]`` the values and ``beta`` along the
    lanes; ``s_ref [1, heads, K, V]`` the row's slot of the pool."""
    del slot_ref                    # read by the index maps
    fresh = fresh_ref[pl.program_id(0)] != 0
    a = qkg_ref[0, 0]
    for h in range(heads):
        qc = a[:, h:h + 1]
        kc = a[:, heads + h:heads + h + 1]
        gcol = a[:, 2 * heads + h:2 * heads + h + 1]
        s = jnp.where(fresh, 0.0, s_ref[0, h]) * jnp.exp(gcol)
        b = b_ref[0, h:h + 1, :]
        u = b * (v_ref[0, h:h + 1, :]
                 - jnp.sum(s * kc, axis=0, keepdims=True))
        s = s + kc * u
        o_ref[0, h:h + 1, :] = jnp.sum(s * qc, axis=0, keepdims=True)
        s_out_ref[0, h] = s


@functools.lru_cache(maxsize=32)
def _make_step(rows, slots, h, kd, vd, hb, lanes, interpret):
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=2,
        grid=(rows, h // hb),
        in_specs=[
            pl.BlockSpec((1, 1, kd, lanes), lambda r, j, sl, fr: (r, j, 0, 0)),
            pl.BlockSpec((1, hb, vd), lambda r, j, sl, fr: (r, j, 0)),
            pl.BlockSpec((1, hb, vd), lambda r, j, sl, fr: (r, j, 0)),
            pl.BlockSpec((1, hb, kd, vd),
                         lambda r, j, sl, fr: (sl[r], j, 0, 0)),
        ],
        out_specs=[
            pl.BlockSpec((1, hb, vd), lambda r, j, sl, fr: (r, j, 0)),
            pl.BlockSpec((1, hb, kd, vd),
                         lambda r, j, sl, fr: (sl[r], j, 0, 0)),
        ],
    )
    f32 = jnp.float32

    def call(slot, fresh, qkg, v, b, pool):
        return pl.pallas_call(
            functools.partial(_kda_step_kernel, heads=hb),
            grid_spec=grid_spec,
            out_shape=[jax.ShapeDtypeStruct((rows, h, vd), f32),
                       jax.ShapeDtypeStruct((slots, h, kd, vd), f32)],
            # the pool (operand 5, the scalars counted) is updated in
            # place: a slot no row names is never touched
            input_output_aliases={5: 1},
            compiler_params=pltpu.CompilerParams(
                dimension_semantics=("arbitrary", "arbitrary")),
            interpret=interpret,
            name="paddle_tpu.kda_step",
        )(slot, fresh, qkg, v, b, pool)

    return call


def kda_step(q, k, v, g, beta, pool, slots, fresh):
    """One token of every row against the state POOL, in place.

    ``q, k, g [R, H, K]``, ``v [R, H, V]``, ``beta [R, H]`` (float32
    mathematics whatever they come in), ``pool [S, H, K, V]`` float32,
    ``slots [R]`` the slot each row's state lies in, ``fresh [R]``
    (bool) the rows that start from zeros. Returns ``(o [R, H, V]``
    float32, the pool``)``: slot ``slots[r]`` holds row ``r``'s new
    state and every other slot is as it was. Rows must name different
    slots, but any number may name one slot that holds nothing (the
    engine's last: where rows that are none read and write)."""
    if not _HAS_PLTPU:
        raise RuntimeError("kda_step needs jax.experimental.pallas.tpu")
    with jax.named_scope(SCOPE):
        rows, h, kd = q.shape
        vd = v.shape[-1]
        hb = HEADS_PER_BLOCK if h % HEADS_PER_BLOCK == 0 else h
        lanes = -(-3 * hb // 128) * 128
        f32 = jnp.float32

        def cols(a):        # [R, H, K] -> [R, H/hb, K, hb]: a head a lane
            return jnp.swapaxes(a.astype(f32).reshape(rows, h // hb, hb,
                                                      kd), 2, 3)

        qkg = jnp.concatenate(
            [cols(q), cols(k), cols(g),
             jnp.zeros((rows, h // hb, kd, lanes - 3 * hb), f32)], axis=-1)
        b = jnp.broadcast_to(beta.astype(f32)[:, :, None], (rows, h, vd))
        call = _make_step(rows, pool.shape[0], h, kd, vd, hb, lanes,
                          _interpret())
        o, pool = call(slots.astype(jnp.int32), fresh.astype(jnp.int32),
                       qkg, v.astype(f32), b, pool)
        return o, pool
