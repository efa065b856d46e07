"""Flash attention as Pallas TPU kernels (forward + backward), with GQA.

Capability reference: `python/paddle/nn/functional/flash_attention.py:147`
and the external flash-attn v2 library the reference dynloads
(`paddle/phi/backends/dynload/flashattn.cc`). This is an original
blockwise-softmax implementation in Pallas (TPU-first: MXU matmuls with
fp32 accumulation, VMEM-resident K/V per head, online max/sum rescaling —
no O(S^2) materialization in HBM).

Layout: inputs [B, S, H, D] (the reference's layout). Grouped-query
attention (H query heads sharing H_kv key/value heads, H % H_kv == 0) is
native: the grid is (batch, q_head, q_block) and the K/V BlockSpec index
map points q-head ``h`` at kv-head ``h // group``, so no K/V replication
ever materializes in HBM — the MXU reads the shared heads straight from
VMEM.

Backward uses the standard recomputation split:
  dV_j = sum_i P_ij^T dO_i
  dK_j = sum_i (P_ij ∘ (dP_ij - D_i))^T Q_i * scale
  dQ_i = sum_j (P_ij ∘ (dP_ij - D_i)) K_j * scale
with P recomputed from the saved log-sum-exp rows. The dK/dV kernel runs
per (kv-head, k-block) and walks the query rows of its ``group`` query
heads that see the block, with the keys on the sublanes (scores
transposed), so lse and D are lane-dense rows.

The MXU takes its operands in the input's dtype, as flash-attn v2 feeds
its matmuls: q, k, v and dO tiles as they come, P and dS cast to q's dtype
before the dots that consume them; every dot accumulates in f32, and the
softmax statistics (m, l, lse, D) and the accumulators stay f32. For f32
inputs every cast is a no-op.

The causal walk: the keys a query block sees in full go in wide blocks
with no mask; only the steps that straddle the diagonal build one. Block
sizes follow from the sequence lengths alone (``_walk_blocks``,
``_dkv_blocks``).
"""

from __future__ import annotations

import functools
import math
import warnings

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from ..framework.tensor import run_op

__all__ = ["flash_attention", "supported"]

# sequence lengths are taken in multiples of these (``supported``)
BLOCK_Q = 128
BLOCK_K = 128
NEG_INF = -1e30
_KV_VMEM_BUDGET = 12 * 1024 * 1024
_F32 = jnp.float32
_NT = (((1,), (1,)), ((), ()))      # a @ b.T
_NN = (((1,), (0,)), ((), ()))      # a @ b


def _interpret():
    return jax.default_backend() != "tpu"


def _lanes(d):
    return -(-d // 128) * 128


def supported(q, k, v, attn_mask, causal):
    """Pallas path preconditions; anything else falls back to XLA."""
    if attn_mask is not None:
        return False
    qs = q.shape if not hasattr(q, "_data") else q._data.shape
    ks = k.shape if not hasattr(k, "_data") else k._data.shape
    vs = v.shape if not hasattr(v, "_data") else v._data.shape
    if len(qs) != 4 or len(ks) != 4:
        return False
    if tuple(vs) != tuple(ks):
        return False
    b, sq, h, d = qs
    if ks[0] != b:
        return False
    sk, hk = ks[1], ks[2]
    if hk == 0 or h % hk:
        return False
    if ks[3] != d:
        return False
    if causal and sq > sk:
        # bottom-right alignment gives offset < 0: leading q-blocks would
        # see zero keys (l == 0 -> 0/0 NaN rows); let the XLA path mask them
        return False
    if sq < BLOCK_Q or sk < BLOCK_K:
        return False
    if sq % BLOCK_Q or sk % BLOCK_K:
        return False
    if d % 8 or d > 256:
        return False
    # VMEM, the one refusal that is about size and not form: the fwd and
    # dQ kernels keep the whole (sk, d) K and V of one kv head resident,
    # each double-buffered by the pipeline (lanes pad to 128), beside
    # one query block; the dK/dV kernel keeps one key block and a window
    # of one query head's q and dO rows (with their lse and D rows), as
    # many rows as fit this same budget (``_dkv_window``), so only the
    # first can refuse. The v5e's compiler took 24 MB of such streams
    # and refused 32 MB (tests/test_chip_compile.py compiles the largest
    # lengths accepted).
    itemsize = jnp.dtype(q.dtype).itemsize if hasattr(q, "dtype") else 4
    if 2 * 2 * sk * _lanes(d) * itemsize > _KV_VMEM_BUDGET:
        warnings.warn(
            f"flash attention turns away q{tuple(qs)} k/v{tuple(ks)} "
            f"{jnp.dtype(q.dtype).name}: one kv head's K and V do not "
            f"fit the forward and dQ kernels' VMEM; the XLA path taken "
            f"instead holds the {sq} x {sk} scores of every head",
            stacklevel=2)
        return False
    return True


# ---------------------------------------------------------------------------
# block sizes: from the shape alone
# ---------------------------------------------------------------------------
def _block(n, widest):
    """The largest of ``widest``, ``widest / 2``, ... 128 dividing ``n``."""
    b = widest
    while n % b:
        b //= 2
    return b


def _walk_blocks(sq, sk):
    """(query block, key block) of the forward and dQ kernels."""
    return _block(sq, 128), _block(sk, 512)


def _dkv_blocks(sq, sk):
    """(key block, query step) of the dK/dV kernel."""
    return _block(sk, 512), _block(sq, 128)


def _rows_bytes(n, d, itemsize):
    """VMEM of ``n`` rows of one query head's q and dO, double-buffered,
    with their lse and D rows (f32, 8 sublanes)."""
    return 2 * 2 * n * (_lanes(d) * itemsize + 8 * 4)


def _dkv_window(sq, d, itemsize, step):
    """Query rows of one dK/dV window: a whole head's where they fit the
    budget, else the most rows, a multiple of ``step`` dividing ``sq``,
    that do."""
    fits = [n for n in range(step, sq + 1, step) if sq % n == 0
            and _rows_bytes(n, d, itemsize) <= _KV_VMEM_BUDGET]
    return max(fits, default=step)


def _visible(shape, q0, k0, q_axis):
    """Where a query sees a key in a score tile whose query positions
    (among the keys) start at ``q0`` along ``q_axis`` and whose key
    positions start at ``k0`` along the other axis."""
    qi = jax.lax.broadcasted_iota(jnp.int32, shape, q_axis)
    ki = jax.lax.broadcasted_iota(jnp.int32, shape, 1 - q_axis)
    return qi - ki >= k0 - q0


def _walk(body, carry, *, first, rows, sk, block_k, causal):
    """``body(start, width, masked, carry)`` over the keys a block of
    ``rows`` queries sees, ``first`` the position of its first query among
    the keys (causal alignment is bottom-right: the last query attends to
    every key). Keys every query of the block sees go in blocks of
    ``block_k``, then in steps of ``min(rows, block_k)``, with no mask;
    the steps that straddle the diagonal follow, masked."""
    def loop(start, width, masked, n, carry):
        return jax.lax.fori_loop(
            0, n, lambda j, c: body(start + j * width, width, masked, c),
            carry)

    if not causal:
        return loop(0, block_k, False, sk // block_k, carry)
    step = min(rows, block_k)
    n_big = (first + 1) // block_k
    carry = loop(0, block_k, False, n_big, carry)
    base = n_big * block_k
    if step < block_k:
        n_small = (first + 1 - base) // step
        carry = loop(base, step, False, n_small, carry)
        base = base + n_small * step
    return loop(base, step, True, pl.cdiv(first + rows - base, step), carry)


# ---------------------------------------------------------------------------
# forward kernel: one (batch, q_head, q-block) program; K/V stream in VMEM
# ---------------------------------------------------------------------------
def _fwd_kernel(q_ref, k_ref, v_ref, o_ref, lse_ref, *, scale, causal,
                block_k, offset):
    # ``offset = sk - sq``: causal alignment is bottom-right (last query
    # attends to every key), matching the naive fallback in
    # nn/functional/attention.py
    q = q_ref[0, 0]                              # [Bq, D]
    bq, d = q.shape
    first = pl.program_id(2) * bq + offset

    def body(start, width, masked, carry):
        acc, m_prev, l_prev = carry
        keys = pl.ds(pl.multiple_of(start, 128), width)
        k = k_ref[0, 0, keys, :]
        v = v_ref[0, 0, keys, :]
        s = jax.lax.dot_general(q, k, _NT,
                                preferred_element_type=_F32) * scale
        if masked:
            s = jnp.where(_visible(s.shape, first, start, 0), s, NEG_INF)
        m_new = jnp.maximum(m_prev, jnp.max(s, axis=-1, keepdims=True))
        alpha = jnp.exp(m_prev - m_new)
        p = jnp.exp(s - m_new)
        l_new = l_prev * alpha + jnp.sum(p, axis=-1, keepdims=True)
        acc = acc * alpha + jax.lax.dot_general(
            p.astype(v.dtype), v, _NN, preferred_element_type=_F32)
        return acc, m_new, l_new

    init = (jnp.zeros((bq, d), _F32),
            jnp.full((bq, 1), NEG_INF, _F32),
            jnp.zeros((bq, 1), _F32))
    acc, m, l = _walk(body, init, first=first, rows=bq, sk=k_ref.shape[2],
                      block_k=block_k, causal=causal)
    o_ref[0, 0] = (acc / l).astype(o_ref.dtype)
    # lse is [Bq, 1]: the trailing singleton keeps the Mosaic block 2-D
    # (blocks of a (B, H, Sq) array would be (1, Bq) — second-to-last dim 1
    # fails the sublane-divisibility rule on real TPU lowering)
    lse_ref[0, 0] = m + jnp.log(l)


def _walk_specs(q, k, group):
    """(key block, grid, the q-block, K/V and lse-row windows, compiler
    parameters) of the forward and dQ kernels: a program a (batch, query
    head, query block), the whole K and V of the head's kv head
    resident."""
    b, h, sq, d = q.shape
    sk = k.shape[2]
    bq, bk = _walk_blocks(sq, sk)

    def block(bi, hi, qi):
        return bi, hi, qi, 0

    return (bk, (b, h, sq // bq), pl.BlockSpec((1, 1, bq, d), block),
            pl.BlockSpec((1, 1, sk, d),
                         lambda bi, hi, qi: (bi, hi // group, 0, 0)),
            pl.BlockSpec((1, 1, bq, 1), block))


def _fwd(q, k, v, scale, causal, group):
    """q: [B, H, Sq, D]; k/v: [B, Hk, Sk, D] head-major."""
    b, h, sq, d = q.shape
    bk, grid, q_spec, kv_spec, row_spec = _walk_specs(q, k, group)
    out, lse = pl.pallas_call(
        functools.partial(_fwd_kernel, scale=scale, causal=causal,
                          block_k=bk, offset=k.shape[2] - sq),
        grid=grid,
        in_specs=[q_spec, kv_spec, kv_spec],
        out_specs=[q_spec, row_spec],
        out_shape=[
            jax.ShapeDtypeStruct((b, h, sq, d), q.dtype),
            jax.ShapeDtypeStruct((b, h, sq, 1), _F32),
        ],
        interpret=_interpret(),
        name="paddle_tpu.flash_fwd",
    )(q, k, v)
    return out, lse


# ---------------------------------------------------------------------------
# backward kernels
# ---------------------------------------------------------------------------
def _bwd_dq_kernel(q_ref, k_ref, v_ref, do_ref, lse_ref, delta_ref, dq_ref,
                   *, scale, causal, block_k, offset):
    q = q_ref[0, 0]
    do = do_ref[0, 0]
    lse = lse_ref[0, 0]                         # [Bq, 1]
    delta = delta_ref[0, 0]                     # [Bq, 1]
    bq = q.shape[0]
    first = pl.program_id(2) * bq + offset

    def body(start, width, masked, dq):
        keys = pl.ds(pl.multiple_of(start, 128), width)
        k = k_ref[0, 0, keys, :]
        v = v_ref[0, 0, keys, :]
        s = jax.lax.dot_general(q, k, _NT,
                                preferred_element_type=_F32) * scale
        if masked:
            s = jnp.where(_visible(s.shape, first, start, 0), s, NEG_INF)
        p = jnp.exp(s - lse)
        dp = jax.lax.dot_general(do, v, _NT, preferred_element_type=_F32)
        ds = p * (dp - delta)
        return dq + jax.lax.dot_general(ds.astype(k.dtype), k, _NN,
                                        preferred_element_type=_F32)

    dq = _walk(body, jnp.zeros(q.shape, _F32), first=first, rows=bq,
               sk=k_ref.shape[2], block_k=block_k, causal=causal)
    dq_ref[0, 0] = (dq * scale).astype(dq_ref.dtype)


def _bwd_dq(q, k, v, do, lse, delta, scale, causal, group):
    """dQ [B, H, Sq, D]; lse and delta [B, H, Sq, 1] f32."""
    bk, grid, q_spec, kv_spec, row_spec = _walk_specs(q, k, group)
    return pl.pallas_call(
        functools.partial(_bwd_dq_kernel, scale=scale, causal=causal,
                          block_k=bk, offset=k.shape[2] - q.shape[2]),
        grid=grid,
        in_specs=[q_spec, kv_spec, kv_spec, q_spec, row_spec, row_spec],
        out_specs=q_spec,
        out_shape=jax.ShapeDtypeStruct(q.shape, q.dtype),
        interpret=_interpret(),
        name="paddle_tpu.flash_dq",
    )(q, k, v, do, lse, delta)


def _bwd_dkv_kernel(q_ref, k_ref, v_ref, do_ref, lse_ref, delta_ref,
                    dk_ref, dv_ref, dk_acc, dv_acc, *, scale, causal, step,
                    offset):
    """One (batch, kv-head, k-block) tile of dK and dV, accumulated over
    the grid's last two axes: the group's query heads, then the windows
    of one head's query rows (one window wherever a head's q and dO fit
    VMEM). Each program walks the rows of its window that see the block
    in steps of ``step``: first those that straddle the diagonal, masked,
    then those that see every key of the block, with no mask."""
    ki, g, j = pl.program_id(2), pl.program_id(3), pl.program_id(4)
    bk = k_ref.shape[2]
    window = q_ref.shape[2]
    k0 = ki * bk
    lo = j * window

    @pl.when((g == 0) & (j == 0))
    def _init():
        dk_acc[...] = jnp.zeros_like(dk_acc)
        dv_acc[...] = jnp.zeros_like(dv_acc)

    k = k_ref[0, 0]                              # [Bk, D]
    v = v_ref[0, 0]

    def body(start, masked):
        rows = pl.ds(pl.multiple_of(start - lo, 128), step)
        q = q_ref[0, 0, rows, :]                 # [step, D]
        do = do_ref[0, 0, rows, :]
        lse = lse_ref[0, 0, :, rows]             # [1, step]
        delta = delta_ref[0, 0, :, rows]
        s = jax.lax.dot_general(k, q, _NT,
                                preferred_element_type=_F32) * scale
        if masked:
            s = jnp.where(_visible(s.shape, start + offset, k0, 1), s,
                          NEG_INF)
        p = jnp.exp(s - lse)                     # [Bk, step]
        dv_acc[...] += jax.lax.dot_general(p.astype(do.dtype), do, _NN,
                                           preferred_element_type=_F32)
        dp = jax.lax.dot_general(v, do, _NT, preferred_element_type=_F32)
        ds = p * (dp - delta)
        dk_acc[...] += jax.lax.dot_general(ds.astype(q.dtype), q, _NN,
                                           preferred_element_type=_F32)

    def rows(a, b, masked):
        def one(i, carry):
            body(a + i * step, masked)
            return carry
        jax.lax.fori_loop(0, (b - a) // step, one, 0)

    hi = lo + window
    if causal:
        # rows before the block's first key see none of it; from the
        # first whose position reaches its last key on, rows see all of it
        live = jnp.clip(_first_live_qb(ki, bk, step, offset) * step, lo, hi)
        full = jnp.clip(pl.cdiv(k0 + bk - 1 - offset, step) * step, live, hi)
        rows(live, full, True)
        rows(full, hi, False)
    else:
        rows(lo, hi, False)

    @pl.when((g == pl.num_programs(3) - 1) & (j == pl.num_programs(4) - 1))
    def _finish():
        dk_ref[0, 0] = (dk_acc[...] * scale).astype(dk_ref.dtype)
        dv_ref[0, 0] = dv_acc[...].astype(dv_ref.dtype)


def _first_live_qb(ki, block_k, block_q, offset):
    """First q block a causal k block ``ki`` can see."""
    return jax.lax.max(0, (ki * block_k - offset) // block_q)


def _bwd_dkv(q, k, v, do, lse, delta, scale, causal, group):
    """dK, dV [B, Hk, Sk, D]; lse and delta [B, H, Sq, 1] f32."""
    b, h, sq, d = q.shape
    hk, sk = k.shape[1], k.shape[2]
    bk, step = _dkv_blocks(sq, sk)
    itemsize = jnp.dtype(q.dtype).itemsize
    window = _dkv_window(sq, d, itemsize, step)
    offset = sk - sq
    # lane-dense rows: a [window, 1] f32 block would pad to 128 lanes
    lse, delta = (a.reshape(b, h, 1, sq) for a in (lse, delta))

    def window_of(ki, j):
        # a window whose rows all precede the block re-points at the first
        # live one, which the pipeline holds next: no fetch for it
        return jax.lax.max(j, _first_live_qb(ki, bk, window, offset)) \
            if causal else j

    q_spec = pl.BlockSpec(
        (1, 1, window, d),
        lambda bi, hi, ki, g, j: (bi, hi * group + g, window_of(ki, j), 0))
    row_spec = pl.BlockSpec(
        (1, 1, 1, window),
        lambda bi, hi, ki, g, j: (bi, hi * group + g, 0, window_of(ki, j)))
    k_spec = pl.BlockSpec((1, 1, bk, d),
                          lambda bi, hi, ki, g, j: (bi, hi, ki, 0))
    return pl.pallas_call(
        functools.partial(_bwd_dkv_kernel, scale=scale, causal=causal,
                          step=step, offset=offset),
        grid=(b, hk, sk // bk, group, sq // window),
        in_specs=[q_spec, k_spec, k_spec, q_spec, row_spec, row_spec],
        out_specs=[k_spec, k_spec],
        out_shape=[
            jax.ShapeDtypeStruct((b, hk, sk, d), k.dtype),
            jax.ShapeDtypeStruct((b, hk, sk, d), v.dtype),
        ],
        scratch_shapes=[pltpu.VMEM((bk, d), _F32),
                        pltpu.VMEM((bk, d), _F32)],
        interpret=_interpret(),
        name="paddle_tpu.flash_dkdv",
    )(q, k, v, do, lse, delta)


def _bwd(scale, causal, group, res, g):
    qh, kh, vh, out, lse = res                   # head-major
    do = g.astype(qh.dtype)
    delta = jnp.sum(do.astype(_F32) * out.astype(_F32),
                    axis=-1, keepdims=True)      # [B, H, Sq, 1]
    dq = _bwd_dq(qh, kh, vh, do, lse, delta, scale, causal, group)
    dk, dv = _bwd_dkv(qh, kh, vh, do, lse, delta, scale, causal, group)
    return dq, dk, dv


@functools.lru_cache(maxsize=64)
def _make_flash(scale, causal, group):
    """Build the custom-vjp function for a given static config. Memoized:
    JAX's compilation cache keys on callable identity, so a fresh closure
    per call would recompile the kernels every eager step."""

    @jax.custom_vjp
    def fa(q, k, v):
        # [B, S, H, D] -> head-major [B, H, S, D]
        out, _ = _fwd(q.transpose(0, 2, 1, 3), k.transpose(0, 2, 1, 3),
                      v.transpose(0, 2, 1, 3), scale, causal, group)
        return out.transpose(0, 2, 1, 3)

    def fa_fwd(q, k, v):
        qh = q.transpose(0, 2, 1, 3)
        kh = k.transpose(0, 2, 1, 3)
        vh = v.transpose(0, 2, 1, 3)
        out, lse = _fwd(qh, kh, vh, scale, causal, group)
        return out.transpose(0, 2, 1, 3), (qh, kh, vh, out, lse)

    def fa_bwd(res, g):
        dq, dk, dv = _bwd(scale, causal, group, res,
                          g.transpose(0, 2, 1, 3))
        to_bshd = lambda x: x.transpose(0, 2, 1, 3)
        return to_bshd(dq), to_bshd(dk), to_bshd(dv)

    fa.defvjp(fa_fwd, fa_bwd)
    return fa


def flash_attention(query, key, value, attn_mask=None, causal=False,
                    scale=None):
    """Tape-integrated flash attention; q [B,S,H,D], k/v [B,S,Hk,D] with
    H % Hk == 0 (GQA/MQA native — no K/V replication)."""
    if not supported(query, key, value, attn_mask, causal):
        raise ValueError(
            "flash_attention Pallas preconditions not met (need 4-D "
            f"[B,S,H,D], S % {BLOCK_Q} == 0, head_dim % 8 == 0 and <= 256, "
            "num_heads divisible by num_kv_heads, attn_mask None); use "
            "scaled_dot_product_attention for the XLA fallback")
    qs = query._data.shape if hasattr(query, "_data") else query.shape
    ks = key._data.shape if hasattr(key, "_data") else key.shape
    b, sq, h, d = qs
    hk = ks[2]
    s = scale if scale is not None else 1.0 / math.sqrt(d)
    fa = _make_flash(s, bool(causal), h // hk)
    return run_op("flash_attention", fa, (query, key, value))
