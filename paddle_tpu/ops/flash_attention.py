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
per (kv-head, k-block) and accumulates over its ``group`` query heads'
q-blocks along the last grid axis, one 128-row tile at a time.
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

BLOCK_Q = 128
BLOCK_K = 128
NEG_INF = -1e30
_KV_VMEM_BUDGET = 12 * 1024 * 1024


def _interpret():
    return jax.default_backend() != "tpu"


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
    # each double-buffered by the pipeline (lanes pad to 128); every
    # other window of the three kernels is one 128-row tile. The v5e's
    # compiler took 24 MB of such streams and refused 32 MB
    # (tests/test_chip_compile.py walks the boundary's safe side).
    itemsize = jnp.dtype(q.dtype).itemsize if hasattr(q, "dtype") else 4
    if 2 * 2 * sk * (-(-d // 128) * 128) * itemsize > _KV_VMEM_BUDGET:
        warnings.warn(
            f"flash attention turns away q{tuple(qs)} k/v{tuple(ks)} "
            f"{jnp.dtype(q.dtype).name}: one kv head's K and V do not "
            f"fit the kernels' VMEM; the XLA path taken instead holds "
            f"the {sq} x {sk} scores of every head", stacklevel=2)
        return False
    return True


# ---------------------------------------------------------------------------
# forward kernel: one (batch, q_head, q-block) program; K/V stream in VMEM
# ---------------------------------------------------------------------------
def _fwd_kernel(q_ref, k_ref, v_ref, o_ref, lse_ref, *, scale, causal,
                block_k, offset):
    # ``offset = sk - sq``: causal alignment is bottom-right (last query
    # attends to every key), matching the naive fallback in
    # nn/functional/attention.py
    q = q_ref[0, 0].astype(jnp.float32)         # [Bq, D]
    sk = k_ref.shape[2]
    num_kb = sk // block_k
    qi = pl.program_id(2)

    def body(j, carry):
        acc, m_prev, l_prev = carry
        k = k_ref[0, 0, pl.ds(j * block_k, block_k), :].astype(jnp.float32)
        v = v_ref[0, 0, pl.ds(j * block_k, block_k), :].astype(jnp.float32)
        s = jax.lax.dot_general(
            q, k, (((1,), (1,)), ((), ())),
            preferred_element_type=jnp.float32) * scale   # [Bq, Bk]
        if causal:
            q_pos = qi * q.shape[0] + jax.lax.broadcasted_iota(
                jnp.int32, s.shape, 0) + offset
            k_pos = j * block_k + jax.lax.broadcasted_iota(
                jnp.int32, s.shape, 1)
            s = jnp.where(q_pos >= k_pos, s, NEG_INF)
        m_cur = jnp.max(s, axis=-1, keepdims=True)        # [Bq, 1]
        m_new = jnp.maximum(m_prev, m_cur)
        alpha = jnp.exp(m_prev - m_new)
        p = jnp.exp(s - m_new)
        l_new = l_prev * alpha + jnp.sum(p, axis=-1, keepdims=True)
        acc = acc * alpha + jax.lax.dot_general(
            p, v, (((1,), (0,)), ((), ())),
            preferred_element_type=jnp.float32)
        return acc, m_new, l_new

    bq, d = q.shape
    init = (jnp.zeros((bq, d), jnp.float32),
            jnp.full((bq, 1), NEG_INF, jnp.float32),
            jnp.zeros((bq, 1), jnp.float32))
    if causal:
        # only blocks with k_start <= last query position contribute
        last = (qi + 1) * bq + offset
        num_iters = jax.lax.min(num_kb, pl.cdiv(last, block_k))
    else:
        num_iters = num_kb
    acc, m, l = jax.lax.fori_loop(0, num_iters, body, init)
    o_ref[0, 0] = (acc / l).astype(o_ref.dtype)
    # lse is [Bq, 1]: the trailing singleton keeps the Mosaic block 2-D
    # (blocks of a (B, H, Sq) array would be (1, Bq) — second-to-last dim 1
    # fails the sublane-divisibility rule on real TPU lowering)
    lse_ref[0, 0] = m + jnp.log(l)


def _fwd(q, k, v, scale, causal, group):
    """q: [B, H, Sq, D]; k/v: [B, Hk, Sk, D] head-major."""
    b, h, sq, d = q.shape
    sk = k.shape[2]
    grid = (b, h, sq // BLOCK_Q)
    out, lse = pl.pallas_call(
        functools.partial(_fwd_kernel, scale=scale, causal=causal,
                          block_k=BLOCK_K, offset=sk - sq),
        grid=grid,
        in_specs=[
            pl.BlockSpec((1, 1, BLOCK_Q, d), lambda bi, hi, qi: (bi, hi, qi, 0)),
            pl.BlockSpec((1, 1, sk, d),
                         lambda bi, hi, qi: (bi, hi // group, 0, 0)),
            pl.BlockSpec((1, 1, sk, d),
                         lambda bi, hi, qi: (bi, hi // group, 0, 0)),
        ],
        out_specs=[
            pl.BlockSpec((1, 1, BLOCK_Q, d), lambda bi, hi, qi: (bi, hi, qi, 0)),
            pl.BlockSpec((1, 1, BLOCK_Q, 1), lambda bi, hi, qi: (bi, hi, qi, 0)),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((b, h, sq, d), q.dtype),
            jax.ShapeDtypeStruct((b, h, sq, 1), jnp.float32),
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
    q = q_ref[0, 0].astype(jnp.float32)
    do = do_ref[0, 0].astype(jnp.float32)
    lse = lse_ref[0, 0]                         # [Bq, 1]
    delta = delta_ref[0, 0]                     # [Bq, 1]
    sk = k_ref.shape[2]
    num_kb = sk // block_k
    qi = pl.program_id(2)
    bq = q.shape[0]

    def body(j, dq):
        k = k_ref[0, 0, pl.ds(j * block_k, block_k), :].astype(jnp.float32)
        v = v_ref[0, 0, pl.ds(j * block_k, block_k), :].astype(jnp.float32)
        s = jax.lax.dot_general(q, k, (((1,), (1,)), ((), ())),
                                preferred_element_type=jnp.float32) * scale
        if causal:
            q_pos = qi * bq + jax.lax.broadcasted_iota(
                jnp.int32, s.shape, 0) + offset
            k_pos = j * block_k + jax.lax.broadcasted_iota(
                jnp.int32, s.shape, 1)
            s = jnp.where(q_pos >= k_pos, s, NEG_INF)
        p = jnp.exp(s - lse)
        dp = jax.lax.dot_general(do, v, (((1,), (1,)), ((), ())),
                                 preferred_element_type=jnp.float32)
        ds = p * (dp - delta) * scale
        return dq + jax.lax.dot_general(ds, k, (((1,), (0,)), ((), ())),
                                        preferred_element_type=jnp.float32)

    if causal:
        num_iters = jax.lax.min(num_kb,
                                pl.cdiv((qi + 1) * bq + offset, block_k))
    else:
        num_iters = num_kb
    dq = jax.lax.fori_loop(0, num_iters, body,
                           jnp.zeros(q.shape, jnp.float32))
    dq_ref[0, 0] = dq.astype(dq_ref.dtype)


def _bwd_dkv_kernel(q_ref, k_ref, v_ref, do_ref, lse_ref, delta_ref,
                    dk_ref, dv_ref, dk_acc, dv_acc, *, scale, causal,
                    block_q, offset, num_qb):
    """One (batch, kv-head, k-block) output tile, accumulated over the
    LAST grid axis: step ``j`` brings in q-block ``j % num_qb`` of the
    group's query head ``j // num_qb``, so the q/do/lse/delta windows
    are one (block_q, D) tile each and VMEM does not grow with the
    sequence length."""
    ki = pl.program_id(2)
    j = pl.program_id(3)
    i = j % num_qb
    bk = k_ref.shape[2]

    @pl.when(j == 0)
    def _init():
        dk_acc[...] = jnp.zeros_like(dk_acc)
        dv_acc[...] = jnp.zeros_like(dv_acc)

    # causal: q blocks whose last position precedes this k block never
    # attend (`_dkv_q_index` parks their fetch on the first live block)
    live = i >= _first_live_qb(ki, bk, block_q, offset) if causal \
        else True

    @pl.when(live)
    def _accumulate():
        k = k_ref[0, 0].astype(jnp.float32)          # [Bk, D]
        v = v_ref[0, 0].astype(jnp.float32)
        q = q_ref[0, 0].astype(jnp.float32)          # [Bq, D]
        do = do_ref[0, 0].astype(jnp.float32)
        lse = lse_ref[0, 0]                          # [Bq, 1]
        delta = delta_ref[0, 0]
        s = jax.lax.dot_general(q, k, (((1,), (1,)), ((), ())),
                                preferred_element_type=jnp.float32) * scale
        if causal:
            q_pos = i * block_q + jax.lax.broadcasted_iota(
                jnp.int32, s.shape, 0) + offset
            k_pos = ki * bk + jax.lax.broadcasted_iota(
                jnp.int32, s.shape, 1)
            s = jnp.where(q_pos >= k_pos, s, NEG_INF)
        p = jnp.exp(s - lse)                          # [Bq, Bk]
        dv_acc[...] += jax.lax.dot_general(
            p, do, (((0,), (0,)), ((), ())),
            preferred_element_type=jnp.float32)
        dp = jax.lax.dot_general(do, v, (((1,), (1,)), ((), ())),
                                 preferred_element_type=jnp.float32)
        ds = p * (dp - delta) * scale
        dk_acc[...] += jax.lax.dot_general(
            ds, q, (((0,), (0,)), ((), ())),
            preferred_element_type=jnp.float32)

    @pl.when(j == pl.num_programs(3) - 1)
    def _finish():
        dk_ref[0, 0] = dk_acc[...].astype(dk_ref.dtype)
        dv_ref[0, 0] = dv_acc[...].astype(dv_ref.dtype)


def _first_live_qb(ki, block_k, block_q, offset):
    """First q block a causal k block ``ki`` can see."""
    return jax.lax.max(0, (ki * block_k - offset) // block_q)


def _bwd(scale, causal, group, res, g):
    qh, kh, vh, out, lse = res                   # head-major
    b, h, sq, d = qh.shape
    hk, sk = kh.shape[1], kh.shape[2]
    do = g
    delta = jnp.sum(do.astype(jnp.float32) * out.astype(jnp.float32),
                    axis=-1, keepdims=True)      # [B, H, Sq, 1]
    dq = pl.pallas_call(
        functools.partial(_bwd_dq_kernel, scale=scale, causal=causal,
                          block_k=BLOCK_K, offset=sk - sq),
        grid=(b, h, sq // BLOCK_Q),
        in_specs=[
            pl.BlockSpec((1, 1, BLOCK_Q, d), lambda bi, hi, qi: (bi, hi, qi, 0)),
            pl.BlockSpec((1, 1, sk, d),
                         lambda bi, hi, qi: (bi, hi // group, 0, 0)),
            pl.BlockSpec((1, 1, sk, d),
                         lambda bi, hi, qi: (bi, hi // group, 0, 0)),
            pl.BlockSpec((1, 1, BLOCK_Q, d), lambda bi, hi, qi: (bi, hi, qi, 0)),
            pl.BlockSpec((1, 1, BLOCK_Q, 1), lambda bi, hi, qi: (bi, hi, qi, 0)),
            pl.BlockSpec((1, 1, BLOCK_Q, 1), lambda bi, hi, qi: (bi, hi, qi, 0)),
        ],
        out_specs=pl.BlockSpec((1, 1, BLOCK_Q, d),
                               lambda bi, hi, qi: (bi, hi, qi, 0)),
        out_shape=jax.ShapeDtypeStruct((b, h, sq, d), qh.dtype),
        interpret=_interpret(),
        name="paddle_tpu.flash_dq",
    )(qh, kh, vh, do, lse, delta)
    # per-kv-head: the group's query heads are a contiguous head block,
    # walked (head, q-block) by the last grid axis
    num_qb = sq // BLOCK_Q
    offset = sk - sq

    def q_index(bi, hi, ki, j):
        i = j % num_qb
        if causal:
            # a dead q block re-points at the first live one, which the
            # pipeline already holds: no fetch for a step that is skipped
            i = jax.lax.max(i, _first_live_qb(ki, BLOCK_K, BLOCK_Q,
                                              offset))
        return bi, hi * group + j // num_qb, i, 0

    def k_index(bi, hi, ki, j):
        return bi, hi, ki, 0

    dk, dv = pl.pallas_call(
        functools.partial(_bwd_dkv_kernel, scale=scale, causal=causal,
                          block_q=BLOCK_Q, offset=offset, num_qb=num_qb),
        grid=(b, hk, sk // BLOCK_K, group * num_qb),
        in_specs=[
            pl.BlockSpec((1, 1, BLOCK_Q, d), q_index),
            pl.BlockSpec((1, 1, BLOCK_K, d), k_index),
            pl.BlockSpec((1, 1, BLOCK_K, d), k_index),
            pl.BlockSpec((1, 1, BLOCK_Q, d), q_index),
            pl.BlockSpec((1, 1, BLOCK_Q, 1), q_index),
            pl.BlockSpec((1, 1, BLOCK_Q, 1), q_index),
        ],
        out_specs=[
            pl.BlockSpec((1, 1, BLOCK_K, d), k_index),
            pl.BlockSpec((1, 1, BLOCK_K, d), k_index),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((b, hk, sk, d), kh.dtype),
            jax.ShapeDtypeStruct((b, hk, sk, d), vh.dtype),
        ],
        scratch_shapes=[pltpu.VMEM((BLOCK_K, d), jnp.float32),
                        pltpu.VMEM((BLOCK_K, d), jnp.float32)],
        interpret=_interpret(),
        name="paddle_tpu.flash_dkdv",
    )(qh, kh, vh, do, lse, delta)
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
