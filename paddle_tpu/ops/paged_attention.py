"""Paged attention: decode-step GQA attention over a paged KV pool.

Capability reference: the reference's serving attention with a paged KV
cache (`paddle/phi/kernels/fusion/gpu/block_multi_head_attention_kernel.cu`
+ `masked_multihead_attention`). TPU-native design: a Pallas kernel over
a global page pool with per-sequence block tables delivered through
scalar prefetch — the block table entry picks which HBM page each grid
step streams into VMEM (`PrefetchScalarGridSpec` index maps), so KV for
a sequence never needs to be contiguous and batches of ragged sequences
decode in one launch.

Shapes:
  q             [B, H, D]           one new token per sequence
  k_pages       [P, Hk, page_size, D]   global pool, any page owner
                                        (head-major: the Mosaic lowering
                                        needs the last two block dims to
                                        tile as (page, D))
  v_pages       [P, Hk, page_size, D]
  block_tables  [B, max_pages] int32    page ids per sequence (row-major
                                        position order; unused tail
                                        entries may hold anything — they
                                        are clamped into [0, P) before
                                        reaching the index map)
  context_lens  [B] int32              valid tokens per sequence,
                                        *including* the current one
                                        (its K/V must already be written)
  -> out        [B, H, D]

The kernel runs grid (B, Hk, max_pages) with one online-softmax
accumulator in VMEM scratch per (sequence, kv-head); query heads of the
same GQA group ride along as a [group, D] MXU operand. Pages past
ceil(context_len / page_size) are skipped (no HBM read cost beyond the
prefetched block spec's page — the table tail can point at page 0).
Decode is inference-only: no VJP is defined.
"""

from __future__ import annotations

import functools
import math

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

try:
    from jax.experimental.pallas import tpu as pltpu
    _HAS_PLTPU = True
except ImportError:  # pragma: no cover
    pltpu = None
    _HAS_PLTPU = False

from ..framework.tensor import run_op

__all__ = ["paged_attention", "paged_attention_xla", "supported"]

NEG_INF = -1e30


def _interpret():
    return jax.default_backend() != "tpu"


def supported(q, k_pages, v_pages, block_tables, context_lens):
    if not _HAS_PLTPU:
        return False
    qs = getattr(q, "_data", q).shape
    ks = getattr(k_pages, "_data", k_pages).shape
    bt = getattr(block_tables, "_data", block_tables).shape
    cl = getattr(context_lens, "_data", context_lens).shape
    if len(qs) != 3 or len(ks) != 4 or len(bt) != 2 or len(cl) != 1:
        return False
    b, h, d = qs
    p, hk, page_size, dk = ks
    if getattr(v_pages, "_data", v_pages).shape != tuple(ks):
        return False
    if d != dk or hk == 0 or h % hk or bt[0] != b or cl[0] != b:
        return False
    if d % 8 or d > 256 or page_size % 8:
        return False
    return True


def _decode_kernel(tables_ref, lens_ref,  # scalar prefetch
                   q_ref, k_ref, v_ref, o_ref,
                   acc_ref, m_ref, l_ref, *, page_size, scale):
    b = pl.program_id(0)
    p = pl.program_id(2)
    num_pages = pl.num_programs(2)

    @pl.when(p == 0)
    def _init():
        acc_ref[...] = jnp.zeros_like(acc_ref)
        m_ref[...] = jnp.full_like(m_ref, NEG_INF)
        l_ref[...] = jnp.zeros_like(l_ref)

    ctx = lens_ref[b]
    page_start = p * page_size

    @pl.when(page_start < ctx)
    def _compute():
        q = q_ref[0, 0].astype(jnp.float32) * scale      # [G, D]
        k = k_ref[0, 0].astype(jnp.float32)              # [page, D]
        v = v_ref[0, 0].astype(jnp.float32)
        s = jax.lax.dot_general(q, k, (((1,), (1,)), ((), ())),
                                preferred_element_type=jnp.float32)
        kpos = page_start + jax.lax.broadcasted_iota(jnp.int32, s.shape, 1)
        s = jnp.where(kpos < ctx, s, NEG_INF)
        m_prev, l_prev = m_ref[...], l_ref[...]
        m_cur = jnp.max(s, axis=-1, keepdims=True)
        m_new = jnp.maximum(m_prev, m_cur)
        alpha = jnp.exp(m_prev - m_new)
        pexp = jnp.exp(s - m_new)
        l_ref[...] = l_prev * alpha + jnp.sum(pexp, axis=-1, keepdims=True)
        m_ref[...] = m_new
        acc_ref[...] = acc_ref[...] * alpha + jax.lax.dot_general(
            pexp, v, (((1,), (0,)), ((), ())),
            preferred_element_type=jnp.float32)

    @pl.when(p == num_pages - 1)
    def _finish():
        o_ref[0, 0] = (acc_ref[...] / l_ref[...]).astype(o_ref.dtype)


@functools.lru_cache(maxsize=32)
def _make_paged(scale, page_size, group, interpret):
    def call(q4, k_pages, v_pages, tables, lens):
        b, hk, g, d = q4.shape
        max_pages = tables.shape[1]
        grid_spec = pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=2,
            grid=(b, hk, max_pages),
            in_specs=[
                pl.BlockSpec((1, 1, g, d),
                             lambda bi, hi, pi, tables, lens: (bi, hi, 0, 0)),
                # the prefetched block table picks the HBM page to stream
                pl.BlockSpec((1, 1, page_size, d),
                             lambda bi, hi, pi, tables, lens:
                             (tables[bi, pi], hi, 0, 0)),
                pl.BlockSpec((1, 1, page_size, d),
                             lambda bi, hi, pi, tables, lens:
                             (tables[bi, pi], hi, 0, 0)),
            ],
            out_specs=pl.BlockSpec(
                (1, 1, g, d),
                lambda bi, hi, pi, tables, lens: (bi, hi, 0, 0)),
            scratch_shapes=[
                pltpu.VMEM((g, d), jnp.float32),
                pltpu.VMEM((g, 1), jnp.float32),
                pltpu.VMEM((g, 1), jnp.float32),
            ],
        )
        return pl.pallas_call(
            functools.partial(_decode_kernel, page_size=page_size,
                              scale=scale),
            grid_spec=grid_spec,
            out_shape=jax.ShapeDtypeStruct((b, hk, g, d), q4.dtype),
            interpret=interpret,
            name="paddle_tpu.paged_attn_decode",
        )(tables, lens, q4, k_pages, v_pages)

    return call


def _paged_impl(q, k_pages, v_pages, block_tables, context_lens, scale):
    b, h, d = q.shape
    hk = k_pages.shape[1]
    group = h // hk
    page_size = k_pages.shape[2]
    q4 = q.reshape(b, hk, group, d)
    call = _make_paged(scale, page_size, group, _interpret())
    # Tail entries past a sequence's last page are never *read* for the
    # output, but they still feed the Pallas index map — clamp so an
    # arbitrary tail value can't index the page pool out of bounds
    # (unspecified behavior in Mosaic).
    tables = jnp.clip(block_tables.astype(jnp.int32), 0,
                      k_pages.shape[0] - 1)
    out = call(q4, k_pages, v_pages, tables,
               context_lens.astype(jnp.int32))
    return out.reshape(b, h, d)


def paged_attention(q, k_pages, v_pages, block_tables, context_lens,
                    scale=None):
    """Decode-step attention over the paged pool (see module docstring).
    Tape-integrated but non-differentiable (serving path)."""
    if not supported(q, k_pages, v_pages, block_tables, context_lens):
        raise ValueError(
            "paged_attention preconditions not met: need q [B,H,D], pages "
            "[P,Hk,page,D] (page % 8 == 0, D % 8 == 0, D <= 256, "
            "H % Hk == 0), tables [B,max_pages], lens [B]")
    d = getattr(q, "_data", q).shape[-1]
    s = scale if scale is not None else 1.0 / math.sqrt(d)

    def fn(q, kp, vp, bt, cl):
        return _paged_impl(q, kp, vp, bt, cl, s)

    return run_op("paged_attention", fn,
                  (q, k_pages, v_pages, block_tables, context_lens),
                  differentiable=False)


def paged_attention_xla(q, k_pages, v_pages, block_tables, context_lens,
                        scale=None):
    """XLA reference path: gather pages to a contiguous [B, S, Hk, D]
    window, mask, softmax. Semantically identical; used for parity tests
    and as the fallback where Pallas is unavailable."""
    q, k_pages, v_pages, block_tables, context_lens = (
        getattr(a, "_data", a)
        for a in (q, k_pages, v_pages, block_tables, context_lens))
    b, h, d = q.shape
    p, hk, page_size, _ = k_pages.shape
    group = h // hk
    s = scale if scale is not None else 1.0 / math.sqrt(d)
    # [B, max_pages, Hk, page, D] -> [B, S, Hk, D]
    k = jnp.swapaxes(k_pages[block_tables], 2, 3).reshape(b, -1, hk, d)
    v = jnp.swapaxes(v_pages[block_tables], 2, 3).reshape(b, -1, hk, d)
    kq = jnp.repeat(k, group, axis=2)
    vq = jnp.repeat(v, group, axis=2)
    logits = jnp.einsum("bhd,bshd->bhs", q.astype(jnp.float32),
                        kq.astype(jnp.float32)) * s
    kpos = jnp.arange(k.shape[1])[None, None, :]
    logits = jnp.where(kpos < context_lens[:, None, None], logits, NEG_INF)
    w = jax.nn.softmax(logits, axis=-1)
    return jnp.einsum("bhs,bshd->bhd", w, vq.astype(jnp.float32)) \
        .astype(q.dtype)
