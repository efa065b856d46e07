"""In-VMEM dequant matmul: ``y = x @ (int8 w * per-block scales)``.

The decode-side projection kernel for weight-only int8 serving
(:mod:`paddle_tpu.quant.format`): HBM streams int8 weight tiles plus
their f32 scale rows; the dequantize (upcast x scale) happens in VMEM
right before one whole-K f32-accumulated ``dot_general``. Grid is
``(M/bm, N/bn)`` with whole-K tiles — each output tile is ONE dot over
the full contraction, so the accumulation order matches the XLA
reference's single dot and the two paths are bitwise-identical (the
``test_weight_quant`` parity bar, same contract as ``grouped_gemm``).

``supported()`` gates the kernel the same way ``grouped_gemm`` does:
TPU backend only (the interpreter is orders slower than XLA — CPU
always takes the reference), lane/sublane-friendly shapes, a scale
layout that tiles exactly (``K % B == 0``), and one grid step's blocks
within the VMEM budget. Everything else transparently serves
:func:`dequant_matmul_xla` — the *exact-parity* formulation (the same
elementwise dequant products, the same single f32 dot), not an
approximation.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

from ..framework.tensor import Tensor, run_op
from .format import effective_block

__all__ = ["dequant_matmul", "dequant_matmul_xla", "supported"]

#: VMEM budget for one grid step's blocks (x tile + int8 w tile + scale
#: tile + dequantized f32 w + out tile), kept well under the ~16 MB/core
#: ceiling (see pallas_guide.md)
_VMEM_BUDGET = 12 * 1024 * 1024


def _interpret():
    return jax.default_backend() != "tpu"


def _raw(a):
    return a._data if isinstance(a, Tensor) else a


def _dequant_w(q, scales, block):
    """The dequant expression — shared between the kernel body and the
    XLA formulation so both compute the SAME elementwise products
    (bitwise parity needs identical operands, and an elementwise
    multiply of identical operands is deterministic)."""
    k, n = q.shape[-2], q.shape[-1]
    kb = scales.shape[-2]
    w = q.astype(jnp.float32)
    if kb * block == k:
        shape = q.shape[:-2] + (kb, block, n)
        return (w.reshape(shape)
                * scales[..., :, None, :]).reshape(q.shape)
    # ragged last block (K % B != 0): broadcast scales by repeat+crop —
    # same per-element products, just not kernel-tileable
    s = jnp.repeat(scales, block, axis=-2)[..., :k, :]
    return w * s


def _vmem_bytes(bm, k, kb, bn, x_itemsize):
    return (bm * k * x_itemsize     # x tile
            + k * bn                # int8 weight tile
            + kb * bn * 4           # f32 scale tile
            + k * bn * 4            # dequantized f32 weight
            + bm * bn * 4)          # f32 accumulator / out tile


def _blocks(m, k, kb, n, itemsize):
    """(block_m, block_n): row tiles sublane-aligned and capped at 128;
    n tiles lane-sized when N allows, shrunk under the VMEM budget."""
    bm = min(128, -(-m // 8) * 8)
    if n % 256 == 0:
        bn = 256
    elif n % 128 == 0:
        bn = 128
    else:
        bn = n          # one lane tile; N % 8 == 0 by supported()
    while bn > 128 and _vmem_bytes(bm, k, kb, bn, itemsize) \
            > _VMEM_BUDGET:
        bn //= 2
    return bm, bn


def supported(x, w_q, scales, block=None):
    """Pallas-path preconditions for ``x [M, K] @ dequant(w_q [K, N])``:
    TPU backend, int8 weights, scales ``[K/B, N]`` tiling K exactly,
    K/N sublane/lane friendly, one grid step within the VMEM budget.
    Anything else takes the exact XLA formulation."""
    xa, qa, sa = _raw(x), _raw(w_q), _raw(scales)
    if _interpret():
        return False
    if getattr(xa, "ndim", 0) != 2 or getattr(qa, "ndim", 0) != 2 \
            or getattr(sa, "ndim", 0) != 2:
        return False
    m, k = xa.shape
    kw, n = qa.shape
    if kw != k or sa.shape[1] != n:
        return False
    if jnp.dtype(qa.dtype) != jnp.int8 \
            or jnp.dtype(sa.dtype) != jnp.float32:
        return False
    b = effective_block(k, block)
    if k % b or sa.shape[0] != k // b:
        return False    # whole-K reshape tiling only (exact parity)
    if m == 0 or k % 8 or n % 8:
        return False
    itemsize = jnp.dtype(xa.dtype).itemsize
    bm, bn = _blocks(m, k, k // b, n, itemsize)
    if n % bn:
        return False
    return _vmem_bytes(bm, k, k // b, bn, itemsize) <= _VMEM_BUDGET


def _dq_kernel(x_ref, w_ref, s_ref, o_ref, *, block):
    w = _dequant_w(w_ref[...], s_ref[...], block)
    o_ref[...] = jax.lax.dot_general(
        x_ref[...].astype(jnp.float32), w,
        (((1,), (0,)), ((), ())),
        preferred_element_type=jnp.float32).astype(o_ref.dtype)


@functools.lru_cache(maxsize=64)
def _make_dq(m, k, kb, n, block, bm, bn, out_dtype, interpret):
    grid = (m // bm, n // bn)
    return pl.pallas_call(
        functools.partial(_dq_kernel, block=block),
        grid=grid,
        in_specs=[
            pl.BlockSpec((bm, k), lambda mi, ni: (mi, 0)),
            pl.BlockSpec((k, bn), lambda mi, ni: (0, ni)),
            pl.BlockSpec((kb, bn), lambda mi, ni: (0, ni)),
        ],
        out_specs=pl.BlockSpec((bm, bn), lambda mi, ni: (mi, ni)),
        out_shape=jax.ShapeDtypeStruct((m, n), out_dtype),
        interpret=interpret,
        name="paddle_tpu.dequant_matmul",
    )


def _kernel_impl(x, q, scales, block):
    """Pallas dispatch (raw arrays, 2-D x). Rows pad to the tile
    explicitly (each out row depends only on its own x row, so pad rows
    can't contaminate real ones) and crop after."""
    m, k = x.shape
    n = q.shape[1]
    kb = scales.shape[0]
    bm, bn = _blocks(m, k, kb, n, jnp.dtype(x.dtype).itemsize)
    mp = -(-m // bm) * bm
    xp = jnp.pad(x, ((0, mp - m), (0, 0))) if mp != m else x
    call = _make_dq(mp, k, kb, n, block, bm, bn, x.dtype, _interpret())
    y = call(xp, q, scales)
    return y[:m] if mp != m else y


def _xla_impl(x, q, scales, block):
    """The exact-parity XLA formulation: identical dequant products,
    one whole-K f32 dot — the fallback AND the parity bar."""
    w = _dequant_w(q, scales, block)
    y = jax.lax.dot_general(
        x.astype(jnp.float32), w,
        (((1,), (0,)), ((), ())),
        preferred_element_type=jnp.float32)
    return y.astype(x.dtype)


def _dequant_matmul(x, q, scales, block=None, use_kernel=None):
    """Raw-array entry: x ``[..., K]``, auto-selecting the kernel when
    :func:`supported` holds (``use_kernel`` forces a path — the parity
    tests run the kernel in interpret mode through ``True``)."""
    k = x.shape[-1]
    b = effective_block(k, block)
    lead = x.shape[:-1]
    x2 = x.reshape((-1, k))
    if use_kernel is None:
        use_kernel = supported(x2, q, scales, b)
    impl = _kernel_impl if use_kernel else _xla_impl
    y = impl(x2, q, scales, b)
    return y.reshape(lead + (q.shape[-1],))


def dequant_matmul(x, w_q, scales, block=None):
    """Tensor-level ``x @ dequant(w_q)``: int8 weights + per-block
    scales stay int8 in HBM, dequantized in VMEM on use. Serving-side
    only (not differentiable — quantized weights are frozen)."""
    return run_op(
        "dequant_matmul",
        lambda a, q, s: _dequant_matmul(a, q, s, block),
        (x, w_q, scales), differentiable=False)


def dequant_matmul_xla(x, w_q, scales, block=None):
    """The exact-parity XLA formulation (parity bar / forced fallback)."""
    return run_op(
        "dequant_matmul_xla",
        lambda a, q, s: _dequant_matmul(a, q, s, block,
                                        use_kernel=False),
        (x, w_q, scales), differentiable=False)
