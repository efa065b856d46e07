"""Grouped GEMM: one Pallas kernel for every expert's ragged matmul.

Capability reference: the operator-fusion direction of *MPK*
(arXiv 2512.22219) and *Neptune* (arXiv 2510.08726) applied to MoE
dispatch — instead of a gather → per-expert einsum → scatter chain (or
a dense ``[E, C, D]`` one-hot dispatch einsum), ONE kernel walks every
expert's contiguous row block and runs its matmul against that expert's
weight, skipping experts with no rows and masking ragged block tails.
This is the kernel behind the rebuilt ragged MoE path
(`paddle_tpu/incubate/moe`) and the MoE serving FFN
(`paddle_tpu/models/llama.py` ``LlamaMoEMLP``).

Shapes (E experts, stride C rows per expert, M = E * C total rows):
  x            [M, K]     rows laid out expert-contiguous: expert ``e``
                          owns rows ``[e*C, (e+1)*C)``; only the first
                          ``group_sizes[e]`` of them are real — the
                          rest are padding the kernel never reads
                          (masked) and never writes (zeroed)
  w            [E, K, N]  stacked per-expert weights
  group_sizes  [E] int32  real rows per expert (0 <= gs[e] <= C); the
                          scalar-prefetch metadata — together with the
                          static stride it is the ``(group_start,
                          group_len)`` description of every expert's
                          row block
  -> y         [M, N]     y[e*C + i] = x[e*C + i] @ w[e] for
                          i < group_sizes[e], else 0

Semantics match ``grouped_gemm_xla`` exactly (same contraction, f32
accumulation): the XLA reference is the parity bar and the fallback
where the kernel's preconditions don't hold — the same contract as the
flash / paged / ragged attention kernels.

The kernel runs grid (E, MT, NT): the scalar-prefetched ``group_sizes``
decide, per (expert, row-tile), whether the MXU runs at all — an empty
expert's tiles (and every tile past an expert's last real row) write
zeros without touching the weights, and the x BlockSpec index map clamps
skipped tiles onto the expert's last active block so consecutive
skipped grid steps re-use the already-resident VMEM block instead of
streaming dead rows from HBM. Ragged tails (group_sizes[e] not a
multiple of the row tile) are masked inside the tile.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np
from jax.experimental import pallas as pl

try:  # pltpu imports on CPU too (interpret mode)
    from jax.experimental.pallas import tpu as pltpu
    _HAS_PLTPU = True
except ImportError:  # pragma: no cover
    pltpu = None
    _HAS_PLTPU = False

from ..framework.tensor import run_op

__all__ = ["grouped_gemm", "grouped_gemm_xla", "supported",
           "grouped_gemm_q8", "grouped_gemm_q8_xla", "supported_q8",
           "grouped_gemm_packed", "grouped_gemm_packed_xla",
           "supported_packed", "pack_by_expert", "packed_block_m",
           "packed_rows"]

#: VMEM budget for one grid step's blocks (x tile + w tile + out tile),
#: kept well under the ~16 MB/core ceiling (see pallas_guide.md)
_VMEM_BUDGET = 12 * 1024 * 1024


def _interpret():
    return jax.default_backend() != "tpu"


def _shape_of(a):
    return tuple(getattr(a, "_data", a).shape)


def _blocks(c, k, n, itemsize):
    """(block_m, block_n) for the kernel grid: row tiles sublane-aligned
    and capped at 128; n tiles lane-sized when N allows."""
    bm = min(128, -(-c // 8) * 8)
    if n % 256 == 0:
        bn = 256
    elif n % 128 == 0:
        bn = 128
    else:
        bn = n          # one lane tile; N % 8 == 0 by supported()
    # shrink bn while a grid step's blocks exceed the VMEM budget
    while bn > 128 and (bm * k + k * bn + bm * bn) * itemsize \
            > _VMEM_BUDGET:
        bn //= 2
    return bm, bn


def supported(x, w, group_sizes):
    """Pallas-path preconditions: a TPU backend (off-chip the
    interpreter would be orders of magnitude slower than the XLA
    formulation, so CPU always takes the reference — the fallback
    contract the tests pin), x [M, K] with M a multiple of E,
    w [E, K, N], group_sizes [E]; K and N sublane/lane friendly; one
    grid step's blocks within the VMEM budget. Anything else takes
    :func:`grouped_gemm_xla`."""
    if not _HAS_PLTPU or _interpret():
        return False
    xs, ws, gs = _shape_of(x), _shape_of(w), _shape_of(group_sizes)
    if len(xs) != 2 or len(ws) != 3 or len(gs) != 1:
        return False
    m, k = xs
    e, kw, n = ws
    if e == 0 or gs[0] != e or kw != k:
        return False
    if m == 0 or m % e:
        return False
    if k % 8 or n % 8:
        return False
    c = m // e
    itemsize = jnp.dtype(getattr(x, "_data", x).dtype).itemsize
    bm, bn = _blocks(c, k, n, max(itemsize, 4))
    if (bm * k + k * bn + bm * bn) * max(itemsize, 4) > _VMEM_BUDGET:
        return False
    return True


def _gg_kernel(gs_ref, x_ref, w_ref, o_ref, *, block_m):
    e = pl.program_id(0)
    mi = pl.program_id(1)
    rows = gs_ref[e]

    @pl.when(mi * block_m < rows)
    def _compute():
        x = x_ref[0].astype(jnp.float32)                    # [BM, K]
        # mask the ragged tail: rows at or past group_sizes[e] are
        # padding (and, when C % BM != 0, Pallas pad garbage) — they
        # must contribute zeros, exactly like the XLA reference's mask
        ridx = mi * block_m + jax.lax.broadcasted_iota(
            jnp.int32, (block_m, 1), 0)
        x = jnp.where(ridx < rows, x, 0.0)
        o_ref[0] = jax.lax.dot_general(
            x, w_ref[0].astype(jnp.float32),
            (((1,), (0,)), ((), ())),
            preferred_element_type=jnp.float32).astype(o_ref.dtype)

    @pl.when(mi * block_m >= rows)
    def _skip():
        # an empty expert / a tile fully past the group's last row:
        # no MXU work, defined zeros out
        o_ref[0] = jnp.zeros(o_ref.shape[1:], o_ref.dtype)


@functools.lru_cache(maxsize=64)
def _make_grouped(e, c, k, n, block_m, block_n, out_dtype, interpret):
    mt = -(-c // block_m)
    nt = -(-n // block_n)

    def x_index(ei, mi, ni, gs):
        # skipped tiles (mi past the expert's last real row) clamp onto
        # the expert's last ACTIVE block: consecutive skipped grid
        # steps keep the same block index, so the pipeline never
        # streams dead rows from HBM for them
        last = jnp.maximum(gs[ei] - 1, 0) // block_m
        return (ei, jnp.minimum(mi, last), 0)

    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=1,
        grid=(e, mt, nt),
        in_specs=[
            pl.BlockSpec((1, block_m, k), x_index),
            pl.BlockSpec((1, k, block_n),
                         lambda ei, mi, ni, gs: (ei, 0, ni)),
        ],
        out_specs=pl.BlockSpec((1, block_m, block_n),
                               lambda ei, mi, ni, gs: (ei, mi, ni)),
    )

    def call(x3, w, gs):
        return pl.pallas_call(
            functools.partial(_gg_kernel, block_m=block_m),
            grid_spec=grid_spec,
            out_shape=jax.ShapeDtypeStruct((e, c, n), out_dtype),
            interpret=interpret,
            name="paddle_tpu.grouped_gemm",
        )(gs, x3, w)

    return call


def _grouped_impl(x, w, group_sizes):
    """Pallas dispatch (raw jax arrays). Caller guarantees
    :func:`supported`."""
    m, k = x.shape
    e, _, n = w.shape
    c = m // e
    bm, bn = _blocks(c, k, n, max(jnp.dtype(x.dtype).itemsize, 4))
    call = _make_grouped(e, c, k, n, bm, bn, x.dtype, _interpret())
    gs = jnp.clip(group_sizes.astype(jnp.int32), 0, c)
    return call(x.reshape(e, c, k), w, gs).reshape(m, n)


def _xla_impl(x, w, group_sizes):
    """XLA reference (raw jax arrays): mask each expert's padding rows,
    batch-matmul against the stacked weights. Semantically identical to
    the kernel (f32 accumulation, zeros on padded rows)."""
    m, k = x.shape
    e, _, n = w.shape
    c = m // e
    gs = jnp.clip(group_sizes.astype(jnp.int32), 0, c)
    x3 = x.reshape(e, c, k)
    mask = (jnp.arange(c, dtype=jnp.int32)[None, :] < gs[:, None])
    x3 = jnp.where(mask[..., None], x3.astype(jnp.float32), 0.0)
    y = jax.lax.dot_general(
        x3, w.astype(jnp.float32),
        (((2,), (1,)), ((0,), (0,))),
        preferred_element_type=jnp.float32)
    return y.astype(x.dtype).reshape(m, n)


@functools.lru_cache(maxsize=2)
def _grouped_vjp_fn(use_kernel):
    """Module-level custom-VJP grouped GEMM, one per impl choice.
    ``group_sizes`` is a PRIMAL (float0 cotangent), never a closure —
    a closed-over traced value would leak into the partial-eval
    jaxpr's constants and crash the backward lowering."""
    impl = _grouped_impl if use_kernel else _xla_impl

    @jax.custom_vjp
    def f(x, w, gs):
        return impl(x, w, gs)

    def fwd(x, w, gs):
        return f(x, w, gs), (x, w, gs)

    def bwd(res, g):
        x, w, gs0 = res
        m, k = x.shape
        e, _, n = w.shape
        c = m // e
        gs = jnp.clip(gs0.astype(jnp.int32), 0, c)
        # dx rows past group_sizes[e] must be zero (those x rows never
        # reached the output) — the grouped gemm against w^T masks
        # them. The transposed weight swaps K and N, so the forward's
        # supported() verdict does not transfer: re-select (a kernel
        # forward whose swapped shape blows the VMEM budget falls back
        # to XLA for dx), but never upgrade an XLA forward (the SPMD
        # path) to the kernel.
        dx = _grouped(g, jnp.swapaxes(w, 1, 2), gs0,
                      use_kernel=None if use_kernel else False)
        mask = (jnp.arange(c, dtype=jnp.int32)[None, :]
                < gs[:, None])[..., None]
        x3 = jnp.where(mask, x.reshape(e, c, k).astype(jnp.float32), 0.0)
        g3 = jnp.where(mask, g.reshape(e, c, n).astype(jnp.float32), 0.0)
        dw = jax.lax.dot_general(
            x3, g3, (((1,), (1,)), ((0,), (0,))),
            preferred_element_type=jnp.float32).astype(w.dtype)
        return (dx.astype(x.dtype), dw,
                np.zeros(gs0.shape, jax.dtypes.float0))

    f.defvjp(fwd, bwd)
    return f


def _grouped(x, w, group_sizes, use_kernel=None):
    """Raw-array grouped GEMM with a custom VJP — the building block
    the MoE layers trace over. ``use_kernel=None`` auto-selects the
    Pallas path when :func:`supported` holds; ``False`` forces the XLA
    formulation (the SPMD/expert-parallel path: GSPMD partitions the
    batched dot and inserts the dispatch collectives — a Pallas custom
    call would force replication)."""
    if use_kernel is None:
        use_kernel = supported(x, w, group_sizes)
    f = _grouped_vjp_fn(bool(use_kernel))
    return f(x, w, group_sizes.astype(jnp.int32))


def grouped_gemm(x, w, group_sizes):
    """Tensor-level grouped GEMM over expert-contiguous row blocks (see
    module docstring): ``y[e*C + i] = x[e*C + i] @ w[e]`` for
    ``i < group_sizes[e]``, zeros past each group's length. Dispatches
    the Pallas kernel when :func:`supported` holds, the XLA reference
    otherwise; differentiable (custom VJP: dx is a grouped GEMM against
    ``w^T``, dw a masked batched contraction)."""

    def fn(x, w, gs):
        return _grouped(x, w, gs)

    return run_op("grouped_gemm", fn, (x, w, group_sizes))


def grouped_gemm_xla(x, w, group_sizes):
    """XLA reference path (parity bar and non-Pallas fallback)."""

    def fn(x, w, gs):
        return _grouped(x, w, gs, use_kernel=False)

    return run_op("grouped_gemm_xla", fn, (x, w, group_sizes))


# ---------------------------------------------------------------------------
# int8 weight-only variant (paddle_tpu.quant): stacked expert weights
# stay int8 in HBM with per-block f32 scale sidecars [E, K/B, N]; the
# dequantize (upcast x scale) happens in VMEM right before each
# expert's dot. Serving-side only — quantized weights are frozen, so
# there is no VJP; the ragged row semantics (masking, skip, clamp) are
# identical to the float kernel above.
# ---------------------------------------------------------------------------

def _q8_dequant_w(w_q, scales, block):
    """Shared dequant expression (see quant.kernels._dequant_w): the
    kernel and the XLA formulation compute the SAME elementwise
    products, so both paths stay bitwise-identical."""
    k, n = w_q.shape[-2], w_q.shape[-1]
    kb = scales.shape[-2]
    shape = w_q.shape[:-2] + (kb, block, n)
    return (w_q.astype(jnp.float32).reshape(shape)
            * scales[..., :, None, :]).reshape(w_q.shape)


def _q8_vmem(bm, k, kb, bn, itemsize):
    return (bm * k * itemsize       # x tile
            + k * bn                # int8 weight tile
            + kb * bn * 4           # f32 scale tile
            + k * bn * 4            # dequantized f32 weight
            + bm * bn * 4)          # out tile


def supported_q8(x, w_q, scales, group_sizes, block):
    """Pallas-path preconditions for the int8 grouped GEMM: everything
    :func:`supported` checks, plus int8 weights, scales
    ``[E, K/B, N]`` tiling K exactly, and the (bigger — dequant temp)
    VMEM budget."""
    if not _HAS_PLTPU or _interpret():
        return False
    xs, ws, ss, gs = (_shape_of(x), _shape_of(w_q), _shape_of(scales),
                      _shape_of(group_sizes))
    if len(xs) != 2 or len(ws) != 3 or len(ss) != 3 or len(gs) != 1:
        return False
    m, k = xs
    e, kw, n = ws
    if e == 0 or gs[0] != e or kw != k:
        return False
    if m == 0 or m % e or k % 8 or n % 8:
        return False
    b = int(block)
    if b <= 0 or k % b:
        return False
    if ss != (e, k // b, n):
        return False
    qa = getattr(w_q, "_data", w_q)
    sa = getattr(scales, "_data", scales)
    if jnp.dtype(qa.dtype) != jnp.int8 \
            or jnp.dtype(sa.dtype) != jnp.float32:
        return False
    c = m // e
    itemsize = max(jnp.dtype(getattr(x, "_data", x).dtype).itemsize, 4)
    bm, bn = _blocks(c, k, n, itemsize)
    if n % bn:
        return False
    return _q8_vmem(bm, k, k // b, bn, itemsize) <= _VMEM_BUDGET


def _gg_q8_kernel(gs_ref, x_ref, w_ref, s_ref, o_ref, *, block_m,
                  block):
    e = pl.program_id(0)
    mi = pl.program_id(1)
    rows = gs_ref[e]

    @pl.when(mi * block_m < rows)
    def _compute():
        x = x_ref[0].astype(jnp.float32)                    # [BM, K]
        ridx = mi * block_m + jax.lax.broadcasted_iota(
            jnp.int32, (block_m, 1), 0)
        x = jnp.where(ridx < rows, x, 0.0)
        w = _q8_dequant_w(w_ref[0], s_ref[0], block)        # [K, BN]
        o_ref[0] = jax.lax.dot_general(
            x, w, (((1,), (0,)), ((), ())),
            preferred_element_type=jnp.float32).astype(o_ref.dtype)

    @pl.when(mi * block_m >= rows)
    def _skip():
        o_ref[0] = jnp.zeros(o_ref.shape[1:], o_ref.dtype)


@functools.lru_cache(maxsize=64)
def _make_grouped_q8(e, c, k, n, kb, block, block_m, block_n,
                     out_dtype, interpret):
    mt = -(-c // block_m)
    nt = -(-n // block_n)

    def x_index(ei, mi, ni, gs):
        last = jnp.maximum(gs[ei] - 1, 0) // block_m
        return (ei, jnp.minimum(mi, last), 0)

    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=1,
        grid=(e, mt, nt),
        in_specs=[
            pl.BlockSpec((1, block_m, k), x_index),
            pl.BlockSpec((1, k, block_n),
                         lambda ei, mi, ni, gs: (ei, 0, ni)),
            pl.BlockSpec((1, kb, block_n),
                         lambda ei, mi, ni, gs: (ei, 0, ni)),
        ],
        out_specs=pl.BlockSpec((1, block_m, block_n),
                               lambda ei, mi, ni, gs: (ei, mi, ni)),
    )

    def call(x3, w_q, scales, gs):
        return pl.pallas_call(
            functools.partial(_gg_q8_kernel, block_m=block_m,
                              block=block),
            grid_spec=grid_spec,
            out_shape=jax.ShapeDtypeStruct((e, c, n), out_dtype),
            interpret=interpret,
            name="paddle_tpu.grouped_gemm_q8",
        )(gs, x3, w_q, scales)

    return call


def _q8_impl(x, w_q, scales, group_sizes, block):
    """Pallas dispatch (raw arrays). Caller guarantees
    :func:`supported_q8` (or forces interpret for the parity tests)."""
    m, k = x.shape
    e, _, n = w_q.shape
    c = m // e
    kb = scales.shape[1]
    bm, bn = _blocks(c, k, n, max(jnp.dtype(x.dtype).itemsize, 4))
    call = _make_grouped_q8(e, c, k, n, kb, int(block), bm, bn,
                            x.dtype, _interpret())
    gs = jnp.clip(group_sizes.astype(jnp.int32), 0, c)
    return call(x.reshape(e, c, k), w_q, scales, gs).reshape(m, n)


def _q8_xla_impl(x, w_q, scales, group_sizes, block):
    """XLA formulation: dequantize the stacked weights with the SAME
    elementwise expression the kernel uses, then the float reference's
    masked batched dot — exact parity by construction."""
    m, k = x.shape
    e, _, n = w_q.shape
    c = m // e
    gs = jnp.clip(group_sizes.astype(jnp.int32), 0, c)
    w = _q8_dequant_w(w_q, scales, int(block))
    x3 = x.reshape(e, c, k)
    mask = (jnp.arange(c, dtype=jnp.int32)[None, :] < gs[:, None])
    x3 = jnp.where(mask[..., None], x3.astype(jnp.float32), 0.0)
    y = jax.lax.dot_general(
        x3, w, (((2,), (1,)), ((0,), (0,))),
        preferred_element_type=jnp.float32)
    return y.astype(x.dtype).reshape(m, n)


def _grouped_q8(x, w_q, scales, group_sizes, block, use_kernel=None):
    """Raw-array int8 grouped GEMM (no VJP — serving-only frozen
    weights). ``use_kernel=None`` auto-selects; ``True`` forces the
    kernel (interpret mode off-TPU: the parity tests); ``False`` the
    XLA formulation (the SPMD path)."""
    if use_kernel is None:
        use_kernel = supported_q8(x, w_q, scales, group_sizes, block)
    impl = _q8_impl if use_kernel else _q8_xla_impl
    return impl(x, w_q, scales, group_sizes.astype(jnp.int32),
                int(block))


def grouped_gemm_q8(x, w_q, scales, group_sizes, block):
    """Tensor-level int8 grouped GEMM: ``y[e*C + i] = x[e*C + i] @
    (w_q[e] * scales[e])`` for ``i < group_sizes[e]``, zeros past each
    group's length. Weights stay int8 in HBM (scale sidecars ride the
    same expert index); dequant happens in VMEM. Not differentiable."""

    def fn(x, w, s, gs):
        return _grouped_q8(x, w, s, gs, block)

    return run_op("grouped_gemm_q8", fn,
                  (x, w_q, scales, group_sizes), differentiable=False)


def grouped_gemm_q8_xla(x, w_q, scales, group_sizes, block):
    """XLA formulation of :func:`grouped_gemm_q8` (parity bar)."""

    def fn(x, w, s, gs):
        return _grouped_q8(x, w, s, gs, block, use_kernel=False)

    return run_op("grouped_gemm_q8_xla", fn,
                  (x, w_q, scales, group_sizes), differentiable=False)


# ---------------------------------------------------------------------------
# packed variant: rows sorted by expert and PACKED, each expert's group
# rounded up to whole row tiles, instead of a stride of C rows an expert.
# With many small experts (256 experts, 8 a token) the strided buffer is
# E*n rows of which n*k are real; the packed one is n*k rows plus at most
# one tile of padding for each expert that got a row. A row tile belongs
# to ONE expert (`tile_expert`, scalar-prefetched), so the grid is
# (n tiles, row tiles): consecutive tiles of one expert keep its weight
# block resident, an expert with no row has no tile and its weights are
# never read, and tiles past `num_tiles` are skipped without a copy.
# ---------------------------------------------------------------------------
def packed_block_m(n_rows, num_experts, sublane=16):
    """Row tile of the packed layout for ``n_rows`` assignments over
    ``num_experts``: the power of two next above the mean group, between
    one packed sublane tile and 128."""
    mean = max(1, -(-int(n_rows) // int(num_experts)))
    bm = 1 << (mean - 1).bit_length()
    return int(min(128, max(sublane, bm)))


def packed_rows(n_rows, num_experts, block_m):
    """Static row count of the packed buffer: every assignment, plus
    less than a tile of padding for each expert that can hold one."""
    worst = n_rows + min(num_experts, n_rows) * (block_m - 1)
    return -(-worst // block_m) * block_m


def pack_by_expert(expert_ids, num_experts, block_m):
    """Lay ``expert_ids [n, k]`` (token ``i``'s ``k`` experts) out packed
    by expert (traceable). Returns a dict: ``row_token [M]`` the token
    each packed row holds (``n`` = none: a zero row), ``dest [n, k]``
    the packed row of each assignment, ``tile_expert [M / block_m]``,
    ``num_tiles [1]`` the tiles in use, ``counts [E]`` rows an expert."""
    n, k = expert_ids.shape
    nk, e = n * k, int(num_experts)
    m = packed_rows(nk, e, block_m)
    # an id of ``num_experts`` drops the assignment: it gets no row, is
    # not counted, and its ``dest`` is row 0 (give it weight 0)
    flat = jnp.clip(expert_ids.reshape(-1).astype(jnp.int32), 0, e)
    counts_all = jnp.zeros((e + 1,), jnp.int32).at[flat].add(1)
    counts = counts_all[:e]
    padded = -(-counts // block_m) * block_m
    ends = jnp.cumsum(padded)
    pstart = jnp.append(ends - padded, 0)
    start = jnp.cumsum(counts_all) - counts_all
    order = jnp.argsort(flat, stable=True)
    se = flat[order]
    dest_sorted = jnp.where(
        se < e,
        pstart[se] + jnp.arange(nk, dtype=jnp.int32) - start[se], m)
    dest = jnp.zeros((nk,), jnp.int32).at[order].set(dest_sorted)
    row_token = jnp.full((m,), n, jnp.int32).at[dest].set(
        jnp.arange(nk, dtype=jnp.int32) // k, mode="drop")
    dest = jnp.where(dest < m, dest, 0)
    num_tiles = (ends[-1] // block_m).astype(jnp.int32)
    tiles = jnp.arange(m // block_m, dtype=jnp.int32)
    # a tile past the last one in use names that one's expert, so the
    # weight block's index does not move and nothing is fetched for it
    live = jnp.minimum(tiles, jnp.maximum(num_tiles - 1, 0))
    tile_expert = jnp.clip(jnp.searchsorted(
        ends, live * block_m, side="right"), 0, e - 1).astype(jnp.int32)
    return {"row_token": row_token, "dest": dest.reshape(n, k),
            "tile_expert": tile_expert,
            "num_tiles": num_tiles.reshape(1), "counts": counts}


def _packed_block_n(k, n, itemsize):
    """The whole N where one weight block (double-buffered) fits the
    budget, else the largest multiple of 128 dividing N that does."""
    if 2 * k * n * itemsize <= _VMEM_BUDGET or n % 128:
        return n
    bn = n
    while bn > 128 and (2 * k * bn * itemsize > _VMEM_BUDGET or n % bn):
        bn -= 128
    return bn


def supported_packed(x, w, block_m):
    """Pallas preconditions of the packed kernel: a TPU backend (the
    CPU takes the XLA formulation, as the strided kernel does), M whole
    row tiles, K and N whole lane tiles."""
    if not _HAS_PLTPU or _interpret():
        return False
    (m, k), (e, kw, n) = _shape_of(x), _shape_of(w)
    sub = 32 // jnp.dtype(getattr(x, "_data", x).dtype).itemsize
    return kw == k and e > 0 and m % block_m == 0 and block_m % sub == 0 \
        and k % 128 == 0 and n % 128 == 0


def _gg_packed_kernel(te_ref, nt_ref, x_ref, w_ref, o_ref):
    mi = pl.program_id(1)

    @pl.when(mi < nt_ref[0])
    def _compute():
        # operands as stored (bf16 on the chip), f32 accumulation; the
        # padding rows of a tile are zero rows of x
        o_ref[...] = jax.lax.dot_general(
            x_ref[...], w_ref[0], (((1,), (0,)), ((), ())),
            preferred_element_type=jnp.float32).astype(o_ref.dtype)

    @pl.when(mi >= nt_ref[0])
    def _skip():
        o_ref[...] = jnp.zeros(o_ref.shape, o_ref.dtype)


@functools.lru_cache(maxsize=64)
def _make_packed(m, k, n, e, block_m, block_n, dtype, interpret):
    mt, nt = m // block_m, n // block_n

    def live(mi, nt_ref):
        return jnp.minimum(mi, jnp.maximum(nt_ref[0] - 1, 0))

    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=2,
        grid=(nt, mt),
        in_specs=[
            pl.BlockSpec((block_m, k),
                         lambda ni, mi, te, nt_: (live(mi, nt_), 0)),
            pl.BlockSpec((1, k, block_n),
                         lambda ni, mi, te, nt_: (te[mi], 0, ni)),
        ],
        out_specs=pl.BlockSpec((block_m, block_n),
                               lambda ni, mi, te, nt_: (mi, ni)),
    )
    item = jnp.dtype(dtype).itemsize
    vmem = 2 * (block_m * k + k * block_n + block_m * block_n) * item \
        + block_m * block_n * 4

    def call(x, w, tile_expert, num_tiles):
        return pl.pallas_call(
            _gg_packed_kernel,
            grid_spec=grid_spec,
            out_shape=jax.ShapeDtypeStruct((m, n), dtype),
            compiler_params=pltpu.CompilerParams(
                dimension_semantics=("arbitrary", "arbitrary"),
                vmem_limit_bytes=min(100 << 20,
                                     max(32 << 20, vmem + (8 << 20)))),
            interpret=interpret,
            name="paddle_tpu.grouped_gemm_packed",
        )(tile_expert, num_tiles, x, w)

    return call


def _packed_kernel_impl(x, w, tile_expert, num_tiles, block_m):
    m, k = x.shape
    e, _, n = w.shape
    bn = _packed_block_n(k, n, jnp.dtype(x.dtype).itemsize)
    call = _make_packed(m, k, n, e, int(block_m), bn, jnp.dtype(x.dtype),
                        _interpret())
    return call(x, w.astype(x.dtype), tile_expert.astype(jnp.int32),
                num_tiles.astype(jnp.int32))


def _packed_xla_impl(x, w, tile_expert, num_tiles, block_m):
    """The same mathematics in XLA: the padded group sizes, recovered
    from the tiles' experts, drive ``lax.ragged_dot`` (rows past the
    tiles in use come out zero)."""
    m, _ = x.shape
    e = w.shape[0]
    tiles = jnp.arange(m // block_m, dtype=jnp.int32)
    used = (tiles < num_tiles[0]).astype(jnp.int32)
    padded = jnp.zeros((e,), jnp.int32).at[tile_expert].add(
        used * block_m)
    rows = jnp.arange(m, dtype=jnp.int32)[:, None]
    x = jnp.where(rows < num_tiles[0] * block_m, x, jnp.zeros_like(x))
    y = jax.lax.ragged_dot(x, w.astype(x.dtype), padded,
                           preferred_element_type=jnp.float32)
    y = jnp.where(rows < num_tiles[0] * block_m, y, 0.0)
    return y.astype(x.dtype)


def _grouped_packed(x, w, tile_expert, num_tiles, block_m,
                    use_kernel=None):
    """Raw-array packed grouped GEMM (inference only): ``y[r] = x[r] @
    w[tile_expert[r // block_m]]`` for rows of the tiles in use, zeros
    past them. The Pallas program where `supported_packed`, else XLA."""
    if use_kernel is None:
        use_kernel = supported_packed(x, w, block_m)
    impl = _packed_kernel_impl if use_kernel else _packed_xla_impl
    return impl(x, w, tile_expert, num_tiles, int(block_m))


def grouped_gemm_packed(x, w, tile_expert, num_tiles, block_m):
    """Tensor-level packed grouped GEMM (see `pack_by_expert`)."""
    def fn(x, w, te, nt):
        return _grouped_packed(x, w, te, nt, block_m)

    return run_op("grouped_gemm_packed", fn,
                  (x, w, tile_expert, num_tiles), differentiable=False)


def grouped_gemm_packed_xla(x, w, tile_expert, num_tiles, block_m):
    """XLA formulation of the packed grouped GEMM (the parity bar)."""
    def fn(x, w, te, nt):
        return _grouped_packed(x, w, te, nt, block_m, use_kernel=False)

    return run_op("grouped_gemm_packed_xla", fn,
                  (x, w, tile_expert, num_tiles), differentiable=False)
