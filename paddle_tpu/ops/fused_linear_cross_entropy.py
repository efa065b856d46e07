"""Chunked fused cross-entropy lm-head: loss without the [B*S, V] logits.

Capability reference: the locality-driven fusion direction of *Neptune*
(arXiv 2510.08726) applied to the training loss — `LlamaForCausalLM`
previously materialized the full ``[B*S, V]`` float32 logits tensor just
to reduce it to one scalar; at llama3-8b vocab (128256) that single
tensor and its softmax round trips dwarf every decoder layer's HBM
traffic. ``fused_linear_cross_entropy(hidden, lm_head_w, labels)``
computes the same mean next-token loss blockwise over vocab chunks (and
sequence tiles): per chunk, partial logits -> a running online logsumexp
and label-logit pick -> per-token loss, with a custom VJP that
RECOMPUTES each chunk's logits in the backward and emits
``d_hidden``/``d_w`` chunk by chunk — the ``[N, V]`` tensor never
exists in either pass.

Shapes (N = B*S tokens, D hidden, V vocab):
  hidden  [N, D]   (any float dtype; compute is f32-accumulated)
  w       [D, V]   the lm-head projection (``nn.Linear`` layout)
  labels  [N] int  next-token ids, ``ignore_index`` rows excluded from
                   the mean (the ``F.cross_entropy`` contract)
  -> loss scalar f32: ``sum(nll[valid]) / max(count(valid), 1)``

Three formulations, one contract:

- **Pallas kernel** where :func:`supported` holds (TPU backend, lane
  friendly D): grid ``(row-tiles, vocab-tiles)`` with the vocab index
  minor, so VMEM scratch carries each row tile's running
  ``(max, sumexp, label-logit)`` across that row's vocab sweep — one
  read of ``hidden``, one stream over ``w``, outputs ``[N]``.
- **chunked-XLA formulation** (the parity bar and the fallback
  everywhere else): the SAME online update unrolled over static vocab
  chunks. Math is identical op for op, so the kernel is testable
  against it at matching chunking.
- **SPMD formulation** when ``w`` is vocab-parallel sharded (the
  ``shard_llama`` lm-head layout): a single batched product with a
  ``with_sharding_constraint`` pinning the logits' vocab dim to the
  mesh axis — each device holds ``[N, V/mp]``, GSPMD partitions the
  logsumexp reduction (the ``mp_layers`` vocab-parallel embedding
  contract), and the mesh — not the chunk loop — bounds peak memory.

``PADDLE_TPU_FUSED_CE=0`` restores the materialized path in
``LlamaForCausalLM`` byte-for-byte; ``PADDLE_TPU_FUSED_CE_CHUNK``
(default 8192) sets the vocab chunk of the XLA formulation.
"""

from __future__ import annotations

import functools
import os

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

__all__ = ["fused_linear_cross_entropy", "fused_linear_cross_entropy_xla",
           "supported"]

#: the kernel's name in a trace (the forward ``pallas_call``'s ``name=``
#: and the scope of the backward's ops)
KERNEL_NAME = "paddle_tpu.fused_ce"

#: VMEM budget for one grid step's input windows (hidden tile + w tile,
#: each double-buffered by the pipeline), kept under the 16 MB of scoped
#: VMEM the compiler grants a kernel
_VMEM_BUDGET = 12 * 1024 * 1024


def _interpret():
    return jax.default_backend() != "tpu"


def _shape_of(a):
    return tuple(getattr(a, "_data", a).shape)


def default_chunk():
    """Vocab chunk of the XLA formulation (env
    ``PADDLE_TPU_FUSED_CE_CHUNK``, default 8192)."""
    try:
        return max(8, int(os.environ.get("PADDLE_TPU_FUSED_CE_CHUNK",
                                         "8192")))
    except ValueError:
        return 8192


def _window_bytes(bn, d, bv, itemsize):
    return 2 * (bn * d + d * bv) * itemsize


def _blocks(n, d, v, itemsize):
    """(block_n, block_v) for the kernel grid: row tiles sublane-aligned
    and capped at 128 (the sequence tile), vocab tiles shrunk while one
    grid step's input windows exceed the VMEM budget."""
    bn = min(128, -(-n // 8) * 8)
    bv = min(512, -(-v // 128) * 128)
    while bv > 128 and _window_bytes(bn, d, bv, itemsize) > _VMEM_BUDGET:
        bv //= 2
    return bn, bv


def supported(hidden2d, w):
    """Pallas-path preconditions: a TPU backend (off-chip the interpreter
    would be orders of magnitude slower than the chunked XLA formulation,
    so CPU always takes the reference — the same fallback contract as
    ``grouped_gemm``), hidden [N, D] with D lane-aligned, w [D, V], and
    one grid step's blocks within the VMEM budget."""
    if not _HAS_PLTPU or _interpret():
        return False
    hs, ws = _shape_of(hidden2d), _shape_of(w)
    if len(hs) != 2 or len(ws) != 2:
        return False
    n, d = hs
    dw, v = ws
    if n == 0 or d == 0 or v == 0 or dw != d:
        return False
    if d % 128 or v < 128:
        return False
    itemsize = jnp.dtype(getattr(hidden2d, "_data", hidden2d).dtype).itemsize
    bn, bv = _blocks(n, d, v, itemsize)
    return _window_bytes(bn, d, bv, itemsize) <= _VMEM_BUDGET


# ---------------------------------------------------------------------------
# chunked-XLA formulation: the parity bar (and the universal fallback)
# ---------------------------------------------------------------------------
def _xla_parts(h2d, w, labels, chunk):
    """(lse [N], pick [N]) via the online chunked logsumexp — the
    ``[N, V]`` logits never exist; peak extra memory is one ``[N, chunk]``
    f32 block. ``labels`` int32; rows whose label appears in no chunk
    (the ignore_index rows) get pick == 0, masked by the caller."""
    n, d = h2d.shape
    v = w.shape[1]
    h32 = h2d.astype(jnp.float32)
    m = jnp.full((n,), -jnp.inf, jnp.float32)
    s = jnp.zeros((n,), jnp.float32)
    pick = jnp.zeros((n,), jnp.float32)
    for lo in range(0, v, chunk):
        hi = min(lo + chunk, v)
        wc = jax.lax.slice_in_dim(w, lo, hi, axis=1).astype(jnp.float32)
        lg = jax.lax.dot_general(
            h32, wc, (((1,), (0,)), ((), ())),
            preferred_element_type=jnp.float32)           # [N, hi-lo]
        cm = jnp.max(lg, axis=1)
        m_new = jnp.maximum(m, cm)
        # first chunk: m == -inf so the rescale term is exactly 0 * 0
        s = s * jnp.exp(m - m_new) \
            + jnp.sum(jnp.exp(lg - m_new[:, None]), axis=1)
        m = m_new
        cols = lo + jnp.arange(hi - lo, dtype=jnp.int32)
        pick = pick + jnp.sum(
            jnp.where(cols[None, :] == labels[:, None], lg, 0.0), axis=1)
    return m + jnp.log(s), pick


# ---------------------------------------------------------------------------
# Pallas kernel: same math, one grid
# ---------------------------------------------------------------------------
def _ce_kernel(h_ref, w_ref, lab_ref, lse_ref, pick_ref, m_s, s_s, p_s,
               *, block_v, v):
    vi = pl.program_id(1)

    @pl.when(vi == 0)
    def _init():
        # fresh row tile: the general update below then matches the XLA
        # formulation's (-inf, 0, 0) start bit for bit
        m_s[...] = jnp.full(m_s.shape, -jnp.inf, jnp.float32)
        s_s[...] = jnp.zeros(s_s.shape, jnp.float32)
        p_s[...] = jnp.zeros(p_s.shape, jnp.float32)

    h = h_ref[...].astype(jnp.float32)                    # [BN, D]
    wb = w_ref[...].astype(jnp.float32)                   # [D, BV]
    lg = jax.lax.dot_general(
        h, wb, (((1,), (0,)), ((), ())),
        preferred_element_type=jnp.float32)               # [BN, BV]
    # ragged vocab tail: pad columns past V contribute exp(-inf) == 0 to
    # the sum and never win the max, exactly like the XLA formulation's
    # exact-sized last chunk
    col = vi * block_v + jax.lax.broadcasted_iota(jnp.int32, lg.shape, 1)
    lg = jnp.where(col < v, lg, -jnp.inf)
    cm = jnp.max(lg, axis=1, keepdims=True)               # [BN, 1]
    m_old = m_s[...]
    m_new = jnp.maximum(m_old, cm)
    s_s[...] = s_s[...] * jnp.exp(m_old - m_new) \
        + jnp.sum(jnp.exp(lg - m_new), axis=1, keepdims=True)
    m_s[...] = m_new
    hit = col == lab_ref[...]                             # [BN, BV]
    p_s[...] = p_s[...] + jnp.sum(jnp.where(hit, lg, 0.0), axis=1,
                                  keepdims=True)

    @pl.when(vi == pl.num_programs(1) - 1)
    def _emit():
        lse_ref[...] = m_s[...] + jnp.log(s_s[...])
        pick_ref[...] = p_s[...]


@functools.lru_cache(maxsize=32)
def _make_ce_call(n, d, v, block_n, block_v, interpret):
    nt = -(-n // block_n)
    vt = -(-v // block_v)
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=0,
        grid=(nt, vt),
        in_specs=[
            pl.BlockSpec((block_n, d), lambda ni, vi: (ni, 0)),
            pl.BlockSpec((d, block_v), lambda ni, vi: (0, vi)),
            pl.BlockSpec((block_n, 1), lambda ni, vi: (ni, 0)),
        ],
        out_specs=[
            pl.BlockSpec((block_n, 1), lambda ni, vi: (ni, 0)),
            pl.BlockSpec((block_n, 1), lambda ni, vi: (ni, 0)),
        ],
        scratch_shapes=[pltpu.VMEM((block_n, 1), jnp.float32),
                        pltpu.VMEM((block_n, 1), jnp.float32),
                        pltpu.VMEM((block_n, 1), jnp.float32)],
    )

    def call(h2d, w, lab2d):
        return pl.pallas_call(
            functools.partial(_ce_kernel, block_v=block_v, v=v),
            grid_spec=grid_spec,
            out_shape=[jax.ShapeDtypeStruct((n, 1), jnp.float32),
                       jax.ShapeDtypeStruct((n, 1), jnp.float32)],
            interpret=interpret,
            name=KERNEL_NAME,
        )(h2d, w, lab2d)

    return call


def _kernel_parts(h2d, w, labels, block_v=None):
    """Pallas dispatch (raw jax arrays) -> (lse [N], pick [N]). Caller
    guarantees :func:`supported` (tests pass ``block_v`` explicitly and
    run the interpreter off-TPU)."""
    n, d = h2d.shape
    v = w.shape[1]
    bn, bv = _blocks(n, d, v, jnp.dtype(h2d.dtype).itemsize)
    if block_v is not None:
        bv = int(block_v)
    call = _make_ce_call(n, d, v, bn, bv, _interpret())
    lse2, pick2 = call(h2d, w, labels.reshape(n, 1))
    return lse2[:, 0], pick2[:, 0]


# ---------------------------------------------------------------------------
# custom VJP: backward recomputes each chunk's logits
# ---------------------------------------------------------------------------
@functools.lru_cache(maxsize=8)
def _fused_ce_vjp_fn(use_kernel, chunk, ignore_index):
    """Module-level custom-VJP per-token nll, one per (impl, chunk,
    ignore) choice. ``labels`` is a PRIMAL (float0 cotangent), never a
    closure — the ``grouped_gemm`` contract: a closed-over traced value
    would leak into the partial-eval jaxpr's constants and crash the
    backward lowering."""

    def parts(h2d, w, lab):
        if use_kernel:
            return _kernel_parts(h2d, w, lab)
        return _xla_parts(h2d, w, lab, chunk)

    def nll_of(lse, pick, lab):
        return jnp.where(lab != ignore_index, lse - pick, 0.0)

    @jax.custom_vjp
    def f(h2d, w, lab):
        lse, pick = parts(h2d, w, lab)
        return nll_of(lse, pick, lab)

    def fwd(h2d, w, lab):
        lse, pick = parts(h2d, w, lab)
        return nll_of(lse, pick, lab), (h2d, w, lab, lse)

    # the backward is XLA's: its ops carry the kernel's name as a scope,
    # so a trace finds the whole of the head's cost under one name
    @jax.named_scope(KERNEL_NAME)
    def bwd(res, g):
        h2d, w, lab, lse = res
        n, d = h2d.shape
        v = w.shape[1]
        h32 = h2d.astype(jnp.float32)
        coef = jnp.where(lab != ignore_index,
                         g.astype(jnp.float32), 0.0)      # [N]
        dh = jnp.zeros((n, d), jnp.float32)
        # each vocab slot of d_w is written exactly once, so the chunks
        # land in ONE preallocated buffer via in-place slice updates —
        # a concatenate would keep every piece alive until the join
        dw = jnp.zeros((d, v), w.dtype)
        for lo in range(0, v, chunk):
            hi = min(lo + chunk, v)
            wc = jax.lax.slice_in_dim(w, lo, hi,
                                      axis=1).astype(jnp.float32)
            # recompute this chunk's logits: dlogits = (softmax -
            # onehot) * coef, so d_hidden/d_w accumulate chunk by chunk
            # and [N, V] never exists in the backward either
            lg = jax.lax.dot_general(
                h32, wc, (((1,), (0,)), ((), ())),
                preferred_element_type=jnp.float32)
            p = jnp.exp(lg - lse[:, None])
            cols = lo + jnp.arange(hi - lo, dtype=jnp.int32)
            hot = (cols[None, :] == lab[:, None]).astype(jnp.float32)
            dlg = (p - hot) * coef[:, None]               # [N, hi-lo]
            dh = dh + jax.lax.dot_general(
                dlg, wc, (((1,), (1,)), ((), ())),
                preferred_element_type=jnp.float32)
            dwc = jax.lax.dot_general(
                h32, dlg, (((0,), (0,)), ((), ())),
                preferred_element_type=jnp.float32).astype(w.dtype)
            dw = jax.lax.dynamic_update_slice(dw, dwc, (0, lo))
        return (dh.astype(h2d.dtype), dw,
                np.zeros(lab.shape, jax.dtypes.float0))

    f.defvjp(fwd, bwd)
    return f


def _loss_raw(h2d, w, lab, chunk, ignore_index, use_kernel):
    """Raw-array mean loss (the building block train steps trace over):
    ``sum(nll)/max(count, 1)``, the ``F.cross_entropy`` mean contract."""
    f = _fused_ce_vjp_fn(bool(use_kernel), int(chunk), int(ignore_index))
    lab = lab.astype(jnp.int32)
    nll = f(h2d, w, lab)
    valid = (lab != ignore_index).astype(jnp.float32)
    return jnp.sum(nll) / jnp.maximum(jnp.sum(valid), 1.0)


def _spmd_loss_raw(h2d, w, lab, ignore_index, jax_mesh, axis):
    """Vocab-parallel SPMD formulation: ONE batched product whose vocab
    dim is constrained to the mesh axis carrying ``Shard(1)`` of ``w`` —
    each device materializes only its ``[N, V/mp]`` shard and GSPMD
    partitions the logsumexp/pick reductions (plain jax AD handles the
    backward; GSPMD partitions it the same way)."""
    from jax.sharding import NamedSharding, PartitionSpec as P

    lab = lab.astype(jnp.int32)
    h32 = h2d.astype(jnp.float32)
    lg = jax.lax.dot_general(
        h32, w.astype(jnp.float32), (((1,), (0,)), ((), ())),
        preferred_element_type=jnp.float32)               # [N, V] sharded
    lg = jax.lax.with_sharding_constraint(
        lg, NamedSharding(jax_mesh, P(P.UNCONSTRAINED, axis)))
    m = jnp.max(lg, axis=1)
    lse = m + jnp.log(jnp.sum(jnp.exp(lg - m[:, None]), axis=1))
    valid = lab != ignore_index
    safe = jnp.where(valid, lab, 0)
    pick = jnp.take_along_axis(lg, safe[:, None], axis=1)[:, 0]
    nll = jnp.where(valid, lse - pick, 0.0)
    denom = jnp.maximum(jnp.sum(valid.astype(jnp.float32)), 1.0)
    return jnp.sum(nll) / denom


def _vocab_parallel_axis(weight):
    """(jax_mesh, axis_name) when ``weight`` [D, V] is annotated with a
    vocab Shard (tensor dim 1) over some mesh axis, else None."""
    if not getattr(weight, "is_dist", False):
        return None
    placements = getattr(weight, "_placements", None)
    mesh = getattr(weight, "_process_mesh", None)
    if not placements or mesh is None:
        return None
    for mesh_dim, p in enumerate(placements):
        if getattr(p, "is_shard", lambda d=None: False)(1):
            return mesh.to_jax_mesh(), mesh.dim_names[mesh_dim]
    return None


# ---------------------------------------------------------------------------
# Tensor-level entry points
# ---------------------------------------------------------------------------
def fused_linear_cross_entropy(hidden, weight, labels, ignore_index=-100,
                               vocab_chunk=None):
    """Mean next-token cross entropy of ``hidden @ weight`` against
    ``labels`` without materializing the logits (module docstring).
    ``hidden`` [..., D] and ``labels`` [...] flatten together; returns a
    scalar f32 Tensor. Dispatches the Pallas kernel when
    :func:`supported` holds, the chunked XLA formulation otherwise, and
    the GSPMD vocab-parallel formulation when ``weight`` carries a
    vocab ``Shard`` annotation; differentiable (custom VJP on the
    chunked paths)."""
    spmd = _vocab_parallel_axis(weight)
    chunk = int(vocab_chunk) if vocab_chunk else default_chunk()

    def fn(h, w, lab):
        d = h.shape[-1]
        h2d = h.reshape((-1, d))
        lab1 = lab.reshape((-1,))
        if spmd is not None:
            return _spmd_loss_raw(h2d, w, lab1, ignore_index, *spmd)
        c = max(8, min(chunk, w.shape[1]))
        return _loss_raw(h2d, w, lab1, c, ignore_index,
                         supported(h2d, w))

    return run_op("fused_linear_cross_entropy", fn,
                  (hidden, weight, labels))


def fused_linear_cross_entropy_xla(hidden, weight, labels,
                                   ignore_index=-100, vocab_chunk=None):
    """Chunked-XLA formulation (parity bar and non-Pallas fallback)."""
    chunk = int(vocab_chunk) if vocab_chunk else default_chunk()

    def fn(h, w, lab):
        d = h.shape[-1]
        h2d = h.reshape((-1, d))
        c = max(8, min(chunk, w.shape[1]))
        return _loss_raw(h2d, w, lab.reshape((-1,)), c, ignore_index,
                         False)

    return run_op("fused_linear_cross_entropy_xla", fn,
                  (hidden, weight, labels))
