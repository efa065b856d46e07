"""Ragged paged LATENT attention: the mixed prefill+decode kernel of
`ragged_paged_attention` for layers whose cache is one shared latent
row a token (multi-head latent attention, served in its absorbed form).

What differs from the K/V kernel: a token keeps ONE row ``[c_kv | k_rope
| zero pad]`` of width ``W`` (a multiple of 128 lanes) for ALL heads, so
the pool is ``[P, page, W]``; every head's query arrives already
absorbed into the latent space (``q' = q_nope W_kvb[K]^T``, then
``[q' | q_rope | 0]``, width ``W``); keys are the row, values are its
first ``v_width`` lanes. One row therefore serves all ``H`` heads and a
``QB``-token chunk row is one ``[QB*H, W] x [W, block]`` product.

Shapes (R rows of one dispatch, T packed tokens):
  q            [T, H, W]        absorbed, roped queries on the packed
                                token axis (row r's tokens sit
                                contiguously at ``w_flat + q_start -
                                w_start``, as in the rope-fused kernel)
  new_rows     [T, W]           this dispatch's latent rows, packed
  pool         [P, page, W]     the layer's latent pages
  block_tables [R, width] int32, kv_lens/q_starts/q_lens/w_starts/
  w_flats/w_ends [R] int32      as `fused_ragged_paged_attention`
  -> out       [R, QB, H, v_width], pool (aliased)

The kernel (named `paddle_tpu.ragged_mla_attn`) runs grid ``(R,)`` and
walks the pages a row HOLDS, as `_fused_rope_kernel` does: a
`fori_loop` of ``ceil(kv_len / (B*page))`` trips, each block's pages
fetched through the scalar-prefetched table by one DMA a page, the next
block in flight while this one is computed; its time does not follow
the table's width. Positions ``[w_start, kv_len)`` were produced by
this dispatch and are overlaid from ``new_rows`` in VMEM (HBM is never
trusted for them); the sequence's last row writes those pages back
once. Dots take operands in the pool's dtype (bf16 on the chip) and
accumulate in f32.

`ragged_mla_attention_xla` is the same mathematics as two dependent XLA
ops (scatter the new rows, then gather every row's pages and attend):
the kernel's parity bar, and the path taken where Pallas cannot serve
the shapes. Inference only."""

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

__all__ = ["ragged_mla_attention", "ragged_mla_attention_xla",
           "supported", "latent_row_width"]

NEG_INF = -1e30
_WALK_TOKENS = 256


def _interpret():
    return jax.default_backend() != "tpu"


def latent_row_width(kv_rank, rope_dim):
    """Lanes of one cached row: ``kv_rank + rope_dim`` rounded up to
    whole 128-lane tiles (the pad lanes stay zero)."""
    return -(-(kv_rank + rope_dim) // 128) * 128


def supported(q, new_rows, pool, v_width, qblock):
    """Can the Pallas program serve these shapes? Off the chip the
    interpreter takes anything; on it the row and the value width are
    whole lane tiles, a page whole sublane tiles of the pool's dtype,
    and ``QB * H`` a whole sublane tile."""
    if not _HAS_PLTPU:
        return False
    t, h, w = q.shape
    _, page, pw = pool.shape
    if pw != w or new_rows.shape != (t, w) or v_width > w or qblock < 1 \
            or w % 128:
        return False
    if _interpret():
        return True
    sub = 32 // jnp.dtype(pool.dtype).itemsize
    return v_width % 128 == 0 and page % sub == 0 \
        and (qblock * h) % sub == 0


def _mla_kernel(tables_ref, kv_lens_ref, q_starts_ref, q_lens_ref,
                w_starts_ref, w_flats_ref, w_ends_ref, q_hbm, pool_hbm,
                nk_ref, o_ref, pool_out, kbuf, q_s, fsem, wsem, qsem,
                acc_ref, m_ref, l_ref, *, page_size, bpages, heads,
                scale, qblock, v_width):
    r = pl.program_id(0)
    bt = bpages * page_size
    # query tokens a sub-block: about 128 softmax rows, a divisor of
    # the query block
    sbt = max(d for d in range(1, qblock + 1)
              if qblock % d == 0 and d * heads <= max(128, heads))
    sb = sbt * heads
    width = tables_ref.shape[1]
    kv_len = kv_lens_ref[r]
    q_len = q_lens_ref[r]
    q_start = q_starts_ref[r]
    ws = w_starts_ref[r]
    # a context longer than its table (never from the engine) is
    # attended as far as the table reaches, as the XLA formulation does
    ctx = jnp.minimum(kv_len, width * page_size)
    nblk = jnp.where(q_len > 0, pl.cdiv(ctx, bt), 0)
    npages = pl.cdiv(ctx, page_size)

    acc_ref[...] = jnp.zeros_like(acc_ref)
    m_ref[...] = jnp.full_like(m_ref, NEG_INF)
    l_ref[...] = jnp.zeros_like(l_ref)

    def page_dmas(act, i, slot, pool, sem, into_vmem, lo=0):
        """``start`` or ``wait`` the per-page copies of block ``i``
        between the pool and slot ``slot`` of the buffer: table slots
        ``[max(lo, first of the block), min(end of the block, pages the
        row holds))``, the same for both acts."""
        def one(pg, carry):
            pid = tables_ref[r, pg]
            piece = kbuf.at[slot, pl.ds(pl.multiple_of(
                (pg - i * bpages) * page_size, page_size), page_size), :]
            src, dst = (pool.at[pid], piece) if into_vmem \
                else (piece, pool.at[pid])
            getattr(pltpu.make_async_copy(src, dst, sem.at[slot]), act)()
            return carry

        jax.lax.fori_loop(jnp.maximum(lo, i * bpages),
                          jnp.minimum((i + 1) * bpages, npages), one, 0)

    fetch = functools.partial(page_dmas, pool=pool_hbm, sem=fsem,
                              into_vmem=True)
    # the pages that overlap the write span [w_start, kv_len)
    write = functools.partial(page_dmas, pool=pool_out, sem=wsem,
                              into_vmem=False, lo=ws // page_size)

    @pl.when(nblk > 0)
    def _row():
        fetch("start", 0, 0)
        # the row's query tokens sit contiguously on the packed axis
        tq = q_hbm.shape[0]
        f0q = jnp.clip(w_flats_ref[r] + q_start - ws, 0, tq - qblock)
        cp = pltpu.make_async_copy(q_hbm.at[pl.ds(f0q, qblock)], q_s,
                                   qsem.at[0])
        cp.start()
        cp.wait()

    last_row = (kv_len == w_ends_ref[r])

    def block(i, carry):
        slot = i % 2
        block_start = i * bt
        fetch("wait", i, slot)

        @pl.when(i + 1 < nblk)
        def _prefetch():
            fetch("start", i + 1, 1 - slot)

        replay = block_start + bt > ws
        kpos = block_start + jax.lax.broadcasted_iota(
            jnp.int32, (bt, 1), 0)

        @pl.when(replay)
        def _overlay():
            # position pos of the write span lives at packed index
            # w_flat + pos - w_start (+ one block of left pad)
            tpad = nk_ref.shape[1]
            f0 = jnp.clip(w_flats_ref[r] + block_start - ws + bt, 0,
                          tpad - bt)
            fresh = (kpos >= ws) & (kpos < kv_len)
            # the new rows ride lane-tile major, [W/128, tpad, 128]:
            # Mosaic takes a dynamic sublane offset on a ref one lane
            # tile wide (as `_fused_rope_kernel`'s [Hk, tpad, 128])
            new = nk_ref[:, pl.ds(f0, bt), :].astype(kbuf.dtype)
            for j in range(new.shape[0]):
                lanes = pl.ds(j * 128, 128)
                kbuf[slot, :, lanes] = jnp.where(
                    fresh, new[j], kbuf[slot, :, lanes])

            @pl.when(last_row)
            def _write():
                write("start", i, slot)

        k = kbuf[slot]                                   # [bt, W]
        # nothing at or past the context is used: a slot there may hold
        # anything (a NaN would survive the zero weight of the P.V dot)
        v = jnp.where(kpos < ctx, k[:, :v_width], jnp.zeros_like(
            k[:, :v_width]))

        def sub(j, c):
            # `sbt` query tokens (all their heads) at a time, and only
            # those the row has: a decode row of a mixed dispatch
            # computes one sub-block, not the whole query block
            tok0 = pl.multiple_of(j * sbt, sbt)
            rows = pl.ds(pl.multiple_of(j * sb, sb), sb)
            q2 = q_s[pl.ds(tok0, sbt)].reshape(sb, q_s.shape[-1])
            s = jax.lax.dot_general(
                q2, k, (((1,), (1,)), ((), ())),
                preferred_element_type=jnp.float32) * scale  # [sb, bt]
            cols = block_start + jax.lax.broadcasted_iota(
                jnp.int32, s.shape, 1)
            # softmax rows are laid out [tokens, H] flattened
            qrow = tok0 + jax.lax.broadcasted_iota(
                jnp.int32, s.shape, 0) // heads
            valid = (cols <= q_start + qrow) & (cols < ctx) \
                & (qrow < q_len)
            s = jnp.where(valid, s, NEG_INF)
            m_prev, l_prev = m_ref[rows], l_ref[rows]
            m_new = jnp.maximum(m_prev,
                                jnp.max(s, axis=-1, keepdims=True))
            alpha = jnp.exp(m_prev - m_new)
            # a fully masked softmax row must add nothing
            pexp = jnp.where(valid, jnp.exp(s - m_new), 0.0)
            l_ref[rows] = l_prev * alpha + jnp.sum(pexp, axis=-1,
                                                   keepdims=True)
            m_ref[rows] = m_new
            acc_ref[rows] = acc_ref[rows] * alpha + jax.lax.dot_general(
                pexp.astype(k.dtype), v, (((1,), (0,)), ((), ())),
                preferred_element_type=jnp.float32)
            return c

        jax.lax.fori_loop(0, pl.cdiv(q_len, sbt), sub, 0)

        @pl.when(replay & last_row)
        def _written():
            write("wait", i, slot)

        return carry

    jax.lax.fori_loop(0, nblk, block, 0)
    l = l_ref[...]
    out = acc_ref[...] / jnp.where(l > 0.0, l, 1.0)
    o_ref[0] = jnp.where(l > 0.0, out, 0.0).astype(o_ref.dtype)


@functools.lru_cache(maxsize=32)
def _make_mla(scale, page_size, bpages, qblock, heads, v_width, dtype,
              interpret):
    bt = bpages * page_size

    def call(qp, pool, nk, tables, kv_lens, q_starts, q_lens, w_starts,
             w_flats, w_ends):
        lt, tpad, _ = nk.shape
        w = lt * 128
        r = tables.shape[0]
        rows = qblock * heads
        grid_spec = pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=7,
            grid=(r,),
            in_specs=[
                # q and the pool stay in HBM: the kernel fetches a
                # row's query block and the pages it holds itself
                pl.BlockSpec(memory_space=pl.ANY),
                pl.BlockSpec(memory_space=pl.ANY),
                # the dispatch's new rows ride whole (fetched once)
                pl.BlockSpec((lt, tpad, 128),
                             lambda ri, *refs: (0, 0, 0)),
            ],
            out_specs=[
                pl.BlockSpec((1, rows, v_width),
                             lambda ri, *refs: (ri, 0, 0)),
                pl.BlockSpec(memory_space=pl.ANY),
            ],
            scratch_shapes=[
                pltpu.VMEM((2, bt, w), pool.dtype),
                pltpu.VMEM((qblock, heads, w), qp.dtype),
                pltpu.SemaphoreType.DMA((2,)),       # fetch [slot]
                pltpu.SemaphoreType.DMA((2,)),       # write-back
                pltpu.SemaphoreType.DMA((1,)),       # the query block
                pltpu.VMEM((rows, v_width), jnp.float32),
                pltpu.VMEM((rows, 1), jnp.float32),
                pltpu.VMEM((rows, 1), jnp.float32),
            ],
        )
        f32 = 4
        vmem = 2 * tpad * w * f32 + 2 * bt * w * pool.dtype.itemsize \
            + rows * w * qp.dtype.itemsize \
            + rows * (3 * v_width + 4 * bt + 2 * 128) * f32
        return pl.pallas_call(
            functools.partial(_mla_kernel, page_size=page_size,
                              bpages=bpages, heads=heads, scale=scale,
                              qblock=qblock, v_width=v_width),
            grid_spec=grid_spec,
            out_shape=[
                jax.ShapeDtypeStruct((r, rows, v_width), dtype),
                jax.ShapeDtypeStruct(pool.shape, pool.dtype),
            ],
            # inputs 0-6 scalar prefetch, 7 packed q, 8 the pool
            input_output_aliases={8: 1},
            compiler_params=pltpu.CompilerParams(
                dimension_semantics=("arbitrary",),
                vmem_limit_bytes=min(100 << 20,
                                     max(32 << 20, vmem + (8 << 20)))),
            interpret=interpret,
            name="paddle_tpu.ragged_mla_attn",
        )(tables, kv_lens, q_starts, q_lens, w_starts, w_flats, w_ends,
          qp, pool, nk)

    # jitted, so that the layers of one step program share one trace
    # and one lowering of the kernel
    return jax.jit(call)


def _kernel_impl(q, new_rows, pool, block_tables, kv_lens, q_starts,
                 q_lens, w_starts, w_flats, w_ends, v_width, scale,
                 qblock):
    t, h, w = q.shape
    page_size = pool.shape[1]
    r = block_tables.shape[0]
    bpages = max(1, _WALK_TOKENS // page_size)
    bt = bpages * page_size
    # the replay slice is a whole block of the walk: a block of pad on
    # either side. An f32 container: a packed 16-bit row cannot be
    # sliced at an arbitrary sublane
    tpad = -(-(t + 2 * bt) // 8) * 8
    nk = jnp.pad(new_rows.astype(pool.dtype).astype(jnp.float32),
                 ((bt, tpad - t - bt), (0, 0)))
    nk = nk.reshape(tpad, w // 128, 128).transpose(1, 0, 2)
    qp = jnp.pad(q.astype(pool.dtype), ((0, qblock), (0, 0), (0, 0)))
    call = _make_mla(float(scale), page_size, bpages, int(qblock), h,
                     int(v_width), jnp.dtype(q.dtype), _interpret())
    i32 = lambda a: a.astype(jnp.int32)      # noqa: E731
    tables = jnp.clip(i32(block_tables), 0, pool.shape[0] - 1)
    out, pool = call(qp, pool, nk, tables, i32(kv_lens), i32(q_starts),
                     i32(q_lens), i32(w_starts), i32(w_flats),
                     i32(w_ends))
    return out.reshape(r, qblock, h, v_width), pool


def _write_rows(new_rows, pool, tables, q_starts, q_lens, w_starts,
                w_flats, qblock):
    """Scatter the dispatch's rows into their pages (traceable): token
    ``qi`` of row r sits at packed index ``w_flat + q_start - w_start +
    qi`` and at position ``q_start + qi`` of its sequence."""
    p, page, _ = pool.shape
    qi = jnp.arange(qblock, dtype=jnp.int32)[None, :]
    pos = q_starts[:, None] + qi
    flat = (w_flats + q_starts - w_starts)[:, None] + qi
    live = qi < q_lens[:, None]
    slot = jnp.clip(pos // page, 0, tables.shape[1] - 1)
    pg = jnp.take_along_axis(tables, slot, axis=1)
    pg = jnp.where(live, pg, p)                      # dropped
    rows = new_rows[jnp.clip(flat, 0, new_rows.shape[0] - 1)]
    return pool.at[pg.reshape(-1), (pos % page).reshape(-1)].set(
        rows.reshape(-1, rows.shape[-1]).astype(pool.dtype), mode="drop")


def _xla_impl(q, new_rows, pool, block_tables, kv_lens, q_starts,
              q_lens, w_starts, w_flats, w_ends, v_width, scale, qblock):
    t, h, w = q.shape
    p, page, _ = pool.shape
    r = block_tables.shape[0]
    i32 = lambda a: a.astype(jnp.int32)      # noqa: E731
    tables = jnp.clip(i32(block_tables), 0, p - 1)
    kv_lens, q_starts, q_lens = i32(kv_lens), i32(q_starts), i32(q_lens)
    pool = _write_rows(new_rows, pool, tables, q_starts, q_lens,
                       i32(w_starts), i32(w_flats), qblock)
    qi = jnp.arange(qblock, dtype=jnp.int32)[None, :]
    flat = (i32(w_flats) + q_starts - i32(w_starts))[:, None] + qi
    # operands rounded to the pool's dtype as the kernel's are, then
    # widened: their products are exact in f32 either way
    f32 = jnp.float32
    q4 = q[jnp.clip(flat, 0, t - 1)].astype(pool.dtype).astype(f32)
    k = pool[tables].reshape(r, -1, w).astype(f32)        # [R, S, W]
    logits = jnp.einsum("rqhw,rsw->rhqs", q4, k) * scale
    kpos = jnp.arange(k.shape[1])[None, None, None, :]
    qpos = (q_starts[:, None] + qi)[:, None, :, None]
    mask = (kpos <= qpos) & (kpos < kv_lens[:, None, None, None]) \
        & (qi < q_lens[:, None])[:, None, :, None]
    logits = jnp.where(mask, logits, NEG_INF)
    wgt = jax.nn.softmax(logits, axis=-1)
    wgt = jnp.where(jnp.any(mask, axis=-1, keepdims=True), wgt, 0.0)
    out = jnp.einsum("rhqs,rsv->rqhv", wgt.astype(pool.dtype).astype(f32),
                     k[..., :v_width])
    return out.astype(q.dtype), pool


def _dispatch(impl, name, q, new_rows, pool, block_tables, kv_lens,
              q_starts, q_lens, w_starts, w_flats, w_ends, v_width,
              scale, qblock):
    s = float(scale) if scale is not None \
        else 1.0 / math.sqrt(q.shape[-1])

    def fn(q, nr, pl_, bt, kl, qs, ql, wss, wfs, wes):
        return impl(q, nr, pl_, bt, kl, qs, ql, wss, wfs, wes,
                    int(v_width), s, int(qblock))

    return run_op(name, fn, (q, new_rows, pool, block_tables, kv_lens,
                             q_starts, q_lens, w_starts, w_flats,
                             w_ends), differentiable=False)


def ragged_mla_attention(q, new_rows, pool, block_tables, kv_lens,
                         q_starts, q_lens, w_starts, w_flats, w_ends,
                         v_width, scale=None, qblock=1):
    """Write this dispatch's latent rows into their pages and attend
    through them (see the module docstring). Returns ``(out [R, QB, H,
    v_width], pool)``. The Pallas program where `supported`, else the
    XLA formulation (the trace then shows no
    ``paddle_tpu.ragged_mla_attn``)."""
    shapes = [getattr(a, "_data", a) for a in (q, new_rows, pool)]
    impl = _kernel_impl if supported(*shapes, v_width, qblock) \
        else _xla_impl
    return _dispatch(impl, "ragged_mla_attention", q, new_rows, pool,
                     block_tables, kv_lens, q_starts, q_lens, w_starts,
                     w_flats, w_ends, v_width, scale, qblock)


def ragged_mla_attention_xla(q, new_rows, pool, block_tables, kv_lens,
                             q_starts, q_lens, w_starts, w_flats, w_ends,
                             v_width, scale=None, qblock=1):
    """The XLA formulation: scatter, then gather and attend."""
    return _dispatch(_xla_impl, "ragged_mla_attention_xla", q, new_rows,
                     pool, block_tables, kv_lens, q_starts, q_lens,
                     w_starts, w_flats, w_ends, v_width, scale, qblock)
