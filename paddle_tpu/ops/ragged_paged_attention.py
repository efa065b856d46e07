"""Ragged paged attention: ONE kernel for a mixed prefill+decode batch.

Capability reference: *Ragged Paged Attention* (arXiv 2604.15464) — a
single TPU kernel that consumes a batch of variable-length prefill
chunks AND single-token decode rows over a shared paged KV pool, so a
serving scheduler never has to serialize the two phases into separate
dispatches. This is the kernel behind the chunked-prefill serving
engine (`paddle_tpu/inference/serving.py`): every layer of every engine
step is one call of `fused_ragged_paged_attention` over rows described
by per-row ``(query_len, kv_len)`` metadata, whether the row is a
128-token prompt chunk or one decode token. The call ropes q and the
new K, writes the new K/V rows into their pages and attends through
them, all in one Pallas program: there is one program a pool dtype
(float pools: `_fused_rope_kernel`; int8 pools with scale sidecars:
`_fused_rope_kernel_q8`) and nothing selects between programs.

Shapes (T packed tokens in R rows, each a prefill chunk or a decode
step of one sequence, rows padded to the static query block QB):
  q             [T, H, D]           PRE-rope, in the packed token
                                    layout: row r's query tokens sit
                                    contiguously at ``w_flats[r] +
                                    q_starts[r] - w_starts[r]``
  new_k, new_v  [T, Hk, D]          the dispatch's packed K/V rows
                                    (new_k PRE-rope)
  rope_sin/cos  [T, D] f32          per-dispatch tables, one row a
                                    packed token (`rope_tables`: neox
                                    duplicated-half layout, computed
                                    once a dispatch, shared by layers)
  k_pages       [P, Hk, page, D]    global pool, head-major (same layout
                                    as `paged_attention`)
  v_pages       [P, Hk, page, D]
  k_scale       [P, Hk, page, 1]    f32 dequant sidecars of int8 pools:
  v_scale       [P, Hk, page, 1]    per-head per-slot symmetric scales,
                                    the math of `quantize_kv_int8`; the
                                    kernel dequantizes ``int8 * scale``
                                    in f32 before the softmax
  block_tables  [R, W] int32        page ids per ROW's sequence (tail
                                    entries clamped into [0, P))
  kv_lens       [R] int32           total context of the row's sequence
                                    *including* this row's query tokens
                                    (0 marks an inactive row — output 0)
  q_starts      [R] int32           absolute position of the row's first
                                    query token in its sequence
  q_lens        [R] int32           valid query tokens in the row
                                    (1 for decode rows, up to QB for
                                    prefill chunks)
  w_starts      [R] int32           first position of the row's sequence
                                    that THIS dispatch writes
  w_flats       [R] int32           that position's packed index
  w_ends        [R] int32           the sequence's final kv_len in this
                                    dispatch (its last row writes back)
  -> out        [R, QB, H, D]       entries at qi >= q_lens[r] are zeros
     and the updated pools (and sidecars), aliased onto the inputs

Semantics: query token qi of row r sits at absolute position
``p = q_starts[r] + qi`` and attends kv positions ``[0, p]`` (causal)
clipped to ``[0, kv_lens[r])``. Two chunks of the same sequence may
appear as two rows of one batch (same block table, consecutive
q_starts). Inference-only: no VJP.

Ordering contract (the subtlety): later prefill chunks of one prompt
may sit in the SAME grid as the rows that produce the K/V they must
attend. The kernels do not rely on in-kernel write-then-read
visibility at all — pipelined page fetches may legally race in-kernel
writes. Instead every row REPLAYS the dispatch's writes on read:
positions ``[w_start[r], kv_lens[r])`` of row r's sequence were
written by rows <= r of this dispatch and are overlaid from the packed
``new_k/new_v`` rows (their flat indices are affine in the position:
chunks of one sequence are packed contiguously in position order, so
position p lives at flat index ``w_flat[r] + p - w_start[r]``); only
positions below ``w_start[r]`` come from the fetched page. The HBM
write-back itself is done ONCE per page, by the sequence's LAST row in
the dispatch (``kv_lens[r] == w_end[r]``) — no page is the write
target of two steps, so no copy-out ordering between steps is ever
required.

The rotation happens in VMEM — ``x * cos + rotate_half(x) * sin`` in
f32, cast back to the model dtype — before the write/attention math:
the transcendentals live in the XLA-computed tables, so the kernels
add only IEEE-exact multiplies and adds, and the pool bytes they write
are bitwise those of the rope-then-scatter reference.

The float program (`_fused_rope_kernel`, every float-pool engine's)
walks the K/V a row HOLDS: grid (R,), the pools left in HBM, and
inside a row a loop of ``ceil(kv_lens[r] / (B*page))`` trips over
blocks of B pages that the kernel fetches itself through the table
(one DMA a page for all kv heads, the next block in flight while this
one is computed) — no trip for an inactive row, the same trips
whatever W is. It makes ONE softmax update a block and head, so its
attention output equals the XLA reference's to float rounding (1e-5
relative in f32); a page is written by a DMA of its own and a step
with nothing to write writes nothing. The softmax rows a row computes
follow its ``q_lens``: a row whose query tokens fit `small_tile`
(``group`` rows rounded up to f32 sublane tiles: every decode row of a
mixed dispatch) ropes, scores, accumulates and finishes that tile, all
kv heads in one batched dot; every other row the whole ``QB * group``
block a head at a time. The kernel picks from ``q_lens_ref[r]``: one
program, the same walk, nothing for a caller to say.

The int8 program (`_fused_rope_kernel_q8`) is still a per-page grid
(R, Hk, W) with one online-softmax accumulator in VMEM scratch per
(row, kv-head): the prefetched block table picks which HBM page each
grid step streams into VMEM, and a step at or past ``kv_lens[r]``
skips the arithmetic but still pays its step and its page fetch, so
its time follows the table's width W. Steps whose page holds no new
token write to the caller-designated ``dump_page`` (the serving
engine's trash page). It quantizes the fresh rows in-kernel with
bitwise the math of ``quantize_kv_int8``.

`ragged_paged_attention_xla` and `fused_ragged_paged_attention_xla`
(rope, THEN scatter, THEN read) are the reference the tests hold both
programs to: dependent XLA ops have unambiguous sequential semantics,
which is what the kernels' replay must reproduce.
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

__all__ = ["ragged_paged_attention_xla", "fused_ragged_paged_attention",
           "fused_ragged_paged_attention_xla", "fused_supported",
           "fused_rope_geometry_ok", "rope_tables", "small_tile"]

NEG_INF = -1e30


def _interpret():
    return jax.default_backend() != "tpu"


def _softmax_accumulate(q, k, v, page_start, q_start, q_len, ctx,
                        group, acc_ref, m_ref, l_ref, window=None):
    """ONE step of the shared online-softmax update over the K/V
    positions ``[page_start, page_start + len(k))``: causal/ragged
    masking, running max/sum rescale, accumulator update. Both kernels
    call exactly this body: the accumulation math is maintained in ONE
    place, never per-kernel copies. ``q`` ``[rows, D]`` is pre-scaled
    f32; ``k``/``v`` f32, ``[page, D]`` from the int8 program's
    per-page grid and ``[B*page, D]`` from the float program's walk.
    All of them (and the three refs) may carry one leading axis of kv
    heads: the float program's small tile updates every head by one
    batched dot (``q [Hk, rows, D]``, ``k``/``v`` ``[Hk, B*page, D]``).
    With ``window`` a query at position ``p`` sees the keys
    ``(p - window, p]`` only."""
    heads = tuple(range(q.ndim - 2))
    row, col = q.ndim - 2, q.ndim - 1
    s = jax.lax.dot_general(q, k, (((col,), (col,)), (heads, heads)),
                            preferred_element_type=jnp.float32)
    kpos = page_start + jax.lax.broadcasted_iota(jnp.int32, s.shape, col)
    # query rows are laid out [QB, G] flattened (qi major): the
    # token index of softmax row i is i // G
    qrow = jax.lax.broadcasted_iota(jnp.int32, s.shape, row) // group
    qpos = q_start + qrow
    valid = (kpos <= qpos) & (kpos < ctx) & (qrow < q_len)
    if window is not None:
        valid &= kpos > qpos - window
    s = jnp.where(valid, s, NEG_INF)
    m_prev, l_prev = m_ref[...], l_ref[...]
    m_cur = jnp.max(s, axis=-1, keepdims=True)
    m_new = jnp.maximum(m_prev, m_cur)
    alpha = jnp.exp(m_prev - m_new)
    pexp = jnp.exp(s - m_new)
    # fully-masked softmax rows (a padded query, or a page entirely
    # behind this query's causal horizon) must contribute nothing:
    # with finite NEG_INF, exp(s - m_new) would be exp(0) = 1 when
    # m_new is still NEG_INF, silently polluting l and acc
    pexp = jnp.where(valid, pexp, 0.0)
    l_ref[...] = l_prev * alpha + jnp.sum(pexp, axis=-1, keepdims=True)
    m_ref[...] = m_new
    acc_ref[...] = acc_ref[...] * alpha + jax.lax.dot_general(
        pexp, v, (((col,), (row,)), (heads, heads)),
        preferred_element_type=jnp.float32)


def _softmax_finish(o_ref, acc_ref, l_ref):
    """Emit the normalized accumulator on the last page step. l == 0:
    inactive row (kv_len 0) or padded query row — emit zeros, never
    NaN."""
    l = l_ref[...]
    out = acc_ref[...] / jnp.where(l > 0.0, l, 1.0)
    o_ref[...] = jnp.where(l > 0.0, out, 0.0).astype(o_ref.dtype)


def small_tile(group):
    """Softmax rows of the float program's small tile: one query
    token's ``group`` rows rounded up to whole f32 sublane tiles. A row
    of a dispatch whose query tokens fit it (``q_len * group <=
    small_tile(group)``: every decode row) computes that tile a kv
    head and not its whole query block."""
    return 8 * -(-group // 8)


def fused_rope_geometry_ok(head_dim):
    """Cheap static gate of both programs: Pallas must be importable
    and the head_dim even (the neox rotation splits it in half). The
    serving engine consults it at construction and refuses a layer it
    fails by name; there is no other program to fall back to."""
    return _HAS_PLTPU and head_dim % 2 == 0 and head_dim >= 2


def rope_tables(pos, head_dim, base):
    """Per-dispatch rotary sin/cos tables, one row per PACKED token:
    ``[T, D]`` f32 with the neox duplicated-half layout (``emb =
    concat([ang, ang])``). Bitwise the same values
    `fused_rotary_position_embedding` derives from ``position_ids`` —
    the single source of the angle formula, computed ONCE per dispatch
    and shared by every layer. ``pos`` is any integer array; it is
    flattened to ``[T]``. Pure jnp — safe under jit/trace."""
    inv = 1.0 / (base ** (jnp.arange(0, head_dim, 2,
                                     dtype=jnp.float32) / head_dim))
    ang = pos.reshape(-1).astype(jnp.float32)[:, None] * inv  # [T, D/2]
    emb = jnp.concatenate([ang, ang], axis=-1)                # [T, D]
    return jnp.sin(emb), jnp.cos(emb)


def fused_supported(q, new_k, new_v, k_pages, v_pages, block_tables,
                    kv_lens, q_starts, q_lens, w_starts, w_flats,
                    w_ends, dump_page, *, rope_sin, rope_cos, qblock,
                    k_scale=None, v_scale=None):
    """Preconditions of `fused_ragged_paged_attention` (shapes in the
    module docstring): packed pre-rope ``q [T, H, D]`` with ``new_k/
    new_v [T, Hk, D]`` (T >= 1) and sin/cos tables ``[T, D]``, pools
    ``[P, Hk, page, D]`` (page % 8 == 0, D % 8 == 0, D <= 256 and even,
    H % Hk == 0), tables ``[R, W]``, the seven per-row arrays ``[R]``,
    ``qblock >= 1``, a ``dump_page`` id inside the pool (a page no live
    table references) and, for int8 pools, BOTH scale sidecars shaped
    ``[P, Hk, page, 1]``."""
    def shape(a):
        return tuple(getattr(a, "_data", a).shape)

    if not _HAS_PLTPU or (k_scale is None) != (v_scale is None):
        return False
    qs, ks, bt = shape(q), shape(k_pages), shape(block_tables)
    if len(qs) != 3 or len(ks) != 4 or len(bt) != 2 \
            or shape(v_pages) != ks:
        return False
    t, h, d = qs
    p, hk, page_size, dk = ks
    r = bt[0]
    if k_scale is not None and not (
            shape(k_scale) == shape(v_scale) == ks[:3] + (1,)):
        return False
    if d != dk or hk == 0 or h % hk:
        return False
    if d % 8 or d > 256 or page_size % 8 \
            or not fused_rope_geometry_ok(d):
        return False
    if any(shape(a) != (r,) for a in (kv_lens, q_starts, q_lens,
                                      w_starts, w_flats, w_ends)):
        return False
    if t < 1 or shape(new_k) != (t, hk, d) or shape(new_v) != (t, hk, d):
        return False
    if shape(rope_sin) != (t, d) or shape(rope_cos) != (t, d):
        return False
    try:
        return int(qblock) >= 1 and 0 <= int(dump_page) < p
    except (TypeError, ValueError):
        return False


def _quantize_rows(xf):
    """Per-slot symmetric int8 quantization of ``[page, D]`` f32 rows —
    bitwise the same math as `quantize_kv_int8` (absmax over D,
    ``maximum(amax, 1e-8) / 127``), returning the clipped integer
    values still in f32 (exact in f32; the caller casts to int8 for
    storage and multiplies by the scale for the dequantized read, which
    is bit-identical to storing int8 and dequantizing later). The
    reciprocal multiply (not a divide) matches `quantize_kv_int8`
    exactly — see the note there."""
    amax = jnp.max(jnp.abs(xf), axis=-1, keepdims=True)
    sc = jnp.maximum(amax, 1e-8) * jnp.float32(1.0 / 127.0)
    q = jnp.clip(jnp.round(xf / sc), -127.0, 127.0)
    return q, sc


def _rot_half(x):
    """``rotate_half`` on the last (head_dim) axis — same values as
    `incubate.nn.functional._rotate_half` (neox pairing)."""
    h = x.shape[-1] // 2
    return jnp.concatenate([-x[..., h:], x[..., :h]], axis=-1)


@jax.jit
def _rope_rows(x, sin, cos):
    """The table-driven rotation of packed rows ``[T, heads, D]``
    (tables ``[T, D]`` f32), out in ``x``'s dtype: the unfused
    `_apply_rope` chain. Jitted ON PURPOSE: XLA contracts the mul+add
    chain into an FMA under jit but not in eager dispatch (a 1-ulp
    difference), so whoever must agree with a jitted chain bit for bit
    (`fused_ragged_paged_attention_xla`, `_fused_rope_impl`) calls
    this one."""
    xf = x.astype(jnp.float32)
    sin, cos = (tb.astype(jnp.float32)[:, None, :] for tb in (sin, cos))
    return (xf * cos + _rot_half(xf) * sin).astype(x.dtype)


def _rope_k_page(nk_ref, sin_ref, cos_ref, f0, page_size, dtype):
    """Rope one replay slice of the packed pre-rope K rows: the SAME
    ``f0`` offset picks the rows and their positions' sin/cos (the
    tables are padded identically), and the rotated rows cast back
    through the MODEL ``dtype`` — exactly `_apply_rope`'s output (the
    rows arrive widened to f32, see `_pack_new_rows`). The int8
    rope-fused kernel's; the float one is handed new_k roped already
    (`_rope_rows`, the same chain)."""
    sin_k = sin_ref[pl.ds(f0, page_size), :]
    cos_k = cos_ref[pl.ds(f0, page_size), :]
    k_new = nk_ref[0, pl.ds(f0, page_size), :]
    return (k_new * cos_k + _rot_half(k_new) * sin_k).astype(dtype)


def _rope_q_block(q_ref, sin_ref, cos_ref, q_starts_ref, w_starts_ref,
                  w_flats_ref, r, pad, qblock, group, scale, dtype):
    """Load + rope + scale one row's query block from the packed
    pre-rope q: the row's tokens sit contiguously on the packed axis
    at ``w_flat + (q_start - w_start)`` — the same affine replay index
    the KV overlay uses, read with the already-prefetched scalars
    (this is what deletes the host-side ``_token_gather`` q pack).
    Returns the scaled f32 ``[QB*G, D]`` block the softmax consumes;
    called ONCE per (row, kv-head) — the result lives in VMEM scratch
    across the page loop."""
    tpad = q_ref.shape[1]
    f0q = jnp.clip(w_flats_ref[r] + q_starts_ref[r] - w_starts_ref[r]
                   + pad, 0, tpad - qblock)
    qv = q_ref[0, pl.ds(f0q, qblock), :, :]           # f32 container
    sin_q = sin_ref[pl.ds(f0q, qblock), :][:, None, :]
    cos_q = cos_ref[pl.ds(f0q, qblock), :][:, None, :]
    q_rot = (qv * cos_q + _rot_half(qv) * sin_q) \
        .astype(dtype)                                # [QB, G, D]
    return q_rot.reshape(qblock * group, qv.shape[-1]) \
        .astype(jnp.float32) * scale                  # [QB*G, D]


_WALK_TOKENS = 128
_WALK_VMEM_BYTES = 8 << 20


def _walk_pages(page_size, hk, d, itemsize):
    """Pages a K/V block of `_fused_rope_kernel`'s walk holds: about
    ``_WALK_TOKENS`` tokens (one lane width of scores), halved while
    the two double-buffered ``[2, Hk, B*page, D]`` VMEM buffers would
    pass ``_WALK_VMEM_BYTES``. Derived from the shapes alone: nothing
    for a caller to set."""
    b = max(1, _WALK_TOKENS // page_size)
    while b > 1 and 4 * hk * b * page_size * d * itemsize \
            > _WALK_VMEM_BYTES:
        b //= 2
    return b


def _fused_rope_kernel(tables_ref, kv_lens_ref, q_starts_ref,
                       q_lens_ref, w_starts_ref, w_flats_ref,
                       w_ends_ref, q_ref, k_hbm, v_hbm, nk_ref, nv_ref,
                       sin_ref, cos_ref, o_ref, ko_hbm, vo_hbm,
                       kbuf, vbuf, fsem, wsem, acc_ref, m_ref, l_ref,
                       q_s, *, page_size, bpages, group, scale, qblock,
                       dtype, window=None, read_only=False):
    """The float rope-fused program: grid ``(R,)``, one step a row,
    the kv heads looped inside. The pools stay in HBM; the row walks
    its OWN context in blocks of ``bpages`` pages: ``ceil(kv_len /
    (bpages*page))`` trips of a `fori_loop`, none for an inactive row,
    whatever the table's width. A block's pages come through the
    scalar-prefetched table by one DMA a page (all kv heads of a page
    are one contiguous piece of ``[P, Hk, page, D]``) into slot
    ``i % 2`` of ``kbuf``/``vbuf`` ``[2, Hk, bpages*page, D]``, the
    next block in flight while this one is computed; pages past the
    context are not fetched. q arrives PRE-rope and packed (``[Hk,
    T+QB, G, D]``, an f32 container of model-dtype values) with its
    sin/cos tables ``[T+QB, D]`` whole in VMEM, and its rotation —
    ``x * cos + rotate_half(x) * sin`` in f32, cast back to the model
    dtype — happens here with no transcendentals (the tables carry
    them). new_k/new_v ``[Hk, tpad, D]`` arrive as the pools store
    them: `_fused_rope_impl` has roped new_k's few rows on the way in
    (`_rope_rows`), once a call and not once a reader.

    A block that reaches into this dispatch's write span ``[w_start,
    kv_len)`` overlays the fresh rows from the packed operands in the
    VMEM buffer (every reader replays; HBM is never trusted for them),
    and the sequence's LAST row then writes each such page back once,
    by a DMA from the buffer to the page; no other step writes
    anything. One `_softmax_accumulate` update a block and head, at the
    row's size (`sized`): the small tile for a row whose query tokens
    fit it, where the update of all kv heads is one batched dot, else
    the whole query block a head at a time. Walk, DMAs, overlay and
    write-back are the same for both.

    With ``window`` (a layer whose queries see the last ``window`` keys
    only) the walk starts at the block that holds the first key the
    row's first query sees, fetches no page behind it and masks what
    the block holds of earlier keys: a row reads about ``window +
    q_len`` keys whatever its context. ``read_only`` (a layer that
    attends through ANOTHER layer's pool, which that layer's call has
    already written): nothing is overlaid and nothing written."""
    r = pl.program_id(0)
    hk = kbuf.shape[1]
    bt = bpages * page_size
    width = tables_ref.shape[1]
    kv_len = kv_lens_ref[r]
    q_len = q_lens_ref[r]
    q_start = q_starts_ref[r]
    ws = w_starts_ref[r]
    # a context longer than its table (never from the engine) is
    # attended as far as the table reaches, as the XLA reference does
    ctx = jnp.minimum(kv_len, width * page_size)
    nblk = jnp.where(q_len > 0, pl.cdiv(ctx, bt), 0)

    # the softmax rows this row computes a kv head: the small tile where
    # its query tokens fit one (a decode row of a mixed dispatch, or a
    # row with nothing to do), else the whole query block: a `[8, D] x
    # [D, bt]` dot sixteen times would starve the MXU on a chunk row. A
    # block no larger than the tile (the decode-only shape) has one size.
    qbg = qblock * group
    tile = small_tile(group)
    small = q_len * group <= tile

    def sized(fn):
        """Run ``fn(rows)`` at this row's size."""
        if tile >= qbg:
            return fn(qbg)
        pl.when(small)(functools.partial(fn, tile))
        pl.when(jnp.logical_not(small))(functools.partial(fn, qbg))

    def heads_of(n):
        """The kv heads of one update at ``n`` rows: the whole block a
        head at a time (`[QB*G, D] x [D, bt]` fills the MXU by itself),
        the small tile all heads in one batched dot."""
        return range(hk) if n == qbg else (slice(None),)

    @sized
    def _init(n):
        rows = pl.ds(0, n)
        acc_ref[:, rows] = jnp.zeros((hk, n) + acc_ref.shape[2:],
                                     jnp.float32)
        m_ref[:, rows] = jnp.full((hk, n, 1), NEG_INF, jnp.float32)
        l_ref[:, rows] = jnp.zeros((hk, n, 1), jnp.float32)

    npages = pl.cdiv(ctx, page_size)
    # the first key the row walks, its page and its block: the first
    # one a query of the row sees, or the first one its sequence writes
    # in this dispatch (the sequence's last row writes those pages back)
    first = 0 if window is None else jnp.maximum(
        jnp.minimum(q_start - window + 1, ws), 0)
    first_page = first // page_size
    blk0 = first // bt

    def page_dmas(act, i, slot, pools, sem, into_vmem, lo=0):
        """``start`` or ``wait`` (``act``) the per-page copies of block
        ``i`` between the pools and slot ``slot`` of the buffers: table
        slots ``[max(lo, first of the block), min(end of the block,
        pages the row holds))``, the same for both acts."""
        def one(pg, carry):
            pid = tables_ref[r, pg]
            for s, (pool, buf) in enumerate(zip(pools, (kbuf, vbuf))):
                piece = buf.at[slot, :, pl.ds(pl.multiple_of(
                    (pg - i * bpages) * page_size, page_size),
                    page_size), :]
                src, dst = (pool.at[pid], piece) if into_vmem \
                    else (piece, pool.at[pid])
                getattr(pltpu.make_async_copy(src, dst, sem.at[s, slot]),
                        act)()
            return carry

        jax.lax.fori_loop(jnp.maximum(lo, i * bpages),
                          jnp.minimum((i + 1) * bpages, npages), one, 0)

    fetch = functools.partial(page_dmas, pools=(k_hbm, v_hbm), sem=fsem,
                              into_vmem=True, lo=first_page)
    # the pages that overlap the write span [w_start, kv_len)
    write = functools.partial(page_dmas, pools=(ko_hbm, vo_hbm), sem=wsem,
                              into_vmem=False, lo=ws // page_size)

    @pl.when(nblk > 0)
    def _row():
        fetch("start", blk0, blk0 % 2)
        # the row's query tokens sit contiguously on the packed axis
        # at w_flat + (q_start - w_start), as do their sin/cos rows:
        # rope + scale them once for all kv heads
        tq = q_ref.shape[1]
        f0q = jnp.clip(w_flats_ref[r] + q_start - ws, 0, tq - qblock)

        @sized
        def _rope(n):
            toks = -(-n // group)           # the tokens of n softmax rows
            qv = q_ref[:, pl.ds(f0q, toks), :, :]     # [Hk, toks, G, D]
            sin_q = sin_ref[pl.ds(f0q, toks), :][None, :, None, :]
            cos_q = cos_ref[pl.ds(f0q, toks), :][None, :, None, :]
            q_rot = (qv * cos_q + _rot_half(qv) * sin_q).astype(dtype)
            q_s[:, pl.ds(0, toks * group)] = q_rot.reshape(
                hk, toks * group, qv.shape[-1]).astype(jnp.float32) * scale

    last_row = (kv_len == w_ends_ref[r])

    def block(i, carry):
        slot = i % 2
        block_start = i * bt
        fetch("wait", i, slot)

        @pl.when(i + 1 < nblk)
        def _prefetch():
            fetch("start", i + 1, 1 - slot)

        kpos = block_start + jax.lax.broadcasted_iota(
            jnp.int32, (bt, 1), 0)
        if not read_only:
            replay = block_start + bt > ws

            @pl.when(replay)
            def _overlay():
                # positions [w_start, kv_len) were produced by rows <= r
                # of THIS dispatch: position pos lives at packed index
                # w_flat + pos - w_start (+ the left pad of one block),
                # roped already and rounded to the pool dtype: what the
                # unfused scatter stores, bit for bit
                tpad = nk_ref.shape[1]
                f0 = jnp.clip(w_flats_ref[r] + block_start - ws + bt, 0,
                              tpad - bt)
                fresh = (kpos >= ws) & (kpos < kv_len)
                kbuf[slot] = jnp.where(
                    fresh[None],
                    nk_ref[:, pl.ds(f0, bt), :].astype(kbuf.dtype),
                    kbuf[slot])
                vbuf[slot] = jnp.where(
                    fresh[None],
                    nv_ref[:, pl.ds(f0, bt), :].astype(vbuf.dtype),
                    vbuf[slot])

                @pl.when(last_row)
                def _write():
                    write("start", i, slot)

        # nothing at or past the context (or on a page behind the
        # window, which was not fetched) is used: a slot there may
        # hold anything (a NaN would survive the zero weight of the
        # P.V dot)
        held = kpos < ctx
        if window is not None:
            held &= kpos >= first_page * page_size

        @sized
        def _attend(n):
            rows = pl.ds(0, n)
            for h in heads_of(n):
                _softmax_accumulate(
                    q_s[h, rows], kbuf[slot, h].astype(jnp.float32),
                    jnp.where(held, vbuf[slot, h].astype(jnp.float32),
                              0.0),
                    block_start, q_start, q_len, ctx, group,
                    acc_ref.at[h, rows], m_ref.at[h, rows],
                    l_ref.at[h, rows], window)

        if not read_only:
            @pl.when(replay & last_row)
            def _written():
                write("wait", i, slot)

        return carry

    jax.lax.fori_loop(blk0, nblk, block, 0)

    @sized
    def _finish(n):
        rows = pl.ds(0, n)
        for h in heads_of(n):
            _softmax_finish(o_ref.at[0, h, rows], acc_ref.at[h, rows],
                            l_ref.at[h, rows])
        if n < qbg:
            # the query tokens past the tile are none of the row's
            o_ref[0, :, pl.ds(n, qbg - n)] = jnp.zeros(
                (hk, qbg - n) + o_ref.shape[3:], o_ref.dtype)


def _fused_rope_kernel_q8(tables_ref, kv_lens_ref, q_starts_ref,
                          q_lens_ref, w_starts_ref, w_flats_ref,
                          w_ends_ref, q_ref, k_ref, v_ref, ks_ref,
                          vs_ref, nk_ref, nv_ref, sin_ref, cos_ref,
                          o_ref, ko_ref, vo_ref, kso_ref, vso_ref,
                          acc_ref, m_ref, l_ref, q_s, *, page_size,
                          group, scale, pad, qblock, dtype):
    """The int8-pool program, a per-page grid ``(R, Hk, W)``: rope the
    fresh rows (through the model dtype, as the float program's new_k
    is), THEN quantize them in-kernel with bitwise `quantize_kv_int8`
    math: the quantizer consumes exactly what a rope-then-quantize-
    then-scatter pipeline's would
    (`fused_ragged_paged_attention_xla`)."""
    r = pl.program_id(0)
    p = pl.program_id(2)
    num_pages = pl.num_programs(2)

    @pl.when(p == 0)
    def _init():
        acc_ref[...] = jnp.zeros_like(acc_ref)
        m_ref[...] = jnp.full_like(m_ref, NEG_INF)
        l_ref[...] = jnp.zeros_like(l_ref)
        q_s[...] = _rope_q_block(q_ref, sin_ref, cos_ref, q_starts_ref,
                                 w_starts_ref, w_flats_ref, r, pad,
                                 qblock, group, scale, dtype)

    ctx = kv_lens_ref[r]
    ws = w_starts_ref[r]
    page_start = p * page_size

    @pl.when(page_start < ctx)
    def _compute():
        tpad = nk_ref.shape[1]
        f0 = jnp.clip(w_flats_ref[r] + page_start - ws + pad, 0,
                      tpad - page_size)
        spos = page_start + jax.lax.broadcasted_iota(
            jnp.int32, (page_size, 1), 0)
        fresh = (spos >= ws) & (spos < ctx)
        # shared rotation chain, then the exact f32 widening the
        # reference's post-rope quantizer consumes
        k_rot = _rope_k_page(nk_ref, sin_ref, cos_ref, f0, page_size,
                             dtype).astype(jnp.float32)
        k_qn, k_scn = _quantize_rows(k_rot)
        v_qn, v_scn = _quantize_rows(nv_ref[0, pl.ds(f0, page_size), :])
        k = jnp.where(fresh, k_qn * k_scn,
                      k_ref[0, 0].astype(jnp.float32) * ks_ref[0, 0])
        v = jnp.where(fresh, v_qn * v_scn,
                      v_ref[0, 0].astype(jnp.float32) * vs_ref[0, 0])

        _softmax_accumulate(q_s[...], k, v, page_start,
                            q_starts_ref[r], q_lens_ref[r], ctx, group,
                            acc_ref, m_ref, l_ref)

        @pl.when((ctx == w_ends_ref[r]) & (page_start + page_size > ws)
                 & (q_lens_ref[r] > 0))
        def _writeback():
            ko_ref[0, 0] = jnp.where(fresh, k_qn.astype(jnp.int8),
                                     k_ref[0, 0])
            vo_ref[0, 0] = jnp.where(fresh, v_qn.astype(jnp.int8),
                                     v_ref[0, 0])
            kso_ref[0, 0] = jnp.where(fresh, k_scn, ks_ref[0, 0])
            vso_ref[0, 0] = jnp.where(fresh, v_scn, vs_ref[0, 0])

    @pl.when(p == num_pages - 1)
    def _finish():
        _softmax_finish(o_ref.at[0, 0], acc_ref, l_ref)


def _fused_write_map(page_size, dump_page):
    """Out-spec index map for the pool write-back: the page the step
    writes when it IS the sequence's last row and the page overlaps the
    dispatch's write span ``[w_start, kv_len)``, else ``dump_page``.
    Must mirror the int8 kernel's ``_writeback`` condition exactly."""
    def wmap(ri, hi, pi, tables, kv_lens, q_starts, q_lens, w_starts,
             w_flats, w_ends):
        ctx = kv_lens[ri]
        written = (pi * page_size < ctx) \
            & ((pi + 1) * page_size > w_starts[ri]) \
            & (ctx == w_ends[ri]) & (q_lens[ri] > 0)
        return jnp.where(written, tables[ri, pi], dump_page), hi, 0, 0

    return wmap


@functools.lru_cache(maxsize=32)
def _make_fused_rope(scale, page_size, bpages, qblock, group, dtype,
                     interpret, window=None, read_only=False):
    bt = bpages * page_size

    def call(qp, k_pages, v_pages, nk, nv, sin, cos, tables, kv_lens,
             q_starts, q_lens, w_starts, w_flats, w_ends):
        hk, tq, g, d = qp.shape
        tpad = nk.shape[1]
        r = tables.shape[0]
        qbg = qblock * group
        whole = lambda *shape: pl.BlockSpec(       # noqa: E731
            shape, lambda ri, *refs: (0,) * len(shape))
        grid_spec = pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=7,
            grid=(r,),
            in_specs=[
                # the packed operands ride whole (constant index map:
                # fetched once a call); the pools stay in HBM and the
                # kernel fetches the pages a row holds itself
                whole(hk, tq, g, d),
                pl.BlockSpec(memory_space=pl.ANY),
                pl.BlockSpec(memory_space=pl.ANY),
                whole(hk, tpad, d),
                whole(hk, tpad, d),
                whole(tq, d),
                whole(tq, d),
            ],
            out_specs=[
                pl.BlockSpec((1, hk, qbg, d),
                             lambda ri, *refs: (ri, 0, 0, 0)),
                pl.BlockSpec(memory_space=pl.ANY),
                pl.BlockSpec(memory_space=pl.ANY),
            ],
            scratch_shapes=[
                pltpu.VMEM((2, hk, bt, d), k_pages.dtype),
                pltpu.VMEM((2, hk, bt, d), v_pages.dtype),
                pltpu.SemaphoreType.DMA((2, 2)),     # fetch [k/v, slot]
                pltpu.SemaphoreType.DMA((2, 2)),     # write-back
                pltpu.VMEM((hk, qbg, d), jnp.float32),
                pltpu.VMEM((hk, qbg, 1), jnp.float32),
                pltpu.VMEM((hk, qbg, 1), jnp.float32),
                # the row's roped+scaled q block, all kv heads
                pltpu.VMEM((hk, qbg, d), jnp.float32),
            ],
        )
        # VMEM: the pipeline holds two copies of every blocked
        # operand; tiles pad G and the softmax columns
        f32 = 4
        operands = hk * tq * -(-g // 8) * 8 * d * f32 \
            + 2 * hk * tpad * d * f32 + 2 * tq * d * f32 \
            + hk * qbg * d * jnp.dtype(dtype).itemsize
        scratch = 4 * hk * bt * d * k_pages.dtype.itemsize \
            + hk * qbg * (2 * d + 2 * 128) * f32
        return pl.pallas_call(
            functools.partial(_fused_rope_kernel, page_size=page_size,
                              bpages=bpages, group=group, scale=scale,
                              qblock=qblock, dtype=dtype, window=window,
                              read_only=read_only),
            grid_spec=grid_spec,
            out_shape=[
                jax.ShapeDtypeStruct((r, hk, qbg, d), dtype),
                jax.ShapeDtypeStruct(k_pages.shape, k_pages.dtype),
                jax.ShapeDtypeStruct(v_pages.shape, v_pages.dtype),
            ],
            # inputs 0-6 scalar prefetch, 7 packed q, 8/9 the pools
            input_output_aliases={8: 1, 9: 2},
            compiler_params=pltpu.CompilerParams(
                dimension_semantics=("arbitrary",),
                vmem_limit_bytes=min(
                    100 << 20, max(32 << 20,
                                   2 * operands + scratch + (8 << 20)))),
            # Pallas's generic interpreter runs the DMAs and semaphores
            # too; its TPU interpreter (`pltpu.InterpretParams()`,
            # NaN-filled VMEM) is ~100x slower and a test's to ask for
            interpret=interpret,
            name="paddle_tpu.ragged_attn_fused_rope",
        )(tables, kv_lens, q_starts, q_lens, w_starts, w_flats, w_ends,
          qp, k_pages, v_pages, nk, nv, sin, cos)

    # jitted, so that the layers of one step program share ONE trace
    # and ONE lowering of the kernel (they call it with equal shapes)
    return jax.jit(call)


@functools.lru_cache(maxsize=32)
def _make_fused_rope_q8(scale, page_size, qblock, group, tpad,
                        dump_page, dtype, interpret):
    wmap = _fused_write_map(page_size, dump_page)

    def call(qp, k_pages, v_pages, k_scale, v_scale, nk, nv, sin, cos,
             tables, kv_lens, q_starts, q_lens, w_starts, w_flats,
             w_ends):
        hk, _, g, d = qp.shape
        r = tables.shape[0]
        qbg = qblock * group
        max_pages = tables.shape[1]
        grid_spec = pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=7,
            grid=(r, hk, max_pages),
            in_specs=[
                pl.BlockSpec((1, tpad, g, d),
                             lambda ri, hi, pi, *refs: (hi, 0, 0, 0)),
                pl.BlockSpec((1, 1, page_size, d),
                             lambda ri, hi, pi, tables, *refs:
                             (tables[ri, pi], hi, 0, 0)),
                pl.BlockSpec((1, 1, page_size, d),
                             lambda ri, hi, pi, tables, *refs:
                             (tables[ri, pi], hi, 0, 0)),
                pl.BlockSpec((1, 1, page_size, 1),
                             lambda ri, hi, pi, tables, *refs:
                             (tables[ri, pi], hi, 0, 0)),
                pl.BlockSpec((1, 1, page_size, 1),
                             lambda ri, hi, pi, tables, *refs:
                             (tables[ri, pi], hi, 0, 0)),
                pl.BlockSpec((1, tpad, d),
                             lambda ri, hi, pi, *refs: (hi, 0, 0)),
                pl.BlockSpec((1, tpad, d),
                             lambda ri, hi, pi, *refs: (hi, 0, 0)),
                pl.BlockSpec((tpad, d),
                             lambda ri, hi, pi, *refs: (0, 0)),
                pl.BlockSpec((tpad, d),
                             lambda ri, hi, pi, *refs: (0, 0)),
            ],
            out_specs=[
                pl.BlockSpec((1, 1, qbg, d),
                             lambda ri, hi, pi, *refs: (ri, hi, 0, 0)),
                pl.BlockSpec((1, 1, page_size, d), wmap),
                pl.BlockSpec((1, 1, page_size, d), wmap),
                pl.BlockSpec((1, 1, page_size, 1), wmap),
                pl.BlockSpec((1, 1, page_size, 1), wmap),
            ],
            scratch_shapes=[
                pltpu.VMEM((qbg, d), jnp.float32),
                pltpu.VMEM((qbg, 1), jnp.float32),
                pltpu.VMEM((qbg, 1), jnp.float32),
                pltpu.VMEM((qbg, d), jnp.float32),
            ],
        )
        return pl.pallas_call(
            functools.partial(_fused_rope_kernel_q8,
                              page_size=page_size, group=group,
                              scale=scale, pad=page_size,
                              qblock=qblock, dtype=dtype),
            grid_spec=grid_spec,
            out_shape=[
                jax.ShapeDtypeStruct((r, hk, qbg, d), dtype),
                jax.ShapeDtypeStruct(k_pages.shape, k_pages.dtype),
                jax.ShapeDtypeStruct(v_pages.shape, v_pages.dtype),
                jax.ShapeDtypeStruct(k_scale.shape, k_scale.dtype),
                jax.ShapeDtypeStruct(v_scale.shape, v_scale.dtype),
            ],
            input_output_aliases={8: 1, 9: 2, 10: 3, 11: 4},
            interpret=interpret,
            name="paddle_tpu.ragged_attn_fused_rope_q8",
        )(tables, kv_lens, q_starts, q_lens, w_starts, w_flats, w_ends,
          qp, k_pages, v_pages, k_scale, v_scale, nk, nv, sin, cos)

    return call


def _pack_new_rows(new, t, pad, tpad, dtype):
    """[T, Hk, D] packed rows -> [Hk, tpad, D] head-major with ``pad``
    rows of left pad (a page for the int8 program, a block of the
    walk for the float one: the length of a replay slice),
    so the kernels' clipped affine slice ``pl.ds(w_flat + start -
    w_start + pad, pad)`` is always in bounds whenever any slot it
    covers is fresh. The values are
    rounded to ``dtype`` and handed over in an f32 container: that
    slice starts at a run-time sublane offset, which Mosaic takes
    unaligned only from a 32-bit array, and 16-bit -> f32 -> 16-bit is
    exact, so the kernel narrows back without changing a bit."""
    nk = jnp.swapaxes(new.astype(dtype).astype(jnp.float32), 0, 1)
    return jnp.pad(nk, ((0, 0), (pad, tpad - t - pad), (0, 0)))


def _pack_new_q(q, t, group, pad, tpad):
    """Pre-rope packed q ``[T, H, D]`` -> ``[Hk, tpad, G, D]``
    head-major with ``pad`` rows of left pad (as `_pack_new_rows`
    where one affine offset addresses q rows, K/V rows and the sin/cos
    tables alike; none where q has an offset of its own); widened to
    f32 for the same reason as the rows."""
    hk = q.shape[1] // group
    d = q.shape[-1]
    q4 = q.astype(jnp.float32).reshape(t, hk, group, d) \
        .transpose(1, 0, 2, 3)
    return jnp.pad(q4, ((0, 0), (pad, tpad - t - pad), (0, 0), (0, 0)))


def _pack_rope_table(tb, t, pad, tpad):
    return jnp.pad(tb.astype(jnp.float32),
                   ((pad, tpad - t - pad), (0, 0)))


def _rope_tpad(t, page_size, qblock):
    """Padded packed-axis length for the rope-fused kernel: the left
    pad is page_size (as in `_pack_new_rows`) and the right pad must
    cover BOTH the page-sized K replay slice and the qblock-sized q
    slice starting at the last packed token."""
    return -(-(t + page_size + max(page_size, qblock)) // 8) * 8


def _fused_rope_impl(q, new_k, new_v, k_pages, v_pages, block_tables,
                     kv_lens, q_starts, q_lens, w_starts, w_flats,
                     w_ends, rope_sin, rope_cos, dump_page, scale,
                     qblock, window=None, read_only=False):
    """``dump_page`` is part of the fused programs' common signature
    and unused here: a step of this program that has nothing to write
    writes nothing."""
    t, h, d = q.shape
    hk = k_pages.shape[1]
    group = h // hk
    page_size = k_pages.shape[2]
    r = block_tables.shape[0]
    bpages = _walk_pages(page_size, hk, d, k_pages.dtype.itemsize)
    bt = bpages * page_size
    # the replay slice is a whole block of the walk, so new_k/new_v
    # take a block of left pad and a block of right pad
    # (`_pack_new_rows`); q and its tables are sliced by row blocks
    # from the row's first token on and take one row block on the right
    tpad = -(-(t + 2 * bt) // 8) * 8
    tq = t + qblock
    # q stays PRE-rope in the model dtype (the kernel ropes a row's
    # block where it reads it). new_k is T rows for all readers: roped
    # here, through the model dtype (the `_apply_rope` output), then
    # pre-cast to the pool dtype like new_v and like the post-rope
    # kernel's operands
    qp = _pack_new_q(q, t, group, 0, tq)
    nk = _pack_new_rows(_rope_rows(new_k, rope_sin, rope_cos), t, bt,
                        tpad, k_pages.dtype)
    nv = _pack_new_rows(new_v, t, bt, tpad, v_pages.dtype)
    sin = _pack_rope_table(rope_sin, t, 0, tq)
    cos = _pack_rope_table(rope_cos, t, 0, tq)
    call = _make_fused_rope(scale, page_size, bpages, qblock, group,
                            jnp.dtype(q.dtype), _interpret(), window,
                            read_only)
    tables = jnp.clip(block_tables.astype(jnp.int32), 0,
                      k_pages.shape[0] - 1)
    out, kp, vp = call(qp, k_pages, v_pages, nk, nv, sin, cos, tables,
                       kv_lens.astype(jnp.int32),
                       q_starts.astype(jnp.int32),
                       q_lens.astype(jnp.int32),
                       w_starts.astype(jnp.int32),
                       w_flats.astype(jnp.int32),
                       w_ends.astype(jnp.int32))
    out = out.reshape(r, hk, qblock, group, d).transpose(0, 2, 1, 3, 4) \
        .reshape(r, qblock, h, d)
    return out, kp, vp


def _fused_rope_impl_q8(q, new_k, new_v, k_pages, v_pages, k_scale,
                        v_scale, block_tables, kv_lens, q_starts,
                        q_lens, w_starts, w_flats, w_ends, rope_sin,
                        rope_cos, dump_page, scale, qblock):
    t, h, d = q.shape
    hk = k_pages.shape[1]
    group = h // hk
    page_size = k_pages.shape[2]
    r = block_tables.shape[0]
    tpad = _rope_tpad(t, page_size, qblock)
    # both packed rows keep the MODEL dtype: the kernel ropes k, round
    # trips through the model dtype and widens to f32 for the bitwise
    # `quantize_kv_int8` math (an exact widening)
    qp = _pack_new_q(q, t, group, page_size, tpad)
    nk = _pack_new_rows(new_k, t, page_size, tpad, new_k.dtype)
    nv = _pack_new_rows(new_v, t, page_size, tpad, new_v.dtype)
    sin = _pack_rope_table(rope_sin, t, page_size, tpad)
    cos = _pack_rope_table(rope_cos, t, page_size, tpad)
    call = _make_fused_rope_q8(scale, page_size, qblock, group, tpad,
                               int(dump_page), jnp.dtype(q.dtype),
                               _interpret())
    tables = jnp.clip(block_tables.astype(jnp.int32), 0,
                      k_pages.shape[0] - 1)
    out, kp, vp, ks, vs = call(
        qp, k_pages, v_pages, k_scale.astype(jnp.float32),
        v_scale.astype(jnp.float32), nk, nv, sin, cos, tables,
        kv_lens.astype(jnp.int32), q_starts.astype(jnp.int32),
        q_lens.astype(jnp.int32), w_starts.astype(jnp.int32),
        w_flats.astype(jnp.int32), w_ends.astype(jnp.int32))
    out = out.reshape(r, hk, qblock, group, d).transpose(0, 2, 1, 3, 4) \
        .reshape(r, qblock, h, d)
    return out, kp, vp, ks, vs


def fused_ragged_paged_attention(q, new_k, new_v, k_pages, v_pages,
                                 block_tables, kv_lens, q_starts,
                                 q_lens, w_starts, w_flats, w_ends,
                                 dump_page, *, rope_sin, rope_cos,
                                 qblock, scale=None, k_scale=None,
                                 v_scale=None, window=None,
                                 read_only=False):
    """Rope, KV page write and ragged paged attention in ONE kernel
    (see module docstring): ropes the packed PRE-rope ``q [T, H, D]``
    and ``new_k [T, Hk, D]`` by the per-dispatch ``rope_sin``/
    ``rope_cos`` ``[T, D]`` f32 tables (:func:`rope_tables`) in VMEM,
    writes ``new_k/new_v`` into each row's pages and attends through
    them, returning ``(out [R, qblock, H, D], k_pages, v_pages)`` —
    plus the updated scale sidecars for int8 pools (``k_scale``/
    ``v_scale`` given). Per-row write metadata: ``w_starts[r]`` is the
    first position of row r's sequence written by THIS dispatch,
    ``w_flats[r]`` that position's index on the packed token axis,
    ``w_ends[r]`` the sequence's final kv_len in this dispatch (so the
    last row owns the write-back). ``qblock`` is the row-block width
    the metadata was built for. ``window`` (float pools only): a query
    at position ``p`` sees the keys ``(p - window, p]``, and a row
    walks the pages that hold them and no others. ``read_only``
    (float pools only): the pools already hold the dispatch's K/V rows
    (the layer that owns them ran before); ``new_k``/``new_v`` are not
    read and the pools come back byte for byte. ``dump_page`` is a page id no live
    table references; the int8 program's steps with nothing to write
    dump there and its contents are undefined after the call.
    Tape-integrated but non-differentiable (serving path)."""
    if not fused_supported(q, new_k, new_v, k_pages, v_pages,
                           block_tables, kv_lens, q_starts, q_lens,
                           w_starts, w_flats, w_ends, dump_page,
                           rope_sin=rope_sin, rope_cos=rope_cos,
                           qblock=qblock, k_scale=k_scale,
                           v_scale=v_scale):
        raise ValueError(
            "fused_ragged_paged_attention preconditions not met: need "
            "packed q [T,H,D], new_k/new_v [T,Hk,D] (T >= 1), rope_sin/"
            "rope_cos [T,D], pages [P,Hk,page,D] (page % 8 == 0, "
            "D % 8 == 0, D <= 256, H % Hk == 0), tables [R,max_pages], "
            "kv_lens/q_starts/q_lens/w_starts/w_flats/w_ends [R], "
            "qblock >= 1 and a dump_page id inside the pool; int8 pools "
            "need BOTH k_scale/v_scale sidecars shaped [P,Hk,page,1]")
    d = getattr(q, "_data", q).shape[-1]
    static = dict(dump_page=int(dump_page), qblock=int(qblock),
                  scale=scale if scale is not None else 1.0 / math.sqrt(d))
    rows = (block_tables, kv_lens, q_starts, q_lens, w_starts, w_flats,
            w_ends, rope_sin, rope_cos)
    if k_scale is not None:
        if window is not None or read_only:
            raise ValueError("the int8-page program has no window mask "
                             "and no read-only call")
        return run_op("fused_rope_ragged_paged_attention_q8",
                      functools.partial(_fused_rope_impl_q8, **static),
                      (q, new_k, new_v, k_pages, v_pages, k_scale,
                       v_scale) + rows, differentiable=False)
    return run_op("fused_rope_ragged_paged_attention",
                  functools.partial(_fused_rope_impl, window=window,
                                    read_only=read_only, **static),
                  (q, new_k, new_v, k_pages, v_pages) + rows,
                  differentiable=False)


def fused_ragged_paged_attention_xla(q, new_k, new_v, k_pages, v_pages,
                                     block_tables, kv_lens, q_starts,
                                     q_lens, w_starts, w_flats, w_ends,
                                     dump_page, scale=None,
                                     k_scale=None, v_scale=None,
                                     rope_sin=None, rope_cos=None,
                                     qblock=None, window=None):
    """Write-THEN-read reference for the fused kernel: scatter every
    row's packed new K/V rows into the pools (host-built indices, rows
    applied in order — unambiguous last-writer-wins), then run the
    plain `ragged_paged_attention_xla` over the updated pools. Two
    dependent ops with sequential semantics are exactly what the fused
    kernel's in-grid replay must reproduce; concrete (non-traced)
    arrays only. Returns the same tuple as the fused kernel. The dump
    page is untouched here — its contents are undefined in the fused
    path, so parity checks must exclude it.

    With ``rope_sin``/``rope_cos`` this is the ROPE-then-write-then-
    read reference: apply the table-driven rotation to the packed
    pre-rope ``q [T, H, D]`` and ``new_k`` first (the unfused
    `_apply_rope` chain, bit for bit), gather q into ``[R, qblock]``
    row blocks via the write metadata, then proceed as above."""
    import numpy as np
    from ..inference.paged_cache import quantize_kv_int8

    unwrap = [getattr(a, "_data", a)
              for a in (q, new_k, new_v, k_pages, v_pages, block_tables,
                        kv_lens, q_starts, q_lens, w_starts, w_flats)]
    (q, new_k, new_v, k_pages, v_pages, block_tables, kv_lens,
     q_starts, q_lens, w_starts, w_flats) = unwrap
    if rope_sin is not None:
        sin = getattr(rope_sin, "_data", rope_sin)
        cos = getattr(rope_cos, "_data", rope_cos)
        q_rot = np.asarray(_rope_rows(q, sin, cos))
        new_k = _rope_rows(new_k, sin, cos)
        # pack the roped q into the row blocks the metadata implies:
        # row r's tokens sit at packed [w_flat + q_start - w_start, +n)
        r_rows = block_tables.shape[0]
        qb = int(qblock)
        qr = np.zeros((r_rows, qb) + q_rot.shape[1:], q_rot.dtype)
        ql_np = np.asarray(q_lens)
        for i in range(r_rows):
            n = int(ql_np[i])
            if n <= 0:
                continue
            f0 = int(np.asarray(w_flats)[i]) \
                + int(np.asarray(q_starts)[i]) \
                - int(np.asarray(w_starts)[i])
            qr[i, :n] = q_rot[f0:f0 + n]
        q = jnp.asarray(qr)
    ps = k_pages.shape[2]
    tables = np.asarray(jnp.clip(block_tables.astype(jnp.int32), 0,
                                 k_pages.shape[0] - 1))
    kv_np = np.asarray(kv_lens)
    ql_np = np.asarray(q_lens)
    qs_np = np.asarray(q_starts)
    ws_np = np.asarray(w_starts)
    wf_np = np.asarray(w_flats)
    quant = k_scale is not None
    if quant:
        ks = getattr(k_scale, "_data", k_scale).astype(jnp.float32)
        vs = getattr(v_scale, "_data", v_scale).astype(jnp.float32)
        qk, sk = quantize_kv_int8(new_k)
        qv, sv = quantize_kv_int8(new_v)
    hidx = np.arange(k_pages.shape[1])[None, :]
    for r in range(q.shape[0]):
        if ql_np[r] <= 0 or kv_np[r] <= 0:
            continue
        start, end = int(qs_np[r]), int(kv_np[r])
        pos = np.arange(start, end)
        pages = tables[r, pos // ps]
        offs = pos % ps
        f = int(wf_np[r]) + pos - int(ws_np[r])
        if quant:
            k_pages = k_pages.at[pages[:, None], hidx,
                                 offs[:, None]].set(qk[f])
            v_pages = v_pages.at[pages[:, None], hidx,
                                 offs[:, None]].set(qv[f])
            ks = ks.at[pages[:, None], hidx, offs[:, None], 0].set(sk[f])
            vs = vs.at[pages[:, None], hidx, offs[:, None], 0].set(sv[f])
        else:
            k_pages = k_pages.at[pages[:, None], hidx, offs[:, None]] \
                .set(new_k[f].astype(k_pages.dtype))
            v_pages = v_pages.at[pages[:, None], hidx, offs[:, None]] \
                .set(new_v[f].astype(v_pages.dtype))
    if quant:
        out = ragged_paged_attention_xla(q, k_pages, v_pages, tables,
                                         kv_lens, q_starts, q_lens,
                                         scale=scale, k_scale=ks,
                                         v_scale=vs)
        return out, k_pages, v_pages, ks, vs
    out = ragged_paged_attention_xla(q, k_pages, v_pages, tables,
                                     kv_lens, q_starts, q_lens,
                                     scale=scale, window=window)
    return out, k_pages, v_pages


def ragged_paged_attention_xla(q, k_pages, v_pages, block_tables,
                               kv_lens, q_starts, q_lens, scale=None,
                               k_scale=None, v_scale=None, window=None):
    """XLA reference path: gather every row's pages to a contiguous
    [R, S, Hk, D] window, apply the causal/ragged mask, softmax.
    Semantically identical to the kernel (zeros on padded query rows
    and inactive rows; int8 pools dequantized by the scale sidecars);
    used for parity tests and as the fallback where Pallas is
    unavailable."""
    q, k_pages, v_pages, block_tables, kv_lens, q_starts, q_lens = (
        getattr(a, "_data", a)
        for a in (q, k_pages, v_pages, block_tables, kv_lens, q_starts,
                  q_lens))
    r, qb, h, d = q.shape
    p, hk, page_size, _ = k_pages.shape
    group = h // hk
    s = scale if scale is not None else 1.0 / math.sqrt(d)
    tables = jnp.clip(block_tables.astype(jnp.int32), 0, p - 1)
    if k_scale is not None:
        ks = getattr(k_scale, "_data", k_scale).astype(jnp.float32)
        vs = getattr(v_scale, "_data", v_scale).astype(jnp.float32)
        k_pages = k_pages.astype(jnp.float32) * ks
        v_pages = v_pages.astype(jnp.float32) * vs
    # [R, W, Hk, page, D] -> [R, S, Hk, D]
    k = jnp.swapaxes(k_pages[tables], 2, 3).reshape(r, -1, hk, d)
    v = jnp.swapaxes(v_pages[tables], 2, 3).reshape(r, -1, hk, d)
    kq = jnp.repeat(k, group, axis=2)
    vq = jnp.repeat(v, group, axis=2)
    logits = jnp.einsum("rqhd,rshd->rhqs", q.astype(jnp.float32),
                        kq.astype(jnp.float32)) * s
    S = k.shape[1]
    kpos = jnp.arange(S)[None, None, None, :]
    qpos = (q_starts[:, None] + jnp.arange(qb)[None, :])[:, None, :, None]
    qvalid = (jnp.arange(qb)[None, :]
              < q_lens[:, None])[:, None, :, None]
    mask = (kpos <= qpos) & (kpos < kv_lens[:, None, None, None]) & qvalid
    if window is not None:
        mask &= kpos > qpos - window
    logits = jnp.where(mask, logits, NEG_INF)
    w = jax.nn.softmax(logits, axis=-1)
    # fully-masked rows (padding / inactive) -> zeros, matching the
    # kernel's l == 0 guard rather than softmax's uniform fallback
    any_valid = jnp.any(mask, axis=-1, keepdims=True)
    w = jnp.where(any_valid, w, 0.0)
    out = jnp.einsum("rhqs,rshd->rqhd", w, vq.astype(jnp.float32))
    return out.astype(q.dtype)
