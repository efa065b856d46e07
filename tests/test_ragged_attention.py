"""Ragged paged attention: the two Pallas programs (interpret mode on
CPU), one a pool dtype, against the rope-then-write-then-read XLA
reference, across ragged mixed prefill+decode shapes.

Both programs and the reference compute f32 softmax attention over the
same paged pool, so outputs must agree to float rounding on EVERY
position, the kernels' defined zeros on padded query rows and inactive
rows included (the float program walks a row's K/V a block of pages at
a time and the int8 one a page at a time: 1e-5 relative in f32), while
the pool bytes they write, and the int8 program's scale sidecars, are
held bitwise.
"""

import functools

import numpy as np
import pytest

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from paddle_tpu.inference.paged_cache import quantize_kv_int8
from paddle_tpu.ops import ragged_paged_attention as RPA


def _pool(rng, num_pages=32, hk=2, page=8, d=16, dtype=jnp.float32):
    kp = jnp.asarray(rng.randn(num_pages, hk, page, d), dtype)
    vp = jnp.asarray(rng.randn(num_pages, hk, page, d), dtype)
    return kp, vp


def _assert_parity(out_p, out_x, tol=1e-5):
    err = float(jnp.max(jnp.abs(out_p.astype(jnp.float32)
                                - out_x.astype(jnp.float32))))
    scale = float(jnp.max(jnp.abs(out_x.astype(jnp.float32))))
    assert err < tol * max(scale, 1.0), err


def _unwrap(a):
    return np.asarray(getattr(a, "_data", a))


def _packed_positions(qs, ql):
    return np.concatenate(
        [np.arange(int(s), int(s) + int(n))
         for s, n in zip(np.asarray(qs), np.asarray(ql))]) \
        .astype(np.int32)


# ----------------------------------------------------------------------
# one hand-built dispatch: rope + write + attention in ONE Pallas
# program, proven against the rope-THEN-write-THEN-read reference
# ----------------------------------------------------------------------

def _rope_case(rng, kp, vp, dump, qb=8):
    """A canonical mixed batch over pools kp/vp: sequence A as TWO
    chunk rows of one dispatch (rows 0/1: the later chunk attends K/V
    the earlier row wrote in-kernel), sequence B as a decode row (row
    2), one inactive row (row 3); PRE-rope packed q [T, H, D] and
    per-dispatch sin/cos tables at the rows' (arbitrary, non-zero-
    based) positions."""
    hk, d = kp.shape[1], kp.shape[3]
    g = 2
    tables = np.full((4, 3), dump, np.int32)
    tables[0, :2] = [2, 3]
    tables[1, :2] = [2, 3]
    tables[2, :2] = [7, 1]
    assert kp.shape[0] > 8
    kv = np.array([11, 13, 10, 0], np.int32)   # A: 5 prior + 6 + 2 new
    qs = np.array([5, 11, 9, 0], np.int32)
    ql = np.array([6, 2, 1, 0], np.int32)
    ws = np.array([5, 5, 9, 0], np.int32)      # A's span [5,13), B [9,10)
    wf = np.array([0, 0, 8, 0], np.int32)      # packed: A at 0..7, B at 8
    we = np.array([13, 13, 10, 0], np.int32)
    t = 9
    new_k = jnp.asarray(rng.randn(t, hk, d), jnp.float32)
    new_v = jnp.asarray(rng.randn(t, hk, d), jnp.float32)
    q_packed = jnp.asarray(rng.randn(t, hk * g, d), jnp.float32)
    sin, cos = RPA.rope_tables(jnp.asarray(_packed_positions(qs, ql)), d,
                               10000.0)
    return (q_packed, new_k, new_v, jnp.asarray(tables), jnp.asarray(kv),
            jnp.asarray(qs), jnp.asarray(ql), jnp.asarray(ws),
            jnp.asarray(wf), jnp.asarray(we), sin, cos, qb)


def test_fused_rope_matches_rope_then_write_then_read():
    """Tentpole contract: the rope-fused kernel equals the rope-then-
    scatter-then-read XLA reference at arbitrary non-contiguous
    positions — outputs to float rounding, written pool bytes
    BITWISE."""
    rng = np.random.RandomState(30)
    kp, vp = _pool(rng, num_pages=16)
    dump = 15
    (q_packed, new_k, new_v, tables, kv, qs, ql, ws, wf, we, sin, cos,
     qb) = _rope_case(rng, kp, vp, dump)
    args = (q_packed, new_k, new_v, kp, vp, tables, kv, qs, ql, ws,
            wf, we, dump)
    out_f, kpf, vpf = map(_unwrap, RPA.fused_ragged_paged_attention(
        *args, rope_sin=sin, rope_cos=cos, qblock=qb))
    out_x, kpx, vpx = map(np.asarray,
                          RPA.fused_ragged_paged_attention_xla(
                              *args, rope_sin=sin, rope_cos=cos,
                              qblock=qb))
    _assert_parity(jnp.asarray(out_f), jnp.asarray(out_x))
    live = [i for i in range(16) if i != dump]
    assert np.array_equal(kpf[live], kpx[live])
    assert np.array_equal(vpf[live], vpx[live])
    # inactive row still emits defined zeros
    assert float(np.max(np.abs(out_f[3]))) == 0.0


def test_fused_rope_all_decode_rows():
    """An all-decode dispatch (every row q_len 1, qblock 1 — the
    engine's scan-tick shape) through the rope-fused kernel matches
    the reference: the decode carry's per-tick metadata is exactly
    this layout."""
    rng = np.random.RandomState(32)
    kp, vp = _pool(rng, num_pages=32)
    dump = 31
    spec = [(9, 1), (17, 1), (32, 1)]
    r = len(spec)
    kv = np.asarray([k for k, _ in spec], np.int32)
    ql = np.asarray([q for _, q in spec], np.int32)
    qs = kv - ql
    tables = jnp.asarray(
        rng.permutation(30)[:r * 4].reshape(r, 4).astype(np.int32))
    ws, wf = qs.copy(), np.arange(r, dtype=np.int32)
    we = kv.copy()
    t = r
    new_k = jnp.asarray(rng.randn(t, 2, 16), jnp.float32)
    new_v = jnp.asarray(rng.randn(t, 2, 16), jnp.float32)
    q_packed = jnp.asarray(rng.randn(t, 4, 16), jnp.float32)
    sin, cos = RPA.rope_tables(jnp.asarray(_packed_positions(qs, ql)),
                               16, 10000.0)
    args = (q_packed, new_k, new_v, kp, vp, tables, jnp.asarray(kv),
            jnp.asarray(qs), jnp.asarray(ql), jnp.asarray(ws),
            jnp.asarray(wf), jnp.asarray(we), dump)
    out_f, kpf, vpf = map(_unwrap, RPA.fused_ragged_paged_attention(
        *args, rope_sin=sin, rope_cos=cos, qblock=1))
    out_x, kpx, vpx = map(np.asarray,
                          RPA.fused_ragged_paged_attention_xla(
                              *args, rope_sin=sin, rope_cos=cos,
                              qblock=1))
    _assert_parity(jnp.asarray(out_f), jnp.asarray(out_x))
    live = [i for i in range(32) if i != dump]
    assert np.array_equal(kpf[live], kpx[live])
    assert np.array_equal(vpf[live], vpx[live])


def test_fused_rope_q8_sidecar_bitwise():
    """Int8 pools under rope fusion: the in-kernel rope->quantize chain
    must land bitwise the same int8 pages AND scale sidecars as the
    rope-then-`quantize_kv_int8`-then-scatter reference."""
    rng = np.random.RandomState(33)
    P, hk, page, d = 16, 2, 8, 16
    base = rng.randn(P, hk, page, d).astype(np.float32)
    amax = np.maximum(np.max(np.abs(base), -1, keepdims=True), 1e-8)
    kq = jnp.asarray(np.clip(np.round(base / (amax / 127.0)), -127,
                             127).astype(np.int8))
    ks = jnp.asarray((amax / 127.0).astype(np.float32))
    vq = jnp.asarray(np.roll(np.asarray(kq), 1, axis=0))
    vs = jnp.asarray(np.roll(np.asarray(ks), 1, axis=0))
    dump = 15
    (q_packed, new_k, new_v, tables, kv, qs, ql, ws, wf, we, sin, cos,
     qb) = _rope_case(rng, jnp.asarray(base), jnp.asarray(base), dump)
    args = (q_packed, new_k, new_v, kq, vq, tables, kv, qs, ql, ws,
            wf, we, dump)
    of, kf, vf, ksf, vsf = map(_unwrap, RPA.fused_ragged_paged_attention(
        *args, k_scale=ks, v_scale=vs, rope_sin=sin, rope_cos=cos,
        qblock=qb))
    ox, kx, vx, ksx, vsx = map(np.asarray,
                               RPA.fused_ragged_paged_attention_xla(
                                   *args, k_scale=ks, v_scale=vs,
                                   rope_sin=sin, rope_cos=cos,
                                   qblock=qb))
    live = [i for i in range(P) if i != dump]
    assert np.array_equal(kf[live], kx[live])
    assert np.array_equal(vf[live], vx[live])
    assert np.array_equal(ksf[live], ksx[live])      # scales BITWISE
    assert np.array_equal(vsf[live], vsx[live])
    err = float(np.max(np.abs(of.astype(np.float32) - ox)))
    assert err < 0.05 * max(float(np.max(np.abs(ox))), 1.0)


def test_fused_rope_poisoned_table_tails_never_written():
    rng = np.random.RandomState(34)
    kp, vp = _pool(rng, num_pages=16)
    dump = 15
    (q_packed, new_k, new_v, tables, kv, qs, ql, ws, wf, we, sin, cos,
     qb) = _rope_case(rng, kp, vp, dump)
    poisoned = np.asarray(tables).copy()
    poisoned[:, 2:] = 10_000
    out_a, kpa, _ = map(_unwrap, RPA.fused_ragged_paged_attention(
        q_packed, new_k, new_v, kp, vp, tables, kv, qs, ql, ws, wf,
        we, dump, rope_sin=sin, rope_cos=cos, qblock=qb))
    out_b, kpb, _ = map(_unwrap, RPA.fused_ragged_paged_attention(
        q_packed, new_k, new_v, kp, vp, jnp.asarray(poisoned), kv, qs,
        ql, ws, wf, we, dump, rope_sin=sin, rope_cos=cos, qblock=qb))
    assert np.array_equal(out_a, out_b)
    live = [i for i in range(16) if i != dump]
    assert np.array_equal(kpa[live], kpb[live])


def test_fused_rope_supported_gates():
    rng = np.random.RandomState(35)
    kp, vp = _pool(rng)
    tables = jnp.zeros((2, 4), jnp.int32)
    ones = jnp.ones((2,), jnp.int32)
    qp = jnp.zeros((2, 4, 16), jnp.float32)       # packed [T, H, D]
    nk = jnp.zeros((2, 2, 16), jnp.float32)
    tb = jnp.zeros((2, 16), jnp.float32)
    base = (qp, nk, nk, kp, vp, tables, ones, ones, ones, ones, ones,
            ones, 31)
    rope = dict(rope_sin=tb, rope_cos=tb, qblock=4)
    assert RPA.fused_supported(*base, **rope)
    # a query block of no token
    assert not RPA.fused_supported(*base, **dict(rope, qblock=0))
    # table rows must match the packed token count
    bad_tb = jnp.zeros((3, 16), jnp.float32)
    assert not RPA.fused_supported(*base, **dict(rope, rope_sin=bad_tb))
    # q must be the packed 3-D layout
    q4 = jnp.zeros((2, 4, 4, 16), jnp.float32)
    assert not RPA.fused_supported(q4, *base[1:], **rope)
    # new rows with the wrong head count
    bad_nk = jnp.zeros((2, 3, 16), jnp.float32)
    assert not RPA.fused_supported(qp, bad_nk, bad_nk, *base[3:], **rope)
    # dump page outside the pool
    assert not RPA.fused_supported(*base[:-1], 99, **rope)
    # write metadata with the wrong row count
    assert not RPA.fused_supported(*base[:9], jnp.ones((3,), jnp.int32),
                                   *base[10:], **rope)
    # one scale sidecar without the other, or of the wrong shape
    sc = jnp.ones(kp.shape[:3] + (1,), jnp.float32)
    assert RPA.fused_supported(*base, **rope, k_scale=sc, v_scale=sc)
    assert not RPA.fused_supported(*base, **rope, k_scale=sc)
    assert not RPA.fused_supported(*base, **rope, k_scale=sc[:-1],
                                   v_scale=sc[:-1])
    # geometry gate: odd head_dim can't rotate
    assert not RPA.fused_rope_geometry_ok(15)
    assert RPA.fused_rope_geometry_ok(16)
    with pytest.raises(ValueError):
        RPA.fused_ragged_paged_attention(*base, **dict(rope, qblock=0))


# ----------------------------------------------------------------------
# dispatches built from sequences: the ragged contract and the edges of
# the float program's walk (bounded by kv_lens, a block of pages a
# trip, pages fetched by the kernel itself), each over float pools and
# over int8 pools with their scale sidecars
# ----------------------------------------------------------------------
PAGE = 8
BLOCK = RPA._walk_pages(PAGE, 2, 16, 4) * PAGE      # tokens a trip
POOLS = pytest.mark.parametrize("quant", [False, True],
                                ids=["float", "int8"])
#: what a poisoned slot reads: NaN in a float pool; in an int8 pool the
#: largest value under a scale no written row has (a NaN scale would
#: mark as well, but an int8 page has no NaN to go with it)
POISON_SCALE = np.float32(1e30)


def _walk_case(rng, seqs, width, qb, tail=10_000, poison=False,
               quant=False, g=2):
    """A dispatch over a fresh pool. ``seqs`` is a list of ``(prior,
    chunks)``: a sequence holding ``prior`` tokens in the pool whose
    next ``chunks`` (a list of lengths) are this dispatch's rows, in
    order; no chunks means one inactive row. Live pages are distinct,
    table tails hold ``tail``. With ``poison`` every page no row
    holds, every slot at or past a sequence's ``prior`` (what this
    dispatch writes too) and the trash page, where the tails then
    point, are poisoned. With ``quant`` the pools are int8 with
    ``[P, Hk, page, 1]`` f32 scale sidecars; ``g`` query heads a kv
    head. Returns the kernel's arguments and its keywords (sin/cos,
    qblock, the sidecars)."""
    hk, d = 2, 16
    kv, qs, ql, ws, wf, we, owner = [], [], [], [], [], [], []
    t = 0
    for si, (prior, chunks) in enumerate(seqs):
        end, at = prior + sum(chunks), prior
        for c in chunks or [0]:
            live = bool(chunks)
            kv.append((at + c) if live else 0)
            ql.append(c)
            qs.append(at if live else 0)
            ws.append(prior if live else 0)
            wf.append(t if live else 0)
            we.append(end if live else 0)
            owner.append(si)
            at += c
        t += sum(chunks)
    held = [-(-(p + sum(c)) // PAGE) if c else 0 for p, c in seqs]
    assert max(held) <= width and max(ql) <= qb
    pool = sum(held) + 3
    dump = pool - 1
    ids = rng.permutation(pool - 1)
    start = np.concatenate([[0], np.cumsum(held)])
    tables = np.full((len(kv), width), tail, np.int32)
    for i, si in enumerate(owner):
        tables[i, :held[si]] = ids[start[si]:start[si] + held[si]]
    kp = rng.randn(pool, hk, PAGE, d).astype(np.float32)
    vp = rng.randn(pool, hk, PAGE, d).astype(np.float32)
    kw = {}
    if quant:
        (kp, ks), (vp, vs) = (
            [np.array(a) for a in quantize_kv_int8(jnp.asarray(x))]
            for x in (kp, vp))
        ks, vs = ks[..., None], vs[..., None]
    if poison:
        clean = np.zeros((pool, PAGE), bool)
        for si, (prior, chunks) in enumerate(seqs):
            n = prior if chunks else 0       # not what is written now
            for j in range(held[si]):
                clean[ids[start[si] + j], :max(0, min(PAGE,
                                                      n - j * PAGE))] = 1
        dirty = ~clean[:, None, :].repeat(hk, 1)
        if quant:
            kp[dirty], vp[dirty] = 127, 127
            ks[dirty], vs[dirty] = POISON_SCALE, POISON_SCALE
        else:
            kp[dirty], vp[dirty] = np.nan, np.nan
        tables[tables == tail] = dump
    if quant:
        kw.update(k_scale=jnp.asarray(ks), v_scale=jnp.asarray(vs))
    t = max(t, 1)
    arr = lambda a: jnp.asarray(np.asarray(a, np.int32))    # noqa: E731
    pos = np.zeros(t, np.int32)
    for i in range(len(kv)):
        f = wf[i] + qs[i] - ws[i]
        pos[f:f + ql[i]] = np.arange(qs[i], kv[i])
    sin, cos = RPA.rope_tables(jnp.asarray(pos), d, 10000.0)
    args = (jnp.asarray(rng.randn(t, hk * g, d), jnp.float32),
            jnp.asarray(rng.randn(t, hk, d), jnp.float32),
            jnp.asarray(rng.randn(t, hk, d), jnp.float32),
            jnp.asarray(kp), jnp.asarray(vp), jnp.asarray(tables),
            arr(kv), arr(qs), arr(ql), arr(ws), arr(wf), arr(we), dump)
    return args, dict(kw, rope_sin=sin, rope_cos=cos, qblock=qb)


def _assert_walk_parity(args, kw):
    """The program against the reference: output to rounding, pools
    (and an int8 pool's sidecars) bitwise but for the trash page, padded
    and inactive rows zero. Returns the program's ``(out, k_pages,
    v_pages[, k_scale, v_scale])``. The reference has no tile: it
    is not handed ``tile_tokens``."""
    got = list(map(_unwrap, RPA.fused_ragged_paged_attention(*args, **kw)))
    want = list(map(np.asarray, RPA.fused_ragged_paged_attention_xla(
        *args, **{k: v for k, v in kw.items() if k != "tile_tokens"})))
    assert len(got) == len(want) == (5 if "k_scale" in kw else 3)
    _assert_parity(jnp.asarray(got[0]), jnp.asarray(want[0]))
    live = [i for i in range(got[1].shape[0]) if i != args[-1]]
    for g, w in zip(got[1:], want[1:]):
        assert g.dtype == w.dtype and np.array_equal(g[live], w[live])
    # padded query rows and inactive rows are defined zeros
    ql = np.asarray(args[8])
    for i, n in enumerate(ql):
        assert float(np.max(np.abs(got[0][i, n:]), initial=0.0)) == 0.0
    return got


def _pools_before(args, kw):
    """The pools (and an int8 pool's sidecars) a case started from, in
    the order the program returns them."""
    return [np.asarray(a) for a in args[3:5]] \
        + [np.asarray(kw[k]) for k in ("k_scale", "v_scale") if k in kw]


def _rows_out(out, ql):
    """The valid query tokens of every row, back to back."""
    return np.concatenate([out[i, :n] for i, n in enumerate(ql)])


def _two_chunks_equal_one(quant, got, args, kw):
    """Chunking is invisible: the prompt as ONE row of a wider block
    (the same seed draws the same pool and the same packed rows)
    gives the same outputs and leaves the same pool bytes."""
    one = _assert_walk_parity(*_walk_case(
        np.random.RandomState(3), [(0, [12])], 4, 16, quant=quant))
    _assert_parity(jnp.asarray(_rows_out(got[0], [8, 4])),
                   jnp.asarray(one[0][0, :12]))
    for g, w in zip(got[1:], one[1:]):
        assert np.array_equal(g[:-1], w[:-1])


def _causal_inside_a_chunk(quant, got, args, kw):
    """Query token p sees exactly kv [0, p]: other K/V rows from packed
    token 5 on change no output before token 5, and every one after."""
    rng = np.random.RandomState(99)
    q, nk, nv = args[:3]
    other = [jnp.concatenate([a[:5], jnp.asarray(
        rng.randn(*a[5:].shape), a.dtype)]) for a in (nk, nv)]
    out = _unwrap(RPA.fused_ragged_paged_attention(
        q, *other, *args[3:], **kw)[0])
    assert np.array_equal(out[0, :5], got[0][0, :5])
    assert all(not np.array_equal(out[0, i], got[0][0, i])
               for i in range(5, 11))


def _untouched_pages_stay(quant, got, args, kw):
    """Pages in no table (two besides the trash page) keep every byte,
    and so does what a held page holds below the write span."""
    tables = np.asarray(args[5])
    free = sorted(set(range(got[1].shape[0] - 1)) - set(tables.ravel()))
    assert len(free) == 2
    before = _pools_before(args, kw)
    for g, b in zip(got[1:], before):
        assert np.array_equal(g[free], b[free])
        # sequence A's first page: positions 0..4 came before the span
        assert np.array_equal(g[tables[0, 0], :, :5], b[tables[0, 0], :, :5])
        assert not np.array_equal(g[tables[0, 0], :, 5:],
                                  b[tables[0, 0], :, 5:])


def _boundary_page_last_writer(quant, got, args, kw):
    """Positions 3..8 and 9..14 in two rows: page 1 (positions 8..15)
    takes slot 0 from the first row and slots 1..6 from the second, and
    is written once, by the last: the same bytes as ONE row writes, and
    slot 7 keeps the page's own."""
    one = _assert_walk_parity(*_walk_case(
        np.random.RandomState(8), [(3, [12])], 4, 16, quant=quant))
    page1 = np.asarray(args[5])[0, 1]
    before = _pools_before(args, kw)
    for g, w, b in zip(got[1:], one[1:], before):
        assert np.array_equal(g[:-1], w[:-1])
        assert np.array_equal(g[page1, :, 7], b[page1, :, 7])
        assert not np.array_equal(g[page1, :, :7], b[page1, :, :7])


def _nothing_happens(quant, got, args, kw):
    before = _pools_before(args, kw)
    assert not got[0].any()
    for g, b in zip(got[1:], before):
        assert np.array_equal(g[:-1], b[:-1])


def _mixed(qb):
    # a chunk in mid-prompt, a decode row, a fresh full chunk, an
    # inactive row
    n = min(qb, 3)
    return [(min(29, qb + 3) - n, [n]), (16, [1]), (0, [qb]), (0, [])]


#: name -> (seed, seqs, table width, qblock, a check of its own or None)
CONTRACT = {
    "mixed-qb1": (0, _mixed(1), 4, 1, None),
    "mixed-qb4": (0, _mixed(4), 4, 4, None),
    "mixed-qb8": (0, _mixed(8), 4, 8, None),
    "all-decode": (2, [(8, [1]), (31, [1]), (0, [1]), (16, [1])], 4, 1,
                   None),
    "all-prefill": (1, [(0, [8]), (8, [5]), (16, [8])], 4, 8, None),
    "two-chunks-equal-one": (3, [(0, [8, 4])], 4, 8,
                             _two_chunks_equal_one),
    "causal-inside-a-chunk": (4, [(0, [11])], 2, 16,
                              _causal_inside_a_chunk),
    "many-ragged-pages": (5, [(49, [8]), (62, [1]), (26, [7]), (56, [8])],
                          8, 8, None),
    "multi-chunk-pool-bytes": (10, [(5, [6, 2]), (9, [1]), (0, [])], 3, 8,
                               _untouched_pages_stay),
    "boundary-page-replay": (8, [(3, [6, 6])], 4, 8,
                             _boundary_page_last_writer),
    "empty-rows": (6, [(0, []), (0, [])], 2, 8, _nothing_happens),
}


@POOLS
@pytest.mark.parametrize("scenario", list(CONTRACT))
def test_ragged_contract(scenario, quant):
    """What the ragged kernels owe the scheduler, whichever program
    serves the pool: each scenario against the rope-then-write-then-read
    reference (`_assert_walk_parity`), some with a check of their own."""
    seed, seqs, width, qb, check = CONTRACT[scenario]
    args, kw = _walk_case(np.random.RandomState(seed), seqs, width, qb,
                          quant=quant)
    got = _assert_walk_parity(args, kw)
    if check is not None:
        check(quant, got, args, kw)


WALK_KV = {"0": 0, "1": 1, "block-1": BLOCK - 1, "block": BLOCK,
           "block+1": BLOCK + 1, "full": 10 ** 9}
#: the int8 program is a per-page grid: under the interpreter a step a
#: table slot, too slow behind tables 161 wide
WIDTHS = [(161, False), (66, False), (5, False), (66, True), (5, True)]
WIDTH_IDS = ["161-float", "66-float", "5-float", "66-int8", "5-int8"]


@pytest.mark.parametrize("width,quant", WIDTHS, ids=WIDTH_IDS)
@pytest.mark.parametrize("kv", list(WALK_KV))
def test_walk_bounded_by_kv_len(kv, width, quant):
    """The walk against the write-then-read reference at the edges of
    a block and of the table: a decode row and a chunk row of context
    ``kv`` with an inactive row between them and a live anchor behind,
    under tables that are no multiple of the block's pages."""
    n = min(WALK_KV[kv], width * PAGE)
    rng = np.random.RandomState(40 + width + n % 97)
    qb = 8
    seqs = [(n - 1, [1]) if n else (0, []),          # decode row
            (0, []),                                 # inactive
            (n - min(n, qb), [min(n, qb)]) if n else (0, []),
            (16, [1])]                               # anchor
    _assert_walk_parity(*_walk_case(rng, seqs, width, qb, quant=quant))


@pytest.mark.parametrize("width,quant", [(161, False), (66, False),
                                         (66, True)],
                         ids=["161-float", "66-float", "66-int8"])
def test_walk_two_chunks_across_a_block_edge(width, quant):
    """Two chunks of ONE sequence in one dispatch whose write span
    straddles a block edge: the later chunk replays what the earlier
    wrote from the packed rows, in two different blocks of its walk,
    and the last row writes pages of both blocks."""
    rng = np.random.RandomState(50 + width)
    seqs = [(BLOCK - 12, [8, 8]), (0, []), (BLOCK + 30, [1]),
            (3, [5])]
    _assert_walk_parity(*_walk_case(rng, seqs, width, 8, quant=quant))


@POOLS
def test_walk_all_decode_batch(quant):
    """qblock 1, every row one token: the scan tick's shape, contexts
    on both sides of a block edge."""
    rng = np.random.RandomState(60)
    seqs = [(n - 1, [1]) for n in (1, BLOCK - 1, BLOCK, BLOCK + 1, 300)]
    seqs.insert(2, (0, []))
    _assert_walk_parity(*_walk_case(rng, seqs, 66, 1, quant=quant))


@POOLS
@pytest.mark.parametrize("qb", [1, 8])
def test_walk_uses_nothing_past_the_context(qb, quant):
    """Every page no row holds, every slot past a row's kv_len and
    every table tail is poisoned (NaN; for int8 pools 127 under a scale
    of 1e30): the output is finite and equal, bit for bit, to the one
    over a clean pool, and no poisoned page is written."""
    seqs = [(BLOCK - 3, [min(qb, 5)]), (0, []), (BLOCK + 9, [1]),
            (2, [qb])]
    runs = []
    for poison in (False, True):
        rng = np.random.RandomState(70 + qb)
        args, kw = _walk_case(rng, seqs, 66, qb, poison=poison,
                              quant=quant)
        runs.append((args, kw, list(map(
            _unwrap, RPA.fused_ragged_paged_attention(*args, **kw)))))
    (_, _, clean), (args, kw, dirty) = runs
    assert np.all(np.isfinite(dirty[0]))
    assert np.array_equal(clean[0], dirty[0])
    # what was written is what the clean run wrote; what was poisoned
    # and not fresh stays so (a written page keeps its other slots).
    # The mark is the NaN of a float page, the scale of an int8 one.
    mark = (lambda a: a == POISON_SCALE) if quant else np.isnan
    was = mark(np.asarray(kw["k_scale"] if quant else args[3]))
    now = mark(dirty[3] if quant else dirty[1])
    fresh = was & ~now
    assert fresh.any() and not (now & ~was).any()
    live = np.ones(was.shape[0], bool)
    live[args[-1]] = False                   # the trash page may differ
    for c, d in zip(clean[1:], dirty[1:]):
        hit = np.broadcast_to(fresh, d.shape)
        assert np.array_equal(c[hit], d[hit])
    before = _pools_before(args, kw)
    for b, d in zip(before, dirty[1:]):
        kept = np.broadcast_to(~fresh, d.shape) & live[:, None, None, None]
        assert np.array_equal(b[kept], d[kept], equal_nan=True)


def test_walk_under_the_tpu_interpreter(monkeypatch):
    """The float program under Pallas's TPU interpreter, which starts
    VMEM as NaN and raises on a read out of bounds (the generic
    interpreter the suite runs under zero-fills): a buffer slot no DMA
    filled, or a table slot past the row's pages, would show."""
    from jax.experimental.pallas import tpu as pltpu
    monkeypatch.setattr(RPA, "_interpret",
                        lambda: pltpu.InterpretParams())
    rng = np.random.RandomState(80)
    seqs = [(BLOCK - 4, [8, 3]), (0, []), (BLOCK + 1, [1])]
    _assert_walk_parity(*_walk_case(rng, seqs, 40, 8))


@POOLS
def test_table_tail_garbage_is_clamped(quant):
    """Unused table tail entries may hold anything, ids past the pool
    included, without observable effect: they are clamped, never read
    by the float program's walk, and never written through."""
    seqs = [(7, [2]), (0, []), (20, [1])]
    runs = []
    for tail in (10_000, 0):
        args, kw = _walk_case(np.random.RandomState(7), seqs, 4, 4,
                              tail=tail, quant=quant)
        runs.append((args[-1], _assert_walk_parity(args, kw)))
    (dump, a), (_, b) = runs
    live = [i for i in range(a[1].shape[0]) if i != dump]
    assert np.array_equal(a[0], b[0])
    for x, y in zip(a[1:], b[1:]):
        assert np.array_equal(x[live], y[live])


# -- a window: queries see the last `window` keys, rows walk those pages --
WINDOW_CASES = {
    # a decode row far past the window, a chunk that straddles it, a
    # context shorter than it, an inactive row
    "mixed": [(5 * BLOCK + 3, [1]), (2 * BLOCK - 5, [8]), (3, [4]),
              (0, [])],
    # two chunks of one sequence in one dispatch: the second reads the
    # first's rows from the packed operands and writes both back
    "two_chunks": [(BLOCK + 6, [8, 5]), (40, [1])],
    "all_decode": [(9 * BLOCK, [1]), (17, [1]), (BLOCK, [1])],
}


@pytest.mark.parametrize("window", [PAGE, 20, BLOCK + 5])
@pytest.mark.parametrize("case", list(WINDOW_CASES))
def test_window_matches_reference(case, window):
    """The float program with a window against the windowed XLA
    reference: same outputs, same pool bytes."""
    rng = np.random.RandomState(90)
    args, kw = _walk_case(rng, WINDOW_CASES[case], 150, 8)
    _assert_walk_parity(args, dict(kw, window=window))


@pytest.mark.parametrize("window", [PAGE, 20])
def test_window_reads_no_page_behind_it(window):
    """Pages wholly behind every query's window may hold anything (a
    ring hands them to later tokens): NaN there changes nothing."""
    seqs = [(5 * BLOCK + 3, [1]), (2 * BLOCK - 5, [8])]
    args, kw = _walk_case(np.random.RandomState(91), seqs, 90, 8)
    kw = dict(kw, window=window)
    clean = _assert_walk_parity(args, kw)
    kp, vp = np.array(args[3]), np.array(args[4])
    tables = np.asarray(args[5])
    for row, (prior, chunks) in enumerate(seqs):
        behind = (prior - window + 1) // PAGE      # pages before this one
        for pg in tables[row, :max(behind, 0)]:
            kp[pg], vp[pg] = np.nan, np.nan
    dirty = list(map(_unwrap, RPA.fused_ragged_paged_attention(
        *args[:3], jnp.asarray(kp), jnp.asarray(vp), *args[5:], **kw)))
    assert np.array_equal(clean[0], dirty[0])


def test_read_only_call_writes_nothing():
    """``read_only``: a layer that reads another layer's pool attends
    through what it holds (this dispatch's positions too) and leaves
    every byte; the packed K/V operands are not read."""
    seqs = [(BLOCK + 3, [1]), (7, [5])]
    args, kw = _walk_case(np.random.RandomState(92), seqs, 40, 8)
    args = list(args)
    # no rotation (sin 0, cos 1), so the reference's rows are q's own
    kw = dict(kw, rope_sin=jnp.zeros_like(kw["rope_sin"]),
              rope_cos=jnp.ones_like(kw["rope_cos"]), read_only=True)
    out, kp, vp = map(_unwrap, RPA.fused_ragged_paged_attention(
        *args, **kw))
    assert np.array_equal(kp, np.asarray(args[3]))
    assert np.array_equal(vp, np.asarray(args[4]))
    q, qb = np.asarray(args[0]), kw["qblock"]
    rows = np.zeros((len(seqs), qb) + q.shape[1:], q.dtype)
    rows[0, :1], rows[1, :5] = q[0:1], q[1:6]
    want = RPA.ragged_paged_attention_xla(
        jnp.asarray(rows), args[3], args[4], args[5], args[6], args[7],
        args[8])
    _assert_parity(jnp.asarray(out), jnp.asarray(want))


@pytest.mark.parametrize("window", [None, 20])
def test_no_rotation_equals_the_identity_tables(window):
    """A layer without positional encoding passes no tables: the
    program built without them gives, bit for bit, what tables that do
    not rotate (sin 0, cos 1) give, outputs and pools alike, for chunk
    rows and decode rows, with and without a window."""
    seqs = [(BLOCK + 3, [1]), (7, [8, 5]), (2 * BLOCK - 5, [2])]
    args, kw = _walk_case(np.random.RandomState(93), seqs, 40, 8)
    kw = dict(kw, window=window)
    ident = list(map(_unwrap, RPA.fused_ragged_paged_attention(
        *args, **dict(kw, rope_sin=jnp.zeros_like(kw["rope_sin"]),
                      rope_cos=jnp.ones_like(kw["rope_cos"])))))
    bare = list(map(_unwrap, RPA.fused_ragged_paged_attention(
        *args, **dict(kw, rope_sin=None, rope_cos=None))))
    for a, b in zip(ident, bare):
        assert np.array_equal(a, b)
    assert not np.array_equal(bare[1], np.asarray(args[3]))


# ----------------------------------------------------------------------
# the small tile (ISSUE 36): a row of a mixed dispatch whose query
# tokens fit ``small_tile(group)`` softmax rows computes that tile a kv
# head and not its whole query block. Below, FROZEN, is the float
# program's body as it stood before (every row the whole block, a head
# at a time): the straight line the sized program is held to, bitwise.
# ----------------------------------------------------------------------
def _frozen_softmax_accumulate(q, k, v, page_start, q_start, q_len, ctx,
                               group, acc_ref, m_ref, l_ref, window=None):
    s = jax.lax.dot_general(q, k, (((1,), (1,)), ((), ())),
                            preferred_element_type=jnp.float32)
    kpos = page_start + jax.lax.broadcasted_iota(jnp.int32, s.shape, 1)
    # query rows are laid out [QB, G] flattened (qi major): the
    # token index of softmax row i is i // G
    qrow = jax.lax.broadcasted_iota(jnp.int32, s.shape, 0) // group
    qpos = q_start + qrow
    valid = (kpos <= qpos) & (kpos < ctx) & (qrow < q_len)
    if window is not None:
        valid &= kpos > qpos - window
    s = jnp.where(valid, s, RPA.NEG_INF)
    m_prev, l_prev = m_ref[...], l_ref[...]
    m_cur = jnp.max(s, axis=-1, keepdims=True)
    m_new = jnp.maximum(m_prev, m_cur)
    alpha = jnp.exp(m_prev - m_new)
    pexp = jnp.exp(s - m_new)
    # fully-masked softmax rows (a padded query, or a page entirely
    # behind this query's causal horizon) must contribute nothing:
    # with finite RPA.NEG_INF, exp(s - m_new) would be exp(0) = 1 when
    # m_new is still RPA.NEG_INF, silently polluting l and acc
    pexp = jnp.where(valid, pexp, 0.0)
    l_ref[...] = l_prev * alpha + jnp.sum(pexp, axis=-1, keepdims=True)
    m_ref[...] = m_new
    acc_ref[...] = acc_ref[...] * alpha + jax.lax.dot_general(
        pexp, v, (((1,), (0,)), ((), ())),
        preferred_element_type=jnp.float32)


def _frozen_softmax_finish(o_ref, acc_ref, l_ref):
    l = l_ref[...]
    out = acc_ref[...] / jnp.where(l > 0.0, l, 1.0)
    o_ref[0, 0] = jnp.where(l > 0.0, out, 0.0).astype(o_ref.dtype)


def _frozen_fused_rope_kernel(tables_ref, kv_lens_ref, q_starts_ref,
                              q_lens_ref, w_starts_ref, w_flats_ref,
                              w_ends_ref, q_ref, k_hbm, v_hbm, nk_ref,
                              nv_ref, sin_ref, cos_ref, o_ref, ko_hbm,
                              vo_hbm, kbuf, vbuf, fsem, wsem, acc_ref,
                              m_ref, l_ref, q_s, *, page_size, bpages,
                              group, scale, qblock, dtype, window=None,
                              read_only=False):
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

    acc_ref[...] = jnp.zeros_like(acc_ref)
    m_ref[...] = jnp.full_like(m_ref, RPA.NEG_INF)
    l_ref[...] = jnp.zeros_like(l_ref)

    npages = pl.cdiv(ctx, page_size)
    # the first key the row walks, its page and its block: the first
    # one a query of the row sees, or the first one its sequence writes
    # in this dispatch (the sequence's last row writes those pages back)
    first = 0 if window is None else jnp.maximum(
        jnp.minimum(q_start - window + 1, ws), 0)
    first_page = first // page_size
    blk0 = first // bt

    def page_dmas(act, i, slot, pools, sem, into_vmem, lo=0):
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
        qv = q_ref[:, pl.ds(f0q, qblock), :, :]       # [Hk, QB, G, D]
        sin_q = sin_ref[pl.ds(f0q, qblock), :][None, :, None, :]
        cos_q = cos_ref[pl.ds(f0q, qblock), :][None, :, None, :]
        q_rot = (qv * cos_q + RPA._rot_half(qv) * sin_q).astype(dtype)
        q_s[...] = q_rot.reshape(hk, qblock * group, qv.shape[-1]) \
            .astype(jnp.float32) * scale

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
        for h in range(hk):
            _frozen_softmax_accumulate(
                q_s[h], kbuf[slot, h].astype(jnp.float32),
                jnp.where(held, vbuf[slot, h].astype(jnp.float32), 0.0),
                block_start, q_start, q_len, ctx, group, acc_ref.at[h],
                m_ref.at[h], l_ref.at[h], window)

        if not read_only:
            @pl.when(replay & last_row)
            def _written():
                write("wait", i, slot)

        return carry

    jax.lax.fori_loop(blk0, nblk, block, 0)
    for h in range(hk):
        _frozen_softmax_finish(o_ref.at[:, pl.ds(h, 1)], acc_ref.at[h],
                               l_ref.at[h])


def _whole_block(monkeypatch, args, kw):
    """What the frozen whole-block body gives for a dispatch (which
    knows no tile, so it takes none)."""
    def frozen(*refs, tile_tokens, **static):
        return _frozen_fused_rope_kernel(*refs, **static)

    monkeypatch.setattr(RPA, "_fused_rope_kernel", frozen)
    RPA._make_fused_rope.cache_clear()
    try:
        return list(map(_unwrap, RPA.fused_ragged_paged_attention(
            *args, **kw)))
    finally:
        monkeypatch.undo()
        RPA._make_fused_rope.cache_clear()


def _assert_sized_equals_whole_block(monkeypatch, args, kw, group):
    """Outputs of every row and both pools, bit for bit, but for ONE
    thing the interpreter does: XLA:CPU contracts the q rotation ``x *
    cos + rot * sin`` into an FMA around either product depending on
    the shape it fuses the chain at (2 tokens on the tile, the whole
    block otherwise: the same one-ulp trap the new K rows avoid by
    `_rope_rows`; the chip's vector unit has no FMA to contract into).
    So with tables that rotate, the small-tile rows are held to 1e-6
    relative and every other row and the pools bitwise; with tables
    that do not (sin 0, cos 1: the hybrid family's), everything is
    bitwise: the dots, the softmax update and the finish sum a row
    the same at the tile's height as at the block's."""
    want = _whole_block(monkeypatch, args, kw)
    got = list(map(_unwrap, RPA.fused_ragged_paged_attention(*args, **kw)))
    for g, w in zip(got[1:], want[1:]):
        assert np.array_equal(g, w, equal_nan=True)     # a poisoned pool
    ql = np.asarray(args[8])
    tile = RPA.small_tile(group, kw.get("tile_tokens", 1))
    on_tile = (ql > 0) & (ql * group <= tile)
    assert on_tile.any() and (~on_tile & (ql > 0)).any()
    assert np.array_equal(got[0][~on_tile], want[0][~on_tile])
    if not bool(jnp.any(kw["rope_sin"] != 0)):
        assert np.array_equal(got[0], want[0])
    err = np.max(np.abs(got[0][on_tile] - want[0][on_tile]))
    assert err <= 1e-6 * np.max(np.abs(want[0])), err
    return got


def _no_rotation(kw):
    return dict(kw, rope_sin=jnp.zeros_like(kw["rope_sin"]),
                rope_cos=jnp.ones_like(kw["rope_cos"]))


# decode rows (one far past a window, one short), 2-token rows (a
# verify row of a token and its draft: one short, one past a window and
# across a block edge), chunk rows of 3, 31 and 32 tokens, a sequence
# whose second row of the dispatch is one token (it owns the write-back
# of both), an inactive row
SIZED_ROWS = [(5 * BLOCK + 3, [1]), (17, [1]), (40, [2]),
              (5 * BLOCK - 1, [2]), (9, [3]), (2 * BLOCK - 5, [31]),
              (3, [32]), (BLOCK + 6, [32, 1]), (0, [])]

# the tile's query tokens: a decode row's one, and a speculative
# engine's ``1 + spec_k`` at ``spec_k = 1`` (at a group of 8, 16 rows)
TILE_TOKENS = pytest.mark.parametrize("tile_tokens", [1, 2],
                                      ids=["tile1", "tile2"])


@pytest.mark.parametrize("group,tokens,rows", [
    (8, 1, 8), (8, 2, 16), (4, 1, 8), (4, 2, 8), (4, 3, 16), (16, 1, 16)])
def test_small_tile_holds_the_rows_of_its_tokens(group, tokens, rows):
    assert RPA.small_tile(group, tokens) == rows
    assert RPA.small_tile(group) == RPA.small_tile(group, 1)


@TILE_TOKENS
@pytest.mark.parametrize("rotate", [True, False],
                         ids=["rope", "no_rope"])
@pytest.mark.parametrize("read_only", [False, True],
                         ids=["writes", "read_only"])
@pytest.mark.parametrize("window", [None, 4 * BLOCK],
                         ids=["whole", "window"])
@pytest.mark.parametrize("group", [4, 8])
def test_small_tile_is_bitwise_the_whole_block(monkeypatch, group, window,
                                               read_only, rotate,
                                               tile_tokens):
    args, kw = _walk_case(np.random.RandomState(36), SIZED_ROWS, 90, 32,
                          g=group)
    kw = dict(kw if rotate else _no_rotation(kw), window=window,
              read_only=read_only, tile_tokens=tile_tokens)
    got = _assert_sized_equals_whole_block(monkeypatch, args, kw, group)
    if read_only:
        assert all(np.array_equal(g, b)
                   for g, b in zip(got[1:], _pools_before(args, kw)))
    else:
        kw.pop("read_only")
        kw.pop("tile_tokens")
        ref = RPA.fused_ragged_paged_attention_xla(*args, **kw)
        _assert_parity(jnp.asarray(got[0]), ref[0])


@TILE_TOKENS
@pytest.mark.parametrize("rotate", [True, False],
                         ids=["rope", "no_rope"])
@pytest.mark.parametrize("group", [4, 8])
def test_small_tile_across_a_block_edge_into_its_write_span(monkeypatch,
                                                            group, rotate,
                                                            tile_tokens):
    """A decode row whose context crosses a block edge INTO its own
    write span (its new token is the next block's first slot), one
    whose new token is a block's last slot, and a 2-token row whose
    span straddles the edge: the tile's rows read the overlaid slots
    and the pages are written back as the whole block's were."""
    seqs = [(BLOCK, [1]), (BLOCK - 1, [1]), (BLOCK - 1, [2]),
            (2 * BLOCK - 3, [7])]
    for poison in (False, True):
        args, kw = _walk_case(np.random.RandomState(37), seqs, 40, 8,
                              poison=poison, g=group)
        kw = dict(kw if rotate else _no_rotation(kw),
                  tile_tokens=tile_tokens)
        got = _assert_sized_equals_whole_block(monkeypatch, args, kw,
                                               group)
        if poison:      # nothing past a context or off a table is used
            assert np.all(np.isfinite(got[0]))
        else:
            _assert_walk_parity(args, kw)
