"""Ragged paged attention: Pallas (interpret mode on CPU) vs the XLA
reference, across ragged mixed prefill+decode shapes.

The exact-parity contract mirrors `test_paged_attention`: both paths
compute f32 softmax attention over the same paged pool, so outputs must
agree to float rounding on EVERY position — including the kernel's
defined zeros on padded query rows and inactive rows. Decode rows
(q_len 1) of the per-page programs must additionally reproduce the
decode-only `paged_attention` kernel bit-for-bit. The float rope-fused
program (the engine's default) walks a row's K/V a block of pages at a
time, so its OUTPUT is held to the reference and to the per-page
programs to float rounding (1e-5 relative in f32), while the pool bytes
it writes stay bitwise.
"""

import numpy as np
import pytest

import jax
import jax.numpy as jnp

from paddle_tpu.ops import paged_attention as PA
from paddle_tpu.ops import ragged_paged_attention as RPA


def _pool(rng, num_pages=32, hk=2, page=8, d=16, dtype=jnp.float32):
    kp = jnp.asarray(rng.randn(num_pages, hk, page, d), dtype)
    vp = jnp.asarray(rng.randn(num_pages, hk, page, d), dtype)
    return kp, vp


def _rows(rng, rows, width, num_pages):
    """Random per-row metadata: (tables, kv_lens, q_starts, q_lens).
    ``rows`` is a list of (kv_len, q_len) pairs; q_start = kv - q."""
    r = len(rows)
    tables = rng.randint(0, num_pages, (r, width)).astype(np.int32)
    kv = np.asarray([k for k, _ in rows], np.int32)
    ql = np.asarray([q for _, q in rows], np.int32)
    qs = kv - ql
    return (jnp.asarray(tables), jnp.asarray(kv), jnp.asarray(qs),
            jnp.asarray(ql))


def _run_both(q, kp, vp, tables, kv, qs, ql):
    d = q.shape[-1]
    out_p = RPA._ragged_impl(q, kp, vp, tables, kv, qs, ql,
                             scale=1.0 / np.sqrt(d))
    out_x = RPA.ragged_paged_attention_xla(q, kp, vp, tables, kv, qs, ql)
    return out_p, out_x


def _assert_parity(out_p, out_x, tol=1e-5):
    err = float(jnp.max(jnp.abs(out_p.astype(jnp.float32)
                                - out_x.astype(jnp.float32))))
    scale = float(jnp.max(jnp.abs(out_x.astype(jnp.float32))))
    assert err < tol * max(scale, 1.0), err


@pytest.mark.parametrize("qb", [1, 4, 8])
def test_mixed_batch_parity(qb):
    rng = np.random.RandomState(0)
    kp, vp = _pool(rng)
    width, page = 4, 8
    spec = [(min(29, qb + 3), min(qb, 3)),   # prefill chunk mid-prompt
            (17, 1),                          # decode row
            (qb, qb),                         # fresh full chunk
            (0, 0)]                           # inactive row
    tables, kv, qs, ql = _rows(rng, spec, width, kp.shape[0])
    q = jnp.asarray(rng.randn(len(spec), qb, 4, 16), jnp.float32)
    out_p, out_x = _run_both(q, kp, vp, tables, kv, qs, ql)
    _assert_parity(out_p, out_x)
    # inactive row and padded query rows are defined zeros in BOTH
    assert float(jnp.max(jnp.abs(out_p[3]))) == 0.0
    assert float(jnp.max(jnp.abs(out_x[3]))) == 0.0


def test_empty_decode_batch_parity():
    """All rows are prefill chunks (no decode row in the batch)."""
    rng = np.random.RandomState(1)
    kp, vp = _pool(rng)
    spec = [(8, 8), (13, 5), (24, 8)]
    tables, kv, qs, ql = _rows(rng, spec, 4, kp.shape[0])
    q = jnp.asarray(rng.randn(3, 8, 4, 16), jnp.float32)
    _assert_parity(*_run_both(q, kp, vp, tables, kv, qs, ql))


def test_empty_prefill_batch_parity_and_decode_equivalence():
    """All rows are decode rows — and the ragged kernel must reproduce
    the decode-only `paged_attention` kernel exactly (same online
    softmax, same order: the serving engine's decode numerics must not
    change when this kernel replaces the decode dispatch)."""
    rng = np.random.RandomState(2)
    kp, vp = _pool(rng)
    spec = [(9, 1), (32, 1), (1, 1), (17, 1)]
    tables, kv, qs, ql = _rows(rng, spec, 4, kp.shape[0])
    q = jnp.asarray(rng.randn(4, 1, 4, 16), jnp.float32)
    out_p, out_x = _run_both(q, kp, vp, tables, kv, qs, ql)
    _assert_parity(out_p, out_x)
    out_d = PA._paged_impl(q[:, 0], kp, vp, tables, kv,
                           scale=1.0 / np.sqrt(16))
    assert float(jnp.max(jnp.abs(out_d - out_p[:, 0]))) == 0.0


def test_two_chunks_of_one_sequence_match_single_chunk():
    """Chunked prefill correctness: a prompt processed as two rows
    (q_starts 0 and c) of one batch must produce the same outputs as
    the same prompt processed as one row — chunking is invisible."""
    rng = np.random.RandomState(3)
    kp, vp = _pool(rng)
    n, c, qb = 12, 8, 8
    table = rng.randint(0, kp.shape[0], (1, 4)).astype(np.int32)
    tables2 = jnp.asarray(np.vstack([table, table]))
    kv2 = jnp.asarray([c, n], np.int32)
    qs2 = jnp.asarray([0, c], np.int32)
    ql2 = jnp.asarray([c, n - c], np.int32)
    q_full = rng.randn(n, 4, 16).astype(np.float32)
    q2 = np.zeros((2, qb, 4, 16), np.float32)
    q2[0, :c] = q_full[:c]
    q2[1, :n - c] = q_full[c:]
    out2 = RPA._ragged_impl(jnp.asarray(q2), kp, vp, tables2, kv2, qs2,
                            ql2, scale=0.25)
    # one-row version needs QB >= n
    q1 = np.zeros((1, 16, 4, 16), np.float32)
    q1[0, :n] = q_full
    out1 = RPA._ragged_impl(jnp.asarray(q1), kp, vp,
                            jnp.asarray(table), jnp.asarray([n], np.int32),
                            jnp.asarray([0], np.int32),
                            jnp.asarray([n], np.int32), scale=0.25)
    got = jnp.concatenate([out2[0, :c], out2[1, :n - c]], axis=0)
    err = float(jnp.max(jnp.abs(got - out1[0, :n])))
    assert err < 1e-5, err


def test_causal_mask_within_chunk():
    """Query token at absolute position p must see exactly kv [0, p]:
    compare against dense causal attention built by hand."""
    rng = np.random.RandomState(4)
    hk, page, d, g = 2, 8, 16, 2
    kp, vp = _pool(rng, num_pages=8, hk=hk, page=page, d=d)
    table = np.asarray([[3, 5]], np.int32)
    n = 11
    q = np.zeros((1, 16, hk * g, d), np.float32)
    q[0, :n] = rng.randn(n, hk * g, d)
    out = RPA._ragged_impl(jnp.asarray(q), kp, vp, jnp.asarray(table),
                           jnp.asarray([n], np.int32),
                           jnp.asarray([0], np.int32),
                           jnp.asarray([n], np.int32),
                           scale=1.0 / np.sqrt(d))
    k_seq = jnp.swapaxes(kp[table[0]], 1, 2).reshape(-1, hk, d)[:n]
    v_seq = jnp.swapaxes(vp[table[0]], 1, 2).reshape(-1, hk, d)[:n]
    kq = jnp.repeat(k_seq, g, axis=1)
    vq = jnp.repeat(v_seq, g, axis=1)
    lg = jnp.einsum("qhd,shd->hqs", jnp.asarray(q[0, :n]), kq) \
        / np.sqrt(d)
    causal = np.tril(np.ones((n, n)))[None]
    lg = jnp.where(causal > 0, lg, -1e30)
    ref = jnp.einsum("hqs,shd->qhd", jax.nn.softmax(lg, axis=-1), vq)
    err = float(jnp.max(jnp.abs(ref - out[0, :n])))
    assert err < 1e-5, err


def test_kv_spanning_many_ragged_pages():
    """Long contexts crossing several pages, ragged lens not multiples
    of the page size, tables deliberately permuted."""
    rng = np.random.RandomState(5)
    kp, vp = _pool(rng, num_pages=64)
    spec = [(57, 8), (63, 1), (33, 7), (64, 8)]
    tables, kv, qs, ql = _rows(rng, spec, 8, kp.shape[0])
    q = jnp.asarray(rng.randn(4, 8, 4, 16), jnp.float32)
    _assert_parity(*_run_both(q, kp, vp, tables, kv, qs, ql))


def test_supported_rejects_bad_shapes():
    rng = np.random.RandomState(6)
    kp, vp = _pool(rng)
    tables = jnp.zeros((2, 4), jnp.int32)
    ones = jnp.ones((2,), jnp.int32)
    q = jnp.zeros((2, 4, 4, 16), jnp.float32)
    assert RPA.supported(q, kp, vp, tables, ones, ones, ones)
    # row-count mismatch
    assert not RPA.supported(q, kp, vp, tables[:1], ones, ones, ones)
    # head dim not a multiple of 8
    qb = jnp.zeros((2, 4, 4, 12), jnp.float32)
    assert not RPA.supported(qb, kp, vp, tables, ones, ones, ones)
    with pytest.raises(ValueError):
        RPA.ragged_paged_attention(qb, kp, vp, tables, ones, ones, ones)


# ----------------------------------------------------------------------
# fused KV page write (fused_ragged_paged_attention): parity against
# the write-THEN-read XLA reference and the unfused kernel pipeline
# ----------------------------------------------------------------------

def _fused_case(rng, kp, vp, dump):
    """A canonical mixed fused batch over pools kp/vp: sequence A as
    TWO chunk rows of one dispatch (rows 0/1 — the later chunk attends
    K/V the earlier row wrote in-kernel), sequence B as a decode row
    (row 2), one inactive row (row 3). Returns (q, new_k, new_v,
    tables, kv, qs, ql, ws, wf, we)."""
    P = kp.shape[0]
    hk, d = kp.shape[1], kp.shape[3]
    g = 2
    tables = np.full((4, 3), dump, np.int32)
    tables[0, :2] = [2, 3]
    tables[1, :2] = [2, 3]
    tables[2, :2] = [7, 1]
    assert P > 8
    kv = np.array([11, 13, 10, 0], np.int32)   # A: 5 prior + 6 + 2 new
    qs = np.array([5, 11, 9, 0], np.int32)
    ql = np.array([6, 2, 1, 0], np.int32)
    ws = np.array([5, 5, 9, 0], np.int32)      # A's span [5,13), B [9,10)
    wf = np.array([0, 0, 8, 0], np.int32)      # packed: A at 0..7, B at 8
    we = np.array([13, 13, 10, 0], np.int32)
    t = 9
    new_k = jnp.asarray(rng.randn(t, hk, d), jnp.float32)
    new_v = jnp.asarray(rng.randn(t, hk, d), jnp.float32)
    q = jnp.asarray(rng.randn(4, 8, hk * g, d), jnp.float32)
    return (q, new_k, new_v, jnp.asarray(tables), jnp.asarray(kv),
            jnp.asarray(qs), jnp.asarray(ql), jnp.asarray(ws),
            jnp.asarray(wf), jnp.asarray(we))


def _unwrap(a):
    return np.asarray(getattr(a, "_data", a))


def test_fused_multi_chunk_parity_and_pool_bytes():
    """Tentpole contract: the fused kernel must equal the write-then-
    read reference on EVERY row — including the later chunk of a
    sequence whose K/V an earlier row of the same grid produced — and
    must leave the non-dump pages of the pools bitwise identical to
    the reference's scatter."""
    rng = np.random.RandomState(10)
    kp, vp = _pool(rng, num_pages=16)
    dump = 15
    case = _fused_case(rng, kp, vp, dump)
    q, new_k, new_v, tables, kv, qs, ql, ws, wf, we = case
    out_f, kpf, vpf = RPA.fused_ragged_paged_attention(
        q, new_k, new_v, kp, vp, tables, kv, qs, ql, ws, wf, we, dump)
    out_x, kpx, vpx = RPA.fused_ragged_paged_attention_xla(
        q, new_k, new_v, kp, vp, tables, kv, qs, ql, ws, wf, we, dump)
    out_f, kpf, vpf = map(_unwrap, (out_f, kpf, vpf))
    _assert_parity(jnp.asarray(out_f), jnp.asarray(np.asarray(out_x)))
    live = [i for i in range(16) if i != dump]
    assert np.array_equal(kpf[live], np.asarray(kpx)[live])
    assert np.array_equal(vpf[live], np.asarray(vpx)[live])
    # untouched pages really untouched (0,4..6,8.. were in no table)
    for pg in (0, 4, 5, 6, 8):
        assert np.array_equal(kpf[pg], np.asarray(kp)[pg])
    # inactive row emits defined zeros
    assert float(np.max(np.abs(out_f[3]))) == 0.0


def test_fused_rows_bitwise_vs_unfused_kernel():
    """Decode rows (and every other row) of the fused kernel must be
    BITWISE what the unfused pipeline computes — scatter the new rows
    first, then run the plain Pallas kernel over the updated pools.
    This is the engine's greedy-token-exact guarantee at kernel
    level."""
    rng = np.random.RandomState(11)
    kp, vp = _pool(rng, num_pages=16)
    dump = 15
    q, new_k, new_v, tables, kv, qs, ql, ws, wf, we = \
        _fused_case(rng, kp, vp, dump)
    out_f = _unwrap(RPA.fused_ragged_paged_attention(
        q, new_k, new_v, kp, vp, tables, kv, qs, ql, ws, wf, we,
        dump)[0])
    # reference pools via the write-then-read scatter
    _, kpx, vpx = RPA.fused_ragged_paged_attention_xla(
        q, new_k, new_v, kp, vp, tables, kv, qs, ql, ws, wf, we, dump)
    out_u = np.asarray(RPA._ragged_impl(
        q, jnp.asarray(np.asarray(kpx)), jnp.asarray(np.asarray(vpx)),
        tables, kv, qs, ql, 1.0 / np.sqrt(q.shape[-1])))
    assert np.array_equal(out_f, out_u)
    # decode row named explicitly: the serving engine's decode contract
    assert np.array_equal(out_f[2], out_u[2])


def test_fused_q8_sidecar_bitwise_parity():
    """Int8 pools: the in-kernel quantizer must land bitwise the same
    int8 values AND scale sidecars as `_page_write_q8`'s
    `quantize_kv_int8` (the write-then-read reference uses it), and
    the fused output must be bitwise the unfused q8 kernel's over the
    scattered pools."""
    rng = np.random.RandomState(12)
    P, hk, page, d = 16, 2, 8, 16
    base = rng.randn(P, hk, page, d).astype(np.float32)
    amax = np.maximum(np.max(np.abs(base), -1, keepdims=True), 1e-8)
    kq = jnp.asarray(np.clip(np.round(base / (amax / 127.0)), -127,
                             127).astype(np.int8))
    ks = jnp.asarray((amax / 127.0).astype(np.float32))
    vq = jnp.asarray(np.roll(np.asarray(kq), 1, axis=0))
    vs = jnp.asarray(np.roll(np.asarray(ks), 1, axis=0))
    dump = 15
    q, new_k, new_v, tables, kv, qs, ql, ws, wf, we = \
        _fused_case(rng, jnp.asarray(base), jnp.asarray(base), dump)
    args = (q, new_k, new_v, kq, vq, tables, kv, qs, ql, ws, wf, we,
            dump)
    of, kf, vf, ksf, vsf = map(_unwrap, RPA.fused_ragged_paged_attention(
        *args, k_scale=ks, v_scale=vs))
    ox, kx, vx, ksx, vsx = map(np.asarray,
                               RPA.fused_ragged_paged_attention_xla(
                                   *args, k_scale=ks, v_scale=vs))
    live = [i for i in range(P) if i != dump]
    assert np.array_equal(kf[live], kx[live])
    assert np.array_equal(vf[live], vx[live])
    assert np.array_equal(ksf[live], ksx[live])      # scales BITWISE
    assert np.array_equal(vsf[live], vsx[live])
    out_u = np.asarray(RPA._ragged_impl_q8(
        q, jnp.asarray(kx), jnp.asarray(vx), jnp.asarray(ksx),
        jnp.asarray(vsx), tables, kv, qs, ql, 1.0 / np.sqrt(d)))
    assert np.array_equal(of, out_u)


def test_fused_boundary_page_replay_last_writer_wins():
    """A page straddling two chunk rows of one sequence is written
    once, by the LAST row, whose replay re-derives the earlier row's
    slots from the same packed values — so the twice-covered slots are
    bitwise the single-writer result (the fused path's last-writer-
    wins pin; `_page_write_q8`'s scatter-side pin lives in
    test_chunked_scheduler)."""
    rng = np.random.RandomState(13)
    kp, vp = _pool(rng, num_pages=16)
    dump = 15
    q, new_k, new_v, tables, kv, qs, ql, ws, wf, we = \
        _fused_case(rng, kp, vp, dump)
    # page 3 holds positions 8..12: row 0 wrote 8..10, row 1 wrote
    # 11..12 — row 1's write-back covers the whole page
    _, kpf, _ = RPA.fused_ragged_paged_attention(
        q, new_k, new_v, kp, vp, tables, kv, qs, ql, ws, wf, we, dump)
    kpf = _unwrap(kpf)
    # expected slots of page 3: positions 8,9,10 from packed rows 3,4,5
    for slot, f in ((0, 3), (1, 4), (2, 5), (3, 6), (4, 7)):
        want = np.asarray(new_k)[f].astype(kpf.dtype)   # [Hk, D]
        assert np.array_equal(kpf[3, :, slot, :], want)
    # slots past the span keep the original page bytes
    assert np.array_equal(kpf[3, :, 5:, :], np.asarray(kp)[3, :, 5:, :])


def test_fused_empty_prefill_and_empty_decode():
    """All-decode and all-chunk fused batches both match the
    reference."""
    rng = np.random.RandomState(14)
    kp, vp = _pool(rng, num_pages=32)
    dump = 31
    for spec in ([(9, 1), (17, 1), (32, 1)],          # all decode
                 [(8, 8), (13, 5), (24, 8)]):         # all chunks
        r = len(spec)
        kv = np.asarray([k for k, _ in spec], np.int32)
        ql = np.asarray([q for _, q in spec], np.int32)
        qs = kv - ql
        # DISJOINT per-row tables: the engine's allocator guarantees a
        # writable page belongs to exactly one sequence — _rows' random
        # ids could alias one row's write span into another row's read
        # span, which the fused contract explicitly excludes (and the
        # write-then-read reference would resolve differently)
        tables = jnp.asarray(
            rng.permutation(30)[:r * 4].reshape(r, 4).astype(np.int32))
        kv, qs, ql = (jnp.asarray(a) for a in (kv, qs, ql))
        t = int(np.asarray(ql).sum())
        ws, wf = np.asarray(qs, np.int32).copy(), np.concatenate(
            [[0], np.cumsum(np.asarray(ql))[:-1]]).astype(np.int32)
        we = np.asarray(kv, np.int32).copy()
        new_k = jnp.asarray(rng.randn(t, 2, 16), jnp.float32)
        new_v = jnp.asarray(rng.randn(t, 2, 16), jnp.float32)
        q = jnp.asarray(rng.randn(r, 8, 4, 16), jnp.float32)
        out_f = _unwrap(RPA.fused_ragged_paged_attention(
            q, new_k, new_v, kp, vp, tables, kv, qs, ql,
            jnp.asarray(ws), jnp.asarray(wf), jnp.asarray(we),
            dump)[0])
        out_x, kpx, vpx = RPA.fused_ragged_paged_attention_xla(
            q, new_k, new_v, kp, vp, tables, kv, qs, ql,
            jnp.asarray(ws), jnp.asarray(wf), jnp.asarray(we), dump)
        _assert_parity(jnp.asarray(out_f), jnp.asarray(np.asarray(out_x)))


def test_fused_poisoned_table_tails_never_written():
    """Table tail entries past the context may hold garbage ids: reads
    clamp (as in the unfused kernel) and the write-back must never
    touch the page a poisoned tail points at."""
    rng = np.random.RandomState(15)
    kp, vp = _pool(rng, num_pages=16)
    dump = 15
    q, new_k, new_v, tables, kv, qs, ql, ws, wf, we = \
        _fused_case(rng, kp, vp, dump)
    poisoned = np.asarray(tables).copy()
    poisoned[:, 2:] = 10_000             # way past the pool
    out_a, kpa, _ = map(_unwrap, RPA.fused_ragged_paged_attention(
        q, new_k, new_v, kp, vp, tables, kv, qs, ql, ws, wf, we, dump))
    out_b, kpb, _ = map(_unwrap, RPA.fused_ragged_paged_attention(
        q, new_k, new_v, kp, vp, jnp.asarray(poisoned), kv, qs, ql,
        ws, wf, we, dump))
    assert np.array_equal(out_a, out_b)
    live = [i for i in range(16) if i != dump]
    assert np.array_equal(kpa[live], kpb[live])


def test_fused_supported_gates():
    rng = np.random.RandomState(16)
    kp, vp = _pool(rng)
    tables = jnp.zeros((2, 4), jnp.int32)
    ones = jnp.ones((2,), jnp.int32)
    q = jnp.zeros((2, 4, 4, 16), jnp.float32)
    nk = jnp.zeros((2, 2, 16), jnp.float32)
    ok = (q, nk, nk, kp, vp, tables, ones, ones, ones, ones, ones,
          ones, 31)
    assert RPA.fused_supported(*ok)
    # new rows with the wrong head count
    bad_nk = jnp.zeros((2, 3, 16), jnp.float32)
    assert not RPA.fused_supported(q, bad_nk, bad_nk, kp, vp, tables,
                                   ones, ones, ones, ones, ones, ones,
                                   31)
    # dump page outside the pool
    assert not RPA.fused_supported(q, nk, nk, kp, vp, tables, ones,
                                   ones, ones, ones, ones, ones, 99)
    # w metadata with the wrong row count
    assert not RPA.fused_supported(q, nk, nk, kp, vp, tables, ones,
                                   ones, ones, jnp.ones((3,), jnp.int32),
                                   ones, ones, 31)
    with pytest.raises(ValueError):
        RPA.fused_ragged_paged_attention(q, bad_nk, bad_nk, kp, vp,
                                         tables, ones, ones, ones,
                                         ones, ones, ones, 31)


# ----------------------------------------------------------------------
# fused rope (rope_sin/rope_cos): rope + write + attention in one
# kernel, proven against the rope-THEN-write-THEN-read reference and
# bitwise against the PR-13 post-rope pipeline
# ----------------------------------------------------------------------

def _packed_positions(qs, ql):
    return np.concatenate(
        [np.arange(int(s), int(s) + int(n))
         for s, n in zip(np.asarray(qs), np.asarray(ql))]) \
        .astype(np.int32)


def _rope_jitted(x, sin, cos):
    """The unfused `_apply_rope` chain, JITTED — XLA contracts the
    mul+add into an FMA under jit (1 ulp off eager), and every path
    under test runs as a jitted computation."""
    import functools

    @functools.partial(jax.jit, static_argnums=())
    def f(x, sin, cos):
        xf = x.astype(jnp.float32)
        h = xf.shape[-1] // 2
        rot = jnp.concatenate([-xf[..., h:], xf[..., :h]], -1)
        out = xf * cos[:, None, :] + rot * sin[:, None, :]
        return out.astype(x.dtype)

    return np.asarray(f(x, sin, cos))


def _rope_case(rng, kp, vp, dump, qb=8):
    """The `_fused_case` geometry with PRE-rope packed q [T, H, D] and
    per-dispatch sin/cos tables at the rows' (arbitrary, non-zero-
    based) positions."""
    q, new_k, new_v, tables, kv, qs, ql, ws, wf, we = \
        _fused_case(rng, kp, vp, dump)
    t = int(np.asarray(ql).sum())
    h = q.shape[2]
    d = q.shape[3]
    q_packed = jnp.asarray(rng.randn(t, h, d), jnp.float32)
    pos = _packed_positions(qs, ql)
    sin, cos = RPA.rope_tables(jnp.asarray(pos), d, 10000.0)
    return (q_packed, new_k, new_v, tables, kv, qs, ql, ws, wf, we,
            sin, cos, qb)


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


def test_fused_rope_bitwise_vs_post_rope_kernel():
    """Given identical rope bits (the jitted table chain), the rope-
    fused kernel must produce BITWISE the PR-13 fused kernel's pools —
    the in-kernel rotation adds only IEEE-exact ops — and its outputs
    to float rounding (the walk accumulates a block at a time). This
    is the engine's fused_rope=0 fallback at kernel level, decode rows
    included."""
    rng = np.random.RandomState(31)
    kp, vp = _pool(rng, num_pages=16)
    dump = 15
    (q_packed, new_k, new_v, tables, kv, qs, ql, ws, wf, we, sin, cos,
     qb) = _rope_case(rng, kp, vp, dump)
    out_f, kpf, vpf = map(_unwrap, RPA.fused_ragged_paged_attention(
        q_packed, new_k, new_v, kp, vp, tables, kv, qs, ql, ws, wf,
        we, dump, rope_sin=sin, rope_cos=cos, qblock=qb))
    # manual rope + row-block pack, then the post-rope fused kernel
    q_rot = _rope_jitted(q_packed, np.asarray(sin), np.asarray(cos))
    k_rot = jnp.asarray(_rope_jitted(new_k, np.asarray(sin),
                                     np.asarray(cos)))
    r = tables.shape[0]
    qr = np.zeros((r, qb) + q_rot.shape[1:], q_rot.dtype)
    off = 0
    for i in range(r):
        n = int(np.asarray(ql)[i])
        qr[i, :n] = q_rot[off:off + n]
        off += n
    out_13, kp13, vp13 = map(_unwrap, RPA.fused_ragged_paged_attention(
        jnp.asarray(qr), k_rot, new_v, kp, vp, tables, kv, qs, ql, ws,
        wf, we, dump))
    # the output is accumulated a block of pages at a time here and a
    # page at a time there: equal to float rounding, decode row (row
    # 2) included; the pool bytes stay bitwise
    _assert_parity(jnp.asarray(out_f), jnp.asarray(out_13))
    _assert_parity(jnp.asarray(out_f[2]), jnp.asarray(out_13[2]))
    live = [i for i in range(16) if i != dump]
    assert np.array_equal(kpf[live], kp13[live])
    assert np.array_equal(vpf[live], vp13[live])


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
    assert RPA.fused_supported(*base, rope_sin=tb, rope_cos=tb,
                               qblock=4)
    # qblock is mandatory with rope tables
    assert not RPA.fused_supported(*base, rope_sin=tb, rope_cos=tb)
    # one table missing
    assert not RPA.fused_supported(*base, rope_sin=tb, qblock=4)
    # table rows must match the packed token count
    bad_tb = jnp.zeros((3, 16), jnp.float32)
    assert not RPA.fused_supported(*base, rope_sin=bad_tb,
                                   rope_cos=bad_tb, qblock=4)
    # q must be the packed 3-D layout when rope is fused
    q4 = jnp.zeros((2, 4, 4, 16), jnp.float32)
    assert not RPA.fused_supported(q4, *base[1:], rope_sin=tb,
                                   rope_cos=tb, qblock=4)
    # geometry gate: odd head_dim can't rotate
    assert not RPA.fused_rope_geometry_ok(15)
    assert RPA.fused_rope_geometry_ok(16)
    with pytest.raises(ValueError):
        RPA.fused_ragged_paged_attention(qp, nk, nk, kp, vp, tables,
                                         ones, ones, ones, ones, ones,
                                         ones, 31, rope_sin=tb,
                                         rope_cos=tb)


# ----------------------------------------------------------------------
# the float rope-fused program's walk: bounded by kv_lens, a block of
# pages a trip, pages fetched by the kernel itself
# ----------------------------------------------------------------------
PAGE = 8
BLOCK = RPA._walk_pages(PAGE, 2, 16, 4) * PAGE      # tokens a trip


def _walk_case(rng, seqs, width, qb, tail=10_000, poison=False):
    """A dispatch over a fresh pool. ``seqs`` is a list of ``(prior,
    chunks)``: a sequence holding ``prior`` tokens in the pool whose
    next ``chunks`` (a list of lengths) are this dispatch's rows, in
    order; no chunks means one inactive row. Live pages are distinct,
    table tails hold ``tail``. With ``poison`` every page no row
    holds, every slot at or past a sequence's ``prior`` (what this
    dispatch writes too) and the trash page, where the tails then
    point, are NaN. Returns the kernel's arguments and qblock."""
    hk, g, d = 2, 2, 16
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
    if poison:
        clean = np.zeros((pool, PAGE), bool)
        for si, (prior, chunks) in enumerate(seqs):
            n = prior if chunks else 0       # not what is written now
            for j in range(held[si]):
                clean[ids[start[si] + j], :max(0, min(PAGE,
                                                      n - j * PAGE))] = 1
        kp[~clean[:, None, :].repeat(hk, 1)] = np.nan
        vp[~clean[:, None, :].repeat(hk, 1)] = np.nan
        tables[tables == tail] = dump
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
    return args, dict(rope_sin=sin, rope_cos=cos, qblock=qb)


def _assert_walk_parity(args, kw):
    out_f, kpf, vpf = map(_unwrap, RPA.fused_ragged_paged_attention(
        *args, **kw))
    out_x, kpx, vpx = map(np.asarray,
                          RPA.fused_ragged_paged_attention_xla(
                              *args, **kw))
    _assert_parity(jnp.asarray(out_f), jnp.asarray(out_x))
    live = [i for i in range(kpf.shape[0]) if i != args[-1]]
    assert np.array_equal(kpf[live], kpx[live])         # BITWISE
    assert np.array_equal(vpf[live], vpx[live])
    # padded query rows and inactive rows are defined zeros
    ql = np.asarray(args[8])
    for i, n in enumerate(ql):
        assert float(np.max(np.abs(out_f[i, n:]), initial=0.0)) == 0.0
    return out_f


WALK_KV = {"0": 0, "1": 1, "block-1": BLOCK - 1, "block": BLOCK,
           "block+1": BLOCK + 1, "full": 10 ** 9}


@pytest.mark.parametrize("width", [161, 66, 5])
@pytest.mark.parametrize("kv", list(WALK_KV))
def test_walk_bounded_by_kv_len(kv, width):
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
    _assert_walk_parity(*_walk_case(rng, seqs, width, qb))


@pytest.mark.parametrize("width", [161, 66])
def test_walk_two_chunks_across_a_block_edge(width):
    """Two chunks of ONE sequence in one dispatch whose write span
    straddles a block edge: the later chunk replays what the earlier
    wrote from the packed rows, in two different blocks of its walk,
    and the last row writes pages of both blocks."""
    rng = np.random.RandomState(50 + width)
    seqs = [(BLOCK - 12, [8, 8]), (0, []), (BLOCK + 30, [1]),
            (3, [5])]
    _assert_walk_parity(*_walk_case(rng, seqs, width, 8))


def test_walk_all_decode_batch():
    """qblock 1, every row one token: the scan tick's shape, contexts
    on both sides of a block edge."""
    rng = np.random.RandomState(60)
    seqs = [(n - 1, [1]) for n in (1, BLOCK - 1, BLOCK, BLOCK + 1, 300)]
    seqs.insert(2, (0, []))
    _assert_walk_parity(*_walk_case(rng, seqs, 66, 1))


@pytest.mark.parametrize("qb", [1, 8])
def test_walk_uses_nothing_past_the_context(qb):
    """Every page no row holds, every slot past a row's kv_len and
    every table tail is NaN: the output is finite and equal, bit for
    bit, to the one over a clean pool, and no NaN page is written."""
    seqs = [(BLOCK - 3, [min(qb, 5)]), (0, []), (BLOCK + 9, [1]),
            (2, [qb])]
    runs = []
    for poison in (False, True):
        rng = np.random.RandomState(70 + qb)
        args, kw = _walk_case(rng, seqs, 66, qb, poison=poison)
        runs.append((args, map(_unwrap, RPA.fused_ragged_paged_attention(
            *args, **kw))))
    (_, (out_a, kpa, _)), (args, (out_b, kpb, vpb)) = runs
    assert np.all(np.isfinite(out_b))
    assert np.array_equal(out_a, out_b)
    # what was written is what the clean run wrote; what was NaN and
    # not fresh stays NaN (a written page keeps its other slots)
    fresh = ~np.isnan(kpb) & np.isnan(np.asarray(args[3]))
    assert fresh.any()
    assert np.array_equal(kpa[fresh], kpb[fresh])
    assert np.array_equal(np.isnan(kpb) | fresh,
                          np.isnan(np.asarray(args[3])))


def test_walk_under_the_tpu_interpreter(monkeypatch):
    """The same program under Pallas's TPU interpreter, which starts
    VMEM as NaN and raises on a read out of bounds (the generic
    interpreter the suite runs under zero-fills): a buffer slot no DMA
    filled, or a table slot past the row's pages, would show."""
    from jax.experimental.pallas import tpu as pltpu
    monkeypatch.setattr(RPA, "_interpret",
                        lambda: pltpu.InterpretParams())
    rng = np.random.RandomState(80)
    seqs = [(BLOCK - 4, [8, 3]), (0, []), (BLOCK + 1, [1])]
    _assert_walk_parity(*_walk_case(rng, seqs, 40, 8))


def test_table_tail_garbage_is_clamped():
    """Unused table tail entries may hold anything — including ids past
    the pool — without observable effect (they are clamped before the
    index map, exactly like `paged_attention`)."""
    rng = np.random.RandomState(7)
    kp, vp = _pool(rng)
    spec = [(9, 2)]
    tables, kv, qs, ql = _rows(rng, spec, 4, kp.shape[0])
    q = jnp.asarray(rng.randn(1, 4, 4, 16), jnp.float32)
    out_a, _ = _run_both(q, kp, vp, tables, kv, qs, ql)
    poisoned = np.asarray(tables).copy()
    poisoned[0, 2:] = 10_000            # way past the pool
    out_b = RPA._ragged_impl(q, kp, vp, jnp.asarray(poisoned), kv, qs,
                             ql, scale=0.25)
    out_a2 = RPA._ragged_impl(q, kp, vp, tables, kv, qs, ql, scale=0.25)
    assert float(jnp.max(jnp.abs(out_a2 - out_b))) == 0.0
