"""Chunked-prefill scheduler invariants.

The engine's contract after the ragged rewrite: ONE mixed dispatch per
step serves prefill chunks and live decodes together under a
``chunk_budget`` token budget. These tests pin the scheduler-level
guarantees (tier-1, CPU, host-driven):

- chunking is invisible to outputs: token-exact vs the model's own
  static-cache greedy decode, whatever the chunk/budget geometry;
- a long prompt admitted mid-stream NEVER stalls live decodes — every
  step emits one token per live decoder while the prompt chunks in;
- prefill progress per step is bounded by the budget;
- deadlines, cancellation and pool-pressure eviction fire at chunk
  boundaries, mid-prefill included, with pages released;
- a prefix-cache warm admission prefills its whole suffix in ONE
  mixed dispatch (the PR-6 per-position teacher-forcing loop is gone).
"""

import time

import numpy as np
import pytest

import paddle_tpu as paddle
from paddle_tpu.models import LlamaForCausalLM, tiny_llama_config
from paddle_tpu.inference.serving import LlamaServingEngine, Request


@pytest.fixture(scope="module")
def model():
    paddle.seed(0)
    m = LlamaForCausalLM(tiny_llama_config())
    m.eval()
    return m


def _reference_continuation(model, prompt, n):
    ids = paddle.to_tensor(np.asarray([prompt], np.int64))
    out = model.generate(ids, max_new_tokens=n)
    return np.asarray(out._data)[0, len(prompt):].tolist()


def _engine(model, **kw):
    kw.setdefault("max_batch", 4)
    kw.setdefault("page_size", 8)
    kw.setdefault("num_pages", 64)
    return LlamaServingEngine(model, **kw)


def test_chunked_prefill_token_exact(model):
    """A prompt far longer than chunk_block prefills across several
    rows/steps and still reproduces the reference exactly."""
    rng = np.random.RandomState(0)
    v = model.config.vocab_size
    p = rng.randint(0, v, (41,)).tolist()
    want = _reference_continuation(model, p, 6)
    engine = _engine(model, chunk_block=8, chunk_budget=16)
    assert engine.chunk_block == 8
    got = engine.generate([p], max_new_tokens=6)[0]
    assert got == want
    assert not engine._live
    engine.close()


def test_multi_chunk_single_dispatch_token_exact(model):
    """A prompt spanning several chunk rows of ONE dispatch (budget >=
    prompt > chunk_block) is still exact — later chunks attend K/V the
    same dispatch wrote."""
    rng = np.random.RandomState(1)
    v = model.config.vocab_size
    p = rng.randint(0, v, (30,)).tolist()
    want = _reference_continuation(model, p, 4)
    engine = _engine(model, chunk_block=8, chunk_budget=32)
    d0 = engine._dispatch_count
    r = Request(p, max_new_tokens=4)
    engine.add_request(r)
    # 30 tokens / block 8 = 4 chunk rows, all inside one 32-token budget
    assert engine._dispatch_count == d0 + 1
    while not r.done:
        engine.step()
    assert r.output_ids == want
    engine.close()


def test_long_prompt_never_stalls_live_decodes(model):
    """THE latency property chunked prefill buys: while a long prompt
    chunks in, every already-live decoder still emits one token per
    step — the prompt never serializes the batch."""
    rng = np.random.RandomState(2)
    v = model.config.vocab_size
    d1 = Request(rng.randint(0, v, (5,)).tolist(), max_new_tokens=64)
    d2 = Request(rng.randint(0, v, (3,)).tolist(), max_new_tokens=64)
    engine = _engine(model, chunk_block=4, chunk_budget=8)
    engine.add_request(d1)
    engine.add_request(d2)
    long = Request(rng.randint(0, v, (40,)).tolist(), max_new_tokens=2)
    engine._admit(long)
    steps = 0
    while long._prefilled < len(long.prompt_ids):
        n1, n2 = len(d1.output_ids), len(d2.output_ids)
        before = long._prefilled
        engine.step()
        steps += 1
        # decoders advanced THIS step, prefill advanced at most budget
        assert len(d1.output_ids) == n1 + 1
        assert len(d2.output_ids) == n2 + 1
        assert 0 < long._prefilled - before <= engine.chunk_budget
        assert steps < 50
    assert steps > 1                    # it really was chunked
    # and everyone remains token-exact
    while not (d1.done and d2.done and long.done):
        engine.step()
    for r in (d1, d2, long):
        want = _reference_continuation(model, list(r.prompt_ids),
                                       r.max_new_tokens)
        assert r.output_ids == want
    engine.close()


def test_deadline_fires_at_chunk_boundary_mid_prefill(model):
    """A deadline lapsing while the prompt is still chunking in expires
    the request at the next chunk boundary — typed, pages released,
    before a single token was emitted."""
    from paddle_tpu.inference.serving import DeadlineExceeded

    rng = np.random.RandomState(3)
    v = model.config.vocab_size
    engine = _engine(model, chunk_block=4, chunk_budget=8)
    free0 = engine.alloc.free_pages
    r = Request(rng.randint(0, v, (40,)).tolist(), max_new_tokens=8,
                deadline=0.005)
    engine._admit(r)
    engine.step()                       # first chunk(s) only
    assert 0 < r._prefilled < len(r.prompt_ids)
    time.sleep(0.02)
    engine.step()                       # boundary check trips it
    assert r.done and r.status == "deadline_exceeded"
    assert isinstance(r.error, DeadlineExceeded)
    assert r.output_ids == []
    assert engine.alloc.free_pages == free0
    engine.close()


def test_cancel_mid_prefill_releases_pages(model):
    rng = np.random.RandomState(4)
    v = model.config.vocab_size
    engine = _engine(model, chunk_block=4, chunk_budget=8)
    free0 = engine.alloc.free_pages
    r = Request(rng.randint(0, v, (40,)).tolist(), max_new_tokens=8)
    engine._admit(r)
    engine.step()
    assert 0 < r._prefilled < len(r.prompt_ids)
    assert engine.cancel(r) is True
    assert r.status == "cancelled" and r.output_ids == []
    assert engine.alloc.free_pages == free0
    # the engine is still healthy and exact afterwards
    p = rng.randint(0, v, (5,)).tolist()
    want = _reference_continuation(model, p, 4)
    assert engine.generate([p], max_new_tokens=4)[0] == want
    engine.close()


def test_pressure_evicts_at_chunk_boundary_and_recovers(model):
    """Decode-boundary pool pressure during mixed steps walks the
    ladder (evict + requeue) and both requests end typed — the chunked
    scheduler preserves the PR-4 contract."""
    from paddle_tpu.observability import metrics as om

    engine = LlamaServingEngine(model, max_batch=2, page_size=8,
                                num_pages=8, chunk_block=4,
                                chunk_budget=8)
    free0 = engine.alloc.free_pages
    r1 = Request([1, 2, 3], max_new_tokens=10000)
    r2 = Request([4, 5], max_new_tokens=10000)
    engine.add_request(r1)
    engine.add_request(r2)
    for _ in range(400):
        if r1.done and r2.done:
            break
        engine.step()
    assert r1.done and r2.done
    for r in (r1, r2):
        assert r.status in ("completed", "evicted"), r.status
    if om.enabled():
        ev = om.counter("serving_degraded_total",
                        labelnames=("rung",)).labels("evict").value
        assert ev >= 1
    assert engine.alloc.free_pages == free0
    assert not engine._live and not engine._requeue
    engine.close()


def test_prefix_suffix_prefills_in_one_dispatch(model):
    """Satellite contract: a warm (prefix-cached) admission prefills
    its whole un-cached suffix as chunk rows of ONE mixed dispatch —
    not one teacher-forced dispatch per suffix position."""
    rng = np.random.RandomState(5)
    v = model.config.vocab_size
    prefix = rng.randint(0, v, (16,)).tolist()      # two full pages
    engine = _engine(model, chunk_block=8, chunk_budget=32)
    cold = Request(prefix + rng.randint(0, v, (6,)).tolist(),
                   max_new_tokens=2)
    engine.add_request(cold)
    while not cold.done:
        engine.step()
    warm_prompt = prefix + rng.randint(0, v, (6,)).tolist()
    want = _reference_continuation(model, warm_prompt, 3)
    warm = Request(warm_prompt, max_new_tokens=3)
    d0 = engine._dispatch_count
    engine.add_request(warm)
    assert warm._cached_tokens == 16                # cache hit
    assert engine._dispatch_count == d0 + 1         # ONE dispatch
    while not warm.done:
        engine.step()
    assert warm.output_ids == want                  # token-exact reuse
    engine.close()


def test_decode_only_steps_use_compact_shape(model):
    """Once every prompt is in, steps dispatch the [max_batch]-token
    decode shape, not the full chunk_budget shape (no padded-token
    compute on the decode hot path)."""
    rng = np.random.RandomState(6)
    v = model.config.vocab_size
    engine = _engine(model, chunk_block=8, chunk_budget=32)
    r = Request(rng.randint(0, v, (5,)).tolist(), max_new_tokens=8)
    engine.add_request(r)
    engine.step()
    assert ("mixed", engine.chunk_budget) in engine._warmed_keys
    assert ("mixed", engine.max_batch) in engine._warmed_keys
    engine.close()


def test_requeue_pump_reprefills_through_chunks(model):
    """An evicted+requeued request re-admitted by the boundary pump
    restarts its prefill from scratch through the chunked path and
    still ends token-exact."""
    engine = LlamaServingEngine(model, max_batch=2, page_size=8,
                                num_pages=8, chunk_block=4,
                                chunk_budget=8)
    p1, p2 = [1, 2, 3, 4, 5, 6, 7, 8, 9], [7, 8]
    r1 = Request(p1, max_new_tokens=30, priority=1)
    r2 = Request(p2, max_new_tokens=30, retry_budget=3)
    engine.add_request(r1)
    engine.add_request(r2)
    for _ in range(400):
        if r1.done and r2.done:
            break
        engine.step()
    assert r1.done and r1.status == "completed"
    assert r2.done and r2.status in ("completed", "evicted")
    if r2.status == "completed" and not r2.trimmed and not r1.trimmed:
        assert r1.output_ids == _reference_continuation(model, p1, 30)
        assert r2.output_ids == _reference_continuation(model, p2, 30)
    engine.close()


# ----------------------------------------------------------------------
# fused in-kernel KV page write (PADDLE_TPU_FUSED_KV): the engine must
# be byte-for-byte indistinguishable fused vs unfused
# ----------------------------------------------------------------------

def _pool_state(engine):
    """(pools, scales, trash) — non-trash page bytes are the cross-path
    parity surface; the trash page is an explicit dump with undefined
    contents under fusion."""
    pools = [np.asarray(p._data) for p in engine.k_pools + engine.v_pools]
    scales = [np.asarray(s._data)
              for s in engine.k_scales + engine.v_scales]
    return pools, scales, engine.trash_page


def _assert_same_pools(a, b, scale_rtol=0.0):
    """`scale_rtol=0` demands bitwise pool equality. Long int8 runs
    pass a tiny rtol for the SCALE sidecars only: a scale is a pure
    f32 function of the K/V row being written, and those rows ride
    through attention outputs that XLA fuses differently in the fused
    vs unfused programs (different surrounding graphs -> different
    FMA/fusion picks), so after many speculative steps a handful of
    scales drift by ~1 ulp while every int8 page byte and every
    greedy token stays exact — the q8 engine bar, not a write bug."""
    pools_a, scales_a, trash = a
    pools_b, scales_b, _ = b
    live = [i for i in range(pools_a[0].shape[0]) if i != trash]
    for x, y in zip(pools_a, pools_b):
        assert np.array_equal(x[live], y[live])
    for x, y in zip(scales_a, scales_b):
        if scale_rtol:
            np.testing.assert_allclose(x[live], y[live],
                                       rtol=scale_rtol, atol=0.0)
        else:
            assert np.array_equal(x[live], y[live])


def test_fused_vs_unfused_token_exact_and_pool_bytes(model):
    """PADDLE_TPU_FUSED_KV=0 must restore the two-op path byte for
    byte: same greedy tokens AND identical non-trash pool bytes, fp
    and int8 (int8 scale sidecars included), across multi-chunk
    prompts and decode steps."""
    rng = np.random.RandomState(20)
    v = model.config.vocab_size
    prompts = [rng.randint(0, v, (n,)).tolist() for n in (30, 5, 12)]

    def run(fused, **kw):
        e = _engine(model, chunk_block=8, chunk_budget=32,
                    fused_kv=fused, **kw)
        out = e.generate(prompts, max_new_tokens=6)
        state = _pool_state(e)
        e.close()
        return out, state

    for kw in ({}, {"kv_dtype": "int8"}):
        out_f, st_f = run(True, **kw)
        out_u, st_u = run(False, **kw)
        assert out_f == out_u
        _assert_same_pools(st_f, st_u)


def test_fused_spec_rollback_pool_bitwise(model):
    """Acceptance: after a speculative ROLLBACK (garbage drafter, every
    draft rejected) the fused engine's pool state is bitwise what the
    unfused path leaves — rejected-draft slots included — and outputs
    stay token-exact, fp and int8."""
    rng = np.random.RandomState(21)
    v = model.config.vocab_size
    p = rng.randint(0, v, (5,)).tolist()

    class GarbageDrafter:
        """Proposes fixed wrong tokens: verification rejects them all,
        exercising rollback every dispatch."""
        def sync(self, prompt_ids, output_ids):
            pass

        def propose(self, k):
            return [1] * k

    for kw in ({}, {"kv_dtype": "int8"}):
        def run(fused):
            e = _engine(model, chunk_block=8, chunk_budget=32,
                        spec_k=3, drafter_factory=GarbageDrafter,
                        fused_kv=fused, **kw)
            r = Request(p, max_new_tokens=6)
            e.add_request(r)
            while not r.done:
                e.step()
            state = _pool_state(e)
            spec = e.spec_stats()
            e.close()
            return r.output_ids, state, spec

        out_f, st_f, spec_f = run(True)
        out_u, st_u, spec_u = run(False)
        assert spec_f["proposed"] > 0           # speculation really ran
        assert spec_f["accepted"] < spec_f["proposed"]  # and rolled back
        assert spec_f == spec_u
        assert out_f == out_u
        if not kw:
            # fp only: int8 pools legitimately shift greedy tokens vs
            # the float reference (the quantized read), while staying
            # deterministic across fused/unfused above
            assert out_f == _reference_continuation(model, p, 6)
        _assert_same_pools(st_f, st_u)


def test_fused_cow_guard_still_fires(model):
    """Prefix-cache COW contract under fusion: a shared page is made
    private BEFORE the in-kernel write lands, the shared original's
    bytes stay untouched, and outputs match an unshared run."""
    rng = np.random.RandomState(22)
    v = model.config.vocab_size
    p = rng.randint(0, v, (4,)).tolist()

    def run(pin):
        e = _engine(model, prefix_cache=False)
        assert e.fused_kv
        r = Request(p, max_new_tokens=8)
        e.add_request(r)
        frozen = None
        if pin:
            sid = r.seq_id
            page0 = e.alloc._tables[sid][0]
            e.alloc.incref(page0)            # simulate another owner
            frozen = [np.asarray(pl._data[page0]).copy()
                      for pl in e.k_pools + e.v_pools]
        while not r.done:
            e.step()
        if pin:
            assert e.alloc.cow_count >= 1    # guard fired pre-write
            for pl, want in zip(e.k_pools + e.v_pools, frozen):
                assert np.array_equal(np.asarray(pl._data[page0]), want)
            e.alloc.decref(page0)
        e.close()
        return r.output_ids

    assert run(pin=True) == run(pin=False)


def test_fused_env_knob_and_shape_key(model, monkeypatch):
    """PADDLE_TPU_FUSED_KV=0 selects the unfused program; the engine
    shape key forks so prewarm recipes never cross the two engines."""
    monkeypatch.setenv("PADDLE_TPU_FUSED_KV", "0")
    e_off = _engine(model)
    assert e_off.fused_kv is False
    monkeypatch.delenv("PADDLE_TPU_FUSED_KV")
    e_on = _engine(model)
    assert e_on.fused_kv is True             # default on
    assert e_on._shape_key != e_off._shape_key
    e_off.close()
    e_on.close()


def test_fused_mixed_hbm_gauge_recorded(model):
    """Satellite: `serving_mixed_hbm_bytes` carries the mixed program's
    static cost_analysis bytes after a dispatch (metrics on)."""
    from paddle_tpu.observability import metrics as om

    if not om.enabled():
        pytest.skip("PADDLE_TPU_METRICS=0")
    engine = _engine(model)
    engine.generate([[1, 2, 3]], max_new_tokens=2)
    assert engine._mixed_bytes                  # analysis cached
    assert om.gauge("serving_mixed_hbm_bytes").value > 0
    engine.close()


def test_mixed_program_does_not_return_the_weights(model):
    """The weights are read-only state of the mixed program: an AOT
    executable would hand a non-donated pass-through back as a fresh
    copy (every weight, every dispatch), so they stay out of its
    outputs — what it returns is the pools (aliased) and the tokens."""
    from paddle_tpu.observability import metrics as om

    if not om.enabled():
        pytest.skip("PADDLE_TPU_METRICS=0")
    engine = _engine(model)
    before = [p._data for p in model.parameters()]
    engine.generate([[1, 2, 3]], max_new_tokens=2)
    weights = sum(p._data.nbytes for p in model.parameters())
    for compiled in engine._mixed_static._aot.values():
        ma = compiled.memory_analysis()
        assert ma.output_size_in_bytes - ma.alias_size_in_bytes < weights / 4
    assert all(p._data is b for p, b in zip(model.parameters(), before))
    engine.close()


# ----------------------------------------------------------------------
# fused rope (PADDLE_TPU_FUSED_ROPE): rope + write + attention in one
# Pallas program — the engine must be byte-for-byte indistinguishable
# from the PR-13 fused-KV path and the fully-unfused path
# ----------------------------------------------------------------------

def test_fused_rope_env_knob_and_shape_key(model, monkeypatch):
    """PADDLE_TPU_FUSED_ROPE=0 restores the PR-13 fused-KV program;
    the shape key forks on the flag; rope fusion requires the fused KV
    write (PADDLE_TPU_FUSED_KV=0 reaches the original two-op path,
    rope knob notwithstanding)."""
    monkeypatch.setenv("PADDLE_TPU_FUSED_ROPE", "0")
    e_off = _engine(model)
    assert e_off.fused_kv is True and e_off.fused_rope is False
    monkeypatch.delenv("PADDLE_TPU_FUSED_ROPE")
    e_on = _engine(model)
    assert e_on.fused_rope is True               # default on
    assert e_on._shape_key != e_off._shape_key
    # no rope fusion without the fused KV write it rides on
    e_u = _engine(model, fused_kv=False)
    assert e_u.fused_rope is False
    assert len({e_on._shape_key, e_off._shape_key, e_u._shape_key}) == 3
    for e in (e_off, e_on, e_u):
        e.close()


def test_fused_rope_vs_pr13_vs_unfused_token_exact_and_pools(model):
    """The three-program ladder (rope-fused / fused-KV / two-op) must
    agree token-exactly with identical non-trash pool bytes, fp and
    int8 (scale sidecars included), across multi-chunk prompts and
    decode steps — including the SAME-prompt multi-chunk replay inside
    one dispatch (the 30-token prompt spans 4 chunk rows of a single
    32-token budget)."""
    rng = np.random.RandomState(40)
    v = model.config.vocab_size
    prompts = [rng.randint(0, v, (n,)).tolist() for n in (30, 5, 12)]

    def run(**kw):
        e = _engine(model, chunk_block=8, chunk_budget=32, **kw)
        out = e.generate(prompts, max_new_tokens=6)
        state = _pool_state(e)
        e.close()
        return out, state

    for kw in ({}, {"kv_dtype": "int8"}):
        out_r, st_r = run(**kw)                       # rope-fused
        out_f, st_f = run(fused_rope=False, **kw)     # PR-13
        out_u, st_u = run(fused_kv=False, **kw)       # two-op
        assert out_r == out_f == out_u
        _assert_same_pools(st_r, st_f)
        _assert_same_pools(st_f, st_u)
    # and the fp outputs match the model's own reference continuation
    want = [_reference_continuation(model, p, 6) for p in prompts]
    assert run()[0] == want


def test_fused_rope_decode_scan_matches_reference(model):
    """The decode scan carry under rope fusion: a long scanned decode
    run (decode_many -> lax.scan ticks, per-tick rope tables from the
    length carry) stays token-exact vs the reference and vs the
    PR-13 path."""
    rng = np.random.RandomState(41)
    v = model.config.vocab_size
    p = rng.randint(0, v, (5,)).tolist()

    def run(fused_rope):
        e = _engine(model, decode_ticks=8, fused_rope=fused_rope)
        r = Request(p, max_new_tokens=20)
        e.add_request(r)
        e.decode_many(20)
        out = list(r.output_ids)
        e.close()
        return out

    want = _reference_continuation(model, p, 20)
    assert run(True) == want
    assert run(False) == want


def test_fused_rope_spec_rollback_pool_bitwise(model):
    """Speculative ROLLBACK under rope fusion: rejected-draft slots
    included, pools bitwise vs the PR-13 path, outputs token-exact,
    fp and int8."""
    rng = np.random.RandomState(42)
    v = model.config.vocab_size
    p = rng.randint(0, v, (5,)).tolist()

    class GarbageDrafter:
        def sync(self, prompt_ids, output_ids):
            pass

        def propose(self, k):
            return [1] * k

    for kw in ({}, {"kv_dtype": "int8"}):
        def run(fused_rope):
            e = _engine(model, chunk_block=8, chunk_budget=32,
                        spec_k=3, drafter_factory=GarbageDrafter,
                        fused_rope=fused_rope, **kw)
            r = Request(p, max_new_tokens=6)
            e.add_request(r)
            while not r.done:
                e.step()
            state = _pool_state(e)
            spec = e.spec_stats()
            e.close()
            return r.output_ids, state, spec

        out_r, st_r, spec_r = run(True)
        out_f, st_f, spec_f = run(False)
        assert spec_r["proposed"] > 0
        assert spec_r["accepted"] < spec_r["proposed"]
        assert spec_r == spec_f
        assert out_r == out_f
        _assert_same_pools(st_r, st_f)


def test_fused_rope_cow_guard_still_fires(model):
    """Prefix-cache COW contract under rope fusion: the shared page
    goes private BEFORE the in-kernel write, the original's bytes stay
    frozen, outputs match an unshared run."""
    rng = np.random.RandomState(43)
    v = model.config.vocab_size
    p = rng.randint(0, v, (4,)).tolist()

    def run(pin):
        e = _engine(model, prefix_cache=False)
        assert e.fused_rope
        r = Request(p, max_new_tokens=8)
        e.add_request(r)
        frozen = None
        if pin:
            sid = r.seq_id
            page0 = e.alloc._tables[sid][0]
            e.alloc.incref(page0)
            frozen = [np.asarray(pl._data[page0]).copy()
                      for pl in e.k_pools + e.v_pools]
        while not r.done:
            e.step()
        if pin:
            assert e.alloc.cow_count >= 1
            for pl, want in zip(e.k_pools + e.v_pools, frozen):
                assert np.array_equal(np.asarray(pl._data[page0]), want)
            e.alloc.decref(page0)
        e.close()
        return r.output_ids

    assert run(pin=True) == run(pin=False)


def test_fused_rope_same_prompt_multi_chunk_replay(model):
    """Multi-chunk same-prompt replay under rope fusion: the same
    prompt pushed through tight budgets (several dispatches) and a
    wide budget (all chunks in ONE dispatch, later chunks attending
    K/V that earlier rows of the same grid roped AND wrote) must agree
    with each other and the reference."""
    rng = np.random.RandomState(44)
    v = model.config.vocab_size
    p = rng.randint(0, v, (41,)).tolist()
    want = _reference_continuation(model, p, 5)

    def run(**kw):
        e = _engine(model, **kw)
        assert e.fused_rope
        out = e.generate([p], max_new_tokens=5)[0]
        e.close()
        return out

    assert run(chunk_block=8, chunk_budget=16) == want
    assert run(chunk_block=8, chunk_budget=48) == want


@pytest.mark.slow
def test_fused_rope_mixed_workload_e2e(model):
    """Heavy rope-fused e2e (slow): decode-heavy batch + long prompts
    + speculation + int8, rope-fused vs PR-13 — token-exact, int8 page
    bytes bitwise, scales at the f32-ulp bar."""
    rng = np.random.RandomState(45)
    v = model.config.vocab_size
    prompts = [rng.randint(0, v, (n,)).tolist() for n in (3, 5, 37, 52)]

    def run(fused_rope):
        e = _engine(model, num_pages=128, chunk_block=8,
                    chunk_budget=16, spec_k=3, kv_dtype="int8",
                    fused_rope=fused_rope)
        reqs = [Request(p, max_new_tokens=12) for p in prompts]
        for r in reqs[:2]:
            e.add_request(r)
        e.decode_many(4)
        for r in reqs[2:]:
            e._admit(r)
        for _ in range(600):
            if all(r.done for r in reqs):
                break
            if not e.step():
                break
        outs = [r.output_ids for r in reqs]
        state = _pool_state(e)
        e.close()
        return outs, state

    out_r, st_r = run(True)
    out_f, st_f = run(False)
    assert out_r == out_f
    _assert_same_pools(st_r, st_f, scale_rtol=1e-6)
    assert all(len(o) == 12 for o in out_r)


def test_page_write_last_writer_wins(model):
    """Regression pin (satellite): a slot written TWICE in one
    `_page_write_q8` dispatch must land the LAST writer's int8 values
    AND its scale — XLA scatter's duplicate ordering is implementation-
    defined, so the op rewrites duplicates to the last value before
    scattering. `_page_write` pins the same rule."""
    import jax.numpy as jnp
    from paddle_tpu.inference.paged_cache import quantize_kv_int8
    from paddle_tpu.inference.serving import _page_write, _page_write_q8

    rng = np.random.RandomState(23)
    P, hk, page, d = 4, 2, 8, 16
    pages = jnp.zeros((P, hk, page, d), jnp.int8)
    scales = jnp.zeros((P, hk, page, 1), jnp.float32)
    new = jnp.asarray(rng.randn(5, hk, d), jnp.float32)
    # tokens 1 and 3 target the SAME slot (page 2, off 4); 3 must win
    pids = jnp.asarray(np.asarray([0, 2, 1, 2, 3], np.int32))
    offs = jnp.asarray(np.asarray([0, 4, 2, 4, 7], np.int32))
    p_out, s_out = _page_write_q8(pages, scales, new, pids, offs)
    p_out = np.asarray(p_out._data)
    s_out = np.asarray(s_out._data)
    want_q, want_s = quantize_kv_int8(new)
    assert np.array_equal(p_out[2, :, 4, :], np.asarray(want_q)[3])
    assert np.array_equal(s_out[2, :, 4, 0], np.asarray(want_s)[3])
    # float path: same last-writer rule
    fpages = jnp.zeros((P, hk, page, d), jnp.float32)
    f_out = np.asarray(_page_write(fpages, new, pids, offs)._data)
    assert np.array_equal(f_out[2, :, 4, :], np.asarray(new)[3])
    # non-duplicate slots unaffected
    assert np.array_equal(f_out[1, :, 2, :], np.asarray(new)[2])


@pytest.mark.slow
def test_fused_mixed_workload_e2e(model):
    """Heavy fused e2e (slow): decode-heavy batch + long prompts +
    speculation + int8, fused vs unfused — every request token-exact
    and pool bytes identical at the end."""
    rng = np.random.RandomState(24)
    v = model.config.vocab_size
    prompts = [rng.randint(0, v, (n,)).tolist() for n in (3, 5, 37, 52)]

    def run(fused):
        e = _engine(model, num_pages=128, chunk_block=8,
                    chunk_budget=16, spec_k=3, kv_dtype="int8",
                    fused_kv=fused)
        reqs = [Request(p, max_new_tokens=12) for p in prompts]
        for r in reqs[:2]:
            e.add_request(r)
        e.decode_many(4)
        for r in reqs[2:]:
            e._admit(r)
        for _ in range(600):
            if all(r.done for r in reqs):
                break
            if not e.step():
                break
        outs = [r.output_ids for r in reqs]
        state = _pool_state(e)
        e.close()
        return outs, state

    out_f, st_f = run(True)
    out_u, st_u = run(False)
    assert out_f == out_u                # int8+spec: fused == unfused
    # int8 page bytes bitwise; scale sidecars at f32-ulp tolerance
    # (see _assert_same_pools — accumulated cross-program fusion noise
    # over a long speculative run, not a write-path divergence)
    _assert_same_pools(st_f, st_u, scale_rtol=1e-6)
    assert all(len(o) == 12 for o in out_f)


@pytest.mark.slow
def test_mixed_workload_e2e_token_exact(model):
    """Acceptance e2e: a decode-heavy batch with long prompts admitted
    mid-stream, driven through mixed steps and decode scans, every
    request token-exact vs its standalone reference."""
    rng = np.random.RandomState(7)
    v = model.config.vocab_size
    engine = _engine(model, num_pages=128, chunk_block=8,
                     chunk_budget=16)
    decoders = [Request(rng.randint(0, v, (k,)).tolist(),
                        max_new_tokens=24) for k in (3, 5)]
    for r in decoders:
        engine.add_request(r)
    engine.decode_many(4)
    longs = [Request(rng.randint(0, v, (n,)).tolist(), max_new_tokens=8)
             for n in (37, 52)]
    for r in longs:
        engine._admit(r)
    reqs = decoders + longs
    for _ in range(600):
        if all(r.done for r in reqs):
            break
        if not engine.step():
            break
    for r in reqs:
        assert r.done and r.status == "completed", r.status
        want = _reference_continuation(model, list(r.prompt_ids),
                                       r.max_new_tokens)
        assert r.output_ids == want
    engine.close()
