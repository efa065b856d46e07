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
from paddle_tpu.incubate.nn import functional as FI
from paddle_tpu.inference.paged_cache import quantize_kv_int8
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
# rope + page write + attention are ONE Pallas program a pool dtype.
# There is no second program to be bitwise with, so the oracle of what
# the engine leaves in its pools is the model's own arithmetic: the
# post-rope K and the V its plain (cache-free) forward computes.
# ----------------------------------------------------------------------
POOLS = pytest.mark.parametrize("kv_dtype", [None, "int8"],
                                ids=["float", "int8"])


def _model_kv(model, ids):
    """``[(K, V)]`` a layer, ``[S, Hk, D]`` each: the post-rope K and
    the V of the model's plain forward over ``ids``."""
    m = model.model
    s = len(ids)
    x = m.embed_tokens(paddle.to_tensor(np.asarray([ids], np.int64)))
    pos = paddle.to_tensor(np.arange(s, dtype=np.int64)[None])
    out = []
    for layer in m.layers:
        att = layer.self_attn
        h = layer.input_layernorm(x)
        q, k, v = (proj(h).reshape([1, s, heads, att.head_dim])
                   for proj, heads in ((att.q_proj, att.num_heads),
                                       (att.k_proj, att.num_kv_heads),
                                       (att.v_proj, att.num_kv_heads)))
        _, k, _ = FI.fused_rotary_position_embedding(
            q, k, v, position_ids=pos,
            rotary_emb_base=att.config.rope_theta)
        out.append((np.asarray(k._data)[0], np.asarray(v._data)[0]))
        x = layer(x, pos)
    return out


def _committed(engine, r):
    """What the pools hold of a live request: ``(ids, [(K, V, k_scale,
    v_scale)] a layer)`` at its committed positions (prompt and outputs
    but the newest, which no step has fed yet), read through its table;
    the allocator holds exactly the pages those positions need."""
    sid, page = r.seq_id, engine.page_size
    n = engine.alloc.context_len(sid)
    ids = (list(r.prompt_ids) + list(r.output_ids))[:n]
    assert n == len(r.prompt_ids) + len(r.output_ids) - 1
    tb = list(engine.alloc._tables[sid])
    assert len(tb) == -(-n // page)

    def rows(pool):
        a = np.asarray(pool._data)
        return np.stack([a[tb[i // page], :, i % page] for i in range(n)])

    layers = []
    for li in range(len(engine.k_pools)):
        sc = [rows(p[li])[..., 0] for p in (engine.k_scales,
                                            engine.v_scales)] \
            if engine.kv_quant else [None, None]
        layers.append((rows(engine.k_pools[li]), rows(engine.v_pools[li]),
                       *sc))
    return ids, layers


def _assert_holds_the_models_kv(model, ids, layers):
    """Float pools hold the model's K/V to float rounding (the step
    program is one jitted graph and the plain forward eager ops: XLA
    contracts and orders their f32 arithmetic differently, and from
    layer 1 on the K/V follow an attention output accumulated a block
    of pages at a time). Int8 pools hold `quantize_kv_int8` of those
    rows: in layer 0, which reads no cache, the same bytes but for a
    value on a rounding tie (one step) under the same scale; deeper
    layers follow attention over int8 reads, so their scales may move
    by a percent and a value by one step."""
    for li, ((wk, wv), (gk, gv, gks, gvs)) in enumerate(
            zip(_model_kv(model, ids), layers)):
        for want, got, scale in ((wk, gk, gks), (wv, gv, gvs)):
            amax = float(np.abs(want).max())
            if scale is None:
                assert got.dtype == np.float32
                assert np.abs(got - want).max() < 1e-5 * max(amax, 1.0)
                continue
            wq, ws = map(np.asarray, quantize_kv_int8(want))
            assert got.dtype == np.int8
            assert np.abs(got.astype(int) - wq.astype(int)).max() <= 1
            assert np.abs(scale / ws - 1).max() < (2e-2 if li else 1e-6)
            assert np.abs(got * scale[..., None] - want).max() \
                < 0.05 * amax


@POOLS
def test_pools_hold_the_models_kv(model, kv_dtype):
    """Multi-chunk prompts beside decode steps, the SAME-prompt replay
    inside one dispatch included (the 30-token prompt spans 4 chunk
    rows of a single 32-token budget): float tokens equal the model's
    own continuation, and at every committed position of every
    sequence the pools hold the model's K/V."""
    rng = np.random.RandomState(40)
    v = model.config.vocab_size
    prompts = [rng.randint(0, v, (n,)).tolist() for n in (30, 5, 12)]
    e = _engine(model, chunk_block=8, chunk_budget=32, kv_dtype=kv_dtype)
    reqs = [Request(p, max_new_tokens=6) for p in prompts]
    seen = {}

    def look():
        for i, r in enumerate(reqs):
            if r.seq_id is not None and not r.done:
                seen[i] = _committed(e, r)

    for r in reqs:
        e.add_request(r)
        look()
    while not all(r.done for r in reqs):
        e.step()
        look()
    if kv_dtype is None:
        # int8 reads legitimately shift greedy tokens off the float
        # reference; its pools are held to its own tokens below
        assert [r.output_ids for r in reqs] == [
            _reference_continuation(model, p, 6) for p in prompts]
    for i, r in enumerate(reqs):
        ids, layers = seen[i]
        # the last sight of a request: all but its final token fed
        assert len(ids) == len(r.prompt_ids) + 4
        _assert_holds_the_models_kv(model, ids, layers)
    e.close()


@POOLS
def test_spec_rollback_leaves_the_models_kv(model, kv_dtype):
    """A garbage drafter: every dispatch writes K/V of rejected drafts
    past the committed position and rolls their pages back. After each
    step the committed positions hold the model's K/V, the allocator
    holds ``ceil(len / page)`` pages, and float tokens equal the
    model's own continuation."""
    rng = np.random.RandomState(42)
    v = model.config.vocab_size
    p = rng.randint(0, v, (5,)).tolist()

    class GarbageDrafter:
        """Proposes fixed wrong tokens: verification rejects them,
        exercising rollback every dispatch."""
        def sync(self, prompt_ids, output_ids):
            pass

        def propose(self, k):
            return [1] * k

    e = _engine(model, chunk_block=8, chunk_budget=32, spec_k=3,
                drafter_factory=GarbageDrafter, kv_dtype=kv_dtype)
    r = Request(p, max_new_tokens=6)
    e.add_request(r)
    sights = 0
    while not r.done:
        e.step()
        if not r.done:
            # drafts crossed into the next page and were rolled back
            _assert_holds_the_models_kv(model, *_committed(e, r))
            sights += 1
    spec = e.spec_stats()
    assert sights >= 3
    assert spec["proposed"] > 0                 # speculation really ran
    assert spec["accepted"] < spec["proposed"]  # and rolled back
    if kv_dtype is None:
        assert r.output_ids == _reference_continuation(model, p, 6)
    assert len(r.output_ids) == 6
    e.close()


@POOLS
def test_cow_guard_still_fires(model, kv_dtype):
    """Prefix-cache COW contract with the page write inside the kernel:
    a shared page is made private BEFORE the in-kernel write lands, the
    shared original's bytes stay untouched, and outputs match an
    unshared run."""
    rng = np.random.RandomState(22)
    v = model.config.vocab_size
    p = rng.randint(0, v, (4,)).tolist()

    def run(pin):
        e = _engine(model, prefix_cache=False, kv_dtype=kv_dtype)

        def pools():             # a dispatch hands the engine new ones
            return e.k_pools + e.v_pools + e.k_scales + e.v_scales

        assert len(pools()) == (8 if kv_dtype else 4)
        r = Request(p, max_new_tokens=8)
        e.add_request(r)
        frozen = None
        if pin:
            sid = r.seq_id
            page0 = e.alloc._tables[sid][0]
            e.alloc.incref(page0)            # simulate another owner
            frozen = [np.asarray(pl._data[page0]).copy() for pl in pools()]
        while not r.done:
            e.step()
        if pin:
            assert e.alloc.cow_count >= 1    # guard fired pre-write
            for pl, want in zip(pools(), frozen):
                assert np.array_equal(np.asarray(pl._data[page0]), want)
            e.alloc.decref(page0)
        e.close()
        return r.output_ids

    assert run(pin=True) == run(pin=False)


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


def test_odd_head_dim_is_refused_by_name():
    """The one serving program rotates heads in halves: a Llama-kind
    layer whose ``head_dim`` it cannot rotate is refused at
    construction, never served by another path."""
    from paddle_tpu.inference.serving import UnsupportedServingFeature

    m = LlamaForCausalLM(tiny_llama_config(hidden_size=20,
                                           intermediate_size=32,
                                           num_hidden_layers=1))
    assert m.config.head_dim == 5
    with pytest.raises(UnsupportedServingFeature,
                       match="LlamaDecoderLayer.*head_dim=5"):
        _engine(m)


def test_fused_rope_decode_scan_matches_reference(model):
    """The decode scan carry: a long scanned decode run (decode_many ->
    lax.scan ticks, per-tick rope tables and write positions from the
    length carry) stays token-exact vs the reference."""
    rng = np.random.RandomState(41)
    v = model.config.vocab_size
    p = rng.randint(0, v, (5,)).tolist()
    e = _engine(model, decode_ticks=8)
    r = Request(p, max_new_tokens=20)
    e.add_request(r)
    e.decode_many(20)
    assert list(r.output_ids) == _reference_continuation(model, p, 20)
    e.close()


def test_fused_rope_same_prompt_multi_chunk_replay(model):
    """Multi-chunk same-prompt replay: the same prompt pushed through
    tight budgets (several dispatches) and a wide budget (all chunks in
    ONE dispatch, later chunks attending K/V that earlier rows of the
    same grid roped AND wrote) must agree with each other and the
    reference."""
    rng = np.random.RandomState(44)
    v = model.config.vocab_size
    p = rng.randint(0, v, (41,)).tolist()
    want = _reference_continuation(model, p, 5)

    def run(**kw):
        e = _engine(model, **kw)
        out = e.generate([p], max_new_tokens=5)[0]
        e.close()
        return out

    assert run(chunk_block=8, chunk_budget=16) == want
    assert run(chunk_block=8, chunk_budget=48) == want


@pytest.mark.slow
def test_fused_rope_mixed_workload_e2e(model):
    """Heavy e2e (slow): decode-heavy batch + long prompts + int8 pages,
    with speculation and without: token-exact (int8 engines are held
    to int8 engines, never to float ones)."""
    rng = np.random.RandomState(45)
    v = model.config.vocab_size
    prompts = [rng.randint(0, v, (n,)).tolist() for n in (3, 5, 37, 52)]

    def run(spec_k):
        e = _engine(model, num_pages=128, chunk_block=8,
                    chunk_budget=16, spec_k=spec_k, kv_dtype="int8")
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
        e.close()
        return outs

    out_s = run(3)
    assert out_s == run(0)
    assert all(len(o) == 12 for o in out_s)


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
