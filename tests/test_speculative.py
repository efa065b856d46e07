"""Speculative decoding + int8 quantized KV pages.

The two ROADMAP-item-3 levers, pinned at every layer:

- drafter unit behavior (n-gram prompt-lookup proposals);
- `PageAllocator.rollback` (rejected draft pages return to the pool,
  refcount/double-free contracts intact);
- engine-level GREEDY TOKEN EXACTNESS: a speculative engine emits
  byte-for-byte what the non-speculative engine emits, whatever the
  drafter proposes (oracle drafts, garbage drafts, the real n-gram
  drafter) — speculation may only ever change dispatch counts;
- lifecycle mid-speculation: cancel / deadline / pool-pressure evict
  land at verify boundaries with every page released;
- int8 KV pages: deterministic engine outputs, attention-level parity
  vs float pages, prefix-cache hits on int8 pages token-exact, and
  `ensure_writable()` COW copying the scale sidecar with the page.
"""

import time

import numpy as np
import pytest

import paddle_tpu as paddle
from paddle_tpu.inference.paged_cache import PageAllocator
from paddle_tpu.inference.serving import LlamaServingEngine, Request
from paddle_tpu.inference.speculative import NGramDrafter
from paddle_tpu.models import LlamaForCausalLM, tiny_llama_config


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
    kw.setdefault("chunk_block", 8)
    kw.setdefault("chunk_budget", 16)
    return LlamaServingEngine(model, **kw)


# ---------------------------------------------------------------------
# drafter
# ---------------------------------------------------------------------
class TestNGramDrafter:
    def test_proposes_continuation_of_repeating_history(self):
        d = NGramDrafter(n=3)
        d.sync([1, 2, 3, 1, 2, 3, 1, 2], [])
        # history ends ...3, 1, 2 — the table says 3 follows (1, 2)
        assert d.propose(4) == [3, 1, 2, 3]

    def test_unseen_context_proposes_nothing(self):
        d = NGramDrafter(n=3)
        d.sync([1, 2, 3, 4, 5, 6, 7], [])
        assert d.propose(4) == []       # 6, 7 never seen before

    def test_longest_context_wins(self):
        d = NGramDrafter(n=2)
        # after (9, 1) comes 5; after a bare 1 comes (most recently) 7;
        # history ends (9, 1) so the 2-gram must beat the 1-gram
        d.sync([1, 7, 9, 1, 5, 1, 7, 2, 9, 1], [])
        assert d.propose(1) == [5]

    def test_sync_is_incremental_over_outputs(self):
        d = NGramDrafter(n=2)
        d.sync([4, 4], [])
        d.sync([4, 4], [4])
        d.sync([4, 4], [4, 4, 4])
        assert d.propose(3) == [4, 4, 4]

    def test_propose_caps_at_k(self):
        d = NGramDrafter(n=1)
        d.sync([2, 2, 2, 2, 2], [])
        assert d.propose(2) == [2, 2]


# ---------------------------------------------------------------------
# allocator rollback
# ---------------------------------------------------------------------
class TestRollback:
    def test_rollback_frees_tail_pages(self):
        alloc = PageAllocator(num_pages=16, page_size=4)
        alloc.admit(0, 10)                  # 3 pages
        free0 = alloc.free_pages
        alloc.extend(0, 6)                  # 16 tokens -> 4 pages
        assert alloc.free_pages == free0 - 1
        freed = alloc.rollback(0, 5)        # back to 11 tokens, 3 pages
        assert freed == 1
        assert alloc.context_len(0) == 11
        assert alloc.free_pages == free0
        assert len(alloc._tables[0]) == 3

    def test_rollback_within_page_frees_nothing(self):
        alloc = PageAllocator(num_pages=16, page_size=4)
        alloc.admit(0, 10)                  # 3 pages
        free0 = alloc.free_pages
        assert alloc.rollback(0, 0) == 0
        assert alloc.rollback(0, 1) == 0    # 9 tokens still need 3 pages
        assert alloc.context_len(0) == 9
        assert alloc.free_pages == free0

    def test_rollback_respects_shared_tail_refcount(self):
        alloc = PageAllocator(num_pages=16, page_size=4)
        alloc.admit(0, 4)
        alloc.extend(0, 4)                  # page 2 appended
        tail = alloc._tables[0][-1]
        alloc.incref(tail)                  # someone else pins it
        assert alloc.rollback(0, 4) == 0    # unpinned, NOT freed
        assert alloc.page_ref(tail) == 1
        assert alloc.double_free_count == 0

    def test_rollback_then_release_keeps_double_free_contract(self):
        alloc = PageAllocator(num_pages=16, page_size=4)
        alloc.admit(0, 6)
        alloc.extend(0, 8)
        alloc.rollback(0, 8)
        alloc.release(0)
        assert alloc.free_pages == 16
        assert alloc.double_free_count == 0
        with pytest.warns(RuntimeWarning):
            alloc.release(0)                # idempotent, counted
        assert alloc.double_free_count == 1

    def test_rollback_past_length_is_typed(self):
        alloc = PageAllocator(num_pages=8, page_size=4)
        alloc.admit(0, 4)
        with pytest.raises(ValueError):
            alloc.rollback(0, 5)


# ---------------------------------------------------------------------
# oracle / adversarial drafters: deterministic accept + rollback paths
# ---------------------------------------------------------------------
class _OracleDrafter:
    """Proposes exactly the reference continuation — forces full
    acceptance so the accept path is exercised deterministically."""

    def __init__(self, want):
        self.want = want
        self._n = 0

    def sync(self, prompt_ids, output_ids):
        self._n = len(output_ids)

    def propose(self, k):
        return self.want[self._n:self._n + int(k)]


class _GarbageDrafter:
    """Proposes tokens that can never match (vocab-1 repeated, which
    the reference run below never emits) — forces full rejection and
    the rollback path on every step."""

    def __init__(self, bad):
        self.bad = bad

    def sync(self, prompt_ids, output_ids):
        pass

    def propose(self, k):
        return [self.bad] * int(k)


class TestSpeculativeEngine:
    def test_ngram_spec_token_exact_random_prompts(self, model):
        rng = np.random.RandomState(0)
        v = model.config.vocab_size
        prompts = [rng.randint(0, v, (n,)).tolist() for n in (5, 12)]
        want = [_reference_continuation(model, p, 10) for p in prompts]
        engine = _engine(model, spec_k=3)
        assert engine.generate(prompts, max_new_tokens=10) == want
        assert engine.spec_stats()["proposed"] >= 0   # may be 0 early
        assert engine.alloc.double_free_count == 0
        engine.close()

    def test_oracle_drafts_accepted_and_fewer_dispatches(self, model):
        rng = np.random.RandomState(1)
        v = model.config.vocab_size
        p = rng.randint(0, v, (6,)).tolist()
        want = _reference_continuation(model, p, 16)
        base = _engine(model, num_pages=96, max_pages_per_seq=8)
        base.generate([p], max_new_tokens=16)
        d_base = base._dispatch_count
        base.close()
        engine = _engine(model, num_pages=96, max_pages_per_seq=8,
                         spec_k=4,
                         drafter_factory=lambda: _OracleDrafter(want))
        assert engine.generate([p], max_new_tokens=16) == [want]
        s = engine.spec_stats()
        assert s["accepted"] == s["proposed"] > 0
        # every verify commits k+1 tokens -> far fewer dispatches
        assert engine._dispatch_count < d_base
        assert engine.alloc.free_pages == engine.alloc.num_pages
        engine.close()

    def test_garbage_drafts_rolled_back_token_exact(self, model):
        rng = np.random.RandomState(2)
        v = model.config.vocab_size
        p = rng.randint(1, v - 1, (6,)).tolist()
        want = _reference_continuation(model, p, 12)
        bad = (want[0] + 1) % v     # provably wrong for the first draft
        engine = _engine(model, spec_k=3,
                         drafter_factory=lambda: _GarbageDrafter(bad))
        got = engine.generate([p], max_new_tokens=12)
        # exactness even under 100%-wrong drafts; every rejected draft
        # page was rolled back (pool fully restored, no double frees)
        assert got == [want]
        s = engine.spec_stats()
        assert s["proposed"] > 0
        assert engine.alloc.free_pages == engine.alloc.num_pages
        assert engine.alloc.double_free_count == 0
        engine.close()

    def test_spec_respects_max_new_tokens_exactly(self, model):
        rng = np.random.RandomState(3)
        v = model.config.vocab_size
        p = rng.randint(0, v, (4,)).tolist()
        want = _reference_continuation(model, p, 5)
        engine = _engine(
            model, spec_k=4,
            drafter_factory=lambda: _OracleDrafter(want + want))
        r = Request(p, max_new_tokens=5)
        engine.add_request(r)
        while not r.done:
            engine.step()
        assert r.output_ids == want         # never overshoots
        assert r.status == "completed"
        engine.close()

    def test_speculation_never_starves_prefill(self, model):
        """Under sustained full acceptance (oracle drafts), a prompt
        admitted mid-stream still makes prefill progress every step —
        a chunk_block of budget stays reserved for prefill, so the
        chunked-prefill TTFT invariant survives speculation."""
        rng = np.random.RandomState(9)
        v = model.config.vocab_size
        p = rng.randint(0, v, (4,)).tolist()
        want = _reference_continuation(model, p, 200)
        engine = LlamaServingEngine(
            model, max_batch=2, page_size=8, num_pages=64,
            max_pages_per_seq=16, chunk_block=8, chunk_budget=16,
            prefix_cache=False, spec_k=7,
            drafter_factory=lambda: _OracleDrafter(want))
        d = Request(p, max_new_tokens=200)
        engine.add_request(d)
        engine.step()
        assert engine.spec_stats()["accepted"] > 0    # speculating
        long = Request(rng.randint(0, v, (40,)).tolist(),
                       max_new_tokens=2)
        engine._admit(long)
        steps = 0
        while long._prefilled < len(long.prompt_ids):
            before = long._prefilled
            engine.step()
            steps += 1
            assert long._prefilled > before, \
                "speculating decoder starved the prefill queue"
            assert steps < 50
        engine.close()

    def test_spec_state_cleaned_on_retire(self, model):
        engine = _engine(model, spec_k=2)
        r = Request([1, 2, 3], max_new_tokens=4)
        engine.add_request(r)
        while not r.done:
            engine.step()
        assert engine._spec_state == {}
        engine.close()


# ---------------------------------------------------------------------
# lifecycle mid-speculation
# ---------------------------------------------------------------------
class TestSpecLifecycle:
    def test_cancel_mid_speculation_releases_pages(self, model):
        engine = _engine(model, spec_k=3)
        free0 = engine.alloc.free_pages
        r = Request([1, 2, 3, 4], max_new_tokens=10000)
        engine.add_request(r)
        for _ in range(3):
            engine.step()                   # speculating
        assert engine.cancel(r) is True
        assert r.status == "cancelled"
        assert engine.alloc.free_pages == free0
        # engine healthy and exact afterwards
        p = [5, 6, 7]
        assert engine.generate([p], max_new_tokens=4)[0] \
            == _reference_continuation(model, p, 4)
        engine.close()

    def test_deadline_mid_speculation_typed_and_released(self, model):
        from paddle_tpu.inference.serving import DeadlineExceeded

        engine = _engine(model, spec_k=3)
        free0 = engine.alloc.free_pages
        r = Request([1, 2, 3], max_new_tokens=10000, deadline=0.03)
        engine.add_request(r)
        t0 = time.perf_counter()
        while not r.done and time.perf_counter() - t0 < 10.0:
            engine.step()
            time.sleep(0.005)
        assert r.done and r.status == "deadline_exceeded"
        assert isinstance(r.error, DeadlineExceeded)
        assert engine.alloc.free_pages == free0
        engine.close()

    def test_pressure_evict_during_speculation_recovers(self, model):
        engine = LlamaServingEngine(model, max_batch=2, page_size=8,
                                    num_pages=8, chunk_block=4,
                                    chunk_budget=8, spec_k=3)
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
        assert engine.alloc.free_pages == free0
        assert engine.alloc.double_free_count == 0
        engine.close()


# ---------------------------------------------------------------------
# int8 KV pages
# ---------------------------------------------------------------------
class TestInt8KV:
    def test_quantized_attention_parity_vs_float_pages(self):
        """Attention over int8 pages + scale sidecars matches float
        pages within int8 tolerance: the kv_int8_parity contract, as
        the XLA reference reads int8 pools (tests/test_ragged_attention
        holds the int8 Pallas program to this reference tightly)."""
        import jax.numpy as jnp
        from paddle_tpu.inference.paged_cache import quantize_kv_int8
        from paddle_tpu.ops import ragged_paged_attention as RPA

        rng = np.random.RandomState(0)
        rows, qb, h, hk, d, page, w = 3, 8, 4, 2, 32, 8, 4
        num_pages = rows * w + 2
        q = jnp.asarray(rng.randn(rows, qb, h, d), jnp.float32)
        kf = jnp.asarray(rng.randn(num_pages, hk, page, d), jnp.float32)
        vf = jnp.asarray(rng.randn(num_pages, hk, page, d), jnp.float32)
        kq, ks = quantize_kv_int8(kf)
        vq, vs = quantize_kv_int8(vf)
        ks = ks[..., None].astype(jnp.float32)
        vs = vs[..., None].astype(jnp.float32)
        tables = jnp.asarray(
            rng.permutation(num_pages)[:rows * w].reshape(rows, w),
            jnp.int32)
        q_lens = jnp.asarray([1, 5, 8], jnp.int32)
        kv = jnp.asarray([17, 9, 30], jnp.int32)
        q_starts = kv - q_lens
        ref = RPA.ragged_paged_attention_xla(
            q, kf, vf, tables, kv, q_starts, q_lens)
        got_xla = RPA.ragged_paged_attention_xla(
            q, kq, vq, tables, kv, q_starts, q_lens,
            k_scale=ks, v_scale=vs)
        scale = float(jnp.max(jnp.abs(ref)))
        err = float(jnp.max(jnp.abs(got_xla - ref)))
        assert 0.0 < err < 0.05 * max(scale, 1.0), err

    def test_int8_engine_deterministic_and_spec_exact(self, model):
        """int8 outputs are deterministic across engines, and a
        speculative int8 engine reproduces the plain int8 engine
        token-for-token (greedy exactness is dtype-independent)."""
        rng = np.random.RandomState(4)
        v = model.config.vocab_size
        prompts = [rng.randint(0, v, (n,)).tolist() for n in (6, 20)]
        e1 = _engine(model, kv_dtype="int8")
        got = e1.generate(prompts, max_new_tokens=10)
        e1.close()
        e2 = _engine(model, kv_dtype="int8")
        assert e2.generate(prompts, max_new_tokens=10) == got
        e2.close()
        e3 = _engine(model, kv_dtype="int8", spec_k=3)
        assert e3.generate(prompts, max_new_tokens=10) == got
        assert e3.alloc.double_free_count == 0
        e3.close()

    def test_int8_prefix_cache_hit_token_exact(self, model):
        """Prefix-cache hits on int8 pages are token-exact: the shared
        pages carry their scale sidecars, so a warm admission decodes
        exactly what a cold admission of the same prompt decodes."""
        rng = np.random.RandomState(5)
        v = model.config.vocab_size
        prefix = rng.randint(0, v, (16,)).tolist()      # 2 full pages
        sfx = rng.randint(0, v, (4,)).tolist()
        engine = _engine(model, kv_dtype="int8")
        filler = Request(prefix + rng.randint(0, v, (3,)).tolist(),
                         max_new_tokens=2)
        engine.add_request(filler)
        while not filler.done:
            engine.step()
        warm = Request(prefix + sfx, max_new_tokens=6)
        engine.add_request(warm)
        assert warm._cached_tokens == 16                # real cache hit
        while not warm.done:
            engine.step()
        engine.close()
        cold_engine = _engine(model, kv_dtype="int8", prefix_cache=False)
        cold = cold_engine.generate([prefix + sfx], max_new_tokens=6)
        cold_engine.close()
        assert warm.output_ids == cold[0]

    def test_cow_copies_scale_sidecar_with_page(self, model):
        """Satellite contract: ensure_writable() COW must copy the
        scale sidecar with the page — a live int8 sequence whose page
        is pinned (shared) decodes exactly like an unpinned one."""
        import jax.numpy as jnp

        rng = np.random.RandomState(6)
        v = model.config.vocab_size
        p = rng.randint(0, v, (4,)).tolist()

        def run(pin):
            engine = _engine(model, kv_dtype="int8", prefix_cache=False)
            r = Request(p, max_new_tokens=8)
            engine.add_request(r)
            if pin:
                sid = r.seq_id
                page0 = engine.alloc._tables[sid][0]
                engine.alloc.incref(page0)      # simulate a shared pin
                # device-level check rides the first COW: old page and
                # copy must match in BOTH pools and sidecars
                cp = engine.alloc.ensure_writable(
                    sid, engine.alloc.context_len(sid) - 1)
                if cp is not None:
                    old, new = cp
                    engine._copy_page(old, new)
                    for li in range(len(engine.k_pools)):
                        assert bool(jnp.all(
                            engine.k_pools[li]._data[old]
                            == engine.k_pools[li]._data[new]))
                        assert bool(jnp.all(
                            engine.k_scales[li]._data[old]
                            == engine.k_scales[li]._data[new]))
            while not r.done:
                engine.step()
            if pin:
                assert engine.alloc.cow_count >= 1
                engine.alloc.decref(page0)
            engine.close()
            return r.output_ids

        assert run(pin=True) == run(pin=False)

    def test_kv_dtype_env_knob(self, model, monkeypatch):
        monkeypatch.setenv("PADDLE_TPU_KV_DTYPE", "int8")
        engine = _engine(model)
        assert engine.kv_quant
        assert engine.k_pools[0]._data.dtype == np.int8
        engine.close()
        monkeypatch.setenv("PADDLE_TPU_KV_DTYPE", "fp8")
        with pytest.raises(ValueError, match="kv_dtype"):
            _engine(model)

    def test_int8_halves_page_bytes(self, model):
        fp = _engine(model)
        q8 = _engine(model, kv_dtype="int8")
        # f32 CPU pools: int8 + f32 sidecar is well under half
        assert q8.kv_bytes_per_token * 2 <= fp.kv_bytes_per_token
        fp.close()
        q8.close()


def test_long_step_driven_decode_no_output_aliasing(model):
    """Regression: the mixed program's next-token output must never
    share an aval with a DONATED input. An [1, T] int64 output exactly
    matched the donated ``tokens`` input, and under the metrics-on AOT
    path XLA aliased the output into a buffer zero-copy-backed by the
    caller's host array — a timing-dependent use-after-free that
    surfaced as out-of-vocab garbage tokens deep into step-driven
    decode runs. The output is 1-D now ([T] speculative, [R] plain —
    no 1-D int64 input exists); this drives the original repro
    geometry long enough to have caught it, on both variants."""
    rng = np.random.RandomState(0)
    v = model.config.vocab_size
    p = rng.randint(0, v, (12,)).tolist()
    prompts = [p, p[::-1]]
    want = [_reference_continuation(model, pp, 96) for pp in prompts]
    for spec_k in (0, 3):
        engine = LlamaServingEngine(model, max_batch=2, page_size=16,
                                    num_pages=48, max_pages_per_seq=8,
                                    chunk_block=16, chunk_budget=16,
                                    prefix_cache=False, spec_k=spec_k)
        reqs = [Request(pp, max_new_tokens=96) for pp in prompts]
        for r in reqs:
            engine.add_request(r)
        while not all(r.done for r in reqs):
            engine.step()
        for r, w in zip(reqs, want):
            assert all(0 <= t < v for t in r.output_ids)
            assert r.output_ids == w
        engine.close()


# ---------------------------------------------------------------------
# acceptance e2e
# ---------------------------------------------------------------------
@pytest.mark.slow
def test_mixed_spec_workload_e2e_token_exact(model):
    """Acceptance e2e: a speculative int8-free engine under the PR-8
    mixed workload — decode-heavy batch, long prompts admitted
    mid-stream, deadline expiry mid-run — every surviving request
    token-exact vs its standalone reference and the pool fully
    restored."""
    rng = np.random.RandomState(7)
    v = model.config.vocab_size
    # prefix_cache off so the end-state pool assertion is strict (the
    # cache legitimately pins completed prompts' pages otherwise)
    engine = _engine(model, max_batch=6, num_pages=128, spec_k=3,
                     prefix_cache=False)
    free0 = engine.alloc.free_pages
    decoders = [Request(rng.randint(0, v, (k,)).tolist(),
                        max_new_tokens=24) for k in (3, 5)]
    for r in decoders:
        engine.add_request(r)
    engine.decode_many(4)
    longs = [Request(rng.randint(0, v, (n,)).tolist(), max_new_tokens=8)
             for n in (37, 52)]
    for r in longs:
        engine._admit(r)
    doomed = Request(rng.randint(0, v, (4,)).tolist(),
                     max_new_tokens=10000, deadline=0.15)
    engine._admit(doomed)
    reqs = decoders + longs + [doomed]
    for _ in range(600):
        if all(r.done for r in reqs):
            break
        if not engine.step():
            break
        time.sleep(0.001)
    for r in decoders + longs:
        assert r.done and r.status == "completed", r.status
        want = _reference_continuation(model, list(r.prompt_ids),
                                       r.max_new_tokens)
        assert r.output_ids == want
    assert doomed.done and doomed.status == "deadline_exceeded"
    assert engine.alloc.free_pages == free0
    assert engine.alloc.double_free_count == 0
    engine.close()
