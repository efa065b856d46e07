"""Host-DRAM KV page tiering: pause/resume instead of evict.

The contract under test (ISSUE 19): when the degradation ladder would
destroy a live sequence's K/V, the engine instead D2H-copies its pages
into a bounded host pool and parks the request ``paused``; resume is
the inverse H2D restore into freshly admitted pages, and the resumed
request's remaining tokens are BITWISE what an uninterrupted run
produces. Every tier failure is typed and degrades to the pre-tier
behavior (evict -> requeue), so under injected copy chaos no request
is ever silently lost and no page or host byte ever leaks.

Compiled dispatches ride the wedge-guard budget in conftest — this
module builds several engine variants (fp/int8 x spec on/off).
"""

import json
import os
import signal
import threading
import time

import numpy as np
import pytest

import paddle_tpu as paddle
from paddle_tpu.inference.kv_tier import (
    KvPageTier, TierCapacityError, TierCorruptError, TierError,
    TierExportError, TierRestoreError)
from paddle_tpu.inference.paged_cache import PageAllocator
from paddle_tpu.inference.serving import (
    AdmissionError, DeadlineExceeded, LlamaServingEngine, Request)
from paddle_tpu.models import LlamaForCausalLM, tiny_llama_config
from paddle_tpu.observability import metrics as om
from paddle_tpu.testing import faults


@pytest.fixture(scope="module")
def model():
    paddle.seed(0)
    m = LlamaForCausalLM(tiny_llama_config())
    m.eval()
    return m


@pytest.fixture()
def clean_faults():
    faults.reset()
    yield
    os.environ.pop(faults.PLAN_ENV, None)
    faults.reset()


def _labeled(counter, *labels):
    return 0.0 if counter is om.NULL else counter.labels(*labels).value


def _value(counter):
    return 0.0 if counter is om.NULL else counter.value


def _drive(engine, reqs, max_steps=1500):
    """Client loop: admit with retry (AdmissionError = backpressure),
    step until every request is terminal."""
    pending = list(reqs)
    steps = 0
    while any(not r.done for r in reqs) and steps < max_steps:
        for r in list(pending):
            try:
                engine.add_request(r)
                pending.remove(r)
            except AdmissionError:
                pass
        engine.step()
        steps += 1
    assert all(r.done for r in reqs), (
        f"stuck after {steps} steps: "
        f"{[(r.status, len(r.output_ids)) for r in reqs]}")
    return steps


def _complete(engine, req):
    engine.add_request(req)
    n = 0
    while not req.done and n < 1500:
        engine.step()
        n += 1
    assert req.done, req.status
    return req


# ---------------------------------------------------------------------
# Allocator tier APIs (no model)
# ---------------------------------------------------------------------
class TestAllocatorTierApi:
    def test_export_table_snapshot(self):
        a = PageAllocator(num_pages=8, page_size=4)
        a.admit(1, 6)
        table, n = a.export_table(1)
        assert n == 6 and len(table) == 2
        # a snapshot, not a live view
        table.append(99)
        assert len(a._tables[1]) == 2

    def test_export_table_unknown_seq(self):
        a = PageAllocator(num_pages=8, page_size=4)
        with pytest.raises(KeyError):
            a.export_table(7)

    def test_import_table_exclusive_pages(self):
        a = PageAllocator(num_pages=8, page_size=4)
        free0 = a.free_pages
        a.import_table(3, 6)
        assert a._lens[3] == 6
        assert len(a._tables[3]) == 2
        assert a.free_pages == free0 - 2
        # restored pages must be exclusively owned: the H2D scatter
        # bypasses ensure_writable, so a shared page would be torn
        for p in a._tables[3]:
            assert a._refs[p] == 1
        a.release(3)
        assert a.free_pages == free0

    def test_take_pages_atomic(self):
        a = PageAllocator(num_pages=6, page_size=4)
        free0 = a.free_pages
        got = a.take_pages(2)
        assert len(got) == 2 and a.free_pages == free0 - 2
        with pytest.raises(MemoryError):
            a.take_pages(free0)         # more than remains
        assert a.free_pages == free0 - 2    # nothing half-taken
        for p in got:
            a.decref(p)     # take_pages hands out one ref per page
        assert a.free_pages == free0


# ---------------------------------------------------------------------
# Fault points (satellite: tier.d2h / tier.h2d registered + validated)
# ---------------------------------------------------------------------
class TestTierFaultPoints:
    def test_points_registered(self):
        assert "tier.d2h" in faults.PROCESS_POINTS
        assert "tier.h2d" in faults.PROCESS_POINTS

    def test_cookbook_plan_validates(self, clean_faults):
        # the documented slow-copy + torn-restore chaos plan parses
        plan = [{"point": "tier.d2h", "action": "sleep",
                 "seconds": 0.05, "count": 2},
                {"point": "tier.h2d", "action": "bitflip", "count": 1}]
        faults.FaultPlan(plan)          # no raise
        os.environ[faults.PLAN_ENV] = json.dumps(plan)
        faults.reset()
        assert faults.plan() is not None

    def test_unknown_point_rejected(self):
        with pytest.raises(ValueError, match="tier"):
            faults.FaultPlan([{"point": "tier.dh2", "action": "raise"}])

    def test_fire_copy_bitflip_returns_torn(self, clean_faults):
        os.environ[faults.PLAN_ENV] = json.dumps(
            [{"point": "tier.h2d", "action": "bitflip", "count": 1}])
        faults.reset()
        # bitflip on a copy point is returned to the CALLER as a torn
        # flag (the buffer is in memory, not a file) — and the count
        # is consumed
        assert faults.fire_copy("tier.h2d") is True
        assert faults.fire_copy("tier.h2d") is False

    def test_fire_copy_raise_and_path_scope(self, clean_faults):
        os.environ[faults.PLAN_ENV] = json.dumps(
            [{"point": "tier.d2h", "action": "raise", "exc": "OSError",
              "path": "seq"}])
        faults.reset()
        # scoped to sequence copies: prefix demotions don't trip it
        assert faults.fire_copy("tier.d2h", path="prefix") is False
        with pytest.raises(OSError):
            faults.fire_copy("tier.d2h", path="seq")


# ---------------------------------------------------------------------
# KvPageTier unit tests (raw jax pools, no engine)
# ---------------------------------------------------------------------
def _pools(num_pages=4, page=2, d=3, seed=0):
    import jax.numpy as jnp
    rng = np.random.default_rng(seed)
    mk = lambda: jnp.asarray(
        rng.standard_normal((num_pages, page, d)).astype(np.float32))
    return [mk()], [mk()]


class TestKvPageTierUnit:
    def test_export_restore_roundtrip(self, clean_faults):
        import jax.numpy as jnp
        k, v = _pools()
        t = KvPageTier(max_bytes=1 << 20, prefetch=False)
        key = t.export_seq(k, v, None, None, [1, 3], 4)
        assert t.pages == 2 and t.bytes > 0
        assert t.seq_tokens(key) == 4
        zk = [jnp.zeros_like(k[0])]
        zv = [jnp.zeros_like(v[0])]
        nk, nv, _, _ = t.restore_seq(key, zk, zv, None, None, [0, 2])
        np.testing.assert_array_equal(
            np.asarray(nk[0][0]), np.asarray(k[0][1]))
        np.testing.assert_array_equal(
            np.asarray(nv[0][2]), np.asarray(v[0][3]))
        assert t.bytes == 0 and t.pages == 0
        assert t.stats()["exports"] == 1
        assert t.stats()["restores"] == 1

    def test_free_idempotent(self, clean_faults):
        k, v = _pools()
        t = KvPageTier(max_bytes=1 << 20, prefetch=False)
        key = t.export_seq(k, v, None, None, [0], 2)
        assert t.free(key) is True
        assert t.free(key) is False
        assert t.bytes == 0

    def test_torn_d2h_caught_at_restore(self, clean_faults):
        # the CRC commits to SOURCE bytes before the injected tear, so
        # a torn D2H is caught by the restore-side verify
        os.environ[faults.PLAN_ENV] = json.dumps(
            [{"point": "tier.d2h", "action": "bitflip", "count": 1}])
        faults.reset()
        k, v = _pools()
        t = KvPageTier(max_bytes=1 << 20, prefetch=False)
        key = t.export_seq(k, v, None, None, [0, 1], 3)
        with pytest.raises(TierCorruptError):
            t.restore_seq(key, k, v, None, None, [2, 3])
        # the corrupt host copy is freed, never retried
        assert t.bytes == 0 and t.stats()["crc_failures"] == 1

    def test_failed_h2d_is_typed_and_freed(self, clean_faults):
        os.environ[faults.PLAN_ENV] = json.dumps(
            [{"point": "tier.h2d", "action": "raise",
              "exc": "OSError", "count": 1}])
        faults.reset()
        k, v = _pools()
        t = KvPageTier(max_bytes=1 << 20, prefetch=False)
        key = t.export_seq(k, v, None, None, [0], 2)
        with pytest.raises(TierRestoreError):
            t.restore_seq(key, k, v, None, None, [1])
        assert t.bytes == 0 and t.stats()["restore_failures"] == 1

    def test_capacity_is_typed(self, clean_faults):
        k, v = _pools()
        t = KvPageTier(max_bytes=1, prefetch=False)
        with pytest.raises(TierCapacityError):
            t.export_seq(k, v, None, None, [0], 2)
        assert t.bytes == 0
        assert t.stats()["capacity_rejections"] == 1

    def test_error_hierarchy(self):
        for exc in (TierCapacityError, TierExportError,
                    TierRestoreError, TierCorruptError):
            assert issubclass(exc, TierError)
        assert issubclass(TierCorruptError, TierRestoreError)
        assert issubclass(TierError, RuntimeError)

    def test_prefix_page_roundtrip(self, clean_faults):
        import jax.numpy as jnp
        k, v = _pools()
        t = KvPageTier(max_bytes=1 << 20, prefetch=False)
        assert t.put_prefix("ab", None, k, v, None, None, 1)
        assert t.has_prefix("ab")
        assert t.prefix_parent("ab") is None
        zk = [jnp.zeros_like(k[0])]
        zv = [jnp.zeros_like(v[0])]
        nk, nv, _, _ = t.restore_prefix("ab", zk, zv, None, None, 3)
        np.testing.assert_array_equal(
            np.asarray(nk[0][3]), np.asarray(k[0][1]))
        # promotion consumes the host copy either way
        assert not t.has_prefix("ab")
        assert t.bytes == 0

    def test_prefix_never_evicts_seqs(self, clean_faults):
        k, v = _pools()
        nbytes = sum(a.nbytes for a in
                     (np.asarray(k[0][0]), np.asarray(v[0][0])))
        t = KvPageTier(max_bytes=nbytes, prefetch=False)
        key = t.export_seq(k, v, None, None, [0], 1)
        # pool is exactly full of a paused SEQUENCE: a prefix demotion
        # must be refused, not make room by dropping the sequence
        assert t.put_prefix("ab", None, k, v, None, None, 1) is False
        assert t.seq_tokens(key) == 1


# ---------------------------------------------------------------------
# Pause/resume token exactness (tentpole acceptance)
# ---------------------------------------------------------------------
class TestPauseResumeTokenExact:
    # tier-1 keeps the pairwise-covering corners (fp/no-spec and
    # int8/spec); the remaining two combos ride the slow tier
    @pytest.mark.parametrize("kv_dtype,spec_k", [
        (None, 0),
        pytest.param("int8", 0, marks=pytest.mark.slow),
        pytest.param(None, 3, marks=pytest.mark.slow),
        ("int8", 3)],
        ids=["fp", "int8", "fp-spec", "int8-spec"])
    def test_resumed_tokens_bitwise_equal(self, model, kv_dtype,
                                          spec_k, clean_faults):
        e = LlamaServingEngine(
            model, max_batch=2, page_size=8, num_pages=32,
            kv_tier=True, prefix_cache=False, kv_dtype=kv_dtype,
            spec_k=spec_k)
        try:
            prompt = list(np.arange(1, 12) % 50)
            free0 = e.alloc.free_pages
            r0 = _complete(e, Request(prompt, max_new_tokens=12))
            assert r0.status == "completed"

            r1 = Request(prompt, max_new_tokens=12)
            e.add_request(r1)
            while len(r1.output_ids) < 4:
                e.step()
            paused0 = _value(e._m["paused"])
            resumed0 = _value(e._m["resumed"])
            with e._lock:
                e._pause(r1)
            assert r1.status == "paused" and r1.seq_id is None
            assert e.tier.pages > 0 and e.tier.bytes > 0
            assert _value(e._m["paused"]) == paused0 + 1 \
                or e._m["paused"] is om.NULL
            assert _labeled(e._m["degraded"], "pause") >= 1 \
                or e._m["degraded"] is om.NULL

            while not r1.done:
                e.step()
            assert r1.status == "completed"
            # the tentpole contract: bitwise what the uninterrupted
            # run produced — mid-stream pause/resume is invisible
            assert list(r1.output_ids) == list(r0.output_ids)
            assert _value(e._m["resumed"]) == resumed0 + 1 \
                or e._m["resumed"] is om.NULL
            # nothing leaked: host tier drained, pages back in pool
            assert e.tier.bytes == 0 and e.tier.pages == 0
            assert e.alloc.free_pages == free0
            assert e.alloc.double_free_count == 0
        finally:
            e.close()


# ---------------------------------------------------------------------
# Lifecycle matrix while paused (satellite)
# ---------------------------------------------------------------------
class TestLifecycleWhilePaused:
    @pytest.fixture()
    def tier_engine(self, model, clean_faults):
        e = LlamaServingEngine(
            model, max_batch=2, page_size=8, num_pages=32,
            kv_tier=True, prefix_cache=False)
        yield e
        e.close()

    def _paused_request(self, e, tokens=3, **kw):
        r = Request([1, 2, 3], max_new_tokens=64, **kw)
        e.add_request(r)
        while len(r.output_ids) < tokens:
            e.step()
        with e._lock:
            e._pause(r)
        assert r.status == "paused" and e.tier.bytes > 0
        return r

    def test_cancel_while_paused_frees_host_copy(self, tier_engine):
        e = tier_engine
        r = self._paused_request(e)
        assert e.cancel(r) is True
        assert r.done and r.status == "cancelled"
        # host pages freed, not leaked
        assert e.tier.bytes == 0 and e.tier.pages == 0
        e.step()        # pump drops the terminal entry from requeue
        assert r not in e._requeue

    def test_deadline_expiry_while_paused(self, tier_engine):
        e = tier_engine
        r = self._paused_request(e, deadline=0.25)
        # the clock KEEPS TICKING while parked — a paused request is
        # still holding its caller's latency budget
        time.sleep(0.3)
        e.step()
        assert r.done and r.status == "deadline_exceeded"
        assert isinstance(r.error, DeadlineExceeded)
        assert e.tier.bytes == 0 and e.tier.pages == 0

    def test_drain_with_parked_requests(self, tier_engine):
        e = tier_engine
        free_before = e.alloc.free_pages
        r = self._paused_request(e)
        # the pause released every HBM page back to the pool; the
        # sequence lives on host DRAM only
        assert e.alloc.free_pages == free_before
        stats = e.drain(timeout=0.5)
        assert r.done
        # parked requests drain TYPED, never silently dropped
        assert r.status in ("completed", "deadline_exceeded")
        if r.status == "deadline_exceeded":
            assert isinstance(r.error, DeadlineExceeded)
        assert e.tier.bytes == 0 and e.tier.pages == 0
        with pytest.raises(AdmissionError):
            e.add_request(Request([1], max_new_tokens=1))
        assert stats["seconds"] >= 0

    def test_sigterm_races_inflight_d2h(self, model, monkeypatch,
                                        clean_faults):
        """SIGTERM lands while a D2H pause copy is in flight: the
        handler must DEFER (the copying thread is inside an engine
        entry), the copy must finish, and the deferred drain must then
        retire the freshly parked request typed and leak-free."""
        os.environ[faults.PLAN_ENV] = json.dumps(
            [{"point": "tier.d2h", "action": "sleep",
              "seconds": 0.6, "count": 1}])
        faults.reset()
        e = LlamaServingEngine(
            model, max_batch=2, page_size=8, num_pages=32,
            kv_tier=True, prefix_cache=False)
        exits = []
        monkeypatch.setattr(os, "_exit", lambda code: exits.append(code))
        prev = e.install_drain_handler(grace=0.5)
        try:
            free0 = e.alloc.free_pages
            r = Request([1, 2, 3], max_new_tokens=100000)
            e.add_request(r)
            while len(r.output_ids) < 2:
                e.step()
            in_entry = threading.Event()

            def _pauser():
                with e._entry():
                    in_entry.set()
                    with e._lock:
                        e._pause(r)     # slow D2H: 0.6s window

            w = threading.Thread(target=_pauser)
            w.start()
            assert in_entry.wait(5.0)
            time.sleep(0.1)             # into the copy window
            os.kill(os.getpid(), signal.SIGTERM)
            w.join(timeout=30.0)
            assert not w.is_alive()
            # the handler deferred; the entry boundary ran the drain
            assert exits == [0]
            assert r.done and r.status == "deadline_exceeded"
            assert isinstance(r.error, DeadlineExceeded)
            assert e.tier.bytes == 0 and e.tier.pages == 0
            assert e.alloc.free_pages == free0
            assert e.alloc.double_free_count == 0
        finally:
            for s, h in prev.items():
                signal.signal(s, h)
            e.close()


# ---------------------------------------------------------------------
# Ladder behavior: pause rung, capacity fallback, POSTPONE counter
# ---------------------------------------------------------------------
class TestLadderRungs:
    # the copy-chaos soak drives the same pressure ladder WITH faults
    # in tier-1; the fault-free variant rides the slow tier
    @pytest.mark.slow
    def test_pressure_pauses_instead_of_evicting(self, model,
                                                 clean_faults):
        """Tight pool, tier on, no faults: the ladder's pressure rung
        pauses victims (work preserved) and every request still
        completes token-exact vs a roomy un-pressured run."""
        prompts = [list((np.arange(3) + 7 * i) % 50 + 1)
                   for i in range(3)]
        roomy = LlamaServingEngine(model, max_batch=4, page_size=8,
                                   num_pages=64, prefix_cache=False)
        try:
            want = [list(_complete(
                roomy, Request(p, max_new_tokens=40)).output_ids)
                for p in prompts]
        finally:
            roomy.close()

        e = LlamaServingEngine(model, max_batch=2, page_size=8,
                               num_pages=8, kv_tier=True,
                               prefix_cache=False)
        try:
            free0 = e.alloc.free_pages
            reqs = [Request(p, max_new_tokens=40, retry_budget=4)
                    for p in prompts]
            _drive(e, reqs)
            st = e.tier.stats()
            assert st["exports"] >= 1 and st["restores"] >= 1, st
            for r, w in zip(reqs, want):
                assert r.status == "completed"
                assert list(r.output_ids) == w
            assert e.alloc.free_pages == free0
            assert e.tier.bytes == 0 and e.tier.pages == 0
        finally:
            e.close()

    def test_full_tier_degrades_to_evict(self, model, clean_faults):
        # a 1-byte pool can hold nothing: every pause falls back to
        # the pre-tier evict -> requeue, and requests still complete
        e = LlamaServingEngine(model, max_batch=2, page_size=8,
                               num_pages=32, kv_tier=True,
                               kv_tier_bytes=1, prefix_cache=False)
        try:
            r = Request([1, 2, 3], max_new_tokens=24, retry_budget=2)
            e.add_request(r)
            while len(r.output_ids) < 3:
                e.step()
            with e._lock:
                e._pause(r)
            assert r.status == "requeued"       # evict fallback
            assert e.tier.stats()["capacity_rejections"] >= 1
            assert e.tier.bytes == 0
            while not r.done:
                e.step()
            assert r.status == "completed"
        finally:
            e.close()

    def test_postponed_counter(self, model, clean_faults):
        """While another thread is mid-entry a victim can't free a
        single page — the ladder POSTPONES it (no state change) and
        counts it on serving_pressure_postponed_total (satellite)."""
        e = LlamaServingEngine(model, max_batch=2, page_size=8,
                               num_pages=8, kv_tier=True,
                               prefix_cache=False)
        try:
            rs = [Request([1, 2, 3], max_new_tokens=8),
                  Request([4, 5, 6], max_new_tokens=8)]
            for r in rs:
                e.add_request(r)
            while any(len(r.output_ids) < 1 for r in rs):
                e.step()
            p0 = _value(e._m["postponed"])
            fake = object()
            with e._lock:
                # two sequences each demanding 5 more pages: combined
                # pressure (under the per-seq trim cap) with deferrals
                # blocked -> POSTPONE, not pause
                e._entry_threads[fake] = 1
                try:
                    e._relieve_pressure(list(e._live.values()),
                                        5 * e.page_size)
                finally:
                    e._entry_threads.pop(fake, None)
            assert all(r.status == "live" for r in rs)  # untouched
            assert _value(e._m["postponed"]) > p0 \
                or e._m["postponed"] is om.NULL
            steps = 0
            while any(not r.done for r in rs) and steps < 400:
                e.step()
                steps += 1
            assert all(r.status == "completed" for r in rs)
        finally:
            e.close()


# ---------------------------------------------------------------------
# Prefix cache demote/promote through the tier
# ---------------------------------------------------------------------
class TestPrefixTiering:
    def test_cold_prefix_demotes_and_promotes(self, model,
                                              clean_faults):
        e = LlamaServingEngine(model, max_batch=2, page_size=8,
                               num_pages=64, kv_tier=True,
                               prefix_cache=True)
        try:
            prompt = list(np.arange(1, 21) % 50)    # 2 cacheable pages
            r0 = _complete(e, Request(prompt, max_new_tokens=8))
            assert e.prefix.pages >= 1
            # cold chains demote to the host tier before being dropped
            e.prefix.evict_pages(e.prefix.pages)
            st = e.tier.stats()
            assert st["prefix_demotions"] >= 1
            assert st["prefix_pages"] >= 1
            # a same-prefix admission promotes them back (H2D) instead
            # of re-prefilling
            r1 = _complete(e, Request(prompt, max_new_tokens=8))
            assert e.tier.stats()["prefix_promotions"] >= 1
            assert r1.status == "completed"
            assert list(r1.output_ids) == list(r0.output_ids)
        finally:
            e.close()


# ---------------------------------------------------------------------
# Fixed-seed copy chaos (tentpole acceptance, tier-1)
# ---------------------------------------------------------------------
class TestCopyChaos:
    def test_no_request_silently_lost(self, model, clean_faults):
        """Pool pressure ping-pongs three requests through pause/
        resume while the plan injects a slow copy, a failed export, a
        failed restore and a TORN restore. Every fault must degrade
        typed (evict -> requeue fallback; CRC catches the tear), every
        request must finish completed-token-exact or with a typed
        error, and the allocator free count and host-tier bytes must
        return to baseline."""
        prompts = [list((np.arange(3) + 7 * i) % 50 + 1)
                   for i in range(3)]
        roomy = LlamaServingEngine(model, max_batch=4, page_size=8,
                                   num_pages=64, prefix_cache=False)
        try:
            want = [list(_complete(
                roomy, Request(p, max_new_tokens=40)).output_ids)
                for p in prompts]
        finally:
            roomy.close()

        plan = [
            {"point": "tier.d2h", "action": "sleep",
             "seconds": 0.01, "count": 2},
            {"point": "tier.d2h", "action": "raise",
             "exc": "OSError", "count": 1, "path": "seq"},
            {"point": "tier.h2d", "action": "raise",
             "exc": "OSError", "count": 1, "path": "seq"},
            {"point": "tier.h2d", "action": "bitflip", "count": 1,
             "path": "seq"},
        ]
        os.environ[faults.PLAN_ENV] = json.dumps(plan)
        faults.reset()
        e = LlamaServingEngine(model, max_batch=2, page_size=8,
                               num_pages=8, kv_tier=True,
                               prefix_cache=False)
        try:
            free0 = e.alloc.free_pages
            reqs = [Request(p, max_new_tokens=40, retry_budget=6)
                    for p in prompts]
            _drive(e, reqs)
            st = e.tier.stats()
            for r, w in zip(reqs, want):
                # NEVER silently lost: terminal status is completed or
                # carries a typed error
                assert r.status == "completed" or r.error is not None, \
                    (r.status, r.error)
                if r.status == "completed":
                    assert list(r.output_ids) == w
            # the injected faults actually happened AND degraded
            assert st["exports"] >= 1 and st["restores"] >= 1, st
            assert st["export_failures"] >= 1, st     # failed D2H
            assert st["restore_failures"] >= 1, st    # failed H2D
            assert st["crc_failures"] >= 1, st        # torn H2D caught
            # leak-free: pages and host bytes back to baseline
            assert e.alloc.free_pages == free0
            assert e.alloc.double_free_count == 0
            assert e.tier.bytes == 0 and e.tier.pages == 0
        finally:
            e.close()


# ---------------------------------------------------------------------
# Metrics wiring (satellite)
# ---------------------------------------------------------------------
class TestTierMetrics:
    def test_engine_metric_keys(self, model):
        e = LlamaServingEngine(model, max_batch=1, page_size=8,
                               num_pages=8, kv_tier=True,
                               prefix_cache=False)
        try:
            for key in ("paused", "resumed", "postponed"):
                assert key in e._m
        finally:
            e.close()

    def test_tier_opt_in_default_off(self, model, monkeypatch):
        monkeypatch.delenv("PADDLE_TPU_KV_TIER", raising=False)
        e = LlamaServingEngine(model, max_batch=1, page_size=8,
                               num_pages=8, prefix_cache=False)
        try:
            assert e.tier is None
        finally:
            e.close()

    def test_tier_env_knobs(self, model, monkeypatch):
        monkeypatch.setenv("PADDLE_TPU_KV_TIER", "1")
        monkeypatch.setenv("PADDLE_TPU_KV_TIER_BYTES", "12345")
        e = LlamaServingEngine(model, max_batch=1, page_size=8,
                               num_pages=8, prefix_cache=False)
        try:
            assert e.tier is not None
            assert e.tier.max_bytes == 12345
        finally:
            e.close()
