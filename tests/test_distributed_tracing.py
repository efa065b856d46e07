"""Cluster-scope distributed tracing (ISSUE 17): W3C trace-context
propagation through the rpc envelope, merged multi-process request
timelines, the one-pane cluster metrics scrape, and SLO burn rates.

The acceptance e2e pushes one HTTP request (with a caller-supplied
``traceparent``) through a frontend + 3-subprocess-replica cluster and
proves: the merged Perfetto-loadable trace contains spans from >= 3
distinct pids with offset-aligned timestamps (no child starts before
its cross-process parent), ``GET /v1/requests/<id>/trace`` returns the
parent-linked tree, the cluster ``/metrics`` pane carries every
replica's registry under a ``replica`` label, and the SLO engine
reports burn rates. Envelope hygiene: with tracing off the rpc wire
layout is byte-for-byte the pre-trace 5-tuple, and the dispatcher
digests 3-/5-/6-tuple envelopes (including foreign trace fields)
without a KeyError.
"""

import json
import os
import time
import urllib.request

import numpy as np
import pytest

import paddle_tpu as paddle
from paddle_tpu.distributed.rpc import RpcEndpoint
from paddle_tpu.inference.cluster import ServingCluster
from paddle_tpu.observability import export as oexport
from paddle_tpu.observability import metrics as om
from paddle_tpu.observability import slo as oslo
from paddle_tpu.observability import trace as otrace
from paddle_tpu.observability import tracing as otracing

_CFG = dict(vocab_size=512, hidden_size=256, intermediate_size=512,
            num_hidden_layers=4, num_attention_heads=4,
            num_key_value_heads=2)
_ENGINE = dict(max_batch=2, page_size=8, num_pages=48)
_SPEC = {"model": {"kind": "tiny_llama", "seed": 0, "config": _CFG},
         "engine": _ENGINE}


@pytest.fixture(autouse=True)
def _fresh_state():
    om.default_registry().clear()
    otrace.clear()
    yield
    om.default_registry().clear()
    otrace.clear()


@pytest.fixture(scope="module")
def shared_cache(tmp_path_factory):
    d = tmp_path_factory.mktemp("warm")
    return {"JAX_PLATFORMS": "cpu",
            "JAX_COMPILATION_CACHE_DIR": str(d / "cache"),
            "PADDLE_TPU_SHAPE_REGISTRY": str(d / "shapes.json")}


def _wait(cond, timeout, what):
    deadline = time.time() + timeout
    while time.time() < deadline:
        if cond():
            return
        time.sleep(0.1)
    raise AssertionError(f"timed out waiting for {what}")


# ---------------------------------------------------------------------------
# TraceContext + traceparent
# ---------------------------------------------------------------------------
class TestTraceContext:
    def test_traceparent_round_trip(self):
        ctx = otracing.mint()
        hdr = otracing.format_traceparent(ctx)
        back = otracing.parse_traceparent(hdr)
        assert back.trace_id == ctx.trace_id
        assert back.span_id == ctx.span_id

    @pytest.mark.parametrize("bad", [
        None, "", "garbage", "00-abc-def-01",
        "ff-" + "a" * 32 + "-" + "b" * 16 + "-01",      # version ff
        "00-" + "0" * 32 + "-" + "b" * 16 + "-01",      # zero trace
        "00-" + "a" * 32 + "-" + "0" * 16 + "-01",      # zero span
        "00-" + "g" * 32 + "-" + "b" * 16 + "-01",      # non-hex
        "00-" + "a" * 31 + "-" + "b" * 16 + "-01",      # short trace
    ])
    def test_parse_rejects_malformed(self, bad):
        assert otracing.parse_traceparent(bad) is None

    def test_adopt_continues_remote_trace(self):
        remote = otracing.mint()
        ctx = otracing.adopt(otracing.format_traceparent(remote))
        assert ctx.trace_id == remote.trace_id
        assert ctx.parent_id == remote.span_id
        assert ctx.span_id != remote.span_id

    def test_adopt_mints_fresh_on_invalid(self):
        a = otracing.adopt("not-a-traceparent")
        b = otracing.adopt(None)
        assert a is not None and b is not None
        assert a.trace_id != b.trace_id
        assert a.parent_id is None

    def test_child_links_parent(self):
        root = otracing.mint()
        kid = root.child()
        assert kid.trace_id == root.trace_id
        assert kid.parent_id == root.span_id

    def test_kill_switch_returns_none(self, monkeypatch, tmp_path):
        monkeypatch.setenv("PADDLE_TPU_METRICS", "0")
        assert otracing.mint() is None
        assert otracing.adopt("00-" + "a" * 32 + "-" + "b" * 16
                              + "-01") is None
        assert otracing.inject() is None
        assert otracing.current() is None
        assert otracing.write_span_shard(tmp_path, "w0") is None
        assert not (tmp_path / otracing.SHARD_DIR).exists()
        assert otracing.record_clock_handshake(tmp_path, "w0") is None
        assert list(tmp_path.iterdir()) == []

    def test_kill_switch_beats_activated_context(self, monkeypatch):
        ctx = otracing.mint()
        with otracing.activate(ctx):
            monkeypatch.setenv("PADDLE_TPU_METRICS", "0")
            assert otracing.current() is None
            assert otracing.inject() is None


# ---------------------------------------------------------------------------
# span <-> context integration
# ---------------------------------------------------------------------------
class TestSpanChaining:
    def test_nested_spans_chain_to_active_context(self):
        buf = otrace.TraceBuffer()
        root = otracing.mint()
        with otracing.activate(root):
            with otrace.span("outer", buffer=buf):
                with otrace.span("inner", buffer=buf):
                    pass
        inner, outer = buf.events()
        assert outer["name"] == "outer"
        assert outer["args"]["trace_id"] == root.trace_id
        assert outer["args"]["parent_id"] == root.span_id
        assert inner["args"]["parent_id"] == outer["args"]["span_id"]

    def test_span_without_context_records_plain(self):
        buf = otrace.TraceBuffer()
        with otrace.span("plain", buffer=buf, k=1):
            pass
        (ev,) = buf.events()
        assert ev["args"] == {"k": 1}
        assert "trace_id" not in ev["args"]

    def test_explicit_trace_ctx_installs_verbatim(self):
        buf = otrace.TraceBuffer()
        ctx = otracing.mint().child()
        with otrace.span("rpc.call", buffer=buf, trace_ctx=ctx):
            with otrace.span("attempt", buffer=buf):
                pass
        att, call = buf.events()
        assert call["args"]["span_id"] == ctx.span_id
        assert att["args"]["parent_id"] == ctx.span_id


# ---------------------------------------------------------------------------
# shards, clock alignment, merge, tree
# ---------------------------------------------------------------------------
def _shard(worker, pid, epoch_unix, events):
    return {"worker": worker, "pid": pid, "epoch_unix": epoch_unix,
            "events": events}


def _ev(name, ts, dur, pid, trace_id=None, span_id=None,
        parent_id=None):
    ev = {"name": name, "ph": "X", "ts": ts, "dur": dur, "pid": pid,
          "tid": 1}
    if trace_id:
        ev["args"] = {"trace_id": trace_id, "span_id": span_id,
                      "parent_id": parent_id}
    return ev


class TestMergeShards:
    def test_offset_alignment_orders_cross_process_parent_first(self):
        # parent on pid 1 starts at unix 100.0+5.0s; child on pid 2 at
        # unix 103.0+2.5s = 105.5 — LATER in wall time although its raw
        # monotonic ts (2.5e6) is smaller than the parent's (5e6)
        t = "a" * 32
        parent = _ev("rpc.call", 5e6, 4e6, 1, t, "p" * 16)
        child = _ev("rpc.handle", 2.5e6, 1e6, 2, t, "c" * 16, "p" * 16)
        merged = otracing.merge_shards([
            _shard("router", 1, 100.0, [parent]),
            _shard("w0", 2, 103.0, [child])])
        spans = [e for e in merged["traceEvents"] if e["ph"] == "X"]
        by_name = {e["name"]: e for e in spans}
        assert by_name["rpc.handle"]["ts"] == pytest.approx(
            2.5e6 + 3e6 * 1.0)
        assert by_name["rpc.call"]["ts"] < by_name["rpc.handle"]["ts"]

    def test_process_metadata_first_and_named(self):
        merged = otracing.merge_shards([
            _shard("w1", 7, 50.0, [_ev("x", 1.0, 1.0, 7)]),
            _shard("w2", 8, 51.0, [_ev("y", 1.0, 1.0, 8)])])
        evs = merged["traceEvents"]
        metas = [e for e in evs if e["ph"] == "M"]
        assert [m["args"]["name"] for m in metas] == ["w1", "w2"]
        assert evs[:len(metas)] == metas    # metadata sorts first

    def test_empty_and_torn_shards_skipped(self, tmp_path):
        sd = tmp_path / otracing.SHARD_DIR
        sd.mkdir()
        (sd / "torn.trace.json").write_text('{"events": [')
        (sd / "foreign.txt").write_text("hi")
        path = otracing.write_span_shard(tmp_path, "good")
        assert path is not None and os.path.exists(path)
        shards = otracing.harvest_shards(tmp_path)
        assert [s["worker"] for s in shards] == ["good"]
        assert otracing.merge_shards([])["traceEvents"] == []

    def test_shard_flush_is_atomic_overwrite(self, tmp_path):
        buf = otrace.TraceBuffer()
        with otrace.span("one", buffer=buf):
            pass
        otracing.write_span_shard(tmp_path, "w0", buffer=buf)
        with otrace.span("two", buffer=buf):
            pass
        otracing.write_span_shard(tmp_path, "w0", buffer=buf)
        (doc,) = otracing.harvest_shards(tmp_path)
        assert [e["name"] for e in doc["events"]] == ["one", "two"]
        files = os.listdir(tmp_path / otracing.SHARD_DIR)
        assert files == ["w0.trace.json"]   # no tmp litter, one file

    def test_clock_handshake_round_trip(self, tmp_path):
        path = otracing.record_clock_handshake(tmp_path, "w3")
        assert os.path.basename(path).startswith(".traceclock.")
        hs = otracing.read_clock_handshakes(tmp_path)
        assert hs["w3"]["pid"] == os.getpid()
        assert hs["w3"]["epoch_unix"] == pytest.approx(
            otrace.epoch_unix())


class TestSpanTree:
    def test_tree_nests_by_parent_and_filters_by_trace(self):
        t, other = "a" * 32, "b" * 32
        events = [
            _ev("root", 0.0, 10.0, 1, t, "r" * 16),
            _ev("mid", 2.0, 5.0, 1, t, "m" * 16, "r" * 16),
            _ev("leaf", 3.0, 1.0, 2, t, "l" * 16, "m" * 16),
            _ev("noise", 0.0, 1.0, 3, other, "n" * 16),
            _ev("untraced", 0.0, 1.0, 3),
        ]
        (root,) = otracing.span_tree(events, t)
        assert root["name"] == "root"
        (mid,) = root["children"]
        assert mid["name"] == "mid"
        assert [c["name"] for c in mid["children"]] == ["leaf"]

    def test_orphaned_parent_surfaces_as_root(self):
        t = "a" * 32
        events = [_ev("leaf", 3.0, 1.0, 2, t, "l" * 16, "gone" * 4)]
        (root,) = otracing.span_tree(events, t)
        assert root["name"] == "leaf"


# ---------------------------------------------------------------------------
# rpc envelope hygiene
# ---------------------------------------------------------------------------
def _add(a, b):
    return a + b


class TestEnvelopeHygiene:
    @pytest.fixture()
    def mesh(self):
        master = RpcEndpoint("router", is_master=True, port=0)
        worker = RpcEndpoint("w0", port=master.port)
        yield master, worker
        worker.stop()
        master.stop()

    def _spy_payloads(self, monkeypatch):
        from paddle_tpu.distributed import rpc as rpc_mod

        captured = []
        orig = rpc_mod._RpcAgent._attempt

        def spy(self, to, payload, timeout, fut):
            captured.append(payload)
            return orig(self, to, payload, timeout, fut)

        monkeypatch.setattr(rpc_mod._RpcAgent, "_attempt", spy)
        return captured

    def test_untraced_envelope_stays_pre_trace_5_tuple(
            self, mesh, monkeypatch):
        master, _ = mesh
        captured = self._spy_payloads(monkeypatch)
        assert master.call_sync("w0", _add, (2, 3), timeout=30) == 5
        import pickle
        msg = pickle.loads(captured[0])
        assert len(msg) == 5        # byte-compat: no 6th trace element

    def test_kill_switch_envelope_5_tuple_even_inside_activate(
            self, mesh, monkeypatch):
        master, _ = mesh
        ctx = otracing.mint()
        monkeypatch.setenv("PADDLE_TPU_METRICS", "0")
        captured = self._spy_payloads(monkeypatch)
        with otracing.activate(ctx):
            assert master.call_sync("w0", _add, (1, 1), timeout=30) == 2
        import pickle
        assert len(pickle.loads(captured[0])) == 5

    def test_traced_envelope_carries_context_and_chains_spans(
            self, mesh, monkeypatch):
        master, _ = mesh
        captured = self._spy_payloads(monkeypatch)
        root = otracing.mint()
        with otracing.activate(root):
            assert master.call_sync("w0", _add, (4, 4), timeout=30) == 8
        import pickle
        msg = pickle.loads(captured[0])
        assert len(msg) == 6
        wire = msg[5]
        assert wire["trace_id"] == root.trace_id
        assert wire["parent_id"] == root.span_id
        # caller records rpc.call under the envelope's exact identity;
        # callee (same process here, own dispatcher thread) records a
        # chained rpc.handle. The driver thread closes its spans just
        # AFTER the reply resolves the future, so poll briefly.
        def _trace_events():
            return {e["name"]: e for e in otrace.get_events()
                    if (e.get("args") or {}).get("trace_id")
                    == root.trace_id}

        _wait(lambda: {"rpc.call", "rpc.attempt",
                       "rpc.handle"} <= set(_trace_events()),
              10, "rpc spans flushed by the driver thread")
        evs = _trace_events()
        assert evs["rpc.call"]["args"]["span_id"] == wire["span_id"]
        assert evs["rpc.handle"]["args"]["parent_id"] == wire["span_id"]
        assert evs["rpc.attempt"]["args"]["parent_id"] == \
            wire["span_id"]

    def test_mixed_version_envelopes_no_keyerror(self, mesh):
        """A traced caller against an untraced receiver (and vice
        versa) degrades cleanly: the dispatcher digests the legacy
        3-tuple, the pre-trace 5-tuple, a 6-tuple with foreign trace
        fields, and a partial trace dict — every call still replies."""
        import pickle

        master, worker = mesh
        store = master._agent.store
        envelopes = [
            (_add, (1, 2), {}),                                # legacy
            ("router", ("t", 1), _add, (3, 4), {}),            # 5-tuple
            ("router", ("t", 2), _add, (5, 6), {},             # traced
             {"trace_id": "a" * 32, "span_id": "b" * 16,
              "parent_id": None}),
            ("router", ("t", 3), _add, (7, 8), {},             # foreign
             {"vendor": "someone-else"}),
            ("router", ("t", 4), _add, (9, 1), {}, None),      # null tr
        ]
        want = [3, 7, 11, 15, 10]
        for env, expect in zip(envelopes, want):
            seq = store.add("rpc/seq/w0", 1) - 1
            store.set(f"rpc/to/w0/{seq}", pickle.dumps(env))
            rsp = store.get(f"rpc/reply/w0/{seq}", timeout=30)
            store.delete_key(f"rpc/reply/w0/{seq}")
            assert rsp[:3] == b"ok:"
            assert pickle.loads(rsp[3:]) == expect

    def test_dedup_redelivery_tagged_suppressed(self, mesh):
        """The same traced envelope delivered twice executes once; the
        second delivery leaves a zero-width ``rpc.dedup`` span marked
        ``suppressed`` on the receiver's timeline."""
        import pickle

        master, worker = mesh
        store = master._agent.store
        tr = {"trace_id": "c" * 32, "span_id": "d" * 16,
              "parent_id": None}
        env = pickle.dumps(("router", ("dup", 9), _add, (20, 22), {},
                            tr))
        for _ in range(2):
            seq = store.add("rpc/seq/w0", 1) - 1
            store.set(f"rpc/to/w0/{seq}", env)
            rsp = store.get(f"rpc/reply/w0/{seq}", timeout=30)
            store.delete_key(f"rpc/reply/w0/{seq}")
            assert pickle.loads(rsp[3:]) == 42
        dedups = [e for e in otrace.get_events()
                  if e["name"] == "rpc.dedup"]
        assert len(dedups) == 1
        assert dedups[0]["args"]["suppressed"] is True
        assert dedups[0]["args"]["trace_id"] == tr["trace_id"]
        handles = [e for e in otrace.get_events()
                   if e["name"] == "rpc.handle"
                   and (e.get("args") or {}).get("trace_id")
                   == tr["trace_id"]]
        assert len(handles) == 1    # executed exactly once


# ---------------------------------------------------------------------------
# one-pane snapshot merge + aggregation exactness
# ---------------------------------------------------------------------------
class TestSnapshotMerge:
    def _replica_registry(self, admitted, ttfts):
        r = om.MetricsRegistry()
        c = r.counter("serving_requests_admitted_total", "h")
        c.inc(admitted)
        h = r.histogram("serving_ttft_seconds", "h",
                        buckets=(0.1, 1.0))
        for v in ttfts:
            h.observe(v)
        r.counter("router_requests_routed_total", "h",
                  labelnames=("replica",)).labels("x").inc(2)
        return r

    def test_merge_labels_preserved_and_aggregate_exact(self):
        r0 = self._replica_registry(3, [0.05, 0.5])
        r1 = self._replica_registry(4, [0.5, 2.0, 2.0])
        merged = oexport.merge_snapshots(
            [("replica-0", oexport.json_snapshot(r0)),
             ("replica-1", oexport.json_snapshot(r1))])
        by_name = {e["name"]: e for e in merged}
        ctr = by_name["serving_requests_admitted_total"]
        assert ctr["labelnames"] == ["replica"]
        assert {tuple(s["labels"]): s["value"]
                for s in ctr["samples"]} == {("replica-0",): 3.0,
                                             ("replica-1",): 4.0}
        # inner labels ride BEHIND the replica label, preserved
        routed = by_name["router_requests_routed_total"]
        assert routed["labelnames"] == ["replica", "replica"] \
            or routed["labelnames"][0] == "replica"
        assert ["replica-0", "x"] in [s["labels"]
                                      for s in routed["samples"]]
        # aggregation: summed counters, element-wise histograms
        agg = {e["name"]: e for e in
               oexport.aggregate_snapshot(merged)}
        assert agg["serving_requests_admitted_total"]["samples"][0][
            "value"] == 7.0
        hist = agg["serving_ttft_seconds"]["samples"][0]
        assert hist["counts"] == [1, 2, 2]
        assert hist["count"] == 5
        assert hist["sum"] == pytest.approx(0.05 + 0.5 + 0.5 + 4.0)
        # merged pane renders to Prometheus text with replica labels
        text = oexport.snapshot_to_prometheus(merged)
        assert 'replica="replica-0"' in text
        assert 'replica="replica-1"' in text

    def test_schema_skew_skipped_not_fatal(self):
        r0 = om.MetricsRegistry()
        r0.counter("m_total", "h").inc()
        r1 = om.MetricsRegistry()
        r1.gauge("m_total", "h").set(5)     # skewed replica
        merged = oexport.merge_snapshots(
            [("a", oexport.json_snapshot(r0)),
             ("b", oexport.json_snapshot(r1))])
        (entry,) = merged
        assert entry["type"] == "counter"
        assert [s["labels"] for s in entry["samples"]] == [["a"]]


# ---------------------------------------------------------------------------
# SLO burn rates
# ---------------------------------------------------------------------------
class TestSloEngine:
    def test_burn_rate_from_cumulative_deltas(self):
        eng = oslo.SloEngine(
            slos=[oslo.SloSpec("ttft", "serving_ttft_seconds", 0.5,
                               objective=0.99)],
            windows=(60.0,), registry=om.MetricsRegistry())
        buckets = (0.1, 0.5, 1.0)
        # t=0: 10 obs, all good; t=30: +10 obs of which 2 above 0.5
        eng.observe("ttft", buckets, [5, 5, 0, 0], now=1000.0)
        eng.observe("ttft", buckets, [9, 9, 1, 1], now=1030.0)
        rates = eng.burn_rates(now=1030.0)
        # window covers both points: delta from zero = 20 obs, 2 bad
        assert rates["ttft"]["60s"] == pytest.approx(
            (2 / 20) / 0.01)

    def test_window_baseline_and_no_traffic(self):
        eng = oslo.SloEngine(
            slos=[oslo.SloSpec("ttft", "m", 0.5, objective=0.9)],
            windows=(10.0, 1000.0), registry=om.MetricsRegistry())
        eng.observe("ttft", (0.5,), [10, 0], now=0.0)
        eng.observe("ttft", (0.5,), [10, 5], now=100.0)
        rates = eng.burn_rates(now=100.0)
        # short window: baseline is the t=0 point -> 5/5 bad
        assert rates["ttft"]["10s"] == pytest.approx(1.0 / 0.1)
        # long window sees the same delta (15 obs, 5 bad)
        assert rates["ttft"]["1000s"] == pytest.approx(
            (5 / 15) / 0.1)
        # quiet window after the last point: no traffic, no burn
        eng.observe("ttft", (0.5,), [10, 5], now=200.0)
        assert eng.burn_rates(now=200.0)["ttft"]["10s"] == 0.0

    def test_counter_reset_reports_zero_not_negative(self):
        eng = oslo.SloEngine(
            slos=[oslo.SloSpec("ttft", "m", 0.5)],
            windows=(60.0,), registry=om.MetricsRegistry())
        eng.observe("ttft", (0.5,), [100, 50], now=0.0)
        eng.observe("ttft", (0.5,), [2, 0], now=100.0)  # replica restart
        # the baseline (t=0) sits behind the reset: delta is negative,
        # report 0 burn rather than a bogus negative rate
        assert eng.burn_rates(now=100.0)["ttft"]["60s"] == 0.0

    def test_threshold_inside_bucket_counts_bucket_good(self):
        good, bad = oslo._split_counts((0.1, 1.0), [3, 4, 5], 0.5)
        assert (good, bad) == (3, 9)
        good, bad = oslo._split_counts((0.1, 1.0), [3, 4, 5], 1.0)
        assert (good, bad) == (7, 5)    # bound == threshold is good

    def test_gauge_published_with_slo_and_window_labels(self):
        reg = om.MetricsRegistry()
        eng = oslo.SloEngine(windows=(60.0,), registry=reg)
        eng.observe("ttft", (0.5,), [1, 1], now=0.0)
        eng.burn_rates(now=0.0)
        m = reg.get("serving_slo_burn_rate")
        assert m.labelnames == ("slo", "window")
        assert {v for v, _ in m.samples()} >= {("ttft", "60s"),
                                               ("tpot", "60s")}

    def test_kill_switch_noop(self, monkeypatch):
        monkeypatch.setenv("PADDLE_TPU_METRICS", "0")
        eng = oslo.SloEngine(windows=(60.0,))
        eng.observe("ttft", (0.5,), [1, 1])
        assert eng.burn_rates()["ttft"]["60s"] == 0.0


# ---------------------------------------------------------------------------
# acceptance e2e: one traced HTTP request across a 3-process cluster
# ---------------------------------------------------------------------------
def test_e2e_traced_request_across_three_processes(tmp_path,
                                                   shared_cache):
    from paddle_tpu.inference.frontend import ServingFrontend

    env = dict(shared_cache, PADDLE_TPU_TRACE_FLUSH="0.1")
    cluster = ServingCluster(
        engine_spec=_SPEC, num_replicas=3,
        store_path=str(tmp_path / "members"), ttl=10.0,
        monitor_interval=0.05, spawn_grace=300.0, slo_interval=0.2,
        subprocess_env=env, log_dir=str(tmp_path / "logs")).start()
    fe = ServingFrontend(cluster=cluster)
    fe.start(port=0)
    pane = cluster.start_http_server(port=0)
    try:
        _wait(lambda: all(r.ready()
                          for r in cluster.replicas().values()),
              300, "3 subprocess replicas ready")

        parent = otracing.mint()
        traceparent = otracing.format_traceparent(parent)
        rng = np.random.RandomState(7)
        prompt = rng.randint(0, _CFG["vocab_size"], (4,)).tolist()
        req = urllib.request.Request(
            f"http://127.0.0.1:{fe.port}/v1/completions",
            data=json.dumps({"prompt": prompt,
                             "max_tokens": 4}).encode(),
            headers={"Content-Type": "application/json",
                     "traceparent": traceparent})
        with urllib.request.urlopen(req, timeout=300) as r:
            doc = json.loads(r.read())
        rid = doc["id"]
        assert doc["choices"][0]["token_ids"]

        # ---- merged Perfetto-loadable trace: >= 3 distinct pids ----
        def merged_pids():
            merged = cluster.collect_trace()
            return {e["pid"] for e in merged["traceEvents"]
                    if e.get("ph") == "X"}

        _wait(lambda: len(merged_pids()) >= 3, 60,
              ">=3 pids in the merged trace (worker shard flushes)")
        out_path = tmp_path / "merged.trace.json"
        merged = cluster.collect_trace(path=str(out_path))
        loaded = json.loads(out_path.read_text())
        assert loaded["traceEvents"]        # loadable + non-empty
        metas = [e for e in loaded["traceEvents"] if e["ph"] == "M"]
        assert {m["args"]["name"] for m in metas} >= {"router"}

        # ---- the request's tree: parent-linked across processes ----
        def fetch_tree():
            url = (f"http://127.0.0.1:{fe.port}/v1/requests/"
                   f"{rid}/trace")
            with urllib.request.urlopen(url, timeout=30) as r:
                return json.loads(r.read())

        def tree_pids(nodes, acc):
            for n in nodes:
                acc.add(n["pid"])
                tree_pids(n["children"], acc)
            return acc

        _wait(lambda: len(tree_pids(fetch_tree()["spans"], set())) >= 2,
              60, "request tree spanning >=2 processes")
        tree = fetch_tree()
        assert tree["trace_id"] == parent.trace_id
        assert tree["request_id"] == rid

        def check_order(node):
            for c in node["children"]:
                # offset alignment: a child never starts before its
                # (possibly cross-process) parent; 1ms slack for the
                # one-time clock-offset measurement error
                assert c["ts"] >= node["ts"] - 1e3, \
                    (node["name"], node["ts"], c["name"], c["ts"])
                check_order(c)

        names = set()

        def collect(nodes):
            for n in nodes:
                names.add(n["name"])
                collect(n["children"])

        for root in tree["spans"]:
            check_order(root)
        collect(tree["spans"])
        assert "frontend.request" in names
        assert "rpc.call" in names
        assert "rpc.handle" in names       # recorded in the worker pid
        frontend_pid = os.getpid()
        worker_pids = tree_pids(tree["spans"], set()) - {frontend_pid}
        assert worker_pids, "no cross-process span in the tree"

        # ---- one-pane /metrics: every replica under its label ----
        with urllib.request.urlopen(
                f"http://127.0.0.1:{pane.port}/metrics.json",
                timeout=60) as r:
            snap = json.loads(r.read())
        replicas_seen = set()
        for entry in snap:
            if entry["labelnames"][:1] == ["replica"]:
                for s in entry["samples"]:
                    replicas_seen.add(s["labels"][0])
        assert replicas_seen >= {"router", "replica-0", "replica-1",
                                 "replica-2"}
        # exactness: aggregate equals the manual per-replica sum
        by_name = {e["name"]: e for e in snap}
        adm = by_name["serving_requests_admitted_total"]
        manual = sum(s["value"] for s in adm["samples"])
        (agg_entry,) = [e for e in oexport.aggregate_snapshot([adm])]
        assert agg_entry["samples"][0]["value"] == manual >= 1
        with urllib.request.urlopen(
                f"http://127.0.0.1:{pane.port}/metrics",
                timeout=60) as r:
            text = r.read().decode()
        assert 'replica="replica-0"' in text

        # ---- SLO burn rates on membership_info + the gauge ----
        cluster._slo_tick(force=True)
        info = cluster.membership_info()
        burn = info["slo_burn_rates"]
        assert set(burn) == {"ttft", "tpot"}
        assert "60s" in burn["ttft"]
        assert all(v >= 0.0 for per in burn.values()
                   for v in per.values())
        assert om.default_registry().get(
            "serving_slo_burn_rate") is not None

        # ---- satellite: postmortem harvest on the death path ----
        victim = "replica-0"
        bundle = (tmp_path / "logs" / victim / "postmortem"
                  / "2001_01_01_00_00_00_pid1_0")
        bundle.mkdir(parents=True)
        (bundle / "MANIFEST.json").write_text("{}")
        cluster.replicas()[victim].kill()
        _wait(lambda: cluster.membership_info()["membership"][victim]
              .get("postmortem") == str(bundle),
              120, "postmortem bundle harvested into restart state")
    finally:
        pane.stop()
        fe.stop()
        cluster.stop()


# ---------------------------------------------------------------------------
# in-process backend: trace plumbing without subprocesses (fast)
# ---------------------------------------------------------------------------
def test_inprocess_cluster_trace_spans_and_request_endpoint(tmp_path):
    from paddle_tpu.inference.frontend import ServingFrontend
    from paddle_tpu.inference.serving import LlamaServingEngine
    from paddle_tpu.models import LlamaForCausalLM, tiny_llama_config

    paddle.seed(0)
    model = LlamaForCausalLM(tiny_llama_config(
        vocab_size=128, hidden_size=64, intermediate_size=128,
        num_hidden_layers=2, num_attention_heads=2,
        num_key_value_heads=2))
    model.eval()
    engine = LlamaServingEngine(model, max_batch=2, page_size=8,
                                num_pages=24, prefix_cache=False)
    fe = ServingFrontend(engine=engine)
    fe.start(port=0)
    try:
        parent = otracing.mint()
        req = urllib.request.Request(
            f"http://127.0.0.1:{fe.port}/v1/completions",
            data=json.dumps({"prompt": [1, 2, 3],
                             "max_tokens": 3}).encode(),
            headers={"Content-Type": "application/json",
                     "traceparent":
                         otracing.format_traceparent(parent)})
        with urllib.request.urlopen(req, timeout=300) as r:
            doc = json.loads(r.read())
        rid = doc["id"]

        def traced_names():
            return {e["name"] for e in otrace.get_events()
                    if (e.get("args") or {}).get("trace_id")
                    == parent.trace_id}

        _wait(lambda: {"frontend.request", "serving.admit",
                       "serving.first_token"} <= traced_names(),
              60, "request spans recorded under the adopted trace")

        url = f"http://127.0.0.1:{fe.port}/v1/requests/{rid}/trace"
        with urllib.request.urlopen(url, timeout=30) as r:
            tree = json.loads(r.read())
        assert tree["trace_id"] == parent.trace_id
        (root,) = tree["spans"]
        assert root["name"] == "frontend.request"
        # the admit/first-token spans hang somewhere under the root
        names = set()

        def collect(n):
            names.add(n["name"])
            for c in n["children"]:
                collect(c)

        collect(root)
        assert "serving.admit" in names
        assert "serving.first_token" in names

        # unknown id -> 404, typed
        bad = f"http://127.0.0.1:{fe.port}/v1/requests/nope/trace"
        with pytest.raises(urllib.error.HTTPError) as ei:
            urllib.request.urlopen(bad, timeout=30)
        assert ei.value.code == 404
    finally:
        fe.stop()
        engine.close()


def test_untraced_request_leaves_no_trace_state(tmp_path):
    """No traceparent + kill switch: the handler runs the plain
    dispatch path — no rid->trace mapping, 404 from the trace
    endpoint, and no trace fields on recorded spans."""
    from paddle_tpu.inference.frontend import ServingFrontend
    from paddle_tpu.inference.serving import LlamaServingEngine
    from paddle_tpu.models import LlamaForCausalLM, tiny_llama_config

    paddle.seed(0)
    model = LlamaForCausalLM(tiny_llama_config(
        vocab_size=128, hidden_size=64, intermediate_size=128,
        num_hidden_layers=2, num_attention_heads=2,
        num_key_value_heads=2))
    model.eval()
    engine = LlamaServingEngine(model, max_batch=2, page_size=8,
                                num_pages=24, prefix_cache=False)
    fe = ServingFrontend(engine=engine)
    fe.start(port=0)
    os.environ["PADDLE_TPU_METRICS"] = "0"
    try:
        req = urllib.request.Request(
            f"http://127.0.0.1:{fe.port}/v1/completions",
            data=json.dumps({"prompt": [1, 2, 3],
                             "max_tokens": 2}).encode(),
            headers={"Content-Type": "application/json",
                     "traceparent": "00-" + "a" * 32 + "-"
                                    + "b" * 16 + "-01"})
        with urllib.request.urlopen(req, timeout=300) as r:
            doc = json.loads(r.read())
        assert doc["choices"][0]["token_ids"]
        assert fe._traces == {}     # nothing remembered
        with pytest.raises(urllib.error.HTTPError) as ei:
            urllib.request.urlopen(
                f"http://127.0.0.1:{fe.port}/v1/requests/"
                f"{doc['id']}/trace", timeout=30)
        assert ei.value.code == 404
    finally:
        os.environ.pop("PADDLE_TPU_METRICS", None)
        fe.stop()
        engine.close()
