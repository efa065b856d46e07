"""Perf attribution layer (ISSUE 18): per-callable roofline gauges
from measured device time x static cost_analysis, the EWMA perf
sentinel (counter + flight-recorder dump on sustained slowdown), the
build-info gauge on every scrape, and cluster-wide on-demand profiler
capture merged into one Perfetto-loadable bundle.

The acceptance e2e runs a frontend + 2-subprocess-replica cluster,
pushes traffic, and proves ``ServingCluster.capture_profile()`` (and
``GET /debug/profile?seconds=N`` over HTTP) returns one merged bundle
with trace data from >= 2 replica processes.
"""

import glob
import json
import os
import time
import urllib.error
import urllib.request

import numpy as np
import pytest

import paddle_tpu as paddle
from paddle_tpu.observability import export as oexport
from paddle_tpu.observability import flight_recorder as ofr
from paddle_tpu.observability import metrics as om
from paddle_tpu.observability import perf
from paddle_tpu.observability import trace as otrace

_CFG = dict(vocab_size=512, hidden_size=256, intermediate_size=512,
            num_hidden_layers=4, num_attention_heads=4,
            num_key_value_heads=2)
_SPEC = {"model": {"kind": "tiny_llama", "seed": 0, "config": _CFG},
         "engine": dict(max_batch=2, page_size=8, num_pages=48)}


@pytest.fixture(autouse=True)
def _fresh_perf():
    om.default_registry().clear()
    perf.reset()
    yield
    om.default_registry().clear()
    perf.reset()
    ofr.uninstall()


def _peek(name, *labels):
    """Gauge/counter value for one label combo, or None when the child
    (or the metric itself) was never created."""
    m = om.default_registry().get(name)
    if m is None:
        return None
    child = m.peek(*labels)
    return None if child is None else child.value


def _wait(cond, timeout, what):
    deadline = time.time() + timeout
    while time.time() < deadline:
        if cond():
            return
        time.sleep(0.1)
    raise AssertionError(f"timed out waiting for {what}")


# ---------------------------------------------------------------------------
# roofline math (observe is the fenced path's internal entry point)
# ---------------------------------------------------------------------------
class TestRoofline:
    def test_observe_publishes_fractions_against_peaks(self):
        peak_flops, peak_bw, _ = perf.device_peaks()
        # 1 ms of device time at exactly 10% of both peaks
        s = perf.observe("m", 1e-3, flops=0.1 * peak_flops * 1e-3,
                         bytes_accessed=0.1 * peak_bw * 1e-3)
        assert s["attained_flops_frac"] == pytest.approx(0.1)
        assert s["attained_hbm_bw_frac"] == pytest.approx(0.1)
        assert _peek("paddle_tpu_perf_device_ms", "m") == \
            pytest.approx(1.0)
        assert _peek("paddle_tpu_perf_attained_flops_frac", "m") == \
            pytest.approx(0.1)
        assert _peek("paddle_tpu_perf_attained_hbm_bw_frac", "m") == \
            pytest.approx(0.1)
        assert _peek("paddle_tpu_perf_fenced_samples_total",
                     "m") == 1.0

    def test_fractions_clamp_to_one(self):
        peak_flops, _, _ = perf.device_peaks()
        # static FLOPs claiming 5x peak (a fused program the analyzer
        # over-counts): clamp, don't report >1
        s = perf.observe("m", 1e-3, flops=5.0 * peak_flops * 1e-3)
        assert s["attained_flops_frac"] == 1.0

    def test_missing_cost_skips_fraction_gauges(self):
        s = perf.observe("m", 1e-3)
        assert "attained_flops_frac" not in s
        assert "attained_hbm_bw_frac" not in s
        assert _peek("paddle_tpu_perf_device_ms", "m") is not None

    def test_unknown_tpu_kind_gets_no_fraction_and_no_default(
            self, monkeypatch):
        import jax

        class _Dev:
            platform = "tpu"
            device_kind = "TPU v99"

        monkeypatch.setattr(jax, "devices", lambda *a: [_Dev()])
        perf.reset()
        assert perf.device_peaks() == (None, None, "TPU v99")
        s = perf.observe("m", 1e-3, flops=1e9, bytes_accessed=1e6)
        assert s["device_kind"] == "TPU v99"
        assert "attained_flops_frac" not in s
        assert "attained_hbm_bw_frac" not in s
        assert _peek("paddle_tpu_perf_device_ms", "m") == \
            pytest.approx(1.0)
        assert _peek("paddle_tpu_perf_attained_flops_frac", "m") is None
        assert _peek("paddle_tpu_perf_attained_hbm_bw_frac", "m") is None

    def test_kill_switches(self, monkeypatch):
        for var in ("PADDLE_TPU_METRICS", "PADDLE_TPU_PERF"):
            monkeypatch.setenv(var, "0")
            assert not perf.enabled()
            assert perf.observe("m", 1e-3, flops=1e9) is None
            assert perf.note_dispatch("m", None, None, 0.0) is None
            monkeypatch.delenv(var)
        assert perf.enabled()


# ---------------------------------------------------------------------------
# EWMA sentinel
# ---------------------------------------------------------------------------
def _feed(name, ms, n):
    last = None
    for _ in range(n):
        last = perf.observe(name, ms / 1e3, flops=1e9)
    return last


class TestSentinel:
    def test_silent_on_steady_traffic(self):
        _feed("steady", 1.0, 40)
        st = perf.recorders()["steady"]
        assert st["regressions"] == 0
        assert _peek("paddle_tpu_perf_regressions_total",
                     "steady") is None

    def test_silent_on_noise_within_ratio(self):
        rng = np.random.RandomState(0)
        for _ in range(60):     # +-20% jitter never breaches 1.5x
            perf.observe("noisy", rng.uniform(0.8e-3, 1.2e-3))
        assert perf.recorders()["noisy"]["regressions"] == 0

    def test_fires_on_sustained_slowdown_and_dumps(self, tmp_path,
                                                   monkeypatch):
        ofr.install(log_dir=str(tmp_path))
        _feed("hot", 1.0, 12)          # baseline past warmup
        _feed("hot", 3.0, 8)           # sustained 3x
        st = perf.recorders()["hot"]
        assert st["regressions"] >= 1
        assert _peek("paddle_tpu_perf_regressions_total",
                     "hot") >= 1.0
        envs = glob.glob(str(tmp_path / "postmortem" / "*"
                             / "env.json"))
        assert envs, "sentinel fired without a flight-recorder bundle"
        doc = json.loads(open(envs[0]).read())
        assert doc["reason"] == "perf_regression"
        assert doc["info"]["callable"] == "hot"
        assert doc["info"]["slowdown_x"] > 1.5

    def test_rebaselines_after_firing(self):
        _feed("rb", 1.0, 12)
        _feed("rb", 3.0, 8)            # fires, slow re-baselined to ~3ms
        fired = perf.recorders()["rb"]["regressions"]
        assert fired >= 1
        _feed("rb", 3.0, 20)           # the new normal: no more events
        assert perf.recorders()["rb"]["regressions"] == fired

    def test_no_fire_during_warmup(self):
        # a slowdown inside the first _SENTINEL_MIN samples is compile/
        # cache noise, not a regression
        _feed("young", 1.0, 3)
        _feed("young", 5.0, 4)
        assert perf.recorders()["young"]["regressions"] == 0

    def test_dump_rate_limited_but_counter_ticks(self, tmp_path,
                                                 monkeypatch):
        calls = []
        monkeypatch.setattr(ofr, "dump",
                            lambda **kw: calls.append(kw) or "/x")
        _feed("rl", 1.0, 12)
        _feed("rl", 3.0, 8)            # event 1 (+ dump)
        _feed("rl", 9.0, 8)            # event 2 inside the 60s window
        st = perf.recorders()["rl"]
        assert st["regressions"] == 2
        assert len(calls) == 1         # dump throttled, counter not


# ---------------------------------------------------------------------------
# dispatch hooks: real serving + hapi callables on the CPU backend
# ---------------------------------------------------------------------------
class TestDispatchIntegration:
    @pytest.fixture()
    def fence_every_call(self, monkeypatch):
        monkeypatch.setenv("PADDLE_TPU_PERF_FENCE_INTERVAL", "0")

    def test_serving_mixed_programs_get_roofline(self, fence_every_call):
        from paddle_tpu.inference.serving import LlamaServingEngine
        from paddle_tpu.models import LlamaForCausalLM, tiny_llama_config

        paddle.seed(0)
        model = LlamaForCausalLM(tiny_llama_config(**_CFG))
        model.eval()
        engine = LlamaServingEngine(model, max_batch=2, page_size=8,
                                    num_pages=48, prefix_cache=False)
        try:
            rng = np.random.RandomState(3)
            prompts = [rng.randint(0, _CFG["vocab_size"], (5,)).tolist()
                       for _ in range(2)]
            out = engine.generate(prompts, max_new_tokens=6)
            assert all(out)
        finally:
            engine.close()
        rec = perf.recorders()
        serving = {n: s for n, s in rec.items()
                   if n.startswith("serving.")}
        assert serving, f"no serving callable attributed: {list(rec)}"
        reg = om.default_registry()
        for name, st in serving.items():
            if not st["samples"]:
                continue
            assert st["device_ewma_ms"] > 0
            frac = _peek("paddle_tpu_perf_attained_flops_frac", name)
            assert frac is not None, f"{name}: no flops fraction"
            assert 0.0 < frac <= 1.0
            hbm = _peek("paddle_tpu_perf_attained_hbm_bw_frac", name)
            assert hbm is not None and 0.0 < hbm <= 1.0
        assert any(st["samples"] for st in serving.values())

    def test_hapi_train_step_gets_roofline(self, fence_every_call):
        import paddle_tpu.nn as nn
        from paddle_tpu.hapi import Model

        paddle.seed(0)
        net = nn.Sequential(nn.Linear(4, 16), nn.ReLU(),
                            nn.Linear(16, 2))
        m = Model(net)
        m.prepare(optimizer=paddle.optimizer.AdamW(
            learning_rate=0.01, parameters=net.parameters()),
            loss=nn.CrossEntropyLoss(), jit=True)
        x = np.random.RandomState(0).randn(8, 4).astype("float32")
        y = (x.sum(axis=1) > 0).astype("int64")
        for _ in range(4):
            m.train_batch([x], [y])
        st = perf.recorders().get("hapi.train_step")
        assert st is not None and st["samples"] >= 1
        frac = _peek("paddle_tpu_perf_attained_flops_frac",
                     "hapi.train_step")
        assert frac is not None and 0.0 < frac <= 1.0

    def test_watched_jit_hook(self, fence_every_call):
        import jax.numpy as jnp
        from paddle_tpu.observability.compile_watch import watched_jit

        f = watched_jit(lambda a, b: a @ b, name="unit.matmul")
        x = jnp.ones((64, 64), jnp.float32)
        for _ in range(3):
            f(x, x)
        st = perf.recorders().get("unit.matmul")
        assert st is not None and st["samples"] >= 1
        # CPU cost_analysis still yields real flops: fraction exists
        assert st["flops"] and st["flops"] > 0

    def test_metrics_off_is_true_noop(self, monkeypatch):
        monkeypatch.setenv("PADDLE_TPU_METRICS", "0")
        import jax.numpy as jnp
        from paddle_tpu.observability.compile_watch import watched_jit

        f = watched_jit(lambda a: a * 2, name="unit.noop")
        f(jnp.ones((8,), jnp.float32))
        assert perf.recorders() == {}
        assert om.default_registry().get(
            "paddle_tpu_perf_device_ms") is None


# ---------------------------------------------------------------------------
# build info
# ---------------------------------------------------------------------------
class TestBuildInfo:
    def test_fields(self):
        info = perf.build_info()
        assert set(info) == {"git_commit", "jax_version",
                             "device_kind"}
        import jax
        assert info["jax_version"] == jax.__version__
        assert info["git_commit"] not in ("", None)

    def test_served_on_every_scrape(self):
        svc = oexport.start_http_server(port=0)
        try:
            with urllib.request.urlopen(
                    f"http://127.0.0.1:{svc.port}/metrics.json",
                    timeout=30) as r:
                snap = json.loads(r.read())
            by_name = {e["name"]: e for e in snap}
            entry = by_name["paddle_tpu_build_info"]
            assert entry["labelnames"] == ["git_commit", "jax_version",
                                           "device_kind"]
            (sample,) = entry["samples"]
            assert sample["value"] == 1.0
            info = perf.build_info()
            assert sample["labels"] == [info["git_commit"],
                                        info["jax_version"],
                                        info["device_kind"]]
            with urllib.request.urlopen(
                    f"http://127.0.0.1:{svc.port}/metrics",
                    timeout=30) as r:
                text = r.read().decode()
            assert "paddle_tpu_build_info{" in text
        finally:
            svc.stop()

    def test_commit_env_override_and_kill_switch(self, monkeypatch):
        monkeypatch.setenv("PADDLE_TPU_BUILD_COMMIT", "deadbeef")
        perf.reset()
        assert perf.build_info()["git_commit"] == "deadbeef"
        monkeypatch.setenv("PADDLE_TPU_METRICS", "0")
        assert perf.ensure_build_info() is None


# ---------------------------------------------------------------------------
# local profiler capture + the local /debug/profile route
# ---------------------------------------------------------------------------
class TestLocalCapture:
    def test_capture_local_shard_shape(self):
        with otrace.span("work.before"):
            pass
        shard = perf.capture_local(0.1, worker_name="w0")
        assert shard["worker"] == "w0"
        assert shard["pid"] == os.getpid()
        assert shard["profiler"]["seconds"] == pytest.approx(0.1)
        names = {e.get("name") for e in shard["events"]}
        assert "work.before" in names   # host spans ride the shard

    def test_capture_bundle_is_perfetto_loadable(self):
        with otrace.span("work.span"):
            pass
        bundle = perf.capture_bundle(0.05, worker_name="solo")
        assert bundle["displayTimeUnit"] == "ms"
        evs = bundle["traceEvents"]
        metas = [e for e in evs if e["ph"] == "M"]
        assert {m["args"]["name"] for m in metas} == {"solo"}
        assert bundle["capture"]["pids"] == [os.getpid()]
        json.dumps(bundle)      # strictly serializable

    def test_debug_profile_route_local(self):
        with otrace.span("http.work"):
            pass
        svc = oexport.start_http_server(port=0)
        try:
            with urllib.request.urlopen(
                    f"http://127.0.0.1:{svc.port}"
                    f"/debug/profile?seconds=0.05", timeout=60) as r:
                doc = json.loads(r.read())
            assert doc["traceEvents"]
            assert doc["capture"]["seconds"] == pytest.approx(0.05)
        finally:
            svc.stop()

    def test_debug_profile_bad_seconds_400(self):
        svc = oexport.start_http_server(port=0)
        try:
            with pytest.raises(urllib.error.HTTPError) as ei:
                urllib.request.urlopen(
                    f"http://127.0.0.1:{svc.port}"
                    f"/debug/profile?seconds=banana", timeout=30)
            assert ei.value.code == 400
        finally:
            svc.stop()

    def test_kill_switch_shard_empty_and_route_503(self, monkeypatch):
        svc = oexport.start_http_server(port=0)
        monkeypatch.setenv("PADDLE_TPU_METRICS", "0")
        try:
            shard = perf.capture_local(0.01)
            assert shard["events"] == []
            assert shard["profiler"]["ok"] is False
            assert perf.capture_bundle(0.01) is None
            with pytest.raises(urllib.error.HTTPError) as ei:
                urllib.request.urlopen(
                    f"http://127.0.0.1:{svc.port}"
                    f"/debug/profile?seconds=0.01", timeout=30)
            assert ei.value.code == 503
        finally:
            monkeypatch.delenv("PADDLE_TPU_METRICS")
            svc.stop()


# ---------------------------------------------------------------------------
# acceptance e2e: cluster-wide capture across subprocess replicas
# ---------------------------------------------------------------------------
def test_e2e_cluster_capture_profile_two_replicas(tmp_path,
                                                  tmp_path_factory):
    from paddle_tpu.inference.cluster import ServingCluster
    from paddle_tpu.inference.frontend import ServingFrontend

    warm = tmp_path_factory.mktemp("warm")
    env = {"JAX_PLATFORMS": "cpu",
           "JAX_COMPILATION_CACHE_DIR": str(warm / "cache"),
           "PADDLE_TPU_SHAPE_REGISTRY": str(warm / "shapes.json")}
    cluster = ServingCluster(
        engine_spec=_SPEC, num_replicas=2,
        store_path=str(tmp_path / "members"), ttl=10.0,
        monitor_interval=0.05, spawn_grace=300.0,
        subprocess_env=env, log_dir=str(tmp_path / "logs")).start()
    fe = ServingFrontend(cluster=cluster)
    fe.start(port=0)
    try:
        _wait(lambda: all(r.ready()
                          for r in cluster.replicas().values()),
              300, "2 subprocess replicas ready")
        # traffic so every process has spans (and the workers have
        # dispatched their serving programs at least once)
        rng = np.random.RandomState(11)
        reqs = [cluster.submit(
            rng.randint(0, _CFG["vocab_size"], (4,)).tolist(),
            max_new_tokens=3) for _ in range(4)]
        for r in reqs:
            r.wait(300.0)

        out_path = tmp_path / "capture.trace.json"
        merged = cluster.capture_profile(seconds=0.3,
                                         path=str(out_path))
        assert merged is not None
        # one merged Perfetto-loadable bundle...
        loaded = json.loads(out_path.read_text())
        assert loaded["displayTimeUnit"] == "ms"
        assert loaded["traceEvents"]
        # ...with trace data from >= 2 replica processes (+ router)
        router_pid = os.getpid()
        span_pids = {e["pid"] for e in loaded["traceEvents"]
                     if e.get("ph") != "M"}
        worker_pids = span_pids - {router_pid}
        assert len(worker_pids) >= 2, (
            f"want >=2 replica pids, got {span_pids}")
        meta_names = {e["args"]["name"]
                      for e in loaded["traceEvents"]
                      if e.get("ph") == "M"}
        assert {"replica-0", "replica-1", "router"} <= meta_names
        cap = loaded["capture"]
        assert set(cap["workers"]) == {"replica-0", "replica-1",
                                       "router"}
        assert len(cap["pids"]) >= 3

        # the frontend serves the same bundle over HTTP
        with urllib.request.urlopen(
                f"http://127.0.0.1:{fe.port}"
                f"/debug/profile?seconds=0.2", timeout=120) as r:
            doc = json.loads(r.read())
        assert doc["traceEvents"]
        http_pids = {e["pid"] for e in doc["traceEvents"]
                     if e.get("ph") != "M"}
        assert len(http_pids - {router_pid}) >= 2

        # build info rides the cluster scrape for every replica
        snap = cluster.scrape()
        by_name = {e["name"]: e for e in snap}
        build = by_name.get("paddle_tpu_build_info")
        assert build is not None
        replicas_with_info = {s["labels"][0]
                              for s in build["samples"]}
        assert {"replica-0", "replica-1"} <= replicas_with_info
    finally:
        fe.stop()
        cluster.stop()
