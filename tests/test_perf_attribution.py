"""What `observability/perf.py` keeps (ISSUE 18, cut down by ISSUE 26):
the build-info gauge on every scrape, and cluster-wide on-demand
profiler capture merged into one Perfetto-loadable bundle.

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
