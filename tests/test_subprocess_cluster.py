"""Process-isolated serving replicas: supervision, crash containment,
and warm restart via the persistent compile cache.

The acceptance e2e runs 3 REAL worker processes under continuous load,
SIGKILLs one, and proves: the supervisor replaces it (backoff), every
request ends token-exact or with a typed error, and the replacement's
warm restart-to-serving time (persistent-cache hits) is measurably
below the cold one recorded in the same test. The crash-loop chaos
test proves a persistently-failing spawn trips the circuit breaker
instead of restart-looping.
"""

import json
import os
import time

import numpy as np
import pytest

import paddle_tpu as paddle
from paddle_tpu.distributed.rpc import RpcEndpoint
from paddle_tpu.distributed.watchdog import FileStore
from paddle_tpu.inference.cluster import (ServingCluster,
                                          SubprocessReplica)
from paddle_tpu.inference.serving import AdmissionError, DeadlineExceeded
from paddle_tpu.models import LlamaForCausalLM, tiny_llama_config
from paddle_tpu.testing import faults

# big enough that XLA backend-compile time (what the persistent cache
# saves) dominates process startup; small enough for CPU CI
_CFG = dict(vocab_size=512, hidden_size=256, intermediate_size=512,
            num_hidden_layers=4, num_attention_heads=4,
            num_key_value_heads=2)
_ENGINE = dict(max_batch=2, page_size=8, num_pages=48)
_SPEC = {"model": {"kind": "tiny_llama", "seed": 0, "config": _CFG},
         "engine": _ENGINE}


@pytest.fixture(scope="module")
def model():
    paddle.seed(0)
    m = LlamaForCausalLM(tiny_llama_config(**_CFG))
    m.eval()
    return m


@pytest.fixture(scope="module")
def shared_cache(tmp_path_factory):
    """One compile cache + shape registry for the non-TTFT tests, so
    only the first worker of the module pays a cold compile."""
    d = tmp_path_factory.mktemp("warm")
    return {"JAX_PLATFORMS": "cpu",
            "JAX_COMPILATION_CACHE_DIR": str(d / "cache"),
            "PADDLE_TPU_SHAPE_REGISTRY": str(d / "shapes.json")}


@pytest.fixture(autouse=True)
def _clean_faults():
    faults.reset()
    yield
    os.environ.pop(faults.PLAN_ENV, None)
    faults.reset()


def _reference_continuation(model, prompt, n):
    ids = paddle.to_tensor(np.asarray([prompt], np.int64))
    out = model.generate(ids, max_new_tokens=n)
    return np.asarray(out._data)[0, len(prompt):].tolist()


def _wait(cond, timeout, what):
    deadline = time.time() + timeout
    while time.time() < deadline:
        if cond():
            return
        time.sleep(0.1)
    raise AssertionError(f"timed out waiting for {what}")


# ---------------------------------------------------------------------
# the dynamic rpc mesh (fast, in-process)
# ---------------------------------------------------------------------
class TestRpcEndpoint:
    def test_typed_error_crosses_the_wire(self):
        master = RpcEndpoint("router", is_master=True, port=0)
        worker = RpcEndpoint("w0", port=master.port)
        try:
            assert master.call_sync("w0", _add, (2, 3), timeout=20) == 5
            with pytest.raises(AdmissionError) as ei:
                master.call_sync("w0", _shed, (), timeout=20)
            assert ei.value.retry_after == 0.5
            assert ei.value.reason == "backlog full"
        finally:
            worker.stop()
            master.stop()

    def test_dead_peer_times_out_typed(self):
        from paddle_tpu.distributed.rpc import RpcTimeoutError

        master = RpcEndpoint("router", is_master=True, port=0)
        try:
            with pytest.raises(RpcTimeoutError) as ei:
                master.call_sync("nobody", _add, (1, 1), timeout=0.5)
            assert ei.value.to == "nobody"
        finally:
            master.stop()

    def test_replacement_incarnation_resumes_mailbox(self):
        """A fresh endpoint reusing a dead incarnation's NAME must
        resume the store's seq counter — starting at 0 would wait
        forever on seqs the corpse already consumed."""
        master = RpcEndpoint("router", is_master=True, port=0)
        w1 = RpcEndpoint("w0", port=master.port)
        try:
            for i in range(3):
                assert master.call_sync("w0", _add, (i, 1),
                                        timeout=20) == i + 1
            w1.stop()               # incarnation 1 dies
            w2 = RpcEndpoint("w0", port=master.port)
            try:
                assert master.call_sync("w0", _add, (40, 2),
                                        timeout=20) == 42
            finally:
                w2.stop()
        finally:
            master.stop()


def _add(a, b):
    return a + b


def _shed():
    raise AdmissionError("backlog full", live=2, max_batch=2,
                         free_pages=0, num_pages=16, retries=0,
                         retry_after=0.5)


# ---------------------------------------------------------------------
# acceptance e2e: SIGKILL under load, failover, warm replacement
# ---------------------------------------------------------------------
def test_e2e_sigkill_failover_and_warm_restart(model, tmp_path):
    """3 subprocess replicas under continuous load survive a SIGKILL of
    one worker process: the supervisor replaces it with backoff, every
    request completes token-exact or ends with a typed error, and the
    replacement's warm restart TTFT (persistent compile cache hits) is
    measurably below the cold TTFT recorded in the same test."""
    env = {"JAX_PLATFORMS": "cpu",
           "JAX_COMPILATION_CACHE_DIR": str(tmp_path / "cache"),
           "PADDLE_TPU_SHAPE_REGISTRY": str(tmp_path / "shapes.json")}
    cluster = ServingCluster(
        engine_spec=_SPEC, num_replicas=3,
        store_path=str(tmp_path / "members"),
        ttl=10.0, monitor_interval=0.05, restart_backoff=0.05,
        restart_backoff_max=1.0, spawn_grace=300.0, failover_budget=5,
        subprocess_env=env, log_dir=str(tmp_path / "logs")).start()
    creqs = []
    try:
        _wait(lambda: all(r.ready()
                          for r in cluster.replicas().values()),
              300, "3 subprocess replicas ready")
        cold_ttft = {rid: rep.restart_ttft
                     for rid, rep in cluster.replicas().items()}
        assert all(v is not None for v in cold_ttft.values())

        def mk_prompt(i):
            rng = np.random.RandomState(1000 + i)
            return rng.randint(0, _CFG["vocab_size"], (3 + i % 4,)) \
                .tolist()

        # phase 1: steady load
        creqs += [cluster.submit(mk_prompt(i), max_new_tokens=4)
                  for i in range(6)]

        # phase 2: SIGKILL one worker PROCESS mid-traffic
        creqs += [cluster.submit(mk_prompt(6 + i), max_new_tokens=4)
                  for i in range(3)]
        victim_id = creqs[-1].replica_id or "replica-0"
        victim = cluster.replicas()[victim_id]
        pid = victim._proc.pid
        victim.kill()                       # real SIGKILL, no goodbye
        creqs += [cluster.submit(mk_prompt(9 + i), max_new_tokens=4)
                  for i in range(3)]

        # the supervisor replaces the dead process (fresh pid)
        _wait(lambda: (cluster.replicas()[victim_id].alive()
                       and cluster.replicas()[victim_id].ready()
                       and cluster.replicas()[victim_id]._proc.pid
                       != pid),
              240, "killed replica replaced")
        replacement = cluster.replicas()[victim_id]
        creqs += [cluster.submit(mk_prompt(12 + i), max_new_tokens=4)
                  for i in range(2)]

        # zero dropped: every request ends terminal — completed
        # (token-exact) or a TYPED error; none lost, none stuck
        for c in creqs:
            assert c.wait(timeout=300), f"request stuck: {c.status}"
        completed = 0
        for c in creqs:
            if c.status == "completed":
                completed += 1
                want = _reference_continuation(
                    model, list(c.prompt_ids), 4)
                assert c.output_ids == want
            else:
                assert isinstance(
                    c.error, (AdmissionError, DeadlineExceeded)), \
                    (c.status, c.error)
        assert completed >= len(creqs) - 2

        # warm restart beats cold: the replacement pre-warmed the
        # registry-recorded programs against the persistent cache
        warm = replacement.restart_ttft
        cold = cold_ttft[victim_id]
        assert warm is not None and warm < cold, (warm, cold)
        assert replacement.cache_stats is not None \
            and replacement.cache_stats["hits"] > 0, \
            replacement.cache_stats
    finally:
        cluster.stop()


# ---------------------------------------------------------------------
# crash-loop chaos: spawn fails every time -> circuit breaker
# ---------------------------------------------------------------------
def test_crash_loop_spawn_fault_quarantines(model, tmp_path,
                                            shared_cache):
    """A serve.spawn fault plan fails every spawn of replica-0: the
    breaker quarantines it after N attempts (metric asserted) and the
    surviving replica keeps serving — typed backpressure, no restart
    storm, no lost requests."""
    from paddle_tpu.observability import metrics as om

    q0 = om.counter("cluster_replica_quarantined_total").value \
        if om.enabled() else 0
    os.environ[faults.PLAN_ENV] = json.dumps(
        [{"point": "serve.spawn", "action": "raise", "exc": "OSError",
          "path": "replica-0"}])
    faults.reset()
    cluster = ServingCluster(
        engine_spec=_SPEC, num_replicas=2,
        store_path=str(tmp_path / "members"), ttl=10.0,
        monitor_interval=0.02, restart_backoff=0.01,
        restart_backoff_max=0.05, breaker_threshold=3,
        breaker_window=60.0, spawn_grace=300.0,
        subprocess_env=shared_cache,
        log_dir=str(tmp_path / "logs")).start()
    try:
        _wait(lambda: "replica-0" in cluster.quarantined(), 60,
              "breaker quarantine")
        if om.enabled():
            assert om.counter(
                "cluster_replica_quarantined_total").value > q0
        rep0 = cluster.replicas()["replica-0"]
        spawns = rep0._spawns
        time.sleep(0.5)
        assert rep0._spawns == spawns, "restart storm past the breaker"
        # the surviving replica serves, token-exact
        _wait(lambda: cluster.replicas()["replica-1"].ready(), 240,
              "surviving replica ready")
        c = cluster.submit([5, 6, 7], max_new_tokens=2)
        assert c.result(timeout=240) \
            == _reference_continuation(model, [5, 6, 7], 2)
        assert c.replica_id == "replica-1"
    finally:
        cluster.stop()


# ---------------------------------------------------------------------
# membership hygiene on abnormal vs clean exit
# ---------------------------------------------------------------------
def _standalone_replica(rid, tmp_path, shared_cache, ttl):
    endpoint = RpcEndpoint("driver", is_master=True, port=0)
    store_path = str(tmp_path / "members")
    store = FileStore(store_path, ttl=ttl)
    rep = SubprocessReplica(
        rid, _SPEC, endpoint, store, store_path, ttl=ttl,
        env=shared_cache, log_dir=str(tmp_path / "logs"))
    return endpoint, store, rep


def test_sigkill_stamp_ages_out_within_ttl(tmp_path, shared_cache):
    """A SIGKILLed worker process never deregisters — its membership
    stamp must age out of hosts() within the TTL (the heartbeat
    sidecar died with the process; nothing refreshes the stamp)."""
    ttl = 1.0
    endpoint, store, rep = _standalone_replica(
        "k0", tmp_path, shared_cache, ttl)
    try:
        rep.start()
        _wait(lambda: "k0" in store.hosts(), 240, "worker registered")
        rep.kill()
        _wait(lambda: rep._proc.poll() is not None, 20, "process gone")
        t0 = time.monotonic()
        _wait(lambda: "k0" not in store.hosts(), ttl + 5.0,
              "stamp aged out")
        # aged out by TTL, not deregistered: the file is still there
        assert os.path.exists(
            os.path.join(str(tmp_path / "members"), "k0"))
        assert time.monotonic() - t0 <= ttl + 5.0
    finally:
        rep.kill()
        endpoint.stop()


def test_clean_stop_deregisters_immediately(tmp_path, shared_cache):
    """A clean stop exits 0 AND removes the stamp file — a deliberate
    shutdown says goodbye instead of leaning on the TTL."""
    endpoint, store, rep = _standalone_replica(
        "c0", tmp_path, shared_cache, 30.0)
    try:
        rep.start()
        _wait(lambda: "c0" in store.hosts(), 240, "worker registered")
        rep.stop()
        assert rep.exit_code == 0
        _wait(lambda: not os.path.exists(
            os.path.join(str(tmp_path / "members"), "c0")), 10,
            "stamp removed")
    finally:
        rep.kill()
        endpoint.stop()
