"""Partition-tolerant control plane (ISSUE 11): network fault
injection, at-least-once rpc with dedup, epoch-fenced membership, and
the seeded chaos smoke/soak.

The fast smoke runs on every PR (tier-1): a 3-replica in-process
cluster under a fixed-seed fault schedule — heartbeat partition of one
replica, jittered heartbeat delays, one SIGKILL-style death mid-load —
finishes every request completed-token-exact or typed, with stale-epoch
rejections observed during the partition, allocator free counts
restored, and no healthy replica quarantined. The full subprocess soak
(real worker processes + rpc-level drops/delays) is marked ``slow``.
"""

import json
import os
import time

import numpy as np
import pytest

import paddle_tpu as paddle
from paddle_tpu.distributed.rpc import (RpcEndpoint, RpcTimeoutError,
                                        _FutureReply)
from paddle_tpu.distributed.watchdog import FileStore, StaleEpochError
from paddle_tpu.inference.cluster import (ClusterRequest, EngineReplica,
                                          ReplicaLostError,
                                          ServingCluster)
from paddle_tpu.inference.serving import (AdmissionError,
                                          DeadlineExceeded,
                                          LlamaServingEngine)
from paddle_tpu.models import LlamaForCausalLM, tiny_llama_config
from paddle_tpu.observability import metrics as om
from paddle_tpu.testing import faults


@pytest.fixture(scope="module")
def model():
    paddle.seed(0)
    m = LlamaForCausalLM(tiny_llama_config())
    m.eval()
    return m


def _factory(model, **kw):
    kw.setdefault("max_batch", 2)
    kw.setdefault("page_size", 8)
    kw.setdefault("num_pages", 48)
    return lambda: LlamaServingEngine(model, **kw)


def _reference_continuation(model, prompt, n):
    ids = paddle.to_tensor(np.asarray([prompt], np.int64))
    out = model.generate(ids, max_new_tokens=n)
    return np.asarray(out._data)[0, len(prompt):].tolist()


def _wait(cond, timeout, what):
    deadline = time.time() + timeout
    while time.time() < deadline:
        if cond():
            return
        time.sleep(0.05)
    raise AssertionError(f"timed out waiting for {what}")


@pytest.fixture(autouse=True)
def _clean_faults():
    faults.reset()
    yield
    os.environ.pop(faults.PLAN_ENV, None)
    faults.reset()


def _plan(rules):
    os.environ[faults.PLAN_ENV] = json.dumps(rules)
    faults.reset()


# ---------------------------------------------------------------------
# fault-plan validation (satellite): a typo'd chaos plan fails loudly
# at parse time instead of silently never firing
# ---------------------------------------------------------------------
class TestPlanValidation:
    def test_unknown_rule_key_rejected(self):
        with pytest.raises(ValueError, match="unknown fault rule key"):
            faults.FaultPlan([{"point": "rename", "action": "raise",
                               "setp": 3}])

    def test_unknown_network_rule_key_rejected(self):
        with pytest.raises(ValueError, match="unknown network fault"):
            faults.FaultPlan([{"point": "rpc.send", "action": "drop",
                               "sorce": "router"}])

    def test_unregistered_point_rejected(self):
        with pytest.raises(ValueError, match="unregistered fault point"):
            faults.FaultPlan([{"point": "serve.spwan",
                               "action": "raise"}])

    def test_unregistered_network_point_rejected(self):
        with pytest.raises(ValueError,
                           match="unregistered network fault point"):
            faults.FaultPlan([{"point": "rpc.snd", "action": "drop"}])

    def test_network_action_at_process_point_rejected(self):
        # "drop" routes the spec to NetworkRule, whose point registry
        # does not contain process points
        with pytest.raises(ValueError, match="unregistered network"):
            faults.FaultPlan([{"point": "rename", "action": "drop"}])

    def test_typod_env_plan_fails_at_first_fire(self):
        _plan([{"point": "rename", "action": "raise"}])
        faults.plan()       # valid plan parses
        _plan([{"point": "renme", "action": "raise"}])
        with pytest.raises(ValueError, match="unregistered fault point"):
            faults.fire("anything")

    def test_probability_out_of_range_rejected(self):
        with pytest.raises(ValueError, match="outside"):
            faults.FaultPlan([{"point": "rpc.send", "action": "drop",
                               "p": 1.5}])

    def test_seeded_probability_replays_identically(self):
        spec = {"point": "rpc.send", "action": "drop", "p": 0.5,
                "seed": 11}
        draws = []
        for _ in range(2):
            rule = faults.NetworkRule(spec)
            draws.append([rule.matches("rpc.send", "a", "b", None)
                          for _ in range(32)])
        assert draws[0] == draws[1]
        assert any(draws[0]) and not all(draws[0])


# ---------------------------------------------------------------------
# rpc: wait(None) cap (satellite), retries, dedup under forced
# duplicate delivery (acceptance)
# ---------------------------------------------------------------------
class TestRpcTimeoutCap:
    def test_wait_none_with_none_call_timeout_hits_default_cap(
            self, monkeypatch):
        """The docstring's 'never an indefinite block': a call made
        with timeout=None still raises a typed RpcTimeoutError at the
        PADDLE_TPU_RPC_DEFAULT_TIMEOUT cap."""
        monkeypatch.setenv("PADDLE_TPU_RPC_DEFAULT_TIMEOUT", "0.1")
        fut = _FutureReply(to="w1", seq=4, timeout=None)
        t0 = time.perf_counter()
        with pytest.raises(RpcTimeoutError) as ei:
            fut.wait()
        assert time.perf_counter() - t0 < 5.0
        assert ei.value.timeout == 0.1

    def test_bad_env_value_falls_back_to_default(self, monkeypatch):
        from paddle_tpu.distributed import rpc as rpc_mod

        monkeypatch.setenv("PADDLE_TPU_RPC_DEFAULT_TIMEOUT", "soon")
        assert rpc_mod._default_rpc_timeout() == rpc_mod._DEFAULT_TIMEOUT


_HANDLED = []


def _count_call(x):
    _HANDLED.append(x)
    return x * 2


_NATIVE = pytest.mark.skipif(
    not __import__("paddle_tpu.native", fromlist=["available"])
    .available(), reason="needs native store")


@_NATIVE
class TestRpcAtLeastOnce:
    @pytest.fixture()
    def mesh(self):
        master = RpcEndpoint("router", is_master=True, port=0)
        worker = RpcEndpoint("w0", port=master.port)
        _HANDLED.clear()
        yield master
        worker.stop()
        master.stop()

    def test_forced_duplicate_delivery_executes_once(self, mesh):
        """Acceptance: a forced duplicate rpc delivery executes its
        handler exactly once — the redelivery is answered from the
        reply cache (rpc_duplicate_deliveries_total asserts the
        cache hit)."""
        d0 = om.counter("rpc_duplicate_deliveries_total").value
        _plan([{"point": "rpc.send", "action": "duplicate",
                "src": "router", "dst": "w0", "count": 1}])
        assert mesh.call_sync("w0", _count_call, (5,), timeout=20) == 10
        _wait(lambda: om.counter(
            "rpc_duplicate_deliveries_total").value == d0 + 1,
            20, "duplicate delivery served from the reply cache")
        assert _HANDLED == [5]      # handler ran ONCE

    def test_dropped_send_is_retried(self, mesh):
        r0 = om.counter("rpc_retries_total").value
        _plan([{"point": "rpc.send", "action": "drop",
                "src": "router", "dst": "w0", "count": 1}])
        assert mesh.call_sync("w0", _count_call, (3,), timeout=5) == 6
        assert om.counter("rpc_retries_total").value > r0
        assert _HANDLED == [3]

    def test_lost_reply_retry_is_exactly_once_effective(self, mesh):
        """A reply lost in the network forces a retry; the peer dedups
        the redelivered request and republishes the cached reply — the
        handler never runs twice."""
        d0 = om.counter("rpc_duplicate_deliveries_total").value
        _plan([{"point": "rpc.reply", "action": "drop",
                "dst": "router", "count": 1}])
        assert mesh.call_sync("w0", _count_call, (7,), timeout=5) == 14
        assert _HANDLED == [7]
        assert om.counter(
            "rpc_duplicate_deliveries_total").value == d0 + 1

    def test_retries_exhausted_is_typed(self, mesh):
        with pytest.raises(RpcTimeoutError) as ei:
            mesh.call_sync("nobody", _count_call, (1,), timeout=0.3,
                           retries=1)
        assert ei.value.to == "nobody"

    def test_handler_error_is_terminal_not_retried(self, mesh):
        with pytest.raises(ValueError, match="boom"):
            mesh.call_sync("w0", _boom, (), timeout=20)
        assert _HANDLED == ["boom"]     # ran once, no retry


def _boom():
    _HANDLED.append("boom")
    raise ValueError("boom")


# ---------------------------------------------------------------------
# epoch-fenced membership (tentpole piece 3)
# ---------------------------------------------------------------------
class TestEpochFencing:
    def test_stale_epoch_heartbeat_rejected_typed(self, tmp_path):
        """Regression (satellite): a heartbeat stamped with a fenced
        epoch raises StaleEpochError and counts the rejection — the
        old incarnation can never resurrect its stamp."""
        store = FileStore(str(tmp_path / "m"), ttl=30.0)
        e1 = store.next_epoch("r0")
        store.register("r0", epoch=e1)
        assert store.heartbeat("r0", epoch=e1) is True
        e2 = store.next_epoch("r0")
        store.register("r0", epoch=e2)
        c0 = om.counter("cluster_stale_epoch_rejections_total").value
        with pytest.raises(StaleEpochError) as ei:
            store.heartbeat("r0", epoch=e1)
        assert (ei.value.host_id, ei.value.epoch, ei.value.current) \
            == ("r0", e1, e2)
        if om.enabled():
            assert om.counter(
                "cluster_stale_epoch_rejections_total").value > c0

    def test_fence_survives_deregistration(self, tmp_path):
        """The kill-and-replace window: the supervisor sweeps the dead
        replica's stamp, and the old incarnation STILL cannot
        re-register — the epoch counter outlives the stamp."""
        store = FileStore(str(tmp_path / "m"), ttl=30.0)
        e1 = store.next_epoch("r0")
        store.register("r0", epoch=e1)
        store.deregister("r0")
        store.next_epoch("r0")          # the replacement's bump
        with pytest.raises(StaleEpochError):
            store.register("r0", epoch=e1)
        assert store.hosts() == []

    def test_epoch_counter_is_monotonic_and_survives(self, tmp_path):
        store = FileStore(str(tmp_path / "m"))
        assert store.epoch_of("a") is None
        assert [store.next_epoch("a") for _ in range(3)] == [1, 2, 3]
        assert store.epoch_of("a") == 3
        # a second store handle on the same dir sees the same counter
        assert FileStore(str(tmp_path / "m")).next_epoch("a") == 4

    def test_stale_epoch_submit_rejected(self, model, tmp_path):
        """Regression (satellite): a submission stamped with a stale
        epoch is rejected typed — a stale router view or a fenced-out
        incarnation can never accept work meant for its successor."""
        store = FileStore(str(tmp_path / "m"), ttl=30.0)
        rep = EngineReplica("r0", _factory(model), store=store,
                            ttl=30.0)
        rep.start()
        try:
            assert rep.epoch == 1
            c0 = om.counter(
                "cluster_stale_epoch_rejections_total").value
            creq = ClusterRequest([1, 2], max_new_tokens=1)
            creq._t_submit = time.perf_counter()
            with pytest.raises(StaleEpochError):
                rep.submit(creq, epoch=0)
            if om.enabled():
                assert om.counter(
                    "cluster_stale_epoch_rejections_total").value > c0
            # the current epoch is accepted and serves normally
            rep.submit(creq, epoch=rep.epoch)
            assert creq.wait(timeout=240)
            assert creq.status == "completed"
        finally:
            rep.stop()

    def test_restart_bumps_epoch(self, model, tmp_path):
        store = FileStore(str(tmp_path / "m"), ttl=30.0)
        rep = EngineReplica("r0", _factory(model), store=store,
                            ttl=30.0)
        rep.start()
        try:
            assert rep.epoch == 1
            rep.stop_worker()
            rep.restart()
            assert rep.epoch == 2       # kill-and-replace fences
        finally:
            rep.stop()

    def test_worker_submit_handler_rejects_stale_epoch(self):
        """The subprocess boundary: _worker_submit refuses a spec
        stamped with an epoch other than the live incarnation's (the
        error travels pickled through the rpc error reply)."""
        import pickle

        from paddle_tpu.inference import replica_worker as rw

        class _Rep:
            epoch = 3

            def submit(self, creq, epoch=None):
                if epoch is not None and int(epoch) != self.epoch:
                    raise StaleEpochError("r0", int(epoch), self.epoch)

        state = rw._WorkerState("r0", _Rep())
        old = rw._WORKER
        rw._WORKER = state
        try:
            spec = {"prompt_ids": [1], "max_new_tokens": 1,
                    "epoch": 2}
            with pytest.raises(StaleEpochError) as ei:
                rw._worker_submit(spec)
            e2 = pickle.loads(pickle.dumps(ei.value))
            assert type(e2) is StaleEpochError and e2.current == 3
            assert rw._worker_submit({"prompt_ids": [1],
                                      "max_new_tokens": 1,
                                      "epoch": 3})
        finally:
            rw._WORKER = old


# ---------------------------------------------------------------------
# duplicate-completion suppression (tentpole piece 4)
# ---------------------------------------------------------------------
class TestDuplicateCompletionSuppression:
    def test_second_terminal_report_is_suppressed_token_exact(self):
        """A request that completes on both the orphaned and the
        replacement replica emits exactly once — the first terminal
        state wins, later reports are suppressed and counted."""
        from paddle_tpu.inference.serving import Request

        creq = ClusterRequest([1, 2, 3], max_new_tokens=2)
        creq._t_submit = time.perf_counter()
        first = Request([1, 2, 3], max_new_tokens=2)
        first.output_ids = [7, 8]
        first.status = "completed"
        second = Request([1, 2, 3], max_new_tokens=2)
        second.output_ids = [7, 8]
        second.status = "completed"
        d0 = om.counter(
            "cluster_duplicate_completions_suppressed_total").value
        assert creq._finish_from(first) is True
        assert creq._finish_from(second) is False
        assert creq.output_ids == [7, 8]        # token-exact, once
        assert creq._finish_remote("completed", [9, 9], None) is False
        assert creq.output_ids == [7, 8]        # late remote ignored
        if om.enabled():
            assert om.counter(
                "cluster_duplicate_completions_suppressed_total")\
                .value == d0 + 2


# ---------------------------------------------------------------------
# /healthz surfaces epoch + heartbeat age (satellite)
# ---------------------------------------------------------------------
def test_healthz_reports_epoch_and_heartbeat_age(model, tmp_path):
    import urllib.request

    cluster = ServingCluster(_factory(model), num_replicas=1,
                             store_path=str(tmp_path / "m"),
                             ttl=30.0).start()
    srv = cluster.start_http_server()
    try:
        with urllib.request.urlopen(
                f"http://127.0.0.1:{srv.port}/healthz", timeout=5) as r:
            doc = json.loads(r.read())
        info = doc["membership"]["replica-0"]
        assert info["epoch"] == 1
        assert info["heartbeat_age_seconds"] is not None
        assert info["heartbeat_age_seconds"] < 30.0
        assert info["alive"] is True and info["quarantined"] is False
    finally:
        srv.stop()
        cluster.stop()


# ---------------------------------------------------------------------
# chaos smoke (tier-1 acceptance): fixed-seed fault schedule on a
# 3-replica cluster — partition + jittered delays + one SIGKILL
# ---------------------------------------------------------------------
def test_chaos_smoke_partition_delay_kill(model, tmp_path):
    """Seeded chaos on a 3-replica in-process cluster: replica-1's
    heartbeats are fully partitioned for 1.5 s (it ages out and is
    replaced under a bumped epoch), replica-0's heartbeats see seeded
    random delays, and replica-2 is SIGKILLed mid-load. Every request
    ends completed-token-exact or with a typed error, a stale-epoch
    heartbeat from the partitioned incarnation is rejected typed
    (counter > 0), KV allocator free counts are fully restored, and no
    healthy replica is quarantined."""
    c0 = om.counter("cluster_stale_epoch_rejections_total").value
    _plan([
        {"point": "store.heartbeat", "action": "partition",
         "src": "replica-1", "seconds": 1.5},
        {"point": "store.heartbeat", "action": "delay",
         "src": "replica-0", "seconds": 0.05, "p": 0.5, "seed": 7},
    ])
    cluster = ServingCluster(
        _factory(model), num_replicas=3,
        store_path=str(tmp_path / "m"), ttl=0.6,
        monitor_interval=0.02, auto_replace=True, failover_budget=5,
        restart_backoff=0.02, restart_backoff_max=0.2).start()
    creqs = []
    try:
        v = model.config.vocab_size

        def mk_prompt(i):
            return np.random.RandomState(500 + i) \
                .randint(0, v, (3 + i % 3,)).tolist()

        # phase 1: load while the partition ages replica-1 out
        creqs += [cluster.submit(mk_prompt(i), max_new_tokens=3)
                  for i in range(4)]

        # the partitioned replica is detected dead and replaced under
        # a BUMPED epoch (the kill-and-replace fence)
        rep1 = cluster.replicas()["replica-1"]
        _wait(lambda: rep1.epoch >= 2 and rep1.ready(), 60,
              "partitioned replica replaced under a new epoch")

        # the partitioned OLD incarnation's heartbeat (epoch 1) after
        # the replacement registered: while the partition window still
        # drops it the beat is simply lost (False); the first beat
        # that gets THROUGH is rejected typed — never a resurrected
        # ghost stamp
        deadline = time.time() + 30
        rejected = False
        while time.time() < deadline and not rejected:
            try:
                accepted = cluster.store.heartbeat("replica-1",
                                                   epoch=1)
                assert accepted is False, \
                    "stale heartbeat resurrected a ghost stamp"
                time.sleep(0.1)     # partition still dropping
            except StaleEpochError:
                rejected = True
        assert rejected, "stale-epoch heartbeat never rejected"
        assert om.counter(
            "cluster_stale_epoch_rejections_total").value > c0
        # the replacement (not the fenced ghost) owns membership
        _wait(lambda: "replica-1" in cluster.store.hosts(), 60,
              "replacement back in membership")

        # phase 2: SIGKILL replica-2 mid-load (no goodbye)
        creqs += [cluster.submit(mk_prompt(4 + i), max_new_tokens=3)
                  for i in range(3)]
        cluster.replicas()["replica-2"].kill()
        creqs += [cluster.submit(mk_prompt(7 + i), max_new_tokens=3)
                  for i in range(3)]
        _wait(lambda: cluster.replicas()["replica-2"].alive(), 60,
              "SIGKILLed replica replaced")

        # every request terminal: completed token-exact or typed
        for c in creqs:
            assert c.wait(timeout=300), f"request stuck: {c.status}"
        completed = 0
        for c in creqs:
            if c.status == "completed":
                completed += 1
                assert c.output_ids == _reference_continuation(
                    model, list(c.prompt_ids), 3)
            else:
                assert isinstance(c.error, (AdmissionError,
                                            DeadlineExceeded,
                                            ReplicaLostError)), \
                    (c.status, c.error)
        assert completed >= len(creqs) - 2

        # no leaked KV pages: every live engine's allocator drains back
        # to fully free once the traffic is terminal
        def _pages_free():
            for rep in cluster.replicas().values():
                e = rep.engine
                if e is not None \
                        and e.alloc.free_pages != e.alloc.num_pages:
                    return False
            return True
        _wait(_pages_free, 30, "allocator free counts restored")

        # one death each is far under the breaker threshold: no
        # healthy replica was quarantined by the chaos
        assert cluster.quarantined() == set()
    finally:
        cluster.stop()


# ---------------------------------------------------------------------
# full chaos soak (slow): subprocess replicas + rpc-level drops/delays
# ---------------------------------------------------------------------
_CFG = dict(vocab_size=512, hidden_size=256, intermediate_size=512,
            num_hidden_layers=4, num_attention_heads=4,
            num_key_value_heads=2)
_SPEC = {"model": {"kind": "tiny_llama", "seed": 0, "config": _CFG},
         "engine": {"max_batch": 2, "page_size": 8, "num_pages": 48}}


@pytest.mark.slow
def test_chaos_soak_subprocess_rpc_faults(tmp_path):
    """The full soak: 3 REAL worker processes under a randomized (but
    seeded) schedule of rpc send/reply drops and delays, a heartbeat
    partition of one worker, and one SIGKILL. Every request finishes
    completed-token-exact or typed, rpc retries fire (at-least-once
    proven end to end), and no healthy replica is quarantined."""
    paddle.seed(0)
    model = LlamaForCausalLM(tiny_llama_config(**_CFG))
    model.eval()
    env = {"JAX_PLATFORMS": "cpu",
           "JAX_COMPILATION_CACHE_DIR": str(tmp_path / "cache"),
           "PADDLE_TPU_SHAPE_REGISTRY": str(tmp_path / "shapes.json")}
    r0 = om.counter("rpc_retries_total").value
    # the plan is inherited by the workers (heartbeat partition fires
    # in the worker's process; the rpc rules fire in the router's)
    _plan([
        {"point": "rpc.send", "action": "drop", "src": "router",
         "p": 0.15, "seed": 3},
        {"point": "rpc.send", "action": "delay", "src": "router",
         "seconds": 0.05, "p": 0.2, "seed": 4},
        {"point": "rpc.reply", "action": "drop", "dst": "router",
         "p": 0.1, "seed": 5},
        {"point": "store.heartbeat", "action": "partition",
         "src": "replica-1", "seconds": 3.0},
    ])
    cluster = ServingCluster(
        engine_spec=_SPEC, num_replicas=3,
        store_path=str(tmp_path / "members"), ttl=6.0,
        monitor_interval=0.05, restart_backoff=0.05,
        restart_backoff_max=1.0, spawn_grace=300.0, failover_budget=5,
        subprocess_env=env, log_dir=str(tmp_path / "logs")).start()
    creqs = []
    try:
        _wait(lambda: all(r.ready()
                          for r in cluster.replicas().values()),
              300, "3 subprocess replicas ready")

        def mk_prompt(i):
            return np.random.RandomState(900 + i) \
                .randint(0, _CFG["vocab_size"], (3 + i % 4,)).tolist()

        creqs += [cluster.submit(mk_prompt(i), max_new_tokens=4)
                  for i in range(6)]
        # SIGKILL one worker process mid-traffic
        victim_id = creqs[-1].replica_id or "replica-0"
        victim = cluster.replicas()[victim_id]
        pid = victim._proc.pid
        victim.kill()
        creqs += [cluster.submit(mk_prompt(6 + i), max_new_tokens=4)
                  for i in range(4)]
        _wait(lambda: (cluster.replicas()[victim_id].alive()
                       and cluster.replicas()[victim_id].ready()
                       and cluster.replicas()[victim_id]._proc.pid
                       != pid),
              240, "killed replica replaced")
        creqs += [cluster.submit(mk_prompt(10 + i), max_new_tokens=4)
                  for i in range(2)]

        for c in creqs:
            assert c.wait(timeout=300), f"request stuck: {c.status}"
        completed = 0
        for c in creqs:
            if c.status == "completed":
                completed += 1
                assert c.output_ids == _reference_continuation(
                    model, list(c.prompt_ids), 4)
            else:
                assert isinstance(c.error, (AdmissionError,
                                            DeadlineExceeded,
                                            ReplicaLostError)), \
                    (c.status, c.error)
        assert completed >= len(creqs) - 3
        # at-least-once proved end to end: losses forced resends
        assert om.counter("rpc_retries_total").value > r0
        assert cluster.quarantined() == set()
    finally:
        cluster.stop()
