"""Test config: force the CPU backend with 8 virtual devices.

The tests run on the CPU wherever they are started: the CPU backend is
forced with ``jax.config.update`` (jax may already be imported when
conftest runs, and an environment edit would then be too late). 8
virtual CPU devices give the multi-chip mesh surface the sharding tests
need (SURVEY §4: the reference tests SPMD rules metadata-only on CPU).
"""

import os

import jax

os.environ["XLA_FLAGS"] = (os.environ.get("XLA_FLAGS", "")
                           + " --xla_force_host_platform_device_count=8")
jax.config.update("jax_platforms", "cpu")

import numpy as np  # noqa: E402
import pytest  # noqa: E402


@pytest.fixture(autouse=True)
def _seed():
    import paddle_tpu as paddle
    paddle.seed(1234)
    np.random.seed(1234)
    yield


# -- wedge guard: a serving engine stuck in a dispatch (or a drain that
#    never converges) must fail WITH a stack dump, not silently eat the
#    suite's global timeout. faulthandler dumps every thread's stack
#    after the per-test budget and exits, so CI sees where it hung. ----
_WEDGE_GUARD_MODULES = {"test_serving", "test_serving_lifecycle",
                        "test_cluster", "test_prefix_cache",
                        "test_subprocess_cluster",
                        "test_chunked_scheduler", "test_speculative",
                        "test_moe_serving", "test_partition_tolerance",
                        "test_ragged_attention", "test_fused_ce",
                        "test_weight_quant", "test_distributed_tracing",
                        "test_perf_attribution", "test_kv_tier",
                        "test_net_store"}

# per-module budgets where the default is wrong: subprocess-cluster
# tests legitimately wait out several worker-process startups (import +
# model build + compile each) inside ONE test, so their wedge budget is
# sized to the e2e's worst case, not the in-process default
_WEDGE_BUDGETS = {"test_subprocess_cluster": 700.0,
                  # the tracing e2e waits out a 3-worker subprocess
                  # cluster startup (import + model build + compile)
                  "test_distributed_tracing": 700.0,
                  # many engines per test (spec/int8 variants of the
                  # mixed program compile per geometry)
                  "test_speculative": 600.0,
                  # every fused-vs-unfused parity test compiles BOTH
                  # mixed programs (in-kernel write + scatter+read),
                  # several times fp/int8/spec per test — and the
                  # rope ladder tests compile THREE (rope-fused /
                  # fused-KV / two-op)
                  "test_chunked_scheduler": 700.0,
                  # the fused-rope parity suite compiles both the
                  # rope-fused and the post-rope Pallas programs per
                  # case (fp + q8)
                  "test_ragged_attention": 600.0,
                  # the slow chaos soak waits out several subprocess
                  # worker startups under injected rpc loss
                  "test_partition_tolerance": 700.0,
                  # donated train-step + memory-analysis tests compile
                  # several full fwd+bwd programs, and the Pallas parity
                  # tests run the interpreter
                  "test_fused_ce": 600.0,
                  # the quality-gate test fits a model on the bundled
                  # prompts (40 Adam steps) and the engine-knob tests
                  # build several serving engines
                  "test_weight_quant": 600.0,
                  # the capture e2e waits out a 2-worker subprocess
                  # cluster startup plus profiler windows
                  "test_perf_attribution": 700.0,
                  # the pause/resume exactness matrix compiles one
                  # engine per fp/int8 x spec-on/off variant, and the
                  # copy-chaos soak ping-pongs requests through slow
                  # injected D2H/H2D copies
                  "test_kv_tier": 600.0,
                  # the store chaos smoke waits out two standalone
                  # lease-server process startups (full package import
                  # each) plus the outage grace windows
                  "test_net_store": 600.0}


@pytest.fixture(autouse=True)
def _serving_wedge_guard(request):
    mod = request.module.__name__.rsplit(".", 1)[-1]
    if mod not in _WEDGE_GUARD_MODULES:
        yield
        return
    import faulthandler
    # default must exceed the largest legitimate per-test wait (the
    # SIGTERM subprocess test budgets up to ~301s of compile tolerance)
    env_budget = os.environ.get("PADDLE_TPU_TEST_WEDGE_TIMEOUT")
    budget = float(env_budget) if env_budget \
        else _WEDGE_BUDGETS.get(mod, 480.0)
    faulthandler.dump_traceback_later(budget, exit=True)
    try:
        yield
    finally:
        faulthandler.cancel_dump_traceback_later()


# -- fast/slow split (VERDICT r4 weak #9): the compile-heavy modules
#    dominate the 20-minute full run; `pytest -m "not slow"` is the
#    iteration loop, the full suite stays the CI gate -------------------
_SLOW_MODULES = {
    "test_llama", "test_bert", "test_pipeline", "test_serving",
    "test_moe", "test_ring_attention", "test_launch", "test_hapi",
    "test_vision_models", "test_jit", "test_jit_save", "test_rpc_misc",
    "test_ps", "test_checkpoint_dist", "test_amp", "test_fleet",
    "test_distributed", "test_autotune",
}


def pytest_collection_modifyitems(config, items):
    for item in items:
        mod = item.module.__name__.rsplit(".", 1)[-1]
        if mod in _SLOW_MODULES:
            item.add_marker(pytest.mark.slow)
