"""One process per chip, and a compile cache that can be placed.

A chip belongs to one process at a time: a parent that has touched JAX
holds it, and a child that needs it then fails or hangs. So importing
the package — in a cluster parent, in the launcher — must leave the JAX
backend uninitialized. And the persistent compile cache goes where
``JAX_COMPILATION_CACHE_DIR`` says, else to one fixed path in the
checkout; both the serving engine and a ``jit.to_static`` step use it.
Each case runs in a fresh interpreter (the state under test is
process-wide).
"""

import os
import subprocess
import sys

import pytest

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def _fresh(code, **env):
    full = {k: v for k, v in os.environ.items()
            if k not in ("JAX_COMPILATION_CACHE_DIR",
                         "PADDLE_TPU_COMPILE_CACHE")}
    full.update(JAX_PLATFORMS="cpu", PYTHONPATH=REPO, **env)
    out = subprocess.run([sys.executable, "-c", code], env=full,
                         capture_output=True, text=True, timeout=300)
    assert out.returncode == 0, out.stderr[-2000:]
    return out.stdout.strip().splitlines()[-1]


@pytest.mark.parametrize("module", [
    "paddle_tpu", "paddle_tpu.inference.cluster",
    "paddle_tpu.distributed.launch", "paddle_tpu.inference.replica_worker"])
def test_import_leaves_backend_uninitialized(module):
    assert _fresh(
        f"import {module}\n"
        "import jax._src.xla_bridge as xb\n"
        "print(len(xb._backends))") == "0"


def test_default_generator_still_seeds_reproducibly():
    assert _fresh(
        "import paddle_tpu as paddle, numpy as np\n"
        "paddle.seed(7); a = np.asarray(paddle.rand([4])._data)\n"
        "paddle.seed(7); b = np.asarray(paddle.rand([4])._data)\n"
        "print(bool((a == b).all()))") == "True"


_TRAIN_STEP = """
import paddle_tpu as paddle, jax
from paddle_tpu.observability import compile_watch as cw
lin = paddle.nn.Linear(8, 8)
opt = paddle.optimizer.AdamW(parameters=lin.parameters())
@paddle.jit.to_static(state=[lin, opt])
def step(x):
    loss = lin(x).sum(); loss.backward(); opt.step(); opt.clear_grad()
    return loss
for _ in range(3):
    step(paddle.ones([2, 8]))
import os
st = cw.persistent_cache_stats()
print(st["dir"], jax.config.jax_compilation_cache_dir,
      len(os.listdir(st["dir"])) > 0, st["hits"] + st["misses"] > 0)
"""


def test_train_step_cache_follows_the_standard_variable(tmp_path):
    d = str(tmp_path / "placed")
    got = _fresh(_TRAIN_STEP, JAX_COMPILATION_CACHE_DIR=d).split()
    # jax read the variable itself; the code set no other directory
    assert got == [d, d, "True", "True"]


def test_cache_defaults_to_one_fixed_path_in_the_checkout():
    fixed = os.path.join(REPO, ".jax_cache")
    got = _fresh(_TRAIN_STEP).split()
    assert got == [fixed, fixed, "True", "True"]
    with open(os.path.join(REPO, ".gitignore")) as f:
        assert ".jax_cache/" in f.read().split()
