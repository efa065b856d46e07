"""Tests for paddle_tpu.distributed on 8 virtual CPU devices.

Mirrors the reference's layered distributed testing (SURVEY §4):
metadata-only placement tests (like test/auto_parallel/spmd_rules/
test_matmul_rule.py:26), virtual-mesh layout tests, TP-layer parity vs a
dense run, and collectives exercised inside shard_map.
"""

import numpy as np
import pytest
import jax
import jax.numpy as jnp
from jax.sharding import PartitionSpec, NamedSharding
from jax import shard_map

import paddle_tpu as paddle
import paddle_tpu.distributed as dist
from paddle_tpu.distributed import Shard, Replicate, Partial, ProcessMesh


def mesh2x4():
    return ProcessMesh(np.arange(8).reshape(2, 4), dim_names=["dp", "mp"])


# ---------------------------------------------------------------------------
# metadata-only placement tests (no device math)
# ---------------------------------------------------------------------------
class TestPartitionSpec:
    def test_shard_one_axis(self):
        m = mesh2x4()
        spec = dist.to_partition_spec(2, m, [Shard(0), Replicate()])
        assert spec == PartitionSpec("dp", None)

    def test_shard_both_axes(self):
        m = mesh2x4()
        spec = dist.to_partition_spec(2, m, [Shard(0), Shard(1)])
        assert spec == PartitionSpec("dp", "mp")

    def test_two_mesh_axes_same_tensor_dim(self):
        m = mesh2x4()
        spec = dist.to_partition_spec(2, m, [Shard(1), Shard(1)])
        assert spec == PartitionSpec(None, ("dp", "mp"))

    def test_replicate_all(self):
        m = mesh2x4()
        spec = dist.to_partition_spec(3, m, [Replicate(), Replicate()])
        assert spec == PartitionSpec(None, None, None)

    def test_length_mismatch_raises(self):
        with pytest.raises(ValueError):
            dist.to_partition_spec(2, mesh2x4(), [Shard(0)])

    def test_shard_dim_out_of_range(self):
        with pytest.raises(ValueError):
            dist.to_partition_spec(1, mesh2x4(), [Shard(3), Replicate()])

    def test_matmul_like_propagation(self):
        # the reference's matmul SPMD rule: X[b, k] @ W[k, n] with W
        # column-sharded -> out sharded on n. GSPMD derives it; assert the
        # layouts we'd feed it are what the rule table would say.
        m = mesh2x4()
        x_spec = dist.to_partition_spec(2, m, [Shard(0), Replicate()])
        w_spec = dist.to_partition_spec(2, m, [Replicate(), Shard(1)])
        assert x_spec == PartitionSpec("dp", None)
        assert w_spec == PartitionSpec(None, "mp")


class TestProcessMesh:
    def test_shape_names_ids(self):
        m = mesh2x4()
        assert m.shape == [2, 4]
        assert m.ndim == 2
        assert m.dim_names == ["dp", "mp"]
        assert m.process_ids == list(range(8))
        assert m.get_dim_size("mp") == 4

    def test_eq_hash(self):
        assert mesh2x4() == mesh2x4()
        assert hash(mesh2x4()) == hash(mesh2x4())
        other = ProcessMesh(np.arange(8).reshape(4, 2), ["dp", "mp"])
        assert mesh2x4() != other

    def test_to_jax_mesh(self):
        jm = mesh2x4().to_jax_mesh()
        assert jm.devices.shape == (2, 4)
        assert jm.axis_names == ("dp", "mp")

    def test_jax_mesh_cache_reused(self):
        m = mesh2x4()
        assert m.to_jax_mesh() is m.to_jax_mesh()

    def test_init_mesh(self):
        m = dist.init_mesh((2, 2, 2), ["pp", "dp", "mp"])
        assert m.shape == [2, 2, 2]
        assert m.get_dim_size("pp") == 2


# ---------------------------------------------------------------------------
# shard_tensor / reshard layouts
# ---------------------------------------------------------------------------
class TestShardTensor:
    def test_layout_committed(self):
        m = mesh2x4()
        x = paddle.ones([8, 16])
        xs = dist.shard_tensor(x, m, [Shard(0), Shard(1)])
        shard_shapes = {tuple(s.data.shape)
                        for s in xs._data.addressable_shards}
        assert shard_shapes == {(4, 4)}
        assert xs.is_dist and xs._placements == [Shard(0), Shard(1)]

    def test_values_preserved(self):
        m = mesh2x4()
        x = paddle.to_tensor(np.random.randn(8, 16).astype("float32"))
        xs = dist.shard_tensor(x, m, [Shard(1), Replicate()])
        np.testing.assert_array_equal(np.asarray(xs._data), x.numpy())

    def test_partial_rejected(self):
        with pytest.raises(ValueError):
            dist.shard_tensor(paddle.ones([4]), mesh2x4(),
                              [Partial(), Replicate()])

    def test_reshard_roundtrip(self):
        m = mesh2x4()
        x = paddle.to_tensor(np.random.randn(8, 16).astype("float32"))
        xs = dist.shard_tensor(x, m, [Shard(0), Replicate()])
        xr = dist.reshard(xs, m, [Replicate(), Shard(0)])
        np.testing.assert_array_equal(np.asarray(xr._data), x.numpy())
        shard_shapes = {tuple(s.data.shape)
                        for s in xr._data.addressable_shards}
        assert shard_shapes == {(2, 16)}

    def test_unshard(self):
        m = mesh2x4()
        xs = dist.shard_tensor(paddle.arange(0, 16, dtype="float32"), m,
                               [Shard(0), Replicate()])
        xu = dist.unshard_dtensor(xs)
        assert not getattr(xu, "is_dist", False)
        np.testing.assert_array_equal(
            np.asarray(xu._data), np.arange(16, dtype="float32"))

    def test_dtensor_from_fn(self):
        m = mesh2x4()
        xs = dist.dtensor_from_fn(paddle.zeros, m,
                                  [Replicate(), Replicate()], [4, 4])
        assert xs._data.shape == (4, 4)

    def test_grad_flows_through_shard(self):
        m = mesh2x4()
        w = paddle.framework.Parameter(jnp.ones((8, 8), jnp.float32))
        ws = dist.shard_tensor(w, m, [Replicate(), Shard(0)])
        x = paddle.ones([2, 8])
        y = paddle.matmul(x, ws)
        y.sum().backward()
        assert ws.grad is not None
        np.testing.assert_allclose(
            np.asarray(ws.grad._data), np.full((8, 8), 2.0), rtol=1e-6)


# ---------------------------------------------------------------------------
# TP layers: parity vs dense single-device run
# ---------------------------------------------------------------------------
class TestMpLayers:
    def _parity(self, make_parallel, make_dense, x_np):
        paddle.seed(7)
        dense = make_dense()
        paddle.seed(7)
        par = make_parallel()
        xd = paddle.to_tensor(x_np)
        xp = paddle.to_tensor(x_np)
        yd = dense(xd)
        yp = par(xp)
        np.testing.assert_allclose(np.asarray(yp._data), np.asarray(yd._data),
                                   rtol=1e-5, atol=1e-5)
        yd.sum().backward()
        yp.sum().backward()
        for pd, pp in zip(dense.parameters(), par.parameters()):
            assert pp.grad is not None
            np.testing.assert_allclose(np.asarray(pp.grad._data),
                                       np.asarray(pd.grad._data),
                                       rtol=1e-5, atol=1e-5)

    def test_column_parallel(self):
        m = mesh2x4()
        x = np.random.randn(4, 16).astype("float32")
        self._parity(
            lambda: dist.ColumnParallelLinear(16, 32, m, axis_name="mp"),
            lambda: paddle.nn.Linear(16, 32), x)

    def test_row_parallel(self):
        m = mesh2x4()
        x = np.random.randn(4, 32).astype("float32")
        self._parity(
            lambda: dist.RowParallelLinear(32, 16, m, axis_name="mp"),
            lambda: paddle.nn.Linear(32, 16), x)

    def test_vocab_parallel_embedding(self):
        m = mesh2x4()
        paddle.seed(3)
        dense = paddle.nn.Embedding(64, 16)
        paddle.seed(3)
        par = dist.VocabParallelEmbedding(64, 16, m, axis_name="mp")
        ids = paddle.to_tensor(np.array([[1, 5, 63], [0, 2, 8]], np.int64))
        np.testing.assert_allclose(np.asarray(par(ids)._data),
                                   np.asarray(dense(ids)._data), rtol=1e-6)

    def test_megatron_mlp_stack(self):
        # column(gather_output=False) -> row: out matches dense 2-layer MLP
        m = mesh2x4()
        paddle.seed(11)
        col = dist.ColumnParallelLinear(16, 64, m, axis_name="mp",
                                        gather_output=False)
        row = dist.RowParallelLinear(64, 16, m, axis_name="mp",
                                     input_is_parallel=True)
        paddle.seed(11)
        l1 = paddle.nn.Linear(16, 64)
        l2 = paddle.nn.Linear(64, 16)
        x = np.random.randn(4, 16).astype("float32")
        yp = row(paddle.nn.functional.relu(col(paddle.to_tensor(x))))
        yd = l2(paddle.nn.functional.relu(l1(paddle.to_tensor(x))))
        np.testing.assert_allclose(np.asarray(yp._data), np.asarray(yd._data),
                                   rtol=1e-4, atol=1e-5)


# ---------------------------------------------------------------------------
# collectives inside shard_map
# ---------------------------------------------------------------------------
class TestCollectives:
    def _mesh(self):
        return mesh2x4().to_jax_mesh()

    def test_all_reduce_sum(self):
        m = self._mesh()
        x = jnp.arange(8.0)

        def body(x):
            t = paddle.Tensor(x.reshape(()))
            out = dist.all_reduce(t, group="mp")
            return out._data.reshape(1)

        f = shard_map(body, mesh=m, in_specs=PartitionSpec(("dp", "mp")),
                      out_specs=PartitionSpec(("dp", "mp")))
        # groups of 4 along mp share a dp row: ranks 0-3 sum to 6, 4-7 to 22
        out = f(x)
        np.testing.assert_allclose(np.asarray(out),
                                   [6, 6, 6, 6, 22, 22, 22, 22])

    def test_all_gather(self):
        m = self._mesh()
        x = jnp.arange(8.0)

        def body(x):
            t = paddle.Tensor(x)   # shape (1,)
            outs = []
            dist.all_gather(outs, t, group="mp")
            assert len(outs) == 4
            return jnp.stack([o._data for o in outs]).reshape(4)

        f = shard_map(body, mesh=m, in_specs=PartitionSpec(("dp", "mp")),
                      out_specs=PartitionSpec(("dp", "mp")))
        out = np.asarray(f(x)).reshape(8, 4)
        np.testing.assert_allclose(out[0], [0, 1, 2, 3])
        np.testing.assert_allclose(out[4], [4, 5, 6, 7])

    def test_reduce_scatter(self):
        m = self._mesh()
        x = jnp.ones((8, 4))

        def body(x):
            src = paddle.Tensor(x.reshape(4))
            out = paddle.zeros([1])
            dist.reduce_scatter(out, src, group="mp")
            return out._data.reshape(1, 1)

        f = shard_map(body, mesh=m, in_specs=PartitionSpec(("dp", "mp")),
                      out_specs=PartitionSpec(("dp", "mp")))
        np.testing.assert_allclose(np.asarray(f(x)), np.full((8, 1), 4.0))

    def test_broadcast_from_src(self):
        m = self._mesh()
        x = jnp.arange(8.0)

        def body(x):
            t = paddle.Tensor(x.reshape(()))
            out = dist.broadcast(t, src=2, group="mp")
            return out._data.reshape(1)

        f = shard_map(body, mesh=m, in_specs=PartitionSpec(("dp", "mp")),
                      out_specs=PartitionSpec(("dp", "mp")))
        np.testing.assert_allclose(np.asarray(f(x)),
                                   [2, 2, 2, 2, 6, 6, 6, 6])

    def test_alltoall(self):
        m = self._mesh()
        x = jnp.arange(32.0).reshape(8, 4)

        def body(x):
            ins = [paddle.Tensor(x[0, i].reshape(1)) for i in range(4)]
            outs = []
            dist.alltoall(outs, ins, group="mp")
            return jnp.concatenate([o._data for o in outs]).reshape(1, 4)

        f = shard_map(body, mesh=m,
                      in_specs=PartitionSpec(("dp", "mp"), None),
                      out_specs=PartitionSpec(("dp", "mp"), None))
        out = np.asarray(f(x))
        # rank j in an mp group receives element j from each rank's list
        np.testing.assert_allclose(out[0], [0, 4, 8, 12])
        np.testing.assert_allclose(out[1], [1, 5, 9, 13])

    def test_p2p_shift_ring(self):
        m = self._mesh()
        x = jnp.arange(8.0)

        def body(x):
            got = dist.p2p.shift(x.reshape(()), "mp", offset=1, wrap=True)
            return got.reshape(1)

        f = shard_map(body, mesh=m, in_specs=PartitionSpec(("dp", "mp")),
                      out_specs=PartitionSpec(("dp", "mp")))
        # ring within each mp group of 4: rank i holds value of i-1 (mod 4)
        np.testing.assert_allclose(np.asarray(f(x)),
                                   [3, 0, 1, 2, 7, 4, 5, 6])

    def test_p2p_send_forward_edge_zeros(self):
        m = self._mesh()
        x = jnp.arange(8.0) + 1

        def body(x):
            got = dist.p2p.send_forward(x.reshape(()), "mp")
            return got.reshape(1)

        f = shard_map(body, mesh=m, in_specs=PartitionSpec(("dp", "mp")),
                      out_specs=PartitionSpec(("dp", "mp")))
        np.testing.assert_allclose(np.asarray(f(x)),
                                   [0, 1, 2, 3, 0, 5, 6, 7])


# ---------------------------------------------------------------------------
# shard_optimizer
# ---------------------------------------------------------------------------
class TestShardOptimizer:
    def test_accumulator_inherits_sharding(self):
        m = mesh2x4()
        lin = paddle.nn.Linear(16, 32)
        lin.weight = dist.shard_tensor(lin.weight, m, [Replicate(), Shard(1)])
        opt = paddle.optimizer.Adam(parameters=lin.parameters())
        opt = dist.shard_optimizer(opt)
        x = paddle.ones([4, 16])
        lin(x).sum().backward()
        opt.step()
        mom = opt._get_accumulator("moment1", lin.weight)
        assert mom._data.sharding.is_equivalent_to(
            lin.weight._data.sharding, 2)

    def test_idempotent(self):
        lin = paddle.nn.Linear(4, 4)
        opt = paddle.optimizer.SGD(parameters=lin.parameters())
        opt = dist.shard_optimizer(opt)
        wrapped = opt._add_accumulator
        opt = dist.shard_optimizer(opt)
        assert opt._add_accumulator is wrapped  # no double-wrap

    def test_shard_fn_overrides_accumulator_placement(self):
        """The shard_fn hook (reference api.py:1120 ShardingStage* use it
        to place optimizer state) must receive every accumulator and its
        returned replacement must be the one the update consumes."""
        m = mesh2x4()
        lin = paddle.nn.Linear(16, 32)
        lin.weight = dist.shard_tensor(lin.weight, m, [Replicate(), Shard(1)])
        seen = []

        def shard_fn(name, param, acc):
            seen.append((name, tuple(param.shape)))
            if name == "moment1" and tuple(acc.shape) == (16, 32):
                # override: replicate moment1 instead of inheriting Shard(1)
                return dist.shard_tensor(acc, m, [Replicate(), Replicate()])
            return None  # keep default for everything else

        opt = paddle.optimizer.Adam(parameters=lin.parameters())
        opt = dist.shard_optimizer(opt, shard_fn)
        x = paddle.ones([4, 16])
        lin(x).sum().backward()
        opt.step()
        assert ("moment1", (16, 32)) in seen
        mom1 = opt._get_accumulator("moment1", lin.weight)
        mom2 = opt._get_accumulator("moment2", lin.weight)
        assert mom1._placements == [Replicate(), Replicate()]
        assert mom2._data.sharding.is_equivalent_to(
            lin.weight._data.sharding, 2)
        # training still works: a second step consumes the replaced state
        lin(x).sum().backward()
        opt.step()


class TestEnv:
    def test_single_process_defaults(self):
        dist.init_parallel_env()
        assert dist.get_rank() == 0
        assert dist.get_world_size() == 1
        env = dist.ParallelEnv()
        assert env.nranks == 1
