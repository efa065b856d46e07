"""Grouped-GEMM kernel contract + the grouped MoE dispatch rebuilt on it.

Bars (ROADMAP item 4): the Pallas kernel (interpret mode on CPU) is
exact-parity with ``grouped_gemm_xla`` across every ragged shape —
empty experts, one-expert hot spots, tails not a multiple of the row
block — and the MoE layer's grouped path reproduces the dense GShard
formulation bit-for-bit including capacity-overflow drops, for top-1
and top-2 gates. ``supported()`` gates the kernel off-TPU (the XLA
reference serves), and the compile-watch / LRU / drop-metric
satellites hold.
"""

import numpy as np
import pytest

import jax
import jax.numpy as jnp

import paddle_tpu as paddle
from paddle_tpu.ops.grouped_gemm import (_grouped, grouped_gemm,
                                         grouped_gemm_xla, supported)


def _mk(e, c, k, n, seed=0):
    rng = np.random.RandomState(seed)
    x = jnp.asarray(rng.randn(e * c, k), jnp.float32)
    w = jnp.asarray(rng.randn(e, k, n) * 0.1, jnp.float32)
    return x, w


def _ref(x, w, gs):
    """Hand-rolled reference: per-group numpy matmul, zeros past len."""
    e, k, n = w.shape
    c = x.shape[0] // e
    x3 = np.asarray(x).reshape(e, c, k)
    out = np.zeros((e, c, n), np.float32)
    for ei in range(e):
        m = int(gs[ei])
        out[ei, :m] = x3[ei, :m] @ np.asarray(w[ei])
    return out.reshape(e * c, n)


class TestKernel:
    """The Pallas kernel itself (interpret mode on CPU)."""

    @pytest.mark.parametrize("gs", [
        [3, 0, 10, 7],          # empty group + full group + ragged tails
        [0, 0, 0, 0],           # every expert empty
        [10, 0, 0, 0],          # all rows on one expert
        [1, 1, 1, 1],
    ])
    def test_kernel_matches_reference(self, gs):
        e, c, k, n = 4, 10, 16, 24
        x, w = _mk(e, c, k, n)
        gsj = jnp.asarray(gs, jnp.int32)
        got = np.asarray(_grouped(x, w, gsj, use_kernel=True))
        np.testing.assert_allclose(got, _ref(x, w, gs), rtol=1e-5,
                                   atol=1e-5)
        # rows past each group's length are defined zeros
        g3 = got.reshape(e, c, n)
        for ei in range(e):
            assert np.all(g3[ei, int(gs[ei]):] == 0)

    def test_kernel_exact_parity_with_xla(self):
        e, c, k, n = 8, 40, 32, 64
        x, w = _mk(e, c, k, n, seed=1)
        gs = jnp.asarray(np.random.RandomState(2).randint(0, c + 1, (e,)),
                         jnp.int32)
        yk = _grouped(x, w, gs, use_kernel=True)
        yx = _grouped(x, w, gs, use_kernel=False)
        np.testing.assert_array_equal(np.asarray(yk), np.asarray(yx))

    def test_rows_not_multiple_of_block(self):
        # c = 5 -> row block rounds to 8 > c: one padded tile per
        # expert; the pad garbage must never leak into outputs
        e, c, k, n = 4, 5, 8, 8
        x, w = _mk(e, c, k, n, seed=3)
        gs = jnp.asarray([5, 2, 0, 3], jnp.int32)
        got = np.asarray(_grouped(x, w, gs, use_kernel=True))
        np.testing.assert_allclose(got, _ref(x, w, np.asarray(gs)),
                                   rtol=1e-5, atol=1e-5)

    def test_group_sizes_clamped_to_stride(self):
        # a group_len past the per-expert stride is clamped, not UB
        e, c, k, n = 2, 4, 8, 8
        x, w = _mk(e, c, k, n, seed=4)
        gs = jnp.asarray([99, 4], jnp.int32)
        got = np.asarray(_grouped(x, w, gs, use_kernel=True))
        np.testing.assert_allclose(got, _ref(x, w, [4, 4]), rtol=1e-5,
                                   atol=1e-5)

    def test_grad_matches_masked_einsum(self):
        e, c, k, n = 4, 6, 8, 16
        x, w = _mk(e, c, k, n, seed=5)
        gs = jnp.asarray([6, 0, 3, 5], jnp.int32)

        def loss_k(x, w):
            return jnp.sum(_grouped(x, w, gs, use_kernel=True) ** 2)

        def loss_ref(x, w):
            m = (jnp.arange(c)[None, :] < gs[:, None])[..., None]
            x3 = jnp.where(m, x.reshape(e, c, k), 0.0)
            return jnp.sum(jnp.einsum("eck,ekn->ecn", x3, w) ** 2)

        gk = jax.grad(loss_k, argnums=(0, 1))(x, w)
        gr = jax.grad(loss_ref, argnums=(0, 1))(x, w)
        for a, b in zip(gk, gr):
            np.testing.assert_allclose(np.asarray(a), np.asarray(b),
                                       rtol=1e-4, atol=1e-5)

    def test_supported_gates_off_tpu_and_on_shapes(self):
        e, c, k, n = 4, 8, 16, 16
        x, w = _mk(e, c, k, n)
        gs = jnp.asarray([8, 8, 8, 8], jnp.int32)
        # CPU backend: the kernel path is off (interpret mode would be
        # orders slower) — grouped_gemm transparently serves the XLA
        # reference
        assert supported(x, w, gs) is False
        # shape gates hold regardless of backend
        assert supported(x[:-1], w, gs) is False        # M % E != 0
        assert supported(x, w[:, :, :7], gs) is False   # N % 8 != 0
        assert supported(x, w, gs[:-1]) is False        # gs length

    def test_tensor_wrapper_falls_back_and_differentiates(self):
        e, c, k, n = 4, 8, 16, 16
        x, w = _mk(e, c, k, n, seed=6)
        gs = jnp.asarray([8, 3, 0, 5], jnp.int32)
        xt = paddle.to_tensor(np.asarray(x), stop_gradient=False)
        wt = paddle.to_tensor(np.asarray(w), stop_gradient=False)
        gt = paddle.to_tensor(np.asarray(gs))
        out = grouped_gemm(xt, wt, gt)         # CPU -> XLA fallback
        ref = grouped_gemm_xla(paddle.to_tensor(np.asarray(x)),
                               paddle.to_tensor(np.asarray(w)), gt)
        np.testing.assert_array_equal(out.numpy(), ref.numpy())
        out.sum().backward()
        assert xt.grad is not None and wt.grad is not None
        # dropped rows contribute no gradient
        xg = xt.grad.numpy().reshape(e, c, k)
        assert np.all(xg[2] == 0) and np.all(xg[1, 3:] == 0)


class TestGroupedMoEDispatch:
    """The MoE layer rebuilt on the grouped GEMM: parity with the dense
    GShard formulation, drops included."""

    @pytest.mark.parametrize("gate,cf", [
        ("switch", 1.0),        # top-1, capacity tight enough to drop
        ("gshard", 1.25),       # top-2
        ("switch", 0.25),       # heavy capacity overflow
    ])
    def test_grouped_equals_dense_with_drops(self, gate, cf):
        from paddle_tpu.incubate.moe import MoELayer

        rng = np.random.RandomState(0)
        paddle.seed(7)
        dense = MoELayer(16, 32, 4, gate=gate, capacity_factor=cf,
                         dispatch_mode="dense")
        paddle.seed(7)
        grouped = MoELayer(16, 32, 4, gate=gate, capacity_factor=cf,
                           dispatch_mode="ragged")
        x = rng.randn(24, 16).astype(np.float32)
        od = dense(paddle.to_tensor(x))
        og = grouped(paddle.to_tensor(x))
        np.testing.assert_allclose(od.numpy(), og.numpy(), atol=2e-5)
        np.testing.assert_allclose(float(dense.l_aux),
                                   float(grouped.l_aux), rtol=1e-6)

    def test_all_tokens_one_expert_and_empty_experts(self):
        from paddle_tpu.incubate.moe import MoELayer

        paddle.seed(8)
        dense = MoELayer(8, 16, 4, gate="switch", capacity_factor=4.0,
                         dispatch_mode="dense")
        paddle.seed(8)
        grouped = MoELayer(8, 16, 4, gate="switch", capacity_factor=4.0,
                           dispatch_mode="ragged")
        # bias the router so every token lands on one expert: three
        # experts see zero rows (empty groups), one sees them all
        for layer in (dense, grouped):
            gw = layer.gate_weight.numpy().copy()
            gw[:, 0] = 10.0
            layer.gate_weight.set_value(paddle.to_tensor(gw))
        x = paddle.to_tensor(
            np.random.RandomState(1).randn(8, 8).astype(np.float32))
        np.testing.assert_allclose(dense(x).numpy(), grouped(x).numpy(),
                                   atol=2e-5)

    def test_fn_cache_is_bounded_lru(self):
        from paddle_tpu.incubate.moe import MoELayer

        paddle.seed(9)
        moe = MoELayer(8, 16, 4, gate="switch")
        for n in range(1, 12):
            moe(paddle.to_tensor(np.ones((n, 8), np.float32)))
        assert len(moe._fns) == MoELayer.FN_CACHE_SIZE
        # most-recent token counts survive
        assert 11 in moe._fns and 1 not in moe._fns

    def test_forward_routes_through_compile_watch(self):
        from paddle_tpu.incubate.moe import MoELayer

        paddle.seed(10)
        moe = MoELayer(8, 16, 4, gate="switch")
        fn = moe.build_fn(16)
        assert getattr(fn, "_watch_name", None) == "moe_layer"
        assert moe.build_fn(16) is fn          # cached

    def test_drop_metrics_recorded(self):
        from paddle_tpu.incubate.moe import MoELayer
        from paddle_tpu.observability import metrics as om

        paddle.seed(11)
        # capacity_factor far below 1: drops guaranteed
        moe = MoELayer(8, 16, 4, gate="switch", capacity_factor=0.25)
        c = om.counter("moe_dropped_tokens_total", "")
        before = c.value
        moe(paddle.to_tensor(
            np.random.RandomState(2).randn(32, 8).astype(np.float32)))
        dropped = c.value - before
        assert dropped > 0
        g = om.gauge("moe_drop_fraction", "")
        assert 0.0 < g.value <= 1.0

    def test_drop_metrics_noop_when_disabled(self, monkeypatch):
        from paddle_tpu.incubate.moe import MoELayer
        from paddle_tpu.observability import metrics as om

        monkeypatch.setenv("PADDLE_TPU_METRICS", "0")
        paddle.seed(12)
        moe = MoELayer(8, 16, 4, gate="switch", capacity_factor=0.25)
        out = moe(paddle.to_tensor(
            np.random.RandomState(3).randn(32, 8).astype(np.float32)))
        assert tuple(out.shape) == (32, 8)      # still functional


# ---------------------------------------------------------------------------
# packed rows (rows sorted by expert, groups rounded to whole row tiles)
# ---------------------------------------------------------------------------
from paddle_tpu.ops import grouped_gemm as gg  # noqa: E402

def _packed_case(n, k, e, kdim, ndim, bm, empty=(), seed=0):
    rng = np.random.default_rng(seed)
    live = [x for x in range(e) if x not in empty]
    ids = np.stack([rng.permutation(live)[:k] for _ in range(n)]) \
        .astype(np.int32)
    x = rng.normal(size=(n, kdim)).astype(np.float32)
    w = rng.normal(size=(e, kdim, ndim)).astype(np.float32)
    pk = gg.pack_by_expert(jnp.asarray(ids), e, bm)
    xp = jnp.concatenate([jnp.asarray(x), jnp.zeros((1, kdim))])[
        pk["row_token"]]
    return ids, x, w, pk, xp


@pytest.mark.parametrize("n,k,e,bm,empty", [
    (37, 3, 8, 8, (5,)),            # an empty expert, ragged tails
    (5, 2, 16, 8, tuple(range(6, 16))),     # most experts empty
    (64, 2, 4, 16, ()),             # groups of several tiles
    (1, 1, 4, 8, (0, 1, 2)),        # one row in all
])
@pytest.mark.parametrize("impl", ["kernel", "xla"])
def test_packed_equals_the_strided_reference(n, k, e, bm, empty, impl):
    """The packed grouped GEMM (Pallas, interpreted here, and its XLA
    formulation) against ``grouped_gemm_xla`` over the same assignments
    laid out strided: the same rows of y, exactly the same mathematics
    (f32 operands here, so 1e-4 covers the order of the sums)."""
    ids, x, w, pk, xp = _packed_case(n, k, e, 128, 256, bm, empty)
    fn = gg._packed_kernel_impl if impl == "kernel" else gg._packed_xla_impl
    y = np.asarray(fn(xp, jnp.asarray(w), pk["tile_expert"],
                      pk["num_tiles"], bm))
    got = y[np.asarray(pk["dest"])]                      # [n, k, N]
    # the strided layout: stride n rows an expert
    counts = np.zeros(e, np.int32)
    strided = np.zeros((e * n, 128), np.float32)
    where = np.zeros((n, k), np.int32)
    for t in range(n):
        for j in range(k):
            ex = ids[t, j]
            where[t, j] = ex * n + counts[ex]
            strided[where[t, j]] = x[t]
            counts[ex] += 1
    want = np.asarray(gg.grouped_gemm_xla(
        jnp.asarray(strided), jnp.asarray(w), jnp.asarray(counts))._data)
    assert np.abs(got - want[where]).max() < 1e-4
    assert list(np.asarray(pk["counts"])) == list(counts)
    assert all(counts[x] == 0 for x in empty)
    # rows past the tiles in use are zero, and a tile names one expert
    used = int(pk["num_tiles"][0]) * bm
    assert not y[used:].any()
    assert used == int(sum(-(-c // bm) * bm for c in counts))


def test_packed_rows_are_far_fewer_than_strided_rows():
    # 1,024 tokens, 8 of 256 experts each: 16,128 packed rows against
    # 262,144 strided ones
    bm = gg.packed_block_m(1024 * 8, 256)
    assert bm == 32
    assert gg.packed_rows(1024 * 8, 256, bm) == 16128
    assert gg.packed_block_m(48 * 8, 256) == 16
    assert gg.packed_rows(48 * 8, 256, 16) == 4224


def test_a_dropped_assignment_gets_no_row():
    ids = jnp.asarray([[0, 2], [4, 4], [1, 3]], jnp.int32)   # 4 = none
    pk = gg.pack_by_expert(ids, 4, 8)
    assert list(np.asarray(pk["counts"])) == [1, 1, 1, 1]
    assert int(pk["num_tiles"][0]) == 4
    assert sorted(np.asarray(pk["row_token"])[
        np.asarray(pk["row_token"]) < 3]) == [0, 0, 2, 2]
