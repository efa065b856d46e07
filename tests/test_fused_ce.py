"""Chunked fused cross-entropy lm-head (ops/fused_linear_cross_entropy),
the donated+prefetched train-step input path, and expert-parallel MoE
pretraining (ISSUE 15)."""

import os
import time

import numpy as np
import pytest

import jax
import jax.numpy as jnp

import paddle_tpu as paddle
from paddle_tpu.models import (LlamaForCausalLM, shard_llama,
                               tiny_llama_config)
from paddle_tpu.ops.fused_linear_cross_entropy import (
    _kernel_parts, _loss_raw, _xla_parts, fused_linear_cross_entropy,
    fused_linear_cross_entropy_xla, supported)


def _materialized(h, w, lab, ignore_index=-100):
    """The reference: full [N, V] f32 logits -> log_softmax -> pick."""
    lg = jnp.matmul(h.astype(jnp.float32), w.astype(jnp.float32))
    logp = jax.nn.log_softmax(lg, axis=-1)
    valid = lab != ignore_index
    safe = jnp.where(valid, lab, 0)
    nll = -jnp.take_along_axis(logp, safe[:, None], axis=1)[:, 0]
    nll = jnp.where(valid, nll, 0.0)
    return jnp.sum(nll) / jnp.maximum(
        jnp.sum(valid.astype(jnp.float32)), 1.0)


def _case(n=24, d=32, v=50, seed=0, ignore=()):
    rng = np.random.RandomState(seed)
    h = jnp.asarray(rng.randn(n, d).astype(np.float32))
    w = jnp.asarray(rng.randn(d, v).astype(np.float32) * 0.2)
    lab = rng.randint(0, v, (n,))
    for i in ignore:
        lab[i] = -100
    return h, w, jnp.asarray(lab.astype(np.int32))


class TestChunkedXlaFormulation:
    def test_loss_matches_materialized_f32(self):
        h, w, lab = _case(ignore=(3, 17))
        ref = float(_materialized(h, w, lab))
        for chunk in (8, 16, 50, 64):   # incl. chunk > V and V % chunk
            got = float(_loss_raw(h, w, lab, chunk, -100, False))
            np.testing.assert_allclose(got, ref, rtol=1e-6, atol=1e-6)

    def test_loss_matches_materialized_bf16(self):
        h, w, lab = _case()
        hb, wb = h.astype(jnp.bfloat16), w.astype(jnp.bfloat16)
        ref = float(_materialized(hb, wb, lab))
        got = float(_loss_raw(hb, wb, lab, 16, -100, False))
        np.testing.assert_allclose(got, ref, rtol=1e-5, atol=1e-5)

    def test_all_ignored_rows_give_zero(self):
        h, w, _ = _case()
        lab = jnp.full((h.shape[0],), -100, jnp.int32)
        assert float(_loss_raw(h, w, lab, 16, -100, False)) == 0.0

    def test_grads_match_materialized(self):
        h, w, lab = _case(ignore=(0, 5))
        gr = jax.grad(_materialized, argnums=(0, 1))(h, w, lab)
        gf = jax.grad(
            lambda h, w, l: _loss_raw(h, w, l, 16, -100, False),
            argnums=(0, 1))(h, w, lab)
        np.testing.assert_allclose(np.asarray(gf[0]), np.asarray(gr[0]),
                                   rtol=1e-5, atol=1e-6)
        np.testing.assert_allclose(np.asarray(gf[1]), np.asarray(gr[1]),
                                   rtol=1e-5, atol=1e-6)

    def test_grads_bf16_weight_dtype(self):
        h, w, lab = _case()
        wb = w.astype(jnp.bfloat16)
        g = jax.grad(
            lambda h, w, l: _loss_raw(h, w, l, 16, -100, False),
            argnums=(0, 1))(h, wb, lab)
        assert g[0].dtype == h.dtype
        assert g[1].dtype == jnp.bfloat16

    def test_tensor_level_ops(self):
        h, w, lab = _case(ignore=(2,))
        ht = paddle.to_tensor(np.asarray(h), stop_gradient=False)
        wt = paddle.to_tensor(np.asarray(w), stop_gradient=False)
        lt = paddle.to_tensor(np.asarray(lab))
        loss = fused_linear_cross_entropy(ht, wt, lt, vocab_chunk=16)
        ref = float(_materialized(h, w, lab))
        np.testing.assert_allclose(float(loss), ref, rtol=1e-6, atol=1e-6)
        loss.backward()
        assert ht.grad is not None and wt.grad is not None
        lx = fused_linear_cross_entropy_xla(ht, wt, lt, vocab_chunk=16)
        np.testing.assert_allclose(float(lx), ref, rtol=1e-6, atol=1e-6)


class TestPallasKernel:
    def test_kernel_bitwise_vs_xla_same_chunking(self):
        # interpret mode off-TPU: same online update, same chunk order
        h, w, lab = _case(ignore=(3,))
        lse_x, pick_x = _xla_parts(h, w, lab, 16)
        lse_k, pick_k = _kernel_parts(h, w, lab, block_v=16)
        np.testing.assert_array_equal(np.asarray(lse_x),
                                      np.asarray(lse_k))
        np.testing.assert_array_equal(np.asarray(pick_x),
                                      np.asarray(pick_k))

    def test_kernel_vocab_not_divisible_by_block(self):
        h, w, lab = _case(n=16, d=32, v=50)      # 50 % 16 != 0
        lse_x, pick_x = _xla_parts(h, w, lab, 16)
        lse_k, pick_k = _kernel_parts(h, w, lab, block_v=16)
        # one ulp on two rows, not bitwise: the last vocab tile is a
        # 2-wide slice there and a masked 16-wide tile here, and
        # XLA:CPU contracts the update ``s * exp(m - m_new) + sum``
        # into an FMA or not by what it fuses the chain with (dot and
        # sum alone agree bit for bit at both widths)
        np.testing.assert_allclose(np.asarray(lse_x), np.asarray(lse_k),
                                   rtol=2.5e-7, atol=0.0)
        np.testing.assert_array_equal(np.asarray(pick_x),
                                      np.asarray(pick_k))

    def test_kernel_rows_not_divisible_by_block(self):
        # N=20 rides a ragged final row tile; real rows must be exact
        h, w, lab = _case(n=20, d=32, v=32)
        lse_x, _ = _xla_parts(h, w, lab, 16)
        lse_k, _ = _kernel_parts(h, w, lab, block_v=16)
        np.testing.assert_array_equal(np.asarray(lse_x),
                                      np.asarray(lse_k))

    def test_kernel_grads_flow_through_custom_vjp(self):
        h, w, lab = _case()
        gk = jax.grad(
            lambda h, w, l: _loss_raw(h, w, l, 16, -100, True),
            argnums=(0, 1))(h, w, lab)
        gx = jax.grad(
            lambda h, w, l: _loss_raw(h, w, l, 16, -100, False),
            argnums=(0, 1))(h, w, lab)
        np.testing.assert_allclose(np.asarray(gk[0]), np.asarray(gx[0]),
                                   rtol=1e-5, atol=1e-6)
        np.testing.assert_allclose(np.asarray(gk[1]), np.asarray(gx[1]),
                                   rtol=1e-5, atol=1e-6)

    def test_supported_gates(self):
        h, w, _ = _case(n=16, d=128, v=256)
        # CPU backend: public dispatch always takes the XLA formulation
        assert supported(h, w) is False


class TestModelWiring:
    def _data(self, cfg, batch=2, seq=12, seed=0):
        rng = np.random.RandomState(seed)
        ids = rng.randint(0, cfg.vocab_size,
                          (batch, seq + 1)).astype(np.int64)
        return (paddle.to_tensor(ids[:, :-1]),
                paddle.to_tensor(ids[:, 1:]))

    def test_knob_on_off_same_loss(self, monkeypatch):
        paddle.seed(0)
        cfg = tiny_llama_config(num_hidden_layers=1)
        m = LlamaForCausalLM(cfg)
        ids, labels = self._data(cfg)
        monkeypatch.setenv("PADDLE_TPU_FUSED_CE_CHUNK", "32")
        monkeypatch.setenv("PADDLE_TPU_FUSED_CE", "1")
        loss_f, logits_f = m(ids, labels)
        assert logits_f is None          # fused: logits never built
        monkeypatch.setenv("PADDLE_TPU_FUSED_CE", "0")
        loss_m, logits_m = m(ids, labels)
        assert logits_m is not None and logits_m.shape[-1] == \
            cfg.vocab_size
        np.testing.assert_allclose(float(loss_f), float(loss_m),
                                   rtol=1e-5, atol=1e-6)

    def test_train_loss_curve_knob_on_off(self, monkeypatch):
        monkeypatch.setenv("PADDLE_TPU_FUSED_CE_CHUNK", "32")

        def curve(knob):
            monkeypatch.setenv("PADDLE_TPU_FUSED_CE", knob)
            paddle.seed(0)
            cfg = tiny_llama_config(num_hidden_layers=1)
            m = LlamaForCausalLM(cfg)
            opt = paddle.optimizer.AdamW(learning_rate=1e-3,
                                         parameters=m.parameters())
            ids, labels = self._data(cfg)
            losses = []
            for _ in range(4):
                loss, _ = m(ids, labels)
                loss.backward()
                opt.step()
                opt.clear_grad()
                losses.append(float(loss))
            return losses

        fused = curve("1")
        materialized = curve("0")
        np.testing.assert_allclose(fused, materialized, rtol=2e-4,
                                   atol=2e-5)
        assert fused[-1] < fused[0]

    def test_tied_embeddings_stay_materialized(self):
        cfg = tiny_llama_config(tie_word_embeddings=True)
        m = LlamaForCausalLM(cfg)
        ids, labels = self._data(cfg)
        loss, logits = m(ids, labels)
        assert logits is not None        # tied: fused path not taken
        assert float(loss) > 0

    def test_donated_to_static_train_step_with_prefetcher(self):
        from paddle_tpu.io import DevicePrefetcher

        paddle.seed(0)
        cfg = tiny_llama_config(num_hidden_layers=1)
        m = LlamaForCausalLM(cfg)
        opt = paddle.optimizer.SGD(learning_rate=0.1,
                                   parameters=m.parameters())

        def step(ids, labels):
            loss, _ = m(ids, labels)
            loss.backward()
            opt.step()
            opt.clear_grad()
            return loss

        compiled = paddle.jit.to_static(step, state=[m, opt],
                                        warmup="once",
                                        donate_inputs=True)
        rng = np.random.RandomState(0)

        def host():
            while True:
                yield rng.randint(0, cfg.vocab_size,
                                  (2, 13)).astype(np.int64)

        with DevicePrefetcher(
                host(),
                transform=lambda ids: (ids[:, :-1].copy(),
                                       ids[:, 1:].copy())) as feed:
            losses = []
            for _ in range(4):
                x, y = next(feed)
                loss = compiled(paddle.to_tensor(x), paddle.to_tensor(y))
                losses.append(float(loss))
            stall, wall = feed.mark()
        assert all(np.isfinite(losses))
        assert 0.0 <= stall <= wall
        # eager reference on the SAME batch stream: donation + fused CE
        # must not change the math
        paddle.seed(0)
        m2 = LlamaForCausalLM(cfg)
        opt2 = paddle.optimizer.SGD(learning_rate=0.1,
                                    parameters=m2.parameters())
        rng = np.random.RandomState(0)
        ref = []
        for _ in range(4):
            ids = rng.randint(0, cfg.vocab_size, (2, 13)).astype(np.int64)
            loss, _ = m2(paddle.to_tensor(ids[:, :-1]),
                         paddle.to_tensor(ids[:, 1:]))
            loss.backward()
            opt2.step()
            opt2.clear_grad()
            ref.append(float(loss))
        np.testing.assert_allclose(losses, ref, rtol=2e-4, atol=2e-5)

    def test_peak_memory_below_materialized_8k_vocab(self):
        # the acceptance gate, statically: compiled fwd+bwd temp bytes
        # of the chunked path strictly below the materialized path at an
        # 8k vocab (the [N, V] f32 logits + softmax residuals dominate)
        n, d, v = 256, 128, 8192
        rng = np.random.RandomState(0)
        h = jnp.asarray(rng.randn(n, d).astype(np.float32) * 0.05)
        w = jnp.asarray(rng.randn(d, v).astype(np.float32) * 0.05)
        lab = jnp.asarray(rng.randint(0, v, (n,)).astype(np.int32))

        def fused(h, w, lab):
            return _loss_raw(h, w, lab, 2048, -100, False)

        sizes = {}
        for key, fn in (("fused", fused), ("mat", _materialized)):
            c = jax.jit(
                jax.value_and_grad(fn, argnums=(0, 1))).lower(
                h, w, lab).compile()
            try:
                sizes[key] = int(c.memory_analysis().temp_size_in_bytes)
            except Exception:
                pytest.skip("backend reports no memory_analysis")
        assert sizes["fused"] < sizes["mat"], sizes


class TestSpmdAndExpertParallel:
    def test_vocab_parallel_matches_single_device(self):
        from paddle_tpu.distributed import ProcessMesh

        ids = None

        def train(shard):
            nonlocal ids
            paddle.seed(3)
            cfg = tiny_llama_config(num_hidden_layers=1)
            m = LlamaForCausalLM(cfg)
            if shard:
                mesh = ProcessMesh(np.arange(8).reshape(2, 4),
                                   dim_names=["dp", "mp"])
                shard_llama(m, mesh, tp_axis="mp")
                # lm_head is vocab-parallel -> the SPMD formulation
                from paddle_tpu.ops.fused_linear_cross_entropy import (
                    _vocab_parallel_axis)
                assert _vocab_parallel_axis(m.lm_head.weight) is not None
            if ids is None:
                rng = np.random.RandomState(0)
                raw = rng.randint(0, cfg.vocab_size,
                                  (2, 13)).astype(np.int64)
                ids = (paddle.to_tensor(raw[:, :-1]),
                       paddle.to_tensor(raw[:, 1:]))
            loss, _ = m(*ids)
            return float(loss)

        single = train(False)
        sharded = train(True)
        np.testing.assert_allclose(single, sharded, rtol=1e-5, atol=1e-6)

    def _moe_losses_and_grads(self, ep):
        from paddle_tpu.distributed import ProcessMesh

        paddle.seed(11)
        cfg = tiny_llama_config(num_hidden_layers=1,
                                moe_num_experts=4, moe_top_k=2)
        m = LlamaForCausalLM(cfg)
        if ep:
            mesh = ProcessMesh(np.arange(4), dim_names=["ep"])
            shard_llama(m, mesh, tp_axis=None, ep_axis="ep")
            mlp = m.model.layers[0].mlp
            assert mlp.sharded is True
            assert mlp.gate_proj._placements[0].is_shard(0)
        opt = paddle.optimizer.SGD(learning_rate=0.05,
                                   parameters=m.parameters())
        rng = np.random.RandomState(5)
        raw = rng.randint(0, cfg.vocab_size, (2, 17)).astype(np.int64)
        ids = (paddle.to_tensor(raw[:, :-1]),
               paddle.to_tensor(raw[:, 1:]))
        losses, grads = [], None
        for _ in range(2):
            loss, _ = m(*ids)
            loss.backward()
            if grads is None:       # first-step grads, pre-update
                grads = {n: np.asarray(p.grad.numpy(), np.float32)
                         for n, p in m.named_parameters()
                         if p.grad is not None}
            opt.step()
            opt.clear_grad()
            losses.append(float(loss))
        return losses, grads

    def test_ep_sharded_moe_matches_replicated(self):
        rep_losses, rep_grads = self._moe_losses_and_grads(ep=False)
        ep_losses, ep_grads = self._moe_losses_and_grads(ep=True)
        np.testing.assert_allclose(ep_losses, rep_losses, rtol=2e-4,
                                   atol=2e-5)
        assert set(ep_grads) == set(rep_grads)
        for name in sorted(rep_grads):
            np.testing.assert_allclose(
                ep_grads[name], rep_grads[name], rtol=2e-3, atol=2e-5,
                err_msg=f"grad mismatch for {name}")
        assert rep_losses[1] < rep_losses[0]


class TestDevicePrefetcher:
    def test_order_and_stop(self):
        from paddle_tpu.io import DevicePrefetcher

        src = (np.full((2, 2), i, np.int64) for i in range(5))
        feed = DevicePrefetcher(src, depth=2)
        seen = [int(np.asarray(b)[0, 0]) for b in feed]
        assert seen == [0, 1, 2, 3, 4]
        with pytest.raises(StopIteration):
            next(feed)
        feed.close()

    def test_transform_tree_and_device(self):
        from paddle_tpu.io import DevicePrefetcher

        src = (np.arange(6, dtype=np.int64).reshape(2, 3)
               for _ in range(2))
        with DevicePrefetcher(
                src, transform=lambda a: {"x": a[:, :-1],
                                          "y": a[:, 1:]}) as feed:
            b = next(feed)
            assert isinstance(b["x"], jax.Array)
            np.testing.assert_array_equal(np.asarray(b["y"]),
                                          [[1, 2], [4, 5]])

    def test_source_error_propagates(self):
        from paddle_tpu.io import DevicePrefetcher

        def bad():
            yield np.zeros((1,), np.int64)
            raise RuntimeError("corrupt shard")

        feed = DevicePrefetcher(bad())
        next(feed)
        with pytest.raises(RuntimeError, match="corrupt shard"):
            for _ in range(2):
                next(feed)
        feed.close()

    def test_stall_accounting_and_gauge(self):
        from paddle_tpu.io import DevicePrefetcher
        from paddle_tpu.observability import metrics as om

        def slow():
            for i in range(3):
                time.sleep(0.05)
                yield np.full((1,), i, np.int64)

        feed = DevicePrefetcher(slow(), depth=1)
        feed.mark()
        for _ in range(3):
            next(feed)
        stall, wall = feed.mark()
        assert stall > 0.0 and wall >= stall
        g = om.default_registry().get("train_input_stall_frac")
        assert g is not None and 0.0 <= g.value <= 1.0
        feed.close()

    def test_close_unblocks_full_queue(self):
        from paddle_tpu.io import DevicePrefetcher

        def endless():
            while True:
                yield np.zeros((1,), np.int64)

        feed = DevicePrefetcher(endless(), depth=1)
        next(feed)
        feed.close()                      # worker blocked on put: must exit
        assert not feed._thread.is_alive()


class TestHonestMfu:
    def test_mfu_reads_compile_watcher_flops(self):
        from paddle_tpu.hapi import MetricsCallback
        from paddle_tpu.observability import metrics as om

        reg = om.MetricsRegistry()
        cb = MetricsCallback(batch_size=4, peak_flops=1e12,
                             registry=reg, sample_memory=False,
                             flops_watch="unit.train_step")
        # no gauge, no analytic count -> mfu untouched
        cb.on_train_batch_begin(0)
        cb.on_train_batch_end(0, {"loss": 1.0})
        assert reg.get("train_mfu").value == 0.0
        # the compile watcher recorded the step program's exact FLOPs
        reg.gauge("paddle_tpu_xla_program_flops",
                  "cost_analysis FLOPs of the last compiled program",
                  labelnames=("callable",)).labels(
            "unit.train_step").set(5e9)
        cb.on_train_batch_begin(1)
        time.sleep(0.01)
        cb.on_train_batch_end(1, {"loss": 1.0})
        mfu = reg.get("train_mfu").value
        assert mfu > 0.0
        # dt >= 10ms and flops = 5e9 -> mfu <= 5e9 / 0.01 / 1e12 = 0.5
        assert mfu <= 0.5
        # the gauge is batch-inclusive: no batch_size needed
        reg2 = om.MetricsRegistry()
        cb2 = MetricsCallback(peak_flops=1e12, registry=reg2,
                              sample_memory=False,
                              flops_watch="unit.train_step")
        reg2.gauge("paddle_tpu_xla_program_flops",
                   "cost_analysis FLOPs of the last compiled program",
                   labelnames=("callable",)).labels(
            "unit.train_step").set(5e9)
        cb2.on_train_batch_begin(0)
        time.sleep(0.005)
        cb2.on_train_batch_end(0, {"loss": 1.0})
        assert reg2.get("train_mfu").value > 0.0

    def test_mfu_falls_back_to_analytic(self):
        from paddle_tpu.hapi import MetricsCallback
        from paddle_tpu.observability import metrics as om

        reg = om.MetricsRegistry()
        cb = MetricsCallback(batch_size=2, peak_flops=1e12,
                             flops_per_sample=1e9, registry=reg,
                             sample_memory=False,
                             flops_watch="absent.callable")
        cb.on_train_batch_begin(0)
        time.sleep(0.005)
        cb.on_train_batch_end(0, {"loss": 1.0})
        assert reg.get("train_mfu").value > 0.0

    def test_peek_never_mints_children(self):
        from paddle_tpu.observability import metrics as om

        reg = om.MetricsRegistry()
        fam = reg.gauge("g", labelnames=("who",))
        assert fam.peek("nobody") is None
        assert fam.samples() == []
        fam.labels("somebody").set(2.0)
        assert fam.peek("somebody").value == 2.0
