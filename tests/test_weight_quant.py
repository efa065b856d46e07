"""Weight-only int8 quantization subsystem (``paddle_tpu.quant``).

Bars (ISSUE 16): the Pallas dequant-matmul (interpret mode on CPU) is
exact-parity with the XLA formulation; the int8 grouped GEMM likewise;
``quantize_model`` swaps serving projections without touching
``lm_head``; the bundled-prompt quality gate clears greedy-match >=
0.99 with logits error inside the 0.05x-scale budget on a
prompt-fitted model; the QAT bridge is lossless (no requantization);
quantized checkpoints commit under the CheckpointManager CRC contract
at ~2x fewer bytes with exact warm-restart parity; and the engine knob
forks ``_shape_key`` while ``weight_dtype='bf16'`` leaves the model
untouched byte for byte.
"""

import copy
import os

import numpy as np
import pytest

import jax
import jax.numpy as jnp

import paddle_tpu as paddle
from paddle_tpu import nn
from paddle_tpu.quant.format import (dequantize_weight, effective_block,
                                     is_quantized, model_weight_block,
                                     quantize_model, quantize_weight,
                                     serving_weight_bytes)
from paddle_tpu.quant.kernels import (_dequant_matmul, dequant_matmul,
                                      dequant_matmul_xla, supported)
from paddle_tpu.quant.layers import WeightOnlyLinear


def _rand(*shape, seed=0, scale=1.0):
    return jnp.asarray(
        np.random.RandomState(seed).randn(*shape) * scale, jnp.float32)


class TestFormat:
    def test_round_trip_error_bound(self):
        w = _rand(64, 48)
        q, s = quantize_weight(w, 32)
        assert q.shape == (64, 48) and q.dtype == jnp.int8
        assert s.shape == (2, 48) and s.dtype == jnp.float32
        wd = dequantize_weight(q, s, 32)
        # absmax grid: error bounded by half a quantization step
        assert float(jnp.max(jnp.abs(wd - w))) \
            <= 0.5 * float(jnp.max(s)) + 1e-7

    def test_ragged_k_and_stacked(self):
        w = _rand(100, 16, seed=1)
        q, s = quantize_weight(w, 32)
        assert s.shape == (4, 16)       # ceil(100/32)
        wd = dequantize_weight(q, s, 32)
        assert float(jnp.max(jnp.abs(wd - w))) \
            <= 0.5 * float(jnp.max(s)) + 1e-7
        w3 = _rand(4, 64, 24, seed=2)
        q3, s3 = quantize_weight(w3, 32)
        assert q3.shape == (4, 64, 24) and s3.shape == (4, 2, 24)

    def test_effective_block_clamps(self):
        assert effective_block(64, 128) == 64
        assert effective_block(64, 32) == 32
        with pytest.raises(ValueError):
            effective_block(64, -1)

    def test_zero_block_dequantizes_to_zeros(self):
        w = jnp.zeros((32, 8), jnp.float32)
        q, s = quantize_weight(w, 16)
        assert float(jnp.max(jnp.abs(dequantize_weight(q, s, 16)))) == 0

    def test_dequantize_rejects_wrong_block(self):
        q, s = quantize_weight(_rand(64, 8), 32)
        with pytest.raises(ValueError):
            dequantize_weight(q, s, 16)


class TestKernel:
    """The Pallas dequant-matmul (interpret mode on CPU)."""

    @pytest.mark.parametrize("m,k,n,block", [
        (13, 64, 48, 32),       # ragged rows
        (8, 64, 48, 64),        # one scale row
        (40, 128, 24, 32),
        (1, 32, 8, 32),         # single decode row
    ])
    def test_kernel_exact_parity_with_xla(self, m, k, n, block):
        x = _rand(m, k, seed=3)
        q, s = quantize_weight(_rand(k, n, seed=4, scale=0.1), block)
        yk = _dequant_matmul(x, q, s, block, use_kernel=True)
        yx = _dequant_matmul(x, q, s, block, use_kernel=False)
        if m == 1:
            # a few ulp, not bitwise: XLA:CPU lowers the one-row dot
            # to a matrix-vector product, the kernel pads the row to a
            # tile and takes the matrix-matrix one, and the two add the
            # 32 products in different orders
            np.testing.assert_allclose(np.asarray(yk), np.asarray(yx),
                                       rtol=1e-6, atol=0.0)
        else:
            np.testing.assert_array_equal(np.asarray(yk), np.asarray(yx))

    def test_bf16_x_exact_parity(self):
        x = _rand(9, 64, seed=5).astype(jnp.bfloat16)
        q, s = quantize_weight(_rand(64, 32, seed=6, scale=0.1), 32)
        yk = _dequant_matmul(x, q, s, 32, use_kernel=True)
        yx = _dequant_matmul(x, q, s, 32, use_kernel=False)
        assert yk.dtype == jnp.bfloat16
        np.testing.assert_array_equal(
            np.asarray(yk.astype(jnp.float32)),
            np.asarray(yx.astype(jnp.float32)))

    def test_leading_dims_flatten(self):
        x = _rand(2, 5, 64, seed=7)
        q, s = quantize_weight(_rand(64, 16, seed=8, scale=0.1), 32)
        y = _dequant_matmul(x, q, s, 32, use_kernel=True)
        assert y.shape == (2, 5, 16)
        y2 = _dequant_matmul(x.reshape(10, 64), q, s, 32,
                             use_kernel=True)
        np.testing.assert_array_equal(np.asarray(y.reshape(10, 16)),
                                      np.asarray(y2))

    def test_matches_float_within_quant_tolerance(self):
        w = _rand(64, 48, seed=9, scale=0.1)
        x = _rand(16, 64, seed=10)
        q, s = quantize_weight(w, 32)
        y = np.asarray(_dequant_matmul(x, q, s, 32, use_kernel=True))
        ref = np.asarray(x) @ np.asarray(w)
        assert np.max(np.abs(y - ref)) \
            < 0.05 * max(float(np.max(np.abs(ref))), 1.0)

    def test_supported_gates_off_tpu_and_on_shapes(self):
        x = _rand(16, 64)
        q, s = quantize_weight(_rand(64, 32, seed=1), 32)
        # CPU backend: kernel off, the XLA formulation serves
        assert supported(x, q, s, 32) is False
        # shape gates hold regardless of backend
        assert supported(x[:, :-1], q, s, 32) is False   # K mismatch
        assert supported(x, q[:, :-1], s, 32) is False   # N mismatch
        assert supported(x, q, s[:-1], 32) is False      # scale rows
        q100, s100 = quantize_weight(_rand(100, 32, seed=2), 32)
        x100 = _rand(8, 100)
        assert supported(x100, q100, s100, 32) is False  # K % B != 0

    def test_tensor_wrapper_and_stop_gradient(self):
        x = paddle.to_tensor(np.asarray(_rand(6, 64, seed=11)))
        q, s = quantize_weight(_rand(64, 16, seed=12, scale=0.1), 32)
        qt = paddle.to_tensor(np.asarray(q))
        st = paddle.to_tensor(np.asarray(s))
        out = dequant_matmul(x, qt, st, 32)      # CPU -> XLA fallback
        ref = dequant_matmul_xla(x, qt, st, 32)
        np.testing.assert_array_equal(out.numpy(), ref.numpy())
        assert out.stop_gradient    # frozen weights: not differentiable


class TestWeightOnlyLinear:
    def test_forward_matches_exact_formulation(self):
        paddle.seed(21)
        lin = nn.Linear(64, 32)
        wq = WeightOnlyLinear.from_linear(lin, block=32)
        x = paddle.to_tensor(np.asarray(_rand(5, 64, seed=13)))
        got = wq(x).numpy()
        q, s = wq.weight_int8, wq.weight_scale
        ref = dequant_matmul_xla(x, q, s, 32)
        ref = (ref + lin.bias).numpy()
        np.testing.assert_array_equal(got, ref)

    def test_bias_free_and_state_dict(self):
        paddle.seed(22)
        lin = nn.Linear(16, 8, bias_attr=False)
        wq = WeightOnlyLinear.from_linear(lin, block=8)
        assert wq.bias is None
        sd = wq.state_dict()
        assert set(sd) == {"weight_int8", "weight_scale"}
        assert sd["weight_int8"].numpy().dtype == np.int8

    def test_cast_keeps_format_invariants(self):
        paddle.seed(23)
        wq = WeightOnlyLinear.from_linear(nn.Linear(16, 8), block=8)
        wq.bfloat16()
        assert wq.weight_int8._data.dtype == jnp.int8
        assert wq.weight_scale._data.dtype == jnp.float32

    def test_scale_shape_validated(self):
        q = np.zeros((16, 8), np.int8)
        with pytest.raises(ValueError):
            WeightOnlyLinear(q, np.zeros((3, 8), np.float32), block=8)


class TestQuantizeModel:
    def _model(self):
        from paddle_tpu.models.llama import (LlamaForCausalLM,
                                             tiny_llama_config)
        paddle.seed(31)
        m = LlamaForCausalLM(tiny_llama_config())
        m.eval()
        return m

    def test_swaps_projections_skips_lm_head(self):
        m = self._model()
        ref = m(paddle.to_tensor(
            np.arange(12, dtype=np.int32)[None])).numpy()
        assert not is_quantized(m)
        quantize_model(m, block=32)
        assert is_quantized(m) and model_weight_block(m) == 32
        att = m.model.layers[0].self_attn
        assert isinstance(att.q_proj, WeightOnlyLinear)
        assert isinstance(m.lm_head, nn.Linear)          # skipped
        got = m(paddle.to_tensor(
            np.arange(12, dtype=np.int32)[None])).numpy()
        scale = max(float(np.max(np.abs(ref))), 1.0)
        assert np.max(np.abs(got - ref)) < 0.05 * scale

    def test_weight_bytes_accounting(self):
        m = self._model().bfloat16()
        a0, b0, e0 = serving_weight_bytes(m)
        assert a0 == b0                     # bf16 model: 2 bytes/elem
        quantize_model(m, block=64)
        a1, b1, e1 = serving_weight_bytes(m)
        assert e1 == e0 and b1 == b0        # same weights, same baseline
        assert a1 < a0                      # int8 shrinks the real bytes
        assert b1 / a1 > 1.4                # ~2x minus float leftovers

    def test_raises_when_nothing_quantizable(self):
        class Empty(nn.Layer):
            pass

        with pytest.raises(ValueError):
            quantize_model(Empty())


class TestGroupedQ8:
    def _mk(self, e, c, k, n, block, seed=0):
        rng = np.random.RandomState(seed)
        x = jnp.asarray(rng.randn(e * c, k), jnp.float32)
        w = jnp.asarray(rng.randn(e, k, n) * 0.1, jnp.float32)
        q, s = quantize_weight(w, block)
        return x, w, q, s

    @pytest.mark.parametrize("gs", [
        [3, 0, 10, 7], [0, 0, 0, 0], [10, 0, 0, 0], [1, 1, 1, 1]])
    def test_kernel_exact_parity_with_xla(self, gs):
        from paddle_tpu.ops.grouped_gemm import _grouped_q8
        e, c, k, n, block = 4, 10, 32, 24, 16
        x, _, q, s = self._mk(e, c, k, n, block)
        gsj = jnp.asarray(gs, jnp.int32)
        yk = _grouped_q8(x, q, s, gsj, block, use_kernel=True)
        yx = _grouped_q8(x, q, s, gsj, block, use_kernel=False)
        np.testing.assert_array_equal(np.asarray(yk), np.asarray(yx))
        # rows past each group's length are defined zeros
        g3 = np.asarray(yk).reshape(e, c, n)
        for ei in range(e):
            assert np.all(g3[ei, int(gs[ei]):] == 0)

    def test_matches_float_grouped_within_tolerance(self):
        from paddle_tpu.ops.grouped_gemm import _grouped, _grouped_q8
        e, c, k, n, block = 4, 8, 32, 16, 16
        x, w, q, s = self._mk(e, c, k, n, block, seed=3)
        gs = jnp.asarray([8, 3, 0, 5], jnp.int32)
        yq = np.asarray(_grouped_q8(x, q, s, gs, block,
                                    use_kernel=False))
        yf = np.asarray(_grouped(x, w, gs, use_kernel=False))
        assert np.max(np.abs(yq - yf)) \
            < 0.05 * max(float(np.max(np.abs(yf))), 1.0)

    def test_supported_q8_gates(self):
        from paddle_tpu.ops.grouped_gemm import supported_q8
        e, c, k, n, block = 4, 8, 32, 16, 16
        x, _, q, s = self._mk(e, c, k, n, block, seed=4)
        gs = jnp.asarray([8, 8, 8, 8], jnp.int32)
        assert supported_q8(x, q, s, gs, block) is False   # CPU
        assert supported_q8(x[:-1], q, s, gs, block) is False
        assert supported_q8(x, q, s, gs, 24) is False      # K % B
        assert supported_q8(x, q, s[:, :-1], gs, block) is False

    def test_moe_layer_quantizes_in_place(self):
        from paddle_tpu.models.llama import LlamaConfig, LlamaMoEMLP
        paddle.seed(41)
        cfg = LlamaConfig(
            vocab_size=64, hidden_size=32, intermediate_size=64,
            num_hidden_layers=1, num_attention_heads=4,
            num_key_value_heads=2, moe_num_experts=4, moe_top_k=2)
        mlp = LlamaMoEMLP(cfg)
        x = paddle.to_tensor(
            np.random.RandomState(5).randn(6, 32).astype(np.float32))
        ref = mlp(x).numpy()
        mlp.quantize_weights(16)
        assert mlp.weight_block == 16
        assert mlp.gate_proj._data.dtype == jnp.int8
        sd = mlp.state_dict()
        assert "gate_proj_scale" in sd and "down_proj_scale" in sd
        got = mlp(x).numpy()
        scale = max(float(np.max(np.abs(ref))), 1.0)
        assert np.max(np.abs(got - ref)) < 0.05 * scale
        # frozen weights: quantize_weights is idempotent
        mlp.quantize_weights(16)
        # dtype casts keep sidecars f32
        mlp.bfloat16()
        assert mlp.gate_proj_scale._data.dtype == jnp.float32


class TestQATBridge:
    def _converted(self, seed=51):
        from paddle_tpu.quantization import QAT, QuantConfig

        paddle.seed(seed)

        class M(nn.Layer):
            def __init__(self):
                super().__init__()
                self.fc1 = nn.Linear(16, 32)
                self.fc2 = nn.Linear(32, 8)

            def forward(self, x):
                import paddle_tpu.nn.functional as F
                return self.fc2(F.relu(self.fc1(x)))

        m = M()
        return m, QAT(QuantConfig()).convert(m, inplace=False)

    def test_bridge_is_lossless_no_requantization(self):
        from paddle_tpu.quant.bridge import bridge_linear
        _, conv = self._converted()
        cl = conv.fc1
        wi8 = cl.weight_int8.numpy()
        s = float(np.asarray(cl.weight_scale.numpy()))
        bl = bridge_linear(cl, block=8)
        # SAME int8 values (no requantization) ...
        np.testing.assert_array_equal(bl.weight_int8.numpy(), wi8)
        # ... and the dequantized weight is bitwise the source's
        np.testing.assert_array_equal(
            np.asarray(dequantize_weight(bl.weight_int8,
                                         bl.weight_scale, 8)),
            wi8.astype(np.float32) * (s / 127.0))

    def test_bridged_model_forward_parity(self):
        from paddle_tpu.quant.bridge import bridge_model
        _, conv = self._converted(seed=52)
        x = paddle.to_tensor(
            np.random.RandomState(6).randn(4, 16).astype(np.float32))
        ref = conv(x).numpy()
        _, conv2 = self._converted(seed=52)
        assert bridge_model(conv2, block=8) == 2
        got = conv2(x).numpy()
        np.testing.assert_allclose(got, ref, rtol=1e-6, atol=1e-6)

    def test_strict_refuses_act_scale(self):
        from paddle_tpu.quant.bridge import bridge_linear, bridge_model
        from paddle_tpu.quantization import PTQ
        m, _ = self._converted(seed=53)
        ptq = PTQ()
        mm = ptq.quantize(m, inplace=False)
        mm(paddle.to_tensor(
            np.random.RandomState(7).randn(4, 16).astype(np.float32)))
        conv = ptq.convert(mm, inplace=False)
        with pytest.raises(ValueError):
            bridge_linear(conv.fc1, block=8)
        assert bridge_model(conv, block=8, strict=False) == 2

    def test_bridge_rejects_plain_linear(self):
        from paddle_tpu.quant.bridge import bridge_linear
        with pytest.raises(TypeError):
            bridge_linear(nn.Linear(4, 4))


class TestQuantizedCheckpoint:
    #: projection-dominated config: vocab tiny relative to the MLP so
    #: the float embedding/lm_head leftovers don't mask the ~2x win
    CFG = dict(vocab_size=64, hidden_size=128, intermediate_size=256,
               num_hidden_layers=2, num_attention_heads=4,
               num_key_value_heads=2, max_position_embeddings=128)

    def _model(self, seed=61):
        from paddle_tpu.models.llama import (LlamaConfig,
                                             LlamaForCausalLM)
        paddle.seed(seed)
        m = LlamaForCausalLM(LlamaConfig(**self.CFG)).bfloat16()
        m.eval()
        return m

    @staticmethod
    def _tree_bytes(root):
        return sum(os.path.getsize(os.path.join(d, f))
                   for d, _, fs in os.walk(root) for f in fs)

    def test_save_commits_and_halves_bytes(self, tmp_path):
        from paddle_tpu.distributed.checkpoint_manager import \
            CheckpointManager
        from paddle_tpu.quant import save_quantized

        m = self._model()
        fp_root = str(tmp_path / "fp")
        CheckpointManager(fp_root, async_save=False).save(
            m.state_dict(), 0, blocking=True)
        q_root = str(tmp_path / "q8")
        step_dir = save_quantized(m, q_root, step=0, block=64)
        # same atomic-commit/CRC contract as every other checkpoint
        assert os.path.exists(os.path.join(step_dir, "COMMITTED"))
        CheckpointManager(q_root, async_save=False).verify_step(0)
        ratio = self._tree_bytes(fp_root) / self._tree_bytes(q_root)
        assert ratio > 1.7      # ~2x minus sidecars + float leftovers

    def test_warm_restart_parity(self, tmp_path):
        from paddle_tpu.quant import load_quantized, save_quantized

        from paddle_tpu.quant.format import model_weight_block

        m = self._model(seed=62)
        root = str(tmp_path / "ckpt")
        save_quantized(m, root, step=3, block=32)
        m2 = self._model(seed=63)       # different init
        # no block arg: the checkpoint records it (sidecar shapes alone
        # can't — ceil(K/b) isn't injective in b)
        assert load_quantized(m2, root) == 3
        assert model_weight_block(m2) == 32
        x = paddle.to_tensor(np.arange(16, dtype=np.int32)[None])
        a = m(x).astype("float32").numpy()
        b = m2(x).astype("float32").numpy()
        np.testing.assert_array_equal(a, b)

    def test_load_into_empty_dir_returns_none(self, tmp_path):
        from paddle_tpu.quant import load_quantized
        m = self._model(seed=64)
        assert load_quantized(m, str(tmp_path / "nope"),
                              block=64) is None


class TestQualityGate:
    def test_bundled_prompts_are_ascii_byte_tokenizable(self):
        from paddle_tpu.quant import quality
        for p in quality.bundled_prompts():
            assert all(b < 128 for b in p.encode("utf-8"))
        ids = quality.bundled_prompt_ids(128)
        assert all(0 <= i < 128 for seq in ids for i in seq)

    def test_quality_bars_hold_on_fitted_model(self):
        from paddle_tpu.models.llama import (LlamaForCausalLM,
                                             tiny_llama_config)
        from paddle_tpu.observability import metrics as om
        from paddle_tpu.quant import quality

        paddle.seed(71)
        m = LlamaForCausalLM(tiny_llama_config())
        quality.fit_on_prompts(m, steps=40)
        m.eval()
        mq = copy.deepcopy(m)
        quantize_model(mq, block=64)
        rep = quality.logits_quality(m, mq)
        assert rep["greedy_match"] >= quality.GREEDY_MATCH_BAR
        scale = max(rep["ref_scale"], 1.0)
        assert rep["max_err"] <= quality.LOGITS_MAX_ERR_REL * scale
        assert rep["mean_err"] <= quality.LOGITS_MEAN_ERR_REL * scale
        assert rep["passes"]
        # the gate publishes its gauges
        assert om.gauge("quant_greedy_match_rate", "").value \
            == rep["greedy_match"]


class TestServingEngineKnob:
    KW = dict(max_batch=2, page_size=8, num_pages=64,
              max_pages_per_seq=16, chunk_block=8, chunk_budget=16,
              prefix_cache=False)

    def _model(self, seed=81):
        from paddle_tpu.models.llama import (LlamaForCausalLM,
                                             tiny_llama_config)
        paddle.seed(seed)
        m = LlamaForCausalLM(tiny_llama_config())
        m.eval()
        return m

    def test_bf16_knob_leaves_model_untouched(self):
        from paddle_tpu.inference.serving import LlamaServingEngine
        m = self._model()
        before = {k: np.asarray(v._data).copy()
                  for k, v in m.state_dict().items()}
        eng = LlamaServingEngine(m, weight_dtype="bf16", **self.KW)
        assert eng.weight_quant is False and eng.weight_block == 0
        eng.close()
        after = m.state_dict()
        assert set(before) == set(after)
        for k in before:
            np.testing.assert_array_equal(before[k],
                                          np.asarray(after[k]._data))
        assert not is_quantized(m)

    def test_int8_knob_quantizes_and_forks_shape_key(self):
        from paddle_tpu.inference.serving import LlamaServingEngine
        m = self._model(seed=82)
        fp = LlamaServingEngine(m, **self.KW)
        key_fp = fp._compute_shape_key()
        assert fp.weight_bytes_per_param > 2.0      # f32 CPU model
        fp.close()
        mq = self._model(seed=82)
        q8 = LlamaServingEngine(mq, weight_dtype="int8",
                                weight_block=32, **self.KW)
        assert q8.weight_quant is True and q8.weight_block == 32
        assert is_quantized(mq) and model_weight_block(mq) == 32
        assert q8.weight_bytes_per_param < 2.0
        key_q8 = q8._compute_shape_key()
        q8.close()
        assert key_fp != key_q8
        # block size forks the key too (it shapes the sidecars)
        m3 = self._model(seed=82)
        q8b = LlamaServingEngine(m3, weight_dtype="int8",
                                 weight_block=16, **self.KW)
        key_q8b = q8b._compute_shape_key()
        q8b.close()
        assert key_q8b not in (key_fp, key_q8)

    def test_prequantized_model_honored(self):
        from paddle_tpu.inference.serving import LlamaServingEngine
        m = self._model(seed=83)
        quantize_model(m, block=32)
        eng = LlamaServingEngine(m, **self.KW)      # no knob needed
        assert eng.weight_quant is True and eng.weight_block == 32
        eng.close()

    def test_env_knob_and_validation(self, monkeypatch):
        from paddle_tpu.inference.serving import LlamaServingEngine
        monkeypatch.setenv("PADDLE_TPU_WEIGHT_DTYPE", "int8")
        m = self._model(seed=84)
        eng = LlamaServingEngine(m, weight_block=32, **self.KW)
        assert eng.weight_quant is True
        eng.close()
        monkeypatch.setenv("PADDLE_TPU_WEIGHT_DTYPE", "int4")
        with pytest.raises(ValueError):
            LlamaServingEngine(self._model(seed=85), **self.KW)

    def test_generate_preserves_weights_and_matches_eager(self):
        # regression: the serving programs must NOT donate model state.
        # With donation on, XLA's aval-based alias assignment scrambled
        # the many same-aval int8/scale pass-through slots across each
        # other from the second dispatch on — the engine silently
        # corrupted the model in place and decoded garbage after the
        # first token. Byte-integrity of every slot plus exact parity
        # vs the eager quantized oracle pins the fix.
        from paddle_tpu.inference.serving import LlamaServingEngine
        m = self._model(seed=87)
        quantize_model(m, block=32)
        before = {k: np.asarray(v._data).copy()
                  for k, v in m.state_dict().items()}
        rng = np.random.RandomState(3)
        v = m.config.vocab_size
        prompts = [rng.randint(0, v, (10,)).tolist() for _ in range(2)]
        eng = LlamaServingEngine(m, **self.KW)
        outs = eng.generate(prompts, max_new_tokens=6)
        eng.close()
        after = m.state_dict()
        for k in before:
            np.testing.assert_array_equal(
                before[k], np.asarray(after[k]._data),
                err_msg=f"engine generate corrupted {k}")
        # the oracle is only valid because the integrity check above
        # proved the engine left the weights untouched
        for p, o in zip(prompts, outs):
            ref = m.generate(
                paddle.to_tensor(np.asarray([p], np.int64)),
                max_new_tokens=6)
            assert o == np.asarray(ref._data)[0, len(p):].tolist()

    @pytest.mark.slow
    def test_e2e_greedy_matches_bf16_engine(self):
        from paddle_tpu.inference.serving import LlamaServingEngine
        m = self._model(seed=86)
        mq = copy.deepcopy(m)
        rng = np.random.RandomState(2)
        v = m.config.vocab_size
        prompts = [rng.randint(0, v, (10,)).tolist() for _ in range(2)]
        fp = LlamaServingEngine(m, **self.KW)
        outs_fp = fp.generate(prompts, max_new_tokens=8)
        fp.close()
        q8 = LlamaServingEngine(mq, weight_dtype="int8",
                                weight_block=32, **self.KW)
        outs_q8 = q8.generate(prompts, max_new_tokens=8)
        q8.close()
        match = sum(a == b for of, oq in zip(outs_fp, outs_q8)
                    for a, b in zip(of, oq))
        total = sum(len(o) for o in outs_fp)
        assert match / total >= 0.99
