"""The serving engine asks each layer for its step
(``layer.serving_step``) and its cache (``layer.serving_cache``) instead
of owning one layer's mathematics. For the Llama layer the moved code is
the code the engine held inline until PR 30: ``legacy_step`` below is the
rope-fused branch of that inline loop body (the one branch PR 32 kept),
frozen here word for word, and an engine whose layers run it must leave
the same tokens and the same pool bytes, bitwise, float and int8 pages."""

import numpy as np
import pytest

import paddle_tpu as paddle
from paddle_tpu.inference.layer_step import _token_gather
from paddle_tpu.inference.serving import LlamaServingEngine
from paddle_tpu.models.llama import (LlamaConfig, LlamaDecoderLayer,
                                     LlamaForCausalLM)
from paddle_tpu.observability import trace as otrace
from paddle_tpu.ops.ragged_paged_attention import \
    fused_ragged_paged_attention


def legacy_step(self, x, step, pages):
    """The body of ``_mixed_forward``'s layer loop as it stood before
    the seam (commit c5e7157), its engine attributes read from ``step``."""
    layer = self
    t, r_rows, qb = step.tokens, step.rows, step.qblock
    k_pool, v_pool = pages[0], pages[1]
    k_scale, v_scale = (pages[2], pages[3]) if step.kv_quant \
        else (None, None)
    tables, kv_lens, q_starts, q_lens = (step.tables, step.kv_lens,
                                         step.q_starts, step.q_lens)
    w_starts, w_flats, w_ends = step.w_starts, step.w_flats, step.w_ends
    flat_idx = step.flat_idx
    trash = step.trash_page
    rsin, rcos = step.rope(layer.self_attn.head_dim,
                           float(layer.self_attn.config.rope_theta))
    new_ks = new_vs = None
    h = layer.input_layernorm(x)
    att = layer.self_attn
    q = att.q_proj(h).reshape([1, t, att.num_heads, att.head_dim])
    k = att.k_proj(h).reshape([1, t, att.num_kv_heads, att.head_dim])
    v = att.v_proj(h).reshape([1, t, att.num_kv_heads, att.head_dim])
    k2 = k.reshape([t, att.num_kv_heads, att.head_dim])
    v2 = v.reshape([t, att.num_kv_heads, att.head_dim])
    q3 = q.reshape([t, att.num_heads, att.head_dim])
    if step.kv_quant:
        attn4, kp, vp, new_ks, new_vs = fused_ragged_paged_attention(
            q3, k2, v2, k_pool, v_pool, tables, kv_lens, q_starts,
            q_lens, w_starts, w_flats, w_ends, trash, k_scale=k_scale,
            v_scale=v_scale, rope_sin=rsin, rope_cos=rcos, qblock=qb)
    else:
        attn4, kp, vp = fused_ragged_paged_attention(
            q3, k2, v2, k_pool, v_pool, tables, kv_lens, q_starts,
            q_lens, w_starts, w_flats, w_ends, trash, rope_sin=rsin,
            rope_cos=rcos, qblock=qb)
    attn = _token_gather(
        attn4.reshape([r_rows * qb, att.num_heads, att.head_dim]),
        flat_idx)
    x = x + att.o_proj(attn.reshape([1, t, -1]))
    x = x + layer.mlp(layer.post_attention_layernorm(x))
    return x, [kp, vp] + ([new_ks, new_vs] if step.kv_quant else []), \
        None


@pytest.fixture(scope="module")
def model():
    paddle.seed(0)
    m = LlamaForCausalLM(LlamaConfig(
        vocab_size=256, hidden_size=64, intermediate_size=128,
        num_hidden_layers=2, num_attention_heads=4, num_key_value_heads=2,
        max_position_embeddings=256))
    m.eval()
    return m


def _run(model, **kw):
    rng = np.random.RandomState(30)
    prompts = [rng.randint(0, 256, (n,)).tolist() for n in (30, 5, 12, 19)]
    e = LlamaServingEngine(model, max_batch=4, page_size=8, num_pages=41,
                           max_pages_per_seq=8, chunk_budget=32,
                           chunk_block=8, **kw)
    e.prewarm(mixed=[e.chunk_budget, e.max_batch])
    otrace.clear()
    out = e.generate(prompts, max_new_tokens=6)
    state = [np.asarray(p._data) for pools in (
        e.k_pools, e.v_pools, e.k_scales, e.v_scales) for p in pools]
    e.close()
    # `generate` prefills one dispatch ahead (ISSUE 38): both sides of
    # the comparison took tokens on the device through ``prev_idx``
    assert sum(ev["args"].get("dev_tokens", 0) for ev in otrace.get_events()
               if ev["name"] == "serving.dispatch") > 0
    return out, state


@pytest.mark.parametrize("kv_dtype", [None, "int8"])
@pytest.mark.parametrize("program", [dict()], ids=["rope_fused"])
def test_llama_layer_step_is_the_engines_old_loop(model, monkeypatch,
                                                  program, kv_dtype):
    kw = dict(program, kv_dtype=kv_dtype)
    out_new, state_new = _run(model, **kw)
    monkeypatch.setattr(LlamaDecoderLayer, "serving_step", legacy_step)
    out_old, state_old = _run(model, **kw)
    assert out_new == out_old
    assert len(state_new) == len(state_old) == (8 if kv_dtype else 4)
    for a, b in zip(state_new, state_old):
        # the trash page (the last) collects the padding's writes too
        assert a.dtype == b.dtype and np.array_equal(a, b)


def test_llama_layer_states_a_k_and_a_v_pool(model):
    layer = model.model.layers[0]
    assert layer.serving_cache() == [(2, 16), (2, 16)]
    e = LlamaServingEngine(model, max_batch=2, page_size=8, num_pages=9)
    assert e.k_pools[0].shape == [9, 2, 8, 16] == e.v_pools[0].shape
    assert len(e.k_pools) == len(e.v_pools) == 2
    assert e.kv_bytes_per_token == 2 * 2 * 16 * 4 * 2
    e.close()
