"""A hybrid of linear attention and latent attention with routed experts
(the Kimi-Linear layout): gated delta-rule (KDA) layers that keep a
matrix state a sequence, NoPE latent-attention layers that keep pages,
and sigmoid-routed expert FFNs of which a chip may hold a share.

``n`` layers, pre-norm RMSNorm, residual after the mixer and after the
FFN, final RMSNorm, untied head. The config's two lists are 1-indexed:
layer ``l`` (0-based) is a **KDA** layer if ``l + 1`` is in
``kda_layers``, a **latent attention** layer if in ``full_attn_layers``.
The FFN is a SwiGLU for ``l < first_k_dense_replace``, else the expert
FFN of `models.mla_moe.MlaMoeMLP`.

**KDA mixer** (``h`` heads of ``K = V = d``; no bias anywhere; ``u`` the
normed input): ``q', k', v' = u W_q, u W_k, u W_v``, each through its own
causal depthwise convolution over the current and the 3 previous inputs,
then SiLU; ``q`` and ``k`` L2-normalised a head (eps 1e-6), ``q`` scaled
by ``d^-0.5``; the gate, per head and per CHANNEL, ``g = -exp(A_log) *
softplus((u W_fa) W_fb + dt_bias)`` (float32); ``beta = sigmoid(u W_b)``;
the state ``S [K, V]`` a head (float32, zeros at a sequence's first
token): ``S' = diag(exp(g_t)) S_{t-1}; S_t = S' + beta_t k_t (v_t - S'^T
k_t)^T; o_t = S_t^T q_t`` (`ops.kda`); out ``(RMSNorm_d(o) * w *
sigmoid((u W_ga) W_gb)) W_o``. A sequence keeps ``S`` and the last 3
inputs of the three convolutions: nothing grows with the context.

**Latent mixer**: `models.mla_moe.MlaAttention` with ONE query
projection (``q_lora_rank`` None) and no rotation (``mla_use_nope``):
the 64 "rope" lanes are ordinary key lanes all heads share.

A layer states its serving cache and runs its own serving step
(`inference.layer_step`). Inference only."""

from __future__ import annotations

import dataclasses

import jax
import jax.numpy as jnp
import numpy as np

from .. import nn
from ..framework.tensor import Tensor, run_op
from ..inference.layer_step import PagedLatent, SlotState
from ..nn.initializer import Constant, Normal
from ..ops import kda
from ..ops.selective_scan import row_index
from .llama import LlamaMLP
from .mla_moe import MlaAttention, MlaMoeMLP, serving_ffn

__all__ = ["KimiLinearConfig", "KdaAttention", "KimiLinearDecoderLayer",
           "KimiLinearModel", "KimiLinearForCausalLM",
           "tiny_kimi_linear_config"]

SCOPE = "paddle_tpu.kda"


@dataclasses.dataclass
class KimiLinearConfig:
    vocab_size: int = 163840
    hidden_size: int = 2304
    intermediate_size: int = 9216
    moe_intermediate_size: int = 1024
    num_hidden_layers: int = 27
    num_attention_heads: int = 32
    q_lora_rank: int | None = None
    kv_lora_rank: int = 512
    qk_nope_head_dim: int = 128
    qk_rope_head_dim: int = 64
    v_head_dim: int = 128
    mla_use_nope: bool = True
    kda_layers: tuple = (1, 2, 3, 5, 6, 7, 9, 10, 11, 13, 14, 15, 17, 18,
                         19, 21, 22, 23, 25, 26)
    full_attn_layers: tuple = (4, 8, 12, 16, 20, 24, 27)
    kda_num_heads: int = 32
    kda_head_dim: int = 128
    short_conv_kernel_size: int = 4
    n_routed_experts: int = 256         # the router's outputs
    n_shared_experts: int = 1
    num_experts_per_tok: int = 8
    first_k_dense_replace: int = 1
    routed_scaling_factor: float = 2.446
    norm_topk_prob: bool = True
    #: the share of an expert-parallel deployment this chip holds: experts
    #: ``[first_expert, first_expert + experts_held)`` (None: all)
    experts_held: int | None = None
    first_expert: int = 0
    max_position_embeddings: int = 1048576
    rms_norm_eps: float = 1e-5
    rope_theta: float = 10000.0         # read by nothing: no layer rotates
    tie_word_embeddings: bool = False
    initializer_range: float = 0.02

    def __post_init__(self):
        self.kda_layers = tuple(int(i) for i in self.kda_layers)
        self.full_attn_layers = tuple(int(i) for i in self.full_attn_layers)
        both = sorted(self.kda_layers + self.full_attn_layers)
        if both != list(range(1, self.num_hidden_layers + 1)):
            raise ValueError("kda_layers and full_attn_layers (1-indexed) "
                             "name every layer once")
        if self.tie_word_embeddings:
            raise ValueError("the head is its own matrix")

    def kind(self, index):
        return "kda" if index + 1 in self.kda_layers else "mla"

    # what the serving engine reads of any decoder's config
    @property
    def head_dim(self):
        return self.qk_nope_head_dim + self.qk_rope_head_dim

    @property
    def num_key_value_heads(self):
        return self.num_attention_heads

    @property
    def moe_num_experts(self):
        return self.n_routed_experts

    @property
    def moe_top_k(self):
        return self.num_experts_per_tok


def tiny_kimi_linear_config(**kw):
    """A few-thousand-parameter config for tests and rehearsals: five
    layers (KDA with a dense FFN, KDA, KDA, latent, KDA)."""
    base = dict(vocab_size=128, hidden_size=64, intermediate_size=128,
                moe_intermediate_size=32, num_hidden_layers=5,
                num_attention_heads=4, kv_lora_rank=32,
                qk_nope_head_dim=16, qk_rope_head_dim=8, v_head_dim=16,
                kda_layers=(1, 2, 3, 5), full_attn_layers=(4,),
                kda_num_heads=4, kda_head_dim=16, n_routed_experts=8,
                num_experts_per_tok=2, max_position_embeddings=512)
    base.update(kw)
    return KimiLinearConfig(**base)


def _winit(cfg):
    return Normal(mean=0.0, std=cfg.initializer_range)


class KdaAttention(nn.Layer):
    """The gated delta-rule mixer (module docstring)."""

    L2_EPS = 1e-6

    def __init__(self, c: KimiLinearConfig):
        super().__init__()
        self.heads, self.d = c.kda_num_heads, c.kda_head_dim
        self.width = c.short_conv_kernel_size
        self.eps = c.rms_norm_eps
        hd, wa = self.heads * self.d, _winit(c)

        def lin(i, o):
            return nn.Linear(i, o, weight_attr=wa, bias_attr=False)

        self.q_proj, self.k_proj, self.v_proj = (
            lin(c.hidden_size, hd) for _ in range(3))
        for name in ("q_conv", "k_conv", "v_conv"):
            setattr(self, name, self.create_parameter([hd, self.width],
                                                      attr=wa))
        # the gate's low-rank pair, beta, the output gate's pair
        self.f_a, self.f_b = lin(c.hidden_size, self.d), lin(self.d, hd)
        self.b_proj = lin(c.hidden_size, self.heads)
        self.A_log = self.create_parameter(
            [self.heads], default_initializer=Constant(0.0))
        self.dt_bias = self.create_parameter(
            [hd], default_initializer=Constant(0.0))
        self.g_a, self.g_b = lin(c.hidden_size, self.d), lin(self.d, hd)
        self.o_norm = self.create_parameter(
            [self.d], default_initializer=Constant(1.0))
        self.o_proj = lin(hd, c.hidden_size)

    def state_shapes(self, dtype):
        """What a sequence keeps: the matrix state a head (float32) and
        the last inputs of the three convolutions (the model's dtype)."""
        hd = self.heads * self.d
        return [((self.heads, self.d, self.d), jnp.float32),
                ((self.width - 1, 3 * hd), dtype)]

    def _operands(self, y, a, bl, alog, dtb):
        """From the convolutions' output ``y [.., 3hd]`` (float32), the
        gate's pre-activation ``a [.., hd]`` and beta's ``bl [.., h]``:
        ``q, k, v, g [.., h, d]`` and ``beta [.., h]``, float32."""
        h, d = self.heads, self.d
        f32 = jnp.float32
        lead = y.shape[:-1]
        q, k, v = (y[..., i * h * d:(i + 1) * h * d].reshape(lead + (h, d))
                   for i in range(3))

        def unit(x):
            return x / jnp.maximum(
                jnp.sqrt(jnp.sum(x * x, -1, keepdims=True)), self.L2_EPS)

        g = -jnp.exp(alog.astype(f32))[:, None] * jax.nn.softplus(
            a.astype(f32) + dtb.astype(f32)).reshape(lead + (h, d))
        return unit(q) * d ** -0.5, unit(k), v, g, \
            jax.nn.sigmoid(bl.astype(f32))

    def _streams(self, u):
        return (self.q_proj(u), self.k_proj(u), self.v_proj(u),
                self.f_b(self.f_a(u)), self.b_proj(u))

    def _weights(self):
        return (self.q_conv, self.k_conv, self.v_conv, self.A_log,
                self.dt_bias)

    def _out(self, u, o):
        """``o [.., h, d]`` (float32) through the gated norm and the
        output projection; ``u`` the mixer's input ``[B, S, H]``."""
        h, d, eps = self.heads, self.d, self.eps

        def fn(o, z, w):
            f32 = jnp.float32
            o = o.reshape(z.shape[:-1] + (h, d))
            o = o * jax.lax.rsqrt(jnp.mean(o * o, -1, keepdims=True) + eps)
            y = o * w.astype(f32) * jax.nn.sigmoid(
                z.astype(f32).reshape(o.shape))
            return y.reshape(z.shape).astype(z.dtype)

        return self.o_proj(run_op(
            "kda_gated_norm", fn, (o, self.g_b(self.g_a(u)), self.o_norm),
            differentiable=False))

    def forward(self, u):
        """Whole sequences ``u [B, S, H]`` from zero states."""
        b, s = u.shape[0], u.shape[1]
        h, d, w = self.heads, self.d, self.width

        def fn(q_, k_, v_, a, bl, cq, ck, cv, alog, dtb):
            xs = jnp.concatenate([q_, k_, v_], axis=-1)
            full = jnp.full((b,), s, jnp.int32)
            y, _ = kda.kda_conv_rows(
                xs, jnp.zeros((b, w - 1, 3 * h * d), xs.dtype),
                jnp.concatenate([cq, ck, cv], axis=0), full)
            o, _ = kda.kda_rows(*self._operands(y, a, bl, alog, dtb),
                                jnp.zeros((b, h, d, d), jnp.float32), full)
            return o

        o = run_op("kda_forward", fn, self._streams(u) + self._weights(),
                   differentiable=False)
        return self._out(u, o)

    def serving(self, u, step, pages):
        """One packed step ``u [1, T, H]`` over this layer's two pools
        (``[slots + 1, h, d, d]`` float32 and ``[slots + 1, 3, 3hd]``):
        a row of one token advances its state in place (`kda.kda_step`);
        the chunk rows, gathered, take the chunkwise form from their
        slot's state (zeros at position 0) and write their last back."""
        t, rows, qb = step.tokens, step.rows, step.qblock
        h, d = self.heads, self.d
        chunk_rows = step.chunk_rows

        def fn(q_, k_, v_, a, bl, cq, ck, cv, alog, dtb, spool, cpool,
               slots, starts, lens, w_flats, w_starts):
            i32 = jnp.int32
            xs = jnp.concatenate([q_, k_, v_], axis=-1).reshape(t, -1)
            a, bl = a.reshape(t, -1), bl.reshape(t, -1)
            cw = jnp.concatenate([cq, ck, cv], axis=0)
            n, slots = lens.astype(i32), slots.astype(i32)
            trash = spool.shape[0] - 1
            first = jnp.clip((w_flats + starts - w_starts).astype(i32), 0,
                             t - 1)
            fresh = starts == 0
            one = n == 1
            with jax.named_scope(kda.SCOPE):
                prev = jnp.where(fresh[:, None, None], 0, cpool[slots])
            # token 0 of every row: what a row of one token is
            y, last = kda.kda_conv_rows(xs[first][:, None], prev, cw,
                                        jnp.ones_like(n))
            slot1 = jnp.where(one, slots, trash)
            o1, spool = kda.kda_step(
                *self._operands(y[:, 0], a[first], bl[first], alog, dtb),
                spool, slot1, fresh)
            with jax.named_scope(kda.SCOPE):
                cpool = cpool.at[slot1].set(last)
                out = jnp.zeros((t, h, d), jnp.float32) \
                    .at[jnp.where(one, first, t)].set(o1, mode="drop")
            if qb == 1:
                return out, spool, cpool
            # the chunk rows, gathered
            with jax.named_scope(kda.SCOPE):
                at = jnp.nonzero(n > 1, size=chunk_rows,
                                 fill_value=rows)[0]
                live, gi = at < rows, jnp.clip(at, 0, rows - 1)
                nl = jnp.where(live, n[gi], 0)
                slot_l = jnp.where(live, slots[gi], trash)
                idx = row_index(first[gi], qb, t)
            y, last = kda.kda_conv_rows(xs[idx], prev[gi], cw, nl)
            with jax.named_scope(kda.SCOPE):
                s0 = jnp.where(fresh[gi][:, None, None, None], 0.0,
                               spool[slot_l])
            ol, sl = kda.kda_rows(
                *self._operands(y, a[idx], bl[idx], alog, dtb), s0, nl,
                long_rows=chunk_rows)
            with jax.named_scope(kda.SCOPE):
                spool = spool.at[slot_l].set(sl)
                cpool = cpool.at[slot_l].set(last)
                ok = jnp.arange(qb, dtype=i32)[None, :] < nl[:, None]
                out = out.at[jnp.where(ok, idx, t)].set(ol, mode="drop")
            return out, spool, cpool

        o, spool, cpool = run_op(
            "kda_serving", fn,
            self._streams(u) + self._weights() + (
                pages[0], pages[1], step.slots, step.q_starts, step.q_lens,
                step.w_flats, step.w_starts), differentiable=False)
        return self._out(u, o), [spool, cpool]


class KimiLinearDecoderLayer(nn.Layer):
    def __init__(self, c: KimiLinearConfig, index: int):
        super().__init__()
        self.index, self.kind = index, c.kind(index)
        self.config = c
        self.input_layernorm = nn.RMSNorm(c.hidden_size,
                                          epsilon=c.rms_norm_eps)
        self.post_attention_layernorm = nn.RMSNorm(c.hidden_size,
                                                   epsilon=c.rms_norm_eps)
        self.mixer = KdaAttention(c) if self.kind == "kda" \
            else MlaAttention(c)
        self.is_moe = index >= c.first_k_dense_replace \
            and c.n_routed_experts > 0
        self.mlp = MlaMoeMLP(c, experts_held=c.experts_held,
                             first_expert=c.first_expert) \
            if self.is_moe else LlamaMLP(c)

    def forward(self, x):
        x = x + self.mixer(self.input_layernorm(x))
        return x + self.mlp(self.post_attention_layernorm(x))

    # -- what the serving engine asks of a layer (inference/layer_step) --
    #: engine features that reach neither a matrix state nor latent pages
    serving_unsupported = ("prefix_cache", "kv_dtype=int8", "kv_tier",
                           "spec_k", "weight_dtype=int8")

    def serving_cache(self):
        if self.kind == "kda":
            return SlotState(self.mixer.state_shapes(
                self.mixer.q_conv._data.dtype))
        from ..ops.ragged_mla_attention import latent_row_width
        return PagedLatent(latent_row_width(self.mixer.kv_rank,
                                            self.mixer.rope))

    def serving_step(self, x, step, pages):
        """``(x, pages, stats)``: ``stats`` is `expert_stats` of an
        expert layer, None of the dense one."""
        u = self.input_layernorm(x)
        if self.kind == "kda":
            # one scope a KDA mixer, projections included: what the
            # per-layer readers of the benchmark find its device time by
            with jax.named_scope(SCOPE):
                y, pages = self.mixer.serving(u, step, pages)
        else:
            y, pool = self.mixer.serving(u, step, pages[0])
            pages = [pool]
        x, stats = serving_ffn(self, x + y, step)
        return x, pages, stats


class KimiLinearModel(nn.Layer):
    def __init__(self, config: KimiLinearConfig):
        super().__init__()
        self.config = config
        self.embed_tokens = nn.Embedding(config.vocab_size,
                                         config.hidden_size,
                                         weight_attr=_winit(config))
        self.layers = nn.LayerList(
            [KimiLinearDecoderLayer(config, i)
             for i in range(config.num_hidden_layers)])
        self.norm = nn.RMSNorm(config.hidden_size,
                               epsilon=config.rms_norm_eps)

    def forward(self, input_ids):
        x = self.embed_tokens(input_ids)
        for layer in self.layers:
            x = layer(x)
        return self.norm(x)


class KimiLinearForCausalLM(nn.Layer):
    """Decoder LM: ``forward(input_ids)`` returns logits ``[B, S, V]``;
    `generate` is greedy and cache-free (the oracle of the serving
    engine's tests, not a server)."""

    def __init__(self, config: KimiLinearConfig):
        super().__init__()
        self.config = config
        self.model = KimiLinearModel(config)
        self.lm_head = nn.Linear(config.hidden_size, config.vocab_size,
                                 weight_attr=_winit(config),
                                 bias_attr=False)

    def _logits(self, hidden):
        return self.lm_head(hidden)

    def forward(self, input_ids):
        return self._logits(self.model(input_ids))

    def num_params(self):
        return sum(int(np.prod(p.shape)) for p in self.parameters())

    def generate(self, input_ids, max_new_tokens=16):
        """Greedy continuation of ``input_ids [B, S]``: the whole
        prefix is recomputed a token (eagerly: a test's oracle)."""
        from ..framework.tensor import no_grad

        ids = np.asarray(input_ids._data)
        with no_grad():
            for _ in range(max_new_tokens):
                logits = self.forward(Tensor(jnp.asarray(ids)))
                nxt = np.asarray(jnp.argmax(logits._data[:, -1], axis=-1))
                ids = np.concatenate([ids, nxt[:, None].astype(ids.dtype)],
                                     axis=1)
        return Tensor(jnp.asarray(ids))
