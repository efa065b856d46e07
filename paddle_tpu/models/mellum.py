"""A GQA decoder whose layers mix window and whole-context attention, with
a softmax-routed expert FFN in every layer (the Mellum 2 layout).

``n`` layers, pre-norm RMSNorm, residual after attention and after the
FFN, final RMSNorm, untied head. Layer ``l``'s kind is ``layer_types[l]``:

- ``sliding_attention``: GQA (``num_attention_heads`` query heads over
  ``num_key_value_heads`` heads of ``head_dim``; no bias, no q/k norm), a
  query at position ``i`` seeing the keys ``(i - sliding_window, i]``,
  the default rope (``rope_theta``);
- ``full_attention``: the same attention over the whole context, with
  YaRN's rope (``yarn``: `ops.ragged_paged_attention.rope_tables`): its
  frequencies, and sin and cos multiplied by its attention factor, so
  that q and k are both scaled by it.

Every layer's FFN is `models.mla_moe.MlaMoeMLP` with ``scoring=
"softmax"``: ``p = softmax(float32(x W_r))``, the top k, their weights
divided by their sum, SwiGLU experts on the packed grouped GEMM; no
shared expert, no correction bias, no scaling.

A layer builds on the Llama layer: `LlamaAttention` told its ``window``
and its ``yarn``, `LlamaDecoderLayer.serving_attention` for the packed
step (a window layer keeps a ring of pages a sequence slot,
``PagedKV(heads, width, window)``; a full layer the whole context), then
`models.mla_moe.serving_ffn`. Inference only."""

from __future__ import annotations

import dataclasses
import math

import jax
import jax.numpy as jnp
import numpy as np

from .. import nn
from ..framework.tensor import run_op
from ..inference.layer_step import PagedKV
from ..nn.initializer import Normal
from ..ops.ragged_paged_attention import _rope_rows, rope_tables
from .llama import LlamaAttention, LlamaDecoderLayer
from .mla_moe import MlaMoeMLP, serving_ffn

__all__ = ["MellumConfig", "MellumAttention", "MellumDecoderLayer",
           "MellumModel", "MellumForCausalLM", "tiny_mellum_config"]

WINDOW, FULL = "sliding_attention", "full_attention"


@dataclasses.dataclass
class MellumConfig:
    vocab_size: int = 98304
    hidden_size: int = 2304
    intermediate_size: int = 7168       # the dense width: no layer has one
    moe_intermediate_size: int = 896
    num_hidden_layers: int = 28
    num_attention_heads: int = 32
    num_key_value_heads: int = 4
    head_dim: int = 128
    layer_types: tuple = ((WINDOW,) * 3 + (FULL,)) * 7
    sliding_window: int = 1024
    n_routed_experts: int = 64
    n_shared_experts: int = 0
    num_experts_per_tok: int = 8
    routed_scaling_factor: float = 1.0
    norm_topk_prob: bool = True
    max_position_embeddings: int = 131072
    rms_norm_eps: float = 1e-6
    rope_theta: float = 500000.0        # both kinds
    #: the full layers' rope: (factor, original_max_position_embeddings,
    #: beta_fast, beta_slow, attention_factor)
    yarn: tuple = (16.0, 8192, 32.0, 1.0, 1.2772588722239782)
    tie_word_embeddings: bool = False
    initializer_range: float = 0.02

    def __post_init__(self):
        self.layer_types = tuple(self.layer_types)
        self.yarn = tuple(self.yarn)
        if len(self.layer_types) != self.num_hidden_layers \
                or set(self.layer_types) - {WINDOW, FULL}:
            raise ValueError(f"layer_types names each of the "
                             f"{self.num_hidden_layers} layers {WINDOW!r} "
                             f"or {FULL!r}")
        if self.tie_word_embeddings:
            raise ValueError("the head is its own matrix")

    # what the serving engine reads of any decoder's config
    @property
    def moe_num_experts(self):
        return self.n_routed_experts

    @property
    def moe_top_k(self):
        return self.num_experts_per_tok


def tiny_mellum_config(**kw):
    """A few-thousand-parameter config for tests and rehearsals: four
    layers (window, window, window, full), a window of 16, 8 query heads
    over one kv head (the published group), 8 experts 2 a token; YaRN's
    ramp from frequency 2 to 6 of 8, so both of its ends act."""
    base = dict(vocab_size=128, hidden_size=64, intermediate_size=128,
                moe_intermediate_size=32, num_hidden_layers=4,
                num_attention_heads=8, num_key_value_heads=1, head_dim=16,
                layer_types=(WINDOW,) * 3 + (FULL,), sliding_window=16,
                n_routed_experts=8, num_experts_per_tok=2,
                max_position_embeddings=512, rope_theta=10000.0,
                yarn=(16.0, 2048, 32.0, 1.0, 1.2772588722239782))
    base.update(kw)
    return MellumConfig(**base)


def _winit(cfg):
    return Normal(mean=0.0, std=cfg.initializer_range)


class MellumAttention(LlamaAttention):
    """`LlamaAttention` of one layer kind (module docstring)."""

    def __init__(self, config: MellumConfig, kind):
        super().__init__(config)
        self.kind = kind
        if kind == WINDOW:
            self.window = int(config.sliding_window)
        else:
            self.yarn = config.yarn

    def forward(self, x):
        """Whole sequences ``x [B, S, H]`` from position 0 (the plain
        forward; serving runs `LlamaDecoderLayer.serving_attention`)."""
        b, s = x.shape[0], x.shape[1]
        h, hk, d = self.num_heads, self.num_kv_heads, self.head_dim
        base, yarn, window = float(self.config.rope_theta), self.yarn, \
            self.window

        def fn(q, k, v):
            sin, cos = rope_tables(jnp.arange(s), d, base, yarn)
            q = _rope_rows(q.reshape(b * s, h, d), jnp.tile(sin, (b, 1)),
                           jnp.tile(cos, (b, 1))).reshape(b, s, h, d)
            k = _rope_rows(k.reshape(b * s, hk, d), jnp.tile(sin, (b, 1)),
                           jnp.tile(cos, (b, 1))).reshape(b, s, hk, d)
            v = v.reshape(b, s, hk, d)
            f32 = jnp.float32
            g = h // hk
            qf = q.astype(f32).reshape(b, s, hk, g, d)
            sc = jnp.einsum("bqkgd,bskd->bkgqs", qf, k.astype(f32)) \
                / math.sqrt(d)
            i = jnp.arange(s)
            mask = i[None, :] <= i[:, None]
            if window is not None:
                mask &= i[None, :] > i[:, None] - window
            p = jax.nn.softmax(jnp.where(mask, sc, -jnp.inf), axis=-1)
            out = jnp.einsum("bkgqs,bskd->bqkgd", p, v.astype(f32))
            return out.reshape(b, s, h * d).astype(q.dtype)

        out = run_op("mellum_attention", fn,
                     (self.q_proj(x), self.k_proj(x), self.v_proj(x)),
                     differentiable=False)
        return self.o_proj(out)


class MellumDecoderLayer(LlamaDecoderLayer):
    """A Llama layer of one attention kind with the softmax-routed expert
    FFN."""

    is_moe = True

    def __init__(self, config: MellumConfig, index: int):
        nn.Layer.__init__(self)
        c = config
        self.index, self.kind = index, c.layer_types[index]
        self.input_layernorm = nn.RMSNorm(c.hidden_size,
                                          epsilon=c.rms_norm_eps)
        self.self_attn = MellumAttention(c, self.kind)
        self.post_attention_layernorm = nn.RMSNorm(c.hidden_size,
                                                   epsilon=c.rms_norm_eps)
        self.mlp = MlaMoeMLP(c, scoring="softmax")

    def forward(self, x):
        x = x + self.self_attn(self.input_layernorm(x))
        return x + self.mlp(self.post_attention_layernorm(x))

    # -- what the serving engine asks of a layer (inference/layer_step) --
    @property
    def serving_unsupported(self):
        """Engine features that reach no ring of pages (or no packed
        expert layer) yet, beside the Llama layer's own."""
        return ("prefix_cache", "kv_dtype=int8", "kv_tier",
                "weight_dtype=int8") + super().serving_unsupported

    def serving_cache(self):
        a = self.self_attn
        return PagedKV(a.num_kv_heads, a.head_dim, window=a.window)

    def serving_step(self, x, step, pages):
        """``(x, pages, stats)``, ``stats`` the expert layer's counters."""
        x, pages = self.serving_attention(x, step, pages)
        x, stats = serving_ffn(self, x, step)
        return x, pages, stats


class MellumModel(nn.Layer):
    def __init__(self, config: MellumConfig):
        super().__init__()
        self.config = config
        self.embed_tokens = nn.Embedding(config.vocab_size,
                                         config.hidden_size,
                                         weight_attr=_winit(config))
        self.layers = nn.LayerList(
            [MellumDecoderLayer(config, i)
             for i in range(config.num_hidden_layers)])
        self.norm = nn.RMSNorm(config.hidden_size,
                               epsilon=config.rms_norm_eps)

    def forward(self, input_ids):
        x = self.embed_tokens(input_ids)
        for layer in self.layers:
            x = layer(x)
        return self.norm(x)


class MellumForCausalLM(nn.Layer):
    """Decoder LM: ``forward(input_ids)`` returns logits ``[B, S, V]``
    (the eager oracle of the serving engine's tests; serving runs the
    layers' `serving_step`)."""

    def __init__(self, config: MellumConfig):
        super().__init__()
        self.config = config
        self.model = MellumModel(config)
        self.lm_head = nn.Linear(config.hidden_size, config.vocab_size,
                                 weight_attr=_winit(config),
                                 bias_attr=False)

    def _logits(self, hidden):
        return self.lm_head(hidden)

    def forward(self, input_ids):
        return self._logits(self.model(input_ids))

    def num_params(self):
        return sum(int(np.prod(p.shape)) for p in self.parameters())
