"""A GQA decoder with per-head q/k norms, post-norm blocks, window layers
beside rope-free full layers, sigmoid-routed experts with a shared one,
and a multi-token prediction module that drafts the next token but one
(the K-EXAONE layout: EXAONE 4.0's attention and block, DeepSeek-V3's
routing and MTP).

``n`` layers, final RMSNorm, untied head. Layer ``l``'s kind is
``layer_types[l]``:

- ``sliding_attention``: GQA (``num_attention_heads`` query heads over
  ``num_key_value_heads`` heads of ``head_dim``; no bias), ``q =
  RMSNorm_d(x W_q)`` and ``k = RMSNorm_d(x W_k)`` a head, then the
  default rope (``rope_theta``); a query at position ``i`` sees the
  keys ``(i - sliding_windows[l], i]``;
- ``full_attention``: the same q/k norms over the whole context, with
  no rope at all (NoPE).

A block is post-norm: ``x <- x + RMSNorm(attn(x))``, then ``x <- x +
RMSNorm(ffn(x))``; no norm before either. Layer ``l``'s FFN is a dense
SwiGLU where ``mlp_layer_types[l]`` is ``dense``, else
`models.mla_moe.MlaMoeMLP`: sigmoid scores, the top k by score plus the
correction bias, weights the scores normalised over the k and scaled by
``routed_scaling_factor``, plus one shared expert; ``experts_held`` and
``first_expert`` make it one chip's share of an expert-parallel layer.

The MTP module (`ExaoneMoeMtp`, DeepSeek-V3's): at position ``i``, from
the main model's last hidden state ``h_i`` (before the final norm) and
the token ``t_{i+1}`` that follows, ``x_i = W_eh [RMSNorm(emb(t_{i+1}));
RMSNorm(h_i)]``, one full-attention post-norm expert layer over the
``x`` of positions ``0..i``, its own final norm and the shared head: a
prediction of ``t_{i+2}``. The serving engine runs it in the step
program (`serving_drafter`): its argmax is the next dispatch's draft.

A layer builds on the Llama layer as Mellum's does: `LlamaAttention`
told its ``window`` (or ``rotary`` False) with `serving_qk` for the
norms, `LlamaDecoderLayer.serving_attention` with ``post_norm``, then
`models.mla_moe.serving_ffn`. Inference only."""

from __future__ import annotations

import dataclasses
import math

import jax
import jax.numpy as jnp
import numpy as np

from .. import nn
from ..framework.tensor import run_op
from ..nn.initializer import Normal
from ..observability.trace import scope
from ..ops.ragged_paged_attention import _rope_rows, rope_tables
from .llama import LlamaAttention, LlamaMLP
from .mellum import MellumDecoderLayer
from .mla_moe import MlaMoeMLP

__all__ = ["ExaoneMoeConfig", "ExaoneMoeAttention", "ExaoneMoeDecoderLayer",
           "ExaoneMoeMtp", "ExaoneMoeModel", "ExaoneMoeForCausalLM",
           "tiny_exaone_moe_config"]

WINDOW, FULL = "sliding_attention", "full_attention"
DENSE, SPARSE = "dense", "sparse"


@dataclasses.dataclass
class ExaoneMoeConfig:
    vocab_size: int = 153600
    hidden_size: int = 6144
    intermediate_size: int = 18432      # the dense layers' width
    moe_intermediate_size: int = 2048
    num_hidden_layers: int = 48
    num_attention_heads: int = 64
    num_key_value_heads: int = 8
    head_dim: int = 128
    layer_types: tuple = ((WINDOW,) * 3 + (FULL,)) * 12
    mlp_layer_types: tuple = (DENSE,) + (SPARSE,) * 47
    sliding_window: int = 128
    #: each layer's window (0 for a full layer); None: ``sliding_window``
    #: for every window layer
    sliding_windows: tuple | None = None
    n_routed_experts: int = 128
    n_shared_experts: int = 1
    num_experts_per_tok: int = 8
    routed_scaling_factor: float = 2.5
    norm_topk_prob: bool = True
    #: the routed experts this chip holds of each layer (None: all) and
    #: the first of them
    experts_held: int | None = None
    first_expert: int = 0
    num_nextn_predict_layers: int = 1
    max_position_embeddings: int = 262144
    rms_norm_eps: float = 1e-5
    rope_theta: float = 1000000.0       # the window layers'
    tie_word_embeddings: bool = False
    initializer_range: float = 0.02

    def __post_init__(self):
        n = self.num_hidden_layers
        self.layer_types = tuple(self.layer_types)
        self.mlp_layer_types = tuple(self.mlp_layer_types)
        if self.sliding_windows is None:
            self.sliding_windows = tuple(
                self.sliding_window if k == WINDOW else 0
                for k in self.layer_types)
        self.sliding_windows = tuple(int(w) for w in self.sliding_windows)
        if len(self.layer_types) != n or set(self.layer_types) \
                - {WINDOW, FULL}:
            raise ValueError(f"layer_types names each of the {n} layers "
                             f"{WINDOW!r} or {FULL!r}")
        if len(self.mlp_layer_types) != n or set(self.mlp_layer_types) \
                - {DENSE, SPARSE}:
            raise ValueError(f"mlp_layer_types names each of the {n} "
                             f"layers {DENSE!r} or {SPARSE!r}")
        if len(self.sliding_windows) != n or any(
                (w > 0) != (k == WINDOW)
                for w, k in zip(self.sliding_windows, self.layer_types)):
            raise ValueError("sliding_windows gives every window layer a "
                             "window and every full layer 0")
        if self.num_nextn_predict_layers not in (0, 1):
            raise ValueError("one MTP module or none")
        if self.tie_word_embeddings:
            raise ValueError("the head is its own matrix")

    # what the serving engine reads of any decoder's config
    @property
    def moe_num_experts(self):
        return self.n_routed_experts

    @property
    def moe_top_k(self):
        return self.num_experts_per_tok


def tiny_exaone_moe_config(**kw):
    """A few-thousand-parameter config for tests and rehearsals: four
    layers (window, window, window, full; the first dense), a window of
    16, 8 query heads over 2 kv heads, 8 experts 2 a token and a shared
    one, the MTP module."""
    base = dict(vocab_size=128, hidden_size=64, intermediate_size=128,
                moe_intermediate_size=32, num_hidden_layers=4,
                num_attention_heads=8, num_key_value_heads=2, head_dim=16,
                layer_types=(WINDOW,) * 3 + (FULL,),
                mlp_layer_types=(DENSE,) + (SPARSE,) * 3,
                sliding_window=16, n_routed_experts=8,
                num_experts_per_tok=2, max_position_embeddings=512,
                rope_theta=10000.0)
    base.update(kw)
    return ExaoneMoeConfig(**base)


def _winit(cfg):
    return Normal(mean=0.0, std=cfg.initializer_range)


class ExaoneMoeAttention(LlamaAttention):
    """`LlamaAttention` of one layer kind with per-head q/k norms (module
    docstring)."""

    def __init__(self, config: ExaoneMoeConfig, window):
        super().__init__(config)
        if window:
            self.window = int(window)
        else:
            self.rotary = False
        eps = config.rms_norm_eps
        self.q_norm = nn.RMSNorm(self.head_dim, epsilon=eps)
        self.k_norm = nn.RMSNorm(self.head_dim, epsilon=eps)

    def serving_qk(self, q, k):
        with scope("qk_norm"):
            return self.q_norm(q), self.k_norm(k)

    def forward(self, x):
        """Whole sequences ``x [B, S, H]`` from position 0 (the plain
        forward; serving runs `LlamaDecoderLayer.serving_attention`)."""
        b, s = x.shape[0], x.shape[1]
        h, hk, d = self.num_heads, self.num_kv_heads, self.head_dim
        base, window, rotary = float(self.config.rope_theta), self.window, \
            self.rotary
        q = self.q_norm(self.q_proj(x).reshape([b, s, h, d]))
        k = self.k_norm(self.k_proj(x).reshape([b, s, hk, d]))

        def fn(q, k, v):
            if rotary:
                sin, cos = rope_tables(jnp.arange(s), d, base)
                sin, cos = jnp.tile(sin, (b, 1)), jnp.tile(cos, (b, 1))
                q = _rope_rows(q.reshape(b * s, h, d), sin, cos)
                k = _rope_rows(k.reshape(b * s, hk, d), sin, cos)
            v = v.reshape(b, s, hk, d)
            f32 = jnp.float32
            qf = q.astype(f32).reshape(b, s, hk, h // hk, d)
            sc = jnp.einsum("bqkgd,bskd->bkgqs", qf,
                            k.astype(f32).reshape(b, s, hk, d)) \
                / math.sqrt(d)
            i = jnp.arange(s)
            mask = i[None, :] <= i[:, None]
            if window is not None:
                mask &= i[None, :] > i[:, None] - window
            p = jax.nn.softmax(jnp.where(mask, sc, -jnp.inf), axis=-1)
            out = jnp.einsum("bkgqs,bskd->bqkgd", p, v.astype(f32))
            return out.reshape(b, s, h * d).astype(v.dtype)

        out = run_op("exaone_attention", fn, (q, k, self.v_proj(x)),
                     differentiable=False)
        return self.o_proj(out)


class ExaoneMoeDecoderLayer(MellumDecoderLayer):
    """A post-norm Llama layer of one attention kind (``window`` 0: the
    whole context, no rope) with a dense or an expert FFN (``moe``)."""

    post_norm = True

    def __init__(self, config: ExaoneMoeConfig, window, moe):
        nn.Layer.__init__(self)
        c = config
        self.kind = WINDOW if window else FULL
        self.self_attn = ExaoneMoeAttention(c, window)
        self.post_attention_layernorm = nn.RMSNorm(c.hidden_size,
                                                   epsilon=c.rms_norm_eps)
        self.post_feedforward_layernorm = nn.RMSNorm(
            c.hidden_size, epsilon=c.rms_norm_eps)
        self.is_moe = bool(moe)
        self.mlp = MlaMoeMLP(c, experts_held=c.experts_held,
                             first_expert=c.first_expert) if moe \
            else LlamaMLP(c)

    def forward(self, x):
        x = x + self.post_attention_layernorm(self.self_attn(x))
        return x + self.post_feedforward_layernorm(self.mlp(x))


class ExaoneMoeMtp(nn.Layer):
    """The multi-token prediction module (module docstring): the
    embedding and the head are the main model's."""

    def __init__(self, config: ExaoneMoeConfig):
        super().__init__()
        c = config
        h = c.hidden_size
        self.enorm = nn.RMSNorm(h, epsilon=c.rms_norm_eps)
        self.hnorm = nn.RMSNorm(h, epsilon=c.rms_norm_eps)
        self.eh_proj = nn.Linear(2 * h, h, weight_attr=_winit(c),
                                 bias_attr=False)
        self.layer = ExaoneMoeDecoderLayer(c, 0, moe=True)
        self.norm = nn.RMSNorm(h, epsilon=c.rms_norm_eps)

    def _inputs(self, hidden, emb):
        from ..tensor import manipulation
        return self.eh_proj(manipulation.concat(
            [self.enorm(emb), self.hnorm(hidden)], axis=-1))

    def forward(self, hidden, emb):
        """``hidden [B, S, H]`` the main model's last hidden states of
        positions ``0..S-1`` and ``emb`` the embeddings of the tokens at
        ``1..S``: the normed states whose head gives ``t_{i+2}``."""
        return self.norm(self.layer(self._inputs(hidden, emb)))

    # -- what the serving engine asks of a drafting module ---------------
    def serving_cache(self):
        return self.layer.serving_cache()

    def serving_draft(self, lm, hidden, given, sampled, step, pages):
        """One packed step over the module's own pages: ``hidden [1, T,
        H]`` the main model's states before its final norm, ``given [1,
        T]`` the token after each packed position where the host knows it
        (-1 elsewhere: the position's own sampled token ``sampled [T]``
        follows it). Returns ``(the argmax draft after each position's
        next token, 1-D [T], pages)``."""
        from ..tensor import search

        with scope("mtp"):
            nxt = run_op("mtp_next_tokens", lambda g, s_: jnp.where(
                g >= 0, g, s_.reshape(g.shape).astype(g.dtype)),
                (given, sampled), differentiable=False)
            x = self._inputs(hidden, lm.model.embed_tokens(nxt))
            x, pages, _ = self.layer.serving_step(x, step, pages)
            logits = lm._logits(self.norm(x))
            drafts = search.argmax(logits, axis=-1).astype("int64") \
                .reshape([step.tokens])
        return drafts, pages


class ExaoneMoeModel(nn.Layer):
    def __init__(self, config: ExaoneMoeConfig):
        super().__init__()
        self.config = config
        c = config
        self.embed_tokens = nn.Embedding(c.vocab_size, c.hidden_size,
                                         weight_attr=_winit(c))
        self.layers = nn.LayerList(
            [ExaoneMoeDecoderLayer(c, c.sliding_windows[i],
                                   c.mlp_layer_types[i] == SPARSE)
             for i in range(c.num_hidden_layers)])
        self.norm = nn.RMSNorm(c.hidden_size, epsilon=c.rms_norm_eps)

    def hidden(self, input_ids):
        """The last hidden states before the final norm."""
        x = self.embed_tokens(input_ids)
        for layer in self.layers:
            x = layer(x)
        return x

    def forward(self, input_ids):
        return self.norm(self.hidden(input_ids))


class ExaoneMoeForCausalLM(nn.Layer):
    """Decoder LM: ``forward(input_ids)`` returns logits ``[B, S, V]``,
    `mtp_logits` the MTP module's (the eager oracles of the serving
    engine's tests; serving runs the layers' `serving_step` and the
    module's `serving_draft`)."""

    def __init__(self, config: ExaoneMoeConfig):
        super().__init__()
        self.config = config
        self.model = ExaoneMoeModel(config)
        self.lm_head = nn.Linear(config.hidden_size, config.vocab_size,
                                 weight_attr=_winit(config),
                                 bias_attr=False)
        self.mtp = ExaoneMoeMtp(config) \
            if config.num_nextn_predict_layers else None

    def _logits(self, hidden):
        return self.lm_head(hidden)

    def forward(self, input_ids):
        return self._logits(self.model(input_ids))

    def mtp_logits(self, input_ids):
        """``[B, S-1, V]``: row ``i`` the MTP module's logits for the
        token at ``i + 2``, from positions ``0..i`` and the tokens at
        ``1..i+1`` of ``input_ids [B, S]``."""
        h = self.model.hidden(input_ids)
        s = input_ids.shape[1]
        emb = self.model.embed_tokens(input_ids[:, 1:])
        return self._logits(self.mtp(h[:, :s - 1], emb))

    def serving_drafter(self):
        """The module that drafts the next token but one inside the
        serving step program (`ExaoneMoeMtp.serving_draft`), or None."""
        return self.mtp

    def num_params(self):
        return sum(int(np.prod(p.shape)) for p in self.parameters())
