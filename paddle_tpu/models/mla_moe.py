"""Latent-attention decoder with sigmoid-routed experts (the DeepSeek-V3
layout: multi-head latent attention with low-rank q and kv projections,
a leading run of dense SwiGLU layers, then expert layers of many small
routed experts plus a shared one).

Per layer, hidden ``h``, pre-norm RMSNorm, residual after attention and
after the FFN, final norm, untied head:

- *Latent attention.* ``c_q = RMSNorm(h W_qa)``; ``q = c_q W_qb`` ->
  heads x ``[q_nope | q_rope]``; ``[c_kv | k_rope] = h W_kva``;
  ``c_kv = RMSNorm(c_kv)``; interleaved RoPE (pairs ``(2i, 2i+1)``) on
  ``q_rope`` a head and on ``k_rope``, which all heads share;
  ``[k_nope | v]`` a head ``= c_kv W_kvb``; scores ``(q_nope.k_nope +
  q_rope.k_rope) / sqrt(nope + rope)``, causal softmax. `forward` runs
  this as written. Serving runs the ABSORBED form: ``q' = q_nope
  W_kvb[K]^T`` (kv_rank a head), scores ``q'.c_kv + q_rope.k_rope``,
  ``u = sum p c_kv``, ``out = u W_kvb[V]``; the cache holds one row
  ``[c_kv after its norm | k_rope after RoPE]`` a token a layer and
  nothing per head (`ops.ragged_mla_attention`).
- *FFN.* The first ``first_k_dense_replace`` layers: SwiGLU. After them:
  ``s = sigmoid(float32(h) W_g)``; the top k by ``s + b`` (the score
  correction bias; no group limit); weights ``s[chosen]`` WITHOUT ``b``,
  divided by their sum, times the routed scaling factor; ``y = sum w_i
  SwiGLU_i(h) + SwiGLU_shared(h)``. Dropless: a token's output is a
  function of that token alone, so the engine's packed step equals the
  plain forward. The routed part is three grouped GEMMs over rows packed
  by expert (`ops.grouped_gemm.pack_by_expert`).

The rotated rope lanes are kept de-interleaved (``[even lanes | odd
lanes]``), for q and k alike: a fixed permutation of the rope lanes
that leaves every score as published.

A decoder layer states its serving cache (`serving_cache`) and runs its
own serving step over its pages (`serving_step`): what
`inference.serving.LlamaServingEngine` asks of every layer kind.
Inference only (no custom gradients are defined for the routed path)."""

from __future__ import annotations

import dataclasses
import math

import jax
import jax.numpy as jnp
import numpy as np

from .. import nn
from ..framework.tensor import Parameter, Tensor, run_op
from ..nn.initializer import Normal
from ..observability.trace import scope
from .llama import LlamaMLP

__all__ = ["MlaMoeConfig", "MlaAttention", "MlaMoeMLP", "expert_stats",
           "serving_attn", "serving_ffn", "MlaMoeDecoderLayer",
           "MlaMoeModel", "MlaMoeForCausalLM", "tiny_mla_moe_config"]


@dataclasses.dataclass
class MlaMoeConfig:
    vocab_size: int = 129280
    hidden_size: int = 2048
    intermediate_size: int = 7168
    moe_intermediate_size: int = 768
    num_hidden_layers: int = 40
    num_attention_heads: int = 32
    q_lora_rank: int | None = 1536   # None: one query projection, no norm
    kv_lora_rank: int = 512
    qk_nope_head_dim: int = 128
    qk_rope_head_dim: int = 64
    v_head_dim: int = 128
    n_routed_experts: int = 256
    n_shared_experts: int = 1
    num_experts_per_tok: int = 8
    first_k_dense_replace: int = 1
    routed_scaling_factor: float = 2.5
    norm_topk_prob: bool = True
    max_position_embeddings: int = 131072
    rms_norm_eps: float = 1e-6
    rope_theta: float = 32000000.0
    tie_word_embeddings: bool = False
    initializer_range: float = 0.02
    #: no rotation: the "rope" lanes are ordinary key lanes all heads share
    mla_use_nope: bool = False

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


def tiny_mla_moe_config(**kw):
    """A few-thousand-parameter config for tests and rehearsals."""
    base = dict(vocab_size=128, hidden_size=64, intermediate_size=128,
                moe_intermediate_size=32, num_hidden_layers=3,
                num_attention_heads=4, q_lora_rank=48, kv_lora_rank=32,
                qk_nope_head_dim=16, qk_rope_head_dim=8, v_head_dim=16,
                n_routed_experts=8, num_experts_per_tok=2,
                first_k_dense_replace=1, max_position_embeddings=256,
                rope_theta=10000.0)
    base.update(kw)
    return MlaMoeConfig(**base)


def _winit(cfg):
    return Normal(mean=0.0, std=cfg.initializer_range)


def rope_tables_interleaved(pos, dim, base):
    """sin/cos ``[T, dim/2]`` f32 of positions ``pos`` (any integer
    array, flattened): pair ``i`` turns by ``pos * base^(-2i/dim)``."""
    inv = 1.0 / (base ** (jnp.arange(0, dim, 2, dtype=jnp.float32) / dim))
    ang = pos.reshape(-1).astype(jnp.float32)[:, None] * inv
    return jnp.sin(ang), jnp.cos(ang)


def rope_interleaved(x, sin, cos):
    """Rotate the pairs ``(2i, 2i+1)`` of ``x [..., T, heads, dim]`` by
    ``sin/cos [T, dim/2]``; the result is laid out ``[even | odd]``."""
    xf = x.astype(jnp.float32)
    x1, x2 = xf[..., 0::2], xf[..., 1::2]
    s, c = sin[:, None, :], cos[:, None, :]
    return jnp.concatenate([x1 * c - x2 * s, x2 * c + x1 * s],
                           axis=-1).astype(x.dtype)


class MlaAttention(nn.Layer):
    """Multi-head latent attention (module docstring). A config with
    ``q_lora_rank`` None has ONE query projection (no low-rank pair, no
    query norm); one with ``mla_use_nope`` rotates nothing. ``config``
    is any object with the fields read here."""

    def __init__(self, config):
        super().__init__()
        self.config = config
        c = config
        self.num_heads = c.num_attention_heads
        self.nope, self.rope = c.qk_nope_head_dim, c.qk_rope_head_dim
        self.v_dim, self.kv_rank = c.v_head_dim, c.kv_lora_rank
        self.head_dim = self.nope + self.rope
        self.scale = 1.0 / math.sqrt(self.head_dim)
        h, wa = self.num_heads, _winit(c)

        def lin(i, o):
            return nn.Linear(i, o, weight_attr=wa, bias_attr=False)

        self.rotates = not getattr(c, "mla_use_nope", False)
        self.q_rank = c.q_lora_rank
        if self.q_rank is None:
            self.q_proj = lin(c.hidden_size, h * self.head_dim)
        else:
            self.q_a = lin(c.hidden_size, c.q_lora_rank)
            self.q_a_norm = nn.RMSNorm(c.q_lora_rank,
                                       epsilon=c.rms_norm_eps)
            self.q_b = lin(c.q_lora_rank, h * self.head_dim)
        self.kv_a = lin(c.hidden_size, self.kv_rank + self.rope)
        self.kv_a_norm = nn.RMSNorm(self.kv_rank, epsilon=c.rms_norm_eps)
        self.kv_b = lin(self.kv_rank, h * (self.nope + self.v_dim))
        self.o = lin(h * self.v_dim, c.hidden_size)

    def _project(self, x):
        """``q [.., heads*(nope+rope)]``, the normed latent ``c_kv`` and
        the un-rotated shared ``k_rope`` of ``x``."""
        q = self.q_proj(x) if self.q_rank is None \
            else self.q_b(self.q_a_norm(self.q_a(x)))
        r = self.kv_rank

        def split(a):
            return a[..., :r], a[..., r:]

        c, kr = run_op("mla_split_latent", split, (self.kv_a(x),))
        return q, self.kv_a_norm(c), kr

    def forward(self, x, position_ids=None):
        """The attention as published (not absorbed), causal over each
        sequence of ``x [B, S, H]``; positions 0..S-1 unless given."""
        b, s = x.shape[0], x.shape[1]
        h, nope, rope, vd = self.num_heads, self.nope, self.rope, self.v_dim
        q, c, kr = self._project(x)
        kv = self.kv_b(c)
        base, scale = float(self.config.rope_theta), self.scale
        rotates = self.rotates

        def fn(q, kr, kv, pos):
            q = q.reshape(b, s, h, nope + rope)
            if rotates:
                pos = jnp.arange(s) if pos is None else pos.reshape(-1)[:s]
                sin, cos = rope_tables_interleaved(pos, rope, base)
                qr = rope_interleaved(q[..., nope:], sin, cos)
                kr_ = rope_interleaved(kr.reshape(b, s, 1, rope), sin, cos)
            else:
                qr, kr_ = q[..., nope:], kr.reshape(b, s, 1, rope)
            kv = kv.reshape(b, s, h, nope + vd)
            f32 = jnp.float32
            sc = jnp.einsum("bqhd,bkhd->bhqk", q[..., :nope].astype(f32),
                            kv[..., :nope].astype(f32)) \
                + jnp.einsum("bqhd,bkd->bhqk", qr.astype(f32),
                             kr_[:, :, 0].astype(f32))
            mask = jnp.tril(jnp.ones((s, s), bool))
            p = jax.nn.softmax(jnp.where(mask, sc * scale, -jnp.inf), -1)
            out = jnp.einsum("bhqk,bkhd->bqhd", p, kv[..., nope:]
                             .astype(f32))
            return out.reshape(b, s, h * vd).astype(q.dtype)

        out = run_op("mla_attention", fn, (q, kr, kv, position_ids),
                     differentiable=False)
        return self.o(out)

    def absorbed(self, x, sin, cos, width):
        """The serving operands of packed tokens ``x [1, T, H]``: the
        absorbed, rotated queries ``[T, heads, width]`` and the latent
        rows to cache ``[T, width]`` (``[c_kv | k_rope | 0]``). A layer
        that rotates nothing is handed ``sin = cos = None``."""
        t = x.shape[1]
        h, nope, rope, r = self.num_heads, self.nope, self.rope, \
            self.kv_rank
        q, c, kr = self._project(x)

        def fn(q, c, kr, wkvb, sin, cos):
            q = q.reshape(t, h, nope + rope)
            if sin is not None:
                qr = rope_interleaved(q[..., nope:], sin, cos)
                kr_ = rope_interleaved(kr.reshape(t, 1, rope), sin,
                                       cos)[:, 0]
            else:
                qr, kr_ = q[..., nope:], kr.reshape(t, rope)
            wk = wkvb.reshape(r, h, nope + self.v_dim)[..., :nope]
            qa = jnp.einsum("thn,chn->thc", q[..., :nope], wk,
                            preferred_element_type=jnp.float32) \
                .astype(q.dtype)
            pad = width - r - rope
            qf = jnp.concatenate(
                [qa, qr, jnp.zeros((t, h, pad), q.dtype)], axis=-1)
            rows = jnp.concatenate(
                [c.reshape(t, r), kr_.astype(c.dtype),
                 jnp.zeros((t, pad), c.dtype)], axis=-1)
            return qf, rows

        return run_op("mla_absorb", fn, (q, c, kr, self.kv_b.weight, sin,
                                         cos), differentiable=False)

    def unabsorb(self, u):
        """``u [T, heads, kv_rank]`` (the attended latents) through the
        value half of ``W_kvb`` and the output projection: ``[1, T, H]``."""
        t = u.shape[0]
        h, nope, vd, r = self.num_heads, self.nope, self.v_dim, \
            self.kv_rank

        def fn(u, wkvb):
            wv = wkvb.reshape(r, h, nope + vd)[..., nope:]
            out = jnp.einsum("thc,chv->thv", u, wv,
                             preferred_element_type=jnp.float32)
            return out.reshape(1, t, h * vd).astype(u.dtype)

        return self.o(run_op("mla_unabsorb", fn, (u, self.kv_b.weight),
                             differentiable=False))


    def serving(self, x, step, pool):
        """One packed step ``x [1, T, H]`` (normed) over this layer's
        latent pool: ``(the attention's output [1, T, H], the pool)``."""
        from ..ops.ragged_mla_attention import ragged_mla_attention

        sin, cos = step.rope_interleaved(
            self.rope, float(self.config.rope_theta)) if self.rotates \
            else (None, None)
        with scope("attn_proj"):
            qf, rows = self.absorbed(x, sin, cos, pool.shape[-1])
        out4, pool = ragged_mla_attention(
            qf, rows, pool, step.tables, step.kv_lens, step.q_starts,
            step.q_lens, step.w_starts, step.w_flats, step.w_ends,
            v_width=self.kv_rank, scale=self.scale, qblock=step.qblock)
        u = step.unpack(out4.reshape([step.rows * step.qblock,
                                      self.num_heads, self.kv_rank]))
        with scope("attn_proj"):
            return self.unabsorb(u), pool


def expert_stats(mlp):
    """What a layer's ``serving_step`` returns of its expert FFN's last
    call, the one shape every model's expert layers use: int32 ``[1, 2,
    1]``, ``[[experts (of those held) that got a row], [rows of the
    largest group]]``. The engine stacks the layers' along axis 0 and
    the host reads them with the tokens."""
    return mlp.last_stats.reshape([1, 2, 1])


def serving_attn(layer, attn, x, step, pool):
    """The latent attention half of a layer's packed step, for any layer
    with an ``input_layernorm``: ``(x + attn(norm(x)), pool)``."""
    with scope("attn"):
        with scope("attn_proj"):
            h = layer.input_layernorm(x)
        y, pool = attn.serving(h, step, pool)
        return x + y, pool


def serving_ffn(layer, x, step):
    """The FFN half of a layer's packed step, for any layer with a
    ``post_attention_layernorm``, an ``mlp`` and ``is_moe``: ``(x + FFN
    (norm(x)), stats)``, ``stats`` `expert_stats` of an expert layer
    (padding tokens get no expert row), None of a dense one. The norm,
    a dense MLP or the shared expert and the residual add lie under the
    scope ``mlp``; the routed experts, which run between the norm and
    the shared expert, under ``moe`` alone.

    A post-norm layer (``layer.post_norm``) runs ``x + RMSNorm(FFN(x))``
    instead, the norm its ``post_feedforward_layernorm``."""
    mlp = layer.mlp
    post = getattr(layer, "post_norm", False)
    norm_in = (lambda a: a) if post else layer.post_attention_layernorm

    def add(y):
        return x + (layer.post_feedforward_layernorm(y) if post else y)

    if not layer.is_moe:
        with scope("mlp"):
            return add(mlp(norm_in(x))), None
    with scope("mlp"):
        h = norm_in(x)
        valid = step.token_valid()
    routed = mlp.routed(h, valid)
    with scope("mlp"):
        if mlp.shared is None:
            return add(routed), expert_stats(mlp)
        return add(routed + mlp.shared(h)), expert_stats(mlp)


class MlaMoeMLP(nn.Layer):
    """The expert FFN: sigmoid router with a score-correction bias,
    top-k routed SwiGLU experts over the packed grouped GEMM, and one
    shared SwiGLU expert (module docstring). ``last_stats`` holds, after
    a call, ``[experts that got a row, rows of the largest group]``.

    ``scoring="softmax"`` routes as Mixtral and Qwen do instead: ``p =
    softmax(float32(x W_r))`` over all experts, the top k by ``p``,
    weights ``p[chosen]`` (divided by their sum where the config's
    ``norm_topk_prob``), times the scaling factor; no correction bias.
    ``n_shared_experts`` 0 builds no shared expert (``shared`` None).

    ``experts_held`` (default: all) and ``first_expert`` make this the
    share of an expert-parallel deployment that one chip computes: it
    holds the weights of experts ``[first_expert, first_expert +
    experts_held)``; the router keeps all its outputs and its ``k`` a
    token; the sum runs over the chosen experts that are held, the shared
    expert is computed whole, and what the absent experts would add is
    left out. ``last_stats`` then counts the held experts. ``config`` is
    any dataclass with the fields read here."""

    def __init__(self, config, experts_held=None, first_expert=0,
                 scoring="sigmoid"):
        super().__init__()
        from ..framework import random as frandom
        from ..framework.dtype import get_default_dtype

        c = config
        if scoring not in ("sigmoid", "softmax"):
            raise ValueError(f"scoring is 'sigmoid' or 'softmax', got "
                             f"{scoring!r}")
        self.scoring = scoring
        self.num_experts = int(c.n_routed_experts)
        self.top_k = int(c.num_experts_per_tok)
        self.scaling = float(c.routed_scaling_factor)
        self.normalize = bool(c.norm_topk_prob)
        self.held = self.num_experts if experts_held is None \
            else int(experts_held)
        self.first = int(first_expert)
        if not 0 <= self.first <= self.num_experts - self.held:
            raise ValueError(
                f"experts [{self.first}, {self.first + self.held}) are not "
                f"among the router's {self.num_experts}")
        e, d, f = self.num_experts, c.hidden_size, c.moe_intermediate_size
        held = self.held
        dt = jnp.dtype(get_default_dtype())
        std = c.initializer_range

        def init(shape):
            # drawn in the parameters' own dtype: float32 experts at
            # published sizes would not fit beside each other
            return Parameter(jax.random.normal(
                frandom.next_key(), shape, dt) * jnp.asarray(std, dt))

        self.router = init((d, e))
        self.router_bias = Parameter(jnp.zeros((e,), dt)) \
            if scoring == "sigmoid" else None
        self.experts_gate = init((held, d, f))
        self.experts_up = init((held, d, f))
        self.experts_down = init((held, f, d))
        ns = int(c.n_shared_experts)
        self.shared = LlamaMLP(dataclasses.replace(
            c, intermediate_size=f * ns)) if ns else None
        self.last_stats = None

    def route(self, x2d, wr, br):
        """``(expert ids [n, k], weights [n, k] f32)`` of tokens
        ``x2d [n, H]`` (raw arrays, traceable; ``br`` None where the
        scoring is softmax)."""
        logits = jnp.matmul(x2d.astype(jnp.float32), wr.astype(jnp.float32))
        if self.scoring == "softmax":
            s = jax.nn.softmax(logits, axis=-1)
            _, idx = jax.lax.top_k(s, self.top_k)
        else:
            s = jax.nn.sigmoid(logits)
            _, idx = jax.lax.top_k(s + br.astype(jnp.float32), self.top_k)
        w = jnp.take_along_axis(s, idx, axis=-1)
        if self.normalize:
            w = w / (jnp.sum(w, axis=-1, keepdims=True) + 1e-20)
        return idx.astype(jnp.int32), w * self.scaling

    def forward(self, x, valid=None):
        """``valid [n]`` (bool) marks the real tokens of a packed step:
        the others get no expert row and are not counted."""
        routed = self.routed(x, valid)
        return routed if self.shared is None else routed + self.shared(x)

    def routed(self, x, valid=None):
        """The routed experts' half of `forward` (``x``'s shape; sets
        ``last_stats``)."""
        from ..ops import grouped_gemm as gg

        shape = x.shape
        d = shape[-1]
        n = int(np.prod(shape[:-1]))
        e, k = self.held, self.top_k
        first, share = self.first, self.held != self.num_experts
        sub = 32 // jnp.dtype(x._data.dtype).itemsize
        bm = gg.packed_block_m(n * k, e, sublane=sub)

        def fn(x2d, wr, br, wg, wu, wd, valid):
            with scope("moe"):
                idx, w = self.route(x2d, wr, br)
                if share:
                    # a chosen expert another chip holds gets no row
                    # here, as an invalid token's do
                    idx = idx - first
                    mine = (idx >= 0) & (idx < e)
                    idx = jnp.where(mine, idx, e)
                    w = jnp.where(mine, w, 0.0)
                if valid is not None:
                    idx = jnp.where(valid[:, None], idx, e)
                    w = jnp.where(valid[:, None], w, 0.0)
                pk = gg.pack_by_expert(idx, e, bm)
                te, nt = pk["tile_expert"], pk["num_tiles"]
                xp = jnp.concatenate(
                    [x2d, jnp.zeros((1, d), x2d.dtype)])[pk["row_token"]]
                g = gg._grouped_packed(xp, wg, te, nt, bm)
                u = gg._grouped_packed(xp, wu, te, nt, bm)
                h = (jax.nn.silu(g.astype(jnp.float32))
                     * u.astype(jnp.float32)).astype(x2d.dtype)
                y = gg._grouped_packed(h, wd, te, nt, bm)
                out = jnp.einsum("nk,nkd->nd", w,
                                 y[pk["dest"]].astype(jnp.float32))
                stats = jnp.stack([jnp.sum(pk["counts"] > 0),
                                   jnp.max(pk["counts"])]).astype(jnp.int32)
                return out.astype(x2d.dtype), stats

        routed, stats = run_op(
            "mla_moe_mlp", fn,
            (x.reshape([n, d]), self.router, self.router_bias,
             self.experts_gate, self.experts_up, self.experts_down, valid),
            differentiable=False)
        self.last_stats = stats
        return routed.reshape(shape)


class MlaMoeDecoderLayer(nn.Layer):
    def __init__(self, config: MlaMoeConfig, index: int):
        super().__init__()
        c = config
        self.input_layernorm = nn.RMSNorm(c.hidden_size,
                                          epsilon=c.rms_norm_eps)
        self.self_attn = MlaAttention(c)
        self.post_attention_layernorm = nn.RMSNorm(c.hidden_size,
                                                   epsilon=c.rms_norm_eps)
        self.is_moe = index >= c.first_k_dense_replace \
            and c.n_routed_experts > 0
        self.mlp = MlaMoeMLP(c) if self.is_moe else LlamaMLP(c)

    def forward(self, x, position_ids=None):
        x = x + self.self_attn(self.input_layernorm(x), position_ids)
        return x + self.mlp(self.post_attention_layernorm(x))

    # -- what the serving engine asks of a layer -----------------------
    #: engine features that do not reach latent pages yet
    serving_unsupported = ("kv_dtype=int8", "kv_tier", "spec_k",
                           "weight_dtype=int8")

    def serving_cache(self):
        """Per token this layer writes ONE pool: no head axis, a row of
        ``kv_rank + rope`` values rounded up to whole lane tiles."""
        from ..ops.ragged_mla_attention import latent_row_width

        a = self.self_attn
        return [(None, latent_row_width(a.kv_rank, a.rope))]

    def serving_step(self, x, step, pages):
        """One packed step of this layer over its latent pages:
        ``(x, pages, stats)``; ``stats`` is `expert_stats` of an expert
        layer, None of a dense one."""
        x, pool = serving_attn(self, self.self_attn, x, step, pages[0])
        x, stats = serving_ffn(self, x, step)
        return x, [pool], stats


class MlaMoeModel(nn.Layer):
    def __init__(self, config: MlaMoeConfig):
        super().__init__()
        self.config = config
        self.embed_tokens = nn.Embedding(config.vocab_size,
                                         config.hidden_size,
                                         weight_attr=_winit(config))
        self.layers = nn.LayerList(
            [MlaMoeDecoderLayer(config, i)
             for i in range(config.num_hidden_layers)])
        self.norm = nn.RMSNorm(config.hidden_size,
                               epsilon=config.rms_norm_eps)

    def forward(self, input_ids, position_ids=None):
        x = self.embed_tokens(input_ids)
        for layer in self.layers:
            x = layer(x, position_ids)
        return self.norm(x)


class MlaMoeForCausalLM(nn.Layer):
    """Decoder LM: ``forward(input_ids)`` returns logits ``[B, S, V]``;
    `generate` is greedy and cache-free (the whole prefix is recomputed
    a token: the oracle of the serving engine's tests, not a server)."""

    def __init__(self, config: MlaMoeConfig):
        super().__init__()
        self.config = config
        self.model = MlaMoeModel(config)
        self.lm_head = nn.Linear(config.hidden_size, config.vocab_size,
                                 weight_attr=_winit(config),
                                 bias_attr=False)

    def _logits(self, hidden):
        return self.lm_head(hidden)

    def forward(self, input_ids, position_ids=None):
        return self._logits(self.model(input_ids, position_ids))

    def num_params(self):
        return sum(int(np.prod(p.shape)) for p in self.parameters())

    def generate(self, input_ids, max_new_tokens=16):
        """Greedy continuation of ``input_ids [B, S]`` by
        ``max_new_tokens``: the prompt and the tokens so far sit in one
        buffer of static length (a causal model's earlier positions
        never see the padding), so every step runs one program."""
        from ..framework.tensor import no_grad
        from .. import jit

        b, s = input_ids.shape[0], input_ids.shape[1]
        total = -(-(s + max_new_tokens) // 16) * 16
        if getattr(self, "_gen_static", None) is None:
            def step_fn(buf, at):
                logits = self.forward(buf)

                def pick(lg, at, buf):
                    at = at.astype(jnp.int32)
                    row = jax.lax.dynamic_index_in_dim(lg, at - 1, axis=1,
                                                       keepdims=False)
                    nxt = jnp.argmax(row, axis=-1).astype(buf.dtype)
                    return jax.lax.dynamic_update_slice(
                        buf, nxt[:, None], (jnp.zeros((), jnp.int32), at))

                return run_op("mla_generate_pick", pick,
                              (logits, at, buf), differentiable=False)

            self._gen_static = jit.StaticFunction(
                step_fn, state=[self], warmup="once", donate=False,
                name="mla_moe.generate_step")
            self._gen_static._warmed_any = True
        buf = jnp.zeros((b, total), input_ids._data.dtype) \
            .at[:, :s].set(input_ids._data)
        with no_grad():
            buf = Tensor(buf)
            for i in range(max_new_tokens):
                buf = self._gen_static(buf, Tensor(jnp.asarray(
                    s + i, jnp.int32)))
        return Tensor(buf._data[:, :s + max_new_tokens])
