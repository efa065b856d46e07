"""A decoder-hybrid-decoder language model (the SambaY layout of
Phi-4-mini-flash-reasoning): state-space layers, window and full
differential attention, one K/V pool that later layers share, gated
memory units.

``n`` layers, pre-norm residual, ``LN`` a LayerNorm with weight and bias:
``h = x + mix_l(LN(x)); y = h + MLP(LN(h))``, ``MLP(u) = W2 (silu(g) *
v)`` with ``[g, v] = W1 u``; a final LayerNorm; the head is the embedding
transposed. No positional encoding anywhere. The mixer by layer:

- ``l < n/2``, even: **Mamba** (`ops.selective_scan`): ``[x, z] = W_in
  u``; ``x = silu(conv1d_causal_depthwise(x) + b)`` over the ``d_conv``
  latest inputs; ``[dt, B, C] = W_x x``; ``D_t = softplus(W_dt dt +
  b_dt)``; ``A = -exp(A_log)``; ``h_t = exp(D_t A) h_{t-1} + D_t B_t
  x_t``; ``m_t = C_t . h_t + D x_t``; out ``W_out (silu(z) * m)``.
  Keeps the state and the last ``d_conv - 1`` conv inputs a sequence.
- ``l < n/2``, odd: **differential attention** over a window of
  ``sliding_window`` keys (the current one included).
- ``l = n/2``: Mamba that also hands on its scan output ``m`` (before
  the gate) to the gated memory units of the same step.
- ``l = n/2 + 1``: differential attention, full causal. Its K/V pool is
  the one every later attention layer reads.
- ``l > n/2 + 1``, even: **gated memory unit**: ``W_out' (silu(W_in' u)
  * m)``, ``m`` layer ``n/2``'s for the same token. Keeps nothing.
- ``l > n/2 + 1``, odd: differential **cross** attention: its own
  queries over layer ``n/2 + 1``'s K/V. Keeps nothing of its own.

Differential attention (layer index ``l``, query heads ``(2p, 2p+1)``,
key and value heads ``(2g, 2g+1)``, ``g = p // 2``): ``A1 = softmax(
q_2p K_2g^T / sqrt(d))``, ``A2 = softmax(q_2p+1 K_2g+1^T / sqrt(d))``,
``V_g = [V_2g | V_2g+1]``; ``lam = exp(lq1.lk1) - exp(lq2.lk2) + lam0``,
``lam0 = 0.8 - 0.6 exp(-0.3 l)``; ``o_p = RMSNorm(A1 V_g - lam A2 V_g) *
(1 - lam0)``. Served, a pair of key heads is ONE 2d-lane head of the
cache (``[K_2g | K_2g+1]``, values alike) and a query head is padded
with zeros on the lanes of the key head it does not see, so both
softmaxes of a pair are rows of one ordinary grouped-query attention
over 2d lanes: the ragged paged program of the Llama layers, without
rotation, with a window bound on its page walk where the layer has one.

A layer states its serving cache and runs its own serving step
(`inference.layer_step`). Inference only."""

from __future__ import annotations

import dataclasses
import math

import jax
import jax.numpy as jnp
import numpy as np

from .. import nn
from ..framework.tensor import Parameter, Tensor, run_op
from ..inference.layer_step import PagedKV, SharedPages, SlotState
from ..nn.initializer import Constant, Normal
from ..ops import selective_scan as ss

__all__ = ["SambaYConfig", "SambaYDecoderLayer", "SambaYModel",
           "SambaYForCausalLM", "tiny_sambay_config"]

SCOPES = {"mamba": "paddle_tpu.ssm", "mamba_memory": "paddle_tpu.ssm",
          "gmu": "paddle_tpu.gmu", "window": "paddle_tpu.diff_attn",
          "full": "paddle_tpu.diff_attn", "cross": "paddle_tpu.diff_attn"}


@dataclasses.dataclass
class SambaYConfig:
    vocab_size: int = 200064
    hidden_size: int = 2560
    intermediate_size: int = 10240
    num_hidden_layers: int = 32
    num_attention_heads: int = 40
    num_key_value_heads: int = 20
    sliding_window: int = 512
    mb_per_layer: int = 2
    layer_norm_eps: float = 1e-5
    tie_word_embeddings: bool = True
    max_position_embeddings: int = 262144
    # not in the published config.json: the published model code's
    mamba_d_state: int = 16
    mamba_d_conv: int = 4
    mamba_expand: int = 2
    mamba_dt_rank: int = 0              # 0: hidden_size / 16
    lambda_std: float = 0.1
    initializer_range: float = 0.02

    def __post_init__(self):
        if self.mb_per_layer != 2 or self.num_hidden_layers % 4 \
                or self.num_hidden_layers < 8:
            raise ValueError("the layout alternates a state-space and an "
                             "attention layer (mb_per_layer 2) over a "
                             "multiple of 4 layers, at least 8")
        if self.num_attention_heads != 2 * self.num_key_value_heads \
                or self.num_key_value_heads % 2:
            raise ValueError("differential attention pairs the heads: "
                             "2 query heads a key head, key heads in "
                             "pairs")
        if not self.tie_word_embeddings:
            raise ValueError("the head is the embedding, transposed")

    @property
    def head_dim(self):
        return self.hidden_size // self.num_attention_heads

    @property
    def d_inner(self):
        return self.mamba_expand * self.hidden_size

    @property
    def dt_rank(self):
        return self.mamba_dt_rank or math.ceil(self.hidden_size / 16)

    # the serving engine reads these of any decoder's config
    rope_theta = 0.0

    def kind(self, index):
        half = self.num_hidden_layers // 2
        if index < half:
            return "window" if index % 2 else "mamba"
        if index == half:
            return "mamba_memory"
        if index == half + 1:
            return "full"
        return "cross" if index % 2 else "gmu"


def tiny_sambay_config(**kw):
    """A few-thousand-parameter config for tests and rehearsals: eight
    layers hold all six kinds."""
    base = dict(vocab_size=128, hidden_size=64, intermediate_size=128,
                num_hidden_layers=8, num_attention_heads=8,
                num_key_value_heads=4, sliding_window=16,
                max_position_embeddings=512)
    base.update(kw)
    return SambaYConfig(**base)


def _winit(cfg):
    return Normal(mean=0.0, std=cfg.initializer_range)


def lambda_init(index):
    return 0.8 - 0.6 * math.exp(-0.3 * index)


def _dense_attention(q, k, v, window):
    """Causal attention of ``q [B, S, H, d]`` over ``k [B, S, Hk, d]``
    and ``v [B, S, Hk, dv]`` (a query head sees key head ``h // (H /
    Hk)``), each query the last ``window`` keys where given: float32."""
    b, s, h, d = q.shape
    g = h // k.shape[2]
    f32 = jnp.float32
    kq = jnp.repeat(k, g, axis=2).astype(f32)
    vq = jnp.repeat(v, g, axis=2).astype(f32)
    sc = jnp.einsum("bqhd,bkhd->bhqk", q.astype(f32), kq)
    i = jnp.arange(s)
    mask = i[None, :] <= i[:, None]
    if window is not None:
        mask &= i[None, :] > i[:, None] - window
    p = jax.nn.softmax(jnp.where(mask, sc, -jnp.inf), axis=-1)
    return jnp.einsum("bhqk,bkhd->bqhd", p, vq)


class SambaYMLP(nn.Layer):
    def __init__(self, c: SambaYConfig):
        super().__init__()
        self.up = nn.Linear(c.hidden_size, 2 * c.intermediate_size,
                            weight_attr=_winit(c), bias_attr=False)
        self.down = nn.Linear(c.intermediate_size, c.hidden_size,
                              weight_attr=_winit(c), bias_attr=False)

    def forward(self, x):
        def act(gv):
            g, v = jnp.split(gv, 2, axis=-1)
            return (jax.nn.silu(g.astype(jnp.float32))
                    * v.astype(jnp.float32)).astype(gv.dtype)

        return self.down(run_op("sambay_gate_up", act, (self.up(x),),
                                differentiable=False))


class MambaMixer(nn.Layer):
    """The state-space mixer (module docstring). ``memory`` marks the
    one whose scan output the gated memory units read."""

    def __init__(self, c: SambaYConfig, memory=False):
        super().__init__()
        self.memory = memory
        self.d_inner, self.d_state = c.d_inner, c.mamba_d_state
        self.d_conv, self.dt_rank = c.mamba_d_conv, c.dt_rank
        ci, n, wa = self.d_inner, self.d_state, _winit(c)

        def lin(i, o, bias=False):
            return nn.Linear(i, o, weight_attr=wa,
                             bias_attr=None if bias else False)

        self.in_proj = lin(c.hidden_size, 2 * ci)
        self.conv_w = self.create_parameter([ci, self.d_conv], attr=wa)
        self.conv_b = self.create_parameter([ci], is_bias=True)
        self.x_proj = lin(ci, self.dt_rank + 2 * n)
        self.dt_proj = lin(self.dt_rank, ci, bias=True)
        # A = -exp(A_log): the states 1..N of every channel
        self.A_log = Parameter(jnp.broadcast_to(jnp.log(jnp.arange(
            1, n + 1, dtype=jnp.float32))[:, None], (n, ci))
            .astype(self.conv_w._data.dtype))
        self.D = self.create_parameter(
            [ci], default_initializer=Constant(1.0))
        self.out_proj = lin(ci, c.hidden_size)

    def state_shapes(self, dtype):
        """What a sequence keeps: the conv's last inputs (the model's
        dtype) and the state (float32), channels on the lanes."""
        return [((self.d_conv - 1, self.d_inner), dtype),
                ((self.d_state, self.d_inner), jnp.float32)]

    def _rows(self, x, z, prev, h0, q_lens, pack, unpack, long_rows=None):
        """The mixer from its in-projection's halves on: ``x, z [T, C]``
        packed tokens; ``pack`` lays packed values out as rows ``[R, Q,
        .]``, ``unpack`` is its inverse. Returns ``(gated [T, C], m [T,
        C], the rows' conv inputs and states to keep)``. Traceable."""
        r, n = self.dt_rank, self.d_state
        f32 = jnp.float32

        def fn(x, z, prev, h0, q_lens, cw, cb, wx, wdt, bdt, alog, dd):
            xc, last = ss.causal_conv_rows(pack(x), prev, cw, cb, q_lens)
            xp = unpack(xc)
            dbc = jnp.matmul(xp, wx, preferred_element_type=f32)
            dt = jax.nn.softplus(
                jnp.matmul(dbc[:, :r].astype(xp.dtype), wdt,
                           preferred_element_type=f32) + bdt.astype(f32))
            m, h = ss.selective_scan_rows(
                xc, pack(dt), pack(dbc[:, r:r + n]), pack(dbc[:, r + n:]),
                -jnp.exp(alog.astype(f32)), dd, h0, q_lens,
                long_rows=long_rows)
            m = unpack(m)
            gated = (jax.nn.silu(z.astype(f32)) * m.astype(f32)) \
                .astype(x.dtype)
            return gated, m, last, h

        return run_op("sambay_mamba", fn,
                      (x, z, prev, h0, q_lens, self.conv_w, self.conv_b,
                       self.x_proj.weight, self.dt_proj.weight,
                       self.dt_proj.bias, self.A_log, self.D),
                      differentiable=False)

    def _halves(self, u):
        ci = self.d_inner

        def fn(xz):
            xz = xz.reshape(-1, 2 * ci)
            return xz[:, :ci], xz[:, ci:]

        return run_op("sambay_mamba_halves", fn, (self.in_proj(u),),
                      differentiable=False)

    def forward(self, u):
        """Whole sequences ``u [B, S, H]`` from zero states:
        ``(out [B, S, H], m [B, S, C])``."""
        b, s = u.shape[0], u.shape[1]
        ci, n = self.d_inner, self.d_state
        x, z = self._halves(u)
        dt_ = x._data.dtype
        gated, m, _, _ = self._rows(
            x, z, Tensor(jnp.zeros((b, self.d_conv - 1, ci), dt_)),
            Tensor(jnp.zeros((b, n, ci), jnp.float32)),
            Tensor(jnp.full((b,), s, jnp.int32)),
            lambda a: a.reshape(b, s, a.shape[-1]),
            lambda a: a.reshape(b * s, a.shape[-1]))
        return self.out_proj(gated.reshape([b, s, ci])), \
            m.reshape([b, s, ci])

    def serving(self, u, step, pages):
        """One packed step ``u [1, T, H]`` over this layer's two state
        pools ``[slots, ., C]``: every row starts from its slot's state
        (zeros at position 0) and writes its last state back."""
        conv_pool, ssm_pool = pages
        x, z = self._halves(u)
        idx, flat, slots, starts = (step.row_index(), step.flat_idx,
                                    step.slots, step.q_starts)
        rows, qb = step.rows, step.qblock

        def take(conv_pool, ssm_pool, slots, starts):
            with jax.named_scope(ss.SCOPE):
                fresh = (starts == 0)[:, None, None]
                s_ = slots.astype(jnp.int32)
                return (jnp.where(fresh, 0, conv_pool[s_]),
                        jnp.where(fresh, 0, ssm_pool[s_]))

        prev, h0 = run_op("sambay_state_read", take,
                          (conv_pool, ssm_pool, slots, starts),
                          differentiable=False)
        gated, m, last, h = self._rows(
            x, z, prev, h0, step.q_lens,
            lambda a: a[idx._data],
            lambda a: a.reshape(rows * qb, a.shape[-1])[flat._data],
            long_rows=step.chunk_rows)

        def put(conv_pool, ssm_pool, slots, last, h):
            with jax.named_scope(ss.SCOPE):
                s_ = slots.astype(jnp.int32)
                return (conv_pool.at[s_].set(last.astype(conv_pool.dtype)),
                        ssm_pool.at[s_].set(h))

        pages = run_op("sambay_state_write", put,
                       (conv_pool, ssm_pool, slots, last, h),
                       differentiable=False)
        t = u.shape[1]
        return self.out_proj(gated.reshape([1, t, self.d_inner])), \
            m, list(pages)


class GatedMemoryUnit(nn.Layer):
    def __init__(self, c: SambaYConfig):
        super().__init__()
        self.in_proj = nn.Linear(c.hidden_size, c.d_inner,
                                 weight_attr=_winit(c), bias_attr=False)
        self.out_proj = nn.Linear(c.d_inner, c.hidden_size,
                                  weight_attr=_winit(c), bias_attr=False)

    def forward(self, u, m):
        """``m`` of the same tokens, any leading shape with ``u``'s
        token count."""
        def fn(g, m):
            f32 = jnp.float32
            return (jax.nn.silu(g.astype(f32))
                    * m.reshape(g.shape).astype(f32)).astype(g.dtype)

        return self.out_proj(run_op("sambay_gmu", fn, (self.in_proj(u), m),
                                    differentiable=False))


class DiffAttention(nn.Layer):
    """Differential attention (module docstring). ``kind``: ``window``,
    ``full`` (own K/V) or ``cross`` (queries only)."""

    def __init__(self, c: SambaYConfig, index, kind):
        super().__init__()
        self.kind = kind
        self.heads, self.kv_heads = c.num_attention_heads, \
            c.num_key_value_heads
        self.d = c.head_dim
        self.window = c.sliding_window if kind == "window" else None
        self.lam0 = lambda_init(index)
        self.eps = c.layer_norm_eps
        wa = _winit(c)
        width = self.heads * self.d
        if kind != "cross":
            width += 2 * self.kv_heads * self.d
        self.qkv = nn.Linear(c.hidden_size, width, weight_attr=wa)
        self.out_proj = nn.Linear(self.heads * self.d, c.hidden_size,
                                  weight_attr=wa)
        lam = Normal(mean=0.0, std=c.lambda_std)
        for name in ("lq1", "lk1", "lq2", "lk2"):
            setattr(self, name, self.create_parameter([self.d], attr=lam))
        self.subln = self.create_parameter(
            [2 * self.d], default_initializer=Constant(1.0))

    def _split(self, qkv):
        """``q [.., H, d]`` and, for a layer with K/V of its own, the
        key pairs and value pairs ``[.., Hk/2, 2d]``."""
        h, hk, d = self.heads, self.kv_heads, self.d
        lead = qkv.shape[:-1]
        q = qkv[..., :h * d].reshape(lead + (h, d))
        if self.kind == "cross":
            return q, None, None
        k = qkv[..., h * d:(h + hk) * d].reshape(lead + (hk // 2, 2 * d))
        v = qkv[..., (h + hk) * d:].reshape(lead + (hk // 2, 2 * d))
        return q, k, v

    def _padded(self, q):
        """Query heads on the lanes of the key head each sees: even
        heads ``[q | 0]``, odd heads ``[0 | q]``."""
        z = jnp.zeros_like(q)
        even = jnp.arange(self.heads) % 2 == 0
        return jnp.where(even[:, None],
                         jnp.concatenate([q, z], -1),
                         jnp.concatenate([z, q], -1))

    def _combine(self, attn, lq1, lk1, lq2, lk2, w):
        """``attn [.., H, 2d]`` (both softmaxes of every pair over the
        pair's values) -> ``[.., H/2 * 2d]``."""
        f32 = jnp.float32
        lam = jnp.exp(jnp.sum(lq1.astype(f32) * lk1.astype(f32))) \
            - jnp.exp(jnp.sum(lq2.astype(f32) * lk2.astype(f32))) \
            + self.lam0
        a = attn.astype(f32)
        o = a[..., 0::2, :] - lam * a[..., 1::2, :]
        o = o * jax.lax.rsqrt(jnp.mean(o * o, -1, keepdims=True)
                              + self.eps) * w.astype(f32)
        o = o * (1.0 - self.lam0)
        return o.reshape(o.shape[:-2] + (-1,)).astype(attn.dtype)

    def _lams(self):
        return (self.lq1, self.lk1, self.lq2, self.lk2, self.subln)

    def forward(self, u, shared=None):
        """Whole sequences ``u [B, S, H]``; a cross layer attends over
        ``shared`` (the full layer's key and value pairs). Returns
        ``(out, (k, v))``."""
        scale = 1.0 / math.sqrt(self.d)

        def fn(qkv, sk, sv, *lams):
            q, k, v = self._split(qkv)
            if k is None:
                k, v = sk, sv
            a = _dense_attention(self._padded(q) * scale, k, v,
                                 self.window).astype(qkv.dtype)
            return self._combine(a, *lams), k, v

        sk, sv = shared if shared is not None else (None, None)
        o, k, v = run_op("sambay_diff_attention", fn,
                         (self.qkv(u), sk, sv) + self._lams(),
                         differentiable=False)
        return self.out_proj(o), (k, v)

    def serving(self, u, step, pages):
        """One packed step over this layer's (or the layer it reads's)
        K/V pools ``[P, Hk/2, page, 2d]``."""
        from ..ops.ragged_paged_attention import \
            fused_ragged_paged_attention

        t, qb = step.tokens, step.qblock
        d2 = 2 * self.d

        def operands(qkv):
            q, k, v = self._split(qkv.reshape(t, -1))
            if k is None:           # not read by a read-only call
                k = v = jnp.zeros((t, self.kv_heads // 2, d2), qkv.dtype)
            return self._padded(q), k, v

        q, k, v = run_op("sambay_diff_operands", operands, (self.qkv(u),),
                         differentiable=False)
        sin, cos = step.no_rope(d2)
        tables = step.tables if self.window is None \
            else step.ring_tables(self.window)
        attn4, kp, vp = fused_ragged_paged_attention(
            q, k, v, pages[0], pages[1], tables, step.kv_lens,
            step.q_starts, step.q_lens, step.w_starts, step.w_flats,
            step.w_ends, pages[0].shape[0] - 1, rope_sin=sin,
            rope_cos=cos, qblock=qb, scale=1.0 / math.sqrt(self.d),
            window=self.window, read_only=self.kind == "cross")
        attn = step.unpack(attn4.reshape([step.rows * qb, self.heads, d2]))
        o = run_op("sambay_diff_combine", self._combine,
                   (attn,) + self._lams(), differentiable=False)
        return self.out_proj(o.reshape([1, t, -1])), [kp, vp]


class SambaYDecoderLayer(nn.Layer):
    def __init__(self, c: SambaYConfig, index: int):
        super().__init__()
        self.index, self.kind = index, c.kind(index)
        self.config = c
        eps = c.layer_norm_eps
        self.input_layernorm = nn.LayerNorm(c.hidden_size, epsilon=eps)
        self.post_attention_layernorm = nn.LayerNorm(c.hidden_size,
                                                     epsilon=eps)
        if self.kind in ("mamba", "mamba_memory"):
            self.mixer = MambaMixer(c, memory=self.kind == "mamba_memory")
        elif self.kind == "gmu":
            self.mixer = GatedMemoryUnit(c)
        else:
            self.mixer = DiffAttention(c, index, self.kind)
        self.mlp = SambaYMLP(c)
        half = c.num_hidden_layers // 2
        #: the layer whose scan output / whose pool this one reads
        self.memory_layer, self.kv_layer = half, half + 1

    def forward(self, x, carry):
        """Whole sequences; ``carry`` holds what earlier layers handed
        on (``"m"``, ``"kv"``) and is updated in place."""
        u = self.input_layernorm(x)
        if self.kind in ("mamba", "mamba_memory"):
            y, m = self.mixer(u)
            if self.mixer.memory:
                carry["m"] = m
        elif self.kind == "gmu":
            y = self.mixer(u, carry["m"])
        else:
            y, kv = self.mixer(u, carry.get("kv"))
            if self.kind == "full":
                carry["kv"] = kv
        x = x + y
        return x + self.mlp(self.post_attention_layernorm(x))

    # -- what the serving engine asks of a layer (inference/layer_step) --
    #: engine features that reach neither a state nor a ring of pages
    serving_unsupported = ("prefix_cache", "kv_dtype=int8", "kv_tier",
                           "spec_k", "weight_dtype=int8")

    def serving_cache(self):
        c = self.config
        if self.kind in ("mamba", "mamba_memory"):
            return SlotState(self.mixer.state_shapes(
                self.mixer.conv_w._data.dtype))
        if self.kind == "gmu":
            return None
        if self.kind == "cross":
            return SharedPages(self.kv_layer)
        return PagedKV(c.num_key_value_heads // 2, 2 * c.head_dim,
                       window=self.mixer.window)

    def serving_step(self, x, step, pages):
        """``(x, pages, None[, carry])``: a layer that keeps nothing
        returns no pages; the memory layer hands on its scan output."""
        u = self.input_layernorm(x)
        carry = None
        # one scope a mixer kind, projections included: what the
        # per-layer readers of the benchmark find a mixer's device time by
        with jax.named_scope(SCOPES[self.kind]):
            if self.kind in ("mamba", "mamba_memory"):
                y, m, pages = self.mixer.serving(u, step, pages)
                if self.mixer.memory:
                    carry = {"m": m}
            elif self.kind == "gmu":
                y, pages = self.mixer(u, step.carry["m"]), []
            else:
                y, pages = self.mixer.serving(u, step, pages)
        x = x + y
        x = x + self.mlp(self.post_attention_layernorm(x))
        return (x, pages, None) if carry is None \
            else (x, pages, None, carry)


class SambaYModel(nn.Layer):
    def __init__(self, config: SambaYConfig):
        super().__init__()
        self.config = config
        self.embed_tokens = nn.Embedding(config.vocab_size,
                                         config.hidden_size,
                                         weight_attr=_winit(config))
        self.layers = nn.LayerList(
            [SambaYDecoderLayer(config, i)
             for i in range(config.num_hidden_layers)])
        self.norm = nn.LayerNorm(config.hidden_size,
                                 epsilon=config.layer_norm_eps)

    def forward(self, input_ids):
        x = self.embed_tokens(input_ids)
        carry = {}
        for layer in self.layers:
            x = layer(x, carry)
        return self.norm(x)


class SambaYForCausalLM(nn.Layer):
    """Decoder LM: ``forward(input_ids)`` returns logits ``[B, S, V]``;
    `generate` is greedy and cache-free (the oracle of the serving
    engine's tests, not a server)."""

    def __init__(self, config: SambaYConfig):
        super().__init__()
        self.config = config
        self.model = SambaYModel(config)

    def _logits(self, hidden):
        from ..tensor import linalg
        return linalg.matmul(hidden, self.model.embed_tokens.weight,
                             transpose_y=True)

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
