"""Llama decoder family (the BASELINE.md north-star model).

Capability reference: the reference framework trains Llama via PaddleNLP on
top of the fused ops in `python/paddle/incubate/nn/functional/` (swiglu,
fused_rms_norm, fused_rotary_position_embedding) and flash attention
(`python/paddle/nn/functional/flash_attention.py:147`). This module is the
TPU-native recipe built on the same in-tree pieces:

- pre-norm decoder blocks: RMSNorm -> GQA attention (+rope) -> RMSNorm ->
  SwiGLU MLP, all through the eager tape so one definition serves eager
  debugging and ``jit.to_static`` whole-step compilation;
- attention dispatches to the Pallas GQA flash kernel when shapes allow
  (`paddle_tpu/ops/flash_attention.py`), XLA fallback otherwise;
- :func:`shard_llama` annotates every weight with (tp, fsdp) placements
  over a ``ProcessMesh`` — GSPMD inserts the Megatron collectives
  (column/row linear all-gather + psum, vocab-parallel embedding) from the
  layout alone, the TPU analog of the reference's
  `fleet/layers/mpu/mp_layers.py`.
"""

from __future__ import annotations

import dataclasses
import math

import jax
import jax.numpy as jnp
import numpy as np

from .. import nn
from ..nn import functional as F
from ..framework.tensor import Tensor
from ..incubate.nn import functional as FI
from ..nn.initializer import Normal

__all__ = ["LlamaConfig", "LlamaMLP", "LlamaMoEMLP", "LlamaAttention",
           "LlamaDecoderLayer", "LlamaModel", "LlamaForCausalLM",
           "shard_llama", "llama3_8b_config", "tiny_llama_config"]


@dataclasses.dataclass
class LlamaConfig:
    vocab_size: int = 32000
    hidden_size: int = 4096
    intermediate_size: int = 11008
    num_hidden_layers: int = 32
    num_attention_heads: int = 32
    num_key_value_heads: int = 32
    max_position_embeddings: int = 8192
    rms_norm_eps: float = 1e-5
    rope_theta: float = 10000.0
    tie_word_embeddings: bool = False
    initializer_range: float = 0.02
    #: checkpoint each decoder layer (training fwd): activations
    #: recompute in the backward sweep, trading ~1 extra forward for
    #: O(L) -> O(1) layer-activation memory (bigger batch/seq fits)
    recompute: bool = False
    #: > 0 selects the mixture-of-experts FFN (:class:`LlamaMoEMLP`,
    #: Mixtral-style) in every decoder layer: stacked ``[E, ...]``
    #: expert weights, dropless top-``moe_top_k`` routing through the
    #: grouped-GEMM kernel. 0 keeps the dense SwiGLU :class:`LlamaMLP`.
    moe_num_experts: int = 0
    moe_top_k: int = 2
    #: per-expert FFN width; None reuses ``intermediate_size``
    moe_intermediate_size: int | None = None

    @property
    def head_dim(self):
        return self.hidden_size // self.num_attention_heads


def llama3_8b_config():
    """Llama-3-8B: GQA 32q/8kv, 128k vocab, rope theta 500k."""
    return LlamaConfig(
        vocab_size=128256, hidden_size=4096, intermediate_size=14336,
        num_hidden_layers=32, num_attention_heads=32, num_key_value_heads=8,
        max_position_embeddings=8192, rms_norm_eps=1e-5, rope_theta=500000.0)


def tiny_llama_config(**kw):
    """A few-thousand-param config for tests and dry runs."""
    base = dict(vocab_size=128, hidden_size=64, intermediate_size=128,
                num_hidden_layers=2, num_attention_heads=4,
                num_key_value_heads=2, max_position_embeddings=256,
                rope_theta=10000.0)
    base.update(kw)
    return LlamaConfig(**base)


def _winit(cfg):
    return Normal(mean=0.0, std=cfg.initializer_range)


def _kv_cache_update(buf, new, start):
    """Write ``new`` [B, s, Hk, D] into ``buf`` [B, max_len, Hk, D] at
    sequence offset ``start`` (a scalar int Tensor, traced-safe)."""
    import jax
    import jax.numpy as jnp
    from ..framework.tensor import run_op

    s, max_len = new.shape[1], buf.shape[1]
    start_arr = start._data if hasattr(start, "_data") else start
    if not isinstance(start_arr, jax.core.Tracer) \
            and int(start_arr) + s > max_len:
        # dynamic_update_slice would silently clamp the start and corrupt
        # the newest cached positions — refuse instead
        raise ValueError(
            f"KV cache overflow: writing {s} tokens at offset "
            f"{int(start_arr)} exceeds the static buffer ({max_len})")

    def fn(b, n, st):
        zero = jnp.zeros((), jnp.int32)
        return jax.lax.dynamic_update_slice(
            b, n.astype(b.dtype), (zero, jnp.asarray(st, jnp.int32),
                                   zero, zero))

    return run_op("kv_cache_update", fn, (buf, new, start))


def _decode_mask(length, s, max_len):
    """Bool [1, 1, s, max_len]: query i (absolute pos length+i) sees key j
    iff j <= length + i — causal over the valid prefix of a static
    buffer."""
    import jax.numpy as jnp
    from ..framework.tensor import run_op

    def fn(ln):
        qpos = jnp.asarray(ln, jnp.int32) + jnp.arange(s, dtype=jnp.int32)
        kpos = jnp.arange(max_len, dtype=jnp.int32)
        return (kpos[None, :] <= qpos[:, None])[None, None]

    return run_op("decode_mask", fn, (length,), differentiable=False)


class LlamaMLP(nn.Layer):
    """SwiGLU MLP: down(silu(gate(x)) * up(x))."""

    def __init__(self, config: LlamaConfig):
        super().__init__()
        wa = _winit(config)
        self.gate_proj = nn.Linear(config.hidden_size,
                                   config.intermediate_size,
                                   weight_attr=wa, bias_attr=False)
        self.up_proj = nn.Linear(config.hidden_size,
                                 config.intermediate_size,
                                 weight_attr=wa, bias_attr=False)
        self.down_proj = nn.Linear(config.intermediate_size,
                                   config.hidden_size,
                                   weight_attr=wa, bias_attr=False)

    def forward(self, x):
        return self.down_proj(FI.swiglu(self.gate_proj(x), self.up_proj(x)))


class LlamaMoEMLP(nn.Layer):
    """Mixture-of-experts SwiGLU FFN (Mixtral-style), selected by
    ``config.moe_num_experts > 0``.

    Per token: softmax router over ``E`` experts, top-``k`` selection
    with renormalized weights, each expert a bias-free SwiGLU MLP with
    stacked ``[E, ...]`` weights. Routing is **dropless** (capacity =
    the token count, which an expert's load can never exceed), so the
    output of every token is a pure function of that token's hidden
    state — independent of how a batch is packed. That invariance is
    what lets the serving engine's token-packed mixed program emit
    greedy tokens EXACTLY equal to the plain ``LlamaForCausalLM``
    forward: pad/trash tokens route somewhere, but never into another
    token's output.

    Compute rides the grouped-GEMM megakernel
    (:mod:`paddle_tpu.ops.grouped_gemm`): one gather lays token-choices
    out expert-contiguous, three grouped GEMMs (gate/up/down) walk the
    ragged per-expert row blocks, one gather combines back. The
    per-token-count forward compiles through the ``moe_mlp`` compile
    watch (bounded LRU, same contract as ``MoELayer``).
    """

    FN_CACHE_SIZE = 8

    def __init__(self, config: LlamaConfig):
        super().__init__()
        import collections

        from ..framework import random as frandom
        from ..framework.tensor import Parameter

        e = int(config.moe_num_experts)
        if e <= 0:
            raise ValueError("LlamaMoEMLP needs config.moe_num_experts "
                             f"> 0, got {e}")
        self.num_experts = e
        self.top_k = max(1, min(int(config.moe_top_k), e))
        self.d_model = config.hidden_size
        self.d_ff = config.moe_intermediate_size \
            or config.intermediate_size
        std = config.initializer_range

        def init(shape):
            return Parameter(jax.random.normal(
                frandom.next_key(), shape, jnp.float32) * std)

        self.gate = init((self.d_model, e))
        self.gate_proj = init((e, self.d_model, self.d_ff))
        self.up_proj = init((e, self.d_model, self.d_ff))
        self.down_proj = init((e, self.d_ff, self.d_model))
        self.l_aux = None
        #: set by shard_llama: sharded expert weights must take the
        #: GSPMD-partitionable XLA formulation (a Pallas custom call
        #: would pin execution to one replica)
        self.sharded = False
        #: set by quantize_weights: the per-block size of the int8
        #: expert weights (None/0 = float weights, the default)
        self.weight_block = None
        self._fns: "dict[int, object]" = collections.OrderedDict()

    def quantize_weights(self, block=None):
        """Swap the stacked expert weights (in place) for their
        weight-only int8 serving form: each ``[E, K, N]`` Parameter
        becomes an int8 buffer of the same shape plus an
        ``[E, ceil(K/B), N]`` f32 scale buffer (``<name>_scale``), and
        the grouped FFN reroutes through ``grouped_gemm_q8`` (in-VMEM
        dequant). Serving-side only — the quantized weights are frozen
        (see :mod:`paddle_tpu.quant`). The router gate stays float
        (tiny, and routing decisions are the quality-critical bits)."""
        from ..quant.format import effective_block, quantize_weight

        if self.weight_block:
            return
        # one nominal block; per-tensor effective blocks (clamped to
        # each K) are derived from it at build time
        block = effective_block(max(self.d_model, self.d_ff), block)
        for name in ("gate_proj", "up_proj", "down_proj"):
            p = getattr(self, name)
            b = min(block, p.shape[-2])
            q, s = quantize_weight(p, b)
            delattr(self, name)
            self.register_buffer(name, Tensor(np.asarray(q)))
            self.register_buffer(name + "_scale", Tensor(np.asarray(s)))
        self.weight_block = int(block)
        self._fns.clear()

    def to(self, device=None, dtype=None, blocking=None):
        # model-wide dtype casts must keep the quantized format's
        # invariant: scale sidecars stay f32 (bf16 scales would change
        # the dequant products; see quant.layers.WeightOnlyLinear.to)
        out = super().to(device=device, dtype=dtype, blocking=blocking)
        if self.weight_block:
            for name in ("gate_proj_scale", "up_proj_scale",
                         "down_proj_scale"):
                s = self._buffers[name]
                if s._data.dtype != jnp.float32:
                    s._data = s._data.astype(jnp.float32)
        return out

    def _build_fn(self, n):
        from ..incubate.moe import top_k_routing
        from ..ops.grouped_gemm import _grouped

        e, k = self.num_experts, self.top_k
        uk = False if self.sharded else None

        if self.weight_block:
            return self._build_q8_fn(n, e, k, uk)

        def fn(x2d, gate, wg, wu, wd):
            logits = jnp.matmul(x2d.astype(jnp.float32), gate)
            # dropless: capacity = n (an expert appears at most once in
            # any token's top-k, so its load never exceeds the token
            # count) — keep is all-True, nothing is ever dropped. The
            # price of that exactness is the strided [E*n, ...] buffer
            # (only n*k rows real; the kernel skips the rest's MXU
            # work): fine at serving chunk budgets, and the lever to
            # revisit if E*chunk_budget ever dominates HBM.
            slot_token, expert_of, pos_of, keep, weights, aux = \
                top_k_routing(logits, k, n, normalize=True)
            gs = jnp.zeros((e,), jnp.int32).at[expert_of.reshape(-1)] \
                .add(keep.reshape(-1).astype(jnp.int32))
            gathered = x2d[jnp.maximum(slot_token, 0)]      # [E*n, D]
            g = _grouped(gathered, wg, gs, use_kernel=uk)
            u = _grouped(gathered, wu, gs, use_kernel=uk)
            h = jax.nn.silu(g) * u                          # swiglu
            y = _grouped(h, wd, gs, use_kernel=uk)
            idx = expert_of * n + jnp.clip(pos_of, 0, n - 1)
            picked = y[idx]                                 # [n, k, D]
            wk = (weights * keep).astype(x2d.dtype)
            return jnp.einsum("nk,nkd->nd", wk, picked), aux

        return fn

    def _build_q8_fn(self, n, e, k, uk):
        """The weight-only int8 forward: same routing, the three
        grouped GEMMs ride ``grouped_gemm_q8`` (int8 expert weights +
        scale sidecars, in-VMEM dequant). Per-tensor effective blocks
        clamp the nominal block to each contraction dim."""
        from ..incubate.moe import top_k_routing
        from ..ops.grouped_gemm import _grouped_q8

        bg = min(self.weight_block, self.d_model)   # gate/up: K=d_model
        bd = min(self.weight_block, self.d_ff)      # down: K=d_ff

        def fn(x2d, gate, wg, sg, wu, su, wd, sd):
            logits = jnp.matmul(x2d.astype(jnp.float32), gate)
            slot_token, expert_of, pos_of, keep, weights, aux = \
                top_k_routing(logits, k, n, normalize=True)
            gs = jnp.zeros((e,), jnp.int32).at[expert_of.reshape(-1)] \
                .add(keep.reshape(-1).astype(jnp.int32))
            gathered = x2d[jnp.maximum(slot_token, 0)]      # [E*n, D]
            g = _grouped_q8(gathered, wg, sg, gs, bg, use_kernel=uk)
            u = _grouped_q8(gathered, wu, su, gs, bg, use_kernel=uk)
            h = jax.nn.silu(g) * u                          # swiglu
            y = _grouped_q8(h, wd, sd, gs, bd, use_kernel=uk)
            idx = expert_of * n + jnp.clip(pos_of, 0, n - 1)
            picked = y[idx]                                 # [n, k, D]
            wk = (weights * keep).astype(x2d.dtype)
            return jnp.einsum("nk,nkd->nd", wk, picked), aux

        return fn

    def build_fn(self, n_tokens):
        """Public access to the per-token-count compiled forward
        (``fn(x2d, gate, gate_proj, up_proj, down_proj) -> (out,
        aux)`` on raw arrays), compile-watched as ``moe_mlp`` with a
        bounded LRU cache."""
        from ..incubate.moe import _watched_fn_cache

        return _watched_fn_cache(self._fns, int(n_tokens),
                                 self._build_fn, "moe_mlp",
                                 self.FN_CACHE_SIZE)

    def forward(self, x):
        from ..framework.tensor import run_op

        shape = x.shape
        d = shape[-1]
        n = 1
        for s in shape[:-1]:
            n *= s
        x2d = x.reshape([n, d])
        if self.weight_block:
            # frozen int8 weights: the op is not differentiable
            out, aux = run_op(
                "moe_mlp", self.build_fn(n),
                (x2d, self.gate, self.gate_proj, self.gate_proj_scale,
                 self.up_proj, self.up_proj_scale, self.down_proj,
                 self.down_proj_scale), differentiable=False)
        else:
            out, aux = run_op(
                "moe_mlp", self.build_fn(n),
                (x2d, self.gate, self.gate_proj, self.up_proj,
                 self.down_proj))
        self.l_aux = aux
        return out.reshape(shape)


def _mesh_attention(q, k, v, mesh, batch_axes, tp_axis):
    """Causal attention of a GSPMD-sharded model, one device's share at
    a time: batch splits over ``batch_axes`` and heads over ``tp_axis``
    (attention mixes neither), each where the axis divides it, and the
    per-device body picks the flash kernel or the XLA composition by
    the same rule as ``scaled_dot_product_attention``. Without the
    ``shard_map`` the chip's compiler refuses the program: a Mosaic
    kernel cannot be partitioned automatically."""
    from .. import flags
    from ..framework.tensor import run_op
    from ..nn.functional.attention import _naive_attention
    from ..ops import flash_attention as fa

    b, _, h, d = q.shape
    hk = k.shape[2]
    sizes = dict(zip(mesh.dim_names, mesh.shape))
    batch = tuple(a for a in batch_axes if b % sizes[a] == 0)
    n_batch = math.prod(sizes[a] for a in batch)
    if b % n_batch:
        batch = ()
    heads = tp_axis if tp_axis and h % sizes[tp_axis] == 0 \
        and hk % sizes[tp_axis] == 0 else None
    spec = jax.sharding.PartitionSpec(batch or None, None, heads, None)
    use_pallas = flags.flag("use_pallas_kernels")

    def local(q_, k_, v_):
        if use_pallas and fa.supported(q_, k_, v_, None, True):
            return fa._make_flash(1.0 / math.sqrt(d), True,
                                  q_.shape[2] // k_.shape[2])(q_, k_, v_)
        return _naive_attention(q_, k_, v_, None, 0.0, True, None)

    def fn(q_, k_, v_):
        return jax.shard_map(local, mesh=mesh.to_jax_mesh(),
                             in_specs=(spec,) * 3, out_specs=spec,
                             check_vma=False)(q_, k_, v_)

    return run_op("mesh_attention", fn, (q, k, v))


class LlamaAttention(nn.Layer):
    """GQA attention with rotary embeddings; [B, S, H, D] layout throughout
    so the Pallas flash kernel path needs no relayout."""

    def __init__(self, config: LlamaConfig):
        super().__init__()
        self.config = config
        self.num_heads = config.num_attention_heads
        self.num_kv_heads = config.num_key_value_heads
        self.head_dim = config.head_dim
        h, hk, d = self.num_heads, self.num_kv_heads, self.head_dim
        wa = _winit(config)
        self.q_proj = nn.Linear(config.hidden_size, h * d, weight_attr=wa,
                                bias_attr=False)
        self.k_proj = nn.Linear(config.hidden_size, hk * d, weight_attr=wa,
                                bias_attr=False)
        self.v_proj = nn.Linear(config.hidden_size, hk * d, weight_attr=wa,
                                bias_attr=False)
        self.o_proj = nn.Linear(h * d, config.hidden_size, weight_attr=wa,
                                bias_attr=False)
        #: set by shard_llama: (ProcessMesh, batch axes, tp axis) — the
        #: training attention then runs per device under shard_map (a
        #: Mosaic kernel cannot be partitioned by GSPMD)
        self.mesh_spec = None

    def forward(self, x, position_ids=None, cache=None, cache_len=None,
                attn_mask=None):
        b, s = x.shape[0], x.shape[1]
        h, hk, d = self.num_heads, self.num_kv_heads, self.head_dim
        q = self.q_proj(x).reshape([b, s, h, d])
        k = self.k_proj(x).reshape([b, s, hk, d])
        v = self.v_proj(x).reshape([b, s, hk, d])
        if cache is not None and cache_len is None:
            raise ValueError(
                "cache_len (scalar int Tensor) is required when a KV "
                "cache is passed — the static buffer needs the write "
                "offset")
        if position_ids is None and cache is not None:
            # direct layer use: rope continues after the cached prefix
            # (LlamaModel.forward precomputes this; keep the layer correct
            # standalone too)
            from ..tensor import creation
            position_ids = creation.arange(
                0, s, dtype="int64").reshape([1, s]) \
                + cache_len.astype("int64")
        q, k, v = FI.fused_rotary_position_embedding(
            q, k, v, position_ids=position_ids,
            rotary_emb_base=self.config.rope_theta)
        if cache is not None:
            # decode path: write into the static [B, max_len, Hk, D] buffer
            # at cache_len (the TPU idiom — no shape growth, one compile for
            # all decode steps; reference capability:
            # phi/kernels/fusion/gpu/block_multi_head_attention_kernel.cu)
            k_buf = _kv_cache_update(cache[0], k, cache_len)
            v_buf = _kv_cache_update(cache[1], v, cache_len)
            if attn_mask is None:
                attn_mask = _decode_mask(cache_len, s, k_buf.shape[1])
            out = F.scaled_dot_product_attention(q, k_buf, v_buf,
                                                 attn_mask=attn_mask)
            out = self.o_proj(out.reshape([b, s, h * d]))
            return out, (k_buf, v_buf)
        if self.mesh_spec is not None:
            out = _mesh_attention(q, k, v, *self.mesh_spec)
        else:
            out = F.scaled_dot_product_attention(q, k, v, is_causal=True)
        return self.o_proj(out.reshape([b, s, h * d]))


class LlamaDecoderLayer(nn.Layer):
    def __init__(self, config: LlamaConfig):
        super().__init__()
        self.input_layernorm = nn.RMSNorm(config.hidden_size,
                                          epsilon=config.rms_norm_eps)
        self.self_attn = LlamaAttention(config)
        self.post_attention_layernorm = nn.RMSNorm(
            config.hidden_size, epsilon=config.rms_norm_eps)
        # config-selected FFN: the serving engine's mixed program and
        # the plain forward both call self.mlp, so an MoE checkpoint
        # serves with zero scheduler changes
        self.mlp = LlamaMoEMLP(config) if config.moe_num_experts \
            else LlamaMLP(config)

    def forward(self, x, position_ids=None, cache=None, cache_len=None,
                attn_mask=None):
        h = self.input_layernorm(x)
        if cache is not None:
            attn, cache = self.self_attn(h, position_ids, cache, cache_len,
                                         attn_mask)
        else:
            attn = self.self_attn(h, position_ids)
        x = x + attn
        x = x + self.mlp(self.post_attention_layernorm(x))
        if cache is not None:
            return x, cache
        return x

    # -- what the serving engine asks of a layer (inference/layer_step) --
    def serving_cache(self):
        """Per token this layer writes a K and a V pool, each ``(kv
        heads, head_dim)``: head-major pages ``[P, Hk, page, D]``."""
        a = self.self_attn
        return [(a.num_kv_heads, a.head_dim), (a.num_kv_heads, a.head_dim)]

    @property
    def serving_unsupported(self):
        """What the engine must refuse for these pages, by name: a
        ``head_dim`` the one serving program cannot rotate."""
        from ..ops.ragged_paged_attention import fused_rope_geometry_ok

        d = self.self_attn.head_dim
        return () if fused_rope_geometry_ok(d) else (f"head_dim={d}",)

    def serving_step(self, x, step, pages):
        """One packed step of this layer over its pages (``[K, V]``, then
        their int8 scale sidecars where the engine quantizes pages):
        ``(x, pages, None)``. Rope, page write and attention are ONE
        `fused_ragged_paged_attention` call: q and k stay PRE-rope in
        the packed token layout, the kernel slices each row's
        contiguous tokens through the scalar-prefetched write metadata,
        rotates them in VMEM by the step's shared sin/cos tables (no
        transcendentals in-kernel: Mosaic and XLA then agree bit for
        bit) and writes each row's K/V into its pages in-grid."""
        from ..ops.ragged_paged_attention import \
            fused_ragged_paged_attention

        att = self.self_attn
        t, qb = step.tokens, step.qblock
        rsin, rcos = step.rope(att.head_dim, float(att.config.rope_theta))
        h = self.input_layernorm(x)
        q = att.q_proj(h).reshape([t, att.num_heads, att.head_dim])
        k = att.k_proj(h).reshape([t, att.num_kv_heads, att.head_dim])
        v = att.v_proj(h).reshape([t, att.num_kv_heads, att.head_dim])
        sidecars = dict(k_scale=pages[2], v_scale=pages[3]) \
            if step.kv_quant else {}
        attn4, *pages = fused_ragged_paged_attention(
            q, k, v, pages[0], pages[1], step.tables, step.kv_lens,
            step.q_starts, step.q_lens, step.w_starts, step.w_flats,
            step.w_ends, step.trash_page, rope_sin=rsin, rope_cos=rcos,
            qblock=qb, **sidecars)
        attn = step.unpack(attn4.reshape([step.rows * qb, att.num_heads,
                                          att.head_dim]))
        x = x + att.o_proj(attn.reshape([1, t, -1]))
        x = x + self.mlp(self.post_attention_layernorm(x))
        return x, pages, None


class LlamaModel(nn.Layer):
    def __init__(self, config: LlamaConfig):
        super().__init__()
        self.config = config
        self.embed_tokens = nn.Embedding(config.vocab_size,
                                         config.hidden_size,
                                         weight_attr=_winit(config))
        self.layers = nn.LayerList(
            [LlamaDecoderLayer(config)
             for _ in range(config.num_hidden_layers)])
        self.norm = nn.RMSNorm(config.hidden_size,
                               epsilon=config.rms_norm_eps)

    def forward(self, input_ids, position_ids=None, caches=None,
                cache_len=None):
        x = self.embed_tokens(input_ids)
        new_caches = [] if caches is not None else None
        attn_mask = None
        if caches is not None:
            if cache_len is None:
                raise ValueError(
                    "cache_len is required when caches are passed")
            s = input_ids.shape[1]
            if position_ids is None:
                # rope positions continue after the cached prefix
                # (cache_len is a traced scalar: one program per shape)
                from ..tensor import creation
                position_ids = creation.arange(
                    0, s, dtype="int64").reshape([1, s]) \
                    + cache_len.astype("int64")
            # identical for every layer — build once, not per layer
            attn_mask = _decode_mask(cache_len, s, caches[0][0].shape[1])
        use_remat = self.config.recompute and caches is None \
            and not x.stop_gradient
        for i, layer in enumerate(self.layers):
            if caches is not None:
                x, c = layer(x, position_ids, caches[i], cache_len,
                             attn_mask)
                new_caches.append(c)
            elif use_remat:
                from ..distributed.recompute import recompute
                pol = "dots" if self.config.recompute == "dots" else None
                x = recompute(layer, x, position_ids, policy=pol)
            else:
                x = layer(x, position_ids)
        x = self.norm(x)
        if caches is not None:
            return x, new_caches
        return x


class LlamaForCausalLM(nn.Layer):
    """Decoder LM. ``forward(input_ids, labels=None)`` returns logits;
    with next-token labels (the input shifted by the caller,
    ignore_index=-100) it returns ``(loss, None)`` on the default
    chunked fused cross-entropy path — the logits are never built — or
    ``(loss, logits)`` under ``PADDLE_TPU_FUSED_CE=0`` / tied
    embeddings (the materialized path)."""

    def __init__(self, config: LlamaConfig):
        super().__init__()
        self.config = config
        self.model = LlamaModel(config)
        if config.tie_word_embeddings:
            self.lm_head = None
        else:
            self.lm_head = nn.Linear(config.hidden_size, config.vocab_size,
                                     weight_attr=_winit(config),
                                     bias_attr=False)

    def _logits(self, hidden):
        if self.lm_head is not None:
            return self.lm_head(hidden)
        from ..tensor import linalg
        return linalg.matmul(hidden, self.model.embed_tokens.weight,
                             transpose_y=True)

    def _fused_ce_enabled(self):
        """Default loss path: the chunked fused cross-entropy lm-head
        (``ops.fused_linear_cross_entropy``) — the ``[B*S, V]`` logits
        tensor never exists. ``PADDLE_TPU_FUSED_CE=0`` restores the
        materialized path byte-for-byte (and the tied-embedding model,
        whose projection is the transposed embedding table, always
        takes it)."""
        import os
        if self.lm_head is None:
            return False
        return os.environ.get("PADDLE_TPU_FUSED_CE", "1") != "0"

    def forward(self, input_ids, labels=None, position_ids=None):
        hidden = self.model(input_ids, position_ids)
        if labels is not None and self._fused_ce_enabled():
            # fused path returns (loss, None): logits were never built.
            # Callers needing them set PADDLE_TPU_FUSED_CE=0.
            from ..ops.fused_linear_cross_entropy import (
                fused_linear_cross_entropy)
            loss = fused_linear_cross_entropy(
                hidden, self.lm_head.weight, labels, ignore_index=-100)
            return loss, None
        logits = self._logits(hidden)
        if labels is None:
            return logits
        v = self.config.vocab_size
        loss = F.cross_entropy(
            logits.reshape([-1, v]).astype("float32"),
            labels.reshape([-1]), ignore_index=-100)
        return loss, logits

    def num_params(self):
        return sum(int(np.prod(p.shape)) for p in self.parameters())

    def flops_per_token(self, seq_len):
        """Approximate training FLOPs/token: 6*N_matmul_params + attention
        term (the standard MFU accounting). The embedding lookup is a
        gather, not a matmul, so its params are excluded — unless the
        embedding is tied and doubles as the output projection."""
        cfg = self.config
        n = self.num_params()
        if not cfg.tie_word_embeddings:
            n -= cfg.vocab_size * cfg.hidden_size  # embed_tokens lookup
        attn = 12 * cfg.num_hidden_layers * cfg.hidden_size * seq_len
        return 6 * n + attn

    @staticmethod
    def _pick_token(logits, rng_key, sampler):
        """next-token rule on [B, 1, V] logits. ``sampler`` is a static
        (do_sample, top_k, top_p, temperature) tuple — each distinct
        config compiles its own decode program."""
        from ..framework.tensor import run_op
        from ..tensor import search

        do_sample, top_k, top_p, temperature = sampler
        if not do_sample:
            return search.argmax(logits, axis=-1).astype("int64")

        def fn(logits, key):
            lg = logits[:, 0, :].astype(jnp.float32)
            lg = lg / max(float(temperature), 1e-6)
            if top_k:  # None or 0 disables the filter (HF/paddle convention)
                k = min(int(top_k), lg.shape[-1])
                kth = jnp.sort(lg, axis=-1)[:, -k][:, None]
                lg = jnp.where(lg >= kth, lg, -1e30)
            if top_p is not None:
                # nucleus over the (possibly top-k-restricted) softmax
                probs = jax.nn.softmax(lg, axis=-1)
                order = jnp.argsort(-probs, axis=-1)
                sp = jnp.take_along_axis(probs, order, axis=-1)
                cum_before = jnp.cumsum(sp, axis=-1) - sp
                keep_sorted = cum_before < float(top_p)
                keep = jnp.zeros_like(keep_sorted).at[
                    jnp.arange(lg.shape[0])[:, None], order].set(
                    keep_sorted)
                lg = jnp.where(keep, lg, -1e30)
            return jax.random.categorical(key, lg, axis=-1)[:, None]

        return run_op("sample_next_token", fn, (logits, rng_key),
                      differentiable=False).astype("int64")

    def _decode_step(self, tokens, cache_len, caches, rng_key=None,
                     sampler=(False, None, None, 1.0)):
        """One generation step: (next_token, new_cache_len, new_caches).
        Pure in (tokens, cache_len, caches, rng_key) so ``to_static``
        compiles it ONCE per shape — the static KV buffers keep every
        decode step the same program, and with input donation XLA updates
        them in place."""
        hidden, caches = self.model(tokens, None, caches, cache_len)
        logits = self._logits(hidden[:, -1:])
        nxt = self._pick_token(logits, rng_key, sampler)
        new_len = cache_len + tokens.shape[1]
        return nxt, new_len, caches

    def generate(self, input_ids, max_new_tokens=16, max_length=None,
                 do_sample=False, top_k=None, top_p=None, temperature=1.0,
                 seed=None):
        """Decode over a static KV cache: one compile for the prefill
        shape + one for the single-token decode shape, reused for every
        subsequent step and every same-shape call. Greedy by default;
        ``do_sample=True`` samples inside the compiled step (temperature
        -> top-k -> top-p nucleus -> categorical), deterministic under
        ``seed``. Inputs of the compiled step are donated (the caches
        alias in place on device), so nothing passed to one step is
        touched after it. The buffer length is bucketed (multiple of 64)
        so prompts of different lengths share the same decode executable."""
        from ..framework.tensor import Tensor, no_grad
        from ..framework import random as frandom
        from ..tensor import manipulation as M
        from .. import jit
        import jax.numpy as jnp

        sampler = (bool(do_sample), top_k, top_p, float(temperature))
        # the compiled step pins parameter objects + the sampler config;
        # rebuild if either changed (e.g. shard_llama swapped Parameters)
        param_key = (tuple(id(p) for p in self.parameters()), sampler)
        if getattr(self, "_decode_static", None) is None \
                or self._decode_param_key != param_key:
            def step_fn(tokens, cache_len, caches, rng_key):
                return self._decode_step(tokens, cache_len, caches,
                                         rng_key, sampler)
            # donate=False: weights are read-only pass-through in the
            # decode step, so donating them buys nothing — and with a
            # quantized model's many same-aval int8/scale slots XLA's
            # aval-based alias matching can scramble the pass-through
            # outputs across donated buffers (the caches still donate
            # via donate_inputs, which is where the in-place win lives)
            self._decode_static = jit.StaticFunction(
                step_fn, state=[self], warmup="once", donate=False,
                donate_inputs=True, name="llama.generate_step")
            self._decode_param_key = param_key
        step = self._decode_static
        base_key = jax.random.key(seed) if seed is not None \
            else frandom.next_key()
        with no_grad():
            b, s = input_ids.shape[0], input_ids.shape[1]
            need = s + max_new_tokens
            max_len = max_length if max_length is not None \
                else ((need + 63) // 64) * 64
            if max_len < need:
                raise ValueError(
                    f"max_length={max_len} < prompt + max_new_tokens "
                    f"({need})")
            caches = self._empty_caches(b, max_len)
            cache_len = Tensor(jnp.asarray(0, jnp.int32))
            # clone: the step donates its inputs, and the caller's
            # input_ids must survive
            tokens = Tensor(jnp.array(input_ids._data))
            new_tokens = []
            for i in range(max_new_tokens):
                key = Tensor(jax.random.fold_in(base_key, i))
                nxt, cache_len, caches = step(tokens, cache_len, caches,
                                              key)
                tokens = nxt.reshape([b, 1])
                # copy: `tokens` itself is donated into the next step, but
                # the appended value must survive until the final concat
                new_tokens.append(Tensor(jnp.array(tokens._data)))
            return M.concat([input_ids] + new_tokens, axis=1)

    def _empty_caches(self, batch, max_len):
        from ..tensor import creation
        cfg = self.config
        dt = self.model.embed_tokens.weight.dtype  # match model dtype
        return [
            (creation.zeros([batch, max_len, cfg.num_key_value_heads,
                             cfg.head_dim], dtype=dt),
             creation.zeros([batch, max_len, cfg.num_key_value_heads,
                             cfg.head_dim], dtype=dt))
            for _ in range(cfg.num_hidden_layers)]


# ---------------------------------------------------------------------------
# sharding recipe: (tp, fsdp) placements per weight — the Megatron layout
# expressed as GSPMD annotations (reference: fleet/layers/mpu/mp_layers.py)
# ---------------------------------------------------------------------------
def shard_llama(model: LlamaForCausalLM, mesh, tp_axis="mp",
                fsdp_axis=None, ep_axis=None):
    """Annotate a LlamaForCausalLM's weights over ``mesh``.

    - attention q/k/v and mlp gate/up: column-parallel (out-dim on tp)
    - attention o and mlp down: row-parallel (in-dim on tp)
    - embedding + lm_head: vocab-parallel
    - fsdp_axis (optional) shards the *other* matrix dim, giving the
      ZeRO-3 layout; norms shard on fsdp only.
    - ep_axis (optional, MoE models) shards the stacked ``[E, ...]``
      expert weights on their EXPERT dim over that mesh axis — expert
      parallelism: each rank owns ``E / ep`` experts' FFN weights, the
      router stays replicated (every rank routes every token), and the
      grouped-GEMM path demotes to the GSPMD XLA formulation exactly as
      the ``sharded`` stamp already does, so GSPMD partitions the
      batched per-expert dot and inserts the dispatch collectives.
    """
    from ..distributed import shard_tensor, Shard, Replicate

    tp_dim = mesh.dim_names.index(tp_axis) if tp_axis else None
    fs_dim = mesh.dim_names.index(fsdp_axis) if fsdp_axis else None
    ep_dim = mesh.dim_names.index(ep_axis) if ep_axis else None
    if ep_axis and not model.config.moe_num_experts:
        raise ValueError(
            "ep_axis shards stacked expert weights, but this config has "
            "moe_num_experts == 0 (dense FFN) — nothing to shard")

    def place(t, tp_tensor_dim, fsdp_tensor_dim, ep_tensor_dim=None):
        p = [Replicate()] * mesh.ndim
        if tp_dim is not None and tp_tensor_dim is not None:
            p[tp_dim] = Shard(tp_tensor_dim)
        if fs_dim is not None and fsdp_tensor_dim is not None:
            p[fs_dim] = Shard(fsdp_tensor_dim)
        if ep_dim is not None and ep_tensor_dim is not None:
            p[ep_dim] = Shard(ep_tensor_dim)
        return shard_tensor(t, mesh, p)

    m = model.model
    m.embed_tokens.weight = place(m.embed_tokens.weight, 0, 1)
    if model.lm_head is not None:
        model.lm_head.weight = place(model.lm_head.weight, 1, 0)
    for layer in m.layers:
        a, mlp = layer.self_attn, layer.mlp
        a.q_proj.weight = place(a.q_proj.weight, 1, 0)
        a.k_proj.weight = place(a.k_proj.weight, 1, 0)
        a.v_proj.weight = place(a.v_proj.weight, 1, 0)
        a.o_proj.weight = place(a.o_proj.weight, 0, 1)
        a.mesh_spec = (mesh, tuple(n for n in mesh.dim_names
                                   if n not in (tp_axis, ep_axis)), tp_axis)
        if isinstance(mlp, LlamaMoEMLP):
            # stacked [E, in, out] expert weights: tp splits the FFN
            # width exactly like the dense column/row layout; the
            # router stays replicated on tp AND ep (every rank routes
            # every token); fsdp shards the other matrix dim; ep shards
            # the expert dim itself
            mlp.gate = place(mlp.gate, None, 0)
            mlp.gate_proj = place(mlp.gate_proj, 2, 1, 0)
            mlp.up_proj = place(mlp.up_proj, 2, 1, 0)
            mlp.down_proj = place(mlp.down_proj, 1, 2, 0)
            # sharded experts: GSPMD needs the XLA grouped formulation
            # (drop any kernel-path programs built before sharding)
            mlp.sharded = True
            mlp._fns.clear()
        else:
            mlp.gate_proj.weight = place(mlp.gate_proj.weight, 1, 0)
            mlp.up_proj.weight = place(mlp.up_proj.weight, 1, 0)
            mlp.down_proj.weight = place(mlp.down_proj.weight, 0, 1)
        layer.input_layernorm.weight = place(
            layer.input_layernorm.weight, None, 0)
        layer.post_attention_layernorm.weight = place(
            layer.post_attention_layernorm.weight, None, 0)
    m.norm.weight = place(m.norm.weight, None, 0)
    return model
