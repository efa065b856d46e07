"""The linear-attention / latent-attention expert family (the Kimi-Linear
layout, here Kimi-Linear-48B-A3B-Instruct): pre-norm RMSNorm; layers
whose mixer is a gated delta rule with a per-channel gate (KDA: three
short causal convolutions, L2-normalised q and k, a matrix state a head)
or NoPE latent attention (one query projection, no rotation), by the
configuration's two 1-indexed lists; a leading dense SwiGLU layer, then
sigmoid-routed experts (top k by score plus a correction bias, weights
without it, normalised and scaled) beside one shared expert, of which
this chip holds a SHARE (``num_experts`` of the router's ``num_experts x
ep_size`` outputs, from expert ``ep_rank x num_experts``); untied head.
Everything the harness knows of it is here, behind the interface that
``harness/family.py`` lists.

Leaves (matrices [in, out]): ``embed`` [V, H], ``head`` [H, V], ``norm``
[H]; a layer has ``ln1 ln2`` and then, KDA, ``q k v`` [H, hd], ``q_conv
k_conv v_conv`` [hd, 4], ``f_a`` [H, d] ``f_b`` [d, hd] (the gate's
pair), ``b`` [H, h], ``A_log`` [h], ``dt_bias`` [hd], ``g_a g_b`` (the
output gate's pair), ``o_norm`` [d], ``o`` [hd, H]; latent, ``q kv_a
kv_a_norm kv_b o``; and, dense, ``gate up down``, or, expert, ``router``
[H, E] ``router_bias`` [E] ``shared_gate shared_up shared_down
experts_gate experts_up experts_down`` (experts stacked [held, in, out]).

The reference is ISSUE 37's equations in straightforward ``jax.numpy``,
float32, matmuls at ``highest``: a token-by-token ``lax.scan`` for the
state, dense causal attention a block of queries at a time (nothing
absorbed), the held experts one at a time over every token, no cache, no
batching; nothing of the program is imported. What the experts that other
chips hold would add is left out, here as in the program.
``quant="int8"`` is the control: every linear layer through
``harness/reference.py:mm`` in int8 (the convolutions, the recurrence,
the softmax and the router stay float32). Departures from the published
code: none known; what the published config does not say is listed under
``assumed`` in the configuration."""

import functools
import json
import math
import os

import jax
import jax.numpy as jnp
import numpy as np

from harness import weights
from harness.costs import causal_pairs
from harness.reference import HIGHEST, head_logits, mm, rmsnorm
from harness.selfcheck import near

HERE = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))

# what the published config.json does not say (the configuration lists
# them under "assumed")
A_MIN, A_MAX = 1.0, 16.0
DT_MIN, DT_MAX = 1e-3, 1e-1
L2_EPS = 1e-6


# ---------------------------------------------------------------------------
# the program's side
# ---------------------------------------------------------------------------
def build_model(cfg, dtype):
    """The program's own constructor."""
    import paddle_tpu as paddle
    from paddle_tpu.models import KimiLinearConfig, KimiLinearForCausalLM
    only = {"hidden_act": "silu", "moe_router_activation_func": "sigmoid",
            "num_expert_group": 1, "topk_group": 1, "moe_layer_freq": 1,
            "rope_scaling": None, "num_nextn_predict_layers": 0,
            "mla_use_nope": True, "q_lora_rank": None,
            "tie_word_embeddings": False}
    for k, v in only.items():
        if cfg.get(k, v) != v:
            raise ValueError(f"family kda_mla_moe runs {k}={v!r} only, the "
                             f"configuration gives {cfg[k]!r}")
    d, lin = dims(cfg), cfg["linear_attn_config"]
    paddle.set_default_dtype(dtype)
    try:
        model = KimiLinearForCausalLM(KimiLinearConfig(
            vocab_size=cfg["vocab_size"], hidden_size=d["h"],
            intermediate_size=d["i"], moe_intermediate_size=d["f"],
            num_hidden_layers=cfg["num_hidden_layers"],
            num_attention_heads=d["heads"], q_lora_rank=None,
            kv_lora_rank=d["kvr"], qk_nope_head_dim=d["nope"],
            qk_rope_head_dim=d["rope"], v_head_dim=d["v"],
            mla_use_nope=True, kda_layers=tuple(lin["kda_layers"]),
            full_attn_layers=tuple(lin["full_attn_layers"]),
            kda_num_heads=d["kh"], kda_head_dim=d["kd"],
            short_conv_kernel_size=d["cw"], n_routed_experts=d["e"],
            n_shared_experts=d["ns"], num_experts_per_tok=d["k"],
            first_k_dense_replace=cfg["first_k_dense_replace"],
            routed_scaling_factor=float(cfg["routed_scaling_factor"]),
            norm_topk_prob=bool(cfg["moe_renormalize"]),
            experts_held=d["held"], first_expert=d["first"],
            rms_norm_eps=float(cfg["rms_norm_eps"]),
            rope_theta=float(cfg["rope_theta"])))
    finally:
        paddle.set_default_dtype("float32")
    return model


def is_kda(cfg, index):
    return index + 1 in cfg["linear_attn_config"]["kda_layers"]


def is_dense(cfg, index):
    return index < cfg["first_k_dense_replace"]


def leaves(model, cfg):
    """``{benchmark leaf name: the program's parameter}``."""
    out = {"embed": model.model.embed_tokens.weight,
           "head": model.lm_head.weight, "norm": model.model.norm.weight}
    for i, layer in enumerate(model.model.layers):
        a, m = layer.mixer, layer.mlp
        own = {"ln1": layer.input_layernorm.weight,
               "ln2": layer.post_attention_layernorm.weight}
        if is_kda(cfg, i):
            own.update(q=a.q_proj.weight, k=a.k_proj.weight,
                       v=a.v_proj.weight, q_conv=a.q_conv, k_conv=a.k_conv,
                       v_conv=a.v_conv, f_a=a.f_a.weight, f_b=a.f_b.weight,
                       b=a.b_proj.weight, A_log=a.A_log, dt_bias=a.dt_bias,
                       g_a=a.g_a.weight, g_b=a.g_b.weight, o_norm=a.o_norm,
                       o=a.o_proj.weight)
        else:
            own.update(q=a.q_proj.weight, kv_a=a.kv_a.weight,
                       kv_a_norm=a.kv_a_norm.weight, kv_b=a.kv_b.weight,
                       o=a.o.weight)
        if is_dense(cfg, i):
            own.update(gate=m.gate_proj.weight, up=m.up_proj.weight,
                       down=m.down_proj.weight)
        else:
            own.update(router=m.router, router_bias=m.router_bias,
                       shared_gate=m.shared.gate_proj.weight,
                       shared_up=m.shared.up_proj.weight,
                       shared_down=m.shared.down_proj.weight,
                       experts_gate=m.experts_gate,
                       experts_up=m.experts_up,
                       experts_down=m.experts_down)
        out.update({f"layers.{i}.{k}": p for k, p in own.items()})
    return out


def engine(model, mix):
    """The serving engine as the mix sizes it, its two step shapes (the
    chunk budget and the decode batch) warm."""
    from paddle_tpu.inference.serving import LlamaServingEngine
    e = LlamaServingEngine(model, **mix["engine"])
    e.prewarm(mixed=[e.chunk_budget, e.max_batch])
    return e


def release(engine):
    """Free the states and the latent pages: the reference runs beside
    the weights alone."""
    engine.k_pools = engine.v_pools = None


# ---------------------------------------------------------------------------
# seeded weights
# ---------------------------------------------------------------------------
def layer_count(cfg):
    return cfg["num_hidden_layers"]


def dims(cfg):
    lin = cfg["linear_attn_config"]
    held, ep = cfg["num_experts"], cfg.get("ep_size", 1)
    return {"h": cfg["hidden_size"], "heads": cfg["num_attention_heads"],
            "kvr": cfg["kv_lora_rank"], "nope": cfg["qk_nope_head_dim"],
            "rope": cfg["qk_rope_head_dim"], "v": cfg["v_head_dim"],
            "i": cfg["intermediate_size"],
            "f": cfg["moe_intermediate_size"],
            # the router's outputs, the experts held here and the first
            "e": held * ep, "held": held,
            "first": cfg.get("ep_rank", 0) * held,
            "k": cfg["num_experts_per_token"],
            "ns": cfg["num_shared_experts"],
            "kh": lin["num_heads"], "kd": lin["head_dim"],
            "cw": lin["short_conv_kernel_size"]}


def layer_shapes(cfg, index):
    """``(normal leaves std 0.02, leaves that are 1, leaves with a
    formula)`` of layer ``index``: name -> shape."""
    d = dims(cfg)
    h, heads = d["h"], d["heads"]
    ones, formula = {"ln1": (h,), "ln2": (h,)}, {}
    if is_kda(cfg, index):
        hd = d["kh"] * d["kd"]
        normal = {"q": (h, hd), "k": (h, hd), "v": (h, hd), "o": (hd, h),
                  "q_conv": (hd, d["cw"]), "k_conv": (hd, d["cw"]),
                  "v_conv": (hd, d["cw"]), "f_a": (h, d["kd"]),
                  "f_b": (d["kd"], hd), "g_a": (h, d["kd"]),
                  "g_b": (d["kd"], hd), "b": (h, d["kh"])}
        ones["o_norm"] = (d["kd"],)
        formula = {"A_log": (d["kh"],), "dt_bias": (hd,)}
    else:
        normal = {"q": (h, heads * (d["nope"] + d["rope"])),
                  "kv_a": (h, d["kvr"] + d["rope"]),
                  "kv_b": (d["kvr"], heads * (d["nope"] + d["v"])),
                  "o": (heads * d["v"], h)}
        ones["kv_a_norm"] = (d["kvr"],)
    if is_dense(cfg, index):
        normal.update(gate=(h, d["i"]), up=(h, d["i"]), down=(d["i"], h))
    else:
        fs = d["f"] * d["ns"]
        normal.update(router=(h, d["e"]), router_bias=(d["e"],),
                      shared_gate=(h, fs), shared_up=(h, fs),
                      shared_down=(fs, h),
                      experts_gate=(d["held"], h, d["f"]),
                      experts_up=(d["held"], h, d["f"]),
                      experts_down=(d["held"], d["f"], h))
    return normal, ones, formula


def _frozen(shapes):
    return tuple(sorted(shapes.items()))


@functools.partial(jax.jit, static_argnames=("normal", "ones", "formula",
                                             "dtype"))
def _layer(key, normal, ones, formula, dtype):
    out = weights.normal_leaves(key, normal, dtype)
    for name, shape in ones:
        out[name] = jnp.ones(shape, dtype)
    for i, (name, shape) in enumerate(formula):
        u = jax.random.uniform(jax.random.fold_in(key, 2000 + i), shape,
                               jnp.float32)
        if name == "A_log":     # A uniform in [1, 16], a head
            out[name] = jnp.log(A_MIN + u * (A_MAX - A_MIN)).astype(dtype)
        else:                   # softplus(dt_bias) log-uniform in range
            dt = jnp.exp(u * (math.log(DT_MAX) - math.log(DT_MIN))
                         + math.log(DT_MIN))
            out[name] = (dt + jnp.log(-jnp.expm1(-dt))).astype(dtype)
    return out


@functools.partial(jax.jit, static_argnames=("vocab", "hidden", "dtype"))
def _ends(key, vocab, hidden, dtype):
    k1, k2 = jax.random.split(key)
    return {"embed": weights.normal(k1, (vocab, hidden), dtype),
            "head": weights.normal(k2, (hidden, vocab), dtype),
            "norm": jnp.ones((hidden,), dtype)}


def layer(cfg, seed, index, dtype):
    return _layer(jax.random.fold_in(weights.key_of(seed), 1 + index),
                  *(_frozen(s) for s in layer_shapes(cfg, index)),
                  jnp.dtype(dtype))


def ends(cfg, seed, dtype):
    return _ends(jax.random.fold_in(weights.key_of(seed), 0),
                 cfg["vocab_size"], cfg["hidden_size"], jnp.dtype(dtype))


# ---------------------------------------------------------------------------
# operations and bytes
# ---------------------------------------------------------------------------
def _size(shape):
    return int(np.prod(shape))


def layer_params(cfg, index):
    return sum(_size(s) for group in layer_shapes(cfg, index)
               for s in group.values())


def kinds(cfg):
    """``(KDA layers, latent layers, expert layers)``."""
    n = cfg["num_hidden_layers"]
    kda = sum(is_kda(cfg, i) for i in range(n))
    return kda, n - kda, sum(not is_dense(cfg, i) for i in range(n))


def expert_params(cfg):
    """One layer's routed experts that are held here."""
    d = dims(cfg)
    return d["held"] * 3 * d["h"] * d["f"]


def total_params(cfg):
    return sum(layer_params(cfg, i) for i in range(cfg["num_hidden_layers"])) \
        + 2 * cfg["vocab_size"] * cfg["hidden_size"] + cfg["hidden_size"]


def streamed_params(cfg):
    """Parameters every executed step reads whatever it carries: all but
    the embedding table (a gather), the routed experts (counted by the
    experts a step touches) and the final norm."""
    return total_params(cfg) - cfg["vocab_size"] * cfg["hidden_size"] \
        - kinds(cfg)[2] * expert_params(cfg) - cfg["hidden_size"]


def experts_touched(cfg, tokens):
    """Expected distinct HELD experts a layer reads for ``tokens`` tokens
    in a step under even routing over all the router's outputs."""
    d = dims(cfg)
    return d["held"] * (1.0 - (1.0 - d["k"] / d["e"]) ** tokens)


def token_flops(cfg):
    """Matmul FLOPs a token outside the latent attention kernel, the KDA
    recurrence and the head: the KDA layers' projections; the latent
    layers' by the ABSORBED form; the FFNs, the routed experts at the
    expected share of a token's ``k`` that is held here."""
    d = dims(cfg)
    h, heads = d["h"], d["heads"]
    hd = d["kh"] * d["kd"]
    kda = 4 * h * hd + 2 * (h * d["kd"] + d["kd"] * hd) + h * d["kh"]
    mla = h * heads * (d["nope"] + d["rope"]) + h * (d["kvr"] + d["rope"]) \
        + heads * d["nope"] * d["kvr"] + heads * d["kvr"] * d["v"] \
        + heads * d["v"] * h
    dense = 3 * h * d["i"]
    moe = h * d["e"] + (d["k"] * d["held"] / d["e"] + d["ns"]) \
        * 3 * h * d["f"]
    n_kda, n_mla, n_moe = kinds(cfg)
    return 2 * (n_kda * kda + n_mla * mla
                + (cfg["num_hidden_layers"] - n_moe) * dense + n_moe * moe)


def latent_bytes_per_token(cfg, itemsize=2):
    """The cached row of one token over the latent layers: the latent and
    the shared key lanes, nothing per head (pad lanes are not work)."""
    d = dims(cfg)
    return (d["kvr"] + d["rope"]) * itemsize * kinds(cfg)[1]


def attn_flops(cfg, pairs):
    """The absorbed attention of the latent layers for a sum of (query
    token x rows it attends to): scores over ``kv_rank + rope`` lanes and
    values over ``kv_rank``, 2 FLOPs a multiply-add, every head."""
    d = dims(cfg)
    return 2 * (2 * d["kvr"] + d["rope"]) * d["heads"] * kinds(cfg)[1] \
        * pairs


def moe_work(cfg, steps, tokens, itemsize=2):
    """``(flops, weight bytes, row bytes)`` of the held routed experts'
    three GEMMs: the expected rows (a token's ``k`` times the share held)
    through gate, up and down; the weights of the held experts a step
    touches once a step; each packed row read and written once a GEMM."""
    d = dims(cfg)
    m = kinds(cfg)[2]
    rows = tokens * d["k"] * d["held"] / d["e"]
    per_step = experts_touched(cfg, tokens / max(steps, 1)) \
        * 3 * d["h"] * d["f"]
    return (2 * rows * 3 * d["h"] * d["f"] * m,
            steps * per_step * itemsize * m,
            rows * 3 * (d["h"] + d["f"]) * itemsize * m)


def state_bytes(cfg, itemsize=2):
    """What ONE KDA layer keeps a sequence: the float32 matrix state a
    head and the three convolutions' last inputs."""
    d = dims(cfg)
    return d["kh"] * d["kd"] * d["kd"] * 4 \
        + (d["cw"] - 1) * 3 * d["kh"] * d["kd"] * itemsize


def kda_work(cfg, prefill, decode, itemsize=2, chunk=64):
    """``(flops, bytes)`` of the convolutions and the gated delta rule of
    all KDA layers (what runs under ``paddle_tpu.kda_scan``). A decoded
    token is the recurrence: decay, ``S'^T k``, the rank-one update,
    ``S^T q``: 7 K V a head. A prompt's token is the chunkwise form at
    chunks of ``chunk``: the two ``[C, C]`` products (4 C K), the
    triangular solve and ``Q U`` (3 C V), the three products with the
    state (6 K V). The states go once in and once out a row (a prompt, a
    decoded token), float32; the convolutions' inputs (the model's
    dtype), q, k, v, g, o (float32) and beta once a token."""
    d = dims(cfg)
    layers = kinds(cfg)[0]
    h, kd = d["kh"], d["kd"]
    hd = h * kd
    p_tok, d_tok = sum(prefill), len(decode)
    conv = 2 * d["cw"] * 3 * hd
    flops = layers * (d_tok * (7 * h * kd * kd + conv)
                      + p_tok * (h * (6 * kd * kd + 7 * chunk * kd) + conv))
    rows = len(prefill) + len(decode)
    nbytes = layers * (2 * rows * state_bytes(cfg, itemsize)
                       + (p_tok + d_tok) * (3 * hd * itemsize + 5 * hd * 4
                                            + h * 4))
    return flops, nbytes


def serve_work(cfg, steps, prefill, decode, itemsize=2):
    """FLOPs and bytes of a serving window (see ``harness/family.py``).
    Logits are needed at the last position of a prompt and at every
    decoded token; the latent rows of each context are read once a latent
    layer; each KDA layer's state goes once in and once out a row; the
    held experts are priced at the expected distinct ones a step of the
    window's mean size touches."""
    tokens = sum(prefill) + len(decode)
    pairs = sum(causal_pairs(p) for p in prefill) + sum(decode)
    heads_flops = 2 * cfg["hidden_size"] * cfg["vocab_size"] \
        * (len(prefill) + len(decode))
    a_flops = attn_flops(cfg, pairs)
    lat = latent_bytes_per_token(cfg, itemsize)
    a_bytes = lat * (sum(prefill) + sum(decode)) + lat * tokens
    m_flops, m_weights, m_rows = moe_work(cfg, steps, tokens, itemsize)
    k_flops, k_bytes = kda_work(cfg, prefill, decode, itemsize)
    return {"flops": token_flops(cfg) * tokens + heads_flops + a_flops
            + k_flops,
            "tokens": tokens,
            "bytes": steps * streamed_params(cfg) * itemsize + m_weights
            + a_bytes + k_bytes,
            "attn_flops": a_flops, "attn_bytes": a_bytes,
            "moe_flops": m_flops, "moe_bytes": m_weights + m_rows,
            "kda_flops": k_flops, "kda_bytes": k_bytes}


def _no_training(*_a, **_k):
    raise NotImplementedError("family kda_mla_moe has no training cell: "
                              "its costs and reference cover serving")


train_flops_per_token = train_attn_flops = train_attn_bytes = \
    train_readings = _no_training


def _config(name):
    with open(os.path.join(HERE, "configs", name + ".json")) as f:
        return json.load(f)


def selfcheck():
    """The cost functions against ISSUE 37's hand figures."""
    c = _config("kimi-linear-48b-a3b-ep16")
    d = dims(c)
    assert (d["e"], d["held"], d["first"]) == (256, 16, 0), d
    assert kinds(c) == (20, 7, 26), kinds(c)
    # KDA mixer 39,514,272 + two norms 4,608 + the dense FFN 63,700,992
    near(layer_params(c, 0), 39514272 + 4608 + 63700992, 0, "layer 0")
    # 16 held experts + the shared one of 7,077,888 each, router 589,824,
    # its bias 256
    ffn = 17 * 7077888 + 589824 + 256
    near(layer_params(c, 1), 39514272 + 4608 + ffn, 0, "KDA expert layer")
    near(layer_params(c, 3), 29114880 + 4608 + ffn, 0,
         "latent expert layer")
    near(total_params(c), 4956660608, 0, "Kimi-Linear ep16 params")
    near(expert_params(c), 16 * 7077888, 0, "held experts of a layer")
    near(latent_bytes_per_token(c), 7 * 1152, 0, "latent bytes a token")
    # 32 x 128 x 128 float32 + 3 x 12288 bf16
    near(state_bytes(c), 2097152 + 73728, 0, "state bytes a layer")
    near(experts_touched(c, 64), 16 * (1 - 0.96875 ** 64), 1e-12,
         "held experts 64 tokens touch")
    near(experts_touched(c, 4096), 16.0, 1e-9, "held experts a big step")
    # one decode token at context 1000 in a step of its own
    w = serve_work(c, 1, [], [1000])
    near(w["attn_bytes"], 1001 * 7 * 1152, 0, "decode token latent bytes")
    near(w["attn_flops"], 2 * 1088 * 32 * 7 * 1000, 0,
         "decode token attention FLOPs")
    near(w["moe_flops"], 2 * (8 * 16 / 256) * 3 * 2304 * 1024 * 26, 0,
         "decode token expert FLOPs")
    near(w["kda_bytes"],
         20 * (2 * 2170880 + 3 * 4096 * 2 + 5 * 4096 * 4 + 128), 0,
         "decode token KDA bytes")
    near(w["kda_flops"], 20 * (7 * 32 * 16384 + 2 * 4 * 12288), 0,
         "decode token KDA FLOPs")
    # all but the embedding, the held experts and the final norm, once
    near(streamed_params(c),
         4956660608 - 163840 * 2304 - 26 * 16 * 7077888 - 2304, 0,
         "streamed parameters")
    near(w["bytes"], 2 * (streamed_params(c) + 26 * 0.5 * 7077888)
         + w["attn_bytes"] + w["kda_bytes"], 1e-9, "decode step bytes")


# ---------------------------------------------------------------------------
# the plain reference
# ---------------------------------------------------------------------------
Q_BLOCK = 256           # queries a block of the attention


@functools.partial(jax.jit, static_argnames=("d", "eps", "quant"))
def kda_mixer(x, w, d, eps, quant):
    """The KDA mixer of one sequence ``x [T, H]`` from a zero state, the
    state advanced a token at a time."""
    d = dict(d)
    h, kd, cw = d["kh"], d["kd"], d["cw"]
    f32 = jnp.float32
    t = x.shape[0]
    u = rmsnorm(x, w["ln1"], eps)

    def stream(name):
        s = jnp.concatenate([jnp.zeros((cw - 1, h * kd), f32),
                             mm(u, w[name], quant)])
        cv = w[name + "_conv"].astype(f32)
        return jax.nn.silu(sum(s[j:j + t] * cv[None, :, j]
                               for j in range(cw))).reshape(t, h, kd)

    def unit(a):
        return a / jnp.maximum(
            jnp.sqrt(jnp.sum(a * a, -1, keepdims=True)), L2_EPS)

    q, k, v = unit(stream("q")) * kd ** -0.5, unit(stream("k")), stream("v")
    a = mm(mm(u, w["f_a"], quant), w["f_b"], quant) \
        + w["dt_bias"].astype(f32)
    g = -jnp.exp(w["A_log"].astype(f32))[None, :, None] \
        * jax.nn.softplus(a).reshape(t, h, kd)
    beta = jax.nn.sigmoid(mm(u, w["b"], quant))                # [T, h]

    def tick(s, tok):
        q_t, k_t, v_t, g_t, b_t = tok
        s = s * jnp.exp(g_t)[:, :, None]
        new = b_t[:, None] * (v_t - jnp.einsum("hk,hkv->hv", k_t, s,
                                               precision=HIGHEST))
        s = s + k_t[:, :, None] * new[:, None, :]
        return s, jnp.einsum("hkv,hk->hv", s, q_t, precision=HIGHEST)

    _, o = jax.lax.scan(tick, jnp.zeros((h, kd, kd), f32),
                        (q, k, v, g, beta))
    z = mm(mm(u, w["g_a"], quant), w["g_b"], quant).reshape(t, h, kd)
    y = o * jax.lax.rsqrt(jnp.mean(o * o, -1, keepdims=True) + eps) \
        * w["o_norm"].astype(f32) * jax.nn.sigmoid(z)
    return mm(y.reshape(t, h * kd), w["o"], quant)


@functools.partial(jax.jit, static_argnames=("d", "eps", "quant"))
def attn_operands(x, w, d, eps, quant):
    """Per head q, k ``[T, heads, nope + rope]`` and v ``[T, heads, v]``
    of one sequence ``x [T, H]``: nothing absorbed, nothing rotated."""
    d = dict(d)
    t, heads = x.shape[0], d["heads"]
    u = rmsnorm(x, w["ln1"], eps)
    q = mm(u, w["q"], quant).reshape(t, heads, d["nope"] + d["rope"])
    lat = mm(u, w["kv_a"], quant)
    c = rmsnorm(lat[:, :d["kvr"]], w["kv_a_norm"], eps)
    kv = mm(c, w["kv_b"], quant).reshape(t, heads, d["nope"] + d["v"])
    k = jnp.concatenate(
        [kv[..., :d["nope"]],
         jnp.broadcast_to(lat[:, None, d["kvr"]:], (t, heads, d["rope"]))],
        -1)
    return q, k, kv[..., d["nope"]:]


@jax.jit
def attn_block(q, k, v, start):
    """Causal attention of the queries ``q [B, heads, D]`` at positions
    ``start..`` over all of ``k, v [T, heads, .]``."""
    s = jnp.einsum("qhd,khd->hqk", q, k, precision=HIGHEST) \
        / np.sqrt(q.shape[-1])
    qpos = start + jnp.arange(q.shape[0])
    mask = jnp.arange(k.shape[0])[None, :] <= qpos[:, None]
    p = jax.nn.softmax(jnp.where(mask[None], s, -jnp.inf), axis=-1)
    return jnp.einsum("hqk,khd->qhd", p, v, precision=HIGHEST)


def latent_mixer(x, w, d, eps, quant):
    q, k, v = attn_operands(x, w, _frozen(d), eps, quant)
    a = jnp.concatenate([attn_block(q[s:s + Q_BLOCK], k, v, s)
                         for s in range(0, x.shape[0], Q_BLOCK)])
    return mm(a.reshape(a.shape[0], -1), w["o"], quant)


@functools.partial(jax.jit, static_argnames=("quant",))
def swiglu(y, gate, up, down, quant):
    return mm(jax.nn.silu(mm(y, gate, quant)) * mm(y, up, quant), down,
              quant)


@functools.partial(jax.jit, static_argnames=("k", "scale", "norm"))
def route(y, router, bias, k, scale, norm):
    """Chosen experts ``[T, k]`` (of ALL the router's outputs) and their
    weights: sigmoid scores, the top k by score + bias, weights WITHOUT
    the bias, normalised, scaled."""
    s = jax.nn.sigmoid(jnp.matmul(y, router.astype(jnp.float32),
                                  precision=HIGHEST))
    _, idx = jax.lax.top_k(s + bias.astype(jnp.float32), k)
    wt = jnp.take_along_axis(s, idx, axis=-1)
    if norm:
        wt = wt / (jnp.sum(wt, axis=-1, keepdims=True) + 1e-20)
    return idx, wt * scale


@functools.partial(jax.jit, static_argnames=("first", "quant"))
def held_experts(y, idx, wt, gate, up, down, first, quant):
    """The held experts' part of the routed sum, one expert at a time
    over every token: expert ``first + j`` weighs a token by the weight
    the router gave it there, 0 where it was not chosen."""
    def one(acc, e):
        j, g, u, dn = e
        mine = jnp.sum(jnp.where(idx == first + j, wt, 0.0), axis=-1)
        return acc + mine[:, None] * swiglu(y, g, u, dn, quant), None

    acc, _ = jax.lax.scan(one, jnp.zeros(y.shape, jnp.float32),
                          (jnp.arange(gate.shape[0]), gate, up, down))
    return acc


def moe_ffn(y, w, cfg, quant):
    d = dims(cfg)
    idx, wt = route(y, w["router"], w["router_bias"], d["k"],
                    float(cfg["routed_scaling_factor"]),
                    bool(cfg["moe_renormalize"]))
    return swiglu(y, w["shared_gate"], w["shared_up"], w["shared_down"],
                  quant) \
        + held_experts(y, idx, wt, w["experts_gate"], w["experts_up"],
                       w["experts_down"], d["first"], quant)


@functools.partial(jax.jit, static_argnames=("eps",))
def _normed(x, w, eps):
    return rmsnorm(x, w, eps)


def after_mixer(x, w, cfg, index, quant):
    """``x + mix_l(RMSNorm(x))`` of one sequence ``x [T, H]``."""
    d, eps = dims(cfg), float(cfg["rms_norm_eps"])
    if is_kda(cfg, index):
        return x + kda_mixer(x, w, _frozen(d), eps, quant)
    return x + latent_mixer(x, w, d, eps, quant)


def layer_forward(x, w, cfg, index, quant):
    """One layer over one sequence ``x [T, H]`` (positions 0..T-1)."""
    x = after_mixer(x, w, cfg, index, quant)
    y = _normed(x, w["ln2"], float(cfg["rms_norm_eps"]))
    if is_dense(cfg, index):
        return x + swiglu(y, w["gate"], w["up"], w["down"], quant)
    return x + moe_ffn(y, w, cfg, quant)


def served_logits(cfg, ids, rows, layer_weights, end_weights, quant=None,
                  block=1):
    """Teacher-forced logits (see ``harness/family.py``): ``ids`` [N, T]
    (prompt, served tokens, padding), ``rows`` [N, K] the positions whose
    next-token logits are wanted -> numpy [N, K, V] float32. A sequence
    at a time at the padded length (one set of shapes a run; a causal
    model's earlier positions never see the padding; ``block`` is not
    used)."""
    ids, rows = np.asarray(ids), np.asarray(rows)
    eps = float(cfg["rms_norm_eps"])
    out = []
    for n in range(len(ids)):
        x = jnp.take(end_weights["embed"], jnp.asarray(ids[n]),
                     axis=0).astype(jnp.float32)
        for i in range(cfg["num_hidden_layers"]):
            x = layer_forward(x, layer_weights(i), cfg, i, quant)
        out.append(np.asarray(head_logits(
            x[None], jnp.asarray(rows[n:n + 1]), end_weights["norm"],
            end_weights["head"], eps=eps, quant=quant)))
    return np.concatenate(out)
