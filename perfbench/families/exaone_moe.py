"""The post-norm window / NoPE expert family with a multi-token prediction
module (the K-EXAONE layout, here K-EXAONE-236B-A23B): every layer GQA
attention with a per-head RMSNorm on q and on k, over a window of
``sliding_windows[l]`` keys with the default rope, or over the whole
context with no rope (NoPE), by the configuration's ``layer_types``;
post-norm blocks (``x + RMSNorm(attn(x))``, then ``x + RMSNorm(ffn(x))``);
the leading dense SwiGLU layer, then sigmoid-routed experts (the top k by
score plus a correction bias, weights the scores normalised and scaled)
with one shared expert; untied head; and one MTP module (DeepSeek-V3's),
which the cell's engine runs in every step to draft. Everything the
harness knows of it is here, behind the interface that
``harness/family.py`` lists.

Leaves (matrices [in, out]): ``embed`` [V, H], ``head`` [H, V], ``norm``
[H]; a layer has ``q`` [H, Hq d], ``k v`` [H, Hk d], ``o`` [Hq d, H],
``q_norm k_norm`` [d], ``ln_attn ln_ffn`` [H] (the norms after attention
and after the FFN), and either ``gate up`` [H, I], ``down`` [I, H] (the
dense layer) or ``router`` [H, E], ``router_bias`` [E], ``shared_gate
shared_up`` [H, F], ``shared_down`` [F, H] and the held experts
``experts_gate experts_up`` [held, H, F], ``experts_down`` [held, F, H].
The MTP module is layer ``num_hidden_layers``: an expert layer of the full
kind plus ``eh_proj`` [2H, H] and ``enorm hnorm mtp_norm`` [H].

One chip's share of an expert-parallel deployment: the configuration's
``num_experts`` is the experts held here of each layer, the router has
``num_experts x ep_size`` outputs and this chip holds experts ``ep_rank x
num_experts ..``; what the others would add is left out, in the program
and the reference alike.

The reference is the model's equations in straightforward ``jax.numpy``,
float32, matmuls at ``highest``: dense masked attention a block of queries
at a time (a window layer against the keys its block can see), the held
experts a group at a time (each token gathered to the experts that chose
it), no cache, no batching; nothing of the program is imported.
``quant="int8"`` is the control: every linear layer through
``harness/reference.py:mm`` in int8 (the router, the norms, the rope and
the softmaxes stay float32). ``mtp_logits`` is the MTP module's, over a
teacher-forced sequence. Departures from the published model, and what
its config does not say, are listed under ``assumed`` in the
configuration: EXAONE 4.0's attention and block (no ``exaone_moe`` code
was at hand), DeepSeek-V3's ``noaux_tc`` routing and MTP module, seeded
weights."""

import functools
import json
import os

import jax
import jax.numpy as jnp
import numpy as np

from harness import weights
from harness.costs import causal_pairs
from harness.reference import HIGHEST, head_logits, mm, rmsnorm, rope
from harness.selfcheck import near

HERE = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
WINDOW, FULL = "sliding_attention", "full_attention"


# ---------------------------------------------------------------------------
# the program's side
# ---------------------------------------------------------------------------
def build_model(cfg, dtype):
    """The program's own constructor."""
    import paddle_tpu as paddle
    from paddle_tpu.models import ExaoneMoeConfig, ExaoneMoeForCausalLM
    only = {"hidden_act": "silu", "scoring_func": "sigmoid", "n_group": 1,
            "topk_group": 1, "tie_word_embeddings": False,
            "num_nextn_predict_layers": 1,
            "mtp_layer_types": [FULL], "mtp_sliding_windows": [0]}
    for k, v in only.items():
        if cfg.get(k, v) != v:
            raise ValueError(f"family exaone_moe runs {k}={v!r} only, the "
                             f"configuration gives {cfg[k]!r}")
    if cfg["rope_parameters"]["rope_type"] != "default":
        raise ValueError("family exaone_moe runs the default rope on the "
                         "window layers")
    types = cfg["mlp_layer_types"]
    first = cfg["first_k_dense_replace"]
    if types != ["dense"] * first + ["sparse"] * (len(types) - first):
        raise ValueError("family exaone_moe runs first_k_dense_replace "
                         "dense layers, then expert layers")
    d = dims(cfg)
    paddle.set_default_dtype(dtype)
    try:
        model = ExaoneMoeForCausalLM(ExaoneMoeConfig(
            vocab_size=cfg["vocab_size"], hidden_size=d["h"],
            intermediate_size=d["i"], moe_intermediate_size=d["f"],
            num_hidden_layers=cfg["num_hidden_layers"],
            num_attention_heads=d["hq"], num_key_value_heads=d["hk"],
            head_dim=d["d"], layer_types=tuple(cfg["layer_types"]),
            mlp_layer_types=tuple(types),
            sliding_window=cfg["sliding_window"],
            sliding_windows=tuple(cfg["sliding_windows"]),
            n_routed_experts=d["e"], n_shared_experts=d["ns"],
            num_experts_per_tok=d["k"],
            routed_scaling_factor=float(cfg["routed_scaling_factor"]),
            norm_topk_prob=bool(cfg["norm_topk_prob"]),
            experts_held=d["held"], first_expert=d["first"],
            num_nextn_predict_layers=1,
            max_position_embeddings=cfg["max_position_embeddings"],
            rms_norm_eps=float(cfg["rms_norm_eps"]),
            rope_theta=float(cfg["rope_parameters"]["rope_theta"])))
    finally:
        paddle.set_default_dtype("float32")
    return model


def _layer_leaves(layer):
    a, m = layer.self_attn, layer.mlp
    own = {"q": a.q_proj.weight, "k": a.k_proj.weight, "v": a.v_proj.weight,
           "o": a.o_proj.weight, "q_norm": a.q_norm.weight,
           "k_norm": a.k_norm.weight,
           "ln_attn": layer.post_attention_layernorm.weight,
           "ln_ffn": layer.post_feedforward_layernorm.weight}
    if layer.is_moe:
        own.update(router=m.router, router_bias=m.router_bias,
                   shared_gate=m.shared.gate_proj.weight,
                   shared_up=m.shared.up_proj.weight,
                   shared_down=m.shared.down_proj.weight,
                   experts_gate=m.experts_gate, experts_up=m.experts_up,
                   experts_down=m.experts_down)
    else:
        own.update(gate=m.gate_proj.weight, up=m.up_proj.weight,
                   down=m.down_proj.weight)
    return own


def leaves(model, cfg):
    """``{benchmark leaf name: the program's parameter}``."""
    out = {"embed": model.model.embed_tokens.weight,
           "head": model.lm_head.weight, "norm": model.model.norm.weight}
    layers = list(model.model.layers)
    for i, layer in enumerate(layers):
        out.update({f"layers.{i}.{k}": p
                    for k, p in _layer_leaves(layer).items()})
    mtp, n = model.mtp, len(layers)
    own = dict(_layer_leaves(mtp.layer), eh_proj=mtp.eh_proj.weight,
               enorm=mtp.enorm.weight, hnorm=mtp.hnorm.weight,
               mtp_norm=mtp.norm.weight)
    out.update({f"layers.{n}.{k}": p for k, p in own.items()})
    return out


def engine(model, mix):
    """The serving engine as the mix sizes it (``spec_k`` 1 and no drafter
    of its own: the MTP module drafts in the step program), its two step
    shapes (the chunk budget and the decode batch) warm."""
    from paddle_tpu.inference.serving import LlamaServingEngine
    e = LlamaServingEngine(model, **mix["engine"])
    e.prewarm(mixed=[e.chunk_budget, e.max_batch])
    return e


def release(engine):
    """Free the rings and the whole-context pools (the MTP module's
    among them): the reference runs beside the weights alone."""
    engine.k_pools = engine.v_pools = None


# ---------------------------------------------------------------------------
# seeded weights
# ---------------------------------------------------------------------------
def layer_count(cfg):
    """The layers, then the MTP module."""
    return cfg["num_hidden_layers"] + cfg["num_nextn_predict_layers"]


def dims(cfg):
    held, ep = cfg["num_experts"], cfg.get("ep_size", 1)
    return {"h": cfg["hidden_size"], "hq": cfg["num_attention_heads"],
            "hk": cfg["num_key_value_heads"], "d": cfg["head_dim"],
            "i": cfg["intermediate_size"],
            "f": cfg["moe_intermediate_size"],
            # the router's outputs, the experts held here and the first
            "e": held * ep, "held": held,
            "first": cfg.get("ep_rank", 0) * held,
            "k": cfg["num_experts_per_tok"],
            "ns": cfg["num_shared_experts"], "w": cfg["sliding_window"],
            "v": cfg["vocab_size"]}


def is_mtp(cfg, index):
    return index >= cfg["num_hidden_layers"]


def window_of(cfg, index):
    """The keys a layer's queries see back (0: the whole context)."""
    if is_mtp(cfg, index):
        return cfg["mtp_sliding_windows"][index - cfg["num_hidden_layers"]]
    return cfg["sliding_windows"][index]


def is_dense(cfg, index):
    return not is_mtp(cfg, index) \
        and cfg["mlp_layer_types"][index] == "dense"


def layer_shapes(cfg, index):
    """``(normal leaves std 0.02, leaves that are 1)`` of layer ``index``
    (the MTP module at ``num_hidden_layers``): name -> shape."""
    d = dims(cfg)
    h, qd, kd = d["h"], d["hq"] * d["d"], d["hk"] * d["d"]
    normal = {"q": (h, qd), "k": (h, kd), "v": (h, kd), "o": (qd, h)}
    ones = {"q_norm": (d["d"],), "k_norm": (d["d"],), "ln_attn": (h,),
            "ln_ffn": (h,)}
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
    if is_mtp(cfg, index):
        normal["eh_proj"] = (2 * h, h)
        ones.update(enorm=(h,), hnorm=(h,), mtp_norm=(h,))
    return normal, ones


def _frozen(shapes):
    return tuple(sorted(shapes.items()))


@functools.partial(jax.jit, static_argnames=("normal", "ones", "dtype"))
def _layer(key, normal, ones, dtype):
    out = weights.normal_leaves(key, normal, dtype)
    for name, shape in ones:
        out[name] = jnp.ones(shape, dtype)
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
def layer_params(cfg, index):
    return sum(int(np.prod(s)) for group in layer_shapes(cfg, index)
               for s in group.values())


def total_params(cfg):
    return sum(layer_params(cfg, i) for i in range(layer_count(cfg))) \
        + 2 * cfg["vocab_size"] * cfg["hidden_size"] + cfg["hidden_size"]


def kinds(cfg):
    """How many layers of each attention kind run a step: ``{"window":
    .., "full": ..}``, the MTP module's layer among the full ones (the
    cell's engine runs it in every step)."""
    wins = [window_of(cfg, i) for i in range(layer_count(cfg))]
    return {"window": sum(1 for w in wins if w),
            "full": sum(1 for w in wins if not w)}


def expert_layers(cfg):
    return sum(not is_dense(cfg, i) for i in range(layer_count(cfg)))


def expert_params(cfg):
    """One layer's routed experts that are held here."""
    d = dims(cfg)
    return d["held"] * 3 * d["h"] * d["f"]


def streamed_params(cfg):
    """Parameters every executed step reads whatever it carries: all but
    the embedding table (a gather), the held routed experts (counted by
    the experts a step touches) and the final norm."""
    return total_params(cfg) - cfg["vocab_size"] * cfg["hidden_size"] \
        - expert_layers(cfg) * expert_params(cfg) - cfg["hidden_size"]


def experts_touched(cfg, tokens):
    """Expected distinct HELD experts a layer reads for ``tokens`` tokens
    in a step under even routing over all the router's outputs."""
    d = dims(cfg)
    return d["held"] * (1.0 - (1.0 - d["k"] / d["e"]) ** tokens)


def token_flops(cfg):
    """Matmul FLOPs a query token outside attention's scores and the
    heads: every layer's projections, the dense FFN, the router, the
    shared expert and the expected share of a token's ``k`` experts that
    is held here, and the MTP module's input projection; 2 a
    multiply-add."""
    d = dims(cfg)
    h = d["h"]
    attn = h * (2 * d["hq"] + 2 * d["hk"]) * d["d"]
    moe = h * d["e"] + (d["k"] * d["held"] / d["e"] + d["ns"]) \
        * 3 * h * d["f"]
    n = layer_count(cfg)
    dense = n - expert_layers(cfg)
    return 2 * (n * attn + dense * 3 * h * d["i"]
                + expert_layers(cfg) * moe
                + cfg["num_nextn_predict_layers"] * 2 * h * h)


def kv_bytes(cfg, itemsize=2):
    """K and V of one token in one layer."""
    d = dims(cfg)
    return 2 * d["hk"] * d["d"] * itemsize


def pair_flops(cfg):
    """A (query token, key) pair in one layer: every query head's score
    and value row, 2 FLOPs a multiply-add."""
    d = dims(cfg)
    return 4 * d["hq"] * d["d"]


def verify_contexts(decode):
    """The (query, context) pairs of the verify rows behind decoded
    tokens: a token served at context ``c`` was the first query of a row
    of two (the token before it and one draft, none accepted: what
    seeded weights give), which see ``c`` and ``c + 1`` keys."""
    return [c for c in decode] + [c + 1 for c in decode]


def window_pairs(cfg, prefill, contexts):
    """Sum over the query tokens of the keys each sees in a window
    layer: a prompt's position ``i`` sees ``min(i + 1, w)``, a query at
    context ``c`` ``min(c, w)``."""
    w = cfg["sliding_window"]

    def prompt(p):
        full = min(p, w)
        return full * (full + 1) // 2 + (p - full) * w

    return sum(prompt(p) for p in prefill) + sum(min(c, w) for c in contexts)


def attn_work(cfg, kind, keys, reads, tokens, itemsize=2):
    """``(flops, bytes)`` of the ragged attention of all layers of one
    ``kind`` (``window`` or ``full``, the MTP module's layer a full one)
    from what the program counted for ONE layer of it
    (``serving.dispatch``'s ``<kind>_keys``, ``<kind>_reads``,
    ``tokens``): every (query, key) pair of a row's queries against the
    keys it reads, those keys read once a row and the new tokens' K and V
    written once."""
    n = kinds(cfg)[kind]
    return n * pair_flops(cfg) * keys, \
        n * kv_bytes(cfg, itemsize) * (reads + tokens)


def moe_work(cfg, steps, tokens, itemsize=2):
    """``(flops, weight bytes, row bytes)`` of the held routed experts'
    three GEMMs over ``tokens`` query tokens: the expected rows (a
    token's ``k`` times the share held) through gate, up and down; the
    weights of the held experts a step touches once a step; each packed
    row read and written once a GEMM. Every expert layer, the MTP
    module's too."""
    d = dims(cfg)
    m = expert_layers(cfg)
    rows = tokens * d["k"] * d["held"] / d["e"]
    per_step = experts_touched(cfg, tokens / max(steps, 1)) \
        * 3 * d["h"] * d["f"]
    return (2 * rows * 3 * d["h"] * d["f"] * m,
            steps * per_step * itemsize * m,
            rows * 3 * (d["h"] + d["f"]) * itemsize * m)


def serve_work(cfg, steps, prefill, decode, itemsize=2):
    """FLOPs and bytes of a serving window (see ``harness/family.py``).
    Every decoded token is priced as the first query of a verify row of
    two (`verify_contexts`); every query token runs every layer, the MTP
    module and both heads (the speculative step takes logits and drafts
    at every packed position); a full layer reads each row's context
    once, a window layer ``min(context, window)`` of it, every layer
    writes each query token's K and V once; the held experts are priced
    at the expected distinct ones a step of the window's mean size
    touches. ``tokens`` is what was served."""
    ctx = verify_contexts(decode)
    queries = sum(prefill) + len(ctx)
    k = kinds(cfg)
    heads_flops = 2 * 2 * cfg["hidden_size"] * cfg["vocab_size"] * queries
    a_flops = pair_flops(cfg) * (
        k["window"] * window_pairs(cfg, prefill, ctx)
        + k["full"] * (sum(causal_pairs(p) for p in prefill) + sum(ctx)))
    w = cfg["sliding_window"]
    a_bytes = kv_bytes(cfg, itemsize) * (
        layer_count(cfg) * (sum(prefill) + queries)
        + k["full"] * sum(decode) + k["window"] * sum(min(c, w)
                                                      for c in decode))
    m_flops, m_weights, m_rows = moe_work(cfg, steps, queries, itemsize)
    return {"flops": token_flops(cfg) * queries + heads_flops + a_flops,
            "tokens": sum(prefill) + len(decode),
            "bytes": steps * streamed_params(cfg) * itemsize + m_weights
            + a_bytes,
            "attn_flops": a_flops, "attn_bytes": a_bytes,
            "moe_flops": m_flops, "moe_bytes": m_weights + m_rows}


def _no_training(*_a, **_k):
    raise NotImplementedError("family exaone_moe has no training cell: "
                              "its costs and reference cover serving")


train_flops_per_token = train_attn_flops = train_attn_bytes = \
    train_readings = _no_training


def _config(name):
    with open(os.path.join(HERE, "configs", name + ".json")) as f:
        return json.load(f)


def selfcheck():
    """The cost functions against figures worked by hand."""
    c = _config("k-exaone-236b-a23b-ep16-l8")
    d = dims(c)
    assert (d["e"], d["held"], d["first"]) == (128, 8, 0), d
    assert kinds(c) == {"window": 6, "full": 3}, kinds(c)
    assert expert_layers(c) == 8 and layer_count(c) == 9
    # attention 113,246,208; router 786,432 and its bias 128; the shared
    # expert 37,748,736; four norms 12,544; 8 experts of 37,748,736
    near(layer_params(c, 1), 151794048 + 8 * 37748736, 0, "an expert layer")
    near(layer_params(c, 1), 453783936, 0, "an expert layer, by hand")
    near(layer_params(c, 0), 113246208 + 3 * 6144 * 18432 + 12544, 0,
         "the dense layer")
    near(layer_params(c, 8), 12288 * 6144 + 3 * 6144 + 453783936, 0,
         "the MTP module")
    near(layer_params(c, 8), 529299840, 0, "the MTP module, by hand")
    near(2 * c["vocab_size"] * c["hidden_size"], 235929600, 0,
         "embedding and head")
    # 452,997,376 + 7 x 453,783,936 + 529,299,840 + 235,929,600, and the
    # final norm of 6,144
    near(total_params(c), 4394714368 + 6144, 0, "K-EXAONE 8-layer params")
    near(kv_bytes(c), 4096, 0, "K/V bytes a token a layer")
    near(window_pairs(c, [], [100, 5000]), 100 + 128, 0, "window keys")
    near(window_pairs(c, [300], []), 128 * 129 // 2 + 172 * 128, 0,
         "window keys of a prompt")
    near(experts_touched(c, 64), 8 * (1 - (120 / 128) ** 64), 1e-12,
         "held experts 64 tokens touch")
    # one decoded token at context 3,000 in a step of its own: a verify
    # row of two queries (contexts 3,000 and 3,001) through all layers
    w = serve_work(c, 1, [], [3000])
    near(w["attn_flops"], 4 * 64 * 128 * (6 * (128 + 128)
                                          + 3 * (3000 + 3001)), 0,
         "verify row attention FLOPs")
    near(w["attn_bytes"], 4096 * (9 * 2 + 3 * 3000 + 6 * 128), 0,
         "verify row K/V bytes")
    near(w["moe_flops"], 2 * 2 * 8 * 8 / 128 * 3 * 6144 * 2048 * 8, 1e-12,
         "verify row held expert FLOPs")
    near(streamed_params(c), total_params(c) - 19200 * 6144
         - 8 * 8 * 37748736 - 6144, 0, "streamed parameters")
    # what the program counts for one layer of each kind: a verify row
    # ending at context 3,001 and a 512-token chunk ending at 2,048
    f, b = attn_work(c, "window", 2 * 129 + 512 * 639, 129 + 639, 514)
    near(f, 6 * 4 * 64 * 128 * (2 * 129 + 512 * 639), 0, "window work")
    near(b, 6 * 4096 * (129 + 639 + 514), 0, "window bytes")
    f, b = attn_work(c, "full", 2 * 3001 + 512 * 2048, 3001 + 2048, 514)
    near(f, 3 * 32768 * (2 * 3001 + 512 * 2048), 0, "full work")
    near(b, 3 * 4096 * (3001 + 2048 + 514), 0, "full bytes")


# ---------------------------------------------------------------------------
# the plain reference
# ---------------------------------------------------------------------------
Q_BLOCK = 256           # queries a block of the attention
EXPERT_GROUP = 8        # held experts upcast at a time


@functools.partial(jax.jit, static_argnames=("d", "eps", "rotary", "theta",
                                             "quant"))
def attn_operands(x, w, d, eps, rotary, theta, quant):
    """``q [T, Hq, d]`` and ``k``, each normed a head (then rotated where
    the layer has a rope), and ``v [T, Hk, d]``."""
    d = dict(d)
    t = x.shape[0]
    q = rmsnorm(mm(x, w["q"], quant).reshape(t, d["hq"], d["d"]),
                w["q_norm"], eps)
    k = rmsnorm(mm(x, w["k"], quant).reshape(t, d["hk"], d["d"]),
                w["k_norm"], eps)
    v = mm(x, w["v"], quant).reshape(t, d["hk"], d["d"])
    if rotary:
        pos = jnp.arange(t)
        q, k = rope(q, pos, theta), rope(k, pos, theta)
    return q, k, v


@functools.partial(jax.jit, static_argnames=("window",))
def attn_block(q, k, v, qpos, kpos, window):
    """Queries ``q [B, Hq, d]`` at positions ``qpos`` over keys ``k, v [S,
    Hk, d]`` at positions ``kpos`` (each key head repeated over its query
    heads): a key is seen where ``0 <= kpos <= qpos``, and with a
    ``window`` where ``kpos > qpos - window``."""
    g = q.shape[1] // k.shape[1]
    k, v = jnp.repeat(k, g, axis=1), jnp.repeat(v, g, axis=1)
    s = jnp.einsum("qhd,khd->hqk", q, k, precision=HIGHEST) \
        / np.sqrt(q.shape[-1])
    mask = (kpos[None, :] <= qpos[:, None]) & (kpos[None, :] >= 0)
    if window:
        mask &= kpos[None, :] > qpos[:, None] - window
    p = jax.nn.softmax(jnp.where(mask[None], s, -jnp.inf), axis=-1)
    return jnp.einsum("hqk,khd->qhd", p, v, precision=HIGHEST)


def attention(q, k, v, window):
    """Causal attention of one sequence a block of queries at a time; a
    window layer's block against its own keys and the ``window - 1``
    before them (the keys left of position 0 are padding it never
    sees)."""
    t = q.shape[0]
    if not window:
        kpos = jnp.arange(t)
        return jnp.concatenate([
            attn_block(q[s:s + Q_BLOCK], k, v,
                       s + jnp.arange(min(Q_BLOCK, t - s)), kpos, 0)
            for s in range(0, t, Q_BLOCK)])
    pad = window - 1
    span = pad + Q_BLOCK
    kp = jnp.pad(k, ((pad, Q_BLOCK), (0, 0), (0, 0)))
    vp = jnp.pad(v, ((pad, Q_BLOCK), (0, 0), (0, 0)))
    return jnp.concatenate([
        attn_block(q[s:s + Q_BLOCK], kp[s:s + span], vp[s:s + span],
                   s + jnp.arange(min(Q_BLOCK, t - s)),
                   s - pad + jnp.arange(span), window)
        for s in range(0, t, Q_BLOCK)])


@functools.partial(jax.jit, static_argnames=("eps", "quant"))
def attn_out(x, a, w, eps, quant):
    """``x + RMSNorm(a W_o)``."""
    o = mm(a.reshape(a.shape[0], -1), w["o"], quant)
    return x + rmsnorm(o, w["ln_attn"], eps)


def swiglu(y, gate, up, down, quant):
    return mm(jax.nn.silu(mm(y, gate, quant)) * mm(y, up, quant), down,
              quant)


@functools.partial(jax.jit, static_argnames=("quant",))
def dense_ffn(y, w, quant):
    return swiglu(y, w["gate"], w["up"], w["down"], quant)


@functools.partial(jax.jit, static_argnames=("k", "scale", "norm", "first",
                                             "held"))
def route(y, router, bias, valid, k, scale, norm, first, held):
    """Chosen experts ``[T, k]`` among the HELD ones (``held`` = not
    held here) and their weights: sigmoid scores over all the router's
    outputs, the top k by score + bias, weights the scores WITHOUT the
    bias, normalised over the k, scaled. A position past the sequence's
    needed length chooses none."""
    s = jax.nn.sigmoid(jnp.matmul(y, router.astype(jnp.float32),
                                  precision=HIGHEST))
    _, idx = jax.lax.top_k(s + bias.astype(jnp.float32), k)
    wt = jnp.take_along_axis(s, idx, axis=-1)
    if norm:
        wt = wt / (jnp.sum(wt, axis=-1, keepdims=True) + 1e-20)
    local = idx - first
    mine = (local >= 0) & (local < held) & valid[:, None]
    return jnp.where(mine, local, held), jnp.where(mine, wt * scale, 0.0)


@functools.partial(jax.jit, static_argnames=("experts", "cap"))
def expert_tables(idx, wt, experts, cap):
    """For each held expert the tokens that chose it, ``cap`` slots each
    (token T = none, weight 0): ``[E, cap]`` ids and weights."""
    t, k = idx.shape
    flat = idx.reshape(-1)
    order = jnp.argsort(flat, stable=True)
    se = flat[order]
    first = jnp.searchsorted(se, jnp.arange(experts + 1), side="left")
    slot = jnp.arange(t * k) - first[jnp.clip(se, 0, experts)]
    ids = jnp.full((experts + 1, cap), t, jnp.int32) \
        .at[se, slot].set((order // k).astype(jnp.int32), mode="drop")
    wts = jnp.zeros((experts + 1, cap), jnp.float32) \
        .at[se, slot].set(wt.reshape(-1)[order], mode="drop")
    return ids[:experts], wts[:experts]


@functools.partial(jax.jit, static_argnames=("quant",))
def expert_group(acc, y, ids, wts, gate, up, down, quant):
    """Add a group of experts' weighted SwiGLU outputs for the tokens
    that chose them: ids/wts ``[G, cap]``, weights ``[G, in, out]``."""
    ypad = jnp.concatenate([y, jnp.zeros((1, y.shape[1]), y.dtype)])
    xg = ypad[ids]
    bmm = jax.vmap(lambda a, b: mm(a, b, quant))
    out = bmm(jax.nn.silu(bmm(xg, gate)) * bmm(xg, up), down)
    return acc.at[ids.reshape(-1)].add(
        (out * wts[..., None]).reshape(-1, out.shape[-1]), mode="drop")


@functools.partial(jax.jit, static_argnames=("quant",))
def shared_ffn(y, w, quant):
    return swiglu(y, w["shared_gate"], w["shared_up"], w["shared_down"],
                  quant)


def moe_ffn(y, w, cfg, valid, quant):
    """The shared expert whole, plus the held experts' part of the routed
    sum."""
    d = dims(cfg)
    idx, wt = route(y, w["router"], w["router_bias"], valid, d["k"],
                    float(cfg["routed_scaling_factor"]),
                    bool(cfg["norm_topk_prob"]), d["first"], d["held"])
    counts = np.bincount(np.asarray(idx).reshape(-1),
                         minlength=d["held"] + 1)[:d["held"]]
    # slots an expert: 64 x a power of two, over the fullest expert
    cap = 64
    while cap < counts.max():
        cap *= 2
    ids, wts = expert_tables(idx, wt, d["held"], cap)
    acc = shared_ffn(y, w, quant)
    for g in range(0, d["held"], EXPERT_GROUP):
        sl = slice(g, g + EXPERT_GROUP)
        acc = expert_group(acc, y, ids[sl], wts[sl], w["experts_gate"][sl],
                           w["experts_up"][sl], w["experts_down"][sl],
                           quant)
    return acc


@functools.partial(jax.jit, static_argnames=("eps",))
def ffn_out(x, f, w, eps):
    """``x + RMSNorm(f)``."""
    return x + rmsnorm(f, w["ln_ffn"], eps)


def layer_forward(x, w, cfg, index, valid, quant):
    """One post-norm layer over one sequence ``x [T, H]`` (positions
    0..T-1)."""
    d, eps = dims(cfg), float(cfg["rms_norm_eps"])
    window = window_of(cfg, index)
    q, k, v = attn_operands(x, w, _frozen(d), eps, bool(window),
                            float(cfg["rope_parameters"]["rope_theta"]),
                            quant)
    a = attention(q, k, v, window)
    del q, k, v
    x = attn_out(x, a, w, eps, quant)
    f = dense_ffn(x, w, quant) if is_dense(cfg, index) \
        else moe_ffn(x, w, cfg, valid, quant)
    return ffn_out(x, f, w, eps)


def _hidden(cfg, ids, layer_weights, end_weights, valid, quant):
    """The last hidden states of one sequence, before the final norm."""
    x = jnp.take(end_weights["embed"], jnp.asarray(ids),
                 axis=0).astype(jnp.float32)
    for i in range(cfg["num_hidden_layers"]):
        x = layer_forward(x, layer_weights(i), cfg, i, valid, quant)
    return x


def served_logits(cfg, ids, rows, layer_weights, end_weights, quant=None,
                  block=1):
    """Teacher-forced logits (see ``harness/family.py``): ``ids`` [N, T]
    (prompt, served tokens, padding), ``rows`` [N, K] the positions whose
    next-token logits are wanted -> numpy [N, K, V] float32. A sequence
    at a time at the padded length (one set of shapes a run; a causal
    model's earlier positions never see the padding, which routes to no
    expert; ``block`` is not used). The MTP module is not run."""
    ids, rows = np.asarray(ids), np.asarray(rows)
    eps = float(cfg["rms_norm_eps"])
    out = []
    for n in range(len(ids)):
        valid = jnp.arange(ids.shape[1]) < int(rows[n].max()) + 1
        x = _hidden(cfg, ids[n], layer_weights, end_weights, valid, quant)
        out.append(np.asarray(head_logits(
            x[None], jnp.asarray(rows[n:n + 1]), end_weights["norm"],
            end_weights["head"], eps=eps, quant=quant)))
    return np.concatenate(out)


@functools.partial(jax.jit, static_argnames=("eps", "quant"))
def _mtp_inputs(h, e, w, eps, quant):
    """``[RMSNorm(e); RMSNorm(h)] W_eh``."""
    return mm(jnp.concatenate([rmsnorm(e, w["enorm"], eps),
                               rmsnorm(h, w["hnorm"], eps)], -1),
              w["eh_proj"], quant)


def mtp_logits(cfg, ids, rows, layer_weights, end_weights, quant=None):
    """The MTP module's logits over teacher-forced sequences: ``ids`` [N,
    T], ``rows`` [N, K] positions ``i`` -> numpy [N, K, V], the
    prediction of the token at ``i + 2`` from the main model's states of
    positions ``0..i`` and the tokens at ``1..i + 1``."""
    ids, rows = np.asarray(ids), np.asarray(rows)
    eps = float(cfg["rms_norm_eps"])
    n_main = cfg["num_hidden_layers"]
    w = layer_weights(n_main)
    out = []
    for n in range(len(ids)):
        valid = jnp.arange(ids.shape[1]) < int(rows[n].max()) + 1
        h = _hidden(cfg, ids[n], layer_weights, end_weights, valid, quant)
        nxt = np.concatenate([ids[n][1:], [0]])
        e = jnp.take(end_weights["embed"], jnp.asarray(nxt),
                     axis=0).astype(jnp.float32)
        x = _mtp_inputs(h, e, w, eps, quant)
        x = layer_forward(x, w, cfg, n_main, valid, quant)
        out.append(np.asarray(head_logits(
            x[None], jnp.asarray(rows[n:n + 1]), w["mtp_norm"],
            end_weights["head"], eps=eps, quant=quant)))
    return np.concatenate(out)
