"""The decoder-hybrid-decoder family (the SambaY layout, here
Phi-4-mini-flash-reasoning): pre-norm LayerNorm with bias, a fused
gate-up MLP, tied head, no positional encoding, and six kinds of layer:
state-space (Mamba) layers alternating with differential attention over
a window in the first half; then one Mamba layer that hands on its scan
output, one full differential attention layer whose K/V every later
attention layer reads, and gated memory units alternating with
differential cross attention. Everything the harness knows of it is
here, behind the interface that ``harness/family.py`` lists.

Leaves (matrices [in, out]): ``embed`` [V, H], ``norm_w norm_b`` [H]; a
layer has ``ln1_w ln1_b ln2_w ln2_b mlp_up mlp_down`` and then, Mamba,
``in_proj conv_w conv_b x_proj dt_proj dt_bias A_log D out_proj``
(``conv_w`` [C, K], ``A_log`` [N, C]: channels last); attention, ``qkv
qkv_b o o_b lq1 lk1 lq2 lk2 subln`` (``qkv`` holds the queries alone in
a cross layer); gated memory unit, ``in_proj out_proj``.

The reference is ISSUE 34's equations in straightforward ``jax.numpy``,
float32, matmuls at ``highest``: a token-by-token ``lax.scan`` for the
state, dense masked attention a block of queries at a time with heads
64 wide, both softmaxes of a pair written out, no cache, no batching;
nothing of the program is imported. ``quant="int8"`` is the control:
every linear layer through ``harness/reference.py:mm`` in int8 (the
convolution, the scan and the softmaxes stay float32)."""

import functools
import json
import math
import os

import jax
import jax.numpy as jnp
import numpy as np

from harness import weights
from harness.reference import HIGHEST, mm
from harness.selfcheck import near

HERE = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))

# what the published config.json does not say: the published model
# code's (the configuration lists them under "assumed")
D_STATE, D_CONV, EXPAND = 16, 4, 2
LAMBDA_STD = 0.1
DT_MIN, DT_MAX = 1e-3, 1e-1


# ---------------------------------------------------------------------------
# the program's side
# ---------------------------------------------------------------------------
CONFIG_KEYS = ("vocab_size", "hidden_size", "intermediate_size",
               "num_hidden_layers", "num_attention_heads",
               "num_key_value_heads", "sliding_window", "mb_per_layer",
               "layer_norm_eps", "tie_word_embeddings",
               "max_position_embeddings")


def build_model(cfg, dtype):
    """The program's own constructor."""
    import paddle_tpu as paddle
    from paddle_tpu.models import SambaYConfig, SambaYForCausalLM
    only = {"hidden_act": "silu", "mlp_bias": False, "lm_head_bias": False,
            "embd_pdrop": 0, "resid_pdrop": 0}
    for k, v in only.items():
        if cfg.get(k, v) != v:
            raise ValueError(f"family sambay runs {k}={v!r} only, the "
                             f"configuration gives {cfg[k]!r}")
    paddle.set_default_dtype(dtype)
    try:
        model = SambaYForCausalLM(SambaYConfig(
            mamba_d_state=D_STATE, mamba_d_conv=D_CONV, mamba_expand=EXPAND,
            lambda_std=LAMBDA_STD, **{k: cfg[k] for k in CONFIG_KEYS}))
    finally:
        paddle.set_default_dtype("float32")
    return model


def kind(cfg, index):
    """mamba, window, mamba_memory, full, gmu or cross."""
    half = cfg["num_hidden_layers"] // 2
    if index < half:
        return "window" if index % 2 else "mamba"
    if index == half:
        return "mamba_memory"
    if index == half + 1:
        return "full"
    return "cross" if index % 2 else "gmu"


def leaves(model, cfg):
    """``{benchmark leaf name: the program's parameter}``."""
    out = {"embed": model.model.embed_tokens.weight,
           "norm_w": model.model.norm.weight,
           "norm_b": model.model.norm.bias}
    for i, layer in enumerate(model.model.layers):
        m = layer.mixer
        own = {"ln1_w": layer.input_layernorm.weight,
               "ln1_b": layer.input_layernorm.bias,
               "ln2_w": layer.post_attention_layernorm.weight,
               "ln2_b": layer.post_attention_layernorm.bias,
               "mlp_up": layer.mlp.up.weight,
               "mlp_down": layer.mlp.down.weight}
        k = kind(cfg, i)
        if k in ("mamba", "mamba_memory"):
            own.update(in_proj=m.in_proj.weight, conv_w=m.conv_w,
                       conv_b=m.conv_b, x_proj=m.x_proj.weight,
                       dt_proj=m.dt_proj.weight, dt_bias=m.dt_proj.bias,
                       A_log=m.A_log, D=m.D, out_proj=m.out_proj.weight)
        elif k == "gmu":
            own.update(in_proj=m.in_proj.weight,
                       out_proj=m.out_proj.weight)
        else:
            own.update(qkv=m.qkv.weight, qkv_b=m.qkv.bias,
                       o=m.out_proj.weight, o_b=m.out_proj.bias,
                       lq1=m.lq1, lk1=m.lk1, lq2=m.lq2, lk2=m.lk2,
                       subln=m.subln)
        out.update({f"layers.{i}.{n}": p for n, p in own.items()})
    return out


def engine(model, mix):
    """The serving engine as the mix sizes it, its two step shapes (the
    chunk budget and the decode batch) warm."""
    from paddle_tpu.inference.serving import LlamaServingEngine
    e = LlamaServingEngine(model, **mix["engine"])
    e.prewarm(mixed=[e.chunk_budget, e.max_batch])
    return e


def release(engine):
    """Free the states, the rings and the shared pool: the reference
    runs beside the weights alone."""
    engine.k_pools = engine.v_pools = None


# ---------------------------------------------------------------------------
# seeded weights
# ---------------------------------------------------------------------------
def layer_count(cfg):
    return cfg["num_hidden_layers"]


def dims(cfg):
    h = cfg["hidden_size"]
    return {"h": h, "i": cfg["intermediate_size"],
            "hq": cfg["num_attention_heads"],
            "hk": cfg["num_key_value_heads"],
            "d": h // cfg["num_attention_heads"], "c": EXPAND * h,
            "n": D_STATE, "k": D_CONV, "r": math.ceil(h / 16),
            "w": cfg["sliding_window"]}


def layer_shapes(cfg, index):
    """``(normal leaves std 0.02, l* vectors std 0.1, leaves that are
    1, leaves with a formula)`` of layer ``index``: name -> shape."""
    d = dims(cfg)
    h, c = d["h"], d["c"]
    normal = {"ln1_b": (h,), "ln2_b": (h,), "mlp_up": (h, 2 * d["i"]),
              "mlp_down": (d["i"], h)}
    lam, ones, formula = {}, {"ln1_w": (h,), "ln2_w": (h,)}, {}
    k = kind(cfg, index)
    if k in ("mamba", "mamba_memory"):
        normal.update(in_proj=(h, 2 * c), conv_w=(c, d["k"]), conv_b=(c,),
                      x_proj=(c, d["r"] + 2 * d["n"]),
                      dt_proj=(d["r"], c), out_proj=(c, h))
        ones["D"] = (c,)
        formula.update(dt_bias=(c,), A_log=(d["n"], c))
    elif k == "gmu":
        normal.update(in_proj=(h, c), out_proj=(c, h))
    else:
        qd = d["hq"] * d["d"]
        width = qd + (0 if k == "cross" else 2 * d["hk"] * d["d"])
        normal.update(qkv=(h, width), qkv_b=(width,), o=(qd, h),
                      o_b=(h,))
        lam = {n: (d["d"],) for n in ("lq1", "lk1", "lq2", "lk2")}
        ones["subln"] = (2 * d["d"],)
    return normal, lam, ones, formula


def _frozen(shapes):
    return tuple(sorted(shapes.items()))


@functools.partial(jax.jit, static_argnames=("normal", "lam", "ones",
                                             "formula", "dtype"))
def _layer(key, normal, lam, ones, formula, dtype):
    out = weights.normal_leaves(key, normal, dtype)
    for i, (name, shape) in enumerate(lam):
        out[name] = (LAMBDA_STD * jax.random.normal(
            jax.random.fold_in(key, 1000 + i), shape, jnp.float32)) \
            .astype(dtype)
    for name, shape in ones:
        out[name] = jnp.ones(shape, dtype)
    for name, shape in formula:
        if name == "A_log":     # A = -(1..N) in every channel
            out[name] = jnp.broadcast_to(jnp.log(jnp.arange(
                1, shape[0] + 1, dtype=jnp.float32))[:, None], shape) \
                .astype(dtype)
        else:                   # softplus(dt_bias) log-uniform in range
            u = jax.random.uniform(jax.random.fold_in(key, 2000), shape,
                                   jnp.float32)
            dt = jnp.exp(u * (math.log(DT_MAX) - math.log(DT_MIN))
                         + math.log(DT_MIN))
            out[name] = (dt + jnp.log(-jnp.expm1(-dt))).astype(dtype)
    return out


@functools.partial(jax.jit, static_argnames=("vocab", "hidden", "dtype"))
def _ends(key, vocab, hidden, dtype):
    k1, k2 = jax.random.split(key)
    return {"embed": weights.normal(k1, (vocab, hidden), dtype),
            "norm_w": jnp.ones((hidden,), dtype),
            "norm_b": weights.normal(k2, (hidden,), dtype)}


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
    """The head is the embedding: counted once."""
    return sum(layer_params(cfg, i) for i in range(cfg["num_hidden_layers"])) \
        + cfg["vocab_size"] * cfg["hidden_size"] + 2 * cfg["hidden_size"]


def kinds(cfg):
    """How many layers of each kind."""
    out = {}
    for i in range(cfg["num_hidden_layers"]):
        out[kind(cfg, i)] = out.get(kind(cfg, i), 0) + 1
    return out


def token_flops(cfg):
    """Matmul FLOPs a token outside the attention, the scan and the
    head: every matrix of every layer once, 2 a multiply-add."""
    per = 0
    for i in range(cfg["num_hidden_layers"]):
        normal = layer_shapes(cfg, i)[0]
        per += sum(int(np.prod(s)) for n, s in normal.items()
                   if len(s) == 2 and n != "conv_w")
    return 2 * per


def state_bytes(cfg, itemsize=2):
    """What ONE Mamba layer keeps a sequence: the float32 state and the
    conv's last inputs."""
    d = dims(cfg)
    return d["c"] * d["n"] * 4 + d["c"] * (d["k"] - 1) * itemsize


def kv_bytes(cfg, itemsize=2):
    """K and V of one token in one attention layer."""
    d = dims(cfg)
    return 2 * d["hk"] * d["d"] * itemsize


def window_keys(cfg, prefill, decode):
    """Sum over the window's query tokens of the keys each sees in a
    window layer."""
    w = cfg["sliding_window"]

    def prompt(p):          # positions 0..p-1 see min(pos + 1, w) keys
        full = min(p, w)
        return full * (full + 1) // 2 + (p - full) * w

    return sum(prompt(p) for p in prefill) + sum(min(c, w) for c in decode)


def full_keys(prefill, decode):
    return sum(p * (p + 1) // 2 for p in prefill) + sum(decode)


def diff_attn_work(cfg, prefill, decode, itemsize=2):
    """``(flops, bytes)`` of the differential attention of all layers: a
    (query, key) pair costs every query head a 64-wide score and a
    128-wide value row, 2 FLOPs a multiply-add; a window layer reads
    min(context, window) keys a row, and the shared pool's context is
    read once for EACH of its readers; a prompt's keys are written once
    a layer that owns them and read once a reader."""
    d, kd = dims(cfg), kinds(cfg)
    pair = 2 * d["hq"] * (d["d"] + 2 * d["d"])
    readers = kd["full"] + kd["cross"]
    flops = pair * (kd["window"] * window_keys(cfg, prefill, decode)
                    + readers * full_keys(prefill, decode))
    kv = kv_bytes(cfg, itemsize)
    w = cfg["sliding_window"]
    nbytes = kv * (
        kd["window"] * (2 * sum(prefill) + len(decode)
                        + sum(min(c, w) for c in decode))
        + (readers + kd["full"]) * sum(prefill) + kd["full"] * len(decode)
        + readers * sum(decode))
    return flops, nbytes


def scan_work(cfg, prefill, decode, itemsize=2):
    """``(flops, bytes)`` of the convolutions and scans of all Mamba
    layers: a token updates ``C x N`` state entries (decay, input, sum:
    3) and reads them out (2), one exponential each, the convolution's
    ``K`` taps and the skip; the states go once in and once out a row
    (a prompt, a decoded token); ``x``, the step size and ``m`` (C wide)
    and ``B``, ``C`` (N wide) once a token."""
    d, kd = dims(cfg), kinds(cfg)
    layers = kd["mamba"] + kd["mamba_memory"]
    tokens = sum(prefill) + len(decode)
    rows = len(prefill) + len(decode)
    flops = layers * tokens * (6 * d["c"] * d["n"]
                               + 2 * d["c"] * (d["k"] + 1))
    nbytes = layers * (2 * rows * state_bytes(cfg, itemsize)
                       + tokens * (2 * d["c"] * itemsize + d["c"] * 4
                                   + 2 * d["n"] * 4))
    return flops, nbytes


def serve_work(cfg, steps, prefill, decode, itemsize=2):
    """FLOPs and bytes of a serving window (see ``harness/family.py``):
    every weight once a step (the embedding is the head, so it is read
    whole), logits at the last position of a prompt and at every decoded
    token, the states of every row, the windows and the shared pool."""
    tokens = sum(prefill) + len(decode)
    heads = 2 * cfg["hidden_size"] * cfg["vocab_size"] \
        * (len(prefill) + len(decode))
    a_flops, a_bytes = diff_attn_work(cfg, prefill, decode, itemsize)
    s_flops, s_bytes = scan_work(cfg, prefill, decode, itemsize)
    return {"flops": token_flops(cfg) * tokens + heads + a_flops + s_flops,
            "tokens": tokens,
            "bytes": steps * total_params(cfg) * itemsize + a_bytes
            + s_bytes,
            "attn_flops": a_flops, "attn_bytes": a_bytes,
            "scan_flops": s_flops, "scan_bytes": s_bytes}


def _no_training(*_a, **_k):
    raise NotImplementedError("family sambay has no training cell: its "
                              "costs and reference cover serving")


train_flops_per_token = train_attn_flops = train_attn_bytes = \
    train_readings = _no_training


def _config(name):
    with open(os.path.join(HERE, "configs", name + ".json")) as f:
        return json.load(f)


def selfcheck():
    """The cost functions against ISSUE 34's hand figures."""
    c = _config("phi-4-mini-flash-reasoning")
    near(layer_params(c, 0), 119.9e6, 1e-3, "Mamba layer")
    near(layer_params(c, 1), 98.3e6, 1e-3, "window attention layer")
    near(layer_params(c, 16), 119.9e6, 1e-3, "memory Mamba layer")
    near(layer_params(c, 17), 98.3e6, 1e-3, "full attention layer")
    near(layer_params(c, 18), 104.9e6, 1e-3, "gated memory unit layer")
    near(layer_params(c, 19), 91.8e6, 1e-3, "cross attention layer")
    near(total_params(c), 3852e6, 1e-3, "Phi-4-mini-flash params")
    k = kinds(c)
    assert (k["mamba"], k["window"], k["mamba_memory"], k["full"],
            k["gmu"], k["cross"]) == (8, 8, 1, 1, 7, 7), k
    # 5120 x 16 float32 + 5120 x 3 bf16 a layer: 3.23 MB over nine
    near(9 * state_bytes(c), 3.23e6, 2e-3, "state bytes a sequence")
    near(kv_bytes(c), 5120, 0, "K/V bytes a token a layer")
    near(window_keys(c, [], [100, 5000]), 100 + 512, 0, "window keys")
    near(window_keys(c, [600], []), 512 * 513 // 2 + 88 * 512, 0,
         "window keys of a prompt")
    # one decode token at context 1100 in a step of its own: all the
    # weights, nine states in and out, eight windows of 512 keys and
    # the new key, the shared pool's 1100 keys eight times and the new
    # key once
    w = serve_work(c, 1, [], [1100])
    near(w["attn_bytes"], 5120 * (8 * (512 + 1) + 8 * 1100 + 1), 0,
         "decode token K/V bytes")
    near(w["attn_flops"], 2 * 40 * 192 * (8 * 512 + 8 * 1100), 0,
         "decode token attention FLOPs")
    near(w["scan_bytes"], 9 * (2 * 358400 + 5120 * 8 + 128), 0,
         "decode token scan bytes")
    near(w["bytes"], 2 * 3852e6 + w["attn_bytes"] + w["scan_bytes"], 1e-3,
         "decode step bytes")


# ---------------------------------------------------------------------------
# the plain reference
# ---------------------------------------------------------------------------
Q_BLOCK = 256           # queries a block of the attention
HEAD_ROWS = 512         # positions a block of the head


def layernorm(x, w, b, eps):
    x = x.astype(jnp.float32)
    mu = jnp.mean(x, -1, keepdims=True)
    var = jnp.mean(jnp.square(x - mu), -1, keepdims=True)
    return (x - mu) * jax.lax.rsqrt(var + eps) * w.astype(jnp.float32) \
        + b.astype(jnp.float32)


@functools.partial(jax.jit, static_argnames=("eps", "quant"))
def mlp_block(x, w, eps, quant):
    """``x + MLP(LN(x))``: gate and value are the halves of one matrix."""
    gv = mm(layernorm(x, w["ln2_w"], w["ln2_b"], eps), w["mlp_up"], quant)
    g, v = jnp.split(gv, 2, axis=-1)
    return x + mm(jax.nn.silu(g) * v, w["mlp_down"], quant)


@functools.partial(jax.jit, static_argnames=("d", "eps", "quant"))
def mamba_mixer(x, w, d, eps, quant):
    """One sequence ``x [T, H]`` from a zero state: ``(W_out (silu(z) *
    m), m)``. The scan advances a token at a time."""
    d = dict(d)
    c, n, r, k = d["c"], d["n"], d["r"], d["k"]
    f32 = jnp.float32
    xz = mm(layernorm(x, w["ln1_w"], w["ln1_b"], eps), w["in_proj"], quant)
    xs, z = xz[:, :c], xz[:, c:]
    t = xs.shape[0]
    pad = jnp.concatenate([jnp.zeros((k - 1, c), f32), xs])
    conv = sum(pad[j:j + t] * w["conv_w"].astype(f32)[None, :, j]
               for j in range(k)) + w["conv_b"].astype(f32)
    xc = jax.nn.silu(conv)
    dbc = mm(xc, w["x_proj"], quant)
    dt = jax.nn.softplus(mm(dbc[:, :r], w["dt_proj"], quant)
                         + w["dt_bias"].astype(f32))
    bm, cm = dbc[:, r:r + n], dbc[:, r + n:]
    a = -jnp.exp(w["A_log"].astype(f32))               # [N, C]

    def tick(h, tok):
        x_t, dt_t, b_t, c_t = tok
        h = jnp.exp(dt_t[None, :] * a) * h \
            + (dt_t * x_t)[None, :] * b_t[:, None]
        return h, jnp.sum(c_t[:, None] * h, axis=0)

    _, y = jax.lax.scan(tick, jnp.zeros((n, c), f32), (xc, dt, bm, cm))
    m = y + w["D"].astype(f32) * xc
    return mm(jax.nn.silu(z) * m, w["out_proj"], quant), m


@functools.partial(jax.jit, static_argnames=("eps", "quant"))
def gmu_mixer(x, m, w, eps, quant):
    g = mm(layernorm(x, w["ln1_w"], w["ln1_b"], eps), w["in_proj"], quant)
    return mm(jax.nn.silu(g) * m, w["out_proj"], quant)


@functools.partial(jax.jit, static_argnames=("d", "eps", "quant", "own"))
def attn_operands(x, w, d, eps, quant, own):
    """``q [T, Hq, d]`` and, of a layer with K/V of its own (``own``),
    ``k, v [T, Hk, d]``."""
    d = dict(d)
    t = x.shape[0]
    qkv = mm(layernorm(x, w["ln1_w"], w["ln1_b"], eps), w["qkv"], quant) \
        + w["qkv_b"].astype(jnp.float32)
    qd, kd = d["hq"] * d["d"], d["hk"] * d["d"]
    q = qkv[:, :qd].reshape(t, d["hq"], d["d"])
    if not own:
        return q, None, None
    return q, qkv[:, qd:qd + kd].reshape(t, d["hk"], d["d"]), \
        qkv[:, qd + kd:].reshape(t, d["hk"], d["d"])


@functools.partial(jax.jit, static_argnames=("window",))
def diff_attn_block(q, k, v, start, lam, window):
    """Differential attention of the queries ``q [B, Hq, d]`` at
    positions ``start..`` over all of ``k, v [T, Hk, d]``: pair ``p``
    takes ``A1 V_g - lam A2 V_g`` with ``A1`` of query head ``2p`` over
    key head ``2g``, ``A2`` of ``2p + 1`` over ``2g + 1``, ``V_g`` both
    value heads side by side, ``g = p // 2``. Returns ``[B, Hq/2, 2d]``."""
    b, hq, d = q.shape
    t = k.shape[0]
    pairs = hq // 2
    g = jnp.arange(pairs) // 2
    qpos = start + jnp.arange(b)
    kpos = jnp.arange(t)
    mask = kpos[None, :] <= qpos[:, None]
    if window is not None:
        mask &= kpos[None, :] > qpos[:, None] - window
    vg = v.reshape(t, -1, 2 * d)[:, g]                  # [T, pairs, 2d]

    def soft(qh, kh):           # [B, pairs, d] x [T, pairs, d]
        s = jnp.einsum("qpd,kpd->pqk", qh, kh, precision=HIGHEST) \
            / np.sqrt(d)
        return jax.nn.softmax(jnp.where(mask[None], s, -jnp.inf), axis=-1)

    a1 = soft(q[:, 0::2], k[:, 2 * g])
    a2 = soft(q[:, 1::2], k[:, 2 * g + 1])
    return jnp.einsum("pqk,kpe->qpe", a1 - lam * a2, vg, precision=HIGHEST)


@functools.partial(jax.jit, static_argnames=("lam0", "eps", "quant"))
def attn_out(a, w, lam0, eps, quant):
    """RMSNorm over a pair's 2d values, times ``1 - lam0``, the pairs
    side by side through the output projection."""
    f32 = jnp.float32
    o = a * jax.lax.rsqrt(jnp.mean(a * a, -1, keepdims=True) + eps) \
        * w["subln"].astype(f32) * (1.0 - lam0)
    return mm(o.reshape(o.shape[0], -1), w["o"], quant) \
        + w["o_b"].astype(f32)


def lambda_init(index):
    return 0.8 - 0.6 * math.exp(-0.3 * index)


def attn_mixer(x, w, cfg, index, shared, quant):
    """``(mixer output, (k, v))``: a cross layer attends over ``shared``,
    the full layer's keys and values."""
    d, eps = dims(cfg), float(cfg["layer_norm_eps"])
    k_ = kind(cfg, index)
    q, k, v = attn_operands(x, w, _frozen(d), eps, quant, k_ != "cross")
    if k is None:
        k, v = shared
    f32 = jnp.float32
    lam0 = lambda_init(index)
    lam = jnp.exp(jnp.sum(w["lq1"].astype(f32) * w["lk1"].astype(f32))) \
        - jnp.exp(jnp.sum(w["lq2"].astype(f32) * w["lk2"].astype(f32))) \
        + lam0
    window = d["w"] if k_ == "window" else None
    a = jnp.concatenate([diff_attn_block(q[s:s + Q_BLOCK], k, v, s, lam,
                                         window)
                         for s in range(0, x.shape[0], Q_BLOCK)])
    return attn_out(a, w, lam0, eps, quant), (k, v)


def layer_forward(x, w, cfg, index, carry, quant):
    """One layer over one sequence ``x [T, H]`` (positions 0..T-1);
    ``carry`` holds layer n/2's scan output and layer n/2+1's K/V."""
    d, eps = dims(cfg), float(cfg["layer_norm_eps"])
    k = kind(cfg, index)
    if k in ("mamba", "mamba_memory"):
        y, m = mamba_mixer(x, w, _frozen(d), eps, quant)
        if k == "mamba_memory":
            carry["m"] = m
    elif k == "gmu":
        y = gmu_mixer(x, carry["m"], w, eps, quant)
    else:
        y, kv = attn_mixer(x, w, cfg, index, carry.get("kv"), quant)
        if k == "full":
            carry["kv"] = kv
    return mlp_block(x + y, w, eps, quant)


@functools.partial(jax.jit, static_argnames=("eps", "quant"))
def head_rows(x, rows, norm_w, norm_b, embed, eps, quant):
    """Logits at positions ``rows [K]`` of ``x [T, H]``: final
    LayerNorm, then the embedding transposed."""
    return mm(layernorm(x[rows], norm_w, norm_b, eps), embed.T, quant)


def hidden_states(cfg, ids, layer_weights, end_weights, quant=None):
    """The last layer's output ``[T, H]`` for one sequence of ids."""
    x = jnp.take(end_weights["embed"], jnp.asarray(ids), axis=0) \
        .astype(jnp.float32)
    carry = {}
    for i in range(cfg["num_hidden_layers"]):
        x = layer_forward(x, layer_weights(i), cfg, i, carry, quant)
    return x


def served_logits(cfg, ids, rows, layer_weights, end_weights, quant=None,
                  block=1):
    """Teacher-forced logits (see ``harness/family.py``): ``ids`` [N, T]
    (prompt, served tokens, padding), ``rows`` [N, K] the positions whose
    next-token logits are wanted -> numpy [N, K, V] float32. A sequence
    at a time at the padded length (one set of shapes a run; a causal
    model's earlier positions never see the padding), the head a block
    of positions at a time (``block`` is not used)."""
    ids, rows = np.asarray(ids), np.asarray(rows)
    eps = float(cfg["layer_norm_eps"])
    out = np.empty(rows.shape + (cfg["vocab_size"],), np.float32)
    for n in range(len(ids)):
        x = hidden_states(cfg, ids[n], layer_weights, end_weights, quant)
        for s in range(0, rows.shape[1], HEAD_ROWS):
            r = np.zeros((HEAD_ROWS,), np.int32)
            got = rows[n, s:s + HEAD_ROWS]
            r[:len(got)] = got
            out[n, s:s + HEAD_ROWS] = np.asarray(head_rows(
                x, jnp.asarray(r), end_weights["norm_w"],
                end_weights["norm_b"], end_weights["embed"], eps,
                quant))[:len(got)]
    return out
