"""Where the benchmark touches the program: building the model through
its constructor, handing it the seeded weights, reading its counters.
Everything else the benchmark needs is its own."""

import numpy as np


def metric(name):
    """Value of one of the program's unlabeled counters or gauges (0
    until the program registers it)."""
    from paddle_tpu.observability import metrics as om
    m = om.default_registry().get(name)
    try:
        return float(m.value) if m is not None else 0.0
    except Exception:
        return 0.0


def cache_stats():
    from paddle_tpu.observability import compile_watch as cw
    return cw.persistent_cache_stats()


def llama_config(cfg, **extra):
    from paddle_tpu.models import LlamaConfig
    return LlamaConfig(
        vocab_size=cfg["vocab_size"], hidden_size=cfg["hidden_size"],
        intermediate_size=cfg["intermediate_size"],
        num_hidden_layers=cfg["num_hidden_layers"],
        num_attention_heads=cfg["num_attention_heads"],
        num_key_value_heads=cfg["num_key_value_heads"],
        max_position_embeddings=cfg["max_position_embeddings"],
        rms_norm_eps=cfg["rms_norm_eps"], rope_theta=cfg["rope_theta"],
        tie_word_embeddings=cfg.get("tie_word_embeddings", False),
        **extra)


def build_model(cfg, dtype, **extra):
    """The program's own constructor (its eager per-parameter init is
    part of set-up until the program can skip it)."""
    import paddle_tpu as paddle
    from paddle_tpu.models import LlamaForCausalLM
    paddle.set_default_dtype(dtype)
    try:
        model = LlamaForCausalLM(llama_config(cfg, **extra))
    finally:
        paddle.set_default_dtype("float32")
    return model


def leaves(model):
    """``{benchmark leaf name: the program's parameter}``."""
    out = {"embed": model.model.embed_tokens.weight,
           "head": model.lm_head.weight, "norm": model.model.norm.weight}
    for i, layer in enumerate(model.model.layers):
        a, m = layer.self_attn, layer.mlp
        out.update({
            f"layers.{i}.q": a.q_proj.weight, f"layers.{i}.k": a.k_proj.weight,
            f"layers.{i}.v": a.v_proj.weight, f"layers.{i}.o": a.o_proj.weight,
            f"layers.{i}.gate": m.gate_proj.weight,
            f"layers.{i}.up": m.up_proj.weight,
            f"layers.{i}.down": m.down_proj.weight,
            f"layers.{i}.ln1": layer.input_layernorm.weight,
            f"layers.{i}.ln2": layer.post_attention_layernorm.weight})
    return out


def assign_weights(model, cfg, seed, dtype, keep=True):
    """Give the program the seeded weights, layer by layer so that at
    most one layer lies twice on the device. Returns the arrays (the
    benchmark's own) when ``keep``."""
    from . import weights
    params = leaves(model)

    def put(name, arr):
        p = params[name]
        if tuple(p.shape) != tuple(arr.shape):
            raise ValueError(f"{name}: program {tuple(p.shape)} vs "
                             f"benchmark {tuple(arr.shape)}")
        p._data = arr

    kept = {"layers": []}
    ends = weights.ends(cfg, seed, dtype)
    for k, a in ends.items():
        put(k, a)
    kept.update(ends)
    for i in range(cfg["num_hidden_layers"]):
        lw = weights.layer(cfg, seed, i, dtype)
        for k, a in lw.items():
            put(f"layers.{i}.{k}", a)
        kept["layers"].append(lw)
    n = sum(int(np.prod(p.shape)) for p in model.parameters())
    return (kept if keep else None), n
