"""Operations and bytes the ALGORITHM needs, from the configuration and
the tokens the benchmark itself saw. Whatever kernel or XLA path does the
work, these stay the same; recomputation is never counted.

A configuration is the dict of a ``perfbench/configs/*.json`` file
(Hugging Face key names)."""


def head_dim(cfg):
    return cfg.get("head_dim") or \
        cfg["hidden_size"] // cfg["num_attention_heads"]


def layer_params(cfg):
    """Parameters of one decoder layer: q, k, v, o, gate, up, down and
    the two RMSNorm weights."""
    h, f, d = cfg["hidden_size"], cfg["intermediate_size"], head_dim(cfg)
    nq, nk = cfg["num_attention_heads"], cfg["num_key_value_heads"]
    return h * nq * d + 2 * h * nk * d + nq * d * h + 3 * h * f + 2 * h


def embed_params(cfg):
    return cfg["vocab_size"] * cfg["hidden_size"]


def total_params(cfg):
    n = cfg["num_hidden_layers"] * layer_params(cfg) + embed_params(cfg) \
        + cfg["hidden_size"]
    if not cfg.get("tie_word_embeddings", False):
        n += embed_params(cfg)
    return n


def matmul_params(cfg):
    """Parameters that a token multiplies through: all but the embedding
    table (a gather) and the norm weights."""
    return cfg["num_hidden_layers"] * (layer_params(cfg)
                                       - 2 * cfg["hidden_size"]) \
        + embed_params(cfg)


def kv_bytes_per_token(cfg, itemsize=2):
    """K and V of one token over all layers."""
    return 2 * cfg["num_key_value_heads"] * head_dim(cfg) * itemsize \
        * cfg["num_hidden_layers"]


def attn_flops(cfg, q_tokens_times_context):
    """QK^T and PV of all layers for a sum of (query token x keys it
    attends to): 2 matmuls x 2 FLOPs x heads x head_dim per pair."""
    return 4 * cfg["num_attention_heads"] * head_dim(cfg) \
        * cfg["num_hidden_layers"] * q_tokens_times_context


def causal_pairs(prompt_len, new_tokens=0):
    """Sum over the query tokens of a sequence of the keys each attends
    to (itself included): positions 0..prompt_len+new_tokens-1."""
    n = prompt_len + new_tokens
    return n * (n + 1) // 2


def train_flops_per_token(cfg, seq_len):
    """6 x matmul parameters plus causal attention forward and backward
    (3 x forward; the flash kernels' recomputation is not counted)."""
    pairs_per_token = (seq_len + 1) / 2.0
    return 6 * matmul_params(cfg) + 3 * attn_flops(cfg, pairs_per_token)


def train_attn_flops(cfg, seq_len, sequences):
    return 3 * attn_flops(cfg, causal_pairs(seq_len)) * sequences


def train_attn_bytes(cfg, seq_len, sequences, itemsize=2):
    """Least traffic of attention forward and backward: q, k, v, o read
    or written once forward; q, k, v, o, do read and dq, dk, dv written
    backward."""
    d = head_dim(cfg)
    q = cfg["num_attention_heads"] * d
    kv = cfg["num_key_value_heads"] * d
    per_token = (2 * q + 2 * kv) + (3 * q + 2 * kv) + (q + 2 * kv)
    return per_token * itemsize * seq_len * sequences \
        * cfg["num_hidden_layers"]


def weight_bytes(cfg, itemsize=2):
    """Bytes of the weights a serving step streams once: every matmul
    parameter but the embedding table (a gather of a few rows)."""
    return matmul_params(cfg) * itemsize


def serve_work(cfg, steps, prefill, decode, kv_itemsize=2,
               weight_itemsize=2):
    """FLOPs and bytes of a serving window.

    ``steps``: executions of the step program (each streams the weights
    once); ``prefill``: list of prompt lengths whose prefill fell in the
    window; ``decode``: list of context lengths (keys attended to) of the
    output tokens decoded in the window. Attention reads the K/V of each
    context once: a prompt once over its own length, a decoded token over
    its context."""
    tokens = sum(prefill) + len(decode)
    pairs = sum(causal_pairs(p) for p in prefill) + sum(decode)
    flops = 2 * matmul_params(cfg) * tokens + attn_flops(cfg, pairs)
    kvb = kv_bytes_per_token(cfg, kv_itemsize)
    kv_read = kvb * (sum(prefill) + sum(decode))
    kv_write = kvb * tokens
    return {"flops": flops, "tokens": tokens,
            "bytes": steps * weight_bytes(cfg, weight_itemsize)
            + kv_read + kv_write,
            "attn_flops": attn_flops(cfg, pairs),
            "attn_bytes": kv_read + kv_write}


def least_seconds(flops, nbytes, pk):
    """The roofline's least time and which bound gives it."""
    tf, tb = flops / pk["flops_per_s"], nbytes / pk["bytes_per_s"]
    return (tf, "compute") if tf >= tb else (tb, "memory")
