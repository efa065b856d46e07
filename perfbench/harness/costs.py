"""Operations and bytes the ALGORITHM needs, from the configuration and
the tokens the benchmark itself saw. Whatever kernel or XLA path does the
work, these stay the same; recomputation is never counted. What depends
on a model's layers is its family's (``perfbench/families``:
``total_params``, ``serve_work``, ``train_*``); what every family shares
is here."""


def causal_pairs(prompt_len, new_tokens=0):
    """Sum over the query tokens of a sequence of the keys each attends
    to (itself included): positions 0..prompt_len+new_tokens-1."""
    n = prompt_len + new_tokens
    return n * (n + 1) // 2


def least_seconds(flops, nbytes, pk):
    """The roofline's least time and which bound gives it."""
    tf, tb = flops / pk["flops_per_s"], nbytes / pk["bytes_per_s"]
    return (tf, "compute") if tf >= tb else (tb, "memory")
