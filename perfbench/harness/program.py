"""Where the benchmark touches the program: handing it the seeded weights,
reading its counters. Its constructor and its parameters' names are the
family's (``perfbench/families``). Everything else the benchmark needs
is its own."""

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


def assign_weights(family, model, cfg, seed, dtype, keep=True):
    """Give the program the seeded weights, layer by layer so that at
    most one layer lies twice on the device. Returns the arrays (the
    benchmark's own: ``{"ends": {leaf: array}, "layers": [{leaf:
    array}]}``) when ``keep``, and the program's parameter count."""
    params = family.leaves(model, cfg)

    def put(name, arr):
        p = params[name]
        if tuple(p.shape) != tuple(arr.shape):
            raise ValueError(f"{name}: program {tuple(p.shape)} vs "
                             f"benchmark {tuple(arr.shape)}")
        p._data = arr

    ends = family.ends(cfg, seed, dtype)
    for k, a in ends.items():
        put(k, a)
    kept = {"ends": ends, "layers": []}
    for i in range(family.layer_count(cfg)):
        lw = family.layer(cfg, seed, i, dtype)
        for k, a in lw.items():
            put(f"layers.{i}.{k}", a)
        kept["layers"].append(lw)
    n = sum(int(np.prod(p.shape)) for p in model.parameters())
    return (kept if keep else None), n
