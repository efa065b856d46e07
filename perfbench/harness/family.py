"""What the harness knows of a model family: one module a family,
``perfbench/families/<family>.py``, named by the ``family`` key of the
configuration's file and loaded from its file as the readers are. The
harness reaches every model fact through that module (``run.family``) and
nothing past it; there is no default family and no table of names.

Every function takes the configuration's dict (``cfg``: the file under
``perfbench/configs``, Hugging Face key names) first, but ``selfcheck``
and those handed the program's own objects. Two keys every family's
configuration has: ``vocab_size`` (the traffic draws its ids from it) and
``torch_dtype`` (the type the weights are served, or autocast, in).

A layer may have leaves of its own: the harness never assumes that layers
are alike. A leaf of layer ``i`` is called ``layers.<i>.<name>``
everywhere (the program's parameters, the reference's readings)."""

import importlib.util
import os

DIRECTORY = os.path.join(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))), "families")

REQUIRED = (
    # the program's side
    "build_model",      # (cfg, dtype) -> the program's own model
    "leaves",           # (model, cfg) -> {leaf name: the program's parameter}
    "engine",           # (model, mix) -> the serving engine, its shapes warm
    "release",          # (engine): drop its device state before the reference
    # seeded weights, for the program and the reference alike
    "layer_count",      # (cfg) -> number of layers
    "ends",             # (cfg, seed, dtype) -> {leaf: array} outside the layers
    "layer",            # (cfg, seed, index, dtype) -> {leaf: array} of one layer
    # operations and bytes the algorithm needs
    "total_params",     # (cfg)
    "serve_work",       # (cfg, steps, prefill, decode) -> flops tokens bytes
                        #   attn_flops attn_bytes
    "train_flops_per_token",    # (cfg, seq_len)
    "train_attn_flops",         # (cfg, seq_len, sequences)
    "train_attn_bytes",         # (cfg, seq_len, sequences)
    # the plain reference
    "served_logits",    # (cfg, ids, rows, layer_weights, end_weights, quant,
                        #   block) -> [N, K, V]
    "train_readings",   # (cfg, seed, batches, hp, quant=, rows=) -> loss grad
                        #   delta
    # the family's costs against figures worked by hand, in every run
    "selfcheck",        # ()
)


def path_of(name):
    return os.path.join(DIRECTORY, str(name) + ".py")


def load(cfg, cfg_file):
    """The module of the family that the configuration names."""
    name = cfg.get("family")
    if not name:
        raise KeyError(f"{cfg_file} names no \"family\": the harness looks "
                       f"for {path_of('<family>')}")
    path = path_of(name)
    if not os.path.isfile(path):
        raise FileNotFoundError(f"{cfg_file} names the family {name!r}, "
                                f"and there is no {path}")
    spec = importlib.util.spec_from_file_location(
        "perfbench_family_" + str(name).replace(".", "_").replace("-", "_"),
        path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    missing = [n for n in REQUIRED if not callable(getattr(mod, n, None))]
    if missing:
        raise AttributeError(f"{path} lacks {', '.join(missing)} of the "
                             f"family interface (harness/family.py)")
    return mod
