"""``paddle_tpu.jit`` — trace-compilation of imperative train steps to XLA.

Reference capability: `python/paddle/jit/api.py:136` (``to_static``) — the
reference captures Python bytecode (SOT) or rewrites ASTs (dy2static) to
turn eager code into a static program. The TPU-native design needs neither:
eager Tensors carry ``jax.Array`` payloads, so the same tape-recording ops
run unmodified under ``jax.jit`` tracing with tracer payloads. ``to_static``
therefore:

1. **warmup call** — runs the wrapped function eagerly once so lazy state
   (optimizer accumulators, RNG streams) materializes;
2. **trace** — swaps every state Tensor's payload for a jit tracer, replays
   the function (forward + ``loss.backward()`` + ``opt.step()`` all record
   through the same tape), and captures the whole step as ONE pure XLA
   computation ``(state, grads, inputs, lr, key) -> (state', grads',
   outputs, key')``;
3. **steady state** — each call dispatches a single compiled executable
   with donated state buffers (no per-op dispatch, no host round-trips).

The learning rate and PRNG key are scalar *inputs* of the compiled program,
so LR schedules and randomness never retrace.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp

from ..framework.tensor import Tensor
from ..framework import random as frandom
from ..framework import amp_state

__all__ = ["to_static", "not_to_static", "ignore_module", "StaticFunction",
           "enable_to_static", "save", "load", "TranslatedLayer"]

_to_static_enabled = True


def enable_to_static(flag: bool):
    global _to_static_enabled
    _to_static_enabled = bool(flag)


def _discover_state(fn, extra):
    """Find Layers / Optimizers / Tensors the function closes over.

    The reference discovers program state by tracing variable usage
    (dy2static's ProgramTranslator); here state is the eager objects
    reachable from the function's closure cells, its ``__self__``, and
    anything passed explicitly via ``to_static(state=[...])``.
    """
    from ..nn import Layer
    from ..optimizer import Optimizer

    import types

    seen = set()
    layers, optimizers, tensors = [], [], []

    def visit(obj, depth=0):
        if id(obj) in seen:
            return
        seen.add(id(obj))
        if isinstance(obj, Layer):
            layers.append(obj)
        elif isinstance(obj, Optimizer):
            optimizers.append(obj)
        elif isinstance(obj, Tensor):
            tensors.append(obj)
        elif hasattr(obj, "__state_tensors__"):
            # stateful helpers (e.g. amp.GradScaler) expose their Tensors
            for t in obj.__state_tensors__():
                visit(t, depth)
        elif isinstance(obj, (list, tuple)):
            for e in obj:
                visit(e, depth)
        elif isinstance(obj, dict):
            for e in obj.values():
                visit(e, depth)
        elif depth < 2 and not isinstance(
                obj, (types.ModuleType, types.FunctionType,
                      types.MethodType, type, str, bytes, int, float,
                      bool, complex)) and hasattr(obj, "__dict__"):
            # plain container objects (a Trainer holding .model/.opt):
            # scan one attribute level so state reached through object
            # attributes is not silently missed (the stale-training trap)
            for e in vars(obj).values():
                visit(e, depth + 1)

    for obj in extra or ():
        visit(obj)
    for cell in getattr(fn, "__closure__", None) or ():
        try:
            visit(cell.cell_contents)
        except ValueError:
            pass
    self_obj = getattr(fn, "__self__", None)
    if self_obj is not None:
        visit(self_obj)
    # module-level model/optimizer referenced as globals (the common script
    # pattern): only names the function actually loads, to keep this cheap.
    # visit() does the type filtering — including the holder-object
    # attribute scan, so a module-level Trainer is discovered too
    code = getattr(fn, "__code__", None)
    if code is not None:
        g = getattr(fn, "__globals__", {})
        for name in code.co_names:
            obj = g.get(name)
            if obj is None or isinstance(
                    obj, (types.ModuleType, types.FunctionType,
                          types.BuiltinFunctionType, type, str, bytes,
                          int, float, bool)):
                continue
            if isinstance(obj, (Layer, Optimizer, Tensor, list, tuple,
                                dict)):
                visit(obj)        # direct state / containers: full scan
                continue
            mod = type(obj).__module__ or ""
            if mod.split(".")[0] in ("numpy", "jax", "builtins"):
                continue  # library objects are never training state
            # co_names mixes globals with attribute names, so this scan
            # can over-approximate; start holder objects at depth 1 (their
            # DIRECT Layer/Optimizer/Tensor attrs only) to bound capture
            visit(obj, depth=1)
    return layers, optimizers, tensors


def _is_arraylike(x):
    return isinstance(x, (jax.Array, Tensor)) or hasattr(x, "__array__")


class StaticFunction:
    """The compiled wrapper returned by ``to_static``."""

    def __init__(self, function, input_spec=None, state=None, donate=True,
                 warmup="per-signature", donate_inputs=False, name=None,
                 keep_args=()):
        functools.update_wrapper(self, function)
        self._fn = function
        self._input_spec = input_spec
        self._extra_state = state
        # donate=True is for steps that UPDATE state (train steps): the
        # old param buffers are dead after the call and XLA reuses them.
        # Pass donate=False for read-only programs (serving, generate) —
        # pass-through state gains nothing from donation, and when many
        # state slots share an aval (e.g. int8 weights + scale sidecars)
        # XLA's aval-based alias matching can scramble the identity
        # outputs across the donated buffers.
        self._donate = donate
        # compile-watch identity: per-callable compile counters/gauges
        # are labeled with this name (see observability.compile_watch)
        if name:
            self._watch_name = name
        else:
            qn = getattr(function, "__qualname__", None)
            mod = getattr(function, "__module__", None)
            if qn:
                # module-qualified so two files' lambdas don't conflate
                self._watch_name = f"{mod}.{qn}" if mod else qn
            else:
                # no qualname (partial/bound callables): a stable,
                # address-free label — repr() would mint one permanent
                # labeled registry child per instance
                self._watch_name = type(function).__name__
        self._aot = {}          # signature -> compiled executable | None
        # donate_inputs additionally donates the INPUT arrays to XLA so
        # same-shaped outputs alias them in place (e.g. KV-cache buffers in
        # a decode loop). Only safe when the caller never reuses an input
        # after the call.
        self._donate_inputs = donate_inputs
        # positional arguments whose arrays are NOT donated with the
        # rest: the caller reads them after the call (an array a later
        # call's output would otherwise be aliased into)
        self._keep_args = frozenset(keep_args)
        self._warmup = warmup   # "per-signature" | "once"
        self._warmed_any = False
        self._cache = {}        # signature -> (jitted fn, grad slots, out box)
        self._warm = set()      # signatures already run eagerly once
        self._layers = []
        self._optimizers = []
        self._state_tensors = None

    # -- state management ---------------------------------------------------
    def _collect_state(self):
        layers, optimizers, tensors = _discover_state(
            self._fn, self._extra_state)
        self._layers = layers
        self._optimizers = optimizers
        state, seen = [], set()

        def add(t):
            if t is not None and id(t) not in seen:
                seen.add(id(t))
                state.append(t)

        for l in layers:
            for p in l.parameters():
                add(p)
            for b in l.buffers():
                add(b)
        for o in optimizers:
            for p in o._parameter_list:
                add(p)
            for acc in o._accumulator_pytree():
                add(acc)
        for t in tensors:
            add(t)
        self._state_tensors = state

    def _signature(self, flat_in, in_treedef):
        training = tuple(l.training for l in self._layers)
        grads = tuple(t.grad is not None for t in self._state_tensors or ())
        shapes = tuple(
            (tuple(a.shape), str(a.dtype)) if hasattr(a, "shape")
            else (type(a).__name__, a if isinstance(a, (int, float, bool, str,
                                                        type(None))) else None)
            for a in flat_in)
        # ambient autocast state is baked into the trace (casts become part
        # of the compiled program), so a program traced inside auto_cast
        # must not be reused outside it — key the cache on it
        amp = amp_state.current()
        amp_key = None if amp is None else (amp.dtype.name, amp.level,
                                            amp.white, amp.black)
        # the treedef distinguishes positional from keyword binding of the
        # same leaves — without it f(x, y) and f(y=y, x=x) would share a
        # compiled entry and silently mis-bind inputs
        return (shapes, repr(in_treedef), training, grads, amp_key)

    # -- the traced pure step ----------------------------------------------
    def _build(self, in_treedef):
        from ..observability import compile_watch as _cw

        # train steps get the same persistent compile cache the serving
        # engine does (idempotent; one function places it for both)
        _cw.enable_persistent_cache()
        state_tensors = self._state_tensors
        optimizers = self._optimizers
        fn = self._fn
        grad_idx = [i for i, t in enumerate(state_tensors)
                    if t.grad is not None]
        out_box = {}
        donate_state = self._donate
        # flat indices of the inputs that are kept out of the donation
        kept_idx, at = [], 0
        for i, arg in enumerate(in_treedef.children()[0].children()):
            if i in self._keep_args:
                kept_idx += range(at, at + arg.num_leaves)
            at += arg.num_leaves
        out_box["kept"] = kept_idx

        def pure_step(state, grads, in_arrays, lrs, key, kept=()):
            if kept:
                in_arrays = list(in_arrays)
                for i, a in zip(kept_idx, kept):
                    in_arrays[i] = a
            saved = [(t._data, t.grad, t._node) for t in state_tensors]
            overrides = [o._lr_override for o in optimizers]
            try:
                for t, a in zip(state_tensors, state):
                    t._data = a
                    t.grad = None
                    t._node = None
                for i, g in zip(grad_idx, grads):
                    state_tensors[i].grad = Tensor(g, stop_gradient=True)
                for o, lr in zip(optimizers, lrs):
                    o._lr_override = lr
                with frandom.rng_guard(key) as gen:
                    ins = [Tensor(a) if isinstance(a, jax.Array) else a
                           for a in in_arrays]
                    args, kwargs = jax.tree_util.tree_unflatten(in_treedef, ins)
                    out = fn(*args, **kwargs)
                    new_key = gen._key
                new_state = [t._data for t in state_tensors]
                new_grads = [
                    state_tensors[i].grad._data
                    if state_tensors[i].grad is not None
                    else jnp.zeros_like(new_state[i])
                    for i in grad_idx]
                if not donate_state:
                    # read-only state (serving weights) stays out of the
                    # outputs: an AOT executable returns a non-donated
                    # pass-through as a fresh COPY — every weight, every
                    # dispatch. None marks "unchanged" for __call__.
                    new_state = [None if new is old else new
                                 for new, old in zip(new_state, state)]
                flat_out, out_treedef = jax.tree_util.tree_flatten(
                    out, is_leaf=lambda x: isinstance(x, Tensor))
                flat_out = [o._data if isinstance(o, Tensor) else o
                            for o in flat_out]
                out_box["treedef"] = out_treedef
                return new_state, new_grads, flat_out, new_key
            finally:
                for t, (d, g, n) in zip(state_tensors, saved):
                    t._data, t.grad, t._node = d, g, n
                for o, ov in zip(optimizers, overrides):
                    o._lr_override = ov

        donate = (0, 1) if donate_state else ()
        if self._donate_inputs:
            donate = donate + (2,)
        return jax.jit(pure_step, donate_argnums=donate), grad_idx, out_box

    def __call__(self, *args, **kwargs):
        if not _to_static_enabled:
            return self._fn(*args, **kwargs)
        flat_in, in_treedef = jax.tree_util.tree_flatten(
            (args, kwargs), is_leaf=lambda x: isinstance(x, Tensor))
        in_arrays = [a._data if isinstance(a, Tensor)
                     else jnp.asarray(a) if _is_arraylike(a) else a
                     for a in flat_in]
        if self._state_tensors is None:
            self._collect_state()
        sig = self._signature(in_arrays, in_treedef)

        if sig not in self._warm and not (self._warmup == "once"
                                          and self._warmed_any):
            # warmup: eager run materializes accumulators / lazy buffers.
            # Bookkeeping only after success — a failed warmup (OOM, data
            # bug) must not mark the function warm, or a retry would trace
            # with never-materialized accumulators and leak tracers.
            out = self._fn(*args, **kwargs)
            self._warm.add(sig)
            self._warmed_any = True
            self._collect_state()  # re-collect: step() created accumulators
            # the grown state changes the signature; mark it warm so the
            # next same-shape call compiles instead of re-warming
            self._warm.add(self._signature(in_arrays, in_treedef))
            return out

        entry = self._cache.get(sig)
        if entry is None:
            entry = self._build(in_treedef)
            self._cache[sig] = entry
        jitted, grad_idx, out_box = entry

        state = [t._data for t in self._state_tensors]
        grads = [self._state_tensors[i].grad._data for i in grad_idx]
        lrs = [jnp.asarray(o.get_lr(), jnp.float32)
               for o in self._optimizers]
        key = frandom.next_key()
        step_args = (state, grads, in_arrays, lrs, key)
        if out_box["kept"]:
            # the kept arrays ride as an argument of their own, which
            # is not donated; None holds their place among the rest
            kept = [in_arrays[i] for i in out_box["kept"]]
            donated = [None if i in out_box["kept"] else a
                       for i, a in enumerate(in_arrays)]
            step_args = (state, grads, donated, lrs, key, kept)
        if self._donate_inputs:
            # some inputs (e.g. prefill tokens) have no same-shaped output
            # to alias — the resulting JAX warning is expected, not a bug
            import warnings
            with warnings.catch_warnings():
                warnings.filterwarnings(
                    "ignore", message="Some donated buffers were not usable")
                new_state, new_grads, flat_out, _ = self._dispatch(
                    sig, jitted, step_args)
        else:
            new_state, new_grads, flat_out, _ = self._dispatch(
                sig, jitted, step_args)
        for t, a in zip(self._state_tensors, new_state):
            if a is not None:       # None: the step left it unchanged
                t._data = a
                t._node = None
        for i, g in zip(grad_idx, new_grads):
            self._state_tensors[i].grad = Tensor(g, stop_gradient=True)
        outs = [Tensor(a, stop_gradient=True) if isinstance(a, jax.Array)
                else a for a in flat_out]
        return jax.tree_util.tree_unflatten(out_box["treedef"], outs)

    def _sig_desc(self, sig):
        """Compile-watch signature descriptor: the user-input shapes
        (the churn the storm diagnosis must name) plus the remaining
        cache-key components as labeled pseudo-args."""
        shapes, tree, training, grads, amp_key = sig
        desc = []
        for i, s in enumerate(shapes):
            if isinstance(s[0], tuple):
                desc.append(
                    (f"arg{i}",
                     f"{s[1]}[{','.join(str(d) for d in s[0])}]"))
            else:
                desc.append((f"arg{i}", f"{s[0]}={s[1]!r}"))
        desc.append(("training", str(training)))
        desc.append(("grads", str(grads)))
        desc.append(("amp", str(amp_key)))
        desc.append(("tree", tree))
        return tuple(desc)

    def _dispatch(self, sig, jitted, step_args):
        """Run the compiled step. With metrics enabled, the first call
        per signature compiles ahead-of-time through the compile watcher
        (exact compile count + duration + static cost/memory analysis)
        and later calls dispatch the cached executable; with
        ``PADDLE_TPU_METRICS=0`` this is exactly ``jitted(*step_args)``
        — the jit cache path untouched, byte-identical, sync-free."""
        from ..observability import compile_watch as _cw

        if not _cw.enabled():
            return jitted(*step_args)
        if _cw._in_outer_trace():
            # inside an outer trace only the plain jit path composes
            # (an AOT executable cannot take tracers)
            return jitted(*step_args)
        compiled = self._aot.get(sig)
        if compiled is None:
            if sig in self._aot:
                # state avals drifted earlier (see below): bail before
                # touching the watch lock or building the descriptor —
                # this runs per dispatch on the hot path
                return jitted(*step_args)
            compiled = self._aot[sig] = _cw.watch(self._watch_name) \
                .aot_compile(jitted, step_args, desc=self._sig_desc(sig))
        try:
            return compiled(*step_args)
        except _cw.AOT_MISMATCH_ERRORS:
            # the cache signature tracks user inputs, not state avals: a
            # state drift the signature can't see (the model cast to a
            # new dtype, a resharded parameter) mismatches the AOT
            # executable's fixed input types/shardings. jax.jit retraces
            # such drift transparently — stop AOT-ing this signature and
            # let the plain path own it
            self._aot[sig] = None
            return jitted(*step_args)

    @property
    def code(self):
        import inspect
        return inspect.getsource(self._fn)

    def rollback(self):
        return self._fn


def to_static(function=None, input_spec=None, build_strategy=None,
              backend=None, state=None, full_graph=True,
              warmup="per-signature", name=None, donate_inputs=False,
              **kwargs):
    """Decorator/wrapper: compile an imperative step into one XLA program.

    ``state`` optionally lists Layers/Optimizers/Tensors the function
    mutates (auto-discovered from the closure when omitted). Matches the
    reference's ``paddle.jit.to_static`` call shapes: bare decorator,
    decorator-with-args, and direct wrapping of a Layer.

    ``warmup="once"``: only the first call runs eagerly (to materialize
    optimizer accumulators); later unseen shapes compile directly. Use when
    the eager pass at full shape would exceed HBM (eager holds every
    intermediate; the compiled program lets XLA schedule memory).

    ``donate_inputs=True`` additionally donates the call's INPUT buffers
    to XLA (e.g. a train step's ids/labels: their HBM is reusable as
    workspace the moment the embedding gather read them). Only safe when
    every call gets fresh inputs — a caller re-feeding the same device
    batch would dispatch donated (invalidated) buffers.
    """
    def wrap(fn):
        from ..nn import Layer
        if isinstance(fn, Layer):
            layer = fn
            sf = StaticFunction(layer.forward, input_spec=input_spec,
                                state=[layer] + list(state or ()),
                                warmup=warmup,
                                donate_inputs=donate_inputs,
                                name=name or type(layer).__name__)
            layer.forward = sf
            return layer
        return StaticFunction(fn, input_spec=input_spec, state=state,
                              warmup=warmup, donate_inputs=donate_inputs,
                              name=name)
    if function is not None:
        return wrap(function)
    return wrap


def not_to_static(fn):
    fn._not_to_static = True
    return fn


def ignore_module(modules):
    pass


from .serialization import save, load, TranslatedLayer  # noqa: F401,E402
