"""Compile & device-memory observability: the XLA compile watcher.

The two things that dominate TPU behavior — XLA compilation and device
memory — are invisible to host-side spans: a silent recompile storm in
``jit.to_static`` or ``LlamaModel.generate`` looks identical to slow
hardware, and an OOM leaves no record of what was resident. This module
is the single choke-point every framework-owned ``jax.jit`` entry
compiles through:

- :class:`CompileWatch` — per-callable compile accounting. The first
  dispatch of a new signature compiles ahead-of-time
  (``jitted.lower(...).compile()``) so the watcher gets the exact
  compile count, a wall-clock duration histogram, and the program's
  static ``cost_analysis`` / ``memory_analysis`` (FLOPs, bytes
  accessed, peak temp memory) — no double compile, because the
  returned executable IS what the caller dispatches afterwards.
- Recompile-storm detection: when a callable exceeds N distinct
  signatures (``PADDLE_TPU_RECOMPILE_STORM_SIGS``, default 8) a storm
  counter fires with a one-line diagnosis naming the churning argument
  shapes/dtypes.
- :func:`watched_jit` — drop-in ``jax.jit`` replacement for raw jit
  entries (the compiled pipeline schedule) that routes through the same
  watcher.
- A ``jax.monitoring`` listener tallies EVERY backend compile in the
  process (``paddle_tpu_xla_backend_compile_total``) — the catch-all
  that surfaces compile churn outside the framework's own entries.
- :func:`sample_device_memory` — live-bytes/peak gauges from
  ``device.memory_stats`` + ``jax.live_arrays()`` (metadata only, no
  device sync), sampled per hapi step and per serving wave.

Everything honors the PR-1 kill switch: with ``PADDLE_TPU_METRICS=0``
:func:`watch` returns a shared no-op, callers skip the AOT path, and
dispatch stays byte-identical to the unwatched ``jax.jit`` fast path.
"""

from __future__ import annotations

import itertools
import json
import os
import sys
import threading
import time
import weakref
from collections import deque

from . import metrics as om
from .metrics import enabled
from .trace import _EPOCH

__all__ = [
    "CompileWatch", "NULL_WATCH", "watch", "watched_jit", "describe_args",
    "sample_device_memory", "recent_compile_events", "reset",
    "COMPILE_BUCKETS", "DEFAULT_STORM_THRESHOLD",
    "enable_persistent_cache", "persistent_cache_stats",
    "SignatureRegistry", "shape_registry",
]

#: compile-duration buckets: 10ms (tiny CPU programs) .. 300s (big TPU
#: programs); the PR-1 latency defaults top out at 10s — too short
COMPILE_BUCKETS = (0.01, 0.05, 0.1, 0.25, 0.5, 1.0, 2.5, 5.0, 15.0,
                   60.0, 300.0)

DEFAULT_STORM_THRESHOLD = 8

_BACKEND_COMPILE_EVENT = "/jax/core/compile/backend_compile_duration"

#: what ``jax.stages.Compiled.__call__`` raises when the concrete args
#: no longer match the executable's fixed signature: TypeError for
#: shape/dtype/pytree drift, ValueError for sharding/layout drift. Every
#: AOT dispatch site catches exactly this tuple and falls back to the
#: plain jit path (which retraces such drift transparently) — no Python
#: user code runs inside the compiled call, so these cannot mask a user
#: error.
AOT_MISMATCH_ERRORS = (TypeError, ValueError)

_lock = threading.Lock()
_watches: dict[str, "CompileWatch"] = {}
_listener_installed = False
#: bounded ring of recent compile events (dicts) for the flight recorder
_events: deque = deque(maxlen=512)
#: name of the program currently compiling in this thread (enriches the
#: listener's flight-recorder entries; carries no metric state)
_tls = threading.local()


def storm_threshold():
    """Distinct-signature count past which a callable is a recompile
    storm (env ``PADDLE_TPU_RECOMPILE_STORM_SIGS``, checked per compile
    so tests can tune it)."""
    try:
        return int(os.environ.get("PADDLE_TPU_RECOMPILE_STORM_SIGS",
                                  DEFAULT_STORM_THRESHOLD))
    except ValueError:
        return DEFAULT_STORM_THRESHOLD


def _note_event(event):
    # deque.append alone is atomic, but the flight recorder snapshots
    # the ring with list() mid-crash — an unlocked append from a serving
    # thread compiling a new burst would raise "deque mutated during
    # iteration" and cost the bundle its compile history
    with _lock:
        _events.append(event)


def recent_compile_events():
    """Recent compile events (newest last) — the flight recorder's
    compile log."""
    with _lock:
        return list(_events)


def reset():
    """Drop all per-callable signature state, the event ring, and the
    memory-sample throttle/high-water (test isolation; production code
    never needs this)."""
    global _mem_peak
    with _lock:
        _watches.clear()
        _events.clear()
    _mem_last.clear()
    _mem_peak = 0


def _ensure_listener():
    """Register the process-wide ``jax.monitoring`` listeners once: every
    XLA backend compile — watched or not — lands in the global tally and
    the flight-recorder ring, and persistent-compilation-cache hit/miss
    events land in the warm-restart counters. A registration failure (a
    jax build without the API) degrades to per-callable counting only —
    it must never crash the user's first compiled step."""
    global _listener_installed
    with _lock:
        if _listener_installed:
            return
        _listener_installed = True
    try:
        import jax.monitoring

        jax.monitoring.register_event_duration_secs_listener(_on_jax_event)
        jax.monitoring.register_event_listener(_on_jax_count_event)
    except Exception:
        pass


def _on_jax_event(name, duration, **kwargs):
    if name != _BACKEND_COMPILE_EVENT or not enabled():
        return
    om.counter("paddle_tpu_xla_backend_compile_total",
               "XLA backend compiles in this process (all sources)").inc()
    om.histogram("paddle_tpu_xla_backend_compile_seconds",
                 "XLA backend compile duration (all sources)",
                 buckets=COMPILE_BUCKETS).observe(duration)
    _note_event({
        "kind": "backend_compile",
        "name": getattr(_tls, "current", None) or "(unattributed)",
        "ts": (time.perf_counter() - _EPOCH) * 1e6 - duration * 1e6,
        "dur": duration * 1e6,
    })


#: raw persistent-cache tallies — kept as plain ints alongside the
#: metric counters so a replica worker can report its warm-start hit
#: rate over rpc even under ``PADDLE_TPU_METRICS=0``
_cache_counts = {"hits": 0, "misses": 0}


def _on_jax_count_event(name, **kwargs):
    """Count-event listener: the persistent compilation cache announces
    ``/jax/compilation_cache/cache_hits`` / ``.../cache_misses`` per
    lookup — the signal that says whether a restarted replica's compiles
    were served from disk (seconds) or paid in full (~19 s on a real
    chip)."""
    if "/jax/compilation_cache/cache_hit" in name:
        _cache_counts["hits"] += 1
        if enabled():
            om.counter("compile_cache_hit_total",
                       "XLA programs served from the persistent "
                       "compilation cache").inc()
    elif "/jax/compilation_cache/cache_miss" in name:
        _cache_counts["misses"] += 1
        if enabled():
            om.counter("compile_cache_miss_total",
                       "XLA programs compiled from scratch (persistent "
                       "cache lookup missed)").inc()


def persistent_cache_stats():
    """``{"hits", "misses", "dir"}`` for this process — independent of
    the metrics kill switch so workers can report warm-start health."""
    return {"hits": _cache_counts["hits"],
            "misses": _cache_counts["misses"],
            "dir": _cache_dir}


_cache_dir = None
_cache_lock = threading.Lock()


#: the checkout's own cache, used when ``JAX_COMPILATION_CACHE_DIR``
#: does not place it: a fixed path (the path is part of the cache key,
#: so a directory that moves never hits), git-ignored
_CHECKOUT_CACHE = os.path.join(
    os.path.dirname(os.path.dirname(os.path.dirname(
        os.path.abspath(__file__)))), ".jax_cache")


def default_cache_dir():
    """Persistent-cache location: ``JAX_COMPILATION_CACHE_DIR`` (jax's
    own variable) or the fixed ``<checkout>/.jax_cache``."""
    return os.environ.get("JAX_COMPILATION_CACHE_DIR") or _CHECKOUT_CACHE


def enable_persistent_cache():
    """Wire JAX's persistent compilation cache (ROADMAP item 5: kill the
    ~19 s cold start). Every backend compile is keyed by its HLO and
    stored in :func:`default_cache_dir`; a fresh process re-compiling
    the same programs (serving mixed-step shapes, decode scans, a
    ``jit.to_static`` train step) gets executables back in seconds.
    Called by the serving engine and by the ``jit.to_static``/hapi train
    step alike — set ``PADDLE_TPU_COMPILE_CACHE=0`` to opt out, or
    ``JAX_COMPILATION_CACHE_DIR`` to place it (jax reads that variable
    itself, so no directory is set in code then; replicas sharing a
    host should share the directory). ``min_compile_time_secs`` is
    forced to 0 so even small programs cache — elastic restart is about
    the SUM of compiles, not the largest one.

    Returns the cache directory, or None when disabled or unwritable.
    Idempotent; hit/miss land in ``compile_cache_hit_total`` /
    ``compile_cache_miss_total`` and :func:`persistent_cache_stats`."""
    global _cache_dir
    if os.environ.get("PADDLE_TPU_COMPILE_CACHE", "1").lower() \
            in ("0", "off", "false"):
        return None
    with _cache_lock:
        if _cache_dir is not None:
            return _cache_dir
        import jax
        from jax.experimental.compilation_cache import \
            compilation_cache as _jcc

        cache = default_cache_dir()
        try:
            os.makedirs(cache, exist_ok=True)
        except OSError:
            return None     # unwritable location: run uncached
        if not os.environ.get("JAX_COMPILATION_CACHE_DIR"):
            jax.config.update("jax_compilation_cache_dir", cache)
        jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
        jax.config.update("jax_persistent_cache_min_entry_size_bytes", -1)
        # a program compiled BEFORE this config landed makes jax latch
        # "no cache" and ignore the directory from then on; reset
        # re-arms the lazy init so the next compile picks it up
        _jcc.reset_cache()
        _cache_dir = cache
    _ensure_listener()
    return _cache_dir


class SignatureRegistry:
    """Durable record of the shape signatures a named callable compiled
    — the compile watcher's in-memory ``_sigs``, persisted so the NEXT
    process knows what to pre-warm before traffic arrives.

    The file is JSON ``{key: {kind: [values]}}`` where ``key`` names one
    compile surface (the serving engine hashes its model dims + batch
    geometry into it) and each ``kind`` collects the distinct values
    seen (mixed-program token shapes, decode-scan tick counts, ...). Writes are
    read-merge-replace with a write-aside temp file, mirroring the
    FileStore stamp protocol, so concurrent replicas on one host can
    record without tearing the file (a lost race drops one record until
    its next compile re-records it — never corruption)."""

    def __init__(self, path):
        self.path = path
        self._lock = threading.Lock()

    def _load(self):
        try:
            with open(self.path) as f:
                doc = json.load(f)
            return doc if isinstance(doc, dict) else {}
        except (OSError, ValueError):
            return {}

    def record(self, key, kind, value):
        """Merge one (key, kind, value) into the registry. Returns True
        when the value was new for that key/kind."""
        with self._lock:
            doc = self._load()
            kinds = doc.setdefault(str(key), {})
            vals = kinds.setdefault(str(kind), [])
            if value in vals:
                return False
            vals.append(value)
            vals.sort()
            tmp = f"{self.path}.{os.getpid()}.tmp"
            try:
                os.makedirs(os.path.dirname(self.path) or ".",
                            exist_ok=True)
                with open(tmp, "w") as f:
                    json.dump(doc, f, indent=0, sort_keys=True)
                os.replace(tmp, self.path)
            except OSError:
                try:
                    os.unlink(tmp)
                except OSError:
                    pass
                return False
            return True

    def lookup(self, key):
        """``{kind: [values]}`` recorded for ``key`` (empty when none)."""
        with self._lock:
            return self._load().get(str(key), {})


_shape_registry = None


def shape_registry():
    """The process-default :class:`SignatureRegistry`
    (``PADDLE_TPU_SHAPE_REGISTRY`` or ``<cache_dir>/serving_shapes.json``
    next to the persistent compile cache, so replicas sharing the cache
    share the warm-up recipe)."""
    global _shape_registry
    with _cache_lock:
        if _shape_registry is None:
            path = os.environ.get("PADDLE_TPU_SHAPE_REGISTRY") \
                or os.path.join(default_cache_dir(), "serving_shapes.json")
            _shape_registry = SignatureRegistry(path)
        return _shape_registry


def _in_outer_trace():
    """True when this thread is inside an active jax trace (grad/vjp/an
    enclosing jit) — only the plain jit path composes there. O(1): the
    per-dispatch guard must not walk the model state."""
    import jax

    return not jax.core.trace_ctx.is_top_level()


def _arg_key(args, kwargs=None):
    """Cheap hashable cache key over the call: raw (shape, dtype)
    tuples per leaf plus the pytree structure — no string formatting,
    because this runs on EVERY watched dispatch (the pipeline train
    step's hot path). The treedef matters: ``f(x, s=2.0)`` and
    ``f(x, 2.0)`` carry identical leaves but bind differently, and
    sharing a cache entry would dispatch the wrong executable. Default
    flattening (no is_leaf): custom registered pytree containers
    decompose into their array leaves instead of being identity-hashed
    as opaque leaves (which would mint a fresh signature per instance),
    and ``None`` placement is captured by the treedef. Returns None when
    a leaf is unhashable (the caller skips watching)."""
    import jax

    leaves, treedef = jax.tree_util.tree_flatten((args, kwargs or {}))
    out = [("~tree", treedef)]
    for leaf in leaves:
        shape = getattr(leaf, "shape", None)
        dtype = getattr(leaf, "dtype", None)
        if shape is not None and dtype is not None:
            out.append((tuple(shape), str(dtype)))
        elif isinstance(leaf, (bool, int, float, complex)):
            # jax.jit traces Python scalars as weak-typed values — one
            # compile per TYPE; keying on the value would AOT-compile an
            # identical program per distinct scalar (and trip the storm
            # alarm on a changing learning rate)
            out.append(("~weak", type(leaf).__name__))
        else:
            try:
                hash(leaf)
            except TypeError:
                return None
            out.append(("~static", leaf))
    return tuple(out)


def _key_desc(key):
    """Render an :func:`_arg_key` into the labeled string descriptor the
    storm diagnosis names args by — built only on compile, never on the
    dispatch hot path."""
    out = []
    for i, k in enumerate(key):
        tag, val = k
        if tag == "~tree":
            out.append(("tree", str(val)))
        elif tag == "~weak":
            out.append((f"arg{i - 1}", f"weak_{val}"))
        elif tag == "~static":
            out.append((f"arg{i - 1}", f"{type(val).__name__}={val!r}"))
        else:
            out.append((f"arg{i - 1}",
                        f"{val}[{','.join(str(int(s)) for s in tag)}]"))
    return tuple(out)


def describe_args(args, kwargs=None):
    """Labeled signature descriptor for storm diagnosis — ``("arg0",
    "float32[4,8]")`` for arrays, ``("arg1", "weak_float")`` for Python
    scalars. None when a leaf is unhashable."""
    key = _arg_key(args, kwargs)
    return None if key is None else _key_desc(key)


class _NullWatch:
    """Shared no-op watch returned when metrics are disabled — keeps
    call chains valid at zero cost."""

    __slots__ = ()

    def aot_compile(self, jitted, args, kwargs=None, desc=None):
        return jitted.lower(*args, **(kwargs or {})).compile()

    def timed_first_dispatch(self, jitted, args, kwargs=None, desc=None):
        return jitted(*args, **(kwargs or {}))

    def observe_signature(self, desc):
        pass

    def record_compile(self, duration, desc=None, compiled=None):
        pass

    @property
    def last_diagnosis(self):
        return None


NULL_WATCH = _NullWatch()


class CompileWatch:
    """Compile accounting for ONE named callable.

    Metric families (all labeled ``callable``), created on the default
    registry at record time so registry clears between tests cannot
    orphan children:

    - ``paddle_tpu_xla_compile_total`` — programs compiled
    - ``paddle_tpu_xla_compile_seconds`` — compile duration histogram
    - ``paddle_tpu_xla_distinct_signatures`` — distinct signatures seen
    - ``paddle_tpu_xla_recompile_storm_total`` — new signatures past the
      storm threshold
    - ``paddle_tpu_xla_program_flops`` / ``..._program_bytes_accessed``
      / ``..._program_peak_temp_bytes`` — static analysis of the most
      recently compiled program
    """

    def __init__(self, name):
        self.name = name
        self._sigs: dict[tuple, int] = {}
        self._storm_announced = False
        self.last_diagnosis = None
        self._lock = threading.Lock()

    # -- metric handles (re-resolved per record: compiles are rare) -----
    def _m(self, kind):
        if kind == "compiles":
            fam = om.counter("paddle_tpu_xla_compile_total",
                             "XLA programs compiled per callable",
                             labelnames=("callable",))
        elif kind == "seconds":
            fam = om.histogram("paddle_tpu_xla_compile_seconds",
                               "XLA compile duration per callable",
                               labelnames=("callable",),
                               buckets=COMPILE_BUCKETS)
        elif kind == "sigs":
            fam = om.gauge("paddle_tpu_xla_distinct_signatures",
                           "distinct compile signatures per callable",
                           labelnames=("callable",))
        elif kind == "storms":
            fam = om.counter(
                "paddle_tpu_xla_recompile_storm_total",
                "new signatures past the recompile-storm threshold",
                labelnames=("callable",))
        elif kind == "flops":
            fam = om.gauge("paddle_tpu_xla_program_flops",
                           "cost_analysis FLOPs of the last compiled "
                           "program", labelnames=("callable",))
        elif kind == "bytes":
            fam = om.gauge("paddle_tpu_xla_program_bytes_accessed",
                           "cost_analysis bytes accessed of the last "
                           "compiled program", labelnames=("callable",))
        else:
            fam = om.gauge("paddle_tpu_xla_program_peak_temp_bytes",
                           "memory_analysis peak temp bytes of the last "
                           "compiled program", labelnames=("callable",))
        return fam.labels(self.name)

    # -- signature bookkeeping ------------------------------------------
    def observe_signature(self, desc):
        """Track one (possibly new) signature; fires the storm counter +
        one-line diagnosis when the callable exceeds the threshold."""
        if desc is None:
            return
        announce = None
        with self._lock:
            if desc in self._sigs:
                self._sigs[desc] += 1
                return
            self._sigs[desc] = 1
            n = len(self._sigs)
            self._m("sigs").set(n)
            if n > storm_threshold():
                self._m("storms").inc()
                self.last_diagnosis = self._diagnose(n)
                if not self._storm_announced:
                    self._storm_announced = True
                    announce = self.last_diagnosis
        if announce:
            print(announce, file=sys.stderr)

    def _diagnose(self, n):
        """One line naming the churning argument shapes/dtypes."""
        by_label: dict[str, set] = {}
        order: list[str] = []
        for desc in self._sigs:
            for label, value in desc:
                if label not in by_label:
                    by_label[label] = set()
                    order.append(label)
                by_label[label].add(value)
        churn = ", ".join(
            f"{label} churns {len(by_label[label])} variants "
            f"({' | '.join(sorted(by_label[label])[:4])}"
            f"{', ...' if len(by_label[label]) > 4 else ''})"
            for label in order if len(by_label[label]) > 1)
        return (f"[compile_watch] recompile storm: {self.name!r} has "
                f"{n} distinct signatures "
                f"(threshold {storm_threshold()}); "
                f"{churn or 'churn outside tracked args'}")

    # -- the compile choke-point ----------------------------------------
    def aot_compile(self, jitted, args, kwargs=None, desc=None):
        """Lower + compile ``jitted`` for these concrete args, recording
        count, duration, and cost/memory analysis. Returns the compiled
        executable (dispatch it for all later same-signature calls).
        Whatever ``jax.jit`` can run it can lower, so nothing is caught
        here: a refusal — of the tracer, of Mosaic, of the device's
        memory — propagates with its message."""
        kwargs = kwargs or {}
        _ensure_listener()
        self.observe_signature(desc)
        _tls.current = self.name
        t0 = time.perf_counter()
        try:
            compiled = jitted.lower(*args, **kwargs).compile()
        finally:
            _tls.current = None
        dur = time.perf_counter() - t0
        self.record_compile(dur, desc=desc, compiled=compiled)
        return compiled

    def timed_first_dispatch(self, jitted, args, kwargs=None, desc=None):
        """For a jit with static arguments (whose ``Compiled`` takes a
        different call shape): dispatch through the jit wrapper and
        record its first-call wall time as the compile duration
        (over-counts by one execution — honest upper bound)."""
        _ensure_listener()
        self.observe_signature(desc)
        _tls.current = self.name
        t0 = time.perf_counter()
        try:
            out = jitted(*args, **(kwargs or {}))
        finally:
            _tls.current = None
        self.record_compile(time.perf_counter() - t0, desc=desc)
        return out

    def record_compile(self, duration, desc=None, compiled=None):
        """Record one compile of this callable (counter + histogram +
        static program analysis when the executable is given)."""
        self._m("compiles").inc()
        self._m("seconds").observe(duration)
        event = {
            "kind": "compile",
            "name": self.name,
            "ts": (time.perf_counter() - _EPOCH) * 1e6 - duration * 1e6,
            "dur": duration * 1e6,
        }
        if desc:
            event["signature"] = "; ".join(f"{k}={v}" for k, v in desc)
        if compiled is not None:
            flops, nbytes, temp = self._analyze(compiled)
            if flops is not None:
                self._m("flops").set(flops)
                event["flops"] = flops
            if nbytes is not None:
                self._m("bytes").set(nbytes)
                event["bytes_accessed"] = nbytes
            if temp is not None:
                self._m("temp").set(temp)
                event["peak_temp_bytes"] = temp
        _note_event(event)

    @staticmethod
    def _analyze(compiled):
        """(flops, bytes_accessed, peak_temp_bytes) from the executable's
        static analyses; None per field where the backend doesn't
        report."""
        flops = nbytes = temp = None
        try:
            ca = compiled.cost_analysis()
            if isinstance(ca, (list, tuple)):
                ca = ca[0] if ca else {}
            if ca:
                flops = float(ca.get("flops", float("nan")))
                flops = None if flops != flops else flops
                nbytes = float(ca.get("bytes accessed", float("nan")))
                nbytes = None if nbytes != nbytes else nbytes
        except Exception:
            pass
        try:
            ma = compiled.memory_analysis()
            if ma is not None:
                temp = float(getattr(ma, "temp_size_in_bytes", None))
        except Exception:
            temp = None
        return flops, nbytes, temp


def watch(name):
    """The process-wide :class:`CompileWatch` for ``name`` (a no-op
    watch under ``PADDLE_TPU_METRICS=0`` — checked per call so tests can
    toggle the environment)."""
    if not enabled():
        return NULL_WATCH
    with _lock:
        w = _watches.get(name)
        if w is None:
            w = _watches[name] = CompileWatch(name)
        return w


def _static_arg_key(args, kwargs, static_nums, static_names):
    """Cache key for a jit with static arguments: static positions key
    by VALUE (each distinct value is its own program, exactly jit's
    cache rule), dynamic ones by the usual shape/dtype key. None when a
    static value is unhashable (jit itself would reject it)."""
    key = []
    for i, a in enumerate(args):
        if i in static_nums:
            try:
                hash(a)
            except TypeError:
                return None
            key.append(("~staticval", a))
        else:
            sub = _arg_key((a,))
            if sub is None:
                return None
            key.append(sub)
    for k in sorted(kwargs):
        v = kwargs[k]
        if k in static_names:
            try:
                hash(v)
            except TypeError:
                return None
            key.append((k, "~staticval", v))
        else:
            sub = _arg_key((v,))
            if sub is None:
                return None
            key.append((k, sub))
    return tuple(key)


def watched_jit(fun, name=None, **jit_kwargs):
    """``jax.jit`` with compile observability: each new call signature
    compiles through :meth:`CompileWatch.aot_compile` (counted, timed,
    cost-analyzed), later calls dispatch the cached executable. Under
    ``PADDLE_TPU_METRICS=0`` every call takes the plain jit fast path —
    byte-identical dispatch, no signature hashing.

    With ``static_argnums``/``static_argnames`` the AOT path is skipped
    (a ``jax.stages.Compiled`` takes only the dynamic arguments, so
    dispatching it with the original call shape would mismatch and
    double-compile); those functions dispatch plain jit, with compiles
    counted per distinct static-value signature via the timed first
    dispatch."""
    import functools

    import jax

    jitted = jax.jit(fun, **jit_kwargs)
    watch_name = name or getattr(fun, "__qualname__", None) or repr(fun)
    cache: dict[tuple, object] = {}
    nums = jit_kwargs.get("static_argnums")
    names = jit_kwargs.get("static_argnames")
    static_nums = frozenset((nums,) if isinstance(nums, int)
                            else nums or ())
    static_names = frozenset((names,) if isinstance(names, str)
                             else names or ())
    has_statics = bool(static_nums or static_names)

    @functools.wraps(fun)
    def wrapper(*args, **kwargs):
        if not enabled():
            return jitted(*args, **kwargs)
        if _in_outer_trace():
            # called inside an outer trace (grad/vjp/an enclosing jit):
            # an AOT executable cannot take tracers, but jit composes —
            # it inlines into the outer program (no separate compile to
            # watch here; the OUTER program's watcher accounts for it)
            return jitted(*args, **kwargs)
        if has_statics:
            key = _static_arg_key(args, kwargs, static_nums,
                                  static_names)
            if key is None or key in cache:
                return jitted(*args, **kwargs)
            cache[key] = None   # counted once; plain jit owns dispatch
            desc = tuple((f"arg{i}", repr(k))
                         for i, k in enumerate(key))
            return watch(watch_name).timed_first_dispatch(
                jitted, args, kwargs, desc=desc)
        key = _arg_key(args, kwargs)
        if key is None:         # unhashable static leaf: unwatchable
            return jitted(*args, **kwargs)
        compiled = cache.get(key)
        if compiled is None:
            if key in cache:    # avals drifted earlier: plain jit owns it
                return jitted(*args, **kwargs)
            compiled = cache[key] = watch(watch_name).aot_compile(
                jitted, args, kwargs, desc=_key_desc(key))
        try:
            return compiled(*args, **kwargs)
        except AOT_MISMATCH_ERRORS:
            # aval drift the key cannot see (weak->strong type, a
            # sharding change): plain jit retraces transparently — stop
            # AOT-ing this signature rather than crash
            cache[key] = None
            return jitted(*args, **kwargs)

    wrapper._watch_name = watch_name
    wrapper._jitted = jitted
    return wrapper


# ---------------------------------------------------------------------------
# device-memory accounting
# ---------------------------------------------------------------------------
_mem_seq = itertools.count()
#: per-registry throttle clocks — one hot sampler (the serving wave into
#: the default registry) must not starve another registry's gauges.
#: Weak keys: a GC'd registry must neither leak its entry nor bequeath
#: its clock to a new registry reusing the same address (id() would)
_mem_last: "weakref.WeakKeyDictionary" = weakref.WeakKeyDictionary()
#: sampler high-water of bytes_in_use, for backends that report no peak
_mem_peak = 0


def sample_device_memory(registry=None, device=None, min_interval=0.0):
    """Publish live-bytes/peak gauges from ``device.memory_stats`` and
    ``jax.live_arrays()`` — metadata walks only, no device sync. Called
    per hapi train step and per serving wave; returns the sampled dict,
    or None under ``PADDLE_TPU_METRICS=0`` (nothing touched).

    ``min_interval`` (seconds) throttles the live-array walk: hot call
    sites (a decode step per token) pass ~1s so the O(live arrays)
    enumeration never rides the latency path; a throttled call returns
    None without touching anything. The first call per registry always
    samples, and the throttle is per registry."""
    if not enabled():
        return None
    global _mem_peak
    reg = registry if registry is not None else om.default_registry()
    if min_interval:
        now = time.monotonic()
        if now - _mem_last.get(reg, -float(min_interval)) \
                < min_interval:
            return None
        _mem_last[reg] = now
    import jax

    from .. import device as device_mod

    live = jax.live_arrays()
    # hand the walked list to memory_stats: its CPU fallback sums live
    # arrays too, and the sampler must not pay the enumeration twice
    stats = device_mod.memory_stats(device, live_arrays=live)
    in_use = int(stats.get("bytes_in_use", 0))
    live_bytes = sum(int(x.nbytes) for x in live)
    sample = {
        "bytes_in_use": in_use,
        "live_array_bytes": live_bytes,
        "live_array_count": len(live),
        "source": stats.get("source", "allocator"),
        "sample_seq": next(_mem_seq),
    }
    reg.gauge("paddle_tpu_device_bytes_in_use",
              "allocator bytes in use on the default device").set(in_use)
    reg.gauge("paddle_tpu_live_array_bytes",
              "total bytes of live jax arrays in this process") \
        .set(live_bytes)
    reg.gauge("paddle_tpu_live_array_count",
              "live jax arrays in this process").set(len(live))
    peak = stats.get("peak_bytes_in_use")
    if peak is None:
        # no allocator peak (the CPU backend): the sampler's own
        # high-water — derived from the stats already fetched, not a
        # second memory_stats() walk
        _mem_peak = max(_mem_peak, in_use)
        peak = _mem_peak
    sample["peak_bytes_in_use"] = int(peak)
    reg.gauge("paddle_tpu_device_peak_bytes_in_use",
              "allocator peak bytes in use (sampler high-water when the "
              "backend does not report a peak)").set(int(peak))
    limit = stats.get("bytes_limit")
    if limit is not None:
        sample["bytes_limit"] = int(limit)
        reg.gauge("paddle_tpu_device_bytes_limit",
                  "allocator byte limit reported by the backend") \
            .set(int(limit))
    return sample
