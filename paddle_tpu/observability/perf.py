"""Performance attribution: per-callable roofline gauges, an EWMA perf
sentinel, and on-demand profiler capture.

The compile watcher already holds every AOT executable plus its static
``cost_analysis`` (FLOPs, bytes accessed). This module pairs that with
*measured* per-dispatch device time to answer "where does device time
go, and is this callable near its roofline?":

- every watched dispatch (``StaticFunction._dispatch``, ``watched_jit``)
  pays one cheap host-side timer and feeds :func:`note_dispatch`;
- on a per-callable throttle (``PADDLE_TPU_PERF_FENCE_INTERVAL``
  seconds, default 0.5; ``0`` fences every call) the timed window is
  extended through ``jax.block_until_ready`` — a *true* device-time
  sample, since an unfenced dispatch returns at enqueue;
- each fenced sample publishes the roofline gauges against the
  per-device-kind peak table (:data:`PEAKS`):
  ``paddle_tpu_perf_device_ms{callable}``,
  ``paddle_tpu_perf_attained_flops_frac{callable}`` (measured FLOP/s as
  a fraction of peak — MFU per callable) and
  ``paddle_tpu_perf_attained_hbm_bw_frac{callable}`` (attained HBM
  bandwidth fraction);
- an EWMA perf sentinel per callable (fast vs slow EWMA of fenced
  device time) counts sustained regressions — e.g. a recompile-storm
  slowdown — on ``paddle_tpu_perf_regressions_total{callable}`` and
  flight-records a diagnosis bundle (rate-limited).

Everything obeys ``PADDLE_TPU_METRICS=0`` (the watched dispatch paths
never reach this module then); ``PADDLE_TPU_PERF=0`` turns off just the
attribution layer while the rest of observability stays on.

:func:`capture_local` is the per-process half of cluster-wide on-demand
profiler capture (``/debug/profile?seconds=N`` /
``ServingCluster.capture_profile``): it runs a ``jax.profiler`` trace
over a window while the caller keeps serving, harvests any chrome-trace
events the device profiler wrote, and returns a span-shard document the
PR-17 merge machinery (:func:`~.tracing.merge_shards`) aligns into one
Perfetto-loadable bundle.
"""

from __future__ import annotations

import glob
import gzip
import json
import os
import shutil
import tempfile
import threading
import time

from . import metrics as _om
from .metrics import enabled as _metrics_enabled

__all__ = [
    "PEAKS", "enabled", "device_peaks", "note_dispatch", "observe",
    "recorders", "reset", "build_info", "ensure_build_info",
    "capture_local", "capture_bundle",
]

#: (peak FLOP/s, peak HBM bytes/s) per chip, keyed by
#: ``jax.devices()[0].device_kind`` — the bf16 MXU peak and the HBM
#: bandwidth Google Cloud's TPU documentation publishes for each
#: generation ("TPU v5e": 197 TFLOP/s, 819 GB/s; likewise the v2, v3,
#: v4, v5p and v6e system-architecture pages). A kind that is not here
#: gets device time but no roofline fraction. The CPU row is nominal
#: (the CPU tests exercise the fraction gauges through it; ROADMAP S0
#: settles it).
PEAKS = {
    "TPU v2": (46e12, 700e9),
    "TPU v3": (123e12, 900e9),
    "TPU v4": (275e12, 1228e9),
    "TPU v5 lite": (197e12, 819e9),
    "TPU v5e": (197e12, 819e9),
    "TPU v5": (459e12, 2765e9),
    "TPU v5p": (459e12, 2765e9),
    "TPU v6 lite": (918e12, 1640e9),
    "TPU v6e": (918e12, 1640e9),
    "cpu": (1e12, 50e9),
}

#: EWMA smoothing: fast tracks the last few fenced samples, slow is the
#: baseline the sentinel compares against
_ALPHA_FAST = 0.5
_ALPHA_SLOW = 0.05
#: fenced samples before the sentinel arms (the slow EWMA must have a
#: baseline before a ratio test means anything)
_SENTINEL_MIN = 8
#: seconds between flight-recorder dumps per callable (the counter
#: still ticks every sustained regression)
_DUMP_INTERVAL = 60.0


def enabled():
    """Attribution is on when metrics are on, unless ``PADDLE_TPU_PERF=0``
    (checked per call so tests/benches can toggle the environment)."""
    return (_metrics_enabled()
            and os.environ.get("PADDLE_TPU_PERF", "1") != "0")


def _fence_interval():
    raw = os.environ.get("PADDLE_TPU_PERF_FENCE_INTERVAL")
    if not raw:
        return 0.5
    try:
        return max(0.0, float(raw))
    except ValueError:
        return 0.5


def _sentinel_ratio():
    raw = os.environ.get("PADDLE_TPU_PERF_SENTINEL_RATIO")
    try:
        return float(raw) if raw else 1.5
    except ValueError:
        return 1.5


def _sentinel_k():
    raw = os.environ.get("PADDLE_TPU_PERF_SENTINEL_K")
    try:
        return max(1, int(raw)) if raw else 4
    except ValueError:
        return 4


# ---------------------------------------------------------------------------
# peak table
# ---------------------------------------------------------------------------
_peaks_lock = threading.Lock()
_peaks_cache = None


def device_peaks():
    """``(peak_flops_per_s, peak_hbm_bytes_per_s, device_kind)`` for the
    default device, from :data:`PEAKS`; both peaks are None for a kind
    the table does not hold. Cached after the first (device-touching)
    call."""
    global _peaks_cache
    with _peaks_lock:
        if _peaks_cache is None:
            import jax

            kind = str(jax.devices()[0].device_kind)
            _peaks_cache = PEAKS.get(kind, (None, None)) + (kind,)
        return _peaks_cache


# ---------------------------------------------------------------------------
# per-callable state
# ---------------------------------------------------------------------------
def _perf_metrics():
    return {
        "host_ms": _om.gauge(
            "paddle_tpu_perf_host_ms",
            "EWMA host-side dispatch wall time per watched callable "
            "(returns at enqueue — NOT device time; see "
            "paddle_tpu_perf_device_ms)", labelnames=("callable",)),
        "device_ms": _om.gauge(
            "paddle_tpu_perf_device_ms",
            "EWMA device time per watched callable from block_until_"
            "ready-fenced samples", labelnames=("callable",)),
        "flops_frac": _om.gauge(
            "paddle_tpu_perf_attained_flops_frac",
            "measured FLOP/s of the callable as a fraction of the "
            "device's peak (per-callable MFU; static cost_analysis "
            "FLOPs over fenced device time)", labelnames=("callable",)),
        "hbm_frac": _om.gauge(
            "paddle_tpu_perf_attained_hbm_bw_frac",
            "attained HBM bandwidth of the callable as a fraction of "
            "the device's peak (static bytes-accessed over fenced "
            "device time)", labelnames=("callable",)),
        "fenced": _om.counter(
            "paddle_tpu_perf_fenced_samples_total",
            "block_until_ready-fenced device-time samples taken",
            labelnames=("callable",)),
        "regressions": _om.counter(
            "paddle_tpu_perf_regressions_total",
            "sustained perf regressions the EWMA sentinel detected "
            "(fast EWMA above ratio x slow EWMA for K consecutive "
            "fenced samples)", labelnames=("callable",)),
    }


class _CallableState:
    """Rolling perf state for one named callable."""

    __slots__ = ("name", "host_ewma_ms", "device_ewma_ms", "fast_ms",
                 "slow_ms", "samples", "streak", "regressions",
                 "last_fence", "last_dump", "last_flops", "last_nbytes",
                 "_lock")

    def __init__(self, name):
        self.name = str(name)
        self.host_ewma_ms = None
        self.device_ewma_ms = None
        self.fast_ms = None
        self.slow_ms = None
        self.samples = 0
        self.streak = 0
        self.regressions = 0
        self.last_fence = None
        self.last_dump = None
        self.last_flops = None
        self.last_nbytes = None
        self._lock = threading.Lock()

    # -- cheap path: every dispatch -----------------------------------
    def note_host(self, host_s, metrics):
        ms = host_s * 1e3
        with self._lock:
            prev = self.host_ewma_ms
            self.host_ewma_ms = ms if prev is None else \
                prev + _ALPHA_FAST * (ms - prev)
            val = self.host_ewma_ms
        metrics["host_ms"].labels(self.name).set(val)

    def fence_due(self, now_mono):
        """Claim the next fenced sample slot if the throttle allows
        (the claim happens BEFORE the block, so concurrent dispatch
        threads can't pile up fences)."""
        interval = _fence_interval()
        with self._lock:
            if (self.last_fence is not None
                    and now_mono - self.last_fence < interval):
                return False
            self.last_fence = now_mono
            return True

    # -- fenced sample: gauges + sentinel -----------------------------
    def observe_device(self, device_s, flops, nbytes, metrics):
        """Fold one fenced device-time sample in; publish the roofline
        gauges and run the sentinel. Returns the sample summary."""
        ratio = _sentinel_ratio()
        k = _sentinel_k()
        ms = device_s * 1e3
        regression = False
        with self._lock:
            self.samples += 1
            if flops is not None:
                self.last_flops = flops
            if nbytes is not None:
                self.last_nbytes = nbytes
            self.device_ewma_ms = ms if self.device_ewma_ms is None \
                else self.device_ewma_ms \
                + _ALPHA_FAST * (ms - self.device_ewma_ms)
            self.fast_ms = ms if self.fast_ms is None else \
                self.fast_ms + _ALPHA_FAST * (ms - self.fast_ms)
            self.slow_ms = ms if self.slow_ms is None else \
                self.slow_ms + _ALPHA_SLOW * (ms - self.slow_ms)
            if (self.samples > _SENTINEL_MIN and self.slow_ms > 0
                    and self.fast_ms > ratio * self.slow_ms):
                self.streak += 1
            else:
                self.streak = 0
            if self.streak >= k:
                # sustained: count it, re-baseline the slow EWMA on the
                # new level (one regression = one event, not an event
                # per sample until the slow EWMA catches up), reset
                regression = True
                self.regressions += 1
                self.streak = 0
                slow_before = self.slow_ms
                self.slow_ms = self.fast_ms
            device_ms = self.device_ewma_ms
            ewma_s = device_ms / 1e3
        peak_flops, peak_bw, kind = device_peaks()
        sample = {"callable": self.name, "device_ms": device_ms,
                  "device_kind": kind, "flops": flops, "bytes": nbytes,
                  "regression": regression}
        metrics["device_ms"].labels(self.name).set(device_ms)
        metrics["fenced"].labels(self.name).inc()
        if flops and flops > 0 and ewma_s > 0 and peak_flops:
            frac = min(1.0, flops / (ewma_s * peak_flops))
            sample["attained_flops_frac"] = frac
            metrics["flops_frac"].labels(self.name).set(frac)
        if nbytes and nbytes > 0 and ewma_s > 0 and peak_bw:
            frac = min(1.0, nbytes / (ewma_s * peak_bw))
            sample["attained_hbm_bw_frac"] = frac
            metrics["hbm_frac"].labels(self.name).set(frac)
        if regression:
            metrics["regressions"].labels(self.name).inc()
            self._flight_record(ms, slow_before, ratio, k, sample)
        return sample

    def _flight_record(self, ms, slow_before, ratio, k, sample):
        """One postmortem bundle per sustained regression, rate-limited
        per callable (the counter still ticks every event)."""
        now = time.monotonic()
        with self._lock:
            if (self.last_dump is not None
                    and now - self.last_dump < _DUMP_INTERVAL):
                return
            self.last_dump = now
        from . import flight_recorder as _fr

        try:
            _fr.dump(reason="perf_regression", info={
                "callable": self.name,
                "device_ms_last": round(ms, 3),
                "device_ms_baseline": round(slow_before, 3),
                "slowdown_x": round(ms / max(slow_before, 1e-9), 3),
                "sentinel_ratio": ratio, "sentinel_k": k,
                "sample": {kk: vv for kk, vv in sample.items()
                           if kk != "regression"},
            })
        except Exception:
            pass    # telemetry must never break the dispatch path

    def snapshot(self):
        with self._lock:
            return {"callable": self.name,
                    "host_ewma_ms": self.host_ewma_ms,
                    "device_ewma_ms": self.device_ewma_ms,
                    "fast_ms": self.fast_ms, "slow_ms": self.slow_ms,
                    "samples": self.samples, "streak": self.streak,
                    "regressions": self.regressions,
                    "flops": self.last_flops,
                    "bytes_accessed": self.last_nbytes}


_state_lock = threading.Lock()
_states: dict[str, _CallableState] = {}
_metrics_cache = None


def _metrics():
    global _metrics_cache
    if _metrics_cache is None or isinstance(
            _metrics_cache["host_ms"], _om._NullMetric):
        # rebuilt when the kill switch flips back on mid-process (tests)
        _metrics_cache = _perf_metrics()
    return _metrics_cache


def _state(name):
    with _state_lock:
        st = _states.get(name)
        if st is None:
            st = _states[name] = _CallableState(name)
        return st


def recorders():
    """``{callable: state snapshot}`` — the sentinel/roofline state per
    watched callable (diagnostics; the gauges are the stable API)."""
    with _state_lock:
        states = list(_states.values())
    return {st.name: st.snapshot() for st in states}


def reset():
    """Drop all per-callable state and caches (tests)."""
    global _peaks_cache, _metrics_cache, _build_info_cache
    with _state_lock:
        _states.clear()
    with _peaks_lock:
        _peaks_cache = None
    _metrics_cache = None
    _build_info_cache = None
    with _cost_lock:
        _cost_cache.clear()


# ---------------------------------------------------------------------------
# static-cost cache: executable -> (flops, bytes accessed)
# ---------------------------------------------------------------------------
_cost_lock = threading.Lock()
#: keyed by id(compiled) — safe because watched executables are held
#: for the life of the process by their dispatch caches (StaticFunction
#: ._aot / watched_jit's cache); bounded as a leak backstop
_cost_cache: dict[int, tuple] = {}


def _cost_for(compiled):
    key = id(compiled)
    with _cost_lock:
        hit = _cost_cache.get(key)
    if hit is not None:
        return hit
    from .compile_watch import CompileWatch

    flops, nbytes, _ = CompileWatch._analyze(compiled)
    with _cost_lock:
        if len(_cost_cache) > 4096:
            _cost_cache.clear()
        _cost_cache[key] = (flops, nbytes)
    return flops, nbytes


# ---------------------------------------------------------------------------
# the dispatch hook
# ---------------------------------------------------------------------------
def note_dispatch(name, compiled, out, t0):
    """Account one watched dispatch of ``compiled`` under ``name`` that
    started at ``time.perf_counter()`` value ``t0`` and returned
    ``out`` (still possibly in flight — dispatch is async).

    Cheap path: fold the host wall time into the per-callable EWMA.
    When the fence throttle allows, additionally ``block_until_ready``
    the outputs — extending the timed window to a true device-time
    sample — and publish the roofline gauges + run the sentinel.
    Never raises (attribution must not break a dispatch); returns the
    fenced-sample dict when one was taken, else None."""
    if not enabled():
        return None
    try:
        now = time.perf_counter()
        st = _state(name)
        m = _metrics()
        st.note_host(now - t0, m)
        if not st.fence_due(time.monotonic()):
            return None
        import jax

        jax.block_until_ready(out)
        device_s = time.perf_counter() - t0
        flops, nbytes = _cost_for(compiled)
        return st.observe_device(device_s, flops, nbytes, m)
    except Exception:
        return None


def observe(name, device_s, flops=None, bytes_accessed=None):
    """Feed one measured device-time sample for ``name`` directly —
    what the fenced dispatch path does internally; also the injection
    point for tests and external harnesses (a Pallas bench loop, a
    hand-fenced region). Returns the sample dict, or None when
    disabled."""
    if not enabled():
        return None
    return _state(name).observe_device(
        float(device_s), flops, bytes_accessed, _metrics())


# ---------------------------------------------------------------------------
# build-info gauge
# ---------------------------------------------------------------------------
_build_info_cache = None


def build_info():
    """``{"git_commit", "jax_version", "device_kind"}`` for this
    process — what a merged cluster pane needs to identify what each
    replica is running. Cached; ``PADDLE_TPU_BUILD_COMMIT`` overrides
    the git lookup (set it in images built without a .git dir)."""
    global _build_info_cache
    if _build_info_cache is not None:
        return _build_info_cache
    commit = os.environ.get("PADDLE_TPU_BUILD_COMMIT")
    if not commit:
        try:
            import subprocess

            commit = subprocess.run(
                ["git", "rev-parse", "--short", "HEAD"],
                cwd=os.path.dirname(os.path.dirname(
                    os.path.dirname(os.path.abspath(__file__)))),
                capture_output=True, text=True,
                timeout=5).stdout.strip() or "unknown"
        except Exception:
            commit = "unknown"
    try:
        import jax

        jax_version = jax.__version__
    except Exception:
        jax_version = "unknown"
    _build_info_cache = {"git_commit": commit,
                         "jax_version": jax_version,
                         "device_kind": device_peaks()[2]}
    return _build_info_cache


def ensure_build_info(registry=None):
    """Register/refresh ``paddle_tpu_build_info`` (value 1, identity in
    the labels) on ``registry`` (default registry when None) so every
    ``/metrics`` scrape and every cluster-merged pane carries it. No-op
    under ``PADDLE_TPU_METRICS=0``."""
    if not _metrics_enabled():
        return None
    reg = registry if registry is not None else _om.default_registry()
    g = reg.gauge(
        "paddle_tpu_build_info",
        "build/runtime identity (git commit, jax version, device kind "
        "as labels; value is always 1)",
        labelnames=("git_commit", "jax_version", "device_kind"))
    info = build_info()
    g.labels(info["git_commit"], info["jax_version"],
             info["device_kind"]).set(1)
    return g


# ---------------------------------------------------------------------------
# on-demand profiler capture (the per-process half)
# ---------------------------------------------------------------------------
#: device-trace events shipped per capture, bounded so a busy chip
#: can't balloon the rpc reply / HTTP body
_MAX_DEVICE_EVENTS = 20000


def _harvest_device_trace(trace_dir, base_us, pid):
    """Chrome-trace events the jax profiler wrote under ``trace_dir``
    (``plugins/profile/<run>/*.trace.json.gz``), rebased so the capture
    window starts at ``base_us`` on this process's span clock and
    stamped with this process's pid (so the cluster merge groups them
    with the process's host spans)."""
    events = []
    pattern = os.path.join(trace_dir, "plugins", "profile",
                           "*", "*.trace.json*")
    for path in sorted(glob.glob(pattern)):
        try:
            if path.endswith(".gz"):
                with gzip.open(path, "rt") as f:
                    doc = json.load(f)
            else:
                with open(path) as f:
                    doc = json.load(f)
        except Exception:
            continue
        evs = [e for e in doc.get("traceEvents", [])
               if isinstance(e, dict) and e.get("ph") != "M"
               and isinstance(e.get("ts"), (int, float))]
        if not evs:
            continue
        t_min = min(float(e["ts"]) for e in evs)
        for e in evs:
            e = dict(e)
            e["ts"] = float(e["ts"]) - t_min + base_us
            e["pid"] = pid
            events.append(e)
    events.sort(key=lambda e: e["ts"])
    return events[:_MAX_DEVICE_EVENTS]


def capture_local(seconds, worker_name=None):
    """One on-demand profile window in THIS process: start a
    ``jax.profiler`` trace, let the caller's workload run for
    ``seconds``, stop, and return a span-shard document (worker / pid /
    epoch_unix / events — see :func:`~.tracing.local_shard`) whose
    events are the process's host spans plus any device-trace events
    the profiler produced, ready for :func:`~.tracing.merge_shards`.

    Blocks the calling thread for the window (serving/training threads
    keep running); returns an empty shard under
    ``PADDLE_TPU_METRICS=0`` (profiler never started, no files)."""
    from . import trace as _trace
    from . import tracing as _tracing

    name = worker_name or f"pid{os.getpid()}"
    if not _metrics_enabled():
        return {"worker": str(name), "pid": os.getpid(),
                "epoch_unix": _trace.epoch_unix(), "events": [],
                "profiler": {"ok": False, "reason": "metrics disabled"}}
    seconds = max(0.0, float(seconds))
    tmp = tempfile.mkdtemp(prefix="paddle_tpu_profile_")
    profiler_ok = False
    t0 = time.perf_counter()
    try:
        import jax

        jax.profiler.start_trace(tmp)
        profiler_ok = True
    except Exception:
        pass
    time.sleep(seconds)
    if profiler_ok:
        try:
            import jax

            jax.profiler.stop_trace()
        except Exception:
            profiler_ok = False
    shard = _tracing.local_shard(name)
    device_events = []
    if profiler_ok:
        # window start on this process's span clock: device events sit
        # where the capture actually happened relative to host spans
        base_us = (t0 - _trace._EPOCH) * 1e6
        device_events = _harvest_device_trace(tmp, base_us,
                                              os.getpid())
    shutil.rmtree(tmp, ignore_errors=True)
    shard["events"] = shard["events"] + device_events
    shard["profiler"] = {"ok": profiler_ok, "seconds": seconds,
                         "device_events": len(device_events)}
    return shard


def capture_bundle(seconds, worker_name=None):
    """Single-process convenience over :func:`capture_local`: the
    merged Perfetto-loadable document (what the local ``/debug/profile``
    route serves when no cluster is behind it). None under
    ``PADDLE_TPU_METRICS=0``."""
    if not _metrics_enabled():
        return None
    from . import tracing as _tracing

    shard = capture_local(seconds, worker_name=worker_name)
    merged = _tracing.merge_shards([shard])
    merged["capture"] = {"seconds": float(seconds),
                         "workers": [shard.get("worker")],
                         "pids": [shard.get("pid")],
                         "profiler": [shard.get("profiler")]}
    return merged
