"""Build identity, the per-device-kind peak table, and on-demand profiler
capture.

- :func:`device_peaks` reads the default device's published peaks from
  :data:`PEAKS`; :func:`build_info` / :func:`ensure_build_info` put the
  build's identity on every scrape (``paddle_tpu_build_info``).
- :func:`capture_local` is the per-process half of cluster-wide on-demand
  profiler capture (``/debug/profile?seconds=N`` /
  ``ServingCluster.capture_profile``): it runs a ``jax.profiler`` trace
  over a window while the caller keeps serving and returns a span-shard
  document the merge machinery (:func:`~.tracing.merge_shards`) aligns
  into one Perfetto-loadable bundle. Host spans
  (:class:`~.trace.span`) are events of the profiler's own trace too, so
  the device's ops are placed on the span clock by a marker annotation
  that is read back from the trace, not by a guess.

Where device time goes, kernel by kernel and phase by phase, is read from
a profiler trace by name (``paddle_tpu.*`` kernels, the ``serving.*``
spans); nothing on a dispatch path measures it.
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
    "PEAKS", "device_peaks", "reset", "build_info", "ensure_build_info",
    "capture_local", "capture_bundle",
]

#: (peak FLOP/s, peak HBM bytes/s) per chip, keyed by
#: ``jax.devices()[0].device_kind`` — the bf16 MXU peak and the HBM
#: bandwidth Google Cloud's TPU documentation publishes for each
#: generation ("TPU v5e": 197 TFLOP/s, 819 GB/s; likewise the v2, v3,
#: v4, v5p and v6e system-architecture pages). A kind that is not here
#: has no peaks.
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
}

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


def reset():
    """Drop the cached peaks and build identity (tests)."""
    global _peaks_cache, _build_info_cache
    with _peaks_lock:
        _peaks_cache = None
    _build_info_cache = None


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


#: dropped right after the profiler starts, as the benchmark does: the
#: one event whose time is known on both clocks
_MARK = "paddle_tpu.capture_mark"


def _harvest_device_trace(trace_dir, mark_us, pid):
    """Chrome-trace events the jax profiler wrote under ``trace_dir``
    (``plugins/profile/<run>/*.trace.json.gz``), moved onto this
    process's span clock and stamped with this process's pid (so the
    cluster merge groups them with the process's host spans). The shift
    is read from the marker annotation, which the profiler recorded at
    ``mark_us`` on the span clock; a file without the marker falls back
    to taking its earliest event for the marker. Returns ``(events,
    whether the marker was found in every file)``."""
    events, marked = [], True
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
        marks = [float(e["ts"]) for e in evs if e.get("name") == _MARK]
        if not marks:
            marked = False
        shift = mark_us - (min(marks) if marks
                           else min(float(e["ts"]) for e in evs))
        for e in evs:
            if e.get("name") == _MARK:
                continue
            e = dict(e)
            e["ts"] = float(e["ts"]) + shift
            e["pid"] = pid
            events.append(e)
    events.sort(key=lambda e: e["ts"])
    return events[:_MAX_DEVICE_EVENTS], marked


def capture_local(seconds, worker_name=None):
    """One on-demand profile window in THIS process: start a
    ``jax.profiler`` trace, let the caller's workload run for
    ``seconds``, stop, and return a span-shard document (worker / pid /
    epoch_unix / events — see :func:`~.tracing.local_shard`) whose
    events are the process's host spans plus the events the profiler
    produced (device ops, and the same spans as the profiler saw them),
    on one clock, ready for :func:`~.tracing.merge_shards`.

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
    t_mark = time.perf_counter()
    try:
        import jax

        jax.profiler.start_trace(tmp)
        profiler_ok = True
        t_mark = time.perf_counter()
        with jax.profiler.TraceAnnotation(_MARK):
            time.sleep(0.001)
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
    device_events, marked = [], False
    if profiler_ok:
        device_events, marked = _harvest_device_trace(
            tmp, (t_mark - _trace.to_perf_counter(0.0)) * 1e6,
            os.getpid())
    shutil.rmtree(tmp, ignore_errors=True)
    shard["events"] = shard["events"] + device_events
    shard["profiler"] = {"ok": profiler_ok, "seconds": seconds,
                         "device_events": len(device_events),
                         "clock": "marker" if marked else "assumed"}
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
