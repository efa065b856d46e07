"""``paddle.profiler`` — tracing + throughput benchmarking.

Reference: `python/paddle/profiler/profiler.py:346` (``Profiler`` state
machine with scheduler + on_trace_ready), ``RecordEvent`` host
instrumentation, chrome-trace export (`chrometracing_logger.cc`), and the
ips benchmark timer (`profiler/timer.py`).

TPU-native mechanics: the device tracer is the XLA/JAX profiler —
``start_trace`` collects host + device (TPU) timelines into an XPlane
protobuf AND a chrome ``trace.json.gz`` under
``<log_dir>/plugins/profile/<run>/`` (TensorBoard's profile plugin reads
the same directory). ``RecordEvent`` is
``paddle_tpu.observability.trace.span`` with begin/end, which is a
``jax.profiler.TraceAnnotation`` too, so user ranges appear on the device
timeline, the analog of the reference's RecordEvent instrumentation.
"""

from __future__ import annotations

import glob
import os
import time

import jax

from ..observability.trace import span as _span

__all__ = ["Profiler", "RecordEvent", "ProfilerTarget",
           "export_chrome_tracing", "make_scheduler", "benchmark",
           "Benchmark"]


class ProfilerTarget:
    CPU = "cpu"
    GPU = "gpu"          # accepted for API parity; maps to the device
    CUSTOM_DEVICE = "custom_device"
    TPU = "tpu"


def export_chrome_tracing(dir_name, worker_name=None):
    """Returns an on_trace_ready handler that keeps traces under
    ``dir_name`` (reference profiler.py export_chrome_tracing). The JAX
    profiler already writes chrome json; the handler reports its paths —
    only from runs created by THIS profiler session. ``dir_name`` is a
    long-lived log directory, so a bare glob would resurrect every run
    any previous session ever wrote there; runs present at ``start()``
    (recorded in ``prof._preexisting_runs``) are excluded."""

    def handle(prof):
        stale = getattr(prof, "_preexisting_runs", set())
        prof._last_chrome_traces = sorted(
            trace
            for run in glob.glob(
                os.path.join(dir_name, "plugins", "profile", "*"))
            if run not in stale
            for trace in glob.glob(
                os.path.join(run, "*.trace.json.gz")))
        return prof._last_chrome_traces

    handle._log_dir = dir_name
    return handle


def make_scheduler(*, closed=0, ready=0, record=1, repeat=0,
                   skip_first=0):
    """Step-state scheduler (reference profiler_utils make_scheduler):
    returns a callable step -> bool(record)."""
    cycle = closed + ready + record

    def schedule(step):
        if step < skip_first:
            return False
        s = step - skip_first
        if repeat and s >= cycle * repeat:
            return False
        return (s % cycle) >= (closed + ready)

    return schedule


class Profiler:
    """Reference profiler.py:346. Usage::

        p = Profiler(on_trace_ready=export_chrome_tracing('./log'))
        p.start()
        for ...: train(); p.step()
        p.stop()
        p.summary()
    """

    def __init__(self, targets=None, scheduler=None, on_trace_ready=None,
                 timer_only=False, record_shapes=False, profile_memory=False,
                 with_flops=False):
        self._on_trace_ready = on_trace_ready
        self._log_dir = getattr(on_trace_ready, "_log_dir", None) \
            or "./profiler_log"
        self._timer_only = timer_only
        self._scheduler = scheduler
        self._tracing = False
        self._steps = 0
        self._step_times = []
        self._t0 = None
        self._last_chrome_traces = []
        self._preexisting_runs = set()

    # -- lifecycle -----------------------------------------------------------
    def _want_trace(self, step):
        if self._timer_only:
            return False
        if self._scheduler is None:
            return True
        return bool(self._scheduler(step))

    def _set_tracing(self, want):
        if want and not self._tracing:
            os.makedirs(self._log_dir, exist_ok=True)
            jax.profiler.start_trace(self._log_dir)
            self._tracing = True
        elif not want and self._tracing:
            jax.profiler.stop_trace()
            self._tracing = False

    def start(self):
        self._t0 = time.perf_counter()
        # snapshot the runs already under the log dir: on_trace_ready
        # handlers report only runs this session creates, not a previous
        # session's leftovers
        self._preexisting_runs = set(glob.glob(
            os.path.join(self._log_dir, "plugins", "profile", "*")))
        self._set_tracing(self._want_trace(self._steps))
        return self

    def stop(self):
        self._set_tracing(False)
        if self._on_trace_ready is not None:
            self._on_trace_ready(self)
        return self

    def step(self, num_samples=None):
        now = time.perf_counter()
        if self._t0 is not None:
            self._step_times.append((now - self._t0, num_samples))
        self._t0 = now
        self._steps += 1
        # scheduled tracing windows open/close on step boundaries
        self._set_tracing(self._want_trace(self._steps))

    def __enter__(self):
        return self.start()

    def __exit__(self, *exc):
        self.stop()
        return False

    # -- results -------------------------------------------------------------
    def chrome_trace_paths(self):
        return list(self._last_chrome_traces)

    def summary(self, sorted_by=None, op_detail=True, thread_sep=False,
                time_unit="ms"):
        """Host-side step statistics (the full op table lives in the
        exported trace, viewable in TensorBoard / Perfetto)."""
        if not self._step_times:
            print("Profiler: no steps recorded")
            return {}
        times = [t for t, _ in self._step_times]
        counted = [(t, n) for t, n in self._step_times if n]
        mean = sum(times) / len(times)
        stats = {"steps": len(times),
                 "avg_step_ms": mean * 1e3,
                 "min_step_ms": min(times) * 1e3,
                 "max_step_ms": max(times) * 1e3}
        if counted:
            # pair each sample count with ITS step's time (a warmup step
            # without num_samples must not pollute ips)
            stats["ips"] = sum(n for _, n in counted) \
                / sum(t for t, _ in counted)
        print("Profiler summary: " + ", ".join(
            f"{k}={v:.3f}" if isinstance(v, float) else f"{k}={v}"
            for k, v in stats.items()))
        if self._last_chrome_traces:
            print("chrome traces: " + ", ".join(self._last_chrome_traces))
        return stats


class RecordEvent:
    """User-annotated range on the profiler timeline (reference
    profiler.py RecordEvent): a begin/end wrapper over
    :class:`~paddle_tpu.observability.trace.span`, so the range is in
    the span ring and, as a ``TraceAnnotation``, in the device trace."""

    def __init__(self, name, event_type=None):
        self.name = name
        self._span = None

    def begin(self):
        self._span = _span(self.name)
        self._span.__enter__()

    def end(self):
        if self._span is not None:
            self._span.__exit__(None, None, None)
            self._span = None

    def __enter__(self):
        self.begin()
        return self

    def __exit__(self, *exc):
        self.end()
        return False


class Benchmark:
    """ips/step-time tracker (reference `profiler/timer.py` Benchmark,
    the engine behind hapi's throughput logs)."""

    def __init__(self):
        self.reset()

    def reset(self):
        self._t = None
        self._times = []
        self._samples = 0

    def begin(self):
        self._t = time.perf_counter()

    def step(self, num_samples=None):
        now = time.perf_counter()
        if self._t is not None:
            self._times.append(now - self._t)
        self._t = now
        if num_samples:
            self._samples += num_samples

    def end(self):
        self._t = None

    @property
    def ips(self):
        tot = sum(self._times)
        return self._samples / tot if tot and self._samples else 0.0

    def speed_average(self):
        return self.ips

    def report(self):
        return {"steps": len(self._times),
                "avg_step_s": (sum(self._times) / len(self._times))
                if self._times else 0.0,
                "ips": self.ips}


_global_benchmark = Benchmark()


def benchmark():
    """Reference timer.py ``benchmark()`` — the global Benchmark."""
    return _global_benchmark
