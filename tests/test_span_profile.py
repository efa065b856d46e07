"""One span primitive on the device trace's clock (ISSUE 26): a
``trace.span`` open while ``jax.profiler`` traces is an event of that name
on a host plane of the ``.xplane.pb``, with its args as stats, nested as
in the ring and at the same time; ``PADDLE_TPU_METRICS=0`` turns both
off; ``profiler.RecordEvent`` and ``perf.capture_local`` ride the same
primitive."""

import glob
import os
import time

import jax
import jax.numpy as jnp
import pytest

from paddle_tpu import profiler
from paddle_tpu.observability import perf
from paddle_tpu.observability import trace as otrace

MARK = "test.mark"


@pytest.fixture(autouse=True)
def _fresh_ring():
    otrace.clear()
    yield
    otrace.clear()


def _traced(tmp_path, body):
    """Run ``body()`` under a profiler session with the benchmark's
    options and marker. Returns (host events by name, marker's start on
    the trace clock in ns, marker's ``perf_counter``)."""
    from jax.profiler import ProfileData
    opts = jax.profiler.ProfileOptions()
    opts.python_tracer_level = 0
    opts.host_tracer_level = 1
    jax.profiler.start_trace(str(tmp_path), profiler_options=opts)
    try:
        t_mark = time.perf_counter()
        with jax.profiler.TraceAnnotation(MARK):
            time.sleep(0.001)
        body()
    finally:
        jax.profiler.stop_trace()
    files = glob.glob(os.path.join(str(tmp_path), "plugins", "profile",
                                   "*", "*.xplane.pb"))
    assert len(files) == 1
    events = {}
    for plane in ProfileData.from_file(files[0]).planes:
        if plane.name.startswith("/device:"):
            continue
        for line in plane.lines:
            for e in line.events:
                events.setdefault(e.name, []).append(
                    (int(e.start_ns), int(e.duration_ns),
                     {str(k): str(v) for k, v in e.stats}))
    assert len(events[MARK]) == 1
    return events, events[MARK][0][0], t_mark


def _ring(name):
    return [e for e in otrace.get_events() if e["name"] == name]


def test_span_is_an_event_of_the_profile_with_its_args(tmp_path):
    def body():
        with otrace.span("unit.outer", step=7, kind="mixed") as outer:
            with otrace.span("unit.inner"):
                jnp.ones((64, 64)).sum().block_until_ready()
            outer.set(rows=3)

    events, mark_ns, t_mark = _traced(tmp_path, body)
    (o_start, o_dur, o_stats), = events["unit.outer"]
    (i_start, i_dur, _), = events["unit.inner"]
    assert o_stats == {"step": "7", "kind": "mixed", "rows": "3"}
    # nested in the profile as in the ring
    assert o_start <= i_start and i_start + i_dur <= o_start + o_dur
    (ro,), (ri,) = _ring("unit.outer"), _ring("unit.inner")
    assert ro["args"] == {"step": 7, "kind": "mixed", "rows": 3}
    assert ro["ts"] <= ri["ts"] \
        and ri["ts"] + ri["dur"] <= ro["ts"] + ro["dur"]
    # one clock: the ring's stamp, mapped through the marker as
    # perfbench/harness/trace.py maps its window, is the profile's start
    for ring_ev, start_ns in ((ro, o_start), (ri, i_start)):
        at_ns = mark_ns + (otrace.to_perf_counter(ring_ev["ts"])
                           - t_mark) * 1e9
        assert abs(at_ns - start_ns) < 1e6, (at_ns, start_ns)
    assert abs(o_dur - ro["dur"] * 1e3) < 1e6


def test_metrics_off_leaves_no_span_and_no_annotation(tmp_path,
                                                      monkeypatch):
    monkeypatch.setenv("PADDLE_TPU_METRICS", "0")

    def body():
        with otrace.span("unit.off", step=1) as s:
            s.set(rows=2)
        otrace.record("unit.off_record", 1.0, 2.0)

    events, _, _ = _traced(tmp_path, body)
    assert "unit.off" not in events
    assert not _ring("unit.off") and not _ring("unit.off_record")


def test_cancelled_span_records_nothing_but_closes_cleanly():
    with otrace.span("unit.kept"):
        with otrace.span("unit.dropped") as s:
            s.cancel()
            s.cancel()          # idempotent
            s.set(late=1)       # a closed span takes no args
    assert not _ring("unit.dropped")
    assert len(_ring("unit.kept")) == 1
    # the ring timestamp maps back onto perf_counter
    now = time.perf_counter()
    at = otrace.to_perf_counter(_ring("unit.kept")[0]["ts"])
    assert 0 <= now - at < 5.0


def test_record_writes_a_finished_span_from_kept_stamps():
    t1 = time.perf_counter()
    otrace.record("unit.request", t1 - 0.25, t1, seq_id=4, status="ok")
    (e,) = _ring("unit.request")
    assert e["dur"] == pytest.approx(0.25e6)
    assert otrace.to_perf_counter(e["ts"]) == pytest.approx(t1 - 0.25)
    assert e["args"] == {"seq_id": 4, "status": "ok"}


def test_ring_keeps_a_benchmark_window():
    # 51 s of the fastest cell today: ~10 dispatches a second, 7 spans
    # each, and ~600 requests at 3 spans: the old 4,096 was too few
    need = 51 * 10 * 7 + 600 * 3
    assert otrace.RING_CAPACITY >= 4 * need
    assert otrace.default_buffer()._events.maxlen == otrace.RING_CAPACITY


def test_record_event_is_a_span(tmp_path):
    def body():
        ev = profiler.RecordEvent("unit.record_event")
        ev.begin()
        ev.end()
        ev.end()                # a second end is a no-op
        with profiler.RecordEvent("unit.record_event_ctx"):
            pass

    events, _, _ = _traced(tmp_path, body)
    assert len(events["unit.record_event"]) == 1
    assert len(events["unit.record_event_ctx"]) == 1
    assert len(_ring("unit.record_event")) == 1
    assert len(_ring("unit.record_event_ctx")) == 1


def test_capture_local_puts_profile_and_ring_on_one_clock():
    import threading
    f = jax.jit(lambda x: (x @ x).sum())
    x = jnp.ones((128, 128))
    f(x).block_until_ready()
    stop = threading.Event()

    def work():
        i = 0
        while not stop.is_set():
            with otrace.span("unit.step", i=i):
                f(x).block_until_ready()
            i += 1
            time.sleep(0.01)

    th = threading.Thread(target=work)
    th.start()
    try:
        time.sleep(0.05)
        shard = perf.capture_local(0.3, worker_name="w0")
    finally:
        stop.set()
        th.join()
    assert shard["profiler"]["ok"] and shard["profiler"]["clock"] == "marker"
    steps = [e for e in shard["events"] if e.get("name") == "unit.step"]
    ring = {e["args"]["i"]: e for e in steps
            if isinstance(e.get("args", {}).get("i"), int)}
    prof = {int(e["args"]["i"]): e for e in steps
            if isinstance(e.get("args", {}).get("i"), str)}
    both = sorted(set(ring) & set(prof))
    assert len(both) >= 5, (len(ring), len(prof))
    # the profiler's copy of a span lies where the ring's does: the
    # device ops it caused are shown under it, not at a guessed offset
    for i in both:
        assert abs(ring[i]["ts"] - prof[i]["ts"]) < 1e3, (ring[i], prof[i])
    assert not [e for e in shard["events"]
                if e.get("name") == perf._MARK]
