"""From the profiler's trace to numbers. The reduction works on a plain
structure, so a small recorded trace kept as JSON checks it
(``perfbench/data/sample_trace.json``, ``harness/selfcheck.py``):

    {"planes": [{"name": "/device:TPU:0",
                 "lines": [{"name": "XLA Ops",
                            "events": [[name, start_ns, duration_ns],
                                       ...]}]}]}
"""

import glob
import os
import re

DEVICE_PLANE = re.compile(r"^/device:TPU:(\d+)$")
OPS_LINE = "XLA Ops"
MODULES_LINE = "XLA Modules"
MARK = "perfbench.mark"
# every ``jit.to_static`` program runs on the device as this module
STEP_MODULE = r"^jit_pure_step"


_HLO = re.compile(r"^(%[\w.\-]+) = (.*?)\s([\w\-]+)\(")
_TARGET = re.compile(r'custom_call_target="([^"]+)"')


def short_name(text):
    """The profiler names a device op by its whole HLO text. Kept: the
    instruction's name, its opcode, a custom call's target and the start
    of its result type (which tells one kernel's shapes from another's):
    ``%pure_step.16 custom-call tpu_custom_call (bf16[36,8,128,128]...``"""
    m = _HLO.match(text)
    if not m:
        return text[:120]
    name, result, opcode = m.groups()
    if opcode != "custom-call":
        return f"{name} {opcode}"
    target = _TARGET.search(text)
    return f"{name} {opcode} {target.group(1) if target else '?'} " \
        f"{result[:100]}"


def start(trace_dir):
    """Start the profiler (host annotations on, Python tracer and HLO
    protos off) and drop the benchmark's marker; returns the host clock
    at the marker."""
    import time

    import jax
    opts = jax.profiler.ProfileOptions()
    opts.python_tracer_level = 0
    opts.host_tracer_level = 1
    opts.enable_hlo_proto = False
    jax.profiler.start_trace(trace_dir, profiler_options=opts)
    t_mark = time.perf_counter()
    with jax.profiler.TraceAnnotation(MARK):
        time.sleep(0.001)
    return t_mark


def traced_window(trace, t_mark, t_unmark):
    """The traced window on the trace's clock: from the marker for as
    long as the host says the trace ran; where the marker is missing,
    the span of the device's ops. Returns (window, span, marker)."""
    mark, sp = mark_ns(trace), span(trace)
    if mark is None:
        return sp, sp, None
    return (mark, mark + int((t_unmark - t_mark) * 1e9)), sp, mark


def load_xplane(trace_dir):
    """Read the newest ``.xplane.pb`` under ``trace_dir`` into the plain
    structure (device planes, and of host planes only the marker)."""
    from jax.profiler import ProfileData

    files = sorted(glob.glob(os.path.join(
        trace_dir, "plugins", "profile", "*", "*.xplane.pb")),
        key=os.path.getmtime)
    if not files:
        raise FileNotFoundError(f"no .xplane.pb under {trace_dir}")
    data = ProfileData.from_file(files[-1])
    planes = []
    for plane in data.planes:
        device = DEVICE_PLANE.match(plane.name)
        lines = []
        for line in plane.lines:
            if device and line.name == OPS_LINE:
                ev = [[short_name(e.name), int(e.start_ns),
                       int(e.duration_ns)] for e in line.events]
            elif device:
                ev = [[e.name[:120], int(e.start_ns), int(e.duration_ns)]
                      for e in line.events]
            else:
                ev = [[e.name, int(e.start_ns), int(e.duration_ns)]
                      for e in line.events if e.name == MARK]
            if ev:
                lines.append({"name": line.name, "events": ev})
        if lines:
            planes.append({"name": plane.name, "lines": lines})
    return {"planes": planes}


def device_planes(trace):
    return [p for p in trace["planes"] if DEVICE_PLANE.match(p["name"])]


def line_events(plane, line_name):
    for line in plane["lines"]:
        if line["name"] == line_name:
            return line["events"]
    return []


def union(intervals):
    """Merged, sorted [start, end) intervals."""
    out = []
    for s, e in sorted(intervals):
        if out and s <= out[-1][1]:
            if e > out[-1][1]:
                out[-1][1] = e
        else:
            out.append([s, e])
    return out


def clip(events, window):
    """Events cut to the window [t0, t1) in ns; None keeps all."""
    if window is None:
        return [[n, s, d] for n, s, d in events if d > 0]
    t0, t1 = window
    out = []
    for n, s, d in events:
        a, b = max(s, t0), min(s + d, t1)
        if b > a:
            out.append([n, a, b - a])
    return out


def busy_ns(plane, window=None):
    """Nanoseconds in which an operation ran on this device: the union
    of the op line's intervals."""
    ev = clip(line_events(plane, OPS_LINE), window)
    return sum(e - s for s, e in union([s, s + d] for _, s, d in ev))


def span(trace):
    """[first start, last end) over every device's op line."""
    starts, ends = [], []
    for p in device_planes(trace):
        for _, s, d in line_events(p, OPS_LINE):
            starts.append(s)
            ends.append(s + d)
    return (min(starts), max(ends)) if starts else None


def idle_share(trace, window):
    """1 - busy/window on the FULLEST device (percent)."""
    planes = device_planes(trace)
    if not planes or window is None or window[1] <= window[0]:
        return None
    busiest = max(busy_ns(p, window) for p in planes)
    return 100.0 * (1.0 - busiest / (window[1] - window[0]))


def mean_busy_s(trace, window):
    planes = device_planes(trace)
    if not planes:
        return None
    return sum(busy_ns(p, window) for p in planes) / len(planes) / 1e9


def module_events(trace, pattern, window=None):
    """Executions of the step program on the first device's module line,
    by a regular expression on the module's name."""
    planes = device_planes(trace)
    if not planes:
        return []
    rx = re.compile(pattern)
    ev = [e for e in line_events(planes[0], MODULES_LINE)
          if rx.search(e[0])]
    if window is not None:
        ev = [e for e in ev if window[0] <= e[1] < window[1]]
    return sorted(ev, key=lambda e: e[1])


def op_seconds(trace, pattern, window=None):
    """Summed device seconds, averaged over devices, of the ops whose
    name matches; None when nothing matches."""
    rx = re.compile(pattern)
    planes = device_planes(trace)
    total, hit = 0, False
    for p in planes:
        for n, _, d in clip(line_events(p, OPS_LINE), window):
            if rx.search(n):
                total += d
                hit = True
    return total / len(planes) / 1e9 if hit else None


def _base(name):
    """An op's name without its instance number:
    ``%fusion.123 fusion`` -> ``%fusion fusion``."""
    head, _, rest = name.partition(" ")
    head = re.sub(r"[.\d]+$", "", head) or head
    return (head + " " + rest).strip()[:100]


def top_ops(trace, window=None, limit=10):
    planes = device_planes(trace)
    if not planes:
        return []
    acc = {}
    for n, _, d in clip(line_events(planes[0], OPS_LINE), window):
        acc[_base(n)] = acc.get(_base(n), 0) + d
    top = sorted(acc.items(), key=lambda kv: -kv[1])[:limit]
    return [[n, d / 1e9] for n, d in top]


def idle_gaps(trace, window, label_of, limit=10):
    """Idle seconds of the first device inside the window, summed by the
    label ``label_of(start_ns, end_ns)`` gives each gap."""
    planes = device_planes(trace)
    if not planes or window is None:
        return []
    ev = clip(line_events(planes[0], OPS_LINE), window)
    busy = union([s, s + d] for _, s, d in ev)
    acc, at = {}, window[0]
    for s, e in busy + [[window[1], window[1]]]:
        if s > at:
            lab = label_of(at, s)
            acc[lab] = acc.get(lab, 0) + (s - at)
        at = max(at, e)
    top = sorted(acc.items(), key=lambda kv: -kv[1])[:limit]
    return [[n, d / 1e9] for n, d in top]


def mark_ns(trace):
    """Trace-clock start of the benchmark's marker annotation, or None."""
    for p in trace["planes"]:
        if DEVICE_PLANE.match(p["name"]):
            continue
        for line in p["lines"]:
            for n, s, _ in line["events"]:
                if n == MARK:
                    return s
    return None
