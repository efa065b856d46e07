"""The program's own spans, for the per-layer readers that read them.

``paddle_tpu.observability.trace.span`` records every span twice: in the
program's ring (``get_events()``, host ``perf_counter``, alive after the
cluster stopped) and, while a profiler session runs, as a
``TraceAnnotation`` on a host plane of the same ``.xplane.pb`` as the
device's "XLA Ops" line, on that line's clock. ``harness/trace.py`` keeps
of the host planes only the marker, so this helper reads them itself
from ``run.trace_dir``, which still exists when the readers run.

Everything below the loaders is a pure function of plain structures, so
a small recorded sample checks it (``perfbench/data/sample_spans.json``,
``perfbench/tests/test_spans.py``):

    host spans   [[name, start_ns, duration_ns, {stat: value}], ...]
                 (trace clock; one list per run, every host thread)
    ring         the ring's chrome-trace events as the program keeps them
    device ops   [[HLO text, start_ns, duration_ns, scope], ...] of the
                 first device's "XLA Ops" line, the scope being the
                 event metadata's ``tf_op`` stat

A reader built on these returns None, never a guess, where a span is
missing (a program that lacks it, as the parent of the PR that added the
spans does) or the ring has wrapped past the window's open.
"""

import glob
import os
import re

from . import stats, trace as tr

PHASES = ("serving.schedule", "serving.build", "serving.wait",
          "serving.apply")
DISPATCH, TICK, FIRST_TOKEN = "serving.dispatch", "replica.tick", \
    "serving.first_token"
# a kernel's name as the program gives it to ``pallas_call(name=)``: it
# is part of the HLO instruction's name, which starts a device event's
# text (``%jvp_paddle_tpu.flash_fwd_.1 = ...``)
KERNEL = re.compile(r"paddle_tpu\.([a-z0-9]+(?:_[a-z0-9]+)*)")


# ---------------------------------------------------------------------------
# loaders (the only functions that touch the program or the trace files)
# ---------------------------------------------------------------------------
def ring_events():
    """The program's ring and the function that maps its timestamps to
    ``perf_counter``; (None, None) for a program without them."""
    try:
        from paddle_tpu.observability import trace as ptrace
    except Exception:
        return None, None
    to_pc = getattr(ptrace, "to_perf_counter", None)
    if to_pc is None:
        return None, None
    return ptrace.get_events(), to_pc


def load_host_spans(trace_dir, names):
    """Events of the host planes of the newest ``.xplane.pb`` whose name
    is in ``names``, as ``[name, start_ns, duration_ns, stats]``."""
    from jax.profiler import ProfileData

    path = newest_xplane(trace_dir)
    if path is None:
        return []
    out = []
    for plane in ProfileData.from_file(path).planes:
        if tr.DEVICE_PLANE.match(plane.name):
            continue
        for line in plane.lines:
            for e in line.events:
                if e.name not in names:
                    continue
                st = {str(k): _number(v) for k, v in e.stats}
                if "cancelled" not in st:   # a span the program dropped
                    out.append([e.name, int(e.start_ns), int(e.duration_ns),
                                st])
    return sorted(out, key=lambda s: s[1])


def _varint(buf, i):
    v, shift = 0, 0
    while True:
        b = buf[i]
        i += 1
        v |= (b & 0x7F) << shift
        shift += 7
        if b < 0x80:
            return v, i


def _fields(buf):
    """The fields of one protobuf message: ``(number, wire type, value)``
    with a varint's value or a length-delimited field's bytes; fixed
    fields are skipped over."""
    i, n = 0, len(buf)
    while i < n:
        key, i = _varint(buf, i)
        num, wire = key >> 3, key & 7
        if wire == 0:
            v, i = _varint(buf, i)
            yield num, wire, v
        elif wire == 2:
            ln, i = _varint(buf, i)
            yield num, wire, buf[i:i + ln]
            i += ln
        elif wire in (1, 5):
            i += 8 if wire == 1 else 4
        else:
            raise ValueError(f"wire type {wire} in an xplane")


def op_scopes(xspace):
    """``{op's HLO text: its "tf_op" stat}`` of the first TPU plane of a
    serialized XSpace. ``jax.profiler.ProfileData`` shows an event's own
    stats only; the scope an op was traced under (``jax.named_scope``,
    a kernel's ``name=``) is a stat of the event's METADATA, so the few
    fields needed are read off the wire: XSpace.planes = 1; XPlane.name
    = 2, .event_metadata = 4, .stat_metadata = 5 (maps: key 1, value 2);
    XEventMetadata.name = 2, .stats = 5; XStatMetadata.name = 2;
    XStat.metadata_id = 1, .str_value = 5, .ref_value = 7."""
    for num, wire, plane in _fields(memoryview(xspace)):
        if num != 1 or wire != 2:
            continue
        name, metas, stat_names = "", [], {}
        for f, w, v in _fields(plane):
            if f == 2 and w == 2:
                name = bytes(v).decode()
            elif f in (4, 5) and w == 2:
                entry = {k: val for k, _, val in _fields(v)}
                if f == 4:
                    metas.append(entry.get(2, b""))
                else:
                    stat_names[entry.get(1, 0)] = next(
                        (bytes(x).decode() for k, w2, x in
                         _fields(entry.get(2, b"")) if k == 2 and w2 == 2),
                        "")
        if not tr.DEVICE_PLANE.match(name):
            continue
        out = {}
        for meta in metas:
            text, scope = "", None
            for f, w, v in _fields(meta):
                if f == 2 and w == 2:
                    text = bytes(v).decode()
                elif f == 5 and w == 2:
                    st = {k: val for k, _, val in _fields(v)}
                    if stat_names.get(st.get(1)) != "tf_op":
                        continue
                    scope = bytes(st[5]).decode() if 5 in st \
                        else stat_names.get(st.get(7), "")
            if scope is not None:
                out[text] = scope
        return out
    return {}


def load_device_ops(trace_dir):
    """The first TPU plane's "XLA Ops" events of the newest
    ``.xplane.pb`` as ``[HLO text, start_ns, duration_ns, scope]``."""
    from jax.profiler import ProfileData

    path = newest_xplane(trace_dir)
    if path is None:
        return []
    with open(path, "rb") as f:
        scopes = op_scopes(f.read())
    for plane in ProfileData.from_file(path).planes:
        if not tr.DEVICE_PLANE.match(plane.name):
            continue
        for line in plane.lines:
            if line.name == tr.OPS_LINE:
                return [[e.name, int(e.start_ns), int(e.duration_ns),
                         scopes.get(e.name, "")] for e in line.events]
    return []


def newest_xplane(trace_dir):
    files = sorted(glob.glob(os.path.join(
        trace_dir, "plugins", "profile", "*", "*.xplane.pb")),
        key=os.path.getmtime)
    return files[-1] if files else None


def _number(v):
    try:
        f = float(v)
    except (TypeError, ValueError):
        return str(v)
    return int(f) if f.is_integer() else f


def loaded(run):
    """What the span readers share, read once a run: ``{"host": host
    spans that began in the traced window, "ring": the ring, "to_pc":
    its clock map}``, or None without a trace or without a program that
    records spans. The first call also prints the run's tables."""
    if "spans" in run.facts:
        return run.facts["spans"]
    run.facts["spans"] = None
    t, win = run.facts.get("trace"), run.facts.get("window_ns")
    ring, to_pc = ring_events()
    if t is None or win is None or not ring:
        return None
    names = {e["name"] for e in ring}
    host = [s for s in load_host_spans(run.trace_dir, names)
            if win[0] <= s[1] < win[1]]
    if not host:
        return None
    run.facts["spans"] = {"host": host, "ring": ring, "to_pc": to_pc}
    report(run)
    return run.facts["spans"]


def device_ops(run):
    """The traced run's device ops with their scopes, read once; the
    first call prints the kernel table. [] without a trace."""
    if "device_ops" not in run.facts:
        run.facts["device_ops"] = load_device_ops(run.trace_dir) \
            if run.facts.get("trace") is not None else []
        if run.facts["device_ops"]:
            run.note(table="kernel_seconds_by_name",
                     **kernels(run.facts["device_ops"],
                               run.facts["window_ns"]))
    return run.facts["device_ops"]


def ttft_part_ms(run, later, earlier):
    """Median of ``later - earlier`` in ms over the
    ``serving.first_token`` markers of the window's requests; printed
    once with its two siblings as the run's TTFT anatomy."""
    ring, to_pc = ring_events()
    if not ring or run.setup_s is None:
        return None
    reqs = requests(ring, to_pc, window_open(run))
    if reqs and "ttft_anatomy" not in run.facts:
        run.facts["ttft_anatomy"] = True
        run.note(table="ttft_anatomy", requests=len(reqs), **{
            f"{a}_minus_{b}_ms": {
                "p50": stamp_gap_ms(reqs, a, b),
                "p90": 1e3 * stats.percentile(
                    [r[a] - r[b] for r in reqs], 90)}
            for a, b in (("t_admit", "t_submit"),
                         ("t_first_chunk", "t_admit"),
                         ("t_first_token", "t_first_chunk"),
                         ("t_first_token", "t_submit"))})
    return stamp_gap_ms(reqs, later, earlier)


def window_open(run):
    """``perf_counter`` just before the window opened (``setup_s`` is
    stamped there): what was submitted after it is the window's."""
    return run.t0 + run.setup_s


# ---------------------------------------------------------------------------
# pure functions
# ---------------------------------------------------------------------------
def named(spans, name):
    return [s for s in spans if s[0] == name]


def median_ms(spans, name):
    """Median duration in ms of the spans called ``name``; None without
    any."""
    d = [s[2] for s in named(spans, name)]
    return stats.median(d) / 1e6 if d else None


def self_ns(span, children):
    """A span's duration minus what ``children`` cover of it."""
    s, e = span[1], span[1] + span[2]
    cover = tr.union([max(c[1], s), min(c[1] + c[2], e)]
                     for c in children if c[1] < e and c[1] + c[2] > s)
    return span[2] - sum(b - a for a, b in cover)


def tick_self_ms(spans):
    """Median self time of ``replica.tick`` in ms: the loop's turn less
    the dispatches inside it."""
    ticks, disp = named(spans, TICK), named(spans, DISPATCH)
    if not ticks:
        return None
    return stats.median([self_ns(t, disp) for t in ticks]) / 1e6


def stat_ratio(spans, over, under):
    """100 x sum of stat ``over`` / sum of stat ``under`` over the
    window's dispatches; None where a dispatch lacks either."""
    disp = named(spans, DISPATCH)
    if not disp or any(over not in s[3] or under not in s[3]
                       for s in disp):
        return None
    total = sum(s[3][under] for s in disp)
    return 100.0 * sum(s[3][over] for s in disp) / total if total else None


def idle_intervals(trace, window):
    """The first device's idle intervals inside the window, as
    ``trace.idle_gaps`` takes them."""
    planes = tr.device_planes(trace)
    if not planes or window is None:
        return []
    ev = tr.clip(tr.line_events(planes[0], tr.OPS_LINE), window)
    out, at = [], window[0]
    for s, e in tr.union([s, s + d] for _, s, d in ev) \
            + [[window[1], window[1]]]:
        if s > at:
            out.append([at, s])
        at = max(at, e)
    return out


def innermost(spans):
    """The spans flattened to disjoint ``[start, end, name]`` segments,
    each called by the span that began last among those covering it."""
    edges = sorted({b for s in spans for b in (s[1], s[1] + s[2])})
    order = sorted(spans, key=lambda s: s[1])
    out, live, i = [], [], 0
    for a, b in zip(edges, edges[1:]):
        while i < len(order) and order[i][1] <= a:
            live.append(order[i])
            i += 1
        live = [s for s in live if s[1] + s[2] > a]
        if live:
            name = max(live, key=lambda s: s[1])[0]
            if out and out[-1][2] == name and out[-1][1] == a:
                out[-1][1] = b
            else:
                out.append([a, b, name])
    return out


def idle_by_span(idle, spans):
    """Idle nanoseconds by the innermost program span covering them;
    what no span covers is under ``None``."""
    segs, acc, j = innermost(spans), {}, 0
    for a, b in idle:
        at = a
        while j < len(segs) and segs[j][1] <= a:
            j += 1
        k = j
        while k < len(segs) and segs[k][0] < b:
            s, e, name = segs[k]
            if s > at:
                acc[None] = acc.get(None, 0) + (s - at)
            lo, hi = max(s, at), min(e, b)
            if hi > lo:
                acc[name] = acc.get(name, 0) + (hi - lo)
                at = hi
            k += 1
        if b > at:
            acc[None] = acc.get(None, 0) + (b - at)
    return acc


def named_share(idle, spans):
    """Percent of the idle nanoseconds lying under some span."""
    total = sum(b - a for a, b in idle)
    if not total:
        return None
    return 100.0 * (1.0 - idle_by_span(idle, spans).get(None, 0) / total)


def instruction(text):
    """An op's instruction name: what its HLO text starts with."""
    return text.split(" ", 1)[0]


def kernels(ops, window):
    """Seconds and events per kernel name (``pallas_call(name=)``, read
    from the instruction's name alone) among the device ops."""
    acc = {}
    for text, _, d in tr.clip([o[:3] for o in ops], window):
        m = KERNEL.search(instruction(text))
        if m:
            a = acc.setdefault(m.group(1), [0, 0])
            a[0] += d
            a[1] += 1
    return {k: {"seconds": ns / 1e9, "events": c}
            for k, (ns, c) in sorted(acc.items())}


def scope_seconds(ops, pattern, window):
    """Device seconds inside the window of the ops whose scope (the
    ``tf_op`` stat: ``jit(pure_step)/optimizer/sub:``) matches; None
    when none does."""
    rx = re.compile(pattern)
    hit = [[t, s, d] for t, s, d, scope in ops if rx.search(scope)]
    return sum(d for _, _, d in tr.clip(hit, window)) / 1e9 if hit else None


def wrapped(ring, to_pc, t_from):
    """Has the ring lost what happened at ``perf_counter`` ``t_from``?
    It has unless it still holds something older."""
    return not ring or to_pc(min(e["ts"] for e in ring)) > t_from


def dispatches(ring, to_pc, t_from, t_to):
    """The ring's dispatches that began in ``[t_from, t_to)`` on the
    ``perf_counter`` clock, each ``{"step", "start", "ms", "kind",
    "tokens", phase: ms}``; None where the ring has wrapped past
    ``t_from``."""
    if wrapped(ring, to_pc, t_from):
        return None
    phases = {}
    for e in ring:
        if e["name"] in PHASES and "step" in e.get("args", ()):
            phases.setdefault(e["args"]["step"], {})[
                e["name"].split(".")[1] + "_ms"] = e["dur"] / 1e3
    out = []
    for e in ring:
        if e["name"] == DISPATCH and t_from <= to_pc(e["ts"]) < t_to:
            a = e.get("args", {})
            out.append(dict(step=a.get("step"), start=to_pc(e["ts"]),
                            ms=e["dur"] / 1e3, kind=a.get("kind"),
                            tokens=a.get("tokens"),
                            **phases.get(a.get("step"), {})))
    return out


def late_dispatches(disp, over_ms=10.0):
    """The three slowest dispatches, the one that ended the longest wait
    between two dispatches' ends (the largest gap between tokens, seen
    from inside), and every dispatch whose HOST time (the dispatch less
    its ``serving.wait``, under which the device works) lies more than
    ``over_ms`` over the median of its kind."""
    if not disp:
        return {}
    disp = [dict(d, host_ms=d["ms"] - d.get("wait_ms", 0.0)) for d in disp]
    kinds = {d["kind"] for d in disp}
    med = {k: stats.median([d["host_ms"] for d in disp if d["kind"] == k])
           for k in kinds}
    late = [dict(d, over_ms=d["host_ms"] - med[d["kind"]])
            for d in disp if d["host_ms"] - med[d["kind"]] > over_ms]
    ends = [d["start"] + d["ms"] / 1e3 for d in disp]
    gaps = [(b - a, i + 1) for i, (a, b) in enumerate(zip(ends, ends[1:]))]
    out = {"median_ms_by_kind": {k: stats.median(
               [d["ms"] for d in disp if d["kind"] == k]) for k in kinds},
           "median_host_ms_by_kind": med,
           "slowest": sorted(disp, key=lambda d: -d["ms"])[:3],
           "late_count": len(late),
           "late": sorted(late, key=lambda d: -d["over_ms"])[:8]}
    if gaps:
        gap, i = max(gaps)
        out["largest_gap"] = dict(disp[i], gap_ms=gap * 1e3)
    return out


def requests(ring, to_pc, t_from):
    """The stamps of the requests submitted from ``t_from`` on that got
    a first token (the args of their ``serving.first_token`` markers:
    ``serving.request`` is written only when a request retires, which
    half of an open-loop window's requests have not when it closes);
    None where the ring has wrapped past ``t_from``."""
    if wrapped(ring, to_pc, t_from):
        return None
    keys = ("t_submit", "t_admit", "t_first_chunk", "t_first_token")
    return [e["args"] for e in ring if e["name"] == FIRST_TOKEN
            and all(e.get("args", {}).get(k) is not None for k in keys)
            and e["args"]["t_submit"] >= t_from]


def stamp_gap_ms(reqs, later, earlier):
    """Median of ``later - earlier`` over the requests, in ms."""
    if not reqs:
        return None
    return 1e3 * stats.median([r[later] - r[earlier] for r in reqs])


# ---------------------------------------------------------------------------
# the run's tables, as earlier lines of standard output
# ---------------------------------------------------------------------------
def report(run):
    sp = run.facts["spans"]
    t, win = run.facts["trace"], run.facts["window_ns"]
    by = idle_by_span(idle_intervals(t, win), sp["host"])
    run.note(table="idle_seconds_by_span",
             **{str(k): v / 1e9 for k, v in
                sorted(by.items(), key=lambda kv: -kv[1])})
    device_ops(run)
    disp = dispatches(sp["ring"], sp["to_pc"], window_open(run),
                      window_open(run) + run.seconds)
    if disp:
        run.note(table="dispatches", count=len(disp),
                 **late_dispatches(disp))
