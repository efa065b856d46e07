"""A look at a trace by hand: planes, lines, the heaviest ops with their
stats, and the ops of the first executions of the step program. Used
once, when a kernel's pattern is written (``--dump-trace PATH``)."""

import glob
import json
import os


def dump(trace_dir, out_path, step_pattern="jit_pure_step", steps=2):
    from jax.profiler import ProfileData
    files = sorted(glob.glob(os.path.join(
        trace_dir, "plugins", "profile", "*", "*.xplane.pb")),
        key=os.path.getmtime)
    data = ProfileData.from_file(files[-1])
    doc = {"file_bytes": os.path.getsize(files[-1]), "planes": []}
    for plane in data.planes:
        p = {"name": plane.name, "lines": []}
        for line in plane.lines:
            events = list(line.events)
            entry = {"name": line.name, "events": len(events)}
            if plane.name.startswith("/device:TPU:0"):
                acc = {}
                for e in events:
                    a = acc.setdefault(e.name, [0, 0, None])
                    a[0] += e.duration_ns
                    a[1] += 1
                    if a[2] is None:
                        a[2] = {str(k): str(v)[:300] for k, v in e.stats}
                top = sorted(acc.items(), key=lambda kv: -kv[1][0])[:80]
                entry["top"] = [{"name": n, "ns": a[0], "count": a[1],
                                 "stats": a[2]} for n, a in top]
                if line.name == "XLA Modules":
                    mods = [e for e in events if step_pattern in e.name]
                    entry["first_steps"] = [
                        [e.name, int(e.start_ns), int(e.duration_ns)]
                        for e in mods[:steps + 1]]
                    doc["_cut"] = (int(mods[0].start_ns),
                                   int(mods[steps].start_ns)) \
                        if len(mods) > steps else None
            elif events:
                entry["first"] = [[e.name, int(e.start_ns),
                                   int(e.duration_ns)] for e in events[:5]]
            p["lines"].append(entry)
        doc["planes"].append(p)
    cut = doc.pop("_cut", None)
    if cut:
        for plane in data.planes:
            if plane.name.startswith("/device:TPU:0"):
                for line in plane.lines:
                    if line.name == "XLA Ops":
                        doc["ops_of_first_steps"] = [
                            [e.name, int(e.start_ns), int(e.duration_ns)]
                            for e in line.events
                            if cut[0] <= e.start_ns < cut[1]]
    os.makedirs(os.path.dirname(out_path) or ".", exist_ok=True)
    with open(out_path, "w") as f:
        json.dump(doc, f)
