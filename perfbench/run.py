#!/usr/bin/env python3
"""One run of one cell of the benchmark.

    python3 perfbench/run.py --workload <cell> --seed <n> --seconds <s>
                             --trace <0|1>

Everything about a cell is data: ``BENCHMARK.json`` names its
configuration (``perfbench/configs``), its traffic mix
(``perfbench/mixes``) and its per-layer metrics (one reader each in
``perfbench/metrics``); the configuration names its model family (one
module each in ``perfbench/families``). Without the chips the cell asks
for the run fails and prints no result. ``--rehearse 1`` walks the
control flow on whatever backend there is at the sizes the ``rehearse``
blocks of the configuration and of the mix give; it prints no metric and
exits 4.

The last line of standard output is the result (``correct``,
``attempted``, ``failed``, ``metrics``, ``device``, with ``--trace 1``
``breakdown``, and last ``checks``: each number compared beside its
limit, which are also the last lines of standard error)."""

import time
_T0 = time.perf_counter()

import argparse          # noqa: E402
import importlib.util    # noqa: E402
import json              # noqa: E402
import os                # noqa: E402
import shutil            # noqa: E402
import sys               # noqa: E402

HERE = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(HERE)
REHEARSAL_EXIT = 4
NO_CHIP_EXIT = 3


class Run:
    """What one run knows: its cell's data going in, what the driver
    measured coming out. Per-layer readers get this object."""

    def __init__(self, **kw):
        self.__dict__.update(kw)
        self.facts, self.e2e, self.checks = {}, {}, []
        self.correct, self.attempted, self.failed = False, 0, 0
        self.memory_peak, self.setup_s = None, None

    def note(self, **kw):
        """An earlier line of standard output."""
        print(json.dumps(kw, default=str), flush=True)

    def read_memory_peak(self):
        import jax
        peaks = [(d.memory_stats() or {}).get("peak_bytes_in_use")
                 for d in jax.local_devices()]
        peaks = [p for p in peaks if p is not None]
        return max(peaks) if peaks else None


def load_json(*parts):
    with open(os.path.join(ROOT, *parts)) as f:
        return json.load(f)


def deep_update(base, over):
    for k, v in over.items():
        if isinstance(v, dict) and isinstance(base.get(k), dict):
            deep_update(base[k], v)
        else:
            base[k] = v


def reader_path(name):
    """A per-layer metric's reader: ``metrics/<name>.py``, else the one
    its quantity's splits share, ``metrics/<name before the last dot>.py``
    (``idle_share.py`` reads ``idle_share.gap`` and ``idle_share.train``)."""
    for stem in (name, name.rpartition(".")[0]):
        path = os.path.join(HERE, "metrics", stem + ".py")
        if stem and os.path.isfile(path):
            return path
    raise FileNotFoundError(f"no reader for per-layer metric {name!r} "
                            f"under perfbench/metrics")


def load_reader(name):
    path = reader_path(name)
    spec = importlib.util.spec_from_file_location(
        "perfbench_metric_" + name.replace(".", "_").replace("-", "_"),
        path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def applies(metric, cell):
    """Does this metric belong to the cell? By its ``workloads`` list;
    without one it belongs to every cell."""
    return cell["name"] in metric.get("workloads", (cell["name"],))


def execute(argv=None):
    """One run. Returns ``(exit code, result)``; the result is None where
    nothing may be printed."""
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--seconds", type=float, default=None)
    ap.add_argument("--trace", type=int, default=0, choices=(0, 1))
    ap.add_argument("--rehearse", type=int, default=0, choices=(0, 1))
    ap.add_argument("--control", default="",
                    help="put a control in the program's place in the "
                         "comparison (int8; training also half_batch): "
                         "the line's `correct` then has to read false")
    ap.add_argument("--dump-trace", default="",
                    help="write a summary of the raw trace here, for the "
                         "look by hand")
    ap.add_argument("--sweep", default="",
                    help="open-loop rates per second, comma-separated: "
                         "one window each in one process, to find the "
                         "knee; prints no result (never used by the "
                         "driver)")
    args = ap.parse_args(argv)

    if not os.path.isdir(os.path.join(ROOT, "paddle_tpu")):
        print("perfbench: the program (paddle_tpu/) is not in this "
              "checkout; nothing was run", file=sys.stderr)
        return 2, None
    for p in (ROOT, HERE):
        if p not in sys.path:
            sys.path.insert(0, p)
    spec = load_json("BENCHMARK.json")
    cells = {c["name"]: c for c in spec["workloads"]}
    if args.workload not in cells:
        print(f"perfbench: no cell {args.workload!r} in BENCHMARK.json",
              file=sys.stderr)
        return 2, None
    cell = cells[args.workload]
    config = {c["name"]: c for c in spec["configs"]}[cell["config"]]
    cfg = load_json(config["file"])
    mix = load_json("perfbench", "mixes", cell["traffic"] + ".json")
    seconds = args.seconds if args.seconds is not None \
        else float(spec["run_seconds"])
    sweep = [float(x) for x in args.sweep.split(",") if x]
    if args.rehearse:
        deep_update(cfg, cfg.get("rehearse", {}))
        deep_update(mix, mix.get("rehearse", {}).get("mix", {}))

    from harness import family, peaks, selfcheck
    fam = family.load(cfg, config["file"])
    selfcheck.run()
    fam.selfcheck()

    import jax
    devs = jax.devices()
    device = {"platform": devs[0].platform, "kind": devs[0].device_kind,
              "count": len(devs)}
    if not args.rehearse and (device["platform"] != "tpu"
                              or device["count"] != cell["chips"]):
        print(f"perfbench: cell {cell['name']} needs {cell['chips']} TPU "
              f"chip(s); jax found {device}; nothing was run",
              file=sys.stderr)
        return NO_CHIP_EXIT, None

    work_dir = os.path.join(ROOT, ".perfbench_work")
    trace_dir = os.path.join(work_dir, "trace." + cell["name"])
    shutil.rmtree(trace_dir, ignore_errors=True)
    run = Run(spec=spec, cell=cell, cfg=cfg, mix=mix, seed=args.seed,
              seconds=seconds, trace=bool(args.trace), chips=cell["chips"],
              device=device, t0=_T0, control=args.control,
              sweep=sweep, rehearse=bool(args.rehearse), family=fam,
              work_dir=work_dir, trace_dir=trace_dir,
              peaks=None if args.rehearse else peaks.peak(device["kind"]))
    from harness import serve, train
    drivers = {"open_poisson": serve.run, "closed_clients": serve.run,
               "train_feed": train.run}
    drivers[mix["generator"]](run)
    if sweep:
        return REHEARSAL_EXIT, None

    metrics, breakdown = {}, None
    if not args.trace:
        for m in spec["end_to_end"]:
            if m["name"] in run.e2e and applies(m, cell):
                metrics[m["name"]] = {"value": run.e2e[m["name"]],
                                      "unit": m["unit"]}
    else:
        from harness import trace as tr
        for m in spec["per_layer"]:
            if not applies(m, cell):
                continue
            value = load_reader(m["name"]).read(run)
            if value is not None:
                metrics[m["name"]] = {"value": float(value),
                                      "unit": m["unit"]}
        if args.dump_trace:
            from harness import tracedump
            tracedump.dump(trace_dir, args.dump_trace,
                           run.facts["step_pattern"].lstrip("^"))
        t, win = run.facts["trace"], run.facts["window_ns"]
        device["busy_s"] = tr.mean_busy_s(t, win)
        device["window_s"] = (win[1] - win[0]) / 1e9
        breakdown = {"device_ops": tr.top_ops(t, win),
                     "idle_gaps": tr.idle_gaps(t, win,
                                               run.facts["gap_label"])}
        run.note(phase="trace", mark_found=run.facts["mark_found"],
                 window_ns=win, span_ns=run.facts["span_ns"])
        shutil.rmtree(trace_dir, ignore_errors=True)
    device["memory_peak_bytes"] = run.memory_peak
    checks = {name: {"value": value, "limit": limit}
              for name, value, limit in run.checks}
    result = {"correct": bool(run.correct), "attempted": run.attempted,
              "failed": run.failed, "metrics": metrics, "device": device}
    if breakdown is not None:
        result["breakdown"] = breakdown
    result["checks"] = checks
    if args.rehearse:
        # a rehearsal prints no metric and never exits 0
        result = {"rehearsal": True, "correct": result["correct"],
                  "attempted": run.attempted, "failed": run.failed,
                  "metric_names": sorted(metrics), "checks": checks}
        return REHEARSAL_EXIT, result
    return 0, result


def main():
    code, result = execute()
    if result is not None:
        sys.stdout.flush()
        for name, c in result["checks"].items():
            print(f"check {name}: value {c['value']!r} limit "
                  f"{c['limit']!r}", file=sys.stderr)
        sys.stderr.flush()
        print(json.dumps(result), flush=True)
    sys.exit(code)


if __name__ == "__main__":
    main()
