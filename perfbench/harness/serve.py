"""Driver of the serving cells: ``ServingCluster`` over one in-process
``EngineReplica``, fed by the open-loop or the closed-loop generator."""

import bisect
import gc
import threading
import time

import numpy as np

from . import program, stats, trace as tr, traffic

pc = time.perf_counter


class Rec:
    """What the benchmark saw of one request."""
    __slots__ = ("req", "due", "sent", "stamps", "creq", "done_at",
                 "error")

    def __init__(self, req):
        self.req, self.due, self.sent = req, None, None
        self.stamps, self.creq, self.done_at, self.error = [], None, None, \
            None

    def hook(self):
        stamps = self.stamps

        def on_token(token):
            stamps.append((pc(), int(token)))
        return on_token


def _submit(cluster, rec):
    rec.sent = pc()
    try:
        rec.creq = cluster.submit(rec.req["prompt"],
                                  rec.req["max_new_tokens"],
                                  on_token=rec.hook())
    except Exception as exc:      # a refusal is a failed request
        rec.error = repr(exc)


def open_loop(cluster, recs, t_open):
    """Send every request at its due time, whatever the server does."""
    for rec in recs:
        rec.due = t_open + rec.req["due"]
        wait = rec.due - pc()
        if wait > 0:
            time.sleep(wait)
        _submit(cluster, rec)


def closed_loop(cluster, recs, clients, t_close, stop):
    """``clients`` callers, each sending its next request when its last
    one completed, with no think time."""
    slots, nxt = [None] * clients, 0
    while pc() < t_close and not stop.is_set():
        moved = False
        for c in range(clients):
            cur = slots[c]
            if cur is not None and cur.error is None \
                    and not cur.creq.done:
                continue
            if cur is not None and cur.done_at is None:
                cur.done_at = pc()
            if nxt >= len(recs):
                stop.set()
                break
            slots[c] = recs[nxt]
            nxt += 1
            slots[c].due = pc()
            _submit(cluster, slots[c])
            moved = True
        if not moved:
            time.sleep(0.001)
    now = pc()
    for cur in slots:
        if cur is not None and cur.done_at is None and cur.creq is not None \
                and cur.creq.done:
            cur.done_at = now


def build(run):
    """Set-up: the model with the seed's weights, the cluster, the
    engine with this cell's two step shapes warm."""
    import jax
    from paddle_tpu.inference.cluster import ServingCluster
    from paddle_tpu.observability import compile_watch as cw

    cw.enable_persistent_cache()
    cfg, mix, family = run.cfg, run.mix, run.family
    t = pc()
    model = family.build_model(cfg, cfg["torch_dtype"])
    model.eval()
    run.note(phase="build_model", seconds=pc() - t)
    t = pc()
    run.weights, n = program.assign_weights(
        family, model, cfg, run.seed, cfg["torch_dtype"])
    jax.block_until_ready(run.weights)
    if n != family.total_params(cfg):
        raise RuntimeError(f"program holds {n} parameters, the "
                           f"configuration {family.total_params(cfg)}")
    run.note(phase="seed_weights", seconds=pc() - t, parameters=n)
    box = {}

    def factory():
        box["engine"] = family.engine(model, mix)
        return box["engine"]

    t = pc()
    cluster = ServingCluster(factory, num_replicas=1,
                             **mix["cluster"]).start()
    deadline = pc() + 900
    while not cluster.ready():
        if pc() > deadline:
            raise RuntimeError("the cluster never became ready")
        time.sleep(0.05)
    run.note(phase="cluster_ready", seconds=pc() - t,
             prewarmed=box["engine"].prewarmed)
    run.model, run.cluster, run.engine = model, cluster, box["engine"]
    # a few real requests through every path the window uses
    t = pc()
    warm = [Rec({"prompt": traffic.prompt_ids(cfg["vocab_size"], run.seed,
                                              10 ** 6 + i, n_),
                 "max_new_tokens": o_})
            for i, (n_, o_) in enumerate(mix["warm"])]
    for w in warm:
        _submit(cluster, w)
    for w in warm:
        if w.error or not w.creq.wait(600) or w.creq.error is not None:
            raise RuntimeError(f"warm-up request failed: "
                               f"{w.error or w.creq.error or w.creq.status}")
    run.note(phase="warm_requests", seconds=pc() - t, requests=len(warm))


def window(run, seconds, rate=None, seed=None):
    """One measured window. Returns the records and its facts. A sweep
    gives each of its windows a rate and a seed, and the seed then sets
    the order too."""
    import jax
    cfg, mix, cluster = run.cfg, run.mix, run.cluster
    gen = mix["generator"]
    seed = run.seed if seed is None else seed
    if gen == "open_poisson":
        m = mix
        if rate:
            m = dict(mix, rate_per_s=rate)
            m.pop("schedule_seed", None)
        recs = [Rec(r) for r in traffic.open_poisson(
            m, cfg["vocab_size"], seed, seconds)]
    elif gen == "closed_clients":
        recs = [Rec(r) for r in traffic.closed_clients(
            mix, cfg["vocab_size"], seed)]
    else:
        raise ValueError(f"serve driver cannot run generator {gen!r}")
    traced = run.trace and rate is None
    t_len = min(float(mix["trace_seconds"]), seconds * 0.8)
    t_at = (seconds - t_len) / 2
    gc.collect()
    gc.freeze()
    compiles0 = program.metric("paddle_tpu_xla_backend_compile_total")
    stop = threading.Event()
    t_open = pc()
    t_close = t_open + seconds
    if gen == "open_poisson":
        th = threading.Thread(target=open_loop, name="perfbench-load",
                              args=(cluster, recs, t_open), daemon=True)
    else:
        th = threading.Thread(target=closed_loop, name="perfbench-load",
                              args=(cluster, recs, int(mix["clients"]),
                                    t_close, stop), daemon=True)
    th.start()
    facts = {"t_open": t_open, "t_close": t_close, "kv_pool_peak": 0.0}

    def wait_until(t):
        """Sleep until ``t``, reading the engine's pool gauge (pages not
        free, cached prefixes among them) four times a second."""
        while True:
            facts["kv_pool_peak"] = max(
                facts["kv_pool_peak"],
                program.metric("serving_kv_page_utilization"))
            left = t - pc()
            if left <= 0:
                return
            time.sleep(min(0.25, left))

    if traced:
        wait_until(t_open + t_at)
        facts["t_mark"] = tr.start(run.trace_dir)
        wait_until(facts["t_mark"] + t_len)
        facts["t_unmark"] = pc()
        jax.profiler.stop_trace()
    wait_until(t_close)
    stop.set()
    th.join(timeout=120)
    if th.is_alive():
        raise RuntimeError("the load generator did not stop")
    facts["compiles_in_window"] = program.metric(
        "paddle_tpu_xla_backend_compile_total") - compiles0
    # every request due in the window is waited for (a late answer is
    # late, not wrong): its first token, up to a minute past the close
    sent = [r for r in recs if r.sent is not None]
    until = t_close + 60.0
    for r in sent:
        while r.error is None and not r.stamps and not r.creq.done \
                and pc() < until:
            time.sleep(0.005)
    facts["t_end"] = pc()
    gc.unfreeze()
    return sent, facts


def measure(run, sent, facts):
    """End-to-end metrics of a window: all the work, all the samples."""
    t_open, t_close = facts["t_open"], facts["t_close"]
    seconds = t_close - t_open
    gen = run.mix["generator"]
    failed = [r for r in sent if r.error is not None or not r.stamps
              or (r.creq.done and r.creq.status != "completed")]
    out = {"attempted": len(sent), "failed": len(failed)}
    done = [r for r in sent if r.creq is not None and r.creq.done
            and r.creq.status == "completed"]
    e2e = {}
    if gen == "open_poisson":
        worst = facts["t_end"] - t_open
        ttft = [(r.stamps[0][0] - r.due) if r.stamps and r.error is None
                else worst for r in sent]
        gaps = [b[0] - a[0] for r in sent
                for a, b in zip(r.stamps, r.stamps[1:]) if b[0] <= t_close]
        e2e["ttft_p90_ms"] = 1e3 * stats.percentile(ttft, 90)
        e2e["gap_p95_ms"] = 1e3 * stats.percentile(gaps, 95)
        late = [r.sent - r.due for r in sent]
        out.update(ttft_p50_ms=1e3 * stats.median(ttft),
                   ttft_mean_ms=1e3 * sum(ttft) / len(ttft),
                   ttft_max_ms=1e3 * max(ttft),
                   gap_p50_ms=1e3 * stats.median(gaps),
                   gap_p90_ms=1e3 * stats.percentile(gaps, 90),
                   gap_p99_ms=1e3 * stats.percentile(gaps, 99),
                   gap_max_ms=1e3 * max(gaps),
                   ttft_samples=len(ttft), gap_samples=len(gaps),
                   gen_late_p99_ms=1e3 * stats.percentile(late, 99),
                   finished_by_end=len(done),
                   backlog_at_close=sum(
                       1 for r in sent if not r.stamps
                       or r.stamps[0][0] > t_close))
    else:
        inside = [r for r in done if r.done_at is not None
                  and r.done_at <= t_close]
        toks = sum(len(r.req["prompt"]) + len(r.creq.output_ids)
                   for r in inside)
        e2e["serve_tok_s"] = toks / seconds
        out.update(completed_in_window=len(inside),
                   requests_per_s=len(inside) / seconds)
    out["served_tokens"] = sum(len(r.stamps) for r in sent)
    pool = (run.mix["engine"]["num_pages"] - 1) \
        * run.mix["engine"]["page_size"]
    out["kv_live_tokens_peak"] = kv_live_peak(sent)
    out["kv_live_share_of_pool"] = out["kv_live_tokens_peak"] / pool
    out["kv_pool_share_peak_engine_gauge"] = facts["kv_pool_peak"]
    return e2e, out, done


def kv_live_peak(sent):
    """Most tokens whose K/V the traffic held at one time, from the
    benchmark's own stamps: over the requests between their first and
    their last token, prompt plus the tokens served so far."""
    events = []
    for r in sent:
        if not r.stamps:
            continue
        p = len(r.req["prompt"])
        events.append((r.stamps[0][0], p + 1))
        events += [(t, 1) for t, _ in r.stamps[1:]]
        if r.creq is not None and r.creq.done:
            events.append((r.stamps[-1][0], -(p + len(r.stamps))))
    live = peak = 0
    for _, d in sorted(events, key=lambda e: (e[0], -e[1])):
        live += d
        peak = max(peak, live)
    return peak


def traced_work(sent, facts):
    """Prompt lengths prefilled and contexts decoded while the trace
    ran, from the benchmark's own stamps."""
    a, b = facts["t_mark"], facts["t_unmark"]
    prefill, decode = [], []
    for r in sent:
        p = len(r.req["prompt"])
        for j, (t, _) in enumerate(r.stamps):
            if a <= t < b:
                if j == 0:
                    prefill.append(p)
                else:
                    decode.append(p + j)
    return prefill, decode


def outstanding(sent, t_end):
    """Merged host-clock intervals in which some request was in flight."""
    iv = []
    for r in sent:
        if r.sent is None:
            continue
        end = r.done_at or (r.stamps[-1][0] if r.creq is not None
                            and r.creq.done and r.stamps else t_end)
        iv.append([r.sent, max(end, r.sent)])
    return tr.union(iv)


def read_trace(run, sent, facts):
    """Load the trace, fix the traced window on the trace's clock, and
    leave what the per-layer readers need in ``run.facts``."""
    t = tr.load_xplane(run.trace_dir)
    win, sp, mark = tr.traced_window(t, facts["t_mark"], facts["t_unmark"])
    busy_iv = outstanding(sent, facts["t_end"])
    starts = [s for s, _ in busy_iv]

    def label(s_ns, e_ns):
        if mark is None:
            return "host: unattributed"
        at = facts["t_mark"] + ((s_ns + e_ns) / 2 - mark) / 1e9
        i = bisect.bisect_right(starts, at) - 1
        if i >= 0 and busy_iv[i][1] >= at:
            return "host: unattributed (requests in flight)"
        return "benchmark: no request in flight"

    prefill, decode = traced_work(sent, facts)
    run.facts.update(trace=t, window_ns=win, span_ns=sp, mark_found=mark
                     is not None, gap_label=label, prefill=prefill,
                     decode=decode,
                     step_pattern=tr.STEP_MODULE)


def pick_sample(run, done):
    """Requests to hold against the reference: a seeded draw of those
    the window finished, the longest among them."""
    k = int(run.mix["check"]["sample"])
    if not done:
        return []
    size = [len(r.req["prompt"]) + len(r.creq.output_ids) for r in done]
    longest = int(np.argmax(size))
    rest = [i for i in range(len(done)) if i != longest]
    pick = traffic.rng_of(run.seed, 5).permutation(len(rest))[:max(0, k - 1)]
    return [done[longest]] + [done[rest[i]] for i in pick]


def served_gaps(run, sample, quant=None):
    """For each served token of the sample, how far its reference logit
    lies below the reference's best; with ``quant`` the same for the
    token the lower precision puts first there."""
    cfg, chk = run.cfg, run.mix["check"]
    pad = int(chk["pad_to"])
    kmax = max(len(r.creq.output_ids) for r in sample)
    ids = np.zeros((len(sample), pad), np.int64)
    rows = np.zeros((len(sample), kmax), np.int32)
    mask = np.zeros((len(sample), kmax), bool)
    for i, r in enumerate(sample):
        p, o = r.req["prompt"], list(r.creq.output_ids)
        if len(p) + len(o) > pad:
            raise ValueError("check.pad_to is shorter than a request")
        ids[i, :len(p)] = p
        ids[i, len(p):len(p) + len(o)] = o
        rows[i, :len(o)] = np.arange(len(p) - 1, len(p) - 1 + len(o))
        mask[i, :len(o)] = True
    w, logits = run.weights, run.family.served_logits
    block = int(chk["block"])
    ref = logits(cfg, ids, rows, lambda i: w["layers"][i], w["ends"], None,
                 block)
    best = ref.max(-1)
    out = {}
    served = np.zeros_like(rows)
    for i, r in enumerate(sample):
        served[i, :len(r.creq.output_ids)] = r.creq.output_ids
    took = np.take_along_axis(ref, served[:, :, None], -1)[:, :, 0]
    out["served"] = (best - took)[mask]
    if quant:
        low = logits(cfg, ids, rows, lambda i: w["layers"][i], w["ends"],
                     quant, block)
        first = low.argmax(-1)
        took = np.take_along_axis(ref, first[:, :, None], -1)[:, :, 0]
        out["control"] = (best - took)[mask]
    if not np.isfinite(ref).all():
        raise RuntimeError("reference logits are not finite")
    return out


def check(run, done):
    """The comparison that decides ``correct``: every number beside its
    limit. With ``--control`` the control's readings stand in the
    program's place, and ``correct`` has to come out false."""
    limits = run.mix["check"]["limits"]
    sample = pick_sample(run, done)
    if not sample:
        return [["sampled_requests", 0, ">=1"]], False
    t = pc()
    g = served_gaps(run, sample, run.control)
    run.note(phase="reference", seconds=pc() - t, requests=len(sample),
             served_tokens=int(g["served"].size),
             longest=max(len(r.req["prompt"]) + len(r.creq.output_ids)
                         for r in sample))
    held = g["served"]
    if run.control:
        run.note(phase="control", in_the_programs_place=run.control,
                 program_gap_max=float(held.max()),
                 program_gap_mean=float(held.mean()))
        held = g["control"]
    checks = [["gap_max", float(held.max()), float(limits["gap_max"])],
              ["gap_mean", float(held.mean()), float(limits["gap_mean"])]]
    integrity = sum(len(r.stamps) != len(r.creq.output_ids)
                    or [t_ for _, t_ in r.stamps] != list(r.creq.output_ids)
                    for r in done)
    checks.append(["streamed_ne_final", int(integrity), 0])
    return checks, all(v <= lim for _, v, lim in checks)


def sweep(run, rates, seconds):
    """Find the knee: one window per rate in one process, each with a
    seed of its own (the run's seed plus the window's number)."""
    for i, rate in enumerate(rates):
        sent, facts = window(run, seconds, rate=rate, seed=run.seed + i)
        e2e, out, done = measure(run, sent, facts)
        # empty the engine before the next rate: what still runs is
        # cancelled (its tails were already read)
        drained = pc()
        for r in sent:
            if r.creq is not None and not r.creq.done:
                run.cluster.cancel(r.creq)
        until = pc() + 120
        while run.engine._live and pc() < until:
            time.sleep(0.05)
        run.note(phase="sweep", rate_per_s=rate, seed=run.seed + i,
                 seconds=seconds,
                 cancelled_at_s=drained - facts["t_close"], **e2e, **out,
                 share_first_token_in_window=1 - out.get(
                     "backlog_at_close", 0) / max(1, out["attempted"]),
                 requests=[[len(r.req["prompt"]), r.req["max_new_tokens"],
                            round(r.req.get("due", 0.0), 3),
                            round(r.stamps[0][0] - r.due, 4)
                            if r.stamps else None] for r in sent])


def run(run):
    build(run)
    if run.sweep:
        sweep(run, run.sweep, run.seconds)
        run.cluster.stop()
        return
    run.setup_s = pc() - run.t0
    sent, facts = window(run, run.seconds)
    e2e, out, done = measure(run, sent, facts)
    run.note(phase="window", compiles_in_window=facts["compiles_in_window"],
             cache=program.cache_stats(), **e2e, **out,
             engine_generated_tokens=program.metric(
                 "serving_generated_tokens_total"),
             engine_completed=program.metric(
                 "serving_requests_completed_total"))
    run.e2e = dict(e2e, setup_s=run.setup_s)
    run.attempted, run.failed = out["attempted"], out["failed"]
    run.facts.update(out)
    run.memory_peak = run.read_memory_peak()
    # beside the device's peak, which counts the page pool as reserved:
    # what the traffic filled of it
    run.device.update(
        kv_live_tokens_peak=out["kv_live_tokens_peak"],
        kv_pool_share_peak=out["kv_pool_share_peak_engine_gauge"])
    if run.trace:
        read_trace(run, sent, facts)
    # free the program's state before the reference runs
    run.cluster.stop()
    run.family.release(run.engine)
    run.cluster = run.engine = run.model = None
    gc.collect()
    run.checks, ok = check(run, done)
    run.correct = bool(ok and run.failed == 0)
    run.checks.append(["failed_requests", run.failed, 0])
