"""Driver benchmark: Llama training step MFU on the real chip + Pallas
flash-attention vs XLA micro-benchmark with an on-device parity check.

Prints exactly ONE JSON line to stdout:
  {"metric": "llama_train_mfu", "value": <mfu>, "unit": "fraction_of_peak",
   "vs_baseline": <mfu / 0.40>, ...diagnostic keys...}

The 0.40 baseline is the BASELINE.md north star (Llama pretraining >= 40%
MFU). Reference bar for the harness itself: `tools/ci_op_benchmark.sh`,
`python/paddle/profiler/timer.py` (ips benchmarking).
"""

import json
import os
import sys
import time

import numpy as np


def log(*a):
    print(*a, file=sys.stderr, flush=True)


# bf16 peak FLOP/s per chip by device kind (MXU peak, the MFU denominator)
PEAK_FLOPS = {
    "TPU v2": 46e12,
    "TPU v3": 123e12,
    "TPU v4": 275e12,
    "TPU v5 lite": 197e12,
    "TPU v5e": 197e12,
    "TPU v5": 459e12,
    "TPU v5p": 459e12,
    "TPU v6 lite": 918e12,
    "TPU v6e": 918e12,
}


def device_peak():
    import jax
    d = jax.devices()[0]
    if d.platform != "tpu":
        return 1e12, d.platform  # nominal; bench is only meaningful on TPU
    return PEAK_FLOPS.get(d.device_kind, 197e12), d.device_kind


#: bump when the snapshot layout changes; tools/bench_check.py refuses
#: to diff snapshots whose schema versions disagree
BENCH_SCHEMA_VERSION = 1

#: the knobs that change what a bench run measures — stamped into every
#: snapshot so a regression diff can rule out "different config"
_PROVENANCE_KNOBS = (
    "PADDLE_TPU_METRICS", "PADDLE_TPU_SERVING_Q8",
)


def bench_provenance():
    """The identity block every snapshot carries: what ran, where, and
    under which knobs — so a later ``bench_check`` diff can tell a real
    regression from a config or platform change."""
    from paddle_tpu.observability import perf as _perf

    info = _perf.build_info()
    return {
        "git_commit": info["git_commit"],
        "jax_version": info["jax_version"],
        "device_kind": info["device_kind"],
        "wall_clock_unix": round(time.time(), 3),
        "env": {k: os.environ[k] for k in _PROVENANCE_KNOBS
                if k in os.environ},
    }


def bench_train_step(cfg_kw, batch, seq, steps=10, amp=True):
    """Train-step wall time through to_static; returns a result dict.

    Every TIMED step consumes a FRESH batch through the
    ``DevicePrefetcher`` (double-buffered async host->device copy) with
    the step's ids/labels buffers donated — the real recipe's input
    path, so the measured MFU pays (or hides) the transfer cost a
    replayed device-resident batch would mask. ``input_stall_frac``
    reports the fraction of the timed window the loop spent blocked on
    input."""
    import paddle_tpu as paddle
    from paddle_tpu.io import DevicePrefetcher
    from paddle_tpu.models import LlamaForCausalLM, LlamaConfig

    paddle.seed(0)
    cfg = LlamaConfig(**cfg_kw)
    model = LlamaForCausalLM(cfg)
    opt = paddle.optimizer.AdamW(learning_rate=1e-4, weight_decay=0.01,
                                 parameters=model.parameters())

    use_amp = amp and hasattr(paddle.amp, "auto_cast")

    def step(ids, labels):
        if use_amp:
            with paddle.amp.auto_cast(dtype="bfloat16"):
                loss, _ = model(ids, labels)
        else:
            loss, _ = model(ids, labels)
        loss.backward()
        opt.step()
        opt.clear_grad()
        return loss

    compiled = paddle.jit.to_static(step, state=[model, opt],
                                    warmup="once", donate_inputs=True)

    # the prefetch worker draws from its OWN stream: sharing one
    # RandomState with the main thread's warmup draw would make seeded
    # runs scheduler-dependent
    rng = np.random.RandomState(0)
    feed_rng = np.random.RandomState(1)

    def host_batches():
        while True:
            yield feed_rng.randint(0, cfg.vocab_size,
                                   (batch, seq + 1)).astype(np.int64)

    feed = DevicePrefetcher(
        host_batches(),
        transform=lambda ids: (np.ascontiguousarray(ids[:, :-1]),
                               np.ascontiguousarray(ids[:, 1:])))

    def batch_of():
        x, y = next(feed)
        return paddle.to_tensor(x), paddle.to_tensor(y)

    try:
        # eager warmup on a tiny shape (materializes optimizer
        # accumulators without holding full-size eager intermediates in
        # HBM) ...
        wids = rng.randint(0, cfg.vocab_size, (1, 257)).astype(np.int64)
        compiled(paddle.to_tensor(wids[:, :-1]),
                 paddle.to_tensor(wids[:, 1:]))
        # ... then the real shape compiles directly
        t0 = time.perf_counter()
        loss = compiled(*batch_of())
        compile_s = time.perf_counter() - t0
        log(f"compile {compile_s:.1f}s  first loss {float(loss):.4f}")

        compiled(*batch_of())  # one steady-state call before timing
        feed.mark()
        t0 = time.perf_counter()
        for _ in range(steps):
            loss = compiled(*batch_of())
        lossf = float(loss)  # host sync: blocks until every step done
        elapsed = time.perf_counter() - t0
        stall, _ = feed.mark()
    finally:
        feed.close()
    step_time = elapsed / steps

    tokens = batch * seq
    flops = model.flops_per_token(seq) * tokens
    peak, kind = device_peak()
    mfu = flops / step_time / peak
    # pin the model for the decode bench only on SUCCESS — a failed
    # candidate must be garbage-collected before the fallback allocates
    bench_train_step.last_model = model
    return {
        "model": f"llama-h{cfg.hidden_size}-L{cfg.num_hidden_layers}",
        "n_params": model.num_params(),
        "batch": batch, "seq": seq,
        "amp_bf16": use_amp,
        "step_time_ms": round(step_time * 1e3, 3),
        "tokens_per_sec": round(tokens / step_time, 1),
        "mfu": round(mfu, 4),
        "input_stall_frac": round(stall / max(elapsed, 1e-9), 4),
        "final_loss": round(lossf, 4),
        "compile_s": round(compile_s, 1),
        "device": kind,
        "peak_flops": peak,
    }


def _timed(fn):
    t0 = time.perf_counter()
    fn()
    return time.perf_counter() - t0


def bench_decode(model, batch=4, prompt=128, new_tokens=64):
    """Static-KV-cache serving throughput: steady-state decode tok/s."""
    import paddle_tpu as paddle

    rng = np.random.RandomState(0)
    ids = paddle.to_tensor(rng.randint(
        0, model.config.vocab_size, (batch, prompt)).astype(np.int64))
    model.eval()
    # warm both shapes (prefill + single-token step) to steady state
    model.generate(ids, max_new_tokens=new_tokens)
    model.generate(ids, max_new_tokens=new_tokens)
    model.generate(ids, max_new_tokens=1)
    # best-of-3 on both timed sections: this number is the serving
    # comparisons' denominator
    t_prefill = min(_timed(lambda: model.generate(ids, max_new_tokens=1))
                    for _ in range(3))
    t_full = min(_timed(lambda: model.generate(
        ids, max_new_tokens=new_tokens)) for _ in range(3))
    model.train()
    # steady-state decode: the extra (new_tokens - 1) steps beyond the
    # prefill-only call
    dt = max(t_full - t_prefill, 1e-9)
    steps = new_tokens - 1
    return {
        "decode_batch": batch,
        "decode_new_tokens": new_tokens,
        "decode_prefill_ms": round(t_prefill * 1e3, 3),
        "decode_tokens_per_sec": round(batch * steps / dt, 1),
        "decode_ms_per_token": round(dt / steps * 1e3, 3),
    }


def bench_flash(batch=4, seq=2048, heads=16, kv_heads=8, dim=128, iters=20):
    """Pallas flash kernel vs XLA attention, fwd+bwd, on device."""
    import jax
    import jax.numpy as jnp
    from paddle_tpu.ops import flash_attention as FA
    from paddle_tpu.nn.functional.attention import _naive_attention

    rng = np.random.RandomState(0)
    dt = jnp.bfloat16 if jax.default_backend() == "tpu" else jnp.float32
    q = jnp.asarray(rng.randn(batch, seq, heads, dim), dt)
    k = jnp.asarray(rng.randn(batch, seq, kv_heads, dim), dt)
    v = jnp.asarray(rng.randn(batch, seq, kv_heads, dim), dt)
    assert FA.supported(q, k, v, None, True), "Pallas preconditions not met"
    fa = FA._make_flash(1.0 / np.sqrt(dim), True, heads // kv_heads)

    def loss_fa(q, k, v):
        return jnp.sum(fa(q, k, v).astype(jnp.float32))

    def loss_xla(q, k, v):
        return jnp.sum(
            _naive_attention(q, k, v, None, 0.0, True, None)
            .astype(jnp.float32))

    def timeit(f, *args):
        g = jax.jit(jax.grad(f, argnums=(0, 1, 2)))
        out = g(*args)
        jax.block_until_ready(out)
        t0 = time.perf_counter()
        for _ in range(iters):
            out = g(*args)
        jax.block_until_ready(out)
        return (time.perf_counter() - t0) / iters * 1e3

    pallas_ms = timeit(loss_fa, q, k, v)
    xla_ms = timeit(loss_xla, q, k, v)
    # parity on device: fwd outputs and dq
    o_p = fa(q, k, v).astype(jnp.float32)
    o_x = _naive_attention(q, k, v, None, 0.0, True, None).astype(jnp.float32)
    fwd_err = float(jnp.max(jnp.abs(o_p - o_x)))
    g_p = jax.grad(loss_fa)(q, k, v).astype(jnp.float32)
    g_x = jax.grad(loss_xla)(q, k, v).astype(jnp.float32)
    bwd_err = float(jnp.max(jnp.abs(g_p - g_x)))
    scale = float(jnp.max(jnp.abs(o_x)))
    gscale = float(jnp.max(jnp.abs(g_x)))
    return {
        "flash_pallas_ms": round(pallas_ms, 3),
        "flash_xla_ms": round(xla_ms, 3),
        "flash_speedup": round(xla_ms / pallas_ms, 3),
        "flash_fwd_max_err": round(fwd_err, 5),
        "flash_dq_max_err": round(bwd_err, 5),
        "flash_parity_ok": bool(fwd_err < 0.05 * max(scale, 1.0)
                                and bwd_err < 0.05 * max(gscale, 1.0)),
        "pallas_branch": True,
    }


def bench_paged(batch=8, heads=16, kv_heads=8, dim=128, page=64,
                ctx=2048, iters=50):
    """Paged-attention decode kernel vs XLA gather path, on device."""
    import jax
    import jax.numpy as jnp
    from paddle_tpu.ops import paged_attention as PA

    rng = np.random.RandomState(0)
    dt = jnp.bfloat16 if jax.default_backend() == "tpu" else jnp.float32
    max_pages = ctx // page
    num_pages = batch * max_pages + 8
    q = jnp.asarray(rng.randn(batch, heads, dim), dt)
    kp = jnp.asarray(rng.randn(num_pages, kv_heads, page, dim), dt)
    vp = jnp.asarray(rng.randn(num_pages, kv_heads, page, dim), dt)
    perm = rng.permutation(num_pages)[:batch * max_pages]
    tables = jnp.asarray(perm.reshape(batch, max_pages), jnp.int32)
    lens = jnp.asarray(
        rng.randint(ctx // 2, ctx + 1, (batch,)), jnp.int32)

    def timeit(f):
        g = jax.jit(f)
        out = g(q, kp, vp, tables, lens)
        jax.block_until_ready(out)
        t0 = time.perf_counter()
        for _ in range(iters):
            out = g(q, kp, vp, tables, lens)
        jax.block_until_ready(out)
        return (time.perf_counter() - t0) / iters * 1e3, out

    def pallas_path(q, kp, vp, tables, lens):
        return PA._paged_impl(q, kp, vp, tables, lens,
                              scale=1.0 / float(np.sqrt(dim)))

    pallas_ms, o_p = timeit(pallas_path)
    xla_ms, o_x = timeit(PA.paged_attention_xla)
    err = float(jnp.max(jnp.abs(o_p.astype(jnp.float32)
                                - o_x.astype(jnp.float32))))
    scale = float(jnp.max(jnp.abs(o_x.astype(jnp.float32))))
    return {
        "paged_pallas_ms": round(pallas_ms, 3),
        "paged_xla_ms": round(xla_ms, 3),
        "paged_speedup": round(xla_ms / pallas_ms, 3),
        "paged_parity_ok": bool(err < 0.05 * max(scale, 1.0)),
    }


def bench_serving(model, n_requests=24, new_tokens=48, max_batch=16,
                  decode_ceiling=None, on_tpu=True):
    """Chunked-prefill engine throughput: ragged prompts admitted on the
    fly over ONE mixed prefill+decode program (the ragged paged-
    attention kernel). Three regimes:

    - ``serving_tokens_per_sec``: the historical e2e number — admit
      n_requests ragged prompts, run to completion (prefill + decode +
      admission bookkeeping included).
    - ``serving_steady_tokens_per_sec`` (+ ``serving_ceiling_frac``):
      a full batch on the scanned decode path, no retirements — the
      sustained rate vs the raw decode ceiling.
    - ``serving_chunked_tokens_per_sec`` (+ TTFT p50/p99): the MIXED
      workload — long prompts admitted while a decode-heavy batch is
      live, chunks interleaving with decodes every step. The gate
      ``serving_chunked_ok`` requires >= 1.5x the e2e rate measured in
      the same run."""
    from paddle_tpu.inference.serving import LlamaServingEngine, Request

    model.eval()
    engine = LlamaServingEngine(model, max_batch=max_batch, page_size=64,
                                num_pages=max_batch * 8 + 8,
                                max_pages_per_seq=8, decode_ticks=32)
    rng = np.random.RandomState(0)
    v = model.config.vocab_size
    prompts = [rng.randint(0, v, (int(rng.randint(16, 128)),)).tolist()
               for _ in range(n_requests)]
    # warm TWICE: pass 1 traces, pass 2 lands both mixed-program shapes
    # and the full-length scan in the compile cache
    engine.generate(prompts, max_new_tokens=2)
    engine.generate(prompts, max_new_tokens=engine.decode_ticks + 2)
    t0 = time.perf_counter()
    outs = engine.generate(prompts, max_new_tokens=new_tokens)
    dt = time.perf_counter() - t0
    total = sum(len(o) for o in outs)
    e2e = total / dt

    # steady-state decode throughput: a full batch scanning with no
    # retirements (the engine's sustained rate, free of prefill and
    # admission bookkeeping)
    rng2 = np.random.RandomState(1)
    for _ in range(max_batch):
        engine.add_request(Request(
            rng2.randint(0, v, (32,)).tolist(),
            max_new_tokens=new_tokens * 8 + 64))
    engine.decode_many(engine.decode_ticks)  # warm the scan path
    # best-of-3: a single timed window under-reports the engine's
    # sustained rate
    steady = 0.0
    for _ in range(3):
        t0 = time.perf_counter()
        served = engine.decode_many(new_tokens * 2)
        steady = max(steady, served / (time.perf_counter() - t0))
    for r in list(engine._live.values()):
        engine.alloc.release(r.seq_id)
        engine._live.pop(r.seq_id)

    # mixed long-prompt + decode-heavy workload: decode-bound requests
    # stay live while multi-chunk prompts stream in; TTFT of each long
    # admission is measured with the batch busy (the number the old
    # wave/burst split could not bound)
    n_dec = max(1, max_batch - 2)
    decoders = [Request(rng2.randint(0, v, (32,)).tolist(),
                        max_new_tokens=100000)
                for _ in range(n_dec)]
    for r in decoders:
        engine.add_request(r)
    long_len = 4 * engine.page_size          # 4 pages, multi-chunk
    n_long = 6 if on_tpu else 3
    ttfts = []
    done0 = sum(len(r.output_ids) for r in decoders)
    longs = []
    t0 = time.perf_counter()
    for i in range(n_long):
        lr = Request(rng2.randint(0, v, (long_len,)).tolist(),
                     max_new_tokens=4)
        longs.append(lr)
        ts = time.perf_counter()
        engine.add_request(lr)               # chunks + decodes interleave
        ttfts.append(time.perf_counter() - ts)
        engine.decode_many(8 if on_tpu else 4)
    dt_mixed = time.perf_counter() - t0
    # mixed throughput counts every token the engine PROCESSED in the
    # window: decode tokens emitted plus prompt tokens chunk-prefilled
    # (the standard chunked-prefill accounting — prefill is the work
    # the old wave/burst split serialized)
    mixed_tokens = (sum(len(r.output_ids) for r in decoders) - done0
                    + sum(len(r.output_ids) + r._prefilled
                          for r in longs))
    chunked = mixed_tokens / dt_mixed
    for r in list(engine._live.values()):
        engine.cancel(r)
    engine.close()
    model.train()
    out = {
        "serving_requests": n_requests,
        "serving_tokens": total,
        "serving_tokens_per_sec": round(e2e, 1),
        "serving_steady_tokens_per_sec": round(steady, 1),
        "serving_chunked_tokens_per_sec": round(chunked, 1),
        "serving_chunked_speedup": round(chunked / max(e2e, 1e-9), 3),
        "serving_chunked_ok": bool(chunked >= 1.5 * e2e),
        "serving_ttft_p50_ms": round(
            float(np.percentile(ttfts, 50)) * 1e3, 2),
        "serving_ttft_p99_ms": round(
            float(np.percentile(ttfts, 99)) * 1e3, 2),
        "serving_max_batch": max_batch,
        "serving_chunk_budget": engine.chunk_budget,
        "serving_chunk_block": engine.chunk_block,
        "serving_decode_ticks": engine.decode_ticks,
    }
    if decode_ceiling:
        out["serving_ceiling_frac"] = round(steady / decode_ceiling, 3)
    return out


def bench_prefix_cluster(model, on_tpu=True):
    """Shared-prefix KV cache + multi-replica cluster (ROADMAP item 2):
    TTFT for a prompt whose page-aligned prefix is already cached vs a
    cold prompt of identical shape, the cache hit rate, and aggregate
    tokens/sec routed over in-process engine replicas. Tracks the
    scale-out trajectory the way serving_tokens_per_sec tracks the
    single engine."""
    from paddle_tpu.inference.cluster import ServingCluster
    from paddle_tpu.inference.serving import LlamaServingEngine, Request

    model.eval()
    page = 64 if on_tpu else 8
    prefix_pages = 16 if on_tpu else 32   # 1024- / 256-token prefix
    # CPU smoke runs measure the pure prefix win (1 un-cached token);
    # on the chip the margin is structural (a [B, 1088]-bucket dense
    # prefill vs a handful of decode dispatches), so a realistic
    # suffix is kept
    suffix = 8 if on_tpu else 1
    max_batch = 8 if on_tpu else 2
    pps = prefix_pages + 4
    kw = dict(max_batch=max_batch, page_size=page,
              num_pages=max_batch * pps + prefix_pages * 4 + 8,
              max_pages_per_seq=pps)
    engine = LlamaServingEngine(model, **kw)
    rng = np.random.RandomState(7)
    v = model.config.vocab_size

    def prompt_with(prefix, seed):
        sfx = np.random.RandomState(seed).randint(0, v, (suffix,))
        return prefix + sfx.tolist()

    # land the prefill bucket + decode programs outside the timed
    # windows, then drop the warmup prompt's cache entries
    warm = rng.randint(0, v, (prefix_pages * page,)).tolist()
    engine.generate([prompt_with(warm, 0)], max_new_tokens=2)
    engine.prefix.clear()
    shared = rng.randint(0, v, (prefix_pages * page,)).tolist()

    def ttft(prompt):
        r = Request(prompt, max_new_tokens=1)
        t0 = time.perf_counter()
        engine.add_request(r)      # prefill emits the first token
        return time.perf_counter() - t0

    ttft(prompt_with(shared, 1))   # cold fill: prefix enters the cache
    ttft(prompt_with(shared, 2))   # first hit pays the suffix-path warm
    t_cold = min(ttft(prompt_with(
        rng.randint(0, v, (prefix_pages * page,)).tolist(), 10 + i))
        for i in range(3))
    t_warm = min(ttft(prompt_with(shared, 20 + i)) for i in range(3))
    s = engine.prefix.stats()
    engine.close()
    out = {
        "serving_prefix_cold_ttft_ms": round(t_cold * 1e3, 3),
        "serving_prefix_ttft_ms": round(t_warm * 1e3, 3),
        "serving_prefix_ttft_speedup": round(t_cold / max(t_warm, 1e-9),
                                             3),
        "serving_prefix_hit_rate": round(s["hit_rate"], 4),
        "serving_prefix_saved_tokens": s["saved_tokens"],
    }

    # cluster throughput: shared-prefix workload over N replicas, each
    # with its own engine + prefix cache (prefill once PER REPLICA)
    n_replicas = 2
    cluster = ServingCluster(lambda: LlamaServingEngine(model, **kw),
                             num_replicas=n_replicas, ttl=60.0)
    cluster.start()
    new_toks = 32 if on_tpu else 4
    n_req = 16 if on_tpu else 4
    for c in [cluster.submit(prompt_with(shared, 50 + i),
                             max_new_tokens=2)
              for i in range(n_replicas * 2)]:
        c.result(timeout=600)      # warm both replicas' programs
    t0 = time.perf_counter()
    creqs = [cluster.submit(prompt_with(shared, 100 + i),
                            max_new_tokens=new_toks)
             for i in range(n_req)]
    outs = [c.result(timeout=600) for c in creqs]
    dt = time.perf_counter() - t0
    cluster.stop()
    out.update({
        "serving_cluster_replicas": n_replicas,
        "serving_cluster_requests": n_req,
        "serving_cluster_tokens_per_sec": round(
            sum(len(o) for o in outs) / dt, 1),
    })
    return out


def bench_speculative(model, on_tpu=True):
    """Speculative decoding gates (ROADMAP item 3a): a self-speculative
    (n-gram prompt-lookup) engine vs the same chunked engine with
    speculation off, on the same decode-heavy workload.

    Both engines are driven by the SERVING loop regime — one
    :meth:`step` per tick, the way a cluster replica's worker actually
    serves (a multi-tick decode scan would block admissions and prompt
    chunks for its whole length, so the admission-responsive tick is
    the production decode path). In that regime every non-speculative
    tick emits exactly one token per live row; speculation multiplies
    what one dispatch commits — exactly the dispatch-amortization lever
    named in ROADMAP item 3.

    - ``spec_parity_ok``: greedy outputs TOKEN-EXACT vs the
      non-speculative engine — the hard gate; speculation may only
      change dispatch counts, never a token.
    - ``spec_accept_rate`` / ``serving_spec_tokens_per_dispatch``: how
      much each verify dispatch commits.
    - ``serving_spec_tokens_per_sec`` + ``spec_throughput_ok``: >= 1.3x
      the chunked baseline measured in the same run (CPU smoke gate;
      greedy decode settles into repetition the drafter locks onto).
    - ``serving_spec_batch_tokens_per_sec`` (informational): the same
      engines under the batch :meth:`generate` regime, where the
      baseline may amortize host round trips with decode scans and the
      speculative engine auto-falls back to them when the drafter has
      nothing (speculation never costs more than not speculating)."""
    from paddle_tpu.inference.serving import LlamaServingEngine, Request

    model.eval()
    kw = dict(max_batch=2, page_size=16, num_pages=48,
              max_pages_per_seq=8, chunk_block=16, chunk_budget=16,
              prefix_cache=False)
    # long enough for greedy decode to settle into the repetition the
    # drafter locks onto — the first few dozen tokens are a cold
    # history with nothing to propose
    new_toks = 96
    rng = np.random.RandomState(0)
    v = model.config.vocab_size
    cands = [rng.randint(0, v, (12,)).tolist() for _ in range(4)]
    pairs = [[p, p[::-1]] for p in cands]

    def serve_loop(spec_k):
        e = LlamaServingEngine(model, spec_k=spec_k, **kw)
        # pair 0 warms every dispatched shape end to end; pairs 1..N
        # are the timed workload (one engine, compile excluded)
        e.generate(pairs[0], max_new_tokens=4)
        warm = [Request(p, max_new_tokens=new_toks) for p in pairs[0]]
        for r in warm:
            e.add_request(r)
        while not all(r.done for r in warm):
            e.step()
        tokens, dt, dispatches, outs = 0, 0.0, 0, []
        for pair in pairs[1:]:
            reqs = [Request(p, max_new_tokens=new_toks) for p in pair]
            for r in reqs:
                e.add_request(r)
            d0 = e._dispatch_count
            pre = sum(len(r.output_ids) for r in reqs)
            t0 = time.perf_counter()
            while not all(r.done for r in reqs):
                e.step()
            dt += time.perf_counter() - t0
            dispatches += e._dispatch_count - d0
            tokens += sum(len(r.output_ids) for r in reqs) - pre
            outs.append([r.output_ids for r in reqs])
        stats = e.spec_stats()
        # batch regime (scans allowed) on the same engine, second pass
        t0 = time.perf_counter()
        bouts = e.generate(pairs[1], max_new_tokens=new_toks)
        bt = sum(len(o) for o in bouts) / (time.perf_counter() - t0)
        e.close()
        return (tokens / dt, tokens / max(1, dispatches), stats, outs,
                bt)

    base_tps, base_tpd, _, outs_base, base_batch = serve_loop(0)
    spec_tps, spec_tpd, stats, outs_spec, spec_batch = serve_loop(7)
    model.train()
    return {
        "spec_parity_ok": bool(outs_spec == outs_base),
        "spec_k": stats["k"],
        "spec_accept_rate": round(stats["accept_rate"], 4),
        "serving_spec_tokens_per_dispatch": round(spec_tpd, 3),
        "serving_spec_baseline_tokens_per_dispatch": round(base_tpd, 3),
        "serving_spec_tokens_per_sec": round(spec_tps, 1),
        "serving_spec_baseline_tokens_per_sec": round(base_tps, 1),
        "spec_speedup": round(spec_tps / max(base_tps, 1e-9), 3),
        "spec_throughput_ok": bool(spec_tps >= 1.3 * base_tps),
        "serving_spec_batch_tokens_per_sec": round(spec_batch, 1),
        "serving_spec_batch_baseline_tokens_per_sec": round(base_batch,
                                                            1),
    }


def bench_kv_int8(model, on_tpu=True):
    """Int8 KV-page gates (ROADMAP item 3b).

    - ``kv_int8_parity_ok``: attention over int8 pages + scale
      sidecars within exact-logit tolerance of float pages (the same
      0.05x-scale bar as every other ``*_parity_ok`` kernel gate).
    - ``kv_int8_capacity_x``: float KV bytes / int8 KV bytes per cached
      token (sidecars counted) — how many times more tokens one HBM
      pool admits before the degradation ladder fires (~2x at bf16
      head_dim 128; higher for f32 pools).
    - ``kv_int8_tokens_per_sec``: the int8 engine on the e2e workload
      (the win is capacity, not speed — this guards against a
      dequant-path regression)."""
    import jax
    import jax.numpy as jnp
    from paddle_tpu.inference.paged_cache import quantize_kv_int8
    from paddle_tpu.inference.serving import LlamaServingEngine
    from paddle_tpu.ops import ragged_paged_attention as RPA

    rng = np.random.RandomState(0)
    dt = jnp.bfloat16 if on_tpu else jnp.float32
    rows, qb, h, hk, d = (8, 16, 16, 8, 128) if on_tpu \
        else (4, 8, 4, 2, 32)
    page, w = (64, 32) if on_tpu else (8, 8)
    num_pages = rows * w + 8
    q = jnp.asarray(rng.randn(rows, qb, h, d), dt)
    kf = jnp.asarray(rng.randn(num_pages, hk, page, d), dt)
    vf = jnp.asarray(rng.randn(num_pages, hk, page, d), dt)
    kq, ks = quantize_kv_int8(kf)
    vq, vs = quantize_kv_int8(vf)
    ks, vs = ks[..., None], vs[..., None]
    tables = jnp.asarray(rng.permutation(num_pages)[:rows * w]
                         .reshape(rows, w), jnp.int32)
    q_lens = np.asarray([1 if i % 2 else qb for i in range(rows)],
                        np.int32)
    kv = np.maximum(rng.randint(page, page * w + 1, (rows,))
                    .astype(np.int32), q_lens)
    q_starts = jnp.asarray(kv - q_lens)
    kv_lens, q_lens = jnp.asarray(kv), jnp.asarray(q_lens)

    ref = jax.jit(RPA.ragged_paged_attention_xla)(
        q, kf, vf, tables, kv_lens, q_starts, q_lens)
    got = jax.jit(RPA.ragged_paged_attention_xla)(
        q, kq, vq, tables, kv_lens, q_starts, q_lens, k_scale=ks,
        v_scale=vs)
    err = float(jnp.max(jnp.abs(got.astype(jnp.float32)
                                - ref.astype(jnp.float32))))
    scale = float(jnp.max(jnp.abs(ref.astype(jnp.float32))))

    model.eval()
    kw = dict(max_batch=2, page_size=16 if on_tpu else 8, num_pages=64,
              max_pages_per_seq=16, chunk_block=8, chunk_budget=16,
              prefix_cache=False)
    rng2 = np.random.RandomState(1)
    v = model.config.vocab_size
    prompts = [rng2.randint(0, v, (12,)).tolist() for _ in range(2)]
    new_toks = 64 if on_tpu else 24
    q8e = LlamaServingEngine(model, kv_dtype="int8", **kw)
    q8e.generate(prompts, max_new_tokens=q8e.decode_ticks + 2)
    t0 = time.perf_counter()
    outs = q8e.generate(prompts, max_new_tokens=new_toks)
    dt_q8 = time.perf_counter() - t0
    q8_bytes = q8e.kv_bytes_per_token
    q8e.close()
    fpe = LlamaServingEngine(model, **kw)
    fp_bytes = fpe.kv_bytes_per_token
    fpe.close()
    model.train()
    return {
        "kv_int8_max_err": round(err, 5),
        "kv_int8_parity_ok": bool(err < 0.05 * max(scale, 1.0)),
        "kv_int8_capacity_x": round(fp_bytes / q8_bytes, 3),
        "kv_page_bytes_per_token": q8_bytes,
        "kv_fp_page_bytes_per_token": fp_bytes,
        "kv_int8_tokens_per_sec": round(
            sum(len(o) for o in outs) / dt_q8, 1),
    }


def bench_weight_int8(model, on_tpu=True):
    """Weight-only int8 serving gates (ROADMAP item 3, weight side;
    ``paddle_tpu/quant``).

    - ``weight_int8_greedy_match`` / ``weight_int8_parity_ok``: the
      bundled-prompt quality gate (``quant/quality.py``) on a briefly
      prompt-fitted copy of the bench model — greedy-match >= 0.99 and
      logits error within the 0.05x-scale budget (the stated bars;
      random-init models measure tie-breaking noise instead, see
      ``quality.fit_on_prompts``).
    - ``weight_int8_capacity_x``: bf16 weight bytes / as-served bytes
      (int8 + f32 scale sidecars + the float leftovers — embeddings,
      norms, lm_head — all counted). ~2x on real configs where
      projections dominate; the small-vocab bench config lands lower
      because its embedding slice is proportionally large, so the gate
      is >= 1.4.
    - ``weight_int8_dequant_ms`` vs ``weight_int8_dequant_xla_ms``:
      fused (in-VMEM dequant) Pallas kernel vs the exact XLA
      formulation on the model's MLP projection shape (TPU only).
    - ``weight_int8_tokens_per_sec`` / ``weight_bf16_tokens_per_sec``:
      e2e serving throughput both paths, plus
      ``weight_int8_token_match`` (greedy e2e agreement)."""
    import copy

    import jax
    import jax.numpy as jnp

    from paddle_tpu.inference.serving import LlamaServingEngine
    from paddle_tpu.quant import quality
    from paddle_tpu.quant.format import (quantize_model, quantize_weight,
                                         serving_weight_bytes)
    from paddle_tpu.quant.kernels import _dequant_matmul

    block = 128 if on_tpu else 64

    # -- quality gate on prompt-fitted copies --------------------------
    mfp = copy.deepcopy(model)
    quality.fit_on_prompts(mfp, steps=40)
    mfp.eval()
    mq = copy.deepcopy(mfp)
    quantize_model(mq, block=block)
    rep = quality.logits_quality(mfp, mq)

    # -- capacity: judged against the bf16 counterfactual --------------
    if hasattr(mq, "bfloat16"):
        mcap = copy.deepcopy(mq).bfloat16()   # int8 buffers survive
    else:
        mcap = mq
    actual, bf16_base, _ = serving_weight_bytes(mcap)
    capacity_x = bf16_base / max(actual, 1)

    # -- fused vs XLA dequant-matmul micro-bench (TPU only) ------------
    h = model.config.hidden_size
    inter = model.config.intermediate_size
    rng = np.random.RandomState(0)
    dt = jnp.bfloat16 if on_tpu else jnp.float32
    wq, ws = quantize_weight(
        jnp.asarray(rng.randn(h, inter) * 0.05, jnp.float32), block)
    xs = jnp.asarray(rng.randn(256 if on_tpu else 16, h), dt)
    dq_ms = {}
    iters = 20 if on_tpu else 2
    for key, uk in (("weight_int8_dequant_ms", True),
                    ("weight_int8_dequant_xla_ms", False)):
        if uk and not on_tpu:
            continue    # interpret-mode timing is meaningless
        f = jax.jit(lambda a, q, s, uk=uk: _dequant_matmul(
            a, q, s, block, use_kernel=uk))
        f(xs, wq, ws).block_until_ready()
        t0 = time.perf_counter()
        for _ in range(iters):
            y = f(xs, wq, ws)
        y.block_until_ready()
        dq_ms[key] = round((time.perf_counter() - t0) / iters * 1e3, 4)

    # -- e2e serving throughput, both paths ----------------------------
    kw = dict(max_batch=2, page_size=16 if on_tpu else 8, num_pages=64,
              max_pages_per_seq=16, chunk_block=8, chunk_budget=16,
              prefix_cache=False)
    v = model.config.vocab_size
    prompts = [p[:12] for p in quality.bundled_prompt_ids(v)[:2]]
    new_toks = 64 if on_tpu else 24

    q8e = LlamaServingEngine(mq, **kw)      # pre-quantized: honored
    q8_bytes = q8e.weight_bytes_per_param
    q8e.generate(prompts, max_new_tokens=q8e.decode_ticks + 2)
    t0 = time.perf_counter()
    outs_q8 = q8e.generate(prompts, max_new_tokens=new_toks)
    dt_q8 = time.perf_counter() - t0
    q8e.close()

    fpe = LlamaServingEngine(mfp, **kw)
    fpe.generate(prompts, max_new_tokens=fpe.decode_ticks + 2)
    t0 = time.perf_counter()
    outs_fp = fpe.generate(prompts, max_new_tokens=new_toks)
    dt_fp = time.perf_counter() - t0
    fpe.close()

    tok_match = sum(a == b for of, oq in zip(outs_fp, outs_q8)
                    for a, b in zip(of, oq))
    tok_total = max(sum(len(o) for o in outs_fp), 1)

    out = {
        "weight_int8_greedy_match": round(rep["greedy_match"], 4),
        "weight_int8_logits_max_err": round(rep["max_err"], 5),
        "weight_int8_parity_ok": bool(rep["passes"]),
        "weight_int8_capacity_x": round(capacity_x, 3),
        "weight_int8_capacity_ok": bool(capacity_x >= 1.4),
        "serving_weight_bytes_per_param": round(q8_bytes, 4),
        "weight_int8_token_match": round(tok_match / tok_total, 4),
        "weight_int8_tokens_per_sec": round(
            sum(len(o) for o in outs_q8) / dt_q8, 1),
        "weight_bf16_tokens_per_sec": round(
            sum(len(o) for o in outs_fp) / dt_fp, 1),
    }
    out.update(dq_ms)
    return out


def bench_restart_ttft(on_tpu=True):
    """Cold vs warm-cache restart-to-first-token for a SUBPROCESS
    serving replica (ROADMAP item 5 / PR 7): a worker process is
    started against an empty persistent compile cache (cold — it pays
    the full XLA compile bill before its self-probe's first token),
    SIGKILLed, and replaced by the supervisor; the replacement
    pre-warms the registry-recorded shape buckets against the now-warm
    cache. The delta is what makes kill-and-replace a non-event."""
    import shutil
    import tempfile

    from paddle_tpu.inference.cluster import ServingCluster

    root = tempfile.mkdtemp(prefix="paddle_tpu_restart_bench_")
    cfg = (dict(vocab_size=8192, hidden_size=512, intermediate_size=1408,
                num_hidden_layers=8, num_attention_heads=8,
                num_key_value_heads=4) if on_tpu else
           dict(vocab_size=512, hidden_size=256, intermediate_size=512,
                num_hidden_layers=4, num_attention_heads=4,
                num_key_value_heads=2))
    spec = {"model": {"kind": "tiny_llama", "seed": 0, "config": cfg},
            "engine": dict(max_batch=4 if on_tpu else 2,
                           page_size=16 if on_tpu else 8,
                           num_pages=128 if on_tpu else 48)}
    env = {"JAX_COMPILATION_CACHE_DIR": os.path.join(root, "cache"),
           "PADDLE_TPU_SHAPE_REGISTRY": os.path.join(root, "shapes.json")}
    cluster = ServingCluster(
        engine_spec=spec, num_replicas=1,
        store_path=os.path.join(root, "members"), ttl=30.0,
        monitor_interval=0.05, restart_backoff=0.05,
        spawn_grace=900.0, subprocess_env=env).start()
    try:
        deadline = time.time() + 900
        rep = cluster.replicas()["replica-0"]
        while not rep.ready() and time.time() < deadline:
            time.sleep(0.2)
        cold = rep.restart_ttft
        # a little real load so decode lands in the shape registry via
        # actual dispatches, then SIGKILL: the supervised replacement
        # path IS the measured path
        cluster.submit([1, 2, 3], max_new_tokens=4).result(timeout=600)
        pid = rep._proc.pid
        rep.kill()
        deadline = time.time() + 900
        while time.time() < deadline:
            rep = cluster.replicas()["replica-0"]
            if rep.alive() and rep.ready() and rep._proc.pid != pid:
                break
            time.sleep(0.2)
        warm = rep.restart_ttft
        hits = (rep.cache_stats or {}).get("hits", 0)
    finally:
        cluster.stop()
        shutil.rmtree(root, ignore_errors=True)
    return {
        "serving_restart_cold_ttft_ms": round(cold * 1e3, 1),
        "serving_restart_ttft_ms": round(warm * 1e3, 1),
        "serving_restart_ttft_speedup": round(cold / max(warm, 1e-9), 3),
        "serving_restart_cache_hits": hits,
    }


def bench_store_failover(on_tpu=True):
    """Control-plane store cost (ROADMAP item 4a / PR 20): per-op
    latency of the membership surface on the shared-filesystem
    FileStore vs the TCP LeaseStore, and how long membership takes to
    RE-CONVERGE after the lease server is stopped and restarted on the
    same port (client reconnect + fresh registration + a scan that
    shows every host again) — the number the chaos drills bound."""
    import shutil
    import tempfile

    from paddle_tpu.distributed.net_store import (LeaseStore,
                                                  LeaseStoreServer)
    from paddle_tpu.distributed.watchdog import FileStore

    iters = 300 if on_tpu else 60
    root = tempfile.mkdtemp(prefix="paddle_tpu_store_bench_")

    def _ops_ms(store):
        # one warm-up round so neither backend pays its first-touch
        # cost (fs clock probe / TCP session handshake) in the loop
        store.register("h0")
        store.heartbeat("h0")
        store.hosts()
        t0 = time.perf_counter()
        for _ in range(iters):
            store.heartbeat("h0")
            store.hosts()
        return (time.perf_counter() - t0) / (2 * iters) * 1e3

    try:
        file_ms = _ops_ms(FileStore(os.path.join(root, "m"), ttl=30.0))
        srv = LeaseStoreServer()
        port = srv.port
        st = LeaseStore(f"127.0.0.1:{port}", ttl=30.0, retries=6)
        try:
            tcp_ms = _ops_ms(st)
            st.register("h1")
            srv.stop()
            t0 = time.perf_counter()
            srv = LeaseStoreServer(port=port)
            deadline = time.time() + 60
            while time.time() < deadline:
                try:
                    st.register("h0")
                    st.register("h1")
                    if st.hosts() == ["h0", "h1"]:
                        break
                except OSError:
                    pass
                time.sleep(0.005)
            reconverge_ms = (time.perf_counter() - t0) * 1e3
        finally:
            st.close()
            srv.stop()
    finally:
        shutil.rmtree(root, ignore_errors=True)
    return {
        "store_file_op_ms": round(file_ms, 4),
        "store_tcp_op_ms": round(tcp_ms, 4),
        "store_reconverge_ms": round(reconverge_ms, 2),
    }


def bench_kv_tiering(model, on_tpu=True):
    """Host-DRAM KV tiering (ROADMAP item 5a): time-to-next-token of a
    RESUMED request (H2D page restore + one decode) vs the pre-tier
    evict fallback (full re-prefill + one decode) for the same prompt
    on the same warmed engine. The speedup is the pause rung's whole
    value proposition: preserving decoded K/V beats regenerating it,
    and the gap widens with context length."""
    from paddle_tpu.inference.serving import LlamaServingEngine, Request

    model.eval()
    prompt_len = 384 if on_tpu else 96
    prompt = [int(t) for t in (np.arange(prompt_len) % 251 + 1)]
    e = LlamaServingEngine(
        model, max_batch=2, page_size=16 if on_tpu else 8,
        num_pages=128 if on_tpu else 48, kv_tier=True,
        prefix_cache=False)
    try:
        def _next_token(req):
            """Steps until ``req`` emits one more token; seconds."""
            n0 = len(req.output_ids)
            t0 = time.perf_counter()
            while len(req.output_ids) <= n0 and not req.done:
                e.step()
            return time.perf_counter() - t0

        # warm every measured path (prefill, decode, D2H export, H2D
        # restore scatter) so neither arm pays a compile
        w = Request(prompt, max_new_tokens=8)
        e.add_request(w)
        while len(w.output_ids) < 2:
            e.step()
        with e._lock:
            e._pause(w)
        while not w.done:
            e.step()

        # arm 1: pause -> resume (restore restores the decoded pages)
        r = Request(prompt, max_new_tokens=8)
        e.add_request(r)
        while len(r.output_ids) < 2:
            e.step()
        with e._lock:
            e._pause(r)
        resumed = _next_token(r)
        while not r.done:
            e.step()

        # arm 2: the pre-tier fallback — evict resets to a from-scratch
        # re-prefill of the whole prompt
        r2 = Request(prompt, max_new_tokens=8, retry_budget=2)
        e.add_request(r2)
        while len(r2.output_ids) < 2:
            e.step()
        with e._lock:
            e._evict(r2)
        reprefill = _next_token(r2)
        while not r2.done:
            e.step()
        st = e.tier.stats()
    finally:
        e.close()
    return {
        "kv_tier_resumed_ttft_ms": round(resumed * 1e3, 2),
        "kv_tier_reprefill_ttft_ms": round(reprefill * 1e3, 2),
        "kv_tier_resume_speedup": round(
            reprefill / max(resumed, 1e-9), 3),
        "kv_tier_bench_exports": st["exports"],
        "kv_tier_bench_restores": st["restores"],
    }


# second MFU entry (~0.7-0.9B): best-first with HBM fallbacks
LARGE_CANDIDATES = [
    (dict(vocab_size=32000, hidden_size=2048, intermediate_size=5632,
          num_hidden_layers=12, num_attention_heads=16,
          num_key_value_heads=8, max_position_embeddings=4096), 3, 2048),
    (dict(vocab_size=32000, hidden_size=2048, intermediate_size=5632,
          num_hidden_layers=16, num_attention_heads=16,
          num_key_value_heads=8, max_position_embeddings=4096), 2, 2048),
    (dict(vocab_size=32000, hidden_size=2048, intermediate_size=5632,
          num_hidden_layers=12, num_attention_heads=16,
          num_key_value_heads=8, max_position_embeddings=4096), 2, 2048),
]


def bench_frontend(model, on_tpu=True):
    """The HTTP front door under a replayed two-tenant trace: a
    batch-class tenant floods `/v1/completions` while a premium tenant
    trickles streaming requests. Reports per-tenant TTFT/TPOT p99
    (client-observed, through real sockets), shed counts, and
    ``frontend_stream_overhead_frac`` — how much of the in-process
    token rate the HTTP+SSE layer costs. The gate ``frontend_qos_ok``
    requires the flood to be shed while every premium request
    completes in full."""
    import socket
    import threading
    import urllib.error
    import urllib.request

    from paddle_tpu.inference.frontend import ServingFrontend
    from paddle_tpu.inference.qos import QosGate, Tenant
    from paddle_tpu.inference.serving import LlamaServingEngine

    model.eval()
    max_batch = 8 if on_tpu else 2
    new_tokens = 48 if on_tpu else 8
    n_prem = 8 if on_tpu else 3
    n_flood = 24 if on_tpu else 8
    engine = LlamaServingEngine(model, max_batch=max_batch,
                                page_size=64,
                                num_pages=max_batch * 8 + 8,
                                max_pages_per_seq=8, prefix_cache=False)
    rng = np.random.RandomState(0)
    v = model.config.vocab_size
    prompts = [rng.randint(0, v, (24,)).tolist()
               for _ in range(max(n_prem, 4))]

    # in-process baseline at the same geometry (warm first)
    engine.generate(prompts[:2], max_new_tokens=2)
    t0 = time.perf_counter()
    outs = engine.generate(prompts, max_new_tokens=new_tokens)
    inproc_tps = sum(len(o) for o in outs) / (time.perf_counter() - t0)

    # flood refills slowly enough that replaying the trace overruns
    # its share; premium is effectively unmetered
    gate = QosGate([
        Tenant("prem", tier="premium", rate=10 ** 6,
               ttft_slo=30.0 if not on_tpu else 2.0),
        Tenant("flood", tier="batch", rate=new_tokens * 2,
               burst=new_tokens * 2),
    ])
    fe = ServingFrontend(engine=engine, qos=gate)
    fe.start(port=0)

    def post(body, tenant):
        req = urllib.request.Request(
            f"http://127.0.0.1:{fe.port}/v1/completions",
            data=json.dumps(body).encode(),
            headers={"Content-Type": "application/json",
                     "X-Tenant": tenant})
        with urllib.request.urlopen(req, timeout=300) as r:
            return json.loads(r.read())

    def stream(body, tenant):
        """(ttft, n_tokens, wall) client-observed over a raw socket."""
        payload = json.dumps(dict(body, stream=True)).encode()
        sock = socket.create_connection(("127.0.0.1", fe.port),
                                        timeout=300)
        sock.sendall(
            f"POST /v1/completions HTTP/1.1\r\nHost: x\r\n"
            f"X-Tenant: {tenant}\r\n"
            f"Content-Length: {len(payload)}\r\n\r\n".encode()
            + payload)
        rf = sock.makefile("rb")
        t0 = time.perf_counter()
        rf.readline()
        while rf.readline().strip():
            pass
        ttft, n = None, 0
        for line in rf:
            line = line.strip()
            if not line.startswith(b"data: ") or line == b"data: [DONE]":
                continue
            obj = json.loads(line[len(b"data: "):])
            toks = obj["choices"][0].get("token_ids") or []
            if toks and ttft is None:
                ttft = time.perf_counter() - t0
            n += len(toks)
        wall = time.perf_counter() - t0
        rf.close()
        sock.close()
        return ttft, n, wall

    # warm the door (and the engine's programs) through the real path
    stream({"prompt": prompts[0], "max_tokens": 4}, "prem")

    shed = {"n": 0}
    ok = {"n": 0}

    def flood_worker(k):
        r = np.random.RandomState(100 + k)
        for _ in range(n_flood // 2):
            try:
                post({"prompt": r.randint(0, v, (16,)).tolist(),
                      "max_tokens": new_tokens}, "flood")
                ok["n"] += 1
            except urllib.error.HTTPError:
                shed["n"] += 1

    prem_stats = []
    floods = [threading.Thread(target=flood_worker, args=(k,))
              for k in range(2)]
    t_trace = time.perf_counter()
    for th in floods:
        th.start()
    for i in range(n_prem):
        ttft, n, wall = stream(
            {"prompt": prompts[i % len(prompts)],
             "max_tokens": new_tokens}, "prem")
        prem_stats.append((ttft, n, wall))
    for th in floods:
        th.join()
    trace_wall = time.perf_counter() - t_trace
    fe.stop()
    engine.close()
    model.train()

    ttfts = [s[0] for s in prem_stats if s[0] is not None]
    tpots = [(s[2] - s[0]) / (s[1] - 1) for s in prem_stats
             if s[0] is not None and s[1] > 1]
    prem_tokens = sum(s[1] for s in prem_stats)
    # per-request streamed rate vs the in-process batch rate is not
    # apples to apples under concurrency; use aggregate trace tokens
    http_tokens = prem_tokens + ok["n"] * new_tokens
    http_tps = http_tokens / trace_wall
    prem_complete = all(s[1] == new_tokens for s in prem_stats)
    return {
        "frontend_prem_requests": n_prem,
        "frontend_prem_ttft_p50_ms": round(
            float(np.percentile(ttfts, 50)) * 1e3, 2),
        "frontend_prem_ttft_p99_ms": round(
            float(np.percentile(ttfts, 99)) * 1e3, 2),
        "frontend_prem_tpot_p99_ms": round(
            float(np.percentile(tpots, 99)) * 1e3, 2) if tpots else -1.0,
        "frontend_flood_shed": shed["n"],
        "frontend_flood_completed": ok["n"],
        "frontend_http_tokens_per_sec": round(http_tps, 1),
        "frontend_inproc_tokens_per_sec": round(inproc_tps, 1),
        "frontend_stream_overhead_frac": round(
            max(0.0, 1.0 - http_tps / max(inproc_tps, 1e-9)), 3),
        "frontend_qos_ok": bool(shed["n"] > 0 and prem_complete),
    }


def bench_trace_overhead(model, on_tpu=True):
    """Distributed-tracing tax at the cluster tier: tokens/sec through
    a ServingCluster with a per-request trace context active (route +
    admit + first-token spans mint and record) vs plain dispatch.
    ``trace_overhead_frac`` is the fractional rate loss; the gate
    ``trace_overhead_ok`` requires <= 3%."""
    from paddle_tpu.inference.cluster import ServingCluster
    from paddle_tpu.inference.serving import LlamaServingEngine
    from paddle_tpu.observability import tracing as _tracing

    model.eval()
    # each timed run must be long enough that per-span cost (~µs) is
    # resolvable above scheduler jitter — sub-second runs gate on noise
    max_batch = 8 if on_tpu else 2
    new_tokens = 48 if on_tpu else 64
    n_reqs = 24 if on_tpu else 12
    rounds = 3 if on_tpu else 4
    cluster = ServingCluster(
        engine_factory=lambda: LlamaServingEngine(
            model, max_batch=max_batch, page_size=64,
            num_pages=max_batch * 8 + 8, max_pages_per_seq=8,
            prefix_cache=False),
        num_replicas=1, max_backlog=n_reqs * 2)
    cluster.start()
    rng = np.random.RandomState(0)
    v = model.config.vocab_size
    prompts = [rng.randint(0, v, (24,)).tolist() for _ in range(n_reqs)]

    def run(traced):
        reqs = []
        t0 = time.perf_counter()
        for p in prompts:
            if traced:
                with _tracing.activate(_tracing.mint()):
                    reqs.append(cluster.submit(
                        p, max_new_tokens=new_tokens))
            else:
                reqs.append(cluster.submit(p, max_new_tokens=new_tokens))
        for r in reqs:
            r.wait(300.0)
        wall = time.perf_counter() - t0
        return sum(len(r.output_ids) for r in reqs) / wall

    run(False)                  # warm: compile the serving programs
    on, off = [], []
    for _ in range(rounds):     # interleave to share thermal/jit drift
        off.append(run(False))
        on.append(run(True))
    cluster.stop()
    model.train()
    # best-of per mode: external noise (scheduler preemption, a
    # neighbor's compile) only ever SLOWS a run, so the per-mode max is
    # the noise-robust estimate of true capability — a mean would gate
    # on whichever mode drew the unluckier rounds
    tps_on, tps_off = max(on), max(off)
    frac = round(max(0.0, 1.0 - tps_on / max(tps_off, 1e-9)), 3)
    return {
        "trace_tokens_per_sec_on": round(tps_on, 1),
        "trace_tokens_per_sec_off": round(tps_off, 1),
        "trace_overhead_frac": frac,
        "trace_overhead_ok": bool(frac <= 0.03),
    }


def bench_fused_ce(on_tpu=True):
    """Chunked fused cross-entropy lm-head vs the materialized logits
    path at an 8k+ vocab config: fwd+bwd step time, static peak-memory
    delta (``memory_analysis`` temp bytes of the two compiled
    programs), and the ``fused_ce_parity_ok`` gate (loss + both grads
    match at tolerance). ``fused_ce_mem_ok`` (chunked temp bytes
    STRICTLY below materialized) is asserted on TPU; on CPU the same
    comparison is reported — XLA:CPU buffer assignment is a faithful
    proxy for the [N, V] elision."""
    import jax
    import jax.numpy as jnp
    from paddle_tpu.ops.fused_linear_cross_entropy import (
        _loss_raw, default_chunk, supported)

    if on_tpu:
        n, d, v = 4096, 2048, 32000
        iters = 20
        chunk = min(default_chunk(), v)
    else:
        n, d, v = 256, 128, 8192
        iters = 3
        chunk = min(default_chunk(), 2048)   # real multi-chunk smoke
    rng = np.random.RandomState(0)
    h = jnp.asarray(rng.randn(n, d).astype(np.float32) * 0.02)
    w = jnp.asarray(rng.randn(d, v).astype(np.float32) * 0.02)
    lab = jnp.asarray(rng.randint(0, v, (n,)).astype(np.int32))

    def materialized(h, w, lab):
        lg = jnp.matmul(h.astype(jnp.float32), w.astype(jnp.float32))
        logp = jax.nn.log_softmax(lg, axis=-1)
        nll = -jnp.take_along_axis(logp, lab[:, None], axis=1)[:, 0]
        return jnp.mean(nll)

    def fused(h, w, lab):
        return _loss_raw(h, w, lab, chunk, -100, supported(h, w))

    out = {"fused_ce_vocab": v, "fused_ce_tokens": n,
           "fused_ce_chunk": chunk,
           "fused_ce_kernel": bool(supported(h, w))}

    results = {}
    for key, fn in (("fused", fused), ("materialized", materialized)):
        vg = jax.jit(jax.value_and_grad(fn, argnums=(0, 1)))
        compiled = vg.lower(h, w, lab).compile()
        try:
            ma = compiled.memory_analysis()
            out[f"{key}_ce_peak_temp_bytes"] = int(ma.temp_size_in_bytes)
        except Exception:
            pass
        (loss, grads) = compiled(h, w, lab)
        jax.block_until_ready(grads)
        t0 = time.perf_counter()
        for _ in range(iters):
            loss, grads = compiled(h, w, lab)
        jax.block_until_ready(grads)
        results[key] = (float(loss), grads)
        out[f"{key}_ce_step_ms"] = round(
            (time.perf_counter() - t0) / iters * 1e3, 3)

    lf, gf = results["fused"]
    lm, gm = results["materialized"]
    scale_h = float(jnp.max(jnp.abs(gm[0]))) or 1.0
    scale_w = float(jnp.max(jnp.abs(gm[1]))) or 1.0
    parity = (abs(lf - lm) < 1e-4 * max(abs(lm), 1.0)
              and float(jnp.max(jnp.abs(gf[0] - gm[0]))) < 1e-4 * scale_h
              and float(jnp.max(jnp.abs(gf[1] - gm[1]))) < 1e-4 * scale_w)
    out["fused_ce_parity_ok"] = bool(parity)
    out["fused_ce_speedup"] = round(
        out["materialized_ce_step_ms"] / max(out["fused_ce_step_ms"],
                                             1e-9), 3)
    if "fused_ce_peak_temp_bytes" in out \
            and "materialized_ce_peak_temp_bytes" in out:
        mem_ok = out["fused_ce_peak_temp_bytes"] \
            < out["materialized_ce_peak_temp_bytes"]
        out["fused_ce_mem_ok"] = bool(mem_ok)
        if on_tpu:
            assert mem_ok, (
                "chunked fused CE must beat the materialized path's "
                f"peak temp bytes: {out['fused_ce_peak_temp_bytes']} vs "
                f"{out['materialized_ce_peak_temp_bytes']}")
    return out


def bench_moe_train(on_tpu=True):
    """MoE pretraining scaling on ONE device: a compiled train step per
    expert count (same token budget — top-k work is constant, only the
    expert POOL grows), reporting step time per E and
    ``moe_train_scaling_frac`` = (t_max/t_min) / (E_max/E_min). A
    fraction well below 1.0 is the ROADMAP item-5 sublinear gate: step
    time must not grow proportionally with the expert pool. (The
    expert-PARALLEL `shard_llama(ep_axis=...)` path is exercised by
    tests/test_fused_ce.py on the CPU mesh, not by this bench.)"""
    import gc

    import paddle_tpu as paddle
    from paddle_tpu.models import LlamaConfig, LlamaForCausalLM

    if on_tpu:
        counts = (8, 16, 32)
        cfg_kw = dict(vocab_size=8192, hidden_size=1024,
                      intermediate_size=2816, num_hidden_layers=4,
                      num_attention_heads=8, num_key_value_heads=4,
                      max_position_embeddings=2048)
        batch, seq, steps = 2, 1024, 6
    else:
        counts = (2, 4, 8)
        cfg_kw = dict(vocab_size=512, hidden_size=128,
                      intermediate_size=256, num_hidden_layers=2,
                      num_attention_heads=4, num_key_value_heads=2,
                      max_position_embeddings=512)
        batch, seq, steps = 2, 64, 2

    out = {"moe_train_experts": list(counts)}
    rng = np.random.RandomState(0)
    times = []
    for e in counts:
        paddle.seed(0)
        cfg = LlamaConfig(**cfg_kw)
        cfg.moe_num_experts = e
        cfg.moe_top_k = 2
        model = LlamaForCausalLM(cfg)
        opt = paddle.optimizer.AdamW(learning_rate=1e-4,
                                     parameters=model.parameters())

        def step(ids, labels):
            loss, _ = model(ids, labels)
            loss.backward()
            opt.step()
            opt.clear_grad()
            return loss

        compiled = paddle.jit.to_static(step, state=[model, opt],
                                        warmup="once",
                                        donate_inputs=True)

        def batch_of():
            ids = rng.randint(0, cfg.vocab_size,
                              (batch, seq + 1)).astype(np.int64)
            return (paddle.to_tensor(ids[:, :-1]),
                    paddle.to_tensor(ids[:, 1:]))

        compiled(*batch_of())     # eager warmup
        compiled(*batch_of())     # compile
        compiled(*batch_of())     # steady state
        t0 = time.perf_counter()
        for _ in range(steps):
            loss = compiled(*batch_of())
        float(loss)               # host sync
        ms = (time.perf_counter() - t0) / steps * 1e3
        times.append(ms)
        out[f"moe_train_step_ms_e{e}"] = round(ms, 3)
        del model, opt, compiled
        gc.collect()

    growth = times[-1] / max(times[0], 1e-9)
    pool_growth = counts[-1] / counts[0]
    out["moe_train_scaling_frac"] = round(growth / pool_growth, 3)
    out["moe_train_sublinear_ok"] = bool(growth < pool_growth)
    return out


def bench_train_large(steps=6):
    """Second MFU entry at the largest config that fits one chip
    (VERDICT r4 weak #2): ~1B-class Llama. Keys prefixed `large_`."""
    import gc

    # release the decode/serving model pinned by the earlier blocks —
    # its 2 GB of fp32 params would OOM the ~11 GB large config
    bench_train_step.last_model = None
    gc.collect()
    for cfg_kw, batch, seq in LARGE_CANDIDATES:
        try:
            r = bench_train_step(cfg_kw, batch, seq, steps=steps)
            bench_train_step.last_model = None
            import gc
            gc.collect()
            return {"large_" + k: v for k, v in r.items()
                    if k in ("model", "n_params", "batch", "seq",
                             "step_time_ms", "tokens_per_sec", "mfu",
                             "compile_s")}
        except Exception as e:  # OOM etc: next size down
            log(f"large config failed: {e!r:.200}")
    return {"large_error": "no large config fit"}


# (config kwargs, batch, seq) from largest to smallest; the first that
# completes on this chip wins (HBM-driven fallback)
CANDIDATES = [
    (dict(vocab_size=32000, hidden_size=2048, intermediate_size=5632,
          num_hidden_layers=8, num_attention_heads=16, num_key_value_heads=8,
          max_position_embeddings=4096), 3, 2048),
    (dict(vocab_size=32000, hidden_size=2048, intermediate_size=5632,
          num_hidden_layers=8, num_attention_heads=16, num_key_value_heads=8,
          max_position_embeddings=4096), 2, 2048),
    (dict(vocab_size=32000, hidden_size=2048, intermediate_size=5632,
          num_hidden_layers=4, num_attention_heads=16, num_key_value_heads=8,
          max_position_embeddings=4096), 2, 2048),
    (dict(vocab_size=8192, hidden_size=1024, intermediate_size=2816,
          num_hidden_layers=4, num_attention_heads=8, num_key_value_heads=4,
          max_position_embeddings=2048), 2, 1024),
]


def _run_section(result, key, fn, label=None):
    """Run one bench section: merge its dict into ``result``, stamp
    ``<key>_wall_s`` with the section's wall time, and degrade to a
    ``<key>_error`` key on failure (one broken section must not sink
    the whole run — the historical contract of main()'s try blocks)."""
    label = label or key
    t0 = time.perf_counter()
    try:
        result.update(fn())
    except Exception as e:
        log(f"{label} bench failed: {e!r:.300}")
        result[f"{key}_error"] = repr(e)[:200]
    finally:
        result[f"{key}_wall_s"] = round(time.perf_counter() - t0, 3)


def main():
    import jax
    on_tpu = jax.default_backend() == "tpu"
    candidates = CANDIDATES if on_tpu else [
        (dict(vocab_size=512, hidden_size=128, intermediate_size=256,
              num_hidden_layers=2, num_attention_heads=4,
              num_key_value_heads=2, max_position_embeddings=512), 2, 128)]

    result, err = None, None
    for cfg_kw, batch, seq in candidates:
        try:
            result = bench_train_step(cfg_kw, batch, seq,
                                      steps=10 if on_tpu else 2)
            break
        except Exception as e:  # OOM etc.: fall back to the next size
            err = e
            log(f"config h{cfg_kw['hidden_size']}-"
                f"L{cfg_kw['num_hidden_layers']} failed: {e!r:.300}")
    if result is None:
        raise err

    # lambdas read bench_train_step.last_model at CALL time — no local
    # ref lingers to pin the serving model when the large config runs
    _model = lambda: bench_train_step.last_model  # noqa: E731

    if on_tpu:
        _run_section(result, "flash", bench_flash,
                     label="flash micro")
    else:
        _run_section(
            result, "flash",
            lambda: bench_flash(batch=1, seq=256, heads=4, kv_heads=2,
                                dim=64, iters=2),
            label="flash micro")
    _run_section(
        result, "paged",
        bench_paged if on_tpu else
        lambda: bench_paged(batch=2, heads=4, kv_heads=2, dim=32,
                            page=8, ctx=64, iters=2))
    _run_section(
        result, "decode",
        lambda: bench_decode(_model(), batch=16 if on_tpu else 1,
                             prompt=128 if on_tpu else 16,
                             new_tokens=64 if on_tpu else 4))
    _run_section(
        result, "distributed",
        lambda: bench_distributed_onchip(iters=10 if on_tpu else 1),
        label="distributed on-chip")
    _run_section(
        result, "serving",
        lambda: bench_serving(
            _model(), n_requests=24 if on_tpu else 2,
            new_tokens=48 if on_tpu else 4,
            max_batch=16 if on_tpu else 2,
            decode_ceiling=result.get("decode_tokens_per_sec"),
            on_tpu=on_tpu))
    _run_section(result, "cluster",
                 lambda: bench_prefix_cluster(_model(), on_tpu=on_tpu),
                 label="prefix/cluster")
    _run_section(result, "spec",
                 lambda: bench_speculative(_model(), on_tpu=on_tpu),
                 label="speculative")
    _run_section(result, "kv_int8",
                 lambda: bench_kv_int8(_model(), on_tpu=on_tpu),
                 label="kv-int8")
    _run_section(result, "weight_int8",
                 lambda: bench_weight_int8(_model(), on_tpu=on_tpu),
                 label="weight-int8")
    _run_section(result, "restart",
                 lambda: bench_restart_ttft(on_tpu=on_tpu),
                 label="restart-ttft")
    _run_section(result, "store_failover",
                 lambda: bench_store_failover(on_tpu=on_tpu),
                 label="store-failover")
    _run_section(result, "kv_tier",
                 lambda: bench_kv_tiering(_model(), on_tpu=on_tpu),
                 label="kv-tier")
    _run_section(result, "frontend",
                 lambda: bench_frontend(_model(), on_tpu=on_tpu))
    _run_section(result, "trace_overhead",
                 lambda: bench_trace_overhead(_model(), on_tpu=on_tpu),
                 label="trace-overhead")
    _run_section(result, "fused_ce",
                 lambda: bench_fused_ce(on_tpu=on_tpu),
                 label="fused-ce")
    _run_section(result, "moe_train",
                 lambda: bench_moe_train(on_tpu=on_tpu),
                 label="moe-train")
    if on_tpu:
        # ~11 GB large config: nothing above holds the serving model
        # now (only bench_train_step.last_model pins its params)
        _run_section(result, "large", bench_train_large,
                     label="large-model")

    prov = bench_provenance()
    result["device_kind"] = prov["device_kind"]
    result["jax_version"] = prov["jax_version"]
    result["git_commit"] = prov["git_commit"]

    mfu = result["mfu"]
    line = {"metric": "llama_train_mfu", "value": mfu,
            "unit": "fraction_of_peak",
            "vs_baseline": round(mfu / 0.40, 4)}
    line.update(result)
    print(json.dumps(line), flush=True)
    try:
        write_metrics_snapshot(line)
    except Exception as e:
        log(f"metrics snapshot failed: {e!r:.200}")


def write_metrics_snapshot(result,
                           path="BENCH_observability_snapshot.json"):
    """Publish the per-run bench numbers as observability gauges
    (``bench_<key>``) and write the registry snapshot through
    ``observability.export.json_snapshot`` next to the BENCH_*.json
    outputs — strict JSON (``allow_nan=False``), so downstream scrapers
    consume bench history with the exact parser they point at the
    serving /metrics.json endpoint.

    The document is versioned: ``{"schema_version":
    BENCH_SCHEMA_VERSION, "provenance": bench_provenance(), "metrics":
    [json_snapshot entries]}`` — the shape ``tools/bench_check.py``
    diffs against a committed baseline (it also still reads the
    pre-versioning bare-list snapshots). Returns the path, or None
    under ``PADDLE_TPU_METRICS=0`` (the kill switch writes no
    files)."""
    from paddle_tpu.observability import metrics as om
    from paddle_tpu.observability.export import json_snapshot

    if not om.enabled():
        return None
    reg = om.MetricsRegistry()      # private: bench numbers only
    for key, value in result.items():
        if isinstance(value, bool) or not isinstance(value, (int, float)):
            continue
        reg.gauge(f"bench_{key}", "bench.py per-run number") \
            .set(float(value))
    doc = {"schema_version": BENCH_SCHEMA_VERSION,
           "provenance": bench_provenance(),
           "metrics": json_snapshot(reg)}
    with open(path, "w") as f:
        json.dump(doc, f, indent=2, allow_nan=False)
    return path




def bench_distributed_onchip(iters=10):
    """Chip-validate the distributed kernels (VERDICT r4 weak #3): a
    degenerate 1-device mesh still exercises the real TPU lowering of
    the ring-attention block math, the compiled pipeline schedule
    (scan + dynamic indexing), and the MoE dispatch (sort + scatter /
    one-hot einsum) — the paths that previously ran only under the CPU
    test mesh."""
    import jax
    import jax.numpy as jnp
    from jax.sharding import Mesh

    out = {}
    rng = np.random.RandomState(0)

    # --- ring attention (CP ring of 1) vs naive attention ---------------
    from paddle_tpu.distributed.ring_attention import ring_attention
    from paddle_tpu.nn.functional.attention import _naive_attention

    mesh1 = Mesh(np.asarray(jax.devices()[:1]), ("sep",))
    B, S, H, Hk, D = 2, 2048, 8, 4, 128
    q = jnp.asarray(rng.randn(B, S, H, D), jnp.float32)
    k = jnp.asarray(rng.randn(B, S, Hk, D), jnp.float32)
    v = jnp.asarray(rng.randn(B, S, Hk, D), jnp.float32)

    def ring(q, k, v):
        o = ring_attention(q, k, v, mesh1, causal=True)
        return jnp.asarray(getattr(o, "_data", o))

    o_ring = jax.block_until_ready(ring(q, k, v))
    t0 = time.perf_counter()
    for _ in range(iters):
        o_ring = ring(q, k, v)
    jax.block_until_ready(o_ring)
    out["ring_ms"] = round((time.perf_counter() - t0) / iters * 1e3, 3)
    kr = jnp.repeat(k, H // Hk, axis=2)
    vr = jnp.repeat(v, H // Hk, axis=2)
    o_ref = _naive_attention(q, kr, vr, None, 0.0, True, None)
    o_ref = jnp.asarray(getattr(o_ref, "_data", o_ref))
    err = float(jnp.max(jnp.abs(o_ring - o_ref)))
    scale = float(jnp.max(jnp.abs(o_ref)))
    out["ring_parity_ok"] = bool(err < 0.02 * max(scale, 1.0))

    # --- compiled pipeline schedule (P = 1) -----------------------------
    from paddle_tpu.distributed.pipeline import (pipeline_1f1b,
                                                 pipeline_spmd,
                                                 stack_stage_params)

    meshp = Mesh(np.asarray(jax.devices()[:1]), ("pp",))
    L, Dm, Bt = 4, 256, 32
    params = [{"w": jnp.asarray(rng.randn(Dm, Dm).astype(np.float32)
                                * 0.05)} for _ in range(L)]
    stacked = stack_stage_params(params)

    def stage_fn(p, h):
        def body(h, lp):
            return jnp.tanh(h @ lp["w"]), None
        return jax.lax.scan(body, h, p)[0]

    x = jnp.asarray(rng.randn(Bt, Dm).astype(np.float32))
    y = jnp.asarray(rng.randn(Bt, Dm).astype(np.float32))
    o_pp = pipeline_spmd(stage_fn, stacked, x, mesh=meshp,
                         num_microbatches=4)
    hh = x
    for l in range(L):
        hh = jnp.tanh(hh @ stacked["w"][l])
    err = float(jnp.max(jnp.abs(jnp.asarray(o_pp) - hh)))
    out["pipeline_parity_ok"] = bool(err < 1e-4)

    def loss_fn(h, yy):
        return jnp.mean((h - yy) ** 2)

    loss, grads = pipeline_1f1b(stage_fn, loss_fn, stacked, x, y,
                                mesh=meshp, num_microbatches=4)

    def ref_loss(st):
        hm = x.reshape(4, Bt // 4, Dm)
        ym = y.reshape(4, Bt // 4, Dm)
        ls = []
        for m in range(4):
            hh = hm[m]
            for l in range(L):
                hh = jnp.tanh(hh @ st["w"][l])
            ls.append(loss_fn(hh, ym[m]))
        return jnp.mean(jnp.asarray(ls))

    wl, wg = jax.value_and_grad(ref_loss)(stacked)
    ok = abs(float(loss) - float(wl)) < 1e-4 and bool(
        jnp.max(jnp.abs(grads["w"] - wg["w"])) < 1e-3)
    out["pipeline_1f1b_parity_ok"] = ok

    # --- MoE dispatch: grouped-GEMM vs dense at 64 experts --------------
    # The grouped path (dispatch_mode="ragged") is sort-based routing +
    # the Pallas grouped-GEMM megakernel (ops/grouped_gemm.py; XLA
    # grouped formulation off-TPU). Bar: moe_dispatch_speedup > 1.2 on
    # chip with moe_parity_ok vs the dense GShard formulation; the CPU
    # smoke gate is "not slower than dense". Both the switch (top-1)
    # and gshard (top-2) gates are measured.
    import paddle_tpu as paddle
    from paddle_tpu.incubate.moe import MoELayer

    E, Dm2, N = 64, 512, 4096
    xs = paddle.to_tensor(rng.randn(N, Dm2).astype(np.float32))

    def timed_moe(layer):
        # the layer's own compiled forward (public build_fn: the
        # compile-watched per-token-count program — eager per-op
        # dispatch would measure the host, not the dispatch math)
        fn = layer.build_fn(N)
        args = (xs._data, layer.gate_weight._data, layer.w1._data,
                layer.b1._data, layer.w2._data, layer.b2._data)
        o, _, _ = fn(*args)
        jax.block_until_ready(o)
        t0 = time.perf_counter()
        for _ in range(iters):
            o, _, _ = fn(*args)
        jax.block_until_ready(o)
        return (time.perf_counter() - t0) / iters * 1e3, o

    out["moe_experts"] = E
    for gate, prefix in (("switch", "moe_"), ("gshard", "moe_gshard_")):
        paddle.seed(3)
        grouped = MoELayer(Dm2, Dm2 * 2, E, gate=gate,
                           dispatch_mode="ragged")
        paddle.seed(3)
        dense = MoELayer(Dm2, Dm2 * 2, E, gate=gate,
                         dispatch_mode="dense")
        grp_ms, o_grp = timed_moe(grouped)
        den_ms, o_den = timed_moe(dense)
        err = float(jnp.max(jnp.abs(o_grp - o_den)))
        scale = float(jnp.max(jnp.abs(o_den)))
        out[prefix + "parity_ok"] = bool(err < 0.02 * max(scale, 1.0))
        out[prefix + "grouped_ms"] = round(grp_ms, 3)
        out[prefix + "dense_ms"] = round(den_ms, 3)
        out[prefix + "dispatch_speedup"] = round(den_ms / grp_ms, 3)
    return out


if __name__ == "__main__":
    main()
