#!/usr/bin/env python3
"""The quickest proof that paddle_tpu still starts on the chip.

One process drives the two main paths through the entry points a user
calls, at published widths, on one TPU:

- ``walk``: the float rope-fused ragged paged-attention kernel alone,
  at Mistral-7B's head shape over the benchmark's pool (4,097 pages of
  16): chat-open's two programs, the same rows behind tables 66, 161
  and 521 pages wide. It is held against
  ``fused_ragged_paged_attention_xla``, must answer alike at every
  width and must take the same time a layer to within 10%;
  then (``walk_verify``) at a speculative engine's verify shape, 64
  query heads over 8 kv heads and 64 rows of a token and its draft in
  the 512-token program, a NoPE layer and a 128-key window layer,
  timed with the small tile of one token and of two;
- ``latent``: the ragged paged LATENT attention kernel alone, 32 heads
  over a 640-lane row (512 + 64 in use) and 24,577 pages of 16:
  docqa-closed's two programs behind tables 161, 521 and 1,057 pages
  wide, held to ``ragged_mla_attention``'s XLA formulation and to the
  same two rules;
- ``flash``: the three training flash-attention kernels alone, by name,
  at pretrain-4k's attention shape: ms a call each, and at S = 1,024 the
  output and gradients against the XLA path;
- ``ce``: the fused cross-entropy's forward kernel alone at
  pretrain-4k's head shape (f32): ms a call at the blocks it picks and
  at a sweep of others, beside its MXU and HBM floors; lse and pick
  against the chunked-XLA formulation in f32;
- ``serve``: Llama-3-8B widths (hidden 4096, FFN 14336, 32q/8kv, vocab
  128256), depth cut to 8 layers, seeded bf16 weights;
  ``LlamaServingEngine`` with its default (rope-fused) program answers
  8 requests through ``add_request``/``step``, float pages and int8
  pages, and is held against ``model.generate`` and a teacher-forced
  reference on the same chip;
- ``train``: the "0.5b" recipe of ``examples/llama_pretrain.py``, bf16
  autocast, a ``jit.to_static`` AdamW step with the flash-attention and
  fused-CE kernels in the program, 4 steps from a ``TokenFeed``.

``--chips 4`` runs instead (and only) the same train recipe on a
dp2 x mp2 mesh and its one-device comparison.

Every phase prints one JSON line; a phase that raises ends the run
non-zero, nothing is caught. Without a TPU the script exits non-zero at
once. The LAST line of a run that passed is
``{"ok": true, "device": {...}}``. ``--rehearse`` shrinks every size so
the control flow can be walked on the CPU; a rehearsal never prints
that line and never exits 0.
"""

import argparse
import functools
import gc
import json
import os
import sys
import tempfile
import time

import numpy as np

REHEARSAL_EXIT = 4

# first-token / every-token bar of the serve phase: bf16 on the chip
# breaks exact ties differently in the paged kernel (f32 online softmax)
# and in generate's XLA attention, and random weights give near-flat
# logits (top-2 gap ~0.26 at std 1.28 over 128k words), so the engine is
# held to the reference's logits, not to its argmax: each token the
# engine emits must score within this many logit units of the
# reference's best at that position. A wrong token scores ~5 below.
SERVE_LOGIT_TOL = 0.25
# |kernel loss - kernel-free loss| at step 1, relative (bf16 autocast)
TRAIN_LOSS_RTOL = 2e-2
# sharded vs one-device losses over 4 steps, relative (bf16 autocast)
MESH_LOSS_RTOL = 2e-2


def emit(**obj):
    print(json.dumps(obj), flush=True)


def device_info():
    import jax
    d = jax.devices()[0]
    return {"platform": d.platform, "kind": d.device_kind,
            "count": len(jax.devices())}


def device_bytes():
    """``{"in_use", "peak", "limit"}`` of the first device's allocator
    (the peak is the process's so far, not the phase's)."""
    import jax
    stats = jax.devices()[0].memory_stats() or {}
    return {"in_use": stats.get("bytes_in_use"),
            "peak": stats.get("peak_bytes_in_use"),
            "limit": stats.get("bytes_limit")}


def cache_stats():
    from paddle_tpu.observability import compile_watch as cw
    return cw.persistent_cache_stats()


def kernel_counts(static_fns, resharded=False):
    """``{program: count of tpu_custom_call}`` over the AOT executables
    of the given ``{name: StaticFunction}``. A program that lost its
    executable is an error, except where ``resharded``: a GSPMD step
    returns its state in the shardings the partitioner chose, the next
    call no longer matches the executable's fixed input shardings, and
    ``jit.to_static`` hands that signature to plain jit (counted None)."""
    out = {}
    for name, sf in static_fns.items():
        for i, compiled in enumerate(sf._aot.values()):
            if compiled is None and not resharded:
                raise RuntimeError(f"{name}: no AOT executable")
            out[f"{name}#{i}"] = None if compiled is None else \
                compiled.as_text().count("tpu_custom_call")
    return out


def require_kernels(counts, on_chip):
    """Every program that is meant to hold a kernel holds one (on the
    CPU the kernels run interpreted: no custom call to count)."""
    if on_chip:
        missing = [k for k, n in counts.items() if n == 0]
        if missing or not counts:
            raise RuntimeError(f"no tpu_custom_call in {missing or counts}")


# ---------------------------------------------------------------------------
# serve
# ---------------------------------------------------------------------------
def serve_programs(engine):
    fns = {"serving.mixed_step": engine._mixed_static}
    fns.update({f"serving.mixed_scan[{n}]": sf
                for n, sf in engine._scan_static.items()})
    return fns


def drive(engine, prompts, new_tokens):
    """Submit every request, then step until all are done."""
    from paddle_tpu.inference.serving import Request
    reqs = [Request(p, max_new_tokens=new_tokens) for p in prompts]
    for r in reqs:
        engine.add_request(r)
    steps = 0
    while not all(r.done for r in reqs):
        engine.step()
        steps += 1
        if steps > 100 * new_tokens * len(prompts):
            raise RuntimeError("serving engine made no progress")
    bad = [r.status for r in reqs if r.status != "completed"]
    if bad:
        raise RuntimeError(f"requests ended {bad}")
    return [list(r.output_ids) for r in reqs]


def phase_serve(seed, rehearse, on_chip):
    import jax
    import jax.numpy as jnp
    import paddle_tpu as paddle
    from paddle_tpu.inference.serving import LlamaServingEngine
    from paddle_tpu.models import (LlamaForCausalLM, llama3_8b_config,
                                   tiny_llama_config)

    if rehearse:
        cfg = tiny_llama_config()
        lengths, new_tokens, page_size, dtype = (8, 41), 4, 8, "float32"
        n_req = 3
    else:
        cfg = llama3_8b_config()
        cfg.num_hidden_layers = 8
        lengths, new_tokens, page_size, dtype = (64, 1025), 32, 16, \
            "bfloat16"
        n_req = 8
    rng = np.random.RandomState(seed)
    prompts = [rng.randint(0, cfg.vocab_size,
                           (int(rng.randint(*lengths)),)).tolist()
               for _ in range(n_req)]
    pages_per_seq = -(-(max(map(len, prompts)) + new_tokens)
                      // page_size) + 1
    geometry = dict(max_batch=16, page_size=page_size,
                    num_pages=n_req * pages_per_seq + 9,
                    max_pages_per_seq=pages_per_seq)

    t0 = time.perf_counter()
    paddle.seed(seed)
    paddle.set_default_dtype(dtype)
    model = LlamaForCausalLM(cfg)
    paddle.set_default_dtype("float32")
    model.eval()
    jax.block_until_ready([p._data for p in model.parameters()])
    build_s = time.perf_counter() - t0

    # -- float pages -------------------------------------------------
    engine = LlamaServingEngine(model, **geometry)
    t0 = time.perf_counter()
    outs = drive(engine, prompts, new_tokens)
    first_s = time.perf_counter() - t0      # compiles included
    t0 = time.perf_counter()
    again = drive(engine, prompts, new_tokens)
    warm_s = time.perf_counter() - t0
    if again != outs:
        raise RuntimeError("the same engine answered differently twice")
    counts = kernel_counts(serve_programs(engine))
    require_kernels(counts, on_chip)
    engine.close()

    # -- reference 1: model.generate, token for token ------------------
    max_len = -(-(max(map(len, prompts)) + new_tokens) // 64) * 64
    t0 = time.perf_counter()
    exact = []
    for p, o in zip(prompts, outs):
        ids = paddle.to_tensor(np.asarray([p], np.int64))
        ref = np.asarray(model.generate(ids, max_new_tokens=new_tokens,
                                        max_length=max_len)._data)
        ref = ref[0, len(p):].tolist()
        same = [a == b for a, b in zip(o, ref)]
        exact.append({"first_token": same[0],
                      "agree_until": same.index(False)
                      if False in same else len(same)})
    generate_s = time.perf_counter() - t0

    # -- reference 2: teacher-forced logits ----------------------------
    # one forward over prompt + engine output (padded to max_len: a
    # causal model's earlier positions do not see the pad) gives the
    # reference logits at every position the engine sampled from
    def ref_logits(ids, pos):
        hidden = model.model(ids)
        rows = paddle.gather(hidden.reshape([max_len, -1]), pos, axis=0)
        return model._logits(rows).astype("float32")

    ref_fn = paddle.jit.to_static(ref_logits, state=[model], donate=False,
                                  warmup="once")
    ref_fn._warmed_any = True       # no lazy state to materialize
    margins = []
    for p, o in zip(prompts, outs):
        ids = np.zeros((1, max_len), np.int64)
        ids[0, :len(p) + len(o)] = p + o
        pos = np.arange(len(p) - 1, len(p) - 1 + len(o), dtype=np.int32)
        lg = ref_fn(paddle.to_tensor(ids), paddle.to_tensor(pos))._data
        took = lg[jnp.arange(len(o)), jnp.asarray(o)]
        margins.append(np.asarray(lg.max(axis=-1) - took))
        if not np.isfinite(np.asarray(lg)).all():
            raise RuntimeError("reference logits are not finite")
    worst = float(max(m.max() for m in margins))
    worst_first = float(max(m[0] for m in margins))

    emit(phase="serve", device=device_info(), model="llama3-8b widths",
         shapes=dict(hidden=cfg.hidden_size, ffn=cfg.intermediate_size,
                     heads=cfg.num_attention_heads,
                     kv_heads=cfg.num_key_value_heads,
                     vocab=cfg.vocab_size, layers=cfg.num_hidden_layers,
                     dtype=dtype, prompt_lens=[len(p) for p in prompts],
                     new_tokens=new_tokens, **geometry),
         params=model.num_params(), build_seconds=build_s,
         first_drive_seconds=first_s, warm_drive_seconds=warm_s,
         generate_seconds=generate_s, kernels=counts, cache=cache_stats(),
         device_bytes=device_bytes(),
         compared={"with": "model.generate (token for token) and "
                           "teacher-forced reference logits",
                   "vs_generate": exact,
                   "requests_token_exact": sum(
                       e["agree_until"] == new_tokens for e in exact),
                   "first_tokens_equal": sum(
                       e["first_token"] for e in exact),
                   "worst_logit_margin": worst,
                   "worst_first_token_margin": worst_first,
                   "logit_tolerance": SERVE_LOGIT_TOL})
    if not worst <= SERVE_LOGIT_TOL:
        raise RuntimeError(
            f"an engine token scores {worst} below the reference's best "
            f"(tolerance {SERVE_LOGIT_TOL})")

    # -- int8 pages: held against int8, never against float pages ------
    t0 = time.perf_counter()
    q8 = []
    for _ in range(2):
        e8 = LlamaServingEngine(model, kv_dtype="int8", **geometry)
        q8.append(drive(e8, prompts, new_tokens))
        counts8 = kernel_counts(serve_programs(e8))
        e8.close()
    require_kernels(counts8, on_chip)
    if q8[0] != q8[1]:
        raise RuntimeError("two fresh int8-KV engines disagree")
    flat = [(a, b) for o8, o in zip(q8[0], outs) for a, b in zip(o8, o)]
    emit(phase="serve_int8_kv", seconds=time.perf_counter() - t0,
         kernels=counts8, cache=cache_stats(),
         device_bytes=device_bytes(),
         compared={"with": "a second fresh int8-KV engine",
                   "token_exact": True,
                   "share_equal_to_float_pages":
                       sum(a == b for a, b in flat) / len(flat)})


# ---------------------------------------------------------------------------
# walk: the float rope-fused ragged kernel alone, at Mistral-7B's head
# shape, the same rows behind tables of three widths
# ---------------------------------------------------------------------------
WALK_WIDTHS = (66, 161, 521)
# the same rows at every width must cost the same to within this
WALK_WIDTH_RTOL = 0.10


def walk_rows(rng, r_cap, t_cap, qblock, decode_rows, chunks, max_ctx,
              page_size, row_tokens=1, min_ctx=None):
    """One dispatch's row metadata with the values `_dispatch_rows` gives
    the fields ``kv_lens q_starts q_lens w_starts w_flats w_ends pos``:
    ``decode_rows`` sequences of ``row_tokens`` tokens each (a decode
    row, or a verify row of a token and its drafts) and a context in
    ``[min_ctx, max_ctx)`` (``max_ctx // 8`` by default), then
    ``chunks`` rows that continue ONE prompt (consecutive q_starts, one
    write span), then inactive rows. The engine writes them into one
    staged buffer (`DispatchLayout`) its program takes apart; the
    kernels here are fed directly, so they stay separate arrays. Returns
    the [R] arrays and the packed positions."""
    kv = np.zeros(r_cap, np.int32)
    ql = np.zeros(r_cap, np.int32)
    lo = max_ctx // 8 if min_ctx is None else min_ctx
    kv[:decode_rows] = rng.randint(lo, max_ctx, decode_rows)
    ql[:decode_rows] = row_tokens
    first = int(rng.randint(0, max_ctx - chunks * qblock))
    for c in range(chunks):
        kv[decode_rows + c] = first + (c + 1) * qblock
        ql[decode_rows + c] = qblock
    qs = kv - ql
    live = decode_rows + chunks
    assert int(ql.sum()) <= t_cap and live <= r_cap
    wf = np.concatenate([[0], np.cumsum(ql)[:-1]]).astype(np.int32)
    ws, we = qs.copy(), kv.copy()
    ws[decode_rows:live] = first
    wf[decode_rows:live] = decode_rows
    we[decode_rows:live] = first + chunks * qblock
    pos = np.zeros(t_cap, np.int32)
    for i in range(live):
        f = wf[i] + qs[i] - ws[i]
        pos[f:f + ql[i]] = np.arange(qs[i], kv[i])
    pages = -(-kv // page_size)
    pages[decode_rows:live] = pages[live - 1] if chunks else 0
    return kv, qs, ql, ws, wf, we, pos, pages


def walk_tables(r_cap, width, trash, owner, starts, pages, ids):
    """Block tables ``[r_cap, width]`` of one dispatch: row ``i`` holds
    its owner's pages, the rest names the trash page."""
    tables = np.full((r_cap, width), trash, np.int32)
    for i in range(r_cap):
        o = owner[i]
        tables[i, :pages[o]] = ids[starts[o]:starts[o] + pages[o]]
    return tables


def best_ms(fn, args, reps):
    """(the first call's result, the fastest of ``reps`` further calls
    in ms)."""
    import jax
    out = jax.block_until_ready(fn(*args))
    best = float("inf")
    for _ in range(reps):
        t0 = time.perf_counter()
        jax.block_until_ready(fn(*args))
        best = min(best, time.perf_counter() - t0)
    return out, 1e3 * best


def phase_walk(seed, rehearse, on_chip):
    import jax
    import jax.numpy as jnp
    from paddle_tpu.ops import ragged_paged_attention as rpa

    if rehearse:
        h, hk, d, page, pool, dt = 4, 2, 16, 8, 96, jnp.float32
        widths, layers, reps = (5, 9, 13), 2, 1
        programs = {"mixed": (8, 24, 8, 4, 2), "decode": (6, 6, 1, 6, 0)}
    else:
        h, hk, d, page, pool, dt = 32, 8, 128, 16, 4097, jnp.bfloat16
        widths, layers, reps = WALK_WIDTHS, 16, 5
        # chat-open's two programs: 36 rows x 128 tokens with 32-token
        # chunks beside the decode rows, and 32 rows of one token
        programs = {"mixed": (36, 128, 32, 24, 3),
                    "decode": (32, 32, 1, 24, 0)}
    rng = np.random.RandomState(seed)
    trash = pool - 1
    key = jax.random.PRNGKey(seed)
    kp, vp = (jax.random.normal(k, (pool, hk, page, d), jnp.float32)
              .astype(dt) for k in jax.random.split(key))
    timings = {}
    for name, (r_cap, t_cap, qb, n_dec, n_chunk) in programs.items():
        kv, qs, ql, ws, wf, we, pos, pages = walk_rows(
            rng, r_cap, t_cap, qb, n_dec, n_chunk, min(widths) * page,
            page)
        # distinct live pages a sequence; the chunk rows share a table
        ids = rng.permutation(trash)
        owner = np.arange(r_cap)
        owner[n_dec:n_dec + n_chunk] = n_dec
        starts = np.concatenate([[0], np.cumsum(pages)[:-1]])
        q, nk, nv = (jnp.asarray(rng.randn(t_cap, n, d), dt)
                     for n in (h, hk, hk))
        sin, cos = rpa.rope_tables(jnp.asarray(pos), d, 1e6)
        outs = {}
        for width in widths:
            tables = walk_tables(r_cap, width, trash, owner, starts, pages,
                                 ids)
            args = (q, nk, nv, kp, vp, jnp.asarray(tables),
                    *(jnp.asarray(a) for a in (kv, qs, ql, ws, wf, we)))

            @jax.jit
            def stack(q, nk, nv, kp, vp, *meta):
                # `layers` calls chained through the pools, as the
                # layers of one step program are
                for _ in range(layers):
                    out, kp, vp = rpa._fused_rope_impl(
                        q, nk, nv, kp, vp, *meta, sin, cos,
                        dump_page=trash, scale=d ** -0.5, qblock=qb)
                return out, kp, vp

            outs[width], ms = best_ms(stack, args, reps)
            timings[f"{name}.{width}"] = ms / layers
        base = outs[widths[0]]
        for width in widths[1:]:
            if not all(bool(jnp.array_equal(a, b))
                       for a, b in zip(base, outs[width])):
                raise RuntimeError(
                    f"{name}: width {width} answers otherwise than "
                    f"width {widths[0]} on the same rows")
        ref = rpa.fused_ragged_paged_attention_xla(
            *args, trash, scale=d ** -0.5, rope_sin=sin, rope_cos=cos,
            qblock=qb)
        f32 = lambda a: a.astype(jnp.float32)        # noqa: E731
        err = float(jnp.max(jnp.abs(f32(base[0]) - f32(ref[0]))))
        # the pages the kernel wrote against the reference's scatter:
        # equal but for what the two compilations of the rotation
        # round otherwise (an FMA contracted or not, one ulp)
        pool_err = float(jnp.max(jnp.abs(f32(base[1]) - f32(ref[1]))))
        timings[f"{name}.out_err"] = err
        timings[f"{name}.pool_err"] = pool_err
        timings[f"{name}.pool_share_unequal"] = float(
            jnp.mean(base[1] != ref[1]))
        tol = 1e-5 if rehearse else 2e-2
        if not err <= tol * max(1.0, float(jnp.max(jnp.abs(f32(ref[0]))))) \
                or not pool_err <= tol * float(jnp.max(jnp.abs(f32(ref[1])))):
            raise RuntimeError(f"{name}: kernel and XLA reference differ: "
                               f"out {err}, pools {pool_err}")
        ms = [timings[f"{name}.{w}"] for w in widths]
        if on_chip and max(ms) > (1 + WALK_WIDTH_RTOL) * min(ms):
            raise RuntimeError(f"{name}: the kernel's time follows the "
                               f"table's width: {ms} ms at {widths}")
    emit(phase="walk", device=device_info(),
         shapes=dict(heads=h, kv_heads=hk, head_dim=d, page_size=page,
                     num_pages=pool, widths=list(widths),
                     programs={k: dict(zip(("rows", "tokens", "qblock",
                                            "decode_rows", "chunk_rows"),
                                           v))
                               for k, v in programs.items()}),
         ms_a_layer=timings)
    del kp, vp
    gc.collect()
    walk_verify(seed, rehearse, on_chip)


def walk_verify(seed, rehearse, on_chip):
    """The float program at a speculative engine's verify shape:
    reason-mtp-closed's 512-token program with 64 verify rows of two
    tokens (a token and its draft) at contexts of 1-2k, 64 query heads
    over 8 kv heads (a group of 8: a row's 16 softmax rows), page 16,
    for a NoPE whole-context layer and a 128-key window layer with the
    rope. Each is timed with the small tile of one token (8 rows: every
    verify row computes the whole 32-token block) and of two (the
    engine's ``1 + spec_k``), beside the layer's bandwidth floor (the
    keys its rows read, K and V, at 819 GB/s). The tile of two answers
    the first 16 rows as the XLA reference does; whether it answers bit
    for bit as the tile of one is reported (``tile_bitwise``)."""
    import jax
    import jax.numpy as jnp
    from paddle_tpu.ops import ragged_paged_attention as rpa

    if rehearse:
        h, hk, d, page, pool, dt = 8, 1, 16, 8, 160, jnp.float32
        r_cap, t_cap, qb, n_rows, ctx, window = 8, 24, 8, 6, (16, 64), 16
        layers, reps, checked = 2, 1, 4
    else:
        h, hk, d, page, pool, dt = 64, 8, 128, 16, 16385, jnp.bfloat16
        r_cap, t_cap, qb, n_rows, ctx, window = 80, 512, 32, 64, \
            (1024, 2048), 128
        layers, reps, checked = 8, 5, 16
    rng = np.random.RandomState(seed + 1)
    trash = pool - 1
    kv, qs, ql, ws, wf, we, pos, pages = walk_rows(
        rng, r_cap, t_cap, qb, n_rows, 0, ctx[1], page, row_tokens=2,
        min_ctx=ctx[0])
    width = -(-(ctx[1] + 1) // page)
    owner = np.arange(r_cap)
    starts = np.concatenate([[0], np.cumsum(pages)[:-1]])
    tables = jnp.asarray(walk_tables(r_cap, width, trash, owner, starts,
                                     pages, rng.permutation(trash)))
    meta = [jnp.asarray(a) for a in (kv, qs, ql, ws, wf, we)]
    key = jax.random.PRNGKey(seed + 1)
    kp, vp = (jax.random.normal(k, (pool, hk, page, d), jnp.float32)
              .astype(dt) for k in jax.random.split(key))
    q, nk, nv = (jnp.asarray(rng.randn(t_cap, n, d), dt)
                 for n in (h, hk, hk))
    scale = d ** -0.5
    timings = {}
    for kind in ("full", "window"):
        win = window if kind == "window" else None
        sin, cos = rpa.rope_tables(jnp.asarray(pos), d, 1e6) \
            if win else (None, None)
        keys = int(np.minimum(kv, win - 1 + ql).sum()) if win \
            else int(kv.sum())
        timings[f"{kind}.floor"] = 1e3 * 2 * keys * hk * d \
            * jnp.dtype(dt).itemsize / 819e9
        outs = {}
        for tile in (1, 2):
            @jax.jit
            def stack(q, nk, nv, kp, vp, tables, *meta):
                for _ in range(layers):
                    out, kp, vp = rpa._fused_rope_impl(
                        q, nk, nv, kp, vp, tables, *meta, sin, cos,
                        dump_page=trash, scale=scale, qblock=qb,
                        window=win, tile_tokens=tile)
                return out, kp, vp

            outs[tile], ms = best_ms(stack, (q, nk, nv, kp, vp, tables,
                                             *meta), reps)
            timings[f"{kind}.tile{tile}"] = ms / layers
        timings[f"{kind}.tile_bitwise"] = all(
            bool(jnp.array_equal(a, b)) for a, b in zip(outs[1], outs[2]))
        # the reference packs q into row blocks only behind tables: a
        # layer that rotates nothing is what the identity tables give
        ident = (jnp.zeros((t_cap, d)), jnp.ones((t_cap, d)))
        ref = rpa.fused_ragged_paged_attention_xla(
            q, nk, nv, kp, vp, tables[:checked],
            *(a[:checked] for a in meta), trash, scale=scale,
            rope_sin=ident[0] if sin is None else sin,
            rope_cos=ident[1] if cos is None else cos, qblock=qb,
            window=win)[0]
        f32 = lambda a: a.astype(jnp.float32)        # noqa: E731
        err = float(jnp.max(jnp.abs(f32(outs[2][0][:checked]) - f32(ref))))
        timings[f"{kind}.out_err"] = err
        tol = 1e-5 if rehearse else 2e-2
        if not err <= tol * max(1.0, float(jnp.max(jnp.abs(f32(ref))))):
            raise RuntimeError(f"verify {kind}: kernel and XLA reference "
                               f"differ: out {err}")
        del outs, ref
    emit(phase="walk_verify", device=device_info(),
         shapes=dict(heads=h, kv_heads=hk, head_dim=d, page_size=page,
                     num_pages=pool, width=width, rows=r_cap,
                     tokens=t_cap, qblock=qb, verify_rows=n_rows,
                     contexts=list(ctx), window=window),
         ms_a_layer=timings)


def phase_latent(seed, rehearse, on_chip):
    """The ragged paged LATENT attention kernel alone, at the expert
    family's head shape (32 heads over a 640-lane row, 512 + 64 in use)
    behind tables of three widths: the same answers and the same time
    at every width, and the XLA formulation's answers."""
    import jax
    import jax.numpy as jnp
    from paddle_tpu.ops import ragged_mla_attention as mla

    if rehearse:
        h, rank, rope, page, pool, dt = 4, 32, 8, 8, 96, jnp.float32
        widths, layers, reps = (5, 9, 13), 2, 1
        programs = {"mixed": (8, 24, 8, 4, 2), "decode": (6, 6, 1, 6, 0)}
    else:
        h, rank, rope, page, pool, dt = 32, 512, 64, 16, 24577, jnp.bfloat16
        widths, layers, reps = (161, 521, 1057), 5, 5
        # docqa-closed's two programs: 80 rows x 1,024 tokens with
        # 32-token chunks beside 48 decode rows, and 48 rows of a token
        programs = {"mixed": (80, 1024, 32, 48, 30),
                    "decode": (48, 48, 1, 48, 0)}
    w = mla.latent_row_width(rank, rope)
    scale = (rank // 4 + rope) ** -0.5
    rng = np.random.RandomState(seed)
    trash = pool - 1
    lanes = jnp.arange(w) < rank + rope

    def rows_of(key, shape):
        return jnp.where(lanes, jax.random.normal(
            key, (*shape, w), jnp.float32), 0).astype(dt)

    k1, k2, k3 = jax.random.split(jax.random.PRNGKey(seed), 3)
    lp = rows_of(k1, (pool, page))
    timings = {}
    for name, (r_cap, t_cap, qb, n_dec, n_chunk) in programs.items():
        kv, qs, ql, ws, wf, we, _, pages = walk_rows(
            rng, r_cap, t_cap, qb, n_dec, n_chunk, min(widths) * page,
            page)
        ids = rng.permutation(trash)
        owner = np.arange(r_cap)
        owner[n_dec:n_dec + n_chunk] = n_dec
        starts = np.concatenate([[0], np.cumsum(pages)[:-1]])
        q, new = rows_of(k2, (t_cap, h)), rows_of(k3, (t_cap,))
        outs, narrow = {}, None
        for width in widths:
            tables = walk_tables(r_cap, width, trash, owner, starts, pages,
                                 ids)
            args = (q, new, lp, jnp.asarray(tables),
                    *(jnp.asarray(a) for a in (kv, qs, ql, ws, wf, we)))
            # the XLA formulation gathers a table's whole width: it is
            # given the narrowest
            narrow = narrow or args

            @jax.jit
            def stack(q, new, lp, *meta):
                for _ in range(layers):
                    out, lp = mla._kernel_impl(q, new, lp, *meta, rank,
                                               scale, qb)
                return out, lp

            outs[width], ms = best_ms(stack, args, reps)
            timings[f"{name}.{width}"] = ms / layers
        base = outs[widths[0]]
        for width in widths[1:]:
            if not all(bool(jnp.array_equal(a, b))
                       for a, b in zip(base, outs[width])):
                raise RuntimeError(
                    f"{name}: width {width} answers otherwise than "
                    f"width {widths[0]} on the same rows")
        ref = mla._xla_impl(*narrow, rank, scale, qb)
        f32 = lambda a: a.astype(jnp.float32)        # noqa: E731
        live = jnp.asarray(ql > 0)[:, None, None, None]
        err = float(jnp.max(jnp.abs(jnp.where(
            live, f32(base[0]) - f32(ref[0]), 0))))
        unequal = float(jnp.mean(base[1][:trash] != ref[1][:trash]))
        timings[f"{name}.out_err"] = err
        timings[f"{name}.pool_share_unequal"] = unequal
        tol = 1e-5 if rehearse else 2e-2
        if unequal or not err <= tol * max(
                1.0, float(jnp.max(jnp.abs(f32(ref[0]))))):
            raise RuntimeError(f"{name}: latent kernel and XLA formulation "
                               f"differ: out {err}, pages {unequal}")
        ms = [timings[f"{name}.{x}"] for x in widths]
        if on_chip and max(ms) > (1 + WALK_WIDTH_RTOL) * min(ms):
            raise RuntimeError(f"{name}: the latent kernel's time follows "
                               f"the table's width: {ms} ms at {widths}")
    emit(phase="latent", device=device_info(),
         shapes=dict(heads=h, kv_rank=rank, rope=rope, row=w,
                     page_size=page, num_pages=pool, widths=list(widths)),
         ms_a_layer=timings)


def phase_kda(seed, rehearse, on_chip):
    """The gated delta rule alone at Kimi-Linear's head shape (32 heads
    of 128 x 128 float32): the Pallas step over 64 rows of one token
    against a pool of 65 slots (in place; held to the XLA chunkwise form
    on the same rows), and the chunk rows' loop with 1 and with 8 long
    rows of 64 tokens (held to the rows computed all at once)."""
    import jax
    import jax.numpy as jnp
    from paddle_tpu.ops import kda

    if rehearse:
        h, d, rows, slots, long_rows, q, layers, reps = 4, 16, 6, 7, 2, 8, 2, 1
    else:
        h, d, rows, slots, long_rows, q, layers, reps = \
            32, 128, 64, 65, 8, 64, 5, 5
    keys = jax.random.split(jax.random.PRNGKey(seed), 6)
    f32 = jnp.float32

    def unit(a):
        return a / jnp.linalg.norm(a, axis=-1, keepdims=True)

    def operands(lead):
        qq = unit(jax.random.normal(keys[0], lead + (h, d), f32)) * d ** -0.5
        kk = unit(jax.random.normal(keys[1], lead + (h, d), f32))
        vv = jax.random.normal(keys[2], lead + (h, d), f32)
        gg = -jax.random.uniform(keys[3], lead + (h, d), f32)
        bb = jax.random.uniform(keys[4], lead + (h,), f32)
        return qq, kk, vv, gg, bb

    pool = jax.random.normal(keys[5], (slots, h, d, d), f32)
    slot = jnp.asarray(np.random.RandomState(seed).permutation(slots - 1)
                       [:rows], jnp.int32)
    fresh = jnp.zeros((rows,), bool).at[1].set(True)
    one = operands((rows,))
    timings = {}

    @jax.jit
    def steps(pool, *ops):
        for _ in range(layers):
            o, pool = kda.kda_step(*ops, pool, slot, fresh)
        return o, pool

    @jax.jit
    def once(pool, *ops):
        return kda.kda_step(*ops, pool, slot, fresh)

    _, ms = best_ms(steps, (pool,) + one, reps)
    timings["step_ms_a_layer"] = ms / layers
    # least time: every row's state once in and once out
    timings["step_floor_ms"] = 1e3 * 2 * rows * h * d * d * 4 / 819e9
    o, new = once(pool, *one)
    s0 = jnp.where(fresh[:, None, None, None], 0.0, pool[slot])
    want_o, want_s = jax.jit(kda.kda_rows)(
        *(a[:, None] for a in one), s0, jnp.ones((rows,), jnp.int32))
    err_o = float(jnp.max(jnp.abs(o - want_o[:, 0])))
    err_s = float(jnp.max(jnp.abs(new[slot] - want_s)))
    rest = jnp.asarray([i for i in range(slots)
                        if i not in set(np.asarray(slot))], jnp.int32)
    touched = float(jnp.max(jnp.abs(new[rest] - pool[rest])))
    timings.update(step_out_err=err_o, step_state_err=err_s,
                   step_other_slots=touched)
    tol = 1e-4
    if touched or not max(err_o, err_s) <= tol:
        raise RuntimeError(f"kda_step and the XLA form differ: out "
                           f"{err_o}, state {err_s}, other slots {touched}")
    # the chunk rows
    chunk = operands((long_rows, q))
    s0 = pool[:long_rows]
    whole = jax.jit(kda.kda_rows)
    loop = jax.jit(functools.partial(kda.kda_rows, long_rows=long_rows))
    for there in (1, long_rows):
        lens = jnp.where(jnp.arange(long_rows) < there, q, 0) \
            .astype(jnp.int32)
        (lo, ls), ms = best_ms(loop, chunk + (s0, lens), reps)
        timings[f"chunk_ms.{there}_rows"] = ms
        wo, ws = whole(*chunk, s0, lens)
        live = (jnp.arange(long_rows) < there)[:, None, None, None]
        err = max(float(jnp.max(jnp.abs(jnp.where(live, lo - wo, 0)))),
                  float(jnp.max(jnp.abs(ls - ws))))
        timings[f"chunk_err.{there}_rows"] = err
        if not err <= tol:
            raise RuntimeError(f"the chunk rows' loop and the rows at "
                               f"once differ by {err}")
    _, ms = best_ms(whole, chunk + (s0, jnp.full((long_rows,), q,
                                                 jnp.int32)), reps)
    timings["chunk_ms.all_at_once"] = ms
    emit(phase="kda", device=device_info(),
         shapes=dict(heads=h, head_dim=d, rows=rows, slots=slots,
                     chunk_rows=long_rows, chunk=q), timings=timings)


# the flash kernels' bf16 results against the f32 XLA path, relative to
# its largest magnitude (the bf16 XLA path itself reads ~4e-3)
FLASH_RTOL = 2e-2
# bf16 MXU peak of one v5e (perfbench/harness/peaks.py)
V5E_BF16_FLOPS = 197e12


def phase_flash(seed, rehearse, on_chip):
    """The three training flash kernels alone, called by name at
    pretrain-4k's attention shape (2 x 4,096 tokens, 16 query heads over
    8 kv heads of 128, bf16, causal): ms a call each, beside the least
    time of the dots each executes at the bf16 peak. Then, at S = 1,024,
    the forward's output and the three gradients against the XLA path
    computed in f32 from the same bf16 inputs (and the bf16 XLA path's
    own error beside them)."""
    import jax
    import jax.numpy as jnp
    from paddle_tpu.nn.functional.attention import _naive_attention
    from paddle_tpu.ops import flash_attention as fa

    if rehearse:
        b, s, h, hk, d, check_s, reps = 1, 256, 4, 2, 128, 256, 1
    else:
        b, s, h, hk, d, check_s, reps = 2, 4096, 16, 8, 128, 1024, 10
    group, scale, bf16 = h // hk, d ** -0.5, jnp.bfloat16
    keys = jax.random.split(jax.random.PRNGKey(seed), 4)

    def normal(key, *shape):
        return jax.random.normal(key, shape, jnp.float32).astype(bf16)

    # head-major, as the kernels take them
    q, do = normal(keys[0], b, h, s, d), normal(keys[3], b, h, s, d)
    k, v = normal(keys[1], b, hk, s, d), normal(keys[2], b, hk, s, d)
    fwd = jax.jit(lambda q, k, v: fa._fwd(q, k, v, scale, True, group))
    (out, lse), fwd_ms = best_ms(fwd, (q, k, v), reps)
    delta = jnp.sum(do.astype(jnp.float32) * out.astype(jnp.float32),
                    axis=-1, keepdims=True)
    args = (q, k, v, do, lse, delta, scale, True, group)
    _, dq_ms = best_ms(jax.jit(fa._bwd_dq, static_argnums=(6, 7, 8)),
                       args, reps)
    _, dkv_ms = best_ms(jax.jit(fa._bwd_dkv, static_argnums=(6, 7, 8)),
                        args, reps)
    # a dot over every causal (query, key) pair: 2 FLOPs x head_dim
    dot_ms = 1e3 * 2 * d * b * h * s * (s + 1) / 2 / V5E_BF16_FLOPS
    timings = {"flash_fwd_ms": fwd_ms, "flash_dq_ms": dq_ms,
               "flash_dkdv_ms": dkv_ms, "fwd_dots_floor_ms": 2 * dot_ms,
               "dq_dots_floor_ms": 3 * dot_ms,
               "dkdv_dots_floor_ms": 4 * dot_ms}

    # parity at S = check_s, [B, S, H, D] as a model calls it
    q, do = (normal(keys[i], 1, check_s, h, d) for i in (0, 3))
    k, v = (normal(keys[i], 1, check_s, hk, d) for i in (1, 2))

    def xla(q, k, v):
        return _naive_attention(q, k, v, None, 0.0, True, None)

    f32 = lambda a: a.astype(jnp.float32)        # noqa: E731
    with jax.default_matmul_precision("highest"):
        ref, ref_vjp = jax.vjp(xla, f32(q), f32(k), f32(v))
        ref = (ref,) + ref_vjp(f32(do))
    flash = fa._make_flash(scale, True, group)
    for name, fn in (("kernel", flash), ("xla_bf16", xla)):
        got, vjp = jax.vjp(fn, q, k, v)
        for part, a, r in zip(("out", "dq", "dk", "dv"),
                              (got,) + vjp(do), ref):
            timings[f"{name}_err.{part}"] = float(
                jnp.max(jnp.abs(f32(a) - r)) / jnp.max(jnp.abs(r)))
    worst = max(timings[f"kernel_err.{p}"] for p in ("out", "dq", "dk", "dv"))
    if not worst <= FLASH_RTOL:
        raise RuntimeError(f"flash kernels and the f32 XLA path differ by "
                           f"{worst} of the reference's largest magnitude")
    emit(phase="flash", device=device_info(),
         shapes=dict(batch=b, seq=s, heads=h, kv_heads=hk, head_dim=d,
                     dtype="bfloat16", causal=True, check_seq=check_s),
         timings=timings)


# |kernel - XLA path in f32 at `highest`| on lse and pick, in logit units
# (one bf16 rounding of the operands moves a logit of std ~0.9 by ~3e-3)
CE_ATOL = 2e-2
# HBM bandwidth of one v5e (perfbench/harness/peaks.py)
V5E_HBM_BYTES = 819e9
# (block_n, block_v) pairs the ce phase times beside the chosen blocks:
# a 128-row tile (64 weight passes) and the rows x lanes around the
# chosen one
CE_SWEEP = ((128, 512), (512, 256), (512, 512), (512, 1024), (1024, 256),
            (1024, 512), (1024, 1024), (2048, 256), (2048, 512),
            (2048, 1024))


def phase_ce(seed, rehearse, on_chip):
    """The fused cross-entropy's forward kernel alone at pretrain-4k's
    head shape (8,192 tokens over hidden 2,048 and a vocabulary of
    92,544, f32, as the train step hands it over): ms a call at the
    blocks ``_blocks`` picks and at each pair of ``CE_SWEEP``, beside the
    least time of its dots at the bf16 peak and of its weight passes at
    the HBM bandwidth. Then lse and pick at the chosen blocks against
    the chunked-XLA formulation in f32 at `highest` (and the XLA path at
    the default precision beside them)."""
    import jax
    import jax.numpy as jnp
    from paddle_tpu.ops import fused_linear_cross_entropy as flce

    if rehearse:
        n, d, v, reps = 200, 128, 1000, 1
        sweep = ((64, 256), (128, 128))
    else:
        n, d, v, reps = 8192, 2048, 92544, 5
        sweep = CE_SWEEP
    f32 = jnp.float32
    keys = jax.random.split(jax.random.PRNGKey(seed), 3)
    h = jax.random.normal(keys[0], (n, d), f32)
    w = 0.02 * jax.random.normal(keys[1], (d, v), f32)
    lab = jax.random.randint(keys[2], (n,), 0, v, jnp.int32)
    lab = jnp.where(jnp.arange(n) % 97 == 0, -100, lab)   # ignored rows
    bn, bv = flce._blocks(n, d, v, 4)

    def floors(bn):
        passes = -(-n // bn)
        return {"passes": passes,
                "dots_floor_ms": 1e3 * 2 * n * d * v / V5E_BF16_FLOPS,
                "weight_floor_ms": 1e3 * (passes * d * v + n * d) * 4
                / V5E_HBM_BYTES}

    timings = {}
    for nb, vb in ((bn, bv),) + tuple(sweep):
        kern = jax.jit(functools.partial(flce._kernel_parts, block_n=nb,
                                         block_v=vb))
        (lse, pick), ms = best_ms(kern, (h, w, lab), reps)
        timings[f"ms.{nb}x{vb}"] = dict(ms=ms, **floors(nb))
        if (nb, vb) == (bn, bv):
            got = (lse, pick)
    with jax.default_matmul_precision("highest"):
        ref = jax.jit(lambda h, w, l: flce._xla_parts(h, w, l, 8192))(
            h, w, lab)
    xla = jax.jit(lambda h, w, l: flce._xla_parts(h, w, l, 8192))(h, w, lab)
    for name, parts in (("kernel", got), ("xla_default", xla)):
        for part, a, r in zip(("lse", "pick"), parts, ref):
            timings[f"{name}_err.{part}"] = float(jnp.max(jnp.abs(a - r)))
    worst = max(timings["kernel_err.lse"], timings["kernel_err.pick"])
    if not worst <= CE_ATOL:
        raise RuntimeError(f"the fused CE kernel and the f32 XLA path "
                           f"differ by {worst} in lse or pick")
    emit(phase="ce", device=device_info(),
         shapes=dict(tokens=n, hidden=d, vocab=v, dtype="float32",
                     block_n=bn, block_v=bv), timings=timings)


# ---------------------------------------------------------------------------
# train
# ---------------------------------------------------------------------------
def train_config(rehearse):
    from paddle_tpu.models import LlamaConfig, tiny_llama_config
    if rehearse:
        # seq 128 is the shortest the flash kernel takes
        return tiny_llama_config(max_position_embeddings=256), 128, 2
    return LlamaConfig(
        vocab_size=32000, hidden_size=2048, intermediate_size=5632,
        num_hidden_layers=8, num_attention_heads=16,
        num_key_value_heads=8, max_position_embeddings=4096), 2048, 2


def token_batches(cfg, seq, batch, steps, seed, workdir):
    """``steps`` batches of ``[batch, seq + 1]`` ids through the repo's
    ``TokenFeed`` over a corpus made from ``seed``; also the name of the
    feed that served them."""
    from paddle_tpu import native
    from paddle_tpu.io import TokenFeed
    path = os.path.join(workdir, "corpus.bin")
    np.random.RandomState(seed).randint(
        0, cfg.vocab_size, (4 * steps * batch, seq + 1)) \
        .astype(np.int32).tofile(path)
    feed = TokenFeed(path, sample_elems=seq + 1, batch_size=batch,
                     dtype=np.int32, seed=seed)
    name = type(feed).__module__ + "." + type(feed).__name__
    if not native.available():
        name += f" (numpy feed: {native.load_error()})"
    out = [np.array(next(feed), np.int64) for _ in range(steps)]
    feed.close()
    return out, name


def make_step(model, opt, donate_inputs):
    import paddle_tpu as paddle

    def step_fn(ids, labels):
        with paddle.amp.auto_cast(dtype="bfloat16"):
            loss, _ = model(ids, labels)
        loss.backward()
        opt.step()
        opt.clear_grad()
        return loss

    return paddle.jit.to_static(step_fn, state=[model, opt], warmup="once",
                                donate_inputs=donate_inputs,
                                name="chip_smoke.train_step")


def warm_optimizer(compiled, cfg, place):
    """The eager warmup of ``jit.to_static`` on a tiny shape (as
    examples/llama_pretrain.py does): materializes the AdamW state
    without paying a full-size eager pass."""
    ids = np.random.RandomState(0).randint(
        0, cfg.vocab_size, (2, 129)).astype(np.int64)
    compiled(place(ids[:, :-1]), place(ids[:, 1:]))


def phase_train(seed, rehearse, on_chip):
    import paddle_tpu as paddle
    from paddle_tpu import flags
    from paddle_tpu.models import LlamaForCausalLM

    cfg, seq, batch = train_config(rehearse)
    steps = 4
    with tempfile.TemporaryDirectory() as workdir:
        batches, feed_name = token_batches(cfg, seq, batch, steps, seed,
                                           workdir)
    paddle.seed(seed)
    model = LlamaForCausalLM(cfg)
    opt = paddle.optimizer.AdamW(learning_rate=3e-4, weight_decay=0.1,
                                 parameters=model.parameters())

    # the kernel-free reference for step 1: the same forward loss on the
    # same weights with the flash kernel off and the logits materialized
    def ref_loss(ids, labels):
        with paddle.no_grad(), paddle.amp.auto_cast(dtype="bfloat16"):
            loss, _ = model(ids, labels)
        return loss

    ref_fn = paddle.jit.to_static(ref_loss, state=[model], donate=False,
                                  warmup="once",
                                  name="chip_smoke.train_reference")
    ref_fn._warmed_any = True
    x0 = paddle.to_tensor(batches[0][:, :-1])
    y0 = paddle.to_tensor(batches[0][:, 1:])
    flags.set_flags({"use_pallas_kernels": False})
    os.environ["PADDLE_TPU_FUSED_CE"] = "0"
    try:
        reference = float(ref_fn(x0, y0))
    finally:
        flags.set_flags({"use_pallas_kernels": True})
        del os.environ["PADDLE_TPU_FUSED_CE"]
    ref_kernels = kernel_counts({"train_reference": ref_fn})
    if any(ref_kernels.values()):
        raise RuntimeError(f"the reference holds a kernel: {ref_kernels}")

    compiled = make_step(model, opt, donate_inputs=True)
    warm_optimizer(compiled, cfg, paddle.to_tensor)
    losses, seconds = [], []
    for b in batches:
        t0 = time.perf_counter()
        losses.append(float(compiled(paddle.to_tensor(b[:, :-1]),
                                     paddle.to_tensor(b[:, 1:]))))
        seconds.append(time.perf_counter() - t0)
    counts = kernel_counts({"train_step": compiled})
    # the warmup compiled nothing (eager), so every program is full size
    require_kernels(counts, on_chip)
    emit(phase="train", model="0.5b recipe (examples/llama_pretrain.py)",
         shapes=dict(hidden=cfg.hidden_size, ffn=cfg.intermediate_size,
                     heads=cfg.num_attention_heads,
                     kv_heads=cfg.num_key_value_heads,
                     vocab=cfg.vocab_size, layers=cfg.num_hidden_layers,
                     seq=seq, batch=batch, amp="bfloat16"),
         params=model.num_params(), token_feed=feed_name, losses=losses,
         first_step_seconds=seconds[0], later_step_seconds=seconds[1:],
         kernels=counts, cache=cache_stats(),
         device_bytes=device_bytes(),
         compared={"with": "forward loss of step 1, flash kernel off, "
                           "logits materialized (no kernel in program)",
                   "reference_loss": reference, "step1_loss": losses[0],
                   "rtol": TRAIN_LOSS_RTOL})
    if not np.isfinite(losses).all():
        raise RuntimeError(f"loss is not finite: {losses}")
    if abs(losses[0] - reference) > TRAIN_LOSS_RTOL * abs(reference):
        raise RuntimeError(
            f"step-1 loss {losses[0]} vs kernel-free {reference}")


# ---------------------------------------------------------------------------
# four chips: the sharded train step and its one-device comparison
# ---------------------------------------------------------------------------
def mesh_losses(cfg, batches, seed, mesh):
    import jax
    import paddle_tpu as paddle
    from paddle_tpu.distributed import Replicate, Shard, shard_tensor
    from paddle_tpu.models import LlamaForCausalLM, shard_llama

    paddle.seed(seed)
    model = LlamaForCausalLM(cfg)
    place = paddle.to_tensor
    if mesh is not None:
        shard_llama(model, mesh, tp_axis="mp")

        def place(a):
            return shard_tensor(paddle.to_tensor(a), mesh,
                                [Shard(0), Replicate()],
                                stop_gradient=True)

    opt = paddle.optimizer.AdamW(learning_rate=3e-4, weight_decay=0.1,
                                 parameters=model.parameters())
    compiled = make_step(model, opt, donate_inputs=False)
    warm_optimizer(compiled, cfg, place)
    t0 = time.perf_counter()
    losses = [float(compiled(place(b[:, :-1]), place(b[:, 1:])))
              for b in batches]
    seconds = time.perf_counter() - t0
    devices = sorted({s.device.id for p in model.parameters()
                      for s in p._data.addressable_shards})
    sharded = sum(len({s.device.id for s in p._data.addressable_shards
                       if s.data.shape != p._data.shape}) > 1
                  for p in model.parameters())
    counts = kernel_counts({"train_step": compiled},
                           resharded=mesh is not None)
    del compiled, opt, model
    jax.clear_caches()
    return losses, seconds, devices, sharded, counts


def phase_mesh(seed, rehearse, on_chip):
    import jax
    from paddle_tpu.distributed import ProcessMesh

    if len(jax.devices()) != 4:
        raise RuntimeError(f"--chips 4 found {len(jax.devices())} devices")
    cfg, seq, batch = train_config(rehearse)
    with tempfile.TemporaryDirectory() as workdir:
        batches, feed_name = token_batches(cfg, seq, batch, 4, seed,
                                           workdir)
    mesh = ProcessMesh(np.arange(4).reshape(2, 2), dim_names=["dp", "mp"])
    sharded, s_sec, s_dev, n_split, s_k = mesh_losses(cfg, batches, seed,
                                                      mesh)
    single, o_sec, o_dev, _, o_k = mesh_losses(cfg, batches, seed, None)
    emit(phase="mesh", mesh={"dp": 2, "mp": 2},
         model="0.5b recipe (examples/llama_pretrain.py)",
         shapes=dict(seq=seq, batch=batch, amp="bfloat16"),
         token_feed=feed_name, sharded_losses=sharded,
         one_device_losses=single, sharded_seconds=s_sec,
         one_device_seconds=o_sec, parameter_devices=s_dev,
         one_device_parameter_devices=o_dev,
         parameters_split_across_devices=n_split,
         kernels={"sharded": s_k, "one_device": o_k}, cache=cache_stats(),
         device_bytes=device_bytes(),
         compared={"with": "the same 4 steps on one device",
                   "rtol": MESH_LOSS_RTOL})
    if len(s_dev) != 4 or n_split == 0:
        raise RuntimeError(
            f"parameters lie on devices {s_dev}, {n_split} of them split")
    if not np.isfinite(sharded).all():
        raise RuntimeError(f"loss is not finite: {sharded}")
    np.testing.assert_allclose(sharded, single, rtol=MESH_LOSS_RTOL)
    require_kernels(o_k, on_chip)


def main():
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--chips", type=int, default=1, choices=(1, 4),
                    help="4: only the dp2 x mp2 train step and its "
                         "one-device comparison")
    ap.add_argument("--rehearse", action="store_true",
                    help="tiny sizes on whatever backend there is; "
                         "never prints the success line, exits "
                         f"{REHEARSAL_EXIT}")
    ap.add_argument("--phase", default="all",
                    choices=("all", "walk", "latent", "kda", "flash",
                             "ce", "serve", "train"),
                    help="one chip: run only this phase")
    args = ap.parse_args()

    import jax
    on_chip = jax.devices()[0].platform == "tpu"
    if not on_chip and not args.rehearse:
        sys.exit(f"chip_smoke: no TPU (jax found "
                 f"{jax.devices()[0].platform}); nothing was run")
    if len(jax.devices()) != args.chips and not args.rehearse:
        sys.exit(f"chip_smoke: --chips {args.chips} but jax found "
                 f"{len(jax.devices())} devices; nothing was run")

    sys.path.insert(0, os.path.dirname(os.path.abspath(__file__)))
    t0 = time.perf_counter()
    if args.chips == 4:
        phase_mesh(args.seed, args.rehearse, on_chip)
    else:
        for name, phase in (("walk", phase_walk), ("latent", phase_latent),
                            ("kda", phase_kda), ("flash", phase_flash),
                            ("ce", phase_ce), ("serve", phase_serve),
                            ("train", phase_train)):
            if args.phase in ("all", name):
                phase(args.seed, args.rehearse, on_chip)
                gc.collect()    # a phase's weights leave before the next
    emit(phase="all", seconds=time.perf_counter() - t0,
         cache=cache_stats())
    if args.rehearse:
        emit(ok=False, rehearsal=True, device=device_info())
        sys.exit(REHEARSAL_EXIT)
    emit(ok=True, device=device_info())


if __name__ == "__main__":
    main()
