"""Driver of the training cells: the ``jit.to_static`` AdamW step of
``examples/llama_pretrain.py``'s shape, fed by ``TokenFeed`` through
its prefetcher."""

import gc
import os
import time

import numpy as np

from . import program, stats, trace as tr, traffic

pc = time.perf_counter
CHECK_STEPS = 3


def hyper(mix):
    o = mix["optimizer"]
    return (o["learning_rate"], o["beta1"], o["beta2"], o["epsilon"],
            o["weight_decay"])


def _norms(arrays):
    import jax
    import jax.numpy as jnp

    @jax.jit
    def f(xs):
        return [jnp.sqrt(jnp.sum(jnp.square(x.astype(jnp.float32))))
                for x in xs]
    return [float(v) for v in f(arrays)]


def _delta_norms(family, cfg, seed, params):
    """Norm of each leaf's change from the seed's weights (made again
    leaf by leaf: the step donated the ones the program was given)."""
    import jax
    import jax.numpy as jnp

    @jax.jit
    def f(new, old):
        return {k: jnp.sqrt(jnp.sum(jnp.square(
            new[k].astype(jnp.float32) - old[k]))) for k in old}

    out = {}
    ends = family.ends(cfg, seed, "float32")
    out.update(f({k: params[k]._data for k in ends}, ends))
    for i in range(family.layer_count(cfg)):
        lw = family.layer(cfg, seed, i, "float32")
        got = f({k: params[f"layers.{i}.{k}"]._data for k in lw}, lw)
        out.update({f"layers.{i}.{k}": v for k, v in got.items()})
    return {k: float(v) for k, v in out.items()}


def build(run):
    """Set-up: ONE compiled step with its state, reset to the seed."""
    import jax
    import jax.numpy as jnp
    import paddle_tpu as paddle
    from paddle_tpu.io import DevicePrefetcher, TokenFeed
    from paddle_tpu.observability import compile_watch as cw

    cw.enable_persistent_cache()
    cfg, mix, family = run.cfg, run.mix, run.family
    seq, batch = int(mix["seq_len"]), int(mix["batch"])
    os.makedirs(run.work_dir, exist_ok=True)
    path = os.path.join(run.work_dir, f"corpus.{run.cell['name']}.bin")
    traffic.train_corpus(mix, cfg["vocab_size"], run.seed).tofile(path)
    source = TokenFeed(path, sample_elems=seq + 1, batch_size=batch,
                       dtype=np.int32, seed=int(run.seed) % (1 << 31))
    run.fed = []

    def split(ids):
        if len(run.fed) < CHECK_STEPS:
            run.fed.append(np.array(ids))
        ids = ids.astype(np.int64)
        return (np.ascontiguousarray(ids[:, :-1]),
                np.ascontiguousarray(ids[:, 1:]))

    t = pc()
    model = family.build_model(cfg, "float32")
    lr, b1, b2, eps, wd = hyper(mix)
    opt = paddle.optimizer.AdamW(learning_rate=lr, beta1=b1, beta2=b2,
                                 epsilon=eps, weight_decay=wd,
                                 parameters=model.parameters())

    def step_fn(ids, labels):
        with paddle.amp.auto_cast(dtype=cfg["torch_dtype"]):
            loss, _ = model(ids, labels)
        loss.backward()
        opt.step()
        opt.clear_grad()
        return loss

    step = paddle.jit.to_static(step_fn, state=[model, opt], warmup="once",
                                donate_inputs=True,
                                name="perfbench.train_step")
    # the example's eager warm-up on a tiny shape materializes the
    # optimizer's state; it is a real step, so the state is then set
    # back to the seed: weights, zero moments, beta powers of 1
    wids = traffic.rng_of(run.seed, 6).integers(
        0, cfg["vocab_size"], (1, 129)).astype(np.int64)
    step(paddle.to_tensor(wids[:, :-1]), paddle.to_tensor(wids[:, 1:]))
    run.note(phase="build_and_eager_warmup", seconds=pc() - t)
    t = pc()
    _, n = program.assign_weights(family, model, cfg, run.seed, "float32",
                                  keep=False)
    if n != family.total_params(cfg):
        raise RuntimeError(f"program holds {n} parameters, the "
                           f"configuration {family.total_params(cfg)}")
    for name, acc in opt.state_dict().items():
        if name == "LR_Scheduler":
            continue
        fill = 1.0 if name.endswith("_pow_acc") else 0.0
        acc._data = jnp.full(acc._data.shape, fill, acc._data.dtype)
    run.note(phase="seed_state", seconds=pc() - t, parameters=n)
    feed = DevicePrefetcher(source, transform=split)

    def next_batch():
        x, y = next(feed)
        return paddle.to_tensor(x), paddle.to_tensor(y)

    run.step, run.next_batch, run.feed = step, next_batch, feed
    run.model, run.opt = model, opt
    # the first steps, through the window's own call and feed
    params = family.leaves(model, cfg)
    names = list(params)
    t = pc()
    losses = [run.step(*next_batch())]
    jax.block_until_ready(losses[0]._data)
    run.note(phase="first_step", seconds=pc() - t,
             cache=program.cache_stats())
    m1 = [opt._get_accumulator("moment1", params[k])._data for k in names]
    grad = dict(zip(names, [v / (1.0 - b1) for v in _norms(m1)]))
    del m1
    for _ in range(CHECK_STEPS - 1):
        losses.append(run.step(*next_batch()))
    delta = _delta_norms(family, cfg, run.seed, params)
    run.program_readings = {"loss": [float(l) for l in losses],
                            "grad": grad, "delta": delta}
    run.note(phase="check_steps", losses=run.program_readings["loss"])


def window(run, seconds):
    import jax
    mix = run.mix
    tokens_step = int(mix["seq_len"]) * int(mix["batch"])
    traced = run.trace
    t_len = min(float(mix["trace_seconds"]), seconds * 0.8)
    t_at = (seconds - t_len) / 2
    facts = {}
    gc.collect()
    gc.freeze()
    compiles0 = program.metric("paddle_tpu_xla_backend_compile_total")
    run.feed.mark()
    t_open = pc()
    t_close = t_open + seconds
    losses, state = [], "before" if traced else "off"
    while True:
        now = pc()
        if now >= t_close:
            break
        if state == "before" and now >= t_open + t_at:
            if losses:
                jax.block_until_ready(losses[-1]._data)
            facts["t_mark"] = tr.start(run.trace_dir)
            facts["steps_at_mark"] = len(losses)
            state = "on"
        elif state == "on" and now >= facts["t_mark"] + t_len:
            jax.block_until_ready(losses[-1]._data)
            facts["t_unmark"] = pc()
            facts["steps_traced"] = len(losses) - facts["steps_at_mark"]
            jax.profiler.stop_trace()
            state = "done"
        losses.append(run.step(*run.next_batch()))
        if len(losses) > 1:
            # at most one step runs ahead of the host
            jax.block_until_ready(losses[-2]._data)
    jax.block_until_ready(losses[-1]._data)
    t_end = pc()
    if state == "on":
        facts["t_unmark"] = t_end
        facts["steps_traced"] = len(losses) - facts["steps_at_mark"]
        jax.profiler.stop_trace()
    gc.unfreeze()
    stall, wall = run.feed.mark()
    facts.update(t_open=t_open, t_end=t_end, steps=len(losses),
                 tokens=len(losses) * tokens_step,
                 input_stall_share=stall / wall,
                 compiles_in_window=program.metric(
                     "paddle_tpu_xla_backend_compile_total") - compiles0,
                 losses=[float(l) for l in losses])
    return facts


def worst_gap(prog, ref, keep=None):
    """Worst leaf of |program's norm - reference's norm| against the
    reference's norm of that leaf or of the median leaf, whichever is
    larger. Returns (gap, leaf)."""
    med = stats.median(list(ref.values()))
    worst, leaf = 0.0, None
    for k, r in ref.items():
        if keep is not None and k not in keep:
            continue
        g = abs(prog[k] - r) / max(r, med)
        if g >= worst:
            worst, leaf = g, k
    return worst, leaf


def compare(prog, ref):
    """The numbers compared for a training cell."""
    steps = [abs(p - r) / abs(r) for p, r in zip(prog["loss"], ref["loss"])]
    loss = max(steps)
    med = stats.median(list(ref["grad"].values()))
    # leaves whose reference gradient is nought to rounding move under
    # Adam by round-off alone: left out of the change, by this rule
    moved = {k for k, g in ref["grad"].items() if g >= 1e-3 * med}
    grad, gleaf = worst_gap(prog["grad"], ref["grad"])
    delta, dleaf = worst_gap(prog["delta"], ref["delta"], moved)
    return {"loss1_gap": steps[0], "loss_gap": loss, "grad_gap": grad,
            "delta_gap": delta}, \
        {"loss_gaps": steps, "grad_leaf": gleaf, "delta_leaf": dleaf,
         "left_out": sorted(set(ref["grad"]) - moved)}


def check(run):
    """The comparison that decides ``correct``: every number beside its
    limit. With ``--control`` the control's readings (the reference in
    int8, or with half of the batch left out) stand in the program's
    place, and ``correct`` has to come out false."""
    limits = run.mix["check"]["limits"]
    t = pc()
    batches, hp = run.fed[:CHECK_STEPS], hyper(run.mix)
    readings = run.family.train_readings
    ref = readings(run.cfg, run.seed, batches, hp)
    held = run.program_readings
    if run.control:
        planted = {"int8": dict(quant="int8"),
                   "half_batch": dict(rows=range(
                       int(run.mix["batch"]) // 2))}[run.control]
        nums, _ = compare(held, ref)
        run.note(phase="control", in_the_programs_place=run.control,
                 **{"program_" + k: v for k, v in nums.items()})
        held = readings(run.cfg, run.seed, batches, hp, **planted)
    nums, where = compare(held, ref)
    run.note(phase="reference", seconds=pc() - t, losses=ref["loss"],
             held_losses=held["loss"], loss1_gap=nums["loss1_gap"],
             loss_gap=nums["loss_gap"], **where)
    checks = [[k, float(v), float(limits[k])] for k, v in nums.items()
              if k in limits]
    return checks, all(v <= lim for _, v, lim in checks)


def read_trace(run, facts):
    t = tr.load_xplane(run.trace_dir)
    win, sp, mark = tr.traced_window(t, facts["t_mark"], facts["t_unmark"])
    run.facts.update(
        trace=t, window_ns=win, span_ns=sp, mark_found=mark is not None,
        gap_label=lambda s, e: "host: unattributed",
        steps_traced=facts["steps_traced"],
        step_pattern=tr.STEP_MODULE)


def run(run):
    import jax
    build(run)
    run.setup_s = pc() - run.t0
    facts = window(run, run.seconds)
    mix, cfg = run.mix, run.cfg
    wall = facts["t_end"] - facts["t_open"]
    run.e2e = {"train_tok_s": facts["tokens"] / wall,
               "setup_s": run.setup_s}
    flops = run.family.train_flops_per_token(cfg, int(mix["seq_len"]))
    run.note(phase="window", steps=facts["steps"], wall_seconds=wall,
             step_seconds=wall / facts["steps"],
             compiles_in_window=facts["compiles_in_window"],
             input_stall_share=facts["input_stall_share"],
             cache=program.cache_stats(), last_loss=facts["losses"][-1],
             flops_per_token=flops,
             mfu_flops_only_percent=100.0 * flops * facts["tokens"] / wall
             / (run.peaks["flops_per_s"] * run.chips)
             if run.peaks else None, **run.e2e)
    run.attempted, run.failed = facts["steps"], int(
        not np.isfinite(facts["losses"]).all())
    run.facts.update(tokens_per_step=int(mix["seq_len"]) * int(mix["batch"]),
                     sequences_per_step=int(mix["batch"]),
                     seq_len=int(mix["seq_len"]))
    run.memory_peak = run.read_memory_peak()
    if run.trace:
        read_trace(run, facts)
    # free the program's state before the reference runs
    run.feed.close()
    run.step = run.next_batch = run.feed = run.model = run.opt = None
    gc.collect()
    jax.clear_caches()
    run.checks, ok = check(run)
    run.correct = bool(ok and run.failed == 0)
    run.checks.append(["non_finite_losses", run.failed, 0])
