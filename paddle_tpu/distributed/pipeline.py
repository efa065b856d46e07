"""Pipeline parallelism as a compiled collective program.

Reference: `python/paddle/distributed/fleet/meta_parallel/
pipeline_parallel.py:149` (1F1B), `:987` (interleave/VPP),
`passes/pipeline_scheduler_pass/pipeline_zero_bubble.py:32`, with stage
p2p in `pp_utils/p2p_communication.py`.

TPU-native design — the schedule IS the program, not a Python runtime:

- Per-stage weights are STACKED on a leading layer axis and sharded over
  the mesh's ``pp`` axis (``Shard(0)``), so each device holds its stage's
  layers. There is no per-rank process, no send/recv runtime, no
  interceptor actors (reference `fleet_executor/`): one SPMD program runs
  on every device.
- ``pipeline_spmd`` runs the classic fill-drain (GPipe) schedule as a
  ``lax.scan`` over ``M + P - 1`` ticks inside ``shard_map``; activations
  hop stages via ``lax.ppermute`` (collective-permute on the ICI ring —
  the hardware path the reference's NCCL send/recv approximates).
- Backward is ``jax.vjp`` through the scan: XLA schedules the reverse
  pipeline automatically. The 1F1B schedule's *memory* benefit is had via
  ``remat=True`` (``jax.checkpoint`` per stage — recompute activations in
  the backward sweep instead of storing M microbatches of them).

The eager p2p primitives this module rides on live in `p2p.py`
(send_forward/send_backward = the edge-truncated ppermute).
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np
from jax import shard_map
from jax.sharding import PartitionSpec

from .process_mesh import ProcessMesh

__all__ = ["pipeline_spmd", "pipeline_1f1b", "stack_stage_params"]


def stack_stage_params(param_trees):
    """Stack a list of per-layer pytrees into one stacked pytree with a
    leading layer axis (the layout ``pipeline_spmd`` shards over pp)."""
    return jax.tree_util.tree_map(
        lambda *leaves: jnp.stack(leaves, axis=0), *param_trees)


def pipeline_spmd(stage_fn, stacked_params, x, *, mesh, axis="pp",
                  num_microbatches, remat=False, num_virtual_stages=1,
                  watch_name="distributed.pipeline_spmd"):
    """Run ``stage_fn`` as a P-stage pipeline over ``num_microbatches``.

    Args:
        stage_fn: ``(stage_params, h) -> h`` where ``stage_params`` leaves
            have leading dim ``L // (P * V)`` (one chunk's layers) and
            ``h`` is one microbatch of activations. Must preserve ``h``'s
            shape. Pass a STABLE function object — the compiled pipeline
            is memoized on its identity.
        stacked_params: pytree of arrays with leading dim L (total
            layers) in LAYER ORDER; this call commits the pp sharding
            (reordering layers for the interleaved layout internally).
        x: ``[B, ...]`` activations; B must divide by num_microbatches.
        mesh: ProcessMesh (or jax Mesh) containing ``axis``.
        remat: checkpoint each stage application (1F1B-like memory:
            activations recompute in the backward sweep instead of M
            microbatches of them being stored).
        watch_name: compile-watch label for this pipeline's programs
            (callers owning a model, e.g. ``LlamaForCausalLMPipe``, pass
            their own so compile metrics attribute to the model).
        num_virtual_stages: V > 1 runs the interleaved (VPP) schedule of
            the reference's ``PipelineParallelWithInterleave``
            (`pipeline_parallel.py:987`): layer chunk ``c`` lives on
            device ``c % P``, activations ride the ``ppermute`` ring V
            times, and the fill/drain bubble shrinks from
            ``(P-1)/(M+P-1)`` to ``(P-1)/(M*V+P-1)``. Requires
            ``L % (P*V) == 0`` and ``M % P == 0``.

    Returns ``[B, ...]`` outputs, replicated over ``axis``.
    """
    jmesh = mesh.to_jax_mesh() if isinstance(mesh, ProcessMesh) else mesh
    P = jmesh.shape[axis]
    M = int(num_microbatches)
    V = int(num_virtual_stages)
    if x.shape[0] % M:
        raise ValueError(
            f"batch {x.shape[0]} not divisible by microbatches {M}")
    flat, treedef = jax.tree_util.tree_flatten(stacked_params)
    L = flat[0].shape[0]
    if L % (P * V):
        raise ValueError(
            f"{L} stacked layers not divisible by {P} stages x {V} chunks")
    if V > 1:
        if M % P:
            raise ValueError(
                f"interleaved schedule needs microbatches ({M}) divisible "
                f"by stages ({P}) — injection groups are P microbatches")
        # reorder layers chunk-major by owner device: device d's chunks
        # are c = d, P+d, 2P+d, ... so Shard(0) hands it [V, lpc] layers
        lpc = L // (P * V)
        order = np.concatenate(
            [np.arange((v * P + d) * lpc, (v * P + d + 1) * lpc)
             for d in range(P) for v in range(V)])
        flat = [p[order] for p in flat]
    run = _build_run(stage_fn, jmesh, axis, M, bool(remat), treedef, V,
                     watch_name)
    return run(tuple(flat), x)


@functools.lru_cache(maxsize=64)
def _build_run(stage_fn, jmesh, axis, M, remat, treedef, V=1,
               watch_name="distributed.pipeline_spmd"):
    """One jitted pipeline program per (stage_fn, mesh, schedule) config —
    shard_map must live under jit (remat inside eager shard_map is
    unsupported), and the cache keeps eager steps from re-lowering."""
    fn = jax.checkpoint(stage_fn) if remat else stage_fn
    P = jmesh.shape[axis]
    n_leaves = treedef.num_leaves
    p_spec = jax.tree_util.tree_unflatten(
        treedef, [PartitionSpec(axis)] * n_leaves)

    def per_device(params_local, xm_local):
        stage = jax.lax.axis_index(axis)
        T = M + P - 1
        mb = xm_local.shape[1]
        perm = [(i, i + 1) for i in range(P - 1)]

        def tick(carry, t):
            h_recv, out = carry
            idx = jnp.clip(t, 0, M - 1)
            x_in = jax.lax.dynamic_index_in_dim(xm_local, idx, 0,
                                                keepdims=False)
            h_in = jnp.where(stage == 0, x_in, h_recv)
            h_out = fn(params_local, h_in)
            # the last stage banks microbatch t-(P-1) once it exists
            widx = jnp.clip(t - (P - 1), 0, M - 1)
            should = jnp.logical_and(stage == P - 1, t >= P - 1)
            cur = jax.lax.dynamic_index_in_dim(out, widx, 0, keepdims=False)
            out = jax.lax.dynamic_update_index_in_dim(
                out, jnp.where(should, h_out, cur), widx, 0)
            if perm:
                h_next = jax.lax.ppermute(h_out, axis, perm)
            else:
                h_next = h_out
            return (h_next, out), None

        init = (jnp.zeros((mb,) + xm_local.shape[2:], xm_local.dtype),
                jnp.zeros_like(xm_local))
        (_, out), _ = jax.lax.scan(tick, init, jnp.arange(T))
        # only the last stage holds real outputs; make them replicated
        out = jax.lax.psum(
            jnp.where(stage == P - 1, out, jnp.zeros_like(out)), axis)
        return out

    def per_device_interleaved(params_local, xm_local):
        """VPP: device d holds V chunks ([V, lpc] leading dims after the
        caller's layer reorder); an activation rides the wraparound ring
        through virtual stages v*P + d. Device d at tick t serves chunk
        ``v = ((t-d)//P) % V``; injection groups of P microbatches make
        the wrapped activation arrive exactly when its next chunk's slot
        opens (collision-free — see the schedule derivation in
        pipeline_spmd's docstring)."""
        stage = jax.lax.axis_index(axis)
        T = M * V + P - 1
        mb = xm_local.shape[1]
        chunked = jax.tree_util.tree_map(
            lambda p: p.reshape((V, p.shape[0] // V) + p.shape[1:]),
            params_local)
        perm = [(i, (i + 1) % P) for i in range(P)]  # wraparound ring

        def tick(carry, t):
            h_recv, out = carry
            rel = t - stage                   # position in my active window
            v = jnp.clip((rel // P) % V, 0, V - 1)
            g = rel // (V * P)                # injection group
            j = rel % P                       # index within the group
            m = jnp.clip(g * P + j, 0, M - 1)
            x_in = jax.lax.dynamic_index_in_dim(xm_local, m, 0,
                                               keepdims=False)
            inject = jnp.logical_and(stage == 0, v == 0)
            h_in = jnp.where(inject, x_in, h_recv)
            params_v = jax.tree_util.tree_map(
                lambda p: jax.lax.dynamic_index_in_dim(
                    p, v, 0, keepdims=False), chunked)
            h_out = fn(params_v, h_in)
            # last device banks chunk V-1 results as they complete
            should = jnp.logical_and(
                jnp.logical_and(stage == P - 1, v == V - 1),
                jnp.logical_and(rel >= 0, rel < M * V))
            cur = jax.lax.dynamic_index_in_dim(out, m, 0, keepdims=False)
            out = jax.lax.dynamic_update_index_in_dim(
                out, jnp.where(should, h_out, cur), m, 0)
            h_next = jax.lax.ppermute(h_out, axis, perm) if P > 1 else h_out
            return (h_next, out), None

        init = (jnp.zeros((mb,) + xm_local.shape[2:], xm_local.dtype),
                jnp.zeros_like(xm_local))
        (_, out), _ = jax.lax.scan(tick, init, jnp.arange(T))
        out = jax.lax.psum(
            jnp.where(stage == P - 1, out, jnp.zeros_like(out)), axis)
        return out

    if V > 1:
        per_device = per_device_interleaved

    inner = shard_map(per_device, mesh=jmesh,
                      in_specs=(p_spec, PartitionSpec()),
                      out_specs=PartitionSpec(), check_vma=False)

    def run(flat_params, x):
        params = jax.tree_util.tree_unflatten(treedef, list(flat_params))
        B = x.shape[0]
        xm = x.reshape((M, B // M) + x.shape[1:])
        y = inner(params, xm)
        return y.reshape((B,) + y.shape[2:])

    from ..observability.compile_watch import watched_jit
    return watched_jit(run, name=watch_name)


def pipeline_1f1b(stage_fn, loss_fn, stacked_params, x, y, *, mesh,
                  axis="pp", num_microbatches):
    """Explicit 1F1B training schedule (reference
    `fleet/meta_parallel/pipeline_parallel.py:149` ``_forward_backward_
    pipeline``; weight-grad split per
    `passes/pipeline_scheduler_pass/pipeline_zero_bubble.py:32`).

    Unlike :func:`pipeline_spmd` (+ outer ``jax.vjp``), the backward is
    part of the schedule: one ``lax.scan`` over ``2M + 2P - 2`` ticks
    where stage ``s`` runs F of microbatch m at tick ``s + 2m`` and B at
    ``2P - 1 - s + 2m`` — forward and backward interleave exactly as in
    the reference's steady state, so each stage stashes at most
    ``P - s`` in-flight microbatch activations (a static ``min(P, M)``
    slot ring buffer) instead of the fill-drain schedule's ``M``. That
    is 1F1B's memory profile, by construction.

    Zero-bubble property: each B tick computes dx (the cotangent the
    upstream stage is waiting for) and dW from one shared VJP; dW has no
    consumer inside the tick, so XLA's latency-hiding scheduler overlaps
    it with the backward ``ppermute`` — the ZB-H1 "W off the critical
    path" move, emitted by the compiler instead of a hand schedule.

    Args:
        stage_fn: ``(stage_params, h) -> h`` (shape-preserving).
        loss_fn: ``(h, labels) -> scalar`` mean loss per microbatch.
        stacked_params: pytree with leading layer dim ``L`` (sharded
            over ``axis``; ``L % P == 0``).
        x: ``[B, ...]`` inputs; y: ``[B, ...]`` labels.

    Returns ``(loss, grads)`` — scalar mean loss (replicated) and a
    grads pytree shaped like ``stacked_params``.
    """
    jmesh = mesh.to_jax_mesh() if isinstance(mesh, ProcessMesh) else mesh
    P = jmesh.shape[axis]
    M = int(num_microbatches)
    if x.shape[0] % M:
        raise ValueError(
            f"batch {x.shape[0]} not divisible by microbatches {M}")
    flat, treedef = jax.tree_util.tree_flatten(stacked_params)
    if flat[0].shape[0] % P:
        raise ValueError(f"{flat[0].shape[0]} layers not divisible by {P}")
    run = _build_1f1b(stage_fn, loss_fn, jmesh, axis, M, treedef)
    return run(tuple(flat), x, y)


@functools.lru_cache(maxsize=64)
def _build_1f1b(stage_fn, loss_fn, jmesh, axis, M, treedef):
    P = jmesh.shape[axis]
    S = min(P, M)                         # 1F1B in-flight stash depth
    n_leaves = treedef.num_leaves
    p_spec = jax.tree_util.tree_unflatten(
        treedef, [PartitionSpec(axis)] * n_leaves)

    def per_device(params_local, xm, ym):
        stage = jax.lax.axis_index(axis)
        mb = xm.shape[1]
        T = 2 * M + 2 * P - 2
        perm_f = [(i, i + 1) for i in range(P - 1)]
        perm_b = [(i + 1, i) for i in range(P - 1)]

        def tick(carry, t):
            h_recv, g_recv, stash, gacc, loss_acc = carry
            # ---- forward lane: F_m at t = stage + 2m -----------------
            # (F and B parities are opposite per stage, so each tick
            # pays for at most ONE of the two lax.cond bodies — the
            # inactive lane contributes zero FLOPs, giving the schedule
            # its 1F1B cost instead of F+B every tick)
            rel_f = t - stage
            f_act = (rel_f >= 0) & (rel_f % 2 == 0) & (rel_f < 2 * M)
            m_f = jnp.clip(rel_f // 2, 0, M - 1)
            x_in = jax.lax.dynamic_index_in_dim(xm, m_f, 0, keepdims=False)
            h_in = jnp.where(stage == 0, x_in, h_recv)
            h_out = jax.lax.cond(
                f_act, lambda h: stage_fn(params_local, h),
                lambda h: jnp.zeros_like(h), h_in)
            slot_f = m_f % S
            cur = jax.lax.dynamic_index_in_dim(stash, slot_f, 0,
                                               keepdims=False)
            stash = jax.lax.dynamic_update_index_in_dim(
                stash, jnp.where(f_act, h_in, cur), slot_f, 0)
            # ---- backward lane: B_m at t = 2P - 1 - stage + 2m -------
            rel_b = t - (2 * P - 1 - stage)
            b_act = (rel_b >= 0) & (rel_b % 2 == 0) & (rel_b < 2 * M)
            m_b = jnp.clip(rel_b // 2, 0, M - 1)
            h_saved = jax.lax.dynamic_index_in_dim(stash, m_b % S, 0,
                                                   keepdims=False)
            y_in = jax.lax.dynamic_index_in_dim(ym, m_b, 0, keepdims=False)

            def bwd(args):
                h_saved, y_in, g_recv = args
                h_rec, fvjp = jax.vjp(stage_fn, params_local, h_saved)
                loss_m, lvjp = jax.vjp(lambda h: loss_fn(h, y_in), h_rec)
                (ct_loss,) = lvjp(jnp.ones((), loss_m.dtype))
                ct = jnp.where(stage == P - 1, ct_loss, g_recv)
                dp, dx = fvjp(ct)
                return dp, dx, loss_m

            def bwd_zero(args):
                h_saved, y_in, g_recv = args
                return (jax.tree_util.tree_map(jnp.zeros_like,
                                               params_local),
                        jnp.zeros_like(h_saved), jnp.zeros((), jnp.float32))

            dp, dx, loss_m = jax.lax.cond(
                b_act, bwd, bwd_zero, (h_saved, y_in, g_recv))
            gacc = jax.tree_util.tree_map(
                lambda a, d: a + d.astype(a.dtype), gacc, dp)
            loss_acc = loss_acc + jnp.where(
                stage == P - 1, loss_m, 0.0)
            # ---- ride the rings ----------------------------------------
            h_next = jax.lax.ppermute(h_out, axis, perm_f) if perm_f \
                else h_out
            g_next = jax.lax.ppermute(dx, axis, perm_b) if perm_b else dx
            return (h_next, g_next, stash, gacc, loss_acc), None

        zero_h = jnp.zeros((mb,) + xm.shape[2:], xm.dtype)
        init = (zero_h, zero_h,
                jnp.zeros((S,) + zero_h.shape, xm.dtype),
                jax.tree_util.tree_map(
                    lambda p: jnp.zeros(p.shape, jnp.float32),
                    params_local),
                jnp.zeros((), jnp.float32))
        (_, _, _, gacc, loss_acc), _ = jax.lax.scan(
            tick, init, jnp.arange(T))
        loss = jax.lax.psum(loss_acc, axis) / M
        # the objective is the MEAN over microbatches; gacc summed them
        gacc = jax.tree_util.tree_map(lambda g: g / M, gacc)
        return loss, gacc

    inner = shard_map(per_device, mesh=jmesh,
                      in_specs=(p_spec, PartitionSpec(), PartitionSpec()),
                      out_specs=(PartitionSpec(), p_spec),
                      check_vma=False)

    def run(flat_params, x, y):
        params = jax.tree_util.tree_unflatten(treedef, list(flat_params))
        B = x.shape[0]
        xm = x.reshape((M, B // M) + x.shape[1:])
        ym = y.reshape((M, B // M) + y.shape[1:])
        return inner(params, xm, ym)

    from ..observability.compile_watch import watched_jit
    return watched_jit(run, name="distributed.pipeline_1f1b")
