"""Reverse-mode autograd engine.

Analog of the reference's queue-based backward runner
(`paddle/fluid/eager/backward.cc` — ``RunBackward`` + ``GeneralGrad`` for
``paddle.grad()``). Works on the GradNode tape recorded by
``framework.tensor.run_op``; each node's backward is a ``jax.vjp`` closure, so
gradients are exactly JAX's gradients.

Engine design:
- iterative DFS topological order (no recursion limit on deep graphs);
- cotangents for non-leaf tensors are keyed by ``(id(node), out_index)`` so
  gathering a node's output grads is O(n_outputs), not a scan over all live
  cotangents — backward is O(edges) overall;
- ``create_graph=True`` replays each node's backward *through the tape*: the
  vjp is re-derived from the node's saved pure function as a differentiable
  op of (primals, cotangents), so grad-of-grad works (the vjp closure alone
  treats primals as constants and would silently drop second-order terms).
"""

from __future__ import annotations

import jax
import jax.numpy as jnp

from .tensor import Tensor, run_op

__all__ = ["backward", "grad"]


def _topo_order(roots):
    """Reverse-topological order of GradNodes reachable from root tensors.

    Iterative DFS with an explicit stack (gray/black marking): graphs deeper
    than Python's recursion limit — long chains from unrolled loops — are
    fine, and diamond-shaped DAGs order correctly.
    """
    visited = set()
    order = []
    stack = [(t._node, False) for t in roots if t._node is not None]
    while stack:
        node, expanded = stack.pop()
        if expanded:
            order.append(node)
            continue
        if id(node) in visited:
            continue
        visited.add(id(node))
        stack.append((node, True))
        for t in node.inputs:
            n = t._node
            if n is not None and id(n) not in visited:
                stack.append((n, False))
    order.reverse()
    return order


def _key(t):
    """Cotangent-store key for a tensor: leaves by identity, non-leaves by
    their (node, output-slot) so lookup during the node sweep is O(1)."""
    if t._node is None:
        return id(t)
    return (id(t._node), t._out_index)


def _run(tensors, grad_tensors, accumulate_into_grad, targets=None,
         retain_graph=False, create_graph=False):
    """Core engine shared by ``Tensor.backward`` and ``paddle.grad``.

    grads accumulate per tensor slot (``_key``), matching the reference's
    ``GradTensorHolder`` multi-path accumulation.
    """
    from .tensor import no_grad

    # cotangent store: _key(tensor) -> jnp array (or Tensor if create_graph)
    cotangents = {}
    leaf_holders = {}  # id -> Tensor (keep leaves alive for .grad writes)

    def _raw(g):
        return g._data if isinstance(g, Tensor) else g

    def _acc(key, g):
        if key in cotangents:
            prev = cotangents[key]
            if create_graph:
                pt = prev if isinstance(prev, Tensor) else Tensor(prev)
                gt = g if isinstance(g, Tensor) else Tensor(g)
                cotangents[key] = run_op("grad_accumulate", jnp.add, (pt, gt))
            else:
                cotangents[key] = prev + _raw(g)
        else:
            cotangents[key] = g

    hook_owners = {}   # _key -> Tensor with registered hooks
    finalized = set()  # keys whose hooks already fired

    for t, g in zip(tensors, grad_tensors):
        if t.stop_gradient and t._node is None:
            raise RuntimeError(
                "backward() called on a tensor with stop_gradient=True and no "
                "grad history")
        if g is None:
            if t.size != 1:
                raise RuntimeError(
                    "grad_tensor must be given for non-scalar outputs "
                    f"(shape {t.shape})")
            g_val = jnp.ones_like(t._data)
        elif create_graph and isinstance(g, Tensor):
            # keep the Tensor so double-backward sees the dependence on the
            # seed (e.g. HVP w.r.t. the vector in grad_outputs)
            g_val = g
        else:
            g_val = g._data if isinstance(g, Tensor) else jnp.asarray(g)
        _acc(_key(t), g_val)
        if t._backward_hooks:
            hook_owners[_key(t)] = t
        if t._node is None:
            leaf_holders[id(t)] = t

    order = _topo_order(tensors)

    def fire_hooks(t, g):
        if t._backward_hooks:
            tg = g if isinstance(g, Tensor) else Tensor(g, stop_gradient=not create_graph)
            for hook in t._backward_hooks:
                r = hook(tg)
                if r is not None:
                    tg = r if isinstance(r, Tensor) else Tensor(r)
            return tg if create_graph else tg._data
        return g

    def _finalize(key, val):
        """Apply tensor hooks once, on the fully-accumulated gradient
        (reference: hooks run on the final grad, not per-edge partials)."""
        owner = hook_owners.get(key)
        if owner is not None and key not in finalized:
            finalized.add(key)
            val = fire_hooks(owner, val)
        return val

    grad_ctx = _null_ctx if create_graph else no_grad

    # snapshot targets as their cotangents complete: a slot's accumulation is
    # final exactly when its producing node is processed (all consumers come
    # earlier in reverse-topo order), and the sweep pops it then.
    results = {}
    target_slots = {}
    if targets is not None:
        for t in targets:
            target_slots.setdefault(_key(t), []).append(id(t))

    def _snapshot(key, val):
        for tid in target_slots.get(key, ()):
            results[tid] = val

    # prune to the useful subgraph when specific targets are requested
    # (reference: GeneralGrad restricts traversal to output->input paths,
    # `fluid/eager/backward.cc:103`). A node is useful iff its backward
    # contributes — directly or through another useful node — to a target.
    useful = None
    if targets is not None:
        target_ids = {id(t) for t in targets}
        useful = set()
        for node in reversed(order):  # leaf-most first
            for t in node.inputs:
                if id(t) in target_ids or (
                        t._node is not None and id(t._node) in useful):
                    useful.add(id(node))
                    break

    with grad_ctx():
        for node in order:
            if useful is not None and id(node) not in useful:
                continue
            # O(1) gather of this node's output cotangents
            outs = []
            any_ct = False
            for i in range(node.n_outputs):
                found = cotangents.pop((id(node), i), None)
                if found is not None:
                    found = _finalize((id(node), i), found)
                    _snapshot((id(node), i), found)
                if found is None:
                    shape, dt = node.out_avals[i]
                    outs.append(jnp.zeros(shape, dt))
                else:
                    any_ct = True
                    outs.append(_raw(found) if not create_graph else found)
            if not any_ct:
                continue
            if node.vjp_fn is _used_up:
                node.vjp_fn()  # raises the freed-graph error
            if create_graph:
                ct_in = _replay_through_tape(node, outs)
            else:
                ct_in = node.vjp_fn(tuple(outs) if node.n_outputs > 1 else outs[0])
            for t, g in zip(node.inputs, ct_in):
                key = _key(t)
                if t._backward_hooks:
                    hook_owners[key] = t
                if t._node is None:
                    leaf_holders[id(t)] = t
                _acc(key, g)
            if not retain_graph:
                node.vjp_fn = _used_up
                node.pure_fn = None    # release saved-forward closures
                node.replay_fn = None

    if targets is not None:
        for t in targets:
            if id(t) in results:
                continue
            val = cotangents.get(_key(t))
            if val is not None:
                results[id(t)] = _finalize(_key(t), val)
        return results

    # write leaf grads
    for tid, t in leaf_holders.items():
        arr = cotangents.get(tid)
        if arr is None:
            continue
        if t._node is None and not t.stop_gradient and accumulate_into_grad:
            arr = _raw(_finalize(tid, arr))
            if t.grad is None:
                t.grad = Tensor(arr, stop_gradient=True)
            else:
                t.grad = Tensor(t.grad._data + arr, stop_gradient=True)
    return results


def _replay_through_tape(node, out_cts):
    """Run a node's backward as differentiable ops so a new tape is recorded.

    The vjp is re-derived from ``node.pure_fn`` (the pure jax function of the
    node's differentiable inputs saved by ``run_op``): as a function of
    (primals, cotangents) it is itself traceable, so second-order grads see
    the full dependence on the primal inputs.
    """
    ct_tensors = [c if isinstance(c, Tensor) else Tensor(c, stop_gradient=True)
                  for c in out_cts]
    if node.pure_fn is None:
        if node.replay_fn is not None:
            # PyLayer: the user backward runs Tensor ops, recording its own tape
            return node.replay_fn(ct_tensors)
        raise NotImplementedError(
            f"create_graph=True through op '{node.name}' is not supported: "
            "the node has no saved forward function or Tensor-level backward.")
    n_in = len(node.inputs)
    multi = node.n_outputs > 1

    def grad_fn(*args):
        primals = args[:n_in]
        cts = args[n_in:]
        _, vjp = jax.vjp(node.pure_fn, *primals)
        return vjp(tuple(cts) if multi else cts[0])

    res = run_op(node.name + "_grad", grad_fn,
                 tuple(node.inputs) + tuple(ct_tensors))
    return res if isinstance(res, tuple) else (res,)


def _used_up(*_):
    raise RuntimeError(
        "Trying to backward through the graph a second time. Set "
        "retain_graph=True when calling backward the first time.")


class _null_ctx:
    def __enter__(self):
        return self

    def __exit__(self, *a):
        return False


@jax.named_scope("backward")        # a traced step's ops carry the phase
def backward(tensors, grad_tensors=None, retain_graph=False):
    """``paddle.autograd.backward`` — accumulate into ``.grad`` of leaves."""
    if grad_tensors is None:
        grad_tensors = [None] * len(tensors)
    _run(tensors, grad_tensors, accumulate_into_grad=True,
         retain_graph=retain_graph)


def grad(outputs, inputs, grad_outputs=None, retain_graph=None,
         create_graph=False, only_inputs=True, allow_unused=False,
         no_grad_vars=None):
    """``paddle.grad`` — return grads of ``inputs`` without touching ``.grad``.

    Reference: ``GeneralGrad`` in `fluid/eager/backward.cc:103`.
    """
    outputs = outputs if isinstance(outputs, (list, tuple)) else [outputs]
    inputs = inputs if isinstance(inputs, (list, tuple)) else [inputs]
    if grad_outputs is None:
        grad_outputs = [None] * len(outputs)
    elif not isinstance(grad_outputs, (list, tuple)):
        grad_outputs = [grad_outputs]
    if retain_graph is None:
        retain_graph = create_graph
    res = _run(outputs, grad_outputs, accumulate_into_grad=False,
               targets=inputs, retain_graph=retain_graph,
               create_graph=create_graph)
    out = []
    for t in inputs:
        if id(t) in res:
            v = res[id(t)]
            if isinstance(v, Tensor):
                out.append(v)
            else:
                out.append(Tensor(v, stop_gradient=not create_graph))
        else:
            if not allow_unused:
                raise RuntimeError(
                    "One of the input tensors was not used in the graph "
                    "(pass allow_unused=True to return None for it).")
            out.append(None)
    return out
