"""Optimizer base class.

Reference: `python/paddle/optimizer/optimizer.py:104` (``Optimizer``:
accumulator creation, grad clip + regularization hooks, ``step`` /
``clear_grad`` / ``state_dict``). TPU-native design: the whole update is
pure jnp on the Tensor payloads — under ``paddle_tpu.jit`` tracing the
entire ``opt.step()`` folds into the one compiled XLA computation, with
optimizer state as donated inputs. The learning rate enters as a scalar
(host value or scheduler output) so lr changes never retrace.
"""

from __future__ import annotations

import collections

import jax
import jax.numpy as jnp

from ..framework.tensor import Tensor, Parameter, no_grad
from ..framework import dtype as dtypes
from . import lr as lr_mod

__all__ = ["Optimizer"]

_LOW_PRECISION = ("bfloat16", "float16")


class Optimizer:
    """Base optimizer. Subclasses implement ``_create_accumulators`` and
    ``_single_update(p, g, lr)`` returning the new parameter value (and
    updating accumulators via ``_set_accumulator``)."""

    _accum_names: tuple = ()

    def __init__(self, learning_rate=0.001, parameters=None, weight_decay=None,
                 grad_clip=None, multi_precision=False, name=None):
        if parameters is None:
            raise ValueError(
                "parameters is required (eager mode): pass model.parameters()")
        self._parameter_list = []
        self._param_groups = []
        plist = list(parameters)
        if plist and isinstance(plist[0], dict):
            for group in plist:
                g = dict(group)
                g["params"] = list(g["params"])
                self._param_groups.append(g)
                self._parameter_list.extend(g["params"])
        else:
            self._param_groups.append({"params": plist})
            self._parameter_list = plist
        self._learning_rate = learning_rate
        self._lr_override = None   # traced scalar injected by paddle_tpu.jit
        self.regularization = weight_decay
        self._group_weight_decay = None  # set per-group during step()
        self._grad_clip = grad_clip
        self._multi_precision = multi_precision
        self._name = name or type(self).__name__.lower()
        # accumulators: name -> {id(param): Tensor}
        self._accumulators = collections.defaultdict(dict)
        self._accumulators_created = False
        self._param_names = {}
        for i, p in enumerate(self._parameter_list):
            self._param_names[id(p)] = p.name or f"param_{i}"

    # -- learning rate ------------------------------------------------------
    def get_lr(self):
        if self._lr_override is not None:
            return self._lr_override
        if isinstance(self._learning_rate, lr_mod.LRScheduler):
            return self._learning_rate()
        return float(self._learning_rate)

    def set_lr(self, value):
        if isinstance(self._learning_rate, lr_mod.LRScheduler):
            raise RuntimeError(
                "cannot set_lr when the learning rate is an LRScheduler")
        self._learning_rate = float(value)

    def set_lr_scheduler(self, scheduler):
        if not isinstance(scheduler, lr_mod.LRScheduler):
            raise TypeError("expected an LRScheduler")
        self._learning_rate = scheduler

    # -- accumulators -------------------------------------------------------
    def _add_accumulator(self, name, param, dtype=None, fill_value=0.0,
                         shape=None):
        if id(param) in self._accumulators[name]:
            return self._accumulators[name][id(param)]
        shape = shape if shape is not None else param._data.shape
        dt = dtypes.convert_dtype(dtype) if dtype is not None else param._data.dtype
        if self._multi_precision and str(param.dtype) in _LOW_PRECISION \
                and dtype is None:
            dt = jnp.float32
        t = Tensor(jnp.full(shape, fill_value, dtype=dt), stop_gradient=True)
        t.name = f"{self._param_names[id(param)]}_{name}"
        self._accumulators[name][id(param)] = t
        return t

    def _get_accumulator(self, name, param):
        try:
            return self._accumulators[name][id(param)]
        except KeyError:
            raise RuntimeError(
                f"accumulator {name!r} for parameter "
                f"{self._param_names.get(id(param))} not created yet")

    def _set_accumulator(self, name, param, value):
        acc = self._accumulators[name][id(param)]
        acc._data = value if not isinstance(value, Tensor) else value._data

    def _master_weight(self, param):
        """fp32 master copy for low-precision params (reference:
        optimizer.py _create_master_weight)."""
        if not (self._multi_precision and str(param.dtype) in _LOW_PRECISION):
            return None
        if id(param) not in self._accumulators["master_weight"]:
            t = Tensor(param._data.astype(jnp.float32), stop_gradient=True)
            t.name = f"{self._param_names[id(param)]}_master_weight"
            self._accumulators["master_weight"][id(param)] = t
        return self._accumulators["master_weight"][id(param)]

    def _create_accumulators(self, params):
        for name in self._accum_names:
            for p in params:
                self._add_accumulator(name, p)

    # -- the update ---------------------------------------------------------
    def _apply_regularization(self, p, g):
        """L2 regularization folded into the gradient (reference:
        ``append_regularization_ops``). Param-level regularizer wins over
        the group-level one, which wins over the optimizer-level one
        (reference optimizer.py:1918 sets param.regularizer from the group)."""
        if getattr(p, "regularizer", None) is not None:
            reg = p.regularizer
        elif self._group_weight_decay is not None:
            reg = self._group_weight_decay
        else:
            reg = self.regularization
        if reg is None:
            return g
        coeff = getattr(reg, "coeff", None)
        if coeff is None:  # plain float weight_decay == L2Decay
            coeff = float(reg)
        if getattr(reg, "_l1", False):
            return g + coeff * jnp.sign(p._data).astype(g.dtype)
        return g + jnp.asarray(coeff, g.dtype) * p._data.astype(g.dtype)

    @no_grad()
    @jax.named_scope("optimizer")   # a traced step's ops carry the phase
    def step(self):
        params_grads = [(p, p.grad) for p in self._parameter_list
                        if p.trainable and p.grad is not None]
        if self._grad_clip is not None:
            params_grads = self._grad_clip(params_grads)
        # _add_accumulator is idempotent — run every step so params whose
        # grads first appear later (staged unfreezing) get their state
        self._create_accumulators([p for p, _ in params_grads])
        self._accumulators_created = True
        for group in self._param_groups:
            group_lr_scale = group.get("learning_rate", 1.0)
            self._group_weight_decay = group.get("weight_decay")
            group_params = {id(p) for p in group["params"]}
            for p, g in params_grads:
                if id(p) not in group_params:
                    continue
                lr = self.get_lr() * group_lr_scale \
                    * p.optimize_attr.get("learning_rate", 1.0)
                garr = g._data if isinstance(g, Tensor) else g
                master = self._master_weight(p)
                if master is not None:
                    new_master = self._single_update(
                        p, self._apply_regularization(
                            p, garr.astype(jnp.float32)), lr,
                        value=master._data)
                    master._data = new_master
                    p._data = new_master.astype(p._data.dtype)
                else:
                    garr = self._apply_regularization(p, garr.astype(p._data.dtype))
                    p._data = self._single_update(p, garr, lr, value=p._data)

    def _single_update(self, p, g, lr, value):
        raise NotImplementedError

    minimize_step = step

    def minimize(self, loss, startup_program=None, parameters=None,
                 no_grad_set=None):
        """Reference ``Optimizer.minimize``: backward + step."""
        loss.backward()
        self.step()
        return None, [(p, p.grad) for p in self._parameter_list]

    # -- bookkeeping --------------------------------------------------------
    @no_grad()
    def clear_grad(self, set_to_zero=False):
        for p in self._parameter_list:
            p.clear_gradient(set_to_zero)

    clear_gradients = clear_grad

    def state_dict(self):
        """Accumulators keyed by '{param_name}_{acc_name}' (reference:
        optimizer.py state_dict), plus scheduler state under 'LR_Scheduler'."""
        state = {}
        for name, per_param in self._accumulators.items():
            for pid, acc in per_param.items():
                state[acc.name] = acc
        if isinstance(self._learning_rate, lr_mod.LRScheduler):
            state["LR_Scheduler"] = self._learning_rate.state_dict()
        return state

    def set_state_dict(self, state_dict):
        state_dict = dict(state_dict)
        sched = state_dict.pop("LR_Scheduler", None)
        if sched is not None and isinstance(self._learning_rate,
                                            lr_mod.LRScheduler):
            self._learning_rate.set_state_dict(sched)
        if not self._accumulators_created:
            self._create_accumulators(
                [p for p in self._parameter_list if p.trainable])
            self._accumulators_created = True
        for name, per_param in self._accumulators.items():
            for pid, acc in per_param.items():
                if acc.name in state_dict:
                    v = state_dict[acc.name]
                    acc._data = jnp.asarray(
                        v._data if isinstance(v, Tensor) else v,
                        dtype=acc._data.dtype)

    def _accumulator_pytree(self):
        """(names, list-of-lists of Tensors) for jit capture — a stable
        flattening of all optimizer state."""
        out = []
        for name in sorted(self._accumulators):
            for pid in self._accumulators[name]:
                out.append(self._accumulators[name][pid])
        return out
