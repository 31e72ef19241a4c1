"""Optimizer base + SGD family.

Reference: ``python/paddle/optimizer/optimizer.py`` (SURVEY.md §2.1). The
reference's perf trick is fused multi-tensor kernels (``fused_adamw``); the
TPU-native equivalent here is one ``jax.jit``-compiled update over the whole
parameter pytree with **donated** buffers — XLA fuses the elementwise update
chain across all parameters and reuses the parameter memory in place.
"""

from __future__ import annotations

import functools
from typing import Any, Callable, Dict, List, Optional, Sequence, Tuple

import jax
import jax.numpy as jnp

from ..core.tensor import Tensor, to_tensor
from ..enforce import InvalidArgumentError
from .lr import LRScheduler

__all__ = ["Optimizer", "SGD", "Momentum", "Adagrad", "Adadelta", "RMSProp",
           "ASGD", "Rprop"]


class L2Decay:
    def __init__(self, coeff=0.0):
        self.coeff = float(coeff)


class L1Decay:
    def __init__(self, coeff=0.0):
        self.coeff = float(coeff)


class Optimizer:
    """Base optimizer over the eager tape's ``.grad`` accumulators."""

    def __init__(self, learning_rate=0.001, parameters=None, weight_decay=None,
                 grad_clip=None, name=None):
        if parameters is not None:
            parameters = list(parameters)
            if parameters and isinstance(parameters[0], dict):
                raise InvalidArgumentError("param groups not supported yet; pass a flat list")
        self._parameter_list = parameters
        self._learning_rate = learning_rate
        self._grad_clip = grad_clip
        if isinstance(weight_decay, float):
            self._l2_coeff = weight_decay
        elif isinstance(weight_decay, L2Decay):
            self._l2_coeff = weight_decay.coeff
        else:
            self._l2_coeff = 0.0
        self._accumulators: Dict[int, Dict[str, Any]] = {}
        self._step_count = 0
        self._jit_update = None  # cached jitted fused step

    # -- lr ------------------------------------------------------------------
    def get_lr(self) -> float:
        if isinstance(self._learning_rate, LRScheduler):
            return float(self._learning_rate.get_lr())
        return float(self._learning_rate)

    def set_lr(self, value: float):
        if isinstance(self._learning_rate, LRScheduler):
            raise InvalidArgumentError("set_lr not allowed when using an LRScheduler")
        self._learning_rate = float(value)

    # -- state ---------------------------------------------------------------
    def _state_names(self) -> List[str]:
        return []

    def _init_state(self, p: Tensor) -> Dict[str, jax.Array]:
        return {}

    def _ensure_state(self, p: Tensor) -> Dict[str, Any]:
        st = self._accumulators.get(id(p))
        if st is None:
            st = self._init_state(p)
            self._accumulators[id(p)] = st
        return st

    def state_dict(self) -> Dict[str, Any]:
        out: Dict[str, Any] = {"LR_Scheduler": {}, "master_weights": {}}
        if isinstance(self._learning_rate, LRScheduler):
            out["LR_Scheduler"] = self._learning_rate.state_dict()
        params = self._params()
        for i, p in enumerate(params):
            st = self._accumulators.get(id(p))
            if st is None:
                continue
            # export param-shaped state (the Pallas fused path keeps
            # accumulators as flat [rows, 128] segments between steps)
            st = self._shaped_state(p._value, st)
            for k, v in st.items():
                out[f"{p.name}.{k}"] = to_tensor(v) if not isinstance(v, Tensor) else v
        out["@step"] = self._step_count
        return out

    def set_state_dict(self, state: Dict[str, Any]):
        if isinstance(self._learning_rate, LRScheduler) and state.get("LR_Scheduler"):
            self._learning_rate.set_state_dict(state["LR_Scheduler"])
        self._step_count = int(state.get("@step", 0))
        params = {p.name: p for p in self._params()}
        # group state entries per stored param name, preserving order
        grouped: Dict[str, Dict[str, Any]] = {}
        for key, val in state.items():
            if key in ("LR_Scheduler", "master_weights", "@step"):
                continue
            pname, _, sname = key.rpartition(".")
            grouped.setdefault(pname, {})[sname] = (
                val._value if isinstance(val, Tensor) else jnp.asarray(val)
            )
        matched = [n for n in grouped if n in params]
        if grouped and not matched:
            # Auto-generated tensor names are process-global, so a resumed
            # process may have shifted names — fall back to positional
            # mapping (state-dict insertion order vs parameter order).
            ordered = list(self._params())
            for (pname, st_vals), p in zip(grouped.items(), ordered):
                st = self._ensure_state(p)
                st.update(st_vals)
            return
        for pname in matched:
            p = params[pname]
            st = self._ensure_state(p)
            st.update(grouped[pname])

    # -- grads ---------------------------------------------------------------
    def _params(self) -> List[Tensor]:
        if self._parameter_list is None:
            raise InvalidArgumentError(
                "Optimizer was created without a parameters list"
            )
        return [p for p in self._parameter_list if not p.stop_gradient]

    def clear_grad(self, set_to_zero: bool = False):
        for p in self._params():
            p.clear_grad()

    clear_gradients = clear_grad

    # -- the fused step -------------------------------------------------------
    def _update_one(self, p, g, state: Dict[str, Any], lr, step, extras=None):
        """Pure per-parameter update: returns (new_p, new_state)."""
        raise NotImplementedError

    def _per_param_extras(self, p) -> Dict[str, Any]:
        """Per-parameter traced scalars (e.g. AdamW's decay coefficient) —
        passed through the jit as data so host-side per-param decisions don't
        bake into the compiled program."""
        return {}

    def _apply_weight_decay_to_grad(self) -> bool:
        """L2-style decay folded into the gradient (Adam/SGD semantics)."""
        return True

    # elementwise-update optimizers (every _update_one math op is
    # per-element with scalar coefficients) may be FLAT-PACKED by
    # apply_updates: the multi-tensor fused path. Optimizers whose update
    # uses per-PARAM reductions (Lamb's trust ratio, LBFGS) must leave
    # this False.
    _elementwise_update = False
    _FLAT_PACK_MAX = 65536  # elements; larger tensors update solo
    # kind tag for the Pallas flat-buffer fused update
    # (ops/pallas/multi_tensor_update.py). None -> XLA packing only.
    # Lamb sets this DESPITE _elementwise_update=False: the kernel path
    # handles its per-tensor trust reduction via the plan's segment ids.
    _FUSED_PALLAS_KIND: Optional[str] = None

    def _fused_hyper(self, extras: Dict[str, Any]) -> Dict[str, Any]:
        """Static per-group scalars for the Pallas fused update (groups
        are split by ``extras``, so e.g. AdamW decay is one scalar)."""
        return {}

    def apply_updates(self, pvals, gvals, svals, evals, static_evals,
                      lr_, step_):
        """Per-param updates, FLAT-PACKED for elementwise optimizers (the
        reference's fused multi_tensor_momentum/adam kernels): a conv net
        holds hundreds of small tensors, and one compiled fusion per
        param is launch-bound — ~14 ms/step of the ResNet-50 profile
        against a ~0.5 ms HBM floor. Packing groups params whose dtype /
        state structure / extras agree, concatenates them flat, runs ONE
        update, and slices the results back (static offsets).

        ``static_evals`` are the HOST-side extras used for grouping (the
        traced ``evals`` values cannot key a dict at trace time).

        Only SMALL params pack (<= _FLAT_PACK_MAX elements): flattening a
        large tiled conv weight is a physical relayout copy on TPU
        (measured: packing everything traded 14 ms of launches for 32 ms
        of reshapes/copies on ResNet-50), while a big tensor's single
        fused update amortizes its launch anyway. Small 1-D/score tensors
        are exactly the launch-bound population.

        On TPU (flag ``use_pallas_fused_update``) supported optimizers
        route every group through the Pallas flat-buffer kernels instead
        (ops/pallas/multi_tensor_update.py): no stack/concat temporaries,
        params/moments updated in place via aliasing, and state kept in
        the flat layout between steps. CPU / meshes / unsupported kinds
        keep the XLA packing below."""
        n = len(pvals)
        kind = self._FUSED_PALLAS_KIND
        if kind is not None and n > 8:
            from ..ops.pallas import multi_tensor_update as _mtu

            if _mtu.fused_update_active(n, kind):
                return self._apply_updates_pallas(
                    _mtu, kind, pvals, gvals, svals, evals, static_evals,
                    lr_, step_)
        # state may arrive as flat [rows, 128] segments from an earlier
        # Pallas-fused program (the flag was live then); the XLA paths
        # below work on shaped state
        svals = [self._shaped_state(pv, sv)
                 for pv, sv in zip(pvals, svals)]
        if not self._elementwise_update or n <= 8:
            out = [self._update_one(p, g, s, lr_, step_, e)
                   for p, g, s, e in zip(pvals, gvals, svals, evals)]
            return [o[0] for o in out], [o[1] for o in out]
        import numpy as _np

        groups: Dict[Any, list] = {}
        for i, pv in enumerate(pvals):
            skey = tuple(sorted((k, str(v.dtype)) for k, v in
                                svals[i].items()))
            ekey = tuple(sorted((k, float(v)) for k, v in
                                (static_evals[i] or {}).items()))
            if int(_np.prod(pv.shape)) > self._FLAT_PACK_MAX:
                # big tensors STACK by identical shape on a new leading
                # axis — a pure memcpy concat of identically-tiled arrays
                # (flattening would relayout)
                key = ("stack", tuple(pv.shape), str(pv.dtype), skey, ekey)
            else:
                key = ("flat", str(pv.dtype), skey, ekey)
            groups.setdefault(key, []).append(i)
        new_p: list = [None] * n
        new_s: list = [None] * n
        for key, idxs in groups.items():
            if len(idxs) == 1:
                i = idxs[0]
                new_p[i], new_s[i] = self._update_one(
                    pvals[i], gvals[i], svals[i], lr_, step_, evals[i])
                continue
            if key[0] == "stack":
                pc = jnp.stack([pvals[i] for i in idxs])
                gc = jnp.stack([gvals[i] for i in idxs])
                sc = {k: jnp.stack([svals[i][k] for i in idxs])
                      for k in svals[idxs[0]]}
                npc, nsc = self._update_one(pc, gc, sc, lr_, step_,
                                            evals[idxs[0]])
                for j, i in enumerate(idxs):
                    new_p[i] = npc[j]
                    new_s[i] = {k: v[j] for k, v in nsc.items()}
                continue
            sizes = [int(_np.prod(pvals[i].shape)) for i in idxs]
            pc = jnp.concatenate([pvals[i].reshape(-1) for i in idxs])
            gc = jnp.concatenate([gvals[i].reshape(-1) for i in idxs])
            sc = {k: jnp.concatenate([svals[i][k].reshape(-1)
                                      for i in idxs])
                  for k in svals[idxs[0]]}
            npc, nsc = self._update_one(pc, gc, sc, lr_, step_,
                                        evals[idxs[0]])
            off = 0
            for i, sz in zip(idxs, sizes):
                new_p[i] = jax.lax.slice_in_dim(
                    npc, off, off + sz).reshape(pvals[i].shape)
                new_s[i] = {
                    k: jax.lax.slice_in_dim(v, off, off + sz).reshape(
                        svals[i][k].shape) for k, v in nsc.items()}
                off += sz
        return new_p, new_s

    def _shaped_state(self, pv, sv: Dict[str, Any]) -> Dict[str, Any]:
        """Undo the Pallas flat [rows, 128] state layout for paths that
        need param-shaped state (XLA packing after a flag flip, state
        export). Only kind-tagged optimizers can ever hold flat state."""
        if self._FUSED_PALLAS_KIND is None or not sv:
            return sv
        import numpy as _np
        n = int(_np.prod(pv.shape)) if len(pv.shape) else 1
        rows = -(-n // 128)
        out = {}
        for k, v in sv.items():
            if (hasattr(v, "ndim") and v.ndim == 2
                    and tuple(v.shape) == (rows, 128)
                    and tuple(pv.shape) != (rows, 128)):
                v = v.reshape(-1)[:n].reshape(tuple(pv.shape))
            out[k] = v
        return out

    def _apply_updates_pallas(self, mtu, kind, pvals, gvals, svals, evals,
                              static_evals, lr_, step_):
        """The flat-buffer fused path: one Pallas launch per (dtype,
        state-structure, static-extras) group, whole population — big
        conv weights included (the stack path's size split existed to
        bound XLA relayouts; the kernel has none)."""
        n = len(pvals)
        groups: Dict[Any, list] = {}
        for i, pv in enumerate(pvals):
            skey = tuple(sorted((k, str(v.dtype))
                                for k, v in svals[i].items()))
            ekey = tuple(sorted((k, float(v)) for k, v in
                                (static_evals[i] or {}).items()))
            groups.setdefault((str(pv.dtype), skey, ekey), []).append(i)
        new_p: list = [None] * n
        new_s: list = [None] * n
        for key, idxs in groups.items():
            if len(idxs) == 1:
                i = idxs[0]
                new_p[i], new_s[i] = self._update_one(
                    pvals[i], gvals[i],
                    self._shaped_state(pvals[i], svals[i]),
                    lr_, step_, evals[i])
                continue
            plan = mtu.FlatPlan([pvals[i].shape for i in idxs])
            hyper = self._fused_hyper(static_evals[idxs[0]] or {})
            npl, nsl = mtu.apply_flat_update(
                kind, plan, [pvals[i] for i in idxs],
                [gvals[i] for i in idxs], [svals[i] for i in idxs],
                hyper, lr_, step_)
            for j, i in enumerate(idxs):
                new_p[i] = npl[j]
                new_s[i] = nsl[j]
        return new_p, new_s

    def step(self):
        params = self._params()
        # SelectedRows grads (sparse embeddings) densify here: default-mode
        # Adam/SGD touch every row anyway (reference: non-lazy adam over
        # SelectedRows does the same merge+apply).
        pgs = [
            (p, (p.grad.to_dense()._value
                 if getattr(p.grad, "is_selected_rows", False)
                 else p.grad._value))
            for p in params if p.grad is not None
        ]
        if not pgs:
            return
        if self._grad_clip is not None:
            pgs = self._grad_clip(pgs)
        lr = self.get_lr()
        self._step_count += 1
        from ..observability import metrics as _obs

        _obs.counter("optimizer.steps").inc()
        _obs.gauge("optimizer.lr").set(float(lr))
        states = [self._ensure_state(p) for p, _ in pgs]
        state_keys = self._state_names()

        static_evals = [self._per_param_extras(p) for p, _ in pgs]
        # read by the jitted update AT TRACE TIME (a structure change in
        # the param pytree retraces, picking up the current list — a
        # closure captured at build time would go stale). A VALUE change
        # with the same pytree structure would NOT retrace, so the evals
        # repr is part of the cache key: any change drops the cached jit
        # (the stale grouping would silently mis-update fused groups).
        # The Pallas fused-update dispatch state rides the key too: a
        # runtime flag flip must rebuild the program (layout is traced).
        from ..ops.pallas.multi_tensor_update import fused_update_signature
        evals_key = repr((static_evals, fused_update_signature()))
        if getattr(self, "_static_evals_key", None) != evals_key:
            self._jit_update = None
            self._static_evals_key = evals_key
        self._static_evals = static_evals
        if self._jit_update is None:
            from ..jit import register_compiled_cache

            register_compiled_cache(self)  # analysis.recompile introspection
            l2 = self._l2_coeff
            decay_in_grad = self._apply_weight_decay_to_grad()
            opt = self

            @functools.partial(jax.jit, donate_argnums=(0, 2))
            def fused(pvals, gvals, svals, evals, lr_, step_):
                # the same scope name as llama.train_step's update
                with jax.named_scope("optimizer"):
                    gvals = [g.astype(p.dtype) if g.dtype != p.dtype else g
                             for p, g in zip(pvals, gvals)]
                    if l2 and decay_in_grad:
                        gvals = [g + l2 * p for p, g in zip(pvals, gvals)]
                    return opt.apply_updates(pvals, gvals, svals, evals,
                                             opt._static_evals, lr_, step_)

            self._jit_update = fused

        pvals = [p._value for p, _ in pgs]
        gvals = [g for _, g in pgs]
        svals = [{k: s[k] for k in state_keys} for s in states]
        evals = static_evals
        new_p, new_s = self._jit_update(
            pvals, gvals, svals, evals, jnp.float32(lr), jnp.int32(self._step_count)
        )
        for (p, _), np_, ns_ in zip(pgs, new_p, new_s):
            p._inplace_set(np_)
            self._accumulators[id(p)] = ns_

    @jax.named_scope("optimizer_minimize")
    def minimize(self, loss, startup_program=None, parameters=None, no_grad_set=None):
        from ..static.graph import is_symbolic

        if is_symbolic(loss):
            # static mode: register the optimize spec on the loss's program —
            # the Executor computes grads inside the compiled replay and this
            # optimizer steps through its own donated-jit update (see
            # static/executor.py)
            prog = loss.block.program
            if parameters:
                params = [p for p in parameters if not p.stop_gradient]
            elif self._parameter_list is not None:
                params = self._params()
            else:
                params = [t for t in prog.captures.values() if not t.stop_gradient]
            if self._parameter_list is None:
                self._parameter_list = params
            prog._optimize_spec = (self, loss, params)
            prog._version += 1
            return None, None
        loss.backward()
        self.step()
        return None, None

    def cache_info(self):
        """Cache-key introspection (analysis.recompile): the donated jit
        update retraces per (static-extras, kernel-dispatch) signature;
        jax.jit handles shape keying underneath."""
        key = getattr(self, "_static_evals_key", None)
        return {"name": f"optimizer_update:{type(self).__name__}",
                "keys": [key] if key is not None else []}

    def _set_parameters(self, parameters):
        self._parameter_list = list(parameters)
        self._jit_update = None


class SGD(Optimizer):
    _elementwise_update = True
    _FUSED_PALLAS_KIND = "sgd"
    def __init__(self, learning_rate=0.001, parameters=None, weight_decay=None,
                 grad_clip=None, name=None):
        super().__init__(learning_rate, parameters, weight_decay, grad_clip, name)

    def _update_one(self, p, g, state, lr, step, extras=None):
        return p - lr.astype(p.dtype) * g, state


class Momentum(Optimizer):
    _elementwise_update = True
    _FUSED_PALLAS_KIND = "momentum"
    def __init__(self, learning_rate=0.001, momentum=0.9, parameters=None,
                 use_nesterov=False, weight_decay=None, grad_clip=None, name=None):
        super().__init__(learning_rate, parameters, weight_decay, grad_clip, name)
        self._momentum = momentum
        self._nesterov = use_nesterov

    def _fused_hyper(self, extras):
        return {"momentum": self._momentum, "nesterov": self._nesterov}

    def _state_names(self):
        return ["velocity"]

    def _init_state(self, p):
        return {"velocity": jnp.zeros_like(p._value)}

    def _update_one(self, p, g, state, lr, step, extras=None):
        mu = self._momentum
        v = mu * state["velocity"] + g
        if self._nesterov:
            upd = g + mu * v
        else:
            upd = v
        return p - lr.astype(p.dtype) * upd, {"velocity": v}


class Adagrad(Optimizer):
    _elementwise_update = True
    def __init__(self, learning_rate, epsilon=1e-6, parameters=None,
                 weight_decay=None, grad_clip=None, initial_accumulator_value=0.0,
                 name=None):
        super().__init__(learning_rate, parameters, weight_decay, grad_clip, name)
        self._epsilon = epsilon
        self._init_val = initial_accumulator_value

    def _state_names(self):
        return ["moment"]

    def _init_state(self, p):
        return {"moment": jnp.full_like(p._value, self._init_val)}

    def _update_one(self, p, g, state, lr, step, extras=None):
        m = state["moment"] + g * g
        return p - lr.astype(p.dtype) * g / (jnp.sqrt(m) + self._epsilon), {"moment": m}


class Adadelta(Optimizer):
    _elementwise_update = True
    def __init__(self, learning_rate=0.001, epsilon=1e-6, rho=0.95,
                 parameters=None, weight_decay=None, grad_clip=None, name=None):
        super().__init__(learning_rate, parameters, weight_decay, grad_clip, name)
        self._epsilon = epsilon
        self._rho = rho

    def _state_names(self):
        return ["avg_squared_grad", "avg_squared_update"]

    def _init_state(self, p):
        return {
            "avg_squared_grad": jnp.zeros_like(p._value),
            "avg_squared_update": jnp.zeros_like(p._value),
        }

    def _update_one(self, p, g, state, lr, step, extras=None):
        rho, eps = self._rho, self._epsilon
        ag = rho * state["avg_squared_grad"] + (1 - rho) * g * g
        upd = g * jnp.sqrt(state["avg_squared_update"] + eps) / jnp.sqrt(ag + eps)
        au = rho * state["avg_squared_update"] + (1 - rho) * upd * upd
        return p - lr.astype(p.dtype) * upd, {
            "avg_squared_grad": ag, "avg_squared_update": au,
        }


class RMSProp(Optimizer):
    _elementwise_update = True
    def __init__(self, learning_rate, rho=0.95, epsilon=1e-6, momentum=0.0,
                 centered=False, parameters=None, weight_decay=None,
                 grad_clip=None, name=None):
        super().__init__(learning_rate, parameters, weight_decay, grad_clip, name)
        self._rho = rho
        self._epsilon = epsilon
        self._momentum = momentum
        self._centered = centered

    def _state_names(self):
        return ["mean_square", "mean_grad", "momentum"]

    def _init_state(self, p):
        return {
            "mean_square": jnp.zeros_like(p._value),
            "mean_grad": jnp.zeros_like(p._value),
            "momentum": jnp.zeros_like(p._value),
        }

    def _update_one(self, p, g, state, lr, step, extras=None):
        rho, eps, mu = self._rho, self._epsilon, self._momentum
        ms = rho * state["mean_square"] + (1 - rho) * g * g
        mg = state["mean_grad"]
        if self._centered:
            mg = rho * mg + (1 - rho) * g
            denom = jnp.sqrt(ms - mg * mg + eps)
        else:
            denom = jnp.sqrt(ms + eps)
        mom = mu * state["momentum"] + lr.astype(p.dtype) * g / denom
        return p - mom, {"mean_square": ms, "mean_grad": mg, "momentum": mom}


class ASGD(Optimizer):
    """Averaged SGD (reference ``paddle.optimizer.ASGD``): keeps the last
    ``batch_num`` gradients' running sum ``d`` (a cyclic buffer ``ys``
    holds the individual entries) and steps by lr * d / n."""

    # ys carries an extra leading [batch_num] dim, so the flat-pack
    # reshape(-1) grouping cannot treat it like a param-shaped state
    _elementwise_update = False

    def __init__(self, learning_rate=0.001, batch_num=1, parameters=None,
                 weight_decay=None, grad_clip=None, multi_precision=False,
                 name=None):
        super().__init__(learning_rate, parameters, weight_decay,
                         grad_clip, name)
        self._batch_num = int(batch_num)

    def _state_names(self):
        return ["d", "ys"]

    def _init_state(self, p):
        return {
            "d": jnp.zeros_like(p._value),
            "ys": jnp.zeros((self._batch_num,) + tuple(p._value.shape),
                            p._value.dtype),
        }

    def _update_one(self, p, g, state, lr, step, extras=None):
        bn = self._batch_num
        idx = (step - 1) % bn
        y_old = jax.lax.dynamic_index_in_dim(state["ys"], idx,
                                             keepdims=False)
        d = state["d"] - y_old + g
        ys = jax.lax.dynamic_update_index_in_dim(state["ys"], g, idx, 0)
        n = jnp.minimum(step, bn).astype(jnp.float32)
        new_p = p - (lr / n).astype(p.dtype) * d
        return new_p, {"d": d, "ys": ys}


class Rprop(Optimizer):
    """Resilient backprop (reference ``paddle.optimizer.Rprop``):
    per-ELEMENT step sizes grown/shrunk by gradient sign agreement;
    magnitude of the gradient is ignored."""

    _elementwise_update = True

    def __init__(self, learning_rate=0.001, learning_rate_range=(1e-5, 50),
                 parameters=None, etas=(0.5, 1.2), grad_clip=None,
                 multi_precision=False, name=None):
        super().__init__(learning_rate, parameters, None, grad_clip, name)
        self._lr_min, self._lr_max = (float(learning_rate_range[0]),
                                      float(learning_rate_range[1]))
        self._eta_n, self._eta_p = float(etas[0]), float(etas[1])
        self._init_lr = float(learning_rate)

    def _state_names(self):
        return ["prev_grad", "learning_rate"]

    def _init_state(self, p):
        return {
            "prev_grad": jnp.zeros_like(p._value),
            "learning_rate": jnp.full_like(p._value, self._init_lr),
        }

    def _update_one(self, p, g, state, lr, step, extras=None):
        sign = g * state["prev_grad"]
        lr_e = jnp.where(
            sign > 0,
            jnp.minimum(state["learning_rate"] * self._eta_p, self._lr_max),
            jnp.where(sign < 0,
                      jnp.maximum(state["learning_rate"] * self._eta_n,
                                  self._lr_min),
                      state["learning_rate"]))
        g_eff = jnp.where(sign < 0, jnp.zeros_like(g), g)
        new_p = p - jnp.sign(g_eff).astype(p.dtype) * lr_e.astype(p.dtype)
        return new_p, {"prev_grad": g_eff, "learning_rate": lr_e}
