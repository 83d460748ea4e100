"""Optimizers. ≙ reference «python/paddle/optimizer/» (AdamW with
multi-precision master weights, grad clip, LR schedulers) [U].

Each optimizer keeps per-parameter state as jax arrays and performs its
update as one fused XLA computation per parameter (the jit path in
paddle_tpu.jit folds all updates into the single train-step program)."""
from __future__ import annotations

import math
from typing import Iterable, Optional

import numpy as np
import jax
import jax.numpy as jnp

from ..core import dtype as dtypes
from ..core.tensor import Parameter, Tensor
from ..nn import ClipGradByGlobalNorm, ClipGradByNorm, ClipGradByValue
from . import lr as lr  # noqa: F401
from .lr import LRScheduler


class Optimizer:
    """Base optimizer. ≙ paddle.optimizer.Optimizer."""

    def __init__(self, learning_rate=0.001, parameters=None, weight_decay=None,
                 grad_clip=None, multi_precision=False, name=None):
        if parameters is None:
            # the reference's static-graph style: parameters bound later
            # by minimize() from the recording Program's captured params
            from ..static import _recording_program
            if _recording_program() is None:
                raise ValueError(
                    "parameters must be provided (dygraph-style "
                    "construction), or construct the optimizer inside a "
                    "static.program_guard and call minimize(loss)")
            parameters = []
        self._parameter_list = list(parameters)
        self._learning_rate = learning_rate
        self._grad_clip = grad_clip
        self._multi_precision = multi_precision
        self._weight_decay = weight_decay
        self._accumulators: dict[str, dict[int, jax.Array]] = {}
        self._master_weights: dict[int, jax.Array] = {}
        self._step_count = 0
        # param groups support (list of dicts with 'params')
        if self._parameter_list and isinstance(self._parameter_list[0], dict):
            groups = self._parameter_list
            self._param_groups = groups
            self._parameter_list = [p for g in groups for p in g["params"]]
        else:
            self._param_groups = [{"params": self._parameter_list}]

    # -- lr ------------------------------------------------------------------
    def get_lr(self) -> float:
        if isinstance(self._learning_rate, LRScheduler):
            return float(self._learning_rate())
        return float(self._learning_rate)

    def set_lr(self, value):
        if isinstance(self._learning_rate, LRScheduler):
            raise RuntimeError(
                "cannot set_lr when learning rate is a scheduler")
        self._learning_rate = float(value)

    def set_lr_scheduler(self, scheduler):
        self._learning_rate = scheduler

    # -- state ---------------------------------------------------------------
    def _acc(self, name: str, p: Parameter, init=None, dtype=None):
        store = self._accumulators.setdefault(name, {})
        k = id(p)
        if k not in store:
            # a restored state_dict may predate lazy creation (resume
            # before the first step): consume the pending value if present
            pend = getattr(self, "_pending_state", None)
            if pend:
                i = next((j for j, q in enumerate(self._parameter_list)
                          if q is p), None)
                key = f"{name}_{p.name or i}"
                if key in pend:
                    v = pend.pop(key)
                    store[k] = v._value if isinstance(v, Tensor) \
                        else jnp.asarray(v)
                    return store[k]
            dt = dtype or (jnp.float32 if self._multi_precision
                           else p._value.dtype)
            store[k] = (self._zeros_like(p, dt) if init is None
                        else init)
        return store[k]

    @staticmethod
    def _zeros_like(p: Parameter, dt):
        """Zeros shaped like `p`, living where `p` lives. Plain
        `jnp.zeros` lands on the default device: for a parameter that
        `shard_tensor` spread over a mesh that would put the WHOLE of a
        ZeRO-sharded model's moments on device 0 until the first
        compiled step reshards them — 9.2 GiB for Llama-3.2-1B, which
        is what stopped the four-chip train step from loading."""
        v = p._value
        sharding = None if isinstance(v, jax.core.Tracer) \
            else getattr(v, "sharding", None)
        if sharding is not None and len(sharding.device_set) > 1:
            return jnp.zeros(v.shape, dt, device=sharding)
        return jnp.zeros(v.shape, dt)

    def _set_acc(self, name: str, p: Parameter, value):
        self._accumulators[name][id(p)] = value

    def _master(self, p: Parameter):
        """fp32 master weight for low-precision params (multi_precision)."""
        k = id(p)
        if k not in self._master_weights:
            self._master_weights[k] = p._value.astype(jnp.float32)
        return self._master_weights[k]

    def _use_master(self, p: Parameter) -> bool:
        return self._multi_precision and p._value.dtype in (
            jnp.float16, jnp.bfloat16)

    def _create_state(self, p: Parameter) -> None:
        """Create this optimizer's accumulators for `p` (zeros), exactly the
        ones `_update_param` touches. Subclasses override; base = stateless
        (SGD). Must stay in sync with `_update_param`'s `_acc` calls."""

    def ensure_state(self, p: Parameter | None = None) -> None:
        """Instantiate all optimizer state (accumulators + master weights)
        for `p` — or every trainable param — ahead of the first step(), so
        a compiled train step sees a stable state signature from step 0.
        State creation is optimizer-owned: a new optimizer subclass only
        has to override `_create_state` and compiled mode follows."""
        ps = ([p] if p is not None
              else [q for q in self._parameter_list if not q.stop_gradient])
        for q in ps:
            self._create_state(q)
            if self._use_master(q):
                self._master(q)

    # -- grad plumbing -------------------------------------------------------
    def _grads(self):
        out = []
        for p in self._parameter_list:
            if p.grad is not None and not p.stop_gradient:
                out.append((p, p.grad._value))
        return out

    def _clip_grads(self, pg):
        clip = self._grad_clip
        if clip is None:
            return pg
        if isinstance(clip, ClipGradByValue):
            return [(p, jnp.clip(g, clip.min, clip.max)) for p, g in pg]
        if isinstance(clip, ClipGradByNorm):
            out = []
            for p, g in pg:
                n = jnp.sqrt(jnp.sum(jnp.square(g.astype(jnp.float32))))
                scale = jnp.minimum(clip.clip_norm / jnp.maximum(
                    n, 1e-6), 1.0)
                out.append((p, (g * scale).astype(g.dtype)))
            return out
        if isinstance(clip, ClipGradByGlobalNorm):
            sq = [jnp.sum(jnp.square(g.astype(jnp.float32))) for _, g in pg]
            if not sq:
                return pg
            gn = jnp.sqrt(jnp.sum(jnp.stack(sq)))
            scale = clip.clip_norm / jnp.maximum(gn, clip.clip_norm)
            return [(p, (g * scale).astype(g.dtype)) for p, g in pg]
        return pg

    # -- api -----------------------------------------------------------------
    def step(self):
        pg = self._clip_grads(self._grads())
        self._step_count += 1
        for p, g in pg:
            self._update_param(p, g)

    def _update_param(self, p: Parameter, g):
        raise NotImplementedError

    def clear_grad(self, set_to_zero: bool = False):
        for p in self._parameter_list:
            p.grad = None

    clear_gradients = clear_grad

    def minimize(self, loss, startup_program=None, parameters=None,
                 no_grad_set=None):
        # static mode: record the train-step intent on the active Program
        # (Executor.run then does fwd+bwd+update in one compiled program)
        from ..static import _recording_program
        prog = _recording_program()
        if prog is not None and prog._slot(loss) is not None:
            if not self._parameter_list:
                self._parameter_list = prog.all_parameters()
                self._param_groups = [{"params": self._parameter_list}]
            prog._minimize = (self, prog._slot(loss))
            return None, None
        loss.backward()
        self.step()
        return None, None

    def state_dict(self) -> dict:
        sd = {}
        for name, store in self._accumulators.items():
            for i, p in enumerate(self._parameter_list):
                if id(p) in store:
                    key = f"{name}_{p.name or i}"
                    sd[key] = Tensor(store[id(p)])
        for i, p in enumerate(self._parameter_list):
            if id(p) in self._master_weights:
                sd[f"master_{p.name or i}"] = Tensor(
                    self._master_weights[id(p)])
        if isinstance(self._learning_rate, LRScheduler):
            sd["LR_Scheduler"] = self._learning_rate.state_dict()
        sd["@step"] = self._step_count
        return sd

    def set_state_dict(self, state_dict: dict):
        self._step_count = int(state_dict.get("@step", 0))
        if "LR_Scheduler" in state_dict and isinstance(
                self._learning_rate, LRScheduler):
            self._learning_rate.set_state_dict(state_dict["LR_Scheduler"])
        for name, store in list(self._accumulators.items()):
            for i, p in enumerate(self._parameter_list):
                key = f"{name}_{p.name or i}"
                if key in state_dict:
                    v = state_dict[key]
                    store[id(p)] = v._value if isinstance(v, Tensor) \
                        else jnp.asarray(v)
        for i, p in enumerate(self._parameter_list):
            key = f"master_{p.name or i}"
            if key in state_dict:
                v = state_dict[key]
                self._master_weights[id(p)] = v._value if isinstance(
                    v, Tensor) else jnp.asarray(v)
        # stash entries for accumulators that don't exist yet (lazy
        # creation) — consumed by _acc() on first touch
        consumed = {f"{name}_{p.name or i}"
                    for name in self._accumulators
                    for i, p in enumerate(self._parameter_list)}
        self._pending_state = {k: v for k, v in state_dict.items()
                               if k not in consumed
                               and k not in ("@step", "LR_Scheduler")
                               and not k.startswith("master_")}

    def _wd(self, p: Parameter) -> float:
        wd = self._weight_decay
        if wd is None:
            return 0.0
        if callable(getattr(wd, "__float__", None)) or isinstance(
                wd, (int, float)):
            return float(wd)
        return 0.0


class SGD(Optimizer):
    def __init__(self, learning_rate=0.001, parameters=None,
                 weight_decay=None, grad_clip=None, multi_precision=False,
                 name=None):
        super().__init__(learning_rate, parameters, weight_decay, grad_clip,
                         multi_precision, name)

    def _update_param(self, p, g):
        lr = self.get_lr()
        wd = self._wd(p)
        if self._use_master(p):
            m = self._master(p)
            g32 = g.astype(jnp.float32)
            if wd:
                g32 = g32 + wd * m
            m = m - lr * g32
            self._master_weights[id(p)] = m
            p._value = m.astype(p._value.dtype)
        else:
            if wd:
                g = g + wd * p._value
            p._value = (p._value - lr * g).astype(p._value.dtype)


class Momentum(Optimizer):
    def __init__(self, learning_rate=0.001, momentum=0.9, parameters=None,
                 use_nesterov=False, weight_decay=None, grad_clip=None,
                 multi_precision=False, name=None):
        super().__init__(learning_rate, parameters, weight_decay, grad_clip,
                         multi_precision, name)
        self._momentum = momentum
        self._nesterov = use_nesterov

    def _create_state(self, p):
        self._acc("velocity", p,
                  dtype=jnp.float32 if self._use_master(p)
                  else p._value.dtype)

    def _update_param(self, p, g):
        lr = self.get_lr()
        wd = self._wd(p)
        mw = self._master(p) if self._use_master(p) else p._value
        g = g.astype(mw.dtype)
        if wd:
            g = g + wd * mw
        vel = self._acc("velocity", p, dtype=mw.dtype)
        vel = self._momentum * vel + g
        self._set_acc("velocity", p, vel)
        upd = g + self._momentum * vel if self._nesterov else vel
        new = mw - lr * upd
        if self._use_master(p):
            self._master_weights[id(p)] = new
            p._value = new.astype(p._value.dtype)
        else:
            p._value = new


class Adam(Optimizer):
    def __init__(self, learning_rate=0.001, beta1=0.9, beta2=0.999,
                 epsilon=1e-08, parameters=None, weight_decay=None,
                 grad_clip=None, lazy_mode=False, multi_precision=False,
                 amsgrad=False, name=None):
        super().__init__(learning_rate, parameters, weight_decay, grad_clip,
                         multi_precision, name)
        self._beta1 = beta1
        self._beta2 = beta2
        self._epsilon = epsilon
        self._amsgrad = amsgrad

    def _create_state(self, p):
        self._acc("moment1", p, dtype=jnp.float32)
        self._acc("moment2", p, dtype=jnp.float32)
        if self._amsgrad:
            self._acc("moment2_max", p, dtype=jnp.float32)

    def _adam_core(self, p, g, decoupled_wd=0.0, coupled_wd=0.0):
        lr = self.get_lr()
        mw = self._master(p) if self._use_master(p) else p._value
        g = g.astype(jnp.float32)
        mwf = mw.astype(jnp.float32)
        if coupled_wd:
            g = g + coupled_wd * mwf
        m = self._acc("moment1", p, dtype=jnp.float32)
        v = self._acc("moment2", p, dtype=jnp.float32)
        b1, b2 = self._beta1, self._beta2
        t = self._step_count
        m = b1 * m + (1 - b1) * g
        v = b2 * v + (1 - b2) * jnp.square(g)
        self._set_acc("moment1", p, m)
        self._set_acc("moment2", p, v)
        mhat = m / (1 - b1 ** t)
        if self._amsgrad:
            vmax = self._acc("moment2_max", p, dtype=jnp.float32)
            vmax = jnp.maximum(vmax, v)
            self._set_acc("moment2_max", p, vmax)
            vhat = vmax / (1 - b2 ** t)
        else:
            vhat = v / (1 - b2 ** t)
        new = mwf - lr * (mhat / (jnp.sqrt(vhat) + self._epsilon)
                          + decoupled_wd * mwf)
        if self._use_master(p):
            self._master_weights[id(p)] = new
            p._value = new.astype(p._value.dtype)
        else:
            p._value = new.astype(p._value.dtype)

    def _update_param(self, p, g):
        self._adam_core(p, g, coupled_wd=self._wd(p))


class AdamW(Adam):
    """Decoupled weight decay. ≙ paddle.optimizer.AdamW with
    apply_decay_param_fun and multi-precision master weights [U]."""

    def __init__(self, learning_rate=0.001, beta1=0.9, beta2=0.999,
                 epsilon=1e-08, parameters=None, weight_decay=0.01,
                 lr_ratio=None, apply_decay_param_fun=None, grad_clip=None,
                 lazy_mode=False, multi_precision=False, amsgrad=False,
                 name=None):
        super().__init__(learning_rate, beta1, beta2, epsilon, parameters,
                         None, grad_clip, lazy_mode, multi_precision, amsgrad,
                         name)
        self._weight_decay = weight_decay
        self._apply_decay_fn = apply_decay_param_fun
        self._lr_ratio = lr_ratio

    def _update_param(self, p, g):
        wd = float(self._weight_decay) if self._weight_decay else 0.0
        if self._apply_decay_fn is not None and not self._apply_decay_fn(
                p.name):
            wd = 0.0
        self._adam_core(p, g, decoupled_wd=wd)


class Adamax(Optimizer):
    def __init__(self, learning_rate=0.001, beta1=0.9, beta2=0.999,
                 epsilon=1e-08, parameters=None, weight_decay=None,
                 grad_clip=None, name=None):
        super().__init__(learning_rate, parameters, weight_decay, grad_clip,
                         False, name)
        self._beta1, self._beta2, self._epsilon = beta1, beta2, epsilon

    def _create_state(self, p):
        self._acc("moment", p, dtype=jnp.float32)
        self._acc("inf_norm", p, dtype=jnp.float32)

    def _update_param(self, p, g):
        lr = self.get_lr()
        g = g.astype(jnp.float32)
        if self._wd(p):
            g = g + self._wd(p) * p._value.astype(jnp.float32)
        m = self._acc("moment", p, dtype=jnp.float32)
        u = self._acc("inf_norm", p, dtype=jnp.float32)
        m = self._beta1 * m + (1 - self._beta1) * g
        u = jnp.maximum(self._beta2 * u, jnp.abs(g))
        self._set_acc("moment", p, m)
        self._set_acc("inf_norm", p, u)
        t = self._step_count
        p._value = (p._value.astype(jnp.float32)
                    - lr / (1 - self._beta1 ** t) * m / (u + self._epsilon)
                    ).astype(p._value.dtype)


class Adagrad(Optimizer):
    def __init__(self, learning_rate, epsilon=1e-06, parameters=None,
                 weight_decay=None, grad_clip=None,
                 initial_accumulator_value=0.0, name=None):
        super().__init__(learning_rate, parameters, weight_decay, grad_clip,
                         False, name)
        self._epsilon = epsilon
        self._init_acc = initial_accumulator_value

    def _create_state(self, p):
        self._acc("moment", p,
                  init=jnp.full(p._value.shape, self._init_acc, jnp.float32))

    def _update_param(self, p, g):
        lr = self.get_lr()
        g = g.astype(jnp.float32)
        if self._wd(p):
            g = g + self._wd(p) * p._value.astype(jnp.float32)
        acc = self._acc("moment", p,
                        init=jnp.full(p._value.shape, self._init_acc,
                                      jnp.float32))
        acc = acc + jnp.square(g)
        self._set_acc("moment", p, acc)
        p._value = (p._value.astype(jnp.float32)
                    - lr * g / (jnp.sqrt(acc) + self._epsilon)).astype(
            p._value.dtype)


class Adadelta(Optimizer):
    def __init__(self, learning_rate=0.001, epsilon=1e-06, rho=0.95,
                 parameters=None, weight_decay=None, grad_clip=None,
                 name=None):
        super().__init__(learning_rate, parameters, weight_decay, grad_clip,
                         False, name)
        self._epsilon, self._rho = epsilon, rho

    def _create_state(self, p):
        self._acc("avg_squared_grad", p, dtype=jnp.float32)
        self._acc("avg_squared_update", p, dtype=jnp.float32)

    def _update_param(self, p, g):
        lr = self.get_lr()
        g = g.astype(jnp.float32)
        if self._wd(p):
            g = g + self._wd(p) * p._value.astype(jnp.float32)
        avg_sq = self._acc("avg_squared_grad", p, dtype=jnp.float32)
        avg_up = self._acc("avg_squared_update", p, dtype=jnp.float32)
        avg_sq = self._rho * avg_sq + (1 - self._rho) * jnp.square(g)
        upd = (jnp.sqrt(avg_up + self._epsilon)
               / jnp.sqrt(avg_sq + self._epsilon)) * g
        avg_up = self._rho * avg_up + (1 - self._rho) * jnp.square(upd)
        self._set_acc("avg_squared_grad", p, avg_sq)
        self._set_acc("avg_squared_update", p, avg_up)
        p._value = (p._value.astype(jnp.float32) - lr * upd).astype(
            p._value.dtype)


class RMSProp(Optimizer):
    def __init__(self, learning_rate, rho=0.95, epsilon=1e-06, momentum=0.0,
                 centered=False, parameters=None, weight_decay=None,
                 grad_clip=None, name=None):
        super().__init__(learning_rate, parameters, weight_decay, grad_clip,
                         False, name)
        self._rho, self._epsilon = rho, epsilon
        self._momentum, self._centered = momentum, centered

    def _create_state(self, p):
        self._acc("mean_square", p, dtype=jnp.float32)
        self._acc("momentum", p, dtype=jnp.float32)
        if self._centered:
            self._acc("mean_grad", p, dtype=jnp.float32)

    def _update_param(self, p, g):
        lr = self.get_lr()
        g = g.astype(jnp.float32)
        if self._wd(p):
            g = g + self._wd(p) * p._value.astype(jnp.float32)
        ms = self._acc("mean_square", p, dtype=jnp.float32)
        ms = self._rho * ms + (1 - self._rho) * jnp.square(g)
        self._set_acc("mean_square", p, ms)
        if self._centered:
            mg = self._acc("mean_grad", p, dtype=jnp.float32)
            mg = self._rho * mg + (1 - self._rho) * g
            self._set_acc("mean_grad", p, mg)
            denom = jnp.sqrt(ms - jnp.square(mg) + self._epsilon)
        else:
            denom = jnp.sqrt(ms + self._epsilon)
        mom = self._acc("momentum", p, dtype=jnp.float32)
        mom = self._momentum * mom + lr * g / denom
        self._set_acc("momentum", p, mom)
        p._value = (p._value.astype(jnp.float32) - mom).astype(p._value.dtype)


class Lamb(Optimizer):
    """Layer-wise adaptive moments for large-batch training.
    ≙ paddle.optimizer.Lamb [U]."""

    def __init__(self, learning_rate=0.001, lamb_weight_decay=0.01, beta1=0.9,
                 beta2=0.999, epsilon=1e-06, parameters=None, grad_clip=None,
                 exclude_from_weight_decay_fn=None, multi_precision=False,
                 name=None):
        super().__init__(learning_rate, parameters, lamb_weight_decay,
                         grad_clip, multi_precision, name)
        self._beta1, self._beta2, self._epsilon = beta1, beta2, epsilon
        self._wd_value = lamb_weight_decay
        self._exclude_fn = exclude_from_weight_decay_fn

    def _create_state(self, p):
        self._acc("moment1", p, dtype=jnp.float32)
        self._acc("moment2", p, dtype=jnp.float32)

    def _update_param(self, p, g):
        lr = self.get_lr()
        mw = self._master(p) if self._use_master(p) else p._value
        mwf = mw.astype(jnp.float32)
        g = g.astype(jnp.float32)
        m = self._acc("moment1", p, dtype=jnp.float32)
        v = self._acc("moment2", p, dtype=jnp.float32)
        b1, b2 = self._beta1, self._beta2
        t = self._step_count
        m = b1 * m + (1 - b1) * g
        v = b2 * v + (1 - b2) * jnp.square(g)
        self._set_acc("moment1", p, m)
        self._set_acc("moment2", p, v)
        mhat = m / (1 - b1 ** t)
        vhat = v / (1 - b2 ** t)
        wd = self._wd_value
        if self._exclude_fn is not None and self._exclude_fn(p):
            wd = 0.0
        r = mhat / (jnp.sqrt(vhat) + self._epsilon) + wd * mwf
        w_norm = jnp.sqrt(jnp.sum(jnp.square(mwf)))
        r_norm = jnp.sqrt(jnp.sum(jnp.square(r)))
        trust = jnp.where((w_norm > 0) & (r_norm > 0), w_norm / r_norm, 1.0)
        new = mwf - lr * trust * r
        if self._use_master(p):
            self._master_weights[id(p)] = new
            p._value = new.astype(p._value.dtype)
        else:
            p._value = new.astype(p._value.dtype)


class NAdam(Optimizer):
    """≙ paddle.optimizer.NAdam (Nesterov Adam) [U]."""

    def __init__(self, learning_rate=0.001, beta1=0.9, beta2=0.999,
                 epsilon=1e-08, momentum_decay=0.004, parameters=None,
                 weight_decay=None, grad_clip=None, multi_precision=False,
                 name=None):
        super().__init__(learning_rate, parameters, weight_decay, grad_clip,
                         multi_precision, name)
        self._beta1, self._beta2 = beta1, beta2
        self._epsilon = epsilon
        self._md = momentum_decay

    def _create_state(self, p):
        self._acc("moment1", p, dtype=jnp.float32)
        self._acc("moment2", p, dtype=jnp.float32)
        self._acc("mu_product", p, init=jnp.zeros((), jnp.float32),
                  dtype=jnp.float32)

    def _update_param(self, p, g):
        lr = self.get_lr()
        mw = self._master(p) if self._use_master(p) else p._value
        mwf = mw.astype(jnp.float32)
        g = g.astype(jnp.float32)
        cwd = self._wd(p)
        if cwd:
            g = g + cwd * mwf
        b1, b2 = self._beta1, self._beta2
        t = self._step_count
        mu_t = b1 * (1.0 - 0.5 * 0.96 ** (t * self._md))
        mu_t1 = b1 * (1.0 - 0.5 * 0.96 ** ((t + 1) * self._md))
        mu_prod = self._acc("mu_product", p,
                            init=jnp.zeros((), jnp.float32),
                            dtype=jnp.float32)
        # accumulator starts at 0; treat 0 as "empty" product = 1
        mu_prod = jnp.where(mu_prod == 0, 1.0, mu_prod) * mu_t
        self._set_acc("mu_product", p, mu_prod)
        m = self._acc("moment1", p, dtype=jnp.float32)
        v = self._acc("moment2", p, dtype=jnp.float32)
        m = b1 * m + (1 - b1) * g
        v = b2 * v + (1 - b2) * jnp.square(g)
        self._set_acc("moment1", p, m)
        self._set_acc("moment2", p, v)
        # the mu coefficients live INSIDE these terms (torch NAdam form):
        # update = ghat + mhat, NOT a second mu-weighted mix of them
        ghat = g * (1 - mu_t) / (1 - mu_prod)
        mhat = m * mu_t1 / (1 - mu_prod * mu_t1)
        vhat = v / (1 - b2 ** t)
        new = mwf - lr * (ghat + mhat) \
            / (jnp.sqrt(vhat) + self._epsilon)
        if self._use_master(p):
            self._master_weights[id(p)] = new
        p._value = new.astype(p._value.dtype)


class RAdam(Optimizer):
    """≙ paddle.optimizer.RAdam (rectified Adam) [U]."""

    def __init__(self, learning_rate=0.001, beta1=0.9, beta2=0.999,
                 epsilon=1e-08, parameters=None, weight_decay=None,
                 grad_clip=None, multi_precision=False, name=None):
        super().__init__(learning_rate, parameters, weight_decay, grad_clip,
                         multi_precision, name)
        self._beta1, self._beta2 = beta1, beta2
        self._epsilon = epsilon

    def _create_state(self, p):
        self._acc("moment1", p, dtype=jnp.float32)
        self._acc("moment2", p, dtype=jnp.float32)

    def _update_param(self, p, g):
        lr = self.get_lr()
        mw = self._master(p) if self._use_master(p) else p._value
        mwf = mw.astype(jnp.float32)
        g = g.astype(jnp.float32)
        cwd = self._wd(p)
        if cwd:
            g = g + cwd * mwf
        b1, b2 = self._beta1, self._beta2
        t = self._step_count
        m = self._acc("moment1", p, dtype=jnp.float32)
        v = self._acc("moment2", p, dtype=jnp.float32)
        m = b1 * m + (1 - b1) * g
        v = b2 * v + (1 - b2) * jnp.square(g)
        self._set_acc("moment1", p, m)
        self._set_acc("moment2", p, v)
        mhat = m / (1 - b1 ** t)
        rho_inf = 2.0 / (1 - b2) - 1.0
        rho_t = rho_inf - 2.0 * t * (b2 ** t) / (1 - b2 ** t)
        if rho_t > 5.0:
            vhat = jnp.sqrt(v / (1 - b2 ** t))
            r = math.sqrt(((rho_t - 4) * (rho_t - 2) * rho_inf)
                          / ((rho_inf - 4) * (rho_inf - 2) * rho_t))
            new = mwf - lr * r * mhat / (vhat + self._epsilon)
        else:
            new = mwf - lr * mhat
        if self._use_master(p):
            self._master_weights[id(p)] = new
        p._value = new.astype(p._value.dtype)


class Rprop(Optimizer):
    """≙ paddle.optimizer.Rprop (resilient backprop; full-batch method) [U]."""

    def __init__(self, learning_rate=0.001, learning_rate_range=(1e-5, 50.0),
                 parameters=None, etas=(0.5, 1.2), grad_clip=None,
                 multi_precision=False, name=None):
        super().__init__(learning_rate, parameters, None, grad_clip,
                         multi_precision, name)
        self._lr_min, self._lr_max = learning_rate_range
        self._eta_neg, self._eta_pos = etas
        self._init_lr = learning_rate

    def _create_state(self, p):
        self._acc("prev_grad", p, dtype=jnp.float32)
        store = self._accumulators.setdefault("step_size", {})
        if id(p) not in store:
            store[id(p)] = jnp.full(tuple(p.shape), float(self._init_lr),
                                    jnp.float32)

    def _update_param(self, p, g):
        self._create_state(p)
        mw = self._master(p) if self._use_master(p) else p._value
        mwf = mw.astype(jnp.float32)
        g = g.astype(jnp.float32)
        prev = self._acc("prev_grad", p, dtype=jnp.float32)
        step = self._accumulators["step_size"][id(p)]
        sign = jnp.sign(g * prev)
        step = jnp.clip(jnp.where(sign > 0, step * self._eta_pos,
                                  jnp.where(sign < 0,
                                            step * self._eta_neg, step)),
                        self._lr_min, self._lr_max)
        g_eff = jnp.where(sign < 0, 0.0, g)
        self._set_acc("prev_grad", p, g_eff)
        self._accumulators["step_size"][id(p)] = step
        new = mwf - jnp.sign(g_eff) * step
        if self._use_master(p):
            self._master_weights[id(p)] = new
        p._value = new.astype(p._value.dtype)


class ASGD(Optimizer):
    """≙ paddle.optimizer.ASGD (averaged SGD) [U]. Keeps a running
    average of the iterates; `d`/`y` follow the paddle formulation with a
    fixed-size history of n gradients collapsed to the streaming form."""

    def __init__(self, learning_rate=0.001, batch_num=1, parameters=None,
                 weight_decay=None, grad_clip=None, multi_precision=False,
                 name=None):
        super().__init__(learning_rate, parameters, weight_decay, grad_clip,
                         multi_precision, name)
        self._batch_num = batch_num

    def _create_state(self, p):
        self._acc("d", p, dtype=jnp.float32)
        self._acc("ys", p, dtype=jnp.float32)

    def _update_param(self, p, g):
        lr = self.get_lr()
        mw = self._master(p) if self._use_master(p) else p._value
        mwf = mw.astype(jnp.float32)
        g = g.astype(jnp.float32)
        cwd = self._wd(p)
        if cwd:
            g = g + cwd * mwf
        d = self._acc("d", p, dtype=jnp.float32)
        ys = self._acc("ys", p, dtype=jnp.float32)
        # streaming average over the last batch_num grads:
        # d <- d - oldest + newest; with n=batch_num the oldest estimate
        # is ys/n (mean), giving an exponential-window approximation
        oldest = ys / self._batch_num
        d = d - oldest + g
        ys = ys - oldest + g
        self._set_acc("d", p, d)
        self._set_acc("ys", p, ys)
        new = mwf - lr / self._batch_num * d
        if self._use_master(p):
            self._master_weights[id(p)] = new
        p._value = new.astype(p._value.dtype)


class LBFGS(Optimizer):
    """≙ paddle.optimizer.LBFGS — limited-memory BFGS with strong-Wolfe
    line search. Matches the reference's closure-based `step(closure)` API
    («python/paddle/optimizer/lbfgs.py» [U]); eager-only by nature (the
    line search re-evaluates the closure a data-dependent number of
    times — exactly the reference's behavior, and not a jit target)."""

    def __init__(self, learning_rate=1.0, max_iter=20, max_eval=None,
                 tolerance_grad=1e-07, tolerance_change=1e-09,
                 history_size=100, line_search_fn=None, parameters=None,
                 weight_decay=None, grad_clip=None, multi_precision=False,
                 name=None):
        super().__init__(learning_rate, parameters, weight_decay, grad_clip,
                         multi_precision, name)
        self._max_iter = max_iter
        self._max_eval = max_eval or max_iter * 5 // 4
        self._tol_grad = tolerance_grad
        self._tol_change = tolerance_change
        self._history = history_size
        self._line_search = line_search_fn  # None | 'strong_wolfe'
        self._s_hist: list = []
        self._y_hist: list = []
        self._prev_flat_grad = None
        self._n_inner = 0  # lifetime inner-iteration count (ref parity)

    def _flat_params(self):
        return jnp.concatenate(
            [p._value.astype(jnp.float32).reshape(-1)
             for p in self._parameter_list])

    def _set_flat_params(self, flat):
        off = 0
        for p in self._parameter_list:
            n = int(np.prod(tuple(p.shape))) if p.shape else 1
            p._value = flat[off:off + n].reshape(tuple(p.shape)).astype(
                p._value.dtype)
            off += n

    def _flat_grad(self):
        gs = []
        for p in self._parameter_list:
            if p.grad is None:
                gs.append(jnp.zeros(int(np.prod(tuple(p.shape))),
                                    jnp.float32))
            else:
                gs.append(p.grad._value.astype(jnp.float32).reshape(-1))
        return jnp.concatenate(gs)

    def _eval(self, closure):
        for p in self._parameter_list:
            p.grad = None
        loss = closure()
        return float(loss), self._flat_grad()

    def step(self, closure=None):
        if closure is None:
            raise ValueError("LBFGS.step needs a closure returning the "
                             "loss (it re-evaluates the model)")
        loss, g = self._eval(closure)
        evals = 1
        for _ in range(self._max_iter):
            if float(jnp.max(jnp.abs(g))) <= self._tol_grad:
                break
            # two-loop recursion
            q = -g
            alphas = []
            for s, y in reversed(list(zip(self._s_hist, self._y_hist))):
                rho = 1.0 / float(jnp.dot(y, s))
                a = rho * float(jnp.dot(s, q))
                alphas.append((a, rho, s, y))
                q = q - a * y
            if self._y_hist:
                y_last = self._y_hist[-1]
                s_last = self._s_hist[-1]
                gamma = float(jnp.dot(s_last, y_last)
                              / jnp.maximum(jnp.dot(y_last, y_last), 1e-10))
                q = q * gamma
            for a, rho, s, y in reversed(alphas):
                b = rho * float(jnp.dot(y, q))
                q = q + (a - b) * s
            d = q
            x0 = self._flat_params()
            g0 = g
            f0 = loss
            gtd = float(jnp.dot(g, d))
            if gtd > -1e-15:
                break
            t = float(self.get_lr())
            self._n_inner += 1
            if self._line_search is None:
                # reference default: one fixed t=lr step per inner
                # iteration, no search (search only for 'strong_wolfe');
                # the very first step ever is damped by min(1, 1/sum|g|)
                if self._n_inner == 1:
                    t = min(1.0, 1.0 / float(jnp.sum(jnp.abs(g)))) * t
                self._set_flat_params(x0 + t * d)
                loss, g = self._eval(closure)
                evals += 1
            else:
                # backtracking (armijo) line search + curvature check
                ok = False
                for _ls in range(25):
                    self._set_flat_params(x0 + t * d)
                    loss, g = self._eval(closure)
                    evals += 1
                    if loss <= f0 + 1e-4 * t * gtd:
                        if abs(float(jnp.dot(g, d))) <= 0.9 * abs(gtd):
                            ok = True
                            break
                    t *= 0.5
                    if evals >= self._max_eval:
                        break
                if not ok:
                    self._set_flat_params(x0)
                    loss, g = self._eval(closure)
                    break
            s = self._flat_params() - x0
            y = g - g0
            if float(jnp.dot(s, y)) > 1e-10:
                self._s_hist.append(s)
                self._y_hist.append(y)
                if len(self._s_hist) > self._history:
                    self._s_hist.pop(0)
                    self._y_hist.pop(0)
            if abs(f0 - loss) < self._tol_change:
                break
            if evals >= self._max_eval:
                break
        self._step_count += 1
        import paddle_tpu as paddle
        return paddle.to_tensor(np.float32(loss))
