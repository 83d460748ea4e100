"""paddle_tpu.jit — the compile path.

≙ reference `@paddle.jit.to_static` + SOT/dy2static + PIR + CINN +
InterpreterCore (SURVEY.md §3.4) collapsed into ONE mechanism: because every
eager op in this framework is a traceable JAX computation (including the
autograd tape and the optimizer update), re-executing the user's eager train
step under `jax.jit` tracing yields a single fused XLA program per step —
no bytecode interpretation, no separate IR. Data-dependent Python control
flow is a graph break: under `to_static` (full_graph=False, the reference
default) it logs and falls back to eager (SOT-lite); under full_graph=True
or `TrainStep` it raises the pointed GraphBreakError.

Key pieces:
* `to_static(fn_or_layer)`   — jit a function/Layer forward (inference path).
* `TrainStep(model, opt)`    — whole-train-step compilation with buffer
  donation: params/opt-state are threaded as traced inputs and donated, so
  updates are in-place in HBM (≙ the reference's inplace AdamW kernels).
* `jit.save/load`            — serialize compiled functions via jax.export
  (StableHLO), ≙ paddle.jit.save inference programs [U].
"""
from __future__ import annotations

import functools
import warnings
from typing import Any, Callable, Optional, Sequence

import numpy as np
import jax
import jax.numpy as jnp

from ..core.tensor import GraphBreakError, Parameter, Tensor
from ..tensor.random import default_generator


def _tensors_to_values(tree):
    return jax.tree_util.tree_map(
        lambda x: x._value if isinstance(x, Tensor) else x, tree,
        is_leaf=lambda x: isinstance(x, Tensor))


def _spec_of(tree):
    return jax.tree_util.tree_map(
        lambda x: isinstance(x, Tensor), tree,
        is_leaf=lambda x: isinstance(x, Tensor))


#: record of every graph break that fell back to eager this process:
#: list of (qualname, reason) — ≙ the reference SOT's break-graph log
#: (`sot.opcode_translator` info logs). Inspect with jit.sot_graph_breaks().
_graph_break_log: list = []


def sot_graph_breaks() -> list:
    """(qualname, reason) for every to_static graph break that fell back
    to eager execution in this process (SOT-lite diagnostics)."""
    return list(_graph_break_log)


class StaticFunction:
    """jit wrapper for a pure function or a Layer's forward.

    SOT-lite contract (≙ reference `python/paddle/jit/sot/` [U]): with
    full_graph=False (the default, matching the reference), data-dependent
    Python control flow on a traced Tensor does not error — the graph break
    is logged and the function falls back to EAGER execution (numerics
    identical, per-op dispatch instead of one fused XLA program). The
    fallback decision is cached per function: the reference re-traces
    subgraphs between breaks; here the unit of capture is the whole
    function, which is the bounded version of the same contract.
    full_graph=True keeps the pointed GraphBreakError."""

    def __init__(self, function, layer=None, input_spec=None,
                 full_graph=False, **kwargs):
        self._fn = function
        self._layer = layer
        self._input_spec = input_spec
        self._jitted = None
        self._full_graph = full_graph
        self.graph_break_reason = None   # set on first fallback
        functools.update_wrapper(self, function)

    def _build(self):
        layer = self._layer
        fn = self._fn

        if layer is not None:
            params = list(layer.parameters())
            buffers = list(layer.buffers())

            def pure(param_vals, buf_vals, arg_vals, kw_vals):
                old_p = [p._value for p in params]
                old_b = [b._value for b in buffers]
                try:
                    for p, v in zip(params, param_vals):
                        p._value = v
                    for b, v in zip(buffers, buf_vals):
                        b._value = v
                    args = jax.tree_util.tree_map(Tensor, arg_vals)
                    kwargs = jax.tree_util.tree_map(Tensor, kw_vals)
                    out = fn(*args, **kwargs)
                    return _tensors_to_values(out)
                finally:
                    for p, v in zip(params, old_p):
                        p._value = v
                    for b, v in zip(buffers, old_b):
                        b._value = v
            self._jitted = jax.jit(pure)
        else:
            def pure(arg_vals, kw_vals):
                args = jax.tree_util.tree_map(Tensor, arg_vals)
                kwargs = jax.tree_util.tree_map(Tensor, kw_vals)
                out = fn(*args, **kwargs)
                return _tensors_to_values(out)
            self._jitted = jax.jit(pure)

    def _call_eager(self, args, kwargs):
        # same input normalization as the compiled path (every array leaf
        # becomes a Tensor) so numerics and types match trace-mode exactly
        args = jax.tree_util.tree_map(Tensor, _tensors_to_values(list(args)))
        kwargs = jax.tree_util.tree_map(Tensor,
                                        _tensors_to_values(dict(kwargs)))
        return self._fn(*args, **kwargs)

    def __call__(self, *args, **kwargs):
        if self.graph_break_reason is not None:
            return self._call_eager(args, kwargs)
        if self._jitted is None:
            self._build()
        arg_vals = _tensors_to_values(list(args))
        kw_vals = _tensors_to_values(dict(kwargs))
        try:
            if self._layer is not None:
                pv = [p._value for p in self._layer.parameters()]
                bv = [b._value for b in self._layer.buffers()]
                out_vals = self._jitted(pv, bv, arg_vals, kw_vals)
            else:
                out_vals = self._jitted(arg_vals, kw_vals)
        except (GraphBreakError, jax.errors.ConcretizationTypeError,
                jax.errors.TracerArrayConversionError,
                jax.errors.TracerIntegerConversionError) as e:
            # GraphBreakError: a framework Tensor coercion (`if t:`,
            # float(t), .item(), .numpy()) under trace; the jax errors:
            # the same coercions on a raw jax array in user code (the
            # Array/Integer variants do NOT subclass
            # ConcretizationTypeError in the installed jax).
            if self._full_graph:
                raise
            reason = str(e).splitlines()[0]
            self.graph_break_reason = reason
            name = getattr(self._fn, "__qualname__", repr(self._fn))
            _graph_break_log.append((name, reason))
            warnings.warn(
                f"to_static: graph break in {name!r} — falling back to "
                f"eager execution for this function (numerics unchanged, "
                f"no XLA fusion). Reason: {reason}  Pass full_graph=True "
                "to error instead.", stacklevel=2)
            return self._call_eager(args, kwargs)
        return jax.tree_util.tree_map(Tensor, out_vals)

    @property
    def code(self):
        import inspect
        try:
            return inspect.getsource(self._fn)
        except OSError:
            return "<source unavailable>"


def to_static(function=None, input_spec=None, build_strategy=None,
              backend=None, full_graph=False, **kwargs):
    """≙ @paddle.jit.to_static. Works on functions of Tensors and on
    nn.Layer instances (forward gets compiled with params as traced inputs).

    full_graph=False (default, reference parity): graph breaks fall back
    to eager with a warning (SOT-lite). full_graph=True: graph breaks
    raise GraphBreakError with a pointed diagnostic."""
    from ..nn.layer.layers import Layer

    def decorate(obj):
        if isinstance(obj, Layer):
            sf = StaticFunction(obj.forward, layer=obj,
                                input_spec=input_spec,
                                full_graph=full_graph)
            obj.forward = sf
            return obj
        return StaticFunction(obj, input_spec=input_spec,
                              full_graph=full_graph)

    if function is not None:
        return decorate(function)
    return decorate


def not_to_static(fn):
    fn._not_to_static = True
    return fn


class ignore_module:
    def __init__(self, modules):
        pass

    def __enter__(self):
        return self

    def __exit__(self, *a):
        return False


class TrainStep:
    """Whole-train-step XLA compilation with state donation.

    Usage::

        step = paddle_tpu.jit.TrainStep(model, opt,
                                        loss_fn=lambda m, x, y: F.cross_entropy(m(x), y))
        loss = step(x, y)      # one compiled XLA program; params updated

    The eager tape + optimizer run under jax tracing; params, optimizer
    accumulators and master weights are inputs AND outputs of the compiled
    program, donated to keep updates in-place in HBM. The RNG key is threaded
    so dropout differs per step (≙ the reference's RNG state tracker).

    `accumulate_steps=k` (≙ fleet gradient-merge meta-optimizer /
    `pipeline_configs['accumulate_steps']`, SURVEY.md §2.4) splits the batch
    into k micro-batches inside the ONE compiled program: each micro-loss is
    scaled by 1/k, backward accumulates into the grads, the optimizer steps
    once. Loss returned is the mean micro-loss. Leading dim of every input
    must be divisible by k.
    """

    def __init__(self, model, optimizer=None, loss_fn=None, scaler=None,
                 donate=True, accumulate_steps=1):
        self.model = model
        self.optimizer = optimizer
        self.loss_fn = loss_fn
        self.scaler = scaler
        self.donate = donate
        self.accumulate_steps = int(accumulate_steps)
        self._params = [p for p in model.parameters()]
        self._buffers = list(model.buffers())
        self._jitted = None
        self._step_i = 0

    def _make_pure(self):
        model, opt, loss_fn = self.model, self.optimizer, self.loss_fn
        params, buffers = self._params, self._buffers
        scaler = self.scaler

        def pure(param_vals, buf_vals, acc_tree, master_list, key, lr,
                 step_count, arg_vals):
            old_key = default_generator._key
            old_p = [p._value for p in params]
            old_g = [p.grad for p in params]
            old_b = [b._value for b in buffers]
            old_acc = opt._accumulators if opt is not None else None
            old_master = opt._master_weights if opt is not None else None
            old_step = opt._step_count if opt is not None else None
            old_get_lr = opt.get_lr if opt is not None else None
            try:
                for p, v in zip(params, param_vals):
                    p._value = v
                    p.grad = None
                for b, v in zip(buffers, buf_vals):
                    b._value = v
                default_generator._key = key
                if opt is not None:
                    opt._accumulators = {
                        name: {id(params[i]): arr
                               for i, arr in store.items()}
                        for name, store in acc_tree.items()}
                    opt._master_weights = {
                        id(params[i]): arr for i, arr in master_list.items()}
                    opt._step_count = step_count
                    opt.get_lr = lambda: lr
                args = jax.tree_util.tree_map(Tensor, arg_vals)
                k = self.accumulate_steps

                def run_micro(margs):
                    out = (loss_fn(model, *margs) if loss_fn is not None
                           else model(*margs))
                    a = None
                    if isinstance(out, (tuple, list)):
                        out, a = out[0], out[1:]
                    scaled = out / k if k > 1 else out
                    if scaler is not None and scaler._enable:
                        scaled = scaler.scale(scaled)
                    scaled.backward()
                    return out, a

                if k > 1:
                    def slice_micro(t, j):
                        b = t.shape[0]
                        if b % k:
                            raise ValueError(
                                f"accumulate_steps={k} does not divide "
                                f"batch dim {b}")
                        mb = b // k
                        return t[j * mb:(j + 1) * mb]
                    micro_losses = []
                    micro_aux = []
                    for j in range(k):
                        margs = jax.tree_util.tree_map(
                            lambda t: slice_micro(t, j), args,
                            is_leaf=lambda x: isinstance(x, Tensor))
                        mloss, maux = run_micro(margs)
                        micro_losses.append(mloss._value)
                        micro_aux.append(maux)
                    loss = Tensor(
                        jnp.mean(jnp.stack(micro_losses)),
                        stop_gradient=True)
                    # re-assemble per-example aux (logits etc.) across the
                    # micro-batches so callers see the FULL batch, not the
                    # last micro-batch mislabeled as the whole step
                    aux = None
                    if micro_aux[0] is not None:
                        aux = jax.tree_util.tree_map(
                            lambda *xs: Tensor(jnp.concatenate(
                                [x._value if isinstance(x, Tensor)
                                 else x for x in xs], axis=0)),
                            *micro_aux,
                            is_leaf=lambda x: isinstance(x, Tensor))
                else:
                    loss, aux = run_micro(args)
                if opt is not None:
                    opt.step()
                new_params = [p._value for p in params]
                new_bufs = [b._value for b in buffers]
                new_acc = {
                    name: {i: store[id(params[i])]
                           for i in range(len(params))
                           if id(params[i]) in store}
                    for name, store in (opt._accumulators if opt else {}
                                        ).items()}
                new_master = {i: opt._master_weights[id(params[i])]
                              for i in range(len(params))
                              if opt and id(params[i]) in opt._master_weights}
                out_key = default_generator._key
                loss_val = loss._value
                aux_vals = _tensors_to_values(list(aux)) if aux else []
                return (new_params, new_bufs, new_acc, new_master, out_key,
                        loss_val, aux_vals)
            finally:
                default_generator._key = old_key
                for p, v, g in zip(params, old_p, old_g):
                    p._value = v
                    p.grad = g
                for b, v in zip(buffers, old_b):
                    b._value = v
                if opt is not None:
                    # restore python-side optimizer state: tracing (e.g.
                    # memory_analysis, or an aborted trace) must not leak
                    # tracers into _accumulators/_step_count/get_lr
                    opt._accumulators = old_acc
                    opt._master_weights = old_master
                    opt._step_count = old_step
                    opt.get_lr = old_get_lr

        donate = (0, 2, 3) if self.donate else ()
        return jax.jit(pure, donate_argnums=donate,
                       out_shardings=self._pin_state_to_mesh())

    def _pin_state_to_mesh(self):
        """The step's `out_shardings`: the state goes back laid out as
        it came in. Left to itself GSPMD returns some of a sharded
        model's state with shardings of its own choosing (a replicated
        norm weight comes back split over `sharding`; the rope tables
        and the RNG key, fed from one device, come back on the mesh),
        the next call then sees new input types and the whole step
        traces and compiles AGAIN — the two steps after the first took
        41.5 s each at Llama-3.2-1B on four v5e chips (chip run, PR 21).
        So under a mesh the buffers and the key are first put on it
        (replicated), and every state output is pinned to its input's
        sharding. With everything on one device: None, the compiler
        chooses."""
        def of(v):
            s = getattr(v, "sharding", None)
            return s if s is not None and len(s.device_set) > 1 else None

        on_mesh = next((s for s in (of(p._value) for p in self._params)
                        if isinstance(s, jax.sharding.NamedSharding)),
                       None)
        if on_mesh is None:
            return None
        repl = jax.sharding.NamedSharding(on_mesh.mesh,
                                          jax.sharding.PartitionSpec())
        for b in self._buffers:
            if of(b._value) is None:
                b._value = jax.device_put(b._value, repl)
        default_generator._key = jax.device_put(default_generator._key,
                                                repl)
        acc, master = self._materialize_state()
        return ([of(p._value) for p in self._params],
                [of(b._value) for b in self._buffers],
                {name: {i: of(a) for i, a in store.items()}
                 for name, store in acc.items()},
                {i: of(a) for i, a in master.items()},
                repl, None, None)

    def _materialize_state(self):
        """Run one eager warmup step ONLY to create optimizer accumulators
        lazily? Instead: pre-create accumulators with zeros so the compiled
        program's signature is stable from step 0."""
        opt = self.optimizer
        if opt is None:
            return {}, {}
        # touch accumulators for all trainable params by running the
        # optimizer's state creation paths
        acc_by_index = {}
        for name, store in opt._accumulators.items():
            acc_by_index[name] = {
                i: store[id(p)] for i, p in enumerate(self._params)
                if id(p) in store}
        master = {i: opt._master_weights[id(p)]
                  for i, p in enumerate(self._params)
                  if id(p) in opt._master_weights}
        return acc_by_index, master

    def __call__(self, *args):
        if self._jitted is None:
            self._warmup(*args)
        opt = self.optimizer
        acc, master = self._materialize_state()
        lr = np.float32(opt.get_lr()) if opt else np.float32(0.0)
        key = default_generator._key
        arg_vals = _tensors_to_values(list(args))
        # pass the PRE-step count; opt.step() increments it inside the trace
        step_count = opt._step_count if opt else 0
        (new_p, new_b, new_acc, new_master, out_key, loss_val,
         aux_vals) = self._jitted(
            [p._value for p in self._params],
            [b._value for b in self._buffers],
            acc, master, key, lr, np.int32(step_count), arg_vals)
        for p, v in zip(self._params, new_p):
            p._value = v
            p.grad = None
        for b, v in zip(self._buffers, new_b):
            b._value = v
        if opt is not None:
            for name, store in new_acc.items():
                opt._accumulators[name] = {
                    id(self._params[i]): arr for i, arr in store.items()}
            opt._master_weights = {
                id(self._params[i]): arr
                for i, arr in new_master.items()}
            opt._step_count = step_count + 1
            if hasattr(opt._learning_rate, "step"):
                pass  # user drives scheduler.step() as in the reference
        default_generator._key = out_key
        loss = Tensor(loss_val)
        if aux_vals:
            return (loss,) + tuple(jax.tree_util.tree_map(Tensor, aux_vals))
        return loss

    def _warmup(self, *args):
        """Create optimizer state eagerly (zeros) so the jitted signature is
        stable, then build the compiled function. State creation is
        optimizer-owned (`Optimizer.ensure_state`) — a new optimizer
        subclass only overrides `_create_state` and compiled mode works."""
        if self.optimizer is not None:
            self.optimizer.ensure_state()
        self._jitted = self._make_pure()

    def lower(self, *args):
        """The `jax.stages.Lowered` of THIS train step at the given
        example inputs — the same pure function `__call__` runs, so its
        text says which kernels the step contains
        (`ops.mosaic_kernels(step.lower(x, y).as_text())`) and
        `.compile()` gives its buffer assignment. Runs nothing."""
        if self._jitted is None:
            self._warmup(*args)
        opt = self.optimizer
        acc, master = self._materialize_state()
        lr = np.float32(opt.get_lr()) if opt else np.float32(0.0)
        arg_vals = _tensors_to_values(list(args))
        return self._jitted.lower(
            [p._value for p in self._params],
            [b._value for b in self._buffers],
            acc, master, default_generator._key, lr,
            np.int32(opt._step_count if opt else 0), arg_vals)

    def memory_analysis(self, *args):
        """XLA buffer-assignment sizes for THIS train step at the given
        example inputs (utils.memory.compiled_memory_stats over the same
        pure function __call__ runs): the per-step HBM accounting that
        defends remat/ZeRO/pipeline memory claims. ≙ the reference's
        `max_memory_allocated` + StatAllocator observability (SURVEY.md
        §5), but ahead-of-time and exact."""
        from ..utils.memory import analysis_dict
        return analysis_dict(
            self.lower(*args).compile().memory_analysis())


def save(layer, path, input_spec=None, **configs):
    """≙ paddle.jit.save: serialize (a) params via paddle save format and
    (b) the traced StableHLO program via jax.export when input_spec given."""
    from ..framework import io as fio
    from ..nn.layer.layers import Layer

    if isinstance(layer, Layer):
        fio.save(layer.state_dict(), path + ".pdiparams")
        if input_spec is not None:
            try:
                from jax import export as jexport
                # derive BOTH lists from state_dict: that is exactly
                # what .pdiparams serializes and what TranslatedLayer
                # rebinds positionally at load — same membership
                # (non-persistable buffers excluded; they bake as
                # constants) and same ORDER, or the arity/binding drifts
                sd = layer.state_dict()
                params = [t for t in sd.values()
                          if isinstance(t, Parameter)]
                buffers = [t for t in sd.values()
                           if isinstance(t, Tensor)
                           and not isinstance(t, Parameter)]

                def pure(param_vals, buf_vals, *arg_vals):
                    # bind_state restores the live values afterwards —
                    # without it the export trace left TRACERS on the
                    # model's parameters (caught by the predictor-API
                    # tests: the model was unusable after jit.save)
                    from ..models.generation import bind_state
                    with bind_state(params, buffers, param_vals,
                                    buf_vals):
                        out = layer(*[Tensor(a) for a in arg_vals])
                        return _tensors_to_values(out)
                specs = [jax.ShapeDtypeStruct(tuple(s.shape), s.dtype)
                         for s in input_spec]
                exp = jexport.export(jax.jit(pure))(
                    [p._value for p in params],
                    [b._value for b in buffers], *specs)
                with open(path + ".pdmodel", "wb") as f:
                    f.write(exp.serialize())
                # sidecar metadata: the REAL input arity/names, so the
                # Predictor never has to reverse-engineer them from
                # flat-aval arithmetic (advisor r4: that breaks when
                # buffers bake as constants or inputs are pytrees)
                import json
                meta = {
                    "input_names": [
                        getattr(s, "name", None) or f"input_{i}"
                        for i, s in enumerate(input_spec)],
                    "n_inputs": len(list(input_spec)),
                    "n_params": len(params),
                    "n_buffers": len(buffers),
                }
                with open(path + ".pdmeta", "w") as f:
                    json.dump(meta, f)
            except Exception as e:  # export is best-effort
                import warnings
                warnings.warn(f"StableHLO export skipped: {e}")
    else:
        raise TypeError("jit.save expects an nn.Layer")


def load(path, params_file=None, **configs):
    """≙ paddle.jit.load — returns a TranslatedLayer-like callable.
    `params_file` overrides the default `<path>.pdiparams`."""
    from ..framework import io as fio
    state = fio.load(params_file or path + ".pdiparams")

    class TranslatedLayer:
        def __init__(self):
            self.state = state
            self._exported = None
            self.meta = None
            import os
            if os.path.exists(path + ".pdmodel"):
                from jax import export as jexport
                with open(path + ".pdmodel", "rb") as f:
                    self._exported = jexport.deserialize(f.read())
            if os.path.exists(path + ".pdmeta"):
                import json
                with open(path + ".pdmeta") as f:
                    self.meta = json.load(f)

        def state_dict(self):
            return self.state

        def __call__(self, *args):
            if self._exported is None:
                raise RuntimeError(
                    "no serialized program; jit.save was called without "
                    "input_spec")
            params = [t._value for t in self.state.values()
                      if isinstance(t, Parameter)]
            bufs = [t._value for t in self.state.values()
                    if isinstance(t, Tensor) and not isinstance(t, Parameter)]
            vals = [a._value if isinstance(a, Tensor) else jnp.asarray(a)
                    for a in args]
            out = self._exported.call(params, bufs, *vals)
            return jax.tree_util.tree_map(Tensor, out)

    return TranslatedLayer()


class InputSpec:
    """≙ paddle.static.InputSpec."""

    def __init__(self, shape, dtype="float32", name=None, stop_gradient=True):
        from ..core import dtype as dtypes
        self.shape = tuple(-1 if s is None else int(s) for s in shape)
        self.dtype = dtypes.convert_dtype(dtype)
        self.name = name

    def __repr__(self):
        return f"InputSpec(shape={self.shape}, dtype={self.dtype})"


def enable_to_static(flag: bool = True):
    pass
