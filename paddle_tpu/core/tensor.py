"""Eager Tensor with Paddle-style semantics over jax.Array.

Capability parity with the reference's eager Tensor (SURVEY.md §2.1
«paddle/fluid/pybind/eager*.cc», «paddle/phi/core/» `DenseTensor` [U]):
mutable `.grad`, `stop_gradient`, `.numpy()`, operator overloads, in-place
`__setitem__`, method surface. Unlike the reference (C++ tensor + pybind),
this Tensor is a thin Python wrapper over an immutable `jax.Array`; "in-place"
ops rebind `_value` (functionally pure underneath, so the same code traces
cleanly under `jax.jit`).

Registered as a JAX pytree so Tensors can cross `jit`/`shard_map` boundaries.
"""
from __future__ import annotations

from typing import Any, Callable, Optional, Sequence

import numpy as np
import jax
import jax.numpy as jnp

from . import dtype as dtypes
from . import tape
from .tape import is_grad_enabled, no_grad  # re-export


class Tensor:
    __slots__ = ("_value", "stop_gradient", "grad", "name", "persistable",
                 "_node", "_out_index", "_grad_hooks", "trainable",
                 "__weakref__", "__dict__")

    def __init__(self, value, stop_gradient: bool = True, name: str | None = None):
        if isinstance(value, Tensor):
            value = value._value
        elif not isinstance(value, jax.Array):
            value = jnp.asarray(value)
        self._value = value
        self.stop_gradient = stop_gradient
        self.grad = None
        self.name = name
        self.persistable = False
        self.trainable = not stop_gradient
        self._node = None       # tape.Node that produced this tensor
        self._out_index = 0
        self._grad_hooks = None

    # -- basic properties ---------------------------------------------------
    @property
    def shape(self) -> list:
        return list(self._value.shape)

    @property
    def ndim(self) -> int:
        return self._value.ndim

    @property
    def size(self) -> int:
        return int(np.prod(self._value.shape)) if self._value.shape else 1

    @property
    def dtype(self) -> np.dtype:
        return np.dtype(self._value.dtype)

    @property
    def place(self):
        devs = getattr(self._value, "devices", None)
        return list(devs())[0] if callable(devs) else None

    @property
    def T(self) -> "Tensor":
        return apply("transpose", lambda v: jnp.transpose(v), (self,))

    @property
    def mT(self) -> "Tensor":
        return apply("matrix_transpose", lambda v: jnp.swapaxes(v, -1, -2), (self,))

    @property
    def is_leaf(self) -> bool:
        return self._node is None

    def numpy(self) -> np.ndarray:
        if isinstance(self._value, jax.core.Tracer):
            self._graph_break("numpy()")
        return np.asarray(self._value)

    def _graph_break(self, coercion: str):
        raise GraphBreakError(
            f"{coercion} on a traced Tensor: data-dependent Python "
            "control flow cannot be compiled into one XLA program "
            "(≙ a SOT graph break in the reference). Inside "
            "to_static/TrainStep/static.Executor, express the branch "
            "with tensor ops (paddle.where, logical masks) or move it "
            "outside the compiled step; paddle.jit.not_to_static marks "
            "helpers that must stay eager.")

    def _scalar(self, coercion: str) -> np.ndarray:
        """Concrete 0-d view for python-scalar coercion: paddle allows
        float()/int()/bool() on any 1-element tensor (numpy deprecated
        the implicit squeeze, so do it explicitly)."""
        if isinstance(self._value, jax.core.Tracer):
            self._graph_break(coercion)
        arr = self.numpy()
        if arr.ndim:
            if arr.size != 1:
                raise TypeError(
                    f"only 1-element tensors convert to python scalars "
                    f"(got shape {tuple(arr.shape)})")
            arr = arr.reshape(())
        return arr

    def __bool__(self):
        return bool(self._scalar("bool()/if-condition"))

    def __float__(self):
        return float(self._scalar("float()"))

    def __int__(self):
        return int(self._scalar("int()"))

    def __index__(self):
        arr = self._scalar("integer indexing coercion")
        if not np.issubdtype(arr.dtype, np.integer) and \
                arr.dtype != np.bool_:
            raise TypeError(
                f"only integer tensors are valid indices (got "
                f"{arr.dtype})")
        return int(arr)

    def item(self, *idx):
        if isinstance(self._value, jax.core.Tracer):
            self._graph_break(".item()")
        if idx:
            return self.numpy().item(*idx)
        return self.numpy().item()

    def tolist(self):
        return self.numpy().tolist()

    def __len__(self):
        if self.ndim == 0:
            raise TypeError("len() of a 0-D tensor")
        return self._value.shape[0]

    def __repr__(self):
        grad_s = "" if self.stop_gradient else ", stop_gradient=False"
        return (f"Tensor(shape={self.shape}, dtype={self.dtype.name}"
                f"{grad_s},\n       {np.asarray(self._value)!r})")

    def __format__(self, spec):
        if self.ndim == 0:
            return format(self.item(), spec)
        return repr(self)

    def __hash__(self):
        return id(self)

    def __array__(self, dtype=None):
        a = self.numpy()
        return a.astype(dtype) if dtype is not None else a

    def __iter__(self):
        for i in range(len(self)):
            yield self[i]

    # -- autograd -----------------------------------------------------------
    def backward(self, grad_tensor=None, retain_graph: bool = False) -> None:
        tape.backward(self, grad=grad_tensor, retain_graph=retain_graph)

    def clear_grad(self) -> None:
        self.grad = None

    def clear_gradient(self) -> None:  # paddle alias
        self.grad = None

    def register_hook(self, hook: Callable) -> "RemovableHook":
        if self._grad_hooks is None:
            self._grad_hooks = []
        self._grad_hooks.append(hook)
        return RemovableHook(self._grad_hooks, hook)

    def detach(self) -> "Tensor":
        return Tensor(self._value, stop_gradient=True, name=self.name)

    def detach_(self) -> "Tensor":
        self._node = None
        self.stop_gradient = True
        return self

    def clone(self) -> "Tensor":
        return apply("clone", lambda v: v + jnp.zeros((), v.dtype), (self,))

    # torch-migration aliases (paddle.Tensor exposes these too [U])
    def dim(self) -> int:
        return self._value.ndim

    ndimension = dim

    def nelement(self) -> int:
        import numpy as _np
        return int(_np.prod(self._value.shape)) if self._value.shape else 1

    def element_size(self) -> int:
        return self._value.dtype.itemsize

    # -- conversion / movement ---------------------------------------------
    def astype(self, dt) -> "Tensor":
        dt = dtypes.convert_dtype(dt)
        return apply("cast", lambda v: v.astype(dt), (self,))

    cast = astype

    def to(self, *args, **kwargs) -> "Tensor":
        """to(dtype) / to(device) / to(device, dtype). Device moves use
        jax.device_put; 'cpu'/'tpu'/'gpu' strings accepted."""
        out = self
        for a in list(args) + list(kwargs.values()):
            if isinstance(a, (str, jax.Device)) and not _is_dtype_like(a):
                dev = _resolve_device(a)
                v = jax.device_put(out._value, dev)
                t = Tensor(v, stop_gradient=out.stop_gradient, name=out.name)
                t._node, t._out_index = out._node, out._out_index
                out = t
            else:
                out = out.astype(a)
        return out

    def cpu(self) -> "Tensor":
        return self.to("cpu")

    def cuda(self, *a, **k) -> "Tensor":  # parity shim: "cuda" = accelerator
        return self.to("tpu")

    def pin_memory(self) -> "Tensor":
        return self

    def contiguous(self) -> "Tensor":
        return self

    # -- python operators (full surface wired in ops/__init__) --------------
    def __getitem__(self, idx) -> "Tensor":
        idx = _index_to_static(idx)
        return apply("getitem", lambda v: v[idx], (self,))

    def __setitem__(self, idx, value) -> None:
        idx = _index_to_static(idx)
        if isinstance(value, Tensor):
            out = apply("setitem",
                        lambda v, w: v.at[idx].set(w.astype(v.dtype)),
                        (self, value))
        else:
            out = apply("setitem", lambda v: v.at[idx].set(value), (self,))
        self._assign_inplace(out)

    def _assign_inplace(self, out: "Tensor") -> None:
        """Rebind this tensor to a new value, preserving autograd wiring.
        This is how every `*_`-suffixed in-place op is implemented."""
        self._value = out._value
        self._node = out._node
        self._out_index = out._out_index
        self.stop_gradient = out.stop_gradient

    # Arithmetic dunders are attached by paddle_tpu.tensor (method registry);
    # minimal set defined here so the core module is usable standalone.
    def __neg__(self):
        return apply("neg", lambda v: -v, (self,))

    def __abs__(self):
        return apply("abs", jnp.abs, (self,))


class RemovableHook:
    def __init__(self, hooks: list, hook):
        self._hooks, self._hook = hooks, hook

    def remove(self):
        try:
            self._hooks.remove(self._hook)
        except ValueError:
            pass


class Parameter(Tensor):
    """Trainable tensor; ≙ reference `EagerParamBase`/`Parameter` [U]."""

    def __init__(self, value, trainable: bool = True, name: str | None = None):
        super().__init__(value, stop_gradient=not trainable, name=name)
        self.persistable = True
        self.trainable = trainable

    def __repr__(self):
        return "Parameter containing:\n" + super().__repr__()


# -- pytree registration ----------------------------------------------------
def _tensor_flatten(t: Tensor):
    return (t._value,), (t.stop_gradient, t.name, type(t))


def _tensor_unflatten(aux, children):
    stop_gradient, name, cls = aux
    val, = children
    if cls is Parameter:
        out = Parameter.__new__(Parameter)
        Tensor.__init__(out, val, stop_gradient=stop_gradient, name=name)
        out.persistable = True
        out.trainable = not stop_gradient
        return out
    return cls(val, stop_gradient=stop_gradient, name=name)


jax.tree_util.register_pytree_node(Tensor, _tensor_flatten, _tensor_unflatten)
jax.tree_util.register_pytree_node(Parameter, _tensor_flatten, _tensor_unflatten)


# -- op application (the single dispatch point) ------------------------------
def _check_nan_inf(name: str, out_vals, multi_output: bool) -> None:
    """FLAGS_check_nan_inf eager path: scan op outputs, raise with the op
    name — ≙ the reference's per-kernel scan with op-level blame
    («paddle/fluid/framework/details/nan_inf_utils*» [U?], SURVEY.md §5).
    Traced values are skipped (can't concretize); jax_debug_nans covers
    the compiled path."""
    outs = out_vals if multi_output else (out_vals,)
    for i, v in enumerate(outs):
        if not isinstance(v, jax.Array) or isinstance(v, jax.core.Tracer):
            continue
        if not jnp.issubdtype(v.dtype, jnp.floating) and \
                not jnp.issubdtype(v.dtype, jnp.complexfloating):
            continue
        bad = bool(jnp.any(jnp.isnan(v) | jnp.isinf(v)))
        if bad:
            n_nan = int(jnp.sum(jnp.isnan(v)))
            n_inf = int(jnp.sum(jnp.isinf(v)))
            raise RuntimeError(
                f"FLAGS_check_nan_inf: op '{name}' output {i} "
                f"(shape {tuple(v.shape)}, dtype {v.dtype}) contains "
                f"{n_nan} NaN / {n_inf} Inf values")


# optional per-op observer (amp.debugging operator-stats collection);
# a module-level hook because every op module binds `apply` by reference
_op_observer = None

# optional post-op recorder (paddle.static Program capture): called with
# (name, fn, in_tensors, out, multi_output) after the op executed
_op_recorder = None


class GraphBreakError(TypeError):
    """Data-dependent Python control flow reached a traced Tensor.

    ≙ the reference SOT front end's graph-break detection
    («python/paddle/jit/sot/», SURVEY.md §2.2): instead of silently
    unrolling or failing deep inside XLA, the framework raises this
    pointed error at the exact Python coercion (`if t:`, `float(t)`,
    `int(t)`, `t.numpy()`) that cannot be compiled."""


def apply(name: str,
          fn: Callable,
          tensors: Sequence[Tensor],
          multi_output: bool = False):
    """Execute op `fn` over the values of `tensors`; record a grad node when
    any input requires grad. ≙ reference generated `*_ad_func` + PHI dispatch
    (SURVEY.md §3.1) collapsed into one function — kernel selection is XLA's
    job on TPU."""
    if _op_observer is not None:
        _op_observer(name, tensors)
    vals = [t._value for t in tensors]

    # AMP autocast: cast float inputs per op lists (≙ eager AMP insertion,
    # SURVEY.md §3.1)
    from . import amp_state as _amp
    decision = _amp.resolve(name)
    fn_effective = fn
    if decision is not None:
        from . import dtype as _dt
        low = _dt.convert_dtype(_amp.amp_state.dtype)
        if decision == "low":
            def _cast(v):
                return v.astype(low) if v.dtype == jnp.float32 else v
        else:
            def _cast(v):
                return (v.astype(jnp.float32)
                        if v.dtype in (jnp.float16, jnp.bfloat16) else v)
        vals = [_cast(v) for v in vals]

        # the static recorder replays fn on RAW env values, so the AMP
        # cast must be part of the recorded function — bake it in
        def fn_effective(*vs, _fn=fn, _c=_cast):
            return _fn(*[_c(v) for v in vs])

    needs_grad = is_grad_enabled() and any(
        (not t.stop_gradient) for t in tensors)

    from ..utils import flags as _flags
    try:
        if needs_grad:
            out_vals, vjp_fn = jax.vjp(fn, *vals)
            node = tape.record(name, fn, tensors, out_vals, vjp_fn,
                               multi_output)
        else:
            out_vals = fn(*vals)
            node = None
    except FloatingPointError as e:
        # jax_debug_nans raised inside the op — re-raise with op-level
        # blame (≙ reference nan_inf_utils op attribution, SURVEY.md §5)
        raise RuntimeError(
            f"FLAGS_check_nan_inf: op '{name}' produced non-finite "
            f"values ({e})") from e

    if _flags.check_nan_inf_enabled:
        _check_nan_inf(name, out_vals, multi_output)

    def make(i, v):
        t = Tensor(v, stop_gradient=not needs_grad)
        if node is not None:
            t._node, t._out_index = node, i
        return t

    if multi_output:
        out = type(out_vals)(make(i, v) for i, v in enumerate(out_vals))
    else:
        out = make(0, out_vals)
    if _op_recorder is not None:
        _op_recorder(name, fn_effective, tensors, out, multi_output)
    return out


def to_tensor(data, dtype=None, place=None, stop_gradient: bool = True) -> Tensor:
    """≙ `paddle.to_tensor` [U]."""
    if isinstance(data, Tensor):
        v = data._value
        if dtype is not None:
            v = v.astype(dtypes.convert_dtype(dtype))
        t = Tensor(v, stop_gradient=stop_gradient)
        return t
    if dtype is not None:
        v = jnp.asarray(data, dtype=dtypes.convert_dtype(dtype))
    else:
        v = jnp.asarray(data)
        # python floats default to framework default dtype (fp32), like paddle
        if isinstance(data, float):
            v = v.astype(dtypes.get_default_dtype())
        elif isinstance(data, (list, tuple)) and v.dtype == jnp.float64:
            v = v.astype(dtypes.get_default_dtype())
        elif isinstance(data, np.ndarray) and data.dtype == np.float64:
            v = v.astype(dtypes.get_default_dtype())
    if place is not None:
        v = jax.device_put(v, _resolve_device(place))
    return Tensor(v, stop_gradient=stop_gradient)


def _is_dtype_like(a) -> bool:
    if isinstance(a, str):
        try:
            dtypes.convert_dtype(a)
            return True
        except TypeError:
            return False
    return False


def _resolve_device(d):
    if isinstance(d, jax.Device):
        return d
    s = str(d).lower()
    plat = s.split(":")[0]
    idx = int(s.split(":")[1]) if ":" in s else 0
    if plat in ("gpu", "cuda", "tpu", "xpu"):  # any accelerator alias
        accel = [x for x in jax.devices() if x.platform != "cpu"]
        if not accel:
            raise RuntimeError(
                f"device {d!r} names an accelerator but jax.devices() "
                f"= {jax.devices()} has none")
        return accel[min(idx, len(accel) - 1)]
    if plat == "cpu":
        return jax.devices("cpu")[0] if any(
            x.platform == "cpu" for x in jax.devices()) else jax.devices()[0]
    return jax.devices()[0]


def _index_to_static(idx):
    """Convert Tensor indices inside a getitem key to concrete arrays."""
    if isinstance(idx, Tensor):
        return idx._value
    if isinstance(idx, tuple):
        return tuple(_index_to_static(i) for i in idx)
    if isinstance(idx, list):
        return jnp.asarray(np.asarray(idx))
    return idx
