"""Fused RMSNorm / LayerNorm Pallas kernels with custom VJP.

≙ reference fused rms_norm / layer-norm CUDA kernels
(«paddle/phi/kernels/fusion/», fused_bias_dropout_residual_layer_norm [U]).
Row-blocked over (rows, hidden): one VMEM pass computes stats + normalized
output; bwd recomputes x_hat from saved rstd (memory-light) and reduces
dgamma/dbeta across row blocks via output accumulation.

Mosaic tiling: per-row stats (rstd/mean) are stored broadcast across a
full 128-lane register as (n, LANES) arrays — the same convention as
flash_attention.py's lse/delta residuals — because Mosaic requires the
minor block dim to be 128-aligned and XLA tiles 1-D f32 arrays with its
own T(1024) layout that a (block_rows,) BlockSpec cannot match (this
exact mismatch failed compilation on v5e at (16384, 1024)). Stats are
max-reduced back to a column on read in the bwd kernels. The `n % br`
guard in the *_values entry points routes ragged row counts to the XLA
fallback.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

from . import on_tpu
from ..core.tensor import Tensor, apply

BLOCK_ROWS = 256
# Stats live lane-broadcast in (n, LANES) arrays; see module docstring.
LANES = 128


def _interpret() -> bool:
    return not on_tpu()


# -- rmsnorm -----------------------------------------------------------------
def _rms_fwd_kernel(x_ref, w_ref, o_ref, rstd_ref, *, eps):
    x = x_ref[:].astype(jnp.float32)
    ms = jnp.mean(jnp.square(x), axis=-1, keepdims=True)
    rstd = jax.lax.rsqrt(ms + eps)
    o_ref[:] = (x * rstd * w_ref[:].astype(jnp.float32)).astype(o_ref.dtype)
    rstd_ref[:] = jnp.broadcast_to(rstd, rstd_ref.shape)


def _rms_bwd_kernel(x_ref, w_ref, rstd_ref, g_ref, dx_ref, dw_ref, *, eps):
    # dw accumulates across row blocks into one revisited (1, h) output
    # block — Mosaic can't tile a (nb, h) partials array with (1, h) blocks.
    @pl.when(pl.program_id(0) == 0)
    def _init():
        dw_ref[:] = jnp.zeros_like(dw_ref)

    x = x_ref[:].astype(jnp.float32)
    g = g_ref[:].astype(jnp.float32)
    w = w_ref[:].astype(jnp.float32)
    rstd = jnp.max(rstd_ref[:], axis=-1, keepdims=True)
    xhat = x * rstd
    wg = g * w
    # dx = rstd * (wg - xhat * mean(wg * xhat))
    mean_wgx = jnp.mean(wg * xhat, axis=-1, keepdims=True)
    dx_ref[:] = (rstd * (wg - xhat * mean_wgx)).astype(dx_ref.dtype)
    dw_ref[:] += jnp.sum(g * xhat, axis=0, keepdims=True)


def _rms_fwd(x2, w, eps, block_rows):
    n, h = x2.shape
    grid = (pl.cdiv(n, block_rows),)
    o, rstd = pl.pallas_call(
        functools.partial(_rms_fwd_kernel, eps=eps),
        grid=grid,
        in_specs=[pl.BlockSpec((block_rows, h), lambda i: (i, 0)),
                  pl.BlockSpec((h,), lambda i: (0,))],
        out_specs=[pl.BlockSpec((block_rows, h), lambda i: (i, 0)),
                   pl.BlockSpec((block_rows, LANES), lambda i: (i, 0))],
        out_shape=[jax.ShapeDtypeStruct((n, h), x2.dtype),
                   jax.ShapeDtypeStruct((n, LANES), jnp.float32)],
        interpret=_interpret(),
        name="rms_norm_fwd",
    )(x2, w)
    return o, rstd


@functools.partial(jax.custom_vjp, nondiff_argnums=(2, 3))
def _rms(x2, w, eps, block_rows):
    return _rms_fwd(x2, w, eps, block_rows)[0]


def _rms_fwd_rule(x2, w, eps, block_rows):
    o, rstd = _rms_fwd(x2, w, eps, block_rows)
    # keep only one lane as the autograd residual (all LANES are identical);
    # re-broadcast transiently at bwd time
    return o, (x2, w, rstd[:, :1])


def _rms_bwd_rule(eps, block_rows, res, g):
    x2, w, rstd1 = res
    n, h = x2.shape
    rstd = jnp.broadcast_to(rstd1, (n, LANES))
    nb = pl.cdiv(n, block_rows)
    dx, dw_acc = pl.pallas_call(
        functools.partial(_rms_bwd_kernel, eps=eps),
        grid=(nb,),
        in_specs=[pl.BlockSpec((block_rows, h), lambda i: (i, 0)),
                  pl.BlockSpec((h,), lambda i: (0,)),
                  pl.BlockSpec((block_rows, LANES), lambda i: (i, 0)),
                  pl.BlockSpec((block_rows, h), lambda i: (i, 0))],
        out_specs=[pl.BlockSpec((block_rows, h), lambda i: (i, 0)),
                   pl.BlockSpec((1, h), lambda i: (0, 0))],
        out_shape=[jax.ShapeDtypeStruct((n, h), x2.dtype),
                   jax.ShapeDtypeStruct((1, h), jnp.float32)],
        interpret=_interpret(),
        name="rms_norm_bwd",
    )(x2, w, rstd, g)
    return dx, dw_acc[0].astype(w.dtype)


_rms.defvjp(_rms_fwd_rule, _rms_bwd_rule)


def _row_roles(ndim):
    """Rows are independent, so they split over whatever the mesh
    splits the leading (batch, seq) dimensions over; the normalized
    dimension stays whole."""
    return (("batch", "seq") + (None,) * ndim)[:ndim - 1] + (None,)


def rms_norm_values(x, w, eps=1e-6, block_rows=BLOCK_ROWS):
    def local(x, w):
        shape = x.shape
        h = shape[-1]
        x2 = x.reshape(-1, h)
        n = x2.shape[0]
        br = min(block_rows, n)
        if n % br:  # fall back to XLA for ragged row counts
            xf = x.astype(jnp.float32)
            ms = jnp.mean(jnp.square(xf), -1, keepdims=True)
            return (xf * jax.lax.rsqrt(ms + eps)
                    * w.astype(jnp.float32)).astype(x.dtype)
        return _rms(x2, w, float(eps), br).reshape(shape)

    from ..distributed.mesh import shard_kernel
    roles = _row_roles(x.ndim)
    return shard_kernel(local, (x, w), (roles, (None,)), roles)


def rms_norm(x: Tensor, weight: Tensor, epsilon: float = 1e-6) -> Tensor:
    # op name matches the XLA path so the AMP BLACK_LIST fp32 protection
    # applies identically on both backends
    def fn(v, w):
        return rms_norm_values(v, w, epsilon)
    return apply("rms_norm", fn, (x, weight))


# -- layernorm ---------------------------------------------------------------
def _ln_fwd_kernel(x_ref, w_ref, b_ref, o_ref, mean_ref, rstd_ref, *, eps):
    x = x_ref[:].astype(jnp.float32)
    mu = jnp.mean(x, axis=-1, keepdims=True)
    var = jnp.mean(jnp.square(x - mu), axis=-1, keepdims=True)
    rstd = jax.lax.rsqrt(var + eps)
    xhat = (x - mu) * rstd
    o_ref[:] = (xhat * w_ref[:].astype(jnp.float32)
                + b_ref[:].astype(jnp.float32)).astype(o_ref.dtype)
    mean_ref[:] = jnp.broadcast_to(mu, mean_ref.shape)
    rstd_ref[:] = jnp.broadcast_to(rstd, rstd_ref.shape)


def _ln_bwd_kernel(x_ref, w_ref, mean_ref, rstd_ref, g_ref,
                   dx_ref, dw_ref, db_ref, *, eps):
    @pl.when(pl.program_id(0) == 0)
    def _init():
        dw_ref[:] = jnp.zeros_like(dw_ref)
        db_ref[:] = jnp.zeros_like(db_ref)

    x = x_ref[:].astype(jnp.float32)
    g = g_ref[:].astype(jnp.float32)
    w = w_ref[:].astype(jnp.float32)
    mu = jnp.max(mean_ref[:], axis=-1, keepdims=True)
    rstd = jnp.max(rstd_ref[:], axis=-1, keepdims=True)
    xhat = (x - mu) * rstd
    wg = g * w
    m1 = jnp.mean(wg, axis=-1, keepdims=True)
    m2 = jnp.mean(wg * xhat, axis=-1, keepdims=True)
    dx_ref[:] = (rstd * (wg - m1 - xhat * m2)).astype(dx_ref.dtype)
    dw_ref[:] += jnp.sum(g * xhat, axis=0, keepdims=True)
    db_ref[:] += jnp.sum(g, axis=0, keepdims=True)


@functools.partial(jax.custom_vjp, nondiff_argnums=(3, 4))
def _ln(x2, w, b, eps, block_rows):
    return _ln_fwd(x2, w, b, eps, block_rows)[0]


def _ln_fwd(x2, w, b, eps, block_rows):
    return _ln_fwd_call(x2, w, b, eps, block_rows, _interpret())


@functools.partial(jax.jit,
                   static_argnames=("eps", "block_rows", "interpret"))
def _ln_fwd_call(x2, w, b, eps, block_rows, interpret):
    """Under `jax.jit` so that a program with a norm a layer traces and
    lowers the kernel once a shape, not once a call site (PR 26 did the
    same for the ragged kernel)."""
    n, h = x2.shape
    o, mean, rstd = pl.pallas_call(
        functools.partial(_ln_fwd_kernel, eps=eps),
        grid=(pl.cdiv(n, block_rows),),
        in_specs=[pl.BlockSpec((block_rows, h), lambda i: (i, 0)),
                  pl.BlockSpec((h,), lambda i: (0,)),
                  pl.BlockSpec((h,), lambda i: (0,))],
        out_specs=[pl.BlockSpec((block_rows, h), lambda i: (i, 0)),
                   pl.BlockSpec((block_rows, LANES), lambda i: (i, 0)),
                   pl.BlockSpec((block_rows, LANES), lambda i: (i, 0))],
        out_shape=[jax.ShapeDtypeStruct((n, h), x2.dtype),
                   jax.ShapeDtypeStruct((n, LANES), jnp.float32),
                   jax.ShapeDtypeStruct((n, LANES), jnp.float32)],
        interpret=interpret,
    )(x2, w, b)
    return o, mean, rstd


def _ln_fwd_rule(x2, w, b, eps, block_rows):
    o, mean, rstd = _ln_fwd(x2, w, b, eps, block_rows)
    return o, (x2, w, mean[:, :1], rstd[:, :1])


def _ln_bwd_rule(eps, block_rows, res, g):
    x2, w, mean1, rstd1 = res
    n, h = x2.shape
    mean = jnp.broadcast_to(mean1, (n, LANES))
    rstd = jnp.broadcast_to(rstd1, (n, LANES))
    nb = pl.cdiv(n, block_rows)
    dx, dw_p, db_p = pl.pallas_call(
        functools.partial(_ln_bwd_kernel, eps=eps),
        grid=(nb,),
        in_specs=[pl.BlockSpec((block_rows, h), lambda i: (i, 0)),
                  pl.BlockSpec((h,), lambda i: (0,)),
                  pl.BlockSpec((block_rows, LANES), lambda i: (i, 0)),
                  pl.BlockSpec((block_rows, LANES), lambda i: (i, 0)),
                  pl.BlockSpec((block_rows, h), lambda i: (i, 0))],
        out_specs=[pl.BlockSpec((block_rows, h), lambda i: (i, 0)),
                   pl.BlockSpec((1, h), lambda i: (0, 0)),
                   pl.BlockSpec((1, h), lambda i: (0, 0))],
        out_shape=[jax.ShapeDtypeStruct((n, h), x2.dtype),
                   jax.ShapeDtypeStruct((1, h), jnp.float32),
                   jax.ShapeDtypeStruct((1, h), jnp.float32)],
        interpret=_interpret(),
    )(x2, w, mean, rstd, g)
    return (dx, dw_p[0].astype(w.dtype), db_p[0].astype(w.dtype))


_ln.defvjp(_ln_fwd_rule, _ln_bwd_rule)


def layer_norm_values(x, w, b, eps=1e-5, block_rows=BLOCK_ROWS):
    def local(x, w, b):
        shape = x.shape
        h = shape[-1]
        x2 = x.reshape(-1, h)
        n = x2.shape[0]
        br = min(block_rows, n)
        if n % br:
            xf = x.astype(jnp.float32)
            mu = jnp.mean(xf, -1, keepdims=True)
            var = jnp.mean(jnp.square(xf - mu), -1, keepdims=True)
            return ((xf - mu) * jax.lax.rsqrt(var + eps)
                    * w.astype(jnp.float32)
                    + b.astype(jnp.float32)).astype(x.dtype)
        return _ln(x2, w, b, float(eps), br).reshape(shape)

    from ..distributed.mesh import shard_kernel
    roles = _row_roles(x.ndim)
    return shard_kernel(local, (x, w, b), (roles, (None,), (None,)),
                        roles)


def layer_norm(x: Tensor, weight: Tensor, bias: Tensor,
               epsilon: float = 1e-5) -> Tensor:
    def fn(v, w, b):
        return layer_norm_values(v, w, b, epsilon)
    return apply("layer_norm", fn, (x, weight, bias))
