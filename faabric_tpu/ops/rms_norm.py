"""Fused RMS-norm Pallas kernel.

One VMEM pass: mean-square, rsqrt and scale fuse into a single kernel
instead of the separate reductions + elementwise XLA would otherwise
schedule through HBM for large rows. fp32 statistics regardless of input
dtype (matches the model's _rms_norm semantics). Differentiable via
recompute-through-reference VJP.

Which path runs is a pure function of the shape and the backend:
:func:`uses_kernel` answers it. Row counts the block does not divide,
and sub-tile shapes on real hardware, run the jnp reference; a shape
the Mosaic compiler refuses is an error, never the reference.
"""

from __future__ import annotations

import functools
import math

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

DEFAULT_BLOCK_ROWS = 128


def _reference_rms_norm(x, scale, eps=1e-6):
    var = jnp.mean(jnp.square(x.astype(jnp.float32)), axis=-1, keepdims=True)
    return (x * jax.lax.rsqrt(var + eps).astype(x.dtype)) * scale.astype(x.dtype)


def _rms_kernel(x_ref, scale_ref, o_ref, *, eps: float):
    x = x_ref[:].astype(jnp.float32)
    var = jnp.mean(jnp.square(x), axis=-1, keepdims=True)
    normed = x * jax.lax.rsqrt(var + eps)
    o_ref[:] = (normed * scale_ref[:].astype(jnp.float32)).astype(o_ref.dtype)


@functools.partial(jax.custom_vjp, nondiff_argnums=(2,))
def rms_norm(x, scale, eps: float = 1e-6):
    """x (..., D), scale (D,) → same shape as x."""
    return _rms_forward(x, scale, eps)


def uses_kernel(x_shape) -> bool:
    """True when :func:`rms_norm` on an (..., D) input runs the Pallas
    kernel on the current backend, False when it runs the jnp
    reference."""
    d = x_shape[-1]
    rows = math.prod(x_shape[:-1])
    if rows % min(DEFAULT_BLOCK_ROWS, rows):
        return False
    # Sub-tile rows (vs the 128-lane register tiling) stay on the
    # reference path on real hardware; interpret mode has no tiling
    return not (jax.default_backend() == "tpu" and (d < 128 or rows < 8))


def _rms_forward(x, scale, eps):
    if not uses_kernel(x.shape):
        return _reference_rms_norm(x, scale, eps)
    orig_shape = x.shape
    d = orig_shape[-1]
    rows = math.prod(orig_shape[:-1])
    flat = x.reshape(rows, d)
    block = min(DEFAULT_BLOCK_ROWS, rows)

    interpret = jax.default_backend() == "cpu"
    out = pl.pallas_call(
        functools.partial(_rms_kernel, eps=eps),
        grid=(rows // block,),
        in_specs=[
            pl.BlockSpec((block, d), lambda i: (i, 0)),
            pl.BlockSpec((d,), lambda i: (0,)),
        ],
        out_specs=pl.BlockSpec((block, d), lambda i: (i, 0)),
        out_shape=jax.ShapeDtypeStruct((rows, d), x.dtype),
        interpret=interpret,
        name="rms_norm",
    )(flat, scale)
    return out.reshape(orig_shape)


def _rms_fwd(x, scale, eps):
    return _rms_forward(x, scale, eps), (x, scale)


def _rms_bwd(eps, res, g):
    x, scale = res
    _, vjp = jax.vjp(lambda x, s: _reference_rms_norm(x, s, eps), x, scale)
    return vjp(g)


rms_norm.defvjp(_rms_fwd, _rms_bwd)
