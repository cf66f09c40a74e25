"""Fused LayerNorm / RMSNorm Pallas kernels.

One pass through VMEM: moments + normalize + affine in a single kernel so
the activation never round-trips to HBM between the reduction and the
scale (XLA usually fuses this too — the kernel guarantees it and is the
template for fancier fusions like norm+residual+quant).
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

from ray_tpu._private.accelerator import is_tpu
from ray_tpu.ops.partition import over_leading_dim


def _layernorm_kernel(x_ref, w_ref, b_ref, o_ref, *, eps: float):
    x = x_ref[...].astype(jnp.float32)
    mean = x.mean(-1, keepdims=True)
    var = ((x - mean) ** 2).mean(-1, keepdims=True)
    y = (x - mean) * jax.lax.rsqrt(var + eps)
    o_ref[...] = (y * w_ref[...] + b_ref[...]).astype(o_ref.dtype)


def _rmsnorm_kernel(x_ref, w_ref, o_ref, *, eps: float):
    x = x_ref[...].astype(jnp.float32)
    ms = (x * x).mean(-1, keepdims=True)
    y = x * jax.lax.rsqrt(ms + eps)
    o_ref[...] = (y * w_ref[...]).astype(o_ref.dtype)


@functools.partial(jax.custom_vjp, nondiff_argnums=(3,))
def layernorm(x, weight, bias, eps: float = 1e-5):
    """x: [..., D]; weight/bias: [D]. Fused pallas forward; analytic
    backward in plain JAX (XLA fuses it into adjacent matmul epilogues)."""
    return _over_rows(functools.partial(_layernorm_fwd_impl, eps=eps),
                      x, weight, bias)


def _over_rows(fn, x, *params):
    """Rows are independent kernel instances: under a sharded jit each
    device normalises its own slice of x; weight and bias are whole."""
    return over_leading_dim(fn, (True,) + (False,) * len(params))(
        x, *params)


def _layernorm_fwd_impl(x, weight, bias, *, eps: float,
                        block_rows: int = 256):
    orig_shape = x.shape
    d = orig_shape[-1]
    n = 1
    for s in orig_shape[:-1]:
        n *= s
    xf = x.reshape(n, d)
    block = min(block_rows, n)
    if n % block:
        block = n  # fall back to one block
    out = pl.pallas_call(
        functools.partial(_layernorm_kernel, eps=eps),
        grid=(n // block,),
        in_specs=[
            pl.BlockSpec((block, d), lambda i: (i, 0)),
            pl.BlockSpec((d,), lambda i: (0,)),
            pl.BlockSpec((d,), lambda i: (0,)),
        ],
        out_specs=pl.BlockSpec((block, d), lambda i: (i, 0)),
        out_shape=jax.ShapeDtypeStruct((n, d), x.dtype),
        interpret=not is_tpu(),
        name="layernorm",
    )(xf, weight, bias)
    return out.reshape(orig_shape)


def _layernorm_fwd(x, weight, bias, eps):
    return layernorm(x, weight, bias, eps), (x, weight)


def _layernorm_bwd(eps, res, g):
    x, weight = res
    xf = x.astype(jnp.float32)
    gf = g.astype(jnp.float32)
    mean = xf.mean(-1, keepdims=True)
    var = ((xf - mean) ** 2).mean(-1, keepdims=True)
    inv = jax.lax.rsqrt(var + eps)
    xhat = (xf - mean) * inv
    gw = gf * weight.astype(jnp.float32)
    dx = inv * (gw - gw.mean(-1, keepdims=True)
                - xhat * (gw * xhat).mean(-1, keepdims=True))
    red = tuple(range(x.ndim - 1))
    dw = (gf * xhat).sum(red)
    db = gf.sum(red)
    return (dx.astype(x.dtype), dw.astype(weight.dtype),
            db.astype(weight.dtype))


layernorm.defvjp(_layernorm_fwd, _layernorm_bwd)


@functools.partial(jax.custom_vjp, nondiff_argnums=(2,))
def rmsnorm(x, weight, eps: float = 1e-6):
    return _over_rows(functools.partial(_rmsnorm_fwd_impl, eps=eps),
                      x, weight)


def _rmsnorm_fwd_impl(x, weight, *, eps: float, block_rows: int = 256):
    orig_shape = x.shape
    d = orig_shape[-1]
    n = 1
    for s in orig_shape[:-1]:
        n *= s
    xf = x.reshape(n, d)
    block = min(block_rows, n)
    if n % block:
        block = n
    out = pl.pallas_call(
        functools.partial(_rmsnorm_kernel, eps=eps),
        grid=(n // block,),
        in_specs=[
            pl.BlockSpec((block, d), lambda i: (i, 0)),
            pl.BlockSpec((d,), lambda i: (0,)),
        ],
        out_specs=pl.BlockSpec((block, d), lambda i: (i, 0)),
        out_shape=jax.ShapeDtypeStruct((n, d), x.dtype),
        interpret=not is_tpu(),
        name="rmsnorm",
    )(xf, weight)
    return out.reshape(orig_shape)


def _rmsnorm_fwd(x, weight, eps):
    return rmsnorm(x, weight, eps), (x, weight)


def _rmsnorm_bwd(eps, res, g):
    x, weight = res
    xf = x.astype(jnp.float32)
    gf = g.astype(jnp.float32)
    ms = (xf * xf).mean(-1, keepdims=True)
    inv = jax.lax.rsqrt(ms + eps)
    gw = gf * weight.astype(jnp.float32)
    d = x.shape[-1]
    dx = inv * gw - xf * (inv ** 3) * (gw * xf).sum(-1, keepdims=True) / d
    red = tuple(range(x.ndim - 1))
    dw = (gf * xf * inv).sum(red)
    return dx.astype(x.dtype), dw.astype(weight.dtype)


rmsnorm.defvjp(_rmsnorm_fwd, _rmsnorm_bwd)
