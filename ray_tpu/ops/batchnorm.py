"""Fused batchnorm backward as a Pallas TPU kernel.

PERF.md's profile shows BN backward is the bandwidth tax on the ResNet
headline bench: its two per-channel reductions (Σdy and Σdy·x̂) re-read
every activation, and XLA fuses them into the dW-conv fusions where they
compete for the same HBM streams. This kernel computes BOTH reductions in
ONE pass over (x, dy) tiles — each bf16 tile is read once into VMEM and
feeds both fp32 accumulators — so the backward costs exactly one extra
read of x and dy beyond the unavoidable dx write. The dx elementwise that
follows is left in plain JAX on purpose: it is a pure map, so XLA fuses
it with the neighboring conv backward exactly like the baseline.

Semantically identical to the XLA path in models/resnet.py `_bn` (same
one-pass E[x²]−E[x]² variance with the same clamp), selected by
`ResNetConfig(bn_mode="pallas")`.

Reference analog: the reference trains ResNet through cuDNN's fused
batchnorm backward (torch BatchNorm2d → cudnnBatchNormalizationBackward);
this is the TPU-native equivalent of that single-pass reduction fusion.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax import lax
from jax.experimental import pallas as pl

from ray_tpu._private.accelerator import is_tpu


def _sums_kernel(x_ref, dy_ref, mean_ref, inv_ref, sdy_ref, sdyx_ref):
    """Grid (C_blocks, M_blocks), M innermost (sequential on TPU): each
    step streams one [bm, bc] tile of x and dy through VMEM and folds both
    per-channel partial sums into the [1, bc] fp32 accumulators."""
    mi = pl.program_id(1)
    x = x_ref[...].astype(jnp.float32)
    dy = dy_ref[...].astype(jnp.float32)
    xhat = (x - mean_ref[...]) * inv_ref[...]
    p_sdy = dy.sum(axis=0, keepdims=True)
    p_sdyx = (dy * xhat).sum(axis=0, keepdims=True)

    @pl.when(mi == 0)
    def _init():
        sdy_ref[...] = p_sdy
        sdyx_ref[...] = p_sdyx

    @pl.when(mi != 0)
    def _acc():
        sdy_ref[...] += p_sdy
        sdyx_ref[...] += p_sdyx


def _pick_block_m(m: int) -> int | None:
    for bm in (1024, 512, 256, 128, 64, 32, 16, 8):
        if m % bm == 0:
            return bm
    return None


def _bn_bwd_sums(x2, dy2, mean, inv, *, interpret: bool):
    """x2, dy2: [M, C]. Returns (Σdy, Σdy·x̂): two [C] fp32 vectors in one
    HBM pass. Falls back to XLA reductions when M isn't 8-tileable."""
    m, c = x2.shape
    bm = _pick_block_m(m)
    bc = c if c < 128 else 128
    if bm is None or c % bc:
        xf = x2.astype(jnp.float32)
        dyf = dy2.astype(jnp.float32)
        xhat = (xf - mean) * inv
        return dyf.sum(0), (dyf * xhat).sum(0)
    kernel = _sums_kernel
    sdy, sdyx = pl.pallas_call(
        kernel,
        grid=(c // bc, m // bm),
        in_specs=[
            pl.BlockSpec((bm, bc), lambda ci, mi: (mi, ci)),
            pl.BlockSpec((bm, bc), lambda ci, mi: (mi, ci)),
            pl.BlockSpec((1, bc), lambda ci, mi: (0, ci)),
            pl.BlockSpec((1, bc), lambda ci, mi: (0, ci)),
        ],
        out_specs=[
            pl.BlockSpec((1, bc), lambda ci, mi: (0, ci)),
            pl.BlockSpec((1, bc), lambda ci, mi: (0, ci)),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((1, c), jnp.float32),
            jax.ShapeDtypeStruct((1, c), jnp.float32),
        ],
        interpret=interpret,
    )(x2, dy2, mean.reshape(1, c), inv.reshape(1, c))
    return sdy[0], sdyx[0]


@functools.partial(jax.custom_vjp, nondiff_argnums=(3,))
def bn_train(x, scale, bias, eps: float = 1e-5):
    """Training-mode batchnorm over NHW: x [N,H,W,C] (any float dtype),
    scale/bias [C] fp32 → (y [N,H,W,C] x.dtype, mean [C] f32, var [C] f32).

    mean/var are auxiliary outputs for the running-stats update — they
    carry no gradient (the caller feeds them into non-differentiated
    state). Forward math matches models/resnet.py `_bn` exactly; backward
    runs the Pallas one-pass dual reduction.
    """
    y, mean, var, _ = _bn_fwd_math(x, scale, bias, eps)
    return y, mean, var


def _bn_fwd_math(x, scale, bias, eps):
    xf = x.astype(jnp.float32)
    mean = jnp.mean(xf, axis=(0, 1, 2))
    # clamp: one-pass E[x²]−E[x]² can dip negative from fp32 rounding
    var = jnp.maximum(
        jnp.mean(jnp.square(xf), axis=(0, 1, 2)) - jnp.square(mean), 0.0)
    inv = lax.rsqrt(var + eps)
    a = inv * scale
    offset = bias - mean * a
    y = x * a.astype(x.dtype) + offset.astype(x.dtype)
    return y, mean, var, inv


def _bn_train_fwd(x, scale, bias, eps):
    y, mean, var, inv = _bn_fwd_math(x, scale, bias, eps)
    return (y, mean, var), (x, mean, inv, scale)


def _bn_train_bwd(eps, residuals, cotangents):
    x, mean, inv, scale = residuals
    dy, _g_mean, _g_var = cotangents  # mean/var are aux state: no grad
    n, h, w, c = x.shape
    m = n * h * w
    x2 = x.reshape(m, c)
    dy2 = dy.reshape(m, c)
    sdy, sdyx = _bn_bwd_sums(x2, dy2, mean, inv, interpret=not is_tpu())
    # dx = inv·scale · (dy − Σdy/M − x̂ · Σ(dy·x̂)/M); pure map, so XLA
    # fuses it into the adjacent conv backward like the baseline BN did
    a = (inv * scale).astype(x.dtype)
    k1 = (sdy / m).astype(x.dtype)
    k2 = (sdyx / m * inv).astype(x.dtype)  # folds x̂ = (x−mean)·inv
    mu = mean.astype(x.dtype)
    dx = a * (dy - k1 - (x - mu) * k2)
    return dx.astype(x.dtype), sdyx, sdy


bn_train.defvjp(_bn_train_fwd, _bn_train_bwd)
