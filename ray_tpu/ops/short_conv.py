"""The gated short convolution of a conv mixer, as one operator.

From the layer's input projection `bcx = u W_in`, `[B, T, 3 D]`, split
in three along the features as `[B | C | x]`:

    z = B * x
    c_t = sum_j taps[j] * z_{t - (K - 1) + j}     z before position 0 is 0
    y = C * c

a depthwise, causal convolution of K taps (one tap vector of D a
position, no bias) between two gates. A handful of operations an
element over three streams in and one out: bound by memory bandwidth.

Two forms of the same function. `short_conv_xla` is the plain `jnp`
form, left to XLA's fusion (and what the kernels are tested against).
`short_conv` has a `custom_vjp` over two Mosaic kernels, named so the
device trace carries them: `short_conv` (the forward pass, and its
rematerialised copy) and `short_conv_bwd`. Both read the product once,
in tiles of `tile` positions over all features; a tile brings the
`K - 1` rows of history it needs as a second, 16-row block of the same
array (the backward also the 16 rows after it: the gradient of z runs
against time), and walks the features in slabs of `SLAB` lanes so that
its float32 temporaries stay small. All arithmetic is float32; the
result is cast once. The kernels are independent over the batch: under
a sharded jit each device runs them on its own rows
(`ops/partition.py`).
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from ray_tpu._private.accelerator import is_tpu
from ray_tpu.ops.partition import over_leading_dim

HALO = 16          # rows of the history block: bf16's sublane tile
TILE = 512         # positions a grid step holds
SLAB = 512         # lanes of a tile worked on at once
_PARAMS = pltpu.CompilerParams(
    dimension_semantics=("parallel", "parallel"),
    vmem_limit_bytes=96 * 1024 * 1024)


def short_conv_xla(bcx, taps):
    """bcx: [B, T, 3 D]; taps: [K, D] float32 -> y [B, T, D] in bcx's
    dtype. The convolution as K shifted products."""
    k, d = taps.shape
    t = bcx.shape[1]
    f32 = functools.partial(jnp.asarray, dtype=jnp.float32)
    b, c, x = (f32(bcx[..., i * d:(i + 1) * d]) for i in range(3))
    z = jnp.pad(b * x, ((0, 0), (k - 1, 0), (0, 0)))
    conv = sum(f32(taps[j]) * z[:, j:j + t] for j in range(k))
    return (c * conv).astype(bcx.dtype)


def _shifted(tile, edge, shift: int):
    """Row r of the result is row r - shift of `tile` ([tile rows, n]),
    rows before the tile's first taken from the END of `edge` ([HALO,
    n]); `shift` < 0 looks ahead, rows past the last taken from the
    START of `edge`."""
    rows = tile.shape[0]
    if shift > 0:
        return pltpu.roll(jnp.concatenate([edge, tile], axis=0),
                          shift, 0)[HALO:]
    return pltpu.roll(jnp.concatenate([tile, edge], axis=0),
                      rows + HALO + shift, 0)[:rows]


def _slabs(d: int):
    slab = SLAB if d % SLAB == 0 else d
    return [pl.ds(lo, slab) for lo in range(0, d, slab)]


def _fwd_kernel(bcx_ref, before_ref, taps_ref, o_ref, *, k: int, d: int):
    first = pl.program_id(1) == 0
    f32 = jnp.float32
    for lanes in _slabs(d):
        def part(ref, i, lanes=lanes):
            return ref[:, pl.ds(i * d + lanes.start, lanes.size)].astype(f32)

        z = part(bcx_ref, 0) * part(bcx_ref, 2)
        z_before = jnp.where(first, 0.0,
                             part(before_ref, 0) * part(before_ref, 2))
        conv = taps_ref[pl.ds(k - 1, 1), lanes] * z
        for shift in range(1, k):
            conv += taps_ref[pl.ds(k - 1 - shift, 1), lanes] * _shifted(
                z, z_before, shift)
        o_ref[:, lanes] = (part(bcx_ref, 1) * conv).astype(o_ref.dtype)


def _bwd_kernel(bcx_ref, before_ref, after_ref, dy_ref, dy_after_ref,
                taps_ref, dbcx_ref, dtaps_ref, *, k: int, d: int):
    first = pl.program_id(1) == 0
    last = pl.program_id(1) == pl.num_programs(1) - 1
    f32 = jnp.float32
    for lanes in _slabs(d):
        def part(ref, i, lanes=lanes):
            return ref[:, pl.ds(i * d + lanes.start, lanes.size)].astype(f32)

        def put(i, value, lanes=lanes):
            dbcx_ref[:, pl.ds(i * d + lanes.start, lanes.size)] = \
                value.astype(dbcx_ref.dtype)

        b, c, x = (part(bcx_ref, i) for i in range(3))
        dy = dy_ref[:, lanes].astype(f32)
        z = b * x
        z_before = jnp.where(first, 0.0,
                             part(before_ref, 0) * part(before_ref, 2))
        dconv = dy * c
        dconv_after = jnp.where(
            last, 0.0, dy_after_ref[:, lanes].astype(f32) * part(after_ref, 1))
        tap = taps_ref[pl.ds(k - 1, 1), lanes]
        conv, dz = tap * z, tap * dconv
        dtaps_ref[pl.ds(k - 1, 1), lanes] = (dconv * z).sum(0, keepdims=True)
        for shift in range(1, k):
            tap = taps_ref[pl.ds(k - 1 - shift, 1), lanes]
            z_back = _shifted(z, z_before, shift)
            conv += tap * z_back
            dz += tap * _shifted(dconv, dconv_after, -shift)
            dtaps_ref[pl.ds(k - 1 - shift, 1), lanes] = (
                dconv * z_back).sum(0, keepdims=True)
        put(0, dz * x)
        put(1, dy * conv)
        put(2, dz * b)


def _tiling(t: int, tile: int):
    """(rows a grid step holds, T padded to whole steps)."""
    rows = min(tile, -(-t // HALO) * HALO)
    return rows, -(-t // rows) * rows


def _fwd_call(bcx, taps, *, tile: int):
    batch, t, _ = bcx.shape
    k, d = taps.shape
    rows, padded = _tiling(t, tile)
    bcx = jnp.pad(bcx, ((0, 0), (0, padded - t), (0, 0)))
    per = rows // HALO
    y = pl.pallas_call(
        functools.partial(_fwd_kernel, k=k, d=d),
        grid=(batch, padded // rows),
        in_specs=[
            pl.BlockSpec((None, rows, 3 * d), lambda b, i: (b, i, 0)),
            pl.BlockSpec((None, HALO, 3 * d),
                         lambda b, i: (b, jnp.maximum(i * per - 1, 0), 0)),
            pl.BlockSpec((k, d), lambda b, i: (0, 0))],
        out_specs=pl.BlockSpec((None, rows, d), lambda b, i: (b, i, 0)),
        out_shape=jax.ShapeDtypeStruct((batch, padded, d), bcx.dtype),
        compiler_params=_PARAMS, interpret=not is_tpu(),
        name="short_conv",
    )(bcx, bcx, taps)
    return y[:, :t]


def _bwd_call(bcx, taps, dy, *, tile: int):
    """-> (dbcx [B, T, 3 D], the taps' gradient by batch row and tile
    [B, tiles, K, D] float32: summed by the caller)."""
    batch, t, _ = bcx.shape
    k, d = taps.shape
    rows, padded = _tiling(t, tile)
    pad = ((0, 0), (0, padded - t), (0, 0))
    bcx, dy = jnp.pad(bcx, pad), jnp.pad(dy, pad)
    per, tiles = rows // HALO, padded // rows

    def before(b, i):
        return (b, jnp.maximum(i * per - 1, 0), 0)

    def after(b, i):
        return (b, jnp.minimum((i + 1) * per, tiles * per - 1), 0)

    dbcx, dtaps = pl.pallas_call(
        functools.partial(_bwd_kernel, k=k, d=d),
        grid=(batch, tiles),
        in_specs=[
            pl.BlockSpec((None, rows, 3 * d), lambda b, i: (b, i, 0)),
            pl.BlockSpec((None, HALO, 3 * d), before),
            pl.BlockSpec((None, HALO, 3 * d), after),
            pl.BlockSpec((None, rows, d), lambda b, i: (b, i, 0)),
            pl.BlockSpec((None, HALO, d), after),
            pl.BlockSpec((k, d), lambda b, i: (0, 0))],
        out_specs=[
            pl.BlockSpec((None, rows, 3 * d), lambda b, i: (b, i, 0)),
            pl.BlockSpec((None, None, k, d), lambda b, i: (b, i, 0, 0))],
        out_shape=[
            jax.ShapeDtypeStruct((batch, padded, 3 * d), bcx.dtype),
            jax.ShapeDtypeStruct((batch, tiles, k, d), jnp.float32)],
        compiler_params=_PARAMS, interpret=not is_tpu(),
        name="short_conv_bwd",
    )(bcx, bcx, bcx, dy, dy, taps)
    return dbcx[:, :t], dtaps


@functools.partial(jax.custom_vjp, nondiff_argnums=(2,))
def short_conv(bcx, taps, tile: int = TILE):
    """bcx: [B, T, 3 D] (`[B | C | x]` along the features); taps: [K, D]
    float32, K <= 17 -> y [B, T, D] in bcx's dtype."""
    return over_leading_dim(functools.partial(_fwd_call, tile=tile),
                            (True, False))(bcx, taps)


def _short_conv_fwd(bcx, taps, tile):
    return short_conv(bcx, taps, tile), (bcx, taps)


def _short_conv_bwd(tile, res, dy):
    bcx, taps = res
    dbcx, dtaps = over_leading_dim(
        functools.partial(_bwd_call, tile=tile),
        (True, False, True))(bcx, taps, dy)
    return dbcx, dtaps.sum((0, 1)).astype(taps.dtype)


short_conv.defvjp(_short_conv_fwd, _short_conv_bwd)
