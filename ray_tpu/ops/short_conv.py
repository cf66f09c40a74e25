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

`mixer_conv`, below them, is the convolution in FRONT of a recurrent
rule (the state-space, gated-delta and KDA mixers) on the same
machinery: K taps over one stream, an optional bias, SiLU and, for the
leading q and k channels, the unit norm of each head, one pass each way.
"""

from __future__ import annotations

import functools
import math

import jax
import jax.numpy as jnp
from jax import lax
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


def _slabs(d: int, slab: int = SLAB):
    slab = slab if d % slab == 0 else d
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


# --- the convolution in front of a recurrent rule --------------------------

LANES = 128        # a lane tile: what a block and a head are whole numbers of
CBLOCK = 2048      # lanes a grid step of mixer_conv holds at most
CONV_TILE = 256    # positions it holds, and
CONV_SLAB = 128    # lanes of them worked on at once: see `mixer_conv`
UNIT_EPS = 1e-6    # under the root of a head's unit norm
_PARAMS3 = pltpu.CompilerParams(
    dimension_semantics=("parallel", "parallel", "parallel"),
    vmem_limit_bytes=96 * 1024 * 1024)


def mixer_conv_xla(x, taps, bias=None, n_unit: int = 0, head: int = 0):
    """x: [B, T, C] in the compute dtype; taps: [K, C] float32; bias: [C]
    float32 or None; the first `n_unit` channels are q then k, heads of
    `head` channels each -> [B, T, C] in x's dtype. K shifted products,
    float32 sums of the compute dtype's rows (the padded copy stays in
    that dtype), the bias, SiLU; q's and k's heads L2-normalised, q
    times `head ** -0.5`; float32 throughout, cast once. Plain `jnp`,
    left to XLA's fusion."""
    k, (b, t, c) = taps.shape[0], x.shape
    padded = jnp.pad(x, ((0, 0), (k - 1, 0), (0, 0)))
    conv = sum(taps[j] * padded[:, j:j + t].astype(jnp.float32)
               for j in range(k))
    s = jax.nn.silu(conv if bias is None else conv + bias)
    if n_unit:
        z = s[..., :n_unit].reshape(b, t, 2, -1, head)
        z = z * lax.rsqrt((z * z).sum(-1, keepdims=True) + UNIT_EPS)
        z = z * jnp.asarray([head ** -0.5, 1.0], jnp.float32).reshape(2, 1, 1)
        s = jnp.concatenate([z.reshape(b, t, n_unit), s[..., n_unit:]], -1)
    return s.astype(x.dtype)


def _channel_block(c: int, k: int, n_unit: int, head: int) -> int:
    """The lanes a grid step holds, from the shapes: the largest divisor
    of C under CBLOCK that is whole lane tiles and, where heads are
    normalised, whole heads and a divisor of the q half, so that a block
    is q, k or neither (and VMEM does not grow with C). 0 where the shape
    will not tile — C that is no whole lane tiles, a head that is not (or
    none that divides both C and the q half), more history than the halo
    holds: `mixer_conv` is then `mixer_conv_xla`."""
    if c % LANES or k - 1 > HALO or (n_unit and head % LANES):
        return 0
    of, step = (math.gcd(c, n_unit // 2), head) if n_unit else (c, LANES)
    return max((d for d in range(step, min(of, CBLOCK) + 1, step)
                if of % d == 0), default=0)


def _conv(x, x_before, taps_ref, bias_ref, lanes):
    """Sum over j of taps[j] * x_{t - (K - 1) + j}, oldest tap first,
    and the bias where there is one."""
    k, conv = taps_ref.shape[0], None
    for j in range(k):
        back = k - 1 - j
        term = taps_ref[pl.ds(j, 1), lanes] * (
            _shifted(x, x_before, back) if back else x)
        conv = term if conv is None else conv + term
    return conv if bias_ref is None else conv + bias_ref[:, lanes]


def _heads(n: int, head: int):
    return [slice(lo, lo + head) for lo in range(0, n, head)]


def _kinds(unit_blocks: int, blocks: int, body):
    """Run `body(normalised)` for this grid step's channel block: the
    first `unit_blocks` blocks of the channels are normalised."""
    if unit_blocks:
        pl.when(pl.program_id(2) < unit_blocks)(lambda: body(True))
    if unit_blocks < blocks:
        pl.when(pl.program_id(2) >= unit_blocks)(lambda: body(False))


def _mixer_conv_fwd_kernel(*refs, has_bias: bool, head: int,
                           unit_blocks: int, blocks: int, slab: int):
    x_ref, before_ref, taps_ref = refs[:3]
    bias_ref, o_ref = (refs[3] if has_bias else None), refs[-1]
    first = pl.program_id(1) == 0
    f32 = jnp.float32
    q_scale = unit_blocks and jnp.where(
        pl.program_id(2) < unit_blocks // 2, head ** -0.5, 1.0)

    def body(normalised: bool):
        for lanes in _slabs(x_ref.shape[-1], slab):
            x = x_ref[:, lanes].astype(f32)
            x_before = jnp.where(first, 0.0, before_ref[:, lanes].astype(f32))
            s = jax.nn.silu(_conv(x, x_before, taps_ref, bias_ref, lanes))
            if not normalised:
                o_ref[:, lanes] = s.astype(o_ref.dtype)
                continue
            for part in _heads(lanes.size, head):
                z = s[:, part]
                r = lax.rsqrt((z * z).sum(-1, keepdims=True) + UNIT_EPS)
                o_ref[:, pl.ds(lanes.start + part.start, head)] = (
                    z * (r * q_scale)).astype(o_ref.dtype)

    _kinds(unit_blocks, blocks, body)


def _mixer_conv_bwd_kernel(*refs, has_bias: bool, head: int,
                           unit_blocks: int, blocks: int, slab: int):
    x_ref, before_ref, after_ref, dy_ref, dy_after_ref, taps_ref = refs[:6]
    bias_ref = refs[6] if has_bias else None
    dx_ref, dtaps_ref = refs[6 + has_bias:8 + has_bias]
    dbias_ref = refs[-1] if has_bias else None
    first = pl.program_id(1) == 0
    last = pl.program_id(1) == pl.num_programs(1) - 1
    f32 = jnp.float32
    q_scale = unit_blocks and jnp.where(
        pl.program_id(2) < unit_blocks // 2, head ** -0.5, 1.0)

    def body(normalised: bool):
        def dconv(c, dy):
            """The gradient at the convolution's output c of what gave
            dy at the operator's: through the heads' norm, then SiLU."""
            sig = jax.nn.sigmoid(c)
            s = c * sig
            if normalised:
                parts = []
                for part in _heads(c.shape[-1], head):
                    z, dz = s[:, part], dy[:, part]
                    r = lax.rsqrt((z * z).sum(-1, keepdims=True) + UNIT_EPS)
                    along = (dz * z).sum(-1, keepdims=True) * (r * r)
                    parts.append((dz - z * along) * (r * q_scale))
                dy = parts[0] if len(parts) == 1 else jnp.concatenate(
                    parts, axis=-1)
            return dy * (sig + s * (1.0 - sig))

        for lanes in _slabs(x_ref.shape[-1], slab):
            x = x_ref[:, lanes].astype(f32)
            x_before = jnp.where(first, 0.0, before_ref[:, lanes].astype(f32))
            dc = dconv(_conv(x, x_before, taps_ref, bias_ref, lanes),
                       dy_ref[:, lanes].astype(f32))
            # the HALO rows after the tile: their history is the tile's end
            dc_after = jnp.where(last, 0.0, dconv(
                _conv(after_ref[:, lanes].astype(f32), x[-HALO:], taps_ref,
                      bias_ref, lanes),
                dy_after_ref[:, lanes].astype(f32)))
            k, dx = taps_ref.shape[0], None
            for j in range(k):
                back = k - 1 - j
                tap = taps_ref[pl.ds(j, 1), lanes]
                dtaps_ref[pl.ds(j, 1), lanes] = (dc * (
                    _shifted(x, x_before, back) if back else x)).sum(
                        0, keepdims=True)
                term = tap * (_shifted(dc, dc_after, -back) if back else dc)
                dx = term if dx is None else dx + term
            dx_ref[:, lanes] = dx.astype(dx_ref.dtype)
            if has_bias:
                dbias_ref[:, lanes] = dc.sum(0, keepdims=True)

    _kinds(unit_blocks, blocks, body)


def _tile_maps(rows: int, padded: int):
    """Index maps of a grid step (sequence b, tile i, channel block j):
    the tile itself, and the HALO rows before and after it as blocks of
    HALO rows (the first tile's and the last's stay inside the array:
    the kernels put zeros in their place)."""
    per, last = rows // HALO, padded // HALO - 1

    def here(b, i, j):
        return (b, i, j)

    def before(b, i, j):
        return (b, jnp.maximum(i * per - 1, 0), j)

    def after(b, i, j):
        return (b, jnp.minimum((i + 1) * per, last), j)

    return here, before, after


def _mixer_conv_plan(x, taps, n_unit: int, head: int, tile: int):
    """(rows a step holds, T padded, lanes a step holds, the kernels'
    static arguments)."""
    rows, padded = _tiling(x.shape[1], tile)
    k, c = taps.shape
    cb = _channel_block(c, k, n_unit, head)
    return rows, padded, cb, dict(
        head=head, unit_blocks=n_unit // cb, blocks=c // cb,
        slab=math.lcm(CONV_SLAB, head) if n_unit else CONV_SLAB)


# Under a jit of their own: the layers of a stack, and a layer's two
# forwards, share ONE trace and ONE lowering of a kernel (a body unrolled
# over a block's 16 slabs is seconds of Python in the worker otherwise).
_CALL = functools.partial(
    jax.jit, static_argnames=("n_unit", "head", "tile", "interpret"))


@_CALL
def _mixer_conv_fwd_call(x, taps, *bias, n_unit: int, head: int, tile: int,
                         interpret: bool):
    batch, t, c = x.shape
    k = taps.shape[0]
    rows, padded, cb, static = _mixer_conv_plan(x, taps, n_unit, head, tile)
    x = jnp.pad(x, ((0, 0), (0, padded - t), (0, 0)))
    here, before, _ = _tile_maps(rows, padded)
    y = pl.pallas_call(
        functools.partial(_mixer_conv_fwd_kernel, has_bias=bool(bias),
                          **static),
        grid=(batch, padded // rows, c // cb),
        in_specs=[
            pl.BlockSpec((None, rows, cb), here),
            pl.BlockSpec((None, HALO, cb), before),
            pl.BlockSpec((k, cb), lambda b, i, j: (0, j)),
            *[pl.BlockSpec((1, cb), lambda b, i, j: (0, j)) for _ in bias]],
        out_specs=pl.BlockSpec((None, rows, cb), here),
        out_shape=jax.ShapeDtypeStruct((batch, padded, c), x.dtype),
        compiler_params=_PARAMS3, interpret=interpret, name="mixer_conv",
    )(x, x, taps, *[v.reshape(1, c) for v in bias])
    return y[:, :t]


@_CALL
def _mixer_conv_bwd_call(x, dy, taps, *bias, n_unit: int, head: int,
                         tile: int, interpret: bool):
    """-> (dx [B, T, C], the taps' gradient by batch row and tile [B,
    tiles, K, C] float32 and, with a bias, the bias's [B, tiles, C]:
    summed by the caller)."""
    batch, t, c = x.shape
    k = taps.shape[0]
    rows, padded, cb, static = _mixer_conv_plan(x, taps, n_unit, head, tile)
    pad = ((0, 0), (0, padded - t), (0, 0))
    x, dy = jnp.pad(x, pad), jnp.pad(dy, pad)
    tiles = padded // rows
    here, before, after = _tile_maps(rows, padded)

    def by_tile(n):
        return pl.BlockSpec((None, None, n, cb), lambda b, i, j: (b, i, 0, j))

    dx, dtaps, *dbias = pl.pallas_call(
        functools.partial(_mixer_conv_bwd_kernel, has_bias=bool(bias),
                          **static),
        grid=(batch, tiles, c // cb),
        in_specs=[
            pl.BlockSpec((None, rows, cb), here),
            pl.BlockSpec((None, HALO, cb), before),
            pl.BlockSpec((None, HALO, cb), after),
            pl.BlockSpec((None, rows, cb), here),
            pl.BlockSpec((None, HALO, cb), after),
            pl.BlockSpec((k, cb), lambda b, i, j: (0, j)),
            *[pl.BlockSpec((1, cb), lambda b, i, j: (0, j)) for _ in bias]],
        out_specs=[pl.BlockSpec((None, rows, cb), here), by_tile(k),
                   *[by_tile(1) for _ in bias]],
        out_shape=[
            jax.ShapeDtypeStruct((batch, padded, c), x.dtype),
            jax.ShapeDtypeStruct((batch, tiles, k, c), jnp.float32),
            *[jax.ShapeDtypeStruct((batch, tiles, 1, c), jnp.float32)
              for _ in bias]],
        compiler_params=_PARAMS3, interpret=interpret, name="mixer_conv_bwd",
    )(x, x, x, dy, dy, taps, *[v.reshape(1, c) for v in bias])
    return (dx[:, :t], dtaps, *[d[:, :, 0] for d in dbias])


def _present(bias):
    return () if bias is None else (bias,)


@functools.partial(jax.custom_vjp, nondiff_argnums=(3, 4, 5))
def _mixer_conv(x, taps, bias, n_unit, head, tile):
    extra = _present(bias)
    return over_leading_dim(
        functools.partial(_mixer_conv_fwd_call, n_unit=n_unit, head=head,
                          tile=tile, interpret=not is_tpu()),
        (True,) + (False,) * (1 + len(extra)))(x, taps, *extra)


def _mixer_conv_fwd(x, taps, bias, n_unit, head, tile):
    return _mixer_conv(x, taps, bias, n_unit, head, tile), (x, taps, bias)


def _mixer_conv_bwd(n_unit, head, tile, res, dy):
    x, taps, bias = res
    extra = _present(bias)
    dx, dtaps, *dbias = over_leading_dim(
        functools.partial(_mixer_conv_bwd_call, n_unit=n_unit, head=head,
                          tile=tile, interpret=not is_tpu()),
        (True, True) + (False,) * (1 + len(extra)))(x, dy, taps, *extra)
    return (dx, dtaps.sum((0, 1)).astype(taps.dtype),
            dbias[0].sum((0, 1)).astype(bias.dtype) if dbias else None)


_mixer_conv.defvjp(_mixer_conv_fwd, _mixer_conv_bwd)


def mixer_conv(x, taps, bias=None, n_unit: int = 0, head: int = 0,
               tile: int = CONV_TILE):
    """x: [B, T, C] in the compute dtype; taps: [K, C] float32; bias: [C]
    float32 or None; the first `n_unit` channels q then k, heads of
    `head` -> [B, T, C] in x's dtype: `mixer_conv_xla`'s function as two
    Mosaic kernels, `mixer_conv` and `mixer_conv_bwd` (and that plain
    form itself where `_channel_block` finds no block for the shape).
    The backward keeps x, taps and bias, and makes the convolution, SiLU
    and norm again in its tile. Tiles of 256 positions walked 128 lanes
    at a time, smaller than `short_conv`'s: the body's float32
    temporaries are then a slab's 32 vector registers each, and on the
    chip (v5e, bf16, [2, 8192, C], ms a call forward / backward by (tile,
    CBLOCK, slab): C 12 288 with heads normalised 1.73 / 3.39 at (512,
    2048, 512), 1.60 / 3.09 at (512, 2048, 128), 1.54 / 2.83 at (256,
    2048, 128), 1.65 / 2.80 at (128, 2048, 128), 5.4 backward at (1024,
    2048, 512) and (512, 4096, 512); C 6144 with a bias 1.09 / 2.18,
    0.86 / 1.71, 0.72 / 1.44, 0.78 / 1.24: PR 63)."""
    k, c = taps.shape
    if x.shape[-1] != c or (n_unit and (n_unit > c or n_unit % (2 * head))):
        raise ValueError(
            f"mixer_conv: x {x.shape}, taps {taps.shape}, {n_unit} channels "
            f"in heads of {head}: one tap vector a channel; the normalised "
            "channels are q then k, whole heads each")
    if not _channel_block(c, k, n_unit, head):
        return mixer_conv_xla(x, taps, bias, n_unit, head)
    return _mixer_conv(x, taps, bias, n_unit, head, tile)
