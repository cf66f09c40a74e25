"""Grouped matmul over the experts a chip holds, as Pallas TPU kernels.

The rows of `x` are token–expert assignments grouped by expert, every
group padded with zero rows to whole tiles of `tile` rows
(`parallel/moe.py::group_by_expert` lays them out), so a row tile
belongs to ONE expert and the kernel is a plain tiled matmul whose
weight block is picked per tile from a scalar-prefetched table:

    out[r] = x[r] @ w[tile_group[r // tile]]        for r in active tiles

`n_tiles` (a device scalar) says how many tiles hold rows; the static
row count is what the caller hands in — the rung of
`parallel/moe.py::row_ladder` its routing chose: a prefix of the
worst-case layout (every assignment held here) that covers the filled
tiles — and tiles past `n_tiles` are neither fetched, multiplied nor
written: their block index is clamped to the last active tile's and the
body is skipped, so the work follows the rows really routed here. What
such rows of the output hold is undefined; the caller masks by row
validity, and by walking a short rung has few of them.

Three kernels, named so the device trace carries them: `moe_gmm` (the
forward product, and its rematerialised copy), `moe_gmm_dx` (the same
walk against the transposed weight) and `moe_gmm_dw` (per expert
x_g^T dy_g, accumulated in float32 over the expert's tiles). A weight
kept as [G, N, K] (`moe_gmm(..., transposed=True)`) swaps the two walks
under the same names. The shape
follows `jax.experimental.pallas.ops.tpu.megablox`; aligning groups to
tiles is what makes it this short.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from ray_tpu._private.accelerator import is_tpu

_VMEM_LIMIT = 64 * 1024 * 1024


def _divisor(dim: int, prefs: tuple[int, ...]) -> int:
    """The lanes (or rows) of a weight block along `dim`: the first of
    `prefs` that divides it, and where none does — 1856 = 14.5 x 128,
    the first width to come that no lane tile divides — the WHOLE
    dimension as one block, on purpose: a block that spans its array's
    full extent needs no alignment, so no width is padded in HBM and no
    remainder tile is masked; the cost is one block of that many lanes
    in VMEM (at K = 2688 in bf16, double-buffered: 2 x 2688 x 1856 x 2 B
    = 20 MB of the 64 the kernels ask for) and one grid step along it.
    A width that some preference divides keeps the tile it had (1792
    takes 256, 768 takes 384)."""
    return next((p for p in prefs if p <= dim and dim % p == 0), dim)


def _gmm_kernel(group_ref, n_ref, x_ref, w_ref, o_ref, *, transpose_rhs):
    @pl.when(pl.program_id(1) < n_ref[0])
    def _():
        dims = (((1,), (1 if transpose_rhs else 0,)), ((), ()))
        o_ref[...] = jax.lax.dot_general(
            x_ref[...], w_ref[...], dims,
            preferred_element_type=jnp.float32).astype(o_ref.dtype)


def _gmm(x, w, tile_group, n_tiles, *, tile: int, transpose_rhs: bool,
         name: str):
    """x: [R, K]; w: [G, K, N] (or [G, N, K] with `transpose_rhs`) ->
    [R, N]. Grid (N tiles, row tiles): a weight block stays in VMEM over
    the consecutive row tiles of its expert."""
    rows, k = x.shape
    n = w.shape[1] if transpose_rhs else w.shape[2]
    tn = _divisor(n, (512, 384, 256, 128))

    def tile_of(i, n_ref):
        return jnp.minimum(i, n_ref[0] - 1)

    if transpose_rhs:
        w_spec = pl.BlockSpec(
            (None, tn, k),
            lambda j, i, g, nt: (g[tile_of(i, nt)], j, 0))
    else:
        w_spec = pl.BlockSpec(
            (None, k, tn),
            lambda j, i, g, nt: (g[tile_of(i, nt)], 0, j))
    return pl.pallas_call(
        functools.partial(_gmm_kernel, transpose_rhs=transpose_rhs),
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=2,
            grid=(n // tn, rows // tile),
            in_specs=[
                pl.BlockSpec((tile, k),
                             lambda j, i, g, nt: (tile_of(i, nt), 0)),
                w_spec],
            out_specs=pl.BlockSpec(
                (tile, tn), lambda j, i, g, nt: (tile_of(i, nt), j))),
        out_shape=jax.ShapeDtypeStruct((rows, n), x.dtype),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "arbitrary"),
            vmem_limit_bytes=_VMEM_LIMIT),
        interpret=not is_tpu(),
        name=name,
    )(tile_group, n_tiles, x, w)


def _dw_kernel(group_ref, n_ref, x_ref, dy_ref, o_ref):
    i = pl.program_id(2)

    @pl.when(i < n_ref[0])
    def _():
        # the first tile of an expert clears its block; every expert has
        # at least one tile, so every block is written
        @pl.when((i == 0) | (group_ref[i] != group_ref[jnp.maximum(i, 1) - 1]))
        def _():
            o_ref[...] = jnp.zeros_like(o_ref)

        o_ref[...] += jax.lax.dot_general(
            x_ref[...], dy_ref[...], (((0,), (0,)), ((), ())),
            preferred_element_type=jnp.float32)


def _gmm_dw(x, dy, tile_group, n_tiles, *, tile: int, groups: int):
    """x: [R, K], dy: [R, N] -> float32 [G, K, N]: per expert x_g^T dy_g.
    The row tiles are the innermost grid axis, so an expert's output
    block stays in VMEM while its tiles accumulate into it."""
    rows, k = x.shape
    n = dy.shape[1]
    tk = _divisor(k, (1280, 1024, 768, 512, 384, 256, 128))
    tn = _divisor(n, (512, 384, 256, 128))

    def tile_of(i, n_ref):
        return jnp.minimum(i, n_ref[0] - 1)

    return pl.pallas_call(
        _dw_kernel,
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=2,
            grid=(k // tk, n // tn, rows // tile),
            in_specs=[
                pl.BlockSpec((tile, tk),
                             lambda a, b, i, g, nt: (tile_of(i, nt), a)),
                pl.BlockSpec((tile, tn),
                             lambda a, b, i, g, nt: (tile_of(i, nt), b))],
            out_specs=pl.BlockSpec(
                (None, tk, tn),
                lambda a, b, i, g, nt: (g[tile_of(i, nt)], a, b))),
        out_shape=jax.ShapeDtypeStruct((groups, k, n), jnp.float32),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "arbitrary"),
            vmem_limit_bytes=_VMEM_LIMIT),
        interpret=not is_tpu(),
        name="moe_gmm_dw",
    )(tile_group, n_tiles, x, dy)


@functools.partial(jax.custom_vjp, nondiff_argnums=(4, 5))
def moe_gmm(x, w, tile_group, n_tiles, tile: int, transposed: bool = False):
    """x: [R, K] rows grouped by expert in tiles of `tile`; w: [G, K, N],
    or with `transposed` [G, N, K]: an expert's rows are its OUTPUTS
    (how `parallel/moe.py::dropless_moe` keeps an ungated expert's
    `w_up`, so that the model width is the minor dimension of both its
    matrices). Nothing is transposed in HBM: the forward walks such a
    weight as `moe_gmm_dx` walks the other form, and the reverse;
    `moe_gmm_dw` takes its two operands in the other order. tile_group:
    int32 [R // tile], the expert of each tile; n_tiles: int32 [1], the
    tiles that hold rows. Returns [R, N] in x's dtype; rows of tiles
    past `n_tiles` are undefined."""
    return _gmm(x, w, tile_group, n_tiles, tile=tile,
                transpose_rhs=transposed, name="moe_gmm")


def _moe_gmm_fwd(x, w, tile_group, n_tiles, tile, transposed):
    return moe_gmm(x, w, tile_group, n_tiles, tile, transposed), (
        x, w, tile_group, n_tiles)


def _moe_gmm_bwd(tile, transposed, res, dy):
    x, w, tile_group, n_tiles = res
    dx = _gmm(dy, w, tile_group, n_tiles, tile=tile,
              transpose_rhs=not transposed, name="moe_gmm_dx")
    pair = (dy, x) if transposed else (x, dy)
    dw = _gmm_dw(*pair, tile_group, n_tiles, tile=tile, groups=w.shape[0])
    return dx, dw.astype(w.dtype), None, None


moe_gmm.defvjp(_moe_gmm_fwd, _moe_gmm_bwd)
