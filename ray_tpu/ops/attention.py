"""Flash attention as a Pallas TPU kernel.

Blockwise streaming-softmax attention: Q blocks stream through VMEM, K/V
are scanned in blocks, the MXU does the two matmuls per block, and the
running (max, denom) accumulators live in f32 — the standard flash
schedule, written for the TPU memory hierarchy (HBM→VMEM via BlockSpecs).

The forward's tile comes from the call's shape, as the backward's does:
`flash_attention(..., block_q=None, block_k=None)` asks `fwd_tiles`, whose
rule (`_fwd_tiles`: 512 x 512 wherever 512 divides T, then 768, 256, 128;
all of T below a tile) was read on the chip at the shapes the GPT-2 cells
run — the table is in its docstring, the readings in PERF.md — and a
caller that passes numbers keeps them (`models/decoder.py` does). Which
shapes reach the kernel is not the rule's to change: those that 128 x 128
tiles took (T a multiple of 128, or of 8 below 128; head size a multiple
of 8).

The backward is a kernel too, ONE for every call the forward kernel
takes, `flash_bwd_fused`: the forward under a gradient also writes the row
log-sum-exp (float32, one number a query row and head), the residuals
are (q, k, v, o, lse), and one pass over the score tiles inside the mask
rebuilds p = exp(s - lse) in VMEM and accumulates dq, dk and dv
in float32 — no score tile, no float32 dk/dv carry ever reaches HBM, and
the blocks the mask empties are skipped. MXU operands are in the
inputs' dtype (p and ds cast to it, as the forward casts p); scores, lse,
delta, ds and the accumulators float32. Its tile is `_bwd_tiles`' (from
T, head size and dtype; the table is in its docstring). Sequences no tile
divides (T % 8) take one checkpointed dense block, forward and backward.
On CPU (tests) the kernels run in interpret mode.

Under a mask the forward's K loop is runs of key blocks (`_key_runs`,
the one statement of the walk, traced in the kernel and numpy for what
is static of a shape): the blocks every entry of which the mask keeps
run with no mask, the blocks it cuts masked, in the order of the keys —
(the window's far edge) -> whole -> diagonal under the causal mask;
whole -> cut, then the noised blocks, under the block-diffusion mask.
What is static of a run over ALL query blocks chooses its form: a run
no query block has is not traced; a run every query block has a block
of gives its last block to straight-line code after the loop; a run
that can hold several blocks takes `_BLOCKS_AN_ITERATION` an iteration.
The same products in the same order with the same float32 sums: every
output and log-sum-exp is the one masked loop's, bit for bit (read on
the chip at eleven shapes, PERF.md section 6, PR 60; `_fwd_tiles` has
the table). The backward's Q loop is one masked loop still: cut the
same way it gained 1.2-1.6 %.

Two things beyond the plain causal kernel, both off by default and both
static at trace time: a sliding `window` (query i sees keys j with
0 <= i - j < window; the forward's K loop starts at the first block the
window reaches, the backward's Q loop ends at the last block that still
reaches the key block, and the tile's mask gains the second bound), and
grouped-query heads (k and v with fewer heads than q: query head g reads
key/value head g // (H // H_kv), chosen in the BlockSpec index map, so
no repeated K/V is ever written to HBM; the backward's grid gains a group
axis between heads and key blocks, over which dk and dv of a key/value
head are summed in float32 VMEM scratch of a whole sequence and written
once). With `window=None` and equal head counts every such branch folds
away: the traced program, forward and backward, is the one this file
built before either existed.

A third, static in the shapes: q and k of one width and v of another
(`[B, T, H, D_qk]`, `[B, T, H, D_v]` -> `[B, T, H, D_v]`; latent
attention as trained has 192-wide queries and keys, a 128-wide part of
which carries no position, and 128-wide values). The score products
contract D_qk, the value product and o are D_v wide; dq (accumulated
transposed, `[D_qk, block_q]` slabs) and dk are D_qk wide, dv D_v; no
v, o, do or dv padded to D_qk is ever written to HBM (192 is no multiple
of the 128 lanes: a 192-wide block sits on 256 lanes in VMEM, which is
why the forward asks for its own VMEM limit there). The kernels keep
their names, `flash_fwd` and `flash_bwd_fused`. With D_qk == D_v every
such branch folds away too.

A fourth, static at trace time and off by default: the block-diffusion
mask (`diffusion=b`; BD3-LM's training pass, arXiv:2503.09573). The T
rows are a sequence of L = T / 2 positions twice over, `[clean 0..L) ;
noised 0..L)`, cut into blocks of b positions. Its rule: a clean query
at position i sees the clean keys j with j // b <= i // b; a noised
query at i sees the clean keys with j // b < i // b and the noised keys
with j // b == i // b; a clean query sees no noised key. `_diffusion_
reach` says it once, for a scalar, a column of a tile or a whole plane:
where the clean keys a row sees end, and where its b noised keys start.
The loop bounds it moves: the forward's K loop becomes two, over the
clean key blocks before the tile's last row's reach and over the noised
key blocks its rows' own blocks touch (none for a clean tile); the
backward's Q loop becomes two, over the clean query blocks from the
key block's first block on and over the noised query blocks that see
it (later blocks' for a clean key block, its own blocks' for a noised
one). A quarter of the plane and its diagonal are walked where causal
walks a half (`diffusion_tiles` counts them from the same bounds), the
grouped heads share K and V as they do without it, and the kernels keep
their names. A tile must lie in one half (L a multiple of both tile
sides) and L be whole blocks; else the dense fallback, which builds the
same mask from the same function. With `diffusion=None` every such
branch folds away: the traced program is the one this file built
before.

A sixth, off by default: a mask that is DATA (`selected=(plane,
tile_counts)`; learned sparse attention, `ops/sparse_index.py`). The
plane `[B, T, T]` int8 says which keys at or before it a query of a
batch row sees, the same for every head; it is cut into tiles outside
the kernels (a query block's row of `[block_q, block_k]` tiles forward,
a key block's column of `[block_k, block_q]` tiles — the keys along a
tile's rows, as the backward has its scores — backward: two int8
transposes in XLA, so no kernel slices a plane along its lanes). Both
kernels keep the causal walk, mask a tile by `where(plane > 0, s,
NEG_INF)` and, by a count a tile prefetched as scalars, SKIP a tile
that holds no selected pair; a row's first selected score rescales
whatever its empty tiles left behind by exp(NEG_INF - s) = 0.0, and
every row selects a key. The kernels keep their names, grouped heads
share K, V and the plane's tile, and the call also returns the rows'
log-sum-exp over the selection (the indexer's loss rebuilds the
probabilities from it). The forward keeps the runs' loop forms (the
diagonal run's last block straight-line, three blocks an iteration
before it) with the plane's tile on every block of both runs. A shape
no tile divides takes the dense form over the same plane. With
`selected=None` every such branch folds away, in both kernels and both
calls: the traced program is the one this file built before.
"""

from __future__ import annotations

import functools
import logging

import jax
import jax.numpy as jnp
from jax.ad_checkpoint import checkpoint_name
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

import numpy as np

from ray_tpu._private.accelerator import is_tpu
from ray_tpu.ops.partition import over_leading_dim

logger = logging.getLogger(__name__)

NEG_INF = -1e30
# What `flash_bwd_fused` may hold in VMEM: q and do of a whole sequence
# (twice: double-buffered), dq.T in float32, the float32 tiles and,
# under grouped heads, dk and dv of a whole sequence in float32.
_BWD_VMEM_LIMIT = 64 * 1024 * 1024
# ... and the forward under two widths: k and v of a whole sequence
_FWD_WIDE_VMEM_LIMIT = 48 * 1024 * 1024
# ... what k and v of a whole sequence, double-buffered, may take of the
# compiler's default scope (16 MiB) beside q, o and the float32 tiles
_FWD_KV_DEFAULT_SCOPE = 12 * 1024 * 1024
# The names the forward under a gradient gives the two residuals its
# kernel produced: the output as the caller sees it ([B, T, H, D_v]) and
# the row log-sum-exp as `_bwd` reads it ([B, H, T] float32). A block
# rematerialised under `save_only_these_names(*SAVED_ACROSS_REMAT)` keeps
# both and runs `flash_fwd` once a step (`models/transformer.py` does);
# with no policy asking for them the names lower to nothing.
SAVED_ACROSS_REMAT = ("flash_attention_out", "flash_attention_lse")
# How many key blocks one iteration of a run's loop takes where a query
# block can have that many (read on the chip, `_fwd_tiles`: 2, 3 and 4
# lie within 2.5 % of each other and 3 is first at six shapes of eight)
_BLOCKS_AN_ITERATION = 3


def _diffusion_reach(row, half: int, block: int, where=jnp.where):
    """The block-diffusion mask, said once. For query rows `row` (a
    scalar, a column or row of a tile, a whole `arange`: any integers)
    of a `[clean ; noised]` sequence of `half` positions each, in blocks
    of `block`: (where the clean keys a row sees end, the first of the
    `block` noised keys it sees). A clean query at position i sees the
    clean keys before its block's end and no noised key (the empty
    range `[-block, 0)`); a noised one the clean keys before its
    block's START and its own block of the noised half. So key row j is
    seen where `j < end or start <= j < start + block`."""
    noised = row >= half
    position = row - where(noised, half, 0)
    first = position - position % block      # its block's first position
    return (where(noised, first, first + block),
            where(noised, half + first, -block))


def _diffusion_keep(key_row, reach, block: int):
    """A tile's share of the mask: `key_row` the keys' rows, `reach`
    `_diffusion_reach` of the queries' rows, shaped to broadcast."""
    end, start = reach
    return (key_row < end) | ((key_row >= start) & (key_row < start + block))


def _diffusion_key_blocks(qi, block_q: int, block_k: int, half: int,
                          block: int, where=jnp.where):
    """The key blocks a query block's rows see part of: `[0, clean)`
    and `[first, last)` of the noised half (empty for a clean query
    block). The last row reaches furthest into the clean half; the
    first and the last row's own blocks bound the noised keys. Ahead of
    the three, `whole`: the clean key blocks `[0, whole)` end at or
    before what the block's FIRST row reaches, so every row sees every
    key of them; `[whole, clean)` and the noised ones are cut by the
    mask."""
    reach, low = _diffusion_reach(qi * block_q, half, block, where)
    end, high = _diffusion_reach((qi + 1) * block_q - 1, half, block, where)
    noised = qi * block_q >= half
    return (reach // block_k,
            (end + block_k - 1) // block_k,
            where(noised, low // block_k, 0),
            where(noised, (high + block + block_k - 1) // block_k, 0))


def _diffusion_query_blocks(ki, block_q: int, block_k: int, half: int,
                            block: int, where=jnp.where):
    """The query blocks that see part of a key block: `[first, half /
    block_q)` of the clean queries (none for a noised key block) and
    `[low, high)` of the noised ones. A clean key is seen by the clean
    queries from its block's first position on and by the noised
    queries from the NEXT block's first position on; a noised key by
    its own block's noised queries."""
    noised = ki * block_k >= half
    j0 = ki * block_k - where(noised, half, 0)   # the first key's position
    j1 = j0 + block_k - 1                        # ... and the last one's
    first = j0 - j0 % block          # the first key's block's first position
    end = j1 - j1 % block + block    # the last key's block's end
    return (where(noised, half, first) // block_q,
            (half + where(noised, first, first + block)) // block_q,
            where(noised, half + end + block_q - 1, 2 * half) // block_q)


def _causal_key_blocks(qi, block_q: int, block_k: int, num_k: int,
                       window: int | None, minimum=jnp.minimum,
                       maximum=jnp.maximum):
    """(first, whole, diagonal, end) of the key blocks the forward's K
    loop walks for query block `qi` under the causal mask and an
    optional window: `[first, end)` is the walk, and `[whole, diagonal)`
    of it the blocks every entry of which the mask keeps; `[first,
    whole)` is cut by the window's far edge (empty without one),
    `[diagonal, end)` by the diagonal. `qi` a traced scalar in the
    kernel, a numpy array in `_key_runs`' other callers."""
    # only scan K blocks at or before this Q block
    if block_q % block_k:
        # the block holding this Q block's last row, exactly
        last = ((qi + 1) * block_q + block_k - 1) // block_k
    else:   # the expression this kernel always had, text for text
        last = (qi + 1) * block_q // block_k + (block_q % block_k != 0)
    num_k_active = minimum(num_k, last)
    # the blocks whose last key is at or before the block's FIRST row
    # lie wholly under the diagonal
    diagonal = (qi * block_q + 1) // block_k
    if window is None:
        return 0, 0, diagonal, num_k_active
    # ... and, under a window, at or after the first block the
    # block's first query still reaches. A row whose keys all lie in
    # later blocks leaves that block with m = NEG_INF and l = the
    # block's width (exp(0) a masked score); its first real score
    # rescales both by exp(NEG_INF - s) = 0.0 exactly, and every row
    # has one (its own key), so o and m + log(l) are exact at the end
    first = maximum(0, qi * block_q - (window - 1)) // block_k
    # a block is wholly inside the window when its first key is within
    # the window of the block's LAST row (a window narrower than the two
    # tile sides leaves none: `whole` stops at `diagonal`)
    whole = minimum(diagonal, (maximum(
        0, (qi + 1) * block_q - window) + block_k - 1) // block_k)
    return first, whole, diagonal, num_k_active


def _key_runs(qi, t: int, block_q: int, block_k: int, window: int | None,
              diffusion: int | None, xp=jnp):
    """The forward's walk for query block `qi` under a mask, said once:
    runs `(start, stop, masked)` of key blocks in the order they are
    walked. A run with `masked` False holds only blocks every entry of
    which the mask keeps; a masked run's blocks are cut by it. `qi` and
    `xp`: a traced scalar and `jnp` in the kernel; `np.arange` of the
    query blocks and `np` for what is static of a shape (`forward_tiles`,
    and the kernel's own choice of each run's form)."""
    if diffusion is not None:
        whole, clean, first, last = _diffusion_key_blocks(
            qi, block_q, block_k, t // 2, diffusion, xp.where)
        return ((0, whole, False), (whole, clean, True),
                (first, last, True))
    first, whole, diagonal, end = _causal_key_blocks(
        qi, block_q, block_k, t // block_k, window, xp.minimum, xp.maximum)
    return ((first, whole, True), (whole, diagonal, False),
            (diagonal, end, True))


def _causal_query_blocks(ki, block_q: int, block_k: int, last, causal: bool,
                         window: int | None, minimum=jnp.minimum):
    """(first, end) of the query blocks the backward's Q loop walks for
    key block `ki`, `last` the end without a window: only the Q blocks
    whose last row reaches this K block's first key and, under a window,
    whose first row is still within the window of its last."""
    first = ki * block_k // block_q if causal else 0
    if window is not None:
        last = minimum(
            last, ((ki + 1) * block_k + window - 2) // block_q + 1)
    return first, last


def _walk(tile, start, stop, carry, most: int, fewest: int):
    """`tile(ki, carry)` over the key blocks `[start, stop)` of one run,
    in order; `most` and `fewest`: the blocks any query block of the
    shape has in this run (static), which choose the loop's form."""
    # a run every query block has a block of: its last block comes
    # after the loop, straight-line (measured: the loop's exit and the
    # grid step's end are scheduled around it, `_fwd_tiles`)
    peeled = int(fewest > 0)
    stop, most = stop - peeled, most - peeled
    if most > 1:
        # several blocks an iteration, the odd ones after
        n = min(_BLOCKS_AN_ITERATION, most)

        def several(j, carry):
            for i in range(n):
                carry = tile(start + n * j + i, carry)
            return carry

        groups = (stop - start) // n
        carry = jax.lax.fori_loop(0, groups, several, carry)
        carry = jax.lax.fori_loop(start + n * groups, stop, tile, carry)
    elif most:      # (a run no query block has a block of is not traced)
        carry = jax.lax.fori_loop(start, stop, tile, carry)
    return tile(stop, carry) if peeled else carry


def _flash_kernel(*refs, block_k: int, causal: bool, scale: float,
                  window: int | None = None, diffusion: int | None = None,
                  heads: int | None = None):
    """`heads` (None: no selection): under a selection plane the query
    heads of a batch row; the refs then start with the tiles' counts (a
    scalar prefetch) and hold the query block's row of the plane's tiles
    after v."""
    if heads is None:
        q_ref, k_ref, v_ref, o_ref, *lse_ref = refs
    else:
        counts_ref, q_ref, k_ref, v_ref, plane_ref, o_ref, *lse_ref = refs
    qi = pl.program_id(1)
    q = q_ref[...]  # [block_q, d]
    t = k_ref.shape[0]
    d = v_ref.shape[-1]   # o's width is v's; the scores contract q's
    block_q = q.shape[0]
    if diffusion is not None:
        # what each of the tile's query rows sees: a column a grid step
        reach = _diffusion_reach(
            qi * block_q + jax.lax.broadcasted_iota(
                jnp.int32, (block_q, 1), 0), t // 2, diffusion)
    if heads is not None:
        # this query block's row of the counts, one a key block
        counted = ((pl.program_id(0) // heads) * pl.num_programs(1)
                   + qi) * (t // block_k)

    def body(ki, carry, masked: bool = True):
        o, m, l = carry
        k = k_ref[pl.ds(ki * block_k, block_k), :]  # [block_k, d]
        v = v_ref[pl.ds(ki * block_k, block_k), :]
        s = jax.lax.dot_general(
            q, k, (((1,), (1,)), ((), ())),
            preferred_element_type=jnp.float32) * scale  # [bq, bk]
        if heads is not None:
            # the plane's tile is the mask (it holds no pair above the
            # diagonal), the same for every head of the batch row
            s = jnp.where(plane_ref[ki].astype(jnp.float32) > 0, s, NEG_INF)
        elif masked and diffusion is not None:
            k_pos = ki * block_k + jax.lax.broadcasted_iota(
                jnp.int32, (block_q, block_k), 1)
            s = jnp.where(_diffusion_keep(k_pos, reach, diffusion), s,
                          NEG_INF)
        elif masked and causal:
            q_pos = qi * block_q + jax.lax.broadcasted_iota(
                jnp.int32, (block_q, block_k), 0)
            k_pos = ki * block_k + jax.lax.broadcasted_iota(
                jnp.int32, (block_q, block_k), 1)
            keep = q_pos >= k_pos
            if window is not None:
                keep &= q_pos - k_pos < window
            s = jnp.where(keep, s, NEG_INF)
        m_new = jnp.maximum(m, s.max(-1))
        p = jnp.exp(s - m_new[:, None])
        corr = jnp.exp(m - m_new)
        l_new = l * corr + p.sum(-1)
        pv = jax.lax.dot_general(
            p.astype(v.dtype), v, (((1,), (0,)), ((), ())),
            preferred_element_type=jnp.float32)
        o_new = o * corr[:, None] + pv
        return o_new, m_new, l_new

    tile = body
    if heads is not None:
        # a tile that holds no selected pair is skipped. A row that has
        # seen nothing yet carries m = NEG_INF and l = the masked entries
        # it met; its first selected score rescales both by
        # exp(NEG_INF - s) = 0.0 exactly, and every row selects a key
        def tile(ki, carry, masked: bool = True):
            return jax.lax.cond(counts_ref[counted + ki] > 0,
                                functools.partial(body, ki), lambda c: c,
                                carry)

    carry = (jnp.zeros((block_q, d), jnp.float32),
             jnp.full((block_q,), NEG_INF, jnp.float32),
             jnp.zeros((block_q,), jnp.float32))
    if not causal:      # no mask: the one loop it always was
        carry = jax.lax.fori_loop(0, t // block_k, body, carry)
    else:
        # the walk as runs of key blocks, in the order of the keys: the
        # blocks the mask keeps whole run with no mask (no iota, no
        # comparison, no select: where(True, s, NEG_INF) is s), the
        # blocks it cuts as they always did. A row that sees nothing in
        # a block leaves it with m = NEG_INF (`_causal_key_blocks`),
        # and every row sees its own key in the end. Same products,
        # same order, same float32 sums as one masked loop over them
        # all: every output is that loop's, bit for bit. (Under a
        # selection the runs are the causal ones and the plane cuts
        # every block of both.)
        runs = _key_runs(qi, t, block_q, block_k, window, diffusion)
        # ... and the same bounds for every query block at once: what
        # is static of each run chooses its form
        static = _key_runs(np.arange(t // block_q), t, block_q, block_k,
                           window, diffusion, np)
        for (start, stop, masked), (lo, hi, _) in zip(runs, static):
            carry = _walk(functools.partial(tile, masked=masked), start,
                          stop, carry, int(np.max(hi - lo)),
                          int(np.min(hi - lo)))
    o, m, l = carry
    denom = jnp.where(l > 0, l, 1.0)
    o_ref[...] = (o / denom[:, None]).astype(o_ref.dtype)
    if lse_ref:
        # the row log-sum-exp the backward kernels rebuild p from, laid
        # along the lanes ([1, block_q]) as they read it
        lse_ref[0][...] = (m + jnp.log(denom)).reshape(1, block_q)


def _flash_aligned(t: int, d: int, block_q: int, block_k: int,
                   d_v: int | None = None) -> bool:
    """Mosaic constraints: K/V dynamic-slice starts must be provably
    8-aligned (sublane) and the lane dim 128-padded; unaligned shapes go
    through the dense path (short sequences — dense is fine there)."""
    block_q = min(block_q, t)
    block_k = min(block_k, t)
    return (t % block_q == 0 and t % block_k == 0
            and block_q % 8 == 0 and block_k % 8 == 0 and d % 8 == 0
            and (d_v or d) % 8 == 0)


def _diffusion_tiled(t: int, *sides: int) -> bool:
    """Under the block-diffusion mask a tile lies in one half of the
    rows: every tile side divides L = t / 2."""
    return all(side <= t // 2 and t // 2 % side == 0 for side in sides)


def _kernel_tiles(t: int, d: int, d_v: int, dtype, block_q: int | None,
                  block_k: int | None, diffusion: int | None = None):
    """The forward kernel's tile for a call, or None where the shape
    takes the dense path."""
    block_q, block_k = fwd_tiles(t, d, dtype, block_q, block_k, d_v)
    if not _flash_aligned(t, d, block_q, block_k, d_v) or (
            diffusion is not None
            and not _diffusion_tiled(t, block_q, block_k)):
        return None
    return min(block_q, t), min(block_k, t)


def _flash_fwd_impl(q, k, v, *, causal: bool, scale: float,
                    block_q: int | None, block_k: int | None, interpret: bool,
                    window: int | None = None, save_lse: bool = False,
                    diffusion: int | None = None, selected=None):
    """`block_q`, `block_k`: None asks `fwd_tiles`. `save_lse` (under a
    gradient): returns (out, lse), lse [B, H, T] float32 — None where
    the dense fallback ran. `selected`: (plane, tile counts or None);
    the call then returns (out, lse) whatever `save_lse`, the dense
    form's lse where that ran."""
    b, t, h, d = q.shape
    d_v = v.shape[-1]
    if k.shape[-1] != d or v.shape[:3] != k.shape[:3]:
        raise ValueError(
            f"flash_attention: q {q.shape} and k {k.shape} share the score "
            f"width, k and v {v.shape} batch, length and heads")
    if selected is not None and (
            not causal or window is not None or diffusion is not None
            or selected[0].shape != (b, t, t)):
        raise ValueError(
            "flash_attention: a selection keeps the causal walk and takes "
            "neither a window nor the block-diffusion mask; its plane "
            f"{selected[0].shape} is [batch, T, T]")
    plain = window is None and k.shape[2] == h and diffusion is None \
        and selected is None
    if not plain and (not causal or h % k.shape[2]):
        raise ValueError(
            "flash_attention: a window, grouped heads and the "
            "block-diffusion mask need causal=True, "
            f"and the {h} query heads a multiple of the {k.shape[2]} "
            "key/value heads")
    if diffusion is not None and (window is not None or diffusion < 1
                                  or t % (2 * diffusion)):
        raise ValueError(
            f"flash_attention: diffusion={diffusion} cuts the two halves "
            f"of {t} rows into whole blocks, and takes no window")
    tiles = _kernel_tiles(t, d, d_v, q.dtype, block_q, block_k, diffusion)
    if tiles is None:
        if selected is not None:
            return _dense_selected(q, k, v, selected[0], scale)
        if t >= 512:
            import warnings

            warnings.warn(
                f"flash_attention: seq {t} / head_dim {d} not tile-aligned;"
                " falling back to dense O(T^2) attention — pad the sequence"
                " to a multiple of 8 for the pallas kernel", stacklevel=2)
        out = _dense_fallback(q, k, v, causal, scale, window, diffusion)
        return (out, None) if save_lse else out
    block_q, block_k = tiles
    # once a traced call: the tile is static in the compiled program
    logger.debug("flash_fwd %s %s: tiles %d x %d", q.shape, q.dtype, block_q,
                 block_k)
    call = functools.partial(_flash_call, causal=causal, scale=scale,
                             block_q=block_q, block_k=block_k,
                             interpret=interpret, save_lse=save_lse)
    if not plain:
        call = functools.partial(call, window=window)
    if diffusion is not None:
        call = functools.partial(call, diffusion=diffusion)
    # batch rows are independent kernel instances: under a sharded jit
    # each device runs the kernel on its own [b, T, H, D] slice
    if selected is None:
        return over_leading_dim(call, (True, True, True))(q, k, v)
    plane, counts = selected
    if counts is None or counts.shape[1:] != (t // block_q, t // block_k):
        counts = tile_counts(plane, block_q, block_k)   # this call's tile's
    return over_leading_dim(
        lambda q, k, v, *selected: call(q, k, v, selected=selected,
                                        save_lse=True),
        (True,) * 5)(q, k, v, plane, counts)


def _fold(x):
    """[B, T, H, D] -> [B*H, T, D], the layout the kernels walk."""
    b, t, h, d = x.shape
    return x.transpose(0, 2, 1, 3).reshape(b * h, t, d)


def _unfold(x, b):
    bh, t, d = x.shape
    return x.reshape(b, bh // b, t, d).transpose(0, 2, 1, 3)


def _flash_call(q, k, v, *, causal: bool, scale: float, block_q: int,
                block_k: int, interpret: bool, window: int | None = None,
                save_lse: bool = False, diffusion: int | None = None,
                selected=None):
    b, t, h, d = q.shape
    h_kv, d_v = k.shape[2], v.shape[3]
    qf, kf, vf = _fold(q), _fold(k), _fold(v)

    kernel = functools.partial(_flash_kernel, block_k=block_k,
                               causal=causal, scale=scale)
    if window is not None:
        kernel = functools.partial(kernel, window=window)
    if diffusion is not None:
        kernel = functools.partial(kernel, diffusion=diffusion)
    # (an index map's last argument under a selection: the counts' ref)
    if h_kv == h:
        def kv_index(bh, qi, *_):
            return (bh, 0, 0)
    else:
        # query head g reads key/value head g // group: consecutive
        # query heads of a group map to one block, fetched once
        group = h // h_kv

        def kv_index(bh, qi, *_):
            return ((bh // h) * h_kv + (bh % h) // group, 0, 0)
    out_specs = pl.BlockSpec((None, block_q, d_v),
                             lambda bh, qi, *_: (bh, qi, 0))
    out_shape = jax.ShapeDtypeStruct((b * h, t, d_v), q.dtype)
    # two widths (keys wider than values): a whole sequence of 192-wide
    # keys sits in VMEM on 256 lanes, more than the default scope holds
    # at 8k tokens; so do k and v of ONE width from 256 x 8192 in bf16
    # up (double-buffered: 4 t d bytes an item), beside a query block's
    # row of a plane's tiles (int8, double-buffered) under a selection;
    # below that the call is the one it always was
    held = 4 * t * d * q.dtype.itemsize + (
        0 if selected is None else 2 * block_q * t)
    fits = d_v == d and held <= _FWD_KV_DEFAULT_SCOPE
    wide = {} if fits else {"compiler_params": pltpu.CompilerParams(
        vmem_limit_bytes=_FWD_WIDE_VMEM_LIMIT)}
    if save_lse:
        # one [1, block_q] row of float32 a grid step
        out_specs = [out_specs, pl.BlockSpec(
            (None, None, 1, block_q), lambda bh, qi, *_: (bh, qi, 0, 0))]
        out_shape = [out_shape, jax.ShapeDtypeStruct(
            (b * h, t // block_q, 1, block_q), jnp.float32)]
    grid = dict(
        grid=(b * h, t // block_q),
        in_specs=[
            pl.BlockSpec((None, block_q, d), lambda bh, qi, *_: (bh, qi, 0)),
            pl.BlockSpec((None, t, d), kv_index),
            pl.BlockSpec((None, t, d_v), kv_index),
        ],
        out_specs=out_specs)
    operands = (qf, kf, vf)
    if selected is not None:
        plane, counts = selected
        kernel = functools.partial(kernel, heads=h)
        # a query block's row of the plane's tiles: the same block for
        # every head of a batch row; a count a tile, prefetched
        grid["in_specs"].append(pl.BlockSpec(
            (None, None, t // block_k, block_q, block_k),
            lambda bh, qi, *_: (bh // h, qi, 0, 0, 0)))
        grid = dict(grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=1, **grid))
        operands = (counts.reshape(-1), *operands,
                    plane_tiles(plane, block_q, block_k, False))
    out = pl.pallas_call(
        kernel,
        **grid,
        out_shape=out_shape,
        interpret=interpret,
        name="flash_fwd",
        **wide,
    )(*operands)
    if save_lse:
        out, lse = out
        return _unfold(out, b), lse.reshape(b, h, t)
    return _unfold(out, b)


def _dense_attention(q, k, v, causal, scale, pad_mask=None):
    """Reference/fallback path. pad_mask: [B, Tk] bool, True = real
    token."""
    scores = jnp.einsum("bqhd,bkhd->bhqk", q, k,
                        preferred_element_type=jnp.float32) * scale
    if causal:
        tq, tk = q.shape[1], k.shape[1]
        mask = jnp.arange(tq)[:, None] >= jnp.arange(tk)[None, :]
        scores = jnp.where(mask[None, None], scores, NEG_INF)
    if pad_mask is not None:
        scores = jnp.where(pad_mask[:, None, None, :], scores, NEG_INF)
    probs = jax.nn.softmax(scores, axis=-1)
    return jnp.einsum("bhqk,bkhd->bqhd", probs, v).astype(q.dtype)


def block_diffusion_mask(t: int, block: int):
    """[t, t] booleans: which key rows (second axis) a query row sees
    under the block-diffusion mask over `[clean ; noised]` halves of
    t / 2 positions in blocks of `block`."""
    rows = jnp.arange(t)
    return _diffusion_keep(rows[None, :], _diffusion_reach(
        rows[:, None], t // 2, block), block)


def forward_tiles(t: int, d: int, dtype, block_q: int | None,
                  block_k: int | None, window: int | None = None,
                  diffusion: int | None = None) -> tuple[int, int]:
    """Of one head's T x T score plane under the causal mask (with its
    `window`, or the block-diffusion mask in its place): (the tiles
    `flash_fwd` runs with no mask, the tiles it walks), from the runs
    the kernel itself follows (`_key_runs`), reckoned in numpy. (0, 0)
    for a shape the kernel refuses: the dense path has no tiles."""
    block_q, block_k = fwd_tiles(t, d, dtype, block_q, block_k)
    if not _flash_aligned(t, d, block_q, block_k) or (
            diffusion is not None
            and not _diffusion_tiled(t, block_q, block_k)):
        return 0, 0
    block_q, block_k = min(block_q, t), min(block_k, t)
    runs = [(int(np.sum(stop - start)), masked)
            for start, stop, masked in _key_runs(
                np.arange(t // block_q), t, block_q, block_k, window,
                diffusion, np)]
    return (sum(n for n, masked in runs if not masked),
            sum(n for n, _ in runs))


def diffusion_tiles(t: int, block: int, block_q: int, block_k: int
                    ) -> tuple[int, int]:
    """(score tiles the forward kernel's two K loops walk over one
    head's T x T plane, the plane's tiles), from the bounds the kernel
    itself uses, reckoned in numpy. Of the walked ones the clean key
    blocks that end before what a query block's first row reaches run
    with no mask, the rest of the clean ones and every noised one
    masked: `forward_tiles` counts the two apart."""
    block_q, block_k = min(block_q, t), min(block_k, t)
    _, clean, first, last = _diffusion_key_blocks(
        np.arange(t // block_q), block_q, block_k, t // 2, block, np.where)
    return int((clean + last - first).sum()), (t // block_q) * (t // block_k)


def window_scores(t: int, window: int, d: int, dtype, block_q: int | None,
                  block_k: int | None) -> tuple[int, int, int]:
    """Of one head's T x T score plane under causal AND `window`: (the
    entries inside the mask, the entries of the tiles `flash_fwd`'s K
    loop walks, those of the tiles `flash_bwd_fused`'s Q loop walks),
    from the bounds and the tiles the kernels themselves use, reckoned
    in numpy. A window of one tile side fills at best half of what is
    walked: every 512 keys seen lie across two tiles. A shape the
    kernels refuse takes the dense path, which walks the plane. Of the
    forward's tiles those between the window's far edge and the diagonal
    run with no mask (`forward_tiles` counts them; none under a window
    narrower than the two tile sides together), the two edges masked;
    the backward masks every tile it walks."""
    seen = min(window, t)       # rows 0 .. seen - 1 see i + 1 keys
    inside = seen * (seen + 1) // 2 + (t - seen) * seen
    block_q, block_k = fwd_tiles(t, d, dtype, block_q, block_k)
    if not _flash_aligned(t, d, block_q, block_k):
        return inside, t * t, t * t
    block_q, block_k = min(block_q, t), min(block_k, t)
    first, _, _, end = _causal_key_blocks(
        np.arange(t // block_q), block_q, block_k, t // block_k, window,
        np.minimum, np.maximum)
    fwd = int((end - first).sum()) * block_q * block_k
    block_q, block_k = _bwd_tiles(t, d, dtype)
    first, end = _causal_query_blocks(
        np.arange(t // block_k), block_q, block_k, t // block_q, True,
        window, np.minimum)
    return inside, fwd, int((end - first).sum()) * block_q * block_k


def _dense_grouped(q, k, v, scale, window, diffusion=None):
    """Dense causal attention under an optional window (or under the
    block-diffusion mask in its place), with k and v of
    fewer heads than q (query head g reads key/value head g // group):
    the unaligned fallback of that path; scores in float32."""
    b, t, h, d = q.shape
    h_kv = k.shape[2]
    qg = q.reshape(b, t, h_kv, h // h_kv, d)
    scores = jnp.einsum("bqkgd,bskd->bkgqs", qg, k,
                        preferred_element_type=jnp.float32) * scale
    ahead = jnp.arange(t)[:, None] - jnp.arange(t)[None, :]
    keep = ahead >= 0
    if window is not None:
        keep &= ahead < window
    if diffusion is not None:
        keep = block_diffusion_mask(t, diffusion)
    scores = jnp.where(keep[None, None, None], scores, NEG_INF)
    probs = jax.nn.softmax(scores, axis=-1)
    out = jnp.einsum("bkgqs,bskd->bqkgd", probs, v)
    return out.reshape(b, t, h, v.shape[-1]).astype(q.dtype)


def _dense_fallback(q, k, v, causal, scale, window, diffusion=None):
    """What a call no tile divides takes, forward and (checkpointed)
    backward."""
    if window is None and k.shape[2] == q.shape[2] and diffusion is None:
        return _dense_attention(q, k, v, causal, scale)
    return _dense_grouped(q, k, v, scale, window, diffusion)


def masked_attention(q, k, v, pad_mask, causal=False, scale=None):
    """Attention with key padding mask (BERT-style batches). pad_mask:
    [B, T] bool. Dense path — padded fine-tune batches are short."""
    scale = scale if scale is not None else q.shape[-1] ** -0.5
    return _dense_attention(q, k, v, causal, scale, pad_mask=pad_mask)


@functools.partial(jax.custom_vjp, nondiff_argnums=(3, 4, 5, 6, 7, 8))
def flash_attention(q, k, v, causal: bool = True, scale: float | None = None,
                    block_q: int | None = None, block_k: int | None = None,
                    window: int | None = None, diffusion: int | None = None,
                    selected=None):
    """q: [B, T, H, D_qk]; k: [B, T, H_kv, D_qk]; v: [B, T, H_kv, D_v]
    with H a multiple of H_kv (query head g reads key/value head
    g // (H // H_kv)); D_v may differ from D_qk. `scale`: None is
    D_qk ** -0.5. `window`: query i sees keys j with 0 <= i - j < window
    (needs causal). `block_q`, `block_k`: the forward kernel's tile; None
    asks `fwd_tiles`, a number is taken as given. `diffusion`: the T
    rows are `[clean ; noised]` copies of T / 2 positions in blocks of
    that many, under the block-diffusion mask in the causal one's place
    (the header has its rule; needs causal, takes no window).
    `selected`: `(plane, tile_counts)`, the mask as DATA — `plane` [B,
    T, T] int8, nonzero where query t of the batch row sees key s (every
    head the same; only pairs with s <= t, and at least one a row),
    `tile_counts` [B, T / block_q, T / block_k] int32 its nonzero
    entries a forward tile, or None (they are counted here); needs
    causal, takes neither window nor diffusion; no gradient reaches
    either. Returns [B, T, H, D_v]; under a selection (that, the rows'
    log-sum-exp over their selected keys [B, H, T] float32, a constant:
    what the indexer's loss rebuilds the probabilities from)."""
    actual_scale = scale if scale is not None else q.shape[-1] ** -0.5
    return _flash_fwd_impl(q, k, v, causal=causal, scale=actual_scale,
                           block_q=block_q, block_k=block_k,
                           interpret=not is_tpu(), window=window,
                           diffusion=diffusion, selected=selected)


def _fwd(q, k, v, causal, scale, block_q, block_k, window, diffusion=None,
         selected=None):
    actual_scale = scale if scale is not None else q.shape[-1] ** -0.5
    out, lse = _flash_fwd_impl(q, k, v, causal=causal, scale=actual_scale,
                               block_q=block_q, block_k=block_k,
                               interpret=not is_tpu(), window=window,
                               save_lse=True, diffusion=diffusion,
                               selected=selected)
    # (under a selection the dense form gives a log-sum-exp too)
    kernel = lse is not None and (selected is None or _kernel_tiles(
        q.shape[1], q.shape[3], v.shape[3], q.dtype, block_q,
        block_k) is not None)
    if kernel:   # the kernel ran: name what it produced
        out = checkpoint_name(out, SAVED_ACROSS_REMAT[0])
        lse = checkpoint_name(lse, SAVED_ACROSS_REMAT[1])
    residuals = (q, k, v, out, lse if kernel else None, selected)
    return (out if selected is None else (out, lse)), residuals


_NT = (((1,), (1,)), ((), ()))   # a @ b.T


def _flash_bwd_kernel(*refs, block_q: int, causal: bool, scale: float,
                      window: int | None = None, group: int = 1,
                      diffusion: int | None = None,
                      per_row: int | None = None):
    """One key block of the backward, in one pass over the query blocks
    at or after it (all of them without the mask; under a window only
    those that still reach it): dk and dv of its keys, and its share of
    every dq. A tile has the KEYS along its rows —
    p.T and ds.T, [block_k, block_q], rebuilt in VMEM from the saved
    log-sum-exp — so `lse` and `delta` are read as rows along the lanes
    and all three products are plain ones: dv += p.T @ do, dk += ds.T @ q,
    dq.T += k.T @ ds.T. dq is accumulated transposed (a [d, block_q]
    slab a query block: whole lanes at head size 64) over the grid's key
    axis and written at its last step; every accumulator is float32
    scratch, cast once. MXU operands in the inputs' dtype, as the forward
    feeds them.

    Grouped heads (`group` query heads a key/value head) put the group
    between the head axis and the key axis of the grid: q, do, lse,
    delta and dq.T are ONE query head's, k and v its key/value head's,
    and dk, dv are summed over the group's query heads in float32
    scratch of a whole sequence, a key block written when its last query
    head has been through.

    Under a selection plane (`per_row`: the grid's rows a batch row; None
    without one) the refs start with the tiles' counts, a scalar
    prefetch, and hold the key block's column of the plane's tiles, the
    keys along a tile's rows, after v: the tile is the mask, and a tile
    that holds no selected pair is skipped."""
    if per_row is None:
        (q_ref, do_ref, lse_ref, delta_ref, k_ref, v_ref, dqt_ref, dk_ref,
         dv_ref, dqt_acc, dk_acc, dv_acc) = refs
    else:
        (counts_ref, q_ref, do_ref, lse_ref, delta_ref, k_ref, v_ref,
         plane_ref, dqt_ref, dk_ref, dv_ref, dqt_acc, dk_acc, dv_acc) = refs
    grouped = group > 1
    ki = pl.program_id(2 if grouped else 1)
    k = k_ref[...]   # [block_k, d]
    v = v_ref[...]
    kt = k.T
    block_k = k.shape[0]
    # this key block's rows of the dk / dv accumulators
    keys = pl.ds(ki * block_k, block_k) if grouped else ...

    @pl.when(ki == 0)
    def _():
        dqt_acc[...] = jnp.zeros_like(dqt_acc)

    if grouped:
        @pl.when(pl.program_id(1) == 0)
        def _():
            dk_acc[keys] = jnp.zeros((block_k, k.shape[1]), jnp.float32)
            dv_acc[keys] = jnp.zeros((block_k, v.shape[1]), jnp.float32)
    else:
        dk_acc[...] = jnp.zeros_like(dk_acc)
        dv_acc[...] = jnp.zeros_like(dv_acc)
    if per_row is not None:
        # this key block's column of the counts, one a query block
        counted = ((pl.program_id(0) // per_row) * pl.num_programs(
            2 if grouped else 1) + ki) * (q_ref.shape[0] // block_q)
    elif diffusion is not None:
        half = q_ref.shape[0] // 2
        k_pos = ki * block_k + jax.lax.broadcasted_iota(
            jnp.int32, (block_k, block_q), 0)
    elif causal:
        # key position minus query position, for a tile at the origin
        ahead = (jax.lax.broadcasted_iota(jnp.int32, (block_k, block_q), 0)
                 - jax.lax.broadcasted_iota(jnp.int32, (block_k, block_q), 1))

    def body(qi, _):
        rows = pl.ds(qi * block_q, block_q)
        q = q_ref[rows, :]   # [block_q, d]
        do = do_ref[rows, :]
        st = jax.lax.dot_general(
            k, q, _NT, preferred_element_type=jnp.float32) * scale
        if per_row is not None:
            st = jnp.where(plane_ref[qi].astype(jnp.float32) > 0, st,
                           NEG_INF)
        elif diffusion is not None:
            # what each of the tile's queries sees: a row along the lanes
            reach = _diffusion_reach(
                qi * block_q + jax.lax.broadcasted_iota(
                    jnp.int32, (1, block_q), 1), half, diffusion)
            st = jnp.where(_diffusion_keep(k_pos, reach, diffusion), st,
                           NEG_INF)
        elif causal:
            reach = qi * block_q - ki * block_k
            keep = ahead <= reach
            if window is not None:
                keep &= ahead > reach - window
            st = jnp.where(keep, st, NEG_INF)
        pt = jnp.exp(st - lse_ref[qi])   # lse, delta: [1, block_q]
        dpt = jax.lax.dot_general(v, do, _NT,
                                  preferred_element_type=jnp.float32)
        dst = (pt * (dpt - delta_ref[qi])).astype(q.dtype)
        dv_acc[keys] += jnp.dot(pt.astype(do.dtype), do,
                                preferred_element_type=jnp.float32)
        dk_acc[keys] += jnp.dot(dst, q, preferred_element_type=jnp.float32)
        dqt_acc[qi] += jnp.dot(kt, dst, preferred_element_type=jnp.float32)

    if per_row is not None:
        tile = body

        def body(qi, _):
            pl.when(counts_ref[counted + qi] > 0)(
                functools.partial(tile, qi, None))

    if diffusion is not None:
        # the clean query blocks from this key block's first block on,
        # then the noised ones that see it
        first, low, high = _diffusion_query_blocks(
            ki, block_q, block_k, half, diffusion)
        jax.lax.fori_loop(first, half // block_q, body, None)
        first, last = low, high
    else:
        # (the block-diffusion mask takes no window)
        first, last = _causal_query_blocks(
            ki, block_q, block_k, q_ref.shape[0] // block_q, causal, window)
    jax.lax.fori_loop(first, last, body, None)

    def write():
        dk_ref[...] = (dk_acc[keys] * scale).astype(dk_ref.dtype)
        dv_ref[...] = dv_acc[keys].astype(dv_ref.dtype)

    if grouped:
        pl.when(pl.program_id(1) == group - 1)(write)
    else:
        write()

    @pl.when(ki == pl.num_programs(2 if grouped else 1) - 1)
    def _():
        dqt_ref[...] = (dqt_acc[...] * scale).astype(dqt_ref.dtype)


def _fit(t: int, target: int) -> int:
    """The largest block of at most `target` rows, halving from it, that
    divides t (t % 8 == 0 wherever the kernels run)."""
    block = min(target, t)
    while t % block:
        block //= 2
    return block


def _bwd_tiles(t: int, d: int, dtype, d_v: int | None = None
               ) -> tuple[int, int]:
    """(block_q, block_k) of `flash_bwd_fused`, from what the call
    observes: 512 x 512, halved until it divides t (all of t below
    that). Read on the chip, a layer's whole backward (the kernel, delta
    and the layout copies around it), bf16, causal, ms a call:

        tile        [3,8192,28|4,128]  the same,    [9,4096,32|8,64]
                    window 4096        no window
        128 x 512     27.07              33.31        30.50
        256 x 256     22.52              28.11        26.02
        256 x 512     20.85              25.45        23.61
        512 x 256     21.41              25.98        24.48
        512 x 512     20.18              24.45        22.73
        512 x 1024    20.79              24.28        23.14
        1024 x 512    21.42              24.78        23.37
        1024 x 1024   20.69              23.92        22.78
        the scan this path had: 100.8    212.5        214.8

    (the expert cells' layers at their batch, PR 36, PERF.md section 6:
    134, 148 and 68 TFLOP/s inside the mask at 512 x 512). At head size
    64, T 1024 (`[32, 1024, 12, 64]`, PR 32): 512 x 512 3.81 ms,
    256 x 512 3 % and 256 x 256 12 % behind, 1024 on either side 11-15 %
    behind. From 512 rows up the tiles lie within 3 % of each other at
    every shape read, the window moves nothing, and 1024 x 1024's 2 % at
    8k without a window is a loss at T 1024: neither d, dtype, the
    window nor a value width `d_v` other than d moves the rule yet (it
    compiles for float32, for heads of 128 and 256 and for 192 | 128).

    At T = 16 384 (PR 65, my chip runs: `[1, 16384, 32 | 4, 128]` bf16,
    the forward at 256 x 512 and this backward at 512 x 512, wall time
    of forward + backward a call): 51.7 ms causal, 64.7 ms under a
    selection plane that keeps 2048 keys a query — the backward alone
    about 34 and 36 ms. Plain, q and do of a whole sequence
    double-buffered (16 MiB), dq.T (8 MiB) and 16 MiB of float32 dk / dv
    scratch stayed inside `_BWD_VMEM_LIMIT`; under a selection the key
    block's column of the plane (8 MiB, double-buffered) did not, and
    that form asks for `_BWD_SELECTED_VMEM_LIMIT`."""
    block = _fit(t, 512)
    return block, block


def _fwd_tiles(t: int, d: int, dtype, d_v: int | None = None
               ) -> tuple[int, int]:
    """(block_q, block_k) of `flash_fwd`, from what the call observes:
    the first of 512, 768, 256, 128 rows that divides t (all of t below
    that), square. Read on the chip (PR 34, PERF.md section 6), the
    kernel alone, causal, ms a call without / with the lse written:

        tile       [384,1024,64] bf16  [160,1024,64] bf16  [96,1024,64] f32
        128 x 128    6.42 / 6.44         2.59 / 2.61         1.71 / 1.72
        256 x 256    3.18 / 3.90         1.26 / 1.58         0.91 / 1.10
        256 x 512    2.43 / 3.12         0.95 / 1.24         0.76 / 0.91
        512 x 512    2.38 / 3.09         0.93 / 1.23         0.71 / 0.89
        512 x 1024   2.72 / 3.43         1.07 / 1.37         0.80 / 0.99
        1024 x 1024  2.60 / 3.20         1.02 / 1.28         0.76 / 0.90

    (the two GPT-2 cells' batch x heads, T 1024, head size 64). A tile
    far from square loses (128 x 512 3.19, 512 x 128 4.51), and block_k
    1024 spends its gain on the half-masked diagonal block. 512 x 512
    is also first at T 512 (1.86; 256 x 256 2.35), 1536, 2048 (3.18;
    256 x 256 4.85, 1024 x 1024 3.53) and 4096, and at head sizes 32,
    128 and 256, so neither d nor dtype moves the rule yet. Where 512
    does not divide, at T 768: 768 x 768 1.75, 256 x 256 2.06, 384 x 384
    2.10, 128 x 128 3.92 (at T 1536 768 x 768 is 13 % behind 512 x 512,
    256 x 256 45 %). Without the mask 1024 x 1024 reads 2.16 against
    512 x 512's 2.67 and 128 x 128's 10.41: no caller runs full
    attention at T 1024 or more, so the rule does not ask. The lse
    column's 0.6-0.7 ms above block_q 128 is the probe's own
    `lse.reshape(B, H, T)`; in a step whose backward tile is also 512
    both forwards read 2.32 ms a call (`gpt2s_epoch`'s trace).

    The loop's form (PR 60, PERF.md section 6; the kernel alone by the
    device trace's own events, bf16, writing the lse, ms a call; every
    output bit for bit the one masked loop's). `runs`: a loop a run, the
    blocks the mask keeps whole with no mask. `no mask at all`: the one
    loop with the mask taken off EVERY tile (wrong values: the most the
    mask can cost). `last straight`: the last block after the loop, as
    straight-line code, every block masked / the whole ones not. `2`,
    `3`, `4`: that, and as many blocks an iteration of the whole run:

        [B,T,H|H_kv,D] mask, tiles      one    runs   no mask  last straight    2      3      4
                                        loop          at all   masked / cut
        [3,8192,28|4,128] w 4096       12.372 12.823  11.997   12.118 / 11.733 11.235 10.928 11.231
        [1,8192,32,192|128] causal      7.027  7.276   7.132    6.903 /  6.928  6.401  6.266  6.243
        [1,8192,32|4,128] diffusion 4   3.921  4.002   3.647    3.917 /  3.830  3.565  3.521  3.550
        [2,4096,16,128] causal          1.615  1.699   1.616    1.526 /  1.519  1.450  1.428  1.450
        [32,1024,12,64] causal, 512^2   2.281  2.567   2.296    1.943 /  1.939  1.932  1.932  1.932
        [2,8192,64|8,128] w 512         7.436    -       -        -      -      6.563  6.563  6.563
        [2,8192,16|2,256] causal        8.968    -       -        -      -      8.152  7.920  7.871
        [9,4096,32|8,64] causal        15.012    -       -        -      -     13.106 13.001 13.250

    (256 x 512 tiles but the fifth row; the tree as shipped, three an
    iteration, in a later call: 10.825, 6.282, 3.527, 1.420, 1.939,
    6.470, 7.936, 13.046, and [2,8192,48|8,128] causal 16.754 -> 13.994,
    [2,8192,32|2,128] 11.170 -> 9.332, [8,1024,20,64] 0.950 -> 0.808:
    10-16 % at eleven shapes). The mask is all but free here:
    taken off every tile it gives 3 % under a window, 7 % under block
    diffusion and nothing under the plain diagonal, and a loop a run
    LOSES 2-13 % to its zero- and one-trip loops. What pays is the
    loop's form: the last block out of the loop (1.4-15 %), and three
    blocks an iteration (4-6 % more at 8k); the unmasked run adds 3
    points under a window and 2 under block diffusion.

    At T = 16 384 (PR 65, my chip runs, `[1, 16384, 32 | 4, 128]` bf16,
    wall time a call, writing the lse): causal 256 x 512 17.82 ms,
    512 x 512 17.43; under a selection plane that keeps 2048 keys a
    query (every causal tile holds a pair: none is skipped; the plane's
    cut into tiles and the count a tile, 256 MiB of int8 through XLA,
    are in the figure) 256 x 512 28.88 ms, 512 x 512 30.39: the plane's
    tile, int8 widened to float32, compared and selected a head and
    block, costs 62 % where no block runs unmasked (as ONE masked loop,
    without the last block straight-line and three an iteration, the
    same call read 30.65 and 32.57). K and V of a key head whole
    (16 MiB double-buffered) and a query block's row of the plane's
    tiles (8 MiB) stayed inside `_FWD_WIDE_VMEM_LIMIT`.

    A t that 128 does not divide (nor t itself, below 128) never
    reached the kernel: it answers 128 x 128, which `_flash_aligned`
    refuses as it always has, and the call takes the dense path. `d_v`,
    the value width where it differs from d, does not move the rule."""
    if t % min(128, t):
        return 128, 128
    block = next(b for b in (512, 768, 256, 128) if t % min(b, t) == 0)
    return block, block


def fwd_tiles(t: int, d: int, dtype, block_q: int | None = None,
              block_k: int | None = None, d_v: int | None = None
              ) -> tuple[int, int]:
    """The (block_q, block_k) `flash_attention` hands `flash_fwd` for a
    call of sequence length t, head size d and `dtype`: the caller's
    numbers where it passes them, `_fwd_tiles`' where it passes None.
    Static per compiled shape, so this function is the record of which
    tile a program runs."""
    rule_q, rule_k = _fwd_tiles(t, d, dtype, d_v)
    return (rule_q if block_q is None else block_q,
            rule_k if block_k is None else block_k)


def _flash_bwd_call(q, k, v, o, lse, g, *plane, causal: bool, scale: float,
                    interpret: bool, window: int | None = None,
                    diffusion: int | None = None):
    """(dq, dk, dv) in one kernel, `flash_bwd_fused`. Scores, lse, delta,
    ds and the accumulators in float32; p and ds cast to the inputs'
    dtype for the MXU, as the forward casts p. The grid is (batch x
    heads, key blocks); with fewer key/value heads than query heads it
    is (batch x key/value heads, group, key blocks), and no K, V, dk or
    dv of a repeated head ever reaches HBM. `plane`: a selection's, or
    nothing."""
    b, t, h, d = q.shape
    h_kv, d_v = k.shape[2], v.shape[3]
    group = h // h_kv
    # under the block-diffusion mask a tile lies in one half of the rows
    block_q, block_k = _bwd_tiles(t if diffusion is None else t // 2, d,
                                  q.dtype, d_v)
    # once a traced call, as the forward says its own
    logger.debug("flash_bwd_fused %s | %d %s: tiles %d x %d", q.shape, h_kv,
                 q.dtype, block_q, block_k)
    # delta = rowsum(o * do): what the softmax's backward takes off dp
    delta = jnp.einsum("bthd,bthd->bht", o.astype(jnp.float32),
                       g.astype(jnp.float32))

    num_q = t // block_q
    num_k = t // block_k

    def rows(x):
        # [B, H, T] float32 as one [1, block_q] row a query block
        return x.reshape(b * h, num_q, 1, block_q)

    # a grid step's (query head, key/value head, dk / dv block it writes)
    if group == 1:
        grid = (b * h, num_k)

        def at(bh, ki):
            return bh, bh, ki
    else:
        grid = (b * h_kv, group, num_k)

        # query head gi of key/value head bkv, in the folded layout; the
        # output block stays the first until the group's last query
        # head, which writes each: one write-back a block, of final sums
        def at(bkv, gi, ki):
            return bkv * group + gi, bkv, jnp.where(gi == group - 1, ki, 0)

    n = len(grid)   # (an index map's argument after these: the counts' ref)

    def whole(width):   # a query head's whole sequence: q, do
        return pl.BlockSpec((None, t, width),
                            lambda *i: (at(*i[:n])[0], 0, 0))

    def kv_spec(width):
        return pl.BlockSpec((None, block_k, width),
                            lambda *i: (at(*i[:n])[1], i[n - 1], 0))

    def dkv_spec(width):
        return pl.BlockSpec((None, block_k, width),
                            lambda *i: at(*i[:n])[1:] + (0,))

    row_spec = pl.BlockSpec((None, num_q, 1, block_q),
                            lambda *i: (at(*i[:n])[0], 0, 0, 0))
    # dk, dv scratch: a key block's, or under grouped heads the whole
    # sequence's, summed over the group
    kv_rows = block_k if group == 1 else t
    kernel = functools.partial(_flash_bwd_kernel, block_q=block_q,
                               causal=causal, scale=scale, window=window,
                               group=group, diffusion=diffusion)
    spec = dict(
        grid=grid,
        in_specs=[whole(d), whole(d_v), row_spec, row_spec, kv_spec(d),
                  kv_spec(d_v)],
        out_specs=[pl.BlockSpec((None, num_q, d, block_q),
                                lambda *i: (at(*i[:n])[0], 0, 0, 0)),
                   dkv_spec(d), dkv_spec(d_v)],
        scratch_shapes=[pltpu.VMEM((num_q, d, block_q), jnp.float32),
                        pltpu.VMEM((kv_rows, d), jnp.float32),
                        pltpu.VMEM((kv_rows, d_v), jnp.float32)])
    operands = (_fold(q), _fold(g), rows(lse), rows(delta), _fold(k),
                _fold(v))
    if plane:
        per_row = grid[0] // b
        kernel = functools.partial(kernel, per_row=per_row)
        # a key block's column of the plane's tiles, the same block for
        # every head of a batch row; a count a tile, prefetched
        spec["in_specs"].append(pl.BlockSpec(
            (None, None, num_q, block_k, block_q),
            lambda *i: (i[0] // per_row, i[n - 1], 0, 0, 0)))
        spec = dict(grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=1, **spec))
        operands = (
            tile_counts(*plane, block_q, block_k).swapaxes(1, 2).reshape(-1),
            *operands, plane_tiles(*plane, block_q, block_k, True))
    dqt, dk, dv = pl.pallas_call(
        kernel,
        **spec,
        out_shape=[jax.ShapeDtypeStruct((b * h, num_q, d, block_q), q.dtype),
                   jax.ShapeDtypeStruct((b * h_kv, t, d), k.dtype),
                   jax.ShapeDtypeStruct((b * h_kv, t, d_v), v.dtype)],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel",) + ("arbitrary",) * (
                len(grid) - 1),
            vmem_limit_bytes=_BWD_SELECTED_VMEM_LIMIT if plane
            else _BWD_VMEM_LIMIT),
        interpret=interpret,
        name="flash_bwd_fused",
    )(*operands)
    dq = dqt.reshape(b, h, num_q, d, block_q).transpose(
        0, 2, 4, 1, 3).reshape(b, t, h, d)
    return dq, _unfold(dk, b), _unfold(dv, b)


def _bwd(causal, scale, block_q, block_k, window, diffusion, residuals, g):
    q, k, v, o, lse, selected = residuals
    actual_scale = scale if scale is not None else q.shape[-1] ** -0.5
    # an integer input's cotangent; the selection's log-sum-exp is
    # handed on as a constant
    nothing = jax.tree.map(
        lambda x: np.zeros(x.shape, jax.dtypes.float0), selected)
    if selected is not None:
        g, _ = g
    if lse is None:
        # unaligned fallback, as the forward's: one checkpointed dense block
        if selected is None:
            f = functools.partial(_dense_fallback, causal=causal,
                                  scale=actual_scale, window=window,
                                  diffusion=diffusion)
        else:
            def f(q, k, v):
                return _dense_selected(q, k, v, selected[0], actual_scale)[0]
        _, vjp = jax.vjp(jax.checkpoint(f), q, k, v)
        return (*vjp(g), nothing)
    call = functools.partial(_flash_bwd_call, causal=causal,
                             scale=actual_scale, interpret=not is_tpu(),
                             window=window, diffusion=diffusion)
    plane = () if selected is None else selected[:1]
    # as the forward: each device takes its own rows of the batch
    return (*over_leading_dim(call, (True,) * (6 + len(plane)))(
        q, k, v, o, lse, g, *plane), nothing)


flash_attention.defvjp(_fwd, _bwd)

# What `flash_bwd_fused` may hold under a selection: `_BWD_VMEM_LIMIT`'s,
# and the key block's column of the plane (T x block_k int8,
# double-buffered)
_BWD_SELECTED_VMEM_LIMIT = 96 * 1024 * 1024


def plane_tiles(plane, block_q: int, block_k: int, keys_first: bool):
    """[B, T, T] -> the plane cut into tiles a kernel indexes by their
    leading dimensions: [B, T / bq, T / bk, bq, bk], or with the KEYS
    first (the backward's layout: a tile has the keys along its rows)
    [B, T / bk, T / bq, bk, bq]."""
    b, t, _ = plane.shape
    cut = plane.reshape(b, t // block_q, block_q, t // block_k, block_k)
    return cut.transpose((0, 3, 1, 4, 2) if keys_first else (0, 1, 3, 2, 4))


def tile_counts(plane, block_q: int, block_k: int):
    """The selected pairs a tile of a plane (or of some rows of one),
    [B, rows / bq, T / bk] int32."""
    b, rows, t = plane.shape
    return plane.reshape(b, rows // block_q, block_q, t // block_k,
                         block_k).sum((2, 4), dtype=jnp.int32)


def _dense_selected(q, k, v, plane, scale):
    """Dense attention over the selection (grouped heads or not), and
    the rows' log-sum-exp [B, H, T]: what a call no tile divides
    takes."""
    b, t, h, d = q.shape
    h_kv = k.shape[2]
    scores = jnp.einsum("bqkgd,bskd->bkgqs",
                        q.reshape(b, t, h_kv, h // h_kv, d), k,
                        preferred_element_type=jnp.float32) * scale
    scores = jnp.where((plane != 0)[:, None, None], scores, NEG_INF)
    out = jnp.einsum("bkgqs,bskd->bqkgd", jax.nn.softmax(scores, axis=-1), v)
    return (out.reshape(b, t, h, v.shape[-1]).astype(q.dtype),
            jax.lax.stop_gradient(jax.nn.logsumexp(
                scores, axis=-1).reshape(b, h, t)))
