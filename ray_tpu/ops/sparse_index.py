"""Learned sparse attention's indexer: scores, the selection, its loss.

The lightning indexer of DeepSeek Sparse Attention (the DeepSeek-V3.2
reports), as `models/decoder.py` trains it beside a `full` attention
layer. With `q_I` `[B, T, H_I, D_I]`, ONE key head `k_I` `[B, T, D_I]`
and a weight a query and head `w` `[B, T, H_I]` (float32):

    I[t, s] = sum over j of w[t, j] * relu(q_I[t, j] . k_I[s]),  s <= t

(products in the inputs' dtype, float32 sums; the ReLU, the weighted sum
and everything after it float32; an exact zero is +0.0). A query keeps
`S_t`, its `min(t + 1, topk)` largest scores, ties to the LOWER key
index: `jax.lax.top_k`'s set, ties included. `index_select` gives the
selection as a PLANE `[B, T, T]` int8 (one a batch row, shared by every
head of the attention it steers), the row's log-sum-exp of I over `S_t`,
and the selected pairs a (query tile, key tile) — what lets
`ops.flash_attention(..., selected=)` skip a tile that holds none. It
walks the query rows a STRIP at a time (`strip_rows`): the scores of a
strip are the Mosaic kernel `index_scores` (16 products `[bq, D_I] x
[D_I, bk]` a tile, causal tiles only), a `[strip, T]` float32 array,
and no `[T, T]` float32 array nor any `[T, H_I, T]` array exists at any
length. The threshold is an exact k-th largest a row, found on the
scores' bit patterns (`monotone_key`: int32 keys in the floats' order)
by a radix search, `RADIX_BITS` bits a pass — `(2 ** RADIX_BITS - 1)`
counts over the strip a pass, 32 / `RADIX_BITS` passes — and the ties
at the threshold by a bisection of the key index: fused compares and
row sums, no sort. `index_select_xla` is the plain form it is tested
against: the whole plane of scores and `lax.top_k`.

`index_kl` is the indexer's loss against the attention it steered, the
sparse training stage's: with `p[t, s]` the main attention's
probabilities over `S_t`, averaged over its heads and taken as a
constant,

    KL_t = sum over s in S_t of p[t, s] (log p[t, s] - log softmax_S(I)[t, s])

summed over the rows. Its gradient reaches `q_I`, `k_I`, `w` alone:
`dI[t, s] = softmax_S(I)[t, s] - p[t, s]` on `S_t`, through the ReLU.
Value and gradient are made in ONE pass, the gradient kept as the
custom derivative's residual: the backward pass multiplies it by the
cotangent and walks nothing. Under a rematerialised block the pass
would run twice, the forward's and the recomputed one, and each throw
half of what it made away: the forward rule NAMES the three gradient
arrays (`KL_SAVED_ACROSS_REMAT`), a block that keeps those names across
its remat (`models/decoder.py::_block`: 35 MiB a layer at 16 384
tokens) runs the pass once, and the recomputed copy is dead code.
Under a selection the pass is the Mosaic kernel `index_kl`,
a (query tile, key tile) a grid step, the keys along
a tile's rows as `flash_bwd_fused` has them: it rebuilds p from the
attention's saved log-sum-exp (`exp(s_h - lse_h)` summed over the heads
in VMEM), rebuilds the tile of I from `q_I`, `k_I`, `w` and the row's
log-sum-exp of I over `S_t` (`index_select`'s), adds the tile's share of
KL_t, and pushes `dI` through the ReLU into `dq_I` (transposed slabs, a
query tile's accumulated over its key tiles), `dw` and `dk_I` (float32,
the whole sequence's, accumulated in the output's block); a tile that
holds no selected pair is skipped, and no probability or score tile
reaches HBM. Where nothing is selected away (T <= topk: short
sequences) or no tile divides the shape, the plain form runs, strips of
query rows under `lax.scan` with XLA's own derivative
(`index_kl_xla`: also what the kernel is tested against).

On CPU (tests) the kernels run in interpret mode.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np
from jax import lax
from jax.ad_checkpoint import checkpoint_name
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from ray_tpu._private.accelerator import is_tpu
from ray_tpu.ops.attention import _NT, plane_tiles, tile_counts
from ray_tpu.ops.partition import over_leading_dim

STRIP_ROWS = 512        # query rows whose scores exist at once, at most
KL_STRIP_ROWS = 64      # ... whose probabilities of every head do
RADIX_BITS = 2          # bits of the threshold a pass over a strip finds
_INT_MIN = np.int32(-2 ** 31)


def strip_rows(t: int, block_q: int, most: int = STRIP_ROWS) -> int:
    """The rows of a strip: the largest multiple of `block_q` that
    divides t and is at most `most` and, where t has two tiles, t / 2
    (so that the smallest shapes walk two strips too); all of t where
    there is none."""
    for rows in range(min(most, t // 2) // block_q * block_q, 0, -block_q):
        if t % rows == 0:
            return rows
    return t


def monotone_key(x):
    """float32 -> int32 in the same order (signed compare): a positive
    float's bits as they are, a negative one's magnitude bits flipped.
    No finite float maps to the least int32, which stands for a pair
    outside the causal mask."""
    bits = lax.bitcast_convert_type(x.astype(jnp.float32), jnp.int32)
    return jnp.where(bits < 0, bits ^ jnp.int32(0x7FFFFFFF), bits)


def _wrapped(value: int) -> np.int32:
    """A 32-bit pattern as the int32 that holds it."""
    return np.uint32(value & 0xFFFFFFFF).astype(np.int32)


def kth_largest(key, k, bits: int = RADIX_BITS):
    """The k-th largest of each row of `key` [R, T] int32, `k` [R] >= 1,
    [R] int32: the largest tau with `count(key >= tau) >= k`, built from
    its high bits down, `bits` a pass. In the unsigned order of `key ^
    INT_MIN` a candidate is the prefix so far with one digit set; the
    counts fall as the digit rises, so the digit is how many candidates
    still count k."""
    prefix = jnp.zeros(key.shape[:1], jnp.int32)
    for shift in range(32 - bits, -1, -bits):
        digit = jnp.zeros_like(prefix)
        for d in range(1, 2 ** bits):
            candidate = (prefix | _wrapped(d << shift)) ^ _INT_MIN
            count = (key >= candidate[:, None]).sum(-1, dtype=jnp.int32)
            digit += (count >= k).astype(jnp.int32)
        prefix |= lax.shift_left(digit, jnp.int32(shift))
    return prefix ^ _INT_MIN


def tie_bound(tied, need):
    """`tied` [R, T] bool, `need` [R] >= 1 (at most a row's count of
    them) -> [R] int32, the index of a row's `need`-th True: the tied
    pairs at or before it are the `need` of lowest index. A bisection of
    the index from its high bit down: the largest M with fewer than
    `need` of them before M."""
    col = lax.broadcasted_iota(jnp.int32, tied.shape, 1)
    most = jnp.zeros(tied.shape[:1], jnp.int32)
    for bit in range(max(tied.shape[1] - 1, 1).bit_length() - 1, -1, -1):
        candidate = most | np.int32(1 << bit)
        before = (tied & (col < candidate[:, None])).sum(-1, dtype=jnp.int32)
        most = jnp.where(before < need, candidate, most)
    return most


def select_rows(scores, rows, topk: int):
    """The selection of a strip. `scores` [R, T] float32 (whatever lies
    outside the causal mask), `rows` [R] the queries' positions -> [R, T]
    bool: the `min(row + 1, topk)` largest of a row's causal scores,
    ties to the lower index."""
    col = lax.broadcasted_iota(jnp.int32, scores.shape, 1)
    causal = col <= rows[:, None]
    key = jnp.where(causal, monotone_key(scores), _INT_MIN)
    k = jnp.minimum(rows + 1, topk).astype(jnp.int32)
    tau = kth_largest(key, k)[:, None]
    above = key > tau
    tied = key == tau
    last = tie_bound(tied, k - above.sum(-1, dtype=jnp.int32))
    return causal & (above | (tied & (col <= last[:, None])))


def _positive_zero(x):
    return jnp.where(x == 0, 0.0, x)


def index_scores_xla(q_i, k_i, w):
    """The plain form, whole: [B, T, T] float32 (tests and small sizes;
    entries above the diagonal are computed too and mean nothing)."""
    pre = jnp.einsum("btjd,bsd->bjts", q_i, k_i,
                     preferred_element_type=jnp.float32)
    return _positive_zero(jnp.einsum(
        "bjts,btj->bts", jax.nn.relu(pre), w.astype(jnp.float32),
        precision=lax.Precision.HIGHEST))


def _row_lse(scores, selected):
    return jax.nn.logsumexp(jnp.where(selected, scores, -jnp.inf), axis=-1)


def index_select_xla(q_i, k_i, w, topk: int, tile: tuple[int, int]):
    """`index_select`'s outputs by the plain road: the whole plane of
    scores and `lax.top_k` a row (a row's first `min(t + 1, topk)`
    picks; what lies above the diagonal scores -inf and sorts last)."""
    b, t, _ = k_i.shape
    scores = index_scores_xla(q_i, k_i, w)
    causal = jnp.tril(jnp.ones((t, t), bool))
    _, picks = lax.top_k(jnp.where(causal, scores, -jnp.inf), min(topk, t))
    plane = jnp.zeros((b, t, t), bool).at[
        jnp.arange(b)[:, None, None], jnp.arange(t)[None, :, None],
        picks].set(True) & causal
    ahead = jnp.arange(t)[:, None] - jnp.arange(t)[None, :]
    return lax.stop_gradient((
        plane.astype(jnp.int8), _row_lse(scores, plane),
        tile_counts(plane, *tile),
        (plane & (ahead >= topk)).sum((1, 2), dtype=jnp.int32)))


def _index_scores_kernel(row0_ref, q_ref, w_ref, k_ref, o_ref):
    """One tile of a strip's scores: `sum_j w_j relu(q_j k^T)` where the
    tile meets the causal mask, -inf where it lies wholly above the
    diagonal (and on the cut tiles' entries above it)."""
    block_q, block_k = o_ref.shape
    first_row = row0_ref[0] + pl.program_id(1) * block_q
    first_key = pl.program_id(2) * block_k

    @pl.when(first_key <= first_row + block_q - 1)
    def _():
        k = k_ref[...]
        acc = jnp.zeros((block_q, block_k), jnp.float32)
        for j in range(q_ref.shape[0]):
            pre = lax.dot_general(q_ref[j], k, _NT,
                                  preferred_element_type=jnp.float32)
            acc += w_ref[j] * jnp.maximum(pre, 0.0)
        ahead = (first_key - first_row
                 + lax.broadcasted_iota(jnp.int32, acc.shape, 1)
                 - lax.broadcasted_iota(jnp.int32, acc.shape, 0))
        o_ref[...] = jnp.where(ahead <= 0, jnp.where(acc == 0, 0.0, acc),
                               -jnp.inf)

    @pl.when(first_key > first_row + block_q - 1)
    def _():
        o_ref[...] = jnp.full(o_ref.shape, -jnp.inf, jnp.float32)


def index_scores(q_strip, w_strip, k_i, row0, tile: tuple[int, int]):
    """The scores of one strip of query rows, the kernel `index_scores`.
    `q_strip` [B, H_I, R, D_I] and `w_strip` [B, H_I, R, 1] float32 (the
    strip's rows, head-major), `k_i` [B, T, D_I] whole, `row0` the
    strip's first row (a traced int32 scalar) -> [B, R, T] float32, -inf
    above the diagonal."""
    b, heads, rows, d = q_strip.shape
    t = k_i.shape[1]
    block_q, block_k = min(tile[0], rows), min(tile[1], t)

    def call(q_strip, w_strip, k_i):
        return pl.pallas_call(
            _index_scores_kernel,
            grid_spec=pltpu.PrefetchScalarGridSpec(
                num_scalar_prefetch=1,
                grid=(q_strip.shape[0], rows // block_q, t // block_k),
                in_specs=[
                    pl.BlockSpec((None, heads, block_q, d),
                                 lambda i, qi, ki, _: (i, 0, qi, 0)),
                    pl.BlockSpec((None, heads, block_q, 1),
                                 lambda i, qi, ki, _: (i, 0, qi, 0)),
                    pl.BlockSpec((None, block_k, d),
                                 lambda i, qi, ki, _: (i, ki, 0))],
                out_specs=pl.BlockSpec((None, block_q, block_k),
                                       lambda i, qi, ki, _: (i, qi, ki))),
            out_shape=jax.ShapeDtypeStruct((q_strip.shape[0], rows, t),
                                           jnp.float32),
            interpret=not is_tpu(),
            name="index_scores",
        )(jnp.reshape(row0, (1,)).astype(jnp.int32), q_strip, w_strip, k_i)

    return over_leading_dim(call, (True, True, True))(q_strip, w_strip, k_i)


def index_select(q_i, k_i, w, topk: int, tile: tuple[int, int],
                 strip: int | None = None):
    """q_i [B, T, H_I, D_I], k_i [B, T, D_I], w [B, T, H_I] float32 ->
    (the selection plane [B, T, T] int8, the row log-sum-exp of I over
    the selection [B, T] float32, the selected pairs a tile of `tile` =
    (block_q, block_k) rows and keys [B, T / block_q, T / block_k] int32,
    the selected pairs at a distance of `topk` keys or more [B] int32),
    a strip of `strip` query rows at a time (None: `strip_rows`). None
    of them carries a gradient."""
    b, t, heads, d = q_i.shape
    block_q, block_k = min(tile[0], t), min(tile[1], t)
    rows = strip_rows(t, block_q) if strip is None else strip
    if t % rows or rows % block_q or t % block_k:
        raise ValueError(
            f"index_select: strips of {rows} rows in tiles of {block_q} x "
            f"{block_k} do not cut {t} rows whole")
    n = t // rows
    q_i, k_i, w = lax.stop_gradient((q_i, k_i, w))
    # head-major strips: a head's [rows, D_I] slab is a block's leading
    # index in the kernel, its weight a column
    q_strips = q_i.reshape(b, n, rows, heads, d).transpose(1, 0, 3, 2, 4)
    w_strips = w.astype(jnp.float32).reshape(b, n, rows, heads).transpose(
        1, 0, 3, 2)[..., None]

    def one(strip):
        q_strip, w_strip, at = strip
        row0 = at * rows
        scores = index_scores(q_strip, w_strip, k_i, row0,
                              (block_q, block_k))
        position = row0 + jnp.arange(rows, dtype=jnp.int32)
        chosen = jax.vmap(lambda s: select_rows(s, position, topk))(scores)
        ahead = position[:, None] - jnp.arange(t, dtype=jnp.int32)[None, :]
        return (chosen.astype(jnp.int8), _row_lse(scores, chosen),
                tile_counts(chosen, block_q, block_k),
                (chosen & (ahead >= topk)).sum((1, 2), dtype=jnp.int32))

    plane, lse, counts, beyond = lax.map(
        one, (q_strips, w_strips, jnp.arange(n, dtype=jnp.int32)))
    return (plane.transpose(1, 0, 2, 3).reshape(b, t, t),
            lse.transpose(1, 0, 2).reshape(b, t),
            counts.transpose(1, 0, 2, 3).reshape(b, t // block_q,
                                                 t // block_k),
            beyond.sum(0))


def _kl_strip(q_i, k_i, w, q, k, keep, scale: float):
    """One strip's sum of KL_t. q_i [R, H_I, D_I], k_i [T, D_I], w [R,
    H_I]; the main attention's q [R, H, D] and k [T, H_kv, D]; `keep`
    [R, T] bool, the selection -> a float32 scalar. The gradient with
    respect to q_i, k_i and w is the indexer's: p is a constant."""
    r, h, d = q.shape
    h_kv = k.shape[1]
    s = jnp.einsum("rkgd,skd->kgrs", q.reshape(r, h_kv, h // h_kv, d), k,
                   preferred_element_type=jnp.float32) * scale
    s = jnp.where(keep, s, -jnp.inf)
    p = lax.stop_gradient(
        jax.nn.softmax(s, axis=-1).reshape(h, r, -1).mean(0))
    pre = jnp.einsum("rjd,sd->jrs", q_i, k_i,
                     preferred_element_type=jnp.float32)
    scores = _positive_zero(jnp.einsum(
        "jrs,rj->rs", jax.nn.relu(pre), w.astype(jnp.float32),
        precision=lax.Precision.HIGHEST))
    log_q = scores - jax.nn.logsumexp(
        jnp.where(keep, scores, -jnp.inf), axis=-1, keepdims=True)
    held = keep & (p > 0)
    return jnp.where(held, p * (jnp.log(jnp.where(held, p, 1.0)) - log_q),
                     0.0).sum()


def index_kl_xla(q_i, k_i, w, q, k, plane, scale: float,
                 strip: int | None = None):
    """The plain form: (sum of KL_t over all rows, its gradient with
    respect to q_i, k_i, w), strips of query rows under one scan a batch
    row, XLA's own derivative a strip."""
    b, t, heads, d = q_i.shape
    rows = strip_rows(t, 8, KL_STRIP_ROWS) if strip is None else strip
    n = t // rows
    grad = jax.value_and_grad(_kl_strip, argnums=(0, 1, 2))

    def one_row(q_i, k_i, w, q, k, plane):
        def one(carry, strip):
            total, dk_sum = carry
            q_strip, w_strip, q_main, at = strip
            position = at * rows + jnp.arange(rows, dtype=jnp.int32)
            if plane is None:   # every causal key is selected
                keep = jnp.arange(t)[None, :] <= position[:, None]
            else:
                keep = lax.dynamic_slice_in_dim(plane, at * rows, rows) != 0
            value, (dq, dk, dw) = grad(q_strip, k_i, w_strip, q_main, k,
                                       keep, scale)
            return (total + value, dk_sum + dk.astype(jnp.float32)), (dq, dw)

        (total, dk), (dq, dw) = lax.scan(
            one, (jnp.zeros((), jnp.float32),
                  jnp.zeros(k_i.shape, jnp.float32)),
            (q_i.reshape(n, rows, heads, d), w.reshape(n, rows, heads),
             q.reshape(n, rows, *q.shape[1:]),
             jnp.arange(n, dtype=jnp.int32)))
        return total, dq.reshape(q_i.shape), dk.astype(k_i.dtype), \
            dw.reshape(w.shape)

    if plane is None:
        total, dq, dk, dw = jax.vmap(
            lambda *x: one_row(*x, None))(q_i, k_i, w, q, k)
    else:
        total, dq, dk, dw = jax.vmap(one_row)(q_i, k_i, w, q, k, plane)
    return total.sum(), (dq, dk, dw)


KL_TILE = 512                       # `index_kl`'s tile, both sides
_KL_VMEM_LIMIT = 64 * 1024 * 1024   # every head's query tile, twice; dk_I
# The names `index_kl`'s forward rule gives the gradient to q_I, k_I and
# w, as the backward rule reads them: all its pass made but the loss,
# which is the call's own value and no residual. A block rematerialised
# under `save_only_these_names(*KL_SAVED_ACROSS_REMAT)` keeps the three
# (`models/decoder.py::_block` does), its recomputed copy of the pass
# has no reader left and the pass runs once a step; with no policy
# asking for them the names lower to nothing.
KL_SAVED_ACROSS_REMAT = ("index_kl_dq", "index_kl_dk", "index_kl_dw")


def _index_kl_kernel(counts_ref, q_ref, lse_ref, k_ref, qi_ref, w_ref, ki_ref,
                     lsei_ref, plane_ref, kl_ref, dqt_ref, dw_ref, dk_ref,
                     kl_acc, dqt_acc, dw_acc, *, scale: float, group: int):
    """One (query tile, key tile) of the indexer's loss and gradient,
    the KEYS along the tile's rows: rows of log-sum-exps and of w lie
    along the lanes and every product is a plain one. q_ref [H, bq, D]
    and lse_ref [H, 1, bq]: every head of the attention; k_ref [H_kv,
    bk, D]; qi_ref [H_I, bq, D_I], w_ref [H_I, 1, bq], ki_ref [bk, D_I],
    lsei_ref [1, bq]: the indexer's; plane_ref [bk, bq] int8. The grid
    is (batch, query tiles, key tiles): kl, dq_I.T and dw accumulate
    over a query tile's key tiles in scratch, dk_I over the whole grid
    of a batch row in its output block."""
    b, qi, ki = pl.program_id(0), pl.program_id(1), pl.program_id(2)
    num_q, num_k = pl.num_programs(1), pl.num_programs(2)
    block_k, block_q = plane_ref.shape

    @pl.when(ki == 0)
    def _():
        kl_acc[...] = jnp.zeros_like(kl_acc)
        dqt_acc[...] = jnp.zeros_like(dqt_acc)
        dw_acc[...] = jnp.zeros_like(dw_acc)

    @pl.when((qi == 0) & (ki == 0))
    def _():
        dk_ref[...] = jnp.zeros_like(dk_ref)

    @pl.when(counts_ref[(b * num_q + qi) * num_k + ki] > 0)
    def _():
        selected = plane_ref[...].astype(jnp.float32) > 0
        zeros = jnp.zeros((block_k, block_q), jnp.float32)

        def head(h, total):     # a selected pair's s - lse is <= 0
            st = lax.dot_general(k_ref[h // group], q_ref[h], _NT,
                                 preferred_element_type=jnp.float32) * scale
            return total + jnp.exp(jnp.minimum(st - lse_ref[h], 0.0))

        p = jnp.where(selected, lax.fori_loop(
            0, q_ref.shape[0], head, zeros) / q_ref.shape[0], 0.0)
        k_i = ki_ref[...]

        def score(j, total):
            pre = lax.dot_general(k_i, qi_ref[j], _NT,
                                  preferred_element_type=jnp.float32)
            return total + w_ref[j] * jnp.maximum(pre, 0.0)

        scores = lax.fori_loop(0, qi_ref.shape[0], score, zeros)
        log_q = jnp.where(scores == 0, 0.0, scores) - lsei_ref[...]
        held = selected & (p > 0)
        kl_acc[...] += jnp.where(
            held, p * (jnp.log(jnp.where(held, p, 1.0)) - log_q),
            0.0).sum(0, keepdims=True)
        d_scores = jnp.where(
            selected, jnp.exp(jnp.minimum(log_q, 0.0)) - p, 0.0)
        k_it = k_i.T

        def push(j, dk):
            q_j = qi_ref[j]
            pre = lax.dot_general(k_i, q_j, _NT,
                                  preferred_element_type=jnp.float32)
            on = pre > 0
            dw_acc[j] += jnp.where(on, d_scores * pre, 0.0).sum(
                0, keepdims=True)
            d_pre = jnp.where(on, d_scores * w_ref[j], 0.0).astype(q_j.dtype)
            dqt_acc[j] += jnp.dot(k_it, d_pre,
                                  preferred_element_type=jnp.float32)
            return dk + jnp.dot(d_pre, q_j,
                                preferred_element_type=jnp.float32)

        dk_ref[pl.ds(ki * block_k, block_k), :] += lax.fori_loop(
            0, qi_ref.shape[0], push,
            jnp.zeros((block_k, k_i.shape[1]), jnp.float32))

    @pl.when(ki == num_k - 1)
    def _():
        kl_ref[...] = kl_acc[...]
        dqt_ref[...] = dqt_acc[...].astype(dqt_ref.dtype)
        dw_ref[...] = dw_acc[...]


def _index_kl_call(q_i, k_i, w, q, k, plane, lse, lse_i, *, scale: float,
                   block: int, interpret: bool):
    """(sum of KL_t a batch row [B], dq_i, dk_i, dw) by the kernel."""
    b, t, heads_i, d_i = q_i.shape
    heads, h_kv, d = q.shape[2], k.shape[2], q.shape[3]
    n = t // block
    f32 = jnp.float32

    def rows(x):    # [B, H, T] -> one [1, block] row a head and query tile
        return x.reshape(*x.shape[:-1], n, 1, block)

    def by_query(*lead):
        """A block a query tile: `lead` sizes whole, then the tile."""
        return lambda i, qi, ki, _: (i,) + (0,) * len(lead) + (qi, 0, 0)

    kl, dqt, dw, dk = pl.pallas_call(
        functools.partial(_index_kl_kernel, scale=scale,
                          group=heads // h_kv),
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=1, grid=(b, n, n),
            in_specs=[
                pl.BlockSpec((None, heads, block, d),
                             lambda i, qi, ki, _: (i, 0, qi, 0)),
                pl.BlockSpec((None, heads, None, 1, block), by_query(heads)),
                pl.BlockSpec((None, h_kv, block, d),
                             lambda i, qi, ki, _: (i, 0, ki, 0)),
                pl.BlockSpec((None, heads_i, block, d_i),
                             lambda i, qi, ki, _: (i, 0, qi, 0)),
                pl.BlockSpec((None, heads_i, None, 1, block),
                             by_query(heads_i)),
                pl.BlockSpec((None, block, d_i),
                             lambda i, qi, ki, _: (i, ki, 0)),
                pl.BlockSpec((None, None, 1, block), by_query()),
                pl.BlockSpec((None, None, None, block, block),
                             lambda i, qi, ki, _: (i, ki, qi, 0, 0))],
            out_specs=[
                pl.BlockSpec((None, None, 1, block), by_query()),
                pl.BlockSpec((None, heads_i, None, d_i, block),
                             by_query(heads_i)),
                pl.BlockSpec((None, heads_i, None, 1, block),
                             by_query(heads_i)),
                pl.BlockSpec((None, t, d_i), lambda i, qi, ki, _: (i, 0, 0))],
            scratch_shapes=[pltpu.VMEM((1, block), f32),
                            pltpu.VMEM((heads_i, d_i, block), f32),
                            pltpu.VMEM((heads_i, 1, block), f32)]),
        out_shape=[jax.ShapeDtypeStruct((b, n, 1, block), f32),
                   jax.ShapeDtypeStruct((b, heads_i, n, d_i, block),
                                        q_i.dtype),
                   jax.ShapeDtypeStruct((b, heads_i, n, 1, block), f32),
                   jax.ShapeDtypeStruct((b, t, d_i), f32)],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "arbitrary", "arbitrary"),
            vmem_limit_bytes=_KL_VMEM_LIMIT),
        interpret=interpret,
        name="index_kl",
    )(tile_counts(plane, block, block).reshape(-1),
      q.transpose(0, 2, 1, 3), rows(lse), k.transpose(0, 2, 1, 3),
      q_i.transpose(0, 2, 1, 3),
      rows(w.astype(f32).transpose(0, 2, 1)), k_i, rows(lse_i),
      plane_tiles(plane, block, block, True))

    def by_row(x):  # [B, H_I, n, width, block] -> [B, T, H_I, width]
        return x.transpose(0, 2, 4, 1, 3).reshape(b, t, heads_i, -1)

    return (kl.sum((1, 2, 3)), by_row(dqt), dk.astype(k_i.dtype),
            by_row(dw)[..., 0].astype(w.dtype))


def _kl_pass(q_i, k_i, w, q, k, plane, lse, lse_i, scale, strip):
    """(sum of KL_t over all rows, (dq_i, dk_i, dw)): the kernel under a
    selection whose tiles divide the shape, else the plain form."""
    t = q_i.shape[1]
    block = min(KL_TILE, t)
    if plane is None or lse is None or t % block or block % 8 \
            or q.shape[3] % 8 or q_i.shape[3] % 8:
        return index_kl_xla(q_i, k_i, w, q, k, plane, scale, strip)
    call = functools.partial(_index_kl_call, scale=scale, block=block,
                             interpret=not is_tpu())
    total, dq, dk, dw = over_leading_dim(call, (True,) * 8)(
        q_i, k_i, w, q, k, plane, lse, lse_i)
    return total.sum(), (dq, dk, dw)


@functools.partial(jax.custom_vjp, nondiff_argnums=(8, 9))
def index_kl(q_i, k_i, w, q, k, plane, lse, lse_i, scale: float,
             strip: int | None = None):
    """The indexer's loss summed over the B * T rows (the caller takes
    the mean). q_i [B, T, H_I, D_I], k_i [B, T, D_I], w [B, T, H_I]: the
    indexer's; q [B, T, H, D], k [B, T, H_kv, D]: the main attention's
    query and key as its kernel reads them (constants here), `scale` its
    scale; `plane` [B, T, T] int8 the selection, `lse` [B, H, T] float32
    the attention's row log-sum-exp over it (`flash_attention`'s second
    result) and `lse_i` [B, T] float32 that of I (`index_select`'s) — or
    all three None where every causal key is selected. The gradient
    reaches q_i, k_i and w alone; the forward rule makes it beside the
    loss and names its three arrays `KL_SAVED_ACROSS_REMAT`, for a
    `jax.checkpoint` around the call to keep (else the pass runs again
    in the recomputed copy)."""
    return _kl_pass(q_i, k_i, w, q, k, plane, lse, lse_i, scale, strip)[0]


def _index_kl_fwd(q_i, k_i, w, q, k, plane, lse, lse_i, scale, strip):
    total, grads = _kl_pass(q_i, k_i, w, q, k, plane, lse, lse_i, scale,
                            strip)
    grads = tuple(map(checkpoint_name, grads, KL_SAVED_ACROSS_REMAT))
    return total, (grads, q, k, plane, lse, lse_i)


def _index_kl_bwd(scale, strip, residuals, g):
    (dq, dk, dw), *constants = residuals

    def zero(x):
        if x is None:
            return None
        return np.zeros(x.shape, jax.dtypes.float0) \
            if jnp.issubdtype(x.dtype, jnp.integer) else jnp.zeros_like(x)

    return ((g * dq).astype(dq.dtype), (g * dk).astype(dk.dtype),
            (g * dw).astype(dw.dtype), *map(zero, constants))


index_kl.defvjp(_index_kl_fwd, _index_kl_bwd)
