"""Flash attention as a Pallas TPU kernel.

Blockwise streaming-softmax attention: Q blocks stream through VMEM, K/V
are scanned in blocks, the MXU does the two matmuls per block, and the
running (max, denom) accumulators live in f32 — the standard flash
schedule, written for the TPU memory hierarchy (HBM→VMEM via BlockSpecs).

Backward uses recompute (custom_vjp whose bwd re-runs dense attention in
checkpointed blocks) — flash-style memory: nothing but (q, k, v, o, lse) is
saved. On CPU (tests) the kernel runs in interpret mode.

Two things beyond the plain causal kernel, both off by default: a
sliding `window` (query i sees keys j with 0 <= i - j < window; the
forward's K loop starts at the first block the window reaches, the
backward gives a query block a key slice of fixed length window + block
instead of all T), and grouped-query heads (k and v with fewer heads
than q: query head g reads key/value head g // (H // H_kv), chosen in
the BlockSpec index map, so no repeated K/V is ever written to HBM).
With `window=None` and equal head counts the traced program is the one
this file built before either existed.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

from ray_tpu._private.accelerator import is_tpu
from ray_tpu.ops.partition import over_leading_dim

NEG_INF = -1e30
# Query rows a step of the windowed / grouped backward takes.
BWD_BLOCK_Q = 64


def _flash_kernel(q_ref, k_ref, v_ref, o_ref, *, block_k: int, causal: bool,
                  scale: float, window: int | None = None):
    qi = pl.program_id(1)
    q = q_ref[...]  # [block_q, d]
    t = k_ref.shape[0]
    d = q.shape[-1]
    block_q = q.shape[0]

    def body(ki, carry):
        o, m, l = carry
        k = k_ref[pl.ds(ki * block_k, block_k), :]  # [block_k, d]
        v = v_ref[pl.ds(ki * block_k, block_k), :]
        s = jax.lax.dot_general(
            q, k, (((1,), (1,)), ((), ())),
            preferred_element_type=jnp.float32) * scale  # [bq, bk]
        if causal:
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

    o0 = jnp.zeros((block_q, d), jnp.float32)
    m0 = jnp.full((block_q,), NEG_INF, jnp.float32)
    l0 = jnp.zeros((block_q,), jnp.float32)
    num_k = t // block_k
    if causal:
        # only scan K blocks at or before this Q block
        if block_q % block_k:
            # the block holding this Q block's last row, exactly
            last = ((qi + 1) * block_q + block_k - 1) // block_k
        else:   # the expression this kernel always had, text for text
            last = (qi + 1) * block_q // block_k + (block_q % block_k != 0)
        num_k_active = jnp.minimum(num_k, last)
        # ... and, under a window, at or after the first block the
        # block's first query still reaches (a row whose keys all lie in
        # later blocks accumulates exp(0) there; the first real score
        # rescales that to nothing, as a causal row's masked tail does)
        first = 0 if window is None else jnp.maximum(
            0, qi * block_q - (window - 1)) // block_k
        o, m, l = jax.lax.fori_loop(first, num_k_active, body, (o0, m0, l0))
    else:
        o, m, l = jax.lax.fori_loop(0, num_k, body, (o0, m0, l0))
    denom = jnp.where(l > 0, l, 1.0)
    o_ref[...] = (o / denom[:, None]).astype(o_ref.dtype)


def _flash_aligned(t: int, d: int, block_q: int, block_k: int) -> bool:
    """Mosaic constraints: K/V dynamic-slice starts must be provably
    8-aligned (sublane) and the lane dim 128-padded; unaligned shapes go
    through the dense path (short sequences — dense is fine there)."""
    block_q = min(block_q, t)
    block_k = min(block_k, t)
    return (t % block_q == 0 and t % block_k == 0
            and block_q % 8 == 0 and block_k % 8 == 0 and d % 8 == 0)


def _flash_fwd_impl(q, k, v, *, causal: bool, scale: float, block_q: int,
                    block_k: int, interpret: bool, window: int | None = None):
    b, t, h, d = q.shape
    plain = window is None and k.shape[2] == h
    if not plain and (not causal or h % k.shape[2]):
        raise ValueError(
            "flash_attention: a window and grouped heads need causal=True, "
            f"and the {h} query heads a multiple of the {k.shape[2]} "
            "key/value heads")
    if not _flash_aligned(t, d, block_q, block_k):
        if t >= 512:
            import warnings

            warnings.warn(
                f"flash_attention: seq {t} / head_dim {d} not tile-aligned;"
                " falling back to dense O(T^2) attention — pad the sequence"
                " to a multiple of 8 for the pallas kernel", stacklevel=2)
        if plain:
            return _dense_attention(q, k, v, causal, scale)
        return _dense_grouped(q, k, v, scale, 0, 0, window)
    block_q = min(block_q, t)
    block_k = min(block_k, t)
    call = functools.partial(_flash_call, causal=causal, scale=scale,
                             block_q=block_q, block_k=block_k,
                             interpret=interpret)
    if not plain:
        call = functools.partial(call, window=window)
    # batch rows are independent kernel instances: under a sharded jit
    # each device runs the kernel on its own [b, T, H, D] slice
    return over_leading_dim(call, (True, True, True))(q, k, v)


def _flash_call(q, k, v, *, causal: bool, scale: float, block_q: int,
                block_k: int, interpret: bool, window: int | None = None):
    b, t, h, d = q.shape
    h_kv = k.shape[2]
    # fold batch and heads; layout [B*H, T, D]
    qf = q.transpose(0, 2, 1, 3).reshape(b * h, t, d)
    kf = k.transpose(0, 2, 1, 3).reshape(b * h_kv, t, d)
    vf = v.transpose(0, 2, 1, 3).reshape(b * h_kv, t, d)

    kernel = functools.partial(_flash_kernel, block_k=block_k,
                               causal=causal, scale=scale)
    if window is not None:
        kernel = functools.partial(kernel, window=window)
    if h_kv == h:
        def kv_index(bh, qi):
            return (bh, 0, 0)
    else:
        # query head g reads key/value head g // group: consecutive
        # query heads of a group map to one block, fetched once
        group = h // h_kv

        def kv_index(bh, qi):
            return ((bh // h) * h_kv + (bh % h) // group, 0, 0)
    out = pl.pallas_call(
        kernel,
        grid=(b * h, t // block_q),
        in_specs=[
            pl.BlockSpec((None, block_q, d), lambda bh, qi: (bh, qi, 0)),
            pl.BlockSpec((None, t, d), kv_index),
            pl.BlockSpec((None, t, d), kv_index),
        ],
        out_specs=pl.BlockSpec((None, block_q, d), lambda bh, qi: (bh, qi, 0)),
        out_shape=jax.ShapeDtypeStruct((b * h, t, d), q.dtype),
        interpret=interpret,
        name="flash_fwd",
    )(qf, kf, vf)
    return out.reshape(b, h, t, d).transpose(0, 2, 1, 3)


def _dense_attention(q, k, v, causal, scale, q_offset=0, pad_mask=None):
    """Reference/fallback path. q_offset shifts the causal mask (used by
    the blockwise backward); pad_mask: [B, Tk] bool, True = real token."""
    scores = jnp.einsum("bqhd,bkhd->bhqk", q, k,
                        preferred_element_type=jnp.float32) * scale
    if causal:
        tq, tk = q.shape[1], k.shape[1]
        mask = (q_offset + jnp.arange(tq))[:, None] >= jnp.arange(tk)[None, :]
        scores = jnp.where(mask[None, None], scores, NEG_INF)
    if pad_mask is not None:
        scores = jnp.where(pad_mask[:, None, None, :], scores, NEG_INF)
    probs = jax.nn.softmax(scores, axis=-1)
    return jnp.einsum("bhqk,bkhd->bqhd", probs, v).astype(q.dtype)


def _dense_grouped(q, k, v, scale, q_offset, k_offset, window):
    """Causal attention of a query block against a key slice, by absolute
    positions (`q_offset`, `k_offset`: the first row's and first key's),
    under an optional window, with k and v of fewer heads than q (query
    head g reads key/value head g // group). The backward's block and
    the unaligned fallback; scores in float32."""
    b, tq, h, d = q.shape
    tk, h_kv = k.shape[1], k.shape[2]
    qg = q.reshape(b, tq, h_kv, h // h_kv, d)
    scores = jnp.einsum("bqkgd,bskd->bkgqs", qg, k,
                        preferred_element_type=jnp.float32) * scale
    q_pos = (q_offset + jnp.arange(tq))[:, None]
    k_pos = (k_offset + jnp.arange(tk))[None, :]
    keep = q_pos >= k_pos
    if window is not None:
        keep &= q_pos - k_pos < window
    scores = jnp.where(keep[None, None, None], scores, NEG_INF)
    probs = jax.nn.softmax(scores, axis=-1)
    out = jnp.einsum("bkgqs,bskd->bqkgd", probs, v)
    return out.reshape(b, tq, h, d).astype(q.dtype)


def masked_attention(q, k, v, pad_mask, causal=False, scale=None):
    """Attention with key padding mask (BERT-style batches). pad_mask:
    [B, T] bool. Dense path — padded fine-tune batches are short."""
    scale = scale if scale is not None else q.shape[-1] ** -0.5
    return _dense_attention(q, k, v, causal, scale, pad_mask=pad_mask)


@functools.partial(jax.custom_vjp, nondiff_argnums=(3, 4, 5, 6, 7))
def flash_attention(q, k, v, causal: bool = True, scale: float | None = None,
                    block_q: int = 128, block_k: int = 128,
                    window: int | None = None):
    """q: [B, T, H, D]; k, v: [B, T, H_kv, D] with H a multiple of H_kv
    (query head g reads key/value head g // (H // H_kv)). `window`:
    query i sees keys j with 0 <= i - j < window (needs causal).
    Returns [B, T, H, D]."""
    actual_scale = scale if scale is not None else q.shape[-1] ** -0.5
    return _flash_fwd_impl(q, k, v, causal=causal, scale=actual_scale,
                           block_q=block_q, block_k=block_k,
                           interpret=not is_tpu(), window=window)


def _fwd(q, k, v, causal, scale, block_q, block_k, window):
    out = flash_attention(q, k, v, causal, scale, block_q, block_k, window)
    return out, (q, k, v)


def _bwd_grouped(scale, window, q, k, v, g):
    """The backward under a window or grouped heads: scan over Q blocks,
    each against the ONE key slice its mask can reach, accumulating
    dk/dv into that slice. Past the window that slice is window + block
    keys ending with the block's last row: work and the float32 score
    tile are linear in the window, not in T. The blocks before that —
    all of them under the causal mask alone — see every key up to their
    last row, and go in up to four stages, each against the keys its
    LAST block reaches (a quarter, a half, ... of the prefix: five
    eighths of the work of giving each the whole prefix, where the mask
    keeps a half). The block is this path's own (`BWD_BLOCK_Q`), not the
    forward kernel's."""
    b, t, h, d = q.shape
    bq = min(BWD_BLOCK_Q, t)
    if t % bq:
        def f(q, k, v):
            return _dense_grouped(q, k, v, scale, 0, 0, window)

        _, vjp = jax.vjp(jax.checkpoint(f), q, k, v)
        return vjp(g)
    n = t // bq
    # (first block, blocks, keys in the slice) of every stage
    prefix = n if window is None else min(n, window // bq)
    stages = next(s for s in (4, 2, 1) if prefix % s == 0)
    plan = [(s * prefix // stages, prefix // stages,
             (s + 1) * prefix // stages * bq)
            for s in range(stages) if prefix]
    if prefix < n:
        plan.append((prefix, n - prefix,
                     min(t, -(-(window - 1) // bq) * bq + bq)))
    qb = jnp.moveaxis(q.reshape(b, n, bq, h, d), 1, 0)   # [n, B, bq, H, D]
    gb = jnp.moveaxis(g.reshape(b, n, bq, h, d), 1, 0)

    def stage(span):
        def body(carry, inp):
            dk, dv = carry
            i, q_blk, g_blk = inp
            lo = jnp.clip((i + 1) * bq - span, 0, t - span)
            k_sl = jax.lax.dynamic_slice_in_dim(k, lo, span, axis=1)
            v_sl = jax.lax.dynamic_slice_in_dim(v, lo, span, axis=1)

            def f(q_blk, k_sl, v_sl):
                return _dense_grouped(q_blk, k_sl, v_sl, scale, i * bq, lo,
                                      window)

            _, vjp = jax.vjp(f, q_blk, k_sl, v_sl)
            dq_blk, dk_i, dv_i = vjp(g_blk)

            def add(acc, part):
                old = jax.lax.dynamic_slice_in_dim(acc, lo, span, axis=1)
                return jax.lax.dynamic_update_slice_in_dim(
                    acc, old + part, lo, axis=1)

            return (add(dk, dk_i), add(dv, dv_i)), dq_blk

        return body

    carry = (jnp.zeros_like(k, jnp.float32), jnp.zeros_like(v, jnp.float32))
    dq = []
    for first, count, span in plan:
        carry, dq_stage = jax.lax.scan(
            stage(span), carry, (first + jnp.arange(count),
                                 qb[first:first + count],
                                 gb[first:first + count]))
        dq.append(dq_stage)
    dk, dv = carry
    dq = jnp.moveaxis(jnp.concatenate(dq), 0, 1).reshape(b, t, h, d)
    return dq.astype(q.dtype), dk.astype(k.dtype), dv.astype(v.dtype)


def _bwd(causal, scale, block_q, block_k, window, residuals, g):
    """Blockwise-remat backward: scan over Q blocks, each recomputing its
    attention against full K/V and accumulating dk/dv. Peak extra memory is
    one [B, H, block_q, T] score block (linear in T), not the full T×T
    matrix — flash-style memory from only (q, k, v) residuals."""
    q, k, v = residuals
    actual_scale = scale if scale is not None else q.shape[-1] ** -0.5
    if window is not None or k.shape[2] != q.shape[2]:
        return _bwd_grouped(actual_scale, window, q, k, v, g)
    b, t, h, d = q.shape
    bq = min(block_q, t)

    if t % bq:
        # unaligned fallback: single checkpointed dense block
        def f(q, k, v):
            return _dense_attention(q, k, v, causal, actual_scale)

        _, vjp = jax.vjp(jax.checkpoint(f), q, k, v)
        return vjp(g)

    n = t // bq
    qb = jnp.moveaxis(q.reshape(b, n, bq, h, d), 1, 0)   # [n, B, bq, H, D]
    gb = jnp.moveaxis(g.reshape(b, n, bq, h, d), 1, 0)

    def body(carry, inp):
        dk, dv = carry
        i, q_blk, g_blk = inp

        def f(q_blk, k, v):
            return _dense_attention(q_blk, k, v, causal, actual_scale,
                                    q_offset=i * bq)

        _, vjp = jax.vjp(f, q_blk, k, v)
        dq_blk, dk_i, dv_i = vjp(g_blk)
        return (dk + dk_i, dv + dv_i), dq_blk

    (dk, dv), dq = jax.lax.scan(
        body, (jnp.zeros_like(k, jnp.float32), jnp.zeros_like(v, jnp.float32)),
        (jnp.arange(n), qb, gb))
    dq = jnp.moveaxis(dq, 0, 1).reshape(b, t, h, d)
    return dq.astype(q.dtype), dk.astype(k.dtype), dv.astype(v.dtype)


flash_attention.defvjp(_fwd, _bwd)
