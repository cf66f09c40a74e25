"""Ring attention: exact attention over sequence shards with a ppermute
ring (sequence/context parallelism — capability absent from the reference,
SURVEY §2.4; supplied here as a first-class primitive).

Each device on the `sp` axis holds a sequence block of Q, K, V. K/V blocks
rotate around the ring; every step each device accumulates its Q block's
attention against the visiting K/V block with streaming (flash-style)
softmax — max/denominator carried in float32 — so the result is exact
regardless of ring size. Communication (ppermute over ICI) overlaps with
the block matmuls under XLA's latency-hiding scheduler.

Causal masking uses global positions derived from each block's ring
origin, so blocks whose keys are entirely in the future are fully masked
(they still transit the ring — uniform schedule keeps the ICI pattern
static and XLA-friendly).
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.sharding import Mesh, PartitionSpec as P


def _block_attn(q, k, v, o, m, l, q_offset, kv_offset, causal, scale):
    """One streaming-softmax accumulation step.

    q: [B, Tq, H, D]   k/v: [B, Tk, H, D]
    o: [B, Tq, H, D] f32 accumulator, m/l: [B, H, Tq] f32 running max/denom.
    """
    scores = jnp.einsum("bqhd,bkhd->bhqk", q, k,
                        preferred_element_type=jnp.float32) * scale
    if causal:
        tq, tk = q.shape[1], k.shape[1]
        q_pos = q_offset + jnp.arange(tq)[:, None]
        k_pos = kv_offset + jnp.arange(tk)[None, :]
        mask = q_pos >= k_pos
        scores = jnp.where(mask[None, None], scores, -jnp.inf)
    block_max = jnp.max(scores, axis=-1)  # [B,H,Tq]
    new_m = jnp.maximum(m, block_max)
    # fully-masked rows have new_m == -inf; keep exp() finite
    safe_m = jnp.where(jnp.isfinite(new_m), new_m, 0.0)
    p = jnp.exp(scores - safe_m[..., None])  # [B,H,Tq,Tk]
    if causal:
        p = jnp.where(jnp.isfinite(scores), p, 0.0)
    correction = jnp.where(jnp.isfinite(m), jnp.exp(m - safe_m), 0.0)
    l_new = l * correction + p.sum(-1)
    pv = jnp.einsum("bhqk,bkhd->bqhd", p, v,
                    preferred_element_type=jnp.float32)
    o_new = o * correction.transpose(0, 2, 1)[..., None] + pv
    return o_new, new_m, l_new


def ring_attention(q, k, v, *, axis_name: str = "sp", causal: bool = True,
                   scale: float | None = None):
    """Call INSIDE shard_map: q,k,v are local blocks [B, T_local, H, D]
    sharded along T over `axis_name`. Returns the local output block."""
    sp = jax.lax.axis_size(axis_name)
    idx = jax.lax.axis_index(axis_name)
    b, t_local, h, d = q.shape
    if scale is None:
        scale = d ** -0.5
    o = jnp.zeros(q.shape, jnp.float32)
    m = jnp.full((b, h, t_local), -jnp.inf, jnp.float32)
    l = jnp.zeros((b, h, t_local), jnp.float32)
    perm = [(i, (i + 1) % sp) for i in range(sp)]
    q_offset = idx * t_local
    for step in range(sp):
        kv_origin = (idx - step) % sp
        o, m, l = _block_attn(q, k, v, o, m, l,
                              q_offset, kv_origin * t_local, causal, scale)
        if step != sp - 1:
            k = jax.lax.ppermute(k, axis_name, perm)
            v = jax.lax.ppermute(v, axis_name, perm)
    denom = jnp.where(l > 0, l, 1.0).transpose(0, 2, 1)[..., None]
    return (o / denom).astype(q.dtype)


def ring_attention_sharded(q, k, v, mesh: Mesh, *, causal: bool = True,
                           batch_axis: str = "dp", seq_axis: str = "sp",
                           head_axis: str = "tp"):
    """Driver-level entry: q,k,v are global [B, T, H, D]; batch sharded over
    dp, sequence over sp, heads over tp."""
    spec = P(batch_axis, seq_axis, head_axis, None)
    fn = jax.shard_map(
        functools.partial(ring_attention, axis_name=seq_axis, causal=causal),
        mesh=mesh, in_specs=(spec, spec, spec), out_specs=spec,
        check_vma=False)
    return fn(q, k, v)


def reference_attention(q, k, v, causal: bool = True,
                        scale: float | None = None):
    """Dense reference used in tests and as the sp=1 fast path."""
    d = q.shape[-1]
    if scale is None:
        scale = d ** -0.5
    scores = jnp.einsum("bqhd,bkhd->bhqk", q, k,
                        preferred_element_type=jnp.float32) * scale
    if causal:
        tq, tk = q.shape[1], k.shape[1]
        mask = jnp.arange(tq)[:, None] >= jnp.arange(tk)[None, :]
        scores = jnp.where(mask[None, None], scores, -jnp.inf)
    probs = jax.nn.softmax(scores, axis=-1)
    return jnp.einsum("bhqk,bkhd->bqhd", probs, v).astype(q.dtype)
