"""Ulysses sequence parallelism: all-to-all head/sequence transposition
(DeepSpeed-Ulysses; capability absent from the reference, SURVEY §2.4 —
supplied as the second SP primitive next to ring attention).

Each device on the `sp` axis holds a sequence shard [B, S/sp, H, D]. One
all_to_all re-partitions to [B, S, H/sp, D] — full sequence, head shard —
so every device runs ordinary (flash-able) attention for its heads with
NO inner communication; a second all_to_all transposes back. Total
traffic is 2 all-to-alls of the activation (vs ring attention's sp-step
ppermute pipeline): cheaper on all-to-all-friendly fabrics and for short
rings, while ring attention wins when S is huge and overlap matters —
that trade-off is why both exist.

Constraint: num_heads % sp == 0 (heads are the second shard axis)."""

from __future__ import annotations

import functools

import jax
from jax import lax
from jax.sharding import Mesh, PartitionSpec as P

from ray_tpu.parallel.ring_attention import reference_attention


def ulysses_attention(q, k, v, *, axis_name: str = "sp",
                      causal: bool = True, scale: float | None = None):
    """Call INSIDE shard_map: q,k,v local [B, S_local, H, D], sequence
    sharded over `axis_name`. Returns the local output shard."""
    sp = jax.lax.axis_size(axis_name)
    b, s_local, h, d = q.shape
    if h % sp:
        raise ValueError(
            f"ulysses needs num_heads divisible by the sp axis "
            f"({h} % {sp} != 0); use ring_attention instead")
    if sp == 1:
        return reference_attention(q, k, v, causal=causal, scale=scale)

    def seq_to_head(x):
        # [B, S/sp, H, D] -> [B, S, H/sp, D]: split heads across the
        # axis, gather the full sequence
        return lax.all_to_all(x, axis_name, split_axis=2, concat_axis=1,
                              tiled=True)

    qg, kg, vg = seq_to_head(q), seq_to_head(k), seq_to_head(v)
    out = reference_attention(qg, kg, vg, causal=causal, scale=scale)
    # [B, S, H/sp, D] -> [B, S/sp, H, D]
    return lax.all_to_all(out, axis_name, split_axis=1, concat_axis=2,
                          tiled=True)


def ulysses_attention_sharded(q, k, v, mesh: Mesh, *,
                              causal: bool = True,
                              batch_axis: str = "dp",
                              seq_axis: str = "sp"):
    """Driver-level entry: q,k,v global [B, S, H, D]; batch over dp,
    sequence over sp (heads stay replicated outside, sharded inside)."""
    spec = P(batch_axis, seq_axis, None, None)
    fn = jax.shard_map(
        functools.partial(ulysses_attention, axis_name=seq_axis,
                          causal=causal),
        mesh=mesh, in_specs=(spec, spec, spec), out_specs=spec,
        check_vma=False)
    return fn(q, k, v)
