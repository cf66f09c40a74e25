"""Pipeline parallelism: GPipe microbatch schedule over the `pp` mesh axis
(capability absent from the reference, SURVEY §2.4 — nearest analog was
streaming channels N16).

Each device on the pp axis holds one stage's parameters (stacked leading
`stage` axis sharded over pp). Activations flow stage-to-stage with
ppermute; the schedule runs M + P - 1 ticks for M microbatches over P
stages. Everything is a static python loop — XLA sees a fixed ICI
communication pattern it can software-pipeline.

Backward just works: jax differentiates through ppermute, producing the
mirrored reverse schedule.
"""

from __future__ import annotations

import functools
from typing import Callable

import jax
import jax.numpy as jnp
from jax.sharding import Mesh, PartitionSpec as P


def _pipeline_local(stage_params, x_micro, *, stage_fn: Callable,
                    axis_name: str):
    """Runs inside shard_map. stage_params: this stage's params (leading
    stage axis already sliced to size 1 — squeezed here). x_micro:
    [M, mb, ...] microbatched input (replicated; only stage 0 reads it).
    Returns [M, mb, ...] outputs (replicated via masked psum)."""
    pp = jax.lax.axis_size(axis_name)
    idx = jax.lax.axis_index(axis_name)
    params = jax.tree.map(lambda p: p[0], stage_params)
    m = x_micro.shape[0]
    perm = [(i, (i + 1) % pp) for i in range(pp)]

    carry = jnp.zeros_like(x_micro[0])  # inter-stage activation register
    outputs = jnp.zeros_like(x_micro)
    for tick in range(m + pp - 1):
        # stage 0 injects microbatch `tick` (if still in range)
        inject = x_micro[jnp.minimum(tick, m - 1)]
        stage_in = jnp.where(idx == 0,
                             jnp.where(tick < m, inject, jnp.zeros_like(inject)),
                             carry)
        y = stage_fn(params, stage_in)
        # last stage commits microbatch (tick - pp + 1)
        out_slot = tick - (pp - 1)
        if 0 <= out_slot < m:
            commit = jnp.where(idx == pp - 1, 1.0, 0.0)
            outputs = outputs.at[out_slot].add(
                (commit * y).astype(outputs.dtype))
        carry = jax.lax.ppermute(y, axis_name, perm)
    # replicate last-stage outputs to all pp ranks
    return jax.lax.psum(outputs, axis_name)


def pipeline_apply(stage_fn: Callable, stage_params, x, *,
                   mesh: Mesh, num_microbatches: int, axis_name: str = "pp",
                   data_axis: str = "dp"):
    """stage_fn(params, x) -> y with matching x/y shapes (transformer-block
    stack). stage_params: pytree with leading `stage` axis of size pp.
    x: [B, ...] global batch (sharded over dp)."""
    b = x.shape[0]
    if b % num_microbatches:
        raise ValueError("batch not divisible by num_microbatches")
    x_micro = x.reshape((num_microbatches, b // num_microbatches)
                        + x.shape[1:])

    fn = jax.shard_map(
        functools.partial(_pipeline_local, stage_fn=stage_fn,
                          axis_name=axis_name),
        mesh=mesh,
        in_specs=(P(axis_name), P(None, data_axis)),
        out_specs=P(None, data_axis),
        check_vma=False,
    )
    y_micro = fn(stage_params, x_micro)
    return y_micro.reshape((b,) + y_micro.shape[2:])


def stack_stage_params(per_stage_params: list):
    """Stack per-stage param pytrees along a new leading `stage` axis."""
    return jax.tree.map(lambda *xs: jnp.stack(xs), *per_stage_params)
