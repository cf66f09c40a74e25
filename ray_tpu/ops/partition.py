"""Run a Pallas kernel per device under a sharded jit.

A Mosaic kernel is opaque to XLA's SPMD partitioner: inside a jit whose
operands are sharded over a multi-device mesh (the training operator's
mesh step) lowering fails on the chip with "Mosaic kernels cannot be
automatically partitioned. Please wrap the call in a shard_map" —
interpret mode never shows it, because there the kernel lowers to
ordinary ops. The kernels here are independent over their leading
(batch) dimension, so each device can run the kernel on its own rows.

Which mesh axes split the batch is not guessed here. The layer that
shards the batch declares it while its step is traced
(`batch_sharded`, set by `TrainingOperator` from its `batch_spec`);
with nothing declared the kernel is called as it is.
"""

from __future__ import annotations

import contextlib
import contextvars

import jax
from jax.sharding import PartitionSpec as P

# (mesh, the mesh axes dimension 0 of a batch is split over)
_DECLARED = contextvars.ContextVar("ray_tpu_batch_sharding", default=None)


@contextlib.contextmanager
def batch_sharded(mesh, batch_spec: P):
    """While tracing inside: batches are laid out over `mesh` with
    `batch_spec` (only its entry for dimension 0 matters here)."""
    token = _DECLARED.set((mesh, batch_spec[0] if len(batch_spec) else None))
    try:
        yield
    finally:
        _DECLARED.reset(token)


def over_leading_dim(fn, split: tuple[bool, ...]):
    """`fn(*args) -> array, or a tuple of them` (the attention forward's
    output and log-sum-exp, its backward's dq, dk, dv) with dimension 0
    of every result and of every arg flagged in `split` divided the way
    the declared batch is;
    unflagged args (weights) are whole on every device. Every mesh axis
    is manual inside (Mosaic accepts nothing less): over an axis the
    batch is not split on, devices repeat the same rows."""

    def call(*args):
        declared = _DECLARED.get()
        if declared is None:
            return fn(*args)
        mesh, axes = declared
        rows = P(axes)
        return jax.shard_map(
            fn, mesh=mesh, in_specs=tuple(rows if s else P() for s in split),
            out_specs=rows, check_vma=False)(*args)

    return call
