"""Collective types (reference: python/ray/util/collective/types.py)."""

from __future__ import annotations

import enum


class Backend(str, enum.Enum):
    """Collective backends.

    XLA — in-process device-mesh collectives (the ICI path): ops compile to
          XLA collectives (psum/all_gather/...) over a jax Mesh; this is the
          TPU-native replacement for the reference's NCCL backend
          (reference: collective_group/nccl_collective_group.py:115).
    HOST — cross-process CPU collectives over TCP with GCS rendezvous (the
          gloo-equivalent; also the DCN stand-in between TPU hosts).
    AUTO — XLA when the group is a single process with >1 device, else HOST.
    """

    XLA = "xla"
    HOST = "host"
    AUTO = "auto"


class Transport(str, enum.Enum):
    """HOST-backend data-plane tiers (selected per op by payload size and
    node placement; pin one with HostGroup(transport=...) or the
    RAY_TPU_COLLECTIVE_TRANSPORT env var — tests and the perf A/B do).

    HUB — star topology through rank 0's socket; latency-optimal for
          control-sized tensors, carries every op kind.
    RING — direct rank-to-rank TCP ring, chunk-pipelined and zero-copy;
          the bandwidth path for large tensors across nodes.
    SHM — one mmap'd tmpfs segment per group when every rank shares a
          node: collectives become pure memory traffic.
    DEVICE — the accelerator's own interconnect: when every rank's
          payload is a jax.Array and the group's processes share one
          jax runtime (parallel/multihost), ops dispatch through cached
          jitted shard_map collectives (psum/all_gather/psum_scatter)
          so bytes ride ICI/XLA without touching host RAM
          (backends/xla_backend.DeviceTransport).
    PALLAS — the fused-kernel refinement of the device plane for
          SMALL latency-critical ops (decode-step allreduce, small grad
          buckets): the whole quantized/exact ring schedule — chunk,
          DMA to the ICI neighbor, combine, relay-gather — runs inside
          ONE pallas_call (backends/pallas_backend.PallasTransport), so
          an op is one kernel launch instead of a shard_map dispatch
          graph. Ops above `pallas_max_bytes` fall through to DEVICE;
          a pallas pin therefore behaves like a device pin for large
          payloads and for the op kinds the kernel tier does not carry
          (broadcast).
    AUTO — pallas for small device arrays when the runtime spans the
          group, else device, else shm when node-local, else ring,
          else hub.
    """

    AUTO = "auto"
    HUB = "hub"
    RING = "ring"
    SHM = "shm"
    DEVICE = "device"
    PALLAS = "pallas"


class ReduceOp(str, enum.Enum):
    SUM = "sum"
    PRODUCT = "product"
    MIN = "min"
    MAX = "max"
    MEAN = "mean"  # TPU-native addition: fused mean avoids a divide pass


_NUMPY_REDUCE = {
    ReduceOp.SUM: "add",
    ReduceOp.PRODUCT: "multiply",
    ReduceOp.MIN: "minimum",
    ReduceOp.MAX: "maximum",
}

# Block-scaled int8 quantization (EQuARX-style): payloads are cut into
# QUANT_BLOCK-element blocks, each carried on the wire as int8 values
# plus one float32 scale (absmax/127); the reduce happens on the
# dequantized float32 values.  Shared by the host ring's quantized chunk
# format and the device tier's quantized ppermute ring so both planes
# agree on the wire granularity (and the analytic error bound).
QUANT_BLOCK = 256
QUANTIZE_INT8 = "int8"


def is_jax_array(tensor) -> bool:
    """True for jax.Arrays WITHOUT importing jax in pure-host processes:
    if jax was never imported, the payload cannot be one. The single
    probe behind the public-API payload prep and the DEVICE-tier
    routing — they must never disagree about what counts as a device
    array."""
    import sys

    jmod = sys.modules.get("jax")
    return jmod is not None and isinstance(tensor, jmod.Array)


def normalize_quantize(quantize) -> str | None:
    """Canonicalize the `quantize=` knob: None/""/"none"/False mean
    exact; "int8" selects block-scaled int8. Anything else is a typo
    that must fail loudly (a silently-ignored lossy knob would corrupt
    an A/B)."""
    if quantize in (None, False, "", "none"):
        return None
    if str(quantize).lower() == QUANTIZE_INT8:
        return QUANTIZE_INT8
    raise ValueError(
        f"unknown quantize mode {quantize!r} (expected None or 'int8')")
