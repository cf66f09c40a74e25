"""Training-plane metrics (registered at import so the metrics-registry
drift gate — tests/test_observability.py — can hold ARCHITECTURE.md to
them).

step_dispatch_s is the interval between two step DISPATCHES of an
epoch's loop: input wait (ingest get / loader next) + dispatch. The
loop is asynchronous, so this is NOT a device step; it reads as one only
once the dispatch queue is full and each dispatch waits for a slot.
ingest_wait_s isolates the input half, so "input-bound" reads directly
off the pair (a healthy double-buffered ingest pipeline keeps
ingest_wait_s p50 ~0). optim_shard_bytes is
the per-process optimizer-state footprint — 1/N of the replicated
figure once the weight update is sharded."""

from __future__ import annotations

from ray_tpu._private import stats

STEP_DISPATCH_S = stats.Histogram(
    "train.step_dispatch_s", stats.LATENCY_BOUNDARIES_S,
    "interval between two step dispatches of an epoch's loop, input "
    "wait included (per worker); not a device step: the loop is "
    "asynchronous")

SAMPLES_TOTAL = stats.Count(
    "train.samples_total",
    "training examples (rows of the batch) consumed by dispatched steps "
    "(per worker; samples/s = delta over the metrics history)")

INGEST_WAIT_S = stats.Histogram(
    "train.ingest_wait_s", stats.LATENCY_BOUNDARIES_S,
    "time the step loop blocked waiting for the next prefetched ingest "
    "batch (p50 ~0 = input fully overlapped with compute)")

OPT_SHARD_BYTES = stats.Gauge(
    "train.optim_shard_bytes",
    "bytes of optimizer state held by this worker (the local 1/N shard "
    "under the sharded weight update; the full replicated state "
    "otherwise)")
