"""Transport.PALLAS: fused ICI ring collectives as single Pallas kernels.

The DEVICE tier (xla_backend._DeviceOps) expresses the EQuARX-style
quantized ring as a shard_map graph: one XLA op per quantize /
ppermute / dequantize / combine step, re-dispatched per hop. That is
the right shape for bandwidth-bound payloads, but a decode-step
allreduce (KBs, every token) pays the whole dispatch stack per op.
This tier fuses the ENTIRE schedule — quantize, `make_async_remote_copy`
DMA to the ICI ring neighbor, dequantize+combine, repeat for the
reduce-scatter phase, then quantize-once relay-gather — into ONE
`pallas_call`, so a small collective is a single kernel launch.

Kernel schedule (w ranks, per-rank flat payload split into w chunks of
C elements):

  reduce-scatter: acc := own chunk; for s in 1..w-1:
      [quantize acc ->] stage in a write-once send slot -> DMA to the
      right neighbor's recv slot for THIS hop -> wait on that slot's
      recv semaphore -> acc := combine(recv [dequantized], chunk
      (rank - s) mod w).  After w-1 hops rank r holds the reduced
      chunk (r+1) mod w (delta=0 schedule, same as the DEVICE qring).
  relay-gather: [quantize acc ONCE ->] w-1 relay hops forwarding the
      SAME bytes, every rank writes the received chunk into its output
      row — so in the quantized arm all ranks dequantize identical
      data and outputs agree bitwise across ranks.

Comm-slot discipline: every hop sends from one slot and receives into
a DIFFERENT slot, and no slot is written twice within one kernel
invocation (recv slot == hop index; staged sends are write-once).
A single slot serving as both DMA src and dst — or a 2-slot double
buffer reused across hops — races on real hardware: hop-lockstep is
enforced only by each rank's own recv wait, so an upstream neighbor
can run several hops ahead and its inbound DMA would overwrite bytes
the local outbound send engine is still reading. Unique slots make
that impossible by construction (the payloads here are small — this
is the latency tier — so O(world) slots of chunk size are cheap).

Neighbor ids ride scalar prefetch (`PrefetchScalarGridSpec`): the ring
position comes from `jax.lax.axis_index` OUTSIDE the kernel — a traced
value cannot be closure-captured by the kernel body.

Interpreter-mode contract: with `interpret=True` the remote-DMA
primitive discharges to `lax.all_gather` + dynamic indexing over the
mapped axis — real XLA collectives — so the IDENTICAL kernel runs on
CPU (including across jax.distributed process groups over gloo) and is
bit-exactness- and chaos-tested in tier-1. That is the only way the
tier has ever run: for a v5e the chip's compiler REFUSES these kernels
(direct loads from ANY-space refs; they would have to stage through
VMEM scratch with local async copies), so on a live TPU backend
`pallas_supported()` is False and the tier votes itself unavailable —
it never runs interpreted on a chip.

PallasTransport subclasses DeviceTransport so every host-semantics
guarantee (integer MEAN promoting to float64 on the host, f16 MEAN
accumulating in f32, hub-style reducescatter splits, quantized-ring
padding) is inherited verbatim — only the op bodies change. Ops the
kernel tier does not carry (broadcast, shift_right, uneven
reducescatter fallbacks) delegate to an embedded _DeviceOps, which is
also the documented fallthrough for payloads above the routing layer's
`pallas_max_bytes` threshold.
"""

from __future__ import annotations

import functools

import numpy as np

from ray_tpu.collective.types import QUANT_BLOCK, ReduceOp

try:  # pragma: no cover - import guard mirrors xla_backend
    import jax
    import jax.numpy as jnp
except Exception:  # noqa: BLE001 - jax missing: the vote never turns 1
    jax = None
    jnp = None

from ray_tpu._private.accelerator import is_tpu  # noqa: E402
from ray_tpu.collective.backends.xla_backend import (  # noqa: E402
    DeviceTransport, _DeviceOps, _shard_map, dequantize_blocks,
    quantize_blocks)

# combine step per reduce op inside the fused kernel (MEAN accumulates
# with add; the wrapper divides by world afterwards — DeviceTransport
# semantics)
_PALLAS_COMBINE = {
    ReduceOp.SUM: "add",
    ReduceOp.MEAN: "add",
    ReduceOp.MAX: "max",
    ReduceOp.MIN: "min",
    ReduceOp.PRODUCT: "mul",
}

_COMBINE_FNS = {
    "add": (lambda a, b: a + b),
    "max": (lambda a, b: jnp.maximum(a, b)),
    "min": (lambda a, b: jnp.minimum(a, b)),
    "mul": (lambda a, b: a * b),
}


def _compiler_params(collective_id: int):
    """Mosaic compiler params (the kernel performs remote DMAs, so it is
    marked side-effecting and carries a collective id); ignored under
    interpret."""
    from jax.experimental.pallas import tpu as pltpu

    return pltpu.CompilerParams(has_side_effects=True,
                                collective_id=collective_id)


def _ring_ids(axis: str, world: int):
    """(me, right-neighbor) as the int32 scalar-prefetch operand."""
    me = jax.lax.axis_index(axis).astype(jnp.int32)
    return jnp.stack([me, (me + 1) % world])


def _remote_copy(src_buf, src_slot, dst_buf, dst_slot, sem_s, sem_r,
                 right):
    """One ring hop: send src_buf[src_slot] into the right neighbor's
    dst_buf[dst_slot]. Src and dst are ALWAYS distinct slots and the
    semaphores are indexed by the dst slot, so `.wait()` waits on the
    recv semaphore of the slot the inbound DMA actually wrote."""
    from jax.experimental.pallas import tpu as pltpu

    return pltpu.make_async_remote_copy(
        src_ref=src_buf.at[src_slot], dst_ref=dst_buf.at[dst_slot],
        send_sem=sem_s.at[dst_slot], recv_sem=sem_r.at[dst_slot],
        device_id=right, device_id_type=pltpu.DeviceIdType.LOGICAL)


def _make_allreduce_kernel(world: int, chunk: int, combine: str):
    """Fused exact ring allreduce: reduce-scatter + relay-gather, w-1
    hops each. Sends are staged in write-once slots (`stage`), every
    hop receives into its own dedicated slot (`rbuf[hop]`) — no slot
    is reused, so no inbound DMA can overwrite bytes an outbound send
    is still reading."""
    import jax.experimental.pallas as pl

    cmb = _COMBINE_FNS[combine]

    def kernel(ids_ref, x_ref, o_ref, stage, rbuf, sem_s, sem_r):
        my, right = ids_ref[0], ids_ref[1]
        acc = x_ref[0, pl.ds(my * chunk, chunk)]
        for s in range(1, world):
            hop = s - 1
            stage[hop] = acc
            rdma = _remote_copy(stage, hop, rbuf, hop, sem_s, sem_r,
                                right)
            rdma.start()
            rdma.wait()
            acc = cmb(rbuf[hop],
                      x_ref[0, pl.ds(((my - s) % world) * chunk, chunk)])
        o_ref[0, pl.ds(((my + 1) % world) * chunk, chunk)] = acc
        stage[world - 1] = acc
        for s in range(1, world):
            hop = world - 1 + (s - 1)
            # hop 1 relays the staged reduced chunk; later hops relay
            # the previous hop's recv slot (written once, final)
            src_buf, src_slot = ((stage, world - 1) if s == 1
                                 else (rbuf, hop - 1))
            rdma = _remote_copy(src_buf, src_slot, rbuf, hop, sem_s,
                                sem_r, right)
            rdma.start()
            rdma.wait()
            o_ref[0, pl.ds(((my - s + 1) % world) * chunk, chunk)] = \
                rbuf[hop]

    return kernel


def _make_reducescatter_kernel(world: int, chunk: int):
    """Reduce-scatter phase only (SUM), delta=-1 schedule so rank r
    finishes holding reduced chunk r (psum_scatter tiled semantics).
    Same write-once slot discipline as the allreduce kernel."""
    import jax.experimental.pallas as pl

    def kernel(ids_ref, x_ref, o_ref, stage, rbuf, sem_s, sem_r):
        my, right = ids_ref[0], ids_ref[1]
        acc = x_ref[0, pl.ds(((my - 1) % world) * chunk, chunk)]
        for s in range(1, world):
            hop = s - 1
            stage[hop] = acc
            rdma = _remote_copy(stage, hop, rbuf, hop, sem_s, sem_r,
                                right)
            rdma.start()
            rdma.wait()
            acc = rbuf[hop] + x_ref[
                0, pl.ds(((my - 1 - s) % world) * chunk, chunk)]
        o_ref[0, :] = acc

    return kernel


def _make_allgather_kernel(world: int, width: int):
    """Relay ring allgather: own row copied out, then w-1 relay hops.
    `comm` has one slot per ring position — slot 0 holds the local
    row, hop s receives into slot s and forwards slot s-1 — so every
    slot is written exactly once."""
    import jax.experimental.pallas as pl

    def kernel(ids_ref, x_ref, o_ref, comm, sem_s, sem_r):
        my, right = ids_ref[0], ids_ref[1]
        o_ref[0, pl.ds(my * width, width)] = x_ref[0, :]
        comm[0] = x_ref[0, :]
        for s in range(1, world):
            rdma = _remote_copy(comm, s - 1, comm, s, sem_s, sem_r,
                                right)
            rdma.start()
            rdma.wait()
            o_ref[0, pl.ds(((my - s) % world) * width, width)] = comm[s]

    return kernel


def _make_quantized_allreduce_kernel(world: int, chunk: int, combine: str):
    """The fused EQuARX ring: every reduce hop re-quantizes the partial
    to int8 + per-block f32 scales (two DMAs per hop, payload+scales);
    the gather phase quantizes ONCE and relays the same bytes."""
    import jax.experimental.pallas as pl

    cmb = _COMBINE_FNS[combine]
    nblocks = chunk // QUANT_BLOCK

    def kernel(ids_ref, x_ref, o_ref, qstage, sstage, qrbuf, srbuf,
               qsem_s, qsem_r, ssem_s, ssem_r):
        my, right = ids_ref[0], ids_ref[1]

        def hop_dma(qsrc_buf, qsrc, ssrc_buf, ssrc, hop):
            r1 = _remote_copy(qsrc_buf, qsrc, qrbuf, hop,
                              qsem_s, qsem_r, right)
            r2 = _remote_copy(ssrc_buf, ssrc, srbuf, hop,
                              ssem_s, ssem_r, right)
            r1.start()
            r2.start()
            r1.wait()
            r2.wait()

        acc = x_ref[0, pl.ds(my * chunk, chunk)]
        for s in range(1, world):
            hop = s - 1
            q, sc = quantize_blocks(acc)
            qstage[hop] = q
            sstage[hop] = sc
            hop_dma(qstage, hop, sstage, hop, hop)
            acc = cmb(dequantize_blocks(qrbuf[hop], srbuf[hop]),
                      x_ref[0, pl.ds(((my - s) % world) * chunk, chunk)])
        q, sc = quantize_blocks(acc)
        qstage[world - 1] = q
        sstage[world - 1] = sc
        o_ref[0, pl.ds(((my + 1) % world) * chunk, chunk)] = \
            dequantize_blocks(q, sc)
        for s in range(1, world):
            hop = world - 1 + (s - 1)
            if s == 1:  # relay the staged quantized reduced chunk...
                hop_dma(qstage, world - 1, sstage, world - 1, hop)
            else:  # ...then forward the previous hop's recv slots
                hop_dma(qrbuf, hop - 1, srbuf, hop - 1, hop)
            o_ref[0, pl.ds(((my - s + 1) % world) * chunk, chunk)] = \
                dequantize_blocks(qrbuf[hop], srbuf[hop])

    assert nblocks * QUANT_BLOCK == chunk
    return kernel


class _PallasOps:
    """Cached jitted pallas_call collectives over one mesh axis — the
    fused-kernel mirror of xla_backend._DeviceOps (same [world, B] flat
    layout, same cache-key discipline: every compile-relevant input —
    op kind, combine fn, dtype, shape-class, axis name, world size — is
    in the key). Ops without a fused kernel delegate to an embedded
    _DeviceOps, the same bodies the DEVICE tier runs."""

    def __init__(self, mesh, axis: str, world: int):
        self.mesh = mesh
        self.axis = axis
        self.world = world
        # never interpreted on a chip: pallas_supported() keeps the tier
        # off a live TPU backend until Mosaic accepts these kernels
        self.interpret = not is_tpu()
        self._cache: dict = {}
        self._fallback = _DeviceOps(mesh, axis, world)

    # -- plumbing -------------------------------------------------------

    def _pallas_call(self, kernel, out_len: int, dtype, scratch,
                     collective_id: int):
        import jax.experimental.pallas as pl
        from jax.experimental.pallas import tpu as pltpu

        return pl.pallas_call(
            kernel,
            out_shape=jax.ShapeDtypeStruct((1, out_len), dtype),
            grid_spec=pltpu.PrefetchScalarGridSpec(
                num_scalar_prefetch=1,
                in_specs=[pl.BlockSpec(memory_space=pl.ANY)],
                out_specs=pl.BlockSpec(memory_space=pl.ANY),
                scratch_shapes=scratch),
            compiler_params=_compiler_params(collective_id),
            interpret=self.interpret)

    def _jit(self, key, wrapper, out_specs=None):
        """First-call compile-recording cache, same contract as
        _DeviceOps._jit."""
        fn = self._cache.get(key)
        if fn is None:
            from jax.sharding import PartitionSpec as P

            from ray_tpu._private import profiling as _profiling

            jitted = jax.jit(_shard_map(
                wrapper, self.mesh, P(self.axis, None),
                out_specs if out_specs is not None
                else P(self.axis, None)))
            fn = self._cache[key] = _profiling.CompileProbe(
                "pallas:" + ":".join(map(str, key)), jitted)
        return fn

    @staticmethod
    def _pad_to_chunks(B: int, w: int) -> int:
        return w * (-(-B // w))

    # -- fused op surface (same signatures as _DeviceOps) --------------

    def allreduce(self, garr, op: ReduceOp):
        op = ReduceOp(op)
        kind = ReduceOp.SUM if op == ReduceOp.MEAN else op
        combine = _PALLAS_COMBINE.get(kind)
        if combine is None:  # op without a fused combine: DEVICE bodies
            return self._fallback.allreduce(garr, op)
        w, axis = self.world, self.axis
        B = garr.shape[1]
        Bp = self._pad_to_chunks(B, w)
        C = Bp // w
        key = ("par", combine, garr.dtype.name, B, axis, w)
        kernel = _make_allreduce_kernel(w, C, combine)

        def wrapper(x):
            ids = _ring_ids(axis, w)
            xp = jnp.pad(x, ((0, 0), (0, Bp - B))) if Bp > B else x
            out = self._pallas_call(
                kernel, Bp, x.dtype,
                self._scratch_allreduce(C, x.dtype),
                collective_id=1)(ids, xp)
            return out[:, :B]

        return self._jit(key, wrapper)(garr)

    def allgather(self, garr):
        from jax.sharding import PartitionSpec as P

        w, axis = self.world, self.axis
        B = garr.shape[1]
        key = ("pag", garr.dtype.name, B, axis, w)
        kernel = _make_allgather_kernel(w, B)

        def wrapper(x):
            ids = _ring_ids(axis, w)
            out = self._pallas_call(
                kernel, w * B, x.dtype,
                self._scratch_allgather(B, x.dtype),
                collective_id=2)(ids, x)
            return out.reshape(1, w, B)

        return self._jit(key, wrapper, P(axis, None, None))(garr)

    def reducescatter_even(self, garr):
        w, axis = self.world, self.axis
        P_len = garr.shape[1]
        if P_len % w:  # caller guarantees divisibility; stay safe
            return self._fallback.reducescatter_even(garr)
        C = P_len // w
        key = ("prs", garr.dtype.name, P_len, axis, w)
        kernel = _make_reducescatter_kernel(w, C)

        def wrapper(x):
            ids = _ring_ids(axis, w)
            return self._pallas_call(
                kernel, C, x.dtype,
                self._scratch_reducescatter(C, x.dtype),
                collective_id=3)(ids, x)

        return self._jit(key, wrapper)(garr)

    def allreduce_quantized(self, garr, op: ReduceOp):
        """garr: [w, w*C] float32, C % QUANT_BLOCK == 0 (the caller
        pads with _qring_pad — identical layout to the DEVICE qring)."""
        op = ReduceOp(op)
        combine = _PALLAS_COMBINE[op]
        w, axis = self.world, self.axis
        B = garr.shape[1]
        C = B // w
        key = ("pqar", combine, garr.dtype.name, B, axis, w, QUANT_BLOCK)
        kernel = _make_quantized_allreduce_kernel(w, C, combine)

        def wrapper(x):
            ids = _ring_ids(axis, w)
            return self._pallas_call(
                kernel, B, jnp.float32,
                self._scratch_quantized(C), collective_id=4)(ids, x)

        return self._jit(key, wrapper)(garr)

    # -- unfused ops: the documented DEVICE fallthrough ----------------

    def broadcast(self, garr, src: int):
        return self._fallback.broadcast(garr, src)

    def shift_right(self, garr):
        return self._fallback.shift_right(garr)

    # -- scratch shapes -------------------------------------------------
    #
    # Slot counts follow the write-once discipline: `stage` holds one
    # slot per staged send (w-1 reduce-scatter sends + 1 gather stage),
    # recv buffers one slot per hop, DMA semaphores one pair per recv
    # slot. max(1, ...) keeps world==1 (no hops at all) allocatable.

    def _scratch_allreduce(self, chunk: int, dtype):
        from jax.experimental.pallas import tpu as pltpu

        hops = max(1, 2 * (self.world - 1))
        return [pltpu.VMEM((self.world, chunk), jnp.dtype(dtype)),
                pltpu.VMEM((hops, chunk), jnp.dtype(dtype)),
                pltpu.SemaphoreType.DMA((hops,)),
                pltpu.SemaphoreType.DMA((hops,))]

    def _scratch_reducescatter(self, chunk: int, dtype):
        from jax.experimental.pallas import tpu as pltpu

        hops = max(1, self.world - 1)
        return [pltpu.VMEM((hops, chunk), jnp.dtype(dtype)),
                pltpu.VMEM((hops, chunk), jnp.dtype(dtype)),
                pltpu.SemaphoreType.DMA((hops,)),
                pltpu.SemaphoreType.DMA((hops,))]

    def _scratch_allgather(self, width: int, dtype):
        from jax.experimental.pallas import tpu as pltpu

        return [pltpu.VMEM((self.world, width), jnp.dtype(dtype)),
                pltpu.SemaphoreType.DMA((self.world,)),
                pltpu.SemaphoreType.DMA((self.world,))]

    def _scratch_quantized(self, chunk: int):
        from jax.experimental.pallas import tpu as pltpu

        hops = max(1, 2 * (self.world - 1))
        return [pltpu.VMEM((self.world, chunk), jnp.int8),
                pltpu.VMEM((self.world, chunk // QUANT_BLOCK),
                           jnp.float32),
                pltpu.VMEM((hops, chunk), jnp.int8),
                pltpu.VMEM((hops, chunk // QUANT_BLOCK), jnp.float32),
                pltpu.SemaphoreType.DMA((hops,)),
                pltpu.SemaphoreType.DMA((hops,)),
                pltpu.SemaphoreType.DMA((hops,)),
                pltpu.SemaphoreType.DMA((hops,))]


class PallasTransport(DeviceTransport):
    """Transport.PALLAS: DeviceTransport's host-parity op surface over
    _PallasOps fused kernels. Rank/mesh validation, payload lifting,
    MEAN/dtype promotion rules and quantized-ring padding are inherited
    — the tiers differ only in what one op costs, never in what it
    returns."""

    def __init__(self, world_size: int, rank: int):
        super().__init__(world_size, rank)
        self._ops = _PallasOps(self.mesh, self.AXIS, world_size)

    def _counted(self):
        from ray_tpu.collective import metrics as _cm

        _cm.PALLAS_OPS.inc()


@functools.lru_cache(maxsize=1)
def pallas_supported() -> bool:
    """Whether this process can run the fused-kernel tier. Cheap
    group-uniform fact for the topology deriver and the routing vote.

    False on a live TPU backend: the chip's compiler refuses these
    kernels ("Loads are only allowed on VMEM and SMEM references. ANY
    memory space can only be accessed using async_copy" — they load
    directly from ANY-space refs; tests/test_chip_compile.py pins the
    refusal), and the tier never runs interpreted on a chip. A forced
    pin then raises the typed unavailability error and a derived pin
    demotes, through the vote that already exists. Off the chip the
    tier is the interpreted reference the CPU test rigs run."""
    return jax is not None and not is_tpu()
