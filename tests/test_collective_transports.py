"""Host collective data-plane tiers: device (ICI/XLA), shm segment,
pipelined ring, hub.

Covers the transport matrix (exactness guard: bit-identical SUM/MAX/MIN
across the five tiers, hub MEAN semantics), the DEVICE tier's per-op
placement vote + fallback, the int8 block-scaled quantized allreduce
error-bound matrix (analytic bound; quantize=None stays bit-exact),
MEAN/PRODUCT parity across tiers, abort-not-hang fault injection (rank
killed mid-shm-op, mid-ring-step, mid-device-vote and mid-quantized-ring
hop), peer-direct send/recv, and the hub op-table sweep."""

import time

import numpy as np
import pytest

import ray_tpu
from tests.conftest import scale_timeout

WORLD = 3  # odd on purpose: non-divisible stripes everywhere


@ray_tpu.remote
class TransportWorker:
    def init_group(self, world, rank, group_name, timeout=60.0,
                   multihost_name=None, quantize=None):
        from ray_tpu import collective as col

        if multihost_name is not None:
            # join the shared jax.distributed runtime BEFORE any jax
            # backend use: the group becomes device-capable and the
            # DEVICE tier is routable/forcible
            from ray_tpu.parallel import multihost

            multihost.initialize(multihost_name, world, rank)
        col.init_collective_group(world, rank, backend="host",
                                  group_name=group_name, timeout=timeout,
                                  quantize=quantize)
        self.rank = rank
        self.world = world
        self.group_name = group_name
        return rank

    def _group(self):
        from ray_tpu.collective import collective as C

        return C._manager.get_group(self.group_name)

    def run_matrix(self, transports, n):
        """Run every op on every transport; return raw bytes + dtype so
        the driver can compare bit-exactly across ranks AND tiers."""
        from ray_tpu.collective.types import ReduceOp

        group = self._group()
        rng = np.random.default_rng(1234 + self.rank)
        # exactly-representable floats: integer-valued, so float addition
        # is exact and the ring's rotated reduce order cannot change bits
        cases = {
            "f32": (rng.integers(-64, 64, n)).astype(np.float32),
            "i32": rng.integers(-1000, 1000, n).astype(np.int32),
            "f16": (rng.integers(0, 5, n)).astype(np.float16),
        }
        out = {}
        for tr in transports:
            group.force_transport = tr
            for name, arr in cases.items():
                for op in (ReduceOp.SUM, ReduceOp.MAX, ReduceOp.MIN,
                           ReduceOp.MEAN):
                    r = group.allreduce(arr, op)
                    out[f"allreduce/{name}/{op.value}/{tr}"] = (
                        r.tobytes(), r.dtype.str, r.shape)
                rs = group.reducescatter(
                    cases[name].reshape(-1, 1), ReduceOp.SUM)
                out[f"reducescatter/{name}/{tr}"] = (
                    rs.tobytes(), rs.dtype.str, rs.shape)
            ag = group.allgather(cases["f32"])
            out[f"allgather/{tr}"] = [(a.tobytes(), a.dtype.str, a.shape)
                                      for a in ag]
            bc = group.broadcast(cases["i32"], src_rank=1)
            out[f"broadcast/{tr}"] = (bc.tobytes(), bc.dtype.str, bc.shape)
        group.force_transport = None
        return out

    def probe_auto(self, nbytes):
        """One auto-routed large allreduce; report which tier engaged."""
        group = self._group()
        group.allreduce(np.ones(nbytes // 4, np.float32))
        return {"shm": group._shm is not None,
                "ring": getattr(group, "_ring_next", None) is not None}

    def warm(self, transport, nbytes=1 << 20, quantize=None):
        group = self._group()
        group.force_transport = transport
        group.allreduce(np.ones(nbytes // 4, np.float32),
                        quantize=quantize)
        return True

    def timed_allreduce(self, transport, nbytes, quantize=None):
        group = self._group()
        group.force_transport = transport
        arr = np.ones(nbytes // 4, np.float32)
        try:
            t0 = time.monotonic()
            group.allreduce(arr, quantize=quantize)
            return {"ok": True, "elapsed": time.monotonic() - t0}
        except TimeoutError as e:
            return {"ok": False, "elapsed": time.monotonic() - t0,
                    "error": str(e)}

    def probe_device(self, use_device_array, n=1 << 14):
        """One auto-routed allreduce; report whether the DEVICE tier
        engaged and whether the result stayed on device."""
        group = self._group()
        arr = np.ones(n, np.float32)
        if use_device_array:
            import jax.numpy as jnp

            arr = jnp.asarray(arr)
        out = group.allreduce(arr)
        return {"device_built": group._device is not None,
                "pallas_built": group._pallas is not None,
                "out_on_device": not isinstance(out, np.ndarray),
                "val": float(np.asarray(out)[0]),
                "shm": group._shm is not None}

    def quantized_allreduce(self, transport, dtype, opname, n, seed,
                            quantize="int8", integral=False):
        """Seeded deterministic inputs so the driver can rebuild the
        exact reference and the analytic bound (integral=True draws
        exactly-representable values for bit-exactness checks)."""
        from ray_tpu.collective.types import ReduceOp

        group = self._group()
        group.force_transport = transport
        rng = np.random.default_rng(seed + self.rank)
        if integral:
            arr = rng.integers(-64, 64, n).astype(dtype)
        else:
            arr = rng.uniform(-1.0, 1.0, n).astype(dtype)
        try:
            out = group.allreduce(arr, ReduceOp(opname), quantize=quantize)
        finally:
            group.force_transport = None
        return out.tobytes(), np.dtype(out.dtype).str, tuple(out.shape)

    def parity_matrix(self, transports, n):
        """MEAN and PRODUCT on every tier (satellite: _NUMPY_REDUCE
        special-cases must not leave semantic gaps between tiers)."""
        from ray_tpu.collective.types import ReduceOp

        group = self._group()
        rng = np.random.default_rng(77 + self.rank)
        cases = {
            # 1..2 so a 3-rank product stays tiny and exact in f32/i32
            "f32": rng.integers(1, 3, n).astype(np.float32),
            "i32": rng.integers(1, 3, n).astype(np.int32),
        }
        out = {}
        for tr in transports:
            group.force_transport = tr
            for name, arr in cases.items():
                for op in (ReduceOp.MEAN, ReduceOp.PRODUCT):
                    r = group.allreduce(arr, op)
                    out[f"{name}/{op.value}/{tr}"] = (
                        r.tobytes(), np.dtype(r.dtype).str, tuple(r.shape))
        group.force_transport = None
        return out

    def pallas_vote_probe(self, veto, derived):
        """Forced/derived PALLAS pin with an optional rank-local veto:
        reports whether the routing layer raised (forced pin), demoted
        (derived pin), or ran the op — every rank must call this
        together (the vote is a collective ctl round)."""
        group = self._group()
        if veto:
            group._pallas_disabled = True
        group.force_transport = "pallas"
        group._transport_derived = derived
        arr = np.ones(1024, np.float32)
        try:
            out = group.allreduce(arr)
            return {"raised": None, "val": float(np.asarray(out)[0]),
                    "derived_after": group._transport_derived,
                    "forced_after": group.force_transport}
        except RuntimeError as e:
            return {"raised": str(e)}
        finally:
            group._pallas_disabled = False
            group.force_transport = None
            group._transport_derived = False

    def read_counter(self, name):
        from ray_tpu._private import stats

        snap = stats.snapshot().get(name)
        return float(snap["value"]) if snap else 0.0

    def arm_failpoint(self, name, action, **kw):
        from ray_tpu._private import failpoints

        failpoints.arm(name, action, **kw)
        return True

    def swap(self, peer, nbytes):
        """send-then-recv on both sides: must not rendezvous-deadlock."""
        from ray_tpu import collective as col

        mine = np.full(nbytes // 4, float(self.rank), np.float32)
        col.send(mine, peer, group_name=self.group_name, tag=11)
        got = col.recv(peer, group_name=self.group_name, tag=11)
        return bool(np.all(got == float(peer)))

    def ragged_gather(self):
        """Per-rank sizes straddle RING_MIN_BYTES: auto routing must
        converge on the hub via the shared meta round (historically this
        either corrupted payloads or errored)."""
        from ray_tpu import collective as col

        n = 70_000 if self.rank == 0 else 16  # rank 0 above 64KB
        out = col.allgather(np.full(n, float(self.rank), np.float32),
                            group_name=self.group_name)
        return [(len(a), float(a[0])) for a in out]

    def sendrecv(self, peer, nbytes, is_sender):
        from ray_tpu import collective as col

        if is_sender:
            arr = (np.arange(nbytes // 8) % 251).astype(np.float64)
            col.send(arr, peer, group_name=self.group_name, tag=7)
            return None
        got = col.recv(peer, group_name=self.group_name, tag=7)
        expect = (np.arange(nbytes // 8) % 251).astype(np.float64)
        assert got.dtype == np.float64 and np.array_equal(got, expect)
        return got.nbytes

    def destroy_group(self):
        from ray_tpu import collective as col

        col.destroy_collective_group(self.group_name)
        return True

    def die(self):
        import os

        os._exit(0)


def _make_group(n, group_name, timeout=60.0, multihost_name=None,
                quantize=None):
    workers = [TransportWorker.remote() for _ in range(n)]
    ray_tpu.get([w.init_group.remote(n, i, group_name, timeout,
                                     multihost_name, quantize)
                 for i, w in enumerate(workers)], timeout=240)
    return workers


@pytest.fixture(scope="module")
def device_workers(ray_start_shared):
    """One module-wide multihost worker set (jax.distributed startup is
    the expensive part); tests lay additional groups over the same
    actors."""
    workers = _make_group(WORLD, "g_dev", multihost_name="devtier")
    yield workers
    ray_tpu.get([w.destroy_group.remote() for w in workers], timeout=60)
    for w in workers:
        ray_tpu.kill(w)


def _extra_group(workers, group_name, timeout=60.0, quantize=None):
    """Init another collective group on already-multihosted actors."""
    ray_tpu.get([w.init_group.remote(len(workers), i, group_name, timeout,
                                     None, quantize)
                 for i, w in enumerate(workers)], timeout=120)


def test_transport_exactness_matrix(device_workers):
    """device, shm, pipelined ring, unpipelined ring, and hub must agree
    bit-for-bit on SUM/MAX/MIN (ints always; floats with exactly-
    representable values) and on MEAN semantics (float64 accumulate +
    float64 result for integer inputs) across an odd world size and a
    non-divisible tensor length. (5-tier extension of the PR 2 matrix:
    the workers share one jax.distributed runtime, so 'device' is
    forcible and runs the same payloads over the XLA plane; 'pallas'
    runs the fused-kernel tier in interpret mode over the same
    runtime — the 6th tier must agree bitwise with the other 5.)"""
    transports = ["hub", "shm", "ring", "device", "pallas"]
    workers = device_workers
    outs = ray_tpu.get(
        [w.run_matrix.remote(transports, 10_007) for w in workers],
        timeout=scale_timeout(300))

    hub = outs[0]
    for key, val in hub.items():
        if key.startswith("reducescatter/"):
            continue  # output is rank-specific by definition
        # every rank agrees with rank 0 for the same key
        for r in range(1, WORLD):
            assert outs[r][key] == val, f"rank {r} diverged on {key}"
    # cross-tier: each rank's result on every tier vs its hub result
    for r in range(WORLD):
        for key in [k for k in outs[r] if k.endswith("/hub")]:
            base = outs[r][key]
            for tr in transports[1:]:
                other = outs[r][key[:-len("hub")] + tr]
                if "/mean/" in key:
                    # MEAN: same dtype/shape, values allclose
                    # (accumulation order differs across tiers for
                    # float inputs)
                    assert other[1] == base[1] and other[2] == base[2], key
                    a = np.frombuffer(base[0], np.dtype(base[1]))
                    b = np.frombuffer(other[0], np.dtype(other[1]))
                    np.testing.assert_allclose(a, b, rtol=1e-3)
                else:
                    assert other == base, f"rank {r}: {tr} != hub on {key}"
    # MEAN over ints must have promoted to float64 on every tier
    for tr in transports:
        assert hub[f"allreduce/i32/mean/{tr}"][1] == np.dtype(
            np.float64).str
    # (workers belong to the module fixture — no teardown here)


def test_auto_routing_prefers_shm_on_one_node(ray_start_shared):
    workers = _make_group(WORLD, "g_auto")
    probes = ray_tpu.get(
        [w.probe_auto.remote(1 << 20) for w in workers],
        timeout=scale_timeout(90))
    assert all(p["shm"] for p in probes), probes  # same node -> shm tier
    assert not any(p["ring"] for p in probes), probes
    ray_tpu.get([w.destroy_group.remote() for w in workers], timeout=60)
    for w in workers:
        ray_tpu.kill(w)


def test_peer_direct_send_recv_large(ray_start_shared):
    """Payloads above RING_MIN_BYTES go rank-to-rank; the hub mailbox
    only carries the rendezvous message."""
    workers = _make_group(2, "g_p2pdirect")
    nbytes = 1 << 21
    send_ref = workers[1].sendrecv.remote(0, nbytes, True)
    recv_ref = workers[0].sendrecv.remote(1, nbytes, False)
    assert ray_tpu.get(recv_ref, timeout=scale_timeout(60)) == nbytes
    ray_tpu.get(send_ref, timeout=scale_timeout(60))
    ray_tpu.get([w.destroy_group.remote() for w in workers], timeout=60)
    for w in workers:
        ray_tpu.kill(w)


def test_ragged_allgather_straddling_threshold(ray_start_shared):
    """Ragged allgather whose sizes straddle the fast-path threshold
    must return correct per-rank arrays through the hub."""
    workers = _make_group(WORLD, "g_ragged")
    outs = ray_tpu.get([w.ragged_gather.remote() for w in workers],
                       timeout=scale_timeout(90))
    expect = [(70_000, 0.0)] + [(16, float(r)) for r in range(1, WORLD)]
    for out in outs:
        assert out == expect, out
    ray_tpu.get([w.destroy_group.remote() for w in workers], timeout=60)
    for w in workers:
        ray_tpu.kill(w)


def test_peer_direct_symmetric_exchange(ray_start_shared):
    """Both ranks send a large tensor first, then both recv: the
    buffered peer-direct send (payload served off-thread) must complete
    the swap instead of rendezvous-deadlocking."""
    workers = _make_group(2, "g_p2pswap")
    refs = [w.swap.remote(1 - i, 1 << 20) for i, w in enumerate(workers)]
    assert all(ray_tpu.get(refs, timeout=scale_timeout(60)))
    ray_tpu.get([w.destroy_group.remote() for w in workers], timeout=60)
    for w in workers:
        ray_tpu.kill(w)


@pytest.mark.parametrize("transport", ["shm", "ring"])
def test_rank_death_aborts_not_hangs(ray_start_shared, transport):
    """Kill a rank mid-collective on each large-tensor tier: every
    survivor must raise TimeoutError within the group timeout, and the
    group must be destroyable and rebuildable afterward."""
    timeout = scale_timeout(8)
    name = f"g_fault_{transport}"
    # world 4: the rebuilt group (world 3) can still run a forced ring
    workers = _make_group(4, name, timeout=timeout)
    # warm the tier so the victim dies mid-established-path (for the
    # ring: survivors are mid-pipelined-step when the socket drops)
    assert all(ray_tpu.get([w.warm.remote(transport) for w in workers],
                           timeout=scale_timeout(90)))
    victim = workers[-1]
    ray_tpu.kill(victim)  # hard kill: no destroy, no goodbye
    t0 = time.monotonic()
    outs = ray_tpu.get(
        [w.timed_allreduce.remote(transport, 1 << 20)
         for w in workers[:-1]],
        timeout=scale_timeout(120))
    wall = time.monotonic() - t0
    for out in outs:
        assert not out["ok"], f"survivor completed against a dead rank: {out}"
        assert out["elapsed"] < timeout * 3 + 5, out
    assert wall < timeout * 6 + 10
    # group can be torn down and rebuilt at the surviving size
    ray_tpu.get([w.destroy_group.remote() for w in workers[:-1]],
                timeout=scale_timeout(60))
    rebuilt = f"{name}_rebuilt"
    ray_tpu.get([w.init_group.remote(3, i, rebuilt, 30.0)
                 for i, w in enumerate(workers[:-1])],
                timeout=scale_timeout(60))
    res = ray_tpu.get(
        [w.timed_allreduce.remote(transport, 1 << 20)
         for w in workers[:-1]], timeout=scale_timeout(90))
    assert all(r["ok"] for r in res), res
    ray_tpu.get([w.destroy_group.remote() for w in workers[:-1]],
                timeout=60)
    for w in workers[:-1]:
        ray_tpu.kill(w)


def test_device_tier_auto_routing_and_fallback(device_workers):
    """A device-array payload routes the op onto the DEVICE tier on a
    unanimous vote; a numpy payload anywhere vetoes it and every rank
    falls back to the host tiers together (same result, no hang)."""
    workers = device_workers
    _extra_group(workers, "g_devroute")
    # all ranks hold SMALL jax arrays -> the PALLAS fused-kernel tier
    # (the refinement of the device plane for ops under
    # pallas_max_bytes) engages on a unanimous vote; result stays on
    # device
    probes = ray_tpu.get(
        [w.probe_device.remote(True) for w in workers],
        timeout=scale_timeout(120))
    for p in probes:
        assert p["pallas_built"], probes
        assert p["out_on_device"], probes
        assert p["val"] == float(WORLD)
    # LARGE jax arrays fall through the size gate to the DEVICE tier
    probes = ray_tpu.get(
        [w.probe_device.remote(True, n=1 << 18) for w in workers],
        timeout=scale_timeout(120))
    for p in probes:
        assert p["device_built"], probes
        assert p["out_on_device"], probes
        assert p["val"] == float(WORLD)
    # mixed placement: rank 0 passes numpy -> unanimity fails -> host
    # tiers carry the op and every rank still gets the right answer
    probes = ray_tpu.get(
        [w.probe_device.remote(i != 0) for i, w in enumerate(workers)],
        timeout=scale_timeout(120))
    for p in probes:
        assert p["val"] == float(WORLD)
        assert not p["out_on_device"], probes  # fell back to host tiers
    # all-numpy: device never engages, shm serves the big op as before
    probes = ray_tpu.get(
        [w.probe_device.remote(False, n=1 << 18) for w in workers],
        timeout=scale_timeout(120))
    assert all(p["shm"] for p in probes), probes
    ray_tpu.get([w.destroy_group.remote() for w in workers], timeout=60)


def test_pallas_forced_unavailable_raises_derived_demotes(device_workers):
    """The PALLAS vote's two non-unanimous outcomes: a USER-forced pin
    raises the typed unavailability error on every rank (the vote
    result is an allgather, so the decision is group-uniform); a
    placement-DERIVED pin demotes to auto routing in unison and the op
    still completes on the host tiers."""
    workers = device_workers
    _extra_group(workers, "g_pallas_vote")
    # a clean forced pin first: unanimous vote, op runs on the kernel
    # tier (numpy payload — forced short-circuits the placement check)
    probes = ray_tpu.get(
        [w.pallas_vote_probe.remote(False, False) for w in workers],
        timeout=scale_timeout(120))
    for p in probes:
        assert p["raised"] is None, probes
        assert p["val"] == float(WORLD)
    # rank 0 vetoes (kernel tier disabled locally): forced pin -> every
    # rank raises the same typed error instead of hanging or diverging
    probes = ray_tpu.get(
        [w.pallas_vote_probe.remote(i == 0, False)
         for i, w in enumerate(workers)], timeout=scale_timeout(120))
    for p in probes:
        assert p["raised"] is not None, probes
        assert "forced collective transport 'pallas' is unavailable" \
            in p["raised"], p
    # same veto under a DERIVED pin: no raise — all ranks demote to
    # auto routing together and the allreduce completes host-side
    probes = ray_tpu.get(
        [w.pallas_vote_probe.remote(i == 0, True)
         for i, w in enumerate(workers)], timeout=scale_timeout(120))
    for p in probes:
        assert p["raised"] is None, probes
        assert p["val"] == float(WORLD)
        assert p["derived_after"] is False, probes
        assert p["forced_after"] is None, probes
    ray_tpu.get([w.destroy_group.remote() for w in workers], timeout=60)


@pytest.mark.chaos
@pytest.mark.parametrize("nth", [1, 2])
def test_pallas_rank_death_aborts_not_hangs(ray_start_shared, nth):
    """Seeded chaos (satellite): a rank hard-killed at the
    collective.pallas_dispatch seam (mid-pallas-op, before the
    agreement round) leaves every survivor with a typed TimeoutError
    within the group timeout — abort-not-hang for the kernel tier."""
    timeout = scale_timeout(8)
    workers = _make_group(4, f"g_fault_pallas{nth}", timeout=timeout,
                          multihost_name=f"pallasfault{nth}")
    # small payloads route the kernel tier; warm it end to end first
    assert all(ray_tpu.get(
        [w.warm.remote("pallas", nbytes=1 << 14) for w in workers],
        timeout=scale_timeout(240)))
    # rank 0 hosts the jax.distributed coordinator (same failure-domain
    # carve-out as the device-tier chaos case): kill a client rank
    victim_idx = 2
    ray_tpu.get(workers[victim_idx].arm_failpoint.remote(
        "collective.pallas_dispatch", "exit", nth=nth), timeout=30)
    t0 = time.monotonic()
    outs = []
    for _ in range(nth + 1):
        refs = [w.timed_allreduce.remote("pallas", 1 << 14)
                for w in workers]
        outs = []
        for r in refs:
            try:
                outs.append(ray_tpu.get(r, timeout=scale_timeout(120)))
            except Exception:  # the victim dies mid-call
                outs.append({"ok": False, "elapsed": 0.0, "died": True})
        if not all(o["ok"] for o in outs):
            break
    wall = time.monotonic() - t0
    survivors = [o for i, o in enumerate(outs) if i != victim_idx]
    assert all(not o["ok"] for o in survivors), (nth, outs)
    for out in survivors:
        assert out["elapsed"] < timeout * 3 + 5, out
    assert wall < timeout * 8 + 20
    # host tiers still serve the survivors at the surviving size
    keep = [w for i, w in enumerate(workers) if i != victim_idx]
    ray_tpu.get([w.destroy_group.remote() for w in keep],
                timeout=scale_timeout(60))
    ray_tpu.get([w.init_group.remote(3, i, f"g_fault_pallas{nth}_r", 30.0)
                 for i, w in enumerate(keep)],
                timeout=scale_timeout(60))
    res = ray_tpu.get(
        [w.timed_allreduce.remote("ring", 1 << 20) for w in keep],
        timeout=scale_timeout(90))
    assert all(r["ok"] for r in res), res
    ray_tpu.get([w.destroy_group.remote() for w in keep], timeout=60)
    for w in keep:
        ray_tpu.kill(w)


def _quant_bound(w, amax, op, dtype):
    """Analytic block-scaling bound: every output element is touched by
    at most w quantization steps (w-1 reduce hops + 1 gather quantize),
    each perturbing it by <= scale/2 <= partial_absmax/254, with
    partial sums bounded by w*amax (SUM/MEAN) or amax (MAX/MIN)."""
    if op in ("sum",):
        bound = w * (w * amax) / 254.0
    elif op == "mean":
        bound = (w * (w * amax) / 254.0) / w
    else:  # max/min: partials never exceed the input range
        bound = w * amax / 254.0
    if np.dtype(dtype) == np.float16:
        # output rounding to f16 on top of the quantization error
        bound += np.finfo(np.float16).eps * (w * amax + 1.0)
    return bound * 1.001 + 1e-7


@pytest.mark.parametrize("transport", ["ring", "device", "pallas"])
def test_quantized_error_bound_matrix(device_workers, transport):
    """quantize="int8" on the pipelined ring, the device tier, and the
    fused pallas kernel: the lossy result stays within the analytic
    block-scaling bound for every dtype x op, all ranks agree bitwise
    on the lossy result, and quantize=None stays bit-exact vs the
    hub."""
    workers = device_workers
    _extra_group(workers, f"g_q_{transport}")
    w = WORLD
    n = 10_007
    for dtype in ("<f4", "<f2"):
        # the driver rebuilds every rank's input for the reference
        inputs = [np.random.default_rng(5000 + r).uniform(-1.0, 1.0, n)
                  .astype(np.dtype(dtype)) for r in range(w)]
        amax = max(float(np.max(np.abs(x))) for x in inputs)
        for opname in ("sum", "mean", "max"):
            outs = ray_tpu.get(
                [wk.quantized_allreduce.remote(transport, dtype, opname,
                                               n, 5000)
                 for wk in workers], timeout=scale_timeout(240))
            # lossy, but identical on every rank (the gather phase
            # relays one quantized byte stream)
            assert all(o == outs[0] for o in outs[1:]), \
                f"ranks diverged on quantized {opname}/{dtype}"
            blob, dt, shape = outs[0]
            assert np.dtype(dt) == np.dtype(dtype), (opname, dt)
            got = np.frombuffer(blob, np.dtype(dt)).astype(np.float64)
            stack = np.stack([x.astype(np.float64) for x in inputs])
            exact = {"sum": stack.sum(0), "mean": stack.mean(0),
                     "max": stack.max(0)}[opname]
            err = float(np.max(np.abs(got - exact)))
            bound = _quant_bound(w, amax, opname, dtype)
            assert err <= bound, (
                f"{transport}/{opname}/{dtype}: err {err} > analytic "
                f"bound {bound}")
    ray_tpu.get([w.destroy_group.remote() for w in workers], timeout=60)


def test_quantize_none_stays_bit_exact(device_workers):
    """Under an int8 GROUP DEFAULT: the default engages when the per-op
    knob is None (saved-bytes counter moves), while quantize=False
    forces the exact path, bit-identical to the hub on
    exactly-representable payloads — for both wire tiers."""
    workers = device_workers
    _extra_group(workers, "g_qexact", quantize="int8")  # group default!
    w = WORLD
    n = 8_192
    inputs = [np.random.default_rng(6000 + r).integers(-64, 64, n)
              .astype(np.float32) for r in range(w)]
    expect = np.stack(inputs).sum(0)
    for transport in ("ring", "device"):
        # quantize=False overrides the group default: bit-exact
        outs = ray_tpu.get(
            [wk.quantized_allreduce.remote(transport, "<f4", "sum", n,
                                           6000, quantize=False,
                                           integral=True)
             for wk in workers], timeout=scale_timeout(180))
        for blob, dt, shape in outs:
            got = np.frombuffer(blob, np.dtype(dt))
            assert got.dtype == np.float32
            assert np.array_equal(got, expect), transport
        # the group DEFAULT (int8) engages when quantize is None —
        # proven by the saved-bytes counter moving
        before = ray_tpu.get(workers[0].read_counter.remote(
            "collective.quantized_bytes_saved_total"), timeout=30)
        ray_tpu.get(
            [wk.quantized_allreduce.remote(transport, "<f4", "sum", n,
                                           6000, quantize=None)
             for wk in workers], timeout=scale_timeout(120))
        after = ray_tpu.get(workers[0].read_counter.remote(
            "collective.quantized_bytes_saved_total"), timeout=30)
        assert after > before, (transport, before, after)
    ray_tpu.get([w.destroy_group.remote() for w in workers], timeout=60)


def test_quantized_ring_wire_bytes_saved(ray_start_shared):
    """The quantized ring's saved-bytes counter accounts for ~4x wire
    reduction on float32 (int8 payload + one f32 scale per 256-element
    block), on a plain (non-multihost) world-4 group."""
    workers = _make_group(4, "g_qbytes")
    n = 1 << 18  # 1MB of f32, divisible into block-aligned chunks
    before = ray_tpu.get(
        [w.read_counter.remote("collective.quantized_bytes_saved_total")
         for w in workers], timeout=60)
    outs = ray_tpu.get(
        [w.quantized_allreduce.remote("ring", "<f4", "sum", n, 7000)
         for w in workers], timeout=scale_timeout(180))
    assert all(o == outs[0] for o in outs[1:])
    after = ray_tpu.get(
        [w.read_counter.remote("collective.quantized_bytes_saved_total")
         for w in workers], timeout=60)
    w_, c = 4, n // 4  # even split, already block-aligned
    wire_elems = 2 * (w_ - 1) * c
    expect_saved = wire_elems * 4 - wire_elems * (1 + 4 / 256)
    for b, a in zip(before, after):
        saved = a - b
        assert abs(saved - expect_saved) <= 1.0, (saved, expect_saved)
        # ~4x: quantized wire is (1 + 4/256)/4 of the exact wire
        assert saved / (wire_elems * 4) > 0.73, saved
    ray_tpu.get([w.destroy_group.remote() for w in workers], timeout=60)
    for w in workers:
        ray_tpu.kill(w)


def test_mean_product_parity_across_tiers(device_workers):
    """Satellite: ReduceOp.MEAN and PRODUCT agree across ALL tiers
    (hub/shm/ring/device) — PRODUCT bit-exact on
    small-integer payloads, MEAN with identical promotion semantics
    (float64 accumulate + float64 result for integer inputs)."""
    workers = device_workers
    _extra_group(workers, "g_parity")
    transports = ["hub", "shm", "ring", "device", "pallas"]
    outs = ray_tpu.get(
        [w.parity_matrix.remote(transports, 4_099) for w in workers],
        timeout=scale_timeout(300))
    for r in range(1, WORLD):  # cross-rank agreement per key
        assert outs[r] == outs[0], f"rank {r} diverged"
    ref = outs[0]
    for name in ("f32", "i32"):
        for opname in ("mean", "product"):
            base = ref[f"{name}/{opname}/hub"]
            for tr in transports[1:]:
                other = ref[f"{name}/{opname}/{tr}"]
                assert other[1] == base[1], (
                    f"{name}/{opname}/{tr}: dtype {other[1]} != hub "
                    f"{base[1]}")
                assert other[2] == base[2], f"{name}/{opname}/{tr} shape"
                if opname == "product":
                    assert other[0] == base[0], (
                        f"{name}/product/{tr} != hub bits")
                else:
                    a = np.frombuffer(base[0], np.dtype(base[1]))
                    b = np.frombuffer(other[0], np.dtype(other[1]))
                    np.testing.assert_allclose(a, b, rtol=1e-6)
    # integer MEAN promoted to float64 on every tier
    for tr in transports:
        assert ref[f"i32/mean/{tr}"][1] == np.dtype(np.float64).str, tr
    ray_tpu.get([w.destroy_group.remote() for w in workers], timeout=60)


def test_device_rank_death_aborts_not_hangs(ray_start_shared):
    """Kill a rank between device ops: survivors' next device-routed op
    times out in the unanimity vote (abort-not-hang), and the group is
    rebuildable at the surviving size on the host tiers."""
    timeout = scale_timeout(8)
    workers = _make_group(4, "g_fault_dev", timeout=timeout,
                          multihost_name="devtier_fault")
    assert all(ray_tpu.get([w.warm.remote("device") for w in workers],
                           timeout=scale_timeout(240)))
    victim = workers[-1]
    ray_tpu.kill(victim)
    t0 = time.monotonic()
    outs = ray_tpu.get(
        [w.timed_allreduce.remote("device", 1 << 20)
         for w in workers[:-1]], timeout=scale_timeout(120))
    wall = time.monotonic() - t0
    for out in outs:
        assert not out["ok"], f"survivor completed against a dead rank: {out}"
        assert out["elapsed"] < timeout * 3 + 5, out
    assert wall < timeout * 6 + 10
    ray_tpu.get([w.destroy_group.remote() for w in workers[:-1]],
                timeout=scale_timeout(60))
    # rebuild at world 3: the 4-process runtime no longer matches, so
    # the rebuilt group serves from the host tiers
    ray_tpu.get([w.init_group.remote(3, i, "g_fault_dev_rebuilt", 30.0)
                 for i, w in enumerate(workers[:-1])],
                timeout=scale_timeout(60))
    res = ray_tpu.get(
        [w.timed_allreduce.remote("ring", 1 << 20)
         for w in workers[:-1]], timeout=scale_timeout(90))
    assert all(r["ok"] for r in res), res
    ray_tpu.get([w.destroy_group.remote() for w in workers[:-1]],
                timeout=60)
    for w in workers[:-1]:
        ray_tpu.kill(w)


def test_quantized_ring_rank_death_aborts_not_hangs(ray_start_shared):
    """Kill a rank mid-quantized-ring-op (failpoint collective.quantize
    fires inside a ring hop): every survivor raises TimeoutError within
    the group timeout and the group is rebuildable after destroy."""
    timeout = scale_timeout(8)
    workers = _make_group(4, "g_fault_q", timeout=timeout)
    assert all(ray_tpu.get(
        [w.warm.remote("ring", quantize="int8") for w in workers],
        timeout=scale_timeout(120)))
    victim = workers[-1]
    # die at the second quantize seam: mid-op, after the ring is up
    ray_tpu.get(victim.arm_failpoint.remote(
        "collective.quantize", "exit", nth=2), timeout=30)
    t0 = time.monotonic()
    refs = [w.timed_allreduce.remote("ring", 1 << 20, quantize="int8")
            for w in workers]
    outs = []
    for r in refs:
        try:
            outs.append(ray_tpu.get(r, timeout=scale_timeout(120)))
        except Exception:  # the victim dies mid-call
            outs.append({"ok": False, "elapsed": 0.0, "died": True})
    wall = time.monotonic() - t0
    survivors = outs[:-1]
    assert all(not o["ok"] for o in survivors), outs
    for out in survivors:
        assert out["elapsed"] < timeout * 3 + 5, out
    assert wall < timeout * 6 + 10
    ray_tpu.get([w.destroy_group.remote() for w in workers[:-1]],
                timeout=scale_timeout(60))
    ray_tpu.get([w.init_group.remote(3, i, "g_fault_q_rebuilt", 30.0)
                 for i, w in enumerate(workers[:-1])],
                timeout=scale_timeout(60))
    res = ray_tpu.get(
        [w.timed_allreduce.remote("ring", 1 << 20, quantize="int8")
         for w in workers[:-1]], timeout=scale_timeout(90))
    assert all(r["ok"] for r in res), res
    ray_tpu.get([w.destroy_group.remote() for w in workers[:-1]],
                timeout=60)
    for w in workers[:-1]:
        ray_tpu.kill(w)


@pytest.mark.chaos
@pytest.mark.parametrize("seed", [0, 1, 2, 3, 4])
def test_chaos_device_and_quantized_kill_schedule(ray_start_shared, seed):
    """Seeded chaos (satellite): a rank hard-killed at the
    collective.device_dispatch seam (mid-device-op) or at the
    collective.quantize seam (mid-quantized-ring-op) — drawn from the
    seed — must leave every survivor with a TimeoutError within the
    group timeout, and the group rebuildable after destroy."""
    import random as _random

    rng = _random.Random(seed)
    point = rng.choice(["collective.device_dispatch",
                        "collective.quantize"])
    nth = rng.randint(1, 3)
    if point.endswith("device_dispatch"):
        # rank 0 hosts the jax.distributed COORDINATOR: killing it makes
        # the surviving jax runtimes self-terminate (jax's own heartbeat
        # fatal) — that's the multihost runtime's failure domain, not
        # the collective layer's, so device-op chaos draws a client rank
        victim_idx = rng.randrange(1, 4)
    else:
        victim_idx = rng.randrange(4)
    timeout = scale_timeout(8)
    name = f"g_chaos_{seed}"
    mh = f"devchaos{seed}" if point.endswith("device_dispatch") else None
    workers = _make_group(4, name, timeout=timeout, multihost_name=mh)
    transport = ("device" if point.endswith("device_dispatch") else "ring")
    quant = None if transport == "device" else "int8"
    assert all(ray_tpu.get(
        [w.warm.remote(transport, quantize=quant) for w in workers],
        timeout=scale_timeout(240)))
    ray_tpu.get(workers[victim_idx].arm_failpoint.remote(
        point, "exit", nth=nth), timeout=30)
    # the device seam is hit once per op, the quantize seam w+... times
    # per op — issue rounds until the armed kill lands. A deadline
    # overrun here dumps cluster_state + stacks to a per-test artifact
    # before failing (flight-recorder triage for seeded hangs).
    from tests.conftest import state_dump_on_failure

    outs = None
    with state_dump_on_failure(
            f"collective-chaos-{point.replace('.', '_')}-seed{seed}",
            reason="collective kill-schedule deadline overrun"):
        for _ in range(nth + 1):
            refs = [w.timed_allreduce.remote(transport, 1 << 20,
                                             quantize=quant)
                    for w in workers]
            outs = []
            for r in refs:
                try:
                    outs.append(ray_tpu.get(r,
                                            timeout=scale_timeout(180)))
                except Exception:  # the victim's own call dies with it
                    outs.append({"ok": False, "elapsed": 0.0,
                                 "died": True})
            if not all(o["ok"] for o in outs):
                break
        survivors = [o for i, o in enumerate(outs) if i != victim_idx]
        # every survivor errored (TimeoutError) within the deadline; the
        # victim's own slot may be ok=False too (it died mid-call)
        assert all(not o["ok"] for o in survivors), (point, nth, outs)
        assert all(o["elapsed"] < timeout * 3 + 10
                   for o in survivors), outs
    keep = [w for i, w in enumerate(workers) if i != victim_idx]
    ray_tpu.get([w.destroy_group.remote() for w in keep],
                timeout=scale_timeout(60))
    ray_tpu.get([w.init_group.remote(3, i, f"{name}_rebuilt", 30.0)
                 for i, w in enumerate(keep)], timeout=scale_timeout(60))
    res = ray_tpu.get(
        [w.timed_allreduce.remote("ring", 1 << 20, quantize=quant)
         for w in keep], timeout=scale_timeout(90))
    assert all(r["ok"] for r in res), (point, res)
    ray_tpu.get([w.destroy_group.remote() for w in keep], timeout=60)
    for w in keep:
        ray_tpu.kill(w)


def test_collective_state_sweeps_unread_ops():
    """Satellite: a completed op whose readers never reach world_size (a
    rank died after contributing but before reading) must be swept on a
    deadline instead of leaking forever."""
    from ray_tpu.collective.backends.host_backend import _CollectiveState

    state = _CollectiveState(2, sweep_timeout=0.2)
    # simulate the leak: op done, one reader missing
    state.ops[7] = {"arrivals": {0: ("barrier", {}, b""),
                                 1: ("barrier", {}, b"")},
                    "result": {"kind": "barrier"}, "done": True,
                    "done_at": time.monotonic() - 1.0, "readers": {1}}
    # a later op triggers the sweep on entry
    import threading

    t = threading.Thread(
        target=lambda: state.contribute(8, "barrier", 1, {}, b"",
                                        timeout=5.0), daemon=True)
    t.start()
    state.contribute(8, "barrier", 0, {}, b"", timeout=5.0)
    t.join(5.0)
    assert 7 not in state.ops, "completed-but-unread op leaked"
    assert 8 not in state.ops  # fully-read ops still clean up eagerly


def test_hub_mismatched_kinds_error_not_hang():
    """A kind mismatch (e.g. ragged-allgather route divergence) must
    surface as an error on every rank, not a hang."""
    from ray_tpu.collective.backends.host_backend import _CollectiveState

    state = _CollectiveState(2)
    import threading

    errs = []

    def go(rank, kind):
        try:
            state.contribute(1, kind, rank, {}, b"", timeout=5.0)
        except Exception as e:
            errs.append(type(e).__name__)

    ts = [threading.Thread(target=go, args=(0, "barrier"), daemon=True),
          threading.Thread(target=go, args=(1, "allgather_meta"),
                           daemon=True)]
    [t.start() for t in ts]
    [t.join(10.0) for t in ts]
    assert errs == ["ValueError", "ValueError"], errs
