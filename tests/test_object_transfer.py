"""Cross-node object data plane: streaming zero-copy pulls, multi-source
striping, transfer pins, locality-aware lease targeting (reference idiom:
python/ray/tests/test_object_manager.py — real raylet processes, one box).

The chaos sweep at the bottom (pytest -m chaos) kills a source raylet
mid-stream and asserts the pull either completes from a surviving source
or surfaces typed ObjectLostError — never a hang, no leaked arena
creates, no leaked transfer pins."""

import glob
import os
import random
import time

import numpy as np
import pytest

import ray_tpu
from ray_tpu import exceptions as exc
from ray_tpu._private import failpoints as fp
from ray_tpu._private import global_state, rpc
from tests.conftest import scale_timeout


def _connect(cluster):
    cluster.connect_driver()
    return global_state.require_core_worker()


def _call(cw, address, method, data=None, timeout=30):
    """One rpc call to an arbitrary raylet (fresh connection)."""
    async def go():
        conn = await rpc.connect(address, name="test-call")
        try:
            return await conn.call(method, data or {})
        finally:
            await conn.close()

    return cw._io.run(go(), timeout=scale_timeout(timeout))


def _metric(cw, address, name, default=0.0):
    snap = _call(cw, address, "get_metrics", {})
    return snap.get(name, {}).get("value", default)


def _locations(cw, oid: bytes):
    return cw._io.run(cw.gcs.call("get_object_locations",
                                  {"object_id": oid}))


def _wait_locations(cw, oid: bytes, n: int, budget: float = 30):
    deadline = time.monotonic() + scale_timeout(budget)
    while time.monotonic() < deadline:
        if len(_locations(cw, oid)) >= n:
            return
        time.sleep(0.1)
    raise AssertionError(
        f"object never reached {n} registered locations "
        f"(has {_locations(cw, oid)})")


def _expected(n, dtype):
    if np.dtype(dtype) == np.float16:
        return (np.arange(n) % 1001).astype(np.float16)
    if np.dtype(dtype) == np.int32:
        return np.arange(n, dtype=np.int32) * 3 - 7
    return (np.arange(n) % 251).astype(np.uint8)


def _producer(resource):
    @ray_tpu.remote(num_cpus=1, resources={resource: 1})
    def produce(n, dtype_name):
        import numpy as np

        if dtype_name == "float16":
            return (np.arange(n) % 1001).astype(np.float16)
        if dtype_name == "int32":
            return np.arange(n, dtype=np.int32) * 3 - 7
        return (np.arange(n) % 251).astype(np.uint8)

    return produce


def test_streaming_pull_bit_exact(ray_start_cluster):
    """Cross-node streaming pulls are bit-exact for f16/i32/u8 arrays of
    odd (non-chunk-aligned) sizes."""
    cluster = ray_start_cluster
    from ray_tpu._private.node import start_gcs

    cluster.gcs_svc, cluster.gcs_address = start_gcs(
        cluster.session_dir, cluster.config)
    cluster.add_node(num_cpus=2, is_head=True)
    cluster.add_node(num_cpus=1, resources={"src": 2})
    cw = _connect(cluster)
    produce = _producer("src")

    before = _metric(cw, cluster.head_node.address,
                     "raylet.pull_bytes_total")
    cases = [(1_000_003, "float16"),    # ~2MB, odd element count
             (777_777, "int32"),        # ~3MB
             (8 * 1024 * 1024 + 13, "uint8")]  # >chunk size, odd bytes
    for n, dtype in cases:
        ref = produce.remote(n, dtype)
        got = ray_tpu.get(ref, timeout=scale_timeout(90))
        want = _expected(n, dtype)
        assert got.dtype == want.dtype and got.shape == want.shape
        assert np.array_equal(got, want), f"corruption at {n} {dtype}"
        del ref, got
    after = _metric(cw, cluster.head_node.address,
                    "raylet.pull_bytes_total")
    assert after - before > 8 * 1024 * 1024, \
        "pulls did not ride the bulk data plane (pull_bytes_total flat)"


def test_control_path_pull_when_streaming_raises(ray_start_cluster):
    """The fallback _pull_any keeps: when the streaming pull raises (the
    transfer.chunk_recv failpoint, armed at spawn on the PULLER only —
    what an advertised-but-unreachable bulk channel looks like from
    there), the chunked pull over the control connection carries the
    object to the end, bit-exact, and leaves no build file and no pin."""
    cluster = ray_start_cluster
    from ray_tpu._private.node import start_gcs

    cluster.gcs_svc, cluster.gcs_address = start_gcs(
        cluster.session_dir, cluster.config)
    os.environ[fp.ENV_VAR] = "transfer.chunk_recv=raise(role=raylet)"
    try:
        cluster.add_node(num_cpus=2, is_head=True)
    finally:
        del os.environ[fp.ENV_VAR]
    src = cluster.add_node(num_cpus=1, resources={"src": 2})
    cw = _connect(cluster)
    produce = _producer("src")
    head = cluster.head_node.address

    before = _metric(cw, head, "raylet.pull_bytes_total")
    # several control-path chunks (5 MiB each) with an odd tail, and one
    # object smaller than a chunk
    total = 0
    for n, dtype in [(12 * 1024 * 1024 + 13, "uint8"),
                     (777_777, "int32")]:
        ref = produce.remote(n, dtype)
        got = ray_tpu.get(ref, timeout=scale_timeout(90))
        want = _expected(n, dtype)
        assert got.dtype == want.dtype and got.shape == want.shape
        assert np.array_equal(got, want), f"corruption at {n} {dtype}"
        total += want.nbytes
        del ref, got
    # the streaming pull was tried and raised at its first chunk, so
    # every byte counted came over the control connection
    assert _metric(
        cw, head, "failpoints.transfer.chunk_recv.fired_total") >= 2
    assert _metric(cw, head, "raylet.pull_bytes_total") - before >= total
    assert not glob.glob(os.path.join(
        cluster.head_node.store_root, "*.build")), "leaked arena create"
    deadline = time.monotonic() + scale_timeout(15)
    while (time.monotonic() < deadline
           and _metric(cw, src.address, "raylet.transfer_pins") != 0):
        time.sleep(0.2)
    assert _metric(cw, src.address, "raylet.transfer_pins") == 0, \
        "the control-path pull left a transfer pin on the source"


@pytest.mark.slow
def test_streaming_pull_64mb_bit_exact(ray_start_cluster):
    """>=64MB with an odd tail through the streaming path, bit-exact."""
    cluster = ray_start_cluster
    from ray_tpu._private.node import start_gcs

    cluster.gcs_svc, cluster.gcs_address = start_gcs(
        cluster.session_dir, cluster.config)
    cluster.add_node(num_cpus=2, is_head=True)
    cluster.add_node(num_cpus=1, resources={"src": 2})
    _connect(cluster)
    produce = _producer("src")

    n = 64 * 1024 * 1024 + 7
    got = ray_tpu.get(produce.remote(n, "uint8"),
                      timeout=scale_timeout(180))
    assert got.nbytes == n
    assert np.array_equal(got, _expected(n, "uint8"))


def test_striped_pull_two_sources(ray_start_cluster):
    """With two registered holders the pull stripes across both (the
    striped counter ticks) and stays bit-exact."""
    cluster = ray_start_cluster
    from ray_tpu._private.node import start_gcs

    cluster.gcs_svc, cluster.gcs_address = start_gcs(
        cluster.session_dir, cluster.config)
    cluster.add_node(num_cpus=2, is_head=True)
    cluster.add_node(num_cpus=1, resources={"srcb": 2})
    cluster.add_node(num_cpus=1, resources={"srcc": 2})
    cw = _connect(cluster)
    produce = _producer("srcb")

    @ray_tpu.remote(num_cpus=1, resources={"srcc": 1})
    def touch(arr):
        return int(arr.nbytes)

    n = 24 * 1024 * 1024 + 5  # 3 stripe units at the default 8MB
    ref = produce.remote(n, "uint8")
    oid = ref.id().binary()
    # replicate to the second source: the consuming task's node pulls it,
    # then registers its copy in the directory
    assert ray_tpu.get(touch.remote(ref), timeout=scale_timeout(120)) == n
    _wait_locations(cw, oid, 2)

    head = cluster.head_node.address
    striped_before = _metric(cw, head, "raylet.pulls_striped_total")
    got = ray_tpu.get(ref, timeout=scale_timeout(120))  # head-side pull
    assert np.array_equal(got, _expected(n, "uint8"))
    striped_after = _metric(cw, head, "raylet.pulls_striped_total")
    assert striped_after > striped_before, \
        "pull with 2 registered sources did not stripe"


def test_locality_lease_targets_data_node(ray_start_cluster):
    """A big-arg task leases on the node already holding its plasma args
    (lease_policy.h analog), even though the head has free capacity."""
    cluster = ray_start_cluster
    from ray_tpu._private.node import start_gcs

    cluster.gcs_svc, cluster.gcs_address = start_gcs(
        cluster.session_dir, cluster.config)
    cluster.add_node(num_cpus=2, is_head=True)
    data_node = cluster.add_node(num_cpus=2, resources={"src": 1})
    cw = _connect(cluster)
    produce = _producer("src")

    ref = produce.remote(8 * 1024 * 1024, "uint8")  # lands on data_node
    _wait_locations(cw, ref.id().binary(), 1)

    @ray_tpu.remote(num_cpus=1)
    def where(arr):
        from ray_tpu._private import global_state as gs

        return gs.require_core_worker().node_id.hex()

    landed = ray_tpu.get(where.remote(ref), timeout=scale_timeout(90))
    assert landed == data_node.node_id.hex(), (
        "big-arg task did not lease on the node holding its args "
        f"(ran on {landed[:8]})")
    # counter on the head raylet (the redirecting side)
    assert _metric(cw, cluster.head_node.address,
                   "raylet.locality_spillbacks_total") >= 1


def test_spill_restore_racing_pull(ray_start_cluster):
    """An object spilled to disk on the source is restored by the bulk
    server mid-pull and arrives bit-exact."""
    cluster = ray_start_cluster
    from ray_tpu._private.node import start_gcs

    cluster.gcs_svc, cluster.gcs_address = start_gcs(
        cluster.session_dir, cluster.config)
    cluster.add_node(num_cpus=2, is_head=True)
    src = cluster.add_node(num_cpus=1, resources={"src": 2})
    cw = _connect(cluster)
    produce = _producer("src")

    n = 8 * 1024 * 1024 + 3
    ref = produce.remote(n, "uint8")
    oid = ref.id().binary()
    _wait_locations(cw, oid, 1)
    # force the source to spill EVERYTHING (need_bytes > capacity)
    assert _call(cw, src.address, "spill_now",
                 {"need_bytes": 1 << 40}) is True
    spill_files = glob.glob(os.path.join(cluster.session_dir, "spill", "*"))
    assert spill_files, "spill_now spilled nothing"
    got = ray_tpu.get(ref, timeout=scale_timeout(120))
    assert np.array_equal(got, _expected(n, "uint8"))


def test_transfer_pin_blocks_eviction_race(ray_start_cluster):
    """Legacy-path pin coverage: free_objects arriving between a puller's
    object_info and its fetch_chunk is DEFERRED (no mid-pull KeyError),
    and the deferred free completes once the pin lease lapses."""
    cluster = ray_start_cluster
    cluster.config.transfer_pin_ttl_s = 2.0
    from ray_tpu._private.node import start_gcs

    cluster.gcs_svc, cluster.gcs_address = start_gcs(
        cluster.session_dir, cluster.config)
    cluster.add_node(num_cpus=2, is_head=True)
    src = cluster.add_node(num_cpus=1, resources={"src": 2})
    cw = _connect(cluster)
    produce = _producer("src")

    ref = produce.remote(1024 * 1024, "uint8")
    oid = ref.id().binary()
    _wait_locations(cw, oid, 1)

    async def race():
        conn = await rpc.connect(src.address, name="racer")
        try:
            info = await conn.call("object_info", {"object_id": oid})
            assert info is not None
            # the eviction/free racing the transfer
            await conn.call("free_objects", {"object_ids": [oid]})
            # must still serve the chunk (pin deferred the free) —
            # the old path raised KeyError here
            data = await conn.call("fetch_chunk", {
                "object_id": oid, "offset": 0, "size": 4096})
            assert len(data) == 4096
            return info["size"]
        finally:
            await conn.close()

    size = cw._io.run(race(), timeout=scale_timeout(30))
    assert size >= 1024 * 1024  # header + payload
    # once the puller's conn is gone the deferred free completes (conn
    # close releases the pin; the TTL sweep is the backstop)
    deadline = time.monotonic() + scale_timeout(15)
    while time.monotonic() < deadline:
        if _call(cw, src.address, "object_info",
                 {"object_id": oid}) is None:
            break
        time.sleep(0.5)
    assert _call(cw, src.address, "object_info",
                 {"object_id": oid}) is None, \
        "deferred free never completed after the pin was released"
    assert _metric(cw, src.address, "raylet.transfer_pins") == 0


def test_no_location_typed_loss(ray_start_cluster):
    """A pull whose directory stays empty past the deadline propagates
    typed loss ('lost') to wait_object_local waiters instead of spinning
    the 0.2s lookup forever."""
    cluster = ray_start_cluster
    cluster.config.pull_no_location_timeout_s = 2.0
    from ray_tpu._private.node import start_gcs

    cluster.gcs_svc, cluster.gcs_address = start_gcs(
        cluster.session_dir, cluster.config)
    cluster.add_node(num_cpus=2, is_head=True)
    cw = _connect(cluster)

    ghost = os.urandom(24)  # an object id nobody ever created
    t0 = time.monotonic()
    ok = cw._io.run(cw.raylet.call(
        "wait_object_local",
        {"object_id": ghost, "timeout": scale_timeout(30)}))
    took = time.monotonic() - t0
    assert ok == "lost", f"expected typed loss, got {ok!r}"
    assert took < scale_timeout(15), \
        f"loss took {took:.1f}s — the no-location deadline did not fire"


# ---------------------------------------------------------------------------
# seeded chaos sweep: kill a source raylet mid-stream (slow tier)
# ---------------------------------------------------------------------------

_SEEDS = ([int(os.environ["RAY_TPU_CHAOS_SEED"])]
          if os.environ.get("RAY_TPU_CHAOS_SEED")
          else [231, 232, 233, 234, 235])


@pytest.mark.slow
@pytest.mark.chaos
@pytest.mark.parametrize("seed", _SEEDS)
def test_chaos_source_death_mid_stream(seed, ray_start_cluster):
    """Kill a source raylet mid-stream (transfer.chunk_send=exit armed at
    spawn on ONE source): the striped pull completes bit-exact from the
    surviving source. Then kill the ONLY remaining holder mid-stream:
    the puller surfaces typed ObjectLostError within its deadline. No
    leaked arena creates, no leaked transfer pins."""
    rng = random.Random(seed)
    nth = rng.randint(1, 3)
    print(f"[chaos] seed={seed} transfer.chunk_send exit nth={nth} "
          f"(replay: RAY_TPU_CHAOS_SEED={seed})")
    cluster = ray_start_cluster
    cluster.config.transfer_pin_ttl_s = 3.0
    cluster.config.pull_no_location_timeout_s = 3.0
    from ray_tpu._private.node import start_gcs

    cluster.gcs_svc, cluster.gcs_address = start_gcs(
        cluster.session_dir, cluster.config)
    cluster.add_node(num_cpus=2, is_head=True)
    survivor = cluster.add_node(num_cpus=1, resources={"srcc": 2})
    # arm the failpoint at SPAWN, on the doomed source only (env is
    # inherited by the raylet process; role=raylet keeps its workers out)
    os.environ[fp.ENV_VAR] = \
        f"transfer.chunk_send=exit(nth={nth},role=raylet)"
    try:
        doomed = cluster.add_node(num_cpus=1, resources={"srcb": 2})
    finally:
        del os.environ[fp.ENV_VAR]
    cw = _connect(cluster)
    produce = _producer("srcc")

    @ray_tpu.remote(num_cpus=1, resources={"srcb": 1})
    def touch(arr):
        return int(arr.nbytes)

    n = 32 * 1024 * 1024 + 9
    ref = produce.remote(n, "uint8")
    oid = ref.id().binary()
    _wait_locations(cw, oid, 1)
    # Produced on the survivor, replicated TO the doomed node: the
    # doomed raylet only receives, so its chunk_send counter is
    # untouched until the measured pull.
    assert ray_tpu.get(touch.remote(ref),
                       timeout=scale_timeout(120)) == n
    _wait_locations(cw, oid, 2)

    # the striped pull: the doomed source exits at its nth chunk; the
    # survivor resumes the remaining ranges
    from tests.conftest import state_dump_on_failure

    with state_dump_on_failure(f"object-transfer-chaos-seed{seed}",
                               reason="striped pull deadline overrun"):
        got = ray_tpu.get(ref, timeout=scale_timeout(120))
    assert np.array_equal(got, _expected(n, "uint8")), \
        f"[chaos seed={seed}] SILENT CORRUPTION after source death"
    assert not doomed.svc.alive(), \
        "failpoint never fired (source still alive) — schedule inert"
    cluster.remove_node(doomed)
    del got

    # no leaked arena create on the puller, no leaked pins on the
    # survivor once its bulk connection wound down
    assert not glob.glob(os.path.join(
        cluster.head_node.store_root, "*.build")), "leaked arena create"
    deadline = time.monotonic() + scale_timeout(15)
    while time.monotonic() < deadline:
        if _metric(cw, survivor.address, "raylet.transfer_pins") == 0:
            break
        time.sleep(0.5)
    assert _metric(cw, survivor.address, "raylet.transfer_pins") == 0, \
        f"[chaos seed={seed}] leaked transfer pins on the survivor"

    # --- total loss: the ONLY holder dies mid-stream -> typed error ---
    produce2 = ray_tpu.remote(num_cpus=1, resources={"srcc": 1},
                              max_retries=0)(_raw_produce)
    ref2 = produce2.remote(16 * 1024 * 1024 + 1)
    oid2 = ref2.id().binary()
    _wait_locations(cw, oid2, 1)
    fp.arm_cluster("transfer.chunk_send=exit(nth=1,role=raylet)")
    try:
        with pytest.raises(exc.ObjectLostError):
            ray_tpu.get(ref2, timeout=scale_timeout(120))
    except exc.GetTimeoutError:
        from tests.conftest import dump_state_artifact

        dump_state_artifact(f"object-transfer-chaos-loss-seed{seed}",
                            reason="single-source death hung")
        pytest.fail(f"[chaos seed={seed}] single-source death HUNG past "
                    f"its deadline (replay: RAY_TPU_CHAOS_SEED={seed})")
    finally:
        fp.reset()
    assert not survivor.svc.alive(), \
        "failpoint never fired on the last holder"
    cluster.remove_node(survivor)
    assert not glob.glob(os.path.join(
        cluster.head_node.store_root, "*.build")), "leaked arena create"


def _raw_produce(n):
    import numpy as np

    return (np.arange(n) % 251).astype(np.uint8)
