"""Test fixtures (modeled on the reference's conftest: ray_start_regular /
ray_start_cluster, reference: python/ray/tests/conftest.py:70-156).

All tests run with JAX on a virtual 8-device CPU mesh so sharding logic is
exercised without TPU hardware.
"""

import os

# Ownership stamp for the leak check: every runtime process this pytest
# process starts (GCS, raylets, workers — also workers re-parented to
# init after their raylet died) inherits it through the environment, and
# session directories land under a per-owner base. Under pytest-xdist
# each worker is its own owner, so one worker's leak check never counts,
# kills or unlinks what another worker's live cluster owns.
_OWNER = str(os.getpid())
os.environ["RAY_TPU_TEST_OWNER"] = _OWNER
_tmp_base = os.environ.get("RAY_TPU_TMPDIR", "/tmp/ray_tpu")
if os.path.basename(_tmp_base).startswith("owner_"):
    # inherited from the xdist controller's conftest import: don't nest
    _tmp_base = os.path.dirname(_tmp_base)
_OWNER_TMPDIR = os.path.join(_tmp_base, f"owner_{_OWNER}")
os.environ["RAY_TPU_TMPDIR"] = _OWNER_TMPDIR

# Must be set before jax (or anything importing jax) loads in this process
# and in every subprocess the runtime spawns.
os.environ["XLA_FLAGS"] = (
    os.environ.get("XLA_FLAGS", "")
    + " --xla_force_host_platform_device_count=8"
).strip()
os.environ["JAX_PLATFORMS"] = "cpu"

# Arm the driver-shutdown flight-recorder tail for the whole test tree:
# the leak check names leaked workers/leases/pins from the final cluster
# snapshot (debug_state.FINAL_SNAPSHOT). Opt-in by env so production
# driver exits never pay the sweep.
os.environ.setdefault("RAY_TPU_FINAL_SNAPSHOT", "1")

# JAX's own persistent cache stays off in the test tree (the repo-level
# default, <repo>/.jax_cache, would make one run's compiles another's
# hits); the tests of its placement set the variable themselves.
os.environ.pop("JAX_COMPILATION_CACHE_DIR", None)
os.environ["JAX_ENABLE_COMPILATION_CACHE"] = "false"

import pytest  # noqa: E402

# Hard-coded test timeouts assume an unloaded multi-core box; CI for this
# repo often runs on ONE time-shared core where everything (driver, GCS,
# raylet, workers) contends for the same cpu. Scale every wall-clock
# budget: explicitly via RAY_TPU_TEST_TIMEOUT_SCALE, or 2x automatically
# when <=2 cpus are usable (the streaming key-by flake).
_USABLE_CPUS = (len(os.sched_getaffinity(0))
                if hasattr(os, "sched_getaffinity")
                else (os.cpu_count() or 1))
_TIMEOUT_SCALE = float(os.environ.get("RAY_TPU_TEST_TIMEOUT_SCALE") or (
    2.0 if _USABLE_CPUS <= 2 else 1.0))


def scale_timeout(seconds: float) -> float:
    """Scale a hard-coded test timeout for slow/oversubscribed boxes."""
    return seconds * _TIMEOUT_SCALE


def pytest_configure(config):
    config.addinivalue_line(
        "markers", "slow: long-running tests excluded from tier-1")
    config.addinivalue_line(
        "markers",
        "chaos: seeded fault-injection sweep (slow tier). Runs with "
        "`pytest -m chaos`; a failure logs its seed — replay it "
        "deterministically with RAY_TPU_CHAOS_SEED=<seed>.")


# ---------------------------------------------------------------------------
# flight-recorder artifacts: chaos sweeps dump cluster_state + stacks on
# deadline overrun, so a seeded hang is triaged from the recording
# instead of a reproduction run
# ---------------------------------------------------------------------------


def _artifact_dir() -> str:
    return os.environ.get(
        "RAY_TPU_TEST_ARTIFACT_DIR",
        os.path.join(os.path.dirname(os.path.abspath(__file__)),
                     "artifacts"))


def dump_state_artifact(name: str, reason: str = "") -> str | None:
    """Dump the live cluster's state snapshot + this process's thread
    stacks to tests/artifacts/<name>.json. Never raises (triage must
    not mask the original failure); returns the path or None."""
    import re
    import time as _time

    from ray_tpu._private import debug_state, global_state

    try:
        cw = global_state.get_core_worker()
        snap: dict = {}
        if cw is not None:
            try:
                snap = cw.get_cluster_state(timeout=3.0)
            except Exception as e:
                snap = {"error": f"{type(e).__name__}: {e}"}
        safe = re.sub(r"[^A-Za-z0-9._-]+", "_", name)[:150]
        path = os.path.join(_artifact_dir(),
                            f"{safe}-{int(_time.time())}.json")
        out = debug_state.dump_artifact(path, snap, reason=reason)
        print(f"[state-dump] cluster snapshot -> {out}")
        return out
    except Exception as e:  # pragma: no cover - best effort
        print(f"[state-dump] failed: {e}")
        return None


class state_dump_on_failure:
    """Context manager for chaos deadline waits: any escaping exception
    (GetTimeoutError, assert, typed error the test didn't expect) dumps
    a cluster_state + stacks artifact BEFORE the failure propagates —
    while the wedged cluster is still alive to answer."""

    def __init__(self, name: str, reason: str = "chaos deadline overrun"):
        self.name = name
        self.reason = reason

    def __enter__(self):
        return self

    def __exit__(self, exc_type, exc_val, exc_tb):
        if exc_type is not None:
            dump_state_artifact(
                self.name,
                reason=f"{self.reason}: {exc_type.__name__}: {exc_val}")
        return False


# ---------------------------------------------------------------------------
# leak check: no orphaned runtime processes, no leaked /dev/shm segments
# ---------------------------------------------------------------------------
# Timed-out/crashed tests used to leave gcs/raylet/worker orphans that
# poisoned every later test and benchmark on this box (gVisor benches have
# bitten on orphan cleanup before). Enforced per test: anything the test
# spawned must be gone once it no longer holds a cluster.

_RUNTIME_CMD_MARKS = ("ray_tpu.worker.main", "ray_tpu.raylet.raylet",
                      "ray_tpu.gcs.server", "ray_tpu.gcs.shard",
                      "ray_tpu.scalesim.worker")


def _owned(pid: str) -> bool:
    """True when /proc/<pid> carries this pytest process's ownership
    stamp in its (exec-time) environment."""
    try:
        with open(f"/proc/{pid}/environ", "rb") as f:
            env = f.read().split(b"\0")
    except OSError:
        return False
    return f"RAY_TPU_TEST_OWNER={_OWNER}".encode() in env


def _runtime_procs() -> dict:
    """pid -> cmdline of live ray_tpu runtime processes THIS pytest
    process started (zombies excluded: their /proc cmdline reads empty;
    other xdist workers' clusters excluded: they carry another owner)."""
    procs = {}
    for pid in os.listdir("/proc"):
        if not pid.isdigit():
            continue
        try:
            with open(f"/proc/{pid}/cmdline", "rb") as f:
                cmd = f.read().decode(errors="replace").replace("\0", " ")
        except OSError:
            continue
        if any(mark in cmd for mark in _RUNTIME_CMD_MARKS) and _owned(pid):
            procs[int(pid)] = cmd.strip()
    return procs


# Bare segments (no runtime up: segment_dir() falls back to the
# process-independent /dev/shm/ray_tpu_colseg) carry no owner in their
# path, so the ones created in THIS process are recorded at creation.
_BARE_SEGMENTS: set = set()


def _track_bare_segments():
    import ray_tpu.native.store as _store
    from ray_tpu.native.store import segment as _segment

    create = _segment.create_segment

    def create_segment(name, size):
        seg = create(name, size)
        _BARE_SEGMENTS.add(seg.path)
        return seg

    _segment.create_segment = _store.create_segment = create_segment


_track_bare_segments()


def _colseg_files() -> set:
    """Live collective shm segment files (tmpfs bytes a crashed rank can
    leak) of THIS owner: segments under the sessions in this owner's
    session base, plus the bare segments this process created.
    Object-store arenas are session-lifetime by design and are NOT
    counted here."""
    import glob

    found = {p for p in _BARE_SEGMENTS if os.path.exists(p)}
    try:
        sessions = os.listdir(_OWNER_TMPDIR)
    except FileNotFoundError:
        sessions = []
    # segment_dir() puts in-cluster segments BESIDE the store arena:
    # /dev/shm/ray_tpu/<session>/objects/colseg (dirname of store_root =
    # <session>/objects/<node8>)
    for session in sessions:
        for pattern in (f"/dev/shm/ray_tpu/{session}/objects/colseg/*",
                        f"/dev/shm/ray_tpu/{session}/colseg/*"):
            found.update(glob.glob(pattern))
    return found


def _leak_notes(leaked_pids: dict, leaked_segs: set) -> str:
    """Name leaked processes / segments / still-held resources from the
    final cluster snapshot captured at driver shutdown (debug_state
    FINAL_SNAPSHOT), so the failure reads as 'worker abc123 holding
    lease X for actor Y' instead of a bare pid."""
    from ray_tpu._private import debug_state

    snap = debug_state.FINAL_SNAPSHOT
    if not snap:
        return ""
    notes: list[str] = []
    try:
        # drained-node state: a node still DRAINING when the driver shut
        # down means a drain never finished — its raylet process is the
        # usual orphan, so name the wedge before the bare pids
        for n in (snap.get("gcs") or {}).get("nodes_table") or []:
            if n.get("state") == "DRAINING":
                notes.append(
                    f"  node {n.get('node_id')} still DRAINING at "
                    f"shutdown (conn_live={n.get('conn_live')}) — drain "
                    f"never reached DRAINED; its raylet is the likely "
                    f"orphan")
        by_pid: dict[int, str] = {}
        for label, proc in debug_state.iter_processes(snap):
            pid = proc.get("pid")
            if isinstance(pid, int):
                # setdefault: the raylet's worker_pool row (richer —
                # actor/lease held) wins over the worker's own label
                by_pid.setdefault(pid, f"{label} ({proc.get('role', '?')})")
            for w in proc.get("worker_pool") or []:
                desc = (f"worker {w.get('worker_id')} on {label}"
                        + (f" running actor {w['actor_id']}"
                           if w.get("actor_id") else "")
                        + (f" holding lease {w['lease_id']}"
                           if w.get("lease_id") else ""))
                if isinstance(w.get("pid"), int):
                    by_pid[w["pid"]] = desc
        for pid in leaked_pids:
            if pid in by_pid:
                notes.append(f"  pid {pid}: {by_pid[pid]}")
        # resources still held at shutdown — the usual cause of orphans
        for label, proc in debug_state.iter_processes(snap):
            for lease in proc.get("leases") or []:
                notes.append(
                    f"  unreturned lease {lease.get('lease_id')} on "
                    f"{label} -> worker {lease.get('worker')} "
                    f"(inflight={lease.get('inflight')})")
            pins = (proc.get("transfers") or {}).get("pins") or {}
            for oid, rec in pins.items():
                notes.append(f"  leaked transfer pin on {label}: object "
                             f"{oid} ({rec.get('pins')} lease(s), "
                             f"expires_in={rec.get('expires_in_s')}s)")
            if leaked_segs:
                for g in proc.get("collectives") or []:
                    notes.append(
                        f"  live collective group {g.get('group')!r} "
                        f"rank {g.get('rank')} on {label} "
                        f"(op={g.get('op') or 'idle'})")
            # serve replica-group members name their gang: a leaked
            # member reads as 'rank 2 of backend X' instead of a pid
            comp = proc.get("component") or {}
            if (leaked_pids or leaked_segs) and comp.get("kind") == \
                    "serve-replica-group-member":
                notes.append(
                    f"  live replica-group member rank {comp.get('rank')}"
                    f"/{comp.get('world_size')} of backend "
                    f"{comp.get('backend')!r} on {label} "
                    f"(group {comp.get('group')})")
            # streaming tier: KV pages whose owner sequence is gone are
            # a leak named per owner (the chaos sweeps' zero-leaked-
            # pages invariant reads from the same snapshot)
            eng = comp.get("engine") or {}
            for leak in eng.get("kv_leaked") or []:
                notes.append(
                    f"  leaked KV pages on {label} (backend "
                    f"{eng.get('backend')!r}): owner {leak.get('owner')} "
                    f"holds {leak.get('pages')} page(s) / "
                    f"{leak.get('tokens')} token(s) with no live "
                    f"sequence or session")
    except Exception:
        return ""
    if not notes:
        return ""
    return ("\nfinal cluster snapshot (captured at shutdown) names:\n"
            + "\n".join(notes[:20]))


@pytest.fixture(autouse=True)
def leak_check(request):
    """After each test: if the test no longer holds a cluster, every
    runtime process and collective shm segment it created must be gone.
    Leaked processes are killed (so one bad test can't poison the run)
    and the test FAILS, naming them."""
    if os.environ.get("RAY_TPU_NO_LEAK_CHECK"):
        yield
        return
    import signal
    import time

    before_procs = set(_runtime_procs())
    before_segs = _colseg_files()
    yield
    from ray_tpu._private import global_state

    if global_state.get_core_worker() is not None:
        return  # a (module-scoped) cluster is legitimately still up
    # A net under `shutdown()`, which since PR 46 returns only when its
    # processes have left the process table: the wait below is entered
    # only by a test that ends a cluster some other way (a raylet that
    # drained or fail-stopped by itself leaves its workers to their own
    # exit), and the loop exits as soon as the diff is clean.
    deadline = time.monotonic() + scale_timeout(20)
    leaked = {}
    while True:
        leaked = {pid: cmd for pid, cmd in _runtime_procs().items()
                  if pid not in before_procs}
        leaked_segs = _colseg_files() - before_segs
        if (not leaked and not leaked_segs) or time.monotonic() > deadline:
            break
        time.sleep(0.25)
    for pid in leaked:
        try:
            os.kill(pid, signal.SIGKILL)
        except (ProcessLookupError, PermissionError):
            pass
    for path in leaked_segs:
        try:
            os.unlink(path)
        except OSError:
            pass
    notes = (_leak_notes(leaked, leaked_segs)
             if (leaked or leaked_segs) else "")
    assert not leaked, (
        f"test leaked {len(leaked)} orphaned runtime process(es) "
        f"(now killed): {leaked}{notes}")
    assert not leaked_segs, (
        f"test leaked /dev/shm collective segment(s) (now removed): "
        f"{sorted(leaked_segs)}{notes}")
    # continuous-profiler hygiene: with no cluster held, this process
    # must not keep a sampler thread alive (ray_tpu.shutdown stops it;
    # a test that armed one directly must stop it too). Named so the
    # failure reads as the sampler, not an anonymous thread.
    import threading

    from ray_tpu._private import sampling_profiler as _sprof

    orphaned = [t for t in threading.enumerate()
                if t.name == _sprof.THREAD_NAME and t.is_alive()]
    if orphaned:
        _sprof.stop()
        orphan_names = [f"{t.name} (ident={t.ident}, daemon={t.daemon})"
                        for t in orphaned]
        raise AssertionError(
            f"test leaked {len(orphaned)} orphaned sampler thread(s) "
            f"(now stopped): {orphan_names} — a stopped runtime must "
            f"stop its continuous profiler (sampling_profiler.stop)")


@pytest.fixture
def ray_start_regular():
    import ray_tpu

    ray_tpu.init(num_cpus=4)
    try:
        yield
    finally:
        ray_tpu.shutdown()


@pytest.fixture(scope="module")
def ray_start_shared():
    import ray_tpu

    ray_tpu.init(num_cpus=8)
    try:
        yield
    finally:
        ray_tpu.shutdown()


@pytest.fixture
def ray_start_cluster():
    from ray_tpu.cluster_utils import Cluster

    cluster = Cluster(initialize_head=False)
    try:
        yield cluster
    finally:
        from ray_tpu._private import global_state

        cw = global_state.get_core_worker()
        if cw is not None:
            cw.shutdown()
        cluster.shutdown()


@pytest.fixture
def ray_start_cluster_2_nodes():
    from ray_tpu.cluster_utils import Cluster

    cluster = Cluster(initialize_head=True,
                      head_node_args={"num_cpus": 2})
    cluster.add_node(num_cpus=2)
    try:
        yield cluster
    finally:
        from ray_tpu._private import global_state

        cw = global_state.get_core_worker()
        if cw is not None:
            cw.shutdown()
        cluster.shutdown()


@pytest.fixture
def plain_mixer_conv(monkeypatch):
    """-> swap(): programs traced after it is called run the recurrent
    mixers' convolution in its plain form
    (`ops/short_conv.py::mixer_conv_xla`); it returns a list that gets, a
    call, the channel block `mixer_conv` takes at that shape — 0 where
    it would have been the plain form anyway."""
    from ray_tpu.models import decoder
    from ray_tpu.ops import short_conv

    def swap():
        blocks = []

        def plain(x, taps, bias=None, n_unit=0, head=0):
            blocks.append(short_conv._channel_block(*taps.shape[::-1],
                                                    n_unit, head))
            return short_conv.mixer_conv_xla(x, taps, bias, n_unit, head)

        monkeypatch.setattr(decoder, "mixer_conv", plain)
        return blocks

    return swap
