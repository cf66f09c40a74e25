"""Persistent AOT compile cache: executables survive process death.

Covers the key schema (runtime fingerprint + seam parts; quantize mode
never shares an executable — the satellite regression), blob/index
storage round-trips, the CachedFunction resolution contract (hit:
deserialized `jax.export` blob, jax.compiles_total stays FLAT; miss:
export + store + normal compile recording), the gang-restart gate
(warm restart records >=1 hit and strictly fewer compiles than the
cold start), the `compile_cache.load` failpoint degrading to a
re-trace (errors counter, op still serves), and the recorded
MICROBENCH cold_gang_ttft row."""

import json
import os
import tempfile

import numpy as np
import pytest

import ray_tpu
from tests.conftest import scale_timeout

from ray_tpu._private import compile_cache as _cc

WORLD = 3


# ---------------------------------------------------------------------------
# unit layer: keys, fingerprint, storage
# ---------------------------------------------------------------------------


@pytest.fixture()
def cache_sandbox(monkeypatch):
    """A private cache dir per test: the session-wide dir (conftest)
    is shared by every spawned worker, so key-collision assertions
    need their own floor."""
    d = tempfile.mkdtemp(prefix="ray_tpu_cc_unit_")
    monkeypatch.setenv("RAY_TPU_COMPILE_CACHE_DIR", d)
    yield d


def test_make_key_stable_and_fingerprint_sensitive(cache_sandbox,
                                                   monkeypatch):
    """Same (seam, parts) -> same key; any part, the seam, or the
    runtime fingerprint changing -> a different key (a blob compiled
    for another runtime must never load)."""
    k1 = _cc.make_key("collective", ("ar", "exact", "sum", "f32", 1024))
    assert k1 == _cc.make_key("collective",
                              ("ar", "exact", "sum", "f32", 1024))
    assert k1 != _cc.make_key("collective",
                              ("ar", "exact", "max", "f32", 1024))
    assert k1 != _cc.make_key("train.step",
                              ("ar", "exact", "sum", "f32", 1024))
    # fingerprint sensitivity: a different runtime is a clean miss
    real = _cc.runtime_fingerprint()
    monkeypatch.setattr(_cc, "_fingerprint", real + "|other-jaxlib")
    assert k1 != _cc.make_key("collective",
                              ("ar", "exact", "sum", "f32", 1024))


def test_store_lookup_index_clear_round_trip(cache_sandbox):
    key = _cc.make_key("unit", ("blob", 1))
    assert _cc.lookup(key) is None  # absent: no error counted
    assert _cc.store(key, b"\x01\x02\x03", seam="unit",
                     parts=("blob", 1))
    assert _cc.lookup(key) == b"\x01\x02\x03"
    index = _cc.read_index()
    assert key in index
    assert index[key]["seam"] == "unit"
    assert index[key]["parts"] == ["blob", "1"]
    assert index[key]["size"] == 3
    _cc.record_hit(key)
    assert _cc.read_index()[key]["hits"] == 1
    # no stray temp files after a clean writer
    strays = [n for n in os.listdir(cache_sandbox)
              if n.startswith(_cc.TMP_PREFIX)]
    assert not strays, strays
    assert _cc.clear() == 1
    assert _cc.lookup(key) is None
    assert _cc.read_index() == {}


def test_disabled_cache_never_touches_disk(cache_sandbox, monkeypatch):
    monkeypatch.setenv("RAY_TPU_COMPILE_CACHE", "0")
    key = _cc.make_key("unit", ("off",))
    assert not _cc.store(key, b"x")
    assert _cc.lookup(key) is None
    assert not os.path.exists(os.path.join(cache_sandbox,
                                           key + ".jaxexp"))


def test_quantize_modes_never_share_executable(cache_sandbox):
    """Satellite regression: two collective ops differing ONLY in
    quantize mode resolve to different in-process jit-cache keys AND
    different persistent-cache entries (an int8-ring executable loaded
    for an exact op would silently corrupt results)."""
    import jax
    from jax.sharding import Mesh

    from ray_tpu.collective.backends.xla_backend import _DeviceOps
    from ray_tpu.collective.types import QUANT_BLOCK, ReduceOp

    mesh = Mesh(np.array(jax.devices("cpu")[:1]), ("hosts",))
    ops = _DeviceOps(mesh, "hosts", 1)
    n = QUANT_BLOCK * 2  # valid layout for both the exact + int8 rings
    garr = jax.numpy.ones((1, n), jax.numpy.float32)
    ops.allreduce(garr, ReduceOp.SUM)
    ops.allreduce_quantized(garr, ReduceOp.SUM)
    keys = list(ops._cache.keys())
    assert len(keys) == 2
    # the jit-cache keys differ in their op-kind/quantize prefix...
    assert keys[0][0] != keys[1][0], keys
    # ...and so do the PERSISTENT entries: one blob per mode on disk
    index = _cc.read_index()
    assert len(index) == 2, index
    seams = {tuple(e["parts"]) for e in index.values()}
    assert len(seams) == 2, index


def test_fingerprint_not_memoized_while_uninit(monkeypatch):
    """A key built before jax backend init must not pin the degraded
    'uninit' fingerprint for the process's whole life — once the
    backend facts resolve, later keys carry the full fingerprint."""
    import jax

    monkeypatch.setattr(_cc, "_fingerprint", None)
    with monkeypatch.context() as m:
        m.setattr(jax, "default_backend",
                  lambda: (_ for _ in ()).throw(
                      RuntimeError("backend not ready")))
        fp1 = _cc.runtime_fingerprint()
        assert "uninit" in fp1
        assert _cc._fingerprint is None  # degraded facts: no memo
    fp2 = _cc.runtime_fingerprint()
    assert "uninit" not in fp2
    assert _cc._fingerprint == fp2  # complete facts memoize


def test_state_preexisting_excludes_own_stores(cache_sandbox):
    """entries_preexisting counts only entries created BEFORE this
    process: blobs the process itself stored on its own cold misses
    must never read as a warm cache (the doctor false-positive)."""
    key = _cc.make_key("unit", ("pre",))
    assert _cc.store(key, b"x", seam="unit", parts=("pre",))
    st = _cc.state()
    assert st["entries"] == 1
    assert st["entries_preexisting"] == 0  # stored by THIS process
    with _cc._index_lock():
        index = _cc._read_index()
        index[key]["created"] = _cc._PROCESS_START - 60.0
        _cc._write_index(index)
    assert _cc.state()["entries_preexisting"] == 1


def test_doctor_cold_finding_needs_preexisting_entries():
    """diagnose() fires compile_cache_cold only when stored executables
    PREDATE the process — a first-ever cold gang (its own misses
    populated the index) is not 'a restart that re-traced'."""
    from ray_tpu._private import debug_state

    def snap(pre):
        return {"driver": {"pid": 1, "compile_cache": {
            "enabled": True, "dir": "/tmp/x", "entries": 3,
            "entries_preexisting": pre, "hits": 0, "misses": 3,
            "errors": 0}}}

    findings = debug_state.diagnose(snap(0), {})
    assert not any(f["kind"] == "compile_cache_cold" for f in findings)
    findings = debug_state.diagnose(snap(3), {})
    cold = next(f for f in findings
                if f["kind"] == "compile_cache_cold")
    assert "3 stored executables predating" in cold["detail"]


def test_index_update_cross_process_atomic(cache_sandbox):
    """Ranks sharing the cache dir must not lose each other's index
    entries: the read-modify-write holds an OS file lock, so N
    concurrent writers land ALL their entries (an in-process lock
    alone is last-writer-wins across processes)."""
    import subprocess
    import sys

    code = (
        "import sys\n"
        "from ray_tpu._private import compile_cache as cc\n"
        "tag = sys.argv[1]\n"
        "for i in range(20):\n"
        "    cc._index_update('k-%s-%d' % (tag, i), seam='unit',\n"
        "                     size=1, created=1.0)\n")
    env = dict(os.environ, RAY_TPU_COMPILE_CACHE_DIR=cache_sandbox)
    procs = [subprocess.Popen([sys.executable, "-c", code, str(t)],
                              env=env)
             for t in range(4)]
    for p in procs:
        assert p.wait(timeout=scale_timeout(120)) == 0
    keys = [k for k in _cc.read_index() if k.startswith("k-")]
    assert len(keys) == 80, len(keys)


def test_donated_hit_path_validates_before_consuming(cache_sandbox):
    """Donated seams (the paged-KV update, Trainer steps): a corrupt
    blob degrades to a re-trace with the inputs INTACT — the hit path
    AOT-compiles the deserialized module before the first donated
    dispatch, so a stale entry fails while fallback is still possible,
    never on already-deleted buffers. A good blob then resolves to a
    donated hit through the same AOT path."""
    import jax
    import jax.numpy as jnp

    jitted = jax.jit(lambda a, b: a + b, donate_argnums=(0,))
    parts = ("donate", "f32", 8)
    key = _cc.make_key("unit.donate", parts)
    assert _cc.store(key, b"not a jax.export blob")
    e0 = _cc.M_ERRORS.snapshot()["value"]

    cf = _cc.CachedFunction("unit.donate", parts, jitted,
                            donate_argnums=(0,))
    out = cf(jnp.ones((8,), jnp.float32), jnp.ones((8,), jnp.float32))
    assert cf.resolved == "miss"  # degraded, never user-visible
    assert _cc.M_ERRORS.snapshot()["value"] >= e0 + 1
    np.testing.assert_array_equal(np.asarray(out), np.full(8, 2.0))

    # the miss re-exported a VALID blob over the corrupt one: a fresh
    # seam now hits, donation applied via the validated AOT executable
    cf2 = _cc.CachedFunction("unit.donate", parts, jitted,
                             donate_argnums=(0,))
    out2 = cf2(jnp.ones((8,), jnp.float32), jnp.ones((8,), jnp.float32))
    assert cf2.resolved == "hit"
    np.testing.assert_array_equal(np.asarray(out2), np.full(8, 2.0))


def _resolution_spans(cf, *args):
    """One call of `cf` inside a trace: its spans by name, each with
    its attributes less the ids."""
    from ray_tpu._private import tracing

    root = tracing.new_context()
    with tracing.open_tree(root) as rows, tracing.use(root):
        cf(*args)
    return {name: {k: v for k, v in fields.items()
                   if k not in ("tid", "sid", "psid")}
            for name, _, _, fields in rows}


@pytest.mark.parametrize("case", ["miss_then_hit", "export_raises"])
def test_a_resolution_is_spans_and_listens_only_while_open(cache_sandbox,
                                                           monkeypatch,
                                                           case):
    """A first call's parts are `compile.*` spans of the ambient trace
    with the seam's `key`: a miss looks up (`hit` 0), exports (`error`
    0) and dispatches under `jax.compile`, which carries what jax timed
    inside it; the next process's call looks up (`hit` 1) and loads; an
    export that raises says `error` 1 and the call is served by the
    plain jit. The one jax.monitoring listener lives only inside a
    resolution."""
    import jax
    import jax.numpy as jnp
    from jax._src import monitoring

    def listeners():
        return monitoring.get_event_duration_listeners().count(
            _cc._on_jax_duration)

    live = []

    def fn(a, b):
        live.append(listeners())    # while the step is being traced
        return a * b + 1.0

    x = jnp.ones((8,), jnp.float32)
    parts = ("spans", case, "f32", "8")
    key = "unit.spans:" + ":".join(parts)
    if case == "export_raises":
        from jax import export as _export

        def refuse(*a, **kw):
            raise TypeError("an unregistered pytree node")

        monkeypatch.setattr(_export, "export", refuse)
    e0 = _cc.M_ERRORS.snapshot()["value"]
    assert listeners() == 0
    cf = _cc.CachedFunction("unit.spans", parts, jax.jit(fn),
                            fingerprint_computation=True)
    spans = _resolution_spans(cf, x, x)
    assert listeners() == 0 and live and set(live) == {1}
    assert cf.resolved == "miss"
    assert {n: s["key"] for n, s in spans.items()} == dict.fromkeys(
        ["compile.fingerprint", "compile.lookup", "compile.export",
         "jax.compile"], key)
    assert (spans["compile.lookup"]["hit"],
            spans["compile.lookup"]["bytes"]) == (0, 0)
    timed = spans["jax.compile"]
    assert timed["programs"] >= 1 and timed["backend_s"] > 0
    assert timed["persistent_hit"] == 0     # the test tree: cache off
    assert 0 <= timed["lower_s"] and timed["trace_s"] >= 0
    if case == "export_raises":
        assert spans["compile.export"]["error"] == 1
        assert spans["compile.export"]["bytes"] == 0
        assert _cc.M_ERRORS.snapshot()["value"] == e0 + 1
        return
    assert spans["compile.export"]["error"] == 0
    stored = spans["compile.export"]["bytes"]
    assert stored > 0

    # a second process's first call: the same seam, resolved afresh
    cf2 = _cc.CachedFunction("unit.spans", parts, jax.jit(fn),
                             fingerprint_computation=True)
    spans = _resolution_spans(cf2, x, x)
    assert cf2.resolved == "hit" and listeners() == 0
    assert set(spans) == {"compile.fingerprint", "compile.lookup",
                          "compile.load"}
    assert (spans["compile.lookup"]["hit"],
            spans["compile.lookup"]["bytes"]) == (1, stored)
    assert spans["compile.load"]["ok"] == 1
    assert spans["compile.load"]["programs"] >= 1
    # resolved: later calls open no resolution and record no span
    assert _resolution_spans(cf2, x, x) == {}
    # outside any trace a resolution still resolves, and leaves no
    # listener behind
    cf3 = _cc.CachedFunction("unit.spans", parts, jax.jit(fn),
                             fingerprint_computation=True)
    cf3(x, x)
    assert cf3.resolved == "hit" and listeners() == 0


# ---------------------------------------------------------------------------
# gang layer: restart round-trip + failpoint chaos
# ---------------------------------------------------------------------------


@ray_tpu.remote
class CacheWorker:
    def setup(self, world, rank, group_name, multihost_name,
              cache_dir, failpoint=None):
        # this process's cache dir, set BEFORE any cache access: the
        # driver's environment does not reach a worker of the running
        # shared cluster, and the session's dir has seen other tests
        os.environ["RAY_TPU_COMPILE_CACHE_DIR"] = cache_dir
        if failpoint:  # armed BEFORE any cache access in this process
            from ray_tpu._private import failpoints

            failpoints.arm(failpoint, "raise")
        from ray_tpu import collective as col
        from ray_tpu.parallel import multihost

        multihost.initialize(multihost_name, world, rank)
        col.init_collective_group(world, rank, backend="host",
                                  group_name=group_name, timeout=60.0)
        self.group_name = group_name
        return True

    def warm_and_stats(self, n):
        """One forced-DEVICE allreduce (the persistent-cached seam),
        then this process's compile/cache counters."""
        from ray_tpu._private import stats
        from ray_tpu.collective import collective as C

        group = C._manager.get_group(self.group_name)
        group.force_transport = "device"
        out = group.allreduce(np.ones(n, np.float32))
        group.force_transport = None
        snap = stats.snapshot()

        def val(name):
            s = snap.get(name)
            return float(s["value"]) if s else 0.0

        return {"val": float(np.asarray(out)[0]),
                "compiles": val("jax.compiles_total"),
                "hits": val("jax.compile_cache_hits_total"),
                "misses": val("jax.compile_cache_misses_total"),
                "errors": val("jax.compile_cache_errors_total")}

    def destroy_group(self):
        from ray_tpu import collective as col

        col.destroy_collective_group(self.group_name)
        return True


def _rank_cache_dirs(tmp_path):
    """One fresh cache dir per rank, as on a gang with one rank per
    host: a cold rank can only ever find what IT stored (ranks sharing
    a dir race — a slow rank may find a fast one's blob), and a
    restarted rank finds its predecessor's blobs."""
    dirs = [str(tmp_path / f"rank{i}") for i in range(WORLD)]
    for d in dirs:
        os.makedirs(d)
    return dirs


def _gang(tag, cache_dirs, failpoint=None):
    workers = [CacheWorker.remote() for _ in range(WORLD)]
    ray_tpu.get([w.setup.remote(WORLD, i, f"g_cc_{tag}", f"cc{tag}",
                                cache_dirs[i], failpoint)
                 for i, w in enumerate(workers)],
                timeout=scale_timeout(240))
    return workers


def _teardown(workers):
    ray_tpu.get([w.destroy_group.remote() for w in workers], timeout=60)
    for w in workers:
        ray_tpu.kill(w)


def test_gang_restart_hits_cache_and_skips_compiles(ray_start_shared,
                                                    tmp_path):
    """THE acceptance gate: a cold gang populates the cache (misses +
    compiles recorded); the gang is killed; a restarted gang running
    the SAME shape-classes records >=1 cache hit per rank, ZERO new
    `jax.compiles_total` for the cached seam, and strictly fewer
    compiles than the cold start."""
    dirs = _rank_cache_dirs(tmp_path)
    n = 1 << 16  # 256KB: above pallas_max_bytes, squarely device-tier
    cold = _gang("cold", dirs)
    stats_a = ray_tpu.get([w.warm_and_stats.remote(n) for w in cold],
                          timeout=scale_timeout(240))
    for s in stats_a:
        assert s["val"] == float(WORLD)
        assert s["compiles"] >= 1, stats_a  # cold gang traced
        assert s["misses"] >= 1, stats_a  # ...and populated the cache
        assert s["hits"] == 0, stats_a
    _teardown(cold)  # kill the gang: executables outlive the processes

    warm = _gang("warm", dirs)
    stats_b = ray_tpu.get([w.warm_and_stats.remote(n) for w in warm],
                          timeout=scale_timeout(240))
    for a, b in zip(stats_a, stats_b):
        assert b["val"] == float(WORLD)
        assert b["hits"] >= 1, stats_b  # restart deserialized the blob
        # zero new compiles for the cached shape-class: the seam's
        # record_compile never ran, so the counter stayed FLAT
        assert b["compiles"] == 0, stats_b
        assert b["compiles"] < a["compiles"], (stats_a, stats_b)
        assert b["errors"] == 0, stats_b
    _teardown(warm)


def test_cache_load_failpoint_degrades_to_retrace(ray_start_shared,
                                                  tmp_path):
    """Chaos satellite: `compile_cache.load` raising during a gang
    restart must NOT fail the op — every rank re-traces (compiles
    recorded), serves the collective, and counts the typed
    `jax.compile_cache_errors_total`."""
    dirs = _rank_cache_dirs(tmp_path)
    n = 1 << 16
    cold = _gang("fpcold", dirs)
    ray_tpu.get([w.warm_and_stats.remote(n) for w in cold],
                timeout=scale_timeout(240))
    _teardown(cold)

    broken = _gang("fpwarm", dirs, failpoint="compile_cache.load")
    stats_c = ray_tpu.get([w.warm_and_stats.remote(n) for w in broken],
                          timeout=scale_timeout(240))
    for s in stats_c:
        assert s["val"] == float(WORLD)  # the gang still serves
        assert s["errors"] >= 1, stats_c  # typed counter moved
        assert s["hits"] == 0, stats_c
        assert s["compiles"] >= 1, stats_c  # degraded to a re-trace
    _teardown(broken)


# ---------------------------------------------------------------------------
# recorded-benchmark gate
# ---------------------------------------------------------------------------


def test_microbench_cold_gang_ttft_row():
    """Gate on the recorded cold/warm restart A/B (reads
    MICROBENCH.json — deterministic, no benchmarking in CI): the row
    must exist, the warm restart must have recorded cache hits, and
    warm TTFT must not regress past the cold path."""
    root = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    doc = json.load(open(os.path.join(root, "MICROBENCH.json")))
    rows = {r["name"]: r for r in doc["results"]}
    assert "cold_gang_ttft" in rows, "missing cold_gang_ttft row"
    row = rows["cold_gang_ttft"]
    assert row["warm_cache_hits_per_restart"] >= 1, row
    assert row["warm_ttft_ms"] > 0 and row["cold_ttft_ms"] > 0, row
    # the cache may not always buy a big win on a CPU rig, but a warm
    # restart re-tracing MORE than cold means the plane regressed
    assert row["warm_ttft_ms"] <= row["cold_ttft_ms"] * 1.25, row
