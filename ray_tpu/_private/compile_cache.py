"""Persistent AOT compile cache: jitted executables serialized across
process lifetimes (ROADMAP item 5, second half).

PR 13's compile observability showed where gang restarts and elastic
resizes stall: every new process re-traces the same jitted functions —
the `_DeviceOps` collective bodies, the paged-KV donated update, the
Trainer fused/grad/apply steps — for shape classes an identical process
compiled minutes earlier. This module closes the loop: the FIRST process
to compile a (seam, shape-class) pair exports the jitted function via
`jax.export` (StableHLO + calling convention, the only serialization
the runtime can rely on across jax minor versions) and stores the blob
in an on-disk session cache; every later process — a restarted gang
rank, an elastic-resize joiner, a fresh serve replica — deserializes
and skips the trace+compile entirely.

Key schema (sha256 over a JSON list, hex-truncated):

    [seam, *parts, runtime_fingerprint()]

* ``seam`` names the call site class ("collective", "serve.kv_update",
  "train.step") — the same names the compile spans carry.
* ``parts`` is the seam's own cache key: op kind, dtype, shape-class,
  axis name, world size — every compile-relevant input, nothing else.
* ``runtime_fingerprint()`` folds in jax/jaxlib/libtpu versions, the
  backend, the device kinds, and the process count: any of these
  changing invalidates EVERY entry (an executable compiled for another
  runtime must never load — fingerprint mismatch means a different
  key, which means a clean miss, never a wrong executable).

Failure semantics: the cache can only make things faster, never break
them. A load/deserialize failure counts `jax.compile_cache_errors_total`
and falls back to the normal trace+compile path; a store failure counts
the same and the op proceeds on the freshly-jitted function. The
`compile_cache.load` / `compile_cache.store` failpoints inject exactly
these faults in chaos tests. Writes are temp-file + os.replace so a
crashed writer leaves either a whole blob or a ``.ctmp-*`` stray (which
the test-suite leak check names), never a torn file.

The local JSON index (entry key -> seam/parts/size/created/hits) is
mirrored to the GCS KV under ``ray_tpu:compile_cache/index`` so the CLI
(`ray-tpu compile-cache`) and the doctor can see cache state without
touching the cache host's disk.
"""

from __future__ import annotations

import contextlib
import hashlib
import json
import os
import sys
import tempfile
import threading
import time

from ray_tpu._private import stats as _stats

# entries created before this moment predate the process: the doctor's
# compile_cache_cold finding keys off entries_preexisting, never off
# blobs this very process stored on its own first-ever misses (store()
# lives in this module, so any self-stored entry is created after this
# import ran)
_PROCESS_START = time.time()

M_HITS = _stats.Count(
    "jax.compile_cache_hits_total",
    "persistent compile-cache hits: a jitted executable deserialized "
    "from the on-disk AOT cache instead of re-tracing")
M_MISSES = _stats.Count(
    "jax.compile_cache_misses_total",
    "persistent compile-cache misses: no entry for the (seam, "
    "shape-class, runtime-fingerprint) key — the caller traced, "
    "compiled, and (best-effort) populated the cache")
M_ERRORS = _stats.Count(
    "jax.compile_cache_errors_total",
    "persistent compile-cache load/deserialize/store failures — every "
    "one degraded to a normal re-trace, never a user-visible error")
M_LOAD_S = _stats.Histogram(
    "jax.compile_cache_load_s", _stats.LATENCY_BOUNDARIES_S,
    "wall seconds to load + deserialize one cached executable (the "
    "re-trace time this hit avoided is jax.compile_s)")

# stray temp files carry this prefix so the conftest leak check can
# name them (a crashed writer is the only way one survives)
TMP_PREFIX = ".ctmp-"
INDEX_NAME = "index.json"
KV_INDEX_KEY = "ray_tpu:compile_cache/index"

_lock = threading.Lock()


def enabled() -> bool:
    """RAY_TPU_COMPILE_CACHE=0 turns the plane off (every call is a
    plain re-trace and nothing touches disk)."""
    return os.environ.get("RAY_TPU_COMPILE_CACHE", "1") not in (
        "0", "false", "no")


# <checkout>/.jax_cache: a FIXED path (the directory is part of JAX's
# cache key, so one that moves — a tempdir, a pid, a timestamp — never
# hits), inside the checkout, listed in .gitignore.
_DEFAULT_JAX_CACHE = os.path.join(
    os.path.dirname(os.path.dirname(os.path.dirname(
        os.path.abspath(__file__)))), ".jax_cache")


def jax_cache_dir() -> str:
    """Where JAX's persistent compilation cache lives: where
    JAX_COMPILATION_CACHE_DIR says when it is set, else the fixed
    default inside the checkout."""
    return os.environ.get("JAX_COMPILATION_CACHE_DIR") or _DEFAULT_JAX_CACHE


def enable_persistent_cache() -> str:
    """Place JAX's persistent compilation cache. Called once per
    process, before its first JAX use, by every process that compiles
    (worker start-up; bench/smoke children that jit on their own). With
    JAX_COMPILATION_CACHE_DIR set JAX reads the variable itself and no
    code sets another directory; the variable reaches spawned workers
    through the environment the raylet hands them. Unset, the default is
    put in this process's environment — JAX reads it when it is first
    imported, so a worker's start-up does not pay the import here."""
    if not os.environ.get("JAX_COMPILATION_CACHE_DIR"):
        os.environ["JAX_COMPILATION_CACHE_DIR"] = _DEFAULT_JAX_CACHE
        jax = sys.modules.get("jax")
        if jax is not None:  # already imported: the variable was read
            jax.config.update("jax_compilation_cache_dir",
                              _DEFAULT_JAX_CACHE)
    return jax_cache_dir()


def cache_dir() -> str:
    """The export cache's own directory: RAY_TPU_COMPILE_CACHE_DIR, else
    a fixed path beside JAX's default cache."""
    return (os.environ.get("RAY_TPU_COMPILE_CACHE_DIR")
            or os.path.join(_DEFAULT_JAX_CACHE, "export"))


def runtime_fingerprint() -> str:
    """Every runtime fact a serialized executable depends on. Computed
    lazily (jax may not be imported in pure-host processes) and cached
    per process — but ONLY once the backend facts resolved: a key built
    before jax initialization must not pin 'uninit'/'nojax' for the
    process's whole life, or differently-topologized processes collide
    on keys after their backends come up."""
    global _fingerprint
    if _fingerprint is not None:
        return _fingerprint
    parts = []
    complete = True
    try:
        import jax

        parts.append(jax.__version__)
        try:
            import jaxlib

            parts.append(getattr(jaxlib, "__version__", "?"))
        except Exception:
            parts.append("?")
        try:
            parts.append(jax.default_backend())
            parts.append(",".join(sorted(
                {d.device_kind for d in jax.devices()})))
            parts.append(str(jax.process_count()))
        except Exception:
            parts.append("uninit")
            complete = False
        try:  # TPU boxes: the libtpu build changes lowering
            import libtpu  # type: ignore

            parts.append(getattr(libtpu, "__version__", "?"))
        except Exception:
            pass
    except Exception:
        parts.append("nojax")
        complete = False
    fp = "|".join(parts)
    if complete:
        _fingerprint = fp
    return fp


_fingerprint: str | None = None


def make_key(seam: str, parts) -> str:
    blob = json.dumps([seam, list(map(str, parts)),
                       runtime_fingerprint()], sort_keys=True)
    return hashlib.sha256(blob.encode()).hexdigest()[:32]


# ---------------------------------------------------------------------------
# blob + index storage
# ---------------------------------------------------------------------------


def _blob_path(key: str) -> str:
    return os.path.join(cache_dir(), key + ".jaxexp")


def _index_path() -> str:
    return os.path.join(cache_dir(), INDEX_NAME)


def _read_index() -> dict:
    try:
        with open(_index_path(), "r", encoding="utf-8") as f:
            out = json.load(f)
        return out if isinstance(out, dict) else {}
    except Exception:
        return {}


def _write_index(index: dict) -> None:
    """Atomic local write, then best-effort GCS KV mirror (the CLI and
    doctor read the mirror; the cache itself only trusts the disk)."""
    d = cache_dir()
    os.makedirs(d, exist_ok=True)
    fd, tmp = tempfile.mkstemp(prefix=TMP_PREFIX, dir=d)
    try:
        with os.fdopen(fd, "w", encoding="utf-8") as f:
            json.dump(index, f)
        os.replace(tmp, _index_path())
    except Exception:
        try:
            os.unlink(tmp)
        except OSError:
            pass
        raise
    try:
        from ray_tpu.experimental import internal_kv

        internal_kv._kv_put(KV_INDEX_KEY,
                            json.dumps(index).encode())
    except Exception:
        pass  # no GCS (unit test / pure-local): disk is authoritative


@contextlib.contextmanager
def _index_lock():
    """Thread lock + OS file lock around the index read-modify-write:
    the cache dir is shared by every rank on the host (the normal
    multi-rank-per-host case), so an in-process lock alone loses index
    entries and hit counts to last-writer-wins races across processes.
    Degrades to thread-only locking where flock is unavailable."""
    with _lock:
        lockf = None
        try:
            import fcntl

            d = cache_dir()
            os.makedirs(d, exist_ok=True)
            lockf = open(os.path.join(d, INDEX_NAME + ".lock"), "a")
            fcntl.flock(lockf, fcntl.LOCK_EX)
        except Exception:
            if lockf is not None:
                lockf.close()
                lockf = None
        try:
            yield
        finally:
            if lockf is not None:
                try:
                    import fcntl

                    fcntl.flock(lockf, fcntl.LOCK_UN)
                except Exception:
                    pass
                lockf.close()


def _index_update(key: str, **fields) -> None:
    with _index_lock():
        index = _read_index()
        entry = index.setdefault(key, {"hits": 0})
        entry.update(fields)
        _write_index(index)


def read_index(prefer_kv: bool = False) -> dict:
    """The CLI entry point: the KV mirror when reachable (cluster-wide
    view), else the local disk index."""
    if prefer_kv:
        try:
            from ray_tpu.experimental import internal_kv

            raw = internal_kv._kv_get(KV_INDEX_KEY)
            if raw:
                out = json.loads(raw.decode())
                if isinstance(out, dict):
                    return out
        except Exception:
            pass
    return _read_index()


def lookup(key: str) -> bytes | None:
    """The serialized executable for `key`, or None (absent OR load
    failure — the caller re-traces either way; only the counter
    differs)."""
    if not enabled():
        return None
    from ray_tpu._private import failpoints as _fp

    path = _blob_path(key)
    try:
        if _fp.ARMED:
            _fp.fire_strict("compile_cache.load")
        with open(path, "rb") as f:
            return f.read()
    except FileNotFoundError:
        return None
    except Exception:
        M_ERRORS.inc()
        return None


def store(key: str, blob: bytes, seam: str = "", parts=()) -> bool:
    """Best-effort atomic store + index update. False (and an error
    count) on any failure — the caller's freshly-jitted function is
    already the fallback."""
    if not enabled():
        return False
    from ray_tpu._private import failpoints as _fp

    d = cache_dir()
    try:
        if _fp.ARMED:
            _fp.fire_strict("compile_cache.store")
        os.makedirs(d, exist_ok=True)
        fd, tmp = tempfile.mkstemp(prefix=TMP_PREFIX, dir=d)
        try:
            with os.fdopen(fd, "wb") as f:
                f.write(blob)
            os.replace(tmp, _blob_path(key))
        except Exception:
            try:
                os.unlink(tmp)
            except OSError:
                pass
            raise
        _index_update(key, seam=seam,
                      parts=[str(p) for p in parts],
                      size=len(blob), created=time.time())
        return True
    except Exception:
        M_ERRORS.inc()
        return False


def record_hit(key: str) -> None:
    try:
        with _index_lock():
            index = _read_index()
            if key in index:
                index[key]["hits"] = int(index[key].get("hits", 0)) + 1
                _write_index(index)
    except Exception:
        pass


def clear() -> int:
    """Remove every blob + the index (local and KV mirror); returns the
    number of entries removed. The CLI's --clear."""
    d = cache_dir()
    n = 0
    with _index_lock():
        try:
            for name in os.listdir(d):
                if name.endswith(".jaxexp") or name == INDEX_NAME \
                        or name.startswith(TMP_PREFIX):
                    if name.endswith(".jaxexp"):
                        n += 1
                    try:
                        os.unlink(os.path.join(d, name))
                    except OSError:
                        pass
        except FileNotFoundError:
            pass
        try:
            from ray_tpu.experimental import internal_kv

            internal_kv._kv_del(KV_INDEX_KEY)
        except Exception:
            pass
    return n


def state() -> dict:
    """Cache-plane summary for debug_state snapshots and the doctor's
    cold-restart finding. `entries_preexisting` counts only entries
    created BEFORE this process started — the index also holds blobs
    this very process stored on its own misses, and a first-ever cold
    process (misses>0, hits==0, entries>0) must not read as 'restart
    re-traced despite a warm cache'."""
    index = _read_index()
    preexisting = sum(
        1 for e in index.values()
        if isinstance(e, dict)
        and float(e.get("created") or 0.0) > 0.0
        and float(e["created"]) < _PROCESS_START)
    return {
        "enabled": enabled(),
        "dir": cache_dir(),
        "entries": len(index),
        "entries_preexisting": preexisting,
        "hits": int(M_HITS.snapshot()["value"]),
        "misses": int(M_MISSES.snapshot()["value"]),
        "errors": int(M_ERRORS.snapshot()["value"]),
    }


# ---------------------------------------------------------------------------
# what jax itself timed inside a resolution
# ---------------------------------------------------------------------------

# jax.monitoring's duration events -> the attribute that sums them on
# the `jax.compile` / `compile.load` span. `backend_s` is the XLA
# compile OR the load from jax's persistent cache; the retrieval event
# comes only with a persistent-cache hit.
_JAX_TIMED = {
    "/jax/core/compile/jaxpr_trace_duration": "trace_s",
    "/jax/core/compile/jaxpr_to_mlir_module_duration": "lower_s",
    "/jax/core/compile/backend_compile_duration": "backend_s",
    "/jax/compilation_cache/cache_retrieval_time_sec": "cache_retrieval_s",
}
_resolving = 0                  # resolutions open in this process
_timed = threading.local()      # .events: the open resolution's, by thread


def _on_jax_duration(event, duration, **_):
    """The ONE jax.monitoring listener, registered only while a
    resolution is open (`_jax_timings`): keeps the event as an interval
    that ends now. jax times a jit traced inside another's trace once
    more, inside the outer's interval: the outermost alone is kept."""
    attr = _JAX_TIMED.get(event)
    events = getattr(_timed, "events", None)
    if attr is None or events is None:
        return
    end = time.time()
    start = end - duration
    kept = [iv for iv in events.get(attr, ()) if iv[0] < start]
    events[attr] = kept + [(start, end)]


@contextlib.contextmanager
def _jax_timings():
    """Listen to jax's own compile timings for the duration of one
    resolution, in the resolving thread."""
    global _resolving
    import jax.monitoring

    with _lock:
        _resolving += 1
        if _resolving == 1:
            jax.monitoring.register_event_duration_secs_listener(
                _on_jax_duration)
    outer, _timed.events = getattr(_timed, "events", None), {}
    try:
        yield
    finally:
        _timed.events = outer
        with _lock:
            _resolving -= 1
            if not _resolving:
                jax.monitoring.unregister_event_duration_listener(
                    _on_jax_duration)


def _jax_timed(since: float) -> dict:
    """What jax timed in the open resolution from `since` on, as span
    attributes; an event jax did not emit leaves its attribute out."""
    out = {}
    for attr, intervals in (getattr(_timed, "events", None) or {}).items():
        inside = [b - a for a, b in intervals if a >= since - 1e-3]
        if inside:
            out[attr] = round(sum(inside), 4)
            if attr == "backend_s":
                out["programs"] = len(inside)
    if "backend_s" in out:
        out["persistent_hit"] = int("cache_retrieval_s" in out)
    return out


# ---------------------------------------------------------------------------
# the seam wrapper
# ---------------------------------------------------------------------------


class CachedFunction:
    """One jitted callable behind the persistent cache.

    Resolution happens on the FIRST call (the args fix the trace):

    * hit  — deserialize the stored `jax.export` blob, re-wrap with
      `jax.jit(exported.call, donate_argnums=...)` (donation is a
      call-site property the serialized module does not carry), count a
      hit + load seconds, and DO NOT record a compile — the whole point
      is that `jax.compiles_total` stays flat on a warm restart.
      Donating seams AOT-compile the deserialized module BEFORE the
      first dispatch: executing a donated jit consumes its input
      buffers, so a stale/incompatible blob must fail while re-trace
      is still possible, not after the inputs are gone.
    * miss — export + store FIRST (executing a donated jit consumes its
      input buffers; exporting only traces), then dispatch the normal
      jitted function and record the compile exactly as the seam did
      before this cache existed.

    Either way later calls go through one resolved function attribute —
    the wrapper adds a single `is None` check to the steady state.

    Inside a trace a resolution is spans, each with the seam's `key`:
    `compile.fingerprint` (`text_bytes`), `compile.lookup` (`hit`,
    `bytes`), `compile.load` (a hit, through its first dispatch),
    `compile.export` (`error` 1 where the export or the store raised)
    and `jax.compile` (the first dispatch of a miss); all but the lookup
    with what jax itself timed inside them (`_jax_timed`)."""

    def __init__(self, seam: str, parts, jitted, donate_argnums=(),
                 out_shardings=None, record_key: str | None = None,
                 fingerprint_computation: bool = False):
        self.seam = seam
        self.parts = tuple(parts)
        # call-site properties of `jitted` the serialized module does
        # not carry (it keeps the layout, not the sharding OBJECTS a
        # caller pinned): repeated on the jit around the module
        self.donate_argnums = tuple(donate_argnums)
        self.out_shardings = out_shardings
        self._jitted = jitted
        self._record_key = record_key or (
            seam + ":" + ":".join(map(str, parts)))
        # seams whose computation is USER code (Trainer steps: loss_fn,
        # optimizer) fold a jaxpr hash into the key — two models with
        # identical shapes must never share an executable. One extra
        # trace (no compile) per resolution; runtime-owned seams whose
        # key already pins the computation (op kind) skip it.
        self._fp_computation = fingerprint_computation
        self._fn = None
        self._lock = threading.Lock()
        self.resolved: str | None = None  # "hit" | "miss" | "disabled"

    def __call__(self, *args):
        fn = self._fn
        if fn is not None:
            return fn(*args)
        with self._lock:
            if self._fn is not None:
                return self._fn(*args)
            with _jax_timings():
                return self._resolve(args)

    def _span(self, name: str, counts: dict):
        """One part of the resolution as a child of the ambient trace
        (none outside a trace); `counts` is read when it ends."""
        from ray_tpu._private import tracing

        counts["key"] = self._record_key
        return tracing.span(name, tracing.child_of_current(), counts)

    def _resolve(self, args):
        if not enabled():
            self.resolved = "disabled"
            return self._first_dispatch(args)
        parts = self.parts
        if self._fp_computation:
            try:
                import jax

                traced = {}
                with self._span("compile.fingerprint", traced):
                    t0 = time.time()
                    text = str(jax.make_jaxpr(self._jitted)(*args))
                    parts = parts + (hashlib.sha256(
                        text.encode()).hexdigest()[:16],)
                    # the rest of the span: the jaxpr printed and hashed
                    traced.update(_jax_timed(t0), text_bytes=len(text))
            except Exception:
                # can't prove computation identity -> never share
                M_ERRORS.inc()
                self.resolved = "disabled"
                return self._first_dispatch(args)
        found = {"hit": 0, "bytes": 0}
        with self._span("compile.lookup", found):
            key = make_key(self.seam, parts)
            blob = lookup(key)
            if blob is not None:
                found.update(hit=1, bytes=len(blob))
        if blob is not None:
            loaded = {"ok": 0}
            with self._span("compile.load", loaded):
                t0 = time.time()
                try:
                    out = self._load(key, blob, args)
                finally:
                    loaded.update(_jax_timed(t0))
                if self._fn is not None:
                    loaded["ok"] = 1
                    return out
        M_MISSES.inc()
        self.resolved = "miss"
        fn = None
        exported = {"error": 0, "bytes": 0}
        with self._span("compile.export", exported):
            t0 = time.time()
            try:
                from jax import export as _export

                module = _export.export(self._jitted)(*args)
                blob = module.serialize()
                exported["bytes"] = len(blob)
                store(key, blob, seam=self.seam, parts=parts)
                # dispatch THROUGH the exported module, as a later
                # process will on a hit: both then hand XLA the same
                # program, so the restarted process's compile is a hit
                # in JAX's own persistent cache too. Dispatching the
                # original jit here made every warm restart pay one full
                # XLA compile (the exported wrapper is a different
                # program to XLA's cache).
                fn = self._jit_exported(module)
            except Exception:
                M_ERRORS.inc()
                exported["error"] = 1
            exported.update(_jax_timed(t0))
        return self._first_dispatch(args, fn)

    def _load(self, key: str, blob: bytes, args):
        """A hit: the stored module deserialised, compiled and
        dispatched once. Sets `_fn` and returns the outputs, or leaves
        `_fn` None (a blob that will not load: the caller re-traces)."""
        t0 = time.time()
        try:
            from jax import export as _export

            exported = _export.deserialize(bytearray(blob))
            fn = self._jit_exported(exported)
            if self.donate_argnums:
                # dispatching a donated jit consumes the input
                # buffers — AOT-compile the deserialized module
                # first so a stale/corrupt/incompatible blob fails
                # HERE, with the inputs intact and the re-trace
                # fallback still possible
                fn = fn.lower(*args).compile()
        except Exception:
            # a stale/corrupt/incompatible blob: typed error count,
            # then the normal trace path — never user-visible
            M_ERRORS.inc()
            return None
        try:
            out = fn(*args)
        except Exception:
            M_ERRORS.inc()
            if self.donate_argnums:
                # the executable compiled but failed at RUN time with
                # the inputs already donated; the fallback would
                # dispatch on deleted buffers — surface the real
                # execution error instead
                raise
            return None
        self._fn = fn
        self.resolved = "hit"
        M_HITS.inc()
        M_LOAD_S.observe(time.time() - t0)
        record_hit(key)
        return out

    def _jit_exported(self, exported):
        import jax

        return jax.jit(exported.call, donate_argnums=self.donate_argnums,
                       out_shardings=self.out_shardings)

    def compiled_text(self, *args) -> str:
        """Optimised HLO of the program this seam dispatches for `args`
        (lowering consumes no donated buffer). What a chip run reads to
        check that a kernel (`tpu_custom_call`) or a collective is
        really in the step, not its XLA fallback."""
        fn = self._fn if self._fn is not None else self._jitted
        if hasattr(fn, "lower"):  # a jit; a hit on a donating seam
            fn = fn.lower(*args).compile()  # already holds a Compiled
        return fn.as_text()

    def _first_dispatch(self, args, fn=None):
        from ray_tpu._private import profiling as _profiling

        fn = fn if fn is not None else self._jitted
        t0 = time.time()
        out = fn(*args)
        _profiling.record_compile(self._record_key, t0, time.time(),
                                  _jax_timed(t0))
        self._fn = fn
        return out
