"""Task/event profiling -> cluster timeline (reference:
src/ray/core_worker/profiling.h:28 ProfileEvent batches pushed to the GCS
profile table; python/ray/state.py:946 timeline() chrome-trace export).

Workers record spans into a bounded local buffer; the core worker flushes
batches to the GCS, and `ray_tpu.timeline()` renders everything as a
chrome://tracing / Perfetto JSON document. Events carrying trace ids
(tracing.py `tid`/`sid`/`psid` extra fields) additionally land in the
GCS trace table and export with cross-process flow arrows."""

from __future__ import annotations

import collections
import contextlib
import os
import threading
import time

from ray_tpu._private import stats as _stats

# Flush failures (GCS unreachable) requeue drained events locally; only
# events evicted by the deque bound are actually lost — and counted here
# instead of disappearing invisibly.
M_EVENTS_DROPPED = _stats.Count(
    "profiling.events_dropped_total",
    "profile/trace events dropped by the local buffer bound")

# jit-compile observability (drift-gated): every recompile the runtime
# can see — _DeviceOps cache fills, the paged-KV jax update, Trainer
# step shape changes — counts here and lands as a `jax.compile` span,
# so a recompile storm reads as a flamegraph band + a rising
# jax.compiles_total rate + a doctor finding instead of a mystery stall.
M_COMPILES = _stats.Count(
    "jax.compiles_total",
    "jit compile events observed at the runtime's compile seams "
    "(_DeviceOps cache fill, KV-cache jax update, Trainer step)")
M_COMPILE_S = _stats.Histogram(
    "jax.compile_s", _stats.COMPILE_BOUNDARIES_S,
    "wall seconds per observed jit compile (first dispatch of a new "
    "shape class — compile + first execution)")

# recent-compile window for debug_state / the stall doctor's
# compile-storm finding (bounded ring; pruned on read)
COMPILE_RECENT_WINDOW_S = 60.0
_compile_recent: collections.deque = collections.deque(maxlen=256)
_compile_lock = threading.Lock()


def record_compile(key: str, start: float, end: float,
                   timed: dict | None = None) -> None:
    """Record one observed jit compile: metrics + a `jax.compile` span
    (joining the ambient trace when one is active) + the recent window
    the doctor reads. `timed`: what jax itself timed inside the interval
    (`_jax_timed`), added to the span's attributes."""
    from ray_tpu._private import tracing

    seconds = max(0.0, end - start)
    M_COMPILES.inc()
    M_COMPILE_S.observe(seconds)
    with _compile_lock:
        _compile_recent.append((end, seconds, key))
    tracing.record_span("jax.compile", start, end,
                        tracing.child_of_current(),
                        {"name": f"jax.compile {key}", "key": key,
                         **(timed or {})})


def compile_state() -> dict:
    """Compile activity summary for debug_state snapshots: total count
    plus the last-60s window (count, wall seconds, last key) — the
    stall doctor's compile-storm signal."""
    now = time.time()
    with _compile_lock:
        recent = [(ts, s, k) for ts, s, k in _compile_recent
                  if now - ts <= COMPILE_RECENT_WINDOW_S]
        last = _compile_recent[-1] if _compile_recent else None
    return {
        "total": int(M_COMPILES.snapshot()["value"]),
        "recent_60s": len(recent),
        "recent_s": round(sum(s for _, s, _ in recent), 4),
        "last_key": last[2] if last else "",
        "last_age_s": round(now - last[0], 3) if last else None,
    }


# jax.monitoring's duration events -> the attribute that sums them on
# the `jax.compile` span. `backend_s` is the XLA compile OR the load
# from jax's persistent cache; the retrieval event comes only with a
# persistent-cache hit.
_JAX_TIMED = {
    "/jax/core/compile/jaxpr_trace_duration": "trace_s",
    "/jax/core/compile/jaxpr_to_mlir_module_duration": "lower_s",
    "/jax/core/compile/backend_compile_duration": "backend_s",
    "/jax/compilation_cache/cache_retrieval_time_sec": "cache_retrieval_s",
}
_first_dispatches = 0           # open in this process
_timed = threading.local()      # .events: the open first dispatch's


def _on_jax_duration(event, duration, **_):
    """The ONE jax.monitoring listener, registered only while a first
    dispatch is open (`_jax_timings`): keeps the event as an interval
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
    first dispatch, in the dispatching thread."""
    global _first_dispatches
    import jax.monitoring

    with _compile_lock:
        _first_dispatches += 1
        if _first_dispatches == 1:
            jax.monitoring.register_event_duration_secs_listener(
                _on_jax_duration)
    outer, _timed.events = getattr(_timed, "events", None), {}
    try:
        yield
    finally:
        _timed.events = outer
        with _compile_lock:
            _first_dispatches -= 1
            if not _first_dispatches:
                jax.monitoring.unregister_event_duration_listener(
                    _on_jax_duration)


def _jax_timed(since: float) -> dict:
    """What jax timed in the open first dispatch from `since` on, as
    span attributes; an event jax did not emit leaves its attribute
    out."""
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


class CompileProbe:
    """One jitted callable whose FIRST dispatch is timed and recorded.

    The runtime's compile seams — the Trainer's steps and its eval, the
    `_DeviceOps` collective bodies, the paged-KV donated update — keep
    one probe a (program, shape class): jit compiles exactly when the
    shape class is new, so the first dispatch of a probe carries the
    trace, the lowering and the executable (compiled, or loaded from
    JAX's persistent cache: `compile_cache.py`). It is recorded through
    `record_compile` under `key`, with what jax itself timed inside it
    (`trace_s`, `lower_s`, `backend_s`, `cache_retrieval_s`,
    `persistent_hit`, `programs`); the measured interval includes the
    first execution. A first dispatch that raises (a transient OOM, an
    interrupt) proved no compile: nothing is recorded and the retry is
    timed. Later calls cost one attribute check before the jit's own
    call."""

    def __init__(self, key: str, jitted, donate_argnums=()):
        self.key = key
        # what `jitted` donates, for the callers and tests that ask
        self.donate_argnums = tuple(donate_argnums)
        self._jitted = jitted
        self._fn = None     # `jitted`, once a dispatch of it has returned
        self._lock = threading.Lock()

    def __call__(self, *args):
        fn = self._fn
        if fn is not None:
            return fn(*args)
        with self._lock:
            if self._fn is None:
                with _jax_timings():
                    t0 = time.time()
                    out = self._jitted(*args)
                    record_compile(self.key, t0, time.time(),
                                   _jax_timed(t0))
                self._fn = self._jitted
                return out
        return self._jitted(*args)

    def compiled_text(self, *args) -> str:
        """Optimised HLO of the program dispatched for `args` (lowering
        consumes no donated buffer). What a chip run reads to check that
        a kernel (`tpu_custom_call`) or a collective is really in the
        step, not its XLA fallback."""
        return self._jitted.lower(*args).compile().as_text()


def shape_class(batch) -> str:
    """Stable shape-class key for a (possibly nested) batch of arrays —
    the thing whose change forces a jit recompile."""
    shapes: list[str] = []

    def walk(x):
        shape = getattr(x, "shape", None)
        if shape is not None:
            shapes.append("x".join(map(str, shape)) or "scalar")
        elif isinstance(x, dict):
            for k in sorted(x):
                walk(x[k])
        elif isinstance(x, (list, tuple)):
            for v in x:
                walk(v)

    walk(batch)
    return ",".join(shapes) or "none"


class ProfileBuffer:
    def __init__(self, component_type: str, maxlen: int = 20_000):
        self.component_type = component_type
        self.component_id = os.getpid()
        self._events: collections.deque = collections.deque(maxlen=maxlen)
        self._lock = threading.Lock()

    def record(self, event_type: str, start: float, end: float,
               extra: dict | None = None):
        with self._lock:
            if len(self._events) == self._events.maxlen:
                M_EVENTS_DROPPED.inc()
            self._events.append({
                "event_type": event_type,
                "start_time": start,
                "end_time": end,
                "extra_data": extra or {},
            })

    def drain(self) -> list[dict]:
        with self._lock:
            out = list(self._events)
            self._events.clear()
        return out

    def requeue(self, events: list[dict]) -> int:
        """Put drained-but-unflushed events back at the FRONT (a failed
        GCS flush retries them on the next cycle). Keeps the newest
        events when they no longer all fit; returns how many were
        dropped (also counted in profiling.events_dropped_total)."""
        if not events:
            return 0
        with self._lock:
            space = self._events.maxlen - len(self._events)
            dropped = max(0, len(events) - space)
            if dropped:
                M_EVENTS_DROPPED.inc(dropped)
                events = events[dropped:]
            self._events.extendleft(reversed(events))
        return dropped

    def __len__(self):
        with self._lock:
            return len(self._events)

    def profile(self, event_type: str, extra: dict | None = None):
        return _Span(self, event_type, extra)


class _Span:
    def __init__(self, buf: ProfileBuffer, event_type: str, extra):
        self._buf = buf
        self._event_type = event_type
        self._extra = extra

    def __enter__(self):
        self._start = time.time()
        return self

    def __exit__(self, *exc):
        self._buf.record(self._event_type, self._start, time.time(),
                         self._extra)
        return False


def to_chrome_trace(events: list[dict], flow: bool = True) -> list[dict]:
    """GCS profile-table rows -> chrome-trace 'X' (complete) events
    (reference: state.py:946 timeline). Span events (tracing.py: extra
    `sid`/`psid`) additionally get flow arrows ('s'/'f' pairs keyed by
    the child span id) so Perfetto draws the cross-process tree."""
    trace = []
    by_sid: dict[str, dict] = {}
    for batch in events:
        pid = f"{batch['component_type']} {batch.get('node_id', b'').hex()[:8] if isinstance(batch.get('node_id'), bytes) else ''}".strip()
        for ev in batch["events"]:
            extra = ev.get("extra_data", {})
            tev = {
                "cat": ev["event_type"],
                "name": extra.get("name", ev["event_type"]),
                "ph": "X",
                "ts": ev["start_time"] * 1e6,
                "dur": (ev["end_time"] - ev["start_time"]) * 1e6,
                "pid": pid,
                "tid": batch["component_id"],
                "args": extra,
            }
            trace.append(tev)
            sid = extra.get("sid")
            if sid:
                by_sid[sid] = tev
    if flow:
        links = []
        for tev in trace:
            sid = tev["args"].get("sid")
            parent = by_sid.get(tev["args"].get("psid", ""))
            if not sid or parent is None or parent is tev:
                continue
            # anchor the flow start inside the parent slice (chrome
            # binds flow events to the enclosing slice by timestamp)
            start_ts = min(max(tev["ts"], parent["ts"]),
                           parent["ts"] + parent["dur"])
            links.append({"ph": "s", "cat": "trace", "name": "span",
                          "id": sid, "pid": parent["pid"],
                          "tid": parent["tid"], "ts": start_ts})
            links.append({"ph": "f", "bp": "e", "cat": "trace",
                          "name": "span", "id": sid, "pid": tev["pid"],
                          "tid": tev["tid"], "ts": tev["ts"]})
        trace.extend(links)
    return trace


def spans_to_chrome_trace(rows: list[dict], flow: bool = True) -> list[dict]:
    """Flat GCS trace-TABLE rows (get_trace_spans) -> chrome-trace JSON:
    regroups rows into per-process pseudo-batches and reuses
    to_chrome_trace, so `ray-tpu trace` / `/api/trace` render one
    trace's cross-process tree with the same flow arrows as the full
    timeline."""
    batches: dict[tuple, dict] = {}
    for r in rows:
        nid = r.get("node_id")
        key = (r["component_type"], r["component_id"],
               nid if isinstance(nid, bytes) else b"")
        b = batches.get(key)
        if b is None:
            b = batches[key] = {"component_type": r["component_type"],
                                "component_id": r["component_id"],
                                "node_id": nid, "events": []}
        b["events"].append({"event_type": r["event_type"],
                            "start_time": r["start_time"],
                            "end_time": r["end_time"],
                            "extra_data": r.get("extra_data", {})})
    return to_chrome_trace(list(batches.values()), flow=flow)
