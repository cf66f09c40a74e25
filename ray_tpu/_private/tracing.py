"""Distributed tracing — causally-linked spans across every hop.

A compact trace context `(trace_id, span_id, parent_span_id, sampled)`
is minted at every entry point (driver `.remote()`, Serve HTTP ingress,
collective op, bulk object pull) and threaded through the existing
seams: task spec -> lease request -> raylet grant -> worker exec ->
reply, router -> replica, pull request -> chunk stream. Spans record
into the process's bounded ProfileBuffer (profiling.py) alongside plain
profile events, flush in batches to the GCS (profile table + trace
table), and export as Perfetto/chrome-trace JSON with cross-process
flow arrows (reference analog: the OpenTelemetry tracing hooks in
python/ray/util/tracing — here head-sampled and zero-dependency).

Head sampling: `RAY_TPU_TRACE_SAMPLE` (default 1%) at process start, or
live cluster-wide via `ray_tpu.set_trace_sampling(rate)` — the rate
rides the internal KV (KV_KEY) + pubsub (CHANNEL), exactly like the
failpoints arming plane. Propagated contexts are always honored: the
sampling decision is made once, at the trace root.

Call-level entry points (`Trainer.train()`: a handful of spans every few
seconds) mint a context whatever the rate (`always_trace`); what head
sampling decides for them is only the FINE level (`ctx.fine`: per-leaf
spans of a snapshot). A worker returns the spans it recorded under a
traced task in the reply (`collect_reply` / `adopt`), so the owner that
opened a tree for the trace (`open_tree`) holds the whole call when its
`get` returns, without waiting on the GCS flush. Every span is stamped
with `time.time()`: a tree is comparable within one host only. While a
jax profiler session runs in the process (`set_annotating`), `span()`
also enters a `jax.profiler.TraceAnnotation` of the same name, so the
host spans sit in the `.xplane.pb` beside the device's operations.

What a process did before any context reached it — a worker's spawn,
boot, wait for its chips and actor constructor — is kept as a few
PENDING rows (`pending`) and recorded as children of the first traced
task the process runs (`collect_reply`), so it rides home in that
task's reply: a worker group's start is one tree in the driver
(`ray_tpu.train.start_log()`).
"""

from __future__ import annotations

import contextlib
import contextvars
import os
import random
import threading

KV_KEY = "ray_tpu:trace_sample"
CHANNEL = "trace_config"

_DEFAULT_RATE = 0.01


def _env_rate() -> float:
    raw = os.environ.get("RAY_TPU_TRACE_SAMPLE", "")
    if not raw:
        return _DEFAULT_RATE
    try:
        return min(1.0, max(0.0, float(raw)))
    except ValueError:
        return _DEFAULT_RATE


_rate = _env_rate()
_rng = random.Random()
_lock = threading.Lock()
_buffer = None  # ProfileBuffer this process records spans into

# Ambient context: set around task execution / request handling so any
# nested entry point (a task submitted from inside a traced task, a
# collective op inside a traced replica call) joins the same tree.
_CTX: contextvars.ContextVar = contextvars.ContextVar(
    "ray_tpu_trace_ctx", default=None)


class TraceContext:
    """One node of a trace tree. Only sampled contexts exist — an
    unsampled entry point yields None everywhere, so the unsampled hot
    path carries no per-call state at all."""

    __slots__ = ("trace_id", "span_id", "parent_id", "fine", "relay")

    def __init__(self, trace_id: bytes, span_id: bytes,
                 parent_id: bytes | None = None, fine: bool = True,
                 relay: bool = False):
        self.trace_id = trace_id
        self.span_id = span_id
        self.parent_id = parent_id
        # head-sampled (or explicitly traced) tree: record the fine
        # level too. False only under an always_trace() root that the
        # sampler did not pick: a COARSE context, whose tree holds the
        # call-level spans only.
        self.fine = fine
        # coarse context minted in THIS process: its own submits carry
        # it (one hop). A coarse context that came over the wire is not
        # continued by per-operation entry points (maybe_trace), or an
        # epoch's every collective op and ingest fetch would be a span.
        self.relay = relay

    def __repr__(self):
        return (f"TraceContext({self.trace_id.hex()}, {self.span_id.hex()},"
                f" parent={self.parent_id.hex() if self.parent_id else None})")


def sample_rate() -> float:
    return _rate


def set_sample_rate(rate: float) -> None:
    global _rate
    _rate = min(1.0, max(0.0, float(rate)))


def apply_kv_value(value) -> None:
    """Apply a live override published through the GCS KV/pubsub (the
    value is the rate as a string, e.g. b"1.0")."""
    if value is None:
        return
    if isinstance(value, bytes):
        value = value.decode(errors="replace")
    try:
        set_sample_rate(float(value))
    except (TypeError, ValueError):
        pass


def bind_buffer(buffer) -> None:
    """Bind this process's ProfileBuffer (core worker / raylet call this
    at startup) so spans land in the same flush pipeline as profile
    events."""
    global _buffer
    _buffer = buffer


def _get_buffer():
    global _buffer
    if _buffer is None:
        with _lock:
            if _buffer is None:
                from ray_tpu._private import failpoints as _fp
                from ray_tpu._private.profiling import ProfileBuffer

                _buffer = ProfileBuffer(_fp.get_role() or "process")
    return _buffer


def new_context() -> TraceContext:
    """Fresh root context (unconditional — callers wanting head sampling
    use maybe_trace)."""
    return TraceContext(os.urandom(8), os.urandom(8))


def child(ctx: TraceContext) -> TraceContext:
    return TraceContext(ctx.trace_id, os.urandom(8), ctx.span_id, ctx.fine,
                        ctx.relay)


def child_of_current(per_op: bool = False) -> TraceContext | None:
    """A child of the ambient context, or None outside any trace.
    `per_op`: the caller runs once per operation, not once per call —
    None as well under a coarse context this process did not mint."""
    cur = _CTX.get()
    if cur is None or (per_op and not (cur.fine or cur.relay)):
        return None
    return child(cur)


def maybe_trace() -> TraceContext | None:
    """Entry-point mint: continue the ambient trace when one is active
    (nested submit, traced request handler; not a coarse context from
    another process), else head-sample a fresh root at the current
    rate. Returns None when not sampled."""
    ctx = child_of_current(per_op=True)
    if ctx is not None:
        return ctx
    if _rate <= 0.0 or _rng.random() >= _rate:
        return None
    return new_context()


def always_trace(fine: bool = False) -> TraceContext:
    """Entry-point mint for call-level spans that are recorded whatever
    the sampling rate: continue the ambient trace, else a fresh root
    whose FINE level is on when the caller asks for it or the head
    sampler picks the call."""
    cur = _CTX.get()
    if cur is not None:
        ctx = child(cur)
        ctx.fine = cur.fine or fine
    else:
        ctx = new_context()
        ctx.fine = fine or (_rate > 0.0 and _rng.random() < _rate)
    ctx.relay = True
    return ctx


# --- wire format -----------------------------------------------------------
# msgpack-plain [trace_id, span_id, parent_span_id, sampled]: span_id is
# the SENDER's span — the receiver records its spans as children of it.
# sampled: 1 = every level, 2 = call-level spans only (always_trace root
# the sampler did not pick).

def to_wire(ctx: TraceContext) -> list:
    return [ctx.trace_id, ctx.span_id, ctx.parent_id or b"",
            1 if ctx.fine else 2]


def from_wire(wire) -> TraceContext | None:
    if not wire:
        return None
    try:
        trace_id, span_id, parent, sampled = wire
    except (TypeError, ValueError):
        return None
    if not sampled:
        return None
    return TraceContext(bytes(trace_id), bytes(span_id),
                        bytes(parent) or None, fine=sampled == 1)


# --- ambient context -------------------------------------------------------

def current() -> TraceContext | None:
    return _CTX.get()


def current_id() -> str | None:
    """Hex trace id of the ambient context (the histogram-exemplar
    form), or None when the current call is unsampled."""
    ctx = _CTX.get()
    return ctx.trace_id.hex() if ctx is not None else None


def exemplar_of(ctx: TraceContext | None) -> str | None:
    """Hex trace id of `ctx` for Histogram.observe(exemplar=...)."""
    return ctx.trace_id.hex() if ctx is not None else None


def push(ctx: TraceContext | None):
    """Set the ambient context (even to None — execution scopes shadow
    any caller-thread leftovers); returns the reset token."""
    return _CTX.set(ctx)


def pop(token) -> None:
    try:
        _CTX.reset(token)
    except ValueError:
        pass  # token from another context (executor-pool reuse)


@contextlib.contextmanager
def use(ctx: TraceContext | None):
    token = _CTX.set(ctx)
    try:
        yield ctx
    finally:
        pop(token)


# --- span recording --------------------------------------------------------

# Executing side: the spans recorded under one traced task, handed back
# in its reply (core_worker._exec_scope). Bounded: what does not fit
# still goes to the ProfileBuffer, and is counted (`ReplySpans.dropped`,
# the owner's `spans_dropped` on `task.e2e`).
REPLY_SPANS_MAX = 256
_REPLY: contextvars.ContextVar = contextvars.ContextVar(
    "ray_tpu_trace_reply", default=None)
# Owning side: trees open in this process by hex trace id (open_tree).
_trees: dict[str, list] = {}
_annotating = False  # a jax profiler session runs in this process


# Rows of the time before this process had a context, until the first
# traced task claims them (`pending`, `collect_reply`). A handful a
# process; what does not fit is left out.
PENDING_MAX = 16
_pending: list = []


def pending(name: str, start: float, end: float,
            extra: dict | None = None) -> None:
    """Keep one span of this process's life before any trace context
    existed; the first traced task it runs records it as its child."""
    if len(_pending) < PENDING_MAX:
        _pending.append((name, start, end, extra))


class ReplySpans(list):
    """The rows one traced task hands back, and how many did not fit."""

    dropped = 0


@contextlib.contextmanager
def collect_reply(ctx: TraceContext | None):
    """Gather the spans recorded in this execution context (a traced
    task's) as `[name, start, end, fields]` rows; yields None untraced."""
    if ctx is None:
        yield None
        return
    rows = ReplySpans()
    token = _REPLY.set(rows)
    try:
        if _pending:    # the process's first traced task: see pending()
            with _lock:
                claimed = _pending[:]
                del _pending[:]
            for name, start, end, extra in claimed:
                record_span(name, start, end, child(ctx), extra)
        yield rows
    finally:
        try:
            _REPLY.reset(token)
        except ValueError:
            pass  # token from another context (executor-pool reuse)


@contextlib.contextmanager
def open_tree(ctx: TraceContext):
    """Keep every span of `ctx`'s trace that this process records or
    adopts from a reply, for the duration of the block."""
    rows = _trees[ctx.trace_id.hex()] = []
    try:
        yield rows
    finally:
        _trees.pop(ctx.trace_id.hex(), None)


def adopt(rows) -> None:
    """Hang a reply's spans on the tree open for their trace, if any
    (they are already in the executing process's ProfileBuffer)."""
    if not _trees or not rows:
        return
    for row in rows:
        tree = _trees.get(row[3].get("tid"))
        if tree is not None:
            tree.append(row)


def set_annotating(on: bool) -> None:
    """A jax profiler session started / stopped in this process."""
    global _annotating
    _annotating = bool(on)


def annotating() -> bool:
    return _annotating


def record_span(name: str, start: float, end: float,
                ctx: TraceContext | None, extra: dict | None = None):
    """Record one span into the bound ProfileBuffer. With ctx=None this
    degrades to a plain profile event (no trace linkage) — used by the
    unconditional task-execution event. Returns the span's row as the
    open trees and replies keep it (None without a context)."""
    fields = dict(extra) if extra else {}
    row = None
    if ctx is not None:
        fields["tid"] = ctx.trace_id.hex()
        fields["sid"] = ctx.span_id.hex()
        if ctx.parent_id:
            fields["psid"] = ctx.parent_id.hex()
        row = [name, start, end, fields]
        reply = _REPLY.get()
        if reply is not None:
            if len(reply) < REPLY_SPANS_MAX:
                reply.append(row)
            else:
                reply.dropped += 1
        tree = _trees.get(fields["tid"]) if _trees else None
        if tree is not None:
            tree.append(row)
    _get_buffer().record(name, start, end, fields)
    return row


def record_late(rows: list, name: str, start: float, end: float,
                ctx: TraceContext, extra: dict | None = None) -> None:
    """`record_span` for work that may outlive the tree that began it
    (another thread's): the row is kept in `rows`, an `open_tree`'s
    list, whether or not that tree is still open."""
    row = record_span(name, start, end, ctx, extra)
    if not any(kept is row for kept in rows):
        rows.append(row)


@contextlib.contextmanager
def span(name: str, ctx: TraceContext | None, extra: dict | None = None,
         ambient: bool = False, start: float | None = None):
    """Context manager recording `name` over the with-block when ctx is
    not None; `ambient=True` additionally makes ctx the current context
    inside the block (so nested entry points join the tree). `extra` is
    read when the block ends, so counts may be filled in inside it;
    `start` back-dates the span to work done just before the block."""
    import time

    if ctx is None and not _annotating:
        yield None
        return
    note = None
    if _annotating:
        import jax.profiler

        note = jax.profiler.TraceAnnotation(name)
        note.__enter__()
    token = _CTX.set(ctx) if ambient and ctx is not None else None
    if start is None:
        start = time.time()
    try:
        yield ctx
    finally:
        if note is not None:
            note.__exit__(None, None, None)
        if ctx is not None:
            record_span(name, start, time.time(), ctx, extra)
        if token is not None:
            pop(token)
