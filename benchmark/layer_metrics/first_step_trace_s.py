"""Compile caches: Python tracing and lowering inside the run's first
``train()`` call (``call_log()[0]``, ``benchmark/start_log.py``), of
steps that are cached already on a warm run — Σ ``compile.fingerprint``
+ ``compile.export`` spans + ``trace_s`` + ``lower_s`` on ``jax.compile``
(jax's own timing of the plain jit's trace and lowering); seconds."""

from benchmark import start_log


def read(host, trace):
    entry = start_log.first_call_entry(host)
    if entry is None:
        return None
    return (start_log.span_seconds(entry, "compile.fingerprint",
                                   "compile.export") or 0.0) \
        + start_log.attr_sum(entry, "jax.compile", "trace_s", "lower_s")
