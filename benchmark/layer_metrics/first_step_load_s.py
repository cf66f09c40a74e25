"""Compile caches: getting executables inside the run's first ``train()``
call (``call_log()[0]``, ``benchmark/start_log.py``) — Σ ``compile.load``
spans (an export-cache hit: deserialise, compile, first dispatch) +
``backend_s`` on ``jax.compile`` (jax's own timing of the XLA compile, or
of the load from its persistent cache); seconds."""

from benchmark import start_log


def read(host, trace):
    entry = start_log.first_call_entry(host)
    if entry is None:
        return None
    return (start_log.span_seconds(entry, "compile.load") or 0.0) \
        + start_log.attr_sum(entry, "jax.compile", "backend_s")
