"""Compile caches: programs the worker compiled, or loaded from JAX's
cache, inside the window's ``train()`` calls (``jax.monitoring``
``backend_compile_duration`` events). Warm shapes build none."""


def read(host, trace):
    if not host["calls"]:
        return None
    return sum(c["programs_built"] for c in host["calls"])
