"""Where JAX's persistent compilation cache lives.

One cache keeps executables across processes: JAX's own. A restarted
worker, a resized gang's joiner or a fresh serve replica traces and
lowers its programs again (seconds of Python) and finds the executables
there, keyed by JAX on the program itself. This module only places that
cache; the first dispatch of each program is recorded by
`_private/profiling.py::CompileProbe`.
"""

from __future__ import annotations

import os
import sys

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
