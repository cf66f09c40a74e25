"""Kernels: device time in the indexer's own KERNELS — the Mosaic
operations whose ``XLA Ops`` event name carries a ``name=`` of
``ops/sparse_index.py``: ``index_scores.N`` (a strip's scores, each
layer's forward pass and its rematerialised copy) and ``index_kl.N``
(the indexer's loss and its gradient) — own time over device busy time,
in the traced steps. It is NOT the indexer's whole share: what XLA runs
of the mechanism (the indexer's projections, the threshold's radix
passes and the plane's write, strip by strip under ``lax.map``) reaches
the trace's reduction as ``fusion.N`` with no scope, and no reader can
tell it from the rest of the step; PERF.md section 5 gives it by
``tools/scope_share.py`` on a kept trace against the compiled text, and
section 7 says which files of the harness would have to keep the
step's compiled text for a reader to do the same. So a part of the
mechanism that moves from XLA into one of these kernels RAISES this
reading while the step shortens: read it beside ``samples_per_s``. A
program whose trace names neither kernel gives None."""

from benchmark.layer_metrics import expert_matmul_time_share as time_share

KERNELS = ("index_scores", "index_kl")


def read(host, trace):
    own = [s for s in (time_share.seconds(trace, k) for k in KERNELS)
           if s is not None]
    if not own:
        return None
    return 100.0 * sum(own) / trace["busy_s"]
