"""Kernels: device time in the indexer's LOSS kernel alone — the Mosaic
operations whose ``XLA Ops`` event name carries ``index_kl``
(``index_kl.N``: the indexer's loss and its gradient, one pass of a
block) — own time over device busy time, in the traced steps. The pass
makes value and gradient at once, so a step needs it once a layer; a
rematerialised block that keeps nothing of it runs it twice (two
``index_kl.N`` operations, the forward's and the recomputed one's), one
that keeps what the pass made runs it once, and this reading halves
while ``index_time_share``, which adds ``index_scores``, falls by as
much. A program whose trace names no such kernel gives None."""

from benchmark.layer_metrics import flash_fwd_time_share

KERNEL = "index_kl"


def read(host, trace):
    return flash_fwd_time_share.share(trace, KERNEL)
