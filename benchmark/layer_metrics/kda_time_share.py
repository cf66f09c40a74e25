"""Kernels: device time in the per-channel delta rule of the KDA mixers
— the Mosaic operations whose ``XLA Ops`` event name carries the
kernels' ``name=`` (``kda_fwd.N``: each KDA layer's forward pass and its
rematerialised copy, which also writes the chunks' entering states;
``kda_bwd.N``: its backward, walked against time) — own time over device
busy time, in the traced steps. The mixer's projections, convolution, L2
norms, gate and norm are XLA's and not in it. A program whose trace
names neither gives None."""

from benchmark.layer_metrics import expert_matmul_time_share as time_share

KERNELS = ("kda_fwd", "kda_bwd")


def read(host, trace):
    own = [time_share.seconds(trace, k) for k in KERNELS]
    if None in own:
        return None
    return 100.0 * sum(own) / trace["busy_s"]
