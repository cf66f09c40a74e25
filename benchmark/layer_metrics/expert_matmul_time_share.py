"""Experts: device time in the grouped expert matmuls — the Mosaic
operations whose ``XLA Ops`` event name carries the kernels' ``name=``
(``moe_gmm.N``: the forward products and their rematerialised copies;
``moe_gmm_dx.N``, ``moe_gmm_dw.N``: the two backward products) — over
device busy time, in the traced steps. A program without the kernels
names no such operation: nothing to read."""

from benchmark.layer_metrics import flash_fwd_time_share

KERNEL = "moe_gmm"


def seconds(trace, kernel: str):
    """Own time of the Mosaic operations named after `kernel` in the
    traced call, in seconds; None where the trace names none."""
    if not trace or not trace["busy_s"]:
        return None
    ops = [k for k in trace["mosaic_ops"] if kernel in k]
    if not ops:
        return None
    return sum(trace["op_self_s"][k] for k in ops)


def read(host, trace):
    return flash_fwd_time_share.share(trace, KERNEL)
