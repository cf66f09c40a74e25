"""Kernels: device time in the flash-attention forward kernel — the
Mosaic operations whose ``XLA Ops`` event name carries the kernel's
``name=`` (``flash_fwd.N``: the forward pass's and its rematerialised
copy) — over device busy time, in the traced steps. A program whose
kernels carry no name has no such operation: nothing to read."""


def share(trace, kernel: str):
    """Own time of the Mosaic operations named after `kernel`, as a
    percentage of busy time; None where the trace names none."""
    if not trace or not trace["busy_s"]:
        return None
    ops = [k for k in trace["mosaic_ops"] if kernel in k]
    if not ops:
        return None
    return 100.0 * sum(trace["op_self_s"][k] for k in ops) / trace["busy_s"]


def read(host, trace):
    return share(trace, "flash_fwd")
