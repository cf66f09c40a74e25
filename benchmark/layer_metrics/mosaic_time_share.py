"""Kernels: device time in Mosaic kernels (``tpu_custom_call``
operations, all of them together: the program's kernels carry no name
yet) over device busy time, in the traced steps."""


def read(host, trace):
    if not trace or not trace["busy_s"]:
        return None
    return 100.0 * trace["mosaic_s"] / trace["busy_s"]
