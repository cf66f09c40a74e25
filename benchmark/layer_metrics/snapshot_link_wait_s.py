"""Operator: the seconds the worker spent blocked in ``np.asarray`` on a
device array — waiting for the host link — while it brought a call's
snapshot to the host: ``wait_s`` on ``train.snapshot.d2h``, summed over
the call's pieces; median over the window's calls
(``benchmark/boundary_path.py``)."""

from benchmark import boundary_path


def read(host, trace):
    return boundary_path.window_median(host, "link_wait_s")
