"""Driver API: the wall seconds of a ``train()`` call's boundary in which
the WORKER's chain (device→host, put) was on the critical path — the sum
over the snapshot's pieces of ``train.snapshot.wait``'s self time (the
driver blocked in ``ray_tpu.get``, less the ``object.get`` beneath it);
median over the window's calls (``benchmark/boundary_path.py``). With
one piece it is d2h + put + one hop; with several, what of them the
driver's copy did not hide."""

from benchmark import boundary_path


def read(host, trace):
    return boundary_path.window_median(host, "wait_s")
