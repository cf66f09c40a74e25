"""Driver API: what the copy out of the arena costs when it goes into a
buffer set for the SECOND time (a Trainer's calls 3 and 4, the window's
first two) — a call's ``train.snapshot.copy`` seconds, median over the
window's calls whose copies carry ``dest_writes`` = 1; None if the
window holds none (``benchmark/boundary_path.py``). ``snapshot_copy_s``
mixes these calls with the steady ones."""

from benchmark import boundary_path


def read(host, trace):
    return boundary_path.window_median(host, "copy_s", dest_writes=1)
