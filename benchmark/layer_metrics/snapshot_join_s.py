"""Operator: the seconds the worker spent writing shards into its staging
area — the join of leaves split over several chips — ``join_s`` on
``train.snapshot.d2h``, summed over the call's pieces; median over the
window's calls (``benchmark/boundary_path.py``). 0 where no leaf is
split over devices, so only the four-chip cell lists it."""

from benchmark import boundary_path


def read(host, trace):
    return boundary_path.window_median(host, "join_s")
