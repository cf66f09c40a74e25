"""Object plane: the worker waiting to be asked for the next piece — on
its lane, between the first ``state_piece`` task's start and the last
``object.return_put``'s end, the seconds in which neither a
``state_piece`` task nor a put ran (the arena was full, or the driver
late); median over the window's calls (``benchmark/boundary_path.py``).
Listed by the cells whose state crosses in several pieces."""

from benchmark import boundary_path


def read(host, trace):
    return boundary_path.window_median(host, "starved_s")
