"""Object plane: the first state pull of the run — ``train.snapshot`` of
the first ``train()`` call, into buffers and pages nobody has written;
the part of ``first_step_s`` that is no compile or load
(``benchmark/boundary_path.py``)."""

from benchmark import boundary_path


def read(host, trace):
    return boundary_path.first_pull_s(host)
