"""Control plane: ``train.start``'s start to ``worker.boot``'s end in the
run's worker start (``benchmark/start_log.py``) — the actor's
scheduling, the lease, the raylet's ``Popen``, the interpreter's start
with the import of ``ray_tpu``, the worker's registration; seconds."""

from benchmark import start_log


def read(host, trace):
    entry = start_log.start_entry(host)
    interval = entry and start_log.spawn_interval(entry)
    return interval[1] - interval[0] if interval else None
