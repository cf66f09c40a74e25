"""Control plane: the span ``worker.chip_wait`` of the run's worker start
(``benchmark/start_log.py``) — the chip-owning worker's wait, before its
first user code, for device nodes another process still holds; a probe
of milliseconds on a free chip; seconds."""

from benchmark import start_log


def read(host, trace):
    return start_log.span_seconds(start_log.start_entry(host),
                                  "worker.chip_wait")
