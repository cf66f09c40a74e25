"""Device: the share of the device's idle gaps between the window's
consecutive calls (``train.sync`` end to the next ``train.dispatch``
start) that lies inside a named leaf span (``train.snapshot.d2h``,
``object.return_put``, ``object.get``, ``train.snapshot.copy``)."""

from benchmark import span_log


def read(host, trace):
    return span_log.idle_gap_named_share(host)
