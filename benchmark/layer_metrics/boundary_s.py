"""Driver API: what one ``train()`` call costs beyond the worker's
epoch — the median over the window's calls of the driver's wall time
minus the worker's epoch seconds. State pull through the object store,
the copy out of its arena, actor hops."""

import statistics


def read(host, trace):
    if not host["calls"]:
        return None
    return statistics.median(c["wall_s"] - c["epoch_s"]
                             for c in host["calls"])
