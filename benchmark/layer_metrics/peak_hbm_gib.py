"""Device: HBM the fullest chip has held at most (live buffers plus the
runtime's reservation for programs' temporaries, from ``memory_stats()``),
read in the worker after the window's last call."""


def read(host, trace):
    if not host["calls"]:
        return None
    peak = host["calls"][-1]["device"].get("memory_peak_bytes")
    return None if peak is None else peak / 2 ** 30
