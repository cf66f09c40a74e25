"""Device: the share of the traced steps' span (first operation's start
to the last one's end) in which no operation ran on the device."""


def read(host, trace):
    if not trace or not trace["span_s"]:
        return None
    return 100.0 * (1.0 - trace["busy_s"] / trace["span_s"])
