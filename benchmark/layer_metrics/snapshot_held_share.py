"""Driver API: how much of the state the worker holds beside the step.
Over the window's calls, the median of ``held_bytes`` ÷ ``bytes`` on the
call's ``train.hold`` span (the copy the operator takes at an epoch's
end of the pieces that fit the room its devices have: pulled beside the
NEXT epoch, the rest at once), in percent; a call without a
``train.hold`` holds nothing and counts 0. A program whose span does not
say (the parent of the PR that added ``held_bytes``) gives None
(``benchmark/span_log.py``)."""

import statistics

from benchmark import span_log


def read(host, trace):
    entries = span_log.window_entries(host)
    if not entries:
        return None
    try:
        shares = []
        for entry in entries:
            held = [100.0 * span["attrs"]["held_bytes"]
                    / span["attrs"]["bytes"] for span in entry["spans"]
                    if span["name"] == "train.hold"]
            shares.append(held[-1] if held else 0.0)
    except (KeyError, ZeroDivisionError):
        return None
    return statistics.median(shares)
