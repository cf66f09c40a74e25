"""Driver API: how much of the state pull ran beside an epoch. Over the
window's calls whose ``train.snapshot`` is a DEFERRED pull (``deferred``
1: the state the worker held of the call before, pulled while this
call's epoch runs), the share of those spans' seconds that lay inside
the same call's epoch on the worker (``train.dispatch`` start to
``train.sync`` end), in percent; 0 where the window deferred no pull. A
program whose ``train.snapshot`` does not say (the parent of the PR that
added it) gives None (``benchmark/span_log.py``)."""

from benchmark import span_log


def read(host, trace):
    entries = span_log.window_entries(host)
    if not entries:
        return None
    try:
        hidden = total = 0.0
        for entry in entries:
            lo, hi = span_log.epoch_interval(entry)
            for span in entry["spans"]:
                if span["name"] != "train.snapshot":
                    continue
                if span["attrs"]["deferred"]:
                    total += span["end"] - span["start"]
                    hidden += span_log.covered(
                        [(span["start"], span["end"])], lo, hi)
    except (KeyError, ValueError):
        return None
    return 100.0 * hidden / total if total > 0 else 0.0
