"""What a roofline share needs beyond ``read(host, trace)``'s arguments:
the cell this run measures (operations and bytes are computed from the
model's widths) and what the program's own spans say of the TRACED call
(the rows it really multiplied, the steps it really ran). Anything
missing gives None and the reader leaves its metric out."""

from __future__ import annotations

import sys

from benchmark import manifest


def this_cell() -> dict | None:
    """The run's command carries ``--workload <cell>`` (``run.py``,
    ``tools/run_with_log.py``)."""
    argv = sys.argv
    try:
        name = argv[argv.index("--workload") + 1]
        return manifest.cell(name)
    except (ValueError, IndexError, manifest.ManifestError):
        return None


def traced_call_attrs(span_name: str) -> dict | None:
    """The attributes of the span `span_name` of the traced call: the
    run's last ``train()`` call, so the last entry of
    ``ray_tpu.train.call_log()``. None for a program without the log."""
    try:
        from ray_tpu.train import call_log
    except ImportError:
        return None
    log = call_log()
    for span in (log[-1]["spans"] if log else []):
        if span["name"] == span_name:
            return span["attrs"]
    return None
