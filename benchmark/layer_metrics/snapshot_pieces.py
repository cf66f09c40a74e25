"""Object plane: the pieces in which a call's snapshot crossed the
object store — ``pieces`` on the call's ``train.snapshot`` span; 1 when
the state fits the arena; median over the window's calls
(``benchmark/span_log.py``). A program whose span carries no such count
gives None."""

import statistics

from benchmark import span_log


def read(host, trace):
    entries = span_log.window_entries(host)
    if not entries:
        return None
    pieces = [span["attrs"]["pieces"] for entry in entries
              for span in entry["spans"]
              if span["name"] == "train.snapshot"
              and "pieces" in span["attrs"]]
    return statistics.median(pieces) if pieces else None
