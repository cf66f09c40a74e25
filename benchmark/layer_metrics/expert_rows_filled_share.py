"""Experts: how much of the grouped matmul's static rows held an
assignment — ``moe_rows_filled`` over ``moe_rows_static`` on a call's
``train.sync`` span (summed over the routing layers, the MTP block's
included, and the call's steps), median over the window's calls, in
percent. The static row count is the worst case, every assignment held
here; with a sixteenth of the experts held about a sixteenth is filled,
and the rest is memory the step holds for nothing. A program whose
spans carry no such counters gives None."""

import statistics

from benchmark import span_log


def read(host, trace):
    entries = span_log.window_entries(host)
    if not entries:
        return None
    shares = [100.0 * span["attrs"]["moe_rows_filled"]
              / span["attrs"]["moe_rows_static"]
              for entry in entries for span in entry["spans"]
              if span["name"] == "train.sync"
              and span["attrs"].get("moe_rows_static")]
    return statistics.median(shares) if shares else None
