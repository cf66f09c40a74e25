"""Experts: how much of the grouped matmul's static rows the expert
blocks ran over — ``moe_rows_walked`` over ``moe_rows_static`` on a
call's ``train.sync`` span (summed over the routing layers, the MTP
block's included, and the call's steps), median over the window's calls,
in percent. A layer and step the block walks the smallest rung of
``parallel/moe.py::row_ladder`` (1/8, 2/8, 3/8, 4/8 of the worst case's
tiles, or all of them) that holds the tiles its routing filled, so this
reads 12.5, 25, 37.5, 50 or 100 where every layer takes one rung and a
value between where they part; ``expert_rows_filled_share`` beside it
says how much of what was walked held an assignment. A program whose
spans carry no such counter (one that walks the worst case always)
gives None."""

import statistics

from benchmark import span_log


def read(host, trace):
    entries = span_log.window_entries(host)
    if not entries:
        return None
    shares = [100.0 * span["attrs"]["moe_rows_walked"]
              / span["attrs"]["moe_rows_static"]
              for entry in entries for span in entry["spans"]
              if span["name"] == "train.sync"
              and span["attrs"].get("moe_rows_static")
              and "moe_rows_walked" in span["attrs"]]
    return statistics.median(shares) if shares else None
