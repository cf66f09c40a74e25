"""Experts: how uneven the routing is over the held experts — the most
tokens one held expert got in one layer of one step over the mean
(``moe_expert_tokens_max`` / ``moe_expert_tokens_mean`` on a call's
``train.sync`` span), median over the window's calls
(``benchmark/span_log.py``). 1 is even; the grouped matmul's last tile
of every expert and the slowest chip of a deployment follow it. A
program whose spans carry no such counters gives None."""

import statistics

from benchmark import span_log


def read(host, trace):
    entries = span_log.window_entries(host)
    if not entries:
        return None
    ratios = [span["attrs"]["moe_expert_tokens_max"]
              / span["attrs"]["moe_expert_tokens_mean"]
              for entry in entries for span in entry["spans"]
              if span["name"] == "train.sync"
              and span["attrs"].get("moe_expert_tokens_mean")]
    return statistics.median(ratios) if ratios else None
