"""Operator: that the selection engages — ``index_pairs_selected`` over
``index_pairs_causal`` on a call's ``train.sync`` span (the pairs the
indexer kept over the pairs at or below the diagonal, summed over the
layers and the call's steps), median over the window's calls, in
percent. By construction sum of min(t + 1, topk) over t(t + 1) / 2:
23.4 % at 16 384 tokens and topk 2048, 100 % at or below 2048 tokens. A
program whose spans carry no such counters gives None."""

import statistics

from benchmark import span_log


def ratio(host, over: str, under: str, scale: float = 100.0):
    """The median over the window's calls of `scale` x `over` / `under`
    (percent by default), two counters of the `train.sync` span; None
    where no call has them."""
    entries = span_log.window_entries(host)
    if not entries:
        return None
    shares = [scale * span["attrs"][over] / span["attrs"][under]
              for entry in entries for span in entry["spans"]
              if span["name"] == "train.sync"
              and span["attrs"].get(under)]
    return statistics.median(shares) if shares else None


def read(host, trace):
    return ratio(host, "index_pairs_selected", "index_pairs_causal")
