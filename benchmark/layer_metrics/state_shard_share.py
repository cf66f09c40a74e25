"""Operator: how much of the training state the fullest chip holds —
``state_bytes_fullest_chip`` over ``state_bytes`` on the worker's
``train.dispatch`` span (addressable shard bytes of parameters, model
and optimizer state), in percent; median over the window's calls
(``benchmark/span_log.py``). 25 % when four chips share it evenly,
100 % when every chip holds it whole. A program whose span carries no
such counts gives None."""

import statistics

from benchmark import span_log


def read(host, trace):
    entries = span_log.window_entries(host)
    if not entries:
        return None
    shares = []
    for entry in entries:
        for span in entry["spans"]:
            attrs = span["attrs"]
            if span["name"] == "train.dispatch" and attrs.get("state_bytes"):
                shares.append(100.0 * attrs["state_bytes_fullest_chip"]
                              / attrs["state_bytes"])
    return statistics.median(shares) if shares else None
