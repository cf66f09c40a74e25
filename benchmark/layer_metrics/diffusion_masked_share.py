"""Operator: that the noise is drawn, and fresh — ``diffusion_masked``
over ``diffusion_targets`` on a call's ``train.sync`` span (tokens the
step masked over tokens it could have, summed over the call's steps),
median over the window's calls, in percent. Its expectation is
(1 + 1e-3) / 2 = 50.05 %; 16 steps of 8192 tokens spread it by about a
percent, and a noise that repeated every call would read the same
number every call. A program whose spans carry no such counters gives
None."""

import statistics

from benchmark import span_log


def read(host, trace):
    entries = span_log.window_entries(host)
    if not entries:
        return None
    shares = [100.0 * span["attrs"]["diffusion_masked"]
              / span["attrs"]["diffusion_targets"]
              for entry in entries for span in entry["spans"]
              if span["name"] == "train.sync"
              and span["attrs"].get("diffusion_targets")]
    return statistics.median(shares) if shares else None
