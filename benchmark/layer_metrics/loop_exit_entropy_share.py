"""Operator: that the exit gate is computed and has not collapsed —
``exit_entropy`` over ``loop_targets`` x log(walks) on a call's
``train.sync`` span (the entropy of the exit distribution summed over
the tokens scored, against the most it could be; the walks are the
span's ``exit_mass_*`` counters), median over the window's calls, in
percent. A gate at one half everywhere reads 87.5 % over four walks; a
gate that sends every token out of one walk reads 0. A program whose
spans carry no such counters gives None."""

import math
import statistics

from benchmark import span_log


def walks(attrs: dict) -> int:
    return sum(key.startswith("exit_mass_") for key in attrs)


def median_of(host, value) -> float | None:
    """The median of `value(attrs)` over the `train.sync` attributes of
    the window's calls that counted a looped step (`loop_targets` > 0);
    None without a log or without such counters."""
    counters = [span["attrs"]
                for entry in span_log.window_entries(host) or []
                for span in entry["spans"] if span["name"] == "train.sync"
                and span["attrs"].get("loop_targets")]
    try:
        return statistics.median(map(value, counters)) if counters else None
    except (KeyError, ZeroDivisionError, ValueError):
        return None


def read(host, trace):
    return median_of(host, lambda a: 100.0 * a["exit_entropy"] / (
        a["loop_targets"] * math.log(walks(a))))
