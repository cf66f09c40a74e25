"""Experts: how uneven the routing is over the held experts — the most
tokens one held expert got in one layer of one step over the mean,
median over the window's calls: ``expert_load_max_over_mean``'s reading
of the ``moe_expert_tokens_max`` / ``_mean`` counters, under a name of
its own because that metric's entry lists its cells. Here the selection
bias works against it, a thousandth a step."""

from benchmark.layer_metrics.expert_load_max_over_mean import read  # noqa: F401
