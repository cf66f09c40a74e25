"""Experts: how uneven the routing is over the 8 held experts — the
most tokens one held expert got in one expert layer of one step over
the mean, median over the window's calls: ``expert_load_max_over_mean``'s
reading of the ``moe_expert_tokens_max`` / ``_mean`` counters, under a
name of its own because that metric's entry lists its cells. A held
expert sees about 256 assignments a layer, sequence and step here (a
thirty-second of its deployment's), and the selection bias moves after
every step."""

from benchmark.layer_metrics.expert_load_max_over_mean import read  # noqa: F401
