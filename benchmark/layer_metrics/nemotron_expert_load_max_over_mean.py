"""Experts: how uneven the routing is over the eight held experts — the
most tokens one held expert got in one expert block of one step over
the mean, median over the window's calls: ``expert_load_max_over_mean``'s
reading of the ``moe_expert_tokens_max`` / ``_mean`` counters, under a
name of its own because that metric's entry lists its cells. A held
expert sees about 384 assignments a block and step here (a sixteenth of
its deployment's), and on one repeated sequence the router learns which
experts are held."""

from benchmark.layer_metrics.expert_load_max_over_mean import read  # noqa: F401
