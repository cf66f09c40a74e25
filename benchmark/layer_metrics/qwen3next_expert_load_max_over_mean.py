"""Experts: how uneven the routing is over the 32 held experts — the
most tokens one held expert got in one expert layer of one step over
the mean, median over the window's calls: ``expert_load_max_over_mean``'s
reading of the ``moe_expert_tokens_max`` / ``_mean`` counters, under a
name of its own because that metric's entry lists its cells. A held
expert sees about 160 assignments a layer, sequence and step here (a
sixteenth of its deployment's: under a third of a 512-row tile), and on
one repeated batch the router learns which experts are held."""

from benchmark.layer_metrics.expert_load_max_over_mean import read  # noqa: F401
