"""Experts: how uneven the routing is over the sixteen held experts —
the most tokens one held expert got in one routing layer (the MTP
block's included) of one step over the mean, median over the window's
calls: ``expert_load_max_over_mean``'s reading of the
``moe_expert_tokens_max`` / ``_mean`` counters, under a name of its own
because that metric's entry lists its cell. A held expert sees about 768
assignments a layer and step here (a sixteenth of its deployment's), so
the ratio reads higher than with four times the tokens."""

from benchmark.layer_metrics.expert_load_max_over_mean import read  # noqa: F401
