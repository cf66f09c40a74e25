"""Experts: how much of the grouped matmul's static rows held an
assignment — ``expert_rows_filled_share``'s reading of the
``moe_rows_filled`` / ``moe_rows_static`` counters on ``train.sync``
(summed over the five layers and the call's steps), median over the
window's calls, in percent, under a name of its own because that
metric's entry lists its cell. The static rows are the worst case, every
one of the 16 384 x 8 assignments held here; with 16 of 128 experts
held an eighth is filled at uniform routing."""

from benchmark.layer_metrics.expert_rows_filled_share import read  # noqa: F401
