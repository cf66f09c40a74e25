"""Experts: how much of the grouped matmul's static rows held an
assignment — ``expert_rows_filled_share``'s reading of the
``moe_rows_filled`` / ``moe_rows_static`` counters on ``train.sync``
(summed over the four expert layers and the call's steps), median over
the window's calls, in percent, under a name of its own because that
metric's entry lists its cells. The static rows are the worst case,
every one of the T x B x 8 assignments held here; with 8 of 256 experts
held a thirty-second is filled at uniform routing — the smallest filled
share of any cell — and the rest is memory and elementwise passes the
step pays for nothing."""

from benchmark.layer_metrics.expert_rows_filled_share import read  # noqa: F401
