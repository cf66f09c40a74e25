"""Experts: device time in the grouped expert matmuls (``moe_gmm.N``,
``moe_gmm_dx.N``, ``moe_gmm_dw.N``) over device busy time, in the
traced steps: ``expert_matmul_time_share``'s reading, under a name of
its own because that metric's entry lists its cells. It is what
``keye_expert_matmul_roofline_share`` has to be weighed against: the
share of a step that runs at that part of its roofline."""

from benchmark.layer_metrics.expert_matmul_time_share import read  # noqa: F401
