"""Experts: how near the grouped expert matmuls run to the chip's
roofline in this family's cell — ``expert_matmul_roofline_share``'s
reckoning, with the operations and bytes of ``families/keye.py::
expert_matmul_flops_bytes`` (gated SiLU experts, 2048 -> 2 x 768 ->
2048, 16 held) fed the TRACED call's own ``moe_assignments_held`` and
its ``moe_steps`` times the five layers from the call's ``train.sync``
span. A program whose spans carry no such counters, or whose trace
names no such kernel, gives None."""

from benchmark.layer_metrics.lfm2_expert_matmul_roofline_share import read  # noqa: F401
