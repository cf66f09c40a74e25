"""Device: model-FLOP/s utilisation of the worker's own epochs — its
median rate (``worker_samples_per_s``) times the family's model FLOPs
per sample over chips times the bf16 peak of ``peaks.json``. An
end-to-end utilisation, not a kernel's roofline share; recomputation is
not counted."""

from benchmark.layer_metrics import worker_samples_per_s


def read(host, trace):
    rate = worker_samples_per_s.read(host, trace)
    if rate is None or "peaks" not in host:
        return None
    return 100.0 * rate * host["flops_per_sample"] / (
        host["chips"] * host["peaks"]["bf16_flops_per_s"])
