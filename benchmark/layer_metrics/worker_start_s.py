"""Control plane: the driver's clock around ``Trainer(...)`` — lease,
worker spawn, libtpu initialisation, the operator's ``setup`` (weights
and the batch made on the device from the seed)."""


def read(host, trace):
    return host["phases"].get("worker_start_s")
