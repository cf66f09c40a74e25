"""Compile caches: the driver's clock around the first
``train(num_steps=1)``, which compiles (first run in a checkout) or
loads the step from the export cache and JAX's persistent cache."""


def read(host, trace):
    return host["phases"].get("first_step_s")
