"""Builder's tool: ``aot_step.py`` with the delta rules' kernels steered
to Mosaic too (``ops/kda.py`` and ``ops/gated_delta.py`` are newer than
that tool's list of modules; here ``jax.default_backend()`` is the CPU,
and in interpret mode the kernels would lower to ordinary operations,
whose buffers are not the chip's).

    python3 benchmark/tools/kimilinear_aot.py kimilinear_ep32_seq8k <batch>
"""

import os
import sys

sys.path.insert(0, os.path.dirname(os.path.abspath(__file__)))

import aot_step  # noqa: E402  (sets JAX_PLATFORMS and the import path)
from ray_tpu.ops import gated_delta, kda  # noqa: E402

if __name__ == "__main__":
    gated_delta.is_tpu = kda.is_tpu = lambda: True
    aot_step.main()
