"""Builder's tool: ``aot_step.py`` with the delta rule's kernels steered
to Mosaic too (``ops/gated_delta.py`` is newer than that tool's list of
modules; here ``jax.default_backend()`` is the CPU, and in interpret
mode the kernels would lower to ordinary operations, whose buffers are
not the chip's).

    python3 benchmark/tools/qwen3next_aot.py qwen3next_ep16_seq8k <batch>
"""

import os
import sys

sys.path.insert(0, os.path.dirname(os.path.abspath(__file__)))

import aot_step  # noqa: E402  (sets JAX_PLATFORMS and the import path)
from ray_tpu.ops import gated_delta  # noqa: E402

if __name__ == "__main__":
    gated_delta.is_tpu = lambda: True
    aot_step.main()
