"""Builder's tool: ``aot_step.py`` with the indexer's kernel steered to
Mosaic too (``ops/sparse_index.py`` is newer than that tool's list of
modules; here ``jax.default_backend()`` is the CPU, and in interpret
mode the kernel would lower to ordinary operations, whose buffers are
not the chip's).

    python3 benchmark/tools/keye_aot.py keye_ep8_seq16k <batch> [seq] [dump dir]
"""

import os
import sys

sys.path.insert(0, os.path.dirname(os.path.abspath(__file__)))

import aot_step  # noqa: E402  (sets JAX_PLATFORMS and the import path)
from ray_tpu.ops import sparse_index  # noqa: E402

if __name__ == "__main__":
    sparse_index.is_tpu = lambda: True
    aot_step.main()
