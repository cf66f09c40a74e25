"""Builder's tool: one run of one cell, as ``benchmark/run.py`` makes it,
and afterwards the program's call log (``ray_tpu.train.call_log()``:
the span tree of every ``train()`` call the run made, the traced one's
per-leaf spans included) written to a file, to read a single call's
split where the result line gives medians.

    python3 benchmark/tools/run_with_log.py <log.json> --workload <cell> \
        --seed <n> --seconds <s> --trace <0|1>

The result line is ``run.py``'s own, printed last as always; on a
program without the log the file holds ``null``."""

import json
import os
import sys

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__)))))

from benchmark import run  # noqa: E402  (set-up counts from this import)

if __name__ == "__main__":
    code = run.main(sys.argv[2:])
    try:
        from ray_tpu.train import call_log
    except ImportError:
        call_log = None
    with open(sys.argv[1], "w") as f:
        json.dump(call_log and call_log(), f)
    sys.exit(code)
