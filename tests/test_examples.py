"""The examples/ scripts must actually run (reference idiom:
doc/examples are exercised in CI)."""

import os
import subprocess
import sys

import pytest

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


@pytest.mark.parametrize("script,args", [
    ("parameter_server.py", ["2", "8"]),
    ("streaming_word_count.py", []),
    ("serve_canary.py", []),
    # slow tier: the tier-1 window is wall-clock-bound on the 1-core CI
    # box — the streaming demo is covered there by test_serve_streaming
    pytest.param("streaming_chat.py", [], marks=pytest.mark.slow),
    ("tune_tpe.py", []),
])
def test_example_runs(script, args):
    env = dict(os.environ)
    env["JAX_PLATFORMS"] = "cpu"
    env["PYTHONPATH"] = REPO + os.pathsep + env.get("PYTHONPATH", "")
    proc = subprocess.run(
        [sys.executable, os.path.join(REPO, "examples", script), *args],
        capture_output=True, text=True, timeout=420, env=env, cwd=REPO)
    assert proc.returncode == 0, (proc.stdout[-2000:], proc.stderr[-2000:])
