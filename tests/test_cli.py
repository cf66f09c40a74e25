"""CLI + log plumbing (reference: python/ray/scripts/scripts.py `ray
start`/`status`/`memory`/`stop`; log streaming: log_monitor.py:48)."""

import os
import re
import subprocess
import sys
import time

import pytest

import ray_tpu

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def _cli(args, env, timeout=60):
    return subprocess.run(
        [sys.executable, "-m", "ray_tpu.scripts.cli", *args],
        env=env, cwd=REPO, capture_output=True, text=True, timeout=timeout)


def test_cli_start_status_memory_stop(tmp_path):
    env = dict(os.environ)
    env["RAY_TPU_TMPDIR"] = str(tmp_path)
    env["JAX_PLATFORMS"] = "cpu"

    out = _cli(["start", "--head", "--num-cpus", "2"], env)
    assert out.returncode == 0, out.stderr
    m = re.search(r"GCS address: (\S+)", out.stdout)
    assert m, out.stdout
    gcs_address = m.group(1)

    try:
        # The two-shell flow: a separate driver process connects by
        # address and runs work on the CLI-started cluster.
        driver = subprocess.run(
            [sys.executable, "-c", f"""
import ray_tpu
ray_tpu.init(address={gcs_address!r})

@ray_tpu.remote
def f(x):
    return x * 2

assert ray_tpu.get(f.remote(21)) == 42
print("DRIVER_OK")
"""],
            env=env, cwd=REPO, capture_output=True, text=True, timeout=120)
        assert "DRIVER_OK" in driver.stdout, (
            driver.stdout[-1500:] + driver.stderr[-1500:])

        out = _cli(["status"], env)
        assert out.returncode == 0, out.stderr
        assert "1 node(s)" in out.stdout and "(head)" in out.stdout

        out = _cli(["memory"], env)
        assert out.returncode == 0, out.stderr
        assert "worker(s)" in out.stdout
    finally:
        out = _cli(["stop"], env)
    assert out.returncode == 0
    assert not os.path.exists(tmp_path / "cluster.json")

    # The cluster must actually be gone: a status probe now fails.
    out = _cli(["status", "--address", gcs_address], env, timeout=30)
    assert out.returncode != 0


def test_worker_prints_stream_to_driver(ray_start_regular, capfd):
    @ray_tpu.remote
    def chatty():
        print("MARKER_FROM_WORKER_7c3")
        return 1

    assert ray_tpu.get(chatty.remote(), timeout=60) == 1
    deadline = time.monotonic() + 10
    seen = ""
    while time.monotonic() < deadline:
        seen += capfd.readouterr().err
        if "MARKER_FROM_WORKER_7c3" in seen:
            break
        time.sleep(0.2)
    assert "MARKER_FROM_WORKER_7c3" in seen
    assert "(pid=" in seen


def test_cli_submit(tmp_path):
    env = dict(os.environ)
    env["RAY_TPU_TMPDIR"] = str(tmp_path)
    env["JAX_PLATFORMS"] = "cpu"

    out = _cli(["start", "--head", "--num-cpus", "2"], env)
    assert out.returncode == 0, out.stderr
    script = tmp_path / "driver.py"
    script.write_text("""
import os
import sys

import ray_tpu

ray_tpu.init(address=os.environ["RAY_TPU_ADDRESS"])

@ray_tpu.remote
def triple(x):
    return 3 * x

assert sys.argv[1] == "--value"
print("RESULT:", ray_tpu.get(triple.remote(int(sys.argv[2])), timeout=60))
ray_tpu.shutdown()
""")
    try:
        # dash-prefixed driver args must reach the script, not argparse
        out = _cli(["submit", str(script), "--value", "14"], env,
                   timeout=120)
        assert out.returncode == 0, (out.stdout, out.stderr)
        assert "RESULT: 42" in out.stdout
    finally:
        _cli(["stop"], env)


def test_cli_profile_and_top_json(ray_start_regular, tmp_path, capsys):
    """`ray-tpu profile --seconds 2` against a live cluster emits a
    collapsed-stack flamegraph covering >=3 process classes (the
    tentpole acceptance), and `ray-tpu top --json --once` returns the
    machine-readable rate/p99 snapshot (satellite). In-process cli.main
    against the fixture cluster — the start/stop plumbing is already
    covered above."""
    import json

    from ray_tpu import api as _api
    from ray_tpu.scripts import cli

    addr = _api._global_node.gcs_address

    @ray_tpu.remote
    def f(x):
        return x

    assert ray_tpu.get([f.remote(i) for i in range(5)],
                       timeout=60) == list(range(5))

    collapsed = tmp_path / "prof.collapsed"
    capsys.readouterr()
    assert cli.main(["profile", "--address", addr, "--seconds", "2",
                     "-o", str(collapsed)]) == 0
    summary = capsys.readouterr().out
    lines = collapsed.read_text().splitlines()
    assert lines, "empty flamegraph"
    classes = {line.split(";", 1)[0] for line in lines}
    assert {"driver", "raylet", "gcs"} <= classes, (classes, summary)
    # every line is collapsed-format: "frame;frame;... count"
    for line in lines:
        stack, count = line.rsplit(" ", 1)
        assert int(count) > 0 and ";" in stack

    # top --json --once: one-shot machine-readable snapshot
    deadline = time.monotonic() + 20
    while time.monotonic() < deadline and not ray_tpu.cluster_metrics(
            history=1):
        time.sleep(0.3)
    capsys.readouterr()
    assert cli.main(["top", "--address", addr, "--json", "--once"]) == 0
    doc = json.loads(capsys.readouterr().out)
    assert doc["sources"], doc
    row = next(iter(next(iter(doc["sources"].values())).values()))
    assert "latest" in row and "ts" in row
    # p99 rows carry the saturation flag (and exemplars when traced)
    p99s = [r for rs in doc["sources"].values()
            for name, r in rs.items() if name.endswith(".p99")]
    assert all("saturated" in r for r in p99s)
