"""Cluster launcher: `ray-tpu up cluster.yaml` brings a head + workers up
through the provider's command transport, `exec` reaches the head, `down`
stops everything (reference: autoscaler/_private/commands.py
create_or_update_cluster / teardown_cluster; updater.py NodeUpdater).
The hosts provider runs commands through `bash -c` here — the same
template shape as ssh, minus the network."""

import os
import subprocess
import sys
import textwrap

import pytest

from ray_tpu.autoscaler import launcher

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
PY = sys.executable


@pytest.fixture
def launcher_env(tmp_path, monkeypatch):
    monkeypatch.setenv("RAY_TPU_TMPDIR", str(tmp_path))
    monkeypatch.setenv("JAX_PLATFORMS", "cpu")
    monkeypatch.setattr(launcher, "STATE_DIR", str(tmp_path / "clusters"))
    monkeypatch.chdir(REPO)
    yield tmp_path


def _write_config(tmp_path, hosts, extra: str = "") -> str:
    cli = f"{PY} -m ray_tpu.scripts.cli"
    cfg = textwrap.dedent(f"""\
        cluster_name: lctest
        provider:
          type: hosts
          hosts: {hosts!r}
          run_command: "bash -c {{cmd}}"
        port: 0
        head_start_command: "{cli} start --head --port {{port}} --num-cpus 1"
        worker_start_command: "{cli} start --address {{gcs_address}} --num-cpus 1"
        stop_command: "{cli} stop"
        """) + textwrap.dedent(extra)
    path = tmp_path / "cluster.yaml"
    path.write_text(cfg)
    return str(path)


def test_launcher_up_exec_down(launcher_env):
    """Two local "hosts": head + one worker; the launched cluster accepts
    a driver, exec reaches the head with the cluster address, and down
    stops the nodes."""
    path = _write_config(launcher_env, ["127.0.0.1", "127.0.0.1"])
    state = launcher.up(path)
    try:
        assert [n["role"] for n in state["nodes"]] == ["head", "worker"]
        assert state["gcs_address"].startswith("127.0.0.1:")

        # the launched cluster is real: a driver sees both nodes
        driver = subprocess.run(
            [PY, "-c", textwrap.dedent(f"""
                import time
                import ray_tpu
                ray_tpu.init(address={state['gcs_address']!r})
                for _ in range(50):
                    if len(ray_tpu.nodes()) == 2:
                        break
                    time.sleep(0.2)
                assert len(ray_tpu.nodes()) == 2, ray_tpu.nodes()
                print("DRIVER_SAW_2_NODES")
            """)],
            capture_output=True, text=True, timeout=120, cwd=REPO,
            env=dict(os.environ))
        assert "DRIVER_SAW_2_NODES" in driver.stdout, (
            driver.stdout + driver.stderr)

        # exec runs on the head with RAY_TPU_ADDRESS set
        out = launcher.exec_on_head("lctest", "echo addr=$RAY_TPU_ADDRESS")
        assert f"addr={state['gcs_address']}" in out

        # attach is printable without a tty
        cmdline = launcher.attach_command("lctest")
        assert state["gcs_address"] in cmdline
    finally:
        errors = launcher.down("lctest")
    assert errors == 0
    assert launcher.load_state("lctest") is None


def test_launcher_config_validation(launcher_env, tmp_path):
    bad = tmp_path / "bad.yaml"
    bad.write_text("cluster_name: x\nprovider: {type: hosts}\n")
    with pytest.raises(launcher.LauncherError, match="hosts"):
        launcher.load_cluster_config(str(bad))
    bad.write_text("provider: {type: hosts, hosts: [a]}\n")
    with pytest.raises(launcher.LauncherError, match="cluster_name"):
        launcher.load_cluster_config(str(bad))
    bad.write_text(
        "cluster_name: x\nprovider: {type: aws, hosts: [a]}\n")
    with pytest.raises(launcher.LauncherError, match="provider type"):
        launcher.load_cluster_config(str(bad))
    bad.write_text(
        "cluster_name: x\nprovider: {type: hosts, hosts: [a]}\n"
        "bogus_key: 1\n")
    with pytest.raises(launcher.LauncherError, match="bogus_key"):
        launcher.load_cluster_config(str(bad))
    with pytest.raises(launcher.LauncherError, match="no launcher state"):
        launcher.down("never-upped")


def test_launcher_file_mounts(launcher_env, tmp_path):
    """file_mounts sync to every host before setup commands run
    (reference: ray-schema.json file_mounts + updater.sync_file_mounts);
    the bash transport stands in for rsync."""
    src = tmp_path / "payload.txt"
    src.write_text("mounted-content")
    # parent dir intentionally NOT pre-created: _sync_mounts mkdir -p's
    # it on the host first (reference updater behavior)
    dest = tmp_path / "synced" / "payload.txt"
    extra = f"""\
        file_mounts:
          {dest}: {src}
        sync_command: "cp -r {{local}} {{remote}}"
        setup_commands:
          - "test -f {dest}"
        """
    path = _write_config(launcher_env, ["127.0.0.1"], extra)
    state = launcher.up(path)
    try:
        assert dest.read_text() == "mounted-content"
        assert len(state["nodes"]) == 1
    finally:
        assert launcher.down("lctest") == 0

    # a missing source fails loudly before anything starts
    bad = _write_config(launcher_env, ["127.0.0.1"], f"""\
        file_mounts:
          {dest}: {tmp_path / 'nope.txt'}
        sync_command: "cp -r {{local}} {{remote}}"
        """)
    with pytest.raises(launcher.LauncherError, match="does not exist"):
        launcher.up(bad)
