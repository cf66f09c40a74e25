"""CPU rehearsal of chip_smoke.py (on-chip-measurement guide, section 2.1
and 2.2): the script's own phase functions at a tiny size on the virtual
CPU mesh, the no-chip exits, and the rules the smoke rests on — who may
initialise which JAX backend, where the chip count comes from, where the
compile cache goes."""

import json
import os
import shutil
import subprocess
import sys

import pytest

import ray_tpu

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
TINY_RESNET = {"model": "resnet18", "batch": 8, "hw": 32}
TINY_GPT = {"model": "tiny", "batch": 8, "seq": 128}


@pytest.fixture(scope="module")
def declared_tpus():
    """A cluster whose TPU resource is DECLARED (no chip here): the
    TPU-flavour worker it starts computes on the CPU devices."""
    ray_tpu.init(num_cpus=4, num_tpus=4)
    try:
        yield
    finally:
        ray_tpu.shutdown()


def _line(capsys) -> dict:
    """The one JSON line a phase printed."""
    lines = [json.loads(l) for l in capsys.readouterr().out.splitlines()
             if l.startswith("{")]
    assert len(lines) == 1, lines
    return lines[0]


def _check_line(line, returned, steps):
    assert line == json.loads(json.dumps(returned))
    assert line["device"]["platform"] == "cpu"
    assert len(line["step_s"]) == steps and min(line["step_s"]) > 0
    assert line["first_step_s"] >= line["compile_s"] >= 0
    assert line["tpu_custom_calls"] == 0  # interpreted off the chip
    assert line["programs_built"]["timed"] == 0  # warm-up compiled it all
    assert line["jax_cache"]["dir"]


def test_resnet_phase_tiny(declared_tpus, capsys):
    import chip_smoke

    out = chip_smoke.phase_resnet(TINY_RESNET, warmup=1, steps=2)
    line = _line(capsys)
    _check_line(line, out, steps=2)
    assert line["phase"] == "resnet50" and line["model"] == "resnet18"
    assert len(line["losses"]) == 3 and len(line["raw_step_s"]) == 2
    # same seed, same batch, same step: framework and raw jit agree
    assert line["raw_losses"][0] == pytest.approx(line["losses"][0],
                                                  rel=1e-3)


def test_gpt_phase_tiny(declared_tpus, capsys):
    import chip_smoke

    out = chip_smoke.phase_gpt(TINY_GPT, warmup=1, steps=2)
    line = _line(capsys)
    _check_line(line, out, steps=2)
    assert line["shape"] == {"batch": 8, "seq": 128}
    assert line["losses"][-1] < line["losses"][0]


def test_mesh_phase_tiny(declared_tpus, capsys):
    """The four-chip phase on four of the virtual CPU devices: one
    worker with a lease of four chips, so the Trainer's derived
    (data=1, fsdp=4) mesh shards state and batch, against one device of
    the same process."""
    import chip_smoke

    out = chip_smoke.phase_mesh(TINY_GPT, chips=4)
    line = _line(capsys)
    assert line == json.loads(json.dumps(out))
    assert line["phase"] == "tiny_mesh"
    assert line["mesh"] == [1, 4] and line["device"]["count"] == 8
    # parameters live sharded (gathered for use), gradients are combined
    assert line["collectives"]["all-gather"] > 0
    assert line["collectives"]["all-reduce"] > 0
    assert line["state_bytes_per_device"] <= 1.1 * line["state_bytes"] / 4
    assert len(line["losses"]) == len(line["one_device_losses"]) == 3
    # only the first step compiles (the step pins its output layout)
    assert line["programs_built"]["warmup"] > 0
    assert line["programs_built"]["timed"] == 0


def test_no_chip_exits_nonzero_and_prints_no_ok():
    out = subprocess.run([sys.executable, os.path.join(REPO, "chip_smoke.py")],
                         capture_output=True, text=True, timeout=120,
                         cwd=REPO)
    assert out.returncode != 0
    assert '"ok"' not in out.stdout
    assert "exposes 0" in out.stderr


def test_script_alone_fails(tmp_path):
    """In a directory that holds chip_smoke.py and nothing else of the
    repo there is no program to smoke: non-zero, no result."""
    shutil.copy(os.path.join(REPO, "chip_smoke.py"), tmp_path)
    env = {k: v for k, v in os.environ.items() if k != "PYTHONPATH"}
    out = subprocess.run([sys.executable, "chip_smoke.py"], cwd=tmp_path,
                         env=env, capture_output=True, text=True, timeout=60)
    assert out.returncode != 0 and out.stdout == ""


_CPU_WORKER_PROBE = """
import os, ray_tpu
ray_tpu.init(num_cpus=1)

@ray_tpu.remote
def backend():
    import jax
    return os.environ["JAX_PLATFORMS"], jax.default_backend()

try:
    print("RESULT", *ray_tpu.get(backend.remote(), timeout=120))
finally:
    ray_tpu.shutdown()
"""


@pytest.mark.parametrize("ambient", ["", "tpu"])
def test_cpu_worker_is_cpu_under_any_ambient_platform(ambient):
    """A CPU-flavour worker is started with JAX_PLATFORMS=cpu SET: an
    inherited "" or "tpu" would let it take the chip (and libtpu's
    lock) from the TPU-flavour worker."""
    env = dict(os.environ, JAX_PLATFORMS=ambient, PYTHONPATH=REPO)
    out = subprocess.run([sys.executable, "-c", _CPU_WORKER_PROBE], env=env,
                         capture_output=True, text=True, timeout=180)
    assert "RESULT cpu cpu" in out.stdout, out.stdout + out.stderr


_TPU_WORKER_PROBE = """
import os, ray_tpu
ray_tpu.init(num_cpus=1, num_tpus=1)  # declared: this machine has no chip

@ray_tpu.remote(num_tpus=1)
def backend():
    import jax
    return os.environ["JAX_PLATFORMS"], jax.default_backend()

try:
    print("RESULT", *ray_tpu.get(backend.remote(), timeout=120))
except Exception as e:
    print("ERROR", e)
finally:
    ray_tpu.shutdown()
"""


@pytest.mark.parametrize("ambient, expect", [
    # the driver pinned JAX itself (test tree, CPU rehearsals): inherited
    ("cpu", "RESULT cpu cpu"),
    # nothing pinned: the worker is started on `tpu`, and a declared
    # chip that cannot be opened fails with libtpu's error at the
    # driver — it does not compute on the CPU without a word
    ("", "Unable to initialize backend 'tpu'"),
])
def test_tpu_worker_platform_is_not_probed(ambient, expect):
    env = dict(os.environ, JAX_PLATFORMS=ambient, PYTHONPATH=REPO)
    out = subprocess.run([sys.executable, "-c", _TPU_WORKER_PROBE], env=env,
                         capture_output=True, text=True, timeout=180)
    assert expect in out.stdout, out.stdout + out.stderr
    assert ("RESULT" in out.stdout) == (ambient == "cpu")


def test_detect_tpu_chips(monkeypatch):
    from ray_tpu import api
    from ray_tpu._private import accelerator

    monkeypatch.setenv("RAY_TPU_NUM_CHIPS", "3")
    assert api._detect_tpu_chips() == 3.0
    monkeypatch.delenv("RAY_TPU_NUM_CHIPS")
    # what the machine exposes — an installed libtpu package is not a chip
    assert api._detect_tpu_chips() == accelerator.count_tpu_chips()
    if not (os.path.isdir("/dev/vfio") or os.path.exists("/dev/accel0")):
        assert api._detect_tpu_chips() == 0.0
    for ambient, want in (("cpu", "cpu"), ("", "tpu"), ("tpu", "tpu")):
        monkeypatch.setenv("JAX_PLATFORMS", ambient)
        assert accelerator.tpu_worker_jax_platforms() == want


_CACHE_PROBE = """
from ray_tpu._private import compile_cache
where = compile_cache.enable_persistent_cache()
import jax
print("RESULT", where, jax.config.jax_compilation_cache_dir)
"""


@pytest.mark.parametrize("placed", [True, False])
def test_compile_cache_placement(placed, tmp_path):
    """JAX_COMPILATION_CACHE_DIR set: the cache is there and no code
    sets another; unset: the fixed <repo>/.jax_cache."""
    env = {k: v for k, v in os.environ.items()
           if k != "JAX_COMPILATION_CACHE_DIR"}
    env["PYTHONPATH"] = REPO
    if placed:
        env["JAX_COMPILATION_CACHE_DIR"] = str(tmp_path)
    out = subprocess.run([sys.executable, "-c", _CACHE_PROBE], env=env,
                         capture_output=True, text=True, timeout=120)
    want = str(tmp_path) if placed else os.path.join(REPO, ".jax_cache")
    assert f"RESULT {want} {want}\n" in out.stdout, \
        out.stdout + out.stderr
