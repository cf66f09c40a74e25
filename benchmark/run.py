"""One run of one benchmark cell.

    python3 benchmark/run.py --workload <cell> --seed <n> --seconds <s> --trace <0|1>

A user's training job, seen from the driver's side: ``ray_tpu.init()``,
one ``Trainer(Op, num_workers=1, use_tpu=True)``, set-up, then whole
``Trainer.train()`` calls until ``--seconds`` have passed. Throughput is
samples over the driver's own clock, the state pull after every call
included. The last line of standard output is one JSON object
(``correct``, ``attempted``, ``failed``, ``metrics``, ``device``, and
with ``--trace 1`` ``breakdown``).

This process never initialises a JAX backend: the chip belongs to the
worker the Trainer starts. No chip, fewer chips than the cell asks for,
or a device kind that ``peaks.json`` does not list: a non-zero exit and
no result line. ``--rehearse-cpu`` (never in the manifest's command)
runs the cell's tiny rehearsal sizes on the CPU, reports no metric and
``correct: false``.
"""

from __future__ import annotations

import time

T_START = time.perf_counter()   # set-up counts from here

import argparse
import json
import math
import os
import shutil
import sys
import tempfile

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, ROOT)

from benchmark import manifest, trace_reduce  # noqa: E402


class NoResult(Exception):
    """The run cannot give a result: exit non-zero, print no line."""


def _train(trainer, record, **kw) -> dict:
    """One ``Trainer.train()`` call on the driver's clock."""
    record["attempted"] += 1
    t0 = time.perf_counter()
    try:
        out = trainer.train(**kw)
    except Exception:
        record["failed"] += 1
        raise
    wall = time.perf_counter() - t0
    return {
        "wall_s": wall,
        # the worker's own epoch: its samples over its rate, as the
        # operator reports them (the epoch ends in float(loss))
        "epoch_s": out["num_samples"] / out["samples_per_s"],
        "samples": int(out["num_samples"]),
        "worker_samples_per_s": out["samples_per_s"],
        "last_loss": out["last_train_loss"],
        "mean_loss": out["train_loss"],
        "programs_built": int(out["programs_built"]),
        "device": out["device"], "jax_cache": out["jax_cache"],
    }


def wait_for_exit(session: str, patience: float = 20.0) -> None:
    """Every process of the runtime has ended before this one does.
    ``shutdown()`` kills the services; workers that were still starting
    notice a second or two later. They carry the session directory in
    their command line; what outlives `patience` is killed."""
    def alive():
        pids = []
        for pid in filter(str.isdigit, os.listdir("/proc")):
            try:
                with open(f"/proc/{pid}/cmdline", "rb") as f:
                    if session.encode() in f.read():
                        pids.append(int(pid))
            except OSError:
                pass
        return [p for p in pids if p != os.getpid()]

    deadline = time.perf_counter() + patience
    while (left := alive()) and time.perf_counter() < deadline:
        time.sleep(0.1)
    for pid in left:
        try:
            os.kill(pid, 9)
        except OSError:
            pass


def measure(cell: dict, seed: int, seconds: float, trace: bool,
            rehearse: bool) -> dict:
    """Set-up, the window and (traced runs) one profiled call. Returns
    the host record the layer metrics read."""
    import ray_tpu
    from ray_tpu.train import Trainer

    from benchmark.worker_side import operator_cls

    workload, chips = cell["workload"], cell["chips"]
    record = {"attempted": 0, "failed": 0, "phases": {}, "calls": [],
              "trace": None, "chips": chips}
    phases = record["phases"]

    def phase(name, t0):
        phases[name] = time.perf_counter() - t0
        return time.perf_counter()

    # the program's session files go under TMPDIR, not a fixed /tmp path
    os.environ.setdefault("RAY_TPU_TMPDIR", os.path.join(
        tempfile.gettempdir(), "ray_tpu"))
    t = time.perf_counter()
    phases["import_s"] = t - T_START
    from ray_tpu.native.store import native_store_available

    # built here from committed sources: no C++ compiler must be an
    # error, not a silent switch to the slower Python object store
    if not native_store_available():
        raise NoResult("the native object store did not build")
    t = phase("native_store_s", t)
    # bare init: the TPU resource comes from the machine (a rehearsal
    # declares it; its workers inherit JAX_PLATFORMS=cpu)
    session = ray_tpu.init(
        **({"num_tpus": chips} if rehearse else {}))["session_dir"]
    trainer = None
    try:
        tpus = ray_tpu.cluster_resources().get("TPU", 0)
        if tpus < chips:
            raise NoResult(f"this machine exposes {tpus} TPU chip(s); "
                           f"cell {cell['name']} needs {chips}")
        t = phase("init_s", t)
        trainer = Trainer(
            operator_cls(), num_workers=1, use_tpu=True,
            # a worker that dies is a failed call, reported — not a
            # restore retried inside the window (36.8 s in PR 22's smoke)
            max_retries=0,
            config={"model": cell["model"], "workload": workload,
                    "seed": seed},
            resources_per_worker={"CPU": 1, "TPU": chips})
        t = phase("worker_start_s", t)
        first = _train(trainer, record, num_steps=1)  # compiles or loads
        t = phase("first_step_s", t)
        record["first"] = first
        device = first["device"]
        if not rehearse:
            if device["platform"] != "tpu" or device["count"] < chips:
                raise NoResult(f"the worker computes on {device}; the "
                               f"cell needs {chips} TPU chip(s)")
            record["peaks"] = manifest.peaks(device["kind"])
        record["reference_loss"] = trainer.validate(
            num_steps=1)["reference_loss"]
        t = phase("check_s", t)
        # the first step after a train() boundary read high in PR 22
        record["warm"] = _train(trainer, record, num_steps=2)
        t = phase("warm_s", t)

        record["setup_s"] = (t0 := time.perf_counter()) - T_START
        while time.perf_counter() - t0 < seconds:
            record["calls"].append(_train(
                trainer, record, num_steps=workload["steps_per_call"]))
        record["window_s"] = time.perf_counter() - t0

        if trace:
            trace_dir = tempfile.mkdtemp(prefix="bench_trace_")
            try:
                _train(trainer, record, profile_dir=trace_dir,
                       num_steps=workload["trace_steps"])
                path = trace_reduce.find_xplane(trace_dir)
                record["trace"] = path and trace_reduce.reduce_trace(path)
                keep = os.environ.get("BENCH_KEEP_TRACE")
                if keep and path:  # builder's tool: look at a trace by hand
                    os.makedirs(keep, exist_ok=True)
                    shutil.copy(path, keep)
            finally:
                shutil.rmtree(trace_dir, ignore_errors=True)
    finally:
        try:
            if trainer is not None:
                trainer.shutdown(force=True)
        finally:
            ray_tpu.shutdown()
            wait_for_exit(session)
    return record


def judge(cell: dict, record: dict, rehearse: bool) -> dict:
    """Every condition of ``correct``, by name."""
    workload = cell["workload"]
    calls = [record["first"], record["warm"]] + record["calls"]
    losses = [c[k] for c in calls for k in ("mean_loss", "last_loss")]
    first_loss = record["first"]["last_loss"]
    reference = record["reference_loss"]
    # bf16 compute (8 bits of mantissa) against the float32 reference:
    # single values are off by up to 2**-9, the mean over thousands of
    # targets by far less; the cell's file carries the tolerance, tight
    # enough that an 8-bit float or a dropped term of the loss fails
    rtol = workload["reference"]["rtol"]
    return {
        "on_tpu": record["first"]["device"]["platform"] == "tpu",
        "not_a_rehearsal": not rehearse,
        "losses_finite": all(isinstance(x, float) and math.isfinite(x)
                             for x in losses + [reference]),
        # the repeated batch: the optimizer must not diverge on it
        "loss_fell": bool(record["calls"]) and (
            record["calls"][-1]["last_loss"]
            < workload["loss_ceiling"] * first_loss),
        "nothing_built_in_window": all(
            c["programs_built"] == 0 for c in record["calls"]),
        "matches_reference": abs(first_loss - reference)
        <= rtol * abs(reference),
        "no_call_failed": record["failed"] == 0,
    }


def window_split(record: dict) -> tuple[float, float, float]:
    """The window's seconds as (device busy, idle inside epochs, train()
    boundaries). The trace covers epochs only (the profiler runs inside
    ``train_epoch``) and between two epochs the device is idle by
    construction, so busy time is the traced busy share times the
    window's epoch seconds."""
    tr = record["trace"]
    epoch_s = sum(c["epoch_s"] for c in record["calls"])
    busy = tr["busy_s"] / tr["span_s"] * epoch_s
    return busy, epoch_s - busy, sum(
        c["wall_s"] - c["epoch_s"] for c in record["calls"])


def breakdown(record: dict) -> dict:
    tr = record["trace"]
    ops = sorted(tr["op_self_s"].items(), key=lambda x: -x[1])[:8]
    _, in_epochs, boundary = window_split(record)
    gaps = [["train() boundary over the window: state pull, copy-out, "
             "actor hops (host clock)", boundary],
            ["inside the window's epochs (traced idle share x epoch "
             "seconds)", in_epochs]]
    gaps += [[f"traced: after {a} before {b}", g]
             for g, a, b in tr["gaps"][:6]]
    return {"device_ops": [[k, v] for k, v in ops], "idle_gaps": gaps}


def result_line(cell: dict, record: dict, trace: bool,
                rehearse: bool) -> dict:
    checks = judge(cell, record, rehearse)
    device = dict(record["calls"][-1]["device"] if record["calls"]
                  else record["first"]["device"])
    record["flops_per_sample"] = cell["family"].flops_per_sample(
        cell["model"], cell["workload"])
    metrics = {}
    if rehearse:
        pass                      # a CPU run writes no device metric
    elif trace:
        for m in cell["per_layer"]:
            value = cell["readers"][m["name"]](record, record["trace"])
            if value is not None:
                metrics[m["name"]] = {"value": value, "unit": m["unit"]}
        if not record["trace"]:
            raise NoResult("the traced run shows no operation on a device")
        device["busy_s"] = window_split(record)[0]
        device["window_s"] = record["window_s"]
    else:
        samples = sum(c["samples"] for c in record["calls"])
        values = {"samples_per_s": samples / record["window_s"],
                  "setup_s": record["setup_s"]}
        metrics = {m["name"]: {"value": values[m["name"]],
                               "unit": m["unit"]}
                   for m in cell["end_to_end"]}
    line = {"correct": all(checks.values()),
            "attempted": record["attempted"], "failed": record["failed"],
            "metrics": metrics, "device": device, "checks": checks,
            "cell": cell["name"], "rehearsal": rehearse,
            "window": {
                "calls": len(record["calls"]), "seconds": record["window_s"],
                "phases": record["phases"],
                "first_loss": record["first"]["last_loss"],
                "reference_loss": record["reference_loss"],
                "last_loss": record["calls"][-1]["last_loss"],
                "boundary_s": [c["wall_s"] - c["epoch_s"]
                               for c in record["calls"]],
                "jax_cache": record["calls"][-1]["jax_cache"]}}
    if trace and record["trace"] and not rehearse:
        line["breakdown"] = breakdown(record)
    return line


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    ap.add_argument("--rehearse-cpu", action="store_true",
                    help="tiny sizes on the CPU; no metric, correct: false")
    args = ap.parse_args(argv)
    if args.rehearse_cpu:
        os.environ["JAX_PLATFORMS"] = "cpu"
    try:
        cell = manifest.cell(args.workload, rehearse=args.rehearse_cpu)
        record = measure(cell, args.seed, args.seconds, bool(args.trace),
                         args.rehearse_cpu)
        from jax._src import xla_bridge

        if xla_bridge.backends_are_initialized():
            raise NoResult("the driver process initialised a JAX backend")
        line = result_line(cell, record, bool(args.trace),
                           args.rehearse_cpu)
    except (NoResult, manifest.ManifestError) as e:
        print(f"benchmark/run.py: {e}", file=sys.stderr)
        return 3
    print(json.dumps(line), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
