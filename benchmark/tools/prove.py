"""Builder's tool: several runs of one cell in one call, as the
contract's proof asks (sets of runs, each run of a set with another
seed, the same seeds in both sets), with each metric's spread.

    chiprun -- python3 benchmark/tools/prove.py --workload gpt2s_epoch \
        --sets 2 --runs 6 --traced 1

Runs the manifest's command as child processes, one after another (this
parent never touches JAX, so each child's worker gets the chip), writes
every result line to ``chiprun_out/prove_<cell>.jsonl`` and prints the
medians and the spreads: the distance between the first and third
quartile of ``statistics.quantiles(values, n=4)`` as a share of the
median."""

from __future__ import annotations

import argparse
import json
import os
import signal
import statistics
import subprocess
import sys
import time

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
SEED0 = 2147483000   # large on purpose: the driver's seeds are


def spread(values) -> float:
    q = statistics.quantiles(values, n=4)
    return (q[2] - q[0]) / statistics.median(values)


def one_run(command, workload, seed, seconds, trace, env=None,
            timeout=1200.0):
    """One child process, its output kept under chiprun_out/logs/. A run
    that outlasts `timeout` is interrupted (so that it shuts its runtime
    down and says where it stood), then killed."""
    logs = os.path.join(ROOT, "chiprun_out", "logs")
    os.makedirs(logs, exist_ok=True)
    tag = f"{workload}_{seed}_{trace}_{int(time.time())}"
    out_path, err_path = (os.path.join(logs, f"{tag}.{x}")
                          for x in ("out", "err"))
    t0 = time.time()
    with open(out_path, "w") as out, open(err_path, "w") as err:
        child = subprocess.Popen(
            command + ["--workload", workload, "--seed", str(seed),
                       "--seconds", str(seconds), "--trace", str(trace)],
            cwd=ROOT, stdout=out, stderr=err,
            env=dict(os.environ, **(env or {})))
        try:
            child.wait(timeout=timeout)
        except subprocess.TimeoutExpired:
            child.send_signal(signal.SIGINT)
            try:
                child.wait(timeout=60)
            except subprocess.TimeoutExpired:
                child.kill()
                child.wait()
    wall = time.time() - t0
    with open(out_path) as f:
        lines = [x for x in f.read().splitlines() if x.startswith("{")]
    if child.returncode != 0 or not lines:
        with open(err_path) as f:
            sys.stderr.write(f"run failed rc={child.returncode} after "
                             f"{wall:.0f}s\n" + f.read()[-6000:])
        return None
    return dict(json.loads(lines[-1]), seed=seed, trace=trace,
                process_s=wall)


def main() -> int:
    ap = argparse.ArgumentParser()
    ap.add_argument("--workload", required=True)
    ap.add_argument("--sets", type=int, default=2)
    ap.add_argument("--runs", type=int, default=6)
    ap.add_argument("--traced", type=int, default=1)
    ap.add_argument("--seconds", type=float)
    ap.add_argument("--keep-trace", action="store_true")
    ap.add_argument("--run-timeout", type=float, default=1200.0)
    ap.add_argument("--keep-session", action="store_true",
                    help="runtime session files (worker logs) under "
                         "chiprun_out/ray_tmp")
    args = ap.parse_args()
    with open(os.path.join(ROOT, "BENCHMARK.json")) as f:
        bench = json.load(f)
    seconds = args.seconds or bench["run_seconds"]
    out_dir = os.path.join(ROOT, "chiprun_out")
    os.makedirs(out_dir, exist_ok=True)
    log = open(os.path.join(out_dir, f"prove_{args.workload}.jsonl"), "a")
    failed = 0

    def record(line, **tag):
        nonlocal failed
        if line is None:
            failed += 1
            return None
        log.write(json.dumps(dict(line, **tag)) + "\n")
        log.flush()
        return line

    base_env = ({"RAY_TPU_TMPDIR": os.path.join(out_dir, "ray_tmp")}
                if args.keep_session else {})
    sets = []
    for s in range(args.sets):
        values: dict = {}
        for r in range(args.runs):
            line = record(one_run(bench["command"], args.workload,
                                  SEED0 + r, seconds, 0, base_env,
                                  args.run_timeout), set=s, run=r)
            if line is None:
                continue
            for k, v in line["metrics"].items():
                values.setdefault(k, []).append(v["value"])
            print(json.dumps({
                "set": s, "run": r, "correct": line["correct"],
                "process_s": round(line["process_s"], 1),
                "calls": line["window"]["calls"],
                "boundary_s": [round(b, 3)
                               for b in line["window"]["boundary_s"]],
                "phases": {k: round(v, 2) for k, v in
                           line["window"]["phases"].items()},
                **{k: v["value"] for k, v in line["metrics"].items()}}),
                flush=True)
        sets.append(values)
    for s, values in enumerate(sets):
        for k, v in values.items():
            # a set's first run may compile: setup_s is judged without it
            v = v[1:] if k == "setup_s" and s == 0 and len(v) > 3 else v
            if len(v) >= 2:
                print(json.dumps({"set": s, "metric": k, "n": len(v),
                                  "median": statistics.median(v),
                                  "spread": spread(v), "values": v}),
                      flush=True)
    for r in range(args.traced):
        env = dict(base_env, **(
            {"BENCH_KEEP_TRACE": os.path.join(out_dir, "trace")}
            if args.keep_trace else {}))
        line = record(one_run(bench["command"], args.workload,
                              SEED0 + 100 + r, seconds, 1, env,
                              args.run_timeout), traced=r)
        if line is not None:
            print(json.dumps({k: line[k] for k in (
                "correct", "checks", "metrics", "device", "breakdown")
                if k in line}), flush=True)
    return 1 if failed else 0


if __name__ == "__main__":
    sys.exit(main())
