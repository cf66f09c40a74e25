"""Scale-sim: many spoofed raylets against a REAL control plane on one box.

Control-plane scalability can't be measured honestly on a small box by
spawning a real cluster — worker processes eat the budget before the GCS
is ever the bottleneck. This harness spawns only the control plane
itself (director + store shards, the same processes a real cluster
runs), then drives it from spoofed raylets spread over `client_procs`
worker PROCESSES: each sim raylet owns a director connection plus the
shard-routing client (gcs/client.py), a partition of synthetic object
ids it "hosts", and a seeded, PRE-GENERATED op stream shaped like the
real steady state (object-directory add/remove/batched lookups + KV —
the PR 5/6 hot ops). Multiple client processes matter: a single driving
process is itself GIL-bound and would measure the harness, not the
plane; with several, the single-director arm saturates its one event
loop (one core, ever) while the sharded arm's N processes keep scaling —
which is precisely the claim under test.

Two rate metrics, per-second over paired interleaved windows (the
MICROBENCH discipline — both arms live simultaneously, every window runs
each arm once on a shared wall-clock timetable, median over windows):

- **gcs ops**: the mixed table-op stream, summed across sim raylets;
- **scheduler decisions**: one decision = the owner-side locality pick a
  raylet/driver makes per task burst — a batched location lookup over
  the task's args, argmax-bytes node choice, then registering the result
  object's location (2 table round trips of real scheduler shape).

Plus the **director-bypass** counter-check: per-arm server CPU sampled
from /proc (director + every shard) and normalized per issued op. The
sharded arm must drive its steady-state stream AROUND the director
(`director_cpu_us_per_op` collapsing toward 0, `director_bypass_ratio`
« 1) — that is the property that removes the single-process ceiling.
NOTE the wall-clock aggregate rates only exceed the legacy arm when the
box has >= shards+2 cores: on smaller boxes every process timeshares the
same cores and the sharded plane's extra per-tick syscalls (4 sockets
where the legacy arm coalesces onto 1) dominate the measurement — the
rates stay honest, the bypass ratio carries the scaling claim.

Fault story (the chaos-sweep analogs, runnable without a cluster):

- `kill_shard=True` SIGKILLs a seeded store shard MID-window and
  restarts it on its fixed port against its journal; every acked KV
  write is verified readable afterwards (zero lost acked ops — clients
  ride rpc.ReconnectingConnection retry, exactly like real processes);
- at teardown the same shard is quiesced, snapshotted (canonical bytes),
  killed, restarted, and snapshotted again — journal replay must restore
  the tables BIT-IDENTICAL (`replay_identical`).
"""

from __future__ import annotations

import asyncio
import json
import os
import random
import shutil
import statistics
import subprocess
import sys
import time

from ray_tpu._private import rpc
from ray_tpu._private.config import Config
from ray_tpu._private.node import (
    new_session_dir,
    start_gcs,
    start_gcs_shard,
    start_gcs_shards,
)
from ray_tpu.gcs.client import GcsClient

OP_BATCH_LOOKUP = 16  # oids per batched directory lookup
DECISION_ARGS = 3     # plasma args per simulated task's locality pick
                      # (a real lease request carries 1-4, PR 5)


class ControlPlane:
    """One live control plane (director + `shards` store shards) in its
    own session dir. shards=1 spawns NO shard processes — the legacy
    single-GCS layout, byte-identical to today's clusters."""

    def __init__(self, shards: int, label: str = "plane"):
        self.label = label
        self.shards = shards
        self.config = Config.load({"gcs_shards": shards})
        self.session_dir = new_session_dir()
        self.shard_procs, self.shard_addresses = start_gcs_shards(
            self.session_dir, self.config)
        self.gcs_svc, self.gcs_address = start_gcs(
            self.session_dir, self.config,
            shard_addresses=self.shard_addresses)

    def cpu_seconds(self) -> dict[str, float]:
        """Cumulative CPU (utime+stime) per control-plane process, from
        /proc — the director-bypass counter-check: in the sharded arm the
        director must burn ~no CPU per steady-state op."""
        out = {}
        ticks = os.sysconf("SC_CLK_TCK")
        procs = [("director", self.gcs_svc)] + [
            (f"shard{i}", svc) for i, svc in enumerate(self.shard_procs)]
        for name, svc in procs:
            try:
                with open(f"/proc/{svc.proc.pid}/stat") as f:
                    parts = f.read().rsplit(") ", 1)[1].split()
                out[name] = (int(parts[11]) + int(parts[12])) / ticks
            except (OSError, IndexError, ValueError):
                out[name] = 0.0
        return out

    def kill_shard(self, index: int):
        self.shard_procs[index].kill()

    def restart_shard(self, index: int):
        old = self.shard_procs[index]
        svc, _addr = start_gcs_shard(self.session_dir, self.config, index,
                                     port=old.shard_port)
        self.shard_procs[index] = svc

    def kill_director(self):
        self.gcs_svc.kill()

    def restart_director(self):
        port = int(self.gcs_address.rsplit(":", 1)[1])
        self.gcs_svc, _addr = start_gcs(
            self.session_dir, self.config, port=port,
            shard_addresses=self.shard_addresses)

    def close(self, remove_dir: bool = True):
        for svc in [self.gcs_svc, *self.shard_procs]:
            try:
                svc.kill()
            except Exception:
                pass
        if remove_dir:
            shutil.rmtree(self.session_dir, ignore_errors=True)


def sim_node_ids(raylets: int) -> list[bytes]:
    return [bytes([i % 256, i // 256]) * 8 for i in range(raylets)]


def sim_pool(seed: int, idx: int, pool_size: int) -> list[bytes]:
    """Client idx's hosted object ids — derived purely from (seed, idx)
    so every worker process recomputes every client's pool with no IPC."""
    rng = random.Random(seed * 7919 + idx)
    return [rng.randbytes(16) for _ in range(pool_size)]


class SimRaylet:
    """One spoofed raylet: a director connection + shard-routing client,
    a pool of object ids it hosts, and a seeded op stream."""

    def __init__(self, idx: int, seed: int, raylets: int, pool_size: int):
        self.idx = idx
        self.rng = random.Random(seed * 104729 + idx)
        self.node_ids = sim_node_ids(raylets)
        self.node_id = self.node_ids[idx % len(self.node_ids)]
        self.pool = sim_pool(seed, idx, pool_size)
        self.shared_pool = [oid for i in range(raylets)
                            for oid in sim_pool(seed, i, pool_size)]
        self.acked_kv: dict[str, bytes] = {}
        self._kv_seq = 0
        self.gcs: GcsClient | None = None

    async def connect(self, gcs_address: str, config: Config,
                      uds_dir: str | None = None):
        director = rpc.ReconnectingConnection(
            rpc.prefer_uds(gcs_address, uds_dir),
            name=f"sim{self.idx}", retry_timeout=30.0)
        self.gcs = GcsClient(director, config, uds_dir=uds_dir)
        await self.gcs.ensure_connected()

    async def seed_locations(self):
        for oid in self.pool:
            await self.gcs.call("add_object_location", {
                "object_id": oid, "node_id": self.node_id,
                "size": self.rng.randrange(1 << 10, 1 << 20)})

    async def close(self):
        if self.gcs is not None:
            await self.gcs.close()

    # -- the workloads -------------------------------------------------
    # Op payloads are pre-generated OUTSIDE the timed slice (issue_* just
    # pops and sends): the subject under test is the control plane, not
    # the harness's rng.

    def gen_ops(self, n: int) -> list[tuple[str, dict, str | None]]:
        """Pre-generate `n` steps of the steady-state table-op mix: the
        per-object seal/free directory stream every raylet emits (PR 5 —
        single-key adds/removes, the hottest op class by count), a
        single-key lookup tail, and KV traffic. Batched lookups are
        measured by the DECISION metric, not here. Each entry:
        (method, payload, acked_kv_key)."""
        ops = []
        for _ in range(n):
            r = self.rng.random()
            if r < 0.40:
                ops.append(("add_object_location", {
                    "object_id": self.rng.choice(self.pool),
                    "node_id": self.rng.choice(self.node_ids),
                    "size": self.rng.randrange(1 << 10, 1 << 20)}, None))
            elif r < 0.55:
                ops.append(("remove_object_location", {
                    "object_id": self.rng.choice(self.pool),
                    "node_id": self.rng.choice(self.node_ids)}, None))
            elif r < 0.70:
                ops.append(("get_object_locations", {
                    "object_id": self.rng.choice(self.shared_pool)},
                    None))
            elif r < 0.85:
                self._kv_seq += 1
                key = f"sim:{self.idx}:{self._kv_seq}"
                ops.append(("kv_put", {"key": key,
                                       "value": self.rng.randbytes(64)},
                            key))
            else:
                ops.append(("kv_get", {
                    "key": f"sim:{self.idx}:"
                           f"{self.rng.randrange(1, self._kv_seq + 2)}"},
                    None))
        return ops

    async def issue_op(self, op):
        method, payload, kv_key = op
        if method in ("add_object_location", "remove_object_location"):
            # Directory updates are PIPELINED in the real raylet
            # (raylet._register_location: best-effort, issued from a
            # spawned task per seal, errors swallowed) — model them as
            # notify()s; the 45% call mix paces them and the post-slice
            # barrier() proves the server drained every one.
            await self.gcs.notify(method, payload)
            return
        await self.gcs.call(method, payload)
        if kv_key is not None:
            # the call returned => the plane acked it: it must survive
            # any later shard kill (journal replay)
            self.acked_kv[kv_key] = payload["value"]

    def gen_decisions(self, n: int) -> list[list[bytes]]:
        return [[self.rng.choice(self.shared_pool)
                 for _ in range(DECISION_ARGS)] for _ in range(n)]

    async def issue_decision(self, args: list[bytes]):
        """One owner-side scheduling decision: locality-pick the node
        holding the most argument bytes (the PR 5 lease-targeting
        lookup), then register the result object's location there."""
        locs = await self.gcs.call("get_object_locations_batch",
                                   {"object_ids": args})
        by_node: dict[bytes, int] = {}
        for rec in (locs or {}).values():
            for nid in rec["nodes"]:
                by_node[nid] = by_node.get(nid, 0) + int(rec["size"])
        best = (max(by_node, key=by_node.get) if by_node
                else self.node_id)
        await self.gcs.call("add_object_location", {
            "object_id": args[0][::-1], "node_id": best,
            "size": 1 << 12})


def build_schedule(windows: int, arms: list[str]) -> list[dict]:
    """The shared wall-clock timetable every worker process follows:
    window w runs every (kind, arm) slice once, arms interleaved inside
    the window so box-load swings hit both equally."""
    slices = []
    for w in range(windows):
        for kind in ("ops", "decisions"):
            for arm in arms:
                slices.append({"index": len(slices), "window": w,
                               "kind": kind, "arm": arm})
    return slices


async def _shard_snapshot(address: str) -> dict:
    conn = await rpc.connect(address, name="scalesim-snap", timeout=10.0)
    try:
        return await conn.call("shard_snapshot", {}, timeout=10.0)
    finally:
        await conn.close()


def _stat(samples: list[float]) -> dict:
    return {"median": round(statistics.median(samples), 2),
            "samples": [round(s, 2) for s in samples]}


def run_scalesim(shards: int = 4, raylets: int = 16, windows: int = 5,
                 window_s: float = 1.0, seed: int = 0,
                 kill_shard: bool = False, legacy_arm: bool = True,
                 pool_size: int = 32, out: str | None = None,
                 keep_dirs: bool = False, client_procs: int = 3,
                 streams: int = 8, gap_s: float = 0.3) -> dict:
    """Run the scale-sim. Returns (and optionally writes) a result dict
    with per-arm `gcs_ops_per_s` / `decisions_per_s` medians over
    `windows` paired interleaved windows, speedups, and — with
    `kill_shard` — the zero-lost-acked-ops + bit-identical-replay
    verdicts for a seeded mid-window shard kill."""
    rng = random.Random(seed)
    planes = [ControlPlane(shards, label=f"shards{shards}")]
    if legacy_arm:
        planes.append(ControlPlane(1, label="shards1"))
    arm_labels = [p.label for p in planes]
    victim = rng.randrange(max(1, shards)) if shards > 1 else 0
    schedule = build_schedule(windows, arm_labels)
    persist = planes[0].config.gcs_persistence

    result: dict = {
        "shards": shards, "raylets": raylets, "windows": windows,
        "window_s": window_s, "seed": seed, "client_procs": client_procs,
        "arms": {}, "kill": None,
    }

    workdir = os.path.join(planes[0].session_dir, "scalesim")
    os.makedirs(workdir, exist_ok=True)
    go_path = os.path.join(workdir, "go")
    cfg = {
        "planes": {p.label: {"gcs_address": p.gcs_address,
                             "shards": p.shards,
                             "uds_dir": os.path.join(p.session_dir, "sock")}
                   for p in planes},
        "raylets": raylets, "pool_size": pool_size, "seed": seed,
        "schedule": schedule, "window_s": window_s, "gap_s": gap_s,
        "go_path": go_path, "verify_arm": arm_labels[0],
        "streams": streams,
    }
    cfg_path = os.path.join(workdir, "config.json")
    with open(cfg_path, "w") as f:
        json.dump(cfg, f)

    # spread sim raylets over worker processes
    assign = [[] for _ in range(client_procs)]
    for i in range(raylets):
        assign[i % client_procs].append(i)

    procs = []
    out_paths = []
    try:
        for w, indices in enumerate(assign):
            if not indices:
                continue
            res_path = os.path.join(workdir, f"worker{w}.json")
            out_paths.append(res_path)
            log = open(os.path.join(workdir, f"worker{w}.log"), "w")
            procs.append((subprocess.Popen(
                [sys.executable, "-m", "ray_tpu.scalesim.worker",
                 "--config", cfg_path, "--out", res_path,
                 "--clients", ",".join(map(str, indices))],
                stdout=log, stderr=log,
                env={**os.environ,
                     "PYTHONPATH": os.pathsep.join(
                         [os.path.dirname(os.path.dirname(
                             os.path.dirname(os.path.abspath(__file__)))),
                          os.environ.get("PYTHONPATH", "")])}), log))

        # barrier: workers connect + seed their pools, then touch .ready
        deadline = time.monotonic() + 60
        for res_path in out_paths:
            while not os.path.exists(res_path + ".ready"):
                for p, _log in procs:
                    if p.poll() is not None:
                        raise RuntimeError(
                            f"scalesim worker died during setup "
                            f"(see {workdir})")
                if time.monotonic() > deadline:
                    raise TimeoutError("scalesim workers not ready in 60s")
                time.sleep(0.05)

        t0 = time.time() + 0.5
        cpu_before = {p.label: p.cpu_seconds() for p in planes}
        with open(go_path + ".tmp", "w") as f:
            f.write(str(t0))
        os.rename(go_path + ".tmp", go_path)

        kill_info = None
        if kill_shard and shards > 1 and persist:
            # SIGKILL the victim shard halfway through the middle
            # window's sharded ops slice, restart on its fixed port
            kill_slice = next(
                s for s in schedule
                if s["window"] == windows // 2 and s["kind"] == "ops"
                and s["arm"] == arm_labels[0])
            t_kill = (t0 + kill_slice["index"] * (window_s + gap_s)
                      + window_s / 2)
            time.sleep(max(0.0, t_kill - time.time()))
            tk = time.perf_counter()
            planes[0].kill_shard(victim)
            planes[0].restart_shard(victim)
            kill_info = {"victim_shard": victim,
                         "window": kill_slice["window"],
                         "restart_s": round(time.perf_counter() - tk, 3)}

        total_s = len(schedule) * (window_s + gap_s) + 30
        for p, log in procs:
            p.wait(timeout=max(60.0, t0 + total_s - time.time()))
            log.close()
            if p.returncode != 0:
                raise RuntimeError(
                    f"scalesim worker exited rc={p.returncode} "
                    f"(see {workdir})")

        cpu_after = {p.label: p.cpu_seconds() for p in planes}
        counts: dict[tuple, float] = {}
        elapsed: dict[tuple, float] = {}
        acked: dict[str, bytes] = {}
        for res_path in out_paths:
            with open(res_path) as f:
                rec = json.load(f)
            for arm, kind, w, n, dt in rec["counts"]:
                counts[(arm, kind, w)] = counts.get((arm, kind, w), 0) + n
                elapsed[(arm, kind, w)] = max(
                    elapsed.get((arm, kind, w), 0.0), dt)
            for k, v in rec["acked"].items():
                acked[k] = bytes.fromhex(v)

        async def _post():
            nonlocal kill_info
            plane = planes[0]
            director = rpc.ReconnectingConnection(
                plane.gcs_address, name="scalesim-verify")
            client = GcsClient(director, plane.config)
            if plane.shards > 1:
                # director bypass as a COUNT: every acked kv write was
                # routed to a shard, so asked directly (not through the
                # routing client) the director's own table holds none
                held = 0
                for key in acked:
                    held += await director.call(
                        "kv_get", {"key": key}) is not None
                result["director_bypass"] = {"acked_kv_writes": len(acked),
                                             "held_by_director": held}
            if kill_info is not None:
                # zero lost acked ops: every kv write a worker got an
                # ack for must read back its value post-restart
                checked = 0
                for key, value in acked.items():
                    got = await client.call("kv_get", {"key": key})
                    if got != value:
                        raise AssertionError(
                            f"acked op lost: kv[{key!r}] read back "
                            f"{'missing' if got is None else 'wrong'} "
                            f"after shard kill")
                    checked += 1
                kill_info["acked_ops_verified"] = checked
                kill_info["lost_ops"] = 0
            await client.close()
            # teardown replay check: quiesced canonical snapshot ->
            # kill -> journal-replay restart -> BIT-IDENTICAL snapshot
            # (meaningless without a journal: gcs_persistence=False
            # restarts a shard empty by design)
            if planes[0].shards > 1 and persist:
                addr = planes[0].shard_addresses[victim]
                before = await _shard_snapshot(addr)
                planes[0].kill_shard(victim)
                await asyncio.to_thread(planes[0].restart_shard, victim)
                after = await _shard_snapshot(addr)
                if kill_info is None:
                    kill_info = {"victim_shard": victim}
                kill_info["replay_identical"] = (
                    before["state"] == after["state"])
                if not kill_info["replay_identical"]:
                    raise AssertionError(
                        f"shard {victim} journal replay diverged from "
                        f"its pre-kill tables ({len(before['state'])} vs "
                        f"{len(after['state'])} canonical bytes)")

        asyncio.run(_post())

        def _rate(label, kind, w):
            return (counts.get((label, kind, w), 0)
                    / max(elapsed.get((label, kind, w), window_s),
                          window_s))

        for label in arm_labels:
            # director-bypass counter-check: CPU the plane's processes
            # burned across this arm's slices (they idle during the other
            # arm's), normalized per issued table op (a decision ≈ 2 ops:
            # one batched lookup + one location add). In the sharded arm
            # the steady-state stream must route AROUND the director —
            # its CPU/op collapses toward zero, which is the property
            # that removes the single-process ceiling (the wall-clock
            # aggregate only shows it with >= shards+2 cores; see
            # MICROBENCH control_plane notes).
            dcpu = {k: cpu_after[label][k] - cpu_before[label].get(k, 0.0)
                    for k in cpu_after[label]}
            n_ops = sum(counts.get((label, "ops", w), 0)
                        for w in range(windows))
            n_dec = sum(counts.get((label, "decisions", w), 0)
                        for w in range(windows))
            issued = max(n_ops + 2 * n_dec, 1)
            result["arms"][label] = {
                "gcs_ops_per_s": _stat(
                    [_rate(label, "ops", w) for w in range(windows)]),
                "decisions_per_s": _stat(
                    [_rate(label, "decisions", w)
                     for w in range(windows)]),
                "server_cpu_s": {k: round(v, 3) for k, v in dcpu.items()},
                "director_cpu_us_per_op": round(
                    dcpu.get("director", 0.0) / issued * 1e6, 2),
            }
        result["kill"] = kill_info
        result["cores"] = os.cpu_count()
        if legacy_arm:
            a = result["arms"][arm_labels[0]]
            b = result["arms"]["shards1"]
            result["director_bypass_ratio"] = round(
                a["director_cpu_us_per_op"]
                / max(b["director_cpu_us_per_op"], 1e-9), 4)
    finally:
        for p, _log in procs:
            if p.poll() is None:
                p.kill()
        for plane in planes:
            plane.close(remove_dir=not keep_dirs)

    if legacy_arm:
        a = result["arms"][arm_labels[0]]
        b = result["arms"]["shards1"]
        result["speedup_gcs_ops"] = round(
            a["gcs_ops_per_s"]["median"]
            / max(b["gcs_ops_per_s"]["median"], 1e-9), 2)
        result["speedup_decisions"] = round(
            a["decisions_per_s"]["median"]
            / max(b["decisions_per_s"]["median"], 1e-9), 2)
    if out:
        with open(out, "w") as f:
            json.dump(result, f, indent=1)
    return result
