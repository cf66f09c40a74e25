"""The program's own account of set-up: the span tree of the run's one
worker-group start (``ray_tpu.train.start_log()``, root ``train.start``)
and of its first ``train()`` call (``ray_tpu.train.call_log()[0]``, root
``train.call``), each held against the run's own clock around the same
stretch (``phases.worker_start_s``, ``first["wall_s"]``). The readers
``worker_spawn_s``, ``chip_wait_s``, ``backend_init_s``, ``state_init_s``,
``worker_start_named_share``, ``first_step_trace_s``,
``first_step_load_s`` and ``first_step_named_share`` share this file. A
program without the log or the spans (the parent of the PR that added
them) gives None everywhere, and so does a tree whose root and the
run's clock part by more than 1 %."""

from __future__ import annotations

from benchmark.span_log import covered

MATCH_SHARE = 0.01  # a root's seconds against the run's clock around it
# the leaf spans of a start; with the stretch `worker_spawn_s` names
# (root start -> `worker.boot` end) they should tile `train.start`
START_LEAVES = ("worker.chip_wait", "worker.actor_init",
                "train.setup.backend", "train.setup.user",
                "train.setup.init", "train.setup.place")
# ... and of a first call: what is neither is hops and the epoch's loop
FIRST_CALL_NAMED = ("compile.fingerprint", "compile.lookup", "compile.load",
                    "compile.export", "jax.compile", "train.sync",
                    "train.snapshot")


def named(entry, *names) -> list:
    return [s for s in entry["spans"] if s["name"] in names]


def seconds(spans) -> float:
    return sum(s["end"] - s["start"] for s in spans)


def _matched(entry, root_name, clock_s) -> dict | None:
    """`entry`, if its one root `root_name` lasts what the run's clock
    around it read, to 1 %."""
    roots = named(entry, root_name)
    if len(roots) != 1 or not clock_s:
        return None
    if abs(seconds(roots) - clock_s) > MATCH_SHARE * clock_s:
        return None
    return entry


def start_entry(host) -> dict | None:
    """The tree of the run's worker start: the log's one entry (the
    run's Trainer never restarts its group: ``max_retries=0``)."""
    try:
        from ray_tpu.train import start_log
    except ImportError:
        return None
    log = start_log()
    if len(log) != 1:
        return None
    return _matched(log[0], "train.start",
                    host["phases"].get("worker_start_s"))


def first_call_entry(host) -> dict | None:
    """The tree of the run's first ``train()`` call — inside
    ``first_step_s`` — if the program records a resolution's parts
    (a ``compile.lookup`` span, or ``backend_s`` on ``jax.compile``)."""
    try:
        from ray_tpu.train import call_log
    except ImportError:
        return None
    log = call_log()
    if not log or host["attempted"] != len(log) or "first" not in host:
        return None     # the ring has dropped the first call
    entry = _matched(log[0], "train.call", host["first"]["wall_s"])
    if entry is None or not (named(entry, "compile.lookup") or any(
            "backend_s" in s["attrs"] for s in named(entry, "jax.compile"))):
        return None
    return entry


def span_seconds(entry, *names) -> float | None:
    """Σ seconds of the entry's spans of these names; None with none."""
    spans = named(entry, *names) if entry else []
    return seconds(spans) if spans else None


def attr_sum(entry, name, *attrs) -> float:
    """Σ over the spans called `name` of these attributes (absent: 0)."""
    return sum(s["attrs"].get(a, 0.0) for s in named(entry, name)
               for a in attrs)


def named_share(entry, root_name, names, more=()) -> float | None:
    """The share of the root's seconds inside the union of the spans
    called `names` and the intervals `more`, in percent."""
    (root,) = named(entry, root_name)
    lo, hi = root["start"], root["end"]
    if hi <= lo:
        return None
    inside = [(s["start"], s["end"]) for s in named(entry, *names)]
    return 100.0 * covered(inside + list(more), lo, hi) / (hi - lo)


def spawn_interval(entry) -> tuple[float, float] | None:
    """`train.start`'s start to the last `worker.boot`'s end: actor
    scheduling, the lease, the raylet's `Popen`, the interpreter and the
    imports, registration. None without `worker.boot`."""
    boots = named(entry, "worker.boot")
    if not boots:
        return None
    (root,) = named(entry, "train.start")
    return root["start"], max(s["end"] for s in boots)
