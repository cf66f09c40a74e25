"""Builder's tool: a step's device time by ``jax.named_scope``, from a
kept trace and the step's compiled text.

    XLA_FLAGS="--xla_dump_to=<dir> --xla_dump_hlo_as_text" \\
    BENCH_KEEP_TRACE=<dir> python3 benchmark/run.py ... --trace 1
    python3 benchmark/tools/scope_share.py <xplane.pb> <hlo dir or file> \\
        mixer_delta attention_full experts ...

The trace names an operation ``fusion.234`` and nothing more; the
compiled text (``*after_optimizations.txt``: of a directory the largest,
the step's) carries each instruction's ``op_name``, the scopes it was
traced under (``.../checkpoint/mixer_delta/dot_general``, ``transpose(
jvp(mixer_delta))`` on the way back). An operation's own time
(``trace_reduce.union_and_self``) goes to the FIRST scope of the command
line its ``op_name`` holds, else to ``unnamed``; only operations that ran
inside the step's module (the trace's ``XLA Modules`` line) are counted,
so the call's boundary is left out. A fusion has ONE ``op_name``, its
root's: where XLA fused across a scope's edge the time goes to one side.
Prints one JSON object: shares of the step's busy time."""

from __future__ import annotations

import bisect
import glob
import json
import os
import re
import sys

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__)))))

from benchmark import trace_reduce  # noqa: E402

_INSTRUCTION = re.compile(
    r'^\s*(?:ROOT\s+)?%?([\w.\-]+)\s*=.*?metadata=\{[^}]*?op_name="([^"]*)"')
_MODULE = re.compile(r"^HloModule\s+([\w.\-]+)")


def step_text(path: str) -> str:
    """The compiled text: a file, or of a dump directory the largest
    ``*after_optimizations.txt``."""
    if os.path.isdir(path):
        path = max(glob.glob(os.path.join(path, "*after_optimizations.txt")),
                   key=os.path.getsize)
    with open(path) as f:
        return f.read()


def op_names(text: str) -> tuple[str, dict[str, str]]:
    """(the module's name, {instruction: op_name}) of a compiled text."""
    module = _MODULE.search(text)
    found = (_INSTRUCTION.match(line) for line in text.splitlines())
    return module.group(1) if module else "", {
        m.group(1): m.group(2) for m in found if m}


def scope_of(op_name: str, scopes) -> str:
    return next((s for s in scopes if s in op_name), "unnamed")


def shares(plane, module: str, names: dict, scopes) -> dict | None:
    lines = {line.name: line for line in plane.lines}
    if trace_reduce.OPS_LINE not in lines:
        return None
    modules = lines.get(trace_reduce.MODULES_LINE)
    runs = sorted((e.start_ns, e.start_ns + e.duration_ns)
                  for e in (modules.events if modules else ())
                  if e.name.split("(")[0] == module)
    starts = [r[0] for r in runs]

    def in_step(at):
        i = bisect.bisect_right(starts, at) - 1
        return not runs or (i >= 0 and at < runs[i][1])

    intervals = [
        (e.start_ns, e.start_ns + e.duration_ns,
         trace_reduce.short_name(e.name))
        for e in lines[trace_reduce.OPS_LINE].events
        if e.duration_ns > 0 and in_step(e.start_ns)]
    busy, own, _ = trace_reduce.union_and_self(intervals)
    by_scope, unnamed = dict.fromkeys(tuple(scopes) + ("unnamed",), 0), {}
    for op, t in own.items():
        scope = scope_of(names.get(op, ""), scopes)
        by_scope[scope] += t
        if scope == "unnamed":
            unnamed[op] = t
    top = sorted(unnamed.items(), key=lambda kv: -kv[1])[:8]
    return {
        "module": module, "module_runs": len(runs), "busy_s": busy / 1e9,
        "share": {k: v / busy for k, v in by_scope.items()},
        "ops_without_op_name": sum(1 for op in own if op not in names),
        "unnamed_top": [(op, t / busy, names.get(op, "")[-80:])
                        for op, t in top]}


def main():
    from jax.profiler import ProfileData

    trace, text, *scopes = sys.argv[1:]
    module, names = op_names(step_text(text))
    data = ProfileData.from_file(trace)
    for plane in data.planes:
        if trace_reduce._DEVICE_PLANE.match(plane.name):
            found = shares(plane, module, names, scopes)
            if found:
                print(json.dumps(found))


if __name__ == "__main__":
    main()
