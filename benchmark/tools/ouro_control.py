"""Builder's tool: the ``ouro`` cell's reference tolerance held against
its two readings, through ``run.py``'s own comparison —
``sdar_control.py``'s method for a family whose forward pass walks its
layers several times and whose loss weighs the walks by an exit gate.

    chiprun -- python3 benchmark/tools/ouro_control.py \
        --workload ouro_d8_loop4_seq4k --control-seeds 2 <seed> <seed> ...

At the published widths and the timed sizes, in ONE process on the chip,
for every seed: ``program`` (the step-0 loss of the cell's own
``loss_fn`` at the seeded weights), ``reference`` (the family's float32
reference on the same weights and batch), and for the first
``--control-seeds`` seeds ``control`` (the reference with its BLOCKS in
bfloat16; the loss's softmax and sums and the gate in float32) and
``logits``: the distance of EACH WALK's logits of the batch's first
sequence from the float32 reference's, program and control.
``program_matches`` / ``control_matches`` are ``run.judge``'s
``matches_reference`` at the ``reference.rtol`` of the cell's file;
``nll``, ``exit_mass`` and ``entropy`` are the step's counters a token
(every walk's cross-entropy, the exit distribution's mass, its entropy:
what the loss weighed). For the first seed also ``faults``: the control
under each of the reference's ``MUTATIONS``, its loss against the
float32 reference through the same comparison (``caught``) and each
walk's logits' distance (a walk the mutation does not make reads null).
At seeded weights every walk's cross-entropy sits near log(vocabulary)
whatever the exit distribution is; it is the ``- beta H(p)`` term that
lets the step-0 loss see the gate. One JSON line a seed, and all of
them in ``chiprun_out/ouro_control_<cell>.json``."""

from __future__ import annotations

import argparse
import importlib
import json
import os
import sys
import time

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
sys.path.insert(0, ROOT)


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--workload", required=True)
    ap.add_argument("--control-seeds", type=int, default=2)
    ap.add_argument("--rehearse-cpu", action="store_true",
                    help="the cell's rehearsal sizes: the tool's own "
                         "plumbing, never a reading")
    ap.add_argument("seeds", type=int, nargs="+")
    args = ap.parse_args()
    if args.rehearse_cpu:
        os.environ["JAX_PLATFORMS"] = "cpu"

    import jax
    import jax.numpy as jnp

    from benchmark import manifest, run
    from benchmark.common import key_seed
    from benchmark.tools.reference_control import matches
    from ray_tpu.models import decoder

    cell = manifest.cell(args.workload, rehearse=args.rehearse_cpu)
    model, workload, family = cell["model"], cell["workload"], cell["family"]
    reference = importlib.import_module(
        f"benchmark.families.{model['family']}_reference")
    cfg = family.model_cfg(model)

    @jax.jit
    def apart(x, exact, head, exact_head):
        """The distance of the logits x head from exact exact_head."""
        with jax.default_matmul_precision("highest"):
            want = exact @ exact_head
        got = jnp.dot(x, head, preferred_element_type=jnp.float32)
        return jnp.linalg.norm(got - want) / jnp.linalg.norm(want)

    def walk_distances(normed, weights, exact, params):
        """Each walk's logits' distance; None for a walk `normed` lacks."""
        return [float(apart(normed[t], exact[t], weights["head"],
                            params["head"])) if t < len(normed) else None
                for t in range(len(exact))]

    forward = program_walks = None
    rows = []
    for i, seed in enumerate(args.seeds):
        t0 = time.time()
        p = family.pieces(model, workload, seed)
        init = p.model_init(jax.random.key(key_seed(seed)))
        params, state = init
        if forward is None:
            forward = jax.jit(lambda w, s, b: p.loss_fn(w, s, b))
            program_walks = jax.jit(
                lambda w, t: decoder.hidden(w, t, cfg)[0][:, 0])
        program, after = forward(params, state, p.batch)
        program = float(program)
        want = reference.loss(init, p.batch, model)
        counters = {k: float(v) for k, v in after["epoch_counters"].items()}
        n = counters["loop_targets"]
        walks = range(1, cfg.loops + 1)
        row = {"seed": seed, "rtol": workload["reference"]["rtol"],
               "program": program, "reference": want,
               "program_rel": abs(program - want) / abs(want),
               "program_matches": matches(run, cell, program, want),
               "nll": [counters[f"loop_nll_{t}"] / n for t in walks],
               "exit_mass": [counters[f"exit_mass_{t}"] / n for t in walks],
               "entropy": counters["exit_entropy"] / n}
        if i < args.control_seeds:
            low = reference.loss(init, p.batch, model, dtype=jnp.bfloat16)
            row.update(control=low,
                       control_rel=abs(low - want) / abs(want),
                       control_matches=matches(run, cell, low, want))
            half = jax.tree.map(lambda x: x.astype(jnp.bfloat16), params)
            first = p.batch[0]
            with jax.default_matmul_precision("highest"):
                exact, _ = reference.walks(params, first, model)
            row["logits"] = {
                "program": walk_distances(
                    list(program_walks(params, p.batch[:1])), half, exact,
                    params),
                "control": walk_distances(
                    reference.walks(half, first, model)[0], half, exact,
                    params)}
            if i == 0:
                row["faults"] = {}
                for name in reference.MUTATIONS:
                    wrong = reference.loss(init, p.batch, model,
                                           dtype=jnp.bfloat16, mutate=name)
                    row["faults"][name] = {
                        "loss_rel": abs(wrong - want) / abs(want),
                        "caught": not matches(run, cell, wrong, want),
                        "logits": walk_distances(
                            reference.walks(half, first, model, name)[0],
                            half, exact, params)}
            del exact, half
        row["s"] = round(time.time() - t0, 1)
        print(json.dumps(row), flush=True)
        rows.append(row)
        del init, params, state, p
    out = os.path.join(ROOT, "chiprun_out")
    os.makedirs(out, exist_ok=True)
    with open(os.path.join(
            out, f"ouro_control_{args.workload}.json"), "w") as f:
        json.dump(rows, f, indent=1)


if __name__ == "__main__":
    main()
