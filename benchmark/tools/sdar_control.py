"""Builder's tool: the ``sdar`` cell's reference tolerance held against
its two readings, through ``run.py``'s own comparison —
``reference_control.py``'s method for a family whose loss draws noise
(its state carries the noise's seed and step, not a selection bias).

    chiprun -- python3 benchmark/tools/sdar_control.py \
        --workload sdar_ep8_seq4k --control-seeds 3 <seed> <seed> ...

At the published widths and the timed sizes, in ONE process on the chip,
for every seed: ``program`` (the step-0 loss of the cell's own
``loss_fn`` at the seeded weights and the step-0 noise), ``reference``
(the family's float32 reference on the same weights, batch and noise),
and for the first ``--control-seeds`` seeds ``control`` (the reference
with its BLOCKS in bfloat16, the loss's softmax and sums in float32) and
``logits``: the distance of the noised half's logits of the batch's
first sequence from the float32 reference's, program and control.
``program_matches`` / ``control_matches`` are ``run.judge``'s
``matches_reference`` at the ``reference.rtol`` of the cell's file;
``masked`` and ``weight_max`` say what the noise gave the loss to weigh.
Beside the control, what a FAULT reads at these sizes: ``lower`` (the
control with the router's product in bfloat16 too: below the stated
precision), and ``faults``: the control under each of the reference's
``MUTATIONS`` (a wrong mask, a wrong loss), its loss against the
float32 reference through the same comparison (``caught``) and its
logits' distance. ``noise_steps`` is the state's ``noise_step`` after
the program's loss ran once and twice, ``second_loss`` the loss under
the second step's noise at the same weights.
One JSON line a seed, and all of them in
``chiprun_out/sdar_control_<cell>.json``."""

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
    ap.add_argument("--control-seeds", type=int, default=3)
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
    from benchmark.tools.reference_control import distance, matches
    from ray_tpu.models import decoder

    cell = manifest.cell(args.workload, rehearse=args.rehearse_cpu)
    model, workload, family = cell["model"], cell["workload"], cell["family"]
    reference = importlib.import_module(
        f"benchmark.families.{model['family']}_reference")
    cfg = family.model_cfg(model)
    length = workload["seq"]
    forward = program_logits = None
    rows = []
    for i, seed in enumerate(args.seeds):
        t0 = time.time()
        p = family.pieces(model, workload, seed)
        init = p.model_init(jax.random.key(key_seed(seed)))
        params, state = init
        if forward is None:
            forward = jax.jit(lambda w, s, b: p.loss_fn(w, s, b))
            program_logits = jax.jit(
                lambda w, t: decoder.apply(w, t, cfg)[0, length:])
        program, after = forward(params, state, p.batch)
        program = float(program)
        second, later = forward(params, after, p.batch)
        want = reference.loss(init, p.batch, model)
        counters = after["epoch_counters"]
        row = {"seed": seed, "rtol": workload["reference"]["rtol"],
               "program": program, "reference": want,
               "program_rel": abs(program - want) / abs(want),
               "program_matches": matches(run, cell, program, want),
               "masked": float(counters["diffusion_masked"]),
               "weight_max": float(counters["diffusion_weight_max"]),
               "noise_steps": [int(after["noise_step"]),
                               int(later["noise_step"])],
               "second_loss": float(second)}
        if i < args.control_seeds:
            low = reference.loss(init, p.batch, model, dtype=jnp.bfloat16)
            row.update(control=low,
                       control_rel=abs(low - want) / abs(want),
                       control_matches=matches(run, cell, low, want))
            noised, masked, rate = reference.noise(
                p.batch, state["noise_seed"], state["noise_step"],
                model["block_length"], model["vocab_size"] - 1)
            half = jax.tree.map(lambda x: x.astype(jnp.bfloat16), params)

            def logits(weights, cfg_=model, mutate=""):
                out = jax.jit(lambda w, c, n: reference.forward(
                    w, c, n, cfg_, mutate))(weights, p.batch[0], noised[0])
                return out[-length:].astype(jnp.float32)

            def low_loss(cfg_, mutate=""):
                fn = jax.jit(lambda w, *r: reference.weighted_nll(
                    w, *r, cfg_, mutate))
                return sum(float(fn(half, p.batch[j], noised[j], masked[j],
                                    rate[j]))
                           for j in range(p.batch.shape[0])) / p.batch.size

            with jax.default_matmul_precision("highest"):
                exact = logits(params)
            got = program_logits(params, jnp.concatenate(
                [p.batch[:1], noised[:1]], axis=1))
            row["logits"] = {"program": distance(got, exact),
                             "control": distance(logits(half), exact)}
            lower = dict(model, router_dtype="bfloat16")
            below = low_loss(lower)
            row["lower"] = {"loss_rel": abs(below - want) / abs(want),
                            "caught": not matches(run, cell, below, want),
                            "logits": distance(logits(half, lower), exact)}
            if i == 0:
                row["faults"] = {}
                for name in reference.MUTATIONS:
                    wrong = low_loss(model, name)
                    row["faults"][name] = {
                        "loss_rel": abs(wrong - want) / abs(want),
                        "caught": not matches(run, cell, wrong, want),
                        "logits": distance(logits(half, model, name), exact)}
            del exact, got, half
        row["s"] = round(time.time() - t0, 1)
        print(json.dumps(row), flush=True)
        rows.append(row)
        del init, params, state, p
    out = os.path.join(ROOT, "chiprun_out")
    os.makedirs(out, exist_ok=True)
    with open(os.path.join(
            out, f"sdar_control_{args.workload}.json"), "w") as f:
        json.dump(rows, f, indent=1)


if __name__ == "__main__":
    main()
