"""Builder's tool: ``qwen3next_ep16_seq8k``'s reference tolerance held
against its two readings, through ``run.py``'s own comparison —
``reference_control.py``'s reckoning for a family whose state holds no
selection bias.

    chiprun -- python3 benchmark/tools/qwen3next_control.py \
        --control-seeds 3 <seed> <seed> ...

At the published widths and the timed sizes, in ONE process on the chip,
for every seed: ``program`` (the step-0 loss of the cell's own
``loss_fn`` at the seeded weights: kernels, bfloat16 compute, float32
where the configuration says so), ``reference`` (the family's float32
reference on the same weights and batch) and, for the first
``--control-seeds`` seeds, ``control``: the reference with its BLOCKS
in bfloat16 (weights, activations, rotary tables, gates, router, the
delta rule's state, the attention's softmax) and the loss's softmax and
sums in float32 — the
precision below the one the configuration states. ``program_matches`` / ``control_matches`` are ``run.judge``'s
``matches_reference`` at the ``reference.rtol`` of the cell's file: the
tolerance is sound where the first is true on every seed and the second
false. ``logits``: the distance of the first sequence's logits from the
float32 reference's, for the program (``decoder.apply``) and the
control. One JSON line a seed, and all of them in
``chiprun_out/reference_control_qwen3next_ep16_seq8k.json``."""

from __future__ import annotations

import argparse
import json
import os
import sys
import time

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
sys.path.insert(0, ROOT)

CELL = "qwen3next_ep16_seq8k"


def main():
    ap = argparse.ArgumentParser()
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
    from benchmark.families import qwen3_next_reference as reference
    from benchmark.tools.reference_control import distance, matches
    from ray_tpu.models import decoder

    cell = manifest.cell(CELL, rehearse=args.rehearse_cpu)
    model, workload, family = cell["model"], cell["workload"], cell["family"]
    cfg = family.model_cfg(model)
    forward = program_logits = None
    exact_logits = jax.jit(lambda w, t: reference.forward(w, t, model)[0])
    rows = []
    for i, seed in enumerate(args.seeds):
        t0 = time.time()
        p = family.pieces(model, workload, seed)
        init = p.model_init(jax.random.key(key_seed(seed)))
        params, state = init
        if forward is None:
            forward = jax.jit(lambda w, s, b: p.loss_fn(w, s, b)[0])
            program_logits = jax.jit(lambda w, t: decoder.apply(w, t, cfg))
        program = float(forward(params, state, p.batch))
        want = reference.loss(init, p.batch, model)
        row = {"seed": seed, "rtol": workload["reference"]["rtol"],
               "program": program, "reference": want,
               "program_rel": abs(program - want) / abs(want),
               "program_matches": matches(run, cell, program, want)}
        if i < args.control_seeds:
            low = reference.loss(init, p.batch, model, dtype=jnp.bfloat16)
            row.update(control=low,
                       control_rel=abs(low - want) / abs(want),
                       control_matches=matches(run, cell, low, want))
            with jax.default_matmul_precision("highest"):
                exact = exact_logits(params, p.batch[0])
            rough = exact_logits(jax.tree.map(
                lambda x: x.astype(jnp.bfloat16), params), p.batch[0])
            got = program_logits(params, p.batch[:1])[0]
            row["logits"] = {
                "program": distance(got, exact),
                "control": distance(rough.astype(jnp.float32), exact)}
            del exact, rough, got
        row["s"] = round(time.time() - t0, 1)
        print(json.dumps(row), flush=True)
        rows.append(row)
        del init, params, state, p
    out = os.path.join(ROOT, "chiprun_out")
    os.makedirs(out, exist_ok=True)
    with open(os.path.join(out, f"reference_control_{CELL}.json"), "w") as f:
        json.dump(rows, f, indent=1)


if __name__ == "__main__":
    main()
