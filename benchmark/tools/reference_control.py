"""Builder's tool: a cell's reference tolerance held against its two
readings, through ``run.py``'s own comparison.

    chiprun -- python3 benchmark/tools/reference_control.py \
        --workload nemotron3_ep16_seq8k --control-seeds 3 <seed> <seed> ...

For a decoder family (one whose reference's ``loss`` takes ``dtype`` and
whose family file has ``model_cfg``), at the published widths and the
timed sizes, in ONE process on the chip, for every seed:

- ``program``: the step-0 loss of the cell's own ``loss_fn`` at the
  seeded weights (the fused step's forward: kernels, bfloat16 compute,
  float32 where the configuration says so);
- ``reference``: the family's float32 reference on the same weights and
  batch;
- for the first ``--control-seeds`` seeds ``control``: the reference
  with its BLOCKS in bfloat16 (weights, activations, a recurrence's
  state, router, attention softmax) and the loss's softmax and sums in
  float32 — the precision below the one the configuration states;
- ``program_matches`` / ``control_matches``: ``run.judge``'s
  ``matches_reference`` with that loss as the run's first loss, at the
  ``reference.rtol`` of the cell's file. The tolerance is sound where
  the first is true on every seed and the second false;
- ``logits``: beside the loss, which at seeded weights sits near
  log(vocabulary) whatever the blocks compute, the distance of the
  logits themselves (all positions of the batch's first sequence; norm
  of the difference over the reference's norm) from the float32
  reference's, for the program (``decoder.apply``) and the control.

One JSON line a seed, and all of them in
``chiprun_out/reference_control_<cell>.json``."""

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


def matches(run, cell, first_loss: float, reference: float) -> bool:
    """``run.judge`` on a record whose every loss is `first_loss`."""
    import jax

    call = {"mean_loss": first_loss, "last_loss": first_loss,
            "programs_built": 0,
            "device": {"platform": jax.default_backend()}}
    record = {"first": call, "warm": call, "calls": [call], "failed": 0,
              "reference_loss": reference}
    return bool(run.judge(cell, record, False)["matches_reference"])


def distance(a, b) -> float:
    import jax.numpy as jnp

    return float(jnp.linalg.norm((a - b).ravel())
                 / jnp.linalg.norm(b.ravel()))


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
    from ray_tpu.models import decoder

    cell = manifest.cell(args.workload, rehearse=args.rehearse_cpu)
    model, workload, family = cell["model"], cell["workload"], cell["family"]
    reference = importlib.import_module(
        f"benchmark.families.{model['family']}_reference")
    cfg = family.model_cfg(model)
    forward = program_logits = None
    rows = []
    for i, seed in enumerate(args.seeds):
        t0 = time.time()
        p = family.pieces(model, workload, seed)
        init = p.model_init(jax.random.key(key_seed(seed)))
        params, state = init
        bias, tokens = state.get("expert_bias"), p.batch[:1]
        if forward is None:
            forward = jax.jit(lambda w, s, b: p.loss_fn(w, s, b)[0])
            program_logits = jax.jit(
                lambda w, b, t: decoder.apply(w, t, cfg, b))
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
                exact = jax.jit(lambda w, b, t: reference.forward(
                    w, b, t, model)[0])(params, bias, tokens[0])
            low_params = jax.tree.map(
                lambda x: x.astype(jnp.bfloat16), params)
            rough = jax.jit(lambda w, b, t: reference.forward(
                w, b, t, model)[0])(
                    low_params, bias.astype(jnp.bfloat16), tokens[0])
            got = program_logits(params, bias, tokens)[0]
            row["logits"] = {
                "program": distance(got, exact),
                "control": distance(rough.astype(jnp.float32), exact)}
            del exact, rough, got, low_params
        row["s"] = round(time.time() - t0, 1)
        print(json.dumps(row), flush=True)
        rows.append(row)
        del init, params, state, p
    out = os.path.join(ROOT, "chiprun_out")
    os.makedirs(out, exist_ok=True)
    with open(os.path.join(
            out, f"reference_control_{args.workload}.json"), "w") as f:
        json.dump(rows, f, indent=1)


if __name__ == "__main__":
    main()
