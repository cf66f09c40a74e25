"""Builder's tool: ``kimilinear_ep32_seq8k``'s GRADIENTS held against
the float32 reference, and the issue's five controls, in one process on
the chip — what ``reference_control.py`` (the loss and the logits of the
bfloat16 control, for ``reference.rtol``) does not compare.

    chiprun -- python3 benchmark/tools/kimilinear_control.py <seed> ...

``run.judge`` compares the step-0 loss, and at seeded weights that loss
sits near log(vocabulary) whatever a block computes. So, at the
published widths and the timed length, on the batch's FIRST sequence,
for every seed: the program's loss and its gradient's norm by parameter
group (``families/kimi_linear.py::group_norms``: the KDA projections,
W_f, W_g, A_log, dt_bias, the latent mixer, the dense MLP, the router,
the held experts, the shared expert, the norms, the embedding, the head)
against the float32 reference's, each layer of the reference under a
``jax.checkpoint`` of its own so that its gradient fits beside the
weights (the same values); and for the first seed each control
(``kimi_linear_reference.MUTATIONS[:5]``: the decay averaged over a
head's channels, the state and the exponentials in bfloat16, the output
gate left out, the latent layer's 64 shared dimensions rotated,
``routed_scaling_factor`` 1): its loss through ``run.judge``'s own
comparison at the cell's ``reference.rtol`` (``loss_separates``), the
largest relative distance of a group's norm from the float32
reference's and the group (``gradients_separate``: beyond
``GRADIENT_RTOL``, which the program itself has to stay inside). One
JSON line each, and all of them in
``chiprun_out/kimilinear_control.json``."""

from __future__ import annotations

import argparse
import json
import os
import sys
import time

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
sys.path.insert(0, ROOT)

CELL = "kimilinear_ep32_seq8k"
GRADIENT_RTOL = 0.03    # a group's norm, program against reference


def main():
    ap = argparse.ArgumentParser()
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
    from benchmark.families import kimi_linear_reference as reference
    from benchmark.tools.reference_control import matches

    cell = manifest.cell(CELL, rehearse=args.rehearse_cpu)
    model, workload, family = cell["model"], cell["workload"], cell["family"]
    workload = dict(workload, batch=1)

    def exact_loss(params, bias, tokens, mutate):
        """`reference.nll_sum`'s mean, a layer under a checkpoint."""
        h = params["embed"][tokens]
        for mixer, mlp, row in reference.kinds(model):
            def block(h, p, b, mixer=mixer, mlp=mlp):
                return reference.layer(h, p, b, mixer=mixer, mlp=mlp,
                                       model=model, mutate=mutate)[0]

            h = jax.checkpoint(block)(
                h, reference.layer_leaves(params, row),
                bias[row["experts"]] if mlp == "experts" else None)
        logits = reference._norm(h, params["norm_f"],
                                 model["rms_norm_eps"]) @ params["head"]
        logp = jax.nn.log_softmax(logits[:-1].astype(jnp.float32), axis=-1)
        return -jnp.take_along_axis(logp, tokens[1:, None], axis=-1).mean()

    def exact(params, bias, tokens, mutate=""):
        with jax.default_matmul_precision("highest"):
            loss, grads = jax.jit(jax.value_and_grad(
                lambda w: exact_loss(w, bias, tokens, mutate)))(params)
        return float(loss), family.group_norms(grads)

    def furthest(norms, want):
        off = {g: abs(norms[g] - want[g]) / want[g] for g in want}
        group = max(off, key=off.get)
        return {"group": group, "rel": off[group], "by_group": off}

    rows, program = [], None
    for i, seed in enumerate(args.seeds):
        t0 = time.time()
        p = family.pieces(model, workload, seed)
        params, state = p.model_init(jax.random.key(key_seed(seed)))
        if program is None:
            program = jax.jit(jax.value_and_grad(
                lambda w, s, b: p.loss_fn(w, s, b)[0]))
        loss, grads = program(params, state, p.batch)
        norms = family.group_norms(grads)
        del grads
        want_loss, want = exact(params, state["expert_bias"], p.batch[0])
        off = furthest(norms, want)
        row = {"seed": seed, "what": "program", "loss": float(loss),
               "reference_loss": want_loss,
               "loss_rel": abs(float(loss) - want_loss) / want_loss,
               "reference_norms": want, "norms": norms, "furthest": off,
               "gradients_match": off["rel"] <= GRADIENT_RTOL,
               "s": round(time.time() - t0, 1)}
        print(json.dumps(row), flush=True)
        rows.append(row)
        for mutate in reference.MUTATIONS[:5] if i == 0 else ():
            t0 = time.time()
            low, got = exact(params, state["expert_bias"], p.batch[0],
                             mutate)
            off = furthest(got, want)
            row = {"seed": seed, "what": mutate, "loss": low,
                   "loss_rel": abs(low - want_loss) / want_loss,
                   "loss_separates": not matches(run, cell, low, want_loss),
                   "furthest": off,
                   "gradients_separate": off["rel"] > GRADIENT_RTOL,
                   "s": round(time.time() - t0, 1)}
            print(json.dumps(row), flush=True)
            rows.append(row)
        del params, state, p
    out = os.path.join(ROOT, "chiprun_out")
    os.makedirs(out, exist_ok=True)
    with open(os.path.join(out, "kimilinear_control.json"), "w") as f:
        json.dump(rows, f, indent=1)


if __name__ == "__main__":
    main()
