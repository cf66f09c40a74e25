"""Builder's tool: ``keye_ep8_seq16k``'s step-0 loss, BOTH terms, held
against the float32 reference, the selection's agreement with it, and
the issue's controls, in one process on the chip.

    chiprun -- python3 benchmark/tools/keye_control.py [--bfloat16 N] \
        <seed> ...

At the published widths and the timed sizes, for every seed: the
program's loss and its two terms (``loss_main``, ``loss_index`` as the
step counts them) against ``keye_reference.terms`` through
``run.judge``'s own comparison at the cell's ``reference.rtol``. For the
first ``--bfloat16`` seeds (1) also the reference with weights and
activations in bfloat16 (the nearest precision below the
configuration's); for the first seed also: (i) layer by layer, the share of the pairs the PROGRAM
selects (its indexer's products in bfloat16, fed the reference's own
float32 layer input) that the float32 reference selects too; and each
control — the reference
under ``window`` (the selection replaced by the last 2048 keys),
``dense`` (no selection), ``no_index_loss`` (L_I dropped) and
``no_relu`` (the indexer's ReLU dropped): its loss through
``run.judge`` (``loss_separates``) and the distance of its logits over
the sequence's LAST ``LOGIT_ROWS`` rows (the rows that select) from the
float32 reference's, beside the program's own. One JSON line each, and
all of them in ``chiprun_out/keye_control.json``."""

from __future__ import annotations

import argparse
import json
import os
import sys
import time

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
sys.path.insert(0, ROOT)

CELL = "keye_ep8_seq16k"
LOGIT_ROWS = 2048
CONTROLS = ("window", "dense", "no_index_loss", "no_relu")


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--rehearse-cpu", action="store_true",
                    help="the cell's rehearsal sizes: the tool's own "
                         "plumbing, never a reading")
    ap.add_argument("--bfloat16", type=int, default=1,
                    help="how many of the first seeds also read the "
                         "reference in bfloat16")
    ap.add_argument("seeds", type=int, nargs="+")
    args = ap.parse_args()
    if args.rehearse_cpu:
        os.environ["JAX_PLATFORMS"] = "cpu"

    import jax
    import jax.numpy as jnp

    from benchmark import manifest, run
    from benchmark.common import key_seed
    from benchmark.families import keye_reference as reference
    from benchmark.tools.reference_control import distance, matches
    from ray_tpu.models import decoder
    from ray_tpu.ops import sparse_index

    cell = manifest.cell(CELL, rehearse=args.rehearse_cpu)
    model, workload, family = cell["model"], cell["workload"], cell["family"]
    cfg = family.model_cfg(model)
    rows = min(LOGIT_ROWS, workload["seq"] // 2)

    def agreement(params, tokens):
        """(i): layer by layer, on the reference's own layer input."""
        tile = (cfg.attn_block_q, cfg.attn_block_k)
        select = jax.jit(lambda x, p: sparse_index.index_select(
            *decoder._indexer(x[None].astype(cfg.dtype), p, cfg),
            cfg.index_topk, tile)[0][0])
        h, shares = params["embed"][tokens], []
        for l in range(model["num_hidden_layers"]):
            p = {k: v[l] for k, v in params["layers"].items()}
            plane = select(reference._rmsnorm(
                h, p["norm1"], model["rms_norm_eps"]), p) != 0
            with jax.default_matmul_precision("highest"):
                h, _, _, keep = reference.layer(h, p, model)
            shares.append(float((plane & keep).sum() / plane.sum()))
        return shares

    def one_pass(params, tokens, mutate="", dtype=jnp.float32):
        """The first sequence through the reference once: ((CE, L_I),
        the last rows' logits)."""
        w = jax.tree.map(lambda x: x.astype(dtype), params)
        t, layers = tokens.shape[0], model["num_hidden_layers"]
        with jax.default_matmul_precision(
                "highest" if dtype == jnp.float32 else "default"):
            x, kl, _ = reference.hidden(w, tokens, model, mutate)
            main = float(reference.nll_sum(x, w["head"], tokens)) / (t - 1)
            return (main, float(kl) / (t * layers)), \
                (x[-rows:] @ w["head"].T).astype(jnp.float32)

    out_rows, step, apply = [], None, None
    for i, seed in enumerate(args.seeds):
        t0 = time.time()
        p = family.pieces(model, workload, seed)
        init = p.model_init(jax.random.key(key_seed(seed)))
        if step is None:
            step = jax.jit(p.loss_fn)
        loss, state = step(*init, p.batch)
        counters = state["epoch_counters"]
        main, index = reference.terms(init, p.batch, model)
        want = main + model["index_loss_weight"] * index
        row = {"seed": seed, "what": "program", "loss": float(loss),
               "loss_main": float(counters["loss_main"]),
               "loss_index": float(counters["loss_index"]),
               "reference_loss": want, "reference_main": main,
               "reference_index": index,
               "loss_rel": abs(float(loss) - want) / want,
               "main_rel": abs(float(counters["loss_main"]) - main) / main,
               "index_rel": abs(float(counters["loss_index"]) - index)
               / index,
               "selected_share": float(counters["index_pairs_selected"]
                                       / counters["index_pairs_causal"]),
               # what the step's time follows, seed by seed
               "tiles_visited_share": float(
                   counters["index_tiles_visited"]
                   / counters["index_tiles_causal"]),
               "held_share": float(counters["moe_assignments_held"]
                                   / counters["moe_assignments"]),
               "matches": matches(run, cell, float(loss), want),
               "s": round(time.time() - t0, 1)}
        print(json.dumps(row), flush=True)
        out_rows.append(row)
        if i >= max(1, args.bfloat16):
            continue
        tokens = p.batch[0]
        if not i:
            t0 = time.time()
            row = {"seed": seed, "what": "selection_agreement",
                   "by_layer": agreement(init[0], tokens),
                   "s": round(time.time() - t0, 1)}
            print(json.dumps(row), flush=True)
            out_rows.append(row)
        first, exact = one_pass(init[0], tokens)
        first = first[0] + model["index_loss_weight"] * first[1]
        if apply is None:
            apply = jax.jit(lambda w, t: decoder.apply(w, t, cfg)[0, -rows:])
        row = {"seed": seed, "what": "program_logits",
               "logits_distance": distance(apply(init[0], p.batch[:1]),
                                           exact)}
        print(json.dumps(row), flush=True)
        out_rows.append(row)
        for what in ("bfloat16",) + (() if i else CONTROLS):
            t0 = time.time()
            dtype = jnp.bfloat16 if what == "bfloat16" else jnp.float32
            low, logits = one_pass(init[0], tokens,
                                   "" if what == "bfloat16" else what, dtype)
            got = low[0] + model["index_loss_weight"] * low[1]
            row = {"seed": seed, "what": what, "loss": got,
                   "loss_main": low[0], "loss_index": low[1],
                   "loss_rel": abs(got - first) / first,
                   "loss_separates": not matches(run, cell, got, first),
                   "logits_distance": distance(logits, exact),
                   "s": round(time.time() - t0, 1)}
            print(json.dumps(row), flush=True)
            out_rows.append(row)
        del exact
    out = os.path.join(ROOT, "chiprun_out")
    os.makedirs(out, exist_ok=True)
    with open(os.path.join(out, "keye_control.json"), "w") as f:
        json.dump(out_rows, f, indent=1)


if __name__ == "__main__":
    main()
