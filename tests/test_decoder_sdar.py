"""The decoder trained by block diffusion (`cfg.diffusion_block`; family
`sdar`) against the family's plain reference, at `sdar_tiny`: float32,
seeded weights, 8 query heads a key/value head, 2 of 16 experts held,
top-4, blocks of 4 tokens, L 32 (64 rows through the model); the kernels
run in interpret mode.

Tolerances. Program and reference both compute in float32 here, so what
separates them is the order of float32 sums: measured 5e-8 on the loss,
4e-7 on a logit, 1.5e-6 of a leaf's largest gradient. LOSS_RTOL,
LOGIT_ATOL and GRAD_RTOL sit some way above that, and far below what
the smallest mutation of `test_mutation_is_told_apart` moves."""

import dataclasses
import functools
import json
import os
import subprocess
import sys

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from benchmark import manifest
from benchmark.families import sdar, sdar_reference as reference
from ray_tpu.models import decoder

LOSS_RTOL = 3e-6
LOGIT_ATOL = 1e-5
GRAD_RTOL = 3e-5      # of the leaf's largest reference gradient

MODEL = manifest.config_file("sdar_tiny")
LENGTH, BLOCK = 32, MODEL["block_length"]
STEPS = (0, 1, 5)


@functools.cache
def _setup(seed=0):
    cfg = dataclasses.replace(sdar.model_cfg(MODEL), dtype=jnp.float32)
    key = jax.random.key(seed)
    params, state = decoder.init(key, cfg), decoder.state_init(key, cfg)
    tokens = jax.random.randint(jax.random.key(seed + 1), (2, LENGTH), 0,
                                cfg.vocab_size - 1)
    return cfg, params, state, tokens


@functools.cache
def _program(step, seed=0):
    """(loss, the noised half's logits, gradients) of the program at
    `_setup(seed)`'s weights and tokens, at its noise of `step`."""
    cfg, params, state, tokens = _setup(seed)
    seed = state["noise_seed"]
    (loss, _), grads = jax.jit(jax.value_and_grad(
        lambda p: decoder.diffusion_loss(p, tokens, cfg, seed, step),
        has_aux=True))(params)
    doubled, _, _ = decoder.diffusion_inputs(tokens, cfg, seed, step)
    logits = jax.jit(lambda p, t: decoder.apply(p, t, cfg))(
        params, doubled)[:, LENGTH:]
    return loss, logits, grads


def _reference(params, tokens, seed, step, mutate=""):
    with jax.default_matmul_precision("highest"):
        loss, grads = jax.jit(jax.value_and_grad(
            lambda p: reference.loss_of(p, tokens, MODEL, seed, step,
                                        mutate)))(params)
        noised, _, _ = reference.noise(tokens, seed, step, BLOCK,
                                       MODEL["vocab_size"] - 1)
        logits = jnp.stack([
            reference.forward(params, tokens[i], noised[i], MODEL, mutate)
            for i in range(tokens.shape[0])])
    return loss, logits[:, -LENGTH:], grads


def _apart(got, want) -> float:
    """How far two (loss, logits, gradients) lie apart, in units of the
    tolerances: 1 is the limit of agreement."""
    loss = abs(float(got[0]) - float(want[0])) / (
        LOSS_RTOL * abs(float(want[0])))
    logits = float(jnp.abs(got[1] - want[1]).max()) / LOGIT_ATOL
    grads = max(jax.tree.leaves(jax.tree.map(
        lambda a, r: float(jnp.abs(a - r).max()) / (
            GRAD_RTOL * float(jnp.abs(r).max()) + 1e-30), got[2], want[2])))
    return max(loss, logits, grads)


def test_the_tree_and_state_are_the_families():
    cfg, params, state, _ = _setup()
    assert cfg.diffusion_block == BLOCK and cfg.head_rows
    assert params["head"].shape == params["embed"].shape == (128, 64)
    assert sorted(params["layers"]) == [
        "k_norm", "norm1", "norm2", "q_norm", "router", "w_down", "w_gate",
        "w_up", "wk", "wo", "wq", "wv"]
    assert {"noise_seed", "noise_step", "epoch_counters"} == set(state)
    assert state["noise_seed"].dtype == state["noise_step"].dtype == jnp.int32
    assert int(state["noise_step"]) == 0 and int(state["noise_seed"]) > 0
    assert {"diffusion_masked", "diffusion_targets",
            "diffusion_weight_max"} <= set(state["epoch_counters"])
    # another seed, another noise; the same seed, the same
    assert int(_setup(1)[2]["noise_seed"]) != int(state["noise_seed"])
    assert int(decoder.state_init(jax.random.key(0), cfg)["noise_seed"]) \
        == int(state["noise_seed"])


@pytest.mark.parametrize("step", STEPS)
def test_loss_logits_and_every_gradient_match_the_reference(step):
    cfg, params, state, tokens = _setup()
    seed = state["noise_seed"]
    got = _program(step)
    want = _reference(params, tokens, seed, step)
    assert set(jax.tree.leaves(jax.tree.map(
        lambda a, r: a.shape == r.shape, got[2], want[2]))) == {True}
    assert all(float(jnp.abs(g).max()) > 0
               for g in jax.tree.leaves(got[2]))       # every leaf is reached
    assert _apart(got, want) <= 1.0, _apart(got, want)


def test_the_noise_is_the_recipe_and_moves_with_the_step():
    cfg, _, state, tokens = _setup()
    seed = state["noise_seed"]
    seen = []
    for step in STEPS:
        doubled, masked, p = decoder.diffusion_inputs(tokens, cfg, seed, step)
        noised, want_masked, want_p = reference.noise(
            tokens, seed, step, BLOCK, cfg.vocab_size - 1)
        assert np.array_equal(doubled[:, :LENGTH], tokens)
        assert np.array_equal(doubled[:, LENGTH:], noised)
        assert np.array_equal(masked, want_masked)
        assert np.array_equal(p, want_p)
        # one rate a block, inside [1e-3, 1]; masked tokens read MASK
        blocks = np.asarray(p).reshape(2, -1, BLOCK)
        assert (blocks == blocks[..., :1]).all()
        assert 1e-3 <= blocks.min() and blocks.max() <= 1.0
        assert (np.asarray(noised)[np.asarray(masked)]
                == cfg.vocab_size - 1).all()
        assert not (np.asarray(tokens) == cfg.vocab_size - 1).any()
        seen.append(np.asarray(masked))
    assert not np.array_equal(seen[0], seen[1])
    assert not np.array_equal(seen[1], seen[2])


@pytest.mark.parametrize("mutation", reference.MUTATIONS)
def test_mutation_is_told_apart(mutation):
    """Each departure from the objective or the mask moves the loss, a
    logit or a gradient by at least ten times the tolerance."""
    cfg, params, state, tokens = _setup()
    seed = state["noise_seed"]
    assert _apart(_program(1),
                  _reference(params, tokens, seed, 1, mutation)) > 10.0


def test_a_noised_block_reads_its_own_noise_and_the_clean_past():
    """Block 3's logits do not move when a LATER clean block, its own
    clean block or another block's noise changes; they do when an
    earlier clean block or its own noise does."""
    cfg, params, state, tokens = _setup()
    doubled, _, _ = decoder.diffusion_inputs(tokens, cfg,
                                             state["noise_seed"], 0)
    mine = slice(LENGTH + 3 * BLOCK, LENGTH + 4 * BLOCK)
    logits = jax.jit(lambda t: decoder.apply(params, t, cfg)[:, mine])
    base = logits(doubled)

    def moved(rows):
        other = doubled.at[:, rows].set((doubled[:, rows] + 1) % 100)
        return float(jnp.abs(logits(other) - base).max())

    assert moved(slice(4 * BLOCK, LENGTH)) <= 1e-6              # later clean
    assert moved(slice(3 * BLOCK, 4 * BLOCK)) <= 1e-6           # its own clean
    assert moved(slice(LENGTH, LENGTH + 3 * BLOCK)) <= 1e-6     # earlier noise
    assert moved(slice(LENGTH + 4 * BLOCK, 2 * LENGTH)) <= 1e-6  # later noise
    assert moved(slice(0, 3 * BLOCK)) > 1e-4                    # the clean past
    assert moved(mine) > 1e-4                                   # its own noise


def test_shares_add_up_to_the_uncut_layer():
    """The share test: the layer outputs of the eight shares (experts
    0-1, 2-3, .. 14-15 of 16), attention and residual counted once, add
    up to the uncut reference's layer output, on `[clean ; noised]`
    rows under the block-diffusion mask."""
    cfg, _, _, _ = _setup()
    whole_model = dict(MODEL, num_experts=16)
    whole_cfg = dataclasses.replace(sdar.model_cfg(whole_model),
                                    dtype=jnp.float32)
    p = {k: v[0] for k, v in decoder.init(
        jax.random.key(3), whole_cfg)["layers"].items()}
    h = jax.random.normal(jax.random.key(7), (1, 2 * LENGTH, cfg.d_model))
    positions = jnp.arange(2 * LENGTH) % LENGTH
    with jax.default_matmul_precision("highest"):
        whole, m = reference.layer(h[0], p, positions, whole_model)
    attention_and_residual = whole - m        # what every chip computes alike
    total = attention_and_residual
    for first in range(0, 16, 2):
        share = dataclasses.replace(cfg, held=(first, 2))
        mine = dict(p, **{k: p[k][first:first + 2]
                          for k in ("w_gate", "w_up", "w_down")})
        out, counts = jax.jit(functools.partial(
            decoder._layer, cfg=share, mlp="experts", attention="full"))(
                h, mine, decoder._rope_for(2 * LENGTH, share))
        assert int(counts["dropped"]) == 0
        with jax.default_matmul_precision("highest"):
            want, _ = reference.layer(h[0], mine, positions, MODEL,
                                      first=first)
        assert float(jnp.abs(out[0] - want).max()) <= LOGIT_ATOL
        total = total + (out[0] - attention_and_residual)
    assert float(jnp.abs(total - whole).max()) <= LOGIT_ATOL


def test_stateful_loss_moves_the_step_and_counts_the_noise():
    cfg, params, state, tokens = _setup()
    step = jax.jit(lambda s: decoder.stateful_loss(params, s, tokens, cfg))
    losses = []
    for i in range(3):
        loss, state = step(state)
        losses.append(float(loss))
        assert int(state["noise_step"]) == i + 1
    want = [float(decoder.diffusion_loss(
        params, tokens, cfg, state["noise_seed"], i)[0]) for i in range(3)]
    assert losses == pytest.approx(want, rel=1e-6)
    assert len(set(losses)) == 3                  # fresh noise every step
    counters = state["epoch_counters"]
    masked = sum(int(decoder.diffusion_inputs(
        tokens, cfg, state["noise_seed"], i)[1].sum()) for i in range(3))
    assert float(counters["diffusion_masked"]) == masked
    assert float(counters["diffusion_targets"]) == 3 * tokens.size
    assert 1.0 <= float(counters["diffusion_weight_max"]) <= 1e3
    # 2 L rows go through the experts: twice a causal model's assignments
    assert float(counters["moe_assignments"]) \
        == 3 * cfg.n_layers * 2 * tokens.size * cfg.top_k
    assert float(counters["moe_assignments_dropped"]) == 0
    facts = decoder.step_facts(cfg, tokens.shape)
    # (the kernel's tiles a step, over sequences, heads, layers and the
    # rematerialised forward: `attention_tiles_visited` a plane)
    planes = tokens.shape[0] * cfg.n_heads * cfg.n_layers * 2
    assert facts.pop("attention_tiles_walked") == planes * 6
    assert 0 <= facts.pop("attention_tiles_unmasked") < planes * 6
    assert facts == {"diffusion_block": BLOCK,
                     "diffusion_rows": 2 * tokens.size,
                     "attention_tiles_visited": 6,
                     "attention_tiles_plane": 8}
    assert set(decoder.step_facts(decoder.TINY, (2, 64))) == {
        "attention_tiles_unmasked", "attention_tiles_walked"}


def test_what_the_objective_is_not_built_for_is_refused():
    cfg = _setup()[0]
    with pytest.raises(ValueError, match="block diffusion"):
        dataclasses.replace(cfg, attention=("window",))
    with pytest.raises(ValueError, match="block diffusion"):
        dataclasses.replace(cfg, mtp=1)
    with pytest.raises(ValueError, match="whole blocks"):
        decoder.diffusion_inputs(jnp.zeros((1, 30), jnp.int32), cfg, 1, 0)


class _Op:
    """Built lazily: the operator class needs the runtime's imports."""

    @staticmethod
    def cls():
        from ray_tpu.train import TrainingOperator

        class Op(TrainingOperator):
            def setup(self, config):
                import optax

                model = manifest.config_file("sdar_tiny")
                cfg = sdar.model_cfg(model)
                tokens = jax.random.randint(
                    jax.random.key(1), (2, LENGTH), 0, cfg.vocab_size - 1)
                self.register(
                    model_init=lambda key: (decoder.init(key, cfg),
                                            decoder.state_init(key, cfg)),
                    loss_fn=lambda p, s, b: decoder.stateful_loss(
                        p, s, b, cfg),
                    optimizer=optax.adamw(3e-4), stateful=True, seed=5)
                self.register_data(train_loader=[tokens] * 2)

        return Op


def test_noise_step_survives_snapshot_and_restore(ray_start_shared):
    """The losses of a Trainer restored from a snapshot equal an
    unbroken one's: the snapshot carries `noise_step` (and the seed), so
    the noise continues where the saved one stood."""
    from ray_tpu.train import Trainer

    whole = Trainer(_Op.cls(), num_workers=1)
    broken = Trainer(_Op.cls(), num_workers=1)
    resumed = None
    try:
        unbroken = [whole.train()["train_loss"] for _ in range(3)]
        first = broken.train()["train_loss"]
        saved = broken.state_dict()
        assert int(saved["model_state"]["noise_step"]) == 2
        broken.shutdown(force=True)
        resumed = Trainer(_Op.cls(), num_workers=1)
        resumed.load_state_dict(saved)
        rest = [resumed.train()["train_loss"] for _ in range(2)]
        assert int(resumed.state_dict()["model_state"]["noise_step"]) == 6
    finally:
        for tr in (whole, resumed):
            if tr is not None:
                tr.shutdown(force=True)
    assert [first] + rest == pytest.approx(unbroken, rel=1e-6)
    assert len({first, *rest}) == 3


def test_cell_rehearses_on_the_cpu_to_its_end():
    # one CPU device, as a run of the command by hand has: the test
    # tree's eight virtual ones are not the benchmark's to count
    env = {k: v for k, v in os.environ.items() if k != "XLA_FLAGS"}
    out = subprocess.run(
        [sys.executable, "benchmark/run.py", "--workload", "sdar_ep8_seq4k",
         "--seed", str(2 ** 31 + 9), "--seconds", "2", "--trace", "1",
         "--rehearse-cpu"], cwd=manifest.ROOT, capture_output=True,
        text=True, timeout=600, env=env)
    assert out.returncode == 0, out.stderr[-2000:]
    line = json.loads(out.stdout.strip().splitlines()[-1])
    checks = line["checks"]
    assert checks["losses_finite"] and checks["matches_reference"] \
        and checks["no_call_failed"]
    assert line["rehearsal"] and not line["correct"] and not line["metrics"]
