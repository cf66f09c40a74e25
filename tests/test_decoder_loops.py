"""The decoder whose stack is walked several times (`cfg.loops`, the
sandwich norms, the exit gate and the expected-exit loss; family `ouro`)
against the family's plain reference, at `ouro_tiny`: float32, seeded
weights, 4 / 4 heads of 16, two layers, THREE walks (so that "not 4" is
testable), L 32; the kernels run in interpret mode. It is also the
pattern decoder's first configuration without an expert.

Tolerances. Program and reference both compute in float32 here, so what
separates them is the order of float32 sums: measured 1e-7 on the loss,
2e-6 on a logit, 2e-6 of a leaf's largest gradient. LOSS_RTOL,
LOGIT_ATOL and GRAD_RTOL sit some way above that, and far below what
the smallest mutation of `test_mutation_is_told_apart` moves."""

import dataclasses
import functools
import json
import math
import os
import subprocess
import sys

import jax
import jax.numpy as jnp
import pytest

from benchmark import manifest
from benchmark.families import ouro, ouro_reference as reference
from ray_tpu.models import decoder

LOSS_RTOL = 3e-6
LOGIT_ATOL = 3e-5
GRAD_RTOL = 3e-5      # of the leaf's largest reference gradient

MODEL = manifest.config_file("ouro_tiny")
WALKS, LENGTH = MODEL["total_ut_steps"], 32


@functools.cache
def _setup(seed=0):
    cfg = dataclasses.replace(ouro.model_cfg(MODEL), dtype=jnp.float32)
    key = jax.random.key(seed)
    params, state = decoder.init(key, cfg), decoder.state_init(key, cfg)
    tokens = jax.random.randint(jax.random.key(seed + 1), (2, LENGTH), 0,
                                cfg.vocab_size)
    return cfg, params, state, tokens


def _program_of(cfg, params, tokens):
    (loss, _), grads = jax.jit(jax.value_and_grad(
        lambda p: decoder.loss_fn(p, tokens, cfg), has_aux=True))(params)
    logits = jax.jit(lambda p: decoder.apply(p, tokens, cfg))(params)
    return loss, logits, grads


@functools.cache
def _program(seed=0):
    """(loss, every walk's logits [T, B, L, V], gradients) of the
    program at `_setup(seed)`'s weights and tokens."""
    cfg, params, _, tokens = _setup(seed)
    return _program_of(cfg, params, tokens)


def _reference(params, tokens, mutate=""):
    with jax.default_matmul_precision("highest"):
        loss, grads = jax.value_and_grad(
            lambda p: reference.loss_of(p, tokens, MODEL, mutate))(params)
        logits = jnp.stack([reference.walk_logits(params, row, MODEL, mutate)
                            for row in tokens], axis=1)
    return loss, logits, grads


def _apart(got, want) -> float:
    """How far two (loss, logits, gradients) lie apart, in units of the
    tolerances: 1 is the limit of agreement. Walks that one side lacks
    count as far apart."""
    if got[1].shape != want[1].shape:
        return math.inf
    loss = abs(float(got[0]) - float(want[0])) / (
        LOSS_RTOL * abs(float(want[0])))
    logits = float(jnp.abs(got[1] - want[1]).max()) / LOGIT_ATOL
    return max(loss, logits, _gradients_apart(got[2], want[2]))


def _gradients_apart(got, want) -> float:
    """The worst leaf's distance, in units of GRAD_RTOL of the leaf's
    largest reference gradient."""
    return max(jax.tree.leaves(jax.tree.map(
        lambda a, b: float(jnp.abs(a - b).max() / (
            GRAD_RTOL * jnp.abs(b).max())), got, want)))


def test_the_tree_and_state_are_the_families():
    cfg, params, state, _ = _setup()
    assert cfg.kinds == (("full", "dense"),) * 2 and cfg.loops == WALKS == 3
    assert cfg.sandwich and cfg.exit_gate and cfg.exit_beta == 0.1
    # dense through and through: no expert field is named, none is made
    assert (cfg.n_experts, cfg.top_k, cfg.d_expert, cfg.held) == (
        0, 0, 0, (0, 0)) and cfg.moe_layers == 0
    assert set(params) == {"embed", "layers", "norm_f", "head", "exit_gate"}
    assert set(params["layers"]) == {
        "norm1", "norm1_post", "norm2", "norm2_post", "wq", "wk", "wv", "wo",
        "w1", "w2", "w3"}
    assert params["layers"]["norm2_post"].shape == (2, 64)
    assert params["exit_gate"]["w"].shape == (64,) \
        and params["exit_gate"]["b"].shape == () \
        and float(params["exit_gate"]["b"]) == 0.0 \
        and 0.005 < float(params["exit_gate"]["w"].std()) < 0.05
    assert set(state) == {"epoch_counters"}
    counters = set(state["epoch_counters"])
    assert counters == {
        *(f"{name}_{t}" for name in ("loop_nll", "exit_mass")
          for t in (1, 2, 3)), "exit_entropy", "loop_targets"}
    assert not any(name.startswith("moe_") for name in counters)
    facts = decoder.step_facts(cfg, (2, LENGTH))
    # ... and the forward's score tiles, every layer pass's
    assert facts.pop("attention_tiles_walked") % 3 == 0 \
        and facts.pop("attention_tiles_unmasked") % 3 == 0
    assert facts == {"loops": 3, "layer_passes": 6, "head_passes": 3}
    # the configurations from before the loop keep their trees and facts
    assert set(decoder.step_facts(decoder.TINY, (2, 64))) == {
        "attention_tiles_unmasked", "attention_tiles_walked"}
    assert set(decoder.counters_init(decoder.TINY)["epoch_counters"]) == {
        "moe_assignments", "moe_assignments_held", "moe_assignments_dropped",
        "moe_expert_tokens_max", "moe_expert_tokens_mean",
        "moe_experts_held", "moe_experts_total", "moe_steps"}
    assert "exit_gate" not in decoder.init(jax.random.key(0), decoder.TINY)


@pytest.mark.parametrize("seed", [0, 1])
def test_loss_every_walks_logits_and_every_gradient_match_the_reference(
        seed):
    _, params, _, tokens = _setup(seed)
    got, want = _program(seed), _reference(params, tokens)
    assert got[1].shape == (WALKS, 2, LENGTH, MODEL["vocab_size"])
    assert abs(float(got[0]) - float(want[0])) \
        <= LOSS_RTOL * abs(float(want[0]))
    for t in range(WALKS):
        assert float(jnp.abs(got[1][t] - want[1][t]).max()) <= LOGIT_ATOL, t
    assert set(got[2]) == set(want[2])
    assert _apart(got, want) <= 1.0


@functools.cache
def _gradient_by_walk():
    """The reference's gradient of the layers' leaves, one part a walk:
    each walk is handed a tree of its own."""
    _, params, _, tokens = _setup()

    def loss(per_walk):
        return reference.loss_of(dict(params, layers=per_walk), tokens,
                                 MODEL)

    with jax.default_matmul_precision("highest"):
        return jax.grad(loss)([params["layers"]] * WALKS)


@pytest.mark.parametrize("fault", ["the sum", "first walk x T", "last walk",
                                   "mean of the walks"])
def test_a_shared_layers_gradient_is_the_sum_over_the_walks(fault):
    """One set of weights, T uses: the program's gradient of a layer
    leaf is the SUM of what each walk gives it. One walk's part times T,
    the last walk's part alone, or the mean must lie outside the
    tolerance the sum lies inside."""
    parts, got = _gradient_by_walk(), _program()[2]["layers"]
    want = {
        "the sum": jax.tree.map(lambda *g: sum(g), *parts),
        "first walk x T": jax.tree.map(lambda g: WALKS * g, parts[0]),
        "last walk": parts[-1],
        "mean of the walks": jax.tree.map(lambda *g: sum(g) / WALKS, *parts),
    }[fault]
    worst = _gradients_apart(got, want)
    if fault == "the sum":
        assert worst <= 1.0
    else:
        assert worst > 100.0, (fault, worst)


def test_the_exit_distribution_sums_to_one_a_token():
    cfg, params, _, tokens = _setup()
    walks, _ = decoder.hidden(params, tokens, cfg)
    assert walks.shape == (WALKS, 2, LENGTH, cfg.d_model)
    p = decoder.exit_distribution(walks, params)
    assert p.shape == (WALKS, 2, LENGTH) and float(p.min()) > 0.0
    assert float(jnp.abs(p.sum(0) - 1.0).max()) <= 1e-6
    # the reference's own recipe on the program's gates (the last
    # walk's, which the program never computes, is not read there either)
    lam = jax.nn.sigmoid(walks.astype(jnp.float32) @ params["exit_gate"]["w"])
    want = reference.exit_distribution(lam.reshape(WALKS, -1))
    assert float(jnp.abs(p.reshape(WALKS, -1) - want).max()) <= 1e-6
    lost = reference.exit_distribution(lam.reshape(WALKS, -1),
                                       "last_walk_gated")
    assert float(lost.sum(0).max()) < 0.95         # the mutation loses mass


@pytest.mark.parametrize("mutation", reference.MUTATIONS)
def test_mutation_is_told_apart(mutation):
    """Every departure the reference can be told to make moves the loss
    or a walk's logits far past the tolerance the sound one meets."""
    _, params, _, tokens = _setup()
    sound = _program()
    assert _apart(sound, _reference(params, tokens, mutation)) > 20.0


def test_one_walk_without_a_gate_is_the_plain_loss_bit_for_bit():
    """`loops` 1, no gate: nothing of the loop is traced. The plain
    `loss_fn`, the stateful form of a dense pattern and the gate-free
    branch of `loop_loss` fed the one normed walk by hand are the same
    arithmetic, to the bit; the jaxpr names no product of survivals."""
    cfg, params, _, tokens = _setup()
    plain = dataclasses.replace(cfg, loops=1, exit_gate=False)
    params = {k: v for k, v in params.items() if k != "exit_gate"}
    loss, counts = jax.jit(lambda p: decoder.loss_fn(p, tokens, plain))(
        params)
    assert counts == {}
    state = decoder.state_init(jax.random.key(0), plain)
    assert state == {"epoch_counters": {}}
    stateful, after = jax.jit(
        lambda p, s: decoder.stateful_loss(p, s, tokens, plain))(params,
                                                                 state)
    assert after == state and float(stateful) == float(loss)

    def by_hand(p):
        h, _ = decoder.hidden(p, tokens, plain)
        x = decoder.rmsnorm(h, p["norm_f"].astype(h.dtype), plain.rms_eps)
        return decoder.loop_loss(x[None], tokens, p, plain)[0]

    assert float(jax.jit(by_hand)(params)) == float(loss)
    text = str(jax.make_jaxpr(lambda p: decoder.loss_fn(p, tokens, plain))(
        params))
    assert "cumprod" not in text
    looped = str(jax.make_jaxpr(lambda p: decoder.loss_fn(
        p, tokens, cfg))(_setup()[1]))
    assert "cumprod" in looped
    # a gate-free looped stack scores its LAST walk
    free = dataclasses.replace(cfg, exit_gate=False)
    last, none = jax.jit(lambda p: decoder.loss_fn(p, tokens, free))(params)
    assert none == {} and float(last) != float(loss)
    logits = decoder.apply(params, tokens, free)[-1, :, :-1]
    nll = -jnp.take_along_axis(jax.nn.log_softmax(logits, -1),
                               tokens[:, 1:, None], -1)
    assert float(last) == pytest.approx(float(nll.mean()), rel=LOSS_RTOL)


def test_a_pattern_without_experts_needs_no_held_and_counts_no_routing():
    dense = decoder.DecoderConfig(
        vocab_size=64, n_layers=2, d_model=32, n_heads=2, n_kv_heads=2,
        head_dim=16, attention=("full",), mlp=("dense",), window=0,
        rope_theta=1e4, d_dense=48, attn_block_q=16, attn_block_k=16,
        loss_chunk=16, dtype=jnp.float32)
    assert dense.held == (0, 0) and dense.moe_layers == 0
    assert decoder.counters_init(dense) == {"epoch_counters": {}}
    params = decoder.init(jax.random.key(0), dense)
    assert not {"router", "w_gate", "w_up", "w_down"} & set(params["layers"])
    tokens = jax.random.randint(jax.random.key(1), (2, 16), 0, 64)
    loss, state = decoder.stateful_loss(
        params, decoder.state_init(jax.random.key(0), dense), tokens, dense)
    assert math.isfinite(float(loss)) and state == {"epoch_counters": {}}
    # a pattern WITH experts still has to say what it holds, and the
    # message names the dense case
    with pytest.raises(ValueError, match="a dense pattern"):
        dataclasses.replace(dense, mlp=("experts",))
    with pytest.raises(ValueError, match="no share of 8 experts"):
        dataclasses.replace(dense, mlp=("experts",), n_experts=8, top_k=2,
                            d_expert=16, held=(4, 8))


def test_what_the_loop_is_not_built_for_is_refused():
    cfg = _setup()[0]
    for change in ({"mtp": 1}, {"diffusion_block": 4},
                   {"attention": ("ssm",), "ssm_heads": 2, "ssm_head_dim": 8,
                    "ssm_state": 8},
                   {"mlp": ("experts",), "n_experts": 4, "top_k": 2,
                    "d_expert": 16, "held": (0, 4)}, {"loops": 0}):
        with pytest.raises(ValueError, match="walk"):
            dataclasses.replace(cfg, **change)
    with pytest.raises(ValueError, match="needs loops > 1"):
        dataclasses.replace(cfg, loops=1)
    with pytest.raises(ValueError, match="sandwich"):
        dataclasses.replace(decoder.TINY, sandwich=True, d_shared=16)


def test_the_counters_sum_over_two_steps():
    cfg, params, state, tokens = _setup()
    step = jax.jit(lambda s: decoder.stateful_loss(params, s, tokens, cfg))
    loss, once = step(state)
    again, twice = step(once)
    assert float(again) == float(loss) == float(_program()[0])
    one, two = once["epoch_counters"], twice["epoch_counters"]
    n = 2 * (LENGTH - 1)
    assert float(one["loop_targets"]) == n \
        and float(two["loop_targets"]) == 2 * n
    for key in one:
        assert float(two[key]) == pytest.approx(2 * float(one[key]),
                                                rel=1e-6), key
    assert sum(float(one[f"exit_mass_{t}"]) for t in (1, 2, 3)) \
        == pytest.approx(n, rel=1e-6)
    assert 0.0 < float(one["exit_entropy"]) <= n * math.log(WALKS)
    with jax.default_matmul_precision("highest"):
        terms = [reference.sequence_terms(params, row, MODEL)
                 for row in tokens]
    for t in range(WALKS):
        assert float(one[f"loop_nll_{t + 1}"]) == pytest.approx(
            sum(float(each[t].sum()) for each, _, _ in terms), rel=1e-5)
        assert float(one[f"exit_mass_{t + 1}"]) == pytest.approx(
            sum(float(p[t].sum()) for _, p, _ in terms), rel=1e-5)
    assert float(one["exit_entropy"]) == pytest.approx(
        sum(float(h.sum()) for _, _, h in terms), rel=1e-5)
    # the loss is what the counters say it is
    weighted = sum(float((p * each).sum()) for each, p, _ in terms)
    assert float(loss) == pytest.approx(
        (weighted - cfg.exit_beta * float(one["exit_entropy"])) / n,
        rel=1e-5)


def test_remat_on_and_off_agree():
    """What the backward pass makes again changes no value: the shipped
    form (each block rematerialised) against none at all."""
    cfg, params, _, tokens = _setup()
    assert cfg.remat
    other = dataclasses.replace(cfg, remat=False)
    assert _apart(_program_of(other, params, tokens), _program()) <= 1.0


class _Op:
    """Built lazily: the operator class needs the runtime's imports."""

    @staticmethod
    def cls():
        from ray_tpu.train import TrainingOperator

        class Op(TrainingOperator):
            def setup(self, config):
                import optax

                cfg = ouro.model_cfg(manifest.config_file("ouro_tiny"))
                tokens = jax.random.randint(
                    jax.random.key(1), (2, LENGTH), 0, cfg.vocab_size)
                loss_fn = lambda p, s, b: decoder.stateful_loss(  # noqa: E731
                    p, s, b, cfg)
                loss_fn.step_facts = lambda b: decoder.step_facts(
                    cfg, b.shape)
                self.register(
                    model_init=lambda key: (decoder.init(key, cfg),
                                            decoder.state_init(key, cfg)),
                    loss_fn=loss_fn, optimizer=optax.adamw(3e-3),
                    stateful=True, seed=5)
                self.register_data(train_loader=[tokens] * 2)

        return Op


def test_a_snapshot_restored_mid_run_continues_to_the_same_loss(
        ray_start_shared):
    """The losses of a Trainer restored from a snapshot equal an
    unbroken one's, through `Trainer.train()` on the normal path; the
    epoch's counters and the step's facts arrive on the call's result
    and spans."""
    from ray_tpu.train import Trainer, call_log

    whole = Trainer(_Op.cls(), num_workers=1)
    broken = Trainer(_Op.cls(), num_workers=1)
    resumed = None
    try:
        unbroken = [whole.train()["train_loss"] for _ in range(3)]
        spans = {s["name"]: s["attrs"] for s in call_log()[-1]["spans"]}
        first = broken.train()["train_loss"]
        saved = broken.state_dict()
        assert set(saved["model_state"]) == {"epoch_counters"}
        broken.shutdown(force=True)
        resumed = Trainer(_Op.cls(), num_workers=1)
        resumed.load_state_dict(saved)
        rest = [resumed.train()["train_loss"] for _ in range(2)]
    finally:
        for tr in (whole, resumed):
            if tr is not None:
                tr.shutdown(force=True)
    assert [first] + rest == pytest.approx(unbroken, rel=1e-6)
    assert unbroken[2] < unbroken[0]                   # it trains
    facts, counted = spans["train.dispatch"], spans["train.sync"]
    assert (facts["loops"], facts["layer_passes"], facts["head_passes"]) \
        == (3, 6, 3)
    assert counted["loop_targets"] == 2 * 2 * (LENGTH - 1)
    assert sum(counted[f"exit_mass_{t}"] for t in (1, 2, 3)) \
        == pytest.approx(counted["loop_targets"], rel=1e-5)
    assert not any(key.startswith("moe_") for key in counted)


def test_cell_rehearses_on_the_cpu_to_its_end(tmp_path):
    # one CPU device, as a run of the command by hand has: the test
    # tree's eight virtual ones are not the benchmark's to count
    env = {k: v for k, v in os.environ.items() if k != "XLA_FLAGS"}
    log = tmp_path / "log.json"
    out = subprocess.run(
        [sys.executable, "benchmark/tools/run_with_log.py", str(log),
         "--workload", "ouro_d8_loop4_seq4k", "--seed", str(2 ** 31 + 9),
         "--seconds", "2", "--trace", "1", "--rehearse-cpu"],
        cwd=manifest.ROOT, capture_output=True, text=True, timeout=600,
        env=env)
    assert out.returncode == 0, out.stderr[-2000:]
    line = json.loads(out.stdout.strip().splitlines()[-1])
    checks = line["checks"]
    assert checks["losses_finite"] and checks["matches_reference"] \
        and checks["no_call_failed"] and checks["loss_fell"]
    assert line["rehearsal"] and not line["correct"] and not line["metrics"]
    spans = {s["name"]: s["attrs"]
             for s in json.loads(log.read_text())[-1]["spans"]}
    assert spans["train.dispatch"]["layer_passes"] == 6
    assert spans["train.sync"]["loop_targets"] == 2 * 2 * 63
