"""The pattern decoder's later kinds (`models/decoder.py`: the `conv`
mixer, full attention with q/k norms and rotary positions, the dense
MLP, leading layers, a tied head, parameter stacks per leaf), the gated
short convolution (`ops/short_conv.py`) and sigmoid routing with a
selection bias (`parallel/moe.py`), against the plain float32 reference
`benchmark/families/lfm2_reference.py`. CPU, tiny widths: hidden 64, two
leading dense layers and two periods of four expert layers, 8 experts
top-2, T 64; the kernels run in interpret mode.

Tolerances. Program and reference both compute in float32 here, so what
separates them is the order of float32 sums: measured 2.6e-7 on the
loss, 3.6e-7 on a logit, 8.3e-7 of a leaf's largest gradient.
LOSS_RTOL, LOGIT_ATOL and GRAD_RTOL sit about an order of magnitude
above that, and below what the smallest mutation of
`test_mutation_is_told_apart` moves (the bias added to the weights:
1.9e-5 on the loss, 7.6e-3 on a logit)."""

import dataclasses
import functools

import jax
import jax.numpy as jnp
import pytest

from benchmark import manifest
from benchmark.families import lfm2, lfm2_reference as reference
from ray_tpu.models import decoder
from ray_tpu.ops.short_conv import short_conv, short_conv_xla
from ray_tpu.parallel.moe import (balance_bias, dropless_moe,
                                  route_sigmoid_bias)

LOSS_RTOL = 3e-6
LOGIT_ATOL = 5e-6
GRAD_RTOL = 1e-5      # of the leaf's largest reference gradient

# the tiny preset at ten layers: conv + dense, attention + dense, then
# two periods of (attention, conv, conv, conv) under experts
MODEL = dict(manifest.config_file("lfm2_tiny"), num_hidden_layers=10,
             layer_types=["conv", "full_attention"]
             + ["full_attention", "conv", "conv", "conv"] * 2)
HELD = {"all": (0, 8), "subset": (2, 4)}


def _setup(held, seed=0):
    model = dict(MODEL, held_experts_first=held[0], num_experts=held[1])
    cfg = dataclasses.replace(lfm2.model_cfg(model), dtype=jnp.float32)
    key = jax.random.key(seed)
    params, state = decoder.init(key, cfg), decoder.state_init(key, cfg)
    # norms away from one (a unit q/k norm commutes with the rotary
    # turn) and a bias large enough to move a good share of the choices
    noise = iter(jax.random.split(jax.random.key(seed + 2), 8))
    params["layers"] = {
        name: leaf + 0.3 * jax.random.normal(next(noise), leaf.shape)
        if "norm" in name else leaf
        for name, leaf in params["layers"].items()}
    state["expert_bias"] = 5 * state["expert_bias"]
    tokens = jax.random.randint(jax.random.key(seed + 1), (2, 64), 0,
                                cfg.vocab_size)
    return cfg, params, state, tokens, model


def _reference(params, bias, tokens, model, mutate=""):
    """(mean loss, (logits [B, T, V], n [layers, E])): one pass."""
    outs = [reference.forward(params, bias, row, model, mutate)
            for row in tokens]
    logits = jnp.stack([o[0] for o in outs])
    logp = jax.nn.log_softmax(logits[:, :-1], axis=-1)
    nll = -jnp.take_along_axis(logp, tokens[:, 1:, None], axis=-1)
    return nll.mean(), (logits, sum(o[1] for o in outs))


@pytest.fixture(scope="module")
def program():
    """The program's loss, new state, logits and gradients, once a held
    share."""
    out = {}
    for name, held in HELD.items():
        cfg, params, state, tokens, _ = _setup(held)
        (loss, new), grads = jax.jit(jax.value_and_grad(
            lambda p: decoder.stateful_loss(p, state, tokens, cfg),
            has_aux=True))(params)
        logits = jax.jit(lambda p: decoder.apply(
            p, tokens, cfg, state["expert_bias"]))(params)
        out[name] = (float(loss), logits, grads, new)
    return out


def test_parameter_stacks_hold_only_the_layers_that_have_the_leaf():
    cfg, params, state, _, _ = _setup(HELD["all"])
    assert cfg.kinds[:3] == (("conv", "dense"), ("full", "dense"),
                             ("full", "experts"))
    stacks = {k: v.shape[0] for k, v in params["layers"].items()}
    assert stacks == {
        "norm1": 10, "norm2": 10, "wq": 3, "wk": 3, "wv": 3, "wo": 3,
        "q_norm": 3, "k_norm": 3, "conv_in": 7, "conv_taps": 7,
        "conv_out": 7, "w1": 2, "w3": 2, "w2": 2, "router": 8, "w_gate": 8,
        "w_up": 8, "w_down": 8}
    assert "head" not in params                  # tied
    assert state["expert_bias"].shape == (8, 8)
    assert state["expert_bias"].dtype == jnp.float32


@pytest.mark.parametrize("share", list(HELD))
def test_decoder_matches_reference(program, share):
    """Loss, logits, every leaf's gradient and the bias after the step,
    with all experts held and with a held subset (experts 2..5 of 8)."""
    _, params, state, tokens, model = _setup(HELD[share])
    loss, logits, grads, new = program[share]
    bias = state["expert_bias"]
    with jax.default_matmul_precision("highest"):
        (ref_loss, (ref_logits, n)), ref_grads = jax.jit(jax.value_and_grad(
            lambda p: _reference(p, bias, tokens, model),
            has_aux=True))(params)
    assert abs(loss - float(ref_loss)) <= LOSS_RTOL * float(ref_loss)
    assert float(jnp.abs(logits - ref_logits).max()) <= LOGIT_ATOL
    flat, _ = jax.tree_util.tree_flatten_with_path(grads)
    for (path, got), want in zip(flat, jax.tree.leaves(ref_grads)):
        scale = float(jnp.abs(want).max())
        assert scale > 0, path       # every leaf is reached by the loss
        assert float(jnp.abs(got - want).max()) <= GRAD_RTOL * scale, path
    want = reference.bias_update(bias, n, model["expert_bias_update_rate"])
    assert (new["expert_bias"] == want).all()
    c = new["epoch_counters"]
    assert int(c["moe_assignments"]) == 8 * tokens.size * 2
    assert int(c["moe_assignments_dropped"]) == 0
    assert 0 < int(c["moe_assignments_bias_moved"]) < 8 * tokens.size * 2
    assert float(c["moe_bias_abs_max"]) == float(jnp.abs(want).max())
    if share == "all":
        assert int(c["moe_assignments_held"]) == 8 * tokens.size * 2


@pytest.mark.parametrize("name", reference.MUTATIONS)
def test_mutation_is_told_apart(program, name):
    """A reference with one term changed must fail
    `test_decoder_matches_reference` by its tolerances: by ten times
    LOGIT_ATOL on the logits, and on the loss."""
    _, params, state, tokens, model = _setup(HELD["all"])
    loss, logits, _, _ = program["all"]
    with jax.default_matmul_precision("highest"):
        ref_loss, (ref_logits, _) = jax.jit(lambda p: _reference(
            p, state["expert_bias"], tokens, model, name))(params)
    assert float(jnp.abs(logits - ref_logits).max()) > 10 * LOGIT_ATOL
    assert abs(loss - float(ref_loss)) > LOSS_RTOL * float(ref_loss)


def test_bias_after_three_steps_follows_the_references_rule():
    """Three steps of the stateful loss on the same weights: each step
    routes with the bias the step before left, counts what every expert
    of all 8 got, and moves the bias by the rule; the reference does the
    same with its own routing and its plain `bias_update`."""
    cfg, params, state, tokens, model = _setup(HELD["subset"])
    step = jax.jit(lambda s: decoder.stateful_loss(params, s, tokens, cfg))
    bias = state["expert_bias"]
    moved = False
    for _ in range(3):
        _, state = step(state)
        with jax.default_matmul_precision("highest"):
            _, (_, n) = jax.jit(lambda b: _reference(
                params, b, tokens, model))(bias)
        new = reference.bias_update(bias, n, 1e-3)
        moved |= bool((new != bias).any())
        bias = new
        assert (state["expert_bias"] == bias).all()
    assert moved and int(state["epoch_counters"]["moe_steps"]) == 3


def test_shares_add_up_to_the_uncut_layer():
    """The share test: the expert layers' outputs of the four shares
    (experts 0-7, 8-15, 16-23, 24-31 of 32, top-4), mixer and residual
    counted once, add up to the uncut reference's layer output."""
    model = dict(MODEL, router_outputs=32, num_experts=32,
                 num_experts_per_tok=4, held_experts_first=0)
    cfg = dataclasses.replace(lfm2.model_cfg(model), dtype=jnp.float32)
    key = jax.random.key(3)
    params, state = decoder.init(key, cfg), decoder.state_init(key, cfg)
    bias = 5 * state["expert_bias"][1]
    for mixer, first_row in (("full", 1), ("conv", 2)):
        # layer 2 (attention) and layer 3 (conv): expert layers 0 and 1
        names = [n for n, (g, _, _) in decoder._leaves(cfg).items()
                 if g in ("layer", "experts",
                          "attention" if mixer == "full" else "conv")]
        p = {n: params["layers"][n][first_row if n in (
            "wq", "wk", "wv", "wo", "q_norm", "k_norm") else 2]
            for n in names}
        h = jax.random.normal(jax.random.key(7), (1, 64, cfg.d_model))
        with jax.default_matmul_precision("highest"):
            whole, m, n = reference.layer(
                h[0], p, bias, mixer="conv" if mixer == "conv"
                else "full_attention", mlp="experts", model=model)
        assert int(n.sum()) == 64 * 4
        mixer_and_residual = whole - m       # what every chip computes alike
        total = mixer_and_residual
        for first in (0, 8, 16, 24):
            share = dataclasses.replace(cfg, held=(first, 8))
            mine = dict(p, expert_bias=bias, **{
                k: p[k][first:first + 8]
                for k in ("w_gate", "w_up", "w_down")})
            out, counts = jax.jit(functools.partial(
                decoder._layer, cfg=share, mlp="experts", attention=mixer))(
                    h, mine, decoder.rope_tables(
                        jnp.arange(64, dtype=jnp.float32), share))
            assert int(counts["dropped"]) == 0
            assert (counts["routed"] == n).all()
            total = total + (out[0] - mixer_and_residual)
        assert float(jnp.abs(total - whole).max()) <= LOGIT_ATOL


@pytest.mark.parametrize("batch,t,d,tile,dtype", [
    (2, 64, 64, 32, jnp.float32),      # two whole tiles
    (2, 40, 128, 16, jnp.float32),     # T that is no whole tile: padded
    (1, 100, 64, 32, jnp.float32),     # ... and three tiles and a part
    (2, 64, 64, 512, jnp.float32),     # one tile shorter than `tile`
    (2, 48, 128, 16, jnp.bfloat16),    # the compute dtype of the cells
])
def test_short_conv_kernel_matches_its_jnp_form(batch, t, d, tile, dtype):
    """`short_conv` (the kernels, interpret mode) against
    `short_conv_xla`, forward and the gradients of both arguments, also
    under `jax.checkpoint`. Both compute in float32 and cast once:
    measured 5e-7 forward and on the product's gradient, 7e-6 of 42 on
    the taps' (a sum over B x T in another order)."""
    keys = jax.random.split(jax.random.key(0), 3)
    bcx = jax.random.normal(keys[0], (batch, t, 3 * d)).astype(dtype)
    taps = jax.random.uniform(keys[1], (3, d), minval=-0.6, maxval=0.6)
    w = jax.random.normal(keys[2], (batch, t, d))
    f32 = functools.partial(jnp.asarray, dtype=jnp.float32)

    def scalar(fn):
        return lambda a, b: (f32(fn(a, b)) * w).sum()

    ours = jax.checkpoint(lambda a, b: short_conv(a, b, tile))
    exact = dtype == jnp.bfloat16       # one rounding of the same float32
    got, want = jax.jit(ours)(bcx, taps), short_conv_xla(bcx, taps)
    assert got.dtype == dtype
    assert float(jnp.abs(f32(got) - f32(want)).max()) <= (0 if exact
                                                          else 2e-6)
    got = jax.jit(jax.grad(scalar(ours), (0, 1)))(bcx, taps)
    want = jax.grad(scalar(short_conv_xla), (0, 1))(bcx, taps)
    for a, b in zip(got, want):
        assert a.dtype == b.dtype
        scale = float(jnp.abs(f32(b)).max())
        assert float(jnp.abs(f32(a) - f32(b)).max()) <= 1e-6 * scale * (
            4000 if exact else 1)       # bf16: a last place of 2**-8


def test_sigmoid_routing_with_a_selection_bias():
    """The bias moves the CHOICE and not the weights: by hand on four
    experts, top-2."""
    logits = jnp.log(jnp.array([[0.6, 0.5, 0.4, 0.3]]) /
                     (1 - jnp.array([[0.6, 0.5, 0.4, 0.3]])))   # s = those
    idx, w, moved = route_sigmoid_bias(logits, jnp.zeros(4), 2)
    assert idx.tolist() == [[0, 1]] and int(moved) == 0
    assert w[0].tolist() == pytest.approx([0.6 / 1.1, 0.5 / 1.1], rel=1e-5)
    idx, w, moved = route_sigmoid_bias(
        logits, jnp.array([0.0, 0.0, 0.0, 0.25]), 2)
    assert idx.tolist() == [[0, 3]] and int(moved) == 1
    assert w[0].tolist() == pytest.approx([0.6 / 0.9, 0.3 / 0.9], rel=1e-5)
    # the rule: below the mean up, above it down, at it unmoved
    assert balance_bias(jnp.zeros(4), jnp.array([1, 3, 2, 2]), 0.5).tolist() \
        == [0.5, -0.5, 0.0, 0.0]
    # no gradient reaches the bias
    y = jax.random.normal(jax.random.key(0), (16, 8))
    ws = [jax.random.normal(k, s) * 0.3 for k, s in zip(
        jax.random.split(jax.random.key(1), 3),
        [(4, 8, 4), (4, 8, 4), (4, 4, 8)])]
    r = jax.random.normal(jax.random.key(2), (16, 4))
    g = jax.grad(lambda b: dropless_moe(
        y, r, *ws, top_k=2, held=(0, 4), tile=8, activation="silu",
        bias=b)[0].sum())(jnp.array([0.1, -0.2, 0.3, 0.0]))
    assert g.tolist() == [0.0] * 4


@pytest.mark.parametrize("change,message", [
    ({"attention": ("conv", "ring")}, "layer kinds built so far"),
    ({"mlp": ("dense", "switch")}, "layer kinds built so far"),
    ({"rotary": ("conv",)}, "attention kinds"),
    ({"routing": "hash"}, "routing of"),
    ({"router_input": "residual"}, "router_input is one of"),
    ({"activation": "gelu"}, "activation of"),
    ({"n_layers": 5}, "leading layers and whole periods"),
    ({"lead_mlp": ("dense",)}, "leading layers and whole periods"),
])
def test_config_refuses_what_is_not_built(change, message):
    base = dict(
        vocab_size=64, n_layers=4, d_model=32, n_heads=2, n_kv_heads=1,
        head_dim=16, attention=("conv", "full"), mlp=("dense", "experts"),
        window=0, rope_theta=1e6, n_experts=4, top_k=2, d_expert=16,
        held=(0, 4), d_dense=32)
    decoder.DecoderConfig(**base)
    with pytest.raises(ValueError, match=message):
        decoder.DecoderConfig(**dict(base, **change))


def _operator_cls():
    import optax

    from ray_tpu.train import TrainingOperator

    model = manifest.config_file("lfm2_tiny")

    class TinyLfm2Operator(TrainingOperator):
        def setup(self, config):
            p = lfm2.pieces(model, {"batch": 2, "seq": 64}, 5)
            self.register(model_init=p.model_init, loss_fn=p.loss_fn,
                          optimizer=optax.adamw(3e-4), stateful=True)
            self.register_data(train_loader=[p.batch] * 3)

    return TinyLfm2Operator


def test_bias_and_its_counters_through_the_operator():
    """The fused step (loss, gradients, AdamW, the bias's move, donated
    state) through `train_epoch`: the bias is model state beside the
    counters, it moves, AdamW holds no moments for it, and its counters
    are read once in `train.sync`."""
    op = _operator_cls()({}, 0, 1)
    before = jnp.array(op.model_state["expert_bias"])
    assert before.shape == (4, 8)
    for steps in (3, 2):        # the second epoch's counters start at zero
        c = op.train_epoch(num_steps=steps)["counters"]
        assert c["moe_steps"] == steps
        assert c["moe_assignments"] == steps * 4 * 128 * 2
        assert 0 < c["moe_assignments_held"] < c["moe_assignments"]
        assert c["moe_assignments_dropped"] == 0
        assert 0 <= c["moe_assignments_bias_moved"] < c["moe_assignments"]
        assert (c["moe_experts_held"], c["moe_experts_total"]) == (4, 8)
    after = op.model_state["expert_bias"]
    # five steps of +-1e-3 (or 0) on every entry
    step = jnp.abs(after - before)
    assert float(step.max()) <= 5e-3 + 1e-6 and float(step.max()) >= 1e-3 - 1e-6
    assert c["moe_bias_abs_max"] == pytest.approx(
        float(jnp.abs(after).max()))
    moments = jax.tree.leaves(op.opt_state)
    assert sum(x.size for x in moments if x.ndim) == 2 * sum(
        x.size for x in jax.tree.leaves(op.params))
    assert "expert_bias" in op.state_dict()["model_state"]
