"""The pattern decoder's state-space mixer, its layers that are a mixer
or an MLP alone and its ungated experts (`models/decoder.py`), the
Mamba-2 scan (`ops/ssd.py`: kernels in interpret mode) and the ungated
expert layer (`parallel/moe.py`), against the plain float32 reference
`benchmark/families/nemotron_h_reference.py`, whose scan is the
recurrence walked position by position. CPU, tiny widths: hidden 64,
eight blocks `MEM*EEME` (five layers: (ssm, experts), (ssm, none),
(full, experts), (none, experts), (ssm, experts)), 8 state-space heads
of 8 in 2 groups with a state of 16, chunks of 16, 4 attention heads
over 2 key/value heads, 8 experts of width 40 top-3 with a shared one of
80, T 64.

Tolerances. Program and reference both compute in float32 here, so what
separates them is the order of float32 sums (and, in the scan, the
chunked form against the recurrence): measured 9e-8 on the loss, 8e-7
of a leaf's largest gradient, 7e-7 on the scan's gradients at an
ordinary decay and 2e-4 on A's where a chunk forgets (a sum of terms
near 1e2 that cancel), 3e-7 on a logit. LOSS_RTOL, LOGIT_ATOL and
GRAD_RTOL sit some way above that. At seeded weights the loss sits near
log(256) whatever the blocks compute (leaving dt's bias out moves it by
1e-6), so `test_mutation_is_told_apart` reads the LOGITS: the smallest
mutation (rotary positions) moves one by 6.8e-4."""

import dataclasses

import jax
import jax.numpy as jnp
import pytest

from benchmark import manifest
from benchmark.families import nemotron_h, nemotron_h_reference as reference
from ray_tpu.models import decoder
from ray_tpu.ops import moe_gmm, ssd as ssd_ops
from ray_tpu.parallel import moe

LOSS_RTOL = 3e-6
LOGIT_ATOL = 1e-5
GRAD_RTOL = 2e-5      # of the leaf's largest reference gradient

MODEL = manifest.config_file("nemotron_tiny")
HELD = {"all": (0, 8), "subset": (2, 4)}


def _setup(held, seed=0):
    model = dict(MODEL, held_experts_first=held[0], n_routed_experts=held[1])
    cfg = dataclasses.replace(nemotron_h.model_cfg(model), dtype=jnp.float32)
    key = jax.random.key(seed)
    params, state = decoder.init(key, cfg), decoder.state_init(key, cfg)
    # norms away from one, and a bias large enough to move a good share
    # of the choices
    noise = iter(jax.random.split(jax.random.key(seed + 2), 64))
    params = jax.tree_util.tree_map_with_path(
        lambda path, leaf: leaf + 0.3 * jax.random.normal(
            next(noise), leaf.shape)
        if "norm" in jax.tree_util.keystr(path) else leaf, params)
    state["expert_bias"] = 5 * state["expert_bias"]
    tokens = jax.random.randint(jax.random.key(seed + 1), (2, 64), 0,
                                cfg.vocab_size)
    return cfg, params, state, tokens, model


def _reference_logits(params, bias, tokens, model, mutate=""):
    with jax.default_matmul_precision("highest"):
        return jax.jit(jax.vmap(lambda row: reference.forward(
            params, bias, row, model, mutate)[0]))(tokens)


def _reference_loss(params, bias, tokens, model, mutate=""):
    with jax.default_matmul_precision("highest"):
        return sum(reference.nll_sum(params, bias, row, model, mutate)[0]
                   for row in tokens) / (tokens.shape[0]
                                         * (tokens.shape[1] - 1))


@pytest.fixture(scope="module")
def program():
    """The program's loss, new state, gradients and logits, once a held
    share."""
    out = {}
    for name, held in HELD.items():
        cfg, params, state, tokens, _ = _setup(held)
        (loss, new), grads = jax.jit(jax.value_and_grad(
            lambda p: decoder.stateful_loss(p, state, tokens, cfg),
            has_aux=True))(params)
        out[name] = (loss, new, grads, jax.jit(
            lambda p: decoder.apply(p, tokens, cfg, state["expert_bias"]))(
                params))
    return out


def test_layers_hold_no_leaf_of_the_absent_side():
    """(ssm, none) has one norm and no expert leaf, (none, experts) one
    norm and no mixer leaf; nothing is gated, so no gate leaf exists."""
    cfg, params, state, _, _ = _setup(HELD["all"])
    assert cfg.kinds == (("ssm", "experts"), ("ssm", "none"),
                         ("full", "experts"), ("none", "experts"),
                         ("ssm", "experts"))
    stacks = {name: leaf.shape[0] for name, leaf in params["layers"].items()}
    assert stacks == {
        "norm1": 4, "norm2": 4, "wq": 1, "wk": 1, "wv": 1, "wo": 1,
        "ssm_in": 3, "ssm_conv": 3, "ssm_conv_bias": 3, "A_log": 3, "D": 3,
        "dt_bias": 3, "ssm_norm": 3, "ssm_out": 3, "router": 4, "w_up": 4,
        "w_down": 4, "ws_up": 4, "ws_down": 4}
    assert params["layers"]["ssm_in"].shape == (3, 64 + 128 + 8, 64)
    assert params["layers"]["w_up"].shape == (4, 8, 40, 64)
    assert state["expert_bias"].shape == (4, 8)
    # Mamba-2's own start: A in [1, 16], dt in [1e-3, 0.1]
    a = jnp.exp(params["layers"]["A_log"])
    dt = jax.nn.softplus(params["layers"]["dt_bias"])
    assert 1 <= a.min() and a.max() <= 16
    assert 1e-3 * 0.999 <= dt.min() and dt.max() <= 0.1 * 1.001
    # (one attention layer of four heads, forward and rematerialised:
    # 2 sequences x 4 x 2 planes of 4 x 2 tiles of 16 x 32)
    assert decoder.step_facts(cfg, (2, 64)) == {
        "ssm_layers": 3, "ssm_chunks": 3 * 2 * 4,
        "attention_tiles_unmasked": 2 * 4 * 2 * 2,
        "attention_tiles_walked": 2 * 4 * 2 * 6}


@pytest.mark.parametrize("change,message", [
    ({"attention": ("none",), "mlp": ("none",), "n_layers": 1}, "no layer"),
    ({"attention": ("none",), "mlp": ("experts",), "n_layers": 1,
      "router_input": "mixer"}, "reads the MLP's norm"),
    ({"ssm_heads": 0}, "ssm mixer needs"),
    ({"ssm_groups": 3}, "ssm mixer needs"),
    ({"activation": "relu3"}, "activation of")])
def test_config_refuses_what_is_not_built(change, message):
    cfg = nemotron_h.model_cfg(MODEL)
    with pytest.raises(ValueError, match=message):
        dataclasses.replace(cfg, **change)


@pytest.mark.parametrize("share", list(HELD))
def test_decoder_matches_reference(program, share):
    """Loss, every gradient, the selection bias after the step and the
    scan's counters, against the reference and its recurrence."""
    cfg, params, state, tokens, model = _setup(HELD[share])
    loss, new, grads, logits = program[share]
    assert float(jnp.abs(logits - _reference_logits(
        params, state["expert_bias"], tokens, model)).max()) <= LOGIT_ATOL
    ref_loss, ref_grads = jax.jit(jax.value_and_grad(
        lambda p: _reference_loss(p, state["expert_bias"], tokens, model)))(
            params)
    assert abs(float(loss) - float(ref_loss)) <= LOSS_RTOL * float(ref_loss)
    flat, _ = jax.tree_util.tree_flatten_with_path(grads)
    for (path, g), r in zip(flat, jax.tree.leaves(ref_grads)):
        assert float(jnp.abs(g - r).max()) <= GRAD_RTOL * float(
            jnp.abs(r).max()), jax.tree_util.keystr(path)
    with jax.default_matmul_precision("highest"):
        outs = [jax.jit(lambda row: reference.forward(
            params, state["expert_bias"], row, model))(row) for row in tokens]
    n = sum(o[1] for o in outs)
    assert jnp.array_equal(
        new["expert_bias"],
        reference.bias_update(state["expert_bias"], n, cfg.bias_rate))
    counters = new["epoch_counters"]
    assert float(counters["ssm_log_decay_min"]) == pytest.approx(
        min(float(o[2]) for o in outs), rel=1e-5)
    assert float(counters["ssm_dt_max"]) == pytest.approx(
        max(float(o[3]) for o in outs), rel=1e-6)
    assert int(counters["moe_assignments"]) == 4 * 2 * 64 * 3
    assert int(counters["moe_assignments_dropped"]) == 0
    assert float(counters["moe_rows_static"]) == 4 * moe.static_rows(
        2 * 64 * 3, HELD[share][1], cfg.gmm_tile)
    assert counters["moe_rows_filled"] == counters["moe_assignments_held"]
    # every layer walked a rung that holds what its routing filled
    assert counters["moe_rows_filled"] <= counters["moe_rows_walked"] <= (
        counters["moe_rows_static"])


def test_the_convolution_as_a_kernel_is_the_plain_forms(program,
                                                        plain_mixer_conv):
    """The preset's xBC is 128 channels, one lane tile: `program` ran
    `mixer_conv`'s kernels. The loss and every leaf's gradient with the
    plain form in their place are the same."""
    cfg, params, state, tokens, _ = _setup(HELD["all"])
    loss, _, grads, _ = program["all"]
    tiled = plain_mixer_conv()
    want_loss, want = jax.jit(jax.value_and_grad(
        lambda p: decoder.stateful_loss(p, state, tokens, cfg)[0]))(params)
    assert tiled and all(tiled)
    assert abs(float(loss) - float(want_loss)) <= LOSS_RTOL * float(want_loss)
    flat, _ = jax.tree_util.tree_flatten_with_path(grads)
    for (path, got), ref in zip(flat, jax.tree.leaves(want)):
        assert float(jnp.abs(got - ref).max()) <= GRAD_RTOL * float(
            jnp.abs(ref).max()), jax.tree_util.keystr(path)


@pytest.mark.parametrize("name", reference.MUTATIONS)
def test_mutation_is_told_apart(program, name):
    """An alternative the configuration did not take moves a logit by
    more than fifty times the tolerance."""
    _, params, state, tokens, model = _setup(HELD["all"])
    mutated = _reference_logits(params, state["expert_bias"], tokens, model,
                                name)
    assert float(jnp.abs(mutated - program["all"][3]).max()) \
        > 50 * LOGIT_ATOL, name


def test_shares_add_up_to_the_uncut_layer():
    """The routed parts of the two shares of four experts, with the
    shared expert counted once, are the uncut reference's expert block;
    the program's share is its share of it."""
    cfg, params, state, tokens, model = _setup(HELD["all"])
    p = {name: leaf[0] for name, leaf in params["layers"].items()
         if reference._GROUP_OF[name] in ("mlp", "experts")}
    u = jax.random.normal(jax.random.key(5), (64, 64))
    bias = state["expert_bias"][0]
    with jax.default_matmul_precision("highest"):
        whole, _, _ = reference.experts(u, p, bias, model, first=0)
        shared = reference._act(u @ p["ws_up"]) @ p["ws_down"]
        parts = []
        for first in (0, 4):
            share = {**p, "w_up": p["w_up"][first:first + 4],
                     "w_down": p["w_down"][first:first + 4]}
            parts.append(reference.experts(
                u, share, bias, dict(model, n_routed_experts=4),
                first=first)[1])
            got, _ = moe.dropless_moe(
                u, jnp.dot(u, p["router"], precision="highest"), None,
                share["w_up"], share["w_down"], top_k=3, held=(first, 4),
                tile=8, activation="relu2", bias=bias, scale=2.5)
            assert jnp.allclose(got, parts[-1], atol=1e-5)
    assert jnp.allclose(parts[0] + parts[1] + shared, whole, atol=1e-5)


def test_ungated_experts_against_a_loop_over_the_experts():
    """`dropless_moe` with no gate, values and gradients, against each
    held expert applied to the tokens that chose it; the gated form of
    the same weights differs."""
    n, d, f, e, k = 48, 32, 40, 8, 3
    keys = jax.random.split(jax.random.key(3), 5)
    y = jax.random.normal(keys[0], (n, d))
    logits = jax.random.normal(keys[1], (n, e))
    w_up = jax.random.normal(keys[2], (4, f, d)) * 0.2
    w_down = jax.random.normal(keys[3], (4, f, d)) * 0.2
    held = (2, 4)

    def plain(y, logits, w_up, w_down):
        idx, weights = moe.route_topk(logits, k)
        out = 0
        for j in range(held[1]):
            p_e = (weights * (idx == held[0] + j)).sum(-1)
            out = out + p_e[:, None] * (
                jnp.square(jax.nn.relu(y @ w_up[j].T)) @ w_down[j])
        return out

    def layer(y, logits, w_up, w_down):
        return moe.dropless_moe(y, logits, None, w_up, w_down, top_k=k,
                                held=held, tile=8, activation="relu2")[0]

    args = (y, logits, w_up, w_down)
    assert jnp.allclose(layer(*args), plain(*args), atol=1e-5)
    cot = jax.random.normal(keys[4], (n, d))
    got = jax.jit(jax.grad(lambda *a: (layer(*a) * cot).sum(),
                           argnums=(0, 1, 2, 3)))(*args)
    want = jax.jit(jax.grad(lambda *a: (plain(*a) * cot).sum(),
                            argnums=(0, 1, 2, 3)))(*args)
    for g, r in zip(got, want):
        assert float(jnp.abs(g - r).max()) <= 1e-5 * max(
            1.0, float(jnp.abs(r).max()))
    up = w_up.swapaxes(1, 2)
    gated = moe.dropless_moe(y, logits, up, up, w_down, top_k=k,
                             held=held, tile=8, activation="relu2")[0]
    assert not jnp.allclose(gated, layer(*args), atol=1e-3)


def _scan_inputs(seed, strong, b=2, t=64, h=4, p=8, g=2, n=16):
    keys = jax.random.split(jax.random.key(seed), 7)
    dt = jax.nn.softplus(jax.random.normal(keys[1], (b, t, h))
                         + (2.0 if strong else -2.0))
    a = -jnp.exp(jax.random.uniform(keys[2], (h,), minval=0.0,
                                    maxval=3.0 if strong else 1.0))
    return (jax.random.normal(keys[0], (b, t, h, p)), dt, a,
            jax.random.normal(keys[3], (b, t, g, n)),
            jax.random.normal(keys[4], (b, t, g, n)),
            jax.random.normal(keys[5], (h,))), \
        jax.random.normal(keys[6], (b, t, h, p))


def _recurrence(x, dt, a, b, c, d):
    per = x.shape[2] // b.shape[2]
    return jnp.stack([
        reference.scan(x[i], dt[i], a, jnp.repeat(b[i], per, axis=1),
                       jnp.repeat(c[i], per, axis=1), d)
        for i in range(x.shape[0])])


@pytest.mark.parametrize("form", ["ssd", "ssd_xla"])
@pytest.mark.parametrize("strong", [False, True],
                         ids=["ordinary decay", "a chunk nearly forgets"])
def test_scan_against_the_recurrence(form, strong):
    """Values and the gradients of x, dt, A, B, C and D, both forms
    against the reference's position-by-position recurrence; with the
    strong decay the sum of dt A over a chunk of 16 reaches -370, far
    below float32's exp(-87): the chunk forgets what entered it, and
    nothing overflows above the diagonal."""
    args, cot = _scan_inputs(1, strong)
    chunk = 16
    total = (args[1] * args[2]).reshape(2, 4, chunk, 4).sum(2).min()
    assert (total < -87) == strong
    fn = getattr(ssd_ops, form)
    want = jax.jit(_recurrence)(*args)
    got = jax.jit(lambda *a: fn(*a, chunk))(*args)
    assert jnp.isfinite(got).all()
    assert float(jnp.abs(got - want).max()) <= 2e-6 * float(
        jnp.abs(want).max())
    argnums = tuple(range(6))
    g_want = jax.jit(jax.grad(lambda *a: (_recurrence(*a) * cot).sum(),
                              argnums=argnums))(*args)
    g_got = jax.jit(jax.grad(lambda *a: (fn(*a, chunk) * cot).sum(),
                             argnums=argnums))(*args)
    for name, g, r in zip("x dt A B C D".split(), g_got, g_want):
        rtol = 1e-3 if strong and name == "A" else 5e-6
        assert float(jnp.abs(g - r).max()) <= rtol * float(
            jnp.abs(r).max()), name


def test_scan_in_bfloat16_and_its_refusals():
    """bf16 inputs (what the decoder hands it): the products round, the
    sums and the state do not; whole chunks only."""
    args, _ = _scan_inputs(2, False)
    low = tuple(z.astype(jnp.bfloat16) if i in (0, 3, 4) else z
                for i, z in enumerate(args))
    want = _recurrence(*(z.astype(jnp.float32) for z in low))
    got = ssd_ops.ssd(*low, 16)
    assert got.dtype == jnp.bfloat16
    assert float(jnp.abs(got.astype(jnp.float32) - want).max()) <= 2e-2 \
        * float(jnp.abs(want).max())
    with pytest.raises(ValueError, match="whole chunks"):
        ssd_ops.ssd(*args, 48)
    with pytest.raises(ValueError, match="whole chunks"):
        ssd_ops.ssd_xla(*args, 48)
    with pytest.raises(ValueError, match="multiple of G"):
        ssd_ops.ssd(args[0], args[1], args[2], args[3][:, :, :1].repeat(
            3, 2), args[4][:, :, :1].repeat(3, 2), args[5], 16)


def test_scan_is_two_named_kernels_and_saves_states_only_under_grad():
    args, cot = _scan_inputs(3, False)
    plain = str(jax.make_jaxpr(lambda *a: ssd_ops.ssd(*a, 16))(*args))
    assert plain.count("pallas_call") == 1 and "ssd_fwd" in plain
    assert "f32[2,4,4,8,16]" not in plain       # no entering states
    grad = str(jax.make_jaxpr(jax.grad(
        lambda *a: (ssd_ops.ssd(*a, 16) * cot).sum(), argnums=(0, 1)))(*args))
    assert grad.count("pallas_call") == 2
    assert "ssd_fwd" in grad and "ssd_bwd" in grad
    assert "f32[2,4,4,8,16]" in grad            # [B, T / Q, H, P, N]


@pytest.mark.parametrize("dim,prefs,tile", [
    (1856, (512, 384, 256, 128), 1856),     # no lane tile divides 14.5 x 128
    (1856, (1280, 1024, 768, 512, 384, 256, 128), 1856),
    (1792, (512, 384, 256, 128), 256),      # lfm2's width keeps its tiles
    (1792, (1280, 1024, 768, 512, 384, 256, 128), 256),
    (768, (512, 384, 256, 128), 384), (2688, (512, 384, 256, 128), 384),
    (3712, (512, 384, 256, 128), 128), (40, (512, 384, 256, 128), 40)])
def test_weight_block_of_a_width_no_tile_divides_is_the_whole_width(
        dim, prefs, tile):
    assert moe_gmm._divisor(dim, prefs) == tile


def _operator_cls():
    from benchmark.common import Repeat
    from ray_tpu.train.operator import TrainingOperator

    class TinyNemotronOperator(TrainingOperator):
        def setup(self, config):
            pieces = nemotron_h.pieces(MODEL, {"batch": 2, "seq": 64}, seed=3)
            self.register(model_init=pieces.model_init,
                          loss_fn=pieces.loss_fn, optimizer=pieces.optimizer,
                          stateful=True)
            self.register_data(train_loader=Repeat(pieces.batch))

    return TinyNemotronOperator


def test_scan_counters_and_facts_land_on_the_calls_span_tree(
        ray_start_shared):
    """Through `Trainer.train()`, the family's own pieces: the scan's
    counters are attributes of the worker's `train.sync` span, its
    static facts (`loss_fn.step_facts`) of `train.dispatch`, on every
    call; the loss falls."""
    from ray_tpu.train import Trainer, call_log

    tr = Trainer(_operator_cls(), num_workers=1)
    try:
        losses = []
        for _ in range(2):
            out = tr.train(num_steps=2)
            spans = {s["name"]: s["attrs"] for s in call_log()[-1]["spans"]}
            sync, dispatch = spans["train.sync"], spans["train.dispatch"]
            assert sync == out["counters"]
            assert sync["moe_steps"] == 2
            assert -87 < sync["ssm_log_decay_min"] < 0
            assert 0 < sync["ssm_dt_max"] < 1
            assert sync["moe_rows_filled"] == sync["moe_assignments_held"]
            assert (dispatch["ssm_layers"], dispatch["ssm_chunks"],
                    dispatch["steps"]) == (3, 3 * 2 * 4, 2)
            losses.append(out["last_train_loss"])
        assert losses[1] < losses[0]
    finally:
        tr.shutdown(force=True)
