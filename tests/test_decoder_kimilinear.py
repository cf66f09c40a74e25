"""The pattern decoder's kda mixer (`ops/kda.py` in chunks), the latent
mixer without a query rank and without positions, and the lead + period
that mixes them with the expert block (`models/decoder.py`), against the
plain float32 reference `benchmark/families/kimi_linear_reference.py`,
which walks the delta rule position by position. CPU, tiny widths
(`benchmark/configs/kimilinear_tiny.json`): hidden 64, five layers [kda
+ dense, kda, kda, latent, kda] the last four with experts, 4 KDA heads
of 16 / 16 behind a 4-tap convolution with low ranks of 16, latent
attention at 4 heads of 16 + 8 / 16 over a latent of 32, a shared expert
beside top-3 of 16 experts, experts 4..7 held, T 128 (two chunks of the
rule's 64); the kernels run in interpret mode.

Tolerances. Program and reference both compute in float32 here, so what
separates them is the order of float32 sums and the chunked form's
inverse against the recurrence: LOSS_RTOL, LOGIT_ATOL and GRAD_RTOL sit
some way above what was measured (in `test_decoder_matches_reference`'s
note), and far below what the smallest control moves."""

import dataclasses
import functools
import hashlib

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from benchmark import manifest
from benchmark.families import kimi_linear, kimi_linear_reference as reference
from ray_tpu.models import decoder
from ray_tpu.parallel.moe import static_rows

LOSS_RTOL = 3e-6
LOGIT_ATOL = 2e-4
GRAD_RTOL = 2e-3      # of the leaf's largest reference gradient

MODEL = manifest.config_file("kimilinear_tiny")
ALL, HELD = (0, 16), (4, 4)     # every expert held; experts 4..7 of 16
T = 128


@functools.lru_cache
def _setup(held, seed=0):
    model = dict(MODEL, held_experts_first=held[0], num_experts=held[1])
    cfg = dataclasses.replace(kimi_linear.model_cfg(model),
                              dtype=jnp.float32)
    key = jax.random.key(seed)
    params, state = decoder.init(key, cfg), decoder.state_init(key, cfg)
    # norms away from their start, gates and write strengths away from
    # one half, decays that differ by channel and position
    noise = iter(jax.random.split(jax.random.key(seed + 2), 64))

    def moved(path, leaf):
        name = jax.tree_util.keystr(path)
        if "norm" in name:
            return leaf + 0.3 * jax.random.normal(next(noise), leaf.shape)
        if "kda_beta" in name or "kda_down" in name:
            return leaf * 20
        if "kda_f_up" in name or "kda_g_up" in name:
            return leaf * 10
        return leaf * 4 if "kda_in" in name or "wq_latent" in name \
            or "wkv" in name else leaf

    params = jax.tree_util.tree_map_with_path(moved, params)
    tokens = jax.random.randint(jax.random.key(seed + 1), (2, T), 0,
                                cfg.vocab_size)
    return cfg, params, state, tokens, model


def _reference(params, bias, tokens, model, mutate=""):
    """(mean loss, (logits [B, T, V], n [layers, E])): one pass."""
    outs = [reference.forward(params, bias, row, model, mutate)
            for row in tokens]
    logits = jnp.stack([o[0] for o in outs])
    logp = jax.nn.log_softmax(logits[:, :-1].astype(jnp.float32), axis=-1)
    nll = -jnp.take_along_axis(logp, tokens[:, 1:, None], axis=-1)
    return nll.mean(), (logits, sum(o[1] for o in outs))


@pytest.fixture(scope="module")
def program():
    """The program's loss, new state, logits and gradients with a held
    subset (`test_shares_add_up_to_the_uncut_layer` holds them all)."""
    cfg, params, state, tokens, _ = _setup(HELD)
    (loss, new), grads = jax.jit(jax.value_and_grad(
        lambda p: decoder.stateful_loss(p, state, tokens, cfg),
        has_aux=True))(params)
    logits = jax.jit(lambda p: decoder.apply(
        p, tokens, cfg, state["expert_bias"]))(params)
    return float(loss), logits, grads, new


@pytest.fixture(scope="module")
def exact():
    """The float32 reference's loss, logits, counts and gradients."""
    _, params, state, tokens, model = _setup(HELD)
    with jax.default_matmul_precision("highest"):
        return jax.jit(jax.value_and_grad(
            lambda p: _reference(p, state["expert_bias"], tokens, model),
            has_aux=True))(params)


def test_parameter_tree_state_and_facts():
    cfg, params, state, _, _ = _setup(HELD)
    assert cfg.kinds == (("kda", "dense"), ("kda", "experts"),
                         ("kda", "experts"), ("latent", "experts"),
                         ("kda", "experts"))
    assert (cfg.lead_attention, cfg.attention) == (
        ("kda",), ("kda", "kda", "latent", "kda"))
    stacks = {k: v.shape for k, v in params["layers"].items()}
    assert stacks["kda_in"] == (4, 64, 3 * 64) \
        and stacks["kda_conv"] == (4, 4, 3 * 64) \
        and stacks["kda_down"] == (4, 64, 2 * 16) \
        and stacks["kda_f_up"] == stacks["kda_g_up"] == (4, 16, 64) \
        and stacks["kda_beta"] == (4, 4, 64) \
        and stacks["kda_A_log"] == (4, 4) \
        and stacks["kda_dt_bias"] == (4, 64) \
        and stacks["kda_norm"] == (4, 16) \
        and stacks["kda_out"] == (4, 64, 64)
    # no query rank: one direct product, none of the rank's leaves
    assert stacks["wq_latent"] == (1, 64, 4 * 24) \
        and stacks["wkv_a"] == (1, 64, 32 + 8) \
        and stacks["wkv_b"] == (1, 32, 4 * 32) \
        and stacks["wo_latent"] == (1, 4 * 16, 64)
    assert stacks["w1"] == (1, 64, 96) and stacks["router"] == (4, 64, 16) \
        and stacks["w_gate"] == (4, 4, 64, 32) \
        and stacks["ws_gate"] == (4, 64, 32) \
        and stacks["norm1"] == stacks["norm2"] == (5, 64)
    assert not {"wq_a", "wq_b", "q_a_norm", "wq", "delta_in", "A_log",
                "ws_token_gate"} & set(stacks)
    fresh = decoder.init(jax.random.key(0), cfg)["layers"]
    a = jnp.exp(fresh["kda_A_log"])
    assert float(a.min()) >= 1 and float(a.max()) <= 16
    dt = jax.nn.softplus(fresh["kda_dt_bias"])
    assert 1e-4 <= float(dt.min()) < float(dt.max()) <= 0.11
    assert (fresh["kda_norm"] == 1).all()
    assert state["expert_bias"].shape == (4, 16)
    counters = set(state["epoch_counters"])
    assert {"kda_log_decay_min", "kda_decay_spread_sum",
            "kda_decay_spread_count", "kda_beta_sum", "kda_beta_count",
            "kda_gate_sum", "kda_gate_count", "moe_rows_static",
            "moe_rows_filled", "moe_bias_abs_max"} <= counters
    assert not {c for c in counters if c.startswith(("delta_", "ssm_"))}
    facts = decoder.step_facts(cfg, (2, T))
    assert facts.pop("attention_tiles_walked") \
        >= facts.pop("attention_tiles_unmasked") >= 0
    assert facts == {"kda_layers": 4, "kda_chunks": 4 * 2 * (T // 64),
                     "kda_heads": 4, "rope_dim": 0}
    # the latent kind turns nothing: no table is made
    assert decoder._rope_for(T, cfg) == {}
    # readers that ask for qwen3next's delta layers still get three
    model = manifest.config_file("qwen3next_80b_a3b_ep16")
    other = manifest.module("families", "qwen3_next").model_cfg(model)
    facts = decoder.step_facts(other, (2, 8192))
    assert facts["delta_layers"] == 3 and "kda_layers" not in facts


def test_the_cut_has_the_parameters_the_issue_counted():
    """`kimilinear_48b_a3b_ep32` from the built tree: 602 433 408
    parameters, by part."""
    cfg = kimi_linear.model_cfg(
        manifest.config_file("kimilinear_48b_a3b_ep32"))
    shapes = jax.eval_shape(lambda k: decoder.init(k, cfg),
                            jax.random.key(0))
    size = {k: int(np.prod(v.shape[1:]))
            for k, v in shapes["layers"].items()}
    assert shapes["embed"].size + shapes["head"].size == 94_371_840
    kda = sum(v for k, v in size.items() if k.startswith("kda_"))
    assert (size["kda_in"], size["kda_down"], size["kda_f_up"],
            size["kda_beta"], size["kda_out"], kda) == (
                28_311_552, 589_824, 524_288, 73_728, 9_437_184, 39_514_272)
    latent = sum(size[k] for k in ("wq_latent", "wkv_a", "kv_a_norm",
                                   "wkv_b", "wo_latent"))
    assert (size["wq_latent"], latent) == (14_155_776, 29_114_880)
    dense = size["w1"] + size["w2"] + size["w3"]
    shared = size["ws_gate"] + size["ws_up"] + size["ws_down"]
    experts = size["w_gate"] + size["w_up"] + size["w_down"]
    block = size["router"] + experts + shared + size["norm1"] + size["norm2"]
    assert (dense, size["router"], experts, shared, block) == (
        63_700_992, 589_824, 56_623_104, 7_077_888, 64_295_424)
    assert sum(x.size for x in jax.tree.leaves(shapes)) == 602_433_408 \
        == 4 * kda + latent + dense + 4608 + 4 * block + 94_371_840 + 2304
    assert shapes["layers"]["kda_in"].shape == (4, 2304, 12288) \
        and shapes["layers"]["wq_latent"].shape == (1, 2304, 6144) \
        and shapes["layers"]["w_gate"].shape == (4, 8, 2304, 1024)
    facts = decoder.step_facts(cfg, (2, 8192))
    assert facts["kda_layers"] == 4 and facts["kda_chunks"] == 4 * 2 * 128 \
        and facts["kda_heads"] == 32


def test_decoder_matches_reference(program, exact):
    """The loss, the logits, every leaf's gradient and the counters.
    Measured: the loss 1e-7 apart, a logit 2.6e-5, a leaf's gradient
    2.3e-4 of its largest (`kda_A_log`: sums over every position and
    channel of terms that cancel). The smallest control moves a logit by
    0.02."""
    cfg, params, state, tokens, model = _setup(HELD)
    loss, logits, grads, new = program
    (ref_loss, (ref_logits, n)), ref_grads = exact
    assert abs(loss - float(ref_loss)) <= LOSS_RTOL * float(ref_loss)
    assert float(jnp.abs(logits - ref_logits).max()) <= LOGIT_ATOL
    flat, _ = jax.tree_util.tree_flatten_with_path(grads)
    for (path, got), want in zip(flat, jax.tree.leaves(ref_grads)):
        scale = float(jnp.abs(want).max())
        assert scale > 0, path       # every leaf is reached by the loss
        assert float(jnp.abs(got - want).max()) <= GRAD_RTOL * scale, path
    # by parameter group, as the chip's control tool compares them
    norms, ref_norms = (kimi_linear.group_norms(g) for g in (grads, ref_grads))
    assert set(norms) >= {"kda_W_f", "kda_W_g", "kda_A_log", "kda_dt_bias",
                          "latent", "router", "experts", "head"}
    for group, want in ref_norms.items():
        assert abs(norms[group] - want) <= GRAD_RTOL * want, group
    c = new["epoch_counters"]
    first, count = HELD
    assert int(c["moe_assignments"]) == 4 * tokens.size * 3
    assert int(c["moe_assignments_held"]) == int(
        n[:, first:first + count].sum())
    assert int(c["moe_assignments_dropped"]) == 0
    assert int(c["moe_rows_static"]) == 4 * static_rows(
        tokens.size * 3, count, cfg.gmm_tile)
    assert int(c["moe_rows_filled"]) == int(c["moe_assignments_held"])
    # every layer walked a rung that holds what its routing filled
    assert int(c["moe_rows_filled"]) <= int(c["moe_rows_walked"]) <= int(
        c["moe_rows_static"])
    assert int(c["moe_rows_walked"]) % cfg.gmm_tile == 0
    # the selection bias made the reference's step
    np.testing.assert_allclose(
        new["expert_bias"],
        reference.bias_update(state["expert_bias"], n,
                              model["expert_bias_update_rate"]), atol=1e-7)
    # the gate and the write strength are computed, on every element,
    # and are not stuck at one half; the decay is seen, and differs over
    # a head's channels
    assert int(c["kda_beta_count"]) == tokens.size * 4 * 4
    assert int(c["kda_gate_count"]) == tokens.size * 4 * 4 * 16
    assert int(c["kda_decay_spread_count"]) == tokens.size // 64 * 4 * 4
    for name in ("kda_beta", "kda_gate"):
        opened = float(c[f"{name}_sum"] / c[f"{name}_count"])
        assert 0.3 < opened < 0.7 and abs(opened - 0.5) > 1e-4, name
    assert float(c["kda_log_decay_min"]) < -1.0
    assert float(c["kda_decay_spread_sum"]
                 / c["kda_decay_spread_count"]) > 1.0


def test_the_convolution_as_a_kernel_is_the_plain_forms(plain_mixer_conv):
    """The tiny preset's first three layers [kda + dense, kda, latent]
    with one KDA head of 128 behind the convolution (its own heads of 16
    are no whole lanes: its programs run `mixer_conv_xla`), T 64: the
    loss and every leaf's gradient with `mixer_conv`'s kernels are the
    plain form's."""
    model = dict(MODEL, num_hidden_layers=3, linear_attn_config=dict(
        MODEL["linear_attn_config"], kda_layers=[1, 2], full_attn_layers=[3]))
    cfg = dataclasses.replace(
        kimi_linear.model_cfg(model), dtype=jnp.float32, delta_key_heads=1,
        delta_value_heads=1, delta_key_dim=128, delta_value_dim=128)
    key = jax.random.key(0)
    params, state = decoder.init(key, cfg), decoder.state_init(key, cfg)
    params["layers"]["kda_in"] = params["layers"]["kda_in"] * 4
    tokens = jax.random.randint(jax.random.key(1), (2, 64), 0,
                                cfg.vocab_size)
    def step():
        return jax.jit(jax.value_and_grad(
            lambda p: decoder.stateful_loss(p, state, tokens, cfg)[0]))(
                params)

    loss, grads = step()
    tiled = plain_mixer_conv()
    want_loss, want = step()
    assert tiled and all(tiled)       # the first program ran the kernels
    assert abs(float(loss) - float(want_loss)) <= LOSS_RTOL * float(want_loss)
    flat, _ = jax.tree_util.tree_flatten_with_path(grads)
    for (path, got), ref in zip(flat, jax.tree.leaves(want)):
        scale = float(jnp.abs(ref).max())
        assert scale > 0 and float(jnp.abs(got - ref).max()) \
            <= GRAD_RTOL * scale, path


@pytest.mark.parametrize("name", reference.MUTATIONS)
def test_mutation_is_told_apart(program, name):
    """A reference with one mechanism changed — the issue's five controls
    first — must fail `test_decoder_matches_reference` by ten times
    LOGIT_ATOL on the logits (at seeded weights the loss sits near
    log(V) whatever the blocks compute: the logits tell). Not jitted:
    the programs cost more to compile than their operations to
    dispatch."""
    _, params, state, tokens, model = _setup(HELD)
    _, logits, _, _ = program
    with jax.default_matmul_precision("highest"):
        _, (ref_logits, _) = _reference(params, state["expert_bias"], tokens,
                                        model, name)
    assert float(jnp.abs(logits - ref_logits).max()) > 10 * LOGIT_ATOL


def test_bfloat16_throughout_is_told_apart(program):
    """The precision below the one the configuration states — weights,
    activations, gates, router and the rule's state in bfloat16 — reads
    above the tolerances."""
    _, params, state, tokens, model = _setup(HELD)
    loss, logits, _, _ = program
    low = jax.tree.map(lambda x: x.astype(jnp.bfloat16), params)
    ref_loss, (ref_logits, _) = jax.jit(lambda p: _reference(
        p, state["expert_bias"].astype(jnp.bfloat16), tokens, model))(low)
    assert abs(loss - float(ref_loss)) > 10 * LOSS_RTOL * loss
    assert float(jnp.abs(logits - ref_logits).max()) > 10 * LOGIT_ATOL


def test_the_program_in_bfloat16_stays_near_the_reference(exact):
    """The compute dtype the configuration states, at its stated
    tolerance: the loss within 2e-3 of the float32 reference's, a logit
    0.03 off in the mean and in the median row's worst 0.05. The WORST
    row is no measure here: a router score that bfloat16 activations
    move across a tie sends a token to another expert."""
    cfg, params, state, tokens, _ = _setup(HELD)
    low = dataclasses.replace(cfg, dtype=jnp.bfloat16)
    logits = jax.jit(lambda p: decoder.apply(
        p, tokens, low, state["expert_bias"]))(params)
    loss = -jnp.take_along_axis(
        jax.nn.log_softmax(logits[:, :-1], axis=-1), tokens[:, 1:, None],
        axis=-1).mean()
    (ref_loss, (ref_logits, _)), _ = exact
    assert abs(float(loss) - float(ref_loss)) <= 2e-3 * float(ref_loss)
    off = np.abs(np.asarray(logits - ref_logits))
    assert off.mean() <= 0.03 and np.median(off.max(-1)) <= 0.05
    assert (off.max(-1) > 0.1).mean() < 0.1


@pytest.mark.parametrize("at", [1, 3], ids=["kda", "latent"])
def test_shares_add_up_to_the_uncut_layer(at):
    """The share test: the routed parts of the eight shares (experts
    0-1, 2-3, .. of 16), with the mixer, the residual and the shared
    expert counted once, add up to the uncut reference's layer — on a
    KDA layer and on the latent layer."""
    cfg, params, state, _, model = _setup(ALL)
    mixer, mlp, row = reference.kinds(model)[at]
    assert (mixer, mlp) == cfg.kinds[at]
    p = reference.layer_leaves(params, row)
    bias = state["expert_bias"][row["experts"]]
    h = 3 * jax.random.normal(jax.random.key(7), (1, T, cfg.d_model))
    with jax.default_matmul_precision("highest"):
        whole, m, n = reference.layer(h[0], p, bias, mixer=mixer, mlp=mlp,
                                      model=model)
    assert int(n.sum()) == T * 3
    alike = whole - m       # mixer, residual and the shared expert
    total = alike
    # the program's layer in its two parts, so that the mixer's kernels
    # are compiled once and not a share
    h1, _ = jax.jit(functools.partial(
        decoder._layer, cfg=cfg, mlp="none", attention=mixer))(
            h, p, decoder._rope_for(T, cfg))
    for first in range(0, 16, 2):
        share = dataclasses.replace(cfg, held=(first, 2))
        held = dict(p, expert_bias=bias, **{
            k: p[k][first:first + 2] for k in ("w_gate", "w_up", "w_down")})
        out, counts = jax.jit(functools.partial(
            decoder._layer, cfg=share, mlp="experts", attention="none"))(
                h1, held, None)
        assert int(counts["held"]) == int(n[first:first + 2].sum())
        total = total + (out[0] - alike)
    assert float(jnp.abs(m).max()) > 1e-3
    assert float(jnp.abs(total - whole).max()) <= 5e-5


# What the configurations whose code this PR touches gave on the parent
# commit (a11437c): sha256 (16 digits) of the parameter and state tree's
# paths, shapes and dtypes at the published widths; of the jaxpr of
# value_and_grad(stateful_loss) on a batch [1, 1024] at those widths;
# and, at the configuration's tiny preset, of the bytes of every leaf
# seeded from key 0 (`tests/test_decoder_qwen3next.py::RECORDED`'s
# recipe; that file and `test_decoder_laguna.py` hold the seven before).
# (All six recorded again at PR 62: the expert block walks a rung of
# `parallel/moe.py::row_ladder` under a conditional, and the epoch
# counters gained `moe_rows_walked`. At PR 64 the step's text of
# `qwen3next_80b_a3b_ep16` again, its tree and seeded bytes the parent's
# (f6294df): its delta mixers' convolution, SiLU and unit norms are
# `ops/short_conv.py::mixer_conv`. `joyai_flash_ep16` keeps all three.
# This file's own configuration joins them from here on: tree and seeded
# bytes as that parent gave them, the step's text with the operator.)
RECORDED = {
    "qwen3next_80b_a3b_ep16": ("qwen3next_tiny", "64c5cb2f911806e0",
                               "436ba2b25318da6a", "86542d77515d2e3e"),
    "kimilinear_48b_a3b_ep32": ("kimilinear_tiny", "7d1a3bc8d25037f1",
                                "029ac954df25d363", "b30af6c1adb58c8f"),
    "joyai_flash_ep16": ("joyai_tiny", "1e46c2acd199b0f8",
                         "4a2c773ff3d2dc60", "462fab1b65ec7cc3"),
}


def _sha(text: str) -> str:
    return hashlib.sha256(text.encode()).hexdigest()[:16]


def _cfg_of(name: str):
    model = manifest.config_file(name)
    return manifest.module("families", model["family"]).model_cfg(model)


@pytest.mark.parametrize("name", list(RECORDED))
def test_an_earlier_configuration_keeps_its_program(name):
    """Tree paths and shapes, the step's traced program and the seeded
    weights of the delta-rule configuration and of the latent one with a
    query rank and a rotary turn are the parent's."""
    tiny, tree, step, seeded = RECORDED[name]
    cfg = _cfg_of(name)
    shapes = jax.eval_shape(
        lambda k: (decoder.init(k, cfg), decoder.state_init(k, cfg)),
        jax.random.key(0))
    assert _sha("\n".join(
        f"{jax.tree_util.keystr(p)} {x.shape} {x.dtype}"
        for p, x in jax.tree_util.tree_leaves_with_path(shapes))) == tree
    assert _sha(str(jax.make_jaxpr(jax.value_and_grad(
        lambda p, s, b: decoder.stateful_loss(p, s, b, cfg),
        has_aux=True))(
            *shapes, jax.ShapeDtypeStruct((1, 1024), jnp.int32)))) == step
    small, key = _cfg_of(tiny), jax.random.key(0)
    digest = hashlib.sha256()
    for path, leaf in jax.tree_util.tree_leaves_with_path(
            (decoder.init(key, small), decoder.state_init(key, small))):
        digest.update(jax.tree_util.keystr(path).encode())
        digest.update(jnp.asarray(leaf).tobytes())
    assert digest.hexdigest()[:16] == seeded


def test_what_the_new_kinds_are_not_built_for_is_refused():
    cfg, *_ = _setup(ALL)
    with pytest.raises(ValueError, match="the kda mixer needs"):
        dataclasses.replace(cfg, delta_value_heads=8)
    with pytest.raises(ValueError, match="the kda mixer needs"):
        dataclasses.replace(cfg, delta_key_dim=0)
    with pytest.raises(ValueError, match="q_lora_rank or 0"):
        dataclasses.replace(cfg, q_lora_rank=-1)
    with pytest.raises(ValueError, match="q_lora_rank or 0"):
        dataclasses.replace(cfg, kv_lora_rank=0)
    # under by_kind the latent kind is named, and turns nothing
    rule = dict(cfg.by_kind)["latent"]
    with pytest.raises(ValueError, match="turns nothing"):
        dataclasses.replace(cfg, by_kind=(
            ("latent", dataclasses.replace(rule, rope_dim=8)),))
    with pytest.raises(ValueError, match="turns nothing"):
        dataclasses.replace(cfg, by_kind=(
            ("full", dataclasses.replace(rule, rope_dim=8)),))
    # without by_kind it turns its rope part, as joyai's does
    turning = dataclasses.replace(cfg, by_kind=())
    assert decoder._rope_for(T, turning)[0].shape == (T, 4)
    with pytest.raises(ValueError, match="the MTP block is not built"):
        dataclasses.replace(cfg, mtp=1)
    with pytest.raises(ValueError, match="walked more than once"):
        dataclasses.replace(
            cfg, loops=2, lead_mlp=("dense",), mlp=("dense",) * 4,
            d_shared=0, routing="softmax_topk")
    with pytest.raises(ValueError, match="whole chunks"):
        decoder.loss_fn(decoder.init(jax.random.key(0), cfg),
                        jnp.zeros((1, 24), jnp.int32), cfg,
                        jnp.zeros((4, 16)))
