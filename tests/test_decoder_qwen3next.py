"""The pattern decoder's delta mixer (`ops/gated_delta.py` in chunks),
the elementwise attention gate, the (1 + w) norms and the shared
expert's gate (`models/decoder.py`), against the plain float32 reference
`benchmark/families/qwen3_next_reference.py`, which walks the delta rule
position by position. CPU, tiny widths
(`benchmark/configs/qwen3next_tiny.json`): hidden 64, four layers
[delta, delta, delta, full] each with experts, 2 key heads of 8 serving 4
value heads of 16 behind a 4-tap convolution, 4 query
heads over 2 key/value heads of 32 with rotary on 8 of a head's
dimensions, a gated shared expert beside top-3 of 16 experts, experts
4..7 held, T 128 (two chunks of the rule's 64); the kernels run in
interpret mode.

Tolerances. Program and reference both compute in float32 here, so what
separates them is the order of float32 sums and the chunked form's
inverse against the recurrence: LOSS_RTOL, LOGIT_ATOL and GRAD_RTOL sit
some way above what was measured (in `test_decoder_matches_reference`'s
note), and far below what the smallest mutation of
`test_mutation_is_told_apart` moves."""

import dataclasses
import functools
import hashlib

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from benchmark import manifest
from benchmark.families import qwen3_next, qwen3_next_reference as reference
from ray_tpu.models import decoder
from ray_tpu.parallel.moe import static_rows

LOSS_RTOL = 3e-6
LOGIT_ATOL = 2e-4
GRAD_RTOL = 2e-3      # of the leaf's largest reference gradient

MODEL = manifest.config_file("qwen3next_tiny")
ALL, HELD = (0, 16), (4, 4)     # every expert held; experts 4..7 of 16
T = 128


@functools.lru_cache
def _setup(held, seed=0):
    model = dict(MODEL, held_experts_first=held[0], num_experts=held[1])
    cfg = dataclasses.replace(qwen3_next.model_cfg(model), dtype=jnp.float32)
    key = jax.random.key(seed)
    params, state = decoder.init(key, cfg), decoder.counters_init(cfg)
    # norms away from their start, gates away from one half, decays and
    # write strengths that differ by head and position
    noise = iter(jax.random.split(jax.random.key(seed + 2), 64))

    def moved(path, leaf):
        name = jax.tree_util.keystr(path)
        if "norm" in name:
            return leaf + 0.3 * jax.random.normal(next(noise), leaf.shape)
        if "ws_token_gate" in name or "delta_ba" in name:
            return leaf * 20
        return leaf * 4 if "delta_in" in name or "wq_full" in name else leaf

    params = jax.tree_util.tree_map_with_path(moved, params)
    tokens = jax.random.randint(jax.random.key(seed + 1), (2, T), 0,
                                cfg.vocab_size)
    return cfg, params, state, tokens, model


def _reference(params, tokens, model, mutate=""):
    """(mean loss, (logits [B, T, V], n [layers, E])): one pass."""
    outs = [reference.forward(params, row, model, mutate) for row in tokens]
    logits = jnp.stack([o[0] for o in outs])
    logp = jax.nn.log_softmax(logits[:, :-1], axis=-1)
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
    logits = jax.jit(lambda p: decoder.apply(p, tokens, cfg))(params)
    return float(loss), logits, grads, new


def test_parameter_tree_state_and_facts():
    cfg, params, state, _, _ = _setup(HELD)
    assert cfg.kinds == (("delta", "experts"),) * 3 + (("full", "experts"),)
    stacks = {k: v.shape for k, v in params["layers"].items()}
    assert stacks["delta_in"] == (3, 64, 2 * 16 + 2 * 64) \
        and stacks["delta_ba"] == (3, 8, 64) \
        and stacks["delta_conv"] == (3, 4, 96) \
        and stacks["delta_A_log"] == stacks["delta_dt_bias"] == (3, 4) \
        and stacks["delta_norm"] == (3, 16) \
        and stacks["delta_out"] == (3, 64, 64)
    # the doubled query projection; no gate leaf, no zeros for the
    # layers without the part
    assert stacks["wq_full"] == (1, 64, 2 * 4 * 32) \
        and stacks["wo_full"] == (1, 4 * 32, 64) \
        and stacks["wk"] == stacks["wv"] == (1, 64, 2 * 32) \
        and stacks["q_norm"] == stacks["k_norm"] == (1, 32)
    assert stacks["ws_token_gate"] == (4, 64) \
        and stacks["router"] == (4, 64, 16) \
        and stacks["w_gate"] == (4, 4, 64, 32)
    assert not {"wq", "wo", "wg", "wg_full", "A_log", "ssm_in"} & set(stacks)
    # a (1 + w) norm starts near zero, the delta mixer's plain one at one
    fresh = decoder.init(jax.random.key(0), cfg)
    assert float(jnp.abs(fresh["layers"]["norm1"]).max()) < 0.2 \
        and float(jnp.abs(fresh["norm_f"]).max()) < 0.2 \
        and float(jnp.abs(fresh["layers"]["norm1"]).max()) > 0 \
        and (fresh["layers"]["delta_norm"] == 1).all() \
        and (fresh["layers"]["delta_dt_bias"] == 1).all()
    a = jnp.exp(fresh["layers"]["delta_A_log"])
    assert float(a.min()) > 0 and float(a.max()) <= 16
    assert {"delta_log_decay_min", "delta_beta_sum", "delta_beta_count",
            "attn_gate_sum_full", "attn_gate_count_full", "shared_gate_sum",
            "shared_gate_count", "moe_rows_static", "moe_rows_filled"} \
        <= set(state["epoch_counters"])
    facts = decoder.step_facts(cfg, (2, T))
    assert facts.pop("attention_tiles_walked") \
        > facts.pop("attention_tiles_unmasked") > 0
    assert facts == {
        "delta_layers": 3, "delta_chunks": 3 * 2 * (T // 64),
        "delta_heads": 4, "delta_heads_paired": 4,
        "attention_heads_full": 4, "rope_dim": 8}
    # three value heads a key head: the odd one's inverse runs alone
    odd = decoder.step_facts(
        dataclasses.replace(cfg, delta_value_heads=6), (2, T))
    assert (odd["delta_heads"], odd["delta_heads_paired"]) == (6, 4)
    assert set(decoder.step_facts(decoder.TINY, (2, 64))) == {
        "attention_tiles_unmasked", "attention_tiles_walked"}


def test_the_cut_has_the_parameters_the_issue_counted():
    """`qwen3next_80b_a3b_ep16` from the built tree: 625 667 136
    parameters, by part."""
    cfg = qwen3_next.model_cfg(
        manifest.config_file("qwen3next_80b_a3b_ep16"))
    shapes = jax.eval_shape(lambda k: decoder.init(k, cfg),
                            jax.random.key(0))
    size = {k: int(np.prod(v.shape[1:]))
            for k, v in shapes["layers"].items()}
    assert shapes["embed"].size + shapes["head"].size == 77_791_232
    delta = sum(v for k, v in size.items() if k.startswith("delta_"))
    assert (size["delta_in"], size["delta_ba"], size["delta_conv"],
            size["delta_out"], delta) == (
                25_165_824, 131_072, 32_768, 8_388_608, 33_718_464)
    attention = size["wq_full"] + size["wk"] + size["wv"] \
        + size["wo_full"] + size["q_norm"] + size["k_norm"]
    assert (size["wq_full"], attention) == (16_777_216, 27_263_488)
    shared = size["ws_gate"] + size["ws_up"] + size["ws_down"]
    experts = size["w_gate"] + size["w_up"] + size["w_down"]
    block = size["router"] + experts + shared + size["ws_token_gate"] \
        + size["norm1"] + size["norm2"]
    assert (size["router"], experts, shared, block) == (
        1_048_576, 100_663_296, 3_145_728, 104_863_744)
    assert sum(x.size for x in jax.tree.leaves(shapes)) == 625_667_136 \
        == 3 * delta + attention + 4 * block + 77_791_232 + 2048
    assert shapes["layers"]["delta_in"].shape == (3, 2048, 12288) \
        and shapes["layers"]["wq_full"].shape == (1, 2048, 8192) \
        and shapes["layers"]["w_gate"].shape == (4, 32, 2048, 512)
    facts = decoder.step_facts(cfg, (2, 8192))
    # one attention layer, its forward twice (`remat`): 2 sequences x 16
    # heads x 2 planes of 32 x 16 tiles of 256 x 512 under the diagonal
    assert (facts.pop("attention_tiles_unmasked"),
            facts.pop("attention_tiles_walked")) == (64 * 240, 64 * 272)
    assert facts == {"delta_layers": 3, "delta_chunks": 3 * 2 * 128,
                     "delta_heads": 32, "delta_heads_paired": 32,
                     "attention_heads_full": 16,
                     "rope_dim": 64}


def test_the_convolution_as_a_kernel_is_the_plain_forms(plain_mixer_conv):
    """The tiny preset with one key head and two value heads of 128
    behind the convolution (its own key heads of 8 are no whole lanes:
    its programs run `mixer_conv_xla`), T 64: the loss and every leaf's
    gradient with `mixer_conv`'s kernels are the plain form's."""
    cfg = dataclasses.replace(
        qwen3_next.model_cfg(MODEL), dtype=jnp.float32, delta_key_heads=1,
        delta_value_heads=2, delta_key_dim=128, delta_value_dim=128)
    params, state = decoder.init(jax.random.key(0), cfg), \
        decoder.counters_init(cfg)
    params["layers"]["delta_in"] = params["layers"]["delta_in"] * 4
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



def test_decoder_matches_reference(program):
    """The loss, the logits, every leaf's gradient and the counters.
    Measured at the rule's own chunk of 64: the loss 2e-7 apart, a logit
    9.0e-5 (of 0.6), a leaf's gradient 8.6e-4 of its largest
    (`delta_A_log` and `delta_dt_bias`: sums over every position of terms
    that cancel; the next leaves read 4e-4). In chunks of 16 the same
    weights read 6.7e-6 and 1.0e-4: the inverse of a chunk's `I + A` at
    side 64, with write strengths pushed to 0 and 1, is what float32
    loses the digits in (key heads of 32 read 4.9e-5: not the narrow
    heads), in the plain chunked form as in the kernels
    (`tests/test_gated_delta.py` at (2, 64)). The smallest mutation
    moves a logit by 0.068."""
    cfg, params, _, tokens, model = _setup(HELD)
    loss, logits, grads, new = program
    with jax.default_matmul_precision("highest"):
        (ref_loss, (ref_logits, n)), ref_grads = jax.jit(jax.value_and_grad(
            lambda p: _reference(p, tokens, model), has_aux=True))(params)
    assert abs(loss - float(ref_loss)) <= LOSS_RTOL * float(ref_loss)
    assert float(jnp.abs(logits - ref_logits).max()) <= LOGIT_ATOL
    flat, _ = jax.tree_util.tree_flatten_with_path(grads)
    for (path, got), want in zip(flat, jax.tree.leaves(ref_grads)):
        scale = float(jnp.abs(want).max())
        assert scale > 0, path       # every leaf is reached by the loss
        assert float(jnp.abs(got - want).max()) <= GRAD_RTOL * scale, path
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
    # the gates and the write strength are computed, on every element,
    # and are not stuck at one half; the decay is seen
    assert int(c["attn_gate_count_full"]) == tokens.size * 4 * 32
    assert int(c["delta_beta_count"]) == tokens.size * 3 * 4
    assert int(c["shared_gate_count"]) == tokens.size * 4
    for name in ("attn_gate", "delta_beta", "shared_gate"):
        tail = "_full" if name == "attn_gate" else ""
        opened = float(c[f"{name}_sum{tail}"] / c[f"{name}_count{tail}"])
        assert 0.3 < opened < 0.7 and abs(opened - 0.5) > 1e-4, name
    assert float(c["delta_log_decay_min"]) < -1.0


@pytest.mark.parametrize("name", reference.MUTATIONS)
def test_mutation_is_told_apart(program, name):
    """A reference with one mechanism changed must fail
    `test_decoder_matches_reference` by ten times LOGIT_ATOL on the
    logits (at seeded weights the loss sits near log(V) whatever the
    blocks compute: the logits tell). Not jitted: twelve programs cost
    more to compile than their operations to dispatch."""
    _, params, _, tokens, model = _setup(HELD)
    _, logits, _, _ = program
    with jax.default_matmul_precision("highest"):
        _, (ref_logits, _) = _reference(params, tokens, model, name)
    assert float(jnp.abs(logits - ref_logits).max()) > 10 * LOGIT_ATOL


def test_bfloat16_throughout_is_told_apart(program):
    """The precision below the one the configuration states — weights,
    activations, rotary tables, gates, router and the rule's state in
    bfloat16 — reads above the tolerances."""
    _, params, _, tokens, model = _setup(HELD)
    loss, logits, _, _ = program
    low = jax.tree.map(lambda x: x.astype(jnp.bfloat16), params)
    ref_loss, (ref_logits, _) = jax.jit(
        lambda p: _reference(p, tokens, model))(low)
    assert abs(loss - float(ref_loss)) > 10 * LOSS_RTOL * loss
    assert float(jnp.abs(logits - ref_logits).max()) > 10 * LOGIT_ATOL


def test_the_program_in_bfloat16_stays_near_the_reference():
    """The compute dtype the configuration states, at its stated
    tolerance: the loss within 2e-3 of the float32 reference's (measured
    1.6e-4), a logit 0.03 off in the mean (measured 0.009, where a
    logit's own size is 0.13) and in the median row's worst 0.05
    (measured 0.014). The WORST row is no measure here: a router logit
    that bfloat16 activations move across a tie sends a token to another
    expert, and the few rows that happens to read up to 0.54 off — under
    a tenth of the rows pass 0.1."""
    cfg, params, state, tokens, model = _setup(HELD)
    low = dataclasses.replace(cfg, dtype=jnp.bfloat16)
    loss, _ = jax.jit(lambda p: decoder.stateful_loss(
        p, state, tokens, low))(params)
    logits = jax.jit(lambda p: decoder.apply(p, tokens, low))(params)
    with jax.default_matmul_precision("highest"):
        ref_loss, (ref_logits, _) = jax.jit(
            lambda p: _reference(p, tokens, model))(params)
    assert abs(float(loss) - float(ref_loss)) <= 2e-3 * float(ref_loss)
    off = np.abs(np.asarray(logits - ref_logits))
    assert off.mean() <= 0.03 and np.median(off.max(-1)) <= 0.05
    assert (off.max(-1) > 0.1).mean() < 0.1


@pytest.mark.parametrize("kind,at", [("delta", 1), ("full", 3)])
def test_shares_add_up_to_the_uncut_layer(kind, at):
    """The share test: the routed parts of the eight shares (experts
    0-1, 2-3, .. of 16), with the mixer, the residual and the gated
    shared expert counted once, add up to the uncut reference's layer —
    on a delta layer and on the attention layer."""
    cfg, params, _, _, model = _setup(ALL)
    got_kind, p = reference.layer_leaves(params, at, model)
    assert got_kind == kind
    mine = {{"wq": "wq_full", "wo": "wo_full"}.get(k, k): v
            for k, v in p.items()}
    h = 3 * jax.random.normal(jax.random.key(7), (1, T, cfg.d_model))
    with jax.default_matmul_precision("highest"):
        whole, m, n = reference.layer(h[0], p, kind=kind, model=model)
    assert int(n.sum()) == T * 3
    alike = whole - m     # mixer, residual and the gated shared expert
    total = alike
    # the program's layer in its two parts, so that the mixer's kernels
    # are compiled once and not a share: what every chip computes alike
    # up to the MLP's input, then a share's experts beside the shared one
    h1, _ = jax.jit(functools.partial(
        decoder._layer, cfg=cfg, mlp="none", attention=kind))(
            h, mine, decoder._rope_for(T, cfg))
    for first in range(0, 16, 2):
        share = dataclasses.replace(cfg, held=(first, 2))
        held = dict(mine, **{k: mine[k][first:first + 2]
                             for k in ("w_gate", "w_up", "w_down")})
        out, counts = jax.jit(functools.partial(
            decoder._layer, cfg=share, mlp="experts", attention="none"))(
                h1, held, None)
        assert int(counts["held"]) == int(n[first:first + 2].sum())
        total = total + (out[0] - alike)
    assert float(jnp.abs(m).max()) > 1e-3
    assert float(jnp.abs(total - whole).max()) <= 5e-5


def test_the_rotary_turns_a_quarter_of_a_head():
    cfg, *_ = _setup(ALL)
    rule = dict(cfg.by_kind)["full"]
    assert (rule.rope_dim, rule.n_heads, rule.yarn) == (8, 4, None)
    np.testing.assert_allclose(decoder._kind_rates(rule),
                               100.0 ** (-2 * np.arange(4) / 8), rtol=1e-6)
    tables = decoder._rope_for(T, cfg)
    assert set(tables) == {"full"} and tables["full"][0].shape == (T, 4)
    x = jax.random.normal(jax.random.key(3), (1, T, 2, 32))
    turned = decoder._rope(x, *tables["full"])
    assert (turned[..., 8:] == x[..., 8:]).all()
    assert not (turned[:, 1:, :, :8] == x[:, 1:, :, :8]).all()


# What Laguna's configuration gave on the parent commit (556fd1d), the
# seventh row of `tests/test_decoder_laguna.py::RECORDED`, which holds
# the six configurations before it and still runs: sha256 (16 digits) of
# the parameter and state tree's paths, shapes and dtypes at the
# published widths; of the jaxpr of value_and_grad(stateful_loss), the
# step's forward and backward pass, on a batch [1, 1024] at those
# widths; and, at the configuration's tiny preset, of the bytes of every
# leaf seeded from key 0. (The step's text recorded again at PR 60, as
# that file's were: `flash_fwd`'s K loop in runs. All three again at
# PR 62: the expert block walks a rung of `parallel/moe.py::row_ladder`
# under a conditional, and the epoch counters gained `moe_rows_walked`.)
RECORDED = {
    "laguna_xs2_d5": ("laguna_tiny", "76d13b2cc2b33de0", "53920692078322ac",
                      "c4a472490c300ce6"),
}


def _sha(text: str) -> str:
    return hashlib.sha256(text.encode()).hexdigest()[:16]


def _cfg_of(name: str):
    model = manifest.config_file(name)
    return manifest.module("families", model["family"]).model_cfg(model)


@pytest.mark.parametrize("name", list(RECORDED))
def test_an_earlier_configuration_keeps_its_program(name):
    """Tree paths and shapes, the step's traced program and the seeded
    weights of a configuration without the delta mixer, the elementwise
    gate, the (1 + w) norm or the shared expert's gate are the
    parent's."""
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


def test_a_checkpoint_a_part_changes_no_value(program):
    """`remat = "parts"` (the tiny configuration's and the cell's: the
    mixer and the expert block of a layer each under a checkpoint of its
    own) against one checkpoint a block and none: what the backward pass
    makes again changes no loss and no gradient."""
    cfg, params, state, tokens, _ = _setup(HELD)
    assert cfg.remat == "parts"
    loss, _, grads, _ = program
    for remat in (True, False):
        other = dataclasses.replace(cfg, remat=remat)
        (l, _), g = jax.jit(jax.value_and_grad(
            lambda p: decoder.stateful_loss(p, state, tokens, other),
            has_aux=True))(params)
        assert float(l) == pytest.approx(loss, rel=1e-6)
        for a, b in zip(jax.tree.leaves(g), jax.tree.leaves(grads)):
            np.testing.assert_allclose(
                a, b, rtol=1e-4, atol=1e-6 * float(jnp.abs(b).max()) + 1e-12)
    # two checkpoints a layer in the traced step, one under True
    def checkpoints(c):
        return str(jax.make_jaxpr(
            lambda p: decoder.stateful_loss(p, state, tokens, c))(
                params)).count("remat2[")
    assert checkpoints(cfg) - checkpoints(
        dataclasses.replace(cfg, remat=True)) == len(cfg.attention)


def test_what_the_new_properties_are_not_built_for_is_refused():
    cfg, *_ = _setup(ALL)
    with pytest.raises(ValueError, match="the delta mixer needs"):
        dataclasses.replace(cfg, delta_value_heads=3)
    with pytest.raises(ValueError, match="the delta mixer needs"):
        dataclasses.replace(cfg, delta_key_dim=0)
    with pytest.raises(ValueError, match="attn_gate is one of"):
        dataclasses.replace(cfg, attn_gate="elementwise")
    assert dataclasses.replace(cfg, attn_gate=True).attn_gate == "head" \
        and dataclasses.replace(cfg, attn_gate=False).attn_gate == ""
    with pytest.raises(ValueError, match="shared_gate is a gate"):
        dataclasses.replace(cfg, d_shared=0)
    with pytest.raises(ValueError, match="remat is True"):
        dataclasses.replace(cfg, router_input="mixer")
    with pytest.raises(ValueError, match="remat is True"):
        dataclasses.replace(cfg, remat="mixer")
    with pytest.raises(ValueError, match="the MTP block is not built"):
        dataclasses.replace(cfg, mtp=1, attn_gate="")
    with pytest.raises(ValueError, match="walked more than once"):
        dataclasses.replace(
            cfg, loops=2, mlp=("dense",) * 4, d_dense=32, d_shared=0,
            shared_gate=False, attn_gate="")
    with pytest.raises(ValueError, match="whole chunks"):
        decoder.loss_fn(decoder.init(jax.random.key(0), cfg),
                        jnp.zeros((1, 24), jnp.int32), cfg)
