"""The pattern decoder's attention kinds with head counts and rotary
rules of their own, the per-head output gate and the window kernel's
tile count (`models/decoder.py`, `ops/attention.py`), against the plain
float32 reference `benchmark/families/laguna_reference.py`. CPU, tiny
widths (`benchmark/configs/laguna_tiny.json`): hidden 64, five layers
[(full, dense), (window, experts) x 3, (full, experts)], 6 / 8 query
heads over 2 key/value heads of 32, a window of 24 under 16 x 32 tiles,
YaRN on 16 of a full head's 32 dimensions whose ramp runs over pairs 1
to 5, plain rotary on all 32 of a window head's, a shared expert beside
top-3 of 16 experts, 4 held, T 64; the kernels run in interpret mode.

Tolerances. Program and reference both compute in float32 here, so what
separates them is the order of float32 sums: measured 9.8e-8 on the
loss, 3.5e-7 on a logit, 9.7e-7 of a leaf's largest gradient. LOSS_RTOL,
LOGIT_ATOL and GRAD_RTOL sit some way above that, and far below what
the smallest mutation of `test_mutation_is_told_apart` moves (a logit by
0.020: the YaRN ramp off)."""

import dataclasses
import functools
import hashlib

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from benchmark import manifest
from benchmark.families import laguna, laguna_reference as reference
from ray_tpu.models import decoder
from ray_tpu.ops import attention
from ray_tpu.parallel.moe import static_rows

LOSS_RTOL = 3e-6
LOGIT_ATOL = 1e-5
GRAD_RTOL = 3e-5      # of the leaf's largest reference gradient

MODEL = manifest.config_file("laguna_tiny")
ALL, HELD = (0, 16), (4, 4)     # every expert held; experts 4..7 of 16
T = 64


@functools.lru_cache
def _setup(held, seed=0):
    model = dict(MODEL, held_experts_first=held[0], num_experts=held[1])
    cfg = dataclasses.replace(laguna.model_cfg(model), dtype=jnp.float32)
    key = jax.random.key(seed)
    params, state = decoder.init(key, cfg), decoder.counters_init(cfg)
    # norms away from one, and gates away from one half
    noise = iter(jax.random.split(jax.random.key(seed + 2), 64))

    def moved(path, leaf):
        name = jax.tree_util.keystr(path)
        if "norm" in name:
            return leaf + 0.3 * jax.random.normal(next(noise), leaf.shape)
        return leaf * 20 if "wg_" in name else leaf

    params = jax.tree_util.tree_map_with_path(moved, params)
    tokens = jax.random.randint(jax.random.key(seed + 1), (2, T), 0,
                                cfg.vocab_size)
    return cfg, params, state, tokens, model


def _reference(params, tokens, model, mutate=""):
    """(mean loss, (logits [B, T, V], n [sparse layers, E])): one pass."""
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
    assert cfg.kinds == (("full", "dense"),) + (("window", "experts"),) * 3 \
        + (("full", "experts"),)
    stacks = {k: v.shape for k, v in params["layers"].items()}
    assert stacks["wq_full"] == (2, 64, 6 * 32) \
        and stacks["wo_full"] == (2, 6 * 32, 64) \
        and stacks["wg_full"] == (2, 64, 6)
    assert stacks["wq_window"] == (3, 64, 8 * 32) \
        and stacks["wo_window"] == (3, 8 * 32, 64) \
        and stacks["wg_window"] == (3, 64, 8)
    assert stacks["wk"] == stacks["wv"] == (5, 64, 2 * 32)
    assert stacks["w1"] == (1, 64, 96) and stacks["router"] == (4, 64, 16)
    assert stacks["w_gate"] == (4, 4, 64, 32) \
        and stacks["ws_down"] == (4, 32, 64)
    assert not {"wq", "wo", "wg"} & set(stacks)
    # each stack its own draw
    assert not (params["layers"]["wq_window"][0, :, :192]
                == params["layers"]["wq_full"][0]).all()
    assert {f"attn_gate_{what}_{kind}" for what in ("sum", "count")
            for kind in ("full", "window")} | {
                "moe_rows_static", "moe_rows_filled"} \
        <= set(state["epoch_counters"])
    inside = sum(min(i + 1, 24) for i in range(T))
    facts = decoder.step_facts(cfg, (2, T))
    assert {k: facts[k] for k in (
        "attention_heads_full", "attention_heads_window", "attention_window",
        "rope_scaling", "window_scores_inside")} == {
            "attention_heads_full": 6, "attention_heads_window": 8,
            "attention_window": 24, "rope_scaling": "yarn:8",
            "window_scores_inside": 2 * 3 * 8 * 3 * inside}
    assert facts["window_scores_visited"] > facts["window_scores_inside"]
    assert set(decoder.step_facts(decoder.TINY, (2, 64))) == {
        "attention_tiles_unmasked", "attention_tiles_walked"}


def test_the_cut_has_the_parameters_the_issue_counted():
    """`laguna_xs2_d5` from the built tree: 691 623 936 parameters, by
    part."""
    cfg = laguna.model_cfg(manifest.config_file("laguna_xs2_d5"))
    shapes = jax.eval_shape(lambda k: decoder.init(k, cfg),
                            jax.random.key(0))
    size = {k: int(np.prod(v.shape[1:]))
            for k, v in shapes["layers"].items()}
    assert shapes["embed"].size + shapes["head"].size == 51_380_224
    attention_kv = size["wk"] + size["wv"]
    full = size["wq_full"] + size["wo_full"] + attention_kv
    window = size["wq_window"] + size["wo_window"] + attention_kv
    assert (full, size["wg_full"], window, size["wg_window"]) == (
        29_360_128, 98_304, 37_748_736, 131_072)
    norms = size["norm1"] + size["norm2"]
    dense = size["w1"] + size["w2"] + size["w3"]
    shared = size["ws_gate"] + size["ws_up"] + size["ws_down"]
    experts = size["w_gate"] + size["w_up"] + size["w_down"]
    assert (dense, size["router"], shared, experts) == (
        50_331_648, 524_288, 3_145_728, 100_663_296)
    sparse = size["router"] + shared + experts + norms
    assert full + size["wg_full"] + dense + norms == 79_794_176
    assert window + size["wg_window"] + sparse == 142_217_216
    assert full + size["wg_full"] + sparse == 133_795_840
    assert sum(x.size for x in jax.tree.leaves(shapes)) == 691_623_936
    assert shapes["layers"]["wq_full"].shape == (2, 2048, 6144) \
        and shapes["layers"]["wq_window"].shape == (3, 2048, 8192)


def test_decoder_matches_reference(program):
    """The loss, the logits, every leaf's gradient and the counters."""
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
    # the gate is computed, on every head of every layer of a kind, and
    # is not stuck at one half
    assert int(c["attn_gate_count_full"]) == tokens.size * 2 * 6
    assert int(c["attn_gate_count_window"]) == tokens.size * 3 * 8
    for kind in ("full", "window"):
        opened = float(c[f"attn_gate_sum_{kind}"]
                       / c[f"attn_gate_count_{kind}"])
        assert 0.3 < opened < 0.7 and abs(opened - 0.5) > 1e-4


@pytest.mark.parametrize("name", reference.MUTATIONS)
def test_mutation_is_told_apart(program, name):
    """A reference with one mechanism changed must fail
    `test_decoder_matches_reference` by ten times LOGIT_ATOL on the
    logits (at seeded weights the loss sits near log(V) whatever the
    blocks compute: the logits tell). Not jitted: eleven programs cost
    more to compile than their operations to dispatch."""
    _, params, _, tokens, model = _setup(HELD)
    _, logits, _, _ = program
    with jax.default_matmul_precision("highest"):
        _, (ref_logits, _) = _reference(params, tokens, model, name)
    assert float(jnp.abs(logits - ref_logits).max()) > 10 * LOGIT_ATOL


def test_bfloat16_throughout_is_told_apart(program):
    """The precision below the one the configuration states — weights,
    activations, rotary tables, gate and router in bfloat16 — reads
    above the tolerances."""
    _, params, _, tokens, model = _setup(HELD)
    loss, logits, _, _ = program
    low = jax.tree.map(lambda x: x.astype(jnp.bfloat16), params)
    ref_loss, (ref_logits, _) = jax.jit(
        lambda p: _reference(p, tokens, model))(low)
    assert abs(loss - float(ref_loss)) > 10 * LOSS_RTOL * loss
    assert float(jnp.abs(logits - ref_logits).max()) > 10 * LOGIT_ATOL


def test_shares_add_up_to_the_uncut_layer():
    """The share test: the routed parts of the eight shares (experts
    0-1, 2-3, .. of 16), with the attention, its gate, the residual and
    the shared expert counted once, add up to the uncut reference's
    layer."""
    cfg, params, _, _, model = _setup(ALL)
    _, _, p = reference.layer_leaves(params, 1, model)
    mine = {decoder.DecoderConfig.leaf_of(cfg, k, "window")
            if k in ("wq", "wo", "wg") else k: v for k, v in p.items()}
    h = 3 * jax.random.normal(jax.random.key(7), (1, T, cfg.d_model))
    with jax.default_matmul_precision("highest"):
        whole, m, n = reference.layer(h[0], p, kind="window", mlp="experts",
                                      model=model)
    assert int(n.sum()) == T * 3
    alike = whole - m     # mixer, gate, residual and the shared expert
    total = alike
    # the program's layer in its two parts, so that the attention kernel
    # is compiled once and not a share: what every chip computes alike
    # up to the MLP's input, then a share's experts beside the shared one
    h1, _ = jax.jit(functools.partial(
        decoder._layer, cfg=cfg, mlp="none", attention="window"))(
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
    assert float(jnp.abs(total - whole).max()) <= 2e-5


def test_the_rotary_rules_are_the_kinds_own():
    """YaRN's rates as written out, the half that passes untouched, and
    a table a kind."""
    cfg, *_ = _setup(ALL)
    full, window = dict(cfg.by_kind)["full"], dict(cfg.by_kind)["window"]
    assert (full.rope_dim, window.rope_dim) == (16, 32)
    i = np.arange(8)
    plain = 100.0 ** (-2 * i / 16)
    ramp = np.clip((i - 1) / (5 - 1), 0, 1)     # low 1, high 5
    np.testing.assert_allclose(
        decoder._kind_rates(full), plain * (1 - ramp) + plain / 8 * ramp,
        rtol=1e-6)
    np.testing.assert_allclose(decoder._kind_rates(window),
                               1e4 ** (-2 * np.arange(16) / 32), rtol=1e-6)
    tables = decoder._rope_for(T, cfg)
    assert tables["full"][0].shape == (T, 8) \
        and tables["window"][0].shape == (T, 16)
    assert float(tables["full"][0][0, 0]) == pytest.approx(
        1.2079441541679836)
    x = jax.random.normal(jax.random.key(3), (1, T, 2, 32))
    turned = decoder._rope(x, *tables["full"])
    assert (turned[..., 16:] == x[..., 16:]).all()
    assert not (turned[:, 1:, :, :16] == x[:, 1:, :, :16]).all()


@pytest.mark.parametrize("t,window,block_q,block_k", [
    (64, 24, 16, 32), (128, 32, 16, 32), (1024, 512, 256, 512)])
def test_window_scores_are_the_kernels_own_walk(t, window, block_q,
                                                block_k):
    """`window_scores` against the mask and the tiles counted by hand:
    a tile is walked if any of its entries is inside causal AND window
    (the kernels skip only whole tiles, and walk none that is empty)."""
    inside, fwd, bwd = attention.window_scores(
        t, window, 32, jnp.float32, block_q, block_k)
    i, j = np.arange(t)[:, None], np.arange(t)[None, :]
    mask = (i >= j) & (i - j < window)
    assert inside == int(mask.sum())

    def walked(bq, bk):
        tiles = mask.reshape(t // bq, bq, t // bk, bk).any((1, 3))
        return int(tiles.sum()) * bq * bk

    assert fwd == walked(block_q, block_k)
    assert bwd == walked(*attention._bwd_tiles(t, 32, jnp.float32))
    if window == block_k:       # a window of one tile: half at best
        assert 0.45 < inside / fwd <= 0.55


# What the six decoder configurations the benchmark had before this
# family gave on the parent commit (0381f1a): sha256 (16 digits) of the
# parameter and state tree's paths, shapes and dtypes at the published
# widths; of the jaxpr of value_and_grad(stateful_loss), the step's
# forward and backward pass, on a batch [1, 1024] at those widths (its
# lowered text, twice the time to make, was compared by hand: CHANGES.md
# PR 55); and, at the configuration's tiny preset, of the bytes of
# every leaf seeded from key 0. (The step's text recorded again at
# PR 60: its `flash_fwd` walks the key blocks in runs; trees and seeded
# weights are the parent's still. At PR 62 the step's text of the five
# with expert layers again — the block walks a rung of
# `parallel/moe.py::row_ladder` under a conditional — and, of the three
# that count rows (`joyai`, `nemotron3`, `sdar`), the tree and the seeded
# bytes too: one more zero, `moe_rows_walked`, among the epoch counters;
# `ouro_2_6b_d8`, which has no expert, keeps all three. At PR 64 the
# step's text of `nemotron3_nano_ep16` alone: its state-space mixers'
# convolution, bias and SiLU are `ops/short_conv.py::mixer_conv`; its tree
# and seeded bytes, and the other five rows whole, are the parent's.)
RECORDED = {
    "smallthinker_21b_ep4": ("smallthinker_tiny", "e1534e3b726776c9",
                             "44fb3715a183b3ab", "a5041fed5e97536c"),
    "lfm2_8b_a1b_ep4": ("lfm2_tiny", "f133c9bbc8c0c233", "ac4c17aaaf384d9c",
                        "84ce2cd017577a97"),
    "joyai_flash_ep16": ("joyai_tiny", "1e46c2acd199b0f8",
                         "4a2c773ff3d2dc60", "462fab1b65ec7cc3"),
    "nemotron3_nano_ep16": ("nemotron_tiny", "693046ea382e87db",
                            "2de099bada4020a0", "16e06d3e17e37deb"),
    "sdar_30b_a3b_ep8": ("sdar_tiny", "809b8713238c2e2d", "e3c3e90babd2822b",
                         "9648c599b28dfa6f"),
    "ouro_2_6b_d8": ("ouro_tiny", "48c171503f96b155", "5d3edb7e5e8438f2",
                     "42e69148bd51e030"),
}


def _sha(text: str) -> str:
    return hashlib.sha256(text.encode()).hexdigest()[:16]


def _cfg_of(name: str):
    model = manifest.config_file(name)
    return manifest.module("families", model["family"]).model_cfg(model)


@pytest.mark.parametrize("name", list(RECORDED))
def test_a_configuration_that_names_no_kind_keeps_its_program(name):
    """Tree paths and shapes, the step's traced program and the seeded
    weights of a configuration without `by_kind` are the parent's."""
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


def test_what_the_kinds_are_not_built_for_is_refused():
    cfg, *_ = _setup(ALL)
    with pytest.raises(ValueError, match="by_kind names each attention"):
        dataclasses.replace(cfg, by_kind=cfg.by_kind[:1])
    with pytest.raises(ValueError, match="by_kind names each attention"):
        dataclasses.replace(cfg, by_kind=(
            cfg.by_kind[0], ("window", decoder.AttentionKind(7, 1e4, 32))))
    with pytest.raises(ValueError, match="attn_gate gates the heads"):
        dataclasses.replace(cfg, mtp=1)
    # one stack for all attention layers takes the gate too
    plain = dataclasses.replace(decoder.TINY, attn_gate=True)
    assert decoder.init(jax.random.key(0), plain)["layers"]["wg"].shape \
        == (4, 64, 4)
