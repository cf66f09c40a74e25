"""The pattern decoder's latent mixer, shared expert, routing factor and
multi-token-prediction block (`models/decoder.py`), the two-width
attention kernels (`ops/attention.py`) and the routing weights' scale
(`parallel/moe.py`), against the plain float32 reference
`benchmark/families/joyai_reference.py`. CPU, tiny widths: hidden 64, a
leading dense layer and two expert layers, an MTP block, 4 heads of
nope 16 / rope 8 / v 16 (so a key is 24 wide and a value 16), latents of
48 and 32, 8 experts top-3 with a shared one, T 64; the kernels run in
interpret mode.

Tolerances. Program and reference both compute in float32 here, so what
separates them is the order of float32 sums: measured 1.7e-7 on a loss
term, 1.2e-6 on a logit, 1.5e-6 of a leaf's largest gradient. LOSS_RTOL,
LOGIT_ATOL and GRAD_RTOL sit some way above that, and below what the
smallest mutation of `test_mutation_is_told_apart` moves."""

import dataclasses
import functools
import hashlib

import jax
import jax.numpy as jnp
import pytest

from benchmark import manifest
from benchmark.families import joyai, joyai_reference as reference
from ray_tpu.models import decoder
from ray_tpu.ops import attention
from ray_tpu.parallel.moe import static_rows

LOSS_RTOL = 3e-6
LOGIT_ATOL = 1e-5
GRAD_RTOL = 2e-5      # of the leaf's largest reference gradient

MODEL = manifest.config_file("joyai_tiny")
HELD = {"all": (0, 8), "subset": (2, 4)}
MOE_LAYERS = 3        # two main expert layers and the MTP block's


def _setup(held, seed=0):
    model = dict(MODEL, held_experts_first=held[0], n_routed_experts=held[1])
    cfg = dataclasses.replace(joyai.model_cfg(model), dtype=jnp.float32)
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


def _reference(params, bias, tokens, model, mutate=""):
    """(the two-term loss, (main mean, MTP mean, both heads' logits
    [B, T, V], n [routing layers, E])): one pass."""
    outs = [reference.forward(params, bias, row, model, mutate)
            for row in tokens]
    sums = [reference.nll_of(o[0], o[1], row, mutate)
            for o, row in zip(outs, tokens)]
    b, t = tokens.shape
    main = sum(s[0] for s in sums) / (b * (t - 1))
    second = sum(s[1] for s in sums) / (b * (t - 2))
    weight = 0.0 if mutate == "lambda 0" else model["mtp_loss_weight"]
    return main + weight * second, (
        main, second, jnp.stack([o[0] for o in outs]),
        jnp.stack([o[1] for o in outs]), sum(o[2] for o in outs))


@pytest.fixture(scope="module")
def program():
    """The program's loss, new state, both heads' logits and gradients,
    once a held share."""
    out = {}
    for name, held in HELD.items():
        cfg, params, state, tokens, _ = _setup(held)
        (loss, new), grads = jax.jit(jax.value_and_grad(
            lambda p: decoder.stateful_loss(p, state, tokens, cfg),
            has_aux=True))(params)
        logits = jax.jit(lambda p: decoder.apply(
            p, tokens, cfg, state["expert_bias"]))(params)
        logits2 = jax.jit(lambda p: decoder.mtp_apply(
            p, tokens, cfg, state["expert_bias"]))(params)
        out[name] = (float(loss), logits, logits2, grads, new)
    return out


def test_parameter_tree_and_state():
    cfg, params, state, _, _ = _setup(HELD["subset"])
    assert cfg.kinds == (("latent", "dense"),) + (("latent", "experts"),) * 2
    assert cfg.moe_layers == MOE_LAYERS
    stacks = {k: v.shape for k, v in params["layers"].items()}
    assert stacks["wq_b"] == (3, 48, 4 * 24) and stacks["wkv_a"] == (3, 64, 40)
    assert stacks["wkv_b"] == (3, 32, 4 * 32) \
        and stacks["wo_latent"] == (3, 4 * 16, 64)
    assert stacks["ws_gate"] == (2, 64, 32) and stacks["w1"] == (1, 64, 96)
    assert stacks["w_gate"] == (2, 4, 64, 32) and stacks["router"] == (2, 64, 8)
    assert not {"wq", "wk", "wv", "wo", "conv_in"} & set(stacks)
    mtp = params["mtp"]
    assert set(mtp) == {"proj", "norm_h", "norm_e", "norm_f", "layer"}
    assert mtp["proj"].shape == (128, 64)
    assert set(mtp["layer"]) == set(stacks) - {"w1", "w2", "w3"}
    assert all(mtp["layer"][k].shape == stacks[k][1:] for k in mtp["layer"])
    # the block's weights are its own draw, not a copy of a main layer's
    assert not (mtp["layer"]["wq_a"] == params["layers"]["wq_a"][2]).all()
    assert "head" in params and state["expert_bias"].shape == (3, 8)
    assert {"loss_main", "loss_mtp", "moe_rows_static",
            "moe_rows_filled"} <= set(state["epoch_counters"])


@pytest.mark.parametrize("share", list(HELD))
def test_decoder_matches_reference(program, share):
    """Both loss terms, both heads' logits, every leaf's gradient (the
    shared embedding's and head's included) and the biases after the
    step, with all experts held and with a held subset (2..5 of 8)."""
    cfg, params, state, tokens, model = _setup(HELD[share])
    loss, logits, logits2, grads, new = program[share]
    bias = state["expert_bias"]
    with jax.default_matmul_precision("highest"):
        (ref_loss, (main, second, ref_logits, ref_logits2, n)), ref_grads = \
            jax.jit(jax.value_and_grad(
                lambda p: _reference(p, bias, tokens, model),
                has_aux=True))(params)
    c = new["epoch_counters"]
    assert abs(loss - float(ref_loss)) <= LOSS_RTOL * float(ref_loss)
    assert abs(float(c["loss_main"]) - float(main)) <= LOSS_RTOL * float(main)
    assert abs(float(c["loss_mtp"]) - float(second)) \
        <= LOSS_RTOL * float(second)
    assert float(jnp.abs(logits - ref_logits).max()) <= LOGIT_ATOL
    assert float(jnp.abs(logits2 - ref_logits2).max()) <= LOGIT_ATOL
    flat, _ = jax.tree_util.tree_flatten_with_path(grads)
    for (path, got), want in zip(flat, jax.tree.leaves(ref_grads)):
        scale = float(jnp.abs(want).max())
        assert scale > 0, path       # every leaf is reached by the loss
        assert float(jnp.abs(got - want).max()) <= GRAD_RTOL * scale, path
    want = reference.bias_update(bias, n, model["expert_bias_update_rate"])
    assert (new["expert_bias"] == want).all()
    assert int(c["moe_assignments"]) == MOE_LAYERS * tokens.size * 3
    assert int(c["moe_assignments_dropped"]) == 0
    assert 0 < int(c["moe_assignments_bias_moved"]) < int(c["moe_assignments"])
    assert float(c["moe_bias_abs_max"]) == float(jnp.abs(want).max())
    assert int(c["moe_rows_static"]) == MOE_LAYERS * static_rows(
        tokens.size * 3, HELD[share][1], cfg.gmm_tile)
    assert int(c["moe_rows_filled"]) == int(c["moe_assignments_held"])
    # every layer walked a rung that holds what its routing filled
    assert int(c["moe_rows_filled"]) <= int(c["moe_rows_walked"]) <= int(
        c["moe_rows_static"])
    assert int(c["moe_rows_walked"]) % cfg.gmm_tile == 0
    if share == "all":
        assert int(c["moe_assignments_held"]) == int(c["moe_assignments"])
    else:
        assert 0 < int(c["moe_rows_filled"]) < int(c["moe_assignments"])


@pytest.mark.parametrize("name", reference.MUTATIONS)
def test_mutation_is_told_apart(program, name):
    """A reference with one term changed must fail
    `test_decoder_matches_reference` by its tolerances: by ten times
    LOGIT_ATOL on a head's logits, or, where only the second term's
    target or weight changes, by ten times LOSS_RTOL on the loss."""
    _, params, state, tokens, model = _setup(HELD["all"])
    loss, logits, logits2, _, _ = program["all"]
    with jax.default_matmul_precision("highest"):
        ref_loss, (_, _, ref_logits, ref_logits2, _) = jax.jit(
            lambda p: _reference(p, state["expert_bias"], tokens, model,
                                 name))(params)
    off = max(float(jnp.abs(logits - ref_logits).max()),
              float(jnp.abs(logits2 - ref_logits2).max()))
    loss_off = abs(loss - float(ref_loss)) / float(ref_loss)
    if name in ("MTP predicts t_{i+1}", "lambda 0"):
        assert loss_off > 10 * LOSS_RTOL       # the logits are the same
    else:
        # at seeded weights the softmax is near uniform and the loss
        # near log(V) whatever the blocks compute: the logits tell
        assert off > 10 * LOGIT_ATOL, (off, loss_off)


def test_mtp_uses_the_main_models_embedding_and_head():
    """The gradient of the embedding and of the head is the sum of both
    uses: with lambda 0 both change, and the MTP block's own leaves get
    none."""
    cfg, params, state, tokens, _ = _setup(HELD["all"])
    grad = jax.jit(lambda p, c: jax.grad(lambda p: decoder.stateful_loss(
        p, state, tokens, c)[0])(p), static_argnums=1)
    both = grad(params, cfg)
    main = grad(params, dataclasses.replace(cfg, mtp_weight=0.0))
    assert float(jnp.abs(main["mtp"]["proj"]).max()) == 0
    assert float(jnp.abs(both["mtp"]["proj"]).max()) > 0
    for leaf in ("embed", "head"):
        assert float(jnp.abs(both[leaf] - main[leaf]).max()) > 1e-6


def test_shares_add_up_to_the_uncut_layer():
    """The share test: the routed parts of the four shares (experts 0-1,
    2-3, 4-5, 6-7 of 8, top-3), with what every chip computes alike (the
    mixer, the residual and the SHARED expert) counted once, add up to
    the uncut reference's layer output."""
    cfg, params, state, _, model = _setup(HELD["all"])
    bias = state["expert_bias"][0]
    group = {n: g for n, (g, _, _) in decoder._leaves(cfg).items()}
    # layer 1: the first expert layer (row 0 of the experts' stacks)
    p = {n: leaf[0 if group[n] == "experts" else 1]
         for n, leaf in params["layers"].items() if group[n] != "dense"}
    h = jax.random.normal(jax.random.key(7), (1, 64, cfg.d_model))
    with jax.default_matmul_precision("highest"):
        whole, m, n = reference.layer(h[0], p, bias, mlp="experts",
                                      model=model)
    assert int(n.sum()) == 64 * 3
    alike = whole - m      # mixer, residual and the shared expert, once
    total = alike
    for first in (0, 2, 4, 6):
        share = dataclasses.replace(cfg, held=(first, 2))
        mine = dict(p, expert_bias=bias, **{
            k: p[k][first:first + 2] for k in ("w_gate", "w_up", "w_down")})
        out, counts = jax.jit(functools.partial(
            decoder._layer, cfg=share, mlp="experts", attention="latent"))(
                h, mine, decoder._rope_for(64, share))
        with jax.default_matmul_precision("highest"):
            _, m_ref, _ = reference.layer(h[0], mine, bias, mlp="experts",
                                          model=model, first=first)
        assert float(jnp.abs(out[0] - alike - m_ref).max()) <= 2e-6
        assert int(counts["held"]) == int(n[first:first + 2].sum())
        total = total + (out[0] - alike)
    assert float(jnp.abs(m).max()) > 1e-3
    assert float(jnp.abs(total - whole).max()) <= 5e-6


def test_routing_factor_scales_the_routed_part_only():
    """`routed_scale` multiplies the routed sum and leaves the shared
    expert alone; at 1 nothing is traced for it."""
    cfg, params, state, _, _ = _setup(HELD["all"])
    group = {n: g for n, (g, _, _) in decoder._leaves(cfg).items()}
    p = {n: leaf[0 if group[n] == "experts" else 1]
         for n, leaf in params["layers"].items() if group[n] != "dense"}
    p["expert_bias"] = state["expert_bias"][0]
    h = jax.random.normal(jax.random.key(7), (1, 64, cfg.d_model))

    def out(**kw):
        c = dataclasses.replace(cfg, **kw)
        return decoder._layer(h, p, decoder._rope_for(64, c), cfg=c,
                              mlp="experts", attention="latent")[0]

    base = out(routed_scale=1.0, d_shared=0)
    shared = out(routed_scale=1.0) - base
    routed = out(d_shared=0) - out(routed_scale=0.5, d_shared=0)   # 2 x
    assert float(jnp.abs(shared).max()) > 1e-3
    assert float(jnp.abs(out() - out(d_shared=0) - shared).max()) <= 1e-5
    unit = out(routed_scale=1.0, d_shared=0) - out(
        routed_scale=0.5, d_shared=0)                              # 0.5 x
    assert float(jnp.abs(routed - 4 * unit).max()) <= 1e-5


# ----------------------------------------------------------------------
# the kernels at two widths
# ----------------------------------------------------------------------

def _dense(q, k, v, scale):
    s = jnp.einsum("bqhd,bkhd->bhqk", q, k) * scale
    t = q.shape[1]
    s = jnp.where(jnp.tril(jnp.ones((t, t), bool))[None, None], s, -jnp.inf)
    return jnp.einsum("bhqk,bkhd->bqhd", jax.nn.softmax(s, -1), v)


@pytest.mark.parametrize("remat", [False, True], ids=["plain", "checkpoint"])
@pytest.mark.parametrize("t", [64, 128, 48], ids=[
    "two-key-blocks", "bwd-tile-above-fwd", "no-whole-tile"])
def test_flash_attention_at_two_widths(t, remat, monkeypatch):
    """`flash_attention` with 24-wide queries and keys and 16-wide
    values against dense attention: the output (16 wide) and all three
    gradients, plain and under `jax.checkpoint`. T 48 has no whole tile
    of 32 keys and takes the checkpointed dense block."""
    monkeypatch.setattr(attention, "_bwd_tiles",
                        lambda t, d, dtype, d_v=None: (32, 32))
    ks = jax.random.split(jax.random.key(t), 4)
    q, k = (jax.random.normal(x, (2, t, 3, 24)) for x in ks[:2])
    v, w = (jax.random.normal(x, (2, t, 3, 16)) for x in ks[2:])

    def fn(q, k, v):
        return attention.flash_attention(q, k, v, True, None, 16, 32)

    fn = jax.checkpoint(fn) if remat else fn
    with jax.default_matmul_precision("highest"):
        out = fn(q, k, v)
        grads = jax.grad(lambda *a: (fn(*a) * w).sum(), (0, 1, 2))(q, k, v)
        want = _dense(q, k, v, 24 ** -0.5)
        want_grads = jax.grad(lambda *a: (_dense(*a, 24 ** -0.5) * w).sum(),
                              (0, 1, 2))(q, k, v)
    assert out.shape == (2, t, 3, 16)
    assert float(jnp.abs(out - want).max()) <= 2e-6
    for got, ref, width in zip(grads, want_grads, (24, 24, 16)):
        assert got.shape[-1] == width
        assert float(jnp.abs(got - ref).max()) <= 1e-5


def test_flash_attention_refuses_widths_that_do_not_pair():
    q = jnp.zeros((1, 64, 2, 24))
    with pytest.raises(ValueError, match="share the score width"):
        attention.flash_attention(q, jnp.zeros((1, 64, 2, 16)),
                                  jnp.zeros((1, 64, 2, 16)))


# sha256 of the jaxpr of value_and_grad(flash_attention) on the parent
# commit (c636c2d), at SmallThinker-tiny's grouped, windowed shape and at
# the plain one: with one width the two-width code traces to that text
# (recorded again at PR 60, whose forward kernel walks its key blocks in
# runs: the text moved inside `flash_fwd`'s body alone, the backward
# kernel's is the parent's, `_flash_bwd_call` traced beside it)
PARENT_JAXPR = {
    "grouped-window": "1e108832d27aafcfc76cd0b17d1c3da9bb5cea9fcbd5f28e25ab6e7014623e9a",
    "plain": "e4cfb78bd75bf10f1a5936537707cbafcd6d37d7f5fd20fc33e471656ec990ed"}
# ... and with the two `name` equations `_fwd` gives the kernel's output
# and log-sum-exp since PR 40: the text a trace has now. PARENT_JAXPR is
# that text without them, so a change to the kernels still shows.
NAMED_JAXPR = {
    "grouped-window": "326ea4b8d84264a839a5431c6aa1df4187da251c51d590e8390b31758dffd111",
    "plain": "51c446b3c9c2edafff32cb62026c4788173ed7ef988c0c359f42ed295d61f6c2"}


def _equal_widths_jaxpr(case):
    h_kv, window = (2, 16) if case == "grouped-window" else (4, None)
    q = jnp.zeros((2, 64, 4, 16))
    kv = jnp.zeros((2, 64, h_kv, 16))
    text = str(jax.make_jaxpr(jax.value_and_grad(
        lambda q, k, v: attention.flash_attention(
            q, k, v, True, None, 16, 32, window).sum(), (0, 1, 2)))(
                q, kv, kv))
    return hashlib.sha256(text.encode()).hexdigest()


@pytest.mark.parametrize("case", list(PARENT_JAXPR))
def test_equal_widths_trace_to_the_parents_program(case, monkeypatch):
    monkeypatch.setattr(attention, "checkpoint_name", lambda x, name: x)
    assert _equal_widths_jaxpr(case) == PARENT_JAXPR[case]


@pytest.mark.parametrize("case", list(NAMED_JAXPR))
def test_equal_widths_trace_to_the_named_program(case):
    assert _equal_widths_jaxpr(case) == NAMED_JAXPR[case]
