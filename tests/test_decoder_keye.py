"""The decoder under learned sparse attention (`cfg.index_topk`; family
`keye`) against the family's plain reference, at `keye_tiny`: float32,
seeded weights, 4 query heads a key/value head, an indexer of 4 heads of
16 that keeps 32 of up to 128 keys, 2 of 16 experts held, top-4; the
kernels run in interpret mode.

Tolerances. Program and reference both compute in float32 here, so what
separates them is the order of float32 sums: measured 1e-7 on the loss,
3e-7 on a logit, 9e-7 of a leaf's largest gradient. LOSS_RTOL,
LOGIT_ATOL and GRAD_RTOL sit some way above that, and far below what
the smallest mutation of `test_mutation_is_told_apart` moves."""

import dataclasses
import functools
import re

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from benchmark import manifest
from benchmark.families import keye, keye_reference as reference
from ray_tpu.models import decoder

LOSS_RTOL = 3e-6
LOGIT_ATOL = 1e-5
GRAD_RTOL = 3e-5      # of the leaf's largest reference gradient

MODEL = manifest.config_file("keye_tiny")
LENGTH, TOPK = 128, MODEL["sa_config"]["topk"]
INDEXER = ("w_index_q", "w_index_k", "index_k_norm", "w_index_w")


@functools.cache
def _cfg():
    return dataclasses.replace(keye.model_cfg(MODEL), dtype=jnp.float32,
                               index_dtype=jnp.float32)


@functools.cache
def _setup(variant="seeded"):
    """`seeded`: the weights as drawn, the indexer's LayerNorm moved off
    its start (at weight one and bias zero a dropped bias is not seen).
    `tied`: the indexer's query projection zeroed, so that EVERY score
    is +0.0 and a row is one tie: the planted tie."""
    cfg, key = _cfg(), jax.random.key(0)
    params, state = decoder.init(key, cfg), decoder.state_init(key, cfg)
    layers = dict(params["layers"])
    layers["index_k_norm"] = layers["index_k_norm"] + 0.3 * jax.random.normal(
        jax.random.key(5), layers["index_k_norm"].shape)
    if variant == "tied":
        layers["w_index_q"] = jnp.zeros_like(layers["w_index_q"])
    tokens = jax.random.randint(jax.random.key(1), (2, LENGTH), 0,
                                cfg.vocab_size)
    return cfg, dict(params, layers=layers), state, tokens


@functools.cache
def _program_fns():
    cfg = _cfg()

    def terms(p, t):
        _, counts = decoder.loss_fn(p, t, cfg)
        return jnp.stack([counts["loss_main"], counts["loss_index"]])

    return (jax.jit(lambda p, t: (terms(p, t), jax.jacrev(terms)(p, t))),
            jax.jit(lambda p, t: decoder.apply(p, t, cfg)))


@functools.cache
def _program(variant="seeded"):
    """((CE, L_I), logits, each term's gradient: leaves [2, ...])."""
    _, params, _, tokens = _setup(variant)
    both, logits = _program_fns()
    terms, jac = both(params, tokens)
    return terms, logits(params, tokens), jac


@functools.cache
def _reference_fns(mutate=""):
    def terms(p, t):
        return jnp.stack(reference.terms_of(p, t, MODEL, mutate))

    return (jax.jit(lambda p, t: (terms(p, t), jax.jacrev(terms)(p, t))),
            jax.jit(lambda p, t: jnp.stack([
                reference.forward(p, row, MODEL, mutate) for row in t])))


@functools.cache
def _reference(variant="seeded", mutate="", grads=True):
    """`grads` False: the forward pass alone (a mutation that moves a
    logit or a loss term needs no more), the gradients None."""
    _, params, _, tokens = _setup(variant)
    both, logits = _reference_fns(mutate)
    with jax.default_matmul_precision("highest"):
        if grads:
            terms, jac = both(params, tokens)
        else:
            terms, jac = jax.jit(lambda p, t: jnp.stack(reference.terms_of(
                p, t, MODEL, mutate)))(params, tokens), None
        return terms, logits(params, tokens), jac


def _apart(got, want) -> float:
    """How far two ((CE, L_I), logits, gradients) lie apart, in units of
    the tolerances: 1 is the limit of agreement."""
    loss = float(jnp.abs(got[0] - want[0]).max()) / (
        LOSS_RTOL * float(jnp.abs(want[0]).sum()))
    logits = float(jnp.abs(got[1] - want[1]).max()) / LOGIT_ATOL
    if want[2] is None:
        return max(loss, logits)
    grads = max(jax.tree.leaves(jax.tree.map(
        lambda a, r: float(jnp.abs(a - r).max()) / (
            GRAD_RTOL * float(jnp.abs(r).max()) + 1e-30), got[2], want[2])))
    return max(loss, logits, grads)


def test_the_tree_and_state_are_the_families():
    cfg, params, state, _ = _setup()
    assert (cfg.index_topk, cfg.index_heads, cfg.index_dim) == (TOPK, 4, 16)
    layers = params["layers"]
    assert set(INDEXER) <= set(layers)
    assert layers["w_index_q"].shape == (2, 64, 64)
    assert layers["w_index_k"].shape == (2, 64, 16)
    assert layers["index_k_norm"].shape == (2, 2, 16)     # weight, bias
    assert layers["w_index_w"].shape == (2, 64, 4)
    drawn = decoder.init(jax.random.key(0), cfg)["layers"]["index_k_norm"]
    assert np.array_equal(drawn[:, 0], np.ones((2, 16))) \
        and not np.asarray(drawn[:, 1]).any()
    assert {"index_pairs_selected", "index_pairs_causal",
            "index_pairs_beyond_window", "index_tiles_visited",
            "index_tiles_causal", "index_kl_sum", "index_kl_count",
            "loss_main", "loss_index"} <= set(state["epoch_counters"])


def test_loss_logits_and_every_gradient_match_the_reference():
    got, want = _program(), _reference()
    assert _apart(got, want) <= 1.0, _apart(got, want)
    # the indexer's leaves get the second term's gradient alone, every
    # other leaf the first's alone: the zeros are exact
    for name, leaf in got[2]["layers"].items():
        main, index = np.asarray(leaf[0]), np.asarray(leaf[1])
        if name in INDEXER:
            assert not main.any() and index.any(), name
        else:
            assert main.any() and not index.any(), name
    for name in ("embed", "head", "norm_f"):
        assert np.asarray(got[2][name][0]).any() \
            and not np.asarray(got[2][name][1]).any()


def test_a_row_that_is_one_tie_keeps_its_lowest_keys():
    """The planted tie: with every score +0.0 the program selects what
    the reference does (the first min(t + 1, topk) keys of a row), not
    the window a higher-index rule would give."""
    got = _program("tied")
    assert _apart(got, _reference("tied")) <= 1.0
    assert _apart(got, _reference("tied", "ties_high", False)) > 10.0


def test_the_selected_set_is_top_ks():
    """The reference's threshold-and-count selection is `lax.top_k`'s
    set, on seeded scores and on a row of planted ties."""
    scores = jax.random.normal(jax.random.key(2), (64, LENGTH))
    scores = scores.at[:, 5::7].set(0.25).at[40:].set(0.0)
    for lo in (0, 64):
        keep = reference.selection(scores, lo, TOPK)
        row = lo + np.arange(64)[:, None]
        causal = np.arange(LENGTH)[None, :] <= row
        _, picks = jax.lax.top_k(jnp.where(causal, scores, -jnp.inf), TOPK)
        want = np.zeros_like(causal)
        np.put_along_axis(want, np.asarray(picks), True, axis=1)
        assert np.array_equal(np.asarray(keep), want & causal)
        assert np.array_equal(np.asarray(keep).sum(1),
                              np.minimum(row[:, 0] + 1, TOPK))


@pytest.mark.parametrize("mutation", [m for m in reference.MUTATIONS
                                      if m != "ties_high"])
def test_mutation_is_told_apart(mutation):
    """Each departure from the equations moves a loss term, a logit or a
    gradient by at least ten times the tolerance (the two detachments
    move gradients alone)."""
    grads = mutation in ("target_attached", "input_attached")
    assert _apart(_program(), _reference("seeded", mutation, grads)) > 10.0


def test_the_three_stream_rope_is_the_plain_one():
    x = jax.random.normal(jax.random.key(3), (LENGTH, 3, 16))
    positions = jnp.arange(LENGTH)
    plain = reference.rope(x, positions, 1e7)
    streams = reference.rope(x, jnp.tile(positions, (3, 1)), 1e7, [2, 3, 3])
    assert np.array_equal(np.asarray(plain), np.asarray(streams))
    table = decoder.rope_tables(positions.astype(jnp.float32), _cfg())
    assert float(jnp.abs(decoder._rope(x[None], *table)[0] - plain).max()) \
        <= 1e-6
    # and the streams are read: another height moves the pairs it names
    moved = reference.rope(
        x, jnp.stack([positions, positions + 1, positions]), 1e7, [2, 3, 3])
    assert np.array_equal(np.asarray(moved[..., :2]),
                          np.asarray(plain[..., :2]))
    assert not np.array_equal(np.asarray(moved[..., 2:5]),
                              np.asarray(plain[..., 2:5]))


def test_shares_add_up_to_the_uncut_layer():
    """The share test: the layer outputs of the eight shares (experts
    0-1, 2-3, .. 14-15 of 16), attention over the selection and the
    residual counted once, add up to the uncut reference's layer — the
    first and the last rank's share the program's, the six between the
    reference's (each share costs the program a compile)."""
    cfg = _cfg()
    whole_model = dict(MODEL, num_experts=16)
    whole_cfg = dataclasses.replace(keye.model_cfg(whole_model),
                                    dtype=jnp.float32,
                                    index_dtype=jnp.float32)
    p = {k: v[0] for k, v in decoder.init(
        jax.random.key(3), whole_cfg)["layers"].items()}
    h = jax.random.normal(jax.random.key(7), (1, LENGTH, cfg.d_model))
    with jax.default_matmul_precision("highest"):
        whole, m, _, _ = reference.layer(h[0], p, whole_model)
    attention_and_residual = whole - m        # what every chip computes alike
    total = attention_and_residual
    for first in range(0, 16, 2):
        share = dataclasses.replace(cfg, held=(first, 2))
        mine = dict(p, **{k: p[k][first:first + 2]
                          for k in ("w_gate", "w_up", "w_down")})
        with jax.default_matmul_precision("highest"):
            want, routed, _, _ = reference.layer(h[0], mine, MODEL,
                                                 first=first)
        if first in (0, 14):
            out, counts = jax.jit(functools.partial(
                decoder._layer, cfg=share, mlp="experts", attention="full"))(
                    h, mine, decoder._rope_for(LENGTH, share))
            assert int(counts["dropped"]) == 0
            assert float(jnp.abs(out[0] - want).max()) <= LOGIT_ATOL
            routed = out[0] - attention_and_residual
        total = total + routed
    assert float(jnp.abs(total - whole).max()) <= LOGIT_ATOL


def test_a_short_sequence_runs_the_parents_causal_program():
    """T <= index_topk: every causal key is selected, no plane is built
    and the attention is the plain causal call — the logits are those of
    the same weights without an indexer, the step's text holds neither
    the indexer's kernel nor a selection, and the indexer's loss is
    still the reference's."""
    cfg, params, state, tokens = _setup()
    short = tokens[:, :TOPK]
    text = str(jax.make_jaxpr(
        lambda p, s, t: decoder.stateful_loss(p, s, t, cfg))(
            params, state, short))
    assert "index_scores" not in text and "flash_fwd" in text
    assert "index_scores" in str(jax.make_jaxpr(
        lambda p, s, t: decoder.stateful_loss(p, s, t, cfg))(
            params, state, tokens))
    plain = dataclasses.replace(cfg, index_topk=0, index_heads=0,
                                index_dim=0)
    bare = dict(params, layers={k: v for k, v in params["layers"].items()
                                if k not in INDEXER})
    assert np.array_equal(
        np.asarray(jax.jit(lambda p, t: decoder.apply(p, t, cfg))(
            params, short)),
        np.asarray(jax.jit(lambda p, t: decoder.apply(p, t, plain))(
            bare, short)))
    _, new = jax.jit(lambda p: decoder.stateful_loss(p, state, short, cfg))(
        params)
    counters = new["epoch_counters"]
    with jax.default_matmul_precision("highest"):
        want = reference.terms_of(params, short, MODEL)
    assert float(counters["loss_index"]) == pytest.approx(float(want[1]),
                                                          rel=1e-5)
    assert float(counters["index_pairs_selected"]) \
        == float(counters["index_pairs_causal"]) \
        == 2 * 2 * TOPK * (TOPK + 1) // 2
    assert float(counters["index_pairs_beyond_window"]) == 0.0


def test_stateful_loss_counts_the_selection():
    cfg, params, state, tokens = _setup()
    loss, new = jax.jit(
        lambda p, s, t: decoder.stateful_loss(p, s, t, cfg))(
            params, state, tokens)
    c = {k: float(v) for k, v in new["epoch_counters"].items()}
    terms = _program()[0]
    assert float(loss) == pytest.approx(float(terms.sum()), rel=1e-6)
    assert c["loss_main"] == pytest.approx(float(terms[0]), rel=1e-6)
    assert c["loss_index"] == pytest.approx(float(terms[1]), rel=1e-6)
    layers, b = 2, 2
    causal, selected = keye.pairs(LENGTH, TOPK)
    assert c["index_pairs_causal"] == layers * b * causal
    assert c["index_pairs_selected"] == layers * b * selected
    assert 0 < c["index_pairs_beyond_window"] < c["index_pairs_selected"]
    assert 0 < c["index_tiles_visited"] <= c["index_tiles_causal"]
    assert c["index_kl_count"] == layers * b * LENGTH
    assert c["index_kl_sum"] / c["index_kl_count"] == pytest.approx(
        c["loss_index"], rel=1e-5)
    facts = decoder.step_facts(cfg, tokens.shape)
    assert facts["index_topk"] == TOPK and facts["index_tile"] == "16x32" \
        and facts["index_rows"] == layers * b * LENGTH
    assert decoder.step_facts(cfg, (2, TOPK))["index_rows"] == 0


KERNELS = ("index_kl", "index_scores", "flash_fwd", "flash_bwd_fused")


def _kernels_a_layer(cfg):
    """The kernels in the text of the step's gradient: the two layers
    are one scanned period, so a layer's, forward and backward."""
    _, params, state, tokens = _setup()
    text = str(jax.make_jaxpr(jax.grad(
        lambda p: decoder.stateful_loss(p, state, tokens, cfg)[0]))(params))
    return {name: len(re.findall(rf"name={name}\n", text))
            for name in KERNELS}


def _keep_nothing(monkeypatch):
    """The block as the parent commit (397ba61) checkpointed it: no
    policy whatever it holds."""
    monkeypatch.setattr(decoder, "_kept_across_remat", lambda cfg, a: ())


@pytest.mark.parametrize("remat", [True, "parts", False])
def test_the_indexers_loss_runs_once_a_layer(remat):
    """A rematerialised block keeps what `index_kl`'s pass made, so its
    recomputed copy holds the attention and the index scores again but
    not the loss's kernel; `step_facts` says so from the same rule."""
    cfg, twice = dataclasses.replace(_cfg(), remat=remat), 1 + bool(remat)
    assert _kernels_a_layer(cfg) == {
        "index_kl": 1, "index_scores": twice, "flash_fwd": twice,
        "flash_bwd_fused": 1}
    assert decoder.step_facts(cfg, (2, LENGTH))["index_kl_runs"] \
        == cfg.n_layers == 2


@pytest.mark.parametrize("remat", [True, "parts"])
def test_without_the_policy_the_indexers_loss_runs_twice(remat,
                                                         monkeypatch):
    _keep_nothing(monkeypatch)
    cfg = dataclasses.replace(_cfg(), remat=remat)
    assert _kernels_a_layer(cfg) == {
        "index_kl": 2, "index_scores": 2, "flash_fwd": 2,
        "flash_bwd_fused": 1}
    assert decoder.step_facts(cfg, (2, LENGTH))["index_kl_runs"] == 4


def _mixer_part(h, p, rope):
    """Value and gradient of a layer's mixer part as `_block` builds it
    (the whole of what the policy touches: under "parts" it is this
    function that is checkpointed), the indexer's loss among the terms."""
    def value(h, p):
        out, found = decoder._block(_cfg(), "full", "none")(h, p, rope)
        return out.sum() + 0.5 * found["index_kl_sum"], found

    return jax.jit(jax.value_and_grad(value, (0, 1), has_aux=True))(h, p)


def test_what_is_kept_changes_no_bit(monkeypatch):
    """Value, `index_kl_sum`, the counts and every gradient leaf of the
    block that keeps the three arrays equal the unpoliced block's."""
    cfg, params, _, tokens = _setup()
    assert cfg.remat is True
    row = {name: x[0] for name, x in params["layers"].items()}
    given = (params["embed"][tokens[:1]], row,
             decoder._rope_for(LENGTH, cfg))
    kept = _mixer_part(*given)
    _keep_nothing(monkeypatch)
    plain = _mixer_part(*given)
    assert float(kept[0][1]["index_kl_sum"]) > 0
    for (path, a), b in zip(jax.tree_util.tree_leaves_with_path(kept),
                            jax.tree.leaves(plain)):
        assert a.tobytes() == b.tobytes(), jax.tree_util.keystr(path)
    indexer = {name: float(jnp.abs(kept[1][1][name]).max())
               for name in INDEXER}
    assert min(indexer.values()) > 0      # the loss's gradient arrived


def test_a_block_without_an_indexer_gets_no_policy(monkeypatch):
    """No name to keep, no policy: `jax.checkpoint` is called as the
    parent called it, for every kind but an indexed `full` one."""
    calls = []
    monkeypatch.setattr(
        decoder.jax, "checkpoint",
        lambda fn, **kw: calls.append(kw) or fn)
    plain = dataclasses.replace(_cfg(), index_topk=0, index_heads=0,
                                index_dim=0)
    for cfg, mixer in ((plain, "full"), (_cfg(), "window"),
                       (dataclasses.replace(plain, remat="parts"), "full")):
        assert decoder._kept_across_remat(cfg, mixer) == ()
        decoder._block(cfg, mixer, "experts")
    assert calls == [{}, {}, {}, {}]    # "parts": the mixer, then the rest
    decoder._block(dataclasses.replace(_cfg(), remat="parts"), "full",
                   "experts")
    assert [sorted(kw) for kw in calls[4:]] == [["policy"], []]


def test_what_the_indexer_is_not_built_for_is_refused():
    cfg = _cfg()
    for change in ({"mtp": 1}, {"diffusion_block": 4}, {"index_dim": 15},
                   {"index_heads": 0}, {"rotary": ()}):
        with pytest.raises(ValueError, match="index_topk"):
            dataclasses.replace(cfg, **change)
