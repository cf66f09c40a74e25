"""The pattern decoder (`models/decoder.py`), the dropless top-k expert
layer over a held share (`parallel/moe.py`, `ops/moe_gmm.py`) and the
window / grouped-query paths of `ops/attention.py`, against the plain
float32 reference `benchmark/families/smallthinker_reference.py`. CPU,
tiny widths: hidden 64, two periods of four layers, 8 experts top-3,
window 16, T 64, the 7-to-1 head grouping kept as 2 query heads a
key/value head; the kernels run in interpret mode.

Tolerances. Program and reference both compute in float32 here, so what
separates them is the order of float32 sums (blockwise softmax against
dense, a grouped matmul against a masked loop): measured 9e-8 on the
loss and 3e-7 on a logit. LOSS_RTOL, LOGIT_ATOL and GRAD_RTOL sit about
an order of magnitude above that, and below what the smallest mutation
of `test_mutation_is_told_apart` moves (rotary off on one window layer:
9e-6 on the loss, 9e-3 on a logit; the window mask off: 1.6e-3, 0.5)."""

import dataclasses
import functools
import hashlib
import re

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from benchmark.families import smallthinker_reference as reference
from ray_tpu.models import decoder
from ray_tpu.ops import attention
from ray_tpu.ops.attention import flash_attention
from ray_tpu.parallel import moe
from ray_tpu.parallel.moe import dropless_moe

LOSS_RTOL = 5e-7
LOGIT_ATOL = 5e-6
GRAD_RTOL = 5e-6      # of the leaf's largest reference gradient

CFG = dataclasses.replace(decoder.TINY, n_layers=8, dtype=jnp.float32)
# the reference's view of the same model: the source's keys
MODEL = {
    "head_dim": 16, "num_attention_heads": 4, "num_key_value_heads": 2,
    "rms_norm_eps": 1e-6, "rope_theta": 1.5e6, "sliding_window_size": 16,
    "sliding_window_layout": [0, 1, 1, 1] * 2, "rope_layout": [0, 1, 1, 1] * 2,
    "moe_num_active_primary_experts": 3, "held_experts_first": 0}
HELD = {"all": (0, 8), "subset": (2, 4)}


def _setup(held, seed=0):
    cfg = dataclasses.replace(CFG, held=held)
    params = decoder.init(jax.random.key(seed), cfg)
    tokens = jax.random.randint(jax.random.key(seed + 1), (2, 64), 0,
                                cfg.vocab_size)
    return cfg, params, tokens, dict(MODEL, held_experts_first=held[0])


def _reference(params, tokens, model, **kw):
    """(mean loss, logits [B, T, V]) of the plain reference: one pass."""
    logits = jnp.stack([reference.logits(params, row, model, **kw)
                        for row in tokens])
    logp = jax.nn.log_softmax(logits[:, :-1], axis=-1)
    nll = -jnp.take_along_axis(logp, tokens[:, 1:, None], axis=-1)
    return nll.mean(), logits


@pytest.fixture(scope="module")
def program():
    """The program's loss, logits and gradients, once a held share."""
    out = {}
    for name, held in HELD.items():
        cfg, params, tokens, _ = _setup(held)
        (loss, counts), grads = jax.jit(jax.value_and_grad(
            lambda p: decoder.loss_fn(p, tokens, cfg), has_aux=True))(params)
        logits = jax.jit(lambda p: decoder.apply(p, tokens, cfg))(params)
        out[name] = (float(loss), logits, grads, counts)
    return out


@pytest.mark.parametrize("share", list(HELD))
def test_decoder_matches_reference(program, share):
    """Loss, logits and every leaf's gradient, with all experts held
    and with a held subset (experts 2..5 of 8)."""
    _, params, tokens, model = _setup(HELD[share])
    loss, logits, grads, counts = program[share]
    with jax.default_matmul_precision("highest"):
        (ref_loss, ref_logits), ref_grads = jax.jit(jax.value_and_grad(
            lambda p: _reference(p, tokens, model), has_aux=True))(params)
    assert abs(loss - float(ref_loss)) <= LOSS_RTOL * float(ref_loss)
    assert float(jnp.abs(logits - ref_logits).max()) <= LOGIT_ATOL
    flat, _ = jax.tree_util.tree_flatten_with_path(grads)
    for (path, got), want in zip(flat, jax.tree.leaves(ref_grads)):
        scale = float(jnp.abs(want).max())
        assert scale > 0, path       # every leaf is reached by the loss
        assert float(jnp.abs(got - want).max()) <= GRAD_RTOL * scale, path
    n = tokens.size * CFG.top_k
    assert counts["assignments"].tolist() == [n] * CFG.n_layers
    assert counts["dropped"].tolist() == [0] * CFG.n_layers
    assert (counts["expert_tokens"].sum(-1) == counts["held"]).all()
    if share == "all":
        assert counts["held"].tolist() == [n] * CFG.n_layers
    else:
        assert 0 < int(counts["held"].sum()) < n * CFG.n_layers


@pytest.mark.parametrize("windowed", [False, True])
def test_shares_add_up_to_the_uncut_layer(windowed):
    """The share test: the layer outputs of the four shares (experts
    0-1, 2-3, 4-5, 6-7 of 8), attention and residual counted once, add
    up to the uncut reference's layer output."""
    cfg, params, tokens, model = _setup((0, 8))
    layer = 1 if windowed else 0
    p = {k: v[layer] for k, v in params["layers"].items()}
    h = jax.random.normal(jax.random.key(7), (1, 64, cfg.d_model))
    with jax.default_matmul_precision("highest"):
        whole, m = reference.layer(h[0], p, windowed=windowed,
                                   rotary=windowed, model=model)
    attention_and_residual = whole - m        # what every chip computes alike
    total = attention_and_residual
    for first in (0, 2, 4, 6):
        share = dataclasses.replace(cfg, held=(first, 2))
        mine = dict(p, **{k: p[k][first:first + 2]
                          for k in ("w_gate", "w_up", "w_down")})
        out, counts = jax.jit(functools.partial(
            decoder._layer, cfg=share, mlp="experts",
            attention="window" if windowed else "full"))(
                h, mine, decoder.rope_tables(
                    jnp.arange(64, dtype=jnp.float32), share))
        assert int(counts["dropped"]) == 0
        total = total + (out[0] - attention_and_residual)
    assert float(jnp.abs(total - whole).max()) <= LOGIT_ATOL


def test_no_token_is_dropped_under_a_biased_router():
    """A router biased so that ONE expert gets every token: the layer
    still matches the reference, that expert's count is every token and
    the dropped-assignment counter reads 0. (A capacity of 1.25 x the
    mean would have dropped five tokens in six.)"""
    n, d, f, experts, k = 96, 32, 16, 8, 3
    keys = jax.random.split(jax.random.key(3), 5)
    y = jax.random.normal(keys[0], (n, d))
    r = jax.random.normal(keys[1], (n, experts)) + 50.0 * jax.nn.one_hot(
        5, experts)
    p = {"w_gate": jax.random.normal(keys[2], (experts, d, f)) * 0.2,
         "w_up": jax.random.normal(keys[3], (experts, d, f)) * 0.2,
         "w_down": jax.random.normal(keys[4], (experts, f, d)) * 0.2}
    out, counts = jax.jit(functools.partial(
        dropless_moe, top_k=k, held=(0, experts), tile=8))(
            y, r, p["w_gate"], p["w_up"], p["w_down"])
    with jax.default_matmul_precision("highest"):
        want = reference.routed(y, r, p, first=0, k_active=k)
    assert float(jnp.abs(out - want).max()) <= LOGIT_ATOL
    assert int(counts["expert_tokens"][5]) == n
    assert int(counts["dropped"]) == 0 and int(counts["held"]) == n * k


# The ladder of row counts (`moe.row_ladder`). 128 tokens, top-2 of 16
# experts, tiles of 8 rows: with two experts held the worst case is 34
# tiles and the rungs stand at 5, 9, 13, 17 and 34. `crowd` tokens are
# sent to the two held experts with both their slots and no other token
# to either, so each held expert gets `crowd` rows: 2 * ceil(crowd / 8)
# tiles are filled.
# name -> (held, crowd, gated, routing bias, rung taken)
LADDER_CASES = {
    "under-an-eighth": ((4, 2), 16, True, False, 0),
    "under-an-eighth-a-tile-short": ((4, 2), 17, True, False, 1),
    "between-one-and-two-eighths": ((4, 2), 32, True, False, 1),
    "between-two-and-three-eighths": ((4, 2), 48, True, True, 2),
    "between-three-eighths-and-half": ((4, 2), 64, False, False, 3),
    "above-half": ((4, 2), 72, True, False, 4),
    "worst-case-a-held-share": ((4, 2), 128, True, False, 4),
    "every-expert-held": ((0, 16), None, True, False, 4),
    "every-expert-held-ungated-sigmoid-bias": ((0, 16), None, False, True, 4),
}
LADDER_N, LADDER_E, LADDER_K, LADDER_TILE = 128, 16, 2, 8


def _ladder_inputs(held, crowd, gated):
    n, d, f = LADDER_N, 32, 16
    first, count = held
    keys = jax.random.split(jax.random.key(11), 6)
    y = jax.random.normal(keys[0], (n, d))
    logits = jax.random.normal(keys[1], (n, LADDER_E))
    if crowd is not None:
        on_held = (jnp.arange(LADDER_E) >= first) & (
            jnp.arange(LADDER_E) < first + count)
        sent = jnp.arange(n) < crowd
        logits = logits + 12.0 * jnp.where(sent[:, None], 1.0, -1.0) * on_held
    if gated:
        w_gate = jax.random.normal(keys[2], (count, d, f)) * 0.2
        w_up = jax.random.normal(keys[3], (count, d, f)) * 0.2
    else:       # the ungated form keeps `w_up` [count, F, D]
        w_gate, w_up = None, jax.random.normal(keys[3], (count, f, d)) * 0.2
    w_down = jax.random.normal(keys[4], (count, f, d)) * 0.2
    return y, logits, w_gate, w_up, w_down, jax.random.normal(keys[5], (n, d))


@pytest.mark.parametrize("case", LADDER_CASES)
def test_a_rung_is_the_worst_case_bit_for_bit(case):
    """The expert block on the rung its routing chose against the same
    body (`moe._walk`, plainly differentiated) on the worst-case rows:
    the output and the gradients of the tokens, the routing weights and
    the weight stacks (gate and up as the one stack the block takes, and
    down) are EQUAL, no token is dropped and `rows_walked`
    is the expected rung's."""
    held, crowd, gated, biased, rung = LADDER_CASES[case]
    y, logits, w_gate, w_up, w_down, dout = _ladder_inputs(held, crowd, gated)
    activation = "silu" if gated else "relu2"
    bias = 1e-3 * jnp.arange(LADDER_E, dtype=jnp.float32) if biased else None
    ladder = moe.row_ladder(LADDER_N * LADDER_K, held[1], LADDER_TILE)
    assert len(ladder) == 5 and ladder[-1] == moe.static_rows(
        LADDER_N * LADDER_K, held[1], LADDER_TILE)

    if biased:
        idx, weights, _ = moe.route_sigmoid_bias(logits, bias, LADDER_K)
    else:
        idx, weights = moe.route_topk(logits, LADDER_K)
    g = moe.group_by_expert(idx, held, LADDER_TILE)
    tiles = int(g.n_tiles[0])
    assert ladder[rung] >= tiles * LADDER_TILE and (
        rung == 0 or ladder[rung - 1] < tiles * LADDER_TILE)

    # gate and up side by side, as `dropless_moe` hands them to the block
    w_in = w_up if w_gate is None else jnp.concatenate([w_gate, w_up], -1)

    def value_and_grads(block):
        value, grads = jax.jit(jax.value_and_grad(
            lambda *a: (block(*a) * dout).sum(), (0, 1, 2, 3)))(
                y, weights, w_in, w_down)
        return value, *grads

    def ladder_block(y, weights, w_in, w_down):
        return moe._expert_block(y, weights, g, jnp.int32(rung), w_in,
                                 w_down, LADDER_TILE, activation, gated,
                                 ladder)

    def worst_case(y, weights, w_in, w_down):
        return moe._walk(ladder[-1], y, weights, g, w_in, w_down,
                         LADDER_TILE, activation, gated)

    for name, a, b in zip(("out", "dy", "dweights", "dw_in", "dw_down"),
                          value_and_grads(ladder_block),
                          value_and_grads(worst_case)):
        assert float(jnp.abs(b).max()) > 0, name
        assert np.array_equal(np.asarray(a), np.asarray(b)), name

    out, counts = jax.jit(functools.partial(
        dropless_moe, top_k=LADDER_K, held=held, tile=LADDER_TILE,
        activation=activation, bias=bias))(y, logits, w_gate, w_up, w_down)
    assert np.array_equal(np.asarray(out), np.asarray(jax.jit(worst_case)(
        y, weights, w_in, w_down)))
    assert int(counts["rows_walked"]) == ladder[rung]
    assert int(counts["dropped"]) == 0
    assert int(counts["held"]) == int(g.held.sum()) == (
        LADDER_N * LADDER_K if crowd is None else 2 * crowd)


def test_a_small_call_has_fewer_rungs():
    """Whole tiles, ascending, the worst case on top, no rung twice."""
    assert moe.row_ladder(180224 - 32 * 512, 32) == (
        44 * 512, 88 * 512, 132 * 512, 176 * 512, 180224)
    assert moe.row_ladder(16, 1, 8) == (8, 16, 24)
    assert moe.row_ladder(4, 1, 8) == (8, 16)


def test_a_checkpoint_recomputes_no_rung():
    """The compiled gradient of the block under `jax.checkpoint` holds
    TWO conditionals over the ladder, the forward's and the backward's:
    the residuals are the block's inputs, so the checkpoint's second
    forward is dead code and the grouped matmuls run as often as they
    did."""
    held = (4, 2)
    y, logits, w_gate, w_up, w_down, dout = _ladder_inputs(held, 32, True)
    ladder = moe.row_ladder(LADDER_N * LADDER_K, held[1], LADDER_TILE)

    @jax.checkpoint
    def block(y, logits, *w):
        return dropless_moe(y, logits, *w, top_k=LADDER_K, held=held,
                            tile=LADDER_TILE)[0]

    text = jax.jit(jax.value_and_grad(
        lambda *a: (block(*a) * dout).sum(), (0, 1, 2, 3, 4))).lower(
            y, logits, w_gate, w_up, w_down).compile().as_text()
    over_the_ladder = [
        line for line in text.splitlines() if " conditional(" in line
        and "branch_computations={" in line
        and line.split("branch_computations={")[1].split("}")[0].count(",")
        == len(ladder) - 1]
    assert len(over_the_ladder) == 2


@pytest.mark.parametrize("walked, want", [
    ([0.125, 0.125, 0.125], 12.5), ([0.25, 0.375, 0.375, 0.25], 31.25),
    ([None, None], None)])
def test_the_rows_walked_share_reads_the_sync_spans(monkeypatch, walked,
                                                    want):
    """`benchmark/layer_metrics/expert_rows_walked_share.py`:
    `moe_rows_walked` over `moe_rows_static` on each window call's
    `train.sync`, the median over the calls in percent; None, and no
    error, where the spans carry no such counter (the program before the
    ladder) or there is no log."""
    from benchmark.layer_metrics import expert_rows_walked_share as reader
    import ray_tpu.train as train

    static = 4 * 8 * moe.static_rows(16384 * 8, 8)

    def entry(t0, share):
        attrs = {"moe_rows_static": float(static), "moe_rows_filled": 1e5}
        if share is not None:
            attrs["moe_rows_walked"] = share * static
        return {"trace_id": str(t0), "spans": [
            {"name": "train.call", "start": t0, "end": t0 + 5.0,
             "span": "r", "parent": None, "attrs": {}},
            {"name": "train.sync", "start": t0 + 1, "end": t0 + 2,
             "span": "s", "parent": "r", "attrs": attrs}]}

    # the run's log: `first`, `warm`, the window's calls, the traced one
    log = [entry(10.0 * i, share) for i, share in enumerate(
        [walked[0]] * 2 + walked + [walked[0]])]
    monkeypatch.setattr(train, "call_log", lambda: log)
    host = {"calls": [{"wall_s": 5.0}] * len(walked),
            "attempted": len(log)}
    got = reader.read(host, None)
    assert got == want if want is None else got == pytest.approx(want)
    assert reader.read({"calls": [], "attempted": 0}, None) is None


MUTATIONS = {
    # name -> (changes to the reference's model, its keyword arguments,
    #          a change to the parameters it is given)
    "window mask off": ({"sliding_window_size": 10 ** 9}, {}, None),
    "rotary off on a window layer": (
        {"rope_layout": [0, 0, 1, 1] + [0, 1, 1, 1]}, {}, None),
    "top-3 -> top-2": ({"moe_num_active_primary_experts": 2}, {}, None),
    "one held expert dropped": ({}, {}, lambda x: x[:, :-1]),
    "router fed the raw residual": ({}, {"router_input": "residual"}, None),
}


@pytest.mark.parametrize("name", list(MUTATIONS))
def test_mutation_is_told_apart(program, name):
    """A reference with one term changed must fail
    `test_decoder_matches_reference` by its tolerances: by ten times
    LOGIT_ATOL on the logits, and on the loss."""
    changes, kwargs, cut = MUTATIONS[name]
    _, params, tokens, model = _setup(HELD["all"])
    if cut is not None:
        params = dict(params, layers=dict(params["layers"], **{
            k: cut(params["layers"][k])
            for k in ("w_gate", "w_up", "w_down")}))
    loss, logits, _, _ = program["all"]
    model = dict(model, **changes)
    with jax.default_matmul_precision("highest"):
        ref_loss, ref_logits = jax.jit(lambda p: _reference(
            p, tokens, model, **kwargs))(params)
    assert float(jnp.abs(logits - ref_logits).max()) > 10 * LOGIT_ATOL
    assert abs(loss - float(ref_loss)) > LOSS_RTOL * float(ref_loss)


def _dense(q, k, v, window):
    group = q.shape[2] // k.shape[2]
    k, v = jnp.repeat(k, group, axis=2), jnp.repeat(v, group, axis=2)
    s = jnp.einsum("bqhd,bkhd->bhqk", q, k) / np.sqrt(q.shape[-1])
    i, j = jnp.arange(q.shape[1])[:, None], jnp.arange(q.shape[1])[None, :]
    mask = i >= j
    if window is not None:
        mask &= i - j < window
    s = jnp.where(mask, s, -jnp.inf)
    return jnp.einsum("bhqk,bkhd->bqhd", jax.nn.softmax(s, axis=-1), v)


# name -> (T, window, query heads, key/value heads, the forward's
# (block_q, block_k), the backward's as `_bwd_tiles` returns them — None:
# what the file chooses, all of T at these sizes — dtype, tolerance).
# Every case has a random cotangent and a scale other than 1.
ATTENTION_CASES = {
    "T-above-window": (64, 16, 4, 2, (16, 16), None, jnp.float32, 1e-5),
    "window-no-multiple-of-the-block": (256, 48, 6, 2, (32, 32), None,
                                        jnp.float32, 1e-5),
    "T-below-window": (64, 100, 4, 2, (16, 16), None, jnp.float32, 1e-5),
    "grouped-heads-alone": (64, None, 4, 2, (16, 16), None, jnp.float32,
                            1e-5),
    # dk / dv of a key/value head summed over its two query heads in
    # each of four key blocks, under the causal mask alone (the loop's
    # `first` bound with nothing taken off its end)
    "grouped-heads-four-key-blocks": (256, None, 4, 2, (32, 32), (64, 64),
                                      jnp.float32, 1e-5),
    "window-alone": (64, 16, 4, 4, (16, 16), None, jnp.float32, 1e-5),
    "unaligned-T-dense-fallback": (40, 16, 4, 2, (16, 16), None,
                                   jnp.float32, 1e-5),
    # a window (50) that is no multiple of either tile and ends inside a
    # key block: the query loop's `last` bound (one block too few loses
    # the window's oldest keys' dk / dv, one too many only costs time),
    # the tile's second mask, and the lse of rows whose first key block
    # in the forward is wholly masked (row 127 starts at key 78, its
    # block's loop at key 32)
    "window-ends-inside-a-key-block": (256, 50, 4, 2, (32, 32), (32, 64),
                                       jnp.float32, 1e-5),
    "window-ends-inside-a-query-block": (256, 50, 4, 2, (32, 32), (64, 32),
                                         jnp.float32, 1e-5),
    # SmallThinker's 28 | 4: a dk / dv that forgets a query head of the
    # group, counts one twice, or reads another group's, shows
    "group-of-7": (64, 24, 28, 4, (16, 32), (16, 32), jnp.float32, 2e-5),
    "group-of-7-no-window": (64, None, 28, 4, (16, 32), (32, 16),
                             jnp.float32, 2e-5),
    # the backward's tile above, below and equal to the forward's (its
    # 256 x 512 shape, at a sixteenth): the lse is written in rows of
    # the forward's block_q and read in rows of the backward's
    "bwd-tile-above-fwd": (128, 40, 4, 2, (16, 32), (32, 64), jnp.float32,
                           1e-5),
    "bwd-tile-below-fwd": (128, 40, 4, 2, (16, 32), (8, 16), jnp.float32,
                           1e-5),
    "bwd-tile-equal-fwd": (128, 40, 4, 2, (16, 32), (16, 32), jnp.float32,
                           1e-5),
    # bf16 operands on the MXU (p and ds cast to it), float32 elsewhere
    "bf16": (128, 40, 6, 2, (16, 32), (32, 32), jnp.bfloat16, 6e-2),
    "bf16-no-window": (128, None, 6, 2, (16, 32), (32, 32), jnp.bfloat16,
                       6e-2),
    # a window that masks nothing, over several blocks: the loop's end
    # clipped to the sequence's
    "window-at-least-T": (128, 128, 4, 2, (32, 32), (32, 32), jnp.float32,
                          1e-5),
    "window-beyond-T": (128, 1000, 4, 2, (32, 32), (16, 64), jnp.float32,
                        1e-5),
    # a window on the two-axis grid (no group to sum over)
    "window-equal-heads": (128, 40, 4, 4, (32, 32), (32, 16), jnp.float32,
                           1e-5),
    "window-of-one-block": (128, 32, 4, 4, (32, 32), (32, 32), jnp.float32,
                            1e-5),
}


@pytest.mark.parametrize("case", ATTENTION_CASES)
def test_window_and_grouped_attention(case, monkeypatch):
    """`flash_attention` with `window` and grouped heads against dense
    masked attention, forward and backward (the one kernel
    `flash_bwd_fused`, at the case's tile). In float32 the difference is
    the blockwise softmax's order of sums (measured 6e-7 forward, 1.5e-6
    on a gradient); the bf16 cases' reference reads the same bf16 inputs
    in float32."""
    from ray_tpu.ops import attention

    t, window, heads, kv_heads, block, tiles, dtype, tol = (
        ATTENTION_CASES[case])
    if tiles is not None:
        monkeypatch.setattr(attention, "_bwd_tiles", lambda *_: tiles)
    keys = jax.random.split(jax.random.key(0), 4)
    q = jax.random.normal(keys[0], (2, t, heads, 16)).astype(dtype)
    k = jax.random.normal(keys[1], (2, t, kv_heads, 16)).astype(dtype)
    v = jax.random.normal(keys[2], (2, t, kv_heads, 16)).astype(dtype)
    w = jax.random.normal(keys[3], (2, t, heads, 16))
    f32 = [x.astype(jnp.float32) for x in (q, k, v)]

    def ours(q, k, v):
        return flash_attention(q, k, v, True, None, *block, window).astype(
            jnp.float32)

    assert float(jnp.abs(jax.jit(ours)(q, k, v)
                         - _dense(*f32, window)).max()) <= tol / 2
    got = jax.jit(jax.grad(lambda *a: (ours(*a) * w).sum(),
                           (0, 1, 2)))(q, k, v)
    want = jax.grad(lambda *a: (_dense(*a, window) * w).sum(),
                    (0, 1, 2))(*f32)
    for name, a, b in zip(("dq", "dk", "dv"), got, want):
        assert a.dtype == dtype
        assert float(jnp.abs(a.astype(jnp.float32) - b).max()) <= tol, name


@pytest.mark.parametrize("window, kv_heads", [(48, 2), (None, 2), (48, 4)])
def test_window_and_grouped_gradient_is_two_kernels(window, kv_heads):
    """Under a gradient a window / grouped-head call is the forward
    kernel (writing the row log-sum-exp) and ONE backward kernel: no
    scan of dense blocks, no third kernel."""
    q = jax.ShapeDtypeStruct((2, 128, 4, 16), jnp.bfloat16)
    kv = jax.ShapeDtypeStruct((2, 128, kv_heads, 16), jnp.bfloat16)
    text = str(jax.make_jaxpr(jax.value_and_grad(
        lambda q, k, v: flash_attention(q, k, v, True, None, 32, 64,
                                        window).astype(jnp.float32).sum(),
        (0, 1, 2)))(q, kv, kv))
    assert text.count("pallas_call[") == 2
    assert "name=flash_fwd" in text and "name=flash_bwd_fused" in text
    assert "scan[" not in text and "dynamic_update_slice" not in text


# sha256 of the jaxpr of value_and_grad(flash_attention) at GPT-tiny's
# shapes: the forward kernel as it was before `window` and grouped heads
# existed (commit 7f398b5), writing the row log-sum-exp beside its
# output since PR 32, and that PR's backward kernel. Change it only with
# a change MEANT to alter the kernels the GPT-2 cells run. PR 60 was
# one: the forward's K loop became runs (the whole block with no mask,
# the diagonal's straight-line after it); both texts recorded again,
# the backward kernel's unchanged.
PLAIN_JAXPR = "2fcaab48d8700ea87606d75f0d0e1083d03fb1a053459e7f852512d9ee63b09d"
# ... and with the two `name` equations `_fwd` gives the kernel's output
# and log-sum-exp since PR 40 (`attention.SAVED_ACROSS_REMAT`), which is
# the text a trace has now; PLAIN_JAXPR is that text without them
NAMED_JAXPR = "7d371dde2c1ad8c5e5a8a1b42222062adcb187942f7b70502bffb73ae4af0bb1"


def _plain_jaxpr(*extra):
    qkv = jax.ShapeDtypeStruct((8, 128, 4, 16), jnp.bfloat16)
    return str(jax.make_jaxpr(jax.value_and_grad(
        lambda q, k, v: flash_attention(q, k, v, True, *extra).astype(
            jnp.float32).sum(), (0, 1, 2)))(qkv, qkv, qkv))


def test_window_none_is_the_program_the_gpt_cells_ran(monkeypatch):
    """With `window=None` and equal head counts the traced program,
    forward and backward kernel, is the recorded one, text for text,
    however the later arguments are spelled: the kernels' text is the
    one recorded before the residuals had names, once the names are
    taken out."""
    text = _plain_jaxpr()
    assert hashlib.sha256(text.encode()).hexdigest() == NAMED_JAXPR
    assert _plain_jaxpr(None, 128, 128, None) == text
    monkeypatch.setattr(attention, "checkpoint_name", lambda x, name: x)
    unnamed = _plain_jaxpr()
    assert hashlib.sha256(unnamed.encode()).hexdigest() == PLAIN_JAXPR


def test_the_names_are_all_that_the_recorded_text_gained(monkeypatch):
    """The two texts part in two `name` equations (and the letters of
    the variables after them), nothing else: the same primitives in the
    same order."""
    def primitives(text):
        return re.findall(r" = (\w+)[\[ ]", text)

    named = _plain_jaxpr()
    monkeypatch.setattr(attention, "checkpoint_name", lambda x, name: x)
    unnamed = _plain_jaxpr()
    assert [p for p in primitives(named) if p != "name"] == primitives(
        unnamed)
    assert primitives(named).count("name") == 2
    assert sorted(re.findall(r"name\[name=(\w+)\]", named)) == sorted(
        attention.SAVED_ACROSS_REMAT)


TINY_SHARE = dataclasses.replace(decoder.TINY, held=(0, 4))


def _operator_cls():
    import optax

    from ray_tpu.train import TrainingOperator

    class TinyDecoderOperator(TrainingOperator):
        def setup(self, config):
            cfg = TINY_SHARE
            tokens = jax.random.randint(jax.random.key(1), (2, 64), 0,
                                        cfg.vocab_size)
            self.register(
                model_init=lambda key: (decoder.init(key, cfg),
                                        decoder.counters_init(cfg)),
                loss_fn=lambda p, s, b: decoder.stateful_loss(p, s, b, cfg),
                optimizer=optax.adamw(3e-4), stateful=True)
            self.register_data(train_loader=[tokens] * 3)

    return TinyDecoderOperator


def test_epoch_counters_reach_the_sync_span_without_a_sync():
    """The operator zeroes the state's `epoch_counters` when an epoch
    starts, the step adds to them on the device, and `train_epoch` reads
    them once, after the losses."""
    cfg = TINY_SHARE
    op = _operator_cls()({}, 0, 1)
    for steps in (3, 2):        # the second epoch starts from zero again
        c = op.train_epoch(num_steps=steps)["counters"]
        assert c["moe_steps"] == steps
        assert c["moe_assignments"] == steps * cfg.n_layers * 128 * cfg.top_k
        assert 0 < c["moe_assignments_held"] < c["moe_assignments"]
        assert c["moe_assignments_dropped"] == 0
        assert (c["moe_experts_held"], c["moe_experts_total"]) == (4, 8)
        assert c["moe_expert_tokens_mean"] == pytest.approx(
            c["moe_assignments_held"] / (steps * cfg.n_layers * 4))
        assert c["moe_expert_tokens_max"] >= c["moe_expert_tokens_mean"]


def test_counters_land_on_the_calls_span_tree(ray_start_shared):
    """Through `Trainer.train()`: the `moe_*` counters are attributes of
    the worker's `train.sync` span in `call_log()`, and the state pull
    carries the counters' state like any other leaf."""
    from ray_tpu.train import Trainer, call_log

    tr = Trainer(_operator_cls(), num_workers=1)
    try:
        out = tr.train(num_steps=2)
        sync = next(s for s in call_log()[-1]["spans"]
                    if s["name"] == "train.sync")
        assert sync["attrs"] == out["counters"]
        assert sync["attrs"]["moe_steps"] == 2
        assert sync["attrs"]["moe_assignments"] == 2 * 4 * 128 * 3
        assert sync["attrs"]["moe_assignments_dropped"] == 0
        assert (sync["attrs"]["moe_experts_held"],
                sync["attrs"]["moe_experts_total"]) == (4, 8)
        state = tr.state_dict()["model_state"]["epoch_counters"]
        assert int(state["moe_steps"]) == 2
    finally:
        tr.shutdown(force=True)
