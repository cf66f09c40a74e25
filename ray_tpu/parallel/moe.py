"""Expert layers, two of them (capability absent from the reference,
SURVEY §2.4).

1. Switch-style top-1 MoE with all_to_all dispatch over the `ep` mesh
   axis (`moe_apply`). Dense-dispatch formulation (einsum with one-hot
   dispatch/combine masks): no gathers/scatters with dynamic shapes, so
   everything tiles onto the MXU and the only cross-device traffic is
   two all_to_alls on [experts, capacity, model] buffers riding ICI.
   Pads every expert to a capacity and drops what does not fit.
2. Dropless top-k experts over a HELD share of the experts
   (`dropless_moe`, second half of the file): one chip's part of an
   expert-parallel layer, run without its exchange. Two routing rules
   (`ROUTING`): the softmax over the chosen k logits, and sigmoid
   scores with a selection bias that moves the choice and not the
   weights (`route_sigmoid_bias`; the bias is model state, moved by
   `balance_bias`), either times a `scale`; experts gated under ReLU or
   SiLU, or ungated (`w_gate` None: two matrices an expert, say under
   the squared ReLU) (`ACTIVATIONS`). A shared expert is not this
   layer's: the decoder adds it as a plain MLP, outside the grouped
   matmul's rows. The rows are LAID OUT for the worst case, every
   assignment held here (`static_rows`), filled from the front, and
   WALKED as far as a layer's routing filled them: the block runs on
   the smallest rung of `row_ladder` that holds the filled tiles,
   chosen on the device a layer and step (`_expert_block`), the worst
   case its top rung.
"""

from __future__ import annotations

import functools
from typing import NamedTuple

import jax
import jax.numpy as jnp
from jax.sharding import Mesh, PartitionSpec as P


def top1_routing(router_logits, capacity: int):
    """router_logits: [N, E]. Returns (dispatch [N,E,C], combine [N,E,C],
    aux_loss scalar)."""
    n, e = router_logits.shape
    probs = jax.nn.softmax(router_logits, axis=-1)
    expert_idx = jnp.argmax(probs, axis=-1)  # [N]
    expert_mask = jax.nn.one_hot(expert_idx, e, dtype=probs.dtype)  # [N,E]
    # load-balancing auxiliary loss (Switch Transformer eq. 4)
    density = expert_mask.mean(0)
    density_proxy = probs.mean(0)
    aux_loss = (density * density_proxy).sum() * e
    # position of each token within its expert's capacity buffer
    position = (jnp.cumsum(expert_mask, axis=0) - 1.0) * expert_mask  # [N,E]
    keep = (position < capacity).astype(probs.dtype) * expert_mask
    pos_onehot = jax.nn.one_hot(position.sum(-1).astype(jnp.int32), capacity,
                                dtype=probs.dtype)  # [N,C]
    dispatch = keep[:, :, None] * pos_onehot[:, None, :]  # [N,E,C]
    gate = (probs * expert_mask).sum(-1)  # [N]
    combine = dispatch * gate[:, None, None]
    return dispatch, combine, aux_loss


def _moe_local(x, router_w, w_in, w_out, *, axis_name: str,
               capacity_factor: float):
    """Inside shard_map over ep. x: [N_local, D] local tokens; router_w:
    [D, E_total]; w_in/w_out: this shard's experts [E_local, D, F] /
    [E_local, F, D]."""
    ep = jax.lax.axis_size(axis_name)
    n_local, d = x.shape
    e_local = w_in.shape[0]
    e_total = e_local * ep
    capacity = max(1, int(capacity_factor * n_local / e_total))

    logits = x @ router_w  # [N_local, E_total]
    dispatch, combine, aux = top1_routing(logits, capacity)

    # [N,E,C] x [N,D] -> [E_total, C, D] -> group by owner shard
    expert_in = jnp.einsum("nec,nd->ecd", dispatch, x)
    expert_in = expert_in.reshape(ep, e_local, capacity, d)
    # all_to_all: shard i sends block j to shard j; receives [ep, e_local,C,D]
    expert_in = jax.lax.all_to_all(expert_in, axis_name, split_axis=0,
                                   concat_axis=0, tiled=False)
    # -> [ep(sources), e_local, C, D]; fold sources into capacity
    expert_in = expert_in.transpose(1, 0, 2, 3).reshape(
        e_local, ep * capacity, d)

    h = jax.nn.gelu(jnp.einsum("ecd,edf->ecf", expert_in, w_in))
    y = jnp.einsum("ecf,efd->ecd", h, w_out)  # [e_local, ep*C, D]

    y = y.reshape(e_local, ep, capacity, d).transpose(1, 0, 2, 3)
    y = jax.lax.all_to_all(y, axis_name, split_axis=0, concat_axis=0,
                           tiled=False)  # back: [ep, e_local, C, D]
    y = y.reshape(e_total, capacity, d)
    out = jnp.einsum("nec,ecd->nd", combine, y)
    return out.astype(x.dtype), aux[None]


def moe_apply(x, router_w, w_in, w_out, *, mesh: Mesh,
              capacity_factor: float = 1.25, axis_name: str = "ep",
              token_axis: str = "dp"):
    """Driver-level entry. x: [N, D] tokens (sharded over dp); w_in/w_out:
    [E, D, F] / [E, F, D] sharded over ep on the expert axis."""
    fn = jax.shard_map(
        functools.partial(_moe_local, axis_name=axis_name,
                          capacity_factor=capacity_factor),
        mesh=mesh,
        in_specs=(P(token_axis, None), P(), P(axis_name), P(axis_name)),
        out_specs=(P(token_axis, None), P(token_axis)),
        check_vma=False,
    )
    out, aux = fn(x, router_w, w_in, w_out)
    return out, jnp.mean(aux)


# ----------------------------------------------------------------------
# Dropless top-k routing over a HELD share of the experts
# ----------------------------------------------------------------------
#
# The Switch path above pads every expert to a capacity and drops what
# does not fit; its [N, E, C] masks grow with tokens x experts x
# capacity. The layer below drops nothing and is told which experts it
# holds (`held = (first, count)`, the chip's share of an expert-parallel
# deployment): it routes over ALL `n_experts`, groups the token-expert
# assignments that fall on its own experts by expert (a counting sort),
# multiplies each group by its expert (`ops/moe_gmm.py`) and combines by
# routing weight. What the absent experts would add is left out: on one
# chip the layer runs without its exchange, and nothing stands in for
# the other chips. The gathers, the activation and the kernels' outputs
# all have one row an assignment SLOT: sized for the worst case they
# cost four to twenty times what the filled rows need, so the block
# picks its row count from a short ladder by what was filled.

GMM_TILE = 512     # rows a tile of the grouped matmul holds


class Grouping(NamedTuple):
    """Where every assignment of a step sits among the R = `static_rows`
    rows the layout has. Assignment a = token * k + slot. The rows that
    hold one are packed at the FRONT, expert by expert in whole tiles:
    the first `n_tiles` tiles are all of the work, and the expert block
    walks a prefix of the three [R]-sized arrays that covers them
    (`row_ladder`)."""

    row_of: jax.Array         # [N, k] its row; meaningless unless `held`
    held: jax.Array           # [N, k] bool: it falls on an expert held here
    assign_of_row: jax.Array  # [R] the assignment in a row
    row_valid: jax.Array      # [R] bool: the row holds one
    tile_group: jax.Array     # [R // tile] the expert of each row tile
    n_tiles: jax.Array        # [1] the tiles that hold rows, all in front
    expert_tokens: jax.Array  # [count] assignments each held expert got


def route_topk(router_logits, top_k: int):
    """router_logits: [N, E] -> (experts [N, k] int32, weights [N, k]
    float32): the k largest logits and their softmax — the softmax over
    all E renormalised over the chosen k."""
    top, idx = jax.lax.top_k(router_logits.astype(jnp.float32), top_k)
    return idx, jax.nn.softmax(top, axis=-1)


ROUTING_EPS = 1e-6    # beside the chosen scores' sum


def route_sigmoid_bias(router_logits, bias, top_k: int):
    """router_logits: [N, E], bias: [E] float32 -> (experts [N, k],
    weights [N, k] float32, moved): s = sigmoid(logits) over all E; the
    experts are the k largest of s + bias; their weights are the
    UNBIASED s of the chosen over (their sum + 1e-6) (`dropless_moe`
    multiplies them by its `scale`). The bias takes
    part in the choice only (arXiv:2408.15664): no gradient reaches it.
    `moved` counts the assignments the bias changed: experts among the k
    largest of s + bias that are not among the k largest of s."""
    s = jax.nn.sigmoid(router_logits.astype(jnp.float32))
    _, idx = jax.lax.top_k(s + bias, top_k)
    _, plain = jax.lax.top_k(s, top_k)
    moved = (idx[:, :, None] != plain[:, None, :]).all(-1).sum()
    chosen = jnp.take_along_axis(s, idx, axis=-1)
    return idx, chosen / (chosen.sum(-1, keepdims=True) + ROUTING_EPS), moved


def balance_bias(bias, routed, rate: float):
    """The loss-free balancing rule, once a step after the loss: an
    expert that got fewer assignments than the mean is raised by
    `rate`, one that got more is lowered. bias: [..., E]; routed:
    [..., E], the assignments each of ALL E experts got from this
    chip's tokens (a deployment sums them over the chips that share the
    batch before this)."""
    routed = routed.astype(jnp.float32)
    return bias + rate * jnp.sign(
        routed.mean(-1, keepdims=True) - routed)


ROUTING = ("softmax_topk", "sigmoid_bias")
ACTIVATIONS = {"relu": jax.nn.relu, "silu": jax.nn.silu,
               "relu2": lambda x: jnp.square(jax.nn.relu(x))}


def static_rows(assignments: int, count: int, tile: int = GMM_TILE) -> int:
    """The rows the grouped matmul's layout is ALLOCATED for, a step of
    `assignments` = tokens x top_k over `count` held experts: the worst
    case, every assignment held here, in whole tiles, and a tile more an
    expert for its padding. With a sixteenth of the experts held about
    a sixteenth of them is filled, and the block walks a rung of
    `row_ladder` near that, not these."""
    return -(-assignments // tile) * tile + count * tile


def row_ladder(assignments: int, count: int,
               tile: int = GMM_TILE) -> tuple[int, ...]:
    """The row counts the expert block can walk, from the shapes alone:
    1/8, 2/8, 3/8 and 4/8 of the worst case's tiles, rounded up to
    whole tiles, and the worst case (`static_rows`) as the top rung;
    ascending, duplicates dropped, so a small call has fewer."""
    worst = static_rows(assignments, count, tile) // tile
    return tuple(tile * t for t in sorted(
        {-(-worst * eighths // 8) for eighths in (1, 2, 3, 4)} | {worst}))


def group_by_expert(expert_idx, held: tuple[int, int],
                    tile: int = GMM_TILE) -> Grouping:
    """Lay the assignments that fall on experts [first, first + count)
    out by expert, every expert's run padded to whole tiles (at least
    one, so every expert's weight gradient is written). A counting sort:
    an assignment's rank in its expert is a running count, no sort. The
    static row count is the worst case, all N * k assignments held here;
    the tiles really filled are counted in `n_tiles`."""
    first, count = held
    n, k = expert_idx.shape
    a = n * k
    local = expert_idx.reshape(a) - first
    is_held = (local >= 0) & (local < count)
    onehot = (local[:, None] == jnp.arange(count)[None, :])       # [A, count]
    running = jnp.cumsum(onehot.astype(jnp.int32), axis=0)
    sizes = running[-1]                                           # [count]
    rank = jnp.take_along_axis(
        running, jnp.clip(local, 0, count - 1)[:, None], axis=1)[:, 0] - 1
    tiles = jnp.maximum(1, -(-sizes // tile))
    tile_end = jnp.cumsum(tiles)
    row_start = (tile_end - tiles) * tile
    rows = static_rows(a, count, tile)
    row_of = jnp.where(is_held,
                       row_start[jnp.clip(local, 0, count - 1)] + rank, rows)
    assign_of_row = jnp.full((rows,), a, jnp.int32).at[row_of].set(
        jnp.arange(a, dtype=jnp.int32), mode="drop")
    tile_group = jnp.minimum(
        jnp.searchsorted(tile_end, jnp.arange(rows // tile), side="right"),
        count - 1).astype(jnp.int32)
    return Grouping(
        row_of=jnp.where(is_held, row_of, 0).reshape(n, k),
        held=is_held.reshape(n, k), assign_of_row=assign_of_row,
        row_valid=assign_of_row < a, tile_group=tile_group,
        n_tiles=tile_end[-1:].astype(jnp.int32), expert_tokens=sizes)


@jax.custom_vjp
def _dispatch(y, g: Grouping):
    """[N, D] tokens -> [R, D] rows: row r holds the token of its
    assignment, zeros where it holds none. Gathers both ways: the
    backward sums, per token, the rows of its held assignments."""
    k = g.row_of.shape[1]
    tok = jnp.minimum(g.assign_of_row // k, y.shape[0] - 1)
    return jnp.where(g.row_valid[:, None], y[tok], 0)


def _dispatch_fwd(y, g):
    return _dispatch(y, g), g


def _dispatch_bwd(g, dx):
    dy = 0
    for slot in range(g.row_of.shape[1]):
        dy = dy + jnp.where(g.held[:, slot, None], dx[g.row_of[:, slot]], 0)
    return dy, None


_dispatch.defvjp(_dispatch_fwd, _dispatch_bwd)


@jax.custom_vjp
def _combine(rows, weights, g: Grouping):
    """[R, D] expert outputs, [N, k] routing weights -> [N, D]: every
    token's held assignments, weighted, summed in float32."""
    out = 0
    for slot in range(g.row_of.shape[1]):
        out = out + jnp.where(
            g.held[:, slot, None],
            weights[:, slot, None] * rows[g.row_of[:, slot]], 0)
    return out.astype(rows.dtype)


def _combine_fwd(rows, weights, g):
    return _combine(rows, weights, g), (rows, weights, g)


def _combine_bwd(res, dout):
    rows, weights, g = res
    n, k = weights.shape
    a = jnp.minimum(g.assign_of_row, n * k - 1)
    w_row = weights.reshape(n * k)[a]
    drows = jnp.where(g.row_valid[:, None],
                      w_row[:, None] * dout[a // k], 0).astype(rows.dtype)
    dw = jnp.stack([
        jnp.where(g.held[:, slot],
                  (rows[g.row_of[:, slot]].astype(jnp.float32)
                   * dout.astype(jnp.float32)).sum(-1), 0)
        for slot in range(k)], axis=1)
    return drows, dw.astype(weights.dtype), None


_combine.defvjp(_combine_fwd, _combine_bwd)


def _walk(rows: int, y, weights, g: Grouping, w_in, w_down, tile: int,
          activation: str, gated: bool):
    """The expert block on the first `rows` rows of the layout (whole
    tiles, at least `g.n_tiles` of them: the filled rows are packed at
    the front): dispatch, the grouped products around the activation,
    combine. `w_in`: gate and up side by side, [count, D, 2F], or
    ungated the up weights alone, [count, F, D]. -> [N, D]."""
    from ray_tpu.ops.moe_gmm import moe_gmm

    g = g._replace(assign_of_row=g.assign_of_row[:rows],
                   row_valid=g.row_valid[:rows],
                   tile_group=g.tile_group[:rows // tile])
    act_fn = ACTIVATIONS[activation]
    with jax.named_scope("experts"):
        x = _dispatch(y, g)
        if gated:
            f = w_in.shape[-1] // 2
            gate_up = moe_gmm(x, w_in, g.tile_group, g.n_tiles, tile)
            act = act_fn(gate_up[:, :f]) * gate_up[:, f:]
        else:
            act = act_fn(moe_gmm(x, w_in, g.tile_group, g.n_tiles, tile,
                                 True))
        return _combine(moe_gmm(act, w_down, g.tile_group, g.n_tiles, tile),
                        weights, g)


# A rung's forward and its gradient, each under a `jax.jit` of its own:
# nothing of the compiled step changes (XLA inlines the calls), but the
# layers of a stack that call the block at one shape share ONE trace
# and one lowering of each rung's kernels, where five rungs a layer
# would otherwise be traced layer by layer.
_rung = jax.jit(_walk, static_argnums=(0, 6, 7, 8))


@functools.partial(jax.jit, static_argnums=(0, 7, 8, 9))
def _rung_bwd(rows: int, dout, y, weights, g: Grouping, w_in, w_down,
              tile: int, activation: str, gated: bool):
    return jax.vjp(
        lambda y, weights, w_in, w_down: _walk(
            rows, y, weights, g, w_in, w_down, tile, activation, gated),
        y, weights, w_in, w_down)[1](dout)


def _on_rung(fn, rung, ladder: tuple[int, ...], *operands, **static):
    """One conditional, a branch a rung: `fn(rows, *operands)`."""
    return jax.lax.switch(
        rung, [functools.partial(fn, rows, **static) for rows in ladder],
        *operands)


@functools.partial(jax.custom_vjp, nondiff_argnums=(6, 7, 8, 9))
def _expert_block(y, weights, g: Grouping, rung, w_in, w_down, tile: int,
                  activation: str, gated: bool, ladder: tuple[int, ...]):
    """`_walk` over `ladder[rung]` rows, the rung a device scalar. The
    residuals are the block's INPUTS and the backward takes the gradient
    of the same rung's `_walk` inside its own branch, so no rung's
    intermediates leave the conditional (differentiated straight
    through, every rung's residuals would be outputs of the forward,
    zeros for the rungs not taken), and a `jax.checkpoint` around the
    block has nothing to recompute: its second forward is dead code."""
    return _on_rung(_rung, rung, ladder, y, weights, g, w_in, w_down,
                    tile=tile, activation=activation, gated=gated)


def _expert_block_fwd(y, weights, g, rung, w_in, w_down, tile, activation,
                      gated, ladder):
    return (_expert_block(y, weights, g, rung, w_in, w_down, tile,
                          activation, gated, ladder),
            (y, weights, g, rung, w_in, w_down))


def _expert_block_bwd(tile, activation, gated, ladder, res, dout):
    y, weights, g, rung, w_in, w_down = res
    # the barrier keeps what follows (a layer scan's write of each
    # gradient into its stack) out of the branches: moved into them, a
    # branch's output is the whole stack
    dy, dweights, dw_in, dw_down = jax.lax.optimization_barrier(_on_rung(
        _rung_bwd, rung, ladder, dout, y, weights, g, w_in, w_down,
        tile=tile, activation=activation, gated=gated))
    return dy, dweights, None, None, dw_in, dw_down


_expert_block.defvjp(_expert_block_fwd, _expert_block_bwd)


def dropless_moe(y, router_logits, w_gate, w_up, w_down, *, top_k: int,
                 held: tuple[int, int], tile: int = GMM_TILE,
                 activation: str = "relu", bias=None, scale: float = 1.0):
    """Top-k experts over a held share, no token dropped.

    y: [N, D] tokens (compute dtype); router_logits: [N, n_experts], the
    router's output over ALL experts, float32; w_gate, w_up: [count, D,
    F] and w_down: [count, F, D], the held experts' weights. Returns
    (out [N, D]: sum over the chosen AND held experts e of
    p_e * W_down,e (act(W_gate,e y) * (W_up,e y)), counts) — with
    `w_gate` None the experts are UNGATED, p_e * W_down,e act(W_up,e y):
    one grouped product in and one out, nothing standing in for the
    gate, and `w_up` is [count, F, D] as `w_down` is: an expert's rows
    are its hidden units both ways, so the model width is every stored
    weight's minor dimension whatever F is (the device keeps a leaf
    whose minor dimension is no multiple of its 128 lanes while the one
    before it is — F = 1856 — transposed anyway, the step then copies
    the stack and its moments back and forth and the host gets a
    strided view; PERF.md section 6, PR 39); `counts`
    holds `expert_tokens` [count] (assignments each held expert got),
    `assignments` (N * k), `held` (those on held experts), `dropped`
    (held assignments that found no row: 0, by construction, and
    counted from the layout rather than assumed) and `rows_walked` (the
    rows the block ran over).

    The layout has `static_rows` rows, the worst case; the block —
    dispatch, the grouped products, the activation, combine, and their
    gradients — runs on the first `rows` of them, the smallest rung of
    `row_ladder` whose tiles hold the `n_tiles` this call's routing
    filled, picked by `lax.switch` on the device. With every
    assignment held the top rung runs, which is the whole layout; the
    values are the worst case's bit for bit on every rung.

    `activation`: a key of `ACTIVATIONS`. `bias` None: `route_topk`;
    `bias` [n_experts] float32: `route_sigmoid_bias`, and `counts` also
    holds `routed` [n_experts] (assignments each of ALL experts got,
    what `balance_bias` reads) and `bias_moved`. `scale`: a factor on
    the routing weights after their normalisation (a model's
    `routed_scaling_factor`; at 1 nothing is traced for it)."""
    extra = {}
    if bias is None:
        idx, weights = route_topk(router_logits, top_k)
    else:
        idx, weights, moved = route_sigmoid_bias(router_logits, bias, top_k)
        extra = {"routed": (idx[:, :, None] == jnp.arange(
                     router_logits.shape[-1])).sum((0, 1), dtype=jnp.int32),
                 "bias_moved": moved.astype(jnp.int32)}
    if scale != 1:
        weights = weights * scale
    g = group_by_expert(idx, held, tile)
    ladder = row_ladder(idx.size, held[1], tile)
    rungs = jnp.asarray(ladder, jnp.int32)
    # the smallest rung whose tiles hold every filled one
    rung = jnp.searchsorted(rungs // tile, g.n_tiles[0]).astype(jnp.int32)
    with jax.named_scope("experts"):
        # gate and up side by side, once a call and outside the branches:
        # a rung takes the pair as one operand and hands back one gradient
        w_in = w_up if w_gate is None else jnp.concatenate(
            [w_gate, w_up], axis=-1)
    out = _expert_block(y, weights, g, rung, w_in, w_down, tile, activation,
                        w_gate is not None, ladder)
    n_held = g.held.sum()
    counts = {
        "expert_tokens": g.expert_tokens.astype(jnp.int32),
        "assignments": jnp.asarray(idx.size, jnp.int32),
        "held": n_held.astype(jnp.int32),
        "dropped": (n_held - g.row_valid.sum()).astype(jnp.int32),
        "rows_walked": rungs[rung], **extra}
    return out, counts
