"""Plain reference of the ``sdar`` family: the block-diffusion training
pass — noise, forward pass, loss — in ``jax.numpy``, float32, matmul
precision "highest". No kernel, no sort, no scan, no remat: a Python
loop over the layers, the attention mask a dense boolean matrix built
from its three rules, a loop over the held experts with a dense mask of
who chose them, and its OWN copy of the noise recipe. Nothing is
imported from ``ray_tpu``.

Written from the published configuration of SDAR-30B-A3B-Chat
(``config.json``, ``model_type`` ``sdar_moe``), the description of its
family (SDAR, arXiv:2510.06303) and the training pass it takes from
BD3-LM (arXiv:2503.09573). With x_0 ``[L]`` one sequence of data tokens,
b the block length, MASK the slice's last id, s the step:

    k      = fold_in(key(noise_seed), s);  k0, k1 = split(k)
    t      = uniform(k0, [B, L / b])                one rate a block
    p      = (1 - 1e-3) t + 1e-3                    repeated over the block's b tokens
    masked = uniform(k1, [B, L]) < p
    x_t    = where(masked, MASK, x_0)
    rows   = [x_0 ; x_t]                            2 L rows; row r stands at position r mod L

    h  = E[rows]                                    [2 L, 2048]
    x  = RMSNorm1(h)                                eps 1e-6, weight only
    q,k,v = x W_q, x W_k, x W_v                     32 / 4 / 4 heads of 128; no bias
    q,k = RMSNorm_head(q), RMSNorm_head(k)          over each head's 128, weights q_norm / k_norm
    q,k = RoPE(q), RoPE(k)                          theta 1e6, rotate-half, positions r mod L
    a  = softmax(q k^T / sqrt(128) + mask) v        query head g uses key/value head g // 8
         mask(i, j), i and j rows, pos = r mod L, blk = pos // b:
           clean  i, clean  j:  blk(j) <= blk(i)
           noised i, clean  j:  blk(j) <  blk(i)
           noised i, noised j:  blk(j) == blk(i)
           clean  i, noised j:  never
    h1 = h + a W_o
    y  = RMSNorm2(h1)
    r  = y W_r                       in float32, [2 L, 128]   the router reads the MLP's input
    S  = top8(softmax(r));  w = softmax(r)[S] / sum over S    float32; = softmax over the chosen logits
    m  = sum over e in S AND e in Held of  w_e * W_down,e ( silu(W_gate,e y) * (W_up,e y) )
    h' = h1 + m
    logits = RMSNorm_f(h_L[L:]) W_head^T            the NOISED half only
    loss = sum over masked i of  CE(logits[i], x_0[i]) / p_i  /  (B L)        no shift

Departures and choices, each listed under ``assumed`` in the
configuration file: the block length (4), the linear schedule with its
1 / p weights and 1e-3 floor, one rate a block, no shift, the mask id,
the ``[clean ; noised]`` order, repeated positions, the head norms
before the rotary turn, no auxiliary loss; ``Held`` = the experts the
configuration holds (experts 0..15 of 128, rank 0 of the eight chips
that share a layer) — what the others would add is left out here as in
the program, and that partial result goes on to the next layer; the
vocabulary is the slice the configuration holds (ids, logits and loss
over it). The parameter tree is the program's (block leaves stacked
along a leading layer axis, the held experts along the next, the head
``[vocabulary, 2048]``).

It computes in blocks so that it fits beside the training state on the
chip: one sequence at a time, one key/value head (8 query heads) and
``QUERY_BLOCK`` query rows at a time (an ``[8, 1024, 8192]`` float32
score block is 0.27 GB), the logits of a sequence's noised half at once
(4096 x 18 992 float32: 0.31 GB).

``mutate`` names ONE departure from the above, for the tests that show
the comparison tells it apart (``MUTATIONS``)."""

from __future__ import annotations

import math

import jax
import jax.numpy as jnp

QUERY_BLOCK = 1024
NOISE_FLOOR = 1e-3

MUTATIONS = (
    "noised_sees_own_clean_block",   # noised i, clean j: blk(j) <= blk(i)
    "clean_sees_noised",             # clean i sees its block's noised keys
    "causal_inside_block",           # pos(j) <= pos(i) inside a block too
    "loss_over_unmasked",            # every position weighs 1 / p
    "weights_dropped",               # masked positions weigh 1
    "shifted",                       # row i scored against token i + 1
    "positions_not_repeated",        # row r stands at position r
    "clean_half_counted",            # the clean rows' logits scored too
)


def noise(tokens, noise_seed, noise_step, block: int, mask_id: int):
    """The noise of step `noise_step` on tokens [B, L] -> (x_t [B, L],
    masked [B, L] bool, p [B, L])."""
    b, length = tokens.shape
    k0, k1 = jax.random.split(jax.random.fold_in(
        jax.random.key(noise_seed), noise_step))
    t = jax.random.uniform(k0, (b, length // block), jnp.float32)
    p = jnp.repeat((1 - NOISE_FLOOR) * t + NOISE_FLOOR, block, axis=1)
    masked = jax.random.uniform(k1, (b, length), jnp.float32) < p
    return jnp.where(masked, mask_id, tokens), masked, p


def _rmsnorm(x, w, eps):
    return x / jnp.sqrt((x * x).mean(-1, keepdims=True) + eps) * w


def _rope(x, positions, theta):
    """x: [R, H, hd]; rotate-half: dimension i pairs with i + hd / 2."""
    half = x.shape[-1] // 2
    inv = theta ** (-jnp.arange(half, dtype=jnp.float32) / half)
    angle = positions.astype(jnp.float32)[:, None] * inv[None, :]
    cos = jnp.cos(angle)[:, None, :].astype(x.dtype)
    sin = jnp.sin(angle)[:, None, :].astype(x.dtype)
    a, b = x[..., :half], x[..., half:]
    return jnp.concatenate([a * cos - b * sin, b * cos + a * sin], axis=-1)


def mask_rows(lo: int, n: int, length: int, block: int, mutate: str = ""):
    """[n, 2 L] booleans: which of the 2 L key rows the query rows
    [lo, lo + n) see, from the three rules."""
    i = (lo + jnp.arange(n))[:, None]
    j = jnp.arange(2 * length)[None, :]
    noised_i, noised_j = i >= length, j >= length
    pos_i, pos_j = i % length, j % length
    blk_i, blk_j = pos_i // block, pos_j // block
    clean_clean = blk_j <= blk_i
    noised_clean = blk_j < blk_i
    noised_noised = blk_j == blk_i
    clean_noised = jnp.zeros_like(clean_clean)
    if mutate == "noised_sees_own_clean_block":
        noised_clean = blk_j <= blk_i
    if mutate == "clean_sees_noised":
        clean_noised = blk_j == blk_i
    if mutate == "causal_inside_block":
        clean_clean = pos_j <= pos_i
        noised_noised = noised_noised & (pos_j <= pos_i)
    return jnp.where(
        noised_i, jnp.where(noised_j, noised_noised, noised_clean),
        jnp.where(noised_j, clean_noised, clean_clean))


def _attention(q, k, v, length: int, block: int, mutate: str = ""):
    """q: [R, H, hd]; k, v: [R, H_kv, hd]; R = 2 L rows; query head g
    reads key/value head g // (H // H_kv); one key/value head and one
    block of query rows at a time under the dense mask."""
    rows, h, hd = q.shape
    group = h // k.shape[1]
    step = min(QUERY_BLOCK, rows)

    def part(g, lo):
        mask = mask_rows(lo, step, length, block, mutate)
        s = jnp.einsum("qhd,kd->hqk", q[lo:lo + step,
                                        g * group:(g + 1) * group],
                       k[:, g]) / math.sqrt(hd)
        s = jnp.where(mask[None], s.astype(jnp.float32), -jnp.inf)
        return jnp.einsum("hqk,kd->qhd",
                          jax.nn.softmax(s, axis=-1).astype(q.dtype), v[:, g])

    return jnp.concatenate([
        jnp.concatenate([part(g, lo) for lo in range(0, rows, step)], axis=0)
        for g in range(k.shape[1])], axis=1)                  # [R, H, hd]


def routed(y, r, p, *, first: int, k_active: int):
    """The routed experts' part of a layer. y: [R, D] (RMSNorm2's
    output), r: [R, 128] router logits over ALL experts, float32; p
    holds the held experts' `w_gate`, `w_up`, `w_down`, expert e of them
    being expert `first + e` of the router."""
    probs = jax.nn.softmax(r.astype(jnp.float32), axis=-1)
    top, chosen = jax.lax.top_k(probs, k_active)               # [R, 8]
    weight = (top / top.sum(-1, keepdims=True)).astype(y.dtype)
    m = jnp.zeros_like(y)
    for e in range(p["w_gate"].shape[0]):
        w_e = (weight * (chosen == first + e)).sum(-1)         # 0 if not chosen
        m = m + w_e[:, None] * (
            (jax.nn.silu(y @ p["w_gate"][e]) * (y @ p["w_up"][e]))
            @ p["w_down"][e])
    return m


def layer(h, p, positions, model: dict, mutate: str = "",
          first: int | None = None):
    """One block on one sequence's 2 L rows. h: [R, D]; p: the layer's
    leaves. Returns (h', the layer's routed part m). `first`: the first
    held expert, where it is not the configuration's (the share test)."""
    hd, eps = model["head_dim"], model["rms_norm_eps"]
    n_q, n_kv = model["num_attention_heads"], model["num_key_value_heads"]
    rows = h.shape[0]
    x = _rmsnorm(h, p["norm1"], eps)
    q = (x @ p["wq"]).reshape(rows, n_q, hd)
    k = (x @ p["wk"]).reshape(rows, n_kv, hd)
    v = (x @ p["wv"]).reshape(rows, n_kv, hd)
    q, k = _rmsnorm(q, p["q_norm"], eps), _rmsnorm(k, p["k_norm"], eps)
    q = _rope(q, positions, model["rope_theta"])
    k = _rope(k, positions, model["rope_theta"])
    a = _attention(q, k, v, rows // 2, model["block_length"], mutate)
    h1 = h + a.reshape(rows, n_q * hd) @ p["wo"]
    y = _rmsnorm(h1, p["norm2"], eps)
    # the configuration's `router_dtype` (float32); a control lowers it
    router = getattr(jnp, model.get("router_dtype", "float32"))
    m = routed(y, y.astype(router) @ p["router"].astype(router), p,
               first=model["held_experts_first"] if first is None else first,
               k_active=model["num_experts_per_tok"])
    return h1 + m, m


def forward(params, clean, noised, model: dict, mutate: str = ""):
    """ONE sequence: clean x_0 [L] and noised x_t [L] -> logits of the
    noised half [L, vocabulary slice] (of all 2 L rows under the
    mutation that scores the clean half too)."""
    length = clean.shape[0]
    rows = jnp.concatenate([clean, noised])
    positions = jnp.arange(2 * length) % length
    if mutate == "positions_not_repeated":
        positions = jnp.arange(2 * length)
    h = params["embed"][rows]
    layers = params["layers"]
    for l in range(layers["wq"].shape[0]):
        h, _ = layer(h, {name: leaf[l] for name, leaf in layers.items()},
                     positions, model, mutate)
    if mutate != "clean_half_counted":
        h = h[length:]
    return _rmsnorm(h, params["norm_f"], model["rms_norm_eps"]) \
        @ params["head"].T


def weighted_nll(params, clean, noised, masked, p, model, mutate: str = ""):
    """One sequence's sum over masked i of CE(logits[i], x_0[i]) / p_i,
    the loss's softmax and sums in float32 whatever the blocks' dtype."""
    logp = jax.nn.log_softmax(
        forward(params, clean, noised, model, mutate).astype(jnp.float32),
        axis=-1)
    weight = jnp.where(masked, 1.0 / p, 0.0)
    targets = clean
    if mutate == "loss_over_unmasked":
        weight = 1.0 / p
    if mutate == "weights_dropped":
        weight = masked.astype(jnp.float32)
    if mutate == "shifted":
        logp, weight, targets = logp[:-1], weight[1:], clean[1:]
    if mutate == "clean_half_counted":
        weight, targets = jnp.tile(weight, 2), jnp.tile(clean, 2)
    nll = -jnp.take_along_axis(logp, targets[:, None], axis=-1)[:, 0]
    return (nll * weight).sum()


def loss_of(params, batch, model: dict, noise_seed, noise_step,
            mutate: str = ""):
    """The batch's loss at one step's noise, differentiable in `params`:
    one sequence at a time."""
    noised, masked, p = noise(batch, noise_seed, noise_step,
                              model["block_length"], model["vocab_size"] - 1)
    total = sum(weighted_nll(params, batch[i], noised[i], masked[i], p[i],
                             model, mutate) for i in range(batch.shape[0]))
    return total / batch.size


def loss(init, batch, model: dict, dtype=jnp.float32) -> float:
    """The step-0 loss of the whole batch, one sequence at a time, at
    the noise the program's first step draws. `init` is what the
    family's `model_init` returns: (parameters, the model state, whose
    `noise_seed` and `noise_step` are read). `dtype` other than float32
    is for showing that a lower precision is told apart: the BLOCKS in
    it (weights, activations, head norms, router weights, the
    attention's probabilities), the loss's softmax and sums in float32;
    the router's product too where `model["router_dtype"]` says so."""
    params = jax.tree.map(lambda x: x.astype(dtype), init[0])
    seed, step = init[1]["noise_seed"], init[1]["noise_step"]
    noised, masked, p = noise(batch, seed, step, model["block_length"],
                              model["vocab_size"] - 1)
    fn = jax.jit(lambda w, *row: weighted_nll(w, *row, model))
    total = 0.0
    with jax.default_matmul_precision(
            "highest" if dtype == jnp.float32 else "default"):
        for i in range(batch.shape[0]):
            total += float(fn(params, batch[i], noised[i], masked[i], p[i]))
    return total / batch.size
