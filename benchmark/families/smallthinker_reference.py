"""Plain reference of the ``smallthinker`` family: forward pass and
next-token cross-entropy in ``jax.numpy``, float32, matmul precision
"highest". No kernel, no sort, no scan, no remat: a Python loop over the
layers, a dense ``[T, T]`` mask, a loop over the held experts with a
dense mask of who chose them. Nothing is imported from ``ray_tpu``.

Written from the published configuration of SmallThinker-21BA3B-Instruct
(``config.json``) and the description of its family. With h the residual
stream ``[B, T, 2560]``, layer index l, period 4 (``sliding_window_layout``
= ``rope_layout`` = ``[0, 1, 1, 1]`` repeated):

    x  = RMSNorm1(h)                                  eps 1e-6, weight only
    r  = x W_r                     in float32, [B,T,64]   router reads the attention's input
    q,k,v = x W_q, x W_k, x W_v    28 / 4 / 4 heads of 128; no bias
    if rope_layout[l]:            q,k = RoPE(q), RoPE(k)   theta 1.5e6, rotate-half, no scaling
    if sliding_window_layout[l]:  mask = causal AND (i - j < 4096)
    else:                         mask = causal             (layer 0 of four: no positions at all)
    a  = softmax(q k^T / sqrt(128) + mask) v          query head g uses key/value head g // 7
    h1 = h + a W_o
    y  = RMSNorm2(h1)
    S  = top6(r) over all 64;  p = softmax(r[S])      float32; = softmax over 64 renormalised over S
    m  = sum over e in S AND e in Held of  p_e * W_down,e ( relu(W_gate,e y) * (W_up,e y) )
    h' = h1 + m
    logits = RMSNorm_f(h_L) W_head ;  loss = mean next-token cross-entropy over the slice

Departures and choices, each listed under ``assumed`` in the
configuration file: ``Held`` = the experts the configuration holds
(experts 0..15 of 64, rank 0 of the four chips that share a layer) —
what the others would add is left out here as in the program, and that
partial result goes on to the next layer; the vocabulary is the slice
the configuration holds (ids, logits and loss over it); gated ReLU;
the router reads RMSNorm1's output; no bias anywhere; rotate-half
pairing; no auxiliary balancing loss; the secondary experts the family's
description mentions have no key in the config and are not built. The
parameter tree is the program's (block leaves stacked along a leading
layer axis, the held experts along the next).

It computes in blocks so that it fits beside the training state on the
chip: one sequence at a time, one key/value group (7 query heads) and
``QUERY_BLOCK`` queries at a time (a ``[7, 2048, 8192]`` float32 score
block is 0.47 GB), the logits one sequence at a time (1.24 GB)."""

from __future__ import annotations

import math

import jax
import jax.numpy as jnp

QUERY_BLOCK = 2048


def _rmsnorm(x, w, eps):
    return x / jnp.sqrt((x * x).mean(-1, keepdims=True) + eps) * w


def _rope(x, theta):
    """x: [T, H, hd]; rotate-half: dimension i pairs with i + hd / 2."""
    t, _, hd = x.shape
    half = hd // 2
    inv = theta ** (-jnp.arange(half, dtype=jnp.float32) / half)
    angle = jnp.arange(t, dtype=jnp.float32)[:, None] * inv[None, :]
    cos = jnp.cos(angle)[:, None, :].astype(x.dtype)
    sin = jnp.sin(angle)[:, None, :].astype(x.dtype)
    a, b = x[..., :half], x[..., half:]
    return jnp.concatenate([a * cos - b * sin, b * cos + a * sin], axis=-1)


def _attention_block(q, k, v, lo, window):
    """One key/value head's queries [lo, lo + len(q)) against all T keys
    under a dense mask. q: [Tq, group, hd]; k, v: [T, hd]."""
    i = (lo + jnp.arange(q.shape[0]))[:, None]
    j = jnp.arange(k.shape[0])[None, :]
    mask = i >= j
    if window is not None:
        mask = mask & (i - j < window)
    s = jnp.einsum("qhd,kd->hqk", q, k) / math.sqrt(q.shape[-1])
    s = jnp.where(mask[None], s, -jnp.inf)
    return jnp.einsum("hqk,kd->qhd", jax.nn.softmax(s, axis=-1), v)


def _attention(q, k, v, window):
    """q: [T, H, hd]; k, v: [T, H_kv, hd]; query head g reads key/value
    head g // (H // H_kv); one key/value head and one block of queries
    at a time."""
    t, h, _ = q.shape
    group = h // k.shape[1]
    return jnp.concatenate([
        jnp.concatenate([
            _attention_block(
                q[lo:lo + QUERY_BLOCK, g * group:(g + 1) * group],
                k[:, g], v[:, g], lo, window)
            for lo in range(0, t, QUERY_BLOCK)], axis=0)
        for g in range(k.shape[1])], axis=1)                  # [T, H, hd]


def routed(y, r, p, *, first: int, k_active: int):
    """The routed experts' part of a layer. y: [T, D] (RMSNorm2's
    output), r: [T, 64] router logits over ALL experts; p holds the held
    experts' `w_gate`, `w_up`, `w_down`, expert e of them being expert
    `first + e` of the router."""
    top, chosen = jax.lax.top_k(r, k_active)                   # [T, 6]
    weight = jax.nn.softmax(top, axis=-1)
    m = jnp.zeros_like(y)
    for e in range(p["w_gate"].shape[0]):
        p_e = (weight * (chosen == first + e)).sum(-1)         # 0 if not chosen
        m = m + p_e[:, None] * (
            (jax.nn.relu(y @ p["w_gate"][e]) * (y @ p["w_up"][e]))
            @ p["w_down"][e])
    return m


def layer(h, p, *, windowed: bool, rotary: bool, model: dict,
          router_input: str = "norm"):
    """One block on one sequence. h: [T, D]; p: the layer's leaves.
    Returns (h', the layer's routed part m). `router_input="residual"`
    is the alternative the configuration did not take (the tests show
    the comparison tells it apart)."""
    hd, eps = model["head_dim"], model["rms_norm_eps"]
    n_q, n_kv = model["num_attention_heads"], model["num_key_value_heads"]
    t = h.shape[0]
    x = _rmsnorm(h, p["norm1"], eps)
    r = (x if router_input == "norm" else h) @ p["router"]     # [T, 64]
    q = (x @ p["wq"]).reshape(t, n_q, hd)
    k = (x @ p["wk"]).reshape(t, n_kv, hd)
    v = (x @ p["wv"]).reshape(t, n_kv, hd)
    if rotary:
        q, k = _rope(q, model["rope_theta"]), _rope(k, model["rope_theta"])
    a = _attention(q, k, v,
                   model["sliding_window_size"] if windowed else None)
    h1 = h + a.reshape(t, n_q * hd) @ p["wo"]
    m = routed(_rmsnorm(h1, p["norm2"], eps), r, p,
               first=model["held_experts_first"],
               k_active=model["moe_num_active_primary_experts"])
    return h1 + m, m


def logits(params, tokens, model, router_input: str = "norm"):
    """Logits [T, vocabulary slice] of ONE sequence. tokens: [T]."""
    h = params["embed"][tokens]
    layers = params["layers"]
    for l in range(layers["wq"].shape[0]):
        p = {name: leaf[l] for name, leaf in layers.items()}
        h, _ = layer(h, p, windowed=bool(model["sliding_window_layout"][l]),
                     rotary=bool(model["rope_layout"][l]), model=model,
                     router_input=router_input)
    return _rmsnorm(h, params["norm_f"], model["rms_norm_eps"]) \
        @ params["head"]


def nll_sum(params, tokens, model, router_input: str = "norm"):
    """Summed next-token loss of ONE sequence. tokens: [T]."""
    logp = jax.nn.log_softmax(
        logits(params, tokens, model, router_input)[:-1], axis=-1)
    return -jnp.take_along_axis(logp, tokens[1:, None], axis=-1).sum()


def loss(init, batch, model: dict, dtype=jnp.float32) -> float:
    """Mean next-token loss of the whole batch, one sequence at a time.
    `init` is what the family's `model_init` returns: (parameters, the
    counters' state); only the parameters are read. `dtype` other than
    float32 is for showing that a lower precision is told apart."""
    params = jax.tree.map(lambda x: x.astype(dtype), init[0])
    rows, t = batch.shape
    fn = jax.jit(lambda p, tok: nll_sum(p, tok, model))
    total = 0.0
    with jax.default_matmul_precision(
            "highest" if dtype == jnp.float32 else "default"):
        for i in range(rows):
            total += float(fn(params, batch[i]))
    return total / (rows * (t - 1))
