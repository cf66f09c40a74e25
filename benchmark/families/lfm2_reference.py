"""Plain reference of the ``lfm2`` family: forward pass and next-token
cross-entropy in ``jax.numpy``, float32, matmul precision "highest". No
kernel, no grouping, no sort, no scan, no remat: a Python loop over the
layers, the convolution as shifted products, a dense ``[T, T]`` mask, a
loop over the held experts with a dense mask of who chose them. Nothing
is imported from ``ray_tpu``.

Written from the published configuration of LFM2-8B-A1B (``config.json``,
``model_type`` ``lfm2_moe``) and the model code the config belongs to.
With h the residual stream ``[T, 2048]`` of one sequence, u = RMSNorm(h)
(weight only, eps 1e-5), layer l of ``layer_types``:

    conv layer:
      [B | C | x] = u W_in           2048 -> 3 x 2048, split in that order
      z  = B * x
      c_t = sum_{j=0..2} w_j * z_{t-2+j}     z before position 0 is zero;
                                     depthwise, causal, 3 taps, no bias
      h1 = h + (C * c) W_out
    full_attention layer:
      q,k,v = u W_q, u W_k, u W_v    32 / 8 / 8 heads of 64; no bias
      q,k = RMSNorm_64(q), RMSNorm_64(k)   over each head, a weight of 64
      q,k = RoPE(q), RoPE(k)         AFTER the norm; rotate-half, theta 1e6
      a  = softmax(q k^T / 8 + causal) v     query head g uses k/v head g // 4
      h1 = h + a W_o
    y  = RMSNorm2(h1)
    l < num_dense_layers:  m = W_2 (silu(W_1 y) * (W_3 y))       width 7168
    else:  s = sigmoid(y W_r)        float32, all 32 experts; reads y
           S = top4(s + b)           b: the layer's expert_bias, choice only
           p_e = s_e / (sum_{S} s + 1e-6)   the UNBIASED s; x 1 (scaling)
           m = sum over e in S AND e in Held of
               p_e W_2,e (silu(W_1,e y) * (W_3,e y))              width 1792
    h' = h1 + m
    logits = RMSNorm_f(h_L) E^T      the embedding, tied;  loss = mean
                                     next-token cross-entropy over the slice

after the loss, once a step (``bias_update``; arXiv:2408.15664):
    b_e <- b_e + u sign(mean_e' n_e' - n_e)      n_e: assignments expert
                                     e of all 32 got in that layer, u 1e-3

Departures and choices, each under ``assumed`` in the configuration
file: ``Held`` = experts 0..7 of 32 (rank 0 of four chips) and what the
others would add is left out here as in the program; the vocabulary is
the slice held; the head is tied; the bias rule and its rate. The
parameter tree is the program's: every block leaf stacked over the
layers that have it, in layer order (``conv_*`` over the conv layers,
``wq``.. over the attention layers, ``w1`` ``w3`` ``w2`` over the dense
ones, ``router`` ``w_gate`` ``w_up`` ``w_down`` and the bias over the
expert layers, the held experts along the next axis); the expert
leaves' names map as W_1 = ``w_gate``, W_3 = ``w_up``, W_2 = ``w_down``.

``MUTATIONS`` are alternatives the configuration did NOT take; the
tests show the comparison tells each apart.

It computes one sequence at a time, attention one key/value group (4
query heads) and 2048 queries at a time (``smallthinker_reference``'s
blocks, imported with its RMSNorm and rotary), so that it fits beside
the training state on the chip (a ``[4, 2048, 4096]`` float32 score
block is 0.13 GB, a sequence's logits 0.27 GB)."""

from __future__ import annotations

import jax
import jax.numpy as jnp

# the other decoder reference's plain pieces: RMSNorm (weight only),
# rotate-half rotary, causal grouped-query attention by dense masks in
# blocks of queries (window None)
from benchmark.families.smallthinker_reference import (_attention, _rmsnorm,
                                                       _rope)

ROUTING_EPS = 1e-6

MUTATIONS = (
    "taps reversed", "convolution looks ahead", "C and x swapped",
    "q/k norm after rotary", "bias added to the weights",
    "top-k of s without the bias", "ReLU for SiLU",
    "router fed the mixer's input")


def _delayed(z, by: int):
    """Row t of the result is row t - by of z, zero before position 0
    (after the last one where `by` < 0)."""
    if by == 0:
        return z
    pad = jnp.zeros_like(z[:abs(by)])
    return jnp.concatenate([pad, z[:-by]] if by > 0 else [z[-by:], pad])


def conv_mixer(u, p, mutate: str = ""):
    """The gated short convolution with its projections. u: [T, D]."""
    d = u.shape[-1]
    bcx = u @ p["conv_in"]
    b, c, x = bcx[:, :d], bcx[:, d:2 * d], bcx[:, 2 * d:]
    if mutate == "C and x swapped":
        c, x = x, c
    taps = p["conv_taps"][::-1] if mutate == "taps reversed" \
        else p["conv_taps"]
    k = taps.shape[0]
    ahead = -1 if mutate == "convolution looks ahead" else 1
    z = b * x
    conv = sum(taps[j] * _delayed(z, ahead * (k - 1 - j)) for j in range(k))
    return (c * conv) @ p["conv_out"]


def attention_mixer(u, p, model, mutate: str = ""):
    t = u.shape[0]
    n_q, n_kv = model["num_attention_heads"], model["num_key_value_heads"]
    eps, theta = model["norm_eps"], model["rope_theta"]
    q = (u @ p["wq"]).reshape(t, n_q, -1)
    k = (u @ p["wk"]).reshape(t, n_kv, -1)
    v = (u @ p["wv"]).reshape(t, n_kv, -1)
    if mutate == "q/k norm after rotary":
        q = _rmsnorm(_rope(q, theta), p["q_norm"], eps)
        k = _rmsnorm(_rope(k, theta), p["k_norm"], eps)
    else:
        q = _rope(_rmsnorm(q, p["q_norm"], eps), theta)
        k = _rope(_rmsnorm(k, p["k_norm"], eps), theta)
    return _attention(q, k, v, None).reshape(t, -1) @ p["wo"]


def _gated(y, w1, w3, w2, mutate: str = ""):
    act = jax.nn.relu if mutate == "ReLU for SiLU" else jax.nn.silu
    return (act(y @ w1) * (y @ w3)) @ w2


def routed(y, r, p, bias, *, first: int, k_active: int, mutate: str = ""):
    """The routed experts' part of a layer, and who was chosen. y: [T, D]
    (the MLP's input); r: [T, 32] the router's product over ALL experts;
    bias: [32]; p holds the held experts' weights, expert e of them
    being expert `first + e` of the router. Returns (m [T, D], n [32]:
    the assignments each of all experts got)."""
    s = jax.nn.sigmoid(r)
    choice = s if mutate == "top-k of s without the bias" else s + bias
    _, chosen = jax.lax.top_k(choice, k_active)                # [T, 4]
    picked = jnp.take_along_axis(
        s + bias if mutate == "bias added to the weights" else s,
        chosen, axis=-1)
    weight = picked / (picked.sum(-1, keepdims=True) + ROUTING_EPS)
    m = jnp.zeros_like(y)
    for e in range(p["w_gate"].shape[0]):
        p_e = (weight * (chosen == first + e)).sum(-1)         # 0 if not chosen
        m = m + p_e[:, None] * _gated(
            y, p["w_gate"][e], p["w_up"][e], p["w_down"][e], mutate)
    n = (chosen[:, :, None] == jnp.arange(r.shape[-1])).sum((0, 1))
    return m, n


def kinds(model: dict) -> list[tuple[str, str, dict]]:
    """(mixer, mlp, {leaf group: the layer's row in that group's
    stacks}) of every layer run: the first `num_hidden_layers` entries
    of `layer_types`, the first `num_dense_layers` of them dense."""
    out, seen = [], {"conv": 0, "full_attention": 0, "dense": 0,
                     "experts": 0}
    for l, mixer in enumerate(
            model["layer_types"][:model["num_hidden_layers"]]):
        mlp = "dense" if l < model["num_dense_layers"] else "experts"
        out.append((mixer, mlp, {"layer": l, mixer: seen[mixer],
                                 mlp: seen[mlp]}))
        seen[mixer] += 1
        seen[mlp] += 1
    return out


_GROUP_OF = {
    "norm1": "layer", "norm2": "layer",
    "conv_in": "conv", "conv_taps": "conv", "conv_out": "conv",
    "wq": "full_attention", "wk": "full_attention", "wv": "full_attention",
    "wo": "full_attention", "q_norm": "full_attention",
    "k_norm": "full_attention",
    "w1": "dense", "w3": "dense", "w2": "dense",
    "router": "experts", "w_gate": "experts", "w_up": "experts",
    "w_down": "experts"}


def layer(h, p, bias, *, mixer: str, mlp: str, model: dict,
          mutate: str = ""):
    """One block on one sequence. h: [T, D]; p: the layer's leaves; bias:
    the layer's [32], or None in a dense layer. Returns (h', the MLP's
    part m, n or None)."""
    u = _rmsnorm(h, p["norm1"], model["norm_eps"])
    h1 = h + (conv_mixer(u, p, mutate) if mixer == "conv"
              else attention_mixer(u, p, model, mutate))
    y = _rmsnorm(h1, p["norm2"], model["norm_eps"])
    if mlp == "dense":
        return h1 + (m := _gated(y, p["w1"], p["w3"], p["w2"], mutate)), m, None
    r = (u if mutate == "router fed the mixer's input" else y) @ p["router"]
    m, n = routed(y, r, p, bias, first=model["held_experts_first"],
                  k_active=model["num_experts_per_tok"], mutate=mutate)
    return h1 + m, m, n


def forward(params, bias, tokens, model, mutate: str = ""):
    """ONE sequence. tokens: [T]; bias: [expert layers, 32]. Returns
    (logits [T, vocabulary slice], n [expert layers, 32])."""
    h = params["embed"][tokens]
    counts = []
    for mixer, mlp, row in kinds(model):
        p = {name: leaf[row[_GROUP_OF[name]]]
             for name, leaf in params["layers"].items()
             if _GROUP_OF[name] in row}
        h, _, n = layer(h, p, bias[row["experts"]] if mlp == "experts"
                        else None, mixer=mixer, mlp=mlp, model=model,
                        mutate=mutate)
        counts += [] if n is None else [n]
    out = _rmsnorm(h, params["norm_f"], model["norm_eps"]) \
        @ params["embed"].T
    return out, jnp.stack(counts)


def nll_sum(params, bias, tokens, model, mutate: str = ""):
    """(summed next-token loss of ONE sequence, n). tokens: [T]."""
    out, n = forward(params, bias, tokens, model, mutate)
    logp = jax.nn.log_softmax(out[:-1], axis=-1)
    return -jnp.take_along_axis(logp, tokens[1:, None], axis=-1).sum(), n


def bias_update(bias, n, rate: float):
    """The loss-free balancing rule. bias, n: [expert layers, 32]; n the
    assignments every expert got from the whole batch in that layer."""
    n = n.astype(jnp.float32)
    return bias + rate * jnp.sign(n.mean(-1, keepdims=True) - n)


def loss_and_counts(init, batch, model: dict, dtype=jnp.float32):
    """(mean next-token loss of the whole batch, n [expert layers, 32]
    summed over its sequences), one sequence at a time. `init` is what
    the family's `model_init` returns: (parameters, the model state,
    whose `expert_bias` is read)."""
    params = jax.tree.map(lambda x: x.astype(dtype), init[0])
    bias = init[1]["expert_bias"].astype(dtype)
    rows, t = batch.shape
    fn = jax.jit(lambda p, b, tok: nll_sum(p, b, tok, model))
    total, n = 0.0, 0
    with jax.default_matmul_precision(
            "highest" if dtype == jnp.float32 else "default"):
        for i in range(rows):
            part, n_i = fn(params, bias, batch[i])
            total, n = total + float(part), n + n_i
    return total / (rows * (t - 1)), n


def loss(init, batch, model: dict, dtype=jnp.float32) -> float:
    """Mean next-token loss of the whole batch. `dtype` other than
    float32 is for showing that a lower precision is told apart."""
    return loss_and_counts(init, batch, model, dtype)[0]
