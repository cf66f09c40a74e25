"""Plain reference of the ``ouro`` family: a LOOPED language model's
training pass — forward pass, exit gate, loss — in ``jax.numpy``,
float32, matmul precision "highest". No kernel, no scan, no remat:
Python loops over the walks and over the layers, the attention mask a
dense causal matrix. Nothing is imported from ``ray_tpu``.

Written from the published configuration of Ouro-2.6B (``config.json``,
``model_type`` ``ouro``) and the description of its family ("Scaling
Latent Reasoning via Looped Language Models", arXiv:2510.25741). With x
``[L]`` one sequence of tokens, N layers, T = ``total_ut_steps`` walks:

    h = E[x]                                        [L, 2048]
    for t = 1 .. T:                                 the SAME N layers and final norm every walk
      for every layer:
        a  = Attn(RMSNorm1(h))                      eps 1e-6, weight only
        h  = h + RMSNorm1'(a)                       the sandwich norm: AFTER attention, before the sum
        m  = W_down (silu(W_gate y) * (W_up y)),  y = RMSNorm2(h)      width 5632
        h  = h + RMSNorm2'(m)                       the second sandwich norm
      h = RMSNorm_f(h)                              after EVERY walk; fed on, and read by head and gate
      x_t = h
    Attn: q,k,v = y W_q, y W_k, y W_v               16 / 16 heads of 128; no bias, no q/k norm
          q,k = RoPE(q), RoPE(k)                    theta 1e6, rotate-half, positions 0 .. L - 1
          softmax(q k^T / sqrt(128) + causal) v W_o

    l_t[i]   = -log softmax(x_t[i] W_head)[x[i + 1]]        i < L - 1: the last position unscored
    lam_t[i] = sigmoid(w_g . x_t[i] + b_g)                  float32; ONE gate for all walks
    S_0 = 1,  S_t = prod over j <= t of (1 - lam_j)
    p(t) = lam_t S_(t-1)  for t < T,   p(T) = S_(T-1)       the last walk takes what is left
    loss = 1 / (B (L - 1))  sum over i of [ sum over t of p_i(t) l_t[i]  -  beta H(p_i) ]
    H(p) = -sum over t of p(t) log p(t)

Departures and choices, each listed under ``assumed`` in the
configuration file: the sandwich norms and their order, the final norm
after every walk with its output fed on, nothing re-injected between
walks, the gate's form and input, the loss a token with beta 0.1
(``exit_beta``: the paper's first-stage objective, a uniform prior over
the walks), rotate-half. ``early_exit_threshold`` is inference's and
read by nothing. The parameter tree is the program's (block leaves
stacked along a leading layer axis; ``w1`` the gate projection, ``w3``
the up projection, ``w2`` the down projection; the head ``[2048,
vocabulary]``; ``exit_gate`` = ``{"w": [2048], "b": []}``).

It computes in blocks so that it fits beside the training state on the
chip: one sequence at a time, ``QUERY_BLOCK`` query rows of all heads
at a time (a ``[16, 1024, 4096]`` float32 score block is 0.27 GB), the
logits ``ROW_BLOCK`` rows at a time (1024 x 49 152 float32: 0.2 GB).

``mutate`` names ONE departure from the above, for the tests that show
the comparison tells it apart (``MUTATIONS``)."""

from __future__ import annotations

import functools
import math

import jax
import jax.numpy as jnp

QUERY_BLOCK = 1024
ROW_BLOCK = 1024

MUTATIONS = (
    "one_walk_fewer",            # T - 1 walks
    "no_norm_between_walks",     # the un-normed state is fed on
    "no_sandwich_norms",         # h + a, h + m
    "last_walk_gated",           # p(T) = lam_T S_(T-1): mass is lost
    "survival_off_by_one",       # p(t) = lam_t S_t, p(T) = S_T
    "entropy_sign",              # + beta H
    "beta_zero",                 # no entropy term
    "gate_ignored",              # p uniform over the walks
    "gate_reads_unnormed",       # lam from the state before the final norm
)


def _rmsnorm(x, w, eps):
    return x / jnp.sqrt((x * x).mean(-1, keepdims=True) + eps) * w


def _rope(x, theta):
    """x: [L, H, hd] at positions 0 .. L - 1; rotate-half: dimension i
    pairs with i + hd / 2."""
    half = x.shape[-1] // 2
    inv = theta ** (-jnp.arange(half, dtype=jnp.float32) / half)
    angle = jnp.arange(x.shape[0], dtype=jnp.float32)[:, None] * inv[None, :]
    cos = jnp.cos(angle)[:, None, :].astype(x.dtype)
    sin = jnp.sin(angle)[:, None, :].astype(x.dtype)
    a, b = x[..., :half], x[..., half:]
    return jnp.concatenate([a * cos - b * sin, b * cos + a * sin], axis=-1)


def _attention(q, k, v):
    """Causal attention, q, k, v: [L, H, hd] (as many key/value heads as
    query heads), a block of query rows at a time under the dense mask;
    the scores and their softmax in float32."""
    rows, _, hd = q.shape
    step = min(QUERY_BLOCK, rows)
    out = []
    for lo in range(0, rows, step):
        mask = (jnp.arange(rows)[None, :]
                <= (lo + jnp.arange(step))[:, None])
        s = jnp.einsum("qhd,khd->hqk", q[lo:lo + step], k) / math.sqrt(hd)
        s = jnp.where(mask[None], s.astype(jnp.float32), -jnp.inf)
        out.append(jnp.einsum(
            "hqk,khd->qhd", jax.nn.softmax(s, axis=-1).astype(q.dtype), v))
    return jnp.concatenate(out, axis=0)


@functools.partial(jax.jit, static_argnames=(
    "heads", "head_dim", "eps", "theta", "sandwich"))
def layer(h, p, *, heads: int, head_dim: int, eps: float, theta: float,
          sandwich: bool = True):
    """One block on one sequence. h: [L, D]; p: the layer's leaves."""
    rows = h.shape[0]
    x = _rmsnorm(h, p["norm1"], eps)
    q = _rope((x @ p["wq"]).reshape(rows, heads, head_dim), theta)
    k = _rope((x @ p["wk"]).reshape(rows, heads, head_dim), theta)
    v = (x @ p["wv"]).reshape(rows, heads, head_dim)
    a = _attention(q, k, v).reshape(rows, heads * head_dim) @ p["wo"]
    h = h + (_rmsnorm(a, p["norm1_post"], eps) if sandwich else a)
    y = _rmsnorm(h, p["norm2"], eps)
    m = (jax.nn.silu(y @ p["w1"]) * (y @ p["w3"])) @ p["w2"]
    return h + (_rmsnorm(m, p["norm2_post"], eps) if sandwich else m)


def walks(params, tokens, model: dict, mutate: str = ""):
    """ONE sequence of tokens [L] -> (every walk's normed output, every
    walk's state before the final norm): two lists of T arrays [L, D].
    `params["layers"]` may be a LIST of T trees of equal values, one a
    walk: the gradient with respect to tree t is then walk t's part of
    the gradient, and the T parts add up to the whole (the tests split
    the sum over the walks with it)."""
    if model["num_key_value_heads"] != model["num_attention_heads"]:
        raise ValueError("the ouro reference: as many key/value heads as "
                         "query heads")
    steps = model["total_ut_steps"] - (mutate == "one_walk_fewer")
    h = params["embed"][tokens]
    normed, raw = [], []
    for t in range(steps):
        layers = params["layers"]
        if isinstance(layers, (list, tuple)):   # a tree a walk: see above
            layers = layers[t]
        for l in range(layers["wq"].shape[0]):
            h = layer(h, {name: leaf[l] for name, leaf in layers.items()},
                      heads=model["num_attention_heads"],
                      head_dim=model["head_dim"], eps=model["rms_norm_eps"],
                      theta=float(model["rope_theta"]),
                      sandwich=mutate != "no_sandwich_norms")
        raw.append(h)
        normed.append(_rmsnorm(h, params["norm_f"], model["rms_norm_eps"]))
        if mutate != "no_norm_between_walks":
            h = normed[-1]
    return normed, raw


def exit_distribution(lam, mutate: str = ""):
    """lam [T, n], every walk's gate -> p [T, n]."""
    steps = lam.shape[0]
    if mutate == "gate_ignored":
        return jnp.full_like(lam, 1.0 / steps)
    survival = [jnp.ones_like(lam[0])]                     # S_0 .. S_T
    for t in range(steps):
        survival.append(survival[-1] * (1.0 - lam[t]))
    if mutate == "survival_off_by_one":
        return jnp.stack([lam[t] * survival[t + 1] for t in range(steps - 1)]
                         + [survival[steps]])
    last = survival[steps - 1]
    if mutate == "last_walk_gated":
        last = lam[steps - 1] * last
    return jnp.stack([lam[t] * survival[t] for t in range(steps - 1)]
                     + [last])


@jax.jit
def _nll_rows(x, head, targets):
    """-log softmax(x head)[targets], the softmax in float32. x: [n, D]."""
    logp = jax.nn.log_softmax((x @ head).astype(jnp.float32), axis=-1)
    return -jnp.take_along_axis(logp, targets[:, None], axis=-1)[:, 0]


def nll(x, head, targets):
    """Row i of x [n, D] against targets[i] -> [n], `ROW_BLOCK` rows of
    logits at a time."""
    return jnp.concatenate([
        _nll_rows(x[lo:lo + ROW_BLOCK], head, targets[lo:lo + ROW_BLOCK])
        for lo in range(0, x.shape[0], ROW_BLOCK)])


def sequence_terms(params, tokens, model: dict, mutate: str = ""):
    """One sequence -> (l [T, L - 1], p [T, L - 1], H [L - 1]): every
    walk's cross-entropy, the exit distribution and its entropy at the
    L - 1 positions that have a target. The gate is float32 whatever
    the blocks' dtype."""
    normed, raw = walks(params, tokens, model, mutate)
    gate = params["exit_gate"]
    read = raw if mutate == "gate_reads_unnormed" else normed
    lam = jnp.stack([jax.nn.sigmoid(
        x[:-1].astype(jnp.float32) @ gate["w"].astype(jnp.float32)
        + gate["b"].astype(jnp.float32)) for x in read])
    p = exit_distribution(lam, mutate)
    each = jnp.stack([nll(x[:-1], params["head"], tokens[1:])
                      for x in normed])
    entropy = -jnp.where(p > 0, p * jnp.log(jnp.where(p > 0, p, 1.0)),
                         0.0).sum(0)
    return each, p, entropy


def walk_logits(params, tokens, model: dict, mutate: str = ""):
    """One sequence [L] -> every walk's float32 logits [T, L, vocab]
    (tests and small sizes)."""
    normed, _ = walks(params, tokens, model, mutate)
    return jnp.stack([(x @ params["head"]).astype(jnp.float32)
                      for x in normed])


def loss_of(params, batch, model: dict, mutate: str = ""):
    """The batch's loss, differentiable in `params`: one sequence at a
    time."""
    beta = {"beta_zero": 0.0, "entropy_sign": -model["exit_beta"]}.get(
        mutate, model["exit_beta"])
    total = 0.0
    for tokens in batch:
        each, p, entropy = sequence_terms(params, tokens, model, mutate)
        total = total + (p * each).sum() - beta * entropy.sum()
    return total / (batch.shape[0] * (batch.shape[1] - 1))


def loss(init, batch, model: dict, dtype=jnp.float32,
         mutate: str = "") -> float:
    """The step-0 loss of the whole batch, one sequence at a time.
    `init` is what the family's `model_init` returns: (parameters, the
    model state, which is not read). `dtype` other than float32 is for
    showing that a lower precision is told apart: the BLOCKS in it
    (weights, activations, norms, the attention's probabilities, the
    head's product), the loss's softmax and sums and the gate in
    float32."""
    params = jax.tree.map(lambda x: x.astype(dtype), init[0])
    with jax.default_matmul_precision(
            "highest" if dtype == jnp.float32 else "default"):
        return float(loss_of(params, batch, model, mutate))
