"""Plain reference of the ``gpt`` family: forward pass and next-token
cross-entropy in ``jax.numpy``, float32, matmul precision "highest".
No Pallas kernel, no remat, no scan: a Python loop over the layers.

Written from the GPT-2 equations (Radford et al. 2019; pre-norm block
of Vaswani et al. 2017 with learned positions):

    h0 = wte[tokens] + wpe[:T]
    a  = h + Attn(LN1(h)) ;  h' = a + W_out gelu(W_in LN2(a) + b_in) + b_out
    logits = LN_f(h_L) wte^T ;  loss = mean_t -log softmax(logits_t)[tok_{t+1}]

Departures, all the program's own: the attention projections carry no
bias (the parameter tree has none), and the tree stacks each block
parameter along a leading layer axis."""

from __future__ import annotations

import math

import jax
import jax.numpy as jnp


def _layernorm(x, w, b, eps):
    mean = x.mean(-1, keepdims=True)
    var = ((x - mean) ** 2).mean(-1, keepdims=True)
    return (x - mean) / jnp.sqrt(var + eps) * w + b


def _gelu_new(x):
    return 0.5 * x * (1.0 + jnp.tanh(
        math.sqrt(2.0 / math.pi) * (x + 0.044715 * x ** 3)))


def _nll_sum(params, tokens, *, n_head: int, eps: float):
    b, t = tokens.shape
    h = params["wte"][tokens] + params["wpe"][:t][None]
    d = h.shape[-1]
    hd = d // n_head
    blocks = params["blocks"]
    causal = jnp.tril(jnp.ones((t, t), bool))
    for i in range(blocks["wqkv"].shape[0]):
        p = {k: v[i] for k, v in blocks.items()}
        y = _layernorm(h, p["ln1_w"], p["ln1_b"], eps)
        q, k, v = jnp.split(y @ p["wqkv"], 3, axis=-1)
        q, k, v = (z.reshape(b, t, n_head, hd) for z in (q, k, v))
        s = jnp.einsum("bqhd,bkhd->bhqk", q, k) / math.sqrt(hd)
        s = jnp.where(causal, s, -jnp.inf)
        a = jnp.einsum("bhqk,bkhd->bqhd", jax.nn.softmax(s, axis=-1), v)
        h = h + a.reshape(b, t, d) @ p["wo"]
        y = _layernorm(h, p["ln2_w"], p["ln2_b"], eps)
        h = h + _gelu_new(y @ p["w_in"] + p["b_in"]) @ p["w_out"] \
            + p["b_out"]
    h = _layernorm(h, params["lnf_w"], params["lnf_b"], eps)
    head = params["wte"].T if "lm_head" not in params else params["lm_head"]
    logits = (h @ head)[:, :-1]
    logp = jax.nn.log_softmax(logits, axis=-1)
    return -jnp.take_along_axis(logp, tokens[:, 1:, None], axis=-1).sum()


def loss(params, batch, model: dict, rows_per_block: int = 4) -> float:
    """Mean next-token loss of the whole batch, computed `rows_per_block`
    sequences at a time so the float32 logits fit beside the training
    state (4 x 1024 x 50257 floats are 0.8 GB)."""
    params = jax.tree.map(lambda x: x.astype(jnp.float32), params)
    rows, t = batch.shape
    fn = jax.jit(_nll_sum, static_argnames=("n_head", "eps"))
    total = 0.0
    with jax.default_matmul_precision("highest"):
        for i in range(0, rows, rows_per_block):
            total += float(fn(params, batch[i:i + rows_per_block],
                              n_head=model["n_head"],
                              eps=model["layer_norm_epsilon"]))
    return total / (rows * (t - 1))
