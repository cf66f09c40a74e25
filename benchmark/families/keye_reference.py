"""Plain reference of the ``keye`` family: next-token training of a
decoder whose attention runs over the keys a LEARNED INDEXER selects,
with the indexer's own loss beside the cross-entropy — in ``jax.numpy``,
float32, matmul precision "highest". No kernel, no scan, no remat: a
Python loop over the layers, over strips of query rows and over the
key/value heads, the selection a dense boolean mask, a loop over the
held experts with a dense mask of who chose them. Nothing is imported
from ``ray_tpu``.

Written from the published configuration of Keye-VL-2.0-30B-A3B
(``config.json``: the 30B-A3B sparse decoder's keys and ``sa_config``)
and the lightning indexer and sparse training stage of DeepSeek Sparse
Attention as the DeepSeek-V3.2-Exp / DeepSeek-V3.2 reports state them.
One sequence of T tokens, x = RMSNorm1(h) ``[T, 2048]``, xb =
stop_gradient(x):

    q,k,v = x W_q, x W_k, x W_v                     32 / 4 / 4 heads of 128; no bias
    q,k = RMSNorm_head(q), RMSNorm_head(k)          over each head's 128, eps 1e-6
    q,k = RoPE(q), RoPE(k)                          theta 1e7, rotate-half; three position
                                                    streams by mrope_section [16, 24, 24],
                                                    all three the token's index (no image)
    q_I = xb W_qI                                   16 heads of 64
    k_I = LayerNorm_64(xb W_kI)                     ONE head; weight and bias, eps 1e-6
    q_I,k_I = RoPE(q_I), RoPE(k_I)                  the same rule over all 64 dimensions
    w   = (xb W_w) / sqrt(16) / sqrt(64)            [T, 16]
    I[t, s] = sum_j w[t, j] relu(q_I[t, j] . k_I[s])            s <= t
    S_t = the min(t + 1, 2048) largest I[t, s], s <= t; ties to the LOWER s
    P_h[t, s] = softmax over s in S_t of q[t, h] . k[s, h // 8] / sqrt(128)
    a[t, h] = sum over S_t of P_h[t, s] v[s, h // 8]
    h1 = h + a W_o
    p[t, s] = stop_gradient(mean over h of P_h[t, s])
    KL_t = sum over S_t of p[t, s] (log p[t, s] - log softmax_{S_t}(I[t, .])[s])
    y  = RMSNorm2(h1);  r = y W_r (float32, [T, 128])
    S  = top8(softmax(r));  g = softmax(r)[S] / sum over S
    m  = sum over e in S AND e in Held of g_e W_down,e (silu(W_gate,e y) * (W_up,e y))
    h' = h1 + m
    loss = mean_t CE(RMSNorm_f(h_L)[t] W_head^T, token[t + 1])
           + index_loss_weight * mean over t and layers of KL_t

By the two stop-gradients the indexer's four leaves get the second
term's gradient alone and every other leaf the first's. The threshold of
S_t is ``lax.top_k``'s k-th value; the ties AT it go to the lower index
by a running count (``top_k``'s own rule: tests/test_decoder_keye.py
holds the two together with a planted tie); an exact zero of I counts
as +0.0.

Departures and choices, each under ``assumed`` in the configuration
file: the indexer reads the layer's normed input (V3.2 reads its query
latent; this model has none); LayerNorm with weight and bias on k_I and
the rotary turn over the indexer's whole width; the 16 ** -1/2 and
64 ** -1/2 factors; the mean over t where the report writes a sum;
``index_loss_weight`` 1; ``q_chunk_size`` / ``kv_chunk_size`` are read
as the source's tiling and by nothing here; no vision tower: token ids
alone, so the three position streams are equal. ``Held`` = the experts
the configuration holds (0..15 of 128); what the others would add is
left out here as in the program; the vocabulary is the slice held.

It computes in blocks so that it fits beside the training state at
16 384 tokens: one sequence, ``QUERY_BLOCK`` query rows and one
key/value head (8 query heads) at a time (an ``[8, 256, 16 384]``
float32 score block is 128 MiB, the indexer's ``[16, 256, 16 384]``
256 MiB), the logits ``LOSS_BLOCK`` rows at a time (2048 x 18 992
float32: 148 MiB). A strip of query rows and a block of logits are each
ONE jitted function that takes its first row as a number: ``terms``
walks them from Python, so that nothing larger is ever compiled (the
whole pass under one ``jit`` is 320 strips unrolled; the chip machine's
compiler ran out of the host's 40 GiB on it, PR 65).

``mutate`` names ONE departure from the above, for the tests and the
chip controls that show what the comparison tells apart
(``MUTATIONS``)."""

from __future__ import annotations

import functools
import math

import jax
import jax.numpy as jnp

QUERY_BLOCK = 256
LOSS_BLOCK = 2048

MUTATIONS = (
    "window",            # S_t: the last min(t + 1, topk) keys
    "dense",             # S_t: every causal key
    "no_index_loss",     # the second term dropped
    "no_relu",           # I = sum_j w_j (q_j . k)
    "w_unscaled",        # w = xb W_w
    "per_head",          # attention head h selects by indexer head h % 16
    "target_attached",   # p carries a gradient
    "input_attached",    # the indexer reads x, not stop_gradient(x)
    "no_layernorm",      # k_I = xb W_kI
    "ties_high",         # ties at the threshold go to the HIGHER index
)


def _rmsnorm(x, w, eps):
    return x / jnp.sqrt((x * x).mean(-1, keepdims=True) + eps) * w


def _layernorm(x, w, b, eps):
    mean = x.mean(-1, keepdims=True)
    var = ((x - mean) ** 2).mean(-1, keepdims=True)
    return (x - mean) / jnp.sqrt(var + eps) * w + b


def rope(x, positions, theta, sections=None):
    """x: [T, H, hd]; rotate-half: dimension i pairs with i + hd / 2.
    `positions` [T], or with `sections` (mrope_section) [3, T]: pair i
    turns by the stream its section names — temporal, height, width."""
    half = x.shape[-1] // 2
    inv = theta ** (-jnp.arange(half, dtype=jnp.float32) / half)
    if sections is None:
        angle = positions.astype(jnp.float32)[:, None] * inv[None, :]
    else:
        stream = jnp.repeat(jnp.arange(len(sections)),
                            jnp.asarray(sections), total_repeat_length=half)
        angle = positions.astype(jnp.float32).T[:, stream] * inv[None, :]
    cos = jnp.cos(angle)[:, None, :].astype(x.dtype)
    sin = jnp.sin(angle)[:, None, :].astype(x.dtype)
    a, b = x[..., :half], x[..., half:]
    return jnp.concatenate([a * cos - b * sin, b * cos + a * sin], axis=-1)


def index_parts(x, p, model: dict, mutate: str = ""):
    """(q_I [T, 16, 64], k_I [T, 64], w [T, 16]) of one sequence's
    normed input x [T, D]."""
    sa, t = model["sa_config"], x.shape[0]
    heads, dim = sa["indexer_num_heads"], sa["indexer_head_dim"]
    xb = x if mutate == "input_attached" else jax.lax.stop_gradient(x)
    positions = jnp.arange(t)
    q = (xb @ p["w_index_q"]).reshape(t, heads, dim)
    k = xb @ p["w_index_k"]
    if mutate != "no_layernorm":
        k = _layernorm(k, p["index_k_norm"][0], p["index_k_norm"][1],
                       model["rms_norm_eps"])
    w = xb @ p["w_index_w"]
    if mutate != "w_unscaled":
        w = w / math.sqrt(heads) / math.sqrt(dim)
    return (rope(q, positions, model["rope_theta"]),
            rope(k[:, None], positions, model["rope_theta"])[:, 0], w)


def _rows(x, lo, n: int):
    return jax.lax.dynamic_slice_in_dim(x, lo, n)


def index_scores(q_i, k_i, w, lo, n: int, mutate: str = ""):
    """I of the query rows [lo, lo + n) against every key: [n, T], or
    under `per_head` each indexer head's own [16, n, T]."""
    pre = jnp.einsum("rjd,sd->jrs", _rows(q_i, lo, n), k_i)
    if mutate != "no_relu":
        pre = jax.nn.relu(pre)
    weighted = pre * _rows(w, lo, n).T[:, :, None]
    scores = weighted if mutate == "per_head" else weighted.sum(0)
    return jnp.where(scores == 0, 0.0, scores)          # -0.0 is +0.0


def selection(scores, lo, topk: int, mutate: str = ""):
    """scores [.., n, T] of rows lo.. -> the same shape, bool: S_t."""
    n, t = scores.shape[-2:]
    row = (lo + jnp.arange(n))[:, None]
    col = jnp.arange(t)[None, :]
    causal = col <= row
    if mutate == "dense":
        return jnp.broadcast_to(causal, scores.shape)
    if mutate == "window":
        return jnp.broadcast_to(causal & (row - col < topk), scores.shape)
    k = min(topk, t)
    masked = jnp.where(causal, jax.lax.stop_gradient(scores), -jnp.inf)
    top, _ = jax.lax.top_k(masked, k)
    # the k_t-th largest of a row, k_t = min(t + 1, topk)
    at = jnp.broadcast_to(jnp.minimum(row + 1, k) - 1, top.shape[:-1] + (1,))
    tau = jnp.take_along_axis(top, at, axis=-1)
    above, tied = masked > tau, (masked == tau) & causal
    need = (at + 1) - above.sum(-1, keepdims=True)
    if mutate == "ties_high":
        rank = jnp.cumsum(tied[..., ::-1], axis=-1)[..., ::-1]
    else:
        rank = jnp.cumsum(tied, axis=-1)
    return above | (tied & (rank <= need))


@functools.partial(jax.jit, static_argnames=("n", "topk", "mutate"))
def attention_strip(q, k, v, q_i, k_i, w, lo, *, n: int, topk: int,
                    mutate: str = ""):
    """The query rows [lo, lo + n) of one sequence (`lo` a number, not a
    shape: one compiled function serves every strip): (a [n, H, hd],
    the rows' sum of KL_t, the rows' selection [n, T] bool, or None
    where it is not one set a row)."""
    t, h, hd = q.shape
    group, heads_i = h // k.shape[1], q_i.shape[1]
    scores = index_scores(q_i, k_i, w, lo, n, mutate)
    keep = selection(scores, lo, topk, mutate)
    parts, p_sum = [], 0.0
    for g in range(k.shape[1]):
        s = jnp.einsum("qhd,kd->hqk",
                       _rows(q, lo, n)[:, g * group:(g + 1) * group],
                       k[:, g]) / math.sqrt(hd)
        mask = keep if mutate != "per_head" else keep[
            (g * group + jnp.arange(group)) % heads_i]
        prob = jax.nn.softmax(jnp.where(mask, s, -jnp.inf), axis=-1)
        parts.append(jnp.einsum("hqk,kd->qhd", prob, v[:, g]))
        p_sum = p_sum + prob.sum(0)
    a = jnp.concatenate(parts, axis=1)
    if mutate == "per_head":
        return a, 0.0, None
    if mutate == "no_index_loss":
        return a, 0.0, keep
    p = p_sum / h
    if mutate != "target_attached":
        p = jax.lax.stop_gradient(p)
    log_q = jax.nn.log_softmax(jnp.where(keep, scores, -jnp.inf), axis=-1)
    held = keep & (p > 0)
    return a, jnp.where(
        held, p * (jnp.log(jnp.where(held, p, 1.0))
                   - jnp.where(held, log_q, 0.0)), 0.0).sum(), keep


def attention(q, k, v, q_i, k_i, w, model: dict, mutate: str = ""):
    """One sequence: (a [T, H, hd], the sum over rows of KL_t, the
    selection's pairs [T, T] bool where it is one set a row)."""
    t = q.shape[0]
    n = min(QUERY_BLOCK, t)
    strips = [attention_strip(q, k, v, q_i, k_i, w, lo, n=n,
                              topk=model["sa_config"]["topk"], mutate=mutate)
              for lo in range(0, t, n)]
    return (jnp.concatenate([a for a, _, _ in strips], axis=0),
            sum(kl for _, kl, _ in strips),
            None if strips[0][2] is None
            else jnp.concatenate([keep for _, _, keep in strips], axis=0))


def routed(y, r, p, *, first: int, k_active: int):
    """The routed experts' part of a layer. y: [T, D] (RMSNorm2's
    output), r: [T, 128] router logits over ALL experts, float32; p
    holds the held experts' `w_gate`, `w_up`, `w_down`, expert e of them
    being expert `first + e` of the router."""
    probs = jax.nn.softmax(r.astype(jnp.float32), axis=-1)
    top, chosen = jax.lax.top_k(probs, k_active)
    weight = (top / top.sum(-1, keepdims=True)).astype(y.dtype)
    m = jnp.zeros_like(y)
    for e in range(p["w_gate"].shape[0]):
        w_e = (weight * (chosen == first + e)).sum(-1)
        m = m + w_e[:, None] * (
            (jax.nn.silu(y @ p["w_gate"][e]) * (y @ p["w_up"][e]))
            @ p["w_down"][e])
    return m


def layer(h, p, model: dict, mutate: str = "", first: int | None = None):
    """One block on one sequence. h: [T, D]; p: the layer's leaves.
    Returns (h', the layer's routed part m, the sum over rows of KL_t,
    the selection [T, T] bool). `first`: the first held expert, where it
    is not the configuration's (the share test)."""
    hd, eps = model["head_dim"], model["rms_norm_eps"]
    n_q, n_kv = model["num_attention_heads"], model["num_key_value_heads"]
    t = h.shape[0]
    x = _rmsnorm(h, p["norm1"], eps)
    q = (x @ p["wq"]).reshape(t, n_q, hd)
    k = (x @ p["wk"]).reshape(t, n_kv, hd)
    v = (x @ p["wv"]).reshape(t, n_kv, hd)
    q, k = _rmsnorm(q, p["q_norm"], eps), _rmsnorm(k, p["k_norm"], eps)
    # no image: temporal, height and width are all the token's index
    streams = jnp.tile(jnp.arange(t), (3, 1))
    sections = model["rope_scaling"]["mrope_section"]
    q = rope(q, streams, model["rope_theta"], sections)
    k = rope(k, streams, model["rope_theta"], sections)
    a, kl, keep = attention(q, k, v, *index_parts(x, p, model, mutate),
                            model, mutate)
    h1 = h + a.reshape(t, n_q * hd) @ p["wo"]
    y = _rmsnorm(h1, p["norm2"], eps)
    router = getattr(jnp, model.get("router_dtype", "float32"))
    m = routed(y, y.astype(router) @ p["router"].astype(router), p,
               first=model["held_experts_first"] if first is None else first,
               k_active=model["num_experts_per_tok"])
    return h1 + m, m, kl, keep


def hidden(params, tokens, model: dict, mutate: str = ""):
    """ONE sequence of tokens [T] -> (the last block's output under the
    final norm [T, D], the sum over layers and rows of KL_t, every
    layer's selection)."""
    h = params["embed"][tokens]
    layers = params["layers"]
    total, kept = 0.0, []
    for l in range(layers["wq"].shape[0]):
        h, _, kl, keep = layer(
            h, {name: leaf[l] for name, leaf in layers.items()}, model,
            mutate)
        total, kept = total + kl, kept + [keep]
    return _rmsnorm(h, params["norm_f"], model["rms_norm_eps"]), total, kept


def forward(params, tokens, model: dict, mutate: str = "", rows: int = 0):
    """ONE sequence of tokens [T] -> logits [T, vocabulary slice] (of
    its LAST `rows` rows, where given: the rows that select)."""
    x, _, _ = hidden(params, tokens, model, mutate)
    return x[-rows:] @ params["head"].T


@jax.jit
def _nll_block(x, head, targets, lo, n):
    """The sum of the cross-entropies of LOSS_BLOCK rows from `lo` on,
    those at or after `n` (the last row has no target) left out; the
    softmax and the sum in float32."""
    rows = lo + jnp.arange(LOSS_BLOCK)
    logits = _rows(x, lo, LOSS_BLOCK) @ head.T
    logp = jax.nn.log_softmax(logits.astype(jnp.float32), axis=-1)
    nll = -jnp.take_along_axis(
        logp, _rows(targets, lo, LOSS_BLOCK)[:, None], axis=-1)[:, 0]
    return jnp.where(rows < n, nll, 0.0).sum()


def nll_sum(x, head, tokens):
    """The sum over a sequence's T - 1 targets of the next-token
    cross-entropy, from the final norm's output x [T, D], a block of
    logits at a time."""
    t = tokens.shape[0]
    pad = -t % LOSS_BLOCK
    x = jnp.pad(x, ((0, pad), (0, 0)))
    targets = jnp.pad(tokens[1:], (0, pad + 1))
    return sum(_nll_block(x, head, targets, lo, t - 1)
               for lo in range(0, t, LOSS_BLOCK))


def terms_of(params, batch, model: dict, mutate: str = ""):
    """(the mean next-token cross-entropy, the indexer's loss: the mean
    of KL_t over rows and layers) of batch [B, T], differentiable in
    `params`; the loss's softmax and sums in float32."""
    nll = index = 0.0
    b, t = batch.shape
    for tokens in batch:
        x, kl, _ = hidden(params, tokens, model, mutate)
        nll, index = nll + nll_sum(x, params["head"], tokens), index + kl
    return nll / (b * (t - 1)), index / (
        b * t * params["layers"]["wq"].shape[0])


def loss_of(params, batch, model: dict, mutate: str = ""):
    main, index = terms_of(params, batch, model, mutate)
    return main + model.get("index_loss_weight", 1.0) * index


def terms(init, batch, model: dict, dtype=jnp.float32, mutate: str = ""
          ) -> tuple[float, float]:
    """The step-0 loss's two terms. `init` is what the family's
    `model_init` returns: (parameters, model state). Walked from Python,
    a layer's operations one by one and its strips and the logits'
    blocks as jitted functions: nothing of the whole pass is compiled at
    once. `dtype` other than float32 is for showing what a lower
    precision does: weights and activations in it, the loss's softmax
    and sums in float32."""
    params = jax.tree.map(lambda x: x.astype(dtype), init[0])
    with jax.default_matmul_precision(
            "highest" if dtype == jnp.float32 else "default"):
        main, index = terms_of(params, batch, model, mutate)
    return float(main), float(index)


def loss(init, batch, model: dict, dtype=jnp.float32) -> float:
    """The step-0 loss of the whole batch: the cross-entropy plus
    `index_loss_weight` times the indexer's loss."""
    main, index = terms(init, batch, model, dtype)
    return main + model.get("index_loss_weight", 1.0) * index
