"""Plain reference of the ``joyai`` family: forward pass and the
two-term loss in ``jax.numpy``, float32, matmul precision "highest". No
kernel, no grouping, no sort, no scan, no remat: a Python loop over the
layers, a dense mask on blocks of the scores, EVERY held expert applied
to every token under a dense mask of who chose it. Nothing is imported from
``ray_tpu``.

Written from the published configuration of JoyAI-LLM-Flash
(``config.json``, ``model_type`` ``joyai_llm_flash``) and the DeepSeek-V3
technical report (arXiv:2412.19437, sections 2.1 and 2.2), whose block
this ``model_type`` is at other numbers. With h the residual stream
``[T, 2048]`` of one sequence, u = RMSNorm1(h) (weight only, eps 1e-6):

    latent attention (MLA), every layer:
      c_q  = RMSNorm_1536(u W_qa)
      q    = c_q W_qb                -> 32 heads of [q_nope 128 | q_rope 64]
      [c_kv 512 | k_rope 64] = u W_kva
      c_kv = RMSNorm_512(c_kv)
      c_kv W_kvb                     -> 32 heads of [k_nope 128 | v 128]
      q_rope, k_rope = RoPE(.)       theta 32e6, no scaling, INTERLEAVED:
                                     dimensions 2i and 2i + 1 turn together;
                                     k_rope is ONE head, shared by all 32
      k    = [k_nope | k_rope],  q = [q_nope | q_rope]        192 wide
      a    = softmax(q k^T / sqrt(192) + causal) v            128 wide
      h1   = h + a W_o               4096 -> 2048; no bias anywhere
    y  = RMSNorm2(h1)
    layer 0 (first_k_dense_replace 1):
      m = W_2 (silu(W_1 y) * (W_3 y))                         width 7168
    layers 1..:
      s   = sigmoid(y W_r)           float32, all 256 experts
      S   = top8(s + b)              b: the layer's selection bias, choice
                                     only (n_group 1: no group limit)
      p_e = 2.5 * s_e / (sum_{S} s + 1e-6)       the UNBIASED s
      m   = sum over e in S AND e in Held of p_e E_e(y)  +  Shared(y)
                                     both gated SiLU of width 768
    h' = h1 + m
    logits = RMSNorm_f(h_L) W_head   untied, over the vocabulary slice

    multi-token prediction, depth 1 (the report's 2.2), position i:
      x_i  = M [RMSNorm_h(h_L,i) ; RMSNorm_e(Emb(t_{i+1}))]   4096 -> 2048
      x'   = one more block of the expert kind on x (weights, router,
             bias of its own);   logits2_i = RMSNorm_f'(x'_i) W_head
      the SAME Emb and W_head as the main model; row i predicts t_{i+2}
    loss = CE(logits_i, t_{i+1}; i < T-1) + 0.3 * CE(logits2_i, t_{i+2};
           i < T-2), each a mean over its own positions

after the loss, once a step (``bias_update``; arXiv:2408.15664):
    b_e <- b_e + u sign(mean_e' n_e' - n_e)      n_e: assignments expert
                                     e of all 256 got in that layer, u 1e-3

Departures and choices, each under ``assumed`` in the configuration
file: ``Held`` = experts 0..15 of 256 (rank 0 of sixteen chips) and what
the others would add is left out here as in the program, the shared
expert being what every chip computes alike; the vocabulary is the slice
held; the routing's 1e-6; the bias rule and its rate; lambda 0.3; the
order inside the MTP's concatenation, h_L taken before the final norm,
the last position of the MTP block (no token follows it) taking id 0 and
never scored. The parameter tree is the program's: every block leaf
stacked over the layers that have it, in layer order, the held experts
along the next axis, the MTP block under ``params["mtp"]`` (``proj``,
``norm_h``, ``norm_e``, ``norm_f``, ``layer``); the selection bias's
last row is the MTP block's. Leaf names map as W_qa = ``wq_a``, W_qb =
``wq_b``, W_kva = ``wkv_a``, W_kvb = ``wkv_b``, W_o = ``wo_latent``,
W_1, W_3, W_2 = ``w_gate``, ``w_up``, ``w_down`` (``ws_*`` the shared
expert's, ``w1`` ``w3`` ``w2`` the dense layer's).

``MUTATIONS`` are alternatives the configuration did NOT take; the
tests show the comparison tells each apart.

It computes one sequence at a time, attention ``HEAD_BLOCK`` heads and
``QUERY_BLOCK`` queries at a time (a ``[8, 4096, 8192]`` float32 score
block is 1.07 GB, as is the sixteen held experts' ``[16, 8192, 2048]``
output), each head's logits one sequence at a time (0.53 GB), so that it
fits beside the training state on the chip: 2.1 GiB of temporaries. Few
large blocks and the experts as one product, not a loop, because a cold
run COMPILES this on the chip's host: 60 s and 3.9 GiB of host memory so
(216 s and 10.9 GiB with 4 x 2048 blocks and a loop over the experts,
which took a cold run of the cell to the machine's 40 GiB; my sandbox
compiles for a described v5e, PR 37)."""

from __future__ import annotations

import jax
import jax.numpy as jnp

from benchmark.families.smallthinker_reference import _rmsnorm

ROUTING_EPS = 1e-6
QUERY_BLOCK = 4096
HEAD_BLOCK = 8

MUTATIONS = (
    "scale 1/sqrt(nope)", "v padded to the key width, o cut flat",
    "rotary on the nope part", "a rotary key per head",
    "rotate-half for interleaved", "a latent norm left out",
    "the shared expert left out", "factor 1", "MTP predicts t_{i+1}",
    "MTP with an embedding of its own", "lambda 0")


def _rope(x, theta: float, mutate: str = ""):
    """x: [T, H, dim]; interleaved pairing: dimensions 2i and 2i + 1 turn
    by position * theta ** (-2i / dim)."""
    t, _, dim = x.shape
    half = dim // 2
    inv = theta ** (-jnp.arange(half, dtype=jnp.float32) / half)
    angle = jnp.arange(t, dtype=jnp.float32)[:, None] * inv[None, :]
    cos = jnp.cos(angle)[:, None, :].astype(x.dtype)
    sin = jnp.sin(angle)[:, None, :].astype(x.dtype)
    if mutate == "rotate-half for interleaved":
        a, b = x[..., :half], x[..., half:]
        return jnp.concatenate([a * cos - b * sin, b * cos + a * sin], -1)
    a, b = x[..., 0::2], x[..., 1::2]
    return jnp.stack([a * cos - b * sin, b * cos + a * sin],
                     axis=-1).reshape(x.shape)


def _attention(q, k, v, scale: float):
    """Causal attention, q and k [T, H, d_qk], v [T, H, d_v] -> [T, H,
    d_v], a block of heads and of queries at a time under a dense mask."""
    t, h, _ = q.shape
    j = jnp.arange(t)[None, :]
    heads = []
    for g in range(0, h, HEAD_BLOCK):
        blocks = []
        for lo in range(0, t, QUERY_BLOCK):
            qb = q[lo:lo + QUERY_BLOCK, g:g + HEAD_BLOCK]
            i = (lo + jnp.arange(qb.shape[0]))[:, None]
            s = jnp.einsum("qhd,khd->hqk", qb, k[:, g:g + HEAD_BLOCK]) * scale
            s = jnp.where((i >= j)[None], s, -jnp.inf)
            blocks.append(jnp.einsum("hqk,khd->qhd", jax.nn.softmax(s, -1),
                                     v[:, g:g + HEAD_BLOCK]))
        heads.append(jnp.concatenate(blocks, axis=0))
    return jnp.concatenate(heads, axis=1)


def latent_mixer(u, p, model, mutate: str = ""):
    """The latent attention's part of the residual. u: [T, D]."""
    t = u.shape[0]
    h, nope = model["num_attention_heads"], model["qk_nope_head_dim"]
    rot, dv = model["qk_rope_head_dim"], model["v_head_dim"]
    r_kv, eps = model["kv_lora_rank"], model["rms_norm_eps"]
    theta = float(model["rope_theta"])
    c_q = u @ p["wq_a"]
    if mutate != "a latent norm left out":
        c_q = _rmsnorm(c_q, p["q_a_norm"], eps)
    q = (c_q @ p["wq_b"]).reshape(t, h, nope + rot)
    kv_a = u @ p["wkv_a"]
    c_kv = _rmsnorm(kv_a[:, :r_kv], p["kv_a_norm"], eps)
    k_rope = kv_a[:, r_kv:][:, None, :]                     # [T, 1, rot]
    kv = (c_kv @ p["wkv_b"]).reshape(t, h, nope + dv)
    q_nope, q_rope = q[..., :nope], q[..., nope:]
    k_nope, v = kv[..., :nope], kv[..., nope:]
    if mutate == "rotary on the nope part":
        q_nope = jnp.concatenate(
            [_rope(q_nope[..., :rot], theta), q_nope[..., rot:]], -1)
        k_nope = jnp.concatenate(
            [_rope(k_nope[..., :rot], theta), k_nope[..., rot:]], -1)
        k_rope = jnp.broadcast_to(k_rope, (t, h, rot))
    else:
        q_rope = _rope(q_rope, theta, mutate)
        k_rope = jnp.broadcast_to(_rope(k_rope, theta, mutate), (t, h, rot))
    if mutate == "a rotary key per head":
        k_rope = jnp.stack([jnp.roll(k_rope[:, g], g, axis=-1)
                            for g in range(h)], axis=1)
    scale = (nope if mutate == "scale 1/sqrt(nope)" else nope + rot) ** -0.5
    q = jnp.concatenate([q_nope, q_rope], -1)
    k = jnp.concatenate([k_nope, k_rope], -1)
    if mutate == "v padded to the key width, o cut flat":
        wide = jnp.pad(v, ((0, 0), (0, 0), (0, nope + rot - dv)))
        a = _attention(q, k, wide, scale).reshape(t, -1)[:, :h * dv]
    else:
        a = _attention(q, k, v, scale).reshape(t, h * dv)
    return a @ p["wo_latent"]


def _gated(y, w1, w3, w2):
    return (jax.nn.silu(y @ w1) * (y @ w3)) @ w2


def routed(y, r, p, bias, *, first: int, k_active: int, factor: float):
    """The routed experts' part of a layer, and who was chosen. y: [T, D]
    (the MLP's input); r: [T, 256] the router's product over ALL
    experts; bias: [256]; p holds the held experts' weights, expert e of
    them being expert `first + e` of the router. Returns (m [T, D],
    n [256]: the assignments each of all experts got)."""
    s = jax.nn.sigmoid(r)
    _, chosen = jax.lax.top_k(s + bias, k_active)              # [T, 8]
    picked = jnp.take_along_axis(s, chosen, axis=-1)
    weight = factor * picked / (picked.sum(-1, keepdims=True) + ROUTING_EPS)
    held = first + jnp.arange(p["w_gate"].shape[0])
    # p_e of every token for every held expert: 0 where it was not chosen
    p_e = (weight[:, :, None] * (chosen[:, :, None] == held)).sum(1)  # [T, E]
    act = jax.nn.silu(jnp.einsum("td,edf->etf", y, p["w_gate"])) \
        * jnp.einsum("td,edf->etf", y, p["w_up"])
    m = (p_e.T[:, :, None]
         * jnp.einsum("etf,efd->etd", act, p["w_down"])).sum(0)
    n = (chosen[:, :, None] == jnp.arange(r.shape[-1])).sum((0, 1))
    return m, n


def layer(h, p, bias, *, mlp: str, model: dict, mutate: str = "",
          first: int | None = None):
    """One block on one sequence. h: [T, D]; p: the layer's leaves; bias:
    the layer's [256], or None in a dense layer; `first`: the first
    expert held (the configuration's, unless a share test says another).
    Returns (h', the routed part alone, n or None)."""
    eps = model["rms_norm_eps"]
    h1 = h + latent_mixer(_rmsnorm(h, p["norm1"], eps), p, model, mutate)
    y = _rmsnorm(h1, p["norm2"], eps)
    if mlp == "dense":
        return h1 + _gated(y, p["w1"], p["w3"], p["w2"]), None, None
    m, n = routed(
        y, y @ p["router"], p, bias,
        first=model["held_experts_first"] if first is None else first,
        k_active=model["num_experts_per_tok"],
        factor=1.0 if mutate == "factor 1"
        else model["routed_scaling_factor"])
    shared = 0.0 if mutate == "the shared expert left out" else _gated(
        y, p["ws_gate"], p["ws_up"], p["ws_down"])
    return h1 + m + shared, m, n


def kinds(model: dict) -> list[tuple[str, dict]]:
    """(mlp, {leaf group: the layer's row in that group's stacks}) of
    every main layer: the first `first_k_dense_replace` dense."""
    out, seen = [], {"dense": 0, "experts": 0}
    for l in range(model["num_hidden_layers"]):
        mlp = "dense" if l < model["first_k_dense_replace"] else "experts"
        out.append((mlp, {"layer": l, "latent": l, mlp: seen[mlp]}))
        seen[mlp] += 1
    return out


_GROUP_OF = {
    "norm1": "layer", "norm2": "layer",
    "wq_a": "latent", "q_a_norm": "latent", "wq_b": "latent",
    "wkv_a": "latent", "kv_a_norm": "latent", "wkv_b": "latent",
    "wo_latent": "latent",
    "w1": "dense", "w3": "dense", "w2": "dense",
    "router": "experts", "w_gate": "experts", "w_up": "experts",
    "w_down": "experts", "ws_gate": "experts", "ws_up": "experts",
    "ws_down": "experts"}


def forward(params, bias, tokens, model, mutate: str = ""):
    """ONE sequence. tokens: [T]; bias: [expert layers + 1, 256], the MTP
    block's row last. Returns (logits [T, V], the MTP head's logits
    [T, V] whose row i predicts token i + 2, n [expert layers + 1,
    256])."""
    eps = model["rms_norm_eps"]
    h = params["embed"][tokens]
    counts = []
    for mlp, row in kinds(model):
        p = {name: leaf[row[_GROUP_OF[name]]]
             for name, leaf in params["layers"].items()
             if _GROUP_OF[name] in row}
        h, _, n = layer(h, p, bias[row["experts"]] if mlp == "experts"
                        else None, mlp=mlp, model=model, mutate=mutate)
        counts += [] if n is None else [n]
    logits = _rmsnorm(h, params["norm_f"], eps) @ params["head"]
    mtp = params["mtp"]
    following = jnp.concatenate([tokens[1:], jnp.zeros_like(tokens[:1])])
    table = params["head"].T if mutate == "MTP with an embedding of its own" \
        else params["embed"]
    x = jnp.concatenate([_rmsnorm(h, mtp["norm_h"], eps),
                         _rmsnorm(table[following], mtp["norm_e"], eps)],
                        axis=-1) @ mtp["proj"]
    x, _, n = layer(x, mtp["layer"], bias[-1], mlp="experts", model=model,
                    mutate=mutate)
    logits2 = _rmsnorm(x, mtp["norm_f"], eps) @ params["head"]
    return logits, logits2, jnp.stack(counts + [n])


def _nll_sum(logits, targets):
    logp = jax.nn.log_softmax(logits, axis=-1)
    return -jnp.take_along_axis(logp, targets[:, None], axis=-1).sum()


def nll_of(logits, logits2, tokens, mutate: str = ""):
    """(summed main loss, summed MTP loss) of ONE sequence's two heads:
    row i of the first against token i + 1, of the second against token
    i + 2."""
    ahead = 1 if mutate == "MTP predicts t_{i+1}" else 2
    return (_nll_sum(logits[:-1], tokens[1:]),
            _nll_sum(logits2[:-2], tokens[ahead:len(tokens) - 2 + ahead]))


def nll_sums(params, bias, tokens, model, mutate: str = ""):
    """(summed main loss, summed MTP loss, n) of ONE sequence."""
    logits, logits2, n = forward(params, bias, tokens, model, mutate)
    return (*nll_of(logits, logits2, tokens, mutate), n)


def bias_update(bias, n, rate: float):
    """The loss-free balancing rule. bias, n: [expert layers + 1, 256];
    n the assignments every expert got from the whole batch."""
    n = n.astype(jnp.float32)
    return bias + rate * jnp.sign(n.mean(-1, keepdims=True) - n)


def loss_terms(init, batch, model: dict, dtype=jnp.float32,
               mutate: str = ""):
    """(main mean, MTP mean, n summed over the batch's sequences), one
    sequence at a time. `init` is what the family's `model_init`
    returns: (parameters, the model state, whose `expert_bias` is
    read)."""
    params = jax.tree.map(lambda x: x.astype(dtype), init[0])
    bias = init[1]["expert_bias"].astype(dtype)
    rows, t = batch.shape
    fn = jax.jit(lambda p, b, tok: nll_sums(p, b, tok, model, mutate))
    main, second, n = 0.0, 0.0, 0
    with jax.default_matmul_precision(
            "highest" if dtype == jnp.float32 else "default"):
        for i in range(rows):
            a, b, n_i = fn(params, bias, batch[i])
            main, second, n = main + float(a), second + float(b), n + n_i
    return main / (rows * (t - 1)), second / (rows * (t - 2)), n


def loss(init, batch, model: dict, dtype=jnp.float32,
         mutate: str = "") -> float:
    """The two-term loss of the whole batch: main + lambda x MTP.
    `dtype` other than float32 is for showing that a lower precision is
    told apart."""
    main, second, _ = loss_terms(init, batch, model, dtype, mutate)
    weight = 0.0 if mutate == "lambda 0" else model["mtp_loss_weight"]
    return main + weight * second
