"""Plain reference of the ``qwen3_next`` family: forward pass and
next-token cross-entropy in ``jax.numpy``, float32, matmul precision
"highest". No kernel, no chunk, no inverse, no sort, no remat: a Python
loop over the layers, the delta rule walked POSITION BY POSITION (a
``lax.scan`` over t), attention as a masked softmax in query blocks, the
held experts as dense products with a dense mask of who chose them.
Nothing is imported from ``ray_tpu``; gradients are ``jax.grad`` of
this.

Written from the published configuration of
Qwen/Qwen3-Next-80B-A3B-Instruct (``config.json``, ``model_type``
``qwen3_next``) and the Gated DeltaNet paper (arXiv:2412.06464). With h
the residual stream ``[T, 2048]``:

    N(x) = x / sqrt(mean(x^2) + 1e-6) * (1 + w)        the family's zero-centred RMSNorm:
                                                       both norms of a layer, the final one,
                                                       the head norms of q and k
    every layer:  h1 = h + mixer(N1(h));  h' = h1 + moe(N2(h1))
    layer i (from 0) is full attention where (i + 1) % full_attention_interval == 0,
    else Gated DeltaNet.

Gated DeltaNet (16 key heads, 32 value heads, of 128; conv of 4 taps):

    [q 2048 | k 2048 | v 4096 | z 4096] = x W_qkvz;   [b 32 | a 32] = x W_ba
    [q | k | v] <- silu(conv4([q | k | v]))           depthwise, causal, no bias:
                                                      out_t = sum_j tap_j in_(t - 3 + j)
    beta = sigmoid(b);  g = -exp(A_log) * softplus(a + dt_bias)      a value head
    q, k <- x * rsqrt(sum(x^2) + 1e-6) a head;  q <- q / sqrt(128)
    value head n reads key head n // 2
    per value head, S [128, 128] from zero:
        S' = exp(g_t) S_(t-1);  S_t = S' + k_t (beta_t (v_t - S'^T k_t))^T;  o_t = S_t^T q_t
    o <- (o / sqrt(mean(o^2) + 1e-6) * w_n) * silu(z)  a head: the norm BEFORE the gate,
                                                       a plain weight
    mixer = o W_out                                    [4096, 2048]

Gated attention (16 query / 2 key-value heads of 256):

    [query | gate] = x W_q  [T, 16, 2 x 256], split a head;  k, v = x W_k, x W_v  [T, 2, 256]
    q, k <- N over each head;  rotate-half rotary on the first 64 dimensions of a head,
    rates 1e7 ** (-2i / 64), the other 192 pass
    a = softmax(q k^T / sqrt(256) + causal) v          query head n reads key/value head n // 8
    mixer = (a * sigmoid(gate)) W_o                    elementwise, 4096 wide

Expert block (512 experts of 512, top 10, one shared expert of 512):

    p = softmax(y W_r) over all 512;  S = top10(p);  w = p[S] / sum(p[S])
    m = sum over e in S AND e in Held of  w_e W_down,e (silu(W_gate,e y) * (W_up,e y))
    moe = m + sigmoid(y . w_s) * Ws_down (silu(Ws_gate y) * (Ws_up y))

    logits = N_f(h_L) W_head;  loss = mean next-token cross-entropy over the slice

Departures and choices, each under ``assumed`` in the configuration's
file: ``Held`` = the experts the configuration holds (0..31 of 512, rank
0 of the sixteen chips that share a layer) — what the others would add
is left out here as in the program, and that partial result goes on to
the next layer; the vocabulary is the slice held; no MTP block, no
balancing loss. The parameter tree is the program's: block leaves
stacked over the layers that have them (the norms, the router and the
experts over all four; ``delta_*`` over the three delta layers;
``wq_full``, ``wk``, ``wv``, ``wo_full``, ``q_norm``, ``k_norm`` over the
attention layer); ``delta_in``'s columns are ``[q | k | v | z]``,
``delta_ba`` is kept ``[b | a]`` as ROWS, ``[64, 2048]``.

``MUTATIONS`` are alternatives the configuration did NOT take; the tests
show the comparison tells each apart.

It computes in blocks so that it fits beside the training state on the
chip: one sequence at a time, one key/value head's queries
(``QUERY_BLOCK`` of them) at a time, the experts ``EXPERT_BLOCK`` at a
time with the routing weight applied before the down-projection."""

from __future__ import annotations

import math

import jax
import jax.numpy as jnp

QUERY_BLOCK = 1024
EXPERT_BLOCK = 16

MUTATIONS = (
    "decay dropped", "beta dropped", "delta term reads the undecayed state",
    "no L2 norm", "q unscaled", "gate before the norm",
    "key heads paired round-robin", "attention gate dropped",
    "rotary on the whole head", "w for 1 + w", "shared gate dropped",
    "top-10 not renormalised")


def layer_kinds(model: dict) -> list[str]:
    """The mixer of every layer run: full attention closes each
    interval."""
    every = model["full_attention_interval"]
    return ["full" if (i + 1) % every == 0 else "delta"
            for i in range(model["num_hidden_layers"])]


def _norm(x, w, eps, mutate: str = ""):
    gain = w if mutate == "w for 1 + w" else 1.0 + w
    return x / jnp.sqrt((x * x).mean(-1, keepdims=True) + eps) * gain


def delta_rule(q, k, v, g, beta, mutate: str = ""):
    """q, k, v: [T, H, 128] (a value head's own), g, beta: [T, H] ->
    o [T, H, 128], the state from zero, one position at a time."""
    def step(state, part):
        q_t, k_t, v_t, g_t, b_t = part
        decayed = jnp.exp(g_t)[:, None, None] * state
        read = jnp.einsum(
            "hkv,hk->hv", state if mutate
            == "delta term reads the undecayed state" else decayed, k_t)
        state = decayed + jnp.einsum("hk,hv->hkv", k_t,
                                     b_t[:, None] * (v_t - read))
        return state, jnp.einsum("hkv,hk->hv", state, q_t)

    heads, dk, dv = q.shape[1], q.shape[2], v.shape[2]
    _, o = jax.lax.scan(step, jnp.zeros((heads, dk, dv), q.dtype),
                        (q, k, v, g, beta))
    return o


def delta_mixer(x, p, model: dict, mutate: str = ""):
    """The Gated DeltaNet mixer's part of the residual on the first
    norm's output x [T, D]."""
    t = x.shape[0]
    groups, heads = model["linear_num_key_heads"], \
        model["linear_num_value_heads"]
    dk, dv = model["linear_key_head_dim"], model["linear_value_head_dim"]
    taps = model["linear_conv_kernel_dim"]
    keys, values = groups * dk, heads * dv
    proj = x @ p["delta_in"]
    ba = x @ p["delta_ba"].T
    mixed, z = proj[:, :2 * keys + values], proj[:, 2 * keys + values:]
    padded = jnp.concatenate(
        [jnp.zeros((taps - 1, mixed.shape[1]), mixed.dtype), mixed])
    mixed = jax.nn.silu(sum(p["delta_conv"][j] * padded[j:j + t]
                            for j in range(taps)))
    q = mixed[:, :keys].reshape(t, groups, dk)
    k = mixed[:, keys:2 * keys].reshape(t, groups, dk)
    v = mixed[:, 2 * keys:].reshape(t, heads, dv)
    if mutate != "no L2 norm":
        q, k = (z_ * jax.lax.rsqrt((z_ * z_).sum(-1, keepdims=True) + 1e-6)
                for z_ in (q, k))
    if mutate != "q unscaled":
        q = q / math.sqrt(dk)
    if mutate == "key heads paired round-robin":
        reads = jnp.arange(heads) % groups
    else:
        reads = jnp.arange(heads) // (heads // groups)
    beta = jax.nn.sigmoid(ba[:, :heads])
    g = -jnp.exp(p["delta_A_log"]) * jax.nn.softplus(
        ba[:, heads:] + p["delta_dt_bias"])
    if mutate == "beta dropped":
        beta = jnp.ones_like(beta)
    if mutate == "decay dropped":
        g = jnp.zeros_like(g)
    o = delta_rule(q[:, reads], k[:, reads], v, g.astype(x.dtype),
                   beta.astype(x.dtype), mutate)
    gate = jax.nn.silu(z.reshape(t, heads, dv))
    eps = model["rms_norm_eps"]

    def plain_norm(o):
        return o / jnp.sqrt((o * o).mean(-1, keepdims=True) + eps) \
            * p["delta_norm"]

    if mutate == "gate before the norm":
        o = plain_norm(o * gate)
    else:
        o = plain_norm(o) * gate
    return o.reshape(t, values) @ p["delta_out"]


def rope_tables(t: int, model: dict, mutate: str = ""):
    """(cos, sin) [T, r / 2] float32, r the turned width."""
    width = int(model["head_dim"] * model["partial_rotary_factor"])
    if mutate == "rotary on the whole head":
        width = model["head_dim"]
    pair = jnp.arange(width // 2, dtype=jnp.float32)
    rate = model["rope_theta"] ** (-2 * pair / width)
    angle = jnp.arange(t, dtype=jnp.float32)[:, None] * rate[None, :]
    return jnp.cos(angle), jnp.sin(angle)


def _rope(x, cos, sin):
    """x: [T, H, hd]; the first 2 x cos's width dimensions turn,
    rotate-half within them (dimension i with i + width / 2)."""
    half = cos.shape[-1]
    cos, sin = cos[:, None, :].astype(x.dtype), sin[:, None, :].astype(x.dtype)
    a, b, rest = x[..., :half], x[..., half:2 * half], x[..., 2 * half:]
    return jnp.concatenate([a * cos - b * sin, b * cos + a * sin, rest], -1)


def _attention_block(q, k, v, lo):
    """One key/value head's queries [lo, lo + len(q)) against all T keys
    under a dense causal mask. q: [Tq, group, hd]; k, v: [T, hd]."""
    i = (lo + jnp.arange(q.shape[0]))[:, None]
    j = jnp.arange(k.shape[0])[None, :]
    s = jnp.einsum("qhd,kd->hqk", q, k) / math.sqrt(q.shape[-1])
    s = jnp.where((i >= j)[None], s, -jnp.inf)
    return jnp.einsum("hqk,kd->qhd", jax.nn.softmax(s, axis=-1), v)


def attention_mixer(x, p, model: dict, mutate: str = ""):
    """The gated attention's part of the residual on the first norm's
    output x [T, D]; p holds `wq` (doubled), `wk`, `wv`, `wo`, `q_norm`,
    `k_norm`."""
    t, hd = x.shape[0], model["head_dim"]
    heads, n_kv = model["num_attention_heads"], model["num_key_value_heads"]
    eps = model["rms_norm_eps"]
    both = (x @ p["wq"]).reshape(t, heads, 2 * hd)
    q, gate = both[..., :hd], both[..., hd:]
    k = (x @ p["wk"]).reshape(t, n_kv, hd)
    v = (x @ p["wv"]).reshape(t, n_kv, hd)
    q, k = _norm(q, p["q_norm"], eps, mutate), \
        _norm(k, p["k_norm"], eps, mutate)
    tables = rope_tables(t, model, mutate)
    q, k = _rope(q, *tables), _rope(k, *tables)
    group = heads // n_kv
    a = jnp.concatenate([jnp.concatenate([
        _attention_block(q[lo:lo + QUERY_BLOCK, g * group:(g + 1) * group],
                         k[:, g], v[:, g], lo)
        for lo in range(0, t, QUERY_BLOCK)], axis=0)
        for g in range(n_kv)], axis=1)                       # [T, H, hd]
    if mutate != "attention gate dropped":
        a = a * jax.nn.sigmoid(gate)
    return a.reshape(t, heads * hd) @ p["wo"]


def _gated(y, w1, w3, w2):
    return (jax.nn.silu(y @ w1) * (y @ w3)) @ w2


def routed(y, r, p, *, first: int, k_active: int, mutate: str = ""):
    """The routed experts' part of a layer. y: [T, D] (the MLP's input);
    r: [T, 512] the router's product over ALL experts; p holds the held
    experts' weights, expert e of them being expert `first + e` of the
    router. Returns (m [T, D], n [512]: the assignments each of all
    experts got)."""
    scores = jax.nn.softmax(r, axis=-1)
    top, chosen = jax.lax.top_k(scores, k_active)              # [T, 10]
    weight = top if mutate == "top-10 not renormalised" \
        else top / top.sum(-1, keepdims=True)
    m = jnp.zeros_like(y)
    for lo in range(0, p["w_gate"].shape[0], EXPERT_BLOCK):
        w_gate, w_up, w_down = (p[name][lo:lo + EXPERT_BLOCK]
                                for name in ("w_gate", "w_up", "w_down"))
        held = first + lo + jnp.arange(w_gate.shape[0])
        # w_e of every token for these experts: 0 where it was not chosen
        w_e = (weight[:, :, None] * (chosen[:, :, None] == held)).sum(1)
        act = jax.nn.silu(jnp.einsum("td,edf->etf", y, w_gate)) \
            * jnp.einsum("td,edf->etf", y, w_up)
        m = m + jnp.einsum("etf,efd->td", w_e.T[:, :, None] * act, w_down)
    n = (chosen[:, :, None] == jnp.arange(r.shape[-1])).sum((0, 1))
    return m, n


def layer(h, p, *, kind: str, model: dict, mutate: str = "",
          first: int | None = None):
    """One block on one sequence. h: [T, D]; p: the layer's leaves under
    their plain names; `first`: the first expert held (the
    configuration's, unless a share test says another). Returns (h', the
    routed part alone, n)."""
    eps = model["rms_norm_eps"]
    x = _norm(h, p["norm1"], eps, mutate)
    mix = delta_mixer if kind == "delta" else attention_mixer
    h1 = h + mix(x, p, model, mutate)
    y = _norm(h1, p["norm2"], eps, mutate)
    m, n = routed(
        y, y @ p["router"], p,
        first=model["held_experts_first"] if first is None else first,
        k_active=model["num_experts_per_tok"], mutate=mutate)
    shared = _gated(y, p["ws_gate"], p["ws_up"], p["ws_down"])
    if mutate != "shared gate dropped":
        shared = jax.nn.sigmoid(y @ p["ws_token_gate"])[:, None] * shared
    return h1 + m + shared, m, n


_EVERY = ("norm1", "norm2", "router", "w_gate", "w_up", "w_down", "ws_gate",
          "ws_up", "ws_down", "ws_token_gate")
_FULL = {"wq": "wq_full", "wo": "wo_full", "wk": "wk", "wv": "wv",
         "q_norm": "q_norm", "k_norm": "k_norm"}


def layer_leaves(params, l: int, model: dict) -> tuple[str, dict]:
    """(kind, layer l's row of every stack it has a row in; the
    attention layer's `wq_full`, `wo_full` under `wq`, `wo`)."""
    kinds = layer_kinds(model)[:l + 1]
    kind = kinds[-1]
    at = kinds[:-1].count(kind)
    layers = params["layers"]
    p = {name: layers[name][l] for name in _EVERY}
    if kind == "full":
        p.update({plain: layers[name][at] for plain, name in _FULL.items()})
    else:
        p.update({name: leaf[at] for name, leaf in layers.items()
                  if name.startswith("delta_")})
    return kind, p


def forward(params, tokens, model, mutate: str = ""):
    """ONE sequence. tokens: [T] -> (logits [T, V], n [layers, 512])."""
    h = params["embed"][tokens]
    counts = []
    for l in range(model["num_hidden_layers"]):
        kind, p = layer_leaves(params, l, model)
        h, _, n = layer(h, p, kind=kind, model=model, mutate=mutate)
        counts.append(n)
    return _norm(h, params["norm_f"], model["rms_norm_eps"], mutate) \
        @ params["head"], jnp.stack(counts)


def nll_sum(params, tokens, model, mutate: str = ""):
    """Summed next-token loss of ONE sequence. tokens: [T]. The loss's
    own softmax and sum are float32 whatever the blocks compute in."""
    logp = jax.nn.log_softmax(
        forward(params, tokens, model, mutate)[0][:-1].astype(jnp.float32),
        axis=-1)
    return -jnp.take_along_axis(logp, tokens[1:, None], axis=-1).sum()


def loss(init, batch, model: dict, dtype=jnp.float32,
         mutate: str = "") -> float:
    """Mean next-token loss of the whole batch, one sequence at a time.
    `init` is what the family's `model_init` returns: (parameters, the
    counters' state); only the parameters are read. `dtype` other than
    float32 is the precision control: the BLOCKS in `dtype` (weights,
    activations, rotary tables, gates, router, the delta rule's state,
    the attention's softmax), the loss's own softmax and sums still
    float32."""
    params = jax.tree.map(lambda x: x.astype(dtype), init[0])
    rows, t = batch.shape
    fn = jax.jit(lambda p, tok: nll_sum(p, tok, model, mutate))
    total = 0.0
    with jax.default_matmul_precision(
            "highest" if dtype == jnp.float32 else "default"):
        for i in range(rows):
            total += float(fn(params, batch[i]))
    return total / (rows * (t - 1))
