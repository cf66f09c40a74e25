"""Family ``kimi_linear``: a decoder built from a layer pattern by
``ray_tpu.models.decoder`` — three layers in four Kimi Delta Attention
(the delta rule with a decay a CHANNEL of the key, ``ops/kda.py`` in
chunks of 64: 32 heads of 128 / 128 behind a 4-tap depthwise
convolution, low-rank projections for the decay and for a sigmoid output
gate after a head's norm), the fourth latent attention (MLA) with NO
query rank and NO positions (192-wide queries and keys, one shared key
part of 64, 128-wide values), a dense gated-SiLU MLP in the leading
layer and after it top-k routed gated-SiLU experts without dropped
tokens over the HELD share of the experts (sigmoid scores, a selection
bias that is model state, weights normalised over the chosen times a
scaling factor) beside one shared expert, an untied head over a slice of
the vocabulary — trained on next-token cross-entropy over one repeated
batch of seeded random tokens drawn from the slice.

Configuration keys are the source's (``config.json`` of
Kimi-Linear-48B-A3B-Instruct); ``num_experts`` counts the experts held
here, ``router_outputs`` all of them, ``held_experts_first`` the first
one held; ``linear_attn_config`` is carried whole, and the layers run
are the first ``num_hidden_layers`` of its lists. Workload keys:
``batch`` (sequences a step), ``seq`` (tokens a sequence). The step is
registered in the operator's stateful form: the state is the routing and
KDA counters and the selection bias (``decoder.state_init``)."""

from __future__ import annotations

from benchmark.common import Pieces, key_seed, make_optimizer
from benchmark.families.smallthinker import mean_keys
from benchmark.manifest import ManifestError


def _decoder():
    """The program's decoder, or a ManifestError on a checkout from
    before it had the kda mixer: said before any runtime starts (run.py
    exits 3 on it)."""
    from ray_tpu.models import decoder

    if "kda" not in decoder.MIXER_KINDS:
        raise ManifestError("this checkout's ray_tpu.models.decoder has no "
                            "mixer kind \"kda\": it cannot build the "
                            "kimi_linear family")
    return decoder


_decoder()


def layer_kinds(model: dict) -> list[tuple[str, str]]:
    """(mixer, mlp) of every layer run, in the decoder's names, from the
    source's lists (layers numbered from 1; the reference reads them
    with a rule of its own, and the two are compared as everything else
    is)."""
    attn = model["linear_attn_config"]
    latent, kda = set(attn["full_attn_layers"]), set(attn["kda_layers"])
    layers = range(1, model["num_hidden_layers"] + 1)
    if latent & kda or not set(layers) <= latent | kda:
        raise ValueError("linear_attn_config names every layer once, in "
                         "kda_layers or in full_attn_layers")
    return [("latent" if l in latent else "kda",
             "dense" if l <= model["first_k_dense_replace"] else "experts")
            for l in layers]


def _period(kinds: list) -> list:
    """The shortest run of layers that `kinds` is whole copies of."""
    for n in range(1, len(kinds) + 1):
        if len(kinds) % n == 0 and kinds == kinds[:n] * (len(kinds) // n):
            return kinds[:n]
    return kinds


def model_cfg(model: dict):
    import jax.numpy as jnp

    decoder = _decoder()
    attn = model["linear_attn_config"]
    if model["q_lora_rank"] is not None or not model["mla_use_nope"] \
            or model["rope_scaling"] is not None \
            or not model["moe_renormalize"] or model["moe_layer_freq"] != 1 \
            or model["num_expert_group"] != 1 or model["topk_group"] != 1 \
            or (model["moe_router_activation_func"],
                model["hidden_act"]) != ("sigmoid", "silu") \
            or model["tie_word_embeddings"] \
            or model["num_nextn_predict_layers"] \
            or attn["num_heads"] != model["num_attention_heads"]:
        raise ValueError(
            "the kimi_linear family: latent attention without a query "
            "rank and without positions, sigmoid scores with a selection "
            "bias and one group, weights normalised over the chosen, "
            "experts in every layer after the leading ones, SiLU, an "
            "untied head, no MTP block, as many KDA heads as attention "
            "heads")
    kinds, lead = layer_kinds(model), model["first_k_dense_replace"]
    period = _period(kinds[lead:])
    extra = {k: model[k] for k in ("attn_block_q", "attn_block_k", "gmm_tile",
                                   "loss_chunk")
             if k in model}
    heads = model["num_attention_heads"]
    return decoder.DecoderConfig(
        vocab_size=model["vocab_size"], n_layers=len(kinds),
        d_model=model["hidden_size"], n_heads=heads,
        n_kv_heads=model["num_key_value_heads"],
        head_dim=model["qk_nope_head_dim"] + model["qk_rope_head_dim"],
        lead_attention=tuple(a for a, _ in kinds[:lead]),
        lead_mlp=tuple(m for _, m in kinds[:lead]),
        attention=tuple(a for a, _ in period),
        mlp=tuple(m for _, m in period), window=0, rotary=(),
        rope_theta=float(model["rope_theta"]),
        # mla_use_nope: the latent kind turns nothing
        by_kind=(("latent", decoder.AttentionKind(n_heads=heads,
                                                  rope_dim=0)),),
        q_lora_rank=0, kv_lora_rank=model["kv_lora_rank"],
        qk_nope_dim=model["qk_nope_head_dim"],
        qk_rope_dim=model["qk_rope_head_dim"],
        v_head_dim=model["v_head_dim"],
        conv_taps=attn["short_conv_kernel_size"],
        delta_key_heads=attn["num_heads"], delta_value_heads=attn["num_heads"],
        delta_key_dim=attn["head_dim"], delta_value_dim=attn["head_dim"],
        n_experts=model["router_outputs"],
        top_k=model["num_experts_per_token"],
        d_expert=model["moe_intermediate_size"],
        d_shared=model["num_shared_experts"] * model["moe_intermediate_size"],
        routed_scale=model["routed_scaling_factor"],
        d_dense=model["intermediate_size"],
        held=(model["held_experts_first"], model["num_experts"]),
        router_input="mlp", routing="sigmoid_bias",
        bias_rate=model["expert_bias_update_rate"], activation="silu",
        tied_head=False, count_rows=True, rms_eps=model["rms_norm_eps"],
        init_std=model["init_std"],
        dtype=getattr(jnp, model["compute_dtype"]), remat=model["remat"],
        **extra)


def pieces(model: dict, workload: dict, seed: int) -> Pieces:
    import jax

    decoder = _decoder()
    cfg = model_cfg(model)
    batch, seq = workload["batch"], workload["seq"]
    if seq > model["model_max_length"]:
        raise ValueError(f"seq {seq} > model_max_length")
    tokens = jax.random.randint(jax.random.key(key_seed(seed) + 1),
                                (batch, seq), 0, cfg.vocab_size)

    def loss_fn(p, s, b):
        return decoder.stateful_loss(p, s, b, cfg)

    # what `train.dispatch` carries of a step: the KDA layers, the
    # chunks their rule walks, its heads
    loss_fn.step_facts = lambda b: decoder.step_facts(cfg, b.shape)
    return Pieces(
        # one jitted call: the weights are made on the device
        model_init=jax.jit(lambda key: (decoder.init(key, cfg),
                                        decoder.state_init(key, cfg))),
        loss_fn=loss_fn, optimizer=make_optimizer(model["optimizer"]),
        batch=tokens, stateful=True, rows=batch)


def moe_layers(model: dict) -> int:
    """The layers that route."""
    return sum(m == "experts" for _, m in layer_kinds(model))


def _kda_sizes(model: dict):
    from ray_tpu.ops.gated_delta import CHUNK

    attn = model["linear_attn_config"]
    return attn["num_heads"], attn["head_dim"], CHUNK


def kda_layers(model: dict) -> int:
    return sum(a == "kda" for a, _ in layer_kinds(model))


def kda_rule_flops_per_token(model: dict) -> dict:
    """The matrix products the RULE multiplies a token and KDA layer,
    whatever forms them, `{"fwd": .., "bwd": ..}`. Forward, a head: the
    decayed scores M and P, each a sum over the 128 channels for every
    pair of a chunk (2 x 2 C K: a kernel that takes some of them on the
    vector unit still owes them), K S, Q S and the state's update (3 x 2
    K V), Tm R and tril(P) V' (2 x 2 C V), the inverse at what the
    doubling multiplies (`inverse_products(C)` products of [C, C]: 2 C^2
    each a token). Backward, beyond the forward's own values again: six
    products with the state's shape (6 x 2 K V), four with the chunk's
    (4 x 2 C V: P^T dO, dO V'^T, Tm^T dV', dR V'^T) and four with the
    pairs' (4 x 2 C K: dM and dP against k and q, as rows and as
    columns)."""
    from ray_tpu.ops.gated_delta import inverse_products

    heads, d, c = _kda_sizes(model)
    return {
        "fwd": heads * (4 * c * d + 6 * d * d + 4 * c * d
                        + inverse_products(c) * 2 * c * c),
        "bwd": heads * (12 * d * d + 8 * c * d + 8 * c * d)}


def forward_flops_per_token(model: dict, seq: int) -> dict:
    """Forward model FLOPs a token, by part: the matrix products only.
    The KDA layers' projections (W_in, the two low ranks down and up,
    W_beta, W_out) and the 4-tap convolution; their rule's chunk
    products (`kda_rule_flops_per_token`); the latent layer's
    projections (the direct query, the joint compression, the
    up-projection, W_o) and its scores INSIDE the causal mask, 2 x (192
    + 128) a score and head; the dense MLP; the routed experts at their
    expectation under uniform routing, top_k x held / outputs experts a
    token (a quarter of an expert of a token's eight), and said so; the
    shared expert and the router on every expert layer; the vocabulary
    is the slice's. Norms, softmax, sigmoids and the embedding lookup
    are not counted."""
    d, h = model["hidden_size"], model["num_attention_heads"]
    nope, rot, dv = model["qk_nope_head_dim"], model["qk_rope_head_dim"], \
        model["v_head_dim"]
    heads, hd, _ = _kda_sizes(model)
    kinds = layer_kinds(model)
    n_kda = kda_layers(model)
    n_latent = sum(a == "latent" for a, _ in kinds)
    keys = heads * hd
    expert = 2 * 3 * d * model["moe_intermediate_size"]
    held_share = (model["num_experts_per_token"] * model["num_experts"]
                  / model["router_outputs"])
    taps = model["linear_attn_config"]["short_conv_kernel_size"]
    return {
        "kda_projections": n_kda * (
            2 * d * (3 * keys + 2 * hd + heads) + 2 * 2 * hd * keys
            + 2 * keys * d + 2 * taps * 3 * keys),
        "kda_rule": n_kda * kda_rule_flops_per_token(model)["fwd"],
        "latent_projections": n_latent * 2 * (
            d * h * (nope + rot) + d * (model["kv_lora_rank"] + rot)
            + model["kv_lora_rank"] * h * (nope + dv) + h * dv * d),
        "latent_attention": n_latent * 2 * (nope + rot + dv) * h
        * mean_keys(seq, None),
        "dense_mlp": model["first_k_dense_replace"] * 2 * 3 * d
        * model["intermediate_size"],
        "shared_experts": moe_layers(model) * model["num_shared_experts"]
        * expert,
        "routed_experts": moe_layers(model) * (
            held_share * expert + 2 * d * model["router_outputs"]),
        "vocabulary": 2 * d * model["vocab_size"]}


def flops_per_sample(model: dict, workload: dict) -> float:
    """Model FLOPs one sequence needs, forward and backward (3 x the
    forward) of what is HELD, recomputation not counted; the vocabulary
    for the seq - 1 positions that have a target."""
    seq = workload["seq"]
    part = forward_flops_per_token(model, seq)
    vocabulary = part.pop("vocabulary")
    return 3.0 * (seq * sum(part.values()) + (seq - 1) * vocabulary)


def kda_flops_bytes(model: dict, workload: dict, steps: int,
                    chunks: int | None = None, itemsize: int = 2) -> dict:
    """What the per-channel delta rule needs for `steps` steps, the KDA
    layers together: `{"fwd": (FLOPs, bytes), "bwd": (FLOPs, bytes)}`.
    `chunks`: the chunks a step walks (layers x sequences x T / C:
    `kda_chunks` on the traced call's `train.dispatch` span; default:
    the workload's). The forward runs twice a layer and step (the pass
    and its rematerialised copy), the backward once. FLOPs from
    `kda_rule_flops_per_token`: the RULE's products, whatever unit forms
    them (what the backward recomputes is not counted). Bytes, each
    array once a call: forward q, k, v, o in the compute dtype, the
    running sums of g (a float32 a key channel) and beta, and the
    chunks' entering states `[32, 128, 128]` float32 a chunk on BOTH
    calls (as `gdr_fwd`'s are counted: the first forward under
    `jax.checkpoint` is traced with the rule's differentiated form too);
    backward q, k, v, do and dq, dk, dv, the sums and beta and their
    gradients, and the entering states read once."""
    heads, d, c = _kda_sizes(model)
    if chunks is None:
        chunks = kda_layers(model) * workload["batch"] * (
            workload["seq"] // c)
    tokens = chunks * c * steps               # layer-tokens the rule walks
    per = kda_rule_flops_per_token(model)
    acts = itemsize * 4 * heads * d           # q, k, v, o
    sums = 4 * heads * (d + 1)                # Y a channel, beta a head
    state = chunks * steps * heads * d * d * 4
    return {
        "fwd": (2.0 * tokens * per["fwd"],
                2.0 * (tokens * (acts + sums) + state)),
        "bwd": (1.0 * tokens * per["bwd"],
                tokens * (2 * acts + 2 * sums) + state)}


def latent_attention_flops_bytes(model: dict, workload: dict, steps: int,
                                 itemsize: int = 2) -> dict:
    """What the attention kernels' calls of `steps` steps need, the
    latent layers together: `{"fwd": (FLOPs, bytes), "bwd": (FLOPs,
    bytes)}`, reckoned as `families/joyai.py` reckons it (the name is
    what `layer_metrics/latent_attention_*` ask a family for):
    `flash_fwd` twice a layer and step, `flash_bwd_fused` once; FLOPs
    INSIDE the causal mask, forward 2 x (192 + 128) a score, backward 2
    x (3 x 192 + 2 x 128); bytes q, k (192 wide), v, o (128), the row
    log-sum-exp and delta once a call."""
    b, t = workload["batch"], workload["seq"]
    h, dv = model["num_attention_heads"], model["v_head_dim"]
    qk = model["qk_nope_head_dim"] + model["qk_rope_head_dim"]
    calls = sum(a == "latent" for a, _ in layer_kinds(model)) * steps
    scores = b * h * t * (t + 1) / 2
    rows = b * h * t
    return {
        "fwd": (2 * calls * scores * 2 * (qk + dv),
                2 * calls * rows * ((2 * qk + 2 * dv) * itemsize + 4)),
        "bwd": (calls * scores * 2 * (3 * qk + 2 * dv),
                calls * rows * ((4 * qk + 3 * dv) * itemsize + 8))}


def expert_matmul_flops_bytes(model: dict, rows: float, layer_steps: int,
                              itemsize: int = 2) -> tuple[float, float]:
    """What the grouped expert matmuls of `layer_steps` layer-steps need
    when `rows` assignments in all were really multiplied (the traced
    call's `moe_assignments_held`: padding not counted), forward, the
    rematerialised forward and the two backward products of each of the
    two grouped matmuls (gate|up: 2304 -> 2 x 1024, down: 1024 -> 2304):
    4 passes of 2 * rows * 3 D F operations. Bytes: every pass reads its
    rows in and writes them out once, and reads (the weight-gradient
    pass: writes, in float32) the 8 held experts' weights once a
    layer-step."""
    d, f = model["hidden_size"], model["moe_intermediate_size"]
    flops = 4 * 2.0 * rows * 3 * d * f
    row_bytes = itemsize * rows * ((d + 2 * f) + (f + d))
    weights = model["num_experts"] * 3 * d * f * layer_steps
    return flops, 4 * row_bytes + (3 * itemsize + 4) * weights


_GROUPS = {
    "kda_projections": ("kda_in", "kda_conv", "kda_beta", "kda_norm",
                        "kda_out"),
    "kda_A_log": ("kda_A_log",), "kda_dt_bias": ("kda_dt_bias",),
    "latent": ("wq_latent", "wkv_a", "kv_a_norm", "wkv_b", "wo_latent"),
    "dense": ("w1", "w2", "w3"), "router": ("router",),
    "experts": ("w_gate", "w_up", "w_down"),
    "shared": ("ws_gate", "ws_up", "ws_down"),
    "norms": ("norm1", "norm2")}


def group_norms(grads: dict) -> dict:
    """The norm of a gradient (a tree shaped as the parameters) by
    parameter group, floats: the KDA projections, the decay's low-rank
    path W_f (its half of `kda_down`, and `kda_f_up`), the gate's W_g,
    `A_log`, `dt_bias`, the latent mixer, the dense MLP, the router, the
    held experts, the shared expert, the layers' norms, the embedding,
    the head with the final norm."""
    import jax.numpy as jnp

    layers = grads["layers"]
    rank = layers["kda_down"].shape[-1] // 2
    parts = {group: [layers[name] for name in names]
             for group, names in _GROUPS.items()}
    parts.update(
        kda_W_f=[layers["kda_down"][..., :rank], layers["kda_f_up"]],
        kda_W_g=[layers["kda_down"][..., rank:], layers["kda_g_up"]],
        embed=[grads["embed"]], head=[grads["head"], grads["norm_f"]])
    return {group: float(jnp.sqrt(sum(
        (x.astype(jnp.float32) ** 2).sum() for x in leaves)))
        for group, leaves in parts.items()}
