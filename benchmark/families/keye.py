"""Family ``keye``: a decoder built from a layer pattern by
``ray_tpu.models.decoder`` — every layer full grouped-query attention
(an RMSNorm over each head of q and k, then rotate-half rotary
positions) over the keys a LEARNED INDEXER selects (DeepSeek Sparse
Attention's lightning indexer at ``sa_config``'s sizes: 16 heads of 64
and one key head score every earlier token, each query keeps its
``topk`` best, ``ops/sparse_index.py``; the main attention runs over
those alone, ``ops.flash_attention(..., selected=)``), and top-k routed
gated-SiLU experts without dropped tokens over the HELD share of the
experts (softmax over the chosen logits, no shared expert), RMSNorm, an
untied head over a slice of the vocabulary — trained on next-token
cross-entropy PLUS the indexer's loss (its scores' softmax against the
attention it steered, each term reaching its own leaves alone), over
one repeated batch of seeded random tokens drawn from the slice.

Configuration keys are the source's (``config.json`` of
Keye-VL-2.0-30B-A3B; the vision tower is not run); ``num_experts``
counts the experts held here, ``router_outputs`` all of them,
``held_experts_first`` the first one held; ``sa_config`` and
``rope_scaling`` are carried whole; ``embed_init_std`` (the embedding's
own start beside ``init_std``) and the optimizer's ``warmup_steps`` are
the training recipe's, applied here. Workload keys: ``batch`` (sequences
a step), ``seq`` (tokens a sequence). The step is registered in the
operator's stateful form: the state is the routing and indexer counters
(``decoder.state_init``)."""

from __future__ import annotations

import dataclasses

from benchmark.common import Pieces, key_seed
from benchmark.manifest import ManifestError


def _decoder():
    """The program's decoder, or a ManifestError on a checkout from
    before learned sparse attention: said before any runtime starts
    (run.py exits 3 on it)."""
    from ray_tpu.models import decoder

    fields = {f.name for f in dataclasses.fields(decoder.DecoderConfig)}
    if "index_topk" not in fields:
        raise ManifestError("this checkout's ray_tpu.models.decoder has no "
                            "learned sparse attention (`index_topk`): it "
                            "cannot build the keye family")
    return decoder


_decoder()


def model_cfg(model: dict):
    import jax.numpy as jnp

    decoder = _decoder()
    sa, scaling = model["sa_config"], model["rope_scaling"]
    if model["attention_bias"] or model["tie_word_embeddings"] \
            or model["mlp_only_layers"] or model["decoder_sparse_step"] != 1 \
            or model["use_sliding_window"] \
            or scaling["rope_type"] != "default" \
            or 2 * sum(scaling["mrope_section"]) != model["head_dim"] \
            or sa["indexer_num_kv_heads"] != 1 \
            or not model["norm_topk_prob"] or model["hidden_act"] != "silu":
        raise ValueError(
            "the keye family: no bias, an untied head, every layer sparse, "
            "no window, unscaled rotary whose three sections fill half a "
            "head, one indexer key head, weights normalised over the "
            "chosen experts, gated SiLU")
    extra = {k: model[k] for k in ("attn_block_q", "attn_block_k", "gmm_tile",
                                   "loss_chunk")
             if k in model}
    return decoder.DecoderConfig(
        vocab_size=model["vocab_size"], n_layers=model["num_hidden_layers"],
        d_model=model["hidden_size"], n_heads=model["num_attention_heads"],
        n_kv_heads=model["num_key_value_heads"], head_dim=model["head_dim"],
        attention=("full",), mlp=("experts",), window=0,
        rotary=("full",), qk_norm=("full",),
        rope_theta=float(model["rope_theta"]),
        n_experts=model["router_outputs"],
        top_k=model["num_experts_per_tok"],
        d_expert=model["moe_intermediate_size"],
        held=(model["held_experts_first"], model["num_experts"]),
        router_input="mlp", routing="softmax_topk", activation="silu",
        tied_head=False, head_rows=True, count_rows=True,
        index_topk=sa["topk"], index_heads=sa["indexer_num_heads"],
        index_dim=sa["indexer_head_dim"],
        index_loss_weight=model["index_loss_weight"],
        index_dtype=getattr(jnp, model["index_dtype"]),
        rms_eps=model["rms_norm_eps"], init_std=model["init_std"],
        dtype=getattr(jnp, model["compute_dtype"]), remat=model["remat"],
        **extra)


def optimizer(spec: dict):
    """AdamW whose rate climbs linearly to `learning_rate` over
    `warmup_steps` steps (step n runs at (n + 1) / warmup_steps of it)
    and stays there."""
    import jax.numpy as jnp
    import optax

    if spec["name"] != "adamw":
        raise ValueError("the keye family warms up AdamW alone")
    peak, warmup = spec["learning_rate"], spec["warmup_steps"]
    return optax.adamw(
        lambda count: peak * jnp.minimum(1.0, (count + 1) / warmup))


def pieces(model: dict, workload: dict, seed: int) -> Pieces:
    import jax

    decoder = _decoder()
    cfg = model_cfg(model)
    batch, seq = workload["batch"], workload["seq"]
    if seq > model["max_position_embeddings"]:
        raise ValueError(f"seq {seq} > max_position_embeddings")
    tokens = jax.random.randint(jax.random.key(key_seed(seed) + 1),
                                (batch, seq), 0, cfg.vocab_size)

    def loss_fn(p, s, b):
        return decoder.stateful_loss(p, s, b, cfg)

    # what `train.dispatch` carries of a step: index_topk, index_rows,
    # index_tile, attention_tiles_walked / _unmasked
    loss_fn.step_facts = lambda b: decoder.step_facts(cfg, b.shape)

    def init(key):
        params = decoder.init(key, cfg)   # every matrix at init_std
        embed = params["embed"] * (model["embed_init_std"]
                                   / model["init_std"])
        return dict(params, embed=embed), decoder.state_init(key, cfg)

    return Pieces(
        # one jitted call: the weights are made on the device
        model_init=jax.jit(init),
        loss_fn=loss_fn, optimizer=optimizer(model["optimizer"]),
        batch=tokens, stateful=True, rows=batch)


def moe_layers(model: dict) -> int:
    return model["num_hidden_layers"]


def pairs(seq: int, topk: int) -> tuple[int, int]:
    """(causal pairs, selected pairs) of one sequence: query t has
    t + 1 keys at or before it and keeps min(t + 1, topk) of them."""
    kept = min(seq, topk)
    return (seq * (seq + 1) // 2,
            kept * (kept + 1) // 2 + (seq - kept) * topk)


def forward_flops_per_token(model: dict, seq: int) -> dict:
    """Forward model FLOPs of one token, by part: the matrix products
    only. The main attention is counted over the SELECTED pairs (a
    query meets min(t + 1, topk) keys: the model's work, whatever walks
    them), the index scores over every CAUSAL pair (the indexer looks
    at all of them to choose), the experts at their expectation under
    uniform routing (top_k x held / outputs experts a token: one).
    Norms, rotary, softmax, the threshold's compares, the indexer's loss
    (which reads the probabilities the attention has) and the embedding
    lookup are not counted."""
    d, hd = model["hidden_size"], model["head_dim"]
    n_q, n_kv = model["num_attention_heads"], model["num_key_value_heads"]
    sa, layers = model["sa_config"], model["num_hidden_layers"]
    heads, dim = sa["indexer_num_heads"], sa["indexer_head_dim"]
    held_share = (model["num_experts_per_tok"] * model["num_experts"]
                  / model["router_outputs"])
    causal, selected = pairs(seq, sa["topk"])
    return {
        "projections": layers * 2 * (2 * d * n_q * hd + 2 * d * n_kv * hd),
        "index_projections": layers * 2 * d * (heads * dim + dim + heads),
        "index_scores": layers * 2 * heads * dim * causal / seq,
        "routers": layers * 2 * d * model["router_outputs"],
        "attention": layers * 2 * 2 * n_q * hd * selected / seq,
        "experts": layers * held_share * 2 * 3 * d
        * model["moe_intermediate_size"],
        "vocabulary": 2 * d * model["vocab_size"]}


def flops_per_sample(model: dict, workload: dict) -> float:
    """Model FLOPs one sequence needs, forward and backward (3 x the
    forward), recomputation not counted."""
    seq = workload["seq"]
    return 3.0 * seq * sum(forward_flops_per_token(model, seq).values())


def index_flops_bytes(model: dict, workload: dict, steps: int,
                      itemsize: int = 2) -> tuple[float, float]:
    """What the `index_scores` kernel's calls of `steps` steps need:
    (FLOPs, bytes). It runs twice a layer and step (the forward pass
    and its rematerialised copy). FLOPs: the 16 products of width 64 a
    CAUSAL pair, 2 x 16 x 64 each (the ReLU and the weighted sum are
    not counted). Bytes, once a call: q_I, k_I and w read, and the
    scores it writes, float32 over the whole [T, T] plane a sequence
    (a strip at a time; the tiles above the diagonal are written as
    -inf): the write bounds it on this chip."""
    b, seq = workload["batch"], workload["seq"]
    sa = model["sa_config"]
    heads, dim = sa["indexer_num_heads"], sa["indexer_head_dim"]
    calls = 2 * model["num_hidden_layers"] * steps
    causal, _ = pairs(seq, sa["topk"])
    return (calls * b * causal * 2 * heads * dim,
            calls * b * (seq * ((heads + 1) * dim * itemsize + 4 * heads)
                         + 4 * seq * seq))


def attention_flops_bytes(model: dict, workload: dict, steps: int,
                          itemsize: int = 2) -> dict:
    """What the attention kernels' calls of `steps` steps need:
    `{"fwd": (FLOPs, bytes), "bwd": (FLOPs, bytes)}`. `flash_fwd` runs
    twice a layer and step, `flash_bwd_fused` once. FLOPs are the
    products over the SELECTED pairs: forward 4 x head_dim a pair and
    head (q k^T, p v), backward 10 x head_dim (k q^T, v do^T, p^T do,
    ds^T q, k^T ds). The count is of the mathematics: a kernel that
    walks every causal tile for the 23 % of its pairs that are selected
    reads under 23 % times its efficiency. Bytes, each array once a
    call: forward q and o with the query heads, k and v with the
    key/value heads, the float32 row log-sum-exp and the int8 plane;
    backward q, do, dq, k, v, dk, dv, lse, delta and the plane."""
    b, seq, hd = workload["batch"], workload["seq"], model["head_dim"]
    n_q, n_kv = model["num_attention_heads"], model["num_key_value_heads"]
    calls = model["num_hidden_layers"] * steps
    _, selected = pairs(seq, model["sa_config"]["topk"])
    scores = b * n_q * selected
    rows, plane = b * seq, b * seq * seq
    return {
        "fwd": (2 * calls * scores * 4 * hd,
                2 * calls * (rows * ((2 * n_q + 2 * n_kv) * hd * itemsize
                                     + 4 * n_q) + plane)),
        "bwd": (calls * scores * 10 * hd,
                calls * (rows * ((3 * n_q + 4 * n_kv) * hd * itemsize
                                 + 8 * n_q) + plane))}


def expert_matmul_flops_bytes(model: dict, rows: float, layer_steps: int,
                              itemsize: int = 2) -> tuple[float, float]:
    """What the grouped expert matmuls of `layer_steps` layer-steps need
    when `rows` assignments in all were really multiplied: the first
    expert family's reckoning at this family's widths, D 2048, F 768,
    16 held."""
    from benchmark.families import smallthinker

    return smallthinker.expert_matmul_flops_bytes(
        {"hidden_size": model["hidden_size"],
         "moe_ffn_hidden_size": model["moe_intermediate_size"],
         "moe_num_primary_experts": model["num_experts"]},
        rows, layer_steps, itemsize)
