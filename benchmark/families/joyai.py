"""Family ``joyai``: a decoder built from a layer pattern by
``ray_tpu.models.decoder`` — every layer latent attention (MLA: low-rank
query and key-value projections with an RMSNorm on each latent, 192-wide
queries and keys of which 64 turn, one rotary key shared by all heads,
128-wide values), a dense gated-SiLU MLP in the leading layer and after
it top-k routed gated-SiLU experts without dropped tokens over the HELD
share of the experts (sigmoid scores, a selection bias that is model
state, weights normalised over the chosen times a scaling factor) beside
one shared expert, an untied head over a slice of the vocabulary, and a
multi-token-prediction block that shares embedding and head — trained on
the two-term loss over one repeated batch of seeded random tokens drawn
from the slice.

Configuration keys are the source's (``config.json`` of
JoyAI-LLM-Flash); ``n_routed_experts`` counts the experts held here,
``router_outputs`` all of them, ``held_experts_first`` the first one
held. Workload keys: ``batch`` (sequences a step), ``seq`` (tokens a
sequence). The step is registered in the operator's stateful form: the
state is the routing counters and the selection bias
(``decoder.state_init``)."""

from __future__ import annotations

from benchmark.common import Pieces, key_seed, make_optimizer
from benchmark.manifest import ManifestError


def _decoder():
    """The program's decoder, or a ManifestError on a checkout from
    before the latent mixer: said before any runtime starts (run.py
    exits 3 on it)."""
    from ray_tpu.models import decoder

    if "latent" not in decoder.MIXER_KINDS:
        raise ManifestError("this checkout's ray_tpu.models.decoder has no "
                            "`latent` mixer: it cannot build the joyai "
                            "family")
    return decoder


_decoder()


def layer_kinds(model: dict) -> list[tuple[str, str]]:
    """(mixer, mlp) of every main layer run, in the decoder's names."""
    return [("latent", "dense" if l < model["first_k_dense_replace"]
             else "experts") for l in range(model["num_hidden_layers"])]


def model_cfg(model: dict):
    import jax.numpy as jnp

    decoder = _decoder()
    if model["attention_bias"] or model["rope_scaling"] is not None \
            or not model["rope_interleave"] \
            or not model["norm_topk_prob"] or model["n_group"] != 1 \
            or model["topk_group"] != 1 or model["moe_layer_freq"] != 1 \
            or (model["scoring_func"], model["topk_method"],
                model["hidden_act"]) != ("sigmoid", "noaux_tc", "silu") \
            or model["tie_word_embeddings"] \
            or model["num_nextn_predict_layers"] > 1:
        raise ValueError(
            "the joyai family: no bias or rope scaling in the attention, "
            "interleaved rotary pairs, "
            "sigmoid scores with a selection bias and no group limit, "
            "weights normalised over the chosen, experts in every layer "
            "after the leading ones, SiLU, an untied head, at most one "
            "MTP block")
    kinds, lead = layer_kinds(model), model["first_k_dense_replace"]
    extra = {k: model[k] for k in ("attn_block_q", "attn_block_k", "gmm_tile",
                                   "loss_chunk")
             if k in model}
    return decoder.DecoderConfig(
        vocab_size=model["vocab_size"], n_layers=len(kinds),
        d_model=model["hidden_size"], n_heads=model["num_attention_heads"],
        n_kv_heads=model["num_key_value_heads"],
        head_dim=model["qk_head_dim"],
        lead_attention=tuple(a for a, _ in kinds[:lead]),
        lead_mlp=tuple(m for _, m in kinds[:lead]),
        attention=("latent",), mlp=("experts",), window=0, rotary=(),
        rope_theta=float(model["rope_theta"]),
        q_lora_rank=model["q_lora_rank"], kv_lora_rank=model["kv_lora_rank"],
        qk_nope_dim=model["qk_nope_head_dim"],
        qk_rope_dim=model["qk_rope_head_dim"],
        v_head_dim=model["v_head_dim"],
        n_experts=model["router_outputs"],
        top_k=model["num_experts_per_tok"],
        d_expert=model["moe_intermediate_size"],
        d_shared=model["n_shared_experts"] * model["moe_intermediate_size"],
        routed_scale=model["routed_scaling_factor"],
        d_dense=model["intermediate_size"],
        held=(model["held_experts_first"], model["n_routed_experts"]),
        router_input="mlp", routing="sigmoid_bias",
        bias_rate=model["expert_bias_update_rate"], activation="silu",
        tied_head=False, rms_eps=model["rms_norm_eps"],
        init_std=model["init_std"], mtp=model["num_nextn_predict_layers"],
        mtp_weight=model["mtp_loss_weight"],
        dtype=getattr(jnp, model["compute_dtype"]), remat=model["remat"],
        **extra)


def pieces(model: dict, workload: dict, seed: int) -> Pieces:
    import jax

    decoder = _decoder()
    cfg = model_cfg(model)
    batch, seq = workload["batch"], workload["seq"]
    if seq > model["max_position_embeddings"]:
        raise ValueError(f"seq {seq} > max_position_embeddings")
    tokens = jax.random.randint(jax.random.key(key_seed(seed) + 1),
                                (batch, seq), 0, cfg.vocab_size)
    return Pieces(
        # one jitted call: the weights are made on the device
        model_init=jax.jit(lambda key: (decoder.init(key, cfg),
                                        decoder.state_init(key, cfg))),
        loss_fn=lambda p, s, b: decoder.stateful_loss(p, s, b, cfg),
        optimizer=make_optimizer(model["optimizer"]),
        batch=tokens, stateful=True, rows=batch)


def blocks(model: dict) -> int:
    """The blocks with latent attention: the main layers and the MTP's."""
    return model["num_hidden_layers"] + model["num_nextn_predict_layers"]


def moe_layers(model: dict) -> int:
    """The layers that route, the MTP block's included."""
    return sum(m == "experts" for _, m in layer_kinds(model)) \
        + model["num_nextn_predict_layers"]


def forward_flops_per_token(model: dict, seq: int) -> dict:
    """Forward model FLOPs a token, by part: the matrix products only.
    Attention is counted INSIDE the causal mask, 2 x (192 + 128) a score;
    the routed experts at their expectation under uniform routing,
    top_k x held / outputs experts a token (half an expert), and said
    so; the vocabulary is the slice's, taken once by each head. Norms,
    rotary, softmax, gates and the embedding lookups are not counted."""
    d, h = model["hidden_size"], model["num_attention_heads"]
    qk, dv = model["qk_head_dim"], model["v_head_dim"]
    n, n_moe, mtp = blocks(model), moe_layers(model), \
        model["num_nextn_predict_layers"]
    expert = 2 * 3 * d * model["moe_intermediate_size"]
    held_share = (model["num_experts_per_tok"] * model["n_routed_experts"]
                  / model["router_outputs"])
    return {
        "latent_projections": n * 2 * (
            d * model["q_lora_rank"] + model["q_lora_rank"] * h * qk
            + d * (model["kv_lora_rank"] + model["qk_rope_head_dim"])
            + model["kv_lora_rank"] * h * (model["qk_nope_head_dim"] + dv)
            + h * dv * d),
        "latent_attention": n * 2 * (qk + dv) * h * (seq + 1) / 2,
        "dense_mlp": model["first_k_dense_replace"] * 2 * 3 * d
        * model["intermediate_size"],
        "shared_experts": n_moe * model["n_shared_experts"] * expert,
        "routed_experts": n_moe * (held_share * expert
                                   + 2 * d * model["router_outputs"]),
        "mtp_join": mtp * 2 * 2 * d * d,
        "vocabulary": (1 + mtp) * 2 * d * model["vocab_size"]}


def flops_per_sample(model: dict, workload: dict) -> float:
    """Model FLOPs one sequence needs, forward and backward (3 x the
    forward), recomputation not counted; each head's vocabulary product
    for the seq - 1 (seq - 2) positions that have a target counted as
    seq - 1."""
    seq = workload["seq"]
    part = forward_flops_per_token(model, seq)
    vocabulary = part.pop("vocabulary")
    return 3.0 * (seq * sum(part.values()) + (seq - 1) * vocabulary)


def latent_attention_flops_bytes(model: dict, workload: dict, steps: int,
                                 itemsize: int = 2) -> dict:
    """What the attention kernels' calls of `steps` steps need:
    `{"fwd": (FLOPs, bytes), "bwd": (FLOPs, bytes)}`. `flash_fwd` runs
    twice a block and step (the forward pass and its rematerialised
    copy), `flash_bwd_fused` once. FLOPs are the products INSIDE the
    causal mask, T (T + 1) / 2 scores a head and sequence: forward
    2 x (192 + 128) a score (q k^T, p v), backward 2 x (3 x 192 + 2 x
    128) (k q^T, v do^T, p^T do, ds^T q, k^T ds). Bytes, each array once
    a call: forward q, k (192 wide), v, o (128) and the float32 row
    log-sum-exp (counted on both calls; only the second writes it: 4 of
    1284 bytes a row); backward q, k, dq, dk (192), v, do, dv (128), lse
    and delta. The products bound both on this chip (forward about 2 000
    FLOP a byte at 8192, backward 2 900, against the chip's 240)."""
    b, t = workload["batch"], workload["seq"]
    h, qk, dv = model["num_attention_heads"], model["qk_head_dim"], \
        model["v_head_dim"]
    calls = blocks(model) * steps
    scores = b * h * t * (t + 1) / 2
    rows = b * h * t
    return {
        "fwd": (2 * calls * scores * 2 * (qk + dv),
                2 * calls * rows * ((2 * qk + 2 * dv) * itemsize + 4)),
        "bwd": (calls * scores * 2 * (3 * qk + 2 * dv),
                calls * rows * ((4 * qk + 3 * dv) * itemsize + 8))}
