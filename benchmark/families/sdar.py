"""Family ``sdar``: a decoder built from a layer pattern by
``ray_tpu.models.decoder`` — every layer full grouped-query attention
(an RMSNorm over each head of q and k, then rotate-half rotary
positions) and top-k routed gated-SiLU experts without dropped tokens
over the HELD share of the experts (softmax over the chosen logits, no
shared expert), RMSNorm, an untied head over a slice of the vocabulary —
trained by BLOCK DIFFUSION (SDAR, arXiv:2510.06303; the training pass is
BD3-LM's, arXiv:2503.09573): the sequence is cut into blocks of
``block_length`` tokens, each block draws a masking rate and is noised
at it, and one pass over ``[x_0 ; x_t]`` (the clean sequence, then its
noised copy: twice ``seq`` rows, under the block-diffusion attention
mask of ``ray_tpu/ops/attention.py``) predicts every masked token; the
loss is the masked tokens' cross-entropy weighted by 1 / rate, over one
repeated batch of seeded random tokens drawn from the slice WITHOUT its
last id, which is the mask token. The noise is drawn inside the step,
fresh every step, from (the seed, ``noise_step``).

Configuration keys are the source's (``config.json`` of
SDAR-30B-A3B-Chat); ``num_experts`` counts the experts held here,
``router_outputs`` all of them, ``held_experts_first`` the first one
held, ``block_length`` the diffusion block. Workload keys: ``batch``
(DATA sequences a step), ``seq`` (data tokens a sequence: the model
sees twice as many rows). The step is registered in the operator's
stateful form: the state is the routing and noise counters and the
noise's seed and step (``decoder.state_init``)."""

from __future__ import annotations

import dataclasses

from benchmark.common import Pieces, key_seed, make_optimizer
from benchmark.manifest import ManifestError


def _decoder():
    """The program's decoder, or a ManifestError on a checkout from
    before the block-diffusion objective: said before any runtime starts
    (run.py exits 3 on it)."""
    from ray_tpu.models import decoder

    fields = {f.name for f in dataclasses.fields(decoder.DecoderConfig)}
    if not {"diffusion_block", "head_rows"} <= fields:
        raise ManifestError("this checkout's ray_tpu.models.decoder has no "
                            "block-diffusion objective (`diffusion_block`): "
                            "it cannot build the sdar family")
    return decoder


_decoder()


def model_cfg(model: dict):
    import jax.numpy as jnp

    decoder = _decoder()
    if model["attention_bias"] or model["tie_word_embeddings"] \
            or model["mlp_only_layers"] or model["decoder_sparse_step"] != 1 \
            or model["use_sliding_window"] or model["rope_scaling"] \
            or not model["norm_topk_prob"] or model["hidden_act"] != "silu":
        raise ValueError(
            "the sdar family: no bias, an untied head, every layer sparse, "
            "no window, unscaled rotary, weights normalised over the chosen "
            "experts, gated SiLU")
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
        diffusion_block=model["block_length"],
        rms_eps=model["rms_norm_eps"], init_std=model["init_std"],
        dtype=getattr(jnp, model["compute_dtype"]), remat=model["remat"],
        **extra)


def pieces(model: dict, workload: dict, seed: int) -> Pieces:
    import jax

    decoder = _decoder()
    cfg = model_cfg(model)
    batch, seq = workload["batch"], workload["seq"]
    if seq > model["max_position_embeddings"]:
        raise ValueError(f"seq {seq} > max_position_embeddings")
    # the slice without its last id: the mask token is never data
    tokens = jax.random.randint(jax.random.key(key_seed(seed) + 1),
                                (batch, seq), 0, cfg.vocab_size - 1)

    def loss_fn(p, s, b):
        return decoder.stateful_loss(p, s, b, cfg)

    # what `train.dispatch` carries of a step: diffusion_block,
    # diffusion_rows, attention_tiles_visited / _plane
    loss_fn.step_facts = lambda b: decoder.step_facts(cfg, b.shape)
    return Pieces(
        # one jitted call: the weights are made on the device
        model_init=jax.jit(lambda key: (decoder.init(key, cfg),
                                        decoder.state_init(key, cfg))),
        loss_fn=loss_fn, optimizer=make_optimizer(model["optimizer"]),
        batch=tokens, stateful=True, rows=batch)


def moe_layers(model: dict) -> int:
    return model["num_hidden_layers"]


def scores_in_mask(seq: int, block: int) -> float:
    """Scores inside the block-diffusion mask, one head of one sequence
    of `seq` data tokens (2 seq rows): the clean half's block-causal
    triangle, the noised half's strictly earlier clean blocks, and its
    block diagonal — seq ** 2 + seq x block of the plane's 4 seq ** 2."""
    return float(seq * seq + seq * block)


def forward_flops_per_row(model: dict, seq: int) -> dict:
    """Forward model FLOPs of one ROW through the model (a data token
    is two rows: its clean copy and its noised one), by part: the matrix
    products only. Attention is counted INSIDE the block-diffusion mask
    (a row meets seq / 2 + block / 2 keys on average); the experts at
    their expectation under uniform routing, top_k x held / outputs
    experts a row (8 x 16 / 128: one), and said so; the vocabulary is
    the slice's, on the NOISED half of the rows only (the clean half
    needs no logits): half its product a row. Norms, rotary, softmax and
    the embedding lookup are not counted."""
    d, hd = model["hidden_size"], model["head_dim"]
    n_q, n_kv = model["num_attention_heads"], model["num_key_value_heads"]
    layers = model["num_hidden_layers"]
    held_share = (model["num_experts_per_tok"] * model["num_experts"]
                  / model["router_outputs"])
    keys = scores_in_mask(seq, model["block_length"]) / (2 * seq)
    return {
        "projections": layers * 2 * (2 * d * n_q * hd + 2 * d * n_kv * hd),
        "routers": layers * 2 * d * model["router_outputs"],
        "attention": layers * 2 * 2 * n_q * hd * keys,
        "experts": layers * held_share * 2 * 3 * d
        * model["moe_intermediate_size"],
        "vocabulary": 2 * d * model["vocab_size"] / 2}


def flops_per_sample(model: dict, workload: dict) -> float:
    """Model FLOPs one DATA sequence needs, forward and backward (3 x
    the forward), recomputation not counted: 2 seq rows through the
    blocks, the head over seq of them."""
    seq = workload["seq"]
    return 3.0 * 2 * seq * sum(forward_flops_per_row(model, seq).values())


def diffusion_attention_flops_bytes(model: dict, workload: dict, steps: int,
                                    itemsize: int = 2) -> dict:
    """What the attention kernels' calls of `steps` steps need:
    `{"fwd": (FLOPs, bytes), "bwd": (FLOPs, bytes)}`. `flash_fwd` runs
    twice a layer and step (the forward pass and its rematerialised
    copy), `flash_bwd_fused` once. FLOPs are the products INSIDE the
    block-diffusion mask, `scores_in_mask` a head and sequence: forward
    4 x head_dim a score (q k^T, p v), backward 10 x head_dim (k q^T,
    v do^T, p^T do, ds^T q, k^T ds). The count is of the mathematics,
    whatever implements it: a kernel that walked the causal half of the
    plane and masked would read half as high. Bytes, each array once a
    call over the 2 seq rows: forward q and o with the query heads, k
    and v with the key/value heads, and the float32 row log-sum-exp;
    backward q, do, dq (query heads), k, v, dk, dv (key/value heads),
    lse and delta. The products bound both on this chip (forward about
    1 800 FLOP a byte, backward 2 700, against the chip's 240)."""
    b, seq, hd = workload["batch"], workload["seq"], model["head_dim"]
    n_q, n_kv = model["num_attention_heads"], model["num_key_value_heads"]
    calls = model["num_hidden_layers"] * steps
    scores = b * n_q * scores_in_mask(seq, model["block_length"])
    rows = b * 2 * seq
    return {
        "fwd": (2 * calls * scores * 4 * hd,
                2 * calls * rows * (
                    (2 * n_q + 2 * n_kv) * hd * itemsize + 4 * n_q)),
        "bwd": (calls * scores * 10 * hd,
                calls * rows * (
                    (3 * n_q + 4 * n_kv) * hd * itemsize + 8 * n_q))}


def expert_matmul_flops_bytes(model: dict, rows: float, layer_steps: int,
                              itemsize: int = 2) -> tuple[float, float]:
    """What the grouped expert matmuls of `layer_steps` layer-steps need
    when `rows` assignments in all were really multiplied (the traced
    call's `moe_assignments_held`: padding not counted): the first
    expert family's reckoning (4 passes of 2 * rows * 3 D F operations;
    rows in and out once a pass, the held experts' weights once a
    layer-step) at this family's widths, D 2048, F 768, 16 held."""
    from benchmark.families import smallthinker

    return smallthinker.expert_matmul_flops_bytes(
        {"hidden_size": model["hidden_size"],
         "moe_ffn_hidden_size": model["moe_intermediate_size"],
         "moe_num_primary_experts": model["num_experts"]},
        rows, layer_steps, itemsize)
