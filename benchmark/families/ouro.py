"""Family ``ouro``: a LOOPED language model built from a layer pattern
by ``ray_tpu.models.decoder`` — every layer full causal attention (as
many key/value heads as query heads, rotate-half rotary positions, no
bias, no q/k norm) and a dense gated-SiLU MLP, each with an RMSNorm in
front AND one after it before the residual sum (sandwich norms); the
whole stack of layers walked ``total_ut_steps`` times a forward pass
with ONE set of weights, the final norm after every walk, an exit gate
(``Linear(hidden, 1)``, a sigmoid, float32) reading every walk's normed
output, an untied head over the whole vocabulary — trained on the
expected-exit loss (Ouro, "Scaling Latent Reasoning via Looped Language
Models", arXiv:2510.25741, the first-stage objective): the walks'
next-token cross-entropies weighted by the exit distribution the gate
gives a token, minus ``exit_beta`` times that distribution's entropy,
over one repeated batch of seeded random tokens. There is no expert
anywhere: the pattern decoder's dense case.

Configuration keys are the source's (``config.json`` of Ouro-2.6B);
``exit_beta`` is the entropy term's weight, ``remat`` whether the
backward pass makes each block again.
Workload keys: ``batch`` (sequences a step), ``seq`` (tokens a
sequence). The step is registered in the operator's stateful form: the
state is the loop's epoch counters (``decoder.state_init``)."""

from __future__ import annotations

import dataclasses

from benchmark.common import Pieces, key_seed, make_optimizer
from benchmark.manifest import ManifestError


def _decoder():
    """The program's decoder, or a ManifestError on a checkout from
    before the loop: said before any runtime starts (run.py exits 3 on
    it)."""
    from ray_tpu.models import decoder

    fields = {f.name for f in dataclasses.fields(decoder.DecoderConfig)}
    if not {"loops", "sandwich", "exit_gate"} <= fields:
        raise ManifestError("this checkout's ray_tpu.models.decoder walks "
                            "its layers once (no `loops`, no exit gate): it "
                            "cannot build the ouro family")
    return decoder


_decoder()


def model_cfg(model: dict):
    import jax.numpy as jnp

    decoder = _decoder()
    layers = model["num_hidden_layers"]
    if model["tie_word_embeddings"] or model["use_sliding_window"] \
            or model["rope_scaling"] or model["hidden_act"] != "silu" \
            or set(model["layer_types"][:layers]) != {"full_attention"}:
        raise ValueError(
            "the ouro family: an untied head, no window, unscaled rotary, "
            "gated SiLU, every layer full attention")
    extra = {k: model[k] for k in ("attn_block_q", "attn_block_k",
                                   "loss_chunk")
             if k in model}
    return decoder.DecoderConfig(
        vocab_size=model["vocab_size"], n_layers=layers,
        d_model=model["hidden_size"], n_heads=model["num_attention_heads"],
        n_kv_heads=model["num_key_value_heads"], head_dim=model["head_dim"],
        attention=("full",), mlp=("dense",), window=0, rotary=("full",),
        rope_theta=float(model["rope_theta"]),
        d_dense=model["intermediate_size"], activation="silu",
        tied_head=False, loops=model["total_ut_steps"], sandwich=True,
        exit_gate=True, exit_beta=model["exit_beta"],
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
    tokens = jax.random.randint(jax.random.key(key_seed(seed) + 1),
                                (batch, seq), 0, cfg.vocab_size)

    def loss_fn(p, s, b):
        return decoder.stateful_loss(p, s, b, cfg)

    # what `train.dispatch` carries of a step: loops, layer_passes,
    # head_passes
    loss_fn.step_facts = lambda b: decoder.step_facts(cfg, b.shape)
    return Pieces(
        # one jitted call: the weights are made on the device
        model_init=jax.jit(lambda key: (decoder.init(key, cfg),
                                        decoder.state_init(key, cfg))),
        loss_fn=loss_fn, optimizer=make_optimizer(model["optimizer"]),
        batch=tokens, stateful=True, rows=batch)


def layer_passes(model: dict) -> int:
    """Blocks a forward pass runs: every layer once a walk."""
    return model["total_ut_steps"] * model["num_hidden_layers"]


def forward_flops_per_token(model: dict, seq: int) -> dict:
    """Forward model FLOPs a token, by part: the matrix products only,
    EVERY walk's counted (`layer_passes` blocks, `total_ut_steps` passes
    of the head). Attention is counted INSIDE the causal mask (a token
    meets (seq + 1) / 2 keys on average); the gate's product is
    `total_ut_steps - 1` dots of the hidden size (the last walk's gate
    is not read). Norms, rotary, softmax, the sigmoid and the embedding
    lookup are not counted."""
    d, hd = model["hidden_size"], model["head_dim"]
    n_q, n_kv = model["num_attention_heads"], model["num_key_value_heads"]
    passes, walks = layer_passes(model), model["total_ut_steps"]
    return {
        "projections": passes * 2 * (2 * d * n_q * hd + 2 * d * n_kv * hd),
        "attention": passes * 2 * 2 * n_q * hd * (seq + 1) / 2,
        "mlp": passes * 2 * 3 * d * model["intermediate_size"],
        "vocabulary": walks * 2 * d * model["vocab_size"],
        "gate": (walks - 1) * 2 * d}


def flops_per_sample(model: dict, workload: dict) -> float:
    """Model FLOPs one sequence needs, forward and backward (3 x the
    forward), recomputation not counted: `seq` tokens through the
    blocks four times, the head and the gate over the seq - 1 positions
    that have a target, four times."""
    seq = workload["seq"]
    parts = forward_flops_per_token(model, seq)
    scored = parts.pop("vocabulary") + parts.pop("gate")
    return 3.0 * (seq * sum(parts.values()) + (seq - 1) * scored)


def attention_flops_bytes(model: dict, workload: dict, steps: int,
                          passes: int | None = None,
                          itemsize: int = 2) -> dict:
    """What the attention kernels' calls of `steps` steps need:
    `{"fwd": (FLOPs, bytes), "bwd": (FLOPs, bytes)}`, `passes` the
    blocks a step runs forward (the traced call's own `layer_passes`;
    default: the configuration's). `flash_fwd` runs once a pass in the
    forward and once more in the block's rematerialised copy (`remat`),
    `flash_bwd_fused` once. FLOPs are the products INSIDE the causal
    mask, seq (seq + 1) / 2 scores a head and sequence: forward 4 x
    head_dim a score (q k^T, p v), backward 10 x head_dim (k q^T, v
    do^T, p^T do, ds^T q, k^T ds); the count is of the mathematics, so a
    kernel that visits masked tiles reads lower. Bytes, each array once
    a call: forward q, o (query heads), k, v (key/value heads) and the
    float32 row log-sum-exp; backward q, do, dq, k, v, dk, dv, lse and
    delta. The products bound both on this chip."""
    b, seq, hd = workload["batch"], workload["seq"], model["head_dim"]
    n_q, n_kv = model["num_attention_heads"], model["num_key_value_heads"]
    calls = (layer_passes(model) if passes is None else passes) * steps
    forwards = 1 + bool(model["remat"])
    scores = b * n_q * seq * (seq + 1) / 2
    rows = b * seq
    return {
        "fwd": (forwards * calls * scores * 4 * hd,
                forwards * calls * rows * (
                    (2 * n_q + 2 * n_kv) * hd * itemsize + 4 * n_q)),
        "bwd": (calls * scores * 10 * hd,
                calls * rows * (
                    (3 * n_q + 4 * n_kv) * hd * itemsize + 8 * n_q))}
