"""Family ``gpt``: a decoder-only transformer as ``ray_tpu.models.
transformer`` builds it (pre-norm blocks, learned positions, tied
embedding, tanh GELU, causal flash attention), trained on next-token
cross-entropy over one repeated batch of seeded random tokens.

Configuration keys are the Hugging Face GPT-2 ones. Workload keys:
``batch`` (sequences a step) and ``seq`` (tokens a sequence)."""

from __future__ import annotations

from benchmark.common import Pieces, key_seed, make_optimizer


def _model_cfg(model: dict):
    import jax.numpy as jnp

    from ray_tpu.models import transformer

    if model["activation_function"] != "gelu_new":
        raise ValueError("the gpt family computes the tanh GELU only")
    if model["layer_norm_epsilon"] != 1e-5:
        raise ValueError("the program's layernorm has eps 1e-5 built in")
    return transformer.TransformerConfig(
        vocab_size=model["vocab_size"], n_layers=model["n_layer"],
        n_heads=model["n_head"], d_model=model["n_embd"],
        d_ff=model["n_inner"], max_seq=model["n_positions"],
        dtype=getattr(jnp, model["compute_dtype"]), causal=True,
        tie_embeddings=model["tie_word_embeddings"], remat=model["remat"])


def pieces(model: dict, workload: dict, seed: int) -> Pieces:
    import jax

    from ray_tpu.models import transformer

    cfg = _model_cfg(model)
    batch, seq = workload["batch"], workload["seq"]
    if seq > cfg.max_seq:
        raise ValueError(f"seq {seq} > n_positions {cfg.max_seq}")
    tokens = jax.random.randint(jax.random.key(key_seed(seed) + 1),
                                (batch, seq), 0, cfg.vocab_size)
    return Pieces(
        # one jitted call: the weights are made on the device
        model_init=jax.jit(lambda key: transformer.init(key, cfg)),
        loss_fn=lambda p, b: transformer.loss_fn(p, b, cfg),
        optimizer=make_optimizer(model["optimizer"]),
        batch=tokens, stateful=False, rows=batch)


def flops_per_sample(model: dict, workload: dict) -> float:
    """Model FLOPs one sequence needs, forward and backward (3 x the
    forward matmuls), recomputation not counted. Per token: the four
    block matmuls (qkv, output, two of the MLP), the causal half of the
    two attention matmuls, and the output projection onto the
    vocabulary for the seq-1 positions that have a target. Embedding
    lookups, norms and softmax are not counted."""
    d, f, layers = model["n_embd"], model["n_inner"], model["n_layer"]
    seq, vocab = workload["seq"], model["vocab_size"]
    block = 2 * (3 * d * d + d * d + 2 * d * f)     # per token per layer
    attn = 2 * 2 * (seq / 2) * d                    # QK^T and PV, causal
    forward = seq * layers * (block + attn) + (seq - 1) * 2 * d * vocab
    return 3.0 * forward
