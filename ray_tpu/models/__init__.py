"""JAX-native model zoo: the workloads the reference trains/serves
(ResNet via RaySGD, BERT fine-tune, GPT-2 serving, ViT sweeps — BASELINE.json
configs), built functional + sharding-annotated for pjit meshes."""

from ray_tpu.models import (bert, decoder, moe_transformer, resnet,
                            transformer, vit)

__all__ = ["bert", "decoder", "moe_transformer", "resnet", "transformer",
           "vit"]
