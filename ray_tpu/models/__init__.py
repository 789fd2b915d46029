"""Model zoo, TPU-first: bfloat16 by default, logical-axis-annotated
parameters (DP/FSDP/TP/SP/EP shardings applied by the trainer),
remat-friendly blocks, pluggable attention (dense / ring / Ulysses).

Families: GPT-2 decoders (`gpt`), Llama-style decoders with
RoPE/SwiGLU/GQA (`llama`), MoE decoders (`moe_gpt`), latent-attention
decoders with sigmoid-routed experts (`kimi_k2`, serving only; imported
when first asked for), hybrid decoders of Kimi-Delta-Attention layers (one
state a sequence) beside latent attention with group-limited routing
(`ling_hybrid`, serving only; imported when first asked for), decoders
that generate by diffusion over blocks with softmax-routed experts
(`sdar_moe`, serving only; imported when first asked for), decoders of
power-retention layers that keep one large state a sequence and no paged
layer (`brumby`, serving only; imported when first asked for), decoders
of window layers with a learned sink beside full layers, the two kinds with
K/V head counts of their own and key rows wider than value rows, and
sigmoid-routed experts with no shared expert (`mimo_v2`, serving only;
imported when first asked for; `afmoe`, its neighbour with window and full
kinds of one shape, is reached through the engine's registry alone), ResNet
convnets (`resnet`), Vision Transformers (`vit`).
"""

from ray_tpu.models.bert import (BertConfig, BertEncoder,
                                 mask_tokens, mlm_loss)
from ray_tpu.models.gpt import GPT, GPTConfig
from ray_tpu.models.llama import Llama, LlamaConfig
from ray_tpu.models.moe_gpt import MoEGPT, MoEGPTConfig
from ray_tpu.models.resnet import ResNet, ResNetConfig
from ray_tpu.models.vit import ViT, ViTConfig

__all__ = [
    "BertConfig", "BertEncoder", "mask_tokens", "mlm_loss",
    "GPT", "GPTConfig", "Llama", "LlamaConfig", "MoEGPT", "MoEGPTConfig",
    "ResNet", "ResNetConfig", "ViT", "ViTConfig",
    "KimiK2", "KimiK2Config", "LingHybrid", "LingHybridConfig",
    "SdarMoe", "SdarMoeConfig", "Brumby", "BrumbyConfig",
    "MimoV2", "MimoV2Config",
]


def __getattr__(name):
    # the serving engine imports this package for every family: the
    # families it does not run cost it nothing
    for family, names in (("kimi_k2", ("KimiK2", "KimiK2Config")),
                          ("ling_hybrid", ("LingHybrid",
                                           "LingHybridConfig")),
                          ("sdar_moe", ("SdarMoe", "SdarMoeConfig")),
                          ("brumby", ("Brumby", "BrumbyConfig")),
                          ("mimo_v2", ("MimoV2", "MimoV2Config"))):
        if name == family or name in names:
            import importlib

            module = importlib.import_module(f"ray_tpu.models.{family}")
            return module if name == family else getattr(module, name)
    raise AttributeError(f"module {__name__!r} has no attribute {name!r}")
