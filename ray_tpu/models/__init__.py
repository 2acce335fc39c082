"""Model zoo (SURVEY.md §2.2 P10).

Registry mirrors what reference users reach for through HF/torch in Ray
Train/Serve/RLlib examples, re-implemented TPU-first.
"""
from .llama import Llama, LlamaConfig
from .gpt2 import GPT2, GPT2Config
from .mixtral import Mixtral, MixtralConfig
from .latent_moe import LatentMoE, LatentMoEConfig
from .hybrid import Hybrid, HybridConfig
from .vit import ViT, ViTConfig
from .clip import CLIP, CLIPConfig, contrastive_loss
from .mlp import MLP, MLPConfig, ResNetLite

_REGISTRY = {
    "llama3-8b": lambda **kw: Llama(LlamaConfig.llama3_8b(**kw)),
    "llama3-1b": lambda **kw: Llama(LlamaConfig.llama3_1b(**kw)),
    "llama-debug": lambda **kw: Llama(LlamaConfig.debug(**kw)),
    "gpt2": lambda **kw: GPT2(GPT2Config.small(**kw)),
    "gpt2-medium": lambda **kw: GPT2(GPT2Config.medium(**kw)),
    "gpt2-large": lambda **kw: GPT2(GPT2Config.large(**kw)),
    "gpt2-debug": lambda **kw: GPT2(GPT2Config.debug(**kw)),
    "mixtral-8x7b": lambda **kw: Mixtral(MixtralConfig.mixtral_8x7b(**kw)),
    "olmoe-1b-7b": lambda **kw: Mixtral(MixtralConfig.olmoe_1b_7b(**kw)),
    "mixtral-debug": lambda **kw: Mixtral(MixtralConfig.debug(**kw)),
    "sarvam-105b": lambda **kw: LatentMoE(LatentMoEConfig.sarvam_105b(**kw)),
    "latent-moe-debug": lambda **kw: LatentMoE(LatentMoEConfig.debug(**kw)),
    "xing4.0-29b-a4b": lambda **kw: LatentMoE(
        LatentMoEConfig.xing4_29b_a4b(**kw)),
    "xing-debug": lambda **kw: LatentMoE(LatentMoEConfig.xing_debug(**kw)),
    "olmo-hybrid-7b": lambda **kw: Hybrid(HybridConfig.olmo_hybrid_7b(**kw)),
    "hybrid-debug": lambda **kw: Hybrid(HybridConfig.debug(**kw)),
    "lfm2-24b-a2b": lambda **kw: Hybrid(HybridConfig.lfm2_24b_a2b(**kw)),
    "lfm2-moe-debug": lambda **kw: Hybrid(HybridConfig.lfm2_debug(**kw)),
    "solar-open2-250b": lambda **kw: Hybrid(
        HybridConfig.solar_open2_250b(**kw)),
    "solar-debug": lambda **kw: Hybrid(HybridConfig.solar_debug(**kw)),
    "nemotron-3-super-120b": lambda **kw: Hybrid(
        HybridConfig.nemotron_3_super_120b(**kw)),
    "nemotron-debug": lambda **kw: Hybrid(HybridConfig.nemotron_debug(**kw)),
    "vit-base": lambda **kw: ViT(ViTConfig.base(**kw)),
    "vit-debug": lambda **kw: ViT(ViTConfig.debug(**kw)),
    "clip-debug": lambda **kw: CLIP(CLIPConfig.debug(**kw)),
    "resnet-lite": lambda **kw: ResNetLite(**kw),
}


def get_model(name: str, **kw):
    if name not in _REGISTRY:
        raise KeyError(f"unknown model {name!r}; have {sorted(_REGISTRY)}")
    return _REGISTRY[name](**kw)


def register_model(name: str, builder) -> None:
    _REGISTRY[name] = builder


__all__ = ["Llama", "LlamaConfig", "GPT2", "GPT2Config", "Mixtral",
           "MixtralConfig", "LatentMoE", "LatentMoEConfig", "Hybrid",
           "HybridConfig", "ViT",
           "ViTConfig", "CLIP", "CLIPConfig",
           "contrastive_loss", "MLP", "MLPConfig", "ResNetLite",
           "get_model", "register_model"]
