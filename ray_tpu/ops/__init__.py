"""TPU-native compute ops: the building blocks the reference gets from
torch/CUDA kernels (apex, flash-attn), re-built on XLA + Pallas.

XLA fuses elementwise chains into matmuls on its own; Pallas kernels are
reserved for the patterns XLA won't fuse (flash attention inner loop).
Every op here is jit-traceable with static shapes.
"""
from .norms import rms_norm, layer_norm
from .rotary import (apply_rotary, rope_frequencies, yarn_frequencies,
                     yarn_softmax_scale)
from .attention import (multi_head_attention, causal_attention_mask,
                        cached_attention)
from .activations import swiglu, geglu
from .ring_attention import ring_attention
from .moe import (moe_dispatch_combine, moe_dropless, route, router_aux,
                  expert_capacity, MoEAux)

__all__ = ["rms_norm", "layer_norm", "apply_rotary", "rope_frequencies",
           "yarn_frequencies", "yarn_softmax_scale",
           "multi_head_attention", "causal_attention_mask",
           "cached_attention", "swiglu",
           "geglu", "ring_attention", "moe_dispatch_combine",
           "moe_dropless", "route", "router_aux", "expert_capacity",
           "MoEAux"]
