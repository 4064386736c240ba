from .initializer import constant, gen1_default, msra, normal, ones, uniform, xavier, zeros
from .layers import (AvgPool2D, BatchNorm, Conv2D, Conv2DTranspose, Dropout,
                     Embedding, Fc, LayerNorm, Linear, Mamba2Mixer,
                     MaxPool2D, ReluSquaredMLP, RMSNorm, ShortConv, SwiGLU)
from .rotary import apply_rope, yarn_inv_freq, yarn_mscale
from .module import Lambda, Module, Sequential, apply_stat_updates, param_count

__all__ = [
    "Module", "Sequential", "Lambda", "param_count", "apply_stat_updates",
    "Linear", "Fc", "Embedding", "Conv2D", "Conv2DTranspose", "BatchNorm",
    "LayerNorm", "RMSNorm", "SwiGLU", "ReluSquaredMLP", "ShortConv", "Mamba2Mixer", "Dropout", "MaxPool2D", "AvgPool2D",
    "apply_rope", "yarn_inv_freq", "yarn_mscale",
    "constant", "zeros", "ones", "uniform", "normal", "xavier", "msra", "gen1_default",
]
