"""Seeded weights of the ``lfm2_moe`` family, made by the benchmark and given
to the program and to the reference alike: normal(0, 0.02) matrices and
embeddings (``initializer_range``, assumed), unit RMSNorm gains, depthwise
convolution taps uniform(+-1/sqrt(3)) (PyTorch's Conv1d default at fan-in 3,
assumed: at 0.02 the operator would add ~0.03 to a residual stream of ~1
and no comparison would see it), and a NON-zero ``expert_bias`` (normal(0,
0.01), assumed; PR 26's finding: the top sigmoid scores lie within ~0.02 of
one another, so a wider bias decides the selection alone). Every leaf is
drawn on the device in the configuration's dtype, one leaf at a time (the
largest, a layer's 32 gate matrices, is 235 MB). The program contributes
only the shape tree (``jax.eval_shape`` of its ``init``), never a value;
the seed is an ARGUMENT of the drawing programs, so every seed runs the
same compiled code. (chipbench/weights_deepseek_v3.py's scheme; that file
builds a DeepseekV3LM and knows no convolution taps.)
"""

import zlib
from functools import partial

import jax
import jax.numpy as jnp

from chipbench.weights_deepseek_v3 import _normal


@partial(jax.jit, static_argnums=(1, 2, 3))
def _uniform(key, shape, dtype, bound):
    return jax.random.uniform(key, shape, jnp.float32, -bound,
                              bound).astype(dtype)


def make(shape_tree, seed):
    """``shape_tree``: a pytree of ShapeDtypeStruct. Returns the arrays."""
    root = jax.random.PRNGKey(seed)

    def leaf(path, s):
        name = jax.tree_util.keystr(path)
        if name.endswith("['gamma']"):
            return jnp.ones(s.shape, s.dtype)
        key = jax.random.fold_in(root, zlib.crc32(name.encode()) & 0x7FFFFFFF)
        shape, dtype = tuple(s.shape), jnp.dtype(s.dtype)
        if name.endswith("['w_conv']"):
            return _uniform(key, shape, dtype, shape[-1] ** -0.5)
        std = 0.01 if name.endswith("['e_bias']") else 0.02
        return _normal(key, shape, dtype, std)
    return jax.tree_util.tree_map_with_path(leaf, shape_tree)


def model_and_shapes(config, dtype=jnp.bfloat16):
    """The system under test's model object for a configuration file of
    this family (in bfloat16, as every configuration of it states; the
    tests build a float32 one), and the shape tree of its parameters."""
    from chipbench.reference import lfm2 as ref
    from paddle_tpu.models import Lfm2MoeLM
    model = Lfm2MoeLM(
        config["vocab_size"], d_model=config["hidden_size"],
        n_heads=config["num_attention_heads"],
        kv_heads=config["num_key_value_heads"],
        layer_types=ref.layer_types(config),
        n_dense=config["num_dense_layers"],
        dense_width=config["intermediate_size"],
        expert_width=config["moe_intermediate_size"],
        n_experts=config["router_width"],
        experts_held=config["experts_held"],
        top_k=config["num_experts_per_tok"],
        routed_scale=float(config["routed_scaling_factor"]),
        conv_taps=config["conv_L_cache"],
        rope_theta=float(config["rope_theta"]), eps=config["norm_eps"],
        max_len=config["n_positions"], dtype=dtype)
    return model, jax.eval_shape(model.init, jax.random.PRNGKey(0))
