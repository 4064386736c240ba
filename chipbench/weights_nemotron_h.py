"""Seeded weights of the ``nemotron_h`` family, made by the benchmark and
given to the program and to the reference alike: normal(0, 0.02) matrices,
embedding and head (assumed), unit norm gains, the convolution's taps and
bias uniform(+-1/sqrt(conv_kernel)) (PyTorch's Conv1d default, assumed),
``a_log`` = log uniform(1, 16), ``dt_bias`` = the inverse softplus of a step
log-uniform in [time_step_min, time_step_max] floored at time_step_floor
(the published Mamba-2 draw), ``d`` = 1, and a NON-zero
``e_score_correction_bias`` (normal(0, 0.01), assumed; PR 26's finding: the
top sigmoid scores lie within ~0.02 of one another, so a wider bias decides
the selection alone). Every leaf is drawn on the device in its own dtype,
one leaf at a time (the largest, a layer's 16 up matrices, is 160 MB). The
program contributes only the shape tree (``jax.eval_shape`` of its
``init``), never a value; the seed is an ARGUMENT of the drawing programs,
so every seed runs the same compiled code. (chipbench/weights_lfm2.py's
scheme; that file builds an Lfm2MoeLM and knows no state-space scalars.)
"""

import zlib
from functools import partial

import jax
import jax.numpy as jnp

from chipbench.weights_deepseek_v3 import _normal
from chipbench.weights_lfm2 import _uniform


@partial(jax.jit, static_argnums=(1,))
def _a_log(key, shape):
    return jnp.log(jax.random.uniform(key, shape, jnp.float32, 1.0, 16.0))


@partial(jax.jit, static_argnums=(1, 2, 3, 4))
def _dt_bias(key, shape, low, high, floor):
    u = jax.random.uniform(key, shape, jnp.float32)
    step = jnp.maximum(jnp.exp(u * (jnp.log(high) - jnp.log(low))
                               + jnp.log(low)), floor)
    return step + jnp.log(-jnp.expm1(-step))


def make(shape_tree, seed, config):
    """``shape_tree``: a pytree of ShapeDtypeStruct; ``config``: the
    configuration file (its time-step range). Returns the arrays."""
    root = jax.random.PRNGKey(seed)
    taps = config["conv_kernel"]
    steps = (float(config["time_step_min"]), float(config["time_step_max"]),
             float(config["time_step_floor"]))

    def leaf(path, s):
        name = jax.tree_util.keystr(path)
        last = name.rsplit("['", 1)[1][:-2]
        shape, dtype = tuple(s.shape), jnp.dtype(s.dtype)
        if last in ("gamma", "norm_gamma", "d"):
            return jnp.ones(shape, dtype)
        key = jax.random.fold_in(root, zlib.crc32(name.encode()) & 0x7FFFFFFF)
        if last in ("w_conv", "b_conv"):
            return _uniform(key, shape, dtype, taps ** -0.5)
        if last == "a_log":
            return _a_log(key, shape)
        if last == "dt_bias":
            return _dt_bias(key, shape, *steps)
        return _normal(key, shape, dtype, 0.01 if last == "e_bias" else 0.02)
    return jax.tree_util.tree_map_with_path(leaf, shape_tree)


def model_and_shapes(config, dtype=jnp.bfloat16):
    """The system under test's model object for a configuration file of
    this family (in bfloat16 with a float32 carry, as every configuration
    of it states; the tests build a float32 one), and the shape tree of
    its parameters."""
    from paddle_tpu.models import NemotronHLM
    model = NemotronHLM(
        config["vocab_size"], d_model=config["hidden_size"],
        pattern=config["hybrid_override_pattern"],
        n_heads=config["num_attention_heads"],
        kv_heads=config["num_key_value_heads"], d_head=config["head_dim"],
        mamba_heads=config["mamba_num_heads"],
        mamba_head_dim=config["mamba_head_dim"],
        ssm_groups=config["n_groups"], ssm_state=config["ssm_state_size"],
        expert_width=config["moe_intermediate_size"],
        shared_width=config["moe_shared_expert_intermediate_size"],
        n_experts=config["router_width"],
        experts_held=config["experts_held"],
        top_k=config["num_experts_per_tok"],
        routed_scale=float(config["routed_scaling_factor"]),
        conv_taps=config["conv_kernel"], chunk=config["chunk_size"],
        eps=config["layer_norm_epsilon"], max_len=config["n_positions"],
        dtype=dtype)
    return model, jax.eval_shape(model.init, jax.random.PRNGKey(0))
