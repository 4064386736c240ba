"""Seeded weights of the ``mimo_v2_flash`` family, made by the benchmark and
given to the program and to the reference alike: chipbench/weights_lfm2.py's
draw (normal(0, 0.02) matrices, embedding and head; unit RMSNorm gains; a
NON-zero ``e_bias``, normal(0, 0.01): PR 26's finding) and, the one leaf
that family does not have, the sliding layers' SINK logits, normal(ln
window, 1) (assumed; :func:`sink_mean`): a sink that weighs what a
window's worth of keys of score 0 weigh. At these weights a score ``q . k /
sqrt(192)`` has a standard deviation of ~1.6, so 128 keys sum to ~490 in
the denominator and a sink of ln 128 = 4.85 adds 128: a fifth of a row's
mass at the median, and a program that dropped it cannot pass. Drawn
normal(0, 1), as the builder first did, the sink is 0.3 % of that mass and
the served tokens lie as close to a reference WITHOUT the sink as to the
one with it (served_gap_mean 0.000758 against 0.000766, my chip run, PR
47, call 2). Every leaf on the device in its own dtype, one at a time (the
largest, layer 0's 16,384-wide matrices, is 134 MB), the seed an ARGUMENT
of the drawing programs. This file builds the MimoV2LM of a configuration
file.
"""

import math
import zlib

import jax
import jax.numpy as jnp

from chipbench import weights_lfm2
from chipbench.weights_deepseek_v3 import _normal


def sink_mean(config):
    """The mean of a configuration's sink logits: ln(sliding_window)."""
    return math.log(config["sliding_window"])


def make(shape_tree, seed, sink_mean):
    """``shape_tree``: a pytree of ShapeDtypeStruct; ``sink_mean``: the
    mean the sink logits are drawn round (:func:`sink_mean` of the
    configuration). Returns the arrays."""
    root = jax.random.PRNGKey(seed)
    out = weights_lfm2.make(shape_tree, seed)

    def leaf(path, s, drawn):
        name = jax.tree_util.keystr(path)
        if not name.endswith("['sink']"):
            return drawn
        key = jax.random.fold_in(root, zlib.crc32(name.encode()) & 0x7FFFFFFF)
        return sink_mean + _normal(key, tuple(s.shape), jnp.dtype(s.dtype),
                                   1.0)
    return jax.tree_util.tree_map_with_path(leaf, shape_tree, out)


def model_and_shapes(config, dtype=jnp.bfloat16):
    """The system under test's model object for a configuration file of
    this family (in bfloat16, as every configuration of it states; the
    tests build a float32 one), and the shape tree of its parameters."""
    from chipbench.reference import mimo_v2 as ref
    from paddle_tpu.models import MimoV2LM
    hp = ref.hparams(config)
    n = config["num_hidden_layers"]
    model = MimoV2LM(
        config["vocab_size"], d_model=config["hidden_size"],
        n_heads=hp["n_heads"], kv_heads=hp["kv_heads"][0],
        swa_kv_heads=hp["kv_heads"][1], d_head=hp["d_k"], d_value=hp["d_v"],
        rotary=hp["rotary"], layer_kinds=hp["kinds"],
        moe_layers=tuple(config["moe_layer_freq"][:n]), window=hp["window"],
        dense_width=config["intermediate_size"],
        expert_width=config["moe_intermediate_size"],
        n_experts=hp["n_experts"], experts_held=hp["experts_held"],
        top_k=hp["top_k"], rope_theta=hp["theta"][0],
        swa_rope_theta=hp["theta"][1], value_scale=hp["value_scale"],
        sink=hp["sink"], eps=hp["eps"], max_len=config["n_positions"],
        block_tokens=config["block_tokens"], dtype=dtype)
    return model, jax.eval_shape(model.init, jax.random.PRNGKey(0))
