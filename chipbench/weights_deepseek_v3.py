"""Seeded weights of the ``deepseek_v3`` family, made by the benchmark and
given to the program and to the reference alike: normal(0, 0.02) matrices
and embeddings (``initializer_range``, assumed), unit RMSNorm gains, and a
NON-zero ``e_score_correction_bias`` (normal(0, 0.01), assumed) so that the
router's selection (score + bias) and its weights (score) can be told
apart. (0.01, not the 0.1 first tried: at these widths the router's logits
have a standard deviation of 1.7, so the top candidates' sigmoid scores lie
within ~0.02 of one another and a bias of 0.1 decides the selection by
itself — the busiest held expert drew 6.4 times the mean load, and how many
pairs landed on the 16 held experts, and with it the step time, moved by
10 % from seed to seed; PERF.md section 6, PR 26.) Every leaf is drawn on the device in the configuration's dtype, one
leaf at a time (the largest, a layer's 16 held gate matrices, is 470 MB;
drawing them stacked as chipbench/weights.py does would hold the 7 GB of
expert weights twice). The program contributes only the shape tree
(``jax.eval_shape`` of its ``init``), never a value; the seed is an
ARGUMENT of the drawing programs, so every seed runs the same compiled code.
"""

import zlib
from functools import partial

import jax
import jax.numpy as jnp


@partial(jax.jit, static_argnums=(1, 2, 3))
def _normal(key, shape, dtype, std):
    return (std * jax.random.normal(key, shape, jnp.float32)).astype(dtype)


def make(shape_tree, seed):
    """``shape_tree``: a pytree of ShapeDtypeStruct. Returns the arrays."""
    root = jax.random.PRNGKey(seed)

    def leaf(path, s):
        name = jax.tree_util.keystr(path)
        if name.endswith("['gamma']"):
            return jnp.ones(s.shape, s.dtype)
        key = jax.random.fold_in(root, zlib.crc32(name.encode()) & 0x7FFFFFFF)
        std = 0.01 if name.endswith("['e_bias']") else 0.02
        return _normal(key, tuple(s.shape), jnp.dtype(s.dtype), std)
    return jax.tree_util.tree_map_with_path(leaf, shape_tree)


def model_and_shapes(config):
    """The system under test's model object for a configuration file of
    this family, and the shape tree of its parameters."""
    from paddle_tpu.models import DeepseekV3LM
    model = DeepseekV3LM(
        config["vocab_size"], d_model=config["hidden_size"],
        n_heads=config["num_attention_heads"],
        n_layers=config["num_hidden_layers"],
        n_dense=config["first_k_dense_replace"],
        dense_width=config["intermediate_size"],
        expert_width=config["moe_intermediate_size"],
        n_experts=config["router_width"],
        experts_held=config["experts_held"],
        top_k=config["num_experts_per_tok"], n_group=config["n_group"],
        topk_group=config["topk_group"],
        routed_scale=config["routed_scaling_factor"],
        n_shared=config["n_shared_experts"],
        q_rank=config["q_lora_rank"], kv_rank=config["kv_lora_rank"],
        d_nope=config["qk_nope_head_dim"], d_rope=config["qk_rope_head_dim"],
        d_v=config["v_head_dim"], rope_theta=config["rope_theta"],
        rope_scaling=config["rope_scaling"], eps=config["rms_norm_eps"],
        max_len=config["n_positions"], dtype=jnp.bfloat16)
    return model, jax.eval_shape(model.init, jax.random.PRNGKey(0))
