"""Seeded weights of the ``afmoe`` family, made by the benchmark and given to
the program and to the reference alike: chipbench/weights_lfm2.py's draw as
it stands (``make``: normal(0, 0.02) matrices, embedding and head; unit
RMSNorm gains; a NON-zero ``expert_bias``, normal(0, 0.01): PR 26's finding,
the top sigmoid scores lie within ~0.02 of one another, so a wider bias
decides the selection alone) — every leaf on the device in its own dtype,
one at a time (the largest, a layer's 16 gate matrices, is 67 MB), the seed
an ARGUMENT of the drawing programs. That file builds an Lfm2MoeLM; this one
builds the AfmoeLM of a configuration file.
"""

import jax
import jax.numpy as jnp

from chipbench.weights_lfm2 import make  # noqa: F401  (the family's draw)


def model_and_shapes(config, dtype=jnp.bfloat16):
    """The system under test's model object for a configuration file of
    this family (in bfloat16, as every configuration of it states; the
    tests build a float32 one), and the shape tree of its parameters."""
    from chipbench.reference import afmoe as ref
    from paddle_tpu.models import AfmoeLM
    hp = ref.hparams(config)
    model = AfmoeLM(
        config["vocab_size"], d_model=config["hidden_size"],
        n_heads=hp["n_heads"], kv_heads=hp["kv_heads"], d_head=hp["d_head"],
        layer_types=hp["layer_types"], window=hp["window"],
        n_dense=config["num_dense_layers"],
        dense_width=config["intermediate_size"],
        expert_width=config["moe_intermediate_size"],
        n_experts=hp["n_experts"], experts_held=hp["experts_held"],
        top_k=hp["top_k"], n_shared=config["num_shared_experts"],
        routed_scale=hp["route_scale"], rope_theta=hp["theta"],
        embed_scale=hp["embed_scale"], eps=hp["eps"],
        max_len=config["n_positions"], dtype=dtype)
    return model, jax.eval_shape(model.init, jax.random.PRNGKey(0))
