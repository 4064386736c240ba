"""Seeded weights of the ``keye_vl2`` family, made by the benchmark and given
to the program and to the reference alike: chipbench/weights_lfm2.py's draw
as it stands (``make``: normal(0, 0.02) matrices, embedding and head — the
indexer's ``w_idx`` among them, so its queries, its key and its head weights
have the same init and a row's scores are not degenerate; unit norm gains;
this family's router has no bias to draw) — every leaf on the device in its
own dtype, one at a time (the largest, a layer's 16 gate matrices, is 50
MB), the seed an ARGUMENT of the drawing programs. That file builds an
Lfm2MoeLM; this one builds the KeyeSparseLM of a configuration file.
"""

import jax
import jax.numpy as jnp

from chipbench.weights_lfm2 import make  # noqa: F401  (the family's draw)


def model_and_shapes(config, dtype=jnp.bfloat16):
    """The system under test's model object for a configuration file of
    this family (in bfloat16, as every configuration of it states; the
    tests build a float32 one), and the shape tree of its parameters."""
    from chipbench.reference import keye_vl2 as ref
    from paddle_tpu.models import KeyeSparseLM
    hp = ref.hparams(config)
    model = KeyeSparseLM(
        config["vocab_size"], d_model=config["hidden_size"],
        n_heads=hp["n_heads"], kv_heads=hp["kv_heads"], d_head=hp["d_head"],
        n_layers=config["num_hidden_layers"],
        expert_width=config["moe_intermediate_size"],
        n_experts=hp["n_experts"], experts_held=hp["experts_held"],
        top_k=hp["top_k"], index_heads=hp["index_heads"],
        index_dim=hp["index_dim"], index_topk=hp["topk"],
        rope_theta=hp["theta"], eps=hp["eps"],
        max_len=config["n_positions"],
        block_tokens=config.get("block_tokens", 2048), dtype=dtype)
    return model, jax.eval_shape(model.init, jax.random.PRNGKey(0))
