"""Seeded weights, made by the benchmark and given to the program and to the
reference alike: GPT-2's published initialisation (normal(0, 0.02) matrices
and embeddings, normal(0, 0.01) positions as the tree's own default, zero
biases, unit LayerNorm gains), every leaf on the device in ONE jitted call.
The program contributes only the shape tree (``jax.eval_shape`` of its
``init``), never a value.
"""

import jax
import jax.numpy as jnp


def _leaf(path, shape, dtype, key):
    name = jax.tree_util.keystr(path)
    if name.endswith("['gamma']"):
        return jnp.ones(shape, dtype)
    if name.endswith("['beta']") or name.endswith("['b']"):
        return jnp.zeros(shape, dtype)
    std = 0.01 if "pos_embed" in name else 0.02
    return (std * jax.random.normal(key, shape, jnp.float32)).astype(dtype)


_BUILDERS = {}


def make(shape_tree, seed, dtype=None):
    """``shape_tree``: a pytree of ShapeDtypeStruct. Returns the arrays.
    Leaves that differ only in their layer (``blocks_<i>``) are drawn as ONE
    stacked array and cut apart, so the program that makes a 36-layer model
    has a dozen random draws in it, not hundreds. The seed is an ARGUMENT of
    that program: every seed runs the same compiled code."""
    flat, treedef = jax.tree_util.tree_flatten_with_path(shape_tree)
    sig = (treedef, tuple((s.shape, str(dtype or s.dtype)) for _, s in flat))
    if sig not in _BUILDERS:
        groups = {}
        for idx, (path, s) in enumerate(flat):
            name = jax.tree_util.keystr(path)
            head, _, tail = name.partition("]")
            key = (tail if head.startswith("['blocks_") else name, s.shape)
            groups.setdefault(key, []).append(idx)

        def build(key):
            out = [None] * len(flat)
            for k, (_, members) in zip(jax.random.split(key, len(groups)),
                                       sorted(groups.items())):
                path, s = flat[members[0]]
                stacked = _leaf(path, (len(members),) + s.shape,
                                dtype or s.dtype, k)
                for j, idx in enumerate(members):
                    out[idx] = stacked[j]
            return jax.tree_util.tree_unflatten(treedef, out)
        _BUILDERS[sig] = jax.jit(build)
    return _BUILDERS[sig](jax.random.PRNGKey(seed))


def model_and_shapes(config):
    """The system under test's model object for a configuration file, and
    the shape tree of its parameters."""
    from paddle_tpu.models import TransformerLM
    model = TransformerLM(config["vocab_size"], d_model=config["n_embd"],
                          n_heads=config["n_head"],
                          n_layers=config["n_layer"],
                          d_ff=config["n_inner"],
                          max_len=config["n_positions"])
    return model, jax.eval_shape(model.init, jax.random.PRNGKey(0))
