"""paddle_tpu — a TPU-native deep-learning framework.

Brand-new framework with the capability set of early PaddlePaddle (reference at
/root/reference, see SURVEY.md): op/layer zoo, LoD variable-length sequences,
optimizers, readers/datasets, trainer with events/evaluators/checkpoints, and
distributed training — designed TPU-first on JAX/XLA/Pallas/pjit: compute lowers to
HLO onto the MXU, parallelism is SPMD over a jax.sharding.Mesh with XLA collectives
over ICI/DCN (replacing the reference's pserver/RDMA/NCCL paths), and the host runtime
(stats, queues, data master) is native C++.
"""

__version__ = "0.1.0"

import os as _os

from . import (analysis, core, data, faults, fluid, models, nn, obs, ops,
               optimizer, parallel, trainer, utils, v2)
from .core import CPUPlace, Place, SeqBatch, TPUPlace, sequence_mask
from .trainer import Trainer

#: where the compile cache lives when ``$JAX_COMPILATION_CACHE_DIR`` is
#: unset: a FIXED path inside the checkout (git-ignored). The directory is
#: part of what a later process must repeat to hit the cache, so it is
#: never a temp name, a pid or a time.
DEFAULT_COMPILE_CACHE_DIR = _os.path.join(
    _os.path.dirname(_os.path.dirname(_os.path.abspath(__file__))),
    ".jax_cache")


def enable_compile_cache() -> str:
    """Turn on JAX's persistent compilation cache — the ONE place the repo
    decides where it lives (``serve``, ``train``, ``bench.py``'s children,
    ``chip_smoke.py``'s children and tests/conftest.py all call this before
    their first compile; nothing else sets a cache directory).

    ``$JAX_COMPILATION_CACHE_DIR`` set: that directory, and no other.
    Unset: :data:`DEFAULT_COMPILE_CACHE_DIR`. Compiled executables are
    keyed on the serialized computation + jaxlib version, so a restarted
    process (preemption-resume, the second run of a command) loads them
    from disk instead of recompiling. The min-compile-time/entry-size
    floors drop to 0 so small programs cache too. Returns the directory.
    """
    import jax
    path = (_os.environ.get("JAX_COMPILATION_CACHE_DIR")
            or DEFAULT_COMPILE_CACHE_DIR)
    _os.makedirs(path, exist_ok=True)
    jax.config.update("jax_compilation_cache_dir", path)
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", 0)
    return path


def init(**flags):
    """Process-level runtime init (the ``paddle.init`` analog): enables the
    persistent compilation cache (:func:`enable_compile_cache`) and records
    the keyword flags through :func:`v2.init`. Returns the recorded flag
    dict, ``compile_cache_dir`` included."""
    flags["compile_cache_dir"] = enable_compile_cache()
    return v2.init(**flags)


__all__ = ["analysis", "core", "data", "faults", "fluid", "nn", "obs", "ops",
           "optimizer",
           "parallel", "trainer", "utils", "models", "v2", "Trainer",
           "Place", "TPUPlace", "CPUPlace", "SeqBatch", "sequence_mask",
           "init", "enable_compile_cache", "DEFAULT_COMPILE_CACHE_DIR",
           "__version__"]
