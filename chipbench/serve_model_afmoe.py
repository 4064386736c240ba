"""The ``--config`` script ``paddle_tpu serve`` loads in a cell of the
``afmoe`` family: the configuration's AfmoeLM with the BENCHMARK's seeded
bfloat16 weights (chipbench/weights_afmoe.py), so the served model and the
plain reference start from the same arrays. The sizes and the seed arrive in
``CHIPBENCH_MODEL_SPEC`` (JSON: {"config": {...}, "seed": n}), as for
chipbench/serve_model.py.
"""

import json
import os

from chipbench import weights_afmoe as weights

_spec = json.loads(os.environ["CHIPBENCH_MODEL_SPEC"])
model, _shapes = weights.model_and_shapes(_spec["config"])
params = weights.make(_shapes, _spec["seed"])
