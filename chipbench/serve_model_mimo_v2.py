"""The ``--config`` script ``paddle_tpu serve`` loads in a cell of the
``mimo_v2_flash`` family: the configuration's MimoV2LM with the BENCHMARK's
seeded bfloat16 weights (chipbench/weights_mimo_v2.py), so the served model
and the plain reference start from the same arrays. The sizes and the seed
arrive in ``CHIPBENCH_MODEL_SPEC`` (JSON: {"config": {...}, "seed": n}), as
for chipbench/serve_model.py.
"""

import json
import os

from chipbench import weights_mimo_v2 as weights

_spec = json.loads(os.environ["CHIPBENCH_MODEL_SPEC"])
model, _shapes = weights.model_and_shapes(_spec["config"])
params = weights.make(_shapes, _spec["seed"],
                      weights.sink_mean(_spec["config"]))
