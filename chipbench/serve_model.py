"""The ``--config`` script ``paddle_tpu serve`` loads: the configuration's
TransformerLM with the BENCHMARK's seeded weights (chipbench/weights.py), so
the served model and the plain reference start from the same arrays and the
reference takes nothing the program made. The sizes and the seed arrive in
``CHIPBENCH_MODEL_SPEC`` (JSON: {"config": {...}, "seed": n}); ``serve``'s
own --vocab/--d_model/... flags are not read when --config is given.
"""

import json
import os

from chipbench import weights

_spec = json.loads(os.environ["CHIPBENCH_MODEL_SPEC"])
model, _shapes = weights.model_and_shapes(_spec["config"])
params = weights.make(_shapes, _spec["seed"])
