"""Python side of the C inference API (native/capi_inference.cc).

The C ABI embeds CPython and drives this class; it loads the exported
inference bundle (fluid/io.py export_inference_model — program JSON +
params tar, the merged-model artifact of trainer/MergeModel.cpp:29 /
capi/gradient_machine.h:36) and runs the real XLA-backed Executor.
Forward-only; the executor's shape-keyed compile cache makes repeated
fixed-shape calls cheap.
"""

from __future__ import annotations

from typing import List, Tuple

import numpy as np

_DTYPES = {0: np.float32, 1: np.int32}


class InferenceHost:
    def __init__(self, model_dir: str):
        from ..fluid.executor import Executor
        from ..fluid.io import load_inference_model

        self.exe = Executor()
        self.program, self.feed_names, self.fetch_names = \
            load_inference_model(model_dir, self.exe)

    def run(self, arrays: List[np.ndarray], fetch_index: int = 0) -> np.ndarray:
        feed = dict(zip(self.feed_names, arrays))
        outs = self.exe.run(self.program, feed=feed,
                            fetch_list=[self.fetch_names[fetch_index]])
        return np.asarray(outs[0])

    def run_raw(self, raw: List[Tuple[bytes, Tuple[int, ...], int]],
                fetch_index: int = 0) -> Tuple[bytes, Tuple[int, ...]]:
        """C-ABI entry: [(buffer, dims, dtype_code)] -> (f32 buffer, dims)."""
        arrays = [np.frombuffer(buf, _DTYPES[code]).reshape(dims)
                  for buf, dims, code in raw]
        out = self.run(arrays, fetch_index).astype(np.float32)
        return out.tobytes(), tuple(int(d) for d in out.shape)
