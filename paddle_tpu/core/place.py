"""Device placement.

TPU-native analog of the reference's ``Place`` variant (paddle/platform/place.h:
CPUPlace/GPUPlace) and ``DeviceContext`` (paddle/platform/device_context.h:38-74).
Under JAX/PJRT a "place" resolves to a ``jax.Device``; the stream/handle machinery of
CUDADeviceContext is owned by XLA, so the context here only carries the device plus the
default matmul precision/dtype policy used when lowering ops.
"""

from __future__ import annotations

from dataclasses import dataclass
from typing import Optional

import jax


@dataclass(frozen=True)
class Place:
    """A logical device slot: platform + index."""

    platform: str  # "tpu" | "cpu" | "gpu"
    index: int = 0

    def device(self) -> jax.Device:
        devs = [d for d in jax.devices() if d.platform == self.platform]
        if not devs:
            # CPU is always constructible even when the default platform differs.
            devs = jax.devices("cpu") if self.platform == "cpu" else devs
        if not devs:
            raise RuntimeError(f"no devices for platform '{self.platform}'")
        return devs[self.index % len(devs)]

    @property
    def is_tpu(self) -> bool:
        return self.platform == "tpu"


def TPUPlace(index: int = 0) -> Place:
    return Place("tpu", index)


def CPUPlace(index: int = 0) -> Place:
    return Place("cpu", index)


def default_place() -> Place:
    d = jax.devices()[0]
    return Place(d.platform, 0)


@dataclass
class DeviceContext:
    """Per-place execution context (ref: platform/device_context.h).

    XLA owns streams/handles; what remains host-side is the device binding and the
    numeric policy every kernel lowers with.
    """

    place: Place
    matmul_precision: str = "default"
    compute_dtype: Optional[str] = None  # e.g. "bfloat16" to run matmuls in bf16

    def device(self) -> jax.Device:
        return self.place.device()
