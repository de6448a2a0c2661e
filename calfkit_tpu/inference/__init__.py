"""The local TPU inference backend — the seam the reference filled with
remote HTTPS APIs (SURVEY.md §1 layer 4, §2.3).

Compute path: JAX/XLA with GSPMD tensor-parallel sharding over a device mesh;
four Pallas kernels (the paged decode read in place, of K and V pairs and of a latent
pool; a hybrid model's SSM step in one pass over its state; a window stack's prefill chunk
with its scores kept in VMEM); a continuous-batching engine that
the Worker drives from Kafka-partition consumption.

Import is lazy at the package boundary: nothing here pulls in jax until an
inference class is actually constructed.
"""

from typing import Any

from calfkit_tpu.inference.config import (
    ModelConfig,
    PRESETS,
    RuntimeConfig,
    SpecConfig,
)

__all__ = [
    "JaxLocalModelClient",
    "ModelConfig",
    "PRESETS",
    "RuntimeConfig",
    "SpecConfig",
    "assert_engine_fits",
    "initialize_multihost",
]


def __getattr__(name: str) -> Any:
    # lazy: importing calfkit_tpu.inference must not pull in jax
    if name == "JaxLocalModelClient":
        from calfkit_tpu.inference.client import JaxLocalModelClient

        return JaxLocalModelClient
    if name in ("initialize_multihost", "assert_engine_fits"):
        from calfkit_tpu.inference import distributed

        return getattr(distributed, name)
    raise AttributeError(f"module {__name__!r} has no attribute {name!r}")
