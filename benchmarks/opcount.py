"""The roofline shared by every architecture: bytes per weight by
precision, and the least time the chip could take for a given work.

What work a step is (operations and bytes from a configuration's sizes) is
the architecture's to say (``benchmarks/architectures/<name>.py``:
``weight_bytes``, ``state_bytes_per_token``, ``decode_step``,
``prefill_chunk``).  Kept with the benchmark so that no PR that claims a
gain can change the yardstick.
"""

from __future__ import annotations

WEIGHT_BYTES = {"bfloat16": 2.0, "int8": 1.0, "int4": 0.5, "float32": 4.0}


def least_seconds(work: dict, peaks: dict, precision: str = "bfloat16") -> tuple[float, str]:
    """The roofline: the larger of FLOPs over peak and bytes over peak, and
    which of the two bounds it.  Activations are bf16 in every
    configuration here, so the bf16 matmul peak is the compute peak."""
    t_flops = work["flops"] / peaks["bf16_flops_per_s"]
    t_bytes = work["bytes"] / peaks["hbm_bytes_per_s"]
    return (t_flops, "flops") if t_flops >= t_bytes else (t_bytes, "bytes")
