"""Operations and bytes the algorithm needs, from a configuration's sizes.

Kept with the benchmark so that no PR that claims a gain can change the
yardstick.  Counts are what the mathematics requires, not what a given
program happens to do: weights are read once a step at the configuration's
stated precision, and attention reads only the KV that is attended.
"""

from __future__ import annotations

WEIGHT_BYTES = {"bfloat16": 2.0, "int8": 1.0, "int4": 0.5, "float32": 4.0}


def sizes(config: dict) -> dict:
    D, L = config["hidden_size"], config["num_hidden_layers"]
    H, K = config["num_attention_heads"], config["num_key_value_heads"]
    hd = config.get("head_dim") or D // H
    F, V = config["intermediate_size"], config["vocab_size"]
    per_layer = D * H * hd + 2 * D * K * hd + H * hd * D + 3 * D * F
    return dict(D=D, L=L, H=H, K=K, hd=hd, F=F, V=V, per_layer=per_layer,
                matmul_params=L * per_layer + D * V, embed_params=V * D)


def weight_bytes(config: dict) -> float:
    """Bytes of weights one step must read: every layer matrix and the
    head at the stated weight precision (norms are negligible; the
    embedding is a gather of one row a token)."""
    s = sizes(config)
    precision = config["precision"]["weights"]
    return s["matmul_params"] * WEIGHT_BYTES[precision]


def kv_bytes_per_token(config: dict) -> float:
    s = sizes(config)
    return 2.0 * s["L"] * s["K"] * s["hd"] * WEIGHT_BYTES[config["precision"]["kv"]]


def decode_step(config: dict, rows: float, mean_context: float, chips: int = 1) -> dict:
    """One decode step over ``rows`` rows of ``mean_context`` tokens each,
    per chip under tensor parallelism over ``chips``: FLOPs and bytes."""
    s = sizes(config)
    ctx = float(rows) * float(mean_context)
    flops = 2.0 * s["matmul_params"] * rows + 4.0 * s["L"] * s["H"] * s["hd"] * ctx
    bytes_ = weight_bytes(config) + kv_bytes_per_token(config) * ctx
    return {"flops": flops / chips, "bytes": bytes_ / chips}


def prefill_chunk(config: dict, rows: int, chunk: int, offset: int, chips: int = 1) -> dict:
    """One prefill chunk of ``chunk`` tokens a row at ``offset`` tokens of
    earlier context: FLOPs and bytes per chip."""
    s = sizes(config)
    tokens = rows * chunk
    attended = rows * chunk * (offset + (chunk + 1) / 2.0)  # causal
    flops = 2.0 * s["matmul_params"] * tokens + 4.0 * s["L"] * s["H"] * s["hd"] * attended
    bytes_ = weight_bytes(config) + kv_bytes_per_token(config) * rows * (offset + chunk)
    return {"flops": flops / chips, "bytes": bytes_ / chips}


def least_seconds(work: dict, peaks: dict, precision: str = "bfloat16") -> tuple[float, str]:
    """The roofline: the larger of FLOPs over peak and bytes over peak, and
    which of the two bounds it.  Activations are bf16 in every
    configuration here, so the bf16 matmul peak is the compute peak."""
    t_flops = work["flops"] / peaks["bf16_flops_per_s"]
    t_bytes = work["bytes"] / peaks["hbm_bytes_per_s"]
    return (t_flops, "flops") if t_flops >= t_bytes else (t_bytes, "bytes")
