"""Operations and bytes a decode step needs, from the configuration's own
sizes — the yardstick a measured step is held against.

A decode step of ``rows`` sequences reads every matmul weight once, as
stored (int8 integers plus a float32 scale for each output channel under
``--quant int8``), and the keys and values of the tokens that are *live*
in the batch — not of the cache's capacity: a step that reads more than
that is exactly what the share should show as lost.  The embedding table
is not streamed (one row for each sequence is gathered); the output head
is.
"""

from __future__ import annotations

from typing import Dict

from benchmarks.reference import shapes_of

WEIGHT_BYTES = {"int8": 1.0, "bfloat16": 2.0, "int4": 0.5}
CACHE_BYTES = {"bfloat16": 2.0, "int8": 1.0}


def matmul_params(config: Dict) -> Dict[str, int]:
    """Matmul weights a step reads: for each layer and for the head."""
    s = shapes_of(config)
    q = s["dim"] * s["heads"] * s["head_dim"]
    kv = s["dim"] * s["kv_heads"] * s["head_dim"]
    layer = q + 2 * kv + s["heads"] * s["head_dim"] * s["dim"] \
        + 3 * s["dim"] * s["ffn"]
    # q, k, v, o, gate, up, down: one scale for each output channel
    out_channels = (s["heads"] * s["head_dim"]
                    + 2 * s["kv_heads"] * s["head_dim"]
                    + s["dim"] + 2 * s["ffn"] + s["dim"])
    return {"layer": layer, "layers": s["layers"],
            "head": s["dim"] * s["vocab"],
            "layer_out_channels": out_channels, "head_out_channels": s["vocab"]}


def weight_bytes(config: Dict) -> float:
    """Bytes of weights one decode step streams, as stored."""
    p = matmul_params(config)
    per = WEIGHT_BYTES[config["precision"]["weights"]]
    total = (p["layer"] * p["layers"] + p["head"]) * per
    if per < 2.0:  # quantized: a float32 scale for every output channel
        total += 4.0 * (p["layer_out_channels"] * p["layers"]
                        + p["head_out_channels"])
    s = shapes_of(config)
    total += 2.0 * s["dim"] * (2 * s["layers"] + 1)  # norm weights, bf16
    if s["qkv_bias"]:
        total += 2.0 * s["layers"] * (s["heads"] + 2 * s["kv_heads"]) \
            * s["head_dim"]
    return total


def kv_bytes_per_token(config: Dict) -> float:
    s = shapes_of(config)
    per = CACHE_BYTES[config["precision"]["kv_cache"]]
    return 2.0 * s["layers"] * s["kv_heads"] * s["head_dim"] * per


def decode_step_bytes(config: Dict, live_tokens: float) -> float:
    return weight_bytes(config) + kv_bytes_per_token(config) * live_tokens


def decode_step_flops(config: Dict, rows: float, live_tokens: float) -> float:
    """Multiply-adds count twice.  Every row goes through every matmul;
    attention multiplies each query head with each live key and value."""
    p = matmul_params(config)
    s = shapes_of(config)
    dense = 2.0 * (p["layer"] * p["layers"] + p["head"]) * rows
    attention = 4.0 * s["layers"] * s["heads"] * s["head_dim"] * live_tokens
    return dense + attention


def least_step_seconds(config: Dict, peaks: Dict, rows: float,
                       live_tokens: float) -> Dict[str, float]:
    """The least time the chip could take for the step, and which of the
    two bounds it: memory bandwidth or arithmetic."""
    by_bytes = decode_step_bytes(config, live_tokens) / peaks["hbm_bytes_per_s"]
    by_flops = decode_step_flops(config, rows, live_tokens) \
        / peaks["bf16_flops_per_s"]
    return {"seconds": max(by_bytes, by_flops),
            "bound": "memory" if by_bytes >= by_flops else "compute",
            "by_bytes_s": by_bytes, "by_flops_s": by_flops}
