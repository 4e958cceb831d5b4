"""Operations and bytes the latent-attention, routed-expert family needs,
from the configuration's own sizes: the yardstick a measured decode step
and the measured grouped expert products are held against.  The dense
family's count is ``roofline.py``; this is ``mla_moe_reference.py``'s.

A decode step of ``rows`` sequences reads, once each, as stored (bfloat16):

- every layer's attention weights (``W_q``, ``W_kva``, ``W_kvb``, ``W_o``);
- the leading dense layers' three feed-forward matrices;
- of every expert layer: the router, the shared experts, and the three
  matrices of each HELD expert that the step's routing touched: a number
  the program counts (``moe_experts_touched``), never more than are held;
- the output head over the held rows of the vocabulary (the embedding is
  gathered, a row a sequence);
- the cached rows of the tokens that are live in the batch: ``kv_lora_rank +
  qk_rope_head_dim`` values a token and layer.

Its arithmetic (a multiply-add counts twice), in the absorbed form: every
row through ``W_q``, ``W_kva``, both halves of ``W_kvb`` (folded into the
query, applied after the weighted sum) and ``W_o``; each head against each
live token's row, 576 wide for the score and 512 wide for the sum; the
dense feed-forward; the router over all published experts, the shared
experts, and ``6 x hidden x expert width`` for each assignment to a held
expert; the head.
"""

from __future__ import annotations

from typing import Dict

BYTES = {"bfloat16": 2.0, "int8": 1.0}


def sizes(config: Dict) -> Dict[str, float]:
    dm, h = int(config["hidden_size"]), int(config["num_attention_heads"])
    c, dr = int(config["kv_lora_rank"]), int(config["qk_rope_head_dim"])
    dn, dv = int(config["qk_nope_head_dim"]), int(config["v_head_dim"])
    layers = int(config["num_hidden_layers"])
    dense = min(int(config["first_k_dense_replace"]), layers)
    published = config.get("published_counts", {})
    return {
        "layers": layers, "dense_layers": dense,
        "expert_layers": layers - dense,
        "attention": dm * h * (dn + dr) + dm * (c + dr)
        + c * h * (dn + dv) + h * dv * dm,
        "dense_ffn": 3 * dm * int(config["intermediate_size"]),
        "expert": 3 * dm * int(config["moe_intermediate_size"]),
        "shared": int(config["num_shared_experts"]) * 3 * dm
        * int(config["moe_intermediate_size"]),
        "router": dm * int(published.get("num_experts",
                                         config["num_experts"])),
        "held": int(config["num_experts"]),
        "head": dm * int(config["vocab_size"]),
        "row": c + dr, "latent": c, "heads": h,
    }


def cache_bytes_per_token(config: Dict) -> float:
    s = sizes(config)
    return s["layers"] * s["row"] * BYTES[config["precision"]["kv_cache"]]


def decode_step_bytes(config: Dict, live_tokens: float,
                      experts_touched: float) -> float:
    """``experts_touched``: held experts that got a token, summed over the
    step's expert layers (at most held x expert layers)."""
    s = sizes(config)
    touched = min(experts_touched, s["held"] * s["expert_layers"])
    weights = (s["attention"] * s["layers"]
               + s["dense_ffn"] * s["dense_layers"]
               + (s["router"] + s["shared"]) * s["expert_layers"]
               + s["expert"] * touched + s["head"])
    return (weights * BYTES[config["precision"]["weights"]]
            + cache_bytes_per_token(config) * live_tokens)


def decode_step_flops(config: Dict, rows: float, live_tokens: float,
                      held_assignments: float) -> float:
    """``held_assignments``: the step's assignments to held experts, summed
    over its expert layers."""
    s = sizes(config)
    per_row = (s["attention"] * s["layers"]
               + s["dense_ffn"] * s["dense_layers"]
               + (s["router"] + s["shared"]) * s["expert_layers"]
               + s["head"])
    attention = (2.0 * s["layers"] * s["heads"] * (s["row"] + s["latent"])
                 * live_tokens)
    return (2.0 * per_row * rows + attention
            + 2.0 * s["expert"] * held_assignments)


def least_step_seconds(config: Dict, peaks: Dict, rows: float,
                       live_tokens: float, experts_touched: float,
                       held_assignments: float) -> Dict[str, float]:
    by_bytes = decode_step_bytes(config, live_tokens, experts_touched) \
        / peaks["hbm_bytes_per_s"]
    by_flops = decode_step_flops(config, rows, live_tokens,
                                 held_assignments) / peaks["bf16_flops_per_s"]
    return {"seconds": max(by_bytes, by_flops),
            "bound": "memory" if by_bytes >= by_flops else "compute",
            "by_bytes_s": by_bytes, "by_flops_s": by_flops}


def experts_least_seconds(config: Dict, peaks: Dict, experts_touched: float,
                          held_assignments: float) -> Dict[str, float]:
    """The grouped products of one dispatch (all its expert layers and
    steps): each touched expert's three matrices read once, each held
    assignment's row in and out of them (hidden in, hidden out, the expert
    width out and in again), and its multiply-adds."""
    s = sizes(config)
    dm = int(config["hidden_size"])
    width = int(config["moe_intermediate_size"])
    per = BYTES[config["precision"]["weights"]]
    by_bytes = (s["expert"] * experts_touched * per
                + held_assignments * (2 * dm + 4 * width)
                * BYTES[config["precision"]["activations"]]) \
        / peaks["hbm_bytes_per_s"]
    by_flops = 2.0 * s["expert"] * held_assignments \
        / peaks["bf16_flops_per_s"]
    return {"seconds": max(by_bytes, by_flops), "by_bytes_s": by_bytes,
            "by_flops_s": by_flops}
