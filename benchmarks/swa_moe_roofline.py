"""Operations and bytes the family that mixes window and full attention
layers over routed experts needs, from the configuration's own sizes: the
yardstick a measured decode step and the measured grouped expert products
are held against.  The dense family's count is ``roofline.py``, the latent
family's ``mla_moe_roofline.py``; this is ``swa_moe_reference.py``'s.  It
counts the work, whatever implements it.

A decode step of ``rows`` sequences reads, once each, as stored:

- every layer's attention weights (``W_q``, ``W_k``, ``W_v``, ``W_o``, with
  the KV heads of the layer's kind);
- the dense layers' three feed-forward matrices;
- of every routed layer: the router, and the three matrices of each HELD
  expert that the step's routing touched: a number the program counts
  (``moe_experts_touched``), never more than are held;
- the output head over the held rows of the vocabulary (the embedding is
  gathered, a row a sequence);
- the cached keys and values that its attention has to read: in a full layer
  every position of a row's context, in a window layer the last
  ``sliding_window``: numbers the program counts from the rows' positions
  (``kv_rows_full``, ``kv_rows_window``: positions x layers), each position
  ``KV heads of the kind x (head_dim + v_head_dim)`` values.

Its arithmetic (a multiply-add counts twice): every row through the
attention weights, the dense feed-forward, the router over all published
experts and the head; each query head against each position read,
``head_dim`` wide for the score and ``v_head_dim`` wide for the sum; and ``6
x hidden x expert width`` for each assignment to a held expert.
"""

from __future__ import annotations

from typing import Dict

BYTES = {"bfloat16": 2.0, "int8": 1.0}


def sizes(config: Dict) -> Dict[str, float]:
    dm, h = int(config["hidden_size"]), int(config["num_attention_heads"])
    dk, dv = int(config["head_dim"]), int(config["v_head_dim"])
    layers = int(config["num_hidden_layers"])
    window = sum(1 for k in config["hybrid_layer_pattern"][:layers] if k)
    routed = sum(1 for k in config["moe_layer_freq"][:layers] if k)
    kv = {"full": int(config["num_key_value_heads"]),
          "window": int(config["swa_num_key_value_heads"])}
    published = config.get("published_counts", {})

    def attention(kind):
        return dm * h * dk + dm * kv[kind] * (dk + dv) + h * dv * dm

    return {
        "full_layers": layers - window, "window_layers": window,
        "dense_layers": layers - routed, "expert_layers": routed,
        "attention": attention("full") * (layers - window)
        + attention("window") * window,
        "dense_ffn": 3 * dm * int(config["intermediate_size"]),
        "expert": 3 * dm * int(config["moe_intermediate_size"]),
        "router": dm * int(published.get("n_routed_experts",
                                         config["n_routed_experts"])),
        "held": int(config["n_routed_experts"]),
        "head": dm * int(config["vocab_size"]),
        "row_full": kv["full"] * (dk + dv),
        "row_window": kv["window"] * (dk + dv),
        "per_position": h * (dk + dv),
    }


def decode_step_bytes(config: Dict, kv_rows_full: float,
                      kv_rows_window: float, experts_touched: float) -> float:
    """``kv_rows_*``: positions x layers the step's attention has to read,
    by layer kind; ``experts_touched``: held experts that got a token,
    summed over the step's routed layers."""
    s = sizes(config)
    touched = min(experts_touched, s["held"] * s["expert_layers"])
    weights = (s["attention"] + s["dense_ffn"] * s["dense_layers"]
               + s["router"] * s["expert_layers"] + s["expert"] * touched
               + s["head"])
    cache = kv_rows_full * s["row_full"] + kv_rows_window * s["row_window"]
    return (weights * BYTES[config["precision"]["weights"]]
            + cache * BYTES[config["precision"]["kv_cache"]])


def decode_step_flops(config: Dict, rows: float, kv_rows_full: float,
                      kv_rows_window: float, held_assignments: float) -> float:
    s = sizes(config)
    per_row = (s["attention"] + s["dense_ffn"] * s["dense_layers"]
               + s["router"] * s["expert_layers"] + s["head"])
    attention = 2.0 * s["per_position"] * (kv_rows_full + kv_rows_window)
    return (2.0 * per_row * rows + attention
            + 2.0 * s["expert"] * held_assignments)


def least_step_seconds(config: Dict, peaks: Dict, rows: float,
                       kv_rows_full: float, kv_rows_window: float,
                       experts_touched: float,
                       held_assignments: float) -> Dict[str, float]:
    by_bytes = decode_step_bytes(config, kv_rows_full, kv_rows_window,
                                 experts_touched) / peaks["hbm_bytes_per_s"]
    by_flops = decode_step_flops(
        config, rows, kv_rows_full, kv_rows_window,
        held_assignments) / peaks["bf16_flops_per_s"]
    return {"seconds": max(by_bytes, by_flops),
            "bound": "memory" if by_bytes >= by_flops else "compute",
            "by_bytes_s": by_bytes, "by_flops_s": by_flops}


def experts_least_seconds(config: Dict, peaks: Dict, experts_touched: float,
                          held_assignments: float) -> Dict[str, float]:
    """The grouped products of one dispatch (all its routed layers and
    steps): each touched expert's three matrices read once, each held
    assignment's row in and out of them (hidden in, hidden out, the expert
    width out and in again), and its multiply-adds."""
    s = sizes(config)
    dm = int(config["hidden_size"])
    width = int(config["moe_intermediate_size"])
    by_bytes = (s["expert"] * experts_touched
                * BYTES[config["precision"]["weights"]]
                + held_assignments * (2 * dm + 4 * width)
                * BYTES[config["precision"]["activations"]]) \
        / peaks["hbm_bytes_per_s"]
    by_flops = 2.0 * s["expert"] * held_assignments \
        / peaks["bf16_flops_per_s"]
    return {"seconds": max(by_bytes, by_flops), "by_bytes_s": by_bytes,
            "by_flops_s": by_flops}
