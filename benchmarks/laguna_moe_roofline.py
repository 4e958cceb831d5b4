"""Operations and bytes ``Laguna-S-2.1``'s family needs, from the
configuration's own sizes: the yardstick a measured decode step, the
measured grouped expert products and the measured window-layer attention are
held against.  The window-and-full family with one head count is
``swa_moe_roofline.py``; this is ``laguna_moe_reference.py``'s.  It counts
the work, whatever implements it.

A decode step of ``rows`` sequences reads, once each, as stored:

- every layer's attention weights (``W_q``, ``W_o`` and ``W_g`` with the
  query heads of the layer's kind, ``W_k``, ``W_v``);
- the dense layers' three feed-forward matrices;
- of every routed layer: the router, the shared expert's three matrices,
  and the three matrices of each HELD expert that the step's routing
  touched: a number the program counts (``moe_experts_touched``), never more
  than are held;
- the output head over the held rows of the vocabulary (the embedding is
  gathered, a row a sequence);
- the cached keys and values that its attention has to read: in a full layer
  every position of a row's context, in a window layer the last
  ``sliding_window``: numbers the program counts from the rows' positions
  (``kv_rows_full``, ``kv_rows_window``: positions x layers), each position
  ``KV heads x 2 x head_dim`` values.

Its arithmetic (a multiply-add counts twice): every row through the
attention weights, the dense feed-forward, the router over all published
experts, the shared expert and the head; each query head of the layer's kind
against each position read, ``head_dim`` wide for the score and again for
the sum; and ``6 x hidden x expert width`` for each assignment to a held
expert.

The window layers' attention alone (``window_attention_least_seconds``) is
what the program's scope ``attn_window`` holds: the cached positions read
(by need: ``min(position + 1, sliding_window)`` a live row and window layer)
and the gates' ``W_g``; the projections ``W_q``, ``W_k``, ``W_v`` and
``W_o`` run under the scope ``attn`` beside the full layers' and are counted
in the whole step, not here.
"""

from __future__ import annotations

from typing import Dict

BYTES = {"bfloat16": 2.0, "int8": 1.0}


def sizes(config: Dict) -> Dict[str, float]:
    dm, d = int(config["hidden_size"]), int(config["head_dim"])
    kv = int(config["num_key_value_heads"])
    layers = int(config["num_hidden_layers"])
    kinds = config["layer_types"][:layers]
    heads = config["num_attention_heads_per_layer"][:layers]
    routed = sum(1 for k in config["mlp_layer_types"][:layers]
                 if k == "sparse")
    published = config.get("published_counts", {})

    def attention(h):
        # W_q and W_o, W_k and W_v, W_g
        return 2 * dm * h * d + 2 * dm * kv * d + dm * h

    window = [int(h) for k, h in zip(kinds, heads)
              if k == "sliding_attention"]
    full = [int(h) for k, h in zip(kinds, heads) if k == "full_attention"]
    return {
        "full_layers": len(full), "window_layers": len(window),
        "dense_layers": layers - routed, "expert_layers": routed,
        "attention_full": sum(attention(h) for h in full),
        "attention_window": sum(attention(h) for h in window),
        "gate_window": sum(dm * h for h in window),
        "dense_ffn": 3 * dm * int(config["intermediate_size"]),
        "expert": 3 * dm * int(config["moe_intermediate_size"]),
        "shared": 3 * dm * int(config["shared_expert_intermediate_size"]),
        "router": dm * int(published.get("num_experts",
                                         config["num_experts"])),
        "held": int(config["num_experts"]),
        "head": dm * int(config["vocab_size"]),
        "row": kv * 2 * d,
        # multiply-adds a cached position costs a layer: every query head
        # against it, the score and the sum (mean over a kind's layers)
        "per_position_full": 2 * d * (sum(full) / max(len(full), 1)),
        "per_position_window": 2 * d * (sum(window) / max(len(window), 1)),
    }


def parameters(config: Dict) -> float:
    """Parameters held, the embedding among them."""
    s = sizes(config)
    return (s["attention_full"] + s["attention_window"]
            + s["dense_ffn"] * s["dense_layers"]
            + (s["router"] + s["shared"] + s["expert"] * s["held"])
            * s["expert_layers"] + 2 * s["head"])


def _weights_a_step(s, touched: float) -> float:
    return (s["attention_full"] + s["attention_window"]
            + s["dense_ffn"] * s["dense_layers"]
            + (s["router"] + s["shared"]) * s["expert_layers"]
            + s["expert"] * touched + s["head"])


def decode_step_bytes(config: Dict, kv_rows_full: float,
                      kv_rows_window: float, experts_touched: float) -> float:
    """``kv_rows_*``: positions x layers the step's attention has to read,
    by layer kind; ``experts_touched``: held experts that got a token,
    summed over the step's routed layers."""
    s = sizes(config)
    touched = min(experts_touched, s["held"] * s["expert_layers"])
    cache = (kv_rows_full + kv_rows_window) * s["row"]
    return (_weights_a_step(s, touched) * BYTES[config["precision"]["weights"]]
            + cache * BYTES[config["precision"]["kv_cache"]])


def decode_step_flops(config: Dict, rows: float, kv_rows_full: float,
                      kv_rows_window: float, held_assignments: float) -> float:
    s = sizes(config)
    attention = 2.0 * (s["per_position_full"] * kv_rows_full
                       + s["per_position_window"] * kv_rows_window)
    return (2.0 * _weights_a_step(s, 0.0) * rows + attention
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


def window_attention_least_seconds(config: Dict, peaks: Dict, steps: float,
                                   kv_rows_window: float) -> Dict[str, float]:
    """The window layers' attention of ``steps`` decode steps as the scope
    ``attn_window`` holds it: ``kv_rows_window`` cached positions x layers
    read once (what the rows need, not what a ring holds), the gates'
    ``W_g`` once a step, and each window layer's query heads against each
    position read."""
    s = sizes(config)
    by_bytes = (kv_rows_window * s["row"]
                * BYTES[config["precision"]["kv_cache"]]
                + steps * s["gate_window"]
                * BYTES[config["precision"]["weights"]]) \
        / peaks["hbm_bytes_per_s"]
    by_flops = 2.0 * s["per_position_window"] * kv_rows_window \
        / peaks["bf16_flops_per_s"]
    return {"seconds": max(by_bytes, by_flops), "by_bytes_s": by_bytes,
            "by_flops_s": by_flops}
