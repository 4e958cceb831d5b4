"""Operations and bytes the family of one mixer a layer (Mamba-2 state-space
layers, routed experts of two products, attention) needs, from the
configuration's own sizes: the yardstick a measured decode step, the measured
state updates, the measured scans and the measured grouped expert products
are held against.  The dense family's count is ``roofline.py``; this is
``ssm_moe_reference.py``'s.  It counts the work, whatever implements it.

A decode step of ``rows`` live sequences reads, once each, as stored:

- every Mamba-2 layer's ``W_in``, ``W_out`` and convolution, every attention
  layer's ``W_q``, ``W_k``, ``W_v``, ``W_o``;
- of every routed layer: the router, the shared expert's two matrices, and
  the two matrices of each HELD expert that the step's routing touched: a
  number the program counts (``moe_experts_touched``), never more than are
  held;
- the output head over the held rows of the vocabulary;
- the cached keys and values its attention has to read (``kv_rows_full``:
  positions x attention layers, counted by the program from the rows'
  positions), each ``2 x KV heads x head_dim`` values;
- **each live row's recurrent state of every Mamba-2 layer, and writes it
  back**: ``H x P x N`` values of the state's type and the convolution's
  last ``K - 1`` inputs (``state_row_bytes``), twice.  Live rows, never
  slots.

Its arithmetic (a multiply-add counts twice): every row through the weights
above and the router; each query head against each position read; ``4 x
hidden x expert width`` for each assignment to a held expert; and ``5 x H x
P x N`` for each row and Mamba-2 layer (the decay, the outer product's
product and its sum into the state, the contraction with ``C``).

A scan (prefill) of ``positions`` token positions in ``rows`` rows: the same
recurrence, ``5 x H x P x N`` a position and layer; its bytes are each
row's state in and out and each position's ``z``, ``xBC`` and ``dt`` in and
its ``y`` out.  The projections and the convolution around it are not the
scan's.
"""

from __future__ import annotations

from typing import Dict

BYTES = {"float32": 4.0, "bfloat16": 2.0, "int8": 1.0}


def sizes(config: Dict) -> Dict[str, float]:
    dm = int(config["hidden_size"])
    kinds = str(config["hybrid_override_pattern"])[
        : int(config["num_hidden_layers"])]
    h, kv, hd = (int(config["num_attention_heads"]),
                 int(config["num_key_value_heads"]), int(config["head_dim"]))
    sh, sp = int(config["mamba_num_heads"]), int(config["mamba_head_dim"])
    g, n, k = (int(config["n_groups"]), int(config["ssm_state_size"]),
               int(config["conv_kernel"]))
    inner = sh * sp
    conv_dim = inner + 2 * g * n
    published = config.get("published_counts", {})
    precision = config["precision"]
    return {
        "mamba_layers": kinds.count("M"), "expert_layers": kinds.count("E"),
        "attention_layers": kinds.count("*"),
        "mamba": dm * (inner + conv_dim + sh) + inner * dm
        + (k + 1) * conv_dim,
        "attention": dm * h * hd + 2 * dm * kv * hd + h * hd * dm,
        "expert": 2 * dm * int(config["moe_intermediate_size"]),
        "shared": 2 * dm * int(config["moe_shared_expert_intermediate_size"]),
        "router": dm * int(published.get("n_routed_experts",
                                         config["n_routed_experts"])),
        "held": int(config["n_routed_experts"]),
        "head": dm * int(config["vocab_size"]),
        "row_full": 2 * kv * hd,
        "per_position": 2 * h * hd,
        "state_values": sh * sp * n,
        "state_row_bytes": sh * sp * n * BYTES[precision.get(
            "state", "float32")]
        + (k - 1) * conv_dim * BYTES[precision["activations"]],
        "scan_position_values": 2 * inner + conv_dim + sh,
    }


def _dense(s) -> float:
    """The weights every row passes through, whatever is routed."""
    return (s["mamba"] * s["mamba_layers"]
            + s["attention"] * s["attention_layers"]
            + (s["router"] + s["shared"]) * s["expert_layers"] + s["head"])


def state_bytes(config: Dict, row_steps: float) -> float:
    """``row_steps`` live rows' state of every Mamba-2 layer, read and
    written."""
    s = sizes(config)
    return 2.0 * row_steps * s["mamba_layers"] * s["state_row_bytes"]


def decode_step_bytes(config: Dict, rows: float, kv_rows_full: float,
                      experts_touched: float) -> float:
    s = sizes(config)
    touched = min(experts_touched, s["held"] * s["expert_layers"])
    weights = _dense(s) + s["expert"] * touched
    return (weights * BYTES[config["precision"]["weights"]]
            + kv_rows_full * s["row_full"]
            * BYTES[config["precision"]["kv_cache"]]
            + state_bytes(config, rows))


def decode_step_flops(config: Dict, rows: float, kv_rows_full: float,
                      held_assignments: float) -> float:
    s = sizes(config)
    return (2.0 * _dense(s) * rows
            + 5.0 * s["state_values"] * s["mamba_layers"] * rows
            + 2.0 * s["per_position"] * kv_rows_full
            + 2.0 * s["expert"] * held_assignments)


def least_step_seconds(config: Dict, peaks: Dict, rows: float,
                       kv_rows_full: float, experts_touched: float,
                       held_assignments: float) -> Dict[str, float]:
    by_bytes = decode_step_bytes(config, rows, kv_rows_full,
                                 experts_touched) / peaks["hbm_bytes_per_s"]
    by_flops = decode_step_flops(config, rows, kv_rows_full,
                                 held_assignments) / peaks["bf16_flops_per_s"]
    return {"seconds": max(by_bytes, by_flops),
            "bound": "memory" if by_bytes >= by_flops else "compute",
            "by_bytes_s": by_bytes, "by_flops_s": by_flops}


def state_step_least_seconds(config: Dict, peaks: Dict,
                             row_steps: float) -> Dict[str, float]:
    """The state updates of ``row_steps`` live rows x steps in all Mamba-2
    layers: the state in and out, and the recurrence's arithmetic."""
    s = sizes(config)
    by_bytes = state_bytes(config, row_steps) / peaks["hbm_bytes_per_s"]
    by_flops = (5.0 * s["state_values"] * s["mamba_layers"] * row_steps
                / peaks["bf16_flops_per_s"])
    return {"seconds": max(by_bytes, by_flops), "by_bytes_s": by_bytes,
            "by_flops_s": by_flops}


def scan_least_seconds(config: Dict, peaks: Dict, rows: float,
                       positions: float) -> Dict[str, float]:
    """The scans of one prefill dispatch (all its Mamba-2 layers): ``rows``
    real rows, ``positions`` real token positions."""
    s = sizes(config)
    act = BYTES[config["precision"]["activations"]]
    by_bytes = (state_bytes(config, rows)
                + positions * s["mamba_layers"]
                * s["scan_position_values"] * act) / peaks["hbm_bytes_per_s"]
    by_flops = (5.0 * s["state_values"] * s["mamba_layers"] * positions
                / peaks["bf16_flops_per_s"])
    return {"seconds": max(by_bytes, by_flops), "by_bytes_s": by_bytes,
            "by_flops_s": by_flops}


def experts_least_seconds(config: Dict, peaks: Dict, experts_touched: float,
                          held_assignments: float) -> Dict[str, float]:
    """The grouped products of one dispatch (all its routed layers and
    steps): each touched expert's two matrices read once, each held
    assignment's row in and out of them (hidden in, the expert width out
    and in again, hidden out), and its multiply-adds."""
    s = sizes(config)
    dm = int(config["hidden_size"])
    width = int(config["moe_intermediate_size"])
    by_bytes = (s["expert"] * experts_touched
                * BYTES[config["precision"]["weights"]]
                + held_assignments * (2 * dm + 2 * width)
                * BYTES[config["precision"]["activations"]]) \
        / peaks["hbm_bytes_per_s"]
    by_flops = 2.0 * s["expert"] * held_assignments \
        / peaks["bf16_flops_per_s"]
    return {"seconds": max(by_bytes, by_flops), "by_bytes_s": by_bytes,
            "by_flops_s": by_flops}
