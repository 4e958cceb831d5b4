"""Operations and bytes the family of gated delta-rule layers (a matrix state
a head) three in four and full attention the fourth, each followed by a dense
gated MLP, needs, from the configuration's own sizes: the yardstick a measured
decode step, the measured state updates and the measured chunked scans are
held against.  ``granite_hybrid_roofline.py`` is the count of the Mamba-2
family, whose keys a configuration of this family does not have; this is
``olmo_hybrid_reference.py``'s.  It counts the work, whatever implements it,
and bytes as stored.

A decode step of ``rows`` live sequences reads, once each:

- every delta layer's ``W_q``, ``W_k``, ``W_v``, ``W_z``, ``W_a``, ``W_b``,
  ``W_o`` and convolution, every attention layer's ``W_q``, ``W_k``, ``W_v``,
  ``W_o``, every layer's MLP (``W_i`` of two ``intermediate_size`` and
  ``W_o``);
- the head over the whole vocabulary (the embedding's rows a step looks up
  for its tokens are ``rows`` of 100,352 and are not counted);
- the cached keys and values its attention has to read (``kv_rows_full``:
  positions x attention layers, counted by the program from the rows'
  positions), each ``2 x KV heads x head`` values;
- **each live row's state of every delta layer, and writes it back**: ``H x
  Dk x Dv`` values of the state's type (as stored: the leaf lays two rows of
  192 side by side, 384 lanes, no padding) and the convolution's last ``K -
  1`` inputs over ``q | k | v`` (``state_row_bytes``), twice.  Live rows,
  never slots.

Its arithmetic (a multiply-add counts twice): every row through the weights
above; each query head against each position read; and ``7 x H x Dk x Dv``
for each row and delta layer (the decay's product, ``S^T k``, the outer
product and its sum into the state, ``S^T q``).

A scan (prefill) of ``positions`` token positions in ``rows`` rows: the same
recurrence, ``7 x H x Dk x Dv`` a position and layer (the chunked form does
more: a triangular solve a chunk; the yardstick is the rule's own work); its
bytes are each row's state in and out and each position's ``q``, ``k``,
``v``, ``g`` and ``beta`` in and its ``o`` out.  The projections, the
convolution and the gate around it are not the scan's.
"""

from __future__ import annotations

import math
from typing import Dict

BYTES = {"float32": 4.0, "bfloat16": 2.0, "int8": 1.0}


def sizes(config: Dict) -> Dict[str, float]:
    dm = int(config["hidden_size"])
    kinds = list(config["layer_types"])[: int(config["num_hidden_layers"])]
    h, kv = (int(config["num_attention_heads"]),
             int(config["num_key_value_heads"]))
    hd = dm // h
    dh = int(config["linear_num_value_heads"])
    dk, dv = (int(config["linear_key_head_dim"]),
              int(config["linear_value_head_dim"]))
    k = int(config["linear_conv_kernel_dim"])
    conv_dim = dh * (2 * dk + dv)
    precision = config["precision"]
    return {
        "layers": len(kinds),
        "delta_layers": kinds.count("linear_attention"),
        "attention_layers": kinds.count("full_attention"),
        # W_q | W_k | W_v | W_z, W_a | W_b, W_o, the convolution
        "delta": dm * (conv_dim + dh * dv) + 2 * dm * dh + dh * dv * dm
        + k * conv_dim,
        "attention": dm * h * hd + 2 * dm * kv * hd + h * hd * dm,
        "mlp": 3 * dm * int(config["intermediate_size"]),
        "head": dm * int(config["vocab_size"]),
        "row_full": 2 * kv * hd,
        "per_position": 2 * h * hd,
        "state_values": dh * dk * dv,
        "state_row_bytes": dh * dk * dv * BYTES[config.get(
            "state_type", "float32")]
        + (k - 1) * conv_dim * BYTES[precision["activations"]],
        "scan_position_values": conv_dim + dh * dv + 2 * dh,
    }


def held_as(config: Dict):
    """A head's ``[Dk, Dv]`` state as the program's leaf holds it: ``f`` rows
    side by side, the fewest that make the last axis whole lane tiles of 128
    (``models/delta.pack``: the same bytes in the same order)."""
    dk, dv = (int(config["linear_key_head_dim"]),
              int(config["linear_value_head_dim"]))
    f = 128 // math.gcd(dv, 128)
    f = f if dk % f == 0 else 1
    return dk // f, f * dv


def parameters(config: Dict) -> float:
    """Every weight a step reads: the held model's parameters but for its
    norms, time-step biases, ``A`` (a few thousand a layer) and the
    embedding's table (385 M: a step reads ``rows`` of its rows)."""
    s = sizes(config)
    return (s["delta"] * s["delta_layers"]
            + s["attention"] * s["attention_layers"]
            + s["mlp"] * s["layers"] + s["head"])


def state_bytes(config: Dict, row_steps: float) -> float:
    """``row_steps`` live rows' state of every delta layer, read and
    written."""
    s = sizes(config)
    return 2.0 * row_steps * s["delta_layers"] * s["state_row_bytes"]


def decode_step_bytes(config: Dict, rows: float,
                      kv_rows_full: float) -> float:
    s = sizes(config)
    return (parameters(config) * BYTES[config["precision"]["weights"]]
            + kv_rows_full * s["row_full"]
            * BYTES[config["precision"]["kv_cache"]]
            + state_bytes(config, rows))


def decode_step_flops(config: Dict, rows: float,
                      kv_rows_full: float) -> float:
    s = sizes(config)
    return (2.0 * parameters(config) * rows
            + 7.0 * s["state_values"] * s["delta_layers"] * rows
            + 2.0 * s["per_position"] * kv_rows_full)


def _least(by_bytes: float, by_flops: float) -> Dict[str, float]:
    return {"seconds": max(by_bytes, by_flops),
            "bound": "memory" if by_bytes >= by_flops else "compute",
            "by_bytes_s": by_bytes, "by_flops_s": by_flops}


def least_step_seconds(config: Dict, peaks: Dict, rows: float,
                       kv_rows_full: float) -> Dict[str, float]:
    return _least(
        decode_step_bytes(config, rows, kv_rows_full)
        / peaks["hbm_bytes_per_s"],
        decode_step_flops(config, rows, kv_rows_full)
        / peaks["bf16_flops_per_s"])


def state_step_least_seconds(config: Dict, peaks: Dict,
                             row_steps: float) -> Dict[str, float]:
    """The state updates of ``row_steps`` live rows x steps in all delta
    layers: the state in and out, and the rule's arithmetic."""
    s = sizes(config)
    return _least(
        state_bytes(config, row_steps) / peaks["hbm_bytes_per_s"],
        7.0 * s["state_values"] * s["delta_layers"] * row_steps
        / peaks["bf16_flops_per_s"])


def scan_least_seconds(config: Dict, peaks: Dict, rows: float,
                       positions: float) -> Dict[str, float]:
    """The scans of one prefill dispatch (all its delta layers): ``rows``
    real rows, ``positions`` real token positions."""
    s = sizes(config)
    act = BYTES[config["precision"]["activations"]]
    return _least(
        (state_bytes(config, rows) + positions * s["delta_layers"]
         * s["scan_position_values"] * act) / peaks["hbm_bytes_per_s"],
        7.0 * s["state_values"] * s["delta_layers"] * positions
        / peaks["bf16_flops_per_s"])
