"""Operations and bytes the family of a mixer and then a dense gated MLP a
layer (Mamba-2 state-space layers and attention by a list of layer types,
a head tied to the embedding) needs, from the configuration's own sizes: the
yardstick a measured decode step, the measured state updates and the measured
scans are held against.  ``ssm_moe_roofline.py`` is the count of the family
of one mixer a layer, whose keys a configuration of this family does not
have; this is ``granite_hybrid_reference.py``'s.  It counts the work,
whatever implements it.

A decode step of ``rows`` live sequences reads, once each, as stored:

- every Mamba-2 layer's ``W_in``, ``W_out`` and convolution, every attention
  layer's ``W_q``, ``W_k``, ``W_v``, ``W_o``, every layer's MLP (``W_i`` of
  two ``shared_intermediate_size`` and ``W_o``);
- the embedding ONCE, as the output head over the whole vocabulary (the
  rows a step looks up for its tokens are ``rows`` of 100,352 and are not
  counted);
- the cached keys and values its attention has to read (``kv_rows_full``:
  positions x attention layers, counted by the program from the rows'
  positions), each ``2 x KV heads x head`` values;
- **each live row's recurrent state of every Mamba-2 layer, and writes it
  back**: ``H x P x N`` values of the state's type and the convolution's
  last ``K - 1`` inputs (``state_row_bytes``), twice.  Live rows, never
  slots.

Its arithmetic (a multiply-add counts twice): every row through the weights
above; each query head against each position read; and ``5 x H x P x N``
for each row and Mamba-2 layer (the decay, the outer product's product and
its sum into the state, the contraction with ``C``).

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
    kinds = list(config["layer_types"])[: int(config["num_hidden_layers"])]
    h, kv = (int(config["num_attention_heads"]),
             int(config["num_key_value_heads"]))
    hd = dm // h
    sh, sp = int(config["mamba_n_heads"]), int(config["mamba_d_head"])
    g, n, k = (int(config["mamba_n_groups"]), int(config["mamba_d_state"]),
               int(config["mamba_d_conv"]))
    inner = sh * sp
    conv_dim = inner + 2 * g * n
    precision = config["precision"]
    return {
        "layers": len(kinds),
        "mamba_layers": kinds.count("mamba"),
        "attention_layers": kinds.count("attention"),
        "mamba": dm * (inner + conv_dim + sh) + inner * dm
        + (k + 1) * conv_dim,
        "attention": dm * h * hd + 2 * dm * kv * hd + h * hd * dm,
        "mlp": 3 * dm * int(config["shared_intermediate_size"]),
        "head": dm * int(config["vocab_size"]),
        "row_full": 2 * kv * hd,
        "per_position": 2 * h * hd,
        "state_values": sh * sp * n,
        "state_row_bytes": sh * sp * n * BYTES[config.get(
            "state_type", "float32")]
        + (k - 1) * conv_dim * BYTES[precision["activations"]],
        "scan_position_values": 2 * inner + conv_dim + sh,
    }


def parameters(config: Dict) -> float:
    """Every weight a step reads: the published model's parameters but for
    its norms, time-step biases, ``A`` and ``D`` (a few thousand a layer)."""
    s = sizes(config)
    return (s["mamba"] * s["mamba_layers"]
            + s["attention"] * s["attention_layers"]
            + s["mlp"] * s["layers"] + s["head"])


def state_bytes(config: Dict, row_steps: float) -> float:
    """``row_steps`` live rows' state of every Mamba-2 layer, read and
    written."""
    s = sizes(config)
    return 2.0 * row_steps * s["mamba_layers"] * s["state_row_bytes"]


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
            + 5.0 * s["state_values"] * s["mamba_layers"] * rows
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
    """The state updates of ``row_steps`` live rows x steps in all Mamba-2
    layers: the state in and out, and the recurrence's arithmetic."""
    s = sizes(config)
    return _least(
        state_bytes(config, row_steps) / peaks["hbm_bytes_per_s"],
        5.0 * s["state_values"] * s["mamba_layers"] * row_steps
        / peaks["bf16_flops_per_s"])


def scan_least_seconds(config: Dict, peaks: Dict, rows: float,
                       positions: float) -> Dict[str, float]:
    """The scans of one prefill dispatch (all its Mamba-2 layers): ``rows``
    real rows, ``positions`` real token positions."""
    s = sizes(config)
    act = BYTES[config["precision"]["activations"]]
    return _least(
        (state_bytes(config, rows) + positions * s["mamba_layers"]
         * s["scan_position_values"] * act) / peaks["hbm_bytes_per_s"],
        5.0 * s["state_values"] * s["mamba_layers"] * positions
        / peaks["bf16_flops_per_s"])
