"""Operations and bytes the family that generates by masked denoising over
blocks needs for one decode PASS, from the configuration's own sizes: the
yardstick a measured pass and the measured grouped expert products are held
against.  The dense family's count is ``roofline.py``, the latent family's
``mla_moe_roofline.py``, the window-and-full family's
``swa_moe_roofline.py``; this is ``block_diffusion_reference.py``'s.  It
counts the work, whatever implements it.

A pass of ``rows`` sequences carries the current block of ``block_length``
positions of each and, for the ``pending_rows`` of them whose block before
awaits its commit, that block beside it (since PR 48 the commit rides the
next block's first denoise pass; the record counts such rows,
``row_commits_fused``, so the count is read and not taken to be every row).
It reads, once each, as stored:

- every layer's attention weights (``W_q``, ``W_k``, ``W_v``, ``W_o``; the
  two QK norm weights of ``head_dim`` values are left out) and its router;
- the three matrices of each expert that the pass's routing TOUCHED: a
  number the program counts (``moe_experts_touched``, summed over the
  layers), never more than ``num_experts`` a layer;
- the output head, once (the embedding is gathered, a row a position);
- the cached keys and values its attention has to read: a row's prefix below
  its block, once for the block's queries together (a pending block's
  queries ride the same read).  The program counts ``kv_rows_full`` =
  positions x layers SEEN by the current block's queries (each of its
  ``block_length`` queries sees ``base + block_length``), so the positions
  that have to be read are that over ``block_length``; each
  ``2 x KV heads x head_dim`` values;

and writes ``block_length`` such rows a layer for each row that commits a
block in the pass (``commit_rows``): in a pass of its own
(``row_passes_commit``, the schedule before PR 48) or on the way of a
denoise pass (``row_commits_fused``).

Its arithmetic (a multiply-add counts twice): every position the pass
carries, ``(rows + pending_rows) x block_length``, through the attention
weights and the router; the ``block_length / denoising_steps`` positions a
row's pass decides through the head; each query head of the CURRENT block
against each position it sees (``head_dim`` wide for the score and again for
the sum; a pending block's scores against its prefix are left out, the
record does not say which rows carried one: an undercount, so a share reads
low by it and never high, of arithmetic that is a tenth of what bounds the
pass); and ``6 x hidden x expert width`` for each assignment.

**A block, not a pass, is what a client is served.**  A block of
``block_length`` tokens needs ``denoising_steps`` passes of its row at the
least, so the window's decided tokens need ``row_passes_needed`` row-passes;
a schedule that runs more of them (a commit pass of its own, a pass on a row
that has finished) spends device time no token needed.
"""

from __future__ import annotations

from typing import Dict

BYTES = {"bfloat16": 2.0, "int8": 1.0}


def sizes(config: Dict) -> Dict[str, float]:
    dm, h = int(config["hidden_size"]), int(config["num_attention_heads"])
    kv, dk = int(config["num_key_value_heads"]), int(config["head_dim"])
    block = int(config["block_length"])
    return {
        "layers": int(config["num_hidden_layers"]),
        "attention": dm * h * dk + 2 * dm * kv * dk + h * dk * dm,
        "router": dm * int(config["num_experts"]),
        "expert": 3 * dm * int(config["moe_intermediate_size"]),
        "experts": int(config["num_experts"]),
        "head": dm * int(config["vocab_size"]),
        "kv_row": 2 * kv * dk,
        "per_position": 2 * h * dk,
        "block": block,
        "group": block // int(config["denoising_steps"]),
    }


def pass_bytes(config: Dict, kv_rows_full: float, experts_touched: float,
               commit_rows: float) -> float:
    """``kv_rows_full``: positions x layers the current blocks' queries see;
    ``experts_touched``: experts that got a token, summed over the layers;
    ``commit_rows``: rows that write a block's K/V in this pass, alone or on
    the way of a denoise pass."""
    s = sizes(config)
    touched = min(experts_touched, s["experts"] * s["layers"])
    weights = ((s["attention"] + s["router"]) * s["layers"]
               + s["expert"] * touched + s["head"])
    cache = (kv_rows_full / s["block"]
             + commit_rows * s["block"] * s["layers"]) * s["kv_row"]
    return (weights * BYTES[config["precision"]["weights"]]
            + cache * BYTES[config["precision"]["kv_cache"]])


def pass_flops(config: Dict, rows: float, kv_rows_full: float,
               assignments: float, pending_rows: float = 0.0) -> float:
    """``rows``: rows with a current block in the pass; ``pending_rows``:
    those of them that carry the block before beside it."""
    s = sizes(config)
    per_position = (s["attention"] + s["router"]) * s["layers"]
    return (2.0 * per_position * (rows + pending_rows) * s["block"]
            + 2.0 * s["head"] * rows * s["group"]
            + 2.0 * s["per_position"] * kv_rows_full
            + 2.0 * s["expert"] * assignments)


def least_pass_seconds(config: Dict, peaks: Dict, rows: float,
                       kv_rows_full: float, experts_touched: float,
                       assignments: float, commit_rows: float,
                       pending_rows: float = 0.0) -> Dict[str, float]:
    by_bytes = pass_bytes(config, kv_rows_full, experts_touched,
                          commit_rows) / peaks["hbm_bytes_per_s"]
    by_flops = pass_flops(config, rows, kv_rows_full, assignments,
                          pending_rows) / peaks["bf16_flops_per_s"]
    return {"seconds": max(by_bytes, by_flops),
            "bound": "memory" if by_bytes >= by_flops else "compute",
            "by_bytes_s": by_bytes, "by_flops_s": by_flops}


def row_passes_needed(config: Dict, tokens_decided: float) -> float:
    """The fewest row-passes that decide so many tokens: a row's pass
    decides ``block_length / denoising_steps`` of its block's offsets."""
    return tokens_decided / sizes(config)["group"]


def block_share(config: Dict, least_pass_s: float, pass_s: float,
                tokens_decided: float, row_passes: float) -> float:
    """Share (%) of the roofline per delivered block: the least time of the
    passes the decided tokens need over the time of the passes that ran.
    ``row_passes`` row-passes ran, each in a pass that took ``pass_s`` where
    the chip needs ``least_pass_s``; the share of a pass, times needed over
    ran."""
    return (100.0 * least_pass_s / pass_s
            * row_passes_needed(config, tokens_decided) / row_passes)


def experts_least_seconds(config: Dict, peaks: Dict, experts_touched: float,
                          assignments: float) -> Dict[str, float]:
    """The grouped products of one dispatch (all its layers and passes):
    each touched expert's three matrices read once, each assignment's row in
    and out of them (hidden in, hidden out, the expert width out and in
    again), and its multiply-adds."""
    s = sizes(config)
    dm = int(config["hidden_size"])
    width = int(config["moe_intermediate_size"])
    by_bytes = (s["expert"] * experts_touched
                * BYTES[config["precision"]["weights"]]
                + assignments * (2 * dm + 4 * width)
                * BYTES[config["precision"]["activations"]]) \
        / peaks["hbm_bytes_per_s"]
    by_flops = 2.0 * s["expert"] * assignments / peaks["bf16_flops_per_s"]
    return {"seconds": max(by_bytes, by_flops), "by_bytes_s": by_bytes,
            "by_flops_s": by_flops}
