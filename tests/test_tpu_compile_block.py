"""Generation by blocks (ISSUE 38), compiled for a described TPU v5e at
``sdar-30b-a3b-pp7s``'s cell's size: the block decode pass and chunk prefill
hold the cache as stated and fit one chip; and the plan of the cell's
programs.
"""

from __future__ import annotations

import math
import os
import re
from dataclasses import replace

import jax
import jax.numpy as jnp
import pytest

from tests.tpu_programs import (
    _grouped_products,
    _on,
    _plane_work,
    _share_shapes,
)


# ---------------------------------------------------------------------------
# generation by blocks (ISSUE 38)
# ---------------------------------------------------------------------------

#: sdar-30b-a3b-pp7s at the cell's size: 48 slots + the scratch row x 2048.
BD_ROWS, BD_SEQ = 49, 2048
BD_PROGRAMS = {
    "block-decode-2048": lambda T, B, cfg, p, c, b: B.block_decode_step(
        cfg, p, c, b["blk"], b["row49"], b["row49"], b["flag49"],
        kv_view=2048, with_stats=True),
    # (the branch a TPU backend takes: the grouped products as the repo's
    # kernel, ISSUE 39)
    "block-decode-on-the-chip": lambda T, B, cfg, p, c, b:
        B.block_decode_step(
            replace(cfg, flash_force=True), p, c, b["blk"], b["row49"],
            b["row49"], b["flag49"], kv_view=2048, with_stats=True),
    "block-decode-256": lambda T, B, cfg, p, c, b: B.block_decode_step(
        cfg, p, c, b["blk"], b["row49"], b["row49"], b["flag49"],
        kv_view=256, with_stats=True),
    "chunk-512-at-2048": lambda T, B, cfg, p, c, b:
        T.chunk_prefill_into_cache(
            cfg, p, b["tok512"], b["row2"], b["row2"], c, b["row2"],
            kv_view=2048, stat_rows=b["row2"] != 48)[1:],
}


@pytest.mark.parametrize("program", sorted(BD_PROGRAMS))
def test_the_block_programs_hold_the_cache_as_stated_and_fit_one_chip(
        chip, program):
    """``sdar-30b-a3b-pp7s`` at the cell's size: the block decode pass
    (two blocks a row, the block that awaits its commit beside the current
    one, and the ``pending`` flag: ISSUE 48) and chunk prefill (which
    returns no logits in this family: its head is dead code).  The two planes are held at their stated bytes (49 x 2048 x
    14,336 B: 4 KV heads are not padded to a sublane tile), written where
    they lie (no plane-sized copy, both aliased to the donated buffers), and
    no layer's slice of the expert stacks is copied or converted before the
    grouped products read it (1.2 GB a layer); weights, planes, the prefix
    pool of 1024 blocks and the program's temporaries are inside a v5e's 16
    GB.  The routed products are Mosaic kernels."""
    from p2p_llm_tunnel_tpu.models import block_decode as B
    from p2p_llm_tunnel_tpu.models import transformer as T
    from p2p_llm_tunnel_tpu.models.config import get_config

    cfg = get_config("sdar-30b-a3b-pp7s")
    params, cache = _share_shapes(chip, cfg, BD_ROWS, BD_SEQ)
    batch = _on(chip, {
        "row49": jax.ShapeDtypeStruct((BD_ROWS,), jnp.int32),
        "flag49": jax.ShapeDtypeStruct((BD_ROWS,), jnp.bool_),
        "blk": jax.ShapeDtypeStruct((BD_ROWS, 2 * 4), jnp.int32),
        "row2": jax.ShapeDtypeStruct((2,), jnp.int32),
        "tok512": jax.ShapeDtypeStruct((2, 512), jnp.int32)})
    compiled = jax.jit(
        lambda p, c, b: BD_PROGRAMS[program](T, B, cfg, p, c, b),
        donate_argnums=(1,)).lower(params, cache, batch).compile()
    m, hlo = compiled.memory_analysis(), compiled.as_text()
    planes = sum(math.prod(v.shape) * 2 for v in cache.values())
    assert planes == BD_ROWS * BD_SEQ * 14336 == m.alias_size_in_bytes
    assert set(re.findall(r"bf16\[7,49,2048,4,128\]\{[^}]*\}", hlo)) == {
        "bf16[7,49,2048,4,128]{4,3,2,1,0:T(4,128)(2,1)}"}
    copies, made = _plane_work(hlo, math.prod(cache["k"].shape))
    assert copies == []
    if program.startswith("chunk"):
        assert made == []
    aliased = re.search(r"input_output_alias=\{(.*?)\}, entry", hlo).group(1)
    assert aliased.count("alias") == len(cache)
    # the experts of all layers are read where they lie
    moved = [line for line in hlo.splitlines()
             if re.search(r"= \w+\[(?:896|128),(?:2048,768|768,2048)\]", line)
             and re.search(r" (?:copy|convert|dynamic-slice)\(", line)]
    assert moved == []
    assert _grouped_products(hlo, kernel=program.endswith("on-the-chip")) >= 3
    weights = sum(math.prod(x.shape) * x.dtype.itemsize
                  for x in jax.tree.leaves(params))
    assert 9.96e9 < weights < 9.98e9  # 4,984 M parameters
    pool = 1024 * 16 * 14336
    held = (weights + planes + m.temp_size_in_bytes
            + m.output_size_in_bytes - m.alias_size_in_bytes + pool)
    assert held < 12.5 * 2 ** 30, f"{held / 2 ** 30:.2f} GiB"


def test_the_block_cell_plans_the_programs_it_planned_before_the_fusion():
    """(ISSUE 48) A block's commit rides the first pass on the block after
    it in the family's ONE decode program, at wider avals: the plan of the
    cell ``sdar-30b-a3b.blockgen-closed`` keeps its 24 programs (5 views x
    2 step counts of decode, 14 of chunk prefill; with the pool's two copy
    programs the 26 that ``setup_programs`` reads there).  The plan follows
    the engine's arguments and the block length, which ``tiny-sdar-moe``
    shares with ``sdar-30b-a3b-pp7s``: the cell's arguments over the tiny
    model's widths."""
    import json

    from p2p_llm_tunnel_tpu.engine.engine import EngineConfig, InferenceEngine
    from p2p_llm_tunnel_tpu.models.config import get_config

    with open(os.path.join(os.path.dirname(os.path.dirname(__file__)),
                           "benchmarks", "configs",
                           "sdar-30b-a3b.json")) as f:
        serve = json.load(f)["serve"]
    args = dict(zip(serve["args"][::2], serve["args"][1::2]))
    tiny, cell = get_config("tiny-sdar-moe"), get_config(serve["model"])
    assert (tiny.block_length, tiny.denoise_steps) == (
        cell.block_length, cell.denoise_steps)
    eng = InferenceEngine(engine_cfg=EngineConfig(
        model="tiny-sdar-moe", max_seq=serve["max_seq"], mux=True,
        prefix_cache=True, conv_cache=True,
        num_slots=int(args["--slots"]),
        prefix_pool_blocks=int(args["--prefix-pool-blocks"]),
        prefill_chunk=int(args["--prefill-chunk"]),
        prefill_rows=int(args["--prefill-rows"])))
    plan = eng.warmup_plan()
    assert len(plan) == len(set(plan)) == 24
    decode = [shape for kind, shape in plan if kind == "decode"]
    assert sorted(decode) == [(view, steps)
                              for view in (128, 256, 512, 1024, 2048)
                              for steps in (4, 8)]
    assert {kind for kind, _ in plan} == {"decode", "chunk"}
