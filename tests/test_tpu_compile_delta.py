"""The delta rule's matrix state (ISSUE 51, 52), compiled for a described TPU
v5e at ``olmo-hybrid-7b``'s cell's shapes: ``delta_step_rows`` alone over the
aliased leaf, and the whole hybrid's decode burst inside one chip.
"""

from __future__ import annotations

import math
import re
from dataclasses import replace

import jax
import jax.numpy as jnp
import pytest

from p2p_llm_tunnel_tpu.ops.pallas_decode_attention import ROWS_KERNEL
from tests.tpu_programs import _leaf_moves, _on, _share_shapes, _ssm_burst


def test_the_delta_step_kernel_compiles_for_v5e_and_holds_the_leaf_once(chip):
    """``delta_step_rows`` alone at ``olmo-hybrid-7b``'s cell's shapes (the
    leaf ``[12, 65, 30, 48, 384]``, blocks of 15 heads: ISSUE 52), as the
    chip's compiler takes it: Mosaic accepts the butterflies, the lane
    rotations of 192 in rows of 384 and the rows written a sublane at a
    time; the 1.7 GB leaf is aliased in and out, nothing copies it and no
    temporary of a layer's 144 MB slice stands beside it."""
    from p2p_llm_tunnel_tpu.ops.pallas_delta_step import (
        DELTA_STEP_KERNEL,
        delta_step_rows,
        heads_blocks,
    )

    rows, h, dk, dv = 65, 30, 96, 192
    assert heads_blocks(h, 48, 384, dv) == (5, 15)
    f32 = jnp.float32
    args = _on(chip, (
        jax.ShapeDtypeStruct((12, rows, h, 48, 384), f32),
        jax.ShapeDtypeStruct((), jnp.int32),
        jax.ShapeDtypeStruct((1 + rows,), jnp.int32),
        jax.ShapeDtypeStruct((rows, h, dk), f32),
        jax.ShapeDtypeStruct((rows, h, dk), f32),
        jax.ShapeDtypeStruct((rows, h, dv), f32),
        jax.ShapeDtypeStruct((rows, h), f32),
        jax.ShapeDtypeStruct((rows, h), f32)))
    compiled = jax.jit(delta_step_rows, donate_argnums=(0,)).lower(
        *args).compile()
    hlo = compiled.as_text()
    assert _leaf_moves(hlo, args[0].shape) == []
    calls = [ln for ln in hlo.splitlines()
             if "custom-call(" in ln and f"%{DELTA_STEP_KERNEL}" in ln]
    assert len(calls) == 1 and "f32[12,65,30,48,384]" in calls[0]
    m = compiled.memory_analysis()
    assert m.alias_size_in_bytes >= 12 * rows * h * 48 * 384 * 4
    assert m.temp_size_in_bytes < 2 ** 20, m.temp_size_in_bytes


@pytest.mark.slow  # 20 s alone: this file is tier-1's longest (ISSUE 46)
def test_the_delta_hybrid_decodes_where_its_state_lies_and_fits(chip):
    """``olmo-hybrid-7b`` at its cell's shapes (64 slots + the scratch row x
    1024), as a TPU backend runs a decode burst (ISSUE 51): the delta
    state's leaf ``[12, 65, 30, 48, 384]`` (a head's ``[96, 192]`` two rows
    side by side: whole ``(8, 128)`` tiles, no padding) and the tail's
    ``[12, 65, 34560]`` are the donated ones, updated where they lie (no
    copy of either, and the step's temporaries stay far under a layer's
    slice of the state: since ISSUE 52 the update is ``delta_step_rows``
    over the live rows of the aliased leaf, twelve calls a step, and ``k``
    is spread over the lanes in VMEM), the rows kernel over planes whose rows are 30 KV heads of 128
    side by side in the 4 attention layers, and 8.2 GB of weights, the
    cache, 17 snapshots, the pool's 512 blocks and the step's temporaries
    inside a v5e's 16 GB."""
    from p2p_llm_tunnel_tpu.models import transformer as T
    from p2p_llm_tunnel_tpu.models.config import get_config
    from p2p_llm_tunnel_tpu.models.ssm_moe import state_bytes_per_slot

    rows, seq = 65, 1024
    cfg = get_config("olmo-hybrid-7b")
    params, cache = _share_shapes(chip, cfg, rows, seq)
    assert {k: v.shape for k, v in cache.items()} == {
        "k": (4, rows, seq, 3840), "v": (4, rows, seq, 3840),
        "delta": (12, rows, 30, 48, 384), "dconv": (12, rows, 3 * 11520)}
    batch = _on(chip, {"rows": jax.ShapeDtypeStruct((rows,), jnp.int32)})
    compiled = jax.jit(
        lambda p, c, b: _ssm_burst(T, replace(cfg, flash_force=True), p, c,
                                   b["rows"], b["rows"], seq=seq),
        donate_argnums=(1,)).lower(params, cache, batch).compile()
    hlo = compiled.as_text()
    for leaf in ("delta", "dconv"):
        assert _leaf_moves(hlo, cache[leaf].shape) == [], leaf
    aliased = re.search(r"input_output_alias=\{(.*?)\}, entry", hlo).group(1)
    assert aliased.count("alias") == len(cache)
    calls = [ln for ln in hlo.splitlines() if "custom-call(" in ln]
    assert sum(f"%{ROWS_KERNEL}" in ln for ln in calls) == 4
    # the twelve delta layers' updates: the kernel over the live rows
    assert sum("%delta_step_rows" in ln for ln in calls) == 12
    weights = sum(math.prod(x.shape) * x.dtype.itemsize
                  for x in jax.tree.leaves(params))
    assert 8.20e9 < weights < 8.21e9  # 4,101 M parameters
    m = compiled.memory_analysis()
    # a layer's slice of the state is 144 MB: nothing of that size stands
    # beside it
    assert m.temp_size_in_bytes < 64 * 2 ** 20, m.temp_size_in_bytes
    held = (m.argument_size_in_bytes + m.temp_size_in_bytes
            + m.output_size_in_bytes - m.alias_size_in_bytes
            + 17 * state_bytes_per_slot(cfg) + 512 * 16 * 61440)
    assert held < 14.5 * 2 ** 30, f"{held / 2 ** 30:.2f} GiB"
