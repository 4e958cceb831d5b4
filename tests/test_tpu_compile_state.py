"""A recurrent state a slot beside the KV planes (ISSUE 44), compiled for a
described TPU v5e at the cells' sizes (``nemotron-3-nano-30b-a3b-ep2s``;
``granite-4.0-h-micro`` whole): the state updated where it lies by
``ssm_step_rows``, the convolution's tails, and the share inside one chip.
"""

from __future__ import annotations

import math
import re
from dataclasses import replace

import jax
import jax.numpy as jnp
import pytest

from p2p_llm_tunnel_tpu.ops.pallas_decode_attention import ROWS_KERNEL
from tests.tpu_programs import (
    SSM_ROWS,
    SSM_SEQ,
    _grouped_products,
    _leaf_moves,
    _on,
    _share_shapes,
    _ssm_burst,
)


SSM_LEAVES = {"k": (2, SSM_ROWS, SSM_SEQ, 256), "v": (2, SSM_ROWS, SSM_SEQ, 256),
              "ssm": (6, SSM_ROWS, 64, 64, 128),
              "conv": (6, SSM_ROWS, 3 * 6144)}
SSM_PROGRAMS = {
    # (the branch a TPU backend takes: the rows kernel over the attention
    # planes, the grouped kernel over experts held 1920 wide)
    "decode-on-the-chip": lambda T, cfg, p, c, b: T.decode_step(
        replace(cfg, flash_force=True), p, c, b["rows"], b["rows"],
        kv_view=SSM_SEQ, with_stats=True),
    # (the same as the engine's burst holds it: the cache a carry of a scan
    # over the steps, the state kernel's aliased leaf inside the loop)
    "burst-on-the-chip": lambda T, cfg, p, c, b: _ssm_burst(
        T, replace(cfg, flash_force=True), p, c, b["rows"], b["rows"]),
}


@pytest.mark.parametrize("program", sorted(SSM_PROGRAMS))
def test_the_state_is_updated_where_it_lies_and_the_share_fits(chip, program):
    """``nemotron-3-nano-30b-a3b-ep2s`` at the cell's shapes, as a TPU
    backend runs it: the four leaves are the donated ones, no program makes
    a copy of the 1.6 GB state leaf around a layer's update (ISSUE 45: the
    update is the kernel ``ssm_step_rows`` over the live rows of the leaf,
    aliased in and out, six calls a step, in a scan over the steps too) nor
    of the convolution's tails (ISSUE 47: a slot's tail is lanes of one row
    and a layer's write a slice update of the leaf in its one layout),
    the grouped products are Mosaic kernels over experts held in whole lane
    tiles (the chip's compiler refuses a DMA of 1856 columns),
    and weights, cache, 96 snapshots, the pool and the program's own
    temporaries are inside a v5e's 16 GB."""
    from p2p_llm_tunnel_tpu.models import transformer as T
    from p2p_llm_tunnel_tpu.models.config import get_config
    from p2p_llm_tunnel_tpu.models.ssm_moe import state_bytes_per_slot

    cfg = get_config("nemotron-3-nano-30b-a3b-ep2s")
    params, cache = _share_shapes(chip, cfg, SSM_ROWS, SSM_SEQ)
    assert {k: v.shape for k, v in cache.items()} == SSM_LEAVES
    batch = _on(chip, {
        "rows": jax.ShapeDtypeStruct((SSM_ROWS,), jnp.int32)})
    compiled = jax.jit(
        lambda p, c, b: SSM_PROGRAMS[program](T, cfg, p, c, b),
        donate_argnums=(1,)).lower(params, cache, batch).compile()
    hlo = compiled.as_text()
    dims = ",".join(str(d) for d in SSM_LEAVES["ssm"])
    for leaf in ("ssm", "conv"):
        assert _leaf_moves(hlo, SSM_LEAVES[leaf]) == [], leaf
    aliased = re.search(r"input_output_alias=\{(.*?)\}, entry", hlo).group(1)
    assert aliased.count("alias") == len(cache)
    assert _grouped_products(hlo, kernel=True) >= 2
    from p2p_llm_tunnel_tpu.ops.pallas_ssm_step import SSM_STEP_KERNEL

    calls = [ln for ln in hlo.splitlines()
             if "custom-call(" in ln and f"%{SSM_STEP_KERNEL}" in ln]
    assert len(calls) == 6 and all(f"f32[{dims}]" in ln for ln in calls)
    m = compiled.memory_analysis()
    weights = sum(math.prod(x.shape) * x.dtype.itemsize
                  for x in jax.tree.leaves(params))
    # 3,926 M published parameters, the experts held 1920 wide for 1856
    assert 8.06e9 < weights < 8.08e9
    leaves = sum(math.prod(v.shape) * v.dtype.itemsize for v in cache.values())
    assert leaves == SSM_ROWS * (2 * 1024 * SSM_SEQ
                                 + state_bytes_per_slot(cfg))
    snapshots = 97 * state_bytes_per_slot(cfg)
    pool = 4096 * 16 * 2048
    held = (m.argument_size_in_bytes + m.temp_size_in_bytes
            + m.output_size_in_bytes - m.alias_size_in_bytes + snapshots
            + pool)
    assert held < 14.5 * 2 ** 30, f"{held / 2 ** 30:.2f} GiB"


@pytest.mark.slow  # 26 s alone: this file is tier-1's longest (ISSUE 46)
def test_the_whole_hybrid_decodes_through_both_kernels_and_fits(chip):
    """``granite-4.0-h-micro`` whole, at its cell's shapes (64 slots + the
    scratch row x 2560), as a TPU backend runs a decode burst (ISSUE 46):
    the state kernel at ONE group in all 36 Mamba-2 layers over the 4.9 GB
    leaf, aliased in and out (no copy of it), the convolution's tails
    written where their 61 MB leaf lies (ISSUE 47: with the tail's positions
    an axis of their own this program, the one short of memory, moved the
    whole leaf through another layout and back in every layer: 70 copies),
    the rows kernel over planes
    whose rows are 8 KV heads of 64 side by side in the 4 attention layers
    (a head of 64 is half a lane tile: Mosaic takes it), and 6.38 GB of
    weights, the cache, 17 snapshots and the scratch one, the pool's 2,048
    blocks and the step's temporaries inside a v5e's 16 GB."""
    from p2p_llm_tunnel_tpu.models import transformer as T
    from p2p_llm_tunnel_tpu.models.config import get_config
    from p2p_llm_tunnel_tpu.models.ssm_moe import state_bytes_per_slot
    from p2p_llm_tunnel_tpu.ops.pallas_ssm_step import SSM_STEP_KERNEL

    rows, seq = 65, 2560
    cfg = get_config("granite-4.0-h-micro")
    params, cache = _share_shapes(chip, cfg, rows, seq)
    assert {k: v.shape for k, v in cache.items()} == {
        "k": (4, rows, seq, 512), "v": (4, rows, seq, 512),
        "ssm": (36, rows, 64, 64, 128), "conv": (36, rows, 3 * 4352)}
    batch = _on(chip, {"rows": jax.ShapeDtypeStruct((rows,), jnp.int32)})
    compiled = jax.jit(
        lambda p, c, b: _ssm_burst(T, replace(cfg, flash_force=True), p, c,
                                   b["rows"], b["rows"]),
        donate_argnums=(1,)).lower(params, cache, batch).compile()
    hlo = compiled.as_text()
    for leaf in ("ssm", "conv"):
        assert _leaf_moves(hlo, cache[leaf].shape) == [], leaf
    aliased = re.search(r"input_output_alias=\{(.*?)\}, entry", hlo).group(1)
    assert aliased.count("alias") == len(cache)
    calls = [ln for ln in hlo.splitlines() if "custom-call(" in ln]
    assert sum(f"%{SSM_STEP_KERNEL}" in ln for ln in calls) == 36
    assert sum(f"%{ROWS_KERNEL}" in ln for ln in calls) == 4
    weights = sum(math.prod(x.shape) * x.dtype.itemsize
                  for x in jax.tree.leaves(params))
    assert 6.38e9 < weights < 6.39e9  # 3,191 M parameters, the head tied
    m = compiled.memory_analysis()
    held = (m.argument_size_in_bytes + m.temp_size_in_bytes
            + m.output_size_in_bytes - m.alias_size_in_bytes
            + 18 * state_bytes_per_slot(cfg) + 2048 * 16 * 8192)
    assert held < 14.5 * 2 ** 30, f"{held / 2 ** 30:.2f} GiB"
