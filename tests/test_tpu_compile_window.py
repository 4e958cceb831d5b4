"""The families with window rings beside full planes, compiled for a described
TPU v5e at their cells' sizes (``mimo-v2-flash-ep16s``, ISSUE 34;
``laguna-s-2.1-ep8s``, ISSUE 55): the four planes held at their stated bytes
and written where they lie, and the share inside one chip.
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
    SWA_PROGRAMS,
    SWA_RING,
    SWA_ROWS,
    SWA_SEQ,
    _grouped_products,
    _no_layer_of_a_plane,
    _on,
    _plane_work,
    _share_shapes,
    _swa_compiled,
)


@pytest.mark.parametrize("program", sorted(SWA_PROGRAMS))
def test_the_four_planes_are_written_where_they_lie(chip, program):
    """Every serving program of ``mimo-v2-flash-ep16s`` at the cell's shapes
    (the feed-forwards narrowed: they touch no plane): no plane-sized
    ``copy`` around a write of any of the four planes (keys 192 wide are one
    and a half lane tiles a head: a row is the KV heads side by side, 768 or
    1,536 values, whole tiles), every plane written is the donated one, and
    in chunk prefill, where the planes are no carry of the layer loops, no
    loop body makes one."""
    _, cache, compiled = _swa_compiled(
        chip, program, ffn_dim=512, moe_ffn_dim=128, vocab_size=1024)
    hlo = compiled.as_text()
    for name, plane in cache.items():
        copies, made = _plane_work(hlo, math.prod(plane.shape))
        assert copies == [], name
        if program.startswith("chunk"):
            assert made == [], name
    assert "while(" in hlo  # the four window layers are one loop to look into
    aliased = re.search(r"input_output_alias=\{(.*?)\}, entry", hlo).group(1)
    assert aliased.count("alias") == len(cache)


@pytest.mark.parametrize("program", ["decode-8192", "decode-on-the-chip",
                                     "chunk-512-at-8192", "prefill-128"])
def test_the_mimo_share_fits_one_chip_at_its_stated_bytes(chip, program):
    """``mimo-v2-flash-ep16s`` at the cell's size: the compiler holds the
    four planes at their stated bytes to the byte (no width of 192 padded to
    256, no head axis padded to a sublane tile: 2.66 GB, where a uniform
    cache read under a mask would be 49 x 8192 x 30,720 B = 12.3 GB), and
    weights, planes, the prefix pool of 2048 blocks and the program's own
    temporaries are inside a v5e's 16 GB.  The routed products are Mosaic
    kernels."""
    cfg, cache, compiled = _swa_compiled(chip, program)
    m = compiled.memory_analysis()
    planes = sum(math.prod(v.shape) * 2 for v in cache.values())
    assert planes == SWA_ROWS * (2 * 2560 * SWA_SEQ + 5 * 5120 * SWA_RING)
    from p2p_llm_tunnel_tpu.models.transformer import init_params

    weights = sum(math.prod(x.shape) * x.dtype.itemsize for x in
                  jax.tree.leaves(jax.eval_shape(
                      lambda: init_params(cfg, jax.random.PRNGKey(0)))))
    assert 6.85e9 < weights < 6.87e9
    # (the batch's few integers are the rest of the arguments)
    assert 0 <= m.argument_size_in_bytes - weights - planes < 2 ** 20
    tiled = set(re.findall(
        r"bf16\[[25],49,(?:8192|640),\d+\]\{3,2,1,0:T\(8,128\)\(2,1\)\}",
        compiled.as_text()))
    assert len(tiled) == 4, tiled
    pool = 2048 * 16 * 30720
    held = (m.argument_size_in_bytes + m.temp_size_in_bytes
            + m.output_size_in_bytes - m.alias_size_in_bytes + pool)
    assert held < 13.5 * 2 ** 30, f"{held / 2 ** 30:.2f} GiB"
    assert _grouped_products(
        compiled.as_text(), kernel=program == "decode-on-the-chip") >= 3


# ---------------------------------------------------------------------------
# head counts by layer kind and a gate a head (ISSUE 55)
# ---------------------------------------------------------------------------

#: laguna-s-2.1-ep8s at the cell's size: 64 slots + the scratch row x 6144,
#: rings of 1024 (window 512 + segments of 512); a row of 8 x 128 = 1,024
#: values in both kinds.
LAG_ROWS, LAG_SEQ, LAG_RING = 65, 6144, 1024
LAG_PLANES = {"k": (2, LAG_SEQ, 1024), "v": (2, LAG_SEQ, 1024),
              "wk": (6, LAG_RING, 1024), "wv": (6, LAG_RING, 1024)}
LAG_PROGRAMS = {
    # as a TPU backend runs it: the full layers (48 heads, 6 a KV head) on
    # the rows kernel, the grouped products on the grouped kernel
    "decode-on-the-chip": lambda T, cfg, p, c, b: T.decode_step(
        replace(cfg, flash_force=True), p, c, b["rows"], b["rows"],
        kv_view=LAG_SEQ, with_stats=True),
    "chunk-512-at-6144": lambda T, cfg, p, c, b: T.chunk_prefill_into_cache(
        replace(cfg, flash_force=True), p, b["tok512"], b["row2"], b["row2"],
        c, b["row2"], kv_view=LAG_SEQ, stat_rows=b["row2"] != 64),
}


def _laguna_compiled(chip, program, **small):
    from p2p_llm_tunnel_tpu.models import transformer as T
    from p2p_llm_tunnel_tpu.models.config import get_config

    cfg = get_config("laguna-s-2.1-ep8s", ring_positions=LAG_RING, **small)
    params, cache = _share_shapes(chip, cfg, LAG_ROWS, LAG_SEQ)
    assert {k: (v.shape[0],) + v.shape[2:] for k, v in cache.items()} \
        == LAG_PLANES
    batch = _on(chip, {
        "rows": jax.ShapeDtypeStruct((LAG_ROWS,), jnp.int32),
        "row2": jax.ShapeDtypeStruct((2,), jnp.int32),
        "tok512": jax.ShapeDtypeStruct((2, 512), jnp.int32)})
    return cfg, cache, jax.jit(
        lambda p, c, b: LAG_PROGRAMS[program](T, cfg, p, c, b),
        donate_argnums=(1,)).lower(params, cache, batch).compile()


@pytest.mark.parametrize("program", sorted(LAG_PROGRAMS))
def test_the_laguna_share_holds_its_planes_as_stated_and_fits(chip, program):
    """``laguna-s-2.1-ep8s`` at the cell's size, as a TPU backend runs it:
    the four planes at their stated bytes to the byte (65 x (2 x 6,144 + 6 x
    1,024) x 4,096 B = 4.91 GB), no plane-sized ``copy`` around a row write
    of any of them, every plane written is the donated one and in chunk
    prefill no loop body makes one; weights (2,843 M parameters), planes,
    the prefix pool of 2,048 blocks and the program's own temporaries are
    inside a v5e's 16 GB.  Decode holds the rows kernel in each of the two
    runs with a full layer (48 query heads on 8 KV heads: a group of 6) and
    the routed products are Mosaic kernels."""
    cfg, cache, compiled = _laguna_compiled(chip, program)
    hlo = compiled.as_text()
    m = compiled.memory_analysis()
    planes = sum(math.prod(v.shape) * 2 for v in cache.values())
    assert planes == LAG_ROWS * (2 * LAG_SEQ + 6 * LAG_RING) * 4096
    for name, plane in cache.items():
        copies, made = _plane_work(hlo, math.prod(plane.shape))
        assert copies == [], name
        if program.startswith("chunk"):
            assert made == [], name
    aliased = re.search(r"input_output_alias=\{(.*?)\}, entry", hlo).group(1)
    assert aliased.count("alias") == len(cache)
    from p2p_llm_tunnel_tpu.models.transformer import init_params

    weights = sum(math.prod(x.shape) * x.dtype.itemsize for x in
                  jax.tree.leaves(jax.eval_shape(
                      lambda: init_params(cfg, jax.random.PRNGKey(0)))))
    assert 5.68e9 < weights < 5.70e9
    assert 0 <= m.argument_size_in_bytes - weights - planes < 2 ** 20
    tiled = set(re.findall(
        r"bf16\[[26],65,(?:6144|1024),1024\]\{3,2,1,0:T\(8,128\)\(2,1\)\}",
        hlo))
    assert len(tiled) == 2, tiled  # keys and values are equally wide here
    pool = 2048 * 16 * 32768
    held = (m.argument_size_in_bytes + m.temp_size_in_bytes
            + m.output_size_in_bytes - m.alias_size_in_bytes + pool)
    assert held < 14.5 * 2 ** 30, f"{held / 2 ** 30:.2f} GiB"
    assert _grouped_products(hlo, kernel=True) >= 3
    if program.startswith("decode"):
        # the kernel in all four runs (ISSUE 56: the rings too, 72 query
        # heads on 8 KV heads: a group of 9) and no layer of a plane or of a
        # ring sliced out: ``bf16[1,65,1024,1024]`` as a slice and a copy
        # were eight of the ten largest operations of the cell's first line
        assert hlo.count(ROWS_KERNEL) >= 4
        for seq in (LAG_SEQ, LAG_RING):
            _no_layer_of_a_plane(hlo, LAG_ROWS, seq, (1024,))
