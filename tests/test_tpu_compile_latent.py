"""The latent-attention family, compiled for a described TPU v5e at
``sarvam-105b-ep4s``'s cell's size: chunk prefill makes no latent plane in
its layer loops, and the share's programs fit one chip.
"""

from __future__ import annotations

import math
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


@pytest.mark.parametrize("kv,rung,t,view", [
    (None, 8, 128, 1024), ("int8", 8, 128, 1024),
    # the cell's own dispatches: the ladder under --prefill-rows 2
    (None, 1, 512, 2048), (None, 2, 512, 2048),
])
def test_chunk_prefill_makes_no_latent_plane_in_its_layer_loops(
        chip, kv, rung, t, view):
    """The same guard on the latent planes (sarvam-105b's share: 33 rows x
    4096 of 512 latent values a layer and of 128 rope-key values a pair of
    layers; two layer scans): no plane-sized ``copy``, neither loop makes a
    plane, and the planes written are the donated ones.  One plane of 576
    values a row failed this: the compiler kept it sequence-minor and
    converted it whole, there and back, around every row write."""
    from p2p_llm_tunnel_tpu.models.config import get_config
    from p2p_llm_tunnel_tpu.models.transformer import (
        chunk_prefill_into_cache,
    )

    cfg = get_config("sarvam-105b-ep4s", ffn_dim=512, moe_ffn_dim=128,
                     vocab_size=1024)
    params, cache = _share_shapes(chip, cfg, 33, 4096, kv)
    tokens, row = _on(chip, (jax.ShapeDtypeStruct((rung, t), jnp.int32),
                             jax.ShapeDtypeStruct((rung,), jnp.int32)))
    hlo = jax.jit(
        lambda p, c, tok, lengths, starts, slots: chunk_prefill_into_cache(
            cfg, p, tok, lengths, starts, c, slots, kv_view=view),
        donate_argnums=(1,),
    ).lower(params, cache, tokens, row, row, row).compile().as_text()
    for plane in ("c", "kr"):
        copies, made = _plane_work(hlo, math.prod(cache[plane].shape))
        assert copies == [], plane
        assert made == [], plane
    assert "while(" in hlo  # the expert layers are still one loop to look into
    aliased = re.search(r"input_output_alias=\{(.*?)\}, entry", hlo).group(1)
    assert aliased.count("alias") == len(cache)


SHARE_PROGRAMS = {
    "decode-4096": lambda T, cfg, p, c, b: T.decode_step(
        cfg, p, c, b["row33"], b["row33"], kv_view=4096),
    "chunk-512-at-4096": lambda T, cfg, p, c, b: T.chunk_prefill_into_cache(
        cfg, p, b["tok512"], b["row2"], b["row2"], c, b["row2"],
        kv_view=4096),
    "chunk-512-at-2048": lambda T, cfg, p, c, b: T.chunk_prefill_into_cache(
        cfg, p, b["tok512"], b["row2"], b["row2"], c, b["row2"],
        kv_view=2048),
    "prefill-128": lambda T, cfg, p, c, b: T.prefill_into_cache(
        cfg, p, b["tok128"], b["row8"], c, b["row8"],
        return_prompt_logprobs=True),
    # as a TPU backend runs them (ISSUE 39): the grouped products as the
    # repo's kernel, a chunk's 8,192 sorted rows in blocks
    "decode-on-the-chip": lambda T, cfg, p, c, b: T.decode_step(
        replace(cfg, flash_force=True), p, c, b["row33"], b["row33"],
        kv_view=4096),
    "chunk-512-on-the-chip":
        lambda T, cfg, p, c, b: T.chunk_prefill_into_cache(
            replace(cfg, flash_force=True), p, b["tok512"], b["row2"],
            b["row2"], c, b["row2"], kv_view=4096),
}


@pytest.mark.parametrize("program", sorted(SHARE_PROGRAMS))
def test_the_share_presets_programs_fit_one_chip(chip, program):
    """``sarvam-105b-ep4s`` at the cell's size (32 slots + the scratch row x
    4096; prefill dispatches of 2 rows x 512, the echo path's 8 x 128):
    weights, the latent plane, the prefix pool of
    4096 blocks and the program's own temporaries inside a v5e's 16 GB, by
    the compiler's own count.  The routed products are Mosaic kernels."""
    from p2p_llm_tunnel_tpu.models import transformer as T
    from p2p_llm_tunnel_tpu.models.config import get_config

    cfg = get_config("sarvam-105b-ep4s")
    params, cache = _share_shapes(chip, cfg, 33, 4096)
    batch = _on(chip, {
        "row33": jax.ShapeDtypeStruct((33,), jnp.int32),
        "row8": jax.ShapeDtypeStruct((8,), jnp.int32),
        "row2": jax.ShapeDtypeStruct((2,), jnp.int32),
        "tok128": jax.ShapeDtypeStruct((8, 128), jnp.int32),
        "tok512": jax.ShapeDtypeStruct((2, 512), jnp.int32)})
    compiled = jax.jit(
        lambda p, c, b: SHARE_PROGRAMS[program](T, cfg, p, c, b),
        donate_argnums=(1,)).lower(params, cache, batch).compile()
    m = compiled.memory_analysis()
    pool = 4096 * 16 * cfg.n_layers * cfg.head_dim * 2
    held = (m.argument_size_in_bytes + m.temp_size_in_bytes
            + m.output_size_in_bytes - m.alias_size_in_bytes + pool)
    assert held < 15.75 * 2 ** 30, f"{held / 2 ** 30:.2f} GiB"
    assert m.argument_size_in_bytes > 10 * 2 ** 30  # the share is all there
    assert _grouped_products(
        compiled.as_text(), kernel=program.endswith("on-the-chip")) >= 3
