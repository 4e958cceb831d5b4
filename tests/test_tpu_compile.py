"""The dense presets' serving programs, compiled for a described TPU v5e (the
kernels alone: tests/test_tpu_compile_kernels.py; the other families a file
each: tests/test_tpu_compile_latent.py, ``_window``, ``_state``, ``_delta``,
``_block``): chunk prefill makes no cache plane in its layer loop (ISSUE 26),
the shipped decode program slices no plane (ISSUE 33), and the chunk program's
temporaries shrink with the rung.
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
    D,
    MAX_SEQ,
    ROWS,
    SWA_RING,
    SWA_ROWS,
    SWA_SEQ,
    _dense_decode_hlo,
    _no_layer_of_a_plane,
    _on,
    _plane_work,
    _swa_compiled,
)


@pytest.mark.parametrize("model,kv,view,rung", [
    ("qwen2-7b", None, 1024, 8), ("qwen2-7b", "int8", 1024, 8),
    ("qwen2-7b", "int4", 1024, 8), ("mistral-7b", None, 1024, 8),
    ("mistral-7b", "int8", 1024, 8), ("mistral-7b", "int4", 1024, 8),
    ("qwen2-7b", None, 256, 8),
    # the low rungs are programs of their own (ISSUE 30); the cells run
    # them at view 128, the guard also holds at the largest
    ("qwen2-7b", None, 1024, 1), ("qwen2-7b", "int8", 1024, 1),
    ("qwen2-7b", "int4", 1024, 1), ("mistral-7b", None, 1024, 1),
    ("qwen2-7b", None, 128, 1), ("qwen2-7b", None, 128, 2),
])
def test_chunk_prefill_makes_no_cache_plane_in_its_layer_loop(
        chip, model, kv, view, rung):
    """``chunk_prefill_into_cache`` at the benchmark's shapes (33 rows x
    1024, tail ``rung`` x 128, cache donated) and each configuration's own
    attention widths and depth; FFN and vocabulary are cut, they do not
    touch the cache's layout.  With 4 KV heads (qwen2-7b) the compiler
    keeps a loop-carried plane heads-outermost and a scatter wants it
    row-major: carried through the layer scan, the plane was converted
    there and back in every layer (four ``copy bf16[946176,4,128]``, half
    the device in ``qwen2-7b.decode-closed``, PERF.md section 6).  The
    guard for every later configuration with few KV heads: no ``copy``
    makes a plane, the layer loop makes none, and the written cache is
    the donated one."""
    from p2p_llm_tunnel_tpu.models.config import get_config
    from p2p_llm_tunnel_tpu.models.transformer import (
        chunk_prefill_into_cache,
        init_kv_cache,
        init_params,
    )

    cfg = get_config(model, ffn_dim=512, vocab_size=1024)

    def on_chip(tree):
        return jax.tree_util.tree_map(
            lambda x: jax.ShapeDtypeStruct(x.shape, x.dtype, sharding=chip),
            tree,
        )

    params = on_chip(jax.eval_shape(
        lambda: init_params(cfg, jax.random.PRNGKey(0))))
    cache = on_chip(jax.eval_shape(
        lambda: init_kv_cache(cfg, ROWS, MAX_SEQ, quant=kv)))
    tokens, row = on_chip((
        jax.ShapeDtypeStruct((rung, 128), jnp.int32),
        jax.ShapeDtypeStruct((rung,), jnp.int32),
    ))
    hlo = jax.jit(
        lambda p, c, tok, lengths, starts, slots: chunk_prefill_into_cache(
            cfg, p, tok, lengths, starts, c, slots, kv_view=view),
        donate_argnums=(1,),
    ).lower(params, cache, tokens, row, row, row).compile().as_text()

    copies, made = _plane_work(hlo, math.prod(cache["k"].shape))
    assert copies == []
    assert made == []
    assert "while(" in hlo  # the layer scan is still one loop to look into
    # Both planes (and both scale planes) are written on the donated input.
    aliased = re.search(r"input_output_alias=\{(.*?)\}, entry", hlo).group(1)
    assert aliased.count("alias") == len(cache)


@pytest.mark.parametrize("model", ["mistral-7b", "qwen2-7b",
                                   "mimo-v2-flash-ep16s"])
def test_the_shipped_decode_program_slices_no_plane(chip, model):
    """``decode_step`` as a TPU backend runs it at the cells' shapes (33
    rows x 1024 of bf16 cache, 8 and 4 KV heads; FFN and vocabulary cut,
    they do not touch the cache): the rows kernel once in the layer scan,
    no ``dynamic-slice`` of a layer's ``[1,33,S,K,D]`` plane left (the two
    copies that were 16.5 % of mistral's decode-closed window, PERF.md
    section 6), no plane-sized copy, and the cache written is the donated
    one.  The mimo share (ISSUE 36) has two kinds of plane and sizes of its
    own: :func:`_mimo_decode_slices_no_full_plane`."""
    from p2p_llm_tunnel_tpu.models.config import get_config
    from p2p_llm_tunnel_tpu.models.transformer import decode_attention_branch

    if get_config(model).attn_pattern is not None:
        return _mimo_decode_slices_no_full_plane(chip)
    cfg = replace(get_config(model, ffn_dim=512, vocab_size=1024),
                  flash_force=True)  # the branch a TPU backend takes
    assert decode_attention_branch(cfg, None, MAX_SEQ) == "pallas-rows"
    hlo, cache = _dense_decode_hlo(chip, cfg, MAX_SEQ)

    assert hlo.count("tpu_custom_call") == 1 and ROWS_KERNEL in hlo
    plane = f"[1,{ROWS},{MAX_SEQ},{cfg.n_kv_heads},{D}]"
    assert plane not in hlo
    assert "dynamic-slice" not in "".join(
        line for line in hlo.splitlines()
        if f"{ROWS},{MAX_SEQ},{cfg.n_kv_heads},{D}]" in line)
    copies, _ = _plane_work(hlo, math.prod(cache["k"].shape))
    assert copies == []
    aliased = re.search(r"input_output_alias=\{(.*?)\}, entry", hlo).group(1)
    assert aliased.count("alias") == len(cache)


@pytest.mark.parametrize("model,max_seq,t,view,prefill_rows,top_gb", [
    # the eight-row program's temporaries as the chip's own
    # memory_analysis() read them (PERF.md section 4, PR 26): 1.24 GB
    ("qwen2-7b", 1024, 128, 1024, 8, 1.25),
    ("mistral-7b", 1024, 128, 1024, 8, 0.5),
    ("sarvam-105b-ep4s", 4096, 512, 2048, 2, 1.25),
])
def test_chunk_program_temporaries_shrink_with_the_rung(
        chip, model, max_seq, t, view, prefill_rows, top_gb):
    """The whole chunk-prefill program of each configuration at the
    benchmark's shapes (33 cache rows; the dense 7B models with int8 weights
    as served), compiled at the two rungs a segment dispatch pads to: the
    one-row program needs under a third of what the ``prefill_rows`` one
    needs beside the weights and the cache (mostly the ``[rows, t,
    vocabulary]`` logits), so it cannot fail to load where that one loads."""
    from p2p_llm_tunnel_tpu.models import transformer as T
    from p2p_llm_tunnel_tpu.models.config import get_config
    from p2p_llm_tunnel_tpu.models.quant import init_params_quantized

    cfg = get_config(model)
    init = T.init_params if cfg.kv_lora_rank else init_params_quantized
    params = _on(chip, jax.eval_shape(
        lambda: init(cfg, jax.random.PRNGKey(0))))
    cache = _on(chip, jax.eval_shape(
        lambda: T.init_kv_cache(cfg, ROWS, max_seq)))
    temps = []
    for rung in (1, prefill_rows):
        tokens, row = _on(chip, (
            jax.ShapeDtypeStruct((rung, t), jnp.int32),
            jax.ShapeDtypeStruct((rung,), jnp.int32)))
        compiled = jax.jit(
            lambda p, c, tok, lengths, starts, slots:
            T.chunk_prefill_into_cache(
                cfg, p, tok, lengths, starts, c, slots, kv_view=view),
            donate_argnums=(1,),
        ).lower(params, cache, tokens, row, row, row).compile()
        temps.append(compiled.memory_analysis().temp_size_in_bytes)
    assert temps[0] < temps[1] / 3, temps
    assert temps[1] < top_gb * 1e9, temps


def _mimo_decode_slices_no_full_plane(chip):
    """``decode_step`` as a TPU backend runs it at the cell's shapes (ISSUE
    36; the feed-forwards narrowed): the rows kernel once in each of the two
    runs that hold a full layer, and no ``[1,49,8192,768]`` or ``[..,512]``
    left, which as a ``dynamic-slice`` and a ``copy`` of each were the eight
    largest operations of the cell's window (PERF.md section 6, PR 36).
    Since ISSUE 56 the window layers' rings are read where they lie too: the
    kernel in all four runs, no ``[1,49,640,1536]`` or ``[..,1024]`` (the
    einsum path still slices them)."""
    from p2p_llm_tunnel_tpu.models.transformer import decode_attention_branch

    cfg, cache, compiled = _swa_compiled(
        chip, "decode-on-the-chip", ffn_dim=512, moe_ffn_dim=128,
        vocab_size=1024)
    assert decode_attention_branch(
        replace(cfg, flash_force=True), None, 1024, None, SWA_SEQ) \
        == "pallas-rows"
    hlo = compiled.as_text()
    assert hlo.count(ROWS_KERNEL) >= 4
    for seq, widths in ((SWA_SEQ, (768, 512)), (SWA_RING, (1536, 1024))):
        _no_layer_of_a_plane(hlo, SWA_ROWS, seq, widths)
    # and the einsum path, which a CPU backend and the int8 planes keep,
    # still has them
    _, _, einsum = _swa_compiled(
        chip, "decode-8192", ffn_dim=512, moe_ffn_dim=128, vocab_size=1024)
    assert f"[1,{SWA_ROWS},{SWA_SEQ},768]" in einsum.as_text()
    assert f"[1,{SWA_ROWS},{SWA_RING},1536]" in einsum.as_text()
    assert ROWS_KERNEL not in einsum.as_text()
