"""What a CPU test run cannot otherwise see about the chip (ISSUE 21): the
kernels alone.

Every ``pallas_call`` of the attention paths in ``ops/`` is COMPILED (not
interpreted, not merely lowered) for a described TPU v5e at mistral-7b /
llama3-8b widths (H=32, K=8, D=128), in each KV form the engine serves, and
at the cells' own planes and rings.  The TPU compiler is installed here and
compiles for a chip that is described and not attached
(``jax.experimental.topologies``); interpret mode and StableHLO lowering
never reach Mosaic, which refused three of the four kernel families until
this file existed.  A kernel left unrepaired is a strict ``xfail`` carrying
the compiler's sentence.  The presets' whole programs are the other
tests/test_tpu_compile*.py, a file a family.
"""

from __future__ import annotations

import math
import re
from dataclasses import replace

import jax
import jax.numpy as jnp
import pytest

from p2p_llm_tunnel_tpu.ops.pallas_attention import flash_causal_attention
from p2p_llm_tunnel_tpu.ops.pallas_decode_attention import (
    decode_attention_rows,
    decode_ring_worklist,
    decode_rows_worklist,
    rows_block,
)
from p2p_llm_tunnel_tpu.ops.pallas_prefill_attention import (
    ragged_prefill_attention,
)
from tests.tpu_programs import (
    D,
    H,
    K,
    L,
    MAX_SEQ,
    ROWS,
    SWA_ROWS,
    SWA_SEQ,
    WINDOW,
    _dense_decode_hlo,
    _plane_work,
)


KV_FORMS = [None, "int8", "int4"]
VIEWS = [256, 512, 1024]


def _compile(chip, fn, *shapes):
    """Compile ``fn`` for the described chip from shapes alone and return
    how many Mosaic kernels the program holds."""
    args = [
        None if s is None else jax.ShapeDtypeStruct(s[0], s[1], sharding=chip)
        for s in shapes
    ]
    return jax.jit(fn).lower(*args).compile().as_text().count(
        "tpu_custom_call"
    )


def _cache_shapes(kv, rows_axis, seq):
    """(k, v, k_scale, v_scale) shapes of a cache in KV form ``kv`` whose
    leading axes are ``rows_axis``."""
    dtype = jnp.bfloat16 if kv is None else jnp.int8
    srows = seq // 2 if kv == "int4" else seq
    plane = (rows_axis + (srows, K, D), dtype)
    scale = None if kv is None else (rows_axis + (seq, K), jnp.float32)
    return plane, plane, scale, scale


@pytest.mark.parametrize("t", [128, 1024])
def test_flash_prefill_compiles_for_v5e(chip, t):
    """The default whole-prompt prefill kernel — the one kernel that ever
    ran on a chip — must stay green."""
    n = _compile(
        chip,
        lambda q, k, v, valid: flash_causal_attention(
            q, k, v, valid, window=WINDOW),
        ((8, t, H, D), jnp.bfloat16), ((8, t, K, D), jnp.bfloat16),
        ((8, t, K, D), jnp.bfloat16), ((8, t), jnp.bool_),
    )
    assert n == 1


@pytest.mark.parametrize("kv", KV_FORMS)
def test_ragged_prefill_compiles_for_v5e(chip, kv):
    """block_q 16 is what the engine derives from its default page size and
    segment width; 1024 flat tokens = prefill_rows 8 x prefill_chunk 128."""
    block_q, tot = 16, 1024
    nqb = tot // block_q
    k, v, ks, vs = _cache_shapes(kv, (L, ROWS), MAX_SEQ)
    desc = ((nqb,), jnp.int32)
    n = _compile(
        chip,
        lambda q, kn, vn, k_, v_, ks_, vs_, a, b, c, d, layer:
        ragged_prefill_attention(
            q, kn, vn, k_, v_, ks_, vs_, a, b, c, d, layer, block_q=block_q,
            max_row_blocks=128 // block_q, rope_theta=1e4, kv_quant=kv,
            window=WINDOW),
        ((tot, H, D), jnp.bfloat16), ((tot, K, D), jnp.bfloat16),
        ((tot, K, D), jnp.bfloat16), k, v, ks, vs,
        desc, desc, desc, desc, ((), jnp.int32),
    )
    assert n == 1


# ---------------------------------------------------------------------------
# decode reads a row's live keys where they lie (ISSUE 33)
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("heads,kv_heads", [(32, 8), (28, 4)])
def test_rows_decode_compiles_for_v5e(chip, heads, kv_heads):
    """The default decode read (ISSUE 33) at both cells' GQA ratios, over
    the stacked cache: one Mosaic kernel, and the flat ``[S*K, D]`` form it
    reads is the cache's own bytes (a bitcast, no plane-sized copy)."""
    cache = ((L, ROWS, MAX_SEQ, kv_heads, D), jnp.bfloat16)

    block = rows_block(MAX_SEQ, kv_heads)

    def fn(q, k, v, pos, layer):
        return decode_attention_rows(
            q, k, v, layer, decode_rows_worklist(pos, MAX_SEQ, block),
            block=block, window=WINDOW)

    args = [
        jax.ShapeDtypeStruct(s, d, sharding=chip) for s, d in (
            ((ROWS, heads, D), jnp.bfloat16), cache, cache,
            ((ROWS,), jnp.int32), ((), jnp.int32))
    ]
    hlo = jax.jit(fn).lower(*args).compile().as_text()
    assert hlo.count("tpu_custom_call") == 1
    copies, _ = _plane_work(hlo, math.prod(cache[0]))
    assert copies == []


def test_rows_decode_compiles_for_planes_of_heads_side_by_side(chip):
    """The same kernel over the other layout (ISSUE 36): mimo-v2-flash's
    full planes at the cell's size, ``k [2,49,8192,768]`` and ``v
    [2,49,8192,512]`` (4 KV heads of 192 / 128 side by side, 64 query
    heads): one Mosaic kernel that takes both planes as they lie, and the
    answer is a head's 128 value columns."""
    k = ((2, SWA_ROWS, SWA_SEQ, 4 * 192), jnp.bfloat16)
    v = ((2, SWA_ROWS, SWA_SEQ, 4 * 128), jnp.bfloat16)
    block = rows_block(SWA_SEQ, 4)
    assert block == 256

    def fn(q, k, v, pos, layer):
        return decode_attention_rows(
            q, k, v, layer, decode_rows_worklist(pos, SWA_SEQ, block),
            block=block)

    args = [
        jax.ShapeDtypeStruct(s, d, sharding=chip) for s, d in (
            ((SWA_ROWS, 64, 192), jnp.bfloat16), k, v,
            ((SWA_ROWS,), jnp.int32), ((), jnp.int32))
    ]
    compiled = jax.jit(fn).lower(*args).compile()
    hlo = compiled.as_text()
    assert hlo.count("tpu_custom_call") == 1
    assert f"bf16[{SWA_ROWS},64,128]" in hlo
    for plane in (k, v):
        copies, made = _plane_work(hlo, math.prod(plane[0]))
        assert copies == [] and made == []


#: (layers, rows, ring, KV heads, key and value width a head, query heads,
#: window, the full planes' length, a sink?) of the two cells' window layers.
RING_CELLS = {
    "laguna-s-2.1": (6, 65, 1024, 8, 128, 128, 72, 512, 6144, False),
    "mimo-v2-flash": (5, 49, 640, 8, 192, 128, 64, 128, 8192, True),
}


@pytest.mark.parametrize("cell", sorted(RING_CELLS))
def test_rows_decode_compiles_for_the_cells_rings(chip, cell):
    """The ring form (ISSUE 56) at both cells' window shapes: laguna's
    ``[6,65,1024,1024]`` with 72 query heads (9 a KV head), mimo's
    ``[5,49,640,1536]`` / ``[..,1024]`` with 64 and a sink: one Mosaic
    kernel that takes the rings as they lie (every row's queries, ``[B, H,
    K * Dk]`` = 9.6 MB at either, fit VMEM beside the blocks in flight),
    the list of ``B x (window / 128 + 1)`` items."""
    layers, rows, ring, kv, dk, dv, heads, window, seq, sink = RING_CELLS[cell]
    k = ((layers, rows, ring, kv * dk), jnp.bfloat16)
    v = ((layers, rows, ring, kv * dv), jnp.bfloat16)
    block = rows_block(ring, kv)
    assert block == 128

    def fn(q, k, v, pos, layer, logits):
        work = decode_ring_worklist(pos, seq, ring, block, window)
        assert work.shape == (1 + rows * (window // 128 + 1) + rows,)
        return decode_attention_rows(
            q, k, v, layer, work, block=block, window=window, ring=True,
            sink=logits if sink else None)

    args = [
        jax.ShapeDtypeStruct(s, d, sharding=chip) for s, d in (
            ((rows, heads, dk), jnp.bfloat16), k, v,
            ((rows,), jnp.int32), ((), jnp.int32), ((heads,), jnp.float32))
    ]
    hlo = jax.jit(fn).lower(*args).compile().as_text()
    assert hlo.count("tpu_custom_call") == 1
    assert f"bf16[{rows},{heads},{dv}]" in hlo
    for plane in (k, v):
        copies, made = _plane_work(hlo, math.prod(plane[0]))
        assert copies == [] and made == []


@pytest.mark.parametrize("view", VIEWS)
@pytest.mark.parametrize("kv", KV_FORMS[1:])
def test_the_quantised_caches_decode_compiles_for_v5e(chip, kv, view):
    """``decode_step`` over an int8 and a packed int4 cache, which keep the
    einsum on every backend (``--kv-quant`` is the control a configuration's
    limits are read with), at mistral-7b's attention widths and depth and
    each rung of the view ladder: the TPU's compiler takes the program,
    there is no Mosaic kernel in it, no ``copy`` makes a plane (the
    dequantised view is a layer's, never the stacked cache's) and the cache
    written is the donated one."""
    from p2p_llm_tunnel_tpu.models.config import get_config
    from p2p_llm_tunnel_tpu.models.transformer import decode_attention_branch

    cfg = replace(get_config("mistral-7b", ffn_dim=512, vocab_size=1024),
                  flash_force=True)  # the branch a TPU backend takes
    assert decode_attention_branch(cfg, None, view, kv, MAX_SEQ) == "einsum"
    hlo, cache = _dense_decode_hlo(chip, cfg, view, kv)

    assert "tpu_custom_call" not in hlo
    copies, _ = _plane_work(hlo, math.prod(cache["k"].shape))
    assert copies == []
    aliased = re.search(r"input_output_alias=\{(.*?)\}, entry", hlo).group(1)
    assert aliased.count("alias") == len(cache)
