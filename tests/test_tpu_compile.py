"""What a CPU test run cannot otherwise see about the chip (ISSUE 21).

- Every ``pallas_call`` in ``ops/`` is COMPILED — not interpreted, not merely
  lowered — for a described TPU v5e at mistral-7b / llama3-8b widths (H=32,
  K=8, D=128), in each KV form the engine serves.  The TPU compiler is
  installed here and compiles for a chip that is described and not attached
  (``jax.experimental.topologies``); interpret mode and StableHLO lowering
  never reach Mosaic, which refused three of the four kernel families until
  this file existed.  A kernel left unrepaired is a strict ``xfail`` carrying
  the compiler's sentence.
- ``--replicas``: each engine's decode output lives on its own device.
- The compile-cache helper and ``serve --backend tpu``'s refusal of a
  backend it was not asked to run on.
"""

from __future__ import annotations

import math
import os
import re
from dataclasses import replace

os.environ.setdefault("TPU_LOG_DIR", "disabled")  # else the compiler logs under /tmp

import jax
import jax.numpy as jnp
import pytest
from jax.sharding import SingleDeviceSharding

from p2p_llm_tunnel_tpu.ops.pallas_attention import flash_causal_attention
from p2p_llm_tunnel_tpu.ops.pallas_decode_attention import (
    ROWS_KERNEL,
    decode_attention_rows,
    decode_ring_worklist,
    decode_rows_worklist,
    rows_block,
)
from p2p_llm_tunnel_tpu.ops.pallas_prefill_attention import (
    ragged_prefill_attention,
)

# mistral-7b / llama3-8b attention widths, the engine's 32 slots + scratch
# row, max_seq 1024, mistral's window.
H, K, D, L = 32, 8, 128, 32
ROWS, MAX_SEQ, WINDOW = 33, 1024, 4096
KV_FORMS = [None, "int8", "int4"]
VIEWS = [256, 512, 1024]


@pytest.fixture(scope="module")
def chip():
    """One described (not attached) v5e chip to compile for."""
    from jax.experimental import topologies

    try:
        topo = topologies.get_topology_desc(
            platform="tpu", topology_name="v5e:2x2"
        )
    except Exception as e:  # no TPU compiler in this installation
        pytest.skip(f"cannot describe a v5e topology here: {e}")
    return SingleDeviceSharding(topo.devices[0])


@pytest.fixture(scope="module", autouse=True)
def _no_persistent_cache():
    """A compile for a described chip is written to the persistent cache
    but cannot be read back without a chip: the next run would warn and
    compile again.  Keep these compiles out of it."""
    from jax.experimental.compilation_cache import compilation_cache

    was = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    yield
    jax.config.update("jax_enable_compilation_cache", was)
    compilation_cache.reset_cache()


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
# chunk prefill: the KV cache is not a value the layer loop writes (ISSUE 26)
# ---------------------------------------------------------------------------

_ARRAY = re.compile(r"(\w+)\[([\d,]*)\]")


def _plane_sized(type_text, plane):
    """Array types in ``type_text`` with as many elements as a cache plane."""
    return [
        m.group(0) for m in _ARRAY.finditer(type_text)
        if math.prod(int(d) for d in m.group(2).split(",") if d) == plane
    ]


def _plane_work(hlo, plane):
    """(plane-sized ``copy`` results anywhere, plane-sized values that a
    ``while`` body computes) in a compiled program's text.  A loop-invariant
    operand rides the body's tuple too (HLO has no other way to hand it in);
    what the body may not do is make a plane: parameter,
    get-tuple-element and the root tuple that passes it on are all it may
    hold of that size."""
    instr = re.compile(
        r"^\s*(?:ROOT )?%?([\w.\-]+) = (.*?) ([\w\-]+)\(", re.M)
    copies = [
        (name, typ) for name, typ, op in instr.findall(hlo)
        if op == "copy" and _plane_sized(typ, plane)
    ]
    made = []
    for body in set(re.findall(r"\bwhile\(.*?body=%?([\w.\-]+)", hlo)):
        start = re.search(
            rf"^%?{re.escape(body)} \(.*\{{\s*$", hlo, re.M)
        text = hlo[start.end():hlo.index("\n}", start.end())]
        made += [
            (body, name, op) for name, typ, op in instr.findall(text)
            if op not in ("parameter", "get-tuple-element", "tuple")
            and _plane_sized(typ, plane)
        ]
    return copies, made


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


def _grouped_products(hlo, kernel):
    """The routed layers' grouped products in a compiled program: the
    repo's kernel where a TPU backend's branch was traced (ISSUE 39), else
    the compiler's ``ragged-dot``; never both."""
    from p2p_llm_tunnel_tpu.ops.pallas_grouped_matmul import GROUPED_KERNEL

    found = {
        True: len([line for line in hlo.splitlines()
                   if "tpu_custom_call" in line and GROUPED_KERNEL in line]),
        False: hlo.count("ragged-dot")}
    assert not found[not kernel]
    return found[kernel]


def _on(chip, tree):
    return jax.tree_util.tree_map(
        lambda x: jax.ShapeDtypeStruct(x.shape, x.dtype, sharding=chip), tree)


def _share_shapes(chip, cfg, rows, max_seq, kv=None):
    from p2p_llm_tunnel_tpu.models.transformer import (
        init_kv_cache,
        init_params,
    )

    params = _on(chip, jax.eval_shape(
        lambda: init_params(cfg, jax.random.PRNGKey(0))))
    cache = _on(chip, jax.eval_shape(
        lambda: init_kv_cache(cfg, rows, max_seq, quant=kv)))
    return params, cache


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


def _dense_decode_hlo(chip, cfg, view, kv=None):
    """(``decode_step`` at the 7B cells' shapes, 33 rows x 1024 of cache in
    form ``kv``, donated, compiled for the described chip; the cache's
    shapes)."""
    from p2p_llm_tunnel_tpu.models.transformer import decode_step

    params, cache = _share_shapes(chip, cfg, ROWS, MAX_SEQ, kv=kv)
    row = _on(chip, jax.ShapeDtypeStruct((ROWS,), jnp.int32))
    hlo = jax.jit(
        lambda p, c, tok, pos: decode_step(cfg, p, c, tok, pos, kv_view=view),
        donate_argnums=(1,),
    ).lower(params, cache, row, row).compile().as_text()
    return hlo, cache


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


# ---------------------------------------------------------------------------
# window rings beside full planes (ISSUE 34)
# ---------------------------------------------------------------------------

#: mimo-v2-flash-ep16s at the cell's size: 48 slots + the scratch row x 8192,
#: rings of 640 (window 128 + segments of 512).
SWA_ROWS, SWA_SEQ, SWA_RING = 49, 8192, 640
SWA_PLANES = {"k": (2, SWA_SEQ, 4 * 192), "v": (2, SWA_SEQ, 4 * 128),
              "wk": (5, SWA_RING, 8 * 192), "wv": (5, SWA_RING, 8 * 128)}


def _swa(chip, **small):
    from p2p_llm_tunnel_tpu.models.config import get_config

    cfg = get_config("mimo-v2-flash-ep16s", ring_positions=SWA_RING, **small)
    params, cache = _share_shapes(chip, cfg, SWA_ROWS, SWA_SEQ)
    assert {k: (v.shape[0],) + v.shape[2:] for k, v in cache.items()} \
        == SWA_PLANES
    return cfg, params, cache


def _swa_batch(chip):
    return _on(chip, {
        "row49": jax.ShapeDtypeStruct((SWA_ROWS,), jnp.int32),
        "row8": jax.ShapeDtypeStruct((8,), jnp.int32),
        "row2": jax.ShapeDtypeStruct((2,), jnp.int32),
        "row1": jax.ShapeDtypeStruct((1,), jnp.int32),
        "tok128": jax.ShapeDtypeStruct((8, 128), jnp.int32),
        "tok512": jax.ShapeDtypeStruct((2, 512), jnp.int32),
        "tok512x1": jax.ShapeDtypeStruct((1, 512), jnp.int32)})


SWA_PROGRAMS = {
    "decode-8192": lambda T, cfg, p, c, b: T.decode_step(
        cfg, p, c, b["row49"], b["row49"], kv_view=8192, with_stats=True),
    "decode-1024": lambda T, cfg, p, c, b: T.decode_step(
        cfg, p, c, b["row49"], b["row49"], kv_view=1024, with_stats=True),
    # as a TPU backend runs it (ISSUE 36): the full layers on the rows kernel
    "decode-on-the-chip": lambda T, cfg, p, c, b: T.decode_step(
        replace(cfg, flash_force=True), p, c, b["row49"], b["row49"],
        kv_view=8192, with_stats=True),
    "chunk-512-at-8192": lambda T, cfg, p, c, b: T.chunk_prefill_into_cache(
        cfg, p, b["tok512"], b["row2"], b["row2"], c, b["row2"],
        kv_view=8192, stat_rows=b["row2"] != 48),
    "chunk-512-at-512-one-row":
        lambda T, cfg, p, c, b: T.chunk_prefill_into_cache(
            cfg, p, b["tok512x1"], b["row1"], b["row1"], c, b["row1"],
            kv_view=512, stat_rows=b["row1"] != 48),
    "prefill-128": lambda T, cfg, p, c, b: T.prefill_into_cache(
        cfg, p, b["tok128"], b["row8"], c, b["row8"],
        return_prompt_logprobs=True, stat_rows=b["row8"] != 48),
}


def _swa_compiled(chip, program, **small):
    from p2p_llm_tunnel_tpu.models import transformer as T

    cfg, params, cache = _swa(chip, **small)
    return cfg, cache, jax.jit(
        lambda p, c, b: SWA_PROGRAMS[program](T, cfg, p, c, b),
        donate_argnums=(1,)).lower(params, cache, _swa_batch(chip)).compile()


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


def _no_layer_of_a_plane(hlo, rows, seq, widths):
    """No value of one layer's ``[rows, seq, width]`` in a compiled program:
    no slice of it out of the stacked plane, no copy of one."""
    for width in widths:
        assert f"[1,{rows},{seq},{width}]" not in hlo
        assert f"[{rows},{seq},{width}]" not in hlo
        assert "dynamic-slice" not in "".join(
            line for line in hlo.splitlines()
            if f"{rows},{seq},{width}]" in line)


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


# ---------------------------------------------------------------------------
# a recurrent state a slot beside the KV planes (ISSUE 44)
# ---------------------------------------------------------------------------

#: nemotron-3-nano-30b-a3b-ep2s at the cell's size: 128 slots + the scratch
#: row x 4096.
SSM_ROWS, SSM_SEQ = 129, 4096
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


def _ssm_burst(T, cfg, params, cache, tokens, positions, steps=4, seq=None):
    def one(carry, _):
        tok, pos, cache = carry
        logits, cache, stats = T.decode_step(
            cfg, params, cache, tok, pos, kv_view=seq or SSM_SEQ,
            with_stats=True)
        tok = jnp.argmax(logits, axis=-1).astype(jnp.int32)
        return (tok, pos + 1, cache), (tok, stats)

    (_, _, cache), (toks, stats) = jax.lax.scan(
        one, (tokens, positions, cache), None, length=steps)
    return toks, cache, stats.sum(axis=0)


def _leaf_moves(hlo, shape):
    """The operations of a compiled program that move a whole state leaf of
    ``shape``: a ``copy`` whose result is the leaf, or what the compiler's
    rematerialisation makes of one short of memory (``...remat_compressed``
    / ``remat_uncompressed``: the leaf through a change of layout and
    back).  A layer's update where the leaf lies is neither."""
    dims = ",".join(str(d) for d in shape)
    rematerialised = re.compile(
        r"%\S*remat_\S* = \w+\[" + re.escape(dims) + r"\]")
    return [ln for ln in hlo.splitlines() if f"[{dims}]" in ln
            and (" copy(" in ln or rematerialised.search(ln))]


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


# ---------------------------------------------------------------------------
# --replicas: one engine per device
# ---------------------------------------------------------------------------

def test_each_replica_dispatches_on_its_own_device(cpu_devices):
    """cli.py builds replica i under ``jax.default_device(d[i])`` and then
    commits it there.  Without the commit the arrays are uncommitted, the
    engine loop (which runs outside that context) dispatches on device 0,
    and the donated cache follows — four replicas on one chip."""
    from p2p_llm_tunnel_tpu.engine.engine import EngineConfig, InferenceEngine

    def replica(i, commit):
        with jax.default_device(cpu_devices[i]):
            eng = InferenceEngine(engine_cfg=EngineConfig(
                model="tiny", num_slots=2, max_seq=64, dtype="float32",
                decode_steps=2, seed=i, prefix_cache=True,
            ))
        if commit:
            eng.commit_to(cpu_devices[i])
        return eng

    for i in range(4):
        eng = replica(i, commit=True)
        outs, _ = eng._dispatch_decode(view=64, steps=2)
        assert {d.id for d in outs[0].devices()} == {cpu_devices[i].id}
        assert eng.resident_devices() == [cpu_devices[i].id]
    # The control: what the parent commit did for every replica but the first.
    eng = replica(3, commit=False)
    eng._dispatch_decode(view=64, steps=2)
    assert {d.id for d in eng.kv_cache["k"].devices()} == {cpu_devices[0].id}


# ---------------------------------------------------------------------------
# the compile-cache helper
# ---------------------------------------------------------------------------

@pytest.fixture
def cache_updates(monkeypatch):
    """Record jax.config.update calls instead of moving this process's cache."""
    calls = []
    monkeypatch.setattr(
        jax.config, "update", lambda name, value: calls.append((name, value))
    )
    return calls


def test_compile_cache_set_from_outside_sets_nothing_in_code(
        monkeypatch, cache_updates, tmp_path):
    from p2p_llm_tunnel_tpu.utils import compile_cache

    monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR", str(tmp_path))
    assert compile_cache.enable() == str(tmp_path)
    assert cache_updates == []  # JAX reads the variable itself


def test_compile_cache_defaults_to_the_checkout(monkeypatch, cache_updates):
    from p2p_llm_tunnel_tpu.utils import compile_cache

    monkeypatch.delenv("JAX_COMPILATION_CACHE_DIR", raising=False)
    repo = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    want = os.path.join(repo, ".jax_cache")
    assert compile_cache.enable() == want
    assert cache_updates == [("jax_compilation_cache_dir", want)]


# ---------------------------------------------------------------------------
# serve --backend tpu serves the TPU, or the CPU when asked
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("platform,asked,refused", [
    ("tpu", None, False),
    ("tpu", "cpu", False),
    ("cpu", "cpu", False),     # the documented way to run tests and rehearsals
    ("cpu", "cpu,tpu", False),
    ("cpu", None, True),       # JAX fell back: say so, do not serve
    ("cpu", "", True),
    ("cpu", "tpu,cpu", True),
    ("gpu", "cpu", True),
])
def test_require_tpu_backend(monkeypatch, platform, asked, refused):
    from p2p_llm_tunnel_tpu.cli import require_tpu_backend

    if asked is None:
        monkeypatch.delenv("JAX_PLATFORMS", raising=False)
    else:
        monkeypatch.setenv("JAX_PLATFORMS", asked)
    if not refused:
        require_tpu_backend(platform, "serve --backend tpu")
        return
    with pytest.raises(SystemExit) as e:
        require_tpu_backend(platform, "serve --backend tpu")
    assert repr(platform) in str(e.value)  # names the platform it found


def test_serve_backend_tpu_refuses_before_building_an_engine(monkeypatch):
    """Through the CLI's own start-up path: on this CPU-only test process,
    with no explicit JAX_PLATFORMS=cpu, ``serve --backend tpu`` exits and
    no engine is ever constructed."""
    import asyncio

    import p2p_llm_tunnel_tpu.cli as cli_mod
    import p2p_llm_tunnel_tpu.engine.engine as eng_mod

    built = []
    monkeypatch.setattr(
        eng_mod, "InferenceEngine", lambda **kw: built.append(kw)
    )
    monkeypatch.setattr(cli_mod, "_BACKEND", None)
    monkeypatch.delenv("JAX_PLATFORMS", raising=False)
    args = cli_mod.build_parser().parse_args(
        ["serve", "--room", "r", "--backend", "tpu"]
    )
    with pytest.raises(SystemExit, match="'cpu'"):
        asyncio.run(cli_mod._engine_backend(args))
    assert built == []
