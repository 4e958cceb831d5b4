"""What the files that compile for a described v5e share
(tests/test_tpu_compile*.py; the ``chip`` they compile for is
tests/conftest.py's): the 7B cells' shapes, shapes on the chip from a
configuration, and what is read out of a compiled program's text:
plane-sized copies, values a loop body makes, a layer's slice of a plane,
whole-leaf moves, the grouped products.
"""

from __future__ import annotations

import math
import re
from dataclasses import replace

import jax
import jax.numpy as jnp


# mistral-7b / llama3-8b attention widths, the engine's 32 slots + scratch
# row, max_seq 1024, mistral's window.
H, K, D, L = 32, 8, 128, 32
ROWS, MAX_SEQ, WINDOW = 33, 1024, 4096


# ---------------------------------------------------------------------------
# what a compiled program's text says of the planes
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


def _no_layer_of_a_plane(hlo, rows, seq, widths):
    """No value of one layer's ``[rows, seq, width]`` in a compiled program:
    no slice of it out of the stacked plane, no copy of one."""
    for width in widths:
        assert f"[1,{rows},{seq},{width}]" not in hlo
        assert f"[{rows},{seq},{width}]" not in hlo
        assert "dynamic-slice" not in "".join(
            line for line in hlo.splitlines()
            if f"{rows},{seq},{width}]" in line)


# ---------------------------------------------------------------------------
# a recurrent state a slot beside the KV planes (ISSUE 44)
# ---------------------------------------------------------------------------

#: nemotron-3-nano-30b-a3b-ep2s at the cell's size: 128 slots + the scratch
#: row x 4096.
SSM_ROWS, SSM_SEQ = 129, 4096


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
