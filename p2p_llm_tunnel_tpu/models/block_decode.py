"""The decode pass of a model that fills positions a block at a time by
masked denoising (``ModelConfig.block_length``; SDAR's generation).

The layer body, the cache ``[L, rows, S, K, D]``, chunk prefill and the
routed feed-forward are ``models/transformer.py``'s and ``models/moe.py``'s;
what is here is the one program the family adds: a forward over two blocks
of ``block_length`` positions a row, the block that awaits its commit
(``pending``: fully decided, not yet in the cache) and the row's current
block, under the block-causal mask that chunk prefill puts over a prompt's
whole blocks: each block sees the cache prefix below it whole and its own
keys and values whole (no causal mask inside a block).

A row's block goes through ``denoise_steps`` denoise passes.  Denoise pass
``g`` sees the block's decided tokens at offsets ``< g * k`` (``k =
block_length / denoise_steps``) and ``mask_token_id`` from there on; its
logits at offsets ``g * k .. g * k + k - 1`` decide those offsets (in place:
the logits at a position decide that position).  It writes nothing of the
block itself.  A block whose last group is decided is committed (forwarded
clean, its K/V rows written; those logits decide nothing) by the pass that
decides the NEXT block's first group: in each layer the pending block's K/V
are written before the prefix is read, so the current block's queries find
them in the cache, in the cache's own type, as they would after a commit
pass of its own.  Rows are out of phase with each other, so one call mixes
rows with a pending block and rows without; the half that holds nothing (a
row's first block after prefill, the second pass on a block, a parked row)
is computed, dropped and counted nowhere.  Whether an offset is decided is
kept by offset, never by comparing a token with ``mask_token_id``.
"""

from __future__ import annotations

from typing import Optional

import jax
import jax.numpy as jnp

from p2p_llm_tunnel_tpu.models.config import ModelConfig
from p2p_llm_tunnel_tpu.models.transformer import (
    _embed,
    _logits,
    _mlp,
    _moe_total,
    _norm,
    _proj,
    _qkv,
    normed,
    stream_in,
    _quant_kv,
    kv_cache_is_quantized,
    split_experts,
)
from p2p_llm_tunnel_tpu.ops.attention import block_attention


def group_size(cfg: ModelConfig) -> int:
    """Offsets a denoise pass decides."""
    return cfg.block_length // cfg.denoise_steps


def block_decode_step(
    cfg: ModelConfig,
    params,
    kv_cache,
    tokens: jnp.ndarray,  # [B, 2 * block] the block before | the block
    base: jnp.ndarray,  # [B] the block's first position (a multiple of block)
    decided: jnp.ndarray,  # [B] the block's offsets decided so far, < block
    pending: jnp.ndarray,  # [B] bool: the block before awaits its commit
    kv_view: Optional[int] = None,  # static: attend only to cache[:kv_view]
    with_stats: bool = False,  # static: the routed layers' counts come last
):
    """One pass over every row's two blocks at positions ``base - block ..
    base + block - 1`` -> (logits [B, k, V] at the current block's offsets
    ``decided .. decided + k - 1``, new cache), and under ``with_stats``
    what the routed layers counted of the halves that hold something and
    lie inside the cache: a pending block, the current block of a row that
    is not parked at ``base >= S``.

    Where ``pending``, the block before (every token used as it is) is
    forwarded clean against the prefix ``[0, base - block)`` and itself and
    its K/V are written at ``[base - block, base)``; elsewhere that half's
    tokens are never read for anything kept and its write position is past
    the cache and dropped.  The current block (its tokens used where
    decided, ``mask_token_id`` from there on) is forwarded against ``[0,
    base)`` and itself and never written, so a row with nothing pending
    leaves the cache as it found it.  Rows parked at ``base >= S`` compute
    junk and write nothing.

    An int8 cache (values and a float32 scale a token, layer and KV head:
    the benchmark's cache control) takes the committed rows quantised and
    is read dequantised, the pending block's rows by the current block's
    queries too; a block's own keys and values never pass through the
    cache on the way to its own queries and stay as they were made."""
    b, n = tokens.shape[0], cfg.block_length
    quant = kv_cache_is_quantized(kv_cache)
    s = kv_cache["k"].shape[2]
    if kv_view is None or kv_view > s:
        kv_view = s
    k_of = group_size(cfg)
    offs = jnp.arange(n)
    inp = jnp.concatenate([tokens[:, :n], jnp.where(
        offs[None, :] < decided[:, None], tokens[:, n:], cfg.mask_token_id)],
        axis=1)
    pos = (base - n)[:, None] + jnp.arange(2 * n)[None, :]  # [B, 2 * block]
    write_pos = jnp.where(pending[:, None], pos[:, :n], s)  # s: dropped

    def by_half(behind, current):  # [B], [B] -> [B, 2 * block]
        return jnp.repeat(jnp.stack([behind, current], axis=1), n, axis=1)

    # what a query sees of the cache: the positions below its own block
    bound = by_half(base - n, base)
    rows = jnp.arange(b)[:, None]
    counted = None
    if with_stats:
        counted = by_half(pending & (base - n < s), base < s)
    x, act = stream_in(cfg, _embed(cfg, params, inp))
    scanned, stacked = split_experts(cfg, params["blocks"])
    view_shape = (1, b, kv_view, cfg.n_kv_heads, cfg.head_dim)

    def step(carry, xs):
        x, cache = carry
        blk, idx = xs
        with jax.named_scope("attn"):
            h, _ = normed(cfg, x, blk["attn_norm"], act)
            q, k, v = _qkv(cfg, blk, h, pos)
        cache = dict(cache)
        with jax.named_scope("kv_write"):
            held = {"k": k[:, :n], "v": v[:, :n]}
            if quant:
                for name in ("k", "v"):
                    held[name], held[name + "_scale"] = _quant_kv(held[name])
            for name, rows_new in held.items():
                cache[name] = cache[name].at[idx, rows, write_pos].set(
                    rows_new)
        with jax.named_scope("kv_read"):
            zero = jnp.zeros((), idx.dtype)
            start = (idx, zero, zero, zero, zero)
            k_l = jax.lax.dynamic_slice(cache["k"], start, view_shape)[0]
            v_l = jax.lax.dynamic_slice(cache["v"], start, view_shape)[0]
            if quant:
                k_s = jax.lax.dynamic_slice(
                    cache["k_scale"], start[:4], view_shape[:4])[0]
                v_s = jax.lax.dynamic_slice(
                    cache["v_scale"], start[:4], view_shape[:4])[0]
                k_l = (k_l.astype(jnp.float32) * k_s[..., None]).astype(act)
                v_l = (v_l.astype(jnp.float32) * v_s[..., None]).astype(act)
        with jax.named_scope("attn"):
            attn = block_attention(q, k_l, v_l, k, v, bound, block=n,
                                   scale=cfg.query_scale)
            x = x + _proj(cfg, attn.reshape(b, 2 * n, -1), blk["wo"])
        with jax.named_scope("ffn"):
            h, h32 = normed(cfg, x, blk["mlp_norm"], act)
            mlp, stats = _mlp(cfg, blk, h, counted, stacked, idx, h32)
            x = x + mlp
        return (x, cache), stats

    (x, new_cache), stats = jax.lax.scan(
        step, (x, dict(kv_cache)), (scanned, jnp.arange(cfg.n_layers)))
    with jax.named_scope("head_sample"):
        # only the current block's offsets this pass decides reach the head
        sel = n + jnp.clip(decided[:, None] + jnp.arange(k_of)[None, :],
                           0, n - 1)
        x = jnp.take_along_axis(x, sel[:, :, None], axis=1)  # [B, k, Dm]
        logits = _logits(cfg, params, _norm(
            cfg, x, params["final_norm"]).astype(act))
    if with_stats:
        return logits, new_cache, _moe_total(stats)
    return logits, new_cache
