"""Attention ops: batched GQA for prefill (causal) and slot-decode (cached).

XLA reference path — einsums the MXU tiles directly; fp32 softmax; optional
gemma-2 score softcapping and sliding windows.  The Pallas flash kernel
(ops/pallas_attention.py) replaces the prefill einsum on TPU for long
sequences; this module is the always-correct fallback and the decode path.

Shapes (B=batch/slots, T=query len, S=kv len, H=q heads, K=kv heads, G=H/K,
D=head dim):
- activations [B, T, H, D]; kv cache [B, S, K, D]
"""

from __future__ import annotations

from typing import Optional

import jax
import jax.numpy as jnp

_NEG_INF = -0.7 * float(jnp.finfo(jnp.float32).max)


def _softcap(scores: jnp.ndarray, cap: Optional[float]) -> jnp.ndarray:
    if cap is None:
        return scores
    return cap * jnp.tanh(scores / cap)


def _gqa_scores(q: jnp.ndarray, k: jnp.ndarray, scale: float) -> jnp.ndarray:
    """q [B,T,K,G,D] × k [B,S,K,D] → scores [B,K,G,T,S] in fp32."""
    return jnp.einsum(
        "btkgd,bskd->bkgts", q, k, preferred_element_type=jnp.float32
    ) * scale


def _gqa_out(probs: jnp.ndarray, v: jnp.ndarray) -> jnp.ndarray:
    """probs [B,K,G,T,S] × v [B,S,K,D] → out [B,T,K,G,D]."""
    return jnp.einsum("bkgts,bskd->btkgd", probs, v.astype(jnp.float32))


def causal_attention(
    q: jnp.ndarray,
    k: jnp.ndarray,
    v: jnp.ndarray,
    valid: jnp.ndarray,
    *,
    scale: Optional[float] = None,
    softcap: Optional[float] = None,
    window: Optional[int] = None,
    block: int = 0,
) -> jnp.ndarray:
    """Self-attention over one padded prompt batch (prefill).

    q [B,T,H,D], k/v [B,T,K,D], valid [B,T] bool marks real (non-pad) tokens.
    ``block``: block-causal, position ``i`` sees ``j`` where ``j // block
    <= i // block`` (its own block whole); 0 is causal.
    """
    b, t, h, d = q.shape
    kh = k.shape[2]
    g = h // kh
    if scale is None:
        scale = d**-0.5

    q5 = q.reshape(b, t, kh, g, d)
    scores = _gqa_scores(q5, k, scale)  # [B,K,G,T,S]
    scores = _softcap(scores, softcap)

    i = jnp.arange(t)[:, None]
    j = jnp.arange(t)[None, :]
    mask = j // block <= i // block if block else j <= i
    if window is not None:
        mask &= (i - j) < window
    mask = mask[None, None, None, :, :] & valid[:, None, None, None, :]
    scores = jnp.where(mask, scores, _NEG_INF)

    probs = jnp.exp(scores - scores.max(axis=-1, keepdims=True))
    probs = probs / probs.sum(axis=-1, keepdims=True)
    out = _gqa_out(probs, v)
    return out.reshape(b, t, h, d).astype(q.dtype)


def history_attention(
    q: jnp.ndarray,
    k_cache: jnp.ndarray,
    v_cache: jnp.ndarray,
    starts: jnp.ndarray,
    *,
    scale: Optional[float] = None,
    softcap: Optional[float] = None,
    window: Optional[int] = None,
    block: int = 0,
) -> jnp.ndarray:
    """Chunk attention for prefill-with-history (prefix caching).

    Row ``r``'s query ``t`` sits at global position ``g = starts[r] + t``;
    cache index ``j`` is attendable iff ``j <= g`` (and within the sliding
    window when set).  With ``starts == 0`` this degenerates to causal
    prefill over the cache; with ``T == 1`` it equals ``cached_attention``.
    The cache row must already hold this chunk's own KV at positions
    ``[starts, starts+T)`` (callers scatter before attending) plus the
    reused history at ``[0, starts)``.

    Pad queries (t >= the row's real tail length) produce junk outputs the
    caller discards; their global positions exceed every real query's, so
    the junk KV they wrote is never attended by real queries — the same
    overwrite-before-read argument as ``prefill_into_cache``.

    ``block``: block-causal instead (``j // block <= g // block``: a
    query sees its own block whole).  Callers keep ``starts`` and every
    real tail length a multiple of ``block``, so a real query's block holds
    no pad position.

    q [B,T,H,D]; k/v_cache [B,S,K,D]; starts [B] int32.
    """
    b, t, h, d = q.shape
    kh = k_cache.shape[2]
    g_heads = h // kh
    if scale is None:
        scale = d**-0.5

    q5 = q.reshape(b, t, kh, g_heads, d)
    scores = _gqa_scores(q5, k_cache, scale)  # [B,K,G,T,S]
    scores = _softcap(scores, softcap)

    s = k_cache.shape[1]
    g = starts[:, None] + jnp.arange(t)[None, :]  # [B,T] global query pos
    j = jnp.arange(s)[None, None, :]  # [1,1,S]
    if block:
        mask = j // block <= g[:, :, None] // block
    else:
        mask = j <= g[:, :, None]  # [B,T,S]
    if window is not None:
        mask &= (g[:, :, None] - j) < window
    scores = jnp.where(mask[:, None, None, :, :], scores, _NEG_INF)

    probs = jnp.exp(scores - scores.max(axis=-1, keepdims=True))
    probs = probs / probs.sum(axis=-1, keepdims=True)
    out = _gqa_out(probs, v_cache)
    return out.reshape(b, t, h, d).astype(q.dtype)


def cached_attention(
    q: jnp.ndarray,
    k_cache: jnp.ndarray,
    v_cache: jnp.ndarray,
    q_positions: jnp.ndarray,
    *,
    scale: Optional[float] = None,
    softcap: Optional[float] = None,
    window: Optional[int] = None,
) -> jnp.ndarray:
    """One-token-per-slot decode attention against the full KV cache.

    q [B,1,H,D]; k/v_cache [B,S,K,D]; q_positions [B] = the position of the
    query token (== cache length written so far minus one).  Cache entries at
    index j are attendable when j <= q_position (and within the sliding
    window when set) — the static-shape masking that makes slot-batched
    continuous decode one fixed XLA program.
    """
    b, t, h, d = q.shape
    assert t == 1, "decode step processes exactly one token per slot"
    kh = k_cache.shape[2]
    g = h // kh
    if scale is None:
        scale = d**-0.5

    q5 = q.reshape(b, 1, kh, g, d)
    scores = _gqa_scores(q5, k_cache, scale)  # [B,K,G,1,S]
    scores = _softcap(scores, softcap)

    s = k_cache.shape[1]
    j = jnp.arange(s)[None, :]  # [1,S]
    pos = q_positions[:, None]  # [B,1]
    mask = j <= pos
    if window is not None:
        mask &= (pos - j) < window
    scores = jnp.where(mask[:, None, None, None, :], scores, _NEG_INF)

    probs = jnp.exp(scores - scores.max(axis=-1, keepdims=True))
    probs = probs / probs.sum(axis=-1, keepdims=True)
    out = _gqa_out(probs, v_cache)
    return out.reshape(b, 1, h, d).astype(q.dtype)


def block_attention(
    q: jnp.ndarray,
    k_cache: jnp.ndarray,
    v_cache: jnp.ndarray,
    k_own: jnp.ndarray,
    v_own: jnp.ndarray,
    bound: jnp.ndarray,
    *,
    block: int,
    scale: Optional[float] = None,
) -> jnp.ndarray:
    """``T`` query positions a row, whole blocks of ``block``: each against
    the cache prefix below its own bound, ``[0, bound[b, t])`` whole, and
    its own block's ``block`` keys and values whole (no causal mask inside
    a block, nothing of another block of the call: the own part is
    block-diagonal), which need not be in the cache: the decode pass of a
    model that fills positions a block at a time (models/block_decode.py:
    the block that awaits its commit beside the current one; the second's
    queries reach the first's keys through the cache, where the pass has
    just written them).  One softmax over both parts.

    q [B,T,H,D]; k/v_cache [B,S,K,D]; k/v_own [B,T,K,D]; bound [B,T] int32.
    """
    b, t, h, d = q.shape
    kh = k_cache.shape[2]
    if scale is None:
        scale = d**-0.5
    q5 = q.reshape(b, t, kh, h // kh, d)
    hist = _gqa_scores(q5, k_cache, scale)  # [B,K,G,T,S]
    seen = jnp.arange(k_cache.shape[1])[None, None, :] < bound[:, :, None]
    hist = jnp.where(seen[:, None, None, :, :], hist, _NEG_INF)  # [B,T,S]
    with jax.named_scope("attn_block"):
        own = _gqa_scores(q5, k_own, scale)  # [B,K,G,T,T]
        of = jnp.arange(t) // block
        own = jnp.where(of[:, None] == of[None, :], own, _NEG_INF)
        top = jnp.maximum(hist.max(axis=-1, keepdims=True),
                          own.max(axis=-1, keepdims=True))
    p_hist = jnp.exp(hist - top)
    o_hist = _gqa_out(p_hist, v_cache)
    with jax.named_scope("attn_block"):
        p_own = jnp.exp(own - top)
        denom = (p_hist.sum(axis=-1, keepdims=True)
                 + p_own.sum(axis=-1, keepdims=True))
        out = o_hist + _gqa_out(p_own, v_own)
        out = out / jnp.moveaxis(denom, 3, 1)  # [B,T,K,G,1]
    return out.reshape(b, t, h, d).astype(q.dtype)


# ---------------------------------------------------------------------------
# latent attention (MLA): one cached row [c | k_r] a token, shared by all heads
# ---------------------------------------------------------------------------

def _masked_softmax(scores: jnp.ndarray, mask: jnp.ndarray) -> jnp.ndarray:
    scores = jnp.where(mask, scores, _NEG_INF)
    probs = jnp.exp(scores - scores.max(axis=-1, keepdims=True))
    return probs / probs.sum(axis=-1, keepdims=True)


def latent_attention_decompressed(
    q_n: jnp.ndarray,   # [B,T,H,Dn] no-rope part of the query
    q_r: jnp.ndarray,   # [B,T,H,Dr] roped part
    k_n: jnp.ndarray,   # [B,S,H,Dn] keys decompressed from the latent
    k_r: jnp.ndarray,   # [B,S,Dr] the one roped key all heads share
    v: jnp.ndarray,     # [B,S,H,Dv] values decompressed from the latent
    mask: jnp.ndarray,  # [B,T,S] bool
    scale: float,
) -> jnp.ndarray:
    """The prefill form: keys of width Dn + Dr, values of width Dv, per
    head.  Returns [B,T,H,Dv]."""
    scores = (
        jnp.einsum("bthd,bshd->bhts", q_n, k_n,
                   preferred_element_type=jnp.float32)
        + jnp.einsum("bthd,bsd->bhts", q_r, k_r,
                     preferred_element_type=jnp.float32)
    ) * scale
    probs = _masked_softmax(scores, mask[:, None])
    out = jnp.einsum("bhts,bshd->bthd", probs.astype(v.dtype), v,
                     preferred_element_type=jnp.float32)
    return out.astype(q_n.dtype)


def latent_attention_absorbed(
    q_c: jnp.ndarray,    # [B,T,H,C] no-rope query folded through W_kn
    q_r: jnp.ndarray,    # [B,T,H,Dr]
    c: jnp.ndarray,      # [B,S,C] cached latents
    k_r: jnp.ndarray,    # [B,S,Dr] cached roped keys
    mask: jnp.ndarray,   # [B,T,S] bool
    scale: float,
) -> jnp.ndarray:
    """The decode form: every head scores against the one cached row
    ``[c | k_r]`` and the weighted sum is taken of the latent itself.
    Returns [B,T,H,C]; the caller applies W_kvb's value half.  Same
    mathematics as the decompressed form (tests/test_mla_moe.py holds them
    equal)."""
    scores = (
        jnp.einsum("bthc,bsc->bhts", q_c, c,
                   preferred_element_type=jnp.float32)
        + jnp.einsum("bthd,bsd->bhts", q_r, k_r,
                     preferred_element_type=jnp.float32)
    ) * scale
    probs = _masked_softmax(scores, mask[:, None])
    # (summed head-major: the CPU backend has no bfloat16 product into
    # float32 for the token-major result; the swap is of a [B,H,T,C] array)
    out = jnp.einsum("bhts,bsc->bhtc", probs.astype(c.dtype), c,
                     preferred_element_type=jnp.float32)
    return jnp.swapaxes(out, 1, 2).astype(q_c.dtype)


# ---------------------------------------------------------------------------
# attention under a mask by position, values narrower than keys, a sink
# ---------------------------------------------------------------------------

#: Bytes of float32 scores one block of queries may take in
#: :func:`masked_attention`: a chunk-prefill segment of 2 x 512 queries
#: against 8192 cached positions and 64 heads would score 2 GB at once.
_SCORE_BLOCK_BYTES = 1 << 28


def window_mask(q_pos: jnp.ndarray, k_pos: jnp.ndarray,
                window: Optional[int] = None) -> jnp.ndarray:
    """Which key a query sees, by position: ``q_pos [B,T]``, ``k_pos [B,S]``
    (a key slot that holds nothing yet carries a negative position) ->
    ``[B,T,S]``.  Causal; under ``window`` also ``q - k < window``.  The
    keys may lie in any order, so a ring's slots are named by the positions
    they hold (:func:`ring_positions`)."""
    q, k = q_pos[:, :, None], k_pos[:, None, :]
    mask = (k >= 0) & (k <= q)
    if window is not None:
        mask &= (q - k) < window
    return mask


def ring_positions(last: jnp.ndarray, ring: int) -> jnp.ndarray:
    """The position each slot of a ring of ``ring`` slots holds once
    position ``last [B]`` has been written at ``last % ring``: slot ``r``
    holds the newest ``p <= last`` with ``p % ring == r``; negative where
    the ring has not come round to it yet.  -> ``[B, ring]``."""
    r = jnp.arange(ring)[None, :]
    return last[:, None] - jnp.mod(last[:, None] - r, ring)


def masked_attention(
    q: jnp.ndarray,      # [B,T,H,Dk]
    k: jnp.ndarray,      # [B,S,K,Dk]
    v: jnp.ndarray,      # [B,S,K,Dv]: Dv need not be Dk
    mask: jnp.ndarray,   # [B,T,S] bool
    scale: float,
    sink: Optional[jnp.ndarray] = None,  # [H] float32 logits
) -> jnp.ndarray:
    """GQA attention over whatever keys ``mask`` admits -> ``[B,T,H,Dv]``.

    ``sink``: one learned logit a head that joins the softmax's denominator
    and carries no value, ``p_ij = exp(s_ij) / (exp(b_h) + sum_j' exp(s_ij'))``:
    a head may then give its keys less than all of its weight.

    Scores are float32; the weighted sum takes the probabilities rounded to
    the values' type.  Queries are scored a block at a time where all at
    once would take more than ``_SCORE_BLOCK_BYTES``."""
    b, t, h, _ = q.shape
    s, kh, dv = k.shape[1], k.shape[2], v.shape[-1]
    g = h // kh
    sink_kg = None if sink is None else sink.astype(jnp.float32).reshape(kh, g)

    def attend(q_blk, mask_blk):
        q5 = q_blk.reshape(b, -1, kh, g, q_blk.shape[-1])
        scores = _gqa_scores(q5, k, scale)  # [B,K,G,t,S]
        scores = jnp.where(mask_blk[:, None, None], scores, _NEG_INF)
        top = scores.max(axis=-1, keepdims=True)
        if sink_kg is not None:
            top = jnp.maximum(top, sink_kg[None, :, :, None, None])
        probs = jnp.exp(scores - top)
        denom = probs.sum(axis=-1, keepdims=True)
        if sink_kg is not None:
            denom = denom + jnp.exp(sink_kg[None, :, :, None, None] - top)
        probs = (probs / denom).astype(v.dtype)
        out = jnp.einsum("bkgts,bskd->btkgd", probs, v,
                         preferred_element_type=jnp.float32)
        return out.reshape(b, -1, h, dv).astype(q.dtype)

    per_query = b * h * s * 4
    block = t
    while block > 16 and block * per_query > _SCORE_BLOCK_BYTES \
            and block % 2 == 0:
        block //= 2
    if block == t:
        return attend(q, mask)
    n = t // block
    q_blocks = jnp.moveaxis(q.reshape(b, n, block, h, -1), 1, 0)
    m_blocks = jnp.moveaxis(mask.reshape(b, n, block, s), 1, 0)
    out = jax.lax.map(lambda xs: attend(*xs), (q_blocks, m_blocks))
    return jnp.moveaxis(out, 0, 1).reshape(b, t, h, dv)
