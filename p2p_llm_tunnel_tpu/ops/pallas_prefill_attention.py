"""Ragged grouped Pallas flash-prefill kernel (ISSUE 15 tentpole).

ONE program processes a whole admission group's variable-length tail
segments — per-BLOCK ``(slot, start, qoff, base)`` descriptors ride
scalar prefetch and drive every block index map, so the group needs no
per-(tail-bucket, kv-view) program specialization and pays no pad compute
across rows.  That kills the two axes that made the warmup/AOT grid big
(``chunk[t, view]`` per tail bucket per view bucket) and is why
``EngineConfig.ragged_prefill`` collapses the prefill half of cold start
to a handful of programs (see engine.warmup_plan).

Layout: the group's tail tokens are FLAT-PACKED along one axis — row
``r``'s tail occupies ``[flat_off_r, flat_off_r + qlen_r)``, with each
row's region rounded up to a ``block_q`` multiple (pad waste is bounded
by ``block_q - 1`` tokens per row instead of a whole power-of-2 bucket).
The grid is ``(n_qblocks, n_hist_blocks + max_row_blocks)`` — the tail
axis is ROW-RELATIVE (step ``t`` stages the row's own block
``base + t``), so it spans the widest single row's tail, never the whole
flat bucket, and the grid grows linearly with group size.  Per q-block
program, all kv-heads:

- ``sj == 0``: RoPE the block's q rows at their global positions
  ``start + qoff + i`` (the exact ops/rope.py rotate-half formula, so CPU
  interpret reproduces the unfused reference bit-for-bit) and stash them
  pre-scaled in scratch.
- history steps (``sj < n_hist_blocks``): frontier-clamped flash
  attention over the row's CACHE prefix ``[0, start)`` — the index map
  clamps past-frontier steps to the frontier block, so Pallas elides
  their DMA and ``pl.when`` skips their compute; reading the cache at
  its FULL length this way is what removes the static ``kv_view``
  program axis.  Quantized caches dequantize in VMEM right after the
  (halved / quartered) DMA; packed int4 unpacks two nibbles per byte
  along the sequence axis.
- tail steps: causal flash attention over the row's OWN tail K/V blocks
  ``[base, qb]`` from the flat k_new/v_new stream — roped in VMEM at
  their global positions and quantize→dequantize ROUNDTRIPPED through
  the cache precision first, because the unfused chunk path attends to
  the values it just wrote through the cache (quantized), and the two
  paths must stay token-identical.
- the step staging the block's own K/V (``sj - n_hist == qb``) also
  performs the APPEND: the roped, cache-precision rows write into the
  aliased cache output block — no XLA scatter ever materializes.  Under
  ``kv_quant="int4"`` the write packs two adjacent tokens per byte;
  ``start`` and ``block_q`` are required even (the ISSUE 14 whole-byte
  page/segment alignment the engine guarantees — chunk starts are page
  or segment multiples), so every packed write covers whole bytes and no
  nibble read-modify-write is needed: a row with an ODD tail length ends
  mid-byte, but the junk pad nibble it writes sits at position
  ``start + qlen`` which decode's own RMW append overwrites before it is
  ever attendable (the standard prefill-pad argument).  Odd ``start``
  values are rejected loudly rather than silently corrupting a
  neighbour's nibble.
- ``sj == last``: normalize the online softmax and emit the block's
  attention output.

Weight matmuls stay in XLA, where MXU fusion already works, and so does
the pre-attention RMSNorm: it precedes the QKV projections and XLA fuses it
into their operand reads.  The
einsum path (``chunk_prefill_into_cache`` + ops/attention.py
``history_attention``) remains the numerics oracle —
tests/test_ragged_prefill.py pins this kernel against it in interpret
mode across kv quants, windows, softcap, and ragged group shapes.
"""

from __future__ import annotations

import functools
from typing import List, Optional, Sequence, Tuple

import jax
import jax.numpy as jnp
import numpy as np
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from p2p_llm_tunnel_tpu.models.quant import (
    INT4_PACK_TOKENS,
    pack_byte,
    unpack_seq,
)

_NEG_INF = -0.7 * float(jnp.finfo(jnp.float32).max)

#: Default q-block width: matches the default prefix-cache page size, so
#: every chunk start (a page or segment multiple) is block-aligned.
RAGGED_BLOCK_Q = 16

#: History-axis block of the ragged kernel; clamped to 128 (or, interpret
#: only, the whole cache) when the cache length doesn't divide.
RAGGED_BLOCK_S = 256


def plan_ragged_group(
    entries: Sequence[Tuple[int, int, int]],
    block_q: int,
    tot: int,
    scratch_slot: int,
    max_row_blocks: int = 0,
) -> Tuple[np.ndarray, np.ndarray, np.ndarray, np.ndarray, np.ndarray,
           List[int]]:
    """Pure host planner for one grouped launch: pack ``entries`` of
    ``(slot, start, tail_len)`` rows into a ``tot``-token flat buffer.

    Each row's tail is placed at the next ``block_q``-aligned flat offset;
    the remaining blocks are PAD blocks pointing at the scratch slot (zero
    length, self-based, so their compute masks out entirely and their
    append lands in the scratch row — junk by definition).  Returns the
    per-block descriptor arrays ``(slot_of, start_of, qoff_of, qlen_of,
    base_of)`` plus each row's flat token offset.

    Raises when the group does not fit ``tot`` or when a start violates
    the ``block_q`` alignment the cache-append block maps require.
    """
    if tot % block_q:
        raise ValueError(f"tot {tot} not a multiple of block_q {block_q}")
    nqb = tot // block_q
    slot_of = np.full((nqb,), scratch_slot, np.int32)
    start_of = np.zeros((nqb,), np.int32)
    qoff_of = np.zeros((nqb,), np.int32)
    qlen_of = np.zeros((nqb,), np.int32)
    base_of = np.arange(nqb, dtype=np.int32)  # pad blocks: self-based
    flat_offs: List[int] = []
    blk = 0
    for slot, start, tail_len in entries:
        if tail_len <= 0:
            raise ValueError("ragged group rows need tail_len >= 1")
        if start % block_q:
            raise ValueError(
                f"ragged prefill start {start} is not a multiple of the "
                f"q-block width {block_q}: chunk starts must be page/"
                f"segment multiples (the ISSUE 14 alignment contract)"
            )
        n_blocks = -(-tail_len // block_q)
        if max_row_blocks and n_blocks > max_row_blocks:
            raise ValueError(
                f"row tail of {tail_len} tokens exceeds the kernel's "
                f"{max_row_blocks}-block per-row bound (its row-relative "
                f"tail grid axis would never stage the overflow blocks)"
            )
        if (blk + n_blocks) * block_q > tot:
            raise ValueError(
                f"ragged group overflows the {tot}-token flat bucket"
            )
        flat_offs.append(blk * block_q)
        for j in range(n_blocks):
            slot_of[blk + j] = slot
            start_of[blk + j] = start
            qoff_of[blk + j] = j * block_q
            qlen_of[blk + j] = tail_len
            base_of[blk + j] = blk
        blk += n_blocks
    return slot_of, start_of, qoff_of, qlen_of, base_of, flat_offs


def _ragged_prefill_kernel(
    lay_sref,    # scalar-prefetch [1] int32: layer index into [L,...] cache
    win_sref,    # scalar-prefetch [1] int32: sliding window (sentinel = off)
    slot_sref,   # scalar-prefetch [NQB] int32: cache slot per q-block
    start_sref,  # scalar-prefetch [NQB] int32: history length per q-block
    qoff_sref,   # scalar-prefetch [NQB] int32: block's offset in its tail
    base_sref,   # scalar-prefetch [NQB] int32: row's first flat block index
    q_ref,   # [BQ, H, D] this block's queries, PRE-rope
    kn_ref,  # [BQ, K, D] ONE tail block of new keys, PRE-rope (clamped map)
    vn_ref,  # [BQ, K, D]
    k_ref,   # [BS, K, D] cache history block (raw/int8) | [BS/2, K, D] int4
    v_ref,   # same layout as k_ref
    *rest,   # kv_quant: ks/vs [BS, K, 1], then outputs + scratch
    scale: float,
    softcap: Optional[float],
    block_q: int,
    block_s: int,
    n_hist: int,
    n_total: int,
    kh: int,
    g: int,
    rope_theta: float,
    out_dtype,
    kv_quant: Optional[str],
):
    """See the module docstring for the per-step contract."""
    if kv_quant is not None:
        (ks_ref, vs_ref,
         o_ref, ok_ref, ov_ref, oks_ref, ovs_ref,
         q_sc, m_sc, l_sc, acc_sc) = rest
    else:
        (o_ref, ok_ref, ov_ref, q_sc, m_sc, l_sc, acc_sc) = rest
    qb = pl.program_id(0)
    sj = pl.program_id(1)
    start = start_sref[qb]
    qoff = qoff_sref[qb]
    base = base_sref[qb]
    window = win_sref[0]
    d = q_ref.shape[-1]
    half = d // 2
    qmax = 7.0 if kv_quant == "int4" else 127.0
    # Global positions of this block's q tokens ([BQ, 1] for masking).
    qpos = start + qoff + jax.lax.broadcasted_iota(
        jnp.int32, (block_q, 1), 0
    )

    def rope_tables(pos_col):  # [N, 1] int32 -> (sin, cos) [N, D/2] f32
        # EXACTLY ops.rope.rope_table's expression graph (freqs over the
        # even-lane arange, angle = pos * freq): rope feeds the
        # quantization rounding, so the kernel must reproduce apply_rope
        # BIT-for-bit on CPU interpret or a near-half value rounds the
        # other way and the appended cache bytes split from the chunk
        # path's (observed: 1-in-~1e3 elements at a different nibble).
        # (integer iota converted to f32 — Mosaic's tpu.iota yields
        # integer vectors only; small ints convert exactly.)
        lane2 = 2.0 * jax.lax.broadcasted_iota(
            jnp.int32, (1, half), 1
        ).astype(jnp.float32)
        freqs = 1.0 / (rope_theta ** (lane2 / d))
        ang = pos_col.astype(jnp.float32) * freqs
        return jnp.sin(ang), jnp.cos(ang)

    def rope(x, sin, cos):  # x [N, heads, D] f32; sin/cos [N, D/2]
        # Same per-half formulation as apply_rope (rx1 = x1*cos - x2*sin,
        # rx2 = x2*cos + x1*sin): an algebraically-equal rewrite invites
        # different FMA contraction and breaks the bit identity above.
        x1 = x[..., :half]
        x2 = x[..., half:]
        s_ = sin[:, None, :]
        c_ = cos[:, None, :]
        return jnp.concatenate(
            [x1 * c_ - x2 * s_, x2 * c_ + x1 * s_], axis=-1
        )

    @pl.when(sj == 0)
    def _init():
        m_sc[:] = jnp.full_like(m_sc[:], _NEG_INF)
        l_sc[:] = jnp.zeros_like(l_sc[:])
        acc_sc[:] = jnp.zeros_like(acc_sc[:])
        sin, cos = rope_tables(qpos)
        # The trailing activation-dtype cast mirrors apply_rope's
        # .astype(x.dtype): under a bf16 model the chunk path attends
        # bf16-rounded operands, so the kernel must round the same
        # values (f32 models: no-op, bit-identity preserved).
        q_sc[:] = rope(
            q_ref[:].astype(jnp.float32), sin, cos
        ).astype(q_ref.dtype).astype(jnp.float32) * scale

    def _online(k_blk, v_blk, mask):
        """One flash step over a staged [N, K, D] K/V block for every
        kv-head — the shared online-softmax update (mask [BQ, N])."""
        n = k_blk.shape[0]
        for h in range(kh):
            qh = q_sc[:, h * g:(h + 1) * g, :].reshape(block_q * g, d)
            s = jax.lax.dot_general(
                qh, k_blk[:, h, :], (((1,), (1,)), ((), ())),
                preferred_element_type=jnp.float32,
            ).reshape(block_q, g, n)
            if softcap is not None:
                s = softcap * jnp.tanh(s / softcap)
            s = jnp.where(mask[:, None, :], s, _NEG_INF)
            m_prev = m_sc[:, h * g:(h + 1) * g, :1]  # [BQ, g, 1]
            l_prev = l_sc[:, h * g:(h + 1) * g, :1]
            m_new = jnp.maximum(m_prev, s.max(axis=-1, keepdims=True))
            corr = jnp.where(m_prev == _NEG_INF, 0.0, jnp.exp(m_prev - m_new))
            p = jnp.exp(s - m_new)
            p = jnp.where(s == _NEG_INF, 0.0, p)
            acc_sc[:, h * g:(h + 1) * g, :] = (
                acc_sc[:, h * g:(h + 1) * g, :] * corr
                + jax.lax.dot_general(
                    p.reshape(block_q * g, n), v_blk[:, h, :],
                    (((1,), (0,)), ((), ())),
                    preferred_element_type=jnp.float32,
                ).reshape(block_q, g, d)
            )
            l_new = l_prev * corr + p.sum(axis=-1, keepdims=True)
            m_sc[:, h * g:(h + 1) * g, :] = jnp.broadcast_to(
                m_new, (block_q, g, m_sc.shape[-1])
            )
            l_sc[:, h * g:(h + 1) * g, :] = jnp.broadcast_to(
                l_new, (block_q, g, l_sc.shape[-1])
            )

    # -- history: cache prefix [0, start), frontier-clamped ---------------
    @pl.when((sj < n_hist) & (sj * block_s < start))
    def _hist():
        if kv_quant == "int4":
            k_blk = unpack_seq(k_ref[:]).astype(jnp.float32)
            v_blk = unpack_seq(v_ref[:]).astype(jnp.float32)
        else:
            k_blk = k_ref[:].astype(jnp.float32)  # [BS, K, D]
            v_blk = v_ref[:].astype(jnp.float32)
        if kv_quant is not None:
            # Dequantized history passes through the activation dtype
            # exactly like the chunk path's view read (bf16 rounding;
            # f32: no-op).
            k_blk = (k_blk * ks_ref[:]).astype(q_ref.dtype).astype(
                jnp.float32)
            v_blk = (v_blk * vs_ref[:]).astype(q_ref.dtype).astype(
                jnp.float32)
        k_pos = sj * block_s + jax.lax.broadcasted_iota(
            jnp.int32, (1, block_s), 1
        )
        # STRICTLY below start: the cache's tail region is stale this
        # layer (its writes are this kernel's own appends); the tail
        # contribution comes from the k_new/v_new stream below.  History
        # positions are < start <= every q position, so causality holds
        # by construction and only the window can further mask.
        mask = (k_pos < start) & ((qpos - k_pos) < window)
        _online(k_blk, v_blk, mask)

    # -- tail: the row's own new K/V blocks [base, qb], causal ------------
    # The tail axis is ROW-RELATIVE (step t stages the row's block
    # base + t), so it spans only max_row_blocks steps — the widest tail
    # any row can have — instead of the whole flat bucket: the grid stays
    # linear in group size, not quadratic.
    tj = sj - n_hist
    @pl.when((sj >= n_hist) & (base + tj <= qb))
    def _tail():
        tbase = start + tj * block_q
        tpos = tbase + jax.lax.broadcasted_iota(
            jnp.int32, (block_q, 1), 0
        )  # [BQ, 1] global positions of the staged tail block
        # Row-vector twin for the mask (a [None, :, 0] squeeze of tpos
        # lowers as a Mosaic-unsupported gather).
        tpos_row = tbase + jax.lax.broadcasted_iota(
            jnp.int32, (1, block_q), 1
        )
        sin, cos = rope_tables(tpos)
        kn = rope(kn_ref[:].astype(jnp.float32), sin, cos)  # [BQ, K, D]
        vn = vn_ref[:].astype(jnp.float32)
        if kv_quant is not None:
            # models.transformer._quant_kv/_quant_kv4 formula, verbatim —
            # any drift breaks ragged/chunked token identity.
            k_s = jnp.maximum(jnp.abs(kn).max(-1, keepdims=True), 1e-8) / qmax
            v_s = jnp.maximum(jnp.abs(vn).max(-1, keepdims=True), 1e-8) / qmax
            kq = jnp.clip(jnp.round(kn / k_s), -qmax, qmax)
            vq = jnp.clip(jnp.round(vn / v_s), -qmax, qmax)
            # The attention term uses the quantize->dequantize ROUNDTRIP:
            # the chunk oracle writes the tail through the cache and reads
            # it back quantized, so the kernel must attend to the same
            # dequantized values, not the raw f32 rows.
            kd = (kq * k_s).astype(q_ref.dtype).astype(jnp.float32)
            vd = (vq * v_s).astype(q_ref.dtype).astype(jnp.float32)
        else:
            # Raw caches store at the CACHE dtype: roundtrip the roped
            # rows through it so the attention term sees the values the
            # chunk path reads back (bf16 rounding; f32: no-op).
            kd = kn.astype(ok_ref.dtype).astype(jnp.float32)
            vd = vn.astype(ov_ref.dtype).astype(jnp.float32)
        mask = (tpos_row <= qpos) & ((qpos - tpos_row) < window)
        _online(kd, vd, mask)

        # The APPEND: this program stages the block's own rows exactly
        # when base + tj == qb — write them through to the aliased cache
        # output (pad blocks land in the scratch row; pad tokens past a
        # row's real length write junk that decode overwrites before it
        # is attendable — the standard prefill-pad argument).
        @pl.when(base + tj == qb)
        def _append():
            if kv_quant == "int4":
                kq_i = kq.astype(jnp.int32).reshape(
                    block_q // 2, 2, kh, d
                )
                vq_i = vq.astype(jnp.int32).reshape(
                    block_q // 2, 2, kh, d
                )
                # Whole-byte pack (models.quant.pack_int4 layout): token
                # 2i low nibble, 2i+1 high.  start/block_q evenness makes
                # every write byte-aligned — no nibble RMW on this path.
                ok_ref[:] = pack_byte(kq_i[:, 0], kq_i[:, 1])
                ov_ref[:] = pack_byte(vq_i[:, 0], vq_i[:, 1])
            elif kv_quant == "int8":
                ok_ref[:] = kq.astype(jnp.int8)
                ov_ref[:] = vq.astype(jnp.int8)
            else:
                ok_ref[:] = kn.astype(ok_ref.dtype)
                ov_ref[:] = vn.astype(ov_ref.dtype)
            if kv_quant is not None:
                oks_ref[:] = k_s
                ovs_ref[:] = v_s

    @pl.when(sj == n_total - 1)
    def _emit():
        o_ref[:] = (
            acc_sc[:] / jnp.maximum(l_sc[:, :, :1], 1e-30)
        ).astype(out_dtype)


def ragged_prefill_attention(
    q: jnp.ndarray,      # [TOT, H, D] flat-packed tail queries, PRE-rope
    k_new: jnp.ndarray,  # [TOT, K, D] flat-packed new keys, PRE-rope
    v_new: jnp.ndarray,  # [TOT, K, D]
    k_cache: jnp.ndarray,  # [L, B, S, K, D] raw/int8 | [L, B, S/2, K, D] int4
    v_cache: jnp.ndarray,
    k_scale: Optional[jnp.ndarray],  # [L, B, S, K] f32, or None
    v_scale: Optional[jnp.ndarray],
    slot_of: jnp.ndarray,   # [NQB] int32 descriptors (plan_ragged_group;
    start_of: jnp.ndarray,  # [NQB] int32  the planner's qlen_of output is
    qoff_of: jnp.ndarray,   # [NQB] int32  caller bookkeeping — pad tokens
    base_of: jnp.ndarray,   # [NQB] int32  are handled causally, not by it)
    layer_idx,  # int32 scalar (traced: the lax.scan layer index)
    *,
    block_q: int = RAGGED_BLOCK_Q,
    max_row_blocks: int = 0,  # static: widest per-row tail in blocks
    #                           (0 = the whole flat bucket — fully
    #                           general, but the tail grid axis scales
    #                           with it: callers that bound per-row tails
    #                           should pass the bound)
    rope_theta: float,
    kv_quant: Optional[str] = None,  # None | "int8" | "int4"
    scale: Optional[float] = None,
    softcap: Optional[float] = None,
    window=None,  # None | int | traced int scalar
    interpret: bool = False,
):
    """Ragged grouped flash prefill over one layer (see module docstring).

    Returns ``(attn [TOT, H, D], k_cache', v_cache', k_scale', v_scale')``
    — cache leaves updated in place via input/output aliasing (scale
    entries None when ``kv_quant`` is None).

    Requirements (the engine's gates enforce them):
    - ``block_q`` divides every row's ``start`` (chunk starts are page or
      segment multiples — the ISSUE 14 alignment contract) and, under
      ``kv_quant="int4"``, is even so packed writes cover whole bytes;
    - the cache length tiles (``% 128 == 0``) unless interpreting;
    - ``head_dim % 128 == 0`` unless interpreting.
    """
    tot, h, d = q.shape
    kh = k_new.shape[1]
    g = h // kh
    quantized = k_scale is not None
    if (kv_quant is not None) != quantized:
        raise ValueError("kv_quant requires k_scale/v_scale and vice versa")
    if tot % block_q:
        raise ValueError(f"flat length {tot} not a multiple of {block_q}")
    nqb = tot // block_q
    if slot_of.shape != (nqb,):
        raise ValueError(
            f"descriptor arrays must be [{nqb}] (one entry per q-block)"
        )
    if kv_quant == "int4" and block_q % INT4_PACK_TOKENS:
        raise ValueError(
            f"packed int4 ragged prefill needs an even block_q, got "
            f"{block_q} (two tokens share a byte)"
        )
    s_tokens = k_cache.shape[2] * (2 if kv_quant == "int4" else 1)
    if s_tokens % RAGGED_BLOCK_S == 0:
        bs = RAGGED_BLOCK_S
    elif s_tokens % 128 == 0:
        bs = 128
    elif interpret:
        # Interpret-only small caches (CPU test configs): one history
        # block spanning the whole cache keeps the grid legal.
        bs = s_tokens
    else:
        raise ValueError(
            f"ragged prefill needs cache length % 128 == 0, got {s_tokens}"
        )
    n_hist = s_tokens // bs
    if max_row_blocks <= 0 or max_row_blocks > nqb:
        max_row_blocks = nqb
    n_total = n_hist + max_row_blocks
    if scale is None:
        scale = d**-0.5
    win = (
        jnp.full((1,), s_tokens + tot + 1, jnp.int32) if window is None
        else jnp.reshape(window, (1,)).astype(jnp.int32)
    )
    lay = jnp.reshape(layer_idx, (1,)).astype(jnp.int32)

    kernel = functools.partial(
        _ragged_prefill_kernel,
        scale=scale,
        softcap=softcap,
        block_q=block_q,
        block_s=bs,
        n_hist=n_hist,
        n_total=n_total,
        kh=kh,
        g=g,
        rope_theta=rope_theta,
        out_dtype=q.dtype,
        kv_quant=kv_quant,
    )

    def q_index(qb, sj, lay_r, win_r, slot_r, start_r, qoff_r, base_r):
        return (qb, 0, 0)

    def tail_index(qb, sj, lay_r, win_r, slot_r, start_r, qoff_r, base_r):
        # ROW-RELATIVE tail step: step t stages the row's flat block
        # base + t, clamped to the row's own range [base, qb] — inactive
        # steps resolve to an already-staged index -> Pallas elides the
        # fetch (history steps clamp to base; past-own steps to qb).
        t = jnp.minimum(
            jnp.maximum(sj - n_hist, 0) + base_r[qb], qb
        )
        return (t, 0, 0)

    def hist_index(qb, sj, lay_r, win_r, slot_r, start_r, qoff_r, base_r):
        # Frontier clamp over the row's HISTORY: blocks at or past start
        # resolve to the frontier block (start==0 rows pin to block 0 and
        # compute nothing).  Block units, so one map serves the packed
        # int4 byte axis and the full-width layouts alike.
        f = jnp.maximum(start_r[qb] - 1, 0) // bs
        return (lay_r[0], slot_r[qb], jnp.minimum(sj, f), 0, 0)

    def append_index(qb, sj, lay_r, win_r, slot_r, start_r, qoff_r, base_r):
        # Constant over sj: the appended block flushes ONCE per q-block.
        return (lay_r[0], slot_r[qb],
                (start_r[qb] + qoff_r[qb]) // block_q, 0, 0)

    pack = 2 if kv_quant == "int4" else 1
    in_specs = [
        pl.BlockSpec((block_q, h, d), q_index),
        pl.BlockSpec((block_q, kh, d), tail_index),
        pl.BlockSpec((block_q, kh, d), tail_index),
        pl.BlockSpec((None, None, bs // pack, kh, d), hist_index),
        pl.BlockSpec((None, None, bs // pack, kh, d), hist_index),
    ]
    operands = [
        lay, win,
        slot_of.astype(jnp.int32), start_of.astype(jnp.int32),
        qoff_of.astype(jnp.int32), base_of.astype(jnp.int32),
        q, k_new, v_new, k_cache, v_cache,
    ]
    out_shapes = [
        jax.ShapeDtypeStruct((tot, h, d), q.dtype),
        jax.ShapeDtypeStruct(k_cache.shape, k_cache.dtype),
        jax.ShapeDtypeStruct(v_cache.shape, v_cache.dtype),
    ]
    out_specs = [
        pl.BlockSpec((block_q, h, d), q_index),
        pl.BlockSpec((None, None, block_q // pack, kh, d), append_index),
        pl.BlockSpec((None, None, block_q // pack, kh, d), append_index),
    ]
    # Operand index (scalar-prefetch args included) -> output index.
    aliases = {9: 1, 10: 2}
    scratch = [
        pltpu.VMEM((block_q, h, d), jnp.float32),  # q_sc (roped, scaled)
    ]
    if quantized:
        ks5 = k_scale.astype(jnp.float32)[..., None]  # [L, B, S, K, 1]
        vs5 = v_scale.astype(jnp.float32)[..., None]
        in_specs += [
            pl.BlockSpec((None, None, bs, kh, 1), hist_index),
            pl.BlockSpec((None, None, bs, kh, 1), hist_index),
        ]
        operands += [ks5, vs5]
        out_shapes += [
            jax.ShapeDtypeStruct(ks5.shape, jnp.float32),
            jax.ShapeDtypeStruct(vs5.shape, jnp.float32),
        ]
        out_specs += [
            pl.BlockSpec((None, None, block_q, kh, 1), append_index),
            pl.BlockSpec((None, None, block_q, kh, 1), append_index),
        ]
        aliases.update({11: 3, 12: 4})
    scratch += [
        pltpu.VMEM((block_q, h, 128), jnp.float32),  # m
        pltpu.VMEM((block_q, h, 128), jnp.float32),  # l
        pltpu.VMEM((block_q, h, d), jnp.float32),    # acc
    ]

    # Scoped VMEM the program needs, stated to the compiler: the f32
    # scratch (q, m, l, acc) and the double-buffered q/out/tail/history
    # blocks all grow with block_q and pass Mosaic's 16 MiB default near
    # block_q=128 (the v5e compiler asked for 18.1 MiB at H=32, D=128).
    # Twice the buffer bytes leaves room for the step's f32 temporaries;
    # a v5e core has 128 MiB of VMEM.
    act, kvb = q.dtype.itemsize, k_cache.dtype.itemsize
    vmem_buffers = (
        block_q * h * (2 * d + 2 * 128) * 4
        + 2 * 2 * block_q * h * d * act
        + 2 * 2 * block_q * kh * d * act
        + 2 * 2 * (bs // pack) * kh * d * kvb
        + 2 * 2 * (block_q // pack) * kh * d * kvb
    )
    vmem_limit = min(max(16 << 20, 2 * vmem_buffers), 96 << 20)

    outs = pl.pallas_call(
        kernel,
        out_shape=tuple(out_shapes),
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=6,
            grid=(nqb, n_total),
            in_specs=in_specs,
            out_specs=tuple(out_specs),
            scratch_shapes=scratch,
        ),
        input_output_aliases=aliases,
        compiler_params=pltpu.CompilerParams(vmem_limit_bytes=vmem_limit),
        interpret=interpret,
    )(*operands)
    if quantized:
        attn, kc, vc, ks5, vs5 = outs
        return attn, kc, vc, ks5[..., 0], vs5[..., 0]
    attn, kc, vc = outs
    return attn, kc, vc, None, None
