"""Pallas decode-attention kernels: one token per slot vs the KV cache.

The decode analog of ops/pallas_attention.py (VERDICT r3 item 4).  The
default read of the plain bf16 cache on the TPU is ``decode_attention_rows``
(ISSUE 33, at the end of this file: one invocation a layer over the stacked
cache, a software pipeline over each live row's blocks, no view; since ISSUE
36 also over planes whose rows hold a position's KV heads side by side, keys
and values not equally wide: models/swa.py's full layers).  Behind options,
TWO older bodies share the online-softmax math:

- ``flash_decode_attention_sgrid`` (r5, VERDICT r4 item 2): the sequence
  axis joins the grid — program (slot, s-block) stages ONE
  [BLOCK_S, K, D] block, all kv-heads, and loops over them (a block that
  squeezes K out of the native layout is refused by the TPU lowering).
  The slot's position rides scalar prefetch, and the
  K/V index map CLAMPS past-frontier steps to the frontier block: Pallas
  skips the re-fetch of an unchanged block, so blocks past the frontier
  cost neither DMA nor compute (`pl.when`).  VMEM per program is
  ~2·BLOCK_S·K·D bytes-per-value regardless of view — no view cap,
  arbitrary max_seq.
  The s-grid kernel serves THREE KV precisions through one body
  (``kv_quant``): raw bf16/f32, int8 + per-(token, head) scales, and
  packed int4 (two adjacent tokens per byte along the sequence axis) —
  each quantized form dequantizes in VMEM right after its (halved /
  quartered) DMA.
- ``fused_decode_layer`` (ISSUE 4 tentpole): one program per (slot,
  s-block) covering ALL kv-heads, which additionally performs the
  per-layer decode plumbing that used to be 6-8 separate XLA kernels:
  RoPE at the slot's position (q and the new k row), in-VMEM
  quantization of the new KV row to the cache's precision, the cache
  APPEND (an aliased in-place row write into the full [L, B, S, K, D]
  cache — no XLA scatter, no dynamic-slice read), and the
  frontier-clamped flash attention.  Weight matmuls stay in XLA where
  MXU fusion already works; pre-attention RMSNorm also stays in XLA —
  it precedes the QKV projections, and XLA fuses it into their operand
  reads, so there is nothing left to fold into this kernel for the
  supported model families (a post-projection q/k-norm would be the
  case that folds here, and none of our presets uses one).

Fuses score, mask, softmax, and value matmuls into one kernel where the
einsum path (ops/attention.py cached_attention) lowers to several — fewer
kernel launches per decode step matters at 32 layers × 16 steps per burst
(≈4k launches per dispatch; PERF.md "fused decode layer").

Reads the cache in its native [.., S, K, D] layout — no per-step
transpose of a GB-scale cache.  The fused kernel's blocks span all
kv-heads ([BLOCK_S, K, D]) so the trailing block dims match the array
and the kernel cross-lowers for TPU from any host
(tests/test_tpu_compile.py compiles it for a described v5e).

The einsum path remains the numerics oracle (tests/test_pallas_decode.py,
tests/test_fused_decode_layer.py validate against it) and the fallback
for non-tileable shapes.
"""

from __future__ import annotations

import functools
from typing import Optional

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

_NEG_INF = -0.7 * float(jnp.finfo(jnp.float32).max)

#: Tokens per byte along the packed int4 sequence axis — THE packing
#: constant the page-alignment contract (ISSUE 14) is a multiple of.
#: Every kernel here handles a mid-byte FRONTIER (the nibble RMW in the
#: fused append; nibble unpack in the s-grid reads), but bulk writers —
#: chunk-prefill segments, pool page copies — must land on whole bytes:
#: the engine keeps pool pages and chunk widths multiples of this.
INT4_PACK_TOKENS = 2


def page_alignment_violations(kv_quant: Optional[str], page_tokens: int,
                              chunk_tokens: int) -> list:
    """The ONE spelling of the ISSUE 14 block-page alignment rule, kept
    beside the kernels whose packed-byte layout it protects: under
    ``kv_quant="int4"`` the pool page size and the chunk-prefill segment
    width must both be multiples of :data:`INT4_PACK_TOKENS`, so every
    chunk start (a page or segment multiple) and every page copy covers
    whole bytes — misalignment would silently corrupt the neighbouring
    nibble's token.  Returns human-readable violation strings (empty =
    aligned); the engine turns them into config fences at startup."""
    if kv_quant != "int4":
        return []
    out = []
    if page_tokens % INT4_PACK_TOKENS:
        out.append(
            f"pool page size {page_tokens} is not a multiple of the int4 "
            f"packing ({INT4_PACK_TOKENS} tokens/byte)"
        )
    if chunk_tokens > 0 and chunk_tokens % INT4_PACK_TOKENS:
        out.append(
            f"chunk segment width {chunk_tokens} is not a multiple of the "
            f"int4 packing ({INT4_PACK_TOKENS} tokens/byte)"
        )
    return out


def _nibbles_i32(p):
    """Packed int4 bytes -> (low, high) sign-extended nibbles as int32.
    The shifts run in int32: Mosaic does not legalize ``arith.shli`` on
    int8 vectors.  Values are identical to the int8 arithmetic shifts of
    models.quant.unpack_int4."""
    p32 = p.astype(jnp.int32)
    return (jnp.right_shift(jnp.left_shift(p32, 28), 28),
            jnp.right_shift(p32, 4))


def _unpack_seq(p):
    """[N/2, ...] packed bytes -> [N, ...] int32 values in [-8, 7]: token
    2i from the low nibble, 2i+1 from the high one."""
    lo, hi = _nibbles_i32(p)
    return jnp.stack([lo, hi], axis=1).reshape(
        (2 * p.shape[0],) + p.shape[1:]
    )


def _pack_byte(lo, hi):
    """int32 nibble values -> one int8 byte (models.quant.pack_int4
    layout: low nibble = even token)."""
    return (jnp.left_shift(hi, 4) | (lo & 0x0F)).astype(jnp.int8)


def flash_decode_attention(
    q: jnp.ndarray,  # [B, 1, H, D]
    k_cache: jnp.ndarray,  # [B, S, K, D]
    v_cache: jnp.ndarray,  # [B, S, K, D]
    q_positions: jnp.ndarray,  # [B] int32
    **kwargs,
) -> jnp.ndarray:
    """Drop-in for ops.attention.cached_attention on TPU-tileable shapes:
    the S-GRIDDED kernel, which fetches one block a program, skips
    past-frontier DMA, and has no view cap."""
    return flash_decode_attention_sgrid(q, k_cache, v_cache, q_positions,
                                        **kwargs)


# ---------------------------------------------------------------------------
# S-gridded variant: DMA-level frontier skipping (VERDICT r4 item 2)
# ---------------------------------------------------------------------------

#: S-axis block of the gridded kernel; clamped to the view when smaller.
BLOCK_S = 256


def _decode_kernel_sgrid(
    pos_sref,  # scalar-prefetch [B] int32: per-slot query position
    win_sref,  # scalar-prefetch [1] int32: sliding window (S+1 = disabled)
    q_ref,  # [H, D] this slot's query heads (head = kv_head * G + g)
    k_ref,  # [BS, K, D] ONE s-block of keys, all kv-heads (bf16/f32 or
    #         int8), or [BS/2, K, D] packed int4 bytes (kv_quant="int4":
    #         adjacent tokens share a byte — low nibble = token 2i)
    v_ref,  # same layout as k_ref
    *rest,  # kv_quant: (ks_ref [BS,K,1], vs_ref [BS,K,1], o, m, l, acc)
    #         else:     (o, m, l, acc)
    scale: float,
    softcap: Optional[float],
    block_s: int,
    n_sblocks: int,
    kh: int,
    g: int,
    out_dtype,
    kv_quant: Optional[str],
):
    """ONE kernel for the raw, int8-KV, and packed-int4-KV s-gridded
    variants — the online-softmax/masking/frontier logic must never
    diverge between them.  ``kv_quant`` is a static python flag
    (None | "int8" | "int4"): quantized paths get two extra per-(token,
    head) scale refs and dequantize in VMEM right after the DMA, composing
    the cut HBM traffic with the fused kernel (pre-r5 the engine forced
    the einsum path for int8 KV).  int4 additionally unpacks two nibbles
    per byte along the SEQUENCE axis (the lane axis stays D-wide, so TPU
    tiling is unaffected) — the weight-quant lesson applied to KV: packed
    bytes cross HBM, the wide copy exists only in VMEM.

    The staged block spans ALL kv-heads ([BS, K, D], the cache's trailing
    dims whole) and the body loops over them, exactly like
    ``_fused_decode_layer_kernel``: a block that squeezes the K axis out of
    the native [.., S, K, D] layout is refused by the TPU lowering (the
    last two block dims must tile (8, 128) or equal the array's)."""
    if kv_quant is not None:
        ks_ref, vs_ref, o_ref, m_sc, l_sc, acc_sc = rest
    else:
        o_ref, m_sc, l_sc, acc_sc = rest
    bi = pl.program_id(0)
    sj = pl.program_id(1)
    pos = pos_sref[bi]
    window = win_sref[0]
    # Last s-block holding any attendable key for this slot.  Parked rows
    # (pos >= view) clamp to the full range — junk output, discarded by the
    # engine's inactive mask.
    frontier = jnp.minimum(pos // block_s, n_sblocks - 1)

    @pl.when(sj == 0)
    def _init():
        m_sc[:] = jnp.full_like(m_sc[:], _NEG_INF)
        l_sc[:] = jnp.zeros_like(l_sc[:])
        acc_sc[:] = jnp.zeros_like(acc_sc[:])

    @pl.when(sj <= frontier)
    def _compute():
        if kv_quant == "int4":
            k_blk = _unpack_seq(k_ref[:]).astype(jnp.float32)  # [BS, K, D]
            v_blk = _unpack_seq(v_ref[:]).astype(jnp.float32)
        else:
            k_blk = k_ref[:].astype(jnp.float32)  # [BS, K, D]
            v_blk = v_ref[:].astype(jnp.float32)
        if kv_quant is not None:
            k_blk = k_blk * ks_ref[:]
            v_blk = v_blk * vs_ref[:]
        k_pos = sj * block_s + jax.lax.broadcasted_iota(
            jnp.int32, (1, block_s), 1
        )
        mask = (k_pos <= pos) & ((pos - k_pos) < window)
        for h in range(kh):
            rows = slice(h * g, (h + 1) * g)
            q = q_ref[rows, :].astype(jnp.float32) * scale  # [G, D]
            s = jax.lax.dot_general(
                q, k_blk[:, h, :], (((1,), (1,)), ((), ())),
                preferred_element_type=jnp.float32,
            )  # [G, BS]
            if softcap is not None:
                s = softcap * jnp.tanh(s / softcap)
            s = jnp.where(mask, s, _NEG_INF)

            m_prev = m_sc[rows, :1]  # [G, 1]
            l_prev = l_sc[rows, :1]
            m_new = jnp.maximum(m_prev, s.max(axis=-1, keepdims=True))
            corr = jnp.where(m_prev == _NEG_INF, 0.0, jnp.exp(m_prev - m_new))
            p = jnp.exp(s - m_new)
            p = jnp.where(s == _NEG_INF, 0.0, p)
            acc_sc[rows, :] = acc_sc[rows, :] * corr + jax.lax.dot_general(
                p, v_blk[:, h, :], (((1,), (0,)), ((), ())),
                preferred_element_type=jnp.float32,
            )
            l_new = l_prev * corr + p.sum(axis=-1, keepdims=True)
            # Lane-replicated stores: scratch tiles are [H, 128]; sub-lane
            # writes are awkward on TPU, broadcasting the [G, 1] scalars
            # across the lane axis keeps every store full-width.
            m_sc[rows, :] = jnp.broadcast_to(m_new, (g, m_sc.shape[-1]))
            l_sc[rows, :] = jnp.broadcast_to(l_new, (g, l_sc.shape[-1]))

    @pl.when(sj == n_sblocks - 1)
    def _emit():
        o_ref[:] = (
            acc_sc[:] / jnp.maximum(l_sc[:, :1], 1e-30)
        ).astype(out_dtype)


def flash_decode_attention_sgrid(
    q: jnp.ndarray,  # [B, 1, H, D]
    k_cache: jnp.ndarray,  # [B, S, K, D] (int8 when scales given)
    v_cache: jnp.ndarray,  # [B, S, K, D]
    q_positions: jnp.ndarray,  # [B] int32
    *,
    k_scale: Optional[jnp.ndarray] = None,  # [B, S, K] f32 (quantized cache)
    v_scale: Optional[jnp.ndarray] = None,
    kv_quant: Optional[str] = None,  # None | "int8" | "int4"
    scale: Optional[float] = None,
    softcap: Optional[float] = None,
    window=None,  # None | int | traced int scalar
    interpret: bool = False,
) -> jnp.ndarray:
    """S-gridded drop-in for ``flash_decode_attention``: per-block DMA,
    frontier-clamped index map, no view-size cap.

    Grid (B, S/BLOCK_S) with the s-axis innermost: scratch accumulators
    carry the online softmax across s-steps of one slot, every kv-head in
    the same program.  Blocks past the slot's frontier resolve to the SAME
    block index as the frontier (scalar-prefetch clamp), so Pallas elides
    their fetch; their compute is skipped with `pl.when`.  With
    ``k_scale``/``v_scale`` the cache is quantized and dequantized in
    VMEM: ``kv_quant="int8"`` reads [B, S, K, D] int8 planes, ``"int4"``
    reads [B, S/2, K, D] bytes with two adjacent tokens packed per byte
    (pack with models.quant.pack_int4(axis=1)).
    """
    b, t, h, d = q.shape
    assert t == 1, "decode step processes exactly one token per slot"
    quantized = k_scale is not None
    assert (v_scale is not None) == quantized
    if kv_quant is None and quantized:
        kv_quant = "int8"
    if (kv_quant is not None) != quantized:
        raise ValueError("kv_quant requires k_scale/v_scale and vice versa")
    # Logical sequence length: the int4 cache's s-axis is byte-packed.
    s = k_cache.shape[1] * (2 if kv_quant == "int4" else 1)
    kh = k_cache.shape[2]
    g = h // kh
    if scale is None:
        scale = d**-0.5
    # Largest supported block dividing S: views are multiples of 128 but
    # not necessarily of 256 (max_seq 384/640/... buckets).
    if s % BLOCK_S == 0:
        bs = BLOCK_S
    elif s % 128 == 0:
        bs = 128
    else:
        raise ValueError(f"sgrid decode kernel needs S % 128 == 0, got {s}")
    n_sb = s // bs

    pos = q_positions.astype(jnp.int32)  # [B]
    win = (
        jnp.full((1,), s + 1, jnp.int32) if window is None
        else jnp.reshape(window, (1,)).astype(jnp.int32)
    )

    kernel = functools.partial(
        _decode_kernel_sgrid,
        scale=scale,
        softcap=softcap,
        block_s=bs,
        n_sblocks=n_sb,
        kh=kh,
        g=g,
        out_dtype=q.dtype,
        kv_quant=kv_quant,
    )

    def slot_index(bi, sj, pos_r, win_r):
        return (bi, 0, 0)

    def kv_index(bi, sj, pos_r, win_r):
        # Clamp past-frontier steps to the frontier block: same index as
        # the previous step -> Pallas skips the DMA.  Block indices are in
        # block units, so the same map serves the packed int4 axis (block
        # bs/2 of a S/2-length axis) and the full-width layouts.
        return (bi, jnp.minimum(sj, pos_r[bi] // bs), 0, 0)

    kv_rows = bs // 2 if kv_quant == "int4" else bs
    in_specs = [
        pl.BlockSpec((None, h, d), slot_index),
        pl.BlockSpec((None, kv_rows, kh, d), kv_index),
        pl.BlockSpec((None, kv_rows, kh, d), kv_index),
    ]
    operands = [pos, win, q[:, 0], k_cache, v_cache]
    if quantized:
        in_specs += [
            pl.BlockSpec((None, bs, kh, 1), kv_index),
            pl.BlockSpec((None, bs, kh, 1), kv_index),
        ]
        operands += [
            k_scale.astype(jnp.float32)[..., None],  # [B, S, K, 1]
            v_scale.astype(jnp.float32)[..., None],
        ]

    out = pl.pallas_call(
        kernel,
        out_shape=jax.ShapeDtypeStruct((b, h, d), q.dtype),
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=2,
            grid=(b, n_sb),
            in_specs=in_specs,
            out_specs=pl.BlockSpec((None, h, d), slot_index),
            scratch_shapes=[
                pltpu.VMEM((h, 128), jnp.float32),
                pltpu.VMEM((h, 128), jnp.float32),
                pltpu.VMEM((h, d), jnp.float32),
            ],
        ),
        interpret=interpret,
    )(*operands)
    return out.reshape(b, 1, h, d)


def flash_decode_attention_sgrid_int8(
    q: jnp.ndarray,
    k_cache: jnp.ndarray,
    v_cache: jnp.ndarray,
    k_scale: jnp.ndarray,
    v_scale: jnp.ndarray,
    q_positions: jnp.ndarray,
    **kwargs,
) -> jnp.ndarray:
    """int8-KV convenience entry: delegates to the shared s-grid kernel."""
    return flash_decode_attention_sgrid(
        q, k_cache, v_cache, q_positions,
        k_scale=k_scale, v_scale=v_scale, kv_quant="int8", **kwargs,
    )


def flash_decode_attention_sgrid_int4(
    q: jnp.ndarray,
    k_cache: jnp.ndarray,  # [B, S/2, K, D] int8: two tokens packed per byte
    v_cache: jnp.ndarray,
    k_scale: jnp.ndarray,  # [B, S, K] f32 per-(token, head)
    v_scale: jnp.ndarray,
    q_positions: jnp.ndarray,
    **kwargs,
) -> jnp.ndarray:
    """Packed-int4-KV entry: delegates to the shared s-grid kernel, which
    unpacks the sequence-axis byte pairs in VMEM (models.quant.pack_int4
    with axis=1 produces the expected layout).  The int4 analog of the
    int8 variant — the kernel family covers every weight/KV precision the
    engine serves, dequantizing after the DMA so only packed bytes cross
    HBM.  Oracle-pinned in interpret mode (tests/test_quant_int4.py)."""
    return flash_decode_attention_sgrid(
        q, k_cache, v_cache, q_positions,
        k_scale=k_scale, v_scale=v_scale, kv_quant="int4", **kwargs,
    )


# ---------------------------------------------------------------------------
# Fused decode-layer kernel (ISSUE 4 tentpole)
# ---------------------------------------------------------------------------


def _fused_decode_layer_kernel(
    idx_sref,  # scalar-prefetch [1] int32: layer index into the [L,...] cache
    pos_sref,  # scalar-prefetch [B] int32: per-slot query position
    win_sref,  # scalar-prefetch [1] int32: sliding window (view+1 = disabled)
    q_ref,  # [H, D] this slot's query heads, PRE-rope
    kn_ref,  # [K, D] new key row, PRE-rope
    vn_ref,  # [K, D] new value row
    k_ref,  # [BS, K, D] cache block (raw/int8) | [BS/2, K, D] packed int4
    v_ref,  # same layout as k_ref
    *rest,  # kv_quant: ks_ref/vs_ref [BS, K, 1], then outputs+scratch
    scale: float,
    softcap: Optional[float],
    block_s: int,
    n_sblocks: int,
    kh: int,
    g: int,
    view: int,
    rope_theta: float,
    out_dtype,
    kv_quant: Optional[str],
):
    """ONE kernel for the whole per-layer decode attention sub-block.

    Per (slot, s-block) program, all kv-heads:
    - sj == 0: RoPE q and the new k row at the slot's position (the
      rotate-half convention of ops/rope.py, same freq formula so the
      interpret-mode oracle agrees bit-for-bit on CPU), quantize the new
      row to the cache precision in VMEM, stash everything in scratch.
    - sj <= frontier: online-softmax flash attention over the staged
      cache block, dequantized in VMEM (the s-grid kernel's math; cache
      keys mask STRICTLY below pos — position pos itself is stale until
      this kernel's own append lands).
    - sj == frontier: the APPEND — write the quantized new row (packed
      read-modify-write of the shared byte for int4) into the aliased
      cache row output.  Parked rows (pos >= view) write their old row
      back unchanged, the Pallas analog of XLA's OOB-scatter drop.
    - sj == n_sblocks-1: fold in the new row's own attention term (it is
      attendable at its own position) and emit the normalized output.
    """
    if kv_quant is not None:
        (ks_ref, vs_ref,
         o_ref, ok_ref, ov_ref, oks_ref, ovs_ref,
         q_sc, kq_sc, vq_sc, ksc_sc, vsc_sc, m_sc, l_sc, acc_sc) = rest
    else:
        (o_ref, ok_ref, ov_ref,
         q_sc, kq_sc, vq_sc, m_sc, l_sc, acc_sc) = rest
    bi = pl.program_id(0)
    sj = pl.program_id(1)
    pos = pos_sref[bi]
    window = win_sref[0]
    d = q_ref.shape[-1]
    frontier = jnp.minimum(pos // block_s, n_sblocks - 1)
    parked = pos >= view
    cpos = jnp.minimum(pos, view - 1)
    qmax = 7.0 if kv_quant == "int4" else 127.0

    @pl.when(sj == 0)
    def _init():
        m_sc[:] = jnp.full_like(m_sc[:], _NEG_INF)
        l_sc[:] = jnp.zeros_like(l_sc[:])
        acc_sc[:] = jnp.zeros_like(acc_sc[:])
        # RoPE tables at this slot's position, rotate-half layout: lane i
        # and lane i + D/2 share angle pos / theta^(2i/D) — the exact
        # expression of ops.rope.rope_table so interpret mode reproduces
        # the unfused reference to the ulp.
        half = d // 2
        # Integer iota converted to f32: Mosaic's tpu.iota yields integer
        # vectors only, and small ints convert exactly, so the bit identity
        # with ops.rope.rope_table holds.
        lane = jax.lax.broadcasted_iota(jnp.int32, (1, d), 1)
        pair = jnp.where(lane < half, lane, lane - half).astype(jnp.float32)
        freqs = 1.0 / (rope_theta ** (2.0 * pair / d))
        ang = pos.astype(jnp.float32) * freqs
        sin = jnp.sin(ang)
        cos = jnp.cos(ang)

        def rope(x):  # x [rows, D] f32
            x1 = x[:, :half]
            x2 = x[:, half:]
            rot = jnp.concatenate([-x2, x1], axis=-1)
            return x * cos + rot * sin

        q_sc[:] = rope(q_ref[:].astype(jnp.float32)) * scale
        kn = rope(kn_ref[:].astype(jnp.float32))
        vn = vn_ref[:].astype(jnp.float32)
        if kv_quant is not None:
            # Same formula as models.transformer's _quant_kv/_quant_kv4:
            # symmetric over D, per-(token, head) scale, 1e-8 floor.
            k_s = jnp.maximum(jnp.abs(kn).max(-1, keepdims=True), 1e-8) / qmax
            v_s = jnp.maximum(jnp.abs(vn).max(-1, keepdims=True), 1e-8) / qmax
            kq_sc[:] = jnp.clip(jnp.round(kn / k_s), -qmax, qmax)
            vq_sc[:] = jnp.clip(jnp.round(vn / v_s), -qmax, qmax)
            ksc_sc[:] = jnp.broadcast_to(k_s, ksc_sc.shape)
            vsc_sc[:] = jnp.broadcast_to(v_s, vsc_sc.shape)
        else:
            kq_sc[:] = kn
            vq_sc[:] = vn

    @pl.when(sj <= frontier)
    def _compute():
        if kv_quant == "int4":
            k_blk = _unpack_seq(k_ref[:]).astype(jnp.float32)
            v_blk = _unpack_seq(v_ref[:]).astype(jnp.float32)
        else:
            k_blk = k_ref[:].astype(jnp.float32)  # [BS, K, D]
            v_blk = v_ref[:].astype(jnp.float32)
        if kv_quant is not None:
            k_blk = k_blk * ks_ref[:]
            v_blk = v_blk * vs_ref[:]
        k_pos = sj * block_s + jax.lax.broadcasted_iota(
            jnp.int32, (1, block_s), 1
        )
        # STRICT < pos: the row at pos is stale until this kernel's own
        # append; the new token's term is folded separately at emit.
        mask = (k_pos < pos) & ((pos - k_pos) < window)
        for h in range(kh):
            qh = q_sc[h * g:(h + 1) * g, :]  # [G, D], pre-scaled
            s = jax.lax.dot_general(
                qh, k_blk[:, h, :], (((1,), (1,)), ((), ())),
                preferred_element_type=jnp.float32,
            )  # [G, BS]
            if softcap is not None:
                s = softcap * jnp.tanh(s / softcap)
            s = jnp.where(mask, s, _NEG_INF)
            m_prev = m_sc[h * g:(h + 1) * g, :1]
            l_prev = l_sc[h * g:(h + 1) * g, :1]
            m_new = jnp.maximum(m_prev, s.max(axis=-1, keepdims=True))
            corr = jnp.where(m_prev == _NEG_INF, 0.0, jnp.exp(m_prev - m_new))
            p = jnp.exp(s - m_new)
            p = jnp.where(s == _NEG_INF, 0.0, p)
            acc_sc[h * g:(h + 1) * g, :] = (
                acc_sc[h * g:(h + 1) * g, :] * corr
                + jax.lax.dot_general(
                    p, v_blk[:, h, :], (((1,), (0,)), ((), ())),
                    preferred_element_type=jnp.float32,
                )
            )
            l_new = l_prev * corr + p.sum(axis=-1, keepdims=True)
            m_sc[h * g:(h + 1) * g, :] = jnp.broadcast_to(
                m_new, (g, m_sc.shape[-1])
            )
            l_sc[h * g:(h + 1) * g, :] = jnp.broadcast_to(
                l_new, (g, l_sc.shape[-1])
            )

    @pl.when(sj == frontier)
    def _append():
        # The staged block is the frontier block here, so the old row (for
        # parked write-back and the int4 shared-nibble RMW) is in VMEM.
        if kv_quant == "int4":
            rb = cpos // 2 - frontier * (block_s // 2)
            old = k_ref[pl.ds(rb, 1), :, :]  # [1, K, D] bytes
            old_v = v_ref[pl.ds(rb, 1), :, :]
            even = (cpos % 2) == 0
            kq = jnp.round(kq_sc[:]).astype(jnp.int8)[None]
            vq = jnp.round(vq_sc[:]).astype(jnp.int8)[None]

            def pack_row(new, old_b):
                old_lo, old_hi = _nibbles_i32(old_b)
                new = new.astype(jnp.int32)
                return _pack_byte(jnp.where(even, new, old_lo),
                                  jnp.where(even, old_hi, new))

            ok_ref[:] = jnp.where(parked, old, pack_row(kq, old))
            ov_ref[:] = jnp.where(parked, old_v, pack_row(vq, old_v))
        else:
            row = cpos - frontier * block_s
            old_k = k_ref[pl.ds(row, 1), :, :]
            old_v = v_ref[pl.ds(row, 1), :, :]
            if kv_quant == "int8":
                kq = jnp.round(kq_sc[:]).astype(jnp.int8)[None]
                vq = jnp.round(vq_sc[:]).astype(jnp.int8)[None]
            else:
                kq = kq_sc[:].astype(ok_ref.dtype)[None]
                vq = vq_sc[:].astype(ov_ref.dtype)[None]
            ok_ref[:] = jnp.where(parked, old_k, kq)
            ov_ref[:] = jnp.where(parked, old_v, vq)
        if kv_quant is not None:
            srow = cpos - frontier * block_s
            old_ks = ks_ref[pl.ds(srow, 1), :, :]  # [1, K, 1]
            old_vs = vs_ref[pl.ds(srow, 1), :, :]
            oks_ref[:] = jnp.where(parked, old_ks, ksc_sc[:, :1][None])
            ovs_ref[:] = jnp.where(parked, old_vs, vsc_sc[:, :1][None])

    @pl.when(sj == n_sblocks - 1)
    def _emit():
        # Fold the new token's own (k, v) — attendable at its position,
        # always inside any window — using the DEQUANTIZED values future
        # steps will read back, so fused and unfused stay token-identical.
        if kv_quant is not None:
            kd = kq_sc[:] * ksc_sc[:, :1]
            vd = vq_sc[:] * vsc_sc[:, :1]
        else:
            kd = kq_sc[:]
            vd = vq_sc[:]
        for h in range(kh):
            qh = q_sc[h * g:(h + 1) * g, :]
            s = jax.lax.dot_general(
                qh, kd[h:h + 1, :], (((1,), (1,)), ((), ())),
                preferred_element_type=jnp.float32,
            )  # [G, 1]
            if softcap is not None:
                s = softcap * jnp.tanh(s / softcap)
            m_prev = m_sc[h * g:(h + 1) * g, :1]
            l_prev = l_sc[h * g:(h + 1) * g, :1]
            m_new = jnp.maximum(m_prev, s)
            corr = jnp.where(m_prev == _NEG_INF, 0.0, jnp.exp(m_prev - m_new))
            p = jnp.exp(s - m_new)
            acc = acc_sc[h * g:(h + 1) * g, :] * corr + p * vd[h:h + 1, :]
            l_new = l_prev * corr + p
            o_ref[h * g:(h + 1) * g, :] = (
                acc / jnp.maximum(l_new, 1e-30)
            ).astype(out_dtype)


def fused_decode_layer(
    q: jnp.ndarray,  # [B, H, D] post-projection, PRE-rope
    k_new: jnp.ndarray,  # [B, K, D] post-projection, PRE-rope
    v_new: jnp.ndarray,  # [B, K, D]
    k_cache: jnp.ndarray,  # [L, B, S, K, D] raw/int8 | [L, B, S/2, K, D] int4
    v_cache: jnp.ndarray,
    k_scale: Optional[jnp.ndarray],  # [L, B, S, K] f32, or None
    v_scale: Optional[jnp.ndarray],
    positions: jnp.ndarray,  # [B] int32
    layer_idx,  # int32 scalar (traced: the lax.scan layer index)
    *,
    kv_view: int,  # static: attention reads cache[..., :kv_view, :, :]
    rope_theta: float,
    kv_quant: Optional[str] = None,  # None | "int8" | "int4"
    scale: Optional[float] = None,
    softcap: Optional[float] = None,
    window=None,  # None | int | traced int scalar
    interpret: bool = False,
):
    """Fused per-layer decode attention sub-block (ISSUE 4 tentpole).

    Replaces, in ONE pallas_call per layer, what the unfused decode path
    issues as separate XLA kernels: rope(q), rope(k), the new-row KV
    quantization, 2-4 cache scatters, 2-4 view dynamic-slices, and the
    attention itself.  Takes the FULL stacked cache and the traced layer
    index (scalar prefetch drives the block index maps), so neither a
    per-layer dynamic-slice read nor a scatter write ever materializes;
    the updated cache leaves come back via in-place input/output aliasing
    with only the appended row's bytes actually written to HBM.

    Requirements (the decode_step gate enforces them):
    - ``kv_view`` % 128 == 0, and every ACTIVE slot's position < kv_view
      (the engine's bucket selection guarantees it; positions >= kv_view
      are treated as parked rows — junk output, cache row preserved).
    - head_dim tiles (% 128 == 0) unless running in interpret mode.

    Returns ``(attn [B, H, D], k_cache', v_cache', k_scale', v_scale')``
    (scale entries None when ``kv_quant`` is None).
    """
    l, b = k_cache.shape[0], k_cache.shape[1]
    h, d = q.shape[1], q.shape[2]
    kh = k_new.shape[1]
    g = h // kh
    quantized = k_scale is not None
    if (kv_quant is not None) != quantized:
        raise ValueError("kv_quant requires k_scale/v_scale and vice versa")
    s_tokens = k_cache.shape[2] * (2 if kv_quant == "int4" else 1)
    view = min(kv_view, s_tokens)
    if view % BLOCK_S == 0:
        bs = BLOCK_S
    elif view % 128 == 0:
        bs = 128
    else:
        raise ValueError(f"fused decode layer needs view % 128 == 0, got {view}")
    n_sb = view // bs
    if scale is None:
        scale = d**-0.5
    pos = positions.astype(jnp.int32)
    win = (
        jnp.full((1,), view + 1, jnp.int32) if window is None
        else jnp.reshape(window, (1,)).astype(jnp.int32)
    )
    idx = jnp.reshape(layer_idx, (1,)).astype(jnp.int32)

    kernel = functools.partial(
        _fused_decode_layer_kernel,
        scale=scale,
        softcap=softcap,
        block_s=bs,
        n_sblocks=n_sb,
        kh=kh,
        g=g,
        view=view,
        rope_theta=rope_theta,
        out_dtype=q.dtype,
        kv_quant=kv_quant,
    )

    def slot_index(bi, sj, idx_r, pos_r, win_r):
        return (bi, 0, 0)

    def kv_index(bi, sj, idx_r, pos_r, win_r):
        # Past-frontier steps clamp to the frontier block (same index ->
        # Pallas elides the fetch); block units, so one map serves the
        # packed int4 axis and the full-width layouts alike.
        return (idx_r[0], bi, jnp.minimum(sj, pos_r[bi] // bs), 0, 0)

    pack = 2 if kv_quant == "int4" else 1

    def row_index(bi, sj, idx_r, pos_r, win_r):
        # Constant over sj: the appended row flushes ONCE per slot.
        return (idx_r[0], bi,
                jnp.minimum(pos_r[bi], view - 1) // pack, 0, 0)

    def srow_index(bi, sj, idx_r, pos_r, win_r):
        return (idx_r[0], bi, jnp.minimum(pos_r[bi], view - 1), 0, 0)

    in_specs = [
        pl.BlockSpec((None, h, d), slot_index),
        pl.BlockSpec((None, kh, d), slot_index),
        pl.BlockSpec((None, kh, d), slot_index),
        pl.BlockSpec((None, None, bs // pack, kh, d), kv_index),
        pl.BlockSpec((None, None, bs // pack, kh, d), kv_index),
    ]
    operands = [idx, pos, win, q, k_new, v_new, k_cache, v_cache]
    out_shapes = [
        jax.ShapeDtypeStruct((b, h, d), q.dtype),
        jax.ShapeDtypeStruct(k_cache.shape, k_cache.dtype),
        jax.ShapeDtypeStruct(v_cache.shape, v_cache.dtype),
    ]
    out_specs = [
        pl.BlockSpec((None, h, d), slot_index),
        pl.BlockSpec((None, None, 1, kh, d), row_index),
        pl.BlockSpec((None, None, 1, kh, d), row_index),
    ]
    # Operand index (scalar-prefetch args included) -> output index.
    aliases = {6: 1, 7: 2}
    scratch = [
        pltpu.VMEM((h, d), jnp.float32),  # q_sc (rope'd, pre-scaled)
        pltpu.VMEM((kh, d), jnp.float32),  # kq_sc
        pltpu.VMEM((kh, d), jnp.float32),  # vq_sc
    ]
    if quantized:
        ks5 = k_scale.astype(jnp.float32)[..., None]  # [L, B, S, K, 1]
        vs5 = v_scale.astype(jnp.float32)[..., None]
        in_specs += [
            pl.BlockSpec((None, None, bs, kh, 1), kv_index),
            pl.BlockSpec((None, None, bs, kh, 1), kv_index),
        ]
        operands += [ks5, vs5]
        out_shapes += [
            jax.ShapeDtypeStruct(ks5.shape, jnp.float32),
            jax.ShapeDtypeStruct(vs5.shape, jnp.float32),
        ]
        out_specs += [
            pl.BlockSpec((None, None, 1, kh, 1), srow_index),
            pl.BlockSpec((None, None, 1, kh, 1), srow_index),
        ]
        aliases.update({8: 3, 9: 4})
        scratch += [
            pltpu.VMEM((kh, 128), jnp.float32),  # ksc_sc
            pltpu.VMEM((kh, 128), jnp.float32),  # vsc_sc
        ]
    scratch += [
        pltpu.VMEM((h, 128), jnp.float32),  # m
        pltpu.VMEM((h, 128), jnp.float32),  # l
        pltpu.VMEM((h, d), jnp.float32),  # acc
    ]

    outs = pl.pallas_call(
        kernel,
        out_shape=tuple(out_shapes),
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=3,
            grid=(b, n_sb),
            in_specs=in_specs,
            out_specs=tuple(out_specs),
            scratch_shapes=scratch,
        ),
        input_output_aliases=aliases,
        interpret=interpret,
    )(*operands)
    if quantized:
        attn, kc, vc, ks5, vs5 = outs
        return attn, kc, vc, ks5[..., 0], vs5[..., 0]
    attn, kc, vc = outs
    return attn, kc, vc, None, None


# ---------------------------------------------------------------------------
# Fused K-token speculative verify kernel (ISSUE 17 tentpole)
# ---------------------------------------------------------------------------


def _fused_spec_decode_layer_kernel(
    idx_sref,  # scalar-prefetch [1] int32: layer index into the [L,...] cache
    pos_sref,  # scalar-prefetch [B] int32: per-slot position of burst row 0
    win_sref,  # scalar-prefetch [1] int32: sliding window (view+1 = disabled)
    q_ref,  # [T*H, D] this slot's T query rows' heads, PRE-rope
    kn_ref,  # [T*K, D] new key rows, PRE-rope
    vn_ref,  # [T*K, D] new value rows
    k_ref,  # [BS, K, D] cache block (raw/int8) | [BS/2, K, D] packed int4
    v_ref,  # same layout as k_ref
    *rest,  # kv_quant: ks_ref/vs_ref [BS, K, 1], then outputs+scratch
    scale: float,
    softcap: Optional[float],
    block_s: int,
    n_sblocks: int,
    t_burst: int,
    kh: int,
    g: int,
    view: int,
    rope_theta: float,
    out_dtype,
    kv_quant: Optional[str],
):
    """The K+1-position verify-burst twin of ``_fused_decode_layer_kernel``.

    One program per (slot, grid-step) where the grid's s-axis is
    ``n_sblocks`` flash steps followed by ``t_burst`` append steps:

    - sj == 0: RoPE all T query/key rows at positions ``pos + t`` and
      quantize each new KV row to the cache precision, into scratch.
    - sj <= fmax (flash): online softmax over the staged cache block for
      ALL T queries.  Burst-own rows are SUBSTITUTED into the dequantized
      block where their global position lands (their cache bytes are
      stale until this launch's appends), so query t accumulates rows
      ``< pos + t`` in exactly the block order a sequential
      ``fused_decode_layer`` pass would — per-query attention is
      bit-identical to T unfused launches, which is what keeps spec-on
      and spec-off token streams byte-identical under greedy sampling.
    - sj == n_sblocks - 1: fold each query's OWN row (attendable at its
      position) and emit all T normalized outputs.
    - sj == n_sblocks + t (append, unrolled per static t): write token
      t's quantized row through a 1-row aliased output block.  For int4,
      two adjacent tokens share a byte: consecutive append steps with the
      same byte-row index keep the output block RESIDENT in VMEM (Pallas
      flushes only on an index change), so nibbles accumulate on-chip and
      only whole bytes ever reach HBM — the byte-alignment contract that
      kills the spec_ngram config fence.  The boundary byte's neighbour
      nibble is preserved from the staged input block (its pre-launch
      value: for an odd ``pos`` that is the PREVIOUS committed token).
      Rejected-tail rows need no rollback: every mask here is strictly
      ``< pos``, so a stale speculative row is never attendable before a
      later burst/decode rewrites it.
    """
    if kv_quant is not None:
        (ks_ref, vs_ref,
         o_ref, ok_ref, ov_ref, oks_ref, ovs_ref,
         q_sc, kq_sc, vq_sc, ksc_sc, vsc_sc, m_sc, l_sc, acc_sc) = rest
    else:
        (o_ref, ok_ref, ov_ref,
         q_sc, kq_sc, vq_sc, m_sc, l_sc, acc_sc) = rest
    bi = pl.program_id(0)
    sj = pl.program_id(1)
    pos = pos_sref[bi]
    window = win_sref[0]
    d = q_ref.shape[-1]
    h_all = g * kh
    # Last s-block any burst query may attend: covers the substituted
    # burst rows, not just the cache prefix.  Parked rows (pos >= view)
    # clamp to the full range — junk output, discarded by the engine.
    fmax = jnp.minimum((pos + t_burst - 1) // block_s, n_sblocks - 1)
    qmax = 7.0 if kv_quant == "int4" else 127.0

    @pl.when(sj == 0)
    def _init():
        m_sc[:] = jnp.full_like(m_sc[:], _NEG_INF)
        l_sc[:] = jnp.zeros_like(l_sc[:])
        acc_sc[:] = jnp.zeros_like(acc_sc[:])
        half = d // 2
        lane = jax.lax.broadcasted_iota(jnp.int32, (1, d), 1)
        pair = jnp.where(lane < half, lane, lane - half).astype(jnp.float32)
        freqs = 1.0 / (rope_theta ** (2.0 * pair / d))

        def rope(x, ang):  # x [rows, D] f32
            sin = jnp.sin(ang)
            cos = jnp.cos(ang)
            x1 = x[:, :half]
            x2 = x[:, half:]
            rot = jnp.concatenate([-x2, x1], axis=-1)
            return x * cos + rot * sin

        for t in range(t_burst):
            ang = (pos + t).astype(jnp.float32) * freqs
            q_sc[t * h_all:(t + 1) * h_all] = rope(
                q_ref[t * h_all:(t + 1) * h_all].astype(jnp.float32), ang
            ) * scale
            kn = rope(kn_ref[t * kh:(t + 1) * kh].astype(jnp.float32), ang)
            vn = vn_ref[t * kh:(t + 1) * kh].astype(jnp.float32)
            if kv_quant is not None:
                k_s = jnp.maximum(
                    jnp.abs(kn).max(-1, keepdims=True), 1e-8) / qmax
                v_s = jnp.maximum(
                    jnp.abs(vn).max(-1, keepdims=True), 1e-8) / qmax
                kq_sc[t * kh:(t + 1) * kh] = jnp.clip(
                    jnp.round(kn / k_s), -qmax, qmax)
                vq_sc[t * kh:(t + 1) * kh] = jnp.clip(
                    jnp.round(vn / v_s), -qmax, qmax)
                ksc_sc[t * kh:(t + 1) * kh] = jnp.broadcast_to(
                    k_s, (kh, ksc_sc.shape[-1]))
                vsc_sc[t * kh:(t + 1) * kh] = jnp.broadcast_to(
                    v_s, (kh, vsc_sc.shape[-1]))
            else:
                kq_sc[t * kh:(t + 1) * kh] = kn
                vq_sc[t * kh:(t + 1) * kh] = vn

    def _deq_row(sc, ssc, t, h, stored: bool = False):
        # One burst row's head-h DEQUANTIZED value [1, D] — what a later
        # read of the appended cache row reproduces exactly.  ``stored``
        # additionally roundtrips through the cache storage dtype: the
        # unquantized cache is bf16, so a query attending an EARLIER
        # burst row must see the value a sequential pass would read back,
        # not the full-f32 scratch copy.  (Quantized rows are exact: the
        # int values in scratch ARE the stored bytes.)
        row = sc[t * kh + h:t * kh + h + 1, :]
        if kv_quant is not None:
            return row * ssc[t * kh + h:t * kh + h + 1, :1]
        if stored:
            return row.astype(k_ref.dtype).astype(jnp.float32)
        return row

    @pl.when(sj <= fmax)
    def _compute():
        if kv_quant == "int4":
            k_blk = _unpack_seq(k_ref[:]).astype(jnp.float32)
            v_blk = _unpack_seq(v_ref[:]).astype(jnp.float32)
        else:
            k_blk = k_ref[:].astype(jnp.float32)  # [BS, K, D]
            v_blk = v_ref[:].astype(jnp.float32)
        if kv_quant is not None:
            k_blk = k_blk * ks_ref[:]
            v_blk = v_blk * vs_ref[:]
        k_pos = sj * block_s + jax.lax.broadcasted_iota(
            jnp.int32, (1, block_s), 1
        )
        row_pos = sj * block_s + jax.lax.broadcasted_iota(
            jnp.int32, (block_s, 1), 0
        )
        for h in range(kh):
            k_h = k_blk[:, h, :]  # [BS, D]
            v_h = v_blk[:, h, :]
            # Substitute the burst's own roundtripped rows over their
            # stale cache bytes (parked rows never match: row_pos < view).
            for tt in range(t_burst):
                sel = row_pos == (pos + tt)  # [BS, 1]
                k_h = jnp.where(sel, _deq_row(kq_sc, ksc_sc if kv_quant
                                              else None, tt, h,
                                              stored=True), k_h)
                v_h = jnp.where(sel, _deq_row(vq_sc, vsc_sc if kv_quant
                                              else None, tt, h,
                                              stored=True), v_h)
            for t in range(t_burst):
                lo = t * h_all + h * g
                hi_r = lo + g
                qh = q_sc[lo:hi_r, :]  # [G, D], pre-scaled
                s = jax.lax.dot_general(
                    qh, k_h, (((1,), (1,)), ((), ())),
                    preferred_element_type=jnp.float32,
                )  # [G, BS]
                if softcap is not None:
                    s = softcap * jnp.tanh(s / softcap)
                # STRICT < pos + t: rows before query t's own position —
                # cache prefix plus the substituted earlier burst rows.
                mask = (k_pos < pos + t) & ((pos + t - k_pos) < window)
                s = jnp.where(mask, s, _NEG_INF)
                m_prev = m_sc[lo:hi_r, :1]
                l_prev = l_sc[lo:hi_r, :1]
                m_new = jnp.maximum(m_prev, s.max(axis=-1, keepdims=True))
                corr = jnp.where(
                    m_prev == _NEG_INF, 0.0, jnp.exp(m_prev - m_new))
                p = jnp.exp(s - m_new)
                p = jnp.where(s == _NEG_INF, 0.0, p)
                acc_sc[lo:hi_r, :] = (
                    acc_sc[lo:hi_r, :] * corr
                    + jax.lax.dot_general(
                        p, v_h, (((1,), (0,)), ((), ())),
                        preferred_element_type=jnp.float32,
                    )
                )
                l_new = l_prev * corr + p.sum(axis=-1, keepdims=True)
                m_sc[lo:hi_r, :] = jnp.broadcast_to(m_new, (g, m_sc.shape[-1]))
                l_sc[lo:hi_r, :] = jnp.broadcast_to(l_new, (g, l_sc.shape[-1]))

    @pl.when(sj == n_sblocks - 1)
    def _emit():
        for t in range(t_burst):
            for h in range(kh):
                lo = t * h_all + h * g
                hi_r = lo + g
                qh = q_sc[lo:hi_r, :]
                kd = _deq_row(kq_sc, ksc_sc if kv_quant else None, t, h)
                vd = _deq_row(vq_sc, vsc_sc if kv_quant else None, t, h)
                s = jax.lax.dot_general(
                    qh, kd, (((1,), (1,)), ((), ())),
                    preferred_element_type=jnp.float32,
                )  # [G, 1]
                if softcap is not None:
                    s = softcap * jnp.tanh(s / softcap)
                m_prev = m_sc[lo:hi_r, :1]
                l_prev = l_sc[lo:hi_r, :1]
                m_new = jnp.maximum(m_prev, s)
                corr = jnp.where(
                    m_prev == _NEG_INF, 0.0, jnp.exp(m_prev - m_new))
                p = jnp.exp(s - m_new)
                acc = acc_sc[lo:hi_r, :] * corr + p * vd
                l_new = l_prev * corr + p
                o_ref[lo:hi_r, :] = (
                    acc / jnp.maximum(l_new, 1e-30)
                ).astype(out_dtype)

    # Append steps, unrolled over the STATIC burst offset so each token's
    # parity/first-touch logic stays compile-time simple.
    for t in range(t_burst):
        @pl.when(sj == n_sblocks + t)
        def _append_t(t=t):
            p = pos + t
            cp = jnp.minimum(p, view - 1)
            tok_parked = p >= view
            blk = cp // block_s
            if kv_quant == "int4":
                rb = cp // 2 - blk * (block_s // 2)
                old_k = k_ref[pl.ds(rb, 1), :, :]  # [1, K, D] bytes
                old_v = v_ref[pl.ds(rb, 1), :, :]
                even = (cp % 2) == 0
                if t == 0:
                    # First touch: the neighbour nibble comes from HBM.
                    base_k, base_v = old_k, old_v
                else:
                    # A new byte starts exactly when cp is even; odd cp
                    # shares the byte the PREVIOUS append step wrote,
                    # still resident in the un-flushed output block.
                    base_k = jnp.where(even, old_k, ok_ref[:])
                    base_v = jnp.where(even, old_v, ov_ref[:])
                kq = jnp.round(kq_sc[t * kh:(t + 1) * kh]).astype(
                    jnp.int8)[None]
                vq = jnp.round(vq_sc[t * kh:(t + 1) * kh]).astype(
                    jnp.int8)[None]

                def pack_row(new, old_b):
                    old_lo, old_hi = _nibbles_i32(old_b)
                    new = new.astype(jnp.int32)
                    return _pack_byte(jnp.where(even, new, old_lo),
                                      jnp.where(even, old_hi, new))

                ok_ref[:] = jnp.where(
                    tok_parked, base_k, pack_row(kq, base_k))
                ov_ref[:] = jnp.where(
                    tok_parked, base_v, pack_row(vq, base_v))
            else:
                row = cp - blk * block_s
                old_k = k_ref[pl.ds(row, 1), :, :]
                old_v = v_ref[pl.ds(row, 1), :, :]
                # Parked steps all clamp to row view-1: keep the resident
                # block (which may hold the just-written final real row)
                # rather than re-fetching the pre-launch bytes.
                base_k = old_k if t == 0 else ok_ref[:]
                base_v = old_v if t == 0 else ov_ref[:]
                if kv_quant == "int8":
                    kq = jnp.round(kq_sc[t * kh:(t + 1) * kh]).astype(
                        jnp.int8)[None]
                    vq = jnp.round(vq_sc[t * kh:(t + 1) * kh]).astype(
                        jnp.int8)[None]
                else:
                    kq = kq_sc[t * kh:(t + 1) * kh].astype(
                        ok_ref.dtype)[None]
                    vq = vq_sc[t * kh:(t + 1) * kh].astype(
                        ov_ref.dtype)[None]
                ok_ref[:] = jnp.where(tok_parked, base_k, kq)
                ov_ref[:] = jnp.where(tok_parked, base_v, vq)
            if kv_quant is not None:
                srow = cp - blk * block_s
                old_ks = ks_ref[pl.ds(srow, 1), :, :]  # [1, K, 1]
                old_vs = vs_ref[pl.ds(srow, 1), :, :]
                base_ks = old_ks if t == 0 else oks_ref[:]
                base_vs = old_vs if t == 0 else ovs_ref[:]
                oks_ref[:] = jnp.where(
                    tok_parked, base_ks,
                    ksc_sc[t * kh:(t + 1) * kh, :1][None])
                ovs_ref[:] = jnp.where(
                    tok_parked, base_vs,
                    vsc_sc[t * kh:(t + 1) * kh, :1][None])


def fused_spec_decode_layer(
    q: jnp.ndarray,  # [B, T, H, D] post-projection, PRE-rope
    k_new: jnp.ndarray,  # [B, T, K, D] post-projection, PRE-rope
    v_new: jnp.ndarray,  # [B, T, K, D]
    k_cache: jnp.ndarray,  # [L, B, S, K, D] raw/int8 | [L, B, S/2, K, D] int4
    v_cache: jnp.ndarray,
    k_scale: Optional[jnp.ndarray],  # [L, B, S, K] f32, or None
    v_scale: Optional[jnp.ndarray],
    positions: jnp.ndarray,  # [B] int32: position of burst row 0 per slot
    layer_idx,  # int32 scalar (traced: the lax.scan layer index)
    *,
    kv_view: int,  # static: attention reads cache[..., :kv_view, :, :]
    rope_theta: float,
    kv_quant: Optional[str] = None,  # None | "int8" | "int4"
    scale: Optional[float] = None,
    softcap: Optional[float] = None,
    window=None,  # None | int | traced int scalar
    interpret: bool = False,
):
    """Fused K+1-position speculative verify burst (ISSUE 17 tentpole).

    ``fused_decode_layer`` extended from 1 new position to ``T = K + 1``
    positions per slot in ONE pallas_call per layer: in-VMEM rope for all
    T rows, causal attention among the burst's own rows folded into the
    frontier-clamped flash read over the cache prefix, and the cache
    append as T aliased in-place row writes (whole bytes only under the
    packed int4 layout — the write pattern that deletes the last
    ``config_fences`` entry).  The grid is ``(B, n_sblocks + T)``: flash
    steps first, then one append step per burst row whose 1-row output
    block stays VMEM-resident while consecutive tokens share an int4 byte.

    Requirements (the spec-verify gate enforces them):
    - ``kv_view`` % 128 == 0; every ACTIVE slot satisfies
      ``position + T <= kv_view`` (the engine pads its view bucket by the
      burst width; positions >= kv_view are parked rows — junk output,
      cache rows preserved);
    - head_dim tiles (% 128 == 0) unless running in interpret mode.

    Returns ``(attn [B, T, H, D], k_cache', v_cache', k_scale',
    v_scale')`` (scale entries None when ``kv_quant`` is None).
    """
    l, b = k_cache.shape[0], k_cache.shape[1]
    t_burst, h, d = q.shape[1], q.shape[2], q.shape[3]
    kh = k_new.shape[2]
    g = h // kh
    quantized = k_scale is not None
    if (kv_quant is not None) != quantized:
        raise ValueError("kv_quant requires k_scale/v_scale and vice versa")
    s_tokens = k_cache.shape[2] * (2 if kv_quant == "int4" else 1)
    view = min(kv_view, s_tokens)
    if view % BLOCK_S == 0:
        bs = BLOCK_S
    elif view % 128 == 0:
        bs = 128
    else:
        raise ValueError(
            f"fused spec decode layer needs view % 128 == 0, got {view}")
    n_sb = view // bs
    if scale is None:
        scale = d**-0.5
    pos = positions.astype(jnp.int32)
    win = (
        jnp.full((1,), view + 1, jnp.int32) if window is None
        else jnp.reshape(window, (1,)).astype(jnp.int32)
    )
    idx = jnp.reshape(layer_idx, (1,)).astype(jnp.int32)
    q2 = q.reshape(b, t_burst * h, d)
    kn2 = k_new.reshape(b, t_burst * kh, d)
    vn2 = v_new.reshape(b, t_burst * kh, d)

    kernel = functools.partial(
        _fused_spec_decode_layer_kernel,
        scale=scale,
        softcap=softcap,
        block_s=bs,
        n_sblocks=n_sb,
        t_burst=t_burst,
        kh=kh,
        g=g,
        view=view,
        rope_theta=rope_theta,
        out_dtype=q.dtype,
        kv_quant=kv_quant,
    )

    def slot_index(bi, sj, idx_r, pos_r, win_r):
        return (bi, 0, 0)

    pack = 2 if kv_quant == "int4" else 1

    def _app_t(sj):
        return jnp.clip(sj - n_sb, 0, t_burst - 1)

    def kv_index(bi, sj, idx_r, pos_r, win_r):
        # Flash steps clamp past-fmax fetches to the last needed block
        # (same index -> Pallas elides the DMA).  Append steps re-stage
        # the block CONTAINING the token being appended, so the old
        # neighbour byte / parked row is in VMEM even when the burst
        # crosses an s-block boundary (at most one extra fetch).
        p = pos_r[bi]
        fmax = jnp.minimum((p + t_burst - 1) // bs, n_sb - 1)
        cp = jnp.minimum(p + _app_t(sj), view - 1)
        blk = jnp.where(sj >= n_sb, cp // bs, jnp.minimum(sj, fmax))
        return (idx_r[0], bi, blk, 0, 0)

    def row_index(bi, sj, idx_r, pos_r, win_r):
        # One (byte-)row output block per append step; during flash steps
        # it parks at token 0's row (constant index -> no early flush).
        cp = jnp.minimum(pos_r[bi] + _app_t(sj), view - 1)
        return (idx_r[0], bi, cp // pack, 0, 0)

    def srow_index(bi, sj, idx_r, pos_r, win_r):
        cp = jnp.minimum(pos_r[bi] + _app_t(sj), view - 1)
        return (idx_r[0], bi, cp, 0, 0)

    in_specs = [
        pl.BlockSpec((None, t_burst * h, d), slot_index),
        pl.BlockSpec((None, t_burst * kh, d), slot_index),
        pl.BlockSpec((None, t_burst * kh, d), slot_index),
        pl.BlockSpec((None, None, bs // pack, kh, d), kv_index),
        pl.BlockSpec((None, None, bs // pack, kh, d), kv_index),
    ]
    operands = [idx, pos, win, q2, kn2, vn2, k_cache, v_cache]
    out_shapes = [
        jax.ShapeDtypeStruct((b, t_burst * h, d), q.dtype),
        jax.ShapeDtypeStruct(k_cache.shape, k_cache.dtype),
        jax.ShapeDtypeStruct(v_cache.shape, v_cache.dtype),
    ]
    out_specs = [
        pl.BlockSpec((None, t_burst * h, d), slot_index),
        pl.BlockSpec((None, None, 1, kh, d), row_index),
        pl.BlockSpec((None, None, 1, kh, d), row_index),
    ]
    # Operand index (scalar-prefetch args included) -> output index.
    aliases = {6: 1, 7: 2}
    scratch = [
        pltpu.VMEM((t_burst * h, d), jnp.float32),  # q_sc (rope'd, scaled)
        pltpu.VMEM((t_burst * kh, d), jnp.float32),  # kq_sc
        pltpu.VMEM((t_burst * kh, d), jnp.float32),  # vq_sc
    ]
    if quantized:
        ks5 = k_scale.astype(jnp.float32)[..., None]  # [L, B, S, K, 1]
        vs5 = v_scale.astype(jnp.float32)[..., None]
        in_specs += [
            pl.BlockSpec((None, None, bs, kh, 1), kv_index),
            pl.BlockSpec((None, None, bs, kh, 1), kv_index),
        ]
        operands += [ks5, vs5]
        out_shapes += [
            jax.ShapeDtypeStruct(ks5.shape, jnp.float32),
            jax.ShapeDtypeStruct(vs5.shape, jnp.float32),
        ]
        out_specs += [
            pl.BlockSpec((None, None, 1, kh, 1), srow_index),
            pl.BlockSpec((None, None, 1, kh, 1), srow_index),
        ]
        aliases.update({8: 3, 9: 4})
        scratch += [
            pltpu.VMEM((t_burst * kh, 128), jnp.float32),  # ksc_sc
            pltpu.VMEM((t_burst * kh, 128), jnp.float32),  # vsc_sc
        ]
    scratch += [
        pltpu.VMEM((t_burst * h, 128), jnp.float32),  # m
        pltpu.VMEM((t_burst * h, 128), jnp.float32),  # l
        pltpu.VMEM((t_burst * h, d), jnp.float32),  # acc
    ]

    outs = pl.pallas_call(
        kernel,
        out_shape=tuple(out_shapes),
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=3,
            grid=(b, n_sb + t_burst),
            in_specs=in_specs,
            out_specs=tuple(out_specs),
            scratch_shapes=scratch,
        ),
        input_output_aliases=aliases,
        interpret=interpret,
    )(*operands)
    if quantized:
        attn, kc, vc, ks5, vs5 = outs
        return (attn.reshape(b, t_burst, h, d), kc, vc,
                ks5[..., 0], vs5[..., 0])
    attn, kc, vc = outs
    return attn.reshape(b, t_burst, h, d), kc, vc, None, None


# ---------------------------------------------------------------------------
# Rows over the stacked cache (ISSUE 33): the default decode read
# ---------------------------------------------------------------------------

#: The smallest block of cache positions the rows kernel reads at a time:
#: the decline gate's ``max_seq % 128``.
ROWS_BLOCK = 128
#: Cache rows (positions x kv-heads) a block holds where the sequence
#: allows: under about a thousand the kernel's cost an item shows (4 KV
#: heads at 128 positions ran at 0.40 us an item where the DMA needs 0.32,
#: at 256 positions at the DMA's 0.64; PERF.md section 6, PR 33), above it
#: a row reads further past its own position.
ROWS_COLS = 1024
#: Blocks in flight: the ring holds this many key and value blocks.
ROWS_DEPTH = 4
#: A row's last block is fetched as far as its position, in this many
#: parts (on the chip, a layer of mistral's cell mix: 64.7 us whole, 59.6 in
#: halves, 59.3 in quarters, 67.9 in eighths; PERF.md section 6, PR 33).
ROWS_PARTS = 2

#: The kernel's name in compiled programs and device traces.
ROWS_KERNEL = "decode_attn_rows"


def rows_block(seq: int, kv_heads: int) -> int:
    """Cache positions one item of the rows kernel covers, from the cache's
    own shape: ``ROWS_COLS`` rows of ``[K, D]``, in whole ``ROWS_BLOCK``s
    that divide the sequence."""
    bs = max(ROWS_COLS // kv_heads // ROWS_BLOCK, 1) * ROWS_BLOCK
    while seq % bs:
        bs -= ROWS_BLOCK
    return bs


def decode_rows_worklist(positions: jnp.ndarray, seq: int,
                         block: int) -> jnp.ndarray:
    """The (row, block) pairs one decode step's attention reads, in row
    order: ``[1 + N + B] int32`` with ``N = B * seq // block``: the count
    first, then ``row << 16 | block index`` for every block of ``block``
    positions that holds a position ``<=`` the row's own (a row parked at
    ``positions >= seq`` has none), then the positions themselves — all the
    kernel's scalars that a step's layers share, in the one array that one
    copy brings in.  ``decode_step`` makes it once, outside the layer
    scan."""
    b = positions.shape[0]
    n_sb = seq // block
    pos = positions.astype(jnp.int32)
    nblk = jnp.where(pos < seq, pos // block + 1, 0)
    ends = jnp.cumsum(nblk)
    w = jnp.arange(b * n_sb, dtype=jnp.int32)
    # (compared against every row's end at once: the default search is a
    # loop of its own on the device, once a decode step)
    row = jnp.minimum(
        jnp.searchsorted(ends, w, side="right", method="compare_all")
        .astype(jnp.int32), b - 1)
    blk = w - (ends - nblk)[row]
    return jnp.concatenate([ends[-1:], row << 16 | blk, pos])


def _decode_rows_kernel(
    layer_sref,  # scalar-prefetch [2] int32: layer index into the [L,...]
    #              cache, sliding window (S+1 = disabled)
    work_sref,   # scalar-prefetch [1 + N + B] int32: decode_rows_worklist
    q_ref,      # [B, H, D] every row's query heads (head = kv_head * G + g)
    k_hbm,      # [L, B, S*K, D] the stacked cache where it lies (HBM)
    v_hbm,
    o_ref,      # [B, H, D]
    kbuf,       # [DEPTH, BS*K, D] ring of key blocks
    vbuf,
    sem,        # DMA semaphores [2, DEPTH]
    *,
    scale: float,
    softcap: Optional[float],
    block_s: int,
    depth: int,
    parts: int,
    kv_heads: int,
    side_by_side: bool = False,
):
    """One invocation a layer: a software pipeline over the step's work
    list.  Each item is one ``[BS*K, D]`` block of one row — positions
    outermost, kv-heads inside, as the cache lies — and is scored against
    ALL of the row's query heads in one product; the mask takes the other
    kv-heads' columns out again, so the value product over the same flat
    block sums only a head's own.  The MXU's cost is set by the block
    (each key and value tile passes through once), not by the 4 or 7 query
    rows a kv-head has, so the wasted columns cost nothing and the block
    needs no de-interleaving.

    ``side_by_side`` is the other way a cache lies (ISSUE 36: planes
    ``[L, B, S, K*Dk]`` and ``[L, B, S, K*Dv]``, a position's KV heads in
    its columns): the same work list, ring and softmax over another item.
    A block is ``[BS, K*Dk]`` keys and ``[BS, K*Dv]`` values; ``q_ref`` is
    ``[B, H, K*Dk]`` with a head's query in its own KV head's columns and
    zeros elsewhere, so ONE product scores every head against the block
    with no head mask (the other heads' keys meet zeros), and a KV head's
    value columns — a static slice of whole lane tiles — take only its own
    query heads' weights.  ``o_ref`` is ``[B, H, Dv]``."""
    layer = layer_sref[0]
    window = layer_sref[1]
    n_work = work_sref[0]
    rows_per_blk = kbuf.shape[1]
    b, h = q_ref.shape[:2]
    d_out = o_ref.shape[2]
    # buffer rows a cache position takes
    per_pos = rows_per_blk // block_s
    pos_at = work_sref.shape[0] - b

    def whole_div(x, n):
        # x // n of a small non-negative int32 vector, by way of float32
        # (exact: x + 0.5 is never within 2^-12 of a multiple of n here)
        return ((x.astype(jnp.float32) + 0.5) * (1.0 / n)).astype(jnp.int32)

    # A block column's position inside the block and its kv-head; a query
    # head's kv-head.
    col = jax.lax.broadcasted_iota(jnp.int32, (1, rows_per_blk), 1)
    if side_by_side:
        col_off = col
    else:
        col_off = whole_div(col, kv_heads)
        col_head = col - col_off * kv_heads
        row_head = whole_div(
            jax.lax.broadcasted_iota(jnp.int32, (h, 1), 0), h // kv_heads)

    def item(w):
        """Work item ``w``: its row, its block's index, the row's position."""
        packed = work_sref[1 + w]
        row = packed >> 16
        return row, packed & 0xFFFF, work_sref[pos_at + row]

    def copies(w, slot, then):
        """``then`` each of item ``w``'s two copies into ``slot``.  A row's
        last block is brought in only as far as the part (one of ``parts``)
        that holds the row's position: the rest of the buffer keeps what an
        earlier item left there, which the mask takes out (the value ring
        is zeroed once below, so what it takes out is finite)."""
        row, blk, pos = item(w)
        start = pl.multiple_of(blk * rows_per_blk, rows_per_blk)
        part = rows_per_blk // parts
        needed = jnp.where(blk == pos // block_s,
                           (pos % block_s) * per_pos // part + 1, parts)
        for n in range(1, parts + 1):
            @pl.when(needed == n)
            def _(n=n):
                for hbm, buf, which in ((k_hbm, kbuf, 0), (v_hbm, vbuf, 1)):
                    then(pltpu.make_async_copy(
                        hbm.at[layer, row, pl.ds(start, n * part)],
                        buf.at[slot, pl.ds(0, n * part)],
                        sem.at[which, slot]))

    def start(w):
        @pl.when(w < n_work)
        def _():
            copies(w, w % depth, lambda c: c.start())

    def weighted(p, v):
        """Float32 weights ``[R, N]`` over values ``[N, W]`` -> float32."""
        if v.dtype == jnp.float32:
            return jax.lax.dot_general(
                p, v, (((1,), (0,)), ((), ())),
                preferred_element_type=jnp.float32)
        # The weights in two bf16 halves, stacked on the rows: 2R rows cost
        # the MXU what R do, and the sum is the float32 weight's product to
        # 2^-16.
        hi = p.astype(v.dtype)
        lo = (p - hi.astype(jnp.float32)).astype(v.dtype)
        both = jax.lax.dot_general(
            jnp.concatenate([hi, lo], axis=0), v,
            (((1,), (0,)), ((), ())),
            preferred_element_type=jnp.float32)
        return both[:p.shape[0]] + both[p.shape[0]:]

    # A parked row is in no item: its output is zeros, not what the buffer
    # held.
    o_ref[...] = jnp.zeros_like(o_ref)
    vbuf[...] = jnp.zeros_like(vbuf)
    for w0 in range(depth - 1):
        start(w0)

    def body(w, carry):
        m_prev, l_prev, acc = carry
        slot = w % depth
        row, blk, pos = item(w)
        copies(w, slot, lambda c: c.wait())
        # The slot the previous item has just left takes the item DEPTH - 1
        # ahead.
        start(w + depth - 1)

        first = blk == 0
        m_prev = jnp.where(first, _NEG_INF, m_prev)
        l_prev = jnp.where(first, 0.0, l_prev)
        acc = jnp.where(first, 0.0, acc)

        q = q_ref[row]  # [H, D]
        k = kbuf[slot]  # [BS*K, D]
        v = vbuf[slot]
        s = jax.lax.dot_general(
            q, k, (((1,), (1,)), ((), ())),
            preferred_element_type=jnp.float32,
        ) * scale  # [H, BS*K]
        if softcap is not None:
            s = softcap * jnp.tanh(s / softcap)
        k_pos = blk * block_s + col_off  # [1, BS*K]
        live = (k_pos <= pos) & ((pos - k_pos) < window)
        if not side_by_side:
            live = live & (col_head == row_head)
        s = jnp.where(live, s, _NEG_INF)

        m_new = jnp.maximum(m_prev, s.max(axis=-1, keepdims=True))
        corr = jnp.where(m_prev <= _NEG_INF, 0.0, jnp.exp(m_prev - m_new))
        p = jnp.where(s <= _NEG_INF, 0.0, jnp.exp(s - m_new))
        l_new = l_prev * corr + p.sum(axis=-1, keepdims=True)
        if side_by_side:
            g = h // kv_heads
            pv = jnp.concatenate([
                weighted(p[n * g:(n + 1) * g],
                         v[:, n * d_out:(n + 1) * d_out])
                for n in range(kv_heads)], axis=0)
        else:
            pv = weighted(p, v)
        acc = acc * corr + pv

        @pl.when(blk == pos // block_s)
        def _emit():
            o_ref[row] = (acc / jnp.maximum(l_new, 1e-30)).astype(o_ref.dtype)

        return m_new, l_new, acc

    jax.lax.fori_loop(0, n_work, body, (
        jnp.full((h, 1), _NEG_INF, jnp.float32),
        jnp.zeros((h, 1), jnp.float32),
        jnp.zeros((h, d_out), jnp.float32),
    ))


def decode_attention_rows(
    q: jnp.ndarray,        # [B, H, D]
    k_cache: jnp.ndarray,  # [L, B, S, K, D] the stacked cache, row written,
    v_cache: jnp.ndarray,  # or planes [L, B, S, K*D] and [L, B, S, K*Dv]
    layer_idx,               # int32 scalar (traced: the scan's layer index)
    work: jnp.ndarray,       # decode_rows_worklist(positions, S, block)
    *,
    block: int,  # static: rows_block(S, K), the work list's
    scale: Optional[float] = None,
    softcap: Optional[float] = None,
    window=None,  # None | int | traced int scalar
    interpret: bool = False,
) -> jnp.ndarray:
    """``cached_attention`` over layer ``layer_idx`` of the stacked cache,
    read where it lies: no plane is sliced out, a row's blocks past its
    position are neither fetched nor computed, and a row parked at a
    position ``>= S`` does no work (its output is zeros).  The cache
    already holds this step's own row at the positions the work list was
    made from.  Same mathematics as the einsum: the cache's own operands
    into float32 scores, float32 softmax and accumulation.  A window masks;
    it does not yet bound the blocks fetched from below.

    The layout is the cache's own and read off its shape: five axes are
    ``[L, B, S, K, D]``; four are planes whose rows hold a position's KV
    heads side by side, keys ``K * D`` wide (``D`` the query's) and values
    ``K * Dv``, which need not be as wide.  Returns ``[B, H, D]``, of the
    planes ``[B, H, Dv]``."""
    side_by_side = k_cache.ndim == 4
    h, d = q.shape[1:]
    if side_by_side:
        l, b, s, width = k_cache.shape
        kh = width // d
        dv = v_cache.shape[-1] // kh
        per_pos = 1
        # A head's query over its own KV head's columns, zeros elsewhere.
        own = jnp.eye(kh, dtype=q.dtype)[None, :, None, :, None]
        q = (q.reshape(b, kh, h // kh, 1, d) * own).reshape(b, h, width)
    else:
        l, b, s, kh, d = k_cache.shape
        dv, per_pos = d, kh
    if s % ROWS_BLOCK or s % block:
        raise ValueError(f"rows decode kernel needs S % {ROWS_BLOCK} == 0 "
                         f"and whole blocks of {block}, got {s}")
    if scale is None:
        scale = d**-0.5
    rows = block * per_pos
    layer = jnp.stack([
        jnp.asarray(layer_idx, jnp.int32),
        jnp.asarray(s + 1 if window is None else window, jnp.int32),
    ])

    kernel = functools.partial(
        _decode_rows_kernel,
        scale=scale, softcap=softcap, block_s=block, depth=ROWS_DEPTH,
        parts=ROWS_PARTS, kv_heads=kh, side_by_side=side_by_side,
    )
    if not side_by_side:
        # (a bitcast: positions outermost, KV heads inside, as they lie)
        k_cache = k_cache.reshape(l, b, s * kh, d)
        v_cache = v_cache.reshape(l, b, s * kh, d)
    vmem = pl.BlockSpec(memory_space=pltpu.VMEM)
    hbm = pl.BlockSpec(memory_space=pl.ANY)
    return pl.pallas_call(
        kernel,
        out_shape=jax.ShapeDtypeStruct((b, h, dv), q.dtype),
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=2,
            grid=(1,),
            in_specs=[vmem, hbm, hbm],
            out_specs=vmem,
            scratch_shapes=[
                pltpu.VMEM((ROWS_DEPTH, rows, k_cache.shape[-1]),
                           k_cache.dtype),
                pltpu.VMEM((ROWS_DEPTH, rows, v_cache.shape[-1]),
                           v_cache.dtype),
                pltpu.SemaphoreType.DMA((2, ROWS_DEPTH)),
            ],
        ),
        interpret=interpret,
        name=ROWS_KERNEL,
    )(layer, work, q, k_cache, v_cache)
