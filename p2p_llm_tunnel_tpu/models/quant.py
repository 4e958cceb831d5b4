"""Int8 and int4 weight-only quantization: cut the HBM weight stream.

Decode is HBM-bandwidth-bound (every step streams all weights once), so
weight-only int8 is a ~2x decode-throughput lever and the difference between
Llama-3-8B fitting a 16 GB v5e chip (8 GB int8) or not (16 GB bf16).
Packed int4 halves the weight stream AGAIN (~8.05 → ~4.2 GB/step for 8B,
PERF.md "int4 roofline"), which is the remaining ~2x upper bound once the
int8 decode floor is reached.

int8 scheme: symmetric per-output-channel.  Each matmul weight W[in, out]
stores ``q`` (int8) + ``scale`` (f32 [out]); the dequant multiply runs AFTER
the matmul (y = (x @ q) * scale), so XLA reads int8 from HBM and fuses the
int8→bf16 convert into the dot's operand load.  The embedding keeps
per-row scales, which serve both the gather (x = q[ids] * scale[ids]) and
the tied logits head (logits = (x @ q.T) * scale).

int4 scheme (``QTensor4``): two int4 values packed per int8 byte along the
CONTRACTED axis, symmetric per-group scales (``group_size`` contracted
positions share one f32 scale per output channel; default 128).  Because
the scale varies ALONG the contracted axis, dequant cannot run after the
dot — instead unpack (two arithmetic shifts) + group-scale multiply feed
the dot's operand directly, and XLA fuses them into the operand load the
same way it fuses the int8 convert: the packed bytes are what crosses HBM,
a bf16 copy never materializes (the int8 lesson).

Net-new vs the reference (no ML code there at all, SURVEY.md §2); sized by
BASELINE.md's "Llama-3 8B on v5e-1" config.
"""

from __future__ import annotations

from dataclasses import dataclass
from typing import Any, Dict, Optional

import jax
import jax.numpy as jnp

Params = Dict[str, Any]


@jax.tree_util.register_pytree_node_class
@dataclass
class QTensor:
    """Int8 weight + per-output-channel scale; a pytree leaf pair."""

    q: jnp.ndarray  # int8, same shape as the original weight
    scale: jnp.ndarray  # f32, original shape with the contracted axis dropped

    def tree_flatten(self):
        return (self.q, self.scale), None

    @classmethod
    def tree_unflatten(cls, aux, children):
        return cls(*children)

    @property
    def shape(self):
        return self.q.shape

    @property
    def dtype(self):
        return self.q.dtype


@jax.tree_util.register_pytree_node_class
@dataclass
class QTensor4:
    """Packed int4 weight + per-group scales; a pytree leaf pair.

    ``q`` stores two int4 values per int8 byte along the contracted axis
    (element 2i in the low nibble, 2i+1 in the high nibble); ``scale`` is
    f32 with the contracted axis replaced by a group axis of
    ``ceil(in/group_size)`` entries — SAME RANK as the original weight, so
    sharding specs and lax.scan layer-slicing apply to both leaves alike.

    ``axis`` is stored NEGATIVE (-1 or -2): scanning blocks slices the
    leading layer axis off both leaves, and a negative axis keeps pointing
    at the contracted dimension through that rank drop (tree_unflatten
    reuses the static aux unchanged).
    """

    q: jnp.ndarray  # int8 bytes; contracted axis has ceil(in_pad/2) entries
    scale: jnp.ndarray  # f32; contracted axis -> n_groups
    in_dim: int  # true contracted-axis length before padding
    group_size: int
    axis: int  # contracted axis, negative

    def tree_flatten(self):
        return (self.q, self.scale), (self.in_dim, self.group_size, self.axis)

    @classmethod
    def tree_unflatten(cls, aux, children):
        return cls(*children, *aux)

    @property
    def dtype(self):
        return self.q.dtype

    @property
    def shape(self):
        """LOGICAL shape (contracted axis at its true length)."""
        s = list(self.q.shape)
        s[self.axis] = self.in_dim
        return tuple(s)


def pack_int4(vals: jnp.ndarray, axis: int = -1) -> jnp.ndarray:
    """Pack int values in [-8, 7] two-per-byte along ``axis`` (even size):
    element 2i -> low nibble, 2i+1 -> high nibble."""
    axis = axis % vals.ndim
    n = vals.shape[axis]
    if n % 2:
        raise ValueError(f"pack_int4 needs an even axis size, got {n}")
    v = vals.astype(jnp.int8)
    shape = v.shape[:axis] + (n // 2, 2) + v.shape[axis + 1:]
    pairs = v.reshape(shape)
    lo = jnp.take(pairs, 0, axis=axis + 1)
    hi = jnp.take(pairs, 1, axis=axis + 1)
    return ((hi << 4) | (lo & 0x0F)).astype(jnp.int8)


def unpack_int4(packed: jnp.ndarray, axis: int = -1) -> jnp.ndarray:
    """Inverse of pack_int4: int8 bytes -> int8 values in [-8, 7], the
    packed axis doubling.  Two arithmetic shifts per nibble — cheap enough
    for XLA to fuse into a consuming dot's operand load."""
    axis = axis % packed.ndim
    lo = jnp.right_shift(jnp.left_shift(packed, 4), 4)  # sign-extend low
    hi = jnp.right_shift(packed, 4)  # arithmetic on int8
    out_shape = (
        packed.shape[:axis] + (2 * packed.shape[axis],) + packed.shape[axis + 1:]
    )
    return jnp.stack([lo, hi], axis=axis + 1).reshape(out_shape)


#: Tokens per byte along the packed int4 sequence axis — THE packing
#: constant the page-alignment contract (ISSUE 14) is a multiple of.
#: A single decode token lands mid-byte through ``append_packed_token``'s
#: covering-byte merge, but bulk writers — chunk-prefill segments, pool
#: page copies — must land on whole bytes: the engine keeps pool pages and
#: chunk widths multiples of this.
INT4_PACK_TOKENS = 2


def page_alignment_violations(kv_quant: Optional[str], page_tokens: int,
                              chunk_tokens: int) -> list:
    """The ONE spelling of the ISSUE 14 block-page alignment rule, kept
    beside the packed-byte layout it protects: under
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
    :func:`unpack_int4`."""
    p32 = p.astype(jnp.int32)
    return (jnp.right_shift(jnp.left_shift(p32, 28), 28),
            jnp.right_shift(p32, 4))


def unpack_seq(p):
    """[N/2, ...] packed bytes -> [N, ...] int32 values in [-8, 7]: token
    2i from the low nibble, 2i+1 from the high one.  The form a Pallas
    kernel reads a packed block with (ops/pallas_prefill_attention.py)."""
    lo, hi = _nibbles_i32(p)
    return jnp.stack([lo, hi], axis=1).reshape(
        (2 * p.shape[0],) + p.shape[1:]
    )


def pack_byte(lo, hi):
    """int32 nibble values -> one int8 byte (:func:`pack_int4`'s layout:
    low nibble = even token), a kernel's in-register packing."""
    return (jnp.left_shift(hi, 4) | (lo & 0x0F)).astype(jnp.int8)


# ---------------------------------------------------------------------------
# Byte-aligned packed-KV write helpers (the TC19 commit points)
#
# Every XLA-path write into a packed int4 KV plane goes through one of the
# four helpers below — they are the ONLY places a pack_int4 result may meet
# an ``.at[...].set`` (tunnelcheck TC19 enforces this statically).  The
# contract they defend (ISSUE 14/17): HBM stores into the packed plane
# cover WHOLE bytes; a nibble shared with a neighbouring token is merged in
# registers from a gathered covering byte, never half-written.  Parked rows
# ride the standard OOB semantics: gathers clamp (value unused), scatters
# drop.
# ---------------------------------------------------------------------------

def write_packed_prefix(plane: jnp.ndarray, slots: jnp.ndarray,
                        vals: jnp.ndarray) -> jnp.ndarray:
    """Full-prefix packed write: ``vals [L, Bp, T(even), K, D]`` int4 values
    land at positions ``[0, T)`` of each slot row of ``plane
    [L, R, S//2, K, D]``.  Position 0 is byte-aligned by definition, so the
    packed write is a plain whole-byte scatter."""
    packed = pack_int4(vals, axis=2)
    return plane.at[:, slots, : packed.shape[2]].set(packed)


def write_packed_chunk(plane: jnp.ndarray, rows: jnp.ndarray,
                       bpos: jnp.ndarray, vals: jnp.ndarray) -> jnp.ndarray:
    """Page-aligned chunk write, all layers at once: ``vals
    [L, Bp, T(even), K, D]`` at EVEN token starts, pre-translated by the
    caller to byte positions ``bpos [Bp, T//2]`` of rows ``rows [Bp, 1]``.
    Byte i of the write holds exactly tokens ``(start + 2i, start + 2i + 1)``
    — whole bytes, no RMW."""
    return plane.at[:, rows, bpos].set(pack_int4(vals, axis=2))


def append_packed_token(plane: jnp.ndarray, idx: jnp.ndarray,
                        slots: jnp.ndarray, positions: jnp.ndarray,
                        vals: jnp.ndarray) -> jnp.ndarray:
    """Single-token decode append: ``vals [B, K, D]`` at arbitrary-parity
    ``positions [B]``.  The new token shares a byte with its sequence
    neighbour, whose nibble must survive (for odd positions it holds the
    PREVIOUS token's real value) — gather the covering byte, merge the new
    nibble, store the whole byte."""
    bidx = positions // 2
    even = (positions % 2 == 0)[:, None, None]
    old = plane[idx, slots, bidx]
    lo = jnp.where(even, vals, old) & 0x0F
    hi = jnp.where(even, jnp.right_shift(old, 4), vals)
    return plane.at[idx, slots, bidx].set(
        (jnp.left_shift(hi, 4) | lo).astype(jnp.int8)
    )


def splice_packed_rows(plane: jnp.ndarray, slots: jnp.ndarray,
                       starts: jnp.ndarray, vals: jnp.ndarray) -> jnp.ndarray:
    """Arbitrary-start multi-token splice, all layers at once — the write
    shape of a spec-verify burst (ISSUE 17): ``vals [L, B, T, K, D]`` int4
    values land at token positions ``[starts, starts + T)`` of each row,
    ``starts [B]`` of ANY parity and T of any parity.  Gather the covering
    whole-byte range (``T//2 + 1`` bytes spans every parity case), unpack,
    overlay the burst tokens, repack, scatter the SAME whole bytes back —
    boundary nibbles outside the burst are preserved from the gathered
    bytes, and positions past the plane's end drop on the scatter (parked /
    overflow rows)."""
    _, b, t, _, _ = vals.shape
    nb = t // 2 + 1
    bpos = starts[:, None] // 2 + jnp.arange(nb)[None, :]  # [B, nb]
    old = plane[:, slots[:, None], bpos]  # [L, B, nb, K, D]
    old_tok = unpack_int4(old, axis=2)  # [L, B, 2*nb, K, D]
    jrel = jnp.arange(2 * nb)[None, :] - (starts % 2)[:, None]  # [B, 2nb]
    use_new = (jrel >= 0) & (jrel < t)
    newv = vals[:, jnp.arange(b)[:, None], jnp.clip(jrel, 0, t - 1)]
    merged = jnp.where(use_new[:, :, None, None], newv, old_tok)
    return plane.at[:, slots[:, None], bpos].set(pack_int4(merged, axis=2))


def _quantize4(w: jnp.ndarray, axis: int, group_size: int = 128) -> QTensor4:
    """Symmetric int4 over ``axis`` with per-group scales.

    Pads the contracted axis to a whole number of groups (pad weights are
    zero, so they quantize to 0 and contribute nothing to any dot) and
    clips to the symmetric range [-7, 7].
    """
    if group_size % 2:
        raise ValueError(f"group_size must be even, got {group_size}")
    axis = axis - w.ndim if axis >= 0 else axis  # normalize negative
    a = axis % w.ndim
    k = w.shape[a]
    n_groups = -(-k // group_size)
    kp = n_groups * group_size
    wf = w.astype(jnp.float32)
    if kp != k:
        pad = [(0, 0)] * w.ndim
        pad[a] = (0, kp - k)
        wf = jnp.pad(wf, pad)
    gshape = wf.shape[:a] + (n_groups, group_size) + wf.shape[a + 1:]
    wg = wf.reshape(gshape)  # contracted axis -> (n_groups, group_size)
    sub_axis = a + 1
    amax = jnp.abs(wg).max(axis=sub_axis, keepdims=True)
    scale = jnp.maximum(amax, 1e-8) / 7.0
    q = jnp.clip(jnp.round(wg / scale), -7, 7)
    q = q.reshape(wf.shape)  # back to [.., kp, ..]
    packed = pack_int4(q, axis=axis)
    return QTensor4(
        q=packed,
        scale=scale.squeeze(sub_axis).astype(jnp.float32),
        in_dim=k,
        group_size=group_size,
        axis=axis,
    )


def _dequant4(qt: QTensor4, dtype) -> jnp.ndarray:
    """Unpack + group-scale multiply -> dense weight at its LOGICAL shape.

    Callers feed the result straight into a dot; the unpack shifts, the
    scale multiply, and the slice all fuse into the dot's operand load, so
    HBM reads stay packed bytes + scales.
    """
    axis = qt.axis % qt.q.ndim
    vals = unpack_int4(qt.q, axis=axis)  # [.., kp, ..] int8
    kp = vals.shape[axis]
    n_groups = kp // qt.group_size
    gshape = (
        vals.shape[:axis] + (n_groups, qt.group_size) + vals.shape[axis + 1:]
    )
    scale = jnp.expand_dims(qt.scale, axis=axis + 1)  # [.., n_groups, 1, ..]
    deq = (vals.reshape(gshape).astype(jnp.float32) * scale).reshape(vals.shape)
    if qt.in_dim != kp:
        deq = jax.lax.slice_in_dim(deq, 0, qt.in_dim, axis=axis)
    return deq.astype(dtype)


def _quantize(w: jnp.ndarray, axis: int) -> QTensor:
    """Symmetric int8 over ``axis`` (the contracted/input axis)."""
    a = jnp.abs(w.astype(jnp.float32)).max(axis=axis, keepdims=True)
    scale = jnp.maximum(a, 1e-8) / 127.0
    q = jnp.clip(jnp.round(w.astype(jnp.float32) / scale), -127, 127).astype(jnp.int8)
    return QTensor(q=q, scale=scale.astype(jnp.float32).squeeze(axis))


def _quantize_act(x: jnp.ndarray):
    """Dynamic per-token symmetric int8 activation quant: (xq, scale)."""
    amax = jnp.max(jnp.abs(x.astype(jnp.float32)), axis=-1, keepdims=True)
    xs = jnp.maximum(amax, 1e-8) / 127.0
    xq = jnp.clip(jnp.round(x.astype(jnp.float32) / xs), -127, 127)
    return xq.astype(jnp.int8), xs


def _int8_dot(x: jnp.ndarray, q: jnp.ndarray, rhs_contract: int) -> jnp.ndarray:
    """W8A8 path: quantize activations per-token and run a native int8×int8
    MXU dot (int32 accumulate).  HBM reads stay int8 — the whole point: the
    dequant-after-dot path can materialize a bf16 weight copy (3x traffic),
    which is the r3 decode bottleneck (VERDICT Weak #1).  Returns fp32
    ``(x_int8 @ q) * x_scale`` — caller applies the weight scale."""
    xq, xs = _quantize_act(x)
    y = jax.lax.dot_general(
        xq, q,
        dimension_numbers=(((x.ndim - 1,), (rhs_contract,)), ((), ())),
        preferred_element_type=jnp.int32,
    )
    return y.astype(jnp.float32) * xs


def mm(x: jnp.ndarray, w, act_quant: bool = False) -> jnp.ndarray:
    """x @ w for plain arrays, QTensors, or QTensor4s.

    QTensor paths: weight-only (dequant after the dot, default) or W8A8
    (``act_quant=True``: dynamic int8 activations, int8 MXU dot).
    QTensor4 is always weight-only (the per-group scale varies along the
    contracted axis, so dequant feeds the operand instead — fused by XLA;
    ``act_quant`` is ignored).  A plain array under ``act_quant`` takes
    :func:`round_act`'s activations."""
    if isinstance(w, QTensor4):
        return x @ _dequant4(w, x.dtype)
    if isinstance(w, QTensor):
        if act_quant:
            y = _int8_dot(x, w.q, rhs_contract=0)
            return (y * w.scale.astype(jnp.float32)).astype(x.dtype)
        y = x @ w.q.astype(x.dtype)
        return y * w.scale.astype(x.dtype)
    return round_act(x, act_quant) @ w


def round_act(x: jnp.ndarray, act_quant: bool) -> jnp.ndarray:
    """The activations a product over UNQUANTISED weights takes: ``x``, or
    under ``act_quant`` ``x`` rounded to per-token int8 and back (``--quant
    a8``: the benchmark's activations control for a family served in
    bfloat16, not a serving mode: the dot itself stays in x's type)."""
    if not act_quant:
        return x
    xq, xs = _quantize_act(x)
    return (xq.astype(jnp.float32) * xs).astype(x.dtype)


def embed_lookup(embed, tokens: jnp.ndarray, dtype) -> jnp.ndarray:
    """Row gather for a plain or quantized embedding table."""
    if isinstance(embed, QTensor4):
        # Gather PACKED rows + their group scales, then dequantize just the
        # gathered [.., ceil(dm/2)] bytes — the table itself stays packed.
        rows = unpack_int4(embed.q[tokens], axis=-1)  # [.., kp] int8
        n_groups = rows.shape[-1] // embed.group_size
        scales = embed.scale[tokens]  # [.., n_groups]
        deq = (
            rows.reshape(rows.shape[:-1] + (n_groups, embed.group_size))
            .astype(jnp.float32) * scales[..., None]
        ).reshape(rows.shape)
        return deq[..., : embed.in_dim].astype(dtype)
    if isinstance(embed, QTensor):
        rows = embed.q[tokens].astype(dtype)
        return rows * embed.scale[tokens][..., None].astype(dtype)
    return embed[tokens]


def head_matmul(x: jnp.ndarray, embed, act_quant: bool = False) -> jnp.ndarray:
    """Tied-head logits: x @ embed.T with per-vocab-row dequant after
    (int8) or in-operand group dequant (int4)."""
    if isinstance(embed, QTensor4):
        return x @ _dequant4(embed, x.dtype).T
    if isinstance(embed, QTensor):
        if act_quant:
            logits = _int8_dot(x, embed.q, rhs_contract=1)  # [.., V]
            return (logits * embed.scale.astype(jnp.float32)).astype(x.dtype)
        logits = x @ embed.q.T.astype(x.dtype)
        return logits * embed.scale[None, :].astype(x.dtype)
    return x @ embed.T.astype(x.dtype)


def init_params_quantized(cfg, key: jax.Array) -> Params:
    """Random-init directly in int8 on-device.

    For benchmarks/tests of big models: the bf16 tree (2x the chip's HBM
    for 8B on v5e) never exists anywhere — int8 leaves are generated
    straight on the accelerator.  The WHOLE tree builds inside one jit so
    init costs one compile + one dispatch, not one per leaf.  Checkpoint
    loads use quantize_params.
    """
    return jax.jit(_build_params_quantized, static_argnums=(0,))(cfg, key)


def _build_params_quantized(cfg, key: jax.Array) -> Params:
    import jax.numpy as jnp

    if getattr(cfg, "n_experts", 0):
        raise NotImplementedError(
            "int8 quantization of MoE expert weights is not implemented; "
            "serve MoE models with quant='none'"
        )

    l, dm, h, kh, hd, f, v = (
        cfg.n_layers, cfg.dim, cfg.n_heads, cfg.n_kv_heads,
        cfg.head_dim, cfg.ffn_dim, cfg.vocab_size,
    )
    keys = jax.random.split(key, 8)

    def qdense(k, shape, fan_in, scale_shape):
        q = jax.random.randint(k, shape, -127, 128, jnp.int8)
        # scale ≈ (fan_in^-0.5)/127 reproduces the bf16 init's magnitude
        scale = jnp.full(scale_shape, (fan_in**-0.5) / 127.0, jnp.float32)
        return QTensor(q=q, scale=scale)

    dtype = jnp.bfloat16
    blocks = {
        "attn_norm": jnp.zeros((l, dm), dtype) if cfg.post_norms else jnp.ones((l, dm), dtype),
        "mlp_norm": jnp.zeros((l, dm), dtype) if cfg.post_norms else jnp.ones((l, dm), dtype),
        "wq": qdense(keys[0], (l, dm, h * hd), dm, (l, h * hd)),
        "wk": qdense(keys[1], (l, dm, kh * hd), dm, (l, kh * hd)),
        "wv": qdense(keys[2], (l, dm, kh * hd), dm, (l, kh * hd)),
        "wo": qdense(keys[3], (l, h * hd, dm), h * hd, (l, dm)),
        "w_gate": qdense(keys[4], (l, dm, f), dm, (l, f)),
        "w_up": qdense(keys[5], (l, dm, f), dm, (l, f)),
        "w_down": qdense(keys[6], (l, f, dm), f, (l, dm)),
    }
    if cfg.post_norms:
        blocks["post_attn_norm"] = jnp.zeros((l, dm), dtype)
        blocks["post_mlp_norm"] = jnp.zeros((l, dm), dtype)
    if getattr(cfg, "attn_bias", False):
        # Biases stay bf16 — 1-D, bandwidth-trivial, not worth quantizing.
        bkey = jax.random.fold_in(key, 77)
        blocks["bq"] = (jax.random.normal(bkey, (l, h * hd), jnp.float32)
                        * dm**-0.5).astype(dtype)
        blocks["bk"] = (jax.random.normal(jax.random.fold_in(bkey, 1),
                                          (l, kh * hd), jnp.float32)
                        * dm**-0.5).astype(dtype)
        blocks["bv"] = (jax.random.normal(jax.random.fold_in(bkey, 2),
                                          (l, kh * hd), jnp.float32)
                        * dm**-0.5).astype(dtype)
    params: Params = {
        "embed": qdense(keys[7], (v, dm), dm, (v,)),  # per-row: gather + tied head
        "blocks": blocks,
        "final_norm": jnp.zeros((dm,), dtype) if cfg.post_norms else jnp.ones((dm,), dtype),
    }
    if not cfg.tie_embeddings:
        params["lm_head"] = qdense(jax.random.fold_in(key, 99), (dm, v), dm, (v,))
    return params


def quantize_params(params: Params, cfg=None) -> Params:
    """Quantize every matmul weight; norms stay in their original dtype.

    Block weights are stacked [L, in, out]: the contracted axis is 1, so
    scales are per (layer, out-channel).  The embedding quantizes per row
    (axis=1 over dim), serving gather and tied head alike.
    """
    del cfg
    blocks = params["blocks"]
    if "router" in blocks:
        raise NotImplementedError(
            "int8 quantization of MoE expert weights is not implemented; "
            "serve MoE models with quant='none'"
        )
    qblocks = dict(blocks)
    for name in ("wq", "wk", "wv", "wo", "w_gate", "w_up", "w_down"):
        qblocks[name] = _quantize(blocks[name], axis=1)
    out: Params = {
        "embed": _quantize(params["embed"], axis=1),
        "blocks": qblocks,
        "final_norm": params["final_norm"],
    }
    if "lm_head" in params:
        out["lm_head"] = _quantize(params["lm_head"], axis=0)
    return out


def quantize_params_int4(params: Params, group_size: int = 128) -> Params:
    """Quantize every matmul weight to packed int4 with per-group scales.

    Same tree walk as quantize_params; contracted axes in NEGATIVE terms so
    the stored aux survives lax.scan's leading-layer-axis slicing:
    block weights [L, in, out] -> axis -2; embed [V, dm] -> -1 (one packing
    serves the gather and the tied head); lm_head [dm, V] -> -2.
    """
    blocks = params["blocks"]
    if "router" in blocks:
        raise NotImplementedError(
            "int4 quantization of MoE expert weights is not implemented; "
            "serve MoE models with quant='none'"
        )
    qblocks = dict(blocks)
    for name in ("wq", "wk", "wv", "wo", "w_gate", "w_up", "w_down"):
        qblocks[name] = _quantize4(blocks[name], axis=-2, group_size=group_size)
    out: Params = {
        "embed": _quantize4(params["embed"], axis=-1, group_size=group_size),
        "blocks": qblocks,
        "final_norm": params["final_norm"],
    }
    if "lm_head" in params:
        out["lm_head"] = _quantize4(
            params["lm_head"], axis=-2, group_size=group_size
        )
    return out


def init_params_quantized_int4(
    cfg, key: jax.Array, group_size: int = 128
) -> Params:
    """Random-init directly in packed int4 on-device (one jit, one
    dispatch — same rationale as init_params_quantized)."""
    return jax.jit(
        _build_params_quantized_int4, static_argnums=(0, 2)
    )(cfg, key, group_size)


def _build_params_quantized_int4(cfg, key: jax.Array, group_size: int) -> Params:
    if getattr(cfg, "n_experts", 0):
        raise NotImplementedError(
            "int4 quantization of MoE expert weights is not implemented; "
            "serve MoE models with quant='none'"
        )
    if group_size % 2:
        raise ValueError(f"group_size must be even, got {group_size}")

    l, dm, h, kh, hd, f, v = (
        cfg.n_layers, cfg.dim, cfg.n_heads, cfg.n_kv_heads,
        cfg.head_dim, cfg.ffn_dim, cfg.vocab_size,
    )
    keys = jax.random.split(key, 8)

    def qdense4(k, shape, fan_in, axis=-2):
        a = axis % len(shape)
        n_groups = -(-shape[a] // group_size)
        packed_shape = (
            shape[:a] + (n_groups * group_size // 2,) + shape[a + 1:]
        )
        scale_shape = shape[:a] + (n_groups,) + shape[a + 1:]
        # Random BYTES: each holds two int4 nibbles; scale ≈ (fan_in^-0.5)/7
        # reproduces the bf16 init's magnitude.
        q = jax.random.randint(k, packed_shape, -128, 128, jnp.int8)
        scale = jnp.full(scale_shape, (fan_in**-0.5) / 7.0, jnp.float32)
        return QTensor4(q=q, scale=scale, in_dim=shape[a],
                        group_size=group_size, axis=axis - len(shape)
                        if axis >= 0 else axis)

    dtype = jnp.bfloat16
    blocks = {
        "attn_norm": jnp.zeros((l, dm), dtype) if cfg.post_norms else jnp.ones((l, dm), dtype),
        "mlp_norm": jnp.zeros((l, dm), dtype) if cfg.post_norms else jnp.ones((l, dm), dtype),
        "wq": qdense4(keys[0], (l, dm, h * hd), dm),
        "wk": qdense4(keys[1], (l, dm, kh * hd), dm),
        "wv": qdense4(keys[2], (l, dm, kh * hd), dm),
        "wo": qdense4(keys[3], (l, h * hd, dm), h * hd),
        "w_gate": qdense4(keys[4], (l, dm, f), dm),
        "w_up": qdense4(keys[5], (l, dm, f), dm),
        "w_down": qdense4(keys[6], (l, f, dm), f),
    }
    if cfg.post_norms:
        blocks["post_attn_norm"] = jnp.zeros((l, dm), dtype)
        blocks["post_mlp_norm"] = jnp.zeros((l, dm), dtype)
    if getattr(cfg, "attn_bias", False):
        bkey = jax.random.fold_in(key, 77)
        blocks["bq"] = (jax.random.normal(bkey, (l, h * hd), jnp.float32)
                        * dm**-0.5).astype(dtype)
        blocks["bk"] = (jax.random.normal(jax.random.fold_in(bkey, 1),
                                          (l, kh * hd), jnp.float32)
                        * dm**-0.5).astype(dtype)
        blocks["bv"] = (jax.random.normal(jax.random.fold_in(bkey, 2),
                                          (l, kh * hd), jnp.float32)
                        * dm**-0.5).astype(dtype)
    params: Params = {
        "embed": qdense4(keys[7], (v, dm), dm, axis=-1),
        "blocks": blocks,
        "final_norm": jnp.zeros((dm,), dtype) if cfg.post_norms else jnp.ones((dm,), dtype),
    }
    if not cfg.tie_embeddings:
        params["lm_head"] = qdense4(jax.random.fold_in(key, 99), (dm, v), dm)
    return params
