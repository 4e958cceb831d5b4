"""The latent-attention (MLA) decoder with routed experts: ``sarvam-105b``.

``models/transformer.py`` hands its entry points here when
``cfg.kv_lora_rank`` is set, so the engine, the prefix pool and the tunnel
run this family through the calls they make for every other.

Layers.  Two stacked groups, each its own ``lax.scan``: the leading dense
layers (``params["dense_blocks"]``: attention + one SwiGLU of ``ffn_dim``)
and the expert layers (``params["blocks"]``: attention + ``models/moe``'s
routed layer).  Attention, ``h`` the normed input:

- ``q = W_q h`` [H, Dn + Dr]; with ``qk_norm`` an RMS norm with a weight
  over each head's whole query; the last ``Dr`` of each head roped (yarn).
- ``[c, k_r] = W_kva h`` [C + Dr]; ``c`` RMS-normed with a weight; ``k_r``
  roped, one for all heads.  **Cached: the row ``[c | k_r]``**, C + Dr
  values a token and layer, no key/value pair, no head axis.  It is kept as
  two planes whose rows are whole lane tiles: ``"c" [L, rows, S, C]`` and
  ``"kr" [L/2, rows, S, 2 Dr]``, in which two layers share a row (layer
  ``l``: plane ``l // 2``, half ``l % 2``).  One plane of C + Dr = 576
  values a row is what this was first written as: the chip's compiler then
  keeps it sequence-minor (576 is four and a half lane tiles, and it will
  not pad), and every row write, in decode, in chunk prefill and in the pool
  copies, converted the whole plane there and back
  (tests/test_tpu_compile.py guards it).
- ``[k_n, v] = W_kvb c`` [H, Dn + Dv] decompresses the latent.

Two forms of the same attention (``ops/attention``): *decompressed* in both
prefill programs (scope ``mla_up``: ``W_kvb`` over every row attended, then
per-head keys of width Dn + Dr and values of width Dv), *absorbed* in
decode (``W_kvb``'s key half folded into the query, scores and the weighted
sum taken against the cached row itself, ``W_kvb``'s value half after it).
The score scale is yarn's (``ops/rope.yarn_score_scale``).

Int8 planes (``--kv-quant int8``) keep int8 values with two float32
scales a token and layer (``"c_scale" [L, rows, S, 2]``: the latent's, the
rope key's): the benchmark's cache control, which no cell serves.

Chunk prefill keeps ISSUE 26's structure: the plane is no carry of the layer
scans; each layer reads its (layer, view) rows, lays its fresh tail over
them, and the tails of all layers are written once after the scans.

The residual stream is float32 and every product takes it rounded to the
parameters' type (bfloat16 as served): a routed layer compares scores, and
a stream rounded to bfloat16 at every addition routed one token in six to
another expert than the float32 reference's in some layer, which was most
of what ``correct`` read (PERF.md section 6, PR 28).  The router scores the
normed stream before that rounding.

Every program can also return what its expert layers counted
(``moe.STATS`` int32 values summed over layers; see ``moe_mlp``).
"""

from __future__ import annotations

from typing import Optional

import jax
import jax.numpy as jnp

from p2p_llm_tunnel_tpu.models.config import ModelConfig
from p2p_llm_tunnel_tpu.models.moe import STATS, moe_mlp
from p2p_llm_tunnel_tpu.models.quant import _quantize_act, mm
from p2p_llm_tunnel_tpu.ops.attention import (
    latent_attention_absorbed,
    latent_attention_decompressed,
)
from p2p_llm_tunnel_tpu.ops.norms import rms_norm
from p2p_llm_tunnel_tpu.ops.rope import (
    apply_rope,
    yarn_inv_freq,
    yarn_mscale,
    yarn_score_scale,
)

#: Spread of the selection bias as drawn (a power of two: the product is
#: exact).  Beside sigmoid scores of unit-variance logits it changes which
#: experts are chosen for about one token in three without piling the
#: tokens on a few experts, which is what a trained bias is there to undo.
ROUTER_BIAS_STD = 0.03125


# ---------------------------------------------------------------------------
# init
# ---------------------------------------------------------------------------

def init_params(cfg: ModelConfig, key: jax.Array, dtype=jnp.bfloat16):
    """Random init.  Expert ``e`` of expert layer ``i`` is drawn from its own
    key (``fold_in(fold_in(leaf key, i), e)`` with ``e`` the PUBLISHED
    index), one expert at a time: a share's experts are the whole model's
    of the same seed, and no float32 copy of a multi-gigabyte leaf exists
    while it is drawn."""
    dm, h, v = cfg.dim, cfg.n_heads, cfg.vocab_size
    c, dr, dn, dv = (cfg.kv_lora_rank, cfg.qk_rope_head_dim,
                     cfg.qk_nope_head_dim, cfg.v_head_dim)
    kinds = cfg.layer_kinds
    ld, lm = kinds.count("dense"), kinds.count("moe")
    keys = jax.random.split(key, 16)

    def dense(k, shape, fan_in):
        return (jax.random.truncated_normal(k, -2, 2, shape, jnp.float32)
                * (fan_in ** -0.5)).astype(dtype)

    def attn(k, l):
        ks = jax.random.split(k, 4)
        return {
            "attn_norm": jnp.ones((l, dm), dtype),
            "mlp_norm": jnp.ones((l, dm), dtype),
            "wq": dense(ks[0], (l, dm, h * (dn + dr)), dm),
            "q_norm": jnp.ones((l, dn + dr), dtype),
            "wkva": dense(ks[1], (l, dm, c + dr), dm),
            "kv_norm": jnp.ones((l, c), dtype),
            "wkvb": dense(ks[2], (l, c, h * (dn + dv)), c),
            "wo": dense(ks[3], (l, h * dv, dm), h * dv),
        }

    params = {
        "embed": dense(keys[7], (v, dm), dm),
        "final_norm": jnp.ones((dm,), dtype),
        "lm_head": dense(jax.random.fold_in(key, 99), (dm, v), dm),
    }
    if ld:
        f = cfg.ffn_dim
        params["dense_blocks"] = dict(
            attn(keys[0], ld),
            w_gate=dense(keys[4], (ld, dm, f), dm),
            w_up=dense(keys[5], (ld, dm, f), dm),
            w_down=dense(keys[6], (ld, f, dm), f),
        )
    if lm:
        e, fe = cfg.n_experts, cfg.expert_dim
        lo, held = cfg.experts_held

        def experts(k, shape, fan_in):
            def one(i):
                ke = jax.random.fold_in(
                    jax.random.fold_in(k, i // held), lo + i % held)
                return dense(ke, shape, fan_in)

            flat = jax.lax.map(one, jnp.arange(lm * held))
            return flat.reshape((lm, held) + shape)

        blocks = dict(
            attn(keys[1], lm),
            router=dense(keys[8], (lm, dm, e), dm),
            moe_gate=experts(keys[9], (dm, fe), dm),
            moe_up=experts(keys[10], (dm, fe), dm),
            moe_down=experts(keys[11], (fe, dm), fe),
        )
        if cfg.router_bias:
            blocks["router_bias"] = ROUTER_BIAS_STD * jax.random.normal(
                keys[12], (lm, e), jnp.float32)
        if cfg.n_shared_experts:
            fs = cfg.n_shared_experts * fe
            blocks.update(
                shared_gate=dense(keys[13], (lm, dm, fs), dm),
                shared_up=dense(keys[14], (lm, dm, fs), dm),
                shared_down=dense(keys[15], (lm, fs, dm), fs),
            )
        params["blocks"] = blocks
    return params


def init_kv_cache(cfg: ModelConfig, num_slots: int, max_seq: int,
                  dtype=jnp.bfloat16, quant=False):
    """The latent planes ``{"c": [L, rows, S, C], "kr": [ceil(L/2), rows, S,
    2 Dr]}``; under ``quant`` (``"int8"``) int8 values and ``c_scale`` [L,
    rows, S, 2] float32.  (With an odd ``L`` the last ``kr`` row is half
    used.)"""
    l, dr = cfg.n_layers, cfg.qk_rope_head_dim
    c = (l, num_slots, max_seq, cfg.kv_lora_rank)
    kr = ((l + 1) // 2, num_slots, max_seq, 2 * dr)
    if quant in (False, None, "none", ""):
        return {"c": jnp.zeros(c, dtype), "kr": jnp.zeros(kr, dtype)}
    if quant in (True, "int8"):
        return {"c": jnp.zeros(c, jnp.int8), "kr": jnp.zeros(kr, jnp.int8),
                "c_scale": jnp.zeros(c[:-1] + (2,), jnp.float32)}
    raise ValueError(f"the latent planes have no KV quant mode {quant!r} "
                     "(none | int8)")


def _quant_rows(latent: jnp.ndarray, k_r: jnp.ndarray):
    """-> (int8 latent, int8 rope key, [..., 2] scales: latent, rope key)."""
    cq, cs = _quantize_act(latent)
    rq, rs = _quantize_act(k_r)
    return cq, rq, jnp.concatenate([cs, rs], axis=-1)


def _dequant(q: jnp.ndarray, scale: jnp.ndarray, dtype) -> jnp.ndarray:
    return (q.astype(jnp.float32) * scale[..., None]).astype(dtype)


def _pair_layers(k_r: jnp.ndarray) -> jnp.ndarray:
    """Rope keys of all layers [L, ..., Dr] -> the ``kr`` planes' rows
    [ceil(L/2), ..., 2 Dr]: layers 2i and 2i + 1 side by side."""
    if k_r.shape[0] % 2:
        k_r = jnp.concatenate([k_r, jnp.zeros_like(k_r[:1])], axis=0)
    two = k_r.reshape((k_r.shape[0] // 2, 2) + k_r.shape[1:])
    return jnp.concatenate([two[:, 0], two[:, 1]], axis=-1)


def _my_half(pair: jnp.ndarray, idx) -> jnp.ndarray:
    """Layer ``idx``'s rope keys [..., Dr] of a ``kr`` row [..., 2 Dr]."""
    dr = pair.shape[-1] // 2
    return jnp.where(idx % 2 == 0, pair[..., :dr], pair[..., dr:])


# ---------------------------------------------------------------------------
# shared layer pieces
# ---------------------------------------------------------------------------

def _rope(cfg: ModelConfig, x, positions):
    if cfg.yarn is None:
        return apply_rope(x, positions, cfg.rope_theta)
    y = cfg.yarn
    return apply_rope(
        x, positions, cfg.rope_theta,
        inv_freq=yarn_inv_freq(cfg.qk_rope_head_dim, cfg.rope_theta, y),
        mscale=(yarn_mscale(y.factor, y.mscale)
                / yarn_mscale(y.factor, y.mscale_all_dim)),
    )


def _score_scale(cfg: ModelConfig) -> float:
    if cfg.query_scale is not None:
        return cfg.query_scale
    return yarn_score_scale(cfg.q_head_dim, cfg.yarn)


def _attn_inputs(cfg: ModelConfig, blk, h, positions):
    """h [B,T,Dm] -> q_n [B,T,H,Dn], q_r [B,T,H,Dr] (roped) and what the
    token caches: the normed latent [B,T,C] and the roped shared key
    [B,T,Dr]."""
    b, t, _ = h.shape
    c, dn = cfg.kv_lora_rank, cfg.qk_nope_head_dim
    aq = cfg.act_quant
    q = mm(h, blk["wq"], aq).reshape(b, t, cfg.n_heads, cfg.q_head_dim)
    if cfg.qk_norm:
        q = rms_norm(q, blk["q_norm"], cfg.norm_eps)
    q_n, q_r = q[..., :dn], _rope(cfg, q[..., dn:], positions)
    ckr = mm(h, blk["wkva"], aq)
    latent = rms_norm(ckr[..., :c], blk["kv_norm"], cfg.norm_eps)
    k_r = _rope(cfg, ckr[..., None, c:], positions)[..., 0, :]
    return q_n, q_r, latent, k_r


def _wkvb(cfg: ModelConfig, blk):
    """W_kvb as [C, H, Dn + Dv]."""
    return blk["wkvb"].reshape(cfg.kv_lora_rank, cfg.n_heads,
                               cfg.qk_nope_head_dim + cfg.v_head_dim)


def _attend_decompressed(cfg, blk, q_n, q_r, latent, k_r, mask):
    """Prefill form over cached rows ``latent [B,S,C]``, ``k_r [B,S,Dr]``;
    returns [B,T,H*Dv]."""
    b, s, _ = latent.shape
    dn = cfg.qk_nope_head_dim
    with jax.named_scope("mla_up"):
        kv = mm(latent, blk["wkvb"], cfg.act_quant).reshape(
            b, s, cfg.n_heads, dn + cfg.v_head_dim)
    with jax.named_scope("attn"):
        out = latent_attention_decompressed(
            q_n, q_r, kv[..., :dn], k_r, kv[..., dn:], mask,
            _score_scale(cfg))
        return out.reshape(b, q_n.shape[1], -1)


def _attend_absorbed(cfg, blk, q_n, q_r, latent, k_r, mask):
    """Decode form over the cached rows; returns [B,T,H*Dv]."""
    dn = cfg.qk_nope_head_dim
    w = _wkvb(cfg, blk)
    q_c = jnp.einsum("bthd,chd->bthc", q_n, w[..., :dn],
                     preferred_element_type=jnp.float32).astype(q_n.dtype)
    ctx = latent_attention_absorbed(q_c, q_r, latent, k_r, mask,
                                    _score_scale(cfg))
    out = jnp.einsum("bthc,chd->bthd", ctx, w[..., dn:],
                     preferred_element_type=jnp.float32).astype(q_n.dtype)
    return out.reshape(out.shape[0], out.shape[1], -1)


#: The expert leaves: kept out of the layer scan's sliced operands and read
#: whole by the grouped products (``moe_mlp``'s ``stacked``).
EXPERT_LEAVES = ("moe_gate", "moe_up", "moe_down")


def _ffn(cfg: ModelConfig, kind: str, blk, h32, counted, stacked=None,
         layer=None):
    """The layer's feed-forward of the normed stream ``h32`` (float32) ->
    (out, stats or None)."""
    from p2p_llm_tunnel_tpu.models.transformer import _act

    h = h32.astype(blk["wq"].dtype)
    if kind == "moe":
        return moe_mlp(cfg, blk, h, lambda x: _act(cfg, x), counted,
                       stacked=stacked, layer=layer, router_in=h32)
    aq = cfg.act_quant
    gate = _act(cfg, mm(h, blk["w_gate"], aq)) * mm(h, blk["w_up"], aq)
    return mm(gate, blk["w_down"], aq), None


def _groups(cfg: ModelConfig, params):
    """[(kind, stacked blocks, index of the group's first layer)]."""
    kinds = cfg.layer_kinds
    ld = kinds.count("dense")
    out = []
    if ld:
        out.append(("dense", params["dense_blocks"], 0))
    if len(kinds) > ld:
        out.append(("moe", params["blocks"], ld))
    return out


def _scan_groups(cfg, params, layer, carry):
    """Run ``layer(kind, carry, blk, idx, ffn) -> (carry, ys, stats)`` over
    both groups, ``ffn(h, counted)`` the layer's feed-forward; returns
    (carry, ys of all layers concatenated, stats summed).  The expert
    leaves are no sliced operand of the scan: each layer's routed layer
    reads its experts in the whole stack."""
    all_ys, total = [], jnp.zeros((STATS,), jnp.int32)
    for kind, blocks, first in _groups(cfg, params):
        n = jax.tree_util.tree_leaves(blocks)[0].shape[0]
        stacked = None
        if kind == "moe":
            stacked = {k: blocks[k].reshape((-1,) + blocks[k].shape[2:])
                       for k in EXPERT_LEAVES}
            blocks = {k: v for k, v in blocks.items()
                      if k not in EXPERT_LEAVES}

        def step(carry, xs, kind=kind, stacked=stacked, first=first):
            blk, idx = xs

            def ffn(h, counted):
                return _ffn(cfg, kind, blk, h, counted, stacked, idx - first)

            carry, ys, stats = layer(kind, carry, blk, idx, ffn)
            return carry, (ys, stats)

        carry, (ys, stats) = jax.lax.scan(
            step, carry, (blocks, first + jnp.arange(n)))
        all_ys.append(ys)
        if stats is not None:
            total = total + stats.sum(axis=0)
    ys = jax.tree.map(lambda *a: jnp.concatenate(a, axis=0), *all_ys)
    return carry, ys, total


def _head(cfg, params, x):
    from p2p_llm_tunnel_tpu.models.transformer import _logits, _norm

    with jax.named_scope("head_sample"):
        h = _norm(cfg, x, params["final_norm"]).astype(params["embed"].dtype)
        return _logits(cfg, params, h)


def _embed(cfg, params, tokens):
    """The residual stream's first value, float32."""
    from p2p_llm_tunnel_tpu.models.transformer import _embed as embed

    return embed(cfg, params, tokens).astype(jnp.float32)


def _normed(cfg, x, weight, blk=None):
    """RMS norm of the stream; with ``blk`` rounded to its weights' type, as
    a product takes it."""
    h = rms_norm(x, weight, cfg.norm_eps)
    return h if blk is None else h.astype(blk["wq"].dtype)


def _counted(valid, stat_rows):
    return valid if stat_rows is None else valid & stat_rows[:, None]


# ---------------------------------------------------------------------------
# the three serving programs (+ the whole-prompt forward)
# ---------------------------------------------------------------------------

def prefill(cfg: ModelConfig, params, tokens, valid, counted=None):
    """Whole-prompt forward: (logits [B,T,V], rows [L,B,T,C+Dr] = each
    layer's [latent | rope key], stats of the ``counted`` tokens, the valid
    ones by default)."""
    b, t = tokens.shape
    positions = jnp.broadcast_to(jnp.arange(t), (b, t))
    causal = jnp.arange(t)[None, :] <= jnp.arange(t)[:, None]
    mask = causal[None] & valid[:, None, :]
    if counted is None:
        counted = valid

    def layer(kind, x, blk, idx, ffn):
        with jax.named_scope("attn"):
            h = _normed(cfg, x, blk["attn_norm"], blk)
            q_n, q_r, latent, k_r = _attn_inputs(cfg, blk, h, positions)
        a = _attend_decompressed(cfg, blk, q_n, q_r, latent, k_r, mask)
        with jax.named_scope("attn"):
            x = x + mm(a, blk["wo"], cfg.act_quant)
        with jax.named_scope("ffn"):
            h = _normed(cfg, x, blk["mlp_norm"])
            out, stats = ffn(h, counted)
            return x + out, jnp.concatenate([latent, k_r], axis=-1), stats

    x, rows, stats = _scan_groups(cfg, params, layer,
                                  _embed(cfg, params, tokens))
    return _head(cfg, params, x), rows, stats


def _write_tails(cfg, kv_cache, latent, k_r, scale, at):
    """All layers' rows (``latent [L, ..., C]``, ``k_r [L, ..., Dr]``, under
    int8 planes already quantised, with ``scale [L, ..., 2]``) into the
    planes at index ``at`` (after the layer axis).  One write a layer and
    plane, each of whole rows: a single write with the layer axis in its
    window makes the chip's compiler turn the plane layers-innermost for it
    and back (tests/test_tpu_compile.py)."""
    out = dict(kv_cache)
    with jax.named_scope("kv_write"):
        for name, rows in (("c", latent), ("kr", _pair_layers(k_r)),
                           ("c_scale", scale)):
            if rows is None:
                continue
            plane = kv_cache[name]
            for i in range(rows.shape[0]):
                plane = plane.at[(i,) + at].set(rows[i])
            out[name] = plane
    return out


def prefill_into_cache(cfg, params, tokens, lengths, kv_cache, slots,
                       return_prompt_logprobs=False, stat_rows=None):
    """``transformer.prefill_into_cache`` for the latent planes; returns
    (last logits, cache[, prompt log-probs], stats)."""
    b, t = tokens.shape
    valid = jnp.arange(t)[None, :] < lengths[:, None]
    logits, rows, stats = prefill(cfg, params, tokens, valid,
                                  _counted(valid, stat_rows))
    last = jnp.take_along_axis(
        logits, (lengths - 1)[:, None, None], axis=1)[:, 0]
    rows = rows[:, :, : kv_cache["c"].shape[2]]
    c = cfg.kv_lora_rank
    latent, k_r, scale = rows[..., :c], rows[..., c:], None
    if "c_scale" in kv_cache:
        latent, k_r, scale = _quant_rows(latent, k_r)
    out = _write_tails(cfg, kv_cache, latent, k_r, scale,
                       (slots, slice(0, rows.shape[2])))
    if not return_prompt_logprobs:
        return last, out, stats
    lsm = jax.nn.log_softmax(logits[:, :-1], axis=-1)
    scored = jnp.take_along_axis(lsm, tokens[:, 1:, None], axis=-1)[..., 0]
    prompt_lps = jnp.concatenate(
        [jnp.zeros((b, 1), jnp.float32), scored.astype(jnp.float32)], axis=1)
    return last, out, prompt_lps, stats


def chunk_prefill_into_cache(cfg, params, tokens, lengths, starts, kv_cache,
                             slots, kv_view: Optional[int] = None,
                             return_all_logits: bool = False,
                             stat_rows=None):
    """``transformer.chunk_prefill_into_cache`` for the latent planes: the
    tail of each prompt against the history rows its slot already holds,
    decompressed.  Returns (logits, cache, stats)."""
    from p2p_llm_tunnel_tpu.models.transformer import (
        lay_tail,
        read_cache_view,
        tail_placement,
    )

    b, t = tokens.shape
    s = kv_cache["c"].shape[2]
    if kv_view is None or kv_view > s:
        kv_view = s
    quant = "c_scale" in kv_cache
    pos = starts[:, None] + jnp.arange(t)[None, :]
    place, fresh = tail_placement(kv_view, starts, t)
    mask = jnp.arange(kv_view)[None, None, :] <= pos[:, :, None]  # [Bp,T,view]
    valid = jnp.arange(t)[None, :] < lengths[:, None]
    counted = _counted(valid, stat_rows)

    def seen(plane, idx, tail):
        """The view's rows of the dispatch's slots with the tail laid over
        its positions: what the plane will hold."""
        return lay_tail(read_cache_view(kv_cache[plane], idx, kv_view, slots),
                        tail, place, fresh)

    def layer(kind, x, blk, idx, ffn):
        with jax.named_scope("attn"):
            h = _normed(cfg, x, blk["attn_norm"], blk)
            q_n, q_r, latent, k_r = _attn_inputs(cfg, blk, h, pos)
        scale = None
        with jax.named_scope("kv_write"):
            if quant:  # the tail in the form the planes will hold it
                latent, k_r, scale = _quant_rows(latent, k_r)
        with jax.named_scope("kv_read"):
            # (the pair's other half rides along under this layer's tail
            # and is dropped again)
            both = jnp.concatenate([k_r, k_r], axis=-1)
            c_all = seen("c", idx, latent)
            k_all = _my_half(seen("kr", idx // 2, both), idx)
            if quant:
                scales = seen("c_scale", idx, scale)
                c_all = _dequant(c_all, scales[..., 0], x.dtype)
                k_all = _dequant(k_all, scales[..., 1], x.dtype)
        a = _attend_decompressed(cfg, blk, q_n, q_r, c_all, k_all, mask)
        with jax.named_scope("attn"):
            x = x + mm(a, blk["wo"], cfg.act_quant)
        with jax.named_scope("ffn"):
            h = _normed(cfg, x, blk["mlp_norm"])
            out, stats = ffn(h, counted)
            return x + out, (latent, k_r, scale), stats

    x, (latents, k_rs, scales), stats = _scan_groups(
        cfg, params, layer, _embed(cfg, params, tokens))
    new_cache = _write_tails(cfg, kv_cache, latents, k_rs, scales,
                             (slots[:, None], pos))
    logits = _head(cfg, params, x)
    if return_all_logits:
        return logits, new_cache, stats
    last = jnp.take_along_axis(
        logits, (lengths - 1)[:, None, None], axis=1)[:, 0]
    return last, new_cache, stats


def decode_step(cfg, params, kv_cache, tokens, positions,
                kv_view: Optional[int] = None, mesh=None):
    """``transformer.decode_step`` for the latent planes, absorbed form
    (``mesh`` is the families' signature: nothing here is gated by it); the
    planes are the carry of both layer scans and take one in-place row write
    a layer each (the ``kr`` row read first: the layer's pair-mate owns its
    other half).  Rows parked at ``positions >= S`` write nothing (the
    gather clamps, the scatter drops) and count for nothing.  Returns
    (logits [B,V], cache, stats)."""
    b = tokens.shape[0]
    s = kv_cache["c"].shape[2]
    if kv_view is None or kv_view > s:
        kv_view = s
    quant = "c_scale" in kv_cache
    pos2d = positions[:, None]
    slot_ids = jnp.arange(b)
    mask = (jnp.arange(kv_view)[None, :] <= pos2d)[:, None, :]  # [B,1,view]
    counted = pos2d < s
    dr = cfg.qk_rope_head_dim
    upper = jnp.arange(2 * dr) >= dr

    def view_of(plane, idx):
        zero = jnp.zeros((), idx.dtype)
        return jax.lax.dynamic_slice(
            plane, (idx, zero, zero, zero),
            (1, b, kv_view, plane.shape[-1]))[0]

    def layer(kind, carry, blk, idx, ffn):
        x, cache = carry
        with jax.named_scope("attn"):
            h = _normed(cfg, x, blk["attn_norm"], blk)
            q_n, q_r, latent, k_r = _attn_inputs(cfg, blk, h, pos2d)
        cache = dict(cache)
        at, pair_at = (idx, slot_ids, positions), (idx // 2, slot_ids, positions)
        with jax.named_scope("kv_write"):
            latent, k_r = latent[:, 0], k_r[:, 0]
            if quant:
                latent, k_r, scale = _quant_rows(latent, k_r)
                cache["c_scale"] = cache["c_scale"].at[at].set(scale)
            cache["c"] = cache["c"].at[at].set(latent)
            mine = upper == (idx % 2 == 1)
            cache["kr"] = cache["kr"].at[pair_at].set(jnp.where(
                mine, jnp.concatenate([k_r, k_r], axis=-1),
                cache["kr"][pair_at]))
        with jax.named_scope("kv_read"):
            c_all = view_of(cache["c"], idx)
            k_all = _my_half(view_of(cache["kr"], idx // 2), idx)
            if quant:
                scales = view_of(cache["c_scale"], idx)
                c_all = _dequant(c_all, scales[..., 0], x.dtype)
                k_all = _dequant(k_all, scales[..., 1], x.dtype)
        with jax.named_scope("attn"):
            a = _attend_absorbed(cfg, blk, q_n, q_r, c_all, k_all, mask)
            x = x + mm(a, blk["wo"], cfg.act_quant)
        with jax.named_scope("ffn"):
            h = _normed(cfg, x, blk["mlp_norm"])
            out, stats = ffn(h, counted)
            return (x + out, cache), None, stats

    (x, new_cache), _, stats = _scan_groups(
        cfg, params, layer,
        (_embed(cfg, params, tokens[:, None]), dict(kv_cache)))
    return _head(cfg, params, x)[:, 0], new_cache, stats
