"""Window and full attention layers mixed by a pattern, over routed experts:
``MiMo-V2-Flash``, ``Laguna-S-2.1``.

``models/transformer.py`` hands its entry points here when
``cfg.attn_pattern`` is set, so the engine, the prefix pool and the tunnel
run this family through the calls they make for every other.

Layers.  A layer is one of two attention kinds (``cfg.attn_kinds``) over one
of two feed-forwards (``cfg.layer_kinds``), and the kinds differ in shape,
so no one ``[L, ...]`` stack holds them: each kind's weights are stacked by
themselves (``attn_full``, ``attn_window``, ``dense_ffn``, ``blocks`` = the
routed layers) and an index map (:func:`layer_runs`) names, for each run of
consecutive layers of one (attention, feed-forward) pair, where its layers
lie in their stacks.  A run is one ``lax.scan`` over those indices; the
stacks are closed over and each layer takes its slice inside the loop, the
routed layers reading the stacked experts where they lie
(``moe_mlp(stacked=...)``).

Attention of kind ``k``, ``h`` the normed input (H query heads, ``K_k`` KV
heads, keys ``Dk`` and values ``Dv`` wide):

- ``q = W_q h`` [H, Dk], ``k = W_k h`` [K_k, Dk], ``v = value_scale * (W_v
  h)`` [K_k, Dv]; the leading ``rotary_dim`` columns of every query and key
  head are roped (rotate-half), theta ``rope_theta`` in full layers and
  ``window_rope_theta`` in window layers; the other columns pass.
- scores ``q . k / sqrt(Dk)``, causal; a window layer sees the last
  ``sliding_window`` positions only, and its softmax's denominator takes one
  more term, a learned logit a head (``sink``), that carries no value.

**The cache is two sets of planes** under one allocator (one dict, one
prefix pool, one set of slots).  Full layers: ``"k" [Lf, rows, S, Kf * Dk]``
and ``"v" [Lf, rows, S, Kf * Dv]``.  Window layers: **rings** of ``R``
positions a slot, ``"wk" [Lw, rows, R, Kw * Dk]`` and ``"wv" [Lw, rows, R,
Kw * Dv]``: position ``p`` lies at ``p % R``, a read names each ring slot by
the position it holds (``ops.attention.ring_positions``) and masks by
position.  ``R`` (``cfg.ring_default``) is the window and the widest
chunk-prefill segment in whole 128s, so a slot's window layers hold ``R``
positions where a uniform cache held ``max_seq``.  A plane's row is a
token's KV heads side by side (768 and 512 values in a full layer, 1,536 and
1,024 in a window layer): whole lane tiles with the sequence on the
sublanes, the form PR 28 found the chip's compiler leaves as it is around a
row write, where a head of 192 values is a tile and a half
(tests/test_tpu_compile.py holds the four planes to their stated bytes and
to no conversion around a write).

A pooled token (``engine/prefix_cache``) holds its keys and values of every
layer, window layers too: ``sum over layers of K_k * (Dk + Dv)`` values.  A
prefix hit restores full layers whole and, of a window layer, the last ``R``
positions of the prefix into the ring.

The residual stream is float32 and every product takes it rounded to the
parameters' type; the router scores the normed stream before that rounding
(``models/mla.py``'s reasons hold here: a routed layer compares scores).
Every program also returns what its routed layers counted (``moe.STATS``).

Decode reads by what ``transformer.decode_attention_branch`` answers: on the
TPU, over plain bf16 planes whose rows are whole lane tiles,
``decode_attention_rows`` takes the stacked planes where they lie, a full
layer's row up to its own position (ISSUE 36), a window layer's ring by the
blocks that hold its window (ISSUE 56, ``ring_kernel_decline``): no layer
sliced out, no view.  Elsewhere the einsum over a full layer's ``kv_view``
positions and a ring whole; chunk prefill keeps the einsum and its views.

Int8 planes (``--kv-quant int8``) keep int8 values with one float32 scale a
token, layer and KV head beside each plane (``"k_scale" [Lf, rows, S, Kf]``
and so on): the benchmark's cache control, which no cell serves.
"""

from __future__ import annotations

from typing import List, NamedTuple, Optional, Tuple

import jax
import jax.numpy as jnp

from p2p_llm_tunnel_tpu.models.config import ModelConfig
from p2p_llm_tunnel_tpu.models.mla import (
    EXPERT_LEAVES,
    ROUTER_BIAS_STD,
    _counted,
    _embed,
    _head,
)
from p2p_llm_tunnel_tpu.models.moe import STATS, moe_mlp
from p2p_llm_tunnel_tpu.models.quant import _quantize_act, mm
from p2p_llm_tunnel_tpu.ops.attention import (
    masked_attention,
    ring_positions,
    window_mask,
)
from p2p_llm_tunnel_tpu.ops.norms import rms_norm
from p2p_llm_tunnel_tpu.ops.rope import apply_rope, yarn_inv_freq

# (the selection bias's spread as drawn, the expert leaves kept out of a
# scan's sliced operands, the float32 stream's first value and the head are
# models/mla.py's, for its reasons)

#: Where a kind's weights and cache planes are found.
ATTN_GROUP = {"full": "attn_full", "window": "attn_window"}
PLANES = {"full": ("k", "v"), "window": ("wk", "wv")}
#: The cache's ring leaves (engine/prefix_cache copies them by ``p % R``).
RING_KEYS = frozenset(
    name + tail for name in PLANES["window"] for tail in ("", "_scale"))


class Run(NamedTuple):
    """Consecutive layers of one (attention, feed-forward) pair: the first
    one's place in the model, in its attention stack and in its feed-forward
    stack, and how many they are."""
    attn: str
    ffn: str
    first: int
    attn_first: int
    ffn_first: int
    n: int


def layer_runs(cfg: ModelConfig) -> List[Run]:
    """The index map: the model's layers as runs, in order."""
    runs: List[Run] = []
    seen = {"full": 0, "window": 0, "dense": 0, "moe": 0}
    for l, (a, f) in enumerate(zip(cfg.attn_kinds, cfg.layer_kinds)):
        if runs and (runs[-1].attn, runs[-1].ffn) == (a, f):
            runs[-1] = runs[-1]._replace(n=runs[-1].n + 1)
        else:
            runs.append(Run(a, f, l, seen[a], seen[f], 1))
        seen[a] += 1
        seen[f] += 1
    return runs


def kind_counts(cfg: ModelConfig) -> Tuple[int, int]:
    """(full layers, window layers)."""
    kinds = cfg.attn_kinds
    return kinds.count("full"), kinds.count("window")


# ---------------------------------------------------------------------------
# init
# ---------------------------------------------------------------------------

def init_params(cfg: ModelConfig, key: jax.Array, dtype=jnp.bfloat16):
    """Random init; an expert is drawn from its own key by its PUBLISHED
    index, one at a time (``models/mla.init_params``'s scheme: a share's
    experts are the whole model's of the same seed)."""
    dm, v = cfg.dim, cfg.vocab_size
    dk, dv = cfg.head_dim, cfg.v_head_dim
    lf, lw = kind_counts(cfg)
    ld = cfg.layer_kinds.count("dense")
    lm = cfg.n_layers - ld
    keys = jax.random.split(key, 16)

    def dense(k, shape, fan_in):
        return (jax.random.truncated_normal(k, -2, 2, shape, jnp.float32)
                * (fan_in ** -0.5)).astype(dtype)

    def attn(k, n, kind):
        ks, h, kv = jax.random.split(k, 4), *_heads(cfg, kind)
        return {
            "attn_norm": jnp.ones((n, dm), dtype),
            "wq": dense(ks[0], (n, dm, h * dk), dm),
            "wk": dense(ks[1], (n, dm, kv * dk), dm),
            "wv": dense(ks[2], (n, dm, kv * dv), dm),
            "wo": dense(ks[3], (n, h * dv, dm), h * dv),
            **_attn_extras(cfg, k, n, h, dense, dtype)}

    params = {
        "embed": dense(keys[7], (v, dm), dm),
        "final_norm": jnp.ones((dm,), dtype),
        "lm_head": dense(jax.random.fold_in(key, 99), (dm, v), dm),
    }
    if lf:
        params["attn_full"] = attn(keys[0], lf, "full")
    if lw:
        params["attn_window"] = attn(keys[1], lw, "window")
        if cfg.window_sink:
            # a logit among scores of about unit spread: drawn so that it
            # takes a real share of a head's weight
            params["attn_window"]["sink"] = jax.random.normal(
                keys[2], (lw, cfg.heads_of("window")), jnp.float32)
    if ld:
        f = cfg.ffn_dim
        params["dense_ffn"] = {
            "mlp_norm": jnp.ones((ld, dm), dtype),
            "w_gate": dense(keys[4], (ld, dm, f), dm),
            "w_up": dense(keys[5], (ld, dm, f), dm),
            "w_down": dense(keys[6], (ld, f, dm), f),
        }
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

        blocks = {
            "mlp_norm": jnp.ones((lm, dm), dtype),
            "router": dense(keys[8], (lm, dm, e), dm),
            "moe_gate": experts(keys[9], (dm, fe), dm),
            "moe_up": experts(keys[10], (dm, fe), dm),
            "moe_down": experts(keys[11], (fe, dm), fe),
        }
        if cfg.router_bias:
            blocks["router_bias"] = ROUTER_BIAS_STD * jax.random.normal(
                keys[12], (lm, e), jnp.float32)
        params["blocks"] = dict(blocks, **_shared_leaves(cfg, keys, lm, dense))
    return params


def init_kv_cache(cfg: ModelConfig, num_slots: int, max_seq: int,
                  dtype=jnp.bfloat16, quant=False):
    """``{"k", "v"}`` of the full layers at ``max_seq`` positions a row and
    ``{"wk", "wv"}`` of the window layers at ``cfg.ring_default(max_seq)``;
    a row of a plane is a token's KV heads side by side."""
    if quant in (True, "int8"):
        dtype = jnp.int8
    elif quant not in (False, None, "none", ""):
        raise ValueError(f"the window and full planes have no KV quant mode "
                         f"{quant!r} (none | int8)")
    widths = (cfg.head_dim, cfg.v_head_dim)
    out = {}
    for kind, layers in zip(("full", "window"), kind_counts(cfg)):
        kv = cfg.kv_heads_of(kind)
        at = max_seq if kind == "full" else cfg.ring_default(max_seq)
        for name, width in zip(PLANES[kind], widths):
            out[name] = jnp.zeros((layers, num_slots, at, kv * width), dtype)
            if dtype == jnp.int8:
                out[name + "_scale"] = jnp.zeros(
                    (layers, num_slots, at, kv), jnp.float32)
    return out


def cache_section(cfg: ModelConfig, kv_cache) -> dict:
    """What /healthz ``config.model.cache`` says of the two kinds of plane:
    per kind its layers, KV heads, widths and positions a slot, and the
    bytes a pooled token and a slot take."""
    kinds, token, slot = {}, 0, 0
    for kind, (kp, vp) in PLANES.items():
        layers, positions = kv_cache[kp].shape[0], kv_cache[kp].shape[2]
        per_token = sum(
            a.shape[3] * a.dtype.itemsize for name, a in kv_cache.items()
            if name.split("_")[0] in (kp, vp))
        kinds[kind] = {
            "layers": layers, "kv_heads": cfg.kv_heads_of(kind),
            "key_width": cfg.head_dim, "value_width": cfg.v_head_dim,
            "positions_per_slot": positions,
            "bytes_per_token_layer": per_token,
        }
        token += layers * per_token
        slot += layers * per_token * positions
    return {"form": "window_rings+full", "kinds": kinds,
            "ring_positions": kv_cache["wk"].shape[2],
            "window": cfg.sliding_window,
            "bytes_per_token": token, "bytes_per_slot": slot}


# ---------------------------------------------------------------------------
# shared layer pieces
# ---------------------------------------------------------------------------

# What Laguna-S-2.1 adds to the family (``_rope``, ``_attn_inputs``,
# ``_attn_out`` and the draws' helpers stand further down, after the pieces
# that ``models/ssm_moe.py`` shares with this module: those keep their lines,
# see the note there):
#
# - query heads by layer kind, ``H_k = cfg.heads_of(kind)``: ``W_q``, ``W_o``,
#   ``W_g`` and the scores' reshape go by kind;
# - under ``cfg.qk_norm`` every query and key head is RMS-normed over its
#   columns before the rope;
# - the leading ``cfg.rotary_of(kind)`` columns of a head are roped; under
#   ``cfg.yarn`` a FULL layer's turn at yarn's frequencies over that width,
#   sin and cos times its ``attention_factor``; a window layer ropes
#   plainly at ``window_rope_theta``;
# - under ``cfg.attn_gate`` ``g = sigmoid(W_g h)`` [H_k], one number a token
#   and query head, multiplies that head's weighted sum before ``W_o``
#   (scope ``attn_gate`` inside the kind's);
# - a routed layer is ``models/moe.py``'s whole: the shared expert and the
#   scaling factor where the configuration has them.
#
# ``W_g`` is drawn ``GATE_STD`` times the standard draw: gate logits of
# spread about 1.8 over the normed stream, so that a fifth of the gates lie
# under 0.2 or over 0.8 and a head's gate is no constant near one half.
#
# THE LINES OF ``_pack`` ... ``_as_held`` BELOW ARE THE PARENT'S (PR 55): the
# chip compiler's broadcast rewriter stands at the edge of its stack in
# granite-4.0-h-micro's cold start (ROADMAP Speed 6), and that model's
# programs trace through these functions.

def _pack(rows, heads: int, quant: bool):
    """Rows ``[..., heads * D]`` as the planes hold them -> (rows, scales
    ``[..., heads]`` or None): int8 with one scale a head under ``quant``."""
    if not quant:
        return rows, None
    q, scale = _quantize_act(rows.reshape(rows.shape[:-1] + (heads, -1)))
    return q.reshape(rows.shape), scale[..., 0]


def _unpack(rows, scale, dtype):
    if scale is None:
        return rows
    x = rows.reshape(rows.shape[:-1] + (scale.shape[-1], -1))
    x = x.astype(jnp.float32) * scale[..., None]
    return x.astype(dtype).reshape(rows.shape)


def _attend(cfg: ModelConfig, kind: str, blk, q, k_rows, v_rows, mask):
    """q [B,T,H,Dk] over cached rows ``k_rows [B,S,K*Dk]``, ``v_rows
    [B,S,K*Dv]`` -> [B,T,H*Dv]."""
    b, s, _ = k_rows.shape
    kv = cfg.kv_heads_of(kind)
    # (under ``attn`` too: a reader that knows no kinds still finds it there)
    with jax.named_scope("attn"), jax.named_scope(
            "attn_window" if kind == "window" else "attn_full"):
        out = masked_attention(
            q, k_rows.reshape(b, s, kv, cfg.head_dim),
            v_rows.reshape(b, s, kv, cfg.v_head_dim), mask,
            cfg.query_scale or cfg.head_dim ** -0.5,
            sink=blk.get("sink") if kind == "window" else None)
        return out.reshape(b, q.shape[1], -1)


def _ffn(cfg: ModelConfig, run: Run, blk, h32, counted, stacked, layer, dtype):
    """The layer's feed-forward of the normed stream ``h32`` (float32) ->
    (out, stats or None)."""
    from p2p_llm_tunnel_tpu.models.transformer import _act

    h = h32.astype(dtype)
    if run.ffn == "moe":
        return moe_mlp(cfg, blk, h, lambda x: _act(cfg, x), counted,
                       stacked=stacked, layer=layer, router_in=h32)
    aq = cfg.act_quant
    gate = _act(cfg, mm(h, blk["w_gate"], aq)) * mm(h, blk["w_up"], aq)
    return mm(gate, blk["w_down"], aq), None


def _slice(stack, i):
    return {k: jax.lax.dynamic_index_in_dim(a, i, 0, keepdims=False)
            for k, a in stack.items()}


def _scan_runs(cfg, params, layer, carry):
    """Run ``layer(run, carry, attn_blk, ai, ffn) -> (carry, ys, stats)`` over
    every run in order: ``ai`` the layer's index in its attention stack (and
    so in its kind's cache planes), ``ffn(h, counted)`` its feed-forward.
    Returns (carry, {kind: ys of that kind's layers, in stack order}, stats
    summed)."""
    ys_of = {"full": [], "window": []}
    total = jnp.zeros((STATS,), jnp.int32)
    dtype = params["embed"].dtype
    stacked = None
    if "blocks" in params:
        stacked = {k: params["blocks"][k].reshape(
            (-1,) + params["blocks"][k].shape[2:]) for k in EXPERT_LEAVES}
    for run in layer_runs(cfg):
        attn_stack = params[ATTN_GROUP[run.attn]]
        ffn_stack = params["blocks" if run.ffn == "moe" else "dense_ffn"]
        ffn_stack = {k: v for k, v in ffn_stack.items()
                     if k not in EXPERT_LEAVES}

        def step(carry, j, run=run, attn_stack=attn_stack,
                 ffn_stack=ffn_stack):
            ai, fi = run.attn_first + j, run.ffn_first + j
            ffn_blk = _slice(ffn_stack, fi)

            def ffn(x, counted):
                h = rms_norm(x, ffn_blk["mlp_norm"], cfg.norm_eps)
                return _ffn(cfg, run, ffn_blk, h, counted, stacked, fi, dtype)

            carry, ys, stats = layer(run, carry, _slice(attn_stack, ai), ai,
                                     ffn)
            return carry, (ys, stats)

        carry, (ys, stats) = jax.lax.scan(step, carry, jnp.arange(run.n))
        ys_of[run.attn].append(ys)
        if stats is not None:
            total = total + stats.sum(axis=0)
    out = {kind: jax.tree.map(lambda *a: jnp.concatenate(a, axis=0), *ys)
           for kind, ys in ys_of.items() if ys}
    return carry, out, total


def _normed(cfg, x, blk, dtype):
    return rms_norm(x, blk["attn_norm"], cfg.norm_eps).astype(dtype)


def _ring_slots(positions, keep, ring: int):
    """Ring slots of ``positions``; ``ring`` itself (past the end: a scatter
    drops it) where ``keep`` is false."""
    return jnp.where(keep, jnp.mod(positions, ring), ring)


def _write(cfg, kv_cache, kind, rows_k, rows_v, slots, positions, keep):
    """All of a kind's layers' rows ``[Lk, Bp, T, width]`` into its planes
    at ``positions [Bp, T]`` of cache rows ``slots [Bp]``; a window layer's
    at ``p % R`` and only where ``keep [Bp, T]``.  One write a layer: with
    the layer axis in a scatter's window the chip's compiler turns the plane
    layers-innermost for it and back."""
    out = dict(kv_cache)
    kp, vp = PLANES[kind]
    at = positions
    if kind == "window":
        at = _ring_slots(positions, keep, kv_cache[kp].shape[2])
    quant = kp + "_scale" in kv_cache
    with jax.named_scope("kv_write"):
        for name, rows in ((kp, rows_k), (vp, rows_v)):
            rows, scale = _pack(rows, cfg.kv_heads_of(kind), quant)
            for leaf, vals in ((name, rows), (name + "_scale", scale)):
                if vals is None:
                    continue
                plane = kv_cache[leaf]
                for i in range(vals.shape[0]):
                    plane = plane.at[i, slots[:, None], at].set(vals[i])
                out[leaf] = plane
    return out


def _as_held(cfg, kind, rows, quant: bool):
    """Fresh rows as the planes will hold them: through int8 and back
    under ``quant``, so that a tail is attended to as later reads see it."""
    if not quant:
        return rows
    return _unpack(*_pack(rows, cfg.kv_heads_of(kind), True), rows.dtype)


# ---------------------------------------------------------------------------
# what goes by layer kind (Laguna-S-2.1): the draws, the rope, the gate
# ---------------------------------------------------------------------------

#: What multiplies the standard draw of ``W_g`` (the note above).
GATE_STD = 2.0


def _heads(cfg: ModelConfig, kind: str) -> Tuple[int, int]:
    """(query heads, KV heads) of a layer of ``kind``."""
    return cfg.heads_of(kind), cfg.kv_heads_of(kind)


def _attn_extras(cfg: ModelConfig, k, n, h, dense, dtype) -> dict:
    """The leaves of ``n`` attention layers of ``h`` query heads that only
    some configurations have: the QK norm's weights (ones) and ``W_g``."""
    out = {}
    if cfg.qk_norm:
        out["q_norm"] = jnp.ones((n, cfg.head_dim), dtype)
        out["k_norm"] = jnp.ones((n, cfg.head_dim), dtype)
    if cfg.attn_gate:
        out["wg"] = GATE_STD * dense(
            jax.random.fold_in(k, 4), (n, cfg.dim, h), cfg.dim)
    return out


def _shared_leaves(cfg: ModelConfig, keys, lm, dense) -> dict:
    """The shared experts of ``lm`` routed layers (``models/mla.py``'s key
    parts), or nothing."""
    if not cfg.n_shared_experts:
        return {}
    dm = cfg.dim
    fs = cfg.n_shared_experts * (cfg.shared_expert_dim or cfg.expert_dim)
    return {"shared_gate": dense(keys[13], (lm, dm, fs), dm),
            "shared_up": dense(keys[14], (lm, dm, fs), dm),
            "shared_down": dense(keys[15], (lm, fs, dm), fs)}


def _rope(cfg: ModelConfig, kind: str, x, positions):
    """The leading ``cfg.rotary_of(kind)`` columns of each head roped; the
    rest pass.  Under ``cfg.yarn`` a full layer's columns turn at yarn's
    frequencies over that width, sin and cos times its stated factor."""
    theta = cfg.window_rope_theta if kind == "window" else cfg.rope_theta
    r = cfg.rotary_of(kind) or x.shape[-1]
    how = {}
    if cfg.yarn is not None and kind == "full":
        how = {"inv_freq": yarn_inv_freq(r, theta, cfg.yarn),
               "mscale": cfg.yarn.attention_factor or 1.0}
    if r == x.shape[-1]:
        return apply_rope(x, positions, theta, **how)
    return jnp.concatenate(
        [apply_rope(x[..., :r], positions, theta, **how), x[..., r:]],
        axis=-1)


def _attn_inputs(cfg: ModelConfig, kind: str, blk, h, positions):
    """h [B,T,Dm] -> q [B,T,H_k,Dk] (roped), what the token caches: its
    roped keys [B,T,K*Dk] and scaled values [B,T,K*Dv], heads side by
    side, and the heads' gates [B,T,H_k] float32 (None without
    ``cfg.attn_gate``)."""
    b, t, _ = h.shape
    kv, dk = cfg.kv_heads_of(kind), cfg.head_dim
    aq = cfg.act_quant

    def heads(w, n, norm):
        x = mm(h, blk[w], aq).reshape(b, t, n, dk)
        if cfg.qk_norm:  # over each head's columns, before the rope
            x = rms_norm(x, blk[norm], cfg.norm_eps)
        return _rope(cfg, kind, x, positions)

    q = heads("wq", cfg.heads_of(kind), "q_norm")
    k = heads("wk", kv, "k_norm")
    v = mm(h, blk["wv"], aq)
    if cfg.value_scale != 1.0:
        v = (v.astype(jnp.float32) * cfg.value_scale).astype(v.dtype)
    gate = None
    if cfg.attn_gate:
        with jax.named_scope(ATTN_GROUP[kind]), jax.named_scope("attn_gate"):
            gate = jax.nn.sigmoid(mm(h, blk["wg"], aq).astype(jnp.float32))
    return q, k.reshape(b, t, kv * dk), v, gate


def _attn_out(cfg: ModelConfig, kind: str, blk, a, gate):
    """The heads' weighted sums ``a [B,T,H_k*Dv]`` -> what the layer adds to
    the stream: under a gate each head's sum times its gate, then ``W_o``."""
    with jax.named_scope("attn"):
        if gate is not None:
            with jax.named_scope(ATTN_GROUP[kind]), jax.named_scope(
                    "attn_gate"):
                heads = a.reshape(gate.shape + (-1,)).astype(jnp.float32)
                a = (heads * gate[..., None]).astype(a.dtype).reshape(a.shape)
        return mm(a, blk["wo"], cfg.act_quant)


# ---------------------------------------------------------------------------
# the three serving programs (+ the whole-prompt forward)
# ---------------------------------------------------------------------------

def prefill(cfg: ModelConfig, params, tokens, valid, counted=None):
    """Whole-prompt forward: (logits [B,T,V], {kind: (keys [Lk,B,T,K*Dk],
    values [Lk,B,T,K*Dv])}, stats of the ``counted`` tokens)."""
    b, t = tokens.shape
    positions = jnp.broadcast_to(jnp.arange(t), (b, t))
    key_pos = jnp.where(valid, positions, -1)
    dtype = params["embed"].dtype
    if counted is None:
        counted = valid

    def layer(run, x, blk, ai, ffn):
        with jax.named_scope("attn"):
            q, k, v, gate = _attn_inputs(
                cfg, run.attn, blk, _normed(cfg, x, blk, dtype), positions)
        mask = window_mask(
            positions, key_pos,
            cfg.sliding_window if run.attn == "window" else None)
        a = _attend(cfg, run.attn, blk, q, k, v, mask)
        x = x + _attn_out(cfg, run.attn, blk, a, gate)
        with jax.named_scope("ffn"):
            out, stats = ffn(x, counted)
            return x + out, (k, v), stats

    x, rows, stats = _scan_runs(cfg, params, layer,
                                _embed(cfg, params, tokens))
    return _head(cfg, params, x), rows, stats


def prefill_into_cache(cfg, params, tokens, lengths, kv_cache, slots,
                       return_prompt_logprobs=False, stat_rows=None):
    """``transformer.prefill_into_cache`` for the two kinds of plane: a full
    layer keeps every position, a ring the prompt's last ``R``.  Returns
    (last logits, cache[, prompt log-probs], stats)."""
    b, t = tokens.shape
    valid = jnp.arange(t)[None, :] < lengths[:, None]
    logits, rows, stats = prefill(cfg, params, tokens, valid,
                                  _counted(valid, stat_rows))
    last = jnp.take_along_axis(
        logits, (lengths - 1)[:, None, None], axis=1)[:, 0]
    positions = jnp.broadcast_to(jnp.arange(t), (b, t))
    out = kv_cache
    for kind, (k, v) in rows.items():
        s = kv_cache[PLANES[kind][0]].shape[2]
        if kind == "full":
            k, v, at = k[:, :, :s], v[:, :, :s], positions[:, :s]
            keep = None
        else:
            at, keep = positions, valid & (positions >= lengths[:, None] - s)
        out = _write(cfg, out, kind, k, v, slots, at, keep)
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
    """``transformer.chunk_prefill_into_cache`` for the two kinds of plane:
    the tail of each prompt against what its slot already holds.  A full
    layer reads its (layer, view) rows and lays the fresh tail over them
    (ISSUE 26's structure); a window layer reads the ``sliding_window``
    positions before ``starts`` out of the ring and sets the tail beside
    them, so its keys are ``window + T`` whatever the context.  The planes
    are no carry of the layer loops: all tails are written once after them,
    a ring taking the last ``R`` real positions of a tail.
    Returns (logits, cache, stats)."""
    from p2p_llm_tunnel_tpu.models.transformer import (
        lay_tail,
        read_cache_view,
        tail_placement,
    )

    b, t = tokens.shape
    s = kv_cache["k"].shape[2]
    ring = kv_cache["wk"].shape[2]
    w = cfg.sliding_window
    if kv_view is None or kv_view > s:
        kv_view = s
    quant = "k_scale" in kv_cache
    dtype = params["embed"].dtype
    pos = starts[:, None] + jnp.arange(t)[None, :]
    valid = jnp.arange(t)[None, :] < lengths[:, None]
    counted = _counted(valid, stat_rows)
    place, fresh = tail_placement(kv_view, starts, t)
    full_mask = window_mask(
        pos, jnp.broadcast_to(jnp.arange(kv_view), (b, kv_view)))
    # the ring's last ``w`` positions before the tail, oldest first
    hist_pos = starts[:, None] - w + jnp.arange(w)[None, :]
    hist_at = jnp.mod(hist_pos, ring)
    win_mask = window_mask(
        pos, jnp.concatenate([hist_pos, jnp.where(valid, pos, -1)], axis=1), w)

    def view_rows(name, ai):
        """Positions ``[0, kv_view)`` of the dispatch's slots."""
        rows = read_cache_view(kv_cache[name], ai, kv_view, slots)
        if not quant:
            return rows
        scale = read_cache_view(kv_cache[name + "_scale"], ai, kv_view, slots)
        return _unpack(rows, scale, dtype)

    def ring_rows(name, ai):
        """The window before ``starts`` out of the ring."""
        def at(plane):
            layer = jax.lax.dynamic_index_in_dim(plane, ai, 0, keepdims=False)
            return layer[slots[:, None], hist_at]

        rows = at(kv_cache[name])
        return _unpack(rows, at(kv_cache[name + "_scale"]), dtype) \
            if quant else rows

    def layer(run, x, blk, ai, ffn):
        with jax.named_scope("attn"):
            q, k, v, gate = _attn_inputs(
                cfg, run.attn, blk, _normed(cfg, x, blk, dtype), pos)
        kp, vp = PLANES[run.attn]
        with jax.named_scope("kv_read"):
            k_new = _as_held(cfg, run.attn, k, quant)
            v_new = _as_held(cfg, run.attn, v, quant)
            if run.attn == "full":
                k_all = lay_tail(view_rows(kp, ai), k_new, place, fresh)
                v_all = lay_tail(view_rows(vp, ai), v_new, place, fresh)
                mask = full_mask
            else:
                k_all = jnp.concatenate([ring_rows(kp, ai), k_new], axis=1)
                v_all = jnp.concatenate([ring_rows(vp, ai), v_new], axis=1)
                mask = win_mask
        a = _attend(cfg, run.attn, blk, q, k_all, v_all, mask)
        x = x + _attn_out(cfg, run.attn, blk, a, gate)
        with jax.named_scope("ffn"):
            out, stats = ffn(x, counted)
            return x + out, (k, v), stats

    x, rows, stats = _scan_runs(cfg, params, layer,
                                _embed(cfg, params, tokens))
    keep = valid & (pos >= (starts + lengths)[:, None] - ring)
    new_cache = kv_cache
    for kind, (k, v) in rows.items():
        new_cache = _write(cfg, new_cache, kind, k, v, slots, pos, keep)
    logits = _head(cfg, params, x)
    if return_all_logits:
        return logits, new_cache, stats
    last = jnp.take_along_axis(
        logits, (lengths - 1)[:, None, None], axis=1)[:, 0]
    return last, new_cache, stats


def ring_kernel_decline(cfg: ModelConfig, ring: int) -> Optional[str]:
    """Why a window layer's decode keeps the einsum over its ring of ``ring``
    positions where the full layers take the rows kernel; ``None`` where it
    takes the kernel too (ISSUE 56).  What a ring adds to
    ``transformer.decode_kernel_decline``: its slots in whole blocks of 128
    and, unless interpreting, a window layer's key and value rows in whole
    lane tiles."""
    if ring % 128:
        return f"a ring of {ring} positions does not tile (% 128)"
    if cfg.flash_interpret:
        return None
    kv = cfg.kv_heads_of("window")
    for what, width in (("key", kv * cfg.head_dim),
                        ("value", kv * cfg.v_head_dim)):
        if width % 128:
            return (f"a window layer's {what} row of {width} does not tile "
                    "(% 128)")
    return None


def ring_read(cfg: ModelConfig, ring: int) -> str:
    """How a window layer's decode reads its ring where the full layers take
    the rows kernel, as /healthz prints it
    (``transformer.decode_branch_coverage``)."""
    if ring_kernel_decline(cfg, ring) is None:
        return "rows of the ring"
    return "einsum over the ring"


def decode_step(cfg, params, kv_cache, tokens, positions,
                kv_view: Optional[int] = None, mesh=None):
    """``transformer.decode_step`` for the two kinds of plane; the planes
    are the carry of the runs' scans and take one in-place row write a layer
    each.  Both kinds read by what ``decode_attention_branch`` answers:
    ``"pallas-rows"`` (the TPU, plain bf16 planes) is one kernel over the
    stacked planes where they lie, so no layer is sliced out and ``kv_view``
    bounds nothing: a full layer's row stops at its own position, a window
    layer's takes the blocks of its ring that hold its window and masks a
    slot by the position it holds (a ring ``ring_kernel_decline`` refuses
    keeps the einsum).  The einsum reads a full layer's ``kv_view``
    positions under the causal mask and a window layer's whole ring under
    the mask by position.  Rows parked at ``positions >= S`` write nothing
    and count for nothing.  Returns (logits [B,V], cache, stats)."""
    from p2p_llm_tunnel_tpu.models.transformer import decode_attention_branch

    b = tokens.shape[0]
    s = kv_cache["k"].shape[2]
    ring = kv_cache["wk"].shape[2]
    if kv_view is None or kv_view > s:
        kv_view = s
    quant = "k_scale" in kv_cache
    dtype = params["embed"].dtype
    pos2d = positions[:, None]
    slot_ids = jnp.arange(b)
    live = positions < s
    counted = live[:, None]
    masks, work = {}, {}  # a kind reads by its work list or under its mask
    if decode_attention_branch(
            cfg, mesh, kv_view, "int8" if quant else None, s) == "pallas-rows":
        from p2p_llm_tunnel_tpu.ops.pallas_decode_attention import (
            decode_attention_rows,
            decode_ring_worklist,
            decode_rows_worklist,
            rows_block,
        )

        # One work list a step and kind, shared by the kind's layers.
        block = {"full": rows_block(s, cfg.kv_heads_of("full"))}
        work["full"] = decode_rows_worklist(positions, s, block["full"])
        if ring_kernel_decline(cfg, ring) is None:
            block["window"] = rows_block(ring, cfg.kv_heads_of("window"))
            work["window"] = decode_ring_worklist(
                positions, s, ring, block["window"], cfg.sliding_window)
    if "full" not in work:
        masks["full"] = window_mask(
            pos2d, jnp.broadcast_to(jnp.arange(kv_view), (b, kv_view)))
    if "window" not in work:
        masks["window"] = window_mask(
            pos2d, ring_positions(positions, ring), cfg.sliding_window)
    at = {"full": positions,
          "window": _ring_slots(positions, live, ring)}
    extent = {"full": kv_view, "window": ring}

    def layer(run, carry, blk, ai, ffn):
        x, cache = carry
        kind = run.attn
        with jax.named_scope("attn"):
            q, k, v, gate = _attn_inputs(
                cfg, kind, blk, _normed(cfg, x, blk, dtype), pos2d)
        cache = dict(cache)
        zero = jnp.zeros((), ai.dtype)

        def seen(leaf):
            return jax.lax.dynamic_slice(
                cache[leaf], (ai, zero, zero, zero),
                (1, b, extent[kind], cache[leaf].shape[-1]))[0]

        rows = []
        for name, row in zip(PLANES[kind], (k, v)):
            with jax.named_scope("kv_write"):
                row, scale = _pack(row[:, 0], cfg.kv_heads_of(kind), quant)
                where = (ai, slot_ids, at[kind])
                cache[name] = cache[name].at[where].set(row)
                if quant:
                    cache[name + "_scale"] = cache[
                        name + "_scale"].at[where].set(scale)
            if kind not in work:
                with jax.named_scope("kv_read"):
                    rows.append(
                        _unpack(seen(name), seen(name + "_scale"), dtype)
                        if quant else seen(name))
        if kind in work:
            window = kind == "window"
            with jax.named_scope("attn"), jax.named_scope(ATTN_GROUP[kind]):
                a = decode_attention_rows(
                    q[:, 0], *(cache[name] for name in PLANES[kind]), ai,
                    work[kind], block=block[kind],
                    scale=cfg.query_scale or cfg.head_dim ** -0.5,
                    window=cfg.sliding_window if window else None,
                    ring=window, sink=blk.get("sink") if window else None,
                    interpret=cfg.flash_interpret).reshape(b, 1, -1)
        else:
            a = _attend(cfg, kind, blk, q, rows[0], rows[1], masks[kind])
        x = x + _attn_out(cfg, kind, blk, a, gate)
        with jax.named_scope("ffn"):
            out, stats = ffn(x, counted)
            return (x + out, cache), None, stats

    (x, new_cache), _, stats = _scan_runs(
        cfg, params, layer,
        (_embed(cfg, params, tokens[:, None]), dict(kv_cache)))
    return _head(cfg, params, x)[:, 0], new_cache, stats
