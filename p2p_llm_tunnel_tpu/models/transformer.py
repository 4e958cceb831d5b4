"""Functional decoder-only transformer (Llama/Gemma families) in pure JAX.

TPU-first design choices:
- params are pytrees of stacked per-layer arrays; the layer loop is a
  ``lax.scan`` so an 80-layer model traces/compiles as one small program
- everything is shape-static: padded prompt batches for prefill, a
  fixed-slot KV cache written in place for decode (continuous batching
  slots, SURVEY.md §7 hard-part #1)
- bf16 params/activations, fp32 softmax/norm accumulations (MXU-friendly)
- sharding-agnostic: callers place params/cache with NamedSharding and jit;
  the same functions serve single-chip and tensor-parallel meshes

The reference has no ML code at all (SURVEY.md §2); this module is the
in-process upstream that replaces its reqwest→Ollama hop (serve.rs:219).
"""

from __future__ import annotations

import functools
import math
from typing import Dict, Optional, Tuple

import jax
import jax.numpy as jnp

from p2p_llm_tunnel_tpu.models.config import ModelConfig
from p2p_llm_tunnel_tpu.models.quant import (
    embed_lookup,
    head_matmul,
    mm,
    round_act,
)
from p2p_llm_tunnel_tpu.ops.attention import cached_attention, causal_attention
from p2p_llm_tunnel_tpu.ops.norms import rms_norm
from p2p_llm_tunnel_tpu.ops.rope import apply_rope

Params = Dict[str, jnp.ndarray]
KVCache = Dict[str, jnp.ndarray]  # {'k','v': [L, B, S, K, D]}


# ---------------------------------------------------------------------------
# init
# ---------------------------------------------------------------------------

def init_params(
    cfg: ModelConfig, key: jax.Array, dtype=jnp.bfloat16
) -> Params:
    """Random init (truncated-normal fan-in); layout matches checkpoint loader."""
    family = _family_module(cfg)
    if family is not None:
        return family.init_params(cfg, key, dtype)
    l, dm, h, kh, hd, f, v = (
        cfg.n_layers, cfg.dim, cfg.n_heads, cfg.n_kv_heads,
        cfg.head_dim, cfg.ffn_dim, cfg.vocab_size,
    )
    keys = jax.random.split(key, 12)

    def dense(k, shape, fan_in):
        return (jax.random.truncated_normal(k, -2, 2, shape, jnp.float32)
                * (fan_in**-0.5)).astype(dtype)

    blocks = {
        "attn_norm": jnp.zeros((l, dm), dtype) if cfg.post_norms else jnp.ones((l, dm), dtype),
        "mlp_norm": jnp.zeros((l, dm), dtype) if cfg.post_norms else jnp.ones((l, dm), dtype),
        "wq": dense(keys[0], (l, dm, h * hd), dm),
        "wk": dense(keys[1], (l, dm, kh * hd), dm),
        "wv": dense(keys[2], (l, dm, kh * hd), dm),
        "wo": dense(keys[3], (l, h * hd, dm), h * hd),
    }
    if cfg.n_experts:
        from p2p_llm_tunnel_tpu.models.moe import init_moe_blocks

        # (a block-generation model is drawn an expert at a time: its
        # 128 experts a layer do not fit the chip as one float32 draw)
        blocks.update(init_moe_blocks(cfg, keys[8:12], dense,
                                      per_expert=bool(cfg.block_length)))
    else:
        blocks.update({
            "w_gate": dense(keys[4], (l, dm, f), dm),
            "w_up": dense(keys[5], (l, dm, f), dm),
            "w_down": dense(keys[6], (l, f, dm), f),
        })
    if cfg.post_norms:
        blocks["post_attn_norm"] = jnp.zeros((l, dm), dtype)
        blocks["post_mlp_norm"] = jnp.zeros((l, dm), dtype)
    if cfg.qk_norm:
        # Qwen3's: one learned weight a column of a head, shared by the
        # heads.  Drawn about 1 (not ones) so tests exercise a weight that
        # changes the output.
        for name, part in (("q_norm", 60), ("k_norm", 61)):
            blocks[name] = (1.0 + 0.1 * jax.random.normal(
                jax.random.fold_in(key, part), (l, hd), jnp.float32)
            ).astype(dtype)
    if cfg.attn_bias:
        # qwen2: bias on Q/K/V projections only.  Random init (not zeros)
        # so tests exercise a bias that actually changes the output.
        blocks["bq"] = dense(jax.random.fold_in(key, 50), (l, h * hd), dm)
        blocks["bk"] = dense(jax.random.fold_in(key, 51), (l, kh * hd), dm)
        blocks["bv"] = dense(jax.random.fold_in(key, 52), (l, kh * hd), dm)

    params: Params = {
        "embed": dense(keys[7], (v, dm), dm),
        "blocks": blocks,
        "final_norm": jnp.zeros((dm,), dtype) if cfg.post_norms else jnp.ones((dm,), dtype),
    }
    if not cfg.tie_embeddings:
        params["lm_head"] = dense(jax.random.fold_in(key, 99), (dm, v), dm)
    return params


def init_kv_cache(
    cfg: ModelConfig, num_slots: int, max_seq: int, dtype=jnp.bfloat16,
    quant=False,
) -> KVCache:
    """Slot cache; ``quant`` selects the storage precision.

    ``True``/``"int8"`` stores int8 values + per-(token, head) fp32 scales
    — halves the KV read term that dominates decode HBM traffic at long
    context (the KV analog of weight-only int8; scales add 1/D of the
    saving back).  ``"int4"`` (ISSUE 4) packs TWO ADJACENT TOKENS per int8
    byte along the sequence axis (token 2i low nibble, 2i+1 high — the
    models.quant.pack_int4(axis) layout), quartering the KV stream; the
    scale planes stay per-token full resolution.  ``max_seq`` must be even
    for int4 (every serving bucket is).

    A latent-attention config (``cfg.kv_lora_rank``) gets its own form, the
    latent planes (models/mla.py); one with an ``attn_pattern`` gets window
    layers as rings beside full-length planes (models/swa.py)."""
    family = _family_module(cfg)
    if family is not None:
        return family.init_kv_cache(cfg, num_slots, max_seq, dtype, quant)
    shape = (cfg.n_layers, num_slots, max_seq, cfg.n_kv_heads, cfg.head_dim)
    _modes = {False: None, True: "int8", None: None, "none": None, "": None,
              "int8": "int8", "int4": "int4"}
    if quant not in _modes:
        raise ValueError(
            f"unknown KV quant mode {quant!r}; expected one of "
            "False/True/None/'none'/'int8'/'int4'"
        )
    mode = _modes[quant]
    if mode is None:
        return {"k": jnp.zeros(shape, dtype), "v": jnp.zeros(shape, dtype)}
    if mode == "int4":
        if max_seq % 2:
            raise ValueError(f"int4 KV cache needs an even max_seq, got {max_seq}")
        packed = shape[:2] + (max_seq // 2,) + shape[3:]
        return {
            "k": jnp.zeros(packed, jnp.int8),
            "v": jnp.zeros(packed, jnp.int8),
            "k_scale": jnp.zeros(shape[:-1], jnp.float32),
            "v_scale": jnp.zeros(shape[:-1], jnp.float32),
        }
    return {
        "k": jnp.zeros(shape, jnp.int8),
        "v": jnp.zeros(shape, jnp.int8),
        "k_scale": jnp.zeros(shape[:-1], jnp.float32),
        "v_scale": jnp.zeros(shape[:-1], jnp.float32),
    }


def kv_cache_is_quantized(kv_cache: KVCache) -> bool:
    return "k_scale" in kv_cache


def kv_cache_quant_mode(kv_cache: KVCache) -> Optional[str]:
    """None | "int8" | "int4" — int4 is recognized by its byte-packed
    sequence axis (half the scale plane's)."""
    if "k_scale" not in kv_cache:
        return None
    if kv_cache["k"].shape[2] * 2 == kv_cache["k_scale"].shape[2]:
        return "int4"
    return "int8"


def _quant_kv(x: jnp.ndarray):
    """Symmetric int8 over the trailing head_dim axis → (q, scale).

    Same formula as activation quant — one definition (models/quant.py
    _quantize_act); only the scale's keepdims differs."""
    from p2p_llm_tunnel_tpu.models.quant import _quantize_act

    q, scale = _quantize_act(x)
    return q, scale[..., 0]


def _quant_kv4(x: jnp.ndarray):
    """Symmetric int4 over the trailing head_dim axis → (q in [-7, 7] as
    int8 VALUES — caller packs — and per-(token, head) scale).  The same
    formula the ragged prefill kernel applies in VMEM: any drift between
    the two breaks ragged/chunk token identity."""
    amax = jnp.max(jnp.abs(x.astype(jnp.float32)), axis=-1, keepdims=True)
    scale = jnp.maximum(amax, 1e-8) / 7.0
    q = jnp.clip(jnp.round(x.astype(jnp.float32) / scale), -7, 7)
    return q.astype(jnp.int8), scale[..., 0]


# ---------------------------------------------------------------------------
# shared block pieces
# ---------------------------------------------------------------------------

def _norm(cfg: ModelConfig, x, w):
    return rms_norm(x, w, cfg.norm_eps, plus_one=cfg.post_norms)


def stream_in(cfg: ModelConfig, x):
    """The embedded activations as the residual stream carries them, and
    the type the layers' products take (``x``'s own): float32 under
    ``cfg.residual_f32``, else ``x`` as it is (no operation)."""
    return (x.astype(jnp.float32) if cfg.residual_f32 else x), x.dtype


def normed(cfg: ModelConfig, x, w, act):
    """RMSNorm of the stream -> (in the products' type ``act``, as the
    router scores it: the float32 norm under ``cfg.residual_f32``, else
    None = the same)."""
    h = _norm(cfg, x, w)
    if not cfg.residual_f32:
        return h, None
    return h.astype(act), h


def _act(cfg: ModelConfig, x):
    if cfg.act == "gelu":
        return jax.nn.gelu(x, approximate=True)
    if cfg.act == "relu2":
        return jnp.square(jax.nn.relu(x))
    return jax.nn.silu(x)


def _mlp(cfg: ModelConfig, blk, h, counted=None, stacked=None, layer=None,
         router_in=None):
    """The block's feed-forward -> (out, what a routed layer counted of the
    ``counted`` tokens (models/moe.py) or None for a dense one).
    ``stacked`` + ``layer``: :func:`split_experts`; ``router_in``:
    :func:`normed`."""
    if cfg.n_experts:
        from p2p_llm_tunnel_tpu.models.moe import moe_mlp

        return moe_mlp(cfg, blk, h, lambda x: _act(cfg, x), counted,
                       stacked=stacked, layer=layer, router_in=router_in)
    aq = cfg.act_quant
    gate = _act(cfg, mm(h, blk["w_gate"], aq)) * mm(h, blk["w_up"], aq)
    return mm(gate, blk["w_down"], aq), None


def split_experts(cfg: ModelConfig, blocks):
    """``blocks`` as (the leaves a layer scan slices, the experts of all
    layers as three arrays [L * E, ...] that a routed layer reads where
    they lie, or None).  A layer's slice of the expert stack handed to the
    grouped product is a copy first (models/moe.py ``stacked``): 1.2 GB a
    layer at 128 experts of 2048 x 768.  Taken by the block-generation
    family, which holds every expert of a layer; the other routed presets
    of this module keep their slices (``--ep`` shards their expert axis)."""
    if not (cfg.n_experts and cfg.block_length):
        return blocks, None
    from p2p_llm_tunnel_tpu.models.moe import EXPERT_LEAVES

    stacked = {k: blocks[k].reshape((-1,) + blocks[k].shape[2:])
               for k in EXPERT_LEAVES}
    return ({k: v for k, v in blocks.items() if k not in EXPERT_LEAVES},
            stacked)


def reads_expert_stack(cfg: ModelConfig, program: str) -> bool:
    """Whether ``program`` (the engine's name for it) hands a routed layer
    the experts of all layers to read where they lie (``moe_mlp``'s
    ``stacked``): every program of a family module, and the two that take
    :func:`split_experts` here, the decode passes and chunk prefill of a
    model that generates by blocks."""
    return _family_module(cfg) is not None or (
        bool(cfg.n_experts and cfg.block_length)
        and program in ("decode", "chunk_prefill"))


def _moe_total(stats):
    """Per-layer counts [L, STATS] of a layer scan -> their sum; zeros for a
    dense model."""
    from p2p_llm_tunnel_tpu.models.moe import STATS

    if stats is None:
        return jnp.zeros((STATS,), jnp.int32)
    return stats.sum(axis=0)


def _family_module(cfg: ModelConfig):
    """The module that serves a family whose cache is not one ``[L, rows, S,
    K, D]`` pair of planes (latent rows: models/mla.py; window rings beside
    full planes: models/swa.py; a recurrent state a slot beside KV planes,
    one mixer a layer: models/ssm_moe.py), or None."""
    if cfg.mixer_pattern is not None:
        from p2p_llm_tunnel_tpu.models import ssm_moe

        return ssm_moe
    if cfg.kv_lora_rank:
        from p2p_llm_tunnel_tpu.models import mla

        return mla
    if cfg.attn_pattern is not None:
        from p2p_llm_tunnel_tpu.models import swa

        return swa
    return None


def _proj(cfg: ModelConfig, x, w):
    """``x @ w`` (models/quant.py ``mm``); under ``cfg.residual_f32`` the
    result stays float32: the operands are ``x``'s type either way."""
    if cfg.residual_f32:
        return jnp.dot(round_act(x, cfg.act_quant), w,
                       preferred_element_type=jnp.float32)
    return mm(x, w, cfg.act_quant)


def _qkv_proj(cfg: ModelConfig, blk, h):
    """QKV projections + bias + head split, NO rope — the ragged prefill
    kernel applies rope in VMEM at each token's position, so
    ``ragged_prefill_into_cache`` consumes these directly."""
    b, t, _ = h.shape
    q = _proj(cfg, h, blk["wq"])
    k = _proj(cfg, h, blk["wk"])
    v = _proj(cfg, h, blk["wv"])
    if cfg.attn_bias:  # qwen2: additive bias on the Q/K/V projections
        q = q + blk["bq"].astype(q.dtype)
        k = k + blk["bk"].astype(k.dtype)
        v = v + blk["bv"].astype(v.dtype)
    q = q.reshape(b, t, cfg.n_heads, cfg.head_dim)
    k = k.reshape(b, t, cfg.n_kv_heads, cfg.head_dim)
    v = v.reshape(b, t, cfg.n_kv_heads, cfg.head_dim)
    if cfg.qk_norm:  # over each head's columns, before rope
        q = rms_norm(q, blk["q_norm"], cfg.norm_eps)
        k = rms_norm(k, blk["k_norm"], cfg.norm_eps)
    return q, k, v


def _qkv(cfg: ModelConfig, blk, h, positions):
    q, k, v = _qkv_proj(cfg, blk, h)
    q = apply_rope(q, positions, cfg.rope_theta)
    k = apply_rope(k, positions, cfg.rope_theta)
    if cfg.residual_f32:
        # projected, normed and roped in float32, rounded once: to what the
        # scores take and the cache holds
        q, k, v = (a.astype(h.dtype) for a in (q, k, v))
    return q, k, v


def _layer_window(cfg: ModelConfig, layer_idx, seq_len):
    """Per-layer sliding-window size as a traced scalar; None when the
    config never uses windows.  gemma-2 alternates local/global layers;
    mistral windows every layer."""
    if cfg.sliding_window is None:
        return None
    if cfg.window_pattern == "all":
        return jnp.asarray(cfg.sliding_window)
    use = (layer_idx % 2) == 0
    return jnp.where(use, cfg.sliding_window, seq_len + 1)


def _embed(cfg: ModelConfig, params, tokens):
    embed = params["embed"]
    # Quantized tables (QTensor int8, QTensor4 packed int4 — both expose
    # .q as int8 storage) dequantize into bf16 activations; every
    # projection/head matmul downstream follows x's dtype (quant.mm).
    dtype = embed.q.dtype if hasattr(embed, "q") else embed.dtype
    if dtype == jnp.int8:
        dtype = jnp.bfloat16
    x = embed_lookup(embed, tokens, dtype)
    if cfg.embed_scale:
        x = (x.astype(jnp.float32) * math.sqrt(cfg.dim)).astype(x.dtype)
    return x


def _logits(cfg: ModelConfig, params, x):
    if cfg.tie_embeddings:
        logits = head_matmul(x, params["embed"], cfg.act_quant).astype(jnp.float32)
    else:
        logits = mm(x, params["lm_head"], cfg.act_quant).astype(jnp.float32)
    if cfg.logit_softcap is not None:
        logits = cfg.logit_softcap * jnp.tanh(logits / cfg.logit_softcap)
    return logits


# ---------------------------------------------------------------------------
# prefill
# ---------------------------------------------------------------------------

def prefill_attention_branch(cfg: ModelConfig, mesh, t: int) -> str:
    """Which attention implementation whole-prompt prefill takes at padded
    width ``t``: ``"ulysses"`` | ``"ring"`` (sp>1), ``"pallas-flash"``, or
    ``"einsum"``.  The ONE predicate — :func:`_prefill_attention_fn`
    selects by it and the engine reports it (/healthz
    ``config.attention``), so what is printed is what ran."""
    axes = dict(mesh.shape) if mesh is not None else {}
    if axes.get("sp", 1) > 1:
        return cfg.sp_mode
    if (
        cfg.flash
        and (jax.default_backend() == "tpu" or cfg.flash_interpret)
        and t % 128 == 0
        and cfg.head_dim % 128 == 0
        and cfg.attn_pattern is None
        and not cfg.block_length  # the flash kernel's mask is causal
    ):
        return "pallas-flash"
    return "einsum"


def decode_kernel_decline(cfg: ModelConfig, mesh, kv_view: int):
    """Why the Pallas decode kernel (``decode_attention_rows``) cannot serve
    this (config, mesh, view) — ``None`` when it can.  The gate
    ``decode_attention_branch`` asks:

    - off the TPU backend the kernel runs only in interpret mode (CPU
      tests) or under ``flash_force`` (lowering-only tests);
    - tp>1 declines: pallas_call is not GSPMD-partitioned, so under a tp
      mesh XLA would all-gather the sharded q/KV onto every chip (the
      hazard prefill's flash_tp shard_map wrapper exists for — apply the
      same wrapper here before enabling);
    - shapes must tile (view and head_dim % 128) unless interpreting; in a
      model with an ``attn_pattern`` or a ``mixer_pattern`` the lane width
      is asked of a full layer's ROWS (its KV heads side by side,
      models/swa.py and models/ssm_moe.py), not of a head: 8 heads of 64
      are four lane tiles."""
    backend = jax.default_backend()
    if not (backend == "tpu" or cfg.flash_interpret or cfg.flash_force):
        return f"backend {backend!r} is not tpu"
    tp = dict(mesh.shape).get("tp", 1) if mesh is not None else 1
    if tp > 1:
        return (f"pallas_call is not GSPMD-partitioned: under a tp={tp} "
                "mesh XLA would all-gather the sharded cache")
    if kv_view % 128:
        return f"kv view {kv_view} does not tile (% 128)"
    if cfg.flash_interpret:
        return None
    if cfg.attn_pattern is not None or cfg.mixer_pattern is not None:
        # Planes whose rows are a position's KV heads side by side
        # (models/swa.py, models/ssm_moe.py): a row has to be whole lane
        # tiles; a head's own width need not be.
        kv = cfg.kv_heads_of("full")
        for what, width in (("key", kv * cfg.head_dim),
                            ("value", kv * cfg.v_head_dim)):
            if width % 128:
                return (f"a full layer's {what} row of {width} does not "
                        "tile (% 128)")
    elif cfg.head_dim % 128:
        return f"head_dim {cfg.head_dim} does not tile (% 128)"
    return None


def decode_attention_branch(cfg: ModelConfig, mesh, kv_view: int,
                            kv_quant: Optional[str] = None,
                            max_seq: Optional[int] = None) -> str:
    """Which attention ``decode_step`` takes at this view over a cache of
    precision ``kv_quant`` and ``max_seq`` positions (the view's, where not
    given).  The one place that chooses, and the ``if`` below is the whole
    rule: ``"pallas-rows"`` wherever that kernel can run — by what the code
    can observe, no option: the gate passes for the whole cache, which is
    what the kernel reads (the view bounds nothing there), and the cache is
    plain planes of KV heads (the int8 and int4 caches, the latent family
    and generation by blocks keep the einsum) — else ``"einsum"``.
    ``cfg.flash`` off is the einsum everywhere, as in prefill (the
    reference a kernel is held against).

    In a model with an ``attn_pattern`` (models/swa.py) the answer is the
    FULL layers': their planes are what follows a view.  A window layer
    follows it where ``swa.ring_kernel_decline`` lets its ring, else reads
    its whole ring by einsum (:func:`decode_branch_coverage`)."""
    if (cfg.flash and kv_quant is None and not cfg.kv_lora_rank
            and not cfg.block_length  # models/block_decode.py: an einsum
            and decode_kernel_decline(cfg, mesh, max_seq or kv_view) is None):
        return "pallas-rows"
    return "einsum"


def decode_branch_coverage(cfg: ModelConfig, branch: str, ring: int) -> str:
    """``branch`` as /healthz ``config.attention.decode`` prints it: with the
    layers it covers, where a model's full layers and its rings may differ."""
    if cfg.attn_pattern is None or branch == "einsum":
        return branch
    from p2p_llm_tunnel_tpu.models.swa import ring_read
    return f"{branch} (full layers; window layers: {ring_read(cfg, ring)})"


def _prefill_attention_fn(cfg: ModelConfig, mesh, t: int):
    """Pick the prefill attention implementation for this (config, mesh).

    Returns ``fn(q, k, v, valid, window) -> [B,T,H,D]``.  Selection:
    - sp axis > 1 → sequence parallelism, strategy per ``cfg.sp_mode``:
      "ulysses" (all_to_all head/sequence swap; windows and pad masks
      work) or "ring" (ppermute KV rotation over the ICI ring; rejects
      sliding windows) — SURVEY §5's two long-context strategies;
    - the Pallas flash kernel when shapes tile, wrapped in shard_map over
      the head axes when a ``tp`` axis > 1 is present (pallas_call is not
      GSPMD-partitioned — VERDICT r2 item 6);
    - the dense einsum fallback otherwise (always-correct oracle).
    """
    axes = dict(mesh.shape) if mesh is not None else {}
    sp, tp = axes.get("sp", 1), axes.get("tp", 1)

    if sp > 1 and cfg.sp_mode not in ("ring", "ulysses"):
        raise ValueError(f"unknown sp_mode {cfg.sp_mode!r}")
    if sp > 1 and cfg.sp_mode == "ulysses":
        from p2p_llm_tunnel_tpu.ops.ulysses_attention import (
            make_ulysses_attention,
        )

        if cfg.n_heads % sp or cfg.n_kv_heads % sp:
            raise ValueError(
                f"ulysses sp={sp} needs H ({cfg.n_heads}) and K "
                f"({cfg.n_kv_heads}) divisible by sp; use sp_mode='ring'"
            )
        ulysses = make_ulysses_attention(
            mesh, "sp", scale=cfg.query_scale, softcap=cfg.attn_softcap,
            head_axis="tp" if tp > 1 else None,
        )

        def ulysses_fn(q, k, v, valid, window):
            # Full-sequence inner attention: pad masks and sliding windows
            # apply unchanged (the capability ring attention lacks).
            return ulysses(q, k, v, valid, window=window)

        return ulysses_fn

    if sp > 1:
        if cfg.sliding_window is not None:
            raise NotImplementedError(
                "ring attention does not support sliding windows; "
                "use sp_mode='ulysses' or an sp=1 mesh for windowed models"
            )
        from p2p_llm_tunnel_tpu.ops.ring_attention import make_ring_attention

        ring = make_ring_attention(
            mesh, "sp",
            scale=cfg.query_scale,
            softcap=cfg.attn_softcap,
            head_axis="tp" if tp > 1 else None,
        )

        def ring_fn(q, k, v, valid, window):
            # Right-padded prompts need no pad mask: pad KV sits at positions
            # strictly after every real query, so causality masks it.
            return ring(q, k, v)

        return ring_fn

    if prefill_attention_branch(cfg, mesh, t) == "pallas-flash":
        from p2p_llm_tunnel_tpu.ops.pallas_attention import (
            flash_causal_attention,
        )

        flash = functools.partial(
            flash_causal_attention,
            scale=cfg.query_scale,
            softcap=cfg.attn_softcap,
            interpret=cfg.flash_interpret,
        )
        if tp > 1:
            from jax.sharding import PartitionSpec as P

            head_spec = P(None, None, "tp", None)
            rep = P(None, None)

            def flash_tp(q, k, v, valid, window):
                # One kernel per head-shard: q heads and kv heads both split
                # on tp (column-parallel wq/wk/wv), so GQA grouping is
                # preserved shard-locally.  window crosses the shard_map
                # boundary as a replicated scalar (t+1 = disabled).
                win = jnp.asarray(t + 1 if window is None else window, jnp.int32)
                return jax.shard_map(
                    lambda q_, k_, v_, valid_, win_: flash(
                        q_, k_, v_, valid_, window=win_
                    ),
                    mesh=mesh,
                    in_specs=(head_spec, head_spec, head_spec, rep, P()),
                    out_specs=head_spec,
                    # pallas_call does not annotate varying-mesh-axes on its
                    # outputs; the per-shard kernel is trivially correct
                    # (no cross-shard comms), so skip the vma check.
                    check_vma=False,
                )(q, k, v, valid, win)

            return flash_tp
        return lambda q, k, v, valid, window: flash(q, k, v, valid, window=window)

    return lambda q, k, v, valid, window: causal_attention(
        q, k, v, valid,
        scale=cfg.query_scale,
        softcap=cfg.attn_softcap,
        window=window,
        block=cfg.block_length,
    )


def prefill(
    cfg: ModelConfig,
    params: Params,
    tokens: jnp.ndarray,  # [B, T] right-padded
    valid: jnp.ndarray,  # [B, T] bool
    mesh=None,
    counted=None,
):
    """Full-prompt forward. Returns (logits [B,T,V], k, v [L,B,T,K,D]); a
    latent-attention config returns its cached rows [L,B,T,C+Dr] for ``k``
    and None for ``v``, a window-and-full one its rows by kind.  With ``counted`` ([B,T] bool) a fourth value: the
    routed layers' counts of those tokens (see ``apply_blocks``).

    ``mesh`` (optional jax.sharding.Mesh) selects sharded attention paths:
    tp shard_map's the flash kernel over head shards; sp>1 runs ring
    attention over the sequence axis (see _prefill_attention_fn).
    """
    b, t = tokens.shape
    family = _family_module(cfg)
    if family is not None:
        logits, rows, stats = family.prefill(cfg, params, tokens, valid,
                                             counted)
        if counted is not None:
            return logits, rows, None, stats
        return logits, rows, None
    x = _embed(cfg, params, tokens)
    attention = _prefill_attention_fn(cfg, mesh, t)
    out = apply_blocks(cfg, params["blocks"], x, valid, attention,
                       counted=counted)
    x = _norm(cfg, out[0], params["final_norm"]).astype(x.dtype)
    return (_logits(cfg, params, x),) + tuple(out[1:])


def encode_pooled(
    cfg: ModelConfig,
    params: Params,
    tokens: jnp.ndarray,  # [B, T] right-padded
    valid: jnp.ndarray,  # [B, T] bool
    mesh=None,
) -> jnp.ndarray:
    """Mean-pooled, L2-normalized final hidden states — the embeddings
    surface (/v1/embeddings, Ollama /api/embed).  Masked mean over the
    real tokens of the post-final-norm activations; a standard last-layer
    pooling baseline that becomes genuinely useful with real checkpoints.
    Returns [B, Dm] float32."""
    x = _embed(cfg, params, tokens)
    attention = _prefill_attention_fn(cfg, mesh, tokens.shape[1])
    x, _ks, _vs = apply_blocks(cfg, params["blocks"], x, valid, attention)
    x = _norm(cfg, x, params["final_norm"]).astype(jnp.float32)
    m = valid[..., None].astype(jnp.float32)
    pooled = (x * m).sum(axis=1) / jnp.maximum(m.sum(axis=1), 1.0)
    return pooled / jnp.maximum(
        jnp.linalg.norm(pooled, axis=-1, keepdims=True), 1e-9
    )


def apply_blocks(
    cfg: ModelConfig,
    blocks: Params,  # stacked [L_chunk, ...] (the whole stack or a pp stage)
    x: jnp.ndarray,  # [B, T, Dm] embedded activations
    valid: jnp.ndarray,  # [B, T] bool
    attention,  # fn(q, k, v, valid, window) -> [B,T,H,D]
    layer_offset=0,  # global index of blocks[0] (pp stages pass stage*L/S)
    counted=None,  # [B, T] bool: with it, also returns the routed layers' counts
):
    """Run a stacked block chunk over activations; returns (x', ks, vs), and
    with ``counted`` a fourth value: what the routed layers counted of those
    tokens (models/moe.py STATS, summed over layers; zeros for a dense model).

    Factored out of ``prefill`` so the pipeline-parallel stage executor
    (parallel/pipeline.py) runs exactly the same per-layer computation on
    its layer shard — one definition of what a block IS."""
    b, t, _ = x.shape
    positions = jnp.broadcast_to(jnp.arange(t), (b, t))
    n_chunk = jax.tree_util.tree_leaves(blocks)[0].shape[0]
    layer_idx = layer_offset + jnp.arange(n_chunk)

    x, act = stream_in(cfg, x)

    def step(x, xs):
        blk, idx = xs
        h, _ = normed(cfg, x, blk["attn_norm"], act)
        q, k, v = _qkv(cfg, blk, h, positions)
        attn = attention(q, k, v, valid, _layer_window(cfg, idx, t))
        attn = _proj(cfg, attn.reshape(b, t, -1), blk["wo"])
        if cfg.post_norms:
            attn = _norm(cfg, attn, blk["post_attn_norm"])
        x = x + attn
        h, h32 = normed(cfg, x, blk["mlp_norm"], act)
        mlp, stats = _mlp(cfg, blk, h, counted, router_in=h32)
        if cfg.post_norms:
            mlp = _norm(cfg, mlp, blk["post_mlp_norm"])
        x = x + mlp
        return x, (k, v, stats)

    x, (ks, vs, stats) = jax.lax.scan(step, x, (blocks, layer_idx))
    if counted is not None:
        return x, ks, vs, _moe_total(stats)
    return x, ks, vs


def prefill_into_cache(
    cfg: ModelConfig,
    params: Params,
    tokens: jnp.ndarray,  # [Bp, T]
    lengths: jnp.ndarray,  # [Bp]
    kv_cache: KVCache,
    slots: jnp.ndarray,  # [Bp] cache slot per prompt
    mesh=None,
    return_prompt_logprobs: bool = False,
    stat_rows=None,  # [Bp] bool: with it, the routed layers' counts come last
):
    """Prefill prompts and scatter their KV into cache slots.

    Returns last-real-token logits [Bp, V] and the updated cache.  Positions
    past a prompt's length hold junk KV, but decode overwrites position
    ``length + n`` before it ever becomes attendable, so junk is never read.

    With ``return_prompt_logprobs`` (a STATIC flag; the echo/scoring path of
    the legacy completions API) additionally returns ``[Bp, T]`` log-probs
    of each prompt token given its prefix — entry ``t`` scores
    ``tokens[:, t]`` under the logits at position ``t-1``; entry 0 is 0.0
    (no context) and entries past a prompt's length are junk the caller
    masks by ``lengths``.

    With ``stat_rows`` the last value returned is what the routed layers
    counted (models/moe.py STATS) of the real tokens of those rows; padding
    rows, which the engine parks on its scratch slot, count for nothing.
    """
    b, t = tokens.shape
    family = _family_module(cfg)
    if family is not None:
        out = family.prefill_into_cache(
            cfg, params, tokens, lengths, kv_cache, slots,
            return_prompt_logprobs=return_prompt_logprobs,
            stat_rows=stat_rows)
        return out if stat_rows is not None else out[:-1]
    valid = jnp.arange(t)[None, :] < lengths[:, None]
    stats = None
    if stat_rows is not None:
        logits, ks, vs, stats = prefill(
            cfg, params, tokens, valid, mesh=mesh,
            counted=valid & stat_rows[:, None])
    else:
        logits, ks, vs = prefill(cfg, params, tokens, valid, mesh=mesh)
    last = jnp.take_along_axis(
        logits, (lengths - 1)[:, None, None], axis=1
    )[:, 0]  # [Bp, V]
    prompt_lps = None
    if return_prompt_logprobs:
        lsm = jax.nn.log_softmax(logits[:, :-1], axis=-1)  # [Bp, T-1, V]
        scored = jnp.take_along_axis(
            lsm, tokens[:, 1:, None], axis=-1
        )[..., 0]  # lp of token t given prefix, t = 1..T-1
        prompt_lps = jnp.concatenate(
            [jnp.zeros((b, 1), jnp.float32), scored.astype(jnp.float32)],
            axis=1,
        )

    # [L,Bp,T,K,D] → scatter over slot axis of [L,Slots,S,K,D]
    quant_mode = kv_cache_quant_mode(kv_cache)
    s_max = kv_cache["k"].shape[2] * (2 if quant_mode == "int4" else 1)
    ks = ks[:, :, :s_max]
    vs = vs[:, :, :s_max]
    t_w = ks.shape[2]
    out = dict(kv_cache)
    if quant_mode == "int4":
        from p2p_llm_tunnel_tpu.models.quant import write_packed_prefix

        kq, k_s = _quant_kv4(ks)
        vq, v_s = _quant_kv4(vs)
        if t_w % 2:
            # Packing needs an even token count: pad one junk position.
            # It sits at index >= the prompt length, so decode overwrites
            # it (whole byte when that position is even-aligned) before it
            # is ever attendable — the standard prefill-pad argument.
            pad = ((0, 0), (0, 0), (0, 1), (0, 0), (0, 0))
            kq = jnp.pad(kq, pad)
            vq = jnp.pad(vq, pad)
        out["k"] = write_packed_prefix(kv_cache["k"], slots, kq)
        out["v"] = write_packed_prefix(kv_cache["v"], slots, vq)
        out["k_scale"] = kv_cache["k_scale"].at[:, slots, :t_w].set(k_s)
        out["v_scale"] = kv_cache["v_scale"].at[:, slots, :t_w].set(v_s)
    elif quant_mode == "int8":
        kq, k_s = _quant_kv(ks)
        vq, v_s = _quant_kv(vs)
        out["k"] = kv_cache["k"].at[:, slots, :t_w].set(kq)
        out["v"] = kv_cache["v"].at[:, slots, :t_w].set(vq)
        out["k_scale"] = kv_cache["k_scale"].at[:, slots, :t_w].set(k_s)
        out["v_scale"] = kv_cache["v_scale"].at[:, slots, :t_w].set(v_s)
    else:
        out["k"] = kv_cache["k"].at[:, slots, :t_w].set(ks)
        out["v"] = kv_cache["v"].at[:, slots, :t_w].set(vs)
    ret = (last, out, prompt_lps) if return_prompt_logprobs else (last, out)
    return ret + (stats,) if stat_rows is not None else ret


def tail_placement(kv_view: int, starts: jnp.ndarray, t: int):
    """Which tail position each view position takes, a 0/1 matrix
    ``place [Bp, view, T]``: one 1 for a position in [starts, starts + T),
    none elsewhere, so a tail position at or past the view is dropped; and
    ``fresh [Bp, view]``, the positions that take one."""
    place = (
        jnp.arange(kv_view)[None, :, None] - starts[:, None, None]
        == jnp.arange(t)[None, None, :]
    )
    return place, place.any(axis=-1)


def read_cache_view(plane, idx, n: int, slots):
    """Positions ``[0, n)`` of the rows ``slots`` of layer ``idx`` of a
    cache plane ``[L, rows, S, ...]``: one (layer, view) slice of all cache
    rows, then the row gather."""
    start = (idx,) + (jnp.zeros((), idx.dtype),) * (plane.ndim - 1)
    shape = (1, plane.shape[1], n) + plane.shape[3:]
    return jax.lax.dynamic_slice(plane, start, shape)[0][slots]


def lay_tail(hist, tail, place, fresh):
    """``tail [Bp,T,...]`` over positions ``[starts, starts + T)`` of
    ``hist [Bp,view,...]`` (``place``, ``fresh``: :func:`tail_placement`).
    The 0/1 product puts each tail value in its place exactly (one term a
    position); on the chip it costs less than a scatter into the gathered
    rows, which first copies them into one buffer (PERF.md section 6, PR
    26)."""
    # float32 alone has to ask: the chip's default rounds its products
    # to bfloat16 (asked of bfloat16 tails too, it costs 3 % of a run).
    exact = jax.lax.Precision.HIGHEST if tail.dtype == jnp.float32 else None
    moved = jnp.einsum(
        "bpt,bt...->bp...", place.astype(tail.dtype), tail,
        precision=exact, preferred_element_type=tail.dtype,
    )
    at = fresh.reshape(fresh.shape + (1,) * (hist.ndim - 2))
    return jnp.where(at, moved, hist)


def chunk_prefill_into_cache(
    cfg: ModelConfig,
    params: Params,
    tokens: jnp.ndarray,  # [Bp, T] right-padded TAIL tokens
    lengths: jnp.ndarray,  # [Bp] real tail lengths
    starts: jnp.ndarray,  # [Bp] history length per row (tail begins here)
    kv_cache: KVCache,
    slots: jnp.ndarray,  # [Bp] cache slot per prompt
    kv_view: Optional[int] = None,  # static: attend only to cache[:kv_view]
    return_all_logits: bool = False,  # static: [Bp,T,V] instead of last
    unaligned_int4: bool = False,  # static: arbitrary-parity int4 starts
    stat_rows=None,  # [Bp] bool: with it, the routed layers' counts come last
):
    """Prefill only the TAIL of each prompt against reused history KV.

    The prefix-cache admission path (engine/prefix_cache.py): positions
    ``[0, starts)`` of each row's cache slot already hold KV copied from the
    block pool; this computes the remaining ``lengths`` tokens at global
    positions ``starts + i`` (RoPE included), scatters their KV, and
    attends each tail query to history + the causal part of the tail
    (ops/attention.history_attention).  With ``starts == 0`` it computes
    the same result as ``prefill_into_cache`` — pinned by
    tests/test_prefix_cache.py against that oracle.

    The cache is NOT a carry of the layer scan (ISSUE 26).  Inside the loop
    it is only read, as a loop-invariant operand: each layer slices its own
    (layer, view) rows, lays its fresh tail over positions
    ``[starts, starts + T)`` of that small ``[Bp, view, K, D]`` array (a
    position at or past the view is dropped) and attends to the result —
    the same values at the same positions the cache will hold.  A quantised
    cache lays the QUANTISED tail and its scales over the stored history and
    dequantises the whole view, so a chunk attends to what the cache will
    hold.  The tails leave the scan stacked ``[L, Bp, T, K, D]`` and the
    cache is written ONCE after it, for all layers, in place on the donated
    buffer (as ``prefill_into_cache`` writes).  Carried and scattered in
    every layer, a plane with 4 KV heads was converted between the layout
    the loop keeps it in and the one the scatter wants, whole, there and
    back, in every layer: half the device in ``qwen2-7b.decode-closed``
    (PERF.md section 6, PR 26; tests/test_tpu_compile.py guards it).

    Invariant the carry used to hide: within one dispatch, real rows name
    DISTINCT slots — a row sees the history and its own tail, never another
    row's tail of the same dispatch.  The engine keeps it by construction
    (``_dispatch_segments`` picks one segment a slot from ``_segmented``,
    which is keyed by slot; a prefix-tail row is one admitted request in its
    own slot; spec-verify passes ``arange(b)``).  Padding rows may all name
    the parking row: they write junk there and nothing reads it.

    Scope limits (the engine enforces both):
    - No sequence-parallel path: under an sp>1 mesh the engine disables
      prefix matching entirely, so cache-hit admissions never bypass
      ring/Ulysses attention.  Plain einsum attention here partitions fine
      under tp-only meshes (GSPMD splits the head axes).
    ``kv_view`` mirrors decode_step's: a STATIC python int bounding how
    much of the cache row the attention reads (callers pick the smallest
    power-of-2 bucket covering every row's ``starts + length``), so the
    admission cost of prefix-cache hits and chunked-prefill segments
    tracks the live context, not max_seq (VERDICT r4 item 7 — previously
    this path re-taxed exactly the long prompts it exists to help).
    Writes still target the full cache row.

    int4 page-alignment contract (ISSUE 14): the packed int4 cache IS
    supported, under the alignment the block-paged pool guarantees —
    every ``starts`` value and the padded tail width ``t`` must be EVEN
    (a multiple of the two-tokens-per-byte packing), so the packed write
    covers whole bytes and needs no read-modify-write.  The engine
    enforces this by construction: chunk starts are multiples of
    ``min_prefill_bucket`` (the pool page size) or ``prefill_chunk``,
    both forced even under ``kv_quant="int4"``.  Junk pad positions past
    a row's real length land in high nibbles that decode's RMW append
    overwrites before they are ever attendable (the standard prefill-pad
    argument; see ``prefill_into_cache``).  Spec-verify — the one consumer
    whose starts are arbitrary token positions — passes
    ``unaligned_int4=True`` to route the packed write through
    ``quant.splice_packed_rows`` instead: boundary nibbles are merged in
    registers from gathered covering bytes, so HBM stores stay whole-byte
    and the last ``config_fences`` entry stays dead (ISSUE 17).

    Returns last-real-tail-token logits [Bp, V] and the updated cache, and
    with ``stat_rows`` the routed layers' counts of the real tokens of those
    rows (as ``prefill_into_cache``).
    """
    b, t = tokens.shape
    family = _family_module(cfg)
    if family is not None:
        out = family.chunk_prefill_into_cache(
            cfg, params, tokens, lengths, starts, kv_cache, slots,
            kv_view=kv_view, return_all_logits=return_all_logits,
            stat_rows=stat_rows)
        return out if stat_rows is not None else out[:-1]
    counted = None
    if stat_rows is not None:
        counted = ((jnp.arange(t)[None, :] < lengths[:, None])
                   & stat_rows[:, None])
    quant_mode = kv_cache_quant_mode(kv_cache)
    if quant_mode == "int4" and t % 2 and not unaligned_int4:
        raise ValueError(
            f"packed int4 chunk prefill needs an even (page-aligned) tail "
            f"width, got {t}; the engine pads tails to even buckets"
        )
    # Logical sequence length: the int4 cache's sequence axis is byte-packed.
    s = kv_cache["k"].shape[2] * (2 if quant_mode == "int4" else 1)
    if kv_view is None or kv_view > s:
        kv_view = s
    x, act = stream_in(cfg, _embed(cfg, params, tokens))
    pos = starts[:, None] + jnp.arange(t)[None, :]  # [Bp,T] global positions
    layer_idx = jnp.arange(cfg.n_layers)
    quant = kv_cache_is_quantized(kv_cache)
    rows = slots[:, None]  # [Bp,1] broadcasts against pos [Bp,T]
    place, fresh = tail_placement(kv_view, starts, t)
    # (int4: the packed value planes slice kv_view // 2 BYTE rows and
    # unpack to kv_view tokens.)
    view_rows = kv_view // 2 if quant_mode == "int4" else kv_view
    if quant_mode == "int4":
        from p2p_llm_tunnel_tpu.models.quant import (
            splice_packed_rows,
            unpack_int4,
            write_packed_chunk,
        )

    from p2p_llm_tunnel_tpu.ops.attention import history_attention

    scanned, stacked = split_experts(cfg, params["blocks"])

    def read_view(plane, idx, n):
        return read_cache_view(plane, idx, n, slots)

    def lay(hist, tail):
        return lay_tail(hist, tail, place, fresh)

    def step(x, xs):
        blk, idx = xs
        with jax.named_scope("attn"):
            h, _ = normed(cfg, x, blk["attn_norm"], act)
            q, k, v = _qkv(cfg, blk, h, pos)  # rope at global positions
        k_s = v_s = None
        with jax.named_scope("kv_write"):
            # The tail in the form the cache will hold it.
            if quant_mode == "int4":
                k, k_s = _quant_kv4(k)
                v, v_s = _quant_kv4(v)
            elif quant:
                k, k_s = _quant_kv(k)
                v, v_s = _quant_kv(v)
        with jax.named_scope("kv_read"):
            k_all = read_view(kv_cache["k"], idx, view_rows)
            v_all = read_view(kv_cache["v"], idx, view_rows)
            if quant_mode == "int4":
                k_all = unpack_int4(k_all, axis=1)
                v_all = unpack_int4(v_all, axis=1)
            # The tail over the history: [Bp, view, K, D].
            k_all = lay(k_all, k)
            v_all = lay(v_all, v)
            if quant:
                k_s_all = lay(read_view(kv_cache["k_scale"], idx, kv_view), k_s)
                v_s_all = lay(read_view(kv_cache["v_scale"], idx, kv_view), v_s)
                k_all = (k_all.astype(jnp.float32) * k_s_all[..., None]).astype(act)
                v_all = (v_all.astype(jnp.float32) * v_s_all[..., None]).astype(act)
        with jax.named_scope("attn"):
            attn = history_attention(
                q, k_all, v_all, starts,
                scale=cfg.query_scale,
                softcap=cfg.attn_softcap,
                window=_layer_window(cfg, idx, kv_view),
                block=cfg.block_length,
            )
            attn = _proj(cfg, attn.reshape(b, t, -1), blk["wo"])
            if cfg.post_norms:
                attn = _norm(cfg, attn, blk["post_attn_norm"])
            x = x + attn
        with jax.named_scope("ffn"):
            h, h32 = normed(cfg, x, blk["mlp_norm"], act)
            mlp, stats = _mlp(cfg, blk, h, counted, stacked, idx, h32)
            if cfg.post_norms:
                mlp = _norm(cfg, mlp, blk["post_mlp_norm"])
            x = x + mlp
        return x, (k, v, k_s, v_s, stats)

    x, (ks, vs, k_ss, v_ss, stats) = jax.lax.scan(
        step, x, (scanned, layer_idx)
    )
    # One write for all layers, [L,Bp,T,K,D] into the donated cache.
    new_cache = dict(kv_cache)
    with jax.named_scope("kv_write"):
        if quant_mode == "int4":
            # Whole-byte writes either way (see the docstring contract).
            if unaligned_int4:
                new_cache["k"] = splice_packed_rows(
                    kv_cache["k"], slots, starts, ks)
                new_cache["v"] = splice_packed_rows(
                    kv_cache["v"], slots, starts, vs)
            else:
                # starts is even, so byte i of the write holds exactly
                # tokens (starts + 2i, starts + 2i + 1).
                bpos = starts[:, None] // 2 + jnp.arange(t // 2)[None, :]
                new_cache["k"] = write_packed_chunk(
                    kv_cache["k"], rows, bpos, ks)
                new_cache["v"] = write_packed_chunk(
                    kv_cache["v"], rows, bpos, vs)
        else:
            new_cache["k"] = kv_cache["k"].at[:, rows, pos].set(ks)
            new_cache["v"] = kv_cache["v"].at[:, rows, pos].set(vs)
        if quant:
            new_cache["k_scale"] = kv_cache["k_scale"].at[:, rows, pos].set(k_ss)
            new_cache["v_scale"] = kv_cache["v_scale"].at[:, rows, pos].set(v_ss)
    with jax.named_scope("head_sample"):
        x = _norm(cfg, x, params["final_norm"]).astype(act)
        logits = _logits(cfg, params, x)  # [Bp,T,V]
    tail = (_moe_total(stats),) if stat_rows is not None else ()
    if return_all_logits:
        # Speculative verify (engine spec_ngram): every position's logits
        # decide how many proposed tokens survive.
        return (logits, new_cache) + tail
    last = jnp.take_along_axis(
        logits, (lengths - 1)[:, None, None], axis=1
    )[:, 0]
    return (last, new_cache) + tail


def spec_verify_into_cache(
    cfg: ModelConfig,
    params: Params,
    tokens: jnp.ndarray,  # [B, T] carry token + K proposals per slot
    positions: jnp.ndarray,  # [B] global position of tokens[:, 0]
    kv_cache: KVCache,
    kv_view: Optional[int] = None,  # static: attend only to cache[:kv_view]
) -> Tuple[jnp.ndarray, KVCache]:
    """Speculative draft-verify burst: T = 1 + K positions per slot in ONE
    forward pass (ISSUE 17), so a verify burst costs one weight-stream pass
    instead of T decode steps.  Rejected-tail KV is junk PAST every accepted
    position, rewritten by the row's next burst before any query can attend
    it (all masks are strictly ``< pos``), so acceptance needs no cache
    rollback.

    It is the chunk prefill path with ``unaligned_int4=True``: spec starts
    are arbitrary token positions, so packed int4 writes ride
    ``quant.splice_packed_rows`` (covering-byte gather → nibble merge →
    whole-byte scatter) instead of the page-aligned scatter — the write
    discipline that lets spec_ngram run under ``kv-int4`` with the
    ``config_fences`` registry EMPTY.

    Inactive slots park at ``positions >= kv_view`` and compute junk
    (gathers clamp, scatters drop), masked by the engine.  Returns
    (logits [B, T, V], updated cache).
    """
    b, t = tokens.shape
    lengths = jnp.full((b,), t, jnp.int32)
    return chunk_prefill_into_cache(
        cfg, params, tokens, lengths, positions, kv_cache,
        jnp.arange(b), kv_view=kv_view, return_all_logits=True,
        unaligned_int4=True,
    )


def ragged_prefill_into_cache(
    cfg: ModelConfig,
    params: Params,
    tokens: jnp.ndarray,  # [TOT] flat-packed tail tokens (pads = 0)
    slot_of: jnp.ndarray,   # [NQB] per-q-block descriptors
    start_of: jnp.ndarray,  # (ops/pallas_prefill_attention.plan_ragged_group;
    qoff_of: jnp.ndarray,   # its qlen_of output is caller bookkeeping only)
    base_of: jnp.ndarray,
    sample_idx: jnp.ndarray,  # [R] flat index of each row's last real token
    kv_cache: KVCache,
    block_q: int,  # static: the planner's q-block width
    max_row_blocks: int = 0,  # static: widest per-row tail in blocks
    return_all_logits: bool = False,  # static: [TOT,V] instead of rows
    interpret: Optional[bool] = None,  # static: None = cfg.flash_interpret
):
    """Ragged GROUPED prefill (ISSUE 15): one launch per admission group.

    The ragged twin of :func:`chunk_prefill_into_cache` — the group's
    variable-length tail segments ride ONE flat token axis (no per-row
    pad bucket), and per layer a single Pallas program
    (``ops/pallas_prefill_attention.ragged_prefill_attention``) performs
    rope, KV quantization into the cache precision, the cache append as
    an aliased in-place block write (no XLA scatter), and causal flash
    attention over each row's cache prefix + its own tail — the cache
    read is frontier-clamped per row, so there is NO static ``kv_view``
    argument and no per-(tail, view) program family: one compiled
    program per flat-bucket length serves every group shape
    (engine.warmup_plan's collapse).

    Alignment contract (the planner enforces it): every row's ``start``
    is a ``block_q`` multiple — chunk starts are page or segment
    multiples — which under ``kv_quant="int4"`` makes every packed write
    whole-byte (ISSUE 14).  Numerics: the kernel quantize→dequantize
    ROUNDTRIPS each tail block before attending, exactly as this module's
    chunk path attends through the cache it just wrote, so the two paths
    stay token-identical (pinned in tests/test_ragged_prefill.py).

    Returns ``(logits [R, V], cache')`` — logits of each row's last real
    tail token (junk for pad rows whose ``sample_idx`` is 0), or
    ``[TOT, V]`` with ``return_all_logits`` (the golden-anchor and
    scoring harness path).
    """
    from p2p_llm_tunnel_tpu.ops.pallas_prefill_attention import (
        ragged_prefill_attention,
    )

    tot = tokens.shape[0]
    quant_mode = kv_cache_quant_mode(kv_cache)
    quant = quant_mode is not None
    s = kv_cache["k"].shape[2] * (2 if quant_mode == "int4" else 1)
    if interpret is None:
        interpret = cfg.flash_interpret
    x = _embed(cfg, params, tokens[None])  # [1, TOT, Dm]
    layer_idx = jnp.arange(cfg.n_layers)

    def step(carry, xs):
        x, cache = carry
        blk, idx = xs
        h = _norm(cfg, x, blk["attn_norm"])
        q, k, v = _qkv_proj(cfg, blk, h)  # PRE-rope: the kernel ropes
        attn, ck, cv, k_s, v_s = ragged_prefill_attention(
            q[0], k[0], v[0],
            cache["k"], cache["v"],
            cache.get("k_scale"), cache.get("v_scale"),
            slot_of, start_of, qoff_of, base_of, idx,
            block_q=block_q,
            max_row_blocks=max_row_blocks,
            rope_theta=cfg.rope_theta,
            kv_quant=quant_mode,
            scale=cfg.query_scale,
            softcap=cfg.attn_softcap,
            window=_layer_window(cfg, idx, s),
            interpret=interpret,
        )
        cache = dict(cache)
        cache["k"], cache["v"] = ck, cv
        if quant:
            cache["k_scale"], cache["v_scale"] = k_s, v_s
        attn = mm(attn.reshape(1, tot, -1), blk["wo"], cfg.act_quant)
        if cfg.post_norms:
            attn = _norm(cfg, attn, blk["post_attn_norm"])
        x = x + attn
        h = _norm(cfg, x, blk["mlp_norm"])
        mlp, _ = _mlp(cfg, blk, h)
        if cfg.post_norms:
            mlp = _norm(cfg, mlp, blk["post_mlp_norm"])
        x = x + mlp
        return (x, cache), None

    (x, new_cache), _ = jax.lax.scan(
        step, (x, dict(kv_cache)), (params["blocks"], layer_idx)
    )
    x = _norm(cfg, x, params["final_norm"])
    if return_all_logits:
        return _logits(cfg, params, x)[0], new_cache  # [TOT, V]
    # Only the sampled rows' logits: the lm_head matmul is the widest in
    # the model, and computing it over every flat token would tax exactly
    # the pad-free win the ragged layout buys.
    rows = x[0][sample_idx][None]  # [1, R, Dm]
    return _logits(cfg, params, rows)[0], new_cache


# ---------------------------------------------------------------------------
# decode
# ---------------------------------------------------------------------------

def decode_step(
    cfg: ModelConfig,
    params: Params,
    kv_cache: KVCache,
    tokens: jnp.ndarray,  # [B] one token per slot
    positions: jnp.ndarray,  # [B] where this token goes in the cache
    kv_view: Optional[int] = None,  # static: attend only to cache[:kv_view]
    mesh=None,  # Mesh when params/cache are sharded (gates the flash path)
    with_stats: bool = False,  # static: the routed layers' counts come last
):
    """One decode step over every slot. Returns (logits [B,V], new cache),
    and under ``with_stats`` what the routed layers counted (models/moe.py
    STATS) of the rows that are not parked at ``positions >= S``.

    Static shapes throughout: inactive slots still compute (masked out by the
    engine when sampling) — the XLA-friendly cost of continuous batching.

    The cache is CARRIED through the layer scan and updated with per-token
    in-place writes (XLA keeps dynamic-update-slice on a loop carry in
    place).  The previous xs→ys formulation logically rewrote the whole
    cache every step — ~2.2 GB/step of pure HBM write traffic at 8B/512
    that this layout eliminates (r4 perf round, VERDICT Weak #1).

    ``kv_view`` (a STATIC python int) bounds how much of the cache the
    einsum reads: callers pick the smallest power-of-2 bucket covering
    every active slot's length, so KV read traffic follows actual context
    length instead of max_seq — the long-context lever (VERDICT item 4).
    Writes still target the full cache, so growing into a bigger bucket
    later reads exactly what was written.

    Where ``decode_attention_branch`` answers ``"pallas-rows"`` (ISSUE 33:
    the TPU, the plain bf16 cache) the read is one kernel over the stacked
    cache that stops at each row's own position: no plane is sliced out,
    ``kv_view`` bounds nothing, and the engine compiles one view.
    """
    b = tokens.shape[0]
    family = _family_module(cfg)
    if family is not None:
        out = family.decode_step(cfg, params, kv_cache, tokens, positions,
                                 kv_view=kv_view, mesh=mesh)
        return out if with_stats else out[:-1]
    quant_mode = kv_cache_quant_mode(kv_cache)
    quant = quant_mode is not None
    # Logical sequence length: the int4 cache's sequence axis is byte-packed.
    s = kv_cache["k"].shape[2] * (2 if quant_mode == "int4" else 1)
    if kv_view is None or kv_view > s:
        kv_view = s
    x = _embed(cfg, params, tokens[:, None])  # [B,1,Dm]
    pos2d = positions[:, None]  # [B,1]
    counted = pos2d < s if with_stats else None
    layer_idx = jnp.arange(cfg.n_layers)
    slot_ids = jnp.arange(b)

    use_rows = decode_attention_branch(
        cfg, mesh, kv_view, quant_mode, s) == "pallas-rows"
    if use_rows:
        from p2p_llm_tunnel_tpu.ops.pallas_decode_attention import (
            decode_attention_rows,
            decode_rows_worklist,
            rows_block,
        )

        # Reads the stacked cache where it lies, each row up to its own
        # position: kv_view bounds nothing here, and nothing is sliced.
        block = rows_block(s, cfg.n_kv_heads)
        work = decode_rows_worklist(positions, s, block)

    if quant_mode == "int4":
        from p2p_llm_tunnel_tpu.models.quant import (
            append_packed_token,
            unpack_int4,
        )

    def view_attention(q, cache, idx):
        """The layer's plane cut to the view, then attended."""
        with jax.named_scope("kv_read"):
            # ONE dynamic_slice for (layer, view-prefix): slicing the layer out
            # first and sub-slicing after makes XLA materialize the full-length
            # layer before the view cut — the one slice reads only view bytes.
            view_rows = kv_view // 2 if quant_mode == "int4" else kv_view
            view_shape = (1, b, view_rows, cfg.n_kv_heads, cfg.head_dim)
            zero = jnp.zeros((), idx.dtype)
            start = (idx, zero, zero, zero, zero)
            k_l = jax.lax.dynamic_slice(cache["k"], start, view_shape)[0]
            v_l = jax.lax.dynamic_slice(cache["v"], start, view_shape)[0]
            if quant:
                # Dequant fuses into the attention einsum's operand read:
                # int8 bytes cross HBM, bf16 never materializes (same
                # fusion the int8 weights rely on — PERF.md).
                sc_shape = (1, b, kv_view, cfg.n_kv_heads)
                k_s = jax.lax.dynamic_slice(
                    cache["k_scale"], start[:4], sc_shape)[0]
                v_s = jax.lax.dynamic_slice(
                    cache["v_scale"], start[:4], sc_shape)[0]
                if quant_mode == "int4":
                    k_l = unpack_int4(k_l, axis=1)
                    v_l = unpack_int4(v_l, axis=1)
                k_l = (k_l.astype(jnp.float32)
                       * k_s[..., None]).astype(q.dtype)
                v_l = (v_l.astype(jnp.float32)
                       * v_s[..., None]).astype(q.dtype)
        with jax.named_scope("attn"):
            return cached_attention(
                q, k_l, v_l, positions,
                scale=cfg.query_scale,
                softcap=cfg.attn_softcap,
                window=_layer_window(cfg, idx, s),
            )

    def step(carry, xs):
        x, cache = carry
        blk, idx = xs
        with jax.named_scope("attn"):
            h = _norm(cfg, x, blk["attn_norm"])
            q, k, v = _qkv(cfg, blk, h, pos2d)  # q [B,1,H,D], k/v [B,1,K,D]
        cache = dict(cache)
        with jax.named_scope("kv_write"):
            if quant_mode == "int4":
                kq, k_s = _quant_kv4(k[:, 0])
                vq, v_s = _quant_kv4(v[:, 0])
                # Packed nibble read-modify-write via quant.append_packed_token
                # (the TC19 commit point): the new token shares a byte with its
                # sequence neighbour, whose nibble must survive (for odd
                # positions it holds the PREVIOUS token's real value).  Parked
                # rows (pos >= s) rely on the same OOB semantics as the int8
                # path: the gather clamps (value unused) and the scatter drops
                # the write.
                cache["k"] = append_packed_token(
                    cache["k"], idx, slot_ids, positions, kq)
                cache["v"] = append_packed_token(
                    cache["v"], idx, slot_ids, positions, vq)
                cache["k_scale"] = (
                    cache["k_scale"].at[idx, slot_ids, positions].set(k_s)
                )
                cache["v_scale"] = (
                    cache["v_scale"].at[idx, slot_ids, positions].set(v_s)
                )
            elif quant:
                kq, k_s = _quant_kv(k[:, 0])
                vq, v_s = _quant_kv(v[:, 0])
                cache["k"] = cache["k"].at[idx, slot_ids, positions].set(kq)
                cache["v"] = cache["v"].at[idx, slot_ids, positions].set(vq)
                cache["k_scale"] = (
                    cache["k_scale"].at[idx, slot_ids, positions].set(k_s)
                )
                cache["v_scale"] = (
                    cache["v_scale"].at[idx, slot_ids, positions].set(v_s)
                )
            else:
                cache["k"] = cache["k"].at[idx, slot_ids, positions].set(k[:, 0])
                cache["v"] = cache["v"].at[idx, slot_ids, positions].set(v[:, 0])
        if use_rows:
            with jax.named_scope("attn"):
                attn = decode_attention_rows(
                    q[:, 0], cache["k"], cache["v"], idx, work,
                    block=block,
                    scale=cfg.query_scale,
                    softcap=cfg.attn_softcap,
                    window=_layer_window(cfg, idx, s),
                    interpret=cfg.flash_interpret,
                )
        else:
            attn = view_attention(q, cache, idx)
        with jax.named_scope("attn"):
            attn = mm(attn.reshape(b, 1, -1), blk["wo"], cfg.act_quant)
            if cfg.post_norms:
                attn = _norm(cfg, attn, blk["post_attn_norm"])
            x = x + attn
        with jax.named_scope("ffn"):
            h = _norm(cfg, x, blk["mlp_norm"])
            mlp, stats = _mlp(cfg, blk, h, counted)
            if cfg.post_norms:
                mlp = _norm(cfg, mlp, blk["post_mlp_norm"])
            x = x + mlp
        return (x, cache), stats

    (x, new_cache), stats = jax.lax.scan(
        step,
        (x, dict(kv_cache)),
        (params["blocks"], layer_idx),
    )
    with jax.named_scope("head_sample"):
        x = _norm(cfg, x, params["final_norm"])
        logits = _logits(cfg, params, x)[:, 0]  # [B,V]
    if with_stats:
        return logits, new_cache, _moe_total(stats)
    return logits, new_cache


# ---------------------------------------------------------------------------
# training-style objective (used by __graft_entry__.dryrun_multichip)
# ---------------------------------------------------------------------------

def loss_fn(
    cfg: ModelConfig,
    params: Params,
    tokens: jnp.ndarray,  # [B,T]
    targets: jnp.ndarray,  # [B,T]
    valid: jnp.ndarray,  # [B,T]
) -> jnp.ndarray:
    logits, _, _ = prefill(cfg, params, tokens, valid)
    logp = jax.nn.log_softmax(logits, axis=-1)
    nll = -jnp.take_along_axis(logp, targets[..., None], axis=-1)[..., 0]
    return (nll * valid).sum() / jnp.maximum(valid.sum(), 1)
