"""Plain float32 reference of the family that generates by masked denoising
over blocks (``SDAR-30B-A3B-Chat``: a Qwen3-MoE body under a block-causal
mask), whole or as one pipeline stage's layers.

This is a model family's module (benchmarks/README.md, "A model family"): a
configuration names it with ``"reference": "block_diffusion_reference"``.
It imports nothing of the program, and knows no cache, no pool, no batch and
no pass: it computes, for every position of a sequence, the distribution
the serve path's definition gives that position.

Equations, from the published ``config.json`` of JetLM/SDAR-30B-A3B-Chat
(``model_type`` ``sdar_moe``); what the config leaves open is this family's
convention, listed in the configuration's ``assumed``.  ``h`` the RMS-normed
input of a layer (``attn_norm`` / ``mlp_norm`` / final norm weights are ones
and not stored, eps ``rms_norm_eps``):

- ``q = W_q h`` [H, head_dim], ``k = W_k h``, ``v = W_v h`` [K, head_dim], no
  bias; ``q <- RMSNorm_wq(q)``, ``k <- RMSNorm_wk(k)`` over each head's
  ``head_dim`` columns with a learned weight a column shared by the heads
  (Qwen3's QK norm, before rope); rope ``rope_theta``, rotate-half over all
  columns, at the token's absolute position; ``H / K`` query heads share a
  KV head.
- ``s_ij = q_i . k_j / sqrt(head_dim)``; **allowed(i, j) = j // B <= i // B**
  (whole earlier blocks and the own block, both directions inside it), ``B``
  the configuration's ``block_length``; float32 softmax; ``W_o``; residual.
- every layer routed (``decoder_sparse_step`` 1, ``mlp_only_layers`` []):
  ``p = softmax(W_r h)`` over all ``num_experts`` in float32; the
  ``num_experts_per_tok`` largest; weights ``p_e`` over their sum
  (``norm_topk_prob``); ``y = sum_e w_e W_down,e (silu(W_gate,e h) *
  W_up,e h)``, width ``moe_intermediate_size``; no shared expert;
  ``intermediate_size`` is read by nothing.  A loop over the experts, every
  one over every token, masked by its weight.
- final RMSNorm, untied head.

**What row ``p`` of ``forward_logprobs`` is.**  The log-softmax for position
``q = p + 1`` (offset ``r = q mod B`` of its block, group ``g = r // k``,
``k = B / denoising_steps``): the logits AT ``q`` (in place: the logits at a
position decide that position) of a forward in which ``q``'s block holds its
true tokens at offsets ``< g * k`` and ``mask_token_id`` from there on, over
clean earlier blocks.  It is a function of ``tokens[..p]`` alone.  For each
``g`` one forward over the clean sequence and its noisy copy ``[clean ;
noisy_g]``: a clean position sees clean blocks block-causally; a noisy
position sees the clean blocks BEFORE its own and its own noisy block.  The
clean stream is the same in every ``g`` and is computed once: the streams
go through the layers side by side.

``make_weights`` is the benchmark's own statement of how a seed becomes the
model the program serves (``models/transformer.init_params`` with
``models/moe.init_moe_blocks(per_expert=True)``): truncated normal on [-2,
2] times ``fan_in**-0.5`` rounded to bfloat16, the key split twelve ways:
``W_q, W_k, W_v, W_o`` parts 0-3 (stacked over layers), the embedding part
7, the router part 8, expert ``e`` of layer ``i`` from ``fold_in(fold_in(
part 9/10/11, i), e)``; the QK norm weights ``1 + 0.1 * normal`` of
``fold_in(key, 60)`` and ``61``, the head ``fold_in(key, 99)``.

The head runs once, over the hidden state each position's own stream gives
it, and an expert at a time, so that seven layers at published widths and a
sequence of 1,280 tokens fit one chip beside the weights.
"""

from __future__ import annotations

import functools
from typing import Any, Dict, Optional

import jax
import jax.numpy as jnp

from benchmarks.correctness import TYPE_BYTES

REQUIRED_KEYS = ("num_attention_heads", "num_key_value_heads", "head_dim",
                 "moe_intermediate_size", "num_experts",
                 "num_experts_per_tok", "block_length", "denoising_steps",
                 "mask_token_id")

QK_NORM_STD = 0.1


class _Frozen(dict):
    """A dict usable as a static (hashable) argument of ``jax.jit``."""

    def __hash__(self):  # type: ignore[override]
        return hash(tuple(sorted(self.items())))


def shapes_of(config: Dict[str, Any]) -> Dict[str, Any]:
    unsupported = [
        key for key, want in (
            ("norm_topk_prob", True), ("attention_bias", False),
            ("decoder_sparse_step", 1), ("mlp_only_layers", []),
            ("tie_word_embeddings", False), ("rope_scaling", None),
            ("use_sliding_window", False))
        if config.get(key, want) != want]
    if unsupported:
        raise ValueError(f"this family's reference has no {unsupported}")
    block, steps = int(config["block_length"]), int(config["denoising_steps"])
    if block % steps:
        raise ValueError(f"{steps} steps do not divide a block of {block}")
    return {
        "layers": int(config["num_hidden_layers"]),
        "dim": int(config["hidden_size"]),
        "heads": int(config["num_attention_heads"]),
        "kv": int(config["num_key_value_heads"]),
        "dk": int(config["head_dim"]),
        "theta": float(config["rope_theta"]),
        "expert_ffn": int(config["moe_intermediate_size"]),
        "experts": int(config["num_experts"]),
        "top_k": int(config["num_experts_per_tok"]),
        "vocab": int(config["vocab_size"]),
        "eps": float(config["rms_norm_eps"]),
        "block": block,
        "group": block // steps,
        "mask": int(config["mask_token_id"]),
    }


def cache_bytes_per_token(config: Dict[str, Any]) -> int:
    """A token's keys and values of every held layer, in the type the
    configuration states for the cache."""
    return int(int(config["num_hidden_layers"]) * 2
               * int(config["num_key_value_heads"]) * int(config["head_dim"])
               * TYPE_BYTES[config["precision"]["kv_cache"]])


# ---- the model of a seed ------------------------------------------------------

def _draw(key, shape, fan_in):
    w = jax.random.truncated_normal(key, -2, 2, shape, jnp.float32)
    return (w * fan_in ** -0.5).astype(jnp.bfloat16)


def _make_weights(s, key):
    l, dm, h, kv, dk = s["layers"], s["dim"], s["heads"], s["kv"], s["dk"]
    e, fe, v = s["experts"], s["expert_ffn"], s["vocab"]
    keys = jax.random.split(key, 12)

    def experts(k, shape, fan_in):
        def one(i):
            return _draw(jax.random.fold_in(jax.random.fold_in(k, i // e),
                                            i % e), shape, fan_in)

        return jax.lax.map(one, jnp.arange(l * e)).reshape((l, e) + shape)

    def about_one(part):
        return (1.0 + QK_NORM_STD * jax.random.normal(
            jax.random.fold_in(key, part), (l, dk), jnp.float32)
        ).astype(jnp.bfloat16)

    return {
        "embed": _draw(keys[7], (v, dm), dm),
        "lm_head": _draw(jax.random.fold_in(key, 99), (dm, v), dm),
        "layers": {
            "wq": _draw(keys[0], (l, dm, h * dk), dm),
            "wk": _draw(keys[1], (l, dm, kv * dk), dm),
            "wv": _draw(keys[2], (l, dm, kv * dk), dm),
            "wo": _draw(keys[3], (l, h * dk, dm), h * dk),
            "q_norm": about_one(60),
            "k_norm": about_one(61),
            "router": _draw(keys[8], (l, dm, e), dm),
            "gate": experts(keys[9], (dm, fe), dm),
            "up": experts(keys[10], (dm, fe), dm),
            "down": experts(keys[11], (fe, dm), fe),
        },
    }


def make_weights(shapes: Dict[str, Any], seed: int) -> Dict[str, Any]:
    """The model of ``seed``, bfloat16 values, in one jitted call."""
    build = jax.jit(functools.partial(_make_weights, _Frozen(shapes)))
    return build(jax.random.PRNGKey(int(seed)))


# ---- the forward --------------------------------------------------------------

def _wide(w, bits: Optional[int], axis: int = -2):
    """A weight in float32; under ``bits`` each output channel rounded onto
    a symmetric grid of that many bits (``axis`` is the contracted one)."""
    w = w.astype(jnp.float32)
    if bits is None:
        return w
    top = float(2 ** (bits - 1) - 1)
    scale = jnp.max(jnp.abs(w), axis=axis, keepdims=True) / top
    return jnp.round(w / jnp.maximum(scale, 1e-30)) * scale


def rms_norm(x, eps, w=None):
    y = x / jnp.sqrt(jnp.mean(x * x, axis=-1, keepdims=True) + eps)
    return y if w is None else y * w.astype(jnp.float32)


def rope(x, positions, theta):
    """x [T, heads, D]: rotate-half pairs (column i with i + D / 2)."""
    d = x.shape[-1]
    freqs = 1.0 / theta ** (jnp.arange(0, d, 2, dtype=jnp.float32) / d)
    ang = positions.astype(jnp.float32)[:, None] * freqs
    cos, sin = jnp.cos(ang)[:, None, :], jnp.sin(ang)[:, None, :]
    a, b = x[..., : d // 2], x[..., d // 2:]
    return jnp.concatenate([a * cos - b * sin, b * cos + a * sin], -1)


def _qkv(s, lw, x, positions, bits):
    t = x.shape[0]
    h, kv, dk = s["heads"], s["kv"], s["dk"]
    hin = rms_norm(x, s["eps"])
    q = (hin @ _wide(lw["wq"], bits)).reshape(t, h, dk)
    k = (hin @ _wide(lw["wk"], bits)).reshape(t, kv, dk)
    v = (hin @ _wide(lw["wv"], bits)).reshape(t, kv, dk)
    q = rope(rms_norm(q, s["eps"], lw["q_norm"]), positions, s["theta"])
    k = rope(rms_norm(k, s["eps"], lw["k_norm"]), positions, s["theta"])
    return q.reshape(t, kv, h // kv, dk), k, v


def _attend(s, q, parts):
    """q [T, K, G, D] against ``parts``: [(k, v, seen [T, S])], one softmax
    over all of them."""
    scores = [jnp.where(seen[None, None],
                        jnp.einsum("tkgd,skd->kgts", q, k) * s["dk"] ** -0.5,
                        -jnp.inf) for k, _v, seen in parts]
    top = functools.reduce(jnp.maximum,
                           [sc.max(-1, keepdims=True) for sc in scores])
    es = [jnp.exp(sc - top) for sc in scores]
    denom = sum(e.sum(-1, keepdims=True) for e in es)
    out = sum(jnp.einsum("kgts,skd->tkgd", e / denom, v)
              for e, (_k, v, _seen) in zip(es, parts))
    return out.reshape(q.shape[0], -1)


def routed(s, lw, h, bits):
    p = jax.nn.softmax(h @ _wide(lw["router"], bits), axis=-1)  # [T, E]
    top_w, top_i = jax.lax.top_k(p, s["top_k"])
    top_w = top_w / top_w.sum(-1, keepdims=True)
    weight = (jax.nn.one_hot(top_i, s["experts"]) * top_w[..., None]).sum(-2)

    def one(e, out):
        at = functools.partial(jax.lax.dynamic_index_in_dim, index=e, axis=0,
                               keepdims=False)
        y = (jax.nn.silu(h @ _wide(at(lw["gate"]), bits))
             * (h @ _wide(at(lw["up"]), bits))) @ _wide(at(lw["down"]), bits)
        return out + jax.lax.dynamic_index_in_dim(weight, e, axis=1) * y

    return jax.lax.fori_loop(0, s["experts"], one, jnp.zeros_like(h))


def _layer_of(group, i):
    return {k: jax.lax.dynamic_index_in_dim(v, i, 0, keepdims=False)
            for k, v in group.items()}


@functools.lru_cache(maxsize=None)
def _program(s: "_Frozen", bits: Optional[int]):
    block, group, steps = s["block"], s["group"], s["block"] // s["group"]

    def forward(w, tokens):
        with jax.default_matmul_precision("highest"):
            # one block more than the sequence: row T - 1 is position T's
            tokens = jnp.concatenate(
                [tokens, jnp.zeros((block,), tokens.dtype)])
            t = tokens.shape[0]
            pos = jnp.arange(t)
            blk, off = pos // block, pos % block
            earlier = blk[None, :] < blk[:, None]
            own = blk[None, :] == blk[:, None]

            def embed(ids):
                return _wide(w["embed"][ids], bits, -1)

            # stream 0 the clean sequence, stream 1 + g its noisy copy of
            # group g
            xs = jnp.stack([embed(tokens)] + [
                embed(jnp.where(off < g * group, tokens, s["mask"]))
                for g in range(steps)])

            def layer(i, xs):
                lw = _layer_of(w["layers"], i)
                qkv = [_qkv(s, lw, x, pos, bits) for x in xs]
                _qc, kc, vc = qkv[0]
                outs = [_attend(s, qkv[0][0], [(kc, vc, earlier | own)])]
                for q, k, v in qkv[1:]:
                    outs.append(_attend(
                        s, q, [(kc, vc, earlier), (k, v, own)]))
                wo = _wide(lw["wo"], bits)
                xs = xs + jnp.stack([o @ wo for o in outs])
                flat = xs.reshape(-1, xs.shape[-1])
                flat = flat + routed(s, lw, rms_norm(flat, s["eps"]), bits)
                return flat.reshape(xs.shape)

            xs = jax.lax.fori_loop(0, s["layers"], layer, xs)
            # position q is decided by its group's stream
            mine = jnp.take_along_axis(
                xs[1:], (off // group)[None, :, None], axis=0)[0]
            logits = rms_norm(mine, s["eps"]) @ _wide(w["lm_head"], bits)
            return jax.nn.log_softmax(logits[1: t - block + 1], axis=-1)

    return jax.jit(forward)


def forward_logprobs(shapes: Dict[str, Any], weights: Dict[str, Any], tokens,
                     weight_bits: Optional[int] = None) -> jnp.ndarray:
    """[T, vocab]: row ``p`` the distribution of position ``p + 1`` by the
    definition above.  ``weight_bits`` None is the model as the
    configuration states it; a number is the control: the same arithmetic
    on weights rounded to that many bits."""
    tokens = jnp.asarray(tokens, jnp.int32)
    if tokens.shape[0] % int(shapes["block"]):
        raise ValueError(f"{tokens.shape[0]} tokens are not whole blocks of "
                         f"{shapes['block']} (the reference's process pads "
                         "a sequence to a multiple of 256)")
    bits = None if weight_bits is None else int(weight_bits)
    return _program(_Frozen(shapes), bits)(weights, tokens)
