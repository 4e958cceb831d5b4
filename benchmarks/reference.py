"""Plain float32 reference forward for the dense decoder family the cells run.

Written from the published equations of Mistral-7B-v0.1 and Qwen2-7B (the
Hugging Face ``modeling_mistral`` / ``modeling_qwen2`` descriptions): token
embedding, then per layer RMSNorm -> grouped-query attention with rotary
position embeddings (rotate-half pairing), causal mask and an optional
sliding window, optional additive bias on the Q/K/V projections -> residual
-> RMSNorm -> SwiGLU feed-forward -> residual; a final RMSNorm and an untied
output head.  No cache, no kernels, no batching: one sequence, every
position attends over the whole prefix, everything in float32 under
``jax.default_matmul_precision("highest")``.

It imports nothing of the program.  Its inputs come from the seed alone:
``make_weights`` is the benchmark's own statement of how a seed becomes a
model — int8 integers drawn uniformly from [-127, 127] under a
per-output-channel scale of ``fan_in**-0.5 / 127`` — which is also how the
program's random initialisation reads a seed today.  If the program ever
draws or scales differently, or serves other numbers than these, the
comparison in ``correctness.py`` fails, which is the point.

Shapes come from the configuration file's published keys
(``hidden_size``, ``num_hidden_layers``, ...), never from a preset of the
program.

This is the dense family's module (benchmarks/README.md, "A model family"):
a configuration that names no ``reference`` gets this one.
"""

from __future__ import annotations

import functools
import math
from typing import Any, Dict, Optional

import jax
import jax.numpy as jnp

from benchmarks.correctness import TYPE_BYTES

#: What the int8 draw spans; the scale divides by it.
_QMAX = 127.0

#: The published keys ``shapes_of`` and ``cache_bytes_per_token`` need as
#: whole numbers, beside the three every family has (``hidden_size``,
#: ``num_hidden_layers``, ``vocab_size``).
REQUIRED_KEYS = ("num_attention_heads", "num_key_value_heads",
                 "intermediate_size")


def shapes_of(config: Dict[str, Any]) -> Dict[str, Any]:
    """The sizes the reference needs, read from a configuration file's
    published keys."""
    heads = int(config["num_attention_heads"])
    dim = int(config["hidden_size"])
    return {
        "layers": int(config["num_hidden_layers"]),
        "dim": dim,
        "heads": heads,
        "kv_heads": int(config["num_key_value_heads"]),
        "head_dim": int(config.get("head_dim") or dim // heads),
        "ffn": int(config["intermediate_size"]),
        "vocab": int(config["vocab_size"]),
        "theta": float(config["rope_theta"]),
        "eps": float(config["rms_norm_eps"]),
        # Qwen2's file carries a window it does not use
        "window": (int(config["sliding_window"])
                   if config.get("sliding_window")
                   and config.get("use_sliding_window", True) else None),
        # Qwen2's Q/K/V bias is part of its architecture, not a key
        "qkv_bias": bool(config.get("attention_bias",
                                    config.get("model_type") == "qwen2")),
    }


def cache_bytes_per_token(config: Dict[str, Any]) -> int:
    """Bytes of K and V that one cached token takes in every layer, in the
    type the configuration states for the cache."""
    s = shapes_of(config)
    return int(s["layers"] * 2 * s["kv_heads"] * s["head_dim"]
               * TYPE_BYTES[config["precision"]["kv_cache"]])


def _draw(key, shape, fan_in: int, scale_len_axes):
    q = jax.random.randint(key, shape, -127, 128, jnp.int8)
    scale = jnp.full(scale_len_axes, (fan_in ** -0.5) / _QMAX, jnp.float32)
    return {"q": q, "scale": scale}


def _make_weights(s: Dict[str, Any], seed_key) -> Dict[str, Any]:
    l, dm, h, kh, hd, f, v = (s["layers"], s["dim"], s["heads"],
                              s["kv_heads"], s["head_dim"], s["ffn"],
                              s["vocab"])
    keys = jax.random.split(seed_key, 8)
    w = {
        "wq": _draw(keys[0], (l, dm, h * hd), dm, (l, h * hd)),
        "wk": _draw(keys[1], (l, dm, kh * hd), dm, (l, kh * hd)),
        "wv": _draw(keys[2], (l, dm, kh * hd), dm, (l, kh * hd)),
        "wo": _draw(keys[3], (l, h * hd, dm), h * hd, (l, dm)),
        "w_gate": _draw(keys[4], (l, dm, f), dm, (l, f)),
        "w_up": _draw(keys[5], (l, dm, f), dm, (l, f)),
        "w_down": _draw(keys[6], (l, f, dm), f, (l, dm)),
        # one scale per row of the table: a token's row is q[id] * scale[id]
        "embed": _draw(keys[7], (v, dm), dm, (v,)),
        "lm_head": _draw(jax.random.fold_in(seed_key, 99), (dm, v), dm, (v,)),
    }
    if s["qkv_bias"]:
        bkey = jax.random.fold_in(seed_key, 77)
        for name, k, n in (("bq", bkey, h * hd),
                           ("bk", jax.random.fold_in(bkey, 1), kh * hd),
                           ("bv", jax.random.fold_in(bkey, 2), kh * hd)):
            b = jax.random.normal(k, (l, n), jnp.float32) * dm ** -0.5
            # biases are published in bfloat16; the values ARE the bf16 ones
            w[name] = b.astype(jnp.bfloat16).astype(jnp.float32)
    return w


def make_weights(shapes: Dict[str, Any], seed: int) -> Dict[str, Any]:
    """The model of ``seed``: int8 integers and float32 scales, built on the
    default device in one jitted call.  Norm weights are all ones and are
    not stored."""
    build = jax.jit(functools.partial(_make_weights, _Frozen(shapes)))
    return build(jax.random.PRNGKey(int(seed)))


class _Frozen(dict):
    """A dict usable as a static (hashable) argument of ``jax.jit``."""

    def __hash__(self):  # type: ignore[override]
        return hash(tuple(sorted(self.items())))


def _requant(qw: Dict[str, Any], bits: Optional[int]) -> Dict[str, Any]:
    """The control's weights: the same matrix rounded onto a coarser
    symmetric grid of ``bits`` bits (int4: [-7, 7]).  None: the weights as
    drawn, which are the int8 the configurations state."""
    if bits is None or bits >= 8:
        return qw
    top = float(2 ** (bits - 1) - 1)
    q = jnp.round(qw["q"].astype(jnp.float32) * (top / _QMAX))
    return {"q": q, "scale": qw["scale"] * (_QMAX / top)}


def _deq(qw: Dict[str, Any], scale_axis: int = -1) -> jnp.ndarray:
    q = qw["q"].astype(jnp.float32)
    if scale_axis == 0:
        return q * qw["scale"][:, None]
    return q * qw["scale"]


def rms_norm(x: jnp.ndarray, eps: float) -> jnp.ndarray:
    """RMSNorm with a weight of ones."""
    return x / jnp.sqrt(jnp.mean(x * x, axis=-1, keepdims=True) + eps)


def rope(x: jnp.ndarray, positions: jnp.ndarray, theta: float) -> jnp.ndarray:
    """Rotary embedding, rotate-half pairing: x [T, heads, head_dim]."""
    hd = x.shape[-1]
    inv = 1.0 / (theta ** (jnp.arange(0, hd, 2, dtype=jnp.float32) / hd))
    ang = positions.astype(jnp.float32)[:, None] * inv  # [T, hd/2]
    cos, sin = jnp.cos(ang)[:, None, :], jnp.sin(ang)[:, None, :]
    x1, x2 = x[..., : hd // 2], x[..., hd // 2:]
    return jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin], -1)


def attention(q, k, v, window: Optional[int]) -> jnp.ndarray:
    """Dense causal grouped-query attention.  q [T,H,D]; k, v [T,K,D]."""
    t, h, d = q.shape
    group = h // k.shape[1]
    k = jnp.repeat(k, group, axis=1)
    v = jnp.repeat(v, group, axis=1)
    scores = jnp.einsum("thd,shd->hts", q, k) / math.sqrt(d)
    rows = jnp.arange(t)[:, None]
    cols = jnp.arange(t)[None, :]
    keep = cols <= rows
    if window is not None:
        keep = keep & (rows - cols < window)
    scores = jnp.where(keep[None], scores, -jnp.inf)
    probs = jax.nn.softmax(scores, axis=-1)
    return jnp.einsum("hts,shd->thd", probs, v)


def _layer(s, x, lw, positions):
    h = rms_norm(x, s["eps"])
    q, k, v = h @ _deq(lw["wq"]), h @ _deq(lw["wk"]), h @ _deq(lw["wv"])
    if s["qkv_bias"]:
        q, k, v = q + lw["bq"], k + lw["bk"], v + lw["bv"]
    t = x.shape[0]
    q = rope(q.reshape(t, s["heads"], s["head_dim"]), positions, s["theta"])
    k = rope(k.reshape(t, s["kv_heads"], s["head_dim"]), positions,
             s["theta"])
    v = v.reshape(t, s["kv_heads"], s["head_dim"])
    a = attention(q, k, v, s["window"]).reshape(t, -1)
    x = x + a @ _deq(lw["wo"])
    h = rms_norm(x, s["eps"])
    gate = jax.nn.silu(h @ _deq(lw["w_gate"])) * (h @ _deq(lw["w_up"]))
    return x + gate @ _deq(lw["w_down"])


_LAYER_KEYS = ("wq", "wk", "wv", "wo", "w_gate", "w_up", "w_down",
               "bq", "bk", "bv")


def _take_layer(weights, i, bits: Optional[int]):
    """Layer ``i`` of the stacked weights (``i`` may be traced)."""
    def at(a):
        return jax.lax.dynamic_index_in_dim(a, i, 0, keepdims=False)

    out = {}
    for name in _LAYER_KEYS:
        if name not in weights:
            continue
        w = weights[name]
        if isinstance(w, dict):
            out[name] = _requant({"q": at(w["q"]), "scale": at(w["scale"])},
                                 bits)
        else:
            out[name] = at(w)
    return out


@functools.lru_cache(maxsize=None)
def _program(s: "_Frozen", weight_bits: Optional[int]):
    """The whole forward as one jitted function of (weights, tokens): a loop
    over the layers, one layer's weights in float32 at a time (a 7B model
    in float32 does not fit one chip).  Compiled once for each
    configuration and sequence length."""
    def forward(weights, tokens):
        with jax.default_matmul_precision("highest"):
            positions = jnp.arange(tokens.shape[0])
            emb = _requant(weights["embed"], weight_bits)
            x = (emb["q"][tokens].astype(jnp.float32)
                 * emb["scale"][tokens][:, None])
            x = jax.lax.fori_loop(
                0, s["layers"],
                lambda i, x: _layer(s, x, _take_layer(weights, i,
                                                      weight_bits),
                                    positions), x)
            head = _requant(weights["lm_head"], weight_bits)
            return jax.nn.log_softmax(rms_norm(x, s["eps"]) @ _deq(head),
                                      axis=-1)

    return jax.jit(forward)


def forward_logprobs(shapes: Dict[str, Any], weights: Dict[str, Any],
                     tokens, weight_bits: Optional[int] = None) -> jnp.ndarray:
    """log-softmax of the next-token logits at every position: [T, vocab].
    ``weight_bits`` None is the model as the configuration states it; a
    number is the control: the same arithmetic on weights rounded to that
    many bits."""
    bits = None if weight_bits is None else int(weight_bits)
    return _program(_Frozen(shapes), bits)(
        weights, jnp.asarray(tokens, jnp.int32))


def token_logprobs(shapes: Dict[str, Any], weights: Dict[str, Any], tokens,
                   weight_bits: Optional[int] = None) -> jnp.ndarray:
    """log P(tokens[t + 1] | tokens[..t]) for every t: [T - 1]."""
    tokens = jnp.asarray(tokens, jnp.int32)
    lp = forward_logprobs(shapes, weights, tokens, weight_bits)
    return jnp.take_along_axis(lp[:-1], tokens[1:, None], axis=1)[:, 0]
