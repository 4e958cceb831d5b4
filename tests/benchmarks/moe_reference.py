"""A second model family, as a file: the plain float32 reference of a
mixtral-style decoder (the program's ``tiny-moe`` preset) served unquantised.

``tinycell.py`` copies this file into its test root as
``benchmarks/moe_reference.py`` and a configuration there names it
(``"reference": "moe_reference"``); nothing under ``benchmarks/`` knows of it.
It gives the five names a family's module gives (benchmarks/README.md, "A
model family").

Equations, from the Hugging Face ``modeling_mixtral`` description: the dense
family's attention (grouped-query, rotary, causal; taken from
``benchmarks/reference.py``), and in place of the one feed-forward a router
over ``num_local_experts`` experts: softmax of the router's logits over all
experts, the ``num_experts_per_tok`` largest kept and renormalised to sum to
one, each expert a SwiGLU of width ``intermediate_size``, the result their
weighted sum.

The seed becomes a model as the program's unquantised initialisation draws
one: a truncated normal on [-2, 2] scaled by ``fan_in ** -0.5``, rounded to
the bfloat16 the configuration states for its weights; norm weights are ones.
"""

from __future__ import annotations

import functools
from typing import Any, Dict, Optional

import jax
import jax.numpy as jnp

from benchmarks import reference as dense
from benchmarks.correctness import TYPE_BYTES

REQUIRED_KEYS = ("num_attention_heads", "num_key_value_heads",
                 "intermediate_size", "num_local_experts",
                 "num_experts_per_tok")


def shapes_of(config: Dict[str, Any]) -> Dict[str, Any]:
    heads, dim = int(config["num_attention_heads"]), int(config["hidden_size"])
    return {
        "layers": int(config["num_hidden_layers"]), "dim": dim,
        "heads": heads, "kv_heads": int(config["num_key_value_heads"]),
        "head_dim": int(config.get("head_dim") or dim // heads),
        "ffn": int(config["intermediate_size"]),
        "experts": int(config["num_local_experts"]),
        "top_k": int(config["num_experts_per_tok"]),
        "vocab": int(config["vocab_size"]),
        "theta": float(config["rope_theta"]),
        "eps": float(config["rms_norm_eps"]),
    }


def cache_bytes_per_token(config: Dict[str, Any]) -> int:
    """Keys and values of the KV heads in every layer, as the dense family
    caches them: experts change nothing a token caches."""
    s = shapes_of(config)
    return int(s["layers"] * 2 * s["kv_heads"] * s["head_dim"]
               * TYPE_BYTES[config["precision"]["kv_cache"]])


def _make_weights(s, key):
    l, dm, h, kh, hd, f, e, v = (s["layers"], s["dim"], s["heads"],
                                 s["kv_heads"], s["head_dim"], s["ffn"],
                                 s["experts"], s["vocab"])
    keys = jax.random.split(key, 12)

    def draw(k, shape, fan_in):
        w = jax.random.truncated_normal(k, -2, 2, shape, jnp.float32)
        return (w * fan_in ** -0.5).astype(jnp.bfloat16).astype(jnp.float32)

    return {
        "wq": draw(keys[0], (l, dm, h * hd), dm),
        "wk": draw(keys[1], (l, dm, kh * hd), dm),
        "wv": draw(keys[2], (l, dm, kh * hd), dm),
        "wo": draw(keys[3], (l, h * hd, dm), h * hd),
        "embed": draw(keys[7], (v, dm), dm),
        "router": draw(keys[8], (l, dm, e), dm),
        "gate": draw(keys[9], (l, e, dm, f), dm),
        "up": draw(keys[10], (l, e, dm, f), dm),
        "down": draw(keys[11], (l, e, f, dm), f),
        "lm_head": draw(jax.random.fold_in(key, 99), (dm, v), dm),
    }


def make_weights(shapes: Dict[str, Any], seed: int) -> Dict[str, Any]:
    build = jax.jit(functools.partial(_make_weights, dense._Frozen(shapes)))
    return build(jax.random.PRNGKey(int(seed)))


def _rounded(w, bits: Optional[int], axis: int = -2):
    """The control's weights: each output channel rounded onto a symmetric
    grid of ``bits`` bits (``axis`` is the contracted one)."""
    if bits is None:
        return w
    top = float(2 ** (bits - 1) - 1)
    scale = jnp.max(jnp.abs(w), axis=axis, keepdims=True) / top
    return jnp.round(w / jnp.maximum(scale, 1e-30)) * scale


def _experts(s, h, router, gate, up, down):
    probs = jax.nn.softmax(h @ router, axis=-1)               # [T, E]
    top_p, top_i = jax.lax.top_k(probs, s["top_k"])
    top_p = top_p / top_p.sum(-1, keepdims=True)
    share = (jax.nn.one_hot(top_i, s["experts"]) * top_p[..., None]).sum(-2)
    out = jnp.zeros_like(h)
    for e in range(s["experts"]):
        y = (jax.nn.silu(h @ gate[e]) * (h @ up[e])) @ down[e]
        out = out + share[:, e:e + 1] * y
    return out


@functools.lru_cache(maxsize=None)
def _program(s, bits: Optional[int]):
    def forward(w, tokens):
        w = {k: _rounded(v, bits, -1 if k == "embed" else -2)
             for k, v in w.items()}
        with jax.default_matmul_precision("highest"):
            t = tokens.shape[0]
            positions = jnp.arange(t)
            x = w["embed"][tokens]
            for i in range(s["layers"]):
                h = dense.rms_norm(x, s["eps"])
                q = (h @ w["wq"][i]).reshape(t, s["heads"], s["head_dim"])
                k = (h @ w["wk"][i]).reshape(t, s["kv_heads"], s["head_dim"])
                v = (h @ w["wv"][i]).reshape(t, s["kv_heads"], s["head_dim"])
                a = dense.attention(dense.rope(q, positions, s["theta"]),
                                    dense.rope(k, positions, s["theta"]),
                                    v, None)
                x = x + a.reshape(t, -1) @ w["wo"][i]
                x = x + _experts(s, dense.rms_norm(x, s["eps"]),
                                 w["router"][i], w["gate"][i], w["up"][i],
                                 w["down"][i])
            return jax.nn.log_softmax(
                dense.rms_norm(x, s["eps"]) @ w["lm_head"], axis=-1)

    return jax.jit(forward)


def forward_logprobs(shapes: Dict[str, Any], weights: Dict[str, Any], tokens,
                     weight_bits: Optional[int] = None) -> jnp.ndarray:
    """log-softmax of the next-token logits at every position: [T, vocab]."""
    bits = None if weight_bits is None else int(weight_bits)
    return _program(dense._Frozen(shapes), bits)(
        weights, jnp.asarray(tokens, jnp.int32))
