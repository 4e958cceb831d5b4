"""Plain float32 reference of the latent-attention, routed-expert family
(``sarvam-105b``), as one chip's share of a stated deployment or whole.

This is a model family's module (benchmarks/README.md, "A model family"):
a configuration names it with ``"reference": "mla_moe_reference"``.  It
imports nothing of the program.

Equations, from the published ``config.json`` of sarvamai/sarvam-105b (the
DeepSeek-V2/V3 lineage its keys come from); what the config leaves open is
this family's convention, listed in the configuration's ``assumed``.

Attention, ``h`` the RMS-normed input of the layer (norm weights are ones
and not stored), ``H`` heads:

- ``q = W_q h`` [H, 128 + 64]; with ``use_qk_norm`` each head's 192-wide
  query is RMS-normed (assumed placement: before the rope, beside the
  latent's norm, which keeps the cached row and the absorbed form exact);
  the last 64 of each head are roped.
- ``[c, k_r] = W_kva h`` [512 + 64]; ``c`` RMS-normed; ``k_r`` roped, one
  key for all heads.  A token caches ``[c, k_r]``: ``kv_lora_rank +
  qk_rope_head_dim`` values in every layer.
- ``[k_n, v] = W_kvb c`` [H, 128 + 128].
- scores ``(q_n . k_n + q_r . k_r) * s``, ``s = 192**-0.5 * (0.1 *
  mscale_all_dim * ln(factor) + 1)**2`` (deepseek_yarn), causal softmax,
  ``o = W_o [P v]``.
- rope, rotate-half pairing, yarn frequencies: pair ``i`` of 32 turns
  ``original_max * theta**(-2i/64) / 2pi`` times over the original context;
  ``low = floor(pair(beta_fast))``, ``high = ceil(pair(beta_slow))`` with
  ``pair(n) = 64 ln(original_max / (2 pi n)) / (2 ln theta)``; a linear
  ramp over the pair index from ``low`` to ``high`` mixes ``theta**(-2i/64)``
  (below) with the same divided by ``factor`` (above).  cos and sin are
  scaled by ``mscale(factor, mscale) / mscale(factor, mscale_all_dim)``.

Feed-forward: the first ``first_k_dense_replace`` layers one SwiGLU of
``intermediate_size``; every later layer routed: ``s = sigmoid(W_r h)``
over all PUBLISHED experts in float32 (assumed score function); the
``num_experts_per_tok`` experts of largest ``s + b`` are chosen (``b`` the
selection bias); their weights are the unbiased ``s`` over their sum
(assumed), times ``routed_scaling_factor``; ``y = sum over the chosen
experts HELD HERE of w_e * SwiGLU_e(h) + SwiGLU_shared(h)``.  An
assignment to an expert another chip holds adds nothing, here as in the
program: that partial result is what goes on to the next layer.  A loop
over the held experts, every one over every token, masked by its weight.

The share (benchmarks/README.md, "A configuration cut to a chip's share"):
``num_experts`` and ``vocab_size`` in the file count what is held;
``published_counts`` gives the published numbers, ``layer_chips`` the chips
that share a layer and ``chip_index`` which of them this is: experts
``[chip_index * held, + held)`` of the published ones.  The vocabulary's
slice is simply a smaller vocabulary.

``make_weights`` is the benchmark's own statement of how a seed becomes the
model the program serves: truncated normal on [-2, 2] times ``fan_in**-0.5``
rounded to bfloat16, the key split sixteen ways, attention leaves from a
four-way split of the group's key, expert ``e`` (published index) of expert
layer ``i`` from ``fold_in(fold_in(leaf key, i), e)``, the selection bias
``0.03125 * normal``, drawn non-zero so that it changes the choice.  Weights are
kept in bfloat16 (the values ARE the bfloat16 ones) and widened a layer, an
expert at a time, so that six layers at published widths fit one chip.
"""

from __future__ import annotations

import functools
import math
from typing import Any, Dict, Optional

import jax
import jax.numpy as jnp

from benchmarks.correctness import TYPE_BYTES

REQUIRED_KEYS = ("num_attention_heads", "kv_lora_rank", "qk_rope_head_dim",
                 "qk_nope_head_dim", "v_head_dim", "intermediate_size",
                 "moe_intermediate_size", "num_experts",
                 "num_experts_per_tok", "num_shared_experts",
                 "first_k_dense_replace")

ROUTER_BIAS_STD = 0.03125


class _Frozen(dict):
    """A dict usable as a static (hashable) argument of ``jax.jit``."""

    def __hash__(self):  # type: ignore[override]
        return hash(tuple(sorted(self.items())))


def shapes_of(config: Dict[str, Any]) -> Dict[str, Any]:
    published = config.get("published_counts", {})
    held = int(config["num_experts"])
    experts = int(published.get("num_experts", held))
    chips = int(config.get("layer_chips", 1))
    if held * chips != experts:
        raise ValueError(f"{held} experts held on each of {chips} chips are "
                         f"not the {experts} published")
    yarn = config.get("rope_scaling") or {}
    if yarn and yarn.get("type") != "deepseek_yarn":
        raise ValueError(f"rope_scaling of type {yarn.get('type')!r}")
    return {
        "layers": int(config["num_hidden_layers"]),
        "dense_layers": min(int(config["first_k_dense_replace"]),
                            int(config["num_hidden_layers"])),
        "dim": int(config["hidden_size"]),
        "heads": int(config["num_attention_heads"]),
        "latent": int(config["kv_lora_rank"]),
        "rope_dim": int(config["qk_rope_head_dim"]),
        "nope_dim": int(config["qk_nope_head_dim"]),
        "v_dim": int(config["v_head_dim"]),
        "ffn": int(config["intermediate_size"]),
        "expert_ffn": int(config["moe_intermediate_size"]),
        "experts": experts,
        "held": held,
        "first_held": int(config.get("chip_index", 0)) * held,
        "top_k": int(config["num_experts_per_tok"]),
        "shared": int(config["num_shared_experts"]),
        "bias": bool(config.get("moe_router_enable_expert_bias", False)),
        "routed_scale": float(config.get("routed_scaling_factor", 1.0)),
        "qk_norm": bool(config.get("use_qk_norm", False)),
        "vocab": int(config["vocab_size"]),
        "theta": float(config["rope_theta"]),
        "eps": float(config["rms_norm_eps"]),
        "yarn_factor": float(yarn.get("factor", 1.0)),
        "yarn_original": int(yarn.get("original_max_position_embeddings", 0)),
        "yarn_beta_fast": float(yarn.get("beta_fast", 32)),
        "yarn_beta_slow": float(yarn.get("beta_slow", 1)),
        "yarn_mscale": float(yarn.get("mscale", 1)),
        "yarn_mscale_all_dim": float(yarn.get("mscale_all_dim", 0)),
    }


def cache_bytes_per_token(config: Dict[str, Any]) -> int:
    """The normed latent and the one roped key all heads share, in every
    layer, in the type the configuration states for the cache."""
    return int(config["num_hidden_layers"]
               * (config["kv_lora_rank"] + config["qk_rope_head_dim"])
               * TYPE_BYTES[config["precision"]["kv_cache"]])


# ---- the model of a seed ------------------------------------------------------

def _draw(key, shape, fan_in):
    w = jax.random.truncated_normal(key, -2, 2, shape, jnp.float32)
    return (w * fan_in ** -0.5).astype(jnp.bfloat16)


def _attention_weights(s, key, n):
    dm, h = s["dim"], s["heads"]
    c, dr, dn, dv = s["latent"], s["rope_dim"], s["nope_dim"], s["v_dim"]
    ks = jax.random.split(key, 4)
    return {"wq": _draw(ks[0], (n, dm, h * (dn + dr)), dm),
            "wkva": _draw(ks[1], (n, dm, c + dr), dm),
            "wkvb": _draw(ks[2], (n, c, h * (dn + dv)), c),
            "wo": _draw(ks[3], (n, h * dv, dm), h * dv)}


def _make_weights(s, key):
    dm, v = s["dim"], s["vocab"]
    ld, lm = s["dense_layers"], s["layers"] - s["dense_layers"]
    keys = jax.random.split(key, 16)
    w = {"embed": _draw(keys[7], (v, dm), dm),
         "lm_head": _draw(jax.random.fold_in(key, 99), (dm, v), dm)}
    if ld:
        f = s["ffn"]
        w["dense"] = dict(_attention_weights(s, keys[0], ld),
                          gate=_draw(keys[4], (ld, dm, f), dm),
                          up=_draw(keys[5], (ld, dm, f), dm),
                          down=_draw(keys[6], (ld, f, dm), f))
    if lm:
        e, fe, held, first = (s["experts"], s["expert_ffn"], s["held"],
                              s["first_held"])

        def experts(k, shape, fan_in):
            def one(i):
                ke = jax.random.fold_in(jax.random.fold_in(k, i // held),
                                        first + i % held)
                return _draw(ke, shape, fan_in)

            return jax.lax.map(one, jnp.arange(lm * held)).reshape(
                (lm, held) + shape)

        moe = dict(_attention_weights(s, keys[1], lm),
                   router=_draw(keys[8], (lm, dm, e), dm),
                   gate=experts(keys[9], (dm, fe), dm),
                   up=experts(keys[10], (dm, fe), dm),
                   down=experts(keys[11], (fe, dm), fe))
        if s["bias"]:
            moe["bias"] = ROUTER_BIAS_STD * jax.random.normal(
                keys[12], (lm, e), jnp.float32)
        if s["shared"]:
            fs = s["shared"] * fe
            moe.update(shared_gate=_draw(keys[13], (lm, dm, fs), dm),
                       shared_up=_draw(keys[14], (lm, dm, fs), dm),
                       shared_down=_draw(keys[15], (lm, fs, dm), fs))
        w["moe"] = moe
    return w


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


def rms_norm(x, eps):
    """RMSNorm with a weight of ones."""
    return x / jnp.sqrt(jnp.mean(x * x, axis=-1, keepdims=True) + eps)


def _mscale(factor, m):
    return 0.1 * m * math.log(factor) + 1.0 if factor > 1 else 1.0


def yarn_frequencies(s) -> jnp.ndarray:
    d, theta = s["rope_dim"], s["theta"]
    plain = 1.0 / theta ** (jnp.arange(0, d, 2, dtype=jnp.float32) / d)
    if s["yarn_factor"] <= 1 or not s["yarn_original"]:
        return plain

    def pair(turns):
        return (d * math.log(s["yarn_original"] / (turns * 2 * math.pi))
                / (2 * math.log(theta)))

    low = max(math.floor(pair(s["yarn_beta_fast"])), 0)
    high = min(math.ceil(pair(s["yarn_beta_slow"])), d - 1)
    ramp = jnp.clip((jnp.arange(d // 2, dtype=jnp.float32) - low)
                    / max(high - low, 1e-3), 0.0, 1.0)
    return plain / s["yarn_factor"] * ramp + plain * (1.0 - ramp)


def rope(s, x, positions):
    """x [T, heads, rope_dim], rotate-half pairing, yarn frequencies."""
    d = x.shape[-1]
    ang = positions.astype(jnp.float32)[:, None] * yarn_frequencies(s)
    m = 1.0
    if s["yarn_factor"] > 1:
        m = (_mscale(s["yarn_factor"], s["yarn_mscale"])
             / _mscale(s["yarn_factor"], s["yarn_mscale_all_dim"]))
    cos, sin = jnp.cos(ang)[:, None, :] * m, jnp.sin(ang)[:, None, :] * m
    x1, x2 = x[..., : d // 2], x[..., d // 2:]
    return jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin], -1)


def score_scale(s) -> float:
    scale = (s["nope_dim"] + s["rope_dim"]) ** -0.5
    if s["yarn_factor"] > 1 and s["yarn_mscale_all_dim"]:
        scale *= _mscale(s["yarn_factor"], s["yarn_mscale_all_dim"]) ** 2
    return scale


def attention(s, lw, x, positions, bits):
    t = x.shape[0]
    h, c, dr, dn = s["heads"], s["latent"], s["rope_dim"], s["nope_dim"]
    hin = rms_norm(x, s["eps"])
    q = (hin @ _wide(lw["wq"], bits)).reshape(t, h, dn + dr)
    if s["qk_norm"]:
        q = rms_norm(q, s["eps"])
    q_n, q_r = q[..., :dn], rope(s, q[..., dn:], positions)
    ckr = hin @ _wide(lw["wkva"], bits)
    latent = rms_norm(ckr[:, :c], s["eps"])
    k_r = rope(s, ckr[:, None, c:], positions)[:, 0]
    kv = (latent @ _wide(lw["wkvb"], bits)).reshape(t, h, dn + s["v_dim"])
    k_n, v = kv[..., :dn], kv[..., dn:]
    scores = (jnp.einsum("thd,shd->hts", q_n, k_n)
              + jnp.einsum("thd,sd->hts", q_r, k_r)) * score_scale(s)
    causal = jnp.arange(t)[None, :] <= jnp.arange(t)[:, None]
    probs = jax.nn.softmax(jnp.where(causal[None], scores, -jnp.inf), axis=-1)
    out = jnp.einsum("hts,shd->thd", probs, v).reshape(t, -1)
    return x + out @ _wide(lw["wo"], bits)


def swiglu(h, gate, up, down):
    return (jax.nn.silu(h @ gate) * (h @ up)) @ down


def routed(s, lw, h, bits):
    """The routed layer's output for the share's held experts + the shared
    expert."""
    scores = jax.nn.sigmoid(h @ _wide(lw["router"], bits))      # [T, E]
    chosen_by = scores + lw["bias"] if s["bias"] else scores
    _, top_i = jax.lax.top_k(chosen_by, s["top_k"])
    top_w = jnp.take_along_axis(scores, top_i, axis=-1)
    top_w = top_w / top_w.sum(-1, keepdims=True) * s["routed_scale"]
    weight = (jax.nn.one_hot(top_i, s["experts"]) * top_w[..., None]).sum(-2)

    def one(e, out):
        at = functools.partial(jax.lax.dynamic_index_in_dim, index=e, axis=0,
                               keepdims=False)
        y = swiglu(h, _wide(at(lw["gate"]), bits), _wide(at(lw["up"]), bits),
                   _wide(at(lw["down"]), bits))
        w = jax.lax.dynamic_index_in_dim(weight, s["first_held"] + e, axis=1)
        return out + w * y

    out = jax.lax.fori_loop(0, s["held"], one, jnp.zeros_like(h))
    if s["shared"]:
        out = out + swiglu(h, _wide(lw["shared_gate"], bits),
                           _wide(lw["shared_up"], bits),
                           _wide(lw["shared_down"], bits))
    return out


def _layer_of(group, i):
    return {k: jax.lax.dynamic_index_in_dim(v, i, 0, keepdims=False)
            for k, v in group.items()}


@functools.lru_cache(maxsize=None)
def _program(s: "_Frozen", bits: Optional[int]):
    def forward(w, tokens):
        with jax.default_matmul_precision("highest"):
            positions = jnp.arange(tokens.shape[0])
            x = _wide(w["embed"], bits, -1)[tokens]

            def dense_layer(i, x):
                lw = _layer_of(w["dense"], i)
                x = attention(s, lw, x, positions, bits)
                return x + swiglu(rms_norm(x, s["eps"]),
                                  _wide(lw["gate"], bits),
                                  _wide(lw["up"], bits),
                                  _wide(lw["down"], bits))

            def moe_layer(i, x):
                lw = _layer_of(w["moe"], i)
                x = attention(s, lw, x, positions, bits)
                return x + routed(s, lw, rms_norm(x, s["eps"]), bits)

            if s["dense_layers"]:
                x = jax.lax.fori_loop(0, s["dense_layers"], dense_layer, x)
            if s["layers"] > s["dense_layers"]:
                x = jax.lax.fori_loop(0, s["layers"] - s["dense_layers"],
                                      moe_layer, x)
            return jax.nn.log_softmax(
                rms_norm(x, s["eps"]) @ _wide(w["lm_head"], bits), axis=-1)

    return jax.jit(forward)


def forward_logprobs(shapes: Dict[str, Any], weights: Dict[str, Any], tokens,
                     weight_bits: Optional[int] = None) -> jnp.ndarray:
    """log-softmax of the next-token logits at every position: [T, vocab].
    ``weight_bits`` None is the model as the configuration states it; a
    number is the control: the same arithmetic on weights rounded to that
    many bits."""
    bits = None if weight_bits is None else int(weight_bits)
    return _program(_Frozen(shapes), bits)(
        weights, jnp.asarray(tokens, jnp.int32))
