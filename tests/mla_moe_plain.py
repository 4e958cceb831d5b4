"""The uncut sarvam-style architecture's forward, plainly: ``jax.numpy``,
float32, ``highest``, one sequence, no cache, no batching, no grouped
product (a loop over the experts), on the PROGRAM's parameter tree, so that
a test compares arithmetic and not two draws of the weights.

Equations: benchmarks/mla_moe_reference.py's text (the benchmark's copy of
this family, which draws its own weights).  Departures from the published
description: the rope pairs (x_i, x_{i + d/2}) (rotate-half) where the
published weights pair (x_2i, x_2i+1): a fixed permutation of each head's
rope columns, immaterial with random weights; ``use_qk_norm``'s placement,
the router's sigmoid and the renormalised weights are this family's
convention (the config names none).

``experts`` limits the routed sum to a range of published experts (a
share's part) and ``shared`` says whether the shared expert is counted.
"""

import math

import jax
import jax.numpy as jnp


def _norm(x, w, eps):
    return x / jnp.sqrt(jnp.mean(x * x, -1, keepdims=True) + eps) * w


def _freqs(cfg):
    d, theta, y = cfg.qk_rope_head_dim, cfg.rope_theta, cfg.yarn
    plain = 1.0 / theta ** (jnp.arange(0, d, 2, dtype=jnp.float32) / d)
    if y is None:
        return plain, 1.0, d ** -0.5

    def pair(turns):
        return (d * math.log(y.original_max / (turns * 2 * math.pi))
                / (2 * math.log(theta)))

    def mscale(m):
        return 0.1 * m * math.log(y.factor) + 1.0

    low, high = max(math.floor(pair(y.beta_fast)), 0), min(
        math.ceil(pair(y.beta_slow)), d - 1)
    ramp = jnp.clip((jnp.arange(d // 2) - low) / max(high - low, 1e-3), 0, 1)
    return (plain / y.factor * ramp + plain * (1 - ramp),
            mscale(y.mscale) / mscale(y.mscale_all_dim),
            mscale(y.mscale_all_dim) ** 2)


def _rope(cfg, x, pos):
    freqs, m, _ = _freqs(cfg)
    ang = pos[:, None].astype(jnp.float32) * freqs
    cos, sin = jnp.cos(ang)[:, None] * m, jnp.sin(ang)[:, None] * m
    d = x.shape[-1]
    a, b = x[..., : d // 2], x[..., d // 2:]
    return jnp.concatenate([a * cos - b * sin, b * cos + a * sin], -1)


def attention(cfg, w, x):
    t = x.shape[0]
    pos = jnp.arange(t)
    c, dn, h = cfg.kv_lora_rank, cfg.qk_nope_head_dim, cfg.n_heads
    hin = _norm(x, w["attn_norm"], cfg.norm_eps)
    q = (hin @ w["wq"]).reshape(t, h, -1)
    if cfg.qk_norm:
        q = _norm(q, w["q_norm"], cfg.norm_eps)
    q_n, q_r = q[..., :dn], _rope(cfg, q[..., dn:], pos)
    ckr = hin @ w["wkva"]
    latent = _norm(ckr[:, :c], w["kv_norm"], cfg.norm_eps)
    k_r = _rope(cfg, ckr[:, None, c:], pos)[:, 0]
    kv = (latent @ w["wkvb"]).reshape(t, h, -1)
    k_n, v = kv[..., :dn], kv[..., dn:]
    scale = (dn + cfg.qk_rope_head_dim) ** -0.5 * _freqs(cfg)[2]
    scores = (jnp.einsum("thd,shd->hts", q_n, k_n)
              + jnp.einsum("thd,sd->hts", q_r, k_r)) * scale
    scores = jnp.where(pos[None, :] <= pos[:, None], scores, -jnp.inf)
    out = jnp.einsum("hts,shd->thd", jax.nn.softmax(scores, -1), v)
    return x + out.reshape(t, -1) @ w["wo"]


def _swiglu(h, gate, up, down):
    return (jax.nn.silu(h @ gate) * (h @ up)) @ down


def routed_layer(cfg, w, h, experts=None, shared=True, first_held=0):
    """``w``: one expert layer's leaves, its expert axis starting at the
    published expert ``first_held``.  ``experts``: the range of published
    experts whose part is summed (None: all that ``w`` holds)."""
    scores = jax.nn.sigmoid(h @ w["router"])
    chosen_by = scores + w["router_bias"] if cfg.router_bias else scores
    _, top_i = jax.lax.top_k(chosen_by, cfg.n_experts_per_tok)
    top_w = jnp.take_along_axis(scores, top_i, -1)
    top_w = top_w / top_w.sum(-1, keepdims=True) * cfg.routed_scale
    weight = (jax.nn.one_hot(top_i, cfg.n_experts) * top_w[..., None]).sum(-2)
    held = w["moe_gate"].shape[0]
    out = jnp.zeros_like(h)
    for e in (range(first_held, first_held + held) if experts is None
              else experts):
        i = e - first_held
        out = out + weight[:, e:e + 1] * _swiglu(
            h, w["moe_gate"][i], w["moe_up"][i], w["moe_down"][i])
    if shared and cfg.n_shared_experts:
        out = out + _swiglu(h, w["shared_gate"], w["shared_up"],
                            w["shared_down"])
    return out


def forward_logprobs(cfg, params, tokens):
    """[T] token ids -> [T, V] log-probabilities of the next token."""
    f32 = jax.tree.map(lambda a: jnp.asarray(a, jnp.float32), params)
    first_held = cfg.experts_held[0]
    with jax.default_matmul_precision("highest"):
        x = f32["embed"][jnp.asarray(tokens)]
        for group, kind in (("dense_blocks", "dense"), ("blocks", "moe")):
            if group not in f32:
                continue
            n = f32[group]["wq"].shape[0]
            for i in range(n):
                w = jax.tree.map(lambda a: a[i], f32[group])
                x = attention(cfg, w, x)
                h = _norm(x, w["mlp_norm"], cfg.norm_eps)
                if kind == "dense":
                    x = x + _swiglu(h, w["w_gate"], w["w_up"], w["w_down"])
                else:
                    x = x + routed_layer(cfg, w, h, first_held=first_held)
        x = _norm(x, f32["final_norm"], cfg.norm_eps)
        return jax.nn.log_softmax(x @ f32["lm_head"], -1)
