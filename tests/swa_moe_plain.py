"""The window-and-full architecture's forward, plainly: ``jax.numpy``,
float32, ``highest``, one sequence, no cache, no rings, no batching, no
grouped product (a loop over the experts), on the PROGRAM's parameter tree,
so that a test compares arithmetic and not two draws of the weights.

Equations: benchmarks/swa_moe_reference.py's text (the benchmark's copy of
this family, which draws its own weights).  Every layer is written out in
the model's order; a window layer is a full layer with ``i - j < window``
added to the mask and the sink added to the denominator.

``experts`` limits the routed sum to a range of published experts (a
share's part).
"""

import jax
import jax.numpy as jnp


def _norm(x, w, eps):
    return x / jnp.sqrt(jnp.mean(x * x, -1, keepdims=True) + eps) * w


def _rope(x, pos, theta, rotary):
    """x [T, heads, D]: the leading ``rotary`` columns in rotate-half pairs."""
    r = rotary or x.shape[-1]
    freqs = 1.0 / theta ** (jnp.arange(0, r, 2, dtype=jnp.float32) / r)
    ang = pos[:, None].astype(jnp.float32) * freqs
    cos, sin = jnp.cos(ang)[:, None], jnp.sin(ang)[:, None]
    a, b = x[..., : r // 2], x[..., r // 2: r]
    return jnp.concatenate(
        [a * cos - b * sin, b * cos + a * sin, x[..., r:]], -1)


def attention(cfg, kind, w, x):
    t = x.shape[0]
    pos = jnp.arange(t)
    h, dk, dv = cfg.n_heads, cfg.head_dim, cfg.v_head_dim
    kv = cfg.kv_heads_of(kind)
    theta = cfg.window_rope_theta if kind == "window" else cfg.rope_theta
    hin = _norm(x, w["attn_norm"], cfg.norm_eps)
    q = _rope((hin @ w["wq"]).reshape(t, h, dk), pos, theta, cfg.rotary_dim)
    k = _rope((hin @ w["wk"]).reshape(t, kv, dk), pos, theta, cfg.rotary_dim)
    v = cfg.value_scale * (hin @ w["wv"]).reshape(t, kv, dv)
    k, v = jnp.repeat(k, h // kv, 1), jnp.repeat(v, h // kv, 1)
    scores = jnp.einsum("thd,shd->hts", q, k) * dk ** -0.5
    seen = pos[None, :] <= pos[:, None]
    if kind == "window":
        seen &= pos[:, None] - pos[None, :] < cfg.sliding_window
    e = jnp.where(seen, jnp.exp(scores), 0.0)
    denom = e.sum(-1, keepdims=True)
    if kind == "window" and "sink" in w:
        denom = denom + jnp.exp(w["sink"])[:, None, None]
    out = jnp.einsum("hts,shd->thd", e / denom, v)
    return x + out.reshape(t, -1) @ w["wo"]


def _swiglu(h, gate, up, down):
    return (jax.nn.silu(h @ gate) * (h @ up)) @ down


def routed_layer(cfg, w, h, experts=None, first_held=0):
    """``w``: one routed layer's leaves, its expert axis starting at the
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
    return out


def forward_logprobs(cfg, params, tokens):
    """[T] token ids -> [T, V] log-probabilities of the next token."""
    f32 = jax.tree.map(lambda a: jnp.asarray(a, jnp.float32), params)
    first_held = cfg.experts_held[0]
    seen = {"full": 0, "window": 0, "dense": 0, "moe": 0}
    with jax.default_matmul_precision("highest"):
        x = f32["embed"][jnp.asarray(tokens)]
        for kind, ffn in zip(cfg.attn_kinds, cfg.layer_kinds):
            group = "attn_window" if kind == "window" else "attn_full"
            w = jax.tree.map(lambda a: a[seen[kind]], f32[group])
            x = attention(cfg, kind, w, x)
            stack = f32["blocks" if ffn == "moe" else "dense_ffn"]
            w = jax.tree.map(lambda a: a[seen[ffn]], stack)
            h = _norm(x, w["mlp_norm"], cfg.norm_eps)
            if ffn == "dense":
                x = x + _swiglu(h, w["w_gate"], w["w_up"], w["w_down"])
            else:
                x = x + routed_layer(cfg, w, h, first_held=first_held)
            seen[kind] += 1
            seen[ffn] += 1
        x = _norm(x, f32["final_norm"], cfg.norm_eps)
        return jax.nn.log_softmax(x @ f32["lm_head"], -1)
