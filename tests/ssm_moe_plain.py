"""The one-mixer-a-layer architecture's forward, plainly: ``jax.numpy``,
float32, ``highest``, one sequence, the Mamba-2 recurrence token by token
(``lax.scan`` over positions: no chunks), no cache, no state leaf, no
batching, no grouped product (a loop over the experts), on the PROGRAM's
parameter tree, so that a test compares arithmetic and not two draws of the
weights.

Equations: benchmarks/ssm_moe_reference.py's text (the benchmark's copy of
this family, which draws its own weights).

``experts`` limits the routed sum to a range of published experts (a
share's part); ``shared`` leaves the shared expert in or out.
"""

import jax
import jax.numpy as jnp


def _norm(x, w, eps):
    return x / jnp.sqrt(jnp.mean(x * x, -1, keepdims=True) + eps) * w


def mamba(cfg, w, u):
    """u [T, D] (normed) -> [T, D]: the recurrence a position at a time."""
    t = u.shape[0]
    heads, p, g, n = (cfg.ssm_heads, cfg.ssm_head_dim, cfg.ssm_groups,
                      cfg.ssm_state)
    inner, k = heads * p, cfg.ssm_conv
    zxd = u @ w["w_in"]
    z, xbc, dt = (zxd[:, :inner], zxd[:, inner:inner + cfg.ssm_conv_dim],
                  zxd[:, inner + cfg.ssm_conv_dim:])
    padded = jnp.concatenate([jnp.zeros((k - 1, xbc.shape[1])), xbc])
    xbc = jax.nn.silu(w["conv_b"] + sum(
        w["conv_w"][j] * padded[j:j + t] for j in range(k)))
    x = xbc[:, :inner].reshape(t, heads, p)
    bm = jnp.repeat(xbc[:, inner:inner + g * n].reshape(t, g, n),
                    heads // g, axis=1)
    cm = jnp.repeat(xbc[:, inner + g * n:].reshape(t, g, n),
                    heads // g, axis=1)
    dt = jax.nn.softplus(dt + w["dt_bias"])
    a = -jnp.exp(w["a_log"])

    def step(s, inp):
        x_t, b_t, c_t, dt_t = inp
        s = (jnp.exp(dt_t * a)[:, None, None] * s
             + (dt_t[:, None] * x_t)[:, :, None] * b_t[:, None, :])
        return s, jnp.einsum("hpn,hn->hp", s, c_t)

    _, y = jax.lax.scan(step, jnp.zeros((heads, p, n)), (x, bm, cm, dt))
    y = (y + w["d_skip"][:, None] * x).reshape(t, inner) * jax.nn.silu(z)
    yg = y.reshape(t, g, inner // g)
    yg = yg / jnp.sqrt(jnp.mean(yg * yg, -1, keepdims=True) + cfg.norm_eps)
    return (yg.reshape(t, inner) * w["gate_norm"]) @ w["w_out"]


def attention(cfg, w, u):
    t = u.shape[0]
    h, kv, d = cfg.n_heads, cfg.n_kv_heads, cfg.head_dim
    q = (u @ w["wq"]).reshape(t, h, d)
    k = jnp.repeat((u @ w["wk"]).reshape(t, kv, d), h // kv, 1)
    v = jnp.repeat((u @ w["wv"]).reshape(t, kv, d), h // kv, 1)
    scores = jnp.einsum("thd,shd->hts", q, k) * d ** -0.5
    seen = jnp.arange(t)[None, :] <= jnp.arange(t)[:, None]
    probs = jax.nn.softmax(jnp.where(seen, scores, -jnp.inf), -1)
    return jnp.einsum("hts,shd->thd", probs, v).reshape(t, -1) @ w["wo"]


def _relu2(h, up, down):
    return jnp.square(jax.nn.relu(h @ up)) @ down


def routed_layer(cfg, w, h, experts=None, first_held=0, shared=True):
    """``w``: one routed layer's leaves, its expert axis starting at the
    published expert ``first_held``.  ``experts``: the range of published
    experts whose part is summed (None: all that ``w`` holds)."""
    scores = jax.nn.sigmoid(h @ w["router"])
    _, top_i = jax.lax.top_k(scores + w["router_bias"],
                             cfg.n_experts_per_tok)
    top_w = jnp.take_along_axis(scores, top_i, -1)
    top_w = top_w / top_w.sum(-1, keepdims=True) * cfg.routed_scale
    weight = (jax.nn.one_hot(top_i, cfg.n_experts) * top_w[..., None]).sum(-2)
    held = w["moe_up"].shape[0]
    out = jnp.zeros_like(h)
    for e in (range(first_held, first_held + held) if experts is None
              else experts):
        i = e - first_held
        out = out + weight[:, e:e + 1] * _relu2(
            h, w["moe_up"][i], w["moe_down"][i])
    if shared:
        out = out + _relu2(h, w["shared_up"], w["shared_down"])
    return out


def forward_logprobs(cfg, params, tokens):
    """[T] token ids -> [T, V] log-probabilities of the next token."""
    f32 = jax.tree.map(lambda a: jnp.asarray(a, jnp.float32), params)
    group = {"M": "mamba", "E": "blocks", "*": "attn"}
    seen = dict.fromkeys(group, 0)
    with jax.default_matmul_precision("highest"):
        x = f32["embed"][jnp.asarray(tokens)]
        for kind in cfg.mixer_kinds:
            w = jax.tree.map(lambda a: a[seen[kind]], f32[group[kind]])
            seen[kind] += 1
            u = _norm(x, w["norm"], cfg.norm_eps)
            if kind == "M":
                x = x + mamba(cfg, w, u)
            elif kind == "*":
                x = x + attention(cfg, w, u)
            else:
                x = x + routed_layer(cfg, w, u,
                                     first_held=cfg.experts_held[0])
        x = _norm(x, f32["final_norm"], cfg.norm_eps)
        return jax.nn.log_softmax(x @ f32["lm_head"], -1)
