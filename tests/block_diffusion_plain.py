"""The block-generation architecture's forward, plainly: ``jax.numpy``,
float32, ``highest``, one sequence, no cache, no batching, no kernels, no
grouped product (a loop over the experts), on the PROGRAM's parameter tree,
so that a test compares arithmetic and not two draws of the weights.

Equations: benchmarks/block_diffusion_reference.py's text (the benchmark's
copy of this family, which draws its own weights).  ``forward`` is the
model under the block-causal mask (``j // B <= i // B``); ``denoise_logprobs``
is what the serve path's ``token_logprobs`` means: for the token at
position ``q`` (offset ``r`` of its block, group ``g = r // k``), the
log-softmax of the logits at ``q`` in a forward where ``q``'s block holds
its true tokens at offsets ``< g * k`` and ``mask_token_id`` from there on,
over clean earlier blocks.  For each ``g`` one forward over the clean
sequence and its noisy copy ``[clean ; noisy_g]``: a noisy position sees
the clean blocks before its own and its own noisy block.
"""

import jax
import jax.numpy as jnp


def _norm(x, w, eps):
    return x / jnp.sqrt(jnp.mean(x * x, -1, keepdims=True) + eps) * w


def _rope(x, pos, theta):
    """x [T, heads, D]: rotate-half pairs over all D columns."""
    d = x.shape[-1]
    freqs = 1.0 / theta ** (jnp.arange(0, d, 2, dtype=jnp.float32) / d)
    ang = pos[:, None].astype(jnp.float32) * freqs
    cos, sin = jnp.cos(ang)[:, None], jnp.sin(ang)[:, None]
    a, b = x[..., : d // 2], x[..., d // 2:]
    return jnp.concatenate([a * cos - b * sin, b * cos + a * sin], -1)


def _attention(cfg, w, x, pos, seen):
    """``seen [T, T]``: which keys each query attends to."""
    t = x.shape[0]
    h, kv, d = cfg.n_heads, cfg.n_kv_heads, cfg.head_dim
    hin = _norm(x, w["attn_norm"], cfg.norm_eps)
    q = (hin @ w["wq"]).reshape(t, h, d)
    k = (hin @ w["wk"]).reshape(t, kv, d)
    v = (hin @ w["wv"]).reshape(t, kv, d)
    q = _rope(_norm(q, w["q_norm"], cfg.norm_eps), pos, cfg.rope_theta)
    k = _rope(_norm(k, w["k_norm"], cfg.norm_eps), pos, cfg.rope_theta)
    k, v = jnp.repeat(k, h // kv, 1), jnp.repeat(v, h // kv, 1)
    scores = jnp.einsum("thd,shd->hts", q, k) * d ** -0.5
    p = jax.nn.softmax(jnp.where(seen, scores, -jnp.inf), -1)
    return x + jnp.einsum("hts,shd->thd", p, v).reshape(t, -1) @ w["wo"]


def _routed(cfg, w, h):
    p = jax.nn.softmax(h @ w["router"], -1)
    top_w, top_i = jax.lax.top_k(p, cfg.n_experts_per_tok)
    top_w = top_w / top_w.sum(-1, keepdims=True)
    weight = (jax.nn.one_hot(top_i, cfg.n_experts) * top_w[..., None]).sum(-2)
    out = jnp.zeros_like(h)
    for e in range(cfg.n_experts):
        y = (jax.nn.silu(h @ w["moe_gate"][e]) * (h @ w["moe_up"][e])
             ) @ w["moe_down"][e]
        out = out + weight[:, e:e + 1] * y
    return out


def _logits(cfg, params, tokens, pos, seen):
    f32 = jax.tree.map(lambda a: jnp.asarray(a, jnp.float32), params)
    with jax.default_matmul_precision("highest"):
        x = f32["embed"][jnp.asarray(tokens)]
        for l in range(cfg.n_layers):
            w = jax.tree.map(lambda a: a[l], f32["blocks"])
            x = _attention(cfg, w, x, pos, seen)
            x = x + _routed(cfg, w, _norm(x, w["mlp_norm"], cfg.norm_eps))
        x = _norm(x, f32["final_norm"], cfg.norm_eps)
        return x @ f32["lm_head"]


def forward(cfg, params, tokens):
    """[T] token ids -> [T, V] logits under the block-causal mask."""
    pos = jnp.arange(len(tokens))
    blk = pos // cfg.block_length
    return _logits(cfg, params, tokens, pos, blk[None, :] <= blk[:, None])


def denoise_logprobs(cfg, params, tokens):
    """[T] token ids -> [T + 1, V]: row ``q`` is the distribution the serve
    path gives position ``q`` (``q = T`` : the next token's, where ``T``
    need not end a block)."""
    n, b = len(tokens), cfg.block_length
    k = b // cfg.denoise_steps
    t = -(-(n + 1) // b) * b  # whole blocks that cover position n
    clean = jnp.zeros((t,), jnp.int32).at[:n].set(jnp.asarray(tokens))
    pos = jnp.arange(t)
    blk, off = pos // b, pos % b
    pos2 = jnp.concatenate([pos, pos])
    # [clean ; noisy]: clean sees clean block-causally; noisy sees the clean
    # blocks before its own and its own noisy block
    cc = blk[None, :] <= blk[:, None]
    nc = blk[None, :] < blk[:, None]
    nn = blk[None, :] == blk[:, None]
    seen = jnp.block([[cc, jnp.zeros_like(cc)], [nc, nn]])
    out = jnp.zeros((t, params["lm_head"].shape[-1]), jnp.float32)
    for g in range(cfg.denoise_steps):
        noisy = jnp.where(off < g * k, clean, cfg.mask_token_id)
        logits = _logits(cfg, params, jnp.concatenate([clean, noisy]), pos2,
                         seen)[t:]
        out = jnp.where((off // k == g)[:, None], logits, out)
    return jax.nn.log_softmax(out[: n + 1], -1)
