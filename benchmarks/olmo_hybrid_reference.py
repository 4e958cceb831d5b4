"""Plain float32 reference of the family in which three layers in four are
gated delta-rule layers (a matrix state a head) and the fourth is full
attention, each followed by a dense gated MLP, in OLMo's block: no norm
before a branch, an RMSNorm on its output (``Olmo-Hybrid-7B``, ``model_type``
``olmo_hybrid``).

This is a model family's module (benchmarks/README.md, "A model family"): a
configuration names it with ``"reference": "olmo_hybrid_reference"``.  It
imports nothing of the program, and knows no cache, no state leaf, no chunks,
no snapshots and no pool: the recurrence runs a position at a time
(``lax.scan`` over positions) from a state of zeros.  A layer is made and
used at a time (one jitted function a kind of layer), at the next power of
two positions over the sequence's length: every layer is causal.  The
attention's scores and the head are computed a block of ``BLOCK`` positions
at a time.

Equations.  ``x`` is the residual stream ``[T, D]``, ``D = hidden_size``;
norm weights are ones and not stored, eps ``rms_norm_eps``.

- ``x_0 = E[token]``.
- Layer ``l``, kind ``layer_types[l]``: ``x <- x + RMSNorm(mixer(x))``, then
  ``x <- x + RMSNorm(W_o(silu(a) * b))`` with ``[a | b] = x W_i``
  (``intermediate_size`` wide each).  No norm stands before a branch.
- ``linear_attention`` (``H = linear_num_value_heads =
  linear_num_key_heads``, ``Dk = linear_key_head_dim``, ``Dv =
  linear_value_head_dim``, ``K = linear_conv_kernel_dim``):
  ``q, k, v = silu(conv(x W_q)), silu(conv(x W_k)), silu(conv(x W_v))``
  (widths ``H Dk | H Dk | H Dv``; ``conv`` depthwise, causal over ``K``
  positions, zeros before the sequence, no bias); a head: ``q <- q /
  sqrt(|q|^2 + 1e-6) * Dk ** -0.5``, ``k <- k / sqrt(|k|^2 + 1e-6)``;
  ``beta = 2 sigmoid(x W_b)`` (``linear_allow_neg_eigval``; else ``sigmoid``),
  ``g = -exp(A_log) softplus(x W_a + dt_bias)``, both ``[H]``.  State ``S
  [H, Dk, Dv]`` from zeros: **``S <- e^g S``; ``S <- S + k (x) (beta (v -
  S^T k))``; ``o = S^T q``**.  Then ``y = RMSNorm_Dv(o) * silu(x W_z)`` a
  head (norm first, gate after; weight ones), ``out = y W_o``.
- ``full_attention``: ``q = RMSNorm(x W_q)``, ``k = RMSNorm(x W_k)`` over the
  WHOLE width (before the heads are split), ``v = x W_v``;
  ``num_attention_heads`` query heads on ``num_key_value_heads`` KV heads of
  ``hidden_size / num_attention_heads``, causal softmax of the scores times
  ``head_dim ** -0.5``, ``out = a W_o``; no bias, no positional encoding.
- ``logits = RMSNorm(x) W_head`` (``tie_word_embeddings`` false).

Departures from the published description, each listed in the
configuration's ``assumed`` with where it came from: the block (no norm
before a branch, one after), the whole-width QK norm and ``W_q``, ``W_k``,
``W_v``, ``W_z``, ``W_a``, ``W_b`` as six matrices are the family's
(``transformers``' ``olmo3`` block and ``qwen3_next`` gated delta net: the
config's ``linear_*`` keys are that module's); ``rope_parameters.rope_theta``
null is read as no rotary in the full layers.  The recurrence is token by
token where the published code scans in chunks of 64 (the same sums in
another order); weights are random, made as below.

``make_weights`` is the benchmark's own statement of how a seed becomes the
model the program serves (``models/ssm_moe.init_params``): truncated normal
on [-2, 2] times ``fan_in ** -0.5`` rounded to bfloat16, the key split
sixteen ways: attention from a four-way split of part 0; the MLPs of all
layers from a two-way split of part 2; the delta layers from a ten-way split
of part 3 (``W_q``, ``W_k``, ``W_v``, ``W_z``, ``W_a``, ``W_b``, the
convolution with fan-in ``K``, ``W_o``, the time step, ``A``): ``A`` uniform
in [1, 16], the time step log-uniform in [0.001, 0.1], ``dt_bias`` its
inverse softplus (the family's own initialiser, so that a random model's
state decays as a trained one's and a wrong state shows); the embedding part
7 **times ``hidden_size ** 0.5``** (rows of unit RMS: every branch adds a
normed output of unit RMS to the stream, and rows of ``fan_in ** -0.5``
would be a sixtieth of the first of them); the head from the key folded with
99.
"""

from __future__ import annotations

import functools
from typing import Any, Dict, Optional

import jax
import jax.numpy as jnp

from benchmarks.correctness import TYPE_BYTES

REQUIRED_KEYS = ("num_attention_heads", "num_key_value_heads",
                 "intermediate_size", "linear_num_key_heads",
                 "linear_num_value_heads", "linear_key_head_dim",
                 "linear_value_head_dim", "linear_conv_kernel_dim")

GROUPS = {"linear_attention": "delta", "full_attention": "attn"}
DT_MIN, DT_MAX = 0.001, 0.1
UNIT_EPS = 1e-6
#: Positions a block of the attention's queries and of the head's rows.
BLOCK = 1024


class _Frozen(dict):
    """A dict usable as a static (hashable) argument of ``jax.jit``."""

    def __hash__(self):  # type: ignore[override]
        return hash(tuple(sorted(self.items())))


def _kinds(config: Dict[str, Any]):
    n = int(config["num_hidden_layers"])
    kinds = tuple(config["layer_types"])
    if len(kinds) != n or set(kinds) - set(GROUPS):
        raise ValueError(f"{n} layers, and the layer types {kinds!r}")
    return kinds


def shapes_of(config: Dict[str, Any]) -> Dict[str, Any]:
    unsupported = [
        key for key, want in (
            ("attention_bias", False), ("hidden_act", "silu"),
            ("tie_word_embeddings", False),
            ("linear_num_key_heads", config["linear_num_value_heads"]))
        if config.get(key, want) != want]
    if (config.get("rope_parameters") or {}).get("rope_theta") is not None:
        unsupported.append("rope_parameters.rope_theta")
    if unsupported:
        raise ValueError(f"this family's reference has no {unsupported}")
    dim, heads = int(config["hidden_size"]), int(config["num_attention_heads"])
    return {
        "kinds": _kinds(config),
        "dim": dim,
        "heads": heads,
        "kv": int(config["num_key_value_heads"]),
        "hd": dim // heads,
        "d_heads": int(config["linear_num_value_heads"]),
        "dk": int(config["linear_key_head_dim"]),
        "dv": int(config["linear_value_head_dim"]),
        "conv": int(config["linear_conv_kernel_dim"]),
        "beta_x": 2.0 if config.get("linear_allow_neg_eigval") else 1.0,
        "ffn": int(config["intermediate_size"]),
        "vocab": int(config["vocab_size"]),
        "eps": float(config.get("rms_norm_eps", 1e-6)),
    }


def cache_bytes_per_token(config: Dict[str, Any]) -> int:
    """A token's keys and values of every FULL-ATTENTION layer in the type
    the configuration states for the cache: what the prefix pool holds for a
    token.  (A delta layer caches no rows: its state is a slot's, not a
    token's, and the pool counts its snapshots apart; the program says both
    on /healthz ``config.model.cache`` and ``prefix_pool``.)"""
    layers = _kinds(config).count("full_attention")
    head = int(config["hidden_size"]) // int(config["num_attention_heads"])
    return int(layers * 2 * int(config["num_key_value_heads"]) * head
               * TYPE_BYTES[config["precision"]["kv_cache"]])


# ---- the model of a seed ------------------------------------------------------

def _draw(key, shape, fan_in, times=1.0):
    w = jax.random.truncated_normal(key, -2, 2, shape, jnp.float32)
    return (w * (times * fan_in ** -0.5)).astype(jnp.bfloat16)


def _make_weights(s, key):
    dm, v, f = s["dim"], s["vocab"], s["ffn"]
    kinds = s["kinds"]
    ld, la = kinds.count("linear_attention"), kinds.count("full_attention")
    keys = jax.random.split(key, 16)
    ks = jax.random.split(keys[2], 2)
    w = {"embed": _draw(keys[7], (v, dm), dm, dm ** 0.5),
         "lm_head": _draw(jax.random.fold_in(key, 99), (dm, v), dm),
         "mlp": {"w_in": _draw(ks[0], (len(kinds), dm, 2 * f), dm),
                 "w_out": _draw(ks[1], (len(kinds), f, dm), f)}}
    if la:
        h, kv, hd = s["heads"], s["kv"], s["hd"]
        ks = jax.random.split(keys[0], 4)
        w["attn"] = {"wq": _draw(ks[0], (la, dm, h * hd), dm),
                     "wk": _draw(ks[1], (la, dm, kv * hd), dm),
                     "wv": _draw(ks[2], (la, dm, kv * hd), dm),
                     "wo": _draw(ks[3], (la, h * hd, dm), h * hd)}
    if ld:
        heads, dk, dv, k = s["d_heads"], s["dk"], s["dv"], s["conv"]
        ks = jax.random.split(keys[3], 10)
        dt = jnp.exp(jax.random.uniform(
            ks[8], (ld, heads), jnp.float32, jnp.log(DT_MIN),
            jnp.log(DT_MAX)))
        w["delta"] = {
            "wq": _draw(ks[0], (ld, dm, heads * dk), dm),
            "wk": _draw(ks[1], (ld, dm, heads * dk), dm),
            "wv": _draw(ks[2], (ld, dm, heads * dv), dm),
            "wz": _draw(ks[3], (ld, dm, heads * dv), dm),
            "wa": _draw(ks[4], (ld, dm, heads), dm),
            "wb": _draw(ks[5], (ld, dm, heads), dm),
            "conv_w": _draw(ks[6], (ld, k, heads * (2 * dk + dv)), k),
            "wo": _draw(ks[7], (ld, heads * dv, dm), heads * dv),
            "dt_bias": dt + jnp.log(-jnp.expm1(-dt)),
            "a_log": jnp.log(jax.random.uniform(
                ks[9], (ld, heads), jnp.float32, 1.0, 16.0)),
        }
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


def _conv(w, x):
    """Depthwise causal convolution over ``w.shape[0]`` positions, zeros
    before the sequence, no bias, then silu: ``x [T, C]``, ``w [K, C]``."""
    k, t = w.shape[0], x.shape[0]
    padded = jnp.concatenate([jnp.zeros((k - 1, x.shape[1])), x])
    return jax.nn.silu(sum(w[j] * padded[j:j + t] for j in range(k)))


def delta(s, lw, x, bits):
    """x [T, D] (the stream as it is) -> [T, D]: the delta rule a position
    at a time."""
    t = x.shape[0]
    h, dk, dv = s["d_heads"], s["dk"], s["dv"]
    conv_w = lw["conv_w"].astype(jnp.float32)
    q = _conv(conv_w[:, :h * dk], x @ _wide(lw["wq"], bits))
    k = _conv(conv_w[:, h * dk:2 * h * dk], x @ _wide(lw["wk"], bits))
    v = _conv(conv_w[:, 2 * h * dk:], x @ _wide(lw["wv"], bits))
    q, k, v = (q.reshape(t, h, dk), k.reshape(t, h, dk), v.reshape(t, h, dv))
    q = q / jnp.sqrt(jnp.sum(q * q, -1, keepdims=True) + UNIT_EPS) * dk ** -0.5
    k = k / jnp.sqrt(jnp.sum(k * k, -1, keepdims=True) + UNIT_EPS)
    beta = s["beta_x"] * jax.nn.sigmoid(x @ _wide(lw["wb"], bits))
    g = -jnp.exp(lw["a_log"]) * jax.nn.softplus(
        x @ _wide(lw["wa"], bits) + lw["dt_bias"])

    def step(state, inp):
        q_t, k_t, v_t, g_t, b_t = inp
        state = jnp.exp(g_t)[:, None, None] * state
        d_t = b_t[:, None] * (v_t - jnp.einsum("hkv,hk->hv", state, k_t))
        state = state + k_t[:, :, None] * d_t[:, None, :]
        return state, jnp.einsum("hkv,hk->hv", state, q_t)

    _, o = jax.lax.scan(step, jnp.zeros((h, dk, dv), jnp.float32),
                        (q, k, v, g, beta))
    z = (x @ _wide(lw["wz"], bits)).reshape(t, h, dv)
    y = rms_norm(o, s["eps"]) * jax.nn.silu(z)
    return y.reshape(t, h * dv) @ _wide(lw["wo"], bits)


def attention(s, lw, x, bits):
    t = x.shape[0]
    h, kv, hd = s["heads"], s["kv"], s["hd"]
    q = rms_norm(x @ _wide(lw["wq"], bits), s["eps"]).reshape(
        t, kv, h // kv, hd)
    k = rms_norm(x @ _wide(lw["wk"], bits), s["eps"]).reshape(t, kv, hd)
    v = (x @ _wide(lw["wv"], bits)).reshape(t, kv, hd)
    blk = min(BLOCK, t)

    def block(i):
        """Queries ``i * blk ..`` over every key."""
        q_b = jax.lax.dynamic_slice_in_dim(q, i * blk, blk)
        scores = jnp.einsum("tkgd,skd->kgts", q_b, k) * hd ** -0.5
        seen = jnp.arange(t)[None, :] <= (i * blk + jnp.arange(blk))[:, None]
        probs = jax.nn.softmax(
            jnp.where(seen[None, None], scores, -jnp.inf), -1)
        return jnp.einsum("kgts,skd->tkgd", probs, v)

    out = jax.lax.map(block, jnp.arange(t // blk))
    return out.reshape(t, h * hd) @ _wide(lw["wo"], bits)


def mlp(s, lw, x, bits):
    ab = x @ _wide(lw["w_in"], bits)
    return (jax.nn.silu(ab[:, :s["ffn"]]) * ab[:, s["ffn"]:]) @ _wide(
        lw["w_out"], bits)


MIXERS = {"linear_attention": delta, "full_attention": attention}
#: A sequence is scored at the next power of two positions at or over this:
#: every layer is causal, so what follows a sequence moves nothing in it,
#: and a handful of lengths is a handful of compiles.
LEAST_POSITIONS = 256


@functools.lru_cache(maxsize=None)
def _programs(s: "_Frozen", bits: Optional[int]):
    """One jitted function a kind of layer (+ the embedding and the head):
    a layer's weights are sliced out of the model's outside them, so the
    layers compile as two bodies, once a length."""

    def layer(kind):
        def run(lw, mw, x):
            with jax.default_matmul_precision("highest"):
                x = x + rms_norm(MIXERS[kind](s, lw, x, bits), s["eps"])
                return x + rms_norm(mlp(s, mw, x, bits), s["eps"])

        return jax.jit(run)

    def embed(table, tokens):
        return _wide(table, bits, -1)[tokens]

    def head(w_head, x):
        t = x.shape[0]
        blk = min(BLOCK, t)
        wide = _wide(w_head, bits)

        def block(rows):
            with jax.default_matmul_precision("highest"):
                return jax.nn.log_softmax(
                    rms_norm(rows, s["eps"]) @ wide, axis=-1)

        return jax.lax.map(block, x.reshape(t // blk, blk, -1)).reshape(t, -1)

    return ({kind: layer(kind) for kind in MIXERS}, jax.jit(embed),
            jax.jit(head))


def forward_logprobs(shapes: Dict[str, Any], weights: Dict[str, Any], tokens,
                     weight_bits: Optional[int] = None) -> jnp.ndarray:
    """log-softmax of the next-token logits at every position: [T, vocab].
    ``weight_bits`` None is the model as the configuration states it; a
    number is the control: the same arithmetic on weights rounded to that
    many bits."""
    bits = None if weight_bits is None else int(weight_bits)
    layers, embed, head = _programs(_Frozen(shapes), bits)
    tokens = jnp.asarray(tokens, jnp.int32)
    t = tokens.shape[0]
    padded = LEAST_POSITIONS
    while padded < t:
        padded *= 2
    x = embed(weights["embed"], jnp.pad(tokens, (0, padded - t)))
    seen = dict.fromkeys(GROUPS, 0)
    for l, kind in enumerate(shapes["kinds"]):
        lw = {k: a[seen[kind]] for k, a in weights[GROUPS[kind]].items()}
        mw = {k: a[l] for k, a in weights["mlp"].items()}
        seen[kind] += 1
        x = layers[kind](lw, mw, x)
    return head(weights["lm_head"], x)[:t]
