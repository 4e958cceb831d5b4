"""Plain float32 reference of the family in which a layer is a mixer and then
a dense gated MLP, each under its own norm and residual, the mixer by a list
of layer types: Mamba-2 state-space layers and attention, four published
multipliers and a head tied to the embedding (``granite-4.0-h-micro``,
``model_type`` ``granitemoehybrid`` with ``num_local_experts`` 0), whole.

This is a model family's module (benchmarks/README.md, "A model family"): a
configuration names it with ``"reference": "granite_hybrid_reference"``.  It
imports nothing of the program, and knows no cache, no state leaf, no chunks,
no snapshots and no pool: the recurrence runs a position at a time
(``lax.scan`` over positions) from a state of zeros.  A layer is made and
used at a time (one jitted function a kind of layer), at the next power of
two positions over the sequence's length: every layer is causal.  At the
published widths the attention's scores and the head are computed a block of
``BLOCK`` positions at a time (a sequence of 4,096 positions would otherwise
hold 2.1 GB of scores and 1.6 GB of logits beside their temporaries).

Equations, from the published ``config.json``; what it leaves open is this
family's convention, listed in the configuration's ``assumed``.  ``x`` is the
residual stream, ``D = hidden_size``, ``r = residual_multiplier``; norm
weights are ones and not stored, eps ``rms_norm_eps``.

- ``x_0 = embedding_multiplier * E[token]``.
- Layer ``l``, kind ``layer_types[l]``: ``x <- x + r * mixer(RMSNorm(x))``,
  then ``x <- x + r * W_o(silu(a) * b)`` with ``[a | b] = RMSNorm(x) W_i``
  (``shared_intermediate_size`` wide each; ``num_local_experts`` is 0: no
  router, no routed part).
- ``mamba`` (``H = mamba_n_heads``, ``P = mamba_d_head``, ``G =
  mamba_n_groups``, ``N = mamba_d_state``, ``K = mamba_d_conv``;
  ``mamba_expand`` is read by nothing): ``[z | xBC | dt] = u W_in``, widths
  ``H P | H P + 2 G N | H``, no bias.  ``xBC_t <- silu(b_c + sum_{j<K}
  w_c[j] * xBC_{t-K+1+j})`` (depthwise, causal, zeros before the sequence).
  Split ``x_t [H, P]``, ``B_t [G, N]``, ``C_t [G, N]``; head ``h`` uses
  group ``h // (H / G)``.  ``dt_t = softplus(dt_t + dt_bias) [H]`` (no
  clamp), ``A = -exp(A_log)``.  State ``S [H, P, N]``: **``S_t = exp(dt_t A)
  S_{t-1} + (dt_t x_t) (x) B_t``; ``y_t = S_t C_t + D_skip x_t``**.  Then
  ``y <- RMSNorm_groups(y * silu(z))`` over ``G`` groups (gate first, norm
  after; weight ones), ``out = y W_out``.
- ``attention``: ``q = u W_q`` (``num_attention_heads`` heads of
  ``hidden_size / num_attention_heads``), ``k, v = u W_k, u W_v``
  (``num_key_value_heads``), causal softmax of the scores times
  ``attention_multiplier`` (NOT ``head_dim ** -0.5``), ``out = a W_o``; no
  bias, no positional encoding (``position_embedding_type`` ``nope``;
  ``rope_theta`` is read by nothing).
- ``logits = RMSNorm(x) E^T / logits_scaling`` (``tie_word_embeddings``).

Departures from the published description: none in the equations.  The
recurrence is token by token where the published code scans in chunks of
``mamba_chunk_size`` (the same sums in another order); weights are random,
made as below; the norms' weights are ones.

``make_weights`` is the benchmark's own statement of how a seed becomes the
model the program serves (``models/ssm_moe.init_params``): truncated normal
on [-2, 2] times ``fan_in**-0.5`` rounded to bfloat16, the key split sixteen
ways: attention from a four-way split of part 0; the Mamba-2 layers from a
six-way split of part 1 (``W_in``, the convolution's weights and bias with
fan-in ``K``, ``W_out``, the time step, ``A``): ``A`` uniform in [1, 16],
the time step log-uniform in [0.001, 0.1], floored at 1e-4, ``dt_bias`` its
inverse softplus, ``D_skip`` ones (the family's own initialiser, so that
random weights decay as trained ones do and a wrong state shows); the MLPs
of all layers from a two-way split of part 2 (``W_i``, ``W_o``); the
embedding part 7, **times ``logits_scaling``**: the tied head divides by it,
and a table of ``fan_in**-0.5`` would leave the logits of a random model
flat (a standard deviation of 0.11 over 100,352 words); with it they spread
as the untied families' do (0.9); and **each branch's last matrix** (a
Mamba-2 layer's ``W_out``, an attention layer's ``W_o``, an MLP's ``W_o``)
**times ``1 / residual_multiplier``**: a branch then adds to the stream what
it adds in a model that states no multiplier.  With the table alone widened,
``embedding_multiplier`` times it put a token's own row at 1.9 a value beside
1.3 for all 80 branches of ``r`` = 0.22 together, and under the tied head that
row alone decided the logits: the first chip run read every generated token
at probability 1 in the program and the reference alike, a difference of
0.000000 on three of ``correct``'s four numbers.
"""

from __future__ import annotations

import functools
from typing import Any, Dict, Optional

import jax
import jax.numpy as jnp

from benchmarks.correctness import TYPE_BYTES

REQUIRED_KEYS = ("num_attention_heads", "num_key_value_heads",
                 "mamba_n_heads", "mamba_d_head", "mamba_n_groups",
                 "mamba_d_state", "mamba_d_conv", "shared_intermediate_size")

GROUPS = {"mamba": "mamba", "attention": "attn"}
DT_MIN, DT_MAX, DT_FLOOR = 0.001, 0.1, 1e-4
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
            ("num_local_experts", 0), ("attention_bias", False),
            ("mamba_proj_bias", False), ("mamba_conv_bias", True),
            ("hidden_act", "silu"), ("normalization_function", "rmsnorm"),
            ("position_embedding_type", "nope"),
            ("tie_word_embeddings", True))
        if config.get(key, want) != want]
    if unsupported:
        raise ValueError(f"this family's reference has no {unsupported}")
    dim, heads = int(config["hidden_size"]), int(config["num_attention_heads"])
    return {
        "kinds": _kinds(config),
        "dim": dim,
        "heads": heads,
        "kv": int(config["num_key_value_heads"]),
        "hd": dim // heads,
        "ssm_heads": int(config["mamba_n_heads"]),
        "ssm_p": int(config["mamba_d_head"]),
        "ssm_groups": int(config["mamba_n_groups"]),
        "ssm_n": int(config["mamba_d_state"]),
        "conv": int(config["mamba_d_conv"]),
        "ffn": int(config["shared_intermediate_size"]),
        "vocab": int(config["vocab_size"]),
        "eps": float(config.get("rms_norm_eps", 1e-5)),
        "embed_x": float(config.get("embedding_multiplier", 1.0)),
        "residual_x": float(config.get("residual_multiplier", 1.0)),
        "score_x": float(config["attention_multiplier"]),
        "logits_over": float(config.get("logits_scaling", 1.0)),
    }


def cache_bytes_per_token(config: Dict[str, Any]) -> int:
    """A token's keys and values of every ATTENTION layer in the type the
    configuration states for the cache: what the prefix pool holds for a
    token.  (A Mamba-2 layer caches no rows: its state is a slot's, not a
    token's, and the pool counts its snapshots apart; the program says both
    on /healthz ``config.model.cache`` and ``prefix_pool``.)"""
    layers = _kinds(config).count("attention")
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
    lm, la = kinds.count("mamba"), kinds.count("attention")
    keys = jax.random.split(key, 16)
    ks = jax.random.split(keys[2], 2)
    out_x = 1.0 / s["residual_x"]
    w = {"embed": _draw(keys[7], (v, dm), dm, s["logits_over"]),
         "mlp": {"w_in": _draw(ks[0], (len(kinds), dm, 2 * f), dm),
                 "w_out": _draw(ks[1], (len(kinds), f, dm), f, out_x)}}
    if la:
        h, kv, hd = s["heads"], s["kv"], s["hd"]
        ks = jax.random.split(keys[0], 4)
        w["attn"] = {"wq": _draw(ks[0], (la, dm, h * hd), dm),
                     "wk": _draw(ks[1], (la, dm, kv * hd), dm),
                     "wv": _draw(ks[2], (la, dm, kv * hd), dm),
                     "wo": _draw(ks[3], (la, h * hd, dm), h * hd, out_x)}
    if lm:
        heads, k = s["ssm_heads"], s["conv"]
        inner = heads * s["ssm_p"]
        conv_dim = inner + 2 * s["ssm_groups"] * s["ssm_n"]
        ks = jax.random.split(keys[1], 6)
        dt = jnp.exp(jax.random.uniform(
            ks[4], (lm, heads), jnp.float32, jnp.log(DT_MIN),
            jnp.log(DT_MAX)))
        dt = jnp.maximum(dt, DT_FLOOR)
        w["mamba"] = {
            "w_in": _draw(ks[0], (lm, dm, inner + conv_dim + heads), dm),
            "conv_w": _draw(ks[1], (lm, k, conv_dim), k),
            "conv_b": _draw(ks[2], (lm, conv_dim), k),
            "w_out": _draw(ks[3], (lm, inner, dm), inner, out_x),
            "dt_bias": dt + jnp.log(-jnp.expm1(-dt)),
            "a_log": jnp.log(jax.random.uniform(
                ks[5], (lm, heads), jnp.float32, 1.0, 16.0)),
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


def mamba(s, lw, u, bits):
    """u [T, D] (normed) -> [T, D]: the recurrence a position at a time."""
    t = u.shape[0]
    heads, p, g, n, k = (s["ssm_heads"], s["ssm_p"], s["ssm_groups"],
                         s["ssm_n"], s["conv"])
    inner = heads * p
    conv_dim = inner + 2 * g * n
    zxd = u @ _wide(lw["w_in"], bits)
    z, xbc, dt = (zxd[:, :inner], zxd[:, inner:inner + conv_dim],
                  zxd[:, inner + conv_dim:])
    padded = jnp.concatenate([jnp.zeros((k - 1, conv_dim)), xbc])
    conv_w = lw["conv_w"].astype(jnp.float32)
    xbc = jax.nn.silu(lw["conv_b"].astype(jnp.float32) + sum(
        conv_w[j] * padded[j:j + t] for j in range(k)))
    x = xbc[:, :inner].reshape(t, heads, p)
    bm = jnp.repeat(xbc[:, inner:inner + g * n].reshape(t, g, n),
                    heads // g, axis=1)
    cm = jnp.repeat(xbc[:, inner + g * n:].reshape(t, g, n),
                    heads // g, axis=1)
    dt = jax.nn.softplus(dt + lw["dt_bias"])
    a = -jnp.exp(lw["a_log"])

    def step(state, inp):
        x_t, b_t, c_t, dt_t = inp
        state = (jnp.exp(dt_t * a)[:, None, None] * state
                 + (dt_t[:, None] * x_t)[:, :, None] * b_t[:, None, :])
        return state, jnp.einsum("hpn,hn->hp", state, c_t)

    _, y = jax.lax.scan(step, jnp.zeros((heads, p, n), jnp.float32),
                        (x, bm, cm, dt))
    y = (y + x).reshape(t, inner) * jax.nn.silu(z)      # D_skip is ones
    yg = y.reshape(t, g, inner // g)
    yg = yg / jnp.sqrt(jnp.mean(yg * yg, -1, keepdims=True) + s["eps"])
    return yg.reshape(t, inner) @ _wide(lw["w_out"], bits)


def attention(s, lw, u, bits):
    t = u.shape[0]
    h, kv, hd = s["heads"], s["kv"], s["hd"]
    q = (u @ _wide(lw["wq"], bits)).reshape(t, kv, h // kv, hd)
    k = (u @ _wide(lw["wk"], bits)).reshape(t, kv, hd)
    v = (u @ _wide(lw["wv"], bits)).reshape(t, kv, hd)
    blk = min(BLOCK, t)

    def block(i):
        """Queries ``i * blk ..`` over every key."""
        q_b = jax.lax.dynamic_slice_in_dim(q, i * blk, blk)
        scores = jnp.einsum("tkgd,skd->kgts", q_b, k) * s["score_x"]
        seen = jnp.arange(t)[None, :] <= (i * blk + jnp.arange(blk))[:, None]
        probs = jax.nn.softmax(
            jnp.where(seen[None, None], scores, -jnp.inf), -1)
        return jnp.einsum("kgts,skd->tkgd", probs, v)

    out = jax.lax.map(block, jnp.arange(t // blk))
    return out.reshape(t, h * hd) @ _wide(lw["wo"], bits)


def mlp(s, lw, u, bits):
    ab = u @ _wide(lw["w_in"], bits)
    return (jax.nn.silu(ab[:, :s["ffn"]]) * ab[:, s["ffn"]:]) @ _wide(
        lw["w_out"], bits)


MIXERS = {"mamba": mamba, "attention": attention}
#: A sequence is scored at the next power of two positions at or over this:
#: every layer is causal, so what follows a sequence moves nothing in it,
#: and a handful of lengths is a handful of compiles.
LEAST_POSITIONS = 256


@functools.lru_cache(maxsize=None)
def _programs(s: "_Frozen", bits: Optional[int]):
    """One jitted function a kind of layer (+ the embedding and the head):
    a layer's weights are sliced out of the model's outside them, so the 40
    layers compile as two bodies, once a length."""
    r = s["residual_x"]

    def layer(kind):
        def run(lw, mw, x):
            with jax.default_matmul_precision("highest"):
                x = x + r * MIXERS[kind](s, lw, rms_norm(x, s["eps"]), bits)
                return x + r * mlp(s, mw, rms_norm(x, s["eps"]), bits)

        return jax.jit(run)

    def embed(table, tokens):
        return s["embed_x"] * _wide(table, bits, -1)[tokens]

    def head(table, x):
        t = x.shape[0]
        blk = min(BLOCK, t)
        wide = _wide(table, bits, -1)

        def block(rows):
            with jax.default_matmul_precision("highest"):
                return jax.nn.log_softmax(
                    rms_norm(rows, s["eps"]) @ wide.T / s["logits_over"],
                    axis=-1)

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
    return head(weights["embed"], x)[:t]
