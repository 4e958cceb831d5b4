"""Plain float32 reference of the family in which a layer is one mixer alone,
by a pattern: Mamba-2 state-space layers, routed experts of two products and
attention (``NVIDIA-Nemotron-3-Nano-30B-A3B``, ``model_type``
``nemotron_h``), as one chip's share of a stated deployment or whole.

This is a model family's module (benchmarks/README.md, "A model family"): a
configuration names it with ``"reference": "ssm_moe_reference"``.  It
imports nothing of the program, and knows no cache, no state leaf, no
chunks, no snapshots and no pool: the recurrence runs a position at a time
(``lax.scan`` over positions) from a state of zeros.  A layer is made and
used at a time (one jitted function a kind of layer), at the next power of
two positions over the sequence's length: every layer is causal.

Equations, from the published ``config.json``; what it leaves open is this
family's convention, listed in the configuration's ``assumed``.  ``x`` is the
residual stream, ``D = hidden_size``.  Every layer: ``x <- x + mixer(
RMSNorm(x))`` (norm weights are ones and not stored, eps ``norm_eps``), the
mixer by the layer's letter in ``hybrid_override_pattern``; after the last
layer ``RMSNorm``, then the untied head.  No positional encoding anywhere
(``rope_theta`` and ``partial_rotary_factor`` are read by nothing).

- ``M``, Mamba-2 (``H = mamba_num_heads``, ``P = mamba_head_dim``, ``G =
  n_groups``, ``N = ssm_state_size``, ``K = conv_kernel``; ``expand`` is read
  by nothing): ``[z | xBC | dt] = u W_in``, widths ``H P | H P + 2 G N | H``,
  no bias.  ``xBC_t <- silu(b_c + sum_{j<K} w_c[j] * xBC_{t-K+1+j})``
  (depthwise, causal, zeros before the sequence).  Split ``x_t [H, P]``,
  ``B_t [G, N]``, ``C_t [G, N]``; head ``h`` uses group ``h // (H / G)``.
  ``dt_t = softplus(dt_t + dt_bias) [H]`` (no clamp), ``A = -exp(A_log)``.
  State ``S [H, P, N]``: **``S_t = exp(dt_t A) S_{t-1} + (dt_t x_t) (x)
  B_t``; ``y_t = S_t C_t + D_skip x_t``**.  Then ``y <- RMSNorm_groups(y *
  silu(z))`` over ``G`` groups (gate first, norm after; weight ones), ``out
  = y W_out``.
- ``*``, attention: ``q = u W_q`` (``num_attention_heads`` heads of
  ``head_dim``), ``k, v = u W_k, u W_v`` (``num_key_value_heads``), causal
  softmax at scale ``head_dim ** -0.5``, ``out = a W_o``; no bias, no rotary.
- ``E``, routed experts: ``s = sigmoid(u W_r)`` over all PUBLISHED experts;
  the ``num_experts_per_tok`` of largest ``s + b`` are chosen (``b`` the
  selection bias: it moves the choice, never the weight; ``n_group`` 1 /
  ``topk_group`` 1: no group limit); their weights are ``s`` over their sum
  (``norm_topk_prob``) times ``routed_scaling_factor``; expert ``e``:
  ``relu(u W_up,e)^2 W_down,e`` (``mlp_hidden_act`` ``relu2``, no gate);
  plus the shared expert ``relu(u W_up,s)^2 W_down,s`` of width
  ``moe_shared_expert_intermediate_size`` for every token.  An assignment to
  an expert another chip holds adds nothing, here as in the program.  A loop
  over the held experts, every one over every token, masked by its weight.

The share (benchmarks/README.md, "A configuration cut to a chip's share"):
``n_routed_experts`` and ``vocab_size`` in the file count what is held;
``published_counts`` gives the published numbers, ``layer_chips`` the chips
that share a layer and ``chip_index`` which of them this is.

``make_weights`` is the benchmark's own statement of how a seed becomes the
model the program serves (``models/ssm_moe.init_params``): truncated normal
on [-2, 2] times ``fan_in**-0.5`` rounded to bfloat16, the key split sixteen
ways: attention from a four-way split of part 0; the Mamba-2 layers from a
six-way split of part 1 (``W_in``, the convolution's weights and bias with
fan-in ``K``, ``W_out``, the time step, ``A``): ``A`` uniform in [1, 16],
the time step log-uniform in [``time_step_min``, ``time_step_max``], floored
at ``time_step_floor``, ``dt_bias`` its inverse softplus, ``D_skip`` ones
(the family's own initialiser, so that random weights decay as trained ones
do and a wrong state shows); the embedding part 7, the router part 8, expert
``e`` (published index) of routed layer ``i`` from ``fold_in(fold_in(part
10/11, i), e)``, the selection bias ``0.03125 * normal`` of part 12, the
shared expert parts 14 and 15, the head ``fold_in(key, 99)``.
"""

from __future__ import annotations

import functools
from typing import Any, Dict, Optional

import jax
import jax.numpy as jnp

from benchmarks.correctness import TYPE_BYTES

REQUIRED_KEYS = ("num_attention_heads", "num_key_value_heads", "head_dim",
                 "mamba_num_heads", "mamba_head_dim", "n_groups",
                 "ssm_state_size", "conv_kernel", "moe_intermediate_size",
                 "moe_shared_expert_intermediate_size", "n_routed_experts",
                 "num_experts_per_tok")

ROUTER_BIAS_STD = 0.03125
GROUPS = {"M": "mamba", "E": "moe", "*": "attn"}


class _Frozen(dict):
    """A dict usable as a static (hashable) argument of ``jax.jit``."""

    def __hash__(self):  # type: ignore[override]
        return hash(tuple(sorted(self.items())))


def _kinds(config: Dict[str, Any]) -> str:
    n = int(config["num_hidden_layers"])
    pattern = str(config["hybrid_override_pattern"])
    if len(pattern) < n or set(pattern) - set(GROUPS):
        raise ValueError(f"{n} layers, and the pattern {pattern!r}")
    return pattern[:n]


def shapes_of(config: Dict[str, Any]) -> Dict[str, Any]:
    published = config.get("published_counts", {})
    held = int(config["n_routed_experts"])
    experts = int(published.get("n_routed_experts", held))
    chips = int(config.get("layer_chips", 1))
    if held * chips != experts:
        raise ValueError(f"{held} experts held on each of {chips} chips are "
                         f"not the {experts} published")
    unsupported = [
        key for key, want in (
            ("norm_topk_prob", True), ("n_group", 1), ("topk_group", 1),
            ("attention_bias", False), ("mlp_bias", False),
            ("use_bias", False), ("mamba_proj_bias", False),
            ("use_conv_bias", True), ("mlp_hidden_act", "relu2"),
            ("mamba_hidden_act", "silu"), ("n_shared_experts", 1),
            ("tie_word_embeddings", False))
        if config.get(key, want) != want]
    if unsupported:
        raise ValueError(f"this family's reference has no {unsupported}")
    heads, p = int(config["mamba_num_heads"]), int(config["mamba_head_dim"])
    g, n = int(config["n_groups"]), int(config["ssm_state_size"])
    return {
        "kinds": _kinds(config),
        "dim": int(config["hidden_size"]),
        "heads": int(config["num_attention_heads"]),
        "kv": int(config["num_key_value_heads"]),
        "hd": int(config["head_dim"]),
        "ssm_heads": heads, "ssm_p": p, "ssm_groups": g, "ssm_n": n,
        "conv": int(config["conv_kernel"]),
        "dt_min": float(config.get("time_step_min", 0.001)),
        "dt_max": float(config.get("time_step_max", 0.1)),
        "dt_floor": float(config.get("time_step_floor", 1e-4)),
        "expert_ffn": int(config["moe_intermediate_size"]),
        "shared_ffn": int(config["moe_shared_expert_intermediate_size"]),
        "experts": experts,
        "held": held,
        "first_held": int(config.get("chip_index", 0)) * held,
        "top_k": int(config["num_experts_per_tok"]),
        "routed_scale": float(config.get("routed_scaling_factor") or 1.0),
        "vocab": int(config["vocab_size"]),
        "eps": float(config.get("norm_eps",
                                config.get("layer_norm_epsilon", 1e-5))),
    }


def cache_bytes_per_token(config: Dict[str, Any]) -> int:
    """A token's keys and values of every held ATTENTION layer in the type
    the configuration states for the cache: what the prefix pool holds for a
    token.  (A Mamba-2 layer caches no rows: its state is a slot's, not a
    token's, and the pool counts its snapshots apart; the program says both
    on /healthz ``config.model.cache`` and ``prefix_pool``.)"""
    layers = _kinds(config).count("*")
    per_layer = 2 * int(config["num_key_value_heads"]) * int(
        config["head_dim"])
    return int(layers * per_layer
               * TYPE_BYTES[config["precision"]["kv_cache"]])


# ---- the model of a seed ------------------------------------------------------

def _draw(key, shape, fan_in):
    w = jax.random.truncated_normal(key, -2, 2, shape, jnp.float32)
    return (w * fan_in ** -0.5).astype(jnp.bfloat16)


def _make_weights(s, key):
    dm, v = s["dim"], s["vocab"]
    kinds = s["kinds"]
    lm, le, la = kinds.count("M"), kinds.count("E"), kinds.count("*")
    keys = jax.random.split(key, 16)
    w = {"embed": _draw(keys[7], (v, dm), dm),
         "lm_head": _draw(jax.random.fold_in(key, 99), (dm, v), dm)}
    if la:
        h, kv, hd = s["heads"], s["kv"], s["hd"]
        ks = jax.random.split(keys[0], 4)
        w["attn"] = {"wq": _draw(ks[0], (la, dm, h * hd), dm),
                     "wk": _draw(ks[1], (la, dm, kv * hd), dm),
                     "wv": _draw(ks[2], (la, dm, kv * hd), dm),
                     "wo": _draw(ks[3], (la, h * hd, dm), h * hd)}
    if lm:
        heads, k = s["ssm_heads"], s["conv"]
        inner = heads * s["ssm_p"]
        conv_dim = inner + 2 * s["ssm_groups"] * s["ssm_n"]
        ks = jax.random.split(keys[1], 6)
        dt = jnp.exp(jax.random.uniform(
            ks[4], (lm, heads), jnp.float32, jnp.log(s["dt_min"]),
            jnp.log(s["dt_max"])))
        dt = jnp.maximum(dt, s["dt_floor"])
        w["mamba"] = {
            "w_in": _draw(ks[0], (lm, dm, inner + conv_dim + heads), dm),
            "conv_w": _draw(ks[1], (lm, k, conv_dim), k),
            "conv_b": _draw(ks[2], (lm, conv_dim), k),
            "w_out": _draw(ks[3], (lm, inner, dm), inner),
            "dt_bias": dt + jnp.log(-jnp.expm1(-dt)),
            "a_log": jnp.log(jax.random.uniform(
                ks[5], (lm, heads), jnp.float32, 1.0, 16.0)),
        }
    if le:
        e, fe, fs, held, first = (s["experts"], s["expert_ffn"],
                                  s["shared_ffn"], s["held"], s["first_held"])

        def experts(k, shape, fan_in):
            def one(i):
                ke = jax.random.fold_in(jax.random.fold_in(k, i // held),
                                        first + i % held)
                return _draw(ke, shape, fan_in)

            return jax.lax.map(one, jnp.arange(le * held)).reshape(
                (le, held) + shape)

        w["moe"] = {"router": _draw(keys[8], (le, dm, e), dm),
                    "up": experts(keys[10], (dm, fe), dm),
                    "down": experts(keys[11], (fe, dm), fe),
                    "bias": ROUTER_BIAS_STD * jax.random.normal(
                        keys[12], (le, e), jnp.float32),
                    "shared_up": _draw(keys[14], (le, dm, fs), dm),
                    "shared_down": _draw(keys[15], (le, fs, dm), fs)}
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
    scores = jnp.einsum("tkgd,skd->kgts", q, k) * hd ** -0.5
    seen = jnp.arange(t)[None, :] <= jnp.arange(t)[:, None]
    probs = jax.nn.softmax(jnp.where(seen[None, None], scores, -jnp.inf), -1)
    out = jnp.einsum("kgts,skd->tkgd", probs, v)
    return out.reshape(t, h * hd) @ _wide(lw["wo"], bits)


def _relu2(u, up, down):
    return jnp.square(jax.nn.relu(u @ up)) @ down


def routed(s, lw, u, bits):
    """The routed layer's output for the share's held experts, and the
    shared expert's."""
    scores = jax.nn.sigmoid(u @ _wide(lw["router"], bits))      # [T, E]
    _, top_i = jax.lax.top_k(scores + lw["bias"], s["top_k"])
    top_w = jnp.take_along_axis(scores, top_i, axis=-1)
    top_w = top_w / top_w.sum(-1, keepdims=True) * s["routed_scale"]
    weight = (jax.nn.one_hot(top_i, s["experts"]) * top_w[..., None]).sum(-2)

    def one(e, out):
        at = functools.partial(jax.lax.dynamic_index_in_dim, index=e, axis=0,
                               keepdims=False)
        y = _relu2(u, _wide(at(lw["up"]), bits), _wide(at(lw["down"]), bits))
        w = jax.lax.dynamic_index_in_dim(weight, s["first_held"] + e, axis=1)
        return out + w * y

    out = jax.lax.fori_loop(0, s["held"], one, jnp.zeros_like(u))
    return out + _relu2(u, _wide(lw["shared_up"], bits),
                        _wide(lw["shared_down"], bits))


MIXERS = {"M": mamba, "E": routed, "*": attention}
#: A sequence is scored at the next power of two positions at or over this:
#: every layer is causal, so what follows a sequence moves nothing in it,
#: and a handful of lengths is a handful of compiles.
LEAST_POSITIONS = 256


@functools.lru_cache(maxsize=None)
def _programs(s: "_Frozen", bits: Optional[int]):
    """One jitted function a kind of layer (+ the embedding and the head):
    a layer's weights are sliced out of the model's outside them, so the 13
    layers of a share compile as three bodies, once a length."""
    def layer(kind):
        def run(lw, x):
            with jax.default_matmul_precision("highest"):
                return x + MIXERS[kind](s, lw, rms_norm(x, s["eps"]), bits)

        return jax.jit(run)

    def embed(table, tokens):
        return _wide(table, bits, -1)[tokens]

    def head(lm_head, x):
        with jax.default_matmul_precision("highest"):
            return jax.nn.log_softmax(
                rms_norm(x, s["eps"]) @ _wide(lm_head, bits), axis=-1)

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
    for kind in shapes["kinds"]:
        lw = {k: a[seen[kind]] for k, a in weights[GROUPS[kind]].items()}
        seen[kind] += 1
        x = layers[kind](lw, x)
    return head(weights["lm_head"], x)[:t]
