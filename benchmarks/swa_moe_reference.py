"""Plain float32 reference of the family that mixes window and full attention
layers over routed experts (``MiMo-V2-Flash``), as one chip's share of a
stated deployment or whole.

This is a model family's module (benchmarks/README.md, "A model family"): a
configuration names it with ``"reference": "swa_moe_reference"``.  It
imports nothing of the program, and knows no cache, no ring and no pool: a
window layer is a full layer with one more condition in its mask.

Equations, from the published ``config.json`` of XiaomiMiMo/MiMo-V2-Flash;
what the config leaves open is this family's convention, listed in the
configuration's ``assumed``.  ``h`` the RMS-normed input of a layer (norm
weights are ones and not stored, eps ``layernorm_epsilon``); the kind of
layer ``l`` is ``hybrid_layer_pattern[l]`` (0 full, 1 window):

- ``q = W_q h`` [H, head_dim], ``k = W_k h`` [K, head_dim], ``v =
  attention_value_scale * (W_v h)`` [K, v_head_dim]; ``K`` is
  ``num_key_value_heads`` in a full layer and ``swa_num_key_value_heads`` in
  a window layer; ``H / K`` query heads share a KV head.
- rope on the leading ``floor(partial_rotary_factor * head_dim)`` columns
  (made even) of every query and key head, rotate-half pairing within those
  columns, theta ``rope_theta`` (full) or ``swa_rope_theta`` (window); the
  other columns pass.
- ``s_ij = q_i . k_j / sqrt(head_dim)``, ``j <= i``; a window layer also
  ``i - j < sliding_window``.
- full layers: ``p = softmax_j(s)``.  Window layers
  (``add_swa_attention_sink_bias``): one learned logit ``b_h`` a head joins
  the denominator and carries no value, ``p_ij = exp(s_ij) / (exp(b_h) +
  sum_j' exp(s_ij'))``.
- ``o = W_o [P v]`` [H * v_head_dim -> hidden]; residual.

Feed-forward: a layer with ``moe_layer_freq[l] == 0`` one SwiGLU of
``intermediate_size``; the others routed: ``s = sigmoid(W_r h)`` over all
PUBLISHED experts in float32; the ``num_experts_per_tok`` experts of largest
``s + b`` are chosen (``b`` the selection bias of ``topk_method``
``noaux_tc``: it moves the choice, never the weight); their weights are the
unbiased ``s`` over their sum (``norm_topk_prob``), times
``routed_scaling_factor`` (null: 1); ``y = sum over the chosen experts HELD
HERE of w_e * SwiGLU_e(h)``; no shared expert.  An assignment to an expert
another chip holds adds nothing, here as in the program.  A loop over the
held experts, every one over every token, masked by its weight.

Left out: the multi-token-prediction layers (the config has no key for
them) and any QK norm (likewise).

The share (benchmarks/README.md, "A configuration cut to a chip's share"):
``n_routed_experts`` and ``vocab_size`` in the file count what is held;
``published_counts`` gives the published numbers, ``layer_chips`` the chips
that share a layer and ``chip_index`` which of them this is.

``make_weights`` is the benchmark's own statement of how a seed becomes the
model the program serves (``models/swa.init_params``): truncated normal on
[-2, 2] times ``fan_in**-0.5`` rounded to bfloat16, the key split sixteen
ways: full layers' attention from a four-way split of part 0, window layers'
of part 1, their sinks a standard normal of part 2; the dense feed-forward
parts 4-6, the embedding part 7, the router part 8, expert ``e`` (published
index) of routed layer ``i`` from ``fold_in(fold_in(part 9/10/11, i), e)``,
the selection bias ``0.03125 * normal`` of part 12, the head
``fold_in(key, 99)``.

Attention is computed a block of queries at a time, and an expert at a time,
so that seven layers at published widths and a sequence of eight thousand
tokens fit one chip beside the weights.
"""

from __future__ import annotations

import functools
from typing import Any, Dict, Optional

import jax
import jax.numpy as jnp

from benchmarks.correctness import TYPE_BYTES

REQUIRED_KEYS = ("num_attention_heads", "num_key_value_heads",
                 "swa_num_key_value_heads", "head_dim", "v_head_dim",
                 "sliding_window", "intermediate_size",
                 "moe_intermediate_size", "n_routed_experts",
                 "num_experts_per_tok")

ROUTER_BIAS_STD = 0.03125
#: Queries scored at once (a sequence is padded to a multiple of 256 by the
#: reference's process).
QUERY_BLOCK = 256


class _Frozen(dict):
    """A dict usable as a static (hashable) argument of ``jax.jit``."""

    def __hash__(self):  # type: ignore[override]
        return hash(tuple(sorted(self.items())))


def _kinds(config: Dict[str, Any]):
    """(attention kind, feed-forward kind) of each held layer."""
    n = int(config["num_hidden_layers"])
    pattern, freq = config["hybrid_layer_pattern"], config["moe_layer_freq"]
    if len(pattern) < n or len(freq) < n:
        raise ValueError(f"{n} layers, and patterns of {len(pattern)} and "
                         f"{len(freq)}")
    return (tuple("window" if k else "full" for k in pattern[:n]),
            tuple("moe" if k else "dense" for k in freq[:n]))


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
            ("scoring_func", "sigmoid"), ("norm_topk_prob", True),
            ("n_group", 1), ("topk_group", 1),
            ("add_full_attention_sink_bias", False),
            ("attention_bias", False))
        if config.get(key, want) != want]
    if config.get("n_shared_experts"):
        unsupported.append("n_shared_experts")
    if unsupported:
        raise ValueError(f"this family's reference has no {unsupported}")
    attn, ffn = _kinds(config)
    dk = int(config["head_dim"])
    return {
        "attn": attn,
        "ffn": ffn,
        "dim": int(config["hidden_size"]),
        "heads": int(config["num_attention_heads"]),
        "kv_full": int(config["num_key_value_heads"]),
        "kv_window": int(config["swa_num_key_value_heads"]),
        "dk": dk,
        "dv": int(config["v_head_dim"]),
        "window": int(config["sliding_window"]),
        "rotary": int(float(config.get("partial_rotary_factor", 1.0)) * dk)
        // 2 * 2,
        "theta_full": float(config["rope_theta"]),
        "theta_window": float(config.get("swa_rope_theta",
                                         config["rope_theta"])),
        "value_scale": float(config.get("attention_value_scale", 1.0)),
        "sink": bool(config.get("add_swa_attention_sink_bias", False)),
        "ffn_dim": int(config["intermediate_size"]),
        "expert_ffn": int(config["moe_intermediate_size"]),
        "experts": experts,
        "held": held,
        "first_held": int(config.get("chip_index", 0)) * held,
        "top_k": int(config["num_experts_per_tok"]),
        "bias": config.get("topk_method") == "noaux_tc",
        "routed_scale": float(config.get("routed_scaling_factor") or 1.0),
        "vocab": int(config["vocab_size"]),
        "eps": float(config["layernorm_epsilon"]),
    }


def cache_bytes_per_token(config: Dict[str, Any]) -> int:
    """A token's keys and values of every held layer, each layer with the KV
    heads of its kind, in the type the configuration states for the cache:
    what the prefix pool holds for a token.  (What a SLOT holds is another
    statement: a window layer keeps a ring, not the sequence; the program
    says it on /healthz ``config.model.cache``.)"""
    attn, _ = _kinds(config)
    per_head = int(config["head_dim"]) + int(config["v_head_dim"])
    heads = sum(int(config["swa_num_key_value_heads"]) if kind == "window"
                else int(config["num_key_value_heads"]) for kind in attn)
    return int(heads * per_head * TYPE_BYTES[config["precision"]["kv_cache"]])


# ---- the model of a seed ------------------------------------------------------

def _draw(key, shape, fan_in):
    w = jax.random.truncated_normal(key, -2, 2, shape, jnp.float32)
    return (w * fan_in ** -0.5).astype(jnp.bfloat16)


def _attention_weights(s, key, n, kv):
    dm, h, dk, dv = s["dim"], s["heads"], s["dk"], s["dv"]
    ks = jax.random.split(key, 4)
    return {"wq": _draw(ks[0], (n, dm, h * dk), dm),
            "wk": _draw(ks[1], (n, dm, kv * dk), dm),
            "wv": _draw(ks[2], (n, dm, kv * dv), dm),
            "wo": _draw(ks[3], (n, h * dv, dm), h * dv)}


def _make_weights(s, key):
    dm, v = s["dim"], s["vocab"]
    lf, lw = s["attn"].count("full"), s["attn"].count("window")
    ld, lm = s["ffn"].count("dense"), s["ffn"].count("moe")
    keys = jax.random.split(key, 16)
    w = {"embed": _draw(keys[7], (v, dm), dm),
         "lm_head": _draw(jax.random.fold_in(key, 99), (dm, v), dm)}
    if lf:
        w["full"] = _attention_weights(s, keys[0], lf, s["kv_full"])
    if lw:
        w["window"] = _attention_weights(s, keys[1], lw, s["kv_window"])
        if s["sink"]:
            w["window"]["sink"] = jax.random.normal(
                keys[2], (lw, s["heads"]), jnp.float32)
    if ld:
        f = s["ffn_dim"]
        w["dense"] = {"gate": _draw(keys[4], (ld, dm, f), dm),
                      "up": _draw(keys[5], (ld, dm, f), dm),
                      "down": _draw(keys[6], (ld, f, dm), f)}
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

        w["moe"] = {"router": _draw(keys[8], (lm, dm, e), dm),
                    "gate": experts(keys[9], (dm, fe), dm),
                    "up": experts(keys[10], (dm, fe), dm),
                    "down": experts(keys[11], (fe, dm), fe)}
        if s["bias"]:
            w["moe"]["bias"] = ROUTER_BIAS_STD * jax.random.normal(
                keys[12], (lm, e), jnp.float32)
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


def rope(x, positions, theta, rotary):
    """x [T, heads, D]: the leading ``rotary`` columns in rotate-half
    pairs (column i with column i + rotary / 2); the rest pass."""
    freqs = 1.0 / theta ** (
        jnp.arange(0, rotary, 2, dtype=jnp.float32) / rotary)
    ang = positions.astype(jnp.float32)[:, None] * freqs
    cos, sin = jnp.cos(ang)[:, None, :], jnp.sin(ang)[:, None, :]
    a, b = x[..., : rotary // 2], x[..., rotary // 2: rotary]
    return jnp.concatenate(
        [a * cos - b * sin, b * cos + a * sin, x[..., rotary:]], -1)


def attention(s, kind, lw, x, positions, bits):
    t = x.shape[0]
    h, dk, dv, kv = s["heads"], s["dk"], s["dv"], s["kv_" + kind]
    theta = s["theta_" + kind]
    hin = rms_norm(x, s["eps"])
    q = rope((hin @ _wide(lw["wq"], bits)).reshape(t, h, dk), positions,
             theta, s["rotary"])
    k = rope((hin @ _wide(lw["wk"], bits)).reshape(t, kv, dk), positions,
             theta, s["rotary"])
    v = s["value_scale"] * (hin @ _wide(lw["wv"], bits)).reshape(t, kv, dv)
    q = q.reshape(t, kv, h // kv, dk)
    sink = None
    if kind == "window" and "sink" in lw:
        sink = lw["sink"].reshape(kv, h // kv)[:, :, None, None]

    def block(args):
        q_blk, pos_blk = args
        scores = jnp.einsum("tkgd,skd->kgts", q_blk, k) * dk ** -0.5
        seen = positions[None, :] <= pos_blk[:, None]
        if kind == "window":
            seen &= pos_blk[:, None] - positions[None, :] < s["window"]
        scores = jnp.where(seen[None, None], scores, -jnp.inf)
        top = scores.max(-1, keepdims=True)
        if sink is not None:
            top = jnp.maximum(top, sink)
        e = jnp.exp(scores - top)
        denom = e.sum(-1, keepdims=True)
        if sink is not None:
            denom = denom + jnp.exp(sink - top)
        return jnp.einsum("kgts,skd->tkgd", e / denom, v)

    qb = QUERY_BLOCK if t % QUERY_BLOCK == 0 else t
    out = jax.lax.map(block, (q.reshape(t // qb, qb, kv, h // kv, dk),
                              positions.reshape(t // qb, qb)))
    return x + out.reshape(t, h * dv) @ _wide(lw["wo"], bits)


def swiglu(h, gate, up, down):
    return (jax.nn.silu(h @ gate) * (h @ up)) @ down


def routed(s, lw, h, bits):
    """The routed layer's output for the share's held experts."""
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

    return jax.lax.fori_loop(0, s["held"], one, jnp.zeros_like(h))


def _layer_of(group, i):
    return {k: jax.lax.dynamic_index_in_dim(v, i, 0, keepdims=False)
            for k, v in group.items()}


def _runs(s):
    """Consecutive layers of one (attention, feed-forward) pair: (attention
    kind, feed-forward kind, the first one's index among its attention kind
    and among its feed-forward kind, how many).  One loop each: a program of
    a few bodies compiles in a fraction of the time of every layer written
    out."""
    runs, seen = [], {"full": 0, "window": 0, "dense": 0, "moe": 0}
    for kind, ffn in zip(s["attn"], s["ffn"]):
        if runs and runs[-1][:2] == (kind, ffn):
            runs[-1] = runs[-1][:4] + (runs[-1][4] + 1,)
        else:
            runs.append((kind, ffn, seen[kind], seen[ffn], 1))
        seen[kind] += 1
        seen[ffn] += 1
    return runs


@functools.lru_cache(maxsize=None)
def _program(s: "_Frozen", bits: Optional[int]):
    def forward(w, tokens):
        with jax.default_matmul_precision("highest"):
            positions = jnp.arange(tokens.shape[0])
            x = _wide(w["embed"], bits, -1)[tokens]
            for kind, ffn, a0, f0, n in _runs(s):
                def layer(j, x, kind=kind, ffn=ffn, a0=a0, f0=f0):
                    x = attention(s, kind, _layer_of(w[kind], a0 + j), x,
                                  positions, bits)
                    h = rms_norm(x, s["eps"])
                    lw = _layer_of(w[ffn], f0 + j)
                    if ffn == "dense":
                        return x + swiglu(h, _wide(lw["gate"], bits),
                                          _wide(lw["up"], bits),
                                          _wide(lw["down"], bits))
                    return x + routed(s, lw, h, bits)

                x = jax.lax.fori_loop(0, n, layer, x)
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
