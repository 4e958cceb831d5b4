"""Plain float32 reference of ``Laguna-S-2.1``: full and window attention
layers whose query-head counts differ, a gate a head on attention's output,
over routed experts beside a shared one; as one chip's share of a stated
deployment or whole.

This is a model family's module (benchmarks/README.md, "A model family"): a
configuration names it with ``"reference": "laguna_moe_reference"``.  It
imports nothing of the program, and knows no cache, no ring and no pool: a
window layer is a full layer with one more condition in its mask.

Equations, from the published ``config.json`` of poolside/Laguna-S-2.1; what
the config leaves open is this family's convention, listed in the
configuration's ``assumed`` and marked DEPARTURE below where it is made.
``h`` the RMS-normed input of a layer (norm weights are ones and not stored,
eps ``rms_norm_eps``); layer ``l`` is ``layer_types[l]`` (``full_attention``
or ``sliding_attention``) over ``mlp_layer_types[l]`` (``dense`` or
``sparse``):

- ``H_l = num_attention_heads_per_layer[l]`` query heads (48 in a full
  layer, 72 in a window layer) on ``num_key_value_heads`` KV heads of
  ``head_dim``: ``q = W_q h`` [H_l, D], ``k = W_k h`` [K, D], ``v = W_v h``
  [K, D]; ``H_l / K`` query heads share a KV head; no bias.
- every query and key head RMS-normed over its ``D`` columns (weight ones).
- rope by kind (``rope_parameters``): the leading ``partial_rotary_factor *
  D`` columns of every head in rotate-half pairs within those columns; a
  full layer at YaRN's frequencies (below) with sin and cos times
  ``attention_factor``, a window layer plainly at its own theta; the other
  columns pass.
- ``s_ij = q_i . k_j / sqrt(D)``, ``j <= i``; a window layer also ``i - j <
  sliding_window``; ``p = softmax_j(s)``; no sink.
- ``g = sigmoid(W_g h)`` [H_l] (``gating`` ``per-head``): head ``n``'s
  weighted sum ``sum_j p_ij v_j`` is multiplied by ``g_n`` before
  ``W_o [H_l * D -> hidden]``; residual.

YaRN (``rope_type`` ``yarn``) over a rotary width ``r``: pair ``i`` has the
plain frequency ``f_i = theta ** (-2 i / r)``; with ``c(t) = r * ln(original
/ (2 pi t)) / (2 ln theta)`` the pair index that turns ``t`` times over the
original context, ``low = max(floor(c(beta_fast)), 0)``, ``high =
min(ceil(c(beta_slow)), r - 1)``, ``ramp_i = clip((i - low) / (high - low),
0, 1)``: the frequency is ``f_i / factor * ramp_i + f_i * (1 - ramp_i)``.

Feed-forward: a ``dense`` layer one SwiGLU of ``intermediate_size``; a
``sparse`` layer ``s = sigmoid(W_r h)`` over all PUBLISHED experts in
float32; the ``num_experts_per_tok`` experts of largest ``s + b`` are chosen
(``b`` a selection bias: it moves the choice, never the weight); their
weights are the unbiased ``s`` over their sum (``norm_topk_prob``), times
``moe_routed_scaling_factor``, applied to the experts' outputs; ``y = sum
over the chosen experts HELD HERE of w_e * SwiGLU_e(h)`` + the shared
expert's ``SwiGLU_s(h)`` of ``shared_expert_intermediate_size``, ungated.
An assignment to an expert another chip holds adds nothing, here as in the
program.  A loop over the held experts, every one over every token, masked
by its weight.

The share (benchmarks/README.md, "A configuration cut to a chip's share"):
``num_experts`` and ``vocab_size`` in the file count what is held;
``published_counts`` gives the published numbers, ``layer_chips`` the chips
that share a layer and ``chip_index`` which of them this is.
``share_shared`` false (a test's switch, no file states it) leaves the
shared expert out: the shares of a layer then add up to the uncut layer with
the shared expert counted once.

``make_weights`` is the benchmark's own statement of how a seed becomes the
model the program serves (``models/swa.init_params``): truncated normal on
[-2, 2] times ``fan_in**-0.5`` rounded to bfloat16, the key split sixteen
ways: full layers' attention from a four-way split of part 0 (``W_q``,
``W_k``, ``W_v``, ``W_o``), window layers' of part 1, each kind's ``W_g``
``2 x`` such a draw from ``fold_in(part, 4)``; the dense feed-forward parts
4-6, the embedding part 7, the router part 8, expert ``e`` (published index)
of routed layer ``i`` from ``fold_in(fold_in(part 9/10/11, i), e)``, the
selection bias ``0.03125 * normal`` of part 12, the shared expert parts
13-15, the head ``fold_in(key, 99)``.

Attention is computed a block of queries at a time, and an expert at a time,
so that eight layers at published widths and a sequence of six thousand
tokens fit one chip beside the weights.
"""

from __future__ import annotations

import functools
import math
from typing import Any, Dict, Optional

import jax
import jax.numpy as jnp

from benchmarks.correctness import TYPE_BYTES

REQUIRED_KEYS = ("num_attention_heads", "num_key_value_heads", "head_dim",
                 "sliding_window", "intermediate_size",
                 "moe_intermediate_size", "shared_expert_intermediate_size",
                 "num_experts", "num_experts_per_tok")

ROUTER_BIAS_STD = 0.03125
#: What multiplies the standard draw of ``W_g`` (the configuration's
#: ``assumed.gate_weights``).
GATE_STD = 2.0
#: Queries scored at once (a sequence is padded to a multiple of 256 by the
#: reference's process).
QUERY_BLOCK = 256

_KINDS = {"full_attention": "full", "sliding_attention": "window"}


class _Frozen(dict):
    """A dict usable as a static (hashable) argument of ``jax.jit``."""

    def __hash__(self):  # type: ignore[override]
        return hash(tuple(sorted(self.items())))


def _kinds(config: Dict[str, Any]):
    """(attention kind, feed-forward kind, query heads) of each held
    layer."""
    n = int(config["num_hidden_layers"])
    lists = [config["layer_types"], config["mlp_layer_types"],
             config["num_attention_heads_per_layer"]]
    if any(len(x) < n for x in lists):
        raise ValueError(f"{n} layers, and lists of "
                         f"{[len(x) for x in lists]}")
    return (tuple(_KINDS[k] for k in lists[0][:n]),
            tuple("moe" if k == "sparse" else "dense" for k in lists[1][:n]),
            tuple(int(h) for h in lists[2][:n]))


def yarn_frequencies(rotary: int, rope: Dict[str, Any]):
    """The ``rotary / 2`` frequencies of a ``yarn`` rope, as a tuple of
    Python floats (the module's docstring has the formula)."""
    theta, factor = float(rope["rope_theta"]), float(rope["factor"])
    original = float(rope["original_max_position_embeddings"])

    def pair_of(turns):
        return (rotary * math.log(original / (turns * 2 * math.pi))
                / (2 * math.log(theta)))

    low = max(math.floor(pair_of(float(rope["beta_fast"]))), 0)
    high = min(math.ceil(pair_of(float(rope["beta_slow"]))), rotary - 1)
    out = []
    for i in range(rotary // 2):
        plain = theta ** (-2.0 * i / rotary)
        ramp = min(max((i - low) / max(high - low, 1e-3), 0.0), 1.0)
        out.append(plain / factor * ramp + plain * (1.0 - ramp))
    return tuple(out)


def _rope_of(config, kind: str, dk: int):
    """(frequencies, what multiplies sin and cos) of a layer kind."""
    rope = config["rope_parameters"][
        "full_attention" if kind == "full" else "sliding_attention"]
    rotary = int(float(rope.get("partial_rotary_factor", 1.0)) * dk) // 2 * 2
    if rope.get("rope_type", "default") == "yarn":
        return yarn_frequencies(rotary, rope), float(
            rope.get("attention_factor") or 1.0)
    if rope.get("rope_type", "default") != "default":
        raise ValueError(f"this family's reference has no rope_type "
                         f"{rope['rope_type']!r}")
    theta = float(rope["rope_theta"])
    return tuple(theta ** (-2.0 * i / rotary)
                 for i in range(rotary // 2)), 1.0


def shapes_of(config: Dict[str, Any]) -> Dict[str, Any]:
    published = config.get("published_counts", {})
    held = int(config["num_experts"])
    experts = int(published.get("num_experts", held))
    chips = int(config.get("layer_chips", 1))
    if held * chips != experts:
        raise ValueError(f"{held} experts held on each of {chips} chips are "
                         f"not the {experts} published")
    unsupported = [
        key for key, want in (
            ("norm_topk_prob", True), ("attention_bias", False),
            ("moe_apply_router_weight_on_input", False),
            ("moe_router_logit_softcapping", 0), ("decoder_sparse_step", 1),
            ("gating", "per-head"), ("tie_word_embeddings", False))
        if config.get(key, want) != want]
    if set(config.get("gating_types", ["per_head"])) != {"per_head"}:
        unsupported.append("gating_types")
    if unsupported:
        raise ValueError(f"this family's reference has no {unsupported}")
    attn, ffn, heads = _kinds(config)
    by_kind = {kind: {h for k, h in zip(attn, heads) if k == kind}
               for kind in ("full", "window")}
    if any(len(v) > 1 for v in by_kind.values()):
        raise ValueError(f"one head count a layer kind, not {by_kind}")
    dk = int(config["head_dim"])
    full, window = _rope_of(config, "full", dk), _rope_of(config, "window", dk)
    return {
        "attn": attn,
        "ffn": ffn,
        "dim": int(config["hidden_size"]),
        "heads_full": next(iter(by_kind["full"]),
                           int(config["num_attention_heads"])),
        "heads_window": next(iter(by_kind["window"]),
                             int(config["num_attention_heads"])),
        "kv": int(config["num_key_value_heads"]),
        "dk": dk,
        "window": int(config["sliding_window"]),
        "freqs_full": full[0], "factor_full": full[1],
        "freqs_window": window[0], "factor_window": window[1],
        "ffn_dim": int(config["intermediate_size"]),
        "expert_ffn": int(config["moe_intermediate_size"]),
        "shared_ffn": int(config["shared_expert_intermediate_size"]),
        "share_shared": bool(config.get("share_shared", True)),
        "experts": experts,
        "held": held,
        "first_held": int(config.get("chip_index", 0)) * held,
        "top_k": int(config["num_experts_per_tok"]),
        "routed_scale": float(config.get("moe_routed_scaling_factor") or 1.0),
        "vocab": int(config["vocab_size"]),
        "eps": float(config["rms_norm_eps"]),
    }


def cache_bytes_per_token(config: Dict[str, Any]) -> int:
    """A token's keys and values of every held layer (both kinds have the
    same KV heads), in the type the configuration states for the cache: what
    the prefix pool holds for a token.  (What a SLOT holds is another
    statement: a window layer keeps a ring, not the sequence; the program
    says it on /healthz ``config.model.cache``.)"""
    layers = int(config["num_hidden_layers"])
    values = layers * int(config["num_key_value_heads"]) \
        * 2 * int(config["head_dim"])
    return int(values * TYPE_BYTES[config["precision"]["kv_cache"]])


# ---- the model of a seed ------------------------------------------------------

def _draw(key, shape, fan_in):
    w = jax.random.truncated_normal(key, -2, 2, shape, jnp.float32)
    return (w * fan_in ** -0.5).astype(jnp.bfloat16)


def _attention_weights(s, key, n, h):
    dm, dk, kv = s["dim"], s["dk"], s["kv"]
    ks = jax.random.split(key, 4)
    return {"wq": _draw(ks[0], (n, dm, h * dk), dm),
            "wk": _draw(ks[1], (n, dm, kv * dk), dm),
            "wv": _draw(ks[2], (n, dm, kv * dk), dm),
            "wo": _draw(ks[3], (n, h * dk, dm), h * dk),
            # DEPARTURE (assumed.gate_weights): twice the standard draw, so
            # that the gates are spread and not all near one half
            "wg": GATE_STD * _draw(jax.random.fold_in(key, 4), (n, dm, h),
                                   dm)}


def _make_weights(s, key):
    dm, v = s["dim"], s["vocab"]
    lf, lw = s["attn"].count("full"), s["attn"].count("window")
    ld, lm = s["ffn"].count("dense"), s["ffn"].count("moe")
    keys = jax.random.split(key, 16)
    w = {"embed": _draw(keys[7], (v, dm), dm),
         "lm_head": _draw(jax.random.fold_in(key, 99), (dm, v), dm)}
    if lf:
        w["full"] = _attention_weights(s, keys[0], lf, s["heads_full"])
    if lw:
        w["window"] = _attention_weights(s, keys[1], lw, s["heads_window"])
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

        fs = s["shared_ffn"]
        w["moe"] = {"router": _draw(keys[8], (lm, dm, e), dm),
                    "gate": experts(keys[9], (dm, fe), dm),
                    "up": experts(keys[10], (dm, fe), dm),
                    "down": experts(keys[11], (fe, dm), fe),
                    # DEPARTURE (assumed.selection_bias): drawn, so that it
                    # changes the choice
                    "bias": ROUTER_BIAS_STD * jax.random.normal(
                        keys[12], (lm, e), jnp.float32),
                    "shared_gate": _draw(keys[13], (lm, dm, fs), dm),
                    "shared_up": _draw(keys[14], (lm, dm, fs), dm),
                    "shared_down": _draw(keys[15], (lm, fs, dm), fs)}
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


def rope(x, positions, freqs, factor):
    """x [T, heads, D]: the leading ``2 * len(freqs)`` columns in
    rotate-half pairs (column i with column i + len(freqs)), sin and cos
    times ``factor``; the rest pass.  DEPARTURE (assumed.rotary): the
    published weights may pair (x_2i, x_2i+1), a fixed permutation that
    random weights cannot tell apart."""
    half = len(freqs)
    ang = positions.astype(jnp.float32)[:, None] * jnp.asarray(
        freqs, jnp.float32)
    cos = factor * jnp.cos(ang)[:, None, :]
    sin = factor * jnp.sin(ang)[:, None, :]
    a, b = x[..., :half], x[..., half: 2 * half]
    return jnp.concatenate(
        [a * cos - b * sin, b * cos + a * sin, x[..., 2 * half:]], -1)


def attention(s, kind, lw, x, positions, bits):
    t = x.shape[0]
    h, dk, kv = s["heads_" + kind], s["dk"], s["kv"]
    freqs, factor = s["freqs_" + kind], s["factor_" + kind]
    hin = rms_norm(x, s["eps"])
    # DEPARTURE (assumed.qk_norm): an RMSNorm a head on queries and keys,
    # before the rope; the config has no key for one
    q = rope(rms_norm((hin @ _wide(lw["wq"], bits)).reshape(t, h, dk),
                      s["eps"]), positions, freqs, factor)
    k = rope(rms_norm((hin @ _wide(lw["wk"], bits)).reshape(t, kv, dk),
                      s["eps"]), positions, freqs, factor)
    v = (hin @ _wide(lw["wv"], bits)).reshape(t, kv, dk)
    gate = jax.nn.sigmoid(hin @ _wide(lw["wg"], bits))          # [T, H]
    q = q.reshape(t, kv, h // kv, dk)

    def block(args):
        q_blk, pos_blk = args
        scores = jnp.einsum("tkgd,skd->kgts", q_blk, k) * dk ** -0.5
        seen = positions[None, :] <= pos_blk[:, None]
        if kind == "window":
            seen &= pos_blk[:, None] - positions[None, :] < s["window"]
        scores = jnp.where(seen[None, None], scores, -jnp.inf)
        return jnp.einsum("kgts,skd->tkgd", jax.nn.softmax(scores, -1), v)

    qb = QUERY_BLOCK if t % QUERY_BLOCK == 0 else t
    out = jax.lax.map(block, (q.reshape(t // qb, qb, kv, h // kv, dk),
                              positions.reshape(t // qb, qb)))
    out = out.reshape(t, h, dk) * gate[:, :, None]
    return x + out.reshape(t, h * dk) @ _wide(lw["wo"], bits)


def swiglu(h, gate, up, down):
    return (jax.nn.silu(h @ gate) * (h @ up)) @ down


def routed(s, lw, h, bits):
    """The routed layer's output for the share's held experts, and the
    shared expert's."""
    # DEPARTURE (assumed.router_weights): sigmoid scores and a selection
    # bias; the config names no score function
    scores = jax.nn.sigmoid(h @ _wide(lw["router"], bits))      # [T, E]
    _, top_i = jax.lax.top_k(scores + lw["bias"], s["top_k"])
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
    if not s["share_shared"]:
        return out
    # DEPARTURE (assumed.shared_expert): added ungated
    return out + swiglu(h, _wide(lw["shared_gate"], bits),
                        _wide(lw["shared_up"], bits),
                        _wide(lw["shared_down"], bits))


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


def feed_forward(s, ffn, lw, h, bits):
    if ffn == "dense":
        return swiglu(h, _wide(lw["gate"], bits), _wide(lw["up"], bits),
                      _wide(lw["down"], bits))
    return routed(s, lw, h, bits)


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
                    return x + feed_forward(
                        s, ffn, _layer_of(w[ffn], f0 + j),
                        rms_norm(x, s["eps"]), bits)

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
