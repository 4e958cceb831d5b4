"""One mixer a layer by a pattern: Mamba-2 state-space layers, routed experts
and attention, each alone under one norm and one residual
(``NVIDIA-Nemotron-3-Nano-30B-A3B``, ``model_type`` ``nemotron_h``), or each
followed by a dense gated MLP under a norm and a residual of its own
(``granite-4.0-h-micro``, ``model_type`` ``granitemoehybrid``), or gated
delta-rule layers and attention, each followed by such an MLP, every branch
normed AFTER it (``Olmo-Hybrid-7B``, ``model_type`` ``olmo_hybrid``).

``models/transformer.py`` hands its entry points here when
``cfg.mixer_pattern`` is set, so the engine, the prefix pool and the tunnel
run this family through the calls they make for every other.

Layers.  ``x <- x + mixer(RMSNorm(x))``, the mixer by the layer's letter in
``cfg.mixer_kinds``: ``M`` a Mamba-2 mixer (models/ssm.py), ``L`` a gated
delta-rule mixer (models/delta.py: a matrix state ``[Dk, Dv]`` a head; a
pattern holds ``M`` or ``L``, not both: :func:`state_kind`), ``E`` routed
experts of two products (``down(relu(up u)^2)``) with a shared expert of its
own width (models/moe.py), ``*`` attention (``cfg.n_heads`` query heads on
``cfg.n_kv_heads`` KV heads, causal, no rotary: no position is encoded
anywhere; scores scaled by ``cfg.query_scale`` where the model states one;
under ``cfg.qk_norm`` an RMSNorm over the whole width of the query and of the
key before the heads are split).
The kinds differ in shape, so each kind's weights are stacked by
themselves (``mamba``, ``delta``, ``attn``, ``blocks`` = the routed layers) and the
layers are written out in order, each taking its static slice; where the
grouped products are the kernel's, the routed layers read the stacked
experts where they lie (``moe_mlp(stacked=...)``).

Under ``cfg.mixer_mlp`` a layer is two steps, ``x <- x + r mixer(RMSNorm(
x))`` and then ``x <- x + r W_out(act(a) * b)`` with ``[a | b] = RMSNorm'(x)
W_in`` (``mlp``: one stack over ALL layers, ``cfg.ffn_dim`` wide, scope
``ffn``), ``r = cfg.residual_multiplier`` on both; the embedding's rows are
scaled by ``cfg.embed_multiplier``, the logits divided by
``cfg.logits_divisor``, and under ``cfg.tie_embeddings`` the head is the
embedding (no ``lm_head`` leaf).  Each is the identity at its default, and
a model that sets none of them lowers to the program it lowered to before
they existed.  Under ``cfg.norm_after`` (OLMo's block) no norm stands before
a branch and the branch's ``norm`` weight norms its OUTPUT: ``x <- x +
RMSNorm(mixer(x))``, ``x <- x + RMSNorm(mlp(x))``; the identity at its
default too.

**The cache is KV planes and a state a slot**, one dict under one allocator:

- ``"k"``, ``"v"`` ``[La, rows, S, K * D]``: the attention layers' planes, a
  row a position's KV heads side by side (models/swa.py's full planes:
  whole lane tiles, read by decode where they lie);
- ``"ssm"`` ``[Lm, rows, H, P, N]`` (:data:`STATE_DTYPE`, float32): each
  Mamba-2 layer's recurrent state, and ``"conv"`` ``[Lm, rows, (K - 1) * C]``
  (the activations' type): the convolution's last ``K - 1`` inputs, a
  slot's tail one row, its positions side by side on the lanes, the oldest
  first (:data:`STATE_KEYS`).  The slots are then the sublanes of the leaf
  and of a decode step's write of a layer's part alike, so the write is a
  slice update where the leaf lies; positions on an axis of their own are
  the sublanes of that write and not of the leaf, and a program short of
  memory then moves the whole leaf into the write's layout and back in
  every layer (72 copies of 61 MB a step in granite-4.0-h-micro's cell:
  ISSUE 47);
- of a delta-rule model instead ``"delta"`` ``[Ld, rows, H, Dk / f, f * Dv]``
  (float32: a head's ``[Dk, Dv]`` with ``f`` of its rows side by side on the
  lanes, ``delta.pack``: the same bytes, every row of the leaf whole ``(8,
  128)`` tiles where ``Dv`` is 192) and ``"dconv"`` ``[Ld, rows, (K - 1) *
  C]`` over ``q | k | v`` (:data:`KEYS_OF`: leaves of their own names, so
  that a Mamba-2 model's leaves and programs stay what they were).

A token caches rows in the attention layers only (the prefix pool's pages);
the state is no function of one token, so the pool holds **snapshots** of it
at block boundaries beside the pages (engine/prefix_cache.py).  A sequence
starts from zeros: a prefill that begins at position 0 reads no state (a
slot's last tenant leaves nothing behind), one that continues
(``starts > 0``: a later segment, or the tail behind a restored snapshot)
reads the slot's.  Padding leaves state and tail as they are: a padded
position's ``dt`` is 0, padded rows lie on the scratch slot, a decode row
parked at ``positions >= S`` updates nothing.

The residual stream is float32 and every product takes it rounded to the
parameters' type; the router scores the normed stream before that rounding
(``models/mla.py``'s reasons), and what lies between a layer's products
stays float32 where float32 arithmetic follows (``_mm32``: a Mamba-2
layer's ``z``, ``dt`` and output, an attention layer's output; the experts'
results under ``cfg.residual_f32``, which the presets set): with half of a
layer's experts held, every rounding moves some token to another expert
than the float32 reference's.  Every program also returns what its routed
layers counted (``moe.STATS``).

Decode reads an attention layer by what
``transformer.decode_attention_branch`` answers: on the TPU, over plain
bf16 planes, ``decode_attention_rows`` over the stacked planes where they
lie; elsewhere the einsum over the layer's ``kv_view`` positions.  A
recurrent layer's state is updated where it lies by what
:func:`state_update_branch` answers (scope ``ssm_step`` or ``delta_step``
either way): on the TPU one kernel a layer (``ops/pallas_ssm_step.py`` for a
Mamba-2 state, ``ops/pallas_delta_step.py`` for the delta rule's matrix
state) over the step's LIVE rows of the donated leaf, each row's state read
once and written once with the sums (with ``C``; ``S^T k`` and ``S^T q``) in
the same pass, parked rows and the other layers never named; elsewhere (a
CPU backend, a mesh, ``cfg.flash`` off, a state that is no whole tiles)
``ssm.ssm_step`` / ``delta.delta_step`` elementwise over the layer's slice
of the leaf as it lies, the reference the kernel is held to.  Prefill of a
delta-rule layer is ``delta.delta_scan``, the rule in chunks of
``cfg.delta_chunk``.
Int8 planes (``--kv-quant int8``) are models/swa.py's: the benchmark's cache
control; the state has no quantised form.

Scopes: ``ssm_proj`` (the two projections, the gate and its norm),
``ssm_conv``, ``ssm_scan`` (prefill), ``ssm_step`` (decode); of a delta-rule
layer ``delta_proj`` (the six projections, the L2 norms, the gate and its
norm, the output's norm), ``delta_conv``, ``delta_scan`` (prefill),
``delta_step`` (decode); ``state_read`` / ``state_write`` (a slice or copy of
a state leaf that is not the update itself), beside ``attn``, ``ffn``,
``kv_read``, ``kv_write``, ``moe_route``, ``moe_experts``, ``moe_shared``,
``head_sample``.  The dispatch records' ``state_rows`` / ``state_bytes`` and
the counters ``engine_state_snapshots_total`` / ``engine_state_restores_
total`` read for either kind of state (the engine follows :func:`state_keys`,
:func:`state_bytes_per_slot` and :func:`state_update_branch`, and names no
kind).
"""

from __future__ import annotations

import math
from typing import Optional

import jax
import jax.numpy as jnp

from p2p_llm_tunnel_tpu.models.config import ModelConfig
from p2p_llm_tunnel_tpu.models import mla
from p2p_llm_tunnel_tpu.models.mla import ROUTER_BIAS_STD, _counted
from p2p_llm_tunnel_tpu.models.moe import (
    RAGGED,
    STATS,
    expert_leaves,
    grouped_product_branch,
    moe_mlp,
)
from p2p_llm_tunnel_tpu.models.delta import (
    delta_scan,
    delta_step,
    gated_head_norm,
    pack,
    unit,
)
from p2p_llm_tunnel_tpu.models.quant import mm, round_act
from p2p_llm_tunnel_tpu.models.ssm import (
    causal_conv,
    conv_step,
    gated_group_norm,
    ssm_scan,
    ssm_step,
)
from p2p_llm_tunnel_tpu.models.swa import _as_held, _attend, _pack, _unpack, _write
from p2p_llm_tunnel_tpu.ops.attention import window_mask
from p2p_llm_tunnel_tpu.ops.norms import rms_norm
from p2p_llm_tunnel_tpu.ops.pallas_delta_step import (
    DELTA_STEP_KERNEL,
    delta_step_rows,
    shapes_decline as delta_shapes_decline,
)
from p2p_llm_tunnel_tpu.ops.pallas_ssm_step import (
    ELEMENTWISE,
    SSM_STEP_KERNEL,
    SUBLANES,
    live_rows_worklist,
    shapes_decline,
    ssm_step_rows,
)

#: The cache leaves that are a state a slot, not rows a token: the prefix
#: pool keeps snapshots of them, never pages.  A Mamba-2 model's.
STATE_KEYS = ("ssm", "conv")
#: The same by the letter of the kind of recurrent mixer that holds them
#: (the state, then the convolution's tail): a delta-rule model's are leaves
#: of their own names and shapes, a Mamba-2 model's stay what they were.  A
#: pattern holds one such kind (:func:`state_kind`, :func:`state_keys`).
KEYS_OF = {"M": STATE_KEYS, "L": ("delta", "dconv")}
#: The recurrent state's type: float32, as the model's card asks of its
#: servers.  A constant, not an option: ``correct`` cannot tell a bfloat16
#: state apart (PERF.md section 2), so a narrower state has to come as a
#: path of its own with a number that judges it.
STATE_DTYPE = jnp.float32
#: The weights' group of each letter.
GROUP = {"M": "mamba", "L": "delta", "E": "blocks", "*": "attn"}
#: The scope of a recurrent kind's convolution (decode reads and writes a
#: layer's tail under it).
CONV_SCOPE = {"M": "ssm_conv", "L": "delta_conv"}
#: The scope of a recurrent kind's one-token update of its state.
STEP_SCOPE = {"M": "ssm_step", "L": "delta_step"}
#: A recurrent kind's one-token update: in XLA over a layer's slice of the
#: state, and the kernel over the live rows of the leaf
#: (:func:`state_update_branch` says which a decode program takes).
_STEP = {"M": (ssm_step, ssm_step_rows), "L": (delta_step, delta_step_rows)}


def kind_counts(cfg: ModelConfig) -> dict:
    kinds = cfg.mixer_kinds
    return {k: kinds.count(k) for k in GROUP}


def state_kind(cfg: ModelConfig) -> Optional[str]:
    """The letter of the pattern's recurrent mixers (None: it has none)."""
    kinds = sorted(set(cfg.mixer_kinds) & set(KEYS_OF))
    if len(kinds) > 1:
        raise ValueError(f"the pattern {cfg.mixer_kinds!r} holds two kinds "
                         f"of recurrent state ({kinds}): one cache, one kind")
    return kinds[0] if kinds else None


def state_keys(cfg: ModelConfig) -> tuple:
    """The model's cache leaves that are a state a slot."""
    return KEYS_OF.get(state_kind(cfg), ())


def _places(cfg: ModelConfig):
    """(letter, index in its kind's stack) of every layer, in order."""
    seen = dict.fromkeys(GROUP, 0)
    out = []
    for kind in cfg.mixer_kinds:
        out.append((kind, seen[kind]))
        seen[kind] += 1
    return out


def state_update_branch(cfg: ModelConfig, mesh) -> str:
    """Which implementation decode's one-token update of the recurrent
    state takes: the kernel of the state's kind by its name
    (``SSM_STEP_KERNEL``, ``ops/pallas_ssm_step.py``, a Mamba-2 state;
    ``DELTA_STEP_KERNEL``, ``ops/pallas_delta_step.py``, the delta rule's
    matrix state: the step's live rows of the leaf where they lie, read
    once and written once, the sums in the same pass) or ``"elementwise"``
    (:func:`ssm.ssm_step` / :func:`delta.delta_step` in XLA over the
    layer's slice, every row of it: the reference the kernel is held to).
    Answered from what the code observes, no option (ISSUE 45, 52), as
    ``transformer.decode_attention_branch`` and
    ``moe.grouped_product_branch`` answer:

    - the backend: the kernel is the TPU's (on the CPU only under
      ``cfg.flash_interpret``, the tests' interpret mode, or
      ``cfg.flash_force``, their lowering-only probes); ``cfg.flash`` off
      keeps the reference everywhere;
    - the mesh: a ``pallas_call`` is not GSPMD-partitioned (and state
      under ``--tp`` is refused at start-up);
    - the state's kind and static shapes: a Mamba-2 head's ``[P, N]`` is
      whole registers of 8 sublanes, in the powers of two the kernel's
      butterflies take (``pallas_ssm_step.shapes_decline``); a delta
      head's ``[Dk / f, f * Dv]`` as the leaf holds it is whole lane tiles
      whose columns of ``k`` and ``q`` lie in one register
      (``pallas_delta_step.shapes_decline``); and whole tiles of ``(8,
      128)`` (asked of the chip's compiler, not of the interpreter)."""
    backend = jax.default_backend()
    kind = state_kind(cfg)
    if kind is None or not (cfg.flash and (
            backend == "tpu" or cfg.flash_interpret or cfg.flash_force)):
        return ELEMENTWISE
    if mesh is not None and any(n > 1 for n in dict(mesh.shape).values()):
        return ELEMENTWISE
    if kind == "L":
        (_, r, w), _ = _state_shapes(cfg, kind)
        declined = delta_shapes_decline(r, w, cfg.delta_value_dim)
        tiles = r % SUBLANES == 0
        name = DELTA_STEP_KERNEL
    else:
        h, p, n = cfg.ssm_heads, cfg.ssm_head_dim, cfg.ssm_state
        declined = shapes_decline(h, p, n, cfg.ssm_groups)
        tiles = n % 128 == 0
        name = SSM_STEP_KERNEL
    if declined is not None or not (tiles or cfg.flash_interpret):
        return ELEMENTWISE
    return name


def state_bytes_per_slot(cfg: ModelConfig, dtype=jnp.bfloat16) -> int:
    """What a slot's recurrent state takes in all the layers that hold
    one, as stored."""
    kind = state_kind(cfg)
    if kind is None:
        return 0
    state, tail = _state_shapes(cfg, kind)
    return kind_counts(cfg)[kind] * (
        math.prod(state) * jnp.dtype(STATE_DTYPE).itemsize
        + math.prod(tail) * jnp.dtype(dtype).itemsize)


def _segment_shapes(cfg: ModelConfig, kind: Optional[str]):
    """(a layer's state, its convolution's tail) of one row as a prefill
    segment computes them: the state a head by itself, the tail's positions
    an axis of their own."""
    if kind == "L":
        return ((cfg.delta_heads, cfg.delta_key_dim, cfg.delta_value_dim),
                (cfg.delta_conv - 1, cfg.delta_conv_dim))
    return ((cfg.ssm_heads, cfg.ssm_head_dim, cfg.ssm_state),
            (cfg.ssm_conv - 1, cfg.ssm_conv_dim))


def _state_shapes(cfg: ModelConfig, kind: str):
    """The same a slot as the leaves hold them, the same values in the same
    order: the tail's positions side by side in one row, and a delta
    layer's ``[Dk, Dv]`` ``pack`` rows side by side (models/delta.py: the
    last axis whole lane tiles)."""
    state, tail = _segment_shapes(cfg, kind)
    if kind == "L":
        f = pack(cfg.delta_key_dim, cfg.delta_value_dim)
        state = (state[0], state[1] // f, f * state[2])
    return state, (math.prod(tail),)


# ---------------------------------------------------------------------------
# init
# ---------------------------------------------------------------------------

def init_params(cfg: ModelConfig, key: jax.Array, dtype=jnp.bfloat16):
    """Random init (``benchmarks/ssm_moe_reference.py`` states it again for
    the benchmark); an expert is drawn from its own key by its PUBLISHED
    index, one at a time (``models/mla.init_params``'s scheme).  ``A_log``,
    ``dt_bias`` and ``D`` are drawn as the family's own initialiser draws
    them (``A`` uniform in [1, 16], the time step log-uniform between
    ``ssm_dt_min`` and ``ssm_dt_max`` through the inverse softplus, ``D``
    ones), so that random weights decay as trained ones do.  A
    ``cfg.mixer_mlp`` model's MLPs are one stack over all layers, drawn from
    part 2 of the key (``benchmarks/granite_hybrid_reference.py`` states that
    family's draw again).  The delta-rule layers are drawn from a ten-way
    split of part 3, ``W_q``, ``W_k``, ``W_v``, ``W_z``, ``W_a``, ``W_b``
    each by itself (held side by side: ``w_in``, ``w_ab``), ``A`` and the
    time step as a Mamba-2 layer's; under ``cfg.norm_after`` the embedding's
    rows are drawn at a unit RMS, the scale of what every branch's norm
    adds to the stream (``benchmarks/olmo_hybrid_reference.py`` states that
    family's draw again)."""
    dm, h, kv, hd, v = (cfg.dim, cfg.n_heads, cfg.n_kv_heads, cfg.head_dim,
                        cfg.vocab_size)
    n = kind_counts(cfg)
    lm, ld, le, la = n["M"], n["L"], n["E"], n["*"]
    keys = jax.random.split(key, 16)
    # (a branch's last matrix is drawn 1 / residual_multiplier as wide: the
    # branch then adds to the stream what it adds in a model that states no
    # multiplier, and a token's own row does not drown what the layers
    # compute: under a tied head that row alone would decide the logits)
    out_x = 1.0 / cfg.residual_multiplier

    def dense(k, shape, fan_in, times=1.0):
        return (jax.random.truncated_normal(k, -2, 2, shape, jnp.float32)
                * (times * fan_in ** -0.5)).astype(dtype)

    params = {
        # (a table that is the head too is drawn ``logits_divisor`` times as
        # wide: the logits of a random model are then spread as an untied
        # head's are, not flat under the division)
        "embed": dense(keys[7], (v, dm), dm,
                       cfg.logits_divisor if cfg.tie_embeddings
                       else dm ** 0.5 if cfg.norm_after else 1.0),
        "final_norm": jnp.ones((dm,), dtype),
    }
    if not cfg.tie_embeddings:
        params["lm_head"] = dense(jax.random.fold_in(key, 99), (dm, v), dm)
    if cfg.mixer_mlp:
        ks = jax.random.split(keys[2], 2)
        layers, f = cfg.n_layers, cfg.ffn_dim
        params["mlp"] = {
            "norm": jnp.ones((layers, dm), dtype),
            "w_in": dense(ks[0], (layers, dm, 2 * f), dm),
            "w_out": dense(ks[1], (layers, f, dm), f, out_x),
        }
    if la:
        ks = jax.random.split(keys[0], 4)
        params["attn"] = {
            "norm": jnp.ones((la, dm), dtype),
            "wq": dense(ks[0], (la, dm, h * hd), dm),
            "wk": dense(ks[1], (la, dm, kv * hd), dm),
            "wv": dense(ks[2], (la, dm, kv * hd), dm),
            "wo": dense(ks[3], (la, h * hd, dm), h * hd, out_x),
        }
        if cfg.qk_norm:
            params["attn"]["q_norm"] = jnp.ones((la, h * hd), dtype)
            params["attn"]["k_norm"] = jnp.ones((la, kv * hd), dtype)
    if lm:
        ks = jax.random.split(keys[1], 6)
        inner, conv_dim, heads = cfg.ssm_inner, cfg.ssm_conv_dim, cfg.ssm_heads
        dt = jnp.exp(jax.random.uniform(
            ks[4], (lm, heads), jnp.float32, jnp.log(cfg.ssm_dt_min),
            jnp.log(cfg.ssm_dt_max)))
        dt = jnp.maximum(dt, cfg.ssm_dt_floor)
        params["mamba"] = {
            "norm": jnp.ones((lm, dm), dtype),
            "w_in": dense(ks[0], (lm, dm, inner + conv_dim + heads), dm),
            "conv_w": dense(ks[1], (lm, cfg.ssm_conv, conv_dim), cfg.ssm_conv),
            "conv_b": dense(ks[2], (lm, conv_dim), cfg.ssm_conv),
            "w_out": dense(ks[3], (lm, inner, dm), inner, out_x),
            "dt_bias": dt + jnp.log(-jnp.expm1(-dt)),
            "a_log": jnp.log(jax.random.uniform(
                ks[5], (lm, heads), jnp.float32, 1.0, 16.0)),
            "d_skip": jnp.ones((lm, heads), jnp.float32),
            "gate_norm": jnp.ones((lm, inner), dtype),
        }
    if ld:
        ks = jax.random.split(keys[3], 10)
        heads, dk, dv = cfg.delta_heads, cfg.delta_key_dim, cfg.delta_value_dim
        dt = jnp.exp(jax.random.uniform(
            ks[8], (ld, heads), jnp.float32, jnp.log(cfg.ssm_dt_min),
            jnp.log(cfg.ssm_dt_max)))
        params["delta"] = {
            "norm": jnp.ones((ld, dm), dtype),
            # q | k | v (what the convolution runs over) | z
            "w_in": jnp.concatenate(
                [dense(k_, (ld, dm, heads * w), dm)
                 for k_, w in zip(ks[:4], (dk, dk, dv, dv))], axis=-1),
            # a | b: the decay's and the write strength's
            "w_ab": jnp.concatenate(
                [dense(ks[4], (ld, dm, heads), dm),
                 dense(ks[5], (ld, dm, heads), dm)], axis=-1),
            "conv_w": dense(ks[6], (ld, cfg.delta_conv, cfg.delta_conv_dim),
                            cfg.delta_conv),
            "w_out": dense(ks[7], (ld, heads * dv, dm), heads * dv, out_x),
            "dt_bias": dt + jnp.log(-jnp.expm1(-dt)),
            "a_log": jnp.log(jax.random.uniform(
                ks[9], (ld, heads), jnp.float32, 1.0, 16.0)),
            "gate_norm": jnp.ones((ld, dv), dtype),
        }
    if le:
        e, fe = cfg.n_experts, cfg.expert_dim
        fs = cfg.n_shared_experts * (cfg.shared_expert_dim or fe)
        lo, held = cfg.experts_held

        pad = cfg.expert_dim_held - fe  # zeros: cfg.expert_dim_held

        def experts(k, shape, fan_in):
            def one(i):
                ke = jax.random.fold_in(
                    jax.random.fold_in(k, i // held), lo + i % held)
                w = dense(ke, shape, fan_in)
                return jnp.pad(w, ((0, pad), (0, 0)) if shape[0] == fe
                               else ((0, 0), (0, pad)))

            flat = jax.lax.map(one, jnp.arange(le * held))
            return flat.reshape((le, held) + flat.shape[1:])

        blocks = {
            "norm": jnp.ones((le, dm), dtype),
            "router": dense(keys[8], (le, dm, e), dm),
            "moe_up": experts(keys[10], (dm, fe), dm),
            "moe_down": experts(keys[11], (fe, dm), fe),
        }
        if cfg.expert_gated:
            blocks["moe_gate"] = experts(keys[9], (dm, fe), dm)
        if cfg.router_bias:
            blocks["router_bias"] = ROUTER_BIAS_STD * jax.random.normal(
                keys[12], (le, e), jnp.float32)
        if fs:
            blocks["shared_up"] = dense(keys[14], (le, dm, fs), dm)
            blocks["shared_down"] = dense(keys[15], (le, fs, dm), fs)
            if cfg.expert_gated:
                blocks["shared_gate"] = dense(keys[13], (le, dm, fs), dm)
        params["blocks"] = blocks
    return params


def init_kv_cache(cfg: ModelConfig, num_slots: int, max_seq: int,
                  dtype=jnp.bfloat16, quant=False):
    """The attention layers' planes ``"k"``, ``"v"`` and the recurrent
    layers' state and tail (:data:`KEYS_OF` the pattern's kind; zeros:
    a sequence's start)."""
    plane = dtype
    if quant in (True, "int8"):
        plane = jnp.int8
    elif quant not in (False, None, "none", ""):
        raise ValueError(f"the KV planes beside a recurrent state have no KV "
                         f"quant mode {quant!r} (none | int8)")
    n = kind_counts(cfg)
    kv = cfg.n_kv_heads
    out = {}
    for name, width in (("k", cfg.head_dim), ("v", cfg.v_head_dim)):
        out[name] = jnp.zeros((n["*"], num_slots, max_seq, kv * width), plane)
        if plane == jnp.int8:
            out[name + "_scale"] = jnp.zeros(
                (n["*"], num_slots, max_seq, kv), jnp.float32)
    kind = state_kind(cfg)
    if kind is not None:
        for name, shape, typ in zip(KEYS_OF[kind], _state_shapes(cfg, kind),
                                    (STATE_DTYPE, dtype)):
            out[name] = jnp.zeros((n[kind], num_slots) + shape, typ)
    return out


def cache_section(cfg: ModelConfig, kv_cache) -> dict:
    """What /healthz ``config.model.cache`` says: the attention layers'
    planes (what a pooled token takes) and the state a slot."""
    la, positions = kv_cache["k"].shape[0], kv_cache["k"].shape[2]
    keys = state_keys(cfg)
    per_token = sum(a.shape[3] * a.dtype.itemsize
                    for name, a in kv_cache.items() if name not in keys)
    ssm, conv = (kv_cache[name] for name in keys)
    per_slot = state_bytes_per_slot(cfg, conv.dtype)
    if state_kind(cfg) == "M":
        own = {"heads": cfg.ssm_heads, "head_width": cfg.ssm_head_dim,
               "state_width": cfg.ssm_state,
               "conv_positions": cfg.ssm_conv - 1,
               "conv_width": cfg.ssm_conv_dim}
    else:
        own = {"rule": "gated delta", "heads": cfg.delta_heads,
               "key_width": cfg.delta_key_dim,
               "value_width": cfg.delta_value_dim,
               # a head's [key_width, value_width] as the leaf holds it
               "held_as": list(ssm.shape[3:]),
               "conv_positions": cfg.delta_conv - 1,
               "conv_width": cfg.delta_conv_dim}
    state = {"layers": ssm.shape[0], **own, "type": str(ssm.dtype),
             "conv_type": str(conv.dtype), "bytes_per_slot": per_slot}
    return {
        "form": "kv_heads+state",
        "kinds": {
            "attention": {
                "layers": la, "kv_heads": cfg.n_kv_heads,
                "key_width": cfg.head_dim, "value_width": cfg.v_head_dim,
                "positions_per_slot": positions,
                "bytes_per_token_layer": per_token,
            },
            "state": state,
        },
        "bytes_per_token": la * per_token,
        "bytes_per_slot": la * per_token * positions + per_slot,
    }


# ---------------------------------------------------------------------------
# the layers
# ---------------------------------------------------------------------------

def _mm32(x, w, act_quant: bool):
    """``x @ w`` with the product's float32 sums kept: the operands as
    ``models.quant.mm`` takes them (the activations' type; int8 and back
    under ``--quant a8``), the result not rounded to them.  For the
    projections whose result meets float32 arithmetic next (the residual
    stream's sum, the convolution, the gate): a rounding saved a layer."""
    return jnp.dot(round_act(x, act_quant), w,
                   preferred_element_type=jnp.float32)


def _layer(stack, i: int, skip=()):
    return {k: a[i] for k, a in stack.items() if k not in skip}


def _mamba(cfg: ModelConfig, blk, h, tail, state, real, step=None):
    """One Mamba-2 mixer over ``h [B,T,Dm]`` (normed, the weights' type):
    ``tail [B,K-1,C]`` and ``state [B,H,P,N]`` before the segment, ``real
    [B,T]`` the positions that are no padding -> (out ``[B,T,Dm]``, new
    tail, new state).  ``step``: decode's, ``T`` is 1, the tail in and out
    is ``[B,(K-1)*C]`` as the ``"conv"`` leaf holds it (the convolution is
    its one-token form over the slabs where they lie) and the update is
    ``step(x, dt, a, bm, cm) -> y`` of :func:`ssm.ssm_step`'s operands,
    which holds the state itself (``state`` is None, in and out)."""
    b, t, _ = h.shape
    inner, heads, p = cfg.ssm_inner, cfg.ssm_heads, cfg.ssm_head_dim
    g, n = cfg.ssm_groups, cfg.ssm_state
    conv_dim = cfg.ssm_conv_dim
    with jax.named_scope("ssm_proj"):
        zxd = _mm32(h, blk["w_in"], cfg.act_quant)
        z = zxd[..., :inner]
        # (what the convolution carries from a dispatch to the next is the
        # activations' type: the segment's inputs are rounded as the tail is)
        xbc = zxd[..., inner:inner + conv_dim].astype(h.dtype)
        dt = jax.nn.softplus(zxd[..., inner + conv_dim:] + blk["dt_bias"])
        dt = jnp.where(real[..., None], dt, 0.0)
    with jax.named_scope("ssm_conv"):
        if step is not None:
            xbc, tail = conv_step(blk["conv_w"], blk["conv_b"], tail,
                                  xbc[:, 0], real[:, 0])
            xbc = xbc[:, None]
        else:
            xbc, tail = causal_conv(blk["conv_w"], blk["conv_b"], tail, xbc,
                                    real.sum(axis=1).astype(jnp.int32))
    x = xbc[..., :inner].reshape(b, t, heads, p)
    bm = xbc[..., inner:inner + g * n].reshape(b, t, g, n)
    cm = xbc[..., inner + g * n:].reshape(b, t, g, n)
    a = -jnp.exp(blk["a_log"])
    if step is not None:
        with jax.named_scope("ssm_step"):
            y = step(x[:, 0], dt[:, 0], a, bm[:, 0], cm[:, 0])[:, None]
    else:
        with jax.named_scope("ssm_scan"):
            y, new = ssm_scan(x, dt, a, bm, cm, state, cfg.ssm_chunk)
            state = new.astype(state.dtype)
    with jax.named_scope("ssm_proj"):
        y = y + blk["d_skip"][:, None] * x.astype(jnp.float32)
        y = gated_group_norm(y.reshape(b, t, inner), z, blk["gate_norm"], g,
                             cfg.norm_eps)
        return _mm32(y.astype(h.dtype), blk["w_out"], cfg.act_quant), \
            tail, state


def _delta(cfg: ModelConfig, blk, h, tail, state, real, step=None):
    """One gated delta-rule mixer over ``h [B,T,Dm]`` (the weights' type),
    as :func:`_mamba` is one Mamba-2 mixer: ``tail [B,K-1,C]`` and ``state
    [B,H,Dk,Dv]`` before the segment, ``real [B,T]`` -> (out ``[B,T,Dm]``,
    new tail, new state).  ``step``: decode's, ``T`` is 1, the tail in and
    out is ``[B,(K-1)*C]`` as the leaf holds it and the update is ``step(q,
    k, v, g, beta) -> o`` of :func:`delta.delta_step`'s operands, which
    holds the state itself (``state`` is None, in and out)."""
    b, t, _ = h.shape
    heads, dk, dv = cfg.delta_heads, cfg.delta_key_dim, cfg.delta_value_dim
    conv_dim = cfg.delta_conv_dim
    with jax.named_scope("delta_proj"):
        qkvz = _mm32(h, blk["w_in"], cfg.act_quant)
        # (the convolution's carry is the activations' type, as the tail is)
        qkv = qkvz[..., :conv_dim].astype(h.dtype)
        z = qkvz[..., conv_dim:].reshape(b, t, heads, dv)
        ab = _mm32(h, blk["w_ab"], cfg.act_quant)
        g = -jnp.exp(blk["a_log"]) * jax.nn.softplus(
            ab[..., :heads] + blk["dt_bias"])
        beta = jax.nn.sigmoid(ab[..., heads:])
        if cfg.delta_neg_eigval:
            beta = 2.0 * beta
        # a padded position leaves the state as it is
        g = jnp.where(real[..., None], g, 0.0)
        beta = jnp.where(real[..., None], beta, 0.0)
    with jax.named_scope("delta_conv"):
        no_bias = jnp.zeros((conv_dim,), jnp.float32)
        if step is not None:
            qkv, tail = conv_step(blk["conv_w"], no_bias, tail, qkv[:, 0],
                                  real[:, 0])
            qkv = qkv[:, None]
        else:
            qkv, tail = causal_conv(blk["conv_w"], no_bias, tail, qkv,
                                    real.sum(axis=1).astype(jnp.int32))
    with jax.named_scope("delta_proj"):
        q, k = unit(qkv[..., :heads * dk].reshape(b, t, heads, dk),
                    qkv[..., heads * dk:2 * heads * dk].reshape(
                        b, t, heads, dk))
        v = qkv[..., 2 * heads * dk:].reshape(b, t, heads, dv)
    if step is not None:
        with jax.named_scope("delta_step"):
            o = step(q[:, 0], k[:, 0], v[:, 0], g[:, 0], beta[:, 0])[:, None]
    else:
        with jax.named_scope("delta_scan"):
            o, new = delta_scan(q, k, v, g, beta, state, cfg.delta_chunk)
            state = new.astype(state.dtype)
    with jax.named_scope("delta_proj"):
        y = gated_head_norm(o, z, blk["gate_norm"], cfg.norm_eps)
        return _mm32(y.reshape(b, t, heads * dv).astype(h.dtype),
                     blk["w_out"], cfg.act_quant), tail, state


#: A recurrent kind's mixer, one signature.
_RECURRENT = {"M": _mamba, "L": _delta}


def _qkv(cfg: ModelConfig, blk, h):
    """h [B,T,Dm] -> q [B,T,H,D] and what the token caches: keys and values
    ``[B,T,K*D]``, heads side by side.  No rotary.  Under ``cfg.qk_norm`` an
    RMSNorm over the whole width of the query and of the key, before the
    heads are split."""
    b, t, _ = h.shape
    aq = cfg.act_quant
    q = mm(h, blk["wq"], aq)
    if cfg.qk_norm:
        q = rms_norm(q, blk["q_norm"], cfg.norm_eps)
    q = q.reshape(b, t, cfg.n_heads, cfg.head_dim)
    k = mm(h, blk["wk"], aq)
    if cfg.qk_norm:
        k = rms_norm(k, blk["k_norm"], cfg.norm_eps)
    return q, k, mm(h, blk["wv"], aq)


def _stacked_experts(cfg: ModelConfig, params):
    if "blocks" not in params:
        return None
    return {k: params["blocks"][k].reshape(
        (-1,) + params["blocks"][k].shape[2:]) for k in expert_leaves(cfg)}


def _embed(cfg: ModelConfig, params, tokens):
    """The residual stream's first value, float32: the tokens' rows, times
    ``cfg.embed_multiplier``."""
    x = mla._embed(cfg, params, tokens)
    return x if cfg.embed_multiplier == 1.0 else x * cfg.embed_multiplier


def _head(cfg: ModelConfig, params, x):
    """The final norm and the head (the embedding under
    ``cfg.tie_embeddings``: ``transformer._logits``), over
    ``cfg.logits_divisor``: the normed ``[rows, dim]`` is divided before the
    product, not the ``[rows, vocabulary]`` logits after it in a pass of
    their own (0.7 ms a step at 65 x 100,352: ISSUE 46).  No family here
    states a divisor and a soft cap together."""
    from p2p_llm_tunnel_tpu.models.transformer import _logits, _norm

    if cfg.logits_divisor == 1.0:
        return mla._head(cfg, params, x)
    assert cfg.logit_softcap is None
    with jax.named_scope("head_sample"):
        h = _norm(cfg, x, params["final_norm"]) / cfg.logits_divisor
        return _logits(cfg, params, h.astype(params["embed"].dtype))


def _added(cfg: ModelConfig, out):
    """What a branch adds to the float32 stream."""
    out = out.astype(jnp.float32)
    return out if cfg.residual_multiplier == 1.0 \
        else out * cfg.residual_multiplier


def _mlp(cfg: ModelConfig, blk, x, dtype):
    """The dense gated MLP a ``cfg.mixer_mlp`` layer carries after its
    mixer, from the stream ``x``: its own norm (on its output under
    ``cfg.norm_after``), ``[a | b] = u W_in``, ``W_out(act(a) * b)``; the
    gate's product in float32 (``_mm32``)."""
    from p2p_llm_tunnel_tpu.models.transformer import _act

    with jax.named_scope("ffn"):
        h = x if cfg.norm_after else rms_norm(x, blk["norm"], cfg.norm_eps)
        ab = _mm32(h.astype(dtype), blk["w_in"], cfg.act_quant)
        gated = _act(cfg, ab[..., :cfg.ffn_dim]) * ab[..., cfg.ffn_dim:]
        out = _mm32(gated.astype(dtype), blk["w_out"], cfg.act_quant)
        return rms_norm(out, blk["norm"], cfg.norm_eps) if cfg.norm_after \
            else out


#: The scope of a kind's last product: its output's norm stands there too.
_OUT_SCOPE = {"M": "ssm_proj", "L": "delta_proj", "E": "ffn", "*": "attn"}


def _run_layers(cfg: ModelConfig, params, x, counted, mamba, attend):
    """The layers in order over the float32 stream ``x``: ``mamba(i, blk,
    h)`` and ``attend(i, blk, h)`` give a recurrent (Mamba-2 or delta-rule)
    and an attention layer's output from its normed input (``i``: the
    layer's index in its kind's stack, and so in its kind's cache leaves);
    under ``cfg.mixer_mlp`` the layer's MLP follows.  Under
    ``cfg.norm_after`` a branch reads the stream as it is and its OUTPUT is
    normed (under the scope of the branch's last product).  Returns (x,
    stats)."""
    from p2p_llm_tunnel_tpu.models.transformer import _act

    dtype = params["embed"].dtype
    # The kernel reads the experts of all layers where they lie; the
    # compiler's own grouped product takes the layer's static slice (a
    # stack's group sizes padded out from a static layer index is a pad
    # the chip's compiler fails on).
    stacked, leaves = None, ()
    if grouped_product_branch(cfg, None, x.shape[0] * x.shape[1], True,
                              dtype) != RAGGED:
        stacked, leaves = _stacked_experts(cfg, params), expert_leaves(cfg)
    total = jnp.zeros((STATS,), jnp.int32)
    for layer, (kind, i) in enumerate(_places(cfg)):
        blk = _layer(params[GROUP[kind]], i, leaves)
        h32 = x if cfg.norm_after else rms_norm(x, blk["norm"], cfg.norm_eps)
        h = h32.astype(dtype)
        if kind in KEYS_OF:
            out = mamba(i, blk, h)
        elif kind == "*":
            out = attend(i, blk, h)
        else:
            with jax.named_scope("ffn"):
                out, stats = moe_mlp(
                    cfg, blk, h, lambda v: _act(cfg, v), counted,
                    stacked=stacked, layer=i, router_in=h32)
                total = total + stats
        if cfg.norm_after:
            with jax.named_scope(_OUT_SCOPE[kind]):
                out = rms_norm(out, blk["norm"], cfg.norm_eps)
        x = x + _added(cfg, out)
        if cfg.mixer_mlp:
            x = x + _added(
                cfg, _mlp(cfg, _layer(params["mlp"], layer), x, dtype))
    return x, total


# ---------------------------------------------------------------------------
# the three serving programs (+ the whole-prompt forward)
# ---------------------------------------------------------------------------

def prefill(cfg: ModelConfig, params, tokens, valid, counted=None):
    """Whole-prompt forward from a sequence's start: (logits [B,T,V],
    {"full": (keys [La,B,T,K*D], values), "state": (ssm [Lm,B,H,P,N], conv
    [Lm,B,K-1,C])}, stats of the ``counted`` tokens)."""
    b, t = tokens.shape
    positions = jnp.broadcast_to(jnp.arange(t), (b, t))
    mask = window_mask(positions, jnp.where(valid, positions, -1))
    dtype = params["embed"].dtype
    if counted is None:
        counted = valid
    kv, states = [], []
    kind = state_kind(cfg)
    state_of, tail_of = _segment_shapes(cfg, kind)
    zero_tail = jnp.zeros((b,) + tail_of, dtype)
    zero_state = jnp.zeros((b,) + state_of, jnp.float32)

    def mamba(i, blk, h):
        out, tail, state = _RECURRENT[kind](cfg, blk, h, zero_tail,
                                            zero_state, valid)
        states.append((state, tail))
        return out

    def attend(i, blk, h):
        with jax.named_scope("attn"):
            q, k, v = _qkv(cfg, blk, h)
        kv.append((k, v))
        a = _attend(cfg, "full", blk, q, k, v, mask)
        with jax.named_scope("attn"):
            return _mm32(a, blk["wo"], cfg.act_quant)

    x, stats = _run_layers(cfg, params, _embed(cfg, params, tokens), counted,
                           mamba, attend)
    rows = {}
    if kv:
        rows["full"] = tuple(jnp.stack(a) for a in zip(*kv))
    if states:
        rows["state"] = tuple(jnp.stack(a) for a in zip(*states))
    return _head(cfg, params, x), rows, stats


def _rows_of(leaf, i: int, slots, view: Optional[int] = None):
    """Rows ``slots [Bp]`` of layer ``i`` of a cache leaf ``[L, rows, ...]``
    -> ``[Bp, ...]`` (of a plane ``[L, rows, S, W]`` the first ``view``
    positions: ``[Bp, view, W]``), a row at a time by a slice at a traced
    index: the layer's slice first and a gather of two rows out of it was a
    copy of the layer's 270 MB in every Mamba-2 layer of every chunk-prefill
    dispatch (8 % of the device in the first traced runs), and of a plane's
    ``[rows, view, W]`` in every attention layer, with a copy of each whole
    plane before and after the tails' write (ISSUE 46: 3.8 GB of
    temporaries at 64 slots of 2560 positions, more than the chip had
    left)."""
    zeros = (0,) * (leaf.ndim - 2)
    size = leaf.shape[2:] if view is None else (view,) + leaf.shape[3:]
    return jnp.stack([
        jax.lax.dynamic_slice(
            leaf, (i, slots[r]) + zeros, (1, 1) + size)[0, 0]
        for r in range(slots.shape[0])])


def _write_state(kv_cache, keys, states, slots):
    """The dispatch's rows' new state into their slots, a layer at a time
    (``keys``: the model's state leaves; ``states``: [(state [Bp,H,P,N],
    tail [Bp,K-1,C])] in stack order; each is laid as its leaf holds it, a
    tail's positions side by side)."""
    out = dict(kv_cache)
    with jax.named_scope("state_write"):
        # The leaves and every layer's new state pass one barrier: each
        # write then follows every read of the old state by the data's own
        # order.  Without it the compiler has to find that order itself,
        # and in a dispatch of ONE row (its writes are slice updates, not
        # scatters) it did not: it copied the whole leaf first, 1.6 GB a
        # dispatch where that fitted and more than the chip had at 4.6 GB
        # (ISSUE 46).
        held, states = jax.lax.optimization_barrier(
            ({name: out[name] for name in keys}, states))
        for name, vals in zip(keys, zip(*states)):
            leaf = held[name]
            for i, v in enumerate(vals):
                v = v.reshape(v.shape[:1] + leaf.shape[2:])
                leaf = leaf.at[i, slots].set(v.astype(leaf.dtype))
            out[name] = leaf
    return out


def prefill_into_cache(cfg, params, tokens, lengths, kv_cache, slots,
                       return_prompt_logprobs=False, stat_rows=None):
    """``transformer.prefill_into_cache`` for this family: the prompt's rows
    into the attention planes, the state after its last real token into the
    slot.  Returns (last logits, cache[, prompt log-probs], stats)."""
    b, t = tokens.shape
    valid = jnp.arange(t)[None, :] < lengths[:, None]
    logits, rows, stats = prefill(cfg, params, tokens, valid,
                                  _counted(valid, stat_rows))
    last = jnp.take_along_axis(
        logits, (lengths - 1)[:, None, None], axis=1)[:, 0]
    out = kv_cache
    if "full" in rows:
        s = kv_cache["k"].shape[2]
        k, v = rows["full"]
        positions = jnp.broadcast_to(jnp.arange(t), (b, t))
        out = _write(cfg, out, "full", k[:, :, :s], v[:, :, :s], slots,
                     positions[:, :s], None)
    if "state" in rows:
        out = _write_state(out, state_keys(cfg), list(zip(*rows["state"])),
                           slots)
    if not return_prompt_logprobs:
        return last, out, stats
    lsm = jax.nn.log_softmax(logits[:, :-1], axis=-1)
    scored = jnp.take_along_axis(lsm, tokens[:, 1:, None], axis=-1)[..., 0]
    prompt_lps = jnp.concatenate(
        [jnp.zeros((b, 1), jnp.float32), scored.astype(jnp.float32)], axis=1)
    return last, out, prompt_lps, stats


def chunk_prefill_into_cache(cfg, params, tokens, lengths, starts, kv_cache,
                             slots, kv_view: Optional[int] = None,
                             return_all_logits: bool = False,
                             stat_rows=None):
    """``transformer.chunk_prefill_into_cache`` for this family: the tail of
    each prompt against what its slot already holds.  An attention layer
    reads its (layer, view) rows and lays the fresh tail over them; a
    Mamba-2 layer starts from the slot's state and tail, from zeros where
    ``starts`` is 0.  Neither the planes nor the state are a carry of the
    layers: all tails and states are written once after them.  Returns
    (logits, cache, stats)."""
    from p2p_llm_tunnel_tpu.models.transformer import lay_tail, tail_placement

    b, t = tokens.shape
    s = kv_cache["k"].shape[2]
    if kv_view is None or kv_view > s:
        kv_view = s
    quant = "k_scale" in kv_cache
    dtype = params["embed"].dtype
    pos = starts[:, None] + jnp.arange(t)[None, :]
    valid = jnp.arange(t)[None, :] < lengths[:, None]
    counted = _counted(valid, stat_rows)
    place, fresh = tail_placement(kv_view, starts, t)
    mask = window_mask(
        pos, jnp.broadcast_to(jnp.arange(kv_view), (b, kv_view)))
    carried = starts > 0
    kv, states = [], []
    kind = state_kind(cfg)
    state_key, tail_key = state_keys(cfg) or (None, None)
    state_of, tail_of = _segment_shapes(cfg, kind)

    def view_rows(name, i):
        rows = _rows_of(kv_cache[name], i, slots, kv_view)
        if not quant:
            return rows
        scale = _rows_of(kv_cache[name + "_scale"], i, slots, kv_view)
        return _unpack(rows, scale, dtype)

    def mamba(i, blk, h):
        with jax.named_scope("state_read"):
            state = jnp.where(
                carried[:, None, None, None],
                _rows_of(kv_cache[state_key], i, slots).reshape(
                    (b,) + state_of), 0)
            # (the rows' positions become an axis again behind a barrier,
            # and the select reads what the barrier hands on, as it read
            # the rows before ISSUE 47.  The TPU compiler's broadcast
            # rewriter walks a program's elementwise groups by recursion on
            # a small stack, and granite's t = 512 programs stand at its
            # edge: with this reshape anywhere it could reach, after the
            # select or before it, the compiler overflowed that stack in
            # three cold starts of three)
            tail = jax.lax.optimization_barrier(
                _rows_of(kv_cache[tail_key], i, slots).reshape(
                    (b,) + tail_of))
            tail = jnp.where(carried[:, None, None], tail, 0)
        out, tail, state = _RECURRENT[kind](cfg, blk, h, tail, state, valid)
        states.append((state, tail))
        return out

    def attend(i, blk, h):
        with jax.named_scope("attn"):
            q, k, v = _qkv(cfg, blk, h)
        kv.append((k, v))
        with jax.named_scope("kv_read"):
            k_all = lay_tail(view_rows("k", i),
                             _as_held(cfg, "full", k, quant), place, fresh)
            v_all = lay_tail(view_rows("v", i),
                             _as_held(cfg, "full", v, quant), place, fresh)
        a = _attend(cfg, "full", blk, q, k_all, v_all, mask)
        with jax.named_scope("attn"):
            return _mm32(a, blk["wo"], cfg.act_quant)

    x, stats = _run_layers(cfg, params, _embed(cfg, params, tokens), counted,
                           mamba, attend)
    new_cache = kv_cache
    if kv:
        k, v = (jnp.stack(a) for a in zip(*kv))
        new_cache = _write(cfg, new_cache, "full", k, v, slots, pos, None)
    if states:
        new_cache = _write_state(new_cache, state_keys(cfg), states, slots)
    logits = _head(cfg, params, x)
    if return_all_logits:
        return logits, new_cache, stats
    last = jnp.take_along_axis(
        logits, (lengths - 1)[:, None, None], axis=1)[:, 0]
    return last, new_cache, stats


def decode_step(cfg, params, kv_cache, tokens, positions,
                kv_view: Optional[int] = None, mesh=None):
    """``transformer.decode_step`` for this family: one token a row.  The
    cache is threaded through the layers; an attention layer takes one
    in-place row write and reads by what ``decode_attention_branch``
    answers, a recurrent layer updates its part of the state leaf where it
    lies by what :func:`state_update_branch` answers (its kind's kernel
    over the live rows, or ``ssm.ssm_step`` / ``delta.delta_step`` over the
    layer's slice).  Rows parked at
    ``positions >= S`` write nothing, leave their state as it is (to the
    bit: ``dt`` 0, or never visited) and count for nothing.  Returns
    (logits [B,V], cache, stats)."""
    from p2p_llm_tunnel_tpu.models.transformer import decode_attention_branch

    b = tokens.shape[0]
    s = kv_cache["k"].shape[2]
    if kv_view is None or kv_view > s:
        kv_view = s
    quant = "k_scale" in kv_cache
    dtype = params["embed"].dtype
    pos2d = positions[:, None]
    slot_ids = jnp.arange(b)
    live = positions < s
    kv = cfg.n_kv_heads
    use_rows = decode_attention_branch(
        cfg, mesh, kv_view, "int8" if quant else None, s) == "pallas-rows"
    if use_rows:
        from p2p_llm_tunnel_tpu.ops.pallas_decode_attention import (
            decode_attention_rows,
            decode_rows_worklist,
            rows_block,
        )

        # One work list a step, shared by the attention layers.
        block = rows_block(s, kv)
        work = decode_rows_worklist(positions, s, block)
    else:
        mask = window_mask(
            pos2d, jnp.broadcast_to(jnp.arange(kv_view), (b, kv_view)))
    kind = state_kind(cfg)
    state_key, tail_key = state_keys(cfg) or (None, None)
    one_step, rows_step = _STEP.get(kind, (None, None))
    state_rows = None
    if state_update_branch(cfg, mesh) != ELEMENTWISE:
        # One list of the step's live rows, shared by the recurrent layers.
        with jax.named_scope(STEP_SCOPE[kind]):
            state_rows = live_rows_worklist(positions, s)
    cache = dict(kv_cache)

    def mamba(i, blk, h):
        # (the layer's part of each leaf in and out is the update itself:
        # under its scopes, so that its time is the update's)
        def step(*operands):
            if state_rows is not None:
                y, cache[state_key] = rows_step(
                    cache[state_key], i, state_rows, *operands,
                    interpret=cfg.flash_interpret)
                return y
            y, state = one_step(*operands, cache[state_key][i])
            cache[state_key] = cache[state_key].at[i].set(state)
            return y

        with jax.named_scope(CONV_SCOPE[kind]):
            tail = cache[tail_key][i]
        out, tail, _ = _RECURRENT[kind](cfg, blk, h, tail, None,
                                        live[:, None], step)
        with jax.named_scope(CONV_SCOPE[kind]):
            cache[tail_key] = cache[tail_key].at[i].set(tail)
        return out

    def attend(i, blk, h):
        with jax.named_scope("attn"):
            q, k, v = _qkv(cfg, blk, h)
        rows = []
        for name, row in (("k", k), ("v", v)):
            with jax.named_scope("kv_write"):
                row, scale = _pack(row[:, 0], kv, quant)
                where = (i, slot_ids, positions)
                cache[name] = cache[name].at[where].set(row)
                if quant:
                    cache[name + "_scale"] = cache[
                        name + "_scale"].at[where].set(scale)
            if not use_rows:
                with jax.named_scope("kv_read"):
                    seen = cache[name][i, :, :kv_view]
                    rows.append(
                        _unpack(seen, cache[name + "_scale"][i, :, :kv_view],
                                dtype) if quant else seen)
        if use_rows:
            with jax.named_scope("attn"):
                a = decode_attention_rows(
                    q[:, 0], cache["k"], cache["v"], jnp.int32(i), work,
                    block=block, scale=cfg.query_scale or cfg.head_dim ** -0.5,
                    interpret=cfg.flash_interpret).reshape(b, 1, -1)
        else:
            a = _attend(cfg, "full", blk, q, rows[0], rows[1], mask)
        with jax.named_scope("attn"):
            return _mm32(a, blk["wo"], cfg.act_quant)

    x, stats = _run_layers(cfg, params, _embed(cfg, params, tokens[:, None]),
                           live[:, None], mamba, attend)
    return _head(cfg, params, x)[:, 0], cache, stats
