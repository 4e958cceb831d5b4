"""The routed-expert feed-forward: a layer that is told which experts it holds.

One layer serves every routed family the repo has: mixtral-style blocks
(``tiny-moe``, ``mixtral-8x7b``: softmax router, top-k renormalised, all
experts held) and the aux-free-bias family (``sarvam-105b``: sigmoid scores,
a selection bias, a scaling factor, a shared expert, and possibly only one
chip's share of the experts).  An expert is three products (gated:
``down(act(gate x) * up x)``, the SwiGLU of most families) or two
(``cfg.expert_gated`` off: ``down(act(up x))``, Nemotron-H's relu squared);
a shared expert is of the same form and of its own width
(``cfg.shared_expert_dim``; the routed experts' where that is 0).

What it does, for ``N`` tokens, ``E`` published experts, top-``k``:

- ``moe_route``: scores all ``E`` experts in float32 (the router keeps its
  published width whatever is held) and picks ``k`` a token.
- ``moe_experts``: the ``N * k`` assignments are sorted by expert, those to
  experts this process does not hold last; the held experts run as three
  (or two) grouped matrix products over the sorted rows, and each row's result
  goes back to its token, weighted.  Shapes are static and nothing is
  dropped under any imbalance: the sorted buffer has room for every
  assignment, so one expert may take them all.  Rows past the held groups
  are never multiplied.  Which implementation a product takes is
  ``grouped_product_branch``'s answer.  ``jax.lax.ragged_dot`` is on the TPU
  the chip compiler's grouped kernel, whose visit of a group costs a row
  tile of hundreds of rows whatever the group holds: at the cells' 1.5 to 64
  rows an expert it ran at 188-528 GB/s of expert weights (PERF.md section
  6, PR 39: 9.0 us for a 3.1 MB expert that streams in 3.8).
  ``ops/pallas_grouped_matmul.py`` streams each touched expert once under a
  row window that follows the group, 540-720 GB/s at the same shapes (4.4
  us for that expert), and is taken where the experts are read in the stack
  of all layers, on one chip, up to the rows an expert that were measured.
- ``moe_shared``: the shared experts, one feed-forward over every token.

The held experts are ``cfg.experts_held``: with ``layer_chips`` chips sharing
a layer, chip ``i`` holds experts ``[i * E/n, (i + 1) * E/n)``.  A token
routed to an absent expert gets nothing for that assignment, here as in the
reference (tests/mla_moe_plain.py); no code stands in for the absent
chips or their exchange.  The parts all shares give, the shared expert
counted once, add up to the uncut layer (tests/test_mla_moe.py).

Expert parallelism under a mesh: expert leaves carry their expert axis on
``ep`` (pspecs below) and GSPMD partitions the products.  The exchange of a
routed layer over several chips (all-to-all by expert) is not written; see
ROADMAP.md.
"""

from __future__ import annotations

from typing import Optional, Tuple

import jax
import jax.numpy as jnp
from jax.sharding import PartitionSpec as P

from p2p_llm_tunnel_tpu.models.quant import mm, round_act
from p2p_llm_tunnel_tpu.ops.pallas_grouped_matmul import (
    GROUPED_KERNEL,
    grouped_matmul,
    visit_list,
    vmem_bytes,
)

#: What a routed layer counts of one call, int32: assignments of counted
#: tokens, those to held experts, the fullest held expert's tokens, and the
#: held experts that got a token.
STATS = 4
#: The leaves that hold experts, [L, E, ...] in a stacked block tree: a
#: gated expert's three; an expert of two products has no ``moe_gate``
#: (:func:`expert_leaves`).
EXPERT_LEAVES = ("moe_gate", "moe_up", "moe_down")
#: A dispatch record's ``moe`` where the products are the compiler's.
RAGGED = "ragged-dot"
#: VMEM the grouped kernel may ask for (a v5e core has 128 MiB).
KERNEL_VMEM = 96 * 2**20
#: Sorted rows an expert (of all the published ones: what a held expert
#: expects) the kernel is taken up to: the most the contest measured.
KERNEL_ROWS_AN_EXPERT = 64


def expert_leaves(cfg) -> Tuple[str, ...]:
    """The expert leaves a model of ``cfg`` has."""
    return EXPERT_LEAVES if cfg.expert_gated else EXPERT_LEAVES[1:]


def init_moe_blocks(cfg, keys, dense_fn, per_expert: bool = False) -> dict:
    """Mixtral-style leaves for the stacked block tree (every layer routed,
    all experts held).

    ``dense_fn(key, shape, fan_in)`` is init_params' dense initializer so
    MoE weights follow the same distribution.  Layout:
    router [L, Dm, E]; experts [L, E, Dm, F] (gate/up) and [L, E, F, Dm]
    (down).  ``per_expert``: expert ``e`` of layer ``i`` is drawn from
    ``fold_in(fold_in(key, i), e)``, one at a time (models/swa.py's
    scheme), where a leaf drawn whole in float32 would not fit the chip."""
    l, dm, f, e = cfg.n_layers, cfg.dim, cfg.expert_dim, cfg.n_experts

    def experts(k, shape, fan_in):
        if not per_expert:
            return dense_fn(k, (l, e) + shape, fan_in)

        def one(i):
            return dense_fn(
                jax.random.fold_in(jax.random.fold_in(k, i // e), i % e),
                shape, fan_in)

        return jax.lax.map(one, jnp.arange(l * e)).reshape((l, e) + shape)

    return {
        "router": dense_fn(keys[0], (l, dm, e), dm),
        "moe_gate": experts(keys[1], (dm, f), dm),
        "moe_up": experts(keys[2], (dm, f), dm),
        "moe_down": experts(keys[3], (f, dm), f),
    }


def moe_pspecs() -> dict:
    """PartitionSpecs for the MoE leaves: experts shard on ``ep``; the
    router (tiny) replicates."""
    return {
        "router": P(None, None, None),
        "moe_gate": P(None, "ep", None, None),
        "moe_up": P(None, "ep", None, None),
        "moe_down": P(None, "ep", None, None),
    }


def route(cfg, blk, x: jnp.ndarray) -> Tuple[jnp.ndarray, jnp.ndarray]:
    """x [N, Dm] -> (experts [N, k] int32 over all E, weights [N, k] f32)."""
    k = cfg.n_experts_per_tok
    # float32 for real: the chip's default rounds a float32 product's
    # operands to bfloat16, and a score off in the third digit routes a
    # token to another expert than the reference's
    logits = jnp.dot(x.astype(jnp.float32), blk["router"].astype(jnp.float32),
                     precision=jax.lax.Precision.HIGHEST)
    if cfg.router_score == "sigmoid":
        scores = jax.nn.sigmoid(logits)
    elif cfg.router_score == "softmax":
        scores = jax.nn.softmax(logits, axis=-1)
    else:
        raise ValueError(f"unknown router_score {cfg.router_score!r}")
    chosen_by = scores
    if cfg.router_bias:
        # the bias moves the choice, never the weight
        chosen_by = scores + blk["router_bias"].astype(jnp.float32)
    _, top_i = jax.lax.top_k(chosen_by, k)
    top_w = jnp.take_along_axis(scores, top_i, axis=-1)
    top_w = top_w / jnp.maximum(top_w.sum(-1, keepdims=True), 1e-9)
    return top_i, top_w * cfg.routed_scale


def grouped_product_branch(cfg, mesh, tokens: int, stacked: bool = True,
                           dtype=jnp.bfloat16) -> str:
    """Which implementation the grouped products of a routed layer
    take in a program that runs ``tokens`` token positions a layer:
    ``"ragged-dot"`` (``jax.lax.ragged_dot``: the chip compiler's grouped
    kernel) or ``ops/pallas_grouped_matmul.py``'s kernel by its name
    (``GROUPED_KERNEL``).  Answered from what the code observes, no option
    (ISSUE 39):

    - the backend: the kernel is the TPU's (on the CPU only under
      ``cfg.flash_interpret``, the tests' interpret mode, or
      ``cfg.flash_force``, their lowering-only probes); ``cfg.flash`` off
      keeps the reference everywhere, as in attention;
    - the mesh: a ``pallas_call`` is not GSPMD-partitioned, ``ragged_dot``
      is (``--ep`` / ``--tp`` shard the expert leaves);
    - ``stacked``: whether the layer reads its experts where they lie in
      the stack of all layers (``moe_mlp``'s ``stacked``): a layer's slice
      handed to a kernel is a copy first, and the programs that slice are
      the ones a mesh may shard;
    - the static shapes: an expert's two widths as held
      (``cfg.expert_dim_held``) are whole lane tiles of 128 (asked of the
      chip's compiler, not of the interpreter); the kernel wins where an expert gets few of the
      ``tokens * k`` sorted rows (``KERNEL_ROWS_AN_EXPERT`` of the
      published experts: the contest of PERF.md section 6, PR 39), and it
      keeps a block of rows, its result and a ring of an expert's tiles in
      VMEM (``KERNEL_VMEM``); ``dtype`` is the rows' and the experts'.
    """
    backend = jax.default_backend()
    if not (stacked and cfg.flash
            and (backend == "tpu" or cfg.flash_interpret
                 or cfg.flash_force)):
        return RAGGED
    if mesh is not None and any(n > 1 for n in dict(mesh.shape).values()):
        return RAGGED
    width = cfg.expert_dim_held
    if not cfg.flash_interpret and (cfg.dim % 128 or width % 128):
        # the kernel's DMA takes an expert's matrix in whole lane tiles
        # (the chip's compiler refuses a slice of 1856 columns, 14.5 tiles:
        # a family whose experts are so wide holds them padded,
        # ``cfg.expert_dim_held``)
        return RAGGED
    rows = tokens * cfg.n_experts_per_tok
    out = jnp.float32 if cfg.residual_f32 else dtype
    need = max(vmem_bytes(rows, cfg.dim, width, dtype, out),
               vmem_bytes(rows, width, cfg.dim, dtype, out))
    if rows > KERNEL_ROWS_AN_EXPERT * cfg.n_experts or need > KERNEL_VMEM:
        return RAGGED
    return GROUPED_KERNEL


def moe_mlp(cfg, blk, h: jnp.ndarray, act_fn,
            counted: Optional[jnp.ndarray] = None, stacked=None, layer=None,
            router_in: Optional[jnp.ndarray] = None):
    """Routed feed-forward of one layer: h [B, T, Dm] -> ([B, T, Dm], stats).

    ``blk`` holds this layer's slice: router [Dm, E], the held experts
    [He, ...], and where the config has them ``router_bias`` [E] and the
    shared experts' ``shared_gate/up`` [Dm, Fs], ``shared_down`` [Fs, Dm]
    (no ``moe_gate`` / ``shared_gate`` where ``cfg.expert_gated`` is off).
    ``counted`` [B, T] bool marks the tokens that ``stats`` (int32
    [STATS]) counts: padding is computed like any token and counts for
    nothing.

    ``stacked`` + ``layer``: the experts of ALL expert layers as three (two)
    arrays [layers * He, ...] and this layer's index among them, in place of
    ``blk``'s expert leaves.  The grouped product then names its experts by
    the groups' sizes (every other layer's are empty) and reads them where
    they lie.  A layer's slice of the stack handed to the kernel is a copy
    first: 1.6 GB of experts copied in every layer of every step were 36 %
    of the device in ``sarvam-105b.context-closed`` (PERF.md section 6, PR
    28).

    ``router_in`` [B, T, Dm]: what the router scores, where the caller has
    ``h`` in more digits than the experts take (float32 before its rounding
    to bfloat16): a score is compared with its neighbours, and every digit
    lost routes some token elsewhere.
    """
    b, t, dm = h.shape
    n, k = b * t, cfg.n_experts_per_tok
    lo, held = cfg.experts_held
    x = h.reshape(n, dm)
    aq = cfg.act_quant
    with jax.named_scope("moe_route"):
        top_i, top_w = route(
            cfg, blk, x if router_in is None else router_in.reshape(n, dm))
        local = top_i - lo
        here = (local >= 0) & (local < held)
        # an absent expert's assignments sort after every held one's
        group = jnp.where(here, local, held).reshape(-1)  # [N*k]
        order = jnp.argsort(group, stable=True)
        token_of = order // k
        in_group = jax.nn.one_hot(group, held + 1, dtype=jnp.int32)
        sizes = jnp.sum(in_group, axis=0)[:held]
        weight = jnp.where(here, top_w, 0.0).reshape(-1)[order]
        if counted is None:
            counted = jnp.ones((b, t), bool)
        real = jnp.repeat(counted.reshape(-1), k)  # [N*k], unsorted order
        real_sizes = jnp.sum(
            in_group * real[:, None].astype(jnp.int32), axis=0)[:held]
        stats = jnp.stack([
            jnp.sum(real.astype(jnp.int32)),
            jnp.sum(real_sizes),
            jnp.max(real_sizes),
            jnp.sum((real_sizes > 0).astype(jnp.int32)),
        ])
    with jax.named_scope("moe_experts"):
        experts = blk if stacked is None else stacked
        # (the families that hand over the stack refuse a mesh at start-up)
        kernel = grouped_product_branch(
            cfg, None, n, stacked is not None, x.dtype) != RAGGED
        if kernel:
            visits = visit_list(sizes, layer * held)
        elif stacked is not None:
            sizes = jax.lax.dynamic_update_slice(
                jnp.zeros((stacked["moe_up"].shape[0],), jnp.int32), sizes,
                (layer * held,))
        rows = round_act(x, aq)[token_of]  # [N*k, Dm], sorted by expert
        # under cfg.residual_f32 the values between the three products stay
        # float32 and are rounded to the activations' type once, where the
        # last product takes them; its results reach the weighted sum whole
        wide = ({"preferred_element_type": jnp.float32}
                if cfg.residual_f32 else {})

        def product(lhs, name):
            if not kernel:
                return jax.lax.ragged_dot(lhs, experts[name], sizes, **wide)
            return grouped_matmul(
                lhs, experts[name], visits,
                out_dtype=wide.get("preferred_element_type", lhs.dtype),
                interpret=cfg.flash_interpret)

        if cfg.expert_gated:
            gate = product(rows, "moe_gate")
            up = product(rows, "moe_up")
            inner = act_fn(gate) * up
        else:
            inner = act_fn(product(rows, "moe_up"))
        inner = round_act(inner.astype(rows.dtype), aq)
        down = product(inner, "moe_down")
        # (rows past the held groups hold nothing defined: their weight is 0)
        part = jnp.where(weight[:, None] > 0,
                         down.astype(jnp.float32) * weight[:, None], 0.0)
        out = jnp.zeros((n, dm), jnp.float32).at[token_of].add(part)
    if cfg.n_shared_experts:
        with jax.named_scope("moe_shared"):
            if cfg.expert_gated:
                inner = act_fn(mm(x, blk["shared_gate"], aq)) * mm(
                    x, blk["shared_up"], aq)
            else:
                inner = act_fn(mm(x, blk["shared_up"], aq))
            out = out + mm(inner, blk["shared_down"], aq).astype(jnp.float32)
    # (under cfg.residual_f32 the stream takes the sum as it is)
    return out.astype(jnp.float32 if cfg.residual_f32 else h.dtype).reshape(
        b, t, dm), stats
