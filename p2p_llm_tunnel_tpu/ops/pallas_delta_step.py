"""Decode's one-token update of a gated delta-rule layer's matrix state, over
the step's live rows, where the state lies.

``delta_step_rows(leaf, layer, work, q, k, v, g, beta)`` is
``models.delta.delta_step`` on the rows that ``work`` names
(``pallas_ssm_step.live_rows_worklist``: one list a step, made outside the
layers) of layer ``layer`` of the stacked state leaf ``[Ld, rows, H, Dk / f,
f * Dv]`` float32 as ``delta.pack`` lays it, which comes back updated in place
(``input_output_aliases``): for each live row and block of heads the block
``S [hb, Dk / f, f * Dv]`` comes into VMEM once; from that one copy the
kernel forms ``S^T k`` and ``S^T q`` (sums over the sublanes, the ``f`` lane
parts added after), ``d = beta (v - e^g S^T k)``, ``o = e^g S^T q + (k . q)
d`` (the output from the OLD state: that form is what makes one read
enough) and writes ``S' = e^g S + k (x) d`` once.  A row that is not on the
list, and every other layer, is never named: its state is what it was, to
the bit, and its ``o`` is zeros.  The elementwise form in XLA read all rows
of a layer's slice twice and wrote them once (PERF.md section 6, PR 52).

A block's arithmetic is elementwise on the VPU, float32 throughout, with a
head's ``Dk / f`` rows on the sublanes and its ``f * Dv`` columns on the
lanes as the leaf holds them.  ``k`` and ``q`` meet the state as operands
of the state's own size (``delta._rows_on_lanes``: row ``p``, part ``j``
holds ``k[p * f + j]`` on all of the part's ``Dv`` lanes); they never exist
at that size outside VMEM.  Outside the kernel they are laid lane-dense
(:func:`_columns`: a register of ``[8, 128]`` holds 128 columns of 8
sublanes: a head's ``2 f Dk / (8 f)`` of them side by side, as many heads
as fit); inside, ``pallas_ssm_step._spread``'s butterfly of lane rotations
makes of each column a register that holds it on every lane, and a lane
tile of ``k``'s operand is the register of the part it lies in (a select of
two where a tile straddles parts).  The sums over ``Dk`` are sums of a
head's registers, then over the sublanes, then over the ``f`` parts by lane
rotations of ``Dv``; they come back on every sublane and in every part,
which is the form ``d`` takes in ``k (x) d``.  The other small operands (``v``
``f`` times side by side, ``e^g``, ``beta`` and ``k . q`` a lane tile each)
are one row a head, read onto every sublane.  On the chip the arithmetic
hides under the stream: twelve layers of 55 live rows take 4.75-4.90 ms a
step where the kernel with its body cut to a copy takes 4.69-4.86 and
``delta_step`` in XLA 8.53 (PERF.md section 6, PR 52).

The body is written over arrays of registers and the call is jitted with
the layer an operand (:func:`_step_call`), so that a decode program lowers
the kernel once for all its delta layers (``pallas_ssm_step``'s reasons).
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from p2p_llm_tunnel_tpu.ops.pallas_ssm_step import SUBLANES, _spread

#: The kernel's name in compiled programs and device traces.
DELTA_STEP_KERNEL = "delta_step_rows"
#: A register's lanes: the columns one butterfly spreads.
LANES = 128
#: The most of a row's state one block holds (in and out, each twice: the
#: pipeline's buffers): 15 of Olmo-Hybrid's 30 heads of ``[48, 384]``.  On
#: the chip, twelve layers of 55 live rows: 5.61 ms at 5 heads, 4.90 at 10,
#: 4.76 at 15, 4.75 at 30 (PERF.md section 6, PR 52).
BLOCK_BYTES = 2**21


def _sublanes(r: int) -> int:
    """Sublanes of a register of a head's ``r`` rows: 8 where they are
    whole groups of 8 (what the chip's compiler takes), else all of them
    in one (the interpreter's shapes)."""
    return SUBLANES if r % SUBLANES == 0 else r


def _columns_per_head(r: int, f: int) -> int:
    """Registers of ``k`` and ``q`` a head: a part and group of sublanes
    each."""
    return 2 * f * (r // _sublanes(r))


def heads_blocks(h: int, r: int, w: int, dv: int):
    """(heads a butterfly, heads a block) of a state of ``[h, r, w]`` a
    row: as many heads as lie in one register's 128 columns, a divisor of
    ``h``; whole butterflies up to ``BLOCK_BYTES`` of state, a divisor of
    ``h``."""
    most = max(1, LANES // _columns_per_head(r, w // dv))
    per = max(d for d in range(1, min(most, h) + 1) if h % d == 0)
    hb = max(d for d in range(per, h + 1, per)
             if h % d == 0 and (d == per or d * r * w * 4 <= BLOCK_BYTES))
    return per, hb


def shapes_decline(r: int, w: int, dv: int):
    """Why the kernel cannot take heads of ``[Dk / f, f * Dv]`` = ``[r,
    w]``, at any tiling (``None``: it can).  (Rows in whole
    groups of 8 sublanes are asked of the chip's compiler, not of the
    interpreter: ``ssm_moe.state_update_branch``.)"""
    if w % LANES:
        return f"a row of {w} lanes is no whole lane tiles of {LANES}"
    if w % dv or (w // dv) & (w // dv - 1):
        return f"{w} lanes are no power of two of parts of {dv}"
    if _columns_per_head(r, w // dv) > LANES:
        return (f"a head's {_columns_per_head(r, w // dv)} columns of k and "
                f"q do not lie in one register's {LANES}")
    return None


def _columns(q: jnp.ndarray, k: jnp.ndarray, f: int, per: int):
    """``k`` and ``q [B, H, Dk]`` as the butterflies hold them: ``[B, H /
    per, sg, 128]``, sublane ``s`` and lane ``((hh * 2 + which) * f + j) *
    groups + g`` holding ``(k, q)[which]`` of head ``hh`` of the ``per`` at
    ``Dk`` index ``(g * sg + s) * f + j``: the column that part ``j`` of the
    rows ``g * sg ..`` of that head's state meets (zeros past the last)."""
    b, h, dk = k.shape
    sg = _sublanes(dk // f)
    x = jnp.stack([k, q], axis=2).reshape(
        b, h // per, per, 2, dk // f // sg, sg, f)
    x = jnp.transpose(x, (0, 1, 5, 2, 3, 6, 4)).reshape(b, h // per, sg, -1)
    return jnp.pad(x, ((0, 0),) * 3 + ((0, LANES - x.shape[-1]),))


def _step_kernel(layer_sref,  # scalar-prefetch [1] int32: the layer
                 work_sref,   # scalar-prefetch [1 + B]: live_rows_worklist
                 cols_ref,    # [hb / per, sg, 128] k and q, as _columns
                 vec_ref,     # [hb, W + 3 * 128] v x f | e^g | beta | k . q
                 s_ref,       # [hb, R, W] the block of the row's state
                 o0_hbm,      # the zeros that the rows not visited keep
                 new_ref,     # [hb, R, W] the same block, written once
                 o_ref,       # [hb, W] the outputs, f times side by side
                 *, dv: int, per: int):
    del layer_sref, o0_hbm
    w, j = pl.program_id(0), pl.program_id(1)
    count = work_sref[0]
    hb, r, wd = s_ref.shape
    sg = cols_ref.shape[1]
    f, groups = wd // dv, r // sg
    lane = jax.lax.broadcasted_iota(jnp.int32, (per, groups, sg, LANES), 3)

    def wide(x):
        """``[.., sg, 128]``, the same on every lane -> ``[.., sg, W]``."""
        return jnp.concatenate([x] * (wd // LANES), axis=-1)

    def on_lanes(x):
        """``[per, f, groups, sg, 128]`` (a part's column on every lane) ->
        ``[per, groups, sg, W]``, each part's on its own ``dv`` lanes: a
        lane tile is its part's register, a select where it straddles."""
        tiles = []
        for t in range(wd // LANES):
            first, last = t * LANES // dv, (t * LANES + LANES - 1) // dv
            tile = x[:, first]
            for part in range(first + 1, last + 1):
                tile = jax.lax.select(
                    lane >= part * dv - t * LANES, x[:, part], tile)
            tiles.append(tile)
        return jnp.concatenate(tiles, axis=-1)

    def over_dk(x):
        """``[per, groups, sg, W]`` summed over ``Dk`` -> ``[per, sg, W]``,
        the sum on every sublane and in each of the ``f`` parts."""
        x = jnp.sum(x, axis=1)
        x = jnp.broadcast_to(jnp.sum(x, axis=1, keepdims=True), x.shape)
        shift = dv
        while shift < wd:
            x, shift = x + pltpu.roll(x, shift, 2), shift * 2
        return x

    def chunk(c):
        heads = range(c * per, (c + 1) * per)
        cols = _spread(cols_ref[c])[:per * 2 * f * groups].reshape(
            per, 2, f, groups, sg, LANES)
        k_l, q_l = on_lanes(cols[:, 0]), on_lanes(cols[:, 1])
        rows = jnp.stack([jnp.broadcast_to(
            vec_ref[pl.ds(h, 1), :], (sg, vec_ref.shape[1])) for h in heads])
        v = rows[..., :wd]
        dec, beta, kq = (wide(rows[..., wd + i * LANES:wd + (i + 1) * LANES])
                         for i in range(3))
        s = s_ref[c * per:(c + 1) * per].reshape(per, groups, sg, wd)
        sk = dec * over_dk(s * k_l)
        sq = dec * over_dk(s * q_l)
        d = beta * (v - sk)
        o = sq + kq * d
        new_ref[c * per:(c + 1) * per] = (
            dec[:, None] * s + k_l * d[:, None]).reshape(per, r, wd)
        for i, h in enumerate(heads):
            # (every sublane holds the head's output: the one that lies
            # where the row is written needs no move)
            o_ref[pl.ds(h, 1), :] = o[i, h % sg:h % sg + 1]

    @pl.when(w < count)
    def _():
        for c in range(hb // per):
            chunk(c)

    # No live row: the one block the grid names goes back as it came.
    @pl.when((count == 0) & (w == 0) & (j == 0))
    def _():
        new_ref[...] = s_ref[...]
        o_ref[...] = jnp.zeros(o_ref.shape, o_ref.dtype)


@functools.lru_cache(maxsize=None)
def _step_call(shape: tuple, dv: int, interpret: bool):
    """The update at these static shapes, jitted with the layer an operand:
    a process traces it once, and a program lowers it once for all its
    delta layers (``pallas_ssm_step._step_call``'s form)."""
    _, rows, h, r, wd = shape
    f32 = jnp.float32
    f = wd // dv
    per, hb = heads_blocks(h, r, wd, dv)
    nh = h // hb
    sg = _sublanes(r)

    def at_row(w, j, layer, work):
        return work[1 + w], jnp.where(w < work[0], j, nh - 1), 0, 0

    def state_index(w, j, layer, work):
        return (layer[0],) + at_row(w, j, layer, work)

    state_spec = pl.BlockSpec((None, None, hb, r, wd), state_index)
    out_spec = pl.BlockSpec((None, None, hb, wd), at_row)
    call = pl.pallas_call(
        functools.partial(_step_kernel, dv=dv, per=per),
        out_shape=(jax.ShapeDtypeStruct(shape, f32),
                   jax.ShapeDtypeStruct((rows, nh, hb, wd), f32)),
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=2,
            grid=(rows, nh),
            in_specs=[
                pl.BlockSpec((None, None, hb // per, sg, LANES),
                             lambda *a: at_row(*a) + (0,)),
                pl.BlockSpec((None, None, hb, wd + 3 * LANES), at_row),
                state_spec,
                pl.BlockSpec(memory_space=pl.ANY),
            ],
            out_specs=(state_spec, out_spec),
        ),
        # Operand index (scalar-prefetch args included) -> output index.
        input_output_aliases={4: 0, 5: 1},
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("arbitrary", "arbitrary"),
            vmem_limit_bytes=12 * hb * r * wd * 4 + 16 * 2**20),
        interpret=interpret,
        name=DELTA_STEP_KERNEL,
    )

    def run(leaf, layer, work, q, k, v, g, beta):
        q, k, v = (x.astype(f32) for x in (q, k, v))
        cols = _columns(q, k, f, per).reshape(
            rows, nh, hb // per, sg, LANES)
        small = jnp.stack([jnp.exp(g.astype(f32)), beta.astype(f32),
                           jnp.sum(k * q, axis=-1)], axis=-1)
        vec = jnp.concatenate(
            [jnp.tile(v, f), jnp.repeat(small, LANES, axis=-1)], axis=-1)
        new_leaf, o = call(
            layer.reshape(1), work, cols, vec.reshape(rows, nh, hb, -1),
            leaf, jnp.zeros((rows, nh, hb, wd), f32))
        return o.reshape(rows, h, wd)[..., :dv], new_leaf

    return jax.jit(run)


def delta_step_rows(leaf: jnp.ndarray,   # [Ld, B, H, Dk/f, f*Dv] f32, donated
                    layer_idx,           # int32 scalar
                    work: jnp.ndarray,   # live_rows_worklist(positions, S)
                    q: jnp.ndarray,      # [B, H, Dk] (delta.unit's)
                    k: jnp.ndarray,      # [B, H, Dk]
                    v: jnp.ndarray,      # [B, H, Dv]
                    g: jnp.ndarray,      # [B, H] log-decay
                    beta: jnp.ndarray,   # [B, H]
                    *, interpret: bool = False):
    """``models.delta.delta_step`` over the rows ``work`` names of layer
    ``layer_idx`` of the stacked leaf -> (``o [B, H, Dv]`` float32, zeros
    for a row not on the list; the leaf, those rows of that layer updated
    where they lay)."""
    h, r, wd = leaf.shape[2:]
    dv = v.shape[-1]
    why = shapes_decline(r, wd, dv)
    if why is not None or leaf.dtype != jnp.float32:
        raise ValueError(f"the delta-step kernel cannot take this state: "
                         f"{why or leaf.dtype}")
    return _step_call(tuple(leaf.shape), dv, interpret)(
        leaf, jnp.asarray(layer_idx, jnp.int32), work, q, k, v, g, beta)
