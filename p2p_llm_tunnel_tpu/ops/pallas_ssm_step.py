"""Decode's one-token update of a Mamba-2 layer's recurrent state, over the
step's live rows, where the state lies.

``ssm_step_rows(leaf, layer, work, x, dt, a, bm, cm)`` is
``models.ssm.ssm_step`` on the rows that ``work`` names
(:func:`live_rows_worklist`) of layer ``layer`` of the stacked state leaf
``[Lm, rows, H, P, N]`` float32, which comes back updated in place
(``input_output_aliases``): for each live row and block of heads the block
``S [hb, P, N]`` is read once, ``S' = exp(dt A) S + (dt x) (x) B`` and ``y =
sum_N S' C`` are computed from the block in VMEM, and ``S'`` is written
once.  A row that is not on the list, and every other layer, is never
named: its state is what it was, to the bit, and its ``y`` is zeros.  The
elementwise form in XLA made three passes over all rows of a layer's slice
(the update's read and write, then the sum with ``C`` reading the new state
again: PERF.md section 6, PR 45).

A block's arithmetic is elementwise on the VPU, float32 throughout, with
``P`` on the sublanes and ``N`` on the lanes as the leaf holds them.  Two
operands do not come that way.  ``dt x`` varies along the sublanes and is
the same on every lane, and ``y`` is a sum over the lanes: a vector register
each of the 512 a row's layer takes.  Both go through a butterfly of lane
rotations, :func:`_spread` and :func:`_gather`: a register that holds
``[8, N]`` values of ``dt x`` lane-dense becomes ``N`` registers, each one
lane's values on every lane, by ``log2 N`` levels of one rotation and two
selects an output, and ``N`` registers' lane sums are gathered into one
lane-dense register by the same levels backwards (one rotation, two selects
and one sum an input, where a lane reduction of each register alone takes
``log2 N`` rotations).  Outside the kernel the small operands are laid so
(:func:`_scrambled`, a transpose of ``[B, H, P]``).  On the chip the
arithmetic hides under the stream: the kernel with its body cut to a copy
moves 636 GB/s of a row's state in and out, the whole of it 624 (PERF.md
section 6, PR 45).

The body is written over arrays of registers (``[M, 8, N]``: a level of a
butterfly is five operations whatever ``M``), and the call is jitted with
the layer an operand (:func:`_step_call`): written a register at a time and
traced anew for each layer, the kernel was 4 s of a decode program's
lowering and 40 s of a warm start on the chip's host.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

#: The kernel's name in compiled programs and device traces.
SSM_STEP_KERNEL = "ssm_step_rows"
#: The branch's other answer: ``models.ssm.ssm_step`` in XLA.
ELEMENTWISE = "elementwise"
#: The most of a row's state one block holds (in and out, each twice: the
#: pipeline's buffers): 32 of nemotron's 64 heads.  On the chip, six layers
#: of 100 live rows: 4.45 ms at 16 heads, 4.02 at 32, 3.94 at 64.
BLOCK_BYTES = 2**20
#: A float32 register's sublanes.
SUBLANES = 8


def live_rows_worklist(positions: jnp.ndarray, seq: int) -> jnp.ndarray:
    """The rows one decode step's state updates visit: ``[1 + B] int32``,
    the count of rows at ``positions < seq`` first, then their indices in
    order, the tail repeating the last of them (the last row where none is
    live): a grid step past the count names the block the step before it
    held, so nothing is fetched or written for it.  ``decode_step`` makes it
    once a step, outside the layers."""
    b = positions.shape[0]
    live = positions.astype(jnp.int32) < seq
    ends = jnp.cumsum(live.astype(jnp.int32))
    w = jnp.arange(b, dtype=jnp.int32)
    # (compared against every row's end at once, as decode_rows_worklist)
    rows = jnp.searchsorted(ends, w, side="right",
                            method="compare_all").astype(jnp.int32)
    count = ends[-1:]
    last = jnp.minimum(rows[jnp.maximum(count[0] - 1, 0)], b - 1)
    return jnp.concatenate([count, jnp.where(w < count, rows, last)])


def heads_block(h: int, p: int, n: int) -> int:
    """Heads a block holds: whole butterflies (``n * 8 // p`` heads, where
    the layer has as many) up to ``BLOCK_BYTES`` of state, a divisor of
    ``h``."""
    hb = min(n * SUBLANES // p, h)
    while h % (hb * 2) == 0 and hb * 2 * p * n * 4 <= BLOCK_BYTES:
        hb *= 2
    return hb


def shapes_decline(h: int, p: int, n: int, groups: int):
    """Why the kernel cannot take a state of ``[H, P, N]`` a row with
    ``groups`` groups, at any tiling (``None``: it can).  The butterflies
    take ``N`` lanes and ``P / 8`` registers a head as powers of two."""
    def pow2(v):
        return v > 0 and v & (v - 1) == 0

    if p % SUBLANES or not pow2(p // SUBLANES):
        return f"a head of {p} is not 8 sublanes times a power of two"
    if not pow2(n) or n * SUBLANES < p:
        return f"a state of {n} lanes is no power of two of at least {p} / 8"
    if h % groups:
        return f"{h} heads do not divide into {groups} groups"
    unit = n * SUBLANES // p
    if h % unit and unit % h:
        return f"{h} heads are not whole butterflies of {unit}"
    return None


def _scrambled(v: jnp.ndarray, n: int) -> jnp.ndarray:
    """``v [B, H, P]`` as the butterflies hold it: ``[B, H / hc, 8, N]``
    with ``hc = N * 8 / P`` heads a register, sublane ``s`` and lane ``hh *
    (P / 8) + pb`` holding head ``hh`` of the group at ``p = pb * 8 + s``
    (zeros where the layer has fewer heads than a register)."""
    b, h, p = v.shape
    pbn = p // SUBLANES
    hc = n // pbn
    if h % hc:
        v = jnp.pad(v, ((0, 0), (0, hc - h % hc), (0, 0)))
    v = v.reshape(b, -1, hc, pbn, SUBLANES)
    return jnp.transpose(v, (0, 1, 4, 2, 3)).reshape(b, -1, SUBLANES, n)


def _unscrambled(v: jnp.ndarray, h: int, p: int) -> jnp.ndarray:
    """:func:`_scrambled` backwards: ``[B, H / hc, 8, N] -> [B, H, P]``."""
    b, _, _, n = v.shape
    pbn = p // SUBLANES
    v = v.reshape(b, -1, SUBLANES, n // pbn, pbn)
    return jnp.transpose(v, (0, 1, 3, 4, 2)).reshape(b, -1, p)[:, :h]


def _low(shape, shift: int):
    """Where the lane's bit ``shift`` is clear, over ``shape [.., 8, N]``."""
    lane = jax.lax.broadcasted_iota(jnp.int32, shape, len(shape) - 1)
    return jax.lax.eq(jax.lax.bitwise_and(lane, jnp.int32(shift)),
                      jnp.int32(0))


def _spread(v):
    """``v [8, N]`` -> ``[N, 8, N]``, register ``i`` holding on every lane
    what ``v`` holds on lane ``i``: a level doubles the registers and halves
    the lanes one still tells apart (after the first, register 0 holds lanes
    ``0 .. N/2`` of ``v`` twice over, register 1 the other half), so a
    cyclic rotation is all a level needs."""
    n = v.shape[1]
    out, shift = v[None], n // 2
    while shift:
        low = _low(out.shape, shift)
        turned = pltpu.roll(out, shift, 2)
        out = jnp.stack([jax.lax.select(low, out, turned),
                         jax.lax.select(low, turned, out)], axis=1)
        out, shift = out.reshape((-1,) + v.shape), shift // 2
    return out


def _gather(parts):
    """``parts [M, 8, N]`` (``M`` a power of two up to ``N``) -> ``[8, N]``
    whose lane ``i`` is the sum of register ``i`` over its lanes (zeros
    past the last): :func:`_spread` backwards, a level of pairs at a time.
    Level ``k`` leaves the first of a pair on the lanes whose bit ``k`` is
    clear and the second on the others, each lane the sum of two lanes of
    the level before."""
    n = parts.shape[2]
    shift = 1
    while shift < n:
        if parts.shape[0] > 1:
            pairs = parts.reshape((-1, 2) + parts.shape[1:])
            a, b = pairs[:, 0], pairs[:, 1]
        else:
            a, b = parts, jnp.zeros_like(parts)
        low = _low(a.shape, shift)
        parts = (jax.lax.select(low, a, b)
                 + pltpu.roll(jax.lax.select(low, b, a), shift, 2))
        shift *= 2
    return parts[0]


def _step_kernel(layer_sref,  # scalar-prefetch [1] int32: the layer
                 work_sref,   # scalar-prefetch [1 + B]: live_rows_worklist
                 dec_ref,     # [hb, N] exp(dt A), a head's on every lane
                 dtx_ref,     # [chunks, 8, N] dt x, as _scrambled lays it
                 b_ref,       # [G, N]
                 c_ref,       # [G, N]
                 s_ref,       # [hb, P, N] the block of the row's state
                 y0_hbm,      # the zeros that the rows not visited keep
                 o_ref,       # [hb, P, N] the same block, written once
                 y_ref,       # [chunks, 8, N] the sums, as _scrambled
                 *, heads: int, groups: int):
    del layer_sref, y0_hbm
    w, j = pl.program_id(0), pl.program_id(1)
    count = work_sref[0]
    hb, p, n = s_ref.shape
    chunks = dtx_ref.shape[0]
    pbn = p // SUBLANES
    per = hb // chunks  # heads a butterfly
    tile = (per, 1, SUBLANES, n)

    def rows_of(ref, at):
        """``ref``'s rows ``at`` (one a head) -> ``[per, 1, 8, N]``, a
        head's row on every sublane of its registers."""
        return jnp.stack([jnp.broadcast_to(ref[pl.ds(i, 1), :],
                                           (SUBLANES, n)) for i in at]
                         ).reshape(tile)

    def chunk(c, carry):
        first = c * per
        mine = pl.ds(first, per)
        group = [jax.lax.div(j * hb + first + hh, heads // groups)
                 for hh in range(per)]
        spread = _spread(dtx_ref[c])[:per * pbn]
        state = s_ref[mine].reshape(per, pbn, SUBLANES, n)
        new = (rows_of(dec_ref, [first + hh for hh in range(per)]) * state
               + spread.reshape(state.shape) * rows_of(b_ref, group))
        o_ref[mine] = new.reshape(per, p, n)
        y_ref[c] = _gather((new * rows_of(c_ref, group)).reshape(
            per * pbn, SUBLANES, n))
        return carry

    @pl.when(w < count)
    def _():
        jax.lax.fori_loop(0, chunks, chunk, 0)

    # No live row: the one block the grid names goes back as it came.
    @pl.when((count == 0) & (w == 0) & (j == 0))
    def _():
        o_ref[...] = s_ref[...]
        y_ref[...] = jnp.zeros(y_ref.shape, y_ref.dtype)


@functools.lru_cache(maxsize=None)
def _step_call(shape: tuple, groups: int, interpret: bool):
    """The update at these static shapes, jitted with the layer an operand:
    a process traces it once, and a program lowers it once for all its
    Mamba-2 layers (as ``pallas_grouped_matmul._grouped_call``; traced
    anew a layer, it was 4 s of each decode program's lowering here)."""
    _, rows, h, p, n = shape
    f32 = jnp.float32
    hb = heads_block(h, p, n)
    nh = h // hb
    laid = jax.eval_shape(lambda: _scrambled(jnp.zeros((rows, h, p)), n))
    chunks = laid.shape[1] // nh

    def at_row(*rest):
        def index(w, j, layer, work):
            jj = jnp.where(w < work[0], j, nh - 1)
            return (work[1 + w],) + tuple(
                jj if r is None else r for r in rest)
        return index

    def state_index(w, j, layer, work):
        return (layer[0],) + at_row(None, 0, 0)(w, j, layer, work)

    state_spec = pl.BlockSpec((None, None, hb, p, n), state_index)
    sums_spec = pl.BlockSpec((None, chunks, SUBLANES, n),
                             at_row(None, 0, 0))
    group_spec = pl.BlockSpec((None, groups, n), at_row(0, 0))
    call = pl.pallas_call(
        functools.partial(_step_kernel, heads=h, groups=groups),
        out_shape=(jax.ShapeDtypeStruct(shape, f32),
                   jax.ShapeDtypeStruct(laid.shape, f32)),
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=2,
            grid=(rows, nh),
            in_specs=[
                pl.BlockSpec((None, hb, n), at_row(None, 0)),
                sums_spec, group_spec, group_spec, state_spec,
                pl.BlockSpec(memory_space=pl.ANY),
            ],
            out_specs=(state_spec, sums_spec),
        ),
        # Operand index (scalar-prefetch args included) -> output index.
        input_output_aliases={6: 0, 7: 1},
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("arbitrary", "arbitrary"),
            vmem_limit_bytes=8 * hb * p * n * 4 + 16 * 2**20),
        interpret=interpret,
        name=SSM_STEP_KERNEL,
    )

    def run(leaf, layer, work, x, dt, a, bm, cm):
        x, dt = x.astype(f32), dt.astype(f32)
        dec = jnp.broadcast_to(jnp.exp(dt * a)[:, :, None], (rows, h, n))
        dtx = _scrambled(dt[:, :, None] * x, n)
        new_leaf, y = call(
            layer.reshape(1), work, dec, dtx, bm.astype(f32),
            cm.astype(f32), leaf, jnp.zeros(laid.shape, f32))
        return _unscrambled(y, h, p), new_leaf

    return jax.jit(run)


def ssm_step_rows(leaf: jnp.ndarray,   # [Lm, B, H, P, N] float32, donated
                  layer_idx,           # int32 scalar
                  work: jnp.ndarray,   # live_rows_worklist(positions, S)
                  x: jnp.ndarray,      # [B, H, P]
                  dt: jnp.ndarray,     # [B, H] after softplus
                  a: jnp.ndarray,      # [H], negative
                  bm: jnp.ndarray,     # [B, G, N]
                  cm: jnp.ndarray,     # [B, G, N]
                  *, interpret: bool = False):
    """``models.ssm.ssm_step`` over the rows ``work`` names of layer
    ``layer_idx`` of the stacked leaf -> (``y [B, H, P]`` float32 without
    the skip, zeros for a row not on the list; the leaf, those rows of that
    layer updated where they lay)."""
    h, p, n = leaf.shape[2:]
    g = bm.shape[1]
    why = shapes_decline(h, p, n, g)
    if why is not None or leaf.dtype != jnp.float32:
        raise ValueError(f"the state-update kernel cannot take this state: "
                         f"{why or leaf.dtype}")
    return _step_call(tuple(leaf.shape), g, interpret)(
        leaf, jnp.asarray(layer_idx, jnp.int32), work, x, dt, a, bm, cm)
