"""A grouped matrix product that streams each touched expert once.

``grouped_matmul(rows, experts, visit_list(sizes, first_group))`` is
``jax.lax.ragged_dot`` over one layer's groups of a stack of experts:
``rows [M, K]`` lie sorted by group, group ``g`` (``sizes[g]`` rows) is
multiplied by ``experts[first_group + g]`` ``[K, N]``, rows past the last
group come back as zeros.  What differs is what a group costs (ISSUE 39).
The chip compiler's grouped kernel pays the MXU for a row tile of hundreds
of rows at every group, whatever the group holds; with 2 to 12 rows an
expert that, not the stream of its matrix, is a visit's cost (PERF.md
section 6, PR 39).  Here a visit is:

- one DMA of the expert's matrix (in column tiles of ``TILE_BYTES`` where
  it is larger) into a ring of ``DEPTH`` VMEM buffers, started while the
  visit before it multiplies; an empty group is never visited, and the
  other layers' experts in the stack are never named;
- products of row windows that FOLLOW the group: ``WINDOW`` rows from the
  16-aligned row at or before the group's first, as many windows as the
  group has rows for (one, at the cells' 1.5 to 64 rows an expert).  The
  MXU's time for a window is hidden under the next tile's DMA: on the chip
  windows of 16 to 128 rows ran within 0.7 % of each other and one of 256
  3 % slower, so there is one length and one product in the body;
- a masked store: the window's rows of other groups keep what their own
  visit wrote.

A block of ``ROW_BLOCK`` sorted rows and its result stay in VMEM while the
groups that reach into it are visited (:func:`vmem_bytes`); the
mathematics is ``ragged_dot``'s: the operands as stored, float32
accumulation over all of ``K``, one rounding to ``out_dtype``.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

#: The kernel's name in compiled programs and device traces.
GROUPED_KERNEL = "moe_grouped_rows"
#: Rows a product of a visit multiplies: the MXU's own 128.
WINDOW = 128
#: The row alignment of a window (a bf16 tile's sublanes).
ALIGN = 16
#: The most one DMA of an expert's columns brings (an expert of
#: ``[2048, 768]`` bf16 is one DMA; ``[4096, 2048]`` goes in four).
TILE_BYTES = 4 * 2**20
#: Matrix tiles in flight (on the chip, sdar's decode shape: 608 us a
#: product at 2, 553 at 3 and at 4; PERF.md section 6, PR 39).
DEPTH = 3
#: Sorted rows a call keeps in VMEM at a time, with their result: more rows
#: go through in blocks of this many (a grid step each, the next block's
#: rows fetched under this one's visits; a group that lies across an edge is
#: streamed on both sides of it).
ROW_BLOCK = 2048


def column_tile(k: int, n: int, itemsize: int) -> int:
    """Columns of an expert's ``[k, n]`` matrix one DMA brings: all of
    them where they fit ``TILE_BYTES``, else the largest whole-lane-tile
    divisor of ``n`` that does (128 at least)."""
    tn = n
    while k * tn * itemsize > TILE_BYTES and tn % 256 == 0:
        tn //= 2
    return tn


def row_blocks(m: int) -> tuple:
    """(rows a block, blocks) for ``m`` sorted rows: one block of all of
    them (whole ``ALIGN`` tiles) up to ``ROW_BLOCK``, else blocks of
    ``ROW_BLOCK``."""
    if m <= ROW_BLOCK:
        return -(-m // ALIGN) * ALIGN, 1
    return ROW_BLOCK, -(-m // ROW_BLOCK)


def vmem_bytes(m: int, k: int, n: int, in_dtype, out_dtype) -> int:
    """VMEM the kernel holds at these shapes: a block of rows and its
    result (twice each where the blocks are a pipeline) and the ring of
    matrix tiles."""
    isz = jnp.dtype(in_dtype).itemsize
    rb, blocks = row_blocks(m)
    held = rb * (k * isz + n * jnp.dtype(out_dtype).itemsize)
    return (held * (2 if blocks > 1 else 1)
            + DEPTH * k * column_tile(k, n, isz) * isz)


def visit_list(sizes: jnp.ndarray, first_group) -> tuple:
    """What the kernel walks, from one layer's group sizes ``[held]``: made
    once a layer (its three products share it).  ``meta`` is
    ``[first_group, rows held]``; ``nonempty[g]`` the first group ``>= g``
    that has a row (``held`` past the last); ``starts`` each group's first
    row, and past the last group a row no block reaches."""
    # (lax's own operations here too: jnp's where, concatenate and stack are
    # jitted functions, each lowered again in every program)
    held = sizes.shape[0]
    sizes = jax.lax.convert_element_type(sizes, jnp.int32)
    full = functools.partial(jax.lax.full, (1,), dtype=jnp.int32)
    ids = jax.lax.select(sizes > 0, jax.lax.iota(jnp.int32, held),
                         jax.lax.full((held,), held, jnp.int32))
    nonempty = jax.lax.concatenate(
        [jax.lax.cummin(ids, reverse=True), full(held)], 0)
    ends = jax.lax.cumsum(sizes)
    first = jax.lax.convert_element_type(first_group, jnp.int32)
    meta = jax.lax.concatenate(
        [jax.lax.reshape(first, (1,)), ends[held - 1:]], 0)
    starts = jax.lax.concatenate(
        [ends - sizes, full(jnp.iinfo(jnp.int32).max)], 0)
    return meta, nonempty, starts, sizes


def _grouped_kernel(meta_sref, nonempty_sref, starts_sref, sizes_sref,
                    x_ref,    # [RB, K] a block of the rows, sorted by group
                    w_hbm,    # [G, K, N] the stacked experts where they lie
                    o_ref,    # [RB, N]
                    wbuf,     # [DEPTH, K, tn] ring of matrix tiles
                    sem,      # DMA semaphores [DEPTH]
                    *, window, depth):
    # (the index arithmetic is lax's own: jnp's floor division, remainder
    # and where are jitted functions, slower to trace and to lower, and a
    # warm start pays both for each shape of the kernel; all the integers
    # here are >= 0)
    div, rem, lo_of, hi_of = (jax.lax.div, jax.lax.rem, jax.lax.min,
                              jax.lax.max)
    first = meta_sref[0]
    held = sizes_sref.shape[0]
    rb = x_ref.shape[0]
    tn = wbuf.shape[2]
    chunks = o_ref.shape[1] // tn
    row0 = pl.program_id(0) * rb  # the block's first row
    row_end = row0 + rb

    def tile_copy(g, c, slot):
        col = 0 if chunks == 1 else pl.multiple_of(c * tn, tn)
        return pltpu.make_async_copy(
            w_hbm.at[first + g, :, pl.ds(col, tn)],
            wbuf.at[slot], sem.at[slot])

    def after(g):
        """The next touched group (``held``: none)."""
        return nonempty_sref[lo_of(g + 1, held)]

    def step(g, c):
        """The tile after column tile ``c`` of group ``g``: the group's
        next, or the first of the next touched group."""
        if chunks == 1:
            return after(g), c
        last = c == chunks - 1
        return (jax.lax.select(last, after(g), g),
                jax.lax.select(last, jnp.int32(0), c + 1))

    def products(g, c, slot):
        """The block's rows of group ``g`` times the tile in ``slot``: row
        windows from the aligned row at or before the group's first row in
        the block, stored where the rows are the group's."""
        start = starts_sref[g]
        end = start + sizes_sref[g]
        a0 = div(hi_of(start, row0) - row0, ALIGN) * ALIGN
        span = lo_of(end, row_end) - row0 - a0
        cols = pl.ds(0 if chunks == 1 else pl.multiple_of(c * tn, tn), tn)

        def piece(j, carry):
            a = pl.multiple_of(lo_of(a0 + j * window, rb - window), ALIGN)
            acc = jax.lax.dot_general(
                x_ref[pl.ds(a, window), :], wbuf[slot],
                (((1,), (0,)), ((), ())), preferred_element_type=jnp.float32)
            row = row0 + a + jax.lax.broadcasted_iota(
                jnp.int32, (window, tn), 0)
            o_ref[pl.ds(a, window), cols] = jax.lax.select(
                (row >= start) & (row < end), acc.astype(o_ref.dtype),
                o_ref[pl.ds(a, window), cols])
            return carry
        jax.lax.fori_loop(0, div(span + window - 1, window), piece, 0)

    # rows past the last group, and a block no group reaches, are zeros
    o_ref[...] = jnp.zeros(o_ref.shape, o_ref.dtype)
    # the first touched group that ends past the block's first row: the one
    # the row before it belongs to, or the next
    g0 = jax.lax.while_loop(
        lambda g: (g < held) & (
            starts_sref[g] + sizes_sref[lo_of(g, held - 1)] <= row0),
        after, nonempty_sref[0])

    def turn(carry):
        """Turn ``t`` starts the copy of the block's ``t``-th tile, if it
        has one, into the slot that the tile multiplied a turn ago has
        left, and multiplies the tile started ``DEPTH - 1`` turns ago: one
        copy site and one product in the body, whatever ``chunks`` is (half
        the lowering of a body that names each column tile, and 5 % slower
        at sarvam's decode shape: PERF.md section 6, PR 39)."""
        t, g_in, c_in, g, c = carry

        @pl.when(starts_sref[g_in] < row_end)
        def _():
            tile_copy(g_in, c_in, rem(t, depth)).start()

        due = t >= depth - 1

        @pl.when(due)
        def _():
            slot = rem(t + 1, depth)
            tile_copy(g, c, slot).wait()
            products(g, c, slot)

        g_then, c_then = step(g, c)
        return (t + 1, *step(g_in, c_in), jax.lax.select(due, g_then, g),
                jax.lax.select(due, c_then, c))

    zero = jnp.int32(0)
    jax.lax.while_loop(lambda carry: starts_sref[carry[3]] < row_end, turn,
                       (zero, g0, zero, g0, zero))


@functools.lru_cache(maxsize=None)
def _grouped_call(m: int, k: int, n: int, groups: int, held: int, dtype,
                  out_dtype, interpret: bool):
    """The kernel's call at these static shapes, jitted: a process traces
    it once, however many programs and layers' products take it; a
    program's lowering still lowers it, once for each of its two shapes
    (17 ms here, four times that on the chip's host: PERF.md section 6, PR
    39)."""
    rb, blocks = row_blocks(m)
    tn = column_tile(k, n, jnp.dtype(dtype).itemsize)
    if blocks == 1:
        rows_spec = out_spec = pl.BlockSpec(memory_space=pltpu.VMEM)
    else:
        # a block past the held rows brings nothing new in: it names the
        # last block that has a held row, which is not fetched again
        rows_spec = pl.BlockSpec(
            (rb, k), lambda b, meta, *_: (
                jnp.minimum(b, jnp.maximum(meta[1] - 1, 0) // rb), 0))
        out_spec = pl.BlockSpec((rb, n), lambda b, *_: (b, 0))
    call = pl.pallas_call(
        functools.partial(_grouped_kernel, window=min(WINDOW, rb),
                          depth=DEPTH),
        out_shape=jax.ShapeDtypeStruct((rb * blocks, n), out_dtype),
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=4,
            grid=(blocks,),
            in_specs=[rows_spec, pl.BlockSpec(memory_space=pl.ANY)],
            out_specs=out_spec,
            scratch_shapes=[
                pltpu.VMEM((DEPTH, k, tn), dtype),
                pltpu.SemaphoreType.DMA((DEPTH,)),
            ],
        ),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("arbitrary",),
            vmem_limit_bytes=vmem_bytes(m, k, n, dtype, out_dtype)
            + 16 * 2**20),
        interpret=interpret,
        name=GROUPED_KERNEL,
    )

    def run(meta, nonempty, starts, sizes, rows, experts):
        if rb * blocks != m:
            rows = jnp.pad(rows, ((0, rb * blocks - m), (0, 0)))
        return call(meta, nonempty, starts, sizes, rows, experts)[:m]

    return jax.jit(run)


def grouped_matmul(rows: jnp.ndarray, experts: jnp.ndarray, visits: tuple,
                   *, out_dtype, interpret: bool = False) -> jnp.ndarray:
    """``rows [M, K]`` (sorted by group) times the experts that
    ``visits`` (:func:`visit_list`) names in ``experts [G, K, N]``, read
    where they lie -> ``[M, N]`` ``out_dtype``; rows past the last group
    are zeros."""
    m, k = rows.shape
    groups, _, n = experts.shape
    return _grouped_call(
        m, k, n, groups, visits[3].shape[0], jnp.dtype(rows.dtype),
        jnp.dtype(out_dtype), interpret)(*visits, rows, experts)
