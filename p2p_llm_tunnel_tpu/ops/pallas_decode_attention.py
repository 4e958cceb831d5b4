"""The Pallas decode-attention kernel: one token a slot against the KV cache.

``decode_attention_rows`` (ISSUE 33) is the read of the plain bf16 cache on
the TPU: one invocation a layer over the stacked cache where it lies, a
software pipeline over each live row's blocks, no view; since ISSUE 36 also
over planes whose rows hold a position's KV heads side by side, keys and
values not equally wide (models/swa.py's full layers), and since ISSUE 56
over rings of such planes (its window layers: slot ``p % R`` holds position
``p``), by a work list that stops at the window from below as well and a
mask by the position a slot holds.  Score, mask, softmax and value product
are one kernel where the einsum (ops/attention.py ``cached_attention``)
lowers to several, and a row's blocks past its position are neither fetched
nor computed.

``models/transformer.py`` ``decode_attention_branch`` is the one place that
chooses between this kernel and the einsum, from what the code can observe
(``decode_kernel_decline``: backend, cache precision, mesh, tiling).  The
einsum stays the numerics oracle (tests/test_decode_rows.py) and the path of
every other cache form.  The kernel is compiled for a described v5e at the
cells' widths in tests/test_tpu_compile.py.
"""

from __future__ import annotations

import functools
from typing import Optional

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

_NEG_INF = -0.7 * float(jnp.finfo(jnp.float32).max)


#: The smallest block of cache positions the rows kernel reads at a time:
#: the decline gate's ``max_seq % 128``.
ROWS_BLOCK = 128
#: Cache rows (positions x kv-heads) a block holds where the sequence
#: allows: under about a thousand the kernel's cost an item shows (4 KV
#: heads at 128 positions ran at 0.40 us an item where the DMA needs 0.32,
#: at 256 positions at the DMA's 0.64; PERF.md section 6, PR 33), above it
#: a row reads further past its own position.
ROWS_COLS = 1024
#: Blocks in flight: the ring holds this many key and value blocks.
ROWS_DEPTH = 4
#: A row's last block is fetched as far as its position, in this many
#: parts (on the chip, a layer of mistral's cell mix: 64.7 us whole, 59.6 in
#: halves, 59.3 in quarters, 67.9 in eighths; PERF.md section 6, PR 33).
ROWS_PARTS = 2

#: The kernel's name in compiled programs and device traces.
ROWS_KERNEL = "decode_attn_rows"


def rows_block(seq: int, kv_heads: int) -> int:
    """Cache positions one item of the rows kernel covers, from the cache's
    own shape: ``ROWS_COLS`` rows of ``[K, D]``, in whole ``ROWS_BLOCK``s
    that divide the sequence."""
    bs = max(ROWS_COLS // kv_heads // ROWS_BLOCK, 1) * ROWS_BLOCK
    while seq % bs:
        bs -= ROWS_BLOCK
    return bs


def decode_rows_worklist(positions: jnp.ndarray, seq: int,
                         block: int) -> jnp.ndarray:
    """The (row, block) pairs one decode step's attention reads, in row
    order: ``[1 + N + B] int32`` with ``N = B * seq // block``: the count
    first, then ``row << 16 | block index`` for every block of ``block``
    positions that holds a position ``<=`` the row's own (a row parked at
    ``positions >= seq`` has none), then the positions themselves — all the
    kernel's scalars that a step's layers share, in the one array that one
    copy brings in.  ``decode_step`` makes it once, outside the layer
    scan."""
    b = positions.shape[0]
    n_sb = seq // block
    pos = positions.astype(jnp.int32)
    nblk = jnp.where(pos < seq, pos // block + 1, 0)
    ends = jnp.cumsum(nblk)
    w = jnp.arange(b * n_sb, dtype=jnp.int32)
    # (compared against every row's end at once: the default search is a
    # loop of its own on the device, once a decode step)
    row = jnp.minimum(
        jnp.searchsorted(ends, w, side="right", method="compare_all")
        .astype(jnp.int32), b - 1)
    blk = w - (ends - nblk)[row]
    return jnp.concatenate([ends[-1:], row << 16 | blk, pos])


#: A ring item's marks beside its block's index (the low bits): the row's
#: first item (the softmax starts over), its last (the row is emitted), and
#: whether that last block may be fetched only as far as the row's slot.
RING_FIRST, RING_LAST, RING_PART = 1 << 15, 1 << 14, 1 << 13


def ring_run(positions, ring: int, block: int, window: int):
    """Which blocks of a ring of ``ring`` slots hold a position in ``(p -
    window, p]`` once ``p`` lies at ``p % ring``: (the oldest such
    position's slot, blocks) of the cyclic run that starts at that slot's
    block.  Before the ring wraps the run is ``max(0, p - window + 1) //
    block .. p // block``; after, the blocks over slots ``(p - window + 1) %
    ring .. p % ring``, which come round to their own first block where the
    window is the ring (counted once: never more than the ring's blocks).
    Whole numbers of numpy or of jax alike (the engine counts what a step
    fetches from the host's positions)."""
    oldest = (positions - min(window, ring) + 1).clip(0)
    at = oldest % ring
    blocks = (at % block + (positions - oldest)) // block + 1
    return at, blocks.clip(None, ring // block)


def ring_row_items(ring: int, block: int, window: int) -> int:
    """The most blocks :func:`ring_run` names for one row."""
    return min(ring // block, (window + block - 2) // block + 1)


def decode_ring_worklist(positions: jnp.ndarray, limit: int, ring: int,
                         block: int, window: int) -> jnp.ndarray:
    """:func:`decode_rows_worklist` for rings: for every row at a position
    ``< limit`` (the full planes' length: a row parked there has no item)
    only the ring blocks of :func:`ring_run`, oldest first, so the newest
    block, which holds the row's own slot, is a row's last item.  ``[1 + N
    + B] int32`` with ``N = B * ring_row_items(...)``: the count, ``row <<
    16 | marks | block index`` an item (``RING_FIRST``, ``RING_LAST``;
    ``RING_PART`` on a last item whose slots past the row's own hold nothing
    the window admits), the positions.  Made once a step and shared by the
    window layers."""
    b = positions.shape[0]
    n_sb = ring // block
    pos = positions.astype(jnp.int32)
    at, run = ring_run(pos, ring, block, window)
    nblk = jnp.where(pos < limit, run, 0)
    ends = jnp.cumsum(nblk)
    w = jnp.arange(b * ring_row_items(ring, block, window), dtype=jnp.int32)
    row = jnp.minimum(
        jnp.searchsorted(ends, w, side="right", method="compare_all")
        .astype(jnp.int32), b - 1)
    nth = w - (ends - nblk)[row]
    blk = (at[row] // block + nth) % n_sb
    last = nth == nblk[row] - 1
    # (a run that came round to its first block holds the window's oldest
    # positions past the row's own slot: that block is read whole)
    slot = pos % ring
    came_round = (at // block == slot // block) & (at > slot)
    part = last & ~came_round[row]
    marks = (jnp.where(nth == 0, RING_FIRST, 0) | jnp.where(last, RING_LAST, 0)
             | jnp.where(part, RING_PART, 0))
    return jnp.concatenate([ends[-1:], row << 16 | marks | blk, pos])


def _decode_rows_kernel(
    layer_sref,  # scalar-prefetch [2] int32: layer index into the [L,...]
    #              cache, sliding window (S+1 = disabled)
    work_sref,   # scalar-prefetch [1 + N + B] int32: decode_rows_worklist
    q_ref,      # [B, H, D] every row's query heads (head = kv_head * G + g)
    k_hbm,      # [L, B, S*K, D] the stacked cache where it lies (HBM)
    v_hbm,
    *rest,      # [sink_ref [H, 1] float32 under ``sink``,] then
    #             o_ref [B, H, D],
    #             kbuf, vbuf [DEPTH, BS*K, D] the key and value blocks in
    #             flight, sem [2, DEPTH] their DMA semaphores
    scale: float,
    softcap: Optional[float],
    block_s: int,
    depth: int,
    parts: int,
    kv_heads: int,
    side_by_side: bool = False,
    ring: int = 0,
    sink: bool = False,
):
    """One invocation a layer: a software pipeline over the step's work
    list.  Each item is one ``[BS*K, D]`` block of one row — positions
    outermost, kv-heads inside, as the cache lies — and is scored against
    ALL of the row's query heads in one product; the mask takes the other
    kv-heads' columns out again, so the value product over the same flat
    block sums only a head's own.  The MXU's cost is set by the block
    (each key and value tile passes through once), not by the 4 or 7 query
    rows a kv-head has, so the wasted columns cost nothing and the block
    needs no de-interleaving.

    ``side_by_side`` is the other way a cache lies (ISSUE 36: planes
    ``[L, B, S, K*Dk]`` and ``[L, B, S, K*Dv]``, a position's KV heads in
    its columns): the same work list, ring and softmax over another item.
    A block is ``[BS, K*Dk]`` keys and ``[BS, K*Dv]`` values; ``q_ref`` is
    ``[B, H, K*Dk]`` with a head's query in its own KV head's columns and
    zeros elsewhere, so ONE product scores every head against the block
    with no head mask (the other heads' keys meet zeros), and a KV head's
    value columns — a static slice of whole lane tiles — take only its own
    query heads' weights.  ``o_ref`` is ``[B, H, Dv]``.

    ``ring`` (ISSUE 56: the planes' ``S`` is a ring of that many slots, slot
    ``p % ring`` holding position ``p``) changes two things, both static: a
    block column's position is the newest ``<=`` the row's own that lies at
    its slot (``ops.attention.ring_positions``; one the ring has not come
    round to is negative and masked), and a row's first and last item are
    what ``decode_ring_worklist`` marked, not block 0 and the row's own
    block.  Under ``sink`` a row's denominator takes ``exp(sink_h - m)``
    once, when the row is emitted (``ops.attention.masked_attention``)."""
    sink_ref = rest[0] if sink else None
    o_ref, kbuf, vbuf, sem = rest[-4:]
    layer = layer_sref[0]
    window = layer_sref[1]
    n_work = work_sref[0]
    rows_per_blk = kbuf.shape[1]
    b, h = q_ref.shape[:2]
    d_out = o_ref.shape[2]
    # buffer rows a cache position takes
    per_pos = rows_per_blk // block_s
    pos_at = work_sref.shape[0] - b

    def whole_div(x, n):
        # x // n of a small non-negative int32 vector, by way of float32
        # (exact: x + 0.5 is never within 2^-12 of a multiple of n here)
        return ((x.astype(jnp.float32) + 0.5) * (1.0 / n)).astype(jnp.int32)

    # A block column's position inside the block and its kv-head; a query
    # head's kv-head.
    col = jax.lax.broadcasted_iota(jnp.int32, (1, rows_per_blk), 1)
    if side_by_side:
        col_off = col
    else:
        col_off = whole_div(col, kv_heads)
        col_head = col - col_off * kv_heads
        row_head = whole_div(
            jax.lax.broadcasted_iota(jnp.int32, (h, 1), 0), h // kv_heads)

    def item(w):
        """Work item ``w``: its row, its block's index (under ``ring`` with
        the item's marks above it), the row's position."""
        packed = work_sref[1 + w]
        row = packed >> 16
        return row, packed & 0xFFFF, work_sref[pos_at + row]

    def copies(w, slot, then):
        """``then`` each of item ``w``'s two copies into ``slot``.  A row's
        last block is brought in only as far as the part (one of ``parts``)
        that holds the row's position: the rest of the buffer keeps what an
        earlier item left there, which the mask takes out (the value ring
        is zeroed once below, so what it takes out is finite)."""
        row, blk, pos = item(w)
        if ring:
            in_part = (blk & RING_PART) != 0
            blk, pos = blk & (RING_PART - 1), pos % ring
        start = pl.multiple_of(blk * rows_per_blk, rows_per_blk)
        part = rows_per_blk // parts
        needed = jnp.where(in_part if ring else blk == pos // block_s,
                           (pos % block_s) * per_pos // part + 1, parts)
        for n in range(1, parts + 1):
            @pl.when(needed == n)
            def _(n=n):
                for hbm, buf, which in ((k_hbm, kbuf, 0), (v_hbm, vbuf, 1)):
                    then(pltpu.make_async_copy(
                        hbm.at[layer, row, pl.ds(start, n * part)],
                        buf.at[slot, pl.ds(0, n * part)],
                        sem.at[which, slot]))

    def start(w):
        @pl.when(w < n_work)
        def _():
            copies(w, w % depth, lambda c: c.start())

    def weighted(p, v):
        """Float32 weights ``[R, N]`` over values ``[N, W]`` -> float32."""
        if v.dtype == jnp.float32:
            return jax.lax.dot_general(
                p, v, (((1,), (0,)), ((), ())),
                preferred_element_type=jnp.float32)
        # The weights in two bf16 halves, stacked on the rows: 2R rows cost
        # the MXU what R do, and the sum is the float32 weight's product to
        # 2^-16.
        hi = p.astype(v.dtype)
        lo = (p - hi.astype(jnp.float32)).astype(v.dtype)
        both = jax.lax.dot_general(
            jnp.concatenate([hi, lo], axis=0), v,
            (((1,), (0,)), ((), ())),
            preferred_element_type=jnp.float32)
        return both[:p.shape[0]] + both[p.shape[0]:]

    # A parked row is in no item: its output is zeros, not what the buffer
    # held.
    o_ref[...] = jnp.zeros_like(o_ref)
    vbuf[...] = jnp.zeros_like(vbuf)
    for w0 in range(depth - 1):
        start(w0)

    def body(w, carry):
        m_prev, l_prev, acc = carry
        slot = w % depth
        row, blk, pos = item(w)
        copies(w, slot, lambda c: c.wait())
        # The slot the previous item has just left takes the item DEPTH - 1
        # ahead.
        start(w + depth - 1)

        if ring:
            first, last = (blk & RING_FIRST) != 0, (blk & RING_LAST) != 0
            blk = blk & (RING_PART - 1)
        else:
            first = blk == 0
        m_prev = jnp.where(first, _NEG_INF, m_prev)
        l_prev = jnp.where(first, 0.0, l_prev)
        acc = jnp.where(first, 0.0, acc)

        q = q_ref[row]  # [H, D]
        k = kbuf[slot]  # [BS*K, D]
        v = vbuf[slot]
        s = jax.lax.dot_general(
            q, k, (((1,), (1,)), ((), ())),
            preferred_element_type=jnp.float32,
        ) * scale  # [H, BS*K]
        if softcap is not None:
            s = softcap * jnp.tanh(s / softcap)
        if ring:
            # How far behind the row's own position a slot's is.
            at = pos % ring - (blk * block_s + col_off)
            behind = jnp.where(at < 0, at + ring, at)  # [1, BS*K]
            live = (behind <= pos) & (behind < window)
        else:
            k_pos = blk * block_s + col_off  # [1, BS*K]
            live = (k_pos <= pos) & ((pos - k_pos) < window)
        if not side_by_side:
            live = live & (col_head == row_head)
        s = jnp.where(live, s, _NEG_INF)

        m_new = jnp.maximum(m_prev, s.max(axis=-1, keepdims=True))
        corr = jnp.where(m_prev <= _NEG_INF, 0.0, jnp.exp(m_prev - m_new))
        p = jnp.where(s <= _NEG_INF, 0.0, jnp.exp(s - m_new))
        l_new = l_prev * corr + p.sum(axis=-1, keepdims=True)
        if side_by_side:
            g = h // kv_heads
            pv = jnp.concatenate([
                weighted(p[n * g:(n + 1) * g],
                         v[:, n * d_out:(n + 1) * d_out])
                for n in range(kv_heads)], axis=0)
        else:
            pv = weighted(p, v)
        acc = acc * corr + pv

        @pl.when(last if ring else blk == pos // block_s)
        def _emit():
            if sink:
                top = jnp.maximum(m_new, sink_ref[...])
                keys = jnp.exp(m_new - top)
                total = l_new * keys + jnp.exp(sink_ref[...] - top)
                o_ref[row] = (acc * keys / total).astype(o_ref.dtype)
            else:
                o_ref[row] = (acc / jnp.maximum(l_new, 1e-30)).astype(
                    o_ref.dtype)

        return m_new, l_new, acc

    jax.lax.fori_loop(0, n_work, body, (
        jnp.full((h, 1), _NEG_INF, jnp.float32),
        jnp.zeros((h, 1), jnp.float32),
        jnp.zeros((h, d_out), jnp.float32),
    ))


def decode_attention_rows(
    q: jnp.ndarray,        # [B, H, D]
    k_cache: jnp.ndarray,  # [L, B, S, K, D] the stacked cache, row written,
    v_cache: jnp.ndarray,  # or planes [L, B, S, K*D] and [L, B, S, K*Dv]
    layer_idx,               # int32 scalar (traced: the scan's layer index)
    work: jnp.ndarray,       # decode_rows_worklist(positions, S, block)
    *,
    block: int,  # static: rows_block(S, K), the work list's
    scale: Optional[float] = None,
    softcap: Optional[float] = None,
    window=None,  # None | int | traced int scalar
    ring: bool = False,  # static: ``S`` is a ring, ``work`` its list
    sink: Optional[jnp.ndarray] = None,  # [H] float32 logits
    interpret: bool = False,
) -> jnp.ndarray:
    """``cached_attention`` over layer ``layer_idx`` of the stacked cache,
    read where it lies: no plane is sliced out, a row's blocks past its
    position are neither fetched nor computed, and a row parked at a
    position ``>= S`` does no work (its output is zeros).  The cache
    already holds this step's own row at the positions the work list was
    made from.  Same mathematics as the einsum: the cache's own operands
    into float32 scores, float32 softmax and accumulation.  A window masks;
    over the planes it does not bound the blocks fetched from below.

    Under ``ring`` the planes' ``S`` slots are a ring (slot ``p % S`` holds
    position ``p``), ``work`` is ``decode_ring_worklist``'s and names only
    the blocks that hold a position of the row's window, and a slot is
    masked by the position it holds.  ``sink``: one logit a head that joins
    the softmax's denominator and carries no value
    (``ops.attention.masked_attention``).

    The layout is the cache's own and read off its shape: five axes are
    ``[L, B, S, K, D]``; four are planes whose rows hold a position's KV
    heads side by side, keys ``K * D`` wide (``D`` the query's) and values
    ``K * Dv``, which need not be as wide.  Returns ``[B, H, D]``, of the
    planes ``[B, H, Dv]``."""
    side_by_side = k_cache.ndim == 4
    h, d = q.shape[1:]
    if side_by_side:
        l, b, s, width = k_cache.shape
        kh = width // d
        dv = v_cache.shape[-1] // kh
        per_pos = 1
        # A head's query over its own KV head's columns, zeros elsewhere.
        own = jnp.eye(kh, dtype=q.dtype)[None, :, None, :, None]
        q = (q.reshape(b, kh, h // kh, 1, d) * own).reshape(b, h, width)
    else:
        l, b, s, kh, d = k_cache.shape
        dv, per_pos = d, kh
    if s % ROWS_BLOCK or s % block:
        raise ValueError(f"rows decode kernel needs S % {ROWS_BLOCK} == 0 "
                         f"and whole blocks of {block}, got {s}")
    if scale is None:
        scale = d**-0.5
    rows = block * per_pos
    layer = jnp.stack([
        jnp.asarray(layer_idx, jnp.int32),
        jnp.asarray(s + 1 if window is None else window, jnp.int32),
    ])

    kernel = functools.partial(
        _decode_rows_kernel,
        scale=scale, softcap=softcap, block_s=block, depth=ROWS_DEPTH,
        parts=ROWS_PARTS, kv_heads=kh, side_by_side=side_by_side,
        ring=s if ring else 0, sink=sink is not None,
    )
    more = [] if sink is None else [
        sink.astype(jnp.float32).reshape(h, 1)]
    if not side_by_side:
        # (a bitcast: positions outermost, KV heads inside, as they lie)
        k_cache = k_cache.reshape(l, b, s * kh, d)
        v_cache = v_cache.reshape(l, b, s * kh, d)
    vmem = pl.BlockSpec(memory_space=pltpu.VMEM)
    hbm = pl.BlockSpec(memory_space=pl.ANY)
    return pl.pallas_call(
        kernel,
        out_shape=jax.ShapeDtypeStruct((b, h, dv), q.dtype),
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=2,
            grid=(1,),
            in_specs=[vmem, hbm, hbm] + [vmem] * len(more),
            out_specs=vmem,
            scratch_shapes=[
                pltpu.VMEM((ROWS_DEPTH, rows, k_cache.shape[-1]),
                           k_cache.dtype),
                pltpu.VMEM((ROWS_DEPTH, rows, v_cache.shape[-1]),
                           v_cache.dtype),
                pltpu.SemaphoreType.DMA((2, ROWS_DEPTH)),
            ],
        ),
        interpret=interpret,
        name=ROWS_KERNEL,
    )(layer, work, q, k_cache, v_cache, *more)
