"""The rows decode kernel (ISSUE 33) against the einsum oracle.

``decode_attention_rows`` reads layer ``idx`` of the STACKED cache where it
lies, each row up to its own position; ``cached_attention`` on that layer's
plane is the ground truth.  Interpret mode on the CPU (the kernel compiled
for a described v5e: tests/test_tpu_compile_kernels.py).  The branch that
chooses it, the plan that follows it and the engine on it:
tests/test_decode_rows_engine.py.
"""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from p2p_llm_tunnel_tpu.ops.attention import (
    cached_attention,
    masked_attention,
    ring_positions,
    window_mask,
)
from p2p_llm_tunnel_tpu.ops.pallas_decode_attention import (
    RING_FIRST,
    RING_LAST,
    RING_PART,
    ROWS_BLOCK,
    decode_attention_rows,
    decode_ring_worklist,
    decode_rows_worklist,
    ring_row_items,
    ring_run,
    rows_block,
)
from tests.ring_rows import blocks_by_hand


S = 512
#: position 0, a block's last and the next block's first, S - 1, a row
#: parked at S and one beyond, and ragged ones between
POSITIONS = [0, ROWS_BLOCK - 1, ROWS_BLOCK, S - 1, S, S + 7, 300, 41]


def _mk(h, kh, d, dtype, layers=3, seed=0):
    ks = jax.random.split(jax.random.PRNGKey(seed), 3)
    b = len(POSITIONS)
    q = jax.random.normal(ks[0], (b, h, d), dtype)
    k = jax.random.normal(ks[1], (layers, b, S, kh, d), dtype)
    v = jax.random.normal(ks[2], (layers, b, S, kh, d), dtype)
    return q, k, v, jnp.asarray(POSITIONS, jnp.int32)


def _both(q, k, v, pos, layer, block=ROWS_BLOCK, **kw):
    got = decode_attention_rows(
        q, k, v, jnp.int32(layer), decode_rows_worklist(pos, S, block),
        block=block, interpret=True, **kw)
    want = cached_attention(q[:, None], k[layer], v[layer], pos, **kw)[:, 0]
    return np.asarray(got, np.float32), np.asarray(want, np.float32)


def test_worklist_is_each_live_rows_blocks_in_row_order():
    for block in (ROWS_BLOCK, 2 * ROWS_BLOCK):
        work = np.asarray(decode_rows_worklist(
            jnp.asarray(POSITIONS, jnp.int32), S, block))
        assert work.shape == (1 + len(POSITIONS) * S // block
                              + len(POSITIONS),)
        assert list(work[-len(POSITIONS):]) == POSITIONS
        items = [(int(w) >> 16, int(w) & 0xFFFF)
                 for w in work[1:1 + work[0]]]
        want = [(row, blk) for row, p in enumerate(POSITIONS) if p < S
                for blk in range(p // block + 1)]
        assert items == want
        assert all(row not in (4, 5) for row, _ in items)  # parked: no work


@pytest.mark.parametrize("seq,kv_heads,want", [
    (1024, 8, 128), (1024, 4, 256), (1024, 16, 128), (1024, 2, 512),
    (1024, 1, 1024), (384, 4, 128), (768, 4, 256), (4096, 8, 128),
])
def test_a_block_is_about_a_thousand_cache_rows_in_whole_blocks(
        seq, kv_heads, want):
    assert rows_block(seq, kv_heads) == want


@pytest.mark.parametrize("h,kh", [(32, 8), (28, 4), (4, 4), (8, 1)])
def test_rows_match_the_einsum_on_the_stacked_cache(h, kh):
    """Both GQA ratios of the cells (32:8, 28:4), plain MHA and MQA, over
    ragged positions with every edge; the layer read is the one asked."""
    q, k, v, pos = _mk(h, kh, 32, jnp.float32)
    live = np.asarray(POSITIONS) < S
    for layer, block in ((0, ROWS_BLOCK), (2, rows_block(S, kh))):
        got, want = _both(q, k, v, pos, layer, block)
        np.testing.assert_allclose(got[live], want[live],
                                   rtol=2e-5, atol=2e-5)
        assert not got[~live].any()  # a parked row does no work


@pytest.mark.parametrize("h,kh", [(32, 8), (28, 4)])
def test_rows_match_the_einsum_in_bfloat16(h, kh):
    """The cells' own precision: bf16 operands, float32 scores and sums."""
    q, k, v, pos = _mk(h, kh, 128, jnp.bfloat16, layers=2)
    live = np.asarray(POSITIONS) < S
    got, want = _both(q, k, v, pos, 1)
    np.testing.assert_allclose(got[live], want[live], rtol=2e-2, atol=2e-2)


@pytest.mark.parametrize("kw", [
    dict(window=64), dict(window=200), dict(window=S + 1),
    dict(softcap=20.0), dict(scale=0.25, softcap=30.0, window=130),
])
def test_rows_take_window_scale_and_softcap(kw):
    q, k, v, pos = _mk(8, 2, 32, jnp.float32, seed=3)
    live = np.asarray(POSITIONS) < S
    got, want = _both(q, k, v, pos, 1, **kw)
    np.testing.assert_allclose(got[live], want[live], rtol=2e-5, atol=2e-5)


def test_rows_take_a_traced_window_and_layer_inside_a_scan():
    """gemma-2's alternating layers hand the window, and the layer scan the
    index, as traced scalars."""
    q, k, v, pos = _mk(8, 2, 32, jnp.float32, seed=5)
    work = decode_rows_worklist(pos, S, ROWS_BLOCK)
    wins = jnp.asarray([64, S + 1, 64], jnp.int32)

    def body(_, xs):
        idx, win = xs
        return None, decode_attention_rows(
            q, k, v, idx, work, block=ROWS_BLOCK, window=win,
            interpret=True)

    _, got = jax.lax.scan(body, None, (jnp.arange(3), wins))
    live = np.asarray(POSITIONS) < S
    for layer in range(3):
        want = cached_attention(q[:, None], k[layer], v[layer], pos,
                                window=int(wins[layer]))[:, 0]
        np.testing.assert_allclose(np.asarray(got[layer])[live],
                                   np.asarray(want)[live],
                                   rtol=2e-5, atol=2e-5)


# ---------------------------------------------------------------------------
# the other layout: planes whose rows hold the KV heads side by side
# (ISSUE 36; models/swa.py's full layers, keys and values not equally wide)
# ---------------------------------------------------------------------------

def _planes(h, kh, dk, dv, dtype, layers=2, seed=0):
    ks = jax.random.split(jax.random.PRNGKey(seed), 3)
    b = len(POSITIONS)
    q = jax.random.normal(ks[0], (b, h, dk), dtype)
    k = jax.random.normal(ks[1], (layers, b, S, kh * dk), dtype)
    v = jax.random.normal(ks[2], (layers, b, S, kh * dv), dtype)
    return q, k, v, jnp.asarray(POSITIONS, jnp.int32)


def _plane_oracle(q, k, v, pos, layer, **kw):
    """``masked_attention`` over the layer's plane, causal."""
    b, h, dk = q.shape
    kh = k.shape[-1] // dk
    mask = window_mask(pos[:, None], jnp.broadcast_to(jnp.arange(S), (b, S)),
                       kw.get("window"))
    return masked_attention(
        q[:, None], k[layer].reshape(b, S, kh, dk),
        v[layer].reshape(b, S, kh, -1), mask,
        kw.get("scale") or dk ** -0.5)[:, 0]


@pytest.mark.parametrize("h,kh,dk,dv,dtype,tol", [
    (64, 4, 192, 128, jnp.float32, 2e-5),   # the cell's heads and widths
    (64, 4, 192, 128, jnp.bfloat16, 2e-2),  # and its precision
    (4, 1, 24, 16, jnp.float32, 2e-5),      # tiny-swa-moe's full layers
    (8, 2, 24, 16, jnp.float32, 2e-5),
    (8, 8, 16, 32, jnp.bfloat16, 2e-2),     # values wider than keys, MHA
])
def test_rows_match_the_einsum_on_planes_of_heads_side_by_side(
        h, kh, dk, dv, dtype, tol):
    """Keys ``[L,B,S,K*Dk]`` and values ``[L,B,S,K*Dv]`` with ``Dk != Dv``,
    read off the planes' own shapes: positions 0, a block's last and the next
    block's first, S - 1, parked rows (no work: zeros), ragged ones; the
    layer read is the one asked; the answer is a head's ``Dv`` columns."""
    q, k, v, pos = _planes(h, kh, dk, dv, dtype)
    live = np.asarray(POSITIONS) < S
    for layer, block in ((0, ROWS_BLOCK), (1, rows_block(S, kh))):
        got = decode_attention_rows(
            q, k, v, jnp.int32(layer), decode_rows_worklist(pos, S, block),
            block=block, interpret=True)
        assert got.shape == (len(POSITIONS), h, dv) and got.dtype == dtype
        got = np.asarray(got, np.float32)
        want = np.asarray(_plane_oracle(q, k, v, pos, layer), np.float32)
        np.testing.assert_allclose(got[live], want[live], rtol=tol, atol=tol)
        assert not got[~live].any()


def test_rows_on_planes_take_a_scale_a_window_and_a_traced_layer_in_a_scan():
    q, k, v, pos = _planes(8, 2, 24, 16, jnp.float32, layers=3, seed=5)
    block = rows_block(S, 2)
    work = decode_rows_worklist(pos, S, block)

    def body(_, idx):
        return None, decode_attention_rows(
            q, k, v, idx, work, block=block, scale=0.25, window=130,
            interpret=True)

    _, got = jax.lax.scan(body, None, jnp.arange(3))
    live = np.asarray(POSITIONS) < S
    for layer in range(3):
        want = _plane_oracle(q, k, v, pos, layer, scale=0.25, window=130)
        np.testing.assert_allclose(np.asarray(got[layer])[live],
                                   np.asarray(want)[live],
                                   rtol=2e-5, atol=2e-5)


@pytest.mark.parametrize("layout", ["planes", "stacked"])
def test_a_row_is_read_no_further_than_its_position(layout):
    """What lies past a row's own position is in no item: poison there (and
    in every other layer) changes nothing, to the bit, in either layout."""
    if layout == "planes":
        q, k, v, pos = _planes(8, 2, 24, 16, jnp.float32, layers=2, seed=9)
    else:
        q, k, v, pos = _mk(8, 2, 32, jnp.float32, layers=2, seed=9)
    block = rows_block(S, 2)
    work = decode_rows_worklist(pos, S, block)
    tail = (1,) * (k.ndim - 3)
    past = (jnp.arange(S)[None, :] > pos[:, None]).reshape((1, -1, S) + tail)
    other = (jnp.arange(2) != 1).reshape((2, 1, 1) + tail)
    bad = past | other
    got, poisoned = (
        decode_attention_rows(q, a, b_, jnp.int32(1), work, block=block,
                              interpret=True)
        for a, b_ in ((k, v), (jnp.where(bad, jnp.nan, k),
                               jnp.where(bad, 3e38, v))))
    np.testing.assert_array_equal(np.asarray(got), np.asarray(poisoned))


def _layout(name, seed):
    """(q, k, v, oracle, block): the stacked cache or the planes, four KV
    heads either way, so a work item is two ``ROWS_BLOCK``s long."""
    if name == "stacked":
        q, k, v, _ = _mk(8, 4, 32, jnp.float32, layers=2, seed=seed)

        def oracle(pos):
            return cached_attention(q[:, None], k[1], v[1], pos)[:, 0]
    else:
        q, k, v, _ = _planes(8, 4, 24, 16, jnp.float32, seed=seed)

        def oracle(pos):
            return _plane_oracle(q, k, v, pos, 1)
    block = rows_block(S, 4)
    assert block == 2 * ROWS_BLOCK

    def rows(pos):
        return decode_attention_rows(
            q, k, v, jnp.int32(1), decode_rows_worklist(pos, S, block),
            block=block, interpret=True)
    return rows, oracle, block


@pytest.mark.parametrize("edge", [-1, 0, 1])
@pytest.mark.parametrize("layout", ["stacked", "planes"])
def test_rows_match_the_einsum_where_a_work_list_breaks(layout, edge):
    """A row whose position is an item's last, the next item's first and
    the one after (the last block's partial fetch ends, begins, and holds
    one position), beside rows that end elsewhere, at an item wider than
    ``ROWS_BLOCK``."""
    rows, oracle, block = _layout(layout, seed=11)
    pos = jnp.asarray([block + edge, 0, S - 1, block + edge, 41,
                       block - 1 - edge, 300, ROWS_BLOCK], jnp.int32)
    np.testing.assert_allclose(np.asarray(rows(pos)), np.asarray(oracle(pos)),
                               rtol=2e-5, atol=2e-5)


@pytest.mark.parametrize("layout", ["stacked", "planes"])
def test_a_parked_row_leaves_the_live_rows_answers_to_the_bit(layout):
    """Rows parked at ``S`` and beyond are in no item: the rows around them
    answer what they answer when those rows are live, bit for bit (the
    engine parks a slot between requests, beside slots that decode)."""
    rows, _, block = _layout(layout, seed=13)
    live = np.asarray([0, 1, 3, 5, 7])
    base = np.asarray([block, 5, 0, S - 1, 0, 300, 0, block - 1], np.int32)
    parked, busy = base.copy(), base.copy()
    parked[[2, 4, 6]] = [S, S + 7, S]
    busy[[2, 4, 6]] = [S - 1, 17, block + 1]
    got = np.asarray(rows(jnp.asarray(parked)))
    np.testing.assert_array_equal(
        got[live], np.asarray(rows(jnp.asarray(busy)))[live])
    assert not got[[2, 4, 6]].any() and got[live].any()


# ---------------------------------------------------------------------------
# rings (ISSUE 56; models/swa.py's window layers): slot ``p % R`` holds
# position ``p``, the work list stops at the window from below as well
# ---------------------------------------------------------------------------

#: The full planes' length beside the rings: a row at or past it is parked.
LIMIT = 4096
#: (ring, window) -> positions: a ring part full (some rows short of one
#: block, of the window), a row at position 0, at a block's and the ring's
#: last slot, just wrapped, wrapped many times, parked.
RING_CASES = {
    "window-half-the-ring": (512, 256),       # laguna's proportions
    "window-a-fifth-of-the-ring": (640, 128),  # mimo's
    "window-is-the-ring": (256, 256),
    "window-no-multiple-of-a-block": (384, 200),
    "window-of-one": (256, 1),
    "ring-of-one-block": (128, 128),
    "window-short-of-the-ring-by-one": (384, 383),
}


def _ring_rows(ring):
    return [0, 5, ROWS_BLOCK - 1, ROWS_BLOCK, ring - 1, ring, ring + 1,
            ring + ROWS_BLOCK - 1, 3 * ring + 41, 7 * ring - 1, LIMIT - 1,
            LIMIT, LIMIT + 9, 2 * ring, 300, 1]


@pytest.mark.parametrize("case", sorted(RING_CASES))
def test_ring_worklist_is_each_live_rows_window_blocks_oldest_first(case):
    ring, window = RING_CASES[case]
    rows = _ring_rows(ring)
    work = np.asarray(decode_ring_worklist(
        jnp.asarray(rows, jnp.int32), LIMIT, ring, ROWS_BLOCK, window))
    per_row = ring_row_items(ring, ROWS_BLOCK, window)
    assert per_row == min(ring // ROWS_BLOCK,
                          -(-(window - 1) // ROWS_BLOCK) + 1)
    assert work.shape == (1 + len(rows) * per_row + len(rows),)
    assert list(work[-len(rows):]) == rows
    items = [(int(w) >> 16, int(w) & (RING_PART - 1),
              bool(w & RING_FIRST), bool(w & RING_LAST), bool(w & RING_PART))
             for w in work[1:1 + work[0]]]
    want, most = [], 0
    for row, p in enumerate(rows):
        if p >= LIMIT:
            continue  # parked: no work
        blocks = blocks_by_hand(p, ring, window, ROWS_BLOCK)
        most = max(most, len(blocks))
        newest = p % ring // ROWS_BLOCK
        # the newest block may be cut at the row's slot unless the window's
        # oldest positions lie past it in the same block
        cut = all(held % ring <= p % ring
                  for held in range(max(0, p - window + 1), p + 1)
                  if held % ring // ROWS_BLOCK == newest)
        for n, blk in enumerate(blocks):
            last = n == len(blocks) - 1
            want.append((row, blk, n == 0, last, last and cut))
            assert not (last and cut) or blk == newest
    assert items == want
    assert most <= per_row
    # the engine's count of what a step fetches is the list's
    _, counted = ring_run(np.asarray(rows), ring, ROWS_BLOCK, window)
    assert int(counted[np.asarray(rows) < LIMIT].sum()) == work[0]


def _ring_planes(h, kh, dk, dv, dtype, ring, layers=2, seed=0, sink=False):
    ks = jax.random.split(jax.random.PRNGKey(seed), 4)
    rows = _ring_rows(ring)
    b = len(rows)
    q = jax.random.normal(ks[0], (b, h, dk), dtype)
    k = jax.random.normal(ks[1], (layers, b, ring, kh * dk), dtype)
    v = jax.random.normal(ks[2], (layers, b, ring, kh * dv), dtype)
    logits = jax.random.normal(ks[3], (h,), jnp.float32) if sink else None
    return q, k, v, jnp.asarray(rows, jnp.int32), logits


def _ring_oracle(q, k, v, pos, layer, window, logits):
    """``masked_attention`` over the layer's ring, each slot named by the
    position it holds: what the einsum path computes."""
    b, h, dk = q.shape
    ring, kh = k.shape[2], k.shape[-1] // dk
    mask = window_mask(pos[:, None], ring_positions(pos, ring), window)
    return masked_attention(
        q[:, None], k[layer].reshape(b, ring, kh, dk),
        v[layer].reshape(b, ring, kh, -1), mask, dk ** -0.5,
        sink=logits)[:, 0]


@pytest.mark.parametrize("sink", [False, True], ids=["no-sink", "sink"])
@pytest.mark.parametrize("h,kh,dk,dv,dtype,tol", [
    (72, 8, 128, 128, jnp.float32, 2e-5),   # laguna's window layers: 9 a
    (72, 8, 128, 128, jnp.bfloat16, 2e-2),  # KV head, and its precision
    (64, 8, 192, 128, jnp.float32, 2e-5),   # mimo's: 8 a KV head, keys
    (64, 8, 192, 128, jnp.bfloat16, 2e-2),  # wider than values
    (9, 3, 16, 16, jnp.float32, 2e-5),      # tiny-laguna's
    (4, 2, 24, 16, jnp.float32, 2e-5),      # tiny-swa-moe's
])
def test_rows_match_the_einsum_on_rings(h, kh, dk, dv, dtype, tol, sink):
    """The ring form against ``masked_attention`` over ``ring_positions``,
    planes side by side at both cells' head counts and widths, with and
    without a sink: rings part full, at every edge, wrapped and parked."""
    ring, window = 384, 200
    q, k, v, pos, logits = _ring_planes(h, kh, dk, dv, dtype, ring, sink=sink)
    live = np.asarray(pos) < LIMIT
    work = decode_ring_worklist(pos, LIMIT, ring, ROWS_BLOCK, window)
    got = decode_attention_rows(
        q, k, v, jnp.int32(1), work, block=ROWS_BLOCK, window=window,
        ring=True, sink=logits, interpret=True)
    assert got.shape == (len(live), h, dv) and got.dtype == dtype
    got = np.asarray(got, np.float32)
    want = np.asarray(_ring_oracle(q, k, v, pos, 1, window, logits),
                      np.float32)
    np.testing.assert_allclose(got[live], want[live], rtol=tol, atol=tol)
    assert not got[~live].any()


@pytest.mark.parametrize("case", sorted(RING_CASES))
def test_rows_match_the_einsum_on_every_ring_and_window(case):
    """Every proportion of window and ring, a sink, the layer index traced
    inside a scan (the runs' scans hand it so)."""
    ring, window = RING_CASES[case]
    q, k, v, pos, logits = _ring_planes(8, 2, 24, 16, jnp.float32, ring,
                                        layers=3, seed=3, sink=True)
    work = decode_ring_worklist(pos, LIMIT, ring, ROWS_BLOCK, window)

    def body(_, idx):
        return None, decode_attention_rows(
            q, k, v, idx, work, block=ROWS_BLOCK, window=window, ring=True,
            sink=logits, interpret=True)

    _, got = jax.lax.scan(body, None, jnp.arange(3))
    live = np.asarray(pos) < LIMIT
    for layer in range(3):
        want = _ring_oracle(q, k, v, pos, layer, window, logits)
        np.testing.assert_allclose(np.asarray(got[layer])[live],
                                   np.asarray(want)[live],
                                   rtol=2e-5, atol=2e-5)


def test_a_ring_on_the_stacked_layout_reads_like_the_planes():
    """The position map is the layout's neighbour, not its part: a ring of
    ``[L, B, R, K, D]`` reads like the same values side by side."""
    ring, window = 256, 100
    q, k, v, pos, _ = _ring_planes(8, 2, 32, 32, jnp.float32, ring, seed=7)
    work = decode_ring_worklist(pos, LIMIT, ring, ROWS_BLOCK, window)
    got = [decode_attention_rows(
        q, a, b_, jnp.int32(1), work, block=ROWS_BLOCK, window=window,
        ring=True, interpret=True)
        for a, b_ in ((k, v), (k.reshape(k.shape[:3] + (2, 32)),
                               v.reshape(v.shape[:3] + (2, 32))))]
    np.testing.assert_allclose(np.asarray(got[0]), np.asarray(got[1]),
                               rtol=2e-5, atol=2e-5)


@pytest.mark.parametrize("case", ["window-half-the-ring",
                                  "window-a-fifth-of-the-ring"])
def test_a_ring_is_fetched_no_further_than_its_window(case):
    """What the list does not name is in no item: NaN keys and huge values
    in every block outside a row's run (and in every other layer) change
    nothing, to the bit."""
    ring, window = RING_CASES[case]
    q, k, v, pos, logits = _ring_planes(8, 2, 24, 16, jnp.float32, ring,
                                        seed=9, sink=True)
    work = decode_ring_worklist(pos, LIMIT, ring, ROWS_BLOCK, window)
    named = np.zeros((len(pos), ring // ROWS_BLOCK), bool)
    for w in np.asarray(work[1:1 + int(work[0])]):
        named[int(w) >> 16, int(w) & (RING_PART - 1)] = True
    assert not named.all(axis=1).any()  # every row leaves blocks out
    bad = ~np.repeat(named, ROWS_BLOCK, axis=1)[None, :, :, None]
    bad = jnp.asarray(bad | (np.arange(2) != 1)[:, None, None, None])
    got, poisoned = (
        decode_attention_rows(q, a, b_, jnp.int32(1), work, block=ROWS_BLOCK,
                              window=window, ring=True, sink=logits,
                              interpret=True)
        for a, b_ in ((k, v), (jnp.where(bad, jnp.nan, k),
                               jnp.where(bad, 3e38, v))))
    np.testing.assert_array_equal(np.asarray(got), np.asarray(poisoned))


#: sha256 of the jaxpr text of ``decode_attention_rows`` with the ring's
#: switches off, as the parent (PR 55) traced it: the dense family, the
#: pattern family's attention layers and the window-and-full family's full
#: layers run this specialisation in nine accepted cells (ISSUE 56: with
#: the switches off the kernel traces to the parent's text).
PARENT_JAXPRS = {
    ("stacked", None): "5cb3a5d07f1a5ea8",
    ("stacked", 64): "6cb2ea901d548d80",
    ("planes", None): "2d49f24746bfcaff",
}


@pytest.mark.parametrize("layout,window", sorted(
    PARENT_JAXPRS, key=lambda k: (k[0], k[1] or 0)))
def test_the_switches_off_trace_to_the_parents_kernel(layout, window):
    import hashlib

    b, h, kh, d = 8, 8, 2, 128
    shape = (3, b, S, kh * d) if layout == "planes" else (3, b, S, kh, d)
    block = rows_block(S, kh)

    def fn(q, k, v, pos, idx):
        return decode_attention_rows(
            q, k, v, idx, decode_rows_worklist(pos, S, block), block=block,
            window=window)

    def arg(shape, dtype=jnp.bfloat16):
        return jax.ShapeDtypeStruct(shape, dtype)

    text = str(jax.make_jaxpr(fn)(
        arg((b, h, d)), arg(shape), arg(shape), arg((b,), jnp.int32),
        arg((), jnp.int32)))
    assert hashlib.sha256(text.encode()).hexdigest()[:16] \
        == PARENT_JAXPRS[layout, window]


def test_rows_refuse_a_sequence_that_does_not_tile():
    q = jnp.zeros((1, 2, 16))
    k = jnp.zeros((1, 1, 100, 1, 16))
    with pytest.raises(ValueError, match="S %"):
        decode_attention_rows(q, k, k, jnp.int32(0),
                              jnp.zeros((3,), jnp.int32),
                              block=ROWS_BLOCK, interpret=True)
