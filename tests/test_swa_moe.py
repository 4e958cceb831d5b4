"""The family that mixes window and full attention layers over routed experts
(``tiny-swa-moe``: MiMo-V2-Flash at a size the CPU runs, rings of 16
positions) against its plain reference, tests/swa_moe_plain.py: the three
serving programs through rings that wrap, the attention op, the shares of a
layer and the prefix pool's pages of two kinds of leaf.  The family through
the engine is tests/test_swa_moe_engine.py; its preset, configuration file,
the benchmark's reference and the tiny cell are tests/test_swa_moe_cell.py.
"""

from __future__ import annotations

from dataclasses import replace

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from p2p_llm_tunnel_tpu.models import moe, swa
from p2p_llm_tunnel_tpu.models.config import get_config
from p2p_llm_tunnel_tpu.models.transformer import (
    _act,
    chunk_prefill_into_cache,
    decode_step,
    init_kv_cache,
    init_params,
    prefill,
    prefill_into_cache,
)
from p2p_llm_tunnel_tpu.ops.attention import (
    masked_attention,
    ring_positions,
    window_mask,
)
from tests import swa_moe_plain as plain
from tests.swa_moe_tiny import ATOL, MAX_SEQ, RING, ROWS, _prompt


@pytest.fixture(scope="module", params=["tiny-swa-moe", "tiny-swa-moe-ep2s"])
def model(request):
    cfg = get_config(request.param)
    return cfg, init_params(cfg, jax.random.PRNGKey(11), jnp.float32)


def _logprobs(logits):
    return np.asarray(jax.nn.log_softmax(logits, axis=-1))


# (one trace a shape: an eager scan is traced anew at every call)
_prefill_into_cache = jax.jit(prefill_into_cache, static_argnums=(0,))
_chunk_prefill = jax.jit(chunk_prefill_into_cache, static_argnums=(0,),
                         static_argnames=("kv_view", "return_all_logits"))
_decode_step = jax.jit(decode_step, static_argnums=(0,),
                       static_argnames=("kv_view",))


def _whole(cfg, params, cache, prompt, slot):
    width = 16 * -(-len(prompt) // 16)
    tok = jnp.zeros((1, width), jnp.int32).at[0, :len(prompt)].set(
        jnp.array(prompt))
    return _prefill_into_cache(cfg, params, tok, jnp.array([len(prompt)]),
                               cache, jnp.array([slot]))


def _chunk(cfg, params, cache, prompt, start, end, slot, width=16,
           view=MAX_SEQ):
    """Positions [start, end) of ``prompt`` as one padded segment of
    ``width``, beside a padding row on the scratch slot."""
    n = end - start
    tok = jnp.zeros((2, width), jnp.int32).at[0, :n].set(
        jnp.array(prompt[start:end]))
    return _chunk_prefill(
        cfg, params, tok, jnp.array([n, 1]), jnp.array([start, 0]), cache,
        jnp.array([slot, ROWS - 1]), kv_view=view, return_all_logits=True)


def _decode(cfg, params, cache, slot, token, position, view=MAX_SEQ):
    tokens = jnp.zeros((ROWS,), jnp.int32).at[slot].set(token)
    positions = jnp.full((ROWS,), MAX_SEQ).at[slot].set(position)
    logits, cache = _decode_step(cfg, params, cache, tokens, positions,
                                 kv_view=view)
    return logits[slot], cache


def test_the_presets_cache_is_rings_beside_full_planes(model):
    cfg, _ = model
    cache = init_kv_cache(cfg, ROWS, MAX_SEQ, jnp.float32)
    assert cfg.attn_kinds == ("full", "window", "window", "window", "window",
                              "full", "window")
    assert cfg.layer_kinds == ("dense",) + ("moe",) * 6
    assert {k: v.shape for k, v in cache.items()} == {
        "k": (2, ROWS, MAX_SEQ, 1 * 24), "v": (2, ROWS, MAX_SEQ, 1 * 16),
        "wk": (5, ROWS, RING, 2 * 24), "wv": (5, ROWS, RING, 2 * 16)}
    assert [(r.attn, r.ffn, r.attn_first, r.ffn_first, r.n)
            for r in swa.layer_runs(cfg)] == [
        ("full", "dense", 0, 0, 1), ("window", "moe", 0, 0, 4),
        ("full", "moe", 1, 4, 1), ("window", "moe", 4, 5, 1)]


def test_whole_prompt_prefill_matches_the_reference(model):
    cfg, params = model
    prompt = _prompt(3, 43)
    want = np.asarray(plain.forward_logprobs(cfg, params, prompt))
    tok = jnp.array([prompt + [0] * 5])
    valid = jnp.arange(48)[None, :] < 43
    logits, rows, _ = prefill(cfg, params, tok, valid)
    np.testing.assert_allclose(_logprobs(logits[0, :43]), want, atol=ATOL)
    assert rows["full"][0].shape == (2, 1, 48, 24)
    assert rows["window"][1].shape == (5, 1, 48, 32)
    # into the cache: a full layer keeps every position, a ring the last 16
    cache = init_kv_cache(cfg, ROWS, MAX_SEQ, jnp.float32)
    last, cache = _whole(cfg, params, cache, prompt, 1)
    np.testing.assert_allclose(_logprobs(last[0]), want[42], atol=ATOL)
    np.testing.assert_array_equal(np.asarray(cache["k"][:, 1, :43]),
                                  np.asarray(rows["full"][0][:, 0, :43]))
    for p in range(43):
        held = np.asarray(cache["wk"][:, 1, p % RING])
        fresh = np.asarray(rows["window"][0][:, 0, p])
        assert (held == fresh).all() == (p >= 43 - RING), p


@pytest.mark.parametrize("cuts", [
    (0, 16, 32, 48), (0, 12, 24, 36, 48), (0, 7, 23, 37, 41), (0, 16, 19)],
    ids=["aligned", "seams-inside-segments", "ragged", "short-tail"])
def test_chunked_prefill_and_decode_through_the_rings(model, cuts):
    """Segments of at most 16 tokens against rings of 16: every segment
    after the first reads its window out of a ring that has wrapped or will
    within the segment (a seam inside the tail), and 30 decode steps wrap
    the rings twice more.  Log-probabilities of every position against the
    plain reference, which has no cache."""
    cfg, params = model
    n = cuts[-1]
    seq = _prompt(5, n + 30)
    want = np.asarray(plain.forward_logprobs(cfg, params, seq))
    cache = init_kv_cache(cfg, ROWS, MAX_SEQ, jnp.float32)
    for start, end in zip(cuts, cuts[1:]):
        logits, cache = _chunk(cfg, params, cache, seq, start, end, 2)
        np.testing.assert_allclose(_logprobs(logits[0, :end - start]),
                                   want[start:end], atol=ATOL)
    for p in range(n, n + 30):
        logits, cache = _decode(cfg, params, cache, 2, seq[p], p)
        np.testing.assert_allclose(_logprobs(logits), want[p], atol=ATOL)
    # the scratch row's padding and the parked rows left slot 0 untouched
    assert not np.asarray(cache["wk"][:, 0]).any()


def test_a_segment_wider_than_a_ring_keeps_its_last_positions(model):
    """A tail of 40 real tokens into rings of 16: attention inside the
    segment needs no ring, and the ring is left holding the last 16."""
    cfg, params = model
    seq = _prompt(6, 60)
    want = np.asarray(plain.forward_logprobs(cfg, params, seq))
    cache = init_kv_cache(cfg, ROWS, MAX_SEQ, jnp.float32)
    logits, cache = _chunk(cfg, params, cache, seq, 0, 40, 1, width=48)
    np.testing.assert_allclose(_logprobs(logits[0, :40]), want[:40],
                               atol=ATOL)
    for p in range(40, 60):
        logits, cache = _decode(cfg, params, cache, 1, seq[p], p)
        np.testing.assert_allclose(_logprobs(logits), want[p], atol=ATOL)


def test_an_int8_plane_reads_like_the_plain_one_and_is_narrower(model):
    cfg, params = model
    seq = _prompt(8, 50)
    want = np.asarray(plain.forward_logprobs(cfg, params, seq))
    cache = init_kv_cache(cfg, ROWS, MAX_SEQ, jnp.float32, quant="int8")
    assert {k: (v.shape[2:], str(v.dtype)) for k, v in cache.items()} == {
        "k": ((MAX_SEQ, 24), "int8"), "k_scale": ((MAX_SEQ, 1), "float32"),
        "v": ((MAX_SEQ, 16), "int8"), "v_scale": ((MAX_SEQ, 1), "float32"),
        "wk": ((RING, 48), "int8"), "wk_scale": ((RING, 2), "float32"),
        "wv": ((RING, 32), "int8"), "wv_scale": ((RING, 2), "float32")}
    assert swa.RING_KEYS == {"wk", "wv", "wk_scale", "wv_scale"}
    got = []
    for start in (0, 16, 32):
        logits, cache = _chunk(cfg, params, cache, seq, start, start + 16, 0)
        got.append(_logprobs(logits[0]))
    for p in range(48, 50):
        logits, cache = _decode(cfg, params, cache, 0, seq[p], p)
        got.append(_logprobs(logits)[None])
    err = np.abs(np.concatenate(got) - want).mean()
    # (a model this narrow routes a token elsewhere on a rounding)
    assert 1e-5 < err < 0.3, err  # rounded, and only that
    with pytest.raises(ValueError, match="no KV quant mode 'int4'"):
        init_kv_cache(cfg, ROWS, MAX_SEQ, quant="int4")


# ---- the attention op ------------------------------------------------------------

def test_the_sink_joins_the_denominator_and_carries_no_value():
    key = jax.random.PRNGKey(0)
    q = jax.random.normal(key, (1, 3, 4, 6))
    k = jax.random.normal(jax.random.fold_in(key, 1), (1, 5, 2, 6))
    v = jax.random.normal(jax.random.fold_in(key, 2), (1, 5, 2, 3))
    sink = jnp.array([0.5, -1.0, 2.0, 0.0])
    pos = jnp.array([[2, 3, 4]])
    mask = window_mask(pos, jnp.arange(5)[None, :], window=3)
    assert mask[0].tolist() == [[True, True, True, False, False],
                                [False, True, True, True, False],
                                [False, False, True, True, True]]
    got = masked_attention(q, k, v, mask, 0.4, sink=sink)
    assert got.shape == (1, 3, 4, 3)  # values narrower than keys
    kk, vv = jnp.repeat(k, 2, 2), jnp.repeat(v, 2, 2)
    s = jnp.einsum("bthd,bshd->bhts", q, kk) * 0.4
    e = jnp.where(mask[:, None], jnp.exp(s), 0.0)
    p = e / (e.sum(-1, keepdims=True) + jnp.exp(sink)[None, :, None, None])
    want = jnp.einsum("bhts,bshd->bthd", p, vv)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want), atol=1e-6)
    assert float(p.sum(-1).max()) < 1.0  # a head gives its keys less than all
    plainly = masked_attention(q, k, v, mask, 0.4)
    assert float(jnp.abs(plainly - got).max()) > 1e-2


def test_a_ring_slot_is_named_by_the_position_it_holds():
    held = ring_positions(jnp.array([0, 5, 16, 37]), 16)
    assert held[0].tolist() == [0] + [-16 + r for r in range(1, 16)]
    assert held[1].tolist()[:6] == [0, 1, 2, 3, 4, 5] and held[1, 6] == -10
    assert held[2].tolist() == [16] + list(range(1, 16))
    assert sorted(held[3].tolist()) == list(range(22, 38))
    # queries blocked a few at a time give what one block gives
    from p2p_llm_tunnel_tpu.ops import attention as A

    key = jax.random.PRNGKey(3)
    q = jax.random.normal(key, (2, 64, 4, 8))
    k = jax.random.normal(jax.random.fold_in(key, 1), (2, 64, 2, 8))
    v = jax.random.normal(jax.random.fold_in(key, 2), (2, 64, 2, 4))
    pos = jnp.broadcast_to(jnp.arange(64), (2, 64))
    mask = window_mask(pos, pos, 9)
    whole = masked_attention(q, k, v, mask, 0.3)
    old, A._SCORE_BLOCK_BYTES = A._SCORE_BLOCK_BYTES, 2 * 4 * 64 * 4 * 16
    try:
        blocked = masked_attention(q, k, v, mask, 0.3)
    finally:
        A._SCORE_BLOCK_BYTES = old
    np.testing.assert_allclose(np.asarray(blocked), np.asarray(whole),
                               atol=1e-6)


# ---- the shares ------------------------------------------------------------------

def _layer(params, i=0):
    return jax.tree.map(lambda a: a[i], params["blocks"])


def test_the_shares_add_up():
    """The held-experts parts of both shares equal the uncut reference
    layer (no shared expert in this family: nothing is counted twice)."""
    whole = get_config("tiny-swa-moe")
    h = jax.random.normal(jax.random.PRNGKey(5), (2, 9, whole.dim))
    w = _layer(init_params(whole, jax.random.PRNGKey(11), jnp.float32))
    want = plain.routed_layer(whole, w, h.reshape(-1, whole.dim))
    total, seen = 0.0, 0
    for chip in range(2):
        cfg = replace(get_config("tiny-swa-moe-ep2s"), chip_index=chip)
        part = init_params(cfg, jax.random.PRNGKey(11), jnp.float32)
        lo, held = cfg.experts_held
        np.testing.assert_array_equal(
            np.asarray(part["blocks"]["moe_up"][0]),
            np.asarray(w["moe_up"][lo:lo + held]))
        # attention is held whole on every chip
        np.testing.assert_array_equal(
            np.asarray(part["attn_window"]["wk"]),
            np.asarray(init_params(whole, jax.random.PRNGKey(11),
                                   jnp.float32)["attn_window"]["wk"]))
        with jax.default_matmul_precision("highest"):
            out, stats = moe.moe_mlp(cfg, _layer(part), h,
                                     lambda x: _act(cfg, x))
        total = total + out.reshape(-1, whole.dim)
        seen += int(stats[1])
        assert int(stats[0]) == 18 * cfg.n_experts_per_tok
    assert seen == 18 * whole.n_experts_per_tok  # every assignment held once
    np.testing.assert_allclose(np.asarray(total), np.asarray(want), atol=2e-5)


# ---- the prefix pool: a page with two kinds of leaves ------------------------------

def test_a_page_of_two_kinds_is_restored_into_a_ring(model):
    """Saved segment by segment while the rings hold each block, a prefix
    of 48 tokens is restored into another slot: the full layers whole, the
    window layers' last 16 positions into the ring, and a tail and decode
    steps over the restored slot read what the plain reference gives."""
    from p2p_llm_tunnel_tpu.engine.prefix_cache import (
        init_pool,
        make_batch_copy_ops,
        pad_rows,
    )

    cfg, params = model
    block, nmax = 16, MAX_SEQ // 16
    seq = _prompt(7, 48) + _prompt(8, 30)
    want = np.asarray(plain.forward_logprobs(cfg, params, seq))
    cache = init_kv_cache(cfg, ROWS, MAX_SEQ, jnp.float32)
    pool = init_pool(cache, block, 8)
    # a pooled token holds its keys and values of every layer, window too
    assert {k: v.shape for k, v in pool.items()} == {
        "k": (2, 8, block, 24), "v": (2, 8, block, 16),
        "wk": (5, 8, block, 48), "wv": (5, 8, block, 32)}
    copy_in, copy_out = make_batch_copy_ops(
        block, nmax, 2, layerwise_keys=frozenset(cache),
        ring_keys=swa.RING_KEYS)
    ids = [5, 2, 7]
    for i in range(3):  # each block saved while the ring holds it
        _, cache = _chunk(cfg, params, cache, seq, 16 * i, 16 * i + 16, 0)
        pool = copy_out(pool, cache, *pad_rows([(0, [ids[i]], [i])], 2,
                                               nmax, 0))
    # slot 2 held another sequence's ring before the hit
    _, cache = _chunk(cfg, params, cache, _prompt(9, 16), 0, 16, 2)
    cache = copy_in(cache, pool, *pad_rows([(2, ids, [0, 1, 2])], 2, nmax,
                                           None))
    np.testing.assert_array_equal(np.asarray(cache["k"][:, 2, :48]),
                                  np.asarray(cache["k"][:, 0, :48]))
    # the ring holds positions 32..47 (block 2), where slot 0's does
    np.testing.assert_array_equal(np.asarray(cache["wk"][:, 2]),
                                  np.asarray(cache["wk"][:, 0]))
    np.testing.assert_array_equal(np.asarray(cache["wv"][:, 2]),
                                  np.asarray(pool["wv"][:, 7]))
    logits, cache = _chunk(cfg, params, cache, seq, 48, 57, 2)
    np.testing.assert_allclose(_logprobs(logits[0, :9]), want[48:57],
                               atol=ATOL)
    for p in range(57, 78):
        logits, cache = _decode(cfg, params, cache, 2, seq[p], p)
        np.testing.assert_allclose(_logprobs(logits), want[p], atol=ATOL)
