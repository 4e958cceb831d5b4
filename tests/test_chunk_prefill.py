"""chunk_prefill_into_cache with the cache outside the layer scan's carry
(ISSUE 26): a 7:1-GQA tiny configuration with 4 KV heads (qwen2-7b's
attention shape), every cache form, against ``prefill_into_cache`` and
``decode_step``.

These belong beside the oracles of tests/test_prefix_cache.py; that file is
``slow`` as a whole, which tier-1 deselects, and this guard has to run in
tier-1.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from p2p_llm_tunnel_tpu.models.config import get_config
from p2p_llm_tunnel_tpu.models.quant import unpack_int4
from p2p_llm_tunnel_tpu.models.transformer import (
    chunk_prefill_into_cache,
    decode_step,
    init_kv_cache,
    init_params,
    prefill_into_cache,
)

KV_FORMS = [None, "int8", "int4"]
ROWS, MAX_SEQ, VIEW, T = 4, 64, 32, 16
PARK = ROWS - 1  # the engine's scratch row: the last one
# Largest |difference| from the whole-prompt oracle, of last-token logits and
# of cached values in every layer.  An unquantised cache holds what the oracle
# attends to; a quantised one makes the tail attend to the rounded history,
# the oracle to the exact one.
ATOL = {None: 2e-4, "int8": 0.15, "int4": 1.0}


@pytest.fixture(scope="module")
def model():
    cfg = get_config("tiny-qwen", n_heads=28, n_kv_heads=4, head_dim=8)
    return cfg, init_params(cfg, jax.random.PRNGKey(7), jnp.float32)


def _prompt(seed, n):
    return list(np.random.RandomState(seed).randint(1, 200, size=n))


def _whole(cfg, params, cache, prompt, slot, width=MAX_SEQ):
    tok = jnp.zeros((1, width), jnp.int32).at[0, :len(prompt)].set(
        jnp.array(prompt))
    return prefill_into_cache(
        cfg, params, tok, jnp.array([len(prompt)]), cache, jnp.array([slot]))


def _stored(cache, kv, key):
    """The plane ``key`` as one stored value a token position (a packed
    plane unpacked), and its scales (1.0 for an unquantised cache)."""
    plane = cache[key]
    if kv == "int4":
        plane = unpack_int4(plane, axis=2)
    if kv is None:
        return np.asarray(plane), np.ones(plane.shape[:-1], np.float32)
    return np.asarray(plane, np.int32), np.asarray(cache[key + "_scale"])


def _assert_same_writes(got, ref, kv, key, where=()):
    """Two programs wrote the same keys or values at ``where``.  Their float
    sums differ in the last place, so a scale may differ there and, rarely,
    a rounded value lands one quantisation step away."""
    (gq, gs), (rq, rs) = _stored(got, kv, key), _stored(ref, kv, key)
    gq, gs, rq, rs = gq[where], gs[where], rq[where], rs[where]
    np.testing.assert_allclose(gs, rs, rtol=1e-5, atol=0)
    if kv is None:
        np.testing.assert_allclose(gq, rq, atol=2e-5, rtol=0)
        return
    assert np.abs(gq - rq).max() <= 1
    assert (gq != rq).mean() < 0.02


def _values(cache, kv, key):
    q, scale = _stored(cache, kv, key)
    return q * scale[..., None]


# (history already in the cache, prompt length, rows on the parking slot)
CASES = {
    "start0": (0, 12, 0),
    "mid_history": (16, 28, 0),
    # positions 24..39 are dispatched, 24..29 are real, 32..39 fall past the
    # view: dropped from what the tail attends to, still written to the row
    "tail_past_view": (24, 30, 0),
    "padding_rows": (16, 28, 3),
}


@pytest.mark.parametrize("case", list(CASES))
@pytest.mark.parametrize("kv", KV_FORMS)
def test_chunk_prefill_gqa4_matches_whole_prefill(model, kv, case):
    cfg, params = model
    hist, n, parked = CASES[case]
    prompt = _prompt(3, n)

    want_last, want = _whole(
        cfg, params, init_kv_cache(cfg, ROWS, MAX_SEQ, jnp.float32, quant=kv),
        prompt, slot=1)

    cache = init_kv_cache(cfg, ROWS, MAX_SEQ, jnp.float32, quant=kv)
    # a neighbour the dispatch must leave alone
    _, cache = _whole(cfg, params, cache, _prompt(4, 20), slot=0)
    if hist:
        _, cache = _whole(cfg, params, cache, prompt[:hist], slot=1, width=hist)
    before = jax.tree.map(np.asarray, cache)

    b = 1 + parked
    tokens = np.zeros((b, T), np.int32)
    tokens[0, :n - hist] = prompt[hist:]
    lengths = np.array([n - hist] + [1] * parked, np.int32)
    starts = np.array([hist] + [0] * parked, np.int32)
    slots = np.array([1] + [PARK] * parked, np.int32)
    last, cache = jax.jit(
        lambda c: chunk_prefill_into_cache(
            cfg, params, jnp.asarray(tokens), jnp.asarray(lengths),
            jnp.asarray(starts), c, jnp.asarray(slots), kv_view=VIEW)
    )(cache)

    np.testing.assert_allclose(
        np.asarray(last[0]), np.asarray(want_last[0]),
        atol=ATOL[kv], rtol=0)
    for key in ("k", "v"):
        got, ref = _values(cache, kv, key), _values(want, kv, key)
        # Layer 0's keys and values depend on the token and its position
        # alone: every real position holds what the oracle wrote there.
        _assert_same_writes(cache, want, kv, key, (0, 1, slice(0, n)))
        np.testing.assert_allclose(
            got[:, 1, :n], ref[:, 1, :n], atol=ATOL[kv], rtol=0)
    for key, plane in cache.items():
        # the neighbour's row, and the row nobody named, are as they were
        for row in (0, 2):
            np.testing.assert_array_equal(
                np.asarray(plane[:, row]), before[key][:, row])
        if not parked:
            np.testing.assert_array_equal(
                np.asarray(plane[:, PARK]), before[key][:, PARK])


@pytest.mark.parametrize("kv", KV_FORMS)
def test_chunk_prefill_of_one_token_is_a_decode_step(model, kv):
    """T=1 over every row at arbitrary positions, the spec-verify fallback's
    form (``unaligned_int4``: a packed byte's other nibble survives)."""
    cfg, params = model
    cache = init_kv_cache(cfg, ROWS, MAX_SEQ, jnp.float32, quant=kv)
    positions = [20, 9, 1, 14]
    for slot, n in enumerate(positions):
        _, cache = _whole(cfg, params, cache, _prompt(10 + slot, n), slot)
    tokens = jnp.array([5, 17, 101, 42], jnp.int32)
    pos = jnp.array(positions, jnp.int32)

    want, want_cache = decode_step(
        cfg, params, cache, tokens, pos, kv_view=VIEW)
    got, got_cache = chunk_prefill_into_cache(
        cfg, params, tokens[:, None], jnp.ones((ROWS,), jnp.int32), pos,
        cache, jnp.arange(ROWS), kv_view=VIEW, unaligned_int4=True)

    np.testing.assert_allclose(
        np.asarray(got), np.asarray(want), atol=2e-4, rtol=0)
    for key in ("k", "v"):
        _assert_same_writes(got_cache, want_cache, kv, key)


def _scans(jaxpr):
    for eqn in jaxpr.eqns:
        if eqn.primitive.name == "scan":
            yield eqn
        for sub in jax.core.jaxprs_in_params(eqn.params):
            yield from _scans(sub)


@pytest.mark.parametrize("kv", KV_FORMS)
def test_layer_scan_carries_no_cache_plane(model, kv):
    """The cache is an operand the layer scan reads, never a value it hands
    from layer to layer: carried, the TPU compiler converts a 4-KV-head
    plane's layout there and back in every layer (ISSUE 26;
    tests/test_tpu_compile.py holds the compiled program to it)."""
    cfg, params = model
    cache = init_kv_cache(cfg, ROWS, MAX_SEQ, jnp.float32, quant=kv)
    row = jnp.zeros((2,), jnp.int32)
    jaxpr = jax.make_jaxpr(
        lambda c: chunk_prefill_into_cache(
            cfg, params, jnp.zeros((2, T), jnp.int32), row + T, row, c,
            row.at[1].set(1), kv_view=VIEW)
    )(cache)
    (scan,) = list(_scans(jaxpr.jaxpr))
    n_consts, n_carry = scan.params["num_consts"], scan.params["num_carry"]
    shapes = {leaf.shape for leaf in jax.tree.leaves(cache)}
    consts = [v.aval.shape for v in scan.invars[:n_consts]]
    carried = [v.aval.shape for v in scan.invars[n_consts:n_consts + n_carry]]
    assert not shapes & set(carried)
    assert shapes <= set(consts)  # read inside the loop, as an invariant
    # the tail leaves the scan stacked over layers, [L, Bp, T, K, D]
    assert (cfg.n_layers, 2, T, cfg.n_kv_heads, cfg.head_dim) in [
        v.aval.shape for v in scan.outvars[n_carry:]]
