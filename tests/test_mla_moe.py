"""The latent-attention, routed-expert family (``tiny-mla-moe``: sarvam-105b
at a size the CPU runs) against its plain reference, tests/mla_moe_plain.py:
the three serving programs, the two attention forms, the shares of a layer,
routing under imbalance and latent pages in the prefix pool.  The family
through the engine is tests/test_mla_moe_engine.py; its preset, configuration
file, the benchmark's reference and the tiny cell are
tests/test_mla_moe_cell.py.
"""

from __future__ import annotations

from dataclasses import replace

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from p2p_llm_tunnel_tpu.models import mla, moe
from p2p_llm_tunnel_tpu.models.config import get_config
from p2p_llm_tunnel_tpu.models.transformer import (
    _act,
    init_kv_cache,
    init_params,
)
from tests import mla_moe_plain as plain
from tests.mla_moe_tiny import (
    ATOL,
    MAX_SEQ,
    ROWS,
    _chunk_prefill,
    _decode_step,
    _prefill,
    _prompt,
    _whole,
    model,
)


def _logprobs(logits):
    return np.asarray(jax.nn.log_softmax(logits, axis=-1))


def _chunk(cfg, params, cache, prompt, start, slot, view=MAX_SEQ, **kw):
    tail = prompt[start:]
    tok = jnp.zeros((1, 16), jnp.int32).at[0, :len(tail)].set(jnp.array(tail))
    return _chunk_prefill(
        cfg, params, tok, jnp.array([len(tail)]), jnp.array([start]), cache,
        jnp.array([slot]), kv_view=view, **kw)


# ---- the three programs against the plain reference ---------------------------

def test_whole_prompt_prefill_matches_the_reference(model):
    cfg, params = model
    prompt = _prompt(1, 23)
    want = np.asarray(plain.forward_logprobs(cfg, params, prompt))
    tok = jnp.array([prompt + [0] * 9])
    logits, rows, none = _prefill(cfg, params, tok,
                                  jnp.arange(32)[None] < len(prompt))
    assert none is None and rows.shape == (cfg.n_layers, 1, 32, cfg.head_dim)
    np.testing.assert_allclose(_logprobs(logits[0, :23]), want, atol=ATOL)
    # ... and the log-probabilities the echo path returns
    cache = init_kv_cache(cfg, ROWS, MAX_SEQ, jnp.float32)
    _, _, plps = _whole(cfg, params, cache, prompt, 1,
                        return_prompt_logprobs=True)
    np.testing.assert_allclose(
        np.asarray(plps[0, 1:23]), want[np.arange(22), prompt[1:]], atol=ATOL)


@pytest.mark.parametrize("segments", [(0,), (0, 16), (16,), (0, 16, 32)],
                         ids=["one", "two", "after-whole", "three"])
def test_chunked_prefill_and_decode_match_the_reference(model, segments):
    """Chunk prefill in segments of 16 (``after-whole``: the first 16 tokens
    come from a whole-prompt prefill), then four decode steps through the
    cache, absorbed: every log-probability against the reference's forward
    over the whole sequence."""
    cfg, params = model
    n = segments[-1] + 11
    prompt = _prompt(2, n)
    cache = init_kv_cache(cfg, ROWS, MAX_SEQ, jnp.float32)
    if segments[0]:
        _, cache = _whole(cfg, params, cache, prompt[:segments[0]], 1)
    for start in segments:
        last, cache = _chunk(cfg, params, cache, prompt[:start + 16], start, 1)
    seq, got = list(prompt), [_logprobs(last[0])]
    tokens = np.zeros((ROWS,), np.int32)
    positions = np.full((ROWS,), MAX_SEQ, np.int32)  # parked
    for step in range(4):
        seq.append(int(np.argmax(got[-1])))
        tokens[1], positions[1] = seq[-1], len(seq) - 1
        logits, cache = _decode_step(cfg, params, cache, jnp.array(tokens),
                                    jnp.array(positions), kv_view=MAX_SEQ)
        got.append(_logprobs(logits[1]))
    want = np.asarray(plain.forward_logprobs(cfg, params, seq))
    np.testing.assert_allclose(np.stack(got), want[n - 1:], atol=ATOL)


def test_absorbed_equals_decompressed(model):
    """The decode form and the prefill form are the same attention."""
    cfg, params = model
    blk = jax.tree.map(lambda a: a[0], params["blocks"])
    k = jax.random.split(jax.random.PRNGKey(3), 3)
    b, t, s = 2, 3, 20
    q_n = jax.random.normal(k[0], (b, t, cfg.n_heads, cfg.qk_nope_head_dim))
    q_r = jax.random.normal(k[1], (b, t, cfg.n_heads, cfg.qk_rope_head_dim))
    latent = jax.random.normal(k[2], (b, s, cfg.kv_lora_rank))
    k_r = jax.random.normal(jax.random.fold_in(k[2], 1),
                            (b, s, cfg.qk_rope_head_dim))
    mask = jnp.arange(s)[None, None, :] <= (10 + jnp.arange(t))[None, :, None]
    mask = jnp.broadcast_to(mask, (b, t, s))
    with jax.default_matmul_precision("highest"):
        a = mla._attend_absorbed(cfg, blk, q_n, q_r, latent, k_r, mask)
        d = mla._attend_decompressed(cfg, blk, q_n, q_r, latent, k_r, mask)
    np.testing.assert_allclose(np.asarray(a), np.asarray(d), atol=2e-5)


def test_an_int8_plane_reads_like_the_plain_one_and_is_narrower(model):
    cfg, params = model
    prompt = _prompt(4, 27)
    out = {}
    for kv in (None, "int8"):
        cache = init_kv_cache(cfg, ROWS, MAX_SEQ, jnp.float32, quant=kv)
        _, cache = _chunk(cfg, params, cache, prompt[:16], 0, 2)
        last, cache = _chunk(cfg, params, cache, prompt, 16, 2)
        out[kv] = _logprobs(last[0]), cache
    assert set(out["int8"][1]) == {"c", "kr", "c_scale"}
    assert out["int8"][1]["c"].dtype == out["int8"][1]["kr"].dtype == jnp.int8
    assert out["int8"][1]["c_scale"].shape[-1] == 2  # latent, rope key
    # (a token near a tie routes elsewhere on the rounding: the mean)
    assert np.abs(out["int8"][0] - out[None][0]).mean() < 0.5
    with pytest.raises(ValueError, match="int4"):
        init_kv_cache(cfg, ROWS, MAX_SEQ, jnp.float32, quant="int4")


# ---- the routed layer -----------------------------------------------------------

def _layer(params, i=0):
    return jax.tree.map(lambda a: a[i], params["blocks"])


def test_the_shares_add_up():
    """The held-experts parts of both shares, the shared expert counted
    once, equal the uncut reference layer."""
    whole = get_config("tiny-mla-moe")
    h = jax.random.normal(jax.random.PRNGKey(5), (2, 9, whole.dim))
    w = _layer(init_params(whole, jax.random.PRNGKey(11), jnp.float32))
    want = plain.routed_layer(whole, w, h.reshape(-1, whole.dim))
    shared = plain.routed_layer(whole, w, h.reshape(-1, whole.dim),
                                experts=[], shared=True)
    total = -shared  # each share adds the shared expert: counted once
    seen = 0
    for chip in range(2):
        cfg = replace(get_config("tiny-mla-moe-ep2s"), chip_index=chip)
        part = init_params(cfg, jax.random.PRNGKey(11), jnp.float32)
        # a share's experts are the whole model's
        lo, held = cfg.experts_held
        np.testing.assert_array_equal(
            np.asarray(part["blocks"]["moe_up"][0]),
            np.asarray(w["moe_up"][lo:lo + held]))
        with jax.default_matmul_precision("highest"):
            out, stats = moe.moe_mlp(cfg, _layer(part), h,
                                     lambda x: _act(cfg, x))
        total = total + out.reshape(-1, whole.dim)
        seen += int(stats[1])
        assert int(stats[0]) == 18 * cfg.n_experts_per_tok
    assert seen == 18 * whole.n_experts_per_tok  # every assignment held once
    np.testing.assert_allclose(np.asarray(total), np.asarray(want), atol=2e-5)


@pytest.mark.parametrize("name", ["tiny-mla-moe", "tiny-mla-moe-ep2s",
                                  "tiny-moe"])
def test_routing_under_a_forced_imbalance_drops_nothing(name):
    """Every token to one expert: the grouped product has room for all."""
    cfg = get_config(name)
    params = init_params(cfg, jax.random.PRNGKey(2), jnp.float32)
    w = dict(_layer(params))
    e, n = cfg.n_experts, 24
    favourite = 1
    if cfg.router_bias:
        w["router_bias"] = jnp.full((e,), -50.0).at[favourite].set(50.0)
    else:
        w["router"] = jnp.zeros_like(w["router"]).at[:, favourite].set(1.0)
    h = jnp.abs(jax.random.normal(jax.random.PRNGKey(6), (3, 8, cfg.dim)))
    with jax.default_matmul_precision("highest"):
        out, stats = moe.moe_mlp(cfg, w, h, lambda x: _act(cfg, x))
    assert int(stats[2]) == n  # the fullest held expert took every token
    x = h.reshape(n, cfg.dim)
    if name == "tiny-moe":
        top_i, top_w = moe.route(cfg, w, x)
        want = sum(
            (top_w[:, j:j + 1] * jnp.stack([
                plain._swiglu(x[i], w["moe_gate"][top_i[i, j]],
                              w["moe_up"][top_i[i, j]],
                              w["moe_down"][top_i[i, j]])
                for i in range(n)]))
            for j in range(cfg.n_experts_per_tok))
    else:
        want = plain.routed_layer(cfg, w, x,
                                  first_held=cfg.experts_held[0])
    np.testing.assert_allclose(np.asarray(out.reshape(n, -1)),
                               np.asarray(want), atol=5e-5)


# ---- the prefix pool's latent pages ---------------------------------------------

def test_a_latent_page_survives_pool_slot_pool(model):
    from p2p_llm_tunnel_tpu.engine.prefix_cache import (
        init_pool,
        make_batch_copy_ops,
        pad_rows,
        pool_packed_keys,
    )

    cfg, params = model
    block = 16
    cache = init_kv_cache(cfg, ROWS, MAX_SEQ, jnp.float32)
    _, cache = _whole(cfg, params, cache, _prompt(7, 48), 0)
    pool = init_pool(cache, block, 8)
    # a page is 16 tokens of both planes: the latent of every layer, and the
    # rope keys of every pair of layers
    assert {k: v.shape for k, v in pool.items()} == {
        "c": (cfg.n_layers, 8, block, cfg.kv_lora_rank),
        "kr": (cfg.n_layers // 2, 8, block, 2 * cfg.qk_rope_head_dim)}
    assert pool_packed_keys(cache) == frozenset()
    copy_in, copy_out = make_batch_copy_ops(block, MAX_SEQ // block, 2)
    wrote = {k: np.asarray(v[:, 0, :48]) for k, v in cache.items()}
    assert all(np.abs(w).min(axis=-1).max() > 0 for w in wrote.values())
    pool = copy_out(pool, cache, *pad_rows([(0, [5, 2, 7], [0, 1, 2])], 2,
                                           MAX_SEQ // block, 0))
    cache = copy_in(cache, pool, *pad_rows([(2, [5, 2, 7], [0, 1, 2])], 2,
                                           MAX_SEQ // block, None))
    again = init_pool(cache, block, 8)
    again = copy_out(again, cache, *pad_rows([(2, [1, 3, 4], [0, 1, 2])], 2,
                                             MAX_SEQ // block, 0))
    for key, w in wrote.items():
        np.testing.assert_array_equal(np.asarray(cache[key][:, 2, :48]), w)
        np.testing.assert_array_equal(
            np.asarray(again[key][:, [1, 3, 4]]).reshape(w.shape), w)
    # a chunk over the copied history reads what the first slot's would
    prompt = _prompt(7, 48) + _prompt(8, 9)
    a, _ = _chunk(cfg, params, cache, prompt, 48, 0)
    b, _ = _chunk(cfg, params, cache, prompt, 48, 2)
    np.testing.assert_array_equal(np.asarray(a), np.asarray(b))
