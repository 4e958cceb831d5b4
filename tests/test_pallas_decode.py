"""Pallas decode-attention kernel vs the einsum oracle.

Mirrors tests/test_pallas_attention.py's strategy for the prefill kernel:
interpret mode on CPU, cached_attention (ops/attention.py) as ground truth,
sweeping GQA grouping, positions, sliding windows, and softcap.
"""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from p2p_llm_tunnel_tpu.ops.attention import cached_attention
from p2p_llm_tunnel_tpu.ops.pallas_decode_attention import (
    flash_decode_attention,
    flash_decode_attention_sgrid,
)

# Compile-heavy (JAX jit of engine/model programs): excluded from
# `make test-fast` (VERDICT r4 item 8).
pytestmark = pytest.mark.slow


def _mk(b, s, h, kh, d, seed=0):
    ks = jax.random.split(jax.random.PRNGKey(seed), 3)
    q = jax.random.normal(ks[0], (b, 1, h, d), jnp.float32)
    k = jax.random.normal(ks[1], (b, s, kh, d), jnp.float32)
    v = jax.random.normal(ks[2], (b, s, kh, d), jnp.float32)
    return q, k, v


@pytest.mark.parametrize("h,kh", [(4, 4), (8, 2), (4, 1)])
def test_matches_einsum_oracle(h, kh):
    b, s, d = 3, 256, 32
    q, k, v = _mk(b, s, h, kh, d)
    pos = jnp.array([0, 100, 255], jnp.int32)
    want = cached_attention(q, k, v, pos)
    got = flash_decode_attention(q, k, v, pos, interpret=True)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                               rtol=2e-5, atol=2e-5)


def test_traced_window_scalar():
    """gemma-2 passes the window as a traced scalar from inside lax.scan."""
    b, s, h, kh, d = 1, 128, 2, 1, 16
    q, k, v = _mk(b, s, h, kh, d, seed=4)
    pos = jnp.array([100], jnp.int32)

    def f(win):
        return flash_decode_attention(q, k, v, pos, window=win,
                                      interpret=True)

    got = jax.jit(f)(jnp.asarray(32))
    want = cached_attention(q, k, v, pos, window=32)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                               rtol=2e-5, atol=2e-5)


def test_rejects_untileable_seq():
    q, k, v = _mk(1, 100, 2, 1, 16)
    with pytest.raises(ValueError, match="S %"):
        flash_decode_attention(q, k, v, jnp.array([0]), interpret=True)


@pytest.mark.parametrize("h,kh", [(4, 4), (8, 2), (4, 1)])
def test_sgrid_matches_einsum_oracle(h, kh):
    b, s, d = 3, 512, 32
    q, k, v = _mk(b, s, h, kh, d)
    pos = jnp.array([0, 100, 511], jnp.int32)
    want = cached_attention(q, k, v, pos)
    got = flash_decode_attention_sgrid(q, k, v, pos, interpret=True)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                               rtol=2e-5, atol=2e-5)


@pytest.mark.parametrize("s,seed,pos,kws", [
    # s < BLOCK_S: single-block grid
    (128, 2, [5, 127], (dict(window=32), dict(softcap=20.0), dict())),
    (256, 2, [180, 255], (dict(window=16), dict(window=64))),
    (128, 3, [64, 127], (dict(scale=0.25, softcap=30.0),)),
], ids=["small_view", "sliding_window", "softcap_and_scale"])
def test_sgrid_window_softcap_and_small_view(s, seed, pos, kws):
    b, h, kh, d = 2, 4, 2, 16
    q, k, v = _mk(b, s, h, kh, d, seed=seed)
    pos = jnp.array(pos, jnp.int32)
    for kw in kws:
        want = cached_attention(q, k, v, pos, **kw)
        got = flash_decode_attention(q, k, v, pos, interpret=True, **kw)
        np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                                   rtol=2e-5, atol=2e-5, err_msg=str(kw))


@pytest.mark.parametrize("s,seed,pos,poison_from", [
    (256, 1, [50, 130], 200),
    (512, 3, [50, 300], 301),
], ids=["one_block", "two_blocks"])
def test_sgrid_positions_gate_attendable_prefix(s, seed, pos, poison_from):
    """Frontier pruning must not change results: poison the cache past
    every slot's position (incl. blocks the index-map clamp never fetches)
    and assert identical output."""
    b, h, kh, d = 2, 4, 2, 16
    q, k, v = _mk(b, s, h, kh, d, seed=seed)
    pos = jnp.array(pos, jnp.int32)
    base = flash_decode_attention(q, k, v, pos, interpret=True)
    k2 = k.at[:, poison_from:].set(1e6)
    v2 = v.at[:, poison_from:].set(-1e6)
    poisoned = flash_decode_attention(q, k2, v2, pos, interpret=True)
    np.testing.assert_allclose(np.asarray(base), np.asarray(poisoned))


def test_sgrid_int8_matches_dequant_oracle():
    """int8-KV sgrid kernel vs cached_attention over the dequantized
    cache — the exact arrays the einsum path would read."""
    from p2p_llm_tunnel_tpu.ops.pallas_decode_attention import (
        flash_decode_attention_sgrid_int8,
    )
    from p2p_llm_tunnel_tpu.models.transformer import _quant_kv

    b, s, h, kh, d = 3, 512, 8, 2, 32
    q, k, v = _mk(b, s, h, kh, d, seed=4)
    k8, ks = _quant_kv(k)
    v8, vs = _quant_kv(v)
    kd = k8.astype(jnp.float32) * ks[..., None]
    vd = v8.astype(jnp.float32) * vs[..., None]
    pos = jnp.array([0, 100, 511], jnp.int32)
    for kw in (dict(), dict(window=64), dict(softcap=20.0)):
        want = cached_attention(q, kd, vd, pos, **kw)
        got = flash_decode_attention_sgrid_int8(
            q, k8, v8, ks, vs, pos, interpret=True, **kw
        )
        np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                                   rtol=3e-5, atol=3e-5, err_msg=str(kw))


def test_full_model_decode_int8_sgrid_parity():
    """decode_step: int8 KV + flash_sgrid (interpret) must reproduce the
    int8-KV einsum path through the full tiny model."""
    from dataclasses import replace

    from p2p_llm_tunnel_tpu.models.config import get_config
    from p2p_llm_tunnel_tpu.models.transformer import (
        decode_step, init_kv_cache, init_params, prefill_into_cache,
    )

    cfg = get_config("tiny")
    params = init_params(cfg, jax.random.PRNGKey(0), jnp.float32)
    fcfg = replace(cfg, flash_decode=True, flash_sgrid=True,
                   flash_interpret=True)
    cache = init_kv_cache(cfg, 2, 256, jnp.float32, quant=True)
    toks = jax.random.randint(jax.random.PRNGKey(1), (1, 6), 0,
                              cfg.vocab_size)
    _, cache = prefill_into_cache(
        cfg, params, jnp.pad(toks, ((0, 0), (0, 2))),
        jnp.array([6]), cache, jnp.array([0]),
    )
    cache_f = jax.tree.map(lambda x: x, cache)
    step_tokens = jnp.full((2,), 3, jnp.int32)
    step_pos = jnp.full((2,), 6, jnp.int32)
    ref, _ = decode_step(cfg, params, cache, step_tokens, step_pos,
                         kv_view=128)
    got, _ = decode_step(fcfg, params, cache_f, step_tokens, step_pos,
                         kv_view=128)
    np.testing.assert_allclose(np.asarray(got), np.asarray(ref),
                               rtol=2e-4, atol=2e-4)


def test_full_model_decode_flash_parity():
    """decode_step with flash_decode (interpret) must reproduce the einsum
    path exactly through the full tiny model, including gemma-2 windows."""
    from dataclasses import replace

    from p2p_llm_tunnel_tpu.models.config import get_config
    from p2p_llm_tunnel_tpu.models.transformer import (
        decode_step, init_kv_cache, init_params, prefill_into_cache,
    )

    for preset in ("tiny", "tiny-gemma"):
        for sgrid in (False, True):
            cfg = get_config(preset)
            params = init_params(cfg, jax.random.PRNGKey(0), jnp.float32)
            fcfg = replace(cfg, flash_decode=True, flash_interpret=True,
                           flash_sgrid=sgrid)
            cache = init_kv_cache(cfg, 2, 256, jnp.float32)
            toks = jax.random.randint(jax.random.PRNGKey(1), (1, 6), 0,
                                      cfg.vocab_size)
            _, cache = prefill_into_cache(
                cfg, params, jnp.pad(toks, ((0, 0), (0, 2))),
                jnp.array([6]), cache, jnp.array([0]),
            )
            cache_f = jax.tree.map(lambda x: x, cache)
            step_tokens = jnp.full((2,), 3, jnp.int32)
            step_pos = jnp.full((2,), 6, jnp.int32)
            ref, _ = decode_step(cfg, params, cache, step_tokens, step_pos,
                                 kv_view=128)
            got, _ = decode_step(fcfg, params, cache_f, step_tokens,
                                 step_pos, kv_view=128)
            np.testing.assert_allclose(
                np.asarray(got), np.asarray(ref), rtol=2e-4, atol=2e-4,
                err_msg=f"flash decode diverges on {preset} sgrid={sgrid}",
            )


def test_public_entry_routes_to_sgrid():
    """ISSUE 4 satellite: ``flash_decode_attention`` is the s-grid kernel:
    bit-identical output to calling the s-grid entry directly."""
    b, s, h, kh, d = 2, 256, 4, 2, 16
    q, k, v = _mk(b, s, h, kh, d, seed=9)
    pos = jnp.array([7, 200], jnp.int32)
    routed = flash_decode_attention(q, k, v, pos, interpret=True)
    sgrid = flash_decode_attention_sgrid(q, k, v, pos, interpret=True)
    np.testing.assert_array_equal(np.asarray(routed), np.asarray(sgrid))
