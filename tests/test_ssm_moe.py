"""The family of one mixer a layer (``tiny-ssm-moe``: Nemotron-H at a size
the CPU runs: Mamba-2 state-space layers, routed experts of two products,
attention) against its plain reference, tests/ssm_moe_plain.py: the chunked
scan against the token-by-token recurrence, the three serving programs
through state and cache, rows of different lengths in one dispatch, a slot's
second tenant and the shares of a layer.  The family through the engine is
tests/test_ssm_moe_engine.py; its preset, configuration file, the benchmark's
reference and the tiny cell are tests/test_ssm_moe_cell.py.
"""

from __future__ import annotations

from dataclasses import replace

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from p2p_llm_tunnel_tpu.models import moe, ssm, ssm_moe
from p2p_llm_tunnel_tpu.models.config import get_config
from p2p_llm_tunnel_tpu.models.transformer import (
    chunk_prefill_into_cache,
    decode_step,
    init_kv_cache,
    init_params,
    prefill,
    prefill_into_cache,
)
from p2p_llm_tunnel_tpu.ops.pallas_ssm_step import ELEMENTWISE, SSM_STEP_KERNEL
from tests import ssm_moe_plain as plain
from tests.ssm_moe_tiny import ATOL, UPDATES, _prompt


ROWS, MAX_SEQ = 4, 128


@pytest.fixture(scope="module", params=["tiny-ssm-moe", "tiny-ssm-moe-ep2s"])
def model(request):
    cfg = get_config(request.param)
    return cfg, init_params(cfg, jax.random.PRNGKey(11), jnp.float32)


def _logprobs(logits):
    return np.asarray(jax.nn.log_softmax(logits, axis=-1))


_prefill = jax.jit(prefill, static_argnums=(0,))
_prefill_into_cache = jax.jit(prefill_into_cache, static_argnums=(0,))
_chunk_prefill = jax.jit(chunk_prefill_into_cache, static_argnums=(0,),
                         static_argnames=("kv_view", "return_all_logits"))
_decode_step = jax.jit(decode_step, static_argnums=(0,),
                       static_argnames=("kv_view",))


def _chunk(cfg, params, cache, rows, width=16):
    """``rows``: [(prompt, start, end, slot)] as one padded dispatch of
    ``width`` positions; a lone row gets a padding row on the scratch slot
    beside it."""
    if len(rows) == 1:
        rows = rows + [([0], 0, 1, ROWS - 1)]
    tok = jnp.zeros((len(rows), width), jnp.int32)
    for i, (prompt, start, end, _slot) in enumerate(rows):
        tok = tok.at[i, :end - start].set(jnp.array(prompt[start:end]))
    return _chunk_prefill(
        cfg, params, tok, jnp.array([e - s for _p, s, e, _ in rows]),
        jnp.array([s for _p, s, _e, _ in rows]), cache,
        jnp.array([slot for *_x, slot in rows]), kv_view=MAX_SEQ,
        return_all_logits=True)


def _decode(cfg, params, cache, slot, token, position):
    tokens = jnp.zeros((ROWS,), jnp.int32).at[slot].set(token)
    positions = jnp.full((ROWS,), MAX_SEQ).at[slot].set(position)
    logits, cache = _decode_step(cfg, params, cache, tokens, positions,
                                 kv_view=MAX_SEQ)
    return logits[slot], cache


# ---- the mixer --------------------------------------------------------------------

@jax.jit
def _recurrence(x, dt, a, bm, cm, state):
    """``ssm.ssm_step`` a position at a time: the recurrence itself."""
    def step(state, inp):
        y, state = ssm.ssm_step(*inp[:2], a, *inp[2:], state)
        return state, y

    state, ys = jax.lax.scan(
        step, state, tuple(jnp.moveaxis(v, 1, 0) for v in (x, dt, bm, cm)))
    return jnp.moveaxis(ys, 0, 1), state


_scan = jax.jit(ssm.ssm_scan, static_argnums=(6,))


@pytest.mark.parametrize("t,chunk", [(37, 1), (128, 128), (37, 8), (5, 128)],
                         ids=["chunks-of-1", "one-chunk-of-128",
                              "a-length-no-chunk-divides",
                              "shorter-than-a-chunk"])
def test_the_chunked_scan_is_the_recurrence_at_any_chunking(t, chunk):
    rng = np.random.RandomState(t * 1000 + chunk)
    b, h, p, g, n = 2, 4, 8, 2, 16
    x = jnp.asarray(rng.randn(b, t, h, p), jnp.float32)
    dt = jnp.asarray(rng.uniform(0.001, 0.5, (b, t, h)), jnp.float32)
    dt = dt.at[1, t - 3:].set(0.0)  # padding at the end of the second row
    a = -jnp.asarray(rng.uniform(1, 16, h), jnp.float32)
    bm = jnp.asarray(rng.randn(b, t, g, n), jnp.float32)
    cm = jnp.asarray(rng.randn(b, t, g, n), jnp.float32)
    s0 = jnp.asarray(rng.randn(b, h, p, n), jnp.float32)
    want_y, want_s = _recurrence(x, dt, a, bm, cm, s0)
    got_y, got_s = _scan(x, dt, a, bm, cm, s0, chunk)
    np.testing.assert_allclose(got_y, want_y, atol=2e-4, rtol=1e-4)
    np.testing.assert_allclose(got_s, want_s, atol=2e-4, rtol=1e-4)
    # positions of dt 0 left the second row's state where it was
    _, s_early = _scan(x[1:, :t - 3], dt[1:, :t - 3], a, bm[1:, :t - 3],
                       cm[1:, :t - 3], s0[1:], chunk)
    np.testing.assert_allclose(got_s[1:], s_early, atol=2e-4, rtol=1e-4)


def test_a_step_of_dt_zero_leaves_the_state_to_the_bit():
    rng = np.random.RandomState(0)
    s0 = jnp.asarray(rng.randn(2, 4, 8, 16), jnp.float32)
    _, s1 = ssm.ssm_step(
        jnp.ones((2, 4, 8)), jnp.zeros((2, 4)), -jnp.ones((4,)),
        jnp.ones((2, 2, 16)), jnp.ones((2, 2, 16)), s0)
    np.testing.assert_array_equal(s1, s0)


def test_the_mixer_alone_tells_a_bfloat16_state_apart(t=128):
    """The state is float32 (``ssm_moe.STATE_DTYPE``; the model's card asks
    it of its servers) and ``correct`` does not judge it: a bfloat16 state
    reads inside the seeds' own spread on all four numbers (PERF.md section
    2).  The update alone does tell it apart, against the float64 recurrence
    at the published time steps (log-uniform 0.001-0.1) and decays: a state
    rounded where it is stored puts the output 3e-3 off, one more bfloat16
    rounding of an activation at ANY length (0.0028 / 0.0034 / 0.0028 at 64 /
    128 / 512 steps: state and error grow alike, so a longer decode would
    not show it better), where float32 stays at 1e-7.  The limit stands
    between with ten times of room on either side; the program's own type
    is held under it."""
    rng = np.random.RandomState(7)
    b, h, p, g, n = 1, 8, 8, 2, 16
    x = rng.randn(b, t, h, p)
    dt = np.exp(rng.uniform(np.log(0.001), np.log(0.1), (b, t, h)))
    a = -rng.uniform(1, 16, h)
    bm, cm = rng.randn(b, t, g, n), rng.randn(b, t, g, n)
    state = np.zeros((b, h, p, n))
    want = np.zeros((b, t, h, p))
    for i in range(t):  # float64, the equations as ISSUE 44 writes them
        b_h, c_h = (np.repeat(m[:, i], h // g, axis=1) for m in (bm, cm))
        state = (np.exp(dt[:, i] * a)[..., None, None] * state
                 + (dt[:, i, :, None] * x[:, i])[..., None]
                 * b_h[:, :, None, :])
        want[:, i] = np.sum(state * c_h[:, :, None, :], axis=-1)

    def drift(dtype):
        ys, _ = _recurrence(*(jnp.asarray(v, jnp.float32)
                              for v in (x, dt, a, bm, cm)),
                            jnp.zeros((b, h, p, n), dtype))
        late = slice(t - 64, t)
        return float(np.abs(np.asarray(ys)[:, late] - want[:, late]).mean()
                     / np.abs(want[:, late]).mean())

    limit = 1e-4
    assert drift(ssm_moe.STATE_DTYPE) < limit / 10
    assert drift(jnp.float32) < limit / 10 and drift(jnp.bfloat16) > limit * 10


def test_the_convolutions_tail_is_the_last_real_inputs():
    rng = np.random.RandomState(1)
    w, b = jnp.asarray(rng.randn(4, 6), jnp.float32), jnp.zeros((6,))
    tail = jnp.asarray(rng.randn(2, 3, 6), jnp.float32)
    xbc = jnp.asarray(rng.randn(2, 5, 6), jnp.float32)
    _, new = ssm.causal_conv(w, b, tail, xbc, jnp.array([5, 2]))
    np.testing.assert_array_equal(new[0], xbc[0, 2:5])
    np.testing.assert_array_equal(new[1, 0], tail[1, 2])
    np.testing.assert_array_equal(new[1, 1:], xbc[1, :2])
    _, kept = ssm.causal_conv(w, b, tail, xbc, jnp.array([0, 0]))
    np.testing.assert_array_equal(kept, tail)


# ---- the three programs -----------------------------------------------------------

def test_the_presets_cache_is_planes_and_a_state_a_slot(model):
    cfg, params = model
    cache = init_kv_cache(cfg, ROWS, MAX_SEQ, jnp.float32)
    assert cfg.mixer_kinds == "MEM*EM*" and cfg.attn_kinds == ("full",) * 2
    assert {k: v.shape for k, v in cache.items()} == {
        "k": (2, ROWS, MAX_SEQ, 2 * 16), "v": (2, ROWS, MAX_SEQ, 2 * 16),
        "ssm": (3, ROWS, 4, 8, 16), "conv": (3, ROWS, 3 * (32 + 2 * 2 * 16))}
    assert cache["ssm"].dtype == jnp.float32  # whatever the activations are
    assert init_kv_cache(cfg, ROWS, MAX_SEQ)["ssm"].dtype == jnp.float32
    assert ssm_moe.state_bytes_per_slot(cfg, jnp.float32) == 3 * (
        4 * 8 * 16 * 4 + 3 * 96 * 4)
    assert set(params) == {"embed", "final_norm", "lm_head", "mamba", "attn",
                           "blocks"}
    assert "moe_gate" not in params["blocks"]
    assert "shared_gate" not in params["blocks"]
    assert params["blocks"]["shared_up"].shape == (2, 64, 48)  # its own width


def test_whole_prompt_prefill_matches_the_reference(model):
    cfg, params = model
    prompt = _prompt(3, 43)
    want = np.asarray(plain.forward_logprobs(cfg, params, prompt))
    tok = jnp.array([prompt + [0] * 5])
    valid = jnp.arange(48)[None, :] < 43
    logits, rows, _ = _prefill(cfg, params, tok, valid)
    np.testing.assert_allclose(_logprobs(logits[0, :43]), want, atol=ATOL)
    assert rows["full"][0].shape == (2, 1, 48, 32)
    assert rows["state"][0].shape == (3, 1, 4, 8, 16)


def _decoding(cfg, update):
    cfg = replace(cfg, **UPDATES[update])
    assert ssm_moe.state_update_branch(cfg, None) == (
        SSM_STEP_KERNEL if update == "kernel" else ELEMENTWISE)
    return cfg


@pytest.mark.parametrize("update", sorted(UPDATES))
@pytest.mark.parametrize("cuts", [[(0, 43)], [(0, 16), (16, 27), (27, 43)]],
                         ids=["whole", "uneven-segments"])
def test_prefill_then_64_decode_steps_through_state_and_cache(model, cuts,
                                                              update):
    """The prompt whole (``prefill_into_cache``) or as chunk-prefill
    segments of uneven lengths beside a padding row, then 64 decode steps
    (the state updated by either branch), against ONE full forward of the
    plain reference."""
    cfg, params = model
    full = _prompt(3, 43) + _prompt(4, 64)
    want = np.asarray(plain.forward_logprobs(cfg, params, full))
    cache = init_kv_cache(cfg, ROWS, MAX_SEQ, jnp.float32)
    if len(cuts) == 1:
        tok = jnp.zeros((2, 48), jnp.int32).at[0, :43].set(
            jnp.array(full[:43]))
        last, cache = _prefill_into_cache(
            cfg, params, tok, jnp.array([43, 1]), cache,
            jnp.array([1, ROWS - 1]))
        np.testing.assert_allclose(_logprobs(last[0]), want[42], atol=ATOL)
    for a, b in cuts if len(cuts) > 1 else []:
        logits, cache = _chunk(cfg, params, cache, [(full, a, b, 1)])
        np.testing.assert_allclose(_logprobs(logits[0, :b - a]), want[a:b],
                                   atol=ATOL)
    for p in range(43, 107):
        logits, cache = _decode(_decoding(cfg, update), params, cache, 1,
                                full[p], p)
        np.testing.assert_allclose(_logprobs(logits), want[p], atol=ATOL)


@pytest.mark.parametrize("update", sorted(UPDATES))
def test_two_rows_of_different_lengths_equal_each_alone(model, update):
    """One dispatch carries a row of 16 and a row of 5 (padded to 16): each
    reads as it does alone, and the padded positions leave state and
    convolution tail untouched: both rows then continue from them."""
    cfg, params = model
    long, short = _prompt(5, 40), _prompt(6, 21)
    cache = init_kv_cache(cfg, ROWS, MAX_SEQ, jnp.float32)
    logits, cache = _chunk(cfg, params, cache,
                           [(long, 0, 16, 0), (short, 0, 5, 2)])
    want_l = np.asarray(plain.forward_logprobs(cfg, params, long))
    want_s = np.asarray(plain.forward_logprobs(cfg, params, short))
    np.testing.assert_allclose(_logprobs(logits[0]), want_l[:16], atol=ATOL)
    np.testing.assert_allclose(_logprobs(logits[1, :5]), want_s[:5],
                               atol=ATOL)
    logits, cache = _chunk(cfg, params, cache,
                           [(long, 16, 32, 0), (short, 5, 21, 2)])
    np.testing.assert_allclose(_logprobs(logits[0]), want_l[16:32], atol=ATOL)
    np.testing.assert_allclose(_logprobs(logits[1]), want_s[5:21], atol=ATOL)
    # a decode step of one row leaves the parked rows' state as it is
    before = {k: np.asarray(cache[k][:, 2]) for k in ssm_moe.STATE_KEYS}
    _, cache = _decode(_decoding(cfg, update), params, cache, 0, long[32],
                       32)
    for k in ssm_moe.STATE_KEYS:
        np.testing.assert_array_equal(np.asarray(cache[k][:, 2]), before[k])


def test_a_slots_second_tenant_starts_from_nothing(model):
    cfg, params = model
    first, second = _prompt(7, 30), _prompt(8, 12)
    cache = init_kv_cache(cfg, ROWS, MAX_SEQ, jnp.float32)
    _, cache = _chunk(cfg, params, cache, [(first, 0, 30, 1)], width=32)
    assert float(jnp.abs(cache["ssm"][:, 1]).max()) > 0
    logits, cache = _chunk(cfg, params, cache, [(second, 0, 12, 1)])
    want = np.asarray(plain.forward_logprobs(cfg, params, second))
    np.testing.assert_allclose(_logprobs(logits[0, :12]), want, atol=ATOL)


def test_an_int8_plane_reads_like_the_plain_one_and_is_narrower(model):
    cfg, params = model
    prompt = _prompt(3, 30)
    want = np.asarray(plain.forward_logprobs(cfg, params, prompt))
    cache = init_kv_cache(cfg, ROWS, MAX_SEQ, jnp.float32, quant="int8")
    assert cache["k"].dtype == jnp.int8 and cache["k_scale"].shape == (
        2, ROWS, MAX_SEQ, 2)
    assert cache["ssm"].dtype == jnp.float32  # the state has no int8 form
    _, cache = _chunk(cfg, params, cache, [(prompt, 0, 16, 1)])
    logits, cache = _chunk(cfg, params, cache, [(prompt, 16, 29, 1)])
    err = np.abs(_logprobs(logits[0, :13]) - want[16:29]).mean()
    assert 1e-5 < err < 0.05
    logits, _ = _decode(cfg, params, cache, 1, prompt[29], 29)
    assert np.abs(_logprobs(logits) - want[29]).mean() < 0.05
    with pytest.raises(ValueError, match="no KV quant mode 'int4'"):
        init_kv_cache(cfg, ROWS, MAX_SEQ, quant="int4")


def test_an_expert_wider_than_a_lane_tile_is_held_in_whole_tiles():
    """1856 is 14.5 lane tiles: such an expert is held 1920 wide, the added
    columns and rows zeros, and the forward is the plain one's."""
    assert get_config("nemotron-3-nano-30b-a3b").expert_dim_held == 1920
    assert get_config("sarvam-105b").expert_dim_held == 2048
    cfg = get_config("tiny-ssm-moe", moe_ffn_dim=136)
    assert cfg.expert_dim_held == 256
    params = init_params(cfg, jax.random.PRNGKey(3), jnp.float32)
    up, down = params["blocks"]["moe_up"], params["blocks"]["moe_down"]
    assert up.shape == (2, 8, 64, 256) and down.shape == (2, 8, 256, 64)
    assert float(jnp.abs(up[..., 136:]).max()) == 0.0
    assert float(jnp.abs(down[:, :, 136:]).max()) == 0.0
    assert float(jnp.abs(up[..., :136]).min()) > 0.0
    from p2p_llm_tunnel_tpu.models.transformer import _act

    h = jnp.asarray(np.random.RandomState(2).randn(1, 12, 64), jnp.float32)
    got, _ = moe.moe_mlp(cfg, _layer(params), h, lambda v: _act(cfg, v))
    np.testing.assert_allclose(
        got[0], plain.routed_layer(cfg, _layer(params), h[0]), atol=ATOL)
    # off the interpreter the kernel asks whole tiles of the held width
    force = get_config("nemotron-3-nano-30b-a3b-ep2s", flash_force=True)
    assert moe.grouped_product_branch(force, None, 129) == moe.GROUPED_KERNEL
    narrow = get_config("nemotron-3-nano-30b-a3b-ep2s", flash_force=True,
                        moe_ffn_dim=64 + 32)
    assert moe.grouped_product_branch(narrow, None, 129) == moe.RAGGED


def _layer(params, i=0):
    return jax.tree.map(lambda a: a[i], params["blocks"])


def test_the_shares_add_up():
    """The parts of a routed layer's result that the two chips give, the
    shared expert counted once, add up to the uncut layer's."""
    from p2p_llm_tunnel_tpu.models.transformer import _act

    whole = get_config("tiny-ssm-moe")
    full = init_params(whole, jax.random.PRNGKey(4), jnp.float32)
    h = jnp.asarray(np.random.RandomState(2).randn(1, 24, 64), jnp.float32)
    want = plain.routed_layer(whole, _layer(full), h[0])
    got, stats = moe.moe_mlp(whole, _layer(full), h,
                             lambda v: _act(whole, v))
    np.testing.assert_allclose(got[0], want, atol=ATOL)
    assert int(stats[0]) == int(stats[1]) == 24 * 2
    total = jnp.zeros_like(want)
    for chip in (0, 1):
        share = get_config("tiny-ssm-moe-ep2s", chip_index=chip)
        params = init_params(share, jax.random.PRNGKey(4), jnp.float32)
        lo, held = share.experts_held
        np.testing.assert_array_equal(
            params["blocks"]["moe_up"], full["blocks"]["moe_up"][:, lo:lo + held])
        part, stats = moe.moe_mlp(share, _layer(params), h,
                                  lambda v: _act(share, v))
        routed_only = plain.routed_layer(
            share, _layer(params), h[0], first_held=lo, shared=False)
        shared = plain.routed_layer(share, _layer(params), h[0], experts=(),
                                    first_held=lo)
        np.testing.assert_allclose(part[0], routed_only + shared, atol=ATOL)
        total = total + routed_only
        assert int(stats[1]) < int(stats[0]) == 48
    total = total + shared  # what both chips compute alike, once
    np.testing.assert_allclose(total, want, atol=ATOL)
