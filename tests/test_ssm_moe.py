"""The family of one mixer a layer (``tiny-ssm-moe``: Nemotron-H at a size
the CPU runs: Mamba-2 state-space layers, routed experts of two products,
attention) against its plain reference, tests/ssm_moe_plain.py: the chunked
scan against the token-by-token recurrence, the three serving programs
through state and cache, rows of different lengths in one dispatch, a slot's
second tenant, snapshots of state beside the prefix pool's pages, the shares
of a layer, the records and counters of state traffic, what /healthz says,
what is refused, the benchmark's own copy of the reference and its
configuration file, and the tiny cell in one process.
"""

from __future__ import annotations

import asyncio
import json
import os
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
from tests.moe_records import dispatches_closed

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
ROWS, MAX_SEQ = 4, 128
# float32 program against the float32 reference at `highest`: sums taken in
# another order (a chunked scan, a grouped product over sorted rows) differ
# in the last places of a float32.
ATOL = 2e-4


@pytest.fixture(scope="module", params=["tiny-ssm-moe", "tiny-ssm-moe-ep2s"])
def model(request):
    cfg = get_config(request.param)
    return cfg, init_params(cfg, jax.random.PRNGKey(11), jnp.float32)


def _prompt(seed, n):
    """Token ids under 250: the engine's default tokenizer has 259."""
    return list(np.random.RandomState(seed).randint(1, 250, size=n))


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


#: Decode's state update as ``ssm.ssm_step`` in XLA, and as the kernel over
#: the live rows (interpreted; the decode program's attention and grouped
#: products are their kernels then too): ISSUE 45.
UPDATES = {"elementwise": {}, "kernel": {"flash_interpret": True}}


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


# ---- the engine -------------------------------------------------------------------

def _engine(model_name="tiny-ssm-moe-ep2s", model_cfg=None, **kw):
    from p2p_llm_tunnel_tpu.engine.engine import EngineConfig, InferenceEngine

    return InferenceEngine(model_cfg=model_cfg, engine_cfg=EngineConfig(
        model=model_name, num_slots=2, max_seq=128, dtype="float32",
        decode_steps=2, **kw))


def _generate(eng, prompts, new=10, between=None):
    async def main():
        await eng.start()
        try:
            out = []
            for prompt in prompts:
                events = [ev async for ev in eng.generate(
                    prompt, max_new_tokens=new, logprobs=1, stop_ids=())]
                out.append(([ev.token_id for ev in events],
                            [ev.logprob for ev in events]))
                if between is not None:
                    between()
            await dispatches_closed(eng)
            return out
        finally:
            await eng.stop()

    return asyncio.run(asyncio.wait_for(main(), 300))


def _held_to_the_reference(eng, prompts, outs):
    for prompt, (tokens, values) in zip(prompts, outs):
        want = np.asarray(plain.forward_logprobs(
            eng.mcfg, eng.params, prompt + tokens))
        n = len(prompt)
        np.testing.assert_allclose(
            values, [want[n - 1 + j, t] for j, t in enumerate(tokens)],
            atol=ATOL)


def test_a_prefix_hit_restores_the_snapshot_at_or_before_it(chunk=32):
    """Prompts that share their first blocks, one after another through the
    engine (chunk prefill in segments of 32, the pool, decode bursts).  A
    later one hits the longest pooled boundary that has a snapshot of the
    state, one every 32 tokens here: the prompt of 77 whose rows reach 64
    hits 64; the one of 55 whose rows reach 48 falls back to 32 (with
    ``--prefill-chunk 16`` it hits 48: the tiny cell below).
    Every generated token's log-probability is the plain reference's: a
    restored state reads as a cold prefill does.  Slots are reused
    throughout: a finished request's state carries nothing over."""
    from p2p_llm_tunnel_tpu.utils.metrics import global_metrics

    eng = _engine(mux=True, prefix_cache=True, prefix_pool_blocks=32,
                  prefill_chunk=chunk)
    assert not eng.config_fences
    assert set(eng._pool) == {"k", "v"}  # pages hold rows, never state
    assert {k: v.shape[:2] for k, v in eng._snap_pool.items()} == {
        "ssm": (3, 17), "conv": (3, 17)}  # scratch + 32 x 16 tokens / 32
    base = _prompt(9, 70)
    prompts = [base, base[:55], base[:64] + _prompt(10, 13),
               base[:40] + _prompt(11, 9)]
    seen = [global_metrics.counter("engine_prefix_hit_tokens_total")]
    restores = global_metrics.counter("engine_state_restores_total")
    outs = _generate(eng, prompts, between=lambda: seen.append(
        global_metrics.counter("engine_prefix_hit_tokens_total")))
    assert [b - a for a, b in zip(seen, seen[1:])] == [0, 32, 64, 32]
    assert global_metrics.counter("engine_state_restores_total") - restores \
        == 3
    _held_to_the_reference(eng, prompts, outs)
    # the pool's bytes are the rows'; the snapshots are counted apart
    assert eng._prefix_block_bytes == 16 * 2 * 2 * 32 * 4
    assert global_metrics.gauge("engine_state_snapshots") == len(
        eng._snapshots) > 0
    assert global_metrics.gauge("engine_state_snapshot_bytes") == len(
        eng._snapshots) * ssm_moe.state_bytes_per_slot(eng.mcfg, jnp.float32)


def test_the_snapshots_are_evicted_least_recently_used_first():
    from p2p_llm_tunnel_tpu.engine.prefix_cache import (
        PrefixIndex,
        StateSnapshots,
    )

    snaps = StateSnapshots(3)  # scratch + two
    a, b, c = snaps.allocate(b"a"), snaps.allocate(b"b"), None
    assert {a, b} == {1, 2} and snaps.allocate(b"a") == a
    c = snaps.allocate(b"c")  # b was the least recently used
    assert c == b and b"b" not in snaps and b"a" in snaps and len(snaps) == 2
    assert snaps.lookup(b"b") is None and snaps.evictions == 1
    # a match ends at the longest pooled boundary that has a snapshot
    index = PrefixIndex(4, 16)
    index.snapshots = StateSnapshots(4)
    ids = list(range(1, 18))
    keys = index.block_keys(ids)
    index.allocate(keys[:3])
    assert index.match(ids) == (0, [])
    index.snapshots.allocate(keys[1])
    hist, pool_ids = index.match(ids)
    assert hist == 8 and len(pool_ids) == 2
    index.snapshots.allocate(keys[3])  # past the pooled rows: no use yet
    assert index.match(ids)[0] == 8


def test_an_echoed_prompt_and_a_whole_prompt_prefill_leave_a_snapshot():
    """The whole-prompt path (an echo request) of 64 tokens ends on a block
    boundary: its state is snapshotted, and the next request hits all 64."""
    from p2p_llm_tunnel_tpu.utils.metrics import global_metrics

    eng = _engine(mux=True, prefix_cache=True, prefix_pool_blocks=32,
                  prefill_chunk=16)
    prompt = _prompt(12, 64)

    async def main():
        await eng.start()
        try:
            events = [ev async for ev in eng.generate(
                prompt, max_new_tokens=2, logprobs=1, echo_logprobs=True,
                stop_ids=())]
            hit = global_metrics.counter("engine_prefix_hit_tokens_total")
            later = prompt + _prompt(13, 5)
            more = [ev async for ev in eng.generate(
                later, max_new_tokens=4, logprobs=1, stop_ids=())]
            await asyncio.sleep(0.2)
            return events, more, global_metrics.counter(
                "engine_prefix_hit_tokens_total") - hit
        finally:
            await eng.stop()

    events, more, hit = asyncio.run(asyncio.wait_for(main(), 300))
    assert hit == 64
    want = np.asarray(plain.forward_logprobs(eng.mcfg, eng.params, prompt))
    np.testing.assert_allclose(
        events[0].prompt_logprobs[1:64],
        [want[t - 1, tok] for t, tok in enumerate(prompt)][1:], atol=ATOL)
    later = prompt + _prompt(13, 5)
    _held_to_the_reference(eng, [later], [([e.token_id for e in more],
                                           [e.logprob for e in more])])


def test_the_records_and_the_counters_carry_the_state_read_and_written():
    """One cold request and one that hits through the engine: every prefill
    and decode record says how many rows' state it read and wrote and the
    bytes, from the host's own counts; ``engine.state_snapshot`` and
    ``engine.state_restore`` say theirs; ``engine_state_bytes_total`` grows
    by exactly the records' sum, the other two counters by the events."""
    from p2p_llm_tunnel_tpu.utils.metrics import METRICS_CATALOG, global_metrics
    from tests.moe_records import tracing

    names = ("engine_state_bytes_total", "engine_state_snapshots_total",
             "engine_state_restores_total")
    assert all(n in METRICS_CATALOG for n in names)
    assert "engine_state_snapshots" in METRICS_CATALOG
    assert "engine_state_snapshot_bytes" in METRICS_CATALOG
    prompt = _prompt(9, 37)
    with tracing() as tracer:
        eng = _engine(mux=True, prefix_cache=True, prefix_pool_blocks=16,
                      prefill_chunk=16)
        before = [global_metrics.counter(n) for n in names]
        _generate(eng, [prompt, prompt[:35]], new=5)
        grew = [global_metrics.counter(n) - b for n, b in zip(names, before)]
        records = tracer.records()
    row = ssm_moe.state_bytes_per_slot(eng.mcfg, jnp.float32)
    assert row == eng._state_row_bytes
    segs = [r for r in records if r.name == "engine.prefill_segment"]
    bursts = [r for r in records if r.name == "engine.decode_burst"]
    saved = [r for r in records if r.name == "engine.state_snapshot"]
    restored = [r for r in records if r.name == "engine.state_restore"]
    # 16 + 16 + 5 cold, then 32 restored and a tail of 3
    assert [r.attrs["tokens"] for r in segs] == [16, 16, 5, 3] and bursts
    for r in segs:
        assert (r.attrs["state_rows"], r.attrs["state_bytes"]) == (1, 2 * row)
    for r in bursts:
        a = r.attrs
        assert a["state_rows"] == a["live_rows"] * a["steps"]
        assert a["state_bytes"] == 2 * row * a["state_rows"]
    assert [r.attrs["boundary"] for r in saved] == [16, 32]
    assert [r.attrs["tokens_skipped"] for r in restored] == [32]
    assert all(r.attrs["bytes"] == row for r in saved + restored)
    assert grew == [
        sum(r.attrs["state_bytes"] for r in segs + bursts)
        + row * len(saved + restored), len(saved), len(restored)]
    # a model without a state counts none
    dense = _engine("tiny")
    assert dense._state_row_bytes == 0 and dense._snapshots is None


def test_the_records_moe_and_the_kernel_counter_are_held_to_each_other(
        kernel=True):
    """Every decode and prefill record of a share says which grouped product
    its program ran (two an expert here); the counter grows by the records
    that say the kernel; the kernel (interpreted here, over the stack of all
    layers' experts) emits ``ragged_dot``'s tokens (over a layer's slice)."""
    from tests import moe_records

    def run(interpret):
        eng = _engine(
            model_cfg=get_config("tiny-ssm-moe-ep2s", flash_interpret=interpret,
                                 vocab_size=259),
            mux=True, prefix_cache=True, prefix_pool_blocks=16,
            prefill_chunk=16)
        return (eng,) + moe_records.run_traced(eng, _prompt(9, 37), 5)

    eng, toks, grew, records = run(kernel)
    moe_records.check(eng, grew, records, kernel)
    plain_eng, plain_toks, plain_grew, plain_records = run(False)
    moe_records.check(plain_eng, plain_grew, plain_records, False)
    assert toks == plain_toks


@pytest.mark.parametrize("update", sorted(UPDATES))
def test_the_state_kernels_counter_the_records_and_healthz_name_one_branch(
        update):
    """``engine_decode_state_kernel_steps_total`` grows by the steps of the
    bursts whose program took the state kernel and by none under the
    elementwise branch; every burst's record and /healthz name that branch;
    a parked slot's state is the same to the bit after the bursts."""
    from p2p_llm_tunnel_tpu.utils.metrics import METRICS_CATALOG, global_metrics
    from tests.moe_records import tracing

    name = "engine_decode_state_kernel_steps_total"
    assert name in METRICS_CATALOG
    want = SSM_STEP_KERNEL if update == "kernel" else ELEMENTWISE
    with tracing() as tracer:
        eng = _engine(model_cfg=get_config(
            "tiny-ssm-moe-ep2s", vocab_size=259, **UPDATES[update]))
        # slot 1 is never admitted: what lies there is a parked row's
        eng.kv_cache["ssm"] = eng.kv_cache["ssm"].at[:, 1].set(0.25)
        before = global_metrics.counter(name)
        steps = global_metrics.counter("engine_decode_steps_total")
        _generate(eng, [_prompt(9, 21)], new=7)
        grew = global_metrics.counter(name) - before
        steps = global_metrics.counter("engine_decode_steps_total") - steps
        bursts = [r for r in tracer.records()
                  if r.name == "engine.decode_burst"]
    assert bursts and steps == sum(r.attrs["steps"] for r in bursts)
    assert {r.attrs["state_update"] for r in bursts} == {want}
    assert grew == (steps if update == "kernel" else 0)
    state = eng._model_section()["cache"]["kinds"]["state"]
    assert state["update"] == want == eng._state_update
    np.testing.assert_array_equal(np.asarray(eng.kv_cache["ssm"][:, 1]), 0.25)
    assert float(jnp.abs(eng.kv_cache["ssm"][:, 0]).max()) > 0
    # a model without such a state has no branch, no attr and no count
    dense = _engine("tiny")
    assert dense._state_update is None


REFUSED = {
    "quant-int8": dict(quant="int8"),
    "quant-int4": dict(quant="int4"),
    "quant-w8a8": dict(quant="w8a8"),
    "kv-int4": dict(kv_quant="int4"),
    "tp": dict(tp=2), "sp": dict(sp=2), "ep": dict(ep=2),
    "ragged-prefill": dict(ragged_prefill=True),
    "spec-ngram": dict(spec_ngram=2),
    "ckpt": dict(ckpt_path="/nowhere"),
    "spill-pages": dict(prefix_cache=True, spill_pages=4),
    "role": dict(prefix_cache=True, role="prefill"),
    "prefix-cache-dir": dict(prefix_cache=True, prefix_cache_dir="/nowhere"),
}


@pytest.mark.parametrize("case", sorted(REFUSED))
def test_what_the_family_lacks_is_refused_at_start_up(case):
    with pytest.raises(ValueError, match=r"a recurrent state beside the KV "
                                         r"planes.* cannot be served with --"):
        _engine("tiny-ssm-moe", **REFUSED[case])


def test_healthz_names_the_planes_the_state_and_a_slots_bytes():
    eng = _engine(prefix_cache=True, prefix_pool_blocks=8, mux=True,
                  prefill_chunk=16)
    section = eng._model_section()
    cache = section["cache"]
    assert cache["form"] == "kv_heads+state"
    assert cache["kinds"]["attention"] == {
        "layers": 2, "kv_heads": 2, "key_width": 16, "value_width": 16,
        "positions_per_slot": 128, "bytes_per_token_layer": 64 * 4}
    per_slot = 3 * (4 * 8 * 16 * 4 + 3 * 96 * 4)
    assert cache["kinds"]["state"] == {
        "layers": 3, "heads": 4, "head_width": 8, "state_width": 16,
        "type": "float32", "conv_positions": 3, "conv_width": 96,
        "conv_type": "float32", "bytes_per_slot": per_slot,
        "update": ELEMENTWISE,
        # 8 blocks x 16 tokens / a chunk of 16 (ISSUE 46: said in bytes)
        "snapshots": {"room": 8, "held": 0, "bytes_each": per_slot,
                      "bytes": 8 * per_slot}}
    # one mixer a layer, no multiplier, a head of its own (ISSUE 46)
    assert section["layer"] == {"mixers": {"M": 3, "E": 2, "*": 2},
                                "mlp_width": 0}
    assert section["multipliers"] == {
        "embedding": 1.0, "residual": 1.0, "attention_scores": 0.25,
        "logits_divisor": 1.0}
    assert section["head"] == "its own"
    # two statements: what the pool holds for a token, what a slot holds
    assert cache["bytes_per_token"] == 2 * 64 * 4
    assert cache["bytes_per_slot"] == 2 * 64 * 4 * 128 + per_slot
    rows = eng.ecfg.num_slots + 1
    assert sum(int(a.size) * a.dtype.itemsize
               for a in eng.kv_cache.values()) == rows * cache["bytes_per_slot"]
    assert eng._prefix_block_bytes == 16 * cache["bytes_per_token"]
    assert section["layers"] == {"held": 7, "of": 7}
    assert section["experts"] == {"held": 4, "first": 0, "of": 8}
    assert section["expert_products"]["decode"] == moe.RAGGED
    assert section["vocab_rows"] == {"held": eng.mcfg.vocab_size,
                                     "of": 2 * eng.mcfg.vocab_size}


def test_the_published_preset_and_its_share():
    whole = get_config("nemotron-3-nano-30b-a3b")
    share = get_config("nemotron-3-nano-30b-a3b-ep2s")
    assert (whole.n_layers, whole.n_experts, whole.vocab_size) == (
        52, 128, 131072)
    assert [whole.mixer_kinds.count(k) for k in "ME*"] == [23, 23, 6]
    assert share.mixer_kinds == "MEMEM*EMEMEM*"
    assert share.experts_held == (0, 64) and share.n_layers == 13
    assert share.vocab_size * 2 == whole.vocab_size
    assert len(share.attn_kinds) == 2
    same = {f: getattr(whole, f) for f in (
        "dim", "n_heads", "n_kv_heads", "head_dim", "moe_ffn_dim",
        "shared_expert_dim", "n_experts", "n_experts_per_tok", "ssm_heads",
        "ssm_head_dim", "ssm_groups", "ssm_state", "ssm_conv", "ssm_chunk",
        "router_bias", "routed_scale", "router_score", "expert_gated", "act")}
    assert same == {f: getattr(share, f) for f in same}
    assert (share.ssm_inner, share.ssm_conv_dim) == (4096, 6144)
    # what a slot's state takes: 6 x (2.097 MB + 37 KB)
    assert ssm_moe.state_bytes_per_slot(share) == 6 * (
        64 * 64 * 128 * 4 + 3 * 6144 * 2) == 12_804_096
    # the cut's parameters, by the published shapes (an expert held 1920
    # wide counts its 1856)
    shapes = jax.eval_shape(lambda: init_params(share, jax.random.PRNGKey(0)))
    count = sum(int(np.prod(x.shape)) for x in jax.tree.leaves(shapes))
    count -= 5 * 64 * 2 * 2688 * 64
    assert 3.92e9 < count < 3.93e9


# ---- the benchmark's copy and its configuration -----------------------------------

def _tiny_file():
    import sys

    sys.path.insert(0, os.path.join(REPO, "tests", "benchmarks"))
    import tinycell_ssm

    return tinycell_ssm


def test_the_benchmarks_reference_is_the_same_model(share=True):
    """benchmarks/ssm_moe_reference.py draws the program's weights from the
    seed and computes the plain reference's forward, whole and as a share."""
    from benchmarks import ssm_moe_reference as bench

    config = dict(_tiny_file().CONFIG)
    if not share:
        config.update(n_routed_experts=8, layer_chips=1,
                      published_counts={"n_routed_experts": 8})
    cfg = get_config("tiny-ssm-moe-ep2s" if share else "tiny-ssm-moe")
    shapes = bench.shapes_of(config)
    assert shapes["kinds"] == cfg.mixer_kinds
    weights = bench.make_weights(shapes, 5)
    params = init_params(cfg, jax.random.PRNGKey(5), jnp.bfloat16)
    pairs = [(weights["attn"][k], params["attn"][k])
             for k in ("wq", "wk", "wv", "wo")]
    pairs += [(weights["mamba"][k], params["mamba"][k]) for k in (
        "w_in", "conv_w", "conv_b", "w_out", "dt_bias", "a_log")]
    pairs += [(weights["moe"][a], params["blocks"][b]) for a, b in (
        ("up", "moe_up"), ("down", "moe_down"), ("router", "router"),
        ("bias", "router_bias"), ("shared_up", "shared_up"),
        ("shared_down", "shared_down"))]
    pairs += [(weights["embed"], params["embed"]),
              (weights["lm_head"], params["lm_head"])]
    for mine, theirs in pairs:
        np.testing.assert_array_equal(np.asarray(mine, np.float32),
                                      np.asarray(theirs, np.float32))
    assert float(jnp.abs(params["mamba"]["d_skip"] - 1).max()) == 0
    tokens = _prompt(3, 37)
    got = np.asarray(bench.forward_logprobs(shapes, weights, tokens))
    want = np.asarray(plain.forward_logprobs(cfg, params, tokens))
    np.testing.assert_allclose(got, want, atol=ATOL)
    rounded = np.asarray(bench.forward_logprobs(shapes, weights, tokens,
                                                weight_bits=8))
    assert 1e-3 < np.abs(rounded - want).mean() < 0.5
    assert bench.cache_bytes_per_token(config) == _tiny_file().CACHE_BYTES


def test_the_configuration_file_keeps_the_published_keys():
    with open(os.path.join(REPO, "benchmarks", "configs",
                           "nemotron-3-nano-30b-a3b.json")) as f:
        body = json.load(f)
    with open("/opt/skills/guides/model-configs/architectures.jsonl") as f:
        published = next(
            row["config"] for row in map(json.loads, f)
            if row["name"] == "NVIDIA-Nemotron-3-Nano-30B-A3B-BF16")
    reduced = {"num_hidden_layers": 13,
               "hybrid_override_pattern": "MEMEM*EMEMEM*",
               "n_routed_experts": 64, "vocab_size": 65536}
    assert set(body["reduced"]) == set(reduced)
    for key, value in published.items():
        assert body[key] == reduced.get(key, value), key
    assert {k: body["published_counts"][k] for k in reduced} == {
        k: published[k] for k in reduced}
    assert body["layer_chips"] * body["n_routed_experts"] == \
        published["n_routed_experts"]
    assert 2 * body["vocab_size"] == published["vocab_size"]
    from benchmarks import ssm_moe_reference as bench

    # 2 attention layers x 2 KV heads x (128 + 128) values in bfloat16
    assert bench.cache_bytes_per_token(body) == 2048
    shapes = bench.shapes_of(body)
    share = get_config(body["serve"]["model"])
    assert share.mixer_kinds == shapes["kinds"]
    assert share.experts_held == (shapes["first_held"], shapes["held"])
    assert share.vocab_size == shapes["vocab"]
    assert (share.ssm_heads, share.ssm_head_dim, share.ssm_groups,
            share.ssm_state, share.ssm_conv) == (
        shapes["ssm_heads"], shapes["ssm_p"], shapes["ssm_groups"],
        shapes["ssm_n"], shapes["conv"])
    assert (share.ssm_dt_min, share.ssm_dt_max, share.ssm_dt_floor) == (
        shapes["dt_min"], shapes["dt_max"], shapes["dt_floor"])
    assert share.ssm_chunk == body["chunk_size"]
    assert jnp.dtype(ssm_moe.STATE_DTYPE).name == body["state_type"] \
        == "float32"
    assert (share.expert_dim, share.shared_expert_dim) == (
        shapes["expert_ffn"], shapes["shared_ffn"])
    # the cell's clients are the file's slots
    slots = int(body["serve"]["args"][body["serve"]["args"].index(
        "--slots") + 1])
    with open(os.path.join(REPO, "benchmarks", "traffic",
                           "agents-closed.json")) as f:
        assert json.load(f)["clients"] == slots


# ---- the tiny cell, in one process ------------------------------------------------

#: (the activations and cache controls of the same cell: through the stack,
#: tests/benchmarks/test_bm_ssm_rehearsal.py, ``slow``)
TINY_CELL_MODES = {
    "stated": ({}, None),
    "weights": ({}, 8),
}


@pytest.mark.parametrize("mode", sorted(TINY_CELL_MODES))
def test_the_tiny_cell_is_correct_as_stated_and_not_under_a_control(mode):
    """tests/benchmarks/tinycell_ssm.py's cell (the ``tiny-ssm-moe-ep2s``
    share in bfloat16 against benchmarks/ssm_moe_reference.py given the same
    share) through the engine in this process: what ``correct`` compares,
    as stated and with each stated precision lowered.  The ladder's
    prefixes reach the chunk program through the pool and the snapshots.
    (Through signal + serve + proxy: tests/benchmarks/
    test_bm_ssm_rehearsal.py, ``slow``.)"""
    from test_mla_moe import _ask_in_process

    tiny = _tiny_file()
    from benchmarks import correctness, ssm_moe_reference as bench, traffic
    from p2p_llm_tunnel_tpu.engine.engine import EngineConfig, InferenceEngine
    from p2p_llm_tunnel_tpu.engine.tokenizer import ByteTokenizer
    from p2p_llm_tunnel_tpu.utils.metrics import global_metrics

    engine_args, weight_bits = TINY_CELL_MODES[mode]
    config, seed = tiny.CONFIG, 11
    limits = config["correct"]["limits"]
    vocab = config["vocab_size"]
    plan = traffic.make_plan(
        {"name": "t", "loop": "closed", "clients": 3,
         "requests_per_client": 4, "lead_s": 0.5, "tail_s": 0.0,
         "request_timeout_s": 30.0,
         "prompt_tokens": {"dist": "uniform", "min": 8, "max": 24},
         "output_tokens": {"dist": "uniform", "min": 8, "max": 16}},
        seed, 3, vocab)
    seqs = correctness.sequences(plan, seed, vocab, 256)
    shapes = bench.shapes_of(config)
    weights = bench.make_weights(shapes, seed)
    stated = bench.cache_bytes_per_token(config)
    if weight_bits is None:
        class Words(ByteTokenizer):
            vocab_size = vocab

        restores = global_metrics.counter("engine_state_restores_total")
        eng = InferenceEngine(
            engine_cfg=EngineConfig(
                model=config["serve"]["model"], num_slots=4, max_seq=256,
                seed=seed, mux=True, prefix_cache=True, prefill_chunk=16,
                **engine_args),
            tokenizer=Words())
        _ask_in_process(eng, seqs)
        counted = eng._prefix_block_bytes / eng._prefix_block
        # the ladder went through the snapshots
        assert global_metrics.counter("engine_state_restores_total") \
            > restores
    else:  # the reference in the program's place, its weights rounded
        import importlib.util

        spec = importlib.util.spec_from_file_location(
            "control", os.path.join(REPO, "benchmarks", "control.py"))
        control = importlib.util.module_from_spec(spec)
        spec.loader.exec_module(control)
        counted = stated
        for seq in seqs:
            control.pretend(seq)
            lp = np.asarray(bench.forward_logprobs(
                shapes, weights, seq["tokens"], weight_bits=weight_bits))
            seq["system"] = [float(lp[p, t]) for p, t in seq["probes"]]
    reference = []
    for seq in seqs:
        lp = np.asarray(bench.forward_logprobs(shapes, weights, seq["tokens"]))
        reference.append([float(lp[p, t]) for p, t in seq["probes"]])
    numbers = correctness.compare(seqs, reference)
    said = []
    held = correctness.judge(numbers, limits, counted, stated, said.append)
    assert held is (mode == "stated"), "\n".join(said)
    assert stated == tiny.CACHE_BYTES
    if mode != "stated":
        assert numbers["echo_prompt"]["mean_abs"] > limits["echo_prompt"], said
