"""Laguna-S-2.1's family (``tiny-laguna``: eight layers F WWW F WWW, 6 and 9
query heads on 3 KV heads, a gate a head, yarn on the full layers' leading
columns, QK norm, 16 experts top-3 beside a shared one, rings of 16
positions) against the benchmark's plain reference,
benchmarks/laguna_moe_reference.py, given the program's own weights: the
three serving programs at contexts under the window, between window and
ring and past the ring's wrap, a page restored into a ring, the rows kernel
at a group of 6, the gate, yarn's frequencies and the shares of a routed
layer.  The family through the engine is tests/test_laguna_moe_engine.py;
the presets, the configuration file and the tiny cell are
tests/test_laguna_moe_cell.py.
"""

from __future__ import annotations

import json
import math
import os
from dataclasses import replace

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from benchmarks import laguna_moe_reference as bench
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
from tests.laguna_moe_tiny import (
    ATOL,
    MAX_SEQ,
    RING,
    ROWS,
    _config,
    _prompt,
    as_reference,
)

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


@pytest.fixture(scope="module", params=["tiny-laguna", "tiny-laguna-ep2s"])
def model(request):
    cfg = get_config(request.param)
    params = init_params(cfg, jax.random.PRNGKey(11), jnp.float32)
    shapes = bench.shapes_of(_config(request.param.endswith("ep2s")))
    weights = as_reference(params)

    def want(tokens):
        return np.asarray(bench.forward_logprobs(shapes, weights, tokens))

    return cfg, params, want


def _logprobs(logits):
    return np.asarray(jax.nn.log_softmax(logits, axis=-1))


# (one trace a shape: an eager scan is traced anew at every call)
_prefill_into_cache = jax.jit(prefill_into_cache, static_argnums=(0,))
_chunk_prefill = jax.jit(chunk_prefill_into_cache, static_argnums=(0,),
                         static_argnames=("kv_view", "return_all_logits"))
_decode_step = jax.jit(decode_step, static_argnums=(0,),
                       static_argnames=("kv_view",))


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


def test_the_presets_layers_and_planes(model):
    cfg, params, _ = model
    assert cfg.attn_kinds == ("full", "window", "window", "window") * 2
    assert cfg.layer_kinds == ("dense",) + ("moe",) * 7
    assert (cfg.heads_of("full"), cfg.heads_of("window")) == (6, 9)
    assert (cfg.kv_heads_of("full"), cfg.kv_heads_of("window")) == (3, 3)
    assert (cfg.rotary_of("full"), cfg.rotary_of("window")) == (8, 16)
    cache = init_kv_cache(cfg, ROWS, MAX_SEQ, jnp.float32)
    assert {k: v.shape for k, v in cache.items()} == {
        "k": (2, ROWS, MAX_SEQ, 48), "v": (2, ROWS, MAX_SEQ, 48),
        "wk": (6, ROWS, RING, 48), "wv": (6, ROWS, RING, 48)}
    assert [(r.attn, r.ffn, r.attn_first, r.ffn_first, r.n)
            for r in swa.layer_runs(cfg)] == [
        ("full", "dense", 0, 0, 1), ("window", "moe", 0, 0, 3),
        ("full", "moe", 1, 3, 1), ("window", "moe", 3, 4, 3)]
    held = cfg.experts_held[1]
    shapes = {k: {n: a.shape[1:] for n, a in g.items()}
              for k, g in params.items() if isinstance(g, dict)}
    # W_q, W_o and W_g go by the kind's head count; K and V do not
    assert shapes["attn_full"] == {
        "attn_norm": (64,), "q_norm": (16,), "k_norm": (16,),
        "wq": (64, 96), "wk": (64, 48), "wv": (64, 48), "wo": (96, 64),
        "wg": (64, 6)}
    assert shapes["attn_window"] == dict(
        shapes["attn_full"], wq=(64, 144), wo=(144, 64), wg=(64, 9))
    assert shapes["blocks"] == {
        "mlp_norm": (64,), "router": (64, 16), "router_bias": (16,),
        "moe_gate": (held, 64, 32), "moe_up": (held, 64, 32),
        "moe_down": (held, 32, 64), "shared_gate": (64, 32),
        "shared_up": (64, 32), "shared_down": (32, 64)}


def test_whole_prompt_prefill_matches_the_reference(model):
    cfg, params, want = model
    prompt = _prompt(3, 43)
    ref = want(prompt)
    tok = jnp.array([prompt + [0] * 5])
    valid = jnp.arange(48)[None, :] < 43
    logits, rows, _ = prefill(cfg, params, tok, valid)
    np.testing.assert_allclose(_logprobs(logits[0, :43]), ref, atol=ATOL)
    assert rows["full"][0].shape == (2, 1, 48, 48)
    assert rows["window"][1].shape == (6, 1, 48, 48)
    # into the cache: a full layer keeps every position, a ring the last 16
    cache = init_kv_cache(cfg, ROWS, MAX_SEQ, jnp.float32)
    last, cache = _prefill_into_cache(
        cfg, params, tok, jnp.array([43]), cache, jnp.array([1]))
    np.testing.assert_allclose(_logprobs(last[0]), ref[42], atol=ATOL)
    for p in range(43):
        held = np.asarray(cache["wk"][:, 1, p % RING])
        fresh = np.asarray(rows["window"][0][:, 0, p])
        assert (held == fresh).all() == (p >= 43 - RING), p


@pytest.mark.parametrize("cuts,steps", [
    ((0, 5), 2), ((0, 7, 12), 3), ((0, 16, 32, 48), 30),
    ((0, 7, 23, 37, 41), 30), ((0, 16, 19), 40)],
    ids=["under-the-window", "between-window-and-ring", "aligned-and-wrapped",
         "ragged-and-wrapped", "short-tail-then-three-wraps"])
def test_chunked_prefill_and_decode_through_the_rings(model, cuts, steps):
    """Segments of at most 16 tokens against a window of 8 in rings of 16:
    a context that ends under the window (7 positions), one between the
    window and the ring (15), and ones whose segments and decode steps wrap
    the rings up to three times.  Log-probabilities of every position
    against the reference, which has no cache."""
    cfg, params, want = model
    n = cuts[-1]
    seq = _prompt(5, n + steps)
    ref = want(seq)
    cache = init_kv_cache(cfg, ROWS, MAX_SEQ, jnp.float32)
    for start, end in zip(cuts, cuts[1:]):
        logits, cache = _chunk(cfg, params, cache, seq, start, end, 2)
        np.testing.assert_allclose(_logprobs(logits[0, :end - start]),
                                   ref[start:end], atol=ATOL)
    for p in range(n, n + steps):
        logits, cache = _decode(cfg, params, cache, 2, seq[p], p)
        np.testing.assert_allclose(_logprobs(logits), ref[p], atol=ATOL)
    # the scratch row's padding and the parked rows left slot 0 untouched
    assert not np.asarray(cache["wk"][:, 0]).any()


def test_a_page_is_restored_into_a_ring(model):
    """Saved segment by segment while the rings hold each block, a prefix
    of 48 tokens is restored into another slot: the full layers whole, the
    window layers' last 16 positions into the ring, and a tail and decode
    steps over the restored slot read what the reference gives."""
    from p2p_llm_tunnel_tpu.engine.prefix_cache import (
        init_pool,
        make_batch_copy_ops,
        pad_rows,
    )

    cfg, params, want = model
    block, nmax = 16, MAX_SEQ // 16
    seq = _prompt(7, 48) + _prompt(8, 30)
    ref = want(seq)
    cache = init_kv_cache(cfg, ROWS, MAX_SEQ, jnp.float32)
    pool = init_pool(cache, block, 8)
    assert {k: v.shape for k, v in pool.items()} == {
        "k": (2, 8, block, 48), "v": (2, 8, block, 48),
        "wk": (6, 8, block, 48), "wv": (6, 8, block, 48)}
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
    np.testing.assert_array_equal(np.asarray(cache["wk"][:, 2]),
                                  np.asarray(cache["wk"][:, 0]))
    logits, cache = _chunk(cfg, params, cache, seq, 48, 57, 2)
    np.testing.assert_allclose(_logprobs(logits[0, :9]), ref[48:57],
                               atol=ATOL)
    for p in range(57, 78):
        logits, cache = _decode(cfg, params, cache, 2, seq[p], p)
        np.testing.assert_allclose(_logprobs(logits), ref[p], atol=ATOL)


# ---- the rows kernel at this family's groups --------------------------------------

def test_the_rows_kernel_reads_a_group_of_six_like_the_einsum():
    """48 query heads on 8 KV heads are 6 a KV head, a group none of the
    cells' full layers has had (1, 4, 7, 16): the kernel, interpreted, over
    planes of heads side by side against the einsum's mathematics, rows at
    positions in the first block, across blocks and parked."""
    from p2p_llm_tunnel_tpu.ops.attention import masked_attention, window_mask
    from p2p_llm_tunnel_tpu.ops.pallas_decode_attention import (
        decode_attention_rows,
        decode_rows_worklist,
        rows_block,
    )

    layers, rows, seq, kv, g, d = 2, 4, 256, 2, 6, 16
    key = jax.random.PRNGKey(1)
    q = jax.random.normal(key, (rows, kv * g, d), jnp.float32)
    k = jax.random.normal(jax.random.fold_in(key, 1),
                          (layers, rows, seq, kv * d), jnp.float32)
    v = jax.random.normal(jax.random.fold_in(key, 2),
                          (layers, rows, seq, kv * d), jnp.float32)
    positions = jnp.array([3, 130, 255, seq])
    block = rows_block(seq, kv)
    got = decode_attention_rows(
        q, k, v, jnp.int32(1), decode_rows_worklist(positions, seq, block),
        block=block, interpret=True)
    mask = window_mask(positions[:, None],
                       jnp.broadcast_to(jnp.arange(seq), (rows, seq)))
    want = masked_attention(
        q[:, None], k[1].reshape(rows, seq, kv, d),
        v[1].reshape(rows, seq, kv, d), mask, d ** -0.5)[:, 0]
    np.testing.assert_allclose(np.asarray(got[:3]), np.asarray(want[:3]),
                               atol=2e-5)
    assert not np.asarray(got[3]).any()  # a parked row reads nothing


def test_decode_on_the_rows_kernel_reads_like_the_reference():
    """``decode_step`` as a TPU backend runs it (the kernel interpreted):
    the full layers, 6 heads on 3 KV heads, through the rows kernel and the
    gate after it."""
    from p2p_llm_tunnel_tpu.ops.pallas_decode_attention import ROWS_KERNEL

    cfg = get_config("tiny-laguna", flash_interpret=True)
    params = init_params(cfg, jax.random.PRNGKey(11), jnp.float32)
    shapes = bench.shapes_of(_config(False))
    seq = _prompt(4, 30)
    ref = np.asarray(bench.forward_logprobs(shapes, as_reference(params), seq))
    cache = init_kv_cache(cfg, ROWS, MAX_SEQ, jnp.float32)
    _, cache = _chunk(cfg, params, cache, seq, 0, 16, 1)
    row = jnp.zeros((ROWS,), jnp.int32)
    text = str(jax.make_jaxpr(lambda p, c: decode_step(
        cfg, p, c, row, row, kv_view=MAX_SEQ))(params, cache))
    assert ROWS_KERNEL in text
    for p in range(16, 30):
        logits, cache = _decode(cfg, params, cache, 1, seq[p], p)
        np.testing.assert_allclose(_logprobs(logits), ref[p], atol=ATOL)


# ---- the gate ---------------------------------------------------------------------

def _constant_column(params, head, kinds):
    """``params`` with a column of the stream that every token holds large
    and positive (the embedding's column 0 is 30 where its other entries are
    about 0.1, so the normed stream's column 0 is about 7 in every layer)
    and ``W_g`` of ``head`` in each of ``kinds`` large and negative on that
    column alone: the head's gate is under e**-100 for every token."""
    out = dict(params, embed=params["embed"].at[:, 0].set(30.0))
    for kind in kinds:
        group = dict(out[swa.ATTN_GROUP[kind]])
        wg = group["wg"].at[:, :, head].set(0.0)
        group["wg"] = wg.at[:, 0, head].set(-40.0)
        out[swa.ATTN_GROUP[kind]] = group
    return out


def _without_head(params, head, kinds):
    """``params`` with ``W_o``'s rows of ``head`` zeroed in each of
    ``kinds``: the model that has lost that head's contribution."""
    out = dict(params)
    for kind in kinds:
        group = dict(out[swa.ATTN_GROUP[kind]])
        d = group["wo"].shape[1] // group["wg"].shape[2]
        group["wo"] = group["wo"].at[:, head * d:(head + 1) * d].set(0.0)
        out[swa.ATTN_GROUP[kind]] = group
    return out


@pytest.mark.parametrize("kinds", [("full",), ("window",), ("full", "window")],
                         ids=["full", "window", "both"])
def test_a_gate_driven_shut_loses_its_heads_contribution(kinds):
    """A model whose ``W_g`` is large and negative on one head loses that
    head's contribution, in the program and in the reference alike: both
    read what the model with that head's rows of ``W_o`` zeroed reads, and
    not what the model with its gate as drawn reads."""
    cfg = get_config("tiny-laguna")
    shapes = bench.shapes_of(_config(False))
    head = 4
    drawn = init_params(cfg, jax.random.PRNGKey(11), jnp.float32)
    drawn = dict(drawn, embed=drawn["embed"].at[:, 0].set(30.0))
    shut = _constant_column(drawn, head, kinds)
    lost = _without_head(shut, head, kinds)
    prompt = _prompt(3, 32)
    tok, valid = jnp.array([prompt]), jnp.ones((1, 32), bool)

    run = jax.jit(lambda params: prefill(cfg, params, tok, valid)[0][0])

    def program(params):
        return _logprobs(run(params))

    def reference(params):
        return np.asarray(bench.forward_logprobs(
            shapes, as_reference(params), prompt))

    np.testing.assert_allclose(program(shut), program(lost), atol=1e-5)
    np.testing.assert_allclose(reference(shut), reference(lost), atol=1e-5)
    np.testing.assert_allclose(program(shut), reference(shut), atol=ATOL)
    assert np.abs(program(shut) - program(drawn)).max() > 1e-2
    assert np.abs(reference(shut) - reference(drawn)).max() > 1e-2


def test_the_gates_as_drawn_are_spread():
    """``W_g`` is twice the standard draw: over a normed stream the gates'
    logits spread about 1.8, so a gate is no constant near one half."""
    cfg = get_config("tiny-laguna")
    params = init_params(cfg, jax.random.PRNGKey(11), jnp.float32)
    h = jax.random.normal(jax.random.PRNGKey(2), (512, cfg.dim))
    gates = np.asarray(jax.nn.sigmoid(h @ params["attn_window"]["wg"][0]))
    assert 1.4 < np.log(gates / (1 - gates)).std() < 2.2
    assert 0.1 < np.mean((gates < 0.2) | (gates > 0.8)) < 0.5


# ---- yarn -------------------------------------------------------------------------

def test_yarns_frequencies_and_factor_are_the_published_formula():
    """Laguna's full layers: 64 rotary columns of 128, theta 500,000, factor
    128 over an original context of 8,192, beta 32 and 1.  Pair i turns
    ``8192 * theta**(-i/32) / 2pi`` times over the original context: pairs
    0-9 turn more than 32 times and keep their frequency, pairs 18-31 turn
    less than once and take it over 128, and a linear ramp lies between;
    sin and cos are multiplied by 0.1 ln 128 + 1."""
    from p2p_llm_tunnel_tpu.ops.rope import apply_rope, yarn_inv_freq

    cfg = get_config("laguna-s-2.1")
    assert (cfg.rotary_of("full"), cfg.rotary_of("window")) == (64, 128)
    assert cfg.yarn.attention_factor == pytest.approx(
        0.1 * math.log(128) + 1, abs=1e-12)
    got = np.asarray(yarn_inv_freq(64, cfg.rope_theta, cfg.yarn), np.float64)
    plain = 500000.0 ** (-np.arange(32) / 32.0)
    turns = 8192 * plain / (2 * math.pi)
    assert (turns[:10] > 32).all() and turns[10] < 32
    assert (turns[18:] < 1).all() and turns[17] > 1
    want = np.where(np.arange(32) <= 9, plain,
                    np.where(np.arange(32) >= 18, plain / 128, np.nan))
    ramp = (np.arange(32) - 9) / 9.0
    want = np.where(np.isnan(want), plain / 128 * ramp + plain * (1 - ramp),
                    want)
    np.testing.assert_allclose(got, want, rtol=2e-6)
    # the reference's own statement of it, written apart
    with open(os.path.join(REPO, "benchmarks", "configs",
                           "laguna-s-2.1.json")) as f:
        rope = json.load(f)["rope_parameters"]["full_attention"]
    np.testing.assert_allclose(bench.yarn_frequencies(64, rope), want,
                               rtol=1e-12)
    # as the program ropes a full layer's head: 64 columns turned, sin and
    # cos times the factor, 64 passed
    x = jax.random.normal(jax.random.PRNGKey(0), (1, 5, 2, 128))
    # (a float32 angle at position p is good to p x 1e-7)
    pos = jnp.array([[0, 1, 70, 900, 5000]])
    out = np.asarray(swa._rope(cfg, "full", x, pos))
    ref = np.asarray(bench.rope(x[0], pos[0], tuple(want),
                                cfg.yarn.attention_factor))
    np.testing.assert_allclose(out[0], ref, atol=5e-3)
    np.testing.assert_allclose(out[0, :4], ref[:4], atol=5e-4)
    np.testing.assert_array_equal(out[..., 64:], np.asarray(x[..., 64:]))
    # position 0 is scaled by the factor alone
    np.testing.assert_allclose(out[0, 0, :, :64],
                               np.asarray(x[0, 0, :, :64]) * 1.4852030263919618,
                               rtol=1e-6)
    # a window layer: all 128 columns, plainly at theta 10,000
    win = np.asarray(swa._rope(cfg, "window", x, pos))
    np.testing.assert_allclose(
        win, np.asarray(apply_rope(x, pos, 10000.0)), atol=1e-6)
    np.testing.assert_allclose(
        win[0], np.asarray(bench.rope(
            x[0], pos[0], tuple(10000.0 ** (-np.arange(64) / 64.0)), 1.0)),
        atol=5e-3)


# ---- the shares ------------------------------------------------------------------

def _layer(params, i=0):
    return jax.tree.map(lambda a: a[i], params["blocks"])


def test_the_eight_shares_add_up():
    """The eight shares of a routed layer (2 of 16 experts each), the shared
    expert counted once, add up to the uncut reference's layer."""
    whole = get_config("tiny-laguna")
    shapes = bench.shapes_of(_config(False))
    n = 18
    h = jax.random.normal(jax.random.PRNGKey(5), (2, 9, whole.dim))
    params = init_params(whole, jax.random.PRNGKey(11), jnp.float32)
    lw = {k: a[0] for k, a in as_reference(params)["moe"].items()}
    flat = h.reshape(n, whole.dim)
    with jax.default_matmul_precision("highest"):
        want = bench.routed(shapes, lw, flat, None)
        shared = bench.swiglu(flat, lw["shared_gate"], lw["shared_up"],
                              lw["shared_down"])
    total = -7 * shared  # each share adds the shared expert: counted once
    seen = 0
    for chip in range(8):
        cfg = replace(whole, layer_chips=8, chip_index=chip)
        lo, held = cfg.experts_held
        assert (lo, held) == (2 * chip, 2)
        # a share holds the whole model's experts [lo, lo + held) and the
        # router, the bias and the shared expert whole
        part = dict(_layer(params), **{
            k: params["blocks"][k][0, lo:lo + held]
            for k in moe.EXPERT_LEAVES})
        if chip in (0, 5):  # as the program draws a share from the seed
            drawn = init_params(cfg, jax.random.PRNGKey(11), jnp.float32)
            for k, a in _layer(drawn).items():
                np.testing.assert_array_equal(np.asarray(a),
                                              np.asarray(part[k]), k)
        with jax.default_matmul_precision("highest"):
            out, stats = moe.moe_mlp(cfg, part, h, lambda x: _act(cfg, x))
        total = total + out.reshape(n, whole.dim)
        seen += int(stats[1])
        assert int(stats[0]) == n * whole.n_experts_per_tok
    assert seen == n * whole.n_experts_per_tok  # every assignment held once
    np.testing.assert_allclose(np.asarray(total), np.asarray(want), atol=5e-5)
    # the scaling factor is in the weights: they sum to 2.5 a token
    _, top_w = moe.route(whole, _layer(params), flat)
    np.testing.assert_allclose(np.asarray(top_w.sum(-1)), 2.5, rtol=1e-5)
