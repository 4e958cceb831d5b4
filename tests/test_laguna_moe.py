"""Laguna-S-2.1's family (``tiny-laguna``: eight layers F WWW F WWW, 6 and 9
query heads on 3 KV heads, a gate a head, yarn on the full layers' leading
columns, QK norm, 16 experts top-3 beside a shared one, rings of 16
positions) against the benchmark's plain reference,
benchmarks/laguna_moe_reference.py, given the program's own weights: the
three serving programs at contexts under the window, between window and
ring and past the ring's wrap, a prefix-pool hit that restores a ring, the
rows kernel at a group of 6, the gate, yarn's frequencies, the shares of a
routed layer, the configuration file, and the tiny cell in one process.
"""

from __future__ import annotations

import json
import math
import os
import sys
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

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, os.path.join(REPO, "tests", "benchmarks"))
import tinycell_laguna as tiny  # noqa: E402

ROWS, MAX_SEQ, RING, WINDOW = 4, 128, 16, 8
# float32 program against the float32 reference at `highest`: sums taken in
# another order (a grouped product over sorted rows, a softmax over ring
# slots in another order than positions) differ in the last places of a
# float32; 8 layers and a scaling factor of 2.5 on the routed sum carry
# them to the fifth place of a log-probability.
ATOL = 3e-4


def _config(share: bool):
    """The tiny cell's file, or the same model whole."""
    config = dict(tiny.CONFIG)
    if not share:
        config.update(num_experts=16, layer_chips=1,
                      published_counts={"num_experts": 16})
    return config


def as_reference(params):
    """The program's parameter tree under the reference's names (the values
    as they are: a float32 model is compared in float32)."""
    out = {"embed": params["embed"], "lm_head": params["lm_head"]}
    for kind, group in swa.ATTN_GROUP.items():
        out[kind] = {k: params[group][k]
                     for k in ("wq", "wk", "wv", "wo", "wg")}
    out["dense"] = {k: params["dense_ffn"]["w_" + k]
                    for k in ("gate", "up", "down")}
    b = params["blocks"]
    out["moe"] = dict(
        {k: b["moe_" + k] for k in ("gate", "up", "down")},
        router=b["router"], bias=b["router_bias"],
        **{k: b[k] for k in ("shared_gate", "shared_up", "shared_down")})
    return out


@pytest.fixture(scope="module", params=["tiny-laguna", "tiny-laguna-ep2s"])
def model(request):
    cfg = get_config(request.param)
    params = init_params(cfg, jax.random.PRNGKey(11), jnp.float32)
    shapes = bench.shapes_of(_config(request.param.endswith("ep2s")))
    weights = as_reference(params)

    def want(tokens):
        return np.asarray(bench.forward_logprobs(shapes, weights, tokens))

    return cfg, params, want


def _prompt(seed, n):
    """Token ids under 250: the engine's default tokenizer has 259."""
    return list(np.random.RandomState(seed).randint(1, 250, size=n))


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


# ---- the engine -----------------------------------------------------------------

def _engine(model_name="tiny-laguna-ep2s", model_cfg=None, **kw):
    from p2p_llm_tunnel_tpu.engine.engine import EngineConfig, InferenceEngine

    return InferenceEngine(model_cfg=model_cfg, engine_cfg=EngineConfig(
        model=model_name, num_slots=2, max_seq=128, dtype="float32",
        decode_steps=2, **kw))


def test_a_prefix_hit_through_the_engine_reads_like_the_reference():
    """Prompts that share their first blocks, one after another through the
    engine (chunk prefill in segments of 16, the pool, decode bursts): the
    later ones hit the pool, are restored into rings, and every generated
    token's log-probability is the reference's."""
    from test_swa_moe import _generate

    from p2p_llm_tunnel_tpu.utils.metrics import global_metrics

    eng = _engine(mux=True, prefix_cache=True, prefix_pool_blocks=32,
                  prefill_chunk=16)
    assert eng._ring == RING and not eng.config_fences
    shapes = bench.shapes_of(dict(_config(True), vocab_size=259))
    base = _prompt(9, 70)
    prompts = [base, base[:55], base[:64] + _prompt(10, 13)]
    hit0 = global_metrics.counter("engine_prefix_hit_tokens_total")
    outs = _generate(eng, prompts)
    assert global_metrics.counter("engine_prefix_hit_tokens_total") - hit0 \
        == 48 + 64
    weights = as_reference(eng.params)
    for prompt, (tokens, values) in zip(prompts, outs):
        ref = np.asarray(bench.forward_logprobs(shapes, weights,
                                                prompt + tokens))
        n = len(prompt)
        np.testing.assert_allclose(
            values, [ref[n - 1 + j, t] for j, t in enumerate(tokens)],
            atol=ATOL)


def test_healthz_states_the_heads_by_kind_the_gate_the_ring_and_the_share():
    eng = _engine(prefix_cache=True, prefix_pool_blocks=8, mux=True,
                  prefill_chunk=16)
    section = eng._model_section()
    assert section["attention"] == {
        "query_heads": {"full": 6, "window": 9},
        "rotary_columns": {"full": 8, "window": 16},
        "gate": "per-head", "qk_norm": True}
    cache = section["cache"]
    assert cache["form"] == "window_rings+full"
    assert cache["ring_positions"] == RING and cache["window"] == WINDOW
    assert cache["kinds"]["window"]["layers"] == 6
    assert cache["bytes_per_token"] == tiny.CACHE_BYTES * 2  # float32 here
    assert section["experts"] == {"held": 8, "first": 0, "of": 16}
    assert section["layers"] == {"held": 8, "of": 8}
    assert section["expert_products"]["decode"] == moe.RAGGED
    # a model with one answer for both kinds says so in the same place
    mimo = _engine("tiny-swa-moe")._model_section()["attention"]
    assert mimo == {"query_heads": {"full": 4, "window": 4},
                    "rotary_columns": {"full": 8, "window": 8},
                    "gate": None, "qk_norm": False}


def test_the_branches_answer_from_the_shares_shapes():
    """What ``decode_attention_branch`` and ``grouped_product_branch`` answer
    for the cell's share on a TPU backend: a KV row of 8 x 128 = 1,024
    values is whole lane tiles, so the full layers take the rows kernel; 650
    sorted rows of a decode step (65 x 10) and 10,240 of a chunk dispatch
    (2 x 512 x 10) are under 64 a published expert, widths 3072 and 1024
    are whole tiles and the blocks fit VMEM, so both take the grouped
    kernel.  On the CPU both are the references."""
    from p2p_llm_tunnel_tpu.models.transformer import (
        decode_attention_branch,
        decode_kernel_decline,
    )
    from p2p_llm_tunnel_tpu.ops.pallas_grouped_matmul import GROUPED_KERNEL

    share = get_config("laguna-s-2.1-ep8s")
    chip = replace(share, flash_force=True)
    assert decode_kernel_decline(chip, None, 6144) is None
    assert decode_attention_branch(chip, None, 6144, None, 6144) \
        == "pallas-rows"
    assert decode_attention_branch(chip, None, 6144, "int8", 6144) == "einsum"
    assert decode_attention_branch(share, None, 6144, None, 6144) == "einsum"
    assert moe.grouped_product_branch(chip, None, 65) == GROUPED_KERNEL
    assert moe.grouped_product_branch(chip, None, 2 * 512) == GROUPED_KERNEL
    assert moe.grouped_product_branch(share, None, 65) == moe.RAGGED


def test_what_the_family_lacks_is_refused_at_start_up():
    with pytest.raises(ValueError, match=r"window rings beside full planes"
                                         r".* cannot be served with --"):
        _engine("tiny-laguna", quant="int8")


# ---- the presets, the benchmark's reference and its configuration -----------------

def test_the_published_preset_and_its_share():
    whole, share = get_config("laguna-s-2.1"), get_config("laguna-s-2.1-ep8s")
    assert (whole.n_layers, whole.n_experts, whole.vocab_size) == (
        48, 256, 100352)
    assert whole.attn_kinds == ("full", "window", "window", "window") * 12
    assert whole.layer_kinds == ("dense",) + ("moe",) * 47
    assert share.attn_kinds == ("full", "window", "window", "window") * 2
    assert share.experts_held == (0, 32) and share.n_layers == 8
    assert share.vocab_size * 8 == whole.vocab_size
    same = {f: getattr(whole, f) for f in (
        "dim", "n_heads", "window_heads", "n_kv_heads", "head_dim",
        "v_head_dim", "ffn_dim", "moe_ffn_dim", "shared_expert_dim",
        "n_experts", "n_experts_per_tok", "n_shared_experts",
        "sliding_window", "rotary_dim", "window_rotary_dim", "rope_theta",
        "window_rope_theta", "yarn", "attn_gate", "qk_norm", "router_bias",
        "routed_scale", "router_score")}
    assert same == {f: getattr(share, f) for f in same}
    assert (whole.dim, whole.head_dim, whole.n_kv_heads) == (3072, 128, 8)
    assert (whole.heads_of("full"), whole.heads_of("window")) == (48, 72)
    assert (whole.ffn_dim, whole.moe_ffn_dim, whole.shared_expert_dim) == (
        12288, 1024, 1024)
    assert (whole.n_experts_per_tok, whole.routed_scale) == (10, 2.5)
    # window 512 + the cell's segments of 512
    assert share.ring_default(6144, 512) == 1024
    # the cut's parameters, by the shapes the program would draw
    shapes = jax.eval_shape(lambda: init_params(share, jax.random.PRNGKey(0)))
    count = {k: sum(int(np.prod(x.shape)) for x in jax.tree.leaves(v))
             for k, v in shapes.items()}
    assert count["attn_full"] == pytest.approx(2 * 44.18e6, rel=1e-3)
    assert count["attn_window"] == pytest.approx(6 * 63.13e6, rel=1e-3)
    assert count["dense_ffn"] == pytest.approx(113.2e6, rel=1e-3)
    assert count["blocks"] == pytest.approx(7 * 312.2e6, rel=1e-3)
    assert count["embed"] + count["lm_head"] == 2 * 12544 * 3072
    assert sum(count.values()) == pytest.approx(2.843e9, rel=1e-3)


@pytest.mark.parametrize("share", [False, True], ids=["whole", "share"])
def test_the_benchmarks_reference_draws_the_programs_model(share):
    """benchmarks/laguna_moe_reference.py draws the program's weights from
    the seed, whole and as a share; with 8-bit weights it reads apart."""
    config = _config(share)
    cfg = get_config("tiny-laguna-ep2s" if share else "tiny-laguna")
    shapes = bench.shapes_of(config)
    assert (shapes["heads_full"], shapes["heads_window"]) == (6, 9)
    assert (len(shapes["freqs_full"]), len(shapes["freqs_window"])) == (4, 8)
    weights = bench.make_weights(shapes, 5)
    mine = as_reference(init_params(cfg, jax.random.PRNGKey(5), jnp.bfloat16))
    for group, leaves in mine.items():
        for name, theirs in (leaves.items() if isinstance(leaves, dict)
                             else [(None, leaves)]):
            got = weights[group] if name is None else weights[group][name]
            np.testing.assert_array_equal(np.asarray(got, np.float32),
                                          np.asarray(theirs, np.float32))
    tokens = _prompt(3, 37)
    plain = np.asarray(bench.forward_logprobs(shapes, weights, tokens))
    rounded = np.asarray(bench.forward_logprobs(shapes, weights, tokens,
                                                weight_bits=8))
    assert 1e-3 < np.abs(rounded - plain).mean() < 0.5
    assert bench.cache_bytes_per_token(config) == tiny.CACHE_BYTES


def test_the_configuration_file_keeps_the_published_keys():
    with open(os.path.join(REPO, "benchmarks", "configs",
                           "laguna-s-2.1.json")) as f:
        body = json.load(f)
    with open("/opt/skills/guides/model-configs/architectures.jsonl") as f:
        row = next(row for row in map(json.loads, f)
                   if row["name"] == "Laguna-S-2.1")
    published = row["config"]
    assert body["source"] == row["source_url"]
    reduced = {"num_hidden_layers": 8, "num_experts": 32, "vocab_size": 12544}
    assert set(body["reduced"]) == set(reduced)
    for key, value in published.items():
        assert body[key] == reduced.get(key, value), key
    assert body["published_counts"] == {k: published[k] for k in reduced}
    assert body["layer_chips"] * body["num_experts"] == published["num_experts"]
    assert body["layer_chips"] * body["vocab_size"] == published["vocab_size"]
    assert set(body["assumed"]) >= {"router_weights", "qk_norm",
                                    "shared_expert", "selection_bias",
                                    "gate_weights", "rotary"}
    # 8 layers x 8 KV heads x (128 + 128) values x 2 B
    assert bench.cache_bytes_per_token(body) == 8 * 4096 == 32768
    shapes = bench.shapes_of(body)
    assert (shapes["held"], shapes["experts"], len(shapes["attn"])) == (
        32, 256, 8)
    share = get_config(body["serve"]["model"])
    assert share.experts_held == (shapes["first_held"], shapes["held"])
    assert share.vocab_size == shapes["vocab"]
    assert share.attn_kinds == shapes["attn"]
    assert share.layer_kinds == shapes["ffn"]
    assert (share.heads_of("full"), share.heads_of("window")) == (
        shapes["heads_full"], shapes["heads_window"])
    assert share.yarn.attention_factor == shapes["factor_full"]
    # what a slot holds at the file's serve shape: the second statement
    args = body["serve"]["args"]
    chunk = int(args[args.index("--prefill-chunk") + 1])
    ring = share.ring_default(body["serve"]["max_seq"], chunk)
    assert ring == 1024
    assert 2 * 4096 * body["serve"]["max_seq"] + 6 * 4096 * ring == 75497472


# ---- the tiny cell, in one process ----------------------------------------------

#: (int8 activations and int8 planes, which each start an engine of their
#: own: tests/benchmarks/test_bm_laguna_rehearsal.py, ``slow``)
TINY_CELL_MODES = {
    "stated": ({}, None),
    "weights": ({}, 8),
}


@pytest.mark.parametrize("mode", sorted(TINY_CELL_MODES))
def test_the_tiny_cell_is_correct_as_stated_and_not_under_a_control(mode):
    """tests/benchmarks/tinycell_laguna.py's cell (the ``tiny-laguna-ep2s``
    share in bfloat16 against benchmarks/laguna_moe_reference.py given the
    same share) through the engine in this process: what ``correct``
    compares, as stated and with the reference's own weights rounded to 8
    bits in the program's place.  The tolerances are the file's
    ``correct.limits``, each with its reason there.  (Through signal + serve + proxy: tests/benchmarks/
    test_bm_laguna_rehearsal.py, ``slow``.)"""
    from test_mla_moe import _ask_in_process

    from benchmarks import correctness, traffic
    from p2p_llm_tunnel_tpu.engine.engine import EngineConfig, InferenceEngine
    from p2p_llm_tunnel_tpu.engine.tokenizer import ByteTokenizer

    engine_args, weight_bits = TINY_CELL_MODES[mode]
    config, seed = tiny.CONFIG, 11
    limits = config["correct"]["limits"]
    vocab = config["vocab_size"]
    import tinycell

    plan = traffic.make_plan(dict(tinycell.TINY_CLOSED, name="t"), seed, 3,
                             vocab)
    seqs = correctness.sequences(plan, seed, vocab, 256)
    shapes = bench.shapes_of(config)
    weights = bench.make_weights(shapes, seed)
    stated = bench.cache_bytes_per_token(config)

    def scored(tokens, bits=None):
        """(one length, one program: the mask is causal, so what is added
        after a sequence moves nothing before it)"""
        padded = list(tokens) + [0] * (256 - len(tokens))
        return np.asarray(bench.forward_logprobs(
            shapes, weights, padded, weight_bits=bits))

    if weight_bits is None:
        class Words(ByteTokenizer):
            vocab_size = vocab

        eng = InferenceEngine(
            engine_cfg=EngineConfig(
                model=config["serve"]["model"], num_slots=4, max_seq=256,
                seed=seed, mux=True, prefix_cache=True, prefill_chunk=16,
                **engine_args),
            tokenizer=Words())
        _ask_in_process(eng, seqs)
        counted = eng._prefix_block_bytes / eng._prefix_block
    else:  # the reference in the program's place, its weights rounded
        import importlib.util

        spec = importlib.util.spec_from_file_location(
            "control", os.path.join(REPO, "benchmarks", "control.py"))
        control = importlib.util.module_from_spec(spec)
        spec.loader.exec_module(control)
        counted = stated
        for seq in seqs:
            control.pretend(seq)
            lp = scored(seq["tokens"], weight_bits)
            seq["system"] = [float(lp[p, t]) for p, t in seq["probes"]]
    reference = []
    for seq in seqs:
        lp = scored(seq["tokens"])
        reference.append([float(lp[p, t]) for p, t in seq["probes"]])
    numbers = correctness.compare(seqs, reference)
    said = []
    held = correctness.judge(numbers, limits, counted, stated, said.append)
    assert held is (mode == "stated"), "\n".join(said)
    assert counted == stated == tiny.CACHE_BYTES
    if mode != "stated":
        assert numbers["echo_prompt"]["mean_abs"] > limits["echo_prompt"], said
