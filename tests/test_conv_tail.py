"""The convolution's tail as lanes of a slot's row (ISSUE 47): the ``"conv"``
leaf is ``[Lm, rows, (K - 1) * C]``, decode's one-token convolution
(``ssm.conv_step``) reads and writes a slot's tail where it lies and is
``ssm.causal_conv`` at ``T = 1`` to the bit, and a tail goes from chunk
prefill through a snapshot into another slot and on through decode at the
tiny cells' shapes as the plain references say."""

from __future__ import annotations

import os
import sys
from dataclasses import replace

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from benchmarks import granite_hybrid_reference as granite_plain
from p2p_llm_tunnel_tpu.engine.prefix_cache import make_state_copy_ops
from p2p_llm_tunnel_tpu.models import ssm, ssm_moe
from p2p_llm_tunnel_tpu.models.config import get_config
from p2p_llm_tunnel_tpu.models.transformer import (
    chunk_prefill_into_cache,
    decode_step,
    init_kv_cache,
    init_params,
)
from p2p_llm_tunnel_tpu.ops.pallas_ssm_step import ELEMENTWISE, SSM_STEP_KERNEL
from tests import ssm_moe_plain

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, os.path.join(REPO, "tests", "benchmarks"))
import tinycell_granite  # noqa: E402
import tinycell_ssm  # noqa: E402

# (the float32 programs against float32 references at `highest`, as
# tests/test_ssm_moe.py and tests/test_granite_hybrid.py hold them)
ATOL = 2e-4


def _granite_wants(cfg, params, tokens):
    # (the reference's layout: its norms and ``D`` are ones and not stored)
    tree = {"embed": params["embed"],
            "mlp": {k: params["mlp"][k] for k in ("w_in", "w_out")},
            "attn": {k: params["attn"][k] for k in ("wq", "wk", "wv", "wo")},
            "mamba": {k: params["mamba"][k] for k in (
                "w_in", "conv_w", "conv_b", "w_out", "dt_bias", "a_log")}}
    return granite_plain.forward_logprobs(
        granite_plain.shapes_of(tinycell_granite.CONFIG), tree, tokens)


#: The tiny cells (their files imported, not edited) and each one's plain
#: reference on the program's parameter tree.
CELLS = {
    "tiny-ssm-moe": (tinycell_ssm.CONFIG, ssm_moe_plain.forward_logprobs),
    "tiny-ssm-mlp": (tinycell_granite.CONFIG, _granite_wants),
}


def _serve(config):
    """(model, rows, max_seq, segment) of a tiny cell's ``serve`` section:
    its slots and the scratch row, its ``--prefill-chunk``."""
    serve = config["serve"]
    args = dict(zip(serve["args"][::2], serve["args"][1::2]))
    return (serve["model"], int(args["--slots"]) + 1, int(serve["max_seq"]),
            int(args["--prefill-chunk"]))


def _widths():
    """(``K - 1``, ``C``) of granite-4.0-h-micro, of nemotron and of the
    tiny cells' presets."""
    tiny = [get_config(_serve(config)[0]) for config, _ in CELLS.values()]
    return [(3, 4352), (3, 6144)] + sorted(
        (cfg.ssm_conv - 1, cfg.ssm_conv_dim) for cfg in tiny)


@pytest.mark.parametrize("dtype", [jnp.bfloat16, jnp.float32],
                         ids=["bfloat16", "float32"])
@pytest.mark.parametrize("positions,width", _widths())
def test_the_one_token_convolution_is_causal_convs_bits(positions, width,
                                                        dtype, rows=9):
    """granite-4.0-h-micro's and nemotron's widths and the tiny cells':
    ``conv_step`` over ``[B, (K - 1) * C]`` against ``causal_conv`` over
    ``[B, K - 1, C]`` at ``T = 1`` (both compiled, as decode runs them): the
    outputs and the new tails equal to the bit, a live row's tail shifted
    by one input, a parked row's untouched."""
    k = positions + 1
    rng = np.random.RandomState(width)
    w = jnp.asarray(rng.randn(k, width), dtype)
    b = jnp.asarray(rng.randn(width), dtype)
    tail = jnp.asarray(rng.randn(rows, positions, width), dtype)
    x = jnp.asarray(rng.randn(rows, width), dtype)
    live = jnp.asarray(rng.rand(rows) < 0.6).at[0].set(True).at[1].set(False)
    want, want_tail = jax.jit(ssm.causal_conv)(
        w, b, tail, x[:, None], live.astype(jnp.int32))
    flat = tail.reshape(rows, -1)
    got, got_tail = jax.jit(ssm.conv_step)(w, b, flat, x, live)
    assert got.dtype == got_tail.dtype == dtype
    assert got_tail.shape == (rows, positions * width)
    np.testing.assert_array_equal(np.asarray(got), np.asarray(want[:, 0]))
    np.testing.assert_array_equal(
        np.asarray(got_tail), np.asarray(want_tail.reshape(rows, -1)))
    on = np.asarray(live)
    assert on.any() and not on.all()
    np.testing.assert_array_equal(np.asarray(got_tail)[~on],
                                  np.asarray(flat)[~on])
    np.testing.assert_array_equal(np.asarray(got_tail)[on, :-width],
                                  np.asarray(flat)[on, width:])
    np.testing.assert_array_equal(np.asarray(got_tail)[on, -width:],
                                  np.asarray(x)[on])


_chunk_prefill = jax.jit(chunk_prefill_into_cache, static_argnums=(0,),
                         static_argnames=("kv_view", "return_all_logits"))
_decode_step = jax.jit(decode_step, static_argnums=(0,),
                       static_argnames=("kv_view",))


def _logprobs(logits):
    return np.asarray(jax.nn.log_softmax(logits, axis=-1))


def round_trip(cfg, params, rows, max_seq, segment, tokens, first=1, then=3):
    """``tokens``' first two segments chunk-prefilled into slot ``first``
    (beside a padding row on the scratch slot, as the engine dispatches
    them), the slot's state saved as a snapshot and restored into slot
    ``then`` with the planes' rows, four decode steps there.  Returns (the
    log-probabilities of every position, the cache before the steps, after
    them)."""
    scratch = rows - 1
    cache = init_kv_cache(cfg, rows, max_seq, jnp.float32)
    out = []
    for start in (0, segment):
        tok = jnp.zeros((2, segment), jnp.int32).at[0].set(
            jnp.array(tokens[start:start + segment]))
        logits, cache = _chunk_prefill(
            cfg, params, tok, jnp.array([segment, 1]), jnp.array([start, 0]),
            cache, jnp.array([first, scratch]), kv_view=max_seq,
            return_all_logits=True)
        out.append(_logprobs(logits[0]))
    restore, save = make_state_copy_ops(ssm_moe.STATE_KEYS, 2)
    # (the snapshots as the engine makes them: the leaf's shape, the rows'
    # axis the store's)
    snaps = {k: jnp.zeros(cache[k].shape[:1] + (3,) + cache[k].shape[2:],
                          cache[k].dtype) for k in ssm_moe.STATE_KEYS}
    snaps = save(snaps, cache, jnp.array([first, scratch]), jnp.array([2, 0]))
    cache = restore(cache, snaps, jnp.array([then, scratch]),
                    jnp.array([2, 0]))
    for name in ("k", "v"):
        cache[name] = cache[name].at[:, then].set(cache[name][:, first])
    before = cache
    for p in range(2 * segment, 2 * segment + 4):
        tok = jnp.zeros((rows,), jnp.int32).at[then].set(tokens[p])
        pos = jnp.full((rows,), max_seq).at[then].set(p)
        logits, cache = _decode_step(cfg, params, cache, tok, pos,
                                     kv_view=max_seq)
        out.append(_logprobs(logits[then])[None])
    return np.concatenate(out), before, cache


@pytest.mark.parametrize("update", [ELEMENTWISE, SSM_STEP_KERNEL])
@pytest.mark.parametrize("cell", sorted(CELLS))
def test_a_tail_goes_through_prefill_a_snapshot_and_decode(cell, update):
    """A tiny cell's shapes: the tail of two chunk-prefill segments, saved
    and restored into another slot by the snapshot copy programs (which
    slice the rows' axis, whatever follows it), then shifted by four decode
    steps (the state by either branch), against ONE forward of the cell's
    plain reference; the slots that took no token keep both leaves to the
    bit."""
    config, wants = CELLS[cell]
    model, rows, max_seq, segment = _serve(config)
    cfg = get_config(model)
    if update == SSM_STEP_KERNEL:
        cfg = replace(cfg, flash_interpret=True)
    assert ssm_moe.state_update_branch(cfg, None) == update
    params = init_params(cfg, jax.random.PRNGKey(11), jnp.float32)
    tokens = list(np.random.RandomState(5).randint(1, 250, 2 * segment + 4))
    got, before, after = round_trip(cfg, params, rows, max_seq, segment,
                                    tokens)
    lm = cfg.mixer_kinds.count("M")
    assert after["conv"].shape == (
        lm, rows, (cfg.ssm_conv - 1) * cfg.ssm_conv_dim)
    want = np.asarray(wants(cfg, params, tokens))
    np.testing.assert_allclose(got, want, atol=ATOL)
    parked = np.arange(rows) != 3
    for name in ssm_moe.STATE_KEYS:
        np.testing.assert_array_equal(np.asarray(after[name])[:, parked],
                                      np.asarray(before[name])[:, parked])
        assert (np.asarray(after[name])[:, 3]
                != np.asarray(before[name])[:, 3]).any()
    np.testing.assert_array_equal(np.asarray(before["conv"])[:, 3],
                                  np.asarray(before["conv"])[:, 1])
