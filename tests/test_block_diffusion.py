"""Generation by blocks (``tiny-sdar-moe``: SDAR at a size the CPU runs:
blocks of 4 filled in 2 denoise passes of 2, a block's commit riding the
first pass on the block after it, QK norm, 8 experts top-2) against its
plain reference, tests/block_diffusion_plain.py: the model's programs
through the cache.  The family through the engine is
tests/test_block_diffusion_engine.py; the published shape, the benchmark's
copy of the family, its configuration file and the tiny cell are
tests/test_block_diffusion_cell.py.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from p2p_llm_tunnel_tpu.models.block_decode import block_decode_step
from p2p_llm_tunnel_tpu.models.config import get_config
from p2p_llm_tunnel_tpu.models.transformer import (
    chunk_prefill_into_cache,
    decode_attention_branch,
    init_kv_cache,
    prefill,
    prefill_attention_branch,
)
from tests import block_diffusion_plain as plain
from tests.block_diffusion_tiny import (
    ATOL,
    BLOCK,
    GROUP,
    MAX_SEQ,
    ROWS,
    _prompt,
    model,
)


def _logprobs(logits):
    return np.asarray(jax.nn.log_softmax(logits, axis=-1))


_chunk_prefill = jax.jit(chunk_prefill_into_cache, static_argnums=(0,),
                         static_argnames=("kv_view",))
_block_step = jax.jit(block_decode_step, static_argnums=(0,),
                      static_argnames=("kv_view", "with_stats"))


def _chunk(cfg, params, cache, ids, start, slot, width=16):
    tok = jnp.zeros((1, width), jnp.int32).at[0, :len(ids)].set(
        jnp.array(ids, jnp.int32))
    return _chunk_prefill(cfg, params, tok, jnp.array([len(ids)]),
                          jnp.array([start]), cache, jnp.array([slot]),
                          kv_view=MAX_SEQ)[1]


def _row(values, slot, parked):
    """``values`` in row ``slot`` of ROWS rows, the others parked."""
    out = np.full((ROWS,) + np.shape(values), parked, np.int32)
    out[slot] = values
    return jnp.asarray(out)


# ---- the model's programs ----------------------------------------------------

def test_prefill_under_the_block_causal_mask_is_the_plain_forward(model):
    """Whole-prompt ``prefill`` (the einsum under ``j // 4 <= i // 4``;
    the causal flash kernel and the rows kernel are declined for this
    family by their own gates)."""
    cfg, params = model
    ids = _prompt(1, 20)
    tok = jnp.array([ids + [0] * 12], jnp.int32)
    logits, _, _ = prefill(cfg, params, tok, jnp.arange(32)[None] < 20)
    np.testing.assert_allclose(
        logits[0, :20], plain.forward(cfg, params, ids), atol=ATOL)
    forced = get_config("tiny-sdar-moe", flash_force=True, head_dim=128)
    assert prefill_attention_branch(forced, None, 128) == "einsum"
    assert decode_attention_branch(forced, None, 128) == "einsum"


@pytest.mark.parametrize("cut", [16, 28])
def test_chunk_prefill_and_block_passes_against_the_reference(model, cut):
    """36 prompt tokens prefilled as two segments (the second starts at
    ``cut``: inside the first pool block's successor or past it), then the
    passes of three blocks given the reference's own tokens: every pass's
    logits, both offsets, all columns; a pass with nothing pending leaves
    the cache bit-for-bit as it found it, and the first pass on a block
    writes the 4 rows a layer of the block before it."""
    cfg, params = model
    ids = _prompt(2, 48)
    want = np.asarray(plain.denoise_logprobs(cfg, params, ids))
    cache = init_kv_cache(cfg, ROWS, MAX_SEQ, jnp.float32)
    cache = _chunk(cfg, params, cache, ids[:cut], 0, 1, width=32)
    cache = _chunk(cfg, params, cache, ids[cut:36], cut, 1, width=32)
    for base in (36, 40, 44):
        blk = _row(ids[base - BLOCK:base + BLOCK], 1, 0)  # [behind | block]
        at = _row(base, 1, MAX_SEQ)
        for decided in (0, 2):
            behind = decided == 0 and base > 36
            logits, after = _block_step(
                cfg, params, cache, blk, at, _row(decided, 1, 0),
                _row(behind, 1, 0).astype(bool), kv_view=MAX_SEQ)
            np.testing.assert_allclose(
                _logprobs(logits[1]),
                want[base + decided: base + decided + GROUP], atol=ATOL)
            for key in cache:
                changed = np.argwhere(np.asarray(after[key] != cache[key]))
                if not behind:  # (b) nothing written
                    assert not len(changed), key
                    continue
                assert set(changed[:, 1]) == {1}, key  # the row's own slot
                assert set(changed[:, 2]) == set(range(base - BLOCK, base)), \
                    key
            cache = after


FUSED_CASES = {
    # rows: (tokens held before the pending block, the pending block is
    # there, offsets decided of the current block)
    "a-block-behind": [(16, True, 0)],
    "out-of-phase": [(24, True, 0), (12, False, 2), (8, False, 0)],
    "the-mask-id-behind": [(16, True, 0)],
    "int8-cache": [(16, True, 0), (20, False, 0)],
}


@pytest.mark.parametrize("case", sorted(FUSED_CASES))
def test_the_fused_pass_is_the_commit_and_then_the_first_denoise_pass(
        model, case):
    """One pass over ``[pending | current]`` against the two-pass form
    computed here: the pending block forwarded clean by itself (chunk
    prefill of its 4 tokens at its position: the same block-causal forward,
    another program), then the pass on the current block with nothing
    pending.  Equal logits at the deciding offsets and equal K/V written,
    for a row with a block behind it beside rows without (one of them in
    its second pass), a pending token equal to ``mask_token_id`` (a decided
    token, read as itself) and the int8 cache control (the current block's
    queries read the pending K/V quantised in both forms)."""
    cfg, params = model
    quant = case == "int8-cache"
    cache = init_kv_cache(cfg, ROWS, MAX_SEQ, jnp.float32, quant=quant)
    rows = FUSED_CASES[case]
    toks = np.zeros((ROWS, 2 * BLOCK), np.int32)
    base = np.full((ROWS,), MAX_SEQ, np.int32)
    decided = np.zeros((ROWS,), np.int32)
    behind = np.zeros((ROWS,), bool)
    seqs = []
    for slot, (held, pend, dec) in enumerate(rows):
        ids = _prompt(90 + slot, held + 2 * BLOCK)
        if case == "the-mask-id-behind":
            ids[held + 1] = cfg.mask_token_id
        cache = _chunk(cfg, params, cache, ids[:held], 0, slot, width=32)
        if not pend:  # the block behind is in the cache already
            cache = _chunk(cfg, params, cache, ids[held:held + BLOCK], held,
                           slot, width=BLOCK)
        toks[slot] = ids[held:]
        base[slot], decided[slot], behind[slot] = held + BLOCK, dec, pend
        seqs.append(ids)
    args = (jnp.asarray(toks), jnp.asarray(base), jnp.asarray(decided))
    fused, wrote = _block_step(cfg, params, cache, *args,
                               jnp.asarray(behind), kv_view=MAX_SEQ)
    two = cache
    for slot, (held, pend, _dec) in enumerate(rows):
        if pend:
            two = _chunk(cfg, params, two, seqs[slot][held:held + BLOCK],
                         held, slot, width=BLOCK)
    alone, same = _block_step(cfg, params, two, *args,
                              jnp.zeros((ROWS,), bool), kv_view=MAX_SEQ)
    for key in two:
        assert bool((same[key] == two[key]).all()), key
    live = len(rows)
    # (the int8 grid: a value that rounds the other way moves a key by a
    # 127th of its row's largest, and the logits by what that is worth)
    np.testing.assert_allclose(fused[:live], alone[:live],
                               atol=2e-2 if quant else ATOL)
    for key in two:
        a, b = np.asarray(wrote[key]), np.asarray(two[key])
        if a.dtype == np.int8:
            assert np.abs(a.astype(np.int32) - b).max() <= 1, key
        else:
            np.testing.assert_allclose(a, b, atol=ATOL, err_msg=key)
        changed = np.argwhere(a != np.asarray(cache[key]))
        for slot, (held, pend, _dec) in enumerate(rows):
            at = set(changed[changed[:, 1] == slot][:, 2])
            assert at == (set(range(held, held + BLOCK)) if pend else set()), \
                (key, slot)
    # and the reference, which keeps its separate commit forward
    for slot, (held, _pend, dec) in enumerate([] if quant else rows):
        want = np.asarray(plain.denoise_logprobs(cfg, params, seqs[slot]))
        first = held + BLOCK + dec
        np.testing.assert_allclose(_logprobs(fused[slot]),
                                   want[first:first + GROUP], atol=ATOL)


def test_a_token_equal_to_the_mask_id_is_a_token(model):
    """Whether an offset is decided is kept by offset: a decided token that
    happens to be ``mask_token_id`` conditions the next group as itself
    (here it is the same embedding, so the check is that nothing else
    changes), and an undecided offset's stale token is never read; nor is
    the half before the block where nothing is pending."""
    cfg, params = model
    ids = _prompt(3, 16)
    cache = _chunk(cfg, params, init_kv_cache(cfg, ROWS, MAX_SEQ,
                                              jnp.float32), ids, 0, 0)
    at, dec = _row(16, 0, MAX_SEQ), _row(2, 0, 0)
    none = jnp.zeros((ROWS,), bool)
    stale = [1, 2, 3, 4]
    a, _ = _block_step(cfg, params, cache, _row(stale + [7, 9, 11, 13], 0, 0),
                       at, dec, none, kv_view=MAX_SEQ)
    b, _ = _block_step(cfg, params, cache,
                       _row([5, 6, 7, 8, 7, 9, 200, 201], 0, 0), at, dec,
                       none, kv_view=MAX_SEQ)
    np.testing.assert_array_equal(np.asarray(a[0]), np.asarray(b[0]))
    c, _ = _block_step(cfg, params, cache, _row(stale + [7, 8, 11, 13], 0, 0),
                       at, dec, none, kv_view=MAX_SEQ)
    assert float(jnp.abs(a[0] - c[0]).max()) > 1e-3
