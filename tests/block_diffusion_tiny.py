"""What the files of ``tiny-sdar-moe``'s tests share
(tests/test_block_diffusion.py, the programs;
tests/test_block_diffusion_engine.py and tests/test_block_diffusion_api.py;
tests/test_block_diffusion_cell.py): the sizes, the tolerance and the
helpers that more than one of them calls.
"""

from __future__ import annotations

import asyncio

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from p2p_llm_tunnel_tpu.models.config import get_config
from p2p_llm_tunnel_tpu.models.transformer import init_params
from tests import block_diffusion_plain as plain
from tests.moe_records import dispatches_closed


BLOCK, GROUP = 4, 2
ROWS, MAX_SEQ = 3, 128
# float32 program against the float32 reference at `highest`: sums taken in
# another order (a grouped product over sorted rows, a softmax in two parts
# over the cache prefix and the block) differ in the last places of a
# float32: 2e-6 is what they read, 1e-4 leaves the CPU's threads their
# order.  A bfloat16 product anywhere (8 bits of mantissa: 4e-3 a term)
# reads two orders of magnitude above it.
ATOL = 1e-4


@pytest.fixture(scope="module")
def model():
    cfg = get_config("tiny-sdar-moe", vocab_size=259, mask_token_id=258)
    return cfg, init_params(cfg, jax.random.PRNGKey(11), jnp.float32)


def _prompt(seed, n):
    """Token ids under 250: the engine's default tokenizer has 259."""
    return [int(t) for t in np.random.RandomState(seed).randint(1, 250, n)]


# ---- the engine -----------------------------------------------------------------

def _engine(model_cfg=None, **kw):
    from p2p_llm_tunnel_tpu.engine.engine import EngineConfig, InferenceEngine

    kw = {"num_slots": 3, "decode_steps": 2, "prefill_chunk": 16, **kw}
    return InferenceEngine(model_cfg=model_cfg, engine_cfg=EngineConfig(
        model="tiny-sdar-moe", max_seq=MAX_SEQ, dtype="float32", mux=True,
        prefix_cache=True, prefix_pool_blocks=32, **kw))


def _generate(eng, jobs, together=False, tops=3):
    """``jobs``: [(prompt, max_tokens, echo)] -> their events, one after
    another or all at once."""
    async def one(prompt, new, echo, wait=0.0):
        await asyncio.sleep(wait)
        return [ev async for ev in eng.generate(
            prompt, max_new_tokens=new, logprobs=tops, echo_logprobs=echo,
            stop_ids=())]

    async def main():
        await eng.start()
        try:
            if together:
                out = await asyncio.gather(*(
                    one(*job, wait=0.05 * i) for i, job in enumerate(jobs)))
            else:
                out = [await one(*job) for job in jobs]
            await dispatches_closed(eng)
            return out
        finally:
            await eng.stop()

    return asyncio.run(asyncio.wait_for(main(), 300))


def _check_against_reference(eng, prompt, events):
    """Every generated token's log-probability and its alternatives', and
    under echo every prompt token's, are the reference's for the sequence
    the engine produced."""
    tokens = [ev.token_id for ev in events]
    want = np.asarray(plain.denoise_logprobs(eng.mcfg, eng.params,
                                             prompt + tokens))
    n = len(prompt)
    for j, ev in enumerate(events):
        assert abs(ev.logprob - want[n + j, ev.token_id]) < ATOL, (n, j)
        for tok, value in ev.top_logprobs:
            assert abs(value - want[n + j, tok]) < ATOL, (n, j, tok)
    plps = events[0].prompt_logprobs
    if plps is not None:
        assert len(plps) == n
        np.testing.assert_allclose(
            plps[1:], [want[q, prompt[q]] for q in range(1, n)], atol=ATOL)
