"""What the files of ``tiny-mla-moe``'s tests share (tests/test_mla_moe.py, the
programs; tests/test_mla_moe_engine.py; tests/test_mla_moe_cell.py): the
sizes, the tolerance and the helpers that more than one of them calls.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from p2p_llm_tunnel_tpu.models.config import get_config
from p2p_llm_tunnel_tpu.models.transformer import (
    chunk_prefill_into_cache,
    decode_step,
    init_params,
    prefill,
    prefill_into_cache,
)


ROWS, MAX_SEQ = 4, 64
# float32 program against the float32 reference at `highest`: sums taken in
# another order (a grouped product over sorted rows, the absorbed form's
# folded query) differ in the last places of a float32.
ATOL = 2e-4


@pytest.fixture(scope="module", params=["tiny-mla-moe", "tiny-mla-moe-ep2s"])
def model(request):
    cfg = get_config(request.param)
    return cfg, init_params(cfg, jax.random.PRNGKey(11), jnp.float32)


def _prompt(seed, n):
    return list(np.random.RandomState(seed).randint(1, 500, size=n))


# (one trace a shape: an eager program is a compile an operation, and its layer
# scan is traced and compiled anew at every call)
_prefill = jax.jit(prefill, static_argnums=(0,))
_prefill_into_cache = jax.jit(prefill_into_cache, static_argnums=(0,),
                              static_argnames=("return_prompt_logprobs",))
_chunk_prefill = jax.jit(chunk_prefill_into_cache, static_argnums=(0,),
                         static_argnames=("kv_view",))
_decode_step = jax.jit(decode_step, static_argnums=(0,),
                       static_argnames=("kv_view", "with_stats"))


def _whole(cfg, params, cache, prompt, slot, **kw):
    width = 16 * -(-len(prompt) // 16)
    tok = jnp.zeros((1, width), jnp.int32).at[0, :len(prompt)].set(
        jnp.array(prompt))
    return _prefill_into_cache(cfg, params, tok, jnp.array([len(prompt)]),
                               cache, jnp.array([slot]), **kw)
