"""What the files of ``tiny-delta-mlp``'s tests share
(tests/test_olmo_hybrid.py, the programs; tests/test_olmo_hybrid_engine.py;
tests/test_olmo_hybrid_cell.py): the sizes, the tolerance and the helpers
that more than one of them calls.
"""

from __future__ import annotations

import os
import sys
from dataclasses import replace

import jax.numpy as jnp
import numpy as np

from benchmarks import olmo_hybrid_reference as bench
from p2p_llm_tunnel_tpu.models import ssm_moe
from p2p_llm_tunnel_tpu.ops.pallas_delta_step import DELTA_STEP_KERNEL
from p2p_llm_tunnel_tpu.ops.pallas_ssm_step import ELEMENTWISE

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, os.path.join(REPO, "tests", "benchmarks"))
import tinycell_olmo as tiny  # noqa: E402


# float32 program against the float32 reference at `highest`: sums taken in
# another order (a chunked solve against the recurrence, the output read
# from the old state) differ in the last places of a float32; sixteen
# branches' norms carry them on.  A bfloat16 state reads 40 x this
# (test_a_narrower_state_fails_the_tolerance).
ATOL = 3e-4
SHAPES = bench.shapes_of(tiny.CONFIG)


def _as_reference(params):
    """The program's parameter tree in the reference's layout: its norms are
    ones and not stored there, and the six projections of a delta layer are
    matrices of their own."""
    assert all(float(jnp.abs(params[g]["norm"] - 1).max()) == 0
               for g in ("delta", "attn", "mlp"))
    h, dk, dv = SHAPES["d_heads"], SHAPES["dk"], SHAPES["dv"]
    d = params["delta"]
    cuts = np.cumsum([h * dk, h * dk, h * dv])
    wq, wk, wv, wz = jnp.split(d["w_in"], cuts, axis=-1)
    wa, wb = jnp.split(d["w_ab"], 2, axis=-1)
    return {
        "embed": params["embed"], "lm_head": params["lm_head"],
        "mlp": {k: params["mlp"][k] for k in ("w_in", "w_out")},
        "attn": {k: params["attn"][k] for k in ("wq", "wk", "wv", "wo")},
        "delta": {"wq": wq, "wk": wk, "wv": wv, "wz": wz, "wa": wa,
                  "wb": wb, "conv_w": d["conv_w"], "wo": d["w_out"],
                  "dt_bias": d["dt_bias"], "a_log": d["a_log"]},
    }


def _want(params, tokens):
    return np.asarray(bench.forward_logprobs(
        SHAPES, _as_reference(params), tokens))


def _prompt(seed, n):
    return list(np.random.RandomState(seed).randint(1, 250, size=n))


#: Decode's state update as ``delta.delta_step`` in XLA, and as the kernel
#: over the live rows (interpreted): ISSUE 52.
UPDATES = {"elementwise": {}, "kernel": {"flash_interpret": True}}


def _decoding(cfg, update):
    cfg = replace(cfg, **UPDATES[update])
    assert ssm_moe.state_update_branch(cfg, None) == (
        DELTA_STEP_KERNEL if update == "kernel" else ELEMENTWISE)
    return cfg
