"""What the files of ``tiny-laguna``'s tests share (tests/test_laguna_moe.py,
the programs; tests/test_laguna_moe_engine.py;
tests/test_laguna_moe_cell.py): the sizes, the tolerance and the helpers
that more than one of them calls.
"""

from __future__ import annotations

import os
import sys

import numpy as np

from p2p_llm_tunnel_tpu.models import swa

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


def _prompt(seed, n):
    """Token ids under 250: the engine's default tokenizer has 259."""
    return list(np.random.RandomState(seed).randint(1, 250, size=n))
