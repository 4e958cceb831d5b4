"""The reference's process: makes the model from the seed, runs the plain
float32 forward over each sequence, writes its log-probability at each
probe (a position and a token: log P(token | tokens[..position])).  The
forward is that of the configuration's model family, a module the
configuration names (``correctness.family``); what that module says a token
caches is written beside the log-probabilities, so that the process that
started this one never has to import a family, or JAX.

Started by run.py after the serve process has exited, so on the chip it
has the chip to itself.  Each sequence is padded to the next multiple of
256 tokens before the forward (a causal model's earlier positions do not
see the padding), so the reference is one program for each such length,
compiled once and found in the cache afterwards.
"""

from __future__ import annotations

import json
import os
import sys

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

PAD = 256


def main(spec_path: str, out_path: str) -> int:
    with open(spec_path) as f:
        spec = json.load(f)
    import jax
    import numpy as np

    from benchmarks import correctness

    if jax.default_backend() != spec["platform"]:
        print(f"reference: JAX runs on {jax.default_backend()!r}, the run "
              f"needs {spec['platform']!r}", file=sys.stderr)
        return 3
    with open(spec["config"]) as f:
        config = json.load(f)
    reference = correctness.family(config, spec["data"])
    stated = correctness.cache_bytes_stated(config, spec["data"])
    shapes = reference.shapes_of(config)
    # cache every program, however quick its compile
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0)
    weights = reference.make_weights(shapes, spec["seed"])
    out = []
    for seq in spec["sequences"]:
        tokens = list(seq["tokens"])
        padded = tokens + [0] * (-len(tokens) % PAD)
        lp = reference.forward_logprobs(shapes, weights, padded,
                                        weight_bits=spec["weight_bits"])
        at = np.asarray(seq["probes"], np.int32).reshape(-1, 2)
        assert at[:, 0].max(initial=0) < len(tokens)
        out.append([float(x) for x in np.asarray(lp[at[:, 0], at[:, 1]])])
    with open(out_path + ".tmp", "w") as f:
        json.dump({"logprobs": out, "cache_bytes_per_token": stated}, f)
    os.replace(out_path + ".tmp", out_path)
    return 0


if __name__ == "__main__":
    sys.exit(main(sys.argv[1], sys.argv[2]))
