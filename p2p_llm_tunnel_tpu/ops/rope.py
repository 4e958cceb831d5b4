"""Rotary position embeddings.

Computed from explicit position ids (not sequence offsets) so the same
function serves prefill (positions 0..T-1) and slot-batched decode (each
slot at its own cache length) — a requirement of the static-shape
continuous-batching design.
"""

from __future__ import annotations

import math

import jax.numpy as jnp


def yarn_inv_freq(head_dim: int, theta: float, yarn) -> jnp.ndarray:
    """``deepseek_yarn`` frequencies [head_dim/2] (``yarn``: a
    models.config.YarnRope).  Pair ``i`` turns ``original_max *
    theta**(-2i/d) / 2pi`` times over the original context; pairs that turn
    more than ``beta_fast`` times keep ``theta**(-2i/d)``, those under
    ``beta_slow`` take it divided by ``factor``, and a linear ramp over the
    pair index mixes the two between."""
    plain = 1.0 / (
        theta ** (jnp.arange(0, head_dim, 2, dtype=jnp.float32) / head_dim)
    )

    def pair_of(turns: float) -> float:
        return (head_dim * math.log(yarn.original_max / (turns * 2 * math.pi))
                / (2 * math.log(theta)))

    low = max(math.floor(pair_of(yarn.beta_fast)), 0)
    high = min(math.ceil(pair_of(yarn.beta_slow)), head_dim - 1)
    ramp = jnp.clip(
        (jnp.arange(head_dim // 2, dtype=jnp.float32) - low)
        / max(high - low, 1e-3), 0.0, 1.0)
    return plain / yarn.factor * ramp + plain * (1.0 - ramp)


def yarn_mscale(factor: float, mscale: float) -> float:
    return 0.1 * mscale * math.log(factor) + 1.0 if factor > 1 else 1.0


def yarn_score_scale(q_head_dim: int, yarn) -> float:
    """The softmax scale of a yarn-scaled latent attention:
    ``d**-0.5 * mscale(factor, mscale_all_dim)**2``."""
    scale = q_head_dim ** -0.5
    if yarn is not None and yarn.mscale_all_dim:
        scale *= yarn_mscale(yarn.factor, yarn.mscale_all_dim) ** 2
    return scale


def rope_table(positions: jnp.ndarray, head_dim: int, theta: float,
               inv_freq=None) -> tuple:
    """(sin, cos) tables for given positions; shapes [..., head_dim/2].
    ``inv_freq`` replaces the plain ``theta`` ladder (yarn)."""
    freqs = inv_freq if inv_freq is not None else 1.0 / (
        theta ** (jnp.arange(0, head_dim, 2, dtype=jnp.float32) / head_dim)
    )
    angles = positions[..., None].astype(jnp.float32) * freqs  # [..., D/2]
    return jnp.sin(angles), jnp.cos(angles)


def apply_rope(x: jnp.ndarray, positions: jnp.ndarray, theta: float,
               inv_freq=None, mscale: float = 1.0) -> jnp.ndarray:
    """Rotate q or k. x: [..., seq, heads, head_dim]; positions: [..., seq].

    Uses the interleaved-pair convention (x reshaped to pairs), matching the
    HF Llama "rotate_half" layout after de-interleave — self-consistent for
    training-free use and checkpoint loading handles layout conversion.
    """
    head_dim = x.shape[-1]
    sin, cos = rope_table(positions, head_dim, theta, inv_freq)
    sin = sin[..., None, :]  # broadcast over heads: [..., seq, 1, D/2]
    cos = cos[..., None, :]
    if mscale != 1.0:  # yarn's mscale / mscale_all_dim, where they differ
        sin, cos = sin * mscale, cos * mscale
    x1, x2 = jnp.split(x, 2, axis=-1)  # rotate-half convention
    rx1 = x1 * cos - x2 * sin
    rx2 = x2 * cos + x1 * sin
    out = jnp.concatenate([rx1, rx2], axis=-1)
    return out.astype(x.dtype)
