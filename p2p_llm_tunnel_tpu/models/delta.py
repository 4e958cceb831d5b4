"""The gated delta rule: a matrix state a head, a chunked form for prefill, a
one-token update for decode, one recurrence (``Olmo-Hybrid``'s
``linear_attention`` layers; the reader's torch form is ``transformers``'
``torch_recurrent_gated_delta_rule`` / ``torch_chunk_gated_delta_rule``).

For one sequence and head, ``q_t, k_t [Dk]`` (each over its L2 norm, ``q``
times ``Dk ** -0.5``: :func:`unit`), ``v_t [Dv]``, a write strength ``beta_t``
(in ``[0, 2]`` where the model allows negative eigenvalues) and a log-decay
``g_t <= 0``; the state ``S [Dk, Dv]``, float32, from zeros:

- **``S <- e^g S``; ``d = beta (v - S^T k)``; ``S <- S + k (x) d``; ``o =
  S^T q``.**

What a sequence carries from one dispatch to the next is ``S`` and the
convolution's tail (``models/ssm.causal_conv`` / ``conv_step`` over ``q | k |
v`` side by side, no bias), a slot's recurrent state beside the KV planes
(models/ssm_moe.py).  A position whose ``g`` and ``beta`` are 0 leaves the
state as it is, to the bit (``1 * S + k (x) 0``): padding and parked rows are
masked so, by the caller.

:func:`delta_scan` is the recurrence over a segment in chunks of ``chunk``
positions: inside a chunk ``(I + tril(diag(beta) K K^T . decay, -1))^-1`` is
applied to ``beta v`` and to ``beta k e^{cumsum g}`` (a unit-lower-triangular
solve a chunk and head), the chunk's output is read from the carried ``S``
and the corrected values, and ``S`` is carried on by a scan over the chunks;
it equals the token-by-token recurrence at any chunking
(tests/test_olmo_hybrid.py).  Its products are asked in full float32, as
``ssm.ssm_scan``'s are.

:func:`delta_step` is one position over the state AS THE LEAF HOLDS IT
(decode's update on a CPU backend, under a mesh and with ``cfg.flash`` off,
and the reference that the TPU's kernel over the step's live rows,
``ops/pallas_delta_step.py``, is held to: ``ssm_moe.state_update_branch``),
``[B, H, Dk / f, f * Dv]``: ``f`` (:func:`pack`) rows of a head's ``[Dk,
Dv]`` side by side on the lanes, the same bytes in the same order, so that
the leaf's last axis is whole lane tiles of 128 where ``Dv`` is not (192: two
rows are 384 = 3 tiles; an axis of 192 is padded to 256 on the chip, a third
more bytes a slot and a step) and no step reshapes the state.  The update is
elementwise over that layout: ``k`` is spread over the lanes of its rows
(:func:`_rows_on_lanes`), ``d`` repeated ``f`` times, and the two sums over
``Dk`` (``S^T k``, ``S^T q``) are sums over the sublanes whose ``f`` parts
are added after.  The output is ``e^g S^T q + (k . q) d``, which is ``(e^g S
+ k (x) d)^T q`` read from the OLD state: one pass reads ``S`` for both sums,
a second writes the new one (in XLA, over every row of the slice; the kernel
keeps a live row's block in VMEM between the two: one read, one write).
"""

from __future__ import annotations

import math

import jax
import jax.numpy as jnp

_EXACT = jax.lax.Precision.HIGHEST
#: eps inside the L2 norm of a head's query and key.
UNIT_EPS = 1e-6


def pack(dk: int, dv: int) -> int:
    """Rows of a head's ``[dk, dv]`` state that lie side by side in the
    leaf: the fewest that make the last axis whole lane tiles of 128, where
    ``dk`` is whole groups of them; else 1 (the state as ``[dk, dv]``)."""
    f = 128 // math.gcd(dv, 128)
    return f if dk % f == 0 else 1


def unit(q, k):
    """``q / |q| * Dk ** -0.5`` and ``k / |k|`` over the last axis,
    float32."""
    q, k = q.astype(jnp.float32), k.astype(jnp.float32)
    qn = q * jax.lax.rsqrt(jnp.sum(q * q, -1, keepdims=True) + UNIT_EPS)
    kn = k * jax.lax.rsqrt(jnp.sum(k * k, -1, keepdims=True) + UNIT_EPS)
    return qn * q.shape[-1] ** -0.5, kn


def _rows_on_lanes(x, f: int, dv: int):
    """``x [..., Dk]`` -> ``[..., Dk / f, f * dv]``: entry ``[p, c]`` is
    ``x[p * f + c // dv]``, what row ``p`` of the packed state meets.  A sum
    of ``f`` columns spread over the lanes, each times the 0/1 mask of its
    own ``dv`` lanes: outer products that fuse into the pass that reads
    them (a concatenation of the ``f`` parts stood as arrays of the
    state's own size beside it in the compiled step)."""
    parts = x.reshape(x.shape[:-1] + (x.shape[-1] // f, f))
    own = (jnp.arange(f * dv) // dv == jnp.arange(f)[:, None]).astype(x.dtype)
    return sum(parts[..., j:j + 1] * own[j] for j in range(f))


def delta_step(q, k, v, g, beta, state):
    """One position: ``q``/``k [B,H,Dk]`` (:func:`unit`'s), ``v [B,H,Dv]``,
    ``g``/``beta [B,H]`` (both 0 leave the state), ``state [B,H,Dk/f,f*Dv]``
    as the leaf holds it -> (``o [B,H,Dv]`` float32, the new state in
    ``state``'s type and layout)."""
    dv = v.shape[-1]
    f = state.shape[-1] // dv
    s = state.astype(jnp.float32)
    k_l, q_l = _rows_on_lanes(k, f, dv), _rows_on_lanes(q, f, dv)

    def over_dk(x):  # [B,H,Dk/f,f*Dv] summed over Dk -> [B,H,Dv]
        r = jnp.sum(x, axis=-2)
        return r.reshape(r.shape[:-1] + (f, dv)).sum(axis=-2)

    decay = jnp.exp(g.astype(jnp.float32))
    sk = decay[..., None] * over_dk(s * k_l)
    sq = decay[..., None] * over_dk(s * q_l)
    d = beta.astype(jnp.float32)[..., None] * (v.astype(jnp.float32) - sk)
    o = sq + jnp.sum(k * q, axis=-1, keepdims=True) * d
    new = decay[..., None, None] * s + k_l * jnp.tile(d, f)[..., None, :]
    return o, new.astype(state.dtype)


def delta_scan(q, k, v, g, beta, state, chunk: int):
    """A segment: ``q``/``k [B,T,H,Dk]`` (:func:`unit`'s), ``v [B,T,H,Dv]``,
    ``g``/``beta [B,T,H]`` (both 0 at a position that is padding), ``state
    [B,H,Dk,Dv]`` float32 before the segment -> (``o [B,T,H,Dv]`` float32,
    the state after it).  ``T`` need not be whole chunks: the last one is
    padded with positions that leave the state."""
    b, t, h, dk = k.shape
    dv = v.shape[-1]
    c = max(1, min(chunk, t))
    pad = -t % c
    f32 = jnp.float32
    q, k, v, g, beta = (x.astype(f32) for x in (q, k, v, g, beta))
    if pad:
        q, k, v, g, beta = (
            jnp.pad(x, ((0, 0), (0, pad)) + ((0, 0),) * (x.ndim - 2))
            for x in (q, k, v, g, beta))
    n = (t + pad) // c

    def chunks(x):  # [B,T,H,...] -> [n,B,H,c,...]
        x = x.reshape((b, n, c) + x.shape[2:])
        return jnp.moveaxis(jnp.moveaxis(x, 2, 3), 1, 0)

    q, k, v = chunks(q), chunks(k), chunks(v)          # [n,B,H,c,D]
    g, beta = chunks(g), chunks(beta)                  # [n,B,H,c]
    cum = jnp.cumsum(g, axis=-1)
    seg = cum[..., :, None] - cum[..., None, :]        # [n,B,H,i,j]
    i, j = jnp.arange(c)[:, None], jnp.arange(c)[None, :]
    decay = jnp.where(i >= j, jnp.exp(jnp.where(i >= j, seg, 0.0)), 0.0)
    k_beta, v_beta = k * beta[..., None], v * beta[..., None]
    kk = jnp.einsum("nbhid,nbhjd->nbhij", k_beta, k, precision=_EXACT)
    lower = jnp.where(i > j, kk * decay, 0.0) + jnp.eye(c, dtype=f32)
    # (I + A)^-1 [beta v | beta k e^cum]: one solve for both
    rhs = jnp.concatenate(
        [v_beta, k_beta * jnp.exp(cum)[..., None]], axis=-1)
    solved = jax.lax.linalg.triangular_solve(
        lower, rhs, left_side=True, lower=True, unit_diagonal=True)
    v_fix, k_cum = solved[..., :dv], solved[..., dv:]
    inside = jnp.where(
        i >= j, jnp.einsum("nbhid,nbhjd->nbhij", q, k, precision=_EXACT)
        * decay, 0.0)
    q_in = q * jnp.exp(cum)[..., None]
    k_out = k * jnp.exp(cum[..., -1:] - cum)[..., None]
    total = jnp.exp(cum[..., -1])

    def carry(s, x):
        v_fix, k_cum, inside, q_in, k_out, total = x
        v_new = v_fix - jnp.einsum("bhcd,bhde->bhce", k_cum, s,
                                   precision=_EXACT)
        o = (jnp.einsum("bhcd,bhde->bhce", q_in, s, precision=_EXACT)
             + jnp.einsum("bhij,bhje->bhie", inside, v_new,
                          precision=_EXACT))
        s = total[..., None, None] * s + jnp.einsum(
            "bhcd,bhce->bhde", k_out, v_new, precision=_EXACT)
        return s, o

    last, o = jax.lax.scan(carry, state.astype(f32),
                           (v_fix, k_cum, inside, q_in, k_out, total))
    o = jnp.moveaxis(jnp.moveaxis(o, 0, 1), 2, 3)      # [B,n,c,H,Dv]
    return o.reshape(b, t + pad, h, dv)[:, :t], last


def gated_head_norm(o, z, weight, eps: float):
    """``RMSNorm(o; weight) * silu(z)`` a head (the norm first, the gate
    after; one ``weight [Dv]`` for every head), float32: ``o``/``z
    [..., H, Dv]``."""
    o, z = o.astype(jnp.float32), z.astype(jnp.float32)
    o = o * jax.lax.rsqrt(jnp.mean(o * o, axis=-1, keepdims=True) + eps)
    return o * weight.astype(jnp.float32) * jax.nn.silu(z)
