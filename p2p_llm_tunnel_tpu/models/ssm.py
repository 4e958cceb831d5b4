"""The Mamba-2 state-space mixer: a chunked scan for prefill, a one-token
update for decode, one recurrence.

For one sequence, ``u`` the layer's normed input (``H`` heads of ``P``, ``G``
groups that share ``B`` and ``C`` of ``N`` values, head ``h`` in group ``h //
(H / G)``, a causal depthwise convolution over ``K`` positions):

- ``[z | xBC | dt] = u W_in``, widths ``H P | H P + 2 G N | H``;
- ``xBC_t <- silu(b_c + sum_j w_c[j] * xBC_{t-K+1+j})`` (zeros before the
  sequence), then split into ``x_t [H, P]``, ``B_t [G, N]``, ``C_t [G, N]``;
- ``dt_t = softplus(dt_t + dt_bias) [H]``, ``A = -exp(A_log) [H]``;
- **``S_t = exp(dt_t A) S_{t-1} + (dt_t x_t) (x) B_t``** ``[H, P, N]``,
  ``y_t = S_t C_t + D x_t``;
- ``y <- RMSNorm_groups(y * silu(z); w)`` over ``G`` groups (gate first),
  ``out = y W_out``.

What a sequence carries from one dispatch to the next is ``S`` (float32) and
the convolution's last ``K - 1`` inputs (the activations' type): a slot's
recurrent state, held beside the KV planes (models/ssm_moe.py).
:func:`causal_conv` is the convolution over a segment; :func:`conv_step` is
one position of it over the tail as a slot holds it, the ``K - 1`` inputs
side by side in one row.

:func:`ssm_scan` is the recurrence over a segment in chunks of ``chunk``
positions (the SSD form: inside a chunk the outputs are one masked product,
``C_i . B_j`` weighted by the decay from ``j`` to ``i``; between chunks the
state is carried by a short scan) and equals the token-by-token recurrence
at any chunking (tests/test_ssm_moe.py); :func:`ssm_step` is one position of
it, elementwise in float32.  A position whose ``dt`` is 0 leaves the state
as it is, to the bit (``exp(0) S + 0``): padding and parked rows are masked
so, by the caller.  On the TPU decode's update does not run here: it is
``ops/pallas_ssm_step.py``'s kernel over the step's live rows of the stacked
leaf (``ssm_moe.state_update_branch``), and :func:`ssm_step` is the form
every other backend runs and the reference that kernel is held to
(tests/test_ssm_step_kernel.py).  The scan's products are asked in full
float32: they are a hundredth of the layer's projections, and the state
they make is what every later token reads.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp

_EXACT = jax.lax.Precision.HIGHEST


def causal_conv(conv_w, conv_b, tail, xbc, lengths):
    """The depthwise causal convolution and its carry.

    ``tail [B, K-1, C]``: the ``K - 1`` inputs before the segment (zeros at
    a sequence's start); ``xbc [B, T, C]``: the segment's, of which
    ``lengths [B]`` are real; ``conv_w [K, C]``, ``conv_b [C]``.  Returns
    (``silu(conv) [B, T, C]`` in ``xbc``'s type, the new tail: the last ``K
    - 1`` real inputs, the old tail's where the segment has fewer)."""
    t = xbc.shape[1]
    k = conv_w.shape[0]
    full = jnp.concatenate([tail.astype(xbc.dtype), xbc], axis=1)
    w = conv_w.astype(jnp.float32)
    acc = conv_b.astype(jnp.float32)[None, None, :]
    for j in range(k):
        acc = acc + w[j] * full[:, j:j + t].astype(jnp.float32)
    at = lengths[:, None] + jnp.arange(k - 1)[None, :]
    new_tail = jnp.take_along_axis(full, at[:, :, None], axis=1)
    return jax.nn.silu(acc).astype(xbc.dtype), new_tail


def conv_step(conv_w, conv_b, tail, xbc, live):
    """One position of :func:`causal_conv`, to its bits, over the tail as a
    slot holds it: ``tail [B, (K-1) * C]``, the ``K - 1`` inputs before the
    token side by side (the oldest first), ``xbc [B, C]`` the token's,
    ``live [B]`` the rows that take a token.  Returns (``silu(conv) [B, C]``
    in ``xbc``'s type, the new tail: a live row's shifted by one input, any
    other row's as it was)."""
    k, c = conv_w.shape
    full = jnp.concatenate([tail.astype(xbc.dtype), xbc], axis=1)
    w = conv_w.astype(jnp.float32)
    acc = conv_b.astype(jnp.float32)[None, :]
    for j in range(k):
        acc = acc + w[j] * full[:, j * c:(j + 1) * c].astype(jnp.float32)
    new_tail = jnp.where(live[:, None], full[:, c:], full[:, :(k - 1) * c])
    return jax.nn.silu(acc).astype(xbc.dtype), new_tail


def ssm_step(x, dt, a, bm, cm, state):
    """One position: ``x [B,H,P]``, ``dt [B,H]`` (after softplus; 0 leaves
    the state), ``a [H]`` (negative), ``bm``/``cm [B,G,N]``, ``state
    [B,H,P,N]`` -> (``y [B,H,P]`` float32 without the skip, new state in
    ``state``'s type)."""
    h, g = x.shape[1], bm.shape[1]
    x, dt = x.astype(jnp.float32), dt.astype(jnp.float32)
    b_h = jnp.repeat(bm.astype(jnp.float32), h // g, axis=1)  # [B,H,N]
    c_h = jnp.repeat(cm.astype(jnp.float32), h // g, axis=1)
    decay = jnp.exp(dt * a)[:, :, None, None]
    new = (decay * state.astype(jnp.float32)
           + (dt[:, :, None] * x)[..., None] * b_h[:, :, None, :])
    y = jnp.sum(new * c_h[:, :, None, :], axis=-1)
    return y, new.astype(state.dtype)


def ssm_scan(x, dt, a, bm, cm, state, chunk: int):
    """A segment: ``x [B,T,H,P]``, ``dt [B,T,H]`` (after softplus; 0 at a
    position that is padding), ``a [H]``, ``bm``/``cm [B,T,G,N]``, ``state
    [B,H,P,N]`` float32 before the segment -> (``y [B,T,H,P]`` float32
    without the skip, the state after it, float32).  ``T`` need not be whole
    chunks: the last one is padded with positions of ``dt`` 0."""
    b, t, h, p = x.shape
    g, n = bm.shape[2:]
    hg = h // g
    q = max(1, min(chunk, t))
    pad = -t % q
    if pad:
        x, dt, bm, cm = (jnp.pad(v, ((0, 0), (0, pad)) + ((0, 0),) * (v.ndim - 2))
                         for v in (x, dt, bm, cm))
    nc = (t + pad) // q
    f32 = jnp.float32
    xr = x.astype(f32).reshape(b, nc, q, g, hg, p)
    dtr = dt.astype(f32).reshape(b, nc, q, g, hg)
    br = bm.astype(f32).reshape(b, nc, q, g, n)
    cr = cm.astype(f32).reshape(b, nc, q, g, n)
    cum = jnp.cumsum(dtr * a.astype(f32).reshape(g, hg), axis=2)
    dtx = dtr[..., None] * xr                       # [b,c,q,g,hg,p]
    # inside a chunk: y_i += sum_{j<=i} exp(cum_i - cum_j) (C_i . B_j) dt_j x_j
    cb = jnp.einsum("bcign,bcjgn->bcgij", cr, br, precision=_EXACT)
    seg = cum[:, :, :, None] - cum[:, :, None, :]   # [b,c,i,j,g,hg]
    seen = (jnp.arange(q)[:, None] >= jnp.arange(q)[None, :])[
        None, None, :, :, None, None]
    decay = jnp.where(seen, jnp.exp(jnp.where(seen, seg, 0.0)), 0.0)
    w = jnp.moveaxis(cb, 2, 4)[..., None] * decay   # [b,c,i,j,g,hg]
    y = jnp.einsum("bcijgh,bcjghp->bcighp", w, dtx, precision=_EXACT)
    # what each chunk adds to the state, decayed to the chunk's end
    to_end = jnp.exp(cum[:, :, -1:] - cum)          # [b,c,q,g,hg]
    local = jnp.einsum("bcjgh,bcjghp,bcjgn->bcghpn", to_end, dtx, br,
                       precision=_EXACT)
    total = jnp.exp(cum[:, :, -1])                  # [b,c,g,hg]

    def carry(s, inp):
        tot, loc = inp
        return tot[..., None, None] * s + loc, s

    last, starts = jax.lax.scan(
        carry, state.astype(f32).reshape(b, g, hg, p, n),
        (jnp.moveaxis(total, 1, 0), jnp.moveaxis(local, 1, 0)))
    starts = jnp.moveaxis(starts, 0, 1)             # [b,c,g,hg,p,n]
    # the state a chunk starts from, seen through the decay up to i
    y = y + jnp.einsum("bcign,bcghpn->bcighp", cr, starts,
                       precision=_EXACT) * jnp.exp(cum)[..., None]
    y = y.reshape(b, t + pad, h, p)[:, :t]
    return y, last.reshape(b, h, p, n)


def gated_group_norm(y, z, weight, groups: int, eps: float):
    """``RMSNorm(y * silu(z); weight)`` over ``groups`` groups of the last
    axis (the gate first, the norm after), float32."""
    y = y.astype(jnp.float32) * jax.nn.silu(z.astype(jnp.float32))
    shape = y.shape
    yg = y.reshape(shape[:-1] + (groups, shape[-1] // groups))
    yg = yg * jax.lax.rsqrt(jnp.mean(yg * yg, axis=-1, keepdims=True) + eps)
    return yg.reshape(shape) * weight.astype(jnp.float32)
