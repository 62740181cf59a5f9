"""The selective state-space recurrence (Mamba-2) as the serving pool
runs it: one token a row over a pooled state, and a chunk of positions
from a carried state.

    S_t = exp(dt_t * A) * S_{t-1} + (dt_t * x_t) (x) B_t        y_t = S_t C_t

with one ``A`` a head, ``x_t [heads, P]``, and ``B_t``, ``C_t
[groups, N]`` shared by the heads of a group (head ``h`` reads group
``h // (heads // groups)``).

**The state lies ``[rows, heads, N, P]``, float32**: the head's width
``P`` (128) along the lanes and the state size ``N`` down the sublanes,
so that what a step needs of a row arrives as it lies: ``dt * x`` and the
decay as rows over the lanes, ``y`` as a sum down the sublanes.  (The
public description writes a head's state ``[P, N]``; the numbers are the
same.)

:func:`ssm_state_step` is the decode step's update of every row of a
layer.  A row's state is 4 MiB at the published widths, a pool's more
than a gigabyte, and the step has to read each once and write it once, in
place.  As ``jax.numpy`` it is that already: compiled for a v5e, XLA makes
the update and the product with ``C`` one multi-output fusion a layer over
the donated state, with no copy (``tests/test_tpu_compile.py`` holds the
pooled decode step to that; a Pallas program of the same update measured
0.634 ms a layer against this one's 0.637, PERF.md section 6, PR 38, and
was not kept).

:func:`ssm_chunk_scan` is prefill's: positions in sub-chunks of
``chunk``, inside a sub-chunk three matrix products (what every position
adds to every later one), the state passed from one sub-chunk to the
next.  Float32 throughout; plain ``jax.numpy``.

The **Mamba-1** recurrence (:func:`selective_state_step`,
:func:`selective_chunk_scan`) has no heads: every channel ``c`` of
``inner`` decays each of its ``N`` states on its own,

    S_t[n, c] = exp(dt_t[c] * A[n, c]) * S_{t-1}[n, c] + dt_t[c] x_t[c] B_t[n]
    y_t[c] = sum_n S_t[n, c] C_t[n]

with ``B_t``, ``C_t [N]`` shared by all channels.  A decay a channel and
state leaves nothing to turn into matrix products between positions, so
the chunk is a scan over its positions, each an element-wise update of
the carried state.  **The state lies ``[rows, N, inner]``, float32**:
the channels along the lanes and the ``N`` (16) states down the
sublanes (``[inner, N]``, as the public description writes it, would pad
16 to 128 lanes and cost eight times the bytes); the sum over ``n`` is
then a sum down the sublanes, and ``dt``, ``dt * x`` and ``y`` are rows
over the lanes as they come out of their projections.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp

__all__ = ["ssm_state_step", "ssm_chunk_scan", "selective_state_step",
           "selective_chunk_scan"]

_HI = jax.lax.Precision.HIGHEST


def ssm_state_step(state, decay, dx, b, c):
    """One position a row: ``state [rows, heads, N, P]`` float32,
    ``decay [rows, heads]`` (``exp(dt * A)``), ``dx [rows, heads, P]``
    (``dt * x``), ``b`` and ``c [rows, groups, N]`` -> ``(new state, y
    [rows, heads, P])`` with ``new = decay * state + b (x) dx`` and ``y =
    new . c``.  A row with ``decay`` 1 and ``dx`` 0 keeps its state bit
    for bit: that is how the caller leaves an idle row alone."""
    decay = decay.astype(jnp.float32)
    dx, b, c = (a.astype(jnp.float32) for a in (dx, b, c))
    per_group = state.shape[1] // b.shape[1]
    bh, ch = (jnp.repeat(a, per_group, axis=1) for a in (b, c))
    new = state * decay[:, :, None, None] \
        + bh[:, :, :, None] * dx[:, :, None, :]
    return new.astype(state.dtype), jnp.einsum("rhnp,rhn->rhp", new, ch,
                                               precision=_HI)


def ssm_chunk_scan(x, dt, a, b, c, state, chunk: int = 128):
    """``T`` positions from a carried state: ``x [B, T, heads, P]``, ``dt
    [B, T, heads]`` (0 at a position that must advance nothing: padding),
    ``a [heads]`` (negative), ``b`` and ``c [B, T, groups, N]``, ``state
    [B, heads, N, P]`` -> ``(y [B, T, heads, P], state after position T -
    1)``, all float32.

    Positions go in sub-chunks of ``chunk`` (the last padded with ``dt``
    0).  With ``cum`` the running sum of ``dt * a`` inside a sub-chunk,
    position ``l`` reads ``exp(cum_l) * (S_in C_l)`` of the state that
    entered and ``sum_{s <= l} exp(cum_l - cum_s) (C_l . B_s) dt_s x_s``
    of the positions before it, and the state that leaves is ``exp(cum_L)
    S_in + sum_s exp(cum_L - cum_s) B_s (x) dt_s x_s``: three products a
    sub-chunk, a scan over sub-chunks.  Every exponent is of a number
    that is not positive."""
    bsz, t, heads, p = x.shape
    groups, n = b.shape[2], b.shape[3]
    per_group = heads // groups
    size = max(1, min(int(chunk), t))
    count = -(-t // size)
    padded = count * size

    def chunks(arr):                 # [B, T, ...] -> [count, B, size, ...]
        arr = jnp.pad(arr.astype(jnp.float32),
                      ((0, 0), (0, padded - t)) + ((0, 0),) * (arr.ndim - 2))
        arr = arr.reshape((bsz, count, size) + arr.shape[2:])
        return jnp.moveaxis(arr, 1, 0)

    a = a.astype(jnp.float32)
    causal = jnp.tril(jnp.ones((size, size), bool))

    def one(s_in, args):
        x_c, dt_c, b_c, c_c = args
        cum = jnp.cumsum(dt_c * a, axis=1)                   # [B, L, H]
        dx = (dt_c[..., None] * x_c).reshape(
            bsz, size, groups, per_group, p)
        cum_g = cum.reshape(bsz, size, groups, per_group)
        s_g = s_in.reshape(bsz, groups, per_group, n, p)
        scores = jnp.einsum("blgn,bsgn->bgls", c_c, b_c, precision=_HI)
        cum_t = cum_g.transpose(0, 2, 3, 1)                  # [B, G, h, L]
        decay = jnp.exp(jnp.where(
            causal, cum_t[..., :, None] - cum_t[..., None, :], -jnp.inf))
        y = jnp.einsum("bghls,bsghp->blghp", scores[:, :, None] * decay,
                       dx, precision=_HI)
        y = y + jnp.exp(cum_g)[..., None] * jnp.einsum(
            "blgn,bghnp->blghp", c_c, s_g, precision=_HI)
        last = cum_g[:, -1]                                  # [B, G, h]
        left = jnp.exp(last[:, None] - cum_g)                # [B, s, G, h]
        s_out = jnp.exp(last)[..., None, None] * s_g + jnp.einsum(
            "bsgn,bsghp->bghnp", b_c, left[..., None] * dx, precision=_HI)
        return s_out.reshape(s_in.shape), y.reshape(bsz, size, heads, p)

    state, y = jax.lax.scan(one, state.astype(jnp.float32),
                            (chunks(x), chunks(dt), chunks(b), chunks(c)))
    y = jnp.moveaxis(y, 0, 1).reshape(bsz, padded, heads, p)[:, :t]
    return y, state


def selective_state_step(state, dt, a, dx, b, c):
    """One position a row of the Mamba-1 recurrence: ``state [rows, N,
    inner]`` float32, ``dt [rows, inner]`` (the step sizes), ``a [N,
    inner]`` (negative), ``dx [rows, inner]`` (``dt * x``), ``b`` and ``c
    [rows, N]`` -> ``(new state, y [rows, inner])`` with ``new = exp(dt *
    a) * state + b (x) dx`` and ``y = sum_n new * c``.  A row with ``dt``
    0 and ``dx`` 0 keeps its state bit for bit: that is how the caller
    leaves an idle row alone.  One element-wise pass over the state, which
    XLA makes one fusion over the donated leaf."""
    dt, a, dx, b, c = (v.astype(jnp.float32) for v in (dt, a, dx, b, c))
    new = jnp.exp(dt[:, None, :] * a[None]) * state \
        + b[:, :, None] * dx[:, None, :]
    return new.astype(state.dtype), jnp.sum(new * c[:, :, None], axis=1)


def selective_chunk_scan(x, dt, a, b, c, state, unroll: int = 8):
    """``T`` positions of the Mamba-1 recurrence from a carried state: ``x
    [B, T, inner]``, ``dt [B, T, inner]`` (0 at a position that must
    advance nothing: padding), ``a [N, inner]`` (negative), ``b`` and ``c
    [B, T, N]``, ``state [B, N, inner]`` -> ``(y [B, T, inner], state
    after position T - 1)``, all float32.  A scan over the positions, one
    :func:`selective_state_step` each (``unroll`` of them a turn of the
    loop)."""
    x, dt, b, c = (jnp.moveaxis(v.astype(jnp.float32), 1, 0)
                   for v in (x, dt, b, c))
    a = a.astype(jnp.float32)

    def one(s, at):
        x_t, dt_t, b_t, c_t = at
        return selective_state_step(s, dt_t, a, dt_t * x_t, b_t, c_t)

    state, y = jax.lax.scan(one, state.astype(jnp.float32), (x, dt, b, c),
                            unroll=max(1, min(int(unroll), x.shape[0])))
    return jnp.moveaxis(y, 0, 1), state
