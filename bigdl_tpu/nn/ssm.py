"""The Mamba-2 mixer: a selective state-space layer whose memory of a
sequence is a fixed-size state, not a row a position.

For an input ``u [B, T, hidden]`` (normed by the caller)::

    [z | xBC | dt] = (W_in u) * mu
    xBC = silu(conv(xBC))          depthwise, causal, ``conv_width`` taps, a bias
    x [heads, P], B [groups, N], C [groups, N] = split(xBC)
    dt = softplus(dt + dt_bias),  A = -exp(A_log)              one each a head
    S_t = exp(dt_t A) S_{t-1} + dt_t x_t (x) B_t,   y_t = S_t C_t + D x_t
    out = W_out( RMSNorm_grouped(y * silu(z)) )                gate, then norm

``mu`` spreads five multipliers over the segments ``z, x, B, C, dt`` of
the projection (Falcon-H1's µP); head ``h`` reads group ``h // (heads //
groups)``; tap ``k`` of the convolution multiplies position ``t -
(conv_width - 1) + k``; the gated norm normalises each group's channels
apart.

One :meth:`Mamba2Mixer.forward`, three uses with one meaning:

* the whole sequence (``state=None``): it starts from zeros;
* a chunk from a carried ``state`` (:meth:`init_state`): ``{"ssm": [B,
  heads, N, P] float32, "conv": [B, conv_width - 1, channels]}``, the
  recurrence's state and the last inputs of the convolution;
* one token a row (:meth:`step`): the pool's decode step.

Both return the state after the last **real** position: ``valid [B, T]``
false marks padding, which must trail the real positions of its row and
advances neither the recurrence (its ``dt`` is 0) nor the convolution's
inputs.  The projections take their operands in the weights' dtype and
give float32; everything between them is float32.  The recurrence's state
lies ``[heads, N, P]`` (``ops.ssm_kernels`` says why) and the
convolution's inputs ``[conv_width - 1, channels]``, channels along the
lanes.

:class:`Mamba1Mixer` is the older selective state-space layer (Mamba-1):
no heads, a decay for every channel and state, a step size through a
low-rank projection, ``B`` and ``C`` shared by all channels::

    [x | z] = W_in u
    x = silu(conv(x))              depthwise, causal, ``conv_width`` taps, a bias
    [d | B | C] = W_x x            dt_rank | N | N
    dt = softplus(W_dt d + b_dt),  A = -exp(A_log)             [inner, N]
    S_t[c, n] = exp(dt_t[c] A[c, n]) S_{t-1}[c, n] + dt_t[c] x_t[c] B_t[n]
    y_t[c] = sum_n S_t[c, n] C_t[n] + D[c] x_t[c]
    out = W_out( y * silu(z) )

It has the same three uses with the same meaning, the same ``valid``,
``active`` and ``fresh`` rules, and also returns ``y`` (before the gate,
``D x`` in it): what a later layer's gated memory unit reads.  Its state
lies ``{"ssm": [B, N, inner] float32, "conv": [B, conv_width - 1,
inner]}`` (``ops.ssm_kernels`` says why).
"""

from __future__ import annotations

from typing import Any, Dict, Optional, Sequence

import jax
import jax.numpy as jnp

from bigdl_tpu.core.module import Module, Parameter
from bigdl_tpu.nn.linear import Linear
from bigdl_tpu.ops import ssm_kernels

__all__ = ["Mamba2Mixer", "Mamba1Mixer"]


def _product(x, layer: Linear):
    return jnp.einsum("...i,oi->...o", x.astype(layer.weight.dtype),
                      layer.weight, preferred_element_type=jnp.float32)


class DepthwiseCausalConv(Module):
    """``weight [channels, width]``, ``bias [channels]``: every channel
    its own taps over its own past."""

    def __init__(self, channels: int, width: int):
        super().__init__()
        self.weight = Parameter(jnp.full((channels, width), 1.0 / width))
        self.bias = Parameter(jnp.zeros(channels))

    def forward(self, window):
        """``window [B, width - 1 + T, channels]`` (what came before,
        then the ``T`` positions) -> ``[B, T, channels]`` float32."""
        w = self.weight.astype(jnp.float32)
        width = w.shape[1]
        t = window.shape[1] - (width - 1)
        window = window.astype(jnp.float32)
        return sum(w[:, k] * window[:, k:k + t] for k in range(width)) \
            + self.bias.astype(jnp.float32)


def _last_real_inputs(window, valid, keep: int):
    """Of ``window [B, keep + T, channels]`` (what came before, then the
    ``T`` positions) the ``keep`` inputs before each row's first padded
    position: what the convolution of the next call starts from."""
    real = jnp.sum(valid, axis=1).astype(jnp.int32)
    return jax.vmap(lambda w, n: jax.lax.dynamic_slice_in_dim(
        w, n, keep, axis=0))(window, real)


class GatedGroupNorm(Module):
    """``RMSNorm`` over each group's channels of ``y * silu(z)``."""

    def __init__(self, channels: int, groups: int, eps: float):
        super().__init__()
        self.groups, self.eps = groups, eps
        self.weight = Parameter(jnp.ones(channels))

    def forward(self, y, z):
        g = y * jax.nn.silu(z)
        parts = g.reshape(g.shape[:-1] + (self.groups, -1))
        ms = jnp.mean(jnp.square(parts), axis=-1, keepdims=True)
        return (parts * jax.lax.rsqrt(ms + self.eps)).reshape(g.shape) \
            * self.weight.astype(jnp.float32)


class Mamba2Mixer(Module):
    def __init__(self, hidden_size: int, heads: int, head_dim: int,
                 groups: int, state_size: int, conv_width: int = 4,
                 chunk: int = 128, eps: float = 1e-5,
                 multipliers: Sequence[float] = (1.0,) * 5):
        super().__init__()
        if heads % groups:
            raise ValueError("heads must be a multiple of groups")
        if len(multipliers) != 5:
            raise ValueError("five multipliers: z, x, B, C, dt")
        self.heads, self.head_dim = heads, head_dim
        self.groups, self.state_size = groups, state_size
        self.conv_width, self.chunk = conv_width, chunk
        self.multipliers = tuple(float(m) for m in multipliers)
        self.inner = heads * head_dim
        self.channels = self.inner + 2 * groups * state_size
        self.in_proj = Linear(hidden_size,
                              self.inner + self.channels + heads,
                              with_bias=False)
        self.conv = DepthwiseCausalConv(self.channels, conv_width)
        self.dt_bias = Parameter(jnp.zeros(heads))
        self.A_log = Parameter(jnp.zeros(heads))
        self.D = Parameter(jnp.ones(heads))
        self.norm = GatedGroupNorm(self.inner, groups, eps)
        self.out_proj = Linear(self.inner, hidden_size, with_bias=False)

    # ---- the state ---------------------------------------------------------

    def init_state(self, batch: int, dtype=jnp.float32) -> Dict[str, Any]:
        """Zeros: the recurrence's state float32 whatever ``dtype`` is
        (it accumulates over thousands of steps), the convolution's last
        inputs in ``dtype``."""
        return {"ssm": jnp.zeros((batch, self.heads, self.state_size,
                                  self.head_dim), jnp.float32),
                "conv": jnp.zeros((batch, self.conv_width - 1,
                                   self.channels), dtype)}

    # ---- the pieces ----------------------------------------------------------

    def _project(self, u):
        with jax.named_scope("ssm/project"):
            m = self.multipliers
            gn = self.groups * self.state_size
            mu = jnp.concatenate([
                jnp.full((n,), v, jnp.float32) for n, v in zip(
                    (self.inner, self.inner, gn, gn, self.heads), m)])
            p = _product(u, self.in_proj) * mu
            return (p[..., :self.inner],
                    p[..., self.inner:self.inner + self.channels],
                    p[..., self.inner + self.channels:])

    def _split(self, xbc):
        gn = self.groups * self.state_size
        lead = xbc.shape[:-1]
        return (xbc[..., :self.inner].reshape(
                    lead + (self.heads, self.head_dim)),
                xbc[..., self.inner:self.inner + gn].reshape(
                    lead + (self.groups, self.state_size)),
                xbc[..., self.inner + gn:].reshape(
                    lead + (self.groups, self.state_size)))

    def _steps(self, dt):
        return jax.nn.softplus(dt + self.dt_bias.astype(jnp.float32))

    def _a(self):
        return -jnp.exp(self.A_log.astype(jnp.float32))

    def _gate_out(self, y, x, z):
        with jax.named_scope("ssm/gate_out"):
            y = y + self.D.astype(jnp.float32)[:, None] * x
            g = self.norm.forward(y.reshape(y.shape[:-2] + (self.inner,)), z)
            return _product(g, self.out_proj)

    # ---- the passes ----------------------------------------------------------

    def forward(self, u, state: Optional[Dict[str, Any]] = None, valid=None):
        """``u [B, T, hidden]`` from ``state`` (zeros when None) ->
        ``(out [B, T, hidden] float32, state after the row's last real
        position)``; ``valid [B, T]`` false marks trailing padding."""
        bsz, t, _ = u.shape
        keep = self.conv_width - 1
        if state is None:
            state = self.init_state(bsz, jnp.float32)
        if valid is None:
            valid = jnp.ones((bsz, t), bool)
        z, xbc, dt = self._project(u)
        with jax.named_scope("ssm/conv"):
            window = jnp.concatenate(
                [state["conv"].astype(jnp.float32), xbc], axis=1)
            conv = _last_real_inputs(window, valid, keep)
            x, b, c = self._split(jax.nn.silu(self.conv.forward(window)))
        with jax.named_scope("ssm/scan"):
            dt = jnp.where(valid[..., None], self._steps(dt), 0.0)
            y, ssm = ssm_kernels.ssm_chunk_scan(
                x, dt, self._a(), b, c, state["ssm"], self.chunk)
        new = {"ssm": ssm.astype(state["ssm"].dtype),
               "conv": conv.astype(state["conv"].dtype)}
        return self._gate_out(y, x, z), new

    def step(self, u, state: Dict[str, Any], active=None, fresh=None):
        """One token a row: ``u [B, 1, hidden]`` -> ``(out [B, 1, hidden],
        state)``.  A row whose ``active [B]`` is false only rides along
        and keeps its state as it was; a row whose ``fresh [B]`` is true
        starts from zeros (its sequence's first token)."""
        z, xbc, dt = self._project(u[:, 0])
        with jax.named_scope("ssm/conv"):
            before = state["conv"].astype(jnp.float32)
            if fresh is not None:
                before = jnp.where(fresh[:, None, None], 0.0, before)
            window = jnp.concatenate([before, xbc[:, None]], axis=1)
            x, b, c = self._split(jax.nn.silu(
                self.conv.forward(window)[:, 0]))
            conv = window[:, 1:].astype(state["conv"].dtype)
        with jax.named_scope("ssm/step"):
            dt = self._steps(dt)                              # [B, heads]
            decay, dx = jnp.exp(dt * self._a()), dt[..., None] * x
            if fresh is not None:
                decay = jnp.where(fresh[:, None], 0.0, decay)
            if active is not None:
                conv = jnp.where(active[:, None, None], conv, state["conv"])
                decay = jnp.where(active[:, None], decay, 1.0)
                dx = jnp.where(active[:, None, None], dx, 0.0)
                b = jnp.where(active[:, None, None], b, 0.0)
            ssm, y = ssm_kernels.ssm_state_step(state["ssm"], decay, dx, b, c)
        return self._gate_out(y, x, z)[:, None], {"ssm": ssm, "conv": conv}


class Mamba1Mixer(Module):
    def __init__(self, hidden_size: int, inner: int, state_size: int = 16,
                 dt_rank: Optional[int] = None, conv_width: int = 4):
        super().__init__()
        self.inner, self.state_size = inner, state_size
        self.dt_rank = -(-hidden_size // 16) if dt_rank is None else dt_rank
        self.conv_width = conv_width
        self.in_proj = Linear(hidden_size, 2 * inner, with_bias=False)
        self.conv = DepthwiseCausalConv(inner, conv_width)
        self.x_proj = Linear(inner, self.dt_rank + 2 * state_size,
                             with_bias=False)
        self.dt_proj = Linear(self.dt_rank, inner, with_bias=True)
        self.A_log = Parameter(jnp.log(jnp.broadcast_to(
            jnp.arange(1, state_size + 1, dtype=jnp.float32),
            (inner, state_size))))
        self.D = Parameter(jnp.ones(inner))
        self.out_proj = Linear(inner, hidden_size, with_bias=False)

    def init_state(self, batch: int, dtype=jnp.float32) -> Dict[str, Any]:
        """Zeros: the recurrence's state float32 whatever ``dtype`` is,
        the convolution's last inputs in ``dtype``."""
        return {"ssm": jnp.zeros((batch, self.state_size, self.inner),
                                 jnp.float32),
                "conv": jnp.zeros((batch, self.conv_width - 1, self.inner),
                                  dtype)}

    # ---- the pieces ----------------------------------------------------------

    def _project(self, u):
        with jax.named_scope("mamba1/project"):
            p = _product(u, self.in_proj)
            return p[..., :self.inner], p[..., self.inner:]

    def _selective(self, x):
        """``x [..., inner]`` after the convolution -> ``(dt [..., inner],
        B [..., N], C [..., N])``."""
        n, r = self.state_size, self.dt_rank
        p = _product(x, self.x_proj)
        dt = _product(p[..., :r], self.dt_proj) \
            + self.dt_proj.bias.astype(jnp.float32)
        return jax.nn.softplus(dt), p[..., r:r + n], p[..., r + n:]

    def _a(self):
        """``A [N, inner]``: as the state lies."""
        return -jnp.exp(self.A_log.astype(jnp.float32)).T

    def _gate_out(self, y, z):
        with jax.named_scope("mamba1/gate_out"):
            return _product(y * jax.nn.silu(z), self.out_proj)

    # ---- the passes ----------------------------------------------------------

    def forward(self, u, state: Optional[Dict[str, Any]] = None, valid=None):
        """``u [B, T, hidden]`` from ``state`` (zeros when None) ->
        ``(out [B, T, hidden] float32, state after the row's last real
        position, y [B, T, inner])``; ``valid [B, T]`` false marks
        trailing padding."""
        bsz, t, _ = u.shape
        keep = self.conv_width - 1
        if state is None:
            state = self.init_state(bsz, jnp.float32)
        if valid is None:
            valid = jnp.ones((bsz, t), bool)
        x, z = self._project(u)
        with jax.named_scope("mamba1/conv"):
            window = jnp.concatenate(
                [state["conv"].astype(jnp.float32), x], axis=1)
            conv = _last_real_inputs(window, valid, keep)
            x = jax.nn.silu(self.conv.forward(window))
            dt, b, c = self._selective(x)
        with jax.named_scope("mamba1/scan"):
            dt = jnp.where(valid[..., None], dt, 0.0)
            y, ssm = ssm_kernels.selective_chunk_scan(
                x, dt, self._a(), b, c, state["ssm"])
            y = y + self.D.astype(jnp.float32) * x
        new = {"ssm": ssm.astype(state["ssm"].dtype),
               "conv": conv.astype(state["conv"].dtype)}
        return self._gate_out(y, z), new, y

    def step(self, u, state: Dict[str, Any], active=None, fresh=None):
        """One token a row: ``u [B, 1, hidden]`` -> ``(out [B, 1, hidden],
        state, y [B, 1, inner])``.  A row whose ``active [B]`` is false
        only rides along and keeps its state as it was; a row whose
        ``fresh [B]`` is true starts from zeros (its sequence's first
        token)."""
        x, z = self._project(u[:, 0])
        with jax.named_scope("mamba1/conv"):
            before = state["conv"].astype(jnp.float32)
            if fresh is not None:
                before = jnp.where(fresh[:, None, None], 0.0, before)
            window = jnp.concatenate([before, x[:, None]], axis=1)
            x = jax.nn.silu(self.conv.forward(window)[:, 0])
            conv = window[:, 1:].astype(state["conv"].dtype)
            dt, b, c = self._selective(x)
        with jax.named_scope("mamba1/step"):
            dx, ssm = dt * x, state["ssm"]
            if fresh is not None:
                ssm = jnp.where(fresh[:, None, None], 0.0, ssm)
            if active is not None:
                conv = jnp.where(active[:, None, None], conv, state["conv"])
                dt = jnp.where(active[:, None], dt, 0.0)
                dx = jnp.where(active[:, None], dx, 0.0)
            ssm, y = ssm_kernels.selective_state_step(
                ssm, dt, self._a(), dx, b, c)
            y = y + self.D.astype(jnp.float32) * x
        return self._gate_out(y, z)[:, None], {"ssm": ssm, "conv": conv}, \
            y[:, None]
