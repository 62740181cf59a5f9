"""The gated short convolution (LFM2's ``conv`` layers): a mixer whose
memory of a sequence is the last few inputs of a depthwise convolution
**and nothing else**: no keys, no values, no recurrence.

For an input ``u [B, T, hidden]`` (normed by the caller)::

    [B | C | x] = W_in u                      three thirds of 3 x hidden
    g_t = B_t * x_t
    c_t = sum_j w_j * g_{t - (taps - 1) + j}  depthwise, causal, ``taps`` a
                                              channel, g zero before the
                                              sequence, no bias, no activation
    out_t = W_out (C_t * c_t)

One :meth:`GatedShortConv.forward`, three uses with one meaning, as the
two Mamba mixers of ``nn/ssm.py`` have them:

* the whole sequence (``state=None``): it starts from zeros;
* a chunk from a carried ``state`` (:meth:`init_state`): ``{"conv": [B,
  (taps - 1) * hidden]}``, the tail: ``g`` of the last ``taps - 1``
  positions **side by side along the lanes**, the oldest first, and the
  state's only leaf;
* one token a row (:meth:`step`): the pool's decode step.

Both return the tail after the last **real** position: ``valid [B, T]``
false marks padding, which must trail the real positions of its row and
does not move the tail.  ``active`` and ``fresh`` as the Mamba mixers'
``step`` reads them.  The projections take their operands in the weights'
dtype and give float32; everything between them is float32; the tail is
kept in the dtype it was made in (the cache's).  A tail ``[B, taps - 1,
hidden]`` would put two rows on a tile's sixteen sublanes: compiled for a
v5e, a pool's ``[128, 2, 2048]`` leaf was copied into a layout of its own
and back in every decode step, sixteen copies a step
(``tests/test_tpu_compile.py``); two rows of 2,048 lanes side by side tile
as they lie.
"""

from __future__ import annotations

from typing import Any, Dict, Optional

import jax
import jax.numpy as jnp

from bigdl_tpu.core.module import Module, Parameter
from bigdl_tpu.nn.linear import Linear
from bigdl_tpu.nn.ssm import _last_real_inputs, _product

__all__ = ["GatedShortConv"]


class GatedShortConv(Module):
    """``taps [taps, hidden]``: tap ``j`` of every channel multiplies the
    input ``taps - 1 - j`` positions back (the last row the newest)."""

    def __init__(self, hidden_size: int, taps: int = 3):
        super().__init__()
        if taps < 2:
            raise ValueError("a short convolution has at least two taps")
        self.hidden_size, self.width = hidden_size, int(taps)
        self.taps = Parameter(jnp.full((taps, hidden_size), 1.0 / taps))
        self.in_proj = Linear(hidden_size, 3 * hidden_size, with_bias=False)
        self.out_proj = Linear(hidden_size, hidden_size, with_bias=False)

    def init_state(self, batch: int, dtype=jnp.float32) -> Dict[str, Any]:
        """Zeros: the tail in ``dtype``, the state's only leaf."""
        return {"conv": jnp.zeros(
            (batch, (self.width - 1) * self.hidden_size), dtype)}

    def _project(self, u):
        """``u [..., hidden] -> (g = B * x, C)``, both float32."""
        with jax.named_scope("shortconv/project"):
            h = self.hidden_size
            p = _product(u, self.in_proj)
            return p[..., :h] * p[..., 2 * h:], p[..., h:2 * h]

    def _rows(self, tail):
        """The tail's ``taps - 1`` rows ``[B, hidden]``, oldest first,
        float32."""
        h = self.hidden_size
        return [tail[:, j * h:(j + 1) * h].astype(jnp.float32)
                for j in range(self.width - 1)]

    def _gate_out(self, c, conv):
        with jax.named_scope("shortconv/gate_out"):
            return _product(c * conv, self.out_proj)

    def forward(self, u, state: Optional[Dict[str, Any]] = None, valid=None):
        """``u [B, T, hidden]`` from ``state`` (zeros when None) ->
        ``(out [B, T, hidden] float32, state after the row's last real
        position)``; ``valid [B, T]`` false marks trailing padding."""
        bsz, t, _ = u.shape
        if state is None:
            state = self.init_state(bsz, jnp.float32)
        if valid is None:
            valid = jnp.ones((bsz, t), bool)
        g, c = self._project(u)
        with jax.named_scope("shortconv/conv"):
            window = jnp.concatenate(
                [row[:, None] for row in self._rows(state["conv"])] + [g],
                axis=1)                       # [B, taps - 1 + T, hidden]
            tail = _last_real_inputs(window, valid, self.width - 1)
            w = self.taps.astype(jnp.float32)
            conv = sum(w[j] * window[:, j:j + t] for j in range(self.width))
        return self._gate_out(c, conv), \
            {"conv": tail.reshape(bsz, -1).astype(state["conv"].dtype)}

    def step(self, u, state: Dict[str, Any], active=None, fresh=None):
        """One token a row: ``u [B, 1, hidden]`` -> ``(out [B, 1, hidden],
        state)``.  A row whose ``active [B]`` is false only rides along
        and keeps its tail as it was; a row whose ``fresh [B]`` is true
        starts from zeros (its sequence's first token)."""
        g, c = self._project(u[:, 0])
        with jax.named_scope("shortconv/conv"):
            rows = self._rows(state["conv"])
            if fresh is not None:
                rows = [jnp.where(fresh[:, None], 0.0, row) for row in rows]
            rows.append(g)
            w = self.taps.astype(jnp.float32)
            conv = sum(w[j] * rows[j] for j in range(self.width))
            tail = jnp.concatenate(rows[1:], axis=-1).astype(
                state["conv"].dtype)
            if active is not None:
                tail = jnp.where(active[:, None], tail, state["conv"])
        return self._gate_out(c, conv)[:, None], {"conv": tail}
