"""Scaled dot-product attention kernels.

The reference computes attention as materialized [B, H, Tq, Tk] score
matrices through a graph of MM/SoftMax/Dropout layers
(reference: nn/Attention.scala — matmulLayer/softMaxLayer/dropLayer —
single-node, full materialization; SURVEY §5.7 notes the reference has no
flash/blockwise attention at all).

TPU-first redesign:

* :func:`flash_attention` — a Pallas TPU kernel implementing blockwise
  online-softmax attention (Flash-Attention-style).  K/V/bias are
  STREAMED block-by-block through the pallas grid (the kernel never
  holds a full [Tk, d] panel in VMEM — r03's ~4k ceiling is gone): the
  grid is (batch·heads, q-blocks, k-blocks) with the online-softmax
  (max, sum, acc) recurrence carried in VMEM scratch across the
  sequential k dimension, so HBM traffic is O(T) per query block and
  the QK^T / PV matmuls hit the MXU at [block_q, d] × [d, block_k]
  tile sizes while Pallas double-buffers the incoming K/V blocks.

  Training-ready: the function carries a ``jax.custom_vjp`` whose
  backward is itself blockwise Pallas — the forward additionally emits
  the per-row logsumexp, and the backward recomputes P tile-by-tile
  (dQ kernel streaming K/V; dK/dV kernel streaming Q/dO), never
  materializing the [Tq, Tk] score matrix.  The bias cotangent IS
  O(Tq·Tk); it is produced by a *separate* pallas_call so that when the
  bias is not differentiated (causal/padding masks — the common case)
  jit's dead-code elimination drops that kernel entirely.

* :func:`dot_product_attention` — the public entry: dispatches to the
  Pallas kernel on TPU (when shapes tile cleanly) and to a pure-XLA
  einsum implementation elsewhere; both paths are numerically equivalent
  (tested against each other and against torch SDPA, values and grads).

Shapes follow [batch, heads, length, head_dim] ("BHTD").
"""

from __future__ import annotations

import functools
import math
import os
from typing import NamedTuple, Optional

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

from jax.experimental.pallas import tpu as pltpu

__all__ = ["dot_product_attention", "flash_attention",
           "flash_attention_partial", "xla_attention", "decode_attention",
           "decode_key_block", "ragged_decode_attention"]

_NEG_INF = -1e9  # matches the reference's attention mask fill
                 # (nn/TransformerOperation.scala attentionBiasLowerTriangle)


# ---------------------------------------------------------------------------
# Pure-XLA reference path
# ---------------------------------------------------------------------------

def xla_attention(q, k, v, bias=None, *, causal: bool = False,
                  scale: Optional[float] = None):
    """Materialized attention: softmax(q k^T * scale + bias) v.

    q: [B, H, Tq, D]; k, v: [B, H, Tk, D]; bias broadcastable to
    [B, H, Tq, Tk].  Accumulation in fp32 regardless of input dtype.
    """
    *_, tq, d = q.shape
    tk = k.shape[-2]
    if scale is None:
        scale = 1.0 / (d ** 0.5)
    logits = jnp.einsum("bhqd,bhkd->bhqk", q, k,
                        preferred_element_type=jnp.float32)
    logits = logits * jnp.float32(scale)
    if bias is not None:
        logits = logits + bias.astype(jnp.float32)
    if causal:
        mask = jnp.tril(jnp.ones((tq, tk), bool), k=tk - tq)
        logits = jnp.where(mask, logits, _NEG_INF)
    weights = jax.nn.softmax(logits, axis=-1)
    return jnp.einsum("bhqk,bhkd->bhqd", weights.astype(v.dtype), v,
                      preferred_element_type=jnp.float32).astype(q.dtype)


# ---------------------------------------------------------------------------
# Pallas flash kernels — K/V streamed through the grid
# ---------------------------------------------------------------------------

def _auto_blocks(tq: int, tk: int, d: int, bias: bool = False):
    """Pick (block_q, block_k) for the flash kernels: the largest pair
    dividing the sequence lengths whose f32 score-shaped tiles fit the
    TPU scoped-VMEM budget.

    Block size is THE perf knob here.  At [128, 128] the grid for
    T=4096, B*H=64 is 65k programs of ~4 MFLOP each, so fixed
    per-program cost (DMA waits, grid bookkeeping) dominates the MXU
    work: measured 47x slower than [1024, 1024] on v5e.  Bigger tiles
    amortize that cost; the cap is the ~16 MiB scoped VMEM that must
    hold the f32 score-shaped intermediates (3 in the backward — p, dp,
    ds; with a bias, two more: the upcast bias tile and the dbias
    kernel's ds output) plus the streamed q/k/v/do tiles."""
    def divisors(t, choices):
        return [b for b in choices if t % b == 0]

    per_tile = 20 if bias else 12  # f32 score-shaped tiles, bytes/elem
    best = None
    for bq in divisors(tq, (1024, 768, 512, 384, 256, 128)) or [tq]:
        for bk in divisors(tk, (1024, 768, 512, 384, 256, 128)) or [tk]:
            vmem = per_tile * bq * bk + 6 * (bq + bk) * d
            if vmem > 14 * 2 ** 20:
                continue
            key = (bq * bk, bk)
            if best is None or key > best[0]:
                best = (key, bq, bk)
    if best is not None:
        return best[1], best[2]
    # nothing fits (odd lengths whose only listed divisor — the length
    # itself — blows the budget): fall back to the largest small
    # divisor, mirroring the ring's historic _pick_block tiling so a
    # forced kernel='flash' still runs instead of tripping the
    # divisibility assert
    fb = lambda t: next(b for b in (128, 64, 32, 16, 8, 4, 2, 1)
                        if t % b == 0)
    return fb(tq), fb(tk)


def _resolve_blocks(block_q, block_k, tq, tk, d, bias=False):
    """Fill None block sizes from :func:`_auto_blocks`; explicit sizes
    win.  Shared by every flash entry point so forward and backward
    kernels agree on the tiling."""
    if block_q is None or block_k is None:
        abq, abk = _auto_blocks(tq, tk, d, bias=bias)
        block_q = block_q or abq
        block_k = block_k or abk
    return int(block_q), int(block_k)


class _FlashCfg(NamedTuple):
    """Static kernel configuration (hashable: used as a custom_vjp
    nondiff argument)."""
    causal: bool
    scale: float
    block_q: int
    block_k: int
    interpret: bool


def _dimsem(*sems):
    """TPU compiler hint: which grid dims are parallel (megacore-
    splittable) vs sequential ("arbitrary" — carries a VMEM/output
    accumulator)."""
    return {"compiler_params": pltpu.CompilerParams(
        dimension_semantics=sems)}


def _scratch(shape):
    """VMEM scratch allocation (fp32 accumulator carried across the
    sequential k grid dimension)."""
    return pltpu.VMEM(shape, jnp.float32)


def _causal_mask(s, q_pos0, k_pos0, shape):
    q_pos = q_pos0 + jax.lax.broadcasted_iota(jnp.int32, shape, 0)
    k_pos = k_pos0 + jax.lax.broadcasted_iota(jnp.int32, shape, 1)
    return jnp.where(q_pos >= k_pos, s, _NEG_INF)


def _flash_fwd_kernel(q_ref, k_ref, v_ref, bias_ref, o_ref, lse_ref,
                      acc_ref, m_ref, l_ref, *, cfg: _FlashCfg,
                      nk: int):
    """One (bh, q-block, k-block) program.  Refs are VMEM tiles: q_ref
    [block_q, d]; k_ref/v_ref [block_k, d] (ONE streamed block);
    bias_ref [block_q, block_k] or None; o_ref [block_q, d]; lse_ref
    [block_q, 1].  acc/m/l are VMEM scratch carrying the online-softmax
    state across the sequential k dimension."""
    block_q, block_k = cfg.block_q, cfg.block_k
    q_idx = pl.program_id(1)
    k_idx = pl.program_id(2)

    @pl.when(k_idx == 0)
    def _init():
        acc_ref[...] = jnp.zeros_like(acc_ref)
        m_ref[...] = jnp.full_like(m_ref, _NEG_INF)
        l_ref[...] = jnp.zeros_like(l_ref)

    # causal: blocks entirely above the diagonal contribute nothing
    needed = True
    if cfg.causal:
        needed = k_idx * block_k <= q_idx * block_q + block_q - 1

    @pl.when(needed)
    def _body():
        # dots run in the INPUT dtype (bf16 inputs drive the MXU at
        # native rate — upcasting to f32 first runs the MXU at a
        # fraction of peak) with f32 accumulation; the scale applies to
        # the f32 product, matching xla_attention's ordering
        q = q_ref[...]
        k_blk = k_ref[...]
        v_blk = v_ref[...]
        s = jax.lax.dot_general(
            q, k_blk, (((1,), (1,)), ((), ())),
            preferred_element_type=jnp.float32) * cfg.scale
        if bias_ref is not None:
            s = s + bias_ref[...].astype(jnp.float32)
        if cfg.causal:
            s = _causal_mask(s, q_idx * block_q, k_idx * block_k,
                             (block_q, block_k))
        m_prev = m_ref[...][:, 0]
        l_prev = l_ref[...][:, 0]
        m_cur = jnp.max(s, axis=-1)
        m_new = jnp.maximum(m_prev, m_cur)
        p = jnp.exp(s - m_new[:, None])
        alpha = jnp.exp(m_prev - m_new)
        l_ref[...] = (l_prev * alpha + jnp.sum(p, axis=-1))[:, None]
        m_ref[...] = m_new[:, None]
        acc_ref[...] = acc_ref[...] * alpha[:, None] + jax.lax.dot_general(
            p.astype(v_blk.dtype), v_blk, (((1,), (0,)), ((), ())),
            preferred_element_type=jnp.float32)

    @pl.when(k_idx == nk - 1)
    def _finish():
        l = l_ref[...][:, 0]
        o_ref[...] = (acc_ref[...] / l[:, None]).astype(o_ref.dtype)
        lse_ref[...] = (m_ref[...][:, 0] + jnp.log(l))[:, None].astype(
            jnp.float32)


def _fwd_impl(q, k, v, bias, cfg: _FlashCfg):
    """Run the forward kernel; returns (out [B,H,Tq,D], lse [B*H,Tq,1])."""
    b, h, tq, d = q.shape
    tk = k.shape[2]
    block_q, block_k = cfg.block_q, cfg.block_k
    nk = tk // block_k

    qr = q.reshape(b * h, tq, d)
    kr = k.reshape(b * h, tk, d)
    vr = v.reshape(b * h, tk, d)

    in_specs = [
        pl.BlockSpec((None, block_q, d), lambda bh, i, j: (bh, i, 0)),
        pl.BlockSpec((None, block_k, d), lambda bh, i, j: (bh, j, 0)),
        pl.BlockSpec((None, block_k, d), lambda bh, i, j: (bh, j, 0)),
    ]
    args = [qr, kr, vr]
    if bias is not None:
        biasr = jnp.broadcast_to(bias, (b, h, tq, tk)).reshape(b * h, tq, tk)
        in_specs.append(pl.BlockSpec((None, block_q, block_k),
                                     lambda bh, i, j: (bh, i, j)))
        args.append(biasr)
        kern = functools.partial(_flash_fwd_kernel, cfg=cfg, nk=nk)
    else:
        def kern(q_ref, k_ref, v_ref, o_ref, lse_ref, acc, m, l):
            _flash_fwd_kernel(q_ref, k_ref, v_ref, None, o_ref, lse_ref,
                              acc, m, l, cfg=cfg, nk=nk)

    out, lse = pl.pallas_call(
        kern,
        grid=(b * h, tq // block_q, nk),
        in_specs=in_specs,
        out_specs=[
            pl.BlockSpec((None, block_q, d), lambda bh, i, j: (bh, i, 0)),
            pl.BlockSpec((None, block_q, 1), lambda bh, i, j: (bh, i, 0)),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((b * h, tq, d), q.dtype),
            jax.ShapeDtypeStruct((b * h, tq, 1), jnp.float32),
        ],
        scratch_shapes=[_scratch((block_q, d)), _scratch((block_q, 1)),
                        _scratch((block_q, 1))],
        interpret=cfg.interpret,
        **_dimsem("parallel", "parallel", "arbitrary"),
    )(*args)
    return out.reshape(b, h, tq, d), lse


def _recompute_p(q, k_blk, bias_blk, lse, q_pos0, k_pos0, cfg,
                 shape):
    """Shared tile recompute for the backward kernels: the normalized
    softmax tile P = exp(s - lse) (masked entries → exp(-1e9-lse) = 0).
    q/k are the raw input-dtype tiles — the dot runs at MXU-native rate
    and the scale applies to the f32 product (same order as forward)."""
    s = jax.lax.dot_general(q, k_blk, (((1,), (1,)), ((), ())),
                            preferred_element_type=jnp.float32) * cfg.scale
    if bias_blk is not None:
        s = s + bias_blk
    if cfg.causal:
        s = _causal_mask(s, q_pos0, k_pos0, shape)
    return jnp.exp(s - lse)


def _dq_accum(acc_ref, q_ref, k_ref, v_ref, bias_blk, do_ref,
              lse_ref, delta_ref, q_pos0, k_pos0, cfg: _FlashCfg):
    """Shared dQ tile step: acc += [P ∘ (dO V^T − Δ)] K (P recomputed
    from the q/k tiles + lse).  Used by the full backward (positions
    from program_id) and the ring partial backward (positions scalar-
    prefetched)."""
    q = q_ref[...]
    do = do_ref[...]
    lse = lse_ref[...].astype(jnp.float32)
    delta = delta_ref[...].astype(jnp.float32)
    k_blk = k_ref[...]
    v_blk = v_ref[...]
    p = _recompute_p(q, k_blk, bias_blk, lse, q_pos0, k_pos0, cfg,
                     (cfg.block_q, cfg.block_k))
    dp = jax.lax.dot_general(do, v_blk, (((1,), (1,)), ((), ())),
                             preferred_element_type=jnp.float32)
    ds = p * (dp - delta)
    acc_ref[...] = acc_ref[...] + jax.lax.dot_general(
        ds.astype(k_blk.dtype), k_blk, (((1,), (0,)), ((), ())),
        preferred_element_type=jnp.float32)


def _dkv_accum(dk_acc, dv_acc, k_ref, v_ref, q_ref, bias_blk, do_ref,
               lse_ref, delta_ref, q_pos0, k_pos0, cfg: _FlashCfg):
    """Shared dK/dV tile step: dV += P^T dO; dK += dS^T Q (the caller's
    finish step multiplies dK by `scale` once, so every dot here runs on
    raw input-dtype tiles at MXU-native rate)."""
    k = k_ref[...]
    v = v_ref[...]
    q_blk = q_ref[...]
    do_blk = do_ref[...]
    lse_blk = lse_ref[...].astype(jnp.float32)
    delta_blk = delta_ref[...].astype(jnp.float32)
    p = _recompute_p(q_blk, k, bias_blk, lse_blk, q_pos0, k_pos0, cfg,
                     (cfg.block_q, cfg.block_k))
    dv_acc[...] = dv_acc[...] + jax.lax.dot_general(
        p.astype(do_blk.dtype), do_blk, (((0,), (0,)), ((), ())),
        preferred_element_type=jnp.float32)
    dp = jax.lax.dot_general(do_blk, v, (((1,), (1,)), ((), ())),
                             preferred_element_type=jnp.float32)
    ds = p * (dp - delta_blk)
    dk_acc[...] = dk_acc[...] + jax.lax.dot_general(
        ds.astype(q_blk.dtype), q_blk, (((0,), (0,)), ((), ())),
        preferred_element_type=jnp.float32)


def _flash_dq_kernel(q_ref, k_ref, v_ref, bias_ref, do_ref, lse_ref,
                     delta_ref, dq_ref, acc_ref, *, cfg: _FlashCfg,
                     nk: int):
    """dQ for one (bh, q-block, k-block): K/V stream through the grid.
    dQ = scale * Σ_blocks [P ∘ (dO V^T − Δ)] K."""
    block_q, block_k = cfg.block_q, cfg.block_k
    q_idx = pl.program_id(1)
    k_idx = pl.program_id(2)

    @pl.when(k_idx == 0)
    def _init():
        acc_ref[...] = jnp.zeros_like(acc_ref)

    needed = True
    if cfg.causal:
        needed = k_idx * block_k <= q_idx * block_q + block_q - 1

    @pl.when(needed)
    def _body():
        bias_blk = None
        if bias_ref is not None:
            bias_blk = bias_ref[...].astype(jnp.float32)
        _dq_accum(acc_ref, q_ref, k_ref, v_ref, bias_blk, do_ref,
                  lse_ref, delta_ref, q_idx * block_q, k_idx * block_k,
                  cfg)

    @pl.when(k_idx == nk - 1)
    def _finish():
        dq_ref[...] = (acc_ref[...] * cfg.scale).astype(dq_ref.dtype)


def _flash_dkv_kernel(k_ref, v_ref, q_ref, bias_ref, do_ref, lse_ref,
                      delta_ref, dk_ref, dv_ref, dk_acc, dv_acc, *,
                      cfg: _FlashCfg, nq: int):
    """dK/dV for one (bh, k-block, q-block): Q/dO stream through the
    grid.  dV = P^T dO;  dK = scale * [P ∘ (dO V^T − Δ)]^T Q."""
    block_q, block_k = cfg.block_q, cfg.block_k
    k_idx = pl.program_id(1)
    q_idx = pl.program_id(2)

    @pl.when(q_idx == 0)
    def _init():
        dk_acc[...] = jnp.zeros_like(dk_acc)
        dv_acc[...] = jnp.zeros_like(dv_acc)

    needed = True
    if cfg.causal:
        # q blocks strictly before this k block are fully masked
        needed = q_idx * block_q + block_q - 1 >= k_idx * block_k

    @pl.when(needed)
    def _body():
        bias_blk = None
        if bias_ref is not None:
            bias_blk = bias_ref[...].astype(jnp.float32)
        _dkv_accum(dk_acc, dv_acc, k_ref, v_ref, q_ref, bias_blk,
                   do_ref, lse_ref, delta_ref, q_idx * block_q,
                   k_idx * block_k, cfg)

    @pl.when(q_idx == nq - 1)
    def _finish():
        dk_ref[...] = (dk_acc[...] * cfg.scale).astype(dk_ref.dtype)
        dv_ref[...] = dv_acc[...].astype(dv_ref.dtype)


def _flash_dbias_kernel(q_ref, k_ref, v_ref, bias_ref, do_ref, lse_ref,
                        delta_ref, ds_ref, *, cfg: _FlashCfg):
    """dBias tile [block_q, block_k] for one (bh, q-block, k-block):
    dS itself.  Materializes O(Tq·Tk) — only ever run when the bias is
    actually differentiated (a separate pallas_call so jit DCE removes
    it when the bias is a constant mask)."""
    block_q, block_k = cfg.block_q, cfg.block_k
    q_idx = pl.program_id(1)
    k_idx = pl.program_id(2)

    q = q_ref[...]
    do = do_ref[...]
    lse = lse_ref[...].astype(jnp.float32)
    delta = delta_ref[...].astype(jnp.float32)
    k_blk = k_ref[...]
    v_blk = v_ref[...]
    p = _recompute_p(q, k_blk, bias_ref[...].astype(jnp.float32), lse,
                     q_idx * block_q, k_idx * block_k, cfg,
                     (block_q, block_k))
    dp = jax.lax.dot_general(do, v_blk, (((1,), (1,)), ((), ())),
                             preferred_element_type=jnp.float32)
    ds_ref[...] = (p * (dp - delta)).astype(ds_ref.dtype)


def _bwd_prep(q, k, bias, out, do):
    """Shared backward prologue: flattened (B*H) views, Δ, broadcast bias.
    Δ_i = Σ_d dO_id · O_id  (= Σ_j P_ij dP_ij), computed once in XLA."""
    b, h, tq, d = q.shape
    tk = k.shape[2]
    dor = do.reshape(b * h, tq, d)
    delta = jnp.sum(dor.astype(jnp.float32)
                    * out.reshape(b * h, tq, d).astype(jnp.float32),
                    axis=-1, keepdims=True)
    biasr = None
    if bias is not None:
        biasr = jnp.broadcast_to(bias, (b, h, tq, tk)).reshape(b * h, tq, tk)
    return dor, delta, biasr


def _bwd_impl(q, k, v, bias, out, lse, do, cfg: _FlashCfg, *,
              prep=None):
    """Blockwise backward: returns (dq, dk, dv)."""
    b, h, tq, d = q.shape
    tk = k.shape[2]
    block_q, block_k = cfg.block_q, cfg.block_k
    nq, nk = tq // block_q, tk // block_k

    qr = q.reshape(b * h, tq, d)
    kr = k.reshape(b * h, tk, d)
    vr = v.reshape(b * h, tk, d)
    dor, delta, biasr = prep if prep is not None else _bwd_prep(
        q, k, bias, out, do)

    q_spec = pl.BlockSpec((None, block_q, d), lambda bh, i, j: (bh, i, 0))
    kv_spec = pl.BlockSpec((None, block_k, d), lambda bh, i, j: (bh, j, 0))
    row_spec = pl.BlockSpec((None, block_q, 1), lambda bh, i, j: (bh, i, 0))
    bias_spec = pl.BlockSpec((None, block_q, block_k),
                             lambda bh, i, j: (bh, i, j))

    # ---- dQ: grid (bh, q-block, k-block) ------------------------------
    dq_specs = [q_spec, kv_spec, kv_spec]
    dq_args = [qr, kr, vr]
    if biasr is not None:
        dq_specs.append(bias_spec)
        dq_args.append(biasr)
        dq_kern = functools.partial(_flash_dq_kernel, cfg=cfg, nk=nk)
    else:
        def dq_kern(q_ref, k_ref, v_ref, do_ref, lse_ref, delta_ref,
                    dq_ref, acc):
            _flash_dq_kernel(q_ref, k_ref, v_ref, None, do_ref, lse_ref,
                             delta_ref, dq_ref, acc, cfg=cfg, nk=nk)
    dq_args += [dor, lse, delta]
    dq_specs += [q_spec, row_spec, row_spec]
    dq = pl.pallas_call(
        dq_kern,
        grid=(b * h, nq, nk),
        in_specs=dq_specs,
        out_specs=q_spec,
        out_shape=jax.ShapeDtypeStruct((b * h, tq, d), q.dtype),
        scratch_shapes=[_scratch((block_q, d))],
        interpret=cfg.interpret,
        **_dimsem("parallel", "parallel", "arbitrary"),
    )(*dq_args)

    # ---- dK/dV: grid (bh, k-block, q-block) ---------------------------
    kblk_spec = pl.BlockSpec((None, block_k, d), lambda bh, j, i: (bh, j, 0))
    qstream = pl.BlockSpec((None, block_q, d), lambda bh, j, i: (bh, i, 0))
    rowstream = pl.BlockSpec((None, block_q, 1),
                             lambda bh, j, i: (bh, i, 0))
    bias_stream = pl.BlockSpec((None, block_q, block_k),
                               lambda bh, j, i: (bh, i, j))
    dkv_specs = [kblk_spec, kblk_spec, qstream]
    dkv_args = [kr, vr, qr]
    if biasr is not None:
        dkv_specs.append(bias_stream)
        dkv_args.append(biasr)
        dkv_kern = functools.partial(_flash_dkv_kernel, cfg=cfg, nq=nq)
    else:
        def dkv_kern(k_ref, v_ref, q_ref, do_ref, lse_ref, delta_ref,
                     dk_ref, dv_ref, dk_acc, dv_acc):
            _flash_dkv_kernel(k_ref, v_ref, q_ref, None, do_ref, lse_ref,
                              delta_ref, dk_ref, dv_ref, dk_acc, dv_acc,
                              cfg=cfg, nq=nq)
    dkv_args += [dor, lse, delta]
    dkv_specs += [qstream, rowstream, rowstream]
    dk, dv = pl.pallas_call(
        dkv_kern,
        grid=(b * h, nk, nq),
        in_specs=dkv_specs,
        out_specs=[kblk_spec, kblk_spec],
        out_shape=[jax.ShapeDtypeStruct((b * h, tk, d), k.dtype),
                   jax.ShapeDtypeStruct((b * h, tk, d), v.dtype)],
        scratch_shapes=[_scratch((block_k, d)), _scratch((block_k, d))],
        interpret=cfg.interpret,
        **_dimsem("parallel", "parallel", "arbitrary"),
    )(*dkv_args)

    return (dq.reshape(b, h, tq, d), dk.reshape(b, h, tk, d),
            dv.reshape(b, h, tk, d))


def _dbias_impl(q, k, v, bias, lse, cfg: _FlashCfg, *, prep):
    """Bias cotangent dS, reduced back to the (possibly broadcast) bias
    shape.  A standalone pallas_call: unused ⇒ DCE'd under jit."""
    b, h, tq, d = q.shape
    tk = k.shape[2]
    block_q, block_k = cfg.block_q, cfg.block_k

    qr = q.reshape(b * h, tq, d)
    kr = k.reshape(b * h, tk, d)
    vr = v.reshape(b * h, tk, d)
    dor, delta, biasr = prep

    q_spec = pl.BlockSpec((None, block_q, d), lambda bh, i, j: (bh, i, 0))
    kv_spec = pl.BlockSpec((None, block_k, d), lambda bh, i, j: (bh, j, 0))
    row_spec = pl.BlockSpec((None, block_q, 1), lambda bh, i, j: (bh, i, 0))
    tile = pl.BlockSpec((None, block_q, block_k),
                        lambda bh, i, j: (bh, i, j))

    ds = pl.pallas_call(
        functools.partial(_flash_dbias_kernel, cfg=cfg),
        grid=(b * h, tq // block_q, tk // block_k),
        in_specs=[q_spec, kv_spec, kv_spec, tile, q_spec, row_spec,
                  row_spec],
        out_specs=tile,
        out_shape=jax.ShapeDtypeStruct((b * h, tq, tk), jnp.float32),
        interpret=cfg.interpret,
        **_dimsem("parallel", "parallel", "parallel"),
    )(qr, kr, vr, biasr, dor, lse, delta)

    ds = ds.reshape(b, h, tq, tk)
    # un-broadcast: right-align the bias shape against [B, H, Tq, Tk]
    # (numpy broadcasting aligns trailing dims), then sum over every dim
    # the original bias had as 1 (or lacked entirely)
    aligned = (1,) * (4 - bias.ndim) + tuple(bias.shape)
    for axis, (full, orig) in enumerate(zip((b, h, tq, tk), aligned)):
        if orig == 1 and full != 1:
            ds = jnp.sum(ds, axis=axis, keepdims=True)
    while ds.ndim > bias.ndim:
        ds = jnp.squeeze(ds, axis=0)
    return ds.astype(bias.dtype)


# ---------------------------------------------------------------------------
# Partial (carry-in/carry-out) flash step — the ring-attention kernel
# ---------------------------------------------------------------------------

def _flash_partial_kernel(qoff_ref, koff_ref, q_ref, k_ref, v_ref,
                          acc_in, m_in, l_in, acc_out, m_out, l_out, *,
                          cfg: _FlashCfg):
    """One (bh, q-block, k-block) program merging THIS K/V chunk into a
    running online-softmax state.  qoff/koff are scalar-prefetched
    GLOBAL positions of the chunks (traced values from the ring's
    axis_index arithmetic).  The output refs double as accumulators —
    their block index is constant over the inner k dimension, so they
    stay VMEM-resident across it."""
    block_q, block_k = cfg.block_q, cfg.block_k
    i = pl.program_id(1)
    j = pl.program_id(2)

    @pl.when(j == 0)
    def _seed():
        acc_out[...] = acc_in[...].astype(jnp.float32)
        m_out[...] = m_in[...].astype(jnp.float32)
        l_out[...] = l_in[...].astype(jnp.float32)

    q_pos0 = qoff_ref[0] + i * block_q
    k_pos0 = koff_ref[0] + j * block_k
    needed = True
    if cfg.causal:
        needed = k_pos0 <= q_pos0 + block_q - 1

    @pl.when(needed)
    def _body():
        q = q_ref[...]
        k_blk = k_ref[...]
        v_blk = v_ref[...]
        s = jax.lax.dot_general(
            q, k_blk, (((1,), (1,)), ((), ())),
            preferred_element_type=jnp.float32) * cfg.scale
        if cfg.causal:
            s = _causal_mask(s, q_pos0, k_pos0, (block_q, block_k))
        m_prev = m_out[...][:, 0]
        l_prev = l_out[...][:, 0]
        m_cur = jnp.max(s, axis=-1)
        m_new = jnp.maximum(m_prev, m_cur)
        p = jnp.exp(s - m_new[:, None])
        alpha = jnp.exp(m_prev - m_new)
        l_out[...] = (l_prev * alpha + jnp.sum(p, axis=-1))[:, None]
        m_out[...] = m_new[:, None]
        acc_out[...] = acc_out[...] * alpha[:, None] + jax.lax.dot_general(
            p.astype(v_blk.dtype), v_blk, (((1,), (0,)), ((), ())),
            preferred_element_type=jnp.float32)


def flash_attention_partial(q, k, v, acc, m, l, *, q_offset, k_offset,
                            causal: bool = False,
                            scale: Optional[float] = None,
                            block_q: Optional[int] = None,
                            block_k: Optional[int] = None,
                            interpret: bool = False):
    """Merge blockwise attention of q [B,H,Tq,D] against ONE K/V chunk
    [B,H,Tk,D] into the running online-softmax state
    (acc [B,H,Tq,D] fp32, m/l [B,H,Tq] fp32); returns the updated
    state.  q_offset/k_offset are the chunks' global sequence positions
    (traced scalars fine — scalar-prefetched into the kernel), so the
    causal mask is exact across ring steps.  The caller finishes with
    ``out = acc / l[..., None]``.  Forward-only (the ring layer remats
    around it); no bias (the ring routes biased attention dense)."""
    b, h, tq, d = q.shape
    tk = k.shape[2]
    if scale is None:
        scale = 1.0 / (d ** 0.5)
    block_q, block_k = _resolve_blocks(block_q, block_k, tq, tk, d)
    assert tq % block_q == 0 and tk % block_k == 0, (tq, tk)
    cfg = _FlashCfg(causal=bool(causal), scale=float(scale),
                    block_q=int(block_q), block_k=int(block_k),
                    interpret=bool(interpret))
    qr = q.reshape(b * h, tq, d)
    kr = k.reshape(b * h, tk, d)
    vr = v.reshape(b * h, tk, d)
    accr = acc.reshape(b * h, tq, d).astype(jnp.float32)
    mr = m.reshape(b * h, tq, 1).astype(jnp.float32)
    lr = l.reshape(b * h, tq, 1).astype(jnp.float32)

    # with scalar prefetch, index maps receive the prefetch refs too
    q_spec = pl.BlockSpec((None, block_q, d),
                          lambda bh, i, j, *refs: (bh, i, 0))
    kv_spec = pl.BlockSpec((None, block_k, d),
                           lambda bh, i, j, *refs: (bh, j, 0))
    row_spec = pl.BlockSpec((None, block_q, 1),
                            lambda bh, i, j, *refs: (bh, i, 0))

    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=2,
        grid=(b * h, tq // block_q, tk // block_k),
        in_specs=[q_spec, kv_spec, kv_spec, q_spec, row_spec, row_spec],
        out_specs=[q_spec, row_spec, row_spec],
    )
    acc2, m2, l2 = pl.pallas_call(
        functools.partial(_flash_partial_kernel, cfg=cfg),
        grid_spec=grid_spec,
        out_shape=[jax.ShapeDtypeStruct((b * h, tq, d), jnp.float32),
                   jax.ShapeDtypeStruct((b * h, tq, 1), jnp.float32),
                   jax.ShapeDtypeStruct((b * h, tq, 1), jnp.float32)],
        interpret=cfg.interpret,
        **_dimsem("parallel", "parallel", "arbitrary"),
    )(jnp.asarray(q_offset, jnp.int32).reshape(1),
      jnp.asarray(k_offset, jnp.int32).reshape(1),
      qr, kr, vr, accr, mr, lr)
    return (acc2.reshape(b, h, tq, d), m2.reshape(b, h, tq),
            l2.reshape(b, h, tq))


def _flash_dq_partial_kernel(qoff_ref, koff_ref, q_ref, k_ref, v_ref,
                             do_ref, lse_ref, delta_ref, dq_ref,
                             acc_ref, *, cfg: _FlashCfg, nk: int):
    """dQ contribution of ONE visiting K/V chunk (ring backward).
    lse/delta are the FINAL whole-sequence values, so
    P = exp(s - lse) is already normalized; offsets are global."""
    block_q, block_k = cfg.block_q, cfg.block_k
    i = pl.program_id(1)
    j = pl.program_id(2)

    @pl.when(j == 0)
    def _init():
        acc_ref[...] = jnp.zeros_like(acc_ref)

    q_pos0 = qoff_ref[0] + i * block_q
    k_pos0 = koff_ref[0] + j * block_k
    needed = True
    if cfg.causal:
        needed = k_pos0 <= q_pos0 + block_q - 1

    @pl.when(needed)
    def _body():
        _dq_accum(acc_ref, q_ref, k_ref, v_ref, None, do_ref, lse_ref,
                  delta_ref, q_pos0, k_pos0, cfg)

    @pl.when(j == nk - 1)
    def _finish():
        dq_ref[...] = (acc_ref[...] * cfg.scale).astype(dq_ref.dtype)


def _flash_dkv_partial_kernel(qoff_ref, koff_ref, k_ref, v_ref, q_ref,
                              do_ref, lse_ref, delta_ref, dk_ref,
                              dv_ref, dk_acc, dv_acc, *,
                              cfg: _FlashCfg, nq: int):
    """dK/dV of ONE visiting chunk w.r.t. THIS device's Q/dO (ring
    backward); grid (bh, local k-blocks, local q-blocks)."""
    block_q, block_k = cfg.block_q, cfg.block_k
    j = pl.program_id(1)
    i = pl.program_id(2)

    @pl.when(i == 0)
    def _init():
        dk_acc[...] = jnp.zeros_like(dk_acc)
        dv_acc[...] = jnp.zeros_like(dv_acc)

    q_pos0 = qoff_ref[0] + i * block_q
    k_pos0 = koff_ref[0] + j * block_k
    needed = True
    if cfg.causal:
        needed = q_pos0 + block_q - 1 >= k_pos0

    @pl.when(needed)
    def _body():
        _dkv_accum(dk_acc, dv_acc, k_ref, v_ref, q_ref, None, do_ref,
                   lse_ref, delta_ref, q_pos0, k_pos0, cfg)

    @pl.when(i == nq - 1)
    def _finish():
        dk_ref[...] = (dk_acc[...] * cfg.scale).astype(dk_ref.dtype)
        dv_ref[...] = dv_acc[...].astype(dv_ref.dtype)


def _partial_rows(x, b, h, t):
    return x.reshape(b * h, t, 1).astype(jnp.float32)


def flash_attention_dq_partial(q, k, v, do, lse, delta, *, q_offset,
                               k_offset, causal, scale, block_q,
                               block_k, interpret):
    """dQ contribution of one visiting chunk (see ring backward).
    q/do [B,H,Tq,D]; k/v [B,H,Tk,D]; lse/delta [B,H,Tq] fp32 (FINAL
    whole-sequence logsumexp / Δ rows)."""
    b, h, tq, d = q.shape
    tk = k.shape[2]
    block_q, block_k = _resolve_blocks(block_q, block_k, tq, tk, d)
    assert tq % block_q == 0 and tk % block_k == 0, (tq, tk)
    cfg = _FlashCfg(bool(causal), float(scale), int(block_q),
                    int(block_k), bool(interpret))
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=2,
        grid=(b * h, tq // block_q, tk // block_k),
        in_specs=[
            pl.BlockSpec((None, block_q, d), lambda bh, i, j, *r: (bh, i, 0)),
            pl.BlockSpec((None, block_k, d), lambda bh, i, j, *r: (bh, j, 0)),
            pl.BlockSpec((None, block_k, d), lambda bh, i, j, *r: (bh, j, 0)),
            pl.BlockSpec((None, block_q, d), lambda bh, i, j, *r: (bh, i, 0)),
            pl.BlockSpec((None, block_q, 1), lambda bh, i, j, *r: (bh, i, 0)),
            pl.BlockSpec((None, block_q, 1), lambda bh, i, j, *r: (bh, i, 0)),
        ],
        out_specs=pl.BlockSpec((None, block_q, d),
                               lambda bh, i, j, *r: (bh, i, 0)),
        scratch_shapes=[_scratch((block_q, d))],
    )
    dq = pl.pallas_call(
        functools.partial(_flash_dq_partial_kernel, cfg=cfg,
                          nk=tk // block_k),
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((b * h, tq, d), jnp.float32),
        interpret=cfg.interpret,
        **_dimsem("parallel", "parallel", "arbitrary"),
    )(jnp.asarray(q_offset, jnp.int32).reshape(1),
      jnp.asarray(k_offset, jnp.int32).reshape(1),
      q.reshape(b * h, tq, d), k.reshape(b * h, tk, d),
      v.reshape(b * h, tk, d), do.reshape(b * h, tq, d),
      _partial_rows(lse, b, h, tq), _partial_rows(delta, b, h, tq))
    return dq.reshape(b, h, tq, d)


def flash_attention_dkv_partial(q, k, v, do, lse, delta, *, q_offset,
                                k_offset, causal, scale, block_q,
                                block_k, interpret):
    """(dK, dV) of one visiting chunk against this device's Q/dO."""
    b, h, tq, d = q.shape
    tk = k.shape[2]
    block_q, block_k = _resolve_blocks(block_q, block_k, tq, tk, d)
    assert tq % block_q == 0 and tk % block_k == 0, (tq, tk)
    cfg = _FlashCfg(bool(causal), float(scale), int(block_q),
                    int(block_k), bool(interpret))
    kblk = pl.BlockSpec((None, block_k, d), lambda bh, j, i, *r: (bh, j, 0))
    qstream = pl.BlockSpec((None, block_q, d),
                           lambda bh, j, i, *r: (bh, i, 0))
    rowstream = pl.BlockSpec((None, block_q, 1),
                             lambda bh, j, i, *r: (bh, i, 0))
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=2,
        grid=(b * h, tk // block_k, tq // block_q),
        in_specs=[kblk, kblk, qstream, qstream, rowstream, rowstream],
        out_specs=[kblk, kblk],
        scratch_shapes=[_scratch((block_k, d)), _scratch((block_k, d))],
    )
    dk, dv = pl.pallas_call(
        functools.partial(_flash_dkv_partial_kernel, cfg=cfg,
                          nq=tq // block_q),
        grid_spec=grid_spec,
        out_shape=[jax.ShapeDtypeStruct((b * h, tk, d), jnp.float32),
                   jax.ShapeDtypeStruct((b * h, tk, d), jnp.float32)],
        interpret=cfg.interpret,
        **_dimsem("parallel", "parallel", "arbitrary"),
    )(jnp.asarray(q_offset, jnp.int32).reshape(1),
      jnp.asarray(k_offset, jnp.int32).reshape(1),
      k.reshape(b * h, tk, d), v.reshape(b * h, tk, d),
      q.reshape(b * h, tq, d), do.reshape(b * h, tq, d),
      _partial_rows(lse, b, h, tq), _partial_rows(delta, b, h, tq))
    return dk.reshape(b, h, tk, d), dv.reshape(b, h, tk, d)


# ---- custom_vjp wiring ----------------------------------------------------

@functools.partial(jax.custom_vjp, nondiff_argnums=(3,))
def _flash3(q, k, v, cfg: _FlashCfg):
    out, _ = _fwd_impl(q, k, v, None, cfg)
    return out


def _flash3_fwd(q, k, v, cfg):
    out, lse = _fwd_impl(q, k, v, None, cfg)
    return out, (q, k, v, out, lse)


def _flash3_bwd(cfg, res, do):
    q, k, v, out, lse = res
    return _bwd_impl(q, k, v, None, out, lse, do, cfg)


_flash3.defvjp(_flash3_fwd, _flash3_bwd)


@functools.partial(jax.custom_vjp, nondiff_argnums=(4,))
def _flash4(q, k, v, bias, cfg: _FlashCfg):
    out, _ = _fwd_impl(q, k, v, bias, cfg)
    return out


def _flash4_fwd(q, k, v, bias, cfg):
    out, lse = _fwd_impl(q, k, v, bias, cfg)
    return out, (q, k, v, bias, out, lse)


def _flash4_bwd(cfg, res, do):
    q, k, v, bias, out, lse = res
    prep = _bwd_prep(q, k, bias, out, do)
    dq, dk, dv = _bwd_impl(q, k, v, bias, out, lse, do, cfg, prep=prep)
    dbias = _dbias_impl(q, k, v, bias, lse, cfg, prep=prep)
    return dq, dk, dv, dbias


_flash4.defvjp(_flash4_fwd, _flash4_bwd)


def flash_attention(q, k, v, bias=None, *, causal: bool = False,
                    scale: Optional[float] = None,
                    block_q: Optional[int] = None,
                    block_k: Optional[int] = None,
                    interpret: bool = False):
    """Blockwise online-softmax attention as a Pallas TPU kernel, with a
    blockwise Pallas backward (``jax.custom_vjp``) so it is safe under
    ``jax.grad`` — the reference trains its Transformer/Attention stack
    (nn/Transformer.scala:749, nn/Attention.scala), so must we.

    block_q/block_k default to the largest tiling that fits VMEM (see
    :func:`_auto_blocks` — small blocks are grid-overhead-bound).
    Requires Tq % block_q == 0 and Tk % block_k == 0 (the public
    :func:`dot_product_attention` pads/dispatches).  bias, if given, must
    broadcast to [B, H, Tq, Tk].
    """
    b, h, tq, d = q.shape
    tk = k.shape[2]
    if scale is None:
        scale = 1.0 / (d ** 0.5)
    block_q, block_k = _resolve_blocks(block_q, block_k, tq, tk, d,
                                       bias=bias is not None)
    assert tq % block_q == 0 and tk % block_k == 0
    if causal and tq != tk:
        # the kernel's causal mask is start-aligned; xla_attention's is
        # end-aligned (tril k=tk-tq) — refuse the ambiguous case instead
        # of silently diverging
        raise ValueError("flash_attention causal requires tq == tk")
    cfg = _FlashCfg(causal=bool(causal), scale=float(scale),
                    block_q=int(block_q), block_k=int(block_k),
                    interpret=bool(interpret))
    if bias is None:
        return _flash3(q, k, v, cfg)
    return _flash4(q, k, v, bias, cfg)


# ---------------------------------------------------------------------------
# Ragged decode attention — one query a row over a ``full`` cache row
# ---------------------------------------------------------------------------

_LANES = 128
_DECODE_VMEM = 8 * 2 ** 20   # the streamed K and V blocks, double-buffered


def _ragged_decode_kernel(lens_ref, row_ref, blk_ref, q_ref, k_ref, v_ref,
                          bias_ref, o_ref, s_ref, m_ref, l_ref, a_ref,
                          acc_ref, *, scale: float, block: int, group: int):
    """One (slot, key block) program.  ``k_ref [Hkv, d, block]`` and
    ``v_ref [Hkv, dv, block]`` hold positions on the lanes, as the pool
    stores them, so a head's scores are a sum over sublanes of
    ``K * q`` and its context a sum over lanes of ``V * p``: both on the
    vector unit, in float32, one query head at a time (a product with
    one row a head has nothing for the MXU to do).  ``q_ref [d, Hq]``
    and ``o_ref [dv, Hq]`` keep the width on the sublanes for the same
    reason.  The context gathers lane-wise in ``acc_ref [Hq, dv, 128]``
    and is summed over the lanes once, at the slot's last block."""
    del row_ref, blk_ref                 # the index maps read them
    b, j = pl.program_id(0), pl.program_id(1)
    length = lens_ref[b]
    hkv, hq = k_ref.shape[0], q_ref.shape[1]
    last = j == pl.num_programs(1) - 1

    @pl.when(jnp.logical_and(j == 0, length > 0))
    def _init():
        m_ref[...] = jnp.full_like(m_ref, _NEG_INF)
        l_ref[...] = jnp.zeros_like(l_ref)
        acc_ref[...] = jnp.zeros_like(acc_ref)

    @pl.when(j * block < length)
    def _body():
        for h in range(hkv):
            k_h = k_ref[h].astype(jnp.float32)                 # [d, block]
            for g in range(h * group, (h + 1) * group):
                s_ref[g:g + 1, :] = jnp.sum(
                    k_h * q_ref[:, g:g + 1], axis=0, keepdims=True)
        pos = j * block + jax.lax.broadcasted_iota(
            jnp.int32, (1, block), 1)
        s = s_ref[...] * scale + bias_ref[...]                 # [Hq, block]
        s = jnp.where(pos < length, s, _NEG_INF)
        m_prev = m_ref[...]                                    # [Hq, 1]
        m_new = jnp.maximum(m_prev, jnp.max(s, axis=1, keepdims=True))
        p = jnp.exp(s - m_new)
        alpha = jnp.exp(m_prev - m_new)
        l_ref[...] = alpha * l_ref[...] + jnp.sum(p, axis=1, keepdims=True)
        m_ref[...] = m_new
        # the weights in the values' precision, as xla_attention has them
        s_ref[...] = p.astype(v_ref.dtype).astype(jnp.float32)
        a_ref[...] = jnp.broadcast_to(alpha, a_ref.shape)
        for h in range(hkv):
            v_h = v_ref[h].astype(jnp.float32)                 # [dv, block]
            for g in range(h * group, (h + 1) * group):
                pv = v_h * s_ref[g:g + 1, :]
                part = pv[:, :_LANES]
                for c in range(1, block // _LANES):
                    part = part + pv[:, c * _LANES:(c + 1) * _LANES]
                acc_ref[g] = acc_ref[g] * a_ref[g:g + 1, :] + part

    @pl.when(jnp.logical_and(last, length > 0))
    def _finish():
        inv = 1.0 / l_ref[...]
        for g in range(hq):
            o_ref[:, g:g + 1] = (
                jnp.sum(acc_ref[g], axis=1, keepdims=True)
                * inv[g:g + 1, :]).astype(o_ref.dtype)

    # a row that only rides along: zeros, and none of the work above (a
    # finish is a lane sum a query head: 3.7 us a row at OPT's 32, v5e)
    @pl.when(jnp.logical_and(last, length == 0))
    def _idle():
        o_ref[...] = jnp.zeros_like(o_ref)


def _ragged_decode_mxu_kernel(lens_ref, row_ref, blk_ref, q_ref, *refs,
                              scale: float, block: int, tiles,
                              value_scores: bool = False):
    """One (slot, key block) program where a key head serves a group of
    query heads, or a leaf lies width-minor.  ``q_ref [Hkv, G', d]`` holds
    each key head's queries (``G'``: the group padded to the sublane
    tile, in the keys' dtype) and a head's scores are the product
    ``[G', d] x [d, block]`` on the MXU, accumulated in float32; its
    context likewise ``[G', block] x [block, dv]``.  Each leaf's block is
    taken as the leaf lies (``tiles``: :func:`cache_kernels.cache_row_tiles`
    of the keys and of the values): width-minor ``[Hkv, block, width]``
    (``"sublanes"``) or positions-minor ``[Hkv, width, block]``
    (``"lanes"``), which only says which axis a product contracts.
    ``o_ref [Hkv, G', dv]``; the softmax state ``m_ref``, ``l_ref
    [Hkv, G', 1]`` and ``acc_ref [Hkv, G', dv]`` are float32.

    ``value_scores``: **the value block also scores** (a latent row,
    whose one head serves every query head).  A second operand ``qv_ref
    [Hkv, G', dv]`` then follows ``q_ref``: the part of each query that
    reads the values (the query absorbed into the latent space), ``q_ref``
    being the part that reads the keys (the rotary part); a place's score
    is the sum of the two products, and the context is the weights
    against the same value block, which is fetched once."""
    del row_ref, blk_ref                 # the index maps read them
    qv_ref = refs[0] if value_scores else None
    k_ref, v_ref, bias_ref, o_ref, m_ref, l_ref, acc_ref = refs[value_scores:]
    b, j = pl.program_id(0), pl.program_id(1)
    length = lens_ref[b]
    last = j == pl.num_programs(1) - 1
    k_dims = (((1,), (1 if tiles[0] == "sublanes" else 0,)), ((), ()))
    s_dims = (((1,), (1 if tiles[1] == "sublanes" else 0,)), ((), ()))
    v_dims = (((1,), (0 if tiles[1] == "sublanes" else 1,)), ((), ()))

    @pl.when(jnp.logical_and(j == 0, length > 0))
    def _init():
        m_ref[...] = jnp.full_like(m_ref, _NEG_INF)
        l_ref[...] = jnp.zeros_like(l_ref)
        acc_ref[...] = jnp.zeros_like(acc_ref)

    @pl.when(j * block < length)
    def _body():
        live = j * block + jax.lax.broadcasted_iota(
            jnp.int32, (1, block), 1) < length
        bias = bias_ref[...]                                   # [1, block]
        for h in range(k_ref.shape[0]):
            s = jax.lax.dot_general(
                q_ref[h], k_ref[h], k_dims,
                preferred_element_type=jnp.float32)            # [G', block]
            if value_scores:
                s = s + jax.lax.dot_general(
                    qv_ref[h], v_ref[h], s_dims,
                    preferred_element_type=jnp.float32)
            s = jnp.where(live, s * scale + bias, _NEG_INF)
            m_prev = m_ref[h]                                  # [G', 1]
            m_new = jnp.maximum(m_prev, jnp.max(s, axis=1, keepdims=True))
            p = jnp.exp(s - m_new)
            alpha = jnp.exp(m_prev - m_new)
            l_ref[h] = alpha * l_ref[h] + jnp.sum(p, axis=1, keepdims=True)
            m_ref[h] = m_new
            # the weights in the values' precision, as grouped_attention
            # has them
            acc_ref[h] = acc_ref[h] * alpha + jax.lax.dot_general(
                p.astype(v_ref.dtype), v_ref[h], v_dims,
                preferred_element_type=jnp.float32)            # [G', dv]

    @pl.when(jnp.logical_and(last, length > 0))
    def _finish():
        o_ref[...] = (acc_ref[...] / l_ref[...]).astype(o_ref.dtype)

    @pl.when(jnp.logical_and(last, length == 0))
    def _idle():
        o_ref[...] = jnp.zeros_like(o_ref)


def ragged_decode_attention(q, k, v, lengths, pad=None, *,
                            scale: Optional[float] = None,
                            block_k: Optional[int] = None,
                            interpret: bool = False):
    """Attention of one query a row over the live places of its cache
    row, as a Pallas TPU kernel: ``softmax(q k^T * scale + mask) v`` over
    positions ``< lengths[b]`` that ``pad`` does not flag.

    q: ``[B, Hq, 1, d]``; k: ``[B, Hkv, T, d]`` and v: ``[B, Hkv, T, dv]``
    as the serving pool holds them (``Hq`` a multiple of ``Hkv``, query
    head ``g`` reading key head ``g // (Hq / Hkv)``); lengths: ``[B]``
    int32, 0 for a row that only rides along (it returns zeros); pad:
    ``[B, T]`` bool or None.  Returns ``[B, Hq, 1, dv]`` in q's dtype.

    The grid is (row, key block) and ``lengths`` goes ahead as scalar
    prefetch.  A step with nothing to read (past its row's last live
    block, or of a row with nothing live) skips its arithmetic and
    fetches nothing of its own: it names a block that a live step reads
    (:func:`_step_block`), so every live block is fetched once and
    nothing else is, and what is read is each live length rounded up to
    ``block_k``.  **Which block a dead step names decides behind which
    step the next row's first block is fetched** (the pipeline asks for a
    step's block one step ahead): where a group's queries run on the MXU
    it is block 0 of the next live row, so the fetch goes out behind the
    row's last live step and the next row finds its block in VMEM; the
    vector-unit body keeps a row's dead steps on its own last block, so
    the fetch goes out behind the row's finish, there the longer step
    (:func:`_dead_steps`).  The steps after the last live row stay on its
    last block.  (The row axis is ``parallel``: on a chip with two cores
    a core's last row may name a row the other core owns, one wasted
    fetch a call.)

    **Each leaf is handed over as it lies on the chip**
    (:func:`cache_kernels.cache_row_tiles`): positions-minor,
    ``[B, Hkv, width, T]``, where the width is under the 128 lanes (the
    ``swapaxes`` below is then a change of name and not of bytes), and as
    it reads where the width fills them.  A custom call fixes its
    operands' layout, so a leaf handed over the other way would be copied
    whole on every step; ``tests/test_tpu_compile.py`` holds the compiled
    decode steps to "no copy of a cache leaf".

    **The body follows the operands.**  One query head a key head over
    leaves that both lie positions-minor (OPT's float32 pool) runs on the
    vector unit, a head at a time (:func:`_ragged_decode_kernel`: a
    product with one row has nothing for the MXU to do).  Grouped heads,
    or a width-minor leaf, take :func:`_ragged_decode_mxu_kernel`: a
    group's queries against a key block are a product for the MXU, and
    ``q`` is rounded to the keys' dtype for it (a bfloat16 cache is read
    by bfloat16 queries, as the XLA product reads it).

    Same mathematics as :func:`xla_attention` with ``incremental_bias``
    and as ``nn.attention.grouped_attention``: products and sums in
    float32, softmax in float32, the weights rounded to the values'
    precision; only the order of summation differs (a row whose live
    places are *all* flagged averages those, where the XLA product
    averages the whole row)."""
    b, hq, tq, d = q.shape
    _, hkv, t, dv = v.shape
    if tq != 1 or hq % hkv or k.shape != (b, hkv, t, d):
        raise ValueError(f"decode attention takes one query a row and "
                         f"Hq a multiple of Hkv: q {q.shape}, k {k.shape}, "
                         f"v {v.shape}")
    block = block_k or _decode_block(k.shape, v.shape, k.dtype)
    if not block or t % block or block % _LANES:
        raise ValueError(f"no key block for rows of {t} places "
                         f"(block_k={block_k})")
    if scale is None:
        scale = 1.0 / (d ** 0.5)
    return _ragged_decode(q, k, v, lengths, pad, scale=float(scale),
                          block=int(block), interpret=bool(interpret))


def latent_decode_attention(q_latent, q_rotary, rotary, latent, lengths,
                            pad=None, *, scale: float,
                            block_k: Optional[int] = None,
                            interpret: bool = False):
    """One query a row over the live places of a **latent row**: a cache
    row of one head whose value leaf ``latent [B, 1, T, r]`` (the
    compressed, normed row every head shares) is also most of its key,
    beside a narrow key leaf ``rotary [B, 1, T, dr]`` (the rotated part,
    kept apart).  ``q_latent [B, H, 1, r]`` is each head's query absorbed
    into the latent space and ``q_rotary [B, H, 1, dr]`` its rotary part:
    ``score = (q_latent . latent + q_rotary . rotary) * scale`` over
    positions ``< lengths[b]`` that ``pad`` does not flag, and the result
    ``[B, H, 1, r]`` is ``softmax(score) latent``, in ``q_latent``'s dtype.

    :func:`ragged_decode_attention`'s grid, scalar prefetch and index
    maps, so only live blocks are fetched, each once, and a row's first
    one behind the last live step of the row before it: the body
    (:func:`_ragged_decode_mxu_kernel` with ``value_scores``) scores
    against the value block it then weighs.  Both leaves are handed over
    as they lie (the latent width-minor, the rotary part
    positions-minor).  The weights are rounded to the row's dtype, sums
    are float32."""
    b, h, tq, r = q_latent.shape
    t, dr = rotary.shape[2], rotary.shape[3]
    if tq != 1 or latent.shape != (b, 1, t, r) \
            or rotary.shape != (b, 1, t, dr) \
            or q_rotary.shape != (b, h, 1, dr):
        raise ValueError(
            f"latent decode attention takes one query a row over a row of "
            f"one head: q {q_latent.shape} / {q_rotary.shape}, latent "
            f"{latent.shape}, rotary {rotary.shape}")
    block = block_k or _decode_block(rotary.shape, latent.shape,
                                     latent.dtype)
    if not block or t % block or block % _LANES:
        raise ValueError(f"no key block for rows of {t} places "
                         f"(block_k={block_k})")
    return _ragged_decode(q_rotary, rotary, latent, lengths, pad, q_latent,
                          scale=float(scale), block=int(block),
                          interpret=bool(interpret))


def _dead_steps(lengths, block: int, *, ahead: bool):
    """Where the grid steps of a call that have nothing to read point,
    from ``lengths [B]`` alone: two int32 tables a row, ``row[b]`` and
    ``blk[b]``, the (row, key block) that every step past row ``b``'s
    last live block names, and every step of a row with nothing live
    (:func:`_step_block` reads them).  The pipeline asks for a step's
    block one step ahead, so the tables say behind which step of a row
    the next row's first block is fetched; a dead step itself (~0.2 us)
    hides nothing.

    ``ahead``: **block 0 of the next live row**, so that its fetch goes
    out behind row ``b``'s last live step; with no live row ahead, the
    last live row's last block (nothing is left to fetch); with no row
    live at all, block 0 of row 0 throughout.  Not ``ahead``: row
    ``b``'s own last live block, and for a row with nothing live the
    block where the live row before it ended (or where the first live
    row will start), so that the fetch goes out behind the row's last
    grid step, its finish.  The kernel streams blocks as fast as HBM
    gives them, so either way one of the two steps runs with nothing in
    flight: the caller picks the rule that leaves the shorter one bare
    (:func:`_ragged_decode`)."""
    b = lengths.shape[0]
    rows = jnp.arange(b, dtype=jnp.int32)
    live = lengths > 0
    last = jnp.maximum(lengths - 1, 0) // block
    before = jax.lax.cummax(jnp.where(live, rows, -1))
    if not ahead:
        src = jnp.where(before >= 0, before,
                        jnp.argmax(live).astype(jnp.int32))
        return src, jnp.where(before >= 0, last[src], 0)
    # the first live row from each row on, then from the row after it on
    after = jax.lax.cummin(jnp.where(live, rows, b), reverse=True)
    after = jnp.concatenate([after[1:], jnp.full((1,), b, jnp.int32)])
    final = jnp.maximum(before[-1], 0)
    more = after < b
    return jnp.where(more, after, final), jnp.where(more, 0, last[final])


def _step_block(bi, j, lens, row, blk, *, block: int):
    """The (row, key block) that grid step ``(bi, j)`` names: its own
    while ``j`` is a live block of row ``bi``, else what
    :func:`_dead_steps` gives the row.  A block is fetched where the
    named pair changes from one step to the next, so walking the grid in
    order every live block is fetched at or before its own step, once."""
    own = j * block < lens[bi]
    return jnp.where(own, bi, row[bi]), jnp.where(own, j, blk[bi])


@functools.partial(jax.jit, static_argnames=("scale", "block", "interpret"))
def _ragged_decode(q, k, v, lengths, pad, qv=None, *, scale, block,
                   interpret):
    """:func:`ragged_decode_attention` on checked arguments.  A function
    of its own under ``jit`` so that the layers of a model, which call it
    on the same shapes, share one trace and one lowering of the kernel:
    its body is unrolled over the heads, and traced a layer at a time it
    added 9 s to every start of OPT's decode program.  ``qv`` (``[B, Hq,
    1, dv]``, or None) is the part of each query that scores against the
    value block (:func:`latent_decode_attention`)."""
    from bigdl_tpu.ops.cache_kernels import cache_row_tiles
    b, hq, _, d = q.shape
    _, hkv, t, dv = v.shape
    group = hq // hkv
    lengths = lengths.astype(jnp.int32)
    tiles = tuple(cache_row_tiles(a.shape, a.dtype) for a in (k, v))
    vector_unit = qv is None and group == 1 and tiles == ("lanes", "lanes")
    # the next row's first block is fetched behind the longer of a row's
    # last live step and its finish.  The vector-unit body's finish is a
    # lane sum a query head (3.7 us a row at OPT's 32 heads, over ~2 us of
    # a live block's arithmetic: looking ahead cost 1.2-2.2 us a row
    # there, v5e); the MXU body's is one divide, under a live step of
    # 0.85-3.1 us (what looking ahead saved a row, by the block's bytes)
    tables = _dead_steps(lengths, block, ahead=not vector_unit)
    if pad is None:
        bias = jnp.zeros((b, 1, t), jnp.float32)
    else:
        bias = jnp.where(pad, _NEG_INF, 0.0).astype(jnp.float32)[:, None]

    def lanes_map(bi, j, *refs):
        row, blk = _step_block(bi, j, *refs, block=block)
        return row, 0, 0, blk

    def bias_map(bi, j, *refs):
        row, _, _, blk = lanes_map(bi, j, *refs)
        return row, 0, blk

    def sublanes_map(bi, j, *refs):
        row, _, _, blk = lanes_map(bi, j, *refs)
        return row, 0, blk, 0

    def leaf(a, how):
        """A leaf as it lies, and the block of it a step reads."""
        width = a.shape[3]
        if how == "lanes":
            return jnp.swapaxes(a, 2, 3), pl.BlockSpec(
                (None, hkv, width, block), lanes_map)
        return a, pl.BlockSpec((None, hkv, block, width), sublanes_map)

    (k, k_spec), (v, v_spec) = leaf(k, tiles[0]), leaf(v, tiles[1])
    bias_spec = pl.BlockSpec((None, 1, block), bias_map)
    dtype = q.dtype
    q = q[:, :, 0, :]
    if vector_unit:
        kernel = functools.partial(_ragged_decode_kernel, group=group)
        # the width on the sublanes: [B, d, Hq] in and [B, dv, Hq] out
        queries = [jnp.swapaxes(q, 1, 2).astype(jnp.float32)]
        out = (b, dv, hq)
        scratch = [(hq, block), (hq, 1), (hq, 1), (hq, _LANES),
                   (hq, dv, _LANES)]
    else:
        kernel = functools.partial(_ragged_decode_mxu_kernel, tiles=tiles,
                                   value_scores=qv is not None)
        # a key head's queries together, padded to whole sublane tiles
        sub = 32 // k.dtype.itemsize
        gp = -(-group // sub) * sub

        def grouped(a):
            return jnp.pad(
                a.astype(k.dtype).reshape(b, hkv, group, a.shape[-1]),
                ((0, 0), (0, 0), (0, gp - group), (0, 0)))
        queries = [grouped(q)] if qv is None \
            else [grouped(q), grouped(qv[:, :, 0, :])]
        out = (b, hkv, gp, dv)
        scratch = [(hkv, gp, 1), (hkv, gp, 1), (hkv, gp, dv)]

    def row(bi, j, *refs):
        return (bi,) + (0,) * (len(out) - 1)

    res = pl.pallas_call(
        functools.partial(kernel, scale=scale, block=block),
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=3,
            grid=(b, t // block),
            in_specs=[pl.BlockSpec((None,) + a.shape[1:], row)
                      for a in queries] + [k_spec, v_spec, bias_spec],
            out_specs=pl.BlockSpec((None,) + out[1:], row),
            scratch_shapes=[_scratch(s) for s in scratch]),
        out_shape=jax.ShapeDtypeStruct(out, dtype),
        interpret=interpret,
        **_dimsem("parallel", "arbitrary"),
    )(lengths, *tables, *queries, k, v, bias)
    if vector_unit:
        return jnp.swapaxes(res, 1, 2)[:, :, None, :]
    return res[:, :, :group].reshape(b, hq, 1, dv)


def _decode_block(k_shape, v_shape, dtype) -> Optional[int]:
    """Places of a cache row that :func:`ragged_decode_attention` reads
    at a time, chosen from the shapes: the largest of 512, 256 and 128
    that divides the row and whose K and V blocks fit the kernel's share
    of VMEM twice over (they are double-buffered).  A row pays for every
    step of its grid, live or not (~0.2 us), a live step has a floor
    under its fetch (~1.3 us), and a live length is read rounded up to
    the block: OPT's 16 KB a place fill the VMEM share at 256; leaves of
    a few key heads (2 KB a place) take 512, which one layer alone read
    a quarter faster than 256 on a v5e.  Not more: when 1,024 was tried
    a row's first block was still fetched in the open (behind a dead
    step, too short to hide it), a cost that grows with the block and
    took back what the fewer steps saved, while the rounding grew by a
    tenth; that fetch now goes out behind the row before
    (:func:`_dead_steps`), the rounding stays, and 1,024 has not been
    measured against 512 since.  None where the row does not tile."""
    _, hkv, t, d = k_shape
    dv = v_shape[-1]
    size = jnp.dtype(dtype).itemsize
    sub = 32 // size                              # sublanes of a tile
    if d % sub or dv % sub:
        return None
    for block in (512, 256, 128):
        need = 2 * hkv * (d + dv) * block * size
        if t % block == 0 and need <= _DECODE_VMEM:
            return block
    return None


# ---------------------------------------------------------------------------
# A prefill chunk over the live key blocks of its rows
# ---------------------------------------------------------------------------
# (Listed here and not at the top: the kernels above carry their source
# lines into the programs that hold them, so a line more ahead of them
# would change the lowered text of every pool's decode program.)

__all__ += ["chunk_attention", "chunk_key_block", "ragged_chunk_attention"]

# places of a row that a prefill chunk's attention reads at a time
# (:func:`chunk_key_block`)
CHUNK_KEY_BLOCK = 256
# the chunk kernel's VMEM: the streamed K and V blocks, double-buffered, up
# to half of it; the queries, the output and the softmax state in the rest
_CHUNK_VMEM = 32 * 2 ** 20


def _ragged_chunk_kernel(at_ref, q_ref, k_ref, v_ref, bias_ref, o_ref,
                         m_ref, l_ref, acc_ref, *, scale: float, block: int):
    """One (row, key block) program of a chunk's ``W`` queries a head,
    ``q_ref [H, W, d]``, against ``k_ref [H, d, block]`` and ``v_ref [H,
    dv, block]``, positions on the lanes as the pool stores them: a
    head's scores are the product ``[W, d] x [d, block]`` on the MXU and
    its context ``[W, block] x [dv, block]^T``, operands in the queries'
    dtype, sums in float32.  Query ``i`` stands at position ``at_ref[1] +
    i`` and attends the places up to its own.  ``o_ref [H, W, dv]``; the
    softmax state ``m_ref``, ``l_ref [H, W, 1]`` and ``acc_ref [H, W,
    dv]`` are float32."""
    j = pl.program_id(1)
    heads, width, _ = q_ref.shape
    index = at_ref[1]

    @pl.when(j == 0)
    def _init():
        m_ref[...] = jnp.full_like(m_ref, _NEG_INF)
        l_ref[...] = jnp.zeros_like(l_ref)
        acc_ref[...] = jnp.zeros_like(acc_ref)

    @pl.when(j * block < index + width)
    def _body():
        shape = (width, block)
        live = j * block + jax.lax.broadcasted_iota(jnp.int32, shape, 1) \
            <= index + jax.lax.broadcasted_iota(jnp.int32, shape, 0)
        bias = bias_ref[...]                                   # [1, block]
        for h in range(heads):
            q = q_ref[h]
            s = jax.lax.dot_general(
                q, k_ref[h].astype(q.dtype), (((1,), (0,)), ((), ())),
                preferred_element_type=jnp.float32)            # [W, block]
            s = jnp.where(live, s * scale + bias, _NEG_INF)
            m_prev = m_ref[h]                                  # [W, 1]
            m_new = jnp.maximum(m_prev, jnp.max(s, axis=1, keepdims=True))
            p = jnp.exp(s - m_new)
            alpha = jnp.exp(m_prev - m_new)
            l_ref[h] = alpha * l_ref[h] + jnp.sum(p, axis=1, keepdims=True)
            m_ref[h] = m_new
            acc_ref[h] = acc_ref[h] * alpha + jax.lax.dot_general(
                p.astype(q.dtype), v_ref[h].astype(q.dtype),
                (((1,), (1,)), ((), ())),
                preferred_element_type=jnp.float32)            # [W, dv]

    @pl.when(j == pl.num_programs(1) - 1)
    def _finish():
        o_ref[...] = (acc_ref[...] / l_ref[...]).astype(o_ref.dtype)


@functools.partial(jax.jit, static_argnames=("block", "interpret"))
def ragged_chunk_attention(q, k, v, row, index, pad, *, block: int,
                           interpret: bool = False):
    """:func:`chunk_attention` as a Pallas TPU kernel, for leaves that lie
    positions-minor (a width under the 128 lanes:
    :func:`cache_kernels.cache_row_tiles`), handed over as they lie (the
    ``swapaxes`` below changes a name and no byte, as in
    :func:`ragged_decode_attention`).

    The grid is (row, key block); the rows' first row and the chunk's
    position go ahead as scalar prefetch.  A step past the chunk's last
    block skips its arithmetic and names that last block again, so
    nothing is fetched for it: what is read is ``index + W`` rounded up
    to ``block``.  A function of its own under ``jit`` so that a model's
    layers share one trace of the kernel, whose body is unrolled over
    the heads (:func:`_ragged_decode`)."""
    b, h, w, d = q.shape
    t, dv = v.shape[2], v.shape[3]
    at = jnp.stack([jnp.asarray(row, jnp.int32),
                    jnp.asarray(index, jnp.int32)])
    bias = jnp.where(pad, _NEG_INF, 0.0).astype(jnp.float32)[:, None]

    def own(bi, j, at):
        return bi, 0, 0, 0

    def flags(bi, j, at):
        return at[0] + bi, 0, jnp.minimum(j, (at[1] + w - 1) // block)

    def leaf(bi, j, at):
        r, _, blk = flags(bi, j, at)
        return r, 0, 0, blk

    return pl.pallas_call(
        functools.partial(_ragged_chunk_kernel, scale=1.0 / (d ** 0.5),
                          block=block),
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=1,
            grid=(b, t // block),
            in_specs=[pl.BlockSpec((None, h, w, d), own),
                      pl.BlockSpec((None, h, d, block), leaf),
                      pl.BlockSpec((None, h, dv, block), leaf),
                      pl.BlockSpec((None, 1, block), flags)],
            out_specs=pl.BlockSpec((None, h, w, dv), own),
            scratch_shapes=[_scratch(s) for s in
                            ((h, w, 1), (h, w, 1), (h, w, dv))]),
        out_shape=jax.ShapeDtypeStruct((b, h, w, dv), q.dtype),
        interpret=interpret,
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "arbitrary"),
            vmem_limit_bytes=_CHUNK_VMEM),
    )(at, q, jnp.swapaxes(k, 2, 3), jnp.swapaxes(v, 2, 3), bias)


# ---------------------------------------------------------------------------
# A prefill chunk over the live key blocks of its latent rows
# ---------------------------------------------------------------------------

__all__ += ["latent_chunk_attention", "latent_chunk_takes"]

# query rows (a head's chunk positions, the heads one after another) that a
# grid step holds, and that one pair of products inside the step takes
_LATENT_CHUNK_TILE = 2048
_LATENT_CHUNK_ROWS = 256


def _latent_chunk_kernel(at_ref, ql_ref, qr_ref, kr_ref, c_ref, bias_ref,
                         o_ref, m_ref, l_ref, acc_ref, *, scale: float,
                         block: int, width: int, rows: int):
    """One (row, query tile, key block) program of a chunk over a latent
    row, whose one head every query head shares: the tile's queries are
    rows of ``ql_ref [tile, r]`` (absorbed into the latent space) and
    ``qr_ref [tile, dr]`` (the rotary part), row ``i`` of the ``H x W``
    being chunk position ``i % width`` of head ``i // width``, against the
    block ``c_ref [block, r]`` of the latent leaf and ``kr_ref [dr,
    block]`` of the rotary leaf, each as its leaf lies.  ``rows`` queries
    at a time: scores ``[rows, r] x [block, r]^T + [rows, dr] x [dr,
    block]`` on the MXU, **each exponential taken once** and used twice,
    summed in float32 for the denominator and rounded to the leaf's dtype
    for the context ``[rows, block] x [block, r]``; unrolled over the
    tile, so that one group's exponentials are scheduled under another's
    products.  ``o_ref [tile, r]``; the softmax state ``m_ref``, ``l_ref
    [tile, 1]`` and ``acc_ref [tile, r]`` are float32 and stay on the
    chip from a tile's first block to its last."""
    j = pl.program_id(2)
    tile = ql_ref.shape[0]
    index = at_ref[1]

    @pl.when(j == 0)
    def _init():
        m_ref[...] = jnp.full_like(m_ref, _NEG_INF)
        l_ref[...] = jnp.zeros_like(l_ref)
        acc_ref[...] = jnp.zeros_like(acc_ref)

    @pl.when(j * block < index + width)
    def _body():
        c = c_ref[...]
        kr = kr_ref[...]
        bias = bias_ref[...]                                   # [1, block]
        shape = (rows, block)
        k_pos = j * block + jax.lax.broadcasted_iota(jnp.int32, shape, 1)
        row = jax.lax.broadcasted_iota(jnp.int32, shape, 0)
        masks = {}
        for g in range(tile // rows):
            # a tile starts on a head's first position, so a group's
            # place in its chunk is static
            first = g * rows % width
            if first not in masks:
                masks[first] = k_pos <= index + first + (
                    row if rows <= width else jax.lax.rem(row, width))
            at = slice(g * rows, (g + 1) * rows)
            s = jax.lax.dot_general(
                ql_ref[at], c, (((1,), (1,)), ((), ())),
                preferred_element_type=jnp.float32) + jax.lax.dot_general(
                qr_ref[at], kr, (((1,), (0,)), ((), ())),
                preferred_element_type=jnp.float32)            # [rows, block]
            s = jnp.where(masks[first], s * scale + bias, _NEG_INF)
            m_prev = m_ref[at]                                 # [rows, 1]
            m_new = jnp.maximum(m_prev, jnp.max(s, axis=1, keepdims=True))
            p = jnp.exp(s - m_new)
            alpha = jnp.exp(m_prev - m_new)
            l_ref[at] = alpha * l_ref[at] + jnp.sum(p, axis=1, keepdims=True)
            m_ref[at] = m_new
            acc_ref[at] = acc_ref[at] * alpha + jax.lax.dot_general(
                p.astype(c.dtype), c, (((1,), (0,)), ((), ())),
                preferred_element_type=jnp.float32)            # [rows, r]

    @pl.when(j == pl.num_programs(2) - 1)
    def _finish():
        o_ref[...] = (acc_ref[...] * (1.0 / l_ref[...])).astype(o_ref.dtype)


def _latent_chunk_tiles(heads: int, width: int):
    """``(tile, rows)`` of :func:`_latent_chunk_kernel` for a chunk of
    ``width`` queries a head: at most :data:`_LATENT_CHUNK_TILE` of the
    ``heads x width`` rows a grid step, :data:`_LATENT_CHUNK_ROWS` a
    product, or None where they do not divide into whole heads or whole
    chunks."""
    total = heads * width
    tile, rows = min(total, _LATENT_CHUNK_TILE), \
        min(total, _LATENT_CHUNK_ROWS)
    if total % tile or tile % rows or tile % width \
            or (rows % width and width % rows):
        return None
    return tile, rows


@functools.partial(jax.jit, static_argnames=("scale", "block", "interpret"))
def latent_chunk_attention(q_latent, q_rotary, latent, rotary, row, index,
                           pad, *, scale: float, block: int,
                           interpret: bool = False):
    """A prefill chunk's attention over the live key blocks of its
    **latent rows**, as a Pallas TPU kernel: the mathematics of
    ``nn.latent_attention.latent_rows_attention`` (absorbed form, scores
    and sums in float32, the weights rounded to the row's dtype before
    the context product), only the order of summation differs.

    ``q_latent [B, H, W, r]`` / ``q_rotary [B, H, W, dr]`` at positions
    ``index .. index+W-1`` (rounded to the row's dtype here) over rows
    ``row .. row+B`` of ``latent [S, 1, T, r]`` and ``rotary [S, 1, T,
    dr]`` as they lie after the chunk's window was written (the latent
    width-minor, the rotary part positions-minor: the ``swapaxes`` below
    changes a name and no byte); ``pad [B, T]`` flags padding by position,
    or None.  Returns the context in the latent space ``[B, H, W, r]`` in
    ``q_latent``'s dtype.

    The grid is (row, query tile, key block), key blocks innermost; the
    first row and the chunk's position go ahead as scalar prefetch.  A
    query tile is a group of whole heads (:func:`_latent_chunk_tiles`) and
    reads every live block again (0.59 MB at 512 places of 512 + 64: a
    sixteenth of the tile's two products at the chip's peaks).  A step
    past the chunk's last block skips its arithmetic and names that last
    block again, so nothing is fetched for it.  A function of its own
    under ``jit`` so that a model's layers and a pool's programs share one
    trace of the kernel, whose body is unrolled over the tile
    (:func:`_ragged_decode`)."""
    b, h, w, r = q_latent.shape
    t, dr = rotary.shape[2], rotary.shape[3]
    tile, rows = _latent_chunk_tiles(h, w)
    at = jnp.stack([jnp.asarray(row, jnp.int32),
                    jnp.asarray(index, jnp.int32)])
    bias = jnp.zeros((b, 1, t), jnp.float32) if pad is None else \
        jnp.where(pad, _NEG_INF, 0.0).astype(jnp.float32)[:, None]

    def own(bi, ti, j, at):
        return bi, ti, 0

    def flags(bi, ti, j, at):
        return bi, 0, jnp.minimum(j, (at[1] + w - 1) // block)

    def lanes(bi, ti, j, at):
        return at[0] + bi, 0, 0, flags(bi, ti, j, at)[2]

    def sublanes(bi, ti, j, at):
        return at[0] + bi, 0, flags(bi, ti, j, at)[2], 0

    out = pl.pallas_call(
        functools.partial(_latent_chunk_kernel, scale=scale, block=block,
                          width=w, rows=rows),
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=1,
            grid=(b, h * w // tile, t // block),
            in_specs=[pl.BlockSpec((None, tile, r), own),
                      pl.BlockSpec((None, tile, dr), own),
                      pl.BlockSpec((None, None, dr, block), lanes),
                      pl.BlockSpec((None, None, block, r), sublanes),
                      pl.BlockSpec((None, 1, block), flags)],
            out_specs=pl.BlockSpec((None, tile, r), own),
            scratch_shapes=[_scratch(s) for s in
                            ((tile, 1), (tile, 1), (tile, r))]),
        out_shape=jax.ShapeDtypeStruct((b, h * w, r), q_latent.dtype),
        interpret=interpret,
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "arbitrary"),
            vmem_limit_bytes=_CHUNK_VMEM),
    )(at, q_latent.astype(latent.dtype).reshape(b, h * w, r),
      q_rotary.astype(rotary.dtype).reshape(b, h * w, dr),
      jnp.swapaxes(rotary, 2, 3), latent, bias)
    return out.reshape(b, h, w, r)


# ---------------------------------------------------------------------------
# Dispatch
# ---------------------------------------------------------------------------

def _on_tpu() -> bool:
    """Backend errors propagate: a chip that fails to come up must not
    read as "not a TPU" and silently select the XLA path."""
    return jax.default_backend() == "tpu"


def dot_product_attention(q, k, v, bias=None, *, causal: bool = False,
                          scale: Optional[float] = None,
                          force: Optional[str] = None):
    """Public attention entry (used by nn.Attention and the transformer
    models).  Chooses the Pallas flash kernel on TPU when the sequence
    tiles cleanly, else the XLA path.  ``force`` ∈ {"flash", "xla", None};
    env var BIGDL_TPU_ATTENTION overrides the default choice.

    Traced under a mesh of several devices (the Optimizer's step), the
    kernel runs per shard: batch over the mesh's batch axes, heads over
    the tensor-parallel axis.  The compiler partitions XLA ops on its
    own; a Mosaic kernel it refuses to.
    """
    choice = force or os.environ.get("BIGDL_TPU_ATTENTION")
    tq, tk, d = q.shape[-2], k.shape[-2], q.shape[-1]
    tiles = (tq % 128 == 0 and tk % 128 == 0 and d % 8 == 0
             and (not causal or tq == tk))
    if choice == "flash" or (choice is None and _on_tpu() and tiles):
        kernel = functools.partial(flash_attention, causal=causal,
                                   scale=scale, interpret=not _on_tpu())
        from bigdl_tpu.parallel.mesh import ambient_mesh
        mesh = ambient_mesh()
        if mesh is None or mesh.size == 1:
            return kernel(q, k, v, bias)
        return _per_shard(kernel, mesh, q, k, v, bias)
    return xla_attention(q, k, v, bias, causal=causal, scale=scale)


def decode_key_block(k_shape, v_shape, dtype, *,
                     force: Optional[str] = None) -> Optional[int]:
    """How :func:`decode_attention` reads cache rows of these shapes: the
    key block of the ragged kernel, or None for the XLA product over every
    place of every row.  The kernel is chosen on a TPU when the rows tile
    (``force`` ∈ {"ragged", "xla", None} overrides, as in
    :func:`dot_product_attention`).  The serving pool asks this too, to
    count what its decode program reads."""
    if force == "xla" or (force is None and not _on_tpu()):
        return None
    block = _decode_block(k_shape, v_shape, dtype)
    if block is None and force == "ragged":
        raise ValueError(f"rows {tuple(k_shape)} / {tuple(v_shape)} do "
                         f"not tile for the ragged decode kernel")
    return block


def decode_attention(q, k, v, lengths, pad=None, *,
                     scale: Optional[float] = None,
                     force: Optional[str] = None):
    """Attention of one query a row (``q [B, Hq, 1, d]``) over a cache
    ``k [B, Hkv, T, d]``, ``v [B, Hkv, T, dv]`` of which row ``b`` holds
    ``lengths[b]`` live places, less those ``pad [B, T]`` flags: the
    decode step of a pool whose rows stand at positions of their own.

    On a TPU, rows that tile go through :func:`ragged_decode_attention`,
    which reads live blocks only; everywhere else the masked XLA product
    reads all ``T`` places (:func:`xla_attention` under the bias
    ``nn.attention.incremental_bias`` makes).  A row with ``lengths`` 0
    only rides along and its result is read by nobody: the kernel returns
    zeros for it, the XLA product what its whole row holds, as the pool's
    idle lanes always did."""
    block = decode_key_block(k.shape, v.shape, k.dtype, force=force)
    if block is not None:
        return ragged_decode_attention(q, k, v, lengths, pad, scale=scale,
                                       block_k=block,
                                       interpret=not _on_tpu())
    t = k.shape[2]
    invalid = jnp.arange(t) >= jnp.where(lengths > 0, lengths, t)[:, None]
    if pad is not None:
        invalid = invalid | pad
    bias = jnp.where(invalid, _NEG_INF, 0.0)[:, None, None, :]
    return xla_attention(q, k, v, bias, scale=scale)


def chunk_key_block(k_shape) -> int:
    """Places of a row ``k_shape [S, H, T, d]`` that
    :func:`chunk_attention` reads at a time: :data:`CHUNK_KEY_BLOCK`, or
    what it shares with a row it does not divide.  The serving pool asks
    this too, to count what its chunk programs read."""
    return math.gcd(k_shape[2], CHUNK_KEY_BLOCK)


def _chunk_kernel_takes(k_shape, v_shape, dtype, block: int) -> bool:
    """Whether :func:`ragged_chunk_attention` takes leaves of these
    shapes: both positions-minor, blocks of whole lane tiles that fit the
    kernel's share of VMEM twice over."""
    from bigdl_tpu.ops.cache_kernels import cache_row_tiles
    _, h, _, d = k_shape
    need = 2 * h * (d + v_shape[3]) * block * jnp.dtype(dtype).itemsize
    return (block % _LANES == 0 and 2 * need <= _CHUNK_VMEM
            and cache_row_tiles(k_shape, dtype) == "lanes"
            and cache_row_tiles(v_shape, dtype) == "lanes")


def latent_chunk_takes(q_shape, latent_shape, rotary_shape, dtype,
                       block: int, *, force: Optional[str] = None) -> bool:
    """Whether a chunk of queries ``q_shape [B, H, W, r]`` over latent
    rows of these shapes goes through :func:`latent_chunk_attention`: on a
    TPU, where the leaves tile — the latent width-minor and the rotary
    part positions-minor (:func:`cache_kernels.cache_row_tiles`), blocks
    of whole lane tiles that divide the row, and ``H x W`` query rows that
    divide into tiles of whole heads and sublanes
    (:func:`_latent_chunk_tiles`).  Everything else takes the loop over
    slices, ``nn.latent_attention.latent_rows_attention`` (``force`` ∈
    {"kernel", "loop", None} overrides, as in
    :func:`cache_kernels.cache_row_writer`)."""
    from bigdl_tpu.ops.cache_kernels import _sublanes, cache_row_tiles
    if force == "loop" or (force is None and not _on_tpu()):
        return False
    _, h, w, r = q_shape
    tiles = _latent_chunk_tiles(h, w)
    takes = (block % _LANES == 0 and latent_shape[2] % block == 0
             and tiles is not None and tiles[1] % _sublanes(dtype) == 0
             and cache_row_tiles(latent_shape, dtype) == "sublanes"
             and cache_row_tiles(rotary_shape, dtype) == "lanes")
    if takes:
        # what a step holds: the tile's queries and context (both
        # double-buffered; a rotary part fills whole lanes), the float32
        # state, two blocks of each leaf, a group's scores and weights:
        # inside three quarters of the kernel's VMEM (19 MB of 32 at the
        # sarvam cut's bfloat16 rows; float32 rows of 512 would take 30
        # and are refused by the compiler)
        size = jnp.dtype(dtype).itemsize
        dr = max(rotary_shape[3], _LANES)
        tile, rows = tiles
        need = (2 * tile * (2 * r + dr) * size + 4 * tile * (r + 2 * _LANES)
                + 2 * block * (r + dr) * size + 4 * rows * (3 * block + r))
        takes = 4 * need <= 3 * _CHUNK_VMEM
    if force == "kernel" and not takes:
        raise ValueError(
            f"queries {tuple(q_shape)} over rows {tuple(latent_shape)} / "
            f"{tuple(rotary_shape)} do not tile for the latent chunk kernel")
    return takes


def chunk_attention(q, k, v, row, index, pad, *,
                    force: Optional[str] = None):
    """A prefill chunk's attention **over the live part of its rows**:
    queries ``q [B, Hq, W, d]`` at positions ``index .. index+W-1`` over
    rows ``row .. row+B`` of the cache leaves ``k [S, Hkv, T, d]``, ``v
    [S, Hkv, T, dv]`` as they lie after the chunk's window was written
    (query head ``h`` reads key head ``h // (Hq // Hkv)``); ``pad [S,
    T]`` flags padding by row and position.  Returns the context ``[B, Hq,
    W, dv]`` in ``q``'s dtype: float32 queries give a float32 context.

    Key blocks ``0 .. (index + W - 1) // block`` are read and no place
    beyond (``block``: :func:`chunk_key_block`), by a count that is
    traced: **one compiled program whatever the chunk's position** (a
    program a length would be a program a block count, eight at 2,048
    places, times a pool's four widths and two entries).  The
    mathematics is :func:`xla_attention` under ``chunk_incremental_bias``
    and ``nn.attention.grouped_attention``'s with no window and no sink:
    scores, sums and an online softmax in float32, the weights rounded to
    the values' dtype before the second product; only the order of
    summation differs, and **no array of ``Hq x W x T`` scores exists**.
    (A query none of whose places is valid averages the blocks read where
    the full product averaged the row: nobody reads either.)

    On a TPU a kernel, and **the body follows the operands**
    (:func:`_chunk_body`; ``force`` ∈ {"ragged", "xla", None} overrides):
    :func:`ragged_chunk_attention` for one query head a key head over
    positions-minor leaves (OPT), else :func:`grouped_chunk_attention`,
    each leaf as it lies.  Every other backend, and leaves that do not
    tile, take a ``fori_loop`` over blocks sliced out of the leaves (a
    latent row's chunk has its own: :func:`latent_chunk_attention`)."""
    block = chunk_key_block(k.shape)
    body = _chunk_body(q.shape, k.shape, v.shape, k.dtype, block)
    if force == "ragged" and body is None:
        raise ValueError(f"no chunk kernel takes rows {tuple(k.shape)}")
    kernel = force == "ragged" or (force is None and _on_tpu())
    if kernel and body == "heads":
        return ragged_chunk_attention(q, k, v, row, index, pad, block=block,
                                      interpret=not _on_tpu())
    if kernel and body == "groups":
        return grouped_chunk_attention(q, k, v, row, index, pad, block=block,
                                       interpret=not _on_tpu())
    # a key head's queries one after another, as the grouped kernel has
    # them: query row i stands at position index + i % w
    b, hq, w, d = q.shape
    hkv, dv = k.shape[1], v.shape[3]
    rows = hq // hkv * w
    qg = q.reshape(b, hkv, rows, d)
    scale = jnp.float32(1.0 / (d ** 0.5))
    q_pos = index + jnp.arange(rows, dtype=jnp.int32) % w

    def step(j, carry):
        m, den, acc = carry
        start = j * block
        k_j = jax.lax.dynamic_slice(k, (row, 0, start, 0),
                                    (b, hkv, block, d))
        v_j = jax.lax.dynamic_slice(v, (row, 0, start, 0),
                                    (b, hkv, block, dv))
        s = jnp.einsum("bhqd,bhkd->bhqk", qg, k_j,
                       preferred_element_type=jnp.float32) * scale
        k_pos = start + jnp.arange(block, dtype=jnp.int32)
        ok = (k_pos[None, None, :] <= q_pos[None, :, None]) \
            & ~jax.lax.dynamic_slice(pad, (row, start),
                                     (b, block))[:, None, :]  # [B, rows, block]
        s = jnp.where(ok[:, None], s, _NEG_INF)
        m_new = jnp.maximum(m, jnp.max(s, axis=-1))
        p = jnp.exp(s - m_new[..., None])
        alpha = jnp.exp(m - m_new)
        den = alpha * den + jnp.sum(p, axis=-1)
        acc = acc * alpha[..., None] + jnp.einsum(
            "bhqk,bhkd->bhqd", p.astype(v.dtype), v_j,
            preferred_element_type=jnp.float32)
        return m_new, den, acc

    init = (jnp.full((b, hkv, rows), _NEG_INF, jnp.float32),
            jnp.zeros((b, hkv, rows), jnp.float32),
            jnp.zeros((b, hkv, rows, dv), jnp.float32))
    _, den, acc = jax.lax.fori_loop(0, (index + w - 1) // block + 1, step,
                                    init)
    return (acc / den[..., None]).astype(q.dtype).reshape(b, hq, w, dv)


def _per_shard(kernel, mesh, q, k, v, bias):
    """``kernel(q, k, v, bias)`` under ``shard_map``: the batch dim split
    over the batch axes of ``mesh`` and the head dim over its ``model``
    axis, each only where it divides; sequence and depth stay whole."""
    from jax.sharding import PartitionSpec as P
    from bigdl_tpu.parallel.mesh import BATCH_AXES, shard_map_compat

    b, h = q.shape[0], q.shape[1]
    batch = tuple(a for a in BATCH_AXES if a in mesh.axis_names)
    if not batch or b % math.prod(mesh.shape[a] for a in batch):
        batch = None
    head = ("model" if "model" in mesh.axis_names
            and h % mesh.shape["model"] == 0 else None)
    spec = P(batch, head, None, None)
    args, specs = [q, k, v], [spec, spec, spec]
    if bias is not None:
        bias = bias.reshape((1,) * (4 - bias.ndim) + bias.shape)
        args.append(bias)
        specs.append(P(batch if bias.shape[0] > 1 else None,
                       head if bias.shape[1] > 1 else None, None, None))
    return shard_map_compat(kernel, mesh, in_specs=tuple(specs),
                            out_specs=spec)(*args)


# ---------------------------------------------------------------------------
# A prefill chunk over the live key blocks of its grouped-query rows
# ---------------------------------------------------------------------------
# (At the file's end, behind the dispatch: a kernel's lowered text carries
# the lines of its body and of every call on the way to it, the entries
# above among them, so a line more ahead of those would change the text of
# every pool's programs.)

__all__ += ["grouped_chunk_attention"]


def _grouped_chunk_kernel(at_ref, q_ref, k_ref, v_ref, bias_ref, o_ref,
                          m_ref, l_ref, acc_ref, *, scale: float, block: int,
                          width: int, rows: int, tiles):
    """One (row, key head, query tile, key block) program of a chunk over
    a row whose key head serves a group of query heads: the tile's queries
    are rows of ``q_ref [tile, d]``, row ``i`` of the head's ``G x W``
    being chunk position ``i % width`` of its query head ``i // width``,
    against the head's block of each leaf as the leaf lies (``tiles``:
    :func:`cache_kernels.cache_row_tiles` of the keys and of the values):
    width-minor ``[block, width]`` (``"sublanes"``) or positions-minor
    ``[width, block]`` (``"lanes"``), which only says which axis a product
    contracts.  ``rows`` queries at a time, unrolled over the tile as in
    :func:`_latent_chunk_kernel`: scores ``[rows, d] x [d, block]`` on the
    MXU in float32, each exponential taken once, summed in float32 for
    the denominator and rounded to the values' dtype for the context
    ``[rows, block] x [block, dv]``.  ``o_ref [tile, dv]``; the softmax
    state ``m_ref``, ``l_ref [tile, 1]`` and ``acc_ref [tile, dv]`` are
    float32 and stay on the chip from a tile's first block to its last:
    no score leaves it."""
    j = pl.program_id(3)
    tile = q_ref.shape[0]
    index = at_ref[1]
    k_dims = (((1,), (1 if tiles[0] == "sublanes" else 0,)), ((), ()))
    v_dims = (((1,), (0 if tiles[1] == "sublanes" else 1,)), ((), ()))

    @pl.when(j == 0)
    def _init():
        m_ref[...] = jnp.full_like(m_ref, _NEG_INF)
        l_ref[...] = jnp.zeros_like(l_ref)
        acc_ref[...] = jnp.zeros_like(acc_ref)

    @pl.when(j * block < index + width)
    def _body():
        k = k_ref[...]
        v = v_ref[...]
        bias = bias_ref[...]                                   # [1, block]
        shape = (rows, block)
        k_pos = j * block + jax.lax.broadcasted_iota(jnp.int32, shape, 1)
        row = jax.lax.broadcasted_iota(jnp.int32, shape, 0)
        masks = {}
        for g in range(tile // rows):
            # a tile starts on a head's first position, so a group's
            # place in its chunk is static
            first = g * rows % width
            if first not in masks:
                masks[first] = k_pos <= index + first + (
                    row if rows <= width else jax.lax.rem(row, width))
            at = slice(g * rows, (g + 1) * rows)
            s = jax.lax.dot_general(
                q_ref[at], k, k_dims,
                preferred_element_type=jnp.float32)            # [rows, block]
            s = jnp.where(masks[first], s * scale + bias, _NEG_INF)
            m_prev = m_ref[at]                                 # [rows, 1]
            m_new = jnp.maximum(m_prev, jnp.max(s, axis=1, keepdims=True))
            p = jnp.exp(s - m_new)
            alpha = jnp.exp(m_prev - m_new)
            l_ref[at] = alpha * l_ref[at] + jnp.sum(p, axis=1, keepdims=True)
            m_ref[at] = m_new
            acc_ref[at] = acc_ref[at] * alpha + jax.lax.dot_general(
                p.astype(v.dtype), v, v_dims,
                preferred_element_type=jnp.float32)            # [rows, dv]

    @pl.when(j == pl.num_programs(3) - 1)
    def _finish():
        o_ref[...] = (acc_ref[...] / l_ref[...]).astype(o_ref.dtype)


def _grouped_chunk_tiles(group: int, width: int):
    """``(tile, rows)`` of :func:`_grouped_chunk_kernel` for a key head's
    ``group x width`` query rows: the most rows a product takes, up to
    :data:`_LATENT_CHUNK_ROWS`, that are whole chunks or a whole part of
    one, and the most of them a grid step holds, up to
    :data:`_LATENT_CHUNK_TILE`, that are whole chunks."""
    total = group * width
    rows = max(n for n in range(1, min(total, _LATENT_CHUNK_ROWS) + 1)
               if total % n == 0 and (n % width == 0 or width % n == 0))
    tile = max(n for n in range(width, min(total, max(
        _LATENT_CHUNK_TILE, width)) + 1, width)
        if total % n == 0 and n % rows == 0)
    return tile, rows


@functools.partial(jax.jit, static_argnames=("block", "interpret"))
def grouped_chunk_attention(q, k, v, row, index, pad, *, block: int,
                            interpret: bool = False):
    """:func:`chunk_attention` as a Pallas TPU kernel for **grouped
    queries and leaves of either layout**: ``q [B, Hq, W, d]`` over rows
    ``row .. row+B`` of ``k [S, Hkv, T, d]`` and ``v [S, Hkv, T, dv]``,
    ``Hq = G x Hkv``, each leaf handed over as it lies
    (:func:`cache_kernels.cache_row_tiles`: the ``swapaxes`` of a
    positions-minor leaf changes a name and no byte); ``pad [S, T]``.
    The queries are rounded to the keys' dtype for the product, as
    :func:`ragged_decode_attention` rounds them, and the context comes
    back in the dtype they came in.

    The grid is (row, key head, query tile, key block), key blocks
    innermost; the first row and the chunk's position go ahead as scalar
    prefetch.  A key head's ``G x W`` query rows lie one after another
    (a reshape of ``q``) and a tile of them (:func:`_grouped_chunk_tiles`)
    meets each live block of the head once.  A step past the chunk's last
    block skips its arithmetic and names that last block again, so
    nothing is fetched for it: what is read is ``index + W`` rounded up to
    ``block``, and no score is written anywhere.  A function of its own
    under ``jit`` so that a model's layers and a pool's programs share one
    trace of the kernel (:func:`_ragged_decode`)."""
    from bigdl_tpu.ops.cache_kernels import cache_row_tiles
    b, hq, w, d = q.shape
    hkv, t, dv = v.shape[1], v.shape[2], v.shape[3]
    group = hq // hkv
    tile, rows = _grouped_chunk_tiles(group, w)
    tiles = tuple(cache_row_tiles(a.shape, a.dtype) for a in (k, v))
    at = jnp.stack([jnp.asarray(row, jnp.int32),
                    jnp.asarray(index, jnp.int32)])
    bias = jnp.where(jax.lax.dynamic_slice(pad, (at[0], 0), (b, t)),
                     _NEG_INF, 0.0).astype(jnp.float32)[:, None]

    def own(bi, h, ti, j, at):
        return bi, h, ti, 0

    def flags(bi, h, ti, j, at):
        return bi, 0, jnp.minimum(j, (at[1] + w - 1) // block)

    def lanes(bi, h, ti, j, at):
        return at[0] + bi, h, 0, flags(bi, h, ti, j, at)[2]

    def sublanes(bi, h, ti, j, at):
        return at[0] + bi, h, flags(bi, h, ti, j, at)[2], 0

    def leaf(a, how):
        """A leaf as it lies, and the block of it a step reads."""
        if how == "lanes":
            return jnp.swapaxes(a, 2, 3), pl.BlockSpec(
                (None, None, a.shape[3], block), lanes)
        return a, pl.BlockSpec((None, None, block, a.shape[3]), sublanes)

    (k, k_spec), (v, v_spec) = leaf(k, tiles[0]), leaf(v, tiles[1])
    out = pl.pallas_call(
        functools.partial(_grouped_chunk_kernel, scale=1.0 / (d ** 0.5),
                          block=block, width=w, rows=rows, tiles=tiles),
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=1,
            grid=(b, hkv, group * w // tile, t // block),
            in_specs=[pl.BlockSpec((None, None, tile, d), own),
                      k_spec, v_spec,
                      pl.BlockSpec((None, 1, block), flags)],
            out_specs=pl.BlockSpec((None, None, tile, dv), own),
            scratch_shapes=[_scratch(s) for s in
                            ((tile, 1), (tile, 1), (tile, dv))]),
        out_shape=jax.ShapeDtypeStruct((b, hkv, group * w, dv), q.dtype),
        interpret=interpret,
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "parallel",
                                 "arbitrary"),
            vmem_limit_bytes=_CHUNK_VMEM),
    )(at, q.astype(k.dtype).reshape(b, hkv, group * w, d), k, v, bias)
    return out.reshape(b, hq, w, dv)


def _grouped_chunk_takes(q_shape, k_shape, v_shape, dtype,
                         block: int) -> bool:
    """Whether :func:`grouped_chunk_attention` takes queries ``q_shape
    [B, Hq, W, d]`` over leaves of these shapes: each leaf tiles one way
    or the other (:func:`cache_kernels.cache_row_tiles`), blocks of whole
    lane tiles, products of whole sublane tiles
    (:func:`_grouped_chunk_tiles`), and what a step holds inside three
    quarters of the kernel's VMEM: the tile's queries and its float32
    context (both double-buffered, a width under the lanes filling
    them), the float32 state, two blocks of each leaf, a product's scores
    and weights (9 MB of 32 at the mimo cut's 2,048 rows of 192)."""
    from bigdl_tpu.ops.cache_kernels import _sublanes, cache_row_tiles
    d, dv = (max(s[3], _LANES) for s in (k_shape, v_shape))
    tile, rows = _grouped_chunk_tiles(q_shape[1] // k_shape[1], q_shape[2])
    size = jnp.dtype(dtype).itemsize
    need = (2 * tile * (d * size + dv * 4) + 4 * tile * (dv + 2 * _LANES)
            + 2 * block * (d + dv) * size + 4 * rows * 3 * block)
    return (block % _LANES == 0 and rows % _sublanes(dtype) == 0
            and cache_row_tiles(k_shape, dtype) is not None
            and cache_row_tiles(v_shape, dtype) is not None
            and 4 * need <= 3 * _CHUNK_VMEM)


def _chunk_body(q_shape, k_shape, v_shape, dtype, block: int) -> Optional[str]:
    """Which kernel takes a chunk's queries ``q_shape [B, Hq, W, d]`` over
    leaves of these shapes: ``"heads"``, :func:`ragged_chunk_attention`
    (one query head a key head over leaves that both lie positions-minor:
    every head of a block in one step, the program OPT's pool has had
    since PR 47); ``"groups"``, :func:`grouped_chunk_attention` (grouped
    heads, or a width-minor leaf); None where the leaves tile for
    neither."""
    if q_shape[1] % k_shape[1] or v_shape[:3] != k_shape[:3]:
        raise ValueError(f"chunk attention takes Hq a multiple of Hkv: q "
                         f"{tuple(q_shape)}, k {tuple(k_shape)}, v "
                         f"{tuple(v_shape)}")
    if q_shape[1] == k_shape[1] \
            and _chunk_kernel_takes(k_shape, v_shape, dtype, block):
        return "heads"
    if _grouped_chunk_takes(q_shape, k_shape, v_shape, dtype, block):
        return "groups"
    return None
