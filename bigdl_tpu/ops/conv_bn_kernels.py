"""Fused conv+BN+ReLU Pallas kernels for bottleneck convnets.

The reference's perf identity is fused conv/BN primitives inside its MKL
engine (reference: nn/mkldnn/SpatialConvolution.scala + nn/mkldnn/
SpatialBatchNormalization.scala fuse via mkl-dnn post-ops; whitepaper
docs/docs/whitepaper.md claims its throughput on exactly these chains).
On TPU the XLA path is HBM-bound on ResNet-style chains (measured:
docs/performance.md "Why ResNet-50 sits at ~39% MFU"): the conv kernels
already run at ~94% of HBM peak, so higher MFU needs structurally FEWER
BYTES, not better scheduling.

TPU-first redesign — a fused (normalize → relu → matmul → batch-stats)
op at the (BN_{i-1} → conv_i) granularity:

* forward: one Pallas kernel reads the PRE-normalization activation
  ``x`` tile-by-tile, applies the previous BN's per-channel
  ``(x - mean) * scale + beta`` and ReLU in VMEM (never materializing
  the normalized activation to HBM), feeds the MXU matmul for a 1x1
  conv, writes ``y``, and accumulates the NEXT BN's shifted one-pass
  statistics ``sum(y-K)``/``sum((y-K)^2)`` in VMEM across the
  sequential grid — the stats cost no extra HBM sweep.  HBM traffic is
  ``read A_in + write A_out``; the XLA chain pays two extra full
  activation passes (materializing the normalized input) plus an extra
  read when the stat reduce does not fuse.

* backward: ONE Pallas kernel per fused op.  The trick is the
  factoring: all C-sized algebra (folding batch stats into
  scale/shift, running-stat updates, the gradient flowing through the
  batch statistics) stays OUTSIDE the kernel in XLA, so the classic
  BatchNorm backward's two global reductions become (a) this kernel's
  VMEM-resident channel sums (``sum du``, ``sum du*x``) and (b) a
  gm/gs stats-cotangent fold-in that arrives as two [N] vectors.  The
  kernel reads ``x`` and ``dy`` once, recomputes the normalized
  activation and ``y`` in VMEM (FLOPs are free on an HBM-bound step),
  and writes ``dx`` — ``2*A_in + A_out`` of traffic where the XLA
  chain's bn-backward + wgrad + dgrad fusions pay ``~7*A_out +
  2*A_in`` around each 1x1.

Gradient correctness: the op's batch-stat outputs are real autodiff
outputs.  Downstream, XLA turns them into mean/var → scale/shift of the
next fused op; the cotangents (gm, gs) flow back INTO this op's
backward, where ``dy_total = dy + gm/M + 2*gs*(y-K)/M`` reconstructs
exactly the through-stats terms of the classic fused BN backward.  No
global reduction ever touches HBM twice.

Used by models/resnet.py's fused bottleneck path (BIGDL_TPU_FUSED_CONVBN).
"""

from __future__ import annotations

import functools
import math
from typing import NamedTuple, Optional, Tuple

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

from jax.experimental.pallas import tpu as pltpu

__all__ = ["fused_matmul_bn", "fused_matmul_bn_reference",
           "fused_block_supported", "fused_conv3x3_bn",
           "fused_conv3x3_bn_reference", "fused_conv3x3_supported",
           "shifted_batch_stats"]

_VMEM_BUDGET = 11 * 1024 * 1024  # leave headroom under the ~16MiB VMEM


class _Cfg(NamedTuple):
    """Static kernel config (hashable: custom_vjp nondiff arg)."""
    fuse_input: bool       # apply (x-mean)*scale+beta, relu before matmul
    emit_stats: bool       # accumulate shifted stats of y
    block_m: int
    interpret: bool


def _divisor_block(m: int, target: int, step: int = 8) -> Optional[int]:
    """Largest divisor of m that is a multiple of ``step`` and <= target."""
    best = None
    for bm in range(step, min(target, m) + 1, step):
        if m % bm == 0:
            best = bm
    return best


def _sublane(itemsize: int) -> int:
    """Mosaic's minimum second-to-minor tile dim per dtype: bf16 packs
    as (16, 128) tiles, f32 as (8, 128)."""
    return 16 if itemsize == 2 else 8


def _pick_block_m(m: int, k: int, n: int, itemsize: int) -> Optional[int]:
    """Block over M so that w + dW (resident) + the f32 working tiles fit
    VMEM.  The backward is the fattest occupant: w (bf16) + dW (f32)
    resident = 6*K*N bytes, plus ~(2 f32 + 1 input-width) copies of both
    the [BM,K] and [BM,N] tiles in flight.

    Blocks are rounded to the dtype's sublane multiple where a divisor
    exists (bf16 tiles are (16, 128): a block_m of 8 would lower via
    relayouts); when M has no aligned divisor we keep the old 8-step
    pick so the supported-problem set is unchanged."""
    resident = 6 * k * n
    if resident > _VMEM_BUDGET:
        return None
    per_row = (k + n) * (8 + itemsize) + k * 4
    avail = _VMEM_BUDGET - resident
    target = max(avail // max(per_row, 1), 8)
    cap = min(int(target), 1024)
    sub = _sublane(itemsize)
    if sub != 8:
        aligned = _divisor_block(m, cap, step=sub)
        if aligned is not None:
            return aligned
    return _divisor_block(m, cap)


def fused_block_supported(m: int, k: int, n: int,
                          itemsize: int = 2) -> bool:
    """Whether the Pallas path can tile this (M, K, N) problem."""
    return _pick_block_m(m, k, n, itemsize) is not None


# ---------------------------------------------------------------------------
# Pure-XLA reference (oracle for tests; fallback path)
# ---------------------------------------------------------------------------

def shifted_batch_stats(y, kshift):
    """One-pass shifted statistics over all but the channel axis, the
    exact algebra of nn/normalization.py BatchNormalization.forward:
    returns (sum(y-K), sum((y-K)^2)) in f32."""
    yf = y.astype(jnp.float32) - kshift.astype(jnp.float32)
    axes = tuple(range(y.ndim - 1))
    return jnp.sum(yf, axis=axes), jnp.sum(jnp.square(yf), axis=axes)


def fused_matmul_bn_reference(x2d, w2d, norm=None, kshift=None):
    """jnp mirror of the fused op (same rounding points: normalized
    input cast to x.dtype before the matmul, y cast to x.dtype before
    the statistics)."""
    if norm is not None:
        mean, scale, beta = norm
        xf = x2d.astype(jnp.float32)
        z = jax.nn.relu((xf - mean) * scale + beta).astype(x2d.dtype)
    else:
        z = x2d
    y = jnp.dot(z, w2d, preferred_element_type=jnp.float32).astype(x2d.dtype)
    if kshift is None:
        return y
    s1, s2 = shifted_batch_stats(y, kshift)
    return y, s1, s2


# ---------------------------------------------------------------------------
# Pallas kernels
# ---------------------------------------------------------------------------

def _fwd_kernel(x_ref, w_ref, mean_ref, scale_ref, beta_ref, kshift_ref,
                y_ref, s1_ref, s2_ref, *, cfg: _Cfg):
    m = pl.program_id(0)
    if cfg.fuse_input:
        xf = x_ref[:].astype(jnp.float32)
        u = (xf - mean_ref[:]) * scale_ref[:] + beta_ref[:]
        z = jax.nn.relu(u).astype(x_ref.dtype)
    else:
        z = x_ref[:]
    y = jnp.dot(z, w_ref[:], preferred_element_type=jnp.float32)
    yc = y.astype(y_ref.dtype)
    y_ref[:] = yc
    if cfg.emit_stats:
        yf = yc.astype(jnp.float32) - kshift_ref[:]
        p1 = jnp.sum(yf, axis=0, keepdims=True)
        p2 = jnp.sum(yf * yf, axis=0, keepdims=True)

        @pl.when(m == 0)
        def _init():
            s1_ref[:] = p1
            s2_ref[:] = p2

        @pl.when(m != 0)
        def _acc():
            s1_ref[:] += p1
            s2_ref[:] += p2


def _bwd_kernel(x_ref, w_ref, mean_ref, scale_ref, beta_ref, kshift_ref,
                dy_ref, gm_ref, gs_ref,
                dx_ref, dw_ref, dsx_ref, dsu_ref, *, cfg: _Cfg):
    """One pass: recompute z (and y when the stats were differentiated),
    fold the stats cotangents into dy, then dW += z^T dy, dz = dy w^T,
    and the input-side BN backward's channel sums."""
    m = pl.program_id(0)
    xf = x_ref[:].astype(jnp.float32)
    if cfg.fuse_input:
        u = (xf - mean_ref[:]) * scale_ref[:] + beta_ref[:]
        z = jax.nn.relu(u).astype(x_ref.dtype)
    else:
        z = x_ref[:]
    dy = dy_ref[:].astype(jnp.float32)
    if cfg.emit_stats:
        # reconstruct y exactly as the forward produced it (rounded to
        # the output dtype) — the stats were taken on the rounded values
        y = jnp.dot(z, w_ref[:], preferred_element_type=jnp.float32)
        yr = y.astype(dy_ref.dtype).astype(jnp.float32)
        dy = dy + gm_ref[:] + gs_ref[:] * (yr - kshift_ref[:])
    dyl = dy.astype(dy_ref.dtype)
    dwp = jax.lax.dot_general(
        z, dyl, (((0,), (0,)), ((), ())),
        preferred_element_type=jnp.float32)

    @pl.when(m == 0)
    def _init():
        dw_ref[:] = dwp

    @pl.when(m != 0)
    def _acc():
        dw_ref[:] += dwp

    dz = jax.lax.dot_general(
        dyl, w_ref[:], (((1,), (1,)), ((), ())),
        preferred_element_type=jnp.float32)
    if cfg.fuse_input:
        du = jnp.where(u > 0, dz, 0.0)
        px = jnp.sum(du * xf, axis=0, keepdims=True)
        pu = jnp.sum(du, axis=0, keepdims=True)

        @pl.when(m == 0)
        def _inits():
            dsx_ref[:] = px
            dsu_ref[:] = pu

        @pl.when(m != 0)
        def _accs():
            dsx_ref[:] += px
            dsu_ref[:] += pu

        dx = du * scale_ref[:]
    else:
        dx = dz
    dx_ref[:] = dx.astype(dx_ref.dtype)


def _row(v, n):
    """[1, n] f32 view of a vector (TPU VMEM wants >=2-D operands)."""
    if v is None:
        return jnp.zeros((1, n), jnp.float32)
    return jnp.asarray(v, jnp.float32).reshape(1, n)


def _vec_specs(k, n):
    zero = lambda m: (0, 0)
    return [
        pl.BlockSpec((1, k), zero),   # mean_in
        pl.BlockSpec((1, k), zero),   # scale_in
        pl.BlockSpec((1, k), zero),   # beta_in
        pl.BlockSpec((1, n), zero),   # kshift
    ]


@functools.partial(jax.custom_vjp, nondiff_argnums=(6,))
def _fused_core(x, w, mean_in, scale_in, beta_in, kshift, cfg: _Cfg):
    out = _fused_fwd(x, w, mean_in, scale_in, beta_in, kshift, cfg)[0]
    return out


def _fused_fwd(x, w, mean_in, scale_in, beta_in, kshift, cfg: _Cfg):
    m, k = x.shape
    n = w.shape[1]
    bm = cfg.block_m
    outs = [jax.ShapeDtypeStruct((m, n), x.dtype),
            jax.ShapeDtypeStruct((1, n), jnp.float32),
            jax.ShapeDtypeStruct((1, n), jnp.float32)]
    zero = lambda i: (0, 0)
    y, s1, s2 = pl.pallas_call(
        functools.partial(_fwd_kernel, cfg=cfg),
        grid=(m // bm,),
        in_specs=[pl.BlockSpec((bm, k), lambda i: (i, 0)),
                  pl.BlockSpec((k, n), zero)] + _vec_specs(k, n),
        out_specs=[pl.BlockSpec((bm, n), lambda i: (i, 0)),
                   pl.BlockSpec((1, n), zero),
                   pl.BlockSpec((1, n), zero)],
        out_shape=outs,
        compiler_params=_params(),
        interpret=cfg.interpret,
    )(x, w, mean_in, scale_in, beta_in, kshift)
    result = (y, s1[0], s2[0]) if cfg.emit_stats else y
    return result, (x, w, mean_in, scale_in, beta_in, kshift)


def _params():
    return pltpu.CompilerParams(dimension_semantics=("arbitrary",))


def _fused_bwd(cfg: _Cfg, res, ct):
    x, w, mean_in, scale_in, beta_in, kshift = res
    m, k = x.shape
    n = w.shape[1]
    bm = cfg.block_m
    if cfg.emit_stats:
        dy, gm, gs = ct
        # s1 = sum(y-K), s2 = sum((y-K)^2) are SUMS, so
        # dy_total = dy + gm + 2*gs * (y - K); fold the factor of 2 in
        # here so the kernel does one fma per element
        gm_row = gm.reshape(1, n).astype(jnp.float32)
        gs_row = (2.0 * gs).reshape(1, n).astype(jnp.float32)
    else:
        dy = ct
        gm_row = jnp.zeros((1, n), jnp.float32)
        gs_row = gm_row
    zero = lambda i: (0, 0)
    outs = [jax.ShapeDtypeStruct((m, k), x.dtype),
            jax.ShapeDtypeStruct((k, n), jnp.float32),
            jax.ShapeDtypeStruct((1, k), jnp.float32),
            jax.ShapeDtypeStruct((1, k), jnp.float32)]
    dx, dw, dsx, dsu = pl.pallas_call(
        functools.partial(_bwd_kernel, cfg=cfg),
        grid=(m // bm,),
        in_specs=([pl.BlockSpec((bm, k), lambda i: (i, 0)),
                   pl.BlockSpec((k, n), zero)] + _vec_specs(k, n)
                  + [pl.BlockSpec((bm, n), lambda i: (i, 0)),
                     pl.BlockSpec((1, n), zero),
                     pl.BlockSpec((1, n), zero)]),
        out_specs=[pl.BlockSpec((bm, k), lambda i: (i, 0)),
                   pl.BlockSpec((k, n), zero),
                   pl.BlockSpec((1, k), zero),
                   pl.BlockSpec((1, k), zero)],
        out_shape=outs,
        compiler_params=_params(),
        interpret=cfg.interpret,
    )(x, w, mean_in, scale_in, beta_in, kshift, dy, gm_row, gs_row)
    dw = dw.astype(w.dtype)
    if cfg.fuse_input:
        # channel-vector cotangents from the kernel's sums:
        #   u = (x - mean) * scale + beta
        #   dscale = sum du*(x-mean);  dbeta = sum du;  dmean = -scale*dbeta
        dsu_v = dsu[0]
        dscale = dsx[0] - jnp.asarray(mean_in, jnp.float32)[0] * dsu_v
        dmean = -jnp.asarray(scale_in, jnp.float32)[0] * dsu_v
        dbeta = dsu_v
        return (dx, dw, dmean.reshape(1, k), dscale.reshape(1, k),
                dbeta.reshape(1, k), jnp.zeros_like(kshift))
    zk = jnp.zeros((1, k), jnp.float32)
    return dx, dw, zk, zk, zk, jnp.zeros_like(kshift)


_fused_core.defvjp(_fused_fwd, _fused_bwd)


def fused_matmul_bn(x2d, w2d, *, norm=None, kshift=None,
                    block_m: Optional[int] = None,
                    interpret: bool = False):
    """Fused (normalize → relu → matmul → batch-stats) for 1x1 convs.

    x2d: [M, K] pre-normalization activation (NHWC collapsed to rows);
    w2d: [K, N] (HWIO 1x1 kernel sliced to [Cin, Cout]);
    norm: optional (mean, scale, beta) f32 [K] vectors — the PREVIOUS
      BN folded to subtract-first form (scale = gamma * rsqrt(var+eps));
      None = feed x through unchanged (first conv of a chain);
    kshift: optional f32 [N] shift (the next BN's running_mean, as in
      BatchNormalization.forward's one-pass trick); None = no stats.
      Treated as a CONSTANT under autodiff (zero cotangent) — callers
      must pass it through jax.lax.stop_gradient, exactly as
      BatchNormalization.batch_stats does with its running_mean.

    Returns y [M, N] (and (sum(y-K), sum((y-K)^2)) f32 [N] when kshift
    is given).  Differentiable: jax.custom_vjp with a single fused
    Pallas backward pass.
    """
    m, k = x2d.shape
    kk, n = w2d.shape
    assert k == kk, (x2d.shape, w2d.shape)
    if block_m is None:
        block_m = _pick_block_m(m, k, n, x2d.dtype.itemsize)
    if block_m is None or m % block_m:
        raise ValueError(
            f"fused_matmul_bn cannot tile M={m} K={k} N={n}; "
            "use fused_block_supported() to pre-check")
    cfg = _Cfg(fuse_input=norm is not None, emit_stats=kshift is not None,
               block_m=int(block_m), interpret=bool(interpret))
    if norm is not None:
        mean_in, scale_in, beta_in = (_row(v, k) for v in norm)
    else:
        mean_in = scale_in = beta_in = _row(None, k)
    ks = _row(kshift, n) if kshift is not None else _row(None, n)
    return _fused_core(x2d, w2d, mean_in, scale_in, beta_in, ks, cfg)


# ---------------------------------------------------------------------------
# 3x3 stride-1 SAME conv with fused input normalize+relu and stats
# epilogue — the bottleneck's conv2 (conv-as-9-shifted-matmuls; the MXU
# sees [BH*W, C] x [C, Co] tiles, HBM sees each activation row once).
# Halo rows ride as two extra 1-row block refs (pallas blocks cannot
# overlap); image-boundary rows are zero-masked in VMEM, which IS the
# SAME zero padding.
# ---------------------------------------------------------------------------

class _Conv3Cfg(NamedTuple):
    fuse_input: bool
    emit_stats: bool
    block_h: int
    interpret: bool


def _pick_block_h(h: int, w: int, c: int, co: int,
                  itemsize: int) -> Optional[int]:
    """Block over H.  Resident: w9 (input width) + dW9 (f32) =
    9*C*Co*(itemsize+4); per row-of-block: the haloed x/z/dy tiles (at
    the input width) plus the f32 working copies."""
    resident = 9 * c * co * (itemsize + 4)
    if resident > _VMEM_BUDGET:
        return None
    per_row = w * (c * (2 * itemsize + 8) + co * (itemsize + 8))
    avail = _VMEM_BUDGET - resident
    target = (avail // max(per_row, 1)) - 2
    if target < 1:
        return None  # even a 1-row block would blow the VMEM budget
    cap = min(int(target), h)
    # prefer block_h with block_h*W a multiple of the dtype sublane
    # count (the tiles flatten to (block_h*W, C) rows): smallest step
    # that makes the product aligned is sublane/gcd(sublane, W).  Fall
    # back to any divisor so the supported set is unchanged.
    sub = _sublane(itemsize)
    step = sub // math.gcd(sub, w)
    if step > 1:
        aligned = _divisor_block(h, cap, step=step)
        if aligned is not None:
            return aligned
    return _divisor_block(h, cap, step=1)


def fused_conv3x3_supported(h: int, w: int, c: int, co: int,
                            itemsize: int = 2) -> bool:
    return _pick_block_h(h, w, c, co, itemsize) is not None


def _nz_rows(x, mean, scale, beta, fuse_input, out_dtype):
    """normalize+relu rows in f32 registers, rounded to the compute
    dtype (the same rounding point as the unfused path's materialized
    activation)."""
    if not fuse_input:
        return x
    u = (x.astype(jnp.float32) - mean) * scale + beta
    return jax.nn.relu(u).astype(out_dtype)


def _wshift(rows, dw):
    """SAME-padding column shift: output col w consumes input col
    w + dw - 1."""
    if dw == 0:
        pad = jnp.zeros_like(rows[:, :1])
        return jnp.concatenate([pad, rows[:, :-1]], axis=1)
    if dw == 2:
        pad = jnp.zeros_like(rows[:, :1])
        return jnp.concatenate([rows[:, 1:], pad], axis=1)
    return rows


def _conv3_fwd_kernel(xt_ref, xm_ref, xb_ref, w_ref, mean_ref,
                      scale_ref, beta_ref, kshift_ref,
                      y_ref, s1_ref, s2_ref, *, cfg: _Conv3Cfg):
    i = pl.program_id(1)
    ni = pl.num_programs(1)
    first = (pl.program_id(0) == 0) & (i == 0)
    bh = cfg.block_h
    dt = xm_ref.dtype

    xm = xm_ref[0]                       # [BH, W, C]
    xt = xt_ref[0, 0][None]              # [1, W, C]
    xb = xb_ref[0, 0][None]
    # boundary rows are zero AFTER normalize+relu (SAME zero padding of
    # the conv INPUT z, which is the normalized activation)
    zt = _nz_rows(xt, mean_ref[:], scale_ref[:], beta_ref[:],
                  cfg.fuse_input, dt) * jnp.where(i > 0, 1, 0).astype(dt)
    zb = _nz_rows(xb, mean_ref[:], scale_ref[:], beta_ref[:],
                  cfg.fuse_input, dt) * jnp.where(i < ni - 1, 1,
                                                  0).astype(dt)
    zm = _nz_rows(xm, mean_ref[:], scale_ref[:], beta_ref[:],
                  cfg.fuse_input, dt)
    z = jnp.concatenate([zt, zm, zb], axis=0)   # [BH+2, W, C]

    w_, c = z.shape[1], z.shape[2]
    co = w_ref.shape[-1]
    acc = jnp.zeros((bh * w_, co), jnp.float32)
    for dh in range(3):
        rows = z[dh:dh + bh]
        for dw in range(3):
            patch = _wshift(rows, dw).reshape(bh * w_, c)
            acc += jnp.dot(patch, w_ref[dh, dw],
                           preferred_element_type=jnp.float32)
    yc = acc.astype(dt).reshape(bh, w_, co)
    y_ref[0] = yc
    if cfg.emit_stats:
        yf = yc.astype(jnp.float32) - kshift_ref[0][None]
        p1 = jnp.sum(yf, axis=(0, 1), keepdims=False)[None]
        p2 = jnp.sum(yf * yf, axis=(0, 1), keepdims=False)[None]

        @pl.when(first)
        def _init():
            s1_ref[:] = p1
            s2_ref[:] = p2

        @pl.when(~first)
        def _acc():
            s1_ref[:] += p1
            s2_ref[:] += p2


def _conv3_bwd_kernel(xt_ref, xm_ref, xb_ref, w_ref, mean_ref,
                      scale_ref, beta_ref, kshift_ref,
                      yt_ref, ym_ref, yb_ref,
                      dyt_ref, dym_ref, dyb_ref, gm_ref, gs_ref,
                      dx_ref, dw_ref, dsx_ref, dsu_ref,
                      *, cfg: _Conv3Cfg):
    """One pass per block: recompute z (haloed), fold the stats
    cotangents into dy using the SAVED forward output y (haloed — so
    halo rows fold exactly without a 2-deep recompute), accumulate the
    9 dW tiles and the BN-chain channel sums, and produce dx for the
    block's main rows (complete thanks to the dy halo)."""
    i = pl.program_id(1)
    ni = pl.num_programs(1)
    first = (pl.program_id(0) == 0) & (i == 0)
    bh = cfg.block_h
    dt = xm_ref.dtype

    mean, scale, beta = mean_ref[:], scale_ref[:], beta_ref[:]
    xm = xm_ref[0]
    top_on = jnp.where(i > 0, 1, 0).astype(dt)
    bot_on = jnp.where(i < ni - 1, 1, 0).astype(dt)
    zt = _nz_rows(xt_ref[0, 0][None], mean, scale, beta,
                  cfg.fuse_input, dt) * top_on
    zb = _nz_rows(xb_ref[0, 0][None], mean, scale, beta,
                  cfg.fuse_input, dt) * bot_on
    zm = _nz_rows(xm, mean, scale, beta, cfg.fuse_input, dt)
    z = jnp.concatenate([zt, zm, zb], axis=0)      # [BH+2, W, C]

    w_, c = z.shape[1], z.shape[2]
    co = dym_ref.shape[-1]

    def fold(dy_raw, y_raw):
        dy = dy_raw.astype(jnp.float32)
        if cfg.emit_stats:
            yf = y_raw.astype(jnp.float32)
            dy = dy + gm_ref[0][None] + gs_ref[0][None] * (
                yf - kshift_ref[0][None])
        return dy

    dym = fold(dym_ref[0], ym_ref[0])              # [BH, W, Co] f32
    dyt = fold(dyt_ref[0, 0][None], yt_ref[0, 0][None]) \
        * top_on.astype(jnp.float32)
    dyb = fold(dyb_ref[0, 0][None], yb_ref[0, 0][None]) \
        * bot_on.astype(jnp.float32)
    dym_l = dym.astype(dt)
    dy3 = jnp.concatenate([dyt.astype(dt), dym_l, dyb.astype(dt)],
                          axis=0)                  # [BH+2, W, Co]

    # dW[dh,dw] += z_patch^T dy_main
    for dh in range(3):
        rows = z[dh:dh + bh]
        for dw in range(3):
            patch = _wshift(rows, dw).reshape(bh * w_, c)
            dwp = jax.lax.dot_general(
                patch, dym_l.reshape(bh * w_, co),
                (((0,), (0,)), ((), ())),
                preferred_element_type=jnp.float32)

            @pl.when(first)
            def _init(dh=dh, dw=dw, dwp=dwp):
                dw_ref[dh, dw] = dwp

            @pl.when(~first)
            def _acc(dh=dh, dw=dw, dwp=dwp):
                dw_ref[dh, dw] += dwp

    # dgrad (transposed conv): dz[r,w] = sum_{dh,dw} dy[r+1-(2-dh),
    # w+1-(2-dw)] @ w[dh,dw]^T — expressed as the same 9-shift pattern
    # on the haloed dy with flipped taps and swapped channels
    dz = jnp.zeros((bh * w_, c), jnp.float32)
    for dh in range(3):
        rows = dy3[dh:dh + bh]
        for dw in range(3):
            patch = _wshift(rows, dw).reshape(bh * w_, co)
            dz += jax.lax.dot_general(
                patch, w_ref[2 - dh, 2 - dw],
                (((1,), (1,)), ((), ())),
                preferred_element_type=jnp.float32)
    dz = dz.reshape(bh, w_, c)

    if cfg.fuse_input:
        u = (xm.astype(jnp.float32) - mean) * scale + beta
        du = jnp.where(u > 0, dz, 0.0)
        px = jnp.sum(du * xm.astype(jnp.float32), axis=(0, 1))[None]
        pu = jnp.sum(du, axis=(0, 1))[None]

        @pl.when(first)
        def _inits():
            dsx_ref[:] = px
            dsu_ref[:] = pu

        @pl.when(~first)
        def _accs():
            dsx_ref[:] += px
            dsu_ref[:] += pu

        dx = du * scale
    else:
        dx = dz
    dx_ref[0] = dx.astype(dx_ref.dtype)


def fused_conv3x3_bn_reference(x4d, w, norm=None, kshift=None):
    """jnp mirror (same rounding points) of the fused 3x3 op."""
    if norm is not None:
        mean, scale, beta = norm
        xf = x4d.astype(jnp.float32)
        z = jax.nn.relu((xf - mean) * scale + beta).astype(x4d.dtype)
    else:
        z = x4d
    y = jax.lax.conv_general_dilated(
        z, w, (1, 1), ((1, 1), (1, 1)),
        dimension_numbers=("NHWC", "HWIO", "NHWC"),
        preferred_element_type=jnp.float32).astype(x4d.dtype)
    if kshift is None:
        return y
    s1, s2 = shifted_batch_stats(y, kshift)
    return y, s1, s2


def _conv3_specs(b, h, w_, c, co, bh):
    main = pl.BlockSpec((1, bh, w_, c), lambda b_, i: (b_, i, 0, 0))
    top = pl.BlockSpec(
        (1, 1, w_, c),
        lambda b_, i: (b_, jnp.maximum(i * bh - 1, 0), 0, 0))
    bot = pl.BlockSpec(
        (1, 1, w_, c),
        lambda b_, i: (b_, jnp.minimum((i + 1) * bh, h - 1), 0, 0))
    vec_c = pl.BlockSpec((1, c), lambda b_, i: (0, 0))
    vec_co = pl.BlockSpec((1, co), lambda b_, i: (0, 0))
    wspec = pl.BlockSpec((3, 3, c, co), lambda b_, i: (0, 0, 0, 0))
    return main, top, bot, vec_c, vec_co, wspec


def _conv3_params():
    return pltpu.CompilerParams(
        dimension_semantics=("arbitrary", "arbitrary"))


@functools.partial(jax.custom_vjp, nondiff_argnums=(6,))
def _conv3_core(x, w, mean_in, scale_in, beta_in, kshift,
                cfg: _Conv3Cfg):
    return _conv3_fwd(x, w, mean_in, scale_in, beta_in, kshift, cfg)[0]


def _conv3_fwd(x, w, mean_in, scale_in, beta_in, kshift, cfg: _Conv3Cfg):
    b, h, w_, c = x.shape
    co = w.shape[-1]
    bh = cfg.block_h
    main, top, bot, vec_c, vec_co, wspec = _conv3_specs(
        b, h, w_, c, co, bh)
    ymain = pl.BlockSpec((1, bh, w_, co), lambda b_, i: (b_, i, 0, 0))
    scal = pl.BlockSpec((1, co), lambda b_, i: (0, 0))
    outs = [jax.ShapeDtypeStruct((b, h, w_, co), x.dtype),
            jax.ShapeDtypeStruct((1, co), jnp.float32),
            jax.ShapeDtypeStruct((1, co), jnp.float32)]
    y, s1, s2 = pl.pallas_call(
        functools.partial(_conv3_fwd_kernel, cfg=cfg),
        grid=(b, h // bh),
        in_specs=[top, main, bot, wspec, vec_c, vec_c, vec_c, vec_co],
        out_specs=[ymain, scal, scal],
        out_shape=outs,
        compiler_params=_conv3_params(),
        interpret=cfg.interpret,
    )(x, x, x, w, mean_in, scale_in, beta_in, kshift)
    result = (y, s1[0], s2[0]) if cfg.emit_stats else y
    return result, (x, w, mean_in, scale_in, beta_in, kshift, y)


def _conv3_bwd(cfg: _Conv3Cfg, res, ct):
    x, w, mean_in, scale_in, beta_in, kshift, y = res
    b, h, w_, c = x.shape
    co = w.shape[-1]
    bh = cfg.block_h
    if cfg.emit_stats:
        dy, gm, gs = ct
        gm_row = gm.reshape(1, co).astype(jnp.float32)
        gs_row = (2.0 * gs).reshape(1, co).astype(jnp.float32)
    else:
        dy = ct
        gm_row = jnp.zeros((1, co), jnp.float32)
        gs_row = gm_row
    main, top, bot, vec_c, vec_co, wspec = _conv3_specs(
        b, h, w_, c, co, bh)
    ymain = pl.BlockSpec((1, bh, w_, co), lambda b_, i: (b_, i, 0, 0))
    ytop = pl.BlockSpec(
        (1, 1, w_, co),
        lambda b_, i: (b_, jnp.maximum(i * bh - 1, 0), 0, 0))
    ybot = pl.BlockSpec(
        (1, 1, w_, co),
        lambda b_, i: (b_, jnp.minimum((i + 1) * bh, h - 1), 0, 0))
    dwspec = pl.BlockSpec((3, 3, c, co), lambda b_, i: (0, 0, 0, 0))
    outs = [jax.ShapeDtypeStruct((b, h, w_, c), x.dtype),
            jax.ShapeDtypeStruct((3, 3, c, co), jnp.float32),
            jax.ShapeDtypeStruct((1, c), jnp.float32),
            jax.ShapeDtypeStruct((1, c), jnp.float32)]
    dx, dw, dsx, dsu = pl.pallas_call(
        functools.partial(_conv3_bwd_kernel, cfg=cfg),
        grid=(b, h // bh),
        in_specs=[top, main, bot, wspec, vec_c, vec_c, vec_c, vec_co,
                  ytop, ymain, ybot, ytop, ymain, ybot,
                  vec_co, vec_co],
        out_specs=[main, dwspec,
                   pl.BlockSpec((1, c), lambda b_, i: (0, 0)),
                   pl.BlockSpec((1, c), lambda b_, i: (0, 0))],
        out_shape=outs,
        compiler_params=_conv3_params(),
        interpret=cfg.interpret,
    )(x, x, x, w, mean_in, scale_in, beta_in, kshift,
      y, y, y, dy, dy, dy, gm_row, gs_row)
    dw = dw.astype(w.dtype)
    if cfg.fuse_input:
        dsu_v = dsu[0]
        dscale = dsx[0] - jnp.asarray(mean_in, jnp.float32)[0] * dsu_v
        dmean = -jnp.asarray(scale_in, jnp.float32)[0] * dsu_v
        return (dx, dw, dmean.reshape(1, c), dscale.reshape(1, c),
                dsu_v.reshape(1, c), jnp.zeros_like(kshift))
    zk = jnp.zeros((1, c), jnp.float32)
    return dx, dw, zk, zk, zk, jnp.zeros_like(kshift)


_conv3_core.defvjp(_conv3_fwd, _conv3_bwd)


def fused_conv3x3_bn(x4d, w, *, norm=None, kshift=None,
                     block_h: Optional[int] = None,
                     interpret: bool = False):
    """Fused (normalize → relu → 3x3 stride-1 SAME conv → batch-stats)
    for NHWC inputs — the bottleneck's conv2.

    x4d: [B, H, W, C]; w: [3, 3, C, Co] (HWIO);
    norm: optional (mean, scale, beta) f32 [C] (the previous BN folded
      to subtract-first form); kshift: optional f32 [Co] (next BN's
      running_mean, stop-gradient — see fused_matmul_bn).

    Returns y [B, H, W, Co] (+ (sum(y-K), sum((y-K)^2)) when kshift
    given).  jax.custom_vjp: single fused Pallas backward per block
    (dgrad + the 9 wgrad tiles + BN-chain channel sums), halo rows via
    1-row block refs, stats fold on halo rows taken from the SAVED
    forward output so no 2-deep halo is needed.
    """
    b, h, w_, c = x4d.shape
    assert w.shape[:3] == (3, 3, c), (w.shape, x4d.shape)
    co = w.shape[-1]
    if block_h is None:
        block_h = _pick_block_h(h, w_, c, co, x4d.dtype.itemsize)
    if block_h is None or h % block_h:
        raise ValueError(
            f"fused_conv3x3_bn cannot tile H={h} W={w_} C={c} Co={co}; "
            "use fused_conv3x3_supported() to pre-check")
    cfg = _Conv3Cfg(fuse_input=norm is not None,
                    emit_stats=kshift is not None,
                    block_h=int(block_h), interpret=bool(interpret))
    if norm is not None:
        mean_in, scale_in, beta_in = (_row(v, c) for v in norm)
    else:
        mean_in = scale_in = beta_in = _row(None, c)
    ks = _row(kshift, co) if kshift is not None else _row(None, co)
    return _conv3_core(x4d, w, mean_in, scale_in, beta_in, ks, cfg)
