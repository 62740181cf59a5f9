"""Int8 quantized inference.

Reference: nn/quantized/ (Quantizer.scala walks a trained model and
swaps Linear/SpatialConvolution for int8 versions backed by BigQuant
native GEMM; per-channel min/max quantization windows; algorithm in
docs/docs/whitepaper.md:179-196).

TPU-native design: BigQuant's hand-written int8 CPU GEMM becomes an
int8×int8→int32 ``dot_general``/``conv_general_dilated`` with
``preferred_element_type=int32`` — XLA lowers this straight onto the
MXU's int8 path.  Quantization windows:

* weights: symmetric per-output-channel max-abs scaling, computed once
  at quantize time (≙ BigQuant ConvKernelLoadFromModel per-channel
  min/max);
* activations: symmetric per-row (per-sample) max-abs scaling computed
  dynamically per batch (≙ BigQuant ConvDataInit min/max windows).

Quantized weights live as int8 *buffers* — not parameters — so the
quantized model is inference-only (matching the reference, where
quantized layers error on backward).
"""

from __future__ import annotations

from typing import Optional

import numpy as np

import jax
import jax.numpy as jnp

from bigdl_tpu.core.module import Module, ModuleList
from bigdl_tpu.nn.linear import Linear
from bigdl_tpu.nn.conv import SpatialConvolution, SpatialDilatedConvolution

__all__ = ["QuantizedLinear", "QuantizedSpatialConvolution", "Quantizer",
           "quantize"]


def _quantize_per_channel(w: jnp.ndarray, channel_axis: int):
    """Symmetric max-abs int8 quantization with a per-output-channel
    scale (≙ BigQuant per-channel kernel descriptors)."""
    reduce_axes = tuple(i for i in range(w.ndim) if i != channel_axis)
    amax = jnp.max(jnp.abs(w), axis=reduce_axes, keepdims=True)
    scale = jnp.where(amax > 0, amax / 127.0, 1.0)
    q = jnp.clip(jnp.round(w / scale), -127, 127).astype(jnp.int8)
    return q, scale.astype(jnp.float32)


def _quantize_rows(x: jnp.ndarray):
    """Dynamic symmetric per-row activation quantization: each sample
    row gets its own max-abs window."""
    reduce_axes = tuple(range(1, x.ndim))
    amax = jnp.max(jnp.abs(x), axis=reduce_axes, keepdims=True)
    scale = jnp.where(amax > 0, amax / 127.0, 1.0)
    q = jnp.clip(jnp.round(x / scale), -127, 127).astype(jnp.int8)
    return q, scale.astype(jnp.float32)


class QuantizedLinear(Module):
    """(≙ nn/quantized/Linear.scala over BigQuant FC kernels)"""

    def __init__(self, linear: Linear):
        super().__init__()
        w = linear._params["weight"]                   # [out, in]
        qw, sw = _quantize_per_channel(w, channel_axis=0)
        self.qweight = qw                               # int8 buffer
        self.wscale = sw.reshape(-1)                    # [out]
        self.bias = (jnp.asarray(linear._params["bias"])
                     if "bias" in linear._params else None)
        self.input_size = linear.input_size
        self.output_size = linear.output_size

    def forward(self, x):
        # any leading dims, like Linear: every feature vector is a row
        # with its own window (a [B, T, in] activation of a transformer
        # block quantizes per position, so padding never moves a real
        # position's scale)
        lead = x.shape[:-1]
        x = x.reshape(-1, self.input_size)
        qx, sx = _quantize_rows(x)                      # [b,in], [b,1]
        acc = jax.lax.dot_general(
            qx, self.qweight,
            (((1,), (1,)), ((), ())),
            preferred_element_type=jnp.int32)           # [b, out]
        out = acc.astype(jnp.float32) * sx * self.wscale[None, :]
        if self.bias is not None:
            out = out + self.bias
        return out.astype(x.dtype).reshape(lead + (self.output_size,))


class QuantizedSpatialConvolution(Module):
    """(≙ nn/quantized/SpatialConvolution.scala over BigQuant conv
    kernels).  NHWC; weight stored HWIO-int8."""

    def __init__(self, conv: SpatialConvolution):
        super().__init__()
        if getattr(conv, "n_group", 1) != 1:
            raise NotImplementedError(
                "grouped conv quantization not supported")
        w = conv._params["weight"]                       # HWIO
        qw, sw = _quantize_per_channel(w, channel_axis=3)
        self.qweight = qw
        self.wscale = sw.reshape(-1)                     # [out]
        self.bias = (jnp.asarray(conv._params["bias"])
                     if "bias" in conv._params else None)
        self.stride = conv.stride
        self.pad = conv.pad
        self.dilation = getattr(conv, "dilation", (1, 1))
        self.data_format = conv.data_format

    def forward(self, x):
        if self.data_format == "NCHW":
            x = jnp.transpose(x, (0, 2, 3, 1))
        qx, sx = _quantize_rows(x)                       # [b,h,w,c],[b,1,1,1]
        pad = self.pad
        padding = "SAME" if pad[0] == -1 else \
            ((pad[0], pad[0]), (pad[1], pad[1]))
        acc = jax.lax.conv_general_dilated(
            qx, self.qweight,
            window_strides=self.stride,
            padding=padding,
            rhs_dilation=self.dilation,
            dimension_numbers=("NHWC", "HWIO", "NHWC"),
            preferred_element_type=jnp.int32)
        out = acc.astype(jnp.float32) * sx * self.wscale
        if self.bias is not None:
            out = out + self.bias
        out = out.astype(x.dtype)
        if self.data_format == "NCHW":
            out = jnp.transpose(out, (0, 3, 1, 2))
        return out


class Quantizer:
    """Walk a trained model and swap quantizable layers for int8
    versions (≙ nn/quantized/Quantizer.scala)."""

    SWAPS = {
        Linear: QuantizedLinear,
        SpatialConvolution: QuantizedSpatialConvolution,
        SpatialDilatedConvolution: QuantizedSpatialConvolution,
    }

    @classmethod
    def quantize(cls, model: Module) -> Module:
        model = model.clone().eval_mode()
        swapped = cls._maybe_swap(model)
        if swapped is model:
            cls._walk(model)
        return swapped

    @classmethod
    def _maybe_swap(cls, mod: Module) -> Module:
        for src, dst in cls.SWAPS.items():
            if type(mod) is src:
                try:
                    return dst(mod)
                except NotImplementedError:
                    return mod
        return mod

    @classmethod
    def _walk(cls, mod: Module):
        for name, child in list(mod._modules.items()):
            if isinstance(child, ModuleList):
                for i, item in enumerate(child._items):
                    swapped = cls._maybe_swap(item)
                    if swapped is not item:
                        child._items[i] = swapped
                    else:
                        cls._walk(item)
            else:
                swapped = cls._maybe_swap(child)
                if swapped is not child:
                    mod._modules[name] = swapped
                else:
                    cls._walk(child)


def quantize(model: Module) -> Module:
    """``quantize(model)`` (≙ AbstractModule.quantize:954)."""
    return Quantizer.quantize(model)
