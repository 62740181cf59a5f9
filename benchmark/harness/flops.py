"""Operations and bytes that the algorithm needs, from shapes alone.

A multiply-add counts as two operations.  Training counts the forward
pass once and the backward pass twice (gradients with respect to the
inputs and to the weights); recomputation is not counted.  Elementwise
work (normalisation, activations, softmax) is left out: it is under 1%
of the matrix work at these sizes.
"""
from __future__ import annotations

from typing import Any, Dict, List, Tuple

RESNET_STAGES = ((64, 1), (128, 2), (256, 2), (512, 2))   # planes, stride


def conv_flops(h_out: int, w_out: int, kh: int, kw: int, cin: int,
               cout: int) -> int:
    """Forward operations of one image through one convolution."""
    return 2 * h_out * w_out * kh * kw * cin * cout


def resnet_convs(cfg: Dict[str, Any]) -> List[Tuple[str, int, int, int, int, int, int]]:
    """Every convolution of the bottleneck ResNet as
    ``(name, h_in, h_out, k, cin, cout, stride)`` (square maps)."""
    size = cfg["image_size"]
    out = []
    h = (size + 2 * 3 - 7) // 2 + 1
    out.append(("stem", size, h, 7, 3, 64, 2))
    h = (h + 2 - 3) // 2 + 1                      # 3x3/2 max pool, pad 1
    nin = 64
    for stage, ((planes, stride), n) in enumerate(zip(RESNET_STAGES, cfg["layers"])):
        for i in range(n):
            s = stride if i == 0 else 1
            h2 = (h + 2 - 3) // s + 1
            tag = f"s{stage}b{i}"
            out.append((tag + ".conv1", h, h, 1, nin, planes, 1))
            out.append((tag + ".conv2", h, h2, 3, planes, planes, s))
            out.append((tag + ".conv3", h2, h2, 1, planes, planes * 4, 1))
            if s != 1 or nin != planes * 4:
                out.append((tag + ".down", h, h2, 1, nin, planes * 4, s))
            nin = planes * 4
            h = h2
    return out


def resnet_forward_flops(cfg: Dict[str, Any]) -> int:
    """Forward operations of one image: convolutions and the classifier."""
    total = sum(conv_flops(ho, ho, k, k, cin, cout)
                for _, _, ho, k, cin, cout, _ in resnet_convs(cfg))
    return total + 2 * 2048 * cfg["num_classes"]


def resnet_train_flops_per_step(cfg: Dict[str, Any], batch: int) -> int:
    return 3 * batch * resnet_forward_flops(cfg)


def resnet_conv_bn_bytes_per_step(cfg: Dict[str, Any], batch: int,
                                  act_bytes: int = 2) -> int:
    """Bytes the conv+BN layers of one training step cannot avoid moving
    through HBM: forward, each convolution reads its input map and
    writes its output map once (batch statistics in the epilogue, the
    normalisation applied as the next layer reads); backward, the
    input-gradient convolution reads the output's gradient and writes
    the input's, and the weight-gradient convolution reads the input map
    and the output's gradient again.  Three passes over each map in
    all; weights (under 1%) are left out.  A perfect fusion moves this
    much, so the share of the roofline computed from it cannot pass
    100%.  ``act_bytes`` is the size of an activation."""
    total = 0
    for _, hi, ho, _k, cin, cout, _s in resnet_convs(cfg):
        total += 3 * (hi * hi * cin + ho * ho * cout)
    return total * batch * act_bytes


def lm_layer_matmul_params(cfg: Dict[str, Any]) -> int:
    h, f = cfg["hidden_size"], cfg["ffn_dim"]
    return 4 * h * h + 2 * h * f


def lm_forward_flops(cfg: Dict[str, Any], tokens: int, seq: int,
                     causal: bool = True) -> int:
    """Forward operations of ``tokens`` tokens in sequences of ``seq``:
    the projections and feed-forward of every layer, attention scores
    and values (halved under a causal mask), and the tied output head."""
    h, n = cfg["hidden_size"], cfg["num_hidden_layers"]
    dense = 2 * tokens * lm_layer_matmul_params(cfg) * n
    attn = 2 * 2 * tokens * seq * h * n
    if causal:
        attn //= 2
    head = 2 * tokens * h * (cfg["vocab_size"] + 1)
    return dense + attn + head


def lm_train_flops_per_step(cfg: Dict[str, Any], batch: int, seq: int) -> int:
    return 3 * lm_forward_flops(cfg, batch * seq, seq)


def flash_attention_cost(cfg: Dict[str, Any], batch: int, seq: int,
                         bytes_per: int = 2) -> Dict[str, float]:
    """One training step's causal attention over all layers: forward
    scores and values, backward the same twice and the scores once more
    (recomputed inside the kernel — counted, because the kernel cannot
    avoid it); bytes are q, k, v, o read or written once forward and
    q, k, v, o, do, dq, dk, dv once backward."""
    h, n = cfg["hidden_size"], cfg["num_hidden_layers"]
    fwd = 2 * 2 * batch * seq * seq * h // 2
    flops = (fwd + 2 * fwd + fwd // 2) * n
    elems = batch * seq * h
    nbytes = (4 * elems + 8 * elems) * bytes_per * n
    return {"flops": float(flops), "bytes": float(nbytes)}


def lm_weight_bytes(cfg: Dict[str, Any], bytes_per: int = 2) -> int:
    h, n = cfg["hidden_size"], cfg["num_hidden_layers"]
    per_layer = lm_layer_matmul_params(cfg) + cfg["ffn_dim"] + 5 * h
    return (per_layer * n + (cfg["vocab_size"] + 1) * h + 2 * h) * bytes_per


def decode_step_bytes(cfg: Dict[str, Any], live_positions: float,
                      weight_bytes_per: int = 2,
                      cache_bytes_per: int = 4) -> float:
    """Bytes one pooled decode step must read: every weight once, and the
    keys and values of the positions that the active slots have live."""
    h, n = cfg["hidden_size"], cfg["num_hidden_layers"]
    return (lm_weight_bytes(cfg, weight_bytes_per)
            + 2 * n * h * live_positions * cache_bytes_per)
