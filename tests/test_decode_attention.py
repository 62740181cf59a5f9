"""The ragged decode-attention kernel (``ops.ragged_decode_attention``) in
interpret mode against the path it replaces on a TPU: ``xla_attention``
under the bias ``incremental_bias`` makes, over the whole cache row.

Only the order of summation may differ, so float32 caches agree to a
re-ordered float32 sum; a bfloat16 cache also rounds the softmax weights
to bfloat16 before the normaliser is applied and not after, which is a
bfloat16 rounding of a weight.  ``tests/test_tpu_compile.py`` compiles the
kernel for a described v5e; what it computes there is a chip run's to say.
"""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from bigdl_tpu.nn.attention import incremental_bias
from bigdl_tpu.ops import attention_kernels as ak

T, BLOCK = 512, 256
# every edge of a row's last live block, and a row that only rides along
LENGTHS = [0, 1, BLOCK - 1, BLOCK, BLOCK + 1, T]
# (Hq, Hkv, d, dv): OPT's equal heads; a grouped layer with narrower values
HEADS = {"mha": (4, 4, 64, 64), "gqa16": (32, 2, 48, 32)}


def _operands(heads, dtype, seed):
    hq, hkv, d, dv = HEADS[heads]
    b = len(LENGTHS)
    r = np.random.RandomState(seed)
    q = jnp.asarray(r.randn(b, hq, 1, d), jnp.float32)
    k = jnp.asarray(r.randn(b, hkv, T, d), dtype)
    v = jnp.asarray(r.randn(b, hkv, T, dv), dtype)
    return q, k, v


def _oracle(q, k, v, lengths, pad):
    group = q.shape[1] // k.shape[1]
    bias = incremental_bias(T, lengths - 1, pad)
    return ak.xla_attention(q, jnp.repeat(k, group, axis=1),
                            jnp.repeat(v, group, axis=1), bias)


@pytest.mark.parametrize("padded", [False, True], ids=["nopad", "pad"])
@pytest.mark.parametrize("block", [256, 128])
@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16],
                         ids=["f32", "bf16"])
@pytest.mark.parametrize("heads", sorted(HEADS))
def test_kernel_matches_the_masked_xla_product(heads, dtype, block, padded):
    q, k, v = _operands(heads, dtype, seed=len(heads) + block)
    lengths = jnp.asarray(LENGTHS, jnp.int32)
    pad = np.zeros((len(LENGTHS), T), bool)
    if padded:
        # flags inside the live range: a row's first places, a stretch
        # across a block boundary, the place before the query's own
        pad[2, :3] = True
        pad[4, BLOCK - 2:BLOCK + 1] = True
        pad[5, T - 2] = True
        pad[3, 300:] = True       # beyond what is live: changes nothing
    pad = jnp.asarray(pad)
    out = ak.ragged_decode_attention(q, k, v, lengths, pad, block_k=block,
                                     interpret=True)
    assert out.shape == q.shape[:3] + (v.shape[-1],)
    assert out.dtype == q.dtype
    out = np.asarray(out)
    assert not np.isnan(out).any()
    # a row with nothing live: zeros
    assert (out[0] == 0).all()
    want = np.asarray(_oracle(q, k, v, lengths, pad))
    tol = 2e-6 if dtype == jnp.float32 else 6e-3
    np.testing.assert_allclose(out[1:], want[1:], rtol=tol, atol=tol)


def test_rows_between_and_before_the_live_ones_move_nothing_and_read_zero():
    """Idle rows first, between and last: each returns zeros, and the live
    rows read what they read alone."""
    q, k, v = _operands("mha", jnp.float32, seed=7)
    lengths = jnp.asarray([0, 0, 300, 0, 17, 0], jnp.int32)
    out = np.asarray(ak.ragged_decode_attention(q, k, v, lengths,
                                                interpret=True))
    want = np.asarray(_oracle(q, k, v, lengths, None))
    for row in (0, 1, 3, 5):
        assert (out[row] == 0).all()
    np.testing.assert_allclose(out[[2, 4]], want[[2, 4]], rtol=2e-6,
                               atol=2e-6)
    none = ak.ragged_decode_attention(q, k, v, jnp.zeros((6,), jnp.int32),
                                      interpret=True)
    assert (np.asarray(none) == 0).all()


def test_entry_chooses_by_backend_and_shape(monkeypatch):
    """``decode_attention`` on a CPU takes the XLA product; forced, the
    kernel (interpreted here); rows that do not tile keep XLA on a TPU and
    refuse the force."""
    q, k, v = _operands("mha", jnp.float32, seed=3)
    lengths = jnp.asarray(LENGTHS, jnp.int32)
    assert ak.decode_key_block(k.shape, v.shape, k.dtype) is None
    xla = ak.decode_attention(q, k, v, lengths)
    forced = ak.decode_attention(q, k, v, lengths, force="ragged")
    np.testing.assert_allclose(np.asarray(forced)[1:], np.asarray(xla)[1:],
                               rtol=2e-6, atol=2e-6)
    np.testing.assert_allclose(
        np.asarray(xla)[1:], np.asarray(_oracle(q, k, v, lengths, None))[1:],
        rtol=1e-6, atol=1e-6)
    monkeypatch.setattr(ak, "_on_tpu", lambda: True)
    assert ak.decode_key_block(k.shape, v.shape, k.dtype) == 256
    assert ak.decode_key_block((6, 2, 384, 48), (6, 2, 384, 32),
                               jnp.float32) == 128
    assert ak.decode_key_block(k.shape, v.shape, k.dtype,
                               force="xla") is None
    odd = (6, 2, 200, 48), (6, 2, 200, 32)
    assert ak.decode_key_block(*odd, jnp.float32) is None
    with pytest.raises(ValueError):
        ak.decode_key_block(*odd, jnp.float32, force="ragged")
    # OPT's pool leaf: 256 places of 32 heads fill the kernel's VMEM share
    assert ak.decode_key_block((6, 32, 2048, 64), (6, 32, 2048, 64),
                               jnp.float32) == 256
    assert ak.decode_key_block((6, 64, 2048, 64), (6, 64, 2048, 64),
                               jnp.float32) == 128


def test_kernel_refuses_what_it_cannot_tile():
    q, k, v = _operands("mha", jnp.float32, seed=1)
    lengths = jnp.asarray(LENGTHS, jnp.int32)
    with pytest.raises(ValueError):
        ak.ragged_decode_attention(q, k, v, lengths, block_k=192,
                                   interpret=True)
    with pytest.raises(ValueError):      # three queries a row
        ak.ragged_decode_attention(jnp.tile(q, (1, 1, 3, 1)), k, v, lengths,
                                   interpret=True)
    with pytest.raises(ValueError):      # 4 query heads over 3 key heads
        ak.ragged_decode_attention(q, k[:, :3], v[:, :3], lengths,
                                   interpret=True)
