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
    # four heads of 64 in float32 are 2 KB a place: a row of 512 is one
    # block
    assert ak.decode_key_block(k.shape, v.shape, k.dtype) == 512
    assert ak.decode_key_block((6, 2, 384, 48), (6, 2, 384, 32),
                               jnp.float32) == 128
    assert ak.decode_key_block(k.shape, v.shape, k.dtype,
                               force="xla") is None
    odd = (6, 2, 200, 48), (6, 2, 200, 32)
    assert ak.decode_key_block(*odd, jnp.float32) is None
    with pytest.raises(ValueError):
        ak.decode_key_block(*odd, jnp.float32, force="ragged")
    # OPT's pool leaf, 16 KB a place: 256 places fill the kernel's VMEM
    # share, as they always did; twice the heads fit 128
    assert ak.decode_key_block((6, 32, 2048, 64), (6, 32, 2048, 64),
                               jnp.float32) == 256
    assert ak.decode_key_block((6, 64, 2048, 64), (6, 64, 2048, 64),
                               jnp.float32) == 128
    # the hybrid cells' leaves, 2 and 2.5 KB a place: 512 and not more
    # (3,584 = 7 x 512; 6,144 would divide by 1,024 too)
    assert ak.decode_key_block((48, 4, 3584, 128), (48, 4, 3584, 128),
                               jnp.bfloat16) == 512
    assert ak.decode_key_block((32, 4, 6144, 192), (32, 4, 6144, 128),
                               jnp.bfloat16) == 512


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


# ---- grouped heads and width-minor leaves: the body for the MXU -------------
# (Hq, Hkv, d, dv) at a small size, each in the shape class of a served
# model: Falcon-H1's leaves both lie width-minor (128 wide, bfloat16),
# MiMo's keys positions-minor (192 wide) and its values width-minor.
GROUPED = {"falcon": ((10, 2, 128, 128), ("sublanes", "sublanes")),
           "mimo": ((32, 2, 192, 128), ("lanes", "sublanes"))}


def _grouped_operands(model, rows, seed):
    (hq, hkv, d, dv), tiles = GROUPED[model]
    r = np.random.RandomState(seed)
    q = jnp.asarray(r.randn(rows, hq, 1, d), jnp.bfloat16)
    k = jnp.asarray(0.3 * r.randn(rows, hkv, T, d), jnp.bfloat16)
    v = jnp.asarray(r.randn(rows, hkv, T, dv), jnp.bfloat16)
    from bigdl_tpu.ops.cache_kernels import cache_row_tiles
    assert (cache_row_tiles(k.shape, k.dtype),
            cache_row_tiles(v.shape, v.dtype)) == tiles
    return q, k, v


def _grouped_oracle(q, k, v, lengths, pad):
    """``grouped_attention`` as the per-row decode step calls it: the
    query of row ``b`` at position ``lengths[b] - 1`` over a full row."""
    from bigdl_tpu.nn.attention import cache_positions, grouped_attention
    index = lengths - 1
    return grouped_attention(q, k, v, index[:, None],
                             cache_positions(T, index, False), None, pad)


@pytest.mark.parametrize("padded", [False, True], ids=["nopad", "pad"])
@pytest.mark.parametrize("block", [256, 128])
@pytest.mark.parametrize("model", sorted(GROUPED))
def test_mxu_body_matches_grouped_attention(model, block, padded):
    """Every edge of a row's last live block and a row that only rides
    along, each leaf taken as it lies, against the XLA product over the
    whole row: float32 scores and softmax on both sides, so what differs
    is the order of summation and that a weight is rounded to bfloat16
    under its block's running maximum (an ulp of a weight)."""
    q, k, v = _grouped_operands(model, len(LENGTHS), seed=block + padded)
    lengths = jnp.asarray(LENGTHS, jnp.int32)
    pad = np.zeros((len(LENGTHS), T), bool)
    if padded:
        pad[2, :3] = True
        pad[4, BLOCK - 2:BLOCK + 1] = True
        pad[5, T - 2] = True
        pad[3, 300:] = True       # beyond what is live: changes nothing
    pad = jnp.asarray(pad)
    out = ak.ragged_decode_attention(q.astype(jnp.float32), k, v, lengths,
                                     pad, block_k=block, interpret=True)
    assert out.shape == q.shape[:3] + (v.shape[-1],)
    assert out.dtype == jnp.float32
    out = np.asarray(out)
    assert not np.isnan(out).any()
    assert (out[0] == 0).all()
    want = np.asarray(_grouped_oracle(q, k, v, lengths, pad))
    np.testing.assert_allclose(out[1:], want[1:], rtol=6e-3, atol=6e-3)


@pytest.mark.parametrize("model", sorted(GROUPED))
def test_mxu_body_in_a_pool_with_idle_rows(model):
    """Only some rows active: the idle ones, first, between and last,
    read zeros and the live rows what they read alone."""
    q, k, v = _grouped_operands(model, 6, seed=11)
    lengths = jnp.asarray([0, 0, 300, 0, 17, 0], jnp.int32)
    out = np.asarray(ak.ragged_decode_attention(
        q.astype(jnp.float32), k, v, lengths, interpret=True))
    want = np.asarray(_grouped_oracle(q, k, v, lengths, None))
    for row in (0, 1, 3, 5):
        assert (out[row] == 0).all()
    np.testing.assert_allclose(out[[2, 4]], want[[2, 4]], rtol=6e-3,
                               atol=6e-3)


def kernel_call(q, k, v):
    """The ``pallas_call`` that ``ragged_decode_attention`` traces to on
    operands of these shapes: its grid, the shapes it is handed, and the
    products (``dot_general``) in its body.  Shapes only; nothing runs."""
    def find(jaxpr, name):
        for eqn in jaxpr.eqns:
            if eqn.primitive.name == name:
                yield eqn
            for param in eqn.params.values():
                # a jaxpr, a closed one, or the branches of a ``when``
                for sub in param if isinstance(param, tuple) else (param,):
                    sub = getattr(sub, "jaxpr", sub)
                    if hasattr(sub, "eqns"):
                        yield from find(sub, name)
    rows = q[0][0]
    args = [jax.ShapeDtypeStruct(*a) for a in (
        q, k, v, ((rows,), jnp.int32), ((rows, k[0][2]), jnp.bool_))]
    call, = find(jax.make_jaxpr(ak.ragged_decode_attention)(*args).jaxpr,
                 "pallas_call")
    return (call.params["grid_mapping"].grid,
            [var.aval.shape for var in call.invars[4:]],
            len(list(find(call.params["jaxpr"], "dot_general"))))


def test_body_follows_the_leaves_and_the_group():
    """Which body a call takes is read off its operands.  One query head
    a key head over leaves that both lie positions-minor keeps the
    vector-unit body and the block of 256: OPT-1.3B's pool as the
    benchmark serves it traces to the call it always did (no product in
    the body, the queries' width on the sublanes, both leaves with their
    last axes swapped).  Grouped heads or a width-minor leaf take the MXU
    body, two products a key head, each leaf as it lies."""
    f32, bf16 = jnp.float32, jnp.bfloat16
    grid, operands, products = kernel_call(
        ((6, 32, 1, 64), bf16), ((6, 32, 2048, 64), f32),
        ((6, 32, 2048, 64), f32))
    assert (grid, products) == ((6, 8), 0)
    assert operands == [(6, 64, 32), (6, 32, 64, 2048), (6, 32, 64, 2048),
                        (6, 1, 2048)]
    # Falcon-H1's leaves (group 5, padded to bfloat16's 16 sublanes)
    grid, operands, products = kernel_call(
        ((48, 20, 1, 128), f32), ((48, 4, 3584, 128), bf16),
        ((48, 4, 3584, 128), bf16))
    assert products == 2 * 4 and grid[0] == 48
    assert operands[:3] == [(48, 4, 16, 128), (48, 4, 3584, 128),
                            (48, 4, 3584, 128)]
    # MiMo's: keys positions-minor, values width-minor
    grid, operands, products = kernel_call(
        ((32, 64, 1, 192), f32), ((32, 4, 6144, 192), bf16),
        ((32, 4, 6144, 128), bf16))
    assert products == 2 * 4 and grid[0] == 32
    assert operands[:3] == [(32, 4, 16, 192), (32, 4, 192, 6144),
                            (32, 4, 6144, 128)]
    # grouped heads over OPT's kind of leaf; one head a key head over a
    # width-minor float32 leaf (the group padded to 8 sublanes)
    _, operands, products = kernel_call(
        ((6, 8, 1, 64), f32), ((6, 4, 512, 64), f32), ((6, 4, 512, 64), f32))
    assert products == 2 * 4 and operands[0] == (6, 4, 8, 64)
    _, operands, products = kernel_call(
        ((6, 4, 1, 128), f32), ((6, 4, 512, 128), f32),
        ((6, 4, 512, 128), f32))
    assert products == 2 * 4
    assert operands[:2] == [(6, 4, 8, 128), (6, 4, 512, 128)]


@pytest.mark.parametrize("window", [None, 64], ids=["full", "window"])
def test_grouped_query_attention_step_takes_the_kernel_on_full_rows_only(
        window, monkeypatch):
    """``GroupedQueryAttention.forward``'s per-row step as a TPU process
    runs it (the kernel forced, interpreted) against the XLA product it
    takes here: a full layer goes through ``ragged_decode_attention`` and
    gives the same rows where a row is active, an idle row included in
    the pool; a window layer never asks for the kernel."""
    import functools
    from bigdl_tpu.nn.attention import GroupedQueryAttention
    rows, max_len = 5, 256
    layer = GroupedQueryAttention(
        64, 8, 2, 16, 8, window=window, rope_theta=1e4, rotary_dim=8,
        sink=window is not None, value_scale=0.5)
    r = np.random.RandomState(5)
    x = jnp.asarray(r.randn(rows, 1, 64), jnp.float32)
    cache = {n: jnp.asarray(r.randn(*leaf.shape), jnp.float32)
             for n, leaf in layer.init_cache(rows, max_len,
                                             ring_margin=16).items()}
    index = jnp.asarray([0, 127, 128, max_len - 1, max_len - 1], jnp.int32)
    active = jnp.asarray([True, True, True, True, False])
    pad = jnp.asarray(r.rand(rows, max_len) < 0.2).at[:, 0].set(False)
    want, kv_want = layer.forward(x, index, cache, pad, active=active)

    calls = []
    kernel = ak.ragged_decode_attention

    def spy(*args, **kw):
        calls.append(kw["block_k"])
        return kernel(*args, **kw)
    monkeypatch.setattr(ak, "ragged_decode_attention", spy)
    monkeypatch.setattr(ak, "decode_key_block", functools.partial(
        ak.decode_key_block, force="ragged"))
    got, kv_got = layer.forward(x, index, cache, pad, active=active)
    for n in ("k", "v"):
        assert np.array_equal(np.asarray(kv_got[n]), np.asarray(kv_want[n]))
    if window is None:
        assert calls == [layer.decode_key_block(cache)] == [256]
        live = np.asarray(active)
        np.testing.assert_allclose(np.asarray(got)[live],
                                   np.asarray(want)[live],
                                   rtol=2e-5, atol=2e-5)
    else:
        assert calls == [] and layer.decode_key_block(cache) is None
        assert np.array_equal(np.asarray(got), np.asarray(want))


# ---- a latent row: one head for every query head, the value block scores ----
# (H, r, dr) at a small size in sarvam-105b's shape class: the latent leaf
# width-minor (a multiple of the 128 lanes), the rotary key positions-minor
LATENT = (16, 256, 64)
LATENT_SCALE = 0.11


def _latent_operands(rows, dtype, seed):
    h, r, dr = LATENT
    rs = np.random.RandomState(seed)
    q_latent = jnp.asarray(0.5 * rs.randn(rows, h, 1, r), jnp.float32)
    q_rotary = jnp.asarray(rs.randn(rows, h, 1, dr), jnp.float32)
    latent = jnp.asarray(rs.randn(rows, 1, T, r), dtype)
    rotary = jnp.asarray(rs.randn(rows, 1, T, dr), dtype)
    from bigdl_tpu.ops.cache_kernels import cache_row_tiles
    assert (cache_row_tiles(rotary.shape, dtype),
            cache_row_tiles(latent.shape, dtype)) == ("lanes", "sublanes")
    return q_latent, q_rotary, rotary, latent


def _latent_oracle(q_latent, q_rotary, rotary, latent, lengths, pad):
    """The masked ``jax.numpy`` product the step takes off a TPU: one key
    head of ``[latent ; rotary]`` for all the query heads, the latent as
    the value, through ``grouped_attention``; the queries rounded to the
    row's dtype, as the kernel rounds them."""
    from bigdl_tpu.nn.attention import cache_positions, grouped_attention
    dtype = latent.dtype
    index = lengths - 1
    return grouped_attention(
        jnp.concatenate([q_latent, q_rotary], axis=-1).astype(dtype),
        jnp.concatenate([latent, rotary], axis=-1), latent, index[:, None],
        cache_positions(T, index, False), None, pad, scale=LATENT_SCALE)


@pytest.mark.parametrize("padded", [False, True], ids=["nopad", "pad"])
@pytest.mark.parametrize("block", [256, 128])
@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16],
                         ids=["f32", "bf16"])
def test_latent_body_matches_the_masked_product(dtype, block, padded):
    """Ragged lengths at every edge of a row's last live block and a row
    that only rides along: the score is the sum of the product with the
    value block and the product with the narrow key block, the context the
    weights against the same value block."""
    ql, qr, rotary, latent = _latent_operands(len(LENGTHS), dtype,
                                              seed=block + padded)
    lengths = jnp.asarray(LENGTHS, jnp.int32)
    pad = np.zeros((len(LENGTHS), T), bool)
    if padded:
        pad[2, :3] = True
        pad[4, BLOCK - 2:BLOCK + 1] = True
        pad[5, T - 2] = True
        pad[3, 300:] = True       # beyond what is live: changes nothing
    pad = jnp.asarray(pad)
    out = ak.latent_decode_attention(ql, qr, rotary, latent, lengths, pad,
                                     scale=LATENT_SCALE, block_k=block,
                                     interpret=True)
    assert out.shape == ql.shape and out.dtype == jnp.float32
    out = np.asarray(out)
    assert not np.isnan(out).any()
    assert (out[0] == 0).all()
    want = np.asarray(_latent_oracle(ql, qr, rotary, latent, lengths, pad))
    tol = 2e-5 if dtype == jnp.float32 else 6e-3
    np.testing.assert_allclose(out[1:], want[1:], rtol=tol, atol=tol)
    # the narrow key block is in the score: without it the result differs
    blind = np.asarray(ak.latent_decode_attention(
        ql, jnp.zeros_like(qr), rotary, latent, lengths, pad,
        scale=LATENT_SCALE, block_k=block, interpret=True))
    assert np.abs(blind[3:] - out[3:]).max() > 0.05


def test_latent_body_in_a_pool_with_idle_rows():
    ql, qr, rotary, latent = _latent_operands(6, jnp.bfloat16, seed=12)
    lengths = jnp.asarray([0, 0, 300, 0, 17, 0], jnp.int32)
    out = np.asarray(ak.latent_decode_attention(
        ql, qr, rotary, latent, lengths, scale=LATENT_SCALE, interpret=True))
    want = np.asarray(_latent_oracle(ql, qr, rotary, latent, lengths, None))
    for row in (0, 1, 3, 5):
        assert (out[row] == 0).all()
    np.testing.assert_allclose(out[[2, 4]], want[[2, 4]], rtol=6e-3,
                               atol=6e-3)


def test_latent_call_fetches_each_block_once_and_refuses_other_shapes():
    """sarvam-105b's pool as the benchmark serves it: the grid and index
    maps are the ragged kernel's, the block is 512 places, the value leaf
    is handed over once (as it lies) beside the rotary leaf with its last
    axes swapped, and the body holds three products: two for the score,
    one for the context."""
    f32, bf16 = jnp.float32, jnp.bfloat16

    def find(jaxpr, name):
        for eqn in jaxpr.eqns:
            if eqn.primitive.name == name:
                yield eqn
            for param in eqn.params.values():
                for sub in param if isinstance(param, tuple) else (param,):
                    sub = getattr(sub, "jaxpr", sub)
                    if hasattr(sub, "eqns"):
                        yield from find(sub, name)
    args = [jax.ShapeDtypeStruct(*a) for a in (
        ((112, 64, 1, 512), f32), ((112, 64, 1, 64), f32),
        ((112, 1, 7168, 64), bf16), ((112, 1, 7168, 512), bf16),
        ((112,), jnp.int32), ((112, 7168), jnp.bool_))]
    call, = find(jax.make_jaxpr(
        lambda *a: ak.latent_decode_attention(*a, scale=0.135))(*args).jaxpr,
        "pallas_call")
    assert call.params["grid_mapping"].grid == (112, 14)
    assert [v.aval.shape for v in call.invars[4:]] == [
        (112, 1, 64, 64), (112, 1, 64, 512), (112, 1, 64, 7168),
        (112, 1, 7168, 512), (112, 1, 7168)]
    assert len(list(find(call.params["jaxpr"], "dot_general"))) == 3
    ql, qr, rotary, latent = _latent_operands(2, jnp.bfloat16, seed=1)
    lengths = jnp.asarray([5, 9], jnp.int32)
    with pytest.raises(ValueError, match="one head"):
        ak.latent_decode_attention(ql, qr, rotary, jnp.repeat(latent, 2, 1),
                                   lengths, scale=1.0, interpret=True)
    with pytest.raises(ValueError, match="no key block"):
        ak.latent_decode_attention(ql, qr, rotary, latent, lengths,
                                   scale=1.0, block_k=96, interpret=True)
