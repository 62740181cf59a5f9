"""The ragged decode-attention kernel (``ops.ragged_decode_attention``) in
interpret mode against the path it replaces on a TPU: ``xla_attention``
under the bias ``incremental_bias`` makes, over the whole cache row.

Only the order of summation may differ, so float32 caches agree to a
re-ordered float32 sum; a bfloat16 cache also rounds the softmax weights
to bfloat16 before the normaliser is applied and not after, which is a
bfloat16 rounding of a weight.  ``tests/test_tpu_compile.py`` compiles the
kernel for a described v5e; what it computes there is a chip run's to say.
"""
import functools

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


def parent_dead_steps(lengths, block):
    """The oracle of the index maps: the rule the kernel had until PR 45,
    in ``_dead_steps``' form.  A step past a row's last live block stays
    on that block; a row with nothing live stays where the live row
    before it ended, or where the first live row will start."""
    rows = jnp.arange(lengths.shape[0], dtype=jnp.int32)
    live = lengths > 0
    last = jnp.maximum(lengths - 1, 0) // block
    before = jax.lax.cummax(jnp.where(live, rows, -1))
    src = jnp.where(before >= 0, before, jnp.argmax(live).astype(jnp.int32))
    return src, jnp.where(before >= 0, last[src], 0)


ahead_dead_steps = functools.partial(ak._dead_steps, ahead=True)


@pytest.fixture
def under_rule(monkeypatch):
    """``under_rule(rule, asked)``: entered, every body's steps name their
    blocks by ``rule(lengths, block)`` (traced anew: ``_ragged_decode``
    keeps its traces), and ``asked`` gathers what each call would have
    chosen.  The
    same blocks are read in the same order under either rule, so a result
    is the other's bit for bit."""
    import contextlib

    @contextlib.contextmanager
    def enter(rule, asked):
        def forced(lengths, block, *, ahead):
            asked.append(ahead)
            return rule(lengths, block)

        def anew(*args, **kw):      # a function of its own: a trace of its own
            return traced(*args, **kw)
        traced = ak._ragged_decode.__wrapped__
        with monkeypatch.context() as m:
            m.setattr(ak, "_dead_steps", forced)
            m.setattr(ak, "_ragged_decode", jax.jit(
                anew, static_argnames=("scale", "block", "interpret")))
            yield
    return enter


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


def test_rows_between_and_before_the_live_ones_move_nothing_and_read_zero(
        under_rule):
    """Idle rows first, between and last: each returns zeros, and the live
    rows read what they read alone, bit for bit what the parent's maps
    gave and what the other rule gives (the vector-unit body, which keeps
    the parent's rule; with pad flags across a block's edge, three blocks
    of four live, too)."""
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
    pad = jnp.zeros((6, T), bool).at[2, 120:130].set(True)
    padded = ak.ragged_decode_attention(q, k, v, lengths, pad, block_k=128,
                                        interpret=True)
    for rule in (parent_dead_steps, ahead_dead_steps):
        asked = []
        with under_rule(rule, asked):
            np.testing.assert_array_equal(out, np.asarray(
                ak.ragged_decode_attention(q, k, v, lengths,
                                           interpret=True)))
            np.testing.assert_array_equal(np.asarray(padded), np.asarray(
                ak.ragged_decode_attention(q, k, v, lengths, pad,
                                           block_k=128, interpret=True)))
        assert asked == [False, False]      # the finish is the longer step


# ---- what a grid step names: pure functions of the lengths, no kernel run ----
# (rows, places a row, key block) of the five served pools
POOLS = {"opt": (6, 2048, 256), "mimo": (32, 6144, 512),
         "falcon": (48, 3584, 512), "sarvam": (112, 7168, 512),
         "chains": (96, 5120, 512)}


def _all_live(b, t, block, r):
    return r.randint(1, t + 1, size=b)


def _idle_first_between_last(b, t, block, r):
    lengths = r.randint(1, t + 1, size=b)
    lengths[[0, 1, b // 2, b - 1]] = 0
    return lengths


def _one_live(b, t, block, r):
    lengths = np.zeros(b, int)
    lengths[b // 3] = block + 5
    return lengths


def _none_live(b, t, block, r):
    return np.zeros(b, int)


def _to_the_last_block(b, t, block, r):
    lengths = r.randint(0, t + 1, size=b)
    lengths[[0, b // 2, b - 1]] = t, t - block + 1, t
    return lengths


def _on_a_blocks_edge(b, t, block, r):
    edges = [block - 1, block, block + 1, 2 * block, 2 * block + 1, 0]
    return np.resize(edges, b)


PATTERNS = [_all_live, _idle_first_between_last, _one_live, _none_live,
            _to_the_last_block, _on_a_blocks_edge]


def _named(rule, lengths, block, steps):
    """The (row, block) that each step of the ``[B, steps]`` grid names
    under ``rule`` (``_dead_steps`` looking ahead, or the parent's), as
    two arrays."""
    lens = jnp.asarray(lengths, jnp.int32)
    bi = np.arange(len(lengths))[:, None]
    j = np.arange(steps)[None, :]
    row, blk = ak._step_block(bi, j, np.asarray(lens),
                              *map(np.asarray, rule(lens, block)),
                              block=block)
    return np.asarray(row), np.asarray(blk)


@pytest.mark.parametrize("pattern", PATTERNS,
                         ids=[f.__name__[1:] for f in PATTERNS])
@pytest.mark.parametrize("pool", sorted(POOLS))
def test_what_a_step_names(pool, pattern):
    """Looking ahead (the rule of the MXU bodies): every live block is
    named at its own step; walking the grid in order the named block
    changes once a live block and never else, so each is fetched once; a
    step with nothing to read names block 0 of the next live row, the
    fetch the row's last live step then covers; behind the last live row
    the steps stay on its last block; with no row live nothing moves."""
    b, t, block = POOLS[pool]
    steps = t // block
    lengths = pattern(b, t, block, np.random.RandomState(b + block))
    blocks = -(-lengths // block)                 # live blocks a row
    row, blk = _named(ahead_dead_steps, lengths, block, steps)
    own = np.arange(steps)[None, :] < blocks[:, None]
    rows = np.broadcast_to(np.arange(b)[:, None], own.shape)
    cols = np.broadcast_to(np.arange(steps)[None, :], own.shape)
    np.testing.assert_array_equal(row[own], rows[own])
    np.testing.assert_array_equal(blk[own], cols[own])
    walk = np.stack([row.ravel(), blk.ravel()], axis=1)
    moves = (walk[1:] != walk[:-1]).any(axis=1).sum()
    assert moves == max(blocks.sum() - 1, 0)
    live = np.flatnonzero(lengths)
    if not live.size:
        assert (walk == walk[0]).all()
        return
    # the steps that read nothing: the next live row's block 0, or the
    # last live row's last block
    for r in range(b):
        ahead = live[live > r]
        want = (ahead[0], 0) if ahead.size \
            else (live[-1], blocks[live[-1]] - 1)
        assert (row[r][~own[r]] == want[0]).all()
        assert (blk[r][~own[r]] == want[1]).all()
    # the parent's rule, which ``_dead_steps`` keeps for the body whose
    # finish is the longer step, named the same block at every step that
    # reads, and moved as often: only where the dead steps wait differs
    lens = jnp.asarray(lengths, jnp.int32)
    for kept, was in zip(ak._dead_steps(lens, block, ahead=False),
                         parent_dead_steps(lens, block)):
        np.testing.assert_array_equal(np.asarray(kept), np.asarray(was))
    was_row, was_blk = _named(parent_dead_steps, lengths, block, steps)
    np.testing.assert_array_equal(was_row[own], row[own])
    np.testing.assert_array_equal(was_blk[own], blk[own])
    was = np.stack([was_row.ravel(), was_blk.ravel()], axis=1)
    assert (was[1:] != was[:-1]).any(axis=1).sum() == moves
    if blocks[live[0]] < steps and live.size > 1:
        assert (was_row[live[0]] == live[0]).all()
        assert row[live[0], -1] == live[1]


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
def test_mxu_body_in_a_pool_with_idle_rows(model, under_rule):
    """Only some rows active: the idle ones, first, between and last,
    read zeros and the live rows what they read alone, bit for bit what
    the parent's maps gave (one block a row, and three of four)."""
    q, k, v = _grouped_operands(model, 6, seed=11)
    lengths = jnp.asarray([0, 0, 300, 0, 17, 0], jnp.int32)
    out = np.asarray(ak.ragged_decode_attention(
        q.astype(jnp.float32), k, v, lengths, interpret=True))
    want = np.asarray(_grouped_oracle(q, k, v, lengths, None))
    for row in (0, 1, 3, 5):
        assert (out[row] == 0).all()
    np.testing.assert_allclose(out[[2, 4]], want[[2, 4]], rtol=6e-3,
                               atol=6e-3)
    for block in (None, 128):
        got = ak.ragged_decode_attention(q.astype(jnp.float32), k, v,
                                         lengths, block_k=block,
                                         interpret=True)
        asked = []
        with under_rule(parent_dead_steps, asked):
            np.testing.assert_array_equal(np.asarray(got), np.asarray(
                ak.ragged_decode_attention(
                    q.astype(jnp.float32), k, v, lengths, block_k=block,
                    interpret=True)))
        assert asked == [True]          # a live step is the longer one


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
            [var.aval.shape for var in call.invars[3:]],
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


def test_latent_body_in_a_pool_with_idle_rows(under_rule):
    ql, qr, rotary, latent = _latent_operands(6, jnp.bfloat16, seed=12)
    lengths = jnp.asarray([0, 0, 300, 0, 17, 0], jnp.int32)
    out = np.asarray(ak.latent_decode_attention(
        ql, qr, rotary, latent, lengths, scale=LATENT_SCALE, interpret=True))
    want = np.asarray(_latent_oracle(ql, qr, rotary, latent, lengths, None))
    for row in (0, 1, 3, 5):
        assert (out[row] == 0).all()
    np.testing.assert_allclose(out[[2, 4]], want[[2, 4]], rtol=6e-3,
                               atol=6e-3)
    # bit for bit what the parent's maps gave, at one block a row and at
    # three of four
    for block in (None, 128):
        got = ak.latent_decode_attention(
            ql, qr, rotary, latent, lengths, scale=LATENT_SCALE,
            block_k=block, interpret=True)
        asked = []
        with under_rule(parent_dead_steps, asked):
            np.testing.assert_array_equal(np.asarray(got), np.asarray(
                ak.latent_decode_attention(
                    ql, qr, rotary, latent, lengths, scale=LATENT_SCALE,
                    block_k=block, interpret=True)))
        assert asked == [True]


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
    # three prefetched scalars a row go ahead: the lengths, and the (row,
    # block) its dead steps name
    assert [v.aval.shape for v in call.invars[:3]] == [(112,)] * 3
    assert [v.aval.shape for v in call.invars[3:]] == [
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


# ---- a prefill chunk over grouped-query rows: live key blocks only ----------
# (d, dv) by how the two leaves lie at rows of CHUNK_T places: both
# width-minor (trinity-mini, Falcon-H1), both positions-minor (LFM2, OPT),
# keys positions-minor with values width-minor (MiMo)
CHUNK_T, CHUNK_W = 768, 16
CHUNK_LEAVES = {"sublanes-sublanes": (128, 128), "lanes-lanes": (64, 64),
                "lanes-sublanes": (192, 128)}
# where a count of blocks could go wrong: the chunk's first position
CHUNK_PLACES = {"first-block": 3, "across-an-edge": 250,
                "last-block": CHUNK_T - CHUNK_W}


@pytest.mark.parametrize("place", sorted(CHUNK_PLACES))
@pytest.mark.parametrize("leaves", sorted(CHUNK_LEAVES))
@pytest.mark.parametrize("group,dtype", [
    (1, jnp.float32), (4, jnp.float32), (5, jnp.bfloat16), (8, jnp.float32),
    (16, jnp.bfloat16)], ids=["g1", "g4", "g5-bf16", "g8", "g16-bf16"])
def test_a_chunk_over_grouped_rows_equals_grouped_attention_over_the_row(
        group, dtype, leaves, place):
    """``ops.chunk_attention`` with ``G`` query heads a key head, over each
    pair of leaf layouts, against ``grouped_attention`` over the whole row
    under the chunk's causal mask and padding flags: the kernel a TPU
    takes (interpreted) and the loop every other backend has.  **Every
    place past the chunk's last block holds NaN** in the leaves the entry
    is handed and zero in the oracle's: a block read in vain would show in
    the result, and none does.  Float32 rows agree to a re-ordered sum; a
    bfloat16 row also rounds a weight under a running maximum where the
    oracle rounds it under the row's."""
    from bigdl_tpu.nn.attention import grouped_attention
    from bigdl_tpu.ops.cache_kernels import cache_row_tiles
    (d, dv), index = CHUNK_LEAVES[leaves], CHUNK_PLACES[place]
    hkv, slots, row = 2, 3, 1
    r = np.random.RandomState(group + index)
    # queries the row's dtype holds exactly, handed over in float32
    q = jnp.asarray(r.randn(1, hkv * group, CHUNK_W, d),
                    dtype).astype(jnp.float32)
    k = jnp.asarray(0.3 * r.randn(slots, hkv, CHUNK_T, d), dtype)
    v = jnp.asarray(r.randn(slots, hkv, CHUNK_T, dv), dtype)
    assert "-".join(cache_row_tiles(a.shape, dtype) for a in (k, v)) == leaves
    pad = np.zeros((slots, CHUNK_T), bool)
    pad[row, 1] = pad[row, index // 2] = pad[row, index + 2] = True
    pad[row + 1] = True                     # another slot's flags: not read
    pad = jnp.asarray(pad)
    block = ak.chunk_key_block(k.shape)
    assert block == ak.CHUNK_KEY_BLOCK
    unread = jnp.arange(CHUNK_T)[None, None, :, None] \
        >= -(-(index + CHUNK_W) // block) * block
    want = grouped_attention(
        q.astype(dtype), jnp.where(unread, 0, k)[row:row + 1],
        jnp.where(unread, 0, v)[row:row + 1],
        index + jnp.arange(CHUNK_W)[None], jnp.arange(CHUNK_T)[None], None,
        pad[row:row + 1])
    tol = dict(rtol=2e-5, atol=2e-5) if dtype == jnp.float32 \
        else dict(rtol=2e-2, atol=2e-2)
    for how in ("ragged", "xla"):
        got = ak.chunk_attention(q, jnp.where(unread, jnp.nan, k),
                                 jnp.where(unread, jnp.nan, v), row, index,
                                 pad, force=how)
        assert got.shape == want.shape and got.dtype == jnp.float32
        np.testing.assert_allclose(np.asarray(got), np.asarray(want), **tol)


def test_the_chunk_body_follows_the_leaves_and_the_group(monkeypatch):
    """One query head a key head over leaves that both lie positions-minor
    keeps ``ragged_chunk_attention`` (OPT's pool traces to the call it
    always did); grouped heads, or a width-minor leaf, take
    ``grouped_chunk_attention``, a tile of whole chunks of a key head's
    queries a grid step; rows that tile for neither are refused when the
    kernel is forced and take the loop otherwise."""
    taken = []
    for name in ("ragged_chunk_attention", "grouped_chunk_attention"):
        def spy(q, *args, _name=name, **kw):
            taken.append(_name)
            return q
        monkeypatch.setattr(ak, name, spy)

    def call(hq, hkv, t, d, dtype=jnp.float32, w=16):
        del taken[:]
        ak.chunk_attention(
            jnp.zeros((1, hq, w, d)), jnp.zeros((2, hkv, t, d), dtype),
            jnp.zeros((2, hkv, t, d), dtype), 0, 0, jnp.zeros((2, t), bool),
            force="ragged")
        return taken[0]
    assert call(32, 32, 2048, 64) == "ragged_chunk_attention"
    assert call(32, 8, 5632, 64) == "grouped_chunk_attention"
    assert call(4, 4, 512, 128) == "grouped_chunk_attention"
    with pytest.raises(ValueError):       # rows of 100 places tile no way
        call(8, 2, 100, 64)
    with pytest.raises(ValueError):       # 12 bfloat16 rows a product
        call(3, 1, 512, 128, jnp.bfloat16, w=4)
    with pytest.raises(ValueError):       # 5 query heads over 2 key heads
        call(5, 2, 512, 128)
    # a product of up to 256 rows, whole chunks or a whole part of one; a
    # tile of up to 2,048, whole chunks
    assert ak._grouped_chunk_tiles(16, 256) == (2048, 256)
    assert ak._grouped_chunk_tiles(5, 256) == (1280, 256)
    assert ak._grouped_chunk_tiles(5, 64) == (320, 64)
    assert ak._grouped_chunk_tiles(8, 32) == (256, 256)
    assert ak._grouped_chunk_tiles(1, 512) == (512, 256)


@pytest.mark.parametrize("how", ["ragged", "xla"])
def test_grouped_query_attention_chunk_reads_live_blocks_of_full_rows_only(
        how, monkeypatch):
    """``GroupedQueryAttention.forward`` for a chunk (scalar ``index``,
    ``slot``) with the head norms, the gate, a key scale and rotation on:
    through ``ops.chunk_attention`` (the kernel a TPU takes, interpreted,
    and the loop) it gives what it gave through ``grouped_attention`` over
    the slot's whole row, and writes the same leaves; a window layer and a
    layer with a sink still answer None and never ask for it."""
    from bigdl_tpu.nn.attention import GroupedQueryAttention
    slots, max_len, width, index, slot = 3, 512, 16, 250, 1
    kinds = {"full": {}, "window": dict(window=64),
             "sink": dict(sink=True)}
    calls = []
    entry = ak.chunk_attention

    def spy(*args):
        calls.append(args[4])
        return entry(*args, force=how)
    monkeypatch.setattr(ak, "chunk_attention", spy)
    for kind, over in kinds.items():
        layer = GroupedQueryAttention(
            64, 8, 2, 128, rope_theta=1e4, rotary_dim=32, value_scale=0.5,
            key_scale=0.7, qk_norm=True, gate=True, **over)
        r = np.random.RandomState(7)
        for lin in (layer.q_layer, layer.k_layer, layer.v_layer,
                    layer.output_layer, layer.gate_layer):
            lin.weight = jnp.asarray(
                0.2 * r.randn(*lin.weight.shape), jnp.float32)
        x = jnp.asarray(r.randn(1, width, 64), jnp.float32)
        cache = {n: jnp.asarray(r.randn(*leaf.shape), jnp.float32)
                 for n, leaf in layer.init_cache(
                     slots, max_len, ring_margin=width).items()}
        pad = jnp.asarray(r.rand(slots, max_len) < 0.2).at[:, 0].set(False)
        del calls[:]
        got, kv_got = layer.forward(x, index, cache, pad, slot=slot)
        if kind != "full":
            assert calls == [] and layer.chunk_key_block(cache) is None
            continue
        assert calls == [index] and layer.chunk_key_block(cache) == 256
        with monkeypatch.context() as m:
            m.setattr(GroupedQueryAttention, "chunk_key_block",
                      lambda self, cache: None)
            want, kv_want = layer.forward(x, index, cache, pad, slot=slot)
        assert calls == [index]
        for n in ("k", "v"):
            assert np.array_equal(np.asarray(kv_got[n]),
                                  np.asarray(kv_want[n]))
        np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                                   rtol=2e-5, atol=2e-5)
