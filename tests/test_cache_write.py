"""The row-write kernel of the pooled decode step (``ops.write_cache_rows``)
in interpret mode against what it replaces on a TPU: one
``dynamic_update_slice`` a row and leaf (``nn.attention._write_rows``'s
static loop, the path a CPU process takes).  The kernel copies values and
never computes with them, so the caches are compared bit for bit.
``tests/test_tpu_compile.py`` compiles the decode step that holds it for a
described v5e; what it costs there is a chip run's to say."""

import functools

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from bigdl_tpu.models import mimo_v2
from bigdl_tpu.nn import attention as att
from bigdl_tpu.ops import attention_kernels, cache_kernels
from bigdl_tpu.serving.generation import GenerationScheduler

# MiMo-V2.5's widths: keys 192 (stored positions-minor: a tile of 128
# positions), values 128 (width-minor: a tile of 8 or 16 positions)
D, DV = 192, 128
# places of a row: the first and last position of a 128-position tile and of
# a 16-position one (which holds the 8-position tile's edges too), and the
# row's end
LENGTH = {"full": 256, "ring": 384}


def _bits(a):
    return np.asarray(jax.lax.bitcast_convert_type(
        a, jnp.uint16 if a.dtype == jnp.bfloat16 else jnp.uint32))


def _force_kernel(monkeypatch):
    """The writer a TPU process would choose, run interpreted: the code
    under test asks ``cache_row_writer`` which path to take and
    ``_on_tpu()`` whether to interpret."""
    monkeypatch.setattr(
        cache_kernels, "cache_row_writer",
        functools.partial(cache_kernels.cache_row_writer, force="kernel"))


@pytest.mark.parametrize("heads", [4, 8])
@pytest.mark.parametrize("dtype", [jnp.bfloat16, jnp.float32],
                         ids=["bf16", "f32"])
@pytest.mark.parametrize("kind", ["full", "ring"])
def test_kernel_writes_bit_for_bit_what_the_loop_writes(
        kind, dtype, heads, monkeypatch):
    """Rows written at the edges of both tiles, at ``max_len - 1`` (where
    a full row that only rides along writes), at a ring's last ring place
    and at its spare place (where a ring row that only rides along
    writes): every place of both leaves holds the bits the loop leaves."""
    length = LENGTH[kind]
    place = [0, 15, 16, 127, 128, 8, 7, length - 2, length - 1, 200]
    r = np.random.RandomState(heads + length)
    rows = len(place)
    cache = {"k": jnp.asarray(r.randn(rows, heads, length, D), dtype),
             "v": jnp.asarray(r.randn(rows, heads, length, DV), dtype)}
    # float32 news, as the projections make them: both paths round once
    k = jnp.asarray(r.randn(rows, heads, 1, D), jnp.float32)
    v = jnp.asarray(r.randn(rows, heads, 1, DV), jnp.float32)
    k = k.at[0, 0, 0, :3].set(jnp.asarray([-0.0, jnp.inf, -jnp.inf]))
    place = jnp.asarray(place, jnp.int32)
    want = att._write_rows(cache, k, v, place)
    _force_kernel(monkeypatch)
    assert cache_kernels.cache_row_writer(
        cache["k"].shape, cache["v"].shape, dtype) == ("lanes", "sublanes")
    got = jax.jit(att._write_rows)(cache, k, v, place)
    for n in ("k", "v"):
        assert got[n].dtype == dtype
        assert np.array_equal(_bits(got[n]), _bits(want[n])), n
        # and the loop wrote what it was given, where it was told
        new = (k if n == "k" else v).astype(dtype)
        for b, p in enumerate(np.asarray(place)):
            assert np.array_equal(_bits(want[n][b, :, p]), _bits(new[b, :, 0]))


@pytest.mark.parametrize("window", [None, 128], ids=["full", "ring"])
def test_a_decode_step_through_the_kernel_leaves_the_loops_cache(
        window, monkeypatch):
    """``GroupedQueryAttention.forward`` on a position a row, one row only
    riding along: the cache after the step is the loop's bit for bit (a
    ring's idle row wrote its spare place, a full row's where the caller
    sent it) and so is what the step attended."""
    layer = att.GroupedQueryAttention(64, 8, 2, 32, v_head_dim=16,
                                      window=window, rotary_dim=8,
                                      sink=window is not None)
    max_len = 256
    cache = layer.init_cache(4, max_len, jnp.bfloat16, ring_margin=128)
    r = np.random.RandomState(3)
    cache = {n: jnp.asarray(r.randn(*leaf.shape), leaf.dtype)
             for n, leaf in cache.items()}
    x = jnp.asarray(r.randn(4, 1, 64), jnp.bfloat16)
    index = jnp.asarray([5, 130, 255, 254], jnp.int32)
    active = jnp.asarray([True, True, False, True])
    step = jax.jit(lambda c: layer.forward(x, index, c, active=active))
    y_loop, kv_loop = step(cache)
    _force_kernel(monkeypatch)
    y, kv = jax.jit(lambda c: layer.forward(x, index, c, active=active))(cache)
    for n in ("k", "v"):
        assert np.array_equal(_bits(kv[n]), _bits(kv_loop[n])), n
    assert np.array_equal(np.asarray(y), np.asarray(y_loop))
    if window is not None:
        # the idle row wrote the spare place and nothing else
        spare = kv["k"].shape[2] - 1
        assert np.array_equal(_bits(kv["k"][2, :, :spare]),
                              _bits(cache["k"][2, :, :spare]))
        assert not np.array_equal(_bits(kv["k"][2, :, spare]),
                                  _bits(cache["k"][2, :, spare]))


def test_leaves_that_do_not_tile_keep_the_loop(monkeypatch):
    """What the code sees decides: off a TPU the loop, whatever the
    shapes; on one, the kernel where both leaves tile and the loop where
    one does not (positions off the tile, a width off the sublanes, a
    tile too large for its share of VMEM).  The traced step then holds
    ``dynamic_update_slice`` and no kernel."""
    tiled = ((4, 2, 256, 192), (4, 2, 256, 128))
    writer = cache_kernels.cache_row_writer
    assert writer(*tiled, jnp.bfloat16) is None          # this process: CPU
    monkeypatch.setattr(attention_kernels, "_on_tpu", lambda: True)
    assert writer(*tiled, jnp.bfloat16) == ("lanes", "sublanes")
    assert writer((4, 2, 200, 128), (4, 2, 200, 128), jnp.float32) \
        == ("sublanes", "sublanes")
    for k_shape, v_shape, dtype in [
            ((4, 2, 100, 192), (4, 2, 100, 128), jnp.float32),   # k: no tile
            ((4, 2, 264, 128), (4, 2, 264, 128), jnp.bfloat16),  # 264 % 16
            ((4, 2, 256, 100), (4, 2, 256, 128), jnp.float32),   # 100 % 8
            ((4, 64, 2048, 192), (4, 64, 2048, 128), jnp.float32)]:  # VMEM
        assert writer(k_shape, v_shape, dtype) is None
        with pytest.raises(ValueError, match="do not tile"):
            writer(k_shape, v_shape, dtype, force="kernel")
    assert writer(*tiled, jnp.bfloat16, force="loop") is None
    cache = {"k": jnp.zeros((3, 2, 100, 192)),
             "v": jnp.zeros((3, 2, 100, 128))}
    text = str(jax.make_jaxpr(att._write_rows)(
        cache, jnp.ones((3, 2, 1, 192)), jnp.ones((3, 2, 1, 128)),
        jnp.asarray([0, 50, 99], jnp.int32)))
    assert text.count("dynamic_update_slice") == 2 * 3
    assert "pallas_call" not in text
    with pytest.raises(ValueError, match="one new position a row"):
        cache_kernels.write_cache_rows(
            jnp.zeros((3, 2, 256, 192)), jnp.zeros((3, 2, 256, 128)),
            jnp.ones((3, 2, 2, 192)), jnp.ones((3, 2, 1, 128)),
            jnp.zeros((3,), jnp.int32), interpret=True)


# ---- through the model and the pool -----------------------------------------

WINDOW, CHUNK, MAX_LEN, VOCAB = 64, 64, 128, 40
LAYERS = 3


def _small_decoder():
    """A full dense layer and two window layers of experts (the pool
    takes a model of this kind by its expert layers) whose float32 leaves
    tile: 128 positions a full row and a ring (window 64, a chunk of 64 and
    the spare place), keys 16 wide and values 8."""
    cfg = dict(vocab_size=VOCAB, hidden_size=32, num_hidden_layers=LAYERS,
               hybrid_layer_pattern=[0, 1, 1], moe_layer_freq=[0, 1, 1],
               num_attention_heads=4, num_key_value_heads=1,
               swa_num_key_value_heads=2, head_dim=16, v_head_dim=8,
               partial_rotary_factor=0.5, rope_theta=1e6, swa_rope_theta=1e4,
               sliding_window=WINDOW, attention_value_scale=0.707,
               add_swa_attention_sink_bias=True, intermediate_size=48,
               moe_intermediate_size=16, n_routed_experts=4,
               num_experts_per_tok=2, norm_topk_prob=True,
               layernorm_epsilon=1e-5)
    return mimo_v2(cfg, MAX_LEN).eval_mode()


def test_decode_step_flags_equal_the_loops():
    """The padding flags after a per-row step are the old flags with each
    row's one position replaced, an idle row's at ``max_len - 1``: what a
    ``dynamic_update_slice`` a row wrote."""
    m = _small_decoder()
    rows = 5
    caches = m.init_cache(rows, ring_margin=CHUNK)
    r = np.random.RandomState(0)
    old = r.rand(rows, MAX_LEN) < 0.3
    caches["pad"] = jnp.asarray(old)
    tokens = jnp.asarray([[3], [0], [7], [0], [9]], jnp.int32)
    index = jnp.asarray([0, 17, MAX_LEN - 1, 64, 5], jnp.int32)
    active = jnp.asarray([True, True, True, True, False])
    _, new, _ = jax.jit(m.decode_step)(tokens, index, caches, active=active)
    want = old.copy()
    for b, (p, a) in enumerate(zip(np.asarray(index), np.asarray(active))):
        want[b, p if a else MAX_LEN - 1] = int(tokens[b, 0]) == 0
    assert np.array_equal(np.asarray(new["pad"]), want)
    text = str(jax.make_jaxpr(
        lambda t, i, c: m.decode_step(t, i, c, active=active))(
            tokens, index, caches))
    assert text.count("dynamic_update_slice") == rows * 2 * LAYERS


@pytest.mark.parametrize("kernel", [False, True], ids=["loop", "kernel"])
def test_the_pool_counts_the_programs_that_write_its_cache(
        kernel, monkeypatch):
    """``SlotPool.cache_write_programs`` is the device programs of a decode
    step that write the cache: on the loop a ``dynamic_update_slice`` a
    slot and leaf and the flags' one select, on the kernel one program a
    layer and that select.  The tokens are the same either way."""
    if kernel:
        _force_kernel(monkeypatch)
    slots = 2
    m = _small_decoder()
    prompt = np.arange(1, 71, dtype=np.int32) % VOCAB + 1
    eng = GenerationScheduler(m, slots=slots, prefill_chunk=CHUNK)
    try:
        assert "cache_write_programs" not in eng.stats()
        row = eng.submit_async(prompt, 10).result(timeout=300)
        st = eng.stats()
        a_step = eng.pool.cache_write_programs
    finally:
        eng.shutdown()
    assert a_step == (LAYERS + 1 if kernel else slots * 2 * LAYERS + 1)
    assert st["decode_dispatches"] >= 10
    want = np.asarray(m.generate(jnp.asarray(prompt)[None], 10, chunk=CHUNK))
    assert np.array_equal(row, want[0])


def test_a_model_that_does_not_say_writes_a_slot_and_leaf_at_a_time():
    """``TransformerLM`` keeps its own loop: keys, values and flags of
    every layer, a ``dynamic_update_slice`` a slot each."""
    from bigdl_tpu.models import transformer_lm
    from bigdl_tpu.serving.generation import SlotPool
    lm = transformer_lm(vocab_size=20, num_layers=2, hidden_size=16,
                        num_heads=2, filter_size=32, max_len=32).eval_mode()
    assert not hasattr(lm, "cache_write_programs")
    assert SlotPool(lm, slots=3).cache_write_programs == 3 * (2 * 2 + 1)
