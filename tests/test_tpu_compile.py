"""The Pallas kernels of the main path, compiled for a v5e that is
described and not attached (``/opt/skills/guides/on-chip-measurement``
§2.3): the TPU compiler is installed wherever libtpu is, and it refuses
here what it would refuse on the chip — a slice off the tiling, too much
VMEM, a kernel that cannot be lowered.  Interpret-mode tests cannot see
any of that.  Nothing runs: a compile that passes says nothing about
results or times, and is never reported as a chip run.

Shapes are the ones the main path uses at real width: the transformer LM
of ``chip_smoke.py`` and the four ResNet-50 stages at batch 128.

The serving slot pool's donated programs are compiled the same way and
held to "no copy of a whole pool leaf": a relayout the compiler puts
inside a program is invisible to every CPU test and to the input/output
alias, and cost three quarters of a served token's time before PR 27.

Loading libtpu takes its multi-process lock for the life of the process,
so two processes that compile for a described chip cannot overlap; the
CPU-platform workers of test_distributed_multiprocess.py never load it.
"""

import collections
import functools
import os
import re

os.environ.setdefault("TPU_LOG_DIR", "disabled")

import jax
import jax.numpy as jnp
import pytest
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P
from jax.sharding import SingleDeviceSharding

from bigdl_tpu.nn.moe import ROUTING
from bigdl_tpu.ops import cache_kernels
from bigdl_tpu.ops import conv_bn_kernels as ck
from bigdl_tpu.ops.attention_kernels import (_grouped_chunk_takes,
                                             flash_attention,
                                             grouped_chunk_attention,
                                             latent_chunk_attention,
                                             latent_chunk_takes,
                                             ragged_decode_attention)


@pytest.fixture(scope="module")
def v5e():
    """The described 2x2 v5e topology, with the persistent compile cache
    off: an entry compiled for a described chip is written to the cache
    but cannot be read back without one."""
    from jax.experimental import topologies
    from jax.experimental.compilation_cache import compilation_cache
    try:
        topo = topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:  # no libtpu on this machine
        pytest.skip(f"cannot describe a v5e here: {e}")
    was = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    yield topo
    jax.config.update("jax_enable_compilation_cache", was)
    compilation_cache.reset_cache()


def _compile(fn, args):
    return jax.jit(fn).lower(*args).compile()


def _sum32(tree):
    return sum(jnp.sum(leaf.astype(jnp.float32))
               for leaf in jax.tree_util.tree_leaves(tree))


# (B, H, T, D): the largest flash shape with chip history, the LM of
# chip_smoke.py, and a 128-wide head
FLASH_SHAPES = [(4, 16, 4096, 64), (8, 8, 2048, 64), (2, 8, 1024, 128)]
# ResNet-50 at batch 128: (M = B*H*W, K, N) of each stage's 1x1 conv1
MATMUL_SHAPES = [(401408, 256, 64), (100352, 512, 128),
                 (25088, 1024, 256), (6272, 2048, 512)]
# (H = W, C = Cout) of the stride-1 3x3 conv2 in stages 1-3
CONV3_SHAPES = [(56, 64), (28, 128), (14, 256)]


def _flash_case(shape, bias, backward):
    def build(sds):
        b, h, t, d = shape
        q = sds(shape, jnp.bfloat16)
        args = [q, q, q]
        if bias:
            args.append(sds((1, 1, t, t), jnp.bfloat16))

        def fwd(q, k, v, *rest):
            return flash_attention(q, k, v, *rest, causal=True)

        if not backward:
            return fwd, args
        return jax.grad(lambda *a: _sum32(fwd(*a)), argnums=(0, 1, 2)), args
    name = "flash-{}-{}{}".format(
        "bwd" if backward else "fwd", "x".join(map(str, shape)),
        "-bias" if bias else "")
    return pytest.param(build, id=name)


def _matmul_case(shape, backward):
    def build(sds):
        m, k, n = shape
        vec = sds((k,), jnp.float32)
        args = [sds((m, k), jnp.bfloat16), sds((k, n), jnp.bfloat16),
                vec, vec, vec, sds((n,), jnp.float32)]

        def fwd(x, w, mean, scale, beta, kshift):
            return ck.fused_matmul_bn(x, w, norm=(mean, scale, beta),
                                      kshift=kshift)

        if not backward:
            return fwd, args
        return jax.grad(lambda *a: _sum32(fwd(*a)), argnums=(0, 1)), args
    name = "matmul_bn-{}-{}".format("bwd" if backward else "fwd",
                                    "x".join(map(str, shape)))
    return pytest.param(build, id=name)


def _conv3_case(shape, backward):
    def build(sds):
        hw, c = shape
        vec = sds((c,), jnp.float32)
        args = [sds((128, hw, hw, c), jnp.bfloat16),
                sds((3, 3, c, c), jnp.bfloat16), vec, vec, vec, vec]

        def fwd(x, w, mean, scale, beta, kshift):
            return ck.fused_conv3x3_bn(x, w, norm=(mean, scale, beta),
                                       kshift=kshift)

        if not backward:
            return fwd, args
        return jax.grad(lambda *a: _sum32(fwd(*a)), argnums=(0, 1)), args
    name = "conv3x3_bn-{}-{}x{}".format("bwd" if backward else "fwd",
                                        *shape)
    return pytest.param(build, id=name)


def _decode_case(slots, hq, hkv, t, d, dv, dtype):
    """The ragged decode kernel over a pool leaf ``[slots, hkv, t, d]``,
    handed over as the pool holds it."""
    def build(sds):
        args = [sds((slots, hq, 1, d), jnp.bfloat16),
                sds((slots, hkv, t, d), dtype), sds((slots, hkv, t, dv), dtype),
                sds((slots,), jnp.int32), sds((slots, t), jnp.bool_)]
        return ragged_decode_attention, args
    name = "ragged_decode-%dx%dq%dkv-%dx%dx%d-%s" % (
        slots, hq, hkv, t, d, dv, jnp.dtype(dtype).name)
    return pytest.param(build, id=name)


# the pool leaves the benchmark serves: OPT-1.3B's (one query head a key
# head, float32, both leaves positions-minor: the vector-unit body), the
# falcon-h1-34b cut's (20 query heads over 4, both leaves width-minor) and
# the mimo-v2.5 cut's full layer (64 over 4, keys of 192 positions-minor,
# values of 128 width-minor); the last two take the MXU body
DECODE_CASES = [_decode_case(6, 32, 32, 2048, 64, 64, jnp.float32),
                _decode_case(48, 20, 4, 3584, 128, 128, jnp.bfloat16),
                _decode_case(32, 64, 4, 6144, 192, 128, jnp.bfloat16)]

def _latent_chunk_case(width, heads, slots, t, r, dr, dtype):
    """The latent chunk kernel: ``width`` queries a head over the live key
    blocks of a row of the pooled latent leaves ``[slots, 1, t, r]`` and
    ``[slots, 1, t, dr]``, handed over as the pool holds them."""
    def build(sds):
        args = [sds((1, heads, width, r), dtype),
                sds((1, heads, width, dr), dtype),
                sds((slots, 1, t, r), dtype), sds((slots, 1, t, dr), dtype),
                sds((), jnp.int32), sds((), jnp.int32),
                sds((1, t), jnp.bool_)]
        assert latent_chunk_takes(args[0].shape, args[2].shape,
                                  args[3].shape, dtype, 512, force="kernel")
        return functools.partial(latent_chunk_attention, scale=0.135,
                                 block=512), args
    name = "latent_chunk-%dx%d-%dx%d+%d-%s" % (
        width, heads, t, r, dr, jnp.dtype(dtype).name)
    return pytest.param(build, id=name)


# the sarvam-105b cut's chunk at the pool's full width and at the narrowest
# of its joint widths: 64 heads over one 512-wide latent and one 64-wide
# rotary key a place
LATENT_CHUNK_CASES = [
    _latent_chunk_case(256, 64, 112, 7168, 512, 64, jnp.bfloat16),
    _latent_chunk_case(32, 64, 112, 7168, 512, 64, jnp.bfloat16)]


def _grouped_chunk_case(width, hq, hkv, slots, t, d, dv, dtype):
    """The grouped chunk kernel: ``width`` float32 queries a head, ``hq``
    heads over ``hkv``, over the live key blocks of a row of the pooled
    leaves ``[slots, hkv, t, d]`` and ``[slots, hkv, t, dv]``, each handed
    over as the pool holds it."""
    def build(sds):
        args = [sds((1, hq, width, d), jnp.float32),
                sds((slots, hkv, t, d), dtype), sds((slots, hkv, t, dv), dtype),
                sds((), jnp.int32), sds((), jnp.int32),
                sds((slots, t), jnp.bool_)]
        assert _grouped_chunk_takes(args[0].shape, args[1].shape,
                                    args[2].shape, dtype, 256)
        return functools.partial(grouped_chunk_attention, block=256), args
    name = "grouped_chunk-%dx%dq%dkv-%dx%dx%d-%s" % (
        width, hq, hkv, t, d, dv, jnp.dtype(dtype).name)
    return pytest.param(build, id=name)


# the full layers whose chunk the benchmark serves at the pool's full
# width: the trinity-mini cut's (32 heads over 4, both leaves width-minor),
# the mimo-v2.5 cut's (64 over 4, keys of 192 positions-minor, values of 128
# width-minor) and the lfm2-24b-a2b cut's (32 over 8, both positions-minor)
GROUPED_CHUNK_CASES = [
    _grouped_chunk_case(256, 32, 4, 96, 14336, 128, 128, jnp.bfloat16),
    _grouped_chunk_case(256, 64, 4, 32, 6144, 192, 128, jnp.bfloat16),
    _grouped_chunk_case(256, 32, 8, 128, 5632, 64, 64, jnp.bfloat16)]


def _row_write_case(slots, heads, t, d, dv, dtype):
    """The row-write kernel over a layer's two leaves, each handed over as
    the chip stores it (``cache_row_writer`` says how)."""
    def build(sds):
        k, v = (slots, heads, t, d), (slots, heads, t, dv)
        tiles = cache_kernels.cache_row_writer(k, v, dtype, force="kernel")
        args = [sds(k, dtype), sds(v, dtype), sds((slots, heads, 1, d), dtype),
                sds((slots, heads, 1, dv), dtype), sds((slots,), jnp.int32)]
        return functools.partial(cache_kernels.write_cache_rows,
                                 tiles=tiles), args
    name = "row_write-%dx%d-%dx%dx%d-%s" % (
        slots, heads, t, d, dv, jnp.dtype(dtype).name)
    return pytest.param(build, id=name)


# the mimo-v2.5 cut's full layer and ring (keys positions-minor, values
# width-minor), and OPT-1.3B's float32 leaf (both positions-minor), which
# no program hands the kernel yet
ROW_WRITE_CASES = [_row_write_case(32, 4, 6144, 192, 128, jnp.bfloat16),
                   _row_write_case(32, 8, 384, 192, 128, jnp.bfloat16),
                   _row_write_case(6, 32, 2048, 64, 64, jnp.float32)]

CASES = (
    [_flash_case(s, bias, bwd) for s in FLASH_SHAPES
     for bias in (False, True) for bwd in (False, True)]
    + DECODE_CASES + LATENT_CHUNK_CASES + GROUPED_CHUNK_CASES
    + ROW_WRITE_CASES
    + [_matmul_case(s, bwd) for s in MATMUL_SHAPES for bwd in (False, True)]
    + [_conv3_case(s, bwd) for s in CONV3_SHAPES for bwd in (False, True)]
)


@pytest.mark.parametrize("build", CASES)
def test_kernel_compiles_for_v5e(v5e, build):
    one_chip = SingleDeviceSharding(v5e.devices[0])
    fn, args = build(lambda shape, dtype: jax.ShapeDtypeStruct(
        shape, dtype, sharding=one_chip))
    compiled = _compile(fn, args)
    assert "tpu_custom_call" in compiled.as_text()


def test_conv3x3_stage4_is_refused_before_the_compiler():
    """H = W = 7, C = 512 (ResNet-50's last stage) has no block the
    kernel accepts: the model asks ``fused_conv3x3_supported`` first and
    keeps that conv on the XLA emitter; the kernel itself raises rather
    than hand Mosaic a tiling it would refuse."""
    assert not ck.fused_conv3x3_supported(7, 7, 512, 512, 2)
    x = jax.ShapeDtypeStruct((128, 7, 7, 512), jnp.bfloat16)
    w = jax.ShapeDtypeStruct((3, 3, 512, 512), jnp.bfloat16)
    k = jax.ShapeDtypeStruct((512,), jnp.float32)
    with pytest.raises(ValueError):
        jax.eval_shape(
            lambda x, w, k: ck.fused_conv3x3_bn(x, w, kshift=k), x, w, k)


def test_ring_attention_flash_blocks_compile_on_four_chips(
        v5e, monkeypatch):
    """The ring's per-step block path (parallel/ring_attention.py) on a
    mesh over the four described chips: T=8192 split four ways gives the
    2048-token chunks the flash-partial kernels tile.  The code under
    test asks ``_on_tpu()`` to choose kernel and interpret mode; here
    that answer is steered by the test, since the process's own backend
    is the CPU."""
    from bigdl_tpu.ops import attention_kernels
    from bigdl_tpu.parallel.ring_attention import ring_self_attention
    monkeypatch.setattr(attention_kernels, "_on_tpu", lambda: True)
    mesh = Mesh(v5e.devices, ("seq",))
    q = jax.ShapeDtypeStruct(
        (2, 8, 8192, 64), jnp.bfloat16,
        sharding=NamedSharding(mesh, P(None, None, "seq", None)))

    def loss(q, k, v):
        return _sum32(ring_self_attention(q, k, v, mesh, "seq",
                                          causal=True))

    compiled = _compile(jax.grad(loss, argnums=(0, 1, 2)), [q, q, q])
    text = compiled.as_text()
    assert "tpu_custom_call" in text
    assert "collective-permute" in text


# ---------------------------------------------------------------------------
# The slot pool's donated programs: no relayout of a pool leaf
# ---------------------------------------------------------------------------

POOL_SLOTS, POOL_HEADS, POOL_HEAD_DIM, POOL_MAX_LEN = 6, 2, 64, 2048
POOL_PROGRAMS = ["decode", "chunk_prefill", "scatter", "kv_copy"]


def _pool_leaf_ops(text, ops):
    """Instructions of the optimized HLO ``text`` named in ``ops`` whose
    result has a pool leaf's shape, with or without the unit axis a
    vmapped program inserts behind the slots."""
    leaf = r"f32\[%d,(?:1,)?%d,(?:%d,%d|%d,%d)\]" % (
        POOL_SLOTS, POOL_HEADS, POOL_MAX_LEN, POOL_HEAD_DIM,
        POOL_HEAD_DIM, POOL_MAX_LEN)
    return re.findall(r"= %s\S* (?:%s)\(" % (leaf, "|".join(ops)), text)


@pytest.fixture(scope="module")
def pool():
    """A slot pool at head size 64 and 2,048 positions: the smallest
    whose leaves the TPU compiler stores positions-minor (``{2,3,1,0}``:
    64 would fill half a lane tile), which is what made the vmapped
    decode step transpose them.  Two heads, one layer: 12 MB."""
    return _fixture_pool()


def _fixture_pool(layers=1):
    from bigdl_tpu.models import transformer_lm
    from bigdl_tpu.serving.generation import SlotPool
    lm = transformer_lm(vocab_size=30, num_layers=layers,
                        hidden_size=POOL_HEADS * POOL_HEAD_DIM,
                        num_heads=POOL_HEADS, filter_size=256,
                        max_len=POOL_MAX_LEN)
    return SlotPool(lm, slots=POOL_SLOTS)


def _lower_pool_program(pool, program, sharding=None):
    """``program`` of ``pool`` lowered on abstract arguments, placed on
    ``sharding`` when given (a described chip) and on the process's own
    backend otherwise."""
    def sds(shape, dtype):
        return jax.ShapeDtypeStruct(shape, dtype, sharding=sharding)

    def aval(tree):
        return jax.tree_util.tree_map(
            lambda a: sds(a.shape, a.dtype), tree)

    model, caches = aval(pool.model), aval(pool.caches)
    scalar = sds((), jnp.int32)
    h, d, s = POOL_HEADS, POOL_HEAD_DIM, pool.slots

    def rows(lead, t):
        return [{"k": sds(lead + (h, t, d), jnp.float32),
                 "v": sds(lead + (h, t, d), jnp.float32)}
                for _ in pool.caches["layers"]]

    if program == "decode":
        return pool._decode_jit.lower(
            model, caches, sds((s,), jnp.int32), sds((s,), jnp.int32),
            sds((s,), jnp.bool_), aval(pool._routing))
    if program == "chunk_prefill":
        return pool._chunk_jit.lower(
            model, caches, scalar, sds((64,), jnp.int32), scalar,
            aval(pool._routing))
    if program == "scatter":
        b = pool.prefill_batch
        return pool._scatter_jit.lower(
            caches, sds((b,), jnp.int32), rows((b,), 255),
            sds((b, 255), jnp.bool_))
    assert program == "kv_copy"
    return pool._kv_copy_jit.lower(
        caches, scalar, rows((), 64), sds((64,), jnp.bool_), scalar)


@pytest.mark.parametrize("program", POOL_PROGRAMS)
def test_pool_program_holds_no_pool_sized_copy_on_v5e(v5e, pool, program):
    """Compiled for the described v5e, no donated pool program copies a
    whole K or V leaf, and the decode step holds no ``while`` (what the
    compiler makes of a scatter over the pool).  The vmapped decode step
    this replaced held four such copies per layer and one loop per
    scatter; the chunk program never did, which is how it was known
    that they could go."""
    text = _lower_pool_program(
        pool, program, SingleDeviceSharding(v5e.devices[0])
    ).compile().as_text()
    assert "dynamic-update-slice" in text or "scatter" in text
    assert not _pool_leaf_ops(text, ["copy", "copy-start"])
    if program == "decode":
        assert " while(" not in text


@pytest.mark.parametrize("program", ["decode", "chunk_prefill"])
def test_pool_program_with_its_kernel_relayouts_no_leaf_on_v5e(
        v5e, monkeypatch, program):
    """The decode step and the chunk program as a TPU process traces them
    — attention through the ragged decode kernel and through the chunk
    kernel, which want their operands row-major — still hold no copy,
    transpose or ``while`` of a pool leaf's size: a kernel is handed keys
    and values positions-minor, which is how the leaf lies, so the change
    of axes is a ``bitcast``.  A fresh pool: the fixture's programs may
    already be traced the other way; of two layers, since the chunk
    program returns no logits and what the last layer attends is nobody's
    to read.  Which path a process takes it asks ``_on_tpu()``; here the
    test answers."""
    from bigdl_tpu.ops import attention_kernels
    monkeypatch.setattr(attention_kernels, "_on_tpu", lambda: True)
    pool = _fixture_pool(layers=2)
    assert pool.key_block == 512            # two heads of 64: 1 KB a place
    assert pool.chunk_key_block == 256
    text = _lower_pool_program(
        pool, program, SingleDeviceSharding(v5e.devices[0])
    ).compile().as_text()
    calls = 2 if program == "decode" else 1
    assert text.count('custom_call_target="tpu_custom_call"') == calls
    assert "dynamic-update-slice" in text
    assert not _pool_leaf_ops(text, ["copy", "copy-start", "transpose",
                                     "convert"])
    assert len(_pool_leaf_ops(text, ["bitcast"])) == 2 * calls
    assert " while(" not in text


def test_opt_pool_leaf_keeps_the_vector_unit_body_and_its_block_on_v5e(v5e):
    """OPT-1.3B's pool leaf as the benchmark serves it (6 slots, 32 heads
    of 64, 2,048 places, float32) goes through the call it has had since
    PR 31, whatever bodies the kernel has gained for other leaves: the
    queries with their width on the sublanes (``[6, 64, 32]`` in, the same
    out: the vector-unit body's operands, where the MXU body takes
    ``[6, 32, 8, 64]``), both leaves positions-minor by a ``bitcast``, a
    key block of 256 (eight steps a row), and the blocks and scratch of
    that body and block in VMEM (the MXU body would hold a twentieth of
    the scratch, a block of 128 half the blocks)."""
    from bigdl_tpu.ops import attention_kernels
    one_chip = SingleDeviceSharding(v5e.devices[0])
    fn, args = DECODE_CASES[0].values[0](
        lambda shape, dtype: jax.ShapeDtypeStruct(shape, dtype,
                                                  sharding=one_chip))
    k, v = args[1], args[2]
    assert attention_kernels._decode_block(k.shape, v.shape, k.dtype) == 256
    text = _compile(fn, args).as_text()
    call, = [line for line in text.splitlines()
             if 'custom_call_target="tpu_custom_call"' in line]
    assert re.search(r"= bf16\[6,64,32\]\S* custom-call\(", call)
    # three prefetched scalars a row (the lengths and what a dead step
    # names), then the body's four operands
    assert ("{s32[6]{0}, s32[6]{0}, s32[6]{0}, f32[6,64,32]{2,1,0}, "
            "f32[6,32,64,2048]{3,2,1,0}, f32[6,32,64,2048]{3,2,1,0}, "
            "f32[6,1,2048]{2,1,0}}") in call
    assert len(re.findall(r"= f32\[6,32,64,2048\]\S* bitcast\(", text)) == 2
    assert not re.findall(
        r"= f32\[6,32,(?:64,2048|2048,64)\]\S* (?:copy|transpose)\(", text)
    used, = re.findall(r'"used_scoped_memory_configs":\[\{"memory_space":"1",'
                       r'"offset":"0","size":"(\d+)"\}\]', call)
    # K and V blocks of 256 places twice over: 8 MiB; the body's scratch
    # (scores, weights and a lane-wise context a query head): 1.1 MiB
    assert 9.0e6 < int(used) < 9.8e6, used


def test_opt_pool_joint_program_reads_each_layers_weights_once_on_v5e(
        v5e, monkeypatch):
    """OPT-1.3B's pool as the benchmark serves it (6 slots of 2,048
    places, float32 rows, bfloat16 weights; model and rows as shapes),
    its decode step that carries a 64-token chunk
    (``TransformerLM.decode_step_with_chunk``) as a TPU process traces it,
    compiled for the described v5e: no copy, transpose or conversion of a
    whole pool leaf and no ``while``; the rows attend through the ragged
    decode kernel and the chunk through the chunk kernel, which reads the
    key blocks up to the chunk's last position (``ops.chunk_attention``:
    no product over the row's 2,048 places is left), one call of each a
    layer; and **a block's output projection and both
    feed-forward weights (five sixths of its bytes) are each the operand
    of one product** over the 6 rows and the 64 chunk tokens together,
    where the chunk program followed by the step made two.  Queries, keys
    and values are projected a half: sharing those products too measured
    0.5 ms a pass slower on the chip (PERF.md section 6, PR 43)."""
    from bigdl_tpu.models import transformer_lm
    from bigdl_tpu.ops import attention_kernels
    from bigdl_tpu.serving.generation import SlotPool
    monkeypatch.setattr(attention_kernels, "_on_tpu", lambda: True)
    slots, max_len, chunk, layers, hidden, ffn = 6, 2048, 64, 24, 2048, 8192
    sds = functools.partial(jax.ShapeDtypeStruct,
                            sharding=SingleDeviceSharding(v5e.devices[0]))
    abstract = jax.eval_shape(lambda: transformer_lm(
        vocab_size=50272, hidden_size=hidden, num_layers=layers,
        num_heads=32, filter_size=ffn, max_len=max_len).eval_mode())
    assert abstract.blocks[0].ffn.filter_layer.weight.shape == (ffn, hidden)
    model = jax.tree_util.tree_map(
        lambda a: sds(a.shape, jnp.bfloat16), abstract)
    caches = jax.tree_util.tree_map(
        lambda a: sds(a.shape, a.dtype),
        jax.eval_shape(lambda: abstract.init_cache(slots, jnp.float32)))
    pool = object.__new__(SlotPool)
    pool.slots = slots
    pool.cache_layers = tuple(abstract.cache_layers())
    pool.expert_layers = 0
    pool.trace_counts = dict(TRACE_COUNTS, decode_with_chunk={})
    pool._build_programs()
    text = _lower(pool, "decode_with_chunk", model, caches,
                  sds((0,), jnp.int32), sds, slots, chunk).compile().as_text()
    assert not re.findall(
        r"= \w+\[6,32,(?:64,2048|2048,64)\]\S* "
        r"(?:copy|copy-start|transpose|scatter|convert)\(", text)
    assert " while(" not in text
    # the rows' kernel goes out behind three prefetched scalars a row, the
    # chunk's behind its first row and its position
    calls = [line for line in text.splitlines()
             if 'custom_call_target="tpu_custom_call"' in line]
    rows = "operand_layout_constraints={s32[6]{0}, s32[6]{0}, s32[6]{0}, "
    a_chunk = ("operand_layout_constraints={s32[2]{0}, "
               "bf16[1,32,%d,64]{3,2,1,0}, f32[6,32,64,2048]{3,2,1,0}, "
               "f32[6,32,64,2048]{3,2,1,0}, f32[6,1,2048]{2,1,0}}" % chunk)
    assert len([c for c in calls if rows in c]) == layers
    assert len([c for c in calls if a_chunk in c]) == layers
    assert len(calls) == 2 * layers
    # the program's products: q, k and v a half, onto the heads; the
    # output projection and both feed-forward layers over the 70 rows and
    # chunk tokens together; the head over the 6 rows alone (the chunk's
    # scores and context are inside its kernel)
    both = slots + chunk
    by_result = collections.Counter(re.findall(
        r"= (\w+\[[\d,]*\])\S* convolution\(", text))
    assert by_result == {
        "bf16[%d,32,64]" % slots: 3 * layers,
        "bf16[%d,32,64]" % chunk: 3 * layers,
        "bf16[%d,%d]" % (both, hidden): 2 * layers,
        "bf16[%d,%d]" % (both, ffn): layers,
        "bf16[%d,50273]" % slots: 1}, by_result
    # and by operand: the feed-forward's first weight, as the leaf lies
    shape_of = dict(re.findall(r"(%[\w.-]+) = (\w+\[[\d,]*\])", text))
    reads = [args for args in re.findall(r" convolution\(([^)]*)\)", text)
             if "bf16[%d,%d]" % (ffn, hidden) in [
                 shape_of[name] for name in re.findall(r"%[\w.-]+", args)]]
    assert len(reads) == layers


def test_pool_decode_step_lowers_to_no_scatter_over_the_pool(pool):
    """Runs anywhere: the decode step as JAX hands it to the compiler
    (StableHLO) writes the pool with ``dynamic_update_slice`` and never
    with a ``scatter`` whose operand is a pool leaf — the form a
    per-lane position under ``vmap`` takes, and the cause of the
    relayout the test above looks for."""
    text = _lower_pool_program(pool, "decode").as_text()
    # the first operand's type closes each scatter's update region
    operands = re.findall(
        r'"stablehlo\.scatter"\(.*?\}\) : \((tensor<[^>]*>)', text, re.S)
    assert not [t for t in operands if t.endswith(
        "x%dx%dxf32>" % (POOL_MAX_LEN, POOL_HEAD_DIM))], operands
    assert "stablehlo.dynamic_update_slice" in text


# ---- the pool of mixed cache layers, at the benchmark's cut -----------------
# (benchmark/configs/mimo-v2.5.json: 64 query heads; full layers of 4
# key/value heads over 32 slots of 6,144 positions, window layers of 8 over
# rings of the 128 window and a 256-token prefill chunk; keys 192 wide and
# values 128; 16 held experts of 256: 5.42 B parameters in bfloat16.)
# Nothing is allocated: the model and the caches are shapes, and the pool's
# programs are built around them.

POOL_MODEL_PROGRAMS = ["decode", "chunk_prefill", "decode_with_chunk"]
TRACE_COUNTS = {"decode": 0, "prefill": {}, "scatter": {},
                "chunk_prefill": {}, "kv_copy": {}, "kv_extract": {}}


def _lower(pool, program, model, caches, routing, sds, slots, chunk):
    """One of a pool's three programs that run the model, lowered on
    shapes: the decode step, the chunk program, or the joint program (a
    decode step that carries a chunk of the full width)."""
    feed = (sds((slots,), jnp.int32), sds((slots,), jnp.int32),
            sds((slots,), jnp.bool_))
    a_chunk = (sds((), jnp.int32), sds((chunk,), jnp.int32),
               sds((), jnp.int32))
    if program == "decode":
        return pool._decode_jit.lower(model, caches, *feed, routing)
    if program == "chunk_prefill":
        return pool._chunk_jit.lower(model, caches, *a_chunk, routing)
    return pool._decode_with_chunk_jit.lower(model, caches, *feed, routing,
                                             *a_chunk)


def _cut():
    """MiMo-V2.5's published widths, cut to one chip's share of a 16-chip
    layer group as the benchmark's cell serves it (layers 0-10 of 48, 16 of
    256 experts held, an eighth of the vocabulary), in the keys
    ``mimo_v2`` reads."""
    n = 11
    return {
        "vocab_size": 19072, "hidden_size": 4096, "num_hidden_layers": n,
        "hybrid_layer_pattern": [0, 1, 1, 1, 1, 0, 1, 1, 1, 1, 1],
        "moe_layer_freq": [0] + [1] * (n - 1),
        "num_attention_heads": 64, "head_dim": 192, "v_head_dim": 128,
        "num_key_value_heads": 4, "swa_num_key_value_heads": 8,
        "rope_theta": 1e7, "swa_rope_theta": 1e4,
        "partial_rotary_factor": 0.334, "sliding_window": 128,
        "add_swa_attention_sink_bias": True, "attention_value_scale": 0.707,
        "intermediate_size": 16384, "moe_intermediate_size": 2048,
        "n_routed_experts": 256, "experts_held": 16,
        "num_experts_per_tok": 8, "norm_topk_prob": True,
        "layernorm_epsilon": 1e-5,
        "serving": {"slots": 32, "max_len": 6144, "prefill_chunk": 256}}


def _kernel_calls(text, also=()):
    """The program's Pallas calls by kernel: ``(row writers, ragged
    decode attentions)`` and then those of each ``jit`` in ``also``, told
    apart by the ``jit`` each was traced under."""
    names = ("_write_cache_rows", "_ragged_decode") + tuple(also)
    calls = re.findall(
        r'custom_call_target="tpu_custom_call".*?op_name="[^"]*jit\((\w+)\)'
        r'/pallas_call"', text)
    assert set(calls) <= set(names), calls
    assert len(calls) == text.count('custom_call_target="tpu_custom_call"')
    return tuple(calls.count(name) for name in names)


CHUNK_KERNEL = "grouped_chunk_attention"


def _whole_row_scores(text, heads, kv_heads, width, max_len):
    """Every float32 array of a compiled program that is as large as a
    chunk's scores over a whole full row: ``[heads, width, max_len]`` or
    ``[kv_heads, group, width, max_len]`` behind any leading ones.  A
    chunk that attends through ``ops.chunk_attention`` makes none (its
    scores never leave the kernel)."""
    return re.findall(r"f32\[(?:1,)*(?:%d|%d,%d),%d,%d\]" % (
        heads, kv_heads, heads // kv_heads, width, max_len), text)


def _lower_cut_program(program, sharding):
    from bigdl_tpu.models import mimo_v2
    from bigdl_tpu.serving.generation import SlotPool
    cfg = _cut()
    s = cfg["serving"]
    slots, chunk = s["slots"], s["prefill_chunk"]

    def sds(shape, dtype):
        return jax.ShapeDtypeStruct(shape, dtype, sharding=sharding)

    abstract = jax.eval_shape(lambda: mimo_v2(cfg, s["max_len"]))
    model = jax.tree_util.tree_map(
        lambda a: sds(a.shape, jnp.bfloat16), abstract)
    caches = jax.tree_util.tree_map(
        lambda a: sds(a.shape, a.dtype), jax.eval_shape(
            lambda: abstract.init_cache(slots, jnp.bfloat16,
                                        ring_margin=chunk)))
    # the pool's programs around a model that is shapes only
    pool = object.__new__(SlotPool)
    pool.slots = slots
    pool.cache_layers = tuple(abstract.cache_layers())
    pool.expert_layers = abstract.expert_layers()
    pool.trace_counts = dict(TRACE_COUNTS, decode_with_chunk={})
    pool._build_programs()
    routing = sds((ROUTING,), jnp.int32)
    return _lower(pool, program, model, caches, routing, sds, slots,
                  chunk), cfg, caches


@pytest.mark.parametrize("program", POOL_MODEL_PROGRAMS)
def test_cut_pool_program_copies_no_leaf_and_expands_no_key_on_v5e(
        v5e, program, monkeypatch):
    """The decode step and the chunk program of the 5.42 B cut, as a TPU
    process traces them, compiled for the described v5e: no copy or
    transpose of a whole cache leaf (a ring's or a full layer's, keys or
    values, as it reads or as the row-write kernel is handed it), no
    ``scatter`` over one and, in the decode step, no ``while``; no keys or
    values at the 64 query heads; the experts' products on the held stacks
    as they lie (no copy of a stack); and weights, caches and temporaries
    inside the chip.  The decode step writes its cache with one kernel
    call a layer (``ops.write_cache_rows``, keys positions-minor as they
    lie: a ``bitcast``) and no ``dynamic-update-slice`` into a leaf or the
    flags, and its two full layers attend through the ragged decode
    kernel, which takes the same leaves the same way; the chunk program
    writes windows, as it did, and its two full layers attend through the
    grouped chunk kernel over the same leaves: no float32 array of 64 x
    256 x 6,144 scores is left in either chunk program.  Which path a
    process takes it asks ``_on_tpu()``; here the test answers."""
    from bigdl_tpu.ops import attention_kernels
    monkeypatch.setattr(attention_kernels, "_on_tpu", lambda: True)
    lowered, cfg, caches = _lower_cut_program(
        program, SingleDeviceSharding(v5e.devices[0]))
    s = cfg["serving"]
    shapes = sorted({leaf.shape for layer in caches["layers"]
                     for leaf in layer["self"].values()})
    ring = cfg["sliding_window"] + s["prefill_chunk"]
    assert shapes == [(32, 4, s["max_len"], 128), (32, 4, s["max_len"], 192),
                      (32, 8, ring, 128), (32, 8, ring, 192)]
    compiled = lowered.compile()
    text = compiled.as_text()
    # a leaf as it reads, and with its last two axes swapped
    leaf = "(?:%s)" % "|".join(
        r"bf16\[%d,%d,(?:%d,%d|%d,%d)\]" % (a, b, c, d, d, c)
        for a, b, c, d in shapes)
    assert not re.findall(
        r"= %s\S* (?:copy|copy-start|transpose|scatter)\(" % leaf, text)
    full = cfg["hybrid_layer_pattern"].count(0)
    updates = re.findall(
        r"= (?:%s|pred\[32,%d\])\S* dynamic-update-slice\(" % (
            leaf, s["max_len"]), text)
    calls = _kernel_calls(text, also=(CHUNK_KERNEL,))
    if program == "decode":
        assert calls == (cfg["num_hidden_layers"], full, 0)
        assert not updates
        assert " while(" not in text
    elif program == "chunk_prefill":
        assert calls == (0, 0, full)
        assert "dynamic-update-slice" in text
    else:
        # the joint program: the step's kernels, the chunk's windows (two
        # leaves a layer and the flags) and its kernel in the two full
        # layers, and still no loop
        assert calls == (cfg["num_hidden_layers"], full, full)
        assert len(updates) == 2 * cfg["num_hidden_layers"] + 1
        assert " while(" not in text
    heads = cfg["num_attention_heads"]
    # a chunk's scores over a full row (403 MB a layer) are nowhere
    assert not _whole_row_scores(text, heads, 4, s["prefill_chunk"],
                                 s["max_len"])
    expanded = r"bf16\[\d+,(?:%d|4,16|8,8),(?:%d|%d),(?:128|192)\]" % (
        heads, s["max_len"], ring)
    assert not re.findall(expanded, text)
    held, h, f = (cfg["experts_held"], cfg["hidden_size"],
                  cfg["moe_intermediate_size"])
    stack = r"bf16\[%d,(?:%d,%d|%d,%d)\]" % (held, h, f, f, h)
    assert not re.findall(r"= %s\S* (?:copy|copy-start|transpose)\(" % stack,
                          text)
    # 32 tokens a decode step, 256 a chunk: both take the batched product
    # over every held stack (HeldExperts.DENSE_TOKENS), not the grouped one
    assert len(re.findall(stack, text)) >= 3 * sum(cfg["moe_layer_freq"])
    assert "ragged-dot" not in text
    mem = compiled.memory_analysis()
    held_bytes = mem.argument_size_in_bytes + mem.temp_size_in_bytes
    assert 10e9 < held_bytes < 15.5 * 2 ** 30, held_bytes


def test_cut_pool_decode_step_lowers_to_no_scatter_over_a_cache_leaf():
    """Runs anywhere: the cut's decode step as JAX hands it to the
    compiler (StableHLO) on the path every backend can take writes every
    cache leaf with one ``dynamic_update_slice`` a slot and none with a
    ``scatter`` (PR 27's test, on the pool whose layers differ); the
    padding flags go in one select."""
    lowered, cfg, caches = _lower_cut_program("decode", None)
    text = lowered.as_text()
    operands = re.findall(
        r'"stablehlo\.scatter"\(.*?\}\) : \((tensor<[^>]*>)', text, re.S)
    leaves = {"x".join(str(n) for n in leaf.shape[1:]) + "xbf16>"
              for layer in caches["layers"]
              for leaf in layer["self"].values()}
    assert not [t for t in operands if any(t.endswith(e) for e in leaves)], \
        operands
    n_leaves = 2 * cfg["num_hidden_layers"]
    assert text.count("stablehlo.dynamic_update_slice") \
        == n_leaves * cfg["serving"]["slots"]
    assert "tpu_custom_call" not in text


# ---- the pool that keeps a state beside its rows, at the benchmark's cut ----
# (benchmark/configs/falcon-h1-34b.json: every layer grouped-query attention
# over 48 slots of 3,584 positions beside a Mamba-2 mixer whose recurrence
# keeps 4 MiB of float32 a slot and layer; six layers and the whole
# vocabulary, 5.26 B parameters in bfloat16.)  Shapes only, as above.

def _state_cut():
    return {
        "vocab_size": 261120, "hidden_size": 5120, "num_hidden_layers": 6,
        "num_attention_heads": 20, "num_key_value_heads": 4, "head_dim": 128,
        "rope_theta": 1e11, "intermediate_size": 21504, "rms_norm_eps": 1e-5,
        "mamba_n_heads": 32, "mamba_d_head": 128, "mamba_d_ssm": 4096,
        "mamba_n_groups": 2, "mamba_d_state": 256, "mamba_d_conv": 4,
        "mamba_chunk_size": 128,
        "ssm_multipliers": [0.354, 0.25, 0.177, 0.5, 0.354],
        "mlp_multipliers": [0.177, 0.0112], "embedding_multiplier": 5.66,
        "lm_head_multiplier": 0.0078, "key_multiplier": 0.011,
        "attention_out_multiplier": 0.0375, "ssm_in_multiplier": 0.25,
        "ssm_out_multiplier": 0.088,
        "serving": {"slots": 48, "max_len": 3584, "prefill_chunk": 256}}


def _lower_state_cut_program(program, sharding):
    from bigdl_tpu.models import falcon_h1
    from bigdl_tpu.serving.generation import SlotPool
    cfg = _state_cut()
    s = cfg["serving"]
    slots, chunk = s["slots"], s["prefill_chunk"]

    def sds(shape, dtype):
        return jax.ShapeDtypeStruct(shape, dtype, sharding=sharding)

    abstract = jax.eval_shape(lambda: falcon_h1(cfg, s["max_len"]))
    model = jax.tree_util.tree_map(
        lambda a: sds(a.shape, jnp.bfloat16), abstract)
    caches = jax.tree_util.tree_map(
        lambda a: sds(a.shape, a.dtype), jax.eval_shape(
            lambda: abstract.init_cache(slots, jnp.bfloat16,
                                        ring_margin=chunk)))
    pool = object.__new__(SlotPool)
    pool.slots = slots
    pool.cache_layers = tuple(abstract.cache_layers())
    pool.expert_layers = abstract.expert_layers()
    pool.trace_counts = dict(TRACE_COUNTS, decode_with_chunk={})
    pool._build_programs()
    routing = sds((0,), jnp.int32)
    return _lower(pool, program, model, caches, routing, sds, slots,
                  chunk), cfg, caches


@pytest.mark.parametrize("program", POOL_MODEL_PROGRAMS)
def test_state_pool_program_moves_each_state_in_place_on_v5e(
        v5e, program, monkeypatch):
    """The decode step and the chunk program of the falcon-h1-34b cut, as
    a TPU process traces them, compiled for the described v5e.  A layer's
    pooled state is 201 MB of float32 and a step must read it once and
    write it once: no ``copy``, ``transpose`` or ``scatter`` of a state
    (or of a row leaf) anywhere.  The decode step updates each state by
    one fusion a layer (the ``tpu_custom_call``s are the row writers' and
    the ragged decode kernel's, one of each a layer, both taking the
    width-minor leaves as they read) and holds no ``while``: should a
    fusion choice ever split it or copy the state, this is where it
    shows.  The chunk program writes a
    slot's state by a ``dynamic-update-slice`` and scans in a loop a
    layer.  Weights, pool and temporaries fit the chip."""
    from bigdl_tpu.ops import attention_kernels
    monkeypatch.setattr(attention_kernels, "_on_tpu", lambda: True)
    lowered, cfg, caches = _lower_state_cut_program(
        program, SingleDeviceSharding(v5e.devices[0]))
    layers = cfg["num_hidden_layers"]
    state = caches["layers"][0]["ssm"]["ssm"]
    assert (state.shape, state.dtype) == ((48, 32, 256, 128), jnp.float32)
    assert caches["layers"][0]["ssm"]["conv"].shape == (48, 3, 5120)
    assert caches["layers"][0]["self"]["k"].shape == (48, 4, 3584, 128)
    compiled = lowered.compile()
    text = compiled.as_text()
    leaf = r"(?:f32\[48,32,256,128\]|bf16\[48,4,(?:3584,128|128,3584)\])"
    assert not re.findall(
        r"= %s\S* (?:copy|copy-start|transpose|scatter)\(" % leaf, text)
    calls = _kernel_calls(text, also=(CHUNK_KERNEL,))
    whiles = len(re.findall(r" while\(", text))
    updates = re.findall(
        r"= f32\[48,32,256,128\]\S* dynamic-update-slice\(", text)
    fusions = len(re.findall(r"f32\[48,32,256,128\]\S*\) fusion\(", text))
    if program == "decode":
        assert (calls, whiles, len(updates)) == ((layers, layers, 0), 0, 0)
        assert fusions == layers
    elif program == "chunk_prefill":
        # every layer's chunk attends through the grouped chunk kernel (a
        # lone chunk's rows give no logits: the last layer's context feeds
        # nothing and the compiler drops its call)
        assert (calls, whiles, len(updates)) == ((0, 0, layers - 1), layers,
                                                 layers)
    else:
        # the joint program: what the two hold, added; the rows' update
        # still one fusion a layer over the state the chunk wrote into
        assert (calls, whiles, len(updates)) == (
            (layers, layers, layers), layers, layers)
        assert fusions == layers
    assert not _whole_row_scores(text, 20, 4, 256, 3584)
    mem = compiled.memory_analysis()
    held = mem.argument_size_in_bytes + mem.temp_size_in_bytes
    assert 13.5e9 < held < 15.5 * 2 ** 30 if program != "chunk_prefill" \
        else 10e9 < held < 15.5 * 2 ** 30, held


# ---- the pool of latent rows, at the benchmark's cut -------------------------
# (benchmark/configs/sarvam-105b.json: every layer latent attention over 112
# slots of 7,168 positions, one compressed 512-wide row and one 64-wide
# rotary key a position for all 64 heads; a dense first layer, then 16 held
# of 128 experts beside a shared one; six layers and an eighth of the
# vocabulary, 3.18 B parameters in bfloat16.)  Shapes only, as above.

def _latent_cut():
    return {
        "vocab_size": 32768, "hidden_size": 4096, "num_hidden_layers": 6,
        "num_attention_heads": 64, "qk_nope_head_dim": 128,
        "qk_rope_head_dim": 64, "q_head_dim": 192, "v_head_dim": 128,
        "kv_lora_rank": 512, "intermediate_size": 16384,
        "moe_intermediate_size": 2048, "num_experts": 128, "experts_held": 16,
        "num_experts_per_tok": 8, "num_shared_experts": 1,
        "routed_scaling_factor": 2.5, "first_k_dense_replace": 1,
        "moe_router_enable_expert_bias": True, "use_qk_norm": True,
        "rms_norm_eps": 1e-6, "rope_theta": 10000,
        "rope_scaling": {"type": "deepseek_yarn", "factor": 40,
                         "original_max_position_embeddings": 4096,
                         "beta_fast": 32, "beta_slow": 1, "mscale": 1,
                         "mscale_all_dim": 1},
        "serving": {"slots": 112, "max_len": 7168, "prefill_chunk": 256}}


def _lower_latent_cut_program(program, sharding):
    from bigdl_tpu.models import sarvam_mla
    from bigdl_tpu.serving.generation import SlotPool
    cfg = _latent_cut()
    s = cfg["serving"]
    slots, chunk = s["slots"], s["prefill_chunk"]

    def sds(shape, dtype):
        return jax.ShapeDtypeStruct(shape, dtype, sharding=sharding)

    abstract = jax.eval_shape(lambda: sarvam_mla(cfg, s["max_len"]))
    model = jax.tree_util.tree_map(
        lambda a: sds(a.shape, jnp.bfloat16), abstract)
    caches = jax.tree_util.tree_map(
        lambda a: sds(a.shape, a.dtype), jax.eval_shape(
            lambda: abstract.init_cache(slots, jnp.bfloat16)))
    pool = object.__new__(SlotPool)
    pool.slots = slots
    pool.cache_layers = tuple(abstract.cache_layers())
    pool.expert_layers = abstract.expert_layers()
    pool.trace_counts = dict(TRACE_COUNTS, decode_with_chunk={})
    pool._build_programs()
    routing = sds((ROUTING,), jnp.int32)
    return _lower(pool, program, model, caches, routing, sds, slots,
                  chunk), cfg, caches, abstract


@pytest.mark.parametrize("program", POOL_MODEL_PROGRAMS)
def test_latent_pool_program_copies_no_latent_leaf_on_v5e(
        v5e, program, monkeypatch):
    """The decode step and the chunk program of the sarvam-105b cut, as a
    TPU process traces them, compiled for the described v5e: no ``copy``,
    ``transpose`` or ``scatter`` of a latent leaf or of a rotary leaf (as
    it reads, or positions-minor as the kernels are handed it), and the
    row never expanded to the 64 heads.  The decode step writes each
    layer's row in **one** program (``ops.write_cache_rows``) and attends
    it in one (the ragged decode kernel with the body in which the value
    block also scores): two kernel calls a layer, no
    ``dynamic-update-slice`` into a leaf or the flags, no ``while``; the
    model answers the pool ``cache_write_programs`` = one a layer and the
    flags' select, and a key block of 512.  The chunk program writes a
    window and attends the row's live key blocks in one kernel call a
    layer (``ops.latent_chunk_attention``): no ``while``, and the running
    context nowhere outside the kernel.
    Weights, rows and temporaries fit the chip, and fill 11 GB of it."""
    from bigdl_tpu.ops import attention_kernels
    monkeypatch.setattr(attention_kernels, "_on_tpu", lambda: True)
    lowered, cfg, caches, abstract = _lower_latent_cut_program(
        program, SingleDeviceSharding(v5e.devices[0]))
    layers = cfg["num_hidden_layers"]
    assert [{n: leaf.shape for n, leaf in layer["self"].items()}
            for layer in caches["layers"]] == [
        {"k": (112, 1, 7168, 64), "v": (112, 1, 7168, 512)}] * layers
    assert abstract.cache_layers() == (("latent", 7168),) * layers
    assert abstract.decode_key_block(caches) == 512
    assert abstract.cache_write_programs(caches) == layers + 1
    compiled = lowered.compile()
    text = compiled.as_text()
    leaf = r"bf16\[112,1,(?:7168,512|512,7168|7168,64|64,7168)\]"
    assert not re.findall(
        r"= %s\S* (?:copy|copy-start|transpose|scatter)\(" % leaf, text)
    # keys or values at the 64 heads over a whole row
    assert not re.findall(r"bf16\[\d+,64,7168,(?:128|192|256)\]", text)
    calls = _kernel_calls(text, also=("latent_chunk_attention",))
    whiles = len(re.findall(r" while\(", text))
    updates = len(re.findall(
        r"= (?:%s|pred\[112,7168\])\S* dynamic-update-slice\(" % leaf, text))
    if program == "decode":
        assert (calls, whiles, updates) == ((layers, layers, 0), 0, 0)
    elif program == "chunk_prefill":
        assert (calls, whiles, updates) == ((0, 0, layers), 0,
                                            2 * layers + 1)
    else:
        # the joint program: the step's two kernels a layer beside the
        # chunk's windows and its kernel over the slot's live key blocks
        assert (calls, whiles, updates) == ((layers, layers, layers), 0,
                                            2 * layers + 1)
    # a chunk's running context (64 heads x 256 places x 512, float32)
    # lives in the chunk kernel's scratch and nowhere in the program
    assert "f32[1,64,256,512]" not in text
    # the experts' batched product on the held stacks as they lie
    stack = r"bf16\[16,(?:4096,2048|2048,4096)\]"
    assert not re.findall(r"= %s\S* (?:copy|copy-start|transpose)\(" % stack,
                          text)
    assert "ragged-dot" not in text
    mem = compiled.memory_analysis()
    held = mem.argument_size_in_bytes + mem.temp_size_in_bytes
    assert 10.5e9 < held < 15.5 * 2 ** 30, held
    if program != "chunk_prefill":
        assert held > 11e9


# ---- the pool of one shared row, rings and states, at the published sizes ----
# (benchmark/configs/phi-4-mini-flash-reasoning.json: all 32 layers of
# Phi-4-mini-flash-reasoning over 96 slots of 5,120 positions: nine Mamba-1
# layers that keep a float32 state [16, 5120] a slot and no row, eight
# differential-attention layers over rings of 512 + 255 + 1 places, one over
# the full row that seven cross layers read too, seven gated memory units
# that keep nothing; 3.85 B parameters in bfloat16.)  Shapes only, as above.

def _shared_row_config():
    return {
        "vocab_size": 200064, "hidden_size": 2560, "num_hidden_layers": 32,
        "num_attention_heads": 40, "num_key_value_heads": 20,
        "intermediate_size": 10240, "sliding_window": 512, "mb_per_layer": 2,
        "layer_norm_eps": 1e-5, "tie_word_embeddings": True,
        "serving": {"slots": 96, "max_len": 5120, "prefill_chunk": 256}}


def _lower_shared_row_program(program, sharding):
    from bigdl_tpu.models import phi4_flash
    from bigdl_tpu.serving.generation import SlotPool
    cfg = _shared_row_config()
    s = cfg["serving"]
    slots, chunk = s["slots"], s["prefill_chunk"]

    def sds(shape, dtype):
        return jax.ShapeDtypeStruct(shape, dtype, sharding=sharding)

    abstract = jax.eval_shape(lambda: phi4_flash(cfg, s["max_len"]))
    model = jax.tree_util.tree_map(
        lambda a: sds(a.shape, jnp.bfloat16), abstract)
    caches = jax.tree_util.tree_map(
        lambda a: sds(a.shape, a.dtype), jax.eval_shape(
            lambda: abstract.init_cache(slots, jnp.bfloat16,
                                        ring_margin=chunk)))
    pool = object.__new__(SlotPool)
    pool.slots = slots
    pool.cache_layers = tuple(abstract.cache_layers())
    pool.expert_layers = abstract.expert_layers()
    pool.trace_counts = dict(TRACE_COUNTS, decode_with_chunk={})
    pool._build_programs()
    routing = sds((0,), jnp.int32)
    return _lower(pool, program, model, caches, routing, sds, slots,
                  chunk), caches, abstract


@pytest.mark.parametrize("program", POOL_MODEL_PROGRAMS)
def test_shared_row_pool_program_copies_no_leaf_on_v5e(
        v5e, program, monkeypatch):
    """The decode step, the chunk program and the joint program of
    Phi-4-mini-flash-reasoning whole, as a TPU process traces them,
    compiled for the described v5e.  No ``copy``, ``transpose`` or
    ``scatter`` of a pooled state, of the shared row or of a ring; the
    state's leaf lies channels-minor (``[slots, 16, 5120]``, the 5,120
    channels along the lanes: ``[5120, 16]`` would pad 16 to 128).  The
    decode step writes nine layers' rows through ``ops.write_cache_rows``
    (eight rings and the full row) and attends the **one full row eight
    times** through the ragged decode kernel (the full layer and seven
    cross layers; the rings go through the XLA product), with no
    ``while``; a chunk's rows scan nine states in nine loops and hold no
    kernel call (they never reach a cross layer, and leave after layer
    17's keys and values are written).  Weights, pool and temporaries fit
    the chip and fill 13.5 GB of it."""
    from bigdl_tpu.ops import attention_kernels
    monkeypatch.setattr(attention_kernels, "_on_tpu", lambda: True)
    lowered, caches, abstract = _lower_shared_row_program(
        program, SingleDeviceSharding(v5e.devices[0]))
    layers = caches["layers"]
    assert layers[16]["ssm"]["ssm"].shape == (96, 16, 5120)
    assert layers[16]["ssm"]["ssm"].dtype == jnp.float32
    assert layers[17]["self"]["k"].shape == (96, 10, 5120, 128)
    assert layers[15]["self"]["v"].shape == (96, 10, 768, 128)
    assert layers[18] == {} and layers[31] == {}
    assert abstract.decode_key_block(caches) in (256, 512)
    # nine row writers and the flags' select; two writers a state
    assert abstract.cache_write_programs(caches) == 1 + 9 + 2 * 9
    compiled = lowered.compile()
    text = compiled.as_text()
    leaf = (r"(?:f32\[96,16,5120\]|bf16\[96,10,(?:5120,128|128,5120|768,128"
            r"|128,768)\])")
    assert not re.findall(
        r"= %s\S* (?:copy|copy-start|transpose|scatter)\(" % leaf, text)
    layouts = set(re.findall(r"f32\[96,16,5120\]\{([\d,]+)", text))
    assert layouts and all(lay.startswith("2,1,0") for lay in layouts), layouts
    calls = _kernel_calls(text)
    whiles = len(re.findall(r" while\(", text))
    if program == "decode":
        assert (calls, whiles) == ((9, 8), 0)
    elif program == "chunk_prefill":
        assert (calls, whiles) == ((0, 0), 9)
    else:
        assert (calls, whiles) == ((9, 8), 9)
    mem = compiled.memory_analysis()
    held = mem.argument_size_in_bytes + mem.temp_size_in_bytes
    assert held < 15.5 * 2 ** 30, held
    if program != "chunk_prefill":
        assert held > 13.5e9, held


# ---- the pool whose states are tails, all of a layer's experts held --------------
# (benchmark/configs/lfm2-24b-a2b.json: LFM2-24B-A2B's first pipeline stage,
# layers 0-9: eight gated short convolutions that keep [slots, 2 x 2048] and
# no row, two attention layers of 8 key heads of 64 with the head norm, two
# leading dense layers, then 64 experts of 1,536 a layer, all held)

def _conv_moe_config():
    return {
        "vocab_size": 65536, "hidden_size": 2048, "num_hidden_layers": 10,
        "layer_types": ["conv", "conv", "full_attention", "conv", "conv",
                        "conv", "full_attention", "conv", "conv", "conv"],
        "num_attention_heads": 32, "num_key_value_heads": 8,
        "intermediate_size": 11776, "num_dense_layers": 2,
        "moe_intermediate_size": 1536, "num_experts": 64,
        "num_experts_per_tok": 4, "conv_L_cache": 3, "conv_bias": False,
        "norm_eps": 1e-5, "norm_topk_prob": True, "use_expert_bias": True,
        "routed_scaling_factor": 1,
        "rope_parameters": {"rope_theta": 1000000, "rope_type": "default"},
        "serving": {"slots": 128, "max_len": 5632, "prefill_chunk": 256}}


def _lower_conv_moe_program(program, sharding):
    from bigdl_tpu.models import lfm2_moe
    from bigdl_tpu.serving.generation import SlotPool
    cfg = _conv_moe_config()
    s = cfg["serving"]
    slots, chunk = s["slots"], s["prefill_chunk"]

    def sds(shape, dtype):
        return jax.ShapeDtypeStruct(shape, dtype, sharding=sharding)

    abstract = jax.eval_shape(lambda: lfm2_moe(cfg, s["max_len"]))
    model = jax.tree_util.tree_map(
        lambda a: sds(a.shape, jnp.bfloat16), abstract)
    caches = jax.tree_util.tree_map(
        lambda a: sds(a.shape, a.dtype), jax.eval_shape(
            lambda: abstract.init_cache(slots, jnp.bfloat16,
                                        ring_margin=chunk)))
    pool = object.__new__(SlotPool)
    pool.slots = slots
    pool.cache_layers = tuple(abstract.cache_layers())
    pool.expert_layers = abstract.expert_layers()
    pool.trace_counts = dict(TRACE_COUNTS, decode_with_chunk={})
    pool._build_programs()
    routing = sds((ROUTING,), jnp.int32)
    return _lower(pool, program, model, caches, routing, sds, slots,
                  chunk), caches, abstract


@pytest.mark.parametrize("program", POOL_MODEL_PROGRAMS)
def test_conv_moe_pool_program_copies_no_leaf_and_no_stack_on_v5e(
        v5e, program, monkeypatch):
    """The decode step, the chunk program and the joint program of
    LFM2-24B-A2B's first stage, as a TPU process traces them, compiled for
    the described v5e.  No ``copy``, ``transpose`` or ``scatter`` of a
    pooled tail or row, and none of a whole expert stack (64 experts of
    2,048 x 1,536: 403 MB a stack).  A tail's two rows lie side by side
    along the lanes (``[128, 4096]``; as ``[128, 2, 2048]`` the leaf was
    copied into a layout of its own and back in every step).  The decode
    step writes its two attention
    layers' rows through ``ops.write_cache_rows`` and attends them
    through the ragged decode kernel at a **block of 512** (heads of 64
    in bfloat16, four query heads a key head: the kernel's MXU body),
    with no ``while``.  Which product the experts take follows the call's
    held share (``HeldExperts.product_of``): all 64 held, every call goes
    through the tiled product, two kernel calls a layer
    (``ops.expert_kernels``) on the stacks as they lie; no loop and no
    ``ragged-dot``.  Weights, pool and
    temporaries fit the chip and fill over 13 GB of it."""
    from bigdl_tpu.nn.moe import HeldExperts
    from bigdl_tpu.ops import attention_kernels
    monkeypatch.setattr(attention_kernels, "_on_tpu", lambda: True)
    lowered, caches, abstract = _lower_conv_moe_program(
        program, SingleDeviceSharding(v5e.devices[0]))
    layers = caches["layers"]
    assert list(layers[0]["ssm"]) == ["conv"]
    assert layers[0]["ssm"]["conv"].shape == (128, 2 * 2048)
    assert layers[0]["ssm"]["conv"].dtype == jnp.bfloat16
    assert layers[2]["self"]["k"].shape == (128, 8, 5632, 64)
    assert abstract.decode_key_block(caches) == 512
    # two row writers, the flags' select, one writer a tail
    assert abstract.cache_write_programs(caches) == 1 + 2 + 8
    compiled = lowered.compile()
    text = compiled.as_text()
    leaf = r"bf16\[128,(?:4096|8,5632,64|8,64,5632)\]"
    stack = r"bf16\[64,(?:2048,1536|1536,2048)\]"
    assert not re.findall(
        r"= (?:%s|%s)\S* (?:copy|copy-start|transpose|scatter)\(" % (
            leaf, stack), text)
    layouts = set(re.findall(r"bf16\[128,4096\]\{([\d,]+)", text))
    assert layouts and all(lay.startswith("1,0") for lay in layouts), layouts
    calls = collections.Counter(re.findall(
        r'custom_call_target="tpu_custom_call".*?op_name="[^"]*jit\((\w+)\)'
        r'/pallas_call"', text))
    assert sum(calls.values()) == text.count(
        'custom_call_target="tpu_custom_call"')
    tokens = {"decode": 128, "chunk_prefill": 256,
              "decode_with_chunk": 128 + 256}[program]
    tiled = HeldExperts.product_of(64, 64, tokens) == "tiled"
    # a lone chunk's rows give no logits: the last layer's products feed
    # nothing and the compiler drops them (its counts stay)
    n = 7 if program == "chunk_prefill" else 8
    assert tiled and (calls["gate_up"], calls["down"]) == (n, n)
    if program == "chunk_prefill":
        assert (calls["_write_cache_rows"], calls["_ragged_decode"]) == (0, 0)
    else:
        assert (calls["_write_cache_rows"], calls["_ragged_decode"]) == (2, 2)
    # a chunk attends its slot's two rows through the grouped chunk kernel
    assert calls[CHUNK_KERNEL] == (0 if program == "decode" else 2)
    assert abstract.chunk_key_block(caches) == 256
    assert not _whole_row_scores(text, 32, 8, 256, 5632)
    assert " while(" not in text and "ragged-dot" not in text
    mem = compiled.memory_analysis()
    held = mem.argument_size_in_bytes + mem.temp_size_in_bytes
    assert held < 15.5 * 2 ** 30, held
    if program != "chunk_prefill":
        assert held > 13e9, held


# ---- the pool of gated window layers, all of a layer's experts held --------------
# (benchmark/configs/trinity-mini.json: one pipeline stage of Trinity-Mini,
# five layers: four window layers whose rings are 2,304 places of 4 key heads
# of 128, one full layer of 14,336 places, a gate as wide as the heads'
# output, four norms a block, one leading dense layer, then 128 experts of
# 1,024 a layer, all held, beside a shared one)

def _gated_window_config():
    return {
        "vocab_size": 200192, "hidden_size": 2048, "num_hidden_layers": 5,
        "layer_types": ["sliding_attention", "sliding_attention",
                        "sliding_attention", "full_attention",
                        "sliding_attention"],
        "num_attention_heads": 32, "num_key_value_heads": 4, "head_dim": 128,
        "sliding_window": 2048, "rope_theta": 10000, "rope_scaling": None,
        "intermediate_size": 6144, "num_dense_layers": 1,
        "moe_intermediate_size": 1024, "num_experts": 128,
        "num_experts_per_tok": 8, "num_shared_experts": 1,
        "route_scale": 2.826, "route_norm": True, "score_func": "sigmoid",
        "rms_norm_eps": 1e-5, "mup_enabled": True,
        "tie_word_embeddings": False,
        "serving": {"slots": 96, "max_len": 14336, "prefill_chunk": 256}}


def _lower_gated_window_program(program, sharding):
    from bigdl_tpu.models import afmoe
    from bigdl_tpu.serving.generation import SlotPool
    cfg = _gated_window_config()
    s = cfg["serving"]
    slots, chunk = s["slots"], s["prefill_chunk"]

    def sds(shape, dtype):
        return jax.ShapeDtypeStruct(shape, dtype, sharding=sharding)

    abstract = jax.eval_shape(lambda: afmoe(cfg, s["max_len"]))
    model = jax.tree_util.tree_map(
        lambda a: sds(a.shape, jnp.bfloat16), abstract)
    caches = jax.tree_util.tree_map(
        lambda a: sds(a.shape, a.dtype), jax.eval_shape(
            lambda: abstract.init_cache(slots, jnp.bfloat16,
                                        ring_margin=chunk)))
    pool = object.__new__(SlotPool)
    pool.slots = slots
    pool.cache_layers = tuple(abstract.cache_layers())
    pool.expert_layers = abstract.expert_layers()
    pool.trace_counts = dict(TRACE_COUNTS, decode_with_chunk={})
    pool._build_programs()
    routing = sds((ROUTING,), jnp.int32)
    return _lower(pool, program, model, caches, routing, sds, slots,
                  chunk), caches, abstract


@pytest.mark.parametrize("program", POOL_MODEL_PROGRAMS)
def test_gated_window_pool_program_copies_no_ring_row_or_stack_on_v5e(
        v5e, program, monkeypatch):
    """The decode step, the chunk program and the joint program of
    Trinity-Mini's five-layer stage, as a TPU process traces them, compiled
    for the described v5e.  No ``copy``, ``transpose`` or ``scatter`` of a
    pooled ring (``[96, 4, 2304, 128]``) or row (``[96, 4, 14336, 128]``),
    and none of a whole expert stack (128 experts of 2,048 x 1,024: 537 MB
    a stack).  The row-write kernel takes both leaf shapes
    (``cache_write_programs``: one a layer and the flags' select, not 2 x
    96 a layer), so the decode step writes all five layers through
    ``ops.write_cache_rows``; its one full layer attends through the
    ragged decode kernel at a **block of 512** (8 query heads to a key
    head of 128 in bfloat16: the kernel's MXU body) and its four window
    layers read their rings through the grouped product, with no
    ``while``.  All 128 experts held: every call goes through the tiled
    product, two kernel calls an expert layer on the stacks as they lie,
    and no ``ragged-dot``.  Weights, pool and temporaries fit the chip and
    fill over 13 GB of it."""
    from bigdl_tpu.nn.moe import HeldExperts
    from bigdl_tpu.ops import attention_kernels
    monkeypatch.setattr(attention_kernels, "_on_tpu", lambda: True)
    lowered, caches, abstract = _lower_gated_window_program(
        program, SingleDeviceSharding(v5e.devices[0]))
    layers = caches["layers"]
    assert [layer["self"]["k"].shape for layer in layers] == [
        (96, 4, 14336 if i == 3 else 2304, 128) for i in range(5)]
    assert abstract.decode_key_block(caches) == 512
    assert abstract.chunk_key_block(caches) == 256
    assert abstract.cache_write_programs(caches) == 1 + 5
    compiled = lowered.compile()
    text = compiled.as_text()
    leaf = r"bf16\[96,4,(?:2304,128|128,2304|14336,128|128,14336)\]"
    stack = r"bf16\[128,(?:2048,1024|1024,2048)\]"
    assert not re.findall(
        r"= (?:%s|%s)\S* (?:copy|copy-start|transpose|scatter)\(" % (
            leaf, stack), text)
    calls = collections.Counter(re.findall(
        r'custom_call_target="tpu_custom_call".*?op_name="[^"]*jit\((\w+)\)'
        r'/pallas_call"', text))
    assert sum(calls.values()) == text.count(
        'custom_call_target="tpu_custom_call"')
    tokens = {"decode": 96, "chunk_prefill": 256,
              "decode_with_chunk": 96 + 256}[program]
    assert HeldExperts.product_of(128, 128, tokens) == "tiled"
    # a lone chunk's rows give no logits: the last layer's products feed
    # nothing and the compiler drops them (its counts stay)
    n = 3 if program == "chunk_prefill" else 4
    assert (calls["gate_up"], calls["down"]) == (n, n)
    if program == "chunk_prefill":
        assert (calls["_write_cache_rows"], calls["_ragged_decode"]) == (0, 0)
    else:
        assert (calls["_write_cache_rows"], calls["_ragged_decode"]) == (5, 1)
    # a chunk attends its slot's one full row through the grouped chunk
    # kernel (its scores over the row would be 470 MB) and its four rings
    # through the grouped product
    assert calls[CHUNK_KERNEL] == (0 if program == "decode" else 1)
    assert not _whole_row_scores(text, 32, 4, 256, 14336)
    assert " while(" not in text and "ragged-dot" not in text
    mem = compiled.memory_analysis()
    held = mem.argument_size_in_bytes + mem.temp_size_in_bytes
    assert held < 15.5 * 2 ** 30, held
    if program != "chunk_prefill":
        assert held > 13e9, held
