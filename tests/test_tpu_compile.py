"""The Pallas kernels of the main path, compiled for a v5e that is
described and not attached (``/opt/skills/guides/on-chip-measurement``
§2.3): the TPU compiler is installed wherever libtpu is, and it refuses
here what it would refuse on the chip — a slice off the tiling, too much
VMEM, a kernel that cannot be lowered.  Interpret-mode tests cannot see
any of that.  Nothing runs: a compile that passes says nothing about
results or times, and is never reported as a chip run.

Shapes are the ones the main path uses at real width: the transformer LM
of ``chip_smoke.py`` and the four ResNet-50 stages at batch 128.

Loading libtpu takes its multi-process lock for the life of the process,
so two processes that compile for a described chip cannot overlap; the
CPU-platform workers of test_distributed_multiprocess.py never load it.
"""

import os

os.environ.setdefault("TPU_LOG_DIR", "disabled")

import jax
import jax.numpy as jnp
import pytest
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P
from jax.sharding import SingleDeviceSharding

from bigdl_tpu.ops import conv_bn_kernels as ck
from bigdl_tpu.ops.attention_kernels import flash_attention


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


CASES = (
    [_flash_case(s, bias, bwd) for s in FLASH_SHAPES
     for bias in (False, True) for bwd in (False, True)]
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
