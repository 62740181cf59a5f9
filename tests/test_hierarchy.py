"""Hierarchical gradient sync + wire compression on the fake-DCN mesh.

Covers the two-tier story end to end on the 8-virtual-CPU backend as
2 slices × 4 devices: the codecs' error bounds, the
``hierarchical_grad_sync`` schedule's numerics, the Optimizer wiring
(``set_gradient_sync``) including fixed-seed loss equivalence vs the
flat XLA-inserted sync, and the acceptance byte counts read straight
out of the compiled HLO (cross-slice payload ≤ 55% of the flat fp32
baseline under bf16, ≤ 30% under int8; byte-identical HLO with sync
unset).
"""

import re

import numpy as np
import pytest

import jax
import jax.numpy as jnp
from jax.sharding import Mesh, PartitionSpec as P

import bigdl_tpu.nn as nn
from bigdl_tpu.parallel.compression import (
    Bf16Codec, Int8Codec, get_codec, wire_bytes, wire_itemsize,
)
from bigdl_tpu.parallel.hierarchy import (
    batch_axes_of, dcn_slice_map, fast_batch_axes_of,
    hierarchical_grad_sync, shard_map,
)
from bigdl_tpu.parallel.mesh import MeshConfig, batch_sharding, make_mesh
from bigdl_tpu.utils.xla_cost import cross_group_hlo_bytes


def _dcn_mesh():
    return make_mesh({"dcn": 2, "data": -1}, jax.devices()[:8])


# ---------------------------------------------------------------------------
# codecs
# ---------------------------------------------------------------------------

def test_bf16_codec_round_trip():
    x = jnp.asarray(np.random.default_rng(0).normal(size=(513,)),
                    jnp.float32)
    c = Bf16Codec()
    out = c.decode(c.encode(x), x.shape[0])
    assert out.dtype == jnp.float32
    # bf16 has 8 mantissa bits: relative error bounded by 2^-8
    np.testing.assert_allclose(np.asarray(out), np.asarray(x),
                               rtol=2 ** -8, atol=1e-30)


def test_int8_codec_error_bound_deterministic():
    rng = np.random.default_rng(1)
    x = jnp.asarray(rng.normal(size=(1000,)) * 3.0, jnp.float32)
    c = Int8Codec(bucket_size=128, stochastic=False)
    out = np.asarray(c.decode(c.encode(x), x.shape[0]))
    assert out.shape == (1000,)
    # per-bucket bound: |err| <= max|bucket|/254 for round-to-nearest
    xs = np.asarray(x)
    pad = (-len(xs)) % 128
    xb = np.pad(xs, (0, pad)).reshape(-1, 128)
    bound = np.abs(xb).max(axis=1) / 254.0 + 1e-7
    err = np.abs(np.pad(out - xs, (0, pad)).reshape(-1, 128))
    assert (err <= bound[:, None]).all(), (err.max(), bound)


def test_int8_codec_stochastic_bound_and_unbiased():
    x = jnp.asarray(np.random.default_rng(2).normal(size=(256,)),
                    jnp.float32)
    c = Int8Codec(bucket_size=256, stochastic=True)
    scale = float(jnp.max(jnp.abs(x))) / 127.0
    outs = np.stack([
        np.asarray(c.decode(c.encode(x, key=jax.random.key(k)), 256))
        for k in range(64)])
    # stochastic floor(v+u): one full quantization step worst case
    assert np.abs(outs - np.asarray(x)).max() <= scale + 1e-7
    # unbiased: averaging across keys converges on the input
    mean_err = np.abs(outs.mean(axis=0) - np.asarray(x)).max()
    assert mean_err < 0.35 * scale, (mean_err, scale)


def test_int8_codec_zero_bucket_stays_zero():
    x = jnp.zeros((512,), jnp.float32)
    c = Int8Codec(bucket_size=64)
    out = np.asarray(c.decode(c.encode(x), 512))
    assert np.isfinite(out).all() and (out == 0).all()


def test_int8_codec_small_vector_clamps_bucket():
    """A shard SMALLER than bucket_size must not be zero-padded up to a
    full bucket — the wire would exceed flat fp32 (the whole point of
    the codec inverted).  The bucket clamps to the vector length."""
    x = jnp.asarray(np.random.default_rng(3).normal(size=(61,)),
                    jnp.float32)
    c = Int8Codec()  # default bucket_size=512 >> 61
    q, scale = c.encode(x)
    wire = q.size * q.dtype.itemsize + scale.size * scale.dtype.itemsize
    assert wire < 61 * 4, (wire, q.shape, scale.shape)
    out = np.asarray(c.decode((q, scale), 61))
    bound = float(jnp.max(jnp.abs(x))) / 254.0 + 1e-7
    assert out.shape == (61,)
    assert np.abs(out - np.asarray(x)).max() <= bound


def test_get_codec_resolution():
    assert get_codec(None) is None
    assert get_codec("fp32") is None
    assert isinstance(get_codec("bf16"), Bf16Codec)
    assert isinstance(get_codec(jnp.bfloat16), Bf16Codec)
    assert isinstance(get_codec("int8"), Int8Codec)
    custom = Int8Codec(bucket_size=64, stochastic=False)
    assert get_codec(custom) is custom
    with pytest.raises(ValueError):
        get_codec("fp8_someday")
    assert wire_itemsize(None) == 4.0
    assert wire_itemsize("bf16") == 2.0
    assert wire_itemsize("int8") == pytest.approx(1.0 + 4.0 / 512)


# ---------------------------------------------------------------------------
# dcn mesh construction + error paths (satellite)
# ---------------------------------------------------------------------------

def test_dcn_mesh_axes_and_batch_sharding():
    mesh = _dcn_mesh()
    assert mesh.axis_names == ("dcn", "data")
    assert mesh.shape["dcn"] == 2 and mesh.shape["data"] == 4
    assert batch_axes_of(mesh) == ("dcn", "data")
    assert fast_batch_axes_of(mesh) == ("data",)
    sh = batch_sharding(mesh)
    assert sh.spec == P(("dcn", "data"))
    sm = dcn_slice_map(mesh)
    assert sorted(sm) == list(range(8))
    assert sorted(set(sm.values())) == [0, 1]
    assert sum(1 for v in sm.values() if v == 0) == 4


def test_meshconfig_accepts_dcn():
    mesh = MeshConfig(dcn=2, data=-1).build()
    assert mesh.shape["dcn"] == 2
    assert mesh.shape["data"] == len(jax.devices()) // 2


def test_make_mesh_rejects_two_wildcards():
    with pytest.raises(ValueError, match="only one mesh axis may be -1"):
        make_mesh({"data": -1, "fsdp": -1})


def test_make_mesh_rejects_non_dividing_wildcard():
    # 8 devices, dcn=3 leaves no integer data extent for the -1
    with pytest.raises(ValueError, match="don't divide"):
        make_mesh({"dcn": 3, "data": -1}, jax.devices()[:8])


def test_make_mesh_rejects_oversized_product():
    with pytest.raises(ValueError, match="exceed device count"):
        make_mesh({"data": 16}, jax.devices()[:8])


def test_make_mesh_unknown_axes_order_after_known():
    """Unknown extra axes append AFTER the canonical AXES, in
    insertion order — the documented ordering contract."""
    mesh = make_mesh({"zeta": 2, "data": 2, "alpha": 2},
                     jax.devices()[:8])
    assert mesh.axis_names == ("data", "zeta", "alpha")


def test_make_mesh_truncation_warns_with_device_ids(caplog):
    import logging
    with caplog.at_level(logging.WARNING, logger="bigdl_tpu.parallel"):
        mesh = make_mesh({"data": 4}, jax.devices()[:8])
    assert int(np.prod(mesh.devices.shape)) == 4
    dropped = [d.id for d in jax.devices()[4:8]]
    msgs = [r.getMessage() for r in caplog.records
            if "dropping device" in r.getMessage()]
    assert msgs, caplog.records
    for did in dropped:
        assert str(did) in msgs[0]


# ---------------------------------------------------------------------------
# hierarchical_grad_sync numerics
# ---------------------------------------------------------------------------

def _sync_stacked(mesh, wire=None, n=97):
    """Run the primitive via shard_map on stacked per-device local
    grads [8, n] (+ a second ragged leaf) and return the synced tree."""
    rng = np.random.default_rng(3)
    a = jnp.asarray(rng.normal(size=(8, n)), jnp.float32)
    b = jnp.asarray(rng.normal(size=(8, 3, 5)), jnp.float32)

    def local(av, bv):
        grads = {"a": av.reshape(-1), "b": bv[0]}
        out = hierarchical_grad_sync(grads, mesh, wire_dtype=wire,
                                     rng=jax.random.key(0))
        return out["a"], out["b"]

    fn = jax.jit(shard_map(
        local, mesh,
        in_specs=(P(("dcn", "data")), P(("dcn", "data"))),
        out_specs=(P(), P())))
    oa, ob = fn(a, b)
    return (np.asarray(oa), np.asarray(ob),
            np.asarray(a).mean(axis=0), np.asarray(b).mean(axis=0))


def test_hier_sync_fp32_matches_mean():
    mesh = _dcn_mesh()
    oa, ob, ra, rb = _sync_stacked(mesh)
    np.testing.assert_allclose(oa, ra, rtol=1e-6, atol=1e-7)
    np.testing.assert_allclose(ob, rb, rtol=1e-6, atol=1e-7)
    assert ob.shape == (3, 5)  # tree structure + shapes survive


def test_hier_sync_bf16_within_tolerance():
    oa, ob, ra, rb = _sync_stacked(_dcn_mesh(), wire="bf16")
    np.testing.assert_allclose(oa, ra, rtol=0, atol=2e-2)
    np.testing.assert_allclose(ob, rb, rtol=0, atol=2e-2)


def test_hier_sync_int8_within_tolerance():
    oa, ob, ra, rb = _sync_stacked(_dcn_mesh(), wire="int8")
    np.testing.assert_allclose(oa, ra, rtol=0, atol=5e-2)
    np.testing.assert_allclose(ob, rb, rtol=0, atol=5e-2)


def test_hier_sync_degenerates_without_dcn_axis():
    """On a dcn-less mesh the schedule collapses to rs+ag — an
    explicit flat mean, numerically exact."""
    mesh = make_mesh({"data": 8}, jax.devices()[:8])
    a = jnp.asarray(np.random.default_rng(4).normal(size=(8, 32)),
                    jnp.float32)

    fn = jax.jit(shard_map(
        lambda v: hierarchical_grad_sync({"g": v.reshape(-1)},
                                         mesh)["g"],
        mesh, in_specs=P("data"), out_specs=P()))
    np.testing.assert_allclose(np.asarray(fn(a)),
                               np.asarray(a).mean(axis=0),
                               rtol=1e-6, atol=1e-7)


def test_hier_sync_accounts_dcn_axis_bytes():
    """The dcn hop lands in collective_bytes_total{op, axis="dcn"} at
    trace time through the PR-7 wrappers."""
    from bigdl_tpu import telemetry
    from bigdl_tpu.telemetry import families as tfam
    mesh = _dcn_mesh()
    telemetry.enable()
    telemetry.reset()
    try:
        a = jnp.ones((8, 64), jnp.float32)
        jax.jit(shard_map(
            lambda v: hierarchical_grad_sync(
                {"g": v.reshape(-1)}, mesh, wire_dtype="bf16")["g"],
            mesh, in_specs=P(("dcn", "data")), out_specs=P()),
        ).lower(a).compile()
        dcn_bytes = sum(
            v for (op, ax), v in
            tfam.collective_bytes_total().samples() if ax == "dcn")
        fast_bytes = sum(
            v for (op, ax), v in
            tfam.collective_bytes_total().samples() if ax == "data")
        # bf16 gather across 2 slices of the 16-elem shard: 2*16*2 B
        assert dcn_bytes == 2 * 16 * 2
        # rs (64*4/4) + ag (64*4) over the fast axis
        assert fast_bytes == 64 + 256
    finally:
        telemetry.reset()
        telemetry.disable()


def test_hier_sync_compressed_bytes_constant_in_slice_count():
    """The compressed dcn hop is a chunk-ownership all-reduce
    (all_to_all + all-gather): 2·shard·w bytes, CONSTANT in the slice
    count.  A gather-everything schedule would grow as S·shard·w and
    pessimize compression beyond 2 slices."""
    from bigdl_tpu import telemetry
    from bigdl_tpu.telemetry import families as tfam
    mesh = make_mesh({"dcn": 4, "data": -1}, jax.devices()[:8])
    telemetry.enable()
    telemetry.reset()
    try:
        a = jnp.ones((8, 64), jnp.float32)
        jax.jit(shard_map(
            lambda v: hierarchical_grad_sync(
                {"g": v.reshape(-1)}, mesh, wire_dtype="bf16")["g"],
            mesh, in_specs=P(("dcn", "data")), out_specs=P()),
        ).lower(a).compile()
        dcn_bytes = sum(
            v for (op, ax), v in
            tfam.collective_bytes_total().samples() if ax == "dcn")
        # F=2 -> 32-elem shard; a2a (4 chunks x 8) bf16 = 64 B, gather
        # of the 8-elem reduced chunk = 8*2*4 = 64 B: 2*shard*2, NOT
        # S*shard*2 (=256)
        assert dcn_bytes == 2 * 32 * 2, dcn_bytes
    finally:
        telemetry.reset()
        telemetry.disable()


# ---------------------------------------------------------------------------
# Optimizer wiring: loss equivalence + compiled-HLO byte acceptance
# ---------------------------------------------------------------------------

_N_STEPS = 20


def _train(mesh_axes, hierarchical=False, wire=None):
    from bigdl_tpu.dataset import SampleToMiniBatch
    from bigdl_tpu.dataset.dataset import DataSet, Sample
    from bigdl_tpu.optim import Optimizer, SGD, Trigger
    from bigdl_tpu.utils import set_seed
    set_seed(99)
    model = nn.Sequential(
        nn.Linear(16, 32), nn.ReLU(), nn.Linear(32, 10), nn.LogSoftMax())
    rng = np.random.default_rng(5)
    samples = [Sample(rng.normal(size=(16,)).astype(np.float32),
                      int(rng.integers(1, 11))) for _ in range(64)]
    data = (DataSet.array(samples, shuffle=False)
            .transform(SampleToMiniBatch(16)))
    opt = (Optimizer(model, data, nn.ClassNLLCriterion())
           .set_optim_method(SGD(0.1, momentum=0.9, dampening=0.0))
           .set_end_when(Trigger.max_iteration(_N_STEPS))
           .set_log_interval(1)
           .set_mesh(MeshConfig(**mesh_axes)))
    if hierarchical:
        opt.set_gradient_sync(hierarchical=True, wire_dtype=wire)
    opt.optimize()
    leaves = [np.asarray(l) for l in
              jax.tree_util.tree_leaves(model.parameters())]
    return float(opt.state["loss"]), leaves


_FLAT_CACHE = {}


def _flat_run():
    if "flat" not in _FLAT_CACHE:
        _FLAT_CACHE["flat"] = _train({"data": 8})
    return _FLAT_CACHE["flat"]


def test_optimizer_flat_sync_ignores_dcn_mesh_shape():
    """A dcn×data mesh with the sync mode UNSET is still plain DP: the
    fixed-seed run matches the data-only mesh bit for bit."""
    l_flat, p_flat = _flat_run()
    l_dcn, p_dcn = _train({"dcn": 2, "data": -1})
    assert l_dcn == l_flat
    for a, b in zip(p_flat, p_dcn):
        np.testing.assert_array_equal(a, b)


def test_optimizer_hierarchical_fp32_matches_flat():
    l_flat, p_flat = _flat_run()
    l_h, p_h = _train({"dcn": 2, "data": -1}, hierarchical=True)
    np.testing.assert_allclose(l_h, l_flat, rtol=1e-5)
    for a, b in zip(p_flat, p_h):
        np.testing.assert_allclose(a, b, rtol=1e-4, atol=1e-6)


def test_optimizer_hierarchical_bf16_loss_within_tolerance():
    """Acceptance: fixed-seed loss after 20 steps matches flat sync
    within 1e-2 relative under the bf16 wire."""
    l_flat, _ = _flat_run()
    l_b, _ = _train({"dcn": 2, "data": -1}, hierarchical=True,
                    wire="bf16")
    assert abs(l_b - l_flat) <= 1e-2 * abs(l_flat), (l_b, l_flat)


@pytest.mark.slow
def test_optimizer_hierarchical_int8_loss_within_tolerance():
    l_flat, _ = _flat_run()
    l_i, _ = _train({"dcn": 2, "data": -1}, hierarchical=True,
                    wire="int8")
    assert abs(l_i - l_flat) <= 2e-2 * abs(l_flat), (l_i, l_flat)


def _mini_batch():
    from bigdl_tpu.dataset.dataset import MiniBatch
    rng = np.random.default_rng(5)
    return MiniBatch(rng.normal(size=(16, 16)).astype(np.float32),
                     rng.integers(1, 11, size=(16,)).astype(np.int64))


def _program_text(compiled) -> str:
    """The compiled program without jax 0.9's source-location tables
    (FileNames ... StackFrames) and the frame ids that point into them:
    they record the line each caller stood on, which differs between two
    calls of one helper that lower the identical program."""
    text = re.sub(r"\nFileNames\n.*?\n\n\n", "\n", compiled.as_text(),
                  flags=re.S)
    return re.sub(r" ?stack_frame_id=\d+", "", text)


def _compiled_step(hierarchical=False, wire=None):
    from bigdl_tpu.dataset.dataset import Sample
    from bigdl_tpu.optim import Optimizer, SGD
    from bigdl_tpu.utils import set_seed
    set_seed(99)
    model = nn.Sequential(
        nn.Linear(16, 32), nn.ReLU(), nn.Linear(32, 10), nn.LogSoftMax())
    opt = (Optimizer(model, [Sample(np.zeros(16, np.float32), 1)],
                     nn.ClassNLLCriterion(), batch_size=16)
           .set_optim_method(SGD(0.1))
           .set_mesh(MeshConfig(dcn=2, data=-1)))
    if hierarchical:
        opt.set_gradient_sync(hierarchical=True, wire_dtype=wire)
    elif wire == "explicit-off":
        opt.set_gradient_sync(hierarchical=False)
    return opt.compile_step(_mini_batch())


def test_compiled_cross_slice_bytes_acceptance():
    """Acceptance: on the 8-fake-device 2-slice mesh, the compiled
    hierarchical step's cross-slice (dcn-axis) payload is ≤ 55% of the
    flat fp32 all-reduce baseline under bf16 and ≤ 30% under int8."""
    sm = dcn_slice_map(_dcn_mesh())
    base = cross_group_hlo_bytes(_compiled_step(), sm)
    assert base is not None and base["total"] > 0
    bf16 = cross_group_hlo_bytes(
        _compiled_step(hierarchical=True, wire="bf16"), sm)["total"]
    int8 = cross_group_hlo_bytes(
        _compiled_step(hierarchical=True, wire="int8"), sm)["total"]
    assert bf16 <= 0.55 * base["total"], (bf16, base)
    assert int8 <= 0.30 * base["total"], (int8, base)
    # and the hierarchy alone (fp32 wire) already beats flat: the
    # cross-slice hop carries 1/F of the gradient
    fp32 = cross_group_hlo_bytes(
        _compiled_step(hierarchical=True), sm)["total"]
    assert fp32 <= 0.30 * base["total"], (fp32, base)


def test_compiled_step_hlo_identical_when_sync_unset():
    """Acceptance: with the sync mode unset the step HLO is
    byte-identical to a build that never saw set_gradient_sync."""
    default = _program_text(_compiled_step())
    explicit_off = _program_text(_compiled_step(wire="explicit-off"))
    assert default == explicit_off
    # and the hierarchical program is genuinely different
    assert _program_text(_compiled_step(hierarchical=True)) != default


def test_compile_step_restores_training_mode():
    """compile_step is a read-only introspection hook: lowering needs
    the training-mode program, but an eval_mode'd model must come back
    out in eval mode (dropout/BN-update must not silently re-arm)."""
    from bigdl_tpu.dataset.dataset import Sample
    from bigdl_tpu.optim import Optimizer, SGD
    from bigdl_tpu.utils import set_seed
    set_seed(99)
    model = nn.Sequential(
        nn.Linear(16, 32), nn.ReLU(), nn.Linear(32, 10), nn.LogSoftMax())
    opt = (Optimizer(model, [Sample(np.zeros(16, np.float32), 1)],
                     nn.ClassNLLCriterion(), batch_size=16)
           .set_optim_method(SGD(0.1))
           .set_mesh(MeshConfig(dcn=2, data=-1)))
    model.eval_mode()
    opt.compile_step(_mini_batch())
    assert not model.is_training()
    assert not any(m.training for _, m in model.named_modules())


def test_compile_step_mirrors_watchdog_health_wiring():
    """A watchdog-armed optimize() dispatches the health=True step
    (in-graph grad-norm + guards) — compile_step must introspect THAT
    program, not the bare one."""
    from bigdl_tpu.dataset.dataset import Sample
    from bigdl_tpu.optim import Optimizer, SGD
    from bigdl_tpu.utils import set_seed

    def build(watchdog):
        set_seed(99)
        model = nn.Sequential(
            nn.Linear(16, 32), nn.ReLU(), nn.Linear(32, 10),
            nn.LogSoftMax())
        opt = (Optimizer(model, [Sample(np.zeros(16, np.float32), 1)],
                         nn.ClassNLLCriterion(), batch_size=16)
               .set_optim_method(SGD(0.1))
               .set_mesh(MeshConfig(dcn=2, data=-1)))
        if watchdog:
            opt.set_health_watchdog()
        return opt

    bare = build(False).compile_step(_mini_batch())
    armed = build(True).compile_step(_mini_batch())
    # the armed program returns the extra grad-norm output
    n_out = lambda c: len(jax.tree_util.tree_leaves(  # noqa: E731
        c.output_shardings))
    assert n_out(armed) == n_out(bare) + 1


def test_compile_step_abstract_state_hlo_identical():
    """compile_step lowers the opt states from avals (no device
    allocation of momentum/variance buffers) — the program must be
    byte-identical to one lowered from the concrete init_state arrays,
    for a params-congruent state (SGD velocity, Adam m/v) AND a
    non-congruent one (LBFGS's flat history buffers)."""
    import jax
    from bigdl_tpu.dataset.dataset import Sample
    from bigdl_tpu.optim import Optimizer, SGD, Adam, LBFGS
    from bigdl_tpu.optim.optimizer import (
        _stage, batch_sharding, shard_model_params)
    from bigdl_tpu.utils import get_seed, set_seed

    def build(method, hierarchical):
        set_seed(99)
        model = nn.Sequential(
            nn.Linear(16, 32), nn.ReLU(), nn.Linear(32, 10),
            nn.LogSoftMax())
        opt = (Optimizer(model, [Sample(np.zeros(16, np.float32), 1)],
                         nn.ClassNLLCriterion(), batch_size=16)
               .set_optim_method(method)
               .set_mesh(MeshConfig(dcn=2, data=-1)))
        if hierarchical:
            opt.set_gradient_sync(hierarchical=True, wire_dtype="bf16")
        return opt

    def concrete_compile(opt, batch):
        # compile_step's body with abstract_state=False
        mesh = opt.mesh_config.build()
        model = shard_model_params(opt.model.train_mode(), mesh,
                                   opt.sharding_rules)
        (pg, rest, names, _m, states, specs) = opt._setup_step_state(
            model, abstract_state=False)
        step = opt._build_step(mesh, names, specs, raw=True)
        xs = batch_sharding(mesh)
        with mesh:
            x = _stage(batch.get_input(), xs)
            y = _stage(batch.get_target(), xs)
            rng = jax.random.fold_in(jax.random.key(get_seed()), 0)
            return step.lower(pg, rest, states, x, y, rng, 1).compile()

    mb = _mini_batch()
    for method, hier in ((lambda: SGD(0.1, momentum=0.9), True),
                         (lambda: Adam(1e-3), True),
                         (lambda: SGD(0.1, momentum=0.9), False),
                         (lambda: LBFGS(), False)):
        abstract = _program_text(build(method(), hier).compile_step(mb))
        concrete = _program_text(
            concrete_compile(build(method(), hier), mb))
        assert abstract == concrete, (method(), hier)


# ---------------------------------------------------------------------------
# config validation
# ---------------------------------------------------------------------------

def _opt_for_plan(**mesh_axes):
    from bigdl_tpu.dataset.dataset import Sample
    from bigdl_tpu.optim import Optimizer
    model = nn.Sequential(nn.Linear(4, 4))
    return (Optimizer(model, [Sample(np.zeros(4, np.float32), 1)],
                      nn.ClassNLLCriterion(), batch_size=1)
            .set_mesh(MeshConfig(**mesh_axes)))


def test_set_gradient_sync_rejects_unknown_wire():
    with pytest.raises(ValueError, match="wire dtype"):
        _opt_for_plan(data=8).set_gradient_sync(
            hierarchical=True, wire_dtype="fp4")


def test_grad_sync_plan_rejects_wire_without_hierarchical():
    # the setter itself rejects the pairing at configure time …
    with pytest.raises(ValueError, match="hierarchical=True"):
        _opt_for_plan(data=8).set_gradient_sync(
            hierarchical=False, wire_dtype="bf16")
    # … and plan resolution backstops a bypassed setter
    opt = _opt_for_plan(data=8)
    opt.grad_sync_wire_dtype = "bf16"  # bypass the setter's pairing
    with pytest.raises(ValueError, match="hierarchical=True"):
        opt._grad_sync_plan(opt.mesh_config.build())


def test_grad_sync_plan_rejects_model_axes():
    opt = _opt_for_plan(data=2, model=4).set_gradient_sync(
        hierarchical=True)
    with pytest.raises(ValueError, match="batch-parallel"):
        opt._grad_sync_plan(opt.mesh_config.build())


def test_grad_sync_plan_rejects_sum_reduction_criterion():
    """The hierarchical step averages per-shard losses/gradients —
    valid only for a mean-reduction criterion.  size_average=False
    would silently train at lr/n_devices, including one SMUGGLED
    inside a composite (MultiCriterion's crits / TimeDistributed's
    critrn), which the guard walks named_modules to find."""
    from bigdl_tpu.dataset.dataset import Sample
    from bigdl_tpu.optim import Optimizer

    def plan(crit):
        opt = (Optimizer(nn.Sequential(nn.Linear(4, 4)),
                         [Sample(np.zeros(4, np.float32), 1)],
                         crit, batch_size=1)
               .set_mesh(MeshConfig(data=8))
               .set_gradient_sync(hierarchical=True))
        return opt._grad_sync_plan(opt.mesh_config.build())

    for crit in (
            nn.ClassNLLCriterion(size_average=False),
            nn.CrossEntropyCriterion(size_average=False),
            nn.MultiCriterion().add(
                nn.ClassNLLCriterion(size_average=False)),
            nn.TimeDistributedCriterion(
                nn.ClassNLLCriterion(size_average=False),
                size_average=True),
            # batch-sum criteria WITHOUT a size_average flag — the
            # attribute probe can't see them, the class list must
            nn.KLDCriterion(),
            nn.MultiCriterion().add(nn.GaussianCriterion())):
        with pytest.raises(ValueError, match="mean-reduction"):
            plan(crit)
    # TimeDistributedCriterion's OWN size_average=False (the default)
    # normalizes over TIME, not batch — same extent on every shard, so
    # it must stay accepted
    assert plan(nn.TimeDistributedCriterion(
        nn.ClassNLLCriterion())) is not None


def test_grad_sync_plan_warns_on_weighted_criterion(caplog):
    """Class-weighted (or padding-masked) criteria divide by the LOCAL
    shard's weight sum, so the hierarchical pmean of local means
    differs from the flat step's global weighted mean when shards draw
    different class mixes — advisory, not rejection (uniform weights
    and no padding agree exactly).  Covers the bare criterion and the
    CrossEntropy wrapper's ``inner``."""
    import logging
    from bigdl_tpu.dataset.dataset import Sample
    from bigdl_tpu.optim import Optimizer
    for crit in (nn.ClassNLLCriterion(weights=[1.0, 2.0]),
                 nn.CrossEntropyCriterion(weights=[1.0, 2.0]),
                 # explicit paddingValue: the same local-denominator
                 # rescaling, detected without class weights
                 nn.ClassNLLCriterion(paddingValue=0),
                 # nested inside a composite: the walk must find it
                 nn.MultiCriterion().add(
                     nn.ClassNLLCriterion(weights=[1.0, 2.0]))):
        opt = (Optimizer(nn.Sequential(nn.Linear(4, 2)),
                         [Sample(np.zeros(4, np.float32), 1)],
                         crit, batch_size=1)
               .set_mesh(MeshConfig(dcn=2, data=-1))
               .set_gradient_sync(hierarchical=True))
        caplog.clear()
        with caplog.at_level(logging.WARNING, logger="bigdl_tpu.optim"):
            plan = opt._grad_sync_plan(opt.mesh_config.build())
        assert plan is not None
        assert any("weight sum" in r.getMessage()
                   for r in caplog.records), type(crit).__name__
    # unweighted criteria stay silent
    caplog.clear()
    opt2 = _opt_for_plan(dcn=2, data=-1).set_gradient_sync(
        hierarchical=True)
    with caplog.at_level(logging.WARNING, logger="bigdl_tpu.optim"):
        assert opt2._grad_sync_plan(opt2.mesh_config.build()) is not None
    assert not any("weight sum" in r.getMessage()
                   for r in caplog.records)


def test_grad_sync_plan_rejects_sharding_rules():
    from bigdl_tpu.parallel import ShardingRules
    opt = _opt_for_plan(data=8)
    opt.set_mesh(MeshConfig(data=8), ShardingRules(fsdp=True))
    opt.set_gradient_sync(hierarchical=True)
    with pytest.raises(ValueError, match="replicated"):
        opt._grad_sync_plan(opt.mesh_config.build())


def test_grad_sync_plan_warns_wire_without_dcn(caplog):
    import logging
    opt = _opt_for_plan(data=8).set_gradient_sync(
        hierarchical=True, wire_dtype="bf16")
    with caplog.at_level(logging.WARNING, logger="bigdl_tpu.optim"):
        plan = opt._grad_sync_plan(opt.mesh_config.build())
    assert plan is not None and plan["wire_dtype"] is None
    assert any("no slow hop" in r.getMessage() for r in caplog.records)


def test_grad_sync_plan_warns_on_batch_stat_modules(caplog):
    """BatchNorm under the hierarchical shard_map computes shard-local
    statistics (data-parallel BN), not the flat step's global-batch
    stats — the plan warns naming the module, and stays resolvable."""
    import logging
    from bigdl_tpu.dataset.dataset import Sample
    from bigdl_tpu.optim import Optimizer
    model = nn.Sequential(
        nn.Linear(4, 4), nn.BatchNormalization(4), nn.ReLU())
    opt = (Optimizer(model, [Sample(np.zeros(4, np.float32), 1)],
                     nn.ClassNLLCriterion(), batch_size=1)
           .set_mesh(MeshConfig(dcn=2, data=-1))
           .set_gradient_sync(hierarchical=True, wire_dtype="bf16"))
    with caplog.at_level(logging.WARNING, logger="bigdl_tpu.optim"):
        plan = opt._grad_sync_plan(opt.mesh_config.build())
        # bench resolves the plan once for artifact stamping and the
        # step build resolves it again — the advisory fires once
        opt._grad_sync_plan(opt.mesh_config.build())
    assert plan is not None and plan["wire_dtype"] == "bf16"
    msgs = [r.getMessage() for r in caplog.records
            if "batch statistics" in r.getMessage()]
    assert len(msgs) == 1 and "BatchNormalization" in msgs[0], \
        caplog.records
    # BN-free models stay silent
    caplog.clear()
    opt2 = _opt_for_plan(dcn=2, data=-1).set_gradient_sync(
        hierarchical=True)
    with caplog.at_level(logging.WARNING, logger="bigdl_tpu.optim"):
        assert opt2._grad_sync_plan(opt2.mesh_config.build()) is not None
    assert not [r for r in caplog.records
                if "batch statistics" in r.getMessage()]


# ---------------------------------------------------------------------------
# analytic floor + HLO classifier units + dcn roofline
# ---------------------------------------------------------------------------

def test_grad_allreduce_bytes_hierarchical_mode():
    from bigdl_tpu.parallel.sharding import grad_allreduce_bytes
    model = nn.Linear(12, 16)  # (16*12 + 16) * 4 = 832 B fp32
    mesh = _dcn_mesh()  # F=4, S=2
    flat = grad_allreduce_bytes(model, mesh)
    assert flat["bytes_per_step"] == 832.0  # unchanged default mode
    # the flat all-reduce crosses DCN at FULL width on a dcn>1 mesh —
    # the baseline needs its own dcn roofline floor
    assert flat["dcn_bytes_per_step"] == 832.0
    h = grad_allreduce_bytes(model, mesh, hierarchical=True)
    assert h["flat_fp32_bytes_per_step"] == 832.0
    assert h["intra_bytes_per_step"] == 832.0 / 4 + 832.0
    assert h["dcn_bytes_per_step"] == 832.0 / 4  # uncompressed psum
    hb = grad_allreduce_bytes(model, mesh, hierarchical=True,
                              wire_dtype="bf16")
    assert hb["dcn_bytes_per_step"] == 2 * (832.0 / 4) * 0.5
    assert hb["compression_ratio"] == pytest.approx(
        832.0 / (832.0 / 4 + 832.0 + 832.0 / 4))
    hi = grad_allreduce_bytes(model, mesh, hierarchical=True,
                              wire_dtype="int8")
    # 208 B shard = 52 elems in S=2 chunks of 26: the bucket clamps to
    # the 26-elem chunk, so each hop pays 52 int8 bytes + 2 fp32
    # scales — NOT the nominal 1+4/512 per-element asymptote
    assert hi["dcn_bytes_per_step"] == pytest.approx(2 * (52 + 2 * 4))
    assert hi["dcn_bytes_per_step"] == pytest.approx(
        2 * wire_bytes("int8", 52, n_chunks=2))
    assert hi["wire_dtype"] == "int8"
    # uncompressed SPELLINGS ("fp32"/"none") resolve to no codec at
    # runtime — the estimator must cost the single-hop psum, not the
    # two-hop codec schedule
    hf = grad_allreduce_bytes(model, mesh, hierarchical=True,
                              wire_dtype="fp32")
    assert hf["dcn_bytes_per_step"] == h["dcn_bytes_per_step"]
    assert hf["wire_dtype"] is None


def test_grad_allreduce_bytes_hierarchical_rejects_rules():
    """The hierarchical estimator models replicated params (the
    primitive's requirement); rules would silently understate the
    floor by the shard factor for a config optimize() rejects."""
    from bigdl_tpu.parallel import ShardingRules
    from bigdl_tpu.parallel.sharding import grad_allreduce_bytes
    with pytest.raises(ValueError, match="replicated"):
        grad_allreduce_bytes(nn.Linear(12, 16), _dcn_mesh(),
                             ShardingRules(fsdp=True),
                             hierarchical=True)


def test_cross_group_hlo_bytes_text_units():
    text = "\n".join([
        "ENTRY main {",
        # within-group: devices {0,1} and {2,3} are both group-pure
        "  %a = f32[8]{0} all-reduce(%p), replica_groups={{0,1},{2,3}}",
        # cross-group explicit: {0,2} spans groups
        "  %b = f32[4]{0} all-reduce(%q), replica_groups={{0,2},{1,3}}",
        # iota form [2,2]<=[4] -> groups {0,1},{2,3}: within
        "  %c = bf16[16]{0} all-gather(%r), replica_groups=[2,2]<=[4]",
        # iota with transpose [2,2]<=[2,2]T(1,0) -> {0,2},{1,3}: cross
        "  %d = s8[32]{0} all-gather(%s), "
        "replica_groups=[2,2]<=[2,2]T(1,0)",
        # async pair: groups on -start, payload at -done (cross)
        "  %e.s = (f32[4]{0}, f32[8]{0}) all-reduce-start(%t), "
        "replica_groups={{0,3}}",
        "  %e.d = f32[8]{0} all-reduce-done(%e.s)",
        # collective-permute prints source_target_pairs, not
        # replica_groups — a ring strictly inside each group must NOT
        # fall through to the "spans everything" default
        "  %f = f32[8]{0} collective-permute(%u), "
        "source_target_pairs={{0,1},{1,0},{2,3},{3,2}}",
        # one pair hops the group boundary: counts
        "  %g = f32[16]{0} collective-permute(%v), "
        "source_target_pairs={{1,2}}",
        "}",
    ])
    group_of = {0: 0, 1: 0, 2: 1, 3: 1}
    out = cross_group_hlo_bytes(text, group_of)
    assert out["all-reduce"] == 4 * 4 + 8 * 4  # %b + %e.d
    assert out["all-gather"] == 32  # %d only (s8)
    assert out["collective-permute"] == 64  # %g only
    assert out["total"] == 16 + 32 + 32 + 64
    # single-group world: nothing crosses
    assert cross_group_hlo_bytes(text, {i: 0 for i in range(4)})[
        "total"] == 0.0


def test_roofline_dcn_bound_verdict():
    from bigdl_tpu.telemetry import perf as tperf
    roof = tperf.roofline_verdict(
        1e12, 1e8, 1e15, 1e12,
        comm_bytes_per_step=1e9, ici_bytes_per_s=200e9,
        dcn_bytes_per_step=2e8, dcn_bytes_per_s=12.5e9)
    # dcn floor: 2e8/12.5e9 = 16 ms > comm 5 ms > compute 1 ms
    assert roof["verdict"] == "dcn_bound"
    assert roof["min_dcn_s"] == pytest.approx(16e-3)
    assert roof["attainable_step_s"] == pytest.approx(16e-3)
    # without a dcn budget the three-floor behavior is unchanged
    old = tperf.roofline_verdict(
        1e12, 1e8, 1e15, 1e12,
        comm_bytes_per_step=1e9, ici_bytes_per_s=200e9)
    assert old["verdict"] == "comm_bound"
    assert "min_dcn_s" not in old


def test_device_dcn_table_and_env_override(monkeypatch):
    from bigdl_tpu.telemetry import perf as tperf
    assert tperf.device_dcn_bytes_per_s("TPU v5e") == 12.5e9
    assert tperf.device_dcn_bytes_per_s("weird") is None
    monkeypatch.setenv("BIGDL_TPU_DCN_BYTES_PER_S", "1e6")
    assert tperf.device_dcn_bytes_per_s("TPU v5e") == 1e6
    assert tperf.device_dcn_bytes_per_s(None) == 1e6


def test_device_dcn_env_override_bad_value_warns(caplog, monkeypatch):
    """An unparsable override must not be silently discarded — the
    verdict would be computed from the spec table while the operator
    believes their measured number is in effect."""
    import logging
    from bigdl_tpu.telemetry import perf as tperf
    monkeypatch.setenv("BIGDL_TPU_DCN_BYTES_PER_S", "12.5GB")
    with caplog.at_level(logging.WARNING, logger="bigdl_tpu.telemetry"):
        assert tperf.device_dcn_bytes_per_s("TPU v5e") == 12.5e9
    assert any("BIGDL_TPU_DCN_BYTES_PER_S" in r.getMessage()
               for r in caplog.records), caplog.records


def test_attribution_report_dcn_section():
    from bigdl_tpu.telemetry import perf as tperf
    records = [
        {"iterations": 1, "wall_s": 0.1, "data_wait_s": 0.01,
         "host_staging_s": 0.01, "device_compute_s": 0.07,
         "readback_s": 0.01}
        for _ in range(3)
    ]
    rep = tperf.attribution_report(
        records, flops_per_step=1e12, bytes_per_step=1e9,
        peak_spec_flops=197e12, hbm_bytes_per_s=819e9,
        comm_bytes_per_step=5e9, ici_bytes_per_s=200e9,
        dcn_bytes_per_step=1e9, dcn_bytes_per_s=12.5e9)
    assert rep["dcn"]["bytes_per_step"] == 1e9
    assert rep["dcn"]["min_dcn_s"] == pytest.approx(0.08)
    assert rep["roofline"]["verdict"] == "dcn_bound"
