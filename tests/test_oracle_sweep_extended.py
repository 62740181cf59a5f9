"""Extended numerical-oracle sweep: criterions, recurrent cells, and
layer-zoo tail vs torch CPU, plus the zoo-wide coverage manifest.

Widens tests/test_layers_torch_oracle.py toward the reference's per-layer
spec density (reference: spark/dl/src/test/.../nn/ has ~205 per-layer
specs and integration/torch/TH.scala drives a live Torch7 oracle; here
torch-cpu is the in-process oracle).  Criterions compare loss VALUES and
input GRADIENTS; recurrent cells run full sequences through Recurrent()
against a hand-rolled torch time loop (fwd + grads).

The manifest test at the bottom classifies EVERY public nn export:
oracle-swept here or in the base file, covered by a named test file
(claim verified against that file's source), or waived with a reason.
Adding a new export without classifying it fails the suite.
"""

import re

import numpy as np
import pytest

import jax
import jax.numpy as jnp
import torch
import torch.nn.functional as F

import bigdl_tpu.nn as nn

RTOL, ATOL = 1e-4, 1e-5


def rnd(*shape, seed=0):
    return np.random.RandomState(seed).randn(*shape).astype(np.float32)


def pos(*shape, seed=0, lo=0.05, hi=0.95):
    r = np.random.RandomState(seed).uniform(lo, hi, shape)
    return r.astype(np.float32)


def classes(n, k, seed=0):
    """1-based class targets, reference convention."""
    return np.random.RandomState(seed).randint(1, k + 1, n).astype(np.int64)


def signs(*shape, seed=0):
    return np.where(np.random.RandomState(seed).rand(*shape) > 0.5,
                    1.0, -1.0).astype(np.float32)


# ---------------------------------------------------------------------------
# Criterion sweep: (name, make_ours, torch_fn(inp..., target), make_data)
# make_data -> (inputs_list, target); a list of >1 inputs is passed as a
# table.  torch_fn receives torch tensors mirroring (inputs..., target).
# ---------------------------------------------------------------------------

def _t(x):
    return torch.tensor(x)


CRITERION_SWEEP = [
    ("AbsCriterion", lambda: nn.AbsCriterion(),
     lambda x, t: F.l1_loss(x, t),
     lambda: ([rnd(4, 5, seed=1)], rnd(4, 5, seed=2))),
    ("MSECriterion", lambda: nn.MSECriterion(),
     lambda x, t: F.mse_loss(x, t),
     lambda: ([rnd(4, 5, seed=3)], rnd(4, 5, seed=4))),
    ("SmoothL1Criterion", lambda: nn.SmoothL1Criterion(),
     lambda x, t: F.smooth_l1_loss(x, t),
     lambda: ([rnd(4, 5, seed=5)], rnd(4, 5, seed=6))),
    ("BCECriterion", lambda: nn.BCECriterion(),
     lambda x, t: F.binary_cross_entropy(x, t),
     lambda: ([pos(4, 5, seed=7)], pos(4, 5, seed=8))),
    ("ClassNLLCriterion", lambda: nn.ClassNLLCriterion(),
     lambda x, t: F.nll_loss(x, t.long() - 1),
     lambda: ([np.log(pos(4, 6, seed=9))], classes(4, 6, seed=10))),
    ("CrossEntropyCriterion", lambda: nn.CrossEntropyCriterion(),
     lambda x, t: F.cross_entropy(x, t.long() - 1),
     lambda: ([rnd(4, 6, seed=11)], classes(4, 6, seed=12))),
    ("CategoricalCrossEntropy", lambda: nn.CategoricalCrossEntropy(),
     lambda x, t: -(t * x.clamp(1e-8, 1.0).log()).sum(-1).mean(),
     lambda: ([pos(4, 6, seed=13)],
              np.eye(6, dtype=np.float32)[classes(4, 6, seed=14) - 1])),
    ("DistKLDivCriterion", lambda: nn.DistKLDivCriterion(),
     lambda x, t: F.kl_div(x, t, reduction="mean"),
     lambda: ([np.log(pos(4, 6, seed=15))], pos(4, 6, seed=16))),
    ("SoftMarginCriterion", lambda: nn.SoftMarginCriterion(),
     lambda x, t: F.soft_margin_loss(x, t),
     lambda: ([rnd(4, 5, seed=17)], signs(4, 5, seed=18))),
    ("MarginCriterion", lambda: nn.MarginCriterion(),
     lambda x, t: F.relu(1.0 - x * t).mean(),
     lambda: ([rnd(4, 5, seed=19)], signs(4, 5, seed=20))),
    ("MarginCriterion_squared",
     lambda: nn.MarginCriterion(squared=True),
     lambda x, t: F.relu(1.0 - x * t).pow(2).mean(),
     lambda: ([rnd(4, 5, seed=21)], signs(4, 5, seed=22))),
    ("HingeEmbeddingCriterion", lambda: nn.HingeEmbeddingCriterion(1.0),
     lambda x, t: F.hinge_embedding_loss(x, t, margin=1.0),
     lambda: ([np.abs(rnd(4, 5, seed=23))], signs(4, 5, seed=24))),
    ("MarginRankingCriterion", lambda: nn.MarginRankingCriterion(1.0),
     lambda a, b, t: F.margin_ranking_loss(a, b, t, margin=1.0),
     lambda: ([rnd(6, seed=25), rnd(6, seed=26)], signs(6, seed=27))),
    ("CosineEmbeddingCriterion",
     lambda: nn.CosineEmbeddingCriterion(0.1),
     lambda a, b, t: F.cosine_embedding_loss(a, b, t, margin=0.1),
     lambda: ([rnd(5, 8, seed=28), rnd(5, 8, seed=29)],
              signs(5, seed=30))),
    ("L1HingeEmbeddingCriterion",
     lambda: nn.L1HingeEmbeddingCriterion(1.0),
     lambda a, b, t: torch.where(
         t > 0, (a - b).abs().sum(-1),
         F.relu(1.0 - (a - b).abs().sum(-1))).sum(),
     lambda: ([rnd(5, 8, seed=31), rnd(5, 8, seed=32)],
              signs(5, seed=33))),
    ("MultiLabelSoftMarginCriterion",
     lambda: nn.MultiLabelSoftMarginCriterion(),
     lambda x, t: F.multilabel_soft_margin_loss(x, t),
     lambda: ([rnd(4, 6, seed=34)],
              (np.random.RandomState(35).rand(4, 6) > 0.5
               ).astype(np.float32))),
    ("MultiMarginCriterion", lambda: nn.MultiMarginCriterion(),
     lambda x, t: F.multi_margin_loss(x, t.long() - 1, margin=1.0),
     lambda: ([rnd(4, 6, seed=36)], classes(4, 6, seed=37))),
    ("MultiMarginCriterion_p2",
     lambda: nn.MultiMarginCriterion(p=2),
     lambda x, t: F.multi_margin_loss(x, t.long() - 1, p=2, margin=1.0),
     lambda: ([rnd(4, 6, seed=38)], classes(4, 6, seed=39))),
    ("CosineDistanceCriterion", lambda: nn.CosineDistanceCriterion(),
     lambda x, t: (1.0 - F.cosine_similarity(x, t, dim=-1)).mean(),
     lambda: ([rnd(5, 8, seed=40)], rnd(5, 8, seed=41))),
    ("CosineProximityCriterion",
     lambda: nn.CosineProximityCriterion(),
     lambda x, t: -(F.normalize(x, dim=-1)
                    * F.normalize(t, dim=-1)).sum(-1).mean(),
     lambda: ([rnd(5, 8, seed=42)], rnd(5, 8, seed=43))),
    ("DotProductCriterion", lambda: nn.DotProductCriterion(),
     lambda x, t: -(x * t).sum(),
     lambda: ([rnd(4, 5, seed=44)], rnd(4, 5, seed=45))),
    ("PoissonCriterion", lambda: nn.PoissonCriterion(),
     lambda x, t: F.poisson_nll_loss(x, t, log_input=False, eps=1e-8),
     lambda: ([pos(4, 5, seed=46, lo=0.2, hi=3.0)],
              pos(4, 5, seed=47, lo=0.0, hi=4.0))),
    ("MeanAbsolutePercentageCriterion",
     lambda: nn.MeanAbsolutePercentageCriterion(),
     lambda x, t: 100.0 * ((t - x).abs()
                           / t.abs().clamp(min=1e-7)).mean(),
     lambda: ([rnd(4, 5, seed=48)], rnd(4, 5, seed=49))),
    ("MeanSquaredLogarithmicCriterion",
     lambda: nn.MeanSquaredLogarithmicCriterion(),
     lambda x, t: ((x.clamp(min=1e-7) + 1).log()
                   - (t.clamp(min=1e-7) + 1).log()).pow(2).mean(),
     lambda: ([pos(4, 5, seed=50, lo=0.1, hi=3.0)],
              pos(4, 5, seed=51, lo=0.1, hi=3.0))),
    ("KullbackLeiblerDivergenceCriterion",
     lambda: nn.KullbackLeiblerDivergenceCriterion(),
     lambda x, t: (t.clamp(1e-7, 1.0)
                   * (t.clamp(1e-7, 1.0).log()
                      - x.clamp(1e-7, 1.0).log())).sum(-1).mean(),
     lambda: ([pos(4, 6, seed=52)], pos(4, 6, seed=53))),
    ("MultiLabelMarginCriterion",
     lambda: nn.MultiLabelMarginCriterion(),
     # torch targets are 0-based padded with -1; ours 1-based padded 0,
     # so t-1 maps exactly
     lambda x, t: F.multilabel_margin_loss(x, t.long() - 1),
     lambda: ([rnd(4, 6, seed=110)],
              np.stack([np.concatenate([
                  np.random.RandomState(111 + i).choice(
                      np.arange(1, 7), 2, replace=False),
                  np.zeros(4)]).astype(np.int64) for i in range(4)]))),
    ("L1Cost", lambda: nn.L1Cost(),
     lambda x, t: x.abs().sum(),
     lambda: ([rnd(4, 5, seed=54)], rnd(4, 5, seed=55))),
    ("DiceCoefficientCriterion",
     lambda: nn.DiceCoefficientCriterion(epsilon=1.0),
     lambda x, t: (1.0 - (2.0 * (x * t).sum(1) + 1.0)
                   / (x.sum(1) + t.sum(1) + 1.0)).mean(),
     lambda: ([pos(4, 10, seed=56)],
              (np.random.RandomState(57).rand(4, 10) > 0.5
               ).astype(np.float32))),
    ("PGCriterion", lambda: nn.PGCriterion(),
     lambda x, t: -(x.clamp(1e-8, 1.0).log() * t).sum(),
     lambda: ([pos(4, 5, seed=58)], rnd(4, 5, seed=59))),
    ("KLDCriterion", lambda: nn.KLDCriterion(),
     lambda m, lv, t: 0.5 * (m.pow(2) + lv.exp() - lv - 1.0).sum(),
     lambda: ([rnd(4, 6, seed=60), rnd(4, 6, seed=61) * 0.3],
              rnd(4, 6, seed=62))),
    ("GaussianCriterion", lambda: nn.GaussianCriterion(),
     lambda m, lv, t: 0.5 * (lv + (t - m).pow(2) / lv.exp()
                             + np.log(2 * np.pi)).sum(),
     lambda: ([rnd(4, 6, seed=63), rnd(4, 6, seed=64) * 0.3],
              rnd(4, 6, seed=65))),
    ("ClassSimplexCriterion", lambda: nn.ClassSimplexCriterion(5),
     lambda x, t, o=None: None,  # torch fn built per-instance below
     lambda: ([rnd(4, 5, seed=66)], classes(4, 5, seed=67))),
    ("TimeDistributedCriterion",
     lambda: nn.TimeDistributedCriterion(nn.MSECriterion()),
     lambda x, t: sum(F.mse_loss(x[:, i], t[:, i])
                      for i in range(x.shape[1])),
     lambda: ([rnd(3, 4, 5, seed=68)], rnd(3, 4, 5, seed=69))),
    ("MultiCriterion",
     lambda: nn.MultiCriterion().add(nn.MSECriterion(), 0.5).add(
         nn.AbsCriterion(), 2.0),
     lambda x, t: 0.5 * F.mse_loss(x, t) + 2.0 * F.l1_loss(x, t),
     lambda: ([rnd(4, 5, seed=70)], rnd(4, 5, seed=71))),
]


@pytest.mark.parametrize("case", CRITERION_SWEEP, ids=lambda c: c[0])
def test_criterion_sweep_value_and_grad(case):
    name, make_ours, tfn, make_data = case
    ours = make_ours()
    inputs, target = make_data()
    jx = [jnp.asarray(a) for a in inputs]
    tx = [torch.tensor(a, requires_grad=True) for a in inputs]
    tt = _t(target)

    if name == "ClassSimplexCriterion":
        # torch mirror needs the instance's simplex embedding buffer
        simplex = torch.tensor(np.asarray(ours.simplex))

        def tfn(x, t):
            emb = simplex[t.long() - 1]
            return (x - emb).pow(2).sum(-1).mean()

    def fwd(args):
        inp = args[0] if len(args) == 1 else list(args)
        return ours.forward(inp, jnp.asarray(target))

    out = float(fwd(jx))
    tout = tfn(*tx, tt)
    np.testing.assert_allclose(out, float(tout), rtol=RTOL, atol=ATOL,
                               err_msg=f"{name}: loss value")

    gs = jax.grad(lambda args: fwd(args))(tuple(jx))
    tout.backward()
    for i, (g, t) in enumerate(zip(gs, tx)):
        np.testing.assert_allclose(
            np.asarray(g), t.grad.numpy(), rtol=RTOL, atol=ATOL,
            err_msg=f"{name}: grad of input {i}")


# ---------------------------------------------------------------------------
# Recurrent cells: full sequences through Recurrent(cell) vs a torch
# time loop with copied weights (fwd + input grads).
# ---------------------------------------------------------------------------

def _torch_rnn_loop(step, x, state):
    outs = []
    for t in range(x.shape[1]):
        out, state = step(x[:, t], state)
        outs.append(out)
    return torch.stack(outs, dim=1)


def _np(p):
    return torch.tensor(np.asarray(p))


CELL_SWEEP = [
    ("RnnCell", lambda: nn.RnnCell(6, 5),
     lambda c: (lambda x: _torch_rnn_loop(
         lambda xt, h: ((lambda hn: (hn, hn))(
             torch.tanh(xt @ _np(c.w_input) + _np(c.bias)
                        + h @ _np(c.w_hidden)))),
         x, torch.zeros(x.shape[0], 5)))),
    ("LSTM", lambda: nn.LSTM(6, 5),
     lambda c: (lambda x: _torch_rnn_loop(
         lambda xt, st: (lambda gates: (lambda i, f, g, o: (
             lambda cn: (torch.sigmoid(o) * torch.tanh(cn),
                         (torch.sigmoid(o) * torch.tanh(cn), cn)))(
             torch.sigmoid(f) * st[1]
             + torch.sigmoid(i) * torch.tanh(g)))(
             *gates.chunk(4, dim=-1)))(
             xt @ _np(c.w_input) + _np(c.bias)
             + st[0] @ _np(c.w_hidden)),
         x, (torch.zeros(x.shape[0], 5), torch.zeros(x.shape[0], 5))))),
    ("LSTMPeephole", lambda: nn.LSTMPeephole(6, 5),
     lambda c: (lambda x: _torch_rnn_loop(
         lambda xt, st: (lambda gates: (lambda ii, ff, gg, oo: (
             lambda i, f: (lambda cn: (lambda o:
                           (o * torch.tanh(cn), (o * torch.tanh(cn), cn)))(
                 torch.sigmoid(oo + _np(c.peep_o) * cn)))(
                 f * st[1] + i * torch.tanh(gg)))(
             torch.sigmoid(ii + _np(c.peep_i) * st[1]),
             torch.sigmoid(ff + _np(c.peep_f) * st[1])))(
             *gates.chunk(4, dim=-1)))(
             xt @ _np(c.w_input) + _np(c.bias)
             + st[0] @ _np(c.w_hidden)),
         x, (torch.zeros(x.shape[0], 5), torch.zeros(x.shape[0], 5))))),
    ("GRU", lambda: nn.GRU(6, 5),
     lambda c: (lambda x: _torch_rnn_loop(
         lambda xt, h: (lambda xp: (lambda rz: (lambda r, z: (
             lambda g: ((1 - z) * g + z * h, (1 - z) * g + z * h))(
             torch.tanh(xp[..., 10:] + (r * h) @ _np(c.w_candidate))))(
             *rz.chunk(2, dim=-1)))(
             torch.sigmoid(xp[..., :10] + h @ _np(c.w_hidden))))(
             xt @ _np(c.w_input) + _np(c.bias)),
         x, torch.zeros(x.shape[0], 5)))),
]


@pytest.mark.parametrize("case", CELL_SWEEP, ids=lambda c: c[0])
def test_recurrent_cell_sweep(case):
    name, make_cell, make_torch = case
    from bigdl_tpu.utils import set_seed
    set_seed(hash(name) % 10000)
    cell = make_cell().eval_mode()
    rec = nn.Recurrent(cell).eval_mode()
    x = rnd(3, 4, 6, seed=80)
    tfn = make_torch(cell)

    jx = jnp.asarray(x)
    tx = torch.tensor(x, requires_grad=True)
    out = rec(jx)
    tout = tfn(tx)
    np.testing.assert_allclose(np.asarray(out), tout.detach().numpy(),
                               rtol=RTOL, atol=ATOL,
                               err_msg=f"{name}: forward")

    g = jax.grad(lambda a: jnp.sum(rec(a) ** 2))(jx)
    (tout ** 2).sum().backward()
    np.testing.assert_allclose(np.asarray(g), tx.grad.numpy(),
                               rtol=1e-3, atol=1e-4,
                               err_msg=f"{name}: input grad")


def test_multi_rnn_cell_matches_composition():
    """MultiRNNCell([a, b]) == feeding a's output stream into b."""
    from bigdl_tpu.utils import set_seed
    set_seed(2)
    a = nn.RnnCell(6, 6)
    b = nn.RnnCell(6, 5)
    stack = nn.Recurrent(nn.MultiRNNCell([a, b])).eval_mode()
    x = jnp.asarray(rnd(3, 4, 6, seed=81))
    out = stack(x)
    ref = nn.Recurrent(b).eval_mode()(nn.Recurrent(a).eval_mode()(x))
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                               rtol=RTOL, atol=ATOL)


# ---------------------------------------------------------------------------
# Layer-zoo tail rows (same harness shape as the base SWEEP)
# ---------------------------------------------------------------------------

EXTRA_SWEEP = [
    ("Swish", lambda: nn.Swish(), lambda o: F.silu,
     lambda: [rnd(3, 6, seed=90)]),
    ("BinaryThreshold", lambda: nn.BinaryThreshold(0.2),
     lambda o: (lambda x: (x > 0.2).float() + x * 0),
     lambda: [rnd(3, 6, seed=91)]),
    ("Flatten", lambda: nn.Flatten(),
     lambda o: (lambda x: x.reshape(x.shape[0], -1)),
     lambda: [rnd(3, 4, 5, seed=92)]),
    ("Echo", lambda: nn.Echo(), lambda o: (lambda x: x),
     lambda: [rnd(3, 4, seed=93)]),
    ("GlobalAveragePooling2D", lambda: nn.GlobalAveragePooling2D(),
     lambda o: (lambda x: x.mean(dim=(1, 2))),
     lambda: [rnd(2, 5, 5, 3, seed=94)]),
    ("GlobalAveragePooling3D", lambda: nn.GlobalAveragePooling3D(),
     lambda o: (lambda x: x.mean(dim=(1, 2, 3))),
     lambda: [rnd(2, 4, 4, 4, 3, seed=95)]),
    ("GlobalMaxPooling3D", lambda: nn.GlobalMaxPooling3D(),
     lambda o: (lambda x: x.amax(dim=(1, 2, 3))),
     lambda: [rnd(2, 4, 4, 4, 3, seed=96)]),
    ("GroupNorm", lambda: nn.GroupNorm(8, n_groups=4),
     lambda o: (lambda x: F.group_norm(
         x.permute(0, 3, 1, 2), 4,
         _np(o.weight), _np(o.bias), eps=1e-5).permute(0, 2, 3, 1)),
     lambda: [rnd(2, 5, 5, 8, seed=97)]),
    ("SReLU", lambda: nn.SReLU((6,)),
     lambda o: (lambda x: (lambda y: torch.where(
         y <= _np(o.t_left),
         _np(o.t_left) + _np(o.a_left) * (y - _np(o.t_left)), y))(
         torch.where(x >= _np(o.t_right),
                     _np(o.t_right) + _np(o.a_right) * (x - _np(o.t_right)),
                     x))),
     lambda: [rnd(3, 6, seed=98) * 2]),
    ("Highway", lambda: nn.Highway(5, activation=nn.ReLU()),
     lambda o: (lambda x: (lambda t, h: t * h + (1 - t) * x)(
         torch.sigmoid(F.linear(x, _np(o.gate.weight), _np(o.gate.bias))),
         F.relu(F.linear(x, _np(o.transform.weight),
                         _np(o.transform.bias))))),
     lambda: [rnd(4, 5, seed=99)]),
    ("InferReshape", lambda: nn.InferReshape((0, -1), batch_mode=False),
     lambda o: (lambda x: x.reshape(x.shape[0], -1)),
     lambda: [rnd(3, 4, 5, seed=100)]),
    ("Scale", lambda: nn.Scale((4,)),
     lambda o: (lambda x: x * _np(o.cmul.weight) + _np(o.cadd.bias)),
     lambda: [rnd(3, 4, seed=101)]),
    ("TimeDistributed", lambda: nn.TimeDistributed(nn.Linear(5, 3)),
     lambda o: (lambda x: F.linear(x, _np(o.layer.weight),
                                   _np(o.layer.bias))),
     lambda: [rnd(3, 4, 5, seed=102)]),
    ("SpatialShareConvolution",
     lambda: nn.SpatialShareConvolution(3, 6, 3, 3, 1, 1, 1, 1),
     lambda o: (lambda x: F.conv2d(
         x.permute(0, 3, 1, 2),
         _np(np.transpose(np.asarray(o.weight), (3, 2, 0, 1))),
         _np(o.bias), padding=1).permute(0, 2, 3, 1)),
     lambda: [rnd(2, 6, 6, 3, seed=103)]),
    ("ResizeBilinear_align",
     lambda: nn.ResizeBilinear(7, 9, align_corners=True),
     lambda o: (lambda x: F.interpolate(
         x.permute(0, 3, 1, 2), size=(7, 9), mode="bilinear",
         align_corners=True).permute(0, 2, 3, 1)),
     lambda: [rnd(2, 4, 5, 3, seed=104)]),
]


@pytest.mark.parametrize("case", EXTRA_SWEEP, ids=lambda c: c[0])
def test_extra_layer_sweep(case):
    name, make_ours, make_torch, make_inputs = case
    from bigdl_tpu.utils import set_seed
    set_seed(sum(map(ord, name)) % 7919)
    ours = make_ours().eval_mode()
    tfn = make_torch(ours)
    inputs = make_inputs()
    jx = [jnp.asarray(a) for a in inputs]
    tx = [torch.tensor(a, requires_grad=True) for a in inputs]

    out = ours.forward(jx[0] if len(jx) == 1 else list(jx))
    tout = tfn(*tx)
    np.testing.assert_allclose(np.asarray(out), tout.detach().numpy(),
                               rtol=RTOL, atol=ATOL,
                               err_msg=f"{name}: forward")

    gs = jax.grad(lambda args: jnp.sum(
        ours.forward(args[0] if len(args) == 1 else list(args)) ** 2))(
        tuple(jx))
    (tout ** 2).sum().backward()
    for i, (g, t) in enumerate(zip(gs, tx)):
        if t.grad is None:
            continue  # non-differentiable path (e.g. thresholds)
        np.testing.assert_allclose(np.asarray(g), t.grad.numpy(),
                                   rtol=1e-3, atol=1e-4,
                                   err_msg=f"{name}: grad of input {i}")


def test_gradient_reversal_flips_and_scales():
    """No torch counterpart needed: the contract IS the gradient."""
    layer = nn.GradientReversal(0.7)
    x = jnp.asarray(rnd(3, 4, seed=105))
    np.testing.assert_allclose(np.asarray(layer(x)), np.asarray(x))
    g = jax.grad(lambda a: jnp.sum(layer(a)))(x)
    np.testing.assert_allclose(np.asarray(g), -0.7 * np.ones_like(x),
                               rtol=1e-6, atol=1e-6)


def test_penalty_layers_record_loss():
    """L1Penalty / ActivityRegularization / NegativeEntropyPenalty are
    identity forwards whose penalty value must match the formula."""
    x = jnp.asarray(rnd(3, 4, seed=106))
    l1 = nn.L1Penalty(0.5)
    np.testing.assert_allclose(np.asarray(l1(x)), np.asarray(x))
    np.testing.assert_allclose(float(l1.loss),
                               0.5 * float(jnp.sum(jnp.abs(x))), rtol=1e-6)
    ar = nn.ActivityRegularization(l1=0.3, l2=0.7)
    ar(x)
    np.testing.assert_allclose(
        float(ar.loss),
        0.3 * float(jnp.sum(jnp.abs(x))) + 0.7 * float(jnp.sum(x * x)),
        rtol=1e-6)
    p = jnp.asarray(pos(3, 4, seed=107))
    p = p / jnp.sum(p, -1, keepdims=True)
    ne = nn.NegativeEntropyPenalty(0.2)
    ne(p)
    np.testing.assert_allclose(
        float(ne.loss), 0.2 * float(jnp.sum(p * jnp.log(p))), rtol=1e-5)


# ---------------------------------------------------------------------------
# Coverage manifest: every public nn export is classified.
# ---------------------------------------------------------------------------

# covered by a DEDICATED oracle test in the base file (function-style
# tests there, not table rows)
BASE_DEDICATED = {
    "Linear", "SpatialConvolution", "SpatialFullConvolution",
}

# name -> test file that covers it (claim VERIFIED against file source)
ELSEWHERE = {
    # detection stack
    "Anchor": "test_detection.py",
    "PriorBox": "test_detection.py", "Proposal": "test_detection.py",
    "RegionProposal": "test_detection.py",
    "DetectionOutputSSD": "test_detection.py",
    "BoxHead": "test_detection.py", "MaskHead": "test_detection.py",
    "FPN": "test_detection.py", "Pooler": "test_detection.py",
    "RoiAlign": "test_detection.py", "RoiPooling": "test_detection.py",
    "SmoothL1CriterionWithWeights": "test_detection.py",
    "SoftmaxWithCriterion": "test_detection.py",
    # attention / transformer stack (oracled vs torch SDPA there)
    "Attention": "test_attention.py",
    "FeedForwardNetwork": "test_serializer.py",
    "Transformer": "test_transformer_lm.py",
    "TransformerEncoderLayer": "test_parallel.py",
    "TransformerDecoderLayer": "test_attention.py",
    "SequenceBeamSearch": "test_attention.py",
    # sparse / tree
    "SparseTensor": "test_sparse_tree_misc.py",
    "SparseLinear": "test_sparse_tree_misc.py",
    "SparseJoinTable": "test_sparse_tree_misc.py",
    "LookupTableSparse": "test_sparse_tree_misc.py",
    "DenseToSparse": "test_sparse_tree_misc.py",
    "TreeLSTM": "test_sparse_tree_misc.py",
    "BinaryTreeLSTM": "test_sparse_tree_misc.py",
    # int8 (fidelity harness is the oracle)
    "Quantizer": "test_quantized.py",
    "QuantizedLinear": "test_quantized.py",
    "QuantizedSpatialConvolution": "test_quantized.py",
    "TableOperation": "test_t7_table_metrics.py",
    # parallel / moe
    "MoE": "test_parallel.py",
    "HeldExperts": "test_hybrid_decoder.py",
    "GroupedQueryAttention": "test_hybrid_decoder.py",
    "Mamba2Mixer": "test_state_space.py",
    "LatentAttention": "test_latent_attention.py",
    "Mamba1Mixer": "test_selective_ssm.py",
    "DifferentialAttention": "test_differential_attention.py",
    "GatedShortConv": "test_short_conv.py",
    # containers & recurrent variants exercised with numerics elsewhere
    "Sequential": "test_optim.py",
    "ConvLSTMPeephole3D": "test_sparse_tree_misc.py",
    "LocallyConnected1D": "test_keras.py",
    "LocallyConnected2D": "test_keras.py",
    "SpatialConvolutionMap": "test_sparse_tree_misc.py",
    "SpatialSubtractiveNormalization": "test_sparse_tree_misc.py",
    "SpatialDivisiveNormalization": "test_sparse_tree_misc.py",
    "SpatialContrastiveNormalization": "test_sparse_tree_misc.py",
    "BatchNormalization": "test_optim.py",
    "ParallelCriterion": "test_criterions.py",
}

# name -> why no torch oracle applies (abstract bases, stochastic
# layers, debug aids)
WAIVED = {
    "Module": "abstract base (infrastructure, not a layer)",
    "ModuleList": "container infrastructure",
    "Container": "abstract base",
    "Criterion": "abstract base",
    "Cell": "abstract recurrent base",
    "Node": "graph-DSL infrastructure",
    "RNN": "alias wrapper over Recurrent(RnnCell) — both oracled",
    "SpatialDropout1D": "stochastic; eval-identity + mask shape are the "
                        "contract, locked in test_keras.py",
    "SpatialDropout2D": "stochastic; see SpatialDropout1D",
    "SpatialDropout3D": "stochastic; see SpatialDropout1D",
}


def _nn_exports():
    import glob
    import os
    names = set()
    pat = os.path.join(os.path.dirname(nn.__file__), "*.py")
    for f in glob.glob(pat):
        src = open(f).read()
        m = re.search(r"__all__\s*=\s*\[([^\]]*)\]", src, re.S)
        if m:
            names |= set(re.findall(r'"([A-Za-z0-9_]+)"', m.group(1)))
    return {n for n in names if n[:1].isupper()}


def _table_names(table):
    return {row[0].split("_")[0] for row in table}


def test_zoo_coverage_manifest():
    """Every public nn export must be oracle-swept, covered by a named
    test file (verified), or waived with a reason."""
    import os
    from tests.test_layers_torch_oracle import SWEEP

    here = os.path.dirname(os.path.abspath(__file__))
    this_src = open(os.path.join(here, "test_oracle_sweep_extended.py")
                    ).read()
    base_src = open(os.path.join(here, "test_layers_torch_oracle.py")
                    ).read()

    oracled = (_table_names(SWEEP) | _table_names(CRITERION_SWEEP)
               | _table_names(CELL_SWEEP) | _table_names(EXTRA_SWEEP)
               | BASE_DEDICATED)
    # dedicated function-style tests in either oracle file also count
    for src in (this_src, base_src):
        oracled |= set(re.findall(r"nn\.([A-Z][A-Za-z0-9]*)\(", src))

    exports = _nn_exports()
    unclassified = sorted(
        exports - oracled - set(ELSEWHERE) - set(WAIVED))
    assert not unclassified, (
        f"unclassified nn exports (add an oracle row, an ELSEWHERE "
        f"entry, or a waiver): {unclassified}")

    # ELSEWHERE claims must be true: the named file must reference the
    # name (guards against stale claims as tests move)
    for name, fname in ELSEWHERE.items():
        path = os.path.join(here, fname)
        assert os.path.exists(path), f"{name}: {fname} does not exist"
        src = open(path).read()
        assert re.search(rf"\b{name}\b", src), (
            f"ELSEWHERE claims {name} is covered by {fname}, but that "
            f"file never mentions it")

    # no double-booking between waivers and real coverage
    assert not (set(WAIVED) & oracled)


# ---------------------------------------------------------------------------
# Behavior oracles for names no other test exercised (found by this
# file's manifest audit): table algebra, containers, detection post-ops,
# stochastic/autoregressive layers.
# ---------------------------------------------------------------------------

def test_table_ops_semantics():
    a, b, c = (jnp.asarray(rnd(3, 4, seed=120 + i)) for i in range(3))

    assert all(np.allclose(x, y) for x, y in zip(
        nn.ConcatTable(nn.Identity(), nn.Identity())(a), (a, a)))
    pt = nn.ParallelTable(nn.ReLU(), nn.Tanh())([a, b])
    np.testing.assert_allclose(pt[0], np.maximum(np.asarray(a), 0))
    np.testing.assert_allclose(pt[1], np.tanh(np.asarray(b)), rtol=1e-6)
    mt = nn.MapTable(nn.ReLU())([a, b])
    np.testing.assert_allclose(mt[1], np.maximum(np.asarray(b), 0))
    np.testing.assert_allclose(nn.SelectTable(2)([a, b, c]), b)
    np.testing.assert_allclose(nn.SelectTable(-1)([a, b, c]), c)
    flat = nn.FlattenTable()([a, (b, (c,))])
    assert len(flat) == 3 and np.allclose(flat[2], c)
    nt = nn.NarrowTable(2, 2)([a, b, c])
    assert len(nt) == 2 and np.allclose(nt[0], b)

    parts = nn.SplitTable(2)(a)  # split dim 2 (1-based) -> 4 slices
    assert len(parts) == 4
    np.testing.assert_allclose(parts[1], np.asarray(a)[:, 1])
    lo, hi = nn.BifurcateSplitTable(2)(a)
    np.testing.assert_allclose(lo, np.asarray(a)[:, :2])
    np.testing.assert_allclose(hi, np.asarray(a)[:, 2:])

    g = jax.nn.softmax(jnp.asarray(rnd(3, 2, seed=123)))
    mix = nn.MixtureTable()([g, (a, b)])
    ref = (np.asarray(g)[:, :1] * np.asarray(a)
           + np.asarray(g)[:, 1:] * np.asarray(b))
    np.testing.assert_allclose(mix, ref, rtol=1e-5)

    cp = nn.CrossProduct()([a, b, c])
    ref = np.stack([np.sum(np.asarray(a) * np.asarray(b), -1),
                    np.sum(np.asarray(a) * np.asarray(c), -1),
                    np.sum(np.asarray(b) * np.asarray(c), -1)], -1)
    np.testing.assert_allclose(cp, ref, rtol=1e-5)
    # table algebra must be differentiable end to end
    gr = jax.grad(lambda x: jnp.sum(nn.CrossProduct()([x, b, c]) ** 2))(a)
    assert np.isfinite(np.asarray(gr)).all()


def test_concat_and_bottle_containers():
    from bigdl_tpu.utils import set_seed
    set_seed(9)
    l1, l2 = nn.Linear(4, 3), nn.Linear(4, 5)
    cat = nn.Concat(2, l1, l2)
    x = jnp.asarray(rnd(3, 4, seed=124))
    np.testing.assert_allclose(
        cat(x), np.concatenate([np.asarray(l1(x)), np.asarray(l2(x))], 1),
        rtol=1e-6)

    inner = nn.Linear(5, 2)
    bot = nn.Bottle(inner, 2, 2)
    y = jnp.asarray(rnd(3, 4, 5, seed=125))
    ref = np.asarray(inner(y.reshape(12, 5))).reshape(3, 4, 2)
    np.testing.assert_allclose(bot(y), ref, rtol=1e-6)


def test_nms_behavior():
    boxes = jnp.asarray(np.array([
        [0, 0, 10, 10], [1, 1, 10.5, 10.5],   # heavy overlap pair
        [20, 20, 30, 30],                      # isolated
        [0, 0, 10.2, 9.8],                     # overlaps the first pair
    ], np.float32))
    scores = jnp.asarray(np.array([0.9, 0.8, 0.95, 0.7], np.float32))
    keep, valid = nn.Nms(iou_threshold=0.5, max_output=4)(scores, boxes)
    kept = [int(k) for k, v in zip(keep, valid) if bool(v)]
    # score order: box2 (isolated), box0; boxes 1 and 3 suppressed
    assert kept == [2, 0], kept


def test_normalize_scale_matches_formula():
    layer = nn.NormalizeScale(p=2.0, scale=3.0, size=(5,))
    x = jnp.asarray(rnd(4, 5, seed=126))
    n = np.asarray(x) / (np.linalg.norm(np.asarray(x), axis=-1,
                                        keepdims=True) + 1e-10)
    np.testing.assert_allclose(layer(x), n * 3.0, rtol=1e-5)


def test_spatial_within_channel_lrn_matches_torch_compose():
    layer = nn.SpatialWithinChannelLRN(size=3, alpha=1.0, beta=0.75)
    x = rnd(2, 6, 6, 4, seed=127)
    tx = torch.tensor(np.transpose(x, (0, 3, 1, 2)))
    local_sum = F.avg_pool2d(tx * tx, 3, stride=1, padding=1,
                             count_include_pad=True) * 9.0
    ref = tx * (1.0 + (1.0 / 9.0) * local_sum).pow(-0.75)
    np.testing.assert_allclose(
        np.transpose(np.asarray(layer(jnp.asarray(x))), (0, 3, 1, 2)),
        ref.numpy(), rtol=1e-4, atol=1e-5)


def test_gaussian_sampler_reparameterization():
    from bigdl_tpu.core.module import forward_context
    mean = jnp.asarray(rnd(4, 6, seed=128))
    log_var = jnp.asarray(rnd(4, 6, seed=129) * 0.2)
    layer = nn.GaussianSampler()
    with forward_context(rng=jax.random.key(3)):
        z1 = layer([mean, log_var])
    with forward_context(rng=jax.random.key(3)):
        z2 = layer([mean, log_var])
    np.testing.assert_allclose(z1, z2)  # same rng -> same sample
    eps = (np.asarray(z1) - np.asarray(mean)) / np.exp(
        0.5 * np.asarray(log_var))
    assert np.abs(eps).max() < 6.0  # standardized residual is N(0,1)
    with pytest.raises(Exception):
        # stochastic layers must fail loudly without an rng context
        layer.train_mode()([mean, log_var])


def test_recurrent_decoder_feeds_back_output():
    from bigdl_tpu.utils import set_seed
    set_seed(4)
    cell = nn.RnnCell(5, 5)
    dec = nn.RecurrentDecoder(3, cell).eval_mode()
    x0 = jnp.asarray(rnd(2, 5, seed=130))
    out = dec(x0)
    # manual unroll: input of step t+1 is output of step t
    h = cell.init_state(2)
    inp, outs = x0, []
    for _ in range(3):
        o, h = cell.step(cell.precompute_inputs(inp), h)
        outs.append(np.asarray(o))
        inp = o
    np.testing.assert_allclose(np.asarray(out), np.stack(outs, 1),
                               rtol=1e-5, atol=1e-6)


def test_transformer_and_masked_criterion_wrappers():
    mse = nn.MSECriterion()
    tc = nn.TransformerCriterion(mse, input_transformer=nn.Tanh())
    x = jnp.asarray(rnd(3, 4, seed=131))
    t = jnp.asarray(rnd(3, 4, seed=132))
    np.testing.assert_allclose(
        float(tc(x, t)), float(mse(jnp.tanh(x), t)), rtol=1e-6)

    td = nn.TimeDistributedMaskCriterion(
        nn.ClassNLLCriterion(paddingValue=0))
    logp = jnp.asarray(np.log(pos(2, 3, 4, seed=133)))
    tgt = np.array([[1, 2, 0], [3, 0, 0]], np.int64)  # 0 = pad
    out = float(td(logp, jnp.asarray(tgt)))
    assert np.isfinite(out)
    # padded positions contribute nothing: changing their logits is a
    # no-op on the loss
    logp2 = logp.at[0, 2].set(logp[0, 2] - 5.0)
    np.testing.assert_allclose(out, float(td(logp2, jnp.asarray(tgt))),
                               rtol=1e-6)


def test_detection_output_frcnn_shapes_and_ranking():
    """Synthetic ROI-head outputs through the Faster-R-CNN post-op:
    fixed [max_per_image, 6] rows, finite, scores descending over the
    valid prefix, labels in range."""
    n, C = 8, 4
    rs = np.random.RandomState(134)
    rois = np.concatenate(
        [np.zeros((n, 1), np.float32),
         np.abs(rs.rand(n, 4).astype(np.float32)) * 40], axis=1)
    rois[:, 3:5] = rois[:, 1:3] + 10 + rois[:, 3:5]  # x2>x1, y2>y1
    cls_prob = rs.dirichlet(np.ones(C), n).astype(np.float32)
    bbox_pred = (rs.randn(n, 4 * C) * 0.1).astype(np.float32)
    im_info = jnp.asarray(np.array([60.0, 60.0, 1.0], np.float32))
    layer = nn.DetectionOutputFrcnn(n_classes=C, max_per_image=6)
    out = np.asarray(layer([im_info, jnp.asarray(cls_prob),
                            jnp.asarray(bbox_pred), jnp.asarray(rois)]))
    assert out.shape == (6, 6)
    valid = out[:, 1] > 0
    assert np.isfinite(out[valid]).all()
    sc = out[valid, 1]
    assert (np.diff(sc) <= 1e-6).all()  # sorted by score
    assert ((out[valid, 0] >= 1) & (out[valid, 0] < C)).all()


def test_index_and_masked_select():
    x = jnp.asarray(rnd(3, 5, seed=135))
    idx = jnp.asarray(np.array([2, 1, 4], np.int64))
    out = nn.Index(2)([x, idx])  # 1-based index_select along dim 2
    ref = np.asarray(x)[:, [1, 0, 3]]
    np.testing.assert_allclose(out, ref)

    mask = jnp.asarray((rnd(3, 5, seed=136) > 0))
    vals = nn.MaskedSelect()([x, mask])
    np.testing.assert_allclose(
        np.asarray(vals), np.asarray(x)[np.asarray(mask)])
