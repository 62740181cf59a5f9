"""The Mamba-1 mixer (``nn.ssm.Mamba1Mixer``, ``ops.ssm_kernels``'
``selective_state_step`` and ``selective_chunk_scan``) on the CPU at a
small size, against the plain recurrence of
``benchmark/reference/shared_kv_ssm_lm.py`` (loaded by path: one position
after another, the state ``[channels, states]``, nothing of the program):
the whole sequence = chunks from a carried state = steps, and the
``valid``, ``active`` and ``fresh`` rules a slot pool relies on."""

import os
import sys

import jax
import jax.numpy as jnp
import numpy as np
import pytest

BENCH = os.path.join(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))), "benchmark")
if BENCH not in sys.path:
    sys.path.insert(0, BENCH)

from reference import shared_kv_ssm_lm as ref                 # noqa: E402

from bigdl_tpu.nn.ssm import Mamba1Mixer                      # noqa: E402
from bigdl_tpu.ops import ssm_kernels                         # noqa: E402

HIDDEN, INNER, N, RANK = 24, 48, 8, 3
CFG = dict(hidden_size=HIDDEN, mamba_expand=2, mamba_d_state=N,
           mamba_d_conv=4, mamba_dt_rank=RANK, num_attention_heads=4)
TOL = 2e-5


@pytest.fixture(autouse=True)
def highest_precision():
    with jax.default_matmul_precision("highest"):
        yield


@pytest.fixture(scope="module")
def mixer():
    """Seeded leaves, the recurrence's set to remember: step sizes about
    0.05 and ``A`` 1..8, a memory of a few to some twenty tokens."""
    m = Mamba1Mixer(HIDDEN, INNER, N, RANK).eval_mode()
    flat, tree = jax.tree_util.tree_flatten_with_path(m)
    key, leaves = jax.random.key(5), []
    for i, (path, leaf) in enumerate(flat):
        name = jax.tree_util.keystr(path)
        noise = jax.random.normal(jax.random.fold_in(key, i), leaf.shape)
        if name.endswith("dt_proj.bias"):
            leaf = -3.0 + 0.4 * noise
        elif name.endswith("A_log"):
            pass                                              # as built: 1..N
        elif leaf.ndim == 1:
            leaf = 0.1 * noise if name.endswith("bias") else 1 + 0.1 * noise
        else:
            leaf = noise * leaf.shape[-1] ** -0.5
        leaves.append(leaf)
    return jax.tree_util.tree_unflatten(tree, leaves)


def leaves_of(m):
    flat = jax.tree_util.tree_flatten_with_path(m)[0]
    return {jax.tree_util.keystr(p): leaf for p, leaf in flat}


def inputs(t, batch=2, seed=0):
    return jax.random.normal(jax.random.key(seed), (batch, t, HIDDEN))


def close(a, b, tol=TOL):
    return float(jnp.max(jnp.abs(jnp.asarray(a) - jnp.asarray(b)))) <= tol


def test_whole_sequence_equals_the_reference(mixer):
    u = inputs(37)
    out, state, y = mixer.forward(u)
    want, want_y = ref.mamba(u, leaves_of(mixer), CFG, lambda a: a)
    assert close(out, want) and close(y, want_y)
    assert state["ssm"].shape == (2, N, INNER)       # channels along the lanes
    assert state["ssm"].dtype == jnp.float32
    assert state["conv"].shape == (2, 3, INNER)


def test_the_recurrence_remembers(mixer):
    """An early token changes a late output: the state is not decoration."""
    u = inputs(30)
    out, _, _ = mixer.forward(u)
    out2, _, _ = mixer.forward(u.at[:, 2].add(1.0))
    assert float(jnp.max(jnp.abs(out2[:, 20:] - out[:, 20:]))) > 1e-3


@pytest.mark.parametrize("cuts", [(8, 16, 13), (1, 36), (37,), (5, 5, 27)])
def test_chunks_from_a_carried_state_equal_the_whole_sequence(mixer, cuts):
    u = inputs(37)
    want, want_state, want_y = mixer.forward(u)
    state, at, outs, ys = mixer.init_state(2), 0, [], []
    for w in cuts:
        out, state, y = mixer.forward(u[:, at:at + w], state)
        outs.append(out)
        ys.append(y)
        at += w
    assert close(jnp.concatenate(outs, 1), want)
    assert close(jnp.concatenate(ys, 1), want_y)
    assert close(state["ssm"], want_state["ssm"])
    assert close(state["conv"], want_state["conv"])


def test_steps_equal_the_whole_sequence(mixer):
    u = inputs(19)
    want, want_state, want_y = mixer.forward(u)
    state = mixer.init_state(2)
    for t in range(19):
        out, state, y = mixer.step(u[:, t:t + 1], state)
        assert close(out[:, 0], want[:, t]) and close(y[:, 0], want_y[:, t])
    assert close(state["ssm"], want_state["ssm"])
    assert close(state["conv"], want_state["conv"])


def test_chunks_then_steps_equal_the_whole_sequence(mixer):
    u = inputs(23)
    want, _, _ = mixer.forward(u)
    _, state, _ = mixer.forward(u[:, :8])
    _, state, _ = mixer.forward(u[:, 8:15], state)
    for t in range(15, 23):
        out, state, _ = mixer.step(u[:, t:t + 1], state)
        assert close(out[:, 0], want[:, t])


def test_valid_marks_trailing_padding_that_advances_nothing(mixer):
    """A last chunk padded at its end leaves the state (and the
    convolution's inputs) after its last real position."""
    u = inputs(16)
    real = jnp.asarray([11, 16])
    valid = jnp.arange(16)[None, :] < real[:, None]
    _, state, _ = mixer.forward(u, None, valid)
    _, want0, _ = mixer.forward(u[:1, :11])
    _, want1, _ = mixer.forward(u[1:])
    for name in ("ssm", "conv"):
        assert close(state[name][0], want0[name][0])
        assert close(state[name][1], want1[name][0])


def test_an_idle_row_keeps_its_state_bit_for_bit(mixer):
    u = inputs(9)
    _, state, _ = mixer.forward(u[:, :8])
    active = jnp.asarray([True, False])
    _, new, _ = mixer.step(u[:, 8:9], state, active=active)
    for name in ("ssm", "conv"):
        np.testing.assert_array_equal(np.asarray(new[name][1]),
                                      np.asarray(state[name][1]))
        assert not close(new[name][0], state[name][0], 1e-6)


def test_a_fresh_row_starts_from_zeros(mixer):
    """``fresh``: the row's first token forgets whoever held the row."""
    u = inputs(9)
    _, state, _ = mixer.forward(u[:, :8])
    fresh = jnp.asarray([False, True])
    out, new, _ = mixer.step(u[:, 8:9], state, fresh=fresh)
    alone, alone_state, _ = mixer.forward(u[1:, 8:9])
    assert close(out[1], alone[0])
    assert close(new["ssm"][1], alone_state["ssm"][0])
    assert close(new["conv"][1], alone_state["conv"][0])
    carried, _, _ = mixer.step(u[:, 8:9], state)
    assert close(out[0], carried[0]) and not close(out[1], carried[1], 1e-4)


@pytest.mark.parametrize("t,unroll", [(1, 8), (7, 8), (16, 8), (33, 4)])
def test_chunk_scan_equals_the_plain_recurrence(t, unroll):
    rng = np.random.default_rng(t)
    x = jnp.asarray(rng.normal(size=(2, t, INNER)), jnp.float32)
    dt = jnp.asarray(rng.uniform(0.01, 0.2, (2, t, INNER)), jnp.float32)
    b, c = (jnp.asarray(rng.normal(size=(2, t, N)), jnp.float32)
            for _ in range(2))
    a = -jnp.asarray(rng.uniform(1, 8, (INNER, N)), jnp.float32)
    y, state = ssm_kernels.selective_chunk_scan(
        x, dt, a.T, b, c, jnp.zeros((2, N, INNER)), unroll=unroll)
    for r in range(2):
        assert close(y[r], ref.recurrence(x[r], dt[r], a, b[r], c[r]), 1e-5)
    # from a carried state: the second half alone from the first's state
    h = t // 2
    if h:
        _, s1 = ssm_kernels.selective_chunk_scan(
            x[:, :h], dt[:, :h], a.T, b[:, :h], c[:, :h],
            jnp.zeros((2, N, INNER)))
        y2, s2 = ssm_kernels.selective_chunk_scan(
            x[:, h:], dt[:, h:], a.T, b[:, h:], c[:, h:], s1)
        assert close(y2, y[:, h:], 1e-5) and close(s2, state, 1e-5)


def test_state_step_equals_a_scan_of_one_position():
    rng = np.random.default_rng(3)
    state = jnp.asarray(rng.normal(size=(3, N, INNER)), jnp.float32)
    x = jnp.asarray(rng.normal(size=(3, INNER)), jnp.float32)
    dt = jnp.asarray(rng.uniform(0.01, 0.2, (3, INNER)), jnp.float32)
    dt = dt.at[1].set(0.0)                                    # an idle row
    b, c = (jnp.asarray(rng.normal(size=(3, N)), jnp.float32)
            for _ in range(2))
    a = -jnp.asarray(rng.uniform(1, 8, (N, INNER)), jnp.float32)
    new, y = ssm_kernels.selective_state_step(state, dt, a, dt * x, b, c)
    want_y, want = ssm_kernels.selective_chunk_scan(
        x[:, None], dt[:, None], a, b[:, None], c[:, None], state)
    assert close(new, want, 1e-6) and close(y, want_y[:, 0], 1e-5)
    np.testing.assert_array_equal(np.asarray(new[1]), np.asarray(state[1]))
