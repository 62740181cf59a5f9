"""The gated short convolution (``nn.short_conv.GatedShortConv``) on the CPU
at a small size, against the three shifted products of
``benchmark/reference/conv_moe_lm.py`` (loaded by path: nothing of the
program): the whole sequence = chunks from a carried tail = steps, and the
``valid``, ``active`` and ``fresh`` rules a slot pool relies on.  Its
state is the tail and nothing else."""

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

from reference import conv_moe_lm as ref                      # noqa: E402

from bigdl_tpu.nn.short_conv import GatedShortConv            # noqa: E402

HIDDEN, TAPS = 24, 3
TOL = 2e-5


@pytest.fixture(autouse=True)
def highest_precision():
    with jax.default_matmul_precision("highest"):
        yield


@pytest.fixture(scope="module")
def mixer():
    m = GatedShortConv(HIDDEN, TAPS).eval_mode()
    flat, tree = jax.tree_util.tree_flatten_with_path(m)
    key = jax.random.key(7)
    return jax.tree_util.tree_unflatten(tree, [
        jax.random.normal(jax.random.fold_in(key, i), leaf.shape)
        * (TAPS if jax.tree_util.keystr(p) == ".taps"
           else leaf.shape[-1]) ** -0.5
        for i, (p, leaf) in enumerate(flat)])


def leaves_of(m):
    flat = jax.tree_util.tree_flatten_with_path(m)[0]
    return {jax.tree_util.keystr(p): leaf for p, leaf in flat}


def inputs(t, batch=2, seed=0):
    return jax.random.normal(jax.random.key(seed), (batch, t, HIDDEN))


def close(a, b, tol=TOL):
    return float(jnp.max(jnp.abs(jnp.asarray(a) - jnp.asarray(b)))) <= tol


def gates(mixer, u):
    """``g = B * u`` of every position: what a tail holds."""
    p = jnp.einsum("bti,oi->bto", u, mixer.in_proj.weight)
    return p[..., :HIDDEN] * p[..., 2 * HIDDEN:]


def test_whole_sequence_equals_the_three_shifted_products(mixer):
    u = inputs(37)
    out, state = mixer.forward(u)
    assert close(out, ref.short_conv(u, leaves_of(mixer), lambda a: a))
    assert float(jnp.std(out)) > 0.1
    # the state is the tail and nothing else: the last two inputs
    assert list(state) == ["conv"]
    # ... side by side along the lanes, the oldest first
    assert state["conv"].shape == (2, (TAPS - 1) * HIDDEN)
    assert close(state["conv"], gates(mixer, u)[:, -2:].reshape(2, -1))


def test_the_taps_lie_with_the_channels_along_the_lanes(mixer):
    assert mixer.taps.shape == (TAPS, HIDDEN)
    # tap j multiplies the input TAPS - 1 - j positions back
    u = inputs(5, batch=1)
    g = gates(mixer, u)[0]
    p = jnp.einsum("ti,oi->to", u[0], mixer.in_proj.weight)
    c = p[:, HIDDEN:2 * HIDDEN]
    t = 4
    conv = sum(mixer.taps[j] * g[t - (TAPS - 1) + j] for j in range(TAPS))
    want = (c[t] * conv) @ mixer.out_proj.weight.T
    assert close(mixer.forward(u)[0][0, t], want)


@pytest.mark.parametrize("cuts", [(8, 16, 24), (1, 2, 3, 36), (36,), (5,)])
def test_chunks_from_a_carried_tail_equal_the_whole_sequence(mixer, cuts):
    u = inputs(37)
    whole, final = mixer.forward(u)
    state, outs, start = mixer.init_state(2), [], 0
    for stop in cuts + (37,):
        out, state = mixer.forward(u[:, start:stop], state)
        outs.append(out)
        start = stop
    assert close(jnp.concatenate(outs, axis=1), whole)
    assert close(state["conv"], final["conv"])


def test_steps_equal_the_whole_sequence(mixer):
    u = inputs(12)
    whole, final = mixer.forward(u)
    state, outs = mixer.init_state(2), []
    for t in range(12):
        out, state = mixer.step(u[:, t:t + 1], state)
        outs.append(out)
    assert close(jnp.concatenate(outs, axis=1), whole)
    assert close(state["conv"], final["conv"])


def test_a_chunk_then_steps_equal_the_whole_sequence(mixer):
    u = inputs(20)
    whole, _ = mixer.forward(u)
    out, state = mixer.forward(u[:, :13])
    outs = [out]
    for t in range(13, 20):
        out, state = mixer.step(u[:, t:t + 1], state)
        outs.append(out)
    assert close(jnp.concatenate(outs, axis=1), whole)


@pytest.mark.parametrize("real", [(5, 8), (1, 3), (8, 0)])
def test_a_padded_last_chunk_leaves_the_tail_of_its_last_valid_token(
        mixer, real):
    """Rows padded at their end to the chunk's width: the tail is that of
    each row's last real token, whatever the padding held."""
    u = inputs(8, seed=3)
    valid = jnp.arange(8)[None, :] < jnp.asarray(real)[:, None]
    carried = {"conv": inputs(2, seed=9).reshape(2, -1) * 0.3}
    out, state = mixer.forward(u, carried, valid)
    for b, n in enumerate(real):
        if n == 0:
            # a row with no real token keeps the tail it came with
            assert close(state["conv"][b], carried["conv"][b])
            continue
        want_out, want = mixer.forward(
            u[b:b + 1, :n], {"conv": carried["conv"][b:b + 1]})
        assert close(state["conv"][b], want["conv"][0])
        assert close(out[b, :n], want_out[0])


def test_an_idle_row_keeps_its_tail_and_a_fresh_row_starts_from_zeros(mixer):
    u = inputs(1, batch=3, seed=4)
    state = {"conv": inputs(2, batch=3, seed=5).reshape(3, -1) * 0.5}
    active = jnp.asarray([True, False, True])
    fresh = jnp.asarray([False, False, True])
    out, new = mixer.step(u, state, active, fresh)
    # row 0: an ordinary step
    want_out, want = mixer.step(u[:1], {"conv": state["conv"][:1]})
    assert close(out[0], want_out[0]) and close(new["conv"][0],
                                                want["conv"][0])
    # row 1 rides along: its tail is as it was, bit for bit
    np.testing.assert_array_equal(np.asarray(new["conv"][1]),
                                  np.asarray(state["conv"][1]))
    # row 2 starts its sequence: what the slot held before is forgotten
    want_out, want = mixer.step(u[2:], mixer.init_state(1))
    assert close(out[2], want_out[0]) and close(new["conv"][2],
                                                want["conv"][0])
    assert float(jnp.max(jnp.abs(new["conv"][2, :HIDDEN]))) == 0.0


def test_the_tail_is_kept_in_the_dtype_it_was_made_in(mixer):
    state = mixer.init_state(2, jnp.bfloat16)
    assert state["conv"].dtype == jnp.bfloat16
    _, state = mixer.forward(inputs(6), state)
    assert state["conv"].dtype == jnp.bfloat16
    _, state = mixer.step(inputs(1), state, jnp.asarray([True, False]))
    assert state["conv"].dtype == jnp.bfloat16


def test_a_convolution_has_at_least_two_taps():
    with pytest.raises(ValueError, match="two taps"):
        GatedShortConv(HIDDEN, 1)
