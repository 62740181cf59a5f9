"""Decoder-only Transformer LM (models/transformer_lm.py; the
reference's nn/Transformer.scala LanguageModel configuration)."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from bigdl_tpu.core.module import combine, partition
from bigdl_tpu.models import transformer_lm
from bigdl_tpu.models.transformer_lm import TransformerLM
from bigdl_tpu.nn.attention import chunk_incremental_bias
from bigdl_tpu.ops.attention_kernels import (CHUNK_KEY_BLOCK, chunk_attention,
                                             xla_attention)
from bigdl_tpu.utils import set_seed

import bigdl_tpu.nn as nn

import joint_pass


def _model(**kw):
    set_seed(0)
    cfg = dict(vocab_size=50, hidden_size=32, num_layers=2, num_heads=4,
               filter_size=64, max_len=32)
    cfg.update(kw)
    return transformer_lm(**cfg)


def test_forward_shape_and_finite():
    m = _model().eval_mode()
    toks = jnp.asarray(np.random.default_rng(0).integers(1, 51, (2, 12)))
    out = m.forward(toks)
    assert out.shape == (2, 12, 51)
    assert bool(jnp.all(jnp.isfinite(out)))


def test_causality():
    """Changing future tokens must not change past logits."""
    m = _model().eval_mode()
    rng = np.random.default_rng(1)
    a = rng.integers(1, 51, (1, 10))
    b = a.copy()
    b[0, 7:] = rng.integers(1, 51, 3)  # mutate only positions >= 7
    out_a = np.asarray(m.forward(jnp.asarray(a)))
    out_b = np.asarray(m.forward(jnp.asarray(b)))
    np.testing.assert_allclose(out_a[0, :7], out_b[0, :7],
                               rtol=1e-5, atol=1e-5)
    assert not np.allclose(out_a[0, 7:], out_b[0, 7:])


@pytest.mark.slow
def test_remat_matches_plain():
    """jax.checkpoint must change memory, not math: same loss and grads."""
    set_seed(0)
    plain = _model(remat=False)
    set_seed(0)
    remat = _model(remat=True)
    toks = jnp.asarray(np.random.default_rng(2).integers(1, 51, (2, 8)))
    y = jnp.asarray(np.random.default_rng(3).integers(1, 51, (2, 8)))
    crit = nn.CrossEntropyCriterion()

    def loss_of(model):
        params, rest = partition(model)

        def f(p):
            mm = combine(p, rest)
            out = mm.forward(toks).reshape(-1, 51)
            return crit(out, y.reshape(-1))

        return jax.value_and_grad(f)(params)

    l1, g1 = loss_of(plain)
    l2, g2 = loss_of(remat)
    np.testing.assert_allclose(float(l1), float(l2), rtol=1e-6)
    for a, b in zip(jax.tree_util.tree_leaves(g1),
                    jax.tree_util.tree_leaves(g2)):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b),
                                   rtol=1e-5, atol=1e-6)


def test_tied_embedding_head():
    """The output head must literally be the embedding matrix: one shared
    parameter, so vocab logits track embedding updates."""
    m = _model()
    params, _ = partition(m)
    leaves = jax.tree_util.tree_leaves_with_path(params)
    emb_leaves = [kp for kp, v in leaves
                  if "embedding" in jax.tree_util.keystr(kp)]
    assert len(emb_leaves) == 1  # no separate head weight


@pytest.mark.slow
def test_trains_via_optimizer():
    from bigdl_tpu.dataset.dataset import DataSet, MiniBatch
    from bigdl_tpu.optim import Optimizer, Trigger
    from bigdl_tpu.optim.methods import Adam
    from bigdl_tpu.core.module import Module

    set_seed(4)
    rng = np.random.default_rng(4)
    # learnable pattern: next token = current token + 1 (mod vocab)
    seqs = (np.cumsum(np.ones((64, 9), np.int64), axis=1)
            + rng.integers(0, 40, (64, 1))) % 40 + 1

    class LMWrap(Module):
        """LM + flatten to [B*T, V] so ClassNLL-style criteria apply."""

        def __init__(self):
            super().__init__()
            self.lm = _model(vocab_size=41, num_layers=1, hidden_size=16,
                             filter_size=32, num_heads=2)

        def forward(self, x):
            out = self.lm.forward(x)
            return out.reshape(-1, out.shape[-1])

    batches = [MiniBatch(seqs[i:i + 16, :-1].astype(np.int32),
                         seqs[i:i + 16, 1:].reshape(-1).astype(np.int32))
               for i in range(0, 64, 16)]
    opt = (Optimizer(LMWrap(), DataSet.array(batches),
                     nn.CrossEntropyCriterion())
           .set_optim_method(Adam(3e-3))
           .set_end_when(Trigger.max_epoch(10)))
    opt.optimize()
    losses = opt.state["loss"]
    assert np.isfinite(losses)
    assert losses < 3.0  # well below ln(41) ~ 3.71 => it is learning


@pytest.mark.slow
def test_incremental_decode_matches_full_forward():
    """decode_step with the KV cache must reproduce each column of the
    full forward exactly (eval mode)."""
    m = _model().eval_mode()
    rng = np.random.default_rng(5)
    toks = jnp.asarray(rng.integers(1, 51, (2, 9)), jnp.int32)
    full = np.asarray(m.forward(toks))               # [2, 9, 51]
    caches = m.init_cache(2)
    for t in range(9):
        logits, caches = m.decode_step(toks[:, t:t + 1], t, caches)
        np.testing.assert_allclose(np.asarray(logits), full[:, t],
                                   rtol=2e-4, atol=2e-5)


@pytest.mark.parametrize("scenario", joint_pass.ROW_SCENARIOS)
def test_the_joint_pass_equals_the_chunk_program_then_the_step(scenario):
    """``decode_step_with_chunk`` (one walk of the blocks, the decode rows
    and the chunk's tokens one residual stream through each block's norms,
    output projection and feed-forward) against the pooled
    ``prefill_chunk`` followed by ``decode_step`` on the same caches, at
    float32 ``highest``: logits of the live rows, every cache leaf and the
    flags.  ``joint_pass.py`` has the passes and the comparison.  (The
    joint walk writes the rows' places before the chunk attends: the
    fifth pass puts such a place inside the chunk's window.)"""
    m = _model(max_len=24).eval_mode()
    with jax.default_matmul_precision("highest"):
        joint_pass.assert_joint_pass_equals_chunk_then_step(
            m, 4, 50, scenario)


# ---- a chunk attends the live part of its rows -------------------------------
# Rows of four key blocks, sixteen heads of eight (two of the kernel's head
# groups): the helper alone, against the
# masked product over the whole row that it took the place of.

_ROW = 4 * CHUNK_KEY_BLOCK
_PLACES = {"first": lambda w: 0,
           "mid-block": lambda w: CHUNK_KEY_BLOCK + 100,
           # the chunk's first token on a block's last place, and on the
           # next block's first
           "block-end": lambda w: 2 * CHUNK_KEY_BLOCK - 1,
           "block-start": lambda w: 2 * CHUNK_KEY_BLOCK,
           "row-end": lambda w: _ROW - w}
# how the rows lie: a pool's slot, with and without padding flags, and a
# request's own rows (``slot=None``: rows 0 and 1, the second one flagged)
_ROWS = {"slot-2": (1, 2, (True,)), "slot-2-unflagged": (1, 2, (False,)),
         "two-rows": (2, 0, (False, True))}


@pytest.mark.parametrize("how", ["xla", "ragged"])
@pytest.mark.parametrize("rows", _ROWS)
@pytest.mark.parametrize("width", [64, 32, 16, 8])
@pytest.mark.parametrize("place", _PLACES)
def test_a_chunk_attends_the_live_blocks_of_its_rows_only(place, width,
                                                          rows, how):
    """``ops.chunk_attention`` against ``xla_attention`` under
    ``chunk_incremental_bias`` over the whole row, at the chunk positions
    where a count of blocks could go wrong, with padding flagged before
    the chunk (the row's first places, a place mid-row) and inside it;
    the loop every backend has and the kernel a TPU takes (interpreted).
    **Every place past the chunk's last block holds NaN** in the leaves
    the helper is handed, and zero in the reference's: a block read in
    vain would show in the result (``0 * NaN``), and none does."""
    b, row, flagged = _ROWS[rows]
    index = _PLACES[place](width)
    rng = np.random.default_rng(width + index)
    q = jnp.asarray(rng.normal(size=(b, 16, width, 8)), jnp.float32)
    k, v = (jnp.asarray(rng.normal(size=(4, 16, _ROW, 8)), jnp.float32)
            for _ in range(2))
    pad = np.zeros((4, _ROW), bool)
    for r, flag in zip(range(row, row + b), flagged):
        if flag:
            pad[r, :3] = pad[r, index // 2] = True
            pad[r, index + 1] = pad[r, index + width - 2] = True
    pad[:, 0] &= index > 0      # the first query keeps a place to attend
    pad = jnp.asarray(pad)
    read = (index + width - 1) // CHUNK_KEY_BLOCK * CHUNK_KEY_BLOCK \
        + CHUNK_KEY_BLOCK
    unread = jnp.arange(_ROW)[None, None, :, None] >= read
    got = chunk_attention(q, jnp.where(unread, jnp.nan, k),
                          jnp.where(unread, jnp.nan, v), row, index, pad,
                          force=how)
    bias = chunk_incremental_bias(_ROW, index, width, pad[row:row + b])
    want = xla_attention(q, jnp.where(unread, 0, k)[row:row + b],
                         jnp.where(unread, 0, v)[row:row + b], bias)
    assert got.shape == want.shape and got.dtype == q.dtype
    np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                               rtol=2e-5, atol=2e-6)


def test_chunk_key_block_divides_a_short_row():
    """A row shorter than the block, or not a multiple of it, is read in
    blocks of their common divisor (the tests' models have rows of 24 and
    32 places); the pool counts a chunk's reading by the same number."""
    for max_len, block in ((24, 8), (32, 32), (2048, CHUNK_KEY_BLOCK),
                           (384, 128)):
        m = _model(max_len=max_len, num_layers=1)
        assert m.chunk_key_block(m.init_cache(1)) == block


@pytest.mark.slow
def test_greedy_generate_consistent_with_full_forward():
    """Each generated token must be the argmax of the full forward over
    the sequence so far."""
    m = _model().eval_mode()
    rng = np.random.default_rng(6)
    prompt = jnp.asarray(rng.integers(1, 51, (1, 4)), jnp.int32)
    out = np.asarray(m.generate(prompt, max_new_tokens=5))
    assert out.shape == (1, 9)
    seq = np.asarray(prompt)
    for t in range(5):
        logits = np.asarray(m.forward(jnp.asarray(seq)))[:, -1]
        # 1-based criterion convention: logit index i = token i+1's
        # slot; the untrained last row is excluded from the argmax
        nxt = int(np.argmax(logits[:, :-1], axis=-1)[0]) + 1
        assert out[0, 4 + t] == nxt, (t, out, nxt)
        seq = np.concatenate([seq, [[nxt]]], axis=1)


def test_generate_stops_at_eos():
    m = _model().eval_mode()
    rng = np.random.default_rng(7)
    prompt = jnp.asarray(rng.integers(1, 51, (2, 3)), jnp.int32)
    # pick the first greedily-generated token of row 0 as the "EOS"
    free = np.asarray(m.generate(prompt, max_new_tokens=4))
    eos = int(free[0, 3])
    out = np.asarray(m.generate(prompt, max_new_tokens=4, eos_id=eos))
    assert out[0, 3] == eos
    assert (out[0, 4:] == 0).all()   # padded after EOS


@pytest.mark.slow
def test_beam_size_one_matches_greedy():
    m = _model().eval_mode()
    rng = np.random.default_rng(8)
    prompt = jnp.asarray(rng.integers(1, 51, (2, 4)), jnp.int32)
    greedy = np.asarray(m.generate(prompt, max_new_tokens=5))[:, 4:]
    seqs, scores = m.generate_beam(prompt, beam_size=1, max_new_tokens=5)
    np.testing.assert_array_equal(np.asarray(seqs)[:, 0, :], greedy)
    assert np.all(np.isfinite(np.asarray(scores)))

@pytest.mark.slow
def test_incremental_decode_matches_full_forward_with_padding():
    """A prompt containing 0-padding must produce the same logits
    incrementally as forward(), whose padding_bias masks pad slots
    (regression: decode_step only masked future slots)."""
    m = _model().eval_mode()
    rng = np.random.default_rng(9)
    toks = np.asarray(rng.integers(1, 51, (2, 8)), np.int32)
    toks[0, 3] = 0
    toks[1, 5:] = 0
    full = np.asarray(m.forward(jnp.asarray(toks)))
    caches = m.init_cache(2)
    for t in range(8):
        logits, caches = m.decode_step(
            jnp.asarray(toks[:, t:t + 1]), t, caches)
        np.testing.assert_allclose(np.asarray(logits), full[:, t],
                                   rtol=2e-4, atol=2e-5)


def test_generate_never_emits_untrained_or_pad_token():
    """The tied head's LAST logit row (index vocab_size) is never a
    criterion target (1-based convention: target t trains index t-1),
    so it must be masked out of argmax/top_k — otherwise generation
    could emit the out-of-vocab token vocab_size+1.  Token 0 (padding)
    must never be emitted either."""
    m = _model().eval_mode()
    # bias the model so the untrained last row would dominate if unmasked
    from bigdl_tpu.core.module import Parameter
    w = np.array(m.embedding.weight)  # writable copy
    w[-1] = 10.0  # giant norm: with LN'd hidden, the last logit wins
    m.embedding.weight = Parameter(jnp.asarray(w))
    rng = np.random.default_rng(10)
    prompt = jnp.asarray(rng.integers(1, 51, (2, 3)), jnp.int32)
    out = np.asarray(m.generate(prompt, max_new_tokens=6))
    assert (out[:, 3:] != 0).all(), out
    assert (out[:, 3:] <= 50).all(), out   # never the out-of-vocab id
    seqs, _ = m.generate_beam(prompt, beam_size=2, max_new_tokens=4)
    assert (np.asarray(seqs) != 0).all(), seqs
    assert (np.asarray(seqs) <= 50).all(), seqs

@pytest.mark.slow
def test_train_then_generate_token_convention():
    """ADVICE r03 (high): a model trained with the framework's own
    1-based criteria must generate the continuation in TOKEN space —
    train next=cur+1, prompt [5,6,7,8] must continue 9,10,11 (the bug
    emitted raw logit indices, i.e. 8,8,8 shifted down by one)."""
    from bigdl_tpu.core.module import partition, combine
    from bigdl_tpu.optim.methods import Adam

    set_seed(1)
    vocab = 20
    m = TransformerLM(vocab, hidden_size=32, num_layers=1, num_heads=2,
                      filter_size=64, max_len=16)
    rng = np.random.default_rng(2)
    starts = rng.integers(1, vocab - 8, size=(64,))
    seqs = starts[:, None] + np.arange(9)[None, :]   # ascending runs
    x = jnp.asarray(seqs[:, :-1], jnp.int32)
    y = jnp.asarray(seqs[:, 1:], jnp.int32)
    crit = nn.CrossEntropyCriterion()
    params, rest = partition(m)
    method = Adam(5e-3)
    state = method.init_state(params)

    @jax.jit
    def step(p, s):
        def loss_fn(p):
            logits = combine(p, rest).forward(x)
            return crit(logits.reshape(-1, vocab + 1), y.reshape(-1))
        loss, g = jax.value_and_grad(loss_fn)(p)
        p, s = method.update(g, p, s)
        return p, s, loss

    for _ in range(120):
        params, state, loss = step(params, state)
    trained = combine(params, rest).eval_mode()
    out = np.asarray(trained.generate(
        jnp.asarray([[5, 6, 7, 8]], jnp.int32), max_new_tokens=3))
    np.testing.assert_array_equal(out[0], [5, 6, 7, 8, 9, 10, 11])
    seqs_b, _ = trained.generate_beam(
        jnp.asarray([[5, 6, 7, 8]], jnp.int32), beam_size=2,
        max_new_tokens=3)
    np.testing.assert_array_equal(np.asarray(seqs_b)[0, 0], [9, 10, 11])


def test_sequence_parallel_rejects_padded_batch():
    """ADVICE r03 (medium): the ring path has no padding mask — padded
    batches must fail loudly, not silently diverge from dense."""
    from jax.sharding import Mesh

    m = _model(max_len=64).eval_mode()
    mesh = Mesh(np.asarray(jax.devices()[:8]), ("seq",))
    m.set_sequence_parallel(mesh, "seq")
    toks = np.ones((2, 16), np.int32)
    toks[1, 10:] = 0
    with pytest.raises(ValueError, match="padded"):
        m.forward(jnp.asarray(toks))
    # under jit the tokens are traced and can't raise: the output must
    # be NaN-poisoned (loudly wrong), while a clean batch stays finite
    jf = jax.jit(m.forward)
    assert not np.isfinite(np.asarray(jf(jnp.asarray(toks)))).all()
    clean = np.ones((2, 16), np.int32)
    assert np.isfinite(np.asarray(jf(jnp.asarray(clean)))).all()


@pytest.mark.slow
def test_sequence_parallel_matches_dense():
    """set_sequence_parallel (ring attention over the seq axis) must
    reproduce the dense forward and its gradients on an 8-way mesh,
    with the projection weights shared (not copied)."""
    from jax.sharding import Mesh
    from bigdl_tpu.parallel.ring_attention import RingSelfAttention

    m = _model(max_len=64).eval_mode()
    rng = np.random.default_rng(11)
    toks = jnp.asarray(rng.integers(1, 51, (2, 16)), jnp.int32)
    dense = np.asarray(m.forward(toks))

    mesh = Mesh(np.asarray(jax.devices()[:8]), ("seq",))
    orig_q = m.blocks[0].self_attn.q_layer
    m.set_sequence_parallel(mesh, "seq")
    assert isinstance(m.blocks[0].self_attn, RingSelfAttention)
    # weights shared (same module object), not cloned
    assert m.blocks[0].self_attn.q_layer is orig_q
    # reconfiguring with another mesh must take effect, not be skipped
    mesh2 = Mesh(np.asarray(jax.devices()[:4]), ("seq",))
    m.set_sequence_parallel(mesh2, "seq")
    assert m.blocks[0].self_attn.mesh is mesh2
    m.set_sequence_parallel(mesh, "seq")
    ring_out = np.asarray(m.forward(toks))
    np.testing.assert_allclose(ring_out, dense, rtol=2e-4, atol=2e-5)

    # gradients agree too
    y = jnp.asarray(rng.integers(1, 51, (2, 16)), jnp.int32)
    crit = nn.CrossEntropyCriterion()

    def loss_of(model):
        params, rest = partition(model)

        def f(p):
            out = combine(p, rest).forward(toks).reshape(-1, 51)
            return crit(out, y.reshape(-1))

        return jax.grad(f)(params)

    set_seed(0)
    dense_m = _model(max_len=64).eval_mode()
    g1 = loss_of(dense_m)
    g2 = loss_of(m)
    # module re-assignment moves self_attn to the end of the module
    # dict, so leaf ORDER differs — compare by key path
    def by_path(g):
        return {jax.tree_util.keystr(kp): np.asarray(v) for kp, v in
                jax.tree_util.tree_leaves_with_path(g)}
    d1, d2 = by_path(g1), by_path(g2)
    assert set(d1) == set(d2)
    for k in d1:
        np.testing.assert_allclose(d1[k], d2[k], rtol=5e-4, atol=1e-5,
                                   err_msg=k)


def test_sequence_parallel_generation_falls_back_to_dense():
    """Incremental decoding (cache path) must keep working after the
    ring swap — the cache path falls back to dense attention."""
    from jax.sharding import Mesh
    m = _model(max_len=64).eval_mode()
    rng = np.random.default_rng(12)
    prompt = jnp.asarray(rng.integers(1, 51, (1, 4)), jnp.int32)
    want = np.asarray(m.generate(prompt, max_new_tokens=4))
    m.set_sequence_parallel(Mesh(np.asarray(jax.devices()[:8]), ("seq",)))
    got = np.asarray(m.generate(prompt, max_new_tokens=4))
    np.testing.assert_array_equal(got, want)


def test_ring_attention_dropout_training_raises():
    from jax.sharding import Mesh
    m = _model(max_len=64, dropout=0.1)
    m.set_sequence_parallel(Mesh(np.asarray(jax.devices()[:8]), ("seq",)))
    m.train_mode()
    toks = jnp.asarray(np.random.default_rng(13).integers(1, 51, (2, 8)))
    from bigdl_tpu.core.module import forward_context
    with pytest.raises(ValueError, match="ring"):
        with forward_context(rng=jax.random.key(0)):
            m.forward(toks)


@pytest.mark.slow
def test_eval_mode_survives_sequence_parallel_swap():
    """set_sequence_parallel after eval_mode() must not resurrect
    training=True on the swapped attention modules (regression: the
    rng-neutral constructor reset the flag, making generation with
    dropout>0 raise)."""
    from jax.sharding import Mesh
    m = _model(max_len=64, dropout=0.1).eval_mode()
    m.set_sequence_parallel(Mesh(np.asarray(jax.devices()[:8]), ("seq",)))
    assert not m.blocks[0].self_attn.training
    rng = np.random.default_rng(14)
    toks = jnp.asarray(rng.integers(1, 51, (2, 16)), jnp.int32)
    out = m.forward(toks)  # must not raise
    assert bool(jnp.all(jnp.isfinite(out)))
    out2 = m.generate(jnp.asarray(rng.integers(1, 51, (1, 4))), 3)
    assert out2.shape == (1, 7)


def test_ring_rejects_indivisible_sequence():
    from jax.sharding import Mesh
    m = _model(max_len=64).eval_mode()
    m.set_sequence_parallel(Mesh(np.asarray(jax.devices()[:8]), ("seq",)))
    toks = jnp.asarray(np.random.default_rng(15).integers(1, 51, (1, 12)))
    with pytest.raises(ValueError, match="divisible"):
        m.forward(toks)
