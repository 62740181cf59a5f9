"""What the tests of the three ``HybridDecoder`` block kinds and of
``TransformerLM`` share about the joint pass (``decode_step_with_chunk``:
a pool's decode step that carries a prefill chunk, each layer's
feed-forward run once over both): the same pass made of the two entries
it stands for, on the same caches, and the comparison.  A helper, not a
test file: the models and their sizes are the callers'.  Also how a prompt goes into a pool's slot as the
scheduler sends it, and teacher-forced pooled decode steps against a
reference's logits, for the models whose pools keep a state."""

import inspect

import jax
import jax.numpy as jnp
import numpy as np

SCENARIOS = ["idle-row", "padded-last-chunk", "fresh-occupant",
             "own-slot-decodes"]
# one more for a model whose rows keep no state (a state is not written
# at a place): the slot decodes from a place inside its padded chunk
ROW_SCENARIOS = SCENARIOS + ["own-slot-decodes-in-the-padding"]
SLOTS = 3


def _init_cache(model, slots, chunk):
    """A pool's caches; rings get room for a chunk where the model has
    rings to size (``TransformerLM`` keeps full rows only)."""
    if "ring_margin" in inspect.signature(model.init_cache).parameters:
        return model.init_cache(slots, ring_margin=chunk)
    return model.init_cache(slots)


def _prefill_chunk(model, toks, index, caches, slot):
    """``(caches, routing)``: no routing (None) from a model whose chunk
    entry returns the caches alone."""
    out = model.prefill_chunk(toks, index, caches, slot=slot)
    return out if isinstance(out, tuple) else (out, None)


def _fill(model, caches, rng, vocab, slot, n, chunk):
    """``n`` positions of ``slot`` through whole chunks."""
    for s in range(0, n, chunk):
        toks = jnp.asarray(rng.integers(1, vocab + 1, (1, chunk)), jnp.int32)
        caches, _ = _prefill_chunk(model, toks, s, caches, slot)
    return caches


def joint_pass_case(model, chunk, vocab, scenario):
    """``(caches, tokens, index, active, toks, chunk_index, slot)`` of one
    pass of a pool of three slots in which slot 1 takes a chunk while
    slots 0 and 2 decode, late and early in their rows:

    * ``idle-row``: slot 1 is between two of its chunks and rides the
      step idle;
    * ``padded-last-chunk``: the chunk's tail is padding;
    * ``fresh-occupant``: the chunk is a new request's first, at position
      0 of a slot whose rows and state another request left behind;
    * ``own-slot-decodes``: the chunk is its prompt's last and slot 1
      decodes in the same pass, from the position after it;
    * ``own-slot-decodes-in-the-padding``: the same with the chunk's
      tail padding, so that slot 1 decodes from the first padded place:
      the row's write lands inside the chunk's window."""
    assert scenario in ROW_SCENARIOS, scenario
    rng = np.random.default_rng(ROW_SCENARIOS.index(scenario))
    caches = _init_cache(model, SLOTS, chunk)
    caches = _fill(model, caches, rng, vocab, 0, 3 * chunk, chunk)
    caches = _fill(model, caches, rng, vocab, 2, chunk, chunk)
    caches = _fill(model, caches, rng, vocab, 1,
                   2 * chunk if scenario == "fresh-occupant" else chunk,
                   chunk)
    toks = rng.integers(1, vocab + 1, (1, chunk)).astype(np.int32)
    chunk_index = 0 if scenario == "fresh-occupant" else chunk
    tokens = rng.integers(1, vocab + 1, (SLOTS, 1)).astype(np.int32)
    index = np.asarray([3 * chunk, 0, chunk], np.int32)
    active = np.asarray([True, False, True])
    real = chunk
    if scenario in ("padded-last-chunk", "own-slot-decodes-in-the-padding"):
        real = chunk - chunk // 2
        toks[0, real:] = 0
    if scenario.startswith("own-slot-decodes"):
        active[1], index[1] = True, chunk_index + real
    else:
        tokens[1] = 0
    return (caches, jnp.asarray(tokens), jnp.asarray(index),
            jnp.asarray(active), jnp.asarray(toks), chunk_index, 1)


def assert_joint_pass_equals_chunk_then_step(model, chunk, vocab, scenario,
                                             tol=1e-5):
    """The joint entry against ``prefill_chunk`` followed by
    ``decode_step``: logits and every cache leaf to ``tol`` (float32 at
    ``highest``: only the feed-forward's row count differs), the flags
    equal, the experts' pairs (routed, and on a held expert) exactly; an
    expert layer counts one call where the two programs count two (a
    model that counts no routing returns none from any entry)."""
    caches, tokens, index, active, toks, at, slot = joint_pass_case(
        model, chunk, vocab, scenario)
    after_chunk, did_chunk = _prefill_chunk(model, toks, at, caches, slot)
    want, want_caches, *did_step = model.decode_step(
        tokens, index, after_chunk, active=active)
    got, got_caches, *did = model.decode_step_with_chunk(
        tokens, index, caches, active, toks, at, slot)
    live = np.asarray(active)
    assert float(jnp.max(jnp.abs(got - want)[live])) <= tol
    flat_want, tree = jax.tree_util.tree_flatten(want_caches)
    flat_got, tree_got = jax.tree_util.tree_flatten(got_caches)
    assert tree == tree_got
    for a, b in zip(flat_got, flat_want):
        assert a.shape == b.shape and a.dtype == b.dtype
        if a.dtype == jnp.bool_:
            assert bool(jnp.all(a == b))
        else:
            assert float(jnp.max(jnp.abs(a - b))) <= tol
    assert len(did) == len(did_step) == (did_chunk is not None)
    if not did:
        return
    both = np.asarray(did_chunk) + np.asarray(did_step[0])
    layers = model.expert_layers()
    assert list(np.asarray(did[0])[:3]) == [layers, both[1], both[2]]
    assert both[0] == 2 * layers
    if layers:
        assert both[1] > 0


def serve_beside_a_decoding_slot(engine, first, later, new_first=30,
                                 new_later=6, at_token=5, timeout=300):
    """Scripted arrivals: ``first`` alone, and the ``later`` prompts when
    its ``at_token``-th token is emitted (enqueued from the engine's
    thread), so that their prefill runs in passes in which a slot
    decodes.  Returns the finished rows, ``first``'s first."""
    seen, futs = [], []

    def on_token(_tok):
        seen.append(_tok)
        if len(seen) == at_token:
            futs.extend(engine.submit_async(p, new_later) for p in later)

    a = engine.submit_async(first, new_first, on_token=on_token)
    rows = [a.result(timeout=timeout)]
    return rows + [f.result(timeout=timeout) for f in futs]


def logged_pool_calls(pool):
    """Wraps the pool's two dispatch entries; returns the log they fill:
    ``"alone"`` for a chunk program sent by itself, ``"step"`` for a
    decode dispatch and ``"step+chunk"`` for one that carried a chunk."""
    log = []
    alone, step = pool.chunk_prefill_into, pool.decode_dispatch

    def chunk_prefill_into(*a, **k):
        log.append("alone")
        return alone(*a, **k)

    def decode_dispatch(chunk=None):
        log.append("step" if chunk is None else "step+chunk")
        return step(chunk)

    pool.chunk_prefill_into = chunk_prefill_into
    pool.decode_dispatch = decode_dispatch
    return log


def pool_prefill(pool, prompt, slot, chunk, chunks_only=False):
    """A prompt into ``slot`` as the scheduler sends it to a pool that
    keeps a state: no longer than ``chunk`` through ``prefill_kv`` and the
    scatter, longer through the pooled chunk program, the last chunk
    padded at its end (``chunks_only``: the chunk program whatever the
    length, as a first-and-only chunk that is short)."""
    n_prompt, end = len(prompt), len(prompt) - 1
    if n_prompt == 1:
        return
    if n_prompt <= chunk and not chunks_only:
        pool.prefill_into([prompt], [slot], 1 << (n_prompt - 1).bit_length())
        return
    pos = 0
    while pos < end:
        w = chunk if end - pos >= chunk else 1 << (end - pos - 1).bit_length()
        toks = np.zeros(w, np.int32)
        toks[:min(w, end - pos)] = prompt[pos:min(pos + w, end)]
        pool.chunk_prefill_into(toks, slot, pos)
        pos += w


def decode_check(pool, slot, row, n_prompt, want, close, steps=None):
    """Pooled decode steps of ``slot`` alone, teacher-forced, each step's
    logits against the reference's column (``close(got, want)``);
    returns the caches they left."""
    active = jnp.arange(pool.slots) == slot
    caches = pool.caches
    stop = len(row) if steps is None else min(len(row), n_prompt - 1 + steps)
    for t in range(n_prompt - 1, stop):
        tok = jnp.where(active, int(row[t]), 0)[:, None].astype(jnp.int32)
        index = jnp.where(active, t, 0).astype(jnp.int32)
        logits, caches, _ = pool.model.decode_step(tok, index, caches,
                                                   active=active)
        assert close(logits[slot], want[t]), t
    return caches
