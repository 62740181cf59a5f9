"""Continuous batching for generation: iteration-level scheduling over a
fixed-shape KV slot pool, with prefix KV-cache reuse and chunked
prefill.

One-shot serving (scheduler.py) coalesces *single-forward* requests; a
generation request is different in kind — it is a multi-step loop whose
length varies per request.  Padding a batch of ``generate()`` calls to
the slowest request serializes mixed-length traffic (Orca, OSDI '22
names the problem).  This module schedules at ITERATION granularity
instead:

* a **slot pool** of S fixed KV-cache rows (the fixed-shape cousin of
  vLLM's PagedAttention — one contiguous ``max_len`` row per slot, no
  paging, because XLA wants static shapes);
* one jitted, shape-stable **pooled decode step** advances every active
  slot by one token per iteration, each slot at its OWN position, with
  the pooled caches DONATED so the step updates the pool in place
  instead of copying ``S x layers x max_len`` of K/V every token;
* **prefill** is batched by power-of-two prompt-length buckets (reusing
  ``batching.bucket_sizes``) at a fixed prefill batch width, then the
  compact per-layer K/V rows are scattered into free slots — so a
  request joins the pool as soon as a slot frees, mid-flight, and
  leaves individually at EOS / max-tokens without disturbing the
  co-resident slots.

Prefill is the remaining wall of a round of mixed traffic: a request
waits for its whole prompt before its first token, and co-resident
streams wait with it.  Two cooperating optimizations attack it:

* a **prefix KV cache** (prefix_cache.py): prefill K/V is cached at a
  fixed chunk granularity keyed by the full token prefix; on admit the
  longest cached chain is device-copied into the slot row and only the
  suffix is prefilled — repeated system prompts amortize their prefill
  to near zero (the static-shape cousin of RadixAttention prefix
  reuse);
* **chunked prefill interleaved with decode**: long prompts are
  prefilled through a KV-carry-in program
  (``TransformerLM.prefill_chunk``) in fixed-width chunks — one
  compile per chunk width, drawn from ``bucket_sizes(prefill_chunk)``
  so the O(1) compile budget holds — and the engine schedules at most
  ``prefill_chunk_budget`` prefill program calls between pooled decode
  steps, so a long prompt no longer freezes the inter-token cadence of
  every co-resident stream (Sarathi-style chunked prefill, static
  shapes).  The final partial chunk is SUFFIX-ALIGNED: it recomputes a
  little overlap instead of padding, so it stays in bounds and writes
  only real tokens.

Decode readback is **pipelined**: the per-slot token/position feed
lives on device and the step program advances it in-graph, so the
engine dispatches decode step N+1 before doing step N's host-side work
(int conversion, ``on_token`` callbacks, EOS bookkeeping).  Membership
changes (joins, EOS leaves) drain the one-deep pipeline first, so the
host mirrors are current whenever they are pushed to the device.

The compiled-program budget stays O(1) in request count: the decode
step compiles ONCE per (S, cache dtype), prefill/scatter once per
prompt bucket, the chunk program once per chunk width, and the prefix
copy/extract programs once per granularity (``trace_counts`` exposes
the evidence; tests assert it).

The model walks its blocks once for a decode step and a chunk together
(``decode_step_with_chunk``: ``HybridDecoder``, ``TransformerLM``), so a
chunk due in a pass in which slots decode **rides the pass's decode
step**: one joint program in place of the chunk program followed by the
step, in which each layer's feed-forward (``TransformerLM``: its output
projection too) runs once over the decode rows and the chunk's rows, so
the pass reads those weights once.  The joint program takes the lone
chunk program's place at a width (the lone one is kept at the full
width, for an idle pool's long prompt), so the budget is one program
more.

Correctness bar (unchanged from the original engine, property-tested
over randomized arrival schedules, cache hit or miss): greedy tokens
per request are BIT-IDENTICAL to a solo ``model.generate()`` call,
regardless of which requests share the pool or in which order they
join and leave.  The properties that make it hold:

* a slot position is always freshly written before it is read — prefill
  (bucketed, chunked, or prefix-copied) writes positions ``0..Tp-2``,
  each decode step writes its position's K/V and pad flag before
  attending — so a new occupant never sees its predecessor's leftovers
  (no slot-reset pass needed);
* trailing bucket padding is masked exactly (softmax of a -1e9 logit
  underflows to 0.0 in f32), so a padded prefill reproduces the solo
  prefill bit-for-bit at every real position;
* chunked prefill attends over the carried-in cache with the same
  additive masking, so its K/V equal the monolithic prefill's
  bit-for-bit (``prefill_chunk`` is the W-token generalization of
  ``decode_step``, which already equals full-forward columns);
* a prefix-cache hit copies K/V that were extracted from an identical
  (prefix, position) prefill — the bytes are the same bytes.
"""

from __future__ import annotations

import gc
import logging
import threading
import time
from collections import deque
from concurrent.futures import Future
from concurrent.futures import TimeoutError as FuturesTimeout
from typing import Callable, Deque, Dict, List, Optional, Sequence, Tuple

import numpy as np

from bigdl_tpu import telemetry
from bigdl_tpu.serving.admission import (
    BoundedRequestQueue, ServerClosedError,
)
from bigdl_tpu.serving.batching import bucket_sizes, pick_bucket
from bigdl_tpu.serving.prefix_cache import PrefixChunk, PrefixKVCache
from bigdl_tpu.serving.reliability import (
    Deadline, ReplicaDeadError, RequestCancelledError,
)
from bigdl_tpu.telemetry import request_trace, tracing

__all__ = ["GenerationRequest", "SlotPool", "GenerationScheduler"]

logger = logging.getLogger(__name__)


class GenerationRequest:
    """One generation request: prompt + decode budget + its completion
    future.  Duck-types :class:`admission.Request` (``future``,
    ``t_enqueue``) so the bounded queue's admission policies —
    block/reject/shed_oldest — apply to generation unchanged."""

    __slots__ = ("prompt", "max_new_tokens", "eos_id", "on_token",
                 "future", "t_enqueue", "deadline", "trace")

    def __init__(self, prompt, max_new_tokens: int, eos_id=None,
                 on_token: Optional[Callable[[int], None]] = None,
                 deadline: Optional[Deadline] = None, trace=None):
        self.prompt = np.asarray(prompt, np.int32).reshape(-1)
        self.max_new_tokens = int(max_new_tokens)
        self.eos_id = eos_id
        self.on_token = on_token
        self.deadline = deadline
        # TraceContext (telemetry.request_trace) minted at router
        # admission, or None — the telemetry-disabled default — in
        # which case every trace site below is one bool check
        self.trace = trace
        self.future: "Future" = Future()
        self.t_enqueue = time.perf_counter()


def _caches_of(layer) -> Dict[str, Tuple[str, Optional[int]]]:
    """A layer's caches by name, from what the model declares of it
    (``cache_layers()``): one ``(kind, places)``, its keys and values
    (``"self"``), or several by name."""
    return layer if isinstance(layer, dict) else {"self": layer}


class SlotPool:
    """S fixed KV-cache slots plus the jitted shape-stable programs that
    advance them.  Host-side per-slot decode state (current token,
    position, active flag) is MIRRORED here as numpy arrays; the
    authoritative copy lives on device so decode steps chain without a
    host round-trip, and the mirrors are pushed only when membership
    changes (``_dirty``).  The pooled caches live on device and are
    donated through every update.

    **The cache is a list by layer**, each layer with its own rows, heads
    and widths, as the model declares them (``cache_layers()``,
    ``init_cache``): a ``full`` layer keeps ``max_len`` positions a slot;
    a ``ring`` layer (windowed attention) keeps the window and room for a
    prefill chunk beside it (``ring_margin``: a chunk is written before
    it is attended), position ``p`` at place ``p % ring``, and one spare
    place where idle lanes write.  Keys and values of a layer may differ
    in width.  ``TransformerLM`` is the case "every layer full, one
    shape".  A ``latent`` layer (latent attention) keeps ``max_len``
    positions of **one** head shared by every query head: leaf ``"v"``
    the compressed row (value, and most of the key), leaf ``"k"`` the
    rotary part of the key; it is written and read by position as a
    ``full`` row is, through the same programs, and only the accounting
    tells it apart (``cache_nbytes_by_kind``).  A layer may keep more
    than one cache, declared by name
    (``{"self": ("full", max_len), "ssm": ("state", None)}``): a
    ``state`` has **no positions**, one fixed-size value a slot that
    every token of the slot's sequence rewrites (a state-space mixer's
    recurrence and the last inputs of its convolution).  What a row cache
    gets for free a state has to be given: the chunk program starts a
    slot's state from zeros when it is handed position 0 (the reset at
    admission, inside the program that exists), the decode step leaves
    the state of an idle lane as it was (a slot between two of its
    prefill chunks rides every step; a row can send such a lane where
    nothing reads, a state cannot), and a prompt's last chunk is padded
    at its end and not moved back over positions already written (a
    position scanned twice would be in the state twice).  A state cannot
    be copied or extracted by position, so ``kv_copy`` and ``kv_extract``
    are not offered on such a model (the scheduler refuses it a prefix
    cache; the two programs are written for layers that each keep a row).
    The other four programs take the list.  **Not every layer has
    keys and values, and a layer may keep nothing**: a layer with a state
    alone declares ``{"ssm": ("state", None)}``, a layer with no cache
    ``{}``, and a layer that keeps nothing and attends **another layer's
    row** names it, ``{"reads": ("shared", 17)}``: no storage, one more
    reader of that row in every decode step (``full_row_readers``).  A
    model whose later layers keep nothing also says how many layers a
    chunk's rows walk (``chunk_layers``: they stop where the caches
    stop), which ``chunk_layer_positions`` counts by.

    **How the pool lies on the chip.**  A K or V leaf is
    ``[S, heads, max_len, head_dim]``.  With a head size under the 128
    lanes of a TPU tile (OPT's 64) the compiler stores it as
    ``{2,3,1,0}``: positions minor, so no lane is padding.  Every
    program that writes the pool (``_decode``, ``_chunk_prefill``,
    ``_scatter``, ``_kv_copy``) therefore writes windows with
    ``dynamic_update_slice`` / ``.at[].set`` at traced starts, which the
    donated leaf absorbs in place in that layout.  What must never reach
    the pool is a per-lane position under ``jax.vmap``: the batched
    ``dynamic_update_slice`` becomes a ``scatter`` whose operand is the
    whole leaf, the compiler's expansion of it wants ``{3,2,1,0}``, and
    each K and V of each layer is transposed in and back out on every
    step (41.9 of a 54.4 ms step at 6 slots of 2048; PERF.md, PR 27).
    ``tests/test_tpu_compile.py`` compiles the four programs for a
    described v5e and fails on a pool-sized ``copy``;
    ``docs/performance.md`` says how to read such a program's HLO.  On a
    TPU the decode step's attention reads a full row through a kernel
    that fetches live key blocks only (``ops.ragged_decode_attention``,
    handed each leaf positions-minor, as it lies); ``key_block`` is its
    block, and ``decode_dispatch`` counts by it what a step reads."""

    def __init__(self, model, slots: int, dtype=None,
                 prefill_batch: int = 4, ring_margin: int = 1):
        import jax
        import jax.numpy as jnp
        if getattr(model, "seq_parallel", False):
            raise ValueError(
                "sequence-parallel models cannot serve from a slot pool "
                "(the ring path has no decode cache); build a dense copy")
        for attr in ("init_cache", "cache_layers", "decode_step",
                     "decode_step_with_chunk", "prefill_kv",
                     "prefill_chunk", "decode_key_block", "chunk_key_block",
                     "max_len", "_mask_untrained_logit"):
            if not hasattr(model, attr):
                raise TypeError(
                    f"slot-pool generation needs a model with the "
                    f"incremental-decode API (init_cache/cache_layers/"
                    f"decode_step/decode_step_with_chunk/prefill_kv/"
                    f"prefill_chunk): "
                    f"{type(model).__name__} lacks {attr!r}")
        if slots < 1:
            raise ValueError(f"slots must be >= 1, got {slots}")
        # private eval-mode copy: serving must not flip the caller's
        # training flags, and dropout in decode would break greedy
        # equivalence with generate() on an eval'd model.  The copy is of
        # the module STRUCTURE and shares the leaves: ``model.clone()`` is
        # a deep copy, and a deep copy of a jax.Array is a second buffer
        # on the device — a model that fills the chip would stand on it
        # twice.  Arrays are immutable, so sharing them is safe.
        leaves, treedef = jax.tree_util.tree_flatten(model)
        self.model = jax.tree_util.tree_unflatten(treedef,
                                                  leaves).eval_mode()
        self.slots = int(slots)
        self.dtype = jnp.float32 if dtype is None else dtype
        self.prefill_batch = max(1, int(prefill_batch))
        self.max_len = int(model.max_len)
        # each layer's cache as the model declares it: ("full", max_len),
        # ("latent", max_len) or ("ring", window).  A ring is allocated
        # with room for a prefill chunk of ``ring_margin`` positions
        # beside its window: the chunk is written before it is attended,
        # and must leave its first query's keys in place.  The default is room for one
        # position, what a pool that is never handed a chunk needs (the
        # scheduler passes its ``prefill_chunk``).
        self.cache_layers = tuple(model.cache_layers())
        kinds = [[kind for kind, _ in _caches_of(layer).values()]
                 for layer in self.cache_layers]
        self.has_ring = any("ring" in of_layer for of_layer in kinds)
        self.state_layers = sum("state" in of_layer for of_layer in kinds)
        self.has_state = self.state_layers > 0
        # layers whose decode attention reads a full row, for each row
        # written: its own layer and every layer that names it
        named = [places for layer in self.cache_layers
                 for kind, places in _caches_of(layer).values()
                 if kind == "shared"]
        self.full_row_readers = max(
            (1 + named.count(i) for i, layer in enumerate(self.cache_layers)
             if _caches_of(layer).get("self", ("",))[0] in ("full", "latent")),
            default=0)
        # layers that a prefill's positions walk (a model whose later
        # layers keep no cache stops a chunk's rows before them)
        self.chunk_layers = int(getattr(model, "chunk_layers",
                                        len(self.cache_layers)))
        self.ring_margin = int(ring_margin)
        self.caches = self.model.init_cache(
            self.slots, self.dtype,
            **({"ring_margin": self.ring_margin} if self.has_ring else {}))
        # a model with expert layers returns what they did from every
        # pass (int32 [ROUTING]: calls, pairs routed, pairs on held
        # experts, held experts with a token, token-expert rows the
        # product multiplied).  The counts gather on the device
        # (prefill programs add theirs) and ride the next decode step's
        # read-back behind the tokens: no transfer of their own.
        self.expert_layers = int(model.expert_layers()) \
            if hasattr(model, "expert_layers") else 0
        self._routing = jnp.zeros(
            (len(MOE_COUNTERS) if self.expert_layers else 0,), jnp.int32)
        # places of a full row that the decode program's attention reads
        # at a time, where the model's step reads live blocks only; None
        # where it reads every row whole (decode_dispatch counts by it)
        self.key_block = model.decode_key_block(self.caches)
        # the same of a prefill chunk's attention: places of its slot's
        # full row that it reads at a time where the model's chunk path
        # reads live blocks only; None where it reads the row whole
        # (the scheduler counts a chunk's reading by it)
        self.chunk_key_block = model.chunk_key_block(self.caches)
        # the ring layers by what they keep: ``(window, places, layers)``
        # for each window among them, ``places`` what a slot's ring is
        # allocated (the window, the chunk's margin and the spare place).
        # The decode program reads every slot's ring whole in each of
        # them (decode_dispatch counts by it)
        rings: Dict[Tuple[int, int], int] = {}
        for decl, layer in zip(self.cache_layers, self.caches["layers"]):
            kind, window = _caches_of(decl).get("self", ("", None))
            if kind == "ring":
                key = (int(window), int(layer["self"]["k"].shape[2]))
                rings[key] = rings.get(key, 0) + 1
        self.rings = tuple((w, p, n) for (w, p), n in rings.items())
        # device programs that write the cache in one decode step: one
        # dynamic_update_slice a slot and leaf (keys, values, flags),
        # unless the model says that its step writes with fewer
        self.cache_write_programs = int(
            model.cache_write_programs(self.caches)
            if hasattr(model, "cache_write_programs") else
            self.slots * len(jax.tree_util.tree_leaves(self.caches)))
        # the model walks its blocks once for a decode step and a prefill
        # chunk together (``decode_step_with_chunk``: each layer's
        # feed-forward runs once over both): a chunk due in a pass in
        # which slots decode rides the step (``decode_dispatch(chunk)``).
        # The pool holds its chunk programs compiled, by width, all of
        # them from its first chunk on (``_chunk_programs``).  The
        # widest chunk the pool is handed is what the rings have room
        # for (the scheduler's ``prefill_chunk``); and the widths a
        # prompt's last chunk draws from, the upper four of the powers of
        # two up to it (a joint program costs a server's start half as
        # much again as the lone one it replaces: four and the lone one
        # cost less than the lone ones of every width did): a shorter
        # remainder rides the narrowest
        self.chunk_width = min(self.ring_margin, self.max_len)
        self.chunk_widths = tuple(
            w for w in bucket_sizes(self.chunk_width)
            if w >= self.chunk_width // 8)
        self._chunk_compiled: Dict[int, Tuple] = {}
        self.tok = np.zeros((self.slots,), np.int32)
        self.index = np.zeros((self.slots,), np.int32)
        self.active = np.zeros((self.slots,), bool)
        # device-carried decode feed (tok, index, active); rebuilt from
        # the mirrors whenever _dirty (a join or leave happened)
        self._dev: Optional[Tuple] = None
        self._dirty = True
        # per-dispatch credit epoch: a step's emit folds into the host
        # mirrors (and is credited to occupants) ONLY for slots that
        # were active at ITS dispatch and not re-seeded
        # (activate/release) since — otherwise a predecessor's
        # lame-duck token would overwrite or be credited to a fresh
        # occupant.  With the one-deep pipeline the epoch of the
        # still-unread step is finalized into its handle at the next
        # dispatch (see _StepHandle).
        self._emit_active = self.active.copy()
        self._touched = np.zeros((self.slots,), bool)
        self._open_handle: Optional[_StepHandle] = None
        # trace-time counters: the increments below run only while jax
        # traces, so (with jit's cache) they equal compile counts —
        # tests pin decode == 1 and prefill/chunk/copy == one per width
        self.trace_counts: Dict[str, object] = {
            "decode": 0, "decode_with_chunk": {}, "prefill": {},
            "scatter": {}, "chunk_prefill": {}, "kv_copy": {},
            "kv_extract": {}}
        self._build_programs()

    # -- compiled programs --------------------------------------------------

    def _build_programs(self):
        import jax
        import jax.numpy as jnp
        from bigdl_tpu.nn.attention import _write_window
        counts = self.trace_counts
        self.traces = 0         # ``trace_counts`` summed

        def traced(program: str, key: Optional[int] = None) -> None:
            """Runs only while jax traces ``program`` (keyed by a width
            where it has several): with jit's cache, a compile."""
            self.traces += 1
            if key is None:
                counts[program] = counts.get(program, 0) + 1
            else:
                counts[program][key] = counts[program].get(key, 0) + 1

        # The model is an ARGUMENT of every program that runs it, never
        # a closure: closed over, its weights are baked into each
        # executable as constants — hundreds of MB per program at a
        # 32k vocabulary, slow to compile and too large for the
        # persistent compilation cache to keep.

        experts = self.expert_layers > 0

        def _advance(model, logits, new_caches, did, tok, index, active,
                     routing):
            """A step's results from its logits: the caches, the feed
            advanced, what is read back, and the routing cleared."""
            nxt = jnp.argmax(model._mask_untrained_logit(logits),
                             axis=-1).astype(jnp.int32) + 1
            # the feed advances IN-GRAPH so step N+1 can be dispatched
            # before step N's emit is read on the host; inactive slots
            # still burn a lane (S is shape-stable) — mask their
            # emission so 0 reliably means "nothing emitted" (active
            # slots emit argmax+1 >= 1, never 0)
            new_tok = jnp.where(active, nxt, tok)
            new_index = jnp.where(active, index + 1, index)
            # what the expert layers did, here and in the prefill
            # programs since the last step, rides behind the S tokens:
            # one read-back a step, whatever it carries
            emit = jnp.concatenate(
                [jnp.where(active, nxt, 0),
                 routing + did[0] if experts else routing])
            return new_caches, new_tok, new_index, emit, \
                jnp.zeros_like(routing)

        def _decode(model, caches, tok, index, active, routing):
            traced("decode")

            # ONE batched step over the S slots, each written and masked
            # at its own position (decode_step's per-row path) — never a
            # vmap of a batch-1 step: see the class docstring.
            #
            # Every lane writes its position's K/V (S is shape-stable),
            # so the model is told which lanes are idle and sends them
            # where nothing reads: the last position of a full row, the
            # spare place of a ring (a ring has no position that a
            # prompt longer than the window may not just have written).
            # A model with expert layers returns what they did as a
            # third value.
            logits, new_caches, *did = model.decode_step(
                tok[:, None], index, caches, active=active)
            return _advance(model, logits, new_caches, did, tok, index,
                            active, routing)

        self._decode_jit = jax.jit(_decode, donate_argnums=(1, 2, 3))

        def _decode_with_chunk(model, caches, tok, index, active, routing,
                               slot_id, toks, chunk_index):
            w = int(toks.shape[0])
            traced("decode_with_chunk", w)
            # the step above and the chunk program below as ONE walk of
            # the model (``decode_step_with_chunk``): in every layer the
            # chunk's mixer, the rows' mixer on the caches it left, and
            # one feed-forward over both.  A decode step to everything
            # that counts steps (its name begins as the step's does: the
            # profile's readers find steps by it); keyed by chunk width
            logits, new_caches, *did = model.decode_step_with_chunk(
                tok[:, None], index, caches, active, toks[None],
                chunk_index, slot_id)
            return _advance(model, logits, new_caches, did, tok, index,
                            active, routing)

        self._decode_with_chunk_jit = jax.jit(_decode_with_chunk,
                                              donate_argnums=(1, 2, 3))

        def _prefill(model, ptoks):
            t = int(ptoks.shape[1])
            traced("prefill", t + 1)
            return model.prefill_kv(ptoks)

        self._prefill_jit = jax.jit(_prefill)

        def _scatter(caches, slot_ids, layers_kv, pads):
            t = int(pads.shape[1])
            traced("scatter", t + 1)
            new_layers = []
            # a ring keeps each row's newest positions, position p at
            # place p % R: place j takes the newest REAL position
            # congruent to it (trailing bucket padding is not one), by a
            # gather; a place with none yet takes anything, its position
            # reads as unwritten (attention.cache_positions)
            last = jnp.sum(~pads, axis=1).astype(jnp.int32) - 1     # [B]

            def rows(kind, old, kv):
                if kind == "state":
                    # the state after each row's last real token, whole
                    return {n: old[n].at[slot_ids].set(
                        kv[n].astype(old[n].dtype), mode="drop")
                        for n in old}
                new = {}
                for n in ("k", "v"):
                    src, n_places = kv[n].astype(old[n].dtype), t
                    if kind == "ring":
                        n_places = old[n].shape[2] - 1
                        j = jnp.arange(n_places, dtype=jnp.int32)
                        pos = last[:, None] - jnp.mod(last[:, None] - j,
                                                      n_places)
                        src = jnp.take_along_axis(
                            src, jnp.clip(pos, 0, t - 1)[:, None, :, None],
                            axis=2)
                    # rows for padded prefill lanes carry slot_id == S:
                    # mode="drop" discards the out-of-range scatter
                    # instead of writing a real slot
                    new[n] = old[n].at[slot_ids, :, :n_places, :].set(
                        src, mode="drop")
                return new

            for decl, kv, cache in zip(self.cache_layers, layers_kv,
                                       caches["layers"]):
                kv = kv if isinstance(decl, dict) else {"self": kv}
                new_layers.append({
                    name: rows(kind, cache[name], kv[name])
                    for name, (kind, _) in _caches_of(decl).items()
                    if kind != "shared"})
            pad = caches["pad"].at[slot_ids, :t].set(pads, mode="drop")
            return {"layers": new_layers, "pad": pad}

        self._scatter_jit = jax.jit(_scatter, donate_argnums=(0,))

        def _chunk_prefill(model, caches, slot_id, toks, index, routing):
            w = int(toks.shape[0])
            traced("chunk_prefill", w)
            # pooled mode: the model writes exactly the chunk window of
            # the slot's row (a small dynamic_update_slice the donated
            # pool absorbs in place) and reads the row's keys by slice;
            # slot_id and index are traced, so the program is keyed by
            # chunk width alone.  A state has no window to write: the
            # model reads the slot's state (zeros when index is 0: the
            # slot's new occupant), scans the chunk's real tokens and
            # writes the state back
            out = model.prefill_chunk(toks[None], index, caches,
                                      slot=slot_id)
            new_caches, *did = out if isinstance(out, tuple) else (out,)
            return new_caches, routing + did[0] if experts else routing

        self._chunk_jit = jax.jit(_chunk_prefill, donate_argnums=(1,))

        def _kv_copy(caches, slot_id, layers_kv, pad, index):
            g = int(pad.shape[0])
            traced("kv_copy", g)
            new_layers = []
            for (kind, _), kv, cache in zip(self.cache_layers, layers_kv,
                                            caches["layers"]):
                old = cache["self"]
                new_layers.append({"self": {
                    n: _write_window(old[n], kv[n][None], slot_id, index,
                                     kind == "ring")[0]
                    for n in ("k", "v")}})
            new_pad = jax.lax.dynamic_update_slice(
                caches["pad"], pad[None], (slot_id, index))
            return {"layers": new_layers, "pad": new_pad}

        self._kv_copy_jit = jax.jit(_kv_copy, donate_argnums=(0,))

        def _kv_extract(caches, slot_id, index, width):
            traced("kv_extract", width)
            layers = []
            for (kind, _), cache in zip(self.cache_layers,
                                        caches["layers"]):
                out = {}
                for n, leaf in cache["self"].items():
                    _, h, places, d = leaf.shape
                    if kind == "ring":
                        # by place: right only while the ring still
                        # holds these positions (the scheduler keeps the
                        # prefix cache off a model with rings)
                        row = jax.lax.dynamic_slice(
                            leaf, (slot_id, 0, 0, 0), (1, h, places, d))[0]
                        out[n] = jnp.take(row, jnp.mod(
                            index + jnp.arange(width), places - 1), axis=1)
                    else:
                        out[n] = jax.lax.dynamic_slice(
                            leaf, (slot_id, 0, index, 0),
                            (1, h, width, d))[0]
                layers.append(out)
            pad = jax.lax.dynamic_slice(caches["pad"], (slot_id, index),
                                        (1, width))[0]
            return layers, pad

        # NOT donated: the slot keeps decoding from these caches
        self._kv_extract_jit = jax.jit(_kv_extract, static_argnums=(3,))

        def _seed(tok, index, active, slot, t, i, a):
            traced("seed")
            return (tok.at[slot].set(t), index.at[slot].set(i),
                    active.at[slot].set(a))

        # membership changes (join/leave) update the DEVICE feed with
        # this one-slot scatter instead of a host push, so the decode
        # pipeline never has to drain for them — draining costs every
        # co-resident stream a ~2x inter-token gap per join/leave
        self._seed_jit = jax.jit(_seed, donate_argnums=(0, 1, 2))

    # -- introspection ------------------------------------------------------

    def cache_nbytes(self) -> int:
        import jax
        return sum(int(leaf.size) * leaf.dtype.itemsize
                   for leaf in jax.tree_util.tree_leaves(self.caches))

    def cache_nbytes_by_kind(self) -> Dict[str, int]:
        """Bytes of the layers' caches by kind (``full`` | ``ring`` |
        ``state`` | ``latent``)."""
        import jax
        out = {"full": 0, "ring": 0, "state": 0, "latent": 0}
        for decl, layer in zip(self.cache_layers, self.caches["layers"]):
            for name, (kind, _) in _caches_of(decl).items():
                if kind == "shared":
                    continue
                out[kind] += sum(
                    int(leaf.size) * leaf.dtype.itemsize
                    for leaf in jax.tree_util.tree_leaves(layer[name]))
        return out

    def _routing_aval(self):
        import jax
        return jax.ShapeDtypeStruct(self._routing.shape,
                                    self._routing.dtype)

    def _cache_avals(self):
        import jax
        return jax.tree_util.tree_map(
            lambda a: jax.ShapeDtypeStruct(a.shape, a.dtype), self.caches)

    def _feed_avals(self) -> Tuple:
        import jax
        import jax.numpy as jnp
        s = (self.slots,)
        return (jax.ShapeDtypeStruct(s, jnp.int32),
                jax.ShapeDtypeStruct(s, jnp.int32),
                jax.ShapeDtypeStruct(s, jnp.bool_))

    @staticmethod
    def _chunk_avals(width: int) -> Tuple:
        """``(slot, toks, index)`` of a chunk of ``width``."""
        import jax
        import jax.numpy as jnp
        scalar = jax.ShapeDtypeStruct((), jnp.int32)
        return scalar, jax.ShapeDtypeStruct((width,), jnp.int32), scalar

    def decode_compiled(self):
        """Compiled pooled decode step at the live pool shapes."""
        return self._decode_jit.lower(
            self.model, self._cache_avals(), *self._feed_avals(),
            self._routing_aval()).compile()

    def decode_hlo_text(self) -> str:
        """Optimized HLO of the pooled decode step at the live pool
        shapes — feed to ``analysis.hlo_lint.donated_alias_bytes`` to
        verify the cache donation really elides the full copy."""
        return self.decode_compiled().as_text()

    def _chunk_lowered(self, width: int, caches=None):
        return self._chunk_jit.lower(
            self.model, caches or self._cache_avals(),
            *self._chunk_avals(width), self._routing_aval())

    def chunk_prefill_compiled(self, width: int):
        """Compiled KV-carry-in chunk-prefill program at ``width`` —
        what the graftlint budget probe lowers."""
        return self._chunk_lowered(width).compile()

    def _chunk_programs(self, width: int) -> Tuple:
        """The compiled programs that can carry a chunk of ``width``,
        ``(joint, alone)``.  The joint program takes the place of the
        lone one at a width and does not stand beside it (a chunk beside
        an idle pool rides it with every row idle); the lone program is
        kept (``alone`` is not None) at the full width only, where an
        idle server's long prompt would otherwise pay a dead step a
        chunk.

        **The pool's first chunk compiles the programs of every width**
        (``chunk_widths``), whichever sends it: a width that only idle
        pools had seen must not compile its joint program when it first
        meets decoding slots, inside a measured window.  They are lowered
        here in turn and compiled (or loaded from the persistent cache)
        meanwhile on a few threads."""
        from concurrent.futures import ThreadPoolExecutor
        held = self._chunk_compiled
        if width not in held:
            caches, feed = self._cache_avals(), self._feed_avals()
            widths = {width} if held else {width, *self.chunk_widths}
            with ThreadPoolExecutor(max_workers=4) as workers:
                jobs = {}
                for w in sorted(widths, reverse=True):
                    lowered = [self._decode_with_chunk_jit.lower(
                        self.model, caches, *feed, self._routing_aval(),
                        *self._chunk_avals(w))]
                    if w == self.chunk_width:
                        lowered.append(self._chunk_lowered(w, caches))
                    jobs[w] = [workers.submit(low.compile)
                               for low in lowered]
                for w, (joint, *alone) in jobs.items():
                    held[w] = (joint.result(),
                               alone[0].result() if alone else None)
        return held[width]

    def kv_copy_compiled(self, granularity: int):
        """Compiled prefix KV-copy program at ``granularity``."""
        import jax
        import jax.numpy as jnp
        scalar = jax.ShapeDtypeStruct((), jnp.int32)
        layers = []
        for cache in self.caches["layers"]:
            layers.append({
                n: jax.ShapeDtypeStruct(
                    (leaf.shape[1], granularity, leaf.shape[3]), leaf.dtype)
                for n, leaf in cache["self"].items()})
        pad = jax.ShapeDtypeStruct((granularity,), jnp.bool_)
        return self._kv_copy_jit.lower(
            self._cache_avals(), scalar, layers, pad, scalar).compile()

    # -- pool operations ----------------------------------------------------

    def free_slots(self) -> List[int]:
        return [i for i in range(self.slots) if not self.active[i]]

    def n_active(self) -> int:
        return int(self.active.sum())

    @property
    def dirty(self) -> bool:
        """True when the host mirrors diverged from the device feed (a
        join or leave happened) — the next dispatch pushes them."""
        return self._dirty

    def _seed_slot(self, slot: int, tok: int, index: int,
                   active: bool) -> None:
        """Re-seed one slot's decode feed: host mirrors always, and the
        device copy in-graph when it exists (no pipeline drain — the
        scatter rides the same device queue as the steps around it)."""
        self.tok[slot] = tok
        self.index[slot] = index
        self.active[slot] = active
        self._touched[slot] = True
        if self._dev is None:
            self._dirty = True
            return
        tok_d, idx_d, act_d = self._dev
        self._dev = self._seed_jit(tok_d, idx_d, act_d, np.int32(slot),
                                   np.int32(tok), np.int32(index),
                                   np.bool_(active))

    def activate(self, slot: int, tok: int, index: int) -> None:
        """Mark ``slot`` decode-ready: feed ``tok`` at ``index`` on the
        next step (the request's last prompt token at its position)."""
        self._seed_slot(slot, tok, index, True)

    def release(self, slot: int) -> None:
        self._seed_slot(slot, 0, 0, False)

    def free(self) -> None:
        """Let go of everything this pool holds on the device: the
        caches, the device feed and the model (its leaves are shared
        with whoever built it, and live on only while that caller still
        holds them).  A freed pool runs no further program."""
        self.caches = None
        self.model = None
        self._dev = None
        self._chunk_compiled.clear()
        self._routing = None
        self._open_handle = None

    def invalidate_feed(self) -> None:
        """Drop the device feed (e.g. after a failed dispatch may have
        consumed its donated buffers); the next dispatch rebuilds it
        from the host mirrors."""
        self._dev = None
        self._dirty = True

    def prefill_into(self, prompts: Sequence[np.ndarray],
                     slot_ids: Sequence[int], bucket: int) -> None:
        """Batched prefill of ``prompts`` (true lengths <= bucket) into
        ``slot_ids``, at the fixed prefill batch width so the compiled
        program is keyed by bucket alone.  Single-token buckets skip the
        dense prefill entirely (the first decode step writes position
        0), matching ``generate()``'s Tp == 1 path."""
        import jax.numpy as jnp
        n = len(prompts)
        assert n == len(slot_ids) and 0 < n <= self.prefill_batch
        if bucket > 1:
            padded = np.zeros((self.prefill_batch, bucket), np.int32)
            for i, p in enumerate(prompts):
                # the decode step feeds the last prompt token.  A row
                # rewrites its position then; a state would hold it twice
                keep = len(p) - 1 if self.has_state else len(p)
                padded[i, :keep] = p[:keep]
            if n < self.prefill_batch:
                # dead lanes repeat row 0 (any valid prompt); their
                # scatter is dropped via the out-of-range slot id
                padded[n:] = padded[0]
            ids = np.full((self.prefill_batch,), self.slots, np.int32)
            ids[:n] = np.asarray(slot_ids, np.int32)
            layers_kv, pads, *did = self._prefill_jit(
                self.model, jnp.asarray(padded[:, :-1]))
            if self.expert_layers:      # their routing: see __init__
                self._routing = self._routing + did[0]
            self.caches = self._scatter_jit(
                self.caches, jnp.asarray(ids), layers_kv, pads)
        for p, s in zip(prompts, slot_ids):
            # decode resumes from the last REAL prompt token at its true
            # position — bucket padding never shifts a request
            self.activate(s, int(p[len(p) - 1]), len(p) - 1)

    def chunk_prefill_into(self, toks: np.ndarray, slot: int,
                           index: int) -> None:
        """One KV-carry-in prefill chunk: write K/V + pad flags for
        ``toks`` (a fixed-width window of the prompt) at positions
        ``[index, index+len(toks))`` of ``slot``'s cache row, attending
        to everything already written below ``index``."""
        import jax.numpy as jnp
        toks = jnp.asarray(np.ascontiguousarray(toks, np.int32))
        joint, alone = self._chunk_programs(int(toks.shape[0]))
        if alone is not None:
            self.caches, self._routing = alone(
                self.model, self.caches, np.int32(slot), toks,
                np.int32(index), self._routing)
            return
        # the joint program with every row idle: the rows write where
        # nothing reads and emit nothing, whatever decodes meanwhile; the
        # routing comes back behind their zeros
        self.caches, _, _, emit, _ = joint(
            self.model, self.caches, jnp.zeros((self.slots,), jnp.int32),
            jnp.zeros((self.slots,), jnp.int32),
            jnp.zeros((self.slots,), bool), self._routing,
            np.int32(slot), toks, np.int32(index))
        self._routing = emit[self.slots:]

    def kv_copy_into(self, slot: int,
                     chain: Sequence[PrefixChunk]) -> None:
        """Copy a matched prefix-cache chain into ``slot``'s row (one
        device-side scatter per chunk, compiled once per granularity)."""
        self._refuse_state("copied into")
        for chunk in chain:
            self.caches = self._kv_copy_jit(
                self.caches, np.int32(slot), chunk.layers, chunk.pad,
                np.int32(chunk.index))

    def _refuse_state(self, what: str) -> None:
        if self.has_state:
            raise ValueError(
                f"keys and values by position cannot be {what} a slot "
                f"that also keeps a state: the state is of the whole "
                f"sequence and has no positions to take a part of")

    def kv_extract(self, slot: int, index: int, width: int):
        """Read back ``width`` positions of ``slot``'s K/V row starting
        at ``index`` (compact per-layer arrays + pad flags) — what the
        prefix cache stores.  Does NOT donate the pool caches."""
        self._refuse_state("extracted from")
        return self._kv_extract_jit(self.caches, np.int32(slot),
                                    np.int32(index), int(width))

    # -- decode (pipelined dispatch/readback) -------------------------------

    def decode_dispatch(self, chunk: Optional[Tuple] = None) \
            -> "_StepHandle":
        """Dispatch one pooled decode step and return its handle
        WITHOUT reading it back — the device feed advances in-graph
        (and membership seeds ride the same queue), so the next step
        can be dispatched before this one's host work.  Finalizes the
        credit epoch of the still-outstanding previous step first.
        ``chunk`` (``(toks, slot, index)`` as :meth:`chunk_prefill_into`
        takes them) rides the step:
        one program writes the chunk and then advances the slots, what
        the chunk program followed by the step would have done."""
        import jax.numpy as jnp
        if self._open_handle is not None \
                and self._open_handle.mask is None:
            # seeds between the previous dispatch and now belong to
            # ITS epoch: freeze them into its credit mask before this
            # dispatch resets the epoch
            self._open_handle.mask = self._emit_active & ~self._touched
        if self._dirty or self._dev is None:
            # copies, not views: the CPU backend may hand back an array
            # that aliases the numpy buffer, the mirrors are written in
            # place at every join and leave, and a step still in flight
            # would see a slot become active with a stale feed (a state
            # layer starts such a row from zeros)
            self._dev = (jnp.array(self.tok), jnp.array(self.index),
                         jnp.array(self.active))
            self._dirty = False
        # what this step attends, from the mirrors: an active slot's
        # positions up to its own.  The mirrors stand one step behind the
        # device for the slots whose last step is still unread.
        lengths = self.index[self.active] + 1
        if self._open_handle is not None:
            lengths = lengths + self._open_handle.mask[self.active]
        block = self.key_block
        positions = (int(lengths.sum()),
                     int((-(-lengths // block) * block).sum()) if block
                     else self.slots * self.max_len)
        # the rows the ragged kernel starts in a full-layer call, and
        # those of them whose first key block it fetches behind a step of
        # the live row before (all but the call's first); none where the
        # step reads whole rows
        rows = (len(lengths), max(len(lengths) - 1, 0)) if block else (0, 0)
        # the same of the ring layers, summed over them: the places an
        # active slot's query can attend (its positions, the window at
        # most), and what the program reads, every slot's ring whole
        rings = (sum(n * int(np.minimum(lengths, w).sum())
                     for w, _, n in self.rings),
                 sum(n * self.slots * p for _, p, n in self.rings))
        tok_d, idx_d, act_d = self._dev
        if chunk is None:
            out = self._decode_jit(self.model, self.caches, tok_d, idx_d,
                                   act_d, self._routing)
        else:
            toks, slot, at = chunk
            out = self._chunk_programs(len(toks))[0](
                self.model, self.caches, tok_d, idx_d, act_d, self._routing,
                np.int32(slot),
                jnp.asarray(np.ascontiguousarray(toks, np.int32)),
                np.int32(at))
        self.caches, new_tok, new_idx, emit, self._routing = out
        self._dev = (new_tok, new_idx, act_d)
        self._emit_active = self.active.copy()
        self._touched[:] = False
        handle = _StepHandle(emit, positions, rows, rings)
        self._open_handle = handle
        return handle

    def read_emit_masked(self, handle: "_StepHandle") \
            -> Tuple[np.ndarray, np.ndarray]:
        """Block on one step's handle and fold its emit into the host
        mirrors — only for slots in the step's credit epoch (active at
        ITS dispatch, mirrors not re-seeded since), which keep their
        fresh values otherwise.  Returns ``(tokens [S], credit [S]
        bool)``: ``credit`` marks the slots whose emission belongs to
        the occupant resident at dispatch — a slot released and
        re-occupied since must not have the predecessor's trailing
        token credited to the new request."""
        was = handle.mask
        if was is None:
            was = self._emit_active & ~self._touched
        if self._open_handle is handle:
            self._open_handle = None
        out = np.asarray(handle.emit)
        out, handle.routing = out[:self.slots], out[self.slots:]
        feed = out.astype(np.int32)
        self.tok = np.where(was, feed, self.tok).astype(np.int32)
        self.index = np.where(was, self.index + 1,
                              self.index).astype(np.int32)
        return out, was

    def read_emit(self, handle: "_StepHandle") -> np.ndarray:
        return self.read_emit_masked(handle)[0]

    def decode(self) -> np.ndarray:
        """Synchronous decode step (dispatch + readback) — kept for
        callers that do not pipeline."""
        return self.read_emit(self.decode_dispatch())


class _StepHandle:
    """One dispatched decode step: its unread emit plus the credit
    epoch (finalized at the NEXT dispatch — until then the pool's live
    epoch applies)."""

    __slots__ = ("emit", "mask", "routing", "positions", "rows", "rings")

    def __init__(self, emit, positions=(0, 0), rows=(0, 0), rings=(0, 0)):
        self.emit = emit
        # (live, read): the cache positions this step's attention may
        # attend, and those its program reads to do so, a full layer
        self.positions = positions
        # (live, prefetched): the active rows of a full layer's call of
        # the ragged decode kernel, and those with a live row before them
        self.rows = rows
        # (live, read): the ring places this step's attention may attend
        # and those its program reads, summed over the ring layers
        self.rings = rings
        self.mask: Optional[np.ndarray] = None
        # read back with the tokens: what the expert layers did since
        # the previous step (empty for a model without them)
        self.routing: Optional[np.ndarray] = None


class _ActiveSlot:
    """Host bookkeeping for one occupied slot (prefilling or decoding)."""

    __slots__ = ("req", "emitted", "t_first", "t_last", "eos_id", "slot",
                 "phase", "next_pos", "end_pos", "was_follower",
                 "t_decode")

    def __init__(self, req: GenerationRequest, eos_id, slot: int):
        self.req = req
        self.emitted: List[int] = []
        self.t_first: Optional[float] = None
        self.t_last: Optional[float] = None
        self.eos_id = eos_id
        self.slot = slot
        self.phase = "prefill"
        self.next_pos = 0                       # next prefill position
        self.end_pos = max(len(req.prompt) - 1, 0)   # prefill covers [0, end)
        self.was_follower = False               # dedup counted once
        self.t_decode: Optional[float] = None   # decode-join stamp


# The engine thread's wall time, split: every instant of ``_run`` lies in
# exactly one of these (``other`` is a pass's own time: the reliability
# sweep, the queue poll, loop bookkeeping; ``idle`` is blocked on an empty
# queue or in the follower poll), so the seven sum to the thread's life.
_ENGINE_PHASES = ("admit", "prefill_dispatch", "decode_dispatch",
                  "readback_wait", "emit", "other", "idle")
# what an expert layer's call counts, in the order it returns them
# (``nn.moe.HeldExperts.forward``, ``nn.moe.ROUTING``)
MOE_COUNTERS = ("moe_layer_calls", "moe_pairs_total", "moe_pairs_held",
                "moe_active_experts", "moe_rows_computed")
_ENGINE_COUNTERS = (
    "iterations", "decode_dispatches", "pipeline_drains",
    "gaps_plain", "gaps_prefill", "gap_seconds_plain",
    "gap_seconds_prefill", "prefill_positions", "prefill_prompt_tokens",
    "admitted", "queue_wait_seconds",
    "decode_positions_live", "decode_positions_read",
    "decode_rows_live", "decode_rows_prefetched",
    "ring_positions_live", "ring_positions_read",
    "chunk_positions_live", "chunk_positions_read",
    "chunks_joint", "chunks_alone", "chunk_layer_positions",
    *MOE_COUNTERS,
    "ssm_layer_calls", "ssm_scan_positions", "ssm_scan_positions_real",
    "state_resets")


_NO_CHUNK = (0, -1, -1)     # a pass record's chunk fields without a chunk


def _fold_counts(acc: Dict[str, float], life: Dict[str, float],
                 eng: Dict[str, float]) -> None:
    """Publish what the engine thread gathered: the counters since the
    last fold move into the published sums, the phase seconds (running
    totals) replace theirs.  The caller holds the scheduler's lock."""
    for k, v in acc.items():
        if v:
            eng[k] += v
            acc[k] = 0
    eng.update(life)


class _Reservoir:
    """Bounded uniform sample for host-side latency quantiles — the
    serving.metrics reservoir scheme, sized for the engine (TTFT and
    inter-token gaps; a mean hides exactly the head-of-line tail this
    engine exists to bound)."""

    __slots__ = ("cap", "vals", "seen", "_rng")

    def __init__(self, cap: int = 8192, seed: int = 0):
        self.cap = cap
        self.vals: List[float] = []
        self.seen = 0
        self._rng = np.random.default_rng(seed)

    def add(self, v: float) -> None:
        self.seen += 1
        if len(self.vals) < self.cap:
            self.vals.append(float(v))
        else:
            j = int(self._rng.integers(self.seen))
            if j < self.cap:
                self.vals[j] = float(v)

    def quantiles(self, qs=(0.5, 0.99)) -> Dict[str, float]:
        if not self.vals:
            return {f"p{int(q * 100)}": 0.0 for q in qs}
        out = np.quantile(np.asarray(self.vals), list(qs))
        return {f"p{int(q * 100)}": float(v) for q, v in zip(qs, out)}


# One record a decode step's read-back (``GenerationScheduler._emit_step``).
# ``seq`` is the decode dispatch's number.  Then what went out between the
# previous decode dispatch and this one, which the device ran inside the
# gap this record closes: ``joint`` (the step carried a chunk), the lone
# chunk programs and bucketed prefills, and of the chunk (the joint one,
# else the last that went alone) its width, its first position in its row
# and its slot (0, -1, -1 without one).  Then the step.  ``t`` is the
# ``perf_counter()`` instant the read-back returned (what the step's tokens
# are stamped with) and ``gap_s`` the step gap that instant closes (NaN
# where a pause lies before it).  Last, where the engine thread was since
# the previous record's ``t`` (the seven phases tile that time: they sum
# to ``gap_s``), and what else happened in it: seconds of collector passes
# (any generation, any thread) and programs traced.
PASS_RECORD = np.dtype(
    # what the dispatch knows (``_dispatch_decode``) ...
    [("seq", "i8"), ("joint", "i1"), ("chunks_alone", "i4"),
     ("bucketed", "i4"), ("chunk_width", "i4"), ("chunk_index", "i4"),
     ("chunk_slot", "i4"), ("drained", "i1"), ("n_active", "i4"),
     ("positions_live", "i8"), ("positions_read", "i8"),
     # ... and what its read-back adds (``_emit_step``)
     ("t", "f8"), ("gap_s", "f8"), ("emitted", "i4")]
    + [(k, "f8") for k in _ENGINE_PHASES]
    + [("gc_s", "f8"), ("compiles", "i4")])
# the engine writes these as running totals (no arithmetic a pass); a
# record's share is the difference from the record before it
_RUNNING = _ENGINE_PHASES + ("gc_s", "compiles")


class _PassRing:
    """The engine's pass records, the newest ``capacity`` of them.  One
    writer (the engine thread); any thread may read."""

    def __init__(self, capacity: int = 16384):
        self.capacity = int(capacity)
        # one row more: the record before the oldest, to difference from
        self._rows = np.zeros((self.capacity + 1,), PASS_RECORD)
        self._begun = 0             # records whose write has begun,
        self._done = 0              # and ended

    def append(self, row: tuple) -> None:
        """``row`` in PASS_RECORD's order, its ``_RUNNING`` fields as the
        engine's running totals."""
        n = self._done
        self._begun = n + 1
        self._rows[n % len(self._rows)] = row
        self._done = n + 1

    def dropped(self) -> int:
        return max(self._done - self.capacity, 0)

    def records(self, t0: Optional[float] = None,
                t1: Optional[float] = None) -> np.ndarray:
        """A copy of the records with ``t0 <= t < t1``, oldest first."""
        hi = self._done
        rows = self._rows.copy()
        # a record written while the copy ran may be torn in it: keep
        # those that ended before it and that none begun since overwrote
        lo = max(self._begun - self.capacity, 0)
        hi = max(hi, lo)
        if lo:
            rows = rows[np.arange(lo - 1, hi) % len(rows)]
        else:       # before the first record every total was zero
            rows = np.concatenate([np.zeros((1,), PASS_RECORD), rows[:hi]])
        for k in _RUNNING:
            rows[k][1:] = np.diff(rows[k])
        rows = rows[1:]
        keep = np.ones(len(rows), bool)
        if t0 is not None:
            keep &= rows["t"] >= t0
        if t1 is not None:
            keep &= rows["t"] < t1
        return rows[keep]


class PassLog(dict):
    """What ``stats()`` carries under ``"pass_log"``.  To a serialiser it
    is three numbers: ``seq`` of the newest record the snapshot's sums
    hold, the ring's ``capacity``, and how many records it has
    ``dropped``.  Behind them is the engine's ring, which outlives the
    engine and its pool: :meth:`records` copies a window out of it."""

    def __init__(self, ring: _PassRing, seq: int):
        super().__init__(seq=seq, capacity=ring.capacity,
                         dropped=ring.dropped())
        self._ring = ring

    def records(self, t0: Optional[float] = None,
                t1: Optional[float] = None) -> np.ndarray:
        return self._ring.records(t0, t1)


class GenerationScheduler:
    """Continuous-batching decode engine: the generation sibling of
    :class:`BatchScheduler`.  One daemon thread owns the
    admit -> prefill -> decode -> emit loop; submitters talk to it
    through a :class:`BoundedRequestQueue` with the same admission
    policies and drain machinery as one-shot serving.

    Prefill scheduling: prompts whose whole prefill fits one chunk
    (``len(prompt) <= prefill_chunk``) and hit no cached prefix go
    through the original bucketed batch prefill; longer prompts — and
    every cache-hit suffix — are prefilled in fixed-width chunks
    through the KV-carry-in program.  While any slot is decoding, at
    most ``prefill_chunk_budget`` prefill program calls run per engine
    iteration, bounding how long a long prompt can stall the token
    cadence of co-resident streams; with nothing decoding, pending
    prefill drains at full speed.  A chunk prepared while slots decode is
    not dispatched by itself: the pass's decode dispatch carries it
    (``chunks_joint`` in ``stats()``; ``chunks_alone`` counts the chunk
    programs that went out by themselves).

    ``prefix_cache_bytes`` (None = off) enables the prefix KV cache at
    ``prefix_granularity`` token chunks with an LRU byte budget;
    ``prefix_cache=`` injects an existing :class:`PrefixKVCache`
    instead — SHARING one cache between engines is how the
    disaggregated prefill/decode split hands K/V across (see
    ``serving.replica.DisaggregatedEngine``).

    With a cache on, prefill is SINGLE-FLIGHT per prefix chunk: the
    first request needing an uncached chunk claims it as the in-flight
    leader; identical (or prefix-sharing) requests admitted while the
    leader prefills park as followers and re-match once the leader's
    insert lands — a burst of identical cold prompts prefills ONCE.
    Dedup counts surface in ``stats()`` (``prefill_dedup_leaders`` /
    ``prefill_dedup_followers``) and the
    ``generation_prefill_dedup_total{result}`` family.

    ``role="prefill"`` builds a PREFILL-ONLY engine: a request's
    prompt is prefilled and its K/V published through the (mandatory)
    prefix cache, then the future resolves without decoding a single
    token — the producer half of disaggregated serving.  Prefill-role
    requests may pass ``max_new_tokens=0``.

    >>> engine = GenerationScheduler(lm, slots=8)
    >>> fut = engine.submit_async([5, 9, 2], max_new_tokens=16)
    >>> fut.result()        # [Tp + 16] tokens, == lm.generate() solo
    >>> engine.shutdown()   # drains admitted requests to completion
    """

    def __init__(self, model, slots: int = 8,
                 queue_capacity: Optional[int] = None,
                 admission: str = "block",
                 prefill_batch: int = 4, dtype=None,
                 eos_id=None, start: bool = True,
                 prefill_chunk: int = 64,
                 prefill_chunk_budget: int = 1,
                 prefix_cache_bytes: Optional[int] = None,
                 prefix_granularity: int = 32,
                 prefix_cache: Optional[PrefixKVCache] = None,
                 role: str = "mixed"):
        self.pool = SlotPool(model, slots, dtype=dtype,
                             prefill_batch=prefill_batch,
                             ring_margin=int(prefill_chunk))
        self._cache_bytes = self.pool.cache_nbytes_by_kind()
        self.default_eos_id = eos_id
        if role not in ("mixed", "prefill"):
            raise ValueError(
                f"role must be 'mixed' or 'prefill', got {role!r}")
        self.role = role
        if prefill_chunk < 2:
            raise ValueError(
                f"prefill_chunk must be >= 2, got {prefill_chunk}")
        if prefill_chunk_budget < 1:
            raise ValueError(
                f"prefill_chunk_budget must be >= 1, got "
                f"{prefill_chunk_budget}")
        self.prefill_chunk = min(int(prefill_chunk), self.pool.max_len)
        self.prefill_chunk_budget = int(prefill_chunk_budget)
        self._chunk_buckets = self.pool.chunk_widths
        if prefix_cache is not None:
            self._prefix_cache = prefix_cache
        else:
            self._prefix_cache = (
                None if not prefix_cache_bytes
                else PrefixKVCache(int(prefix_cache_bytes),
                                   int(prefix_granularity)))
        if self._prefix_cache is not None and self.pool.has_ring:
            raise ValueError(
                "the prefix cache copies and extracts keys and values by "
                "position, which a ring does not keep: a model with "
                "ring (windowed) cache layers is served with the prefix "
                "cache off")
        if self._prefix_cache is not None and self.pool.has_state:
            raise ValueError(
                "the prefix cache copies and extracts keys and values by "
                "position; a state (a state-space layer's) is of a whole "
                "sequence, has no positions, and is not snapshotted at "
                "chunk boundaries: a model with state cache layers is "
                "served with the prefix cache off")
        if role == "prefill" and self._prefix_cache is None:
            raise ValueError(
                "a prefill-role engine publishes its K/V through the "
                "prefix cache; pass prefix_cache= (shared with the "
                "decode-role engine) or prefix_cache_bytes=")
        cap = queue_capacity if queue_capacity is not None else 8 * slots
        self._queue = BoundedRequestQueue(
            cap, policy=admission, on_shed=self._record_shed)
        self._prompt_buckets = bucket_sizes(self.pool.max_len)
        self._slot_state: List[Optional[_ActiveSlot]] = [None] * slots
        self._prefill_work: Deque[Tuple] = deque()
        # dedup followers parked on another request's in-flight prefill
        # (engine-thread-only, like _slot_state/_prefill_work)
        self._follow_work: List[_ActiveSlot] = []
        # (step handle, n_active, prefill programs dispatched before it,
        # the dispatch's fields of the pass record)
        self._pending: Optional[Tuple] = None
        # a prefill chunk, ``(toks, slot, index)``, prepared in this pass
        # for the pass's decode dispatch to carry (slots decoding).  It
        # never outlives its pass: the next prefill work item, or a pass
        # that ends without a dispatch, sends it alone first
        # (``_send_held_chunk``)
        self._held_chunk: Optional[Tuple] = None
        self._lock = threading.Lock()
        self._outstanding = 0
        self._dedup_leaders = 0
        self._dedup_followers = 0
        self._requests_done = 0
        self._tokens_emitted = 0
        self._decode_steps = 0
        self._prefill_calls = 0
        # always-on measurement of the engine thread.  ``_acc`` and
        # ``_life`` belong to that thread alone: counters gather in the
        # one between folds, phase seconds (``_mark``) in the other as
        # running totals.  ``_eng`` is the published sum that stats()
        # reads; ``_fold_counts`` brings it up to date under the lock,
        # once a pass in ``_emit_step``.
        self._acc: Dict[str, float] = dict.fromkeys(_ENGINE_COUNTERS, 0)
        self._life: Dict[str, float] = dict.fromkeys(_ENGINE_PHASES, 0.0)
        self._eng: Dict[str, float] = dict.fromkeys(
            _ENGINE_PHASES + _ENGINE_COUNTERS, 0)
        self._phase_key = "other"
        self._phase_t = time.perf_counter()
        # read-back return of the previous decode step; None across a
        # pause in which the pool was empty (no step gap spans it)
        self._t_readback: Optional[float] = None
        # the pass log (PASS_RECORD), engine-thread state as ``_acc`` is:
        # ``_seq`` numbers the decode dispatches, and what went out since
        # the last of them waits here for the next to carry it to the gap
        # in which the device ran it
        self._ring = _PassRing()
        self._seq = 0
        self._seq_folded = 0        # newest record in ``_eng``'s sums
        self._alone_since = 0
        self._bucketed_since = 0
        self._chunk_alone = _NO_CHUNK   # (width, index, slot), the last
        # seconds of collector passes, summed by ``_note_gc`` on whichever
        # thread collects
        self._gc_seconds = 0.0
        self._gc_t0 = 0.0
        self._occupancy_sum = 0
        self._ttft_sum = 0.0
        self._ttft_n = 0
        self._ttft_res = _Reservoir(seed=1)
        self._itl_res = _Reservoir(seed=2)
        self._prefix_copies = 0
        self._shed = 0
        self._shutdown = False
        # reliability plane: caller-side cancels land here (lock-
        # guarded; the engine sweep consumes them), a hard kill() lands
        # in _die_exc (the loop checks it every iteration)
        self._cancel_requests: set = set()
        self._die_exc: Optional[Exception] = None
        # tokens/s gauge window (scheduler-thread-only state)
        self._tps_tokens = 0
        self._tps_t0 = time.perf_counter()
        self._thread: Optional[threading.Thread] = None
        if start:
            self.start()

    # -- lifecycle ----------------------------------------------------------

    def start(self) -> "GenerationScheduler":
        if self._thread is not None:
            raise RuntimeError("generation scheduler already started")
        gc.callbacks.append(self._note_gc)      # until ``_run`` ends
        self._thread = threading.Thread(
            target=self._run, name="bigdl-serving-generation", daemon=True)
        self._thread.start()
        return self

    @property
    def alive(self) -> bool:
        return self._thread is not None and self._thread.is_alive()

    def shutdown(self, drain: bool = True,
                 timeout: Optional[float] = 30.0) -> None:
        """Stop admitting.  With ``drain`` (default) every queued
        request is still generated to completion; otherwise queued
        requests fail with ServerClosedError.  Requests already IN a
        slot (decoding OR mid-prefill) always finish — a multi-step
        decode is never abandoned half-emitted."""
        with self._lock:
            first, self._shutdown = not self._shutdown, True
        if first:
            self._queue.close(discard=not drain)
        # after a kill() too: the engine thread is then on its way out,
        # and whoever waits here finds the pool freed
        if self._thread is not None \
                and self._thread is not threading.current_thread():
            self._thread.join(timeout)
            if self._thread.is_alive():
                logger.warning(
                    "generation scheduler did not drain within %ss",
                    timeout)

    def kill(self, exc: Optional[Exception] = None) -> None:
        """Hard death (the chaos ``kill_replica_mode=hard`` fault):
        unlike :meth:`shutdown`, nothing drains — every queued request
        fails with ServerClosedError, every SLOT-RESIDENT request
        (mid-prefill or mid-decode) fails with ``exc`` (default
        :class:`ReplicaDeadError`), and the engine thread exits.  The
        router's failover path depends on exactly this shape: the
        inner future of an interrupted stream fails typed, carrying
        whatever tokens ``on_token`` already delivered."""
        if exc is None:
            exc = ReplicaDeadError("replica killed hard mid-flight")
        with self._lock:
            self._shutdown = True
            self._die_exc = exc
        # wakes a _run loop blocked in _queue.get(); queued requests
        # fail ServerClosedError (they never reached a slot, so a
        # plain re-submit elsewhere is safe)
        self._queue.close(discard=True)

    # -- submission ---------------------------------------------------------

    def submit_async(self, prompt, max_new_tokens: int, eos_id=None,
                     on_token: Optional[Callable[[int], None]] = None,
                     timeout: Optional[float] = None,
                     deadline: Optional[Deadline] = None,
                     trace=None) -> Future:
        """Admit one prompt (1-D int tokens) and return a Future of the
        full ``[Tp + max_new_tokens]`` row — bit-identical to
        ``model.generate(prompt[None], max_new_tokens, eos_id)[0]``.
        ``on_token`` (optional) streams each emitted token from the
        scheduler thread the iteration it is decoded.  ``deadline``
        (optional) rides the request through admit and decode: once
        expired, the engine fails the future with the typed
        :class:`DeadlineExceededError` and frees the slot instead of
        decoding an answer nobody is waiting for.  ``trace`` (optional)
        is the request's :class:`~bigdl_tpu.telemetry.request_trace.
        TraceContext`: the engine then records its queue / prefill /
        decode / emit phases as spans of that trace (the replica layer
        forwards it only when this signature accepts it — feature
        detection, like ``deadline``)."""
        req = GenerationRequest(prompt, max_new_tokens, eos_id=eos_id,
                                on_token=on_token, deadline=deadline,
                                trace=trace)
        err = self._validate(req)
        if err is not None:
            raise err
        # count BEFORE the put: the engine may resolve the future
        # before this thread returns, and the done-callback must never
        # decrement a count that was not yet incremented
        with self._lock:
            self._outstanding += 1
        try:
            self._queue.put(req, timeout=timeout)
        except BaseException:
            with self._lock:
                self._outstanding -= 1
            raise
        req.future.add_done_callback(self._dec_outstanding)
        return req.future

    def _dec_outstanding(self, _fut) -> None:
        with self._lock:
            self._outstanding -= 1

    def admitted_outstanding(self) -> int:
        """Admitted requests not yet terminal (queued, prefilling, or
        decoding) — the number a drain must take to ZERO before the
        replica may be torn down; the router asserts exactly that
        during deploy instead of inferring it from counters."""
        with self._lock:
            return self._outstanding

    def submit(self, prompt, max_new_tokens: int, eos_id=None,
               timeout: Optional[float] = None) -> np.ndarray:
        deadline = (None if timeout is None
                    else time.perf_counter() + timeout)
        fut = self.submit_async(prompt, max_new_tokens, eos_id=eos_id,
                                timeout=timeout)
        remaining = (None if deadline is None
                     else max(deadline - time.perf_counter(), 0.0))
        try:
            return fut.result(remaining)
        except FuturesTimeout:
            # the caller is walking away: without this cancel the
            # abandoned request stays slot-resident and decodes to
            # completion — a slot leak under client-side timeouts
            self.cancel(fut)
            raise

    def cancel(self, fut: Future) -> bool:
        """Best-effort cancel of a submitted request.  Still queued →
        plain ``Future.cancel`` (``_admit``'s RUNNING gate drops it
        without consuming a slot).  Slot-resident → the engine sweep
        frees the slot within one loop iteration and fails the future
        with :class:`RequestCancelledError`.  Returns False only for a
        future that already completed."""
        if fut.cancel():
            return True
        if fut.done():
            return False
        with self._lock:
            self._cancel_requests.add(fut)
        return True

    def _validate(self, req: GenerationRequest) -> Optional[Exception]:
        tp = len(req.prompt)
        if tp < 1:
            return ValueError("empty prompt")
        # a prefill-role request decodes nothing: 0 new tokens is its
        # natural budget (the future resolves after the K/V publish)
        min_new = 0 if self.role == "prefill" else 1
        if req.max_new_tokens < min_new:
            return ValueError(
                f"max_new_tokens must be >= {min_new}, got "
                f"{req.max_new_tokens}")
        if tp + req.max_new_tokens > self.pool.max_len:
            return ValueError(
                f"prompt {tp} + {req.max_new_tokens} new tokens exceeds "
                f"max_len={self.pool.max_len}")
        return None

    # -- observability ------------------------------------------------------

    def queue_depth(self) -> int:
        return len(self._queue)

    def _record_shed(self) -> None:
        with self._lock:
            self._shed += 1

    def prefix_cache_stats(self) -> Optional[Dict[str, object]]:
        return (None if self._prefix_cache is None
                else self._prefix_cache.stats())

    def stats(self) -> Dict[str, object]:
        """One lock-coherent snapshot of the engine counters (always on;
        the unified telemetry families mirror a subset when enabled).
        Queue-to-first-token and inter-token latency are published as
        reservoir p50/p99 beside the mean — the mean hides the
        head-of-line tail that chunked prefill exists to bound."""
        with self._lock:
            steps = self._decode_steps
            ttft_q = self._ttft_res.quantiles()
            itl_q = self._itl_res.quantiles()
            eng = dict(self._eng)
            seq = self._seq_folded
            # seconds between the read-back returns of consecutive decode
            # steps: each second of decoding is counted once, however
            # many steps are in flight
            decode_s = eng["gap_seconds_plain"] + eng["gap_seconds_prefill"]
            out = {
                "requests_done": self._requests_done,
                "tokens_emitted": self._tokens_emitted,
                "decode_steps": steps,
                "prefill_calls": self._prefill_calls,
                "decode_seconds": decode_s,
                # host seconds spent DISPATCHING prefill programs (a jit
                # call returns when the program is enqueued), not the
                # device's time in them
                "prefill_seconds": float(eng["prefill_dispatch"]),
                "slot_occupancy_mean": (self._occupancy_sum / steps
                                        if steps else 0.0),
                "queue_to_first_token_s_mean": (
                    self._ttft_sum / self._ttft_n if self._ttft_n
                    else 0.0),
                "queue_to_first_token_s_p50": ttft_q["p50"],
                "queue_to_first_token_s_p99": ttft_q["p99"],
                "inter_token_s_p50": itl_q["p50"],
                "inter_token_s_p99": itl_q["p99"],
                "prefix_chunks_copied": self._prefix_copies,
                "prefill_chunk": self.prefill_chunk,
                "prefill_chunk_budget": self.prefill_chunk_budget,
                "prefill_dedup_leaders": self._dedup_leaders,
                "prefill_dedup_followers": self._dedup_followers,
                "admitted_outstanding": self._outstanding,
                "role": self.role,
                "shed": self._shed,
                "slots": self.pool.slots,
                "tokens_per_second": (self._tokens_emitted / decode_s
                                      if decode_s else 0.0),
                "iterations": eng["iterations"],
                "engine_phase_seconds": {k: float(eng[k])
                                         for k in _ENGINE_PHASES},
                "decode_dispatches": eng["decode_dispatches"],
                "pipeline_drains": eng["pipeline_drains"],
                "step_gaps": {"plain": eng["gaps_plain"],
                              "prefill": eng["gaps_prefill"]},
                "step_gap_seconds": {
                    "plain": float(eng["gap_seconds_plain"]),
                    "prefill": float(eng["gap_seconds_prefill"])},
                "prefill_positions": eng["prefill_positions"],
                "prefill_prompt_tokens": eng["prefill_prompt_tokens"],
                "admitted": eng["admitted"],
                "queue_wait_seconds": float(eng["queue_wait_seconds"]),
                # cache positions, a full layer, summed over the decode
                # steps dispatched: those the active slots' queries could
                # attend, and those the decode program read to attend
                # them (each length rounded up to the key block where the
                # step reads live blocks only, every slot's whole row
                # where it does not)
                "decode_positions_live": eng["decode_positions_live"],
                "decode_positions_read": eng["decode_positions_read"],
                # rows, a full layer's call of the ragged decode kernel,
                # summed likewise: the active ones, and those of them
                # with an active row before them in the call, whose
                # first key block is fetched behind a step of that row
                # (both zero where the step reads whole rows)
                "decode_rows_live": eng["decode_rows_live"],
                "decode_rows_prefetched": eng["decode_rows_prefetched"],
                # ring places, summed over the ring layers and the decode
                # steps dispatched: those the active slots' queries could
                # attend (a slot's positions, its window at most), and
                # those the decode program read (every slot's ring whole:
                # the window, the chunk's margin and the spare place).
                # Both zero for a model without window layers
                "ring_positions_live": eng["ring_positions_live"],
                "ring_positions_read": eng["ring_positions_read"],
                # the same of the prefill chunks, joint and lone alike,
                # summed over the chunks dispatched: the places of its
                # slot's row that a chunk's last query could attend (its
                # start and its width), and those the chunk's attention
                # read (that rounded up to the chunk's key block where
                # the model's chunk path reads live blocks only, the
                # whole row where it does not)
                "chunk_positions_live": eng["chunk_positions_live"],
                "chunk_positions_read": eng["chunk_positions_read"],
                # chunk programs (not bucketed prefills) that rode a
                # decode step as one joint program, and that went out
                # alone
                "chunks_joint": eng["chunks_joint"],
                "chunks_alone": eng["chunks_alone"],
                # layers whose decode attention reads a full row, for
                # each row written (1, unless layers that keep nothing
                # attend another layer's row), and positions x layers
                # that chunks and bucketed prefills walked
                # (``prefill_positions`` x the model's depth, unless a
                # chunk's rows stop where the caches stop)
                "full_row_readers": self.pool.full_row_readers,
                "chunk_layer_positions": eng["chunk_layer_positions"],
                # what the expert layers did, in decode and prefill
                # programs alike: calls of an expert layer, the
                # token-to-expert pairs they routed, those that landed
                # on an expert held here, the held experts that had a
                # token, and the token-expert rows the experts' product
                # multiplied (held x tokens where every token goes through
                # every held expert; a grouped product's tiles, padding
                # and all), summed over the calls (all zero for a model
                # without expert layers)
                "moe_layer_calls": eng["moe_layer_calls"],
                "moe_pairs_total": eng["moe_pairs_total"],
                "moe_pairs_held": eng["moe_pairs_held"],
                "moe_active_experts": eng["moe_active_experts"],
                "moe_rows_computed": eng["moe_rows_computed"],
                # what the state layers did (all zero for a model without
                # them): calls of a state layer, by decode steps and
                # prefill programs alike; the positions the prefill scans
                # ran over, bucket padding and dead lanes included, and
                # the real ones among them; admissions whose first
                # prefill program started a slot's state from zeros
                "ssm_layer_calls": eng["ssm_layer_calls"],
                "ssm_scan_positions": eng["ssm_scan_positions"],
                "ssm_scan_positions_real": eng["ssm_scan_positions_real"],
                "state_resets": eng["state_resets"],
                "cache_bytes_full": self._cache_bytes["full"],
                "cache_bytes_window": self._cache_bytes["ring"],
                "cache_bytes_state": self._cache_bytes["state"],
                "cache_bytes_latent": self._cache_bytes["latent"],
            }
        # one record a decode step over the newest 16,384 (PASS_RECORD):
        # ``["pass_log"].records(t0, t1)``
        out["pass_log"] = PassLog(self._ring, seq)
        cache = self._prefix_cache
        out["prefix_cache"] = None if cache is None else cache.stats()
        return out

    # -- measurement (engine thread) ----------------------------------------

    def _mark(self, key: str) -> float:
        """The engine thread passes from one phase to the next: the time
        since the last mark goes to the phase that ends here.  One
        ``perf_counter()`` read; returns it so that callers stamp with
        the same instant."""
        now = time.perf_counter()
        self._life[self._phase_key] += now - self._phase_t
        self._phase_key = key
        self._phase_t = now
        return now

    def _note_gc(self, phase: str, info: Dict[str, int]) -> None:
        """``gc.callbacks`` hook: a collector pass stops every thread,
        the engine's among them."""
        if phase == "start":
            self._gc_t0 = time.perf_counter()
        else:
            self._gc_seconds += time.perf_counter() - self._gc_t0

    def _sent_alone(self, width: int, index: int, slot: int) -> None:
        """A chunk program went out by itself."""
        self._acc["chunks_alone"] += 1
        self._alone_since += 1
        self._chunk_alone = (width, index, slot)

    def _fold(self) -> None:
        """Publish what the engine thread gathered, where no decode step
        will do it soon: before it blocks, and when it ends."""
        acc, life = self._acc, self._life
        with self._lock:
            _fold_counts(acc, life, self._eng)

    # -- the engine loop ----------------------------------------------------

    def _run(self) -> None:
        self._phase_key = "other"
        self._phase_t = time.perf_counter()
        try:
            self._loop()
        finally:
            self._mark("other")
            self._fold()
            gc.callbacks.remove(self._note_gc)
            with self._lock:
                killed = self._die_exc is not None
            if killed:
                # a replica killed hard gives the chip back: whoever still
                # holds the engine (a router's table, a load generator)
                # must not keep its weights and caches on the device
                self.pool.free()

    def _loop(self) -> None:
        while True:
            with self._lock:
                exc = self._die_exc
            if exc is not None:
                self._fail_in_flight(exc)
                return              # hard-killed: nothing drains
            arrivals: List[GenerationRequest] = []
            if self._pending is None and not self._prefill_work \
                    and not any(st is not None for st in self._slot_state):
                with tracing.span("serving/idle"):
                    self._mark("idle")
                    self._fold()
                    first = self._queue.get(timeout=None)
                    self._mark("other")
                if first is None:
                    with self._lock:
                        exc = self._die_exc
                    if exc is not None:
                        self._fail_in_flight(exc)
                    return          # closed + drained, nothing in flight
                arrivals.append(first)
            with tracing.span("serving/iteration",
                              n_active=self.pool.n_active(),
                              queued=len(self._queue)):
                self._iteration(arrivals)

    def _iteration(self, arrivals: List[GenerationRequest]) -> None:
        """One pass that has work: sweep, poll the queue, admit, at most
        a budget of prefill programs, one pooled decode step."""
        pool = self.pool
        self._acc["iterations"] += 1
        self._sweep_reliability()
        occupied = sum(1 for st in self._slot_state if st is not None)
        free = pool.slots - occupied - len(arrivals)
        if free > 0:
            arrivals.extend(self._queue.get_nowait_up_to(free))
        try:
            if arrivals or self._prefill_work or self._follow_work:
                # admits, prefix copies and prefill chunks only
                # extend the donated cache chain — they are safe
                # with a decode step in flight (the pipeline is
                # drained lazily by _dispatch_decode when the
                # mirrors must be pushed), so prefill work does not
                # forfeit the async-readback overlap
                if arrivals:
                    with tracing.span("serving/admit",
                                      arrivals=len(arrivals)):
                        self._mark("admit")
                        self._admit(arrivals)
                        self._mark("other")
                self._run_prefill()
            if pool.n_active():
                self._dispatch_decode()
            else:
                self._send_held_chunk()     # no dispatch to carry it
                if self._pending is not None:
                    # the pool emptied with a step in flight: its
                    # read-back overlaps nothing
                    self._acc["pipeline_drains"] += 1
                self._drain_pending()
                self._t_readback = None     # no step gap spans the pause
                if self._follow_work and not self._prefill_work:
                    # every parked follower waits on ANOTHER
                    # engine's in-flight prefill (a shared cache —
                    # a local leader would still be in
                    # _prefill_work): poll, don't spin
                    with tracing.span("serving/idle"):
                        self._mark("idle")
                        time.sleep(0.0005)
                        self._mark("other")
        except Exception as e:  # noqa: BLE001 - engine must survive
            # the BatchScheduler invariant, kept: a failing dispatch
            # fails the affected futures and the loop continues —
            # it never kills the one engine thread and strands
            # RUNNING futures forever (per-site handlers below fail
            # narrowly; this belt catches bookkeeping bugs)
            logger.exception("generation engine iteration failed")
            self._mark("other")
            self._fail_in_flight(e)

    def _fail_in_flight(self, exc: Exception) -> None:
        """Fail every slot-resident request (decoding or mid-prefill)
        with ``exc`` and free its slot; the engine keeps serving later
        arrivals (positions are freshly written before read, so a
        poisoned cache cannot leak into a new occupant)."""
        self._pending = None
        self._held_chunk = None     # its request fails with the others
        self._t_readback = None
        self._prefill_work.clear()
        self._follow_work.clear()   # followers are slot-resident: the
        # loop below fails them with everyone else
        # the failed dispatch may have consumed the donated feed
        # buffers: rebuild from mirrors on the next dispatch
        self.pool.invalidate_feed()
        now = time.perf_counter()
        for slot in range(self.pool.slots):
            st = self._slot_state[slot]
            if st is None:
                continue
            self._release_claims(st)
            if st.req.trace is not None:
                # the aborted phase span: the assembled trace shows how
                # far this replica got before the failure cut it off
                # (the failover replay's salvage is len(st.emitted))
                name = ("request/decode" if st.phase == "decode"
                        else "request/prefill")
                request_trace.record_span(
                    name, st.t_decode if st.t_decode is not None
                    else st.req.t_enqueue, now, ctx=st.req.trace,
                    aborted=type(exc).__name__,
                    new_tokens=len(st.emitted))
            if not st.req.future.done():
                st.req.future.set_exception(exc)
            self._slot_state[slot] = None
            self.pool.release(slot)

    # -- reliability sweep (engine thread) ----------------------------------

    def _sweep_reliability(self) -> None:
        """Free slots whose occupant was cancelled by the caller or ran
        out of deadline budget.  Runs at the top of every engine
        iteration, so an abandoned request costs at most one more
        decode step before its slot is reusable.  ``pool.release`` is a
        plain mirror write (safe in any phase), the credit-epoch masks
        already discard a late in-flight emit for a re-seeded slot, and
        the claim release wakes any dedup followers parked on us."""
        cancels = None
        with self._lock:
            if self._cancel_requests:
                cancels = self._cancel_requests
                self._cancel_requests = set()
        now = time.perf_counter()
        for slot in range(self.pool.slots):
            st = self._slot_state[slot]
            if st is None:
                continue
            exc: Optional[Exception] = None
            if cancels and st.req.future in cancels:
                exc = RequestCancelledError(
                    "caller abandoned the request (client-side "
                    "timeout or explicit cancel)")
            elif st.req.deadline is not None \
                    and st.req.deadline.expired(now):
                stage = "decode" if st.phase == "decode" else "prefill"
                exc = st.req.deadline.error(
                    stage, now,
                    trace_id=(st.req.trace.trace_id
                              if st.req.trace is not None else None))
            if exc is None:
                continue
            self._purge_prefill_work(st)
            self._release_claims(st)
            if st.req.trace is not None:
                request_trace.record_span(
                    "request/decode" if st.phase == "decode"
                    else "request/prefill",
                    st.t_decode if st.t_decode is not None
                    else st.req.t_enqueue, now, ctx=st.req.trace,
                    aborted=type(exc).__name__,
                    new_tokens=len(st.emitted))
            if not st.req.future.done():
                st.req.future.set_exception(exc)
            self._slot_state[slot] = None
            self.pool.release(slot)

    def _purge_prefill_work(self, st: "_ActiveSlot") -> None:
        """Drop every pending prefill work item that references ``st``
        (its chunk entry, its seat in a legacy bucket batch, its
        follower parking) so an evicted request cannot be prefilled
        into a slot that no longer belongs to it."""
        if st in self._follow_work:
            self._follow_work.remove(st)
        if not self._prefill_work:
            return
        kept: Deque[Tuple] = deque()
        for item in self._prefill_work:
            if item[0] == "chunk" and item[1] is st:
                continue
            if item[0] == "legacy":
                sts = [s for s in item[2] if s is not st]
                if not sts:
                    continue
                item = ("legacy", item[1], sts)
            kept.append(item)
        self._prefill_work = kept

    # -- admit + prefill ----------------------------------------------------

    def _admit(self, arrivals: List[GenerationRequest]) -> None:
        pool = self.pool
        ready: List[GenerationRequest] = []
        for req in arrivals:
            err = self._validate(req)   # re-check: queue bypass callers
            if err is None and req.deadline is not None \
                    and req.deadline.expired():
                # budget burned in the queue: typed rejection before a
                # slot (and a prefill) is spent on it
                err = req.deadline.error(
                    "queue",
                    trace_id=(req.trace.trace_id
                              if req.trace is not None else None))
            if err is not None:
                if req.future.set_running_or_notify_cancel():
                    req.future.set_exception(err)
                continue
            # PENDING -> RUNNING here: a future cancelled while queued
            # drops out without consuming a slot, and cancel() can no
            # longer race the final set_result
            if req.future.set_running_or_notify_cancel():
                ready.append(req)
        if not ready:
            return
        free = [i for i in range(pool.slots)
                if self._slot_state[i] is None]
        tel = telemetry.enabled()
        legacy: Dict[int, List[_ActiveSlot]] = {}
        for req in ready:
            slot = free.pop(0)
            eos = (req.eos_id if req.eos_id is not None
                   else self.default_eos_id)
            st = _ActiveSlot(req, eos, slot)
            self._slot_state[slot] = st
            # queue phase ends at slot assignment, not at dequeue:
            # "how long before a slot worked on it", which is what an
            # SLO debugger wants (and what queue_wait_seconds sums)
            t_slot = time.perf_counter()
            self._acc["admitted"] += 1
            self._acc["queue_wait_seconds"] += t_slot - req.t_enqueue
            # the slot's first program starts its state from zeros: the
            # first chunk, the scatter, or a one-token prompt's first step
            self._acc["state_resets"] += pool.has_state
            if req.trace is not None:
                request_trace.record_span(
                    "request/queue", req.t_enqueue, t_slot,
                    ctx=req.trace, slot=slot)
            try:
                st.next_pos = self._copy_cached_prefix(st, tel)
            except Exception as e:  # noqa: BLE001 - fail the request,
                # not the engine: nothing was activated yet
                logger.exception("prefix KV copy failed for slot %d",
                                 slot)
                if not req.future.done():
                    req.future.set_exception(e)
                self._slot_state[slot] = None
                continue
            self._route_after_prefix(st, tel, legacy=legacy)
        for bucket in sorted(legacy):
            sts = legacy[bucket]
            for lo in range(0, len(sts), pool.prefill_batch):
                self._prefill_work.append(
                    ("legacy", bucket, sts[lo:lo + pool.prefill_batch]))

    def _route_after_prefix(self, st: _ActiveSlot, tel: bool,
                            legacy: Optional[Dict] = None) -> None:
        """Route a slot-resident request whose prefix-cache match just
        set ``st.next_pos``: complete (nothing left to prefill), park
        as a dedup follower (another request is prefilling its next
        missing chunk), or schedule the remaining prefill.  ``legacy``
        batches bucket-prefill candidates across one _admit call;
        woken followers pass None and schedule singleton batches."""
        pool = self.pool
        req = st.req
        if st.end_pos - st.next_pos <= 0:
            # the cached prefix (or a 1-token prompt) covers the whole
            # prefill region — straight to decode (or, prefill role,
            # straight to done: everything it would publish is cached)
            if self.role == "prefill":
                self._complete_prefill_role(st, tel)
            else:
                pool.activate(st.slot, int(req.prompt[-1]), st.end_pos)
                st.phase = "decode"
                st.t_decode = time.perf_counter()
            return
        if self._claim_or_park(st, tel):
            return
        st.phase = "prefill"
        if st.next_pos == 0 and len(req.prompt) <= self.prefill_chunk:
            b = pick_bucket(len(req.prompt), self._prompt_buckets)
            if legacy is not None:
                legacy.setdefault(b, []).append(st)
            else:
                self._prefill_work.append(("legacy", b, [st]))
        else:
            self._prefill_work.append(("chunk", st))

    def _claim_or_park(self, st: _ActiveSlot, tel: bool) -> bool:
        """Single-flight prefill dedup.  With a prefix cache on, the
        request either CLAIMS its missing chunk keys (it will prefill
        them — the leader) or PARKS as a follower because its next
        missing chunk is already being prefilled by someone else (in
        this engine or another one sharing the cache).  Returns True
        when parked."""
        cache = self._prefix_cache
        if cache is None or st.end_pos < cache.granularity:
            return False
        region = st.req.prompt[:st.end_pos]
        missing = cache.missing_boundaries(region)
        if not missing:
            return False     # only the sub-granule tail remains
        first_key = cache.boundary_key(region, missing[0])
        owner = cache.prefill_owner(first_key)
        if owner is not None and owner is not st:
            st.phase = "follow"
            self._follow_work.append(st)
            if not st.was_follower:
                # once per REQUEST: a woken follower re-parking on a
                # later chunk's leader is the same deduplicated
                # request, not a second dedup win
                st.was_follower = True
                with self._lock:
                    self._dedup_followers += 1
                if tel:
                    from bigdl_tpu.telemetry import families
                    families.generation_prefill_dedup_total().labels(
                        "follower").inc()
            return True
        keys = [cache.boundary_key(region, i) for i in missing]
        if cache.claim_prefill(keys, st):
            with self._lock:
                self._dedup_leaders += 1
            if tel:
                from bigdl_tpu.telemetry import families
                families.generation_prefill_dedup_total().labels(
                    "leader").inc()
        return False

    def _release_claims(self, st: _ActiveSlot) -> None:
        cache = self._prefix_cache
        if cache is not None:
            cache.release_prefill(st)

    def _sweep_followers(self, tel: bool) -> None:
        """Re-examine parked followers: any whose blocking chunk is now
        cached (the leader's insert landed) or unowned (the leader
        failed — the follower re-claims and leads) re-matches the cache
        and re-routes; the rest stay parked."""
        cache = self._prefix_cache
        if cache is None or not self._follow_work:
            return
        parked, self._follow_work = self._follow_work, []
        for st in parked:
            if self._slot_state[st.slot] is not st:
                continue    # failed/cleared while parked
            region = st.req.prompt[:st.end_pos]
            missing = cache.missing_boundaries(region)
            if missing:
                owner = cache.prefill_owner(
                    cache.boundary_key(region, missing[0]))
                if owner is not None and owner is not st:
                    self._follow_work.append(st)    # still in flight
                    continue
            try:
                st.next_pos = self._copy_cached_prefix(st, tel)
            except Exception as e:  # noqa: BLE001 - fail the request,
                # not the engine (same contract as the admit-time copy)
                logger.exception("prefix KV copy failed for woken "
                                 "follower in slot %d", st.slot)
                if not st.req.future.done():
                    st.req.future.set_exception(e)
                self._slot_state[st.slot] = None
                continue
            self._route_after_prefix(st, tel)

    def _complete_prefill_role(self, st: _ActiveSlot, tel: bool) -> None:
        """Prefill-role terminal: the prompt's K/V is published through
        the shared prefix cache; resolve the future (row = prompt, no
        decoded tokens) and free the slot — releasing it if a batched
        ``prefill_into`` already marked it decode-ready."""
        self._release_claims(st)
        self._finish(st, time.perf_counter(), tel)
        self._slot_state[st.slot] = None
        self.pool.release(st.slot)

    def _copy_cached_prefix(self, st: _ActiveSlot, tel: bool) -> int:
        """Match the prompt's prefill region against the prefix cache
        and copy the longest cached chain into the slot row.  Returns
        the number of positions covered (0 = miss or cache off)."""
        cache = self._prefix_cache
        if cache is None or st.end_pos < cache.granularity:
            return 0
        chain = cache.match(st.req.prompt[:st.end_pos])
        if tel:
            from bigdl_tpu.telemetry import families
            families.generation_prefix_cache_events_total().labels(
                "hit" if chain else "miss").inc()
            if chain:
                families.generation_prefix_cache_bytes_reused_total() \
                    .inc(sum(c.nbytes for c in chain))
        if not chain:
            return 0
        self.pool.kv_copy_into(st.slot, chain)
        with self._lock:
            self._prefix_copies += len(chain)
        return len(chain) * cache.granularity

    def _run_prefill(self) -> None:
        """Execute pending prefill work: at most
        ``prefill_chunk_budget`` program calls while any slot is
        decoding (so a long prompt cannot freeze the token cadence);
        unbounded when nothing is decoding (nobody is starved by
        finishing prefill fast)."""
        pool = self.pool
        limit = (self.prefill_chunk_budget if pool.n_active() else None)
        done = 0
        tel = telemetry.enabled()
        if self._follow_work:
            self._sweep_followers(tel)
        while self._prefill_work and (limit is None or done < limit):
            # what comes next takes the caches as the chunk leaves them
            self._send_held_chunk()
            item = self._prefill_work[0]
            if item[0] == "legacy":
                self._prefill_work.popleft()
                self._legacy_prefill(item[1], item[2], tel)
            else:
                st = item[1]
                self._chunk_prefill_step(st, tel)
                if st.phase == "decode" \
                        or self._slot_state[st.slot] is not st:
                    self._prefill_work.popleft()
            done += 1

    def _legacy_prefill(self, bucket: int, sts: List[_ActiveSlot],
                        tel: bool) -> None:
        """The original batched bucket prefill (whole prompt, one
        program call, up to ``prefill_batch`` requests amortized)."""
        pool = self.pool
        try:
            # the span and the seconds cover the DISPATCH of the prefill
            # and scatter programs: a jit call returns once its program
            # is enqueued, and the device runs it later
            with tracing.span("serving/prefill", bucket=bucket,
                              n_real=len(sts)):
                t0 = self._mark("prefill_dispatch")
                pool.prefill_into([st.req.prompt for st in sts],
                                  [st.slot for st in sts], bucket)
                t1 = self._mark("other")
        except Exception as e:  # noqa: BLE001 - fail the chunk, not the
            # engine: the slots were never activated
            self._mark("other")
            logger.exception("prefill of bucket %d failed", bucket)
            for st in sts:
                self._release_claims(st)
                if not st.req.future.done():
                    st.req.future.set_exception(e)
                self._slot_state[st.slot] = None
            self._sweep_followers(tel)  # a parked follower re-claims
            return
        dt = t1 - t0
        self._bucketed_since += 1
        if bucket > 1:
            # every lane of the fixed-width batch is computed, dead
            # lanes and bucket padding included
            self._acc["prefill_positions"] += \
                pool.prefill_batch * (bucket - 1)
            self._acc["chunk_layer_positions"] += \
                pool.chunk_layers * pool.prefill_batch * (bucket - 1)
            self._acc["ssm_layer_calls"] += pool.state_layers
            self._acc["ssm_scan_positions"] += \
                pool.state_layers * pool.prefill_batch * (bucket - 1)
        for st in sts:
            self._acc["prefill_prompt_tokens"] += st.end_pos - st.next_pos
            self._acc["ssm_scan_positions_real"] += \
                pool.state_layers * (st.end_pos - st.next_pos)
            st.next_pos = st.end_pos
            self._store_prefix(st)
            self._release_claims(st)
            if st.req.trace is not None:
                request_trace.record_span(
                    "request/prefill", t0, t1, ctx=st.req.trace,
                    bucket=bucket, batched=len(sts))
            if self.role == "prefill":
                self._complete_prefill_role(st, tel)
            else:
                st.phase = "decode"
                st.t_decode = t1
        self._sweep_followers(tel)
        with self._lock:
            self._prefill_calls += 1
        if tel:
            from bigdl_tpu.telemetry import families
            families.generation_phase_seconds().labels(
                "prefill").observe(dt)

    def _chunk_prefill_step(self, st: _ActiveSlot, tel: bool) -> None:
        """One fixed-width prefill chunk for ``st``.  Full chunks run at
        ``prefill_chunk``; the final partial chunk picks the smallest
        bucket covering the remainder and SUFFIX-ALIGNS it (recomputing
        a little overlap, which rewrites identical K/V) so it never
        writes past the prefill region and carries no padded lanes.  On a
        model that keeps a state it is padded at its end instead."""
        pool = self.pool
        p = st.req.prompt
        end = st.end_pos
        r = end - st.next_pos
        if r >= self.prefill_chunk:
            w, s = self.prefill_chunk, st.next_pos
            toks = p[s:s + w]
        else:
            w = pick_bucket(r, self._chunk_buckets)
            if pool.has_state:
                # a state holds every position it was handed, once: the
                # last chunk starts where the one before ended and pads
                # its tail (or, where that would pass the row's end,
                # takes the widest bucket that is all prompt)
                s = st.next_pos
                if s + w > pool.max_len:
                    w = 1 << (r.bit_length() - 1)
            else:
                s = max(end - w, 0)
            toks = p[s:min(s + w, end)]
            if len(toks) < w:
                # short only for a first-and-only chunk (s == 0) or the
                # last chunk of a state model: pad the tail; those
                # positions are re-written by decode before they are ever
                # attended, and padding advances no state
                toks = np.concatenate(
                    [toks, np.zeros(w - len(toks), np.int32)])
        # beside decoding slots the pass's decode step carries the chunk
        # (_dispatch_decode).  With a prefix cache a prompt's last chunk
        # goes out alone: its keys are extracted right after it
        hold = pool.n_active() > 0 and not (
            self._prefix_cache is not None and s + w >= end)
        try:
            with tracing.span("serving/prefill", chunk=w, index=s,
                              slot=st.slot):
                t0 = self._mark("prefill_dispatch")
                if hold:
                    self._held_chunk = (toks, st.slot, s)
                else:
                    pool.chunk_prefill_into(toks, st.slot, s)
                    self._sent_alone(w, s, st.slot)
                t1 = self._mark("other")
        except Exception as e:  # noqa: BLE001 - fail this request only
            self._mark("other")
            logger.exception("chunked prefill failed for slot %d",
                             st.slot)
            self._release_claims(st)
            if not st.req.future.done():
                st.req.future.set_exception(e)
            self._slot_state[st.slot] = None
            self._sweep_followers(tel)  # a parked follower re-claims
            return
        dt = t1 - t0
        if st.req.trace is not None:
            # one child span PER CHUNK: a slow prefill shows up in the
            # assembled trace as which chunk stalled, not one blur
            request_trace.record_span(
                "request/prefill", t0, t1, ctx=st.req.trace,
                chunk=w, index=s)
        new_pos = end if s + w >= end else s + w
        self._acc["prefill_positions"] += w
        self._acc["chunk_layer_positions"] += pool.chunk_layers * w
        block = pool.chunk_key_block
        self._acc["chunk_positions_live"] += s + w
        self._acc["chunk_positions_read"] += \
            -(-(s + w) // block) * block if block else pool.max_len
        self._acc["prefill_prompt_tokens"] += new_pos - st.next_pos
        self._acc["ssm_layer_calls"] += pool.state_layers
        self._acc["ssm_scan_positions"] += pool.state_layers * w
        self._acc["ssm_scan_positions_real"] += \
            pool.state_layers * (new_pos - st.next_pos)
        st.next_pos = new_pos
        with self._lock:
            self._prefill_calls += 1
        if tel:
            from bigdl_tpu.telemetry import families
            families.generation_phase_seconds().labels(
                "prefill").observe(dt)
        if st.next_pos >= end:
            self._store_prefix(st)
            self._release_claims(st)
            if self.role == "prefill":
                self._complete_prefill_role(st, tel)
            else:
                pool.activate(st.slot, int(p[-1]), end)
                st.phase = "decode"
                st.t_decode = t1
            self._sweep_followers(tel)

    def _store_prefix(self, st: _ActiveSlot) -> None:
        """After a prompt's prefill completed, extract and cache the
        granularity-aligned chunks not yet in the prefix cache (the
        prefill region stays intact in the slot row for the request's
        whole residency, so extraction is always safe here).  The store
        is BEST-EFFORT: the request already prefilled successfully, so
        a failure here (an extract dispatch under memory pressure, say)
        must cost only the cache entry — never this request, and never
        the co-resident futures via the engine's belt handler."""
        cache = self._prefix_cache
        if cache is None:
            return
        try:
            region = st.req.prompt[:st.end_pos]
            missing = cache.missing_boundaries(region)
            if not missing:
                return
            g = cache.granularity
            for i in missing:
                layers, pad = self.pool.kv_extract(st.slot,
                                                   (i - 1) * g, g)
                cache.insert(region, i, layers, pad)
            if telemetry.enabled():
                from bigdl_tpu.telemetry import families
                families.generation_prefix_cache_resident_bytes().set(
                    cache.resident_bytes())
        except Exception:   # noqa: BLE001 - cache population is an
            # optimization; the prefilled request proceeds regardless
            logger.exception("prefix-cache store failed for slot %d "
                             "(entry skipped)", st.slot)

    # -- decode (pipelined) -------------------------------------------------

    def _drain_pending(self) -> None:
        prev, self._pending = self._pending, None
        if prev is not None:
            self._emit_step(prev)

    def _send_held_chunk(self) -> None:
        """The chunk held for the pass's decode dispatch goes out alone,
        now: something else is about to take the caches, or no dispatch
        follows."""
        chunk, self._held_chunk = self._held_chunk, None
        if chunk is not None:
            self._mark("prefill_dispatch")
            self.pool.chunk_prefill_into(*chunk)
            self._mark("other")
            toks, slot, index = chunk
            self._sent_alone(len(toks), index, slot)

    def _dispatch_decode(self) -> None:
        pool = self.pool
        prev = self._pending
        drained = False
        if prev is not None and pool.dirty:
            # membership changed since that step was dispatched (an EOS
            # leave) — fold its emit into the mirrors BEFORE the
            # refreshed mirrors are pushed to the device
            self._pending = None
            self._acc["pipeline_drains"] += 1
            self._emit_step(prev)
            prev = None
            drained = True
            if pool.n_active() == 0:
                self._send_held_chunk()
                return
        n_active = pool.n_active()
        chunk, self._held_chunk = self._held_chunk, None
        # prefill programs dispatched since the previous decode dispatch
        # run on the device between that step and this one: their count,
        # and what the pass record says of them, ride this step's handle
        # to the gap its read-back closes
        joint = chunk is not None
        alone, bucketed = self._alone_since, self._bucketed_since
        of_chunk = (len(chunk[0]), chunk[2], chunk[1]) if joint \
            else self._chunk_alone
        after_prefill = joint + alone + bucketed
        seq = self._seq + 1
        try:
            with tracing.span("serving/decode_dispatch", seq=seq,
                              n_active=n_active,
                              after_prefill=after_prefill, drained=drained):
                self._mark("decode_dispatch")
                emit = pool.decode_dispatch(chunk)
                self._mark("other")
        except Exception as e:  # noqa: BLE001 - fail the residents,
            # keep the engine thread alive for later arrivals
            self._mark("other")
            logger.exception("pooled decode step failed")
            self._fail_in_flight(e)
            return
        self._seq = seq
        self._alone_since = self._bucketed_since = 0
        self._chunk_alone = _NO_CHUNK
        self._acc["decode_dispatches"] += 1
        self._acc["decode_positions_live"] += emit.positions[0]
        self._acc["decode_positions_read"] += emit.positions[1]
        self._acc["decode_rows_live"] += emit.rows[0]
        self._acc["decode_rows_prefetched"] += emit.rows[1]
        self._acc["ring_positions_live"] += emit.rings[0]
        self._acc["ring_positions_read"] += emit.rings[1]
        self._acc["ssm_layer_calls"] += pool.state_layers
        self._acc["chunks_joint"] += joint
        self._pending = (emit, n_active, after_prefill, (
            seq, joint, alone, bucketed, *of_chunk, drained, n_active,
            *emit.positions))
        if prev is not None:
            # THE async-readback overlap: step N's host-side emit work
            # (int conversion, callbacks, EOS checks) runs while step
            # N+1 executes on device
            self._emit_step(prev)

    def _emit_step(self, pending: Tuple) -> None:
        pool = self.pool
        emit, n_active, after_prefill, sent = pending
        with tracing.span("serving/readback", seq=sent[0]):
            self._mark("readback_wait")
            out, credit = pool.read_emit_masked(emit)
            now = self._mark("emit")
        # the pass record's share of this instant: the running totals of
        # the phases (both ends of a record's share are ``_mark()``'s own
        # ``now``), of the collector's seconds and of the traces
        running = (*self._life.values(), self._gc_seconds, pool.traces)
        # the step gap: from the previous step's read-back return to this
        # one's.  Consecutive gaps tile the time the pool spent decoding,
        # whatever is in flight; dispatch-to-read-back intervals overlap
        # their neighbours under the one-deep pipeline.
        dt = None if self._t_readback is None else now - self._t_readback
        self._t_readback = now
        if len(emit.routing):
            for key, n in zip(MOE_COUNTERS, emit.routing):
                self._acc[key] += int(n)
        if dt is not None:
            kind = "prefill" if after_prefill else "plain"
            self._acc["gaps_" + kind] += 1
            self._acc["gap_seconds_" + kind] += dt
        # who emits, and who ends with this token: decided before the
        # span opens, because a profiler annotation takes its arguments
        # when it starts
        plan: List[tuple] = []
        n_finished = 0
        for slot in range(pool.slots):
            st = self._slot_state[slot]
            if st is None or st.phase != "decode" or not credit[slot]:
                continue
            tok = int(out[slot])
            if tok == 0:
                continue    # slot was not active at this dispatch
            done = (st.eos_id is not None and tok == st.eos_id) \
                or len(st.emitted) + 1 >= st.req.max_new_tokens
            n_finished += done
            plan.append((slot, st, tok, done))
        self._ring.append(sent + (
            now, np.nan if dt is None else dt, len(plan)) + running)
        with tracing.span("serving/emit", emitted=len(plan),
                          finished=n_finished):
            self._emit_tokens(plan, n_active, dt, now, sent[0])
        self._mark("other")

    def _emit_tokens(self, plan: List[tuple], n_active: int,
                     dt: Optional[float], now: float, seq: int) -> None:
        """Host work of one read-back step: callbacks, bookkeeping, the
        requests that end with this token."""
        pool = self.pool
        # (gap_s, trace-or-None) pairs: the trace rides along so the
        # inter-token histogram can attach an exemplar and the tail
        # sampler can watermark the causing request, not just the value
        gaps: List[tuple] = []
        for _slot, st, tok, _done in plan:
            st.emitted.append(tok)
            if st.t_first is None:
                st.t_first = now
            else:
                gaps.append((now - st.t_last, st.req.trace))
            st.t_last = now
            if st.req.on_token is not None:
                try:
                    st.req.on_token(tok)
                except Exception:   # noqa: BLE001 - user callback
                    logger.exception("on_token callback failed")
        tel = telemetry.enabled()
        acc, life = self._acc, self._life
        # counters BEFORE any future resolves: a waiter whose result()
        # just returned may immediately read stats(), which must
        # already include the iteration that finished it
        with self._lock:
            self._decode_steps += 1
            self._tokens_emitted += len(plan)
            self._occupancy_sum += n_active
            for g, _ in gaps:
                self._itl_res.add(g)
            _fold_counts(acc, life, self._eng)
            self._seq_folded = seq
        for slot, st, _tok, done in plan:
            if done:
                self._finish(st, now, tel)
                self._slot_state[slot] = None
                pool.release(slot)
        if tel:
            self._publish_telemetry(dt, n_active, len(plan), gaps, now)

    def _finish(self, st: _ActiveSlot, now: float, tel: bool) -> None:
        req = st.req
        row = np.zeros((len(req.prompt) + req.max_new_tokens,), np.int32)
        row[:len(req.prompt)] = req.prompt
        row[len(req.prompt):len(req.prompt) + len(st.emitted)] = st.emitted
        ttft = ((st.t_first if st.t_first is not None else now)
                - req.t_enqueue)
        acc, life = self._acc, self._life
        with self._lock:
            # before set_result, same reason as the step counters (a
            # prefill-role engine never emits: this is its only fold
            # between idle periods)
            self._requests_done += 1
            self._ttft_sum += ttft
            self._ttft_n += 1
            self._ttft_res.add(ttft)
            _fold_counts(acc, life, self._eng)
        if req.trace is not None:
            # BEFORE set_result: the router's terminal callback files
            # the trace the moment the future resolves, and these
            # phase spans belong in it, not as late arrivals
            request_trace.record_span(
                "request/decode",
                st.t_decode if st.t_decode is not None
                else req.t_enqueue,
                now, ctx=req.trace, new_tokens=len(st.emitted))
            if st.t_first is not None and st.t_last is not None:
                # retroactive: the emit span covers first->last token
                request_trace.record_span(
                    "request/emit", st.t_first, st.t_last,
                    ctx=req.trace, tokens=len(st.emitted))
            request_trace.observe_ttft(req.trace, ttft)
        # positions after EOS stay 0 — exactly generate()'s padding
        req.future.set_result(row)
        if tel:
            from bigdl_tpu.telemetry import families
            families.generation_queue_to_first_token_seconds().observe(
                ttft, exemplar=(req.trace.trace_id
                                if req.trace is not None else None))
            tracing.record_span("serving/generate", req.t_enqueue, now,
                                prompt_len=len(req.prompt),
                                new_tokens=len(st.emitted))

    def _publish_telemetry(self, dt: Optional[float], n_active: int,
                           emitted: int, gaps: List[tuple],
                           now: float) -> None:
        from bigdl_tpu.telemetry import families
        if dt is not None:
            families.generation_phase_seconds().labels(
                "decode").observe(dt)
        families.generation_slot_occupancy().set(n_active / self.pool.slots)
        itl = families.generation_inter_token_seconds()
        for g, ctx in gaps:
            # exemplar + watermark: a breached inter-token histogram
            # bucket names the trace that put it there, and the tail
            # sampler retains that trace even if the bulk ring drops it
            itl.observe(g, exemplar=(ctx.trace_id if ctx is not None
                                     else None))
            request_trace.observe_inter_token(ctx, g)
        # tokens/s over a rolling ~0.5 s window (scheduler-thread-only
        # counters; the gauge is the published aggregate)
        self._tps_tokens += emitted
        elapsed = now - self._tps_t0
        if elapsed >= 0.5:
            families.generation_tokens_per_second().set(
                self._tps_tokens / elapsed)
            self._tps_tokens = 0
            self._tps_t0 = now
