"""Prefix KV cache: token-id-keyed reuse of prefill K/V across
requests.

Prefill is the dominant cost of a continuous-batching round of mixed
traffic, and production prompt streams repeat: the same system prompt /
few-shot preamble heads most requests.  Recomputing its
K/V per request is pure waste, because the K/V of position ``t`` depends
only on tokens ``0..t`` (causal attention) — two prompts sharing a
prefix share that prefix's K/V bit-for-bit.  This is the static-shape
cousin of SGLang's RadixAttention prefix reuse.

Layout: the cache stores prefill K/V at a fixed chunk **granularity**
``G`` (a power-of-two width from ``batching.bucket_sizes``, so every
cached tensor has the same static shape and the copy/extract programs
compile exactly once).  An entry is keyed by the FULL token prefix up to
and including its chunk — ``key(i) = tokens[:(i+1)·G].tobytes()`` — not
by the chunk's own tokens, because K/V are position- and
history-dependent.  A lookup walks chunk boundaries ``G, 2G, 3G, ...``
and returns the longest contiguous chain of cached chunks; the engine
copies the chain into the admitted request's slot row and chunk-prefills
only the remaining suffix.

Budgeting is LRU by bytes: entries hold device arrays (copying a hit is
a device-side scatter, never a host round-trip), so the budget bounds
accelerator memory.  Eviction only drops the *cache's* reference —
chains already matched by an in-flight admit keep their arrays alive, so
eviction under byte pressure mid-stream is safe by construction.

Thread-safety: every mutation and read takes ``self._lock``; within one
engine the scheduler thread is the only writer, but the cache may be
SHARED between engines (the disaggregated prefill/decode split hands
K/V from a prefill-role engine to a decode-role engine through it) and
``stats()`` is served to arbitrary threads (``/statusz``, telemetry
collectors).

Single-flight prefill: a burst of identical cold prompts would prefill
the same chunks once per request.  The :meth:`claim_prefill` /
:meth:`prefill_owner` / :meth:`release_prefill` registry lets the first
requester claim the missing chunk keys as the in-flight LEADER; later
requests seeing an owned key park as FOLLOWERS until the leader's
insert lands (or its claim is released on failure), then re-match and
hit.  The registry is keyed by the same full-prefix chunk keys as the
entries, so it deduplicates across engines sharing one cache too.
"""

from __future__ import annotations

import threading
from collections import OrderedDict
from typing import Dict, List, Optional, Sequence

import numpy as np

__all__ = ["PrefixKVCache", "PrefixChunk"]


class PrefixChunk:
    """One cached chunk: per-layer K/V (``[heads, G, head_dim]`` device
    arrays) + padding flags (``[G]``) for prompt positions
    ``[index, index+G)``, valid only after the exact token prefix the
    key encodes."""

    __slots__ = ("key", "index", "layers", "pad", "nbytes")

    def __init__(self, key: bytes, index: int, layers: Sequence[Dict],
                 pad) -> None:
        self.key = key
        self.index = int(index)
        self.layers = list(layers)
        self.pad = pad
        # sizes come from metadata only: pad is usually a just-
        # dispatched device array, and materializing it here would
        # block the engine thread on the extract for every insert
        n = int(np.prod(pad.shape))              # pad bytes (bool = 1)
        for lay in self.layers:
            for arr in lay.values():
                n += int(arr.size) * arr.dtype.itemsize
        self.nbytes = n


class PrefixKVCache:
    """LRU byte-budgeted map from token-prefix keys to
    :class:`PrefixChunk` entries at fixed granularity ``G``."""

    def __init__(self, byte_budget: int, granularity: int) -> None:
        if byte_budget < 1:
            raise ValueError(
                f"byte_budget must be >= 1, got {byte_budget} (pass "
                f"prefix_cache_bytes=None to disable caching instead)")
        if granularity < 2 or granularity & (granularity - 1):
            raise ValueError(
                f"granularity must be a power of two >= 2 (a bucket "
                f"width), got {granularity}")
        self.byte_budget = int(byte_budget)
        self.granularity = int(granularity)
        self._lock = threading.Lock()
        self._entries: "OrderedDict[bytes, PrefixChunk]" = OrderedDict()
        self._inflight: Dict[bytes, object] = {}
        self._bytes = 0
        self._lookups = 0
        self._hits = 0
        self._misses = 0
        self._chunks_hit = 0
        self._bytes_reused = 0
        self._inserts = 0
        self._evictions = 0

    # ---- lookup ----------------------------------------------------------

    def match(self, tokens: np.ndarray) -> List[PrefixChunk]:
        """Longest chain of cached chunks covering a prefix of
        ``tokens`` (the prompt's prefill region).  Returns ``[]`` on a
        miss; chain ``c`` covers positions ``[0, len(c)*G)``.  Prompts
        shorter than one granule are uncacheable and count as neither
        hit nor miss."""
        toks = np.ascontiguousarray(np.asarray(tokens, np.int32))
        g = self.granularity
        m = len(toks) // g
        chain: List[PrefixChunk] = []
        with self._lock:
            if m < 1:
                return chain
            self._lookups += 1
            for i in range(1, m + 1):
                entry = self._entries.get(toks[:i * g].tobytes())
                if entry is None:
                    break
                self._entries.move_to_end(entry.key)
                chain.append(entry)
            if chain:
                self._hits += 1
                self._chunks_hit += len(chain)
                self._bytes_reused += sum(c.nbytes for c in chain)
            else:
                self._misses += 1
        return chain

    def missing_boundaries(self, tokens: np.ndarray) -> List[int]:
        """Chunk indices ``i`` (1-based) whose prefix ``tokens[:i*G]``
        is not yet cached — what the engine should extract-and-insert
        after prefilling this prompt."""
        toks = np.ascontiguousarray(np.asarray(tokens, np.int32))
        g = self.granularity
        with self._lock:
            return [i for i in range(1, len(toks) // g + 1)
                    if toks[:i * g].tobytes() not in self._entries]

    def boundary_key(self, tokens: np.ndarray, chunk_index: int) -> bytes:
        """The cache key of chunk ``chunk_index`` (1-based) of
        ``tokens`` — the full token prefix up to and including it."""
        toks = np.ascontiguousarray(np.asarray(tokens, np.int32))
        return toks[:chunk_index * self.granularity].tobytes()

    # ---- single-flight prefill (in-flight dedup) -------------------------

    def claim_prefill(self, keys: Sequence[bytes], owner) -> List[bytes]:
        """Register ``owner`` as the in-flight prefiller of every key in
        ``keys`` that is neither cached nor already claimed; returns the
        keys actually claimed.  ``owner`` is an opaque identity token —
        claims are compared by ``is`` and released all at once via
        :meth:`release_prefill`."""
        claimed: List[bytes] = []
        with self._lock:
            for k in keys:
                if k in self._entries or k in self._inflight:
                    continue
                self._inflight[k] = owner
                claimed.append(k)
        return claimed

    def prefill_owner(self, key: bytes) -> Optional[object]:
        """The in-flight owner of ``key`` (None when nobody is
        prefilling it) — a request whose next missing chunk has an
        owner other than itself parks as a dedup follower."""
        with self._lock:
            return self._inflight.get(key)

    def release_prefill(self, owner) -> None:
        """Drop every in-flight claim held by ``owner`` — called when
        the leader's insert landed (followers now hit) or its prefill
        failed (a follower re-claims and becomes the new leader).
        Safe to call when ``owner`` holds nothing."""
        with self._lock:
            for k in [k for k, o in self._inflight.items()
                      if o is owner]:
                del self._inflight[k]

    # ---- insertion / eviction -------------------------------------------

    def insert(self, tokens: np.ndarray, chunk_index: int,
               layers: Sequence[Dict], pad) -> Optional[PrefixChunk]:
        """Cache the K/V of chunk ``chunk_index`` (1-based: positions
        ``[(i-1)*G, i*G)``) of ``tokens``.  A chunk larger than the
        whole budget is refused (it could never be kept)."""
        toks = np.ascontiguousarray(np.asarray(tokens, np.int32))
        g = self.granularity
        key = toks[:chunk_index * g].tobytes()
        entry = PrefixChunk(key, (chunk_index - 1) * g, layers, pad)
        if entry.nbytes > self.byte_budget:
            return None
        with self._lock:
            # the chunk is resident from here on: any in-flight claim
            # on it is moot, and followers must see owner None
            self._inflight.pop(key, None)
            old = self._entries.pop(key, None)
            if old is not None:
                self._bytes -= old.nbytes
            self._entries[key] = entry
            self._bytes += entry.nbytes
            self._inserts += 1
            while self._bytes > self.byte_budget and self._entries:
                _, victim = self._entries.popitem(last=False)
                self._bytes -= victim.nbytes
                self._evictions += 1
        return entry

    # ---- observability ---------------------------------------------------

    def resident_bytes(self) -> int:
        with self._lock:
            return self._bytes

    def __len__(self) -> int:
        with self._lock:
            return len(self._entries)

    def stats(self) -> Dict[str, object]:
        with self._lock:
            return {
                "granularity": self.granularity,
                "byte_budget": self.byte_budget,
                "resident_bytes": self._bytes,
                "entries": len(self._entries),
                "lookups": self._lookups,
                "hits": self._hits,
                "misses": self._misses,
                "hit_rate": (self._hits / self._lookups
                             if self._lookups else 0.0),
                "chunks_hit": self._chunks_hit,
                "bytes_reused": self._bytes_reused,
                "inserts": self._inserts,
                "evictions": self._evictions,
                "inflight_prefills": len(self._inflight),
            }
