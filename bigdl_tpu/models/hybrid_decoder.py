"""Decoder-only language model over a list of blocks that a model
family builds for itself: window and full grouped-query attention layers,
layers whose attention runs in parallel with a state-space mixer,
latent-attention layers, layers that are a state-space mixer or a gated
short convolution alone, layers that keep nothing and read what an
earlier layer made in the same pass (its row of keys and values, or its
mixer's scan output), dense gated feed-forward layers, and a held share
of sigmoid-routed gated experts (with or without a shared expert).

A family is a factory (``mimo_v2``, ``falcon_h1``, ``sarvam_mla``,
``phi4_flash``, ``lfm2_moe``, ``afmoe``; each one's docstring has its
walk): it
reads its ``config.json``, refuses what is not built, makes each layer's
block (:class:`HybridBlock` over an attention layer, :class:`ParallelBlock`,
:class:`MixerBlock`, :class:`CrossBlock`, :class:`MemoryBlock`) and hands
the list to :class:`HybridDecoder`, which asks a block what it keeps and
never what it is.  **Not every layer has ``cache["self"]``**: a
mixer-only layer keeps a state and no row, a unit and a cross layer keep
nothing, and a chunk's rows stop where the caches stop.

It keeps the repo's conventions (``TransformerLM``): token ids are
1-based with 0 as padding, and generation emits ``argmax + 1`` (the
untied head has exactly ``vocab_size`` rows: none is untrained).  It has
the incremental API the serving slot pool drives — ``init_cache``,
``decode_step`` with a position per row, ``prefill_kv``,
``prefill_chunk``, ``max_len``, ``_mask_untrained_logit`` — and declares
each layer's caches (:meth:`cache_layers`): a ``full`` row of ``max_len``
positions, a ``ring`` of the window or a ``latent`` row (``max_len``
positions of one head: the rotary key and the compressed row), and
beside the row of a parallel layer a ``state`` (no positions: the
mixer's recurrence and the last inputs of its convolution).  A model
with expert layers also returns what they did (``routing``, int32
``[ROUTING]``: ``HeldExperts.forward``) from every pass the pool runs.

The residual stream, the norms, the scores and the router are float32;
the matrix products take their operands in the weights' dtype.
"""

from __future__ import annotations

from typing import Any, Dict, Optional, Sequence, Tuple

import jax
import jax.numpy as jnp

from bigdl_tpu.core.module import Module, ModuleList, Parameter
from bigdl_tpu.nn.attention import GroupedQueryAttention
from bigdl_tpu.nn.differential_attention import DifferentialAttention
from bigdl_tpu.nn.latent_attention import LatentAttention
from bigdl_tpu.nn.linear import Linear, LookupTable
from bigdl_tpu.nn.moe import ROUTING, HeldExperts
from bigdl_tpu.nn.short_conv import GatedShortConv
from bigdl_tpu.nn.ssm import Mamba1Mixer, Mamba2Mixer
from bigdl_tpu.ops import cache_kernels

__all__ = ["HybridDecoder", "mimo_v2", "falcon_h1", "sarvam_mla",
           "phi4_flash", "lfm2_moe", "afmoe"]


def _product(x, layer: Linear):
    """``x [..., in] @ W.T`` with float32 out of the product."""
    return jnp.einsum("...i,oi->...o", x, layer.weight,
                      preferred_element_type=jnp.float32)


class RMSNorm(Module):
    """``x / rms(x) * gain`` in float32."""

    def __init__(self, hidden_size: int, eps: float = 1e-5):
        super().__init__()
        self.eps = eps
        self.weight = Parameter(jnp.ones(hidden_size))

    def forward(self, x):
        x = x.astype(jnp.float32)
        ms = jnp.mean(jnp.square(x), axis=-1, keepdims=True)
        return x * jax.lax.rsqrt(ms + self.eps) \
            * self.weight.astype(jnp.float32)


class LayerNorm(Module):
    """``(x - mean) / sqrt(var + eps) * gain + bias`` in float32."""

    def __init__(self, hidden_size: int, eps: float = 1e-5):
        super().__init__()
        self.eps = eps
        self.weight = Parameter(jnp.ones(hidden_size))
        self.bias = Parameter(jnp.zeros(hidden_size))

    def forward(self, x):
        x = x.astype(jnp.float32)
        x = x - jnp.mean(x, axis=-1, keepdims=True)
        var = jnp.mean(jnp.square(x), axis=-1, keepdims=True)
        return x * jax.lax.rsqrt(var + self.eps) \
            * self.weight.astype(jnp.float32) + self.bias.astype(jnp.float32)


class GatedFFN(Module):
    """``W_d(silu(W_g x * m_gate) * W_u x) * m_down``, no bias; float32
    out.  The two multipliers are constants (1: the plain gated layer)."""

    def __init__(self, hidden_size: int, filter_size: int,
                 gate_multiplier: float = 1.0, down_multiplier: float = 1.0):
        super().__init__()
        self.gate_multiplier = float(gate_multiplier)
        self.down_multiplier = float(down_multiplier)
        self.gate = Linear(hidden_size, filter_size, with_bias=False)
        self.up = Linear(hidden_size, filter_size, with_bias=False)
        self.down = Linear(filter_size, hidden_size, with_bias=False)

    def forward(self, x):
        x = x.astype(self.gate.weight.dtype)
        g = _product(x, self.gate)
        if self.gate_multiplier != 1.0:
            g = g * self.gate_multiplier
        a = jax.nn.silu(g) * _product(x, self.up)
        y = _product(a.astype(x.dtype), self.down)
        return y if self.down_multiplier == 1.0 else y * self.down_multiplier


def _fresh_state(state, fresh):
    """``state`` (a mixer's, a few rows) as zeros where ``fresh`` (a
    scalar) says that its sequence starts here."""
    return jax.tree_util.tree_map(
        lambda leaf: jnp.where(fresh, jnp.zeros_like(leaf), leaf), state)


def _run_mixer(mixer, u, index, pooled, slot, active, valid):
    """A state-space mixer on ``u`` over a layer's pooled state (None: the
    whole sequence from zeros) -> what the mixer returns, its state put
    back into the pool's.  The state has no positions: a chunk (scalar
    ``index``) reads row ``slot``'s state, or starts from zeros when
    ``index`` is 0 (whoever held the row before is forgotten), and writes
    back the state after its last valid token; a per-row step leaves the
    state of a row that is not ``active`` as it was, and starts a row at
    position 0 from zeros."""
    if pooled is None:
        return mixer.forward(u, None, valid)
    if jnp.ndim(index) == 1:
        return mixer.step(u, pooled, active, index == 0)
    rows = pooled if slot is None else jax.tree_util.tree_map(
        lambda leaf: jax.lax.dynamic_slice_in_dim(leaf, slot, 1), pooled)
    s, state, *more = mixer.forward(
        u, _fresh_state(rows, jnp.asarray(index) == 0), valid)
    if slot is not None:
        state = jax.tree_util.tree_map(
            lambda leaf, row: jax.lax.dynamic_update_slice_in_dim(
                leaf, row.astype(leaf.dtype), slot, 0),
            pooled, state)
    return (s, state, *more)


class HybridBlock(Module):
    """A mixer and a feed-forward, each behind its norm and on the
    residual stream.  ``norm`` makes the two norms (``RMSNorm`` or
    ``LayerNorm``).  The mixer here is an attention layer that keeps its
    own keys and values (``cache["self"]``); the subclasses keep a state
    beside them, a state alone, or nothing.

    A family whose sub-layers are normed **on both sides** hands the
    block the two further norms as modules (``attn_post_norm``,
    ``ffn_post_norm``): ``h = x + N2(Mix(N1 x))``, ``y = h + N4(FF(N3
    h))`` (named scope ``block/post_norm``).  A block has the norms it was
    handed: without them a sub-layer's output joins the stream as it
    comes, and the block has no leaf and no operation more.

    ``walk`` is what one pass hands from block to block beside the
    residual stream (a dict the decoder makes anew for every pass):
    ``"memory"``, the scan output of the mixer that hands it on, and
    ``"row"``, the keys and values of the layer whose row later layers
    read, as that layer left them in this pass.

    What the decoder asks of a block and no more: ``keeps`` (whether the
    layer keeps anything in a pool's cache), ``writes_alone``,
    ``shares_row``, ``hands_on``, ``reads`` (the layer whose row it
    attends, or None) and ``reads_memory``."""

    keeps = True
    hands_on = False
    reads = None
    reads_memory = False

    def __init__(self, hidden_size: int, attn: Optional[Module],
                 ffn: Module, eps: float, norm=RMSNorm,
                 shares_row: bool = False,
                 attn_post_norm: Optional[Module] = None,
                 ffn_post_norm: Optional[Module] = None):
        super().__init__()
        self.attn_norm = norm(hidden_size, eps)
        if attn is not None:
            self.attn = attn
        if attn_post_norm is not None:
            self.attn_post_norm = attn_post_norm
        self.ffn_norm = norm(hidden_size, eps)
        self.ffn = ffn
        if ffn_post_norm is not None:
            self.ffn_post_norm = ffn_post_norm
        self.sparse = isinstance(ffn, HeldExperts)
        self.shares_row = bool(shares_row)

    @property
    def window(self) -> Optional[int]:
        """The window of the ring this layer keeps, or None."""
        return getattr(getattr(self, "attn", None), "window", None)

    @property
    def writes_alone(self) -> bool:
        """Whether :meth:`write` can put a chunk's keys and values into
        the layer's cache with no attention and no feed-forward."""
        return hasattr(self.attn, "write")

    def init_cache(self, batch: int, max_len: int, dtype, ring_margin: int):
        return {"self": self.attn.init_cache(batch, max_len, dtype,
                                             ring_margin)}

    def cache_kinds(self, max_len: int):
        return self.attn.cache_kind(max_len)

    def cache_write_programs(self, cache, rows: int) -> int:
        """Device programs that write ``cache`` (this layer's, of ``rows``
        rows) in one per-row decode step: its keys and values in one
        where ``ops.cache_row_writer`` takes its leaves, a
        ``dynamic_update_slice`` a row and leaf where not; for a state
        one a leaf whatever the rows (a recurrence's update, and the
        select that shifts a convolution's inputs)."""
        programs = len(cache.get("ssm", ()))
        if "self" in cache:
            k, v = cache["self"]["k"], cache["self"]["v"]
            programs += 1 if cache_kernels.cache_row_writer(
                k.shape, v.shape, k.dtype) is not None else 2 * rows
        return programs

    def forward(self, x, index=0, cache=None, pad=None, slot=None,
                active=None, valid=None, walk=None):
        """``x [B, T, H]`` float32 -> ``(y, caches, counts)``: see
        :meth:`GroupedQueryAttention.forward` for ``index``, ``pad``,
        ``slot`` and ``active``; ``cache`` is the layer's caches
        (:meth:`init_cache`) or None, and ``caches`` what they became
        (with None: the compact keys and values); ``valid [B, T]`` false
        keeps a token from the experts; ``counts`` is what the expert
        layer did (zeros for a dense layer)."""
        h, caches = self._mix(x, index, cache, pad, slot, active, valid,
                              {} if walk is None else walk)
        y, counts = self._feed_forward(h, valid)
        return y, caches, counts

    def forward_step_and_chunk(self, cache, step, chunk, walk=None):
        """A pool's decode rows and a prefill chunk of one of its rows in
        one pass -> ``(y, y_chunk, caches, counts)``.  ``step`` is ``(x
        [S, 1, H], index [S], pad, active [S], valid [S, 1])`` and
        ``chunk`` ``(x [1, W, H], index, pad, slot, valid [1, W])``, each
        as :meth:`forward` takes them; ``walk`` is the rows' (what the
        chunk's mixer would hand on is read by nobody: a chunk's rows
        stop where the caches stop).  The chunk's mixer, then the rows'
        on the caches it left (what :meth:`forward` on the chunk and then
        on the rows does: ``slot`` itself may be among the rows that
        decode), then **one** feed-forward over both residual streams
        laid end to end, which reads the layer's feed-forward weights
        once and counts as one call of the expert layer."""
        x, index, pad, active, valid = step
        xc, chunk_index, chunk_pad, slot, chunk_valid = chunk
        hc, cache = self._mix(xc, chunk_index, cache, chunk_pad, slot, None,
                              chunk_valid, {})
        h, cache = self._mix(x, index, cache, pad, None, active, valid,
                             {} if walk is None else walk)
        rows = x.shape[0]
        y, counts = self._feed_forward(
            jnp.concatenate([h.reshape(1, rows, -1), hc], axis=1),
            jnp.concatenate([valid.reshape(1, rows), chunk_valid], axis=1))
        return y[0, :rows, None], y[:, rows:], cache, counts

    def write(self, x, index, cache, slot):
        """The keys and values of a chunk's rows into the layer's cache
        (compact, with ``cache`` None) and nothing else: no query, no
        attention, no feed-forward.  Of a layer whose attention can
        (``DifferentialAttention.write``)."""
        n = self.attn_norm(x).astype(self.attn.q_layer.weight.dtype)
        kv = self.attn.write(n, index,
                             None if cache is None else cache["self"], slot)
        return kv if cache is None else {"self": kv}

    def _mix(self, x, index, cache, pad, slot, active, valid, walk):
        """The mixer on the normed input and its residual: ``(h,
        caches)``."""
        n = self.attn_norm(x).astype(self.attn.q_layer.weight.dtype)
        a, kv = self.attn.forward(
            n, index, None if cache is None else cache["self"], pad, slot,
            active)
        if self.shares_row:
            walk["row"] = kv
        return x + self._behind("attn_post_norm", a), \
            (kv if cache is None else {"self": kv})

    def _behind(self, name: str, out):
        """A sub-layer's output through the further norm ``name`` where
        the factory handed the block one; as it comes where not."""
        norm = getattr(self, name, None)
        if norm is None:
            return out
        with jax.named_scope("block/post_norm"):
            return norm(out)

    def _feed_forward(self, h, valid):
        n = self.ffn_norm(h)
        if self.sparse:
            f, counts = self.ffn.forward(n, valid)
        else:
            f, counts = self.ffn.forward(n), jnp.zeros((ROUTING,), jnp.int32)
        return h + self._behind("ffn_post_norm", f), counts


class ParallelBlock(HybridBlock):
    """A full attention layer and a state-space mixer on the same normed
    input, summed: the layer keeps a row of keys and values **and** a
    state.  ``multipliers``: ``attention_in``, ``attention_out``,
    ``ssm_in``, ``ssm_out``."""

    def __init__(self, hidden_size: int, attn: GroupedQueryAttention,
                 ssm: Mamba2Mixer, ffn: Module, eps: float,
                 multipliers: Dict[str, float]):
        super().__init__(hidden_size, attn, ffn, eps)
        self.ssm = ssm
        self.multipliers = {k: float(multipliers.get(k, 1.0)) for k in (
            "attention_in", "attention_out", "ssm_in", "ssm_out")}

    def init_cache(self, batch: int, max_len: int, dtype, ring_margin: int):
        return {"self": self.attn.init_cache(batch, max_len, dtype,
                                             ring_margin),
                "ssm": self.ssm.init_state(batch, dtype)}

    def cache_kinds(self, max_len: int):
        return {"self": ("full", max_len), "ssm": ("state", None)}

    def _mix(self, x, index, cache, pad, slot, active, valid, walk):
        """As :meth:`HybridBlock._mix`; the state as :func:`_run_mixer`
        keeps it."""
        m = self.multipliers
        n = self.attn_norm(x)
        dtype = self.attn.q_layer.weight.dtype
        a, kv = self.attn.forward(
            (n * m["attention_in"]).astype(dtype), index,
            None if cache is None else cache["self"], pad, slot, active)
        s, state = _run_mixer(
            self.ssm, (n * m["ssm_in"]).astype(dtype), index,
            None if cache is None else cache["ssm"], slot, active, valid)
        return x + s * m["ssm_out"] + a * m["attention_out"], \
            {"self": kv, "ssm": state}


class MixerBlock(HybridBlock):
    """A mixer with a state alone: the layer keeps a **state and no
    row**.  The mixer is a :class:`Mamba1Mixer` (a recurrence and the
    last inputs of its convolution) or a :class:`GatedShortConv`, whose
    state is **the convolution's tail and nothing else**: the block asks
    the mixer for its state (``init_state``) and takes whatever leaves it
    has, and a mixer returns ``(out, state)`` and, where it has one, its
    scan output.  With ``hands_on`` that scan output of this pass goes
    into ``walk["memory"]``, for the gated memory units after it."""

    writes_alone = False

    def __init__(self, hidden_size: int, ssm: Module, ffn: Module,
                 eps: float, norm=RMSNorm, hands_on: bool = False):
        super().__init__(hidden_size, None, ffn, eps, norm)
        self.ssm = ssm
        self.hands_on = bool(hands_on)

    def init_cache(self, batch: int, max_len: int, dtype, ring_margin: int):
        return {"ssm": self.ssm.init_state(batch, dtype)}

    def cache_kinds(self, max_len: int):
        return {"ssm": ("state", None)}

    def _mix(self, x, index, cache, pad, slot, active, valid, walk):
        u = self.attn_norm(x).astype(self.ssm.in_proj.weight.dtype)
        s, state, *scan = _run_mixer(
            self.ssm, u, index, None if cache is None else cache["ssm"],
            slot, active, valid)
        if self.hands_on:
            walk["memory"] = scan[0]
        return x + s, {"ssm": state}


class CrossBlock(HybridBlock):
    """An attention layer with a query and an output projection only,
    over **another layer's row** (``reads``: that layer's index): it
    keeps nothing, and attends ``walk["row"]``, the row as that layer
    wrote it in this pass."""

    keeps = writes_alone = False

    def __init__(self, hidden_size: int, attn: DifferentialAttention,
                 ffn: Module, eps: float, norm=RMSNorm, reads: int = 0):
        super().__init__(hidden_size, attn, ffn, eps, norm)
        self.reads = int(reads)

    def init_cache(self, batch: int, max_len: int, dtype, ring_margin: int):
        return {}

    def cache_kinds(self, max_len: int):
        return {"reads": ("shared", self.reads)}

    def _mix(self, x, index, cache, pad, slot, active, valid, walk):
        n = self.attn_norm(x).astype(self.attn.q_layer.weight.dtype)
        a, _ = self.attn.forward(n, index, cache, pad, slot, active,
                                 shared=walk["row"])
        return x + a, {}


class GatedMemoryUnit(Module):
    """``W_2( silu(W_1 n) * m )``, no bias: the layer's input gates a
    memory ``m`` that an earlier layer's mixer handed on (the same
    position's, of the same pass)."""

    def __init__(self, hidden_size: int, inner: int):
        super().__init__()
        self.in_proj = Linear(hidden_size, inner, with_bias=False)
        self.out_proj = Linear(inner, hidden_size, with_bias=False)

    def forward(self, n, memory):
        with jax.named_scope("gmu"):
            g = jax.nn.silu(_product(n, self.in_proj)) * memory
            return _product(g.astype(n.dtype), self.out_proj)


class MemoryBlock(HybridBlock):
    """A gated memory unit on ``walk["memory"]``: the layer keeps
    nothing at all."""

    keeps = writes_alone = False
    reads_memory = True

    def __init__(self, hidden_size: int, unit: GatedMemoryUnit, ffn: Module,
                 eps: float, norm=RMSNorm):
        super().__init__(hidden_size, None, ffn, eps, norm)
        self.unit = unit

    def init_cache(self, batch: int, max_len: int, dtype, ring_margin: int):
        return {}

    def cache_kinds(self, max_len: int):
        return {}

    def _mix(self, x, index, cache, pad, slot, active, valid, walk):
        n = self.attn_norm(x).astype(self.unit.in_proj.weight.dtype)
        return x + self.unit.forward(n, walk["memory"]), {}


class HybridDecoder(Module):
    """``forward(tokens [B, T] int, 1-based; 0 = padding) -> logits
    [B, T, vocab]`` float32 (column ``j`` scores token ``j + 1``).

    ``blocks`` are the layers as a family's factory built them
    (:class:`HybridBlock` and its subclasses), between an embedding and a
    final ``norm`` (``RMSNorm`` or ``LayerNorm``, at ``eps``) before the
    head, which with ``tie_head`` scores with the embedding table.  The
    embedding and the logits are scaled by their multipliers (constants;
    1: not scaled).  What only the combination of blocks can get wrong is
    refused here: a block that attends another layer's row names an
    earlier layer that shares it, and one that reads a scan output comes
    after a mixer that hands its own on.

    **Not every layer has ``cache["self"]``, and a layer may have no cache
    at all**: the walk carries ``walk`` beside the residual stream
    (:class:`HybridBlock`).  **A chunk's rows leave the walk where the
    caches stop**: :meth:`prefill_chunk`, :meth:`prefill_kv` and the chunk
    half of :meth:`decode_step_with_chunk` write caches and give no
    logits, so where the layers after the last one that keeps a cache
    keep none, a chunk's rows walk the layers before it whole, write that
    layer's keys and values (:meth:`HybridBlock.write`: no query, no
    attention, no feed-forward) and stop (``chunk_layers``)."""

    def __init__(self, vocab_size: int, hidden_size: int,
                 blocks: Sequence[HybridBlock], eps: float = 1e-5,
                 max_len: int = 512, norm=RMSNorm, tie_head: bool = False,
                 embedding_multiplier: float = 1.0,
                 lm_head_multiplier: float = 1.0):
        super().__init__()
        for depth, blk in enumerate(blocks):
            if blk.reads is not None and not (
                    blk.reads < depth and blocks[blk.reads].shares_row):
                raise ValueError(
                    f"layer {depth} reads layer {blk.reads}: an earlier "
                    f"layer that shares its row")
            if blk.reads_memory and not any(
                    before.hands_on for before in blocks[:depth]):
                raise ValueError(
                    f"layer {depth} reads a scan output: a mixer before it "
                    f"that hands its own on")
        self.hidden_size = hidden_size
        self.max_len = max_len
        self.embedding_multiplier = float(embedding_multiplier)
        self.lm_head_multiplier = float(lm_head_multiplier)
        self.embedding = LookupTable(vocab_size, hidden_size)
        self.embedding.weight = Parameter(
            self.embedding.weight * hidden_size ** -0.5)
        self.blocks = ModuleList(blocks)
        # where a chunk's rows stop (the class docstring): the layers they
        # walk whole, and whether they then write one layer's keys and
        # values more
        last = max(i for i, blk in enumerate(blocks) if blk.keeps)
        self.chunk_writes = last < len(blocks) - 1 \
            and blocks[last].writes_alone
        self.chunk_layers = last + (not self.chunk_writes)
        self.final_norm = norm(hidden_size, eps)
        self.tied = bool(tie_head)
        if not self.tied:
            self.lm_head = Linear(hidden_size, vocab_size, with_bias=False)

    # ---- what the slot pool asks ------------------------------------------

    def cache_layers(self) -> Tuple[Any, ...]:
        """Each layer's caches.  A layer with one, its keys and values
        (``"self"``), declares it ``("full", max_len)``, ``("ring",
        window)`` or ``("latent", max_len)``; a ring is allocated with
        room for a prefill chunk beside the window (:meth:`init_cache`,
        ``ring_margin``); a latent row is a full row of one head whose
        leaf ``"k"`` is the rotary key and ``"v"`` the compressed row.  A
        parallel layer declares two by name: ``{"self": ("full",
        max_len), "ssm": ("state", None)}``, a state having no
        positions.  A layer with a state alone declares ``{"ssm":
        ("state", None)}``; a layer that keeps nothing declares ``{}``,
        and one that keeps nothing and attends another layer's row names
        that layer: ``{"reads": ("shared", 17)}`` (no storage: the pool
        counts it among that row's readers)."""
        return tuple(blk.cache_kinds(self.max_len) for blk in self.blocks)

    def expert_layers(self) -> int:
        return sum(1 for blk in self.blocks if blk.sparse)

    def init_cache(self, batch: int, dtype=jnp.float32,
                   ring_margin: int = 1) -> Dict[str, Any]:
        """Per-layer keys and values by :meth:`cache_layers`, each with
        its layer's heads and widths, and the padding flags by
        position."""
        return {
            "layers": [blk.init_cache(batch, self.max_len, dtype,
                                      ring_margin) for blk in self.blocks],
            "pad": jnp.zeros((batch, self.max_len), bool),
        }

    def _full_rows(self, caches):
        """``(attention, row)`` of every layer that keeps a full row."""
        return [(blk.attn, layer["self"])
                for blk, layer in zip(self.blocks, caches["layers"])
                if "self" in layer and blk.window is None]

    def decode_key_block(self, caches) -> Optional[int]:
        """Places of a full row that the per-row decode step's attention
        reads at a time, or None where it reads every row whole whatever
        is live (each full row's attention says: its
        ``decode_key_block``; rings are read whole either way): the
        serving pool counts what its decode program reads of its full
        rows by this."""
        blocks = {attn.decode_key_block(row)
                  for attn, row in self._full_rows(caches)}
        return blocks.pop() if len(blocks) == 1 else None

    def chunk_key_block(self, caches) -> Optional[int]:
        """Places of its slot's full row that a prefill chunk's attention
        reads at a time, where every full row's attention says one (live
        blocks only, on every backend: grouped-query and latent rows);
        None where a chunk reads its row whole (differential attention):
        the serving pool counts what its chunk programs read by this."""
        blocks = {attn.chunk_key_block(row)
                  for attn, row in self._full_rows(caches)}
        return blocks.pop() if len(blocks) == 1 else None

    def cache_write_programs(self, caches) -> int:
        """Device programs that write ``caches`` in one per-row decode
        step (:meth:`decode_step` with ``index [B]``): one select over
        the padding flags, and each layer's own
        (:meth:`HybridBlock.cache_write_programs`).  The serving pool
        counts by this."""
        rows = caches["pad"].shape[0]
        return 1 + sum(blk.cache_write_programs(layer, rows)
                       for blk, layer in zip(self.blocks, caches["layers"]))

    @staticmethod
    def _mask_untrained_logit(logits):
        """The untied head has no untrained row: nothing to mask."""
        return logits

    # ---- the four passes ---------------------------------------------------

    def _embed(self, tokens):
        x = self.embedding.forward(jnp.maximum(tokens, 1)).astype(
            jnp.float32)
        return x if self.embedding_multiplier == 1.0 \
            else x * self.embedding_multiplier

    def _logits(self, x):
        # a tied head scores with the embedding table itself
        w = (self.embedding if self.tied else self.lm_head).weight
        y = jnp.einsum("...i,oi->...o", self.final_norm(x).astype(w.dtype),
                       w, preferred_element_type=jnp.float32)
        return y if self.lm_head_multiplier == 1.0 \
            else y * self.lm_head_multiplier

    def forward(self, tokens):
        _B, T = tokens.shape
        if T > self.max_len:
            raise ValueError(
                f"sequence length {T} exceeds max_len={self.max_len}")
        pad = tokens == 0
        x, walk = self._embed(tokens), {}
        for blk in self.blocks:
            x, _, _ = blk.forward(x, pad=pad, valid=~pad, walk=walk)
        return self._logits(x)

    def _chunk_walk(self, x, caches, index, pad, slot, valid):
        """A chunk's rows ``x`` through the layers that keep a cache:
        ``chunk_layers`` blocks whole (on each layer's cache; compact
        where ``caches`` is None), then, where the walk stops short of
        the model's end, the next block's keys and values alone
        (:meth:`HybridBlock.write`), and ``{}`` for every layer after it
        -> ``(layers, routing)``."""
        layers, routing, walk = [], jnp.zeros((ROUTING,), jnp.int32), {}
        for i, blk in enumerate(self.blocks):
            cache = None if caches is None else caches["layers"][i]
            if i < self.chunk_layers:
                x, kv, counts = blk.forward(x, index, cache, pad, slot,
                                            valid=valid, walk=walk)
                routing = routing + counts
            elif i == self.chunk_layers and self.chunk_writes:
                kv = blk.write(x, index, cache, slot)
            else:
                kv = {}
            layers.append(kv)
        return layers, routing

    def prefill_kv(self, ptoks):
        """Compact per-layer keys and values ``[B, Hkv, T, d]`` of every
        position of ``ptoks`` (a layer with a state: ``{"self": those,
        "ssm": the state after each row's last real token}``, or the
        state alone; ``{}`` for a layer that keeps nothing), the ``[B,
        T]`` padding flags, and the expert layers' ``routing``: what a
        bucketed prefill scatters into slots.  The rows walk what a
        chunk's rows walk (:meth:`_chunk_walk`)."""
        pad = ptoks == 0
        layers, routing = self._chunk_walk(self._embed(ptoks), None, 0, pad,
                                           None, ~pad)
        return layers, pad, routing

    def _chunk_flags(self, toks, index, caches, slot):
        """The padding flags with a chunk's written (refusing a chunk
        wider than a ring has room for)."""
        _B, W = toks.shape
        for blk, cache in zip(self.blocks, caches["layers"]):
            win = blk.window
            R = 0 if win is None else cache["self"]["k"].shape[2] - 1
            if win is not None and R < min(self.max_len, win + W - 1):
                raise ValueError(
                    f"a chunk of {W} positions needs a ring of "
                    f"{win + W - 1} places, the cache has {R} "
                    f"(init_cache(ring_margin={W}))")
        return jax.lax.dynamic_update_slice(
            caches["pad"], toks == 0, (0 if slot is None else slot, index))

    def _row_flags(self, tokens, index, pad, active):
        """A per-row step's ``(index, flags, valid)``: an idle row sent
        where nothing reads, every row's flag in one select."""
        flag = tokens == 0
        if active is not None:
            # a full row's last position is beyond every prefill
            # query's mask and rewritten by its occupant's own decode
            # before it is attended; a ring sends the row to its
            # spare place (the attention layer's ``forward``)
            index = jnp.where(active, index, jnp.int32(self.max_len - 1))
        # one select over the flags, not a write a row
        with jax.named_scope("cache/write"):
            here = jnp.arange(self.max_len, dtype=jnp.int32)[None, :] \
                == index[:, None]
            pad = jnp.where(here, flag, pad)
        valid = ~flag if active is None else ~flag & active[:, None]
        return index, pad, valid

    def prefill_chunk(self, toks, index, caches, slot=None):
        """Write keys, values and padding flags of ``toks [B, W]`` at
        positions ``index .. index+W`` of a cache filled below ``index``
        (row ``slot`` of a pool when given, ``B == 1``), attending the
        cache and itself; returns ``(caches, routing)``.  A ring must
        have ``W - 1`` places beside its window: the chunk is written
        before it is attended."""
        pad = self._chunk_flags(toks, index, caches, slot)
        new_layers, routing = self._chunk_walk(self._embed(toks), caches,
                                               index, pad, slot, toks != 0)
        return dict(caches, layers=new_layers, pad=pad), routing

    def decode_step(self, tokens, index, caches, with_logits=True,
                    active=None):
        """One token a row: ``tokens [B, 1]`` at ``index`` (a scalar, or
        ``[B]``: a position per row) -> ``(logits [B, vocab], caches,
        routing)``.  ``active [B]`` false (with ``index [B]``) marks a row
        that only rides along: it writes where nothing reads and the
        experts do not see it."""
        if jnp.ndim(index) == 1:
            index, pad, valid = self._row_flags(tokens, index,
                                                caches["pad"], active)
        else:
            flag = tokens == 0
            pad = jax.lax.dynamic_update_slice(caches["pad"], flag,
                                               (0, index))
            valid = ~flag if active is None else ~flag & active[:, None]
        x, walk = self._embed(tokens), {}
        new_layers, routing = [], jnp.zeros((ROUTING,), jnp.int32)
        for blk, cache in zip(self.blocks, caches["layers"]):
            x, kv, counts = blk.forward(x, index, cache, pad,
                                        active=active, valid=valid,
                                        walk=walk)
            new_layers.append(kv)
            routing = routing + counts
        new_caches = dict(caches, layers=new_layers, pad=pad)
        if not with_logits:
            return None, new_caches, routing
        return self._logits(x)[:, 0], new_caches, routing

    def decode_step_with_chunk(self, tokens, index, caches, active, toks,
                               chunk_index, slot):
        """A pool's pass that carries a prefill chunk, as one walk of the
        blocks: what ``prefill_chunk(toks, chunk_index, caches, slot)``
        followed by ``decode_step(tokens, index, ., active=active)``
        gives, ``(logits [B, vocab], caches, routing)``, with every
        block's feed-forward run **once** over the ``B`` rows and the
        chunk's ``W`` tokens together, so that the pass reads each
        layer's feed-forward weights once
        (:meth:`HybridBlock.forward_step_and_chunk`).  ``tokens [B, 1]``,
        ``index [B]``, ``active [B]``; ``toks [1, W]`` into row ``slot``
        at ``chunk_index``.  Chunk first, then step, layer by layer: row
        ``slot`` may decode in the same pass (its last chunk), and then
        attends what the chunk wrote.  An expert layer counts one call.
        Where the chunk's rows stop before the model's end
        (``chunk_layers``), the layers after that run over the decode
        rows alone."""
        chunk_pad = self._chunk_flags(toks, chunk_index, caches, slot)
        index, pad, valid = self._row_flags(tokens, index, chunk_pad, active)
        chunk_valid = toks != 0
        x, xc, walk = self._embed(tokens), self._embed(toks), {}
        new_layers, routing = [], jnp.zeros((ROUTING,), jnp.int32)
        for i, (blk, cache) in enumerate(zip(self.blocks, caches["layers"])):
            if i < self.chunk_layers:
                x, xc, kv, counts = blk.forward_step_and_chunk(
                    cache, (x, index, pad, active, valid),
                    (xc, chunk_index, chunk_pad, slot, chunk_valid), walk)
            else:
                if i == self.chunk_layers and self.chunk_writes:
                    cache = blk.write(xc, chunk_index, cache, slot)
                x, kv, counts = blk.forward(x, index, cache, pad,
                                            active=active, valid=valid,
                                            walk=walk)
            new_layers.append(kv)
            routing = routing + counts
        return self._logits(x)[:, 0], \
            dict(caches, layers=new_layers, pad=pad), routing

    def generate(self, prompt, max_new_tokens: int, eos_id=None,
                 chunk: int = 64):
        """Greedy continuation ``prompt [B, Tp] -> [B, Tp +
        max_new_tokens]``: the prompt in chunks of ``chunk`` through
        :meth:`prefill_chunk`, then a scan of decode steps; positions
        after ``eos_id`` are 0."""
        B, Tp = prompt.shape
        if Tp + max_new_tokens > self.max_len:
            raise ValueError(
                f"prompt {Tp} + {max_new_tokens} new tokens exceeds "
                f"max_len={self.max_len}")
        prompt = jnp.asarray(prompt, jnp.int32)
        caches = self.init_cache(B, ring_margin=chunk)
        for s in range(0, Tp - 1, chunk):
            caches, _ = self.prefill_chunk(
                prompt[:, s:min(s + chunk, Tp - 1)], s, caches)

        def gen_step(carry, t):
            tok, caches, done = carry
            logits, caches, _ = self.decode_step(tok, t, caches)
            nxt = jnp.argmax(logits, axis=-1).astype(jnp.int32) + 1
            nxt = jnp.where(done, 0, nxt)
            if eos_id is not None:
                done = done | (nxt == eos_id)
            return (nxt[:, None], caches, done), nxt

        (_, _, _), toks = jax.lax.scan(
            gen_step, (prompt[:, -1:], caches, jnp.zeros((B,), bool)),
            Tp - 1 + jnp.arange(max_new_tokens))
        return jnp.concatenate([prompt, toks.T], axis=1)


def mimo_v2(config: Dict[str, Any], max_len: int) -> HybridDecoder:
    """The model from the keys of a public ``mimo_v2`` ``config.json``
    (MiMo-V2.5) plus the chip's share: ``experts_held`` (how many of
    ``n_routed_experts`` live here, from ``experts_offset``, default 0)
    and ``vocab_size`` as sliced.  ``hybrid_layer_pattern`` and
    ``moe_layer_freq`` are read for the first ``num_hidden_layers``
    layers.  Pre-norm and sequential: ``h = x + Attn_i(RMSNorm(x))``,
    ``y = h + FFN_i(RMSNorm(h))``.  ``hybrid_layer_pattern[i]`` names
    layer ``i``'s attention: 0 a **full** layer (causal over everything),
    1 a **window** layer (the last ``sliding_window`` positions, a
    learned sink logit per head, key/value heads and rotary base of its
    own).  Keys are ``head_dim`` wide, values ``v_head_dim``; the first
    ``partial_rotary_factor * head_dim`` dims of every query and key head
    are rotated by position.  ``moe_layer_freq[i]`` names the
    feed-forward: 0 a dense gated layer ``W_d(silu(W_g n) * W_u n)``, 1
    :class:`bigdl_tpu.nn.HeldExperts`.  Final RMSNorm, an untied head,
    the embedding not scaled."""
    c = config
    if c.get("attention_bias") or c.get("n_shared_experts") \
            or c.get("add_full_attention_sink_bias") \
            or c.get("tie_word_embeddings"):
        raise ValueError("mimo_v2: attention bias, a sink on full layers "
                         "and a tied head are not built, and a mimo_v2 "
                         "config has no shared experts (HeldExperts has "
                         "them: sarvam_mla)")
    if c.get("scoring_func", "sigmoid") != "sigmoid" \
            or c.get("topk_method", "noaux_tc") != "noaux_tc" \
            or c.get("n_group", 1) != 1 or c.get("topk_group", 1) != 1 \
            or c.get("routed_scaling_factor") not in (None, 1, 1.0):
        raise ValueError("mimo_v2: sigmoid scores, a noaux_tc selection "
                         "bias and one group are what is built, and a "
                         "mimo_v2 config has no routed_scaling_factor "
                         "(HeldExperts has one: sarvam_mla)")
    if c.get("swa_head_dim", c["head_dim"]) != c["head_dim"] \
            or c.get("swa_v_head_dim", c["v_head_dim"]) != c["v_head_dim"] \
            or c.get("swa_num_attention_heads",
                     c["num_attention_heads"]) != c["num_attention_heads"]:
        raise ValueError("mimo_v2: window and full layers share their "
                         "query heads and head widths here")
    n, hidden = c["num_hidden_layers"], c["hidden_size"]
    pattern = list(c["hybrid_layer_pattern"])[:n]
    freq = list(c["moe_layer_freq"])[:n]
    if len(pattern) != len(freq):
        raise ValueError("mimo_v2: one hybrid_layer_pattern and one "
                         "moe_layer_freq entry a layer")
    eps = c.get("layernorm_epsilon", 1e-5)
    held = (c.get("experts_offset", 0),
            c.get("experts_held", c["n_routed_experts"]))
    blocks = []
    for window, sparse in zip(pattern, freq):
        attn = GroupedQueryAttention(
            hidden, c["num_attention_heads"],
            c["swa_num_key_value_heads" if window else "num_key_value_heads"],
            c["head_dim"], c["v_head_dim"],
            window=c["sliding_window"] if window else None,
            rope_theta=float(c["swa_rope_theta" if window else "rope_theta"]),
            rotary_dim=2 * (int(c["partial_rotary_factor"] * c["head_dim"])
                            // 2),
            sink=bool(window and c.get("add_swa_attention_sink_bias")),
            value_scale=float(c.get("attention_value_scale") or 1.0))
        ffn = HeldExperts(
            hidden, c["moe_intermediate_size"], c["n_routed_experts"],
            c["num_experts_per_tok"], held,
            c.get("norm_topk_prob", True)) if sparse \
            else GatedFFN(hidden, c["intermediate_size"])
        blocks.append(HybridBlock(hidden, attn, ffn, eps))
    return HybridDecoder(c["vocab_size"], hidden, blocks, eps, max_len)


_FALCON_H1_REFUSED = ("attention_bias", "mlp_bias", "projectors_bias",
                      "mamba_proj_bias", "rope_scaling",
                      "tie_word_embeddings", "attn_layer_indices")


def falcon_h1(config: Dict[str, Any], max_len: int) -> HybridDecoder:
    """The model from the keys of a public ``falcon_h1`` ``config.json``:
    every layer a parallel block (grouped-query attention over full rows,
    its keys scaled, beside a Mamba-2 mixer, both on the same normed
    input and summed: :class:`ParallelBlock`), a dense gated feed-forward
    with its two multipliers, an untied head, and the µP multipliers
    (the embedding's and the logits' among them) as constants.
    What is not built is refused: biases on the projections, a scaled
    rotary embedding, a tied head, attention on some layers only, a
    mixer without its gated norm or with the norm before the gate."""
    c = config
    for key in _FALCON_H1_REFUSED:
        if c.get(key):
            raise ValueError(f"falcon_h1: {key}={c[key]!r} is not built")
    if not c.get("mamba_rms_norm", True) or c.get("mamba_norm_before_gate") \
            or not c.get("mamba_conv_bias", True) \
            or c.get("hidden_act", "silu") != "silu":
        raise ValueError("falcon_h1: a gated grouped RMS norm after the "
                         "gate, a bias on the convolution and silu are "
                         "what is built")
    heads, width = c["mamba_n_heads"], c["mamba_d_head"]
    if heads * width != c["mamba_d_ssm"]:
        raise ValueError("falcon_h1: mamba_d_ssm is mamba_n_heads heads of "
                         "mamba_d_head")
    hidden, eps = c["hidden_size"], c.get("rms_norm_eps", 1e-5)
    gate, down = c.get("mlp_multipliers") or (1.0, 1.0)
    multipliers = {side: c.get(f"{side}_multiplier", 1.0) for side in (
        "attention_in", "attention_out", "ssm_in", "ssm_out")}
    blocks = []
    for _ in range(c["num_hidden_layers"]):
        attn = GroupedQueryAttention(
            hidden, c["num_attention_heads"], c["num_key_value_heads"],
            c["head_dim"], rope_theta=float(c["rope_theta"]),
            rotary_dim=c["head_dim"],
            key_scale=c.get("key_multiplier", 1.0))
        ffn = GatedFFN(hidden, c["intermediate_size"], gate, down)
        ssm = Mamba2Mixer(
            hidden, heads, width, c["mamba_n_groups"], c["mamba_d_state"],
            c["mamba_d_conv"], c.get("mamba_chunk_size", 128), eps,
            c.get("ssm_multipliers") or (1.0,) * 5)
        blocks.append(ParallelBlock(hidden, attn, ssm, ffn, eps,
                                    multipliers))
    return HybridDecoder(
        c["vocab_size"], hidden, blocks, eps, max_len,
        embedding_multiplier=c.get("embedding_multiplier", 1.0),
        lm_head_multiplier=c.get("lm_head_multiplier", 1.0))


_SARVAM_REFUSED = ("q_lora_rank", "tie_word_embeddings", "attention_bias")


def sarvam_mla(config: Dict[str, Any], max_len: int) -> HybridDecoder:
    """The model from the keys of a public ``sarvam_mla`` ``config.json``
    (sarvam-105b) plus the chip's share: ``experts_held`` (how many of
    ``num_experts`` live here, from ``experts_offset``, default 0) and
    ``vocab_size`` as sliced.  Sequential like ``mimo_v2``'s, every layer
    a latent-attention layer
    (:class:`bigdl_tpu.nn.latent_attention.LatentAttention`: one
    compressed row a position shared by all heads; ``kv_lora_rank``,
    ``qk_nope_head_dim``, ``qk_rope_head_dim``, ``v_head_dim``, YaRN
    from ``rope_scaling``); the first
    ``first_k_dense_replace`` (1) a dense gated layer of
    ``intermediate_size``, the others ``num_experts`` sigmoid-routed
    experts of ``moe_intermediate_size`` with a selection bias
    (``moe_router_enable_expert_bias``), ``num_experts_per_tok`` a token,
    weights normalised over the chosen, the routed sum times
    ``routed_scaling_factor``, beside ``num_shared_experts`` shared
    experts as one gated layer.  ``use_qk_norm`` is read as the norm on
    the compressed row (there is no query latent to norm, and the rotary
    key is not normed).  What is not built is refused: a low-rank query,
    a tied head, a bias, expert groups, another ``rope_scaling`` than
    ``deepseek_yarn``, more or fewer leading dense layers than one, a
    router without its selection bias."""
    c = config
    for key in _SARVAM_REFUSED:
        if c.get(key):
            raise ValueError(f"sarvam_mla: {key}={c[key]!r} is not built")
    for key in ("n_group", "topk_group"):
        if c.get(key, 1) not in (None, 1):
            raise ValueError(f"sarvam_mla: {key}={c[key]!r}: one group of "
                             f"experts is what is built")
    scaling = c.get("rope_scaling") or {}
    if scaling and scaling.get("type", scaling.get("rope_type")) \
            != "deepseek_yarn":
        raise ValueError(f"sarvam_mla: rope_scaling {scaling!r}: "
                         f"deepseek_yarn is what is built")
    if c.get("first_k_dense_replace", 1) != 1:
        raise ValueError("sarvam_mla: one leading dense layer is what is "
                         "built (first_k_dense_replace 1)")
    if not c.get("moe_router_enable_expert_bias", True) \
            or not c.get("use_qk_norm", True) \
            or c.get("hidden_act", "silu") != "silu":
        raise ValueError("sarvam_mla: a router with its selection bias, "
                         "the norm on the compressed row (use_qk_norm) "
                         "and silu are what is built")
    if c.get("q_head_dim", c["qk_nope_head_dim"] + c["qk_rope_head_dim"]) \
            != c["qk_nope_head_dim"] + c["qk_rope_head_dim"]:
        raise ValueError("sarvam_mla: q_head_dim is qk_nope_head_dim + "
                         "qk_rope_head_dim")
    hidden, eps = c["hidden_size"], c.get("rms_norm_eps", 1e-6)
    width, shared = c["moe_intermediate_size"], c.get("num_shared_experts", 0)
    held = (c.get("experts_offset", 0),
            c.get("experts_held", c["num_experts"]))
    blocks = []
    for i in range(c["num_hidden_layers"]):
        attn = LatentAttention(
            hidden, c["num_attention_heads"], c["qk_nope_head_dim"],
            c["qk_rope_head_dim"], c["v_head_dim"], c["kv_lora_rank"],
            float(c.get("rope_theta", 10000.0)), scaling or None, eps)
        ffn = HeldExperts(
            hidden, width, c["num_experts"], c["num_experts_per_tok"], held,
            c.get("norm_topk_prob", True),
            shared=GatedFFN(hidden, shared * width) if shared else None,
            scale=float(c.get("routed_scaling_factor") or 1.0)) if i >= 1 \
            else GatedFFN(hidden, c["intermediate_size"])
        blocks.append(HybridBlock(hidden, attn, ffn, eps))
    return HybridDecoder(c["vocab_size"], hidden, blocks, eps, max_len)


_PHI4_FLASH_REFUSED = ("mlp_bias", "lm_head_bias", "embd_pdrop",
                       "resid_pdrop", "attention_dropout", "rope_scaling",
                       "mamba_proj_bias", "use_positional_embedding")


def phi4_flash(config: Dict[str, Any], max_len: int) -> HybridDecoder:
    """The model from the keys of a public ``phi4flash`` ``config.json``
    (Phi-4-mini-flash-reasoning; the SambaY decoder-hybrid-decoder,
    arXiv:2507.06607), ``L = num_hidden_layers`` layers: in the first
    half a Mamba-1 mixer on the even layers (one in ``mb_per_layer`` = 2)
    and differential attention over the last ``sliding_window`` positions
    on the odd ones; layer ``L/2`` a Mamba-1 mixer that also hands on its
    scan output; layer ``L/2 + 1`` differential attention over
    everything, whose keys and values are the one full row; after it a
    gated memory unit on the even layers and differential **cross**
    attention to that row on the odd ones.  LayerNorms with a bias, a
    gated feed-forward of ``intermediate_size`` in every layer, a head
    tied to the embedding, no position encoded anywhere.  The mixer's
    sizes are ``mamba_d_state`` (16), ``mamba_d_conv`` (4),
    ``mamba_expand`` (2) and ``mamba_dt_rank`` (``"auto"``: ``ceil(hidden
    / 16)``), the public configuration class's defaults where the
    ``config.json`` has none.  What is not built is refused by name:
    biases on the feed-forward or the head, dropout, a scaled or any
    rotary embedding, an untied head, another activation than silu,
    another ``mb_per_layer`` than 2, a depth that is not whole periods
    (a multiple of 4, at least 8)."""
    c = config
    for key in _PHI4_FLASH_REFUSED:
        if c.get(key):
            raise ValueError(f"phi4_flash: {key}={c[key]!r} is not built")
    if not c.get("tie_word_embeddings", True):
        raise ValueError("phi4_flash: tie_word_embeddings=False is not "
                         "built (the head is the embedding)")
    if c.get("hidden_act", "silu") != "silu":
        raise ValueError(f"phi4_flash: hidden_act={c['hidden_act']!r} is "
                         f"not built (silu)")
    if c.get("mb_per_layer", 2) != 2:
        raise ValueError(f"phi4_flash: mb_per_layer={c['mb_per_layer']!r} "
                         f"is not built (2)")
    n = c["num_hidden_layers"]
    if n % 4 or n < 8:
        raise ValueError(f"phi4_flash: num_hidden_layers={n} is not built "
                         f"(whole periods: a multiple of 4, at least 8)")
    hidden, heads = c["hidden_size"], c["num_attention_heads"]
    if hidden % heads:
        raise ValueError("phi4_flash: hidden_size is num_attention_heads "
                         "heads")
    half, eps = n // 2, c.get("layer_norm_eps", 1e-5)
    inner, rank = c.get("mamba_expand", 2) * hidden, \
        c.get("mamba_dt_rank", "auto")
    blocks = []
    for i in range(n):
        # odd layers attend: a window before the full layer ``half + 1``,
        # whose row the layers after it read
        attn = DifferentialAttention(
            hidden, heads, c["num_key_value_heads"], hidden // heads, i,
            window=c["sliding_window"] if i < half else None,
            cross=i > half + 1, eps=eps) if i % 2 else None
        ffn = GatedFFN(hidden, c["intermediate_size"])
        if i % 2 and i > half + 1:
            blk = CrossBlock(hidden, attn, ffn, eps, LayerNorm,
                             reads=half + 1)
        elif i % 2:
            blk = HybridBlock(hidden, attn, ffn, eps, LayerNorm,
                              shares_row=i == half + 1)
        elif i > half:
            blk = MemoryBlock(hidden, GatedMemoryUnit(hidden, inner), ffn,
                              eps, LayerNorm)
        else:
            blk = MixerBlock(
                hidden, Mamba1Mixer(
                    hidden, inner, c.get("mamba_d_state", 16),
                    None if rank == "auto" else int(rank),
                    c.get("mamba_d_conv", 4)),
                ffn, eps, LayerNorm, hands_on=i == half)
        blocks.append(blk)
    return HybridDecoder(c["vocab_size"], hidden, blocks, eps, max_len,
                         LayerNorm, tie_head=True)


_LFM2_REFUSED = ("conv_bias", "attention_bias", "mlp_bias", "rope_scaling",
                 "num_shared_experts", "n_shared_experts")


def lfm2_moe(config: Dict[str, Any], max_len: int) -> HybridDecoder:
    """The model from the keys of a public ``lfm2_moe`` ``config.json``
    (LFM2-24B-A2B) plus the chip's share: ``experts_held`` (how many of
    ``num_experts`` live here, from ``experts_offset``, default 0: all of
    them).  ``layer_types[i]`` names layer ``i``'s mixer for the first
    ``num_hidden_layers`` layers: ``"conv"`` a gated short convolution of
    ``conv_L_cache`` taps (:class:`bigdl_tpu.nn.short_conv.GatedShortConv`:
    a :class:`MixerBlock` whose state is the convolution's two-row tail
    and nothing else), ``"full_attention"`` grouped-query attention
    (``num_attention_heads`` heads of ``hidden_size / num_attention_heads``
    over ``num_key_value_heads``) whose query and key heads go through an
    RMS norm before the half-split rotation over the whole head (base
    ``rope_parameters.rope_theta``).  The first ``num_dense_layers``
    feed-forwards are dense gated layers of ``intermediate_size``, the
    others ``num_experts`` sigmoid-routed experts of
    ``moe_intermediate_size`` with a selection bias (``use_expert_bias``),
    ``num_experts_per_tok`` a token, weights over the sum of the chosen
    plus ``1e-6``, the routed sum times ``routed_scaling_factor``.  RMS
    norms at ``norm_eps``, no bias anywhere, the head tied to the
    embedding.  What is not built is refused by name: a bias on the
    convolution or a projection, a scaled rotary embedding, shared
    experts, a router without its selection bias, weights not normalised
    over the chosen, an untied head, another layer type."""
    c = config
    for key in _LFM2_REFUSED:
        if c.get(key):
            raise ValueError(f"lfm2_moe: {key}={c[key]!r} is not built")
    for key in ("use_expert_bias", "norm_topk_prob", "tie_word_embeddings"):
        if not c.get(key, True):
            raise ValueError(f"lfm2_moe: {key}={c[key]!r} is not built")
    rope = c.get("rope_parameters") or {}
    if rope.get("rope_type", "default") != "default":
        raise ValueError(f"lfm2_moe: rope_parameters={rope!r} is not built "
                         f"(rope_type default)")
    types = list(c["layer_types"])[:c["num_hidden_layers"]]
    for kind in types:
        if kind not in ("conv", "full_attention"):
            raise ValueError(f"lfm2_moe: layer_types has {kind!r}: 'conv' "
                             f"and 'full_attention' are what is built")
    hidden, heads = c["hidden_size"], c["num_attention_heads"]
    head_dim = c.get("head_dim") or hidden // heads
    eps = c.get("norm_eps", 1e-5)
    held = (c.get("experts_offset", 0),
            c.get("experts_held", c["num_experts"]))
    blocks = []
    for i, kind in enumerate(types):
        attn = GroupedQueryAttention(
            hidden, heads, c["num_key_value_heads"], head_dim,
            rope_theta=float(rope.get("rope_theta",
                                      c.get("rope_theta", 1e6))),
            rotary_dim=head_dim, qk_norm=True, norm_eps=eps) \
            if kind == "full_attention" else None
        ffn = HeldExperts(
            hidden, c["moe_intermediate_size"], c["num_experts"],
            c["num_experts_per_tok"], held,
            scale=float(c.get("routed_scaling_factor") or 1.0),
            normalize_eps=1e-6) if i >= c.get("num_dense_layers", 0) \
            else GatedFFN(hidden, c["intermediate_size"])
        blocks.append(
            HybridBlock(hidden, attn, ffn, eps) if attn is not None else
            MixerBlock(hidden, GatedShortConv(hidden,
                                              c.get("conv_L_cache", 3)),
                       ffn, eps))
    return HybridDecoder(c["vocab_size"], hidden, blocks, eps, max_len,
                         tie_head=True)


_AFMOE_ONE = ("n_group", "num_expert_groups", "topk_group",
              "num_limited_groups")


def afmoe(config: Dict[str, Any], max_len: int) -> HybridDecoder:
    """The model from the keys of a public ``afmoe`` ``config.json``
    (Trinity-Mini) plus the chip's share: ``experts_held`` (how many of
    ``num_experts`` live here, from ``experts_offset``, default 0: all of
    them).  ``layer_types[i]`` names layer ``i``'s attention for the first
    ``num_hidden_layers`` layers: ``"sliding_attention"`` a **window**
    layer over the last ``sliding_window`` positions (a ring in a pool),
    whose query and key heads are rotated by position over the whole head
    (half-split pairs, base ``rope_theta``); ``"full_attention"`` a layer
    that attends everything before it and **rotates nothing**: it sees no
    position at all.  Both are grouped-query attention
    (``num_attention_heads`` heads of ``head_dim`` over
    ``num_key_value_heads``) whose query and key heads go through an RMS
    norm before any rotation and whose context is **gated**: ``W_o (ctx *
    sigmoid(W_a n))`` (:class:`GroupedQueryAttention`, ``gate``).  A block
    norms its sub-layers **on both sides**: ``h = x + N2(Attn(N1 x))``,
    ``y = h + N4(FF(N3 h))`` (:class:`HybridBlock`'s further norms).  The
    first ``num_dense_layers`` feed-forwards are dense gated layers of
    ``intermediate_size``, the others ``num_experts`` sigmoid-routed
    experts of ``moe_intermediate_size`` with a selection bias,
    ``num_experts_per_tok`` a token, weights over the sum of the chosen
    plus ``1e-20`` (``route_norm``), the routed sum times ``route_scale``,
    beside ``num_shared_experts`` (1 or 0) shared expert of the same
    width.  RMS norms at ``rms_norm_eps``, no bias anywhere, an untied
    head, and with ``mup_enabled`` the embedding times ``sqrt(hidden_size)``
    (false: a multiplier of 1).  What is not built is refused by name:
    another ``score_func`` than sigmoid, expert groups, weights not
    normalised over the chosen, a scaled rotary embedding, more shared
    experts than one, a tied head, another activation than silu, another
    layer type."""
    c = config
    if c.get("score_func", "sigmoid") != "sigmoid":
        raise ValueError(f"afmoe: score_func={c['score_func']!r} is not "
                         f"built (sigmoid)")
    for key in _AFMOE_ONE:
        if c.get(key, 1) not in (None, 1):
            raise ValueError(f"afmoe: {key}={c[key]!r} is not built (one "
                             f"group of experts)")
    if not c.get("route_norm", True):
        raise ValueError("afmoe: route_norm=False is not built (weights "
                         "over the sum of the chosen)")
    if c.get("rope_scaling"):
        raise ValueError(f"afmoe: rope_scaling={c['rope_scaling']!r} is "
                         f"not built")
    shared = c.get("num_shared_experts", 0)
    if shared not in (0, 1):
        raise ValueError(f"afmoe: num_shared_experts={shared!r} is not "
                         f"built (1 or 0)")
    if c.get("tie_word_embeddings"):
        raise ValueError("afmoe: tie_word_embeddings=True is not built "
                         "(the head is its own table)")
    if c.get("hidden_act", "silu") != "silu":
        raise ValueError(f"afmoe: hidden_act={c['hidden_act']!r} is not "
                         f"built (silu)")
    types = list(c["layer_types"])[:c["num_hidden_layers"]]
    for kind in types:
        if kind not in ("sliding_attention", "full_attention"):
            raise ValueError(f"afmoe: layer_types has {kind!r}: "
                             f"'sliding_attention' and 'full_attention' "
                             f"are what is built")
    hidden, heads = c["hidden_size"], c["num_attention_heads"]
    head_dim = c.get("head_dim") or hidden // heads
    eps, width = c.get("rms_norm_eps", 1e-5), c["moe_intermediate_size"]
    held = (c.get("experts_offset", 0),
            c.get("experts_held", c["num_experts"]))
    blocks = []
    for i, kind in enumerate(types):
        window = kind == "sliding_attention"
        attn = GroupedQueryAttention(
            hidden, heads, c["num_key_value_heads"], head_dim,
            window=c["sliding_window"] if window else None,
            rope_theta=float(c.get("rope_theta", 10000.0)),
            rotary_dim=head_dim if window else 0, qk_norm=True,
            norm_eps=eps, gate=True)
        ffn = HeldExperts(
            hidden, width, c["num_experts"], c["num_experts_per_tok"], held,
            shared=GatedFFN(hidden, width) if shared else None,
            scale=float(c.get("route_scale") or 1.0),
            normalize_eps=1e-20) if i >= c.get("num_dense_layers", 0) \
            else GatedFFN(hidden, c["intermediate_size"])
        blocks.append(HybridBlock(
            hidden, attn, ffn, eps, attn_post_norm=RMSNorm(hidden, eps),
            ffn_post_norm=RMSNorm(hidden, eps)))
    return HybridDecoder(
        c["vocab_size"], hidden, blocks, eps, max_len,
        embedding_multiplier=hidden ** 0.5 if c.get("mup_enabled") else 1.0)
