"""Decoder-only Transformer language model.

The reference's Transformer (nn/Transformer.scala:749, `TranslationModel`
/ `LanguageModel` modes) covers encoder-decoder and LM configurations;
this is the LM configuration as a standalone model family, built from
the same attention stack (nn/attention.py) plus:

* weight-tied embedding/output head (standard LM practice; the
  reference ties via `embeddingSharedWeights`),
* `jax.checkpoint` (rematerialization) per block when
  ``remat=True`` — trades recompute for activation memory so long
  sequences fit HBM,
* a causal+padding additive bias built once per batch.

TPU notes: the per-block compute is three dense matmuls + attention —
all MXU work; under a mesh, `parallel.tensor_parallel_rules
(column=[".*q_layer.*|.*k_layer.*|.*v_layer.*|.*filter_layer.*"],
row=[".*output_layer.*|.*out_layer.*"])` gives Megatron-style TP, and
`parallel.ring_attention` substitutes for in-block attention when the
sequence axis is sharded.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp

from bigdl_tpu.core.module import Module, ModuleList, Parameter
from bigdl_tpu.nn.attention import (SequenceBeamSearch,
                                    TransformerDecoderLayer,
                                    _residual_dropout, causal_bias,
                                    incremental_bias, padding_bias,
                                    position_encoding)
from bigdl_tpu.nn.linear import LookupTable
from bigdl_tpu.nn.normalization import LayerNormalization

__all__ = ["TransformerLM", "transformer_lm"]


class TransformerLM(Module):
    """``forward(tokens [B,T] int, 1-based; 0 = padding) → logits
    [B, T, vocab+1]``.

    Logit-axis convention (locked by test_train_then_generate_token_
    convention): the framework's criteria are 1-based — target token t
    trains logit index t-1 — so logit index 0 is token 1's TRAINED slot
    and the LAST index (vocab_size) is the only never-trained row.
    Generation therefore emits ``argmax + 1`` and masks the last row."""

    def __init__(self, vocab_size: int, hidden_size: int = 256,
                 num_layers: int = 4, num_heads: int = 4,
                 filter_size: int = 1024, max_len: int = 512,
                 dropout: float = 0.0, remat: bool = False,
                 padded_inputs: bool = True):
        super().__init__()
        self.hidden_size = hidden_size
        self.max_len = max_len
        self.remat = remat
        self.seq_parallel = False
        # pipeline-parallel routing (set_pipeline_parallel): when armed,
        # the block stack runs through the GPipe schedule over pipe_mesh
        self.pipe_mesh = None
        self.pipe_axis = "pipe"
        self.pipe_microbatches = 1
        # padded_inputs=False: contiguous LM batching (no token-0
        # padding) — the causal mask moves INSIDE the attention kernel
        # (flash skips above-diagonal blocks; no [B,H,T,T] bias is
        # materialized or streamed).  Padding in that mode fails loudly
        # like the sequence-parallel path.
        self.padded_inputs = padded_inputs
        self.embedding = LookupTable(vocab_size + 1, hidden_size)
        # N(0, 1/H) init (reference embeddingSharedWeights / T2T): with
        # the weight-tied head, unit-std embeddings would give init
        # logits of std sqrt(H) and a start loss far above ln(vocab)
        self.embedding.weight = Parameter(
            self.embedding.weight * hidden_size ** -0.5)
        self.blocks = ModuleList([
            TransformerDecoderLayer(hidden_size, num_heads, filter_size,
                                    attention_dropout=dropout,
                                    ffn_dropout=dropout,
                                    with_cross_attention=False)
            for _ in range(num_layers)])
        self.final_norm = LayerNormalization(hidden_size)

    def set_sequence_parallel(self, mesh, axis: str = "seq",
                              kernel=None,
                              head_axis=None) -> "TransformerLM":
        """Run every block's self-attention through ring attention over
        ``mesh[axis]`` (sequence/context parallelism — contexts longer
        than one chip's HBM; see parallel/ring_attention.py).  The
        projection weights are SHARED with the existing Attention
        modules, so this toggles execution strategy, not parameters.
        The ring applies the causal mask itself; padded batches are not
        supported on this path (contiguous LM batching has none): a
        padded batch raises ValueError eagerly, and NaN-poisons the
        output under jit (tracers can't raise on data)."""
        from bigdl_tpu.parallel.ring_attention import RingSelfAttention
        for blk in self.blocks:
            if isinstance(blk.self_attn, RingSelfAttention):
                # reconfiguration: update in place, never keep a stale
                # mesh/axis from an earlier call
                blk.self_attn.mesh = mesh
                blk.self_attn.seq_axis = axis
                blk.self_attn.ring_kernel = kernel
                blk.self_attn.head_axis = head_axis
            else:
                blk.self_attn = RingSelfAttention.from_attention(
                    blk.self_attn, mesh, axis, causal=True,
                    kernel=kernel, head_axis=head_axis)
        self.seq_parallel = True
        return self

    def set_pipeline_parallel(self, mesh, axis: str = "pipe",
                              num_microbatches: int = None) \
            -> "TransformerLM":
        """Run the block stack through the GPipe schedule over
        ``mesh[axis]`` (embedding/posenc and final_norm/head stay
        replicated around it; the blocks are homogeneous
        TransformerDecoderLayers, so stage parameters stack and shard
        over the pipe axis).  Like the sequence-parallel path, the
        causal mask moves INSIDE the attention kernel (the per-batch
        padding bias cannot ride the microbatch ring), so padded
        batches are rejected the same way.  ``mesh=None`` disarms."""
        if mesh is not None:
            n = len(self.blocks)
            s = mesh.shape[axis]
            if n % s:
                raise ValueError(
                    f"set_pipeline_parallel: {n} blocks do not divide "
                    f"into {s} stages on axis {axis!r}")
        self.pipe_mesh = mesh
        self.pipe_axis = axis
        self.pipe_microbatches = (num_microbatches
                                  or (mesh.shape[axis] if mesh is not None
                                      else 1))
        return self

    def _blocks_gpipe(self, x):
        """Run the (homogeneous) blocks as pipeline stages: stack
        per-block leaves onto [S, per_stage, ...] and stream the batch
        through parallel.pipeline.gpipe.  Gradients flow through the
        schedule via autodiff (the Optimizer's outer value_and_grad)."""
        from bigdl_tpu.parallel.pipeline import gpipe
        mesh, axis = self.pipe_mesh, self.pipe_axis
        s = mesh.shape[axis]
        blocks = list(self.blocks)
        per_stage = len(blocks) // s
        flats = [jax.tree_util.tree_flatten(b)[0] for b in blocks]
        treedef0 = jax.tree_util.tree_structure(blocks[0])
        stacked_leaves = [
            jnp.stack(ls).reshape((s, per_stage) + ls[0].shape)
            for ls in zip(*flats)]
        stacked = jax.tree_util.tree_unflatten(treedef0, stacked_leaves)

        def stage_apply(stage_tree, x_mb):
            def one(i, acc):
                blk = jax.tree_util.tree_map(
                    lambda l: jax.lax.dynamic_index_in_dim(
                        l, i, 0, keepdims=False), stage_tree)
                return blk.forward(acc, self_bias=None, self_causal=True)
            return jax.lax.fori_loop(0, per_stage, one, x_mb)

        return gpipe(stage_apply, stacked, x, mesh, axis,
                     self.pipe_microbatches)

    def forward(self, tokens):
        B, T = tokens.shape
        if T > self.max_len:
            raise ValueError(
                f"sequence length {T} exceeds max_len={self.max_len}")
        # 0 is padding; clamp for the gather, bias masks it out of loss
        x = self.embedding.forward(jnp.maximum(tokens, 1))
        x = x * (self.hidden_size ** 0.5)
        x = x + position_encoding(T, self.hidden_size, dtype=x.dtype)
        pipe = self.pipe_mesh is not None
        causal_in_kernel = False
        if self.seq_parallel or pipe or not self.padded_inputs:
            # Both modes handle causality INSIDE the attention kernel
            # (the ring applies it per block pair; the dense causal
            # flash path skips above-diagonal blocks) — an additive
            # bias would defeat their O-of-memory/traffic win.  Padded
            # batches are NOT supported on either — fail loudly instead
            # of silently diverging (contiguous LM batching has none):
            # eagerly that's a ValueError; under jit (tokens traced)
            # the activations are NaN-poisoned so the loss/logits are
            # unmistakably wrong, not subtly so
            mode = ("sequence-parallel" if self.seq_parallel
                    else "pipeline-parallel" if pipe
                    else "padded_inputs=False")
            if not isinstance(tokens, jax.core.Tracer):
                if bool(jnp.any(tokens == 0)):
                    raise ValueError(
                        f"{mode} TransformerLM does not support padded "
                        "batches (token 0): this path has no padding "
                        "mask; use contiguous LM batching")
            else:
                x = x + jnp.where(jnp.any(tokens == 0),
                                  jnp.asarray(jnp.nan, x.dtype),
                                  jnp.asarray(0, x.dtype))
            bias = None
            causal_in_kernel = not self.seq_parallel
        else:
            bias = causal_bias(T, dtype=x.dtype) \
                + padding_bias(tokens).astype(x.dtype)

        if pipe:
            x = self._blocks_gpipe(x)
        else:
            for blk in self.blocks:
                if self.remat:
                    # recompute the block in backward instead of storing
                    # its activations (jax.checkpoint); module buffers
                    # are not mutated in these blocks so the functional
                    # wrap is safe
                    def run(blk_, x_, bias_):
                        return blk_.forward(x_, self_bias=bias_,
                                            self_causal=causal_in_kernel)
                    x = jax.checkpoint(run)(blk, x, bias)
                else:
                    x = blk.forward(x, self_bias=bias,
                                    self_causal=causal_in_kernel)
        x = self.final_norm(x)
        # weight-tied output head: logits against the embedding matrix
        emb = self.embedding.weight            # [vocab+1, H]
        return jnp.einsum("bth,vh->btv", x, emb)


    # ---- incremental decoding (KV cache) -------------------------------

    def cache_layers(self):
        """Each layer's cache as the serving slot pool allocates it:
        here every layer keeps a ``full`` row of ``max_len`` positions
        (a windowed layer would declare ``("ring", window)``)."""
        return (("full", self.max_len),) * len(self.blocks)

    def decode_key_block(self, caches):
        """Places of a row that the per-row decode step's attention
        reads at a time, or None where it reads every row whole whatever
        is live (``ops.decode_key_block``): the serving pool counts what
        its decode program reads by this."""
        from bigdl_tpu.ops import attention_kernels
        leaf = caches["layers"][0]["self"]
        return attention_kernels.decode_key_block(
            leaf["k"].shape, leaf["v"].shape, leaf["k"].dtype)

    def chunk_key_block(self, caches):
        """Places of its slot's row that a prefill chunk's attention reads
        at a time (``ops.chunk_attention``: live blocks only, on every
        backend): the serving pool counts what its chunk programs read
        by this."""
        from bigdl_tpu.ops import attention_kernels
        return attention_kernels.chunk_key_block(
            caches["layers"][0]["self"]["k"].shape)

    def init_cache(self, batch: int, dtype=jnp.float32):
        """Per-block KV caches sized to ``max_len``, plus the per-slot
        padding flags the full forward expresses via padding_bias (one
        pytree, so everything flows through scan/while_loop and beam
        gathering together)."""
        return {
            "layers": [{"self": blk.self_attn.init_cache(
                batch, self.max_len, dtype)} for blk in self.blocks],
            "pad": jnp.zeros((batch, self.max_len), bool),
        }

    def decode_step(self, tokens, index, caches, with_logits=True,
                    active=None):
        """One token step: ``tokens [B, 1]`` at position ``index`` →
        (logits [B, vocab+1], new caches).  Equivalent to column
        ``index`` of the full forward incl. its padding mask (tested),
        at O(T) cost instead of O(T^2).  ``with_logits=False`` skips
        the vocab projection (prefill).

        ``index`` is a scalar (every row at the same position:
        ``generate()``, beam search) or has shape ``[B]``: row ``b`` is
        written and masked at ``index[b]`` — the serving slot pool,
        whose rows are requests at positions of their own.  Same
        mathematics either way; the rank of ``index`` only decides how
        the new key and value reach the cache (see
        :meth:`_decode_step_rows`).

        ``active [B]`` false (with ``index [B]``) marks a row that only
        rides along, as an idle lane of the slot pool does: every row
        writes its position's K/V, so such a row must write somewhere
        provably unread.  ``max_len - 1`` is beyond every prefill
        query's mask and is always freshly rewritten by an occupant's
        own decode before it is attended — a stale index would instead
        clobber a co-scheduled chunked prefill's freshly written
        positions (test_decode_does_not_disturb_inactive_rows)."""
        if jnp.ndim(index) == 1:
            index, lengths = self._row_places(index, active)
            return self._decode_step_rows(tokens, index, lengths, caches,
                                          with_logits)
        pad = jax.lax.dynamic_update_slice(
            caches["pad"], tokens == 0, (0, index))
        x = self.embedding.forward(jnp.maximum(tokens, 1))
        x = x * (self.hidden_size ** 0.5)
        pos = jax.lax.dynamic_slice_in_dim(
            position_encoding(self.max_len, self.hidden_size,
                              dtype=x.dtype), index, 1, axis=0)
        x = x + pos[None]
        bias = incremental_bias(self.max_len, index, pad, x.dtype)
        new_layers = []
        for blk, cache in zip(self.blocks, caches["layers"]):
            x, nc = blk.forward(x, self_bias=bias, cache=cache,
                                cache_index=index)
            new_layers.append(nc)
        new_caches = {"layers": new_layers, "pad": pad}
        if not with_logits:
            return None, new_caches
        return self._head(x), new_caches

    def _decode_step_rows(self, tokens, index, lengths, caches,
                          with_logits):
        """:meth:`decode_step` with a position per row (``index [B]``;
        ``lengths [B]`` is ``index + 1``, or 0 for a row that only rides
        along and whose result nobody reads).

        Each row's new key, value and padding flag go into the cache
        with a ``dynamic_update_slice`` of their own at
        ``(b, 0, index[b], 0)`` — a static loop over ``B`` — and
        attention then reads the cache where it lies.  A
        ``dynamic_update_slice`` touches only its window, so a donated
        cache is updated in place in whatever layout it has.

        Why not ``jax.vmap`` of the scalar path: a batched
        ``dynamic_update_slice`` whose start differs per row becomes a
        ``scatter`` over the whole cache leaf.  On a TPU a leaf
        ``f32[B, h, T, d]`` with ``d`` = 64 lives as ``{2,3,1,0}``
        (positions minor: 64 would fill half a lane tile), the
        compiler expands the scatter into a loop that wants
        ``{3,2,1,0}``, and transposes the leaf in and back out: four
        copies of the whole cache per layer and step, three quarters of
        a served token's time (PERF.md, PR 27).  The layout is the
        compiler's to choose, so nothing here names it;
        tests/test_tpu_compile.py holds the compiled programs to "no
        cache-sized copy".

        Attention is inlined as in :meth:`prefill_chunk` (the K/V
        written are the K/V attended), expecting eval mode.  It goes
        through ``ops.decode_attention``: on a TPU a kernel that reads,
        of each row, the blocks that hold live positions; elsewhere the
        XLA product over the whole row under ``incremental_bias``'s
        mask."""
        from bigdl_tpu.ops import attention_kernels
        pad = self._write_rows(caches["pad"], tokens == 0, index)
        x = self._embed(tokens)
        pos = jnp.take(position_encoding(self.max_len, self.hidden_size,
                                         dtype=x.dtype), index, axis=0)
        x = x + pos[:, None]
        new_layers = []
        for blk, cache in zip(self.blocks, caches["layers"]):
            attn = blk.self_attn
            xn = blk.self_norm(x)
            old = cache["self"]
            # one position per row: [B, h, 1, d] into a leaf [B, h, T, d]
            k = self._write_rows(
                old["k"], attn._split_heads(attn.k_layer(xn)), index)
            v = self._write_rows(
                old["v"], attn._split_heads(attn.v_layer(xn)), index)
            new_layers.append({"self": {"k": k, "v": v}})
            q = attn._split_heads(attn.q_layer(xn))
            ctxt = attention_kernels.decode_attention(q, k, v, lengths, pad)
            x = self._block_tail(blk, x, attn._combine_heads(ctxt))
        new_caches = {"layers": new_layers, "pad": pad}
        if not with_logits:
            return None, new_caches
        return self._head(x), new_caches

    # what the pooled walks share (eval mode: the pool runs an eval clone)

    def _row_places(self, index, active):
        """``(index, lengths)`` of a per-row step: what a row's query may
        attend is its positions up to its own; a row that only rides
        along (``active`` false) attends nothing and writes at ``max_len
        - 1`` (:meth:`decode_step` says why there)."""
        lengths = index + 1
        if active is not None:
            lengths = jnp.where(active, lengths, 0)
            index = jnp.where(active, index, jnp.int32(self.max_len - 1))
        return index, lengths

    def _embed(self, tokens):
        # 0 is padding; clamp for the gather, the flags mask it
        x = self.embedding.forward(jnp.maximum(tokens, 1))
        return x * (self.hidden_size ** 0.5)

    @staticmethod
    def _write_rows(leaf, new, index):
        """``new``'s row ``b`` (one position: ``[B, h, 1, d]`` for a K/V
        leaf ``[B, h, T, d]``, ``[B, 1]`` for the padding flags ``[B,
        T]``) into ``leaf`` at position ``index[b]``, a
        ``dynamic_update_slice`` a row (:meth:`_decode_step_rows` says
        why)."""
        new = new.astype(leaf.dtype)
        for b in range(new.shape[0]):
            at = ((b, index[b]) if leaf.ndim == 2
                  else (b, 0, index[b], 0))
            leaf = jax.lax.dynamic_update_slice(leaf, new[b:b + 1], at)
        return leaf

    @staticmethod
    def _block_tail(blk, x, ctxt):
        """A block from its attention's context on (``ctxt [B, T, H]``,
        the heads joined): the output projection and the feed-forward,
        each on its residual."""
        y = blk.self_attn.output_layer(ctxt)
        x = x + _residual_dropout(y, blk.ffn_dropout, blk.training)
        y = blk.ffn(blk.ffn_norm(x))
        return x + _residual_dropout(y, blk.ffn_dropout, blk.training)

    def _head(self, x):
        """``x [B, 1, H]`` -> logits ``[B, vocab+1]`` against the tied
        embedding."""
        x = self.final_norm(x)
        logits = jnp.einsum("bth,vh->btv", x, self.embedding.weight)
        return logits[:, 0]

    def prefill_kv(self, ptoks):
        """Per-layer K/V for every position of ``ptoks`` (a prompt minus
        its final token) as compact ``[B, heads, T, head_dim]`` arrays,
        plus the ``[B, T]`` bool padding flags — the parallel-prefill
        compute WITHOUT a max_len cache allocation.  ``_prefill``
        scatters these into the front of a fresh cache; the serving slot
        pool (serving/generation.py) scatters the same rows into
        individual pool slots instead, so both prefill paths share one
        implementation and cannot drift."""
        _B, T = ptoks.shape
        pad_cols = ptoks == 0
        x = self.embedding.forward(jnp.maximum(ptoks, 1))
        x = x * (self.hidden_size ** 0.5)
        x = x + position_encoding(T, self.hidden_size, dtype=x.dtype)
        bias = causal_bias(T, dtype=x.dtype) \
            + padding_bias(ptoks).astype(x.dtype)
        from bigdl_tpu.ops import dot_product_attention
        layers = []
        for blk in self.blocks:
            # inline the block's attention so the K/V computed for the
            # cache are the ones used (blk.forward would recompute the
            # norm and all projections a second time)
            attn = blk.self_attn
            xn = blk.self_norm(x)
            k = attn._split_heads(attn.k_layer(xn))
            v = attn._split_heads(attn.v_layer(xn))
            layers.append({"k": k, "v": v})
            if blk.training and attn.attention_dropout > 0.0:
                # rare train-mode prefill: the materialized-dropout path
                # must run; recomputing k/v there is acceptable
                y = attn(xn, None, bias)
            else:
                q = attn._split_heads(attn.q_layer(xn))
                ctxt = dot_product_attention(q, k, v, bias)
                y = attn.output_layer(attn._combine_heads(ctxt))
            x = x + _residual_dropout(y, blk.ffn_dropout, blk.training)
            y = blk.ffn(blk.ffn_norm(x))
            x = x + _residual_dropout(y, blk.ffn_dropout, blk.training)
        return layers, pad_cols

    def prefill_chunk(self, toks, index, caches, slot=None):
        """KV-carry-in prefill: write K/V + padding flags for ``toks
        [B, W]`` at positions ``[index, index+W)`` of an incremental
        cache whose positions ``< index`` are already filled.  The chunk
        attends to the carried-in prefix AND itself (causally), so a
        long prompt can be prefilled in fixed-width chunks interleaved
        with decode steps instead of one monolithic forward — the
        static-shape cousin of Sarathi-style chunked prefill.  Same
        contract as :meth:`decode_step` (of which this is the W-token
        generalization, equivalent to columns ``[index, index+W)`` of
        the full forward); no logits are produced (prefill never needs
        the vocab projection).

        Two cache layouts:

        * ``slot=None`` — per-request rows: caches carry ``B`` rows
          aligned with ``toks``.
        * ``slot`` given (a traced scalar) — POOLED: caches hold S slot
          rows, ``toks`` is [1, W], and only ``slot``'s row is touched.
          The cache write covers exactly the chunk window (so a DONATED
          pool updates in place at O(chunk) write cost — writing a
          whole gathered row back was measured to cost the full row's
          traffic per chunk).

        **What a chunk reads.**  Its queries attend their rows where they
        lie after the write, **the key blocks up to the chunk's last
        position and no place beyond** (``ops.chunk_attention``; blocks
        of :meth:`chunk_key_block` places): a chunk at position 0 reads
        one block of its row, one at 1,920 of 2,048 all eight.  The
        count of blocks is traced, a loop's length or a kernel's
        prefetched scalar, and not a shape: a program a length would be a
        program a block count at every width, and the pool keeps its
        chunk programs by width alone.

        Attention is inlined like :meth:`prefill_kv` (the K/V written
        to the cache are the K/V attended), expecting eval mode — the
        serving slot pool always runs an eval clone."""
        from bigdl_tpu.ops import attention_kernels
        _B, W = toks.shape
        row = 0 if slot is None else slot
        pad = jax.lax.dynamic_update_slice(caches["pad"], toks == 0,
                                           (row, index))
        x = self._embed(toks)
        pos = jax.lax.dynamic_slice_in_dim(
            position_encoding(self.max_len, self.hidden_size,
                              dtype=x.dtype), index, W, axis=0)
        x = x + pos[None]
        new_layers = []
        for blk, cache in zip(self.blocks, caches["layers"]):
            attn = blk.self_attn
            xn = blk.self_norm(x)
            old = cache["self"]
            k, v = (jax.lax.dynamic_update_slice(
                old[n], attn._split_heads(layer(xn)).astype(old[n].dtype),
                (row, 0, index, 0))
                for n, layer in (("k", attn.k_layer), ("v", attn.v_layer)))
            new_layers.append({"self": {"k": k, "v": v}})
            q = attn._split_heads(attn.q_layer(xn))
            ctxt = attention_kernels.chunk_attention(q, k, v, row, index,
                                                     pad)
            x = self._block_tail(blk, x, attn._combine_heads(ctxt))
        return {"layers": new_layers, "pad": pad}

    def decode_step_with_chunk(self, tokens, index, caches, active, toks,
                               chunk_index, slot):
        """A pool's pass that carries a prefill chunk, as one walk of the
        blocks: what ``prefill_chunk(toks, chunk_index, caches,
        slot=slot)`` followed by ``decode_step(tokens, index, .,
        active=active)`` gives, ``(logits [B, vocab+1], caches)``, in one
        program (the serving pool takes this entry for a chunk due while
        slots decode).  ``tokens [B, 1]``, ``index [B]``, ``active [B]``;
        ``toks [1, W]`` into row ``slot`` at ``chunk_index``.

        The ``B`` rows and the chunk's ``W`` tokens are one residual
        stream ``[1, B + W, H]``: a block's norms, its output projection
        and its feed-forward run once over both, so the pass reads those
        weights once.  In between each half projects its own queries,
        keys and values and keeps its own attention.  **Both halves write
        first** (the chunk's window, then the rows' positions: one chain
        of in-place updates a leaf) **and then both attend the leaf as it
        lies**: the chunk the key blocks up to its last position
        (:meth:`prefill_chunk`, "What a chunk reads"), the rows what is
        live (:meth:`_decode_step_rows`).  That is the data flow of
        chunk first, then rows: a place a row writes is one the chunk's
        queries cannot attend (another row; past the chunk's last
        position; or, where row ``slot`` decodes in the same pass from a
        place inside a padded last chunk, a place the chunk's own flags
        mask), and row ``slot``, decoding its first token in the pass of
        its prompt's last chunk, attends what the chunk wrote.  With a
        read between the two writes the compiler copied every key leaf
        twice a layer once the program had 24 layers
        (``tests/test_tpu_compile.py`` holds it to none).

        Why q, k and v are not shared too: on a v5e at OPT-1.3B's serving
        shapes (6 rows beside 64 tokens) the walk as it stands took 8.78
        ms where the two programs took 11.31, and with those three
        products also made over the 70 rows together 9.31: the weights
        stream in behind the cache writes and the rows' attention either
        way, and the joint product's parting into heads cost more than
        the second, small product (PERF.md, PR 43)."""
        from bigdl_tpu.ops import attention_kernels
        B, W = tokens.shape[0], toks.shape[1]
        chunk_pad = jax.lax.dynamic_update_slice(
            caches["pad"], toks == 0, (slot, chunk_index))
        index, lengths = self._row_places(index, active)
        pad = self._write_rows(chunk_pad, tokens == 0, index)
        x, xc = self._embed(tokens), self._embed(toks)
        table = position_encoding(self.max_len, self.hidden_size,
                                  dtype=x.dtype)
        x = x + jnp.take(table, index, axis=0)[:, None]
        xc = xc + jax.lax.dynamic_slice_in_dim(table, chunk_index, W,
                                               axis=0)[None]
        # the rows, one position each, then the chunk: [1, B + W, H]
        h = jnp.concatenate([x.reshape(1, B, -1), xc], axis=1)
        new_layers = []
        for blk, cache in zip(self.blocks, caches["layers"]):
            attn = blk.self_attn
            qkv = (attn.q_layer, attn.k_layer, attn.v_layer)
            hn = blk.self_norm(h)
            # each half projects its own queries, keys and values, in the
            # shape its own walk has (a row's one position [B, heads, 1,
            # d], the chunk [1, heads, W, d])
            q, k, v = (attn._split_heads(layer(hn[0, :B, None]))
                       for layer in qkv)
            qc, kc, vc = (attn._split_heads(layer(hn[:, B:]))
                          for layer in qkv)
            old = cache["self"]
            k_leaf = jax.lax.dynamic_update_slice(
                old["k"], kc.astype(old["k"].dtype),
                (slot, 0, chunk_index, 0))
            v_leaf = jax.lax.dynamic_update_slice(
                old["v"], vc.astype(old["v"].dtype),
                (slot, 0, chunk_index, 0))
            k_leaf = self._write_rows(k_leaf, k, index)
            v_leaf = self._write_rows(v_leaf, v, index)
            ctxt_chunk = attention_kernels.chunk_attention(
                qc, k_leaf, v_leaf, slot, chunk_index, chunk_pad)
            new_layers.append({"self": {"k": k_leaf, "v": v_leaf}})
            ctxt = attention_kernels.decode_attention(q, k_leaf, v_leaf,
                                                      lengths, pad)
            h = self._block_tail(blk, h, jnp.concatenate(
                [attn._combine_heads(ctxt).reshape(1, B, -1),
                 attn._combine_heads(ctxt_chunk)], axis=1))
        return self._head(h[0, :B, None]), \
            {"layers": new_layers, "pad": pad}

    def _prefill(self, prompt, caches):
        """Write prompt[:, :-1]'s per-layer K/V into the caches with ONE
        dense forward over the whole prompt (parallel over T, MXU-
        friendly) rather than Tp sequential decode steps; the last
        prompt token is fed by the first decode step instead."""
        Tp = prompt.shape[1]
        if Tp == 1:
            return caches
        layers_kv, pad = self.prefill_kv(prompt[:, :-1])
        pad_cols = jax.lax.dynamic_update_slice(caches["pad"], pad, (0, 0))
        new_layers = []
        for kv, cache in zip(layers_kv, caches["layers"]):
            old = cache["self"]
            new_layers.append({"self": {
                "k": jax.lax.dynamic_update_slice(
                    old["k"], kv["k"].astype(old["k"].dtype),
                    (0, 0, 0, 0)),
                "v": jax.lax.dynamic_update_slice(
                    old["v"], kv["v"].astype(old["v"].dtype),
                    (0, 0, 0, 0)),
            }})
        return {"layers": new_layers, "pad": pad_cols}

    @staticmethod
    def _mask_untrained_logit(logits):
        """The framework's criteria are 1-based (ClassNLL/CrossEntropy:
        target token t trains logit index t-1), so logit index
        ``vocab_size`` (the last row of the tied head) is never a target
        and stays untrained noise — it must not win argmax/top_k.
        (Logit index 0 IS trained: it is token 1's slot.)"""
        neg = jnp.asarray(-1e9, logits.dtype)
        return logits.at[..., -1].set(neg)

    def generate(self, prompt, max_new_tokens: int, eos_id=None):
        """Greedy continuation: ``prompt [B, Tp]`` →
        ``[B, Tp + max_new_tokens]``; positions after ``eos_id`` (when
        given) are padded with 0."""
        B, Tp = prompt.shape
        if Tp + max_new_tokens > self.max_len:
            raise ValueError(
                f"prompt {Tp} + {max_new_tokens} new tokens exceeds "
                f"max_len={self.max_len}")
        prompt = jnp.asarray(prompt, jnp.int32)
        caches = self._prefill(prompt, self.init_cache(B))

        def gen_step(carry, t):
            tok, caches, done = carry
            logits, caches = self.decode_step(tok, t, caches)
            # logit index i is token i+1's slot (1-based criteria), so
            # the emitted token id is argmax + 1
            nxt = jnp.argmax(self._mask_untrained_logit(logits),
                             axis=-1).astype(jnp.int32) + 1
            nxt = jnp.where(done, 0, nxt)
            if eos_id is not None:
                done = done | (nxt == eos_id)
            return (nxt[:, None], caches, done), nxt

        done0 = jnp.zeros((B,), bool)
        (_, _, _), toks = jax.lax.scan(
            gen_step, (prompt[:, -1:], caches, done0),
            Tp - 1 + jnp.arange(max_new_tokens))
        return jnp.concatenate([prompt, toks.T], axis=1)

    def generate_beam(self, prompt, beam_size: int = 4,
                      max_new_tokens: int = 20, eos_id: int = -1,
                      alpha: float = 0.6):
        """Length-normalized beam search continuation via
        nn.SequenceBeamSearch; returns (sequences [B, beam, T_new],
        scores [B, beam]).  ``eos_id=-1`` (no EOS) decodes to the full
        budget."""
        B, Tp = prompt.shape
        if Tp + max_new_tokens > self.max_len:
            raise ValueError(
                f"prompt {Tp} + {max_new_tokens} new tokens exceeds "
                f"max_len={self.max_len}")
        prompt = jnp.asarray(prompt, jnp.int32)
        caches = self._prefill(prompt, self.init_cache(B))
        # the search feeds a zero "start" id at step 0; carry the true
        # last prompt token inside the cache pytree so it rides the
        # per-beam replication/gathering
        cache = dict(caches, tok0=prompt[:, -1:])
        vocab = self.embedding.weight.shape[0]
        # the search operates in LOGIT-INDEX space (ids start at 0 =
        # pad/start, reference SequenceBeamSearch.scala); our criteria
        # are 1-based, so EOS token id t lives at logit index t-1
        search = SequenceBeamSearch(
            vocab, beam_size, alpha, max_new_tokens,
            eos_id - 1 if eos_id >= 0 else eos_id)

        def logits_fn(ids, i, cache):
            # ids are the previous step's logit indices → token id + 1
            tok = jnp.where(i == 0, cache["tok0"],
                            ids.astype(jnp.int32) + 1)
            logits, sub = self.decode_step(
                tok, Tp - 1 + i,
                {"layers": cache["layers"], "pad": cache["pad"]})
            return self._mask_untrained_logit(logits), dict(
                sub, tok0=cache["tok0"])

        search.set_logit_fn(logits_fn)
        seqs, scores = search.search(B, cache)
        # back to token-id space; re-pad positions after the first EOS
        # (they were 0 in index space and must stay 0 in token space)
        toks = seqs + 1
        if eos_id >= 0:
            eos_before = jnp.cumsum(toks == eos_id, axis=-1) \
                - (toks == eos_id)
            toks = jnp.where(eos_before > 0, 0, toks)
        return toks, scores


def transformer_lm(vocab_size: int, hidden_size: int = 256,
                   num_layers: int = 4, num_heads: int = 4,
                   filter_size: int = 1024, max_len: int = 512,
                   dropout: float = 0.0, remat: bool = False,
                   padded_inputs: bool = True) -> TransformerLM:
    """Factory mirroring the models/* builder convention."""
    return TransformerLM(vocab_size, hidden_size, num_layers, num_heads,
                         filter_size, max_len, dropout, remat,
                         padded_inputs=padded_inputs)
