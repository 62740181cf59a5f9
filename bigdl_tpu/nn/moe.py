"""Mixture-of-Experts with expert parallelism.

The reference's closest layer is MixtureTable (nn/MixtureTable.scala —
a gater weighting expert outputs on ONE node, no parallelism); real
expert parallelism is new TPU-first capability (SURVEY §2.6: EP absent
from the reference).

Design: top-k token routing with load-balancing auxiliary loss (the
standard Shazeer/Switch recipe).  Three execution paths:

* dense (single device / no expert axis): every expert runs over all
  tokens via ``vmap`` over the stacked expert parameters; outputs combine
  with the routing weights.  O(E·T) compute — exact, used for tests and
  small E.
* expert-parallel all_to_all (``set_mesh(..., capacity_factor=f)``) —
  THE scalable path: tokens are sharded over the expert axis alongside
  the experts; each device builds a capacity-bounded dispatch for its
  local S = B·T/n tokens (position-in-expert via cumsum, overflow
  DROPPED per the Switch policy), ships [E, C, H] expert buffers with
  ``lax.all_to_all``, runs its E/n local experts over the n·C received
  slots, and reverses the exchange to combine.  Per-device activation
  memory is O(f·k·B·T·H/n) — tokens/device, NOT the full batch.
* expert-parallel psum fallback (``capacity_factor=None``): each device
  computes its local experts' contribution over fully-replicated
  activations and psums.  Exact (no capacity drops) but O(B·T·H)
  replicated memory — right for small E / small batches only.

The experts' parameters are **stacked leaves** (a leading expert axis),
stacked once when the layer is built: no path re-stacks a Python list of
modules on every call.

:class:`HeldExperts` is the serving-side layer: one chip's share of a
sigmoid-routed layer of gated experts (or all of it), no capacity and no
drop; a batched product over every held stack for a share's step or
chunk, and the pairs laid out by expert in tiles (``ops.expert_kernels``)
for a longer call and for a layer that holds every expert.
"""

from __future__ import annotations

import functools
from typing import Callable, List, Optional

import jax
import jax.numpy as jnp
from jax.sharding import Mesh, PartitionSpec as P

from bigdl_tpu.core.module import Module, Parameter
from bigdl_tpu.telemetry import collectives as _coll
from bigdl_tpu.nn.linear import Linear
from bigdl_tpu.ops import attention_kernels, expert_kernels
from bigdl_tpu.utils.rng import next_key
from bigdl_tpu.parallel.mesh import pin_replicated, shard_map_compat

__all__ = ["MoE", "HeldExperts", "route_top_k"]

ROUTING = 5     # what a call of HeldExperts counts (``forward``)

# Per-device (inside-shard_map) buffer shapes of the most recent a2a
# trace — a debug/test hook (module attrs would pollute the pytree).
LAST_A2A_SHAPES = {}


def route_top_k(scores, k: int, normalize: bool = True, bias=None,
                eps: float = 0.0):
    """Routing from scores to weights, the one place it is decided:
    ``scores [..., E]`` (softmax probabilities, sigmoids: any
    non-negative score) -> ``(experts [..., k] int32, weights [..., k])``.
    The ``k`` largest of ``scores + bias`` are chosen (``bias [E]``: a
    selection bias that balances load and never reaches the weights);
    each weight is the chosen expert's own score and, with
    ``normalize``, over the sum of the chosen plus ``eps``
    (``norm_topk_prob``; some families publish ``+ 1e-6`` there)."""
    ranked = scores if bias is None else scores + bias
    _, idx = jax.lax.top_k(ranked, k)
    vals = jnp.take_along_axis(scores, idx, axis=-1)
    if normalize:
        total = jnp.sum(vals, axis=-1, keepdims=True)
        vals = vals / (total + eps if eps else total)
    return idx.astype(jnp.int32), vals


class MoE(Module):
    """Top-k routed mixture of experts over position-wise expert modules.

    experts: list of identical Modules mapping [..., H] -> [..., H]
    (e.g. FeedForwardNetwork), stacked here once into ``self.experts``:
    one module of the same class whose every leaf has a leading expert
    axis (apply it under ``vmap``).  ``forward(x)`` takes [B, T, H].
    After a forward, ``self.aux_loss`` holds the load-balancing loss
    (mean over tokens of E · Σ_e f_e · p_e) to be added to the training
    objective by the caller.
    """

    def __init__(self, hidden_size: int, experts: List[Module],
                 top_k: int = 2):
        super().__init__()
        self.hidden_size = hidden_size
        self.top_k = top_k
        self.num_experts = len(experts)
        self.experts = jax.tree_util.tree_map(
            lambda *leaves: jnp.stack(leaves), *list(experts))
        self.gate = Linear(hidden_size, self.num_experts, with_bias=False)
        self.aux_loss = jnp.zeros(())
        # overflow-drop fraction of the last a2a forward (0 on the
        # dense/psum paths, which never drop)
        self.drop_rate = jnp.zeros(())
        self.expert_mesh = None
        self.expert_axis = "expert"
        self.capacity_factor = None

    def set_mesh(self, mesh: Mesh, axis: str = "expert",
                 capacity_factor: Optional[float] = None) -> "MoE":
        """Route ``forward`` through the expert-parallel path on this
        mesh, so the layer composes with the Optimizer (whose jitted
        step just calls ``model.forward``).

        ``capacity_factor``: when set, use capacity-based all_to_all
        token dispatch (per-expert, per-source-device capacity
        C = max(1, round(f·k·S/E)) with S = B·T/n local tokens; tokens
        beyond capacity are dropped, Switch-style).  ``None`` keeps the
        exact psum fallback (replicated activations — small E only)."""
        self.expert_mesh = mesh
        self.expert_axis = axis
        self.capacity_factor = capacity_factor
        return self

    # -- routing -----------------------------------------------------------

    def _gate_probs(self, x):
        """Softmax routing probabilities [B, T, E] (fp32)."""
        logits = self.gate(x)  # [B, T, E]
        return jax.nn.softmax(logits.astype(jnp.float32), axis=-1)

    def _set_aux_loss(self, probs, mask):
        """Switch-style load-balancing loss:
        E · Σ_e (fraction routed to e)·(mean prob of e)."""
        frac = jnp.mean(mask.astype(jnp.float32), axis=(0, 1))
        mean_p = jnp.mean(probs, axis=(0, 1))
        self.aux_loss = self.num_experts * jnp.sum(frac * mean_p)

    def _topk_mask(self, probs):
        top_vals, _ = jax.lax.top_k(probs, self.top_k)
        return probs >= top_vals[..., -1:]

    def _route(self, x, probs=None):
        """Returns combine weights [B, T, E] (zero for non-top-k) and
        stores the load-balancing aux loss.  ``probs`` lets a caller
        that already ran the gate avoid running it twice."""
        if probs is None:
            probs = self._gate_probs(x)
        idx, vals = route_top_k(probs, self.top_k)
        hot = jax.nn.one_hot(idx, self.num_experts, dtype=probs.dtype)
        weights = jnp.einsum("...k,...ke->...e", vals, hot)
        self._set_aux_loss(probs, jnp.sum(hot, axis=-2) > 0)
        return weights.astype(x.dtype)

    @staticmethod
    def _apply_stacked(stacked, x):
        """vmap one expert-apply over the stacked leading axis; x is
        shared across experts.  Returns [E, B, T, H]."""
        def one(tree):
            return tree(x)
        return jax.vmap(one, in_axes=(0,))(stacked)

    # -- dense path --------------------------------------------------------

    def forward(self, x):
        # reset so the telemetry never carries a stale a2a value onto a
        # path that cannot drop (comment contract at __init__)
        self.drop_rate = jnp.zeros(())
        if self.expert_mesh is not None:
            return self.forward_on_mesh(x, self.expert_mesh,
                                        self.expert_axis)
        weights = self._route(x)  # [B, T, E]
        outs = self._apply_stacked(self.experts, x)  # [E,B,T,H]
        return jnp.einsum("ebth,bte->bth", outs, weights)

    # -- expert-parallel paths --------------------------------------------

    def _dispatch_combine(self, probs, capacity: int):
        """Capacity-bounded dispatch/combine tensors for S local tokens.

        probs [S, E] fp32 → (dispatch [S, E, C] 0/1, combine [S, E, C]).
        Slot-by-slot greedy assignment (top-1 choices claim positions
        before top-2, the Switch/GShard priority); position-in-expert by
        cumsum over the device-local token order; tokens whose position
        exceeds the capacity are dropped (their combine weight is 0 —
        the residual stream carries them unchanged)."""
        S, E = probs.shape
        top_idx, top_w = route_top_k(probs, self.top_k)   # renormalized
        dispatch = jnp.zeros((S, E, capacity), jnp.float32)
        combine = jnp.zeros((S, E, capacity), jnp.float32)
        counts = jnp.zeros((E,), jnp.int32)
        kept = jnp.zeros((), jnp.float32)
        for slot in range(self.top_k):
            mask = jax.nn.one_hot(top_idx[:, slot], E,
                                  dtype=jnp.int32)       # [S, E]
            pos_e = jnp.cumsum(mask, axis=0) - mask + counts[None, :]
            pos = jnp.sum(pos_e * mask, axis=1)          # [S]
            counts = counts + jnp.sum(mask, axis=0)
            keep = (pos < capacity).astype(jnp.float32)  # overflow drop
            kept = kept + jnp.sum(keep)
            slot_hot = (mask.astype(jnp.float32)[:, :, None]
                        * jax.nn.one_hot(pos, capacity)[:, None, :]
                        * keep[:, None, None])           # [S, E, C]
            dispatch = dispatch + slot_hot
            w = top_w[:, slot]
            combine = combine + slot_hot * w[:, None, None]
        # fraction of routed (token, slot) assignments that overflowed
        # this shard's per-expert capacity — the telemetry the reference
        # never needed (its MoE is single-node); exposed via
        # ``self.drop_rate`` so training loops can watch whether the
        # aux loss is balancing load well enough
        drop_rate = 1.0 - kept / (S * self.top_k)
        return dispatch, combine, drop_rate

    def forward_on_mesh(self, x, mesh: Mesh, axis: str = "expert"):
        self.drop_rate = jnp.zeros(())  # psum path cannot drop
        if self.capacity_factor is not None:
            return self._forward_a2a(x, mesh, axis, self.capacity_factor)
        return self._forward_psum(x, mesh, axis)

    def _forward_a2a(self, x, mesh: Mesh, axis: str,
                     capacity_factor: float):
        """Scalable EP: tokens sharded over the expert axis; per-device
        capacity-bounded dispatch; two all_to_all exchanges bracket the
        local expert compute.  Per-device shapes (recorded in
        the module-level ``LAST_A2A_SHAPES`` while tracing, for the memory
        test): dispatch [S, E, C], expert buffers [E, C, H] and
        [E/n, n·C, H] — all O(B·T/n), never the full batch."""
        B, T, H = x.shape
        E, k = self.num_experts, self.top_k
        n = mesh.shape[axis]
        s_total = B * T
        assert E % n == 0, (E, n)
        assert s_total % n == 0, (s_total, n)
        S = s_total // n
        capacity = max(1, int(round(capacity_factor * k * S / E)))

        # routing probs computed once, full-batch (the gate is tiny);
        # aux loss uses the pre-capacity mask exactly like the dense
        # path (per-shard top_k for dispatch happens in _dispatch_combine)
        probs = self._gate_probs(x)                   # [B, T, E]
        self._set_aux_loss(probs, self._topk_mask(probs))
        xf = x.reshape(s_total, H)
        pf = probs.reshape(s_total, E)
        stacked = self.experts

        moe = self

        def shard_fn(stacked_local, x_loc, p_loc):
            # x_loc [S, H]; p_loc [S, E]; stacked_local leaves [E/n, ...]
            dispatch, combine, drop = moe._dispatch_combine(p_loc,
                                                            capacity)
            expert_in = jnp.einsum("sec,sh->ech", dispatch,
                                   x_loc.astype(jnp.float32))  # [E, C, H]
            expert_in = expert_in.astype(x_loc.dtype)
            # ship each device its local experts' slots from everyone
            recv = _coll.all_to_all(expert_in, axis, split_axis=0,
                                      concat_axis=1, tiled=True)
            # recv [E/n, n*C, H]
            LAST_A2A_SHAPES.update(
                dispatch=dispatch.shape, expert_in=expert_in.shape,
                recv=recv.shape)
            outs = jax.vmap(lambda tree, xe: tree(xe),
                            in_axes=(0, 0))(stacked_local, recv)
            back = _coll.all_to_all(outs, axis, split_axis=1,
                                      concat_axis=0, tiled=True)
            # back [E, C, H]
            y = jnp.einsum("sec,ech->sh", combine,
                           back.astype(jnp.float32))
            return (y.astype(x_loc.dtype),
                    _coll.pmean(drop, axis))

        fn = shard_map_compat(
            shard_fn, mesh=mesh,
            in_specs=(jax.tree_util.tree_map(lambda _: P(axis), stacked),
                      P(axis), P(axis)),
            out_specs=(P(axis), P()))
        # pin operands replicated — see parallel.mesh.pin_replicated
        stacked = pin_replicated(stacked, mesh)
        xf = pin_replicated(xf, mesh)
        pf = pin_replicated(pf, mesh)
        y, drop = fn(stacked, xf, pf)
        self.drop_rate = jax.lax.stop_gradient(drop)
        return y.reshape(B, T, H)

    def _forward_psum(self, x, mesh: Mesh, axis: str = "expert"):
        n = mesh.shape[axis]
        assert self.num_experts % n == 0, (self.num_experts, n)
        weights = self._route(x)
        stacked = self.experts

        def shard_fn(stacked_local, x_rep, w_rep):
            # stacked_local leaves: [E/n, ...]; w_rep [B, T, E]
            me = jax.lax.axis_index(axis)
            e_local = jax.tree_util.tree_leaves(stacked_local)[0].shape[0]
            outs = MoE._apply_stacked(stacked_local, x_rep)  # [E/n,B,T,H]
            w_local = jax.lax.dynamic_slice_in_dim(
                w_rep, me * e_local, e_local, axis=2)
            part = jnp.einsum("ebth,bte->bth", outs, w_local)
            return _coll.psum(part, axis)

        fn = shard_map_compat(
            shard_fn, mesh=mesh,
            in_specs=(jax.tree_util.tree_map(lambda _: P(axis), stacked),
                      P(), P()),
            out_specs=P())
        stacked = pin_replicated(stacked, mesh)
        x = pin_replicated(x, mesh)
        weights = pin_replicated(weights, mesh)
        return fn(stacked, x, weights)


class Router(Module):
    """The router of :class:`HeldExperts`: ``weight [E, H]`` scores every
    expert, ``bias [E]`` moves which are chosen and never the weights."""

    def __init__(self, hidden_size: int, num_experts: int):
        super().__init__()
        self.weight = Parameter(jax.random.normal(
            next_key(), (num_experts, hidden_size)) * hidden_size ** -0.5)
        self.bias = Parameter(jnp.zeros(num_experts))


class HeldExperts(Module):
    """One chip's share of a sigmoid-routed layer of gated experts.

    ``s = sigmoid(x W_r)`` over all ``num_experts`` (the router keeps its
    published width), the ``top_k`` largest of ``s + b`` chosen (``b`` a
    selection bias), ``w_e = s_e`` over the sum of the chosen; expert
    ``e`` is ``W_d(silu(W_g x) * W_u x)``.  This layer holds experts
    ``first .. first + count`` of them as stacked leaves
    ``[count, in, out]`` (the layout both products below take as it lies:
    compiled for a v5e, a stack ``[count, out, in]`` is copied whole, 268
    MB at 16 x 2048 x 4096, on every call), and computes their part of
    the result for the tokens routed to them.  No capacity, no drop; what
    the absent experts would add is left out, and nothing stands in for
    the exchange that would bring other chips' tokens.

    ``y = scale * sum_chosen w_e E_e(x) + E_shared(x)``: ``scale`` (a
    constant, ``routed_scaling_factor``) multiplies the routed sum, and
    ``shared`` (a module ``x -> y``, the gated layer of the shared
    experts' width, or None) is applied to every valid token whatever the
    router says.  Every chip of the deployment computes the shared expert
    alike and it is counted once: it is no pair, and ``counts`` does not
    see it.

    **Two products over the stacks; which one a call takes follows from
    static shapes** (:meth:`product_of`: the held share and the call's
    tokens; no option).

    *Every stack* (``_every_stack``): every token goes through every held
    expert in one batched product and the routing weights, zero for an
    expert a token did not choose, pick the result.  It is what **a held
    share** takes up to ``DENSE_TOKENS`` tokens a call (a slot pool's
    decode step and its prefill chunks): each held stack is read once a
    call whatever the routing, as it is in the deployment the share stands
    for, whose other chips' sequences leave no held expert idle; a call's
    cost then does not follow which experts its few tokens chose.

    *Tiled* (``_tiled``): the token-to-expert pairs are laid out by held
    expert, each expert's rows padded to whole tiles of
    ``ops.expert_kernels.ROW_TILE``, and two Pallas programs do the three
    products, a tile's expert a prefetched scalar: each chosen expert's
    stack is read once a call, an expert nobody chose is never read, and
    the cost follows the tiles.  It is what a share's longer calls take,
    and **every call of a layer that holds every expert**: such a layer
    has all the pairs there are (no other chip's tokens would come), and
    through every held expert it would multiply ``num_experts / top_k``
    times the rows the routing asks for.

    One layer alone on a v5e, bfloat16, ms a call (PERF.md section 6, PR
    46; *XLA's* ``ragged_dot`` *over sorted pairs, the longer calls'
    product until then, is gone: the tiles beat it at every size*):

    ====================  ======  ===========  ==========  ===========
    held / experts, k     tokens  every stack  ragged_dot  tiled (16)
    ====================  ======  ===========  ==========  ===========
    64 / 64, 4            16      1.749        1.342       1.249
    (2,048 x 1,536)       128     1.770        4.001       1.823
    ..                    256     2.210        4.103       2.185
    ..                    384     3.035        4.219       2.477
    16 / 256, 8           32      1.227        1.116       0.859
    (4,096 x 2,048)       288     1.568        2.045       1.677
    ..                    1,024   4.990        4.697       3.364
    16 / 128, 8           32      1.227        1.574       1.179
    ..                    288     1.552        2.127       1.811
    ====================  ======  ===========  ==========  ===========

    With tiles of 32, which is what stands (``ops.expert_kernels`` says
    why), the 64-held layer read 1.848, 1.978 and 2.064 ms at 128, 256 and
    384 tokens: within 5 % of every stack at a step's 128 rows, 10 and 32 %
    under it at a chunk's and a joint pass's sizes.

    The standing cells' shares (16 held; 32 to 112 rows a step, 288 to 368
    tokens a joint pass) would win a plain step and lose a joint pass,
    where their tail sits: they keep every stack, as the rule above says
    they should.  ``jax.experimental.pallas.ops.tpu.megablox.gmm`` under
    the same layout read within 4 % of the repo's kernels at 64 held and 4
    to 9 % under them at 16 (its blocks are fetched a stack at a time), and
    brings ``while`` loops of group metadata into every program; it is not
    used.

    ``forward(x [..., H], valid=None) -> (y [..., H] float32, counts)``:
    ``x`` is routed as it comes (float32 from a float32 norm) and cast to
    the experts' dtype for their products; ``valid [...]`` false keeps a
    token away from every expert (a slot pool's idle lanes, padding);
    ``counts`` is int32 ``[ROUTING]``: 1 (this call), the token-to-expert
    pairs routed, those that landed on a held expert, the held experts
    that had at least one, and ``rows_computed``: the token-expert rows
    the product multiplied (held experts x tokens through every stack;
    the rows the tiles covered, padding and all, on the tiled product)."""

    DENSE_TOKENS = 512

    def __init__(self, hidden_size: int, expert_size: int, num_experts: int,
                 top_k: int, held: Optional[tuple] = None,
                 normalize: bool = True, shared: Optional[Module] = None,
                 scale: float = 1.0, normalize_eps: float = 0.0):
        super().__init__()
        first, count = (0, num_experts) if held is None else held
        if not 0 <= first < first + count <= num_experts:
            raise ValueError(f"held share {held} outside 0..{num_experts}")
        self.num_experts, self.top_k = num_experts, top_k
        self.first, self.count = int(first), int(count)
        self.normalize = normalize
        self.normalize_eps = float(normalize_eps)
        self.scale = float(scale)
        self.has_shared = shared is not None
        if shared is not None:
            self.shared = shared

        def stack(fan_in, fan_out):
            return Parameter(jax.random.normal(
                next_key(), (count, fan_in, fan_out)) * fan_in ** -0.5)
        self.w_gate = stack(hidden_size, expert_size)
        self.w_up = stack(hidden_size, expert_size)
        self.w_down = stack(expert_size, hidden_size)
        self.router = Router(hidden_size, num_experts)

    def route(self, x):
        """``x [T, H] -> (experts [T, k], weights [T, k] float32)``.  The
        router runs in float32 whatever the experts are served in: it is
        a sliver of the layer's work, and a score rounded across the
        ``top_k``-th place sends a token to another expert."""
        with jax.named_scope("moe/route"):
            logits = jnp.einsum(
                "th,eh->te", x.astype(jnp.float32),
                self.router.weight.astype(jnp.float32),
                precision=jax.lax.Precision.HIGHEST)
            return route_top_k(jax.nn.sigmoid(logits), self.top_k,
                               self.normalize,
                               self.router.bias.astype(jnp.float32),
                               self.normalize_eps)

    def _every_stack(self, x, local, weights, held):
        """Every token through every held expert: ``x [T, H]`` in the
        experts' dtype -> ``(y [T, H] float32, held experts chosen,
        token-expert rows multiplied)``."""
        with jax.named_scope("moe/experts"):
            dot = functools.partial(jnp.einsum,
                                    preferred_element_type=jnp.float32)
            xs = jnp.broadcast_to(x, (self.count,) + x.shape)
            act = jax.nn.silu(dot("eth,ehf->etf", xs, self.w_gate)) \
                * dot("eth,ehf->etf", xs, self.w_up)
            out = dot("etf,efh->eth", act.astype(x.dtype), self.w_down)
        with jax.named_scope("moe/combine"):
            chose = held[..., None] & (
                local[..., None] == jnp.arange(self.count))  # [T, k, count]
            w = jnp.sum(jnp.where(chose, weights[..., None], 0.0), axis=1)
            return (jnp.einsum("eth,te->th", out, w),
                    jnp.sum(jnp.any(chose, axis=(0, 1))),
                    jnp.int32(self.count * x.shape[0]))

    def _tiled(self, x, local, weights, held):
        """The pairs laid out by held expert, each expert's rows padded to
        whole tiles (``ops.expert_kernels``): same arguments and result.
        No sort: a pair's place is its expert's first row plus how many
        pairs of that expert come before it (a running count), and the
        token of each place is read off by comparing places (pairs x
        places booleans: a few million at a pool's pass)."""
        T, k = local.shape
        tile, pairs = expert_kernels.ROW_TILE, T * k
        interpret = not attention_kernels._on_tpu()
        with jax.named_scope("moe/experts"):
            on = held.reshape(-1)
            hot = on[:, None] & (
                local.reshape(-1, 1) == jnp.arange(self.count))
            sizes = jnp.sum(hot, axis=0, dtype=jnp.int32)
            padded = (sizes + tile - 1) // tile * tile
            ends = jnp.cumsum(padded)
            before = jnp.cumsum(hot, axis=0, dtype=jnp.int32) - hot
            # a token chooses an expert once, and every expert's last tile
            # may be all but empty
            places = T * min(k, self.count) + self.count * (tile - 1)
            places = (places + tile - 1) // tile * tile
            place = jnp.where(on, jnp.sum(
                jnp.where(hot, ends - padded + before, 0), axis=1), places)
            token = jnp.sum(jnp.where(
                place[None, :] == jnp.arange(places)[:, None],
                jnp.arange(pairs) // k, 0), axis=1)         # padding: token 0
            group = jnp.minimum(jnp.sum(
                ends[None, :] <= tile * jnp.arange(places // tile)[:, None],
                axis=1, dtype=jnp.int32), self.count - 1)
            used = ends[-1:] // tile
            act = expert_kernels.gate_up(x[token], self.w_gate, self.w_up,
                                         group, used, interpret=interpret)
            out = expert_kernels.down(act, self.w_down, group, used,
                                      interpret=interpret)
            mine = out[jnp.minimum(place, places - 1)]        # [pairs, H]
        with jax.named_scope("moe/combine"):
            w = (weights * held).reshape(-1, 1)
            # a pair of no held expert reads a row nobody computed
            mine = jnp.where(w > 0, mine * w, 0.0)
            return (mine.reshape(T, k, -1).sum(axis=1),
                    jnp.sum(sizes > 0), ends[-1])

    @classmethod
    def product_of(cls, held: int, experts: int, tokens: int) -> str:
        """Which product a call of ``tokens`` tokens takes in a layer that
        holds ``held`` of ``experts`` experts, ``"every_stack"`` or
        ``"tiled"``: from static shapes alone (the class docstring says
        why, and has the table)."""
        share = held < experts
        return "every_stack" if share and tokens <= cls.DENSE_TOKENS \
            else "tiled"

    def _product(self, tokens: int):
        return getattr(self, "_" + self.product_of(
            self.count, self.num_experts, tokens))

    def forward(self, x, valid=None):
        lead, H = x.shape[:-1], x.shape[-1]
        x = x.reshape(-1, H)
        experts, weights = self.route(x)
        T, k = experts.shape
        local = experts - self.first
        routed = jnp.ones((T, 1), bool) if valid is None \
            else valid.reshape(-1, 1)
        held = (local >= 0) & (local < self.count) & routed
        y, chosen, rows = self._product(T)(
            x.astype(self.w_gate.dtype), local, weights, held)
        if self.scale != 1.0:
            y = y * self.scale
        if self.has_shared:
            with jax.named_scope("moe/shared"):
                y = y + jnp.where(routed, self.shared.forward(x), 0.0)
        counts = jnp.stack([jnp.int32(1), jnp.sum(routed) * k, jnp.sum(held),
                            chosen, rows]).astype(jnp.int32)
        return y.reshape(lead + (H,)), counts
