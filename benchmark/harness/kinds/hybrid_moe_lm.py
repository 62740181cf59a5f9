"""Configuration kind ``hybrid_moe_lm``: the repo's ``HybridDecoder``
(``bigdl_tpu.models.mimo_v2``: a sequential pre-norm decoder of window and
full grouped-query layers named by a per-layer pattern, a leading dense
gated feed-forward layer, then a sigmoid-routed layer of gated experts of
which this chip holds a share; an untied head), served through
``ModelServer`` -> ``GenerationScheduler`` -> ``SlotPool``.

**Schema** (``configs/<name>.json``; ``benchmark/README.md`` lists the keys
every kind shares).  The sizes carry the names of the model's public
``config.json``: ``vocab_size`` (as sliced), ``hidden_size``,
``num_hidden_layers`` (as cut), ``hybrid_layer_pattern`` (the published
list, 0 a full layer and 1 a window layer; the first ``num_hidden_layers``
entries are built), ``moe_layer_freq`` (likewise: 0 a dense layer of
``intermediate_size``, 1 an expert layer of ``moe_intermediate_size``),
``num_attention_heads``, ``head_dim`` (queries and keys), ``v_head_dim``,
and by layer kind ``num_key_value_heads`` / ``rope_theta`` (full) and
``swa_num_key_value_heads`` / ``swa_rope_theta`` (window);
``partial_rotary_factor``, ``sliding_window``, ``attention_value_scale``,
``add_swa_attention_sink_bias``, ``num_experts_per_tok``,
``norm_topk_prob``, ``layernorm_epsilon``.  The chip's share:
``n_routed_experts`` is the number of experts **held here**, from
``experts_offset`` (default 0), and ``published.n_routed_experts`` the
router's width; ``reduced`` says what was cut and ``deployment`` what the
share stands for.  ``serving`` and ``correct.serve`` as for ``decoder_lm``;
``serving.prefill_chunk`` is also what a window layer's ring is allocated
beside its window.

A kind may have no training duties: this one has no training cell, so it
has no ``build_train``, ``batch``, ``train_param_spec`` or
``reference_train``, and a training job that names it fails on the missing
name.
"""
from __future__ import annotations

import math
from typing import Any, Dict, List, Tuple

from harness import weights
# the scheduler decides a prompt's prefill programs, whatever the model
from harness.kinds.decoder_lm import prefill_plan, warmup_prompt_len  # noqa: F401

REFERENCE = "hybrid_moe_lm"


def layer_kinds(cfg: Dict[str, Any]) -> List[Tuple[bool, bool]]:
    """``(window, sparse)`` of each layer that is built."""
    n = cfg["num_hidden_layers"]
    return [(bool(p), bool(f)) for p, f in zip(
        cfg["hybrid_layer_pattern"][:n], cfg["moe_layer_freq"][:n])]


def router_width(cfg: Dict[str, Any]) -> int:
    return cfg.get("published", {}).get("n_routed_experts",
                                        cfg["n_routed_experts"])


def _kv_heads(cfg: Dict[str, Any], window: bool) -> int:
    return cfg["swa_num_key_value_heads" if window else "num_key_value_heads"]


def param_spec(cfg: Dict[str, Any]) -> List[Tuple[str, Tuple[int, ...]]]:
    """Leaves of ``HybridDecoder`` in flattening order.  The expert
    stacks lie ``[experts, in, out]`` (what the TPU's grouped product takes
    as it lies); ``weights.make`` scales a stack by its last axis
    (``assumed.expert_stacks`` in the configuration)."""
    h, d, dv = cfg["hidden_size"], cfg["head_dim"], cfg["v_head_dim"]
    heads, held = cfg["num_attention_heads"], cfg["n_routed_experts"]
    f, fe = cfg["intermediate_size"], cfg["moe_intermediate_size"]
    spec = [(".embedding.weight", (cfg["vocab_size"], h))]
    for i, (window, sparse) in enumerate(layer_kinds(cfg)):
        p, kv = f".blocks[{i}]", _kv_heads(cfg, window)
        spec += [(p + ".attn_norm.weight", (h,)),
                 (p + ".attn.q_layer.weight", (heads * d, h)),
                 (p + ".attn.k_layer.weight", (kv * d, h)),
                 (p + ".attn.v_layer.weight", (kv * dv, h)),
                 (p + ".attn.output_layer.weight", (h, heads * dv))]
        if window and cfg.get("add_swa_attention_sink_bias"):
            spec += [(p + ".attn.sink.bias", (heads,))]
        spec += [(p + ".ffn_norm.weight", (h,))]
        if sparse:
            spec += [(p + ".ffn.w_gate", (held, h, fe)),
                     (p + ".ffn.w_up", (held, h, fe)),
                     (p + ".ffn.w_down", (held, fe, h)),
                     (p + ".ffn.router.weight", (router_width(cfg), h)),
                     (p + ".ffn.router.bias", (router_width(cfg),))]
        else:
            spec += [(p + ".ffn.gate.weight", (f, h)),
                     (p + ".ffn.up.weight", (f, h)),
                     (p + ".ffn.down.weight", (h, f))]
    return spec + [(".final_norm.weight", (h,)),
                   (".lm_head.weight", (cfg["vocab_size"], h))]


def param_blocks(cfg: Dict[str, Any]) -> List[Tuple[str, List[int]]]:
    """The served model in the blocks the check walks: the embedding,
    each layer, and the final norm with the untied head."""
    paths = [p for p, _ in param_spec(cfg)]
    blocks = [("embedding", [paths.index(".embedding.weight")])]
    for i in range(cfg["num_hidden_layers"]):
        blocks.append((f"blocks[{i}]", [n for n, p in enumerate(paths)
                                        if p.startswith(f".blocks[{i}].")]))
    return blocks + [("head", [n for n, p in enumerate(paths) if p.startswith(
        (".final_norm.", ".lm_head."))])]


def expert_params(cfg: Dict[str, Any]) -> int:
    """Parameters of one expert: three products of hidden x expert width."""
    return 3 * cfg["hidden_size"] * cfg["moe_intermediate_size"]


def decode_step_bytes(cfg: Dict[str, Any], live_positions: float,
                      weight_bytes_per: int = 2,
                      cache_bytes_per: int = 2) -> float:
    """Bytes one pooled decode step reads: every weight once, the stacks
    of every held expert among them (the step's batched product reads
    each held stack whatever its tokens chose, as the deployment does,
    whose pooled sequences leave no held expert idle: nothing is assumed
    of the routing; ``moe_active_expert_share`` says how many of them the
    cut's own tokens chose); and the keys and values the step's queries
    attend, by layer kind — a full layer the live positions of the active
    slots at its heads and widths, a window layer at most the window of
    each slot, counted as ``min(live, slots * window)`` (an upper bound: a
    pool of short requests beside one long one attends fewer)."""
    slots = cfg["serving"]["slots"]
    w = sum(math.prod(shape) for _, shape in param_spec(cfg)) \
        * weight_bytes_per
    windowed = min(live_positions, slots * cfg["sliding_window"])
    cache = 0.0
    for window, _ in layer_kinds(cfg):
        per_position = _kv_heads(cfg, window) * (
            cfg["head_dim"] + cfg["v_head_dim"]) * cache_bytes_per
        cache += per_position * (windowed if window else live_positions)
    return w + cache


def expert_layer_cost(cfg: Dict[str, Any], active_experts: float,
                      pairs: float, weight_bytes_per: int = 2) \
        -> Dict[str, float]:
    """Least bytes and operations of the experts' products of expert-layer
    calls in which ``active_experts`` held experts had a token and
    ``pairs`` token-to-held-expert pairs were routed (sums over the
    calls, both counted by the program): each active expert's three
    stacks read once, each pair's row read in and written out at the
    hidden width; three products of ``hidden x expert width`` a pair.
    What the routing needs, not what a product that reads every held
    stack does."""
    return {"bytes": float(active_experts * expert_params(cfg)
                           * weight_bytes_per
                           + pairs * 2 * cfg["hidden_size"]
                           * weight_bytes_per),
            "flops": float(2 * expert_params(cfg) * pairs)}


def expert_stack_shapes(cfg: Dict[str, Any]) -> List[Tuple[int, int, int]]:
    """The shapes of the held expert stacks, as an operation's text names
    its operands."""
    n, h, f = (cfg["n_routed_experts"], cfg["hidden_size"],
               cfg["moe_intermediate_size"])
    return sorted({(n, h, f), (n, f, h)})


def model_config(cfg: Dict[str, Any]) -> Dict[str, Any]:
    """The configuration as ``bigdl_tpu.models.mimo_v2`` reads it: the
    router at its published width, the experts held beside it."""
    return dict(cfg, n_routed_experts=router_width(cfg),
                experts_held=cfg["n_routed_experts"])


def _model(cfg: Dict[str, Any], max_len: int):
    from bigdl_tpu.models import mimo_v2
    return mimo_v2(model_config(cfg), max_len)


def build_serve(cfg: Dict[str, Any], seed: int, queue_capacity: int):
    """``ModelServer`` over a ``GenerationScheduler`` with the
    configuration's serving settings and seeded weights in the dtype
    they are served in, made a block at a time (one program for the
    layers of a kind, and never more than a block's float32 noise beside
    what is already made)."""
    import jax
    import jax.numpy as jnp
    from bigdl_tpu.serving import ModelServer
    from bigdl_tpu.serving.generation import GenerationScheduler

    s = cfg["serving"]
    abstract = jax.eval_shape(lambda: _model(cfg, s["max_len"]))
    weights.reset_program_rng(seed)
    spec = param_spec(cfg)
    weights.check_spec(spec, abstract)
    leaves: List[Any] = [None] * len(spec)
    for _name, idx in param_blocks(cfg):
        for i, leaf in zip(idx, weights.make(
                spec, seed, jnp.dtype(s["weights_dtype"]), only=idx)):
            leaves[i] = leaf
        jax.block_until_ready(leaves[idx[-1]])
    model = jax.tree_util.tree_unflatten(
        jax.tree_util.tree_structure(abstract), leaves).eval_mode()
    engine = GenerationScheduler(
        model, slots=s["slots"], dtype=jnp.dtype(s["cache_dtype"]),
        prefill_chunk=s["prefill_chunk"], prefill_batch=s["prefill_batch"],
        queue_capacity=queue_capacity, admission=s["admission"],
        prefix_cache_bytes=None)
    del model, leaves     # the pool shares the leaves; nothing else holds them
    return ModelServer(generator=engine), engine
