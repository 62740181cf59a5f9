"""Configuration kind ``conv_moe_lm``: the repo's ``HybridDecoder`` built by
``bigdl_tpu.models.lfm2_moe`` (LFM2-24B-A2B): gated short-convolution
layers that keep a **two-row tail and no keys**, one grouped-query
attention layer to every three of them (query and key heads normed before
rotation), leading dense gated layers, then sigmoid-routed experts **all
of which this chip holds**; RMS norms, a head tied to the embedding;
served through ``ModelServer`` -> ``GenerationScheduler`` -> ``SlotPool``.

**Schema** (``configs/<name>.json``; ``benchmark/README.md`` lists the keys
every kind shares, ``benchmark/README.conv_moe_lm.md`` these).  The sizes
carry the names of the model's public ``config.json``: ``vocab_size``,
``hidden_size``, ``num_hidden_layers`` (as cut), ``layer_types`` (the
published list; the first ``num_hidden_layers`` entries are built),
``num_attention_heads``, ``num_key_value_heads``, ``intermediate_size``,
``num_dense_layers``, ``moe_intermediate_size``, ``num_experts`` (the
router's width), ``num_experts_per_tok``, ``conv_L_cache``, ``norm_eps``,
``rope_parameters``, ``routed_scaling_factor``, ``use_expert_bias``,
``norm_topk_prob``, ``conv_bias``.  ``n_routed_experts`` is the number of
experts **held here**, from ``experts_offset`` (the keys the routing
counters' readers divide by): all of them in the configuration that
stands.  ``serving.cache_dtype`` is the dtype of the keys, the values and
the convolutions' tails.  ``seeding`` (``taps_std``, ``routed_down``,
``qk_scale``) says how a layer's leaves are read from
what ``harness.weights`` seeds (``reference.conv_moe_lm.seeded``).

No training duties: a training job that names this kind fails on the
missing name.
"""
from __future__ import annotations

import math
from typing import Any, Dict, List, Tuple

from harness import weights
# the scheduler decides a prompt's prefill programs, whatever the model
from harness.kinds.decoder_lm import prefill_plan, warmup_prompt_len  # noqa: F401
# what the expert readers take is what ``hybrid_moe_lm`` counts, from the
# same keys (``hidden_size``, ``moe_intermediate_size`` and
# ``n_routed_experts``, the experts held here): each active expert's three
# stacks read once a call, a pair's row in and out, three products a pair;
# the stacks' shapes as an operation's text names them
from harness.kinds.hybrid_moe_lm import (  # noqa: F401
    expert_layer_cost, expert_params, expert_stack_shapes)

REFERENCE = "conv_moe_lm"


def head_width(cfg: Dict[str, Any]) -> int:
    return cfg.get("head_dim") or \
        cfg["hidden_size"] // cfg["num_attention_heads"]


def held_experts(cfg: Dict[str, Any]) -> int:
    return cfg.get("n_routed_experts", cfg["num_experts"])


def layer_kinds(cfg: Dict[str, Any]) -> List[Tuple[str, bool]]:
    """``("conv" | "attn", sparse)`` of each layer that is built."""
    names = {"conv": "conv", "full_attention": "attn"}
    return [(names[t], i >= cfg.get("num_dense_layers", 0))
            for i, t in enumerate(
                cfg["layer_types"][:cfg["num_hidden_layers"]])]


def param_spec(cfg: Dict[str, Any]) -> List[Tuple[str, Tuple[int, ...]]]:
    """Leaves of the served model in flattening order.  The expert stacks
    lie ``[experts, in, out]`` (what both of the TPU's products over them
    take as it lies) and the taps ``[taps, hidden]`` (channels along the
    lanes); ``weights.make`` scales both by their last axis
    (``assumed.expert_stacks``, ``seeding`` in the configuration)."""
    h, d = cfg["hidden_size"], head_width(cfg)
    heads, kv = cfg["num_attention_heads"], cfg["num_key_value_heads"]
    f, fe = cfg["intermediate_size"], cfg["moe_intermediate_size"]
    held, wide = held_experts(cfg), cfg["num_experts"]
    spec = [(".embedding.weight", (cfg["vocab_size"], h))]
    for i, (kind, sparse) in enumerate(layer_kinds(cfg)):
        p = f".blocks[{i}]"
        spec += [(p + ".attn_norm.weight", (h,))]
        if kind == "attn":
            spec += [(p + ".attn.q_layer.weight", (heads * d, h)),
                     (p + ".attn.k_layer.weight", (kv * d, h)),
                     (p + ".attn.v_layer.weight", (kv * d, h)),
                     (p + ".attn.output_layer.weight", (h, heads * d)),
                     (p + ".attn.q_norm.weight", (d,)),
                     (p + ".attn.k_norm.weight", (d,))]
        spec += [(p + ".ffn_norm.weight", (h,))]
        if sparse:
            spec += [(p + ".ffn.w_gate", (held, h, fe)),
                     (p + ".ffn.w_up", (held, h, fe)),
                     (p + ".ffn.w_down", (held, fe, h)),
                     (p + ".ffn.router.weight", (wide, h)),
                     (p + ".ffn.router.bias", (wide,))]
        else:
            spec += [(p + ".ffn.gate.weight", (f, h)),
                     (p + ".ffn.up.weight", (f, h)),
                     (p + ".ffn.down.weight", (h, f))]
        if kind == "conv":
            spec += [(p + ".ssm.taps", (cfg.get("conv_L_cache", 3), h)),
                     (p + ".ssm.in_proj.weight", (3 * h, h)),
                     (p + ".ssm.out_proj.weight", (h, h))]
    return spec + [(".final_norm.weight", (h,))]


def param_blocks(cfg: Dict[str, Any]) -> List[Tuple[str, List[int]]]:
    """The served model in the blocks the check walks: the embedding,
    each layer, and the last norm with the tied head (the embedding's
    leaf again)."""
    paths = [p for p, _ in param_spec(cfg)]
    emb = paths.index(".embedding.weight")
    blocks = [("embedding", [emb])]
    for i in range(cfg["num_hidden_layers"]):
        blocks.append((f"blocks[{i}]", [n for n, p in enumerate(paths)
                                        if p.startswith(f".blocks[{i}].")]))
    return blocks + [("head", [emb] + [n for n, p in enumerate(paths)
                                       if p.startswith(".final_norm.")])]


# ---- what the readers count --------------------------------------------------

def place_bytes(cfg: Dict[str, Any], cache_bytes_per: int = 2) -> int:
    """Bytes of one place of an attention layer's row: keys and values."""
    return 2 * cfg["num_key_value_heads"] * head_width(cfg) * cache_bytes_per


def tail_bytes(cfg: Dict[str, Any], cache_bytes_per: int = 2) -> int:
    """Bytes of one slot's tail in one convolution layer."""
    return (cfg.get("conv_L_cache", 3) - 1) * cfg["hidden_size"] \
        * cache_bytes_per


def decode_step_bytes(cfg: Dict[str, Any], live_positions: float,
                      weight_bytes_per: int = 2,
                      cache_bytes_per: int = 2) -> float:
    """Bytes one pooled decode step must move: every weight once, the
    stacks of **every** expert among them (with all of a layer's experts
    here and a hundred rows choosing four each, a step leaves few of the
    64 unchosen; ``moe_active_expert_share`` says how few) and the tied
    head's table read once as the head; the keys and values of the live
    positions of the active slots in each attention layer; and every
    slot's tail read and written in each convolution layer."""
    w = sum(math.prod(shape) for _, shape in param_spec(cfg)) \
        * weight_bytes_per
    kinds = [k for k, _ in layer_kinds(cfg)]
    rows = kinds.count("attn") * live_positions \
        * place_bytes(cfg, cache_bytes_per)
    tails = kinds.count("conv") * 2 * cfg["serving"]["slots"] \
        * tail_bytes(cfg, cache_bytes_per)
    return w + rows + tails


# ---- the served model ----------------------------------------------------------

def model_config(cfg: Dict[str, Any]) -> Dict[str, Any]:
    """The configuration as ``bigdl_tpu.models.lfm2_moe`` reads it: the
    router at its published width, the experts held beside it."""
    return dict(cfg, experts_held=held_experts(cfg))


def _model(cfg: Dict[str, Any], max_len: int):
    from bigdl_tpu.models import lfm2_moe
    return lfm2_moe(model_config(cfg), max_len)


def seed_block(cfg: Dict[str, Any], spec, idx: List[int],
               leaves: List[Any]) -> None:
    """One block's leaves (``idx`` into ``spec`` and ``leaves``) as the
    configuration's ``seeding`` reads what ``weights.make`` made: the
    reference's own function, so that the program serves the numbers the
    check compares it with."""
    import importlib
    import jax.numpy as jnp
    ref = importlib.import_module("reference." + REFERENCE)
    at = {spec[i][0].split("]", 1)[1]: i for i in idx
          if spec[i][0].startswith(".blocks[")}
    if at:
        new = ref.seeded({k: leaves[i] for k, i in at.items()}, cfg,
                         jnp.dtype(cfg["serving"]["weights_dtype"]))
        for k, i in at.items():
            leaves[i] = new[k]


def build_serve(cfg: Dict[str, Any], seed: int, queue_capacity: int):
    """``ModelServer`` over a ``GenerationScheduler`` with the
    configuration's serving settings and seeded weights in the dtype they
    are served in, made a block at a time (one program for the layers of a
    type, and never more than a block's float32 noise beside what is
    already made)."""
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
    blocks = param_blocks(cfg)
    for _name, idx in blocks[:-1] + [("final_norm", blocks[-1][1][1:])]:
        for i, leaf in zip(idx, weights.make(
                spec, seed, jnp.dtype(s["weights_dtype"]), only=idx)):
            leaves[i] = leaf
        seed_block(cfg, spec, idx, leaves)
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
