"""Configuration kind ``latent_moe_lm``: the repo's ``HybridDecoder`` built
by ``bigdl_tpu.models.sarvam_mla`` (a sequential pre-norm decoder whose
every layer attends through a **latent row** - one compressed, normed row a
position shared by all heads, a rotary key kept apart from it - with a
leading dense gated feed-forward layer, then a sigmoid-routed layer of gated
experts of which this chip holds a share, scaled and beside a shared expert;
an untied head), served through ``ModelServer`` -> ``GenerationScheduler``
-> ``SlotPool``.  ``benchmark/README.latent_moe_lm.md`` has the schema.

No training duties (no training cell): a training job that names this kind
fails on the missing name.
"""
from __future__ import annotations

import math
from typing import Any, Dict, List, Tuple

from harness import weights
# the scheduler decides a prompt's prefill programs, whatever the model
from harness.kinds.decoder_lm import prefill_plan, warmup_prompt_len  # noqa: F401

REFERENCE = "latent_moe_lm"


def router_width(cfg: Dict[str, Any]) -> int:
    return cfg.get("published", {}).get("num_experts", cfg["num_experts"])


def is_sparse(cfg: Dict[str, Any], i: int) -> bool:
    return i >= cfg.get("first_k_dense_replace", 1)


def param_spec(cfg: Dict[str, Any]) -> List[Tuple[str, Tuple[int, ...]]]:
    """Leaves of the served ``HybridDecoder`` in flattening order.  The
    expert stacks lie ``[experts, in, out]`` (``weights.make`` scales a
    stack by its last axis: ``assumed.expert_stacks``); the shared expert
    is a gated layer of ``[out, in]`` leaves."""
    h, heads = cfg["hidden_size"], cfg["num_attention_heads"]
    dn, dr = cfg["qk_nope_head_dim"], cfg["qk_rope_head_dim"]
    dv, r = cfg["v_head_dim"], cfg["kv_lora_rank"]
    held, fe = cfg["num_experts"], cfg["moe_intermediate_size"]
    f, fs = cfg["intermediate_size"], cfg["num_shared_experts"] * fe
    spec = [(".embedding.weight", (cfg["vocab_size"], h))]
    for i in range(cfg["num_hidden_layers"]):
        p = f".blocks[{i}]"
        spec += [(p + ".attn_norm.weight", (h,)),
                 (p + ".attn.q_layer.weight", (heads * (dn + dr), h)),
                 (p + ".attn.kv_a_layer.weight", (r + dr, h)),
                 (p + ".attn.kv_norm.weight", (r,)),
                 (p + ".attn.kv_b_layer.weight", (heads * (dn + dv), r)),
                 (p + ".attn.output_layer.weight", (h, heads * dv)),
                 (p + ".ffn_norm.weight", (h,))]
        if is_sparse(cfg, i):
            spec += [(p + ".ffn.w_gate", (held, h, fe)),
                     (p + ".ffn.w_up", (held, h, fe)),
                     (p + ".ffn.w_down", (held, fe, h)),
                     (p + ".ffn.shared.gate.weight", (fs, h)),
                     (p + ".ffn.shared.up.weight", (fs, h)),
                     (p + ".ffn.shared.down.weight", (h, fs)),
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


def latent_row_bytes(cfg: Dict[str, Any], cache_bytes_per: int = 2) -> int:
    """Bytes of one place of one layer's latent row: the compressed row
    and the rotary key."""
    return (cfg["kv_lora_rank"] + cfg["qk_rope_head_dim"]) * cache_bytes_per


def latent_leaf_shape(cfg: Dict[str, Any]) -> Tuple[int, int, int, int]:
    """The pooled latent leaf, as an operation's text names its operand:
    ``[slots, 1, max_len, kv_lora_rank]``."""
    s = cfg["serving"]
    return (s["slots"], 1, s["max_len"], cfg["kv_lora_rank"])


def latent_decode_cost(cfg: Dict[str, Any], live_places: float,
                       cache_bytes_per: int = 2) -> Dict[str, float]:
    """Least bytes and operations of the attention of decode steps whose
    queries could attend ``live_places`` places of one layer (summed over
    the steps and slots; every layer has as many): each live place's row
    read once, and for each of the heads a product of the absorbed query
    with the row (``kv_lora_rank + qk_rope_head_dim``) and of the weights
    with the compressed row (``kv_lora_rank``).  From live places, not
    from what a kernel rounds up to."""
    layers, heads = cfg["num_hidden_layers"], cfg["num_attention_heads"]
    r, dr = cfg["kv_lora_rank"], cfg["qk_rope_head_dim"]
    return {"bytes": float(live_places * layers
                           * latent_row_bytes(cfg, cache_bytes_per)),
            "flops": float(live_places * layers * 2 * heads * (r + dr + r))}


def decode_step_bytes(cfg: Dict[str, Any], live_positions: float,
                      weight_bytes_per: int = 2,
                      cache_bytes_per: int = 2) -> float:
    """Bytes one pooled decode step reads: every layer's weights and the
    head's once - all held stacks (the step's batched product reads each
    whatever its tokens chose: ``kinds/hybrid_moe_lm.py``) and the shared
    expert among them, **not** the embedding's table (a step gathers one
    row a slot of it) - and the latent rows at the live positions of the
    active slots, in every layer."""
    w = sum(math.prod(shape) for path, shape in param_spec(cfg)
            if path != ".embedding.weight") * weight_bytes_per
    return w + live_positions * cfg["num_hidden_layers"] \
        * latent_row_bytes(cfg, cache_bytes_per)


def expert_params(cfg: Dict[str, Any]) -> int:
    """Parameters of one routed expert."""
    return 3 * cfg["hidden_size"] * cfg["moe_intermediate_size"]


def expert_layer_cost(cfg: Dict[str, Any], active_experts: float,
                      pairs: float, weight_bytes_per: int = 2) \
        -> Dict[str, float]:
    """As ``kinds/hybrid_moe_lm.expert_layer_cost``: the routed experts'
    least bytes and operations by the counted routing.  The shared expert
    is no pair and is left out, here and in ``expert_stack_shapes``."""
    return {"bytes": float(active_experts * expert_params(cfg)
                           * weight_bytes_per
                           + pairs * 2 * cfg["hidden_size"]
                           * weight_bytes_per),
            "flops": float(2 * expert_params(cfg) * pairs)}


def expert_stack_shapes(cfg: Dict[str, Any]) -> List[Tuple[int, int, int]]:
    """The shapes of the held expert stacks, as an operation's text names
    its operands."""
    n, h, f = (cfg["num_experts"], cfg["hidden_size"],
               cfg["moe_intermediate_size"])
    return sorted({(n, h, f), (n, f, h)})


def model_config(cfg: Dict[str, Any]) -> Dict[str, Any]:
    """The configuration as ``bigdl_tpu.models.sarvam_mla`` reads it: the
    router at its published width, the experts held beside it."""
    return dict(cfg, num_experts=router_width(cfg),
                experts_held=cfg["num_experts"])


def _model(cfg: Dict[str, Any], max_len: int):
    from bigdl_tpu.models import sarvam_mla
    return sarvam_mla(model_config(cfg), max_len)


def seed_experts(cfg: Dict[str, Any], spec, idx: List[int],
                 leaves: List[Any]) -> None:
    """The expert leaves of one block (``idx`` into ``spec`` and
    ``leaves``) as the configuration's ``seeding`` reads what
    ``harness.weights`` seeded: the function the reference reads them
    through, so both sides hold the same numbers."""
    import importlib
    ref = importlib.import_module("reference." + REFERENCE)
    for i in idx:
        if spec[i][0].endswith(".ffn.w_down"):
            leaves[i] = ref.seeded_experts(
                {".ffn.w_down": leaves[i]}, cfg)[".ffn.w_down"]


def build_serve(cfg: Dict[str, Any], seed: int, queue_capacity: int):
    """``ModelServer`` over a ``GenerationScheduler`` with the
    configuration's serving settings and seeded weights in the dtype
    they are served in, made a block at a time and read through the
    configuration's ``seeding``."""
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
        seed_experts(cfg, spec, idx, leaves)
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
