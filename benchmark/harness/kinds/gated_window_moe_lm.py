"""Configuration kind ``gated_window_moe_lm``: the repo's ``HybridDecoder``
built by ``bigdl_tpu.models.afmoe`` (Trinity-Mini): grouped-query
attention whose context is **gated** and whose query and key heads are
normed, three **window** layers (rotated, over rings) to every **full** one
(not rotated at all, over rows), a norm **on both sides** of every mixer
and feed-forward, leading dense gated layers, then sigmoid-routed experts
**all of which this chip holds** beside a shared one; an untied head and an
embedding scaled by ``sqrt(hidden_size)``; served through ``ModelServer``
-> ``GenerationScheduler`` -> ``SlotPool``.

**Schema** (``configs/<name>.json``; ``benchmark/README.md`` lists the keys
every kind shares, ``benchmark/README.gated_window_moe_lm.md`` these).  The
sizes carry the names of the model's public ``config.json``:
``vocab_size``, ``hidden_size``, ``num_hidden_layers`` (as cut),
``layer_types`` (the published list; the first ``num_hidden_layers``
entries are built), ``num_attention_heads``, ``num_key_value_heads``,
``head_dim``, ``sliding_window``, ``rope_theta``, ``intermediate_size``,
``num_dense_layers`` (as cut), ``moe_intermediate_size``, ``num_experts``
(the router's width), ``num_experts_per_tok``, ``num_shared_experts``,
``route_scale``, ``route_norm``, ``score_func``, ``rms_norm_eps``,
``mup_enabled``.  ``n_routed_experts`` is the number of experts **held
here**, from ``experts_offset`` (the keys the routing counters' readers
divide by): all of them in the configuration that stands.
``serving.cache_dtype`` is the dtype of the keys and values, rings and rows
alike; ``serving.prefill_chunk`` is also what a ring is allocated beside
its window.  ``seeding`` (``qk_scale``, ``routed_down``)
says how a layer's leaves are read from what ``harness.weights`` seeds
(``reference.gated_window_moe_lm.seeded``).

No training duties: a training job that names this kind fails on the
missing name.
"""
from __future__ import annotations

import math
from typing import Any, Dict, List, Optional, Tuple

from harness import weights
# a head's width and the experts held are read from the keys ``conv_moe_lm``
# reads them from
from harness.kinds.conv_moe_lm import head_width, held_experts
# the scheduler decides a prompt's prefill programs, whatever the model
from harness.kinds.decoder_lm import prefill_plan, warmup_prompt_len  # noqa: F401
# what the expert readers take is what ``hybrid_moe_lm`` counts, from the
# same keys (``hidden_size``, ``moe_intermediate_size`` and
# ``n_routed_experts``, the experts held here)
from harness.kinds.hybrid_moe_lm import (  # noqa: F401
    expert_layer_cost, expert_params, expert_stack_shapes)

REFERENCE = "gated_window_moe_lm"


def layer_kinds(cfg: Dict[str, Any]) -> List[Tuple[Optional[int], bool]]:
    """``(window or None, sparse)`` of each layer that is built."""
    windows = {"sliding_attention": cfg["sliding_window"],
               "full_attention": None}
    return [(windows[t], i >= cfg.get("num_dense_layers", 0))
            for i, t in enumerate(
                cfg["layer_types"][:cfg["num_hidden_layers"]])]


def layer_spec(cfg: Dict[str, Any], sparse: bool,
               held: Optional[int] = None) \
        -> List[Tuple[str, Tuple[int, ...]]]:
    """One layer's leaves by their names inside it, in flattening order;
    ``held`` the experts held (default: the configuration's)."""
    h, d = cfg["hidden_size"], head_width(cfg)
    heads, kv = cfg["num_attention_heads"], cfg["num_key_value_heads"]
    f, fe = cfg["intermediate_size"], cfg["moe_intermediate_size"]
    wide = cfg["num_experts"]
    held = held_experts(cfg) if held is None else held
    spec = [(".attn_norm.weight", (h,)),
            (".attn.q_layer.weight", (heads * d, h)),
            (".attn.k_layer.weight", (kv * d, h)),
            (".attn.v_layer.weight", (kv * d, h)),
            (".attn.output_layer.weight", (h, heads * d)),
            (".attn.q_norm.weight", (d,)),
            (".attn.k_norm.weight", (d,)),
            (".attn.gate_layer.weight", (heads * d, h)),
            (".attn_post_norm.weight", (h,)),
            (".ffn_norm.weight", (h,))]
    if sparse:
        spec += [(".ffn.w_gate", (held, h, fe)),
                 (".ffn.w_up", (held, h, fe)),
                 (".ffn.w_down", (held, fe, h))]
        if cfg.get("num_shared_experts"):
            spec += [(".ffn.shared.gate.weight", (fe, h)),
                     (".ffn.shared.up.weight", (fe, h)),
                     (".ffn.shared.down.weight", (h, fe))]
        spec += [(".ffn.router.weight", (wide, h)),
                 (".ffn.router.bias", (wide,))]
    else:
        spec += [(".ffn.gate.weight", (f, h)),
                 (".ffn.up.weight", (f, h)),
                 (".ffn.down.weight", (h, f))]
    return spec + [(".ffn_post_norm.weight", (h,))]


def param_spec(cfg: Dict[str, Any]) -> List[Tuple[str, Tuple[int, ...]]]:
    """Leaves of the served model in flattening order.  The expert stacks
    lie ``[experts, in, out]`` (what the TPU's products over them take as
    it lies); ``weights.make`` scales a stack by its last axis
    (``assumed.expert_stacks``, ``seeding`` in the configuration)."""
    h = cfg["hidden_size"]
    spec = [(".embedding.weight", (cfg["vocab_size"], h))]
    for i, (_window, sparse) in enumerate(layer_kinds(cfg)):
        spec += [(f".blocks[{i}]" + name, shape)
                 for name, shape in layer_spec(cfg, sparse)]
    return spec + [(".final_norm.weight", (h,)),
                   (".lm_head.weight", (cfg["vocab_size"], h))]


def param_blocks(cfg: Dict[str, Any]) -> List[Tuple[str, List[int]]]:
    """The served model in the blocks the check walks: the embedding,
    each layer, and the last norm with the untied head."""
    paths = [p for p, _ in param_spec(cfg)]
    blocks = [("embedding", [paths.index(".embedding.weight")])]
    for i in range(cfg["num_hidden_layers"]):
        blocks.append((f"blocks[{i}]", [n for n, p in enumerate(paths)
                                        if p.startswith(f".blocks[{i}].")]))
    return blocks + [("head", [n for n, p in enumerate(paths) if p.startswith(
        (".final_norm.", ".lm_head."))])]


def published_params(cfg: Dict[str, Any]) -> Dict[str, int]:
    """Parameters of the model as published, from the configuration's own
    keys and ``published`` (the depth and the dense layers it was cut
    from, every expert held): ``total``, and ``active``, what works on
    one token (``num_experts_per_tok`` experts of every expert layer in
    place of all of them, and of the embedding's table the one row it
    looks up)."""
    pub = cfg["published"]
    n, dense = pub["num_hidden_layers"], pub["num_dense_layers"]

    def count(spec):
        return sum(math.prod(shape) for _, shape in spec)
    sparse = count(layer_spec(cfg, True, held=cfg["num_experts"]))
    ends = 2 * cfg["vocab_size"] * cfg["hidden_size"] + cfg["hidden_size"]
    total = dense * count(layer_spec(cfg, False)) + (n - dense) * sparse \
        + ends
    idle = (cfg["num_experts"] - cfg["num_experts_per_tok"]) \
        * expert_params(cfg)
    table = (cfg["vocab_size"] - 1) * cfg["hidden_size"]
    return {"total": total, "active": total - (n - dense) * idle - table}


# ---- what the readers count --------------------------------------------------

def place_bytes(cfg: Dict[str, Any], cache_bytes_per: int = 2) -> int:
    """Bytes of one place of a layer's ring or row: keys and values."""
    return 2 * cfg["num_key_value_heads"] * head_width(cfg) * cache_bytes_per


def ring_places(cfg: Dict[str, Any]) -> int:
    """Places of one slot's ring as the pool allocates it: the window,
    what a prefill chunk needs beside it, and the spare place
    (``GroupedQueryAttention.cache_length``)."""
    s = cfg["serving"]
    return 1 + min(s["max_len"],
                   cfg["sliding_window"] + s["prefill_chunk"] - 1)


def ring_leaf_shape(cfg: Dict[str, Any]) -> Tuple[int, int, int, int]:
    """A window layer's pooled leaf, as an operation's text names its
    operand: ``[slots, key/value heads, ring places, head width]``."""
    return (cfg["serving"]["slots"], cfg["num_key_value_heads"],
            ring_places(cfg), head_width(cfg))


def ring_step_cost(cfg: Dict[str, Any], live_places: float,
                   cache_bytes_per: int = 2) -> Dict[str, float]:
    """Least bytes and operations of the window layers' attention in
    decode steps whose queries could attend ``live_places`` ring places
    (summed over the steps, the slots and the window layers: the pool's
    ``ring_positions_live``): each live place's keys and values read once,
    and for each query head a product with the key and one with the value.
    From live places, not from what a step that reads rings whole reads."""
    heads, d = cfg["num_attention_heads"], head_width(cfg)
    return {"bytes": float(live_places * place_bytes(cfg, cache_bytes_per)),
            "flops": float(live_places * 2 * heads * 2 * d)}


def decode_step_bytes(cfg: Dict[str, Any], live_positions: float,
                      weight_bytes_per: int = 2,
                      cache_bytes_per: int = 2) -> float:
    """Bytes one pooled decode step must move: every weight once but the
    embedding's table (a step looks up a row a slot, and the head has its
    own table), the stacks of **every** expert among them (with all of a
    layer's experts here and some ninety rows choosing eight each, a step
    leaves few of the 128 unchosen; ``moe_active_expert_share`` says how
    few), the shared expert and the untied head; the keys and values of
    the live positions of the active slots (``live_positions``, a full layer) in
    each full layer; and in each window layer the live places of the
    rings, at most the window of each slot, counted as ``min(live, slots
    x window)`` (an upper bound, as ``hybrid_moe_lm`` counts them: a pool
    of short requests beside one long one attends fewer)."""
    w = sum(math.prod(shape) for path, shape in param_spec(cfg)
            if path != ".embedding.weight") * weight_bytes_per
    windows = [window for window, _ in layer_kinds(cfg)]
    rings = sum(min(live_positions, cfg["serving"]["slots"] * window)
                for window in windows if window is not None)
    rows = windows.count(None) * live_positions
    return w + (rings + rows) * place_bytes(cfg, cache_bytes_per)


# ---- the served model ----------------------------------------------------------

def model_config(cfg: Dict[str, Any]) -> Dict[str, Any]:
    """The configuration as ``bigdl_tpu.models.afmoe`` reads it: the
    router at its published width, the experts held beside it."""
    return dict(cfg, experts_held=held_experts(cfg))


def _model(cfg: Dict[str, Any], max_len: int):
    from bigdl_tpu.models import afmoe
    return afmoe(model_config(cfg), max_len)


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
    kind, and never more than a block's float32 noise beside what is
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
    for _name, idx in param_blocks(cfg):
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
