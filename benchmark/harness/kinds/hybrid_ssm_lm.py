"""Configuration kind ``hybrid_ssm_lm``: the repo's ``HybridDecoder`` built
by ``bigdl_tpu.models.falcon_h1`` (every layer a grouped-query attention
over full rows **in parallel** with a Mamba-2 mixer on one normed input,
then a dense gated feed-forward; the architecture's µP multipliers; an
untied head), served through ``ModelServer`` -> ``GenerationScheduler`` ->
``SlotPool``: a row of keys and values **and** a fixed-size state a slot
and layer.

**Schema** (``configs/<name>.json``; ``benchmark/README.md`` lists the keys
every kind shares, ``benchmark/README.hybrid_ssm_lm.md`` these).  The sizes
carry the names of the model's public ``config.json``: ``vocab_size``,
``hidden_size``, ``num_hidden_layers`` (as cut), ``num_attention_heads``,
``num_key_value_heads``, ``head_dim``, ``rope_theta``,
``intermediate_size``, ``rms_norm_eps``; the mixer's ``mamba_n_heads``,
``mamba_d_head``, ``mamba_d_ssm``, ``mamba_n_groups``, ``mamba_d_state``,
``mamba_d_conv``, ``mamba_chunk_size``; and the multipliers
(``embedding_multiplier``, ``lm_head_multiplier``, ``key_multiplier``,
``attention_in_multiplier``, ``attention_out_multiplier``,
``ssm_in_multiplier``, ``ssm_out_multiplier``, ``ssm_multipliers``,
``mlp_multipliers``).  ``serving.cache_dtype`` is the dtype of the keys,
the values and the convolution's inputs; the recurrence's state is float32
whatever it says (``serving.state_dtype`` states that and is checked).
``seeding`` (``A``, ``dt``, ``projection_std``, ``output_std``) says how a
mixer's leaves are read from what ``harness.weights`` seeds
(``reference.hybrid_ssm_lm.seeded_mixer``;
``benchmark/README.hybrid_ssm_lm.md``).

No training duties: a training job that names this kind fails on the
missing name.
"""
from __future__ import annotations

import math
from typing import Any, Dict, List, Optional, Tuple

from harness import weights
# the scheduler decides a prompt's prefill programs, whatever the model
from harness.kinds.decoder_lm import prefill_plan, warmup_prompt_len  # noqa: F401

REFERENCE = "hybrid_ssm_lm"


def mixer_sizes(cfg: Dict[str, Any]) -> Dict[str, int]:
    """``inner`` (heads x head width), ``channels`` (what the convolution
    runs over: x, B and C) and ``projected`` (z, those, and a step size a
    head)."""
    inner = cfg["mamba_n_heads"] * cfg["mamba_d_head"]
    channels = inner + 2 * cfg["mamba_n_groups"] * cfg["mamba_d_state"]
    return {"inner": inner, "channels": channels,
            "projected": inner + channels + cfg["mamba_n_heads"]}


def param_spec(cfg: Dict[str, Any]) -> List[Tuple[str, Tuple[int, ...]]]:
    """Leaves of the served model in flattening order."""
    h, d, f = cfg["hidden_size"], cfg["head_dim"], cfg["intermediate_size"]
    heads, kv = cfg["num_attention_heads"], cfg["num_key_value_heads"]
    m, mh = mixer_sizes(cfg), cfg["mamba_n_heads"]
    spec = [(".embedding.weight", (cfg["vocab_size"], h))]
    for i in range(cfg["num_hidden_layers"]):
        p = f".blocks[{i}]"
        spec += [(p + ".attn_norm.weight", (h,)),
                 (p + ".attn.q_layer.weight", (heads * d, h)),
                 (p + ".attn.k_layer.weight", (kv * d, h)),
                 (p + ".attn.v_layer.weight", (kv * d, h)),
                 (p + ".attn.output_layer.weight", (h, heads * d)),
                 (p + ".ffn_norm.weight", (h,)),
                 (p + ".ffn.gate.weight", (f, h)),
                 (p + ".ffn.up.weight", (f, h)),
                 (p + ".ffn.down.weight", (h, f)),
                 (p + ".ssm.dt_bias", (mh,)),
                 (p + ".ssm.A_log", (mh,)),
                 (p + ".ssm.D", (mh,)),
                 (p + ".ssm.in_proj.weight", (m["projected"], h)),
                 (p + ".ssm.conv.weight", (m["channels"],
                                           cfg["mamba_d_conv"])),
                 (p + ".ssm.conv.bias", (m["channels"],)),
                 (p + ".ssm.norm.weight", (m["inner"],)),
                 (p + ".ssm.out_proj.weight", (h, m["inner"]))]
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


def state_shape(cfg: Dict[str, Any]) -> Tuple[int, int, int, int]:
    """A layer's pooled recurrence state as the program keeps it and as
    an operation's text names it: ``[slots, heads, state, head width]``
    float32 (the head's width along the lanes)."""
    return (cfg["serving"]["slots"], cfg["mamba_n_heads"],
            cfg["mamba_d_state"], cfg["mamba_d_head"])


def ssm_step_cost(cfg: Dict[str, Any],
                  rows: Optional[float] = None) -> Dict[str, float]:
    """Least bytes and operations of one layer's state update in a decode
    step over ``rows`` slots (default: the pool's, which the step's one
    program a layer moves whatever is active): each row's state read once
    and written once in float32, and the rows in and out beside it (the
    decay, ``dt * x``, ``B``, ``C``, ``y``); two multiply-adds an element
    (the update and the product with ``C``)."""
    slots, heads, n, p = state_shape(cfg)
    rows = slots if rows is None else rows
    state = heads * n * p
    beside = 3 * heads * p + 2 * cfg["mamba_n_groups"] * n
    return {"bytes": float(rows * 4 * (2 * state + beside)),
            "flops": float(rows * 4 * state)}


def ssm_scan_cost(cfg: Dict[str, Any], positions: float) -> Dict[str, float]:
    """Least bytes and operations of one layer's chunked scan over
    ``positions`` (padding included: the scan runs over what it is handed)
    in sub-chunks of ``mamba_chunk_size``: a position's products with the
    sub-chunk's others (``C . B`` a group, then the weighted sum of ``dt *
    x`` a head), with the state that entered and into the state that
    leaves; the carried state read and written once a program is left out
    (at most 1/128 of the bytes a position).  Float32 operands."""
    heads, p = cfg["mamba_n_heads"], cfg["mamba_d_head"]
    groups, n = cfg["mamba_n_groups"], cfg["mamba_d_state"]
    sub = cfg.get("mamba_chunk_size", 128)
    flops = positions * 2.0 * (groups * sub * n        # C . B
                               + heads * sub * p       # weights @ dt x
                               + 2 * heads * n * p)    # with and into the state
    per_position = heads * p * 2 + 2 * groups * n + heads   # x, y, B, C, dt
    return {"bytes": float(positions * 4 * per_position), "flops": flops}


def decode_step_bytes(cfg: Dict[str, Any], live_positions: float,
                      weight_bytes_per: int = 2,
                      cache_bytes_per: int = 2) -> float:
    """Bytes one pooled decode step must move: every layer's weights and
    the head's read once (the embedding is **not** counted: a step gathers
    one row a slot of it, ``slots x hidden``, not the table); every
    layer's states **read and written** (the recurrence's in float32 and
    the convolution's inputs, for every slot of the pool: the cell keeps
    them all taken); and the keys and values at the live positions of the
    active slots."""
    spec = param_spec(cfg)
    w = sum(math.prod(shape) for path, shape in spec
            if path != ".embedding.weight") * weight_bytes_per
    slots, layers = cfg["serving"]["slots"], cfg["num_hidden_layers"]
    conv = slots * (cfg["mamba_d_conv"] - 1) * mixer_sizes(cfg)["channels"] \
        * cache_bytes_per
    states = layers * (ssm_step_cost(cfg)["bytes"] + 2 * conv)
    kv = layers * live_positions * 2 * cfg["num_key_value_heads"] \
        * cfg["head_dim"] * cache_bytes_per
    return w + states + kv


def _model(cfg: Dict[str, Any], max_len: int):
    from bigdl_tpu.models import falcon_h1
    return falcon_h1(cfg, max_len)


def seed_mixer(cfg: Dict[str, Any], spec, idx: List[int],
               leaves: List[Any]) -> None:
    """The mixer's leaves of one block (``idx`` into ``spec`` and
    ``leaves``) as the configuration's ``seeding`` reads what
    ``weights.make`` made: the reference's own function, so that the
    program serves the numbers the check compares it with."""
    import importlib
    import jax.numpy as jnp
    ref = importlib.import_module("reference." + REFERENCE)
    at = {spec[i][0].split(".ssm", 1)[1]: i for i in idx
          if ".ssm." in spec[i][0]}
    if at:
        new = ref.seeded_mixer({k: leaves[i] for k, i in at.items()}, cfg,
                               jnp.dtype(cfg["serving"]["weights_dtype"]))
        for k, i in at.items():
            leaves[i] = new[k]


def build_serve(cfg: Dict[str, Any], seed: int, queue_capacity: int):
    """``ModelServer`` over a ``GenerationScheduler`` with the
    configuration's serving settings and seeded weights in the dtype they
    are served in, made a block at a time."""
    import jax
    import jax.numpy as jnp
    from bigdl_tpu.serving import ModelServer
    from bigdl_tpu.serving.generation import GenerationScheduler

    s = cfg["serving"]
    if s.get("state_dtype", "float32") != "float32":
        raise ValueError("the recurrence's state is served in float32")
    abstract = jax.eval_shape(lambda: _model(cfg, s["max_len"]))
    weights.reset_program_rng(seed)
    spec = param_spec(cfg)
    weights.check_spec(spec, abstract)
    leaves: List[Any] = [None] * len(spec)
    for _name, idx in param_blocks(cfg):
        for i, leaf in zip(idx, weights.make(
                spec, seed, jnp.dtype(s["weights_dtype"]), only=idx)):
            leaves[i] = leaf
        seed_mixer(cfg, spec, idx, leaves)
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
