"""Configuration kind ``shared_kv_ssm_lm``: the repo's ``HybridDecoder``
built by ``bigdl_tpu.models.phi4_flash`` (Phi-4-mini-flash-reasoning, the
SambaY decoder-hybrid-decoder): Mamba-1 layers that keep a **state and no
row**, differential attention over rings and over **one full row that
eight layers read**, gated memory units that keep nothing, LayerNorms with
a bias, a head tied to the embedding, no position encoded; served through
``ModelServer`` -> ``GenerationScheduler`` -> ``SlotPool``.

**Schema** (``configs/<name>.json``; ``benchmark/README.md`` lists the keys
every kind shares, ``benchmark/README.shared_kv_ssm_lm.md`` these).  The
sizes carry the names of the model's public ``config.json``:
``vocab_size``, ``hidden_size``, ``num_hidden_layers``,
``num_attention_heads``, ``num_key_value_heads``, ``intermediate_size``,
``sliding_window``, ``mb_per_layer``, ``layer_norm_eps``,
``tie_word_embeddings``; the mixer's ``mamba_d_state``, ``mamba_d_conv``,
``mamba_expand``, ``mamba_dt_rank`` are the public configuration class's
defaults (``assumed``).  ``serving.cache_dtype`` is the dtype of the keys,
the values and the convolution's inputs; the recurrence's state is float32
whatever it says (``serving.state_dtype`` states that and is checked).
``seeding`` (``A``, ``dt``, ``bc_scale``) says how a layer's leaves are
read from what ``harness.weights`` seeds
(``reference.shared_kv_ssm_lm.seeded``).

No training duties: a training job that names this kind fails on the
missing name.
"""
from __future__ import annotations

import math
from typing import Any, Dict, List, Optional, Tuple

from harness import weights
# the scheduler decides a prompt's prefill programs, whatever the model
from harness.kinds.decoder_lm import prefill_plan, warmup_prompt_len  # noqa: F401

REFERENCE = "shared_kv_ssm_lm"


def sizes(cfg: Dict[str, Any]) -> Dict[str, int]:
    """``inner`` (the mixer's channels), ``state``, ``taps``, ``rank`` (of
    the step size's projection), ``head`` (a head's width) and ``half``
    (the layer that hands on its scan output; the full layer follows
    it)."""
    h = cfg["hidden_size"]
    rank = cfg.get("mamba_dt_rank", "auto")
    return {"inner": cfg.get("mamba_expand", 2) * h,
            "state": cfg.get("mamba_d_state", 16),
            "taps": cfg.get("mamba_d_conv", 4),
            "rank": -(-h // 16) if rank == "auto" else int(rank),
            "head": h // cfg["num_attention_heads"],
            "half": cfg["num_hidden_layers"] // 2}


def layer_kinds(cfg: Dict[str, Any]) -> List[str]:
    """``"mamba"``, ``"window"``, ``"full"``, ``"memory"`` or ``"cross"``
    for every layer."""
    half = sizes(cfg)["half"]
    return [("mamba" if i % 2 == 0 else "window") if i < half else
            "mamba" if i == half else "full" if i == half + 1 else
            ("memory" if i % 2 == 0 else "cross")
            for i in range(cfg["num_hidden_layers"])]


def param_spec(cfg: Dict[str, Any]) -> List[Tuple[str, Tuple[int, ...]]]:
    """Leaves of the served model in flattening order."""
    h, f = cfg["hidden_size"], cfg["intermediate_size"]
    z = sizes(cfg)
    inner, n, d = z["inner"], z["state"], z["head"]
    hq, hkv = cfg["num_attention_heads"], cfg["num_key_value_heads"]
    spec = [(".embedding.weight", (cfg["vocab_size"], h))]
    for i, kind in enumerate(layer_kinds(cfg)):
        p = f".blocks[{i}]"
        spec += [(p + ".attn_norm.weight", (h,)), (p + ".attn_norm.bias", (h,))]
        if kind in ("window", "full", "cross"):
            out = hq * d + (0 if kind == "cross" else 2 * hkv * d)
            spec += [(p + ".attn.lambda_q1", (d,)),
                     (p + ".attn.lambda_k1", (d,)),
                     (p + ".attn.lambda_q2", (d,)),
                     (p + ".attn.lambda_k2", (d,)),
                     (p + ".attn.q_layer.weight", (out, h)),
                     (p + ".attn.q_layer.bias", (out,)),
                     (p + ".attn.output_layer.weight", (h, hq * d)),
                     (p + ".attn.output_layer.bias", (h,)),
                     (p + ".attn.norm.weight", (2 * d,))]
        spec += [(p + ".ffn_norm.weight", (h,)), (p + ".ffn_norm.bias", (h,)),
                 (p + ".ffn.gate.weight", (f, h)),
                 (p + ".ffn.up.weight", (f, h)),
                 (p + ".ffn.down.weight", (h, f))]
        if kind == "mamba":
            spec += [(p + ".ssm.A_log", (inner, n)),
                     (p + ".ssm.D", (inner,)),
                     (p + ".ssm.in_proj.weight", (2 * inner, h)),
                     (p + ".ssm.conv.weight", (inner, z["taps"])),
                     (p + ".ssm.conv.bias", (inner,)),
                     (p + ".ssm.x_proj.weight", (z["rank"] + 2 * n, inner)),
                     (p + ".ssm.dt_proj.weight", (inner, z["rank"])),
                     (p + ".ssm.dt_proj.bias", (inner,)),
                     (p + ".ssm.out_proj.weight", (h, inner))]
        elif kind == "memory":
            spec += [(p + ".unit.in_proj.weight", (inner, h)),
                     (p + ".unit.out_proj.weight", (h, inner))]
    return spec + [(".final_norm.weight", (h,)), (".final_norm.bias", (h,))]


def param_blocks(cfg: Dict[str, Any]) -> List[Tuple[str, List[int]]]:
    """The served model in the blocks the check walks: the embedding,
    each layer, and the final norm with the tied head (the embedding's
    leaf again)."""
    paths = [p for p, _ in param_spec(cfg)]
    emb = paths.index(".embedding.weight")
    blocks = [("embedding", [emb])]
    for i in range(cfg["num_hidden_layers"]):
        blocks.append((f"blocks[{i}]", [n for n, p in enumerate(paths)
                                        if p.startswith(f".blocks[{i}].")]))
    return blocks + [("head", [emb] + [n for n, p in enumerate(paths)
                                       if p.startswith(".final_norm.")])]


# ---- what the readers count -----------------------------------------------------

def selective_state_shape(cfg: Dict[str, Any]) -> Tuple[int, int, int]:
    """A Mamba-1 layer's pooled recurrence state as the program keeps it
    and as an operation's text names it: ``[slots, state, inner]`` float32
    (the channels along the lanes)."""
    z = sizes(cfg)
    return (cfg["serving"]["slots"], z["state"], z["inner"])


def state_layers(cfg: Dict[str, Any]) -> int:
    return layer_kinds(cfg).count("mamba")


def shared_leaf_shape(cfg: Dict[str, Any]) -> Tuple[int, int, int, int]:
    """The pooled leaf (keys, or values) of the one full row, as an
    operation's text names it: ``[slots, key/value heads / 2, max_len, 2 x
    head width]`` (paired heads side by side)."""
    s = cfg["serving"]
    return (s["slots"], cfg["num_key_value_heads"] // 2, s["max_len"],
            2 * sizes(cfg)["head"])


def place_bytes(cfg: Dict[str, Any], cache_bytes_per: int = 2) -> int:
    """Bytes of one place of a row or ring: keys and values."""
    return 2 * cfg["num_key_value_heads"] * sizes(cfg)["head"] \
        * cache_bytes_per


def shared_kv_decode_cost(cfg: Dict[str, Any], reads: float,
                          cache_bytes_per: int = 2) -> Dict[str, float]:
    """Least bytes and operations of attending ``reads`` places of the
    shared row (live places x the layers that read them): each place's
    keys and values read once a reader, and for each of the query heads a
    product of the (zero-filled) query with the paired key and of the
    weights with the paired value."""
    wide = 2 * sizes(cfg)["head"]
    return {"bytes": float(reads * place_bytes(cfg, cache_bytes_per)),
            "flops": float(reads * cfg["num_attention_heads"] * 2 * 2 * wide)}


def selective_step_cost(cfg: Dict[str, Any],
                        rows: Optional[float] = None) -> Dict[str, float]:
    """Least bytes and operations of one Mamba-1 layer's state update in a
    decode step over ``rows`` slots (default: the pool's, which the step's
    one program a layer moves whatever is active): each row's state read
    once and written once in float32 and the rows beside it (``dt``, ``dt
    * x``, ``y``, ``B``, ``C``); an exponential, two multiply-adds and the
    sum with ``C`` an element."""
    slots, n, inner = selective_state_shape(cfg)
    rows = slots if rows is None else rows
    return {"bytes": float(rows * 4 * (2 * n * inner + 3 * inner + 2 * n)),
            "flops": float(rows * 6 * n * inner)}


def selective_scan_cost(cfg: Dict[str, Any],
                        positions: float) -> Dict[str, float]:
    """Least bytes and operations of one Mamba-1 layer's scan over
    ``positions`` (padding included: the scan runs over what it is
    handed): a position's ``x``, ``dt`` and ``y`` over the channels and its
    ``B`` and ``C`` in float32, the carried state left out (it stays where
    it is between positions); the update's operations as
    :func:`selective_step_cost` counts them."""
    _, n, inner = selective_state_shape(cfg)
    return {"bytes": float(positions * 4 * (3 * inner + 2 * n)),
            "flops": float(positions * 6 * n * inner)}


def decode_step_bytes(cfg: Dict[str, Any], live_positions: float,
                      weight_bytes_per: int = 2,
                      cache_bytes_per: int = 2) -> float:
    """Bytes one pooled decode step must move: every layer's weights and
    the tied head's (the embedding table, read once **as the head**; the
    step's lookups gather one row a slot of it); the Mamba-1 layers'
    states **read and written** (float32, and the convolution's inputs,
    for every slot of the pool: the cell keeps them all taken); every
    ring whole (a window layer's step reads its ring, not what is live
    in it); and the keys and values of the one full row at the live
    positions of the active slots, **once for every layer that reads
    them** (the full layer and the cross layers)."""
    w = sum(math.prod(shape) for _, shape in param_spec(cfg)) \
        * weight_bytes_per
    kinds, z = layer_kinds(cfg), sizes(cfg)
    slots, s = cfg["serving"]["slots"], cfg["serving"]
    conv = slots * (z["taps"] - 1) * z["inner"] * cache_bytes_per
    states = kinds.count("mamba") * (selective_step_cost(cfg)["bytes"]
                                     + 2 * conv)
    ring = 1 + min(s["max_len"], cfg["sliding_window"] + s["prefill_chunk"] - 1)
    rings = kinds.count("window") * slots * ring \
        * place_bytes(cfg, cache_bytes_per)
    readers = 1 + kinds.count("cross")
    return w + states + rings + readers * live_positions \
        * place_bytes(cfg, cache_bytes_per)


# ---- the served model -----------------------------------------------------------

def _model(cfg: Dict[str, Any], max_len: int):
    from bigdl_tpu.models import phi4_flash
    return phi4_flash(cfg, max_len)


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
