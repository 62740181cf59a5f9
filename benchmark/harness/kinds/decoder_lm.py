"""Configuration kind ``decoder_lm``: the repo's ``TransformerLM``, trained
through ``Optimizer.optimize()`` and served through ``ModelServer`` ->
``GenerationScheduler`` -> ``SlotPool``."""
from __future__ import annotations

from typing import Any, Dict, List, Tuple

from harness import flops, weights

REFERENCE = "decoder_lm"


def param_spec(cfg: Dict[str, Any], prefix: str = "") \
        -> List[Tuple[str, Tuple[int, ...]]]:
    """Leaves of ``TransformerLM`` in flattening order."""
    h, f = cfg["hidden_size"], cfg["ffn_dim"]
    spec = [(prefix + ".embedding.weight", (cfg["vocab_size"] + 1, h))]
    for i in range(cfg["num_hidden_layers"]):
        p = f"{prefix}.blocks[{i}]"
        spec += [(p + ".self_norm.weight", (h,)), (p + ".self_norm.bias", (h,))]
        spec += [(p + f".self_attn.{n}.weight", (h, h))
                 for n in ("q_layer", "k_layer", "v_layer", "output_layer")]
        spec += [(p + ".ffn_norm.weight", (h,)), (p + ".ffn_norm.bias", (h,)),
                 (p + ".ffn.filter_layer.weight", (f, h)),
                 (p + ".ffn.filter_layer.bias", (f,)),
                 (p + ".ffn.output_layer.weight", (h, f)),
                 (p + ".ffn.output_layer.bias", (h,))]
    spec += [(prefix + ".final_norm.weight", (h,)),
             (prefix + ".final_norm.bias", (h,))]
    return spec


def param_blocks(cfg: Dict[str, Any]) -> List[Tuple[str, List[int]]]:
    """The served model in the blocks the check walks, as ``(name,
    indices into param_spec)``: the embedding, each layer, and the final
    norm with the head (tied: the embedding's leaf once more)."""
    paths = [p for p, _ in param_spec(cfg)]
    blocks = [("embedding", [paths.index(".embedding.weight")])]
    for i in range(cfg["num_hidden_layers"]):
        blocks.append((f"blocks[{i}]", [n for n, p in enumerate(paths)
                                        if p.startswith(f".blocks[{i}].")]))
    return blocks + [("head", [n for n, p in enumerate(paths) if p.startswith(
        (".embedding.", ".final_norm."))])]


# bytes one pooled decode step of this kind must read, for decode_roofline
decode_step_bytes = flops.decode_step_bytes


def _lm(cfg: Dict[str, Any], max_len: int, **kw):
    from bigdl_tpu.models import transformer_lm
    return transformer_lm(
        vocab_size=cfg["vocab_size"], hidden_size=cfg["hidden_size"],
        num_layers=cfg["num_hidden_layers"],
        num_heads=cfg["num_attention_heads"], filter_size=cfg["ffn_dim"],
        max_len=max_len, **kw)


def _flat_class():
    from bigdl_tpu.core.module import Module

    class FlatLM(Module):
        """``[B, T]`` tokens to ``[B*T, vocab+1]`` logits, the shape the
        flat-target criteria take (as ``bigdl-tpu-perf`` wraps its LMs)."""

        def __init__(self, lm):
            super().__init__()
            self.lm = lm

        def forward(self, x):
            out = self.lm.forward(x)
            return out.reshape(-1, out.shape[-1])

    return FlatLM


def build_train(cfg: Dict[str, Any], job: Dict[str, Any], seed: int,
                devices) -> Dict[str, Any]:
    import jax
    import jax.numpy as jnp
    import bigdl_tpu.nn as nn

    seq = job["seq_len"]
    flat = _flat_class()
    abstract = jax.eval_shape(lambda: flat(_lm(
        cfg, seq, remat=bool(job.get("remat", False)), padded_inputs=False)))
    weights.reset_program_rng(seed)
    spec = train_param_spec(cfg)
    weights.check_spec(spec, abstract)
    shardings = (weights.row_shardings(spec, devices)
                 if len(devices) > 1 else None)
    leaves = weights.make(spec, seed, jnp.float32, shardings)
    model = jax.tree_util.tree_unflatten(
        jax.tree_util.tree_structure(abstract), leaves)
    x, y = batch(cfg, job, seed)
    return {"model": model, "criterion": nn.CrossEntropyCriterion(),
            "x": x, "y": y, "spec": spec,
            "flops_per_step": flops.lm_train_flops_per_step(
                cfg, job["batch"], seq),
            "samples_per_step": job["batch"] * seq}


def batch(cfg: Dict[str, Any], job: Dict[str, Any], seed: int):
    """One batch of token ids ``[B, T]`` and next-token targets
    ``[B*T]`` (both 1-based, no padding), made on the device."""
    import jax
    b, t, v = job["batch"], job["seq_len"], cfg["vocab_size"]

    @jax.jit
    def make(key):
        toks = jax.random.randint(key, (b, t + 1), 1, v + 1)
        return toks[:, :-1], toks[:, 1:].reshape(-1)
    return make(weights.seed_key(seed, 2))


def build_serve(cfg: Dict[str, Any], seed: int, queue_capacity: int):
    """``ModelServer`` over a ``GenerationScheduler`` with the
    configuration's serving settings and seeded weights in the dtype
    they are served in."""
    import jax
    import jax.numpy as jnp
    from bigdl_tpu.serving import ModelServer
    from bigdl_tpu.serving.generation import GenerationScheduler

    s = cfg["serving"]
    abstract = jax.eval_shape(lambda: _lm(cfg, s["max_len"]))
    weights.reset_program_rng(seed)
    spec = param_spec(cfg)
    weights.check_spec(spec, abstract)
    leaves = weights.make(spec, seed, jnp.dtype(s["weights_dtype"]))
    model = jax.tree_util.tree_unflatten(
        jax.tree_util.tree_structure(abstract), leaves).eval_mode()
    engine = GenerationScheduler(
        model, slots=s["slots"], dtype=jnp.dtype(s["cache_dtype"]),
        prefill_chunk=s["prefill_chunk"], prefill_batch=s["prefill_batch"],
        queue_capacity=queue_capacity, admission=s["admission"],
        prefix_cache_bytes=None)
    del model, leaves     # the pool holds its own copy
    return ModelServer(generator=engine), engine


def _buckets(top: int) -> List[int]:
    """Powers of two up to ``top``, and ``top`` itself (the shapes the
    scheduler compiles prefill programs for)."""
    out, b = [], 1
    while b < top:
        out.append(b)
        b *= 2
    return out + [top]


def prefill_plan(prompt_len: int, serving: Dict[str, Any]) -> List[Tuple[str, int]]:
    """The prefill program calls one prompt needs, as the scheduler
    makes them with the prefix cache off: a prompt no longer than
    ``prefill_chunk`` takes one bucketed call; a longer one takes full
    chunks and a last chunk from the power-of-two widths.  The benchmark
    uses this to warm up exactly the shapes its traffic needs and to
    place a prompt's tokens in time; ``compiles_in_window`` tells when
    it no longer matches the program."""
    chunk = serving["prefill_chunk"]
    if prompt_len <= 1:
        return []
    if prompt_len <= chunk:
        b = next(b for b in _buckets(serving["max_len"]) if prompt_len <= b)
        return [("legacy", b)]
    plan, pos, end = [], 0, prompt_len - 1
    while pos < end:
        r = end - pos
        if r >= chunk:
            plan.append(("chunk", chunk))
            pos += chunk
        else:
            plan.append(("chunk", next(b for b in _buckets(chunk) if r <= b)))
            pos = end
    return plan


def warmup_prompt_len(shape: Tuple[str, int], serving: Dict[str, Any]) -> int:
    """A prompt length whose prefill uses the program ``shape``."""
    mode, w = shape
    if mode == "legacy":
        return w
    chunk = serving["prefill_chunk"]
    return chunk + 1 if w == chunk else chunk + w + 1


TRAIN_PREFIX = ".lm"      # the trained model is FlatLM(lm)


def train_param_spec(cfg: Dict[str, Any]):
    return param_spec(cfg, TRAIN_PREFIX)


def reference_train(ref, params, x, y, cfg, job, steps, precision):
    """Losses of the first ``steps`` steps; the trained leaves of 1.3 B
    parameters are not read back."""
    return ref.train_losses(params, x, y, cfg, job["optimizer"], steps,
                            precision, TRAIN_PREFIX), None
