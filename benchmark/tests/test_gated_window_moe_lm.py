"""The kind ``gated_window_moe_lm`` and its reference: the specification is
the program's model leaf for leaf, the cell is one pipeline stage as the
configuration states it (4,241.5 M parameters here, 26,124 M from the
published keys), the check's blocks in turn compute what the whole forward
computes, the seeding reads the harness's leaves as it says, the rehearsal
run of the cell is ``correct``, each broken path of the mechanism reads
over the limit, the int8 control fails the limit, and the three readers read what they say
on recorded observations.  ``BROKEN`` is also what the builder's chip runs
break (``PERF.md`` section 2)."""
import json
import os

import jax
import jax.numpy as jnp
import numpy as np
import pytest

import run
from harness import manifest, weights
from harness.kinds import gated_window_moe_lm as kind
from reference import gated_window_moe_lm as ref

LIMIT_AT_TEST_SIZE = 0.05
BENCH = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
REHEARSAL = os.path.join(BENCH, "rehearsal")
CELL = "trinitymini_serve_mixed"
NAME = "trinity-mini"


def rehearsal_cfg():
    return manifest.load_json(os.path.join(REHEARSAL, "configs",
                                           NAME + ".json"))


def cell_cfg():
    return manifest.load_json(os.path.join(BENCH, "configs", NAME + ".json"))


@pytest.mark.parametrize("cfg", [rehearsal_cfg(), cell_cfg()],
                         ids=["rehearsal", "cell"])
def test_the_specification_is_the_programs_model(cfg):
    """Leaf for leaf, in order, shapes only: nothing is allocated."""
    abstract = jax.eval_shape(
        lambda: kind._model(cfg, cfg["serving"]["max_len"]))
    spec = kind.param_spec(cfg)
    weights.check_spec(spec, abstract)
    blocks = kind.param_blocks(cfg)
    assert [n for n, _ in blocks] == ["embedding"] + [
        f"blocks[{i}]" for i in range(cfg["num_hidden_layers"])] + ["head"]
    # each leaf once: the head is its own table
    assert sorted(i for _, idx in blocks for i in idx) \
        == list(range(len(spec)))
    assert {weights._rule(p) for p, _ in spec} == {"embedding", "weight",
                                                   "bias"}


def test_the_cell_is_one_stage_as_the_configuration_states():
    cfg = cell_cfg()
    spec = kind.param_spec(cfg)
    params = sum(int(np.prod(s)) for _, s in spec)
    assert round(params / 1e5) == 42415               # 4,241.5 M
    published = kind.published_params(cfg)
    assert round(published["total"] / 1e6) == 26124    # 26B
    assert round(published["active"] / 1e6) == 3065    # A3B

    def layer(i):
        return sum(int(np.prod(s)) for p, s in spec
                   if p.startswith(f".blocks[{i}]."))
    assert round(layer(0) / 1e6, 2) == 65.02       # a window, dense
    assert round(layer(1) / 1e6, 1) == 839.1       # a window, experts
    assert layer(3) == layer(1)                    # the full layer
    attention = sum(int(np.prod(s)) for p, s in spec
                    if p.startswith(".blocks[1].attn."))
    assert round(attention / 1e6, 2) == 27.26
    assert round(128 * kind.expert_params(cfg) / 1e6, 1) == 805.3
    assert kind.layer_kinds(cfg) == [(2048, False), (2048, True),
                                     (2048, True), (None, True),
                                     (2048, True)]
    shapes = dict(spec)
    assert shapes[".blocks[0].attn.gate_layer.weight"] == (4096, 2048)
    assert shapes[".blocks[0].attn.k_layer.weight"] == (512, 2048)
    assert shapes[".blocks[0].attn.q_norm.weight"] == (128,)
    assert shapes[".blocks[0].ffn.gate.weight"] == (6144, 2048)
    assert shapes[".blocks[1].ffn.w_gate"] == (128, 2048, 1024)
    assert shapes[".blocks[1].ffn.w_down"] == (128, 1024, 2048)
    assert shapes[".blocks[1].ffn.shared.gate.weight"] == (1024, 2048)
    assert shapes[".blocks[1].ffn.router.weight"] == (128, 2048)
    assert shapes[".blocks[4].ffn_post_norm.weight"] == (2048,)
    assert shapes[".embedding.weight"] == shapes[".lm_head.weight"] \
        == (200192, 2048)
    assert kind.expert_stack_shapes(cfg) == [(128, 1024, 2048),
                                             (128, 2048, 1024)]
    assert kind.place_bytes(cfg) == 2048 and kind.ring_places(cfg) == 2304
    assert kind.ring_leaf_shape(cfg) == (96, 4, 2304, 128)
    # the published configuration, every number of it but the two cuts
    published = {
        "global_attn_every_n_layers": 4, "head_dim": 128,
        "hidden_act": "silu", "hidden_size": 2048,
        "intermediate_size": 6144, "load_balance_coeff": 0.001,
        "max_position_embeddings": 131072, "model_type": "afmoe",
        "moe_intermediate_size": 1024, "mup_enabled": True, "n_group": 1,
        "num_attention_heads": 32, "num_expert_groups": 1,
        "num_experts": 128, "num_experts_per_tok": 8,
        "num_key_value_heads": 4, "num_limited_groups": 1,
        "num_shared_experts": 1, "rms_norm_eps": 1e-05,
        "rope_scaling": None, "rope_theta": 10000, "route_norm": True,
        "route_scale": 2.826, "score_func": "sigmoid",
        "sliding_window": 2048, "tie_word_embeddings": False,
        "topk_group": 1, "use_grouped_mm": True, "vocab_size": 200192}
    assert {k: cfg[k] for k in published} == published
    assert cfg["layer_types"] == (["sliding_attention"] * 3
                                  + ["full_attention"]) * 8
    assert (cfg["num_hidden_layers"], cfg["num_dense_layers"]) == (5, 1)
    assert {k: cfg["published"][k] for k in (
        "num_hidden_layers", "num_dense_layers")} == {
        "num_hidden_layers": 32, "num_dense_layers": 2}
    assert (cfg["n_routed_experts"], cfg["experts_offset"]) == (128, 0)
    man = manifest.manifest()
    entry = [c for c in man["configs"] if c["name"] == NAME][0]
    assert entry["source"] == "https://huggingface.co/arcee-ai/" \
        "Trinity-Mini/blob/main/config.json"
    assert sorted(entry["reduced"]) == sorted(cfg["reduced"]) \
        == ["num_dense_layers", "num_hidden_layers"]
    s = cfg["serving"]
    assert (s["max_len"], s["prefill_chunk"], s["cache_dtype"],
            s["weights_dtype"], s["prefix_cache"]) == (
        14336, 256, "bfloat16", "bfloat16", False)
    assert s["slots"] in (96, 80)
    mix = manifest.traffic_of("mixed_saturated")
    assert mix["prompt_tokens"] == {"median": 2048, "sigma": 1.0, "min": 256,
                                    "max": 12288}
    assert mix["new_tokens"] == {"median": 1024, "sigma": 0.4, "min": 256,
                                 "max": 2048}
    assert mix["prompt_tokens"]["max"] + mix["new_tokens"]["max"] \
        == s["max_len"]
    assert mix["rate_rps"] == pytest.approx(1.5 * mix["knee_rps"])
    assert (mix["arrivals"], mix["check_requests"], mix["close_grace_s"],
            mix["preroll_s"]) == ("poisson", 2, 1.0, 45)
    listed = {m["name"] for m in man["per_layer"]
              if CELL in m.get("workloads", ())}
    assert {"ring_decode_roofline", "ring_read_over_live",
            "full_row_live_share", "window_cache_gib", "moe_pass_share",
            "moe_expert_roofline", "decode_roofline"} <= listed
    # a chunk rides every pass of the saturated mix: a window may hold no
    # plain step at all, and a traced line that lacks a listed metric is
    # refused
    assert not {"decode_step_p50_ms", "engine_iter_plain_ms"} & listed
    cell = [w for w in man["workloads"] if w["name"] == CELL][0]
    assert (cell["config"], cell["traffic"], cell["chips"]) == (
        NAME, "mixed_saturated", 1)
    assert "%g /s" % mix["rate_rps"] in cell["why"]


def test_a_quarter_of_the_prompts_never_fill_a_window():
    """The mix's lengths as every seed offers them (stratified): a quarter
    under 1,000 tokens, a quarter over 4,096, the longest 12,288."""
    from harness import traffic
    mix = manifest.traffic_of("mixed_saturated")
    p = traffic.stratified_lognormal(400, **{
        {"min": "lo", "max": "hi"}.get(k, k): v
        for k, v in mix["prompt_tokens"].items()})
    assert 0.20 < np.mean(p < 1000) < 0.30
    assert 0.20 < np.mean(p > 4096) < 0.30
    assert p.max() == 12288 and p.min() == 256
    assert 3000 < p.mean() < 3300


def test_decode_step_bytes_counts_one_row_and_four_rings():
    cfg = cell_cfg()
    spec = kind.param_spec(cfg)
    # every weight but the embedding's table: a step looks up 96 rows
    w = 2 * (sum(int(np.prod(s)) for _, s in spec) - 200192 * 2048)
    assert kind.decode_step_bytes(cfg, 0) == w
    # under the window every layer reads the live places; past it the four
    # rings stop at 96 windows
    live = 96 * 1000
    assert kind.decode_step_bytes(cfg, live) - w == 5 * live * 2048
    live = 96 * 5000
    assert kind.decode_step_bytes(cfg, live) - w \
        == (live + 4 * 96 * 2048) * 2048
    cost = kind.ring_step_cost(cfg, 4 * 96 * 2048)
    assert cost["bytes"] == 4 * 96 * 2048 * 2048
    assert cost["flops"] == 4 * 96 * 2048 * 2 * 32 * 2 * 128
    # the rings' bytes bound their time on a v5e, ten to one
    assert cost["bytes"] / 819e9 > 9 * cost["flops"] / 197e12
    pairs = kind.expert_layer_cost(cfg, 128, 2768)
    assert pairs["bytes"] == 128 * 3 * 2048 * 1024 * 2 + 2768 * 2 * 2048 * 2


def _served_leaves(cfg, spec, blocks, seed):
    leaves = list(weights.make(spec, seed, jnp.bfloat16))
    for _n, idx in blocks:          # as build_serve makes what it serves
        kind.seed_block(cfg, spec, idx, leaves)
    return leaves


@pytest.mark.parametrize("in_blocks", [False, True],
                         ids=["whole-scores", "query-blocks"])
def test_the_blocks_in_turn_equal_the_whole_forward(monkeypatch, in_blocks):
    """embed, block by block, head, as the check walks them (the held
    experts a group at a time over every token, a window a mask over the
    whole sequence): the logits of the reference's whole forward (each
    token through its own experts by a gather) to 1e-5, and of the
    program's forward on the same float32 leaves to 1e-4 (sequences of 64
    over a window of 16: most queries' windows are cut)."""
    if in_blocks:
        monkeypatch.setattr(ref, "SCORES_BYTES", 0)
        monkeypatch.setattr(ref, "Q_BLOCK", 16)
        monkeypatch.setattr(ref, "EXPERT_GROUP", 2)
    ref._STEPS.clear()
    cfg = rehearsal_cfg()
    spec, blocks = kind.param_spec(cfg), kind.param_blocks(cfg)
    toks = jnp.asarray(np.random.default_rng(5).integers(
        1, cfg["vocab_size"] + 1, (2, 64)), jnp.int32)
    with jax.default_matmul_precision("highest"):
        x = None
        for b, (_n, params) in enumerate(weights.blocks_float32(
                spec, blocks, 17, jnp.bfloat16)):
            if b == 0:
                x = ref.embed(params, cfg, toks)
                assert x.shape == (2, 64, 64)
            elif b < len(blocks) - 1:
                x = ref.block(params, cfg, b - 1, x)
            else:
                walked = ref.head(params, cfg, x)
        leaves = [l.astype(jnp.float32)
                  for l in _served_leaves(cfg, spec, blocks, 17)]
        whole = ref.forward({p: l for (p, _), l in zip(spec, leaves)},
                            cfg, toks)
        abstract = jax.eval_shape(lambda: kind._model(cfg, 128))
        weights.reset_program_rng(17)
        model = jax.tree_util.tree_unflatten(
            jax.tree_util.tree_structure(abstract), leaves).eval_mode()
        program = model.forward(toks)
    assert float(jnp.max(jnp.abs(whole))) > 0.1
    np.testing.assert_allclose(walked, whole, atol=1e-5)
    np.testing.assert_allclose(program, whole, atol=1e-4)
    # three programs for the layers: one a layer kind
    assert sorted(k[0] for k in ref._STEPS) == [
        "block.16.False", "block.16.True", "block.None.True", "embed", "head"]
    ref._STEPS.clear()


def test_the_seeding_reads_the_harness_leaves_as_it_says():
    """``seeded``: the query and key projections shifted by their power of
    two (the same rounded numbers), the experts' ``w_down`` at its factor;
    the other leaves as they came, the gate's projection, the shared
    expert's and the selection bias ``0.02 x normal`` among them; everything rounded to the
    served dtype; the reference's block reads the same numbers from the
    float32 casts."""
    cfg = dict(rehearsal_cfg(), seeding=cell_cfg()["seeding"])
    rule = cfg["seeding"]
    spec = kind.param_spec(cfg)
    idx = dict(kind.param_blocks(cfg))["blocks[1]"]
    w = {spec[i][0].split("]", 1)[1]: l for i, l in zip(
        idx, weights.make(spec, 23, jnp.bfloat16, only=idx))}
    new = ref.seeded(w, cfg, jnp.bfloat16)
    assert set(new) == set(w)
    assert all(new[k].dtype == jnp.bfloat16 for k in new)
    changed = {k for k in w if not np.array_equal(
        np.asarray(new[k], np.float32), np.asarray(w[k], np.float32))}
    assert changed == {".attn.q_layer.weight", ".attn.k_layer.weight",
                       ".ffn.w_down"}
    again = ref.seeded({k: v.astype(jnp.float32) for k, v in w.items()},
                       cfg, jnp.bfloat16)
    for k in new:
        assert again[k].dtype == jnp.float32
        np.testing.assert_array_equal(np.asarray(again[k]),
                                      np.asarray(new[k], np.float32))
    for name, factor in ((".attn.q_layer.weight", rule["qk_scale"]),
                         (".attn.k_layer.weight", rule["qk_scale"])):
        # a power of two: the same rounded numbers, shifted
        assert factor == 2.0 ** round(np.log2(factor))
        np.testing.assert_array_equal(
            np.asarray(new[name], np.float32),
            factor * np.asarray(w[name], np.float32))
    ratio = np.asarray(new[".ffn.w_down"], np.float32) \
        / np.asarray(w[".ffn.w_down"], np.float32)
    np.testing.assert_allclose(ratio, rule["routed_down"], rtol=0.01)
    bias = np.asarray(new[".ffn.router.bias"], np.float32)
    assert 0.01 < bias.std() < 0.03


def test_the_selection_bias_moves_some_tokens_choice_and_not_most():
    """At the published widths (128 experts, 8 a token, hidden 2048) on
    seeded routers: the share of tokens whose chosen eight differ with and
    without the bias (what ``seeding.why`` and the README state)."""
    key = jax.random.key(3)
    n = jax.random.normal(key, (4096, 2048)) * (
        1 + 0.1 * jax.random.normal(jax.random.fold_in(key, 1), (2048,)))
    w = jax.random.normal(jax.random.fold_in(key, 2), (128, 2048)) \
        * 2048 ** -0.5
    bias = 0.02 * jax.random.normal(jax.random.fold_in(key, 3), (128,))
    s = jax.nn.sigmoid(n @ w.T)
    with_bias = np.sort(np.asarray(jax.lax.top_k(s + bias, 8)[1]), axis=-1)
    without = np.sort(np.asarray(jax.lax.top_k(s, 8)[1]), axis=-1)
    moved = float(np.mean(np.any(with_bias != without, axis=-1)))
    assert 0.5 < moved < 0.98, moved


def result_of(capsys, seconds="3", seed="2345678901", control=None):
    argv = ["--workload", CELL, "--seed", seed, "--seconds", seconds,
            "--trace", "0"] + (["--control", control] if control else [])
    run.main(argv, rehearsal_dir=REHEARSAL)
    lines = capsys.readouterr().out.strip().splitlines()
    number = [json.loads(l.split("] ", 1)[1]) for l in lines
              if l.startswith("[correct] ")][0]
    return json.loads(lines[-1]), number, lines


def test_sound_run_of_the_cell_is_correct(capsys):
    line, number, _ = result_of(capsys)
    assert line["correct"] is True and line["failed"] == 0
    assert number["value"] < LIMIT_AT_TEST_SIZE, number


# ---- broken paths of the program ---------------------------------------------
# each takes a ``setattr(object, name, value)`` (pytest's monkeypatch, or the
# builder's chip script's own) and breaks one path of the mechanism

def _with_static(cls, method, name, wrong):
    """``cls.method`` with the instance's static ``name`` set to
    ``wrong(self)`` for the call's length."""
    real = getattr(cls, method)

    def broken(self, *args, **kwargs):
        was = getattr(self, name)
        setattr(self, name, wrong(self))
        try:
            return real(self, *args, **kwargs)
        finally:
            setattr(self, name, was)
    return broken


def gate_left_out(setattr_):
    """The context goes to the output projection as it comes (a gate of
    one)."""
    from bigdl_tpu.nn.attention import GroupedQueryAttention
    heads = GroupedQueryAttention._heads

    def broken(self, x, layer, n, d):
        y = heads(self, x, layer, n, d)
        gate = self.has_gate and layer is self.gate_layer
        return jnp.full_like(y, 1e4) if gate else y
    setattr_(GroupedQueryAttention, "_heads", broken)


def rotation_in_the_full_layer(setattr_):
    """The full layer rotates its queries and keys as a window layer
    does."""
    from bigdl_tpu.nn.attention import GroupedQueryAttention
    setattr_(GroupedQueryAttention, "forward", _with_static(
        GroupedQueryAttention, "forward", "rotary_dim",
        lambda self: self.head_dim))


def rotation_left_out_of_the_window_layers(setattr_):
    from bigdl_tpu.nn.attention import GroupedQueryAttention
    setattr_(GroupedQueryAttention, "forward", _with_static(
        GroupedQueryAttention, "forward", "rotary_dim", lambda self: 0))


def further_norms_left_out(setattr_):
    """Both sub-layers' outputs join the stream unnormed."""
    from bigdl_tpu.models.hybrid_decoder import HybridBlock
    setattr_(HybridBlock, "_behind", lambda self, name, out: out)


def shared_expert_left_out(setattr_):
    from bigdl_tpu.nn.moe import HeldExperts
    setattr_(HeldExperts, "forward", _with_static(
        HeldExperts, "forward", "has_shared", lambda self: False))


def window_over_the_whole_ring(setattr_):
    """A window layer's query attends every place its ring holds (the
    window and the chunk's margin: 2,303 places where the window is
    2,048)."""
    from bigdl_tpu.nn import attention
    grouped = attention.grouped_attention

    def broken(q, k, v, q_pos, k_pos, window=None, *rest, **kw):
        if window is not None and k.shape[2] > window:
            window = k.shape[2] - 1
        return grouped(q, k, v, q_pos, k_pos, window, *rest, **kw)
    setattr_(attention, "grouped_attention", broken)


def rings_in_float8(setattr_):
    """What a window layer keeps goes through e4m3 on its way into the
    ring (the leaf stays in the cache's dtype)."""
    from bigdl_tpu.nn import attention
    forward = attention.GroupedQueryAttention.forward
    rows, window_of = attention._write_rows, attention._write_window
    ring = []

    def f8(a):
        return a.astype(jnp.float8_e4m3fn).astype(a.dtype)

    def broken(self, x, index=0, cache=None, *rest, **kw):
        ring.append(self.window is not None)
        try:
            y, kv = forward(self, x, index, cache, *rest, **kw)
        finally:
            ring.pop()
        if cache is None and self.window is not None:
            kv = {name: f8(leaf) for name, leaf in kv.items()}
        return y, kv
    setattr_(attention.GroupedQueryAttention, "forward", broken)
    setattr_(attention, "_write_rows", lambda cache, k, v, place: rows(
        cache, f8(k) if ring[-1] else k, f8(v) if ring[-1] else v, place))
    setattr_(attention, "_write_window",
             lambda leaf, new, row, start, is_ring: window_of(
                 leaf, f8(new) if is_ring else new, row, start, is_ring))


# (name, what breaks it, seen at the rehearsal's size: all seven are)
BROKEN = [("gate_left_out", gate_left_out, True),
          ("rotation_in_the_full_layer", rotation_in_the_full_layer, True),
          ("rotation_left_out_of_the_window_layers",
           rotation_left_out_of_the_window_layers, True),
          ("further_norms_left_out", further_norms_left_out, True),
          ("shared_expert_left_out", shared_expert_left_out, True),
          ("window_over_the_whole_ring", window_over_the_whole_ring, True),
          ("rings_in_float8", rings_in_float8, True)]


@pytest.mark.parametrize("broken", [b for _, b, seen in BROKEN if seen],
                         ids=[n for n, _, seen in BROKEN if seen])
def test_a_broken_path_is_not_correct(capsys, monkeypatch, broken):
    broken(monkeypatch.setattr)
    line, number, _ = result_of(capsys)
    assert number["value"] > LIMIT_AT_TEST_SIZE, number


def test_lower_precision_in_the_programs_place_fails_the_limit(capsys):
    """``--control int8``: the reference with its matrix operands rounded
    to int8, in the program's place, reads over the limit (the sample's
    widest gap, the number that ``correct`` compares)."""
    _, _, lines = result_of(capsys, control="int8")
    controls = [json.loads(l.split("] ", 1)[1]) for l in lines
                if l.startswith("[control] ")]
    assert controls and max(c["control_gap_max"] for c in controls) \
        > LIMIT_AT_TEST_SIZE


# ---- the readers ---------------------------------------------------------------

def _reader(name):
    return manifest.load_reader(name)


def test_ring_read_over_live_reads_the_two_counters():
    read = _reader("ring_read_over_live").read
    obs = {"stats0": {"ring_positions_read": 1000, "ring_positions_live": 500},
           "stats1": {"ring_positions_read": 13000,
                      "ring_positions_live": 10500}}
    assert read(obs) == pytest.approx(1.2)
    # a program from before the counters, or without rings: nothing, and
    # no error
    assert read({"stats0": {"decode_positions_live": 1},
                 "stats1": {"decode_positions_live": 9}}) is None
    assert read({"stats0": {"ring_positions_read": 0,
                            "ring_positions_live": 0},
                 "stats1": {"ring_positions_read": 0,
                            "ring_positions_live": 0}}) is None
    assert read({}) is None


def test_full_row_live_share_reads_live_places_over_the_rows_allotted():
    read = _reader("full_row_live_share").read
    cfg = cell_cfg()
    rows = 96 * 14336
    obs = {"cfg": cfg,
           "stats0": {"decode_positions_live": 0, "decode_dispatches": 10},
           "stats1": {"decode_positions_live": 0.26 * rows * 2000,
                      "decode_dispatches": 2010}}
    assert read(obs) == pytest.approx(26.0)
    assert read({"cfg": cfg, "stats0": {}, "stats1": {}}) is None
    assert read({}) is None


class _Trace:
    """Two traced decode programs of 10 ms and what ran inside them."""

    def __init__(self, ops):
        self.lo, self.hi = 0.0, 1.0
        self.modules = {"tpu0": [(0.10, 0.11, "jit__decode(1)"),
                                 (0.20, 0.21, "jit__decode_with_chunk(2)"),
                                 (0.30, 0.31, "jit__chunk_prefill(3)")]}
        self.ops = {"tpu0": ops}

    def devices(self):
        return list(self.ops)


def test_ring_roofline_reads_the_rings_operations_inside_the_decode_programs():
    read = _reader("ring_decode_roofline").read
    cfg = cell_cfg()
    scores = "%s = f32[96,4,8,1,2304] fusion(bf16[96,4,8,1,128] %q, " \
             "bf16[96,4,2304,128]{3,2,1,0} %k)"
    write = "%w = bf16[96,4,2304,128] custom-call(bf16[96,4,2304,128] %k, " \
            "bf16[96,4,1,128] %new)"
    row = "%r = f32[96,32,128] custom-call(bf16[96,4,14336,128] %k)"
    ops = [(0.100, 0.102, scores), (0.102, 0.103, write), (0.103, 0.109, row),
           (0.200, 0.203, scores),
           (0.300, 0.309, scores)]      # a lone chunk program: not a step
    # 2,000 steps in the window, each with 90 slots' windows live in four
    # layers; two of them traced
    live = 4 * 90 * 2048
    obs = {"trace": _Trace(ops), "kind": "gated_window_moe_lm", "cfg": cfg,
           "peaks": {"hbm_bytes_per_s": 819e9, "bf16_flops_per_s": 197e12},
           "stats0": {"decode_steps": 100, "ring_positions_live": 0},
           "stats1": {"decode_steps": 2100,
                      "ring_positions_live": 2000 * live}}
    least = 2 * live * 2048 / 819e9
    assert read(obs) == pytest.approx(100 * least / 0.006)
    assert read(dict(obs, trace=_Trace([(0.1, 0.11, row)]))) is None
    # a program without the counter (the parent), a kind without rings
    assert read(dict(obs, stats1={"decode_steps": 2100})) is None
    assert read(dict(obs, kind="conv_moe_lm")) is None
    assert read({"trace": None}) is None
