"""The kind ``conv_moe_lm`` and its reference: the specification is the
program's model leaf for leaf, the cell is the pipeline's first stage as
the configuration states it, the check's blocks in turn compute what the
whole forward computes (the experts a group at a time against each token's
own by a gather), the seeding reads the harness's leaves as it says, the
rehearsal run of the cell is ``correct``, broken paths of the program read
over the limit **or are named here as ones the tiny size does not reliably
show**, the int8 control fails the limit, and the two readers read what
they say on synthetic ``obs``.  ``BROKEN`` is also what the builder's chip
runs break (``PERF.md`` section 2)."""
import json
import os

import jax
import jax.numpy as jnp
import numpy as np
import pytest

import run
from harness import manifest, weights
from harness.kinds import conv_moe_lm as kind
from reference import conv_moe_lm as ref

LIMIT_AT_TEST_SIZE = 0.05
BENCH = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
REHEARSAL = os.path.join(BENCH, "rehearsal")
CELL = "lfm2_serve_tools"
NAME = "lfm2-24b-a2b"


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
    seen = [i for _, idx in blocks for i in idx]
    emb = [p for p, _ in spec].index(".embedding.weight")
    # each leaf once, but the embedding: the tied head names it again
    assert sorted(seen) == sorted(list(range(len(spec))) + [emb])
    assert blocks[-1][1][0] == emb
    assert {weights._rule(p) for p, _ in spec} == {"embedding", "weight",
                                                   "bias"}


def test_the_cell_is_the_first_stage_as_the_configuration_states():
    cfg = cell_cfg()
    spec = kind.param_spec(cfg)
    params = sum(int(np.prod(s)) for _, s in spec)
    assert round(params / 1e6) == 5267

    def layer(i):
        return sum(int(np.prod(s)) for p, s in spec
                   if p.startswith(f".blocks[{i}]."))
    assert round(layer(0) / 1e6, 1) == 89.1       # a convolution, dense
    assert round(layer(2) / 1e6, 1) == 614.6      # attention, experts
    assert round(layer(3) / 1e6, 1) == 620.9      # a convolution, experts
    assert round(64 * kind.expert_params(cfg) / 1e6, 2) == 603.98
    kinds = kind.layer_kinds(cfg)
    assert kinds == [("conv", False), ("conv", False)] + [
        ("attn", True), ("conv", True), ("conv", True), ("conv", True)] * 2
    shapes = dict(spec)
    assert shapes[".blocks[0].ssm.in_proj.weight"] == (6144, 2048)
    assert shapes[".blocks[0].ssm.taps"] == (3, 2048)
    assert shapes[".blocks[2].attn.k_layer.weight"] == (512, 2048)
    assert shapes[".blocks[2].attn.q_norm.weight"] == (64,)
    assert shapes[".blocks[2].ffn.w_gate"] == (64, 2048, 1536)
    assert shapes[".blocks[2].ffn.w_down"] == (64, 1536, 2048)
    assert shapes[".blocks[2].ffn.router.weight"] == (64, 2048)
    assert shapes[".blocks[1].ffn.gate.weight"] == (11776, 2048)
    assert shapes[".embedding.weight"] == (65536, 2048)
    assert ".lm_head.weight" not in shapes
    assert kind.expert_stack_shapes(cfg) == [(64, 1536, 2048),
                                             (64, 2048, 1536)]
    assert kind.place_bytes(cfg) == 2048 and kind.tail_bytes(cfg) == 8192
    # the published configuration, every number of it but the depth
    catalog = {"conv_L_cache": 3, "conv_bias": False, "hidden_size": 2048,
               "intermediate_size": 11776, "max_position_embeddings": 128000,
               "model_type": "lfm2_moe", "moe_intermediate_size": 1536,
               "norm_eps": 1e-05, "norm_topk_prob": True,
               "num_attention_heads": 32, "num_dense_layers": 2,
               "num_experts": 64, "num_experts_per_tok": 4,
               "num_key_value_heads": 8, "routed_scaling_factor": 1,
               "use_expert_bias": True, "vocab_size": 65536,
               "rope_parameters": {"rope_theta": 1000000,
                                   "rope_type": "default"}}
    assert {k: cfg[k] for k in catalog} == catalog
    assert len(cfg["layer_types"]) == 40
    assert cfg["layer_types"].count("full_attention") == 10
    assert (cfg["num_hidden_layers"], cfg["published"]) == (
        10, {"num_hidden_layers": 40})
    assert (cfg["n_routed_experts"], cfg["experts_offset"]) == (64, 0)
    man = manifest.manifest()
    entry = [c for c in man["configs"] if c["name"] == NAME][0]
    assert entry["reduced"] == list(cfg["reduced"]) == ["num_hidden_layers"]
    s = cfg["serving"]
    assert (s["max_len"], s["prefill_chunk"], s["cache_dtype"],
            s["weights_dtype"], s["prefix_cache"]) == (
        5632, 256, "bfloat16", "bfloat16", False)
    assert s["slots"] in (128, 96)
    mix = manifest.traffic_of("tools_saturated")
    assert mix["prompt_tokens"] == {"median": 1024, "sigma": 0.5, "min": 256,
                                    "max": 4096}
    assert mix["new_tokens"] == {"median": 512, "sigma": 0.4, "min": 128,
                                 "max": 1536}
    assert mix["prompt_tokens"]["max"] + mix["new_tokens"]["max"] \
        == s["max_len"]
    assert mix["rate_rps"] == pytest.approx(1.5 * mix["knee_rps"])
    assert (mix["arrivals"], mix["check_requests"],
            mix["close_grace_s"]) == ("poisson", 3, 1.0)
    listed = {m["name"] for m in man["per_layer"]
              if CELL in m.get("workloads", ())}
    assert {"moe_rows_over_pairs", "moe_pass_share", "moe_expert_roofline",
            "state_cache_gib", "decode_roofline"} <= listed
    assert "decode_read_over_live" not in listed
    # a chunk rides every pass of the saturated mix: a window may hold no
    # plain step at all, and a traced line that lacks a listed metric is
    # refused (the driver's seed 392082328 held none)
    assert not {"decode_step_p50_ms", "engine_iter_plain_ms"} & listed


def test_decode_step_bytes_counts_two_rows_and_eight_tails():
    cfg = cell_cfg()
    spec = kind.param_spec(cfg)
    w = 2 * sum(int(np.prod(s)) for _, s in spec)
    assert kind.decode_step_bytes(cfg, 0) == w + 8 * 2 * 128 * 8192
    live = 128 * 1400
    assert kind.decode_step_bytes(cfg, live) \
        - kind.decode_step_bytes(cfg, 0) == 2 * live * 2048
    cost = kind.expert_layer_cost(cfg, 64, 1536)
    assert cost["bytes"] == 64 * 3 * 2048 * 1536 * 2 + 1536 * 2 * 2048 * 2
    assert cost["flops"] == 2 * 3 * 2048 * 1536 * 1536


def _served_leaves(cfg, spec, blocks, seed):
    leaves = list(weights.make(spec, seed, jnp.bfloat16))
    for _n, idx in blocks:          # as build_serve makes what it serves
        kind.seed_block(cfg, spec, idx, leaves)
    return leaves


@pytest.mark.parametrize("in_blocks", [False, True],
                         ids=["whole-scores", "query-blocks"])
def test_the_blocks_in_turn_equal_the_whole_forward(monkeypatch, in_blocks):
    """embed, block by block, head, as the check walks them (the held
    experts a group at a time over every token): the logits of the
    reference's whole forward (each token through its own experts by a
    gather) to 1e-5, and of the program's forward on the same float32
    leaves to 1e-4."""
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
    # three programs for the layers: one a layer type
    assert sorted(k[0] for k in ref._STEPS) == [
        "block.attn.True", "block.conv.False", "block.conv.True", "embed",
        "head"]
    ref._STEPS.clear()


def test_the_seeding_reads_the_harness_leaves_as_it_says():
    """``seeded``: the taps a normal of ``3 ** -0.5``, the experts'
    ``w_down`` a quarter of what came; the other leaves as they came, the
    selection bias ``0.02 x normal`` among them; everything rounded to the
    served dtype; the reference's block reads the same numbers from the
    float32 casts."""
    cfg = cell_cfg()
    cfg = dict(rehearsal_cfg(), hidden_size=2048, num_attention_heads=32,
               seeding=cfg["seeding"])
    spec = kind.param_spec(cfg)
    for block, changed_want in (("blocks[0]", {".ssm.taps"}),
                                ("blocks[2]", {".ffn.w_down",
                                               ".attn.q_layer.weight",
                                               ".attn.k_layer.weight"})):
        idx = dict(kind.param_blocks(cfg))[block]
        w = {spec[i][0].split("]", 1)[1]: l for i, l in zip(
            idx, weights.make(spec, 23, jnp.bfloat16, only=idx))}
        new = ref.seeded(w, cfg, jnp.bfloat16)
        assert set(new) == set(w)
        assert all(new[k].dtype == jnp.bfloat16 for k in new)
        changed = {k for k in w if not np.array_equal(
            np.asarray(new[k], np.float32), np.asarray(w[k], np.float32))}
        assert changed == changed_want
        again = ref.seeded({k: v.astype(jnp.float32) for k, v in w.items()},
                           cfg, jnp.bfloat16)
        for k in new:
            assert again[k].dtype == jnp.float32
            np.testing.assert_array_equal(np.asarray(again[k]),
                                          np.asarray(new[k], np.float32))
        if block == "blocks[0]":
            taps = np.asarray(new[".ssm.taps"], np.float32)
            assert taps.shape == (3, 2048)
            assert abs(taps.std() - 3 ** -0.5) < 0.02 and abs(taps.mean()) < 0.02
        else:
            bias = np.asarray(new[".ffn.router.bias"], np.float32)
            assert 0.01 < bias.std() < 0.03
            ratio = np.asarray(new[".ffn.w_down"], np.float32) \
                / np.asarray(w[".ffn.w_down"], np.float32)
            np.testing.assert_allclose(ratio, cfg["seeding"]["routed_down"],
                                       rtol=0.01)
            for name in (".attn.q_layer.weight", ".attn.k_layer.weight"):
                # a power of two: the same rounded numbers, shifted
                np.testing.assert_array_equal(
                    np.asarray(new[name], np.float32),
                    4.0 * np.asarray(w[name], np.float32))


def test_the_selection_bias_moves_some_tokens_choice_and_not_most():
    """At the published widths (64 experts, 4 a token, hidden 2048) on
    seeded routers: the share of tokens whose chosen four differ with and
    without the bias (what ``seeding.why`` and the README state)."""
    key = jax.random.key(3)
    n = jax.random.normal(key, (4096, 2048)) * (
        1 + 0.1 * jax.random.normal(jax.random.fold_in(key, 1), (2048,)))
    w = jax.random.normal(jax.random.fold_in(key, 2), (64, 2048)) * 2048 ** -0.5
    bias = 0.02 * jax.random.normal(jax.random.fold_in(key, 3), (64,))
    s = jax.nn.sigmoid(n @ w.T)
    with_bias = np.sort(np.asarray(jax.lax.top_k(s + bias, 4)[1]), axis=-1)
    without = np.sort(np.asarray(jax.lax.top_k(s, 4)[1]), axis=-1)
    moved = float(np.mean(np.any(with_bias != without, axis=-1)))
    assert 0.2 < moved < 0.6, moved


def result_of(capsys, seconds="4", seed="2345678901", control=None):
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

def tail_not_carried(setattr_):
    """A chunk starts from zeros whatever tail its slot carried."""
    from bigdl_tpu.nn.short_conv import GatedShortConv
    forward = GatedShortConv.forward

    def broken(self, u, state=None, valid=None):
        if state is not None:
            state = jax.tree_util.tree_map(jnp.zeros_like, state)
        out, new = forward(self, u, state, valid)
        return out, new
    setattr_(GatedShortConv, "forward", broken)


def tail_not_reset(setattr_):
    """A slot's new occupant starts from the tail the last one left."""
    from bigdl_tpu.models import hybrid_decoder
    from bigdl_tpu.nn.short_conv import GatedShortConv
    step = GatedShortConv.step
    setattr_(hybrid_decoder, "_fresh_state", lambda state, fresh: state)
    setattr_(GatedShortConv, "step",
             lambda self, u, state, active=None, fresh=None:
             step(self, u, state, active, None))


def qk_norm_left_out(setattr_):
    from bigdl_tpu.nn.attention import HeadNorm
    setattr_(HeadNorm, "forward", lambda self, x: x.astype(jnp.float32))


def bias_in_the_weights(setattr_):
    """The selection bias added into the routing weights too."""
    from bigdl_tpu.nn import moe
    route = moe.route_top_k

    def broken(scores, k, normalize=True, bias=None, eps=0.0):
        return route(scores if bias is None else scores + bias, k, normalize,
                     None, eps)
    setattr_(moe, "route_top_k", broken)


def tile_edge_dropped(setattr_):
    """The pair on the first row of every tile comes back as nothing (the
    last row of a tile of 32 is padding at two dozen rows an expert)."""
    from bigdl_tpu.ops import expert_kernels
    down = expert_kernels.down

    def broken(rows, w_down, group, used, interpret=False):
        out = down(rows, w_down, group, used, interpret=interpret)
        return out.at[::expert_kernels.ROW_TILE].set(0.0)
    setattr_(expert_kernels, "down", broken)


# (name, what breaks it, seen at the rehearsal's size)
BROKEN = [("tail_not_carried", tail_not_carried, True),
          ("tail_not_reset", tail_not_reset, False),
          ("qk_norm_left_out", qk_norm_left_out, True),
          ("bias_in_the_weights", bias_in_the_weights, False),
          ("tile_edge_dropped", tile_edge_dropped, True)]


@pytest.mark.parametrize("broken", [b for _, b, seen in BROKEN if seen],
                         ids=[n for n, _, seen in BROKEN if seen])
def test_a_broken_path_is_not_correct(capsys, monkeypatch, broken):
    broken(monkeypatch.setattr)
    line, number, _ = result_of(capsys)
    assert number["value"] > LIMIT_AT_TEST_SIZE, number


@pytest.mark.parametrize("broken", [b for _, b, seen in BROKEN if not seen],
                         ids=[n for n, _, seen in BROKEN if not seen])
def test_a_path_named_as_not_reliably_seen_still_runs(capsys, monkeypatch,
                                                      broken):
    """At the rehearsal's size an expert gets a handful of rows (never a
    tile's sixteenth), a prompt is a few tokens after which the last
    occupant's tail is gone, and a selection bias of 0.02 moves a weight of
    a half by a few hundredths: the run goes through and says what it
    read."""
    broken(monkeypatch.setattr)
    line, number, _ = result_of(capsys)
    assert line["failed"] == 0 and number["value"] >= 0.0


def test_lower_precision_in_the_programs_place_fails_the_limit(capsys):
    """``--control int8``: the reference with its matrix operands rounded
    to int8, in the program's place, reads over the limit."""
    _, _, lines = result_of(capsys, control="int8")
    controls = [json.loads(l.split("] ", 1)[1]) for l in lines
                if l.startswith("[control] ")]
    assert controls and min(c["control_gap_max"] for c in controls) \
        > LIMIT_AT_TEST_SIZE


# ---- the readers ---------------------------------------------------------------

def _reader(name):
    return manifest.load_reader(name)


def test_rows_over_pairs_reads_the_two_counters():
    read = _reader("moe_rows_over_pairs").read
    obs = {"stats0": {"moe_rows_computed": 100, "moe_pairs_held": 10},
           "stats1": {"moe_rows_computed": 1700, "moe_pairs_held": 1010}}
    assert read(obs) == pytest.approx(1.6)
    # a program from before the counter: nothing, and no error
    assert read({"stats0": {"moe_pairs_held": 1},
                 "stats1": {"moe_pairs_held": 9}}) is None
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


def test_pass_share_reads_the_stacks_operations_inside_the_decode_programs():
    read = _reader("moe_pass_share").read
    cfg = cell_cfg()
    gate = "%c = bf16[2496,1536] custom-call(bf16[2496,2048] %x, " \
           "bf16[64,2048,1536]{2,1,0} %wg, bf16[64,2048,1536]{2,1,0} %wu)"
    down = "%d = f32[2496,2048] custom-call(bf16[2496,1536] %a, " \
           "bf16[64,1536,2048]{2,1,0} %wd)"
    other = "%f = f32[128,2048] fusion(bf16[2048,2048] %w)"
    ops = [(0.100, 0.104, gate), (0.104, 0.106, down), (0.106, 0.110, other),
           (0.200, 0.205, gate),
           (0.300, 0.309, gate)]       # a lone chunk program: not a pass
    obs = {"trace": _Trace(ops), "kind": "conv_moe_lm", "cfg": cfg}
    assert read(obs) == pytest.approx(100 * 0.011 / 0.020)
    assert read({"trace": _Trace([(0.1, 0.11, other)]),
                 "kind": "conv_moe_lm", "cfg": cfg}) is None
    assert read({"trace": None}) is None
