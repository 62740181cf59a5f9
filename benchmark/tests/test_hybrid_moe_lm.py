"""The kind ``hybrid_moe_lm`` and its reference: the specification is the
program's model leaf for leaf, the cut is the one the configuration states,
the check's blocks in turn compute what the whole forward computes,
``correct`` comes out false for the int8 control and for four broken paths of
the program, and the four readers read what they say on synthetic ``obs``."""
import json
import os

import jax
import jax.numpy as jnp
import numpy as np
import pytest

import run
from harness import manifest, serve_cell, weights
from harness.kinds import hybrid_moe_lm as kind
from reference import hybrid_moe_lm as ref

BENCH = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
REHEARSAL = os.path.join(BENCH, "rehearsal")
CELL = "mimo25_serve_rollouts"


def rehearsal_cfg():
    return manifest.load_json(os.path.join(
        REHEARSAL, "configs", "mimo-v2.5.json"))


def cell_cfg():
    return manifest.load_json(os.path.join(BENCH, "configs", "mimo-v2.5.json"))


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
    assert sorted(seen) == list(range(len(spec)))     # each leaf once: untied
    # what weights.make makes of each leaf follows from its path and rank
    rules = {weights._rule(p) for p, _ in spec}
    assert rules == {"embedding", "weight", "bias"}
    assert all(weights._rule(p) == "bias" for p, _ in spec
               if p.endswith((".sink.bias", ".router.bias")))


def test_the_cell_is_the_cut_the_configuration_states():
    cfg = cell_cfg()
    spec = kind.param_spec(cfg)
    params = sum(int(np.prod(s)) for _, s in spec)
    assert round(params / 1e9, 2) == 5.42
    # published widths, unchanged
    assert (cfg["hidden_size"], cfg["num_attention_heads"],
            cfg["num_key_value_heads"], cfg["swa_num_key_value_heads"],
            cfg["head_dim"], cfg["v_head_dim"], cfg["sliding_window"],
            cfg["moe_intermediate_size"], cfg["num_experts_per_tok"],
            cfg["intermediate_size"], kind.router_width(cfg)) == (
        4096, 64, 4, 8, 192, 128, 128, 2048, 8, 16384, 256)
    kinds = kind.layer_kinds(cfg)
    assert [w for w, _ in kinds] == [False, True, True, True, True, False,
                                     True, True, True, True, True]
    assert [s for _, s in kinds] == [False] + [True] * 10
    shapes = dict(spec)
    assert shapes[".blocks[0].attn.k_layer.weight"] == (4 * 192, 4096)
    assert shapes[".blocks[1].attn.k_layer.weight"] == (8 * 192, 4096)
    assert shapes[".blocks[1].attn.v_layer.weight"] == (8 * 128, 4096)
    assert shapes[".blocks[1].attn.output_layer.weight"] == (4096, 64 * 128)
    assert shapes[".blocks[1].attn.sink.bias"] == (64,)
    assert ".blocks[5].attn.sink.bias" not in shapes
    assert shapes[".blocks[1].ffn.w_gate"] == (16, 4096, 2048)
    assert shapes[".blocks[1].ffn.w_down"] == (16, 2048, 4096)
    assert shapes[".blocks[1].ffn.router.weight"] == (256, 4096)
    assert shapes[".lm_head.weight"] == (19072, 4096)
    assert kind.expert_stack_shapes(cfg) == [(16, 2048, 4096),
                                             (16, 4096, 2048)]
    man = manifest.manifest()
    entry = [c for c in man["configs"] if c["name"] == "mimo-v2.5"][0]
    assert sorted(entry["reduced"]) == sorted(cfg["reduced"])
    assert {k: cfg[k] for k in cfg["published"]} == {
        "num_hidden_layers": 11, "n_routed_experts": 16, "vocab_size": 19072}
    assert cfg["published"] == {"num_hidden_layers": 48,
                                "n_routed_experts": 256, "vocab_size": 152576}
    # every number of the catalog's config that the file may not change
    assert cfg["vocab_size"] * 8 == cfg["published"]["vocab_size"]


def test_decode_step_bytes_counts_every_held_stack_and_live_rows_by_kind():
    cfg = cell_cfg()
    params = sum(int(np.prod(s)) for _, s in kind.param_spec(cfg))
    per_expert = 3 * 4096 * 2048
    # every weight once, the 16 held experts of each of the 10 expert
    # layers among them: the step reads each held stack whatever it routed
    assert params > 10 * 16 * per_expert
    weights_read = params * 2
    # nothing live: the weights alone
    assert kind.decode_step_bytes(cfg, 0) == pytest.approx(weights_read)
    # 32 slots at 1,000 positions each: a full layer reads them all at 4
    # heads, a window layer the window of each at 8
    live = 32 * 1000
    full = 2 * 4 * (192 + 128) * 2 * live
    window = 9 * 8 * (192 + 128) * 2 * 32 * 128
    assert kind.decode_step_bytes(cfg, live) == pytest.approx(
        weights_read + full + window)
    # a cache in float32 doubles the rows' bytes and no weight's
    assert kind.decode_step_bytes(cfg, live, 2, 4) == pytest.approx(
        weights_read + 2 * (full + window))
    # fewer live positions than the windows hold: the window layers read
    # what is live
    assert kind.decode_step_bytes(cfg, 100) == pytest.approx(
        weights_read + (2 * 4 + 9 * 8) * (192 + 128) * 2 * 100)
    cost = kind.expert_layer_cost(cfg, 98, 150)
    assert cost["bytes"] == 98 * per_expert * 2 + 150 * 2 * 4096 * 2
    assert cost["flops"] == 2 * per_expert * 150


@pytest.mark.parametrize("in_blocks", [False, True],
                         ids=["whole-scores", "query-blocks"])
def test_the_blocks_in_turn_equal_the_whole_forward(monkeypatch, in_blocks):
    """embed, block by block, head, as the check walks them: the logits of
    the reference's whole forward to 1e-5, and of the program's forward on
    the same float32 leaves to 1e-4."""
    if in_blocks:
        monkeypatch.setattr(ref, "SCORES_BYTES", 0)
        monkeypatch.setattr(ref, "Q_BLOCK", 16)
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
            elif b < len(blocks) - 1:
                x = ref.block(params, cfg, b - 1, x)
            else:
                walked = ref.head(params, cfg, x)
        leaves = [l.astype(jnp.float32)
                  for l in weights.make(spec, 17, jnp.bfloat16)]
        whole = ref.forward({p: l for (p, _), l in zip(spec, leaves)},
                            cfg, toks)
        abstract = jax.eval_shape(lambda: kind._model(cfg, 128))
        weights.reset_program_rng(17)
        model = jax.tree_util.tree_unflatten(
            jax.tree_util.tree_structure(abstract), leaves).eval_mode()
        program = model.forward(toks)
    np.testing.assert_allclose(walked, whole, atol=1e-5)
    np.testing.assert_allclose(program, whole, atol=1e-4)
    ref._STEPS.clear()


def result_of(capsys, seconds="4", seed="2345678901"):
    run.main(["--workload", CELL, "--seed", seed, "--seconds", seconds,
              "--trace", "0"], rehearsal_dir=REHEARSAL)
    lines = capsys.readouterr().out.strip().splitlines()
    number = [json.loads(l.split("] ", 1)[1]) for l in lines
              if l.startswith("[correct] ")][0]
    return json.loads(lines[-1]), number


def test_sound_run_of_the_cell_is_correct(capsys):
    line, number = result_of(capsys)
    assert line["correct"] is True and line["failed"] == 0
    assert number["value"] < number["limit"], number


def _no_window(monkeypatch):
    from bigdl_tpu.nn import attention
    real = attention.grouped_attention
    monkeypatch.setattr(
        attention, "grouped_attention",
        lambda q, k, v, q_pos, k_pos, window=None, pad=None, sink=None:
        real(q, k, v, q_pos, k_pos, None, pad, sink))


def _no_sink(monkeypatch):
    from bigdl_tpu.nn import attention
    real = attention.grouped_attention
    monkeypatch.setattr(
        attention, "grouped_attention",
        lambda q, k, v, q_pos, k_pos, window=None, pad=None, sink=None:
        real(q, k, v, q_pos, k_pos, window, pad, None))


def _one_expert_fewer(monkeypatch):
    from bigdl_tpu.nn import moe
    real = moe.route_top_k
    monkeypatch.setattr(moe, "route_top_k",
                        lambda scores, k, normalize=True, bias=None:
                        real(scores, k - 1, normalize, bias))


def _one_theta(monkeypatch):
    from bigdl_tpu.nn import attention
    real = attention.rotary_half
    monkeypatch.setattr(attention, "rotary_half",
                        lambda x, positions, theta, rotary_dim:
                        real(x, positions, 1e4, rotary_dim))


@pytest.mark.parametrize("broken", [_no_window, _no_sink, _one_expert_fewer,
                                    _one_theta],
                         ids=["window-mask-dropped", "sink-dropped",
                              "top-k-less-one", "one-rotary-base"])
def test_a_broken_path_is_not_correct(capsys, monkeypatch, broken):
    broken(monkeypatch)
    line, number = result_of(capsys)
    assert line["correct"] is False, number


def test_lower_precision_in_the_programs_place_fails_the_limit(capsys):
    """The control through the check a run makes, block by block, at a
    size a test can hold: the reference computed in int8 picks tokens
    whose reference logit lies further below the best than the limit
    allows; bfloat16's own picks stay inside it.  (The limit here is this
    size's own, between the two readings: bfloat16 read 0.37, 1.24 and
    1.59 on the three seeds and int8 4.65, 3.67 and 3.06.  A rounding
    that flips one of a token's experts moves a logit more than one that
    does not, and at this size a token has two experts of eight, so the
    two precisions lie nearer than in a dense model; the cell's limit is
    set from chip readings, PERF.md section 2.)"""
    cfg = dict(rehearsal_cfg(), hidden_size=256, num_attention_heads=8,
               num_key_value_heads=2, swa_num_key_value_heads=4, head_dim=48,
               v_head_dim=32, intermediate_size=512,
               moe_intermediate_size=128, vocab_size=4000, sliding_window=32)
    cfg["serving"] = dict(cfg["serving"], max_len=256)
    limit = 2.2
    cfg["correct"] = {"serve": {"logit_gap_max": limit}}
    mix = {"check_requests": 1, "new_tokens": {"max": 128}}
    got = {"bfloat16": [], "int8": []}
    ref._STEPS.clear()
    for seed in (11, 12, 13):
        rng = np.random.default_rng(seed)
        prompt = rng.integers(1, 4001, 96).astype(np.int32)
        served = rng.integers(1, 4001, 128).astype(np.int32)
        for precision, into in got.items():
            capsys.readouterr()
            # the served tokens are random here, so the run's own number
            # fails; what is read is the control's
            assert not serve_cell.check(kind, cfg, mix, seed,
                                        [(prompt, served)], precision)
            lines = capsys.readouterr().out.strip().splitlines()
            control = [json.loads(l.split("] ", 1)[1]) for l in lines
                       if l.startswith("[control] ")][0]
            into.append(control["control_gap_max"])
    ref._STEPS.clear()
    assert max(got["bfloat16"]) < limit < min(got["int8"]), (got, limit)


# ---- the four readers on synthetic observations -----------------------------

def _obs(scale0=1, scale1=3, drop=()):
    def stats(k):
        return {"decode_steps": 1000 * k, "moe_layer_calls": 11500 * k,
                "moe_pairs_total": 3_000_000 * k, "moe_pairs_held": 184_000 * k,
                "moe_active_experts": 115_000 * k,
                "cache_bytes_window": 3 * 2 ** 29, "cache_bytes_full": 2 ** 30}
    s0, s1 = stats(scale0), stats(scale1)
    for key in drop:
        s0.pop(key)
        s1.pop(key)
    return {"kind": "hybrid_moe_lm", "cfg": cell_cfg(), "stats0": s0,
            "stats1": s1}


def test_the_counter_readers_read_differences_across_the_window():
    obs = _obs()
    assert manifest.load_reader("moe_tokens_per_expert").read(obs) \
        == pytest.approx(184_000 * 2 / (16 * 11500 * 2))
    assert manifest.load_reader("moe_active_expert_share").read(obs) \
        == pytest.approx(100.0 * 115_000 * 2 / (16 * 11500 * 2))
    assert manifest.load_reader("window_cache_gib").read(obs) == 1.5


@pytest.mark.parametrize("name,key", [
    ("moe_tokens_per_expert", "moe_layer_calls"),
    ("moe_active_expert_share", "moe_layer_calls"),
    ("window_cache_gib", "cache_bytes_window"),
    ("moe_expert_roofline", "moe_active_experts")])
def test_a_reader_finds_nothing_in_a_program_without_the_counter(name, key):
    """The parent commit's ``stats()`` has none of these: the reader
    returns None and does not raise, and the line leaves the metric out."""
    reader = manifest.load_reader(name)
    obs = _obs(drop=(key,))
    obs["trace"] = None
    assert reader.read(obs) is None
    assert reader.read({"cfg": {}, "stats0": None, "stats1": None,
                        "kind": "decoder_lm"}) is None


class _Trace:
    """As much of ``harness.trace.Trace`` as the roofline reader touches:
    ten decode steps, each with six grouped products of 0.1 ms on the
    stacks, beside operations on other shapes."""

    def __init__(self):
        self.lo, self.hi = 0.0, 1.0
        self.modules = {"/device:TPU:0": [
            (0.01 * i, 0.01 * i + 0.009, "jit__decode(1)") for i in range(10)]}
        gp = ("%ragged-dot.{n} = f32[256,2048]{{1,0}} custom-call("
              "bf16[256,4096]{{1,0}} %a, bf16[16,4096,2048]{{2,1,0}} %w)")
        other = "%fusion.{n} = bf16[32,4096]{{1,0}} fusion(bf16[32,4096] %x)"
        self.ops = {"/device:TPU:0": []}
        for i in range(10):
            for j in range(6):
                s = 0.01 * i + 0.001 * j
                self.ops["/device:TPU:0"].append(
                    (s, s + 1e-4, gp.format(n=6 * i + j)))
            self.ops["/device:TPU:0"].append(
                (0.01 * i + 0.008, 0.01 * i + 0.0085, other.format(n=i)))

    def devices(self):
        return sorted(self.ops)

    def module_seconds(self, prefix):
        evs = [e for e in self.modules["/device:TPU:0"]
               if e[2].startswith(prefix)]
        return sum(e - s for s, e, _ in evs), len(evs)

    def ops_seconds(self, pred):
        from harness import trace as tr
        return sum(e - s for s, e, name in self.ops["/device:TPU:0"]
                   if pred(tr.hlo_category(name), name))


def test_the_expert_roofline_is_least_time_over_the_stacks_device_time():
    reader = manifest.load_reader("moe_expert_roofline")
    obs = _obs()
    obs["trace"] = _Trace()
    obs["peaks"] = {"hbm_bytes_per_s": 819e9, "bf16_flops_per_s": 197e12}
    cfg = obs["cfg"]
    cost = kind.expert_layer_cost(cfg, 115_000 * 2, 184_000 * 2)
    least_window = max(cost["bytes"] / 819e9, cost["flops"] / 197e12)
    assert cost["bytes"] / 819e9 > cost["flops"] / 197e12       # HBM-bound
    # ten of the window's 2,000 steps were traced; 60 products of 0.1 ms
    want = 100.0 * least_window * (10 / 2000) / (60 * 1e-4)
    assert reader.read(obs) == pytest.approx(want)
