"""The kind ``hybrid_ssm_lm`` and its reference: the specification is the
program's model leaf for leaf, the cut is the one the configuration states,
the check's blocks in turn compute what the whole forward computes, the
rehearsal run of the cell is ``correct``, broken paths of the program read
over the limit **or are named here as ones the tiny size does not reliably
show**,
the int8 control fails the limit, and the four readers read what they say
on synthetic ``obs``."""
import json
import os

import jax
import jax.numpy as jnp
import numpy as np
import pytest

import run
from harness import manifest, serve_cell, weights
from harness.kinds import hybrid_ssm_lm as kind
from reference import hybrid_ssm_lm as ref

LIMIT_AT_TEST_SIZE = 0.0002
BENCH = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
REHEARSAL = os.path.join(BENCH, "rehearsal")
CELL = "falconh1_serve_answers"


def rehearsal_cfg():
    return manifest.load_json(os.path.join(
        REHEARSAL, "configs", "falcon-h1-34b.json"))


def cell_cfg():
    return manifest.load_json(os.path.join(
        BENCH, "configs", "falcon-h1-34b.json"))


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
    # what weights.make makes of each leaf follows from its path and rank:
    # it has no rule of its own for a recurrence's leaves (assumed.seeding)
    assert {weights._rule(p) for p, _ in spec} == {"embedding", "weight",
                                                   "bias"}
    assert [p.rsplit(".", 1)[-1] for p, _ in spec
            if weights._rule(p) == "bias"] == ["bias"] * cfg[
                "num_hidden_layers"]                  # the convolution's
    assert all(weights._rule(p) == "weight" for p, _ in spec
               if p.endswith((".A_log", ".dt_bias", ".D")))


def test_the_cell_is_the_cut_the_configuration_states():
    cfg = cell_cfg()
    spec = kind.param_spec(cfg)
    params = sum(int(np.prod(s)) for _, s in spec)
    assert round(params / 1e9, 3) == 5.255
    layer = sum(int(np.prod(s)) for p, s in spec if p.startswith(".blocks[0]."))
    assert round(layer / 1e6, 1) == 430.1
    # published widths, unchanged
    assert (cfg["hidden_size"], cfg["num_attention_heads"],
            cfg["num_key_value_heads"], cfg["head_dim"],
            cfg["intermediate_size"], cfg["vocab_size"], cfg["mamba_n_heads"],
            cfg["mamba_d_head"], cfg["mamba_d_ssm"], cfg["mamba_n_groups"],
            cfg["mamba_d_state"], cfg["mamba_d_conv"],
            cfg["mamba_chunk_size"]) == (
        5120, 20, 4, 128, 21504, 261120, 32, 128, 4096, 2, 256, 4, 128)
    shapes = dict(spec)
    assert shapes[".blocks[0].attn.q_layer.weight"] == (2560, 5120)
    assert shapes[".blocks[0].attn.k_layer.weight"] == (512, 5120)
    assert shapes[".blocks[0].attn.output_layer.weight"] == (5120, 2560)
    assert shapes[".blocks[0].ssm.in_proj.weight"] == (9248, 5120)
    assert shapes[".blocks[0].ssm.conv.weight"] == (5120, 4)
    assert shapes[".blocks[0].ssm.out_proj.weight"] == (5120, 4096)
    assert shapes[".lm_head.weight"] == shapes[".embedding.weight"] \
        == (261120, 5120)
    assert kind.state_shape(cfg) == (48, 32, 256, 128)
    man = manifest.manifest()
    entry = [c for c in man["configs"] if c["name"] == "falcon-h1-34b"][0]
    assert entry["reduced"] == list(cfg["reduced"]) == ["num_hidden_layers"]
    assert cfg["num_hidden_layers"] == 6
    assert cfg["published"] == {"num_hidden_layers": 72}
    s = cfg["serving"]
    assert (s["slots"], s["max_len"], s["prefill_chunk"], s["state_dtype"],
            s["cache_dtype"], s["weights_dtype"]) == (
        48, 3584, 256, "float32", "bfloat16", "bfloat16")
    mix = manifest.traffic_of("answers_saturated")
    assert mix["prompt_tokens"]["max"] + mix["new_tokens"]["max"] \
        <= s["max_len"]
    assert mix["rate_rps"] == pytest.approx(1.5 * mix["knee_rps"])


def test_decode_step_bytes_counts_the_states_read_and_written():
    cfg = cell_cfg()
    spec = kind.param_spec(cfg)
    read = sum(int(np.prod(s)) for p, s in spec
               if p != ".embedding.weight") * 2
    state = 48 * 32 * 256 * 128 * 4
    beside = 48 * (3 * 32 * 128 + 2 * 2 * 256) * 4
    assert kind.ssm_step_cost(cfg)["bytes"] == 2 * state + beside
    assert kind.ssm_step_cost(cfg, rows=1)["bytes"] * 48 \
        == kind.ssm_step_cost(cfg)["bytes"]
    conv = 48 * 3 * 5120 * 2
    states = 6 * (2 * state + beside + 2 * conv)
    # nothing live: the layers' weights and the head's (not the embedding's
    # table: a step gathers 48 rows of it), and the states in and out
    assert kind.decode_step_bytes(cfg, 0) == pytest.approx(read + states)
    assert 2.4e9 < states < 2.5e9 and 7.8e9 < read < 7.9e9
    live = 48 * 1000
    assert kind.decode_step_bytes(cfg, live) == pytest.approx(
        read + states + 6 * live * 2 * 4 * 128 * 2)
    scan = kind.ssm_scan_cost(cfg, 256)
    assert scan["flops"] == 256 * 2 * (2 * 128 * 256 + 32 * 128 * 128
                                       + 2 * 32 * 256 * 128)
    assert scan["bytes"] == 256 * 4 * (2 * 4096 + 2 * 512 + 32)


@pytest.mark.parametrize("in_blocks", [False, True],
                         ids=["whole-scores", "query-blocks"])
def test_the_blocks_in_turn_equal_the_whole_forward(monkeypatch, in_blocks):
    """embed, block by block, head, as the check walks them: the logits of
    the reference's whole forward to 1e-6, and of the program's forward
    (the chunked scan) on the same float32 leaves to 1e-5 (the logits are
    of order 0.01 at the published multipliers)."""
    if in_blocks:
        monkeypatch.setattr(ref, "SCORES_BYTES", 0)
        monkeypatch.setattr(ref, "Q_BLOCK", 16)
        monkeypatch.setattr(ref, "HEAD_BYTES", 500 * 64)   # four blocks
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
        leaves = list(weights.make(spec, 17, jnp.bfloat16))
        for _n, idx in blocks:          # as build_serve makes what it serves
            kind.seed_mixer(cfg, spec, idx, leaves)
        leaves = [l.astype(jnp.float32) for l in leaves]
        whole = ref.forward({p: l for (p, _), l in zip(spec, leaves)},
                            cfg, toks)
        abstract = jax.eval_shape(lambda: kind._model(cfg, 128))
        weights.reset_program_rng(17)
        model = jax.tree_util.tree_unflatten(
            jax.tree_util.tree_structure(abstract), leaves).eval_mode()
        program = model.forward(toks)
    assert float(jnp.max(jnp.abs(whole))) > 0.01
    np.testing.assert_allclose(walked, whole, atol=1e-6)
    np.testing.assert_allclose(program, whole, atol=1e-5)
    ref._STEPS.clear()


def test_the_seeding_gives_a_state_that_remembers_and_matters():
    """What ``seeded_mixer`` makes of the harness's seeded leaves: ``A``
    and ``dt`` inside the stated ranges and spread over them, every
    segment of the projection at the stated size on a normed input, the
    other leaves as they came, everything rounded to the served dtype."""
    cfg = dict(rehearsal_cfg(), mamba_n_heads=64, mamba_d_ssm=1024)
    spec = kind.param_spec(cfg)
    idx = dict(kind.param_blocks(cfg))["blocks[0]"]
    made = dict(zip((spec[i][0] for i in idx),
                    weights.make(spec, 23, jnp.bfloat16, only=idx)))
    w = {p.split(".ssm", 1)[1]: l for p, l in made.items() if ".ssm." in p}
    new = ref.seeded_mixer(w, cfg, jnp.bfloat16)
    assert set(new) == set(w)
    assert all(new[k].dtype == jnp.bfloat16 for k in new)
    changed = {k for k in w if not np.array_equal(
        np.asarray(new[k], np.float32), np.asarray(w[k], np.float32))}
    assert changed == {".A_log", ".dt_bias", ".in_proj.weight",
                       ".out_proj.weight"}
    rule = cfg["seeding"]
    a = np.exp(np.asarray(new[".A_log"], np.float32))
    dt = np.log1p(np.exp(np.asarray(new[".dt_bias"], np.float32)))
    assert rule["A"][0] * 0.99 <= a.min() and a.max() <= rule["A"][1] * 1.01
    assert rule["dt"][0] * 0.95 <= dt.min() \
        and dt.max() <= rule["dt"][1] * 1.05
    assert a.max() / a.min() > 4 and dt.max() / dt.min() > 20
    assert 1.0 / (a * dt).min() > 100          # a head that remembers long
    u = np.random.default_rng(0).normal(size=(4096, cfg["hidden_size"]))
    u = (u / np.sqrt((u ** 2).mean(-1, keepdims=True))).astype(np.float32)
    heads = cfg["mamba_n_heads"]
    gn = cfg["mamba_n_groups"] * cfg["mamba_d_state"]
    inner = heads * cfg["mamba_d_head"]
    out = u * cfg["ssm_in_multiplier"] @ np.asarray(
        new[".in_proj.weight"], np.float32).T
    at = 0
    for size, m in zip((inner, inner, gn, gn, heads), cfg["ssm_multipliers"]):
        assert (out[:, at:at + size] * m).std() == pytest.approx(
            rule["projection_std"], rel=0.1)
        at += size
    ratio = np.asarray(new[".out_proj.weight"], np.float32).std() \
        / np.asarray(w[".out_proj.weight"], np.float32).std()
    assert ratio == pytest.approx(rule["output_std"], rel=0.01)
    # the reference's block reads the same numbers from the float32 casts
    again = ref.seeded_mixer({k: v.astype(jnp.float32) for k, v in w.items()},
                             cfg, jnp.bfloat16)
    for k in new:
        assert again[k].dtype == jnp.float32
        np.testing.assert_array_equal(np.asarray(again[k]),
                                      np.asarray(new[k], np.float32))


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


# ---- broken paths of the program, at the tiny size ------------------------

def _configured(monkeypatch, **over):
    """The program built from another configuration than the reference's."""
    real = kind._model
    monkeypatch.setattr(kind, "_model", lambda cfg, max_len: real(
        dict(cfg, **over), max_len))


def _no_ssm(monkeypatch):
    _configured(monkeypatch, ssm_out_multiplier=0.0)


def _no_attention(monkeypatch):
    _configured(monkeypatch, attention_out_multiplier=0.0)


def _no_key_multiplier(monkeypatch):
    _configured(monkeypatch, key_multiplier=1.0)


def _no_ssm_in_multiplier(monkeypatch):
    _configured(monkeypatch, ssm_in_multiplier=1.0)


def _no_gate_multiplier(monkeypatch):
    _configured(monkeypatch, mlp_multipliers=[
        1.0, rehearsal_cfg()["mlp_multipliers"][1]])


def _no_dt_multiplier(monkeypatch):
    m = list(rehearsal_cfg()["ssm_multipliers"])
    _configured(monkeypatch, ssm_multipliers=m[:4] + [1.0])


def _mixer_state(monkeypatch, change):
    """A chunk's carried state changed on its way into the mixer."""
    from bigdl_tpu.nn.ssm import Mamba2Mixer
    real = Mamba2Mixer.forward
    monkeypatch.setattr(
        Mamba2Mixer, "forward", lambda self, u, state=None, valid=None:
        real(self, u, None if state is None else change(state), valid))


def _conv_dropped(monkeypatch):
    _mixer_state(monkeypatch, lambda s: dict(
        s, conv=jnp.zeros_like(s["conv"])))


def _state_not_carried(monkeypatch):
    _mixer_state(monkeypatch, lambda s: dict(s, ssm=jnp.zeros_like(s["ssm"])))


def _state_not_reset(monkeypatch):
    from bigdl_tpu.models import hybrid_decoder
    from bigdl_tpu.nn.ssm import Mamba2Mixer
    monkeypatch.setattr(hybrid_decoder, "_fresh_state",
                        lambda state, fresh: state)
    real = Mamba2Mixer.step
    monkeypatch.setattr(Mamba2Mixer, "step",
                        lambda self, u, state, active=None, fresh=None:
                        real(self, u, state, active, None))


def _state_bfloat16(monkeypatch):
    from bigdl_tpu.nn.ssm import Mamba2Mixer
    real = Mamba2Mixer.init_state
    monkeypatch.setattr(
        Mamba2Mixer, "init_state", lambda self, batch, dtype=jnp.float32:
        dict(real(self, batch, dtype), ssm=real(self, batch, dtype)[
            "ssm"].astype(jnp.bfloat16)))


# What the check reads at the tiny size (CPU, PR 38, the configuration's
# ``seeding``; three or four seeds each), against the rehearsal's limit of
# 1.5e-4: sound runs 0.0-5.4e-5.  **Seen, on every seed**: the SSM branch
# left out 8.5e-3-1.2e-2, the key multiplier 6.8e-4-1.4e-3, the mixer's
# input multiplier 8.1e-3-9.5e-3, the step sizes' multiplier 3.1e-3-5.7e-3,
# the gate's 5.5e-4-1.1e-3, attention left out 3.8e-4-1.4e-3.  **Not
# reliably seen at this size** (a sample of six requests of 25-160 tokens
# over 500 tokens of vocabulary sees a fault only where it turns a served
# token, and which requests it holds follows the clock): the
# state not carried between chunks 4.9e-5-3.3e-4 (three seeds of four
# over the limit), not reset at admission 4e-7-3.6e-4 (one of four), the
# convolution's inputs dropped between chunks 0.0-8.0e-5, the state kept in
# bfloat16 0.0-2.8e-5 (its error grows with the tokens a state has
# summed; these sequences are short).  Those four are read on the chip, at
# the cell's own size, where a sample is two requests of a thousand tokens
# each over 261,120: PERF.md section 2 has the readings, and the tier-1
# tests hold each rule with leaves that remember
# (tests/test_state_space.py).  Before the configuration had a ``seeding``
# every one of them read 0.0.
BROKEN = [
    ("ssm-branch-left-out", _no_ssm, True),
    ("attention-branch-left-out", _no_attention, True),
    ("key-multiplier-left-out", _no_key_multiplier, True),
    ("ssm-in-multiplier-left-out", _no_ssm_in_multiplier, True),
    ("dt-multiplier-left-out", _no_dt_multiplier, True),
    ("gate-multiplier-left-out", _no_gate_multiplier, True),
    ("conv-state-dropped-between-chunks", _conv_dropped, False),
    ("state-not-carried-between-chunks", _state_not_carried, False),
    ("state-not-reset-at-admission", _state_not_reset, False),
    ("state-kept-in-bfloat16", _state_bfloat16, False),
]


@pytest.mark.parametrize("broken", [b for _, b, seen in BROKEN if seen],
                         ids=[n for n, _, seen in BROKEN if seen])
def test_a_broken_path_is_not_correct(capsys, monkeypatch, broken):
    broken(monkeypatch)
    line, number = result_of(capsys)
    assert line["correct"] is False, number


@pytest.mark.parametrize("broken", [b for _, b, seen in BROKEN if not seen],
                         ids=[n for n, _, seen in BROKEN if not seen])
def test_a_path_named_as_not_reliably_seen_still_runs(capsys, monkeypatch,
                                                      broken):
    """The paths the comment above names: the broken program serves its
    requests and the check gives a number; whether it passes the limit
    follows which requests the sample holds, so nothing is asserted of
    it here."""
    broken(monkeypatch)
    line, number = result_of(capsys)
    assert line["failed"] == 0 and number["value"] >= 0.0


def test_lower_precision_in_the_programs_place_fails_the_limit(capsys):
    """The control through the check a run makes, block by block, at a
    size a test can hold: the reference computed in int8 picks tokens
    whose reference logit lies further below the best than the limit
    allows; bfloat16's own picks stay inside it.  (The limit here is this
    size's own, between the two readings; the cell's is set from chip
    readings, PERF.md section 2.)"""
    cfg = dict(rehearsal_cfg(), hidden_size=256, num_attention_heads=8,
               num_key_value_heads=2, head_dim=32, intermediate_size=512,
               vocab_size=4000, mamba_n_heads=8, mamba_d_head=32,
               mamba_d_ssm=256, mamba_d_state=32, mamba_chunk_size=32)
    cfg["serving"] = dict(cfg["serving"], max_len=256)
    limit = LIMIT_AT_TEST_SIZE
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
        return {"decode_steps": 1000 * k, "ssm_layer_calls": 7000 * k,
                "ssm_scan_positions": 600_000 * k,
                "ssm_scan_positions_real": 480_000 * k, "state_resets": 60 * k,
                "cache_bytes_state": 3 * 2 ** 29, "cache_bytes_full": 2 ** 30}
    s0, s1 = stats(scale0), stats(scale1)
    for key in drop:
        s0.pop(key)
        s1.pop(key)
    return {"kind": "hybrid_ssm_lm", "cfg": cell_cfg(), "stats0": s0,
            "stats1": s1}


def test_the_counter_readers_read_the_pool_and_the_window():
    obs = _obs()
    assert manifest.load_reader("state_cache_gib").read(obs) == 1.5
    assert manifest.load_reader("ssm_scan_pad_share").read(obs) \
        == pytest.approx(20.0)


@pytest.mark.parametrize("name,key", [
    ("state_cache_gib", "cache_bytes_state"),
    ("ssm_scan_pad_share", "ssm_scan_positions"),
    ("ssm_scan_pad_share", "ssm_scan_positions_real")])
def test_a_reader_finds_nothing_in_a_program_without_the_counter(name, key):
    """The parent commit's ``stats()`` has none of these: the reader
    returns None and does not raise, and the line leaves the metric out.
    Nor does a model without state layers report a share of nothing."""
    reader = manifest.load_reader(name)
    obs = _obs(drop=(key,))
    assert reader.read(obs) is None
    assert reader.read({"cfg": {}, "stats0": None, "stats1": None,
                        "kind": "decoder_lm"}) is None
    zero = _obs()
    for s in (zero["stats0"], zero["stats1"]):
        s.update(cache_bytes_state=0, ssm_scan_positions=0,
                 ssm_scan_positions_real=0)
    assert reader.read(zero) is None


class _Trace:
    """As much of ``harness.trace.Trace`` as the roofline readers touch:
    ten decode steps, each with six state updates of 0.5 ms (fusions
    that name the pooled state's shape), and four chunk programs
    with six scan loops of 0.3 ms each, beside operations on other shapes
    and a state-shaped write outside a decode step."""

    DEV = "/device:TPU:0"

    def __init__(self):
        self.lo, self.hi = 0.0, 1.0
        step = ("%fusion.{n} = (f32[48,32,256,128]{{3,2,1,0}}, f32[48,32,"
                "128]{{2,1,0}}) fusion(f32[48,32,256,128]{{3,2,1,0}} %s), "
                "kind=kLoop, calls=%fused_computation.{n}")
        loop = ("%while.{n} = (s32[], f32[1,32,256,128]{{2,3,1,0}}, f32[2,1,"
                "128,32,128]{{4,3,2,0,1}}) while(%tuple.{n}), condition=%c, "
                "body=%b")
        other = "%fusion.{n} = bf16[48,5120]{{1,0}} fusion(bf16[48,5120] %x)"
        write = ("%dynamic-update-slice.{n} = f32[48,32,256,128]{{3,2,1,0}} "
                 "dynamic-update-slice(f32[48,32,256,128] %p, f32[1,32,256,"
                 "128] %u)")
        self.modules = {self.DEV: []}
        self.ops = {self.DEV: []}
        for i in range(10):
            t = 0.02 * i
            self.modules[self.DEV].append((t, t + 0.015, "jit__decode(1)"))
            for j in range(6):
                s = t + 0.002 * j
                self.ops[self.DEV].append((s, s + 5e-4,
                                           step.format(n=6 * i + j)))
            self.ops[self.DEV].append((t + 0.014, t + 0.0145,
                                       other.format(n=i)))
        for i in range(4):
            t = 0.5 + 0.1 * i
            self.modules[self.DEV].append((t, t + 0.02,
                                           "jit__chunk_prefill(2)"))
            for j in range(6):
                s = t + 0.003 * j
                self.ops[self.DEV].append((s, s + 3e-4,
                                           loop.format(n=6 * i + j)))
                self.ops[self.DEV].append((s + 1e-4, s + 2e-4,
                                           other.format(n=100 + j)))
            self.ops[self.DEV].append((t + 0.019, t + 0.0195,
                                       write.format(n=i)))

    def devices(self):
        return sorted(self.ops)


def test_the_roofline_readers_find_their_operations_by_the_states_shape():
    obs = dict(_obs(), trace=_Trace(), peaks={
        "hbm_bytes_per_s": 819e9, "bf16_flops_per_s": 197e12})
    cfg = obs["cfg"]
    step = manifest.load_reader("ssm_step_roofline").read(obs)
    least = 10 * 6 * kind.ssm_step_cost(cfg)["bytes"] / 819e9
    assert step == pytest.approx(100.0 * least / (10 * 6 * 5e-4))
    assert 90 < step < 100
    scan = manifest.load_reader("ssm_scan_roofline").read(obs)
    cost = kind.ssm_scan_cost(cfg, 256)
    one = max(cost["bytes"] / 819e9, cost["flops"] / 197e12)
    assert scan == pytest.approx(100.0 * one / 3e-4)
    assert 1 < scan < 10


@pytest.mark.parametrize("name", ["ssm_step_roofline", "ssm_scan_roofline"])
def test_a_roofline_reader_finds_nothing_where_nothing_is(name):
    """No trace, a trace with no such operation (the parent commit's
    program, or another kind's), a kind without the cost function: None."""
    reader = manifest.load_reader(name)
    assert reader.read(dict(_obs(), trace=None)) is None
    empty = _Trace()
    empty.ops = {empty.DEV: [e for e in empty.ops[empty.DEV]
                             if "256,128]" not in e[2]]}
    assert reader.read(dict(_obs(), trace=empty, peaks={
        "hbm_bytes_per_s": 819e9, "bf16_flops_per_s": 197e12})) is None
    other = dict(_obs(), kind="decoder_lm", trace=_Trace())
    assert reader.read(other) is None
