"""The kind ``shared_kv_ssm_lm`` and its reference: the specification is
the program's model leaf for leaf, the cell is the whole model as the
configuration states it, the check's blocks in turn compute what the whole
forward computes (carrying ``m`` and the shared keys and values beside the
residual stream), the seeding reads the harness's leaves as it says, the
rehearsal run of the cell is ``correct``, broken paths of the program read
over the limit **or are named here as ones the tiny size does not reliably
show**, the int8 control fails the limit, and the four readers read what
they say on synthetic ``obs``."""
import json
import os

import jax
import jax.numpy as jnp
import numpy as np
import pytest

import run
from harness import manifest, serve_cell, weights
from harness.kinds import shared_kv_ssm_lm as kind
from reference import shared_kv_ssm_lm as ref

LIMIT_AT_TEST_SIZE = 0.05
BENCH = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
REHEARSAL = os.path.join(BENCH, "rehearsal")
CELL = "phi4flash_serve_chains"
NAME = "phi-4-mini-flash-reasoning"


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


def test_the_cell_is_the_model_whole_as_the_configuration_states():
    cfg = cell_cfg()
    spec = kind.param_spec(cfg)
    params = sum(int(np.prod(s)) for _, s in spec)
    assert round(params / 1e6, 1) == 3852.6

    def layer(i):
        return sum(int(np.prod(s)) for p, s in spec
                   if p.startswith(f".blocks[{i}]."))
    mlp = 3 * 2560 * 10240 + 4 * 2560
    assert round((layer(0) - mlp) / 1e6, 2) == 41.24     # a Mamba-1 mixer
    assert round((layer(1) - mlp) / 1e6, 2) == 19.67     # attention, own k, v
    assert round((layer(19) - mlp) / 1e6, 2) == 13.11    # cross attention
    assert round((layer(18) - mlp) / 1e6, 2) == 26.21    # a gated memory unit
    kinds = kind.layer_kinds(cfg)
    assert [kinds.count(k) for k in ("mamba", "window", "full", "memory",
                                     "cross")] == [9, 8, 1, 7, 7]
    assert kinds[16:20] == ["mamba", "full", "memory", "cross"]
    shapes = dict(spec)
    assert shapes[".blocks[17].attn.q_layer.weight"] == (5120, 2560)
    assert shapes[".blocks[19].attn.q_layer.weight"] == (2560, 2560)
    assert shapes[".blocks[17].attn.norm.weight"] == (128,)
    assert shapes[".blocks[0].ssm.in_proj.weight"] == (10240, 2560)
    assert shapes[".blocks[0].ssm.x_proj.weight"] == (192, 5120)
    assert shapes[".blocks[0].ssm.dt_proj.weight"] == (5120, 160)
    assert shapes[".blocks[0].ssm.A_log"] == (5120, 16)
    assert shapes[".blocks[18].unit.in_proj.weight"] == (5120, 2560)
    assert shapes[".embedding.weight"] == (200064, 2560)
    assert ".lm_head.weight" not in shapes
    assert kind.selective_state_shape(cfg) == (96, 16, 5120)
    assert kind.shared_leaf_shape(cfg) == (96, 10, 5120, 128)
    assert kind.place_bytes(cfg) == 5120
    # the published configuration, every number of it
    catalog = {"embd_pdrop": 0, "hidden_act": "silu", "hidden_size": 2560,
               "intermediate_size": 10240, "layer_norm_eps": 1e-05,
               "max_position_embeddings": 262144, "mb_per_layer": 2,
               "model_type": "phi4flash", "num_attention_heads": 40,
               "num_hidden_layers": 32, "num_key_value_heads": 20,
               "resid_pdrop": 0, "sliding_window": 512,
               "tie_word_embeddings": True, "mlp_bias": False,
               "lm_head_bias": False, "vocab_size": 200064}
    assert {k: cfg[k] for k in catalog} == catalog
    man = manifest.manifest()
    entry = [c for c in man["configs"] if c["name"] == NAME][0]
    assert entry["reduced"] == list(cfg["reduced"]) == []
    s = cfg["serving"]
    assert (s["max_len"], s["prefill_chunk"], s["state_dtype"],
            s["cache_dtype"], s["weights_dtype"]) == (
        5120, 256, "float32", "bfloat16", "bfloat16")
    assert s["slots"] in (96, 80)
    mix = manifest.traffic_of("chains_saturated")
    assert mix["prompt_tokens"] == {"median": 768, "sigma": 0.5, "min": 256,
                                    "max": 2048}
    assert mix["new_tokens"] == {"median": 1536, "sigma": 0.3, "min": 768,
                                 "max": 3072}
    assert mix["prompt_tokens"]["max"] + mix["new_tokens"]["max"] \
        <= s["max_len"]
    assert mix["rate_rps"] == pytest.approx(1.5 * mix["knee_rps"])
    for metric in man["per_layer"]:
        if metric["name"] in ("ssm_step_roofline", "ssm_scan_roofline",
                              "decode_read_over_live"):
            assert CELL not in metric["workloads"]


def test_decode_step_bytes_counts_the_shared_row_once_a_reader():
    cfg = cell_cfg()
    spec = kind.param_spec(cfg)
    read = sum(int(np.prod(s)) for _, s in spec) * 2     # the table as the head
    state = 96 * 16 * 5120 * 4
    beside = 96 * (3 * 5120 + 2 * 16) * 4
    assert kind.selective_step_cost(cfg)["bytes"] == 2 * state + beside
    assert kind.selective_step_cost(cfg, rows=1)["bytes"] * 96 \
        == kind.selective_step_cost(cfg)["bytes"]
    conv = 96 * 3 * 5120 * 2
    states = 9 * (2 * state + beside + 2 * conv)
    rings = 8 * 96 * 768 * 5120
    assert kind.decode_step_bytes(cfg, 0) == pytest.approx(
        read + states + rings)
    assert 0.6e9 < states < 0.7e9 and 7.7e9 < read < 7.72e9
    assert 3.0e9 < rings < 3.05e9
    live = 96 * 1570
    assert kind.decode_step_bytes(cfg, live) == pytest.approx(
        read + states + rings + 8 * live * 5120)
    cost = kind.shared_kv_decode_cost(cfg, 8 * live)
    assert cost["bytes"] == 8 * live * 5120
    assert cost["flops"] == 8 * live * 40 * 2 * 2 * 128
    scan = kind.selective_scan_cost(cfg, 256)
    assert scan["bytes"] == 256 * 4 * (3 * 5120 + 32)
    assert scan["flops"] == 256 * 6 * 16 * 5120


def _served_leaves(cfg, spec, blocks, seed):
    leaves = list(weights.make(spec, seed, jnp.bfloat16))
    for _n, idx in blocks:          # as build_serve makes what it serves
        kind.seed_block(cfg, spec, idx, leaves)
    return leaves


@pytest.mark.parametrize("in_blocks", [False, True],
                         ids=["whole-scores", "query-blocks"])
def test_the_blocks_in_turn_equal_the_whole_forward(monkeypatch, in_blocks):
    """embed, block by block, head, as the check walks them (one array a
    request, ``m`` and the shared keys and values beside the residual
    stream): the logits of the reference's whole forward to 1e-5, and of
    the program's forward on the same float32 leaves to 1e-4."""
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
                assert x.shape == (2, 64, 64 + 128 + 2 * 32)
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
    # five programs for eight layers: embed, mamba, self, memory, cross, head
    assert sorted(k[0] for k in ref._STEPS) == [
        "cross", "embed", "head", "mamba", "memory", "self"]
    ref._STEPS.clear()


def test_the_seeding_reads_the_harness_leaves_as_it_says():
    """``seeded``: the lambda vectors a normal of 0.1, ``A`` 1..16 over
    the states of every channel, step sizes log-uniform in the stated
    range, ``B`` and ``C``'s rows of ``x_proj`` scaled; the other leaves as
    they came; everything rounded to the served dtype; the reference's
    block reads the same numbers from the float32 casts."""
    cfg = rehearsal_cfg()
    spec = kind.param_spec(cfg)
    rule = cfg["seeding"]
    for block, kinds_changed in (
            ("blocks[0]", {".ssm.A_log", ".ssm.dt_proj.bias",
                           ".ssm.x_proj.weight"}),
            ("blocks[1]", {".attn.lambda_q1", ".attn.lambda_k1",
                           ".attn.lambda_q2", ".attn.lambda_k2"}),
            ("blocks[6]", set())):
        idx = dict(kind.param_blocks(cfg))[block]
        w = {spec[i][0].split("]", 1)[1]: l for i, l in zip(
            idx, weights.make(spec, 23, jnp.bfloat16, only=idx))}
        new = ref.seeded(w, cfg, jnp.bfloat16)
        assert set(new) == set(w)
        assert all(new[k].dtype == jnp.bfloat16 for k in new)
        changed = {k for k in w if not np.array_equal(
            np.asarray(new[k], np.float32), np.asarray(w[k], np.float32))}
        assert changed == kinds_changed
        again = ref.seeded({k: v.astype(jnp.float32) for k, v in w.items()},
                           cfg, jnp.bfloat16)
        for k in new:
            assert again[k].dtype == jnp.float32
            np.testing.assert_array_equal(np.asarray(again[k]),
                                          np.asarray(new[k], np.float32))
        if block == "blocks[0]":
            a = np.exp(np.asarray(new[".ssm.A_log"], np.float32))
            np.testing.assert_allclose(
                a, np.broadcast_to(np.arange(1, 17), a.shape), rtol=0.01)
            dt = np.log1p(np.exp(np.asarray(new[".ssm.dt_proj.bias"],
                                            np.float32)))
            lo, hi = rule["dt"]
            assert lo * 0.9 <= dt.min() and dt.max() <= hi * 1.1
            assert dt.max() / dt.min() > 10
            rank = kind.sizes(cfg)["rank"]
            ratio = np.asarray(new[".ssm.x_proj.weight"], np.float32) \
                / np.asarray(w[".ssm.x_proj.weight"], np.float32)
            np.testing.assert_allclose(ratio[:rank], 1.0, rtol=0.01)
            np.testing.assert_allclose(ratio[rank:], rule["bc_scale"],
                                       rtol=0.01)
        if block == "blocks[1]":
            lam = np.asarray(new[".attn.lambda_q1"], np.float32)
            assert abs(lam.mean()) < 0.1 and 0.03 < lam.std() < 0.2


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

def _memory_after_the_gate(monkeypatch):
    from bigdl_tpu.nn.ssm import Mamba1Mixer
    fwd, step = Mamba1Mixer.forward, Mamba1Mixer.step

    def gated(self, out_state_y, u):
        out, state, y = out_state_y
        z = self._project(u)[1].reshape(y.shape)
        return out, state, y * jax.nn.silu(z)
    monkeypatch.setattr(
        Mamba1Mixer, "forward", lambda self, u, state=None, valid=None:
        gated(self, fwd(self, u, state, valid), u))
    monkeypatch.setattr(
        Mamba1Mixer, "step", lambda self, u, state, active=None, fresh=None:
        gated(self, step(self, u, state, active, fresh), u))


def _memory_left_out(monkeypatch):
    from bigdl_tpu.models.hybrid_decoder import GatedMemoryUnit
    real = GatedMemoryUnit.forward
    monkeypatch.setattr(GatedMemoryUnit, "forward",
                        lambda self, n, memory: 0.0 * real(self, n, memory))


def _lambda_of_the_wrong_sign(monkeypatch):
    from bigdl_tpu.nn.differential_attention import DifferentialAttention
    real = DifferentialAttention._lambda
    monkeypatch.setattr(DifferentialAttention, "_lambda",
                        lambda self: -real(self))


def _cross_layers_read_a_window(monkeypatch):
    """A cross layer attending a ring's worth of the row: the last
    ``sliding_window`` positions."""
    from bigdl_tpu.nn import differential_attention as da
    real = da.DifferentialAttention._attend
    window = rehearsal_cfg()["sliding_window"]

    def attend(self, q, keys, vals, index, q_pos, last, pad, slot, active,
               cached):
        if not self.cross:
            return real(self, q, keys, vals, index, q_pos, last, pad, slot,
                        active, cached)
        k_pos = q_pos if not cached else da.cache_positions(
            keys.shape[2], last, False)
        return da.grouped_attention(q, keys, vals, q_pos, k_pos, window, pad,
                                    scale=self.scale)
    monkeypatch.setattr(da.DifferentialAttention, "_attend", attend)


def _mixer_state(monkeypatch, change):
    """A chunk's carried state changed on its way into the mixer."""
    from bigdl_tpu.nn.ssm import Mamba1Mixer
    real = Mamba1Mixer.forward
    monkeypatch.setattr(
        Mamba1Mixer, "forward", lambda self, u, state=None, valid=None:
        real(self, u, None if state is None else change(state), valid))


def _state_not_carried(monkeypatch):
    _mixer_state(monkeypatch, lambda s: dict(s, ssm=jnp.zeros_like(s["ssm"])))


def _state_not_reset(monkeypatch):
    from bigdl_tpu.models import hybrid_decoder
    from bigdl_tpu.nn.ssm import Mamba1Mixer
    monkeypatch.setattr(hybrid_decoder, "_fresh_state",
                        lambda state, fresh: state)
    real = Mamba1Mixer.step
    monkeypatch.setattr(Mamba1Mixer, "step",
                        lambda self, u, state, active=None, fresh=None:
                        real(self, u, state, active, None))


def _state_bfloat16(monkeypatch):
    from bigdl_tpu.nn.ssm import Mamba1Mixer
    real = Mamba1Mixer.init_state
    monkeypatch.setattr(
        Mamba1Mixer, "init_state", lambda self, batch, dtype=jnp.float32:
        dict(real(self, batch, dtype), ssm=real(self, batch, dtype)[
            "ssm"].astype(jnp.bfloat16)))


# What the check reads at the tiny size (CPU, PR 44, one seed each; the
# rehearsal's limit is 0.2 and a sound run reads 0.013): **seen**: ``m``
# taken after the gate, the memory units left out and a lambda of the wrong
# sign (each far over).  **Not held to the limit here** (which requests a
# sample of two holds follows the clock, and these sequences are 20-110
# tokens long): the state not carried between chunks read 0.82 and not reset
# at admission 1.30, both over; the cross layers attending a window of 24
# places 0.027 and the state kept in bfloat16 0.024, a sound run's numbers
# (a prompt here is hardly longer than the window, and a state's rounding
# grows with the tokens it has summed).  All four are read on the chip at
# the cell's own size (PERF.md section 2), and the tier-1 tests hold each
# rule with leaves that remember (tests/test_selective_ssm.py,
# tests/test_shared_kv_decoder.py).
BROKEN = [
    ("memory-taken-after-the-gate", _memory_after_the_gate, True),
    ("memory-units-left-out", _memory_left_out, True),
    ("lambda-of-the-wrong-sign", _lambda_of_the_wrong_sign, True),
    ("cross-layers-read-a-window", _cross_layers_read_a_window, False),
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
               num_key_value_heads=4, intermediate_size=512, vocab_size=4000,
               sliding_window=64)
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
        return {"decode_steps": 1000 * k,
                "decode_positions_live": 150_000_000 * k,
                "full_row_readers": 8, "prefill_positions": 200_000 * k,
                "chunk_layer_positions": 17 * 200_000 * k}
    s0, s1 = stats(scale0), stats(scale1)
    for key in drop:
        s0.pop(key)
        s1.pop(key)
    return {"kind": "shared_kv_ssm_lm", "cfg": cell_cfg(), "stats0": s0,
            "stats1": s1}


PEAKS = {"hbm_bytes_per_s": 819e9, "bf16_flops_per_s": 197e12}


def test_the_counter_reader_reads_the_depth_a_chunk_walks():
    assert manifest.load_reader("prefill_depth_share").read(_obs()) \
        == pytest.approx(100.0 * 17 / 32)
    for key in ("chunk_layer_positions", "prefill_positions"):
        assert manifest.load_reader("prefill_depth_share").read(
            _obs(drop=(key,))) is None
    nothing = _obs()
    for s in (nothing["stats0"], nothing["stats1"]):
        s.update(prefill_positions=0, chunk_layer_positions=0)
    assert manifest.load_reader("prefill_depth_share").read(nothing) is None


class _Trace:
    """As much of ``harness.trace.Trace`` as the roofline readers touch:
    ten decode steps (two of them joint passes), each with nine state
    updates of 0.1 ms (fusions that name the pooled state's shape) and
    eight kernel calls of 1 ms and a write of 0.05 ms that name the
    shared leaf's shape; the joint passes also hold nine scan loops of 1
    ms over 256 positions; beside them operations on other shapes, and a
    leaf-shaped scatter outside any decode step."""

    DEV = "/device:TPU:0"

    def __init__(self):
        self.lo, self.hi = 0.0, 1.0
        step = ("%fusion.{n} = (f32[96,16,5120]{{2,1,0}}, f32[96,5120]"
                "{{1,0}}) fusion(f32[96,16,5120]{{2,1,0}} %s), kind=kLoop, "
                "calls=%fused_computation.{n}")
        attend = ("%custom-call.{n} = f32[96,40,1,128]{{3,2,1,0}} custom-call("
                  "f32[96,40,1,128] %q, bf16[96,10,5120,128]{{3,2,1,0}} %k, "
                  "bf16[96,10,5120,128]{{3,2,1,0}} %v), "
                  "custom_call_target=\"tpu_custom_call\"")
        write = ("%custom-call.{n} = (bf16[96,10,5120,128]{{3,2,1,0}}, bf16["
                 "96,10,5120,128]{{3,2,1,0}}) custom-call(bf16[96,10,5120,128]"
                 " %k), custom_call_target=\"tpu_custom_call\"")
        ring = ("%fusion.{n} = f32[96,40,1,128]{{3,2,1,0}} fusion(bf16[96,10,"
                "768,128]{{3,2,1,0}} %k), kind=kOutput")
        loop = ("%while.{n} = (s32[], f32[1,16,5120]{{2,1,0}}, f32[32,8,1,"
                "5120]{{3,1,2,0}}, f32[32,8,1,16]{{3,1,2,0}}, f32[16,5120]"
                "{{1,0}}) while(%tuple.{n}), condition=%c, body=%b")
        self.modules = {self.DEV: []}
        self.ops = {self.DEV: []}
        for i in range(10):
            t = 0.05 * i
            joint = i in (3, 7)
            self.modules[self.DEV].append((
                t, t + 0.04, "jit__decode_with_chunk(3)" if joint
                else "jit__decode(1)"))
            for j in range(9):
                s = t + 0.001 * j
                self.ops[self.DEV].append((s, s + 1e-4,
                                           step.format(n=9 * i + j)))
                if joint:
                    self.ops[self.DEV].append((s + 0.02, s + 0.021,
                                               loop.format(n=9 * i + j)))
            for j in range(8):
                s = t + 0.01 + 0.001 * j
                self.ops[self.DEV].append((s, s + 1e-3,
                                           attend.format(n=8 * i + j)))
                self.ops[self.DEV].append((s + 0.03, s + 0.0301,
                                           ring.format(n=100 + j)))
            self.ops[self.DEV].append((t + 0.019, t + 0.01905,
                                       write.format(n=1000 + i)))
        self.ops[self.DEV].append((0.9, 0.95, write.format(n=5000)))

    def devices(self):
        return sorted(self.ops)


def test_the_roofline_readers_find_their_operations_by_shape():
    obs = dict(_obs(), trace=_Trace(), peaks=PEAKS)
    cfg = obs["cfg"]
    shared = manifest.load_reader("shared_kv_decode_roofline").read(obs)
    # 2000 steps in the window read 300 M places: 150,000 a step, eight times
    cost = kind.shared_kv_decode_cost(cfg, 8 * 150_000 * 10)
    secs = 10 * (8 * 1e-3 + 5e-5)
    assert shared == pytest.approx(100.0 * (cost["bytes"] / 819e9) / secs)
    assert 90 < shared < 100
    step = manifest.load_reader("selective_step_roofline").read(obs)
    least = 10 * 9 * kind.selective_step_cost(cfg)["bytes"] / 819e9
    assert step == pytest.approx(100.0 * least / (10 * 9 * 1e-4))
    assert 80 < step < 100
    scan = manifest.load_reader("selective_scan_roofline").read(obs)
    cost = kind.selective_scan_cost(cfg, 256)
    one = max(cost["bytes"] / 819e9, cost["flops"] / 197e12)
    assert scan == pytest.approx(100.0 * one / 1e-3)
    assert 0.5 < scan < 10


def test_the_scan_reader_counts_each_traced_loop_for_its_own_width():
    """A trace whose chunks are narrow is counted for what it held."""
    t = _Trace()
    t.ops = {t.DEV: [(s, e, text.replace("f32[32,8,1,", "f32[64,1,"))
                     for s, e, text in t.ops[t.DEV]]}
    obs = dict(_obs(), trace=t, peaks=PEAKS)
    cost = kind.selective_scan_cost(obs["cfg"], 64)
    one = max(cost["bytes"] / 819e9, cost["flops"] / 197e12)
    assert manifest.load_reader("selective_scan_roofline").read(obs) \
        == pytest.approx(100.0 * one / 1e-3)


@pytest.mark.parametrize("name", ["shared_kv_decode_roofline",
                                  "selective_step_roofline",
                                  "selective_scan_roofline"])
def test_a_roofline_reader_finds_nothing_where_nothing_is(name):
    """No trace, a trace with no such operation (the parent commit's
    program, or another kind's), a kind without the cost function, a
    program without the counters: None, and nothing raised."""
    reader = manifest.load_reader(name)
    assert reader.read(dict(_obs(), trace=None)) is None
    empty = _Trace()
    empty.ops = {empty.DEV: [e for e in empty.ops[empty.DEV]
                             if "5120" not in e[2]]}
    assert reader.read(dict(_obs(), trace=empty, peaks=PEAKS)) is None
    other = dict(_obs(), kind="decoder_lm", trace=_Trace(), peaks=PEAKS)
    assert reader.read(other) is None
    if name == "shared_kv_decode_roofline":
        for key in ("full_row_readers", "decode_positions_live",
                    "decode_steps"):
            assert reader.read(dict(_obs(drop=(key,)), trace=_Trace(),
                                    peaks=PEAKS)) is None
