"""``correct`` has to come out false when the timed path is broken, and
when a lower precision stands in the program's place.

Both tests drive the harness's own run (``run.main`` with the tiny
rehearsal configurations, which skips only the look for a chip) and read
the result line it prints.
"""
import json
import os

import numpy as np
import pytest

import run

REHEARSAL = os.path.join(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))), "rehearsal")


def result_of(capsys, workload, seconds="2", seed="2345678901", control=""):
    run.main(["--workload", workload, "--seed", seed, "--seconds", seconds,
              "--trace", "0", "--control", control], rehearsal_dir=REHEARSAL)
    lines = capsys.readouterr().out.strip().splitlines()
    return json.loads(lines[-1]), lines


def tagged(lines, tag):
    return [json.loads(l.split("] ", 1)[1]) for l in lines
            if l.startswith(f"[{tag}] ")]


def test_sound_training_run_is_correct(capsys):
    line, _ = result_of(capsys, "resnet50_train_b256")
    assert line["correct"] is True and line["attempted"] > 0


def test_step_that_returns_its_state_unchanged_is_not_correct(capsys, monkeypatch):
    from bigdl_tpu.optim import methods
    monkeypatch.setattr(methods.SGD, "update",
                        lambda self, grads, params, state, epoch=0:
                        (params, dict(state, t=state["t"] + 1)))
    line, lines = result_of(capsys, "resnet50_train_b256")
    assert line["correct"] is False
    # the first loss is still right; the later ones and the leaves that
    # did not move give it away
    by_name = {c["number"]: c for c in tagged(lines, "correct")}
    assert by_name["loss_step1_gap"]["ok"] and not by_name["loss_step3_gap"]["ok"]
    assert by_name["update_diff"]["value"] == 1.0 and not by_name["update_diff"]["ok"]


@pytest.mark.parametrize("seed", ["11", "12", "13"])
def test_lower_precision_in_the_programs_place_fails_a_training_limit(capsys, seed):
    """The control at the rehearsal's size: the reference with every
    matrix product's operands rounded to int8, both ways, ends the first
    dispatch window further from the float32 reference than the limit
    allows; the program, in bfloat16, stays inside it.  (The limit here
    is this size's own; the cell's is set from chip readings, PERF.md
    section 2.)"""
    line, lines = result_of(capsys, "resnet50_train_b256", seed=seed,
                            control="int8")
    limit = {c["number"]: c["limit"] for c in tagged(lines, "correct")}["update_diff"]
    sound, = tagged(lines, "sound")
    low, = tagged(lines, "control.int8")
    assert line["correct"] is True
    assert sound["update_diff"] <= limit < low["update_diff"], (sound, low)


def test_part_of_the_batch_left_out_is_not_correct(capsys, monkeypatch):
    from harness.kinds import resnet

    real = resnet.batch

    def half(cfg, job, seed):
        x, y = real(cfg, job, seed)
        n = x.shape[0] // 2
        # the program trains on the first half twice; the reference, which
        # calls the unbroken generator first, on the whole batch
        if half.calls:
            x = x.at[n:].set(x[:n])
            y = y.at[n:].set(y[:n])
        half.calls += 1
        return x, y
    half.calls = 0
    monkeypatch.setattr(resnet, "batch", half)
    line, _ = result_of(capsys, "resnet50_train_b256")
    assert line["correct"] is False


def test_sound_serving_run_is_correct(capsys):
    line, _ = result_of(capsys, "opt1b3_serve_chat", "6")
    assert line["correct"] is True and line["failed"] == 0


def test_token_altered_where_it_is_produced_is_not_correct(capsys, monkeypatch):
    from bigdl_tpu.serving.generation import SlotPool
    real = SlotPool.read_emit_masked

    def altered(self, handle):
        out, credit = real(self, handle)
        out = np.asarray(out).copy()
        # every 7th emitted token becomes its neighbour in the vocabulary
        altered.n += 1
        if altered.n % 7 == 0:
            out = np.where(out > 0, out % 400 + 1, out)
        return out, credit
    altered.n = 0
    monkeypatch.setattr(SlotPool, "read_emit_masked", altered)
    line, _ = result_of(capsys, "opt1b3_serve_chat", "6")
    assert line["correct"] is False


def test_lower_precision_in_the_programs_place_fails_a_serving_limit(capsys):
    """The control at a size a test can hold (4 layers of width 256,
    where logits are smaller than at the cell's size, so the limit here
    is this size's own; the cell's is set from chip readings, PERF.md
    section 2), through the check a run makes, block by block.  The
    reference computed in int8, the precision below bfloat16 that this
    repo has a path for, picks tokens whose reference logit lies further
    below the best than the limit allows; bfloat16's own picks stay
    inside it, with a factor of three to spare."""
    from harness import serve_cell
    from harness.kinds import decoder_lm as kind
    limit = 0.01
    cfg = {"vocab_size": 4000, "hidden_size": 256, "num_hidden_layers": 4,
           "num_attention_heads": 4, "ffn_dim": 1024,
           "serving": {"weights_dtype": "bfloat16", "max_len": 256},
           "correct": {"serve": {"logit_gap_max": limit}}}
    mix = {"check_requests": 1, "new_tokens": {"max": 128}}
    got = {"bfloat16": [], "int8": []}
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
            into.append(tagged(lines, "control")[0]["control_gap_max"])
    assert 3 * max(got["bfloat16"]) <= limit < min(got["int8"]), (got, limit)
