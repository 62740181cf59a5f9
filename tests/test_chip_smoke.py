"""chip_smoke.py rehearsed on the CPU (on-chip-measurement guide §2.1,
§2.2): the same phases the chip runs, at a tiny size, with the Pallas
kernels in interpret mode — asked for here by argument, since the script
itself has one behaviour and no switch.  What this finds is wrong paths,
arguments and control flow; what the kernels compute compiled, and
whether the real sizes fit, only the chip says.
"""

import dataclasses
import json

import jax
import pytest

import chip_smoke

TINY = dataclasses.replace(
    chip_smoke.REAL,
    resnet_layers=(1, 1), classes=10, image=32, batch=8,
    steps_per_window=2, windows=2, fused_steps=2,
    flash_shape=(1, 2, 128, 64),
    lm_vocab=64, lm_hidden=32, lm_layers=1, lm_heads=2, lm_seq=128,
    lm_batch=8, lm_steps=2,
    serve_filter=64, serve_max_len=32, serve_slots=2,
    serve_prompt_lens=(3, 9), serve_new_tokens=4,
    dp_steps=2, plan_lm_steps=2)


@pytest.fixture
def cpu():
    """Like the script, the phases get every device JAX reports: here
    the eight virtual CPU devices, which the Optimizer's default mesh
    spreads the batch over."""
    devices = jax.devices()
    return devices, chip_smoke.device_tag(devices)


def _lines(capsys, phase):
    out = capsys.readouterr().out.splitlines()
    hits = [l for l in out if l.startswith(f"[{phase}")]
    assert hits, out
    assert all(" on cpu/cpu x" in l for l in hits), hits
    return hits


def test_train_phase(cpu, capsys):
    devices, tag = cpu
    chip_smoke.phase_train(TINY, devices, tag)
    (line,) = _lines(capsys, "train")
    assert "steps=4" in line and "compiles=1" in line
    assert "ms_per_step_block_until_ready=" in line
    assert "ms_per_step_readback=" in line


def test_kernels_phase_in_interpret_mode(cpu, capsys, monkeypatch):
    # the LM picks its attention path from the backend; steer it to the
    # flash kernel the way a user on the CPU would
    monkeypatch.setenv("BIGDL_TPU_ATTENTION", "flash")
    devices, tag = cpu
    chip_smoke.phase_kernels(TINY, devices, tag, interpret=True)
    flash, lm, fused = _lines(capsys, "kernels")
    assert flash.startswith("[kernels.flash]") and "rel_err_vs_xla" in flash
    assert lm.startswith("[kernels.lm]") and "losses=[" in lm
    assert fused.startswith("[kernels.fused]") and "update_rel_err" in fused


def test_serve_phase(cpu, capsys):
    devices, tag = cpu
    chip_smoke.phase_serve(TINY, devices, tag)
    (line,) = _lines(capsys, "serve")
    assert "requests=2" in line and "equal_to_generate=2" in line
    assert "new_tokens=8" in line and "decode_traces=1" in line


def test_four_chip_phase_on_virtual_devices(capsys):
    devices = jax.devices()
    chip_smoke.phase_four_chips(TINY, devices,
                                chip_smoke.device_tag(devices[:4]))
    dp, lm = _lines(capsys, "chips4")
    assert dp.startswith("[chips4.resnet_dp4]") and "all-reduce" in dp
    assert lm.startswith("[chips4.lm_fsdp2_tp2]") and "all-gather" in lm
    for line in (dp, lm):
        assert "shard_device_ids=[0,1,2,3]" in line


def test_a_failed_check_raises():
    with pytest.raises(RuntimeError, match="chip_smoke: no good"):
        chip_smoke.check(False, "no good")
    chip_smoke.check(True, "fine")


@pytest.mark.parametrize("argv", [[], ["--chips", "4"]])
def test_main_fails_without_a_tpu(argv, capsys):
    """Run on anything but a TPU the script raises (a non-zero exit) and
    prints no result line — before it turns the compile cache on or
    builds anything."""
    with pytest.raises(RuntimeError, match="needs a TPU"):
        chip_smoke.main(argv)
    assert '"ok"' not in capsys.readouterr().out
    assert jax.config.jax_compilation_cache_dir is None


class _Chip:
    platform = "tpu"
    device_kind = "TPU v5 lite"


@pytest.mark.parametrize("count", [1, 4])
def test_last_line_shape(count):
    line = chip_smoke.final_line([_Chip()] * count)
    assert "\n" not in line
    assert json.loads(line) == {"ok": True, "device": {
        "platform": "tpu", "kind": "TPU v5 lite", "count": count}}
    assert line == ('{"ok": true, "device": {"platform": "tpu", '
                    '"kind": "TPU v5 lite", "count": %d}}' % count)


def test_device_phase_names_versions(capsys):
    tag = chip_smoke.phase_device([_Chip()])
    assert tag == "tpu/TPU v5 lite x1"
    (line,) = [l for l in capsys.readouterr().out.splitlines()
               if l.startswith("[device]")]
    for word in (f"jax={jax.__version__}", "jaxlib=", "libtpu=",
                 "peak_bf16_flops=1.97e+14", "hbm_bytes_per_s=8.19e+11"):
        assert word in line, line


def test_device_phase_refuses_cpu_and_unknown_tpu():
    with pytest.raises(RuntimeError, match="needs a TPU"):
        chip_smoke.phase_device(jax.devices()[:1])

    class Unknown(_Chip):
        device_kind = "TPU v9 hyper"
    with pytest.raises(ValueError, match="unknown TPU device_kind"):
        chip_smoke.phase_device([Unknown()])
