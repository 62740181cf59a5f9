"""The trace reduction on a small trace recorded on a v5e chip by
``record_trace.py``: six executions of a jitted chain of four
matmul+tanh fusions (about 50 us each), a 20 ms host pause after the
third."""
import os

import pytest

from harness import trace as tr

PATH = os.path.join(os.path.dirname(__file__), "data", "probe_1chip.xplane.pb")


@pytest.fixture(scope="module")
def t():
    return tr.Trace(PATH)


def test_categories():
    text = ("%convolution_tanh_fusion.3 = bf16[1024,1024]{1,0:T(8,128)(2,1)S(1)} "
            "fusion(bf16[1024,1024]{1,0:T(8,128)(2,1)} %x.1), kind=kOutput, "
            "calls=%fused_computation.3")
    assert tr.hlo_category(text) == "convolution_fusion"
    assert tr.hlo_category(
        "%fusion.7 = f32[8]{0} fusion(f32[8]{0} %a), kind=kLoop, calls=%f") \
        == "loop_fusion"
    assert tr.hlo_category(
        "%copy-start = (bf16[4]{0:S(1)}, bf16[4]{0}, u32[]{:S(2)}) "
        "copy-start(bf16[4]{0} %w.1)") == "copy-start"
    assert tr.hlo_category(
        '%c.1 = bf16[8]{0} custom-call(bf16[8]{0} %a), '
        'custom_call_target="tpu_custom_call"') == "tpu_custom_call"
    assert tr.hlo_category(
        "%all-reduce.5 = f32[8]{0} all-reduce(f32[8]{0} %a), replica_groups={}") \
        == "all-reduce"
    assert tr.is_collective("all-reduce-start", "%all-reduce-start.1 = ...")
    assert not tr.is_collective("loop_fusion", "%fusion.7 = ...")


def test_busy_and_idle(t):
    assert t.devices() == ["/device:TPU:0"]
    # six executions of ~50.5 us; the device is idle for the rest
    secs, n = tr.Trace(PATH).module_seconds("jit_probe_step")
    assert n in (5, 6) and secs / n == pytest.approx(50.5e-6, rel=0.02)
    assert t.busy_s() == pytest.approx(6 * 50.5e-6, rel=0.03)
    assert 0.02 < t.window_s < 0.03
    assert 1 - t.busy_s() / t.window_s > 0.98


def test_categories_of_the_recording(t):
    cats = t.category_seconds()
    assert max(cats, key=cats.get) == "convolution_fusion"
    assert cats["convolution_fusion"] == pytest.approx(t.busy_s(), rel=0.01)


def test_idle_gaps_go_to_the_host_span_that_covers_them(t):
    gaps = t.idle_gaps()
    # the 20 ms pause is the longest gap and lies under bench.pause
    assert max(gaps, key=gaps.get) == "bench.pause"
    assert gaps["bench.pause"] == pytest.approx(0.02, rel=0.1)
    assert sum(gaps.values()) == pytest.approx(t.window_s - t.busy_s(), rel=1e-6)


def test_no_collective_on_one_chip(t):
    assert t.collective_exposed_s() == 0.0


def test_interval_arithmetic():
    a = tr.union([(0, 2), (1, 3), (5, 6)])
    assert a == [(0, 3), (5, 6)]
    assert tr.subtract(a, [(1, 2), (2.5, 5.5)]) == [(0, 1), (2, 2.5), (5.5, 6)]
    # exposed collective time: the collective spans [0, 4], compute
    # covers [1, 2] and [3, 5]: exposed 2 of its 4 seconds
    assert tr.measure(tr.subtract([(0, 4)], [(1, 2), (3, 5)])) == 2
    assert tr.clip([(0, 10)], 2, 3) == [(2, 3)]
