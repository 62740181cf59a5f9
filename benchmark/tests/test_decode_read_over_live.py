"""The reader of ``decode_read_over_live``: the scheduler's two counters of
cache positions as differences across the window, and nothing where a
program has no such counters (a parent commit; a model whose engine does
not count them)."""
import importlib

import pytest


def _read(obs):
    return importlib.import_module(
        "layer_metrics.decode_read_over_live").read(obs)


def _stats(live, read):
    out = {"decode_dispatches": 10}
    if live is not None:
        out["decode_positions_live"] = live
    if read is not None:
        out["decode_positions_read"] = read
    return out


@pytest.mark.parametrize("before,after,want", [
    # a step that reads live key blocks of 256: rounding only
    ((1000, 1500), (23000, 28300), (28300 - 1500) / (23000 - 1000)),
    # every slot's whole row, 6 x 2,048 a step, whatever is live
    ((0, 0), (22000, 10 * 12288), 10 * 12288 / 22000),
    # exactly what was live
    ((5, 5), (105, 105), 1.0),
])
def test_ratio_of_the_counters_differences(before, after, want):
    obs = {"stats0": _stats(*before), "stats1": _stats(*after)}
    assert _read(obs) == pytest.approx(want)


@pytest.mark.parametrize("obs", [
    {},                                                    # no snapshots
    {"stats0": _stats(None, None), "stats1": _stats(None, None)},  # a parent
    {"stats0": _stats(1, 1), "stats1": _stats(None, 9)},   # one side only
    {"stats0": _stats(7, 7), "stats1": _stats(7, 7)},      # no step decoded
])
def test_none_where_the_counters_are_absent_or_still(obs):
    assert _read(obs) is None


def test_the_metric_is_declared_as_the_file_says():
    import json
    import os
    from harness import manifest
    mod = importlib.import_module("layer_metrics.decode_read_over_live")
    with open(os.path.join(manifest.ROOT, "BENCHMARK.json")) as f:
        entry = [m for m in json.load(f)["per_layer"]
                 if m["name"] == "decode_read_over_live"]
    assert len(entry) == 1
    assert (entry[0]["layer"], entry[0]["source"], entry[0]["moves"]) == \
        (mod.LAYER, mod.SOURCE, mod.MOVES)
    assert entry[0]["workloads"] == ["opt1b3_serve_chat", "opt1b3_serve_docs"]
