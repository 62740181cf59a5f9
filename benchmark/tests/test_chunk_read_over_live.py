"""The reader of ``chunk_read_over_live``: the scheduler's two counters of
the positions its prefill chunks attend as differences across the window,
and nothing where a program has no such counters or sent no chunk."""
import importlib

import pytest


def _read(obs):
    return importlib.import_module(
        "layer_metrics.chunk_read_over_live").read(obs)


def _stats(live, read):
    out = {"decode_dispatches": 10}
    if live is not None:
        out["chunk_positions_live"] = live
    if read is not None:
        out["chunk_positions_read"] = read
    return out


@pytest.mark.parametrize("before,after,want", [
    # chunks of 256 at 0, 256 and 512 of a row read in blocks of 256
    ((100, 100), (100 + 1536, 100 + 1536), 1.0),
    # the same three chunks, each reading its slot's row of 6,144 whole
    ((0, 0), (1536, 3 * 6144), 12.0),
    # a last chunk of 32 at 1,000: 1,032 live, 1,280 read
    ((0, 0), (1032, 1280), 1280 / 1032),
])
def test_ratio_of_the_counters_differences(before, after, want):
    obs = {"stats0": _stats(*before), "stats1": _stats(*after)}
    assert _read(obs) == pytest.approx(want)


@pytest.mark.parametrize("obs", [
    {},                                                    # no snapshots
    {"stats0": _stats(None, None), "stats1": _stats(None, None)},
    {"stats0": _stats(1, 1), "stats1": _stats(None, 9)},   # one side only
    {"stats0": _stats(7, 7), "stats1": _stats(7, 7)},      # no chunk sent
])
def test_none_where_the_counters_are_absent_or_still(obs):
    assert _read(obs) is None


def test_the_metric_is_declared_as_the_file_says():
    from harness import manifest
    mod = importlib.import_module("layer_metrics.chunk_read_over_live")
    entry = [m for m in manifest.manifest()["per_layer"]
             if m["name"] == "chunk_read_over_live"]
    assert len(entry) == 1
    assert (entry[0]["layer"], entry[0]["source"], entry[0]["moves"]) == \
        (mod.LAYER, mod.SOURCE, mod.MOVES)
    assert entry[0]["workloads"] == [
        "mimo25_serve_rollouts", "falconh1_serve_answers", "lfm2_serve_tools",
        "trinitymini_serve_mixed"]
