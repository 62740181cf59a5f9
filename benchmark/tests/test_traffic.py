import glob
import os

import numpy as np
import pytest

from harness import manifest, traffic

SPEC = manifest.traffic_of("chat_poisson")

# a serving cell's rate is a stated multiple of a knee measured by sweep.py:
# 0.8 where the tails are judged, 1.5 where the queue is to stay full.  A
# new serving mix takes a row here.
KNEE_MULTIPLE = {"chat_poisson": 0.8, "docs_saturated": 1.5,
                 "rollouts_saturated": 1.5}
SERVE_MIXES = sorted(
    name for name in (os.path.basename(p)[:-len(".json")] for p in glob.glob(
        os.path.join(manifest.BENCH_DIR, "traffic", "*.json")))
    if manifest.traffic_of(name)["kind"] == "serve")


@pytest.mark.parametrize("name", SERVE_MIXES)
def test_rate_is_the_stated_multiple_of_the_knee(name):
    spec = manifest.traffic_of(name)
    assert name in KNEE_MULTIPLE, f"{name}: no multiple of its knee stated"
    assert spec["rate_rps"] == pytest.approx(
        KNEE_MULTIPLE[name] * spec["knee_rps"], rel=1e-6)
    assert spec["knee_from"]          # where the knee was measured


def lengths(reqs):
    return (sorted(len(r["prompt"]) for r in reqs),
            sorted(r["new_tokens"] for r in reqs))


def test_same_seed_same_requests():
    a = traffic.generate(SPEC, 3_000_000_019, 120.0, 50272)
    b = traffic.generate(SPEC, 3_000_000_019, 120.0, 50272)
    assert [r["due"] for r in a] == [r["due"] for r in b]
    assert all(np.array_equal(x["prompt"], y["prompt"]) for x, y in zip(a, b))
    assert [r["new_tokens"] for r in a] == [r["new_tokens"] for r in b]


def test_other_seed_same_multiset_other_order():
    a = traffic.generate(SPEC, 1, 120.0, 50272)
    b = traffic.generate(SPEC, 2, 120.0, 50272)
    assert lengths(a) == lengths(b)
    assert [len(r["prompt"]) for r in a] != [len(r["prompt"]) for r in b]
    assert [r["due"] for r in a] != [r["due"] for r in b]


def test_lengths_follow_the_file():
    reqs = traffic.generate(SPEC, 5, 400.0, 50272)
    p = np.array([len(r["prompt"]) for r in reqs])
    o = np.array([r["new_tokens"] for r in reqs])
    assert p.min() >= SPEC["prompt_tokens"]["min"]
    assert p.max() <= SPEC["prompt_tokens"]["max"]
    assert abs(np.median(p) - SPEC["prompt_tokens"]["median"]) <= 8
    assert abs(np.median(o) - SPEC["new_tokens"]["median"]) <= 4
    assert all(r["prompt"].min() >= 1 and r["prompt"].max() <= 50272
               for r in reqs)


def test_mean_rate_and_poisson_gaps():
    reqs = traffic.generate(SPEC, 7, 2000.0, 50272)
    due = np.array([r["due"] for r in reqs])
    n = len(reqs)
    assert n == round(SPEC["rate_rps"] * 2000.0)
    assert due[0] == 0.0 and np.all(np.diff(due) >= 0)
    # the whole run spans n - 1 gaps of mean 1 / rate
    assert abs(due[-1] / (n - 1) * SPEC["rate_rps"] - 1.0) < 1e-9
    assert due[-1] < 2000.0
    gaps = np.diff(due)
    # exponential gaps: coefficient of variation 1
    assert abs(np.std(gaps) / np.mean(gaps) - 1.0) < 0.1


def test_bursty_gaps_keep_the_rate():
    spec = dict(SPEC, arrivals="gamma", cv=3.0)
    reqs = traffic.generate(spec, 7, 4000.0, 50272)
    gaps = np.diff([r["due"] for r in reqs])
    assert abs(np.mean(gaps) * SPEC["rate_rps"] - 1.0) < 0.01
    assert np.std(gaps) / np.mean(gaps) > 2.0


def test_every_stretch_of_a_run_carries_the_whole_mix():
    rng = np.random.default_rng(3)
    order = traffic.balanced_permutation(rng, 60, strata=6)
    assert sorted(order) == list(range(60))
    for i in range(0, 60, 6):
        assert sorted(v * 6 // 60 for v in order[i:i + 6]) == list(range(6))
    assert list(order) != list(traffic.balanced_permutation(
        np.random.default_rng(4), 60, strata=6))


STRATIFIED = dict(SPEC, arrivals="poisson_stratified", order="drawn")


def test_stratified_gaps_are_the_same_multiset_for_every_seed():
    a = traffic.arrival_gaps(np.random.default_rng(1), 198, 3.6, STRATIFIED)
    b = traffic.arrival_gaps(np.random.default_rng(2), 198, 3.6, STRATIFIED)
    assert sorted(a) == sorted(b) and list(a) != list(b)
    # the exponential's mid-point quantiles: its mean (less the clipped tail
    # beyond the last quantile) and its coefficient of variation
    assert abs(np.mean(a) * 3.6 - 1.0) < 0.02
    assert abs(np.std(a) / np.mean(a) - 1.0) < 0.1
    # any six consecutive gaps hold one of each sextile: 6 arrivals take
    # about 6 / rate seconds wherever a window cuts the run
    # (but for the shortest gap, which goes first: generate() leaves the
    # first gap out)
    assert a[0] == min(a) and b[0] == min(b)
    rank = np.argsort(np.argsort(a))
    for i in range(6, 198, 6):
        got = sorted(r * 6 // 198 for r in rank[i:i + 6])
        assert got == list(range(6)) or 0 not in got   # 0 went to the front


def test_stratified_run_has_poissons_count_and_horizon():
    spec = dict(STRATIFIED, rate_rps=3.6)
    plain = traffic.generate(dict(spec, arrivals="poisson"), 11, 57.0, 50272)
    strat = traffic.generate(spec, 11, 57.0, 50272)
    other = traffic.generate(spec, 12, 57.0, 50272)
    assert len(strat) == len(plain) == round(3.6 * 57.0)
    assert lengths(strat) == lengths(plain) == lengths(other)
    for reqs in (plain, strat, other):
        due = [r["due"] for r in reqs]
        assert due[0] == 0.0 and abs(due[-1] - (len(reqs) - 1) / 3.6) < 1e-9
    # the same gaps in another order: the gap that the first arrival at
    # 0 leaves out is the shortest in every seed
    ga = np.sort(np.diff([r["due"] for r in strat]))
    gb = np.sort(np.diff([r["due"] for r in other]))
    assert np.allclose(ga, gb, rtol=0, atol=1e-12)


@pytest.mark.parametrize("spec", [{}, {"arrivals": "poisson"},
                                  {"arrivals": "gamma", "cv": 3.0}])
def test_default_branches_give_what_they_gave(spec):
    """``poisson`` (the default) and ``gamma`` draw exactly as before the
    stratified branch came in: one call of the generator, the same one."""
    got = traffic.arrival_gaps(np.random.default_rng([7, 0x7261]), 5, 2.0, spec)
    rng = np.random.default_rng([7, 0x7261])
    want = (rng.gamma(1 / 9.0, 0.5 * 9.0, 5) if "cv" in spec
            else rng.exponential(0.5, 5))
    assert list(got) == list(want)


ROWS_MIXES = [name for name in SERVE_MIXES
              if manifest.traffic_of(name).get("order") == "rows"]


@pytest.mark.parametrize("name", ROWS_MIXES)
def test_rows_give_every_seed_the_same_pairs_and_gaps(name):
    """``"order": "rows"``: the same (prompt, answer) pairs and the same
    gaps for every seed, as many requests over the same span as a drawn
    order gives, and another order."""
    spec = manifest.traffic_of(name)
    horizon = spec["preroll_s"] + 51.0
    a = traffic.generate(spec, 3_300_000_601, horizon, 50272)
    b = traffic.generate(spec, 2 ** 31 + 17, horizon, 50272)
    drawn = traffic.generate(dict(spec, order="drawn"), 5, horizon, 50272)
    pairs = lambda reqs: [(len(r["prompt"]), r["new_tokens"]) for r in reqs]
    assert sorted(pairs(a)) == sorted(pairs(b)) and pairs(a) != pairs(b)
    assert lengths(a) == lengths(drawn) and len(a) == len(drawn)
    assert a[0]["due"] == 0.0 and a[-1]["due"] == pytest.approx(drawn[-1]["due"])
    # the gap the first arrival leaves out is the shortest of the row that
    # leads: all the others are the same to within the rescaling, a few
    # parts in 10,000
    ga, gb = (np.diff([r["due"] for r in reqs]) for reqs in (a, b))
    near = np.abs(ga[:, None] / gb[None, :] - 1.0).min(axis=1) < 5e-4
    assert near.sum() >= len(ga) - 1 and ga[~near].sum() < 0.03
    # the pairs are as good as independent: the live places of a run
    # follow the sum of prompt times answer
    p, o = (np.array(v, float) for v in zip(*pairs(a)))
    assert abs((p * o).sum() / (p.mean() * o.mean() * len(a)) - 1.0) < 0.06


@pytest.mark.parametrize("n", [205, 315, 36, 7])
def test_a_row_holds_every_combination_of_length_groups_once(n):
    rows = traffic.paired_rows(n, design=1)
    flat = np.concatenate(rows)
    assert sorted(flat[:, 0]) == sorted(flat[:, 1]) == list(range(n))
    group = lambda place: np.searchsorted(
        [n * g // 6 for g in range(1, 6)], place, side="right")
    for row in rows:
        assert len(row) <= 36
        # whole blocks (the last row may hold a part of one, anywhere)
        for i in range(0, len(row) if len(row) % 6 == 0 else 0, 6):
            assert sorted(group(row[i:i + 6, 0])) == list(range(6))
            assert sorted(group(row[i:i + 6, 1])) == list(range(6))
        if len(row) == 36:
            assert len({(group(p), group(o)) for p, o in row}) == 36
    other = np.concatenate(traffic.paired_rows(n, design=2))
    assert other.tolist() != flat.tolist()


def test_the_seed_orders_whole_rows_and_nothing_else():
    spec = manifest.traffic_of("chat_poisson")
    n = round(spec["rate_rps"] * 57.0)
    p = spec["prompt_tokens"]
    prompts = traffic.stratified_lognormal(n, p["median"], p["sigma"],
                                           p["min"], p["max"])
    rows = [list(prompts[row[:, 0]])
            for row in traffic.paired_rows(n, spec["design"])]
    seen = set()
    for seed in (1, 2, 3, 4):
        got = [len(r["prompt"]) for r in traffic.generate(spec, seed, 57.0, 50272)]
        order = []
        while got:
            k = next(i for i, row in enumerate(rows) if got[:len(row)] == row
                     and i not in order)
            order.append(k)
            got = got[len(rows[k]):]
        assert sorted(order) == list(range(len(rows)))
        seen.add(tuple(order))
    assert len(seen) > 1


def test_a_drawn_order_gives_what_it_gave():
    """Without ``order`` (``rollouts_saturated``) the generator draws as it
    did before rows came in: the two length orders, then the gaps, then the
    tokens, from the one generator."""
    spec = manifest.traffic_of("rollouts_saturated")
    assert "order" not in spec and spec["arrivals"] == "poisson"
    reqs = traffic.generate(spec, 7, 40.0, 1000)
    n = round(spec["rate_rps"] * 40.0)
    rng = np.random.default_rng([7, 0x7261])
    p, o = spec["prompt_tokens"], spec["new_tokens"]
    prompts = traffic.stratified_lognormal(
        n, p["median"], p["sigma"], p["min"], p["max"])[
            traffic.balanced_permutation(rng, n)]
    outs = traffic.stratified_lognormal(
        n, o["median"], o["sigma"], o["min"], o["max"])[
            traffic.balanced_permutation(rng, n)]
    gaps = rng.exponential(1.0 / spec["rate_rps"], n)
    due = np.cumsum(gaps) - gaps[0]
    due *= ((n - 1) / spec["rate_rps"]) / due[-1]
    assert [len(r["prompt"]) for r in reqs] == list(prompts)
    assert [r["new_tokens"] for r in reqs] == list(outs)
    assert np.allclose([r["due"] for r in reqs], due, rtol=0, atol=1e-12)
    assert list(reqs[0]["prompt"]) == list(
        rng.integers(1, 1001, int(prompts[0])).astype(np.int32))
