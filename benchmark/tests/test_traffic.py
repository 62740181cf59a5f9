import numpy as np

from harness import manifest, traffic

SPEC = manifest.traffic_of("chat_poisson")


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
