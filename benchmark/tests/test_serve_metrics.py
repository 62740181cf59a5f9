import pytest

from harness import serve_metrics as sm


def req(submit, stamps, prompt_len=100, calls=2):
    return {"due": submit, "submit": submit, "prompt_len": prompt_len,
            "prefill_calls": calls, "stamps": list(stamps)}


def test_percentile_is_linear_interpolation():
    v = [1.0, 2.0, 3.0, 4.0, 5.0]
    assert sm.percentile(v, 50) == 3.0
    assert sm.percentile(v, 95) == pytest.approx(4.8)
    assert sm.percentile([7.0], 95) == 7.0
    with pytest.raises(ValueError):
        sm.percentile([], 50)


def test_gaps_count_by_where_they_end():
    # window [10, 20): a request straddling the opening edge, one inside,
    # one straddling the closing edge
    a = req(8.0, [9.0, 10.5, 11.0])          # gaps end 10.5, 11.0: both in
    b = req(12.0, [13.0, 13.1])              # one gap in
    c = req(18.0, [19.5, 20.0, 20.5])        # gaps end at 20.0, 20.5: out
    gaps = sm.gaps_in_window([a, b, c], 10.0, 20.0)
    assert sorted(round(g, 6) for g, _ in gaps) == [0.1, 0.5, 1.5]


def test_tokens_counted_one_by_one():
    its = [10.0 + 0.1 * i for i in range(100)]
    a = req(9.0, its[5:50], prompt_len=200, calls=2)
    base = sm.tokens_in_window([a], 10.0, 14.0)
    # one more token emitted inside the window: exactly one token more
    b = req(9.0, its[5:50] + [13.95], prompt_len=200, calls=2)
    more = sm.tokens_in_window([b], 10.0, 14.0)
    assert more["generated"] - base["generated"] == 1.0
    assert more["prompt"] == pytest.approx(base["prompt"])


def test_prompt_tokens_spread_over_the_prefill_interval():
    # iterations every 0.1 s from other requests; this one's first token
    # comes at 10.3 after 2 prefill calls: prefilled during [10.1, 10.3]
    clock = req(0.0, [10.0 + 0.1 * i for i in range(10)], prompt_len=1, calls=1)
    a = req(9.0, [10.3, 10.4], prompt_len=200, calls=2)
    its = sm.iterations([clock, a])
    assert sm.prefill_interval(a, its) == pytest.approx((10.1, 10.3))
    # a window that opens at 10.2 holds half of the prompt's tokens
    t = sm.tokens_in_window([clock, a], 10.2, 20.0)
    assert t["prompt"] == pytest.approx(100.0, rel=1e-6)
    assert t["generated"] == 2.0 + 8.0      # its own two, the clock's eight
    # moving the edge by one iteration moves the count by one call's share
    t2 = sm.tokens_in_window([clock, a], 10.1, 20.0)["prompt"] \
        - sm.tokens_in_window([clock, a], 10.2, 20.0)["prompt"]
    assert 0 < t2 <= 200 / 2 + 1 + 1e-6


def test_prefill_never_starts_before_submission():
    a = req(10.25, [10.3, 10.4], prompt_len=50, calls=5)
    clock = req(0.0, [10.0 + 0.1 * i for i in range(10)], prompt_len=1, calls=1)
    s, e = sm.prefill_interval(a, sm.iterations([clock, a]))
    assert s == 10.25 and e == 10.3


def test_iterations_merge_the_stamps_of_one_step():
    a = req(0.0, [1.0, 1.06, 1.12])
    b = req(0.0, [1.0004, 1.0603])
    its = sm.iterations([a, b])
    assert its == [1.0, 1.06, 1.12]
    flags = sm.prefill_iterations(
        [req(0.0, [1.12], prompt_len=10, calls=1), a, b], its)
    # a and b got their first token in iteration 0, the third request in 2
    assert flags == [True, False, True]


@pytest.mark.parametrize("first_token_after_close, counted", [
    (None, 0.0),      # the engine was stopped at the close: nothing to place
    (20.2, 100.0)])   # it ran on until the first token came (close_grace_s)
def test_a_prompt_in_prefill_at_the_close_counts_once_it_has_a_stamp(
        first_token_after_close, counted):
    # window [10, 20); another request's tokens give the passes their stamps,
    # one every 0.1 s; the prompt's 4 calls run from 19.8 to 20.2, half inside
    other = req(5.0, [10.0 + 0.1 * i for i in range(-20, 110)], calls=1)
    stamps = [] if first_token_after_close is None else [first_token_after_close]
    late = req(12.0, stamps, prompt_len=200, calls=4)
    got = sm.tokens_in_window([other, late], 10.0, 20.0)["prompt"] \
        - sm.tokens_in_window([other], 10.0, 20.0)["prompt"]
    assert got == pytest.approx(counted, abs=1e-6)
