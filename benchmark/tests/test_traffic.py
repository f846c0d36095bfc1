"""The generator's schedule is a function of (parameters, seconds, seed)
alone, and every seed offers the same work in another order."""

from benchmark import traffic

PARAMS = {"arrivals": {"process": "stratified_exponential", "rate_per_s": 40.0},
          "images_per_request": {"1": 0.5, "2": 0.2, "4": 0.15, "8": 0.1,
                                 "16": 0.05}}


def test_same_seed_same_schedule():
    assert traffic.schedule(PARAMS, 30, 2**31 + 9) == traffic.schedule(
        PARAMS, 30, 2**31 + 9)


def test_seeds_differ_in_order_only():
    a = traffic.schedule(PARAMS, 30, 1)
    b = traffic.schedule(PARAMS, 30, 2**31 + 5)
    assert [r.due_s for r in a] != [r.due_s for r in b]
    assert sorted(r.images for r in a) == sorted(r.images for r in b)
    assert len(a) == len(b) == 1200
    gaps = lambda s: sorted(round(y.due_s - x.due_s, 9)  # noqa: E731
                            for x, y in zip(s, s[1:]))
    assert sum(r.images for r in a) == 3720  # 1200 x 3.1
    assert abs(gaps(a)[len(a) // 2] - gaps(b)[len(b) // 2]) < 1e-3


def test_arrivals_fill_the_window_at_the_stated_rate():
    s = traffic.schedule(PARAMS, 30, 7)
    assert 0.0 <= s[0].due_s < 0.2 and 29.5 < s[-1].due_s <= 30.0
    assert all(y.due_s >= x.due_s for x, y in zip(s, s[1:]))
    assert all(0 <= r.seed < 2**31 for r in s)
