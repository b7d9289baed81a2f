"""The request generator: deterministic per seed, the same work for every
seed, and due times that the clock is measured from."""

import collections

import numpy as np
import pytest

from conftest import mix_file
from harness import traffic

BIG = 2 ** 31 + 12345      # seeds may exceed 32 signed bits


def _sched(name, seed, seconds=51.0, vocab=151936):
    return traffic.schedule(mix_file(name), seed, seconds, vocab)


@pytest.mark.parametrize("name", ["chat", "decode"])
def test_deterministic_per_seed(name):
    a, b = _sched(name, BIG), _sched(name, BIG)
    assert [(x.rid, x.due, x.max_new, x.group) for x in a] == \
        [(x.rid, x.due, x.max_new, x.group) for x in b]
    assert all(np.array_equal(x.prompt, y.prompt) for x, y in zip(a, b))
    c = _sched(name, BIG + 1)
    assert any(not np.array_equal(x.prompt, y.prompt) for x, y in zip(a, c))


@pytest.mark.parametrize("name", ["chat", "decode"])
def test_every_seed_gets_the_same_requests_at_the_same_times(name):
    start = {"preroll": -mix_file(name).get("preroll_s", 0.0)}

    def work(s):
        out = {}
        for g in ("in_flight", "backlog", "preroll", "window"):
            rows = [x for x in s if x.group == g]
            dues = [x.due for x in rows if np.isfinite(x.due)]
            out[g] = (sorted(len(x.prompt) for x in rows),
                      sorted(x.max_new for x in rows),
                      sorted(np.round(np.diff([start.get(g, 0.0)] + dues),
                                      9)))
        return out

    a, b = _sched(name, 3), _sched(name, 2 ** 40 + 1)
    assert work(a) == work(b)
    assert [(x.due, len(x.prompt), x.max_new) for x in a] == \
        [(x.due, len(x.prompt), x.max_new) for x in b]
    # in a random order: lengths are not sorted by arrival
    win = [x.max_new for x in a if x.group == "window"]
    assert win != sorted(win)


def test_due_times_fill_the_window_at_the_rate():
    mix = mix_file("chat")
    s = traffic.schedule(mix, BIG, 51.0, 1000)
    win = [x.due for x in s if x.group == "window"]
    pre = [x.due for x in s if x.group == "preroll"]
    assert len(win) == round(mix["rate_per_s"] * 51.0)
    assert win == sorted(win) and 0.0 < win[0] and win[-1] < 51.0
    assert all(-mix["preroll_s"] < d < 0.0 for d in pre)
    # gaps are exponential quantiles: their mean is the rate's
    assert np.mean(np.diff(win)) == pytest.approx(1 / mix["rate_per_s"],
                                                  rel=0.05)


def test_lengths_follow_the_mix():
    mix = mix_file("chat")
    n = 4000
    p = traffic.lengths(mix["prompt_len"], n)
    o = traffic.lengths(mix["output_len"], n)
    assert p.min() >= 16 and p.max() <= 1024 and np.median(p) == 96
    assert o.min() >= 8 and o.max() <= 256 and np.median(o) == 32
    u = traffic.lengths({"dist": "uniform", "min": 4, "max": 32}, 2900)
    assert collections.Counter(u) == {k: 100 for k in range(4, 33)}


def test_in_flight_and_backlog_of_the_decode_mix():
    mix = mix_file("decode")
    s = traffic.schedule(mix, 5, 51.0, 1000)
    fly = [x for x in s if x.group == "in_flight"]
    back = [x for x in s if x.group == "backlog"]
    assert len(fly) == mix["n_slots"] and len(back) == mix["backlog"]
    assert all(x.due == -np.inf for x in fly)
    assert all(x.due == 0.0 for x in back)
    assert all(1 <= x.max_new <= 4096 for x in fly)
    assert all(1024 <= x.max_new <= 4096 for x in back)
    traffic.check_fits(mix)
    for name in ("chat", "decode"):
        traffic.check_fits(mix_file(name))
    with pytest.raises(ValueError):
        traffic.check_fits(dict(mix, max_len=100))
