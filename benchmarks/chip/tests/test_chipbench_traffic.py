"""The traffic generator: same seed, same requests; every seed, the same
work in another order."""

import _paths  # noqa: F401

import random

import pytest

from benchmarks.chip import spec, traffic

CHAT = {"arrival": "poisson", "rate_per_s": 2.5,
        "prompt": {"median": 384, "sigma": 0.8, "min": 32, "max": 1024},
        "output": {"median": 64, "sigma": 0.9, "min": 8, "max": 512}}


def _key(reqs):
    return [(r.rid, r.due, tuple(r.prompt), r.max_new_tokens) for r in reqs]


@pytest.mark.parametrize("seed", [0, 7, 2**31 + 5, 2**40 + 3])
def test_same_seed_same_requests(seed):
    a = traffic.generate(CHAT, seed, 40.0, 151936)
    b = traffic.generate(CHAT, seed, 40.0, 151936)
    assert _key(a) == _key(b)


def test_seeds_deal_the_same_work_in_another_order():
    a = traffic.generate(CHAT, 1, 40.0, 1000)
    b = traffic.generate(CHAT, 2**33 + 1, 40.0, 1000)
    assert _key(a) != _key(b)
    assert sorted(len(r.prompt) for r in a) == \
        sorted(len(r.prompt) for r in b)
    assert sorted(r.max_new_tokens for r in a) == \
        sorted(r.max_new_tokens for r in b)


def test_poisson_fills_the_window_and_clips_lengths():
    reqs = traffic.generate(CHAT, 3, 40.0, 1000)
    assert len(reqs) == 100
    assert reqs[0].due == 0.0
    assert all(0 <= r.due < 40.0 for r in reqs)
    assert all(32 <= len(r.prompt) <= 1024 for r in reqs)
    assert all(8 <= r.max_new_tokens <= 512 for r in reqs)
    assert sorted(r.due for r in reqs) == [r.due for r in reqs]


def test_backlog_is_all_due_at_zero():
    mix = dict(CHAT, arrival="backlog", backlog_per_s=4)
    reqs = traffic.generate(mix, 5, 10.0, 32000)
    assert len(reqs) == 40 and {r.due for r in reqs} == {0.0}


def test_unknown_arrival_is_refused():
    with pytest.raises(ValueError, match="arrival"):
        traffic.generate(dict(CHAT, arrival="bursty"), 0, 10.0, 100)


def test_every_mix_in_the_directory_generates():
    for path in sorted((spec.HERE / "traffic").glob("*.json")):
        mix = spec.traffic(path.stem)
        assert mix["why"]
        assert traffic.generate(mix, 9, 20.0, 1000)


def test_the_order_is_not_smoothed():
    """Long outputs bunch and short gaps run together on some seeds, as in
    independent draws: nothing spreads them over the window."""
    most_long, longest_run = 0, 0
    for seed in range(2**31 + 1, 2**31 + 41):
        reqs = traffic.generate(CHAT, seed, 45.0, 1000)
        top = sorted(r.max_new_tokens for r in reqs)[-len(reqs) // 8]
        for k in range(len(reqs) - 7):
            most_long = max(most_long, sum(
                r.max_new_tokens >= top for r in reqs[k:k + 8]))
        gaps = [b.due - a.due for a, b in zip(reqs, reqs[1:])]
        short = sorted(gaps)[len(gaps) // 2]
        run = 0
        for g in gaps:
            run = run + 1 if g < short else 0
            longest_run = max(longest_run, run)
    # an order spread over the window by bands would cap both near 2 and 4
    assert most_long >= 4 and longest_run >= 8
    vals = list(range(20))
    out = traffic.shuffled(vals, random.Random(4))
    assert sorted(out) == vals and out != vals
