"""The benchmark's arithmetic and its load generator (CPU only, no chip,
no program code): percentiles and spread, and that a traffic file and a
seed give one fixed schedule."""
import json
import math
import os
import sys

import numpy as np
import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
if ROOT not in sys.path:
    sys.path.insert(0, ROOT)

from benchmarks.harness import stats, traffic  # noqa: E402


def _mix(name="chat_steady"):
    with open(os.path.join(ROOT, "benchmarks", "traffic", name + ".json"),
              encoding="utf-8") as f:
        return json.load(f)


# ------------------------------------------------------------------- stats

@pytest.mark.parametrize("values,q,want", [
    ([1.0, 2.0, 3.0, 4.0], 50, 2.5),
    ([1.0, 2.0, 3.0, 4.0], 0, 1.0),
    ([1.0, 2.0, 3.0, 4.0], 100, 4.0),
    ([5.0], 99, 5.0),
    ([3.0, 1.0, 2.0], 50, 2.0),
    (list(range(101)), 99, 99.0),
])
def test_percentile_interpolates_like_numpy(values, q, want):
    assert stats.percentile(values, q) == pytest.approx(want)
    assert stats.percentile(values, q) == pytest.approx(
        float(np.percentile(values, q)))


def test_percentile_of_nothing_is_none():
    assert stats.percentile([], 50) is None
    assert stats.mean([]) is None


def test_missing_observations_count_against_the_percentile():
    # 3 of 10 requests never answered: the median still exists, the p90
    # does not
    values = [1.0] * 7 + [math.inf] * 3
    assert stats.percentile(values, 50) == 1.0
    assert stats.percentile(values, 90) == math.inf
    assert stats.percentile([math.inf, math.inf], 50) == math.inf


def test_spread_is_quartile_distance_over_median():
    values = [98.0, 99.0, 100.0, 101.0, 102.0]
    assert stats.spread(values) == pytest.approx(0.02)
    assert stats.spread([1.0]) is None
    assert stats.spread([0.0, 0.0, 0.0]) is None


# ----------------------------------------------------------------- traffic

def _same(a, b):
    return (np.array_equal(a.due_s, b.due_s)
            and np.array_equal(a.output_len, b.output_len)
            and len(a.prompts) == len(b.prompts)
            and all(np.array_equal(p, q)
                    for p, q in zip(a.prompts, b.prompts)))


def test_same_seed_same_schedule_other_seed_other_schedule():
    mix = _mix()
    a = traffic.make_schedule(mix, 7, 30, 50257)
    b = traffic.make_schedule(mix, 7, 30, 50257)
    c = traffic.make_schedule(mix, 8, 30, 50257)
    assert _same(a, b)
    assert not np.array_equal(a.due_s[:20], c.due_s[:20])
    assert not np.array_equal(a.prompts[0], c.prompts[0])
    assert not np.array_equal(a.output_len[:50], c.output_len[:50])


@pytest.mark.parametrize("seed", [0, 1, 2, 3])
def test_chat_steady_respects_its_clips_and_the_model_positions(seed):
    mix = _mix()
    s = traffic.make_schedule(mix, seed, 60, 50257)
    assert len(s) > 100
    assert s.due_s[0] >= 0 and s.due_s[-1] < 60
    assert np.all(np.diff(s.due_s) >= 0)
    plen = s.prompt_len
    assert plen.min() >= mix["prompt_len"]["min"]
    assert plen.max() <= mix["prompt_len"]["max"]
    assert s.output_len.min() >= 1
    assert s.output_len.max() <= mix["output_len"]["max"]
    assert np.all(plen + s.output_len <= mix["max_total"])
    assert mix["max_total"] <= 1023          # gpt2-medium: 1024 positions
    for p in s.prompts[:50]:
        assert p.dtype == np.int32 and p.min() >= 0 and p.max() < 50257
    # lognormal around its median, prompts longer than answers
    assert 150 < np.median(plen) < 240
    assert 75 < np.median(s.output_len) < 120


def test_fixed_count_and_stratified_lengths_fix_the_amount_of_work():
    mix = _mix()
    assert mix["arrivals"]["count"] == "fixed"
    assert mix["prompt_len"]["stratified"] and mix["output_len"]["stratified"]
    runs = [traffic.make_schedule(mix, seed, 30, 50257) for seed in range(6)]
    want = round(mix["arrivals"]["rate_per_s"] * 30)
    assert {len(s) for s in runs} == {want}
    prompts = [int(s.prompt_len.sum()) for s in runs]
    outputs = [int(s.output_len.sum()) for s in runs]
    assert (max(prompts) - min(prompts)) / min(prompts) < 0.03
    assert (max(outputs) - min(outputs)) / min(outputs) < 0.03
    # the seed still decides when requests come and which is long
    assert len({tuple(s.output_len[:10]) for s in runs}) == 6
    assert len({round(float(s.due_s[0]), 6) for s in runs}) == 6


@pytest.mark.parametrize("rate,seconds", [(2.4, 30), (20.0, 100),
                                          (0.5, 10)])
def test_arrivals_are_poisson_given_their_count(rate, seconds):
    arrivals = {"process": "poisson", "rate_per_s": rate, "count": "fixed"}
    due = traffic.draw_arrivals(arrivals, seconds, np.random.default_rng(5))
    assert len(due) == round(rate * seconds)
    assert due[0] > 0 and due[-1] < seconds and np.all(np.diff(due) > 0)
    if len(due) > 1000:     # exponential gaps: as wide as their mean
        gaps = np.diff(due)
        assert gaps.std() / gaps.mean() == pytest.approx(1.0, abs=0.1)


_POISSON = {"process": "poisson", "rate_per_s": 1.0, "count": "fixed"}
_LOGNORMAL = {"dist": "lognormal", "median": 8, "sigma": 0.5, "min": 1,
              "max": 64, "stratified": True}


@pytest.mark.parametrize("group,change", [
    (_POISSON, {"process": "gamma", "cv": 3.0}),
    (_POISSON, {"process": "constant"}),
    (_POISSON, {"count": None}),
    (_LOGNORMAL, {"dist": "uniform"}),
    (_LOGNORMAL, {"dist": "fixed", "value": 8}),
    (_LOGNORMAL, {"stratified": False}),
])
def test_what_no_mix_uses_is_refused_by_name_not_drawn_as_something_else(
        group, change):
    draw = (traffic.draw_arrivals if group is _POISSON
            else traffic.draw_lengths)
    draw(group, 5, np.random.default_rng(0))
    with pytest.raises(ValueError):
        draw({**group, **change}, 5, np.random.default_rng(0))
