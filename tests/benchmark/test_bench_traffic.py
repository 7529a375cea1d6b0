"""The open-loop generator: the same seed gives the same schedule, every seed gets the same
sizes and gaps in another order, and percentiles are taken by rank."""

import json
import os

import numpy as np
import pytest

from benchmark import traffic

HERE = os.path.dirname(traffic.__file__)


@pytest.fixture(scope="module")
def chat():
    with open(os.path.join(HERE, "testdata", "chat_rehearsal.json")) as f:
        return dict(json.load(f), rate_per_s=2.0)  # some tens of arrivals in a 20 s window


def test_same_seed_same_schedule(chat):
    a, ramp_a = traffic.open_loop_schedule(chat, 2**31 + 11, 20.0, 49152)
    b, ramp_b = traffic.open_loop_schedule(chat, 2**31 + 11, 20.0, 49152)
    assert ramp_a == ramp_b == chat["ramp_seconds"]
    assert [(x.due_s, x.prompt_ids, x.max_new_tokens) for x in a] == [(x.due_s, x.prompt_ids, x.max_new_tokens) for x in b]


def test_every_seed_gets_the_same_work_in_another_order(chat):
    a, _ = traffic.open_loop_schedule(chat, 1, 20.0, 49152)
    b, _ = traffic.open_loop_schedule(chat, 2, 20.0, 49152)
    for measured in (False, True):
        pa = [len(x.prompt_ids) for x in a if x.measured == measured]
        pb = [len(x.prompt_ids) for x in b if x.measured == measured]
        assert sorted(pa) == sorted(pb) and pa != pb
        oa = [x.max_new_tokens for x in a if x.measured == measured]
        ob = [x.max_new_tokens for x in b if x.measured == measured]
        assert sorted(oa) == sorted(ob) and oa != ob
    assert [x.prompt_ids for x in a] != [x.prompt_ids for x in b]


def test_arrivals_fill_the_ramp_and_the_window_at_the_rate(chat):
    seconds = 20.0
    arrivals, ramp = traffic.open_loop_schedule(chat, 5, seconds, 49152)
    window = [x for x in arrivals if x.measured]
    assert len(window) == round(chat["rate_per_s"] * seconds)
    assert all(ramp <= x.due_s < ramp + seconds for x in window)
    assert all(0 <= x.due_s < ramp for x in arrivals if not x.measured)
    assert [x.due_s for x in arrivals] == sorted(x.due_s for x in arrivals)
    law_p, law_o = chat["prompt_tokens"], chat["output_tokens"]
    assert all(law_p["min"] <= len(x.prompt_ids) <= law_p["max"] for x in arrivals)
    assert all(law_o["min"] <= x.max_new_tokens <= law_o["max"] for x in arrivals)
    assert all(len(x.prompt_ids) + x.max_new_tokens <= 4096 for x in arrivals)
    assert all(1 <= t < 49152 for x in arrivals for t in x.prompt_ids)  # 0 is eos and pad


def test_lognormal_quantiles_have_the_stated_median_and_tails():
    values = traffic.lognormal_quantiles(1001, 400, 0.9, 32, 3072)
    assert values[500] == 400 and values.min() >= 32 and values.max() == 3072
    assert np.all(np.diff(values) >= 0)


def test_exponential_gaps_sum_to_the_phase():
    gaps = traffic.exponential_gaps(80, 40.0)
    assert gaps.sum() == pytest.approx(40.0) and gaps.min() > 0
    assert np.median(gaps) == pytest.approx(0.5 * np.log(2), rel=0.05)  # mean gap 0.5 s


@pytest.mark.parametrize(
    "samples, q, value",
    [([1, 2, 3, 4], 0.5, 2), ([1, 2, 3, 4], 0.95, 4), (list(range(1, 101)), 0.95, 95), ([7], 0.95, 7)],
)
def test_percentile_by_rank(samples, q, value):
    assert traffic.percentile(samples, q) == value


def test_percentile_of_nothing_is_an_error():
    with pytest.raises(ValueError):
        traffic.percentile([], 0.95)
