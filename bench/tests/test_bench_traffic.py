"""Traffic from a seed: stratified sizes, closed and open loops."""
import numpy as np
import pytest

from bench.harness import traffic as tr
from bench.tests.tiny import CLOSED, OPEN

BIG = 2**31 + 12345  # seeds beyond 32 signed bits


def test_quantiles_uniform_cover_the_range_evenly():
    v = tr.quantiles({"dist": "uniform", "lo": 6144, "hi": 7168}, 24)
    assert v.min() >= 6144 and v.max() <= 7168
    assert np.all(np.diff(v) > 0)
    assert abs(v.mean() - (6144 + 7168) / 2) < 1


def test_quantiles_lognormal_median_and_clip():
    d = {"dist": "lognormal", "median": 1024, "sigma": 0.8, "lo": 128, "hi": 4096}
    v = tr.quantiles(d, 201)
    assert v[100] == 1024  # the middle point is the median
    assert v.min() >= 128 and v.max() <= 4096
    assert v[-1] == 4096  # the upper tail is clipped


@pytest.mark.parametrize("order_seed", [0, 7, BIG])
def test_the_order_seed_orders_the_same_work(order_seed):
    prompts = lambda ls: [i.prompt_len for lane in ls for i in lane]  # noqa: E731
    base = tr.closed_loop(CLOSED)
    lanes = tr.closed_loop(dict(CLOSED, order_seed=order_seed))
    assert len(lanes) == CLOSED["clients"]
    assert all(len(lane) == CLOSED["requests_per_client"] for lane in lanes)
    assert sorted(prompts(lanes)) == sorted(prompts(base))
    assert tr.closed_loop(dict(CLOSED, order_seed=order_seed)) == lanes


def test_closed_loop_staggers_first_requests():
    mix = dict(CLOSED, clients=4, output_len={"dist": "uniform", "lo": 100, "hi": 100})
    lanes = tr.closed_loop(mix)
    assert [lane[0].max_new for lane in lanes] == [25, 50, 75, 100]
    assert all(i.max_new == 100 for lane in lanes for i in lane[1:])


def test_open_loop_rate_and_sizes():
    items = tr.open_loop(dict(OPEN, order_seed=BIG), seconds=10.0)
    dues = np.array([i.due for i in items])
    assert np.all(np.diff(dues) > 0)
    # stratified exponential gaps: n gaps sum to about n / rate
    assert dues[-1] == pytest.approx(len(items) / OPEN["rate"], rel=0.05)
    assert dues[-1] > OPEN["pre_roll_s"] + 10.0
    other = tr.open_loop(OPEN, seconds=10.0)
    assert sorted(i.prompt_len for i in items) == sorted(i.prompt_len for i in other)
    assert [i.prompt_len for i in items] != [i.prompt_len for i in other]


def test_exponential_gaps_mean():
    g = tr.exponential_gaps(2.0, 1000)
    assert g.mean() == pytest.approx(0.5, rel=0.01)
    with pytest.raises(ValueError):
        tr.exponential_gaps(0.0, 3)


def test_prompt_tokens_from_seed():
    a = tr.prompt_tokens(BIG, 3, 5, 100, 32002)
    assert a.dtype == np.int32 and a.shape == (100,) and a.max() < 32002
    assert np.array_equal(a, tr.prompt_tokens(BIG, 3, 5, 100, 32002))
    assert not np.array_equal(a, tr.prompt_tokens(BIG, 3, 6, 100, 32002))
