"""The open loop's schedule and its clock."""

import pytest

import benchlib


@pytest.fixture(scope='module')
def webhook():
    return benchlib.load_module('drivers', 'webhook')


def test_schedule_is_a_pure_function_of_seed_and_rate(webhook):
    a = webhook.arrivals(3000000019, 6.0, 40.0)
    assert a == webhook.arrivals(3000000019, 6.0, 40.0)
    assert a != webhook.arrivals(3000000020, 6.0, 40.0)
    assert a != webhook.arrivals(3000000019, 7.0, 40.0)
    assert len(a) == 240 and a == sorted(a) and 0 < a[0] and a[-1] < 40.0


def test_every_seed_offers_the_same_gaps_in_another_order(webhook):
    def gaps(seed):
        due = [0.0] + webhook.arrivals(seed, 6.0, 40.0) + [40.0]
        return sorted(round(b - a, 9) for a, b in zip(due, due[1:]))
    assert gaps(1) == gaps(2) == gaps(2**31 + 11)


def test_count_is_the_rounded_rate_times_seconds(webhook):
    assert len(webhook.arrivals(0, 5.6, 40.0)) == 224
    assert webhook.arrivals(0, 0.0, 40.0) == []


def test_latency_runs_from_the_due_time_not_from_the_send(webhook):
    driver = webhook.Driver(
        config={'guarantees': {'answer_within_s': 10}},
        traffic={'yields': {'p50': 'p50_ms', 'p95': 'p95_ms'}},
        seed=0, seconds=40.0, platform='cpu', registry=None)
    driver._window = (100.0, 140.0)
    # due at 101 s, sent 0.5 s late, answered 0.25 s after that; a second
    # request was never answered, and counts as the timeout and as failed
    driver._due = {7: 101.0, 8: 102.0}
    out = driver._reduce_open({7: (101.0, 101.5, 101.75, b'{}')})
    assert driver._samples['latency_ms'] == pytest.approx([750.0, 10000.0])
    assert driver._samples['late_ms'] == pytest.approx([500.0])
    assert (driver.attempted, driver.failed) == (2, 1)
    assert out['metrics']['p50'] == pytest.approx((750.0 + 10000.0) / 2)


def test_closed_loop_counts_only_answers_inside_the_window(webhook):
    driver = webhook.Driver(
        config={'guarantees': {'answer_within_s': 10}},
        traffic={'yields': {'rate': 'rate'}},
        seed=0, seconds=40.0, platform='cpu', registry=None)
    driver._window = (100.0, 140.0)
    out = driver._reduce_closed({1: (100.0, 100.0, 101.0, b'{}'),
                                 2: (139.5, 139.5, 140.5, b'{}')})
    assert driver.attempted == 1 and out['metrics']['rate'] == 1 / 40.0


def test_quantile_interpolates_between_ranks():
    assert benchlib.quantile([1, 2, 3, 4, 5], 0.5) == 3
    assert benchlib.quantile([0, 10], 0.95) == pytest.approx(9.5)
    with pytest.raises(ValueError):
        benchlib.quantile([], 0.5)
