"""Percentile and window arithmetic."""

import statistics

import pytest

from perf.harness import registry, stats
from perf.harness.flops import (causal_attention_train_flops,
                                kv_read_bytes, lm_forward_flops_per_token)


def test_percentile_interpolates_like_numpy():
    xs = [10, 20, 30, 40]
    assert stats.percentile(xs, 0.5) == 25
    assert stats.percentile(xs, 0.95) == pytest.approx(38.5)
    assert stats.percentile([7], 0.95) == 7
    with pytest.raises(ValueError):
        stats.percentile([], 0.5)


def test_quartile_spread_is_the_contracts():
    xs = [100, 101, 102, 103, 104, 110]
    q1, q2, q3 = statistics.quantiles(xs, n=4)
    assert stats.quartile_spread(xs) == pytest.approx((q3 - q1) / q2)


def test_rate_and_window():
    assert stats.rate(50, 1.0, 3.0) == 25
    assert stats.in_window([0.5, 1.0, 2.9, 3.0], 1.0, 3.0) == 2
    with pytest.raises(ValueError):
        stats.rate(1, 2.0, 2.0)


def test_registry_deltas_take_sums_and_counts():
    before = {"h": {"values": [{"labels": {}, "sum": 1.0, "count": 2}]},
              "c": {"values": [{"labels": {"a": "1"}, "value": 5.0}]}}
    after = {"h": {"values": [{"labels": {}, "sum": 4.0, "count": 8}]},
             "c": {"values": [{"labels": {"a": "1"}, "value": 9.0},
                              {"labels": {"a": "2"}, "value": 1.0}]}}
    assert registry.delta(before, after, "h") == (3.0, 6)
    assert registry.mean_ms(before, after, "h") == pytest.approx(500.0)
    assert registry.delta(before, after, "c")[1] == 5.0
    assert registry.mean_ms(before, after, "missing") is None


def test_lm_flops():
    # Cerebras-GPT 1.3B at 24 layers: 2 x (1.21 G block parameters) plus
    # the head and attention
    f = lm_forward_flops_per_token(2048, 8192, 24, 50257, 2048)
    blocks = 24 * 2 * 12 * 2048 ** 2
    assert f == blocks + 24 * 4 * 2048 * 2049 / 2 + 2 * 2048 * 50257
    assert causal_attention_train_flops(1, 1, 4, 2, 1) == 6 * 2 * 16 * 2 / 2
    assert kv_read_bytes(10, 16, 128, 24, 4) == 2 * 10 * 16 * 128 * 4 * 24


def test_interquartile_mean_is_the_mean_of_a_symmetric_sample():
    xs = [10.0 + i for i in range(41)]             # symmetric about 30
    assert stats.interquartile_mean(xs) == pytest.approx(30.0)
    assert stats.interquartile_mean(xs) == pytest.approx(sum(xs) / len(xs))
    assert stats.interquartile_mean([1, 2, 3, 4]) == 2.5   # indices 1..2
    with pytest.raises(ValueError):
        stats.interquartile_mean([1, 2, 3])


def test_interquartile_mean_ignores_a_stall_in_one_percent():
    calm = [40.0 + (i % 20) * 0.5 for i in range(400)]
    stalled = calm[:396] + [2000.0] * 4            # a 2 s stall in 1%
    assert stats.interquartile_mean(stalled) == pytest.approx(
        stats.interquartile_mean(calm), abs=0.1)
    assert sum(stalled) / 400 - sum(calm) / 400 > 15


def _two_populations(low):
    """400 samples in two tight populations, ``low`` of them at ~30 ms
    and the rest at ~50 ms: a gap of 20 ms between them."""
    return ([30.0 + (i % 10) * 0.01 for i in range(low)]
            + [50.0 + (i % 10) * 0.01 for i in range(400 - low)])


def test_interquartile_mean_does_not_sit_on_a_gap():
    # the 50th percentile lies in the gap; 2% of the samples cross it
    before, after = _two_populations(204), _two_populations(196)
    gap = 20.0
    assert (stats.median(after) - stats.median(before)) > gap / 2
    moved = (stats.interquartile_mean(after)
             - stats.interquartile_mean(before))
    assert 0 < moved < gap / 10


def test_follower_share_counts_sends_in_a_convoy():
    # four sends within a millisecond of each other, then three alone
    sends = [0.2089, 0.2091, 0.2100, 0.2109, 0.5, 0.9, 1.3]
    assert stats.follower_share(sends, 0.005) == pytest.approx(3 / 7)
    assert stats.follower_share([0.0, 1.0], 0.005) == 0
    with pytest.raises(ValueError):
        stats.follower_share([], 0.005)
