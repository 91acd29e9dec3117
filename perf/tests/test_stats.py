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
