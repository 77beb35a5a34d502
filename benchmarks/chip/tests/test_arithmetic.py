"""The yardstick's arithmetic against hand counts: FLOPs and bytes,
percentiles, spreads, peaks."""

import json
import os

import pytest

import flops
import peaks
import stats

CHIP = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def config(name):
    with open(os.path.join(CHIP, "configs", f"{name}.json")) as f:
        return json.load(f)


@pytest.mark.parametrize("name,expected", [
    # 24 * 12 * 1024^2 + 1024 * 50257 ; 36 * 12 * 1280^2 + 1280 * 50257
    ("gpt2-medium", 24 * 12 * 1024 * 1024 + 1024 * 50257),
    ("gpt2-large", 36 * 12 * 1280 * 1280 + 1280 * 50257),
])
def test_matmul_params(name, expected):
    assert flops.matmul_params(config(name)) == expected


def test_train_flops_per_token_medium():
    # 6 * 353,453,056 weights + 3 * 4 * 24 * 1024 * 512.5 attention = 2.272e9
    got = flops.train_flops_per_token(config("gpt2-medium"), 1024)
    assert got == pytest.approx(6 * 353_453_056 + 12 * 24 * 1024 * 512.5)
    assert got == pytest.approx(2.2719e9, rel=1e-3)


def test_train_flops_per_token_large():
    got = flops.train_flops_per_token(config("gpt2-large"), 1024)
    assert got == pytest.approx(6 * (36 * 12 * 1280 ** 2 + 1280 * 50257) + 12 * 36 * 1280 * 512.5)


def test_serve_request_flops_by_hand():
    cfg = {"n_embd": 4, "n_layer": 2, "n_head": 2, "vocab_size": 10}
    # body weights 2 * 12 * 16 = 384; prompt 3, 2 new tokens: 4 tokens fed,
    # contexts 1 + 2 + 3 + 4 = 10; head 2 * 4 * 10 per produced token
    expected = 4 * 2 * 384 + 4 * 2 * 4 * 10 + 2 * 2 * 4 * 10
    assert flops.serve_request_flops(cfg, 3, 2) == expected


def test_flash_attention_cost_medium():
    cost = flops.flash_attention_train_cost(config("gpt2-medium"), 8, 1024)
    fwd = 4 * 8 * 16 * 64 * 1024 * 1025 / 2
    assert cost["flops"] == pytest.approx(24 * 3.5 * fwd)
    assert cost["bytes"] == 24 * 12 * (8 * 1024 * 16 * 64 * 2)


def test_paged_decode_cost_large():
    cost = flops.paged_decode_cost(config("gpt2-large"), live_tokens=32 * 400, rows=32)
    assert cost["flops"] == 4 * 1280 * 32 * 400
    assert cost["bytes"] == 2 * 1280 * 32 * 400 * 2 + 2 * 32 * 1280 * 2


def test_roofline_takes_the_larger_bound():
    p = peaks.peaks_for("TPU v5 lite")
    assert flops.roofline_seconds({"flops": 197e12, "bytes": 1.0}, p) == pytest.approx(1.0)
    assert flops.roofline_seconds({"flops": 1.0, "bytes": 819e9 * 2}, p) == pytest.approx(2.0)


def test_unknown_device_is_an_error():
    with pytest.raises(KeyError):
        peaks.peaks_for("TPU v9")


@pytest.mark.parametrize("values,q,expected", [
    (list(range(1, 401)), 95, 380),
    (list(range(1, 21)), 95, 19),
    ([5.0], 95, 5.0),
    ([3, 1, 2], 50, 2),
    (list(range(1, 101)), 100, 100),
])
def test_percentile_nearest_rank(values, q, expected):
    assert stats.percentile(values, q) == expected


def test_percentile_of_nothing_raises():
    with pytest.raises(ValueError):
        stats.percentile([], 95)


def test_spread_is_iqr_over_median():
    import statistics

    values = [10.0, 10.2, 9.9, 10.1, 10.4, 9.8]
    q1, _, q3 = statistics.quantiles(values, n=4)
    assert stats.spread(values) == pytest.approx((q3 - q1) / statistics.median(values))
