"""The generator: the same seed gives the same inputs, another seed the same
sizes in another order."""

import numpy as np
import pytest

import harness
import traffic


def load(name):
    return harness.overlay(harness.load_json("traffic", f"{name}.json"), rehearsal=False)

BIG = 2 ** 31 + 12345


@pytest.mark.parametrize("name", ["serve.closed32"])
def test_pool_is_deterministic_and_seed_only_reorders(name):
    mix = load(name)
    a, b = traffic.request_pool(mix, BIG, 50257), traffic.request_pool(mix, BIG, 50257)
    assert a == b and len(a) == mix["lap"] * mix["laps"]
    c = traffic.request_pool(mix, 7, 50257)
    lap = mix["lap"]
    for key, size in (("prompt_len", lambda r: len(r["prompt"])), ("new_tokens", lambda r: r["new_tokens"])):
        want = sorted(traffic.quantile_lengths(mix[key], lap).tolist())
        assert want[0] >= mix[key]["min"] and want[-1] <= mix[key]["max"]
        # every lap of every seed holds the very same sizes ...
        for pool in (a, c):
            for at in range(0, len(pool), lap):
                assert sorted(map(size, pool[at: at + lap])) == want
        # ... and the seed decides their order
        assert list(map(size, a)) != list(map(size, c))
        assert list(map(size, a[:lap])) != list(map(size, a[lap: 2 * lap]))
    assert all(0 <= t < 50257 for r in a for t in r["prompt"])
    assert a[0]["prompt"] != a[lap]["prompt"]


@pytest.mark.parametrize("name", ["serve.closed32"])
def test_ramp_starts_the_callers_at_every_age(name):
    mix = load(name)
    ramp = traffic.aged_ramp(mix, BIG, 50257)
    assert ramp == traffic.aged_ramp(mix, BIG, 50257) and len(ramp) == mix["clients"]
    assert all(r["ramp"] and 2 <= r["new_tokens"] <= mix["new_tokens"]["max"] for r in ramp)
    whole = sum(traffic.quantile_lengths(mix["new_tokens"], mix["clients"]))
    # cut to shares spread evenly over (0, 1): about half of the lap's tokens are left
    assert 0.35 * whole <= sum(r["new_tokens"] for r in ramp) <= 0.65 * whole
    assert ([r["new_tokens"] for r in ramp]
            != [r["new_tokens"] for r in traffic.aged_ramp(mix, 7, 50257)])


def test_log_uniform_quantiles():
    got = traffic.quantile_lengths({"dist": "log_uniform", "min": 32, "max": 512}, 4)
    assert got.tolist() == [round(32 * 16 ** u) for u in (0.125, 0.375, 0.625, 0.875)]
    assert traffic.quantile_lengths({"min": 9, "max": 9}, 3).tolist() == [9, 9, 9]


def test_token_batches_rows_all_differ():
    mix = load("train.b8s1024")
    a = traffic.token_batches(mix, BIG, 50257, 8)
    b = traffic.token_batches(mix, BIG, 50257, 8)
    assert len(a) == mix["host_batches"] and a[0].shape == (8, 1024) and a[0].dtype == np.int32
    assert all((x == y).all() for x, y in zip(a, b))
    rows = np.concatenate(a)
    assert len({r.tobytes() for r in rows}) == len(rows)
    assert not (traffic.token_batches(mix, 3, 50257, 8)[0] == a[0]).all()
