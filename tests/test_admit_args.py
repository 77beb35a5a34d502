"""An admit group's arguments are built on the host and reach the device in one
transfer (`ServingEngine._admit_group`, `_admit_group_cached`): the rng key
rows come from `engine._host_key_data` where it is exact, a resumed request
keeps the device path, and the tokens are the ones `jax.random.key` gives."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

flax_nn = pytest.importorskip("flax.linen")

pytestmark = pytest.mark.serving

from accelerate_tpu.models.generation import generate  # noqa: E402
from accelerate_tpu.models.gpt2 import GPT2Config, GPT2LMHead  # noqa: E402
from accelerate_tpu.serving import (  # noqa: E402
    FINISH_LENGTH,
    Request,
    SamplingParams,
    ServingEngine,
)
from accelerate_tpu.serving import engine as engine_mod  # noqa: E402

SEEDS = [0, 1, 2**31 - 1, 2**32 + 7, -3, 2**63 - 1]


@pytest.fixture(scope="module")
def model():
    cfg = GPT2Config.tiny(dtype=jnp.float32)
    module = GPT2LMHead(cfg)
    return module, module.init_params(jax.random.key(0))


def _solo(module, params, prompt, n, temperature, top_k, seed):
    ids = jnp.asarray(np.asarray(prompt, np.int32)[None, :])
    out = generate(module, params, ids, max_new_tokens=n, temperature=temperature,
                   top_k=top_k, rng=jax.random.key(seed))
    return np.asarray(out)[0].tolist()


def _sampled(n_requests, n_new=6, prompt_seed=3):
    """Sampled requests, each with its own seed, temperature and top-k."""
    r = np.random.default_rng(prompt_seed)
    seeds = SEEDS + [1000 + i for i in range(max(0, n_requests - len(SEEDS)))]
    return [Request(r.integers(0, 256, (3 + (5 * i) % 11,)).tolist(),
                    SamplingParams(max_new_tokens=n_new, temperature=0.6 + 0.1 * (i % 4),
                                   top_k=(None, 4, 9)[i % 3], seed=seeds[i]))
            for i in range(n_requests)]


# ------------------------------------------------------------- host key data
@pytest.mark.parametrize("x64", [False, True])
@pytest.mark.parametrize("seed", SEEDS)
def test_host_key_data_is_jax_key_data(seed, x64):
    with jax.enable_x64(x64):
        want = np.asarray(jax.random.key_data(jax.random.key(seed)))
        got = engine_mod._host_key_data(seed)
    assert got is not None and got.dtype == np.uint32 == want.dtype
    np.testing.assert_array_equal(got, want)


def test_host_key_data_leaves_what_it_cannot_make_to_the_device():
    """Where the host row would not be jax's, the caller takes the device path:
    a seed `jax.random.key` refuses, a seed that is no Python int, another PRNG
    implementation, a seed offset."""
    with pytest.raises(OverflowError):
        jax.random.key(2**64)
    assert engine_mod._host_key_data(2**64) is None
    assert engine_mod._host_key_data(np.uint64(5)) is None
    with jax.default_prng_impl("rbg"):
        assert engine_mod._host_key_data(5) is None
    jax.config.update("jax_random_seed_offset", 3)
    try:
        assert engine_mod._host_key_data(5) is None
    finally:
        jax.config.update("jax_random_seed_offset", 0)
    assert engine_mod._host_key_data(5) is not None


# ------------------------------------------------------------ one transfer
@pytest.mark.parametrize("cached", [False, True], ids=["plain", "prefix_hit"])
def test_admit_builds_no_device_key_and_makes_one_transfer(model, monkeypatch, cached):
    """After warm-ups that compile every program, an admit group calls
    neither `jax.random.key` nor `jnp.stack` and makes one `jax.device_put`."""
    module, params = model
    kw = dict(paged_kv=True, prefix_cache=True) if cached else {}
    engine = ServingEngine(module, params, max_concurrency=4, prompt_buckets=(16, 32),
                           admit_batch=4, **kw)
    prompt = list(range(1, 25))  # a later one hits its first block of 16

    def batch():
        return [Request(list(prompt) if cached else prompt[:5 + i],
                        SamplingParams(max_new_tokens=3, temperature=0.8, seed=i))
                for i in range(4)]

    engine.run(batch())  # all miss
    engine.run(batch())  # with the prefix cache: all hit
    calls = {"key": 0, "stack": 0, "device_put": 0}

    def spy(name, fn):
        def counted(*args, **kwargs):
            calls[name] += 1
            return fn(*args, **kwargs)
        return counted

    monkeypatch.setattr(jax.random, "key", spy("key", jax.random.key))
    monkeypatch.setattr(jnp, "stack", spy("stack", jnp.stack))
    monkeypatch.setattr(jax, "device_put", spy("device_put", jax.device_put))
    groups = engine.metrics.admit_batch_size.count
    outs = engine.run(batch())
    groups = engine.metrics.admit_batch_size.count - groups
    assert len(outs) == 4 and all(o.finish_reason == FINISH_LENGTH for o in outs)
    assert groups >= 1 and calls == {"key": 0, "stack": 0, "device_put": groups}
    if cached:
        assert engine.metrics.prefix_hits.value >= 1
    snap = engine.metrics.snapshot()
    assert (snap["serving/admit/host_keys"], snap["serving/admit/device_keys"]) == (12, 0)


# ------------------------------------------------------------------ tokens
@pytest.mark.parametrize("admit", [1, 4])
def test_sampled_tokens_are_the_device_keys_tokens(model, monkeypatch, admit):
    """Sampled traffic with distinct seeds gives solo `generate`'s tokens, and
    the same tokens when every key is made on the device as before."""
    module, params = model
    requests = _sampled(8)

    def serve():
        engine = ServingEngine(module, params, max_concurrency=4, prompt_buckets=(8, 16),
                               admit_batch=admit)
        outs = engine.run([Request(list(r.prompt), r.params) for r in requests])
        return [o.tokens for o in sorted(outs, key=lambda o: o.request_id)], engine.metrics

    host, m = serve()
    assert (m.admit_host_keys.value, m.admit_device_keys.value) == (8, 0)
    assert host == [_solo(module, params, r.prompt, r.params.max_new_tokens,
                          r.params.temperature, r.params.top_k, r.params.seed)
                    for r in requests]
    monkeypatch.setattr(engine_mod, "_host_key_data", lambda seed: None)
    device, m = serve()
    assert (m.admit_host_keys.value, m.admit_device_keys.value) == (0, 8)
    assert device == host


def test_a_resumed_request_keeps_the_device_path(model, tmp_path):
    """A request resumed mid-stream from the journal makes its key on the
    device (its chain split past the tokens it already emitted) and its stream
    is the uninterrupted one; a request restored before its first token makes
    its key on the host."""
    module, params = model
    requests = _sampled(4, n_new=10)
    refs = {i: _solo(module, params, r.prompt, r.params.max_new_tokens,
                     r.params.temperature, r.params.top_k, r.params.seed)
            for i, r in enumerate(requests)}
    jpath = tmp_path / "requests.journal"
    first = ServingEngine(module, params, max_concurrency=2, prompt_buckets=(16,),
                          journal=jpath)
    for r in requests:
        assert first.submit(Request(list(r.prompt), r.params)).accepted
    for _ in range(4):
        assert not first.step()  # two mid-stream, two queued, none finished
    assert first.metrics.admit_host_keys.value == 2
    del first

    second = ServingEngine(module, params, max_concurrency=2, prompt_buckets=(16,),
                           journal=jpath)
    report = second.resume()
    assert report.resumed and not report.completed
    final = {}
    while second.has_work:
        for out in second.step():
            final[out.request_id] = out
    assert {rid: o.tokens for rid, o in final.items()} == refs
    m = second.metrics
    assert m.admit_device_keys.value == len(report.resumed)
    assert m.admit_host_keys.value == len(requests) - len(report.resumed)


# ------------------------------------------------------------------ release
def test_a_device_finished_release_leaves_the_tables_to_the_next_admit(model):
    """A slot the device finished keeps its table row on release: no device
    program runs between its retirement and the next admit. A cancelled slot's
    row is pointed at ``num_blocks``, so its lagged writes drop. Later tenants
    of both slots, admitted under a deep pipeline, give solo `generate`'s
    tokens."""
    module, params = model
    r = np.random.default_rng(5)
    prompts = [r.integers(0, 256, (n,)).tolist() for n in (6, 4, 7, 5)]
    budgets = (24, 3, 12, 12)
    refs = [_solo(module, params, p, n, 0.0, None, 0) for p, n in zip(prompts, budgets)]
    engine = ServingEngine(module, params, max_concurrency=2, prompt_buckets=(8,),
                           pipeline_depth=4)
    num_blocks = engine._allocator.num_blocks
    a, b = (engine.submit(Request(prompts[i], SamplingParams(max_new_tokens=budgets[i])))
            for i in range(2))
    slot_of = {}
    done = {}
    while b.request_id not in done:
        slot_of.update({out.request_id: s for s, out in enumerate(engine._slot_out)
                        if out is not None})
        done.update({o.request_id: o for o in engine.step()})
    assert done[b.request_id].finish_reason == FINISH_LENGTH
    assert done[b.request_id].tokens == refs[1]
    tables = np.asarray(engine._d_tables)
    assert (tables[slot_of[b.request_id]] != num_blocks).any()  # left as it was
    cancelled = engine.cancel(a.request_id)
    assert 0 < len(cancelled.tokens) < budgets[0]
    assert cancelled.tokens == refs[0][:len(cancelled.tokens)]
    assert (np.asarray(engine._d_tables)[slot_of[a.request_id]] == num_blocks).all()
    later = [engine.submit(Request(prompts[i], SamplingParams(max_new_tokens=budgets[i])))
             for i in (2, 3)]
    while engine.has_work:
        done.update({o.request_id: o for o in engine.step()})
    assert [done[h.request_id].tokens for h in later] == refs[2:]
