"""Speculative decoding (docs/serving.md "Speculative decoding"): drafters,
the batched k+1-position verify step, per-slot KV frontier rollback, and the
spec-on == spec-off == solo-generate parity bar.

The load-bearing contract mirrors the serving suite's: greedy output through
the engine with speculation enabled must be BIT-IDENTICAL to speculation off
and to a solo ``generate`` — drafts are performance hints, never semantics.
"""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

flax_nn = pytest.importorskip("flax.linen")

pytestmark = pytest.mark.speculation

from accelerate_tpu.models.generation import generate
from accelerate_tpu.models.gpt2 import GPT2Config, GPT2LMHead
from accelerate_tpu.models.kv_cache import _is_index_leaf
from accelerate_tpu.reliability import FaultSpec
from accelerate_tpu.serving import (
    FINISH_EOS,
    FINISH_LENGTH,
    ModelDrafter,
    NGramDrafter,
    PagedKVConfig,
    Request,
    SamplingParams,
    ServingEngine,
    SpeculationConfig,
    Tracer,
)
from accelerate_tpu.serving.speculation import resolve_drafter
from accelerate_tpu.serving.trace import EV_DISPATCH, EV_FETCH


@pytest.fixture(scope="module")
def model():
    cfg = GPT2Config.tiny(dtype=jnp.float32)
    module = GPT2LMHead(cfg)
    params = module.init_params(jax.random.key(0))
    return module, params


def _solo(module, params, prompt, n, temperature=0.0, top_k=None, seed=0):
    ids = jnp.asarray(np.asarray(prompt, np.int32)[None, :])
    out = generate(module, params, ids, max_new_tokens=n,
                   temperature=temperature, top_k=top_k, rng=jax.random.key(seed))
    return np.asarray(out)[0].tolist()


def _prompts(rng_seed, lengths, vocab=256):
    r = np.random.default_rng(rng_seed)
    return [r.integers(0, vocab, (n,)).astype(np.int32).tolist() for n in lengths]


# -------------------------------------------------------------- drafter units
def test_ngram_drafter_lookup_rules():
    d = NGramDrafter(draft_tokens=3, max_ngram=2, min_ngram=1)
    # tail [9]: most recent earlier 9 is at index 4 -> continuation 5 6 7
    assert d.propose([1, 9, 2, 3, 9, 5, 6, 7], [9]) == [5, 6, 7]
    # 2-gram tail beats a more recent 1-gram match: tail [3, 9] matches at
    # index 2 -> continuation starts after it
    assert d.propose([1, 3, 9, 5, 9, 8], [3, 9]) == [5, 9, 8]
    # proposals are capped at draft_tokens
    assert len(d.propose(list(range(4)) * 3, [])) <= 3
    # no repeated tail anywhere -> no proposal
    assert d.propose([1, 2, 3, 4], [5]) == []
    # emitted tokens participate in both the tail and the match pool
    assert d.propose([7, 8], [1, 2, 7, 8, 5, 7, 8]) == [5, 7, 8]
    with pytest.raises(ValueError):
        NGramDrafter(draft_tokens=0)
    with pytest.raises(ValueError):
        NGramDrafter(min_ngram=3, max_ngram=2)


def test_model_drafter_window_and_greedy_proposal(model):
    module, params = model
    d = ModelDrafter(module, params, draft_tokens=3, context_tokens=8)
    prompt = _prompts(3, [11])[0]
    # the context windows to its largest power-of-two tail (bounded compiles)
    assert len(d._window(prompt)) == 8
    assert len(d._window(prompt[:5])) == 4
    got = d.propose(prompt, [])
    ref = _solo(module, params, prompt[-8:], 3)
    assert got == ref


def test_model_drafter_empty_context_and_position_budget(model):
    module, params = model
    d = ModelDrafter(module, params, draft_tokens=3, context_tokens=8)
    # empty prompt+emitted degrades to "no proposal", not a windowing error
    assert d.propose([], []) == []
    # a draft model whose position budget cannot fit one context token plus
    # the drafts is a misconfiguration that must fail at construction, not
    # overrun n_positions inside generate
    n_pos = int(module.config.n_positions)
    with pytest.raises(ValueError, match="n_positions"):
        ModelDrafter(module, params, draft_tokens=n_pos)


def test_resolve_drafter_accepts_int_config_and_drafter():
    d, k = resolve_drafter(3)
    assert isinstance(d, NGramDrafter) and k == 3
    d, k = resolve_drafter(SpeculationConfig(draft_tokens=2, max_ngram=4))
    assert isinstance(d, NGramDrafter) and k == 2 and d.max_ngram == 4

    class Custom:
        draft_tokens = 5

        def propose(self, prompt, emitted):
            return []

    d, k = resolve_drafter(Custom())
    assert isinstance(d, Custom) and k == 5
    custom = Custom()
    d, _ = resolve_drafter(SpeculationConfig(drafter=custom))
    assert d is custom  # an explicit drafter wins over the n-gram knobs
    for bad in (True, "4", 0, SpeculationConfig(draft_tokens=0)):
        with pytest.raises(ValueError):
            resolve_drafter(bad)


def test_engine_rejects_speculation_with_token_scan(model):
    module, params = model
    with pytest.raises(ValueError, match="tokens_per_sync"):
        ServingEngine(module, params, max_concurrency=1, prompt_buckets=(8,),
                      speculation=2, tokens_per_sync=4)


# ------------------------------------------------------------------ parity bar
def test_spec_parity_matrix(model):
    """THE speculation acceptance contract: spec on == spec off == solo,
    bit-for-bit, across pipeline depth x admit batch x block size,
    on a mixed greedy/sampled ragged workload (sampled slots must ride the
    verify dispatch untouched, advancing one token per forward)."""
    module, params = model
    base = _prompts(30, [3, 5, 4, 6])
    prompts = [p + p for p in base]  # repetition gives the drafter traction
    specs = [
        dict(temperature=0.0, top_k=None, seed=0),
        dict(temperature=0.9, top_k=6, seed=11),
        dict(temperature=0.0, top_k=None, seed=0),
        dict(temperature=0.7, top_k=None, seed=5),
    ]
    budgets = [7, 6, 9, 5]
    ref = [_solo(module, params, p, n, **sp)
           for p, n, sp in zip(prompts, budgets, specs)]
    for pool in (True, PagedKVConfig(block_tokens=8, num_blocks=16)):
        for depth in (1, 2):
            for admit in (1, 4):
                engine = ServingEngine(
                    module, params, max_concurrency=2, prompt_buckets=(16,),
                    max_queue=8, pipeline_depth=depth, admit_batch=admit,
                    speculation=3, paged_kv=pool)
                outs = engine.run([
                    Request(list(p), SamplingParams(max_new_tokens=n, **sp))
                    for p, n, sp in zip(prompts, budgets, specs)
                ])
                got = [o.tokens for o in sorted(outs, key=lambda o: o.request_id)]
                assert got == ref, f"pool={pool} depth={depth} admit={admit}"
                assert all(o.finish_reason == FINISH_LENGTH for o in outs)
                # the verify path actually ran and paid off its accounting
                m = engine.metrics
                assert m.spec_forwards.value > 0
                assert m.spec_tokens.value == sum(
                    len(o.tokens) for o in outs) - len(outs)  # minus prefills
                assert m.spec_accepted.value <= m.spec_proposed.value


def test_spec_parity_under_fused_attention_config(model):
    """``kv_paged_attention='fused'`` with speculation: the fused Pallas
    decode kernel is single-query, so verify segments take the gather branch
    — same pool, same tables — and parity must hold regardless."""
    module, params = model
    prompt = _prompts(31, [6])[0] * 2
    ref = _solo(module, params, prompt, 8)
    engine = ServingEngine(
        module, params, max_concurrency=2, prompt_buckets=(16,),
        speculation=2, paged_attention="fused",
        paged_kv=PagedKVConfig(block_tokens=8, num_blocks=16),
    )
    out = engine.run([Request(list(prompt), SamplingParams(max_new_tokens=8))])[0]
    assert out.tokens == ref


# ------------------------------------------------------- truncation mid-verify
def test_spec_eos_mid_verify_truncates_exactly(model):
    """EOS landing INSIDE an accepted draft run: the device clips the accept
    length at the first emitted EOS, so the stream equals the non-spec
    engine's token-for-token (including finish_reason)."""
    module, params = model
    for seed in range(5, 15):
        prompt = _prompts(seed, [6])[0]
        ref = _solo(module, params, prompt, 16)
        eos_pos = next(
            (i for i in range(1, len(ref)) if ref[i] not in ref[:i]), None)
        if eos_pos is not None:
            break
    assert eos_pos is not None
    eos = ref[eos_pos]
    # repetition after the prompt makes the drafter propose past the EOS
    prompt = prompt + prompt
    ref = _solo(module, params, prompt, 16)
    if eos in ref:
        eos_pos = ref.index(eos)
        for spec in (None, 4):
            engine = ServingEngine(module, params, max_concurrency=1,
                                   prompt_buckets=(16,), eos_token_id=eos,
                                   speculation=spec)
            out = engine.run(
                [Request(list(prompt), SamplingParams(max_new_tokens=16))])[0]
            assert out.finish_reason == FINISH_EOS, f"spec={spec}"
            assert out.tokens == ref[: eos_pos + 1], f"spec={spec}"


def test_spec_budget_shorter_than_draft_depth(model):
    """max_new_tokens < k: the accept length clips at the remaining budget
    (never past it — the write-bound proof depends on this), finishing with
    FINISH_LENGTH at exactly the requested count."""
    module, params = model
    prompt = _prompts(33, [5])[0] * 2
    for n_new in (1, 2, 3):
        ref = _solo(module, params, prompt, n_new)
        engine = ServingEngine(module, params, max_concurrency=1,
                               prompt_buckets=(16,), speculation=4)
        out = engine.run(
            [Request(list(prompt), SamplingParams(max_new_tokens=n_new))])[0]
        assert out.finish_reason == FINISH_LENGTH
        assert out.tokens == ref, f"n_new={n_new}"


# -------------------------------------------------------------------- rollback
@pytest.mark.parametrize(
    "pool", [True, PagedKVConfig(block_tokens=8, num_blocks=16)],
    ids=["default", "bt8"])
def test_spec_rollback_keeps_frontier_cursor_exact(model, pool):
    """The engine invariant speculation must preserve: after EVERY step, each
    layer's ``cache_index`` equals the host-mirrored ``_d_pos`` for every
    slot — i.e. the rejected draft suffix was rolled back to the accepted
    frontier, not left dangling (where the next dispatch would append AFTER
    garbage)."""
    module, params = model
    engine = ServingEngine(module, params, max_concurrency=2,
                           prompt_buckets=(16,), speculation=3, paged_kv=pool)
    prompts = [p + p for p in _prompts(34, [4, 6])]
    for p in prompts:
        engine.submit(Request(list(p), SamplingParams(max_new_tokens=10)))
    steps = 0
    while engine.has_work:
        engine.step()
        d_pos = np.asarray(engine._d_pos)
        index_leaves = [
            leaf for path, leaf in jax.tree_util.tree_leaves_with_path(
                engine._cache)
            if _is_index_leaf(path)
        ]
        assert index_leaves
        for leaf in index_leaves:
            np.testing.assert_array_equal(np.asarray(leaf), d_pos)
        steps += 1
        assert steps < 100
    assert engine.metrics.spec_forwards.value > 0


# ----------------------------------------------------------- watchdog + replay
@pytest.mark.fault
def test_spec_quarantine_mid_speculation_replays_exactly(model, fault_injection):
    """Poisoned logits inside a verify dispatch: the slot accepts NOTHING
    from that dispatch (device freeze + rollback), the watchdog re-prefills
    the request, and the replay is token-identical to an unpoisoned run —
    quarantine during speculation loses no tokens and corrupts none."""
    module, params = model
    prompts = [p + p for p in _prompts(10, [4, 6])]
    n_new = 8
    fault_injection(FaultSpec.poison(at_steps=(2,), slots=(1,)))
    engine = ServingEngine(module, params, max_concurrency=2,
                           prompt_buckets=(16,), speculation=2)
    outs = engine.run([Request(list(p), SamplingParams(max_new_tokens=n_new))
                       for p in prompts])
    assert engine.metrics.steps_poisoned.value == 1
    assert engine.metrics.requests_retried.value == 1
    for out, prompt in zip(outs, prompts):
        assert out.finish_reason == FINISH_LENGTH
        assert out.tokens == _solo(module, params, prompt, n_new)


# -------------------------------------------------------------- trace + metrics
def test_spec_trace_attrs_and_validation(model):
    module, params = model
    tracer = Tracer()
    prompt = _prompts(35, [5])[0] * 2
    engine = ServingEngine(module, params, max_concurrency=1,
                           prompt_buckets=(16,), speculation=3, tracer=tracer)
    engine.run([Request(list(prompt), SamplingParams(max_new_tokens=8))])
    valid = tracer.validate()
    assert valid["clean"], valid["anomalies"]
    events = tracer.events()
    disp = [e for e in events if e.kind == EV_DISPATCH
            and e.data.get("what") == "spec"]
    fetch = [e for e in events if e.kind == EV_FETCH
             and e.data.get("what") == "spec"]
    assert disp and fetch
    assert all(e.data["drafted"] == 3 for e in disp)
    assert all(e.data["tokens"] == 4 for e in disp)  # k + 1 positions
    assert all(0 <= e.data["accepted"] <= 4 for e in fetch)


def test_trace_validate_flags_overaccepted_pair():
    """The pairing invariant: a fetch reporting more accepted tokens than the
    dispatch drafted + 1 is structurally impossible — validate must flag it."""
    tracer = Tracer()
    tracer.emit(EV_DISPATCH, None, seq=0, what="spec", drafted=2, tokens=3)
    tracer.emit(EV_FETCH, None, seq=0, what="spec", accepted=4, tokens=3)
    anomalies = tracer.validate()["anomalies"]
    assert any("accepted" in a for a in anomalies), anomalies


def test_spec_metrics_accounting(model):
    """On a self-repeating greedy workload the verify step must beat plain
    decode: > 1 accepted token per forward (equivalently < 1 forward per
    accepted token — the bench gate's number), with the accept-length
    histogram populated and exported in the snapshot."""
    module, params = model
    prompt = _prompts(36, [6])[0] * 4
    engine = ServingEngine(module, params, max_concurrency=1,
                           prompt_buckets=(32,), speculation=4)
    out = engine.run([Request(list(prompt), SamplingParams(max_new_tokens=12))])[0]
    assert len(out.tokens) == 12
    m = engine.metrics
    assert m.spec_forwards.value > 0 and m.spec_tokens.value == 11
    snap = m.snapshot()
    atpf = snap["serving/accepted_tokens_per_forward"]
    assert atpf == pytest.approx(m.spec_tokens.value / m.spec_forwards.value)
    assert atpf > 1.0  # speculation actually pays on this workload
    assert snap["serving/spec_accept_len/count"] == m.spec_forwards.value
    assert snap["serving/spec_accept_len/max"] >= 1
    assert m.spec_accepted.value <= m.spec_proposed.value
