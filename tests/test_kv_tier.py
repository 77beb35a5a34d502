"""Host-RAM KV tier + request hibernation (`serving/kv_tier.py`,
docs/serving.md "KV tiering & hibernation").

The load-bearing contract: PARITY — tier-on greedy (and sampled) token
streams are bit-for-bit equal to tier-off and solo, including a forced
spill -> page-in mid-decode and a forced hibernate -> wake, under both wake
policies, across the paged-attention x pipeline-depth matrix. ACCOUNTING —
the device ledger (free + resident + private == total) never moves except
through all-or-nothing transitions, and the host ledger keeps
``bytes == blocks * block_bytes`` at every step. POLICY — spill picks LRU
unpinned leaves (device-backed => parent device-backed stays invariant),
hibernation picks the coldest slots, the wake cost model never bets an
unproven path, and the thrash guard's enter/exit hysteresis cannot flap.
DURABILITY — a crash mid-spill loses nothing: the journal (not host RAM)
is the durable tier, and resume replays bit-exact.
"""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

flax_nn = pytest.importorskip("flax.linen")

pytestmark = [pytest.mark.serving, pytest.mark.paged, pytest.mark.tier]

from accelerate_tpu.models.generation import generate
from accelerate_tpu.models.gpt2 import GPT2Config, GPT2LMHead
from accelerate_tpu.serving import (
    PagedKVConfig,
    Request,
    RequestJournal,
    SamplingParams,
    ServingEngine,
)
from accelerate_tpu.serving.kv_tier import (
    KVTierConfig,
    ThrashGuard,
    choose_wake,
)

BT = 16  # GPT2Config.tiny has n_positions=128 -> 8 blocks per slot at 16


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


def _requests(prompts, n_new=10, greedy=True):
    return [
        Request(prompt=list(p),
                params=SamplingParams(
                    max_new_tokens=n_new,
                    temperature=0.0 if greedy else 0.8,
                    top_k=None if greedy else 7,
                    seed=i,
                ))
        for i, p in enumerate(prompts)
    ]


def _conservation(engine):
    """Device + host ledger invariants, asserted at every transition."""
    mem = engine.memory_stats()
    assert (mem["block_pool/blocks_free"]
            + mem["block_pool/blocks_resident"]
            + mem["block_pool/blocks_private"]
            == mem["block_pool/blocks_total"])
    assert (mem["host_tier/bytes"]
            == mem["host_tier/blocks"] * mem["host_tier/block_bytes"])
    return mem


def _drain(engine, outs, force_hibernate=False):
    """Step to empty, collecting ``{rid: tokens}``. With ``force_hibernate``,
    parks EVERY active slot the first time one has >= 2 emitted tokens —
    mid-decode, so the wake path re-enters a half-written stream."""
    forced = not force_hibernate
    while engine.has_work:
        for o in engine.step():
            outs[o.request_id] = o.tokens
        if not forced:
            ready = [int(s) for s in np.flatnonzero(engine._active)
                     if engine._slot_out[s] is not None
                     and len(engine._slot_out[s].tokens) >= 2]
            if ready:
                for s in ready:
                    engine.kv_tier.hibernate_slot(s)
                forced = True
        if engine.kv_tier is not None:
            _conservation(engine)
    assert forced, "hibernation was never forced — the scenario proves nothing"
    return outs


# --------------------------------------------------------- wake cost model
def test_choose_wake_cost_model():
    """Upload wins exactly when restoring host bytes beats replaying the
    stream; any unmeasured rate (or nothing on host) means prefill — never
    bet an unproven path on a guess."""
    # 1 KB at 1 MB/s = 1 ms upload vs 100 tokens at 10 tok/s = 10 s replay
    assert choose_wake(1000, 100, 1e6, 10.0) == "upload"
    # 1 GB at 1 KB/s vs 10 tokens at 1M tok/s: replay wins
    assert choose_wake(10**9, 10, 1e3, 1e6) == "prefill"
    # unmeasured rates -> prefill, whichever side is missing
    assert choose_wake(1000, 100, 0.0, 10.0) == "prefill"
    assert choose_wake(1000, 100, 1e6, 0.0) == "prefill"
    assert choose_wake(0, 100, 1e6, 10.0) == "prefill"
    # exact tie -> prefill (strict inequality: the proven path by default)
    assert choose_wake(1000, 10, 100.0, 1.0) == "prefill"


# ------------------------------------------------------- thrash hysteresis
def test_thrash_guard_hysteresis_with_injected_clock():
    """Freeze on the enter edge, unfreeze only after the window stays calm
    for ``exit_s`` continuous seconds; a burst during the calm period resets
    the timer. Both transitions are edges (True exactly once)."""
    t = [0.0]
    g = ThrashGuard(window_s=10.0, enter_events=4, exit_fraction=0.5,
                    exit_s=5.0, clock=lambda: t[0])
    assert g.exit_events == 2
    assert g.record(3) is False and not g.frozen
    assert g.record(1) is True and g.frozen       # enter edge
    assert g.record(5) is False and g.frozen       # no re-edge while frozen
    assert g.poll() is False                       # window still hot
    t[0] = 11.0                                    # everything pruned
    assert g.poll() is False and g.frozen          # calm starts, not yet exit_s
    t[0] = 15.9
    assert g.poll() is False and g.frozen          # 4.9 s calm < 5 s
    t[0] = 14.0
    g.record(3)                                    # burst: window > exit_events
    t[0] = 16.5
    assert g.poll() is False                       # calm reset by the burst
    t[0] = 24.5                                    # burst pruned; calm restarts
    assert g.poll() is False
    t[0] = 29.4
    assert g.poll() is False and g.frozen
    t[0] = 29.6
    assert g.poll() is True and not g.frozen       # exit edge
    assert g.poll() is False                       # no re-edge
    assert g.window_events == 0                    # clean slate after exit
    assert g.record(4) is True and g.frozen        # hysteresis re-arms


def test_config_validation(model):
    module, params = model
    with pytest.raises(ValueError, match="wake_policy"):
        KVTierConfig(wake_policy="teleport")
    with pytest.raises(ValueError, match="min_resident_slots"):
        KVTierConfig(min_resident_slots=-1)
    with pytest.raises(ValueError, match="thrash_enter_events"):
        KVTierConfig(thrash_enter_events=0)
    with pytest.raises(ValueError, match="removed"):
        ServingEngine(module, params, max_concurrency=2, prompt_buckets=(16,),
                      kv_tier=True, paged_kv=False)
    # the tier sits behind the pool every engine has
    assert ServingEngine(module, params, max_concurrency=2, prompt_buckets=(16,),
                         kv_tier=True).kv_tier is not None


# ----------------------------------------------------------- spill ordering
def test_trie_spill_picks_lru_leaf_and_keeps_invariant(model):
    """`_spill_victim` takes the least-recently-used unpinned node with no
    device-backed child, so device-backed => parent device-backed holds
    after every single spill — the precondition for top-down page-in."""
    module, params = model
    engine = ServingEngine(module, params, max_concurrency=4,
                           prompt_buckets=(16, 64), admit_batch=4,
                           prefix_cache=True,
                           paged_kv=PagedKVConfig(block_tokens=BT,
                                                  num_blocks=48),
                           kv_tier=True)
    tier = engine.kv_tier
    prompts = _prompts(23, (40, 40, 21, 9))
    prompts[1] = list(prompts[0])  # shared prefix -> multi-level trie chain
    for o in engine.run(_requests(prompts)):
        assert o.tokens
    pc = engine.prefix_cache
    assert pc.node_count() > 0

    def eligible():
        out, stack = [], list(pc._root.children.values())
        while stack:
            n = stack.pop()
            stack.extend(n.children.values())
            if (n.ref == 0 and n.block_id is not None
                    and not any(c.block_id is not None
                                for c in n.children.values())):
                out.append(n)
        return out

    # LRU choice: stamp distinct recencies on the current frontier
    cands = eligible()
    assert len(cands) >= 2
    for i, n in enumerate(sorted(cands, key=id)):
        n.last_used = 100.0 + i
    coldest = min(cands, key=lambda n: n.last_used)
    assert tier._spill_victim() is coldest

    # spill one block at a time; the trie invariant must hold after EACH
    spilled = 0
    while tier.page_out_trie(1):
        spilled += 1
        stack = [(pc._root, True)]
        while stack:
            node, parent_backed = stack.pop()
            if node is not pc._root and node.block_id is not None:
                assert parent_backed, (
                    "device-backed node under a spilled parent")
            backed = node is pc._root or node.block_id is not None
            stack.extend((c, backed) for c in node.children.values())
        _conservation(engine)
    assert spilled > 0 and tier.trie_host_blocks == spilled
    assert int(engine.metrics.host_page_outs.value) >= spilled


def test_page_in_is_all_or_nothing(model):
    """A page-in that cannot allocate changes NOTHING — no gauge moves, the
    host copy stays, and the node stays hit-able for a later retry."""
    module, params = model
    engine = ServingEngine(module, params, max_concurrency=2,
                           prompt_buckets=(16, 64),
                           prefix_cache=True,
                           paged_kv=PagedKVConfig(block_tokens=BT,
                                                  num_blocks=24),
                           kv_tier=True)
    tier = engine.kv_tier
    for o in engine.run(_requests(_prompts(29, (40, 21)))):
        assert o.tokens
    victim = tier._spill_victim()
    assert victim is not None
    tier._spill_node(victim)
    assert victim.block_id is None and tier.trie_host_blocks == 1

    hog = engine._allocator.alloc(engine._allocator.free_count)
    before = (_conservation(engine), tier.memory_stats())
    assert tier.page_in_node(victim) is False  # pool full -> refuse whole
    assert (_conservation(engine), tier.memory_stats()) == before
    assert victim.block_id is None and tier.trie_blocks.get(victim) is not None

    engine._allocator.free(hog)
    assert tier.page_in_node(victim) is True   # retry succeeds bit-exact
    assert victim.block_id is not None and tier.trie_host_blocks == 0
    assert int(engine.metrics.host_page_ins.value) == 1
    _conservation(engine)


# ------------------------------------------------------- hibernation policy
def test_hibernation_victim_ordering(model):
    """Coldest first: long-idle slots by descending idleness, then the rest
    in arrival order; a slot inside its wake cooldown is exempt."""
    module, params = model
    engine = ServingEngine(module, params, max_concurrency=3,
                           prompt_buckets=(16,), admit_batch=3,
                           paged_kv=PagedKVConfig(block_tokens=BT),
                           kv_tier=KVTierConfig(hibernate_idle_s=30.0,
                                                wake_cooldown_s=10.0))
    tier = engine.kv_tier
    for r in _requests(_prompts(31, (6, 7, 8)), n_new=20):
        assert engine.submit(r).accepted
    for _ in range(4):
        engine.step()
    slots = [int(s) for s in np.flatnonzero(engine._active)]
    assert len(slots) == 3
    assert all(engine._slot_out[s].tokens for s in slots)

    now = 1000.0
    engine._slot_last_token_t[slots[0]] = now - 1.0    # fresh
    engine._slot_last_token_t[slots[1]] = now - 2.0    # fresh, later arrival
    engine._slot_last_token_t[slots[2]] = now - 100.0  # long idle
    assert tier._victims(now) == [slots[2], slots[0], slots[1]]

    engine._slot_last_token_t[slots[1]] = now - 50.0   # long idle, but less
    assert tier._victims(now) == [slots[2], slots[1], slots[0]]

    rid0 = engine._slot_req[slots[0]].request_id
    tier._wake_t[rid0] = now - 1.0                     # inside cooldown
    assert tier._victims(now) == [slots[2], slots[1]]


def test_hibernated_cancel_and_ledger_drain(model):
    """Cancel reaches a hibernated record: the terminal carries the parked
    tokens, and the host ledger drains to zero — nothing leaks."""
    module, params = model
    engine = ServingEngine(module, params, max_concurrency=2,
                           prompt_buckets=(16,), admit_batch=2,
                           paged_kv=PagedKVConfig(block_tokens=BT),
                           kv_tier=True)
    tier = engine.kv_tier
    reqs = _requests(_prompts(37, (6, 9)), n_new=16)
    for r in reqs:
        assert engine.submit(r).accepted
    for _ in range(4):
        engine.step()
    slot = next(int(s) for s in np.flatnonzero(engine._active)
                if engine._slot_out[int(s)].tokens)
    rid = engine._slot_req[slot].request_id
    parked = list(engine._slot_out[slot].tokens)
    assert tier.hibernate_slot(slot) > 0
    assert tier.hibernated_count == 1 and tier.host_blocks > 0
    _conservation(engine)

    out = engine.cancel(rid)
    assert out is not None and out.tokens == parked
    assert tier.hibernated_count == 0 and tier.host_blocks == 0
    mem = _conservation(engine)
    assert mem["host_tier/bytes"] == 0
    # the survivor drains normally
    while engine.has_work:
        engine.step()


# ------------------------------------------------------------------- parity
@pytest.fixture(scope="module")
def tier_refs(model):
    module, params = model
    prompts = _prompts(11, (5, 21, 40, 9))
    return prompts, {i: _solo(module, params, p, 10, seed=i)
                     for i, p in enumerate(prompts)}


@pytest.mark.parametrize("pa", ["gather", "fused"])
@pytest.mark.parametrize("depth", [1, 2])
def test_tier_parity_matrix(model, tier_refs, pa, depth):
    """Tier-on == tier-off == solo, bit-for-bit, across paged-attention x
    pipeline-depth — through a FORCED mid-decode hibernate -> wake of every
    active slot, then a forced full trie spill -> page-in replay (prefix
    hits land on host-resident blocks and restore instead of recompute)."""
    module, params = model
    prompts, refs = tier_refs
    kw = dict(max_concurrency=4, prompt_buckets=(16, 64), pipeline_depth=depth,
              admit_batch=4, paged_attention=pa,
              prefix_cache=True,
              paged_kv=PagedKVConfig(block_tokens=BT, num_blocks=48))
    off = ServingEngine(module, params, **kw)
    assert {o.request_id: o.tokens for o in off.run(_requests(prompts))} == refs

    on = ServingEngine(module, params,
                       kv_tier=KVTierConfig(wake_policy="upload"), **kw)
    reqs = _requests(prompts)
    for r in reqs:
        assert on.submit(r).accepted
    assert _drain(on, {}, force_hibernate=True) == refs
    m = on.metrics
    assert int(m.host_hibernated.value) >= 1
    assert int(m.host_wakeups.value) >= 1

    # spill the donated prefixes wholesale, then replay the same prompts:
    # the trie hit must page in, not recompute — and stay bit-exact
    assert on.kv_tier.page_out_trie(64) > 0
    page_ins_before = int(m.host_page_ins.value)
    replay = _requests(prompts)
    for r in replay:
        assert on.submit(r).accepted
    outs = _drain(on, {})
    assert [outs[r.request_id] for r in replay] == [refs[i] for i in range(4)]
    assert int(m.host_page_ins.value) > page_ins_before
    # drained tier: nothing hibernated, spill not frozen
    mem = on.memory_stats()
    assert mem["host_tier/hibernated"] == 0 and mem["host_tier/spill_frozen"] == 0


@pytest.mark.parametrize("policy", ["upload", "prefill"])
@pytest.mark.parametrize("greedy", [True, False], ids=["greedy", "sampled"])
def test_hibernate_wake_bit_exact_both_policies(model, policy, greedy):
    """Both wake paths resume a half-decoded stream bit-for-bit — upload
    restores the exact KV bytes and rng state, prefill replays through the
    journal-proven continuation lane — for greedy AND sampled streams.
    Forced-upload wake is the only page-in source here (no prefix cache),
    so the counters separate the two paths."""
    module, params = model
    prompts = _prompts(13, (5, 18, 33))
    kw = dict(max_concurrency=3, prompt_buckets=(16, 64), pipeline_depth=2,
              admit_batch=3, paged_kv=PagedKVConfig(block_tokens=BT))
    off = ServingEngine(module, params, **kw)
    refs = {o.request_id: o.tokens
            for o in off.run(_requests(prompts, n_new=12, greedy=greedy))}

    on = ServingEngine(module, params,
                       kv_tier=KVTierConfig(wake_policy=policy), **kw)
    for r in _requests(prompts, n_new=12, greedy=greedy):
        assert on.submit(r).accepted
    assert _drain(on, {}, force_hibernate=True) == refs
    assert int(on.metrics.host_wakeups.value) >= 1
    page_ins = int(on.metrics.host_page_ins.value)
    assert page_ins > 0 if policy == "upload" else page_ins == 0


def test_pressure_spill_then_admit_parity(model):
    """A pool too small for the offered load admits anyway — release_for
    hibernates the coldest slots instead of stalling — and every stream
    still finishes bit-exact. Conservation holds at each step."""
    module, params = model
    prompts = _prompts(41, (40, 37, 40, 33))
    refs = {i: _solo(module, params, p, 12, seed=i)
            for i, p in enumerate(prompts)}
    engine = ServingEngine(module, params, max_concurrency=3,
                           prompt_buckets=(16, 64), admit_batch=1,
                           max_queue=8,
                           paged_kv=PagedKVConfig(block_tokens=BT,
                                                  num_blocks=10),
                           kv_tier=KVTierConfig(min_resident_slots=1,
                                                thrash_enter_events=10_000))
    for r in _requests(prompts, n_new=12):
        assert engine.submit(r).accepted
    outs = _drain(engine, {})  # no nudge: pressure alone must hibernate
    assert outs == refs
    assert int(engine.metrics.host_hibernated.value) >= 1
    assert engine.kv_tier.host_blocks == 0  # ledger fully drained


# --------------------------------------------------------------- durability
def test_crash_exact_resume_mid_spill(model, tmp_path):
    """SIGKILL semantics without the process dance: an engine with journaled
    progress is abandoned mid-spill (hibernated records AND spilled trie
    blocks live only in volatile host RAM), and a fresh tier-on engine
    resumes from the journal alone — zero lost, tokens bit-exact."""
    module, params = model
    journal = str(tmp_path / "serve.journal")
    prompts = _prompts(19, (6, 21, 40, 9))
    refs = {i: _solo(module, params, p, 12, seed=i)
            for i, p in enumerate(prompts)}
    kw = dict(max_concurrency=4, prompt_buckets=(16, 64), admit_batch=4,
              prefix_cache=True,
              paged_kv=PagedKVConfig(block_tokens=BT, num_blocks=48))
    a = ServingEngine(module, params, journal=journal, kv_tier=True, **kw)
    for r in _requests(prompts, n_new=12):
        assert a.submit(r).accepted
    def mid_decode():
        slots = [int(s) for s in np.flatnonzero(a._active)]
        return len(slots) == 4 and all(
            len(a._slot_out[s].tokens) >= 2 for s in slots)

    while not mid_decode():
        a.step()
    tier = a.kv_tier
    for s in [int(s) for s in np.flatnonzero(a._active)][:2]:
        assert tier.hibernate_slot(s) > 0
    assert tier.hibernated_count == 2
    assert tier.page_out_trie(64) >= 0  # spill whatever donation left behind
    # abandoned here: no drain, no snapshot — host buffers die with it

    scan = RequestJournal.scan(journal)
    assert len(scan.submits) == 4 and not scan.finishes
    b = ServingEngine(module, params, journal=journal, kv_tier=True, **kw)
    report = b.resume(journal)
    outcomes = {rid: out.tokens for rid, out in report.completed.items()}
    while b.has_work:
        for o in b.step():
            outcomes[o.request_id] = o.tokens
    lost = sorted(rid for rid in scan.submits if rid not in outcomes)
    assert not lost, f"requests lost across crash + resume: {lost}"
    assert outcomes == refs
    mem = b.memory_stats()
    assert mem["slots_active"] == 0 and mem["host_tier/hibernated"] == 0
