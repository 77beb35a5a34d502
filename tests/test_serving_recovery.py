"""Serving crash recovery: durable request journal, engine snapshot/resume,
and crash-exact continuation (`docs/reliability.md` "Serving recovery").

The load-bearing contract is CRASH-EXACT parity: a run that is interrupted
(journal abandoned mid-decode, or snapshot taken) and resumed on a FRESH
engine must emit, per request, exactly the tokens an uninterrupted run would
— greedy and seeded-sampling alike, with the prefix cache on, and at
``pipeline_depth > 1``. The journal's write-ahead SUBMIT record is the
durability edge: every ``SubmitResult(accepted=True)`` must reach a terminal
outcome across the restart.
"""

import importlib.util
import os
import time

import jax
import jax.numpy as jnp
import numpy as np
import pytest

flax_nn = pytest.importorskip("flax.linen")

pytestmark = [pytest.mark.serving, pytest.mark.recovery]

from accelerate_tpu.models.generation import generate
from accelerate_tpu.models.gpt2 import GPT2Config, GPT2LMHead
from accelerate_tpu.serving import (
    FINISH_LENGTH,
    REJECT_DEADLINE,
    JournalError,
    Request,
    RequestJournal,
    SamplingParams,
    ServingEngine,
)
from accelerate_tpu.serving.journal import REC_FINISH, REC_PROGRESS, REC_SUBMIT


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


def _mixed_requests(prompts, n_tokens):
    """Alternate greedy and seeded-sampling params across the prompt list."""
    return [
        Request(list(p), SamplingParams(
            max_new_tokens=n_tokens,
            temperature=0.9 if i % 2 else 0.0,
            top_k=5 if i % 2 else None,
            seed=100 + i,
        ))
        for i, p in enumerate(prompts)
    ]


def _refs(module, params, reqs):
    return {
        i: _solo(module, params, r.prompt, r.params.max_new_tokens,
                 temperature=r.params.temperature, top_k=r.params.top_k,
                 seed=r.params.seed)
        for i, r in enumerate(reqs)
    }


def _drive(engine, outputs):
    while engine.has_work:
        for out in engine.step():
            outputs[out.request_id] = out
    return outputs


# ----------------------------------------------------------------- journal unit
def test_journal_roundtrip_scan(tmp_path):
    p = tmp_path / "j.journal"
    with RequestJournal(p) as j:
        for rid in range(3):
            j.log_submit(Request([1, 2, 3 + rid],
                                 SamplingParams(max_new_tokens=8, seed=rid),
                                 request_id=rid))
        j.log_first_token(0, 7, 1)
        j.log_progress(0, [8, 9], 3)
        j.log_first_token(1, 4, 1)
        j.log_finish(1, FINISH_LENGTH, [4, 5, 6])
    scan = RequestJournal.scan(p)
    assert scan.records == 7 and scan.anomalies == 0
    assert sorted(scan.submits) == [0, 1, 2]
    assert scan.tokens[0] == [7, 8, 9]
    assert scan.finishes[1] == (FINISH_LENGTH, [4, 5, 6])
    # replay order: admitted (admission order) before queued (submit order)
    assert scan.incomplete() == [0, 2]
    assert scan.truncated_tail_bytes == 0
    # params round-trip with enough fidelity to rebuild the request
    sp = scan.submits[2]["params"]
    assert sp == {"temperature": 0.0, "top_k": None, "seed": 2,
                  "max_new_tokens": 8}


def test_journal_progress_rewind_reconstruction(tmp_path):
    """A watchdog re-prefill legitimately REWINDS the stream; the cumulative
    ``n`` on each PROGRESS record makes the rewind self-describing."""
    p = tmp_path / "j.journal"
    with RequestJournal(p) as j:
        j.log_submit(Request([1], SamplingParams(), request_id=0))
        j.log_first_token(0, 10, 1)
        j.log_progress(0, [11, 12, 13], 4)
        j.log_first_token(0, 10, 1)  # re-prefill: stream restarts at token 1
        j.log_progress(0, [11, 12], 3)
    scan = RequestJournal.scan(p)
    assert scan.anomalies == 0
    assert scan.tokens[0] == [10, 11, 12]


def test_journal_torn_tail_tolerated_and_truncated_on_reopen(tmp_path):
    p = tmp_path / "j.journal"
    with RequestJournal(p) as j:
        j.log_submit(Request([1, 2], SamplingParams(), request_id=0))
        j.log_first_token(0, 9, 1)
    with open(p, "ab") as f:
        f.write(b"\x40\x00\x00\x00\xde\xad\xbe")  # half a frame: the SIGKILL tear
    scan = RequestJournal.scan(p)
    assert scan.records == 2 and scan.anomalies == 0
    assert scan.truncated_tail_bytes == 7  # tolerated crash frontier
    # reopen must TRUNCATE the tear before appending — records written after
    # garbage would be unreachable forever (scan stops at the first bad frame)
    with RequestJournal(p) as j:
        j.log_finish(0, FINISH_LENGTH, [9, 8])
    scan = RequestJournal.scan(p)
    assert scan.truncated_tail_bytes == 0
    assert scan.records == 3 and scan.finishes[0] == (FINISH_LENGTH, [9, 8])


def test_journal_rejects_non_journal_file(tmp_path):
    p = tmp_path / "not_a_journal"
    p.write_bytes(b"definitely not a journal")
    with pytest.raises(JournalError):
        RequestJournal.scan(p)
    with pytest.raises(JournalError):
        RequestJournal(p)


def test_journal_compact_collapses_and_drops_finished(tmp_path):
    p = tmp_path / "j.journal"
    with RequestJournal(p) as j:
        for rid in range(3):
            j.log_submit(Request([rid], SamplingParams(), request_id=rid))
        j.log_first_token(0, 1, 1)
        for n in range(2, 12):
            j.log_progress(0, [n], n)
        j.log_first_token(1, 5, 1)
        j.log_finish(1, FINISH_LENGTH, [5, 6])
    before = os.path.getsize(p)
    scan = RequestJournal.compact(p)
    assert scan.records == 16  # pre-compaction view comes back
    after = RequestJournal.scan(p)
    assert os.path.getsize(p) < before
    assert after.anomalies == 0
    assert 1 not in after.submits  # finished dropped by default
    assert after.tokens[0] == list(range(1, 12))  # chain collapsed, not lost
    assert after.records_by_type == {REC_SUBMIT: 2, REC_PROGRESS: 1}
    # keep_finished variant preserves the terminal record
    with RequestJournal(p) as j:
        j.log_submit(Request([9], SamplingParams(), request_id=9))
        j.log_finish(9, FINISH_LENGTH, [7])
    RequestJournal.compact(p, keep_finished=True)
    kept = RequestJournal.scan(p)
    assert kept.finishes[9] == (FINISH_LENGTH, [7])
    assert kept.records_by_type[REC_FINISH] == 1


def test_journal_fsck_reports_frontier_and_compacts(tmp_path):
    spec = importlib.util.spec_from_file_location(
        "journal_fsck",
        os.path.join(os.path.dirname(os.path.dirname(os.path.abspath(__file__))),
                     "tools", "journal_fsck.py"))
    fsck_mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(fsck_mod)

    p = tmp_path / "j.journal"
    with RequestJournal(p) as j:
        for rid in range(2):
            j.log_submit(Request([rid, rid], SamplingParams(), request_id=rid))
        j.log_first_token(0, 3, 1)
        j.log_finish(1, FINISH_LENGTH, [2])
    with open(p, "ab") as f:
        f.write(b"\x10\x00")
    report = fsck_mod.fsck(str(p))
    assert report["clean"] and report["anomalies"] == 0
    assert report["truncated_tail_bytes"] == 2
    assert report["submitted"] == 2 and report["finished"] == 1
    assert report["in_flight"] == [{"rid": 0, "tokens": 1}]
    compacted = fsck_mod.fsck(str(p), compact=True)
    assert compacted["compacted_bytes"] == os.path.getsize(p)
    assert fsck_mod.fsck(str(p))["finished"] == 0


# ------------------------------------------------------- crash-exact resume
def test_resume_from_journal_is_crash_exact(model, tmp_path):
    """Kill-and-resume via the journal: a fresh engine continues every
    interrupted stream mid-flight, bit-for-bit — greedy and seeded sampling.
    The resuming engine runs with a `Tracer` attached: the crash-replay
    stream (every surviving rid re-enters as EV_SUBMIT recovered=True) must
    pass the same trace invariants as a fresh run."""
    from accelerate_tpu.serving import Tracer
    from accelerate_tpu.serving.trace import EV_SUBMIT, request_streams

    module, params = model
    jpath = tmp_path / "requests.journal"
    reqs = _mixed_requests(_prompts(0, (5, 9, 14, 7)), 12)
    # request 0 finishes BEFORE the crash: the dedup path must not re-run it
    reqs[0] = Request(reqs[0].prompt, SamplingParams(max_new_tokens=3, seed=100))
    refs = _refs(module, params, reqs)

    a = ServingEngine(module, params, max_concurrency=2,
                      prompt_buckets=(16,), journal=jpath)
    for r in reqs:
        assert a.submit(Request(list(r.prompt), r.params)).accepted
    pre = {}
    for _ in range(6):  # some requests finish, some are mid-flight, some queued
        for out in a.step():
            pre[out.request_id] = out
    del a  # simulated SIGKILL: the fsync'd journal is all that survives

    tracer = Tracer()
    b = ServingEngine(module, params, max_concurrency=2,
                      prompt_buckets=(16,), journal=jpath, tracer=tracer)
    report = b.resume()
    assert set(report.completed) == set(pre)  # dedup: finished never re-run
    assert set(report.resumed) | set(report.restored) == set(refs) - set(pre)
    assert report.resumed, "at least one request must resume MID-stream"
    final = dict(report.completed)
    _drive(b, final)
    assert {rid: o.tokens for rid, o in final.items()} == refs
    assert b.metrics.requests_resumed.value == len(report.resumed)
    assert b.metrics.replayed_tokens.value > 0
    # a mid-stream resume splits its key chain on the device; the rest are host rows
    assert b.metrics.admit_device_keys.value == len(report.resumed)
    assert b.metrics.admit_host_keys.value == len(report.restored)
    valid = tracer.validate()
    assert valid["clean"], valid["anomalies"]
    streams = request_streams(tracer.events())
    # every rid the resume REPLAYED has a stream (journal-finished requests
    # are dedup'd at scan time — never re-run, never re-traced), every stream
    # opens with the recovery-flagged SUBMIT, and the mid-stream resumes
    # carry their replayed prefix length
    assert set(streams) == set(refs) - set(pre)
    for rid, stream in streams.items():
        assert stream[0].kind == EV_SUBMIT and stream[0].data.get("recovered")
    for rid in report.resumed:
        assert streams[rid][0].data["resumed"] > 0


def test_resume_parity_with_prefix_cache_and_pipeline(model, tmp_path):
    """The acceptance bar: crash-exact parity must hold with the prefix cache
    ON and ``pipeline_depth > 1`` — resumed continuation prefills bypass the
    block pool, and lagged in-flight dispatches must replay cleanly."""
    module, params = model

    def build(jpath):
        return ServingEngine(
            module, params, max_concurrency=2, prompt_buckets=(16, 32),
            pipeline_depth=2, prefix_cache=True,
            journal=jpath)

    base = _prompts(7, (17, 23))
    prompts = base + [list(base[0]), list(base[1])]  # duplicates: cache hits
    reqs = _mixed_requests(prompts, 8)
    refs = _refs(module, params, reqs)

    jpath = tmp_path / "requests.journal"
    a = build(jpath)
    for r in reqs:
        assert a.submit(Request(list(r.prompt), r.params)).accepted
    pre = {}
    for _ in range(5):
        for out in a.step():
            pre[out.request_id] = out
    del a

    b = build(jpath)
    report = b.resume()
    final = dict(report.completed)
    final.update(pre)
    _drive(b, final)
    assert {rid: o.tokens for rid, o in final.items()} == refs


def test_snapshot_restore_is_crash_exact(model, tmp_path):
    """Snapshot (the SIGTERM drain path) instead of the journal: same parity
    bar, queue order and in-flight progress restored from one JSON file."""
    module, params = model
    # same (length, budget, sampling) shapes as the journal test: the solo
    # reference `generate` traces are shared, only the token data differs
    reqs = _mixed_requests(_prompts(3, (5, 9, 14)), 12)
    # request 0 retires pre-snapshot, freeing its slot for the queued tail
    reqs[0] = Request(reqs[0].prompt, SamplingParams(max_new_tokens=3, seed=100))
    refs = _refs(module, params, reqs)

    a = ServingEngine(module, params, max_concurrency=2, prompt_buckets=(16,))
    for r in reqs:
        assert a.submit(Request(list(r.prompt), r.params)).accepted
    pre = {}
    for _ in range(5):
        for out in a.step():
            pre[out.request_id] = out
    snap = tmp_path / "engine.snap"
    for out in a.snapshot(snap):
        pre[out.request_id] = out

    b = ServingEngine(module, params, max_concurrency=2, prompt_buckets=(16,))
    report = b.resume(snap)
    assert not report.expired
    final = dict(report.completed)
    final.update(pre)
    _drive(b, final)
    assert {rid: o.tokens for rid, o in final.items()} == refs


@pytest.mark.paged
def test_resume_from_journal_paged_crash_exact(model, tmp_path):
    """Journal kill-and-resume with PAGED KV (+ prefix cache + pipelining):
    `resume()` re-prefills every surviving stream into freshly allocated
    blocks — no block id survives the crash, only tokens do — and parity
    must hold with the pool accounting clean after the drain."""
    module, params = model

    def build(jpath):
        return ServingEngine(
            module, params, max_concurrency=2, prompt_buckets=(16, 32),
            pipeline_depth=2, paged_kv=True,
            prefix_cache=True, journal=jpath)

    base = _prompts(7, (17, 23))
    prompts = base + [list(base[0]), list(base[1])]  # duplicates: cache hits
    reqs = _mixed_requests(prompts, 8)
    refs = _refs(module, params, reqs)

    jpath = tmp_path / "requests.journal"
    a = build(jpath)
    for r in reqs:
        assert a.submit(Request(list(r.prompt), r.params)).accepted
    pre = {}
    for _ in range(5):
        for out in a.step():
            pre[out.request_id] = out
    del a

    b = build(jpath)
    report = b.resume()
    final = dict(report.completed)
    final.update(pre)
    _drive(b, final)
    assert {rid: o.tokens for rid, o in final.items()} == refs
    mem = b.memory_stats()
    assert mem["block_pool/blocks_pinned"] == 0
    assert mem["block_pool/blocks_private"] == 0
    assert (mem["block_pool/blocks_free"] + mem["block_pool/blocks_resident"]
            == mem["block_pool/blocks_total"])


@pytest.mark.paged
def test_resume_from_journal_multi_token_crash_exact(model, tmp_path):
    """Journal kill-and-resume with ``tokens_per_sync=4``: the crash abandons
    a dispatch that carried up to 4 un-journaled tokens per slot, and the
    journal's PROGRESS cadence batches multi-token fetches — resume must
    still continue every stream bit-for-bit (the rng fast-forward replays
    whole tokens, never partial scans). Crossed with the fused kernel so the
    restarted engine re-prefills into pool blocks the Pallas path reads."""
    module, params = model

    def build(jpath, pa):
        return ServingEngine(
            module, params, max_concurrency=2, prompt_buckets=(16, 32),
            pipeline_depth=2, paged_kv=True, tokens_per_sync=4,
            paged_attention=pa, journal=jpath)

    prompts = _prompts(5, (17, 23, 9, 12))
    reqs = _mixed_requests(prompts, 11)
    refs = _refs(module, params, reqs)

    for pa in ("gather", "fused"):
        jpath = tmp_path / f"requests-{pa}.journal"
        a = build(jpath, pa)
        for r in reqs:
            assert a.submit(Request(list(r.prompt), r.params)).accepted
        pre = {}
        for _ in range(2):  # mid-flight: 11-token budgets need 3 dispatches
            for out in a.step():
                pre[out.request_id] = out
        del a

        b = build(jpath, pa)
        report = b.resume()
        assert report.resumed or report.restored
        final = dict(report.completed)
        final.update(pre)
        _drive(b, final)
        assert {rid: o.tokens for rid, o in final.items()} == refs, pa
        assert b.metrics.tokens_per_dispatch.count > 0


@pytest.mark.paged
def test_snapshot_restore_paged_crash_exact(model, tmp_path):
    """Snapshot/restore with paged KV and no trie: the same crash-exact bar,
    and the restored engine's pool must drain back to fully free."""
    module, params = model
    reqs = _mixed_requests(_prompts(3, (5, 9, 14)), 12)
    reqs[0] = Request(reqs[0].prompt, SamplingParams(max_new_tokens=3, seed=100))
    refs = _refs(module, params, reqs)

    def build():
        return ServingEngine(module, params, max_concurrency=2,
                             prompt_buckets=(16,), paged_kv=True)

    a = build()
    for r in reqs:
        assert a.submit(Request(list(r.prompt), r.params)).accepted
    pre = {}
    for _ in range(5):
        for out in a.step():
            pre[out.request_id] = out
    snap = tmp_path / "engine.snap"
    for out in a.snapshot(snap):
        pre[out.request_id] = out
    # the abandoned engine's reservations die with it; the fresh one below
    # re-reserves from its own full pool
    b = build()
    report = b.resume(snap)
    assert not report.expired
    final = dict(report.completed)
    final.update(pre)
    _drive(b, final)
    assert {rid: o.tokens for rid, o in final.items()} == refs
    mem = b.memory_stats()
    assert mem["block_pool/blocks_free"] == mem["block_pool/blocks_total"]


def test_resume_requires_idle_engine(model, tmp_path):
    module, params = model
    jpath = tmp_path / "requests.journal"
    with RequestJournal(jpath) as j:
        j.log_submit(Request([1, 2], SamplingParams(max_new_tokens=2),
                             request_id=0))
    b = ServingEngine(module, params, max_concurrency=1, prompt_buckets=(16,),
                      journal=jpath)
    b.submit(Request([3, 4], SamplingParams(max_new_tokens=2)))
    with pytest.raises(RuntimeError):
        b.resume()


# ------------------------------------------------------- deadline accounting
@pytest.fixture(scope="module")
def downtime_restore(model, tmp_path_factory):
    """One snapshot holding BOTH deadline cases: requests 0/1 are ADMITTED
    (mid-stream, deadlines already satisfied by their first token), request 2
    is QUEUED with a 0.2s queue-wait budget that downtime alone will blow.
    Same engine/ref shapes as the parity tests above: every trace is shared."""
    module, params = model
    snap = tmp_path_factory.mktemp("deadline") / "engine.snap"
    prompt = _prompts(11, (14,))[0]
    a = ServingEngine(module, params, max_concurrency=2, prompt_buckets=(16,))
    a.submit(Request(list(prompt), SamplingParams(max_new_tokens=12),
                     deadline_s=0.2))
    a.submit(Request(_prompts(12, (5,))[0], SamplingParams(max_new_tokens=3)))
    a.step()  # both slots admitted: first tokens emitted
    a.submit(Request([4, 5], SamplingParams(max_new_tokens=4), deadline_s=0.2))
    a.snapshot(snap)

    time.sleep(0.35)  # downtime alone blows the 0.2s queue-wait budget
    b = ServingEngine(module, params, max_concurrency=2, prompt_buckets=(16,))
    report = b.resume(snap)
    return b, report, _drive(b, {}), prompt


def test_queued_deadline_elapsed_during_downtime_expires_on_restore(
        downtime_restore):
    """A QUEUED request whose wall-clock deadline passed while the process was
    down must be expired (and reported) at restore — not silently dropped,
    not served to a client that already gave up."""
    b, report, final, _ = downtime_restore
    assert [o.request_id for o in report.expired] == [2]
    assert report.expired[0].finish_reason == f"rejected:{REJECT_DEADLINE}"
    assert b.metrics.requests_expired.value == 1
    assert report.downtime_s >= 0.35
    assert 2 not in final


def test_restored_inflight_request_never_instantly_expires(
        model, downtime_restore):
    """An ADMITTED (mid-stream) request consumed its queue-wait budget before
    the crash; downtime must not retroactively expire it at restore."""
    module, params = model
    _, report, final, prompt = downtime_restore
    assert sorted(report.resumed) == [0, 1]
    assert final[0].finish_reason == FINISH_LENGTH
    assert final[0].tokens == _solo(module, params, prompt, 12)


# ------------------------------------------------- subprocess crash scenarios
@pytest.mark.slow
def test_crash_sigkill_zero_lost_zero_drift():
    import tools.chaos_serve as chaos_serve

    summary = chaos_serve.run_crash("sigkill", n_requests=8, concurrency=2)
    assert summary["value"] == 0
    assert summary["detail"]["parity_drift"] == 0
    assert summary["detail"]["child_exit_code"] == -9
    assert summary["detail"]["resume_source"] == "journal"


@pytest.mark.slow
def test_crash_sigterm_drains_then_snapshots():
    import tools.chaos_serve as chaos_serve

    summary = chaos_serve.run_crash("sigterm", n_requests=8, concurrency=2)
    assert summary["value"] == 0
    assert summary["detail"]["parity_drift"] == 0
    assert summary["detail"]["child_exit_code"] == 143
