"""The host-span ring (`utils/spans.py`) and what feeds it: the serving step,
the train step, the loader; and `RequestOutput.token_times`."""

import gc
import math
import subprocess
import sys

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from accelerate_tpu.utils import spans

flax_nn = pytest.importorskip("flax.linen")

from accelerate_tpu.models.gpt2 import GPT2Config, GPT2LMHead  # noqa: E402
from accelerate_tpu.serving import (  # noqa: E402
    FINISH_LENGTH,
    Request,
    SamplingParams,
    ServingEngine,
    Tracer,
)
from accelerate_tpu.serving.trace import EV_DISPATCH, EV_FETCH  # noqa: E402


@pytest.fixture(scope="module")
def model():
    cfg = GPT2Config.tiny(dtype=jnp.float32)
    module = GPT2LMHead(cfg)
    return module, module.init_params(jax.random.key(0))


def _serve(model, n_requests=6, new_tokens=5, **engine_args):
    """Serve a few requests with a clean ring; returns (engine, outputs, spans)."""
    module, params = model
    engine_args.setdefault("max_concurrency", 4)
    engine = ServingEngine(module, params, prompt_buckets=(8, 16), **engine_args)
    rng = np.random.default_rng(0)
    requests = [Request(prompt=rng.integers(0, 256, (3 + i,)).tolist(),
                        params=SamplingParams(max_new_tokens=new_tokens))
                for i in range(n_requests)]
    spans.RING.clear()
    outputs = engine.run(requests)
    return engine, outputs, spans.RING.snapshot()


# ------------------------------------------------------------------- the ring
def test_ring_is_bounded_ordered_and_counts_drops():
    ring = spans.SpanRing(maxlen=4)
    for i in range(10):
        with spans.span("unit", ring=ring, i=i):
            pass
    held = ring.snapshot()
    assert len(held) == len(ring) == ring.maxlen == 4 and ring.dropped == 6
    assert [s[4]["i"] for s in held] == [6, 7, 8, 9]  # oldest dropped first
    assert all(a[2] <= b[1] for a, b in zip(held, held[1:]))  # in order of ending
    assert all(name == "unit" and start <= end and parent == 0
               for name, start, end, parent, _ in held)
    assert ring.snapshot("other") == []
    ring.clear()
    assert len(ring) == 0 and ring.dropped == 0
    with pytest.raises(ValueError):
        spans.SpanRing(maxlen=0)


def test_spans_opened_inside_a_step_span_name_it_as_their_parent():
    ring = spans.SpanRing()
    with spans.span("before", ring=ring):
        pass
    with spans.span("step", is_step=True, ring=ring, step=7) as step:
        with spans.span("child", ring=ring):
            pass
        with spans.span("probe", ring=ring) as probe:
            probe.drop()
    with spans.span("after", ring=ring):
        pass
    before, child, held, after = ring.snapshot()
    assert held[4] == {"id": step.attrs["id"], "step": 7} and held[4]["id"] != 0
    assert child[0] == "child" and child[3] == held[4]["id"] and child[4] == {}
    assert before[3] == held[3] == after[3] == 0  # a step span has no parent itself


def test_a_step_on_another_thread_is_no_parent_here():
    import threading

    ring = spans.SpanRing()
    inside, leave = threading.Event(), threading.Event()

    def other():
        with spans.span("step", is_step=True, ring=ring):
            inside.set()
            leave.wait(10)

    thread = threading.Thread(target=other)
    thread.start()
    inside.wait(10)
    with spans.span("mine", ring=ring):
        pass
    leave.set()
    thread.join()
    mine, step = ring.snapshot()
    assert mine[0] == "mine" and mine[3] == 0 and step[4]["id"] != 0


def test_record_adds_a_span_timed_elsewhere_under_the_open_step():
    ring = spans.SpanRing()
    spans.record("outside", 1.0, 2.0, ring=ring, rid=3)
    with spans.span("step", is_step=True, ring=ring) as step:
        spans.record("inside", 0.5, 4.0, ring=ring, rid=4, seq=9)
    outside, inside, held = ring.snapshot()
    assert outside == ("outside", 1.0, 2.0, 0, {"rid": 3})
    assert inside == ("inside", 0.5, 4.0, step.attrs["id"], {"rid": 4, "seq": 9})
    assert held[0] == "step"


def test_annotations_carry_the_step_and_sequence_numbers(monkeypatch):
    """What pairs an annotation on a profile with its span in the ring."""
    made = []
    monkeypatch.setattr(spans, "TraceAnnotation", lambda name, **meta: made.append(
        (name, meta)) or __import__("contextlib").nullcontext())
    ring = spans.SpanRing()
    with spans.span("serve.step", is_step=True, ring=ring, step=5):
        with spans.span("serve.dispatch", ring=ring, seq=7, kind="step", key="k"):
            pass
        with spans.span("serve.admit", ring=ring, admitted=0):
            pass
    assert made == [("serve.step", {"step": 5}), ("serve.dispatch", {"seq": 7}),
                    ("serve.admit", {})]


def test_a_capture_holds_the_spans_with_their_numbers(tmp_path):
    """On a real capture the span's annotation lands on the host plane under
    its own name, its number as the event's metadata."""
    import glob

    ring = spans.SpanRing()
    with jax.profiler.trace(str(tmp_path)):
        with spans.span("serve.step", is_step=True, ring=ring, step=41):
            with spans.span("serve.fetch", ring=ring, seq=12, kind="step"):
                jnp.ones(8).block_until_ready()
    (path,) = glob.glob(str(tmp_path / "plugins" / "profile" / "*" / "*.xplane.pb"))
    events = {e.name: dict(e.stats) for plane in jax.profiler.ProfileData.from_file(path).planes
              if plane.name.startswith("/host:") for line in plane.lines for e in line.events
              if e.name.startswith("serve.")}
    assert events == {"serve.step": {"step": 41}, "serve.fetch": {"seq": 12}}


# ---------------------------------------------------------------- collections
@pytest.mark.parametrize("generation", [0, 1, 2])
def test_a_full_collection_is_a_span_of_its_step_and_every_collection_counts(generation):
    """`gc.collect(2)` inside a step is one `host.gc` span naming the step;
    the younger generations are counted in `spans.GC` only."""
    before = (list(spans.GC.collections), list(spans.GC.seconds))
    spans.RING.clear()
    garbage = [[] for _ in range(100)]
    for a, b in zip(garbage, garbage[1:]):
        a.append(b), b.append(a)  # cycles: only a collection frees them
    del garbage, a, b
    with spans.span("serve.step", is_step=True, step=1) as step:
        gc.collect(generation)
    collections = [n - m for n, m in zip(spans.GC.collections, before[0])]
    assert collections[generation] >= 1  # automatic collections may add to it
    assert spans.GC.seconds[generation] > before[1][generation]
    held = [s for s in spans.RING.snapshot() if s[0] == "host.gc"]
    if generation < 2:
        assert held == []
        return
    (name, start, end, parent, attrs), = held
    assert parent == step.attrs["id"] and step.start <= start <= end <= step.end
    assert attrs["generation"] == 2 and attrs["collected"] >= 100


def test_the_ring_holds_the_busiest_cells_window():
    """GPT-2's closed loop: 108 steps a second of five spans, 24 admits of
    three more and a queue wait for each of 25 requests, over 65 s of ramp and
    window; a fifth to spare."""
    assert spans.RING.maxlen == spans.RING_SPANS >= 1.2 * 65 * (108 * 5 + 24 * 3 + 25)


def test_sequence_numbers_are_one_counter_with_the_tracer():
    tracer = Tracer()
    a, b, c = spans.next_seq(), tracer.next_seq(), spans.next_seq()
    assert (b, c) == (a + 1, a + 2)


def test_span_module_imports_neither_flax_nor_serving():
    """Loaded by path (the package's own `__init__` imports everything): the
    module and its first span bring in neither, so the train path can feed the
    ring without the serving stack and the serving stack without flax."""
    code = ("import importlib.util, sys\n"
            f"spec = importlib.util.spec_from_file_location('spans_alone', {spans.__file__!r})\n"
            "s = importlib.util.module_from_spec(spec); spec.loader.exec_module(s)\n"
            "with s.span('x', is_step=True): pass\n"
            "assert len(s.RING) == 1 and 'jax' in sys.modules\n"
            "bad = [m for m in sys.modules if m.split('.')[0] == 'flax'"
            " or m.startswith('accelerate_tpu')]\n"
            "assert not bad, bad\n")
    subprocess.run([sys.executable, "-c", code], check=True, timeout=120)


# ---------------------------------------------------------------- serving spans
@pytest.mark.parametrize("depth,admit", [(1, 1), (1, 4), (2, 1), (2, 4)])
def test_serving_spans_nest_pair_and_sum_to_step_timings(model, depth, admit):
    tracer = Tracer()
    engine, outputs, ring = _serve(model, pipeline_depth=depth, admit_batch=admit,
                                   tracer=tracer)
    assert all(o.finish_reason == FINISH_LENGTH for o in outputs)
    ring = [s for s in ring if s[0] != "host.gc"]  # a full collection may land anywhere
    steps = {s[4]["id"]: s for s in ring if s[0] == "serve.step"}
    dispatches = [s for s in ring if s[0] == "serve.dispatch"]
    fetches = [s for s in ring if s[0] == "serve.fetch"]
    delivers = [s for s in ring if s[0] == "serve.deliver"]
    admits = {s[3]: s for s in ring if s[0] == "serve.admit"}
    assert steps and dispatches and fetches
    assert {s[0] for s in ring} == {"serve.step", "serve.admit", "serve.dispatch", "serve.fetch",
                                    "serve.deliver", "serve.queued"}
    # one admit span a step, counting every request it seated
    assert sorted(admits) == sorted(steps)
    assert sum(s[4]["admitted"] for s in admits.values()) == len(outputs)
    # a delivery follows its fetch and carries its sequence number
    assert sorted((s[4]["seq"], s[4]["kind"]) for s in delivers) == sorted(
        (s[4]["seq"], s[4]["kind"]) for s in fetches)
    # step numbers are the counts ServingMetrics.step_total_s reports
    numbers = [s[4]["step"] for s in steps.values()]
    assert numbers == list(range(1, engine.metrics.step_total_s.count + 1))
    # every dispatch, fetch, delivery and admission lies inside the step it names
    for name, start, end, parent, attrs in dispatches + fetches + delivers + list(admits.values()):
        step = steps[parent]
        assert step[1] <= start <= end <= step[2], (name, attrs)
    # they pair by sequence number: each dispatch is fetched once, later
    by_seq = {s[4]["seq"]: s for s in dispatches}
    assert len(by_seq) == len(dispatches)
    assert sorted(s[4]["seq"] for s in fetches) == sorted(by_seq)
    for f in fetches:
        d = by_seq[f[4]["seq"]]
        assert d[2] <= f[1]
        assert d[4]["kind"] == {"admit": "admit", "step": "step"}[f[4]["kind"]]
    assert {d[4]["kind"] for d in dispatches} == {"admit", "step"}
    # the exported trace carries the same sequence numbers
    events = tracer.events()
    assert sorted(e.data["seq"] for e in events if e.kind == EV_DISPATCH) == sorted(by_seq)
    assert sorted(e.data["seq"] for e in events if e.kind == EV_FETCH) == sorted(by_seq)
    assert tracer.validate()["clean"]
    # StepTimings' sums are the spans' sums: one set of stamps
    m = engine.metrics
    length = lambda group: sum(s[2] - s[1] for s in group)  # noqa: E731
    assert m.step_total_s.sum == pytest.approx(length(steps.values()), abs=1e-9)
    assert m.step_phase_dispatch_s.sum == pytest.approx(length(dispatches), abs=1e-9)
    assert m.step_phase_fetch_blocked_s.sum == pytest.approx(length(fetches), abs=1e-9)
    assert m.step_phase_deliver_s.sum == pytest.approx(length(delivers), abs=1e-9)
    # schedule: a step's stretch to the end of its admit span, net of the
    # spans inside that stretch; the phases partition the step's time
    inside = lambda sid, t: [s for s in dispatches + fetches + delivers  # noqa: E731
                             if s[3] == sid and s[2] <= t]
    schedule = sum(admits[sid][2] - step[1] - length(inside(sid, admits[sid][2]))
                   for sid, step in steps.items())
    assert m.step_phase_schedule_s.sum == pytest.approx(schedule, abs=1e-9)
    phases = sum(getattr(m, f"step_phase_{p}_s").sum for p in ("schedule", "dispatch",
                                                              "fetch_blocked", "deliver"))
    assert phases <= m.step_total_s.sum
    # EV_DISPATCH is built from the dispatch's span: key, flag and wall time
    for e in events:
        if e.kind == EV_DISPATCH:
            d = by_seq[e.data["seq"]]
            assert (e.data["key"], e.data["compiled"]) == (d[4]["key"], d[4]["compiled"])
            assert e.data["dispatch_s"] == round(d[2] - d[1], 6)
    # each compile key compiled once, on its first dispatch
    keys = [d[4]["key"] for d in sorted(dispatches, key=lambda d: d[4]["seq"]) if d[4]["compiled"]]
    assert len(keys) == len(set(keys)) == len(engine.metrics.compiles)
    assert all(set(s[4]) == {"id", "step"} for s in steps.values())


@pytest.mark.parametrize("admit", [1, 4])
def test_one_queue_wait_per_admitted_request_ending_at_its_dispatch(model, admit):
    tracer = Tracer()
    _, outputs, ring = _serve(model, admit_batch=admit, tracer=tracer)
    dispatches = {s[4]["seq"]: s for s in ring if s[0] == "serve.dispatch"}
    queued = [s for s in ring if s[0] == "serve.queued"]
    assert sorted(s[4]["rid"] for s in queued) == sorted(o.request_id for o in outputs)
    admitted = {e.rid: e.data for e in tracer.events() if e.kind == "admit"}
    steps = {s[4]["id"] for s in ring if s[0] == "serve.step"}
    for name, start, end, parent, attrs in queued:
        taken = dispatches[attrs["seq"]]
        assert taken[4]["kind"] == "admit" and end == taken[1] and start <= end
        assert (attrs["seq"], attrs["bucket"]) == (admitted[attrs["rid"]]["seq"],
                                                   admitted[attrs["rid"]]["bucket"])
        assert parent in steps
    arrived = {o.request_id: o.arrival_time for o in outputs}
    assert all(arrived[s[4]["rid"]] <= s[1] for s in queued)


def test_span_attrs_are_untracked_by_the_collector(model):
    """A full collection walks no span the ring holds: their attrs hold
    atomic values alone."""
    _, _, ring = _serve(model)
    assert ring and not any(gc.is_tracked(s[4]) for s in ring)


def test_optional_phases_open_spans_only_when_they_run(model, tmp_path):
    from accelerate_tpu.serving.telemetry import TelemetryExporter

    _, _, ring = _serve(model, n_requests=2, journal=tmp_path / "requests.journal",
                        telemetry=TelemetryExporter(interval_s=0.0),
                        paged_kv=True)
    names = {s[0] for s in ring}
    assert {"serve.journal", "serve.telemetry"} <= names and "serve.draft" not in names
    step_ids = {s[4]["id"] for s in ring if s[0] == "serve.step"}
    journal = [s for s in ring if s[0] == "serve.journal"]
    # appends made by submit() have no step; those made while delivering name theirs
    assert {s[3] for s in journal} - {0} <= step_ids and any(s[3] for s in journal)
    assert all(s[3] in step_ids for s in ring if s[0] == "serve.telemetry")


# ----------------------------------------------------------------- token times
@pytest.mark.parametrize("sync", [1, 2])
def test_token_times_one_stamp_per_token(model, sync):
    kw = dict(paged_kv=True, tokens_per_sync=sync) if sync > 1 else {}
    _, outputs, ring = _serve(model, new_tokens=6, **kw)
    fetch_ends = {s[2] for s in ring if s[0] == "serve.fetch"}
    for out in outputs:
        times = out.token_times
        assert len(times) == len(out.tokens) == 6
        assert all(a <= b for a, b in zip(times, times[1:]))
        assert times[0] == out.first_token_time and times[-1] == out.finish_time
        assert set(times) <= fetch_ends  # a token's stamp is its fetch span's end
        if sync > 1:  # a dispatch's tokens share its delivery stamp
            assert len(set(times)) < len(times)
        else:
            assert len(set(times)) == len(times)


def test_resumed_tokens_carry_nan(model, tmp_path):
    module, params = model
    path = tmp_path / "requests.journal"
    first = ServingEngine(module, params, max_concurrency=2, prompt_buckets=(8, 16),
                          journal=path)
    first.submit(Request(prompt=[1, 2, 3], params=SamplingParams(max_new_tokens=8)))
    for _ in range(4):
        first.step()
    first.journal.close()
    second = ServingEngine(module, params, max_concurrency=2, prompt_buckets=(8, 16),
                           journal=path)
    report = second.resume()
    assert report.resumed
    (out,) = [o for o in second.run([]) if o.request_id in report.resumed]
    resumed = sum(1 for t in out.token_times if math.isnan(t))
    assert len(out.token_times) == len(out.tokens) == 8 and 0 < resumed < 8
    assert all(math.isnan(t) for t in out.token_times[:resumed])
    assert out.token_times[resumed] == out.first_token_time
    assert out.token_times[-1] == out.finish_time


# -------------------------------------------------------------------- training
def test_train_loop_records_one_wait_per_step_and_annotates_the_dispatch(monkeypatch):
    import optax

    from accelerate_tpu.accelerator import Accelerator
    from accelerate_tpu.data_loader import DataLoaderShard
    from accelerate_tpu.state import AcceleratorState, GradientState, PartialState

    for state in (AcceleratorState, GradientState, PartialState):
        state._reset_state()

    class Net(flax_nn.Module):
        @flax_nn.compact
        def __call__(self, x):
            return flax_nn.Dense(1)(x)

    def loss_fn(model, batch):
        return jnp.mean((model(batch["x"]) - batch["y"]) ** 2)

    rng = np.random.default_rng(0)
    batches = [{"x": rng.normal(size=(8, 4)).astype(np.float32),
                "y": rng.normal(size=(8, 1)).astype(np.float32)} for _ in range(5)]
    acc = Accelerator(gradient_accumulation_steps=2)
    module = Net()
    variables = module.init(jax.random.key(0), batches[0]["x"])
    _, _, loader = acc.prepare((module, variables), optax.sgd(1e-2), DataLoaderShard(batches))
    step = acc.make_train_step(loss_fn)
    annotated = []

    class Annotation:  # what a capture would show on the host plane
        def __init__(self, name):
            self.name = name

        def __enter__(self):
            annotated.append(self.name)

        def __exit__(self, *exc):
            pass

    monkeypatch.setattr(jax.profiler, "TraceAnnotation", Annotation)
    spans.RING.clear()
    for _ in range(2):  # two epochs: the exhausted probe at each end records nothing
        for batch in loader:
            step(batch)
    ring = spans.RING.snapshot()
    assert [s[0] for s in ring] == ["train.input_wait"] * 10
    # each names its batch's place in the epoch, and no step: the train loop has none
    assert [s[4] for s in ring] == [{"batch": i} for i in range(5)] * 2
    assert all(s[3] == 0 and s[1] <= s[2] for s in ring)
    assert all(a[2] <= b[1] for a, b in zip(ring, ring[1:]))
    # the step closure's host time is on the profile alone
    assert annotated == ["train.dispatch"] * 10
    for state in (AcceleratorState, GradientState, PartialState):
        state._reset_state()
