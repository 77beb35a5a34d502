"""The host plane's readers (`host_plane.py`, `trace_host.py`) and the two
ring readers that need no profile (`gc_pause_ms.serve`,
`queue_wait_p95_ms.closed`): on a real CPU capture, on hand-built spans and
device events, and against a program without the spans."""

import glob
import importlib.util
import os
import types

import pytest

import host_plane
from accelerate_tpu.utils import spans

METRICS = os.path.join(os.path.dirname(os.path.dirname(os.path.abspath(__file__))), "layer_metrics")
MS = 1e6  # ns


def reader(name: str):
    spec = importlib.util.spec_from_file_location("layer_metric", os.path.join(METRICS, f"{name}.py"))
    module = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(module)
    return module.read


@pytest.fixture(autouse=True)
def clean_ring():
    spans.RING.clear()
    yield
    spans.RING.clear()


def ann(name, start_ms, dur_ms, line="python", **meta):
    return (name, start_ms * MS, dur_ms * MS, line, meta)


# Two engine steps on the program's thread and one on another: step 1 fetches
# (0-6 ms) and admits (6-9, a full collection at 7-8.5), step 2 fetches; the
# caller's code between them (10-14) opens nothing.
SPANS = [ann("serve.step", 0, 10, step=1), ann("serve.fetch", 1, 5, seq=4),
         ann("serve.admit", 6, 3), ann("host.gc", 7, 1.5, generation=2),
         ann("serve.step", 14, 6, step=2), ann("serve.fetch", 15, 4, seq=5),
         ann("serve.step", 0, 30, line="other")]


def test_host_spans_read_a_real_capture(tmp_path):
    """A span inside `jax.profiler.trace` is on the host plane under its own
    name, with its number as metadata, nested where it was opened."""
    import jax
    import jax.numpy as jnp

    with jax.profiler.trace(str(tmp_path)):
        with spans.span("serve.step", is_step=True, step=3):
            with spans.span("serve.dispatch", seq=8, kind="step"):
                jnp.ones(4).block_until_ready()
        with jax.profiler.TraceAnnotation("unrelated"):
            pass
    (path,) = glob.glob(str(tmp_path / "plugins" / "profile" / "*" / "*.xplane.pb"))
    held = host_plane.host_spans(jax.profiler.ProfileData.from_file(path))
    assert [(s[0], s[4]) for s in held] == [("serve.step", {"step": 3}),
                                           ("serve.dispatch", {"seq": 8})]
    (step, dispatch) = held
    assert step[3] == dispatch[3] and step[1] <= dispatch[1]
    assert dispatch[1] + dispatch[2] <= step[1] + step[2]


def test_innermost_names_each_stretch_by_the_deepest_open_span():
    line = host_plane.program_line(SPANS)
    assert all(s[3] == "python" for s in line)  # the thread with the most steps
    stretches = [(a / MS, b / MS, n) for a, b, n in host_plane.innermost(line)]
    assert stretches == [(0, 1, "serve.step"), (1, 6, "serve.fetch"), (6, 7, "serve.admit"),
                         (7, 8.5, "host.gc"), (8.5, 9, "serve.admit"), (9, 10, "serve.step"),
                         (14, 15, "serve.step"), (15, 19, "serve.fetch"), (19, 20, "serve.step")]


@pytest.mark.parametrize("gap,named", [((2, 3), "serve.fetch"), ((6.8, 2), "host.gc"),
                                       ((8.6, 2), "serve.step"), ((10.5, 3), "outside"),
                                       ((9.5, 4.4), "outside")])
def test_a_gap_is_named_by_the_innermost_span_covering_most_of_it(gap, named):
    events = [("op", 0.0, gap[0] * MS), ("op", (gap[0] + gap[1]) * MS, 1 * MS)]
    (label, seconds), = host_plane.label_gaps([(gap[0] * MS, gap[1] * MS)], 0.0, SPANS)
    assert label == f"gap_at_{gap[0]:.3f}ms:{named}" and seconds == pytest.approx(gap[1] / 1e3)
    assert events  # the device's own events play no part in the name


def test_host_exposed_splits_idle_time_in_steps_by_class():
    # busy 0-2, 3-7.5, 9.5-16, 17-20: idle 2-3 (fetch), 7.5-9.5 (gc 7.5-8.5, admit
    # 8.5-9, step 9-9.5), and 16-17 (fetch) inside the steps; none between them
    events = [("a", 0.0, 2 * MS), ("b", 3 * MS, 4.5 * MS), ("c", 9.5 * MS, 6.5 * MS),
              ("d", 17 * MS, 3 * MS)]
    out = host_plane.host_exposed(events, SPANS)
    assert out["steps"] == 2 and out["outside_ms"] == pytest.approx(0.0)
    assert out["by_class_ms"] == pytest.approx({"serve.fetch": 2.0, "host.gc": 1.0,
                                                "serve.admit": 0.5, "serve.step": 0.5})
    assert out["ms_per_step"] == pytest.approx(4.0 / 2)
    # idle between the steps is outside; a step the slice cuts is not counted
    cut = host_plane.host_exposed([("a", 0.0, 11 * MS), ("b", 13 * MS, 5 * MS)], SPANS)
    assert cut["steps"] == 1 and cut["outside_ms"] == 0.0 and cut["ms_per_step"] == 0.0
    assert host_plane.host_exposed(events, []) is None


def test_profile_offset_recovers_a_known_shift():
    """The ring's stamps and the annotations differ by one constant; the
    annotation encloses the ring's stamps by a few hundred ns either side."""
    shift, ring, marks = 1_234_567_890.0, [], []
    for k, t in enumerate((5.0, 5.012, 5.030)):
        ring.append(("serve.step", t, t + 0.009, 0, {"id": 50 + k, "step": k + 1}))
        marks.append(("serve.step", t * 1e9 + shift - 300, 0.009 * 1e9 + 600, "python",
                      {"step": k + 1}))
    ring.append(("serve.fetch", 5.001, 5.002, 50, {"seq": 1}))
    offset, spread, pairs = host_plane.profile_offset_ns(ring, marks + [ann("serve.step", 0, 1)])
    assert pairs == 3 and offset == pytest.approx(shift, abs=1e-3) and spread < 1e-3
    assert host_plane.profile_offset_ns(ring, [ann("serve.step", 0, 1)]) is None


def cell(**over):
    return types.SimpleNamespace(**{"rehearsal": False, "config": {}, **over})


def window_ring(waits_ms, pauses_ms=(), long_step=False):
    """Steps 11-14 in the window (step 10 before it); one wait recorded in
    each listed step and the pauses in step 12."""
    spans.RING.clear()
    t = 1.0
    for i, number in enumerate(range(10, 15)):
        sid = 500 + i
        length = 0.15 if long_step and number == 13 else 0.01
        if number == 12:
            for p in pauses_ms:
                spans.RING.append(("host.gc", t + 1e-3, t + 1e-3 + p / 1e3, sid,
                                   {"generation": 2, "collected": 3}))
        if number >= 11:
            for w in waits_ms[i - 1::4]:
                spans.RING.append(("serve.queued", t - w / 1e3, t + 1e-4, sid,
                                   {"rid": len(spans.RING), "bucket": 128, "seq": 7}))
        spans.RING.append(("serve.step", t, t + length, 0, {"id": sid, "step": number}))
        t += length + 1e-3
    return {"cell": cell(), "window": {"phases0": {"steps": 10}, "phases1": {"steps": 14}}}


def test_gc_pause_is_the_full_collections_of_the_windows_steps(capsys):
    run = window_ring([], pauses_ms=(30.0, 10.0))
    spans.RING.append(("host.gc", 0.0, 5.0, 0, {"generation": 2, "collected": 1}))  # no step
    assert reader("gc_pause_ms.serve")(run) == pytest.approx(40.0 / 4)
    assert "2 full collections in 4 steps, longest 30.000 ms" in capsys.readouterr().out
    assert reader("gc_pause_ms.serve")(window_ring([])) == 0.0


def test_queue_wait_is_the_p95_of_the_windows_admissions(capsys):
    waits = [float(ms) for ms in range(1, 41)]
    run = window_ring(waits, pauses_ms=(2.0,), long_step=True)
    assert reader("queue_wait_p95_ms.closed")(run) == pytest.approx(38.0 + 0.1, abs=1e-6)
    said = capsys.readouterr().out
    assert "40 admissions in 4 steps" in said and "40.100 128 " in said
    assert "host.gc" in said and "serve.step" in said  # what overlapped the longest waits
    assert reader("queue_wait_p95_ms.closed")(window_ring(waits[:19])) is None


def test_the_ring_readers_report_nothing_against_a_program_without_the_spans(monkeypatch):
    run = window_ring([])  # a ring with steps and no `serve.queued`
    assert reader("queue_wait_p95_ms.closed")(run) is None
    monkeypatch.delattr(spans, "GC")  # a program without the collector's hook
    assert reader("gc_pause_ms.serve")(run) is None
    assert reader("gc_pause_ms.serve")({**run, "cell": cell(rehearsal=True)}) is None


def test_trace_host_reports_on_hand_built_planes(capsys):
    """The tool's report on a GPT-2-shaped slice: the decode kernels of each
    step lie between its dispatch and its fetch on the profile's clock."""
    import trace_host

    shift = 2e9
    spans.RING.clear()
    marks, device = [], []
    for k in range(4):
        t = 10.0 + 0.02 * k
        sid = 900 + k
        spans.RING.append(("serve.dispatch", t + 1e-3, t + 2e-3, sid, {"seq": 40 + k, "kind": "step"}))
        spans.RING.append(("serve.fetch", t + 3e-3, t + 15e-3, sid, {"seq": 40 + k, "kind": "step"}))
        spans.RING.append(("serve.step", t, t + 0.016, 0, {"id": sid, "step": k + 1}))
        at = lambda s: s * 1e9 + shift  # noqa: E731
        marks += [("serve.step", at(t), 16 * MS, "python", {"step": k + 1}),
                  ("serve.dispatch", at(t + 1e-3), 1 * MS, "python", {"seq": 40 + k}),
                  ("serve.fetch", at(t + 3e-3), 12 * MS, "python", {"seq": 40 + k})]
        device += [(f'%attn.{36 + layer} = bf16[4] custom-call(), custom_call_target="tpu_custom_call"',
                    at(t + 4e-3) + layer * 4 * MS, 4 * MS) for layer in range(2)]
    run = {"cell": cell(config={"n_layer": 2}), "trace": {"per_device": {"/device:TPU:0": device}}}
    trace_host.report(run, {"spans": marks, "runs": [("jit_step_fn(1)", at(10.0) + 3.5 * MS, 9 * MS)]})
    said = capsys.readouterr().out
    assert "clock check decode kernels of a step: 4 runs paired, 0 violations" in said
    assert "clock check step programs: 1 runs paired, 0 violations" in said
    assert f"ring offset {shift:.0f} ns, spread 0.000 us over 4 steps" in said
    assert "host exposed" in said and "idle gaps of 10.0 ms or more" in said
