"""The readers of the program's span ring, on hand-built device events and a
hand-built ring."""

import importlib.util
import math
import os
import types

import pytest

import program_spans
from accelerate_tpu.utils import spans

METRICS = os.path.join(os.path.dirname(os.path.dirname(os.path.abspath(__file__))), "layer_metrics")
KERNEL = '%attn.{} = bf16[4,2,64] custom-call(...), custom_call_target="tpu_custom_call"'
MS = 1e6  # ns


def reader(name: str):
    spec = importlib.util.spec_from_file_location("layer_metric", os.path.join(METRICS, f"{name}.py"))
    module = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(module)
    return module.read


class Device:
    """Lays programs on a device timeline, one after the other, with the idle
    gaps a real trace shows: inside a program as well as between two."""

    def __init__(self):
        self.events, self.at = [], 1_000.0

    def idle(self, ns):
        self.at += ns
        return self

    def op(self, name, ns):
        self.events.append((name, self.at, ns))
        self.at += ns

    def step(self, ms=10.0, layers=(0, 1), tail=True, gap=8_700.0):
        """A decode step of `ms` + 0.5 ms: per layer a pool copy of 1.5 ms and the
        kernel, then the sort; `layers` and `tail` cut it short at either end."""
        for layer in layers:
            self.op(f"%copy.{layer} = bf16[8] copy(...)", 1.5 * MS)
            self.idle(5_500.0 if layer == 0 else 0.0)  # a step is not one stretch
            self.op(KERNEL.format(36 + layer), (ms - 3.0) * MS / 2)
        if tail:
            self.op("%sort.5 = f32[4,8] sort(...)", 0.5 * MS)
        return self.idle(gap)

    def admit(self, ms=4.0, gap=8_700.0):
        self.op("%fusion.7 = bf16[8] fusion(...)", ms * MS / 2)
        self.idle(4_400.0)  # nor is an admit
        self.op("%attn.9 = bf16[8] fusion(...)", ms * MS / 2)  # XLA attention: no custom call
        return self.idle(gap)

    def helper(self, gap=1_500.0):
        self.op("%scatter.1 = s32[4,8] scatter(...)", 2_000.0)
        return self.idle(gap)


def cell(**over):
    base = dict(rehearsal=False, config={"n_layer": 2},
                traffic={"reference_steps": 3, "host_batches": 4})
    return types.SimpleNamespace(**{**base, **over})


def ring_of(kinds_by_step, first_step=11):
    """A ring in which step `first_step + i` made the dispatches `kinds_by_step[i]`;
    the step before the slice made one `step` dispatch too."""
    spans.RING.clear()
    seq, t = 100, 50.0
    for i, kinds in enumerate([["step"], *kinds_by_step]):
        sid = 1000 + i
        for kind in kinds:
            spans.RING.append(("serve.dispatch", t, t + 1e-3, sid, {"seq": seq, "kind": kind}))
            spans.RING.append(("serve.fetch", t + 2e-3, t + 9e-3, sid, {"seq": seq, "kind": kind}))
            seq += 1
        spans.RING.append(("serve.step", t - 1e-3, t + 10e-3, 0,
                           {"id": sid, "step": first_step - 1 + i}))
        t += 12e-3
    return {"phases0": {"steps": first_step - 1},
            "phases1": {"steps": first_step - 1 + len(kinds_by_step)}}


def run_of(device, traced, **over):
    return {"cell": cell(**over), "trace": {"per_device": {"/device:TPU:0": device.events}},
            "traced": traced}


@pytest.fixture(autouse=True)
def clean_ring():
    yield
    spans.RING.clear()


# ------------------------------------------------------------------ the split
def test_busy_counts_overlap_once_and_clips():
    busy = program_spans.Busy([("a", 0.0, 100.0), ("b", 50.0, 100.0), ("c", 300.0, 50.0)])
    assert busy.between(0.0, 400.0) == 200.0 and busy.between(100.0, 320.0) == 70.0
    assert busy.between(150.0, 300.0) == 0.0 and busy.until(-5.0) == 0.0


def test_decode_cycles_keep_whole_steps_in_layer_order():
    device = Device().step(layers=(1,)).step().step(ms=12.0).step(layers=(0,), tail=False)
    cycles = program_spans.decode_cycles(device.events, 2)
    assert len(cycles) == 2  # the steps cut at either end of the trace are dropped
    assert [round((t1 - t0) / MS, 3) for t0, t1 in cycles] == [8.5, 10.5]
    assert "2 decode kernels by name" in program_spans.decode_cycles(device.events, 3)
    swapped = Device().step().step(layers=(1, 0)).step()
    assert "out of layer order" in program_spans.decode_cycles(swapped.events, 2)


# the slice's steps: step; admit + step; then steps alone; the trace cuts the last
SLICE = [["step"], ["admit", "step"], ["step"], ["step"], ["step"], ["step"], ["step"]]


def test_split_finds_a_planted_admit_program(capsys):
    traced = ring_of(SLICE)
    device = Device().step(layers=(1,))  # the step in flight when the trace began
    device.step().helper().helper().admit(ms=4.0).step().step(ms=12.0).step().step().step()
    device.step(layers=(0,), tail=False)
    run = run_of(device, traced)
    share = reader("admit_device_share.serve")(run)
    step_ms = reader("decode_step_device_ms.serve")(run)
    # six whole steps of 10.5, 10.5, 12.5, 10.5, 10.5, 10.5 ms; between the first and
    # the last kernel lie the steps less one step's edges (its first copy and its
    # sort), and after the first step the admit with the two helpers beside it
    assert step_ms == pytest.approx(10.5)
    steps_ms = 10.5 * 5 + 12.5 - (1.5 + 0.5)
    assert share == pytest.approx(100 * 4.004 / (4.004 + steps_ms), rel=1e-6)
    said = capsys.readouterr().out
    assert said.count("serve split") == 1 and "6 whole steps" in said
    assert "admit 4.004 ms after 1 steps, helpers 0.000 ms" in said


@pytest.mark.parametrize("fault", ["kernel_missing", "admit_not_in_ring", "admit_not_on_device",
                                   "unknown_program", "more_steps_than_dispatches"])
def test_failed_self_check_reports_nothing_and_says_why(fault, capsys):
    kinds = [list(k) for k in SLICE]
    if fault == "admit_not_in_ring":
        kinds[1] = ["step"]
    if fault == "unknown_program":
        kinds[1] = ["tier_wake", "step"]
    if fault == "more_steps_than_dispatches":
        kinds = kinds[:4]
    device = Device().step(layers=(1,)).step()
    if fault != "admit_not_on_device":
        device.admit()
    device.step(layers=(0,) if fault == "kernel_missing" else (0, 1)).step().step().step().step()
    run = run_of(device, ring_of(kinds))
    assert reader("admit_device_share.serve")(run) is None
    assert reader("decode_step_device_ms.serve")(run) is None
    assert "no pairing" in capsys.readouterr().out


def test_readers_report_nothing_without_a_ring_a_trace_or_on_a_rehearsal():
    traced = ring_of([["step"]] * 6)
    device = Device().step().step().step().step().step().step()
    assert reader("decode_step_device_ms.serve")(run_of(device, traced)) == pytest.approx(10.5)
    for name in ("admit_device_share.serve", "decode_step_device_ms.serve"):
        assert reader(name)(run_of(device, traced, rehearsal=True)) is None
        assert reader(name)({"cell": cell(), "trace": None, "traced": traced}) is None
        assert reader(name)({"cell": cell(), "trace": {"per_device": {}}, "traced": traced}) is None
    spans.RING.clear()
    assert reader("admit_device_share.serve")(run_of(device, traced)) is None
    for name in ("itl_p95_ms.serve", "input_wait_ms.train"):
        assert reader(name)({"cell": cell(), "window": {"done": []}, "traced": traced}) is None


def test_a_ring_that_dropped_spans_is_not_read(capsys):
    traced = ring_of([["step"]] * 6)
    device = Device().step().step().step().step().step().step()
    spans.RING.dropped = 1  # what it holds may lack part of the window
    assert program_spans.ring_spans() is None
    assert reader("decode_step_device_ms.serve")(run_of(device, traced)) is None
    assert reader("input_wait_ms.train")({"cell": cell()}) is None
    assert "the ring dropped 1 spans" in capsys.readouterr().out


# --------------------------------------------------------------- token gaps
def turns(n, stalls=None):
    """A ring of `n` engine steps numbered from 1, each 100 ms long, or longer by what
    `stalls` gives its number; every step brings each caller one token, 90 ms in.
    Returns the tokens' stamps."""
    spans.RING.clear()
    stamps, t = [], 50.0
    for k in range(1, n + 1):
        stamps.append(t + 0.09)
        spans.RING.append(("serve.step", t, t + 0.099, 0, {"id": k, "step": k}))
        t += 0.1 + (stalls or {}).get(k, 0.0)
    return stamps


def itl_run(stamps, callers=40):
    """The window holds steps 6 to 65, the traced slice steps 21 to 30."""
    done = [({}, types.SimpleNamespace(token_times=list(stamps))) for _ in range(callers)]
    return {"cell": cell(), "window": {"done": done, "phases0": {"steps": 5}, "phases1": {"steps": 65}},
            "traced": {"phases0": {"steps": 20}, "phases1": {"steps": 30}}}


def test_steps_are_picked_by_number_for_the_slice_and_for_the_window():
    run = itl_run(turns(70))
    ring = spans.RING.snapshot()
    assert sorted(program_spans.steps_of(run, ring)) == list(range(21, 31))
    assert sorted(program_spans.steps_of(run, ring, "window")) == list(range(6, 66))
    assert program_spans.steps_of({}, ring) == {}


def test_itl_reads_every_turn_of_the_window_but_the_profilers_two(capsys):
    # the profiler's start holds the caller up for 5 s before step 21, its stop for 3 s
    # after step 30: neither is the program's
    run = itl_run(turns(70, stalls={20: 5.0, 30: 3.0}))
    assert reader("itl_p95_ms.serve")(run) == pytest.approx(100.0)
    said = capsys.readouterr().out
    # 60 stamps inside the window's steps: 59 gaps a caller, two of them the profiler's
    assert "itl: 2280 gaps in 60 steps (80 left out" in said and "max 100.000" in said
    # without a traced slice nothing is left out
    del run["traced"]
    assert reader("itl_p95_ms.serve")(run) == pytest.approx(100.0)
    assert "itl: 2360 gaps in 60 steps (0 left out" in capsys.readouterr().out


@pytest.mark.parametrize("stalled,p95", [((10, 40), 100.0), ((10, 25, 40, 50), 400.0)])
def test_itl_reads_planted_stalls_once_they_pass_a_twentieth_of_the_turns(stalled, p95):
    run = itl_run(turns(70, stalls={k: 0.3 for k in stalled}))
    assert reader("itl_p95_ms.serve")(run) == pytest.approx(p95)


def test_itl_leaves_out_restored_tokens_and_reports_nothing_on_too_few():
    run = itl_run(turns(70))
    # restored tokens carry nan: their gaps are left out, not counted as zero
    for _, out in run["window"]["done"]:
        out.token_times[:50] = [math.nan] * 50
    assert reader("itl_p95_ms.serve")(run) is None  # under a thousand gaps left
    # a program whose RequestOutput has no token times reports nothing
    run["window"]["done"] = [({}, types.SimpleNamespace(tokens=[1, 2]))] * 8
    assert reader("itl_p95_ms.serve")(run) is None


# --------------------------------------------------------------- input wait
def waits_of(lengths_ms, places):
    spans.RING.clear()
    for i, (ms, place) in enumerate(zip(lengths_ms, places)):
        spans.RING.append(("train.input_wait", float(i), i + ms / 1e3, 0, {"batch": place}))


def test_input_wait_is_the_mean_over_the_windows_steps(capsys):
    lengths = [900.0, 5.0, 5.0] + [0.2] * 9 + [400.2]  # three set-up steps
    waits_of(lengths, [i % 4 for i in range(13)])
    value = reader("input_wait_ms.train")({"cell": cell()})
    assert value == pytest.approx((0.2 * 9 + 400.2) / 10)  # a planted 400 ms stall shows
    assert "10 steps" in capsys.readouterr().out
    traffic = {"reference_steps": 13, "host_batches": 4}
    assert reader("input_wait_ms.train")({"cell": cell(traffic=traffic)}) is None


def test_input_wait_reports_nothing_when_another_loader_passed(capsys):
    # a second prepared loader went through two batches after the set-up's three steps
    waits_of([1.0] * 12, [0, 1, 2, 0, 1, 3, 0, 1, 2, 3, 0, 1])
    assert reader("input_wait_ms.train")({"cell": cell()}) is None
    assert "not one loader's" in capsys.readouterr().out
