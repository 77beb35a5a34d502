"""The Qwen3-Next cell on the CPU at its rehearsal sizes: the walk of a run,
`correct` false when it should be (a served token altered; the reference
computed below the stated precision in the program's place), the counts of
`flops_qwen3_next.py` against hand counts at the published widths, and the
cell's per-layer readers on hand-built device events and counters."""

import importlib
import importlib.util
import json
import os
import time
import types

import pytest

import flops_qwen3_next as flops
import harness
import steps_qwen3_next as steps

CELL = "qwen3-next-80b-a3b.serve.closed128"
DEVICE = {"platform": "cpu", "kind": "cpu", "count": 1}
METRICS = os.path.join(os.path.dirname(os.path.dirname(os.path.abspath(__file__))), "layer_metrics")
MS = 1e6  # ns


def run_cell(capsys, seed=3, seconds=1.0):
    cell = harness.Cell(CELL, rehearsal=True)
    driver = importlib.import_module(f"drivers.{cell.spec['driver']}")
    driver.run(cell, DEVICE, seed=seed, seconds=seconds, trace=False, t0=time.perf_counter())
    out, err = capsys.readouterr()
    line = json.loads(out.strip().splitlines()[-1])
    assert err.strip().splitlines()[-1] == f"correct={line['correct']}"
    assert list(line)[-1] == "compared" and line["rehearsal"] is True
    return line, out


def over_limit(line):
    return [k for k, v in line["compared"].items() if v["value"] > v["limit"]]


# ------------------------------------------------------------------- the walk
def test_sound_run_is_correct_and_reports_the_cells_metrics(capsys):
    line, out = run_cell(capsys, seed=2147483659)
    assert line["correct"] is True and line["attempted"] > 0 and line["failed"] == 0
    assert set(line["metrics"]) == {"serve_tokens_per_s", "tpot_p95_ms", "setup_s"}
    assert all(v is None for v in line["metrics"].values())  # a rehearsal prints no device number
    assert "jax compiles inside the window 0 of" in out and "step counters" in out


def test_altered_token_is_not_correct(capsys, monkeypatch):
    from accelerate_tpu.serving import ServingEngine

    real_step = ServingEngine.step

    def step(self):
        finished = real_step(self)
        for out in finished:
            out.tokens[len(out.tokens) // 2] = (out.tokens[len(out.tokens) // 2] + 1) % 256
        return finished

    monkeypatch.setattr(ServingEngine, "step", step)
    line, _ = run_cell(capsys)
    assert line["correct"] is False
    assert over_limit(line) == ["logit_gap_max", "logit_gap_sq_mean"]


def served_whole(cell, driver, seed, count=24):
    """The pool's first `count` requests served to their ends: the sample a
    run would compare, without the window's clock (which requests finish in a
    timed window depends on the machine's load)."""
    import traffic as traffic_gen
    from drivers.serve import submit

    engine = driver.build(cell, seed)
    pool = traffic_gen.request_pool(cell.traffic, seed, cell.config["vocab_size"])[:count]
    sent = {submit(engine, item, 0.0): item for item in pool}
    done = []
    while engine.has_work:
        done += [(sent[out.request_id], out) for out in engine.step()]
    return done


@pytest.mark.parametrize("seed", [1, 2, 3])
def test_lower_precision_controls_are_not_correct(seed):
    """The reference with the DeltaNet state kept in bfloat16, or the router
    computed in bfloat16, in the program's place fails a limit; so does the
    altered token; the program itself passes both."""
    import calibrate_qwen3_next as calibrate
    from drivers import serve_qwen3_next as driver

    cell = harness.Cell(CELL, rehearsal=True)
    sample, limits = served_whole(cell, driver, seed), cell.spec["limits"]

    def numbers(sample, low=None):
        return driver.gap_numbers(driver.logit_gaps(cell, seed, sample, low=low))

    program = numbers(sample)
    assert all(program[k] <= limits[k] for k in limits)
    for low in calibrate.CONTROLS:
        got = numbers(sample, low)
        assert any(got[k] > limits[k] for k in limits), (low, got)
    fault = numbers(calibrate.altered(sample, cell.config["vocab_size"]))
    assert all(fault[k] > limits[k] for k in limits)


# ------------------------------------------------------------- the hand counts
@pytest.fixture(scope="module")
def published():
    return harness.overlay(harness.load_json("configs", "qwen3-next-80b-a3b.json"), False)


def test_parameter_counts_against_hand_counts(published):
    cfg = published
    assert flops.expert_params(cfg) == 3 * 2048 * 512 == 3_145_728
    linear = (2048 * 12288 + 2048 * 64 + 4 * 8192 + 2 * 32 + 128 + 4096 * 2048  # the mixer
              + 2 * 2048 + 2048 * 512 + 3 * 2048 * 512 + 2048)  # norms, router, shared expert and gate
    full = (2048 * 8192 + 2 * 2048 * 512 + 4096 * 2048 + 2 * 256
            + 2 * 2048 + 2048 * 512 + 3 * 2048 * 512 + 2048)
    assert flops.layer_params_outside_experts(cfg, "linear") == linear == 37_918_912
    assert flops.layer_params_outside_experts(cfg, "full") == full == 31_463_936
    assert flops.kinds(cfg) == ["linear", "linear", "linear", "full"]
    total = 3 * linear + full + 4 * 256 * 3_145_728 + 2 * 75_968 * 2048 + 2048
    assert flops.total_params(cfg) == total == 3_677_613_120  # 7.36 GB in bfloat16
    assert flops.held_picks_per_token(cfg) == 5.0  # 10 picks, half of the 512 held


def test_decode_step_bytes_against_hand_counts(published):
    cfg = published
    got = flops.decode_step_bytes(cfg, rows=128, experts_touched=235.0, live_tokens=128 * 1200.0)
    assert got["experts"] == 4 * 235 * 3_145_728 * 2  # 5.9 GB
    assert got["state"] == 2 * 128 * 3 * (4 * 32 * 128 * 128 + 2 * 3 * 8192)  # read and written
    assert got["kv"] == 2 * 2 * 256 * 2 * (128 * 1200 + 128)  # one full layer, keys and values
    assert got["other_weights"] == (3 * 37_918_912 + 31_463_936 + 75_968 * 2048 + 128 * 2048 + 2048) * 2
    assert got["total"] == sum(got[k] for k in ("experts", "other_weights", "state", "kv"))
    assert 7.5e9 < got["total"] < 9.5e9
    assert flops.delta_step_cost(cfg, 128)["bytes"] == 2 * 128 * 4 * 32 * 128 * 128


def test_request_flops_grow_with_what_is_fed(published):
    cfg = published
    one = flops.serve_request_flops(cfg, 100, 1)
    more = flops.serve_request_flops(cfg, 100, 11)
    per_token = flops.token_flops(cfg) + 2.0 * 2048 * 75_968
    assert more - one == pytest.approx(10 * per_token + flops.attention_flops_per_key(cfg)
                                       * (110 * 111 / 2 - 100 * 101 / 2))


# ----------------------------------------------------------------- the readers
def reader(name: str):
    spec = importlib.util.spec_from_file_location("layer_metric", os.path.join(METRICS, f"{name}.py"))
    module = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(module)
    return module.read


KERNEL = '%attn.7 = bf16[128,16,256] custom-call(...), custom_call_target="tpu_custom_call"'
STATE = "%multiply_reduce_fusion.{} = (f32[128,32,128], f32[128,32,128,128]) fusion(f32[128,32,128,128] %cache, ...)"
SCATTER = "%fusion.18 = f32[128,32,128,128] fusion(f32[128,32,128,128] %pool, f32[4,32,128,128] %copy-done.67)"
RAGGED = "%ragged-dot-none.{} = f32[{},1024] custom-call(...), custom_call_target=\"tpu_custom_call\""


def traced_run(published, steps_held=12, admit_after=(3, 7)):
    """Device events of `steps_held` decode steps of 10 ms each (per layer: the
    delta rule 2 x 0.5 ms on three of four, the grouped products 2 x 0.4 ms; the
    decode kernel 0.3 ms; 0.5 ms of the rest), an admit program of 30 ms after
    the steps in `admit_after`; counters of 3 picks on 2 experts a layer a step."""
    events, at = [], 1000.0

    def op(name, ms):
        nonlocal at
        events.append((name, at, ms * MS))
        at += ms * MS + 2_000.0

    for i in range(steps_held):
        for layer in range(4):
            if layer < 3:
                op(STATE.format(2 * layer), 0.5)
                op(STATE.format(2 * layer + 1), 0.5)
            else:
                op(KERNEL, 0.3)
            op(RAGGED.format(2 * layer, 1280), 0.4)
            op(RAGGED.format(2 * layer + 1, 1280), 0.4)
            op(f"%fusion.{layer} = bf16[128,2048] fusion(...)", 0.95 if layer < 3 else 1.65)
        op("%sort.1 = f32[128,75968] sort(...)", 0.5)
        if i in admit_after:
            op(RAGGED.format(9, 20480), 12.0)
            op(SCATTER, 3.0)
            op("%fusion.99 = bf16[4,512,2048] fusion(...)", 15.0)
    cell = types.SimpleNamespace(rehearsal=False, config=published,
                                 spec={"engine": {"max_concurrency": 128}})
    item, out = {"prompt": [0] * 700}, types.SimpleNamespace(tokens=[0] * 400)
    counters = lambda n: {"steps": n, "moe_picks_held": 4 * 3 * n, "moe_experts_touched": 4 * 2 * n}  # noqa: E731
    window = {"done": [(item, out)] * 5, "seconds": 51.0, "counters0": counters(100), "counters1": counters(400)}
    return {"cell": cell, "peaks_kind": "TPU v5 lite", "chips": 1, "window": window,
            "trace": {"per_device": {"/device:TPU:0": events}, "busy_s": 1.0, "window_s": 1.0},
            "traced": {"counters0": counters(200), "counters1": counters(200 + steps_held)}}


def test_step_device_time_leaves_the_admits_out(published):
    run = traced_run(published)
    # 4 x (0.8 + 0.95) + 3 x 1.0 + 0.3 + 0.7 + 0.5 = 11.5 ms of operations a step
    assert steps.step_device_ns(run) == pytest.approx(11.5 * MS, rel=1e-6)
    assert steps.per_step(run) == {"steps": 12, "picks_held": 12.0, "experts_touched": 8.0}
    assert steps.live_tokens(run) == 128 * 900.0


def test_too_few_steps_read_nothing(published):
    run = traced_run(published, steps_held=5, admit_after=())
    assert steps.step_device_ns(run) is None
    assert reader("step_hbm_roofline.serve.qwen3next")(run) is None


def test_step_hbm_roofline_is_least_bytes_over_the_steps_time(published):
    run = traced_run(published)
    least = flops.decode_step_bytes(published, 128, 2.0, 128 * 900.0)["total"]
    assert reader("step_hbm_roofline.serve.qwen3next")(run) == pytest.approx(
        100.0 * (least / 819e9) / 11.5e-3, rel=1e-6)


def test_expert_products_are_the_decode_steps_own(published):
    run = traced_run(published)
    cost = flops.expert_matmul_cost(published, 3.0, 2.0)
    least = max(cost["flops"] / 197e12, cost["bytes"] / 819e9)
    # 8 events a step of 0.4 ms; the admit program's 20,480-row product is not among them
    assert reader("expert_matmul_roofline.serve")(run) == pytest.approx(100.0 * least / 0.8e-3, rel=1e-6)


def test_delta_rule_events_are_found_by_the_state_they_touch(published):
    run = traced_run(published)
    least = flops.delta_step_cost(published, 128)["bytes"] / 819e9
    # 1.0 ms a layer a step; the admit program's scatter into the same buffer is not among them
    assert reader("delta_state_roofline.serve")(run) == pytest.approx(100.0 * least / 1.0e-3, rel=1e-6)


def test_experts_touched_and_mfu_read_the_window(published):
    run = traced_run(published)
    assert reader("experts_touched.serve")(run) == 2.0
    total = 5 * flops.serve_request_flops(published, 700, 400)
    assert reader("mfu.serve.qwen3next")(run) == pytest.approx(100.0 * total / 51.0 / 197e12)


def test_readers_are_silent_without_counters_or_trace(published):
    run = traced_run(published)
    run["traced"] = {"phases0": {}, "phases1": {}}  # a program without the counters
    run["window"].pop("counters1")
    for name in ("step_hbm_roofline.serve.qwen3next", "expert_matmul_roofline.serve", "experts_touched.serve"):
        assert reader(name)(run) is None
    run["trace"] = None
    assert reader("delta_state_roofline.serve")(run) is None
