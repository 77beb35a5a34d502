"""`correct` has to come out false when it should.

The control, at a size a test run can hold, on three seeds: in the training
cell the reference computed in int8 put in the program's place; in the
serving cell the program itself with its own int8 path switched on. The faults: the rest of a run
driven with the timed path broken underneath (the look for a chip skipped by
the rehearsal flag) — a step that returns its state unchanged, half of the
batch left out, a served token altered where it is produced."""

import gc
import importlib
import json
import time

import pytest

import harness

TRAIN, SERVE = "gpt2-medium.train.b8s1024", "gpt2-large.serve.closed32"
DEVICE = {"platform": "cpu", "kind": "cpu", "count": 1}


@pytest.fixture(autouse=True)
def fresh_state():
    from accelerate_tpu.state import AcceleratorState, GradientState

    AcceleratorState._reset_state(reset_partial_state=True)
    GradientState._reset_state()
    yield
    gc.collect()


def run_cell(name, capsys, seed=3, seconds=1.0):
    cell = harness.Cell(name, rehearsal=True)
    driver = importlib.import_module(f"drivers.{cell.spec['driver']}")
    driver.run(cell, DEVICE, seed=seed, seconds=seconds, trace=False, t0=time.perf_counter())
    out, err = capsys.readouterr()
    line = json.loads(out.strip().splitlines()[-1])
    assert err.strip().splitlines()[-1] == f"correct={line['correct']}"
    assert list(line)[-1] == "compared" and line["rehearsal"] is True
    assert all(v is None for v in line["metrics"].values())
    return line


def over_limit(line):
    return [k for k, v in line["compared"].items() if v["value"] > v["limit"]]


def test_train_sound_run_is_correct(capsys):
    line = run_cell(TRAIN, capsys)
    assert line["correct"] is True and not over_limit(line) and line["failed"] == 0


def test_train_state_unchanged_is_not_correct(capsys, monkeypatch):
    import jax
    import jax.numpy as jnp

    from drivers import train

    real_build = train.build

    def build(cell, seed):
        built = real_build(cell, seed)
        step, model, opt = built["step"], built["model"], built["optimizer"]

        def frozen(batch):
            params = jax.tree.map(jnp.copy, model.params)
            state = jax.tree.map(jnp.copy, opt.opt_state)
            loss = step(batch)
            model.params, opt.opt_state = params, state
            return loss

        built["step"] = frozen
        return built

    monkeypatch.setattr(train, "build", build)
    line = run_cell(TRAIN, capsys)
    assert line["correct"] is False
    assert line["compared"]["delta_norm_gap"]["value"] == pytest.approx(1.0)
    assert line["compared"]["grad_norm_gap"]["value"] == pytest.approx(1.0)


def test_train_half_batch_is_not_correct(capsys, monkeypatch):
    from drivers import train

    real_build = train.build

    def build(cell, seed):
        built = real_build(cell, seed)
        step = built["step"]
        built["step"] = lambda batch: step(
            {"input_ids": batch["input_ids"][: batch["input_ids"].shape[0] // 2]})
        return built

    monkeypatch.setattr(train, "build", build)
    line = run_cell(TRAIN, capsys)
    assert line["correct"] is False and over_limit(line)


@pytest.mark.parametrize("seed", [1, 2, 3])
def test_train_int8_control_is_not_correct(seed):
    import calibrate
    from drivers import train

    cell = harness.Cell(TRAIN, rehearsal=True)
    row = calibrate.train_seed(cell, train, seed, control=True)
    limits = cell.spec["limits"]
    assert all(row["program"][k] <= limits[k] for k in limits)
    assert any(row["control_int8"][k] > limits[k] for k in limits)
    assert any(row["fault_half_batch"][k] > limits[k] for k in limits)


def test_serve_sound_run_is_correct(capsys):
    line = run_cell(SERVE, capsys, seconds=3.0)
    assert line["correct"] is True and line["attempted"] > 0 and line["failed"] == 0


def test_serve_altered_token_is_not_correct(capsys, monkeypatch):
    from accelerate_tpu.serving import ServingEngine

    real_step = ServingEngine.step

    def step(self):
        finished = real_step(self)
        for out in finished:
            out.tokens[len(out.tokens) // 2] = (out.tokens[len(out.tokens) // 2] + 1) % 512
        return finished

    monkeypatch.setattr(ServingEngine, "step", step)
    line = run_cell(SERVE, capsys, seconds=3.0)
    assert line["correct"] is False
    assert over_limit(line) == ["logit_gap_max", "logit_gap_sq_mean"]


def test_serve_short_answer_is_not_correct(capsys, monkeypatch):
    from accelerate_tpu.serving import ServingEngine

    real_step = ServingEngine.step

    def step(self):
        finished = real_step(self)
        for out in finished:
            del out.tokens[-1]
        return finished

    monkeypatch.setattr(ServingEngine, "step", step)
    line = run_cell(SERVE, capsys, seconds=3.0)
    assert line["correct"] is False and line["failed"] == line["attempted"] > 0


@pytest.mark.parametrize("seed", [1, 2, 3])
def test_serve_own_int8_control_is_not_correct(seed):
    """The program with its own int8 weights and int8 paged pool switched on,
    in the program's place, fails the number that a lower precision moves; so
    does the reference computed in int8 or fp8."""
    import calibrate
    from drivers import serve

    cell = harness.Cell(SERVE, rehearsal=True)
    row = calibrate.serve_seed(cell, serve, seed, control=True, seconds=12.0)
    low = calibrate.serve_own_int8(cell, serve, seed, seconds=12.0)["control_own_int8"]
    limits = cell.spec["limits"]
    assert all(row["program"][k] <= limits[k] for k in limits)
    assert low["logit_gap_sq_mean"] > limits["logit_gap_sq_mean"]
    assert row["control_int8"]["logit_gap_sq_mean"] > limits["logit_gap_sq_mean"]
    assert row["control_fp8"]["logit_gap_sq_mean"] > limits["logit_gap_sq_mean"]
    assert all(row["fault_altered_token"][k] > limits[k] for k in limits)
