"""The Kimi K2 cell on the CPU at its rehearsal sizes: the walk of a run,
`correct` false when it should be (a served token altered; the latent pool's
value lanes read shifted by one; the reference computed below the stated
precision in the program's place), the counts of `flops_kimi_k2.py` against
hand counts at the published widths, and the cell's per-layer readers on
hand-built device events and counters."""

import importlib
import importlib.util
import json
import os
import time
import types

import pytest

import flops_kimi_k2 as flops
import harness
import steps_kimi_k2 as steps

CELL = "kimi-k2.7-code.serve.closed256"
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
    assert "jax compiles inside the window 0 of" in out and "live_tokens" in out


def test_cell_reports_the_metrics_the_benchmark_lists_for_it():
    cell = harness.Cell(CELL)
    assert {m["name"] for m in cell.end_to_end} == {"serve_tokens_per_s", "tpot_p95_ms", "setup_s"}
    assert {m["name"] for m in cell.per_layer} == {
        "device_idle_share.serve", "host_step_ms.serve", "ttft_p95_ms.closed", "itl_p95_ms.serve",
        "mfu.serve.kimik2", "step_hbm_roofline.serve.kimik2", "mla_decode_roofline.serve",
        "expert_matmul_roofline.serve.kimik2"}
    assert cell.chips == 1 and cell.traffic["clients"] == 256 and cell.traffic["temperature"] == 0.0
    assert cell.traffic["prompt_len"] == {"min": 128, "max": 1536}
    assert cell.traffic["new_tokens"] == {"min": 512, "max": 3072}
    longest = cell.traffic["prompt_len"]["max"] + cell.traffic["new_tokens"]["max"]
    assert longest == cell.config["n_positions"] == 4608


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


def test_value_lanes_shifted_by_one_are_not_correct(capsys, monkeypatch):
    """The fused kernel reading the value from lanes [1, value_dim + 1) of the
    latent row (its output's lanes moved by one): every decode step's
    attention is then wrong by a little, and the limits see it."""
    import jax.numpy as jnp

    from accelerate_tpu.ops import flash_attention

    real = flash_attention.paged_decode_attention

    def shifted(q, k_pool, v_pool, *args, **kw):
        out = real(q, k_pool, v_pool, *args, **kw)
        return jnp.roll(out, -1, axis=-1) if v_pool is None else out

    monkeypatch.setattr(flash_attention, "paged_decode_attention", shifted)
    line, _ = run_cell(capsys, seed=11)
    assert line["correct"] is False and over_limit(line)


def served_whole(cell, driver, seed, count=24):
    """The pool's first `count` requests served to their ends: the sample a
    run would compare, without the window's clock."""
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
    """The reference with every weight product in int8 or in float8 e4m3, or
    its latent rows rounded through float8, in the program's place fails a
    limit; so does the altered token; the program itself passes both."""
    import calibrate_kimi_k2 as calibrate
    from drivers import serve_kimi_k2 as driver

    cell = harness.Cell(CELL, rehearsal=True)
    sample, limits = served_whole(cell, driver, seed), cell.spec["limits"]

    def numbers(sample, low=None):
        return driver.gap_numbers(driver.logit_gaps(cell, seed, sample, low=low))

    program = numbers(sample)
    assert all(program[k] <= limits[k] for k in limits)
    assert calibrate.CONTROLS == driver.CONTROLS == ("int8", "fp8", "latent_fp8")
    for low in calibrate.CONTROLS:
        got = numbers(sample, low)
        assert any(got[k] > limits[k] for k in limits), (low, got)
    fault = numbers(calibrate.altered(sample, cell.config["vocab_size"]))
    assert all(fault[k] > limits[k] for k in limits)


# ------------------------------------------------------------- the hand counts
@pytest.fixture(scope="module")
def published():
    return harness.overlay(harness.load_json("configs", "kimi-k2.7-code.json"), False)


def test_configuration_keeps_every_published_width(published):
    cfg = published
    widths = dict(hidden_size=7168, intermediate_size=18432, moe_intermediate_size=2048, kv_lora_rank=512,
                  q_lora_rank=1536, qk_nope_head_dim=128, qk_rope_head_dim=64, v_head_dim=128,
                  num_attention_heads=64, num_experts_per_tok=8, n_shared_experts=1,
                  first_k_dense_replace=1, routed_scaling_factor=2.827, rope_theta=50000, rms_norm_eps=1e-5)
    assert {k: cfg[k] for k in widths} == widths
    assert cfg["rope_scaling"] == {"beta_fast": 32, "beta_slow": 1, "factor": 64, "mscale": 1,
                                   "mscale_all_dim": 1, "original_max_position_embeddings": 4096,
                                   "type": "yarn"}
    assert set(cfg["reduced"]) == set(cfg["published"]) == {"num_hidden_layers", "n_routed_experts", "vocab_size"}
    assert (cfg["num_hidden_layers"], cfg["n_routed_experts"], cfg["vocab_size"]) == (5, 12, 20480)
    assert flops.router_width(cfg) == 384 and flops.dense_layers(cfg) == 1 and flops.expert_layers(cfg) == 4


def test_parameter_counts_against_hand_counts(published):
    cfg = published
    attention = 7168 * 1536 + 1536 * 64 * 192 + 7168 * 576 + 512 * 64 * 256 + 64 * 128 * 7168
    assert flops.attention_matmul_params(cfg) == attention == 101_122_048
    assert flops.expert_params(cfg) == 3 * 7168 * 2048 == 44_040_192
    assert flops.dense_mlp_params(cfg) == 3 * 7168 * 18432 == 396_361_728
    dense = attention + 1536 + 512 + 2 * 7168 + 396_361_728
    expert = attention + 1536 + 512 + 2 * 7168 + 7168 * 384 + 384 + 13 * 44_040_192
    assert flops.layer_params(cfg, True) == dense and flops.layer_params(cfg, False) == expert
    total = dense + 4 * expert + 2 * 20480 * 7168 + 7168
    assert flops.total_params(cfg) == total == 3_496_763_904  # 6.99 GB in bfloat16, the routers float32
    assert flops.param_bytes(cfg) == 2 * total + 4 * 2 * (7168 * 384 + 384)
    assert flops.held_picks_per_token(cfg) == 0.25  # 8 picks, 12 of the 384 held


def test_decode_step_bytes_against_hand_counts(published):
    cfg = published
    got = flops.decode_step_bytes(cfg, rows=256, experts_touched=11.5, live_tokens=375_000.0)
    assert got["experts"] == 4 * 11.5 * 44_040_192 * 2  # 4.05 GB
    assert got["attention_weights"] == 5 * (101_122_048 + 2048) * 2  # 1.01 GB
    assert got["dense_mlp"] == 396_361_728 * 2
    assert got["shared_and_router"] == 4 * (44_040_192 * 2 + (7168 * 384 + 384) * 4)
    assert got["latent_rows"] == 5 * 576 * 2 * (375_000 + 256)  # 2.16 GB: the lanes that hold something
    assert got["head_and_norms"] == (20480 * 7168 + 256 * 7168 + 7168 + 10 * 7168) * 2
    assert got["total"] == sum(v for k, v in got.items() if k != "total")
    assert 8.0e9 < got["total"] < 9.5e9
    cost = flops.mla_decode_cost(cfg, 375_000.0, 256)
    assert cost["flops"] == 2 * 64 * (576 + 512) * 375_000 and cost["flops"] / cost["bytes"] == pytest.approx(112, rel=0.02)
    assert cost["flops"] / 197e12 < cost["bytes"] / 819e9  # the bytes bound the kernel, by a factor of two


def test_request_flops_grow_with_what_is_fed(published):
    cfg = published
    one = flops.serve_request_flops(cfg, 100, 1)
    more = flops.serve_request_flops(cfg, 100, 11)
    per_token = flops.token_flops(cfg) + 2.0 * 7168 * 20480
    keys = 10 * 100 + 10 * 11 / 2  # ten decode steps over contexts 101 .. 110
    assert more - one == pytest.approx(10 * per_token + 5 * flops.absorbed_flops_per_key(cfg) * keys)
    assert flops.absorbed_flops_per_key(cfg) == 2 * 64 * 1088 and flops.plain_flops_per_key(cfg) == 2 * 64 * 320


# ----------------------------------------------------------------- the readers
def reader(name: str):
    spec = importlib.util.spec_from_file_location("layer_metric", os.path.join(METRICS, f"{name}.py"))
    module = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(module)
    return module.read


KERNEL = '%attn.{} = bf16[256,64,512] custom-call(...), custom_call_target="tpu_custom_call"'
RAGGED = "%ragged-dot-none.{} = f32[{},{}] custom-call(...), custom_call_target=\"tpu_custom_call\""
LIVE = 300_000


def traced_run(published, steps_held=12, admit_after=(3, 7)):
    """Device events of `steps_held` decode steps (per layer: the decode kernel
    0.6 ms and 1.4 ms of the rest; on the four expert layers two grouped
    products of 0.5 ms), an admit program of 150 ms after the steps in
    `admit_after`; counters of 16 picks on 10 experts a layer a step and
    `LIVE` live rows a dispatch."""
    events, at = [], 1000.0

    def op(name, ms):
        nonlocal at
        events.append((name, at, ms * MS))
        at += ms * MS + 2_000.0

    for i in range(steps_held):
        for layer in range(5):
            op(KERNEL.format(3 + layer), 0.6)
            if layer:
                op(RAGGED.format(2 * layer, 2048, 4096), 0.5)
                op(RAGGED.format(2 * layer + 1, 2048, 7168), 0.5)
            op(f"%fusion.{layer} = bf16[256,7168] fusion(...)", 1.4)
        op("%fusion.77 = f32[256,20480] fusion(...)", 0.5)
        if i in admit_after:
            op("%gmm.9 = f32[12288,4096] custom-call(...), custom_call_target=\"tpu_custom_call\"", 60.0)
            op("%fusion.99 = bf16[4,1536,7168] fusion(...)", 90.0)
    cell = types.SimpleNamespace(rehearsal=False, config=published,
                                 spec={"engine": {"max_concurrency": 256}})
    item, out = {"prompt": [0] * 700}, types.SimpleNamespace(tokens=[0] * 1400)

    def counters(n):
        return {"steps": n, "moe_picks_held": 4 * 16 * n, "moe_experts_touched": 4 * 10 * n,
                "live_tokens": LIVE * n, "span_tokens": 256 * 4608 * n}

    window = {"done": [(item, out)] * 5, "seconds": 51.0, "counters0": counters(100), "counters1": counters(400)}
    return {"cell": cell, "peaks_kind": "TPU v5 lite", "chips": 1, "window": window,
            "trace": {"per_device": {"/device:TPU:0": events}, "busy_s": 1.0, "window_s": 1.0},
            "traced": {"counters0": counters(200), "counters1": counters(200 + steps_held)}}


def test_step_device_time_leaves_the_admits_out(published):
    run = traced_run(published)
    # 5 x (0.6 + 1.4) + 4 x 1.0 + 0.5 = 14.5 ms of operations a step
    assert steps.step_device_ns(run) == pytest.approx(14.5 * MS, rel=1e-6)
    assert steps.per_step(run) == {"steps": 12, "picks_held": 64.0, "experts_touched": 40.0}
    assert steps.live_tokens(run) == LIVE and steps.live_tokens(run, "window") == LIVE


def test_too_few_steps_read_nothing(published):
    run = traced_run(published, steps_held=5, admit_after=())
    assert steps.step_device_ns(run) is None
    assert reader("step_hbm_roofline.serve.kimik2")(run) is None


def test_step_hbm_roofline_is_least_bytes_over_the_steps_time(published):
    run = traced_run(published)
    least = flops.decode_step_bytes(published, 256, 10.0, LIVE)["total"]
    got = reader("step_hbm_roofline.serve.kimik2")(run)
    assert got == pytest.approx(100.0 * (least / 819e9) / 14.5e-3, rel=1e-6) and got < 100.0


def test_latent_kernel_roofline_reads_the_decode_kernels_events(published):
    run = traced_run(published)
    cost = flops.mla_decode_cost(published, LIVE, 256)
    least = max(cost["flops"] / 197e12, cost["bytes"] / 819e9)
    # 0.6 ms an event; an admit program's `%gmm` and flash kernels are not among them
    assert reader("mla_decode_roofline.serve")(run) == pytest.approx(100.0 * least / 0.6e-3, rel=1e-6)


def test_expert_products_are_the_decode_steps_own(published):
    run = traced_run(published)
    cost = flops.expert_matmul_cost(published, 16.0, 10.0)
    least = max(cost["flops"] / 197e12, cost["bytes"] / 819e9)
    # two events of 0.5 ms a layer a step; the admit's 12,288-row product is not among them
    assert reader("expert_matmul_roofline.serve.kimik2")(run) == pytest.approx(100.0 * least / 1.0e-3, rel=1e-6)


def test_mfu_reads_the_window(published):
    run = traced_run(published)
    total = 5 * flops.serve_request_flops(published, 700, 1400)
    assert reader("mfu.serve.kimik2")(run) == pytest.approx(100.0 * total / 51.0 / 197e12)


def test_readers_are_silent_without_counters_or_trace(published):
    run = traced_run(published)
    run["traced"] = {"phases0": {}, "phases1": {}}  # a program without the counters
    run["window"].pop("counters1")
    for name in ("step_hbm_roofline.serve.kimik2", "expert_matmul_roofline.serve.kimik2",
                 "mla_decode_roofline.serve"):
        assert reader(name)(run) is None
    run["trace"] = None
    assert reader("mla_decode_roofline.serve")(run) is None
