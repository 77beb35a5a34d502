"""The Ling 3.0 flash cell on the CPU at its rehearsal sizes: the walk of a
run, `correct` false when it should be (a served token altered; the reference
altered in the program's place: int8 weight products, the per-channel decay
replaced by its head's mean, the group limit dropped), the counts of
`flops_ling3.py` against hand counts at the published widths, and the cell's
per-layer readers on hand-built device events and counters."""

import importlib
import importlib.util
import json
import os
import time
import types

import pytest

import flops_ling3 as flops
import harness
import steps_ling3 as steps

CELL = "ling-3.0-flash-vl.serve.closed256"
DEVICE = {"platform": "cpu", "kind": "cpu", "count": 1}
METRICS = os.path.join(os.path.dirname(os.path.dirname(os.path.abspath(__file__))), "layer_metrics")
NEW_METRICS = ("mfu.serve.ling3", "step_hbm_roofline.serve.ling3", "kda_state_roofline.serve",
               "mla_decode_roofline.serve.ling3", "grouped_matmul_roofline.serve.ling3")
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
    line, out = run_cell(capsys, seed=2147483659)  # a seed past 2**31, as the driver's are
    assert line["correct"] is True and line["attempted"] > 0 and line["failed"] == 0
    assert set(line["metrics"]) == {"serve_tokens_per_s", "tpot_p95_ms", "setup_s"}
    assert all(v is None for v in line["metrics"].values())  # a rehearsal prints no device number
    assert "jax compiles inside the window 0 of" in out and "live_tokens" in out
    assert "moe_rows_routed_here" in out and "slot_state_bytes" in out


def test_cell_reports_the_metrics_the_benchmark_lists_for_it():
    cell = harness.Cell(CELL)
    assert {m["name"] for m in cell.end_to_end} == {"serve_tokens_per_s", "tpot_p95_ms", "setup_s"}
    assert {m["name"] for m in cell.per_layer} == {
        "device_idle_share.serve", "host_step_ms.serve", "ttft_p95_ms.closed", "itl_p95_ms.serve", *NEW_METRICS}
    assert cell.chips == 1 and cell.traffic["clients"] == 256 and cell.traffic["temperature"] == 0.0
    assert cell.traffic["prompt_len"] == {"min": 128, "max": 1536}
    assert cell.traffic["new_tokens"] == {"min": 512, "max": 3072}
    longest = cell.traffic["prompt_len"]["max"] + cell.traffic["new_tokens"]["max"]
    assert longest == cell.config["n_positions"] == 4608
    engine = cell.spec["engine"]
    assert (engine["max_concurrency"], engine["prompt_buckets"], engine["admit_batch"], engine["max_queue"]) \
        == (256, [512, 1536], 4, 512)
    assert not {"prefix_cache", "kv_tier", "speculation", "mesh"} & set(engine)


def test_benchmark_json_gained_what_the_cell_needs_and_nothing_else():
    with open(os.path.join(harness.ROOT, "BENCHMARK.json")) as f:
        bench = json.load(f)
    assert [c["name"] for c in bench["configs"]][-1] == "ling-3.0-flash-vl" and len(bench["configs"]) == 5
    assert [w["name"] for w in bench["workloads"]][-1] == CELL and len(bench["workloads"]) == 5
    assert all(w["chips"] == 1 for w in bench["workloads"])
    assert [m["name"] for m in bench["per_layer"]][-5:] == list(NEW_METRICS)
    for metric in bench["per_layer"][-5:]:
        assert metric["workloads"] == [CELL] and metric["unit"] == "%" and metric["better"] == "higher"
    listed = {m["name"] for m in bench["end_to_end"] + bench["per_layer"] if CELL in m.get("workloads", [])}
    assert listed == {"serve_tokens_per_s", "tpot_p95_ms", "device_idle_share.serve", "host_step_ms.serve",
                      "ttft_p95_ms.closed", "itl_p95_ms.serve", *NEW_METRICS}
    for entry in bench["configs"] + bench["workloads"]:
        assert len(entry["why"]) <= 200


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


def test_a_state_that_decays_by_the_heads_mean_is_not_correct(capsys, monkeypatch):
    """The program's decode step decaying every channel of a head alike (the
    scalar-decay rule in the per-channel one's place): the limits see it."""
    import jax.numpy as jnp

    from accelerate_tpu.models import ling3

    real = ling3.gated_delta_step

    def mean_decay(state, q, k, v, g, beta):
        return real(state, q, k, v, jnp.broadcast_to(g.mean(-1, keepdims=True), g.shape), beta)

    monkeypatch.setattr(ling3, "gated_delta_step", mean_decay)
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
def test_controls_are_not_correct(seed):
    """The reference with every weight product in int8, with the per-channel
    decay replaced by its head's mean, or with the group limit dropped, in the
    program's place fails a limit; so does the altered token; the program
    itself passes both. The state kept in bfloat16 is walked too: at these
    sizes it reads above the float32 limits as well."""
    import calibrate_ling3 as calibrate
    from drivers import serve_ling3 as driver

    cell = harness.Cell(CELL, rehearsal=True)
    sample, limits = served_whole(cell, driver, seed), cell.spec["limits"]
    assert calibrate.CONTROLS == driver.CONTROLS == ("int8", "decay_mean", "no_group_limit", "state_bf16")
    gaps = driver.gaps_by_control(cell, seed, sample, (None, *calibrate.CONTROLS))
    program = driver.gap_numbers(gaps[None])
    assert all(program[k] <= limits[k] for k in limits)
    for low in calibrate.CONTROLS:
        got = driver.gap_numbers(gaps[low])
        assert any(got[k] > limits[k] for k in limits), (low, got)
    fault = driver.gap_numbers(driver.logit_gaps(cell, seed, calibrate.altered(sample, cell.config["vocab_size"])))
    assert all(fault[k] > limits[k] for k in limits)


# ------------------------------------------------------------- the hand counts
@pytest.fixture(scope="module")
def published():
    return harness.overlay(harness.load_json("configs", "ling-3.0-flash-vl.json"), False)


def test_configuration_keeps_every_published_number(published):
    """Every number of the catalog's row under its own key, the three cuts
    apart; widths spelled out here so that a slip shows without the catalog."""
    cfg = published
    widths = dict(hidden_size=2560, intermediate_size=6144, moe_intermediate_size=768,
                  moe_shared_expert_intermediate_size=768, kv_lora_rank=512, qk_nope_head_dim=128,
                  qk_rope_head_dim=64, v_head_dim=128, head_dim=128, num_attention_heads=32,
                  num_key_value_heads=32, num_experts_per_tok=8, first_k_dense_replace=2, n_group=8,
                  topk_group=4, routed_scaling_factor=2.5, rope_theta=6000000, rms_norm_eps=1e-6,
                  layer_group_size=6, short_conv_kernel_size=4, kda_lower_bound=-5, rotary_dim=64,
                  partial_rotary_factor=0.5, max_position_embeddings=131072, group_norm_size=1,
                  num_kv_heads_for_linear_attn=0)
    assert {k: cfg[k] for k in widths} == widths
    assert cfg["q_lora_rank"] is None and cfg["score_function"] == "sigmoid"
    assert cfg["kda_safe_gate"] is True and cfg["no_kda_lora"] is True and cfg["use_qk_norm"] is True
    assert cfg["expert_swiglu_limit_list"][:35] == [0] * 35 and cfg["expert_swiglu_limit_list"][35:] == [4] * 7
    assert cfg["share_expert_swiglu_limit_list"][:34] == [0] * 34 and len(cfg["share_expert_swiglu_limit_list"]) == 42
    assert set(cfg["reduced"]) == set(cfg["published"]) == {"num_hidden_layers", "num_experts", "vocab_size"}
    assert (cfg["num_hidden_layers"], cfg["num_experts"], cfg["vocab_size"]) == (6, 128, 39296)
    assert flops.router_width(cfg) == 512 and flops.dense_layers(cfg) == 2 and flops.expert_layers(cfg) == 4
    assert flops.kinds(cfg) == ["kda"] * 5 + ["latent"]
    assert cfg["deployment"]["chips_sharing_a_layer"] == 4
    for reading in ("layer_group_size", "kda_gate", "kda_lora", "use_qk_norm", "rotary", "group_norm_size",
                    "output_gate", "swiglu_limits", "switches_off", "tower_and_mtp", "weights"):
        assert cfg["assumed"][reading]


def test_a_switch_this_model_has_no_code_for_is_refused(published):
    import weights_ling3 as weights

    weights.model_config(published)
    for key in ("use_nGPT", "value_norm", "scale_router_input"):
        with pytest.raises(ValueError, match=key):
            weights.model_config(dict(published, **{key: True}))
    with pytest.raises(ValueError, match="q_lora_rank"):
        weights.model_config(dict(published, q_lora_rank=1536))
    limited = dict(published, expert_swiglu_limit_list=[0, 0, 0, 4, 0, 0])
    with pytest.raises(NotImplementedError, match="expert_swiglu_limit_list"):
        weights.model_config(limited)


def test_parameter_counts_against_hand_counts(published):
    cfg = published
    kda = 6 * 2560 * 4096 + 2560 * 32 + 4 * 3 * 4096 + 32 + 4096 + 128
    assert flops.kda_params(cfg) == kda == 63_049_888
    latent = 2560 * 32 * 192 + 2560 * 576 + 512 * 32 * 256 + 2560 * 32 + 32 * 128 * 2560 + 512
    assert flops.latent_params(cfg) == latent == 31_965_696
    assert flops.expert_params(cfg) == 3 * 2560 * 768 == 5_898_240
    assert flops.dense_mlp_params(cfg) == 3 * 2560 * 6144 == 47_185_920
    router = 2560 * 512 + 512
    expert_ffn = router + 129 * 5_898_240
    assert [flops.layer_params(cfg, i) for i in range(6)] == [
        kda + 5120 + 47_185_920, kda + 5120 + 47_185_920, kda + 5120 + expert_ffn, kda + 5120 + expert_ffn,
        kda + 5120 + expert_ffn, latent + 5120 + expert_ffn]
    total = 5 * kda + latent + 6 * 5120 + 2 * 47_185_920 + 4 * expert_ffn + 2 * 39296 * 2560 + 2560
    assert flops.total_params(cfg) == total == 3_691_552_544
    print(f"ling-3.0-flash-vl, chip 0 of stage 0: {total:,} parameters, {flops.param_bytes(cfg) / 1e9:.3f} GB")
    assert flops.param_bytes(cfg) == 2 * total + 2 * (4 * router + 5 * (32 + 4096))
    assert flops.held_picks_per_token(cfg) == 2.0  # 8 picks, 128 of the 512 held
    assert flops.kda_state_elements(cfg) == 32 * 128 * 128 and flops.conv_state_elements(cfg) == 3 * 3 * 4096


def test_decode_step_bytes_against_hand_counts(published):
    cfg = published
    got = flops.decode_step_bytes(cfg, rows=256, experts_touched=125.7, live_tokens=500_000.0)
    assert got["experts"] == 4 * 125.7 * 5_898_240 * 2  # 5.93 GB
    assert got["state"] == 2 * 256 * 5 * (4 * 524_288 + 2 * 36_864)  # 5.56 GB: S and the windows, in and out
    assert got["mixer_weights"] == (5 * 63_049_888 + 31_965_696) * 2 + 5 * (32 + 4096) * 2
    assert got["dense_mlp"] == 2 * 47_185_920 * 2
    assert got["shared_and_router"] == 4 * (5_898_240 * 2 + (2560 * 512 + 512) * 4)
    assert got["latent_rows"] == 576 * 2 * (500_000 + 256)  # one latent layer, the lanes that hold something
    assert got["head_and_norms"] == (39296 * 2560 + 256 * 2560 + 2560 + 12 * 2560) * 2
    assert got["total"] == sum(v for k, v in got.items() if k != "total")
    assert 12.5e9 < got["total"] < 13.5e9  # the issue's "about 13 GB, 16 ms at 819 GB/s"
    assert (got["experts"] + got["state"]) / got["total"] > 0.85  # what this configuration adds
    cost = flops.mla_decode_cost(cfg, 500_000.0, 256)
    assert cost["flops"] == 2 * 32 * (576 + 512) * 500_000
    assert cost["flops"] / 197e12 < cost["bytes"] / 819e9  # 60 FLOP a byte: the bytes bound the kernel
    assert flops.kda_step_cost(cfg, 256) == {"flops": 7.0 * 256 * 524_288, "bytes": 2.0 * 256 * 4 * 524_288}


def test_request_flops_grow_with_what_is_fed(published):
    cfg = published
    one = flops.serve_request_flops(cfg, 100, 1)
    more = flops.serve_request_flops(cfg, 100, 11)
    per_token = flops.token_flops(cfg) + 2.0 * 2560 * 39296
    keys = 10 * 100 + 10 * 11 / 2  # ten decode steps over contexts 101 .. 110, one latent layer
    assert more - one == pytest.approx(10 * per_token + flops.absorbed_flops_per_key(cfg) * keys)
    assert flops.absorbed_flops_per_key(cfg) == 2 * 32 * 1088 and flops.plain_flops_per_key(cfg) == 2 * 32 * 320
    by_hand = 2.0 * (5 * (6 * 2560 * 4096 + 2560 * 32) + (31_965_696 - 512) + 2 * 47_185_920
                     + 4 * (2560 * 512 + 3 * 5_898_240)) + 5 * 7.0 * 524_288
    assert flops.token_flops(cfg) == by_hand  # two held picks and the shared expert: three experts a layer


# ----------------------------------------------------------------- the readers
def reader(name: str):
    spec = importlib.util.spec_from_file_location("layer_metric", os.path.join(METRICS, f"{name}.py"))
    module = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(module)
    return module.read


KERNEL = '%attn.{} = bf16[256,32,512] custom-call(...), custom_call_target="tpu_custom_call"'
GMM = '%gmm.{} = f32[{},{}] custom-call(...), custom_call_target="tpu_custom_call"'
STATE = "%multiply_reduce_fusion.{} = (f32[256,32,128,128], f32[256,32,128]) fusion(f32[256,32,128,128] %p)"
LIVE = 450_000


def traced_run(published, steps_held=12, admit_after=(3, 7)):
    """Device events of `steps_held` decode steps (five KDA layers of two
    state-touching fusions, 1.2 and 0.8 ms, and 0.5 ms of the rest; the latent
    layer's kernel 0.7 ms; on the four expert layers two grouped products of
    0.9 and 0.5 ms; the head 0.5 ms), an admit program of 150 ms after the
    steps in `admit_after` (its own state scatter and 12,288-row products
    among it); counters of 500 picks on 120 experts from 200 rows a layer a
    step and `LIVE` live rows a dispatch."""
    events, at = [], 1000.0

    def op(name, ms):
        nonlocal at
        events.append((name, at, ms * MS))
        at += ms * MS + 2_000.0

    for i in range(steps_held):
        for layer in range(6):
            if layer < 5:
                op(STATE.format(2 * layer), 1.2)
                op(STATE.format(2 * layer + 1), 0.8)
            else:
                op(KERNEL.format(3), 0.7)
            if layer >= 2:
                op(GMM.format(2 * layer, 2048, 1536), 0.9)
                op(GMM.format(2 * layer + 1, 2048, 2560), 0.5)
            op(f"%fusion.{layer} = bf16[256,2560] fusion(...)", 0.5)
        op("%fusion.77 = f32[256,39296] fusion(...)", 0.5)
        if i in admit_after:
            op(GMM.format(90, 12288, 1536), 40.0)
            op("%scatter.5 = f32[256,32,128,128] scatter(f32[256,32,128,128] %s, f32[4,32,128,128] %new)", 10.0)
            op("%fusion.99 = bf16[4,1536,2560] fusion(...)", 100.0)
    cell = types.SimpleNamespace(rehearsal=False, config=published,
                                 spec={"engine": {"max_concurrency": 256}})
    item, out = {"prompt": [0] * 700}, types.SimpleNamespace(tokens=[0] * 1400)

    def counters(n):
        return {"steps": n, "moe_picks_held": 4 * 500 * n, "moe_experts_touched": 4 * 120 * n,
                "moe_rows_routed_here": 4 * 200 * n, "live_tokens": LIVE * n, "span_tokens": 256 * 4608 * n}

    window = {"done": [(item, out)] * 5, "seconds": 51.0, "counters0": counters(100), "counters1": counters(400)}
    return {"cell": cell, "peaks_kind": "TPU v5 lite", "chips": 1, "window": window,
            "trace": {"per_device": {"/device:TPU:0": events}, "busy_s": 1.0, "window_s": 1.0},
            "traced": {"counters0": counters(200), "counters1": counters(200 + steps_held)}}


STEP_MS = 5 * 2.0 + 0.7 + 4 * 1.4 + 6 * 0.5 + 0.5  # 19.8 ms of operations a step


def test_step_device_time_leaves_the_admits_out(published):
    run = traced_run(published)
    assert steps.step_device_ns(run) == pytest.approx(STEP_MS * MS, rel=1e-6)
    assert steps.per_step(run) == {"steps": 12, "picks_held": 2000.0, "experts_touched": 480.0}
    assert steps.live_tokens(run) == LIVE and steps.rows_routed_here(run) == 200.0


def test_too_few_steps_read_nothing(published):
    run = traced_run(published, steps_held=5, admit_after=())
    assert steps.step_device_ns(run) is None
    assert reader("step_hbm_roofline.serve.ling3")(run) is None


def test_step_hbm_roofline_is_least_bytes_over_the_steps_time(published):
    run = traced_run(published)
    least = flops.decode_step_bytes(published, 256, 120.0, LIVE)["total"]
    got = reader("step_hbm_roofline.serve.ling3")(run)
    assert got == pytest.approx(100.0 * (least / 819e9) / (STEP_MS * 1e-3), rel=1e-6) and got < 100.0


def test_kda_state_roofline_reads_the_events_that_touch_the_whole_state(published):
    run = traced_run(published)
    least = 2 * 256 * 4 * 524_288 / 819e9  # 1.31 ms a layer a step
    # 2.0 ms a layer a step; the admit's scatter names f32[4,...] too and is left out
    assert reader("kda_state_roofline.serve")(run) == pytest.approx(100.0 * least / 2.0e-3, rel=1e-6)


def test_latent_kernel_roofline_reads_the_decode_kernels_events(published):
    run = traced_run(published)
    cost = flops.mla_decode_cost(published, LIVE, 256)
    assert reader("mla_decode_roofline.serve.ling3")(run) == pytest.approx(
        100.0 * (cost["bytes"] / 819e9) / 0.7e-3, rel=1e-6)


def test_grouped_products_are_the_decode_steps_own(published):
    run = traced_run(published)
    cost = flops.expert_matmul_cost(published, 500.0, 120.0)
    least = max(cost["flops"] / 197e12, cost["bytes"] / 819e9)
    # 0.9 + 0.5 ms a layer a step; the admit's 12,288-row product is not among them
    assert reader("grouped_matmul_roofline.serve.ling3")(run) == pytest.approx(100.0 * least / 1.4e-3, rel=1e-6)


def test_mfu_reads_the_window(published):
    run = traced_run(published)
    total = 5 * flops.serve_request_flops(published, 700, 1400)
    assert reader("mfu.serve.ling3")(run) == pytest.approx(100.0 * total / 51.0 / 197e12)


@pytest.mark.parametrize("name", NEW_METRICS)
def test_each_reader_returns_none_on_an_empty_run(published, name):
    """Against a program without the counters or the kernel, a run with no
    trace, and a rehearsal: nothing to read, nothing raised."""
    run = traced_run(published)
    run["traced"] = {"phases0": {}, "phases1": {}}  # a program without the counters
    run["window"] = {"done": [], "seconds": 51.0}
    run["trace"] = {"per_device": {"/device:TPU:0": [("%fusion.1 = f32[8] fusion(...)", 0.0, 1e6)]},
                    "busy_s": 1.0, "window_s": 1.0}
    assert reader(name)(run) is None
    run["trace"] = None
    assert reader(name)(run) is None
    run["cell"] = types.SimpleNamespace(rehearsal=True, config=published, spec=run["cell"].spec)
    assert reader(name)(run) is None
