"""The MiMo-V2 cell on the CPU at its rehearsal sizes: the walk of a run,
`correct` false when it should be (a served token altered; the reference
altered in the program's place: int8 weight products, the window removed,
the sinks removed; the program without its sinks), the counts of
`flops_mimo_v2.py` against hand counts at the published widths, and the
cell's per-layer readers on hand-built device events and counters."""

import importlib
import importlib.util
import json
import os
import time
import types

import pytest

import flops_mimo_v2 as flops
import harness
import steps_mimo_v2 as steps

CELL = "mimo-v2.5.serve.mixed128"
DEVICE = {"platform": "cpu", "kind": "cpu", "count": 1}
METRICS = os.path.join(os.path.dirname(os.path.dirname(os.path.abspath(__file__))), "layer_metrics")
NEW_METRICS = ("mfu.serve.mimo", "step_hbm_roofline.serve.mimo", "gqa_decode_roofline.serve.mimo",
               "window_decode_roofline.serve.mimo", "window_prefill_roofline.serve.mimo",
               "admit_device_share.serve.mimo")
SHARED_METRICS = ("device_idle_share.serve", "host_step_ms.serve", "ttft_p95_ms.closed", "itl_p95_ms.serve",
                  "gc_pause_ms.serve", "queue_wait_p95_ms.closed")
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
    line, out = run_cell(capsys, seed=2147483659)  # a seed past 2**31
    assert line["correct"] is True and line["attempted"] > 0 and line["failed"] == 0
    assert set(line["metrics"]) == {"serve_tokens_per_s", "tpot_p95_ms", "setup_s"}
    assert all(v is None for v in line["metrics"].values())  # a rehearsal prints no device number
    assert "jax compiles inside the window 0 of" in out and "live_tokens" in out
    assert "window_rows" in out and "slot_state_bytes" in out


def test_cell_reports_the_metrics_the_benchmark_lists_for_it():
    cell = harness.Cell(CELL)
    assert {m["name"] for m in cell.end_to_end} == {"serve_tokens_per_s", "tpot_p95_ms", "setup_s"}
    assert {m["name"] for m in cell.per_layer} == {*SHARED_METRICS, *NEW_METRICS}
    assert cell.chips == 1 and cell.traffic["clients"] == 128 and cell.traffic["temperature"] == 0.0
    assert cell.traffic["prompt_len"] == cell.traffic["new_tokens"] == {"min": 1024, "max": 8192}
    longest = cell.traffic["prompt_len"]["max"] + cell.traffic["new_tokens"]["max"]
    assert longest == cell.config["n_positions"] == 16384
    engine = cell.spec["engine"]
    assert (engine["max_concurrency"], engine["prompt_buckets"], engine["admit_batch"], engine["max_queue"]) \
        == (128, [2048, 4096, 8192], 1, 256)
    assert engine["paged_kv"]["block_tokens"] == 64 and cell.config["n_positions"] % 64 == 0
    assert not {"prefix_cache", "kv_tier", "speculation", "mesh"} & set(engine)
    assert (cell.traffic["lap"], cell.traffic["strata"], cell.traffic["check_requests"],
            cell.traffic["trace_seconds"]) == (128, 8, 4, 3)


def test_callers_start_from_k_exaones_ramp_and_draw_from_its_pool():
    """The driver imports K-EXAONE's ramp and pool, it does not copy them:
    prompts and answers of the pool are each the mix's 128 quantile lengths,
    paired apart, so long prompts meet short answers and short prompts long
    ones in one queue."""
    import numpy as np

    import traffic as traffic_gen
    from drivers import serve_k_exaone, serve_mimo_v2

    assert serve_mimo_v2.even_ramp is serve_k_exaone.even_ramp
    assert serve_mimo_v2.balanced_pool is serve_k_exaone.balanced_pool
    mix = harness.Cell(CELL).traffic
    pool = serve_mimo_v2.balanced_pool(mix, 5, 19072)
    lap = pool[:128]
    quantiles = sorted(int(n) for n in traffic_gen.quantile_lengths(mix["prompt_len"], 128))
    assert sorted(len(r["prompt"]) for r in lap) == quantiles == sorted(r["new_tokens"] for r in lap)
    prompts, answers = (np.asarray(x) for x in zip(*[(len(r["prompt"]), r["new_tokens"]) for r in lap]))
    assert ((prompts > 6000) & (answers < 1500)).any() and ((prompts < 1500) & (answers > 6000)).any()
    ramp = serve_mimo_v2.even_ramp(mix, 5, 19072)
    assert len(ramp) == 128 and all(r["ramp"] and 1024 <= len(r["prompt"]) <= 8192 for r in ramp)


def test_benchmark_json_gained_one_configuration_one_cell_and_its_metrics():
    """The entries of this configuration and cell, found by name wherever
    they stand and however many others the benchmark holds."""
    with open(os.path.join(harness.ROOT, "BENCHMARK.json")) as f:
        bench = json.load(f)
    configs = [c for c in bench["configs"] if c["name"] == "mimo-v2.5"]
    assert len(configs) == 1 and configs[0]["reduced"] == ["num_hidden_layers", "n_routed_experts", "vocab_size"]
    assert configs[0]["source"] == "https://huggingface.co/XiaomiMiMo/MiMo-V2.5/blob/main/config.json"
    cells = [w for w in bench["workloads"] if w["name"] == CELL]
    assert len(cells) == 1 and cells[0]["chips"] == 1 and cells[0]["config"] == "mimo-v2.5"
    metrics = {m["name"]: m for m in bench["per_layer"]}
    for name in NEW_METRICS:
        assert metrics[name]["workloads"] == [CELL] and metrics[name]["unit"] == "%"
        assert metrics[name]["better"] == ("lower" if name.startswith("admit_") else "higher")
    listed = {m["name"] for m in bench["end_to_end"] + bench["per_layer"] if CELL in m.get("workloads", [])}
    assert listed == {"serve_tokens_per_s", "tpot_p95_ms", *SHARED_METRICS, *NEW_METRICS}
    for entry in configs + cells:
        assert len(entry["why"]) <= 200


def test_the_driver_fails_at_once_without_the_model(monkeypatch):
    """A tree without `accelerate_tpu.models.mimo_v2` (one older than this
    model) fails when the driver is imported, before any result."""
    import sys

    import accelerate_tpu.models

    monkeypatch.setitem(sys.modules, "accelerate_tpu.models.mimo_v2", None)
    monkeypatch.delattr(accelerate_tpu.models, "mimo_v2", raising=False)
    monkeypatch.delitem(sys.modules, "drivers.serve_mimo_v2", raising=False)
    with pytest.raises(ImportError):
        importlib.import_module("drivers.serve_mimo_v2")


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


def test_a_program_without_its_sinks_is_not_correct(capsys, monkeypatch):
    """The sliding layers without their sinks, in the prefill and in the
    decode kernel alike: the limits see it."""
    from accelerate_tpu.models import k_exaone

    real = k_exaone._window_attend

    def no_sinks(*args, **kw):
        kw["sinks"] = None
        return real(*args, **kw)

    monkeypatch.setattr("accelerate_tpu.models.mimo_v2._window_attend", no_sinks)
    line, _ = run_cell(capsys, seed=11)
    assert line["correct"] is False and over_limit(line)


def served_whole(cell, driver, seed, count=24):
    """The pool's first `count` requests served to their ends: the sample a
    run would compare, without the window's clock."""
    from drivers.serve import submit

    engine = driver.build(cell, seed)
    pool = driver.balanced_pool(cell.traffic, seed, cell.config["vocab_size"])[:count]
    sent = {submit(engine, item, 0.0): item for item in pool}
    done = []
    while engine.has_work:
        done += [(sent[out.request_id], out) for out in engine.step()]
    return done


@pytest.mark.parametrize("seed", [1, 2, 3])
def test_controls_are_not_correct(seed):
    """The reference with every weight product in int8, with the window
    removed, or with the sinks removed, in the program's place fails a limit;
    so does the altered token; the program itself passes both."""
    import calibrate_mimo_v2 as calibrate
    from drivers import serve_mimo_v2 as driver

    cell = harness.Cell(CELL, rehearsal=True)
    sample, limits = served_whole(cell, driver, seed), cell.spec["limits"]
    assert calibrate.CONTROLS == driver.CONTROLS == ("int8", "no_window", "no_sinks")
    assert max(len(item["prompt"]) for item, _ in sample) > 3 * cell.config["sliding_window"]
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
    return harness.overlay(harness.load_json("configs", "mimo-v2.5.json"), False)


def test_configuration_keeps_every_published_number(published):
    """Every number of the catalog's row under its own key, the three cuts
    apart; widths spelled out here so that a slip shows without the catalog."""
    cfg = published
    widths = dict(hidden_size=4096, intermediate_size=16384, moe_intermediate_size=2048, head_dim=192,
                  v_head_dim=128, swa_head_dim=192, swa_v_head_dim=128, num_attention_heads=64,
                  swa_num_attention_heads=64, num_key_value_heads=4, swa_num_key_value_heads=8,
                  num_experts_per_tok=8, sliding_window=128, sliding_window_size=128, attention_chunk_size=128,
                  partial_rotary_factor=0.334, rope_theta=10000000, swa_rope_theta=10000,
                  attention_value_scale=0.707, layernorm_epsilon=1e-5, n_group=1, topk_group=1,
                  max_position_embeddings=1048576)
    assert {k: cfg[k] for k in widths} == widths
    assert cfg["add_swa_attention_sink_bias"] is True and cfg["add_full_attention_sink_bias"] is False
    assert cfg["n_shared_experts"] is None and cfg["routed_scaling_factor"] is None
    assert cfg["hybrid_layer_pattern"] == [0] + [1] * 4 + [0] + ([1] * 5 + [0]) * 7
    assert cfg["moe_layer_freq"] == [0] + [1] * 47
    assert set(cfg["reduced"]) == set(cfg["published"])
    assert cfg["published"] == {"num_hidden_layers": 48, "n_routed_experts": 256, "vocab_size": 152576}
    assert (cfg["num_hidden_layers"], cfg["n_routed_experts"], cfg["vocab_size"]) == (7, 16, 19072)
    assert flops.router_width(cfg) == 256 and flops.dense_layers(cfg) == 1 and flops.expert_layers(cfg) == 6
    assert flops.kinds(cfg) == [False, True, True, True, True, False, True]
    assert cfg["deployment"]["chips_sharing_a_layer"] == 16
    for reading in ("precision", "layer_equations", "qk_norm", "sinks", "value_scale", "rotary",
                    "sliding_window", "router", "weights", "mtp", "towers"):
        assert cfg["assumed"][reading]


def test_a_published_switch_this_model_has_no_code_for_is_refused(published):
    import weights_mimo_v2 as weights

    weights.model_config(published)
    for key, other in (("scoring_func", "softmax"), ("n_group", 8), ("tie_word_embeddings", True),
                       ("add_full_attention_sink_bias", True)):
        with pytest.raises(ValueError, match=key):
            weights.model_config(dict(published, **{key: other}))
    with pytest.raises(ValueError, match="shared expert"):
        weights.model_config(dict(published, n_shared_experts=1))
    with pytest.raises(ValueError, match="attention_chunk_size"):
        weights.model_config(dict(published, attention_chunk_size=256))


def test_parameter_counts_against_hand_counts(published):
    cfg = published
    sliding = 4096 * (64 * 192 + 8 * 192 + 8 * 128 + 64 * 128)
    full = 4096 * (64 * 192 + 4 * 192 + 4 * 128 + 64 * 128)
    assert flops.attention_matmul_params(cfg, True) == sliding == 94_371_840
    assert flops.attention_matmul_params(cfg, False) == full == 89_128_960
    assert flops.expert_params(cfg) == 3 * 4096 * 2048 == 25_165_824
    assert flops.dense_mlp_params(cfg) == 3 * 4096 * 16384 == 201_326_592
    router = 4096 * 256 + 256
    expert_layer = 2 * 4096 + router + 16 * 25_165_824
    assert [flops.layer_params(cfg, i) for i in range(7)] == \
        [full + 2 * 4096 + 201_326_592] + [sliding + 64 + expert_layer] * 4 + [full + expert_layer] \
        + [sliding + 64 + expert_layer]
    total = 2 * full + 5 * (sliding + 64) + 7 * 2 * 4096 + 201_326_592 + 6 * (router + 16 * 25_165_824) \
        + 2 * 19072 * 4096 + 4096
    assert flops.total_params(cfg) == total == 3_429_955_392
    assert flops.param_bytes(cfg) == 2 * total + 2 * (6 * router + 5 * 64)  # routers, biases, sinks float32
    assert flops.held_picks_per_token(cfg) == 0.5  # 8 picks, 16 of the 256 held


def test_decode_step_bytes_against_hand_counts(published):
    """The least bytes of a decode step at 128 slots and a mean live context
    of 5.75k: about 10.9 GB, 13.3 ms at 819 GB/s, as the cut's arithmetic
    has it with every held expert touched."""
    cfg = published
    live, rings = 128 * 5750.0, 5 * 128 * 128.0
    got = flops.decode_step_bytes(cfg, rows=128, experts_touched=16.0, live_tokens=live, window_rows=rings)
    assert got["experts"] == 6 * 16 * 25_165_824 * 2  # 4.83 GB
    assert got["attention_weights"] == (2 * 89_128_960 + 5 * 94_371_840) * 2 + 5 * 64 * 4
    assert got["dense_mlp"] == 201_326_592 * 2
    assert got["router"] == 6 * (4096 * 256 + 256) * 4
    assert got["head_and_norms"] == (19072 * 4096 + 128 * 4096 + 4096 + 14 * 4096) * 2
    assert got["full_kv"] == 2 * 4 * 320 * 2 * (live + 128)  # 3.77 GB: 2,560 B a position a layer
    assert got["rings"] == 8 * 320 * 2 * (rings + 5 * 128)  # 0.42 GB
    assert got["total"] == sum(v for k, v in got.items() if k != "total")
    assert 10.7e9 < got["total"] < 11.1e9 and 13.1e-3 < got["total"] / 819e9 < 13.5e-3
    cost = flops.gqa_decode_cost(cfg, live, 128)
    assert cost["flops"] == 2 * 64 * 320 * live and cost["bytes"] == 2560 * live + 128 * 64 * 320 * 2
    assert cost["flops"] / 197e12 < cost["bytes"] / 819e9  # 16 FLOP a byte: the bytes bound the kernel
    ring = flops.window_decode_cost(cfg, rings, 128)
    assert ring["bytes"] == 5120 * rings + 5 * 128 * (5120 + 64 * 320 * 2)
    band = flops.window_prefill_cost(cfg, 8192)
    assert band["flops"] == 2 * 64 * 320 * (sum(range(1, 129)) + (8192 - 128) * 128)
    assert band["bytes"] == 8192 * (8 * 320 + 64 * 320) * 2  # q, k, v in and the output out
    assert band["flops"] / 197e12 < band["bytes"] / 819e9


def test_request_flops_grow_with_what_is_fed(published):
    cfg = published
    one = flops.serve_request_flops(cfg, 300, 1)
    more = flops.serve_request_flops(cfg, 300, 11)
    per_token = flops.token_flops(cfg) + 2.0 * 4096 * 19072
    # ten decode steps at positions 300 .. 309: each full layer sees 301 .. 310 keys, each sliding one 128
    keys_full, keys_sliding = sum(range(301, 311)), 10 * 128
    assert more - one == pytest.approx(10 * per_token + 2 * 2.0 * 64 * 320 * keys_full
                                       + 5 * 2.0 * 64 * 320 * keys_sliding)
    by_hand = 2.0 * (2 * 89_128_960 + 5 * 94_371_840 + 201_326_592 + 6 * (4096 * 256 + 0.5 * 25_165_824))
    assert flops.token_flops(cfg) == by_hand


# ----------------------------------------------------------------- the readers
def reader(name: str):
    spec = importlib.util.spec_from_file_location("layer_metric", os.path.join(METRICS, f"{name}.py"))
    module = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(module)
    return module.read


KERNEL = ('%attn.{} = bf16[128,64,128] custom-call(...), custom_call_target="tpu_custom_call", '
          'operand_layout_constraints={{s32[128,256]{{1,0}}, s32[128]{{0}}, bf16[128,64,192]{{2,1,0}}, '
          'bf16[15360,64,768]{{2,1,0}}, bf16[15360,64,512]{{2,1,0}}}}')
RING = ('%window_attn.{} = bf16[128,64,128] custom-call(...), custom_call_target="tpu_custom_call", '
        'operand_layout_constraints={{s32[128,1]{{1,0}}, s32[128]{{0}}, bf16[128,64,192]{{2,1,0}}, '
        'bf16[128,128,1536]{{2,1,0}}, bf16[128,128,1024]{{2,1,0}}, f32[64,128]{{1,0}}}}')
RING_WRITE = '%scatter.{} = bf16[128,128,1024] scatter(bf16[128,128,1024] %ring, s32[128] %i, bf16[128,1024] %new)'
ADMIT_RING = '%fusion.{} = bf16[1,128,1536] fusion(bf16[1,8192,1536] %k)'
BAND = ('%window_attn.{} = (bf16[1,64,8192,128], f32[1,64,8192,8]) custom-call(...), '
        'custom_call_target="tpu_custom_call", operand_layout_constraints={{s32[31]{{0}}, '
        'bf16[1,64,8192,192]{{3,2,1,0}}, bf16[1,8,8192,192]{{3,2,1,0}}, bf16[1,8,8192,128]{{3,2,1,0}}, '
        'f32[64,1,128]{{2,1,0}}}}')
GMM = '%gmm.{} = f32[{},{}] custom-call(...), custom_call_target="tpu_custom_call"'
LIVE, ROWS_READ = 736_000, 80_000


def traced_run(published, steps_held=12, admit_after=(3, 7)):
    """Device events of `steps_held` decode steps (the two full layers' kernel
    3.0 ms each; on each of the five sliding layers the sink kernel 0.1 ms and
    the key and value rings' writes 0.02 ms each; on the six expert layers two
    grouped products of 0.8 and 0.5 ms; 0.4 ms of the rest a layer; the head
    0.3 ms), an admit program after the steps in `admit_after` (the band
    kernel 2.0 ms on each sliding layer, 150 ms else, an admit ring's write);
    counters of 64 picks on 14 experts a layer a step, `LIVE` live keys a
    dispatch and `ROWS_READ` ring rows a step."""
    events, at = [], 1000.0

    def op(name, ms):
        nonlocal at
        events.append((name, at, ms * MS))
        at += ms * MS + 2_000.0

    kinds = flops.kinds(published)
    for i in range(steps_held):
        for layer in range(7):
            if kinds[layer]:
                op(RING.format(layer), 0.1)
                op(RING_WRITE.format(2 * layer), 0.02)
                op(RING_WRITE.format(2 * layer + 1).replace("1024", "1536"), 0.02)
            else:
                op(KERNEL.format(layer + 2), 3.0)
            if layer >= 1:
                op(GMM.format(2 * layer, 1024, 4096), 0.8)
                op(GMM.format(2 * layer + 1, 1024, 4096), 0.5)
            op(f"%fusion.{20 + layer} = bf16[128,4096] fusion(...)", 0.4)
        op("%fusion.77 = f32[128,19072] fusion(...)", 0.3)
        if i in admit_after:
            for layer in range(5):
                op(BAND.format(layer), 2.0)
            op(ADMIT_RING.format(5), 0.2)
            op("%fusion.99 = bf16[1,8192,4096] fusion(...)", 150.0)
    cell = types.SimpleNamespace(rehearsal=False, config=published,
                                 spec={"engine": {"max_concurrency": 128, "admit_batch": 1}})
    item, out = {"prompt": [0] * 4400}, types.SimpleNamespace(tokens=[0] * 3000)

    def counters(n):
        return {"steps": n, "moe_picks_held": 6 * 64 * n, "moe_experts_touched": 6 * 14 * n,
                "window_rows": ROWS_READ * n, "live_tokens": LIVE * n, "span_tokens": 128 * 16384 * n}

    window = {"done": [(item, out)] * 5, "seconds": 51.0, "counters0": counters(100), "counters1": counters(400)}
    return {"cell": cell, "peaks_kind": "TPU v5 lite", "chips": 1, "window": window,
            "trace": {"per_device": {"/device:TPU:0": events}, "busy_s": 1.0, "window_s": 1.0},
            "traced": {"counters0": counters(200), "counters1": counters(200 + steps_held)}}


STEP_MS = 2 * 3.0 + 5 * 0.14 + 6 * 1.3 + 7 * 0.4 + 0.3  # 17.6 ms of operations a step
ADMIT_MS = 5 * 2.0 + 0.2 + 150.0


def test_step_device_time_leaves_the_admits_out(published):
    run = traced_run(published)
    assert steps.step_device_ns(run) == pytest.approx(STEP_MS * MS, rel=1e-6)
    assert steps.per_step(run) == {"steps": 12, "picks_held": 384.0, "experts_touched": 84.0}
    assert steps.live_tokens(run) == LIVE and steps.window_rows(run) == ROWS_READ
    assert len(steps.full_kernels(run)) == 24 and len(steps.step_marks(run)) == 12
    assert len(steps.ring_events(run)) == 12 * 15  # the sink kernels and both rings' writes; no admit row's
    assert [(rows, bucket) for _, rows, bucket in steps.band_events(run)] == [(1, 8192)] * 10


def test_too_few_steps_read_nothing(published):
    run = traced_run(published, steps_held=5, admit_after=())
    assert steps.step_device_ns(run) is None
    assert reader("step_hbm_roofline.serve.mimo")(run) is None


def test_step_hbm_roofline_is_least_bytes_over_the_steps_time(published):
    run = traced_run(published)
    least = flops.decode_step_bytes(published, 128, 14.0, LIVE, ROWS_READ)["total"]
    got = reader("step_hbm_roofline.serve.mimo")(run)
    assert got == pytest.approx(100.0 * (least / 819e9) / (STEP_MS * 1e-3), rel=1e-6) and got < 100.0


def test_gqa_kernel_roofline_reads_both_full_layers_kernels(published):
    run = traced_run(published)
    cost = flops.gqa_decode_cost(published, LIVE, 128)
    assert reader("gqa_decode_roofline.serve.mimo")(run) == pytest.approx(
        100.0 * (cost["bytes"] / 819e9) / 3.0e-3, rel=1e-6)


def test_window_roofline_reads_the_events_that_touch_the_rings(published):
    run = traced_run(published)
    cost = flops.window_decode_cost(published, ROWS_READ, 128)
    # five sink kernels of 0.1 ms and ten writes of 0.02 ms a step; the admit's ring is left out
    assert reader("window_decode_roofline.serve.mimo")(run) == pytest.approx(
        100.0 * (cost["bytes"] / 819e9) / 0.7e-3, rel=1e-6)


def test_window_prefill_roofline_reads_the_band_kernels_of_the_admits(published):
    run = traced_run(published)
    cost = flops.window_prefill_cost(published, 8192)
    assert reader("window_prefill_roofline.serve.mimo")(run) == pytest.approx(
        100.0 * (cost["bytes"] / 819e9) / 2.0e-3, rel=1e-6)


def test_admit_share_reads_what_the_admit_periods_hold_beyond_a_step(published):
    """Two admit programs in 11 periods between 12 step marks."""
    run = traced_run(published)
    assert reader("admit_device_share.serve.mimo")(run) == pytest.approx(
        100.0 * 2 * ADMIT_MS / (11 * STEP_MS + 2 * ADMIT_MS), rel=1e-6)
    assert reader("admit_device_share.serve.mimo")(traced_run(published, admit_after=())) == 0.0


def test_mfu_reads_the_window(published):
    run = traced_run(published)
    total = 5 * flops.serve_request_flops(published, 4400, 3000)
    assert reader("mfu.serve.mimo")(run) == pytest.approx(100.0 * total / 51.0 / 197e12)


@pytest.mark.parametrize("name", NEW_METRICS)
def test_each_reader_returns_none_on_an_empty_run(published, name):
    """Against a program without the counters or the kernels (a tree older
    than this model cannot run the cell at all), a run with no trace, and a
    rehearsal: nothing to read, nothing raised."""
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
