"""The K-EXAONE cell on the CPU at its rehearsal sizes: the walk of a run,
`correct` false when it should be (a served token altered; the reference
altered in the program's place: int8 weight products, the window removed,
rotary positions on the full-attention layer), the counts of
`flops_k_exaone.py` against hand counts at the published widths, and the
cell's per-layer readers on hand-built device events and counters."""

import importlib
import importlib.util
import json
import os
import time
import types

import pytest

import flops_k_exaone as flops
import harness
import steps_k_exaone as steps

CELL = "k-exaone-236b-a23b.serve.long128"
DEVICE = {"platform": "cpu", "kind": "cpu", "count": 1}
METRICS = os.path.join(os.path.dirname(os.path.dirname(os.path.abspath(__file__))), "layer_metrics")
NEW_METRICS = ("mfu.serve.kexaone", "step_hbm_roofline.serve.kexaone", "gqa_decode_roofline.serve.kexaone",
               "window_decode_roofline.serve.kexaone", "admit_device_share.serve.kexaone")
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
    assert cell.traffic["prompt_len"] == {"min": 2048, "max": 8192}
    assert cell.traffic["new_tokens"] == {"min": 1024, "max": 4096}
    longest = cell.traffic["prompt_len"]["max"] + cell.traffic["new_tokens"]["max"]
    assert longest == cell.config["n_positions"] == 12288
    engine = cell.spec["engine"]
    assert (engine["max_concurrency"], engine["prompt_buckets"], engine["admit_batch"]) == (128, [4096, 8192], 1)
    # no prompt padded past twice its length
    assert all(b <= 2 * lo for lo, b in ((2048, 4096), (4097, 8192)))
    assert engine["paged_kv"]["block_tokens"] == 64 and cell.config["n_positions"] % 64 == 0
    assert not {"prefix_cache", "kv_tier", "speculation", "mesh"} & set(engine)


def test_ramp_holds_the_same_work_left_for_every_seed():
    """Every seed starts its callers on the same answers left (the
    mid-quantiles of length x share) and prompts' lengths, in one order, with
    the seed's tokens; their mean is what `traffic.aged_ramp`'s pairs average to."""
    import traffic as traffic_gen
    from drivers import serve_k_exaone as driver

    mix = harness.Cell(CELL).traffic
    a, b = driver.even_ramp(mix, 2147483659, 19200), driver.even_ramp(mix, 7, 19200)
    assert a == driver.even_ramp(mix, 2147483659, 19200) and len(a) == mix["clients"]
    assert all(r["ramp"] for r in a)
    assert [(len(r["prompt"]), r["new_tokens"]) for r in a] == [(len(r["prompt"]), r["new_tokens"]) for r in b]
    assert [r["prompt"][:8] for r in a] != [r["prompt"][:8] for r in b]  # the seed's tokens
    assert sorted(len(r["prompt"]) for r in a) == list(traffic_gen.quantile_lengths(mix["prompt_len"], 128))
    left = [r["new_tokens"] for r in a]
    assert min(left) >= 2 and max(left) < mix["new_tokens"]["max"]
    aged = [r["new_tokens"] for s in range(16) for r in traffic_gen.aged_ramp(mix, s, 19200)]
    assert sum(left) / len(left) == pytest.approx(sum(aged) / len(aged), rel=0.02)


def test_pool_holds_the_mix_in_balanced_blocks():
    """Each lap holds the lap's quantile lengths, as `traffic.request_pool`'s
    does; each block of lap / strata requests one prompt and one answer of
    each stratum, so that four blocks of prompts hold 16 above the 4,096
    bucket and 16 below."""
    import traffic as traffic_gen
    from drivers import serve_k_exaone as driver

    mix = harness.Cell(CELL).traffic
    lap, strata = mix["lap"], mix["strata"]
    pool = driver.balanced_pool(mix, 2147483659, 19200)
    assert pool == driver.balanced_pool(mix, 2147483659, 19200) and len(pool) == lap * mix["laps"]
    other = driver.balanced_pool(mix, 7, 19200)
    def lengths(requests):
        return [(len(r["prompt"]), r["new_tokens"]) for r in requests]

    assert lengths(pool) == lengths(other)  # one order of lengths for every seed
    assert [r["prompt"][:8] for r in pool] != [r["prompt"][:8] for r in other]  # the seed's tokens
    prompts = traffic_gen.quantile_lengths(mix["prompt_len"], lap)
    answers = traffic_gen.quantile_lengths(mix["new_tokens"], lap)
    for at in range(0, len(pool), lap):
        chunk = pool[at: at + lap]
        assert sorted(len(r["prompt"]) for r in chunk) == list(prompts)
        assert sorted(r["new_tokens"] for r in chunk) == list(answers)
    edges_p, edges_a = prompts[:: lap // strata], answers[:: lap // strata]
    for at in range(0, len(pool), strata):
        block = pool[at: at + strata]
        assert sorted(int((edges_p <= len(r["prompt"])).sum()) for r in block) == list(range(1, strata + 1))
        assert sorted(int((edges_a <= r["new_tokens"]).sum()) for r in block) == list(range(1, strata + 1))
        assert sum(len(r["prompt"]) <= 4096 for r in block) == strata // 2
    with pytest.raises(ValueError, match="strata"):
        driver.balanced_pool(dict(mix, strata=5), 1, 19200)


def test_benchmark_json_gained_one_configuration_one_cell_and_its_metrics():
    """The entries of this configuration and cell, found by name wherever
    they stand and however many others the benchmark holds."""
    with open(os.path.join(harness.ROOT, "BENCHMARK.json")) as f:
        bench = json.load(f)
    configs = [c for c in bench["configs"] if c["name"] == "k-exaone-236b-a23b"]
    assert len(configs) == 1 and configs[0]["reduced"] == ["num_hidden_layers", "num_experts", "vocab_size",
                                                           "num_nextn_predict_layers"]
    cells = [w for w in bench["workloads"] if w["name"] == CELL]
    assert len(cells) == 1 and cells[0]["chips"] == 1 and cells[0]["config"] == "k-exaone-236b-a23b"
    metrics = {m["name"]: m for m in bench["per_layer"]}
    for name in NEW_METRICS:
        assert metrics[name]["workloads"] == [CELL] and metrics[name]["unit"] == "%"
        assert metrics[name]["better"] == ("lower" if name.startswith("admit_") else "higher")
    listed = {m["name"] for m in bench["end_to_end"] + bench["per_layer"] if CELL in m.get("workloads", [])}
    assert listed == {"serve_tokens_per_s", "tpot_p95_ms", *SHARED_METRICS, *NEW_METRICS}
    for entry in configs + cells:
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


def test_a_program_without_the_window_is_not_correct(capsys, monkeypatch):
    """The sliding layers' prefill attending the whole causal context (their
    decode steps still read the ring): the limits see it."""
    from accelerate_tpu.models import k_exaone

    real = k_exaone.attention

    def no_window(q, k, v, **kw):
        kw.pop("window", None)
        return real(q, k, v, **kw)

    monkeypatch.setattr(k_exaone, "attention", no_window)
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
    removed, or with rotary positions on the full-attention layer, in the
    program's place fails a limit; so does the altered token; the program
    itself passes both."""
    import calibrate_k_exaone as calibrate
    from drivers import serve_k_exaone as driver

    cell = harness.Cell(CELL, rehearsal=True)
    sample, limits = served_whole(cell, driver, seed), cell.spec["limits"]
    assert calibrate.CONTROLS == driver.CONTROLS == ("int8", "no_window", "rope_global")
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
    return harness.overlay(harness.load_json("configs", "k-exaone-236b-a23b.json"), False)


def test_configuration_keeps_every_published_number(published):
    """Every number of the catalog's row under its own key, the four cuts
    apart; widths spelled out here so that a slip shows without the catalog."""
    cfg = published
    widths = dict(hidden_size=6144, intermediate_size=18432, moe_intermediate_size=2048, head_dim=128,
                  num_attention_heads=64, num_key_value_heads=8, num_experts_per_tok=8, num_shared_experts=1,
                  sliding_window=128, routed_scaling_factor=2.5, rms_norm_eps=1e-5, first_k_dense_replace=1,
                  n_group=1, topk_group=1, max_position_embeddings=262144)
    assert {k: cfg[k] for k in widths} == widths
    assert cfg["rope_parameters"] == {"rope_theta": 1000000, "rope_type": "default"}
    assert cfg["layer_types"] == ["sliding_attention"] * 3 + ["full_attention"] + \
        (["sliding_attention"] * 3 + ["full_attention"]) * 11
    assert cfg["mlp_layer_types"] == ["dense"] + ["sparse"] * 47
    assert cfg["sliding_windows"] == [128, 128, 128, 0] * 12 and cfg["sliding_window_pattern"] == "LLLG"
    assert set(cfg["reduced"]) == set(cfg["published"])
    assert cfg["published"] == {"num_hidden_layers": 48, "num_experts": 128, "vocab_size": 153600,
                                "num_nextn_predict_layers": 1}
    assert (cfg["num_hidden_layers"], cfg["num_experts"], cfg["vocab_size"], cfg["num_nextn_predict_layers"]) \
        == (5, 16, 19200, 0)
    assert flops.router_width(cfg) == 128 and flops.dense_layers(cfg) == 1 and flops.expert_layers(cfg) == 4
    assert flops.kinds(cfg) == ["sliding"] * 3 + ["full", "sliding"]
    assert cfg["deployment"]["chips_sharing_a_layer"] == 8
    for reading in ("precision", "layer_equations", "qk_norm", "rope_on_sliding_only", "sliding_window",
                    "shared_expert", "router", "weights", "mtp"):
        assert cfg["assumed"][reading]


def test_a_published_switch_this_model_has_no_code_for_is_refused(published):
    import weights_k_exaone as weights

    weights.model_config(published)
    for key, other in (("scoring_func", "softmax"), ("n_group", 8), ("tie_word_embeddings", True)):
        with pytest.raises(ValueError, match=key):
            weights.model_config(dict(published, **{key: other}))


def test_parameter_counts_against_hand_counts(published):
    cfg = published
    attention = 2 * 6144 * 8192 + 2 * 6144 * 1024 + 2 * 128
    assert flops.attention_params(cfg) == attention == 113_246_464
    assert flops.expert_params(cfg) == 3 * 6144 * 2048 == 37_748_736
    assert flops.dense_mlp_params(cfg) == 3 * 6144 * 18432 == 339_738_624
    router = 6144 * 128 + 128
    expert_layer = attention + 2 * 6144 + router + 17 * 37_748_736  # 16 held and the shared one
    assert [flops.layer_params(cfg, i) for i in range(5)] == \
        [attention + 2 * 6144 + 339_738_624] + [expert_layer] * 4
    total = attention * 5 + 2 * 6144 * 5 + 339_738_624 + 4 * (router + 17 * 37_748_736) + 2 * 19200 * 6144 + 6144
    assert flops.total_params(cfg) == total == 3_712_028_416
    assert flops.param_bytes(cfg) == 2 * total + 2 * 4 * router  # the routers and biases in float32
    assert flops.held_picks_per_token(cfg) == 1.0  # 8 picks, 16 of the 128 held


def test_decode_step_bytes_against_hand_counts(published):
    """The least bytes of a decode step at 128 slots and a mean live context
    of 5.5k: about 10.3 GB, 12.6 ms at 819 GB/s."""
    cfg = published
    live, rings = 128 * 5500.0, 4 * 128 * 128.0
    got = flops.decode_step_bytes(cfg, rows=128, experts_touched=16.0, live_tokens=live, window_rows=rings)
    assert got["experts"] == 4 * 16 * 37_748_736 * 2  # 4.83 GB
    assert got["attention_weights"] == 5 * 113_246_464 * 2
    assert got["dense_mlp"] == 339_738_624 * 2
    assert got["shared_and_router"] == 4 * (37_748_736 * 2 + (6144 * 128 + 128) * 4)
    assert got["head_and_norms"] == (19200 * 6144 + 128 * 6144 + 6144 + 10 * 6144) * 2
    assert got["full_kv"] == 2 * 1024 * 2 * (live + 128)  # 2.88 GB
    assert got["rings"] == 2 * 1024 * 2 * (rings + 4 * 128)  # 0.27 GB
    assert got["total"] == sum(v for k, v in got.items() if k != "total")
    assert 10.2e9 < got["total"] < 10.45e9 and 12.4e-3 < got["total"] / 819e9 < 12.8e-3
    cost = flops.gqa_decode_cost(cfg, live, 128)
    assert cost["flops"] == 4 * 64 * 128 * live and cost["bytes"] == 4096 * live + 2 * 128 * 8192 * 2
    assert cost["flops"] / 197e12 < cost["bytes"] / 819e9  # 16 FLOP a byte: the bytes bound the kernel
    ring = flops.window_decode_cost(cfg, rings, 128)
    assert ring["bytes"] == 4096 * rings + 4 * 128 * (4096 + 2 * 8192 * 2)


def test_request_flops_grow_with_what_is_fed(published):
    cfg = published
    one = flops.serve_request_flops(cfg, 300, 1)
    more = flops.serve_request_flops(cfg, 300, 11)
    per_token = flops.token_flops(cfg) + 2.0 * 6144 * 19200
    # ten decode steps at positions 300 .. 309: the full layer sees 301 .. 310 keys, each sliding one 128
    keys = sum(range(301, 311)) + 4 * 10 * 128
    assert more - one == pytest.approx(10 * per_token + 4.0 * 64 * 128 * keys)
    assert flops.window_keys(cfg, 0, 130) == sum(range(1, 129)) + 2 * 128
    by_hand = 2.0 * (5 * (2 * 6144 * 8192 + 2 * 6144 * 1024) + 339_738_624
                     + 4 * (6144 * 128 + 2 * 37_748_736))  # one held pick and the shared expert
    assert flops.token_flops(cfg) == by_hand


# ----------------------------------------------------------------- the readers
def reader(name: str):
    spec = importlib.util.spec_from_file_location("layer_metric", os.path.join(METRICS, f"{name}.py"))
    module = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(module)
    return module.read


KERNEL = ('%attn.{} = bf16[128,64,128] custom-call(bf16[128,64,128] %q, bf16[15360,64,1024] %k, '
          'bf16[15360,64,1024] %v, s32[128,192] %t, s32[128] %n), custom_call_target="tpu_custom_call"')
RING = '%fusion.{} = f32[128,8,8,128] fusion(bf16[128,64,128] %q, bf16[128,128,1024] %ring)'
ADMIT_RING = ('%scatter.{} = bf16[128,128,1024] scatter(bf16[128,128,1024] %ring, s32[1,1] %i, '
              'bf16[1,128,1024] %new)')
GMM = '%gmm.{} = f32[{},{}] custom-call(...), custom_call_target="tpu_custom_call"'
LIVE, ROWS_READ = 700_000, 60_000


def traced_run(published, steps_held=12, admit_after=(3, 7)):
    """Device events of `steps_held` decode steps (on each of the four sliding
    layers two ring fusions of 0.06 ms; the full layer's kernel 4.0 ms; on the
    four expert layers two grouped products of 1.0 and 0.6 ms; 0.5 ms of the
    rest a layer; the head 0.3 ms), an admit program of 160 ms after the steps
    in `admit_after` (its own ring scatter among it); counters of 128 picks on
    16 experts a layer a step, `LIVE` live keys a dispatch and `ROWS_READ`
    ring rows a step."""
    events, at = [], 1000.0

    def op(name, ms):
        nonlocal at
        events.append((name, at, ms * MS))
        at += ms * MS + 2_000.0

    for i in range(steps_held):
        for layer in range(5):
            if layer == 3:
                op(KERNEL.format(3), 4.0)
            else:
                op(RING.format(2 * layer), 0.06)
                op(RING.format(2 * layer + 1), 0.06)
            if layer >= 1:
                op(GMM.format(2 * layer, 1024, 4096), 1.0)
                op(GMM.format(2 * layer + 1, 1024, 6144), 0.6)
            op(f"%fusion.{20 + layer} = bf16[128,6144] fusion(...)", 0.5)
        op("%fusion.77 = f32[128,19200] fusion(...)", 0.3)
        if i in admit_after:
            op(GMM.format(90, 65536, 4096), 40.0)
            op(ADMIT_RING.format(5), 0.2)
            op("%fusion.99 = bf16[1,8192,6144] fusion(...)", 120.0)
    cell = types.SimpleNamespace(rehearsal=False, config=published,
                                 spec={"engine": {"max_concurrency": 128, "admit_batch": 1}})
    item, out = {"prompt": [0] * 4400}, types.SimpleNamespace(tokens=[0] * 2200)

    def counters(n):
        return {"steps": n, "moe_picks_held": 4 * 128 * n, "moe_experts_touched": 4 * 16 * n,
                "window_rows": ROWS_READ * n, "live_tokens": LIVE * n, "span_tokens": 128 * 12288 * n}

    window = {"done": [(item, out)] * 5, "seconds": 51.0, "counters0": counters(100), "counters1": counters(400)}
    return {"cell": cell, "peaks_kind": "TPU v5 lite", "chips": 1, "window": window,
            "trace": {"per_device": {"/device:TPU:0": events}, "busy_s": 1.0, "window_s": 1.0},
            "traced": {"counters0": counters(200), "counters1": counters(200 + steps_held)}}


STEP_MS = 8 * 0.06 + 4.0 + 4 * 1.6 + 5 * 0.5 + 0.3  # 13.68 ms of operations a step


def test_step_device_time_leaves_the_admits_out(published):
    run = traced_run(published)
    assert steps.step_device_ns(run) == pytest.approx(STEP_MS * MS, rel=1e-6)
    assert steps.per_step(run) == {"steps": 12, "picks_held": 512.0, "experts_touched": 64.0}
    assert steps.live_tokens(run) == LIVE and steps.window_rows(run) == ROWS_READ
    assert len(steps.full_kernels(run)) == 12 and len(steps.ring_events(run)) == 12 * 8  # no admit scatter


def test_too_few_steps_read_nothing(published):
    run = traced_run(published, steps_held=5, admit_after=())
    assert steps.step_device_ns(run) is None
    assert reader("step_hbm_roofline.serve.kexaone")(run) is None


def test_step_hbm_roofline_is_least_bytes_over_the_steps_time(published):
    run = traced_run(published)
    least = flops.decode_step_bytes(published, 128, 16.0, LIVE, ROWS_READ)["total"]
    got = reader("step_hbm_roofline.serve.kexaone")(run)
    assert got == pytest.approx(100.0 * (least / 819e9) / (STEP_MS * 1e-3), rel=1e-6) and got < 100.0


def test_gqa_kernel_roofline_reads_the_full_layers_kernel(published):
    run = traced_run(published)
    cost = flops.gqa_decode_cost(published, LIVE, 128)
    assert reader("gqa_decode_roofline.serve.kexaone")(run) == pytest.approx(
        100.0 * (cost["bytes"] / 819e9) / 4.0e-3, rel=1e-6)


def test_window_roofline_reads_the_events_that_touch_the_rings(published):
    run = traced_run(published)
    cost = flops.window_decode_cost(published, ROWS_READ, 128)
    # 8 ring fusions of 0.06 ms a step; the admit's scatter names the admit's ring and is left out
    assert reader("window_decode_roofline.serve.kexaone")(run) == pytest.approx(
        100.0 * (cost["bytes"] / 819e9) / 0.48e-3, rel=1e-6)


def test_ring_read_by_the_fused_kernel_is_told_from_the_full_layers():
    """Were the rings read by the fused kernel (a pool of one block a slot),
    its events would name the ring's shape: they count as ring events and not
    as steps."""
    cfg = harness.overlay(harness.load_json("configs", "k-exaone-236b-a23b.json"), False)
    ring_kernel = ('%attn.7 = bf16[128,64,128] custom-call(bf16[128,64,128] %q, bf16[128,128,1024] %k, '
                   'bf16[128,128,1024] %v, s32[128,1] %t, s32[128] %n), custom_call_target="tpu_custom_call"')
    run = traced_run(cfg)
    run["trace"]["per_device"]["/device:TPU:0"].append((ring_kernel, 0.0, 1e5))
    assert len(steps.full_kernels(run)) == 12 and ring_kernel in [e[0] for e in steps.ring_events(run)]


def test_admit_share_reads_what_the_admit_periods_hold_beyond_a_step(published):
    """Two admit programs of 160.2 ms of operations each, in 11 periods
    between 12 full-layer kernels."""
    run = traced_run(published)
    admits = 2 * (40.0 + 0.2 + 120.0)
    assert reader("admit_device_share.serve.kexaone")(run) == pytest.approx(
        100.0 * admits / (11 * STEP_MS + admits), rel=1e-6)
    assert reader("admit_device_share.serve.kexaone")(traced_run(published, admit_after=())) == 0.0


def test_mfu_reads_the_window(published):
    run = traced_run(published)
    total = 5 * flops.serve_request_flops(published, 4400, 2200)
    assert reader("mfu.serve.kexaone")(run) == pytest.approx(100.0 * total / 51.0 / 197e12)


@pytest.mark.parametrize("name", NEW_METRICS)
def test_each_reader_returns_none_on_an_empty_run(published, name):
    """Against a program without the counters or the kernel (the parent of
    this model's PR cannot run the cell at all), a run with no trace, and a
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
