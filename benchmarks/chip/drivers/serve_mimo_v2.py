"""The serving driver for MiMo-V2: K-EXAONE's (`drivers/serve_k_exaone.py`)
with this model's `build` and its reference walk. Imported, not copied: the
closed loop, its tails, sample and warm-up (`drivers/serve.py`), the callers'
start and draws (`serve_k_exaone.even_ramp`, `balanced_pool`, one order of
lengths for every seed), the sample's arrays (`serve_qwen3_next`) and the
counters' reading (`serve_kimi_k2`). Copied, because K-EXAONE's bind its own
`build` and reference by module name: `drive` and `run`, line for line but
for the names.

The traffic pairs prompts and answers of 1-8k independently: 8k-in/1k-out
requests beside 1k-in/8k-out ones in one queue. `even_ramp` starts the
callers at their bare prompts, so a window of a minute holds the start of
that mix: contexts to the largest bucket plus the steps of ramp and window,
and finished answers far shorter than the mix's. The program this module
imports at its top is the one the cell measures, so a tree without it fails
here, at once, with no result line."""

from __future__ import annotations

import gc
import time

import numpy as np

import harness
import stats
import weights_mimo_v2 as weights
from accelerate_tpu.models import mimo_v2  # noqa: F401  (a tree without the model fails at this line)
from drivers.serve import (  # noqa: F401  (close, pick_sample: calibrate_mimo_v2.py reads them here)
    Loop,
    close,
    delivered_inside,
    gap_numbers,
    phase_sums,
    pick_sample,
    tails,
    warm_up,
)
from drivers.serve_k_exaone import balanced_pool, even_ramp
from drivers.serve_kimi_k2 import counter_sums
from drivers.serve_qwen3_next import sample_arrays

ROWS = 1  # reference rows a block of attention: 16,384 queries of 64 heads x 192 in float32 are 0.8 GB
CHUNK = 4096  # tokens a call of the reference's looped-over experts
CONTROLS = ("int8", "no_window", "no_sinks")


def build(cell, seed: int):
    import jax.numpy as jnp

    from accelerate_tpu.serving import PagedKVConfig, ServingEngine

    module = mimo_v2.MiMoV2ForCausalLM(weights.model_config(cell.config))
    params = weights.make_program(seed, cell.config, jnp.dtype(cell.config["param_dtype"]))
    args = dict(cell.spec["engine"])
    args["prompt_buckets"] = tuple(args["prompt_buckets"])
    if isinstance(args["paged_kv"], dict):  # the full layers' pool sized to what the weights and rings leave
        args["paged_kv"] = PagedKVConfig(**args["paged_kv"])
    return ServingEngine(module, params, **args)


def drive(cell, seed: int, seconds: float, tracer=None, t0: float | None = None) -> dict:
    """`drivers/serve.py`'s `drive` with this model's engine and the counters
    read at the window's and the traced slice's edges."""
    mix, vocab = cell.traffic, cell.config["vocab_size"]
    compiles = harness.CompileLog()
    t_build = time.perf_counter()
    engine = build(cell, seed)
    t_warm = time.perf_counter()
    warm_up(engine, cell, vocab)
    t_ramp = time.perf_counter()
    print(f"warm-up compiled {sorted(engine.metrics.compiles)}", flush=True)
    print(f"engine memory {({k: v for k, v in engine.memory_stats().items() if 'bytes' in k})}", flush=True)
    loop = Loop(engine, even_ramp(mix, seed, vocab), balanced_pool(mix, seed, vocab), mix)
    loop.start()
    while len(loop.done) < int(mix.get("ramp_finished", 0)):
        loop.turn()
    ramp = len(loop.done)
    if t0 is not None:
        print(f"set-up: imports and device {t_build - t0:.1f}s, weights and engine "
              f"{t_warm - t_build:.1f}s, warm-up burst {t_ramp - t_warm:.1f}s, ramp "
              f"{time.perf_counter() - t_ramp:.1f}s", flush=True)
    trace_seconds = float(mix.get("trace_seconds", 3))

    def edge():
        return {"phases": phase_sums(engine.metrics), "counters": counter_sums(engine.metrics)}

    compiles0, edge0 = engine.metrics.compile_count.value, edge()
    traced = None
    start = time.perf_counter()
    turns = 0
    while (now := time.perf_counter()) - start < seconds:
        if (tracer is not None and traced is None and not tracer.on
                and now - start >= min(1.0, seconds / 4)):
            tracer.start()
            mark = (len(loop.done), edge())
        loop.turn()
        turns += 1
        if tracer is not None and tracer.on and time.perf_counter() - tracer.t_start >= trace_seconds:
            tracer.stop()
            at = edge()
            traced = {"done": loop.done[mark[0]:], "phases0": mark[1]["phases"], "phases1": at["phases"],
                      "counters0": mark[1]["counters"], "counters1": at["counters"],
                      "seconds": tracer.t_stop - tracer.t_start}
    if tracer is not None:
        tracer.stop()
    stop = time.perf_counter()
    edge1 = edge()
    print(compiles.inside(start, stop), flush=True)
    return {"engine": engine, "loop": loop, "done": loop.done[ramp:], "elapsed": stop - start,
            "turns": turns, "start": start, "stop": stop,
            "setup_s": None if t0 is None else start - t0, "traced": traced,
            "compiles": engine.metrics.compile_count.value - compiles0,
            "phases0": edge0["phases"], "phases1": edge1["phases"],
            "counters0": edge0["counters"], "counters1": edge1["counters"]}


# ------------------------------------------------------------ the reference
def final_hidden(cell, seed: int, ids: np.ndarray, real: np.ndarray, low=None):
    """The reference's hidden states after the last layer, float32 on the
    device, [R, width, H]: layer by layer from the seed (the identical values
    the program holds, upcast). Attention runs ROWS rows at a time over the
    padded width (pads come after a row's tokens, and nothing looks ahead);
    the FFNs, which work token by token, run over the `real` positions alone
    (flat indices into [R * width]), CHUNK at a time: a looped-over expert
    meets every token it is handed."""
    import jax
    import jax.numpy as jnp

    from reference import mimo_v2 as ref

    cfg, held = cell.config, weights.held_experts(cell.config)
    dtype = jnp.dtype(cfg["param_dtype"])
    x = ref.embed(weights.upcast(weights.make_top(seed, cfg, dtype)), jnp.asarray(ids))
    mix = {sliding: jax.jit(lambda p, x, sliding=sliding: ref.mix(p, x, cfg, sliding, low=low))
           for sliding in (True, False)}
    ffn = {dense: jax.jit(lambda p, h, dense=dense: ref.ffn(p, h, cfg, dense, held=held, low=low))
           for dense in (True, False)}
    padded = np.concatenate([real, np.full((-len(real)) % CHUNK, real[-1], real.dtype)])
    for i in range(int(cfg["num_hidden_layers"])):
        p = weights.upcast(weights.make_layer(seed, cfg, i, dtype))
        h = jnp.concatenate([mix[ref.is_sliding(i, cfg)](p, x[at: at + ROWS])
                             for at in range(0, len(ids), ROWS)])
        flat = h.reshape(-1, h.shape[-1])
        added = jnp.concatenate([ffn[ref.is_dense(i, cfg)](p, flat[padded[at: at + CHUNK]])
                                 for at in range(0, len(padded), CHUNK)])
        x = flat.at[real].add(added[: len(real)]).reshape(h.shape)
        jax.block_until_ready(x)
        del p, h, flat, added
    return x


def logit_gaps(cell, seed: int, sample, low=None) -> np.ndarray:
    """For every served token of the sample, how far its logit lies below the
    reference's best at that position. With `low` (one of CONTROLS), the
    control's reading: the gap of the token that the reference altered that
    way puts first."""
    return gaps_by_control(cell, seed, sample, (low,))[low]


def gaps_by_control(cell, seed: int, sample, lows=(None,)) -> dict:
    """{low: `logit_gaps`} for each of `lows`, the float32 pass made once."""
    import jax
    import jax.numpy as jnp

    from reference import mimo_v2 as ref

    if not sample:
        return {low: np.zeros(0) for low in lows}
    cfg = cell.config
    ids, positions, served = sample_arrays(cell, sample)
    lengths = [len(item["prompt"]) + len(out.tokens) for item, out in sample]
    real = np.concatenate([row * ids.shape[1] + np.arange(n) for row, n in enumerate(lengths)])
    hidden = final_hidden(cell, seed, ids, real)
    top = weights.upcast(weights.make_top(seed, cfg, jnp.dtype(cfg["param_dtype"])))

    def block_gaps(top, x, positions, served, x_low, low):
        logits = ref.head_logits(top, x, positions, cfg)
        picked = served if x_low is None else jnp.argmax(ref.head_logits(top, x_low, positions, cfg, low), -1)
        return logits.max(-1) - jnp.take_along_axis(logits, picked[..., None], -1)[..., 0]

    block_gaps = jax.jit(block_gaps, static_argnames="low")
    out = {}
    for low in lows:
        picks = final_hidden(cell, seed, ids, real, low) if low is not None else None
        gaps = []
        for row, (_, reply) in enumerate(sample):
            rows = slice(row, row + 1)
            got = jax.device_get(block_gaps(top, hidden[rows], jnp.asarray(positions[rows]),
                                            jnp.asarray(served[rows]),
                                            None if picks is None else picks[rows], low))
            gaps.append(got[0, : len(reply.tokens)])
        out[low] = np.concatenate(gaps).astype(np.float64)
        del picks
    return out


def run(cell, device, *, seed, seconds, trace, t0):
    from accelerate_tpu.serving import FINISH_LENGTH

    mix = cell.traffic
    tracer = harness.Trace(cell) if trace else None
    served = drive(cell, seed, seconds, tracer, t0)
    done, elapsed, traced = served["done"], served["elapsed"], served["traced"]
    peak = harness.memory_peak_bytes()
    cut_short = close(served)  # ends the engine; partial answers of the requests in flight

    failed = sum(1 for item, out in done
                 if out.finish_reason != FINISH_LENGTH or len(out.tokens) != item["new_tokens"])
    tokens = delivered_inside([out for _, out in done + cut_short], served["start"], served["stop"])
    tail = tails(mix, done, cut_short, served["start"], served["stop"])
    ttft, tpot = tail["ttft_ms"], tail["tpot_ms"]
    print(f"window {elapsed:.3f}s engine steps {served['turns']} requests finished {len(done)} "
          f"with {sum(len(out.tokens) for _, out in done)} tokens, delivered inside {tokens}, "
          f"compiles in window {served['compiles']} in flight at close {len(cut_short)} "
          f"peak bytes {peak} counters {served['counters0']} -> {served['counters1']}", flush=True)
    if len(tpot) >= 2 and len(ttft) >= 2:
        print(f"ttft ms of {len(ttft)}: p50 {stats.percentile(ttft, 50):.2f} p95 "
              f"{stats.percentile(ttft, 95):.2f} max {max(ttft):.2f}; tpot ms of {len(tpot)}: p50 "
              f"{stats.percentile(tpot, 50):.3f} p95 {stats.percentile(tpot, 95):.3f} "
              f"max {max(tpot):.3f}", flush=True)

    # ---- correct: served tokens against the reference's logits
    sample = pick_sample(done, seed, int(mix.get("check_requests", 4)))
    gc.collect()
    t_ref = time.perf_counter()
    gaps = logit_gaps(cell, seed, sample)
    print(f"reference took {time.perf_counter() - t_ref:.1f}s", flush=True)
    print(f"compared {gaps.size} served tokens of {len(sample)} requests, {int((gaps > 0).sum())} "
          f"below the reference's best, by {gaps.mean() if gaps.size else 0.0:.3e} on average", flush=True)
    limits = cell.spec["limits"]
    ok, compared = harness.judge({k: (v, limits.get(k)) for k, v in gap_numbers(gaps).items()})
    ok = ok and failed == 0 and len(done) > 0 and served["compiles"] == 0

    end_to_end = {"setup_s": served["setup_s"]}
    if tpot:
        end_to_end.update(serve_tokens_per_s=tokens / elapsed,
                          tpot_p95_ms=stats.percentile(tpot, 95))
    trace_out, per_layer = None, {}
    if trace and traced is not None:
        trace_out = tracer.reduce()
        per_layer = harness.read_layer_metrics(cell, {
            "cell": cell, "trace": trace_out, "peaks_kind": device["kind"], "chips": cell.chips,
            "traced": traced, "window": {"done": done, "seconds": elapsed, "ttft_ms": ttft,
                                         "phases0": served["phases0"], "phases1": served["phases1"],
                                         "counters0": served["counters0"],
                                         "counters1": served["counters1"]}})
    harness.finish(cell, device, trace=trace, correct=ok, attempted=len(done), failed=failed,
                   end_to_end=end_to_end, per_layer=per_layer, compared=compared, peak=peak,
                   trace_out=trace_out)
