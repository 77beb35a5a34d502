"""The serving driver: `ServingEngine.submit` / `step` under the benchmark's
own closed loop (clients that wait for each reply)."""

from __future__ import annotations

import gc
import time

import numpy as np

import harness
import stats
import traffic as traffic_gen
import weights
from drivers.train import model_config


def build(cell, seed: int):
    import jax.numpy as jnp

    from accelerate_tpu.models.gpt2 import GPT2LMHead
    from accelerate_tpu.serving import ServingEngine

    module = GPT2LMHead(model_config(cell))
    params = weights.make_program(seed, cell.config, jnp.dtype(cell.config["param_dtype"]))
    args = dict(cell.spec["engine"])
    args["prompt_buckets"] = tuple(args["prompt_buckets"])
    return ServingEngine(module, params, **args)


def submit(engine, item: dict, temperature: float):
    from accelerate_tpu.serving import Request, SamplingParams

    request = Request(prompt=list(item["prompt"]),
                      params=SamplingParams(temperature=temperature,
                                            max_new_tokens=item["new_tokens"]))
    result = engine.submit(request)
    if not result.accepted:
        raise RuntimeError(f"request refused: {result.reason} {result.detail}")
    return result.request_id


def warm_up(engine, cell, vocab: int) -> None:
    """One burst that meets every admit program (prompt bucket x admit-batch
    size) and the decode program, so that the window compiles nothing."""
    buckets = sorted(cell.spec["engine"]["prompt_buckets"])
    for size in cell.spec["warmup_admit_sizes"]:
        for bucket in buckets:
            for _ in range(size):
                submit(engine, {"prompt": [1 % vocab] * bucket, "new_tokens": 3}, 0.0)
    while engine.has_work:
        engine.step()


class Loop:
    """`clients` callers around the engine: each sends the pool's next
    request the moment its last one finishes."""

    def __init__(self, engine, ramp, pool, mix):
        self.engine, self.ramp, self.pool = engine, ramp, pool
        self.next = 0
        self.sent: dict[int, dict] = {}
        self.done: list[tuple[dict, object]] = []
        self.temperature = float(mix.get("temperature", 0.0))

    def _send(self, item=None):
        if item is None:
            item = self.pool[self.next % len(self.pool)]
            self.next += 1
        self.sent[submit(self.engine, item, self.temperature)] = item

    def start(self):
        for item in self.ramp:
            self._send(item)

    def turn(self) -> int:
        """One engine step, and a new request for each reply."""
        finished = self.engine.step()
        for out in finished:
            self.done.append((self.sent.pop(out.request_id), out))
            self._send()
        return len(finished)


def delivered_inside(outs, start: float, stop: float) -> int:
    """Output tokens delivered inside [start, stop]. The engine hands over a
    request when it ends, with the times of its first and last token; in
    between, every decode step brings each running request one token, so the
    others are placed evenly. A request cut short at `stop` ends there."""
    count = 0
    for out in outs:
        n, first = len(out.tokens), out.first_token_time
        if n == 0 or first is None:
            continue
        last = min(out.finish_time if out.finish_time is not None else stop, stop)
        gap = (last - first) / (n - 1) if n > 1 and last > first else 0.0
        count += sum(1 for k in range(n) if start <= first + k * gap <= stop)
    return count


def logit_gaps(cell, seed: int, sample, quant=None) -> np.ndarray:
    """Run the plain reference once over each sampled prompt with its served
    tokens, in blocks of rows; for every served token, how far its logit lies
    below the reference's best at that position. With `quant`, the control's
    reading: the gap of the token the lower precision puts first."""
    import jax
    import jax.numpy as jnp

    from reference import gpt2 as ref

    params = weights.make_stacked(seed, cell.config, jnp.float32)
    n_head, mix = cell.config["n_head"], cell.traffic
    # one shape for every block of every run: the mix's longest prompt and
    # answer, so that the reference compiles once
    longest = int(mix["new_tokens"]["max"])
    width = -(-(int(mix["prompt_len"]["max"]) + longest) // 128) * 128
    rows = 8  # a block's logits at the served positions are [8, 256, V] in float32: 0.4 GB

    @jax.jit
    def block_gaps(params, ids, positions, served):
        logits = ref.logits_at(params, ids, positions, n_head)
        picked = served if quant is None else jnp.argmax(
            ref.logits_at(params, ids, positions, n_head, quant=quant), -1)
        return logits.max(-1) - jnp.take_along_axis(logits, picked[..., None], -1)[..., 0]

    gaps = []
    for at in range(0, len(sample), rows):
        block = sample[at: at + rows]
        ids = np.zeros((rows, width), np.int32)
        positions, served = np.zeros((rows, longest), np.int32), np.zeros((rows, longest), np.int32)
        for row, (item, out) in enumerate(block):
            p, t = len(item["prompt"]), len(out.tokens)
            ids[row, : p + t] = list(item["prompt"]) + list(out.tokens)
            positions[row, :t] = np.arange(p - 1, p - 1 + t)  # token k is read off position p - 1 + k
            served[row, :t] = out.tokens
        got = jax.device_get(block_gaps(params, ids, positions, served))
        gaps += [got[row, : len(out.tokens)] for row, (_, out) in enumerate(block)]
    return np.concatenate(gaps).astype(np.float64) if gaps else np.zeros(0)


def gap_numbers(gaps: np.ndarray) -> dict:
    """The numbers compared: the widest gap (one altered token shows here)
    and the mean of the squared gaps over all served tokens compared (a lower
    precision shows here: it flips more near-ties, and wider ones, and the
    square weighs the wider; over some ten thousand tokens it is steady from
    seed to seed, which the widest gap alone is not)."""
    if gaps.size == 0:
        return {"logit_gap_max": float("inf"), "logit_gap_sq_mean": float("inf")}
    return {"logit_gap_max": float(gaps.max()), "logit_gap_sq_mean": float((gaps ** 2).mean())}


def pick_sample(done, seed: int, k: int):
    """k finished requests drawn from the seed, the longest among them."""
    if not done:
        return []
    longest = max(range(len(done)), key=lambda i: len(done[i][0]["prompt"]) + len(done[i][1].tokens))
    others = [i for i in range(len(done)) if i != longest]
    rng = traffic_gen.rng_for(seed, 3)
    chosen = [longest] + list(rng.permutation(others)[: max(0, k - 1)])
    return [done[i] for i in chosen]


def phase_sums(metrics) -> dict:
    """`ServingMetrics.step_phase_*_s` sums and the step count, as read."""
    names = ("schedule", "draft", "dispatch", "fetch_blocked", "deliver", "journal", "telemetry")
    out = {n: float(getattr(metrics, f"step_phase_{n}_s").sum) for n in names}
    out["steps"] = int(metrics.step_total_s.count)
    return out


def drive(cell, seed: int, seconds: float, tracer=None, t0: float | None = None) -> dict:
    """Build the engine, warm it, start the callers at every age, then hold
    the window open for `seconds`. Returns what the window saw; the engine is
    still alive (the caller reads memory, then frees it)."""
    mix, vocab = cell.traffic, cell.config["vocab_size"]
    compiles = harness.CompileLog()
    t_build = time.perf_counter()
    engine = build(cell, seed)
    t_warm = time.perf_counter()
    warm_up(engine, cell, vocab)
    t_ramp = time.perf_counter()
    print(f"warm-up compiled {sorted(engine.metrics.compiles)}", flush=True)
    loop = Loop(engine, traffic_gen.aged_ramp(mix, seed, vocab),
                traffic_gen.request_pool(mix, seed, vocab), mix)
    loop.start()
    while len(loop.done) < int(mix.get("ramp_finished", 0)):
        loop.turn()
    ramp = len(loop.done)
    if t0 is not None:
        print(f"set-up: imports and device {t_build - t0:.1f}s, weights and engine "
              f"{t_warm - t_build:.1f}s, warm-up burst {t_ramp - t_warm:.1f}s, ramp "
              f"{time.perf_counter() - t_ramp:.1f}s", flush=True)
    trace_seconds = float(mix.get("trace_seconds", 3))
    compiles0, phases0 = engine.metrics.compile_count.value, phase_sums(engine.metrics)
    traced = None

    start = time.perf_counter()
    turns = 0
    while (now := time.perf_counter()) - start < seconds:
        if (tracer is not None and traced is None and not tracer.on
                and now - start >= min(1.0, seconds / 4)):
            tracer.start()
            mark = (len(loop.done), phase_sums(engine.metrics))
        loop.turn()
        turns += 1
        if tracer is not None and tracer.on and time.perf_counter() - tracer.t_start >= trace_seconds:
            tracer.stop()
            traced = {"done": loop.done[mark[0]:], "phases0": mark[1],
                      "phases1": phase_sums(engine.metrics),
                      "seconds": tracer.t_stop - tracer.t_start}
    if tracer is not None:
        tracer.stop()
    stop = time.perf_counter()
    print(compiles.inside(start, stop), flush=True)
    return {"engine": engine, "loop": loop, "done": loop.done[ramp:], "elapsed": stop - start,
            "turns": turns, "start": start, "stop": stop,
            "setup_s": None if t0 is None else start - t0, "traced": traced,
            "compiles": engine.metrics.compile_count.value - compiles0,
            "phases0": phases0, "phases1": phase_sums(engine.metrics)}


def close(served: dict) -> list:
    """End the engine; the requests in flight at the close come back cut
    short, each beside the item it answers."""
    loop = served.pop("loop")
    return [(loop.sent[out.request_id], out) for out in served.pop("engine").abort_all()
            if out.request_id in loop.sent]


def tails(mix: dict, done, cut_short, start: float, stop: float) -> dict:
    """Times to the first token, in ms, of every request of the mix that got
    its first token inside the window, finished or not; and times per output
    token of every one that finished inside it, and of every one cut short at
    the close that had by then as many tokens as the mix's shortest answer:
    its time runs to the close, so a request that stalls shows, and no time
    per token is a mean over fewer turns than a finished request's."""
    least = max(2, int(mix["new_tokens"]["min"]))
    ttft, tpot = [], []
    for finished, pairs in ((True, done), (False, cut_short)):
        for item, out in pairs:
            if item.get("ramp") or out.first_token_time is None:
                continue
            if start <= out.first_token_time <= stop:
                ttft.append(1e3 * (out.first_token_time - out.arrival_time))
            n = len(out.tokens)
            if finished and n > 1:
                tpot.append(1e3 * (out.finish_time - out.first_token_time) / (n - 1))
            elif not finished and n >= least:
                tpot.append(1e3 * (stop - out.first_token_time) / (n - 1))
    return {"ttft_ms": ttft, "tpot_ms": tpot}


def run(cell, device, *, seed, seconds, trace, t0):
    from accelerate_tpu.serving import FINISH_LENGTH

    mix = cell.traffic
    tracer = harness.Trace(cell) if trace else None
    served = drive(cell, seed, seconds, tracer, t0)
    done, elapsed, traced = served["done"], served["elapsed"], served["traced"]
    peak = harness.memory_peak_bytes()
    cut_short = close(served)  # partial answers of the requests in flight

    failed = sum(1 for item, out in done
                 if out.finish_reason != FINISH_LENGTH or len(out.tokens) != item["new_tokens"])
    tokens = delivered_inside([out for _, out in done + cut_short], served["start"], served["stop"])
    tail = tails(mix, done, cut_short, served["start"], served["stop"])
    ttft, tpot = tail["ttft_ms"], tail["tpot_ms"]
    print(f"window {elapsed:.3f}s engine steps {served['turns']} requests finished {len(done)} "
          f"with {sum(len(out.tokens) for _, out in done)} tokens, delivered inside {tokens}, "
          f"compiles in window {served['compiles']} in flight at close {len(cut_short)} "
          f"peak bytes {peak}", flush=True)
    if len(tpot) >= 2 and len(ttft) >= 2:
        print(f"ttft ms of {len(ttft)}: p50 {stats.percentile(ttft, 50):.2f} p95 "
              f"{stats.percentile(ttft, 95):.2f} max {max(ttft):.2f}; tpot ms of {len(tpot)}: p50 "
              f"{stats.percentile(tpot, 50):.3f} p95 {stats.percentile(tpot, 95):.3f} "
              f"max {max(tpot):.3f}", flush=True)

    # ---- correct: served tokens against the reference's logits
    sample = pick_sample(done, seed, int(mix.get("check_requests", 4)))
    gc.collect()
    t_ref = time.perf_counter()
    gaps = logit_gaps(cell, seed, sample) if sample else np.zeros(0)
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
                                         "phases0": served["phases0"],
                                         "phases1": served["phases1"]}})
    harness.finish(cell, device, trace=trace, correct=ok, attempted=len(done), failed=failed,
                   end_to_end=end_to_end, per_layer=per_layer, compared=compared, peak=peak,
                   trace_out=trace_out)
