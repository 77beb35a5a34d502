"""The training driver: `Accelerator.prepare` + `make_train_step`, fed through
the prepared `DataLoaderShard`, timed over a window of whole steps."""

from __future__ import annotations

import gc
import time

import numpy as np

import harness
import traffic as traffic_gen
import weights


def model_config(cell):
    import jax.numpy as jnp

    from accelerate_tpu.models.gpt2 import GPT2Config

    c = cell.config
    kv = cell.spec.get("kv_cache_dtype")  # serving cells: "int8" stores the paged pool quantized
    return GPT2Config(
        vocab_size=c["vocab_size"], n_positions=c["n_positions"], n_embd=c["n_embd"],
        n_layer=c["n_layer"], n_head=c["n_head"], layer_norm_epsilon=c["layer_norm_epsilon"],
        dtype=jnp.dtype(c["compute_dtype"]), param_dtype=jnp.dtype(c["param_dtype"]),
        attention_impl=cell.spec.get("attention_impl", "auto"),
        kv_cache_dtype=jnp.dtype(kv) if kv else None)


def build(cell, seed: int):
    """The one object the window drives: the compiled step with its state,
    behind the loader that feeds it."""
    import jax
    import jax.numpy as jnp
    import optax

    from accelerate_tpu.accelerator import Accelerator
    from accelerate_tpu.data_loader import DataLoaderShard
    from accelerate_tpu.models.gpt2 import GPT2LMHead, gpt2_sharding_rules, lm_loss_fn
    from accelerate_tpu.parallel.mesh import ParallelismConfig

    par = {k: v for k, v in cell.spec.get("parallelism", {}).items() if v and v > 1}
    rules = {"gpt2": gpt2_sharding_rules, None: lambda: None}[cell.spec.get("sharding_rules")]()
    acc = Accelerator(mixed_precision=cell.spec["mixed_precision"],
                      parallelism_config=ParallelismConfig(**par) if par else None,
                      sharding_rules=rules)
    module = GPT2LMHead(model_config(cell))
    params = weights.make_program(seed, cell.config, jnp.dtype(cell.config["param_dtype"]))
    hp = dict(cell.spec["optimizer"])
    if hp.pop("name") != "adamw":
        raise ValueError("the reference implements adamw only")
    global_batch = int(cell.traffic["batch_per_chip"]) * len(jax.devices())
    batches = traffic_gen.token_batches(cell.traffic, seed, cell.config["vocab_size"], global_batch)
    model, optimizer, loader = acc.prepare(
        (module, params), optax.adamw(**hp), DataLoaderShard([{"input_ids": b} for b in batches]))
    del params
    step = acc.make_train_step(lm_loss_fn)
    return {"acc": acc, "model": model, "optimizer": optimizer, "loader": loader, "step": step,
            "batches": batches, "hp": hp, "global_batch": global_batch}


def feed(loader):
    """The prepared loader, iterated again and again."""
    while True:
        yield from loader


def adam_mu(opt_state):
    import jax

    found = [s for s in jax.tree.leaves(opt_state, is_leaf=lambda x: hasattr(x, "mu"))
             if hasattr(s, "mu")]
    return found[0].mu


def program_readings(built, cell, seed: int, batches_iter, n_steps: int) -> dict:
    """Drive the first steps through the window's own call and feed; read
    each loss, the first gradient's norms out of Adam's first moment after
    one step, and the norms of the parameters' change after the last."""
    import jax
    import jax.numpy as jnp

    from reference import gpt2 as ref

    b1 = built["hp"]["b1"]
    losses, grad_norms = [], None
    for i in range(n_steps):
        losses.append(float(built["step"](next(batches_iter))))
        if i == 0:
            grad_norms = jax.device_get(jax.jit(
                lambda mu: ref.leaf_norms(weights.from_program(mu)) / (1.0 - b1)
            )(adam_mu(built["optimizer"].opt_state)))
    start = weights.make_stacked(seed, cell.config, jnp.dtype(cell.config["param_dtype"]))
    delta = jax.device_get(jax.jit(
        lambda p, s: ref.leaf_norms(jax.tree.map(jnp.subtract, weights.from_program(p), s))
    )(built["model"].params, start))
    del start
    return {"losses": losses, "grad_norms": grad_norms, "delta_norms": delta}


def reference_readings(cell, seed: int, batches, hp, quant=None, rows=None) -> dict:
    """The plain reference over the same first steps. `rows` keeps only those
    rows of every batch (the planted half-batch fault)."""
    import jax.numpy as jnp

    from reference import gpt2 as ref

    n = int(cell.traffic["reference_steps"])
    params = weights.make_stacked(seed, cell.config, jnp.float32)
    used = [b if rows is None else b[rows] for b in batches[:n]]
    return ref.train(params, used, cell.config["n_head"], hp, quant=quant, rows_per_block=1)


def gaps(got: dict, want: dict) -> dict:
    """The numbers compared: the worst step's loss gap as a share of the
    reference's loss, and by the worst leaf the gap between the program's norm
    and the reference's, against the reference's norm of that leaf or of the
    median leaf, whichever is larger. Leaves whose reference gradient is under
    a thousandth of the median leaf's move by round-off alone under Adam and
    are left out of the change."""
    lg = max(abs(a - b) / abs(b) for a, b in zip(got["losses"], want["losses"]))
    g_ref, d_ref = np.asarray(want["grad_norms"]), np.asarray(want["delta_norms"])
    g_gap = np.abs(np.asarray(got["grad_norms"]) - g_ref) / np.maximum(g_ref, np.median(g_ref))
    d_gap = np.abs(np.asarray(got["delta_norms"]) - d_ref) / np.maximum(d_ref, np.median(d_ref))
    moved = g_ref >= 1e-3 * np.median(g_ref)
    return {"loss_gap": float(lg), "grad_norm_gap": float(g_gap.max()),
            "delta_norm_gap": float(d_gap[moved].max()),
            "worst_grad_leaf": int(g_gap.argmax()), "worst_delta_leaf": int(np.where(moved, d_gap, -1).argmax())}


def run(cell, device, *, seed, seconds, trace, t0):
    import jax

    compiles = harness.CompileLog()
    t_build = time.perf_counter()
    built = build(cell, seed)
    jax.block_until_ready(built["model"].params)
    batches_iter = feed(built["loader"])
    n_ref = int(cell.traffic["reference_steps"])
    t_steps = time.perf_counter()
    got = program_readings(built, cell, seed, batches_iter, n_ref)
    print(f"set-up: imports and device {t_build - t0:.1f}s, weights and prepare "
          f"{t_steps - t_build:.1f}s, first {n_ref} steps and their readings "
          f"{time.perf_counter() - t_steps:.1f}s", flush=True)
    print(f"first losses {got['losses']}", flush=True)
    step, depth = built["step"], int(cell.spec.get("in_flight_steps", 2))
    tokens_per_step = built["global_batch"] * int(cell.traffic["seq"])
    tracer = harness.Trace(cell)
    trace_steps = int(cell.traffic.get("trace_steps", 10))
    traced_tokens = traced_s = None

    # ---- the window: whole steps, at most `depth` in flight, ends blocked
    pending, losses, steps = [], [], 0
    start = time.perf_counter()
    setup_s = start - t0
    while time.perf_counter() - start < seconds:
        if trace and steps == depth + 1 and not tracer.on and traced_s is None:
            jax.block_until_ready(pending)
            tracer.start()
            mark = steps
        pending.append(step(next(batches_iter)))
        steps += 1
        if len(pending) > depth:
            losses.append(pending.pop(0).block_until_ready())
        if tracer.on and steps - mark == trace_steps:
            jax.block_until_ready(pending)
            tracer.stop()
            traced_tokens = trace_steps * tokens_per_step
            traced_s = tracer.t_stop - tracer.t_start
    losses += [p.block_until_ready() for p in pending]
    tracer.stop()
    elapsed = time.perf_counter() - start
    losses = [float(x) for x in losses]
    failed = sum(1 for x in losses if not np.isfinite(x))
    peak = harness.memory_peak_bytes()
    rate = steps * tokens_per_step / elapsed / max(1, len(jax.devices()))
    print(f"window {elapsed:.3f}s steps {steps} tokens/step {tokens_per_step} "
          f"last loss {losses[-1]:.4f} peak bytes {peak}; {compiles.inside(start, start + elapsed)}",
          flush=True)

    # ---- correct: the program's first steps against the reference's
    hp, batches = built["hp"], built["batches"]
    built.clear()
    del step, batches_iter
    gc.collect()
    t_ref = time.perf_counter()
    want = reference_readings(cell, seed, batches, hp)
    numbers = gaps(got, want)
    print(f"reference took {time.perf_counter() - t_ref:.1f}s", flush=True)
    print(f"reference losses {want['losses']} worst leaves grad {numbers['worst_grad_leaf']} "
          f"delta {numbers['worst_delta_leaf']}", flush=True)
    limits = cell.spec["limits"]
    ok, compared = harness.judge({k: (numbers[k], limits.get(k)) for k in
                                  ("loss_gap", "grad_norm_gap", "delta_norm_gap")})
    ok = ok and failed == 0

    trace_out, per_layer = None, {}
    if trace and traced_s is not None:
        trace_out = tracer.reduce()
        per_layer = harness.read_layer_metrics(cell, {
            "cell": cell, "trace": trace_out, "peaks_kind": device["kind"],
            "tokens_per_s": traced_tokens / traced_s, "chips": max(1, len(jax.devices())),
            "steps": trace_steps,
            "global_batch": int(cell.traffic["batch_per_chip"]) * len(jax.devices())})
    harness.finish(cell, device, trace=trace, correct=ok, attempted=steps, failed=failed,
                   end_to_end={"train_tokens_per_s_per_chip": rate, "setup_s": setup_s},
                   per_layer=per_layer, compared=compared, peak=peak, trace_out=trace_out)
