"""Benchmark: GPT-2 training throughput on the chip.

Prints ONE JSON line: {"metric", "value", "unit", "vs_baseline", "detail"},
stamped with the device as JAX reports it (platform, device_kind, count).

It measures a TPU. Without one it exits non-zero and prints no result — a
number from the CPU backend or the Pallas interpreter is not a throughput.
``BENCH_FORCE_CPU=1`` is the explicit exception for CI: a tiny config on the
host CPU, labelled as such, with ``mfu`` and ``vs_baseline`` null.

The reference publishes no training-throughput numbers (BASELINE.md), so
vs_baseline is reported against the north-star MFU target of 40%:
vs_baseline = achieved_MFU / 0.40 (>1.0 beats the target).

Env knobs (all optional):
  BENCH_ITERS / BENCH_BATCH / BENCH_SEQ   timing-loop shape
  BENCH_MODEL       small | medium (default; BASELINE.md north star)
  BENCH_ATTN        flash | xla           attention implementation
  BENCH_SCAN=1      lax.scan over layers (faster compile, one compiled block)
  BENCH_REMAT       full | dots | dots_no_batch   remat policy (default off)
  BENCH_FUSED_CE    1: lax.scan chunked head+CE; 2: Pallas fused-CE kernel
                    (both avoid the full [b,s,V] logits tensor)
  BENCH_CE_CHUNK    fused-CE row-chunk size (default 1024)
  BENCH_FP8         model: fp8-storage block matmuls (ops/fp8 native backend);
                    opt: adamw_fp8 O2 optimizer states; all: both
  BENCH_MU_DTYPE    bfloat16: AdamW first moment in bf16
  BENCH_PREFETCH=1  feed batches through the native C++ staging ring
  BENCH_FORCE_CPU=1 run the tiny CPU rehearsal instead of failing without a chip
"""

from __future__ import annotations

import json
import os
import sys
import time

# Peak dense bf16 FLOP/s of one chip, keyed by `jax.devices()[0].device_kind`.
# A kind that is not here is an error, never a default.
PEAK_BF16_FLOPS = {
    # Google Cloud documentation, "TPU v5e": 197 TFLOP/s bf16 per chip
    "TPU v5 lite": 197e12,
}


def _env_int(name: str, default: int) -> int:
    return int(os.environ.get(name, default))


def main() -> None:
    force_cpu = os.environ.get("BENCH_FORCE_CPU", "0") == "1"
    if force_cpu:
        from accelerate_tpu.test_utils.platform import force_cpu_platform

        force_cpu_platform()

    import jax
    import jax.numpy as jnp
    import numpy as np
    import optax

    from accelerate_tpu.accelerator import Accelerator
    from accelerate_tpu.models.gpt2 import (
        GPT2Config,
        GPT2LMHead,
        lm_loss_fn,
        lm_loss_fn_fused,
        lm_loss_fn_pallas,
    )
    from accelerate_tpu.utils.environment import (
        configure_compile_cache,
        device_description,
        require_tpu,
    )

    if force_cpu:
        device = device_description()
        peak_flops = None
    else:
        device = require_tpu("bench.py", rehearse="BENCH_FORCE_CPU=1")
        if device["kind"] not in PEAK_BF16_FLOPS:
            sys.exit(f"bench.py: no peak FLOP/s on file for device_kind {device['kind']!r}; "
                     "add it to PEAK_BF16_FLOPS with its source")
        peak_flops = PEAK_BF16_FLOPS[device["kind"]]
    configure_compile_cache()

    attn = os.environ.get("BENCH_ATTN", "xla" if force_cpu else "flash")
    scan = os.environ.get("BENCH_SCAN", "0") == "1"
    remat = os.environ.get("BENCH_REMAT", "")
    fp8 = os.environ.get("BENCH_FP8", "")
    if fp8 == "1":  # boolean-style enable means the full feature
        fp8 = "all"
    if fp8 not in ("", "model", "opt", "all"):
        raise SystemExit(f"BENCH_FP8 must be model|opt|all, got {fp8!r}")
    fp8_model_kw = {}
    if fp8 in ("model", "all"):
        from accelerate_tpu.ops.fp8 import DelayedScalingRecipe

        fp8_model_kw = {"fp8_recipe": DelayedScalingRecipe(backend="native")}
    model_name = os.environ.get("BENCH_MODEL", "medium")
    batch = _env_int("BENCH_BATCH", 8)
    if force_cpu:
        cfg = GPT2Config.tiny(dtype=jnp.float32, scan_layers=scan, **fp8_model_kw)
        seq = _env_int("BENCH_SEQ", 64)
        iters = _env_int("BENCH_ITERS", 5)
    else:
        seq = _env_int("BENCH_SEQ", 1024)
        iters = _env_int("BENCH_ITERS", 30)
        cfg_cls = {"small": GPT2Config.small, "medium": GPT2Config.medium}[model_name]
        cfg = cfg_cls(
            dtype=jnp.bfloat16, attention_impl=attn, scan_layers=scan,
            remat=bool(remat), remat_policy=remat or None,
            # long-context rows need the learned position table to cover seq
            n_positions=max(1024, seq), **fp8_model_kw,
        )

    acc = Accelerator(mixed_precision="no" if force_cpu else "bf16")
    module = GPT2LMHead(cfg)
    params = module.init_params(jax.random.key(0), batch=batch, seq=seq)
    # BENCH_MU_DTYPE=bfloat16 halves the AdamW first-moment HBM traffic (optax
    # mu_dtype); second moment stays fp32
    mu_dtype = os.environ.get("BENCH_MU_DTYPE") or None
    if mu_dtype == "bf16":  # accept the common shorthand; optax needs the full name
        mu_dtype = "bfloat16"
    if fp8 in ("opt", "all"):
        from accelerate_tpu.ops.fp8 import adamw_fp8

        tx = adamw_fp8(1e-4, opt_level="O2")
    else:
        tx = optax.adamw(1e-4, mu_dtype=mu_dtype)
    model, opt = acc.prepare((module, params), tx)
    fused_ce = os.environ.get("BENCH_FUSED_CE", "0")
    if fused_ce == "1":
        import functools

        loss_fn = functools.partial(lm_loss_fn_fused, chunk=_env_int("BENCH_CE_CHUNK", 1024))
    elif fused_ce == "2":
        loss_fn = lm_loss_fn_pallas
    else:
        loss_fn = lm_loss_fn
    step = acc.make_train_step(loss_fn)

    ids = np.random.default_rng(0).integers(0, cfg.vocab_size, (batch, seq)).astype(np.int32)
    if os.environ.get("BENCH_PREFETCH", "0") == "1":
        from accelerate_tpu.data_loader import DataLoaderShard

        dl = DataLoaderShard([{"input_ids": ids}] * (iters + 2), prefetch="auto")
        batches = iter(dl)
        next_batch = lambda: next(batches)
    else:
        jbatch = {"input_ids": jnp.asarray(ids)}
        next_batch = lambda: jbatch

    # warm-up: the first call compiles
    step(next_batch()).block_until_ready()
    step(next_batch()).block_until_ready()

    t0 = time.perf_counter()
    for _ in range(iters):
        loss = step(next_batch())
    loss.block_until_ready()
    dt = time.perf_counter() - t0
    final_loss = float(loss)

    tokens_per_sec_chip = batch * seq * iters / dt / device["count"]

    mfu = None
    if peak_flops is not None:
        # MFU: ~6*N FLOPs/token (fwd+bwd) + attention term 12*s*e per token per layer
        n_params = sum(int(np.prod(p.shape)) for p in jax.tree.leaves(params))
        flops_per_token = 6 * n_params + cfg.n_layer * 12 * seq * cfg.n_embd
        mfu = tokens_per_sec_chip * flops_per_token / peak_flops

    print(json.dumps({
        "metric": "gpt2_train_tokens_per_sec_per_chip",
        "value": round(tokens_per_sec_chip, 1),
        "unit": "tokens/s/chip",
        "vs_baseline": None if mfu is None else round(mfu / 0.40, 4),
        "detail": {
            "mfu": None if mfu is None else round(mfu, 4),
            "peak_flops": peak_flops,
            "model": "gpt2-tiny(cpu)" if force_cpu else f"gpt2-{model_name}",
            "batch": batch,
            "seq": seq,
            "attn": attn,
            "scan": scan,
            "remat": remat or "off",
            "fused_ce": fused_ce,
            "fp8": fp8 or "off",
            "platform": device["platform"],
            "device_kind": device["kind"],
            "device_count": device["count"],
            "loss": round(final_loss, 4),
        },
    }), flush=True)


if __name__ == "__main__":
    main()
