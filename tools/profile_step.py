"""Capture a jax.profiler trace of the bench train step on the real chip and
print per-op self-time stats (parsed with tensorboard_plugin_profile, no TPU
UI needed). Exits non-zero when JAX finds no TPU.

Usage: python tools/profile_step.py [out_dir]
Env: same knobs as bench.py (BENCH_BATCH/BENCH_SEQ/BENCH_ATTN/BENCH_FUSED_CE/...).
"""

from __future__ import annotations

import glob
import gzip
import json
import os
import sys


def main() -> None:
    out = sys.argv[1] if len(sys.argv) > 1 else "/tmp/profile_step"
    os.makedirs(out, exist_ok=True)

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

    from accelerate_tpu.utils.environment import configure_compile_cache, require_tpu

    # a trace of the CPU backend or the Pallas interpreter profiles nothing
    # anyone deploys: no chip, no profile
    require_tpu("tools/profile_step.py")
    configure_compile_cache()
    attn = os.environ.get("BENCH_ATTN", "flash")
    scan = os.environ.get("BENCH_SCAN", "0") == "1"
    remat = os.environ.get("BENCH_REMAT", "")
    model_name = os.environ.get("BENCH_MODEL", "small")
    cfg_cls = getattr(GPT2Config, model_name, None)
    if cfg_cls is None:
        sys.exit(f"BENCH_MODEL={model_name!r}: no such GPT2Config preset "
                 "(try small/medium/large)")
    cfg = cfg_cls(
        dtype=jnp.bfloat16,
        attention_impl=attn, scan_layers=scan, remat=bool(remat), remat_policy=remat or None,
    )
    batch = int(os.environ.get("BENCH_BATCH", 8))
    seq = int(os.environ.get("BENCH_SEQ", 1024))

    acc = Accelerator(mixed_precision="bf16")
    module = GPT2LMHead(cfg)
    params = module.init_params(jax.random.key(0), batch=batch, seq=seq)
    model, opt = acc.prepare((module, params), optax.adamw(1e-4))
    fused_ce = os.environ.get("BENCH_FUSED_CE", "0")
    if fused_ce == "1":
        import functools

        loss = functools.partial(lm_loss_fn_fused, chunk=int(os.environ.get("BENCH_CE_CHUNK", 1024)))
    elif fused_ce == "2":
        loss = lm_loss_fn_pallas
    else:
        loss = lm_loss_fn
    step = acc.make_train_step(loss)
    ids = {"input_ids": jnp.asarray(
        np.random.default_rng(0).integers(0, cfg.vocab_size, (batch, seq)).astype(np.int32))}
    float(step(ids))  # compile
    float(step(ids))

    jax.profiler.start_trace(out)
    for _ in range(3):
        loss_val = step(ids)
    float(loss_val)
    jax.profiler.stop_trace()

    reports = summarize(out)
    print(json.dumps(reports, indent=2)[:8000])


def summarize(log_dir: str) -> dict:
    """Parse the xplane into framework-op self times via tensorboard_plugin_profile."""
    paths = glob.glob(os.path.join(log_dir, "**", "*.xplane.pb"), recursive=True)
    if not paths:
        return {"error": f"no xplane under {log_dir}"}
    try:
        from tensorboard_plugin_profile.convert import raw_to_tool_data
    except Exception as e:  # plugin/pywrap mismatch (seen on the CPU path):
        # the trace is still on disk for offline analysis
        return {"xplane": paths[-1], "parse_error": repr(e)}

    out: dict = {"xplane": paths[-1]}
    try:
        data, _ = raw_to_tool_data.xspace_to_tool_data([paths[-1]], "framework_op_stats^", {})
        if isinstance(data, bytes):
            try:
                data = gzip.decompress(data)
            except OSError:
                pass
            data = data.decode("utf-8", "replace")
        rows = json.loads(data)
        out["op_stats"] = _top_ops(rows)
    except Exception as e:  # tool name varies across plugin versions
        out["op_stats_error"] = repr(e)
    try:
        data, _ = raw_to_tool_data.xspace_to_tool_data([paths[-1]], "overview_page^", {})
        if isinstance(data, bytes):
            data = data.decode("utf-8", "replace")
        out["overview_raw_head"] = str(data)[:2000]
    except Exception as e:
        out["overview_error"] = repr(e)
    return out


def _top_ops(rows, n: int = 25):
    """Reduce the framework-op-stats table to the top-N self-time entries."""
    if isinstance(rows, dict):
        rows = rows.get("data", rows)
    return rows[:n] if isinstance(rows, list) else rows


if __name__ == "__main__":
    main()
