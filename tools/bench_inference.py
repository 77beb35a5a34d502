"""Big-model inference benchmark — the reference's headline table, TPU-native.

The reference's only published performance numbers are big-model-inference
load-time + s/token rows (BASELINE.md: GPT-J-6B fp16 loads in 8.7 s and
generates at 0.05 s/token on 2x Titan RTX). This reproduces that flow on one
TPU chip: a sharded fp16 safetensors checkpoint on disk -> device (load phase),
then autoregressive decode with KV cache (generate phase).

Prints ONE JSON line:
  {"metric": "big_model_inference", "detail": {"load_s": ..., "s_per_token":
   ..., "params_b": ..., ...}}

Env:
  BENCH_INF_PRESET   llama2_7b (default on TPU) | tiny (CPU smoke)
  BENCH_INF_TOKENS   new tokens to generate (default 20)
  BENCH_INF_CKPT     checkpoint dir (default /tmp/bench_inference_<preset>;
                     created on first run, reused after)
  BENCH_INF_QUANT    nf4 | fp4 | int8: weight-only quantized decode (the
                     reference's bnb rows) — packed payload in HBM, dequant
                     fused into the matmuls via QuantizedModule
  BENCH_INF_KV       int8: blockwise-quantized KV cache (halves cache HBM;
                     beyond the reference) — composes with BENCH_INF_QUANT

The checkpoint is synthetic (zeros): load-time and s/token depend on bytes
and shapes, not values, and zeros keep corpus creation fast. The reference's
table measures real weights, so treat load_s as the IO+device-transfer floor.
"""

from __future__ import annotations

import json
import os
import time


def main() -> None:
    import jax
    import jax.numpy as jnp
    import numpy as np

    from accelerate_tpu.utils.environment import configure_compile_cache, on_tpu_platform

    on_tpu = on_tpu_platform()
    configure_compile_cache()
    preset = os.environ.get("BENCH_INF_PRESET", "llama2_7b" if on_tpu else "tiny")
    tokens = int(os.environ.get("BENCH_INF_TOKENS", "20"))

    from accelerate_tpu.models.llama import LlamaConfig, LlamaForCausalLM
    from accelerate_tpu.utils.safetensors_io import (
        load_safetensors_checkpoint,
        save_safetensors_checkpoint,
    )

    kv = os.environ.get("BENCH_INF_KV", "")
    if kv not in ("", "int8"):
        raise SystemExit(f"BENCH_INF_KV must be int8 or unset, got {kv!r}")
    kv_kw = {"kv_cache_dtype": jnp.int8} if kv == "int8" else {}
    if preset == "llama2_7b":
        # max positions capped so the KV cache fits one 16 GB chip beside the
        # 13.5 GB of bf16 weights
        cfg = LlamaConfig.llama2_7b(
            dtype=jnp.bfloat16, param_dtype=jnp.bfloat16, max_position_embeddings=512,
            **kv_kw,
        )
    elif preset == "tiny":
        cfg = LlamaConfig(
            vocab_size=512, hidden_size=64, intermediate_size=128, num_layers=2,
            num_heads=4, num_kv_heads=4, max_position_embeddings=128,
            dtype=jnp.float32, param_dtype=jnp.float32, **kv_kw,
        )
    else:
        raise SystemExit(f"unknown BENCH_INF_PRESET {preset!r}")

    module = LlamaForCausalLM(cfg)
    shapes = jax.eval_shape(
        lambda: module.init(jax.random.key(0), jnp.zeros((1, 8), jnp.int32))
    )["params"]

    ckpt = os.environ.get("BENCH_INF_CKPT", f"/tmp/bench_inference_{preset}")
    if not os.path.exists(os.path.join(ckpt, "model.safetensors.index.json")) and not any(
        f.endswith(".safetensors") for f in (os.listdir(ckpt) if os.path.isdir(ckpt) else [])
    ):
        os.makedirs(ckpt, exist_ok=True)
        host = jax.tree.map(lambda s: np.zeros(s.shape, np.float16), shapes)
        save_safetensors_checkpoint(host, ckpt, max_shard_size="5GB")
        del host

    n_params = sum(int(np.prod(s.shape)) for s in jax.tree.leaves(shapes))

    quant = os.environ.get("BENCH_INF_QUANT", "")

    # ---- load phase: disk -> host -> (quantize) -> device
    t0 = time.perf_counter()
    host_params = load_safetensors_checkpoint(ckpt, nested=True)
    if quant:
        from accelerate_tpu.utils.quantization import (
            QuantizationConfig,
            QuantizedModule,
            quantize_params,
            quantized_nbytes,
        )

        qcfg = QuantizationConfig(
            load_in_4bit=quant in ("nf4", "fp4"),
            load_in_8bit=quant == "int8",
            quant_type=quant if quant in ("nf4", "fp4") else "nf4",
            compute_dtype=cfg.dtype,
        )
        # quantize ON DEVICE: each fp16 leaf streams to HBM one at a time and
        # the fused jit pass (absmax/normalize/codebook/pack, source donated)
        # replaces a minutes-long single-host-core numpy quantize of ~13.5 GB.
        # Leaf-at-a-time keeps peak HBM at packed-payload + one leaf, so
        # models whose fp16 exceeds the chip still load.
        params = quantize_params(host_params, qcfg, on_device=True)
        module = QuantizedModule(module)
    else:
        # transfer the checkpoint's fp16 bytes as-is and cast ON DEVICE: the
        # host-side ml_dtypes fp16->bf16 conversion is single-threaded and
        # would serialize ~params_b GB through one core; donation lets XLA
        # alias the same-byte-width buffers so peak HBM stays ~one copy
        params = jax.tree.map(jax.device_put, host_params)
        cast = jax.jit(
            lambda t: jax.tree.map(lambda x: x.astype(cfg.param_dtype), t),
            donate_argnums=0,
        )
        params = cast(params)
    jax.block_until_ready(params)
    load_s = time.perf_counter() - t0
    del host_params

    # ---- generate phase
    from accelerate_tpu.models.generation import generate

    prompt = jnp.ones((1, 64 if preset != "tiny" else 8), jnp.int32)
    out = generate(module, params, prompt, max_new_tokens=tokens)  # compile + run
    jax.block_until_ready(out)
    t0 = time.perf_counter()
    out = generate(module, params, prompt, max_new_tokens=tokens)
    jax.block_until_ready(out)
    gen_s = time.perf_counter() - t0
    s_per_token = gen_s / tokens

    print(json.dumps({
        "metric": "big_model_inference",
        "value": round(s_per_token, 5),
        "unit": "s/token",
        "detail": {
            "preset": preset,
            "quant": quant or "fp16",
            "kv_cache": kv or "full",
            **(
                {"packed_gb": round(quantized_nbytes(params) / 1e9, 3)}
                if quant
                else {}
            ),
            "params_b": round(n_params / 1e9, 3),
            "load_s": round(load_s, 4),
            "s_per_token": round(s_per_token, 5),
            "new_tokens": tokens,
            "platform": jax.devices()[0].platform,
            "reference_row": "GPT-J-6B fp16: 8.7 s load, 0.05 s/token "
                             "(BASELINE.md, 2x Titan RTX)",
        },
    }), flush=True)


if __name__ == "__main__":
    main()
