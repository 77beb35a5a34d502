"""NF4 dequant-matmul: Pallas kernel vs XLA-fused dequant, decode shapes.

Run on TPU only when the `BENCH_INF_QUANT=nf4` vs fp16 decode measurement
shows dequant dominating (docs/PERF_NOTES.md round-4 queue) — this decides
whether the kernel (`ops/nf4_matmul.py`) should replace the XLA path in the
quantized decode loop. Prints one JSON line per shape with both timings.

Env: BENCH_NF4_ITERS (default 50), BENCH_NF4_M (decode batch, default 1).
"""

from __future__ import annotations

import json
import os
import time


def main() -> None:
    import jax
    import jax.numpy as jnp
    import numpy as np

    from accelerate_tpu.ops.nf4_matmul import nf4_matmul
    from accelerate_tpu.utils.quantization import QuantizationConfig, dequantize, quantize

    iters = int(os.environ.get("BENCH_NF4_ITERS", "50"))
    M = int(os.environ.get("BENCH_NF4_M", "1"))
    from accelerate_tpu.utils.environment import on_tpu_platform

    on_tpu = on_tpu_platform()
    # llama-7b decode matmul shapes (qkv/proj/up/down/head)
    shapes = [(4096, 4096), (4096, 11008), (11008, 4096), (4096, 32000)] if on_tpu else [
        (256, 256), (256, 512)]

    for K, N in shapes:
        rng = np.random.default_rng(0)
        W = rng.normal(size=(K, N)).astype(np.float32)
        qt = quantize(W, QuantizationConfig(load_in_4bit=True, quant_type="nf4",
                                            compute_dtype=jnp.bfloat16))
        x = jnp.asarray(rng.normal(size=(M, K)), jnp.bfloat16)

        kernel = jax.jit(lambda x: nf4_matmul(x, qt))
        xla = jax.jit(lambda x: x @ dequantize(qt, jnp.bfloat16))

        def timed(fn):
            fn(x).block_until_ready()  # compile
            t0 = time.perf_counter()
            for _ in range(iters):
                out = fn(x)
            out.block_until_ready()
            return (time.perf_counter() - t0) / iters

        t_kernel, t_xla = timed(kernel), timed(xla)
        print(json.dumps({
            "metric": "nf4_matmul_us",
            "shape": [K, N], "m": M,
            "kernel_us": round(t_kernel * 1e6, 1),
            "xla_dequant_us": round(t_xla * 1e6, 1),
            "speedup": round(t_xla / t_kernel, 3),
            "platform": jax.devices()[0].platform,
        }), flush=True)


if __name__ == "__main__":
    main()
