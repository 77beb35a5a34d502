"""Training-throughput rows for the BASELINE 'targets to measure' table:
nlp_example (BERT-base MRPC-shape classification, samples/sec/chip,
BASELINE.json configs[0]) and cv_example (ResNet-50 image classification,
images/sec/chip, configs[1]). One JSON line per row, SWEEP.jsonl-compatible.

Env: BENCH_EX_ITERS (default 30), BENCH_EX_ROWS=bert,resnet (default both),
BENCH_EX_BERT_BATCH (64), BENCH_EX_RESNET_BATCH (64).
On non-TPU platforms runs tiny shapes so CI completes.
"""

from __future__ import annotations

import json
import os
import time


def _row(metric, value, unit, detail):
    print(json.dumps({"metric": metric, "value": round(value, 1), "unit": unit,
                      "detail": detail}), flush=True)


def main() -> None:
    import jax
    import jax.numpy as jnp
    import numpy as np
    import optax

    from accelerate_tpu.accelerator import Accelerator
    from accelerate_tpu.state import AcceleratorState, GradientState

    from accelerate_tpu.utils.environment import on_tpu_platform

    on_tpu = on_tpu_platform()
    iters = int(os.environ.get("BENCH_EX_ITERS", "30"))
    rows = os.environ.get("BENCH_EX_ROWS", "bert,resnet").split(",")

    def timed(step, batch):
        float(step(batch))  # compile
        float(step(batch))
        t0 = time.perf_counter()
        for _ in range(iters):
            loss = step(batch)
        final = float(loss)  # device->host sync closes the timing region
        return time.perf_counter() - t0, final

    if "bert" in rows:
        from accelerate_tpu.models.bert import (
            BertConfig,
            BertForSequenceClassification,
            classification_loss_fn,
        )

        AcceleratorState._reset_state()
        GradientState._reset_state()
        acc = Accelerator(mixed_precision="bf16" if on_tpu else "no")
        cfg = BertConfig.base() if on_tpu else BertConfig.tiny()
        batch_size = int(os.environ.get("BENCH_EX_BERT_BATCH", "64" if on_tpu else "8"))
        seq = 128 if on_tpu else 32  # MRPC pair length (reference nlp_example pads to 128)
        module = BertForSequenceClassification(cfg)
        params = module.init_params(jax.random.key(0), batch=2, seq=seq)
        model, opt = acc.prepare((module, params), optax.adamw(2e-5))
        step = acc.make_train_step(classification_loss_fn)
        rng = np.random.default_rng(0)
        batch = {
            "input_ids": jnp.asarray(rng.integers(0, cfg.vocab_size, (batch_size, seq)), jnp.int32),
            "attention_mask": jnp.ones((batch_size, seq), jnp.int32),
            "labels": jnp.asarray(rng.integers(0, 2, (batch_size,)), jnp.int32),
        }
        dt, loss = timed(step, batch)
        per_chip = batch_size * iters / dt / len(jax.devices())
        _row("nlp_example_samples_per_sec_per_chip", per_chip, "samples/s/chip", {
            "model": "bert-base" if on_tpu else "bert-tiny(cpu)", "batch": batch_size,
            "seq": seq, "loss": round(loss, 4), "platform": jax.devices()[0].platform,
            "reference_row": "BASELINE configs[0]: measure (no reference value)",
        })

    if "resnet" in rows:
        from accelerate_tpu.models.resnet import (
            ResNetConfig,
            ResNet,
            image_classification_loss_fn,
        )

        AcceleratorState._reset_state()
        GradientState._reset_state()
        acc = Accelerator(mixed_precision="bf16" if on_tpu else "no")
        cfg = ResNetConfig.resnet50() if on_tpu else ResNetConfig.tiny()
        batch_size = int(os.environ.get("BENCH_EX_RESNET_BATCH", "64" if on_tpu else "8"))
        size = 224 if on_tpu else 32
        module = ResNet(cfg)
        params = module.init_params(jax.random.key(0), image_size=size)
        model, opt = acc.prepare((module, params), optax.adamw(1e-3))
        step = acc.make_train_step(image_classification_loss_fn)
        rng = np.random.default_rng(0)
        batch = {
            "image": jnp.asarray(rng.normal(size=(batch_size, size, size, 3)), jnp.float32),
            "label": jnp.asarray(rng.integers(0, cfg.num_classes, (batch_size,)), jnp.int32),
        }
        dt, loss = timed(step, batch)
        per_chip = batch_size * iters / dt / len(jax.devices())
        _row("cv_example_images_per_sec_per_chip", per_chip, "images/s/chip", {
            "model": "resnet50" if on_tpu else "resnet-tiny(cpu)", "batch": batch_size,
            "image": size, "loss": round(loss, 4), "platform": jax.devices()[0].platform,
            "reference_row": "BASELINE configs[1]: measure (no reference value)",
        })


if __name__ == "__main__":
    main()
