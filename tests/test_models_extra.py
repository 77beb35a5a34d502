"""BERT / ResNet model tests + example smoke runs (reference `tests/test_examples.py` role)."""

import os
import subprocess
import sys
from pathlib import Path

import jax
import jax.numpy as jnp
import numpy as np
import optax
import pytest

from accelerate_tpu.accelerator import Accelerator
from accelerate_tpu.data_loader import DataLoaderShard
from accelerate_tpu.models.bert import (
    BertConfig,
    BertForSequenceClassification,
    bert_sharding_rules,
    classification_loss_fn,
)
from accelerate_tpu.models.resnet import ResNet, ResNetConfig, image_classification_loss_fn
from accelerate_tpu.parallel.mesh import ParallelismConfig
from accelerate_tpu.state import AcceleratorState, GradientState

REPO = Path(__file__).resolve().parent.parent


def _fresh(**kwargs):
    AcceleratorState._reset_state()
    GradientState._reset_state()
    return Accelerator(**kwargs)


def test_bert_forward_shapes():
    cfg = BertConfig.tiny(dtype=jnp.float32)
    module = BertForSequenceClassification(cfg)
    params = module.init_params(jax.random.key(0))
    ids = jnp.zeros((2, 16), dtype=jnp.int32)
    mask = jnp.ones((2, 16), dtype=jnp.int32)
    logits = module.apply({"params": params}, ids, mask)
    assert logits.shape == (2, cfg.num_labels)
    assert logits.dtype == jnp.float32


def test_bert_attention_mask_effective():
    cfg = BertConfig.tiny(dtype=jnp.float32)
    module = BertForSequenceClassification(cfg)
    params = module.init_params(jax.random.key(0))
    ids = jnp.asarray(np.random.default_rng(0).integers(0, cfg.vocab_size, (1, 16)), dtype=jnp.int32)
    mask = jnp.ones((1, 16), dtype=jnp.int32).at[:, 8:].set(0)
    # changing masked-out tokens must not change the logits
    ids2 = ids.at[:, 8:].set(7)
    a = module.apply({"params": params}, ids, mask)
    b = module.apply({"params": params}, ids2, mask)
    np.testing.assert_allclose(np.asarray(a), np.asarray(b), atol=1e-5)


def test_bert_tp_training():
    cfg = BertConfig.tiny(dtype=jnp.float32)
    acc = _fresh(
        parallelism_config=ParallelismConfig(data_parallel_size=2, tensor_size=4),
        sharding_rules=bert_sharding_rules(),
    )
    module = BertForSequenceClassification(cfg)
    params = module.init_params(jax.random.key(0))
    rng = np.random.default_rng(0)
    batches = [
        {
            "input_ids": rng.integers(0, cfg.vocab_size, (8, 16)).astype(np.int32),
            "attention_mask": np.ones((8, 16), dtype=np.int32),
            "labels": rng.integers(0, 2, (8,)).astype(np.int32),
        }
        for _ in range(3)
    ]
    model, opt, dl = acc.prepare((module, params), optax.adamw(1e-3), DataLoaderShard(batches))
    step = acc.make_train_step(classification_loss_fn)
    losses = [float(step(b)) for b in dl]
    assert all(np.isfinite(losses))


def test_resnet_trains():
    cfg = ResNetConfig.tiny(dtype=jnp.float32)
    acc = _fresh()
    module = ResNet(cfg)
    params = module.init_params(jax.random.key(0), image_size=16)
    rng = np.random.default_rng(0)
    labels = rng.integers(0, cfg.num_classes, (16,)).astype(np.int32)
    base = labels[:, None, None, None] / cfg.num_classes
    images = (base + 0.05 * rng.normal(size=(16, 16, 16, 3))).astype(np.float32)
    batches = [{"image": images, "label": labels}] * 6
    model, opt, dl = acc.prepare((module, params), optax.sgd(0.1, momentum=0.9), DataLoaderShard(batches))
    step = acc.make_train_step(image_classification_loss_fn)
    losses = [float(step(b)) for b in dl]
    assert losses[-1] < losses[0]


@pytest.mark.parametrize("script,extra", [
    ("examples/nlp_example.py", ["--with_tracking", "--checkpointing"]),
    ("examples/cv_example.py", []),
    ("examples/complete_nlp_example.py", ["--with_tracking", "--checkpointing_steps", "epoch"]),
    ("examples/complete_cv_example.py", ["--with_tracking", "--checkpointing"]),
])
def test_example_scripts_run(tmp_path, script, extra):
    env = dict(os.environ)
    env.update({
        "JAX_PLATFORMS": "cpu",
        "XLA_FLAGS": "--xla_force_host_platform_device_count=8",
        "PYTHONPATH": str(REPO),
    })
    cmd = [sys.executable, str(REPO / script), "--tiny", "--num_epochs", "1",
           "--project_dir", str(tmp_path)]
    cmd += [e for e in extra]
    if script.endswith("/cv_example.py"):
        cmd = [c for c in cmd if c not in ("--project_dir", str(tmp_path))]
    out = subprocess.run(cmd, capture_output=True, text=True, env=env, timeout=600)
    assert out.returncode == 0, out.stdout + out.stderr
    assert "accuracy" in out.stdout
