"""Feature: long-context training with sliding-window (band) attention.

The reference has no long-context lever at all (SURVEY.md §5); this is the
TPU-native story: `LlamaConfig(sliding_window=W)` routes causal attention onto
the Pallas band grid, where only blocks inside the window exist as grid cells —
attention costs O(seq * W) instead of O(seq^2), so doubling the sequence at
fixed W doubles (not quadruples) attention time. GQA composes: grouped K/V are
read in place, never repeated in HBM. For sequences beyond one chip's memory,
add the `sequence` mesh axis + ring attention (`docs/long_context.md`).
"""

from __future__ import annotations

import sys
from pathlib import Path

import jax
import jax.numpy as jnp
import numpy as np
import optax

sys.path.insert(0, str(Path(__file__).parent))
from _common import base_parser

from accelerate_tpu import Accelerator, DataLoaderShard, set_seed
from accelerate_tpu.models.llama import LlamaConfig, LlamaForCausalLM
from accelerate_tpu.utils.environment import on_tpu_platform


def main():
    args = base_parser().parse_args()
    set_seed(args.seed)

    seq, window = 128, 32  # production: e.g. seq 32768, window 4096 (Mistral)
    cfg = LlamaConfig.tiny(
        dtype=jnp.float32,
        max_position_embeddings=seq,
        sliding_window=window,
        # 'flash' engages the Pallas band kernel on TPU (interpreted on CPU);
        # 'xla' computes the same masked attention without the kernel
        attention_impl="flash" if on_tpu_platform() else "xla",
    )
    accelerator = Accelerator(mixed_precision=args.mixed_precision)

    module = LlamaForCausalLM(cfg)
    rng = np.random.default_rng(args.seed)
    n_batches = 8 if args.tiny else 16
    # tokens drawn from a 32-token subset of the 256-token vocab: the LM
    # learns the restricted support, so the loss has room to fall from
    # ~ln(256) toward ~ln(32) (uniform over the FULL vocab would start at
    # the entropy floor with nothing to learn)
    ids = rng.integers(0, 32, (n_batches, 2, seq)).astype(np.int32)
    params = module.init(jax.random.key(0), ids[0])["params"]

    model, optimizer, loader = accelerator.prepare(
        (module, params), optax.adamw(args.lr),
        DataLoaderShard([{"input_ids": b} for b in ids]),
    )

    def loss_fn(m, batch):
        logits = m(batch["input_ids"])
        labels = jnp.roll(batch["input_ids"], -1, axis=1)
        logp = jax.nn.log_softmax(logits[:, :-1], axis=-1)
        return -jnp.take_along_axis(logp, labels[:, :-1, None], axis=-1).mean()

    step = accelerator.make_train_step(loss_fn)
    losses = [float(step(batch)) for batch in loader]
    accelerator.print(
        f"sliding-window W={window} over seq={seq}: "
        f"loss {losses[0]:.3f} -> {losses[-1]:.3f}"
    )
    assert min(losses[1:]) < losses[0], losses


if __name__ == "__main__":
    main()
