"""GPT-2 continuous-batching serving (`docs/serving.md`): ragged requests with
per-request sampling params stream through one jitted decode step over a fixed
set of decode slots, with metrics logged through the standard tracker interface.

Runs on the host CPU in seconds:  JAX_PLATFORMS=cpu python examples/serving_gpt2.py
Swap in `GPT2Config.small()` + real weights and `kv_cache_dtype=jnp.int8`
(half the KV memory -> more slots per chip) for an actual deployment.
"""

import jax
import jax.numpy as jnp
import numpy as np

from accelerate_tpu import Request, SamplingParams, ServingEngine
from accelerate_tpu.models.gpt2 import GPT2Config, GPT2LMHead
from accelerate_tpu.tracking import JSONLTracker


def main():
    cfg = GPT2Config.tiny(dtype=jnp.float32)
    module = GPT2LMHead(cfg)
    params = module.init_params(jax.random.key(0))

    tracker = JSONLTracker("serving_demo", logging_dir="/tmp")
    engine = ServingEngine(
        module, params,
        max_concurrency=4,           # decode batch width == resident requests
        prompt_buckets=(16, 32),     # admission pad targets (one compile each)
        eos_token_id=0,              # recycle a slot early on this token
        tracker=tracker, metrics_log_every=8,
    )

    # ragged prompts, mixed settings: greedy and seeded-sampled requests share
    # the same compiled step (params ride as [max_concurrency] data arrays)
    rng = np.random.default_rng(0)
    requests = [
        Request(prompt=rng.integers(1, cfg.vocab_size, (n,)).astype(np.int32).tolist(),
                params=p)
        for n, p in [
            (5, SamplingParams(max_new_tokens=12)),                      # greedy
            (11, SamplingParams(temperature=0.8, top_k=20, seed=7,
                                max_new_tokens=20)),
            (23, SamplingParams(temperature=1.0, seed=123, max_new_tokens=8)),
            (8, SamplingParams(max_new_tokens=30)),
            (17, SamplingParams(temperature=0.6, top_k=10, seed=1,
                                max_new_tokens=16)),
            (3, SamplingParams(max_new_tokens=6)),
        ]
    ]

    for out in engine.run(requests):
        print(f"req {out.request_id}: prompt_len={out.prompt_len:2d} "
              f"-> {len(out.tokens):2d} tokens ({out.finish_reason}): "
              f"{out.tokens[:8]}{'...' if len(out.tokens) > 8 else ''}")

    m = engine.metrics
    print(f"\n{m.requests_finished.value} requests, "
          f"{m.tokens_generated.value} tokens in {m.steps.value} steps; "
          f"mean slot occupancy {m.slot_occupancy.mean:.0%}; "
          f"metrics stream: {tracker.path}")


if __name__ == "__main__":
    main()
