"""The one traffic generator: a mix is a data file of parameters under
`traffic/`, and everything here is drawn from `--seed`.

Every seed gets the same sizes (stratified quantiles of the stated
distribution) in another order, so that two seeds never differ in the amount
of work, only in which requests meet.

Kinds:
  tokens       training batches: `host_batches` arrays [batch, seq] of ids,
               every row different
  closed_loop  `clients` callers, each sending its next request the moment
               its last one finishes; lengths log-uniform between the `min`
               and `max` of `prompt_len` and `new_tokens`. The pool is `laps`
               laps of `lap` requests: every lap holds the same `lap` prompt
               lengths and the same `lap` output lengths, paired and ordered
               anew from the seed, with token ids of its own.
"""

from __future__ import annotations

import numpy as np


def rng_for(seed: int, stream: int) -> np.random.Generator:
    return np.random.default_rng([int(seed), stream])


def quantile_lengths(spec: dict, n: int) -> np.ndarray:
    """n lengths at the mid-quantiles of a log-uniform distribution between
    `min` and `max`. The same for every seed."""
    lo, hi = int(spec["min"]), int(spec["max"])
    u = (np.arange(n) + 0.5) / n
    return np.clip(np.rint(lo * (hi / lo) ** u), lo, hi).astype(np.int64)


def token_batches(mix: dict, seed: int, vocab: int, global_batch: int) -> list[np.ndarray]:
    rng = rng_for(seed, 1)
    return [rng.integers(0, vocab, (global_batch, int(mix["seq"]))).astype(np.int32)
            for _ in range(int(mix["host_batches"]))]


def request_pool(mix: dict, seed: int, vocab: int) -> list[dict]:
    """`laps * lap` requests: {"prompt": [ids], "new_tokens": n}. The loop
    takes them in order and starts again at the end."""
    if mix["kind"] != "closed_loop":
        raise ValueError(f"unknown kind of request traffic {mix['kind']!r}")
    lap, rng = int(mix["lap"]), rng_for(seed, 2)
    prompt_lens, new_lens = quantile_lengths(mix["prompt_len"], lap), quantile_lengths(mix["new_tokens"], lap)
    pool = []
    for _ in range(int(mix["laps"])):
        for p, t in zip(rng.permutation(prompt_lens), rng.permutation(new_lens)):
            pool.append({"prompt": rng.integers(0, vocab, int(p)).tolist(), "new_tokens": int(t)})
    return pool


def aged_ramp(mix: dict, seed: int, vocab: int) -> list[dict]:
    """The requests the `clients` start with, one each, sent in set-up: a lap
    of the mix whose answers are cut to evenly spread shares of their lengths,
    as a loop that has run for long holds them, so that the window opens on
    callers at every age and not on `clients` that began together. They are
    no part of the mix: marked `ramp`, they count as work done and in no tail."""
    clients, rng = int(mix["clients"]), rng_for(seed, 4)
    prompt_lens = rng.permutation(quantile_lengths(mix["prompt_len"], clients))
    new_lens = rng.permutation(quantile_lengths(mix["new_tokens"], clients))
    left = (rng.permutation(clients) + 0.5) / clients
    return [{"prompt": rng.integers(0, vocab, int(p)).tolist(),
             "new_tokens": max(2, int(round(t * share))), "ramp": True}
            for p, t, share in zip(prompt_lens, new_lens, left)]
