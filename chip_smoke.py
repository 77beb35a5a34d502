"""The quickest proof that the system starts on the chip.

Drives the two hot paths through the entry points a user calls, at the full
width of gpt2-medium (24 layers, 1024 wide, 16 heads, vocab 50,257, 1024
positions, bf16 compute, random weights from a seed), in ONE process:

  kernels  every `pl.pallas_call` in `accelerate_tpu/ops/`, compiled
           (``interpret=False`` passed, not inferred), against a float32
           `jax.numpy` reference at the model's shapes; and the Pallas grouped
           matmul `ops/moe.grouped_product` takes on the TPU, at the two MoE
           cells' decode shapes, against `jax.lax.ragged_dot`; the delta
           rule's one-pass decode update at the two delta-rule cells' heads
  train    `Accelerator.prepare` + `make_train_step(lm_loss_fn)`, flash
           attention, per-chip batch 8 x 1024, a few steps on one batch
  serve    `ServingEngine` (paged KV, fused decode kernel) answering eight
           ragged requests; then the default engine and solo `generate` on the
           same requests, with the greedy token agreement printed
  mesh     (>= 4 devices) train on data=2 x fsdp=2, serve on mesh=(2, 2),
           every device holding memory

It exits non-zero unless JAX's platform is "tpu", when a phase fails, or when a
kernel ran under the Pallas interpreter. The last stdout line of a passing run
is ``{"ok": true, "device": {...}}`` with the device as JAX reports it.

``--rehearsal`` is the only way to run it anywhere else: the same phases at
`GPT2Config.tiny` on four forced CPU devices with interpreted kernels, every
line labelled. It checks the script, not the chip, and its times mean nothing.
Seconds printed here are information for the builder, not benchmark results.
"""

from __future__ import annotations

import argparse
import gc
import json
import sys
import time
from dataclasses import dataclass

@dataclass(frozen=True)
class Sizes:
    """What one mode runs at; the chip sizes are gpt2-medium's."""

    preset: str
    batch: int  # kernel-phase batch
    seq: int
    heads: int
    head_dim: int
    embed: int
    vocab: int
    ce_rows: int
    window: int
    band_block: int
    positions: int
    gqa: tuple  # (query heads, key/value heads, head_dim, positions) of a grouped-query layer
    latent: tuple  # (query heads, row lanes, value lanes, positions) of a latent-attention layer
    experts: tuple  # (tokens, picks a token, router width, experts held, hidden, expert width) of decode steps
    nf4_shapes: tuple  # (K, N) of the quantized weights
    delta: tuple  # (slots, heads, key = value width) of a delta rule's per-slot state
    train_batch_per_chip: int
    train_seq: int
    train_steps: int
    prompt_buckets: tuple
    prompt_lengths: tuple
    new_tokens: tuple
    dtype: str
    # err = max|got - ref| / max|ref| against the float32 reference; bf16 keeps
    # 8 bits of mantissa and the kernels round p and the output once each
    tol: float
    grad_tol: float


CHIP = Sizes(
    preset="medium", batch=8, seq=1024, heads=16, head_dim=64, embed=1024,
    vocab=50257, ce_rows=8192, window=256, band_block=512, positions=1024,
    gqa=(16, 2, 256, 2560),  # Qwen3-Next's attention layer
    latent=(64, 640, 512, 4608),  # Kimi K2's: 64 heads on one row of 576 lanes stored as 640
    # a decode step of the Kimi K2 and Qwen3-Next cells, and a row count the row tile does not divide
    experts=((256, 8, 384, 12, 7168, 2048), (128, 10, 512, 256, 2048, 512), (24, 10, 512, 256, 2048, 512)),
    nf4_shapes=((1024, 3072), (1024, 4096), (4096, 1024)),
    delta=(16, 32, 128),  # the two delta-rule cells' heads, a few slots
    train_batch_per_chip=8, train_seq=1024, train_steps=6,
    prompt_buckets=(32, 128), prompt_lengths=(5, 31, 12, 24, 120, 77, 50, 97),
    new_tokens=(16, 64, 24, 32, 48, 16, 64, 40),
    dtype="bfloat16", tol=2e-2, grad_tol=4e-2,
)
REHEARSAL = Sizes(
    preset="tiny", batch=2, seq=128, heads=2, head_dim=32, embed=64,
    vocab=256, ce_rows=128, window=48, band_block=32, positions=128,
    gqa=(8, 2, 32, 320),
    latent=(4, 128, 96, 320),
    experts=((8, 2, 8, 4, 64, 32),),
    nf4_shapes=((256, 256),),
    delta=(3, 4, 16),
    train_batch_per_chip=2, train_seq=64, train_steps=6,
    prompt_buckets=(16, 64), prompt_lengths=(5, 15, 9, 12, 60, 33, 20, 47),
    new_tokens=(8, 16, 12, 8, 16, 8, 16, 12),
    dtype="float32", tol=1e-4, grad_tol=1e-3,
)


class Smoke:
    """One run: the sizes, the label every line carries, the kernel spy and
    the list of checks that failed."""

    def __init__(self, sizes: Sizes, rehearsal: bool):
        self.sizes = sizes
        self.rehearsal = rehearsal
        self.failures: list[str] = []
        self.kernel_calls: list[tuple[str, bool]] = []

    # ------------------------------------------------------------- reporting
    def say(self, phase: str, text: str) -> None:
        label = "REHEARSAL " if self.rehearsal else ""
        print(f"{label}[{phase}] {text}", flush=True)

    def check(self, phase: str, what: str, ok: bool, detail: str = "") -> None:
        self.say(phase, f"{'PASS' if ok else 'FAIL'} {what}{' — ' + detail if detail else ''}")
        if not ok:
            self.failures.append(f"{phase}: {what}")

    # ------------------------------------------------------------ kernel spy
    def watch_kernels(self) -> None:
        """Record the ``interpret`` flag of every `pl.pallas_call` traced from
        here on. The ops modules call it through the shared module object, so
        one wrapper sees them all."""
        from jax.experimental import pallas as pl

        real = pl.pallas_call

        def spy(kernel, *args, **kwargs):
            fn = getattr(kernel, "func", kernel)
            name = f"{fn.__module__.rsplit('.', 1)[-1]}.{fn.__name__}"
            self.kernel_calls.append((name, bool(kwargs.get("interpret", False))))
            return real(kernel, *args, **kwargs)

        pl.pallas_call = spy

    def kernels_ran_compiled(self, phase: str, expect: tuple[str, ...]) -> None:
        """Every kernel traced since the last call ran the way this mode
        demands — compiled on the chip — and the ones ``expect`` names ran."""
        calls, self.kernel_calls = self.kernel_calls, []
        names = sorted({name for name, _ in calls})
        wrong = sorted({name for name, interp in calls if interp != self.rehearsal})
        self.check(
            phase,
            f"{len(calls)} pallas_call traces, interpret={self.rehearsal}",
            not wrong, f"wrong mode: {wrong}" if wrong else ", ".join(names),
        )
        missing = [e for e in expect if not any(e in n for n in names)]
        self.check(phase, f"reached kernels {list(expect)}", not missing,
                   f"never traced: {missing}" if missing else "")


# ===================================================================== kernels
def _rel_err(got, ref) -> float:
    import numpy as np

    got = np.asarray(got, np.float32)
    ref = np.asarray(ref, np.float32)
    if not np.isfinite(got).all():
        return float("inf")
    return float(np.abs(got - ref).max() / max(float(np.abs(ref).max()), 1e-30))


def _f32(*arrays):
    import jax.numpy as jnp

    return tuple(a.astype(jnp.float32) for a in arrays)


def phase_kernels(run: Smoke) -> None:
    import jax
    import jax.numpy as jnp
    import numpy as np

    from accelerate_tpu.ops.attention import dot_product_attention
    from accelerate_tpu.ops.flash_attention import flash_attention, paged_decode_attention
    from accelerate_tpu.ops.fused_ce import fused_cross_entropy
    from accelerate_tpu.ops.gated_delta import delta_step_kernel
    from accelerate_tpu.ops.moe import grouped_product
    from accelerate_tpu.ops.nf4_matmul import nf4_matmul
    from accelerate_tpu.utils.quantization import QuantizationConfig, dequantize, quantize

    z = run.sizes
    dtype = jnp.dtype(z.dtype)
    interpret = run.rehearsal
    rng = np.random.default_rng(0)
    run.say("kernels", f"dtype={z.dtype} tol={z.tol:g} grad_tol={z.grad_tol:g} "
                       "(max|got-ref|/max|ref| vs a float32 reference)")

    def compare(name, got, ref, tol):
        err = _rel_err(got, ref)
        run.check("kernels", name, err <= tol, f"err={err:.2e} tol={tol:g} shape={tuple(got.shape)}")

    def rand(shape, scale=1.0):
        return jnp.asarray(rng.normal(size=shape) * scale, dtype)

    def exact(fn, *args):
        """The reference: float32 math on the same (rounded) inputs, with
        full-precision matmuls. Only the reference runs under this setting —
        the kernels keep the precision the train and serve phases use."""
        with jax.default_matmul_precision("highest"):
            return jax.jit(fn)(*args)

    # ---- flash attention: rectangular grid, causal band, sliding window
    q, k, v = (rand((z.batch, z.seq, z.heads, z.head_dim)) for _ in range(3))
    w = jnp.asarray(rng.normal(size=q.shape), jnp.float32)
    variants = {
        "flash rect": dict(),
        "flash band": dict(triangle_block=z.band_block),
        "flash window": dict(window=z.window),
    }
    for name, kw in variants.items():
        def loss(q, k, v, kw=kw):
            out = flash_attention(q, k, v, causal=True, interpret=interpret, **kw)
            return (out.astype(jnp.float32) * w).sum(), out

        def ref_loss(q, k, v, kw=kw):
            out = dot_product_attention(q, k, v, causal=True, window=kw.get("window"))
            return (out * w).sum(), out

        (_, out), grads = jax.jit(jax.value_and_grad(loss, (0, 1, 2), has_aux=True))(q, k, v)
        (_, ref), ref_grads = exact(
            jax.value_and_grad(ref_loss, (0, 1, 2), has_aux=True), *_f32(q, k, v))
        compare(f"{name} fwd s={z.seq}", out, ref, z.tol)
        for label, g, rg in zip(("dq", "dk", "dv"), grads, ref_grads):
            compare(f"{name} {label}", g, rg, z.grad_tol)
    del q, k, v, w, out, ref, grads, ref_grads

    # ---- fused LM head + cross entropy
    hidden = rand((z.ce_rows, z.embed))
    wte = rand((z.vocab, z.embed), 0.02)
    labels = rng.integers(0, z.vocab, z.ce_rows)
    labels[:: 17] = -100  # ignored rows
    labels = jnp.asarray(labels, jnp.int32)

    def ce_ref(h, w_):
        logits = jnp.einsum("ne,ve->nv", h, w_)
        mask = labels != -100
        logp = jax.nn.log_softmax(logits, axis=-1)
        ll = jnp.take_along_axis(logp, jnp.where(mask, labels, 0)[:, None], axis=-1)[:, 0]
        return -(ll * mask).sum() / mask.sum()

    loss, (dh, dw) = jax.jit(jax.value_and_grad(
        lambda h, w_: fused_cross_entropy(h, w_, labels, interpret=interpret), (0, 1)
    ))(hidden, wte)
    ref, (rdh, rdw) = exact(jax.value_and_grad(ce_ref, (0, 1)), *_f32(hidden, wte))
    compare(f"fused_ce loss N={z.ce_rows} V={z.vocab}", loss[None], ref[None], z.tol)
    compare("fused_ce dhidden", dh, rdh, z.grad_tol)
    compare("fused_ce dwte", dw, rdw, z.grad_tol)
    del hidden, wte, dh, dw, rdh, rdw

    # ---- fused paged decode, full-precision and int8 pools: the preset's own
    # heads, and the Qwen3-Next layer's (8 query heads a key/value head); rows
    # hold from one position to the whole span
    bt = 16

    def quantize_pool(pool):
        scale = jnp.abs(pool.astype(jnp.float32)).max(axis=-1) / 127.0
        q8 = jnp.round(pool.astype(jnp.float32) / scale[..., None]).astype(jnp.int8)
        return q8, scale

    for heads, kv_heads, head_dim, positions in (
            (z.heads, z.heads, z.head_dim, z.positions), z.gqa):
        bps = positions // bt
        blocks = z.batch * bps
        lengths = np.linspace(1, positions, z.batch).astype(np.int32)
        lengths[1] = bt + 1  # one row just past a block boundary
        tables = rng.permutation(blocks).astype(np.int32).reshape(z.batch, bps)
        # entries past a row's frontier may hold the released-slot sentinel
        for row, n in enumerate(lengths):
            tables[row, -(-int(n) // bt):] = blocks
        tables_j, lengths_j = jnp.asarray(tables), jnp.asarray(lengths)
        qd = rand((z.batch, heads, head_dim))
        pool_shape = (blocks, bt, kv_heads, head_dim)
        k_pool, v_pool = rand(pool_shape), rand(pool_shape)

        def fold(pool):
            # the engine's stored layout: heads folded into the last dim
            return pool.reshape(blocks, bt, kv_heads * head_dim)

        def paged_ref(q, k_view, v_view):
            # the gather oracle: pool[table] laid out contiguously, frontier mask
            gathered = [jnp.repeat(p[jnp.minimum(tables_j, blocks - 1)].reshape(
                z.batch, positions, kv_heads, head_dim), heads // kv_heads, axis=2)
                for p in (k_view, v_view)]
            mask = (jnp.arange(positions)[None] < lengths_j[:, None])[:, None, None, :]
            return dot_product_attention(q[:, None], *gathered, mask=mask)[:, 0]

        shape = f"{heads}:{kv_heads}x{head_dim} pos={positions} bt={bt}"
        got = jax.jit(lambda *a: paged_decode_attention(*a, interpret=interpret))(
            qd, fold(k_pool), fold(v_pool), tables_j, lengths_j)
        ref = exact(paged_ref, *_f32(qd, k_pool, v_pool))
        compare(f"paged_decode {shape}", got, ref, z.tol)

        (k8, ks), (v8, vs) = quantize_pool(k_pool), quantize_pool(v_pool)
        got = jax.jit(lambda q, k, v, t, l, ks_, vs_: paged_decode_attention(
            q, k, v, t, l, k_scale_pool=ks_, v_scale_pool=vs_, interpret=interpret
        ))(qd, fold(k8), fold(v8), tables_j, lengths_j, ks, vs)
        # dequantized through the compute dtype, as both serving paths do
        deq = [(p.astype(jnp.float32) * s[..., None]).astype(dtype).astype(jnp.float32)
               for p, s in ((k8, ks), (v8, vs))]
        ref = exact(paged_ref, qd.astype(jnp.float32), *deq)
        compare(f"paged_decode int8 pool {shape}", got, ref, z.tol)
        del k_pool, v_pool, k8, v8, deq

    # ---- fused paged decode over a latent pool (absorbed latent attention):
    # every query head against one shared row a token, the value its leading
    # lanes; no value pool
    heads, lanes, value_dim, positions = z.latent
    bps = positions // bt
    blocks = z.batch * bps
    lengths = np.linspace(1, positions, z.batch).astype(np.int32)
    lengths[1] = bt + 1
    tables = rng.permutation(blocks).astype(np.int32).reshape(z.batch, bps)
    for row, n in enumerate(lengths):
        tables[row, -(-int(n) // bt):] = blocks  # the released-slot sentinel
    tables_j, lengths_j = jnp.asarray(tables), jnp.asarray(lengths)
    qd, pool = rand((z.batch, heads, lanes)), rand((blocks, bt, lanes))

    def latent_ref(q, pool):
        rows = pool[jnp.minimum(tables_j, blocks - 1)].reshape(z.batch, positions, 1, lanes)
        mask = (jnp.arange(positions)[None] < lengths_j[:, None])[:, None, None, :]
        keys = jnp.repeat(rows, heads, axis=2)
        return dot_product_attention(q[:, None], keys, keys[..., :value_dim], mask=mask)[:, 0]

    got = jax.jit(lambda q, p, t, l: paged_decode_attention(
        q, p, None, t, l, value_dim=value_dim, interpret=interpret))(qd, pool, tables_j, lengths_j)
    ref = exact(latent_ref, *_f32(qd, pool))
    compare(f"paged_decode latent {heads}:1x{lanes}/{value_dim} pos={positions} bt={bt}",
            got, ref, z.tol)
    del pool

    # ---- the held experts' grouped products at a decode step's rows: the
    # Pallas grouped matmul on the TPU (`ops/moe.grouped_product`; off the TPU
    # it is `ragged_dot` itself, and the rehearsal compares that with itself)
    for tokens, picks, router_width, held, hidden, width in z.experts:
        ids = np.stack([rng.permutation(router_width)[:picks] for _ in range(tokens)]).reshape(-1)
        sizes = np.bincount(ids[ids < held], minlength=held).astype(np.int32)
        n_held = int(sizes.sum())
        for name, k_dim, n_dim in (("gate_up", hidden, 2 * width), ("down", width, hidden)):
            rows, wts = rand((tokens * picks, k_dim)), rand((held, k_dim, n_dim), 0.02)
            got = jax.jit(grouped_product)(rows, wts, jnp.asarray(sizes))
            ref = jax.jit(lambda r, w_, s: jax.lax.ragged_dot(
                r, w_, s, preferred_element_type=jnp.float32))(rows, wts, jnp.asarray(sizes))
            compare(f"grouped_product {name} rows={tokens * picks} held={n_held} in "
                    f"{int((sizes > 0).sum())} of {held} groups [{k_dim},{n_dim}]",
                    got[:n_held], ref[:n_held], z.tol)
            del rows, wts

    # ---- the delta rule's decode update, one Pallas pass over the state
    # (`ops/gated_delta.delta_step_kernel`), both decays, slot 0 finished
    # (g = 0, beta = 0: its state comes back bit-equal), against the rule's
    # float32 arithmetic in plain jax.numpy
    def delta_ref(state, q, k, v, g, beta):
        state = state * (jnp.exp(g)[..., :, None] if g.ndim == 3 else jnp.exp(g)[..., None, None])
        d = beta[..., None] * (v - jnp.sum(state * k[..., :, None], axis=-2))
        state = state + k[..., :, None] * d[..., None, :]
        return state, jnp.sum(state * q[..., :, None], axis=-2)

    slots, heads, width = z.delta
    for name, g_shape in (("delta_step", (slots, heads)), ("kda_step", (slots, heads, width))):
        f32 = lambda shape, scale=1.0: jnp.asarray(rng.normal(size=shape) * scale, jnp.float32)  # noqa: E731
        state = f32((slots, heads, width, width), 0.3)
        q, k, v = (f32((slots, heads, width), width ** -0.5) for _ in range(3))
        g = -jnp.asarray(rng.uniform(size=g_shape), jnp.float32).at[0].set(0.0)
        beta = jnp.asarray(rng.uniform(size=(slots, heads)), jnp.float32).at[0].set(0.0)
        got_state, got_o = jax.jit(lambda *a: delta_step_kernel(*a, interpret=interpret))(state, q, k, v, g, beta)
        want_state, want_o = exact(delta_ref, state, q, k, v, g, beta)
        compare(f"{name} state {list(state.shape)}", got_state, want_state, 1e-5)
        compare(f"{name} output", got_o, want_o, 1e-5)
        run.check("kernels", f"{name} finished slot bit-equal", bool(jnp.all(got_state[0] == state[0])))
    del state, want_state, got_state

    # ---- nf4 dequant-matmul (concrete payload: the only way it runs)
    for kdim, ndim in z.nf4_shapes:
        weight = rng.normal(size=(kdim, ndim)).astype(np.float32) * 0.02
        qt = quantize(weight, QuantizationConfig(
            load_in_4bit=True, quant_type="nf4", compute_dtype=dtype))
        x = rand((z.batch, kdim))
        got = nf4_matmul(x, qt, interpret=interpret)
        ref = exact(lambda x_: x_ @ dequantize(qt, jnp.float32), x.astype(jnp.float32))
        compare(f"nf4_matmul {kdim}x{ndim}", got, ref, z.tol)

    run.kernels_ran_compiled("kernels", (
        "flash_attention._fwd_kernel", "flash_attention._dq_kernel",
        "flash_attention._dkv_kernel", "flash_attention._fwd_band_kernel",
        "flash_attention._dq_band_kernel", "flash_attention._dkv_band_kernel",
        "fused_ce._fwd_kernel", "fused_ce._dh_kernel", "fused_ce._dw_kernel",
        "flash_attention._paged_decode_kernel", "nf4_matmul._kernel", "gated_delta._delta_step_kernel",
    ) + (() if run.rehearsal else ("gmm.kernel",)))  # megablox's, through `grouped_product`


# ======================================================================= train
def _reset_state() -> None:
    from accelerate_tpu.state import AcceleratorState, GradientState

    AcceleratorState._reset_state(reset_partial_state=True)
    GradientState._reset_state()


def _bytes_in_use(run: Smoke, phase: str) -> list[int] | None:
    """Per-device ``bytes_in_use``; None where the backend keeps no stats
    (the rehearsal's CPU). On a TPU a missing figure raises."""
    import jax

    from accelerate_tpu.utils.environment import device_memory_stats

    stats = [device_memory_stats(d) for d in jax.devices()]
    if not all(stats):
        run.say(phase, "memory_stats: none on this backend")
        return None
    used = [int(s["bytes_in_use"]) for s in stats]
    peak = [int(s["peak_bytes_in_use"]) for s in stats]
    run.say(phase, "bytes_in_use per device: " + " ".join(f"{u / 2**30:.2f}GiB" for u in used)
            + "; peak so far: " + " ".join(f"{u / 2**30:.2f}GiB" for u in peak))
    return used


def _train(run: Smoke, phase: str, parallelism=None, rules=None, steps=None):
    """prepare + make_train_step on one fixed batch; returns the prepared
    model, the mesh and per-device bytes in use at the end of training."""
    import jax
    import jax.numpy as jnp
    import numpy as np
    import optax

    from accelerate_tpu.accelerator import Accelerator
    from accelerate_tpu.data_loader import DataLoaderShard
    from accelerate_tpu.models.gpt2 import GPT2Config, GPT2LMHead, lm_loss_fn

    z = run.sizes
    _reset_state()
    acc = Accelerator(mixed_precision="bf16", parallelism_config=parallelism, sharding_rules=rules)
    cfg = getattr(GPT2Config, z.preset)(dtype=jnp.bfloat16, attention_impl="flash")
    module = GPT2LMHead(cfg)
    params = module.init_params(jax.random.key(0))
    global_batch = z.train_batch_per_chip * len(jax.devices())
    ids = np.random.default_rng(0).integers(
        0, cfg.vocab_size, (global_batch, z.train_seq)).astype(np.int32)
    model, _opt, loader = acc.prepare(
        (module, params), optax.adamw(3e-4), DataLoaderShard([{"input_ids": ids}]))
    del params
    step = acc.make_train_step(lm_loss_fn)
    batch = next(iter(loader))
    mesh = {k: v for k, v in acc.mesh.shape.items() if v > 1}
    run.say(phase, f"gpt2-{z.preset} layers={cfg.n_layer} embd={cfg.n_embd} heads={cfg.n_head} "
                   f"vocab={cfg.vocab_size} mesh={mesh or 'one device'} "
                   f"global_batch={global_batch} seq={z.train_seq} "
                   f"batch sharding={batch['input_ids'].sharding.spec}")

    losses, seconds = [], []
    for _ in range(steps or z.train_steps):
        t0 = time.perf_counter()
        losses.append(float(step(batch)))  # float() waits for the device
        seconds.append(time.perf_counter() - t0)
    warm = sorted(seconds[1:])[len(seconds[1:]) // 2]
    run.say(phase, "losses " + " ".join(f"{x:.4f}" for x in losses))
    run.say(phase, f"info: first step (compile included) {seconds[0]:.1f}s, "
                   f"warm step median {warm:.3f}s")
    run.check(phase, "every loss finite", bool(np.isfinite(losses).all()))
    run.check(phase, "last loss < first", losses[-1] < losses[0],
              f"{losses[0]:.4f} -> {losses[-1]:.4f}")
    run.kernels_ran_compiled(phase, (
        "flash_attention._fwd_kernel", "flash_attention._dq_kernel", "flash_attention._dkv_kernel"))
    return model, acc.mesh, _bytes_in_use(run, phase)  # while the state is live


def phase_train(run: Smoke) -> None:
    _train(run, "train")


# ======================================================================= serve
def _requests(run: Smoke, vocab: int):
    import numpy as np

    from accelerate_tpu.serving import Request, SamplingParams

    z = run.sizes
    rng = np.random.default_rng(1)
    requests = []
    for i, (plen, new) in enumerate(zip(z.prompt_lengths, z.new_tokens)):
        greedy = i % 2 == 0
        requests.append(Request(
            prompt=rng.integers(0, vocab, plen).tolist(),
            params=SamplingParams(
                temperature=0.0 if greedy else 0.8, top_k=None if greedy else 40,
                seed=100 + i, max_new_tokens=new),
        ))
    return requests


def _serve(run: Smoke, phase: str, name: str, module, params, eos: int, **engine_kw):
    """One engine answering the eight requests; returns their token lists and
    per-device bytes in use while the engine is still alive."""
    import copy

    from accelerate_tpu.serving import FINISH_EOS, FINISH_LENGTH, ServingEngine

    z = run.sizes
    requests = _requests(run, module.config.vocab_size)
    t0 = time.perf_counter()
    engine = ServingEngine(
        module, params, max_concurrency=8, prompt_buckets=z.prompt_buckets,
        eos_token_id=eos, **engine_kw)
    outputs = engine.run(copy.deepcopy(requests))
    wall = time.perf_counter() - t0
    m = engine.metrics
    run.say(phase, f"{name}: {len(outputs)} requests, {sum(len(o.tokens) for o in outputs)} tokens, "
                   f"{m.steps.value} steps, {m.compile_count.value} compiles; "
                   f"info: {wall:.1f}s wall, compiles included")
    reasons = [o.finish_reason for o in outputs]
    run.check(phase, f"{name}: every request ends eos or length",
              len(outputs) == len(requests)
              and all(r in (FINISH_EOS, FINISH_LENGTH) for r in reasons), str(reasons))
    counts_ok = all(
        (o.finish_reason == FINISH_LENGTH and len(o.tokens) == r.params.max_new_tokens
         and eos not in o.tokens[:-1])
        or (o.finish_reason == FINISH_EOS and 0 < len(o.tokens) <= r.params.max_new_tokens
            and o.tokens[-1] == eos and eos not in o.tokens[:-1])
        for o, r in zip(outputs, requests))
    run.check(phase, f"{name}: token counts match max_new_tokens / EOS", counts_ok,
              str([len(o.tokens) for o in outputs]))
    run.check(phase, f"{name}: token ids in range",
              all(0 <= t < module.config.vocab_size for o in outputs for t in o.tokens))
    bad = {k: c.value for k, c in (
        ("steps_poisoned", m.steps_poisoned), ("requests_retried", m.requests_retried),
        ("requests_rejected", m.requests_rejected), ("requests_expired", m.requests_expired),
        ("requests_cancelled", m.requests_cancelled)) if c.value}
    run.check(phase, f"{name}: poisoned/retried/rejected counters are 0", not bad, str(bad or ""))
    return [o.tokens for o in outputs], _bytes_in_use(run, phase)  # while the engine is live


def _agreement(a: list[list[int]], b: list[list[int]], which: list[int]) -> str:
    """Identical streams, and tokens before the first difference, over the
    requests in ``which`` (one flipped argmax changes everything after it)."""
    same = sum(a[i][:len(b[i])] == b[i][:len(a[i])] for i in which)
    prefix = total = 0
    for i in which:
        n = min(len(a[i]), len(b[i]))
        total += n
        prefix += next((j for j in range(n) if a[i][j] != b[i][j]), n)
    return f"{same}/{len(which)} streams identical, {prefix}/{total} tokens before the first difference"


def _serving_model(run: Smoke):
    import jax
    import jax.numpy as jnp

    from accelerate_tpu.models.gpt2 import GPT2Config, GPT2LMHead

    module = GPT2LMHead(getattr(GPT2Config, run.sizes.preset)(dtype=jnp.bfloat16))
    return module, module.init_params(jax.random.key(0))


def phase_serve(run: Smoke) -> None:
    import jax.numpy as jnp
    import numpy as np

    from accelerate_tpu.models.generation import generate

    module, params = _serving_model(run)
    eos = module.config.vocab_size - 1  # GPT-2's <|endoftext|> is the last id
    fused, _ = _serve(run, "serve", "paged+fused", module, params, eos,
                      paged_kv=True, paged_attention="fused")
    run.kernels_ran_compiled("serve", ("flash_attention._paged_decode_kernel",))
    default, _ = _serve(run, "serve", "default (gather)", module, params, eos)

    requests = _requests(run, module.config.vocab_size)
    greedy = [i for i, r in enumerate(requests) if r.params.temperature == 0.0]
    sampled = [i for i in range(len(requests)) if i not in greedy]
    solo: list[list[int]] = [[] for _ in requests]
    t0 = time.perf_counter()
    for i in greedy:
        ids = jnp.asarray(np.asarray(requests[i].prompt, np.int32)[None])
        solo[i] = np.asarray(generate(
            module, params, ids, max_new_tokens=requests[i].params.max_new_tokens))[0].tolist()
    run.say("serve", f"solo generate: {len(greedy)} greedy requests; "
                     f"info: {time.perf_counter() - t0:.1f}s wall, compiles included")
    run.check("serve", "solo generate: token ids in range",
              all(0 <= t < module.config.vocab_size for s in solo for t in s))
    # printed, not enforced: random weights give nearly flat logits, so one
    # bf16 rounding difference between two correct programs can flip an argmax
    run.say("serve", "greedy agreement fused ~ default: " + _agreement(fused, default, greedy))
    run.say("serve", "greedy agreement fused ~ solo:    " + _agreement(fused, solo, greedy))
    run.say("serve", "greedy agreement default ~ solo:  " + _agreement(default, solo, greedy))
    run.say("serve", "sampled agreement fused ~ default: " + _agreement(fused, default, sampled))


# ======================================================================== mesh
def phase_mesh(run: Smoke) -> None:
    """Four chips: the default train phase already ran on data=4. Here: FSDP
    sharding on data=2 x fsdp=2, and the fused engine on mesh=(2, 2)."""
    import jax

    from accelerate_tpu.models.gpt2 import gpt2_sharding_rules
    from accelerate_tpu.parallel.mesh import ParallelismConfig

    if len(jax.devices()) < 4:
        run.say("mesh", f"skipped: {len(jax.devices())} device(s), needs 4")
        return
    model, mesh, used = _train(
        run, "mesh", ParallelismConfig(data_parallel_size=2, fsdp_size=2),
        gpt2_sharding_rules(), steps=4)
    leaves = jax.tree.leaves(model.params)
    sharded = sum(1 for leaf in leaves if not leaf.sharding.is_fully_replicated)
    run.check("mesh", "some parameter leaves are not fully replicated", sharded > 0,
              f"{sharded}/{len(leaves)} leaves sharded on {dict(mesh.shape)}")
    if used is not None:
        run.check("mesh", "every device holds training state",
                  min(used) > 0.1 * max(used), f"min/max = {min(used) / max(used):.2f}")
    del model, leaves
    _reset_state()

    gc.collect()  # the serving engine needs the memory the training state held
    module, params = _serving_model(run)
    _, used = _serve(run, "mesh", "paged+fused mesh=(2, 2)", module, params,
                     module.config.vocab_size - 1, paged_kv=True, paged_attention="fused",
                     mesh=(2, 2))
    run.kernels_ran_compiled("mesh", ("flash_attention._paged_decode_kernel",))
    if used is not None:
        run.check("mesh", "every device holds serving state",
                  min(used) > 0.1 * max(used), f"min/max = {min(used) / max(used):.2f}")


# ======================================================================== main
PHASES = {"kernels": phase_kernels, "train": phase_train, "serve": phase_serve,
          "mesh": phase_mesh}


def main() -> int:
    parser = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    parser.add_argument("--rehearsal", action="store_true",
                        help="tiny config on forced CPU devices, interpreted kernels; "
                             "checks this script, proves nothing about the chip")
    parser.add_argument("--phases", default=",".join(PHASES),
                        help=f"comma-separated subset of {','.join(PHASES)} (default: all)")
    args = parser.parse_args()
    phases = [p for p in args.phases.split(",") if p]
    unknown = [p for p in phases if p not in PHASES]
    if unknown:
        parser.error(f"unknown phases {unknown}")

    if args.rehearsal:
        from accelerate_tpu.test_utils.platform import force_cpu_platform

        force_cpu_platform(4)
    import jax

    from accelerate_tpu.utils.environment import (
        configure_compile_cache,
        device_description,
        require_tpu,
    )

    device = device_description()
    run = Smoke(REHEARSAL if args.rehearsal else CHIP, args.rehearsal)
    run.say("device", f"platform={device['platform']} device_kind={device['kind']!r} "
                      f"count={device['count']} jax={jax.__version__}")
    if not args.rehearsal:
        require_tpu("chip_smoke.py", rehearse="--rehearsal")
    run.say("device", f"compile cache: {configure_compile_cache()}")
    run.watch_kernels()

    started = time.perf_counter()
    for name in phases:
        t0 = time.perf_counter()
        PHASES[name](run)
        gc.collect()  # the next phase needs the device memory this one held
        run.say(name, f"info: phase took {time.perf_counter() - t0:.1f}s")
    if run.kernel_calls:  # traced after a phase's own check
        run.kernels_ran_compiled("done", ())
    run.say("done", f"info: {time.perf_counter() - started:.1f}s in all")

    if run.failures:
        for failure in run.failures:
            print(f"chip_smoke FAILED {failure}", file=sys.stderr)
        return 1
    if set(phases) != set(PHASES):
        run.say("done", f"partial run ({','.join(phases)}): no result line")
        return 0
    result = {"ok": True, "device": device}
    if args.rehearsal:
        result["rehearsal"] = True
    print(json.dumps(result), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
