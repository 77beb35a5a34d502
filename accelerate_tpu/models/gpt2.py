"""GPT-2 family, TPU-first.

This is the flagship training model (BASELINE.md north-star: GPT-2-medium
pretraining tokens/sec/chip). Design choices for the MXU/XLA:

  - flax.linen with explicit ``dtype`` (compute, bf16 by default on TPU) and
    ``param_dtype`` (fp32 masters) — matmuls run bf16 on the MXU, layernorm/
    softmax statistics in fp32.
  - attention dispatches to the Pallas flash kernel for long sequences (or XLA
    fused attention otherwise) via `ops.attention.attention`.
  - optional ``remat`` applies jax.checkpoint per block (HBM <-> FLOPs trade).
  - optional ``scan_layers`` stacks the blocks with `nn.scan`: one compiled block
    body instead of n_layer copies — near-constant compile time with depth, and
    the layer axis becomes a leading param dim (which also gives pipeline
    parallelism a natural stage axis).
  - weights are plain kernels ([in, out]) so Megatron-style TP is pure sharding:
    `gpt2_sharding_rules()` returns the column/row PartitionSpecs.

Interchange: `params_from_hf_gpt2` maps HuggingFace transformers GPT-2 weights
into this layout (reference capability: big-model checkpoint ingestion).
"""

from __future__ import annotations

from dataclasses import dataclass, field
from typing import Any

import flax.linen as nn
import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import PartitionSpec as P

from ..ops.attention import attention
from ..parallel.sharding import ShardingRules


@dataclass(frozen=True)
class GPT2Config:
    vocab_size: int = 50257
    n_positions: int = 1024
    n_embd: int = 768
    n_layer: int = 12
    n_head: int = 12
    mlp_ratio: int = 4
    dropout: float = 0.0
    layer_norm_epsilon: float = 1e-5
    dtype: Any = jnp.bfloat16
    param_dtype: Any = jnp.float32
    remat: bool = False
    remat_policy: str | None = None  # see utils/remat.py: full|dots|dots_no_batch
    scan_layers: bool = False
    attention_impl: str = "auto"  # 'xla' | 'flash' | 'auto'
    kv_cache_dtype: Any = None  # None | jnp.int8 (see models/kv_cache.py)
    # per-slot [b]-vector cache write index instead of one scalar shared by the
    # batch: every row decodes at its own position (the serving engine's
    # continuous-batching slots — serving/engine.py). position_offset may
    # then be a [b] vector too.
    kv_cache_per_slot: bool = False
    # paged KV: decode KV lives in a shared [kv_num_blocks, kv_block_tokens,
    # kv_heads * head_dim] block pool instead of per-slot rows, and each row
    # attends through its block table (models/kv_cache.paged_decode_update —
    # the serving engine's paged_kv mode, docs/serving.md "Paged KV"). Implies
    # the per-slot write-cursor semantics; block_tables must be threaded into
    # __call__ on every decode step.
    kv_cache_paged: bool = False
    kv_num_blocks: int = 0
    kv_block_tokens: int = 16
    # paged decode attention path: "gather" materializes pool[table] into a
    # contiguous per-slot view and runs XLA attention over it (the parity
    # oracle); "fused" reads K/V blocks in place through the block table with
    # the Pallas kernel `ops.flash_attention.paged_decode_attention` — no
    # per-layer per-step gather copy (docs/serving.md "Fused paged decode").
    kv_paged_attention: str = "gather"
    # mesh layout for the per-slot cache (a parallel.sharding.KVCacheSharding,
    # hashable so the frozen config stays hashable): heads sharded on the
    # serving mesh's model axis, slots optionally on data. None everywhere but
    # the mesh-sharded serving engine.
    kv_cache_sharding: Any = None
    # fp8 projections (reference TE convert_model role): a DelayedScalingRecipe
    # switches every block Dense to ops/fp8.Fp8Dense (delayed-scaling fp8
    # matmuls; scaling state rides the mutable fp8_meta collection)
    fp8_recipe: Any = None

    def cache_contract(self):
        """Keys and values only, every head its own (`kv_cache.CacheContract`).
        Every leaf but the LayerNorms' reaches its product through a cast to
        ``dtype`` (`nn.Dense` promotes kernel and bias, `wte`/`wpe` are cast
        before the gather and the tied head), so the engine may hold them in
        ``dtype``. That is declared for the plain `nn.Dense` path only: with
        an fp8 recipe nothing is, and the params are served as given."""
        from .kv_cache import CacheContract

        return CacheContract(kv_heads=self.n_head, head_dim=self.n_embd // self.n_head,
                             param_rules=gpt2_sharding_rules,
                             compute_dtype_params=(None if self.fp8_recipe is not None
                                                   else _outside_layer_norms))

    @classmethod
    def small(cls, **kw) -> "GPT2Config":
        return cls(**{**dict(n_embd=768, n_layer=12, n_head=12), **kw})

    @classmethod
    def medium(cls, **kw) -> "GPT2Config":
        return cls(**{**dict(n_embd=1024, n_layer=24, n_head=16), **kw})

    @classmethod
    def large(cls, **kw) -> "GPT2Config":
        return cls(**{**dict(n_embd=1280, n_layer=36, n_head=20), **kw})

    @classmethod
    def tiny(cls, **kw) -> "GPT2Config":
        """Test-sized config."""
        return cls(**{**dict(vocab_size=256, n_positions=128, n_embd=64, n_layer=2, n_head=2), **kw})


_LAYER_NORMS = frozenset({"ln_1", "ln_2", "ln_f"})


def _outside_layer_norms(path: tuple[str, ...]) -> bool:
    """`CacheContract.compute_dtype_params`: a leaf of no LayerNorm (those
    compute in float32 from their float32 scale and bias)."""
    return _LAYER_NORMS.isdisjoint(path)


def _dense(cfg: GPT2Config, features: int, name: str) -> nn.Module:
    """Block projection factory: plain Dense, or Fp8Dense when the config
    carries an fp8 recipe (ops/fp8.convert_dense_to_fp8 — the reference
    `transformer_engine.py:26-82` convert_model role; same param names, so
    checkpoints stay compatible)."""
    from ..ops.fp8 import convert_dense_to_fp8

    return convert_dense_to_fp8(cfg.fp8_recipe)(
        features, dtype=cfg.dtype, param_dtype=cfg.param_dtype, name=name
    )


def _fused_paged_attention(q, k_pool, v_pool, block_tables, lengths, scale_pools, sharding):
    """`paged_decode_attention`, per shard when the engine serves on a mesh.

    XLA cannot partition a Pallas call (a bare one inside a multi-device jit
    fails to lower), and the kernel is row- and head-local: slot rows split
    over the mesh's data axis, heads over its model axis, the block pool is
    whole on the block dim and split on its folded ``kv_heads * head_dim`` dim
    (a shard is whole heads: heads are contiguous runs of ``head_dim``, and the
    model axis divides them). ``sharding`` is the engine's paged
    `KVCacheSharding` — the layouts the arrays already carry — so the
    shard_map moves nothing."""
    from ..ops.flash_attention import paged_decode_attention

    scales = scale_pools if scale_pools is not None else ()

    def kernel(q, k_pool, v_pool, tables, lengths, *scales):
        k_sp, v_sp = scales if scales else (None, None)
        return paged_decode_attention(
            q, k_pool, v_pool, tables, lengths, k_scale_pool=k_sp, v_scale_pool=v_sp
        )

    if sharding is None:
        return kernel(q, k_pool, v_pool, block_tables, lengths, *scales)
    from jax import shard_map

    rows, _, heads, _ = sharding.gathered.spec
    pool, scale = sharding.kv.spec, sharding.scale.spec
    return shard_map(
        kernel,
        mesh=sharding.kv.mesh,
        in_specs=(P(rows, heads, None), pool, pool, P(rows, None), P(rows))
        + (scale,) * len(scales),
        out_specs=P(rows, heads, None),
        check_vma=False,
    )(q, k_pool, v_pool, block_tables, lengths, *scales)


class SelfAttention(nn.Module):
    config: GPT2Config

    @nn.compact
    def __call__(self, x: jax.Array, deterministic: bool = True, decode: bool = False,
                 cache_write_mask: jax.Array | None = None,
                 block_tables: jax.Array | None = None,
                 cache_write_len: jax.Array | None = None) -> jax.Array:
        cfg = self.config
        b, s, e = x.shape
        head_dim = e // cfg.n_head
        qkv = _dense(cfg, 3 * e, "qkv")(x)
        q, k, v = jnp.split(qkv, 3, axis=-1)
        q = q.reshape(b, s, cfg.n_head, head_dim)
        k = k.reshape(b, s, cfg.n_head, head_dim)
        v = v.reshape(b, s, cfg.n_head, head_dim)
        if (decode and cfg.kv_cache_paged and cfg.kv_paged_attention == "fused"
                and s == 1 and cache_write_len is None):
            # fused paged attention: write the new token at the frontier
            # (pool leaves only — no gathered view), then the Pallas kernel
            # walks the block table in place. The frontier semantics are
            # identical to the gather branch below: the query at cursor idx
            # attends positions <= idx, i.e. a valid span of idx + 1.
            # The kernel is single-query, so multi-token verify segments
            # (s > 1 / cache_write_len — speculative decoding) fall through
            # to the gather branch; s is static, so this costs nothing on the
            # one-token fast path.
            from .kv_cache import paged_decode_write

            k_pool, v_pool, idx, is_init, scale_pools = paged_decode_write(
                self, k, v, cfg.kv_num_blocks, cfg.kv_block_tokens,
                block_tables, kv_cache_dtype=cfg.kv_cache_dtype,
                write_mask=cache_write_mask,
                sharding=cfg.kv_cache_sharding,
            )
            if is_init:
                out = _fused_paged_attention(
                    q[:, 0], k_pool, v_pool, block_tables, idx + 1,
                    scale_pools, cfg.kv_cache_sharding,
                )[:, None]  # [b, 1, n_head, head_dim]
            else:
                # abstract shape-init trace: no pool yet, plain causal
                out = attention(q, k_pool, v_pool, causal=True,
                                implementation="xla")
        elif decode and cfg.kv_cache_paged:
            # paged KV: the cache collection holds a shared block pool, each
            # row attends through its block table (models/kv_cache.py)
            from .kv_cache import paged_decode_update

            k_all, v_all, idx, is_init = paged_decode_update(
                self, k, v, cfg.kv_num_blocks, cfg.kv_block_tokens,
                block_tables, kv_cache_dtype=cfg.kv_cache_dtype,
                write_mask=cache_write_mask,
                write_len=cache_write_len, sharding=cfg.kv_cache_sharding,
            )
            if is_init:
                # same frontier mask as the per-slot path: the gathered view
                # lays position p at index p, and everything past a row's
                # cursor — pad offsets in its frontier block, unallocated
                # table entries — is masked out before softmax, so stale pool
                # contents contribute exactly zero
                span = block_tables.shape[1] * cfg.kv_block_tokens
                q_pos = idx[:, None, None] + jnp.arange(s)[None, :, None]
                kv_pos = jnp.arange(span)[None, None, :]
                mask = (kv_pos <= q_pos)[:, None]  # [b, 1, s, span]
                out = attention(q, k_all, v_all, causal=False, mask=mask,
                                implementation="xla")
            else:
                out = attention(q, k_all, v_all, causal=True, implementation="xla")
        elif decode:
            # autoregressive KV cache (flax decode idiom): fixed n_positions-long
            # buffers, new keys/values written at the running index; optional
            # int8 storage (models/kv_cache.py)
            from .kv_cache import decode_cache_update

            max_len = cfg.n_positions
            k_all, v_all, idx, is_init = decode_cache_update(
                self, k, v, max_len, kv_cache_dtype=cfg.kv_cache_dtype,
                per_slot=cfg.kv_cache_per_slot, write_mask=cache_write_mask,
                write_len=cache_write_len, sharding=cfg.kv_cache_sharding,
            )
            if is_init:
                if cfg.kv_cache_per_slot:
                    # idx is [b]: row i's query j (global pos idx[i]+j) may
                    # attend its own cache slots <= idx[i]+j
                    q_pos = idx[:, None, None] + jnp.arange(s)[None, :, None]
                    kv_pos = jnp.arange(max_len)[None, None, :]
                    mask = (kv_pos <= q_pos)[:, None]  # [b, 1, s, max_len]
                else:
                    # query i (global pos idx+i) may attend cache slots <= idx+i
                    q_pos = idx + jnp.arange(s)[:, None]
                    kv_pos = jnp.arange(max_len)[None, :]
                    mask = kv_pos <= q_pos  # [s, max_len]
                out = attention(q, k_all, v_all, causal=False, mask=mask, implementation="xla")
            else:
                out = attention(q, k_all, v_all, causal=True, implementation="xla")
        elif cfg.attention_impl == "ring":
            # sequence-parallel exact attention over the mesh's ring axis
            from ..parallel.ring_attention import ring_attention_sharded
            from ..state import AcceleratorState

            out = ring_attention_sharded(q, k, v, AcceleratorState().mesh, causal=True)
        else:
            out = attention(q, k, v, causal=True, implementation=cfg.attention_impl)
        out = out.reshape(b, s, e)
        out = _dense(cfg, e, "proj")(out)
        if cfg.dropout > 0.0 and not deterministic:
            out = nn.Dropout(cfg.dropout)(out, deterministic=False)
        return out


class MLP(nn.Module):
    config: GPT2Config

    @nn.compact
    def __call__(self, x: jax.Array, deterministic: bool = True) -> jax.Array:
        cfg = self.config
        hidden = cfg.mlp_ratio * cfg.n_embd
        x = _dense(cfg, hidden, "up")(x)
        x = nn.gelu(x, approximate=True)
        x = _dense(cfg, cfg.n_embd, "down")(x)
        if cfg.dropout > 0.0 and not deterministic:
            x = nn.Dropout(cfg.dropout)(x, deterministic=False)
        return x


class Block(nn.Module):
    config: GPT2Config

    @nn.compact
    def __call__(self, x: jax.Array, deterministic: bool = True, decode: bool = False,
                 cache_write_mask: jax.Array | None = None,
                 block_tables: jax.Array | None = None,
                 cache_write_len: jax.Array | None = None) -> jax.Array:
        cfg = self.config
        # pre-norm transformer; LN statistics in fp32
        h = nn.LayerNorm(epsilon=cfg.layer_norm_epsilon, dtype=jnp.float32, param_dtype=cfg.param_dtype, name="ln_1")(x)
        x = x + SelfAttention(cfg, name="attn")(h.astype(cfg.dtype), deterministic, decode, cache_write_mask, block_tables, cache_write_len)
        h = nn.LayerNorm(epsilon=cfg.layer_norm_epsilon, dtype=jnp.float32, param_dtype=cfg.param_dtype, name="ln_2")(x)
        x = x + MLP(cfg, name="mlp")(h.astype(cfg.dtype), deterministic)
        return x


class GPT2LMHead(nn.Module):
    """Decoder-only LM. Returns logits [batch, seq, vocab] in fp32."""

    config: GPT2Config

    @nn.compact
    def __call__(
        self,
        input_ids: jax.Array,
        deterministic: bool = True,
        decode: bool = False,
        position_offset: jax.Array | int = 0,
        return_hidden: bool = False,
        cache_write_mask: jax.Array | None = None,
        block_tables: jax.Array | None = None,
        cache_write_len: jax.Array | None = None,
    ) -> jax.Array:
        cfg = self.config
        b, s = input_ids.shape
        wte = self.param(
            "wte", nn.initializers.normal(0.02), (cfg.vocab_size, cfg.n_embd), cfg.param_dtype
        )
        wpe = self.param(
            "wpe", nn.initializers.normal(0.01), (cfg.n_positions, cfg.n_embd), cfg.param_dtype
        )
        positions = jnp.asarray(position_offset)
        if positions.ndim == 0:
            positions = positions + jnp.arange(s)  # [s], shared by the batch
            pos_emb = wpe.astype(cfg.dtype)[positions][None]
        else:
            # [b]-vector offsets: every row sits at its own sequence position
            # (per-slot decode, serving/engine.py)
            positions = positions[:, None] + jnp.arange(s)  # [b, s]
            pos_emb = wpe.astype(cfg.dtype)[positions]
        x = wte.astype(cfg.dtype)[input_ids] + pos_emb

        block = Block
        if cfg.remat:
            from ..utils.remat import remat_block

            block = remat_block(Block, cfg.remat_policy, static_argnums=(2, 3))
        if cfg.scan_layers:
            x, _ = nn.scan(
                lambda mdl, carry, _: (mdl(carry, deterministic, decode, cache_write_mask, block_tables, cache_write_len), None),
                # fp8_meta (per-layer delayed-scaling state) stacks on the same
                # leading layer axis as the params
                variable_axes={"params": 0, "fp8_meta": 0},
                split_rngs={"params": True},
                length=cfg.n_layer,
                metadata_params={nn.PARTITION_NAME: "layers"},
            )(block(cfg, name="blocks"), x, None)
        else:
            for i in range(cfg.n_layer):
                x = block(cfg, name=f"block_{i}")(x, deterministic, decode, cache_write_mask, block_tables, cache_write_len)

        x = nn.LayerNorm(epsilon=cfg.layer_norm_epsilon, dtype=jnp.float32, param_dtype=cfg.param_dtype, name="ln_f")(x)
        if return_hidden:
            # pre-head hidden states for the fused (chunked) LM loss, which
            # applies the tied head inside the loss without ever materializing
            # the full [batch, seq, vocab] fp32 logits tensor
            return x.astype(cfg.dtype)
        # tied LM head: logits through the embedding matrix, fp32 accumulation
        logits = jnp.einsum("bse,ve->bsv", x.astype(cfg.dtype), wte.astype(cfg.dtype),
                            preferred_element_type=jnp.float32)
        return logits

    def init_params(self, rng: jax.Array, batch: int = 2, seq: int | None = None) -> Any:
        seq = seq or min(self.config.n_positions, 128)
        dummy = jnp.zeros((batch, seq), dtype=jnp.int32)
        variables = self.init(rng, dummy)
        if len(variables) > 1:
            # mutable collections (fp8_meta scaling state) ride along; prepare()
            # splits them into PreparedModel.extra_state
            return dict(variables)
        return variables["params"]


def gpt2_sharding_rules() -> ShardingRules:
    """Megatron-style TP as pure sharding annotations (SURVEY.md §2.4 TP row):
    qkv/up are column-parallel (shard output dim), proj/down row-parallel (shard
    input dim), embeddings vocab-sharded. XLA inserts the two all-reduces per
    block that Megatron hand-codes."""
    return ShardingRules(
        rules=[
            (r".*attn/qkv/kernel", P(None, "tensor")),
            (r".*attn/proj/kernel", P("tensor", None)),
            (r".*mlp/up/kernel", P(None, "tensor")),
            (r".*mlp/down/kernel", P("tensor", None)),
            # vocab dim over tensor AND fsdp, embed dim replicated: folding fsdp
            # into the embed dim makes the wte-grad scatter reshard the whole
            # (batch, seq, embed) activation gradient into a transposed layout
            # (involuntary full remat); vocab-only sharding needs just the
            # token indices replicated, which they already are.
            (r".*wte", P(("tensor", "fsdp"), None)),
            (r".*wpe", P(None, None)),
            (r".*(qkv|up)/bias", P("tensor")),
        ]
    )


def cross_entropy_loss(logits: jax.Array, labels: jax.Array, ignore_index: int = -100) -> jax.Array:
    """Token-level CE with masking, fp32 accumulation."""
    mask = labels != ignore_index
    safe_labels = jnp.where(mask, labels, 0)
    logprobs = jax.nn.log_softmax(logits.astype(jnp.float32), axis=-1)
    ll = jnp.take_along_axis(logprobs, safe_labels[..., None], axis=-1)[..., 0]
    return -(ll * mask).sum() / jnp.maximum(mask.sum(), 1)


def _next_token_labels(batch) -> jax.Array:
    """Labels for causal LM: explicit ``labels`` or input_ids shifted left with
    the trailing position ignored."""
    labels = batch.get("labels")
    if labels is None:
        labels = jnp.pad(batch["input_ids"][:, 1:], ((0, 0), (0, 1)), constant_values=-100)
    return labels


def lm_loss_fn(model, batch) -> jax.Array:
    """Next-token LM loss usable directly with Accelerator.backward/make_train_step."""
    logits = model(batch["input_ids"])
    return cross_entropy_loss(logits, _next_token_labels(batch))


def chunked_cross_entropy(
    hidden: jax.Array,  # [N, e] pre-head activations (compute dtype)
    wte: jax.Array,  # [V, e] tied embedding (compute dtype)
    labels: jax.Array,  # [N] int labels, ignore_index masked
    ignore_index: int = -100,
    chunk: int = 1024,
) -> jax.Array:
    """Head+CE fused over row chunks: the [N, V] fp32 logits never exist in HBM
    — each [chunk, V] tile is produced, reduced to (logsumexp, label-logit) and
    discarded; `jax.checkpoint` recomputes tiles in the backward. Cuts the LM
    head's HBM traffic by ~V/2 per pass at the cost of one recomputed matmul.
    (Role of reference AMP'd CE; the fusion itself is TPU-native design.)"""
    n, e = hidden.shape
    pad = (-n) % chunk
    if pad:
        hidden = jnp.pad(hidden, ((0, pad), (0, 0)))
        labels = jnp.pad(labels, (0, pad), constant_values=ignore_index)
    nc = hidden.shape[0] // chunk
    hidden = hidden.reshape(nc, chunk, e)
    labels = labels.reshape(nc, chunk)

    @jax.checkpoint
    def one_chunk(x_c, lab_c):
        mask = lab_c != ignore_index
        safe = jnp.where(mask, lab_c, 0)
        logits = jax.lax.dot_general(
            x_c, wte, (((1,), (1,)), ((), ())), preferred_element_type=jnp.float32
        )  # [chunk, V] — lives only inside this chunk
        lse = jax.nn.logsumexp(logits, axis=-1)
        ll = jnp.take_along_axis(logits, safe[:, None], axis=-1)[:, 0]
        return ((lse - ll) * mask).sum(), mask.sum()

    def body(carry, xs):
        loss, cnt = one_chunk(*xs)
        return (carry[0] + loss, carry[1] + cnt), None

    (total, count), _ = jax.lax.scan(body, (jnp.zeros((), jnp.float32), jnp.zeros((), jnp.int32)), (hidden, labels))
    return total / jnp.maximum(count, 1)


def lm_loss_fn_fused(model, batch, chunk: int = 1024) -> jax.Array:
    """Next-token LM loss with the head fused into chunked CE (no full-logits
    materialization). Drop-in for `lm_loss_fn` on GPT2LMHead models."""
    hidden = model(batch["input_ids"], return_hidden=True)
    labels = _next_token_labels(batch)
    b, s, e = hidden.shape
    wte = model.params["wte"].astype(hidden.dtype)
    return chunked_cross_entropy(hidden.reshape(b * s, e), wte, labels.reshape(b * s), chunk=chunk)


def lm_loss_fn_pallas(model, batch, block_r: int | None = None, block_v: int | None = None) -> jax.Array:
    """Next-token LM loss through the Pallas fused head+CE kernel
    (`ops/fused_ce.py`): logits tiles live only in VMEM, row chunks run as
    parallel grid cells (no scan serialization). Drop-in for `lm_loss_fn`.
    Block sizes default from ``ACCELERATE_TPU_FUSED_CE_BLOCK_R/_V`` (sweepable;
    larger models need smaller tiles — the dw kernel's VMEM footprint scales
    with block_v*e)."""
    from ..ops.fused_ce import fused_cross_entropy
    from ..utils.environment import parse_int_from_env

    if block_r is None:
        block_r = parse_int_from_env("ACCELERATE_TPU_FUSED_CE_BLOCK_R", 512)
    if block_v is None:
        block_v = parse_int_from_env("ACCELERATE_TPU_FUSED_CE_BLOCK_V", 1024)
    hidden = model(batch["input_ids"], return_hidden=True)
    labels = _next_token_labels(batch)
    b, s, e = hidden.shape
    wte = model.params["wte"].astype(hidden.dtype)
    return fused_cross_entropy(
        hidden.reshape(b * s, e), wte, labels.reshape(b * s), block_r=block_r, block_v=block_v
    )


def gpt2_blockwise(config: GPT2Config):
    """Decompose GPT-2 into sequential blocks for offload-streaming inference
    (`big_modeling.BlockwiseModel`): embed -> block_i... -> head. Use with
    `gpt2_blockwise_state_dict` to regroup a params tree into per-block subtrees."""
    from ..big_modeling import BlockwiseModel

    def embed_fn(p, input_ids):
        s = input_ids.shape[1]
        return p["wte"].astype(config.dtype)[input_ids] + p["wpe"].astype(config.dtype)[None, :s]

    def make_block_fn(i):
        def block_fn(p, x):
            return Block(config, name=f"block_{i}").apply({"params": p}, x)

        return block_fn

    def head_fn(p, x):
        x = nn.LayerNorm(epsilon=config.layer_norm_epsilon, dtype=jnp.float32).apply(
            {"params": p["ln_f"]}, x
        )
        return jnp.einsum(
            "bse,ve->bsv", x.astype(config.dtype), p["wte"].astype(config.dtype),
            preferred_element_type=jnp.float32,
        )

    fns = [("embed", embed_fn)]
    fns += [(f"block_{i}", make_block_fn(i)) for i in range(config.n_layer)]
    fns += [("head", head_fn)]
    return BlockwiseModel(block_fns=fns)


def gpt2_pipeline_parts(config: GPT2Config, params: dict, num_stages: int):
    """Decompose GPT-2 for PIPELINE TRAINING (`Accelerator.prepare_pipeline` /
    `make_pipeline_train_step`): returns ``(stage_fn, per_stage_params, pre,
    post)`` where each homogeneous stage runs ``n_layer / num_stages``
    transformer blocks, the embedding runs replicated before the pipeline and
    ln_f + LM head after it (reference role: Megatron-LM pp>1 model
    partitioning, `utils/megatron_lm.py`).

    Tying note: the LM head starts as a copy of ``wte`` but trains UNTIED —
    pre/post are separate parameter groups and the Megatron first/last-stage
    embedding-gradient all-reduce is not implemented. Fine-tunes from tied
    checkpoints start tied and may drift apart.
    """
    if config.n_layer % num_stages:
        raise ValueError(
            f"n_layer {config.n_layer} must divide into {num_stages} pipeline stages"
        )
    if "params" in params and "wte" not in params:
        raise ValueError(
            "gpt2_pipeline_parts takes the bare params tree; this looks like a "
            "variables dict with extra collections (fp8_recipe models carry "
            "fp8_meta state that the pipeline decomposition does not thread)."
        )
    if "block_0" not in params:
        raise ValueError(
            "gpt2_pipeline_parts needs the per-layer 'block_i' param layout; "
            "scan_layers=True stacks layers under 'blocks' — initialize the "
            "model with scan_layers=False for pipeline decomposition (the "
            "GPipe schedule is itself the scan over layers)."
        )
    per = config.n_layer // num_stages

    def pre_fn(p, input_ids):
        s = input_ids.shape[1]
        return (
            p["wte"].astype(config.dtype)[input_ids]
            + p["wpe"].astype(config.dtype)[None, :s]
        )

    def stage_fn(p, x):
        for j in range(per):
            x = Block(config, name=f"layer_{j}").apply({"params": p[f"layer_{j}"]}, x)
        return x

    def post_fn(p, y):
        y = nn.LayerNorm(
            epsilon=config.layer_norm_epsilon, dtype=jnp.float32,
            param_dtype=config.param_dtype,
        ).apply({"params": p["ln_f"]}, y)
        return jnp.einsum(
            "bse,ve->bsv", y.astype(config.dtype), p["lm_head"].astype(config.dtype),
            preferred_element_type=jnp.float32,
        )

    per_stage = [
        {f"layer_{j}": params[f"block_{s * per + j}"] for j in range(per)}
        for s in range(num_stages)
    ]
    pre_p = {"wte": params["wte"], "wpe": params["wpe"]}
    # explicit copy: the head is its own buffer from step 0 (aliasing wte would
    # both double-donate one buffer in the fused step and hide the untying)
    post_p = {"ln_f": params["ln_f"], "lm_head": jnp.array(params["wte"])}
    return stage_fn, per_stage, (pre_fn, pre_p), (post_fn, post_p)


def pipeline_lm_loss(logits: jax.Array, input_ids: jax.Array) -> jax.Array:
    """Per-microbatch next-token CE for `make_pipeline_train_step(loss_fn=...)`
    (the `lm_loss_fn` contract, shifted inside the loss so the pipeline's
    targets are just the input ids)."""
    import optax

    return optax.softmax_cross_entropy_with_integer_labels(
        logits[:, :-1].astype(jnp.float32), input_ids[:, 1:]
    ).mean()


def gpt2_blockwise_state_dict(params: dict) -> dict:
    """Regroup a GPT2LMHead param tree into the blockwise layout (the tied wte
    appears in both embed and head groups, like the reference's tied-weight map)."""
    out = {"embed": {"wte": params["wte"], "wpe": params["wpe"]}}
    for k in params:
        if k.startswith("block_"):
            out[k] = params[k]
    out["head"] = {"ln_f": params["ln_f"], "wte": params["wte"]}
    return out


def params_from_hf_gpt2(hf_state_dict: dict, config: GPT2Config) -> dict:
    """Map HuggingFace transformers GPT-2 torch weights into this layout.

    HF GPT-2 uses Conv1D (weights already [in, out]); layer names are remapped.
    (Capability parity with the reference's checkpoint ingestion,
    `utils/modeling.py:1611` load_checkpoint_in_model.)
    """

    def _np(t):
        return t.detach().cpu().numpy() if hasattr(t, "detach") else np.asarray(t)

    p: dict[str, Any] = {
        "wte": _np(hf_state_dict["wte.weight"]),
        "wpe": _np(hf_state_dict["wpe.weight"]),
        "ln_f": {"scale": _np(hf_state_dict["ln_f.weight"]), "bias": _np(hf_state_dict["ln_f.bias"])},
    }
    for i in range(config.n_layer):
        hf = f"h.{i}."
        p[f"block_{i}"] = {
            "ln_1": {"scale": _np(hf_state_dict[hf + "ln_1.weight"]), "bias": _np(hf_state_dict[hf + "ln_1.bias"])},
            "ln_2": {"scale": _np(hf_state_dict[hf + "ln_2.weight"]), "bias": _np(hf_state_dict[hf + "ln_2.bias"])},
            "attn": {
                "qkv": {"kernel": _np(hf_state_dict[hf + "attn.c_attn.weight"]), "bias": _np(hf_state_dict[hf + "attn.c_attn.bias"])},
                "proj": {"kernel": _np(hf_state_dict[hf + "attn.c_proj.weight"]), "bias": _np(hf_state_dict[hf + "attn.c_proj.bias"])},
            },
            "mlp": {
                "up": {"kernel": _np(hf_state_dict[hf + "mlp.c_fc.weight"]), "bias": _np(hf_state_dict[hf + "mlp.c_fc.bias"])},
                "down": {"kernel": _np(hf_state_dict[hf + "mlp.c_proj.weight"]), "bias": _np(hf_state_dict[hf + "mlp.c_proj.bias"])},
            },
        }
    return p
