"""Compile the Pallas kernels for a TPU v5e without one.

The CPU suite runs every kernel under the Pallas interpreter, which lowers a
`pallas_call` to ordinary XLA ops: it can hide a kernel Mosaic rejects (VMEM
limits, layouts) and a call XLA cannot partition over a mesh. libtpu can
compile for a chip that is not there (`jax.experimental.topologies`), so this
file compiles each kernel at gpt2-medium shapes with ``interpret=False`` — on
one device and inside a four-device jit. It checks that the program builds;
only a chip run (`chip_smoke.py`) checks what it computes. Marked slow, all
but the guards at the end: the paged pool's layout (two compiles, about 3 s),
the latent pool and the two latent-attention models' scopes, the delta
rule's in-place update, the expert layer's grouped products, the sampling
tail's conditional, the paged and flash kernels' lowered programs pinned to
their hashes before sinks and split value widths existed (a few seconds), and
MiMo-V2's decode step and admit at the benchmark cell's shapes (about a
minute).
"""

import functools
import hashlib
import re

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

slow = pytest.mark.slow

B, S, H, D = 8, 1024, 16, 64  # gpt2-medium attention at the bench shape
E, V = 1024, 50257
BLOCK_TOKENS = 16


@pytest.fixture(scope="module")
def topology():
    from jax.experimental import topologies

    try:
        return topologies.get_topology_desc(platform="tpu", topology_name="v5e:2x2")
    except Exception as e:  # no libtpu, or one that cannot describe a v5e
        pytest.skip(f"cannot build a v5e:2x2 topology here: {type(e).__name__}: {e}")


@pytest.fixture
def compiled_kernels(monkeypatch):
    """Kernels default to interpret mode off-TPU; the host here is a CPU."""
    from accelerate_tpu.utils import environment

    monkeypatch.setattr(environment, "on_tpu_platform", lambda: True)


def _one_device(topology):
    return NamedSharding(Mesh(np.array(topology.devices[:1]), ("x",)), P())


def _compile(fn, *args):
    return jax.jit(fn).lower(*args).compile()


def _sds(shape, dtype, sharding):
    return jax.ShapeDtypeStruct(shape, dtype, sharding=sharding)


def _paged_args(heads, sharding_for, quant, *, kv_heads=None, head_dim=D, rows=B, positions=S):
    kv_heads = kv_heads or heads
    bps = positions // BLOCK_TOKENS
    blocks = rows * bps
    pool_dtype = jnp.int8 if quant else jnp.bfloat16
    pool = _sds((blocks, BLOCK_TOKENS, kv_heads * head_dim), pool_dtype, sharding_for("pool"))
    args = [
        _sds((rows, heads, head_dim), jnp.bfloat16, sharding_for("q")), pool, pool,
        _sds((rows, bps), jnp.int32, sharding_for("tables")),
        _sds((rows,), jnp.int32, sharding_for("lengths")),
    ]
    if quant:
        args += [_sds((blocks, BLOCK_TOKENS, kv_heads), jnp.float32, sharding_for("scale"))] * 2
    return args


# ------------------------------------------------------------------ one device
@slow
@pytest.mark.parametrize("window", [None, 256])
def test_flash_fwd_bwd_one_device(topology, window):
    from accelerate_tpu.ops.flash_attention import flash_attention

    s = _one_device(topology)
    qkv = [_sds((B, S, H, D), jnp.bfloat16, s)] * 3

    def loss(q, k, v):
        out = flash_attention(q, k, v, causal=True, window=window, interpret=False)
        return out.astype(jnp.float32).sum()

    _compile(jax.grad(loss, argnums=(0, 1, 2)), *qkv)


@slow
def test_fused_ce_fwd_bwd_one_device(topology):
    from accelerate_tpu.ops.fused_ce import fused_cross_entropy

    s = _one_device(topology)
    loss = functools.partial(fused_cross_entropy, interpret=False)
    _compile(
        jax.grad(loss, argnums=(0, 1)),
        _sds((B * S, E), jnp.bfloat16, s), _sds((V, E), jnp.bfloat16, s),
        _sds((B * S,), jnp.int32, s),
    )


@pytest.mark.parametrize("shape", [
    *(pytest.param(dict(heads=heads), id=name, marks=slow)
      for name, heads in (("small", 12), ("medium", 16), ("large", 20))),
    # not slow (the body compiles in a second or two): the Qwen3-Next cell's
    # layer, 16 query heads on 2 key/value heads of 256 over 128 rows of 160
    # table blocks, and a span the span-wide kernel refused at construction
    pytest.param(dict(heads=16, kv_heads=2, head_dim=256, rows=128, positions=2560),
                 id="qwen3-next"),
    pytest.param(dict(heads=16, rows=8, positions=16384), id="16384-positions"),
])
@pytest.mark.parametrize("quant", [False, True])
def test_paged_decode_one_device(topology, shape, quant):
    """The call asks for the VMEM its chunk buffers need and Mosaic takes the
    body: copies from the HBM pools by block, the folded query's lane-offset
    stores, bf16 products (an int8 pool's chunk dequantised in VMEM first)."""
    from accelerate_tpu.ops.flash_attention import paged_decode_attention

    s = _one_device(topology)

    def decode(q, k, v, tables, lengths, *scales):
        k_sp, v_sp = scales if scales else (None, None)
        return paged_decode_attention(
            q, k, v, tables, lengths, k_scale_pool=k_sp, v_scale_pool=v_sp, interpret=False
        )

    shape = dict(shape)
    compiled = _compile(decode, *_paged_args(shape.pop("heads"), lambda _: s, quant, **shape))
    assert compiled.as_text().count('custom_call_target="tpu_custom_call"') == 1


@slow
@pytest.mark.parametrize("shape", [(1024, 3072), (1024, 4096), (4096, 1024)])  # qkv, up, down
def test_nf4_matmul_one_device(topology, shape):
    """The per-tile scale block must be a whole trailing axis: Mosaic rejects
    a (.., bk, 2) block cut out of a (.., K, N/128) array."""
    from accelerate_tpu.ops.nf4_matmul import nf4_matmul
    from accelerate_tpu.utils.quantization import QuantizationConfig, quantize

    weight = np.random.default_rng(0).normal(size=shape).astype(np.float32)
    qt = quantize(weight, QuantizationConfig(
        load_in_4bit=True, quant_type="nf4", compute_dtype=jnp.bfloat16))
    _compile(lambda x: nf4_matmul(x, qt, interpret=False),
             _sds((B, shape[0]), jnp.bfloat16, _one_device(topology)))


# ------------------------------------------------- inside a four-device jit
@pytest.fixture
def data_mesh(topology, monkeypatch):
    """The training mesh over the four chips (data=4), installed as the live
    `AcceleratorState` mesh the kernel call sites read."""
    from accelerate_tpu.state import AcceleratorState
    from accelerate_tpu.utils.constants import MESH_AXIS_NAMES

    shape = tuple(4 if name == "data" else 1 for name in MESH_AXIS_NAMES)
    mesh = Mesh(np.array(topology.devices).reshape(shape), MESH_AXIS_NAMES)
    monkeypatch.setitem(AcceleratorState._shared_state, "mesh", mesh)
    return mesh


@slow
def test_flash_fwd_bwd_four_devices(data_mesh, compiled_kernels):
    from accelerate_tpu.ops.attention import attention

    s = NamedSharding(data_mesh, P("data", None, None, None))
    qkv = [_sds((4 * B, S, H, D), jnp.bfloat16, s)] * 3

    def loss(q, k, v):
        out = attention(q, k, v, causal=True, implementation="flash")
        return out.astype(jnp.float32).sum()

    _compile(jax.grad(loss, argnums=(0, 1, 2)), *qkv)


@slow
def test_fused_ce_fwd_bwd_four_devices(data_mesh, compiled_kernels):
    from accelerate_tpu.ops.fused_ce import fused_cross_entropy

    rows = NamedSharding(data_mesh, P("data"))
    _compile(
        jax.grad(fused_cross_entropy, argnums=(0, 1)),
        _sds((4 * B * S, E), jnp.bfloat16, NamedSharding(data_mesh, P("data", None))),
        _sds((V, E), jnp.bfloat16, NamedSharding(data_mesh, P())),
        _sds((4 * B * S,), jnp.int32, rows),
    )


@slow
@pytest.mark.parametrize("quant", [False, True])
def test_paged_decode_on_serving_mesh(topology, compiled_kernels, quant):
    """The engine's ``mesh=(2, 2)``: slot rows over data, heads over tensor."""
    from accelerate_tpu.models.gpt2 import _fused_paged_attention
    from accelerate_tpu.parallel.mesh import serving_mesh
    from accelerate_tpu.parallel.sharding import block_table_sharding, kv_cache_sharding

    mesh = serving_mesh(data=2, model=2, devices=list(topology.devices))
    sharding = kv_cache_sharding(mesh, slots=B, paged=True)
    named = {
        "q": NamedSharding(mesh, P("data", "tensor", None)),
        "pool": sharding.kv,
        "scale": sharding.scale,
        "tables": block_table_sharding(mesh, slots=B),
        "lengths": sharding.index,
    }

    def decode(q, k, v, tables, lengths, *scales):
        return _fused_paged_attention(q, k, v, tables, lengths, scales or None, sharding)

    compiled = _compile(decode, *_paged_args(H, named.__getitem__, quant))
    assert compiled.output_shardings.spec == P("data", "tensor", None)


# --------------------------------------- the paged pool's layout (not slow)
# The serving cell's pool leaf: gpt2-large, 2048 blocks of 16 tokens, 20 heads
# of 64 folded into the last dim, 32 slot rows with 64 table blocks each.
POOL_BLOCKS, LARGE_HEADS, ROWS, ROW_BLOCKS = 2048, 20, 32, 64
POOL_SHAPE = (POOL_BLOCKS, BLOCK_TOKENS, LARGE_HEADS * D)


def _assert_pool_is_not_relaid(compiled, pool_in, pool_out):
    """A donated pool leaf goes in, is updated in place and comes out: the
    same row-major layout on both sides, no pool-shaped ``copy`` between them
    and no pool-sized temporary. With ``kv_heads, head_dim`` as trailing dims
    (20 x 64 pads to 32 x 128) each leaf cost two whole-pool copies a program."""
    row_major = tuple(range(len(POOL_SHAPE)))
    for fmt in (*pool_in, *pool_out):
        assert fmt.layout.major_to_minor == row_major, fmt
    assert [f.layout for f in pool_in] == [f.layout for f in pool_out]
    dims = ",".join(map(str, POOL_SHAPE))
    copies = re.findall(rf"= \w+\[{dims}\]\S* copy\(", compiled.as_text())
    assert not copies, copies
    leaf_bytes = int(np.prod(POOL_SHAPE)) * 2
    assert compiled.memory_analysis().temp_size_in_bytes < leaf_bytes


def test_decode_write_and_kernel_leave_the_pool_in_place(topology):
    from accelerate_tpu.models.kv_cache import _paged_frontier_write
    from accelerate_tpu.ops.flash_attention import paged_decode_attention

    s = _one_device(topology)
    pool = _sds(POOL_SHAPE, jnp.bfloat16, s)
    new = _sds((ROWS, 1, LARGE_HEADS, D), jnp.bfloat16, s)

    def decode(k_pool, v_pool, q, k, v, tables, idx):
        (k_pool, v_pool), _ = _paged_frontier_write(
            (k_pool, v_pool), (k, v), idx, jnp.ones((ROWS,), bool), None,
            POOL_BLOCKS, BLOCK_TOKENS, tables)
        out = paged_decode_attention(q, k_pool, v_pool, tables, idx + 1, interpret=False)
        return k_pool, v_pool, out

    compiled = jax.jit(decode, donate_argnums=(0, 1)).lower(
        pool, pool, _sds((ROWS, LARGE_HEADS, D), jnp.bfloat16, s), new, new,
        _sds((ROWS, ROW_BLOCKS), jnp.int32, s), _sds((ROWS,), jnp.int32, s),
    ).compile()
    _assert_pool_is_not_relaid(
        compiled, compiled.input_formats[0][:2], compiled.output_formats[:2])


@pytest.mark.parametrize("bucket", [128, S])  # a prompt bucket; the n_positions rows the engine's admits prefill
def test_admit_scatter_leaves_the_pool_in_place(topology, bucket):
    from accelerate_tpu.models.kv_cache import scatter_rows_to_blocks

    s = _one_device(topology)
    nb = 4

    def tree(kv, index):
        return {"cached_key": kv, "cached_value": kv, "cache_index": index}

    cache = tree(_sds(POOL_SHAPE, jnp.bfloat16, s), _sds((ROWS,), jnp.int32, s))
    fresh = tree(_sds((nb, bucket, LARGE_HEADS, D), jnp.bfloat16, s), _sds((nb,), jnp.int32, s))

    def admit(cache, fresh, slots, dest_blocks, prompt_lens):
        return scatter_rows_to_blocks(cache, fresh, slots, dest_blocks, prompt_lens, BLOCK_TOKENS)

    compiled = jax.jit(admit, donate_argnums=(0,)).lower(
        cache, fresh, _sds((nb,), jnp.int32, s),
        _sds((nb, bucket // BLOCK_TOKENS), jnp.int32, s), _sds((nb,), jnp.int32, s),
    ).compile()
    kv = ("cached_key", "cached_value")
    _assert_pool_is_not_relaid(
        compiled,
        [compiled.input_formats[0][0][name] for name in kv],
        [compiled.output_formats[name] for name in kv],
    )


# ----------------------------------------------- the latent pool (not slow)
# The Kimi K2 cell's layer: 64 query heads against one shared row a token of
# 576 lanes stored as 640, the value its first 512; 256 slot rows of 288 table
# blocks over a pool of 43,008 blocks.
LATENT_ROWS, LATENT_HEADS, LATENT_LANES, LATENT_VALUE = 256, 64, 640, 512
LATENT_BLOCKS, LATENT_ROW_BLOCKS = 43008, 288


def test_paged_decode_latent_pool_one_device(topology):
    """Mosaic takes the body with no value pool: one copy a block, the value
    sliced from the key chunk in VMEM at a lane-tile edge, the output 512 wide;
    the frontier write beside it leaves the donated pool in place."""
    from accelerate_tpu.models.kv_cache import _paged_frontier_write
    from accelerate_tpu.ops.flash_attention import paged_decode_attention

    s = _one_device(topology)
    shape = (LATENT_BLOCKS, BLOCK_TOKENS, LATENT_LANES)

    def decode(pool, q, row, tables, idx):
        (pool,), _ = _paged_frontier_write(
            (pool,), (row,), idx, jnp.ones((LATENT_ROWS,), bool), None,
            LATENT_BLOCKS, BLOCK_TOKENS, tables)
        out = paged_decode_attention(q, pool, None, tables, idx + 1, value_dim=LATENT_VALUE,
                                     interpret=False)
        return pool, out

    compiled = jax.jit(decode, donate_argnums=(0,)).lower(
        _sds(shape, jnp.bfloat16, s), _sds((LATENT_ROWS, LATENT_HEADS, LATENT_LANES), jnp.bfloat16, s),
        _sds((LATENT_ROWS, 1, 1, LATENT_LANES), jnp.bfloat16, s),
        _sds((LATENT_ROWS, LATENT_ROW_BLOCKS), jnp.int32, s), _sds((LATENT_ROWS,), jnp.int32, s),
    ).compile()
    text = compiled.as_text()
    assert text.count('custom_call_target="tpu_custom_call"') == 1
    assert f"bf16[{LATENT_ROWS},{LATENT_HEADS},{LATENT_VALUE}]" in text
    dims = ",".join(map(str, shape))
    assert not re.findall(rf"= \w+\[{dims}\]\S* copy\(", text)
    assert compiled.memory_analysis().temp_size_in_bytes < int(np.prod(shape)) * 2 // 8


def test_latent_row_of_576_lanes_is_refused_by_the_compiler(topology):
    """Why the row is stored padded to 640: a bfloat16 array whose minor
    dimension is 576 is tiled to 640 lanes in HBM in any case, and Mosaic
    copies no slice of it that is not a whole number of lane tiles."""
    from accelerate_tpu.ops.flash_attention import paged_decode_attention

    s = _one_device(topology)
    with pytest.raises(Exception, match="aligned to tiling"):
        _compile(lambda q, pool, tables, lengths: paged_decode_attention(
            q, pool, None, tables, lengths, value_dim=LATENT_VALUE, interpret=False),
            _sds((8, LATENT_HEADS, 576), jnp.bfloat16, s), _sds((256, BLOCK_TOKENS, 576), jnp.bfloat16, s),
            _sds((8, 32), jnp.int32, s), _sds((8,), jnp.int32, s))


@pytest.mark.parametrize("program", ["step", "admit"])
def test_kimi_k2_scopes_and_kernel_name(topology, compiled_kernels, program):
    """The model's `jax.named_scope`s reach the compiled HLO's `op_name`, and
    the decode step's fused kernel keeps the flax scope's name (`%attn.N`, what
    the benchmark's readers look for) while the absorbing products around it
    carry `mla_absorb`; no program holds a `ragged_dot`. Published attention
    widths, everything else small."""
    import dataclasses

    from accelerate_tpu.models.kimi_k2 import KimiK2Config, KimiK2ForCausalLM

    s = _one_device(topology)
    cfg = KimiK2Config(
        vocab_size=512, hidden_size=256, intermediate_size=512, moe_intermediate_size=128,
        num_hidden_layers=2, num_attention_heads=8, q_lora_rank=128, n_routed_experts=16,
        experts_held=4, num_experts_per_tok=4, n_positions=2048, kv_cache_per_slot=True)
    rows, bucket = 8, 1024
    if program == "step":
        cfg = dataclasses.replace(cfg, kv_cache_paged=True, kv_num_blocks=256,
                                  kv_paged_attention="fused")
    module = KimiK2ForCausalLM(cfg)
    tables = jnp.zeros((rows, cfg.n_positions // 16), jnp.int32)
    extra = dict(block_tables=tables) if program == "step" else {}
    shapes = jax.eval_shape(lambda: module.init(
        jax.random.key(0), jnp.zeros((rows, 1), jnp.int32), decode=True, **extra))
    place = lambda tree: jax.tree.map(lambda x: _sds(x.shape, x.dtype, s), tree)  # noqa: E731

    def run(params, cache, ids, offsets, tables):
        kw = dict(position_offset=offsets, block_tables=tables) if program == "step" else \
            dict(position_offset=0)
        return module.apply({"params": params, "cache": cache}, ids, decode=True,
                            mutable=["cache", "counters"], **kw)

    ids = _sds((rows, 1 if program == "step" else bucket), jnp.int32, s)
    hlo = jax.jit(run, donate_argnums=(1,)).lower(
        place(shapes["params"]), place(shapes["cache"]), ids, _sds((rows,), jnp.int32, s),
        _sds(tables.shape, jnp.int32, s)).compile().as_text()
    scopes = {"step": ("mla_absorb", "moe_router", "moe_experts", "dense_mlp"),
              "admit": ("mla_prefill", "moe_router", "moe_experts", "dense_mlp")}[program]
    for scope in scopes:
        assert re.search(rf'op_name="[^"]*/{scope}/', hlo), scope
    kernels = re.findall(r'%([\w\-]+)\.\d+ = [^\n]*custom_call_target="tpu_custom_call"', hlo)
    # the one expert layer's two grouped products, decode step and admit alike (`%gmm.N`)
    assert kernels.count("gmm") == 2 and "ragged-dot" not in hlo, kernels
    if program == "step":
        assert "attn" in kernels and "mla_absorb" not in kernels, kernels
        assert "mla_prefill" not in hlo
    else:
        assert "mla_prefill" in kernels and "mla_absorb" not in hlo, kernels  # the flash kernel, plain form


@pytest.mark.parametrize("program", ["step", "admit"])
def test_ling3_scopes_and_kernel_name(topology, compiled_kernels, program):
    """Ling 3.0 flash's scopes reach the compiled HLO's `op_name`: the
    per-channel delta rule's (`kda_step` in a decode step, `kda_prefill` in an
    admit) beside the latent layer's, the router's, the experts' and the dense
    MLP's; the fused kernel of its one latent layer keeps the flax scope's
    name (`%attn.N`); the decode step's delta rule reads and writes the whole
    per-slot state under that shape, which is how the benchmark's reader finds
    it (since PR 36 a Pallas call a KDA layer, `%kda_step.N`). Published head
    sizes, everything else small; one period of six layers."""
    import dataclasses

    from accelerate_tpu.models.ling3 import Ling3Config, Ling3ForCausalLM

    s = _one_device(topology)
    cfg = Ling3Config(
        vocab_size=512, hidden_size=256, intermediate_size=512, moe_intermediate_size=128,
        moe_shared_expert_intermediate_size=128, num_hidden_layers=6, num_attention_heads=4,
        num_experts=16, experts_held=4, num_experts_per_tok=4, n_group=4, topk_group=2,
        n_positions=2048, kv_cache_per_slot=True)
    rows, bucket = 8, 1024
    if program == "step":
        cfg = dataclasses.replace(cfg, kv_cache_paged=True, kv_num_blocks=256,
                                  kv_paged_attention="fused")
    module = Ling3ForCausalLM(cfg)
    tables = jnp.zeros((rows, cfg.n_positions // 16), jnp.int32)
    extra = dict(block_tables=tables) if program == "step" else {}
    shapes = jax.eval_shape(lambda: module.init(
        jax.random.key(0), jnp.zeros((rows, 1), jnp.int32), decode=True, **extra))
    place = lambda tree: jax.tree.map(lambda x: _sds(x.shape, x.dtype, s), tree)  # noqa: E731

    def run(params, cache, ids, lens, tables):
        kw = dict(position_offset=lens, block_tables=tables) if program == "step" else \
            dict(position_offset=0, cache_write_len=lens)
        return module.apply({"params": params, "cache": cache}, ids, decode=True,
                            mutable=["cache", "counters"], **kw)

    ids = _sds((rows, 1 if program == "step" else bucket), jnp.int32, s)
    hlo = jax.jit(run, donate_argnums=(1,)).lower(
        place(shapes["params"]), place(shapes["cache"]), ids, _sds((rows,), jnp.int32, s),
        _sds(tables.shape, jnp.int32, s)).compile().as_text()
    scopes = {"step": ("kda_step", "mla_absorb", "moe_router", "moe_experts", "dense_mlp"),
              "admit": ("kda_prefill", "mla_prefill", "moe_router", "moe_experts", "dense_mlp")}[program]
    for scope in scopes:
        assert re.search(rf'op_name="[^"]*/{scope}/', hlo), scope
    kernels = re.findall(r'%([\w\-]+)\.\d+ = [^\n]*custom_call_target="tpu_custom_call"', hlo)
    assert "gmm" in kernels and "ragged-dot" not in hlo, kernels
    if program == "step":
        assert kernels.count("attn") == 1 and "kda_prefill" not in hlo and "mla_prefill" not in hlo
        assert kernels.count("kda_step") == 5  # the rule's Pallas pass, one a KDA layer
        assert f"f32[{rows},4,128,128]" in hlo  # the whole per-slot state, by shape
    else:
        assert "mla_prefill" in kernels and "kda_step" not in hlo and "mla_absorb" not in hlo


@pytest.mark.parametrize("program", ["step", "admit"])
def test_k_exaone_programs_at_the_cells_shapes(topology, compiled_kernels, program):
    """K-EXAONE's decode step (128 slots, the full layer's pool of 15,360
    blocks of 64) and an 8,192-token admit, at the benchmark cell's published
    widths and cut (five layers, 16 held experts, 19,200 vocabulary rows): the
    scopes `window_attn` and `global_attn` reach the compiled HLO's `op_name`;
    the decode step runs the fused kernel on the full layer's pool under the
    flax scope's name (`%attn.N`) and on each sliding layer's rings, read as
    a pool of one block a slot, under `window_attn` (its line names the rings'
    shape `[128, 128, 1024]`, which the benchmark's readers look for); the admit runs the flash kernel with the window
    on the four sliding layers and causal on the full one; both run the eight
    grouped products, the decode step's in no loop and the admit's inside the
    loop over windows of held picks. The programs with the weights, the pool
    and the rings fit the chip, and the admit's workspace is no larger than
    when its FFNs ran in token chunks. About 20 s."""
    import json
    import os

    from accelerate_tpu.models.k_exaone import KExaoneConfig, KExaoneForCausalLM

    here = os.path.join(os.path.dirname(os.path.dirname(os.path.abspath(__file__))), "benchmarks", "chip")
    with open(os.path.join(here, "configs", "k-exaone-236b-a23b.json")) as f:
        cfg = json.load(f)
    with open(os.path.join(here, "workloads", "k-exaone-236b-a23b.serve.long128.json")) as f:
        engine = json.load(f)["engine"]
    s = _one_device(topology)
    rows, bt, blocks = engine["max_concurrency"], engine["paged_kv"]["block_tokens"], engine["paged_kv"]["num_blocks"]
    n = cfg["num_hidden_layers"]
    model_cfg = KExaoneConfig(
        vocab_size=cfg["vocab_size"], num_hidden_layers=n, layer_types=tuple(cfg["layer_types"][:n]),
        mlp_layer_types=tuple(cfg["mlp_layer_types"][:n]), experts_held=cfg["num_experts"],
        n_positions=cfg["n_positions"], kv_cache_per_slot=True, kv_cache_paged=True, kv_num_blocks=blocks,
        kv_block_tokens=bt, kv_paged_attention="fused")
    module = KExaoneForCausalLM(model_cfg)
    b, t = (rows, 1) if program == "step" else (engine["admit_batch"], max(engine["prompt_buckets"]))
    tables = jnp.zeros((b, model_cfg.n_positions // bt), jnp.int32)
    shapes = jax.eval_shape(lambda: module.init(
        jax.random.key(0), jnp.zeros((b, 1), jnp.int32), decode=True, block_tables=tables))
    place = lambda tree: jax.tree.map(lambda x: _sds(x.shape, x.dtype, s), tree)  # noqa: E731

    def run(params, cache, ids, lens, tables):
        kw = dict(position_offset=lens) if program == "step" else dict(position_offset=0, cache_write_len=lens)
        return module.apply({"params": params, "cache": cache}, ids, decode=True, block_tables=tables,
                            mutable=["cache", "counters"], **kw)

    compiled = jax.jit(run, donate_argnums=(1,)).lower(
        place(shapes["params"]), place(shapes["cache"]), _sds((b, t), jnp.int32, s),
        _sds((b,), jnp.int32, s), _sds(tables.shape, jnp.int32, s)).compile()
    hlo = compiled.as_text()
    for scope in ("window_attn", "global_attn", "moe_router", "moe_experts", "dense_mlp"):
        assert re.search(rf'op_name="[^"]*/{scope}/', hlo), scope
    kernels = re.findall(r'%([A-Za-z_\-]+)(?:\.\d+)? = [^\n]*custom_call_target="tpu_custom_call"', hlo)
    assert kernels.count("gmm") == 8 and "ragged-dot" not in hlo, kernels
    # a decode step's 1,024 picks fit one pass, an admit's 65,536 do not:
    # its grouped products run in the loop over windows of held picks
    comps, _ = _hlo_computations(hlo)
    looped = _reached(comps, [body for lines in comps.values() for line in lines
                              for body in re.findall(r" while\(.*body=%?([\w.\-]+)", line)])
    gmm = re.compile(r'%gmm(\.\d+)? = [^\n]*custom_call_target="tpu_custom_call"')
    in_loops = sum(bool(gmm.search(line)) for comp in looped for line in comps[comp])
    assert in_loops == (0 if program == "step" else 8), in_loops
    if program == "step":
        # the full layer's pool and, on each sliding layer, its rings read as a pool of one block a slot
        assert kernels.count("attn") == 1 and kernels.count("window_attn") == 4 and len(kernels) == 13, kernels
        assert re.search(rf"%window_attn(\.\d+)? = [^\n]*bf16\[{rows},128,1024\]", hlo)  # by the rings' shape
    else:
        assert kernels.count("window_attn") == 4 and kernels.count("global_attn") == 1, kernels
    memory = compiled.memory_analysis()
    rings = 4 * 2 * rows * 128 * 1024 * 2  # the full engine's rings beside the admit rows' own
    held = memory.argument_size_in_bytes + memory.temp_size_in_bytes + memory.output_size_in_bytes \
        - memory.alias_size_in_bytes + (rings if program == "admit" else 0)
    assert held < 14.5e9, held / 1e9
    if program == "admit":
        # the admit's workspace when every FFN ran a 1,024-token chunk at a
        # time, compiled here the same way: the windows hold no more
        assert memory.temp_size_in_bytes <= 1_500_432_384, memory.temp_size_in_bytes


@pytest.mark.parametrize("slots, per_channel", [pytest.param(256, True, id="ling3-kda"),
                                                pytest.param(128, False, id="qwen3-next-delta")])
def test_delta_step_kernel_updates_the_state_in_place(topology, compiled_kernels, slots, per_channel):
    """The delta rule's decode update at both delta-rule cells' state (32
    heads of 128 x 128), donated as the engine donates its cache: one Pallas
    call, named after the rule's scope and not `attn` (the paged kernel's
    readers count those), whose line names the whole state (the benchmark's
    reader finds it so), with the state's buffer aliased from argument to
    result and no copy of it. About a second."""
    from accelerate_tpu.ops.gated_delta import gated_delta_step

    s = _one_device(topology)
    h, d, f32 = 32, 128, jnp.float32
    args = [_sds((slots, h, d, d), f32, s)] + [_sds((slots, h, d), f32, s)] * 3 + [
        _sds((slots, h, d) if per_channel else (slots, h), f32, s), _sds((slots, h), f32, s)]
    hlo = jax.jit(gated_delta_step, donate_argnums=(0,)).lower(*args).compile().as_text()
    kernels = re.findall(r'%([\w\-]+)\.\d+ = [^\n]*custom_call_target="tpu_custom_call"', hlo)
    assert kernels == ["kda_step" if per_channel else "delta_step"], kernels
    state = re.escape(f"f32[{slots},{h},{d},{d}]")
    assert re.search(rf"%{kernels[0]}\.\d+ = \({state}", hlo)
    assert not re.search(rf"= {state}\S* copy(-start)?\(", hlo)
    assert re.search(r"input_output_alias=\{ \{0\}: \(0, ", hlo)

# ------------------------------------- the expert layer's grouped products
# (hidden, expert width, experts held, picks a token)
QWEN3_NEXT_EXPERTS = (2048, 512, 256, 10)  # 256 held of 512 experts
KIMI_K2_EXPERTS = (7168, 2048, 12, 8)  # 12 held of 384 experts
LING3_EXPERTS = (2560, 768, 128, 8)  # 128 held of 512 experts


@pytest.mark.parametrize("tokens, experts", [
    pytest.param(512, QWEN3_NEXT_EXPERTS, id="qwen3next-admit-512"),
    pytest.param(1536, QWEN3_NEXT_EXPERTS, id="qwen3next-admit-1536"),
    pytest.param(128, QWEN3_NEXT_EXPERTS, id="qwen3next-decode-128"),
    pytest.param(256, KIMI_K2_EXPERTS, id="kimik2-decode-256"),
    pytest.param(24, QWEN3_NEXT_EXPERTS, id="rows-240-padded-to-the-tile"),
    pytest.param(256, LING3_EXPERTS, id="ling3-decode-256"),
    pytest.param(1536, LING3_EXPERTS, id="ling3-admit-1536"),
])
def test_held_experts_grouped_product(topology, compiled_kernels, tokens, experts):
    """One expert-parallel chip's share at the three MoE cells' widths: a
    prompt bucket's picks, a decode step's (1,280 rows at 3 a group; Kimi
    K2's 2,048 of which 64 are held; Ling 3.0 flash's 2,048 at 4 a group) and
    a row count the row tile does not divide all compile to the Pallas
    grouped matmul inside its 16 MiB of VMEM, twice, and to no `ragged_dot`
    (Ling 3.0 flash's gate and up in tiles of 2,560 x 768)."""
    from accelerate_tpu.ops.moe import held_experts_mlp

    s = _one_device(topology)
    hidden, width, held, k = experts
    hlo = _compile(
        lambda x, p, idx, wgu, wd: held_experts_mlp(x, p, idx, wgu, wd)[0],
        _sds((tokens, hidden), jnp.bfloat16, s), _sds((tokens, k), jnp.float32, s),
        _sds((tokens, k), jnp.int32, s), _sds((held, hidden, 2 * width), jnp.bfloat16, s),
        _sds((held, width, hidden), jnp.bfloat16, s)).as_text()
    kernels = re.findall(r"%(\S+) = f32\[(\d+),\d+\]\S* custom-call\(.*tpu_custom_call", hlo)
    rows = -(-tokens * k // 128) * 128
    assert [(name.partition(".")[0], int(r)) for name, r in kernels] == [("gmm", rows)] * 2, kernels
    assert "ragged-dot" not in hlo  # the instruction's name; a caller's may ride in the stack frames


# ------------------------------------------- the sampling tail's conditional
# `engine._sample_rows` branches once for the batch, so a decode step or an
# admit program whose rows are all greedy runs no vocabulary-wide sort. A
# `cond` moved back under the `vmap` lowers to a select that runs every side:
# these fail then, where otherwise only the benchmark would show it.
TAIL_VOCAB = 1000  # a sort this wide compiles in a second; 8,192 wide takes 20


def _hlo_computations(hlo):
    """``({computation: its instruction lines}, the ENTRY computation's name)``."""
    comps, entry, name = {}, None, None
    for line in hlo.splitlines():
        head = re.match(r"(ENTRY )?%?([\w.\-]+) \(.*\) -> .* \{$", line)
        if head:
            name = head.group(2)
            comps[name] = []
            entry = name if head.group(1) else entry
        elif line.startswith("}"):
            name = None
        elif name is not None:
            comps[name].append(line)
    return comps, entry


def _reached(comps, roots):
    """The computations reached from ``roots`` through fusions, calls and loop
    bodies, never into a ``conditional``'s branches."""
    todo, seen = list(roots), set()
    while todo:
        comp = todo.pop()
        if comp in seen:
            continue
        seen.add(comp)
        for line in comps[comp]:
            if " conditional(" not in line:
                todo += re.findall(r"(?:calls|to_apply|body|condition)=%?([\w.\-]+)", line)
    return seen


def _always_run_wide_sorts(hlo, width):
    """``(sorts, branch_sorts)``: the ``sort`` instructions over ``[..., width]``
    in computations the program runs on every call (reached from ``ENTRY``
    through fusions, calls and loop bodies), and those reached only through a
    ``conditional``'s branch computations."""
    comps, entry = _hlo_computations(hlo)
    wide = re.compile(rf"\[(\d+,)*{width}\]\S* sort\(")  # the result, or a tuple result's last part
    seen = _reached(comps, [entry])
    always = [line.strip()[:160] for comp in seen for line in comps[comp] if wide.search(line)]
    elsewhere = [line.strip()[:160] for comp in comps.keys() - seen
                 for line in comps[comp] if wide.search(line)]
    return always, elsewhere


@pytest.fixture(scope="module")
def serving_programs():
    """The paged engine's decode step and admit program with the shapes of
    the arguments it dispatched them with, from one tiny request served here
    on the CPU (kernels interpreted; the tests below trace them again)."""
    from accelerate_tpu.models.gpt2 import GPT2Config, GPT2LMHead
    from accelerate_tpu.serving import Request, SamplingParams, ServingEngine

    module = GPT2LMHead(GPT2Config(vocab_size=TAIL_VOCAB, n_positions=256, n_embd=2 * D,
                                   n_layer=1, n_head=2, dtype=jnp.bfloat16))
    engine = ServingEngine(module, module.init_params(jax.random.key(0)), max_concurrency=8,
                           prompt_buckets=(128,), paged_kv=True, paged_attention="fused")
    programs, dispatch = {}, engine._dispatch

    def record(key, fn, *args):
        programs.setdefault(key.partition("@")[0].partition("[")[0],
                            (fn.__wrapped__, jax.tree.map(lambda a: (a.shape, a.dtype), args)))
        return dispatch(key, fn, *args)

    engine._dispatch = record
    engine.run([Request([1, 2, 3], SamplingParams(max_new_tokens=2))])
    return programs


@pytest.mark.parametrize("kind", ["step", "admit"])
def test_sampling_tail_sorts_only_inside_a_conditional(topology, compiled_kernels,
                                                       serving_programs, kind):
    s = _one_device(topology)
    fn, shapes = serving_programs[kind]
    args = jax.tree.map(lambda shape_dtype: _sds(*shape_dtype, s), shapes,
                        is_leaf=lambda x: isinstance(x, tuple) and isinstance(x[0], tuple))
    hlo = jax.jit(fn, donate_argnums=(0,)).lower(*args).compile().as_text()
    # the decode step holds the fused kernel, compiled and not interpreted
    assert ('custom_call_target="tpu_custom_call"' in hlo) is (kind == "step")
    always, in_branches = _always_run_wide_sorts(hlo, TAIL_VOCAB)
    assert not always, always
    assert in_branches, "the top-k branch's sort is gone: this guard sees nothing"


def test_sampling_tail_guard_sees_a_sort_under_the_vmap(topology):
    """What the guard above is for: the per-row body under `vmap`, as every
    step ran it before `_sample_rows`, puts the sort where every call runs it."""
    from accelerate_tpu.serving.engine import _sample_slot

    s = _one_device(topology)
    hlo = _compile(jax.vmap(_sample_slot), _sds((8, TAIL_VOCAB), jnp.float32, s),
                   _sds((8,), jax.random.key(0).dtype, s), _sds((8,), jnp.float32, s),
                   _sds((8,), jnp.int32, s)).as_text()
    always, in_branches = _always_run_wide_sorts(hlo, TAIL_VOCAB)
    assert always and not in_branches


# -------------------------------- the paged and flash kernels lower as before
# sha256 prefixes of the kernels' lowered programs for a described TPU v5e,
# each computed on the tree before sinks and split value widths existed: with
# neither option given, the kernels every served model runs lower the same
PINNED = {
    "band_grad": "9fb6f046804dd8bb",
    "band_window": "43f4a3b7f3d9d497",
    "paged_gpt2_large": "cd00de363e88bf5e",
    "paged_int8": "cc66989fa8cf83be",
    "paged_latent": "bdcf12fd9d19403f",
    "paged_qwen3_next": "47cbb86f27169583",
    "paged_ring": "284508f39d6a5353",
    "rect_causal": "34ce4c8c5a23f663",
    "rect_pad": "7e3c54a8a4edb856",
    "xla_window": "97187fb96f343135",
}


def _kernel_programs(s):
    """{name: (function, argument shapes)} of the pinned programs."""
    from accelerate_tpu.ops.attention import attention
    from accelerate_tpu.ops.flash_attention import flash_attention, paged_decode_attention

    bf, i32 = jnp.bfloat16, jnp.int32

    def sds(shape, dtype=bf):
        return jax.ShapeDtypeStruct(shape, dtype, sharding=s)

    def paged(q, k, v, t, n, *scales):
        kw = dict(k_scale_pool=scales[0], v_scale_pool=scales[1]) if scales else {}
        return paged_decode_attention(q, k, v, t, n, interpret=False, **kw)

    def flash(**kw):
        return lambda q, k, v: flash_attention(q, k, v, causal=True, interpret=False, **kw)

    def band_loss(q, k, v):
        return flash(window=256)(q, k, v).astype(jnp.float32).sum()

    return {
        "paged_gpt2_large": (paged, sds((32, 20, 64)), sds((2048, 16, 1280)), sds((2048, 16, 1280)),
                             sds((32, 64), i32), sds((32,), i32)),
        "paged_int8": (paged, sds((8, 16, 64)), sds((512, 16, 1024), jnp.int8), sds((512, 16, 1024), jnp.int8),
                       sds((8, 64), i32), sds((8,), i32), sds((512, 16, 16), jnp.float32),
                       sds((512, 16, 16), jnp.float32)),
        "paged_qwen3_next": (paged, sds((128, 16, 256)), sds((2048, 16, 512)), sds((2048, 16, 512)),
                             sds((128, 160), i32), sds((128,), i32)),
        "paged_latent": (lambda q, k, t, n: paged_decode_attention(q, k, None, t, n, value_dim=512, interpret=False),
                         sds((256, 64, 640)), sds((1024, 64, 640)), sds((256, 32), i32), sds((256,), i32)),
        "paged_ring": (paged, sds((128, 64, 128)), sds((128, 128, 1024)), sds((128, 128, 1024)),
                       sds((128, 1), i32), sds((128,), i32)),
        "band_window": (flash(window=128), sds((1, 4096, 64, 128)), sds((1, 4096, 8, 128)), sds((1, 4096, 8, 128))),
        "rect_causal": (flash(), sds((1, 4096, 64, 128)), sds((1, 4096, 8, 128)), sds((1, 4096, 8, 128))),
        "rect_pad": (flash(), sds((2, 1024, 4, 80)), sds((2, 1024, 4, 80)), sds((2, 1024, 4, 80))),
        "band_grad": (jax.grad(band_loss, argnums=(0, 1, 2)), *[sds((2, 1024, 16, 64))] * 3),
        "xla_window": (lambda q, k, v: attention(q, k, v, causal=True, window=128, implementation="xla"),
                       sds((1, 512, 8, 64)), sds((1, 512, 2, 64)), sds((1, 512, 2, 64))),
    }


@pytest.mark.parametrize("name", sorted(PINNED))
def test_kernels_without_the_new_options_lower_as_before(topology, name):
    """The paged decode kernel (GPT-2's heads, an int8 pool, Qwen3-Next's
    grouped heads, Kimi K2's latent pool, K-EXAONE's rings), the band and
    rectangular flash kernels (a window, causal, a padded head, a band's
    backward) and the XLA path, lowered for the v5e with no sinks and values
    as wide as keys: the programs hash as they did before the options."""
    limit = jax.config.jax_traceback_in_locations_limit
    jax.config.update("jax_traceback_in_locations_limit", 0)
    try:
        fn, *args = _kernel_programs(_one_device(topology))[name]
        text = jax.jit(fn).lower(*args).as_text()
    finally:
        jax.config.update("jax_traceback_in_locations_limit", limit)
    text = re.sub(r"module @[\w.]+", "module", text, count=1)  # the jitted function's own name
    assert hashlib.sha256(text.encode()).hexdigest()[:16] == PINNED[name]

@pytest.mark.parametrize("program", ["step", "admit"])
def test_mimo_v2_programs_at_the_cells_shapes(topology, compiled_kernels, program):
    """MiMo-V2's decode step (128 slots, the full layers' pool of the
    workload's blocks of 64) and an 8,192-token admit, at the benchmark cell's
    published widths and cut (seven layers, 16 held experts, 19,072
    vocabulary rows): the decode step runs the fused kernel on the two full
    layers' pool (`%attn.N`, keys 4 x 192 and values 4 x 128 apart) and, with
    the sinks, on each sliding layer's rings (`%window_attn.N`, whose line
    names the rings `[128, 128, 1536]` and `[128, 128, 1024]` the readers look
    for); the admit runs the band kernel with the sinks on the five sliding
    layers (its line names the queries `[1, 64, 8192, 192]`) and causal flash
    attention on the two full ones; both run the twelve grouped products. The
    weights, the pool and the rings with the admit's workspace beside them
    fit the chip. About a minute."""
    import json
    import os

    from accelerate_tpu.models.mimo_v2 import MiMoV2Config, MiMoV2ForCausalLM

    here = os.path.join(os.path.dirname(os.path.dirname(os.path.abspath(__file__))), "benchmarks", "chip")
    with open(os.path.join(here, "configs", "mimo-v2.5.json")) as f:
        cfg = json.load(f)
    with open(os.path.join(here, "workloads", "mimo-v2.5.serve.mixed128.json")) as f:
        engine = json.load(f)["engine"]
    s = _one_device(topology)
    rows, bt, blocks = engine["max_concurrency"], engine["paged_kv"]["block_tokens"], engine["paged_kv"]["num_blocks"]
    n = cfg["num_hidden_layers"]
    model_cfg = MiMoV2Config(
        vocab_size=cfg["vocab_size"], num_hidden_layers=n, experts_held=cfg["n_routed_experts"],
        n_positions=cfg["n_positions"], kv_cache_per_slot=True, kv_cache_paged=program == "step",
        kv_num_blocks=blocks, kv_block_tokens=bt, kv_paged_attention="fused")
    module = MiMoV2ForCausalLM(model_cfg)
    b, t = (rows, 1) if program == "step" else (engine["admit_batch"], max(engine["prompt_buckets"]))
    tables = jnp.zeros((b, model_cfg.n_positions // bt), jnp.int32)
    shapes = jax.eval_shape(lambda: module.init(
        jax.random.key(0), jnp.zeros((b, 1), jnp.int32), decode=True, block_tables=tables))
    place = lambda tree: jax.tree.map(lambda x: _sds(x.shape, x.dtype, s), tree)  # noqa: E731

    def run(params, cache, ids, lens, tables):
        kw = dict(position_offset=lens, block_tables=tables) if program == "step" \
            else dict(position_offset=0, cache_write_len=lens)
        return module.apply({"params": params, "cache": cache}, ids, decode=True, mutable=["cache", "counters"], **kw)

    compiled = jax.jit(run, donate_argnums=(1,)).lower(
        place(shapes["params"]), place(shapes["cache"]), _sds((b, t), jnp.int32, s),
        _sds((b,), jnp.int32, s), _sds(tables.shape, jnp.int32, s)).compile()
    hlo = compiled.as_text()
    for scope in ("window_attn", "global_attn", "moe_router", "moe_experts", "dense_mlp"):
        assert re.search(rf'op_name="[^"]*/{scope}/', hlo), scope
    kernels = re.findall(r'%([A-Za-z_\-]+)(?:\.\d+)? = [^\n]*custom_call_target="tpu_custom_call"', hlo)
    assert kernels.count("gmm") == 12 and "ragged-dot" not in hlo, kernels
    rings = ("bf16[128,128,1536]", "bf16[128,128,1024]")
    if program == "step":
        assert kernels.count("attn") == 2 and kernels.count("window_attn") == 5 and len(kernels) == 19, kernels
        ring_kernels = re.findall(r"%window_attn(?:\.\d+)? = [^\n]*", hlo)
        assert all(r in line and "f32[64,128]" in line for line in ring_kernels for r in rings)  # and the sinks
        pools = re.findall(r"%attn(?:\.\d+)? = [^\n]*", hlo)
        assert all(f"bf16[{blocks},64,768]" in line and f"bf16[{blocks},64,512]" in line for line in pools)
    else:
        assert kernels.count("window_attn") == 5 and kernels.count("global_attn") == 2, kernels
        band = re.findall(r"%window_attn(?:\.\d+)? = [^\n]*", hlo)
        assert all("bf16[1,64,8192,192]" in line and "bf16[1,64,8192,128]" in line for line in band)
    memory = compiled.memory_analysis()
    held = memory.argument_size_in_bytes + memory.temp_size_in_bytes + memory.output_size_in_bytes \
        - memory.alias_size_in_bytes
    if program == "admit":  # the engine's pool and rings stay beside the admit's rows and workspace
        held += blocks * bt * 2 * 4 * (192 + 128) * 2 + 5 * rows * 128 * 8 * (192 + 128) * 2
    assert held < 14.6e9, held / 1e9
