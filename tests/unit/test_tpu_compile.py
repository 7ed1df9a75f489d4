"""The TPU's own compiler, asked without a TPU.

Interpret-mode parity (test_masked_flash.py, test_paged_attention.py)
cannot see what Mosaic refuses: a select between i1 vectors, a DMA slice
whose lane dim is not 128-aligned. libtpu compiles for a chip that is
described and not attached, so the Pallas kernels of the two main paths
(GPT-2 345M training, paged serving) are compiled here at real widths
for one device of a described ``v5e:2x2``, and once across its four —
nothing runs, a compile that passes is not a chip run.
``chip_smoke.py`` is the run.

The persistent compile cache is off around these: such an executable
can be written to it but not read back without a chip.
"""

import os
import re

os.environ.setdefault("TPU_LOG_DIR", "disabled")   # else logs under /tmp
# a described topology attaches no chip, so several test processes
# (xdist workers) may hold libtpu at once; without this its lockfile
# lets only the first one in
os.environ.setdefault("ALLOW_MULTIPLE_LIBTPU_LOAD", "1")

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.experimental import topologies
from jax.experimental.compilation_cache import compilation_cache
from jax.sharding import (Mesh, NamedSharding, PartitionSpec as P,
                          SingleDeviceSharding)

from deepspeed_tpu.ops.attention import flash
from deepspeed_tpu.ops.attention.flash import flash_attention
from deepspeed_tpu.ops.attention.paged import (paged_decode_attention,
                                               paged_decode_supported)
from deepspeed_tpu.ops.sparse_attention import (
    BigBirdSparsityConfig, BSLongformerSparsityConfig, FixedSparsityConfig,
    VariableSparsityConfig, block_sparse_attention)
from deepspeed_tpu.parallel.pallas_shard import pallas_kernel_mesh

# (batch, heads, seq, head_dim): GPT-2 345M's micro-batch, and the same
# token count at the head width the paged-decode kernel needs
GPT2_345M = (8, 16, 1024, 64)
HEAD_128 = (4, 16, 1024, 128)

_DEVICES = []       # the described v5e:2x2, filled by the fixture below


@pytest.fixture(scope="module", autouse=True)
def _described_v5e():
    """Describe the topology once per module — in a fixture, not at
    import, so that every xdist worker collects the same tests — and
    skip the whole file where it cannot be built. The persistent compile
    cache is off meanwhile."""
    try:
        topo = topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:      # no libtpu in this installation
        pytest.skip(f"cannot describe a v5e:2x2 topology: {e!r}")
    _DEVICES[:] = topo.devices
    prev = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    yield
    jax.config.update("jax_enable_compilation_cache", prev)
    compilation_cache.reset_cache()


def _spec(shape, dtype=jnp.bfloat16):
    return jax.ShapeDtypeStruct(
        shape, dtype, sharding=SingleDeviceSharding(_DEVICES[0]))


def _compile(fn, *specs):
    """Raises what the chip's compiler would raise."""
    return jax.jit(fn).lower(*specs).compile()


def _attention(dropout_rate):
    def fwd(q, k, v, rng):
        return flash_attention(
            q, k, v, causal=True, interpret=False,
            dropout_rate=dropout_rate,
            dropout_rng=rng if dropout_rate else None)

    def bwd(q, k, v, rng):
        return jax.grad(
            lambda *qkv: jnp.sum(fwd(*qkv, rng).astype(jnp.float32)),
            argnums=(0, 1, 2))(q, k, v)
    return {"fwd": fwd, "bwd": bwd}


# the walk tiles PR 33's sweep tried on the chip, at both cells' shapes
SWEEP_BLOCKS = [128, 256, 512]


@pytest.mark.parametrize("block", SWEEP_BLOCKS)
@pytest.mark.parametrize("shape", [GPT2_345M, HEAD_128],
                         ids=["gpt2_345m", "head128"])
@pytest.mark.parametrize("direction", ["fwd", "bwd"])
@pytest.mark.parametrize("dropout_rate", [0.0, 0.1],
                         ids=["nodrop", "dropout"])
def test_masked_flash_causal_compiles(monkeypatch, shape, direction,
                                      dropout_rate, block):
    """The default training attention (the unified masked kernel with a
    causal BlockMask: a loop over the FULL tiles and one over the
    diagonal's, PR 33), at every tile the sweep tried: the parent of
    PR 21 failed every one of these with ``failed to legalize operation
    'arith.select'``."""
    monkeypatch.setattr(flash, "_FORCE_BLOCKS", (block, block))
    qkv = _spec(shape)
    compiled = _compile(_attention(dropout_rate)[direction], qkv, qkv, qkv,
                        _spec((2,), jnp.uint32))
    assert "tpu_custom_call" in compiled.as_text()


def _bslongformer(h, block=128):
    return BSLongformerSparsityConfig(num_heads=h, block=block,
                                      num_sliding_window_blocks=3)


def _bigbird(h, block=128):
    return BigBirdSparsityConfig(
        num_heads=h, block=block, num_random_blocks=1,
        num_sliding_window_blocks=3, num_global_blocks=1)


# id -> (config, sequence, with a key-padding mask)
SPARSE_CASES = {
    # every SparsityConfig family at block 128
    "bslongformer": (_bslongformer, 2048, False),
    "bigbird": (_bigbird, 2048, False),
    "fixed": (lambda h: FixedSparsityConfig(
        num_heads=h, block=128, num_local_blocks=4, num_global_blocks=1),
        2048, False),
    "variable": (lambda h: VariableSparsityConfig(
        num_heads=h, block=128, num_random_blocks=1,
        local_window_blocks=[4], global_block_indices=[0]), 2048, False),
    "per_head": (lambda h: FixedSparsityConfig(
        num_heads=h, block=128, num_local_blocks=4, num_global_blocks=1,
        different_layout_per_head=True, num_different_global_patterns=4),
        2048, False),
    # the kernel's key-mask operand, on a coarsened and on a fine walk
    "bslongformer-kpm": (_bslongformer, 2048, True),
    "bigbird-kpm": (_bigbird, 2048, True),
    # fine blocks under a lane tile (16 is the reference's default): a
    # band coarsens them away, random blocks walk them as they are
    "bslongformer-block16": (lambda h: _bslongformer(h, 16), 2048, False),
    "bslongformer-block64": (lambda h: _bslongformer(h, 64), 2048, False),
    "bigbird-block64": (lambda h: _bigbird(h, 64), 2048, False),
    "bigbird-block16": (lambda h: _bigbird(h, 16), 2048, False),
    # at flash.STREAM_THRESHOLD K/V stream from HBM by DMA
    "bslongformer-stream8k": (_bslongformer, 8192, False),
    "bigbird-stream8k": (_bigbird, 8192, False),
}


# a finding, not a case to keep a second kernel for (PERF.md §7): a walk
# tile under 128 that no band coarsens away compiles forward, and its
# dk/dv pass does not: it slices the (1, 1, S) lse and delta rows on the
# lanes at ``rq * block``
MOSAIC_REFUSES = pytest.mark.xfail(
    strict=True, reason="Mosaic failed to compile TPU kernel: cannot "
    "statically prove that index in dimension 2 is a multiple of 128 "
    "(vector.load of memref<1x1x2048xf32> -> vector<1x1x64xf32>)")
SPARSE_PARAMS = [
    pytest.param(case, direction, id=f"{case}-{direction}",
                 marks=[MOSAIC_REFUSES] if (case, direction) in (
                     ("bigbird-block64", "bwd"),
                     ("bigbird-block16", "bwd")) else [])
    for case in SPARSE_CASES for direction in ("fwd", "bwd")]


@pytest.mark.parametrize("case,direction", SPARSE_PARAMS)
def test_block_sparse_attention_compiles(case, direction):
    """``block_sparse_attention`` on every SparsityConfig family: until
    PR 29 no sparse layout had been compiled for a TPU since PR 1."""
    make_config, seq, with_kpm = SPARSE_CASES[case]
    batch, heads, head_dim = 2, 4, 64
    layout = make_config(heads).make_layout(seq)
    if case == "per_head":
        assert not (layout == layout[:1]).all()

    def fwd(q, k, v, *kpm):
        return block_sparse_attention(
            q, k, v, layout, interpret=False,
            key_padding_mask=kpm[0] if kpm else None)

    def bwd(q, k, v, *kpm):
        return jax.grad(
            lambda *qkv: jnp.sum(fwd(*qkv, *kpm).astype(jnp.float32)),
            argnums=(0, 1, 2))(q, k, v)

    qkv = _spec((batch, heads, seq, head_dim))
    kpm = [_spec((batch, seq), jnp.float32)] if with_kpm else []
    compiled = _compile({"fwd": fwd, "bwd": bwd}[direction], qkv, qkv, qkv,
                        *kpm)
    assert "tpu_custom_call" in compiled.as_text()


@pytest.mark.parametrize("direction", ["fwd", "bwd"])
def test_flash_own_kernels_compile_for_causal_cross_lengths(direction):
    """``flash.py``'s own forward and backward kernels (ring attention's
    chunk kernels) are reached from ``flash_attention`` by a causal call
    with ``sq != sk``, which has no square-block mask."""
    def fwd(q, k, v):
        return flash_attention(q, k, v, causal=True, interpret=False)

    def bwd(q, k, v):
        return jax.grad(
            lambda *qkv: jnp.sum(fwd(*qkv).astype(jnp.float32)),
            argnums=(0, 1, 2))(q, k, v)

    q, kv = _spec((2, 16, 512, 64)), _spec((2, 16, 1024, 64))
    compiled = _compile({"fwd": fwd, "bwd": bwd}[direction], q, kv, kv)
    assert "tpu_custom_call" in compiled.as_text()


def test_flash_attention_under_a_data_mesh_needs_the_shard_wrap():
    """Four chips, batch sharded over ``data`` (the ZeRO-2 step): the
    compiler refuses a bare pallas_call in a GSPMD program — which
    interpret mode on the CPU mesh never shows — and accepts it under
    the engines' ``pallas_kernel_mesh`` context."""
    mesh = Mesh(np.asarray(_DEVICES), ("data",))
    qkv = jax.ShapeDtypeStruct(GPT2_345M, jnp.bfloat16,
                               sharding=NamedSharding(mesh, P("data")))

    def grads(q, k, v):
        return _attention(0.0)["bwd"](q, k, v, None)

    def grads_under_mesh(q, k, v):
        with pallas_kernel_mesh(mesh, batch_axes=("data",)):
            return grads(q, k, v)

    with pytest.raises(NotImplementedError,
                       match="cannot be automatically partitioned"):
        _compile(grads, qkv, qkv, qkv)
    compiled = _compile(grads_under_mesh, qkv, qkv, qkv)
    assert "tpu_custom_call" in compiled.as_text()


# what Mosaic says of a copy whose rows or lanes are not whole tiles (a
# page's, or a run of pages')
REFUSED = "aligned to tiling|divisible by the tiling"


def _paged_decode_specs(head_dim, page_size, pool_dtype, heads=16,
                        kv_heads=16):
    """The kernel's operands over a stacked pool in its row layout,
    ``(layers, num_pages, page_size, kv_heads * head_dim)``."""
    batch, layers, num_pages, pages_per_seq = 8, 2, 128, 16
    pool = _spec((layers, num_pages, page_size, kv_heads * head_dim),
                 pool_dtype)
    specs = [_spec((batch, heads, head_dim)), pool, pool,
             _spec((batch, pages_per_seq), jnp.int32),
             _spec((batch,), jnp.int32)]
    if pool_dtype == jnp.int8:
        # the engine's scale leaves at kv_quant_block 0: one per head
        scale = _spec((layers, num_pages, page_size, kv_heads),
                      jnp.float32)
        specs += [scale, scale]
    return specs


def _paged_decode(q, kpool, vpool, tables, positions, *scales):
    kw = dict(zip(("k_scales", "v_scales"), scales))
    return paged_decode_attention(q, kpool, vpool, tables, positions,
                                  interpret=False, layer=1, **kw)


def test_paged_decode_bf16_head128_compiles():
    compiled = _compile(_paged_decode,
                        *_paged_decode_specs(128, 16, jnp.bfloat16))
    assert "tpu_custom_call" in compiled.as_text()


# the blocks (tokens a loop turn) the walk is compiled at; the shipped
# one is among them (test_paged_attention.py holds it to that)
BLOCKS_COMPILED = [16, 64, 128, 256]


@pytest.mark.parametrize("block_tokens", BLOCKS_COMPILED)
@pytest.mark.parametrize("head_dim,heads,kv_heads", [
    (64, 16, 16), (128, 32, 8), (128, 64, 8)],
    ids=["gpt2_345m", "llama_gqa", "solar_open2_gqa"])
def test_paged_decode_compiles_at_every_block(monkeypatch, head_dim, heads,
                                              kv_heads, block_tokens):
    """The walk's block (tokens a loop turn) is one constant, shipped at
    128 = 8 pages of 16 (ISSUE 38): it and the settings a later PR may
    move it to compile today, at every served geometry (Solar-Open2's 8
    query groups make the widest accumulator)."""
    from deepspeed_tpu.ops.attention import paged
    monkeypatch.setattr(paged, "_BLOCK_TOKENS", block_tokens)
    compiled = _compile(_paged_decode, *_paged_decode_specs(
        head_dim, 16, jnp.bfloat16, heads, kv_heads))
    assert "tpu_custom_call" in compiled.as_text()


def test_sharded_paged_decode_compiles_across_four_chips():
    """The serving mesh's reader (``sharded_paged_decode``: q over heads,
    the pools over their rows, no collectives) wraps the same walk in
    ``shard_map``: the in-order grid and the slot it carries from grid
    step to grid step (ISSUE 38) compile for the four chips, a shard
    holding 2 of llama-sized GQA's 8 kv heads of 128."""
    from deepspeed_tpu.parallel.pallas_shard import sharded_paged_decode
    mesh = Mesh(np.asarray(_DEVICES), ("model",))

    def spec(shape, dtype, *axes):
        return jax.ShapeDtypeStruct(
            shape, dtype, sharding=NamedSharding(mesh, P(*axes)))
    pool = spec((2, 128, 16, 8 * 128), jnp.bfloat16,
                None, None, None, "model")

    def decode(q, kpool, vpool, tables, positions):
        return sharded_paged_decode(q, kpool, vpool, tables, positions,
                                    mesh, interpret=False, layer=1)
    compiled = _compile(decode, spec((8, 32, 128), jnp.bfloat16,
                                     None, "model"), pool, pool,
                        spec((8, 16), jnp.int32), spec((8,), jnp.int32))
    text = compiled.as_text()
    assert "tpu_custom_call" in text
    assert not re.search(r"all-(gather|reduce|to-all)", text)


@pytest.mark.parametrize("head_dim,page_size,pool_dtype,heads,kv_heads", [
    (128, 32, jnp.int8, 16, 16),      # the scale rows: 16 lanes
    (128, 16, jnp.int8, 16, 16),
    (64, 16, jnp.bfloat16, 16, 16),   # GPT-2 345M: rows of 1,024 lanes
    (128, 16, jnp.bfloat16, 16, 16),
    (128, 32, jnp.bfloat16, 16, 16),
    (128, 8, jnp.float32, 16, 16),
    (64, 16, jnp.bfloat16, 25, 25),   # GPT-2 XL: 1,600, not whole tiles
    (128, 16, jnp.bfloat16, 32, 8),   # llama-sized GQA: 8 x 128
    (128, 8, jnp.bfloat16, 16, 16),   # a page of one sublane tile
    (128, 4, jnp.bfloat16, 16, 16),   # and of half of one
    (64, 16, jnp.bfloat16, 1, 1),     # one narrow head: a 64-lane row
], ids=lambda v: getattr(v, "__name__", str(v)))
def test_paged_decode_gate_agrees_with_the_compiler(head_dim, page_size,
                                                    pool_dtype, heads,
                                                    kv_heads):
    """``paged_decode_supported`` is what keeps the serving engine off a
    kernel that cannot compile: it says yes exactly where Mosaic
    compiles the whole-row walk and no exactly where it refuses (ISSUE
    30: the head width no longer decides; the pool row's width does)."""
    ok, why = paged_decode_supported(page_size, head_dim, pool_dtype,
                                     backend="tpu", kv_heads=kv_heads)
    specs = _paged_decode_specs(head_dim, page_size, pool_dtype, heads,
                                kv_heads)
    if ok:
        _compile(_paged_decode, *specs)
    else:
        with pytest.raises(Exception, match=REFUSED):
            _compile(_paged_decode, *specs)
        assert why


# --------------------------------------------------------------------- #
# the latent pool's reader (ISSUE 43)
# --------------------------------------------------------------------- #
def _latent_decode_specs(row_lanes, rows, table_pages, pool_pages,
                         heads=64, layers=5, page_size=16):
    pool = _spec((layers, pool_pages, page_size, row_lanes))
    return (_spec((rows, heads, row_lanes)), pool,
            _spec((rows, table_pages), jnp.int32),
            _spec((rows,), jnp.int32))


def _latent_decode(value_lanes):
    from deepspeed_tpu.ops.attention.paged import latent_decode_attention

    def decode(q, pool, tables, positions):
        return latent_decode_attention(q, pool, tables, positions, 0.13,
                                       value_lanes, interpret=False,
                                       layer=3)
    return decode


@pytest.mark.parametrize("page_size", [16, 64])
@pytest.mark.parametrize("block_tokens", [128, 512, 1024])
def test_latent_decode_compiles_at_the_cells_rows(monkeypatch,
                                                  block_tokens, page_size):
    """ax-k1.serve-reason-saturated's reader: 193 rows of 64 heads
    against ONE pool of 720,896 tokens' rows of 640 lanes over 5 layers
    (pages of 16 as the cell has them, ISSUE 43's, and of 64),
    a table of 6,144 positions, the values the row's first 512 lanes (a
    lane slice of the landed key tile: no second stream), at the shipped
    block of 512 tokens a turn and the ones measured beside it. The pool
    stays in HBM: the compiled call holds no copy of a layer of it."""
    from deepspeed_tpu.ops.attention import paged
    monkeypatch.setattr(paged, "_LATENT_BLOCK_TOKENS", block_tokens)
    compiled = _compile(_latent_decode(512), *_latent_decode_specs(
        640, 193, 6144 // page_size, 720896 // page_size + 1,
        page_size=page_size))
    text = compiled.as_text()
    assert text.count('custom_call_target="tpu_custom_call"') == 1
    assert compiled.memory_analysis().temp_size_in_bytes < 720896 * 640


@pytest.mark.parametrize("arity,block_pages", [("pair", 8), ("latent", 32)])
def test_a_run_of_pages_is_one_copy_at_the_cells_blocks(arity, block_pages):
    """ISSUE 54: where a block's live pages are consecutive ids the
    walk copies them with one descriptor a stream, a walk's last block
    as the binary pieces of its count, from the pool viewed as rows of
    tokens INSIDE the kernel. Both arities at their cells' shapes
    (GPT-2 345M's pair of pools: pages of 16, 8 a block; ax-k1's latent
    pool: 32 a block; bfloat16) hold copies of a page and of 1, 2, 4,
    ... ``block_pages`` pages and nothing else; Mosaic takes the
    dynamic starts of the pieces on both sides of the copy; and the
    view costs no copy of the pool."""
    if arity == "pair":
        fn = _paged_decode
        pool = _spec((24, POOL_PAGES, PAGE, HEADS * HEAD_DIM))
        specs = (_spec((ROWS, HEADS, HEAD_DIM)), pool, pool,
                 _spec((ROWS, TABLE_PAGES), jnp.int32),
                 _spec((ROWS,), jnp.int32))
        layer_bytes = POOL_PAGES * PAGE * HEADS * HEAD_DIM * 2
    else:
        fn = _latent_decode(512)
        specs = _latent_decode_specs(640, 193, 6144 // 16,
                                     720896 // 16 + 1)
        layer_bytes = 720896 * 640 * 2
    text = re.sub(r"\s+", " ", str(jax.make_jaxpr(fn)(*specs)))
    sizes = {}
    for src, rows in re.findall(
            r"dma_start\(p0\) (.+?) -> \w+\[\w+,([^,]+),:\]", text):
        # a piece lands at ``start:start+rows``; a page and a whole
        # block at static rows ``from:to`` of the slot's block
        if "+" in rows:
            rows = int(rows.split("+")[1])
        else:
            low, high = rows.split(":")
            rows = int(high or block_pages * PAGE) - int(low or 0)
        sizes.setdefault(rows, set()).add("reshape" in src)
    assert sizes == {16 * 2 ** bit: {True} if bit else {True, False}
                     for bit in range(block_pages.bit_length())}
    compiled = _compile(fn, *specs)
    assert compiled.as_text().count(
        'custom_call_target="tpu_custom_call"') == 1
    assert compiled.memory_analysis().temp_size_in_bytes < layer_bytes


@pytest.mark.parametrize("block_tokens", [512, 1024, 2048])
def test_indexer_walk_compiles_at_the_cells_leaf(block_tokens):
    """keye-vl-2.0-30b-a3b.serve-repo-saturated's decode indexer (ISSUE
    56): 17 rows of 16 heads of 64 against the indexer leaf as the cell
    holds it, 6 layers of 38,913 pages of 8 rows of 128 lanes of
    bfloat16 (a page is HALF the 16-row tile: every copy indexes the
    leaf's leading dimensions, a page or a run of pages), a table of
    4,352 pages, at the shipped block of 2,048 tokens (128 pages) a turn
    and the two measured beside it. ONE kernel holds both arms (a run as
    one copy or its binary pieces; page by page): Mosaic takes the
    dynamic page counts and starts, the leaf stays in HBM as XLA lays it
    (no copy, no re-lay), and the planes' interleave is all the call
    holds beside its result."""
    from deepspeed_tpu.ops.attention import indexed
    leaf = (6, 38913, 8, 128)

    def keys(qi, wi, pool, tables, positions):
        return indexed._indexer_decode_call(
            qi, wi, pool, tables, positions, jnp.full((1,), 3, jnp.int32),
            False, block_tokens)
    compiled = _compile(keys, _spec((17, 16, 64)),
                        _spec((17, 16), jnp.float32), _spec(leaf),
                        _spec((17, 4352), jnp.int32),
                        _spec((17,), jnp.int32))
    text = compiled.as_text()
    assert text.count('custom_call_target="tpu_custom_call"') == 1
    assert not re.search(r"= bf16\[%d,%d,8,128\]\S* (copy|fusion)\("
                         % leaf[:2], text)
    assert "s32[17,69632]" in text
    # far less than a layer of the leaf (79.7 MB)
    assert compiled.memory_analysis().temp_size_in_bytes < 3 * 17 * 69632 * 4


@pytest.mark.parametrize("row_lanes,page_size,kv_heads,head_dim", [
    (640, 16, 1, 640),      # the latent pool's ONE rule: 576 held at 640
    (576, 16, 1, 576),      # the row as the equations give it: refused
    (640, 4, 1, 640),       # a page of half a sublane tile
    (1024, 16, 16, 64),     # GPT-2 345M's pair of pools
    (1024, 16, 8, 128),     # Solar-Open2's and Granite's softmax layers
], ids=["latent_640", "latent_576", "latent_page_4", "gpt2_345m",
        "hybrids_gqa"])
def test_every_served_pool_geometry_meets_the_gate_it_met(row_lanes,
                                                          page_size,
                                                          kv_heads,
                                                          head_dim):
    """``paged_decode_supported`` on the latent row (one kv head whose
    width is the row) agrees with Mosaic on the latent reader, and the
    three geometries the benchmark already served still take the
    compiled path."""
    ok, why = paged_decode_supported(page_size, head_dim, jnp.bfloat16,
                                     backend="tpu", kv_heads=kv_heads)
    if kv_heads > 1:
        assert ok and why == "compiled pallas kernel"
        return
    specs = _latent_decode_specs(row_lanes, 8, 16, 64,
                                 page_size=page_size)
    if ok:
        _compile(_latent_decode(512), *specs)
    else:
        with pytest.raises(Exception, match=REFUSED):
            _compile(_latent_decode(512), *specs)
        assert why


# --------------------------------------------------------------------- #
# serving's one-token attention over a gathered stripe (ISSUE 25)
# --------------------------------------------------------------------- #
# gpt2-345m.serve-saturated's decode program: 161 rows, 16 heads of 64, a
# table of 40 pages of 16 tokens over a pool of 2,561 pages
ROWS, HEADS, HEAD_DIM, TABLE_PAGES, PAGE, POOL_PAGES = 161, 16, 64, 40, 16, 2561
STRIPE_ELEMS = ROWS * HEADS * TABLE_PAGES * PAGE * HEAD_DIM


def _cached_reader(cache, q_rows, cache_dtype):
    """Write, (gather,) and stripe math of one layer as the cached
    forward runs them: ``(fn, specs)`` for the paged pool or the
    contiguous cache at the cell's shapes."""
    from deepspeed_tpu.models import gpt2
    from deepspeed_tpu.ops.attention.page_pool import paged_write_index

    qkv = _spec((ROWS, HEADS, q_rows, HEAD_DIM))
    positions = _spec((ROWS,), jnp.int32)
    if cache == "paged":
        pool = _spec((1, POOL_PAGES, PAGE, HEADS * HEAD_DIM), cache_dtype)

        def fn(q, k, v, kpool, vpool, tables, pos):
            box = []
            index = paged_write_index(tables, pos, q_rows, PAGE)
            out = gpt2._paged_cache_attention(
                (kpool, vpool), 0, tables, pos, index, box)(
                    q, k, v, 0.0, None)
            return out, box[0]
        return fn, (qkv, qkv, qkv, pool, pool,
                    _spec((ROWS, TABLE_PAGES), jnp.int32), positions)
    stripe = _spec((ROWS, HEADS, TABLE_PAGES * PAGE, HEAD_DIM), cache_dtype)

    def fn(q, k, v, kcache, vcache, pos):
        box = []
        out = gpt2._offset_cache_attention(kcache, vcache, pos, box)(
            q, k, v, 0.0, None)
        return out, box[0]
    return fn, (qkv, qkv, qkv, stripe, stripe, positions)


def _entry(compiled):
    """The optimized HLO's ENTRY computation, as text."""
    return re.search(r"^ENTRY .*?^}", compiled.as_text(),
                     re.S | re.M).group(0)


def _computation(text, name):
    """The computation ``name`` of an optimized HLO module, as text."""
    return re.search(rf"^%?{re.escape(name)} .*?^}}", text,
                     re.S | re.M).group(0)


def _entry_results(compiled):
    """``(opcode, dtype, elements, called computation)`` of every array
    an instruction of the optimized HLO's ENTRY computation produces
    (tuple results included): what the program really writes, not what
    a fusion holds in registers."""
    return _results(_entry(compiled))


def _results(computation):
    """:func:`_entry_results` of any computation's text."""
    results = []
    for line in computation.splitlines():
        inst = re.match(r"\s*(?:ROOT )?%?[\w.\-]+ = (.*?) ([\w\-]+)\(", line)
        if inst:
            calls = re.search(r"calls=%?([\w.\-]+)", line)
            results += [(inst.group(2), dtype,
                         int(np.prod([int(d) for d in dims.split(",")])),
                         calls and calls.group(1))
                        for dtype, dims in re.findall(r"(\w+)\[([\d,]+)\]",
                                                      inst.group(1))]
    return results


def _entry_float32_results(compiled):
    return [elems for _, dtype, elems, _ in _entry_results(compiled)
            if dtype == "f32"]


@pytest.mark.parametrize("cache", ["paged", "contiguous"])
def test_seq1_attention_writes_no_float32_stripe(cache):
    """A one-row query over a bf16 stripe: the parent's decode program
    wrote the gathered keys and values again in float32 (two stand-alone
    ``convert``s a layer, 98 of its 289 ms on the chip), because a
    one-row dot becomes a VPU multiply-and-reduce and the v5e's VPU has
    no bf16. With the query padded to the sublane tile both dots stay on
    the MXU and take the stripe as it is."""
    fn, specs = _cached_reader(cache, 1, jnp.bfloat16)
    written = _entry_float32_results(_compile(fn, *specs))
    assert written, "the parse found no float32 result at all"
    assert max(written) < STRIPE_ELEMS


@pytest.mark.parametrize("q_rows,cache_dtype", [
    (1, jnp.float32),       # an int8 pool after dequantize_pool, fp32 caches
    (8, jnp.bfloat16),      # the row rule's edge
    (128, jnp.bfloat16),    # a prefill bucket
], ids=["float32_stripe", "rows8", "rows128"])
def test_wide_queries_and_float32_stripes_lower_as_before(q_rows,
                                                          cache_dtype):
    """The new path is taken on the query's row count and the stripe's
    dtype alone: everything else hands XLA the very program the plain
    float32 formulation does (what ``_stripe_attention`` was before
    ISSUE 25), so prefill, chunked prefill and float32 stripes cannot
    have moved."""
    from deepspeed_tpu.models import gpt2
    from tests.unit.test_paged_attention import _plain_stripe_attention

    stripe = _spec((ROWS, HEADS, TABLE_PAGES * PAGE, HEAD_DIM), cache_dtype)
    specs = (_spec((ROWS, HEADS, q_rows, HEAD_DIM)), stripe, stripe,
             _spec((ROWS,), jnp.int32))

    def program(fn):           # the module's first line names the function
        return jax.jit(fn).lower(*specs).as_text().split("\n", 1)[1]
    assert (program(gpt2._stripe_attention)
            == program(_plain_stripe_attention))


# --------------------------------------------------------------------- #
# the page pool written in place (ISSUE 28)
# --------------------------------------------------------------------- #
TRUNK_LAYERS = 4
# opcodes that name or view an array and write none
_NO_WRITE = {"parameter", "tuple", "get-tuple-element", "bitcast"}


def _paged_trunk(family, heads, kv_heads, head_dim, attn_kernel="gather"):
    """A four-layer cached trunk over the paged pool, as the engine's
    serving programs run it (pool donated; the gather reader, or the
    Pallas decode kernel for one-token queries):
    ``(jitted fn(params, cache, ids, positions, tables), param specs)``.
    The vocabulary is small so that no embedding is of a layer slice's
    size."""
    from deepspeed_tpu.models import gpt2, llama
    hidden = heads * head_dim
    if family == "gpt2":
        cfg = gpt2.GPT2Config(vocab_size=512, hidden_size=hidden,
                              num_layers=TRUNK_LAYERS, num_heads=heads,
                              max_position_embeddings=1024)
        init, trunk = gpt2.init_gpt2_params, gpt2._gpt2_trunk_cached
    else:
        cfg = llama.LlamaConfig(vocab_size=512, hidden_size=hidden,
                                num_layers=TRUNK_LAYERS, num_heads=heads,
                                num_kv_heads=kv_heads,
                                max_position_embeddings=1024)
        init, trunk = llama.init_llama_params, llama._llama_trunk_cached
    params = jax.tree_util.tree_map(
        lambda x: _spec(x.shape, x.dtype),
        jax.eval_shape(lambda: init(cfg, jax.random.PRNGKey(0))))

    def fn(params, cache, ids, positions, tables):
        return trunk(params, cfg, ids, cache, positions, jnp.bfloat16,
                     block_tables=tables, paged_attn_kernel=attn_kernel)
    return jax.jit(fn, donate_argnums=(1,)), params


def _scatter_root(text, fusion):
    """The scatter (or update slice) of its first operand that roots the
    fusion computation ``fusion``, if it is one: what aliases a donated
    pool."""
    return re.search(
        rf"^%?{re.escape(fusion or '?')} .*?^\s*ROOT [^\n]*? "
        r"(scatter|dynamic-update-slice)\(%?param_0", text, re.S | re.M)


def _pool_write_branches(text, computation, pool_shape):
    """``[(token rows, whole pages)]``, the two branch computations of
    every conditional of ``computation`` that hands the pool on (ISSUE
    44: the write picks its index granularity from the positions; index
    0 is the predicate's False). Checked here: in either branch the
    ONLY results of the pool's size are scatters that alias it, one a
    leaf."""
    dims = ",".join(map(str, pool_shape))
    pairs = []
    for line in computation.splitlines():
        cond = re.match(
            rf"\s*%?[\w.\-]+ = \((bf16\[{dims}\]\S*(?:, )?)+\) "
            r"conditional\(.*?branch_computations=\{%?([\w.\-]+), "
            r"%?([\w.\-]+)\}", line)
        if not cond:
            continue
        leaves = line.split(" conditional(")[0].count(f"bf16[{dims}]")
        pair = tuple(_computation(text, name) for name in cond.groups()[1:])
        for branch in pair:
            written = [(opcode, calls)
                       for opcode, _, elems, calls in _results(branch)
                       if opcode not in _NO_WRITE
                       and elems == int(np.prod(pool_shape))]
            assert len(written) == leaves, written
            assert all(opcode == "fusion" and _scatter_root(text, calls)
                       for opcode, calls in written), written
        pairs.append(pair)
    return pairs


@pytest.mark.parametrize("rows,tokens", [(ROWS, 1), (8, 128)],
                         ids=["decode", "prefill8x128"])
@pytest.mark.parametrize("family,heads,kv_heads,head_dim", [
    ("gpt2", 16, 16, 64),       # GPT-2 345M: rows of 1,024 lanes
    ("gpt2", 25, 25, 64),       # GPT-2 XL: 1,600
    ("llama", 32, 8, 128),      # Llama-sized GQA: 8 x 128
], ids=["gpt2_345m", "gpt2_xl", "llama_gqa"])
def test_paged_trunk_writes_the_pool_in_place(family, heads, kv_heads,
                                              head_dim, rows, tokens):
    """The pool leaf ``(layers, pages, page_size, kv_heads * head_dim)``
    keeps its default layout as an entry parameter, so a serving program
    that carries it through its layers (a) hands the donated pool back
    in the buffers it came in, (b) writes nothing of a layer slice's
    size or more but the 2 x layers scatters that alias it (in a prompt
    bucket of whole pages inside ONE conditional a layer, whose either
    branch holds the layer's two: ISSUE 44) and, in
    decode, the gathered stripes, and (c) needs temporaries of no more
    than one layer's K and V stripes (their heads on whole lane tiles)
    plus one layer slice. With a ``head_dim`` of 64 as the last
    dimension the parameter's layout put the PAGES on the lanes, and
    every program sliced, re-laid and re-stacked the whole pool
    (2,354 MB of temporaries at these four layers)."""
    width = kv_heads * head_dim
    pool_shape = (TRUNK_LAYERS, POOL_PAGES, PAGE, width)
    layer_elems = int(np.prod(pool_shape[1:]))
    pool_elems = TRUNK_LAYERS * layer_elems
    stripe_elems = rows * TABLE_PAGES * PAGE * width
    fn, params = _paged_trunk(family, heads, kv_heads, head_dim)
    pool = _spec(pool_shape)
    compiled = fn.lower(params, (pool, pool),
                        _spec((rows, tokens), jnp.int32),
                        _spec((rows,), jnp.int32),
                        _spec((rows, TABLE_PAGES), jnp.int32)).compile()
    text = compiled.as_text()

    # (a) results 1 and 2, the K and V pools, alias the pool parameters
    aliases = dict(re.findall(r"\{(\d+)\}: \((\d+), \{\}, may-alias\)",
                              text.split("\n", 1)[0]))
    dims = ",".join(map(str, pool_shape))
    pool_params = set(re.findall(
        rf"= bf16\[{dims}\]\S* parameter\((\d+)\)", _entry(compiled)))
    assert len(pool_params) == 2
    assert {aliases.get("1"), aliases.get("2")} == pool_params

    # (b) what is written at a layer slice's size or more
    scatters = 0
    whole_pages = tokens % PAGE == 0
    for opcode, dtype, elems, calls in _entry_results(compiled):
        if elems < layer_elems or opcode in _NO_WRITE:
            continue
        if elems == pool_elems:
            assert (opcode == "conditional" if whole_pages else
                    opcode == "fusion" and _scatter_root(text, calls)), (
                        opcode, dtype, calls)
            scatters += 1
        else:
            assert elems == stripe_elems, (opcode, dtype, elems)
    assert scatters == 2 * TRUNK_LAYERS
    assert len(_pool_write_branches(text, _entry(compiled), pool_shape)) \
        == TRUNK_LAYERS * whole_pages
    assert not re.search(rf"bf16\[{dims}\]\S* copy\(", text)

    # (c) temporaries: K and V stripes of one layer, a head on whole
    # lane tiles, and one layer slice
    stripes = 2 * rows * kv_heads * TABLE_PAGES * PAGE * max(head_dim, 128)
    assert compiled.memory_analysis().temp_size_in_bytes \
        < 2 * (stripes + layer_elems)


@pytest.mark.parametrize("family,heads,kv_heads,head_dim", [
    ("gpt2", 16, 16, 64),       # the cell: gpt2-345m.serve-saturated
    ("llama", 32, 8, 128),
], ids=["gpt2_345m", "llama_gqa"])
def test_pallas_decode_trunk_holds_no_gathered_stripe(monkeypatch, family,
                                                      heads, kv_heads,
                                                      head_dim):
    """The decode program with the Pallas reader (ISSUE 30), at the
    cell's 161 rows, table of 40 pages and pool of 2,561: the donated
    pool comes back in its own buffers, the only results of half a
    gathered stripe's size or more are the 2 x layers scatters that
    alias it, and NOTHING like a stripe is produced: no ``gather``, no
    ``reshape`` or ``copy`` of one, no fusion writing one. What reads
    the keys and values is one ``tpu_custom_call`` a layer, and the
    program's temporaries are a fraction of ONE stripe (the gather
    reader's were four of them)."""
    from deepspeed_tpu.ops.attention import paged
    monkeypatch.setattr(paged, "_use_pallas", lambda: True)
    width = kv_heads * head_dim
    pool_shape = (TRUNK_LAYERS, POOL_PAGES, PAGE, width)
    layer_elems = int(np.prod(pool_shape[1:]))
    stripe_elems = ROWS * TABLE_PAGES * PAGE * width
    fn, params = _paged_trunk(family, heads, kv_heads, head_dim, "pallas")
    pool = _spec(pool_shape)
    compiled = fn.lower(params, (pool, pool),
                        _spec((ROWS, 1), jnp.int32),
                        _spec((ROWS,), jnp.int32),
                        _spec((ROWS, TABLE_PAGES), jnp.int32)).compile()
    text = compiled.as_text()
    assert len(re.findall(r'custom_call_target="tpu_custom_call"',
                          _entry(compiled))) == TRUNK_LAYERS
    aliases = dict(re.findall(r"\{(\d+)\}: \((\d+), \{\}, may-alias\)",
                              text.split("\n", 1)[0]))
    assert {"1", "2"} <= set(aliases)
    written = [(opcode, elems)
               for opcode, _, elems, _ in _entry_results(compiled)
               if opcode not in _NO_WRITE and elems >= stripe_elems // 2]
    assert written, "the parse found no result of the pool's size"
    assert all(opcode == "fusion" and elems == TRUNK_LAYERS * layer_elems
               for opcode, elems in written), written
    assert len(written) == 2 * TRUNK_LAYERS
    # the embedding lookup is the only gather left, of a row a token
    from deepspeed_tpu.utils.hlo_audit import max_gather_elems
    assert max_gather_elems(text) < stripe_elems // 2
    assert compiled.memory_analysis().temp_size_in_bytes \
        < stripe_elems * 2 // 4


_PREFILL_PROGRAMS = {}


def _gpt2_prefill_program(rows, tokens):
    """GPT-2 345M's ``rows x tokens`` prompt bucket over the cell's pool
    (four layers, the pool donated, the Pallas kernels on), compiled for
    the described chip once a module: ``(compiled, pool shape)``."""
    pool_shape = (TRUNK_LAYERS, POOL_PAGES, PAGE, HEADS * HEAD_DIM)
    if (rows, tokens) not in _PREFILL_PROGRAMS:
        with pytest.MonkeyPatch.context() as patch:
            patch.setattr(flash, "_use_pallas", lambda: True)
            fn, params = _paged_trunk("gpt2", HEADS, HEADS, HEAD_DIM, "pallas")
            pool = _spec(pool_shape)
            _PREFILL_PROGRAMS[rows, tokens] = fn.lower(
                params, (pool, pool), _spec((rows, tokens), jnp.int32),
                _spec((rows,), jnp.int32),
                _spec((rows, TABLE_PAGES), jnp.int32)).compile()
    return _PREFILL_PROGRAMS[rows, tokens], pool_shape


@pytest.mark.parametrize("rows,tokens,kernels", [(8, 64, 0), (8, 512, 1)],
                         ids=["prefill8x64", "prefill8x512"])
def test_gpt2_prefill_attends_to_its_own_keys(rows, tokens, kernels):
    """GPT-2 345M's smallest and largest batch-8 prompt buckets over the
    cell's pool (ISSUE 40): after each layer's in-place write ONE
    conditional picks the reader from the positions. Its own-keys branch
    holds no gather and no float32 ``(rows, heads, 640, 64)`` stripe: at
    8 x 64 the stripe mathematics over the call's 64 keys (``kernels``
    0), at 8 x 512 the flash kernel at 16 heads of 64 (Mosaic takes the
    tile). The
    stripe branch only READS the written pool, so the conditional copies
    none: the donated pool still comes back in its own buffers and the
    only results of its size are the 2 x layers scatters that alias
    it (since ISSUE 44 inside the write's own conditional, a layer:
    ``test_gpt2_prefill_writes_whole_pages_in_place``)."""
    compiled, pool_shape = _gpt2_prefill_program(rows, tokens)
    pool_elems = int(np.prod(pool_shape))
    text = compiled.as_text()
    dims = ",".join(map(str, pool_shape))
    # the reader's conditional hands on a context, the write's the pool
    branches = re.findall(
        rf" = \((?!bf16\[{dims}\])[^\n]*? conditional\(.*?"
        r"branch_computations=\{%?([\w.\-]+), %?([\w.\-]+)\}",
        _entry(compiled))
    assert len(branches) == TRUNK_LAYERS
    stripe_f32 = f"f32[{rows},{HEADS},{TABLE_PAGES * PAGE},{HEAD_DIM}]"
    for stripe, own in branches:          # index 0 is the predicate's False
        own, stripe = _computation(text, own), _computation(text, stripe)
        assert own.count('custom_call_target="tpu_custom_call"') == kernels
        assert " gather(" not in own and stripe_f32 not in own
        assert "tpu_custom_call" not in stripe
        assert "kv_gather" in stripe and "kv_gather" not in own
    assert text.count('custom_call_target="tpu_custom_call"') \
        == kernels * TRUNK_LAYERS

    assert not re.search(rf"bf16\[{dims}\]\S* copy\(", text)
    aliases = dict(re.findall(r"\{(\d+)\}: \((\d+), \{\}, may-alias\)",
                              text.split("\n", 1)[0]))
    assert {"1", "2"} <= set(aliases)
    written = [opcode for opcode, _, elems, _ in _entry_results(compiled)
               if opcode not in _NO_WRITE and elems == pool_elems]
    assert written == ["conditional"] * (2 * TRUNK_LAYERS), written
    assert len(_pool_write_branches(text, _entry(compiled), pool_shape)) \
        == TRUNK_LAYERS


@pytest.mark.parametrize("rows,tokens", [(8, 64), (8, 512)],
                         ids=["prefill8x64", "prefill8x512"])
def test_gpt2_prefill_writes_whole_pages_in_place(rows, tokens):
    """The same two programs at published widths, pages of 16 (ISSUE
    44): the 4.0 GB pool now passes THROUGH a conditional a layer, whose
    branches are the two index granularities of the one write. Nothing
    of the pool's size is copied anywhere in the program, the donated
    pool is aliased input to output, either branch writes it by ONE
    aliasing scatter a leaf (``_pool_write_branches``), the whole-page
    branch with ``rows x tokens / 16`` indices that each move a page of
    ``(16, 1024)``, the other with an index a token row."""
    compiled, pool_shape = _gpt2_prefill_program(rows, tokens)
    text = compiled.as_text()
    dims = ",".join(map(str, pool_shape))
    assert " copy(" not in "".join(        # neither of it nor into it
        line for line in text.splitlines() if f"bf16[{dims}]" in line)
    aliases = dict(re.findall(r"\{(\d+)\}: \((\d+), \{\}, may-alias\)",
                              text.split("\n", 1)[0]))
    pool_params = set(re.findall(
        rf"= bf16\[{dims}\]\S* parameter\((\d+)\)", _entry(compiled)))
    assert len(pool_params) == 2
    assert {aliases.get("1"), aliases.get("2")} == pool_params
    assert compiled.memory_analysis().alias_size_in_bytes \
        == 2 * 2 * int(np.prod(pool_shape))

    branches = _pool_write_branches(text, _entry(compiled), pool_shape)
    assert len(branches) == TRUNK_LAYERS

    def scatters(branch):
        """(indices, the update) of the branch's scatters, as the fused
        scatter takes them."""
        found = []
        for fusion in re.findall(r" fusion\([^\n]*calls=%?([\w.\-]+)",
                                 branch):
            body = _computation(text, fusion)
            root = re.search(r"ROOT [^\n]*? scatter\(%?param_0[\w.]*, "
                             r"%?([\w.\-]+), %?([\w.\-]+)\)", body)
            if root:
                shape = dict(re.findall(
                    r"^\s*%?([\w.\-]+) = (\w+\[[\d,]*\])", body, re.M))
                indices = re.match(r"s32\[(\d+)", shape[root.group(1)])
                found.append((int(indices.group(1)), shape[root.group(2)]))
        return found

    pages = rows * tokens // PAGE
    width = HEADS * HEAD_DIM
    for token_rows, whole_pages in branches:
        assert scatters(whole_pages) == [
            (pages, f"bf16[{pages},{PAGE},{width}]")] * 2
        assert scatters(token_rows) == [
            (rows * tokens, f"bf16[{rows * tokens},{width}]")] * 2


def test_serving_compiler_options_share_the_layers_code(monkeypatch):
    """The in-place programs no longer run the compiler short of memory,
    and it was only when short of memory that it generated the code of
    like fusions once: without the engine's compiler option a 24-layer
    decode program is 71 MB of code where the parent's was 10, the 13
    serving programs hold 0.7 GB more of the chip and no longer fit the
    compile cache they are loaded from (ISSUE 28). The option is the
    TPU compiler's own: this holds its name and its effect to the
    installed compiler."""
    from deepspeed_tpu.inference import engine
    assert engine._program_compiler_options() is None     # not a TPU here
    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
    options = engine._program_compiler_options()
    fn, params = _paged_trunk("gpt2", 16, 16, 64)
    pool = _spec((TRUNK_LAYERS, POOL_PAGES, PAGE, 16 * 64))
    lowered = fn.lower(params, (pool, pool), _spec((ROWS, 1), jnp.int32),
                       _spec((ROWS,), jnp.int32),
                       _spec((ROWS, TABLE_PAGES), jnp.int32))
    plain, shared = (
        lowered.compile(compiler_options=o).memory_analysis()
        .generated_code_size_in_bytes for o in (None, options))
    assert shared < plain / 2


# ---------------------------------------------------------------------------
# SmallThinker-21BA3B-Instruct as one chip's share (ISSUE 32): the banded
# causal mask at its head shape, the experts' grouped product at its
# widths, and the whole ZeRO-2 train step of the cut configuration.
SMALLTHINKER_Q = (1, 28, 8192, 128)
SMALLTHINKER_KV = (1, 4, 8192, 128)


@pytest.mark.parametrize("block", SWEEP_BLOCKS)
@pytest.mark.parametrize("window", [4096, None], ids=["window", "global"])
@pytest.mark.parametrize("direction", ["fwd", "bwd"])
def test_banded_causal_mask_compiles_at_28_over_4_heads_of_128(
        monkeypatch, direction, window, block):
    """The model's two layer kinds at 8,192 positions, 28 query heads in
    groups of 7 over 4 key-value heads of 128, streamed tiles: causal
    inside a 4,096 window (FULL tiles, the diagonal's causal compare, the
    far edge's window compare: three loops) and causal alone, at every
    tile PR 33's sweep tried."""
    monkeypatch.setattr(flash, "_FORCE_BLOCKS", (block, block))

    def fwd(q, k, v):
        return flash_attention(q, k, v, causal=True, window=window,
                               interpret=False)

    def bwd(q, k, v):
        return jax.grad(lambda *qkv: jnp.sum(fwd(*qkv).astype(jnp.float32)),
                        argnums=(0, 1, 2))(q, k, v)

    q, kv = _spec(SMALLTHINKER_Q), _spec(SMALLTHINKER_KV)
    compiled = _compile({"fwd": fwd, "bwd": bwd}[direction], q, kv, kv)
    assert "tpu_custom_call" in compiled.as_text()


@pytest.mark.parametrize("k,n", [(2560, 768), (768, 2560)],
                         ids=["gate_up", "down"])
@pytest.mark.parametrize("direction", ["fwd", "bwd"])
def test_grouped_product_compiles_at_the_expert_widths(monkeypatch, k, n,
                                                       direction):
    """The experts' grouped product over 16 held experts, a 32,768-row
    buffer: forward, and the two backward products."""
    from deepspeed_tpu.ops import moe
    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")

    def fwd(lhs, rhs, sizes):
        return moe.grouped_matmul(lhs, rhs, sizes)

    def bwd(lhs, rhs, sizes):
        return jax.grad(lambda a, b: jnp.sum(
            fwd(a, b, sizes).astype(jnp.float32)), argnums=(0, 1))(lhs, rhs)

    compiled = _compile({"fwd": fwd, "bwd": bwd}[direction],
                        _spec((32768, k)), _spec((16, k, n)),
                        _spec((16,), jnp.int32))
    calls = compiled.as_text().count('custom_call_target="tpu_custom_call"')
    assert calls >= {"fwd": 1, "bwd": 2}[direction]


def test_trained_expert_layer_sums_its_picks_a_choice_a_slab(monkeypatch):
    """`jax.grad` of ONE trained expert layer at train-8k's shapes
    (16,384 tokens x top 6 of 64, 16 held, 2,560 x 768, bfloat16): each
    of its pick-sums (the combine forward, `_take_rows`' backward) lays
    a block's picks (k, block, H). Nothing of shape (block, k, H) may be
    in the compiled text but a bitcast: there the six choices lie on
    sixteen sublanes and every picked row is written out once more
    before it is summed (a `reshape` of 2.05 ms a pair of turns beside a
    reduce of 1.84 where slabs read 0 and 0.42: PERF.md, PR 50)."""
    from deepspeed_tpu.ops import moe
    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
    tokens, top_k, hidden, ffn, held, experts = 16384, 6, 2560, 768, 16, 64

    def layer_grads(x, idx, p, tables):
        def loss(x, p, tables):
            y, _ = moe.dropless_experts(x, idx, p, tables, (0, held),
                                        experts, jax.nn.relu)
            return jnp.sum(y * y)
        return jax.grad(loss, argnums=(0, 1, 2))(x, p, tables)

    compiled = _compile(
        layer_grads, _spec((tokens, hidden)),
        _spec((tokens, top_k), jnp.int32), _spec((tokens, top_k), jnp.float32),
        {"w_gate": _spec((held, hidden, ffn)),
         "w_up": _spec((held, hidden, ffn)),
         "w_down": _spec((held, ffn, hidden))})
    block = moe._TRAINED_BLOCK
    made = lambda shape: re.findall(
        r"= \w+\[%s\]\S* ([a-z-]+)\(" % shape, compiled.as_text())
    assert made(f"{top_k},{block},{hidden}").count("gather") == 2
    assert set(made(f"{block},{top_k},{hidden}")) <= {"bitcast"}
    # the parent's reading, in (2048, k, H): 1,591,955,456; in slabs
    # 1,592,084,480 (the padded copy lay where other temporaries peak)
    assert compiled.memory_analysis().temp_size_in_bytes <= 1.60e9


@pytest.mark.parametrize("k,n", [(4096, 1280), (1280, 4096)],
                         ids=["gate_up", "down"])
def test_grouped_product_compiles_at_the_served_expert_widths(monkeypatch,
                                                              k, n):
    """Solar-Open2's experts (4,096 x 1,280, 40 held) through the capped
    tile of models/solar_open2.py, a prefill bucket's turn of rows."""
    from deepspeed_tpu.models.solar_open2 import _EXPERT_TILE
    from deepspeed_tpu.ops import moe
    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
    compiled = _compile(
        lambda lhs, rhs, sizes: moe.grouped_matmul(lhs, rhs, sizes,
                                                   _EXPERT_TILE),
        _spec((8192, k)), _spec((40, k, n)), _spec((40,), jnp.int32))
    assert 'custom_call_target="tpu_custom_call"' in compiled.as_text()


def test_delta_rule_decode_update_compiles_in_place_at_published_widths(
        monkeypatch):
    """The one-token state update of ops/kda.py over the cell's 193 rows
    of 64 heads of 128 x 128, two layers of a three-layer pool: Mosaic
    takes the kernel, the pool is aliased through both calls and nothing
    of its size is copied."""
    from deepspeed_tpu.ops import kda
    rows, heads, d = 193, 64, 128
    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")

    def two_layers(pool, q, k, v, g, beta):
        o, pool = kda.kda_decode_update(pool, 0, q, k, v, g, beta)
        return kda.kda_decode_update(pool, 2, q, k, o, g, beta)

    vec = _spec((rows, heads, d), jnp.float32)
    compiled = jax.jit(two_layers, donate_argnums=(0,)).lower(
        _spec((3, rows, heads, d, d), jnp.float32), vec, vec, vec, vec,
        _spec((rows, heads), jnp.float32)).compile()
    text = compiled.as_text()
    assert text.count('custom_call_target="tpu_custom_call"') == 2
    memory = compiled.memory_analysis()
    pool_bytes = 3 * rows * heads * d * d * 4
    assert memory.alias_size_in_bytes >= pool_bytes
    assert memory.temp_size_in_bytes < pool_bytes // 100
    assert not re.search(r"= f32\[3,193,64,128,128\]\S* copy\(", text)


@pytest.mark.parametrize("rows,seq,heads", [
    (1, 256, 64), (4, 1024, 64),        # Solar's smallest, largest bucket
    (1, 2048, 32), (2, 2048, 32)])      # kimi's chunk at one and two rows
def test_delta_rule_chunk_scan_compiles_at_published_widths(
        monkeypatch, rows, seq, heads):
    """The prefill recurrence at heads of 128 with ``lengths`` given:
    ONE Mosaic kernel (chunks of 64, the solve and the carried state
    inside it) and no triangular solve left for XLA to expand."""
    from deepspeed_tpu.ops import kda
    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
    x = _spec((rows, seq, heads, 128), jnp.float32)
    lowered = jax.jit(kda.kda_chunk_scan).lower(
        x, x, x, x, _spec((rows, seq, heads), jnp.float32),
        _spec((rows, heads, 128, 128), jnp.float32),
        _spec((rows,), jnp.int32))
    assert "triangular_solve" not in lowered.as_text()
    compiled = lowered.compile()
    text = compiled.as_text()
    assert text.count('custom_call_target="tpu_custom_call"') == 1
    assert "triangular" not in text and "while(" not in text
    # nothing a chunk makes is held outside the kernel: at most a copy of
    # each 4-D PARAMETER into the (rows, seq, heads x 128) the kernel
    # reads (inside a model the producers write that form), a fifth over
    memory = compiled.memory_analysis()
    assert memory.temp_size_in_bytes <= 4.8 * rows * seq * heads * 128 * 4


@pytest.mark.parametrize("program", ["decode", "prefill"])
def test_solar_open2_serving_programs_keep_the_pools_in_place(monkeypatch,
                                                              program):
    """The benchmark configuration's decode program (193 rows) and its
    smallest prefill bucket (1 x 256) at the published widths, weights
    held in bfloat16, the cache tree donated: the page pools and the
    state pool are aliased through the four layers and the compiled
    program holds nothing of the state pool's size beside it."""
    import json
    import os
    import sys
    bench = os.path.join(os.path.dirname(__file__), "..", "..", "benchmarks")
    if bench not in sys.path:
        sys.path.insert(0, bench)
    from families import solar_open2 as family
    from deepspeed_tpu.inference.kv_cache import (PagedStateCache,
                                                  paged_spec_for,
                                                  state_pool_spec_for)
    from deepspeed_tpu.models import solar_open2 as so
    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
    with open(os.path.join(bench, "configs", "solar-open2-250b.json")) as f:
        config = json.load(f)
    model = family.serve_model_of(config)
    inference = config["serve"]["inference"]
    rows = inference["max_batch_size"] + 1
    pages = paged_spec_for(model, inference["paged_kv"]["num_pages"], 16,
                           inference["max_seq_len"])
    state = state_pool_spec_for(model, rows)
    params = jax.tree_util.tree_map(
        lambda a: _spec(a.shape, a.dtype),
        jax.eval_shape(lambda: so.init_solar_open2_params(
            model, jax.random.PRNGKey(0))))
    cache = PagedStateCache(_spec(pages.shape), _spec(pages.shape),
                            _spec(state.state_shape, jnp.float32),
                            _spec(state.tail_shape))
    ints = lambda *shape: _spec(shape, jnp.int32)

    def decode(params, cache, toks, positions, tables):
        logits, cache, counts = so.solar_open2_forward(
            params, model, toks[:, None], kv_cache=cache,
            cache_position=positions, block_tables=tables,
            paged_attn_kernel="pallas", active=tables[:, 0] > 0,
            with_counts=True)
        return jnp.argmax(logits[:, 0], -1), counts, cache

    def prefill(params, cache, ids, lengths, tables, slots):
        logits, cache = so.solar_open2_forward(
            params, model, ids, kv_cache=cache,
            cache_position=jnp.zeros_like(lengths), block_tables=tables,
            paged_attn_kernel="pallas", lengths=lengths, slots=slots)
        return jnp.argmax(logits[:, 0], -1), cache

    if program == "decode":
        fn, args = decode, (ints(rows), ints(rows),
                            ints(rows, pages.pages_per_seq))
    else:
        fn, args = prefill, (ints(1, 256), ints(1),
                             ints(1, pages.pages_per_seq), ints(1))
    compiled = jax.jit(fn, donate_argnums=(1,)).lower(
        params, cache, *args).compile()
    memory = compiled.memory_analysis()
    pool_bytes = int(np.prod(state.state_shape)) * 4
    assert memory.alias_size_in_bytes >= pool_bytes + 2 * int(
        np.prod(pages.shape)) * 2
    assert memory.temp_size_in_bytes < pool_bytes // 4
    text = compiled.as_text()
    assert not re.search(r"= f32\[3,193,64,128,128\]\S* copy\(", text)
    # the weights as they are held: 3.3B parameters in bfloat16
    assert 9.6e9 < memory.argument_size_in_bytes < 9.8e9


def test_ssd_decode_update_compiles_in_place_at_published_widths(
        monkeypatch):
    """The one-token state update of ops/ssd.py over the cell's 65 rows
    of 128 heads of 64 x 128 (a state that is not square), two layers of
    a three-layer pool: Mosaic takes the kernel, the pool is aliased
    through both calls and nothing of its size is copied."""
    from deepspeed_tpu.ops import ssd
    rows, heads, p, n = 65, 128, 64, 128
    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")

    def two_layers(pool, x, dt, a, bm, cm, d):
        y, pool = ssd.ssd_decode_update(pool, 0, x, dt, a, bm, cm, d)
        return ssd.ssd_decode_update(pool, 2, y, dt, a, bm, cm, d)

    f32 = lambda *shape: _spec(shape, jnp.float32)
    compiled = jax.jit(two_layers, donate_argnums=(0,)).lower(
        f32(3, rows, heads, p, n), f32(rows, heads, p), f32(rows, heads),
        f32(heads), f32(rows, n), f32(rows, n), f32(heads)).compile()
    text = compiled.as_text()
    assert text.count('custom_call_target="tpu_custom_call"') == 2
    memory = compiled.memory_analysis()
    pool_bytes = 3 * rows * heads * p * n * 4
    assert memory.alias_size_in_bytes >= pool_bytes
    assert memory.temp_size_in_bytes < pool_bytes // 100
    assert not re.search(r"= f32\[3,65,128,64,128\]\S* copy\(", text)


def test_ssd_chunk_scan_compiles_at_published_widths():
    """The prefill recurrence over the cell's smallest bucket at 128
    heads of 64 x 128: four chunks of 256, the state carried."""
    from deepspeed_tpu.ops import ssd
    f32 = lambda *shape: _spec(shape, jnp.float32)
    compiled = _compile(
        ssd.ssd_chunk_scan, f32(1, 1024, 128, 64), f32(1, 1024, 128),
        f32(128), f32(1, 1024, 128), f32(1, 1024, 128), f32(128),
        f32(1, 128, 64, 128), _spec((1,), jnp.int32))
    # what a chunk holds beside 12.8 GB of weights and pools
    assert compiled.memory_analysis().temp_size_in_bytes < 0.5e9


@pytest.mark.parametrize("program", ["decode", "prefill"])
def test_granite_hybrid_serving_programs_keep_the_pools_in_place(
        monkeypatch, program):
    """The benchmark configuration's decode program (65 rows) and its
    smallest prefill bucket (1 x 2,048) at the published widths, weights
    held in bfloat16, the cache tree donated: the page pools and the
    state pool are aliased through the ten layers and the compiled
    program holds nothing of the state pool's size beside it; the score
    scale reaches the two attention kernels as a constant of theirs."""
    import json
    import os
    import sys
    bench = os.path.join(os.path.dirname(__file__), "..", "..", "benchmarks")
    if bench not in sys.path:
        sys.path.insert(0, bench)
    from families import granite_hybrid as family
    from deepspeed_tpu.inference.kv_cache import (PagedStateCache,
                                                  paged_spec_for,
                                                  state_pool_spec_for)
    from deepspeed_tpu.models import granite_hybrid as gh
    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
    monkeypatch.setattr(flash, "_use_pallas", lambda: True)
    with open(os.path.join(bench, "configs",
                           "granite-4.0-h-small.json")) as f:
        config = json.load(f)
    model = family.serve_model_of(config)
    inference = config["serve"]["inference"]
    rows = inference["max_batch_size"] + 1
    pages = paged_spec_for(model, inference["paged_kv"]["num_pages"], 16,
                           inference["max_seq_len"])
    state = state_pool_spec_for(model, rows)
    params = jax.tree_util.tree_map(
        lambda a: _spec(a.shape, a.dtype),
        jax.eval_shape(lambda: gh.init_granite_hybrid_params(
            model, jax.random.PRNGKey(0))))
    cache = PagedStateCache(_spec(pages.shape), _spec(pages.shape),
                            _spec(state.state_shape, jnp.float32),
                            _spec(state.tail_shape))
    ints = lambda *shape: _spec(shape, jnp.int32)

    def decode(params, cache, toks, positions, tables):
        logits, cache, counts = gh.granite_hybrid_forward(
            params, model, toks[:, None], kv_cache=cache,
            cache_position=positions, block_tables=tables,
            paged_attn_kernel="pallas", active=tables[:, 0] > 0,
            with_counts=True)
        return jnp.argmax(logits[:, 0], -1), counts, cache

    def prefill(params, cache, ids, lengths, tables, slots):
        logits, cache = gh.granite_hybrid_forward(
            params, model, ids, kv_cache=cache,
            cache_position=jnp.zeros_like(lengths), block_tables=tables,
            paged_attn_kernel="pallas", lengths=lengths, slots=slots)
        return jnp.argmax(logits[:, 0], -1), cache

    if program == "decode":
        fn, args = decode, (ints(rows), ints(rows),
                            ints(rows, pages.pages_per_seq))
        kernels = 9 + 1            # the state updates and the paged reader
    else:
        fn, args = prefill, (ints(1, 1024), ints(1),
                             ints(1, pages.pages_per_seq), ints(1))
        kernels = 1 + 3 * 10       # flash, three grouped products a layer
    compiled = jax.jit(fn, donate_argnums=(1,)).lower(
        params, cache, *args).compile()
    memory = compiled.memory_analysis()
    pool_bytes = int(np.prod(state.state_shape)) * 4
    assert memory.alias_size_in_bytes >= pool_bytes + 2 * int(
        np.prod(pages.shape)) * 2
    assert memory.temp_size_in_bytes < pool_bytes // 4
    text = compiled.as_text()
    assert text.count('custom_call_target="tpu_custom_call"') == kernels
    assert not re.search(r"= f32\[9,%d,128,64,128\]\S* copy\(" % rows, text)
    # the weights as they are held (4,757M parameters in bfloat16) and
    # the pools
    assert 12.7e9 < memory.argument_size_in_bytes < 12.9e9


@pytest.mark.parametrize("program", ["decode", "prefill"])
def test_axk1_serving_programs_keep_the_latent_pool_in_place(monkeypatch,
                                                             program):
    """The benchmark configuration's decode program (193 rows) and its
    smallest prefill bucket (1 x 2,048) at the published widths, weights
    held in bfloat16, the cache tree donated: the ONE latent pool is
    aliased through the five layers; decode runs one latent reader a
    layer and forms no key or value of a cached token (nothing of 64
    heads x 192 or x 128 over the table's positions); prefill runs the
    flash kernel at keys of 192 and three grouped products an expert
    layer."""
    import json
    import os
    import sys
    bench = os.path.join(os.path.dirname(__file__), "..", "..", "benchmarks")
    if bench not in sys.path:
        sys.path.insert(0, bench)
    from families import axk1 as family
    from deepspeed_tpu.inference.kv_cache import (paged_kv_bytes,
                                                  paged_spec_for)
    from deepspeed_tpu.models import axk1 as ax
    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
    monkeypatch.setattr(flash, "_use_pallas", lambda: True)
    with open(os.path.join(bench, "configs", "ax-k1.json")) as f:
        config = json.load(f)
    model = family.serve_model_of(config)
    inference = config["serve"]["inference"]
    rows = inference["max_batch_size"] + 1
    pages = paged_spec_for(model, inference["paged_kv"]["num_pages"],
                           inference["paged_kv"]["page_size"],
                           inference["max_seq_len"])
    params = jax.tree_util.tree_map(
        lambda a: _spec(a.shape, a.dtype),
        jax.eval_shape(lambda: ax.init_axk1_params(
            model, jax.random.PRNGKey(0))))
    cache = (_spec(pages.shape),)
    ints = lambda *shape: _spec(shape, jnp.int32)

    def decode(params, cache, toks, positions, tables):
        logits, cache, counts = ax.axk1_forward(
            params, model, toks[:, None], kv_cache=cache,
            cache_position=positions, block_tables=tables,
            paged_attn_kernel="pallas", active=tables[:, 0] > 0,
            with_counts=True)
        return jnp.argmax(logits[:, 0], -1), counts, cache

    def prefill(params, cache, ids, lengths, tables):
        logits, cache, counts = ax.axk1_forward(
            params, model, ids, kv_cache=cache,
            cache_position=jnp.zeros_like(lengths), block_tables=tables,
            paged_attn_kernel="pallas", lengths=lengths, with_counts=True)
        return jnp.argmax(logits[:, 0], -1), counts, cache

    if program == "decode":
        fn, args = decode, (ints(rows), ints(rows),
                            ints(rows, pages.pages_per_seq))
        kernels = 5                # the latent reader, a layer
    else:
        fn, args = prefill, (ints(1, min(inference["prompt_buckets"])),
                             ints(1), ints(1, pages.pages_per_seq))
        kernels = 5 + 3 * 4        # flash a layer, the grouped products
    compiled = jax.jit(fn, donate_argnums=(1,)).lower(
        params, cache, *args).compile()
    memory = compiled.memory_analysis()
    assert memory.alias_size_in_bytes >= paged_kv_bytes(pages)
    # less than ONE layer of the pool: no copy of one is made
    assert memory.temp_size_in_bytes < paged_kv_bytes(pages) // pages.num_layers
    text = compiled.as_text()
    assert text.count('custom_call_target="tpu_custom_call"') == kernels
    if program == "decode":
        # no per-head key or value over the table's 6,144 positions
        assert not re.search(r"\[%d,(64,)?6144,(64,)?(192|128|256)\]" % rows,
                             text)
        assert "paged_decode" not in text and "latent_decode" in text
    # the weights as they are held (3,491M parameters in bfloat16) and
    # the pool (4.61 GB)
    assert 11.55e9 < memory.argument_size_in_bytes < 11.65e9


@pytest.mark.parametrize("program", ["decode", "chunk_1", "chunk_2"])
def test_kimi_linear_serving_programs_keep_one_cache_tree_in_place(
        monkeypatch, program):
    """The benchmark configuration's three programs at the published
    widths (decode at 65 rows; a chunk of 2,048 at 1 and at 2 rows, any
    start), weights held in bfloat16, the cache tree donated: the latent
    pool, the state pool and the tails are aliased through the nine
    layers. Decode runs the delta-rule update in place at seven layers
    and the latent reader at two; a chunk runs three grouped products an
    expert layer, the chunk scan a delta-rule layer and the flash kernel
    TWICE a latent layer (its own rows, and a block of the prefix a loop
    turn), and holds no (chunk x table) scores."""
    import json
    import os
    import sys
    bench = os.path.join(os.path.dirname(__file__), "..", "..", "benchmarks")
    if bench not in sys.path:
        sys.path.insert(0, bench)
    from families import kimi_linear as family
    from deepspeed_tpu.inference.kv_cache import (LatentStateCache,
                                                  paged_kv_bytes,
                                                  paged_spec_for,
                                                  state_pool_bytes,
                                                  state_pool_spec_for)
    from deepspeed_tpu.models import kimi_linear as kl
    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
    monkeypatch.setattr(flash, "_use_pallas", lambda: True)
    with open(os.path.join(bench, "configs",
                           "kimi-linear-48b-a3b.json")) as f:
        config = json.load(f)
    model = family.serve_model_of(config)
    inference = config["serve"]["inference"]
    rows = inference["max_batch_size"] + 1
    chunk = inference["chunked_prefill"]["chunk_tokens"]
    pages = paged_spec_for(model, inference["paged_kv"]["num_pages"],
                           inference["paged_kv"]["page_size"],
                           inference["max_seq_len"])
    state = state_pool_spec_for(model, rows)
    params = jax.tree_util.tree_map(
        lambda a: _spec(a.shape, a.dtype),
        jax.eval_shape(lambda: kl.init_kimi_linear_params(
            model, jax.random.PRNGKey(0))))
    cache = LatentStateCache(_spec(pages.shape),
                             _spec(state.state_shape, jnp.float32),
                             _spec(state.tail_shape))
    ints = lambda *shape: _spec(shape, jnp.int32)

    def decode(params, cache, toks, positions, tables):
        logits, cache, counts = kl.kimi_linear_forward(
            params, model, toks[:, None], kv_cache=cache,
            cache_position=positions, block_tables=tables,
            paged_attn_kernel="pallas", active=tables[:, 0] > 0,
            with_counts=True)
        return jnp.argmax(logits[:, 0], -1), counts, cache

    def prefill(params, cache, ids, lengths, positions, tables, slots):
        logits, cache, counts = kl.kimi_linear_forward(
            params, model, ids, kv_cache=cache, cache_position=positions,
            block_tables=tables, paged_attn_kernel="pallas",
            lengths=lengths, slots=slots, with_counts=True)
        return jnp.argmax(logits[:, 0], -1), counts, cache

    if program == "decode":
        fn, args = decode, (ints(rows), ints(rows),
                            ints(rows, pages.pages_per_seq))
        kernels = 7 + 2
    else:
        b = int(program[-1])
        fn, args = prefill, (ints(b, chunk), ints(b), ints(b),
                             ints(b, pages.pages_per_seq), ints(b))
        kernels = 3 * 8 + 7 + 2 * 2
    compiled = jax.jit(fn, donate_argnums=(1,)).lower(
        params, cache, *args).compile()
    memory = compiled.memory_analysis()
    held = paged_kv_bytes(pages) + state_pool_bytes(state)
    assert memory.alias_size_in_bytes >= held
    # less than ONE layer of either pool: no copy of one is made
    assert memory.temp_size_in_bytes < 1.1e9
    text = compiled.as_text()
    assert text.count('custom_call_target="tpu_custom_call"') == kernels
    # no score of a chunk's queries against the table's 18,432 positions
    assert not re.search(r"\[\d+,(32,)?%d,18432\]" % chunk, text)
    # the weights as they are held (2,366M parameters in bfloat16), the
    # latent pool (2.01 GB) and the state pool (0.99 GB)
    assert 7.70e9 < memory.argument_size_in_bytes < 7.80e9


@pytest.mark.parametrize("program", [
    "decode", "prefill_1x256", "prefill_1x512", "prefill_1x1024",
    "prefill_4x256", "prefill_4x512", "prefill_4x1024"])
def test_lfm2_serving_programs_keep_pages_and_tails_in_place(monkeypatch,
                                                             program):
    """The benchmark configuration's SEVEN programs at the published
    widths, weights held in bfloat16, the cache tree (keys, values,
    tails: no state leaf) donated and aliased through the ten layers.
    Decode (257 rows): the paged reader over a pool row of 8 x 64 = 512
    lanes with groups of 4 queries a key-value head, once an attention
    layer, its only kernel (the expert half is every held expert on
    every row: plain products). A prefill bucket: the flash kernel where the
    scores are many, three grouped products an expert layer, no
    ``moe_shared`` scope anywhere."""
    import json
    import os
    import sys
    bench = os.path.join(os.path.dirname(__file__), "..", "..", "benchmarks")
    if bench not in sys.path:
        sys.path.insert(0, bench)
    from families import lfm2 as family
    from deepspeed_tpu.inference.kv_cache import (PagedTailCache,
                                                  paged_spec_for,
                                                  state_pool_spec_for)
    from deepspeed_tpu.models import lfm2
    from deepspeed_tpu.ops.attention.paged import paged_decode_supported
    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
    monkeypatch.setattr(flash, "_use_pallas", lambda: True)
    with open(os.path.join(bench, "configs", "lfm2-24b-a2b.json")) as f:
        config = json.load(f)
    model = family.serve_model_of(config)
    inference = config["serve"]["inference"]
    rows = inference["max_batch_size"] + 1
    pages = paged_spec_for(model, inference["paged_kv"]["num_pages"], 16,
                           inference["max_seq_len"])
    tails = state_pool_spec_for(model, rows)
    assert pages.shape[-1] == 8 * 64 and not tails.has_state
    assert paged_decode_supported(pages.head_dim, pages.kv_heads,
                                  jnp.bfloat16)
    params = jax.tree_util.tree_map(
        lambda a: _spec(a.shape, a.dtype),
        jax.eval_shape(lambda: lfm2.init_lfm2_params(
            model, jax.random.PRNGKey(0))))
    cache = PagedTailCache(_spec(pages.shape), _spec(pages.shape),
                           _spec(tails.tail_shape))
    ints = lambda *shape: _spec(shape, jnp.int32)

    def decode(params, cache, toks, positions, tables):
        logits, cache, counts = lfm2.lfm2_forward(
            params, model, toks[:, None], kv_cache=cache,
            cache_position=positions, block_tables=tables,
            paged_attn_kernel="pallas", active=tables[:, 0] > 0,
            with_counts=True)
        return jnp.argmax(logits[:, 0], -1), counts, cache

    def prefill(params, cache, ids, lengths, tables, slots):
        logits, cache, counts = lfm2.lfm2_forward(
            params, model, ids, kv_cache=cache,
            cache_position=jnp.zeros_like(lengths), block_tables=tables,
            paged_attn_kernel="pallas", lengths=lengths, slots=slots,
            with_counts=True)
        return jnp.argmax(logits[:, 0], -1), counts, cache

    if program == "decode":
        fn, args = decode, (ints(rows), ints(rows),
                            ints(rows, pages.pages_per_seq))
        kernels = 2                # the paged reader, an attention layer
    else:
        b, s = (int(n) for n in program.split("_")[1].split("x"))
        fn, args = prefill, (ints(b, s), ints(b),
                             ints(b, pages.pages_per_seq), ints(b))
        kernels = None
    lowered = jax.jit(fn, donate_argnums=(1,)).lower(params, cache, *args)
    assert "/moe_shared" not in lowered.as_text(debug_info=True)
    compiled = lowered.compile()
    memory = compiled.memory_analysis()
    tree_bytes = 2 * int(np.prod(pages.shape)) * 2 + int(
        np.prod(tails.tail_shape)) * 2
    assert memory.alias_size_in_bytes >= tree_bytes
    text = compiled.as_text()
    calls = text.count('custom_call_target="tpu_custom_call"')
    if kernels is None:
        # three grouped products a turn of an expert layer's loop + the
        # product down; the flash kernel where the scores are many
        assert calls >= 3 * 8
    else:
        assert calls == kernels
    # the weights as they are held (5,267M parameters in bfloat16), the
    # pools (1.07 GB) and the tails
    assert 11.5e9 < memory.argument_size_in_bytes < 11.8e9
    assert memory.temp_size_in_bytes < 2.5e9
    print(program, "temp", memory.temp_size_in_bytes, "code",
          memory.generated_code_size_in_bytes)


@pytest.mark.parametrize("program", ["decode", "chunk_1", "chunk_2"])
def test_keye_vl2_serving_programs_keep_three_leaves_in_place(monkeypatch,
                                                              program):
    """The benchmark configuration's three programs at the published
    widths (decode at 17 rows over the table's 69,632 positions; a chunk
    of 2,048 at 1 and at 2 rows, any start), weights held in bfloat16,
    the cache tree donated: keys, values AND the indexer leaf (two
    tokens a 128-lane row) are aliased through the six layers, and no
    copy of the indexer leaf is made around the program (a 64-lane row
    was re-laid twice a layer: 28 ms of a decode step, my chip run, PR
    55). Decode runs ONE kernel a layer, the indexer's walk of the live
    pages of its leaf (ISSUE 56), a sort a layer and rows read by (page,
    offset); a chunk runs the flash kernel TWICE a layer (its own rows,
    and a block of the prefix a loop turn) under a mask a (query, key)
    and three grouped products an expert layer, and holds no (heads x
    chunk x table) scores."""
    import json
    import os
    import sys
    bench = os.path.join(os.path.dirname(__file__), "..", "..", "benchmarks")
    if bench not in sys.path:
        sys.path.insert(0, bench)
    from families import keye_vl2 as family
    from deepspeed_tpu.inference.kv_cache import (IndexedPairCache,
                                                  paged_kv_bytes,
                                                  paged_spec_for)
    from deepspeed_tpu.models import keye_vl2 as kv2
    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
    monkeypatch.setattr(flash, "_use_pallas", lambda: True)
    with open(os.path.join(bench, "configs",
                           "keye-vl-2.0-30b-a3b.json")) as f:
        config = json.load(f)
    model = family.serve_model_of(config)
    inference = config["serve"]["inference"]
    rows = inference["max_batch_size"] + 1
    chunk = inference["chunked_prefill"]["chunk_tokens"]
    pages = paged_spec_for(model, inference["paged_kv"]["num_pages"],
                           inference["paged_kv"]["page_size"],
                           inference["max_seq_len"])
    assert pages.index_shape[2:] == (8, 128)
    params = jax.tree_util.tree_map(
        lambda a: _spec(a.shape, a.dtype),
        jax.eval_shape(lambda: kv2.init_keye_vl2_params(
            model, jax.random.PRNGKey(0))))
    cache = IndexedPairCache(_spec(pages.shape), _spec(pages.shape),
                             _spec(pages.index_shape))
    ints = lambda *shape: _spec(shape, jnp.int32)

    def decode(params, cache, toks, positions, tables):
        logits, cache, counts = kv2.keye_vl2_forward(
            params, model, toks[:, None], kv_cache=cache,
            cache_position=positions, block_tables=tables,
            active=tables[:, 0] > 0, with_counts=True)
        return jnp.argmax(logits[:, 0], -1), counts, cache

    def prefill(params, cache, ids, lengths, positions, tables, slots):
        logits, cache, counts = kv2.keye_vl2_forward(
            params, model, ids, kv_cache=cache, cache_position=positions,
            block_tables=tables, lengths=lengths, slots=slots,
            with_counts=True)
        return jnp.argmax(logits[:, 0], -1), counts, cache

    if program == "decode":
        fn, args = decode, (ints(rows), ints(rows),
                            ints(rows, pages.pages_per_seq))
        kernels = 6
    else:
        b = int(program[-1])
        fn, args = prefill, (ints(b, chunk), ints(b), ints(b),
                             ints(b, pages.pages_per_seq), ints(b))
        kernels = 6 * (2 + 3)
    compiled = jax.jit(fn, donate_argnums=(1,)).lower(
        params, cache, *args).compile()
    memory = compiled.memory_analysis()
    held = paged_kv_bytes(pages)
    assert memory.alias_size_in_bytes >= held
    text = compiled.as_text()
    assert text.count('custom_call_target="tpu_custom_call"') == kernels
    # the indexer leaf is never copied whole (nor re-laid)
    leaf = "bf16[%d,%d,8,128]" % pages.index_shape[:2]
    assert not re.search(r"= %s\S* copy\(" % re.escape(leaf), text)
    # no score of a chunk's queries, a head, against the whole table
    assert not re.search(r"\[\d+,(16|32),%d,69632\]" % chunk, text)
    # the weights as they are held (659M parameters in bfloat16) and the
    # three leaves of the pool
    assert abs(memory.argument_size_in_bytes - held - 2 * 659_190_016) < 5e7
    assert memory.temp_size_in_bytes < (2.0e9 if program == "decode"
                                        else 2.2e9 * int(program[-1]))
    print(program, "temp", memory.temp_size_in_bytes, "code",
          memory.generated_code_size_in_bytes)


def test_smallthinker_train_step_compiles_at_the_cut_widths(monkeypatch):
    """`deepspeed_tpu.initialize` + the ONE compiled `_micro_step`
    (ZeRO-2, bf16, Adam, clipping) of the benchmark's configuration at
    its published widths and its 8,192 positions, compiled for one
    described v5e: a global and a window layer (the configuration holds
    one global and three alike window layers), 16 of 64 experts held,
    4,096 vocabulary rows (the configuration holds 38,016) so that the
    state this host must hold stays at 3 GB."""
    import deepspeed_tpu
    from deepspeed_tpu.models import smallthinker as st
    from deepspeed_tpu.ops.attention import flash

    cfg = st.SmallThinkerConfig(
        num_layers=2, rope_layout=(0, 1), sliding_window_layout=(0, 1),
        experts_held=(0, 16), vocab_held=(0, 4096))
    shapes = jax.eval_shape(
        lambda key: st.init_smallthinker_params(cfg, key),
        jax.random.PRNGKey(0))
    params = jax.tree_util.tree_map(
        lambda s: np.zeros(s.shape, np.float32), shapes)
    device = _DEVICES[:1]
    monkeypatch.setattr(jax, "devices", lambda *a, **k: device)
    monkeypatch.setattr(jax, "device_put", lambda x, *a, **k: x)
    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
    monkeypatch.setattr(flash, "_use_pallas", lambda: True)
    engine, *_ = deepspeed_tpu.initialize(
        model=st.smallthinker_loss_fn(cfg), model_parameters=params,
        config={"train_micro_batch_size_per_gpu": 1,
                "gradient_accumulation_steps": 1, "bf16": {"enabled": True},
                "optimizer": {"type": "Adam", "params": {"lr": 1.5e-4}},
                "zero_optimization": {"stage": 2},
                "gradient_clipping": 1.0, "steps_per_print": 1000,
                "mesh": {"axes": {"data": 1}}})
    del params
    on_chip = SingleDeviceSharding(device[0])
    state = jax.tree_util.tree_map(
        lambda a: jax.ShapeDtypeStruct(a.shape, a.dtype, sharding=on_chip),
        engine.state)
    batch = {"input_ids": jax.ShapeDtypeStruct((1, 8193), jnp.int32,
                                               sharding=on_chip)}
    compiled = jax.jit(engine._micro_step, donate_argnums=(0,)).lower(
        state, batch).compile()
    text = compiled.as_text()
    # 2 layers x 3 attention kernels, and the grouped products of the
    # expert layer's forward, its second run and its backward
    assert text.count('custom_call_target="tpu_custom_call"') >= 6 + 2 * 12
    assert "ragged-dot" not in text
    # the step's counters leave the program: (layers, held) int32
    assert "s32[2,16]" in text
