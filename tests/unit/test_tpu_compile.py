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

from deepspeed_tpu.ops.attention.flash import (flash_attention,
                                               get_attention_options)
from deepspeed_tpu.ops.attention.paged import (paged_decode_attention,
                                               paged_decode_supported)
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


@pytest.mark.parametrize("shape", [GPT2_345M, HEAD_128],
                         ids=["gpt2_345m", "head128"])
@pytest.mark.parametrize("direction", ["fwd", "bwd"])
@pytest.mark.parametrize("dropout_rate", [0.0, 0.1],
                         ids=["nodrop", "dropout"])
def test_masked_flash_causal_compiles(shape, direction, dropout_rate):
    """The default training attention (the unified masked kernel with a
    causal BlockMask): the parent of PR 21 failed every one of these
    with ``failed to legalize operation 'arith.select'``."""
    assert get_attention_options().kernel == "masked"
    qkv = _spec(shape)
    compiled = _compile(_attention(dropout_rate)[direction], qkv, qkv, qkv,
                        _spec((2,), jnp.uint32))
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


def _paged_decode_specs(head_dim, page_size, pool_dtype):
    batch, heads, num_pages, pages_per_seq = 8, 16, 128, 16
    pool = _spec((num_pages, heads, page_size, head_dim), pool_dtype)
    specs = [_spec((batch, heads, head_dim)), pool, pool,
             _spec((batch, pages_per_seq), jnp.int32),
             _spec((batch,), jnp.int32)]
    if pool_dtype == jnp.int8:
        # the engine's scale leaves at kv_quant_block 0: one per row
        scale = _spec((num_pages, heads, page_size, 1), jnp.float32)
        specs += [scale, scale]
    return specs


def _paged_decode(q, kpool, vpool, tables, positions, *scales):
    kw = dict(zip(("k_scales", "v_scales"), scales))
    return paged_decode_attention(q, kpool, vpool, tables, positions,
                                  interpret=False, **kw)


def test_paged_decode_bf16_head128_compiles():
    compiled = _compile(_paged_decode,
                        *_paged_decode_specs(128, 16, jnp.bfloat16))
    assert "tpu_custom_call" in compiled.as_text()


@pytest.mark.parametrize("head_dim,page_size,pool_dtype", [
    (128, 32, jnp.int8),        # a page geometry the old gate accepted
    (128, 16, jnp.int8),
    (64, 16, jnp.bfloat16),     # every GPT-2 width
    (128, 16, jnp.bfloat16),
    (128, 32, jnp.bfloat16),
    (128, 8, jnp.float32),
], ids=lambda v: getattr(v, "__name__", str(v)))
def test_paged_decode_gate_agrees_with_the_compiler(head_dim, page_size,
                                                    pool_dtype):
    """``paged_decode_supported`` is what keeps the serving engine off a
    kernel that cannot compile: it says no exactly where Mosaic
    refuses."""
    ok, why = paged_decode_supported(page_size, head_dim, pool_dtype,
                                     backend="tpu")
    specs = _paged_decode_specs(head_dim, page_size, pool_dtype)
    if ok:
        _compile(_paged_decode, *specs)
    else:
        with pytest.raises(Exception, match="aligned to tiling"):
            _compile(_paged_decode, *specs)
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

    qkv = _spec((ROWS, HEADS, q_rows, HEAD_DIM))
    positions = _spec((ROWS,), jnp.int32)
    if cache == "paged":
        pool = _spec((POOL_PAGES, HEADS, PAGE, HEAD_DIM), cache_dtype)

        def fn(q, k, v, kpool, vpool, tables, pos):
            box = []
            out = gpt2._paged_cache_attention(kpool, vpool, tables, pos,
                                              box)(q, k, v, 0.0, None)
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


def _entry_float32_results(compiled):
    """Element counts of every float32 array an instruction of the
    optimized HLO's ENTRY computation produces (tuple results included):
    what the program really writes, not what a fusion holds in
    registers."""
    entry = re.search(r"^ENTRY .*?^}", compiled.as_text(),
                      re.S | re.M).group(0)
    counts = []
    for line in entry.splitlines():
        result = re.match(r"\s*(?:ROOT )?%?[\w.\-]+ = (.*?) [\w\-]+\(", line)
        if result:
            counts += [int(np.prod([int(d) for d in dims.split(",")]))
                       for dims in re.findall(r"f32\[([\d,]+)\]",
                                              result.group(1))]
    return counts


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
