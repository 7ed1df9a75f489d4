"""KV-cache allocation and accounting for the serving engine.

Two cache geometries live here:

**Dense (legacy, ``paged_kv.enabled: false``)** — ONE preallocated pair
of arrays ``(kc, vc)``, each shaped ``(layers, batch_rows, kv_heads,
max_len, head_dim)``: every serving slot owns a full ``max_len`` stripe
whether its request is 6 tokens or 6000. ``batch_rows`` is
``max_batch_size + 1``: the extra row is the *scratch slot* — padding
rows of a partially-filled prefill bucket scatter their (garbage) K/V
there instead of corrupting a live request's slot.

**Paged (default)** — a fixed pool of ``num_pages`` pages, as one pair
of arrays shaped ``(layers, num_pages, page_size, kv_heads *
head_dim)``, plus a host-side :class:`PageAllocator`. A pool ROW is one
token of one layer: its ``kv_heads`` heads side by side, heads major
(head ``h`` is lanes ``h * head_dim:(h + 1) * head_dim``), so a row is
lane-dense at every model width (1,024 lanes for GPT-2 345M, 1,600 for
GPT-2 XL) where a ``head_dim`` of 64 alone is half a lane tile. The
three index dimensions lead, so a token is written in place at
``[layer, page, offset]`` — a prompt bucket whose rows start on page
boundaries a whole page at ``[layer, page]`` (ISSUE 44) — and a donated
pool is never copied around a program's layer stack (ISSUE 28); a
``(page_size, head_dim)`` tile of head ``h`` is
``pool[layer, page, :, h * head_dim:(h + 1) * head_dim]``.
A request occupies ``ceil(total_tokens / page_size)`` pages mapped
through a static-shape per-slot *block table*; HBM occupancy is
therefore bounded by the tokens actually reserved in flight, not
``slots x max_len``. Page 0 is reserved as the *null page*: unallocated
block-table entries and padding-row writes all land there (its contents
are garbage by design and never read unmasked — ``causal_cache_mask``
hides every position a query has not reached). The allocator also
implements **prefix caching**: full, page-aligned prompt prefixes are
chain-hashed and refcounted, so concurrent requests sharing a system
prompt prefill the shared pages once.

**The state pool (beside the pages)** — a family with recurrent layers
(``models/solar_open2.py``: gated delta-rule attention) keeps, for every
such layer, a state that does not grow with the sequence: two more
leaves of the cache tree, ``(recurrent layers, slots + 1, heads, dk,
dv)`` float32 and the short convolution's last inputs ``(recurrent
layers, slots + 1, positions, channels)``, ONE ROW A SLOT (the last row
is the scratch slot of a prefill bucket's pad rows). No allocator: a
slot's row is written WHOLE by its request's prefill, so it is freed
with the slot and a reused slot never sees its predecessor's state;
decode rewrites every row in place. :class:`StatePoolSpec` is built
from the model's ``state_geometry``; a family without one has no such
leaves.

A family served in CHUNKS (``models/kimi_linear.py``) writes the row at
every chunk's true end, so the row is a snapshot at each chunk boundary:
the next chunk starts from it, a chunk at position 0 from zeros (a
reused slot never sees its predecessor's state), and a decode dispatch
leaves the row of a slot that is mid-prefill as it is.

A family whose per-slot layers are gated short convolutions
(``models/lfm2.py``, ``tail_geometry``) keeps the tails and NO state:
its tree is ``(keys, values, tails)`` (:class:`PagedTailCache`), the
state leaf absent, not a zero-sized stand-in. A prefill writes a slot's
tail row whole at its rows' TRUE ends; decode rewrites the rows that
decode and leaves the others as they are.

**The latent pool (in place of the pair)** — a family with multi-head
latent attention (``models/axk1.py``) caches ONE row a token a layer,
``[c (latent_width) | k_r (rope_width)]`` after the norm and the
rotation: every head's keys and values are functions of it, and the
values' operand IS its first ``latent_width`` lanes, so the cache tree
is ONE leaf ``(layers, num_pages, page_size, row_lanes)`` and no second
pool repeats those bytes. ONE rule for the row's lanes, on every
backend: ``latent_width + rope_width`` rounded up to whole 128-lane
tiles, the tail zeros (:func:`latent_row_lanes`; 512 + 64 -> 640: the
Pallas reader's page DMA needs whole tiles, and the scores' contraction
over zero lanes adds nothing). :class:`LatentPoolSpec` is built from the
model's ``latent_geometry``; pages, block tables, the allocator and
admission are the pair's.

**The indexer leaf (a THIRD leaf of the pair's tree)** — a family
whose attention reads a learned selection of its tokens
(``models/keye_vl2.py``) keeps, beside a token's keys and values, ONE
indexer key a token a layer, TWO tokens a pool row: ``(layers,
num_pages, page_size / 2, 2 x index_width)``, token ``o`` of a page in
row ``o // 2`` at lanes ``(o % 2) * index_width`` on (the same bytes as
``(page_size, index_width)`` row-major; at the published width of 64 a
row is one whole 128-lane tile, where a 64-lane row is held padded to
128 and re-laid by the compiler around every program: 28 ms of a 65 ms
decode step, my chip run, PR 55). Written through the same
``PagedWriteIndex`` as the pair
(``ops/attention/indexed.write_index_keys``), mapped by the same block
tables and freed with the same pages (the allocator moves page ids and
knows no leaf).
:class:`PagedKVSpec` carries its width (``index_width``, from the
model's ``indexer_geometry``; 0: no such leaf) and the tree is
:class:`IndexedPairCache`.

Writes happen inside the model forwards via
:func:`deepspeed_tpu.models.gpt2.write_kv_cache` (dense) /
:func:`deepspeed_tpu.ops.attention.page_pool.write_paged_kv_cache`
(paged); this module only owns allocation, the family-specific geometry
(GQA caches are kv_heads-sized), and byte accounting for telemetry.
"""

from typing import Any, NamedTuple, Optional, Tuple

import jax.numpy as jnp
import numpy as np

from deepspeed_tpu.inference.paging import PageAllocator, pages_for

__all__ = ["KVCacheSpec", "cache_spec_for", "init_kv_cache",
           "kv_cache_bytes", "PagedKVSpec", "paged_spec_for",
           "init_paged_kv_cache", "paged_kv_bytes", "pages_for",
           "PageAllocator", "StatePoolSpec", "state_pool_spec_for",
           "init_state_pool", "state_pool_bytes", "PagedStateCache",
           "PagedTailCache", "LatentStateCache", "LatentPoolSpec",
           "latent_row_lanes", "IndexedPairCache"]


class KVCacheSpec(NamedTuple):
    """Static geometry of the dense serving KV cache."""
    num_layers: int
    batch_rows: int      # serving slots + 1 scratch row
    kv_heads: int        # GQA: the cache stays kv_heads-sized
    max_len: int
    head_dim: int
    dtype: Any = jnp.bfloat16

    @property
    def shape(self) -> Tuple[int, int, int, int, int]:
        return (self.num_layers, self.batch_rows, self.kv_heads,
                self.max_len, self.head_dim)


def _model_kv_geometry(model_config):
    kv_heads = getattr(model_config, "kv_heads", None) or \
        model_config.num_heads
    head_dim = getattr(model_config, "head_dim", None) or (
        model_config.hidden_size // model_config.num_heads)
    return kv_heads, head_dim


def cache_spec_for(model_config, batch_rows: int, max_len: int,
                   dtype=jnp.bfloat16) -> KVCacheSpec:
    """Dense cache geometry from a model config (GPT2Config /
    LlamaConfig): kv_heads-sized for GQA families, head-count-sized
    otherwise."""
    kv_heads, head_dim = _model_kv_geometry(model_config)
    if max_len > model_config.max_position_embeddings:
        raise ValueError(
            f"kv cache max_len {max_len} exceeds the model's "
            f"max_position_embeddings {model_config.max_position_embeddings}")
    return KVCacheSpec(num_layers=model_config.num_layers,
                       batch_rows=batch_rows, kv_heads=kv_heads,
                       max_len=max_len, head_dim=head_dim, dtype=dtype)


def init_kv_cache(spec: KVCacheSpec):
    """Allocate the zeroed ``(kc, vc)`` pair."""
    return (jnp.zeros(spec.shape, spec.dtype),
            jnp.zeros(spec.shape, spec.dtype))


def _pair_bytes(spec) -> int:
    """Bytes of a (kc, vc) array pair with ``spec.shape``/``spec.dtype``
    — the one accounting both cache geometries report."""
    return 2 * int(np.prod(spec.shape)) * jnp.dtype(spec.dtype).itemsize


def kv_cache_bytes(spec: KVCacheSpec) -> int:
    """Total bytes of the (kc, vc) pair — the serving memory headline."""
    return _pair_bytes(spec)


# --------------------------------------------------------------------- #
# paged cache
# --------------------------------------------------------------------- #
class PagedKVSpec(NamedTuple):
    """Static geometry of the paged serving KV cache. ``pages_per_seq``
    is the block-table width: every slot's table maps that many logical
    page positions (covering ``max_len`` tokens), entries beyond its
    reservation pointing at the null page 0. ``shape`` is the pool
    leaf's: ``(layers, num_pages, page_size, kv_heads * head_dim)``, one
    token a row with its heads side by side (module docstring).

    **Quantized pool (PR 17)** — ``dtype=int8`` switches the pool to
    int8 payload with per-token-row fp32 absmax scales stored alongside
    (the EQuARX/qwZ recipe applied to the KV pool): the cache tree
    becomes the 4-tuple ``(kc, vc, kscale, vscale)`` where the scale
    pools are shaped ``(layers, num_pages, page_size, kv_heads *
    scale_blocks)``: a token's scales are one row, heads major, like its
    payload. ``quant_block`` is the scale granularity along
    head_dim (0 = one scale per token row, i.e. the whole head_dim);
    scales are per token row because decode fills pages one token at a
    time — a page-wide scale would be rewritten (and degrade) on every
    append."""
    num_layers: int
    num_pages: int       # pool size, INCLUDING the reserved null page 0
    page_size: int
    kv_heads: int
    head_dim: int
    pages_per_seq: int
    dtype: Any = jnp.bfloat16
    quant_block: int = 0  # scale block over head_dim (0 = head_dim)
    # lanes of the indexer key a token a layer holds beside its keys
    # and values (module docstring); 0: the tree has no such leaf
    index_width: int = 0

    @property
    def shape(self) -> Tuple[int, int, int, int]:
        return (self.num_layers, self.num_pages, self.page_size,
                self.kv_heads * self.head_dim)

    @property
    def index_shape(self) -> Tuple[int, int, int, int]:
        """Two tokens a row (module docstring)."""
        return (self.num_layers, self.num_pages, self.page_size // 2,
                2 * self.index_width)

    @property
    def quantized(self) -> bool:
        return jnp.dtype(self.dtype) == jnp.dtype(jnp.int8)

    @property
    def scale_blocks(self) -> int:
        """Scales per token row: head_dim / quant_block."""
        block = self.quant_block or self.head_dim
        return self.head_dim // block

    @property
    def scale_shape(self) -> Tuple[int, int, int, int]:
        return (self.num_layers, self.num_pages, self.page_size,
                self.kv_heads * self.scale_blocks)


def latent_row_lanes(latent_width: int, rope_width: int) -> int:
    """Lanes of a latent pool's row: the latent and the rotary key side
    by side, rounded up to whole 128-lane tiles (the tail zeros)."""
    return -(-(latent_width + rope_width) // 128) * 128


class LatentPoolSpec(NamedTuple):
    """Static geometry of the paged LATENT pool (module docstring): one
    leaf, one row a token a layer. To what reads a pool's geometry it is
    a pool of ONE kv head whose width is the row (``kv_heads``,
    ``head_dim``), never quantized."""
    num_layers: int
    num_pages: int       # pool size, INCLUDING the reserved null page 0
    page_size: int
    latent_width: int    # c: what the values are read from
    rope_width: int      # k_r: the one rotary key slice of all heads
    pages_per_seq: int
    dtype: Any = jnp.bfloat16

    @property
    def row_lanes(self) -> int:
        return latent_row_lanes(self.latent_width, self.rope_width)

    @property
    def shape(self) -> Tuple[int, int, int, int]:
        return (self.num_layers, self.num_pages, self.page_size,
                self.row_lanes)

    kv_heads = property(lambda self: 1)
    head_dim = property(lambda self: self.row_lanes)
    quantized = property(lambda self: False)
    quant_block = property(lambda self: 0)


def paged_spec_for(model_config, num_pages: int, page_size: int,
                   max_len: int, dtype=jnp.bfloat16,
                   kv_quant_block: int = 0):
    """Paged cache geometry from a model config. ``num_pages == 0``
    auto-sizes the pool to the dense worst case (every slot is not known
    here, so callers pass the resolved count); the engine resolves 0
    before calling. ``dtype=int8`` selects the quantized pool;
    ``kv_quant_block`` (0 = head_dim) sets the per-row scale block and
    must divide head_dim."""
    kv_heads, head_dim = _model_kv_geometry(model_config)
    if max_len > model_config.max_position_embeddings:
        raise ValueError(
            f"paged kv cache max_len {max_len} exceeds the model's "
            f"max_position_embeddings {model_config.max_position_embeddings}")
    if page_size < 1 or num_pages < 2:
        raise ValueError(
            f"paged kv cache needs page_size >= 1 and num_pages >= 2 "
            f"(one null + one usable), got page_size={page_size}, "
            f"num_pages={num_pages}")
    quantized = jnp.dtype(dtype) == jnp.dtype(jnp.int8)
    latent = getattr(model_config, "latent_geometry", None)
    if latent is not None:
        if quantized:
            raise ValueError("the latent pool has no int8 form: a row's "
                             "scales have no place in its one leaf")
        return LatentPoolSpec(
            num_layers=model_config.kv_cache_layers, num_pages=num_pages,
            page_size=page_size, latent_width=latent[0],
            rope_width=latent[1],
            pages_per_seq=pages_for(max_len, page_size), dtype=dtype)
    block = int(kv_quant_block) if quantized else 0
    if quantized and block and head_dim % block != 0:
        raise ValueError(
            f"paged kv cache kv_quant_block ({block}) must divide "
            f"head_dim ({head_dim})")
    # a trunk of mixed layer kinds pages only its softmax layers
    layers = getattr(model_config, "kv_cache_layers", None) or \
        model_config.num_layers
    indexer = getattr(model_config, "indexer_geometry", None)
    if indexer is not None and quantized:
        raise ValueError("the indexer leaf has no int8 form: its keys "
                         "are scored as they are held")
    if indexer is not None and page_size % 2:
        raise ValueError(f"the indexer leaf holds two tokens a row: "
                         f"page_size ({page_size}) has to be even")
    return PagedKVSpec(num_layers=layers,
                       num_pages=num_pages, page_size=page_size,
                       kv_heads=kv_heads, head_dim=head_dim,
                       pages_per_seq=pages_for(max_len, page_size),
                       dtype=dtype, quant_block=block,
                       index_width=indexer[0] if indexer else 0)


def init_paged_kv_cache(spec: PagedKVSpec):
    """Allocate the zeroed paged pool tree: the ``(kc, vc)`` pair, plus
    ``(kscale, vscale)`` fp32 scale pools when the spec is int8-
    quantized (4-tuple). Every engine cache op is leaf-generic over this
    tuple, so the two geometries share one code path. A latent pool is
    the 1-tuple of its one leaf."""
    if isinstance(spec, LatentPoolSpec):
        return (jnp.zeros(spec.shape, spec.dtype),)
    pools = (jnp.zeros(spec.shape, spec.dtype),
             jnp.zeros(spec.shape, spec.dtype))
    if spec.quantized:
        # zero scales are fine: the null page / unwritten rows are never
        # read unmasked, and quantized writes always store a scale > 0
        pools = pools + (jnp.zeros(spec.scale_shape, jnp.float32),
                         jnp.zeros(spec.scale_shape, jnp.float32))
    if spec.index_width:
        return IndexedPairCache(*pools,
                                jnp.zeros(spec.index_shape, spec.dtype))
    return pools


def paged_kv_bytes(spec: PagedKVSpec) -> int:
    """Total bytes of the paged pool tree, EVERY leaf that
    :func:`init_paged_kv_cache` builds: int8 payload + fp32 scales when
    quantized (the KV lever of ``quant_serving_bytes``); a latent
    pool's one leaf; the indexer leaf beside the pair."""
    if isinstance(spec, LatentPoolSpec):
        return int(np.prod(spec.shape)) * jnp.dtype(spec.dtype).itemsize
    total = _pair_bytes(spec)
    if spec.quantized:
        total += 2 * int(np.prod(spec.scale_shape)) * 4
    if spec.index_width:
        total += int(np.prod(spec.index_shape)) * jnp.dtype(
            spec.dtype).itemsize
    return total


# --------------------------------------------------------------------- #
# per-slot recurrent state
# --------------------------------------------------------------------- #
class StatePoolSpec(NamedTuple):
    """Static geometry of the per-slot state pool (module docstring):
    ``rows`` is the serving slots + 1 scratch row. A family whose
    per-slot layers keep a convolution's tail and NO recurrent state
    (``models/lfm2.py``) has ``heads`` 0: its pool is the tails alone
    (:attr:`has_state`), and no state leaf is built."""
    num_layers: int      # the per-slot (recurrent / convolution) layers
    rows: int
    heads: int
    key_dim: int
    value_dim: int
    tail_positions: int  # the short convolution's width - 1
    tail_channels: int
    tail_dtype: Any = jnp.bfloat16

    @property
    def state_shape(self) -> Tuple[int, int, int, int, int]:
        return (self.num_layers, self.rows, self.heads, self.key_dim,
                self.value_dim)

    @property
    def tail_shape(self) -> Tuple[int, int, int, int]:
        return (self.num_layers, self.rows, self.tail_positions,
                self.tail_channels)

    @property
    def has_state(self) -> bool:
        return self.heads > 0


def state_pool_spec_for(model_config, rows: int,
                        tail_dtype=jnp.bfloat16) -> Optional[StatePoolSpec]:
    """The state pool's geometry from a model config's
    ``state_geometry`` (recurrent layers, heads, key width, value width,
    tail positions, tail channels), or from its ``tail_geometry``
    (convolution layers, tail positions, tail channels) where a slot
    keeps tails and no state; None for a family that keeps nothing a
    slot beside its keys and values."""
    geometry = getattr(model_config, "state_geometry", None)
    if geometry is None:
        tails = getattr(model_config, "tail_geometry", None)
        if tails is None:
            return None
        layers, positions, channels = tails
        return StatePoolSpec(layers, rows, 0, 0, 0, positions, channels,
                             tail_dtype)
    layers, heads, dk, dv, positions, channels = geometry
    return StatePoolSpec(layers, rows, heads, dk, dv, positions, channels,
                         tail_dtype)


class PagedStateCache(NamedTuple):
    """The cache tree of a family with a state pool, each leaf by name:
    the page pools of its softmax layers and the per-slot pools of its
    recurrent ones. The engine builds it and the family's forward takes
    and returns it (``kv_cache._replace``); to everything that walks
    the tree's leaves it is the tuple it always was."""
    keys: Any
    values: Any
    state: Any
    tails: Any


class PagedTailCache(NamedTuple):
    """The cache tree of a family whose per-slot layers are short
    convolutions (``models/lfm2.py``): the page pools of its softmax
    layers and the convolutions' tails, one row a slot; NO state leaf."""
    keys: Any
    values: Any
    tails: Any


class LatentStateCache(NamedTuple):
    """The cache tree of a family with latent attention AND recurrent
    layers (``models/kimi_linear.py``): the ONE latent page pool of its
    latent layers and the per-slot pools of its recurrent ones. The
    pool comes first, as in every tree (the trunk reads the page size
    off the first leaf)."""
    pool: Any
    state: Any
    tails: Any


class IndexedPairCache(NamedTuple):
    """The cache tree of a family whose attention reads a learned
    selection of its tokens (``models/keye_vl2.py``): the page pools of
    keys and values and, a third leaf over the same pages, ONE indexer
    key a token a layer (module docstring)."""
    keys: Any
    values: Any
    index_keys: Any


def init_state_pool(spec: StatePoolSpec):
    """The zeroed ``(state, tails)`` leaves: float32 states (the
    recurrence is float32 whatever the engine computes in), the tails in
    the engine's compute dtype (they are matmul outputs of it). A spec
    without a state (:attr:`StatePoolSpec.has_state`): ``(tails,)``."""
    tails = jnp.zeros(spec.tail_shape, spec.tail_dtype)
    if not spec.has_state:
        return (tails,)
    return (jnp.zeros(spec.state_shape, jnp.float32), tails)


def state_pool_bytes(spec: StatePoolSpec) -> int:
    """Bytes of the leaves :func:`init_state_pool` builds."""
    state = int(np.prod(spec.state_shape)) * 4 if spec.has_state else 0
    return state + int(
        np.prod(spec.tail_shape)) * jnp.dtype(spec.tail_dtype).itemsize
