"""shard_map wrap for Pallas kernels under a GSPMD mesh.

A ``pallas_call`` cannot be auto-partitioned by GSPMD: left inside a
jit with sharded operands, XLA either replicates the operands (wrong
answer for a sharded cache) or fails to partition — which is why, until
PR 11, the serving engine silently dropped the PR 8 paged-decode kernel
for the max_len-bounded gather path the moment ``inference.mesh`` was
set, losing the O(live tokens) win exactly at pod scale.

The fix is the canonical one: wrap the kernel in ``jax.shard_map`` over
the mesh's head axis, so each device runs the *identical* kernel on its
local head shard — attention is embarrassingly parallel over (kv) heads,
no collectives needed inside. This module is the one home for those
wraps:

- :func:`sharded_paged_decode` — the PR 8 decode kernel over a
  kv-head-sharded page pool (the serving engine's mesh path; the
  compiled program is pinned gather-free by ``hlo_audit.gather_ops``
  in tier-1).
- :func:`sharded_masked_flash` — the unified training kernel
  (``ops/attention/masked_flash.py``) over head-sharded q/k/v. Requires
  a head-uniform BlockMask (``mask.heads == 1`` — dense, causal, and
  every propagated SparsityConfig layout): shard_map is SPMD, so
  per-head metadata cannot differ across shards. NOTE: the in-kernel
  dropout hash is keyed on the *local* head index, so a sharded run
  draws a different (equally valid) keep-mask than an unsharded one.
- :func:`sharded_flash_attention` — the ``flash_attention`` dispatcher
  itself (whichever kernel it selects) over q/k/v sharded by batch over
  the data axes and by head over the model axis: what the training
  engine's GSPMD step needs on more than one chip, where Mosaic
  refuses "Mosaic kernels cannot be automatically partitioned".
- :func:`pallas_kernel_mesh` / :func:`current_kernel_mesh` — a
  trace-time context the engines use to thread their mesh down to the
  models' kernel call sites without widening every forward signature:
  an engine traces its compiled programs under the context,
  ``ops/attention/flash.flash_attention`` and
  ``page_pool.paged_decode_ctx`` consult it.

Head-axis legality mirrors the PR 7 cache sharding: the mesh axis must
divide q heads AND kv heads (each shard then owns whole GQA groups, so
group g of q head h lands on the same shard as kv head h // G).
"""

import contextlib
import functools
import math
from typing import NamedTuple, Optional, Tuple

import jax
from jax.sharding import Mesh, PartitionSpec as P

from deepspeed_tpu.parallel.mesh import axis_size

__all__ = ["sharded_paged_decode", "sharded_masked_flash",
           "pallas_kernel_mesh", "current_kernel_mesh", "KernelMesh",
           "head_shard_supported", "context_prefill_mesh",
           "current_cp_mesh", "sharded_flash_attention"]


class KernelMesh(NamedTuple):
    mesh: Mesh
    axis: str                           # heads shard over this axis
    batch_axes: Tuple[str, ...] = ()    # batch shards over these


_ACTIVE: list = []          # stack; trace-time only
_CP_ACTIVE: list = []       # context-parallel prefill stack (ISSUE 19)


@contextlib.contextmanager
def pallas_kernel_mesh(mesh: Optional[Mesh], axis: str = "model",
                       batch_axes: Tuple[str, ...] = ()):
    """Trace-time context: while active, mesh-aware kernel call sites
    (``flash_attention``, ``page_pool.paged_decode_ctx``) wrap their
    Pallas kernels in shard_map over the mesh — heads over ``axis``,
    batch over ``batch_axes`` (the training engine's data axes; the
    serving engines shard heads only). ``mesh=None`` (or axes that are
    all absent/size-1) is a no-op, so callers can wrap
    unconditionally."""
    batch_axes = tuple(a for a in batch_axes if axis_size(mesh, a) > 1) \
        if mesh is not None else ()
    if mesh is None or (axis_size(mesh, axis) <= 1 and not batch_axes):
        yield
        return
    _ACTIVE.append(KernelMesh(mesh, axis, batch_axes))
    try:
        yield
    finally:
        _ACTIVE.pop()


def current_kernel_mesh() -> Optional[KernelMesh]:
    return _ACTIVE[-1] if _ACTIVE else None


@contextlib.contextmanager
def context_prefill_mesh(mesh: Optional[Mesh], axis: str = "model"):
    """Trace-time context for CONTEXT-PARALLEL prefill (ISSUE 19):
    while active, the models' multi-query paged gather attention
    routes through ``ops.attention.ring.ring_prefill_attention`` —
    the chunk's sequence axis sharded over ``(mesh, axis)`` with K/V
    stripes rotating around the ring. A separate stack from
    :func:`pallas_kernel_mesh` because the serving engine traces its
    CP chunk program under BOTH (the decode-side kernel context stays
    on for any seq-1 call sites). ``mesh=None``/size-1 axis is a
    no-op."""
    if mesh is None or axis_size(mesh, axis) <= 1:
        yield
        return
    _CP_ACTIVE.append(KernelMesh(mesh, axis))
    try:
        yield
    finally:
        _CP_ACTIVE.pop()


def current_cp_mesh() -> Optional[KernelMesh]:
    return _CP_ACTIVE[-1] if _CP_ACTIVE else None


def head_shard_supported(n: int, *head_counts) -> bool:
    """Can a Pallas attention kernel shard over an n-way head axis for
    these head counts? Every count must divide (whole GQA groups per
    shard)."""
    return all(h % n == 0 for h in head_counts)


def sharded_flash_attention(km: KernelMesh, q, k, v, mask=None,
                            dropout_rng=None, **kwargs):
    """``flash_attention`` with the kernel wrapped in shard_map over
    ``km``: batch over ``km.batch_axes``, heads over ``km.axis`` —
    attention needs no collective across either. A dim its axes do not
    divide stays replicated (every shard then computes all of it, which
    is what GSPMD does with such a dim anyway). With dropout each shard
    folds its index into the rng: the in-kernel hash is keyed on the
    LOCAL (batch, head) index, and shards must not draw the same mask.
    ``flash_attention`` calls this when a kernel mesh is active and the
    trace is not already inside a shard_map."""
    from deepspeed_tpu.ops.attention.flash import _local_flash_attention
    mesh = km.mesh
    b, h = q.shape[:2]
    batch_axes = km.batch_axes
    if b % math.prod(axis_size(mesh, a) for a in batch_axes):
        batch_axes = ()
    nh = axis_size(mesh, km.axis)
    head = km.axis if nh > 1 and head_shard_supported(
        nh, h, k.shape[1]) else None
    manual = batch_axes + ((head,) if head else ())
    if not manual:
        return _local_flash_attention(q, k, v, mask=mask,
                                      dropout_rng=dropout_rng, **kwargs)
    batch = batch_axes if len(batch_axes) > 1 else \
        (batch_axes[0] if batch_axes else None)
    qkv_spec = P(batch, head)
    operands, specs = [q, k, v], [qkv_spec] * 3
    if mask is not None:                      # (B, 1, 1, Sk) key mask
        operands.append(mask)
        specs.append(P(batch))
    if dropout_rng is not None:
        operands.append(dropout_rng)
        specs.append(P())

    def inner(q, k, v, *rest):
        rest = list(rest)
        m = rest.pop(0) if mask is not None else None
        rng = rest.pop(0) if dropout_rng is not None else None
        if rng is not None:
            idx = 0
            for a in manual:
                idx = idx * axis_size(mesh, a) + jax.lax.axis_index(a)
            rng = jax.random.fold_in(rng, idx)
        return _local_flash_attention(q, k, v, mask=m, dropout_rng=rng,
                                      **kwargs)

    return jax.shard_map(inner, mesh=mesh, in_specs=tuple(specs),
                         out_specs=qkv_spec, check_vma=False)(*operands)


def sharded_paged_decode(q, kpool, vpool, block_tables, cache_position,
                         mesh: Mesh, axis: str = "model",
                         sm_scale: Optional[float] = None,
                         interpret: Optional[bool] = None,
                         k_scales=None, v_scales=None, layer: int = 0):
    """PR 8 ``paged_decode_attention`` under a GSPMD mesh: q sharded
    over heads, the stacked pools over their row dimension (the engine's
    ``P(None, None, None, 'model')`` pool split: heads are major within
    a row, so each shard holds whole kv heads), block tables and
    positions replicated. The int8-pool arity (``k_scales``/``v_scales``,
    PR 17) shards the fp32 scale pools over the same dimension as the
    payload pools — each shard dequantizes its own head's tiles in
    VMEM, no collectives. Falls through to the plain kernel when the
    axis is absent or size 1."""
    from deepspeed_tpu.ops.attention.paged import paged_decode_attention
    n = axis_size(mesh, axis)
    kernel = functools.partial(paged_decode_attention, sm_scale=sm_scale,
                               interpret=interpret, layer=layer)
    pools = (kpool, vpool)
    if k_scales is not None:
        pools += (k_scales, v_scales)

    def inner(q, block_tables, cache_position, kpool, vpool, ks=None,
              vs=None):
        return kernel(q, kpool, vpool, block_tables, cache_position,
                      k_scales=ks, v_scales=vs)
    if n <= 1:
        return inner(q, block_tables, cache_position, *pools)
    H, KH = q.shape[1], kpool.shape[-1] // q.shape[-1]
    assert head_shard_supported(n, H, KH), (
        f"paged decode: mesh axis {axis!r} ({n}-way) must divide "
        f"q heads ({H}) and kv heads ({KH})")
    f = jax.shard_map(
        inner, mesh=mesh,
        in_specs=(P(None, axis), P(), P())
        + (P(None, None, None, axis),) * len(pools),
        out_specs=P(None, axis), check_vma=False)
    return f(q, block_tables, cache_position, *pools)


def sharded_masked_flash(q, k, v, mask, key_mask=None,
                         mesh: Optional[Mesh] = None, axis: str = "model",
                         sm_scale=None, dropout_rate: float = 0.0,
                         dropout_rng=None,
                         interpret: Optional[bool] = None):
    """The unified training kernel head-sharded over ``(mesh, axis)``
    — same signature and semantics as
    :func:`~deepspeed_tpu.ops.attention.masked_flash.masked_flash_attention`
    plus the mesh. Differentiable (the custom vjp transposes through
    shard_map). Requires a head-uniform mask (``mask.heads == 1``)."""
    import jax.numpy as jnp
    import numpy as np
    from deepspeed_tpu.ops.attention.flash import (_use_pallas,
                                                   dropout_seed_from_rng)
    from deepspeed_tpu.ops.attention.masked_flash import masked_flash_call
    n = axis_size(mesh, axis) if mesh is not None else 1
    b, h, _, d = q.shape
    if sm_scale is None:
        sm_scale = 1.0 / np.sqrt(d)
    if interpret is None:
        interpret = not _use_pallas()
    if n <= 1:
        from deepspeed_tpu.ops.attention.masked_flash import \
            masked_flash_attention
        return masked_flash_attention(q, k, v, mask, key_mask=key_mask,
                                      sm_scale=sm_scale,
                                      dropout_rate=dropout_rate,
                                      dropout_rng=dropout_rng,
                                      interpret=interpret)
    assert mask.heads == 1, (
        "sharded_masked_flash needs a head-uniform BlockMask "
        f"(mask.heads == 1, got {mask.heads}): shard_map is SPMD, so "
        "per-head mask metadata cannot differ across shards")
    assert head_shard_supported(n, h, k.shape[1]), (
        f"mesh axis {axis!r} ({n}-way) must divide q heads ({h}) and "
        f"kv heads ({k.shape[1]})")
    rate = float(dropout_rate)
    if rate > 0.0:
        assert dropout_rng is not None
        seed = dropout_seed_from_rng(dropout_rng)
    else:
        seed = jnp.zeros((1, 1), jnp.int32)
    sk = k.shape[2]
    has_kpm = key_mask is not None
    kpm = jnp.zeros((b, 1), jnp.float32) if key_mask is None else \
        key_mask.reshape(b, sk).astype(jnp.float32)

    def inner(q, k, v, kpm, seed):
        return masked_flash_call(q, k, v, kpm, seed, mask,
                                 float(sm_scale), bool(interpret), rate,
                                 has_kpm)

    f = jax.shard_map(
        inner, mesh=mesh,
        in_specs=(P(None, axis), P(None, axis), P(None, axis), P(), P()),
        out_specs=P(None, axis), check_vma=False)
    return f(q, k, v, kpm, seed)
