"""int8-resident parameter storage — ZeRO++ qwZ blocks kept live.

``runtime/quantized_collectives.py`` established the wire format: int8
payload + per-block fp32 absmax scales (qwZ). Until PR 17 the serving
engine used it only as a *wire* format — ``qwz_distribute_params``
dequantized eagerly back to bf16 on the replica, so the resident HBM
footprint was the full bf16 tree and the only savings was replica
fan-out bytes. This module is the *resident* half: a registered pytree
leaf that keeps the int8 blocks + scales as the live param tree and
dequantizes per block at each matmul inside the compiled program
(EQuARX: quantize the bytes, not the math — the matmul itself runs in
the model dtype after an in-program dequant of the tile).

Layout: quantization is blockwise along the LAST axis, and ``q`` keeps
the ORIGINAL shape/rank of the weight (the last partial block is simply
narrower). Rank preservation is the point — the model families'
PartitionSpecs (``gpt2_param_specs`` / ``llama_param_specs``) apply to
``q`` unchanged, so int8-resident serving reuses the exact same
Megatron TP layout as bf16-resident serving. Scales have shape
``lead + (nb,)`` with ``nb = ceil(d / block)``.

HBM accounting: a (h, d) bf16 weight costs ``2*h*d`` bytes resident;
int8-resident costs ``h*d + 4*h*nb`` — ~0.51x at the default block of
256, i.e. the ~2x weight-HBM lever (pinned in
tests/unit/test_inference.py).
"""

from typing import Any, Tuple

import jax
import jax.numpy as jnp
import numpy as np

__all__ = ["QuantizedParam", "quantize_param", "dequantize_param",
           "quantize_param_tree", "dequantize_param_tree",
           "is_quantized_tree", "quantized_tree_bytes",
           "param_tree_bytes", "DEFAULT_WEIGHT_BLOCK"]

DEFAULT_WEIGHT_BLOCK = 256


@jax.tree_util.register_pytree_node_class
class QuantizedParam:
    """One int8-resident weight: ``q`` int8 (original shape), ``scale``
    fp32 ``lead + (nb,)``, plus the static original dtype it stands in
    for (what :func:`dequantize_param` casts back to when no dtype is
    given). Registered as a pytree node so quantized trees flow through
    ``jax.jit`` / ``device_put`` / ``tree_map`` unchanged — shardings
    trees mirror the same structure (a QuantizedParam whose children
    are NamedShardings)."""

    __slots__ = ("q", "scale", "orig_dtype", "block")

    def __init__(self, q, scale, orig_dtype, block: int):
        self.q = q
        self.scale = scale
        self.orig_dtype = jnp.dtype(orig_dtype)
        self.block = int(block)

    @property
    def shape(self):
        return self.q.shape

    @property
    def ndim(self):
        return self.q.ndim

    @property
    def dtype(self):
        # the logical dtype callers see (what dequant produces); the
        # storage dtype is int8 + fp32 scales
        return self.orig_dtype

    @property
    def nbytes(self) -> int:
        return int(getattr(self.q, "nbytes", 0)) + \
            int(getattr(self.scale, "nbytes", 0))

    def tree_flatten(self):
        return (self.q, self.scale), (self.orig_dtype, self.block)

    @classmethod
    def tree_unflatten(cls, aux, children):
        q, scale = children
        return cls(q, scale, aux[0], aux[1])

    def __repr__(self):
        return (f"QuantizedParam(shape={tuple(np.shape(self.q))}, "
                f"block={self.block}, orig_dtype={self.orig_dtype})")


def quantize_param(x, block: int = DEFAULT_WEIGHT_BLOCK) -> QuantizedParam:
    """Symmetric int8 absmax quantization per ``block`` values along the
    last axis. ``q`` keeps x's shape; ``scale`` is ``lead + (nb,)``."""
    x = jnp.asarray(x)
    d = x.shape[-1]
    nb = -(-d // block)
    pad = nb * block - d
    xf = x.astype(jnp.float32)
    if pad:
        xf = jnp.pad(xf, [(0, 0)] * (x.ndim - 1) + [(0, pad)])
    blocks = xf.reshape(x.shape[:-1] + (nb, block))
    absmax = jnp.max(jnp.abs(blocks), axis=-1)
    scale = jnp.where(absmax > 0, absmax / 127.0, 1.0).astype(jnp.float32)
    q = jnp.clip(jnp.round(blocks / scale[..., None]), -127, 127)
    q = q.reshape(x.shape[:-1] + (nb * block,))[..., :d].astype(jnp.int8)
    return QuantizedParam(q, scale, x.dtype, block)


def dequantize_param(p: QuantizedParam, dtype=None):
    """Per-block dequant back to ``dtype`` (default: the original dtype).
    Traceable — this is the in-program dequant the quantized matmul path
    calls right before each weight use."""
    d = p.q.shape[-1]
    block = p.block
    nb = p.scale.shape[-1]
    s = jnp.repeat(p.scale, block, axis=-1)
    if nb * block != d:
        s = s[..., :d]
    out = p.q.astype(jnp.float32) * s
    return out.astype(dtype if dtype is not None else p.orig_dtype)


def _is_qp(x) -> bool:
    return isinstance(x, QuantizedParam)


def quantize_param_tree(params, block: int = DEFAULT_WEIGHT_BLOCK):
    """Quantize every floating >=2-D leaf of ``params`` (matmul weights
    and embeddings); 1-D leaves (biases, layer norms) stay dense — their
    bytes are negligible and quantizing them buys nothing. Already-
    quantized leaves pass through unchanged, so re-quantizing a mixed or
    fully quantized tree is a no-op (the swap path relies on this)."""
    def one(x):
        if _is_qp(x):
            return x
        if getattr(x, "ndim", 0) >= 2 and \
                jnp.issubdtype(jnp.asarray(x).dtype, jnp.floating):
            return quantize_param(x, block)
        return x
    return jax.tree_util.tree_map(one, params, is_leaf=_is_qp)


def dequantize_param_tree(params, dtype=None):
    """The fp oracle view of a (possibly) quantized tree."""
    return jax.tree_util.tree_map(
        lambda x: dequantize_param(x, dtype) if _is_qp(x) else x,
        params, is_leaf=_is_qp)


def is_quantized_tree(params) -> bool:
    return any(_is_qp(leaf) for leaf in jax.tree_util.tree_leaves(
        params, is_leaf=_is_qp))


def _leaf_bytes(x) -> int:
    if _is_qp(x):
        return x.nbytes
    size = int(np.prod(np.shape(x))) if np.shape(x) else 1
    return size * jnp.dtype(getattr(x, "dtype", jnp.float32)).itemsize


def param_tree_bytes(params) -> int:
    """Resident HBM bytes of a param tree (quantized leaves count int8
    payload + fp32 scales): the weight-HBM lever."""
    return sum(_leaf_bytes(leaf) for leaf in jax.tree_util.tree_leaves(
        params, is_leaf=_is_qp))


def quantized_tree_bytes(params) -> Tuple[int, int]:
    """(quantized_bytes, dense_bytes) of the SAME tree — dense counts
    every quantized leaf at its original dtype. The ratio is the
    ``quant_serving_bytes`` weight lever."""
    quant = param_tree_bytes(params)
    def dense_one(x):
        if _is_qp(x):
            size = int(np.prod(x.shape))
            return size * jnp.dtype(x.orig_dtype).itemsize
        return _leaf_bytes(x)
    dense = sum(dense_one(leaf) for leaf in jax.tree_util.tree_leaves(
        params, is_leaf=_is_qp))
    return quant, dense


def map_quantized(params, fn, dense_fn=None):
    """tree_map with QuantizedParam as a leaf: ``fn`` on quantized
    leaves, ``dense_fn`` (default identity) elsewhere. The shardings
    builder uses this to mirror tree structure."""
    dense_fn = dense_fn or (lambda x: x)
    return jax.tree_util.tree_map(
        lambda x: fn(x) if _is_qp(x) else dense_fn(x),
        params, is_leaf=_is_qp)
