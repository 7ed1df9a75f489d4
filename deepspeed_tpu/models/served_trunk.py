"""The decoder trunk the SERVED hybrid families share
(``models/solar_open2.py``, ``models/granite_hybrid.py``,
``models/axk1.py``): embed, a loop over the layers that norms, calls the
layer's mixer with its cache, adds the residual and runs the
feed-forward half (a dense SwiGLU, or routed experts beside a shared
one), then the final norm, each row's last true position in a served
prefill, the head, the experts' counters and the cache tree.

A family is a :class:`ServedFamily`: each layer's mixer and feed-forward
kind (read from what its config states), the constants only it has (one
left None is ABSENT from its program, not a multiply by one), and a
function a mixer kind, ``mixer(lp, h, call, cache, n) -> (y, cache)``:
the layer's parameters, the normed activations (B, S, H), the
:class:`_Call` the trunk built once, the cache tree as the engine built
it (None: the plain forward) and the layer's index among those of its
kind; back come its output and the tree with ITS leaves replaced. The
trunk names no leaf.
"""

from typing import Any, Callable, Dict, NamedTuple, Optional, Tuple

import jax
import jax.numpy as jnp
from jax.sharding import PartitionSpec

from deepspeed_tpu.ops.attention.page_pool import paged_write_index
from deepspeed_tpu.ops.functional import rms_norm
from deepspeed_tpu.ops.moe import held_experts_every_row, served_experts
from deepspeed_tpu.profiling.spans import scope


def _norm(x, w, eps):
    with scope("ln"):
        return rms_norm(x, w, eps)


def _mm(x, w, dtype):
    return jnp.dot(x.astype(dtype), w.astype(dtype),
                   preferred_element_type=jnp.float32)


def _swiglu(p, flat, dtype):
    act = jax.nn.silu(_mm(flat, p["w_gate"], dtype)) * _mm(
        flat, p["w_up"], dtype)
    return _mm(act, p["w_down"], dtype)


class _Pages(NamedTuple):
    """Where a layer's rows go in the page pool and come from."""
    pools: Any          # the pools as the family finds them in its tree
    layer: int          # among the layers that page
    tables: Any
    positions: Any
    index: Any          # page_pool.paged_write_index's, shared by the layers
    reader: str


class _Call(NamedTuple):
    """What one call's layers share, built once by the trunk. Serving
    fields are None in the plain forward."""
    config: Any
    dtype: Any
    tables: Any             # (B, pages a sequence) block tables
    positions: Any          # (B,) each row's first position in its stream
    index: Any              # page_pool.paged_write_index's
    reader: str             # the engine's decode reader, by name
    lengths: Any            # (B,) true lengths of a served prefill's rows
    slots: Any              # (B,) their rows of the per-slot pools
    token_positions: Any    # (B, S), where the family's mixers rotate
    active: Any = None      # (B,) bool: the rows of a decode that decode
    # a served prefill's rows may start past position 0 (a later CHUNK
    # of a prompt): a mixer then starts from what its predecessors left
    # (the slot's state and tail; the latent rows in the pool), and a
    # decode leaves an inactive row's state and tail as they are
    carry: bool = False


class ServedFamily(NamedTuple):
    """What the trunk is told of a family (module docstring)."""
    # a layer: (mixer kind, "dense" | "experts")
    layers: Tuple[Tuple[str, str], ...]
    mixers: Dict[str, Callable]
    # (flat (T, H) float32, the layer's router) -> (idx, weights, facts)
    route: Optional[Callable] = None
    expert_tile: Optional[Tuple[int, int, int]] = None
    # (the router's facts, active rows or None) -> (B,) bool: one more
    # DECODE counter, the rows it counts
    decode_rows: Optional[Callable] = None
    embedding_multiplier: Optional[float] = None
    residual_multiplier: Optional[float] = None
    logits_scaling: Optional[float] = None
    head: str = "lm_head"           # "tok_emb": the table, tied
    token_positions: bool = False
    # the family's mixers follow chunked prefill (``_Call.carry``)
    chunked: bool = False


def paged_pair_mixer(softmax_mixer, positions=False):
    """``softmax_mixer(ap, config, h, dtype, _Pages or None) -> (y, the
    pools)`` as the trunk calls a mixer: the ``n``-th paged layer over
    the ``keys`` and ``values`` of a cache tree that names them
    (``kv_cache.PagedStateCache``, ``PagedTailCache``). With
    ``positions`` the mixer rotates: it also takes the trunk's
    ``token_positions`` (B, S)."""
    def mixer(lp, h, call, cache, n):
        pages = None if cache is None else _Pages(
            (cache.keys, cache.values), n, call.tables, call.positions,
            call.index, call.reader)
        more = (call.token_positions,) if positions else ()
        y, pools = softmax_mixer(lp["attn"], call.config, h, call.dtype,
                                 pages, *more)
        if cache is None:
            return y, None
        return y, cache._replace(keys=pools[0], values=pools[1])
    return mixer


def whole_leaf_specs(init, config):
    """Only the single-device engine serves these families
    (``inference/engine.py`` refuses a serving mesh): every leaf of
    ``init(config, key)``'s tree whole."""
    return jax.tree_util.tree_map(
        lambda _: PartitionSpec(), jax.eval_shape(
            lambda: init(config, jax.random.PRNGKey(0))))


def _expert_half(lp, family, x, call, active):
    """x -> (x + r (routed [+ shared]), this layer's int32 counters). One
    token a row (decode) works every held expert on every row, and the
    counters are (landed, fullest): assignments of ``active`` rows that
    fell on held experts, and the fullest held expert's (then the
    family's ``decode_rows``, summed). A bucket of prompts goes through
    ``ops.moe.served_experts``, whose work follows the assignments that
    landed here at a TRUE position (``call.lengths`` (B,); None, the
    plain forward: every position), and the counters are (rows its turns
    worked, rows the dropless layer's static turns would have). Not
    ``dropless_experts``: that is the TRAINED layer, whose time must not
    follow the router and whose turns differentiate; what a padded
    position's experts give is read by nothing (causal attention, a scan
    to the true lengths, the logits of the last true position). A layer
    with a ``shared`` expert adds it; one without has none in its
    program."""
    config, dtype = call.config, call.dtype
    B, S, hdim = x.shape
    h2 = _norm(x, lp["ln_2"]["w"], config.rms_norm_eps)
    flat = h2.reshape(B * S, hdim)
    with scope("moe_route"):
        # a correction bias an expert, where the layer has one
        bias = [lp["router_bias"]] if "router_bias" in lp else []
        idx, p, facts = family.route(flat, lp["router"], *bias)
    experts = {n: t.astype(dtype) for n, t in lp["experts"].items()}
    rows = flat.astype(dtype)
    if S == 1:
        y, counts = held_experts_every_row(
            rows, idx, p, experts, config.held, jax.nn.silu, active)
        more = []
        if family.decode_rows is not None:
            with scope("moe_route"):
                more = [family.decode_rows(facts, active)]
        counters = jnp.stack(
            [jnp.sum(counts), jnp.max(counts)]
            + [jnp.sum(here, dtype=jnp.int32) for here in more])
    else:
        counted = None if call.lengths is None else (
            jnp.arange(S) < call.lengths[:, None]).reshape(B * S)
        y, _, counters = served_experts(
            rows, idx, p, experts, config.held, config.num_experts,
            jax.nn.silu, tile=family.expert_tile, counted=counted)
    if "shared" in lp:
        with scope("moe_shared"):
            y = y + _swiglu(lp["shared"], flat, dtype)
    with scope("moe_dispatch"):
        x = _residual(family, x, y.reshape(B, S, hdim))
    return x, counters


def _residual(family, x, y):
    r = family.residual_multiplier
    return x + (y if r is None else r * y)


def served_forward(family: ServedFamily, params, config, input_ids, dtype,
                   kv_cache, cache_position, block_tables,
                   paged_attn_kernel, lengths, slots, active, with_counts):
    """The forward of ``solar_open2_forward`` / ``granite_hybrid_forward``
    / ``axk1_forward`` (their arguments, their returns) for the family
    described: plain (``kv_cache=None``) (B, S) ids -> (B, S, rows)
    float32 logits; serving -> (logits, the cache tree) and, with
    ``with_counts``, the expert layers' counters stacked (int32). A
    served PREFILL (S > 1) returns the logits at each row's LAST true
    position only, (B, 1, rows)."""
    missing = {kind for kind, _ in family.layers} - set(family.mixers)
    if missing:
        raise ValueError(f"the family describes layers of kind "
                         f"{sorted(missing)} and gives no mixer for them")
    B, S = input_ids.shape
    serving = kv_cache is not None
    index = None
    if serving:
        if cache_position is None:
            cache_position = jnp.zeros((B,), jnp.int32)
        # a cache tree's first leaf is a page pool, whatever follows it
        index = paged_write_index(block_tables, cache_position, S,
                                  kv_cache[0].shape[2])
        if S > 1:
            assert lengths is not None, \
                "a served prefill needs each row's length"
    token_positions = None
    if family.token_positions:
        start = cache_position if serving else jnp.zeros((B,), jnp.int32)
        token_positions = start[:, None] + jnp.arange(S)[None, :]
    call = _Call(config, dtype, block_tables, cache_position, index,
                 paged_attn_kernel, lengths, slots, token_positions,
                 active, serving and family.chunked)
    eps = config.rms_norm_eps
    with scope("embed"):
        x = params["tok_emb"][input_ids].astype(jnp.float32)
        if family.embedding_multiplier is not None:
            x = family.embedding_multiplier * x
    counts = []
    seen: Dict[str, int] = {}       # layers of each mixer kind so far
    cache = kv_cache
    for l, (kind, feed_forward) in enumerate(family.layers):
        lp = params[f"h_{l}"]
        h = _norm(x, lp["ln_1"]["w"], eps)
        n = seen.get(kind, 0)
        seen[kind] = n + 1
        y, cache = family.mixers[kind](lp, h, call, cache, n)
        x = _residual(family, x, y)
        if feed_forward == "dense":
            h2 = _norm(x, lp["ln_2"]["w"], eps)
            with scope("mlp"):
                x = _residual(family, x, _swiglu(
                    lp["mlp"], h2.reshape(B * S, -1), dtype).reshape(x.shape))
        else:
            x, c = _expert_half(lp, family, x, call, active)
            counts.append(c)
    x = _norm(x, params["ln_f"]["w"], eps)
    if serving and S > 1:
        x = x[jnp.arange(B), lengths - 1][:, None]
    with scope("lm_head"):
        logits = jax.lax.dot_general(
            x.astype(dtype), params[family.head].astype(dtype),
            (((2,), (1,)), ((), ())), preferred_element_type=jnp.float32)
        if family.logits_scaling is not None:
            logits = logits / family.logits_scaling
    if not serving:
        return logits
    if with_counts:
        return logits, cache, jnp.stack(counts).astype(jnp.int32)
    return logits, cache
