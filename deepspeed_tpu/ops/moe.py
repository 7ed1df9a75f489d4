"""Mixture-of-Experts layer with expert parallelism (TPU-native).

Beyond-reference extension (the DeepSpeed v0.3.0 snapshot has no MoE —
SURVEY.md §2.3 "No MoE/expert parallelism"): completes the ep member of
the tp/pp/dp/sp/ep parallelism family on the same named-mesh design as
the rest of the framework.

Design (GShard/Switch-style, XLA-first):
- Static shapes end to end: top-k routing is expressed as one-hot
  dispatch/combine tensors (T, E, C) — no dynamic gathers, no
  data-dependent shapes, so the whole layer jits and shards cleanly.
- Capacity: each expert owns C = ceil(top_k * T * capacity_factor / E)
  slots; tokens beyond an expert's capacity are dropped for that expert
  (their gate mass is simply lost, GShard semantics). Positions are
  assigned in token order via cumsum — second choices queue behind all
  first choices (GShard's priority rule).
- Expert parallelism = GSPMD: the (E, C, H) expert tensors carry a
  sharding constraint over the ``expert`` mesh axis; XLA inserts the
  all_to_all between the token-sharded and expert-sharded layouts —
  no hand-written collective, which is the named-axis analog of the
  reference's NCCL groups.
- Aux losses ride with the output: Switch load-balance loss
  (E * sum_e f_e * p_e) and router z-loss (mean logsumexp^2), both fp32.
"""

import dataclasses
import functools
from typing import Optional

import jax
import jax.numpy as jnp
import numpy as np

from deepspeed_tpu.profiling.spans import scope


@dataclasses.dataclass
class MoEConfig:
    hidden_size: int
    intermediate_size: int
    num_experts: int
    top_k: int = 2
    capacity_factor: float = 1.25
    load_balance_coef: float = 1e-2
    router_z_coef: float = 1e-3

    def __post_init__(self):
        assert self.top_k >= 1, self.top_k
        assert self.num_experts >= self.top_k, (self.num_experts,
                                                self.top_k)


def init_moe_params(config: MoEConfig, key, dtype=jnp.float32):
    """{"router": (H, E), "wi": (E, H, F), "wo": (E, F, H)}."""
    kr, ki, ko = jax.random.split(key, 3)
    h, f, e = (config.hidden_size, config.intermediate_size,
               config.num_experts)
    return {
        "router": (jax.random.normal(kr, (h, e)) * 0.02).astype(dtype),
        "wi": (jax.random.normal(ki, (e, h, f)) * 0.02).astype(dtype),
        "wo": (jax.random.normal(ko, (e, f, h)) * 0.02).astype(dtype),
    }


def expert_capacity(config: MoEConfig, num_tokens: int) -> int:
    c = int(np.ceil(config.top_k * num_tokens * config.capacity_factor
                    / config.num_experts))
    return max(c, 1)


def _one_hot_positions(mask, capacity, start_counts):
    """Slot positions for one routing choice: mask (T, E) 0/1; tokens take
    slots in token order, starting after ``start_counts`` (E,) already-used
    slots. Returns (pos (T, E) int32, kept (T, E) bool, counts (E,))."""
    pos = jnp.cumsum(mask, axis=0) - 1 + start_counts[None, :]
    kept = jnp.logical_and(mask > 0, pos < capacity)
    counts = start_counts + jnp.sum(mask, axis=0)
    return pos.astype(jnp.int32), kept, counts


def moe_router(params, config: MoEConfig, x_tokens):
    """Routing: x_tokens (T, H) -> (dispatch (T, E, C) f32 0/1,
    combine (T, E, C) f32, aux_loss f32 scalar).

    fp32 router math (softmax over expert logits is tiny and
    precision-sensitive; reference-free design choice matching public
    MoE practice)."""
    t = x_tokens.shape[0]
    e = config.num_experts
    c = expert_capacity(config, t)

    logits = jnp.einsum("th,he->te", x_tokens.astype(jnp.float32),
                        params["router"].astype(jnp.float32))
    probs = jax.nn.softmax(logits, axis=-1)               # (T, E)

    # --- top-k choices (static unroll over k): each round takes the
    # argmax of the remaining probs; earlier rounds claim capacity slots
    # first on ties (GShard priority — round r's choices take slots
    # before any round r+1 choice)
    remaining = probs
    counts = jnp.zeros((e,), jnp.int32)
    choices = []                                          # (mask, gate, pos, kept)
    for _ in range(config.top_k):
        idx = jnp.argmax(remaining, axis=-1)              # (T,)
        mask = jax.nn.one_hot(idx, e, dtype=jnp.float32)  # (T, E)
        gate = jnp.sum(probs * mask, axis=-1)             # (T,)
        pos, kept, counts = _one_hot_positions(mask, c, counts)
        choices.append((mask, gate, pos, kept))
        remaining = remaining * (1.0 - mask)
    if config.top_k > 1:
        # renormalize over the selected gates (GShard)
        denom = jnp.maximum(sum(g for _, g, _, _ in choices), 1e-9)
    else:
        denom = 1.0

    def scatter(kept, pos, gate):
        # (T, E, C): one-hot over the capacity slot, weighted by the gate
        slot = jax.nn.one_hot(pos, c, dtype=jnp.float32)  # (T, E, C)
        d = slot * kept[..., None].astype(jnp.float32)
        return d, d * gate[:, None, None]

    dispatch = jnp.zeros((t, e, c), jnp.float32)
    combine = jnp.zeros((t, e, c), jnp.float32)
    for mask, gate, pos, kept in choices:
        d_r, w_r = scatter(kept, pos, gate / denom)
        dispatch = dispatch + d_r
        combine = combine + w_r

    # Switch load-balance loss: fraction of tokens routed (first choice)
    # vs mean router probability, per expert
    f_e = jnp.mean(choices[0][0], axis=0)
    p_e = jnp.mean(probs, axis=0)
    lb = config.load_balance_coef * e * jnp.sum(f_e * p_e)
    z = config.router_z_coef * jnp.mean(
        jax.nn.logsumexp(logits, axis=-1) ** 2)
    return dispatch, combine, lb + z


# The expert math shared by both dispatch forms (moe_layer injects a
# GSPMD sharding constraint around the (E, C, H) slot tensors; the
# sharded form injects the all_to_all pair) — one implementation, so the
# two forms cannot drift.
def _dispatch_slots(dispatch, xt, dtype):
    return jnp.einsum("tec,th->ech", dispatch.astype(dtype),
                      xt.astype(dtype))


def _expert_ffn(slots, wi, wo, dtype):
    hdn = jax.nn.gelu(jnp.einsum("ech,ehf->ecf", slots, wi.astype(dtype)))
    return jnp.einsum("ecf,efh->ech", hdn, wo.astype(dtype))


def _combine_tokens(combine, out, dtype):
    return jnp.einsum("tec,ech->th", combine.astype(dtype), out)


def moe_layer(params, config: MoEConfig, x, *,
              expert_axis: Optional[str] = None, mesh=None,
              dtype=jnp.bfloat16):
    """MoE FFN: x (B, S, H) -> (y (B, S, H), aux_loss scalar fp32).

    ``expert_axis``: mesh axis name to shard experts over (expert
    parallelism); None = fully replicated experts. The constraint is all
    GSPMD needs — it inserts the token<->expert all_to_all pair. Pass
    ``mesh`` when calling outside a ``with mesh:`` context (e.g. from
    the engine's compiled step, which jits with explicit shardings).

    Scale note: routing is formulated over the GLOBAL token set (T =
    B*S), so expert buffers are (E, C_global, H) — exact and simple, and
    what the tests pin, but the dispatch collective grows with the data
    degree, AND the one-hot dispatch/combine tensors are (T, E, C) with
    E*C ~= top_k*capacity_factor*T, i.e. ~2.5*T^2 elements per MoE layer
    — at T=16k global tokens that is ~2.6GB fp32 of HBM per layer,
    which OOMs before the collective-growth concern bites. Above a few
    thousand global tokens use :func:`moe_layer_sharded` (per-shard
    dispatch under shard_map: local capacity, explicit all_to_all);
    the kernel math here is unchanged by that wrapping."""
    b, s, h = x.shape
    xt = x.reshape(b * s, h)
    dispatch, combine, aux = moe_router(params, config, xt)

    def constrain(v):
        if expert_axis is None:
            return v
        from jax.lax import with_sharding_constraint as wsc
        from jax.sharding import NamedSharding, PartitionSpec as P
        spec = P(expert_axis, None, None)
        if mesh is not None:
            return wsc(v, NamedSharding(mesh, spec))
        return wsc(v, spec)

    slots = constrain(_dispatch_slots(dispatch, xt, dtype))
    out = constrain(_expert_ffn(slots, params["wi"], params["wo"], dtype))
    y = _combine_tokens(combine, out, dtype)
    return y.reshape(b, s, h).astype(x.dtype), aux


def moe_layer_reference(params, config: MoEConfig, x):
    """Token-loop numpy oracle with identical routing/capacity/priority
    semantics — the test ground truth."""
    b, s, h = x.shape
    xt = np.asarray(x, np.float32).reshape(b * s, h)
    router = np.asarray(params["router"], np.float32)
    wi = np.asarray(params["wi"], np.float32)
    wo = np.asarray(params["wo"], np.float32)
    e = config.num_experts
    c = expert_capacity(config, xt.shape[0])

    logits = xt @ router
    probs = np.exp(logits - logits.max(-1, keepdims=True))
    probs /= probs.sum(-1, keepdims=True)

    arange = np.arange(len(xt))
    idxs, gates = [], []
    p = probs.copy()
    for _ in range(config.top_k):
        idx = p.argmax(-1)
        gates.append(probs[arange, idx])
        idxs.append(idx)
        p[arange, idx] = 0.0
    if config.top_k > 1:
        denom = np.maximum(sum(gates), 1e-9)
        gates = [g / denom for g in gates]
    choices = [(r, ti, idxs[r][ti], gates[r][ti])
               for r in range(config.top_k) for ti in range(len(xt))]

    used = np.zeros(e, np.int32)
    y = np.zeros_like(xt)
    # first choices take slots before any second choice (GShard priority)
    for _, ti, ei, g in sorted(choices, key=lambda t: t[0]):
        if used[ei] < c:
            used[ei] += 1
            hdn = _np_gelu(xt[ti] @ wi[ei])
            y[ti] += g * (hdn @ wo[ei])
    return y.reshape(b, s, h)


def _np_gelu(x):
    return 0.5 * x * (1.0 + np.tanh(np.sqrt(2.0 / np.pi) *
                                    (x + 0.044715 * x ** 3)))


def moe_layer_sharded(params, config: MoEConfig, x, mesh,
                      expert_axis: str = "expert", dtype=jnp.bfloat16):
    """Per-shard MoE dispatch under ``shard_map`` — the scalable form of
    :func:`moe_layer` for large meshes.

    Tokens AND experts shard over ``expert_axis`` (the classic
    single-axis MoE layout): each of the P devices routes its local
    T/P tokens with local capacity C_l = ceil(top_k * T_l * cf / E),
    then one explicit ``all_to_all`` pair swaps the (E, C_l, H) slot tensors
    so every device holds its E/P experts' slots from all peers —
    collective payload per device is capacity-bound (E * C_l * H),
    independent of the data degree, where the GSPMD global formulation
    grows with it. Semantics match moe_layer except capacity/priority
    are per shard (identical when nothing overflows).

    x: (B, S, H) with B divisible by the axis size; params as
    init_moe_params (router replicated; wi/wo sharded over experts).
    Returns (y, aux) like moe_layer (aux is the mean over shards).
    """
    from jax.sharding import PartitionSpec as P

    p_size = mesh.shape[expert_axis]
    e = config.num_experts
    assert e % p_size == 0, (e, p_size)
    b = x.shape[0]
    assert b % p_size == 0, (x.shape, p_size)

    def shard_fn(router, wi, vo, xs):
        bs, ss, h = xs.shape
        xt = xs.reshape(bs * ss, h)
        dispatch, combine, aux = moe_router(
            {"router": router}, config, xt)
        # (T_l, E, C_l) x (T_l, H) -> (E, C_l, H) local slots
        slots = jnp.einsum("tec,th->ech", dispatch.astype(dtype),
                           xt.astype(dtype))
        # swap: split experts across peers, gather peers' slots for ours
        slots = jax.lax.all_to_all(slots, expert_axis, split_axis=0,
                                   concat_axis=1, tiled=True)
        hdn = jax.nn.gelu(jnp.einsum("ech,ehf->ecf", slots,
                                     wi.astype(dtype)))
        out = jnp.einsum("ecf,efh->ech", hdn, vo.astype(dtype))
        # swap back: return each peer its tokens' outputs
        out = jax.lax.all_to_all(out, expert_axis, split_axis=1,
                                 concat_axis=0, tiled=True)
        y = jnp.einsum("tec,ech->th", combine.astype(dtype), out)
        aux = jax.lax.pmean(aux, expert_axis)
        return y.reshape(bs, ss, h).astype(xs.dtype), aux

    fn = jax.shard_map(
        shard_fn, mesh=mesh,
        in_specs=(P(), P(expert_axis, None, None),
                  P(expert_axis, None, None), P(expert_axis, None, None)),
        out_specs=(P(expert_axis, None, None), P()),
        check_vma=False)
    return fn(params["router"], params["wi"], params["wo"], x)


# --------------------------------------------------------------------- #
# dropless routed experts over the experts HELD HERE
# --------------------------------------------------------------------- #
# The one-hot path above costs ~2.5*T^2 elements a layer and drops what
# overflows a capacity. This path drops nothing: every (token, choice)
# assignment that names an expert this chip holds is sorted by expert,
# the held experts run as ONE grouped matrix product per weight table
# (rows of expert e contiguous, `group_sizes[e]` of them), and the rows
# go back to their tokens weighted by the router. The layer routes over
# ALL experts and computes its own experts' part of the result (what
# expert parallelism asks of a chip); what the absent experts would add
# is left out, and nothing here stands in for their chips.
#
# Shapes are static, counts are not, and the TIME IS STATIC TOO. The
# sorted assignments are worked off in turns of `chunk_rows` rows (twice
# this chip's even share, held / num_experts of the assignments; all of
# them where that is less), as many turns as cover EVERY assignment, and
# a turn works its whole buffer: the rows that no landed assignment
# fills are given to the last group as rows of weight zero. So nothing
# can overflow, whatever the imbalance, and a step costs the same
# whatever the router does: with random weights under training the
# landed share follows the trajectory (one layer of four at 75-98% of
# its assignments on a quarter of the experts, another at 5%:
# docs/smallthinker.md), and a layer whose time follows it cannot be
# timed to a fraction of a percent. The price is the padding: the
# grouped products run over every assignment's row, landed or not. A
# chip that holds every expert has no padding and one turn. Each turn
# is recomputed in the backward pass, so the layer keeps only its
# inputs.
#
# That is what a TRAIN step needs. A SERVED prefill needs the opposite,
# work that follows the rows whose result is read, and has its own entry
# point, `served_experts`: the same sort and grouped product under a
# loop whose trip count follows the rows that count. Which of the two a
# program runs is its caller's choice.


def route_top_k(router_in, w_router, top_k: int):
    """float32 router: ``r = router_in @ w_router`` (T, E) at full
    precision, the ``top_k`` largest per token, softmax over THOSE only.
    Returns (idx (T, k) int32, p (T, k) f32, r (T, E) f32)."""
    r = jnp.dot(router_in.astype(jnp.float32),
                w_router.astype(jnp.float32),
                precision=jax.lax.Precision.HIGHEST)
    top, idx = jax.lax.top_k(r, top_k)
    return idx.astype(jnp.int32), jax.nn.softmax(top, axis=-1), r


def route_group_limited(router_in, w_router, top_k: int, n_group: int,
                        topk_group: int, scale: float = 1.0, bias=None):
    """float32 router by SIGMOID scores with the choice limited to the
    best groups (models/axk1.py): ``p = sigmoid(router_in @ w_router)``
    (T, E) at full precision; the E experts are ``n_group`` groups of
    consecutive ``E / n_group``; a group's score is the sum of its two
    largest p; the ``topk_group`` best groups are kept; the ``top_k``
    largest p among THEIR experts are chosen, and their weights are
    ``p_i / sum p_i * scale``. Ties go to the lower index, among groups
    and among experts (``lax.top_k``). ``bias`` (E,) float32 (None: the
    family has none) is a correction an expert for the CHOICE only
    (models/kimi_linear.py): groups and experts are ranked by ``p +
    bias``, the weights are made of ``p`` alone. Returns (idx (T, k)
    int32, w (T, k) f32, p (T, E) f32, the groups kept (T, topk_group)
    int32)."""
    p = jax.nn.sigmoid(jnp.dot(router_in.astype(jnp.float32),
                               w_router.astype(jnp.float32),
                               precision=jax.lax.Precision.HIGHEST))
    t, e = p.shape
    ranked = p if bias is None else p + bias.astype(jnp.float32)
    grouped = ranked.reshape(t, n_group, e // n_group)
    _, kept = jax.lax.top_k(
        jnp.sum(jax.lax.top_k(grouped, 2)[0], axis=-1), topk_group)
    open_ = jnp.any(kept[:, :, None] == jnp.arange(n_group), axis=1)
    # a sigmoid is positive: -1 stands behind every expert of a kept
    # group (under a bias: what no ranked score falls to)
    floor = -1.0 if bias is None else jnp.min(ranked) - 1.0
    masked = jnp.where(open_[:, :, None], grouped, floor).reshape(t, e)
    top, idx = jax.lax.top_k(masked, top_k)
    if bias is not None:
        top = jnp.take_along_axis(p, idx, axis=-1)
    w = top / jnp.sum(top, axis=-1, keepdims=True) * scale
    return idx.astype(jnp.int32), w, p, kept.astype(jnp.int32)


def chunk_rows(assignments: int, held: int, num_experts: int) -> int:
    """Rows of a turn's buffer: twice this chip's even share of the
    assignments, a multiple of 512 (a grouped product's row tile), at
    most every assignment."""
    up = lambda n: -(-n // 512) * 512
    return min(up(-(-assignments * held * 2 // num_experts)),
               up(assignments))


# (rows, contraction, columns) of a grouped product's tile, each cut to
# the operand: measured on a v5e at the 21B-A3B expert's shapes
# (docs/smallthinker.md): 32,768 x 2,560 x 768 over 16 groups
_GMM_TILE = (512, 1280, 768)


def _whole_tile(dim: int, cap: int) -> int:
    """The largest multiple of 128 up to ``cap`` that divides ``dim``
    (``dim`` itself where it is smaller or none does)."""
    fits = [t for t in range(128, min(cap, dim) + 1, 128) if dim % t == 0]
    return fits[-1] if fits else min(cap, dim)


def grouped_matmul(lhs, rhs, group_sizes, tile=None):
    """``lhs[rows of group g] @ rhs[g]`` for every group: lhs (M, K),
    rhs (G, K, N), group_sizes (G,) int32 with sum <= M -> (M, N) in
    lhs's dtype, float32 accumulation; M a multiple of 512. Only the row
    tiles the groups cover are computed; rows past the groups' sum hold
    nothing a caller may read. The Pallas grouped product that ships
    with JAX (megablox: ``gmm`` forward, ``gmm`` against the transposed
    table and ``tgmm`` backward), chosen over ``jax.lax.ragged_dot`` by a
    chip measurement (docs/smallthinker.md). ``tile`` (rows,
    contraction, columns) caps the tile in place of ``_GMM_TILE``, each
    side then cut to a whole divisor of the operand (a layer of other
    widths: docs/solar_open2.md)."""
    from jax.experimental.pallas.ops.tpu.megablox import gmm
    if tile is None:
        tm, tk, tn = _GMM_TILE
        tiling = (tm, min(tk, rhs.shape[1]), min(tn, rhs.shape[2]))
    else:
        tiling = (tile[0], _whole_tile(rhs.shape[1], tile[1]),
                  _whole_tile(rhs.shape[2], tile[2]))
    return gmm(lhs, rhs, group_sizes, preferred_element_type=lhs.dtype,
               tiling=tiling, interpret=jax.default_backend() != "tpu")


# tokens a block of the TRAINED layer's pick-sums (the served combine
# passes its own): of 512, 1,024 and 2,048 the fastest on a v5e, the
# layer alone at train-8k's shapes (docs/smallthinker.md "Counters and
# scopes")
_TRAINED_BLOCK = 2048


def _weighted_rows(rows, w, pos, here, block):
    """(T, H) float32: token t sums ``w[t, j] * rows[pos[t, j]]`` over
    its choices j that are ``here``, ``block`` tokens at a time (all of
    them at once where ``block`` does not divide T) so that nothing of
    (T, k, H) is ever held. A block's picks lie a CHOICE a slab,
    (k, block, H), and the slabs add up: (block, k, H) would put the k
    choices on the sublanes (six or ten padded to sixteen) and pay a
    relayout of every picked row before it sums them (granite's prefill
    on the chip: 10.2 ms a layer at 8,192 tokens against 5.6 in slabs of
    512; docs/solar_open2.md). ONE layout for the trained layer's two
    pick-sums and the served combine: they differ in ``block`` alone."""
    t, k = pos.shape
    block = block if t % block == 0 else t

    def one(args):
        pos_b, w_b, here_b = args
        picked = jnp.where(here_b[..., None], rows[pos_b], 0)
        return jnp.sum(picked.astype(jnp.float32) * w_b[..., None], axis=0)

    split = lambda a: a.T.reshape(k, t // block, block).swapaxes(0, 1)
    return jax.lax.map(one, (split(pos), split(w), split(here))
                       ).reshape(t, rows.shape[1])


@jax.custom_vjp
def _take_rows(x, tok, pos, here):
    """x (T, H) -> x[tok] (B, H). The backward pass is a gather too
    (each token sums the rows its assignments sit in), never a
    scatter-add."""
    return x[tok]


def _take_rows_fwd(x, tok, pos, here):
    return x[tok], (pos, here)


def _take_rows_bwd(res, g):
    pos, here = res
    ones = jnp.ones(pos.shape, jnp.float32)
    d_x = _weighted_rows(g, ones, pos, here, _TRAINED_BLOCK)
    return d_x.astype(g.dtype), None, None, None


_take_rows.defvjp(_take_rows_fwd, _take_rows_bwd)


@jax.custom_vjp
def _combine_rows(ys, w, tok, pos, here, w_sorted):
    """ys (B, H) expert outputs in sorted order -> (T, H): token t sums
    ``w[t, j] * ys[pos[t, j]]`` over its choices j that sit in this
    buffer, in float32."""
    return _weighted_rows(ys, w, pos, here, _TRAINED_BLOCK)


def _combine_rows_fwd(ys, w, tok, pos, here, w_sorted):
    return _combine_rows(ys, w, tok, pos, here, w_sorted), \
        (ys, tok, pos, here, w_sorted)


def _combine_rows_bwd(res, g):
    ys, tok, pos, here, w_sorted = res
    g_rows = g[tok]                                    # (B, H) f32
    d_ys = (g_rows * w_sorted[:, None]).astype(ys.dtype)
    d_w_sorted = jnp.sum(g_rows * ys.astype(jnp.float32), axis=-1)
    d_w = jnp.where(here, d_w_sorted[pos], 0.0)
    return d_ys, d_w, None, None, None, None


_combine_rows.defvjp(_combine_rows_fwd, _combine_rows_bwd)


def _sorted_plan(idx, p, experts_held, counted=None):
    """The sort both entry points share. An assignment (token, choice)
    COUNTS when its expert ``idx[t, j]`` is held here and, where
    ``counted`` (T,) bool is given, its token counts; the rest sort
    behind those that do. Returns (counts (held,) int32 of the
    assignments that count on each held expert, (order, pos, here,
    ends): sorted position -> assignment, assignment -> sorted position
    (T, k), which assignments count (T, k), the held experts' ends among
    the sorted rows, w (T, k) float32: ``p`` where it counts, else 0)."""
    first, count = experts_held
    t, top_k = idx.shape
    local = idx - first
    here = (local >= 0) & (local < count)
    if counted is not None:
        here = here & counted[:, None]
    key = jnp.where(here, local, count).reshape(-1)
    counts = jnp.sum(key[:, None] == jnp.arange(count)[None, :],
                     axis=0, dtype=jnp.int32)
    ends = jnp.cumsum(counts)
    # sorted position -> assignment (stable: token order inside an
    # expert), and assignment -> sorted position
    order = jnp.argsort(key, stable=True).astype(jnp.int32)
    pos = jnp.argsort(order).astype(jnp.int32).reshape(t, top_k)
    w = jnp.where(here, p, 0.0).astype(jnp.float32)
    return counts, (order, pos, here, ends), w


def _glu_chunk(rows, activation, tile, c, x, w, experts, plan):
    """The held experts on chunk ``c`` of the sorted assignments: rows
    [c * rows, (c + 1) * rows). Returns this chunk's part of y (T, H)."""
    order, pos, held, ends = plan
    top_k = w.shape[1]
    lo = c * rows
    with scope("moe_dispatch"):
        # (the buffer is a multiple of the row tile: it may pass the end)
        padded = jnp.pad(order, (0, (-order.shape[0]) % rows))
        sorted_a = jax.lax.dynamic_slice(padded, (lo,), (rows,))
        tok = sorted_a // top_k
        valid = lo + jnp.arange(rows) < ends[-1]
        here = held & (pos >= lo) & (pos < lo + rows)
        posc = jnp.clip(pos - lo, 0, rows - 1)
        w_sorted = jnp.where(valid, w.reshape(-1)[sorted_a], 0.0)
        clipped = jnp.clip(ends, lo, lo + rows)
        counts = jnp.diff(clipped, prepend=lo).astype(jnp.int32)
        # the room left goes to the last group: its rows weigh nothing
        counts = counts.at[-1].add(lo + rows - clipped[-1])
        xs = _take_rows(x, tok, posc, here)
    with scope("moe_experts"):
        gate = grouped_matmul(xs, experts["w_gate"], counts, tile)
        up = grouped_matmul(xs, experts["w_up"], counts, tile)
        act = jnp.where(valid[:, None], activation(gate) * up, 0)
        ys = grouped_matmul(act, experts["w_down"], counts, tile)
    with scope("moe_dispatch"):
        return _combine_rows(ys, w, tok, posc, here, w_sorted)


def dropless_experts(x, idx, p, experts, experts_held, num_experts,
                     activation, tile=None):
    """``y[t] = sum over the choices j of token t whose expert idx[t, j]
    is held here of p[t, j] * W_down,e (act(W_gate,e x[t]) * (W_up,e x[t]))``.

    x (T, H) in the compute dtype; idx, p (T, k) from
    :func:`route_top_k`; ``experts`` {"w_gate", "w_up": (held, H, F),
    "w_down": (held, F, H)} in the compute dtype; ``experts_held``
    (first, count) of the ``num_experts`` the router scores;
    ``activation`` the gate's (``jax.nn.relu``: ReGLU, SmallThinker's;
    ``jax.nn.silu``: SwiGLU); ``tile`` as :func:`grouped_matmul`'s.
    The TRAINED layer (``models/smallthinker.py``): static turns, each
    worked whole and recomputed in the backward pass; a turn sums a
    token's picks twice, the combine forward and the rows' backward,
    both :func:`_weighted_rows`. A served prefill calls
    :func:`served_experts`, which shares the sort, the grouped product
    and the combine with its layout, and not the loop; the caller picks,
    nothing here does.
    Returns
    (y (T, H) float32, counts (held,) int32: the assignments that landed
    on each held expert). No assignment is dropped whatever the
    imbalance, and the time does not follow it. Traced under the scopes
    ``moe_route`` (the sort), ``moe_dispatch`` (the permutes, the
    combine) and ``moe_experts`` (the grouped products).
    """
    t, top_k = idx.shape
    rows = chunk_rows(t * top_k, experts_held[1], num_experts)
    with scope("moe_route"):
        counts, plan, w = _sorted_plan(idx, p, experts_held)
    chunk = jax.checkpoint(
        functools.partial(_glu_chunk, rows, activation, tile))

    def turn(y, c):
        part = chunk(c, x, w, experts, plan)
        with scope("moe_dispatch"):
            return y + part, None

    y, _ = jax.lax.scan(turn, jnp.zeros(x.shape, jnp.float32),
                        jnp.arange(-(-t * top_k // rows)))
    return y, counts


def dropless_reglu_experts(x, idx, p, experts, experts_held, num_experts):
    """:func:`dropless_experts` with ReGLU under the name it had while
    ReGLU was all it did: ``benchmarks/families/smallthinker.py`` checks
    the train-8k cell's backward pass through this name, and the
    benchmark's files are not a model PR's to edit. Nothing in the
    package calls it."""
    return dropless_experts(x, idx, p, experts, experts_held, num_experts,
                            jax.nn.relu)


def served_turn_rows(assignments: int, held: int, num_experts: int,
                     tile_rows: int) -> int:
    """Rows of a SERVED turn's buffer: an eighth of this chip's even
    share of the assignments, in whole row tiles of the grouped product,
    at least one tile and at most every assignment."""
    up = lambda n: -(-n // tile_rows) * tile_rows
    return min(max(up(assignments * held // (8 * num_experts)), tile_rows),
               up(assignments))


def served_experts(x, idx, p, experts, experts_held, num_experts,
                   activation, tile=None, counted=None):
    """The sum of :func:`dropless_experts` for a SERVED prefill, where
    the work follows the rows that count: an assignment counts when its
    expert is held here and its token does (``counted`` (T,) bool: the
    true positions of a bucket's prompts; None: every token). The rows
    that do not count sort behind those that do and nothing is done for
    them: the sorted rows are worked in turns of
    :func:`served_turn_rows`, as many as reach the last row that counts
    (a trip count read from the plan: nothing differentiates through
    this); a turn takes its rows of ``x``, hands the two products up the
    held experts' TRUE counts of its rows (megablox computes only the
    row tiles they cover) and writes its activations, (rows, F), into
    one buffer; after the last turn ONE product down over that buffer
    under the held experts' true counts gives every row's output, and
    the tokens combine ONCE from it (:func:`_weighted_rows`). A row
    that counts goes through the operands, the float32 accumulation and
    the weights it has in :func:`dropless_experts`; ``y`` of a token
    that does not count is zero. The time follows the router and the
    prompts' lengths, which is what a served prefill wants and a train
    step does not (docs/solar_open2.md "Trained and served"): the CALLER
    picks.

    Arguments as :func:`dropless_experts`'s. Returns (y (T, H) float32,
    counts (held,) int32 of the assignments that count, (2,) int32: the
    rows the turns worked, and the rows :func:`dropless_experts`' static
    turns would have). Scopes as there."""
    t, top_k = idx.shape
    held = experts_held[1]
    rows = served_turn_rows(t * top_k, held, num_experts,
                            (tile or _GMM_TILE)[0])
    static = chunk_rows(t * top_k, held, num_experts)
    with scope("moe_route"):
        counts, (order, pos, here, ends), w = _sorted_plan(
            idx, p, experts_held, counted)
        turns = -(-ends[-1] // rows)
    with scope("moe_dispatch"):
        # (the buffer is whole turns: it may pass the end)
        order = jnp.pad(order, (0, (-order.shape[0]) % rows))

    def turn(c, act):
        lo = c * rows
        with scope("moe_dispatch"):
            tok = jax.lax.dynamic_slice(order, (lo,), (rows,)) // top_k
            sizes = jnp.diff(jnp.clip(ends, lo, lo + rows),
                             prepend=lo).astype(jnp.int32)
            xs = x[tok]
        with scope("moe_experts"):
            gate = grouped_matmul(xs, experts["w_gate"], sizes, tile)
            up = grouped_matmul(xs, experts["w_up"], sizes, tile)
            return jax.lax.dynamic_update_slice(
                act, activation(gate) * up, (lo, 0))

    # a turn's rows past its groups hold anything, as do the product's
    # below: the combine reads a row only where it counts
    with scope("moe_experts"):
        act = jax.lax.fori_loop(
            0, turns, turn, jnp.zeros(
                (order.shape[0], experts["w_down"].shape[1]), x.dtype))
        ys = grouped_matmul(act, experts["w_down"], counts, tile)
    with scope("moe_dispatch"):
        y = _weighted_rows(ys, w, pos, here, block=512)
    return y, counts, jnp.stack(
        [turns * rows, jnp.int32(-(-t * top_k // static) * static)])


def held_experts_every_row(x, idx, p, experts, experts_held, activation,
                           active=None):
    """The same sum as :func:`dropless_experts` for a step of FEW rows
    (decode: a slot table): every held expert is worked on every row and
    a row's choices weigh the results, ``c[t, e] = p[t, j]`` where
    ``idx[t, j]`` is held expert ``e`` and 0 elsewhere. Nothing is
    sorted or gathered and no shape or trip count knows the router: the
    work is ``T x held`` rows whatever lands here, and the tables are
    read once, which is what bounds the step (a held expert sees a
    handful of rows; its table is read whole either way). Three plain
    products: ``(T, H) x (held, H, F)`` twice and ``(held x T, F)``
    against ``(held, F, H)`` summed over the experts.

    x (T, H); ``active`` (T,) bool: rows that count (None: all). Returns
    (y (T, H) float32, counts (held,) int32: the assignments of ACTIVE
    rows that landed on each held expert)."""
    first, count = experts_held
    with scope("moe_route"):
        local = idx - first                                   # (T, k)
        hit = local[..., None] == jnp.arange(count)           # (T, k, held)
        if active is not None:
            hit = hit & active[:, None, None]
        weight = jnp.sum(jnp.where(hit, p[..., None], 0.0), axis=1)
        counts = jnp.sum(hit, axis=(0, 1), dtype=jnp.int32)
    with scope("moe_experts"):
        rows = jnp.broadcast_to(x, (count,) + x.shape)        # (held, T, H)
        gate = jnp.einsum("eth,ehf->etf", rows, experts["w_gate"],
                          preferred_element_type=jnp.float32)
        up = jnp.einsum("eth,ehf->etf", rows, experts["w_up"],
                        preferred_element_type=jnp.float32)
        act = (activation(gate) * up * weight.T[..., None]).astype(x.dtype)
        y = jnp.einsum("etf,efh->th", act, experts["w_down"],
                       preferred_element_type=jnp.float32)
    return y, counts
