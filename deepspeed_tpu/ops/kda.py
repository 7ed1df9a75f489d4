"""Gated delta-rule linear attention ("KDA"): the recurrence, in the
forms the serving path needs.

Per head, with keys and queries of width ``dk``, values of width ``dv``
and a float32 state ``S`` (dk, dv)::

    S_t = (I - b_t k_t k_t^T) Diag(exp g_t) S_{t-1} + b_t k_t v_t^T
    o_t = S_t^T q_t

``g_t`` (dk,) is the log-decay per head AND channel (<= 0), ``b_t`` the
step size (a scalar per head; up to 2, so an eigenvalue of the
transition may be negative). What the caller hands in is already
normalised and scaled (``models/solar_open2.py``); this file is the
recurrence alone.

- :func:`kda_sequential`: one ``lax.scan`` step a token, the equations
  as written. The form every other is tested against
  (tests/unit/test_solar_open2.py) and the one operations are counted
  from (``benchmarks/core/hybrid_counts.py``).
- :func:`kda_chunk_scan`: prefill, ONE Pallas kernel. Chunks of 64
  tokens; inside a chunk everything is a matrix product but the solve,
  between a row's chunks the state stays in VMEM (a grid of rows x
  blocks of heads x chunks, the chunks in order). With ``G_t`` the decay
  summed from the chunk's start, ``w_t = v_t - (Diag(exp g_t)
  S_{t-1})^T k_t`` solves the unit lower-triangular system ``(I + A
  Diag(b)) W = V - K+ S_0`` where ``A[t, s] = sum_d k_t[d] k_s[d]
  exp(G_t[d] - G_s[d])`` for s < t and ``K+_t = exp(G_t) k_t``; then
  ``O = Q+ S_0 + B Diag(b) W`` (``B`` as ``A`` with q_t for k_t, s <= t)
  and ``S_C = Diag(exp G_C) S_0 + (exp(G_C - G_s) b_s k_s)^T W``.
  Nothing is ever multiplied by ``exp(-G)``, which overflows float32
  once a chunk decays by e^88: ``A`` and ``B`` are built by HALVING. At
  level m = 32, 16, .., 1 a pair (t, s) whose blocks of m differ inside
  one block of 2m is split at the boundary between the halves: t's
  factor is the decay from there through t, s's the decay after s up to
  there (each exponent <= 0), and the level is one product of the
  chunk's scaled keys against its scaled keys and queries, of which the
  level's pairs are kept. The system is solved by substitution, a row a
  step (so ``b = 2`` is no special case). Products are float32 as
  ``HIGHEST`` makes them, six bfloat16 passes written out. Positions at
  or past a row's true length get ``g = 0, b = 0``: the state passes
  them unchanged, so a padded bucket ends at the state of the TRUE
  length; a chunk that STARTS at or past the length is not worked at
  all (its output rows are zeros).
- :func:`kda_decode_update`: decode. One token a row against the
  per-slot state pool ``(layers, rows, heads, dk, dv)``, every row's
  state read once and written once IN PLACE (the pool is aliased to the
  output: no layer is sliced out, nothing of the pool's size is copied).
  A Pallas kernel, a block of heads of one row a grid step (off the TPU
  it runs in the interpreter).
"""

import functools

import jax
import jax.numpy as jnp
import numpy as np
from jax.experimental import pallas as pl

try:
    from jax.experimental.pallas import tpu as pltpu
except ImportError:  # pragma: no cover
    pltpu = None

__all__ = ["kda_sequential", "kda_chunk_scan", "kda_decode_update",
           "CHUNK"]

HIGHEST = jax.lax.Precision.HIGHEST
CHUNK = 64       # tokens whose products go to the MXU together
_HEAD_BLOCK = 8  # heads of one row a grid step of the decode kernel
# and of the chunk kernel: two heads' substitutions hide each other's
# waits, and what more heads would add in registers spills (PERF.md §6)
_SCAN_HEADS = 2


def kda_sequential(q, k, v, g, beta, state):
    """The equations, a token a step. q, k, g (B, S, H, dk), v (B, S, H,
    dv), beta (B, S, H), state (B, H, dk, dv), all float32 ->
    (o (B, S, H, dv), final state)."""
    def step(s, x):
        q_t, k_t, v_t, g_t, b_t = x
        s = s * jnp.exp(g_t)[..., None]
        w = v_t - jnp.einsum("bhkv,bhk->bhv", s, k_t, precision=HIGHEST)
        s = s + (b_t[..., None] * k_t)[..., None] * w[..., None, :]
        return s, jnp.einsum("bhkv,bhk->bhv", s, q_t, precision=HIGHEST)

    seq = lambda a: jnp.moveaxis(a, 1, 0)
    state, o = jax.lax.scan(step, state,
                            tuple(seq(a) for a in (q, k, v, g, beta)))
    return jnp.moveaxis(o, 0, 1), state


def _pieces(x):
    """A float32 array as three bfloat16 ones that sum to it."""
    out = []
    for _ in range(3):
        out.append(x.astype(jnp.bfloat16))
        x = x - out[-1].astype(jnp.float32)
    return out


def _dot(a, b, rows=False):
    """(heads, M, K) x (heads, K, N), or with ``rows`` x (heads, N, K),
    -> (heads, M, N) at float32 as ``HIGHEST`` makes it: six bfloat16
    passes, every pair of pieces but the three smallest, summed in
    float32. Written out, a pass a product, because each operand then
    goes to the MXU sixteen rows a register and a pass's results are
    added before the next pass's arrive (Mosaic's own float32 product
    sends eight rows a register and holds all six passes' results)."""
    dims = (((2,), (2 if rows else 1,)), ((0,), (0,)))
    a3, b3 = _pieces(a), _pieces(b)
    out = None
    for i, j in ((0, 0), (1, 0), (0, 1), (2, 0), (1, 1), (0, 2)):
        p = jax.lax.dot_general(a3[i], b3[j], dims,
                                preferred_element_type=jnp.float32)
        out = p if out is None else out + p
    return out


def _chunk_kernel(len_ref, q_ref, k_ref, v_ref, g_ref, b_ref, s_ref, o_ref,
                  s_out_ref, s_scr):
    """One chunk of one row's block of heads; the block's states stay in
    ``s_scr`` from the row's first chunk to its last. Every array below
    is (heads, .., ..): one operation of the program works the block's
    heads, which the compiler then schedules side by side."""
    hb, dk, dv = s_scr.shape
    c = q_ref.shape[0]
    turn, length = pl.program_id(2), len_ref[pl.program_id(0)]

    @pl.when(turn == 0)
    def _():
        s_scr[...] = s_ref[...]

    @pl.when(turn * c >= length)
    def _():
        o_ref[...] = jnp.zeros_like(o_ref)

    @pl.when(turn * c < length)
    def _():
        row = jax.lax.broadcasted_iota(jnp.int32, (c, 1), 0)
        live = turn * c + row < length
        # a key s down the rows, a query t along the lanes, twice: A^T
        # beside B^T, so that a product's result fills the lanes
        s = jax.lax.broadcasted_iota(jnp.int32, (c, 2 * c), 0)
        lane = jax.lax.broadcasted_iota(jnp.int32, (c, 2 * c), 1)
        t = jnp.where(lane < c, lane, lane - c)
        apart = jnp.where(s < t, t ^ s, 0)    # its highest bit: the level
        upto = (jax.lax.broadcasted_iota(jnp.int32, (c, c), 0)
                >= jax.lax.broadcasted_iota(jnp.int32, (c, c), 1)
                ).astype(jnp.bfloat16)
        heads = lambda x, d: jnp.stack(
            [x[:, h * d:(h + 1) * d] for h in range(hb)], axis=0)
        q, k, v = heads(q_ref, dk), heads(k_ref, dk), heads(v_ref, dv)
        kb = k * jnp.where(live, heads(b_ref, 1), 0.0)
        g = jnp.where(live, g_ref[...], 0.0)
        # the decay summed from the chunk's start through each row, all
        # the heads side by side: 0/1 rows against the decays' three
        # bfloat16 pieces, summed in float32
        gc = heads(jnp.dot(jnp.concatenate([upto] * 3, axis=1),
                           jnp.concatenate(_pieces(g), axis=0),
                           preferred_element_type=jnp.float32), dk)
        g = heads(g, dk)

        def halves(m):
            """The exponent a row brings to level m: the decay from its
            half's start through itself in the SECOND half of its block
            of 2m, from after itself to its half's end in the first.
            Both are differences with ``gc`` at the first half's last
            row, and both are <= 0."""
            if m == 1:
                return jnp.where(row % 2 == 1, g, 0.0)
            over = max(2 * m, 8)    # whole tiles of eight rows
            end = jnp.concatenate([
                jnp.broadcast_to(gc[:, i + m - 1:i + m], (hb, over, dk))
                if m > 2 else jnp.where(row[:8] < 4, gc[:, i + 1:i + 2],
                                        gc[:, i + 5:i + 6])
                for i in range(0, c, over)], axis=1)
            return jnp.where(row // m % 2 == 1, gc - end, end - gc)

        here = jnp.exp(gc)
        both = _dot(jnp.concatenate([k * here, q * here], axis=1),
                    s_scr[...])
        # [A^T | B^T] Diag(beta) level by level; B's diagonal is
        # q_t . k_t
        ab = jnp.where((s == t) & (lane >= c),
                       jnp.sum(q * kb, axis=2, keepdims=True), 0.0)
        m = c // 2
        while m:
            z = jnp.exp(halves(m))
            p = _dot(kb * z, jnp.concatenate([k * z, q * z], axis=1),
                     rows=True)
            ab = jnp.where((apart >= m) & (apart < 2 * m), p, ab)
            m //= 2
        ab = jnp.swapaxes(ab, 1, 2)           # [A; B], a query a row
        # (I + A Diag(beta)) W = V - K+ S by substitution, a row a step:
        # row r is final once the rows before it are taken out of it, and
        # a step touches the rows from its own tile of eight down
        w, done = v - both[:, :c], []
        for r in range(c - 1):
            at = r % 8
            w = w - ab[:, r - at:c, r:r + 1] * w[:, at:at + 1]
            if at == 7:
                done.append(w[:, :8])
                w = w[:, 8:]
        # the output and the state's gain, ONE product: B Diag(beta) over
        # (beta k exp(the decay still to come))^T
        gain = _dot(jnp.concatenate(
            [ab[:, c:], jnp.swapaxes(kb * jnp.exp(gc[:, c - 1:] - gc), 1, 2)],
            axis=1), jnp.concatenate(done + [w], axis=1))
        o = both[:, c:] + gain[:, :c]
        o_ref[...] = jnp.concatenate([o[h] for h in range(hb)], axis=1)
        # exp of the whole chunk's decay with dk down the sublanes, as a
        # state tile has it
        whole = jnp.exp(jnp.sum(g, axis=1)).T
        s_scr[...] = s_scr[...] * jnp.stack(
            [whole[:, h:h + 1] for h in range(hb)], axis=0) + gain[:, c:]

    @pl.when(turn == pl.num_programs(2) - 1)
    def _():
        s_out_ref[...] = s_scr[...]


@functools.partial(jax.jit, static_argnames=("interpret",))
def _chunk_call(q, k, v, g, beta, state, lengths, interpret):
    B, S, H, dk = q.shape
    dv = v.shape[-1]
    hb = max(d for d in range(1, _SCAN_HEADS + 1) if H % d == 0)
    n = S // CHUNK
    flat = lambda a: a.reshape(B, S, -1)
    # a turn past a row's last live chunk names that chunk again: what a
    # skipped turn would read is not fetched
    at = lambda c, b, lens: jnp.minimum(
        c, jnp.maximum(lens[b] - 1, 0) // CHUNK)
    wide = lambda d: pl.BlockSpec(
        (None, CHUNK, hb * d), lambda b, j, c, lens: (b, at(c, b, lens), j))
    tile = pl.BlockSpec((None, hb, dk, dv), lambda b, j, c, lens: (b, j, 0, 0))
    params = None if interpret else pltpu.CompilerParams(
        dimension_semantics=("parallel", "parallel", "arbitrary"))
    return pl.pallas_call(
        _chunk_kernel,
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=1,
            grid=(B, H // hb, n),
            in_specs=[
                wide(dk), wide(dk), wide(dv), wide(dk),
                pl.BlockSpec((None, None, CHUNK, hb),
                             lambda b, j, c, lens: (b, j, at(c, b, lens), 0)),
                tile],
            out_specs=[
                pl.BlockSpec((None, CHUNK, hb * dv),
                             lambda b, j, c, lens: (b, c, j)),
                tile],
            scratch_shapes=[pltpu.VMEM((hb, dk, dv), jnp.float32)]),
        out_shape=[jax.ShapeDtypeStruct((B, S, H * dv), jnp.float32),
                   jax.ShapeDtypeStruct(state.shape, jnp.float32)],
        interpret=interpret,
        compiler_params=params,
    )(lengths, flat(q), flat(k), flat(v), flat(g),
      jnp.moveaxis(beta.reshape(B, S, H // hb, hb), 2, 1), state)


def kda_chunk_scan(q, k, v, g, beta, state, lengths=None):
    """The recurrence over a padded bucket. q, k, g (B, S, H, dk), v
    (B, S, H, dv), beta (B, S, H), state (B, H, dk, dv), all float32;
    ``lengths`` (B,) the true lengths (None: all S). S is padded up to a
    whole number of chunks here. Returns (o (B, S, H, dv), zeros from
    the first chunk that starts at or past a row's true length on; the
    state after each row's TRUE length). One kernel everywhere, as
    :func:`kda_decode_update`."""
    B, S, H, _ = q.shape
    if lengths is None:
        lengths = jnp.full((B,), S, jnp.int32)
    pad = (-S) % CHUNK
    if pad:
        q, k, v, g, beta = (
            jnp.pad(a, ((0, 0), (0, pad)) + ((0, 0),) * (a.ndim - 2))
            for a in (q, k, v, g, beta))
    o, state = _chunk_call(q, k, v, g, beta, state,
                           lengths.astype(jnp.int32),
                           interpret=jax.default_backend() != "tpu")
    return o.reshape(B, S + pad, H, -1)[:, :S], state


def _decode_kernel(a_ref, k_ref, bk_ref, q_ref, v_ref, s_ref, o_ref,
                   s_out_ref):
    """One row's block of heads: its states through VMEM once. The four
    vectors that index dk arrive lane-major, (heads, dk); a state tile
    has dk on its sublanes, so they are transposed here, all at once."""
    hb, dk = a_ref.shape
    cols = jnp.concatenate(
        [a_ref[...], k_ref[...], bk_ref[...], q_ref[...]], axis=0).T
    outs = []
    for h in range(hb):
        col = lambda j: cols[:, j * hb + h:j * hb + h + 1]      # (dk, 1)
        s = s_ref[h] * col(0)
        w = v_ref[h:h + 1, :] - jnp.sum(s * col(1), axis=0, keepdims=True)
        s = s + col(2) * w
        s_out_ref[h] = s
        outs.append(jnp.sum(s * col(3), axis=0, keepdims=True))
    o_ref[...] = jnp.concatenate(outs, axis=0)


@functools.partial(jax.jit, static_argnames=("layer", "interpret"))
def _decode_call(pool, a, k, bk, q, v, layer, interpret):
    L, R, H, dk, dv = pool.shape
    hb = min(_HEAD_BLOCK, H)
    vec = lambda width: pl.BlockSpec((None, hb, width),
                                     lambda r, j: (r, j, 0))
    tile = pl.BlockSpec((None, None, hb, dk, dv),
                        lambda r, j: (layer, r, j, 0, 0))
    params = None
    if pltpu is not None and not interpret:
        params = pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel"))
    o, pool = pl.pallas_call(
        _decode_kernel,
        grid=(R, H // hb),
        in_specs=[vec(dk), vec(dk), vec(dk), vec(dk), vec(dv), tile],
        out_specs=[vec(dv), tile],
        out_shape=[jax.ShapeDtypeStruct((R, H, dv), jnp.float32),
                   jax.ShapeDtypeStruct(pool.shape, pool.dtype)],
        input_output_aliases={5: 1},
        interpret=interpret,
        compiler_params=params,
    )(a, k, bk, q, v, pool)
    return o, pool


def kda_decode_update(pool, layer: int, q, k, v, g, beta):
    """One token a row. pool (layers, R, H, dk, dv) float32, of which
    the static ``layer`` is read and rewritten in place; q, k, g (R, H,
    dk), v (R, H, dv), beta (R, H), float32. Returns (o (R, H, dv), the
    pool). One kernel everywhere: compiled on a TPU, in the Pallas
    interpreter elsewhere (as ``ops/moe.grouped_matmul``'s is)."""
    assert pool.shape[2] % min(_HEAD_BLOCK, pool.shape[2]) == 0, pool.shape
    return _decode_call(pool, jnp.exp(g), k, beta[..., None] * k, q, v,
                        layer=layer,
                        interpret=jax.default_backend() != "tpu")
