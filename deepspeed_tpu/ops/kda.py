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
- :func:`kda_chunk_scan`: prefill. Chunks of 64 tokens; inside a chunk
  everything is a matrix product, between chunks the state is carried.
  With ``G_t`` the decay summed from the chunk's start, ``w_t = v_t -
  (Diag(exp g_t) S_{t-1})^T k_t`` solves the unit lower-triangular
  system ``(I + A Diag(b)) W = V - K+ S_0`` where ``A[t, s] = sum_d
  k_t[d] k_s[d] exp(G_t[d] - G_s[d])`` for s < t and ``K+_t = exp(G_t)
  k_t``; then ``O = Q+ S_0 + B Diag(b) W`` (``B`` as ``A`` with q_t for
  k_t, s <= t) and ``S_C = Diag(exp G_C) S_0 + (exp(G_C - G_s) k_s)^T
  (b W)``. Nothing is ever multiplied by ``exp(-G)``, which overflows
  float32 once a chunk decays by e^88: ``A`` and ``B`` are built in
  sub-chunks of 16, a block BELOW the diagonal with both factors
  measured from the start of its row's sub-chunk (each exponent <= 0),
  a block ON the diagonal pair by pair. Positions at or past a row's
  true length get ``g = 0, b = 0``: the state passes them unchanged, so
  a padded bucket ends at the state of the TRUE length.
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
from jax.experimental import pallas as pl

try:
    from jax.experimental.pallas import tpu as pltpu
except ImportError:  # pragma: no cover
    pltpu = None

__all__ = ["kda_sequential", "kda_chunk_scan", "kda_decode_update",
           "CHUNK", "SUB"]

HIGHEST = jax.lax.Precision.HIGHEST
CHUNK = 64       # tokens whose products go to the MXU together
SUB = 16         # rows of a block of A and B (module docstring)
_HEAD_BLOCK = 8  # heads of one row a grid step of the decode kernel


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


def _mm(a, b, spec):
    return jnp.einsum(spec, a, b, precision=HIGHEST)


def _chunk_matrices(q, k, gc):
    """A (strictly lower) and B (lower) of one chunk for every row and
    head: q, k, gc (..., C, dk) with gc the decay summed from the
    chunk's start -> two (..., C, C)."""
    *lead, c, dk = q.shape
    n = c // SUB
    blocks = lambda a: a.reshape(*lead, n, SUB, dk)
    qb, kb, gb = blocks(q), blocks(k), blocks(gc)
    # the decay at the START of each sub-chunk: the one before it ends
    start = jnp.concatenate(
        [jnp.zeros_like(gb[..., :1, 0, :]), gb[..., :-1, -1, :]], axis=-2)
    rel = jnp.exp(gb - start[..., None, :])              # (.., n, SUB, dk)
    # every earlier key measured from that start (later ones are masked)
    back = jnp.exp(jnp.minimum(
        start[..., :, None, :] - gc[..., None, :, :], 0.0))  # (.., n, C, dk)
    km = k[..., None, :, :] * back
    a_off = _mm(kb * rel, km, "...itd,...isd->...its")   # (.., n, SUB, C)
    b_off = _mm(qb * rel, km, "...itd,...isd->...its")
    # the diagonal blocks pair by pair
    pair = gb[..., :, None, :] - gb[..., None, :, :]     # (.., n, t, s, dk)
    tri = jnp.tril(jnp.ones((SUB, SUB), bool))
    e = jnp.exp(jnp.where(tri[..., None], pair, -jnp.inf))
    ks = kb[..., None, :, :] * e
    a_dg = jnp.sum(kb[..., :, None, :] * ks, -1)         # (.., n, SUB, SUB)
    b_dg = jnp.sum(qb[..., :, None, :] * ks, -1)
    row = jnp.arange(c)[:, None] // SUB
    col = jnp.arange(c)[None, :] // SUB

    def whole(off, dg):
        dg = jnp.einsum("...its,ij->...itjs", dg, jnp.eye(n, dtype=dg.dtype)
                        ).reshape(*lead, c, c)
        return jnp.where(col < row, off.reshape(*lead, c, c), dg)

    t, s = jnp.arange(c)[:, None], jnp.arange(c)[None, :]
    return (jnp.where(s < t, whole(a_off, a_dg), 0.0),
            jnp.where(s <= t, whole(b_off, b_dg), 0.0))


def kda_chunk_scan(q, k, v, g, beta, state, lengths=None, chunk=CHUNK):
    """The recurrence over a padded bucket. q, k, g (B, S, H, dk), v
    (B, S, H, dv), beta (B, S, H), state (B, H, dk, dv), all float32;
    ``lengths`` (B,) the true lengths (None: all S). S is padded up to a
    whole number of chunks here. Returns (o (B, S, H, dv), the state
    after each row's TRUE length)."""
    B, S, H, dk = q.shape
    if lengths is not None:
        live = jnp.arange(S)[None, :] < lengths[:, None]
        g = jnp.where(live[..., None, None], g, 0.0)
        beta = jnp.where(live[..., None], beta, 0.0)
    pad = (-S) % chunk
    if pad:
        # g = 0 and beta = 0 there: the state passes unchanged
        q, k, v, g, beta = (
            jnp.pad(a, ((0, 0), (0, pad)) + ((0, 0),) * (a.ndim - 2))
            for a in (q, k, v, g, beta))
    n = (S + pad) // chunk
    # (n, B, H, C, .): a chunk is the scanned dimension
    split = lambda a: jnp.moveaxis(
        a.reshape(B, n, chunk, H, -1), (1, 3), (0, 2))
    q, k, v, g = split(q), split(k), split(v), split(g)
    beta = split(beta)[..., 0]                           # (n, B, H, C)
    gc = jnp.cumsum(g, axis=-2)
    # the chunks' own matrices, one chunk a turn so that the pairwise
    # diagonal blocks of one chunk are all that is ever held
    a, b = jax.lax.map(lambda x: _chunk_matrices(*x), (q, k, gc))
    kp, qp = k * jnp.exp(gc), q * jnp.exp(gc)
    total = gc[..., -1:, :]                              # (n, B, H, 1, dk)
    kend = k * jnp.exp(total - gc)
    system = jnp.eye(chunk, dtype=a.dtype) + a * beta[..., None, :]
    solved = jax.lax.linalg.triangular_solve(
        system, jnp.concatenate([v, kp], axis=-1), left_side=True,
        lower=True, unit_diagonal=True)
    u, wk = solved[..., :v.shape[-1]], solved[..., v.shape[-1]:]
    bb = b * beta[..., None, :]

    def turn(s, x):
        u_c, wk_c, qp_c, bb_c, kend_c, beta_c, total_c = x
        w = u_c - _mm(wk_c, s, "bhck,bhkv->bhcv")
        o = _mm(qp_c, s, "bhck,bhkv->bhcv") + _mm(bb_c, w,
                                                  "bhct,bhtv->bhcv")
        s = s * jnp.exp(total_c)[..., 0, :, None] + _mm(
            kend_c, beta_c[..., None] * w, "bhck,bhcv->bhkv")
        return s, o

    state, o = jax.lax.scan(turn, state, (u, wk, qp, bb, kend, beta, total))
    o = jnp.moveaxis(o, (0, 2), (1, 3)).reshape(B, S + pad, H, -1)
    return o[:, :S], state


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
