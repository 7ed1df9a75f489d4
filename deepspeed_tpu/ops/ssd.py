"""The Mamba-2 state-space recurrence ("SSD"), in the forms the serving
path needs.

Per head, with inputs ``x_t`` of width ``P``, one pair ``B_t``, ``C_t``
of width ``N`` shared by ALL the heads (one group), a step ``dt_t > 0``
a head, a scalar decay rate ``A < 0`` a head, a skip ``D`` a head and a
float32 state ``S`` (P, N)::

    a_t = exp(dt_t * A)
    S_t = a_t S_{t-1} + dt_t x_t B_t^T
    y_t = S_t C_t + D x_t

What the caller hands in is already convolved, activated and through
its softplus (``models/granite_hybrid.py``); this file is the recurrence
alone. Beside the gated delta-rule of ``ops/kda.py`` it has a SCALAR
decay a head, no rank-one correction (nothing to solve inside a chunk),
B and C shared by the heads, and a state that is not square.

- :func:`ssd_sequential`: one ``lax.scan`` step a token, the equations
  as written. The form every other is tested against
  (tests/unit/test_ssd.py) and the one operations are counted from
  (``benchmarks/core/ssd_counts.py``).
- :func:`ssd_chunk_scan`: prefill. Chunks of 256 tokens (the published
  ``mamba_chunk_size``), one ``lax.scan`` turn a chunk. With ``L_t`` the
  log-decay summed from the chunk's start (inclusive), inside a chunk
  ``Y = [(C B^T) * exp(L_t - L_s) for s <= t] (dt x)``, the state the
  chunk found adds ``exp(L_t) C_t S_0`` and the chunk leaves ``S_Q =
  exp(L_Q) S_0 + sum_s exp(L_Q - L_s) dt_s x_s B_s^T``. Every exponent
  is a DIFFERENCE of two sums with s <= t, so it is <= 0: nothing is
  ever multiplied by ``exp(-L_s)``, which overflows float32 once a chunk
  decays by e^88. Every product is float32 at ``highest`` precision (the
  state is float32 and the one-token form below is exact float32
  arithmetic: prefill and decode then agree to rounding). Positions at
  or past a row's true length get ``dt = 0``: the state passes them
  unchanged, so a padded bucket ends at the state of the TRUE length.
- :func:`ssd_decode_update`: decode. One token a row against the
  per-slot state pool ``(layers, rows, heads, P, N)``, every row's state
  read once and written once IN PLACE (the pool is aliased to the
  output). A Pallas kernel, a block of heads of one row a grid step (off
  the TPU it runs in the interpreter).
"""

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

try:
    from jax.experimental.pallas import tpu as pltpu
except ImportError:  # pragma: no cover
    pltpu = None

__all__ = ["ssd_sequential", "ssd_chunk_scan", "ssd_decode_update", "CHUNK"]

HIGHEST = jax.lax.Precision.HIGHEST
CHUNK = 256       # tokens whose products go to the MXU together
# heads of one row a grid step of the decode kernel: nine layers of 65
# rows in place took 12.08 / 11.10 / 11.49 / 11.47 ms at 8 / 16 / 32 / 64
# (128 does not fit the kernel's VMEM; my chip run, PR 41)
_HEAD_BLOCK = 16


def ssd_sequential(x, dt, A, Bm, Cm, D, state):
    """The equations, a token a step. x (B, S, H, P), dt (B, S, H), A
    and D (H,), Bm and Cm (B, S, N), state (B, H, P, N), all float32 ->
    (y (B, S, H, P), final state)."""
    def step(s, inp):
        x_t, dt_t, b_t, c_t = inp
        s = s * jnp.exp(dt_t * A)[..., None, None] + (
            dt_t[..., None] * x_t)[..., None] * b_t[:, None, None, :]
        y = jnp.einsum("bhpn,bn->bhp", s, c_t, precision=HIGHEST)
        return s, y + D[:, None] * x_t

    seq = lambda a: jnp.moveaxis(a, 1, 0)
    state, y = jax.lax.scan(step, state, (seq(x), seq(dt), seq(Bm), seq(Cm)))
    return jnp.moveaxis(y, 0, 1), state


def _mm(a, b, spec):
    return jnp.einsum(spec, a, b, precision=HIGHEST)


def ssd_chunk_scan(x, dt, A, Bm, Cm, D, state, lengths=None, chunk=CHUNK):
    """The recurrence over a padded bucket. x (B, S, H, P), dt (B, S, H),
    A and D (H,), Bm and Cm (B, S, N), state (B, H, P, N), all float32;
    ``lengths`` (B,) the true lengths (None: all S). S is padded up to a
    whole number of chunks here. Returns (y (B, S, H, P), the state
    after each row's TRUE length)."""
    B, S, H, P = x.shape
    if lengths is not None:
        live = jnp.arange(S)[None, :] < lengths[:, None]
        dt = jnp.where(live[..., None], dt, 0.0)
    pad = (-S) % chunk
    if pad:
        # dt = 0 there: the state passes unchanged
        x, dt, Bm, Cm = (
            jnp.pad(a, ((0, 0), (0, pad)) + ((0, 0),) * (a.ndim - 2))
            for a in (x, dt, Bm, Cm))
    n = (S + pad) // chunk
    # (n, B, C, ...): a chunk is the scanned dimension
    split = lambda a: jnp.moveaxis(
        a.reshape(B, n, chunk, *a.shape[2:]), 1, 0)
    causal = jnp.tril(jnp.ones((chunk, chunk), bool))

    def turn(s, inp):
        x_c, dt_c, b_c, c_c = inp              # (B, C, H, P), (B, C, H), ..
        cum = jnp.cumsum(dt_c * A, axis=1)                    # (B, C, H)
        cum = jnp.moveaxis(cum, 2, 1)                         # (B, H, C)
        # exp(L_t - L_s), s <= t: each exponent <= 0 (module docstring)
        decay = jnp.exp(jnp.where(
            causal, cum[..., :, None] - cum[..., None, :], -jnp.inf))
        scores = _mm(c_c, b_c, "btn,bsn->bts")                # (B, C, C)
        dx = dt_c[..., None] * x_c                            # (B, C, H, P)
        y = _mm(scores[:, None] * decay, dx, "bhts,bshp->bthp")
        # what the state the chunk found still gives each position
        y = y + jnp.moveaxis(jnp.exp(cum), 1, 2)[..., None] * _mm(
            c_c, s, "btn,bhpn->bthp")
        total = cum[..., -1:]                                 # (B, H, 1)
        left = jnp.moveaxis(jnp.exp(total - cum), 1, 2)       # (B, C, H)
        s = s * jnp.exp(total)[..., None] + _mm(
            left[..., None] * dx, b_c, "bshp,bsn->bhpn")
        return s, y + D[:, None] * x_c

    state, y = jax.lax.scan(turn, state,
                            (split(x), split(dt), split(Bm), split(Cm)))
    y = jnp.moveaxis(y, 0, 1).reshape(B, S + pad, H, P)
    return y[:, :S], state


def _decode_kernel(cols_ref, bc_ref, s_ref, y_ref, s_out_ref):
    """One row's block of heads: its states through VMEM once. A state
    tile has P on its sublanes and N on its lanes, so what indexes P (the
    decay and ``dt x`` of each head) arrives as columns, (P, 2 x heads),
    and ``y`` leaves as columns, (P, heads); B and C are rows (1, N)."""
    hb = s_ref.shape[0]
    b_row, c_row = bc_ref[0:1, :], bc_ref[1:2, :]
    outs = []
    for h in range(hb):
        s = s_ref[h] * cols_ref[:, h:h + 1] + \
            cols_ref[:, hb + h:hb + h + 1] * b_row
        s_out_ref[h] = s
        outs.append(jnp.sum(s * c_row, axis=1, keepdims=True))
    y_ref[...] = jnp.concatenate(outs, axis=1)


@functools.partial(jax.jit, static_argnames=("layer", "interpret"))
def _decode_call(pool, cols, bc, layer, interpret):
    L, R, H, P, N = pool.shape
    hb = min(_HEAD_BLOCK, H)
    tile = pl.BlockSpec((None, None, hb, P, N),
                        lambda r, j: (layer, r, j, 0, 0))
    params = None
    if pltpu is not None and not interpret:
        params = pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel"))
    y, pool = pl.pallas_call(
        _decode_kernel,
        grid=(R, H // hb),
        in_specs=[pl.BlockSpec((None, None, P, 2 * hb),
                               lambda r, j: (r, j, 0, 0)),
                  pl.BlockSpec((None, 2, N), lambda r, j: (r, 0, 0)),
                  tile],
        out_specs=[pl.BlockSpec((None, None, P, hb),
                                lambda r, j: (r, j, 0, 0)), tile],
        out_shape=[jax.ShapeDtypeStruct((R, H // hb, P, hb), jnp.float32),
                   jax.ShapeDtypeStruct(pool.shape, pool.dtype)],
        input_output_aliases={2: 1},
        interpret=interpret,
        compiler_params=params,
    )(cols, bc, pool)
    return y, pool


def ssd_decode_update(pool, layer: int, x, dt, A, Bm, Cm, D):
    """One token a row. pool (layers, R, H, P, N) float32, of which the
    static ``layer`` is read and rewritten in place; x (R, H, P), dt
    (R, H), A and D (H,), Bm and Cm (R, N), float32. Returns (y (R, H,
    P), the pool). One kernel everywhere: compiled on a TPU, in the
    Pallas interpreter elsewhere (as ``ops/kda.kda_decode_update``)."""
    _, R, H, P, N = pool.shape
    hb = min(_HEAD_BLOCK, H)
    assert H % hb == 0, pool.shape
    # per block of heads, P on the sublanes: [decay | dt x] (R, H/hb, P,
    # 2 hb): small arrays (a row's state is P x N x 4 B a head, these 8 B)
    a = jnp.broadcast_to(jnp.exp(dt * A)[..., None], x.shape)
    cols = jnp.concatenate(
        [jnp.swapaxes(t.reshape(R, H // hb, hb, P), 2, 3)
         for t in (a, dt[..., None] * x)], axis=-1)
    y, pool = _decode_call(pool, cols, jnp.stack([Bm, Cm], axis=1),
                           layer=layer,
                           interpret=jax.default_backend() != "tpu")
    y = jnp.swapaxes(y, 2, 3).reshape(R, H, P)
    return y + D[:, None] * x, pool
