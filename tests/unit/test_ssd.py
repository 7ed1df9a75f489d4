"""The Mamba-2 state-space recurrence (ops/ssd.py): the chunked prefill
scan and the one-token Pallas update against the sequential form, at
tiny sizes on the CPU."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

import deepspeed_tpu  # noqa: F401
from deepspeed_tpu.ops import ssd


def _inputs(B, S, H, P, N, seed=0, strong=False):
    ks = jax.random.split(jax.random.PRNGKey(seed), 7)
    x = jax.random.normal(ks[0], (B, S, H, P))
    # steps from 0.002 to 1.3 (strong: up to 50, so that a chunk decays
    # by e^-4000 and more: exp(-cumsum) would overflow float32 at e^88)
    dt = jnp.exp(jax.random.uniform(ks[1], (B, S, H), minval=-6.0,
                                    maxval=4.0 if strong else 0.3))
    A = -jnp.exp(jax.random.uniform(ks[2], (H,), minval=0.0,
                                    maxval=np.log(16.0)))
    Bm = jax.random.normal(ks[3], (B, S, N))
    Cm = jax.random.normal(ks[4], (B, S, N))
    D = jax.random.normal(ks[5], (H,))
    s0 = jax.random.normal(ks[6], (B, H, P, N))
    return x, dt, A, Bm, Cm, D, s0


def _close(got, want, what):
    # float32 sums of up to a chunk's 256 terms that cancel, in another
    # order: some hundred units in the last place (6e-8) of the largest
    scale = float(jnp.abs(want).max())
    np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                               atol=3e-5 * scale + 1e-6, err_msg=what)


@pytest.mark.parametrize("S,chunk,strong", [
    (64, 16, False),     # whole chunks
    (37, 16, False),     # a chunk that does not divide the length
    (50, 16, True),      # a decay that underflows a naive exp(-cumsum)
    (7, 16, False),      # shorter than one chunk
    (256, 256, False),   # the published chunk
])
def test_chunked_scan_equals_the_sequential_recurrence(S, chunk, strong):
    args = _inputs(2, S, 3, 8, 16, seed=S, strong=strong)
    if strong:
        # what the naive form would exponentiate
        assert float((args[1] * -args[2]).sum(1).max()) > 88.0
    with jax.default_matmul_precision("highest"):
        want_y, want_s = ssd.ssd_sequential(*args)
        got_y, got_s = jax.jit(
            lambda *a: ssd.ssd_chunk_scan(*a, chunk=chunk))(*args)
    assert np.isfinite(np.asarray(got_y)).all()
    _close(got_y, want_y, "y")
    _close(got_s, want_s, "final state")


def test_a_padded_bucket_ends_at_each_rows_true_length():
    """Ragged true lengths inside one padded bucket: outputs up to the
    length and the final state are those of the row alone."""
    x, dt, A, Bm, Cm, D, s0 = _inputs(3, 48, 2, 8, 16, seed=5)
    lengths = jnp.asarray([48, 21, 1])
    with jax.default_matmul_precision("highest"):
        got_y, got_s = jax.jit(lambda *a: ssd.ssd_chunk_scan(
            *a, chunk=16))(x, dt, A, Bm, Cm, D, s0, lengths)
        for row, n in enumerate((48, 21, 1)):
            cut = lambda a: a[row:row + 1, :n]
            want_y, want_s = ssd.ssd_sequential(
                cut(x), cut(dt), A, cut(Bm), cut(Cm), D, s0[row:row + 1])
            _close(got_y[row, :n], want_y[0], f"row {row} y")
            _close(got_s[row], want_s[0], f"row {row} state")


def test_decode_update_is_one_step_of_the_recurrence_in_its_layer():
    """The kernel (in the interpreter) agrees with one sequential step
    over a state that is NOT square, and the pool's other layers are
    untouched."""
    x, dt, A, Bm, Cm, D, _ = _inputs(5, 1, 64, 8, 128, seed=9)
    pool = jax.random.normal(jax.random.PRNGKey(1), (3, 5, 64, 8, 128))
    want_y, want_s = ssd.ssd_sequential(x, dt, A, Bm, Cm, D, pool[1])
    y, new = ssd.ssd_decode_update(pool, 1, x[:, 0], dt[:, 0], A, Bm[:, 0],
                                   Cm[:, 0], D)
    _close(y, want_y[:, 0], "y")
    _close(new[1], want_s, "state")
    np.testing.assert_array_equal(np.asarray(new[0]), np.asarray(pool[0]))
    np.testing.assert_array_equal(np.asarray(new[2]), np.asarray(pool[2]))


def test_decode_steps_continue_what_the_prefill_scan_left():
    """Prefill to a true length, then token by token through the pool:
    the same outputs and state as the sequential form over the whole."""
    x, dt, A, Bm, Cm, D, _ = _inputs(2, 30, 32, 8, 128, seed=11)
    zero = jnp.zeros((2, 32, 8, 128))
    with jax.default_matmul_precision("highest"):
        want_y, want_s = ssd.ssd_sequential(x, dt, A, Bm, Cm, D, zero)
        cut = lambda a: a[:, :24]
        _, last = ssd.ssd_chunk_scan(cut(x), cut(dt), A, cut(Bm), cut(Cm),
                                     D, zero, chunk=16)
    pool = jnp.zeros((1, 2, 32, 8, 128)).at[0].set(last)
    for t in range(24, 30):
        y, pool = ssd.ssd_decode_update(pool, 0, x[:, t], dt[:, t], A,
                                        Bm[:, t], Cm[:, t], D)
        _close(y, want_y[:, t], f"y at {t}")
    _close(pool[0], want_s, "state")
