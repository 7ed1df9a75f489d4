"""The shared scan-amortized measurement protocol (utils/benchtime.py).

The invariant under test once failed silently in production: a window
smaller than the dispatch round trip's jitter "measured" 0.00 ms and poisoned the
autotune block table.  The protocol must rescale until a window clears
the noise floor and RAISE (NoiseFloorError) when it cannot — a noise
reading must never come back as a measurement.

Reference analog: the GemmTest autotuner's repeated-timing loop
(csrc/includes/gemm_test.h:27).
"""

import jax
import jax.numpy as jnp
import pytest

from deepspeed_tpu.utils.benchtime import (NoiseFloorError, measure_rtt,
                                           scan_grad_seconds)


def _args():
    key = jax.random.PRNGKey(0)
    return tuple(jax.random.normal(jax.random.fold_in(key, i),
                                   (2, 64, 64), jnp.bfloat16)
                 for i in range(3))


def _grad_fn():
    def loss(q, k, v):
        return jnp.sum((q @ k @ v).astype(jnp.float32))
    return jax.grad(loss, argnums=(0, 1, 2))


def test_measures_positive_time_and_beats():
    rtt = measure_rtt()
    beats = []
    sec, n = scan_grad_seconds(_grad_fn(), _args(), rtt, start_len=2,
                               min_floor=0.05, beat=lambda: beats.append(1))
    assert sec > 0.0
    assert n >= 2
    # at least compile+settle and one measured window per growth round
    assert len(beats) >= 2


def test_scan_length_grows_to_clear_floor():
    # tiny per-eval work against a fat floor forces rescaling
    _, n = scan_grad_seconds(_grad_fn(), _args(), rtt=0.0, start_len=1,
                             min_floor=0.05, max_len=4096)
    assert n > 1


def test_raises_noise_floor_error_not_zero():
    # an absurd rtt makes the floor unreachable: the protocol must raise,
    # never return a ~0 "measurement"
    with pytest.raises(NoiseFloorError):
        scan_grad_seconds(_grad_fn(), _args(), rtt=100.0, start_len=1,
                          max_len=2, grow_rounds=2)


def test_noise_floor_error_is_not_a_generic_fallback_trigger():
    # bench.py's sparse row falls back to the v1 kernel on Exception but
    # must re-raise NoiseFloorError; the type distinction is the contract
    assert issubclass(NoiseFloorError, RuntimeError)
    try:
        scan_grad_seconds(_grad_fn(), _args(), rtt=100.0, start_len=1,
                          max_len=2, grow_rounds=2)
    except NoiseFloorError as e:
        # the message must name the scan length actually measured
        assert "scan length 2" in str(e)
