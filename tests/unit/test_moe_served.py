"""The dropless expert layer with its activation an argument, and the
few-rows form a decode step serves through (ops/moe.py; served by
models/solar_open2.py), against a plain sum over the held experts. Beside
test_moe.py, whose GShard end-to-end cases are tier-2 (slow)."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

import deepspeed_tpu  # noqa: F401


def _glu_sum(x, idx, p, experts, first, activation):
    """sum over a token's choices on held experts of p E_e(x), plainly."""
    held = experts["w_gate"].shape[0]
    y = jnp.zeros(x.shape, jnp.float32)
    for e in range(held):
        w = jnp.sum(jnp.where(idx == first + e, p, 0.0), axis=-1)
        out = (activation(x @ experts["w_gate"][e])
               * (x @ experts["w_up"][e])) @ experts["w_down"][e]
        y = y + w[:, None] * out
    return y


def _glu_case(tokens=96, hidden=32, ffn=48, held=3, experts=12, top_k=4):
    ks = jax.random.split(jax.random.PRNGKey(7), 6)
    n = lambda k, shape: jax.random.normal(k, shape, jnp.float32) * 0.3
    x = n(ks[0], (tokens, hidden))
    tables = {"w_gate": n(ks[1], (held, hidden, ffn)),
              "w_up": n(ks[2], (held, hidden, ffn)),
              "w_down": n(ks[3], (held, ffn, hidden))}
    from deepspeed_tpu.ops.moe import route_top_k
    idx, p, _ = route_top_k(x, n(ks[4], (hidden, experts)), top_k)
    return x, idx, p, tables, experts


@pytest.mark.parametrize("activation", [jax.nn.relu, jax.nn.silu],
                         ids=["reglu", "swiglu"])
@pytest.mark.parametrize("tile", [None, (128, 1280, 640)])
def test_dropless_experts_take_their_activation(activation, tile):
    from deepspeed_tpu.ops.moe import dropless_experts
    x, idx, p, tables, experts = _glu_case()
    with jax.default_matmul_precision("highest"):
        y, counts = dropless_experts(x, idx, p, tables, (2, 3), experts,
                                     activation, tile=tile)
        want = _glu_sum(x, idx, p, tables, 2, activation)
    np.testing.assert_allclose(np.asarray(y), np.asarray(want), atol=2e-5)
    assert int(counts.sum()) == int(((idx >= 2) & (idx < 5)).sum())


def test_the_old_name_is_the_reglu_layer():
    """`dropless_reglu_experts` stays for the benchmark's family, which
    checks the train-8k cell's backward pass through it."""
    from deepspeed_tpu.ops.moe import (dropless_experts,
                                       dropless_reglu_experts)
    x, idx, p, tables, experts = _glu_case()
    a, ca = dropless_reglu_experts(x, idx, p, tables, (2, 3), experts)
    b, cb = dropless_experts(x, idx, p, tables, (2, 3), experts,
                             jax.nn.relu)
    np.testing.assert_array_equal(np.asarray(a), np.asarray(b))
    np.testing.assert_array_equal(np.asarray(ca), np.asarray(cb))


@pytest.mark.parametrize("first", [0, 2, 9])
def test_held_experts_on_every_row_equal_the_dropless_layer(first):
    from deepspeed_tpu.ops.moe import (dropless_experts,
                                       held_experts_every_row)
    x, idx, p, tables, experts = _glu_case(tokens=13)
    with jax.default_matmul_precision("highest"):
        y, counts = held_experts_every_row(x, idx, p, tables, (first, 3),
                                           jax.nn.silu)
        want, want_counts = dropless_experts(
            x, idx, p, tables, (first, 3), experts, jax.nn.silu)
    np.testing.assert_allclose(np.asarray(y), np.asarray(want), atol=2e-5)
    np.testing.assert_array_equal(np.asarray(counts),
                                  np.asarray(want_counts))


@pytest.mark.parametrize("dim,cap,want", [(4096, 1280, 1024),
                                          (1280, 640, 640), (1280, 1280, 1280),
                                          (4096, 640, 512), (96, 1280, 96)])
def test_a_capped_tile_divides_its_operand(dim, cap, want):
    from deepspeed_tpu.ops.moe import _whole_tile
    assert _whole_tile(dim, cap) == want
