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


# ------------------------------------------------------------------ #
# the served entry point: work that follows the rows that count
# ------------------------------------------------------------------ #

_SERVED_EXPERTS, _SERVED_HELD, _SERVED_TOP_K = 12, 4, 4


def _served_case(first, landed, tokens=96, hidden=32, ffn=48):
    """Tables of `_SERVED_HELD` experts from `first` and a routing in
    which `landed` of a token's four choices name one of them."""
    x, _, p, tables, _ = _glu_case(tokens, hidden, ffn, _SERVED_HELD,
                                   _SERVED_EXPERTS, _SERVED_TOP_K)
    rng = np.random.default_rng(first + landed)
    here = np.arange(first, first + _SERVED_HELD)
    away = np.setdiff1d(np.arange(_SERVED_EXPERTS), here)
    idx = np.stack([rng.permutation(np.concatenate([
        rng.permutation(here)[:landed],
        rng.permutation(away)[:_SERVED_TOP_K - landed]]))
        for _ in range(tokens)]).astype(np.int32)
    return x, jnp.asarray(idx), p, tables


@pytest.mark.parametrize("activation", [jax.nn.relu, jax.nn.silu],
                         ids=["reglu", "swiglu"])
@pytest.mark.parametrize("tile", [None, (128, 1280, 640)],
                         ids=["tile512", "tile128"])
@pytest.mark.parametrize("first", [0, 4, 8], ids=["first", "middle", "last"])
@pytest.mark.parametrize("landed", [0, 2, 4],
                         ids=["none_lands", "half_lands", "all_land"])
def test_served_experts_equal_the_dropless_layer(activation, tile, first,
                                                 landed):
    from deepspeed_tpu.ops.moe import (chunk_rows, dropless_experts,
                                       served_experts, served_turn_rows)
    x, idx, p, tables = _served_case(first, landed)
    held = (first, _SERVED_HELD)
    with jax.default_matmul_precision("highest"):
        want, want_counts = dropless_experts(
            x, idx, p, tables, held, _SERVED_EXPERTS, activation, tile=tile)
        y, counts, rows = served_experts(
            x, idx, p, tables, held, _SERVED_EXPERTS, activation, tile=tile)
    np.testing.assert_allclose(np.asarray(y), np.asarray(want), atol=1e-6)
    np.testing.assert_array_equal(np.asarray(counts),
                                  np.asarray(want_counts))
    assignments = idx.size
    turn = served_turn_rows(assignments, _SERVED_HELD, _SERVED_EXPERTS,
                            512 if tile is None else tile[0])
    lands = idx.shape[0] * landed
    static = chunk_rows(assignments, _SERVED_HELD, _SERVED_EXPERTS)
    assert [int(r) for r in rows] == [
        -(-lands // turn) * turn, -(-assignments // static) * static]
    if not landed:
        assert not np.asarray(y).any()


@pytest.mark.parametrize("top_k", [2, 6, 10])
@pytest.mark.parametrize("block", [32, 40],
                         ids=["block_divides", "block_does_not"])
@pytest.mark.parametrize("share_here", [0.4, 0.0],
                         ids=["some_here", "no_pick_here"])
def test_weighted_rows_equal_a_plain_sum_over_picks(top_k, block,
                                                    share_here):
    """The ONE layout of `_weighted_rows` (a block's picks a choice a
    slab), the trained layer's and the served combine's: token t sums
    `w[t, j] * rows[pos[t, j]]` over its choices that are `here`, in
    float32, whatever k, whether `block` divides the tokens or not, and
    exactly zero where no pick is here."""
    from deepspeed_tpu.ops.moe import _weighted_rows
    tokens, hidden, buffer = 96, 32, 160
    rng = np.random.default_rng(top_k)
    rows = jnp.asarray(rng.standard_normal((buffer, hidden)), jnp.bfloat16)
    w = jnp.asarray(rng.random((tokens, top_k)), jnp.float32)
    pos = jnp.asarray(rng.integers(0, buffer, (tokens, top_k)), jnp.int32)
    here = jnp.asarray(rng.random((tokens, top_k)) < share_here)
    got = jax.jit(lambda *a: _weighted_rows(*a, block))(rows, w, pos, here)
    picks = jnp.where(here[..., None], rows[pos].astype(jnp.float32), 0.0)
    want = jnp.sum(picks * w[..., None], axis=1)
    assert got.dtype == jnp.float32 and got.shape == (tokens, hidden)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want), atol=2e-6)
    if not share_here:
        assert not np.asarray(got).any()


@pytest.mark.parametrize("true_tokens", [0, 1, 37, 96])
def test_served_experts_work_the_counted_rows_alone(true_tokens):
    """A mask of the tokens that count (a bucket's true positions): their
    rows as the dropless layer gives them, the rest exactly zero, the
    counts those of the counted rows, the turns as many as reach the
    last row that counts."""
    from deepspeed_tpu.ops.moe import (dropless_experts, served_experts,
                                       served_turn_rows)
    x, idx, p, tables = _served_case(4, 2)
    held, tile = (4, _SERVED_HELD), (128, 1280, 640)
    counted = np.arange(idx.shape[0]) % 96 < true_tokens
    with jax.default_matmul_precision("highest"):
        want, _ = dropless_experts(x, idx, p, tables, held,
                                   _SERVED_EXPERTS, jax.nn.silu, tile=tile)
        y, counts, rows = jax.jit(lambda c: served_experts(
            x, idx, p, tables, held, _SERVED_EXPERTS, jax.nn.silu,
            tile=tile, counted=c))(jnp.asarray(counted))
    y = np.asarray(y)
    np.testing.assert_allclose(y[counted], np.asarray(want)[counted],
                               atol=1e-6)
    assert not y[~counted].any()
    local = np.asarray(idx)[counted] - 4
    np.testing.assert_array_equal(
        np.asarray(counts),
        [(local == e).sum() for e in range(_SERVED_HELD)])
    turn = served_turn_rows(idx.size, _SERVED_HELD, _SERVED_EXPERTS, 128)
    assert turn == 128
    assert int(rows[0]) == -(-2 * true_tokens // turn) * turn


@pytest.mark.parametrize("assignments,held,experts,tile,want", [
    (81920, 36, 72, 256, 5120),      # granite, 2 x 4,096: an eighth
    (10240, 36, 72, 256, 768),       # granite, 1 x 1,024: whole tiles
    (2048, 40, 320, 128, 128),       # Solar, 1 x 256: at least a tile
    (32768, 40, 320, 128, 512),      # Solar, 4 x 1,024
    (64, 8, 8, 128, 128),            # at most every assignment
])
def test_a_served_turn_comes_from_the_shapes(assignments, held, experts,
                                             tile, want):
    from deepspeed_tpu.ops.moe import served_turn_rows
    assert served_turn_rows(assignments, held, experts, tile) == want


def _decode_case(tokens, hidden, ffn, experts, top_k, seed=11):
    ks = jax.random.split(jax.random.PRNGKey(seed), 6)
    n = lambda k, shape: jax.random.normal(k, shape, jnp.float32) * 0.3
    x = n(ks[0], (tokens, hidden))
    tables = {"w_gate": n(ks[1], (experts, hidden, ffn)),
              "w_up": n(ks[2], (experts, hidden, ffn)),
              "w_down": n(ks[3], (experts, ffn, hidden))}
    from deepspeed_tpu.ops.moe import route_group_limited
    # expert 5 gets no row: its bias keeps it out of every choice
    bias = jnp.zeros((experts,)).at[5].set(-10.0)
    idx, p, _, _ = route_group_limited(x, n(ks[4], (hidden, experts)),
                                       top_k, 1, 1, bias=bias)
    return x, idx, p, tables


@pytest.mark.parametrize("inactive", [0, 7], ids=["all_active", "inactive"])
def test_every_row_with_a_whole_layer_held_equals_the_dense_sum(inactive):
    """All 16 experts held, top 4, 41 rows: every held expert on every
    row == the plain sum, with rows that do not decode (their picks are
    worked and never counted) and an expert that gets no row."""
    from deepspeed_tpu.ops.moe import held_experts_every_row
    x, idx, p, tables = _decode_case(41, 32, 48, 16, 4)
    active = None if not inactive else jnp.arange(41) % inactive != 0
    with jax.default_matmul_precision("highest"):
        y, counts = held_experts_every_row(
            x, idx, p, tables, (0, 16), jax.nn.silu, active)
        dense = _glu_sum(x, idx, p, tables, 0, jax.nn.silu)
    rows = np.ones(41, bool) if active is None else np.asarray(active)
    np.testing.assert_allclose(np.asarray(y)[rows], np.asarray(dense)[rows],
                               atol=2e-5)
    assert int(counts[5]) == 0 and int(counts.sum()) == 4 * int(rows.sum())


def test_eight_shares_of_eight_add_up_to_the_whole_layers_sum():
    """The family is TOLD what it holds like any other: shares (0, 8),
    (8, 8), ... (56, 8) of 64 experts through the decode form add up to
    what all 64 held give, and to the plain sum."""
    from deepspeed_tpu.ops.moe import held_experts_every_row
    x, idx, p, tables = _decode_case(23, 32, 16, 64, 4, seed=13)
    with jax.default_matmul_precision("highest"):
        whole, counts = held_experts_every_row(
            x, idx, p, tables, (0, 64), jax.nn.silu)
        parts, landed = 0.0, 0
        for first in range(0, 64, 8):
            share = {n: t[first:first + 8] for n, t in tables.items()}
            y, c = held_experts_every_row(
                x, idx, p, share, (first, 8), jax.nn.silu)
            parts, landed = parts + y, landed + int(c.sum())
        dense = _glu_sum(x, idx, p, tables, 0, jax.nn.silu)
    np.testing.assert_allclose(np.asarray(parts), np.asarray(whole),
                               atol=2e-5)
    np.testing.assert_allclose(np.asarray(whole), np.asarray(dense),
                               atol=2e-5)
    assert landed == int(counts.sum()) == 23 * 4
