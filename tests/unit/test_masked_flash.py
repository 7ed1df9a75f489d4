"""ONE mask-parameterized flash kernel (ops/attention/masked_flash.py)
— ISSUE 11: dense, causal, banded and BigBird training attention are
BlockMask choices of a single Pallas kernel.

Tier-1 acceptance pins:
- interpret-mode parity sweep (dense / causal / banded / BigBird) x GQA
  x dropout x stream-vs-resident against the existing oracles
  (attention_reference, block_sparse_attention_reference);
- custom-vjp gradients vs the jnp oracle;
- the sparse + dense dispatches route through the unified kernel by
  default, legacy kernels stay reachable behind flags, and the v1
  per-triple kernels are never auto-selected;
- banded layouts coarsen their walk tile (fine structure in register
  predicates) without changing numerics;
- the shard_map head wrap (parallel/pallas_shard) preserves numerics
  and gradients on a 2-way CPU mesh;
- flash.py's old mutable warn/force globals are gone: options are a
  dataclass knob, fallbacks log once per (reason, shape).

All kernel runs are interpret-mode (CPU) — scalar prefetch, HBM refs
and dynamic-index DMA interpret exactly, so the TPU kernel's numerics
are testable without hardware.
"""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from deepspeed_tpu.ops.attention import flash as F
from deepspeed_tpu.ops.attention import masked_flash as M
from deepspeed_tpu.ops.attention.masked_flash import (BlockMask,
                                                      masked_flash_attention,
                                                      masked_flash_cost,
                                                      masked_flash_reference)
from deepspeed_tpu.ops.sparse_attention import blocksparse as bs
from deepspeed_tpu.ops.sparse_attention.sparsity_config import (
    BigBirdSparsityConfig, BSLongformerSparsityConfig)

S, D = 128, 16
BLOCK = 16


@pytest.fixture(autouse=True)
def _clean_state():
    old_stream = M._FORCE_STREAM
    yield
    M._FORCE_STREAM = old_stream


def _qkv(B=2, H=4, hkv=None, s=S, d=D, seed=0, dtype=jnp.float32):
    rng = np.random.RandomState(seed)
    q = jnp.asarray(rng.randn(B, H, s, d), dtype) * 0.3
    k = jnp.asarray(rng.randn(B, hkv or H, s, d), dtype) * 0.3
    v = jnp.asarray(rng.randn(B, hkv or H, s, d), dtype) * 0.3
    return q, k, v


def _mask_for(family, heads=4, s=S, block=BLOCK):
    if family == "dense":
        return BlockMask.dense(s, s, block)
    if family == "causal":
        return BlockMask.causal(s, block)
    if family == "banded":
        cfg = BSLongformerSparsityConfig(num_heads=heads, block=block,
                                         num_sliding_window_blocks=3)
        return BlockMask.from_layout(cfg.make_layout(s), block)
    if family == "bigbird":
        cfg = BigBirdSparsityConfig(num_heads=heads, block=block,
                                    num_random_blocks=1,
                                    num_sliding_window_blocks=3,
                                    num_global_blocks=1)
        return BlockMask.from_layout(cfg.make_layout(s), block)
    raise AssertionError(family)


# --------------------------------------------------------------------- #
# the new jnp oracle is tied to the EXISTING oracles first
# --------------------------------------------------------------------- #
class TestReferenceTies:
    def test_dense_and_causal_match_attention_reference(self):
        q, k, v = _qkv()
        for family, causal in (("dense", False), ("causal", True)):
            got = masked_flash_reference(q, k, v, _mask_for(family))
            want = F.attention_reference(q, k, v, causal=causal)
            np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                                       atol=2e-6)

    @pytest.mark.parametrize("family", ["banded", "bigbird"])
    def test_layouts_match_blocksparse_reference(self, family):
        q, k, v = _qkv()
        cfg_cls = (BSLongformerSparsityConfig if family == "banded"
                   else BigBirdSparsityConfig)
        cfg = (cfg_cls(num_heads=4, block=BLOCK,
                       num_sliding_window_blocks=3) if family == "banded"
               else cfg_cls(num_heads=4, block=BLOCK, num_random_blocks=1,
                            num_sliding_window_blocks=3,
                            num_global_blocks=1))
        layout = cfg.make_layout(S)
        got = masked_flash_reference(
            q, k, v, BlockMask.from_layout(layout, BLOCK),
            sm_scale=D ** -0.5)
        want = bs.block_sparse_attention_reference(q, k, v, layout,
                                                   sm_scale=D ** -0.5)
        np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                                   atol=2e-6)


# --------------------------------------------------------------------- #
# ISSUE 11 acceptance: the parity sweep
# --------------------------------------------------------------------- #
class TestKernelParity:
    @pytest.mark.parametrize("stream", [False, True])
    @pytest.mark.parametrize("family",
                             ["dense", "causal", "banded", "bigbird"])
    def test_parity_sweep(self, family, stream):
        """dense/causal/banded/BigBird x GQA x dropout x
        stream-vs-resident, all against the oracle."""
        M._FORCE_STREAM = stream
        mask = _mask_for(family)
        rng_key = jax.random.PRNGKey(5)
        seed = F.dropout_seed_from_rng(rng_key).reshape(())
        for hkv in (4, 2):
            for rate in (0.0, 0.25):
                q, k, v = _qkv(hkv=hkv, seed=hkv)
                got = masked_flash_attention(
                    q, k, v, mask, dropout_rate=rate,
                    dropout_rng=rng_key if rate else None,
                    interpret=True)
                want = masked_flash_reference(
                    q, k, v, mask, dropout_rate=rate,
                    dropout_seed=seed if rate else None)
                np.testing.assert_allclose(
                    np.asarray(got), np.asarray(want), atol=5e-5,
                    err_msg=f"{family} stream={stream} hkv={hkv} "
                            f"rate={rate}")

    def test_stream_and_resident_agree_exactly(self):
        mask = _mask_for("causal")
        q, k, v = _qkv()
        M._FORCE_STREAM = True
        o_s = masked_flash_attention(q, k, v, mask, interpret=True)
        M._FORCE_STREAM = False
        o_r = masked_flash_attention(q, k, v, mask, interpret=True)
        np.testing.assert_array_equal(np.asarray(o_s), np.asarray(o_r))

    def test_key_mask_parity(self):
        q, k, v = _qkv(seed=3)
        kpm = np.zeros((2, S), np.float32)
        kpm[:, 100:] = -1e9
        mask = _mask_for("banded")
        got = masked_flash_attention(q, k, v, mask,
                                     key_mask=jnp.asarray(kpm),
                                     interpret=True)
        want = masked_flash_reference(q, k, v, mask,
                                      key_mask=jnp.asarray(kpm))
        np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                                   atol=5e-5)

    def test_bf16(self):
        q, k, v = _qkv(dtype=jnp.bfloat16, seed=6)
        mask = _mask_for("banded")
        got = masked_flash_attention(q, k, v, mask, interpret=True)
        want = masked_flash_reference(q, k, v, mask)
        assert got.dtype == jnp.bfloat16
        np.testing.assert_allclose(np.asarray(got, np.float32),
                                   np.asarray(want, np.float32),
                                   atol=3e-2)

    def test_empty_rows_zero_output(self):
        """Rows whose block-row has no active tile produce exact-zero
        output (blocksparse oracle semantics)."""
        active = np.ones((1, S // BLOCK, S // BLOCK), bool)
        active[0, 2] = False
        mask = BlockMask(active, np.zeros_like(active, np.uint8), BLOCK,
                         S, S)
        q, k, v = _qkv()
        out = masked_flash_attention(q, k, v, mask, interpret=True)
        rows = np.asarray(out)[:, :, 2 * BLOCK:3 * BLOCK]
        assert np.all(rows == 0.0)
        want = masked_flash_reference(q, k, v, mask)
        np.testing.assert_allclose(np.asarray(out), np.asarray(want),
                                   atol=5e-5)

    def test_per_head_layout_supported(self):
        cfg = BigBirdSparsityConfig(num_heads=4, block=BLOCK,
                                    different_layout_per_head=True,
                                    num_random_blocks=1,
                                    num_sliding_window_blocks=3,
                                    num_global_blocks=1)
        layout = cfg.make_layout(S)
        mask = BlockMask.from_layout(layout, BLOCK)
        assert mask.heads == 4                    # no collapse
        q, k, v = _qkv()
        got = masked_flash_attention(q, k, v, mask, sm_scale=D ** -0.5,
                                     interpret=True)
        want = bs.block_sparse_attention_reference(q, k, v, layout,
                                                   sm_scale=D ** -0.5)
        np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                                   atol=5e-5)


# --------------------------------------------------------------------- #
# ISSUE 11 acceptance: custom-vjp gradients vs the jnp oracle
# --------------------------------------------------------------------- #
class TestGradients:
    @pytest.mark.parametrize("family",
                             ["dense", "causal", "banded", "bigbird"])
    def test_grads_match_oracle(self, family):
        mask = _mask_for(family)
        q, k, v = _qkv(seed=9)

        def f_m(q, k, v):
            return jnp.sum(masked_flash_attention(
                q, k, v, mask, interpret=True) ** 2)

        def f_r(q, k, v):
            return jnp.sum(masked_flash_reference(q, k, v, mask) ** 2)

        gm = jax.grad(f_m, argnums=(0, 1, 2))(q, k, v)
        gr = jax.grad(f_r, argnums=(0, 1, 2))(q, k, v)
        for a, b, n in zip(gm, gr, "qkv"):
            np.testing.assert_allclose(np.asarray(a), np.asarray(b),
                                       atol=5e-4, rtol=1e-3,
                                       err_msg=f"{family} d{n}")

    @pytest.mark.parametrize("stream", [False, True])
    def test_gqa_dropout_grads(self, stream):
        """fwd/bwd dropout-mask consistency under GQA in both K/V
        paths: the backward kernels must regenerate the identical hash
        bits."""
        M._FORCE_STREAM = stream
        mask = _mask_for("causal")
        q, k, v = _qkv(hkv=2, seed=4)
        rng = jax.random.PRNGKey(21)
        seed = F.dropout_seed_from_rng(rng).reshape(())

        def f_m(q, k, v):
            return jnp.sum(masked_flash_attention(
                q, k, v, mask, dropout_rate=0.2, dropout_rng=rng,
                interpret=True) ** 2)

        def f_r(q, k, v):
            return jnp.sum(masked_flash_reference(
                q, k, v, mask, dropout_rate=0.2,
                dropout_seed=seed) ** 2)

        gm = jax.grad(f_m, argnums=(0, 1, 2))(q, k, v)
        gr = jax.grad(f_r, argnums=(0, 1, 2))(q, k, v)
        for a, b, n in zip(gm, gr, "qkv"):
            np.testing.assert_allclose(np.asarray(a), np.asarray(b),
                                       atol=1e-3, rtol=2e-3,
                                       err_msg=f"d{n}")

    def test_key_mask_cotangent_is_zero(self):
        q, k, v = _qkv()
        kpm = jnp.zeros((2, S), jnp.float32)
        mask = _mask_for("dense")
        g = jax.grad(lambda m: jnp.sum(masked_flash_attention(
            q, k, v, mask, key_mask=m, interpret=True)))(kpm)
        assert float(jnp.abs(g).max()) == 0.0


# --------------------------------------------------------------------- #
# PR 33: a row's items walked one run a kind, FULL tiles in a loop of
# their own that does none of the mask's work
# --------------------------------------------------------------------- #
def _kpm_emptying_rows(batch, s, block):
    """Batch 0 loses one whole key tile, so that tile's rows are empty
    inside a FULL item; batch 1 loses every key, so its rows are empty
    altogether (zero output, zero gradients)."""
    kpm = np.zeros((batch, s), np.float32)
    kpm[0, block:2 * block] = F.NEG_INF
    kpm[1, :] = F.NEG_INF
    return jnp.asarray(kpm)


# name: (mask builder, q heads, kv heads, head_dim, key mask, dropout)
SPLIT_WALK_CASES = {
    # one tile a row, the diagonal's: no FULL run at all
    "partial_only": (lambda: BlockMask.causal(64, 64), 2, 2, 16, False, 0.0),
    "full_only": (lambda: BlockMask.dense(64, 64, 16), 2, 2, 16, False, 0.0),
    "full_then_partial": (lambda: BlockMask.causal(64, 16), 2, 2, 16,
                          False, 0.0),
    # window == block: the far-edge tile's last query row sees nothing
    # in it, its first row all but one key
    "window_far_edge_empty_row": (
        lambda: BlockMask.causal_window(64, 16, 16), 2, 2, 16, False, 0.0),
    # a diagonal tile wider than the window: both comparisons in one tile
    "window_under_block": (
        lambda: BlockMask.causal_window(128, 40, 64), 2, 2, 16, False, 0.0),
    "full_tiles_key_mask_empties_rows": (
        lambda: BlockMask.dense(64, 64, 16), 2, 2, 16, True, 0.0),
    "causal_key_mask": (lambda: BlockMask.causal(64, 16), 2, 2, 16,
                        True, 0.0),
    "dropout": (lambda: BlockMask.causal_window(64, 40, 16), 2, 2, 16,
                False, 0.25),
    "gqa_28_over_4_d128": (
        lambda: BlockMask.causal_window(256, 192, 64), 28, 4, 128,
        False, 0.0),
    "block_128": (lambda: BlockMask.causal_window(1024, 640, 128), 2, 1, 16,
                  False, 0.0),
    "block_256": (lambda: BlockMask.causal_window(1024, 640, 256), 2, 1, 16,
                  False, 0.0),
    "block_512": (lambda: BlockMask.causal_window(1024, 640, 512), 2, 1, 16,
                  False, 0.0),
}


class TestSplitWalk:
    @pytest.mark.parametrize("stream", [False, True],
                             ids=["resident", "streamed"])
    @pytest.mark.parametrize("case", sorted(SPLIT_WALK_CASES))
    def test_parity(self, case, stream):
        """Forward, dq, dk and dv against the oracle."""
        build, h, hkv, d, with_kpm, rate = SPLIT_WALK_CASES[case]
        M._FORCE_STREAM = stream
        mask = build()
        s = mask.seq_q
        q, k, v = _qkv(B=2, H=h, hkv=hkv, s=s, d=d, seed=11)
        cot = jnp.asarray(np.random.RandomState(12).randn(*q.shape),
                          jnp.float32)
        kpm = _kpm_emptying_rows(2, s, mask.block) if with_kpm else None
        rng = jax.random.PRNGKey(7)
        seed = F.dropout_seed_from_rng(rng).reshape(())

        def ours(q, k, v):
            return masked_flash_attention(
                q, k, v, mask, key_mask=kpm, dropout_rate=rate,
                dropout_rng=rng if rate else None, interpret=True)

        def ref(q, k, v):
            return masked_flash_reference(
                q, k, v, mask, key_mask=kpm, dropout_rate=rate,
                dropout_seed=seed if rate else None)

        o, vjp = jax.vjp(ours, q, k, v)
        o_w, vjp_w = jax.vjp(ref, q, k, v)
        np.testing.assert_allclose(np.asarray(o), np.asarray(o_w),
                                   atol=5e-5, err_msg="o")
        if with_kpm:
            assert np.all(np.asarray(o)[1] == 0.0)
        for got, want, n in zip(vjp(cot), vjp_w(cot), ("dq", "dk", "dv")):
            np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                                       atol=2e-4, rtol=1e-3, err_msg=n)

    @staticmethod
    def _kernel_loops(fn, *args):
        """The ``while`` loops of every Pallas kernel under ``fn``, in
        order, a list a kernel."""
        def subjaxprs(eqn):
            for val in eqn.params.values():
                for x in (val if isinstance(val, (tuple, list)) else [val]):
                    x = getattr(x, "jaxpr", x)
                    if hasattr(x, "eqns"):
                        yield x

        kernels = []

        def visit(jaxpr):
            for eqn in jaxpr.eqns:
                if eqn.primitive.name == "pallas_call":
                    kernels.append([e for e in eqn.params["jaxpr"].eqns
                                    if e.primitive.name == "while"])
                else:
                    for sub in subjaxprs(eqn):
                        visit(sub)
        visit(jax.make_jaxpr(fn)(*args).jaxpr)
        return kernels

    @staticmethod
    def _primitives(jaxpr, tile=None):
        """Names of the primitives under ``jaxpr``; with ``tile`` only
        of those whose result has that shape (one value a score)."""
        names = set()
        for eqn in jaxpr.eqns:
            if tile is None or any(getattr(o.aval, "shape", None) == tile
                                   for o in eqn.outvars):
                names.add(eqn.primitive.name)
            for val in eqn.params.values():
                sub = getattr(val, "jaxpr", val)
                if hasattr(sub, "eqns"):
                    names |= TestSplitWalk._primitives(sub, tile)
        return names

    MASK_WORK = {"select_n", "ge", "gt", "le", "lt", "eq", "ne", "and",
                 "or", "mul"}

    def test_the_full_loop_does_none_of_the_masks_work(self):
        """A causal call without key mask: every kernel is two loops, and
        the FULL tiles' body holds no index, and per score no comparison,
        no select and no multiply in the forward (the backward's one is
        ``p * (dp - delta)``); the diagonal tiles' body holds them."""
        M._FORCE_STREAM = False
        block = 16
        mask = BlockMask.causal(64, block)
        q, k, v = _qkv(s=64, d=32)         # a tile is not (block, d)

        def fwd(q, k, v):
            return masked_flash_attention(q, k, v, mask, interpret=True)

        def bwd(q, k, v):
            return jax.grad(lambda *a: jnp.sum(fwd(*a)),
                            argnums=(0, 1, 2))(q, k, v)

        (fwd_loops,) = self._kernel_loops(fwd, q, k, v)
        kernels = self._kernel_loops(bwd, q, k, v)
        assert len(kernels) == 3                   # fwd, dq, dk/dv
        for loops, allowed in [(fwd_loops, set())] + [
                (loops, {"mul"}) for loops in kernels[1:]]:
            assert len(loops) == len(mask.run_kinds) == 2
            full, partial = (w.params["body_jaxpr"].jaxpr for w in loops)
            assert "iota" not in self._primitives(full)
            a_score = self._primitives(full, (block, block))
            assert not (a_score & self.MASK_WORK) - allowed, a_score
            assert {"exp", "dot_general", "sub"} <= a_score
            assert "iota" in self._primitives(partial)
            assert {"select_n", "ge"} <= self._primitives(
                partial, (block, block))

    def test_a_key_mask_keeps_the_validity_select_in_the_full_loop(self):
        M._FORCE_STREAM = False
        mask = BlockMask.dense(64, 64, 16)
        q, k, v = _qkv(s=64, d=32)
        kpm = jnp.zeros((2, 64), jnp.float32)
        for key_mask, selects in ((None, False), (kpm, True)):
            (loops,) = self._kernel_loops(
                lambda q, k, v: masked_flash_attention(
                    q, k, v, mask, key_mask=key_mask, interpret=True),
                q, k, v)
            (full,) = loops                        # a dense mask: one run
            full = full.params["body_jaxpr"].jaxpr
            assert ("select_n" in self._primitives(full, (16, 16))
                    ) == selects
            assert "iota" not in self._primitives(full)

    @pytest.mark.parametrize("name,build,full,total", [
        ("gpt2_causal_1k_at_512", lambda: BlockMask.causal(1024, 512), 1, 3),
        ("causal_8k_at_512", lambda: BlockMask.causal(8192, 512), 120, 136),
        ("window_4k_of_8k_at_512",
         lambda: BlockMask.causal_window(8192, 4096, 512), 84, 108),
        ("window_under_block",
         lambda: BlockMask.causal_window(512, 40, 128), 0, 7),
        ("coarsened_longformer", lambda: BlockMask.from_layout(
            BSLongformerSparsityConfig(
                num_heads=2, block=128,
                num_sliding_window_blocks=3).make_layout(2048), 128),
         None, None),
    ])
    def test_runs_list_full_items_first(self, name, build, full, total):
        mask = build()
        if full is not None:
            assert (mask.n_full, mask.nnz) == (full, total)
        assert f"full={mask.n_full}, partial={mask.nnz - mask.n_full}" \
            in mask.describe()
        assert mask.run_kinds == tuple(sorted(set(
            mask.kinds[mask.active].tolist())))
        for (offs, ends, idxs), kinds, active in (
                (mask.csr(), mask.kinds, mask.active),
                (mask.csc(), mask.kinds.transpose(0, 2, 1),
                 mask.active.transpose(0, 2, 1))):
            n_rows = active.shape[0] * active.shape[1]
            assert ends.shape == (len(mask.run_kinds), n_rows)
            kinds, active = (x.reshape(n_rows, -1) for x in (kinds, active))
            for row in range(n_rows):
                items = idxs[offs[row]:offs[row] + ends[-1, row]]
                assert sorted(items) == np.nonzero(active[row])[0].tolist()
                lo = 0
                for kind, hi in zip(mask.run_kinds, ends[:, row]):
                    run = items[lo:hi]
                    assert (kinds[row, run] == kind).all()
                    assert (np.diff(run) > 0).all()
                    lo = hi
                if mask.run_kinds[0] == M.KIND_FULL:
                    assert ends[0, row] == (
                        active[row] & (kinds[row] == 0)).sum()


# --------------------------------------------------------------------- #
# banded coarsening: big walk tiles, fine structure in registers
# --------------------------------------------------------------------- #
class TestCoarsening:
    def _longformer(self, s=2048, fb=128):
        cfg = BSLongformerSparsityConfig(num_heads=2, block=fb,
                                         num_sliding_window_blocks=3)
        return cfg.make_layout(s), s, fb

    def test_banded_layout_coarsens(self):
        layout, s, fb = self._longformer()
        mask = BlockMask.from_layout(layout, fb)
        assert mask.block > fb, mask.describe()
        assert mask.band is not None and mask.has_partials
        # the expansion must reproduce the layout's fine bits exactly
        dense = mask.dense_additive()
        want = bs.layout_additive_mask(layout, fb)[:1]
        np.testing.assert_array_equal(dense == 0.0, want == 0.0)

    def test_coarse_matches_fine_and_oracle(self):
        layout, s, fb = self._longformer()
        q, k, v = _qkv(B=1, H=2, s=s, seed=2)
        coarse = BlockMask.from_layout(layout, fb)
        fine = BlockMask.from_layout(layout, fb, walk_block=0)
        assert fine.block == fb and coarse.block > fb
        o_c = masked_flash_attention(q, k, v, coarse,
                                     sm_scale=D ** -0.5, interpret=True)
        o_f = masked_flash_attention(q, k, v, fine, sm_scale=D ** -0.5,
                                     interpret=True)
        want = bs.block_sparse_attention_reference(q, k, v, layout,
                                                   sm_scale=D ** -0.5)
        np.testing.assert_allclose(np.asarray(o_c), np.asarray(want),
                                   atol=1e-4, rtol=1e-4)
        np.testing.assert_allclose(np.asarray(o_f), np.asarray(want),
                                   atol=1e-4, rtol=1e-4)

    def test_causal_banded_clip(self):
        """A causally-clipped band (unidirectional Longformer-class
        realized bits) coarsens with the clip folded into the register
        predicate."""
        n = 16
        idx = np.arange(n)
        rb, cb = idx[:, None], idx[None, :]
        pred = ((rb < 1) | (cb < 1) | (np.abs(rb - cb) <= 1)) & (cb <= rb)
        layout = np.broadcast_to(pred.astype(np.int32),
                                 (2, n, n)).copy()
        fb = 128
        s = n * fb
        mask = BlockMask.from_layout(layout, fb)
        assert mask.block > fb and mask.band is not None
        assert mask.band[-1] is True              # clip folded in
        q, k, v = _qkv(B=1, H=2, s=s, seed=8)
        got = masked_flash_attention(q, k, v, mask, sm_scale=D ** -0.5,
                                     interpret=True)
        want = bs.block_sparse_attention_reference(q, k, v, layout,
                                                   sm_scale=D ** -0.5)
        np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                                   atol=1e-4, rtol=1e-4)

    def test_bigbird_declines_coarsening(self):
        mask = _mask_for("bigbird")
        assert mask.block == BLOCK and mask.band is None

    def test_sparsity_config_resolves_to_block_mask(self):
        cfg = BSLongformerSparsityConfig(num_heads=2, block=128,
                                         num_sliding_window_blocks=3)
        mask = cfg.make_block_mask(2048)
        assert isinstance(mask, BlockMask) and mask.heads == 1
        assert mask.block > 128                    # coarsened
        assert cfg.make_block_mask(2048, walk_block=0).block == 128


# --------------------------------------------------------------------- #
# dispatch: ONE kernel serves every path; v1 retired
# --------------------------------------------------------------------- #
class TestDispatch:
    def test_sparse_dispatch_defaults_to_masked(self):
        cfg = BSLongformerSparsityConfig(num_heads=2, block=32,
                                         num_sliding_window_blocks=3)
        L = cfg.make_layout(512)
        q, k, v = _qkv(B=1, H=2, s=512, seed=1)
        got = bs.block_sparse_attention(q, k, v, L)
        want = bs.block_sparse_attention_reference(q, k, v, L)
        np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                                   atol=5e-5, rtol=5e-5)

    def test_flash_attention_routes_masked_by_default(self):
        q, k, v = _qkv(seed=12)
        o = F.flash_attention(q, k, v, causal=True, interpret=True)
        want = F.attention_reference(q, k, v, causal=True)
        np.testing.assert_allclose(np.asarray(o), np.asarray(want),
                                   atol=2e-5, rtol=2e-5)

    def test_causal_cross_lengths_run_the_chunk_kernels(self):
        """A causal call with sq != sk has no square-block mask: it runs
        flash.py's own kernels (the ones ring attention builds on)."""
        rng = np.random.RandomState(15)
        q = jnp.asarray(rng.randn(2, 4, 64, D), jnp.float32) * 0.3
        k, v = (jnp.asarray(rng.randn(2, 4, S, D), jnp.float32) * 0.3
                for _ in range(2))

        def loss(fn):
            return lambda q, k, v: jnp.sum(fn(q, k, v) ** 2)

        def attn(q, k, v):
            return F.flash_attention(q, k, v, causal=True, interpret=True)

        def ref(q, k, v):
            return F.attention_reference(q, k, v, causal=True)

        np.testing.assert_allclose(np.asarray(attn(q, k, v)),
                                   np.asarray(ref(q, k, v)),
                                   atol=2e-5, rtol=2e-5)
        g = jax.grad(loss(attn), (0, 1, 2))(q, k, v)
        gr = jax.grad(loss(ref), (0, 1, 2))(q, k, v)
        for a, b in zip(g, gr):
            np.testing.assert_allclose(np.asarray(a), np.asarray(b),
                                       atol=1e-4, rtol=1e-3)

    def test_nothing_selects_a_kernel(self):
        """One kernel, chosen by nothing: the two modules carry no
        module-level switch beyond the constants, caches and test hooks
        listed here, no options object, and the environment variable
        that used to pick the dense kernel changes nothing."""
        import subprocess
        import sys
        import deepspeed_tpu.ops.attention as A

        def switches(mod):
            return {n for n, v in vars(mod).items()
                    if n.lstrip("_").isupper() and not callable(v)}
        assert switches(bs) == {"NEG_INF", "VALID_THRESH", "_MASK_CACHE"}
        assert switches(F) == {
            "NEG_INF", "STREAM_THRESHOLD", "_ONCE_KEYS",
            "_BLOCK_ENTRIES", "_BLOCK_TABLE", "_FORCE_BLOCKS",
            "_DENSE_MASK_CACHE", "_DENSE_MASK_CAP"}
        for mod in (bs, F, A):
            assert not [n for n in vars(mod) if "options" in n.lower()
                        or "planned" in n or "coarse" in n.lower()], mod
        assert not hasattr(F, "os")
        retired_env = "DSTPU_ATTENTION_" + "KERNEL"   # in two pieces: a
        # grep for the retired name should find the records, not a test
        import os
        prog = (
            "import jax, jax.numpy as jnp\n"
            "from deepspeed_tpu.ops.attention.flash import "
            "flash_attention\n"
            "s = jax.ShapeDtypeStruct((1, 2, 128, 16), jnp.float32)\n"
            "print(jax.jit(lambda q, k, v: flash_attention("
            "q, k, v, causal=True)).lower(s, s, s).as_text())\n")
        r = subprocess.run(
            [sys.executable, "-c", prog], capture_output=True, text=True,
            timeout=300, cwd=os.path.dirname(os.path.dirname(
                os.path.dirname(os.path.abspath(__file__)))),
            env=dict(os.environ, JAX_PLATFORMS="cpu",
                     **{retired_env: "flash"}))
        assert r.returncode == 0, r.stderr[-2000:]
        s = jax.ShapeDtypeStruct((1, 2, 128, 16), jnp.float32)
        here = jax.jit(lambda q, k, v: F.flash_attention(
            q, k, v, causal=True)).lower(s, s, s).as_text()
        # (the logger's once-lines share stdout with the program text)
        there = r.stdout[r.stdout.index("module @"):]
        assert there.strip() == here.strip()


# --------------------------------------------------------------------- #
# satellite: module-global hygiene — options + once-logging
# --------------------------------------------------------------------- #
class TestOnceLogging:
    def test_log_once_per_shape_reason(self):
        F.reset_once_logging()
        F.log_once(("x", 128), "m1")
        F.log_once(("x", 128), "m1")
        F.log_once(("x", 256), "m2")
        assert len(F._ONCE_KEYS) == 2
        F.reset_once_logging()
        assert not F._ONCE_KEYS

    def test_unknown_masked_block_logs_single_line(self):
        F.reset_once_logging()
        b1 = F.pick_masked_block(192, 192, 48)
        b2 = F.pick_masked_block(192, 192, 48)
        assert b1 == b2 and 192 % b1 == 0
        keys = [k for k in F._ONCE_KEYS if k[0] == "masked-block"]
        assert len(keys) == 1

    def test_no_mutable_warn_globals_remain(self):
        for name in ("_FORCE_REFERENCE", "_WARNED_IRREGULAR_FALLBACK",
                     "_WARNED_IRREGULAR_STREAM", "_WARNED_REF_STREAM"):
            assert not hasattr(F, name), name


# --------------------------------------------------------------------- #
# shard_map head wrap (parallel/pallas_shard)
# --------------------------------------------------------------------- #
class TestShardedMaskedFlash:
    def _mesh(self):
        from deepspeed_tpu.parallel.mesh import build_mesh
        return build_mesh({"model": 2})

    def test_sharded_parity_and_grads(self):
        from deepspeed_tpu.parallel.pallas_shard import \
            sharded_masked_flash
        mesh = self._mesh()
        mask = _mask_for("banded")
        q, k, v = _qkv(seed=15)

        def f_sh(q, k, v):
            return jnp.sum(sharded_masked_flash(
                q, k, v, mask, mesh=mesh, interpret=True) ** 2)

        def f_ref(q, k, v):
            return jnp.sum(masked_flash_reference(q, k, v, mask) ** 2)

        o = sharded_masked_flash(q, k, v, mask, mesh=mesh,
                                 interpret=True)
        np.testing.assert_allclose(
            np.asarray(o), np.asarray(masked_flash_reference(q, k, v,
                                                             mask)),
            atol=5e-5)
        gs = jax.grad(f_sh, argnums=(0, 1, 2))(q, k, v)
        gr = jax.grad(f_ref, argnums=(0, 1, 2))(q, k, v)
        for a, b, n in zip(gs, gr, "qkv"):
            np.testing.assert_allclose(np.asarray(a), np.asarray(b),
                                       atol=5e-4, rtol=1e-3,
                                       err_msg=f"d{n}")

    def test_sharded_gqa_under_jit(self):
        from deepspeed_tpu.parallel.pallas_shard import \
            sharded_masked_flash
        mesh = self._mesh()
        mask = _mask_for("causal")
        q, k, v = _qkv(hkv=2, seed=16)
        f = jax.jit(lambda q, k, v: sharded_masked_flash(
            q, k, v, mask, mesh=mesh, interpret=True))
        np.testing.assert_allclose(
            np.asarray(f(q, k, v)),
            np.asarray(masked_flash_reference(q, k, v, mask)),
            atol=5e-5)

    def test_per_head_mask_rejected(self):
        from deepspeed_tpu.parallel.pallas_shard import \
            sharded_masked_flash
        mesh = self._mesh()
        active = np.ones((4, S // BLOCK, S // BLOCK), bool)
        active[1, 0, 0] = False                    # heads differ
        mask = BlockMask(active, np.zeros_like(active, np.uint8),
                         BLOCK, S, S)
        q, k, v = _qkv()
        with pytest.raises(AssertionError, match="head-uniform"):
            sharded_masked_flash(q, k, v, mask, mesh=mesh,
                                 interpret=True)


class TestFlashAttentionUnderKernelMesh:
    """The training engine's GSPMD path: ``flash_attention`` traced
    inside ``pallas_kernel_mesh`` runs shard_mapped — batch over the
    data axes, heads over the model axis. (That the chip's compiler
    needs this wrap is tests/unit/test_tpu_compile.py's to show.)"""

    def _mesh(self):
        from deepspeed_tpu.parallel.mesh import build_mesh
        return build_mesh({"data": 2, "model": 2})

    @pytest.mark.parametrize("batch_axes,axis", [
        (("data",), "model"),       # batch and heads sharded
        (("data",), "absent"),      # data parallel only
        ((), "model"),              # heads only (the serving engines)
    ])
    def test_parity_and_grads(self, batch_axes, axis):
        from deepspeed_tpu.parallel.pallas_shard import (
            current_kernel_mesh, pallas_kernel_mesh)
        mesh = self._mesh()
        q, k, v = _qkv(hkv=2, seed=21)

        def loss(q, k, v, wrapped):
            ctx = pallas_kernel_mesh(mesh if wrapped else None, axis,
                                     batch_axes=batch_axes)
            with ctx:
                assert (current_kernel_mesh() is not None) == wrapped
                o = F.flash_attention(q, k, v, causal=True,
                                      interpret=True)
            return jnp.sum(o ** 2), o

        grad = jax.jit(jax.value_and_grad(loss, argnums=(0, 1, 2),
                                          has_aux=True),
                       static_argnums=3)
        (_, o_sh), g_sh = grad(q, k, v, True)
        (_, o_ref), g_ref = grad(q, k, v, False)
        np.testing.assert_allclose(np.asarray(o_sh), np.asarray(o_ref),
                                   atol=1e-6)
        for a, b, n in zip(g_sh, g_ref, "qkv"):
            np.testing.assert_allclose(np.asarray(a), np.asarray(b),
                                       atol=1e-5, err_msg=f"d{n}")

    def test_shards_draw_different_dropout_masks(self):
        """The in-kernel hash is keyed on the LOCAL (batch, head) index:
        without the per-shard rng fold, two data shards fed the same
        rows would drop the same cells."""
        from deepspeed_tpu.parallel.pallas_shard import pallas_kernel_mesh
        mesh = self._mesh()
        q, k, v = _qkv(B=1, seed=22)
        q, k, v = (jnp.concatenate([x, x]) for x in (q, k, v))

        @jax.jit
        def attend(q, k, v):
            with pallas_kernel_mesh(mesh, batch_axes=("data",)):
                return F.flash_attention(
                    q, k, v, causal=True, dropout_rate=0.5,
                    dropout_rng=jax.random.PRNGKey(3), interpret=True)

        o = np.asarray(attend(q, k, v))
        assert np.isfinite(o).all()
        assert not np.allclose(o[0], o[1])

    def test_indivisible_batch_stays_replicated(self):
        from deepspeed_tpu.parallel.pallas_shard import pallas_kernel_mesh
        mesh = self._mesh()
        q, k, v = _qkv(B=3, seed=23)
        with pallas_kernel_mesh(mesh, "absent", batch_axes=("data",)):
            o = F.flash_attention(q, k, v, causal=True, interpret=True)
        np.testing.assert_allclose(
            np.asarray(o),
            np.asarray(F.flash_attention(q, k, v, causal=True,
                                         interpret=True)), atol=1e-6)


# --------------------------------------------------------------------- #
# cost model
# --------------------------------------------------------------------- #
class TestCostModel:
    def test_work_proportional_to_nonzero_blocks(self):
        dense = _mask_for("dense")
        bird = _mask_for("bigbird")
        cd = masked_flash_cost(dense, batch=1, heads=4, head_dim=64)
        cb = masked_flash_cost(bird, batch=1, heads=4, head_dim=64)
        # FLOPs scale exactly with items at equal block size
        assert cd["flops"] / cb["flops"] == pytest.approx(
            cd["items"] / cb["items"])
        assert cb["bytes"] < cd["bytes"]

    def test_item_counts_match_csr(self):
        mask = _mask_for("bigbird")
        offs, ends, cols = mask.csr()
        assert int(ends[-1].sum()) == mask.nnz == len(cols)
        coffs, cends, crows = mask.csc()
        assert int(cends[-1].sum()) == mask.nnz
