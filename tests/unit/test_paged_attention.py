"""Pallas paged-attention decode kernel (ops/attention/paged.py) —
ISSUE 8: serve from pages in place, O(live tokens) instead of
O(max_len).

Tier-1 acceptance pins:
- kernel parity vs the gather oracle across page_size {8, 16, 128},
  GQA ratios {1, 4}, and the cache_position edge cases (position 0,
  exactly page-aligned, one-past-page, last slot of the table);
- greedy engine outputs from the pallas decode path EXACTLY match the
  gather path for gpt2 AND llama under continuous batching with prefix
  reuse, warmup program count and steady_state_recompiles == 0
  unchanged;
- the compiled pallas decode program contains no max_len-sized gather
  (the gather program's per-layer stripe is the contrast);
- the which-decode-attention telemetry (Serve/decode_attn_path +
  decode_attn_path event) lands in events.jsonl and obs_report.

All kernel runs here are interpret-mode (CPU): scalar prefetch, HBM
refs, dynamic-index DMA and semaphores interpret exactly, which is
what makes the TPU kernel's numerics testable without hardware.
"""

import importlib.util
import json
import os

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from tests.unit.test_inference import (TINY_INF, tiny_gpt2, tiny_llama)

REPO = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))


def _load_tool(name):
    spec = importlib.util.spec_from_file_location(
        name, os.path.join(REPO, "tools", f"{name}.py"))
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


# every kernel case reads this layer of a two-layer pool whose other
# layer is NaN: a reader that strays off its layer shows at once
LAYER = 1


def _pool_case(rng, kv_heads, gqa, page_size, pages_per_seq, hd=16,
               num_pages=None, batch=5):
    """One kernel test case: random stacked pools in the pool's layout
    ``(layers, num_pages, page_size, kv_heads * hd)`` (one token a row,
    heads major within it; layer ``LAYER`` holds the data, the other is
    NaN) + per-row tables of distinct non-null pages + queries."""
    H = kv_heads * gqa
    num_pages = num_pages or (batch * pages_per_seq + 1)

    def pool():
        data = np.full((2, num_pages, page_size, kv_heads * hd), np.nan)
        data[LAYER] = rng.randn(num_pages, page_size, kv_heads * hd)
        return jnp.asarray(data, jnp.float32)
    kpool, vpool = pool(), pool()
    q = jnp.asarray(rng.randn(batch, H, hd), jnp.float32)
    tables = np.zeros((batch, pages_per_seq), np.int32)
    avail = list(range(1, num_pages))
    rng.shuffle(avail)
    for b in range(batch):
        tables[b] = [avail.pop() for _ in range(pages_per_seq)]
    return q, kpool, vpool, tables


# the blocks (tokens a loop turn) the parity cases run at: one page of
# 16 a turn, and the shipped 8
BLOCKS_PARITY = [16, 128]


def _parity_case(monkeypatch, block_tokens, heads, kv_heads, hd, pool_dtype,
                 ps, pos, dead_rows=(), table_pages=None):
    """The kernel at ``block_tokens`` a loop turn against the float32
    oracle: rows at ``pos``, of which ``dead_rows`` are inactive slots
    (all-null tables, position 0) that must come out finite; every
    reserved-but-unwritten page is NaN."""
    from deepspeed_tpu.ops.attention import paged
    from deepspeed_tpu.ops.attention.paged import (
        paged_decode_attention, paged_decode_reference)
    monkeypatch.setattr(paged, "_BLOCK_TOKENS", block_tokens)
    P = table_pages or max(pos) // ps + 2           # one dead column
    rng = np.random.RandomState(heads + hd + len(pos))
    q, kpool, vpool, tables = _pool_case(
        rng, kv_heads=kv_heads, gqa=heads // kv_heads, page_size=ps,
        pages_per_seq=P, hd=hd, batch=len(pos))
    live = [b for b in range(len(pos)) if b not in dead_rows]
    for b in dead_rows:
        tables[b] = 0                               # an inactive slot
    for b in live:                                  # reserved, unwritten
        dead = tables[b, pos[b] // ps + 1:]
        kpool = kpool.at[LAYER, dead].set(jnp.nan)
        vpool = vpool.at[LAYER, dead].set(jnp.nan)
    q, kpool, vpool = (x.astype(pool_dtype) for x in (q, kpool, vpool))
    tables, pos = jnp.asarray(tables), jnp.asarray(pos, jnp.int32)
    out = paged_decode_attention(q, kpool, vpool, tables, pos,
                                 interpret=True, layer=LAYER)
    assert out.shape == q.shape and out.dtype == q.dtype
    assert bool(jnp.all(jnp.isfinite(out)))
    if not live:
        return
    live = np.asarray(live)
    clean = [jnp.nan_to_num(x.astype(jnp.float32))
             for x in (q, kpool, vpool)]
    ref = paged_decode_reference(clean[0][live], clean[1], clean[2],
                                 tables[live], pos[live], layer=LAYER)
    # the output is rounded to q's dtype once
    atol = 2e-5 if pool_dtype == jnp.float32 else 2e-2
    np.testing.assert_allclose(
        np.asarray(out[live].astype(jnp.float32)), np.asarray(ref),
        atol=atol)


class TestKernelParity:
    @pytest.mark.parametrize("gqa", [1, 4])
    @pytest.mark.parametrize("page_size", [8, 16, 128])
    def test_parity_sweep_vs_gather_oracle(self, page_size, gqa):
        """ISSUE 8 satellite: parity across page sizes and GQA ratios,
        with cache_position edges in one batch — position 0 (only the
        just-written token visible), last slot of page 0 (exactly
        page-aligned context), first slot of page 1 (one-past-page),
        and the table's final position."""
        from deepspeed_tpu.ops.attention.paged import (
            paged_decode_attention, paged_decode_reference)
        rng = np.random.RandomState(page_size + gqa)
        P = 3
        q, kpool, vpool, tables = _pool_case(rng, kv_heads=2, gqa=gqa,
                                             page_size=page_size,
                                             pages_per_seq=P, batch=5)
        pos = jnp.asarray([0, page_size - 1, page_size, page_size + 1,
                           P * page_size - 1], jnp.int32)
        tables = jnp.asarray(tables)
        out = paged_decode_attention(q, kpool, vpool, tables, pos,
                                     interpret=True, layer=LAYER)
        ref = paged_decode_reference(q, kpool, vpool, tables, pos,
                                     layer=LAYER)
        np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                                   atol=2e-5)

    @pytest.mark.parametrize("block_tokens", BLOCKS_PARITY,
                             ids=["page_a_turn", "block128"])
    @pytest.mark.parametrize("pool_dtype", [jnp.float32, jnp.bfloat16],
                             ids=["float32", "bf16"])
    @pytest.mark.parametrize("heads,kv_heads,hd", [
        (16, 16, 64),       # GPT-2 345M: rows of 1,024 lanes
        (25, 25, 64),       # GPT-2 XL: 1,600, heads not a sublane tile
        (32, 8, 128),       # llama-sized GQA: 8 x 128
        (64, 8, 128),       # Solar-Open2's softmax layer: 8 query groups
    ], ids=["mha16x64", "mha25x64", "gqa32_8x128", "gqa64_8x128"])
    def test_parity_at_served_widths_and_block_edges(self, monkeypatch,
                                                     heads, kv_heads, hd,
                                                     pool_dtype,
                                                     block_tokens):
        """ISSUE 30, ISSUE 38: the kernel streams whole pool rows, a
        block of pages a loop turn, at every head width — at the shipped
        block (8 pages of 16: one MXU tile of keys) and at one page a
        turn. Pages of 16; rows at ``cache_position`` 0, with fewer live
        pages than a block, with exactly a block (its last token and one
        short of it), with one page more, and two blocks and a tail; the
        last row is an inactive slot (all-null table), which must come
        out finite. bf16 pools take the MXU path whose probabilities are
        split, not rounded: the float32 oracle over the same bf16 values
        is met to float32 accuracy."""
        ps = 16
        blk = 128                   # the edges of the widest block tested
        pos = [0, 3 * ps + 5, blk - 2, blk - 1, blk, blk + ps - 1,
               2 * blk + 2 * ps + 7, 0]
        _parity_case(monkeypatch, block_tokens, heads, kv_heads, hd,
                     pool_dtype, ps, pos, dead_rows=(len(pos) - 1,))

    @pytest.mark.parametrize("block_tokens", BLOCKS_PARITY,
                             ids=["page_a_turn", "block128"])
    @pytest.mark.parametrize("ps,pos,table_pages,dead_rows", [
        # a table NARROWER than a block: the engine clamps it to the
        # batch's live-page bucket (4 pages under a block of 8)
        (16, [0, 15, 16, 37, 63], 4, ()),
        # pages of 8: 16 pages a turn
        (8, [0, 7, 8, 126, 127, 128, 135, 300], None, ()),
        # the sequences' blocks are ONE stream through the two slots (a
        # program issues the next walk's first block): live rows and
        # all-null tables alternate, so every walk follows, and is
        # followed by, one of the other kind and of another length
        (16, [0, 200, 0, 5, 0, 127, 0, 128, 0], None, (0, 2, 4, 6, 8)),
        (16, [0, 0, 129, 0], None, (0, 1, 3)),
        # batch 1: the first walk is also the last
        (16, [150], None, ()),
        (16, [0], None, (0,)),
    ], ids=["table_narrower_than_block", "pages_of_8", "alternate_null",
            "null_first_and_last", "batch1", "batch1_null"])
    def test_parity_at_walk_edges(self, monkeypatch, block_tokens, ps, pos,
                                  table_pages, dead_rows):
        """ISSUE 38: what a wide block and the lead from walk to walk
        make matter, at GPT-2 345M's row over a bf16 pool."""
        _parity_case(monkeypatch, block_tokens, 16, 16, 64, jnp.bfloat16,
                     ps, pos, dead_rows=dead_rows, table_pages=table_pages)

    def test_shipped_block_is_a_tested_and_compiled_size(self):
        """The walk's block is one constant; whatever it is moved to is
        a size the parity cases above run at and the described chip's
        compiler has taken (test_tpu_compile.py)."""
        from deepspeed_tpu.ops.attention import paged
        from tests.unit.test_tpu_compile import BLOCKS_COMPILED
        assert paged._BLOCK_TOKENS in BLOCKS_PARITY
        assert paged._BLOCK_TOKENS in BLOCKS_COMPILED
        # whole pages at every page size, at least one
        assert paged.block_pages(8) * 8 == paged._BLOCK_TOKENS
        assert paged.block_pages(16) * 16 == paged._BLOCK_TOKENS
        assert paged.block_pages(2 * paged._BLOCK_TOKENS) == 1

    def test_bf16_probabilities_are_not_rounded(self):
        """Point 4 of ISSUE 30: over a bf16 pool the probabilities reach
        the context product whole (three bf16 terms that sum to the
        float32 value), so a float32 query's context equals the float32
        oracle's over the same bf16 keys and values to float32 accuracy;
        rounding them to bf16 would show at 1e-3."""
        from deepspeed_tpu.ops.attention.paged import (
            _probs_dot, paged_decode_attention, paged_decode_reference)
        rng = np.random.RandomState(5)
        p = jnp.asarray(rng.rand(16, 128), jnp.float32)
        v = jnp.asarray(rng.randn(128, 256), jnp.bfloat16)
        exact = np.asarray(p, np.float64) @ np.asarray(
            v.astype(jnp.float32), np.float64)
        np.testing.assert_allclose(np.asarray(_probs_dot(p, v)), exact,
                                   rtol=0, atol=2e-5)
        rounded = np.asarray(p.astype(jnp.bfloat16).astype(jnp.float32),
                             np.float64) @ np.asarray(
                                 v.astype(jnp.float32), np.float64)
        assert np.abs(rounded - exact).max() > 1e-3

    def test_shared_prefix_pages_two_rows_one_batch(self):
        """Prefix-cache sharing at the kernel level: two rows whose
        tables point at the SAME physical pages (one prefilled prefix,
        two readers in one decode batch) read identical K/V — identical
        queries at identical positions produce identical context."""
        from deepspeed_tpu.ops.attention.paged import (
            paged_decode_attention, paged_decode_reference)
        rng = np.random.RandomState(0)
        q, kpool, vpool, tables = _pool_case(rng, kv_heads=2, gqa=2,
                                             page_size=8, pages_per_seq=3,
                                             batch=3)
        tables = np.asarray(tables)
        tables[1, :2] = tables[0, :2]       # rows 0/1 share 2 prefix pages
        q = q.at[1].set(q[0])
        pos = jnp.asarray([17, 17, 5], jnp.int32)
        tables = jnp.asarray(tables)
        out = paged_decode_attention(q, kpool, vpool, tables, pos,
                                     interpret=True, layer=LAYER)
        ref = paged_decode_reference(q, kpool, vpool, tables, pos,
                                     layer=LAYER)
        np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                                   atol=2e-5)
        # divergence only past the shared pages: rows 0/1 differ (their
        # third page differs) but both match the oracle exactly
        assert not np.allclose(np.asarray(out[0]), np.asarray(out[2]))

    def test_null_table_rows_stay_finite(self):
        """Inactive slots carry all-null tables: everything is masked
        inside the kernel, and the output must be finite garbage (the
        host discards it), never NaN."""
        from deepspeed_tpu.ops.attention.paged import \
            paged_decode_attention
        rng = np.random.RandomState(1)
        q, kpool, vpool, _ = _pool_case(rng, kv_heads=2, gqa=1,
                                        page_size=8, pages_per_seq=2,
                                        batch=2)
        out = paged_decode_attention(
            q, kpool, vpool, jnp.zeros((2, 2), jnp.int32),
            jnp.zeros((2,), jnp.int32), interpret=True, layer=LAYER)
        assert bool(jnp.all(jnp.isfinite(out)))

    def test_reads_only_live_pages(self):
        """The O(live tokens) contract: garbage (NaN) planted in pages
        past each row's live count — including the row's OWN reserved
        but unreached pages — must not leak into the output."""
        from deepspeed_tpu.ops.attention.paged import (
            paged_decode_attention, paged_decode_reference)
        rng = np.random.RandomState(2)
        q, kpool, vpool, tables = _pool_case(rng, kv_heads=2, gqa=2,
                                             page_size=8, pages_per_seq=4,
                                             batch=2)
        pos = jnp.asarray([9, 3], jnp.int32)    # live pages: 2 and 1
        ref = paged_decode_reference(q, kpool, vpool,
                                     jnp.asarray(tables), pos, layer=LAYER)
        kpool_n, vpool_n = np.array(kpool), np.array(vpool)
        kpool_n[:, tables[0, 2:]] = np.nan          # row 0: pages 2,3 dead
        kpool_n[:, tables[1, 1:]] = np.nan          # row 1: pages 1..3 dead
        vpool_n[:, tables[0, 2:]] = np.nan
        vpool_n[:, tables[1, 1:]] = np.nan
        out = paged_decode_attention(q, jnp.asarray(kpool_n),
                                     jnp.asarray(vpool_n),
                                     jnp.asarray(tables), pos,
                                     interpret=True, layer=LAYER)
        assert bool(jnp.all(jnp.isfinite(out)))
        np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                                   atol=2e-5)


# --------------------------------------------------------------------- #
# a block of consecutive pages is ONE copy (ISSUE 54)
# --------------------------------------------------------------------- #
def _lay(tables, layout, block_pages, live_pages, rng):
    """``tables`` (every row's pages consecutive ids: all runs) laid
    another way: ``shuffled`` (descending: no two neighbours in order),
    ``mixed`` (every other block's first two pages swapped),
    ``broken_tail`` (the full blocks as they are, a walk's last block
    swapped) or ``run_tail_only`` (the reverse)."""
    out = tables.copy()
    for b, row in enumerate(out):
        n = int((row != 0).sum())
        last = (live_pages[b] - 1) // block_pages * block_pages
        for at in range(0, n - 1, block_pages):
            swap = {"runs": False, "shuffled": False,
                    "mixed": at // block_pages % 2 == 0,
                    "broken_tail": at == last,
                    "run_tail_only": at != last}[layout]
            if swap:
                row[[at, at + 1]] = row[[at + 1, at]]
        if layout == "shuffled":
            row[:n] = row[:n][::-1]
    return out


class TestRunsOfPages:
    """The kernel reads a block whose live pages are consecutive ids
    with one copy a stream (a walk's last block: the binary pieces of
    its count) and any other block page by page, deciding from the
    table a block at a time. The same tiles land either way: a row's
    output is BIT-EQUAL under every layout of the same rows, for the
    pair arity, the int8 one and the latent one, at blocks of 4 pages
    and of 6 (not a power of two). Rows: one page; an inactive slot's
    null table; the whole table; a walk that ends inside a block;
    exactly one block; part of the first block. Every page past a
    row's live ones is NaN, so a copy that takes more than the live
    pages shows."""

    PS = 4
    # (position, active)
    ROWS = [(0, True), (0, False), (None, True), (None, True),
            (None, True), (None, True)]

    def _case(self, monkeypatch, arity, block_pages):
        from deepspeed_tpu.ops.attention import paged
        ps = self.PS
        monkeypatch.setattr(paged, "_BLOCK_TOKENS", block_pages * ps)
        monkeypatch.setattr(paged, "_LATENT_BLOCK_TOKENS",
                            block_pages * ps)
        assert paged.block_pages(ps) == block_pages == \
            paged.block_pages(ps, latent=True)
        P = 3 * block_pages + 2
        pos = np.asarray([0, 0, P * ps - 1,
                          (2 * block_pages + 2) * ps + 1,
                          block_pages * ps - 1,
                          (block_pages - 1) * ps - 2], np.int32)
        B = len(pos)
        rng = np.random.RandomState(block_pages + len(arity))
        tables = (1 + np.arange(B * P, dtype=np.int32)).reshape(B, P)
        tables[1] = 0                                 # inactive slot
        width = 32
        rows = rng.randn(2, B * P + 1, ps, width).astype(np.float32)
        values = rng.randn(2, B * P + 1, ps, width).astype(np.float32)
        live = pos // ps + 1
        for b in range(B):
            dead = tables[b, live[b]:]
            rows[:, dead[dead != 0]] = np.nan
            values[:, dead[dead != 0]] = np.nan
        rows[1 - LAYER] = values[1 - LAYER] = np.nan
        return pos, live, tables, rows, values, rng

    def _read(self, arity, rows, values, base, tables, pos):
        """The rows of ``base``'s pages moved to ``tables``' pages, and
        the kernel's answer there."""
        from deepspeed_tpu.ops.attention.paged import (
            latent_decode_attention, paged_decode_attention)
        k, v = np.full_like(rows, np.nan), np.full_like(values, np.nan)
        k[:, tables.reshape(-1)] = rows[:, base.reshape(-1)]
        v[:, tables.reshape(-1)] = values[:, base.reshape(-1)]
        k[:, 0] = v[:, 0] = 0.0                       # the null page
        rng = np.random.RandomState(7)
        args = (jnp.asarray(tables), jnp.asarray(pos))
        if arity == "latent":
            q = jnp.asarray(rng.randn(len(pos), 4, k.shape[-1]),
                            jnp.float32)
            pool = jnp.asarray(k)
            return q, (pool,), latent_decode_attention(
                q, pool, *args, 0.2, 24, interpret=True, layer=LAYER)
        q = jnp.asarray(rng.randn(len(pos), 4, 16), jnp.float32)
        if arity == "pair":
            pools = (jnp.asarray(k), jnp.asarray(v))
            return q, pools, paged_decode_attention(
                q, *pools, *args, interpret=True, layer=LAYER)
        kq, vq, ks, vs = _quantize_pools(jnp.nan_to_num(jnp.asarray(k)),
                                         jnp.nan_to_num(jnp.asarray(v)))
        # what no live row holds stays poison where it can: the scales
        ks = jnp.where(jnp.isnan(jnp.asarray(k[..., :1])), jnp.nan, ks)
        vs = jnp.where(jnp.isnan(jnp.asarray(v[..., :1])), jnp.nan, vs)
        return q, (kq, vq, ks, vs), paged_decode_attention(
            q, kq, vq, *args, interpret=True, layer=LAYER,
            k_scales=ks, v_scales=vs)

    @pytest.mark.parametrize("layout", ["runs", "mixed", "broken_tail",
                                        "run_tail_only"])
    @pytest.mark.parametrize("block_pages", [4, 6])
    @pytest.mark.parametrize("arity", ["pair", "int8", "latent"])
    def test_every_layout_of_the_same_rows_reads_the_same_bits(
            self, monkeypatch, arity, block_pages, layout):
        from deepspeed_tpu.inference.paging import run_leads
        from deepspeed_tpu.ops.attention.paged import (
            latent_decode_reference, paged_decode_reference)
        pos, live, base, rows, values, rng = self._case(
            monkeypatch, arity, block_pages)
        blocks = -(-base.shape[1] // block_pages)
        outs = {}
        for name in (layout, "shuffled"):
            tables = _lay(base, name, block_pages, live, rng)
            # the layout is what its name says, by the reader's rule
            runs = [[bool(lead >= n) for lead, n in zip(
                run_leads(tables[b], block_pages, blocks), np.clip(
                    live[b] - np.arange(blocks) * block_pages, 0,
                    block_pages)) if n > 1] for b in range(2, len(pos))]
            flat = [r for row in runs for r in row]
            assert {"runs": all(flat), "shuffled": not any(flat)}.get(
                name, any(flat) and not all(flat)), (name, runs)
            q, pools, out = self._read(arity, rows, values, base, tables,
                                       pos)
            assert bool(jnp.all(jnp.isfinite(out)))
            outs[name] = (np.asarray(out), tables, pools)
        got, tables, pools = outs[layout]
        np.testing.assert_array_equal(got, outs["shuffled"][0])
        active = np.asarray([0, 2, 3, 4, 5])
        clean = [jnp.nan_to_num(p) for p in pools]
        if arity == "latent":
            ref = latent_decode_reference(
                q[active], clean[0], jnp.asarray(tables[active]),
                jnp.asarray(pos[active]), 0.2, 24, layer=LAYER)
        else:
            scales = dict(zip(("k_scales", "v_scales"), clean[2:]))
            ref = paged_decode_reference(
                q[active], clean[0], clean[1], jnp.asarray(tables[active]),
                jnp.asarray(pos[active]), layer=LAYER, **scales)
        np.testing.assert_allclose(got[active], np.asarray(ref), atol=2e-5)


def _quantize_rows(pool, kv_heads, scale_blocks=1):
    """One fp pool (or any array of pool rows, ``kv_heads * hd`` last) as
    int8 rows + per-token-row fp32 scale rows ``kv_heads * scale_blocks``
    wide, via the same per-head quantize_kv the models' paged write path
    uses."""
    from deepspeed_tpu.ops.attention.paged import quantize_kv
    lead = pool.shape[:-1]
    q, s = quantize_kv(pool.reshape(lead + (kv_heads, -1)), scale_blocks)
    return q.reshape(pool.shape), s.reshape(lead + (-1,))


def _quantize_pools(kpool, vpool, scale_blocks=1, kv_heads=2):
    kq, ks = _quantize_rows(kpool, kv_heads, scale_blocks)
    vq, vs = _quantize_rows(vpool, kv_heads, scale_blocks)
    return kq, vq, ks, vs


class TestQuantizedPoolParity:
    """ISSUE 17 satellite: the int8-pool kernel arity (per-token-row
    fp32 scales DMA'd alongside the payload, dequant in VMEM) against
    TWO oracles — the dequantized-pool gather reference (must be tight:
    same math, different data path) and the original fp pool (pinned
    quantization-error budget; the values-level analogue of the e2e
    logit budget)."""

    # int8 round-trip error at absmax scaling is ~absmax/254 per value;
    # on randn pools the attention-output error stays well inside this
    QUANT_ATOL = 0.05

    @pytest.mark.parametrize("scale_blocks", [1, 4])
    @pytest.mark.parametrize("gqa", [1, 4])
    @pytest.mark.parametrize("page_size", [8, 16, 128])
    def test_int8_parity_sweep(self, page_size, gqa, scale_blocks):
        from deepspeed_tpu.ops.attention.paged import (
            paged_decode_attention, paged_decode_reference)
        rng = np.random.RandomState(100 + page_size + gqa)
        P = 3
        q, kpool, vpool, tables = _pool_case(rng, kv_heads=2, gqa=gqa,
                                             page_size=page_size,
                                             pages_per_seq=P, batch=5)
        pos = jnp.asarray([0, page_size - 1, page_size, page_size + 1,
                           P * page_size - 1], jnp.int32)
        tables = jnp.asarray(tables)
        kq, vq, ks, vs = _quantize_pools(kpool, vpool, scale_blocks)
        out = paged_decode_attention(q, kq, vq, tables, pos,
                                     interpret=True, layer=LAYER,
                                     k_scales=ks, v_scales=vs)
        # oracle 1: gather reference over the SAME int8 pool — pins the
        # kernel's in-VMEM dequant against the host-side dequant math
        ref_q = paged_decode_reference(q, kq, vq, tables, pos,
                                       k_scales=ks, v_scales=vs,
                                       layer=LAYER)
        np.testing.assert_allclose(np.asarray(out), np.asarray(ref_q),
                                   atol=2e-5)
        # oracle 2: the original fp pool — the quantization-error budget
        ref_fp = paged_decode_reference(q, kpool, vpool, tables, pos,
                                        layer=LAYER)
        np.testing.assert_allclose(np.asarray(out), np.asarray(ref_fp),
                                   atol=self.QUANT_ATOL)

    def test_nan_poisoned_dead_page_scales_stay_masked(self):
        """The O(live tokens) contract for the quantized arity: int8
        payload can't hold NaN, so dead pages are poisoned through
        their fp32 SCALES — NaN scales on pages past each row's live
        count (including the row's own reserved-but-unreached pages)
        must not leak into the output."""
        from deepspeed_tpu.ops.attention.paged import (
            paged_decode_attention, paged_decode_reference)
        rng = np.random.RandomState(102)
        q, kpool, vpool, tables = _pool_case(rng, kv_heads=2, gqa=2,
                                             page_size=8, pages_per_seq=4,
                                             batch=2)
        pos = jnp.asarray([9, 3], jnp.int32)    # live pages: 2 and 1
        kq, vq, ks, vs = _quantize_pools(kpool, vpool)
        ref = paged_decode_reference(q, kq, vq, jnp.asarray(tables),
                                     pos, k_scales=ks, v_scales=vs,
                                     layer=LAYER)
        ks_n, vs_n = np.array(ks), np.array(vs)
        ks_n[:, tables[0, 2:]] = np.nan             # row 0: pages 2,3 dead
        ks_n[:, tables[1, 1:]] = np.nan             # row 1: pages 1..3 dead
        vs_n[:, tables[0, 2:]] = np.nan
        vs_n[:, tables[1, 1:]] = np.nan
        out = paged_decode_attention(q, kq, vq, jnp.asarray(tables),
                                     pos, interpret=True, layer=LAYER,
                                     k_scales=jnp.asarray(ks_n),
                                     v_scales=jnp.asarray(vs_n))
        assert bool(jnp.all(jnp.isfinite(out)))
        np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                                   atol=2e-5)

    def test_shared_prefix_pages_share_scales(self):
        """Prefix sharing on the quantized pool: two rows whose tables
        point at the same physical pages read the same payload AND the
        same scales — identical queries at identical positions produce
        identical context, and both match the oracle."""
        from deepspeed_tpu.ops.attention.paged import (
            paged_decode_attention, paged_decode_reference)
        rng = np.random.RandomState(103)
        q, kpool, vpool, tables = _pool_case(rng, kv_heads=2, gqa=2,
                                             page_size=8, pages_per_seq=3,
                                             batch=3)
        tables = np.asarray(tables)
        tables[1, :2] = tables[0, :2]       # rows 0/1 share 2 prefix pages
        q = q.at[1].set(q[0])
        # both readers inside the shared prefix (live pages = 2): the
        # full context — payload AND scales — is physically shared
        pos = jnp.asarray([15, 15, 5], jnp.int32)
        tables = jnp.asarray(tables)
        kq, vq, ks, vs = _quantize_pools(kpool, vpool)
        out = paged_decode_attention(q, kq, vq, tables, pos,
                                     interpret=True, layer=LAYER,
                                     k_scales=ks, v_scales=vs)
        ref = paged_decode_reference(q, kq, vq, tables, pos,
                                     k_scales=ks, v_scales=vs, layer=LAYER)
        np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                                   atol=2e-5)
        np.testing.assert_array_equal(np.asarray(out[0]),
                                      np.asarray(out[1]))
        assert not np.allclose(np.asarray(out[0]), np.asarray(out[2]))

    def test_quantize_kv_roundtrip_and_bytes(self):
        """quantize_kv/dequantize_pool round-trip error is bounded by
        absmax/254 per value, and at the serving head_dim (128) the
        int8 pool + scales beat the equivalent bf16 pool by >= 1.8x
        (the quant_serving_bytes KV lever)."""
        from deepspeed_tpu.ops.attention.paged import (
            dequantize_pool, quantize_kv)
        rng = np.random.RandomState(104)
        x = jnp.asarray(rng.randn(6, 2, 8, 16), jnp.float32)
        for nb in (1, 4):
            qv, s = quantize_kv(x, nb)
            assert qv.dtype == jnp.int8 and s.dtype == jnp.float32
            assert s.shape == x.shape[:-1] + (nb,)
            back = dequantize_pool(qv, s)
            blk = x.shape[-1] // nb
            bound = np.repeat(np.asarray(
                jnp.max(jnp.abs(x.reshape(x.shape[:-1] + (nb, blk))),
                        axis=-1)), blk, -1) / 254.0 + 1e-7
            assert bool(jnp.all(jnp.abs(back - x) <= bound))
        xs = jnp.asarray(rng.randn(4, 2, 8, 128), jnp.float32)
        qv, s = quantize_kv(xs, 1)
        int8_bytes = qv.size + 4 * s.size
        bf16_bytes = 2 * xs.size
        assert bf16_bytes / int8_bytes >= 1.8


class TestSupportPredicate:
    def test_interpret_path_always_supported(self):
        from deepspeed_tpu.ops.attention.paged import \
            paged_decode_supported
        ok, why = paged_decode_supported(4, 8, jnp.float32,
                                         backend="cpu")
        assert ok and "interpret" in why

    def test_tpu_legality_matrix(self):
        """Compiled-TPU DMA legality: the tile is a page of whole pool
        rows, so the ROW (kv_heads * head_dim) must be whole 128-lane
        tiles and the page whole 8-row sublane tiles; the head width
        alone decides nothing (tests/unit/test_tpu_compile.py holds the
        same cases to the compiler)."""
        from deepspeed_tpu.ops.attention.paged import \
            paged_decode_supported

        def gate(page_size, head_dim, dtype, kv_heads):
            return paged_decode_supported(page_size, head_dim, dtype,
                                          backend="tpu", kv_heads=kv_heads)
        assert gate(16, 64, jnp.bfloat16, 16)[0]        # GPT-2 345M
        assert gate(16, 128, jnp.bfloat16, 8)[0]        # llama GQA
        assert gate(8, 128, jnp.float32, 16)[0]
        assert gate(8, 128, jnp.bfloat16, 16)[0]
        assert gate(16, 128, jnp.bfloat16, 1)[0]
        ok, why = gate(16, 64, jnp.bfloat16, 25)        # GPT-2 XL: 1,600
        assert not ok and "1600" in why
        ok, why = gate(16, 64, jnp.bfloat16, 1)
        assert not ok and "128-lane" in why
        ok, why = gate(4, 128, jnp.bfloat16, 16)
        assert not ok and "page_size" in why
        ok, why = gate(32, 128, jnp.int8, 16)
        assert not ok and "scale rows" in why

    def test_live_pages_and_bytes_model(self):
        from deepspeed_tpu.ops.attention.paged import (decode_read_bytes,
                                                       live_pages)
        assert live_pages(0, 16) == 1
        assert live_pages(15, 16) == 1
        assert live_pages(16, 16) == 2
        pallas, gather = decode_read_bytes(
            [0, 15, 16], page_size=16, pages_per_seq=8, kv_heads=2,
            head_dim=64, dtype_bytes=2)
        per_page = 16 * 2 * 64 * 2 * 2                  # K and V
        assert pallas == (1 + 1 + 2) * per_page
        assert gather == 3 * 8 * per_page
        assert gather / pallas > 2.0


# --------------------------------------------------------------------- #
# engine integration: the pallas path is the DEFAULT paged decode
# --------------------------------------------------------------------- #
PAGED_PALLAS = {"page_size": 4, "num_pages": 14, "attn_kernel": "pallas"}
PAGED_GATHER = {"page_size": 4, "num_pages": 14, "attn_kernel": "gather"}


class TestEngineParity:
    @pytest.mark.parametrize("family", ["gpt2", "llama", "gpt2_head64"])
    def test_pallas_greedy_exactly_matches_gather(self, family):
        """ISSUE 8 acceptance: greedy outputs from the pallas decode
        path exactly match the gather path for both families under
        continuous batching with prefix reuse (shared system prompt),
        mixed lengths, tiny pool — and at GPT-2's head width of 64
        (ISSUE 30: the width the kernel used to refuse on the chip)."""
        from deepspeed_tpu.inference import InferenceEngine
        if family == "gpt2_head64":
            from deepspeed_tpu.models.gpt2 import (GPT2Config,
                                                   init_gpt2_params)
            cfg = GPT2Config(vocab_size=61, max_position_embeddings=32,
                             hidden_size=128, num_layers=2, num_heads=2,
                             embd_dropout=0.0, attn_dropout=0.0,
                             resid_dropout=0.0)
            params = init_gpt2_params(cfg, jax.random.PRNGKey(3))
        else:
            cfg, params = tiny_gpt2() if family == "gpt2" else tiny_llama()
        rng = np.random.RandomState(8)
        sys_prompt = rng.randint(1, 61, (4,)).tolist()   # one full page
        # the sys-prompt pair goes first so both are in flight together
        # (prefix pages are shared while the owner still holds them)
        prompts = [sys_prompt + [int(t)]
                   for t in rng.randint(1, 61, (2,))]    # prefix reuse
        prompts += [rng.randint(1, 61, (n,)).tolist()
                    for n in (3, 5, 7, 2, 8)]
        pallas = InferenceEngine(cfg, params,
                                 dict(TINY_INF, paged_kv=PAGED_PALLAS),
                                 dtype=jnp.float32)
        assert pallas._decode_attn_path == "pallas"
        gather = InferenceEngine(cfg, params,
                                 dict(TINY_INF, paged_kv=PAGED_GATHER),
                                 dtype=jnp.float32)
        assert gather._decode_attn_path == "gather"
        got = pallas.generate(prompts, max_new_tokens=4, temperature=0.0)
        ref = gather.generate(prompts, max_new_tokens=4, temperature=0.0)
        assert got == ref
        assert pallas.scheduler.allocator.prefix_hit_tokens >= 4

    def test_default_config_routes_decode_through_pallas(self):
        """attn_kernel defaults to "pallas": an engine built from the
        stock paged config resolves the kernel path (interpret mode on
        CPU) — the O(live tokens) path is the default, not opt-in."""
        from deepspeed_tpu.inference import InferenceEngine
        cfg, params = tiny_gpt2()
        engine = InferenceEngine(cfg, params, TINY_INF,
                                 dtype=jnp.float32)
        assert engine.config["paged_kv"]["attn_kernel"] == "pallas"
        assert engine._decode_attn_path == "pallas"

    def test_warmup_programs_and_zero_recompiles_unchanged(self):
        """ISSUE 8 acceptance: the pallas default preserves PR 5/7's
        program-set invariant — warmup compiles exactly
        len(batch_buckets) x len(prompt_buckets) prefills + 1 decode,
        and churn stays at 0 steady-state recompiles."""
        from deepspeed_tpu.inference import InferenceEngine
        cfg, params = tiny_gpt2()
        engine = InferenceEngine(cfg, params,
                                 dict(TINY_INF, paged_kv=PAGED_PALLAS),
                                 dtype=jnp.float32)
        programs = engine.warmup()
        # (the merge of first tokens into the device's: a batch bucket)
        assert programs == 2 * 2 + 1 + 2
        assert engine.compile_tracker.counts == {"prefill": 4,
                                                 "decode": 1,
                                                 "merge_tokens": 2}
        rng = np.random.RandomState(5)
        churn = [rng.randint(1, 61, (n,)).tolist()
                 for n in (1, 4, 5, 8, 3, 6)]
        engine.generate(churn, max_new_tokens=3)
        engine.generate(churn[:2], max_new_tokens=5, temperature=0.5)
        assert engine.steady_state_recompiles == 0
        assert engine.compile_tracker.total_compiles == programs

    def test_mesh_serving_keeps_pallas_via_shard_map(self):
        """ISSUE 11 acceptance: with ``inference.mesh`` set and legal
        geometry, the decode path stays on the Pallas kernel — wrapped
        in shard_map over the model axis (parallel/pallas_shard) — and
        the compiled sharded decode program is GATHER-FREE, pinned by
        hlo_audit.gather_ops. No silent gather fallback at pod scale."""
        from deepspeed_tpu.inference import InferenceEngine
        from deepspeed_tpu.utils.hlo_audit import max_gather_elems
        cfg, params = tiny_gpt2()
        engine = InferenceEngine(
            cfg, params, dict(TINY_INF, mesh={"axes": {"model": 2}}),
            dtype=jnp.float32)
        assert engine._decode_attn_path == "pallas"
        assert "shard_map" in engine._decode_attn_reason
        # greedy parity: sharded pallas == unsharded pallas == gather
        rng = np.random.RandomState(11)
        prompts = [rng.randint(1, 61, (n,)).tolist() for n in (3, 6, 2)]
        got = engine.generate(prompts, max_new_tokens=4, temperature=0.0)
        ref_eng = InferenceEngine(cfg, params,
                                  dict(TINY_INF, paged_kv=PAGED_GATHER),
                                  dtype=jnp.float32)
        assert got == ref_eng.generate(prompts, max_new_tokens=4,
                                       temperature=0.0)
        # the compiled sharded decode program contains no stripe gather
        spec = engine.paged_spec
        rows = engine.num_slots + 1
        stripe_elems = (rows * spec.pages_per_seq * spec.kv_heads
                        * spec.page_size * spec.head_dim)
        hlo = engine._decode.lower(
            engine.params, engine._cache,
            jnp.zeros((rows,), jnp.int32), jnp.zeros((rows,), jnp.int32),
            jnp.zeros((rows, spec.pages_per_seq), jnp.int32),
            jnp.zeros((rows, 2), jnp.uint32),
            jnp.zeros((rows,), jnp.float32)).compile().as_text()
        assert max_gather_elems(hlo) < stripe_elems

    def test_mesh_illegal_geometry_rejected_at_init(self):
        """A model axis that does not divide the head counts cannot put
        whole GQA groups on a shard. The engine rejects it at
        CONSTRUCTION (the PR 7 cache-sharding rule), so the shard_map
        decode wrap never sees an indivisible geometry — pinned here
        along with the predicate it relies on."""
        from deepspeed_tpu.inference import InferenceEngine
        from deepspeed_tpu.parallel.pallas_shard import \
            head_shard_supported
        assert head_shard_supported(2, 4, 4)
        assert not head_shard_supported(3, 4, 4)
        cfg, params = tiny_gpt2()                     # 4 heads
        with pytest.raises(ValueError, match="must divide"):
            InferenceEngine(
                cfg, params, dict(TINY_INF, mesh={"axes": {"model": 3}}),
                dtype=jnp.float32)


def _plain_stripe_attention(q, kc, vc, cache_position):
    """The gather path's stripe math as it was before ISSUE 25: every
    operand upcast to float32, whatever the query's rows."""
    from deepspeed_tpu.ops.attention.flash import NEG_INF
    from deepspeed_tpu.ops.attention.page_pool import causal_cache_mask
    scores = jnp.einsum("bhqd,bhld->bhql", q.astype(jnp.float32),
                        kc.astype(jnp.float32)) / np.sqrt(q.shape[-1])
    mask = causal_cache_mask(cache_position, q.shape[2], kc.shape[2])
    probs = jax.nn.softmax(jnp.where(mask, scores, NEG_INF), axis=-1)
    return jnp.einsum("bhql,bhld->bhqd", probs,
                      vc.astype(jnp.float32)).astype(q.dtype)


class TestGatherSeq1Path:
    """ISSUE 25: a one-row query over a stripe narrower than float32 is
    padded to the sublane tile and contracts the stripe in the dtype it
    arrives in (``_stripe_attention``); float32 stripes — a float32
    pool, an int8 pool once dequantized — keep the float32 operands."""

    # what each pool's path may differ from the float32 oracle by: one
    # rounding of the context to bf16 (outputs stay under 4, where a
    # bf16 step is 2^-6) and the order of float32 sums; float32 stripes
    # only the latter
    TOLERANCE = {"bf16": 2.0 ** -6, "float32": 2e-5, "int8": 2e-5}

    @pytest.mark.parametrize("pool", ["bf16", "float32", "int8"])
    def test_write_gather_attend_matches_the_oracle(self, pool):
        """Ragged positions, a one-token cache, a table mapped only as
        far as it is used, the last position of the table, and an
        inactive slot whose table is all null page."""
        from deepspeed_tpu.models.gpt2 import _paged_cache_attention
        from deepspeed_tpu.ops.attention.page_pool import paged_write_index
        from deepspeed_tpu.ops.attention.paged import \
            paged_decode_reference
        rng = np.random.RandomState(25)
        heads, hd, ps, pages = 4, 16, 4, 5
        positions = np.asarray([0, 0, 6, 19, 7, 12], np.int32)
        batch = len(positions)
        _, kpool, vpool, tables = _pool_case(rng, heads, 1, ps, pages,
                                             hd=hd, batch=batch)
        tables[1] = 0                     # the inactive slot
        tables[4, 2:] = 0                 # mapped as far as position 7
        dtype = jnp.bfloat16 if pool == "bf16" else jnp.float32
        q, k, v = (jnp.asarray(rng.randn(batch, heads, 1, hd), dtype)
                   for _ in range(3))
        if pool == "int8":
            pools = _quantize_pools(kpool, vpool, kv_heads=heads)
        else:
            pools = (kpool.astype(dtype), vpool.astype(dtype))
        tables, positions = jnp.asarray(tables), jnp.asarray(positions)
        index = paged_write_index(tables, positions, 1, ps)
        box = []
        got = _paged_cache_attention(
            pools, LAYER, tables, positions, index, box,
            attn_kernel="gather")(q, k, v, 0.0, None)
        assert got.shape == (batch, heads, 1, hd) and got.dtype == dtype
        kp, vp, *written_scales = box[0]
        ref = paged_decode_reference(
            q[:, :, 0].astype(jnp.float32), kp, vp, tables, positions,
            layer=LAYER,
            **dict(zip(("k_scales", "v_scales"), written_scales)))
        np.testing.assert_allclose(
            np.asarray(got[:, :, 0].astype(jnp.float32)), np.asarray(ref),
            rtol=0, atol=self.TOLERANCE[pool])

    @pytest.mark.parametrize("cache", ["paged", "contiguous"])
    def test_bf16_greedy_tokens_equal_the_float32_formulation(
            self, cache, monkeypatch):
        """A short bf16 generation through the gather path (and through
        the contiguous cache, the same stripe math) picks the tokens the
        parent's all-float32 formulation picks."""
        from deepspeed_tpu.inference import InferenceEngine
        from deepspeed_tpu.models import gpt2
        cfg, params = tiny_gpt2()
        rng = np.random.RandomState(25)
        prompts = [rng.randint(1, 61, (n,)).tolist()
                   for n in (3, 5, 7, 2, 8, 4)]
        inf = dict(TINY_INF, paged_kv=(PAGED_GATHER if cache == "paged"
                                       else {"enabled": False}))

        def generate():
            engine = InferenceEngine(cfg, params, inf, dtype=jnp.bfloat16)
            assert (engine.paged_spec is not None) == (cache == "paged")
            return engine.generate(prompts, max_new_tokens=6,
                                   temperature=0.0)

        got = generate()
        monkeypatch.setattr(gpt2, "_stripe_attention",
                            _plain_stripe_attention)
        assert got == generate()


def _np_write(pool, layer, new, tables, positions):
    """The paged write, plainly: token j of row b of ``new``
    (B, heads, S, w) becomes the pool row ``[layer, page, offset]``, its
    heads side by side; a position past the table lands in null page 0.
    ``pool`` is (layers, num_pages, page_size, heads * w)."""
    pool = np.array(pool)
    ps = pool.shape[2]
    for b, row in enumerate(np.asarray(new)):
        for j in range(row.shape[1]):
            pos = int(positions[b]) + j
            slot = pos // ps
            page = tables[b, slot] if slot < tables.shape[1] else 0
            pool[layer, page, pos % ps] = row[:, j].reshape(-1)
    return pool


def _np_gather(pool, layer, tables, kv_heads):
    """The paged gather, plainly: (B, kv_heads, P * page_size, w) with
    position ``t * page_size + o`` of row b read from the pool row
    ``[layer, tables[b, t], o]``."""
    pool = np.asarray(pool)
    ps, width = pool.shape[2:]
    out = np.zeros((tables.shape[0], kv_heads, tables.shape[1] * ps,
                    width // kv_heads), pool.dtype)
    for b, row in enumerate(tables):
        for t, page in enumerate(row):
            for o in range(ps):
                out[b, :, t * ps + o] = pool[layer, page, o].reshape(
                    kv_heads, -1)
    return out


class TestPoolRoundTrip:
    """ISSUE 28: the pool's row layout
    ``(layers, num_pages, page_size, kv_heads * head_dim)`` and its
    in-place write at ``[layer, page, offset]``, against plain numpy."""

    HEADS, HD, PS, PAGES = 4, 8, 4, 3       # a table of 12 positions

    def _case(self, seed, positions, tokens):
        """Two-layer K/V pools of noise, a table a row of distinct
        non-null pages, and new K/V of ``tokens`` tokens a row."""
        rng = np.random.RandomState(seed)
        batch = len(positions)
        _, kpool, vpool, tables = _pool_case(rng, self.HEADS, 1, self.PS,
                                             self.PAGES, hd=self.HD,
                                             batch=batch)
        kpool, vpool = (jnp.asarray(rng.randn(*pool.shape), jnp.float32)
                        for pool in (kpool, vpool))
        k, v = (jnp.asarray(rng.randn(batch, self.HEADS, tokens, self.HD),
                            jnp.float32) for _ in range(2))
        return kpool, vpool, tables, np.asarray(positions, np.int32), k, v

    @pytest.mark.parametrize("positions,tokens", [
        ([0, 3, 4, 7, 11], 1),      # decode: both sides of each page edge
        ([0, 2, 3, 5], 6),          # prefill: rows that cross one or two
        ([0, 0, 0], 12),            # prefill: the whole table
    ], ids=["ragged_decode", "ragged_prefill", "full_table"])
    def test_write_then_gather_match_numpy(self, positions, tokens):
        """Every written row is where the plain write puts it, every
        other row of the pool — the other layer's too — is bit for bit
        what it was, and the gather returns the plain gather's
        stripe."""
        from deepspeed_tpu.ops.attention.page_pool import (
            gather_paged_kv, paged_write_index, write_paged_kv_cache)
        kpool, _, tables, positions, k, _ = self._case(28, positions,
                                                       tokens)
        index = paged_write_index(jnp.asarray(tables),
                                  jnp.asarray(positions), tokens, self.PS)
        got = write_paged_kv_cache(kpool, LAYER, k, index)
        want = _np_write(kpool, LAYER, k, tables, positions)
        assert got.shape == kpool.shape and got.dtype == kpool.dtype
        np.testing.assert_array_equal(np.asarray(got), want)
        np.testing.assert_array_equal(
            np.asarray(gather_paged_kv(got, LAYER, jnp.asarray(tables),
                                       self.HEADS)),
            _np_gather(want, LAYER, tables, self.HEADS))

    def test_write_past_the_table_lands_in_the_null_page(self):
        """A decode position at the table's extent, a prefill that runs
        over it, and a row whose table is unreserved (all 0): what has
        no page goes to null page 0 and no live page is touched."""
        from deepspeed_tpu.ops.attention.page_pool import (
            paged_write_index, write_paged_kv_cache)
        extent = self.PS * self.PAGES
        kpool, _, tables, positions, k, _ = self._case(
            29, [extent - 2, extent, 1], 3)
        tables[2] = 0
        index = paged_write_index(jnp.asarray(tables),
                                  jnp.asarray(positions), 3, self.PS)
        np.testing.assert_array_equal(
            np.asarray(index.page).reshape(3, 3),
            [[tables[0, -1], tables[0, -1], 0], [0, 0, 0], [0, 0, 0]])
        got = np.asarray(write_paged_kv_cache(kpool, LAYER, k, index))
        want = _np_write(kpool, LAYER, k, tables, positions)
        # several rows land on one null-page row: which of them stays
        # is not defined, and nothing reads it unmasked
        np.testing.assert_array_equal(got[:, 1:], want[:, 1:])
        np.testing.assert_array_equal(got[1 - LAYER], np.asarray(kpool)[0])

    @pytest.mark.parametrize("scale_blocks", [1, 2])
    def test_int8_tuple_write_then_gather(self, scale_blocks):
        """The int8 4-tuple: payload rows and scale rows
        (``kv_heads * scale_blocks`` wide) land through the same index,
        and the gathered stripe is the dequantized plain gather."""
        from deepspeed_tpu.ops.attention.page_pool import (
            gather_paged_layer, paged_write_index, write_paged_layer)
        from deepspeed_tpu.ops.attention.paged import (dequantize_pool,
                                                       quantize_kv)
        kpool, vpool, tables, positions, k, v = self._case(
            30, [0, 3, 6, 2], 5)
        pools = _quantize_pools(kpool, vpool, scale_blocks,
                                kv_heads=self.HEADS)
        assert pools[2].shape == kpool.shape[:3] + (
            self.HEADS * scale_blocks,)
        index = paged_write_index(jnp.asarray(tables),
                                  jnp.asarray(positions), 5, self.PS)
        got = write_paged_layer(pools, LAYER, k, v, index)
        assert [g.dtype for g in got] == [jnp.int8, jnp.int8,
                                          jnp.float32, jnp.float32]
        new = quantize_kv(k, scale_blocks) + quantize_kv(v, scale_blocks)
        want = [_np_write(pool, LAYER, x, tables, positions)
                for pool, x in zip(pools, (new[0], new[2], new[1],
                                           new[3]))]
        for g, w in zip(got, want):
            np.testing.assert_array_equal(np.asarray(g), w)
        kc, vc = gather_paged_layer(got, LAYER, jnp.asarray(tables),
                                    self.HEADS)
        for stripe, payload, scales in ((kc, want[0], want[2]),
                                        (vc, want[1], want[3])):
            np.testing.assert_array_equal(
                np.asarray(stripe),
                np.asarray(dequantize_pool(
                    _np_gather(payload, LAYER, tables, self.HEADS),
                    _np_gather(scales, LAYER, tables, self.HEADS))))

    @pytest.mark.parametrize("kv_dtype", ["bf16", "int8"])
    def test_export_then_import_returns_the_same_rows(self, kv_dtype):
        """Pages exported from one pool (the page axis is dimension 1 of
        every leaf, whatever a row holds) and imported into another at
        other page numbers gather back, layer by layer, as the rows that
        left."""
        from deepspeed_tpu.inference import InferenceEngine
        from deepspeed_tpu.inference.kv_cache import (init_paged_kv_cache,
                                                      paged_spec_for)
        from deepspeed_tpu.ops.attention.page_pool import gather_paged_kv
        cfg, _ = tiny_gpt2()
        dtype = jnp.int8 if kv_dtype == "int8" else jnp.bfloat16
        spec = paged_spec_for(cfg, 9, 4, 16, dtype=dtype)
        assert spec.shape == (cfg.num_layers, 9, 4,
                              spec.kv_heads * spec.head_dim)
        rng = np.random.RandomState(31)
        source = tuple(
            jnp.asarray(rng.randint(-100, 100, leaf.shape), leaf.dtype)
            for leaf in init_paged_kv_cache(spec))
        src_pages, dst_pages = np.asarray([5, 2, 7]), np.asarray([1, 8, 3])
        slab = InferenceEngine._export_pages_impl(None, source,
                                                  jnp.asarray(src_pages))
        assert all(s.shape == (leaf.shape[0], 3) + leaf.shape[2:]
                   for s, leaf in zip(slab, source))
        dest = InferenceEngine._import_pages_impl(
            None, init_paged_kv_cache(spec), slab, jnp.asarray(dst_pages))
        for layer in range(spec.num_layers):
            for src, dst in zip(source, dest):
                np.testing.assert_array_equal(
                    np.asarray(gather_paged_kv(
                        dst, layer, jnp.asarray(dst_pages[None]),
                        spec.kv_heads)),
                    np.asarray(gather_paged_kv(
                        src, layer, jnp.asarray(src_pages[None]),
                        spec.kv_heads)))
        untouched = np.setdiff1d(np.arange(spec.num_pages), dst_pages)
        assert not np.asarray(dest[0])[:, untouched].any()


class TestDecodeWidthBuckets:
    """ISSUE 8 satellite: the gather fallback's decode reads are
    bounded by the batch's LIVE page bucket, not pages_per_seq."""

    def test_width_bucketed_warmup_and_zero_recompiles(self):
        """decode_page_buckets=[2] compiles one decode program per
        width (2 and full) at warmup; mixed-length churn crossing the
        bucket boundary compiles nothing more."""
        from deepspeed_tpu.inference import InferenceEngine
        cfg, params = tiny_gpt2()
        engine = InferenceEngine(
            cfg, params,
            dict(TINY_INF, paged_kv=dict(PAGED_GATHER,
                                         decode_page_buckets=[2])),
            dtype=jnp.float32)
        assert engine._decode_page_buckets == (2, 8)
        programs = engine.warmup()
        # (the merge of first tokens into the device's: a batch bucket)
        assert programs == 2 * 2 + 2 + 2
        assert engine.compile_tracker.counts == {"prefill": 4,
                                                 "decode": 2,
                                                 "merge_tokens": 2}
        rng = np.random.RandomState(6)
        # short requests decode at width 2; the 8-token prompts cross
        # into the full-width program
        prompts = [rng.randint(1, 61, (n,)).tolist()
                   for n in (2, 3, 8, 7, 1, 8)]
        outs = engine.generate(prompts, max_new_tokens=4)
        assert engine.steady_state_recompiles == 0
        assert engine.compile_tracker.total_compiles == programs
        # numerics: identical to the single-width engine
        ref = InferenceEngine(cfg, params,
                              dict(TINY_INF, paged_kv=PAGED_GATHER),
                              dtype=jnp.float32).generate(
                                  prompts, max_new_tokens=4)
        assert outs == ref

    def test_scheduler_max_live_pages_and_table_clamp(self):
        from deepspeed_tpu.inference.kv_cache import PageAllocator
        from deepspeed_tpu.inference.scheduler import Request, Scheduler
        s = Scheduler(3, (4, 16), (1, 2), 32,
                      allocator=PageAllocator(20, 4))
        assert s.max_live_pages() == 1          # idle: null column only
        s.submit(Request(prompt=[1] * 9, max_new_tokens=4))   # pos 9
        s.submit(Request(prompt=[2, 3], max_new_tokens=4))    # pos 2
        s.admit()
        # positions 9 and 2 -> 9//4+1 = 3 live pages max
        assert s.max_live_pages() == 3
        full = s.block_table_rows(4, 4)
        clamped = s.block_table_rows(4, 3)
        np.testing.assert_array_equal(clamped, full[:, :3])

    def test_table_rows_follow_a_slots_pages(self):
        """The table's rows are copied from arrays kept a reservation,
        not rebuilt from the lists a step: a slot re-homed onto other
        pages, and a slot freed and taken by the next request, show
        THEIR pages the step after."""
        from deepspeed_tpu.inference.kv_cache import PageAllocator
        from deepspeed_tpu.inference.scheduler import Request, Scheduler
        s = Scheduler(2, (4, 16), (1, 2), 32,
                      allocator=PageAllocator(20, 4))
        s.submit(Request(prompt=[1] * 5, max_new_tokens=1))
        s.submit(Request(prompt=[2] * 3, max_new_tokens=6))
        s.admit()
        s.record_tokens({0: 7, 1: 8})     # first tokens: slot 0 is done
        for _ in range(2):                # the second reads the kept rows
            table = s.block_table_rows(3, 4)
            assert table[0].tolist() == [0] * 4
            assert table[1].tolist() == (s.slots[1].pages + [0] * 4)[:4]
        fresh = s.allocator.alloc(len(s.slots[1].pages))
        s.adopt_pages(1, fresh)
        assert s.block_table_rows(3, 4)[1].tolist() == (fresh + [0] * 4)[:4]
        s.submit(Request(prompt=[3] * 9, max_new_tokens=4))
        s.admit()
        s.record_tokens({0: 9})
        assert s.block_table_rows(3, 4)[0].tolist() == \
            (s.slots[0].pages + [0] * 4)[:4]
        assert s.slots[0].pages[0] not in (0, fresh[0])


class TestDecodeAttnTelemetry:
    def test_path_lands_in_events_and_report(self, tmp_path):
        """Serve/decode_attn_path scalar + the decode_attn_path event
        row (with the WHY) land in events.jsonl; obs_report renders the
        path — a silent fallback to gather is visible in run
        reports."""
        from deepspeed_tpu.inference import InferenceEngine
        cfg, params = tiny_gpt2()
        icfg = dict(TINY_INF, events_dir=str(tmp_path),
                    paged_kv=PAGED_PALLAS)
        engine = InferenceEngine(cfg, params, icfg, dtype=jnp.float32)
        engine.generate([[1, 2, 3], [4, 5]], max_new_tokens=3)
        engine.close()
        rows = [json.loads(line)
                for line in open(tmp_path / "events.jsonl")]
        vals = [r["value"] for r in rows
                if r.get("tag") == "Serve/decode_attn_path"]
        assert vals and all(v == 1.0 for v in vals)
        ev = next(r for r in rows
                  if r.get("event") == "decode_attn_path")
        assert ev["path"] == "pallas" and ev["requested"] == "pallas"
        assert ev["reason"]
        obs_report = _load_tool("obs_report")
        s = obs_report.summarize(str(tmp_path))
        assert s["serving"]["paged_kv"]["decode_attn_path"] == "pallas"
        assert "decode_attn     : pallas" in obs_report.render(s)

    def test_gather_fallback_flagged_in_report(self, tmp_path):
        from deepspeed_tpu.inference import InferenceEngine
        cfg, params = tiny_gpt2()
        icfg = dict(TINY_INF, events_dir=str(tmp_path),
                    paged_kv=PAGED_GATHER)
        engine = InferenceEngine(cfg, params, icfg, dtype=jnp.float32)
        engine.generate([[1, 2, 3]], max_new_tokens=2)
        engine.close()
        obs_report = _load_tool("obs_report")
        s = obs_report.summarize(str(tmp_path))
        assert s["serving"]["paged_kv"]["decode_attn_path"] == "gather"
        assert "fallback" in obs_report.render(s)

    def test_tag_registry_in_sync(self):
        from deepspeed_tpu import profiling as prof
        from deepspeed_tpu.utils import monitor as m
        obs_report = _load_tool("obs_report")
        assert m.TAG_SERVE_DECODE_ATTN == prof.TAG_SERVE_DECODE_ATTN == \
            obs_report.T_DECODE_ATTN


class TestCompiledProgramAudit:
    def test_pallas_decode_program_free_of_stripe_gathers(self):
        """ISSUE 8 acceptance: the compiled pallas decode program
        contains no gather anywhere near the per-layer stripe size; the
        gather program materializes it."""
        from deepspeed_tpu.inference import InferenceEngine
        from deepspeed_tpu.utils.hlo_audit import max_gather_elems
        cfg, params = tiny_gpt2()

        def decode_hlo(pk):
            eng = InferenceEngine(cfg, params,
                                  dict(TINY_INF, paged_kv=pk),
                                  dtype=jnp.float32)
            rows = eng.num_slots + 1
            pps = eng.paged_spec.pages_per_seq
            args = (eng.params, eng._cache,
                    jnp.zeros((rows,), jnp.int32),
                    jnp.zeros((rows,), jnp.int32),
                    jnp.zeros((rows, pps), jnp.int32),
                    jnp.zeros((rows, 2), jnp.uint32),
                    jnp.zeros((rows,), jnp.float32))
            hlo = jax.jit(eng._decode_paged_impl).lower(
                *args).compile().as_text()
            return hlo, eng.paged_spec, rows

        hlo_p, spec, rows = decode_hlo(PAGED_PALLAS)
        hlo_g, _, _ = decode_hlo(PAGED_GATHER)
        stripe = (rows * spec.pages_per_seq * spec.kv_heads
                  * spec.page_size * spec.head_dim)
        assert max_gather_elems(hlo_g) >= stripe
        assert max_gather_elems(hlo_p) < stripe

    def test_quantized_decode_program_stays_gather_free(self):
        """ISSUE 17 acceptance: with int8-resident weights AND the
        int8 KV pool the compiled pallas decode program is still free
        of stripe-sized gathers — the dequant happens per streamed
        tile inside the kernel (and per matmul for weights), never by
        materializing a dequantized pool or stripe."""
        from deepspeed_tpu.inference import InferenceEngine
        from deepspeed_tpu.utils.hlo_audit import max_gather_elems
        cfg, params = tiny_gpt2()
        eng = InferenceEngine(
            cfg, params,
            dict(TINY_INF, quantize_weights="int8",
                 paged_kv=dict(PAGED_PALLAS, kv_dtype="int8")),
            dtype=jnp.float32)
        assert len(eng._cache) == 4       # int8 pools + fp32 scales
        rows = eng.num_slots + 1
        pps = eng.paged_spec.pages_per_seq
        args = (eng.params, eng._cache,
                jnp.zeros((rows,), jnp.int32),
                jnp.zeros((rows,), jnp.int32),
                jnp.zeros((rows, pps), jnp.int32),
                jnp.zeros((rows, 2), jnp.uint32),
                jnp.zeros((rows,), jnp.float32))
        hlo = jax.jit(eng._decode_paged_impl).lower(
            *args).compile().as_text()
        spec = eng.paged_spec
        stripe = (rows * spec.pages_per_seq * spec.kv_heads
                  * spec.page_size * spec.head_dim)
        assert max_gather_elems(hlo) < stripe


class TestPagedAttnConfig:
    def test_defaults_and_validation(self):
        from deepspeed_tpu.runtime.config import (DeepSpeedConfigError,
                                                  get_inference_config)
        cfg = get_inference_config({})
        assert cfg["paged_kv"]["attn_kernel"] == "pallas"
        assert cfg["paged_kv"]["decode_page_buckets"] == []
        with pytest.raises(DeepSpeedConfigError, match="attn_kernel"):
            get_inference_config(
                {"inference": {"paged_kv": {"attn_kernel": "cuda"}}})
        with pytest.raises(DeepSpeedConfigError,
                           match="decode_page_buckets"):
            get_inference_config(
                {"inference": {"paged_kv":
                               {"decode_page_buckets": [4, 2]}}})
        ok = get_inference_config(
            {"inference": {"paged_kv": {"decode_page_buckets": [2, 4],
                                        "attn_kernel": "gather"}}})
        assert ok["paged_kv"]["decode_page_buckets"] == [2, 4]
