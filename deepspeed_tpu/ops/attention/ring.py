"""Ring attention — sequence/context parallelism over a mesh axis.

Long-context support beyond the reference snapshot (whose only answer is
block-sparse attention, docs/_posts/2020-09-09-sparse-attention.md): the
sequence dimension is sharded over the ``seq`` mesh axis and K/V chunks
rotate around the ring via ``lax.ppermute`` (ICI neighbor exchange), while
each device's Q stays resident. Per visiting chunk the local Pallas flash
kernel (ops/attention/flash.py) produces a normalized partial output plus
its log-sum-exp; partials combine exactly with online-softmax reweighting,
so the result is bitwise the same attention math at 1/P sequence memory
per device — attention over sequences no single chip could hold.

Algorithm (RingAttention, arXiv:2310.01889, re-derived on the flash
kernel's (o, lse) interface — no kernel changes needed):

forward, P = ring size, idx = my shard index, step j holds chunk
``src = (idx - j) mod P``:
- j = 0: the diagonal chunk (src == idx): local causal flash.
- j > 0: non-causal flash against the visiting chunk; for causal
  attention a chunk from the future (src > idx) is discarded by masking
  its combine weight — computed uniformly on every device, so the
  ppermute stays uniform (same invariant as the pipeline executor,
  runtime/pipe/spmd.py).
- combine: running (o, lse) with logaddexp reweighting in fp32.

backward re-runs the ring: dq accumulates locally; (dk, dv) for the
visiting chunk accumulate in buffers that rotate *with* k/v and arrive
back at their owner after the full cycle. Each per-chunk backward calls
the flash backward with the TOTAL lse/delta, which is exactly the
decomposition ds = p * (dp - delta) with p = exp(s - lse_total).

Causal cost note: the plain ring computes all P chunks and discards the
future ones (~2x the minimal causal work, like the unbalanced ring in the
paper). ``zigzag=True`` runs the load-balanced schedule instead: the
global sequence is cut into 2P chunks and shard i owns chunks
(i, 2P-1-i) — its local sequence is the concatenation of those two
halves. Each ring step then computes exactly TWO half-chunk flash calls
per device: (late half vs visiting early half), which causality always
needs, plus one call whose operands are SELECTED by the uniform
predicate ``src < idx`` — (early vs visiting-early) for past sources,
(late vs visiting-late) for future ones — merged into the right half's
accumulator by masked combines. Work is identical on every device and
totals the minimal causal 2P+1 half-chunk pairs per device (~half the
plain ring's FLOPs), with the same single rotating KV channel.
Use :func:`zigzag_layout_indices` to lay the global sequence out.

Dropout: each chunk pair derives a distinct seed (seed ^ mix(src) plain,
seed ^ mix(q_chunk, k_chunk) zigzag) so the in-kernel counter-based mask
never repeats across chunks and regenerates identically in forward and
backward.
"""

import functools
from typing import Optional

import jax
import jax.numpy as jnp
import numpy as np

from deepspeed_tpu.ops.attention.flash import (
    _flash_bwd, _flash_fwd, _use_pallas, dropout_seed_from_rng)

NEG_BIG = -1e30
VALID_THRESH = -1e29


def _chunk_seed(seed, src):
    # distinct per-chunk dropout stream; int32 wraparound is fine
    return seed + (src * jnp.int32(-1640531527))  # 2654435761 as int32


def _combine(o_acc, lse_acc, o_j, lse_j):
    """Exact online-softmax merge of normalized partials (fp32)."""
    lse_new = jnp.maximum(lse_acc, lse_j) + jnp.log1p(
        jnp.exp(-jnp.abs(lse_acc - lse_j)))
    w_acc = jnp.where(lse_acc <= VALID_THRESH, 0.0,
                      jnp.exp(lse_acc - lse_new))
    w_j = jnp.where(lse_j <= VALID_THRESH, 0.0, jnp.exp(lse_j - lse_new))
    o_new = o_acc * w_acc[..., None] + o_j.astype(jnp.float32) * \
        w_j[..., None]
    lse_new = jnp.where(
        jnp.logical_and(lse_acc <= VALID_THRESH, lse_j <= VALID_THRESH),
        NEG_BIG, lse_new)
    return o_new, lse_new


def _rot(x, axis_name, P, shift=1):
    return jax.lax.ppermute(x, axis_name,
                            [(i, (i + shift) % P) for i in range(P)])


def _ring_fwd_impl(q, k, v, kpm, seed, axis_name, causal, sm_scale,
                   interpret, rate):
    P = jax.lax.axis_size(axis_name)
    idx = jax.lax.axis_index(axis_name)

    # step 0: diagonal chunk, local causal (or plain) flash
    o0, lse0 = _flash_fwd(q, k, v, kpm, causal, sm_scale, interpret,
                          dropout_rate=rate,
                          seed=_chunk_seed(seed, idx) if rate > 0.0 else seed)
    o_acc = o0.astype(jnp.float32)
    lse_acc = lse0

    def step(carry, j):
        k_cur, v_cur, kpm_cur, o_acc, lse_acc = carry
        k_cur = _rot(k_cur, axis_name, P)
        v_cur = _rot(v_cur, axis_name, P)
        if kpm_cur is not None:
            kpm_cur = _rot(kpm_cur, axis_name, P)
        src = (idx - j) % P
        sj = _chunk_seed(seed, src) if rate > 0.0 else seed
        o_j, lse_j = _flash_fwd(q, k_cur, v_cur, kpm_cur, False, sm_scale,
                                interpret, dropout_rate=rate, seed=sj)
        if causal:
            valid = src < idx          # strictly-past chunk
            lse_j = jnp.where(valid, lse_j, NEG_BIG)
        o_acc, lse_acc = _combine(o_acc, lse_acc, o_j, lse_j)
        return (k_cur, v_cur, kpm_cur, o_acc, lse_acc), None

    if P > 1:
        (_, _, _, o_acc, lse_acc), _ = jax.lax.scan(
            step, (k, v, kpm, o_acc, lse_acc), jnp.arange(1, P))
    return o_acc.astype(q.dtype), lse_acc


def _ring_bwd_impl(res, do, axis_name, causal, sm_scale, interpret, rate):
    q, k, v, kpm, seed, o, lse = res
    P = jax.lax.axis_size(axis_name)
    idx = jax.lax.axis_index(axis_name)

    # diagonal chunk
    dq, dk0, dv0, _ = _flash_bwd(
        (q, k, v, kpm,
         _chunk_seed(seed, idx) if rate > 0.0 else seed, o, lse),
        do, causal, sm_scale, interpret, dropout_rate=rate)
    dq = dq.astype(jnp.float32)
    dk_acc = dk0.astype(jnp.float32)
    dv_acc = dv0.astype(jnp.float32)

    def step(carry, j):
        k_cur, v_cur, kpm_cur, dk_cur, dv_cur, dq = carry
        # rotate k/v (+ their key mask) and grad accumulators together
        k_cur = _rot(k_cur, axis_name, P)
        v_cur = _rot(v_cur, axis_name, P)
        if kpm_cur is not None:
            kpm_cur = _rot(kpm_cur, axis_name, P)
        dk_cur = _rot(dk_cur, axis_name, P)
        dv_cur = _rot(dv_cur, axis_name, P)
        src = (idx - j) % P
        sj = _chunk_seed(seed, src) if rate > 0.0 else seed
        dq_j, dk_j, dv_j, _ = _flash_bwd(
            (q, k_cur, v_cur, kpm_cur, sj, o, lse), do, False, sm_scale,
            interpret, dropout_rate=rate)
        if causal:
            valid = (src < idx).astype(jnp.float32)
            dq_j = dq_j * valid
            dk_j = dk_j * valid
            dv_j = dv_j * valid
        dq = dq + dq_j.astype(jnp.float32)
        dk_cur = dk_cur + dk_j.astype(jnp.float32)
        dv_cur = dv_cur + dv_j.astype(jnp.float32)
        return (k_cur, v_cur, kpm_cur, dk_cur, dv_cur, dq), None

    if P > 1:
        (_, _, _, dk_acc, dv_acc, dq), _ = jax.lax.scan(
            step, (k, v, kpm, dk_acc, dv_acc, dq), jnp.arange(1, P))
        # one final rotation completes the cycle: each (dk, dv) buffer
        # returns to the device owning that chunk
        dk_acc = _rot(dk_acc, axis_name, P)
        dv_acc = _rot(dv_acc, axis_name, P)
    return dq.astype(q.dtype), dk_acc.astype(k.dtype), \
        dv_acc.astype(v.dtype)


# --------------------------------------------------------------------- #
# zigzag (load-balanced causal) schedule
# --------------------------------------------------------------------- #
def zigzag_layout_indices(P: int, seq: int) -> np.ndarray:
    """Global gather indices for the zigzag layout: shard i's local
    sequence = global chunks (i, 2P-1-i) concatenated. ``g`` is laid out
    shard-major, so with a (seq,)-sharded array x over P shards,
    ``x[..., g, :]`` re-distributes it into the zigzag layout (one XLA
    all-to-all under GSPMD); apply ``np.argsort(g)`` to invert."""
    assert seq % (2 * P) == 0, (seq, P)
    lc = seq // (2 * P)
    out = []
    for i in range(P):
        out.extend(range(i * lc, (i + 1) * lc))
        out.extend(range((2 * P - 1 - i) * lc, (2 * P - i) * lc))
    return np.asarray(out, np.int64)


def _zz_seed(seed, qc, kc, P):
    # distinct stream per (q-chunk, k-chunk) pair, fwd/bwd reproducible
    return seed + ((qc * 2 * P + kc + 1) * jnp.int32(-1640531527))


def _halves(x, axis=2):
    if x is None:
        return None, None
    lc = x.shape[axis] // 2
    lo = jax.lax.slice_in_dim(x, 0, lc, axis=axis)
    hi = jax.lax.slice_in_dim(x, lc, 2 * lc, axis=axis)
    return lo, hi


def _sel(pred, a, b):
    return None if a is None else jnp.where(pred, a, b)


def _zz_fwd_impl(q, k, v, kpm, seed, axis_name, sm_scale, interpret, rate):
    P = jax.lax.axis_size(axis_name)
    idx = jax.lax.axis_index(axis_name)
    a1, a2 = idx, 2 * P - 1 - idx
    q1, q2 = _halves(q)
    k1, k2 = _halves(k)
    v1, v2 = _halves(v)
    m1, m2 = _halves(kpm, axis=3)

    def fwd(qc, kc, vc, mc, causal, sq, sk):
        s = _zz_seed(seed, sq, sk, P) if rate > 0.0 else seed
        return _flash_fwd(qc, kc, vc, mc, causal, sm_scale, interpret,
                          dropout_rate=rate, seed=s)

    # local: causal diagonals of both halves + (late vs own early)
    o1, l1 = fwd(q1, k1, v1, m1, True, a1, a1)
    o1 = o1.astype(jnp.float32)
    o2a, l2a = fwd(q2, k2, v2, m2, True, a2, a2)
    o2b, l2b = fwd(q2, k1, v1, m1, False, a2, a1)
    o2, l2 = _combine(o2a.astype(jnp.float32), l2a, o2b, l2b)

    def step(carry, j):
        k_cur, v_cur, m_cur, o1, l1, o2, l2 = carry
        k_cur = _rot(k_cur, axis_name, P)
        v_cur = _rot(v_cur, axis_name, P)
        if m_cur is not None:
            m_cur = _rot(m_cur, axis_name, P)
        src = (idx - j) % P
        b1, b2 = src, 2 * P - 1 - src
        kb1, kb2 = _halves(k_cur)
        vb1, vb2 = _halves(v_cur)
        mb1, mb2 = _halves(m_cur, axis=3)
        # call A: late half vs visiting early half — always causal-valid
        oA, lA = fwd(q2, kb1, vb1, mb1, False, a2, b1)
        o2, l2 = _combine(o2, l2, oA, lA)
        # call B: operand-selected by the uniform predicate src < idx
        pred = src < idx
        qB = _sel(pred, q1, q2)
        kB = _sel(pred, kb1, kb2)
        vB = _sel(pred, vb1, vb2)
        mB = _sel(pred, mb1, mb2) if m_cur is not None else None
        sq_ = jnp.where(pred, a1, a2)
        sk_ = jnp.where(pred, b1, b2)
        oB, lB = fwd(qB, kB, vB, mB, False, sq_, sk_)
        o1, l1 = _combine(o1, l1, oB, jnp.where(pred, lB, NEG_BIG))
        o2, l2 = _combine(o2, l2, oB, jnp.where(pred, NEG_BIG, lB))
        return (k_cur, v_cur, m_cur, o1, l1, o2, l2), None

    if P > 1:
        (_, _, _, o1, l1, o2, l2), _ = jax.lax.scan(
            step, (k, v, kpm, o1, l1, o2, l2), jnp.arange(1, P))
    o = jnp.concatenate([o1, o2], axis=2).astype(q.dtype)
    lse = jnp.concatenate([l1, l2], axis=2)
    return o, lse


def _zz_bwd_impl(res, do, axis_name, sm_scale, interpret, rate):
    q, k, v, kpm, seed, o, lse = res
    P = jax.lax.axis_size(axis_name)
    idx = jax.lax.axis_index(axis_name)
    a1, a2 = idx, 2 * P - 1 - idx
    q1, q2 = _halves(q)
    k1, k2 = _halves(k)
    v1, v2 = _halves(v)
    m1, m2 = _halves(kpm, axis=3)
    o1, o2 = _halves(o)
    l1, l2 = _halves(lse)
    do1, do2 = _halves(do)

    def bwd(qc, kc, vc, mc, oc, lc_, doc, causal, sq, sk):
        s = _zz_seed(seed, sq, sk, P) if rate > 0.0 else seed
        dq_, dk_, dv_, _ = _flash_bwd(
            (qc, kc, vc, mc, s, oc, lc_), doc, causal, sm_scale,
            interpret, dropout_rate=rate)
        return (dq_.astype(jnp.float32), dk_.astype(jnp.float32),
                dv_.astype(jnp.float32))

    # local pairs
    dq1, dk1, dv1 = bwd(q1, k1, v1, m1, o1, l1, do1, True, a1, a1)
    dq2, dk2, dv2 = bwd(q2, k2, v2, m2, o2, l2, do2, True, a2, a2)
    g2b = bwd(q2, k1, v1, m1, o2, l2, do2, False, a2, a1)
    dq2 = dq2 + g2b[0]
    dk1 = dk1 + g2b[1]
    dv1 = dv1 + g2b[2]
    dk_buf = jnp.concatenate([dk1, dk2], axis=2)
    dv_buf = jnp.concatenate([dv1, dv2], axis=2)

    def step(carry, j):
        k_cur, v_cur, m_cur, dk_buf, dv_buf, dq1, dq2 = carry
        k_cur = _rot(k_cur, axis_name, P)
        v_cur = _rot(v_cur, axis_name, P)
        if m_cur is not None:
            m_cur = _rot(m_cur, axis_name, P)
        dk_buf = _rot(dk_buf, axis_name, P)
        dv_buf = _rot(dv_buf, axis_name, P)
        src = (idx - j) % P
        b1, b2 = src, 2 * P - 1 - src
        kb1, kb2 = _halves(k_cur)
        vb1, vb2 = _halves(v_cur)
        mb1, mb2 = _halves(m_cur, axis=3)
        dkb1, dkb2 = _halves(dk_buf)
        dvb1, dvb2 = _halves(dv_buf)
        # call A: q2 vs visiting early half — always valid
        gA = bwd(q2, kb1, vb1, mb1, o2, l2, do2, False, a2, b1)
        dq2 = dq2 + gA[0]
        dkb1 = dkb1 + gA[1]
        dvb1 = dvb1 + gA[2]
        # call B: operand-selected
        pred = src < idx
        qB = _sel(pred, q1, q2)
        kB = _sel(pred, kb1, kb2)
        vB = _sel(pred, vb1, vb2)
        mB = _sel(pred, mb1, mb2) if m_cur is not None else None
        oB = _sel(pred, o1, o2)
        lB = _sel(pred, l1, l2)
        doB = _sel(pred, do1, do2)
        sq_ = jnp.where(pred, a1, a2)
        sk_ = jnp.where(pred, b1, b2)
        gB = bwd(qB, kB, vB, mB, oB, lB, doB, False, sq_, sk_)
        w = pred.astype(jnp.float32)
        dq1 = dq1 + gB[0] * w
        dq2 = dq2 + gB[0] * (1.0 - w)
        dkb1 = dkb1 + gB[1] * w
        dkb2 = dkb2 + gB[1] * (1.0 - w)
        dvb1 = dvb1 + gB[2] * w
        dvb2 = dvb2 + gB[2] * (1.0 - w)
        dk_buf = jnp.concatenate([dkb1, dkb2], axis=2)
        dv_buf = jnp.concatenate([dvb1, dvb2], axis=2)
        return (k_cur, v_cur, m_cur, dk_buf, dv_buf, dq1, dq2), None

    if P > 1:
        (_, _, _, dk_buf, dv_buf, dq1, dq2), _ = jax.lax.scan(
            step, (k, v, kpm, dk_buf, dv_buf, dq1, dq2), jnp.arange(1, P))
        # final rotation returns each (dk, dv) buffer to its chunk owner
        dk_buf = _rot(dk_buf, axis_name, P)
        dv_buf = _rot(dv_buf, axis_name, P)
    dq = jnp.concatenate([dq1, dq2], axis=2)
    return dq.astype(q.dtype), dk_buf.astype(k.dtype), \
        dv_buf.astype(v.dtype)


@functools.partial(jax.custom_vjp, nondiff_argnums=(4, 5, 6, 7, 8))
def _zz_attention(q, k, v, seed, has_kpm, axis_name, sm_scale,
                  interpret, rate):
    kpm, seed = seed if has_kpm else (None, seed)
    o, _ = _zz_fwd_impl(q, k, v, kpm, seed, axis_name, sm_scale,
                        interpret, rate)
    return o


def _zz_attention_fwd(q, k, v, seed, has_kpm, axis_name, sm_scale,
                      interpret, rate):
    kpm, seed = seed if has_kpm else (None, seed)
    o, lse = _zz_fwd_impl(q, k, v, kpm, seed, axis_name, sm_scale,
                          interpret, rate)
    return o, (q, k, v, kpm, seed, o, lse)


def _zz_attention_bwd(has_kpm, axis_name, sm_scale, interpret, rate,
                      res, g):
    dq, dk, dv = _zz_bwd_impl(res, g, axis_name, sm_scale, interpret,
                              rate)
    return dq, dk, dv, ((None, None) if has_kpm else None)


_zz_attention.defvjp(_zz_attention_fwd, _zz_attention_bwd)


@functools.partial(jax.custom_vjp, nondiff_argnums=(4, 5, 6, 7, 8, 9))
def _ring_attention(q, k, v, seed, has_kpm, axis_name, causal, sm_scale,
                    interpret, rate):
    kpm, seed = seed if has_kpm else (None, seed)
    o, _ = _ring_fwd_impl(q, k, v, kpm, seed, axis_name, causal, sm_scale,
                          interpret, rate)
    return o


def _ring_attention_fwd(q, k, v, seed, has_kpm, axis_name, causal,
                        sm_scale, interpret, rate):
    kpm, seed = seed if has_kpm else (None, seed)
    o, lse = _ring_fwd_impl(q, k, v, kpm, seed, axis_name, causal,
                            sm_scale, interpret, rate)
    return o, (q, k, v, kpm, seed, o, lse)


def _ring_attention_bwd(has_kpm, axis_name, causal, sm_scale, interpret,
                        rate, res, g):
    dq, dk, dv = _ring_bwd_impl(res, g, axis_name, causal, sm_scale,
                                interpret, rate)
    return dq, dk, dv, ((None, None) if has_kpm else None)


_ring_attention.defvjp(_ring_attention_fwd, _ring_attention_bwd)


def ring_attention(q, k, v, axis_name: str = "seq", causal: bool = False,
                   sm_scale: Optional[float] = None,
                   dropout_rate: float = 0.0, dropout_rng=None,
                   key_padding_mask=None,
                   interpret: Optional[bool] = None,
                   zigzag: bool = False):
    """Sequence-parallel flash attention over ``axis_name``.

    Call INSIDE ``shard_map`` with ``axis_name`` manual; q/k/v are this
    device's sequence shard, shape (batch, heads, seq_local, head_dim)
    with identical seq_local on every shard. Plain layout: shard i owns
    positions [i*seq_local, (i+1)*seq_local). ``zigzag=True`` (causal
    only) uses the load-balanced layout instead — shard i owns global
    chunks (i, 2P-1-i) of 2P, concatenated (:func:`zigzag_layout_indices`)
    — for ~half the causal FLOPs at identical math (module docstring).

    ``key_padding_mask``: optional *additive* (B, 1, 1, seq_local) mask
    for this shard's keys (BERT padding); it rotates around the ring
    with its K/V chunk.
    """
    if sm_scale is None:
        sm_scale = 1.0 / np.sqrt(q.shape[-1])
    if interpret is None:
        interpret = not _use_pallas()
    dropout_rate = float(dropout_rate)
    if dropout_rate > 0.0:
        assert dropout_rng is not None, \
            "ring_attention: dropout_rate > 0 requires dropout_rng"
        seed = dropout_seed_from_rng(dropout_rng)
    else:
        seed = jnp.zeros((1, 1), jnp.int32)
    if zigzag:
        assert causal, "zigzag schedule is a causal-attention optimization"
        assert q.shape[2] % 2 == 0, \
            f"zigzag needs an even local seq, got {q.shape[2]}"
        if key_padding_mask is not None:
            return _zz_attention(q, k, v, (key_padding_mask, seed), True,
                                 axis_name, float(sm_scale), interpret,
                                 dropout_rate)
        return _zz_attention(q, k, v, seed, False, axis_name,
                             float(sm_scale), interpret, dropout_rate)
    if key_padding_mask is not None:
        return _ring_attention(q, k, v, (key_padding_mask, seed), True,
                               axis_name, causal, float(sm_scale),
                               interpret, dropout_rate)
    return _ring_attention(q, k, v, seed, False, axis_name, causal,
                           float(sm_scale), interpret, dropout_rate)


# --------------------------------------------------------------------- #
# forward-only ring prefill over a paged-KV stripe (serving, ISSUE 19)
# --------------------------------------------------------------------- #
def _ring_prefill_shard(q, kc, vc, cache_position, axis_name, P, Sl, Ll,
                        sm_scale):
    """Per-shard body of :func:`ring_prefill_attention` (inside the
    shard_map): my Q block stays resident while K/V stripe blocks
    rotate around the ring; each visit contributes a normalized fp32
    partial (o_j, lse_j) masked by the ABSOLUTE-position causal rule of
    ``page_pool.causal_cache_mask`` — q position ``cache_position +
    global_q_idx`` attends stripe slots ``<=`` it — and partials merge
    with the exact online-softmax combine. GQA runs group-wise like
    the llama gather fallback (q heads fold onto their kv head)."""
    idx = jax.lax.axis_index(axis_name)
    B, H, _, hd = q.shape
    KH = kc.shape[1]
    G = H // KH
    qg = q.astype(jnp.float32).reshape(B, KH, G, Sl, hd)
    q_pos = (cache_position[:, None] + idx * Sl
             + jnp.arange(Sl)[None, :])                       # (B, Sl)

    def partial(k_blk, v_blk, src):
        scores = jnp.einsum("bkgsd,bkld->bkgsl", qg,
                            k_blk.astype(jnp.float32)) * sm_scale
        kv_pos = src * Ll + jnp.arange(Ll)                    # (Ll,)
        valid = kv_pos[None, None, :] <= q_pos[:, :, None]    # (B,Sl,Ll)
        scores = jnp.where(valid[:, None, None], scores, NEG_BIG)
        m = jnp.max(scores, axis=-1)
        p = jnp.exp(scores - m[..., None])
        s = jnp.sum(p, axis=-1)
        o = jnp.einsum("bkgsl,bkld->bkgsd", p,
                       v_blk.astype(jnp.float32)) / \
            jnp.maximum(s, 1e-30)[..., None]
        lse = jnp.where(m <= VALID_THRESH, NEG_BIG,
                        m + jnp.log(jnp.maximum(s, 1e-30)))
        return o, lse

    o_acc, lse_acc = partial(kc, vc, idx)

    def step(carry, j):
        k_cur, v_cur, o_acc, lse_acc = carry
        k_cur = _rot(k_cur, axis_name, P)
        v_cur = _rot(v_cur, axis_name, P)
        src = (idx - j) % P
        o_j, lse_j = partial(k_cur, v_cur, src)
        o_acc, lse_acc = _combine(o_acc, lse_acc, o_j, lse_j)
        return (k_cur, v_cur, o_acc, lse_acc), None

    if P > 1:
        (_, _, o_acc, lse_acc), _ = jax.lax.scan(
            step, (kc, vc, o_acc, lse_acc), jnp.arange(1, P))
    return o_acc.reshape(B, H, Sl, hd).astype(q.dtype)


def ring_prefill_attention(q, kc, vc, cache_position, mesh,
                           axis: str = "model",
                           sm_scale: Optional[float] = None):
    """Context-parallel PREFILL attention for the serving engine's
    chunk dispatches (forward-only — serving never needs the ring
    backward): ``q`` (B, H, S, hd) is the chunk's queries, ``kc``/
    ``vc`` (B, KH, L, hd) the gathered (dequantized) paged-KV stripe,
    ``cache_position`` (B,) each row's absolute prefilled offset —
    exactly the operands of the models' gather-fallback attention,
    same masking rule, same fp32 math, with the sequence axes sharded
    over ``(mesh, axis)``: Q blocks stay resident, K/V stripe blocks
    ring via ppermute, partials merge with the exact online-softmax
    combine. Requires S and L divisible by the axis size (the engine
    validates at init and logs the fallback otherwise)."""
    from jax.sharding import PartitionSpec as P_

    P = mesh.shape[axis]
    B, H, S, hd = q.shape
    L = kc.shape[2]
    assert S % P == 0 and L % P == 0, (
        f"ring_prefill_attention: seq ({S}) and stripe ({L}) must be "
        f"divisible by mesh axis {axis!r} ({P}-way)")
    assert H % kc.shape[1] == 0, (H, kc.shape[1])
    if sm_scale is None:
        sm_scale = 1.0 / np.sqrt(hd)

    def inner(q, kc, vc, cache_position):
        return _ring_prefill_shard(q, kc, vc, cache_position, axis, P,
                                   S // P, L // P, float(sm_scale))

    seq_spec = P_(None, None, axis, None)
    f = jax.shard_map(
        inner, mesh=mesh,
        in_specs=(seq_spec, seq_spec, seq_spec, P_()),
        out_specs=seq_spec, check_vma=False)
    return f(q, kc, vc, cache_position)
