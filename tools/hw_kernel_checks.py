"""On-chip parity sweep of the SPARSE-attention kernels: run each path
on the REAL TPU against its jnp oracle and print PASS/FAIL per check
(the unit suite runs these in interpret mode on CPU; this is the
hardware evidence). The kernels of the main path — masked flash causal
with and without dropout, paged decode — are checked at real widths by
``chip_smoke.py``, whose helpers this sweep shares.

Run on hardware:  PYTHONPATH=/root/repo python tools/hw_kernel_checks.py
(each check pays at most one compile, shared via the persistent
compile cache). Exits nonzero if any check fails.
"""

import sys
import traceback

import numpy as np

from chip_smoke import assert_close as _close
from chip_smoke import grad_pair as _grad_pair
from chip_smoke import random_qkv as _qkv

CHECKS = []


def check(name):
    def deco(fn):
        CHECKS.append((name, fn))
        return fn
    return deco


def _sparse_vs_oracle(layout, seed, expect_kernel=None):
    """Shared body of the sparse-kernel parity checks: dispatcher vs
    the dense-masked oracle, fwd + all three grads, with an optional
    planned-kernel pin so a dispatch regression cannot silently pass
    as a different (correct) kernel family."""
    import jax.numpy as jnp
    from deepspeed_tpu.ops.sparse_attention import block_sparse_attention
    from deepspeed_tpu.ops.sparse_attention.blocksparse import (
        layout_additive_mask, planned_kernel)
    from deepspeed_tpu.ops.attention.flash import attention_reference
    H = layout.shape[0]
    S = layout.shape[1] * 128
    if expect_kernel is not None:
        got = planned_kernel(layout, 128)
        assert got == expect_kernel, \
            f"layout no longer dispatches to {expect_kernel} (got {got})"
    q, k, v = _qkv(1, H, S, 64, seed=seed)
    am = jnp.asarray(layout_additive_mask(layout, 128))[None]

    def kern(q, k, v):
        return block_sparse_attention(q, k, v, layout)

    def orac(q, k, v):
        return attention_reference(q, k, v, mask=am)

    _close(kern(q, k, v), orac(q, k, v), msg="fwd")
    ga, gb = _grad_pair(kern, orac, (q, k, v))
    for a, b, n in zip(ga, gb, "qkv"):
        _close(a, b, msg=f"d{n}")


@check("banded Longformer w=3 fwd+grad vs dense-masked oracle (S=2048)")
def _splash_banded():
    from deepspeed_tpu.ops.sparse_attention import (
        BSLongformerSparsityConfig)
    cfg = BSLongformerSparsityConfig(num_heads=4, block=128,
                                     num_sliding_window_blocks=3)
    _sparse_vs_oracle(cfg.make_layout(2048), seed=3,
                      expect_kernel="banded")


@check("hybrid BigBird fwd+grad vs dense-masked oracle (S=2048)")
def _hybrid_bigbird():
    from deepspeed_tpu.ops.sparse_attention import BigBirdSparsityConfig
    cfg = BigBirdSparsityConfig(num_heads=4, block=128,
                                num_random_blocks=1,
                                num_sliding_window_blocks=3,
                                num_global_blocks=1)
    _sparse_vs_oracle(cfg.make_layout(2048), seed=7,
                      expect_kernel="hybrid")


@check("splash v2 (banded forced off) Longformer vs oracle (S=2048)")
def _splash_v2():
    from deepspeed_tpu.ops.sparse_attention import (
        BSLongformerSparsityConfig)
    from deepspeed_tpu.ops.sparse_attention import blocksparse as bs
    cfg = BSLongformerSparsityConfig(num_heads=4, block=128,
                                     num_sliding_window_blocks=3)
    old = bs.USE_BANDED
    bs.USE_BANDED = False
    try:
        _sparse_vs_oracle(cfg.make_layout(2048), seed=3)
    finally:
        bs.USE_BANDED = old


@check("coarse walk (forced 512) == fine walk, grads (S=2048)")
def _coarse_parity():
    import jax
    import jax.numpy as jnp
    from deepspeed_tpu.ops.sparse_attention import (
        BSLongformerSparsityConfig, block_sparse_attention)
    from deepspeed_tpu.ops.sparse_attention import blocksparse as bs
    H, S = 4, 2048
    cfg = BSLongformerSparsityConfig(num_heads=H, block=128,
                                     num_sliding_window_blocks=3)
    layout = cfg.make_layout(S)
    q, k, v = _qkv(1, H, S, 64, seed=5)

    old = bs.USE_BANDED
    bs.USE_BANDED = False          # the coarse/fine walk is the v2 path
    try:
        def run(force):
            # _FN_CACHE keys on _FORCE_COARSE_BLOCK: no clear() needed
            bs._FORCE_COARSE_BLOCK = force
            try:
                g = jax.jit(jax.grad(
                    lambda q, k, v: jnp.sum(
                        block_sparse_attention(q, k, v, layout)
                        .astype(jnp.float32)), argnums=(0, 1, 2)))
                return jax.tree_util.tree_map(np.asarray, g(q, k, v))
            finally:
                bs._FORCE_COARSE_BLOCK = None
        fine, coarse = run(0), run(512)
        for a, b, n in zip(fine, coarse, "qkv"):
            _close(a, b, msg=f"d{n}")
    finally:
        bs.USE_BANDED = old


@check("fine block=16 rides the coarse streamed path (S=2048)")
def _small_block_coarse():
    from deepspeed_tpu.ops.sparse_attention import (
        FixedSparsityConfig, block_sparse_attention,
        block_sparse_attention_reference)
    from deepspeed_tpu.ops.sparse_attention import blocksparse as bs
    H, S = 2, 2048
    cfg = FixedSparsityConfig(num_heads=H, block=16, num_local_blocks=4)
    layout = cfg.make_layout(S)
    assert bs._pick_coarse_block(np.asarray(layout), 16,
                                 has_am=False) is not None, \
        "cost model declined to coarsen a block=16 layout"
    q, k, v = _qkv(1, H, S, 32, seed=9)
    _close(block_sparse_attention(q, k, v, layout),
           block_sparse_attention_reference(q, k, v, layout), msg="fwd")


def main():
    import jax
    backend = jax.default_backend()
    print(f"# backend: {backend}", flush=True)
    if backend != "tpu" and "--allow-cpu" not in sys.argv:
        # a green interpret-mode run is NOT hardware evidence — refuse
        # rather than record a false on-chip parity sweep (the unit
        # suite already covers interpret mode)
        print("# NOT on TPU — refusing to produce 'hardware evidence' "
              "from interpret mode (pass --allow-cpu to smoke-test the "
              "harness itself)", flush=True)
        sys.exit(3)
    from deepspeed_tpu.utils.platform import enable_compile_cache
    enable_compile_cache()
    failed = 0
    for name, fn in CHECKS:
        try:
            fn()
            print(f"PASS  {name}", flush=True)
        except Exception:
            failed += 1
            print(f"FAIL  {name}", flush=True)
            traceback.print_exc()
    print(f"# {len(CHECKS) - failed}/{len(CHECKS)} kernel checks passed",
          flush=True)
    sys.exit(1 if failed else 0)


if __name__ == "__main__":
    main()
