"""V=2 vs V=4 interleave: the single-chip-measurable half (VERDICT r4
weak #5).

The 3D flagship's interleave choice trades three terms
(docs/pipeline.md): per-device stage memory (compiler-analyzed in
test_flagship_memory.py), collective-permute traffic (pinned statically
— 2 ppermutes per tick, tile-sized, test_hlo_collectives.py — and
linear in V), and the COMPUTE cost of finer virtual-stage granularity:
V=4 runs 6-layer stage blocks where V=2 runs 12-layer blocks, so the
compiled tick body XLA fuses/overlaps across is half as deep.

Only that last term needs hardware, and it needs just ONE chip: grad
time of lax.scan(12-layer block, length=1) vs lax.scan(6-layer block,
length=2) at the flagship block shape — identical total FLOPs,
identical weights, the only difference is the tick granularity, which
is exactly how the 1F1B executor structures the work
(runtime/pipe/spmd.py: one scan step per tick). The measured ratio
plus the static permute count completes the interleave trade with
real numbers (record in docs/pipeline.md).

Run on hardware:
  PYTHONPATH=/root/repo python tools/ab_interleave.py
"""
import numpy as np
import jax
import jax.numpy as jnp

from deepspeed_tpu.utils.platform import enable_compile_cache
from deepspeed_tpu.models.gpt2 import (GPT2Config, gpt2_block,
                                       init_gpt2_params)


def main():
    enable_compile_cache()
    # flagship block shape (GPT-2 1.5B: hidden 1600, 20 heads), seq and
    # micro-batch from the 3D bench config; 12 layers = one device's
    # stage depth at pipe=2 x V=2 for 48 layers
    H, SEQ, MB, DEPTH12 = 1600, 1024, 4, 12
    cfg = GPT2Config(vocab_size=64, max_position_embeddings=SEQ,
                     hidden_size=H, num_layers=DEPTH12, num_heads=20,
                     embd_dropout=0.0, attn_dropout=0.0,
                     resid_dropout=0.0)
    p12 = init_gpt2_params(cfg, jax.random.PRNGKey(0))
    layers = [p12[f"h_{i}"] for i in range(DEPTH12)]

    def stacked_blocks(nb, depth):
        """Pytree with leaves (nb, depth, ...) from the same 12 layers."""
        rows = []
        for b in range(nb):
            blk = layers[b * depth:(b + 1) * depth]
            rows.append(jax.tree_util.tree_map(
                lambda *xs: jnp.stack(xs), *blk))
        return jax.tree_util.tree_map(lambda *xs: jnp.stack(xs), *rows)

    def make_loss(nb, depth):
        def loss(stacked, x):
            def tick(carry, blk):
                for i in range(depth):
                    lp = jax.tree_util.tree_map(lambda a: a[i], blk)
                    carry = gpt2_block(lp, cfg, carry, None, True,
                                       jnp.bfloat16, None, None)
                return carry, ()
            out, _ = jax.lax.scan(tick, x, stacked)
            return jnp.sum(out.astype(jnp.float32))
        return loss

    from deepspeed_tpu.utils.benchtime import measure_rtt, scan_grad_seconds
    rtt = measure_rtt()
    print(f"rtt: {rtt * 1e3:.1f} ms", flush=True)
    x0 = jax.random.normal(jax.random.PRNGKey(9), (MB, SEQ, H),
                           jnp.bfloat16)

    times = {}
    for V, (nb, depth) in ((2, (1, 12)), (4, (2, 6))):
        stacked = stacked_blocks(nb, depth)
        # scan_grad_seconds feeds back per positional ARRAY arg — pass
        # the param pytree as flattened leaves
        leaves, treedef = jax.tree_util.tree_flatten(stacked)
        loss = make_loss(nb, depth)

        def loss_flat(*args, _treedef=treedef, _loss=loss):
            *ls, x = args
            return _loss(jax.tree_util.tree_unflatten(_treedef, ls), x)

        grad_fn = jax.grad(loss_flat,
                           argnums=tuple(range(len(leaves) + 1)))
        try:
            sec, n = scan_grad_seconds(grad_fn, (*leaves, x0), rtt,
                                       start_len=8)
        except Exception as e:
            print(f"V={V}: FAILED {type(e).__name__}: {e}", flush=True)
            continue
        times[V] = sec
        print(f"V={V} (scan of {nb} x {depth}-layer tick): "
              f"{sec * 1e3:.2f} ms/12-layer grad ({n}-chained)",
              flush=True)

    if 2 in times and 4 in times:
        ratio = times[4] / times[2]
        print(f"\ncompute overhead of V=4 granularity: {ratio:.3f}x "
              f"(+{(ratio - 1) * 100:.1f}% per device-stage)", flush=True)
        print("permute side (static audit): 2 ppermutes/tick, "
              "tile-sized; V=4 runs 2x the ticks -> 2x permute traffic "
              "(test_hlo_collectives.py, docs/pipeline.md)", flush=True)


if __name__ == "__main__":
    main()
