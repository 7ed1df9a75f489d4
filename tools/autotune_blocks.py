"""Attention block-size autotune sweep (VERDICT r2 #6, r3 #6).

TPU-native analog of the reference's GemmTest autotuner
(/root/reference/csrc/includes/gemm_test.h:27): instead of per-GEMM
algorithm search at engine construction, this offline harness times
kernel block combinations per shape class on the REAL chip and writes
the winners to ``deepspeed_tpu/ops/attention/block_table.json``,
consulted at trace time by ``flash._pick_blocks`` (kind="flash": keys
seq_q/seq_k/d/stream/gqa), ``flash.lookup_masked_blocks``
(kind="masked": keys seq_q/seq_k/d/stream, one square ``b`` — the
unified mask-parameterized kernel's dense/causal walk tile, PR 11) and
``flash.lookup_banded_blocks`` (kind="banded": keys
seq/fine_block/band_w/causal for the legacy banded sparse walk).
Unknown shapes keep the hand-measured heuristics (one logged line per
shape for the masked kernel).

Every entry is stamped with the measuring chip's ``device_kind``; the
lookups only consume same-device entries (legacy unstamped entries act
as a global fallback), so a v5p never consumes v5e-tuned blocks. On a
hardware run this tool also stamps any legacy unstamped entries with the
current device_kind — this rig has only ever measured on its one chip.

Run on hardware:  PYTHONPATH=/root/repo python tools/autotune_blocks.py
(~minutes; each combo pays one compile, amortized by the persistent
compile cache). Timing: value-fetch completion barrier + RTT
subtraction via the shared scan-amortized protocol (utils/benchtime.py).
Idempotent: shapes that already have an entry for this device_kind are
skipped (pass --force to re-measure) so a re-run in a later hardware
window costs nothing and keeps the bench source digest stable.
"""

import argparse
import json
import os
import time


REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
OUT = os.path.join(REPO, "deepspeed_tpu", "ops", "attention",
                   "block_table.json")

# flash shape classes: (seq_q, seq_k, head_dim, gqa_group)
FLASH_SHAPES = [
    (128, 128, 64, 1),         # BERT-large seq128 (bench headline row)
    (512, 512, 64, 1),         # BERT seq512
    (1024, 1024, 64, 1),       # GPT-2 345M / 1.5B pretraining
    (2048, 2048, 64, 1),
    (8192, 8192, 64, 1),       # long-context / sparse-vs-dense row
    (16384, 16384, 64, 1),     # streamed
    (32768, 32768, 64, 1),     # streamed
    (1024, 1024, 80, 1),       # 80-dim heads (e.g. 2560/32-style configs)
    (1024, 1024, 128, 1),      # llama-family head_dim
    (2048, 2048, 128, 4),      # llama GQA (kv_heads = heads/4)
    (4096, 4096, 128, 4),
    (2048, 2048, 64, 4),       # GQA at d=64
]
CANDIDATES = (64, 128, 256, 512)

# banded sparse walk shape classes: (S, fine_block, window_blocks)
# — the bench row (S=8192, fb=128, win=3 BSLongformer) FIRST (sweep is
# incremental; a short window should land the scored shape), then its
# s16k long-context detail and the class-default fb=64 geometry
BANDED_SHAPES = [
    (8192, 128, 3),
    (16384, 128, 3),
    (8192, 64, 3),
    # the reference's OWN headline density: block 16, 48-token window
    # (~1% density -> FLOP bound ~51x vs causal-dense; at (128,128)
    # walk tiles the static waste is 8x -> ~6.4x-vs-flash potential,
    # above the 6.3x claim). Feeds the bench row's refdensity detail.
    (8192, 16, 3),
]
# each combo compiles 7 pallas kernels: keep the candidate list small — static walk_stats
# says the FLOP spread (128,128) 1.0x -> (512,512) 4.1x of bound, so
# these four bracket the overhead-vs-waste trade
BANDED_COMBOS = ((128, 128), (256, 256), (256, 512), (512, 512))


def _rtt():
    from deepspeed_tpu.utils.benchtime import measure_rtt
    return measure_rtt()


def _device_kind():
    import jax
    try:
        return jax.devices()[0].device_kind
    except Exception:
        return None


def _shape_plan(sq):
    """(batch, heads, scan_iters) per shape class: batch*heads mirrors the
    bench/model ladder's grid occupancy, scan_iters targets O(0.5-2s) of
    pure device time so the host's per-dispatch latency is amortized
    away inside one dispatch."""
    if sq <= 512:
        return 8, 16, 100
    if sq <= 2048:
        return 1, 16, 40
    if sq <= 8192:
        return 1, 8, 8
    return 1, 4, 3


def time_combo(sq, sk, d, bq, bk, rtt, iters=None, heads=None, gqa=1):
    # iters/heads are debug-only overrides (smoke tests); the sweep itself
    # always lets _shape_plan pick them so winners aren't latency-noise.
    import jax
    import jax.numpy as jnp
    from deepspeed_tpu.ops.attention import flash as F

    batch, h, n = _shape_plan(max(sq, sk))
    if heads is not None:
        h = heads
    if iters is not None:
        n = iters
    h = max(h, gqa)
    key = jax.random.PRNGKey(0)
    q = jax.random.normal(jax.random.fold_in(key, 0), (batch, h, sq, d),
                          jnp.bfloat16)
    k, v = (jax.random.normal(jax.random.fold_in(key, i),
                              (batch, h // gqa, sk, d), jnp.bfloat16)
            for i in (1, 2))

    def loss(q, k, v):
        return jnp.sum(F.flash_attention(q, k, v, causal=True)
                       .astype(jnp.float32))

    grad_fn = jax.grad(loss, argnums=(0, 1, 2))

    # Shared scan-amortized protocol (utils/benchtime.py): chained grad
    # evals in ONE dispatch, RTT-noise floor with rescaling, fail —
    # never ~0 — when the floor is unreachable.
    from deepspeed_tpu.utils.benchtime import scan_grad_seconds

    # kind="flash" entries feed the LEGACY per-path kernels — pin them
    # for the measurement (the default dispatch is the masked kernel,
    # which sweeps separately through time_masked_combo)
    old_opts = F.set_attention_options(kernel="flash")
    F._FORCE_BLOCKS = (bq, bk)
    try:
        sec, _n = scan_grad_seconds(grad_fn, (q, k, v), rtt, start_len=n,
                                    max_len=n * 4096)
        # normalize to the old (1, 8, S) work unit so tables stay comparable
        return sec * 8.0 / (batch * h)
    finally:
        F._FORCE_BLOCKS = None
        F._OPTIONS = old_opts


def time_masked_combo(sq, sk, d, b, rtt, iters=None, gqa=1):
    """One dense/causal grad eval through the UNIFIED masked kernel at
    a forced square walk tile ``b`` (kind="masked" table entries)."""
    import jax
    import jax.numpy as jnp
    from deepspeed_tpu.ops.attention import flash as F
    from deepspeed_tpu.utils.benchtime import scan_grad_seconds

    batch, h, n = _shape_plan(max(sq, sk))
    if iters is not None:
        n = iters
    h = max(h, gqa)
    key = jax.random.PRNGKey(0)
    q = jax.random.normal(jax.random.fold_in(key, 0), (batch, h, sq, d),
                          jnp.bfloat16)
    k, v = (jax.random.normal(jax.random.fold_in(key, i),
                              (batch, h // gqa, sk, d), jnp.bfloat16)
            for i in (1, 2))

    def loss(q, k, v):
        return jnp.sum(F.flash_attention(q, k, v, causal=True)
                       .astype(jnp.float32))

    # pin the unified kernel (a DSTPU_ATTENTION_KERNEL A/B export must
    # not abort the sweep — time_combo pins "flash" the same way)
    old_opts = F.set_attention_options(kernel="masked")
    F._FORCE_BLOCKS = (b, b)
    F._DENSE_MASK_CACHE.clear()
    try:
        sec, _n = scan_grad_seconds(jax.grad(loss, argnums=(0, 1, 2)),
                                    (q, k, v), rtt, start_len=n,
                                    max_len=n * 4096)
        return sec * 8.0 / (batch * h)
    finally:
        F._FORCE_BLOCKS = None
        F._OPTIONS = old_opts
        F._DENSE_MASK_CACHE.clear()


def time_banded_combo(S, fb, win, bq, bk, rtt, iters=None):
    """One banded-walk grad eval at the bench row's geometry."""
    import jax
    import jax.numpy as jnp
    from deepspeed_tpu.ops.sparse_attention import banded
    from deepspeed_tpu.ops.sparse_attention import blocksparse as bs
    from deepspeed_tpu.ops.sparse_attention.sparsity_config import (
        BSLongformerSparsityConfig)
    from deepspeed_tpu.utils.benchtime import scan_grad_seconds

    H = 16 if S <= 8192 else 8
    _, _, n = _shape_plan(S)
    if iters is not None:
        n = iters
    cfg = BSLongformerSparsityConfig(num_heads=H, block=fb,
                                     num_sliding_window_blocks=win)
    layout = cfg.make_layout(S)
    key = jax.random.PRNGKey(0)
    q, k, v = (jax.random.normal(jax.random.fold_in(key, i),
                                 (1, H, S, 64), jnp.bfloat16)
               for i in range(3))

    def loss(q, k, v):
        return jnp.sum(bs.block_sparse_attention(q, k, v, layout)
                       .astype(jnp.float32))

    banded._FORCE_BLOCKS = (bq, bk)
    bs._FN_CACHE.clear()
    try:
        if bs.planned_kernel(layout, fb) != "banded":
            raise RuntimeError("banded path did not engage")
        # pick_blocks silently falls back to table/heuristic tiles when
        # the forced pair fails _blocks_valid — make sure the kernel we
        # are about to time actually walks (bq, bk), or the measurement
        # would be recorded under the wrong label (ADVICE r4)
        import numpy as _np
        fn = bs._sparse_attention_fn(_np.asarray(layout), fb,
                                     float(1.0 / _np.sqrt(64)),
                                     has_am=False, interpret=False)
        got = getattr(fn, "banded_blocks", None)
        if got != (bq, bk):
            raise RuntimeError(
                f"forced banded blocks did not engage: built {got}, "
                f"forced {(bq, bk)}")
        sec, _n2 = scan_grad_seconds(jax.grad(loss, argnums=(0, 1, 2)),
                                     (q, k, v), rtt, start_len=n,
                                     max_len=n * 4096)
        return sec * 8.0 / H
    finally:
        banded._FORCE_BLOCKS = None
        bs._FN_CACHE.clear()


def _entry_key(r):
    """Merge identity: shape class + measuring device."""
    if r.get("kind") == "banded":
        shape = ("banded", r["seq"], r["fine_block"], r.get("band_w"),
                 bool(r.get("causal", False)))
    elif r.get("kind") == "masked":
        shape = ("masked", r["seq_q"], r["seq_k"], r["d"],
                 bool(r.get("stream")))
    else:
        shape = ("flash", r["seq_q"], r["seq_k"], r["d"],
                 bool(r.get("stream")), r.get("gqa", 1))
    return shape + (r.get("device_kind"),)


def _merge_write(out_path, rows, backend, device_kind):
    """Merge-write the table keyed by shape class + device: entries
    measured in THIS run replace same-shape-same-device entries, every
    other existing entry survives — a sweep that dies mid-ladder (device
    lost) must never erase the shapes a previous window already paid
    for. On hardware, legacy unstamped entries get stamped with the
    current device_kind (see module docstring)."""
    if backend != "tpu":
        return
    existing = []
    try:
        with open(out_path) as f:
            existing = json.load(f)
    except (OSError, ValueError):
        pass
    if device_kind:
        for r in existing:
            r.setdefault("device_kind", device_kind)
    merged = {}
    for r in existing:
        try:
            merged[_entry_key(r)] = r
        except KeyError:
            continue                      # malformed row: drop
    for r in rows:
        merged[_entry_key(r)] = r
    tmp = out_path + ".tmp"
    with open(tmp, "w") as f:
        json.dump(sorted(merged.values(), key=lambda r: json.dumps(
            _entry_key(r), default=str)), f, indent=1)
    os.replace(tmp, out_path)


def _covered(existing, key_wo_device, device_kind):
    for r in existing:
        try:
            k = _entry_key(r)
        except KeyError:
            continue
        if "ms" not in r:
            # seeded/unmeasured placeholder (e.g. the masked entries
            # shipped from the flash square winners): it serves lookups
            # as a fallback but must never stop the sweep from actually
            # MEASURING the shape
            continue
        if k[:-1] == key_wo_device and k[-1] in (device_kind, None):
            return True
    return False


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--out", default=OUT)
    ap.add_argument("--iters", type=int, default=None,
                    help="override the per-shape scan length (debug only; "
                         "default: _shape_plan governs)")
    ap.add_argument("--force", action="store_true",
                    help="re-measure shapes already covered for this "
                         "device_kind")
    ap.add_argument("--stall-timeout", type=int, default=1200,
                    help="seconds without a completed combo before the "
                         "watchdog flushes measured shapes and exits (a "
                         "dead-device fetch hangs in C++ where signals "
                         "never run; cf. bench.py run_child)")
    args = ap.parse_args()

    # Arm the watchdog BEFORE any device touch: jax backend init and the
    # rtt probe themselves hang on a dead device, inside C++ where
    # signal handlers never run, and a watchdog started after them would
    # never start at all.
    rows = []
    backend = [None]
    kind_box = [None]
    last_beat = [time.monotonic()]

    def _watchdog():
        while True:
            time.sleep(30)
            if time.monotonic() - last_beat[0] > args.stall_timeout:
                print(f"# WATCHDOG: no combo finished in "
                      f"{args.stall_timeout}s - flushing "
                      f"{len(rows)} shapes and exiting", flush=True)
                _merge_write(args.out, rows, backend[0], kind_box[0])
                os._exit(3)

    import threading
    threading.Thread(target=_watchdog, daemon=True).start()

    import jax
    from deepspeed_tpu.ops.attention import flash as F
    from deepspeed_tpu.utils.platform import enable_compile_cache
    enable_compile_cache()
    backend[0] = jax.default_backend()
    kind_box[0] = device_kind = _device_kind()
    print(f"# backend: {backend[0]} device_kind: {device_kind} "
          "(results are only meaningful on tpu)")
    rtt = _rtt()
    print(f"# rtt: {rtt*1e3:.2f} ms")
    last_beat[0] = time.monotonic()

    existing = []
    try:
        with open(args.out) as f:
            existing = json.load(f)
    except (OSError, ValueError):
        pass
    # stamp legacy entries even if every shape below is already covered
    if backend[0] == "tpu":
        _merge_write(args.out, [], backend[0], device_kind)

    # ---- banded sparse walk first: it feeds the scored bench row ----
    for S, fb, win in BANDED_SHAPES:
        key_wo = ("banded", S, fb, win // 2, False)
        if not args.force and _covered(existing, key_wo, device_kind):
            print(f"# banded S={S} fb={fb} already covered - skip")
            continue
        results = {}
        for bq, bk in BANDED_COMBOS:
            if S % bq or S % bk:
                continue
            try:
                dt = time_banded_combo(S, fb, win, bq, bk, rtt,
                                       iters=args.iters)
                results[(bq, bk)] = dt
                print(f"banded S={S} fb={fb} bq={bq} bk={bk}: "
                      f"{dt*1e3:.2f} ms", flush=True)
            except Exception as e:
                print(f"banded S={S} fb={fb} bq={bq} bk={bk}: "
                      f"FAILED {type(e).__name__}", flush=True)
            last_beat[0] = time.monotonic()
        if not results:
            continue
        (bq, bk), dt = min(results.items(), key=lambda kv: kv[1])
        print(f"--> best banded (S={S}, fb={fb}): bq={bq} bk={bk} "
              f"{dt*1e3:.2f} ms", flush=True)
        rows.append({"kind": "banded", "seq": S, "fine_block": fb,
                     "band_w": win // 2, "causal": False,
                     "bq": bq, "bk": bk, "ms": round(dt * 1e3, 3),
                     "backend": backend[0], "device_kind": device_kind})
        # incremental: each finished shape lands immediately, so a later
        # lost device costs only the in-flight shape
        _merge_write(args.out, rows, backend[0], device_kind)

    # ---- masked (unified-kernel) dense/causal shape classes: the
    # DEFAULT training path sweeps before the legacy flash oracle ----
    for sq, sk, d, gqa in FLASH_SHAPES:
        stream = F._use_stream(sq, sk)
        key_wo = ("masked", sq, sk, d, stream)
        if gqa != 1:
            continue          # the masked table is GQA-agnostic (square
            # walk tiles; kv delivery is the same row select)
        if not args.force and _covered(existing, key_wo, device_kind):
            print(f"# masked ({sq},{sk},{d}) already covered - skip")
            continue
        results = {}
        for b in CANDIDATES:
            if sq % b or sk % b or (stream and b % 128):
                continue
            try:
                dt = time_masked_combo(sq, sk, d, b, rtt,
                                       iters=args.iters)
                results[b] = dt
                print(f"masked S=({sq},{sk}) d={d} stream={stream} "
                      f"b={b}: {dt*1e3:.2f} ms", flush=True)
            except Exception as e:
                print(f"masked S=({sq},{sk}) d={d} b={b}: "
                      f"FAILED {type(e).__name__}", flush=True)
            last_beat[0] = time.monotonic()
        if not results:
            continue
        b, dt = min(results.items(), key=lambda kv: kv[1])
        print(f"--> best masked ({sq},{sk},{d}): b={b} "
              f"{dt*1e3:.2f} ms", flush=True)
        rows.append({"kind": "masked", "seq_q": sq, "seq_k": sk, "d": d,
                     "stream": stream, "b": b, "ms": round(dt * 1e3, 3),
                     "backend": backend[0], "device_kind": device_kind})
        _merge_write(args.out, rows, backend[0], device_kind)

    # ---- flash shape classes (legacy oracle kernels) ----
    for sq, sk, d, gqa in FLASH_SHAPES:
        stream = F._use_stream(sq, sk)
        key_wo = ("flash", sq, sk, d, stream, gqa)
        if not args.force and _covered(existing, key_wo, device_kind):
            print(f"# flash ({sq},{sk},{d},gqa{gqa}) already covered - "
                  "skip")
            continue
        combos = [
            (bq, bk) for bq in CANDIDATES for bk in CANDIDATES
            if sq % bq == 0 and sk % bk == 0
            # streamed tiles put the block width in the DMA lane dim
            and (not stream or (bq % 128 == 0 and bk % 128 == 0))
        ]
        results = {}
        for bq, bk in combos:
            try:
                dt = time_combo(sq, sk, d, bq, bk, rtt, iters=args.iters,
                                gqa=gqa)
                results[(bq, bk)] = dt
                print(f"S=({sq},{sk}) d={d} gqa={gqa} stream={stream} "
                      f"bq={bq} bk={bk}: {dt*1e3:.2f} ms", flush=True)
            except Exception as e:  # combo may not compile (VMEM, Mosaic)
                print(f"S=({sq},{sk}) d={d} gqa={gqa} bq={bq} bk={bk}: "
                      f"FAILED {type(e).__name__}", flush=True)
            last_beat[0] = time.monotonic()
        if not results:
            continue
        (bq, bk), dt = min(results.items(), key=lambda kv: kv[1])
        default = F._pick_blocks(sq, sk)   # heuristic, table not loaded
        print(f"--> best ({sq},{sk},{d},gqa{gqa}): bq={bq} bk={bk} "
              f"{dt*1e3:.2f} ms (heuristic would pick {default})",
              flush=True)
        rows.append({"seq_q": sq, "seq_k": sk, "d": d, "stream": stream,
                     "gqa": gqa, "bq": bq, "bk": bk,
                     "ms": round(dt * 1e3, 3), "backend": backend[0],
                     "device_kind": device_kind})
        _merge_write(args.out, rows, backend[0], device_kind)

    if backend[0] != "tpu":
        print("# not on TPU - NOT writing the table")
        return
    _merge_write(args.out, rows, backend[0], device_kind)
    print(f"# wrote/merged {len(rows)} entries into {args.out}")


if __name__ == "__main__":
    main()
