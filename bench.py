"""Benchmark ladder: JSON rows on stdout, headline LAST.

Rows are streamed the moment they complete AND re-emitted at the end in
canonical order (headline last), so a metric may appear twice —
consumers key on metric name and take the LAST occurrence. The final
line is always the headline (value row or explicit error row).

Metrics (BASELINE.md rows):
- comm_wire_bytes_per_step : HARDWARE-FREE — per-rank wire bytes of the
  qgZ two-hop quantized gradient allreduce at W=8 for a 1M-element
  gradient, counted from the partitioned HLO on a forced 8-device CPU
  mesh (same accounting as tests/unit/test_hlo_quantized_comm.py);
  vs_baseline = quantized / dense-bf16-ring ratio (acceptance: <= 0.6)
- comm_overlap_structure : HARDWARE-FREE — structural compute/comm
  overlap of the comm_autotune fused step: fraction of grad-exchange
  collectives in the scan body whose operand cone is dot-general-free
  (data-independent of the iteration's compute -> schedulable under it;
  serial = 0, overlapped = 1), counted from the partitioned HLO on the
  forced 8-device CPU mesh; vs_baseline = modeled overlapped/serial
  step time from the comm_autotune cost model
- mfu_cost_model : HARDWARE-FREE — XLA cost-analysis FLOPs/token of the
  compiled GPT-2 micro-step (the same record the observability layer's
  flops profiler writes per run), on the forced 8-device CPU mesh;
  vs_baseline = cost-model / analytic (6N + 12LSH) FLOPs ratio — a
  drift guard on the MFU accounting both bench rows and per-run MFU
  telemetry rely on
- host_dispatch_overhead : HARDWARE-FREE — compiled-program dispatches
  and forced host syncs per train_batch at gas=4, counted by the
  observability CompileTracker on the forced 8-device CPU mesh — pins
  the async-pipeline contract (1 fused dispatch/step, 0 steady-state
  syncs); vs_baseline = fused dispatches / the per-micro loop's gas
- decode_throughput : HARDWARE-FREE — serving tokens/s of the inference
  engine's bucketed KV-cache decode on a tiny GPT-2 (CPU), after bucket
  warmup; pins the serving contract of 0 steady-state recompiles (the
  CompileTracker count is in detail and must be 0); vs_baseline =
  cached decode tokens/s / a no-cache full-forward-per-token loop at
  the same batch size (isolates the KV-cache payoff from batching)
- paged_kv_occupancy : HARDWARE-FREE — serving-capacity payoff of the
  paged KV cache on a mixed-length workload at EQUAL cache HBM budget:
  value = peak live tokens in flight per cache KiB for the paged
  engine, vs_baseline = that density / the dense slot x max_len
  engine's (acceptance: >= 2x); detail carries both engines' decode
  tokens/s, peak concurrency, prefix hit rate, and the paged engine's
  0-steady-state-recompile pin under the mixed-length churn
- paged_decode_bytes : HARDWARE-FREE — serving-BANDWIDTH payoff of the
  fused Pallas paged-decode kernel (ops/attention/paged.py): the
  compiled pallas decode program is audited gather-free (no
  max_len-sized stripe materialization; the gather fallback's program
  shows the per-layer stripe gather as the contrast), and a bytes-read
  cost model (live pages streamed vs the full table-width stripe, the
  mfu_cost_model pattern) prices the mixed-length reference workload:
  value = modeled pallas KiB/decode-step, vs_baseline = stripe bytes /
  pallas bytes (ISSUE 8 acceptance: >= 2x reduction)
- masked_flash_flops_bytes : HARDWARE-FREE — mask-proportional work of
  the ONE unified flash kernel (ops/attention/masked_flash.py): cost-
  model FLOPs and K/V stream bytes for a dense BlockMask vs the BigBird
  reference layout at S=8192 (H=16, D=64, fine block 128), structurally
  pinned against the CSR metadata the kernel walks and a small
  interpret-mode oracle run; value = modeled BigBird K/V KiB/fwd,
  vs_baseline = dense/BigBird K/V bytes (ISSUE 11 acceptance: >= 2.5x,
  BigBird <= 40% of dense bytes in detail)
- sparse_attn_speedup_v2 : TPU — the r01 1.066x sparse config
  (BSLongformer block=128 win=3 @ S=8192) re-measured through the
  UNIFIED masked kernel (banded structure walks coarse MXU tiles,
  fine bits in register predicates); sparse_attention_speedup_s8k now
  pins the LEGACY dispatch at the same geometry, so the pair A/Bs the
  kernels on hardware (next window)
- serve_trace_overhead : HARDWARE-FREE — cost of the request-granular
  serving observability plane (inference/tracing.py): the identical
  mixed-length continuous-batching workload runs with tracing OFF and
  with tracing ON at the DEFAULT config (full lifecycle trail into
  events.jsonl, per-token TBT sampling, decode-window rows at the
  default 1/16 stride), both engines carrying the baseline event log;
  the compiled program set and per-step dispatch counts must be
  IDENTICAL (tracing is host-side by construction, so with equal
  dispatches any wall delta IS host gap), steady-state recompiles 0
  for both, greedy outputs bitwise equal; value = wall-clock overhead
  percent (min-of-5 interleaved runs), acceptance <= 5%;
  vs_baseline = traced tokens/s / untraced tokens/s
- async_ckpt_stall_ms : HARDWARE-FREE — step-loop stall per global batch
  when a checkpoint save rides every step, async (snapshot-and-return,
  background writer commits) vs blocking, at EQUAL checkpoint size on
  the forced 8-device CPU mesh: value = async stall ms/step (loop wall
  minus a no-save baseline), vs_baseline = async stall / blocking stall
  (ISSUE 10 acceptance: <= 0.20); detail pins dispatches/train_batch
  unchanged at 1.0 for both modes and the newest async tag
  COMMITTED+VERIFIED after the drain
- spec_decode_accepted_per_dispatch : HARDWARE-FREE — speculative
  multi-token decoding on the paged pool (ISSUE 13): a repetitive
  workload (prompts the host-side n-gram drafter can actually predict)
  runs spec OFF vs spec ON at the same config/seed; value = verified-
  and-kept tokens emitted per decode-phase dispatch with speculation
  (acceptance >= 2.0), vs_baseline = spec dispatches / baseline
  dispatches (< 1.0 — fewer device round-trips for the same tokens);
  pins greedy outputs bitwise equal and 0 steady-state recompiles for
  both engines
- disagg_dispatch_structure : HARDWARE-FREE — the disaggregated
  prefill/decode step discipline as pure dispatch ordering: a workload
  submitted in waves (so prefill and decode phases mix within single
  steps) must show every decode/verify dispatch preceding every
  prefill dispatch of its step; value = decode_first_fraction
  (acceptance == 1.0), pins greedy parity vs the interleaved engine,
  0 recompiles, and TTFT queue/prefill/handoff decomposition in the
  trail
- quant_serving_bytes : HARDWARE-FREE — serving-HBM payoff of int8
  quantization on BOTH byte levers (ISSUE 17), pure accounting vs bf16
  at the same geometry: value = bf16/int8 KV pool byte ratio
  (per-token-row fp32 scales included), vs_baseline = bf16/int8
  resident weight byte ratio (qwZ block 256, 1-D leaves dense);
  detail cross-checks the pool ratio against the decode_read_bytes
  cost model on the mixed-length workload (acceptance: both >= 1.8x)
- quant_kv_occupancy : HARDWARE-FREE — serving-capacity payoff of the
  int8 KV pool: the paged_kv_occupancy experiment with pool dtype as
  the only variable; value = int8 engine's peak live tokens in flight
  per cache KiB, vs_baseline = that density / the bf16 pool engine's;
  pins 0 steady-state recompiles for both and carries greedy
  agreement + decode tokens/s
- paged_decode_tokens_per_s : TPU — wall-clock decode tokens/s of the
  serving engine with the compiled Pallas paged-decode kernel at a
  TPU-legal geometry (head_dim 128), vs_baseline = pallas tokens/s /
  the gather-fallback engine's at identical config; pins
  0 steady-state recompiles for both (next hardware window)
- quant_decode_tokens_per_s : TPU — wall-clock decode tokens/s of the
  FULLY quantized engine (int8-resident weights + int8 KV pool,
  dequant in-program/in-kernel) vs the unquantized engine at identical
  config; decode is KV-bandwidth-bound so the halved pool bytes should
  price into tokens/s on hardware; functional pin off-TPU (next
  hardware window)
- disagg_ttft_p95 : TPU — p95 TTFT of the disaggregated engine
  (decode-first step order, handoff queue between the phases) vs the
  interleaved engine under the same open-loop load; on a non-TPU
  backend it is a functional pin, not a perf number (next hardware
  window)
- bert_large_samples_per_s : BERT-large fused-layer training @ seq 128
  (reference: 272 samples/s on 1x V100, fastest-bert post :38-40)
- bert_onebit_samples_per_s : BERT + 1-bit Adam in the compression
  phase vs plain Adam at the same geometry (BASELINE.md ladder item 5;
  vs_baseline = onebit/adam throughput, the single-chip compression
  tax — the wire saving is pinned by the HLO audit)
- sparse_attention_speedup_s8k : block-sparse vs dense O(S^2) softmax
  attention fwd+bwd wall time @ S=8192 — the baseline the reference's
  6.3x claim uses (sparse-attention post :28-33); the unit string names
  the baseline actually measured (vanilla, or flash if the O(S^2)
  buffers don't fit), and detail.vs_flash carries the tougher
  sparse-vs-our-own-flash ratio
- gpt2_train_mfu_dropout : the 345M step with the realistic pretraining
  config (attn/resid/embd dropout 0.1 — exercises the in-kernel Pallas
  dropout path)
- gpt2_train_mfu : the headline — Megatron-GPT2 345M + ZeRO-2, bf16,
  printed last (reference hardware-efficiency headline: 52% of peak)

Architecture: the parent process NEVER touches the device — a chip
belongs to one process at a time, and a parent that had touched JAX
would hold it. Each metric runs in its own child subprocess
(`bench.py --metric NAME`), one child at a time, with a wall-clock
timeout; a hung device hangs (and then kills) one child, not the whole
ladder. Completed rows are checkpointed to a commit-keyed partial file
so a re-run resumes instead of repeating, and each failed metric is
retried after a device liveness probe. A flaky device therefore yields
N good rows + an error row for the metric that died — never a single
error line.

Timing protocol (inside each child): value-fetch completion barrier
minus the round trip of a trivial dispatch + fetch (``measure_rtt``).

MFU accounting: model flops/token = 6*N + 12*L*S*H (PaLM appendix formula);
peak = 197 TFLOP/s bf16 (TPU v5e).
"""

import json
import os
import subprocess
import sys
import threading
import time

import numpy as np

_EMIT_LOCK = threading.Lock()

# Canonical ladder order; headline last (the driver reads the final line).
# comm_wire_bytes_per_step is HARDWARE-FREE (compiled-HLO accounting on a
# virtual CPU mesh) and runs first: it lands even when the device is dead.
METRICS = [
    "comm_wire_bytes_per_step",
    "comm_overlap_structure",
    "mfu_cost_model",
    "host_dispatch_overhead",
    "decode_throughput",
    "paged_kv_occupancy",
    "paged_decode_bytes",
    "masked_flash_flops_bytes",
    "serve_trace_overhead",
    "health_overhead",
    "async_ckpt_stall_ms",
    "spec_decode_accepted_per_dispatch",
    "disagg_dispatch_structure",
    "chunked_prefill_tbt",
    "fleet_drain_goodput",
    "fleet_migration_goodput",
    "fleet_trace_overhead",
    "quant_serving_bytes",
    "quant_kv_occupancy",
    "paged_decode_tokens_per_s",
    "quant_decode_tokens_per_s",
    "disagg_ttft_p95",
    "long_prompt_prefill_tokens_per_s",
    "bert_large_samples_per_s",
    "bert_onebit_samples_per_s",
    "sparse_attention_speedup_s8k",
    "sparse_attn_speedup_v2",
    "gpt2_train_mfu_dropout",
    "gpt2_train_mfu",
]
HEADLINE = "gpt2_train_mfu"
# metrics that never touch the device: forced onto a virtual
# 8-device CPU mesh in their child, runnable with the device down
HW_FREE = {"comm_wire_bytes_per_step", "comm_overlap_structure",
           "mfu_cost_model", "host_dispatch_overhead",
           "decode_throughput", "paged_kv_occupancy",
           "paged_decode_bytes", "masked_flash_flops_bytes",
           "serve_trace_overhead", "health_overhead",
           "async_ckpt_stall_ms",
           "spec_decode_accepted_per_dispatch",
           "disagg_dispatch_structure", "chunked_prefill_tbt",
           "fleet_drain_goodput",
           "fleet_migration_goodput", "fleet_trace_overhead",
           "quant_serving_bytes", "quant_kv_occupancy"}

PARTIAL_PATH = os.environ.get(
    "BENCH_PARTIAL", "/tmp/dstpu_bench_partial.jsonl")
if os.environ.get("JAX_PLATFORMS", "").startswith("cpu") and \
        "BENCH_PARTIAL" not in os.environ:
    # forced-CPU smoke runs must not clobber the TPU ladder's checkpoint
    # (a CPU parent run once overwrote the hardware rows the stale-
    # pointer audit trail depends on)
    PARTIAL_PATH += ".cpu"
# First metric in a cold child pays the compile time; give headroom.
METRIC_TIMEOUT = int(os.environ.get("BENCH_METRIC_TIMEOUT", "1500"))
METRIC_RETRIES = int(os.environ.get("BENCH_METRIC_RETRIES", "1"))
# Hardware-free rows compile tiny programs on the CPU backend — a much
# tighter per-row budget than the device rows, so the rows that CAN
# always land do so early (the BENCH_r05 rc=124 empty-tail fix: two
# hw-free children at the full 1500s each could eat the driver's whole
# window before a single row printed).
HW_FREE_TIMEOUT = int(os.environ.get("BENCH_HW_FREE_TIMEOUT", "300"))
# Overall ladder wall-clock budget: when it runs out, remaining metrics
# become explicit error rows IMMEDIATELY and the ladder finishes with
# the headline line — completed rows are never lost to an outer
# timeout's SIGKILL. 0 disables the budget.
TIME_BUDGET = int(os.environ.get("BENCH_TIME_BUDGET", "840"))
_T_START = time.monotonic()


def _remaining_budget():
    """Seconds left in the ladder budget, or None when unbudgeted."""
    if TIME_BUDGET <= 0:
        return None
    return TIME_BUDGET - (time.monotonic() - _T_START)


def _budget_exhausted(floor=45):
    rem = _remaining_budget()
    return rem is not None and rem < floor
# Child stall watchdog: a cold model compile sends no heartbeat (the
# first train_batch call IS the compile), so the stall budget tracks
# the per-metric budget rather than
# racing it.  Control knob: excluded from the source digest (see
# _git_head's control set).
STALL_TIMEOUT = int(os.environ.get(
    "BENCH_STALL_TIMEOUT", str(max(900, METRIC_TIMEOUT - 120))))


# Stall-watchdog heartbeat, shared with the child watchdog in run_child:
# long compiles inside the scan-timing protocol beat this so a
# slow-but-alive device is not mistaken for a dead one.
_BEAT = [time.monotonic()]
# health-plane black box (utils/health.py FlightRecorder), armed by
# run_child: every _beat() lands a ring row, and the child watchdog
# dumps the ring + all-thread stacks to _flight_path() on a stall so
# the parent can salvage a postmortem instead of an empty tail
_FLIGHT = [None]


def _beat():
    _BEAT[0] = time.monotonic()
    if _FLIGHT[0] is not None:
        _FLIGHT[0].record({"event": "bench_beat",
                           "t_mono": round(time.monotonic(), 3)})


def _flight_path(metric):
    """Where the child's black box lands — deterministic per metric so
    the parent knows where to look after a kill. Control knob: excluded
    from the source digest (see _git_head's control set)."""
    return os.environ.get("BENCH_FLIGHT_PATH",
                          f"/tmp/dstpu_bench_flight_{metric}.json")


def _rtt():
    from deepspeed_tpu.utils.benchtime import measure_rtt
    return measure_rtt()


def _emit_row(row):
    with _EMIT_LOCK:
        print(json.dumps(row), flush=True)


def _emit(metric, value, unit, vs_baseline, detail):
    row = {"metric": metric, "value": value, "unit": unit,
           "vs_baseline": vs_baseline, "detail": detail}
    _emit_row(row)
    return row


def _hbm_peak_mb():
    """Child-process-wide device peak memory, recorded by each metric
    function AFTER its measurements (the device is known alive there —
    _emit itself must stay device-free: it also serves the dead-device
    error paths, where a memory_stats() call would hang in C++ past
    every watchdog). Each metric runs in its own subprocess, so this is
    the peak across everything that row measured (for the sparse row:
    incl. its vanilla/flash baselines and the S=16k detail)."""
    try:
        import jax
        stats = jax.local_devices()[0].memory_stats() or {}
        peak = stats.get("peak_bytes_in_use")
        return round(peak / 2**20, 1) if peak else None
    except Exception:
        return None


# ---------------------------------------------------------------- metrics


def bench_bert_large(on_tpu, rtt):
    import jax
    import deepspeed_tpu
    from deepspeed_tpu.models.bert import (BERT_LARGE, BertConfig,
                                           bert_mlm_loss_fn,
                                           init_bert_params)

    if on_tpu:
        cfg, batch, seq, steps = BERT_LARGE, 32, 128, 10
    else:
        cfg = BertConfig(vocab_size=512, hidden_size=64, num_layers=2,
                         num_heads=2, intermediate_size=128,
                         max_position_embeddings=128)
        batch, seq, steps = 4, 32, 2
    # BENCH_SCAN_LAYERS=1: stacked-layer scan trunk — ~num_layers x less
    # to compile (A/B knob for short hardware windows; throughput parity
    # should be confirmed on hardware before making it the default)
    if os.environ.get("BENCH_SCAN_LAYERS", "0") == "1":
        cfg = cfg._replace(scan_layers=True)

    n_dev = jax.device_count()
    params = init_bert_params(cfg, jax.random.PRNGKey(0))
    # realistic pretraining config: dropout ON (cfg defaults 0.1)
    loss_fn = bert_mlm_loss_fn(cfg, deterministic=False)
    engine, *_ = deepspeed_tpu.initialize(
        model=loss_fn, model_parameters=params,
        config={
            "train_micro_batch_size_per_gpu": max(batch // n_dev, 1),
            "gradient_accumulation_steps": 1,
            "bf16": {"enabled": True},
            "steps_per_print": 10**9,
            "zero_optimization": {"stage": 2 if n_dev > 1 else 0},
            "optimizer": {"type": "Adam", "params": {"lr": 1e-4}},
        })

    rng = np.random.RandomState(0)
    ids = rng.randint(0, cfg.vocab_size, (batch, seq)).astype(np.int32)
    labels = np.where(rng.rand(batch, seq) < 0.15, ids, -100).astype(np.int32)
    from jax.sharding import NamedSharding, PartitionSpec
    shd = NamedSharding(engine.mesh,
                        PartitionSpec("data" if n_dev > 1 else None))
    b = {"input_ids": jax.device_put(ids, shd),
         "labels": jax.device_put(labels, shd)}

    loss = engine.train_batch(iter([b]))
    np.asarray(loss)
    t0 = time.perf_counter()
    for _ in range(steps):
        loss = engine.train_batch(iter([b]))
    np.asarray(loss)
    dt = max(time.perf_counter() - t0 - rtt, 1e-9)
    sps = batch * steps / dt
    return _emit("bert_large_samples_per_s", round(sps / max(n_dev, 1), 2),
                 "samples_per_s_per_chip", round(sps / max(n_dev, 1) / 272.0, 4),
                 {"seq": seq, "batch": batch, "dropout": 0.1,
                  "step_ms": round(dt / steps * 1000, 2), "loss": float(loss),
                  "hbm_peak_mb_child": _hbm_peak_mb()})


def bench_bert_onebit(on_tpu, rtt):
    """BERT + 1-bit Adam, compression phase (BASELINE.md ladder item 5;
    reference claim: <=5x comm reduction, 3.5x e2e on 40GbE clusters —
    onebit-adam-blog-post.md:85,135). A single chip cannot show the
    cluster speedup, so this row measures the COMPRESSION TAX: 1-bit
    samples/s vs plain-Adam samples/s at the same geometry
    (vs_baseline = onebit/adam; 1.0 = compression is free). The wire
    saving itself is pinned backend-invariantly by
    test_hlo_collectives.py::test_onebit_adam_compressed_wire_traffic
    (compressed exchange <= 1/5 of the dense exchange in elements,
    1/32 in payload bytes)."""
    import jax
    import deepspeed_tpu
    from deepspeed_tpu.models.bert import (BERT_LARGE, BertConfig,
                                           bert_mlm_loss_fn,
                                           init_bert_params)

    if on_tpu:
        cfg, batch, seq, steps = BERT_LARGE, 32, 128, 10
    else:
        cfg = BertConfig(vocab_size=512, hidden_size=64, num_layers=2,
                         num_heads=2, intermediate_size=128,
                         max_position_embeddings=128)
        batch, seq, steps = 4, 32, 2
    if os.environ.get("BENCH_SCAN_LAYERS", "0") == "1":
        cfg = cfg._replace(scan_layers=True)
    n_dev = jax.device_count()
    warm = 2  # freeze_step: warmup optimizer steps before compression

    rng = np.random.RandomState(0)
    ids = rng.randint(0, cfg.vocab_size, (batch, seq)).astype(np.int32)
    labels = np.where(rng.rand(batch, seq) < 0.15, ids, -100).astype(np.int32)
    from jax.sharding import NamedSharding, PartitionSpec
    shd_spec = PartitionSpec("data" if n_dev > 1 else None)

    def make_engine(opt):
        params = init_bert_params(cfg, jax.random.PRNGKey(0))
        loss_fn = bert_mlm_loss_fn(cfg, deterministic=False)
        engine, *_ = deepspeed_tpu.initialize(
            model=loss_fn, model_parameters=params,
            config={
                "train_micro_batch_size_per_gpu": max(batch // n_dev, 1),
                "gradient_accumulation_steps": 1,
                "bf16": {"enabled": True},
                "steps_per_print": 10**9,
                # OnebitAdam requires ZeRO stage 0 (reference
                # is_zero_supported_optimizer); keep Adam comparable
                "zero_optimization": {"stage": 0},
                "optimizer": opt,
            })
        shd = NamedSharding(engine.mesh, shd_spec)
        b = {"input_ids": jax.device_put(ids, shd),
             "labels": jax.device_put(labels, shd)}
        return engine, b

    def timed_sps(engine, b, n):
        loss = engine.train_batch(iter([b]))
        np.asarray(loss)                       # compile + settle
        t0 = time.perf_counter()
        for _ in range(n):
            loss = engine.train_batch(iter([b]))
        np.asarray(loss)
        dt = max(time.perf_counter() - t0 - rtt, 1e-9)
        return batch * n / dt, float(loss)

    # -- 1-bit engine: run past freeze_step so the timed window is the
    # compression phase (the phase switch recompiles once)
    engine1, b1 = make_engine(
        {"type": "OneBitAdam",
         "params": {"lr": 1e-4, "freeze_step": warm}})
    for _ in range(warm + 1):                  # cross the phase boundary
        engine1.train_batch(iter([b1]))
    assert engine1._onebit_compression, "compression phase not reached"
    sps1, loss1 = timed_sps(engine1, b1, steps)
    distributed = bool(engine1._onebit_dist)
    # free the 1-bit engine's full state (params + master + moments +
    # error feedback) before the Adam engine allocates its own — the
    # row must not need 2x one configuration's HBM
    del engine1, b1
    _beat()

    # -- plain-Adam reference at the same geometry
    engine0, b0 = make_engine(
        {"type": "Adam", "params": {"lr": 1e-4}})
    sps0, _loss0 = timed_sps(engine0, b0, steps)

    return _emit("bert_onebit_samples_per_s",
                 round(sps1 / max(n_dev, 1), 2), "samples_per_s_per_chip",
                 round(sps1 / sps0, 4),
                 {"seq": seq, "batch": batch, "freeze_step": warm,
                  "phase": "compression",
                  "distributed": distributed,
                  "adam_samples_per_s_per_chip":
                      round(sps0 / max(n_dev, 1), 2),
                  "compression_tax": round(1.0 - sps1 / sps0, 4),
                  "loss": loss1,
                  "hbm_peak_mb_child": _hbm_peak_mb()})


def _sparse_row_geometry(on_tpu):
    """Shared r01 geometry + scan length for the TWO sparse ladder rows
    (sparse_attention_speedup_s8k = legacy dispatch,
    sparse_attn_speedup_v2 = unified kernel): the pair A/Bs the kernels
    directly, so config and timing protocol MUST stay identical — one
    definition, consumed by both."""
    if on_tpu:
        return 1, 16, 8192, 64, 32, 128, 3    # B, H, S, D, iters, block, win
    return 1, 2, 256, 16, 2, 16, 3


def _sparse_vanilla_loss(S):
    """The reference-methodology dense baseline (materialized O(S^2)
    causal softmax, bf16) both sparse rows measure against."""
    import jax
    import jax.numpy as jnp

    def vanilla_loss(q, k, v):
        sm = q.shape[-1] ** -0.5
        s_ = jnp.einsum("bhqd,bhkd->bhqk", q, k) * sm
        idx = jnp.arange(S)
        s_ = jnp.where(idx[:, None] >= idx[None, :], s_, -1e30)
        p = jax.nn.softmax(s_, axis=-1)
        o = jnp.einsum("bhqk,bhkd->bhqd", p, v)
        return jnp.sum(o.astype(jnp.float32))
    return vanilla_loss


def _sparse_scan_timed(fn, args, rtt, iters):
    """Shared scan-amortized fwd+bwd timing (utils/benchtime.py) for
    the sparse ladder rows — chained grad evals in ONE dispatch."""
    import jax
    from deepspeed_tpu.utils.benchtime import scan_grad_seconds
    sec, _n = scan_grad_seconds(jax.grad(fn, argnums=(0, 1, 2)), args,
                                rtt, start_len=iters, beat=_beat)
    return sec


def bench_sparse_attention(on_tpu, rtt):
    # Pin the LEGACY dispatch (pre-PR-11 flash + banded/hybrid/v2
    # kernels) so this row stays comparable with the r01..r05 ladder
    # history; the unified masked kernel measures through its own row
    # (sparse_attn_speedup_v2) at the identical geometry.
    from deepspeed_tpu.ops.attention import flash as _Fo
    from deepspeed_tpu.ops.sparse_attention import blocksparse as _bso
    old_masked = _bso.USE_MASKED_FLASH
    # an explicit BENCH_REF_ATTN=1 "reference" request must survive the
    # pin (ADVICE r3 #2: never misattribute the dense baseline) — only
    # the masked default is re-routed to the legacy kernels
    pin = ("flash" if _Fo.get_attention_options().kernel == "masked"
           else _Fo.get_attention_options().kernel)
    old_opts = _Fo.set_attention_options(kernel=pin)
    _bso.USE_MASKED_FLASH = False
    _bso._FN_CACHE.clear()
    try:
        return _bench_sparse_attention_legacy(on_tpu, rtt)
    finally:
        _bso.USE_MASKED_FLASH = old_masked
        _Fo._OPTIONS = old_opts
        _bso._FN_CACHE.clear()


def _bench_sparse_attention_legacy(on_tpu, rtt):
    import jax
    import jax.numpy as jnp
    from deepspeed_tpu.ops.attention.flash import flash_attention
    from deepspeed_tpu.ops.sparse_attention import (
        SparseSelfAttention, BSLongformerSparsityConfig)

    # S=8192 with both kernels DMA-streaming; the O(S) Longformer
    # layout is where block-sparse pulls ahead, and the gap widens
    # at S=16k/32k where dense pays the full O(S^2) compute (the
    # reference's 10x-longer-sequences claim). win=3 is the
    # BSLongformer class default on both sides (reference
    # sparsity_config.py:556) — 384-token window, 4.7% density at
    # S=8192; the reference's 6.3x was measured at comparable or
    # lower density (its default block=16 window is 48 tokens).
    B, H, S, D, iters, block, win = _sparse_row_geometry(on_tpu)

    key = jax.random.PRNGKey(0)
    q, k, v = (jax.random.normal(jax.random.fold_in(key, i), (B, H, S, D),
                                 jnp.bfloat16) for i in range(3))
    sp = SparseSelfAttention(BSLongformerSparsityConfig(
        num_heads=H, block=block, num_sliding_window_blocks=win))

    def dense_loss(q, k, v):
        return jnp.sum(flash_attention(q, k, v, causal=True)
                       .astype(jnp.float32))

    def sparse_loss(q, k, v):
        return jnp.sum(sp(q, k, v).astype(jnp.float32))

    def timed(fn, arrays=None, start_len=None):
        # Scan-amortized timing (_sparse_scan_timed): chained grad
        # evals in ONE dispatch, scalar result.  A per-call loop pays
        # the host's per-dispatch latency AND eagerly transfers 48MB
        # of gradients per call — at S=8192 that measured ~870ms/call
        # for a kernel whose device time is ~10ms.
        return _sparse_scan_timed(
            fn, (q, k, v) if arrays is None else arrays, rtt,
            iters if start_len is None else start_len)

    from deepspeed_tpu.utils.benchtime import NoiseFloorError
    t_dense = timed(dense_loss)
    try:
        t_sparse = timed(sparse_loss)
        from deepspeed_tpu.ops.sparse_attention import blocksparse as _bsk
        kernel = _bsk.planned_kernel(sp.get_layout(S), block)
    except NoiseFloorError:
        raise   # measurement failure, not a kernel failure: error row
    except Exception:
        # fall back to the per-triple v1 kernels rather than losing the
        # row (banded must drop too or the retry re-dispatches the very
        # kernel that failed; hybrid rides USE_SPLASH_V2).  Restore the
        # flags afterwards — a later metric in the same process must
        # not silently measure v1 (ADVICE r4).
        from deepspeed_tpu.ops.sparse_attention import blocksparse as bs
        old_v2, old_banded = bs.USE_SPLASH_V2, bs.USE_BANDED
        bs.USE_SPLASH_V2 = False
        bs.USE_BANDED = False
        bs._FN_CACHE.clear()
        try:
            t_sparse = timed(sparse_loss)
        finally:
            bs.USE_SPLASH_V2, bs.USE_BANDED = old_v2, old_banded
            bs._FN_CACHE.clear()
        kernel = "v1-fallback"
    # the reference's 6.3x headline compares sparse vs its dense O(S^2)
    # softmax attention (sparse-attention post :28-33) — mirror that
    # methodology with a bf16 materialized-scores path (the reference's
    # dense kernels are fp16; bf16 keeps the S^2 buffers inside HBM at
    # S=8192), and report sparse-vs-our-own-flash alongside in detail
    vanilla_loss = _sparse_vanilla_loss(S)

    try:
        t_vanilla = timed(vanilla_loss)
    except NoiseFloorError:
        raise   # measurement failure: error row, not a baseline switch
    except Exception:
        t_vanilla = None               # O(S^2) buffers may not fit
    # Long-context detail (reference claim: 10x longer sequences,
    # sparse-attention post :28): at 2x the row's sequence the dense
    # kernel pays O(S^2) while the Longformer walk stays O(S) — measure
    # sparse-vs-flash at S=16k as evidence the gap widens.  Best-effort:
    # a failure (VMEM, device) never costs the row.
    s16k = {}
    if on_tpu:
        try:
            S2 = 2 * S
            q2, k2, v2 = (jax.random.normal(jax.random.fold_in(key, 9 + i),
                                            (B, H, S2, D), jnp.bfloat16)
                          for i in range(3))
            # sp resolves its layout per sequence length at call time
            args2 = (q2, k2, v2)
            n2 = max(iters // 2, 1)
            t_d2 = timed(dense_loss, arrays=args2, start_len=n2)
            t_s2 = timed(sparse_loss, arrays=args2, start_len=n2)
            s16k = {"s16k_flash_ms": round(t_d2 * 1000, 2),
                    "s16k_sparse_ms": round(t_s2 * 1000, 2),
                    "s16k_vs_flash": round(t_d2 / t_s2, 3)}
        except Exception as e:
            s16k = {"s16k_error": f"{type(e).__name__}: {e}"[:120]}
    # Best-effort auxiliary layout details (shared shape with s16k: a
    # failure never costs the row). Each times the dispatcher on one
    # more layout family at this row's geometry:
    # - refdensity: the reference's OWN 6.3x-headline geometry — block
    #   16, 48-token window, ~1% density (this row's canonical config
    #   is the denser class-default 384-token window). FLOP bound ~51x
    #   vs causal-dense; static waste 8x at (128,128) walk tiles ->
    #   ~6x-vs-flash potential.
    # - bigbird: random blocks ride the hybrid banded+residual
    #   lse-merge path (hybrid.py; reference sparsity_config.py:421).
    def aux_layout_detail(prefix, sp_cfg, fb):
        if not on_tpu:
            return {}
        try:
            from deepspeed_tpu.ops.sparse_attention import (
                SparseSelfAttention as _SSA)
            from deepspeed_tpu.ops.sparse_attention import (
                blocksparse as _bsx)
            sp_x = _SSA(sp_cfg)

            def aux_loss(q, k, v):
                return jnp.sum(sp_x(q, k, v).astype(jnp.float32))

            t_x = timed(aux_loss, start_len=max(iters // 2, 1))
            out = {f"{prefix}_sparse_ms": round(t_x * 1000, 2),
                   f"{prefix}_vs_flash": round(t_dense / t_x, 3),
                   f"{prefix}_kernel": _bsx.planned_kernel(
                       sp_x.get_layout(S), fb)}
            if t_vanilla:
                out[f"{prefix}_vs_vanilla"] = round(t_vanilla / t_x, 3)
            return out
        except Exception as e:
            return {f"{prefix}_error": f"{type(e).__name__}: {e}"[:120]}

    refdensity = aux_layout_detail(
        "refdensity", BSLongformerSparsityConfig(
            num_heads=H, block=16, num_sliding_window_blocks=win), 16)
    from deepspeed_tpu.ops.sparse_attention import BigBirdSparsityConfig
    bigbird = aux_layout_detail(
        "bigbird", BigBirdSparsityConfig(
            num_heads=H, block=block, num_random_blocks=1,
            num_sliding_window_blocks=win, num_global_blocks=1), block)

    # which walk the cost model actually picked for this layout
    try:
        from deepspeed_tpu.ops.sparse_attention import blocksparse as _bs
        coarse_pick = _bs._pick_coarse_block(
            np.asarray(sp.sparsity_config.make_layout(S)), block,
            has_am=False)
    except Exception:
        coarse_pick = "unknown"

    speedup = (t_vanilla / t_sparse) if t_vanilla else t_dense / t_sparse
    unit = ("vanilla_time_over_sparse_time" if t_vanilla
            else "flash_time_over_sparse_time")
    # record the A/B knob state: with BENCH_REF_ATTN=1 the 'flash'
    # baseline is the XLA reference path below the streaming threshold
    # (ADVICE r3 #2 — never leave that attribution implicit)
    from deepspeed_tpu.ops.attention import flash as _F
    # the 6.3x reference target is vanilla-relative: a flash-relative
    # fallback ratio is not comparable to it, so report no vs_baseline
    return _emit("sparse_attention_speedup_s8k", round(speedup, 3),
                 unit, round(speedup / 6.3, 4) if t_vanilla else None,
                 {"seq": S, "heads": H, "block": block, "window_blocks": win,
                  "kernel": kernel, "coarse_block": coarse_pick,
                  # EFFECTIVE state at this row's S: above the streaming
                  # threshold flash_attention ignores the force knob
                  "ref_attn_forced": bool(
                      _F.get_attention_options().kernel == "reference"
                      and S < _F.STREAM_THRESHOLD),
                  "baseline": "vanilla" if t_vanilla else "flash",
                  "vanilla_ms": round(t_vanilla * 1000, 2) if t_vanilla else None,
                  "flash_ms": round(t_dense * 1000, 2),
                  "vs_flash": round(t_dense / t_sparse, 3),
                  "sparse_ms": round(t_sparse * 1000, 2), **s16k,
                  **refdensity, **bigbird,
                  "hbm_peak_mb_child": _hbm_peak_mb()})


def bench_masked_flash_flops_bytes(on_tpu, rtt):
    """Hardware-free row: the unified mask-parameterized flash kernel's
    work is PROPORTIONAL TO NONZERO BLOCKS (ISSUE 11 acceptance),
    pinned two independent ways (the mfu_cost_model pattern).

    (1) Cost model (masked_flash_cost): modeled MXU FLOPs and K/V
    stream bytes for a dense BlockMask vs the BigBird reference layout
    at the S=8192 ladder geometry (H=16, D=64, fine block 128, win=3,
    1 random + 1 global — the bench_sparse_attention aux config). The
    mask-proportional K/V stream is the priced quantity (q/o/lse
    traffic is S*D regardless of mask and reported separately):
    value = modeled BigBird K/V KiB per forward,
    vs_baseline = dense/BigBird K/V bytes (acceptance >= 2.5x; the
    FLOPs ratio and the BigBird<=40%-of-dense fraction ride in detail).

    (2) Structural pin: the CSR metadata the kernel actually walks has
    exactly nnz items (cost model and kernel count the same work), and
    a small interpret-mode run of the identical kernel matches the
    block-sparse oracle — the cost model prices the kernel that runs,
    not a hypothetical.
    """
    del on_tpu, rtt       # pure accounting + a tiny interpret run
    import jax
    import jax.numpy as jnp
    from deepspeed_tpu.ops.attention.flash import pick_masked_block
    from deepspeed_tpu.ops.attention.masked_flash import (
        BlockMask, masked_flash_attention, masked_flash_cost)
    from deepspeed_tpu.ops.sparse_attention import (
        BigBirdSparsityConfig, BSLongformerSparsityConfig,
        block_sparse_attention_reference)

    S, H, D, fb, win = 8192, 16, 64, 128, 3
    dense = BlockMask.dense(S, S, pick_masked_block(S, S, D))
    bird = BlockMask.from_layout(BigBirdSparsityConfig(
        num_heads=H, block=fb, num_random_blocks=1,
        num_sliding_window_blocks=win,
        num_global_blocks=1).make_layout(S), fb)
    lonf = BlockMask.from_layout(BSLongformerSparsityConfig(
        num_heads=H, block=fb,
        num_sliding_window_blocks=win).make_layout(S), fb)
    cd = masked_flash_cost(dense, 1, H, D)
    cb = masked_flash_cost(bird, 1, H, D)
    cl = masked_flash_cost(lonf, 1, H, D)
    _beat()

    # structural pin: the CSR walk counts the same work the model prices
    offs, cnts, cols, kinds = bird.csr()
    csr_ok = int(cnts.sum()) == bird.nnz == len(cols)

    # tiny interpret-mode parity spot check — same kernel, same masks
    Sp, Hp, Dp, fbp = 256, 2, 16, 16
    rng = np.random.RandomState(0)
    q, k, v = (jnp.asarray(rng.randn(1, Hp, Sp, Dp), jnp.float32) * 0.3
               for _ in range(3))
    layout_p = BigBirdSparsityConfig(
        num_heads=Hp, block=fbp, num_random_blocks=1,
        num_sliding_window_blocks=win,
        num_global_blocks=1).make_layout(Sp)
    o = masked_flash_attention(q, k, v,
                               BlockMask.from_layout(layout_p, fbp),
                               sm_scale=Dp ** -0.5, interpret=True)
    ref = block_sparse_attention_reference(q, k, v, layout_p,
                                           sm_scale=Dp ** -0.5)
    parity = float(np.abs(np.asarray(o) - np.asarray(ref)).max())
    _beat()

    kv_ratio = cd["kv_bytes"] / cb["kv_bytes"]
    return _emit(
        "masked_flash_flops_bytes", round(cb["kv_bytes"] / 1024, 2),
        "modeled_kv_kib_per_fwd", round(kv_ratio, 3),
        {"flops_ratio_dense_over_bigbird": round(
            cd["flops"] / cb["flops"], 3),
         "bigbird_frac_of_dense_kv_bytes": round(
             cb["kv_bytes"] / cd["kv_bytes"], 4),
         "bigbird_frac_of_dense_total_bytes": round(
             cb["bytes"] / cd["bytes"], 4),
         "longformer_frac_of_dense_kv_bytes": round(
             cl["kv_bytes"] / cd["kv_bytes"], 4),
         "walk_blocks": {"dense": cd["block"], "bigbird": cb["block"],
                         "longformer": cl["block"]},
         "items": {"dense": cd["items"], "bigbird": cb["items"],
                   "longformer": cl["items"]},
         "longformer_coarsened": bool(lonf.block > fb),
         "csr_items_match_nnz": bool(csr_ok),
         "interpret_parity_max_abs": round(parity, 8),
         "geometry": {"seq": S, "heads": H, "d": D, "fine_block": fb,
                      "window_blocks": win},
         "backend": jax.default_backend(),
         "source": "masked_flash_cost model + CSR structural pin + "
                   "interpret parity (hardware-free)"})


def bench_sparse_attn_speedup_v2(on_tpu, rtt):
    """TPU ladder row (next hardware window): the r01 1.066x config —
    BSLongformer block=128 win=3 at B=1 H=16 S=8192 D=64, fwd+bwd —
    re-measured through the UNIFIED masked kernel (ISSUE 11): banded
    structure walks coarsened MXU tiles with the fine bits in register
    predicates, zero mask bytes from HBM. Same protocol and baselines
    as sparse_attention_speedup_s8k (which now pins the LEGACY
    dispatch), so the two rows A/B the kernels directly. On a non-TPU
    backend this is a small functional pin (backend in detail)."""
    from deepspeed_tpu.ops.attention import flash as _F
    from deepspeed_tpu.ops.sparse_attention import blocksparse as _bs

    # this row's identity IS the unified kernel: pin it for the row's
    # duration even when a global A/B knob (BENCH_REF_ATTN /
    # BENCH_LEGACY_ATTN) re-routed the process default
    old_masked = _bs.USE_MASKED_FLASH
    old_opts = _F.set_attention_options(kernel="masked")
    _bs.USE_MASKED_FLASH = True
    _bs._FN_CACHE.clear()
    try:
        return _bench_sparse_attn_speedup_v2(on_tpu, rtt)
    finally:
        _bs.USE_MASKED_FLASH = old_masked
        _F._OPTIONS = old_opts
        _bs._FN_CACHE.clear()


def _bench_sparse_attn_speedup_v2(on_tpu, rtt):
    import jax
    import jax.numpy as jnp
    from deepspeed_tpu.ops.attention.flash import flash_attention
    from deepspeed_tpu.ops.sparse_attention import (
        SparseSelfAttention, BSLongformerSparsityConfig)
    from deepspeed_tpu.ops.sparse_attention import blocksparse as _bs

    B, H, S, D, iters, block, win = _sparse_row_geometry(on_tpu)

    key = jax.random.PRNGKey(0)
    q, k, v = (jax.random.normal(jax.random.fold_in(key, i), (B, H, S, D),
                                 jnp.bfloat16) for i in range(3))
    sp = SparseSelfAttention(BSLongformerSparsityConfig(
        num_heads=H, block=block, num_sliding_window_blocks=win))
    planned = _bs.planned_kernel(sp.get_layout(S), block)

    def dense_loss(q, k, v):
        return jnp.sum(flash_attention(q, k, v, causal=True)
                       .astype(jnp.float32))

    def sparse_loss(q, k, v):
        return jnp.sum(sp(q, k, v).astype(jnp.float32))

    vanilla_loss = _sparse_vanilla_loss(S)

    def timed(fn):
        return _sparse_scan_timed(fn, (q, k, v), rtt, iters)

    t_dense = timed(dense_loss)
    t_sparse = timed(sparse_loss)
    try:
        t_vanilla = timed(vanilla_loss)
    except Exception:
        t_vanilla = None               # O(S^2) buffers may not fit
    speedup = (t_vanilla / t_sparse) if t_vanilla else t_dense / t_sparse
    unit = ("vanilla_time_over_sparse_time" if t_vanilla
            else "flash_time_over_sparse_time")
    return _emit(
        "sparse_attn_speedup_v2", round(speedup, 3), unit,
        round(speedup / 6.3, 4) if t_vanilla else None,
        {"seq": S, "heads": H, "block": block, "window_blocks": win,
         "kernel": planned, "r01_legacy_anchor": 1.066,
         "baseline": "vanilla" if t_vanilla else "flash",
         "vanilla_ms": round(t_vanilla * 1000, 2) if t_vanilla else None,
         "flash_ms": round(t_dense * 1000, 2),
         "vs_flash": round(t_dense / t_sparse, 3),
         "sparse_ms": round(t_sparse * 1000, 2),
         "backend": jax.default_backend(),
         "hbm_peak_mb_child": _hbm_peak_mb(),
         "source": "unified masked kernel, scan-amortized fwd+bwd "
                   "wall clock"})


def gpt2_analytic_flops_per_token(n_params, num_layers, seq, hidden):
    """PaLM-appendix model FLOPs/token: 6N + 12*L*S*H (fwd+bwd; shared
    by the hardware MFU rows and the mfu_cost_model drift guard — keep
    ONE instance so a correction can't silently diverge them)."""
    return 6 * n_params + 12 * num_layers * seq * hidden


def bench_gpt2(on_tpu, rtt, dropout: float, metric: str):
    import jax
    import jax.numpy as jnp
    import deepspeed_tpu
    from deepspeed_tpu.models.gpt2 import (
        GPT2Config, count_params, gpt2_loss_fn, init_gpt2_params)

    if on_tpu:
        # GPT-2 345M: the reference baseline's stated config
        # (BASELINE.md north star: Megatron-GPT2 345M + ZeRO-2 >=45% MFU)
        cfg = GPT2Config(vocab_size=50304,  # 128-aligned vocab
                         max_position_embeddings=1024,
                         hidden_size=1024, num_layers=24, num_heads=16,
                         embd_dropout=dropout, attn_dropout=dropout,
                         resid_dropout=dropout)
        batch, seq, steps = 8, 1024, 15 if dropout == 0.0 else 10
    else:  # CPU smoke fallback
        cfg = GPT2Config(vocab_size=512, max_position_embeddings=128,
                         hidden_size=64, num_layers=2, num_heads=2,
                         embd_dropout=dropout, attn_dropout=dropout,
                         resid_dropout=dropout)
        batch, seq, steps = 4, 64, 2
    if os.environ.get("BENCH_SCAN_LAYERS", "0") == "1":
        cfg = cfg._replace(scan_layers=True)   # see bench_bert_large

    n_dev = jax.device_count()
    params = init_gpt2_params(cfg, jax.random.PRNGKey(0))
    n_params = count_params(params)
    loss_fn = gpt2_loss_fn(cfg, dtype=jnp.bfloat16,
                           deterministic=(dropout == 0.0))

    bf16_cfg = {"enabled": True}
    if os.environ.get("BENCH_MASTER_FREE", "0") == "1":
        # master-weight-free bf16 + stochastic rounding (docs/config.md):
        # A/B the fp32-master-less update (no fp32 param copy to stream
        # through HBM at the optimizer boundary; same compute path)
        bf16_cfg.update(master_weights=False, stochastic_rounding=True)
    # BENCH_ADAM8BIT=1: quantized moments — ~4x less optimizer-state
    # HBM traffic at the update boundary (A/B knob)
    opt_type = ("Adam8bit"
                if os.environ.get("BENCH_ADAM8BIT", "0") == "1"
                else "Adam")
    engine, *_ = deepspeed_tpu.initialize(
        model=loss_fn, model_parameters=params,
        config={
            "train_micro_batch_size_per_gpu": max(batch // n_dev, 1),
            "gradient_accumulation_steps": 1,
            "bf16": bf16_cfg,
            "steps_per_print": 10**9,
            "zero_optimization": {"stage": 2 if n_dev > 1 else 0},
            "optimizer": {"type": opt_type, "params": {"lr": 1e-4}},
        })

    rng = np.random.RandomState(0)
    ids = rng.randint(0, cfg.vocab_size, (batch, seq + 1)).astype(np.int32)
    from jax.sharding import NamedSharding, PartitionSpec
    b = {"input_ids": jax.device_put(
        ids, NamedSharding(engine.mesh,
                           PartitionSpec("data" if n_dev > 1 else None)))}

    loss = engine.train_batch(iter([b]))
    np.asarray(loss)  # compile + settle

    t0 = time.perf_counter()
    for _ in range(steps):
        loss = engine.train_batch(iter([b]))
    np.asarray(loss)
    dt = max(time.perf_counter() - t0 - rtt, 1e-9)

    tokens_per_s = batch * seq * steps / dt
    flops_per_token = gpt2_analytic_flops_per_token(
        n_params, cfg.num_layers, seq, cfg.hidden_size)
    tflops = tokens_per_s * flops_per_token / 1e12
    peak = 197.0 if on_tpu else 1e9
    mfu = tflops / peak / max(n_dev, 1)
    return _emit(metric, round(mfu, 4), "fraction_of_peak_bf16",
                 round(mfu / 0.52, 4),
                 {"model": f"gpt2-{n_params/1e6:.0f}M", "dropout": dropout,
                  "tokens_per_s_per_chip": round(tokens_per_s / max(n_dev, 1), 1),
                  "tflops_per_chip": round(tflops / max(n_dev, 1), 2),
                  "step_ms": round(dt / steps * 1000, 2), "loss": float(loss),
                  "hbm_peak_mb_child": _hbm_peak_mb()})


def bench_comm_wire_bytes(on_tpu, rtt):
    """Hardware-free row: per-rank DP gradient-exchange wire bytes of the
    qgZ two-hop quantized allreduce, measured from the PARTITIONED HLO
    of a >= 1M-element gradient at W=8 (the same accounting the tier-1
    audits pin, tests/unit/test_hlo_quantized_comm.py) — so the ladder
    tracks the compression ratio without a hardware window.

    value = per-rank wire bytes per step; vs_baseline = quantized /
    dense-bf16-ring ratio (< 0.6 is the ISSUE-2 acceptance bar; the
    legacy all_gather exchange scores > 2 here at W=8).
    """
    del on_tpu, rtt           # compiled-HLO accounting; no device timing
    import jax
    import jax.numpy as jnp
    from jax.sharding import PartitionSpec as P
    from deepspeed_tpu.parallel.mesh import build_mesh
    from deepspeed_tpu.runtime.quantized_collectives import (
        ALGO_ALLGATHER, ALGO_TWOHOP, quantized_allreduce_mean)
    from deepspeed_tpu.utils.hlo_audit import (
        collect_collectives_full, dense_allreduce_ring_bytes,
        wire_bytes_of)

    n = 1 << 20
    W = 8
    assert jax.device_count() >= W, \
        f"comm audit needs {W} devices (forced-cpu child env), " \
        f"got {jax.device_count()}"
    mesh = build_mesh({"data": W})

    def hlo_bytes(algo):
        def inner(x):
            return quantized_allreduce_mean(x[0], "data", algo=algo,
                                            world_size=W)
        g = jax.ShapeDtypeStruct((W, n), jnp.float32)
        txt = jax.jit(jax.shard_map(
            inner, mesh=mesh, in_specs=(P("data"),), out_specs=P(),
            check_vma=False)).lower(g).compile().as_text()
        return wire_bytes_of(collect_collectives_full(txt))

    twohop = hlo_bytes(ALGO_TWOHOP)
    _beat()
    legacy = hlo_bytes(ALGO_ALLGATHER)
    dense = dense_allreduce_ring_bytes(n, W, dtype_bytes=2)
    return _emit("comm_wire_bytes_per_step", twohop,
                 "bytes_per_rank_per_step", round(twohop / dense, 4),
                 {"elements": n, "world": W, "algo": "twohop",
                  "dense_bf16_ring_bytes": dense,
                  "legacy_allgather_bytes": legacy,
                  "legacy_vs_dense": round(legacy / dense, 3),
                  "backend": jax.default_backend(),
                  "source": "partitioned-HLO audit (hardware-free)"})


def bench_comm_overlap_structure(on_tpu, rtt):
    """Hardware-free row: structural compute/comm overlap of the
    comm_autotune fused step (ISSUE 6), from the partitioned HLO of a
    tiny quantized-comm engine on the virtual 8-device CPU mesh.

    value = fraction of grad-exchange collectives inside the scan body
    whose operand cone contains NO dot-general — i.e. they consume only
    the double-buffered carry, so the scheduler can run them under the
    iteration's compute (serial exchange scores 0, overlapped 1; the
    same dependence audit tier-1 pins in test_hlo_quantized_comm.py).
    vs_baseline = modeled overlapped/serial step time from the
    comm_autotune cost model + the program's cost-analysis FLOPs at the
    45%-MFU v5e bar (< 1.0 = overlap pays). detail carries the serial
    program's fractions (sanity: ~0), the post-scan flush count, and
    the positional interleave view (printed HLO order — NOT schedule
    order on CPU, reported for reference only).
    """
    del on_tpu, rtt           # compiled-HLO accounting; no device timing
    import jax
    import jax.numpy as jnp
    import deepspeed_tpu
    from jax.sharding import NamedSharding, PartitionSpec
    from deepspeed_tpu.profiling.flops import profile_jit_fn
    from deepspeed_tpu.runtime.comm_autotune import (LinkModel,
                                                     exchange_time_us)
    from deepspeed_tpu.utils.hlo_audit import overlap_structure

    gas, d_in, d_h = 3, 64, 256
    n_dev = jax.device_count()

    def loss_fn(params, batch, rngs=None):
        h = jnp.tanh(batch["x"] @ params["w1"])
        return jnp.mean((h @ params["w2"] - batch["y"]) ** 2)

    key = jax.random.PRNGKey(0)
    params = {"w1": jax.random.normal(key, (d_in, d_h)) * 0.1,
              "w2": jax.random.normal(key, (d_h, d_in)) * 0.1}

    def fused_hlo(overlap):
        engine, *_ = deepspeed_tpu.initialize(
            model=loss_fn, model_parameters=params,
            config={"train_micro_batch_size_per_gpu": 4,
                    "gradient_accumulation_steps": gas,
                    "steps_per_print": 10**9,
                    "quantized_comm": {"enabled": True},
                    "comm_autotune": {"enabled": True, "overlap": overlap},
                    "optimizer": {"type": "Adam", "params": {"lr": 1e-3}}})
        rs = np.random.RandomState(0)
        shd = NamedSharding(engine.mesh,
                            PartitionSpec(engine._dp_axis_entry))
        b = {"x": jax.device_put(rs.randn(4 * n_dev, d_in)
                                 .astype(np.float32), shd),
             "y": jax.device_put(rs.randn(4 * n_dev, d_in)
                                 .astype(np.float32), shd)}
        stacked = jax.device_put(
            jax.tree_util.tree_map(
                lambda x: np.stack([np.asarray(x)] * gas), b),
            engine._stacked_batch_sharding())
        assert engine._batch_path() and engine._overlap_path() == overlap
        step = engine._get_compiled_batch_step()
        txt = step.lower(engine.state, stacked).compile().as_text()
        return engine, step, (engine.state, stacked), txt

    engine, step, args, txt_o = fused_hlo(True)
    stats_o = overlap_structure(txt_o)
    _beat()
    _eng_s, _step_s, _args_s, txt_s = fused_hlo(False)
    stats_s = overlap_structure(txt_s)
    _beat()

    # modeled step-time gain: per-micro exchange time from the cost
    # model, per-micro compute time from the program's cost-analysis
    # FLOPs at the reference 45%-MFU v5e bar; the overlapped window
    # hides gas-1 of the gas exchanges under the next micro's compute
    sizes = [p.size for p in jax.tree_util.tree_leaves(params)]
    t_ex = exchange_time_us(sizes, engine.dp_world_size,
                            block=engine._quant_block,
                            algo=engine._quant_algo, link=LinkModel())
    prof = profile_jit_fn(step, args, name="fused_step")
    t_c = prof.flops / gas / (0.45 * 197e12) * 1e6   # us per micro
    serial_us = gas * (t_c + t_ex)
    overlap_us = gas * max(t_c, t_ex) + min(t_c, t_ex)
    return _emit("comm_overlap_structure",
                 round(stats_o["overlap_fraction"], 4),
                 "fraction_exchange_collectives_dot_free",
                 round(overlap_us / serial_us, 4),
                 {"gas": gas, "world": engine.dp_world_size,
                  "serial_overlap_fraction":
                      round(stats_s["overlap_fraction"], 4),
                  "flush_outside_loop": stats_o["flush_outside_loop"],
                  "serial_flush_outside_loop":
                      stats_s["flush_outside_loop"],
                  "exchange_collectives_in_body":
                      stats_o["exchange_collectives"],
                  "positional_interleaved_fraction":
                      round(stats_o["interleaved_fraction"], 4),
                  "modeled_exchange_us_per_micro": round(t_ex, 3),
                  "modeled_compute_us_per_micro_at_45pct_v5e":
                      round(t_c, 3),
                  "modeled_serial_step_us": round(serial_us, 3),
                  "modeled_overlapped_step_us": round(overlap_us, 3),
                  "backend": jax.default_backend(),
                  "source": "partitioned-HLO dependence audit + "
                            "comm_autotune cost model (hardware-free)"})


def bench_mfu_cost_model(on_tpu, rtt):
    """Hardware-free row: cost-analysis FLOPs per token of the compiled
    GPT-2 micro-step (fwd + bwd + Adam update, ZeRO-2 over the virtual
    8-device mesh) — the exact record the observability layer's flops
    profiler writes per run (deepspeed_tpu/profiling/flops.py), pinned
    here against the analytic PaLM-appendix count so a silent change in
    what the compiled program computes (lost fusion, duplicated
    backward, an optimizer graph regression) moves a checked number.

    value = cost-model FLOPs/token; vs_baseline = cost / analytic
    (6N + 12LSH) ratio — expected O(1); detail carries a projected v5e
    step time at the reference 45% MFU bar for quick mental math.
    """
    del on_tpu, rtt           # compiled-program accounting; no device timing
    import jax
    import jax.numpy as jnp
    import deepspeed_tpu
    from deepspeed_tpu.models.gpt2 import (
        GPT2Config, count_params, gpt2_loss_fn, init_gpt2_params)
    from deepspeed_tpu.profiling.flops import profile_jit_fn

    cfg = GPT2Config(vocab_size=512, max_position_embeddings=128,
                     hidden_size=64, num_layers=2, num_heads=2)
    batch, seq = 8, 64
    n_dev = jax.device_count()
    params = init_gpt2_params(cfg, jax.random.PRNGKey(0))
    n_params = count_params(params)
    loss_fn = gpt2_loss_fn(cfg, dtype=jnp.bfloat16, deterministic=True)
    engine, *_ = deepspeed_tpu.initialize(
        model=loss_fn, model_parameters=params,
        config={
            "train_micro_batch_size_per_gpu": max(batch // n_dev, 1),
            "gradient_accumulation_steps": 1,
            "bf16": {"enabled": True},
            "steps_per_print": 10**9,
            "zero_optimization": {"stage": 2 if n_dev > 1 else 0},
            "optimizer": {"type": "Adam", "params": {"lr": 1e-4}},
        })
    ids = np.random.RandomState(0).randint(
        0, cfg.vocab_size, (batch, seq + 1)).astype(np.int32)
    from jax.sharding import NamedSharding, PartitionSpec
    b = {"input_ids": jax.device_put(
        ids, NamedSharding(engine.mesh,
                           PartitionSpec("data" if n_dev > 1 else None)))}
    _beat()
    prof = profile_jit_fn(engine._get_compiled_micro_step(),
                          (engine.state, b), name="gpt2_micro_step")
    # cost_analysis flops are PER-DEVICE for the partitioned program
    # (FlopsProfile docstring), so divide by the per-device token share
    tokens = batch * seq
    tokens_per_dev = tokens / max(n_dev, 1)
    flops_per_token = prof.flops / tokens_per_dev
    analytic = gpt2_analytic_flops_per_token(
        n_params, cfg.num_layers, seq, cfg.hidden_size)
    # projected v5e step time at the reference's 45% MFU bar
    # (per-device program against the per-device peak)
    v5e_peak = 197e12
    proj_step_ms = prof.flops / (0.45 * v5e_peak) * 1e3
    return _emit("mfu_cost_model", round(flops_per_token, 1),
                 "flops_per_token_cost_model",
                 round(flops_per_token / analytic, 4),
                 {"model": f"gpt2-{n_params/1e6:.1f}M", "tokens": tokens,
                  "flops_per_step_per_device": prof.flops,
                  "bytes_accessed_per_device": prof.bytes_accessed,
                  "arithmetic_intensity": round(
                      prof.arithmetic_intensity, 3),
                  "analytic_flops_per_token": analytic,
                  "projected_v5e_step_ms_at_45pct_mfu": round(
                      proj_step_ms, 4),
                  "world": n_dev, "backend": jax.default_backend(),
                  "source": "compiled-program cost analysis "
                            "(hardware-free)"})


def bench_host_dispatch_overhead(on_tpu, rtt):
    """Hardware-free row: host-dispatch accounting of the async step
    pipeline on the virtual 8-device CPU mesh — compiled-program
    executions and forced host syncs per ``train_batch`` at gas=4,
    counted exactly by the observability CompileTracker (the same
    counters ``tools/obs_report.py`` surfaces).

    value = dispatches per train_batch on the default (fused) path —
    the async-pipeline contract is exactly 1.0; vs_baseline = fused
    dispatches / the per-micro loop's gas dispatches (0.25 at gas=4).
    detail carries the steady-state forced-sync count (contract: 0)
    and the measured host-gap time.
    """
    del on_tpu, rtt       # CompileTracker accounting; no device timing
    import tempfile
    import jax
    import jax.numpy as jnp
    import deepspeed_tpu

    gas, steps, hidden = 4, 5, 64
    n_dev = jax.device_count()

    def init_params(key):
        k1, k2 = jax.random.split(key)
        scale = 1.0 / np.sqrt(hidden)
        return {"w1": jax.random.normal(k1, (hidden, hidden),
                                        jnp.float32) * scale,
                "w2": jax.random.normal(k2, (hidden, hidden),
                                        jnp.float32) * scale}

    def loss_fn(p, batch):
        h = jnp.maximum(batch["x"] @ p["w1"], 0.0)
        return jnp.mean((h @ p["w2"] - batch["y"]) ** 2)

    obs_dir = tempfile.mkdtemp(prefix="dstpu_bench_obs_")
    engine, *_ = deepspeed_tpu.initialize(
        model=loss_fn, model_parameters=init_params(jax.random.PRNGKey(0)),
        config={
            "train_micro_batch_size_per_gpu": 2,
            "gradient_accumulation_steps": gas,
            "steps_per_print": 10**9,
            "optimizer": {"type": "Adam", "params": {"lr": 1e-3}},
            "observability": {"enabled": True, "events_dir": obs_dir,
                              "flops_profiler": False,
                              "memory_watermarks": False},
        })
    bs = 2 * n_dev
    rng = np.random.RandomState(0)

    def window():
        return iter([{"x": rng.randn(bs, hidden).astype(np.float32),
                      "y": rng.randn(bs, hidden).astype(np.float32)}
                     for _ in range(gas)])

    engine.train_batch(window())          # compile + settle
    _beat()
    tracker = engine.observability.compile_tracker
    d0, s0 = tracker.total_dispatches, engine._host_sync_count
    gaps = []
    for _ in range(steps):
        engine.train_batch(window())
        gaps.append(engine._host_gap_ms or 0.0)
    d_per_step = (tracker.total_dispatches - d0) / steps
    syncs_per_step = (engine._host_sync_count - s0) / steps
    fused = bool(engine._use_fused_batch)
    return _emit("host_dispatch_overhead", round(d_per_step, 3),
                 "dispatches_per_train_batch", round(d_per_step / gas, 4),
                 {"gas": gas, "path": "fused" if fused else "per-micro",
                  "steady_state_syncs_per_step": syncs_per_step,
                  "host_gap_ms_mean": round(sum(gaps) / len(gaps), 3),
                  "last_step_ms": round(engine._last_step_time_ms or 0.0,
                                        3),
                  "compiles": dict(tracker.counts),
                  "world": n_dev, "backend": jax.default_backend(),
                  "source": "CompileTracker dispatch accounting "
                            "(hardware-free)"})


def bench_decode_throughput(on_tpu, rtt):
    """Hardware-free row: serving decode throughput of the inference
    engine (bucketed prefill/decode + continuous batching + donated KV
    cache) on a tiny GPT-2, CPU backend.

    value = generated tokens/s across a mixed-length request burst
    after bucket warmup; vs_baseline = that rate / a no-cache
    full-forward-per-token greedy loop on the same model AT THE SAME
    BATCH as the decode slots — the ratio isolates the KV-cache
    payoff, not batching. detail pins the serving latency contract:
    ``steady_state_recompiles`` MUST be 0 — every steady-state shape
    was compiled during warmup.
    """
    del on_tpu, rtt        # CPU-only accounting + wall-clock on tiny model
    import jax
    import jax.numpy as jnp
    from deepspeed_tpu.models.gpt2 import (GPT2Config, gpt2_forward,
                                           init_gpt2_params)
    from deepspeed_tpu.inference import InferenceEngine

    cfg = GPT2Config(vocab_size=256, max_position_embeddings=128,
                     hidden_size=64, num_layers=2, num_heads=4,
                     embd_dropout=0.0, attn_dropout=0.0,
                     resid_dropout=0.0)
    params = init_gpt2_params(cfg, jax.random.PRNGKey(0))
    new_tokens = 24
    engine = InferenceEngine(cfg, params, {
        "max_batch_size": 4, "prompt_buckets": [8, 16],
        "batch_buckets": [1, 4], "max_seq_len": 128,
        "max_new_tokens": new_tokens}, dtype=jnp.float32)
    warm_programs = engine.warmup()
    _beat()

    rng = np.random.RandomState(0)
    prompts = [rng.randint(1, 256, (l,)).tolist()
               for l in (5, 8, 13, 3, 16, 7, 11, 4)]
    t0 = time.perf_counter()
    outs = engine.generate(prompts, max_new_tokens=new_tokens,
                           temperature=0.0)
    wall = time.perf_counter() - t0
    gen_tokens = sum(len(o) - len(p) for o, p in zip(outs, prompts))
    tps = gen_tokens / wall
    recompiles = engine.steady_state_recompiles
    _beat()

    # baseline: no-cache greedy loop — a full forward over a
    # fixed-length padded buffer for EVERY generated token (what
    # serving without a KV cache costs; fixed shape so the baseline
    # pays compile once, not per token). Runs at the SAME batch as the
    # engine's decode slots so the ratio isolates the KV-cache payoff,
    # not a batching difference.
    fwd = jax.jit(lambda p, ids: gpt2_forward(p, cfg, ids,
                                              dtype=jnp.float32))
    Lfix, nb = 64, 4                   # nb == engine max_batch_size
    buf = np.zeros((nb, Lfix), np.int32)
    for r, prompt in enumerate(prompts[:nb]):
        buf[r, :8] = (prompt + [1] * 8)[:8]    # uniform 8-token prompts
    buf = jnp.asarray(buf)
    cur = 7
    jax.block_until_ready(fwd(params, buf))      # compile outside timing
    t0 = time.perf_counter()
    n_base = 8
    for i in range(n_base):
        logits = fwd(params, buf)
        nxt = jnp.argmax(logits[:, cur + i], axis=-1).astype(jnp.int32)
        buf = buf.at[:, cur + i + 1].set(nxt)
    jax.block_until_ready(buf)
    base_tps = n_base * nb / (time.perf_counter() - t0)
    return _emit("decode_throughput", round(tps, 2), "tokens_per_s",
                 round(tps / base_tps, 3) if base_tps > 0 else 0.0,
                 {"requests": len(prompts), "new_tokens": new_tokens,
                  "warmup_programs": warm_programs,
                  "steady_state_recompiles": recompiles,
                  "baseline_tokens_per_s": round(base_tps, 2),
                  "slots": 4, "backend": jax.default_backend(),
                  "source": "inference engine wall clock + "
                            "CompileTracker (hardware-free)"})


def bench_paged_kv_occupancy(on_tpu, rtt):
    """Hardware-free row: paged vs dense KV cache serving capacity at
    EQUAL cache HBM budget on a mixed-length workload (tiny GPT-2,
    CPU).

    Both engines get the same cache byte budget (dense: 4 slots x
    max_len 128 + scratch; paged: the same token capacity as a page
    pool). The paged engine runs 16 decode slots over it — dense can't,
    its geometry charges every slot max_len up front. value = the paged
    engine's peak live tokens in flight per cache KiB; vs_baseline =
    that density / the dense engine's (ISSUE 7 acceptance: >= 2x on the
    mixed-length workload). detail pins `steady_state_recompiles == 0`
    for the paged engine under the mixed-length churn, and carries both
    engines' decode tokens/s so the capacity win is visibly not bought
    with throughput.
    """
    del on_tpu, rtt        # CPU-only accounting + wall clock, tiny model
    import jax
    import jax.numpy as jnp
    from deepspeed_tpu.inference import InferenceEngine, kv_cache_bytes, \
        paged_kv_bytes
    from deepspeed_tpu.models.gpt2 import GPT2Config, init_gpt2_params

    cfg = GPT2Config(vocab_size=256, max_position_embeddings=128,
                     hidden_size=64, num_layers=2, num_heads=4,
                     embd_dropout=0.0, attn_dropout=0.0,
                     resid_dropout=0.0)
    params = init_gpt2_params(cfg, jax.random.PRNGKey(0))
    max_len, new_tokens, ps = 128, 16, 16
    dense_slots = 4
    # equal budget: dense (slots+1) rows x max_len tokens == page pool
    num_pages = (dense_slots + 1) * (max_len // ps)        # 40 pages
    rng = np.random.RandomState(0)
    prompts = [rng.randint(1, 256, (l,)).tolist()
               for l in (5, 9, 14, 3, 16, 7, 12, 4, 10, 6,
                         15, 8, 5, 11, 3, 13)]

    def serve(engine):
        engine.warmup()
        _beat()
        t0 = time.perf_counter()
        outs = engine.generate(prompts, max_new_tokens=new_tokens,
                               temperature=0.0)
        wall = time.perf_counter() - t0
        gen = sum(len(o) - len(p) for o, p in zip(outs, prompts))
        return outs, gen / wall

    paged = InferenceEngine(cfg, params, {
        "max_batch_size": 16, "prompt_buckets": [8, 16],
        "batch_buckets": [1, 4, 16], "max_seq_len": max_len,
        "max_new_tokens": new_tokens,
        "paged_kv": {"page_size": ps, "num_pages": num_pages}},
        dtype=jnp.float32)
    paged_bytes = paged_kv_bytes(paged.paged_spec)
    paged_outs, paged_tps = serve(paged)
    paged_recompiles = paged.steady_state_recompiles
    paged_peak = paged.scheduler.peak_tokens_in_flight
    alloc = paged.scheduler.allocator
    seen = alloc.prefix_hit_tokens + alloc.prefix_miss_tokens
    _beat()

    dense = InferenceEngine(cfg, params, {
        "max_batch_size": dense_slots, "prompt_buckets": [8, 16],
        "batch_buckets": [1, 4], "max_seq_len": max_len,
        "max_new_tokens": new_tokens,
        "paged_kv": {"enabled": False}}, dtype=jnp.float32)
    dense_bytes = kv_cache_bytes(dense.cache_spec)
    dense_outs, dense_tps = serve(dense)
    dense_peak = dense.scheduler.peak_tokens_in_flight
    _beat()

    parity = paged_outs == dense_outs
    paged_density = paged_peak / (paged_bytes / 1024)
    dense_density = dense_peak / (dense_bytes / 1024)
    return _emit("paged_kv_occupancy", round(paged_density, 4),
                 "tokens_in_flight_per_cache_kib",
                 round(paged_density / dense_density, 3)
                 if dense_density > 0 else 0.0,
                 {"requests": len(prompts), "new_tokens": new_tokens,
                  "page_size": ps, "num_pages": num_pages,
                  "cache_bytes": {"paged": paged_bytes,
                                  "dense": dense_bytes},
                  "peak_tokens_in_flight": {"paged": paged_peak,
                                            "dense": dense_peak},
                  "decode_tokens_per_s": {"paged": round(paged_tps, 2),
                                          "dense": round(dense_tps, 2)},
                  "greedy_outputs_match_dense": bool(parity),
                  "steady_state_recompiles": paged_recompiles,
                  "prefix_hit_rate": round(
                      alloc.prefix_hit_tokens / seen, 4) if seen else 0.0,
                  "backend": jax.default_backend(),
                  "source": "inference engine scheduler accounting "
                            "(hardware-free)"})


def bench_paged_decode_bytes(on_tpu, rtt):
    """Hardware-free row: decode-BANDWIDTH payoff of the fused Pallas
    paged-attention kernel, pinned two independent ways (the
    mfu_cost_model pattern: a structural compiled-program audit plus an
    analytic cost model it cross-checks).

    (1) HLO audit: compile the serving engine's paged decode program
    with ``attn_kernel: "pallas"`` and with ``"gather"`` (CPU,
    interpret-mode kernel — the same jaxpr structure the TPU program
    partitions from) and walk both for ``gather`` instructions. The
    gather program materializes each layer's
    (rows, pages_per_seq, page_size, kv_heads * hd) stripe — a
    max_len-bounded tensor; the pallas program must contain NO gather
    that large (its pool reads are per-page dynamic slices).

    (2) Bytes-read cost model: on the mixed-length reference workload
    (the paged_kv_occupancy prompt mix mid-decode), model the K+V bytes
    one decode step reads — live pages streamed (pallas) vs the full
    table-width stripe (gather). value = modeled pallas KiB/step,
    vs_baseline = stripe/pallas (acceptance >= 2x).
    """
    del on_tpu, rtt        # CPU-only compile + accounting, tiny model
    import jax
    import jax.numpy as jnp
    from deepspeed_tpu.inference import InferenceEngine
    from deepspeed_tpu.models.gpt2 import GPT2Config, init_gpt2_params
    from deepspeed_tpu.ops.attention.paged import decode_read_bytes
    from deepspeed_tpu.utils.hlo_audit import max_gather_elems

    cfg = GPT2Config(vocab_size=256, max_position_embeddings=128,
                     hidden_size=64, num_layers=2, num_heads=4,
                     embd_dropout=0.0, attn_dropout=0.0,
                     resid_dropout=0.0)
    params = init_gpt2_params(cfg, jax.random.PRNGKey(0))
    max_len, ps, slots = 128, 16, 16
    spec_cfg = {"max_batch_size": slots, "prompt_buckets": [8, 16],
                "batch_buckets": [1, 4, 16], "max_seq_len": max_len,
                "max_new_tokens": 16}

    def decode_hlo(attn_kernel):
        eng = InferenceEngine(cfg, params, dict(
            spec_cfg, paged_kv={"page_size": ps,
                                "attn_kernel": attn_kernel}),
            dtype=jnp.float32)
        rows = eng.num_slots + 1
        pps = eng.paged_spec.pages_per_seq
        args = (eng.params, eng._cache,
                jnp.zeros((rows,), jnp.int32),
                jnp.zeros((rows,), jnp.int32),
                jnp.zeros((rows, pps), jnp.int32),
                jnp.zeros((rows, 2), jnp.uint32),
                jnp.zeros((rows,), jnp.float32))
        spec = eng.paged_spec
        hlo = jax.jit(eng._decode_paged_impl).lower(
            *args).compile().as_text()
        return hlo, spec
    hlo_pallas, spec = decode_hlo("pallas")
    _beat()
    hlo_gather, _ = decode_hlo("gather")
    _beat()

    # one layer's stripe: every table entry's page for every row
    stripe_elems = ((slots + 1) * spec.pages_per_seq * spec.kv_heads
                    * spec.page_size * spec.head_dim)
    pallas_max = max_gather_elems(hlo_pallas)
    gather_max = max_gather_elems(hlo_gather)
    pallas_gather_free = pallas_max < stripe_elems
    gather_shows_stripe = gather_max >= stripe_elems

    # mixed-length reference workload: the paged_kv_occupancy prompt mix
    # mid-decode (each request 8 tokens into its generation)
    lens = (5, 9, 14, 3, 16, 7, 12, 4, 10, 6, 15, 8, 5, 11, 3, 13)
    positions = [l + 8 for l in lens]
    pallas_bytes, gather_bytes = decode_read_bytes(
        positions, ps, spec.pages_per_seq, spec.kv_heads,
        spec.head_dim, dtype_bytes=2)          # priced at bf16 serving
    pallas_bytes *= spec.num_layers
    gather_bytes *= spec.num_layers
    reduction = gather_bytes / pallas_bytes if pallas_bytes else 0.0
    return _emit(
        "paged_decode_bytes", round(pallas_bytes / 1024, 2),
        "modeled_kib_per_decode_step",
        round(reduction, 3),
        {"pallas_gather_free": bool(pallas_gather_free),
         "gather_shows_stripe": bool(gather_shows_stripe),
         "max_gather_elems": {"pallas": int(pallas_max),
                              "gather": int(gather_max)},
         "stripe_elems_per_layer": int(stripe_elems),
         "modeled_bytes_per_step": {"pallas": int(pallas_bytes),
                                    "gather_stripe": int(gather_bytes)},
         "workload_positions": positions, "page_size": ps,
         "pages_per_seq": spec.pages_per_seq,
         "backend": jax.default_backend(),
         "source": "compiled-HLO gather audit + live-page bytes cost "
                   "model (hardware-free)"})


def bench_serve_trace_overhead(on_tpu, rtt):
    """Hardware-free row: the request-granular serving observability
    plane must be free at the dispatch level. The same mixed-length
    continuous-batching workload runs on two engines, BOTH with the
    crash-safe events.jsonl wired (the PR-5 aggregate telemetry is the
    shared baseline — its line-buffered IO is the dominant telemetry
    cost on a toy model and is not what this row prices): tracing OFF
    (``observability.serve.enabled: false``) vs tracing ON at the
    default config (full lifecycle trail, per-token TBT sampling,
    ``serve_decode_window`` rows at the default 1/16 stride,
    SLO/goodput scalars).

    Pins (ISSUE 9 acceptance): the warmup program set and per-run
    dispatch counts are IDENTICAL (tracing is host-side pure-Python by
    construction — with equal dispatches, any wall-clock delta IS host
    gap), ``steady_state_recompiles == 0`` for both, greedy outputs
    bitwise equal. value = wall overhead percent of the traced engine
    (min-of-5 interleaved runs — min, not mean, because tiny-model CPU
    wall clocks are noise-dominated); acceptance <= 5%.
    """
    del on_tpu, rtt       # host-side accounting on the CPU backend
    import shutil
    import tempfile

    import jax
    import jax.numpy as jnp
    from deepspeed_tpu.inference import InferenceEngine
    from deepspeed_tpu.models.gpt2 import GPT2Config, init_gpt2_params

    cfg = GPT2Config(vocab_size=256, max_position_embeddings=128,
                     hidden_size=64, num_layers=2, num_heads=4,
                     embd_dropout=0.0, attn_dropout=0.0,
                     resid_dropout=0.0)
    params = init_gpt2_params(cfg, jax.random.PRNGKey(0))
    new_tokens = 24
    icfg = {"max_batch_size": 4, "prompt_buckets": [8, 16],
            "batch_buckets": [1, 4], "max_seq_len": 128,
            "max_new_tokens": new_tokens}
    rng = np.random.RandomState(0)
    prompts = [rng.randint(1, 256, (length,)).tolist()
               for length in (5, 8, 13, 3, 16, 7, 11, 4)]
    tmp = tempfile.mkdtemp(prefix="dstpu_serve_trace_")

    def build(traced):
        ic = dict(icfg, events_dir=os.path.join(
            tmp, "on" if traced else "off"))
        eng = InferenceEngine(
            cfg, params, ic, dtype=jnp.float32,
            observability_config={"serve": {"enabled": traced}})
        eng.warmup()
        return eng

    eng_off = build(False)
    eng_on = build(True)
    warm_off = eng_off.compile_tracker.total_compiles
    warm_on = eng_on.compile_tracker.total_compiles
    _beat()

    def one_run(eng):
        t0 = time.perf_counter()
        outs = eng.generate(prompts, max_new_tokens=new_tokens,
                            temperature=0.0)
        return time.perf_counter() - t0, outs

    walls_off, walls_on = [], []
    outs_off = outs_on = None
    disp0_off = eng_off.compile_tracker.total_dispatches
    disp0_on = eng_on.compile_tracker.total_dispatches
    for _ in range(5):
        w, outs_off = one_run(eng_off)
        walls_off.append(w)
        w, outs_on = one_run(eng_on)
        walls_on.append(w)
        _beat()
    disp_off = eng_off.compile_tracker.total_dispatches - disp0_off
    disp_on = eng_on.compile_tracker.total_dispatches - disp0_on
    gen_tokens = sum(len(o) - len(p) for o, p in zip(outs_off, prompts))
    tps_off = gen_tokens / min(walls_off)
    tps_on = gen_tokens / min(walls_on)
    overhead_pct = (min(walls_on) - min(walls_off)) / min(walls_off) * 100
    state = eng_on.debug_state()
    eng_on.close()
    events_path = os.path.join(tmp, "on", "events.jsonl")
    trail_rows = sum(1 for _ in open(events_path)) \
        if os.path.exists(events_path) else 0
    row = _emit(
        "serve_trace_overhead", round(overhead_pct, 2),
        "pct_wall_overhead",
        round(tps_on / tps_off, 3) if tps_off > 0 else 0.0,
        {"accept_overhead_pct": 5.0,
         "tokens_per_s_off": round(tps_off, 2),
         "tokens_per_s_on": round(tps_on, 2),
         "dispatches_off": disp_off, "dispatches_on": disp_on,
         "dispatch_delta": disp_on - disp_off,
         "warmup_programs_off": warm_off,
         "warmup_programs_on": warm_on,
         "steady_state_recompiles_off": eng_off.steady_state_recompiles,
         "steady_state_recompiles_on": eng_on.steady_state_recompiles,
         "greedy_parity": outs_on == outs_off,
         "trail_rows": trail_rows,
         "slo_attainment": state["slo"]["attainment"],
         "requests_per_run": len(prompts), "new_tokens": new_tokens,
         "backend": jax.default_backend(),
         "source": "interleaved wall clock + CompileTracker dispatch "
                   "accounting (hardware-free)"})
    shutil.rmtree(tmp, ignore_errors=True)
    return row


def bench_health_overhead(on_tpu, rtt):
    """Hardware-free row: the health plane (flight-recorder mirror tap,
    live stall watchdog, numeric detectors) must be free at the
    dispatch level. The same mixed-length continuous-batching workload
    runs on two engines: health fully OFF vs fully ON (ring tap +
    armed watchdog at a timeout the run never hits + all detectors at
    defaults).

    Pins (ISSUE 15 acceptance): per-run dispatch counts IDENTICAL
    (the plane is host-side pure-Python by construction — with equal
    dispatches, any wall delta IS host gap), ``steady_state_recompiles
    == 0`` for both, greedy outputs bitwise equal, zero health alerts
    on the healthy run. value = wall overhead percent of the enabled
    engine (min-of-5 interleaved runs); acceptance <= 2%.
    """
    del on_tpu, rtt       # host-side accounting on the CPU backend
    import shutil
    import tempfile

    import jax
    import jax.numpy as jnp
    from deepspeed_tpu.inference import InferenceEngine
    from deepspeed_tpu.models.gpt2 import GPT2Config, init_gpt2_params

    cfg = GPT2Config(vocab_size=256, max_position_embeddings=128,
                     hidden_size=64, num_layers=2, num_heads=4,
                     embd_dropout=0.0, attn_dropout=0.0,
                     resid_dropout=0.0)
    params = init_gpt2_params(cfg, jax.random.PRNGKey(0))
    new_tokens = 24
    icfg = {"max_batch_size": 4, "prompt_buckets": [8, 16],
            "batch_buckets": [1, 4], "max_seq_len": 128,
            "max_new_tokens": new_tokens}
    rng = np.random.RandomState(0)
    prompts = [rng.randint(1, 256, (length,)).tolist()
               for length in (5, 8, 13, 3, 16, 7, 11, 4)]
    tmp = tempfile.mkdtemp(prefix="dstpu_health_ovh_")

    def build(on):
        ic = dict(icfg, events_dir=os.path.join(
            tmp, "on" if on else "off"))
        # watchdog armed at a timeout the healthy run never trips, so
        # the beat path itself is part of what this row prices
        health = {"enabled": on, "stall_timeout_s": 120.0,
                  "on_stall": "warn"}
        eng = InferenceEngine(
            cfg, params, ic, dtype=jnp.float32,
            observability_config={"health": health})
        eng.warmup()
        return eng

    eng_off = build(False)
    eng_on = build(True)
    _beat()

    def one_run(eng):
        t0 = time.perf_counter()
        outs = eng.generate(prompts, max_new_tokens=new_tokens,
                            temperature=0.0)
        return time.perf_counter() - t0, outs

    walls_off, walls_on = [], []
    outs_off = outs_on = None
    disp0_off = eng_off.compile_tracker.total_dispatches
    disp0_on = eng_on.compile_tracker.total_dispatches
    for _ in range(5):
        w, outs_off = one_run(eng_off)
        walls_off.append(w)
        w, outs_on = one_run(eng_on)
        walls_on.append(w)
        _beat()
    disp_off = eng_off.compile_tracker.total_dispatches - disp0_off
    disp_on = eng_on.compile_tracker.total_dispatches - disp0_on
    gen_tokens = sum(len(o) - len(p) for o, p in zip(outs_off, prompts))
    tps_off = gen_tokens / min(walls_off)
    tps_on = gen_tokens / min(walls_on)
    overhead_pct = (min(walls_on) - min(walls_off)) / min(walls_off) * 100
    alerts_on = eng_on.health.alerts_total
    eng_on.close()
    eng_off.close()
    row = _emit(
        "health_overhead", round(overhead_pct, 2),
        "pct_wall_overhead",
        round(tps_on / tps_off, 3) if tps_off > 0 else 0.0,
        {"accept_overhead_pct": 2.0,
         "tokens_per_s_off": round(tps_off, 2),
         "tokens_per_s_on": round(tps_on, 2),
         "dispatches_off": disp_off, "dispatches_on": disp_on,
         "dispatch_delta": disp_on - disp_off,
         "steady_state_recompiles_off": eng_off.steady_state_recompiles,
         "steady_state_recompiles_on": eng_on.steady_state_recompiles,
         "greedy_parity": outs_on == outs_off,
         "health_alerts_on": alerts_on,
         "requests_per_run": len(prompts), "new_tokens": new_tokens,
         "backend": jax.default_backend(),
         "source": "interleaved wall clock + CompileTracker dispatch "
                   "accounting (hardware-free)"})
    shutil.rmtree(tmp, ignore_errors=True)
    return row


def bench_async_ckpt_stall(on_tpu, rtt):
    """Hardware-free row: the step-loop stall a checkpoint save costs
    per global batch, async vs blocking, at EQUAL checkpoint size
    (ISSUE 10). Three interleave-measured loops on the same
    model/config/seed: no-save baseline, save-every-step blocking, and
    save-every-step async (snapshot-and-return; the stage/commit
    protocol runs on the background writer while the loop keeps
    dispatching — the loop pays only the device->host snapshot).

    The stall is the wall time the step loop spends BLOCKED inside
    ``save_checkpoint`` (async: the snapshot; blocking: the whole
    stage/commit protocol) — on TPU hardware that call is the only
    part the device ever waits on. The CPU harness adds a second,
    harness-only effect the row reports separately in detail: the
    background writer's npz/CRC work shares the host cores with XLA
    compute, so the loop-wall delta (``loop_overhead_ms``) overstates
    what a device-bound run would see.

    value = async stall ms per train_batch (mean save-call wall);
    vs_baseline = async stall / blocking stall — acceptance <= 0.20.
    detail pins the async-save contract: dispatches per train_batch
    identical (1.0) in all three loops, and after the drain the newest
    async tag verifies COMMITTED (sizes + CRC32).
    """
    del on_tpu, rtt      # host wall-clock accounting; no device timing
    import shutil
    import tempfile
    import jax
    import jax.numpy as jnp
    import deepspeed_tpu
    from deepspeed_tpu.runtime import checkpoint as _ckpt

    hidden, layers, gas, steps = 512, 4, 2, 6
    n_dev = jax.device_count()

    def init_params(key):
        p = {}
        scale = 1.0 / np.sqrt(hidden)
        for i in range(layers):
            key, k = jax.random.split(key)
            p[f"w{i}"] = jax.random.normal(
                k, (hidden, hidden), jnp.float32) * scale
        return p

    def loss_fn(p, batch):
        h = batch["x"]
        for i in range(layers):
            h = jnp.maximum(h @ p[f"w{i}"], 0.0)
        return jnp.mean((h - batch["y"]) ** 2)

    bs = 2 * n_dev
    rng = np.random.RandomState(0)
    window_data = [[{"x": rng.randn(bs, hidden).astype(np.float32),
                     "y": rng.randn(bs, hidden).astype(np.float32)}
                    for _ in range(gas)] for _ in range(steps + 1)]

    def make_engine(obs_dir):
        engine, *_ = deepspeed_tpu.initialize(
            model=loss_fn,
            model_parameters=init_params(jax.random.PRNGKey(0)),
            config={
                "train_micro_batch_size_per_gpu": 2,
                "gradient_accumulation_steps": gas,
                "steps_per_print": 10**9,
                "optimizer": {"type": "Adam", "params": {"lr": 1e-3}},
                "observability": {"enabled": True, "events_dir": obs_dir,
                                  "flops_profiler": False,
                                  "memory_watermarks": False},
            })
        return engine

    tmp = tempfile.mkdtemp(prefix="dstpu_bench_ackpt_")

    def run_loop(mode):
        """One measured loop; returns (loop_wall_s, mean save-call
        stall ms, dispatches_per_step, engine, save_dir)."""
        obs_dir = os.path.join(tmp, f"obs_{mode}")
        save_dir = os.path.join(tmp, f"ckpt_{mode}")
        engine = make_engine(obs_dir)
        engine.train_batch(iter(window_data[0]))   # compile + settle
        _beat()
        tracker = engine.observability.compile_tracker
        d0 = tracker.total_dispatches
        stalls = []
        t0 = time.perf_counter()
        for s in range(steps):
            engine.train_batch(iter(window_data[s + 1]))
            if mode != "none":
                t_save = time.perf_counter()
                engine.save_checkpoint(save_dir,
                                       async_=(mode == "async"))
                stalls.append(time.perf_counter() - t_save)
        wall = time.perf_counter() - t0
        disp = (tracker.total_dispatches - d0) / steps
        stall_ms = (sum(stalls) / len(stalls) * 1e3) if stalls else 0.0
        return wall, stall_ms, disp, engine, save_dir

    wall_base, _, disp_base, eng_base, _ = run_loop("none")
    eng_base.close()
    wall_block, stall_block, disp_block, eng_block, _ = run_loop("blocking")
    eng_block.close()
    wall_async, stall_async, disp_async, eng_async, async_dir = \
        run_loop("async")
    # drain OUTSIDE the timed loop: background work must still complete
    # and commit, it just must not stall the step loop
    t_drain = time.perf_counter()
    eng_async.wait_pending_saves()
    drain_ms = (time.perf_counter() - t_drain) * 1e3
    superseded = (eng_async._ckpt_writer.superseded
                  if eng_async._ckpt_writer else 0)
    eng_async.close()

    newest = _ckpt.candidate_tags(async_dir)
    tag_ok, problems = (
        _ckpt.verify_checkpoint_dir(os.path.join(async_dir, newest[0]))
        if newest else (False, ["no committed tag"]))
    ratio = stall_async / stall_block if stall_block > 0 else 0.0
    row = _emit(
        "async_ckpt_stall_ms", round(stall_async, 3), "ms_per_step",
        round(ratio, 4),
        {"accept_ratio": 0.20,
         "stall_blocking_ms": round(stall_block, 3),
         "step_ms_baseline": round(wall_base / steps * 1e3, 3),
         # harness-only CPU contention view: loop wall minus baseline
         # (the background writer shares the host cores with XLA here;
         # on a device backend the step compute doesn't)
         "loop_overhead_ms": {
             "blocking": round(
                 max((wall_block - wall_base) / steps * 1e3, 0.0), 3),
             "async": round(
                 max((wall_async - wall_base) / steps * 1e3, 0.0), 3)},
         "dispatches_per_step": {"baseline": disp_base,
                                 "blocking": disp_block,
                                 "async": disp_async},
         "dispatch_invariant": disp_base == disp_block == disp_async,
         "drain_ms": round(drain_ms, 3),
         "saves_superseded": superseded,
         "newest_async_tag": newest[0] if newest else None,
         "newest_tag_verified": bool(tag_ok),
         "verify_problems": problems if not tag_ok else [],
         "params_mb": round(layers * hidden * hidden * 4 / 2**20, 2),
         "gas": gas, "steps": steps, "world": n_dev,
         "backend": jax.default_backend(),
         "source": "save-call wall clock (the loop's blocked time) + "
                   "no-save loop baseline + CompileTracker dispatch "
                   "accounting (hardware-free)"})
    shutil.rmtree(tmp, ignore_errors=True)
    return row


def bench_paged_decode_tokens_per_s(on_tpu, rtt):
    """TPU ladder row (next hardware window): wall-clock decode
    tokens/s of the serving engine running the COMPILED Pallas
    paged-decode kernel, vs the gather-fallback engine at identical
    config. Geometry is TPU-legal for the kernel (head_dim 128,
    page_size 16); both engines must hold 0 steady-state recompiles.
    On a non-TPU backend the kernel runs interpret mode — the row is
    then a functional pin, not a perf number (backend in detail).
    """
    del rtt
    import jax
    import jax.numpy as jnp
    from deepspeed_tpu.inference import InferenceEngine
    from deepspeed_tpu.models.gpt2 import GPT2Config, init_gpt2_params

    cfg = GPT2Config(vocab_size=256, max_position_embeddings=512,
                     hidden_size=512, num_layers=2, num_heads=4,
                     embd_dropout=0.0, attn_dropout=0.0,
                     resid_dropout=0.0)          # head_dim 128
    params = init_gpt2_params(cfg, jax.random.PRNGKey(0))
    new_tokens = 64
    dtype = jnp.bfloat16 if on_tpu else jnp.float32
    rng = np.random.RandomState(0)
    prompts = [rng.randint(1, 256, (l,)).tolist()
               for l in (5, 8, 13, 3, 16, 7, 11, 4)]

    def serve(attn_kernel):
        eng = InferenceEngine(cfg, params, {
            "max_batch_size": 8, "prompt_buckets": [16],
            "batch_buckets": [8], "max_seq_len": 256,
            "max_new_tokens": new_tokens,
            "paged_kv": {"page_size": 16,
                         "attn_kernel": attn_kernel}}, dtype=dtype)
        path = eng._decode_attn_path
        eng.warmup()
        _beat()
        t0 = time.perf_counter()
        outs = eng.generate(prompts, max_new_tokens=new_tokens,
                            temperature=0.0)
        wall = time.perf_counter() - t0
        gen = sum(len(o) - len(p) for o, p in zip(outs, prompts))
        return gen / wall, path, eng.steady_state_recompiles, outs
    pallas_tps, pallas_path, pallas_rc, pallas_outs = serve("pallas")
    gather_tps, _, gather_rc, gather_outs = serve("gather")
    _beat()
    return _emit(
        "paged_decode_tokens_per_s", round(pallas_tps, 2),
        "tokens_per_s",
        round(pallas_tps / gather_tps, 3) if gather_tps > 0 else 0.0,
        {"gather_tokens_per_s": round(gather_tps, 2),
         "decode_attn_path": pallas_path,
         "steady_state_recompiles": {"pallas": pallas_rc,
                                     "gather": gather_rc},
         "greedy_outputs_match_gather": bool(pallas_outs == gather_outs),
         "new_tokens": new_tokens, "requests": len(prompts),
         "hbm_peak_mb": _hbm_peak_mb(),
         "backend": jax.default_backend(),
         "source": "inference engine wall clock, pallas vs gather "
                   "decode"})


def bench_spec_decode_accepted_per_dispatch(on_tpu, rtt):
    """Hardware-free row: speculative multi-token decoding on the paged
    pool (ISSUE 13). The host-side n-gram drafter proposes k tokens per
    in-flight request; ONE seq-(k+1) verify dispatch through the paged
    path scores them all, and only verified-greedy-matching tokens are
    kept. On a repetitive workload (greedy decode of a tiny model falls
    into a cycle, which prompt-lookup drafting then predicts) the value
    is verified-and-kept tokens emitted per decode-phase device
    dispatch — the device round-trips actually saved.

    Pins (ISSUE 13 acceptance): value >= 2.0; greedy outputs bitwise
    equal to the non-speculative engine at the same config/seed;
    ``steady_state_recompiles == 0`` for BOTH engines (the verify
    program set is fixed at warmup); vs_baseline = spec decode-phase
    dispatches / baseline decode dispatches (< 1.0 — same tokens, fewer
    dispatches).
    """
    del on_tpu, rtt      # host accounting + CPU backend by design
    import jax
    import jax.numpy as jnp
    from deepspeed_tpu.inference import InferenceEngine
    from deepspeed_tpu.models.gpt2 import GPT2Config, init_gpt2_params

    cfg = GPT2Config(vocab_size=61, max_position_embeddings=128,
                     hidden_size=32, num_layers=2, num_heads=4,
                     embd_dropout=0.0, attn_dropout=0.0,
                     resid_dropout=0.0)
    params = init_gpt2_params(cfg, jax.random.PRNGKey(3))
    new_tokens = 24
    icfg = {"max_batch_size": 4, "prompt_buckets": [8, 16],
            "batch_buckets": [1, 4], "max_seq_len": 128,
            "max_new_tokens": new_tokens}
    # period-3 / period-4 repeated patterns: the n-gram drafter's bread
    # and butter, and short enough that greedy decode cycles quickly
    prompts = [[5, 6, 7] * 4, [9, 10, 11, 12] * 3, [1, 2] * 5,
               [20, 21, 22] * 4]

    def serve(spec_on):
        ic = dict(icfg)
        if spec_on:
            ic["spec_decode"] = {"enabled": True, "k": 4}
        eng = InferenceEngine(cfg, params, ic, dtype=jnp.float32)
        eng.warmup()
        _beat()
        d0 = dict(eng.compile_tracker.dispatch_counts)
        outs = eng.generate(prompts, max_new_tokens=new_tokens,
                            temperature=0.0)
        disp = {n: c - d0.get(n, 0)
                for n, c in eng.compile_tracker.dispatch_counts.items()}
        state = eng.debug_state()
        rc = eng.steady_state_recompiles
        eng.close()
        gen = sum(len(o) - len(p) for o, p in zip(outs, prompts))
        return outs, gen, disp, state, rc

    outs_off, gen_off, disp_off, _, rc_off = serve(False)
    outs_on, gen_on, disp_on, state_on, rc_on = serve(True)
    _beat()
    phase_off = disp_off.get("decode", 0)
    phase_on = disp_on.get("verify", 0) + disp_on.get("decode", 0)
    per_dispatch = gen_on / phase_on if phase_on else 0.0
    spec = state_on["slo"]["spec"]
    return _emit(
        "spec_decode_accepted_per_dispatch", round(per_dispatch, 3),
        "kept_tokens_per_dispatch",
        round(phase_on / phase_off, 3) if phase_off else 0.0,
        {"accept_min": 2.0,
         "greedy_parity": bool(outs_on == outs_off),
         "steady_state_recompiles": {"off": rc_off, "on": rc_on},
         "decode_dispatches_off": phase_off,
         "verify_dispatches_on": disp_on.get("verify", 0),
         "fallback_decode_dispatches_on": disp_on.get("decode", 0),
         "drafted": spec["proposed"], "accepted": spec["accepted"],
         "accept_rate": spec["accept_rate"],
         "generated_tokens": gen_on,
         "baseline_tokens": gen_off,
         "backend": jax.default_backend(),
         "source": "CompileTracker dispatch accounting, spec on/off "
                   "(hardware-free)"})


def bench_disagg_dispatch_structure(on_tpu, rtt):
    """Hardware-free row: the disaggregated serving step discipline as
    pure dispatch ordering. Requests are submitted in waves while
    earlier ones still decode, so single engine steps mix the decode
    phase (handoff claims + decode/verify dispatch) with the prefill
    phase. The structural guarantee — no decode dispatch ever waits
    behind a prefill dispatch — is then checkable without a clock:
    within every step of the dispatch trace, all decode-phase ordinals
    precede all prefill ordinals.

    Pins (ISSUE 13 acceptance): value = decode_first_fraction over
    steps that mixed both phases, acceptance == 1.0, and the trace must
    actually contain mixed steps; greedy outputs bitwise equal to the
    interleaved (non-disagg) engine; 0 steady-state recompiles; every
    handoff claimed (queue drains).
    """
    del on_tpu, rtt
    import jax
    import jax.numpy as jnp
    from deepspeed_tpu.inference import InferenceEngine, Request
    from deepspeed_tpu.models.gpt2 import GPT2Config, init_gpt2_params

    cfg = GPT2Config(vocab_size=61, max_position_embeddings=128,
                     hidden_size=32, num_layers=2, num_heads=4,
                     embd_dropout=0.0, attn_dropout=0.0,
                     resid_dropout=0.0)
    params = init_gpt2_params(cfg, jax.random.PRNGKey(3))
    new_tokens = 12
    icfg = {"max_batch_size": 3, "prompt_buckets": [8, 16],
            "batch_buckets": [1, 2], "max_seq_len": 64,
            "max_new_tokens": new_tokens}
    rng = np.random.RandomState(7)
    waves = [[rng.randint(1, 61, (l,)).tolist() for l in lens]
             for lens in ((5, 9, 3), (12, 4), (7, 15, 6))]

    def serve(disagg_on):
        ic = dict(icfg)
        if disagg_on:
            ic["disagg"] = {"enabled": True}
        eng = InferenceEngine(cfg, params, ic, dtype=jnp.float32)
        eng.warmup()
        _beat()
        done = {}
        pending = list(waves)
        uid2prompt = {}
        while pending or not eng.scheduler.idle():
            if pending:
                # next wave lands while the previous one still decodes:
                # the admitting step runs prefill AND decode phases
                for p in pending.pop(0):
                    uid = eng.submit(Request(
                        prompt=p, max_new_tokens=new_tokens,
                        temperature=0.0, seed=0))
                    uid2prompt[uid] = tuple(p)
            for f in eng.step():
                done[uid2prompt[f.uid]] = f.tokens
        state = eng.debug_state()
        rc = eng.steady_state_recompiles
        eng.close()
        return done, state, rc

    base_done, _, base_rc = serve(False)
    dis_done, dis_state, dis_rc = serve(True)
    _beat()
    dg = dis_state["disagg"]
    frac = dg["decode_first_fraction"]
    return _emit(
        "disagg_dispatch_structure",
        round(frac, 4) if frac is not None else -1.0,
        "decode_first_fraction", 1.0 if dis_done == base_done else 0.0,
        {"accept_fraction": 1.0,
         "mixed_steps_traced": frac is not None,
         "greedy_parity": bool(dis_done == base_done),
         "steady_state_recompiles": {"interleaved": base_rc,
                                     "disagg": dis_rc},
         "handoffs": dg["queue"]["handoffs"],
         "handoff_queue_drained": dg["queue"]["depth"] == 0,
         "requeues": dg["queue"]["requeues"],
         "requests": sum(len(w) for w in waves),
         "backend": jax.default_backend(),
         "source": "DispatchTrace step ordering, disagg vs interleaved "
                   "(hardware-free)"})


def bench_fleet_drain_goodput(on_tpu, rtt):
    """Hardware-free row: serve THROUGH a replica preemption. The same
    mixed-length workload runs twice over a 3-replica FleetRouter —
    once undisturbed, once with replica 0 drained mid-run (its queued
    requests redistribute to survivors, in-flight requests finish where
    they are). Pins (ISSUE 14 acceptance): zero dropped responses
    (every submitted uid answers in both runs), greedy outputs bitwise
    identical with and without the drain, zero steady-state recompiles
    on every replica, and goodput (tokens/s over the serve window)
    degrades boundedly rather than collapsing — value is the
    drained/undrained goodput ratio.
    """
    del on_tpu, rtt
    import jax
    import jax.numpy as jnp
    from deepspeed_tpu.inference import (FleetRouter, InferenceEngine,
                                         Request)
    from deepspeed_tpu.models.gpt2 import GPT2Config, init_gpt2_params

    cfg = GPT2Config(vocab_size=61, max_position_embeddings=64,
                     hidden_size=32, num_layers=2, num_heads=4,
                     embd_dropout=0.0, attn_dropout=0.0,
                     resid_dropout=0.0)
    params = init_gpt2_params(cfg, jax.random.PRNGKey(3))
    new_tokens = 8
    icfg = {"max_batch_size": 2, "prompt_buckets": [8, 16],
            "batch_buckets": [1, 2], "max_seq_len": 48,
            "max_new_tokens": new_tokens}
    rng = np.random.RandomState(11)
    prompts = [rng.randint(1, 61, (l,)).tolist()
               for l in (5, 9, 3, 12, 4, 7, 15, 6, 8, 10, 5, 13)]

    def serve(do_drain):
        engines = []
        for _ in range(3):
            eng = InferenceEngine(cfg, params, dict(icfg),
                                  dtype=jnp.float32)
            eng.warmup()
            _beat()
            engines.append(eng)
        router = FleetRouter(engines)
        uids = [router.submit(Request(prompt=p,
                                      max_new_tokens=new_tokens,
                                      temperature=0.0, seed=0))
                for p in prompts]
        t0 = time.perf_counter()
        fins = router.step()
        if do_drain:
            router.drain(0, reason="bench")
        fins.extend(router.run())
        wall = time.perf_counter() - t0
        tokens = sum(len(f.tokens) for f in fins)
        by_uid = {f.uid: f.tokens for f in fins}
        # ordered by submission, so the two runs compare positionally
        # (uids are process-global and differ between runs)
        outs = [by_uid.get(u) for u in uids]
        rc = [e.steady_state_recompiles for e in engines]
        redistributed = router.total_redistributed
        router.close()
        return (outs, tokens / wall if wall > 0 else 0.0,
                rc, redistributed)

    base_out, base_gp, base_rc, _ = serve(False)
    drain_out, drain_gp, drain_rc, redistributed = serve(True)
    _beat()
    dropped = base_out.count(None) + drain_out.count(None)
    parity = base_out == drain_out
    ratio = drain_gp / base_gp if base_gp > 0 else 0.0
    # bounded degradation: a drain costs re-prefill of the redistributed
    # queue, never an order of magnitude (the loose floor keeps the pin
    # meaningful without making a CPU-timing row flaky)
    ok = parity and dropped == 0 and all(r == 0 for r in base_rc + drain_rc) \
        and ratio >= 0.1
    return _emit(
        "fleet_drain_goodput", round(ratio, 4),
        "drained/undrained_goodput_ratio", 1.0 if ok else 0.0,
        {"undrained_tokens_per_s": round(base_gp, 2),
         "drained_tokens_per_s": round(drain_gp, 2),
         "dropped_responses": dropped,
         "greedy_parity": parity,
         "redistributed": redistributed,
         "steady_state_recompiles": {"undrained": base_rc,
                                     "drained": drain_rc},
         "requests": len(prompts), "replicas": 3,
         "backend": jax.default_backend(),
         "source": "FleetRouter 3 replicas, drain replica 0 mid-run "
                   "vs undisturbed (hardware-free)"})


def bench_fleet_migration_goodput(on_tpu, rtt):
    """Hardware-free row: serve through a replica KILL with live
    KV-page migration (ISSUE 16). The same mixed-length greedy
    workload runs twice over a 3-replica FleetRouter of
    migration-warmed engines — once undisturbed, once with replica 0
    yanked mid-run, its in-flight requests' live pages exported and
    imported into survivors (decode resumes at the same
    cache_position, no re-prefill). Pins: zero dropped responses,
    greedy outputs bitwise identical with and without the kill, at
    least one live migration actually happened, zero steady-state
    recompiles on the survivors (import runs through the
    warmup-compiled programs), and goodput holds >= 0.90 of the
    undisturbed run — migration moves pages, not re-decodes tokens.
    """
    del on_tpu, rtt
    import jax
    import jax.numpy as jnp
    from deepspeed_tpu.inference import (FleetRouter, InferenceEngine,
                                         Request)
    from deepspeed_tpu.models.gpt2 import GPT2Config, init_gpt2_params

    cfg = GPT2Config(vocab_size=61, max_position_embeddings=64,
                     hidden_size=32, num_layers=2, num_heads=4,
                     embd_dropout=0.0, attn_dropout=0.0,
                     resid_dropout=0.0)
    params = init_gpt2_params(cfg, jax.random.PRNGKey(3))
    new_tokens = 16
    icfg = {"max_batch_size": 2, "prompt_buckets": [8, 16],
            "batch_buckets": [1, 2], "max_seq_len": 48,
            "max_new_tokens": new_tokens}
    rng = np.random.RandomState(13)
    # 4 requests over 3 replicas x 2 slots: the survivors hold free
    # decode slots at kill time — an import needs one (a full target
    # falls back to redistribute-and-re-decode, which is the OTHER
    # row's regime)
    prompts = [rng.randint(1, 61, (l,)).tolist()
               for l in (5, 9, 3, 12)]

    def serve(do_kill):
        engines = []
        for _ in range(3):
            eng = InferenceEngine(cfg, params, dict(icfg),
                                  dtype=jnp.float32)
            eng.warmup()
            eng.warm_migration()
            _beat()
            engines.append(eng)
        router = FleetRouter(engines)
        uids = [router.submit(Request(prompt=p,
                                      max_new_tokens=new_tokens,
                                      temperature=0.0, seed=0))
                for p in prompts]
        t0 = time.perf_counter()
        fins = router.step()
        fins.extend(router.step())   # decode underway fleet-wide
        if do_kill:
            router.drain(0, reason="kill")
        fins.extend(router.run())
        wall = time.perf_counter() - t0
        tokens = sum(len(f.tokens) for f in fins)
        by_uid = {f.uid: f.tokens for f in fins}
        outs = [by_uid.get(u) for u in uids]
        # survivors only: the killed replica's programs are gone with it
        rc = [e.steady_state_recompiles for e in engines[1:]]
        migrated = router.total_migrated
        mig_bytes = router.migration_bytes
        router.close()
        return (outs, tokens / wall if wall > 0 else 0.0,
                rc, migrated, mig_bytes)

    base_out, base_gp, base_rc, _, _ = serve(False)
    kill_out, kill_gp, kill_rc, migrated, mig_bytes = serve(True)
    _beat()
    dropped = base_out.count(None) + kill_out.count(None)
    parity = base_out == kill_out
    ratio = kill_gp / base_gp if base_gp > 0 else 0.0
    ok = parity and dropped == 0 and migrated >= 1 \
        and all(r == 0 for r in base_rc + kill_rc) and ratio >= 0.90
    return _emit(
        "fleet_migration_goodput", round(ratio, 4),
        "killed/undisturbed_goodput_ratio", 1.0 if ok else 0.0,
        {"undisturbed_tokens_per_s": round(base_gp, 2),
         "killed_tokens_per_s": round(kill_gp, 2),
         "dropped_responses": dropped,
         "greedy_parity": parity,
         "live_migrations": migrated,
         "migration_bytes": mig_bytes,
         "steady_state_recompiles": {"undisturbed": base_rc,
                                     "killed": kill_rc},
         "requests": len(prompts), "replicas": 3,
         "backend": jax.default_backend(),
         "source": "FleetRouter 3 migration-warmed replicas, kill "
                   "replica 0 mid-decode, live KV pages migrate to "
                   "survivors vs undisturbed (hardware-free)"})


def bench_fleet_trace_overhead(on_tpu, rtt):
    """Hardware-free row: the cross-process tracing plane (ISSUE 18)
    must be free at the dispatch level. The same mixed greedy/seeded
    workload runs over two 2-replica PROCESS fleets — tracing fully
    OFF (serve tracer disabled in every child, no router event log)
    vs fully ON (router trace-id stamping + ``fleet_dispatch`` rows,
    per-child serve trails into per-replica ``events.jsonl``,
    ``clock_sync`` ping rows). The children report their
    CompileTracker dispatch counts through the RPC state piggyback,
    so the pin crosses the process boundary: per-run dispatch counts
    IDENTICAL (``dispatch_delta == 0`` — tracing is host-side pure
    Python on both sides of the wire), steady-state recompiles 0 on
    every replica, outputs bitwise equal between the two fleets.
    value = wall overhead percent of the traced fleet (min-of-3
    interleaved runs); acceptance <= 5%.
    """
    del on_tpu, rtt
    import shutil
    import tempfile

    from deepspeed_tpu.inference import Request
    from deepspeed_tpu.inference.fleet import (FleetRouter,
                                               launch_replica_processes)
    from deepspeed_tpu.utils.monitor import _JsonlWriter

    mcfg = {"vocab_size": 61, "max_position_embeddings": 64,
            "hidden_size": 32, "num_layers": 2, "num_heads": 4,
            "embd_dropout": 0.0, "attn_dropout": 0.0,
            "resid_dropout": 0.0}
    new_tokens = 8
    icfg = {"max_batch_size": 2, "prompt_buckets": [8, 16],
            "batch_buckets": [1, 2], "max_seq_len": 48,
            "max_new_tokens": new_tokens}
    tmp = tempfile.mkdtemp(prefix="dstpu_fleet_trace_")
    env = {"JAX_PLATFORMS": "cpu", "JAX_THREEFRY_PARTITIONABLE": "1"}
    spec = {"family": "gpt2", "model_config": mcfg, "init_seed": 3,
            "dtype": "float32", "inference": icfg}

    def build(traced):
        tag = "on" if traced else "off"
        spec_by = {}
        for i in range(2):
            if traced:
                spec_by[i] = {
                    "inference": dict(icfg, events_dir=os.path.join(
                        tmp, f"{tag}_r{i}")),
                    "observability": {"enabled": True,
                                      "serve": {"enabled": True}}}
            else:
                spec_by[i] = {"observability": {
                    "enabled": True, "serve": {"enabled": False}}}
        reps = launch_replica_processes(
            spec, 2, env_by_replica={i: dict(env) for i in range(2)},
            spec_by_replica=spec_by)
        writer = _JsonlWriter(os.path.join(tmp, f"{tag}_router")) \
            if traced else None
        router = FleetRouter(
            reps, {"process_mode": {"enabled": True}}, writer=writer)
        return router, reps, writer

    def requests(round_no):
        return [Request(prompt=[1 + u % 7, 2, 3, 4, (5 + u) % 61],
                        max_new_tokens=new_tokens,
                        temperature=0.0 if u % 2 == 0 else 0.7,
                        seed=100 + u, uid=round_no * 100 + u)
                for u in range(6)]

    def one_run(router, round_no):
        t0 = time.perf_counter()
        for r in requests(round_no):
            router.submit(r)
        fins = router.run()
        # uid mod 100 folds the per-round uid namespace back so runs
        # compare like-for-like
        return (time.perf_counter() - t0,
                {f.uid % 100: tuple(f.tokens) for f in fins})

    router_off, reps_off, _w_off = build(False)
    _beat()
    router_on, reps_on, w_on = build(True)
    _beat()
    # warm round (not timed) — also primes the dispatch-count baseline
    # via the state piggyback on each RPC reply
    one_run(router_off, 0)
    one_run(router_on, 0)
    disp0_off = sum(r.total_dispatches or 0 for r in reps_off)
    disp0_on = sum(r.total_dispatches or 0 for r in reps_on)
    walls_off, walls_on = [], []
    parity = True
    tokens = 0
    for k in range(1, 4):
        w, o_off = one_run(router_off, k)
        walls_off.append(w)
        w, o_on = one_run(router_on, k)
        walls_on.append(w)
        parity = parity and (o_on == o_off)
        tokens = sum(len(t) for t in o_off.values())
        _beat()
    disp_off = sum(r.total_dispatches or 0
                   for r in reps_off) - disp0_off
    disp_on = sum(r.total_dispatches or 0 for r in reps_on) - disp0_on
    rc = [r.steady_state_recompiles for r in reps_off + reps_on]
    overhead_pct = (min(walls_on) - min(walls_off)) \
        / min(walls_off) * 100
    router_off.close()
    router_on.close()
    if w_on is not None:
        w_on.close()
    trail_rows = 0
    for i in range(2):
        p = os.path.join(tmp, f"on_r{i}", "events.jsonl")
        if os.path.exists(p):
            trail_rows += sum(1 for _ in open(p))
    row = _emit(
        "fleet_trace_overhead", round(overhead_pct, 2),
        "pct_wall_overhead",
        round(min(walls_off) / min(walls_on), 3)
        if min(walls_on) > 0 else 0.0,
        {"accept_overhead_pct": 5.0,
         "wall_off_s": round(min(walls_off), 4),
         "wall_on_s": round(min(walls_on), 4),
         "tokens_per_run": tokens,
         "dispatches_off": disp_off, "dispatches_on": disp_on,
         "dispatch_delta": disp_on - disp_off,
         "steady_state_recompiles": rc,
         "greedy_parity": parity,
         "replica_trail_rows": trail_rows,
         "requests_per_run": 6, "new_tokens": new_tokens,
         "replicas_per_fleet": 2,
         "source": "two 2-replica process fleets, interleaved "
                   "min-of-3 wall + RPC-piggybacked CompileTracker "
                   "dispatch accounting (hardware-free)"})
    shutil.rmtree(tmp, ignore_errors=True)
    return row


def bench_quant_serving_bytes(on_tpu, rtt):
    """Hardware-free row: serving-HBM payoff of int8 quantization on
    BOTH byte levers (ISSUE 17), priced against bf16 serving at the
    same geometry — pure accounting, no wall clock.

    Weight lever: a head_dim-128 GPT-2 param tree in bf16 is qwZ
    block-quantized (block 256) and `quantized_tree_bytes` prices the
    resident int8+fp32-scale footprint against the dense bf16 bytes
    (1-D leaves stay dense by design, so the ratio honestly includes
    them). KV lever: `paged_kv_bytes` of the int8+per-row-scale pool
    vs the bf16 pool at identical page geometry, cross-checked by the
    `decode_read_bytes` cost model on the mixed-length reference
    workload (whole pages stream, so bytes/step shrinks by the same
    ratio — the decode-bandwidth payoff rides the pool dtype).
    value = the KV byte ratio; vs_baseline = the weight byte ratio
    (ISSUE 17 acceptance: BOTH >= 1.8x on top of paged).
    """
    del on_tpu, rtt        # CPU-only byte accounting, tiny model
    import jax
    import jax.numpy as jnp
    from deepspeed_tpu.inference.kv_cache import (paged_kv_bytes,
                                                  paged_spec_for)
    from deepspeed_tpu.models.gpt2 import GPT2Config, init_gpt2_params
    from deepspeed_tpu.ops.attention.paged import decode_read_bytes
    from deepspeed_tpu.runtime.quantized_params import (
        quantize_param_tree, quantized_tree_bytes)

    cfg = GPT2Config(vocab_size=256, max_position_embeddings=512,
                     hidden_size=512, num_layers=2, num_heads=4,
                     embd_dropout=0.0, attn_dropout=0.0,
                     resid_dropout=0.0)          # head_dim 128
    params = init_gpt2_params(cfg, jax.random.PRNGKey(0))
    params = jax.tree_util.tree_map(
        lambda x: x.astype(jnp.bfloat16), params)
    qtree = quantize_param_tree(params, 256)
    wq, wd = quantized_tree_bytes(qtree)
    weight_ratio = wd / wq
    _beat()

    num_pages, ps = 144, 16                   # 9 slots x 256 tokens
    spec_bf16 = paged_spec_for(cfg, num_pages, ps, 256,
                               dtype=jnp.bfloat16)
    spec_int8 = paged_spec_for(cfg, num_pages, ps, 256,
                               dtype=jnp.int8, kv_quant_block=0)
    bf16_pool = paged_kv_bytes(spec_bf16)
    int8_pool = paged_kv_bytes(spec_int8)
    kv_ratio = bf16_pool / int8_pool

    # decode-bytes cross-check on the reference mixed-length workload
    lens = (5, 9, 14, 3, 16, 7, 12, 4, 10, 6, 15, 8, 5, 11, 3, 13)
    positions = [l + 8 for l in lens]
    bf16_step, _ = decode_read_bytes(
        positions, ps, spec_bf16.pages_per_seq, spec_bf16.kv_heads,
        spec_bf16.head_dim, dtype_bytes=2)
    int8_step, _ = decode_read_bytes(
        positions, ps, spec_int8.pages_per_seq, spec_int8.kv_heads,
        spec_int8.head_dim, dtype_bytes=1,
        scale_blocks=spec_int8.scale_blocks)
    step_ratio = bf16_step / int8_step if int8_step else 0.0
    ok = weight_ratio >= 1.8 and kv_ratio >= 1.8 and step_ratio >= 1.8
    return _emit(
        "quant_serving_bytes", round(kv_ratio, 4),
        "bf16/int8_kv_pool_bytes_ratio", round(weight_ratio, 3),
        {"weight_bytes": {"int8_resident": wq, "bf16_dense": wd},
         "weight_ratio": round(weight_ratio, 4),
         "kv_pool_bytes": {"int8": int8_pool, "bf16": bf16_pool},
         "kv_ratio": round(kv_ratio, 4),
         "decode_bytes_per_step": {"int8": int(int8_step * 2),
                                   "bf16": int(bf16_step * 2)},
         "decode_bytes_ratio": round(step_ratio, 4),
         "both_levers_ge_1p8x": bool(ok),
         "quant_block": 256, "kv_quant_block": "head_dim",
         "page_size": ps, "num_pages": num_pages,
         "backend": jax.default_backend(),
         "source": "quantized_tree_bytes + paged_kv_bytes + "
                   "decode_read_bytes accounting (hardware-free)"})


def bench_quant_kv_occupancy(on_tpu, rtt):
    """Hardware-free row: serving-capacity payoff of the int8 KV pool
    — the paged_kv_occupancy experiment re-run with the pool dtype as
    the ONLY variable (ISSUE 17). The same mixed-length workload runs
    on a bf16 pool and on an int8+per-row-scale pool at identical page
    geometry; value = the int8 engine's peak live tokens in flight per
    cache KiB, vs_baseline = that density / the bf16 engine's (the
    byte ratio, since both engines pack the same peak concurrency).
    Pins 0 steady-state recompiles for BOTH engines and carries the
    greedy-vs-bf16 agreement plus decode tokens/s so the density win
    is visibly not bought with accuracy or throughput collapse.
    """
    del on_tpu, rtt        # CPU-only accounting + wall clock, tiny model
    import jax
    import jax.numpy as jnp
    from deepspeed_tpu.inference import InferenceEngine, paged_kv_bytes
    from deepspeed_tpu.models.gpt2 import GPT2Config, init_gpt2_params

    cfg = GPT2Config(vocab_size=256, max_position_embeddings=128,
                     hidden_size=64, num_layers=2, num_heads=4,
                     embd_dropout=0.0, attn_dropout=0.0,
                     resid_dropout=0.0)
    params = init_gpt2_params(cfg, jax.random.PRNGKey(0))
    max_len, new_tokens, ps = 128, 16, 16
    num_pages = 40
    rng = np.random.RandomState(0)
    prompts = [rng.randint(1, 256, (l,)).tolist()
               for l in (5, 9, 14, 3, 16, 7, 12, 4, 10, 6,
                         15, 8, 5, 11, 3, 13)]

    def serve(kv_dtype):
        eng = InferenceEngine(cfg, params, {
            "max_batch_size": 16, "prompt_buckets": [8, 16],
            "batch_buckets": [1, 4, 16], "max_seq_len": max_len,
            "max_new_tokens": new_tokens,
            "paged_kv": {"page_size": ps, "num_pages": num_pages,
                         "kv_dtype": kv_dtype}}, dtype=jnp.float32)
        eng.warmup()
        _beat()
        t0 = time.perf_counter()
        outs = eng.generate(prompts, max_new_tokens=new_tokens,
                            temperature=0.0)
        wall = time.perf_counter() - t0
        gen = sum(len(o) - len(p) for o, p in zip(outs, prompts))
        return (outs, gen / wall, paged_kv_bytes(eng.paged_spec),
                eng.scheduler.peak_tokens_in_flight,
                eng.steady_state_recompiles)

    bf_outs, bf_tps, bf_bytes, bf_peak, bf_rc = serve("bf16")
    q_outs, q_tps, q_bytes, q_peak, q_rc = serve("int8")
    _beat()
    q_density = q_peak / (q_bytes / 1024)
    bf_density = bf_peak / (bf_bytes / 1024)
    agree = sum(a == b for a, b in zip(q_outs, bf_outs))
    return _emit(
        "quant_kv_occupancy", round(q_density, 4),
        "tokens_in_flight_per_cache_kib",
        round(q_density / bf_density, 3) if bf_density > 0 else 0.0,
        {"requests": len(prompts), "new_tokens": new_tokens,
         "page_size": ps, "num_pages": num_pages,
         "cache_bytes": {"int8": q_bytes, "bf16": bf_bytes},
         "peak_tokens_in_flight": {"int8": q_peak, "bf16": bf_peak},
         "decode_tokens_per_s": {"int8": round(q_tps, 2),
                                 "bf16": round(bf_tps, 2)},
         "greedy_agree_with_bf16": f"{agree}/{len(prompts)}",
         "steady_state_recompiles": {"int8": q_rc, "bf16": bf_rc},
         "backend": jax.default_backend(),
         "source": "inference engine scheduler accounting, int8 vs "
                   "bf16 KV pool at equal page geometry "
                   "(hardware-free)"})


def bench_quant_decode_tokens_per_s(on_tpu, rtt):
    """TPU ladder row (next hardware window): wall-clock decode
    tokens/s of the FULLY quantized serving engine — int8-resident
    weights (in-program dequant at the matmuls) + int8 KV pool
    (in-kernel dequant in the Pallas paged-decode kernel) — vs the
    unquantized engine at identical config. The decode step is
    KV-bandwidth-bound, so halving pool bytes should show up as
    tokens/s on hardware; on a non-TPU backend the kernel runs
    interpret mode and the row is a functional pin (zero steady-state
    recompiles for both engines, greedy agreement count in detail),
    not a perf number.
    """
    del rtt
    import jax
    import jax.numpy as jnp
    from deepspeed_tpu.inference import InferenceEngine
    from deepspeed_tpu.models.gpt2 import GPT2Config, init_gpt2_params

    cfg = GPT2Config(vocab_size=256, max_position_embeddings=512,
                     hidden_size=512, num_layers=2, num_heads=4,
                     embd_dropout=0.0, attn_dropout=0.0,
                     resid_dropout=0.0)          # head_dim 128
    params = init_gpt2_params(cfg, jax.random.PRNGKey(0))
    new_tokens = 64
    dtype = jnp.bfloat16 if on_tpu else jnp.float32
    rng = np.random.RandomState(0)
    prompts = [rng.randint(1, 256, (l,)).tolist()
               for l in (5, 8, 13, 3, 16, 7, 11, 4)]

    def serve(quantized):
        icfg = {"max_batch_size": 8, "prompt_buckets": [16],
                "batch_buckets": [8], "max_seq_len": 256,
                "max_new_tokens": new_tokens,
                "paged_kv": {"page_size": 16, "attn_kernel": "pallas"}}
        if quantized:
            icfg["quantize_weights"] = "int8"
            icfg["paged_kv"]["kv_dtype"] = "int8"
        eng = InferenceEngine(cfg, params, icfg, dtype=dtype)
        eng.warmup()
        _beat()
        t0 = time.perf_counter()
        outs = eng.generate(prompts, max_new_tokens=new_tokens,
                            temperature=0.0)
        wall = time.perf_counter() - t0
        gen = sum(len(o) - len(p) for o, p in zip(outs, prompts))
        return gen / wall, eng.steady_state_recompiles, outs
    q_tps, q_rc, q_outs = serve(True)
    fp_tps, fp_rc, fp_outs = serve(False)
    _beat()
    agree = sum(a == b for a, b in zip(q_outs, fp_outs))
    return _emit(
        "quant_decode_tokens_per_s", round(q_tps, 2),
        "tokens_per_s",
        round(q_tps / fp_tps, 3) if fp_tps > 0 else 0.0,
        {"unquantized_tokens_per_s": round(fp_tps, 2),
         "steady_state_recompiles": {"quantized": q_rc,
                                     "unquantized": fp_rc},
         "greedy_agree_with_fp": f"{agree}/{len(prompts)}",
         "new_tokens": new_tokens, "requests": len(prompts),
         "hbm_peak_mb": _hbm_peak_mb(),
         "backend": jax.default_backend(),
         "source": "inference engine wall clock, int8 weights + int8 "
                   "KV pool vs unquantized at identical config"})


def bench_disagg_ttft_p95(on_tpu, rtt):
    """TPU ladder row (next hardware window): p95 TTFT of the
    disaggregated engine — decode-first step order with the handoff
    queue between the phases — vs the interleaved engine under the same
    load. On hardware the interleaved engine stalls every in-flight
    request's next token behind each prefill dispatch; disaggregation
    converts that stall into bounded handoff queue time. On a non-TPU
    backend the row is a functional pin (parity + decomposition), not a
    perf number.
    """
    del rtt
    import jax
    import jax.numpy as jnp
    from deepspeed_tpu.inference import InferenceEngine
    from deepspeed_tpu.models.gpt2 import GPT2Config, init_gpt2_params

    cfg = GPT2Config(vocab_size=256, max_position_embeddings=512,
                     hidden_size=512 if on_tpu else 64,
                     num_layers=2, num_heads=4,
                     embd_dropout=0.0, attn_dropout=0.0,
                     resid_dropout=0.0)
    params = init_gpt2_params(cfg, jax.random.PRNGKey(0))
    new_tokens = 32
    dtype = jnp.bfloat16 if on_tpu else jnp.float32
    rng = np.random.RandomState(0)
    prompts = [rng.randint(1, 256, (l,)).tolist()
               for l in (5, 8, 13, 3, 16, 7, 11, 4, 9, 14, 6, 12)]
    icfg = {"max_batch_size": 4, "prompt_buckets": [16],
            "batch_buckets": [4], "max_seq_len": 256,
            "max_new_tokens": new_tokens}

    def serve(disagg_on):
        ic = dict(icfg)
        if disagg_on:
            ic["disagg"] = {"enabled": True}
        eng = InferenceEngine(cfg, params, ic, dtype=dtype)
        eng.warmup()
        _beat()
        outs = eng.generate(prompts, max_new_tokens=new_tokens,
                            temperature=0.0)
        p95 = eng._tracer.hist["ttft_ms"].percentile(0.95)
        rc = eng.steady_state_recompiles
        eng.close()
        return outs, p95 or 0.0, rc

    outs_i, p95_i, rc_i = serve(False)
    outs_d, p95_d, rc_d = serve(True)
    _beat()
    return _emit(
        "disagg_ttft_p95", round(p95_d, 3), "ms",
        round(p95_i / p95_d, 3) if p95_d > 0 else 0.0,
        {"interleaved_p95_ms": round(p95_i, 3),
         "greedy_parity": bool(outs_d == outs_i),
         "steady_state_recompiles": {"interleaved": rc_i, "disagg": rc_d},
         "requests": len(prompts), "new_tokens": new_tokens,
         "backend": jax.default_backend(),
         "functional_pin_only": jax.default_backend() != "tpu",
         "source": "tracer TTFT histogram, disagg vs interleaved"})


def bench_chunked_prefill_tbt(on_tpu, rtt):
    """Hardware-free row: TBT-max under a mixed one-long-many-short
    workload, chunked prefill vs whole-prompt prefill (ISSUE 19). The
    whole-prompt engine prefills the long prompt in one dispatch, so
    every in-flight short request's next token waits behind the full
    prompt — the TBT spike. The chunked engine runs decode FIRST each
    step and slips at most ONE chunk_tokens-wide chunk dispatch after
    it, so the worst inter-token gap is bounded by one decode + one
    chunk regardless of prompt length.

    Value = chunked TBT-max (ms); vs_baseline = whole-prompt TBT-max /
    chunked TBT-max (>1 means the spike was flattened). Wall clocks on
    CPU are noisy, so the ACCEPTANCE pins are structural: the bound
    itself is checked as pure dispatch ordering (at most one chunk
    dispatch per step, every decode of the step before it), greedy
    outputs bitwise equal to the whole-prompt engine, zero steady-state
    recompiles for both, and the warmup program-count reduction from
    collapsing the prompt-bucket ladder is reported in detail.
    """
    del on_tpu, rtt
    import jax
    import jax.numpy as jnp
    from deepspeed_tpu.inference import InferenceEngine, Request
    from deepspeed_tpu.models.gpt2 import GPT2Config, init_gpt2_params

    cfg = GPT2Config(vocab_size=61, max_position_embeddings=256,
                     hidden_size=64, num_layers=2, num_heads=4,
                     embd_dropout=0.0, attn_dropout=0.0,
                     resid_dropout=0.0)
    params = init_gpt2_params(cfg, jax.random.PRNGKey(5))
    new_tokens = 16
    rng = np.random.RandomState(11)
    shorts = [rng.randint(1, 61, (l,)).tolist() for l in (5, 7, 3, 6)]
    long_prompt = rng.randint(1, 61, (80,)).tolist()

    def serve(chunked):
        icfg = {"max_batch_size": 5, "batch_buckets": [1, 4],
                "max_seq_len": 128, "max_new_tokens": new_tokens,
                "paged_kv": {"page_size": 8, "num_pages": 96}}
        if chunked:
            # the ladder collapse: ONE short bucket; the long prompt is
            # chunk dispatches, not a 96-wide compile
            icfg["prompt_buckets"] = [8]
            icfg["chunked_prefill"] = {"enabled": True,
                                       "chunk_tokens": 16}
        else:
            # the ladder the chunked engine collapses: one bucket per
            # prompt-length regime, each a compiled program per batch
            # bucket
            icfg["prompt_buckets"] = [8, 32, 96]
        eng = InferenceEngine(cfg, params, icfg, dtype=jnp.float32)
        warm = eng.warmup()
        _beat()
        done, uids = {}, {}
        for p in shorts:
            uids[eng.submit(Request(prompt=p, max_new_tokens=new_tokens,
                                    temperature=0.0, seed=0))] = tuple(p)
        # get the shorts decoding before the long prompt lands: the
        # landing step then mixes decode with (chunked) prefill
        for _ in range(3):
            for f in eng.step():
                done[uids[f.uid]] = f.tokens
        uids[eng.submit(Request(prompt=long_prompt,
                                max_new_tokens=new_tokens,
                                temperature=0.0, seed=0))] = \
            tuple(long_prompt)
        while not eng.scheduler.idle():
            for f in eng.step():
                done[uids[f.uid]] = f.tokens
        tbt_max = eng._tracer.hist["tbt_ms"].max or 0.0
        trace = eng._dispatch_trace.rows() \
            if eng._dispatch_trace is not None else []
        rc = eng.steady_state_recompiles
        eng.close()
        return done, tbt_max, warm, rc, trace

    ck_done, ck_tbt, ck_warm, ck_rc, ck_trace = serve(True)
    wp_done, wp_tbt, wp_warm, wp_rc, _ = serve(False)
    _beat()
    # the TBT bound as pure ordering: within every traced step, at most
    # one chunk dispatch, and every decode-phase dispatch precedes it
    by_step = {}
    for step, kind in ck_trace:
        by_step.setdefault(step, []).append(kind)
    chunk_steps = {s: k for s, k in by_step.items() if "chunk" in k}
    at_most_one = all(k.count("chunk") <= 1 for k in chunk_steps.values())
    decode_first = all(
        max((i for i, x in enumerate(k) if x == "decode"), default=-1)
        < k.index("chunk") for k in chunk_steps.values())
    return _emit(
        "chunked_prefill_tbt", round(ck_tbt, 3), "ms",
        round(wp_tbt / ck_tbt, 3) if ck_tbt > 0 else 0.0,
        {"whole_prompt_tbt_max_ms": round(wp_tbt, 3),
         "tbt_bound_structural": {
             "chunk_steps_traced": len(chunk_steps),
             "at_most_one_chunk_per_step": at_most_one,
             "decode_before_chunk": decode_first},
         "greedy_parity": bool(ck_done == wp_done),
         "steady_state_recompiles": {"chunked": ck_rc,
                                     "whole_prompt": wp_rc},
         "warmup_programs": {"chunked": ck_warm,
                             "whole_prompt": wp_warm},
         "long_prompt_tokens": len(long_prompt), "chunk_tokens": 16,
         "requests": len(shorts) + 1,
         "backend": jax.default_backend(),
         "source": "tracer TBT histogram + DispatchTrace ordering, "
                   "chunked vs whole-prompt prefill (hardware-free)"})


def bench_long_prompt_prefill_tokens_per_s(on_tpu, rtt):
    """TPU ladder row (next hardware window): prefill throughput on a
    long prompt, context-parallel chunked prefill (ring K/V rotation
    over the serving mesh) vs single-shard chunked prefill at identical
    config (ISSUE 19). On hardware the CP path divides each chunk's
    attention and MLP work over the mesh's model axis, so long-prompt
    TTFT drops roughly by the shard count; on a non-TPU backend the row
    is a functional pin (bitwise greedy parity CP vs single-shard, zero
    steady-state recompiles, cp_shards actually engaged), not a perf
    number.
    """
    del rtt
    import jax
    import jax.numpy as jnp
    from deepspeed_tpu.inference import InferenceEngine
    from deepspeed_tpu.models.gpt2 import GPT2Config, init_gpt2_params

    n_dev = len(jax.devices())
    shards = 2 if n_dev >= 2 else 1
    cfg = GPT2Config(vocab_size=256, max_position_embeddings=2048,
                     hidden_size=512 if on_tpu else 64,
                     num_layers=2, num_heads=4,
                     embd_dropout=0.0, attn_dropout=0.0,
                     resid_dropout=0.0)
    params = init_gpt2_params(cfg, jax.random.PRNGKey(0))
    dtype = jnp.bfloat16 if on_tpu else jnp.float32
    rng = np.random.RandomState(0)
    plen = 1024 if on_tpu else 192
    prompt = rng.randint(1, 256, (plen,)).tolist()
    new_tokens = 8

    def serve(cp_on):
        icfg = {"max_batch_size": 1, "prompt_buckets": [16],
                "batch_buckets": [1],
                "max_seq_len": plen + new_tokens + 16,
                "max_new_tokens": new_tokens,
                "paged_kv": {"page_size": 16},
                "chunked_prefill": {"enabled": True, "chunk_tokens": 64,
                                    "cp_threshold_tokens":
                                        64 if cp_on else 0}}
        if cp_on and shards > 1:
            icfg["mesh"] = {"axes": {"model": shards}}
        eng = InferenceEngine(cfg, params, icfg, dtype=dtype)
        eng.warmup()
        _beat()
        t0 = time.perf_counter()
        outs = eng.generate([prompt], max_new_tokens=new_tokens,
                            temperature=0.0)
        wall = time.perf_counter() - t0
        ttft = eng._tracer.hist["ttft_ms"].max or 0.0
        state = eng.debug_state()
        rc = eng.steady_state_recompiles
        eng.close()
        return outs, plen / wall, ttft, rc, state

    cp_outs, cp_tps, cp_ttft, cp_rc, cp_state = serve(True)
    ss_outs, ss_tps, ss_ttft, ss_rc, _ = serve(False)
    _beat()
    ck = cp_state.get("chunked_prefill", {})
    return _emit(
        "long_prompt_prefill_tokens_per_s", round(cp_tps, 2),
        "prompt_tokens_per_s",
        round(cp_tps / ss_tps, 3) if ss_tps > 0 else 0.0,
        {"single_shard_tokens_per_s": round(ss_tps, 2),
         "ttft_ms": {"cp": round(cp_ttft, 3),
                     "single_shard": round(ss_ttft, 3)},
         "greedy_parity": bool(cp_outs == ss_outs),
         "steady_state_recompiles": {"cp": cp_rc, "single_shard": ss_rc},
         "cp_shards": ck.get("cp_shards"),
         "cp_reason": ck.get("cp_reason"),
         "prompt_tokens": plen, "chunk_tokens": 64,
         "hbm_peak_mb": _hbm_peak_mb(),
         "backend": jax.default_backend(),
         "functional_pin_only": jax.default_backend() != "tpu",
         "source": "engine wall clock over one long prompt, "
                   "context-parallel vs single-shard chunked prefill"})


# ------------------------------------------------------------- child mode


def run_child(metric):
    """Run one metric in this process; print exactly one JSON row.

    A stall watchdog still guards the child: a blocked device fetch hangs
    inside the C++ runtime where Python signal handlers never run, so a
    watchdog THREAD with os._exit is the only reliable escape (the parent's
    subprocess timeout is the backstop if even this thread is starved).
    """
    _beat()
    flight = _flight_path(metric)

    def _watchdog():
        while True:
            time.sleep(30)
            if time.monotonic() - _BEAT[0] > STALL_TIMEOUT:
                rec = _FLIGHT[0]
                if rec is not None:   # black box first, then the row
                    rec.dump("bench_stall", extra={"stall": {
                        "metric": metric, "phase": "bench_metric",
                        "timeout_s": STALL_TIMEOUT}}, stacks=True)
                _emit(metric, 0.0, "error", 0.0,
                      {"error": "device_unreachable: no benchmark "
                                f"progress for {STALL_TIMEOUT}s "
                                "(device hung?)", "skipped": True,
                       "stall_detected": {"phase": "bench_metric",
                                          "flight": flight}})
                os._exit(2)

    threading.Thread(target=_watchdog, daemon=True).start()
    import jax
    # arm the flight recorder AFTER the watchdog thread exists (the
    # package import below is itself inside the protected window — a
    # dead device can wedge any first device touch)
    from deepspeed_tpu.utils.health import FlightRecorder
    _FLIGHT[0] = FlightRecorder(flight, ring_events=128)
    _FLIGHT[0].record({"event": "bench_start", "metric": metric})
    # persistent compile cache: children share compiled executables, so a
    # retried/resumed ladder only pays each compile once
    from deepspeed_tpu.utils.platform import enable_compile_cache
    enable_compile_cache()
    on_tpu = jax.default_backend() == "tpu"
    if os.environ.get("BENCH_REF_ATTN", "0") == "1":
        # A/B knob: route attention through the XLA-fused reference path
        # (bf16 MXU operands) instead of the Pallas flash kernels
        from deepspeed_tpu.ops.attention import flash as _F
        _F.set_attention_options(kernel="reference")
    if os.environ.get("BENCH_LEGACY_ATTN", "0") == "1":
        # A/B knob: the pre-PR-11 per-path Pallas kernels (flash.py
        # dense/causal + banded/hybrid/v2 sparse dispatch) instead of
        # the unified masked kernel
        from deepspeed_tpu.ops.attention import flash as _F
        from deepspeed_tpu.ops.sparse_attention import blocksparse as _bs
        _F.set_attention_options(kernel="flash")
        _bs.USE_MASKED_FLASH = False
    if os.environ.get("BENCH_DROPOUT_HASH1", "0") == "1":
        # A/B knob: single-round dropout-hash finalizer (same keep
        # statistics, ~half the tile-wide VPU hash work)
        from deepspeed_tpu.ops.attention import flash as _F
        _F._HASH_FINAL_ROUNDS = 1
    rtt = _rtt()
    _beat()

    if metric == "comm_wire_bytes_per_step":
        bench_comm_wire_bytes(on_tpu, rtt)
    elif metric == "comm_overlap_structure":
        bench_comm_overlap_structure(on_tpu, rtt)
    elif metric == "mfu_cost_model":
        bench_mfu_cost_model(on_tpu, rtt)
    elif metric == "host_dispatch_overhead":
        bench_host_dispatch_overhead(on_tpu, rtt)
    elif metric == "decode_throughput":
        bench_decode_throughput(on_tpu, rtt)
    elif metric == "paged_kv_occupancy":
        bench_paged_kv_occupancy(on_tpu, rtt)
    elif metric == "paged_decode_bytes":
        bench_paged_decode_bytes(on_tpu, rtt)
    elif metric == "masked_flash_flops_bytes":
        bench_masked_flash_flops_bytes(on_tpu, rtt)
    elif metric == "serve_trace_overhead":
        bench_serve_trace_overhead(on_tpu, rtt)
    elif metric == "health_overhead":
        bench_health_overhead(on_tpu, rtt)
    elif metric == "async_ckpt_stall_ms":
        bench_async_ckpt_stall(on_tpu, rtt)
    elif metric == "spec_decode_accepted_per_dispatch":
        bench_spec_decode_accepted_per_dispatch(on_tpu, rtt)
    elif metric == "disagg_dispatch_structure":
        bench_disagg_dispatch_structure(on_tpu, rtt)
    elif metric == "chunked_prefill_tbt":
        bench_chunked_prefill_tbt(on_tpu, rtt)
    elif metric == "long_prompt_prefill_tokens_per_s":
        bench_long_prompt_prefill_tokens_per_s(on_tpu, rtt)
    elif metric == "fleet_drain_goodput":
        bench_fleet_drain_goodput(on_tpu, rtt)
    elif metric == "fleet_migration_goodput":
        bench_fleet_migration_goodput(on_tpu, rtt)
    elif metric == "fleet_trace_overhead":
        bench_fleet_trace_overhead(on_tpu, rtt)
    elif metric == "quant_serving_bytes":
        bench_quant_serving_bytes(on_tpu, rtt)
    elif metric == "quant_kv_occupancy":
        bench_quant_kv_occupancy(on_tpu, rtt)
    elif metric == "quant_decode_tokens_per_s":
        bench_quant_decode_tokens_per_s(on_tpu, rtt)
    elif metric == "paged_decode_tokens_per_s":
        bench_paged_decode_tokens_per_s(on_tpu, rtt)
    elif metric == "disagg_ttft_p95":
        bench_disagg_ttft_p95(on_tpu, rtt)
    elif metric == "bert_large_samples_per_s":
        bench_bert_large(on_tpu, rtt)
    elif metric == "bert_onebit_samples_per_s":
        bench_bert_onebit(on_tpu, rtt)
    elif metric == "sparse_attention_speedup_s8k":
        bench_sparse_attention(on_tpu, rtt)
    elif metric == "sparse_attn_speedup_v2":
        bench_sparse_attn_speedup_v2(on_tpu, rtt)
    elif metric == "gpt2_train_mfu_dropout":
        bench_gpt2(on_tpu, rtt, 0.1, "gpt2_train_mfu_dropout")
    elif metric == "gpt2_train_mfu":
        bench_gpt2(on_tpu, rtt, 0.0, "gpt2_train_mfu")
    else:
        raise SystemExit(f"unknown metric {metric!r}")


# ------------------------------------------------------------ parent mode


def _git_head():
    """Resume key: a digest of the sources that determine the measured
    numbers — bench.py itself plus everything importable from the
    package (py/json/cpp/h under deepspeed_tpu/ and csrc/, setup.py).
    Edits to tests/docs/examples/notes do NOT invalidate checkpointed
    rows (they cannot change a measurement); any edit to benchmarked
    code does, whether committed or not."""
    import hashlib
    repo = os.path.dirname(os.path.abspath(__file__))
    # sources only, never build artifacts: the runtime-built .so would
    # make the key unstable (rebuilt on import), and its inputs (.cpp/.h
    # + Makefile flags) are what actually determine the measurement
    exts = (".py", ".json", ".cpp", ".cc", ".h")
    names = ("Makefile",)
    roots = ["bench.py", "setup.py", "deepspeed_tpu", "csrc"]
    try:
        h = hashlib.sha256()
        for root in roots:
            path = os.path.join(repo, root)
            if os.path.isfile(path):
                files = [path]
            else:
                files = []
                for dirpath, dirnames, filenames in os.walk(path):
                    dirnames[:] = [d for d in dirnames
                                   if d != "__pycache__"]
                    files.extend(os.path.join(dirpath, f)
                                 for f in filenames
                                 if f.endswith(exts) or f in names)
            for f in sorted(files):
                try:
                    with open(f, "rb") as fh:
                        content = fh.read()
                except OSError:
                    continue   # racing writer/deleter; skip, stay stable
                h.update(os.path.relpath(f, repo).encode())
                h.update(content)
        # measurement-config env knobs (BENCH_SCAN_LAYERS, BENCH_MASTER_FREE,
        # future ones) change what a row measures and must invalidate it;
        # control knobs (timeouts/paths/retries/resume) must not
        control = {"BENCH_PARTIAL", "BENCH_METRIC_TIMEOUT",
                   "BENCH_METRIC_RETRIES", "BENCH_NO_RESUME",
                   "BENCH_STALL_TIMEOUT", "BENCH_HW_FREE_TIMEOUT",
                   "BENCH_TIME_BUDGET", "BENCH_FLIGHT_PATH"}
        for k in sorted(os.environ):
            if k.startswith("BENCH_") and k not in control:
                h.update(f"{k}={os.environ[k]}".encode())
        # the measurement platform is part of the resume key: rows from
        # a forced-CPU run must never resume as hardware rows (the .cpu
        # partial-path suffix only protects the DEFAULT path)
        if os.environ.get("JAX_PLATFORMS"):
            h.update(f"JAX_PLATFORMS={os.environ['JAX_PLATFORMS']}"
                     .encode())
        return "src-" + h.hexdigest()[:16]
    except Exception:
        return None


def _load_partial(head):
    """Rows checkpointed by a previous run at the SAME commit, else {}."""
    if os.environ.get("BENCH_NO_RESUME") or head is None:
        return {}
    rows = {}
    try:
        with open(PARTIAL_PATH) as f:
            header = json.loads(f.readline())
            if header.get("head") != head:
                return {}
            for line in f:
                row = json.loads(line)
                if row.get("unit") != "error":
                    rows[row["metric"]] = row
    except Exception:
        return {}
    return rows


def _stale_partial(head):
    """Rows from a previous COMPLETED ladder at a DIFFERENT source
    digest. Never resumed as measurements — attached to dead-device
    error rows (clearly labeled) so the audit trail points at the most
    recent hardware data instead of a bare error."""
    try:
        with open(PARTIAL_PATH) as f:
            header = json.loads(f.readline())
            if header.get("head") == head:
                return None
            rows = {}
            for line in f:
                row = json.loads(line)
                if row.get("unit") != "error":
                    rows[row["metric"]] = {
                        "value": row["value"], "unit": row["unit"],
                        "vs_baseline": row["vs_baseline"]}
            if not rows:
                return None
            return {"source_digest": header.get("head"),
                    "note": "measured by an EARLIER source revision; "
                            "NOT a current measurement",
                    "rows": rows}
    except Exception:
        return None


def _append_partial(head, row, fresh):
    """Returns the next value of ``fresh``: stays True if the header
    write failed (appending under a stale different-commit header would
    let a later run resume the wrong rows)."""
    try:
        mode = "w" if fresh else "a"
        with open(PARTIAL_PATH, mode) as f:
            if fresh:
                f.write(json.dumps({"head": head}) + "\n")
            f.write(json.dumps(row) + "\n")
            f.flush()
            os.fsync(f.fileno())
        return False
    except Exception:
        # checkpointing is best-effort; never kill the ladder for it
        return fresh


def _probe_device(timeout=300):
    """True iff a tiny device matmul completes in a fresh subprocess ON
    THE TPU BACKEND. The backend assertion is the round-5 fix: a
    CPU-fallback matmul once passed this probe and burned the hardware
    window measuring nothing — the probe must prove the accelerator, not
    just a working Python. A run explicitly forced to CPU
    (JAX_PLATFORMS=cpu...) only asserts completion."""
    forced_cpu = os.environ.get("JAX_PLATFORMS", "").startswith("cpu")
    code = ("import os, jax\n"
            "import numpy as np, jax.numpy as jnp\n"
            "x = jnp.ones((256,256), jnp.bfloat16)\n"
            "np.asarray(x @ x)\n"
            "assert os.environ.get('JAX_PLATFORMS','').startswith('cpu') "
            "or jax.default_backend() == 'tpu', (\n"
            "    'probe ran on %s, not tpu' % jax.default_backend())\n"
            "print('ok:' + jax.default_backend())")
    try:
        r = subprocess.run([sys.executable, "-c", code],
                           capture_output=True, text=True, timeout=timeout)
        if "ok:" not in r.stdout:
            return False
        backend = r.stdout.split("ok:", 1)[1].strip().splitlines()[0]
        return backend == "tpu" or forced_cpu
    except Exception:
        return False


def _last_metric_row(stdout, metric):
    """Last JSON row for ``metric`` in a child's stdout, preferring
    VALUE rows over error rows: a child whose stall watchdog fired
    during teardown — AFTER the measurement row streamed — appends a
    ``device_unreachable`` error row last, and taking it would discard
    a completed measurement (the same teardown-hang failure the
    TimeoutExpired salvage covers, via the in-child watchdog instead of
    the parent timeout). None when no row matched."""
    row = err_row = None
    for line in (stdout or "").splitlines():
        line = line.strip()
        if line.startswith("{"):
            try:
                cand = json.loads(line)
            except ValueError:
                continue
            if cand.get("metric") == metric:
                if cand.get("unit") == "error":
                    err_row = cand
                else:
                    row = cand
    return row if row is not None else err_row


# Postmortems salvaged from stalled children, keyed by metric. A side
# table (not a third return value) because the (row, err) contract of
# _run_metric_subprocess is pinned by the ladder tests.
_STALL_POSTMORTEMS = {}


def _salvage_stall(metric, flight, err_row=None):
    """Fold a stalled child's black box into _STALL_POSTMORTEMS so the
    parent's error row carries the postmortem (which phase went silent,
    how much pre-stall telemetry survived) instead of a bare timeout."""
    post = {}
    if err_row is not None:
        sd = (err_row.get("detail") or {}).get("stall_detected")
        if sd:
            post["stall_detected"] = sd
    try:
        with open(flight) as f:
            payload = json.load(f)
        post["flight"] = {
            "path": flight,
            "trigger": payload.get("trigger"),
            "rows": len(payload.get("rows", [])),
            "stall": payload.get("stall"),
            "threads": len(payload.get("stacks", [])),
        }
    except FileNotFoundError:
        pass   # child died before the ring armed; nothing to attach
    except Exception:
        post["flight"] = {"path": flight, "error": "unreadable"}
    if post:
        _STALL_POSTMORTEMS[metric] = post


def _run_metric_subprocess(metric):
    """(row, err): parse the child's last JSON row; err string on failure.

    Per-row time budget: hardware-free rows get the tight
    HW_FREE_TIMEOUT, device rows the full METRIC_TIMEOUT, and BOTH are
    clamped to what is left of the overall ladder budget — a slow row
    can delay later rows but never erase already-streamed ones.

    Rows are streamed by the child the moment they land, so a child
    killed by the timeout may STILL have finished its measurement (a
    teardown hang during device shutdown):
    the captured-so-far stdout is parsed and a completed value row is
    salvaged instead of discarded (the r02–r05 "one hang zeroed the
    revision" fix)."""
    cmd = [sys.executable, os.path.abspath(__file__), "--metric", metric]
    timeout = HW_FREE_TIMEOUT if metric in HW_FREE else METRIC_TIMEOUT
    rem = _remaining_budget()
    if rem is not None:
        timeout = max(min(timeout, int(rem) - 10), 30)
    # every child gets a deterministic flight-recorder path so a stalled
    # child's black box can be salvaged even after a hard kill; a stale
    # file from an earlier run must not masquerade as this run's dump
    flight = _flight_path(metric)
    env = dict(os.environ)
    env["BENCH_FLIGHT_PATH"] = flight
    try:
        os.remove(flight)
    except OSError:
        pass
    if metric in HW_FREE:
        # hardware-free audits run on a virtual 8-device CPU mesh in
        # their own child — deterministic, device-independent
        env["JAX_PLATFORMS"] = "cpu"
        env["XLA_FLAGS"] = (env.get("XLA_FLAGS", "") +
                            " --xla_force_host_platform_device_count=8")
        # the child's in-process stall watchdog must not outlive the
        # row budget (it defaults to tracking the device-row budget)
        env["BENCH_STALL_TIMEOUT"] = str(max(timeout - 30, 30))
    try:
        r = subprocess.run(cmd, capture_output=True, text=True,
                           timeout=timeout, env=env)
    except subprocess.TimeoutExpired as e:
        out = e.stdout
        if isinstance(out, bytes):
            out = out.decode(errors="replace")
        row = _last_metric_row(out, metric)
        if row is not None and row.get("unit") != "error":
            row.setdefault("detail", {})["salvaged"] = (
                f"child exceeded {timeout}s after the row landed "
                "(teardown hang); measurement kept")
            return row, None
        _salvage_stall(metric, flight, err_row=row)
        return None, f"metric subprocess exceeded {timeout}s (killed)"
    row = _last_metric_row(r.stdout, metric)
    if row is None:
        _salvage_stall(metric, flight)
        tail = (r.stderr or r.stdout or "").strip().splitlines()[-3:]
        return None, f"child rc={r.returncode}, no row; tail={' | '.join(tail)}"
    if row.get("unit") == "error":
        _salvage_stall(metric, flight, err_row=row)
        return None, str(row.get("detail", {}).get("error", "child error row"))
    if r.returncode != 0:
        # value row streamed, then the child died (in-child watchdog
        # os._exit, teardown crash): the measurement is complete — keep
        # it, flagged
        row.setdefault("detail", {})["salvaged"] = (
            f"child exited rc={r.returncode} after the row landed; "
            "measurement kept")
    return row, None


def main():
    if len(sys.argv) >= 3 and sys.argv[1] == "--metric":
        run_child(sys.argv[2])
        return

    head = _git_head()
    done = _load_partial(head)
    fresh = not done  # rewrite the partial file unless resuming
    if done:
        print(f"# resuming {len(done)} checkpointed row(s) from "
              f"{PARTIAL_PATH}", file=sys.stderr, flush=True)

    # Streaming guarantee (round-5 VERDICT): every completed row is
    # fsynced to the partial file (_append_partial) AND echoed to stdout
    # THE MOMENT it lands, so an rc=124 kill mid-ladder leaves the
    # finished rows on both channels instead of zero captured bytes.
    # The canonical ordered emission (headline last) repeats them at the
    # end; consumers keyed on metric name take the last occurrence.
    for metric in METRICS:
        if metric in done:
            _emit_row(done[metric])

    failed = {}

    # hardware-free metrics first (forced-CPU children): they cannot
    # hang on the device and land even when it is unreachable
    for metric in [m for m in METRICS if m in HW_FREE and m not in done]:
        if _budget_exhausted():
            failed[metric] = (f"skipped: ladder time budget "
                              f"({TIME_BUDGET}s) exhausted")
            continue
        row, err = _run_metric_subprocess(metric)
        if row is not None:
            done[metric] = row
            fresh = _append_partial(head, row, fresh)
            _emit_row(row)
        else:
            failed[metric] = err or "unknown failure"

    need_hw = [m for m in METRICS if m not in done and m not in HW_FREE]
    failed_detail = {}
    device_dead = False
    if need_hw:
        # upfront liveness gate: with a dead device every child would
        # burn METRIC_TIMEOUT before failing (~25 min per metric);
        # probing twice up front converts that into explicit error rows
        # in minutes. The probe asserts default_backend() == "tpu" — a
        # CPU-fallback matmul must never pass for hardware rows. Probe
        # time is clamped to the ladder budget so the gate itself can
        # never eat the window the completed rows need to be reported.
        probe_t = 300
        rem = _remaining_budget()
        if rem is not None:
            probe_t = max(min(300, int(rem / 3)), 30)
        if not _probe_device(probe_t) and \
                (time.sleep(min(60, probe_t)) or not _probe_device(probe_t)):
            device_dead = True
            err = ("device_unreachable: probe-before-run failed twice "
                   "to complete a matmul on the tpu backend — hardware "
                   "rows skipped fast instead of hanging per-metric")
            stale = _stale_partial(head)
            detail = {"error": err, "skipped": True}
            if stale:
                detail["last_completed_ladder"] = stale
            for metric in need_hw:
                failed[metric] = err
                failed_detail[metric] = detail

    if not device_dead:
        for metric in need_hw:
            if _budget_exhausted():
                failed[metric] = (f"skipped: ladder time budget "
                                  f"({TIME_BUDGET}s) exhausted; "
                                  "completed rows already streamed")
                continue
            err = None
            for attempt in range(1 + METRIC_RETRIES):
                if attempt > 0:
                    if _budget_exhausted(floor=120):
                        err = f"{err}; budget exhausted, retry skipped"
                        break
                    # only retry against a live device; a second hang
                    # costs another METRIC_TIMEOUT for nothing. The
                    # probe is clamped to the remaining budget like the
                    # upfront gate — it must never be what overruns it.
                    rem = _remaining_budget()
                    probe_t = (300 if rem is None
                               else max(min(300, int(rem / 3)), 30))
                    if not _probe_device(probe_t):
                        time.sleep(min(60, probe_t))
                        if not _probe_device(probe_t):
                            err = f"{err}; device probe dead, retry skipped"
                            break
                row, err = _run_metric_subprocess(metric)
                if row is not None:
                    done[metric] = row
                    fresh = _append_partial(head, row, fresh)
                    _emit_row(row)
                    break
            if metric not in done:
                failed[metric] = err or "unknown failure"

    # Emit everything in canonical order, headline last. Completed rows
    # are real; failed rows are explicit error rows — a flaky device
    # yields N good rows + per-metric errors, never one bare error line.
    def error_row(metric):
        detail = failed_detail.get(
            metric, {"error": failed.get(metric, "unknown failure")})
        post = _STALL_POSTMORTEMS.get(metric)
        if post:
            # the salvaged black box rides the error row: which phase
            # went silent + how much pre-stall telemetry survived
            detail = dict(detail, stalled=post)
        _emit(metric, 0.0, "error", 0.0, detail)

    for metric in METRICS:
        if metric == HEADLINE:
            continue
        if metric in done:
            _emit_row(done[metric])
        else:
            error_row(metric)
    if HEADLINE in done:
        _emit_row(done[HEADLINE])
    else:
        error_row(HEADLINE)


if __name__ == "__main__":
    main()
