"""Config keys and defaults.

Key names intentionally match the reference JSON schema
(``deepspeed/runtime/constants.py``) so a reference user's ds_config.json works
unchanged; defaults re-tuned for TPU where noted (bf16 on by default is new).
"""


#############################################
# Routes
#############################################
ROUTE_TRAIN = "train"
ROUTE_EVAL = "eval"
ROUTE_PREDICT = "predict"
ROUTE_ENCODE = "encode"

#############################################
# Batch size (reference constants.py:24-40; triangle invariant config.py:557)
#############################################
TRAIN_BATCH_SIZE = "train_batch_size"
TRAIN_BATCH_SIZE_DEFAULT = None

TRAIN_MICRO_BATCH_SIZE_PER_GPU = "train_micro_batch_size_per_gpu"
TRAIN_MICRO_BATCH_SIZE_PER_GPU_DEFAULT = None
# TPU spelling; both accepted.
TRAIN_MICRO_BATCH_SIZE_PER_CHIP = "train_micro_batch_size_per_chip"

GRADIENT_ACCUMULATION_STEPS = "gradient_accumulation_steps"
GRADIENT_ACCUMULATION_STEPS_DEFAULT = None

SPARSE_GRADIENTS = "sparse_gradients"
SPARSE_GRADIENTS_DEFAULT = False
# explicit opt-in list of embedding leaf paths (or path substrings) for
# the CSR grad exchange; when set, the name-regex heuristic is bypassed
SPARSE_GRADIENTS_PARAMS = "sparse_gradients_params"
SPARSE_GRADIENTS_PARAMS_DEFAULT = None

#############################################
# Optimizer / scheduler
#############################################
OPTIMIZER = "optimizer"
OPTIMIZER_TYPE_DEFAULT = None
OPTIMIZER_PARAMS = "params"
TYPE = "type"
LEGACY_FUSION = "legacy_fusion"
LEGACY_FUSION_DEFAULT = False

SCHEDULER = "scheduler"
SCHEDULER_TYPE_DEFAULT = None
SCHEDULER_PARAMS = "params"

MAX_GRAD_NORM = "max_grad_norm"

ADAM_OPTIMIZER = "adam"
LAMB_OPTIMIZER = "lamb"
ONEBIT_ADAM_OPTIMIZER = "onebitadam"
DEEPSPEED_ADAM = "deepspeed_adam"  # reference name for CPU (offload) adam
SGD_OPTIMIZER = "sgd"
ADAMW_OPTIMIZER = "adamw"
DEEPSPEED_OPTIMIZERS = [
    ADAM_OPTIMIZER, ADAMW_OPTIMIZER, LAMB_OPTIMIZER, ONEBIT_ADAM_OPTIMIZER,
    DEEPSPEED_ADAM, SGD_OPTIMIZER,
]

#############################################
# Steps
#############################################
STEPS_PER_PRINT = "steps_per_print"
STEPS_PER_PRINT_DEFAULT = 10

#############################################
# Training options
#############################################
PRESCALE_GRADIENTS = "prescale_gradients"
PRESCALE_GRADIENTS_DEFAULT = False

GRADIENT_PREDIVIDE_FACTOR = "gradient_predivide_factor"
GRADIENT_PREDIVIDE_FACTOR_DEFAULT = 1.0

DISABLE_ALLGATHER = "disable_allgather"
DISABLE_ALLGATHER_DEFAULT = False

DUMP_STATE = "dump_state"
DUMP_STATE_DEFAULT = False

VOCABULARY_SIZE = "vocabulary_size"
VOCABULARY_SIZE_DEFAULT = None

#############################################
# FP16 (reference constants.py:131-154). On TPU fp16 maps to bf16 by default
# unless fp16.force_fp16 is set (bf16 needs no loss scaling).
#############################################
FP16 = "fp16"
FP16_ENABLED = "enabled"
FP16_ENABLED_DEFAULT = False

FP16_LOSS_SCALE = "loss_scale"
FP16_LOSS_SCALE_DEFAULT = 0

FP16_INITIAL_SCALE_POWER = "initial_scale_power"
FP16_INITIAL_SCALE_POWER_DEFAULT = 32

FP16_LOSS_SCALE_WINDOW = "loss_scale_window"
FP16_LOSS_SCALE_WINDOW_DEFAULT = 1000

FP16_HYSTERESIS = "hysteresis"
FP16_HYSTERESIS_DEFAULT = 2

FP16_MIN_LOSS_SCALE = "min_loss_scale"
FP16_MIN_LOSS_SCALE_DEFAULT = 1

#############################################
# BF16 (TPU-native extension; not in the reference snapshot)
#############################################
BF16 = "bf16"
BF16_ENABLED = "enabled"
BF16_ENABLED_DEFAULT = False
# Master-weight-free bf16: params held in bf16 end-to-end (no fp32
# master copy — saves 4 bytes/param of HBM); requires stochastic
# rounding so sub-ulp updates accumulate in expectation. The TPU-native
# analog of the reference's __STOCHASTIC_MODE__ kernel build variant
# (reference setup.py:211-242, transformer.py stochastic_mode flag).
BF16_MASTER_WEIGHTS = "master_weights"
BF16_MASTER_WEIGHTS_DEFAULT = True
BF16_STOCHASTIC_ROUNDING = "stochastic_rounding"
BF16_STOCHASTIC_ROUNDING_DEFAULT = False
BF16_SR_SEED = "sr_seed"
BF16_SR_SEED_DEFAULT = 0

#############################################
# Gradient clipping
#############################################
GRADIENT_CLIPPING = "gradient_clipping"
GRADIENT_CLIPPING_DEFAULT = 0.0

#############################################
# ZeRO stages
#############################################
ZERO_OPTIMIZATION = "zero_optimization"

#############################################
# Logging / tensorboard
#############################################
WALL_CLOCK_BREAKDOWN = "wall_clock_breakdown"
WALL_CLOCK_BREAKDOWN_DEFAULT = False

MEMORY_BREAKDOWN = "memory_breakdown"
MEMORY_BREAKDOWN_DEFAULT = False

#############################################
# Quantized (int8) gradient allreduce — TPU-native extension
# (ZeRO++-style comm compression; see runtime/quantized_collectives.py)
#
# "compressed_allreduce": {"enabled": false, "block": 256}
#############################################
COMPRESSED_ALLREDUCE = "compressed_allreduce"
COMPRESSED_ALLREDUCE_ENABLED = "enabled"
COMPRESSED_ALLREDUCE_ENABLED_DEFAULT = False
COMPRESSED_ALLREDUCE_BLOCK = "block"
COMPRESSED_ALLREDUCE_BLOCK_DEFAULT = 256

#############################################
# Hierarchical quantized collectives — TPU-native extension
# (ZeRO++ qgZ/qwZ/hpZ shapes; see runtime/quantized_collectives.py).
# Supersedes "compressed_allreduce" (still accepted as a legacy alias
# for {enabled, block}).
#
# "quantized_comm": {
#   "enabled": false,
#   "algo": "twohop",           # qgZ two-hop | "allgather" (legacy, dp=2)
#   "block": 256,               # quantization block size
#   "hierarchical": 0,          # intra-slice size (>=2 splits the data
#                               # axis into data_inter x data_intra)
#   "quantize_weights": false,  # qwZ: int8 ZeRO param all-gather
#   "secondary_partition": false# hpZ: intra-sharded compute-dtype copy
# }
#############################################
QUANTIZED_COMM = "quantized_comm"
QUANTIZED_COMM_ENABLED = "enabled"
QUANTIZED_COMM_ENABLED_DEFAULT = False
QUANTIZED_COMM_ALGO = "algo"
QUANTIZED_COMM_ALGO_DEFAULT = "twohop"
QUANTIZED_COMM_BLOCK = "block"
QUANTIZED_COMM_BLOCK_DEFAULT = 256
QUANTIZED_COMM_HIERARCHICAL = "hierarchical"
QUANTIZED_COMM_HIERARCHICAL_DEFAULT = 0
QUANTIZED_COMM_QUANTIZE_WEIGHTS = "quantize_weights"
QUANTIZED_COMM_QUANTIZE_WEIGHTS_DEFAULT = False
QUANTIZED_COMM_SECONDARY_PARTITION = "secondary_partition"
QUANTIZED_COMM_SECONDARY_PARTITION_DEFAULT = False

#############################################
# Topology-aware collective autotuner + compute/comm overlap
# (runtime/comm_autotune.py; docs/performance.md "Collective
# autotuner"). Picks the quantized_comm exchange (algo / block /
# hierarchy split) per mesh topology and gradient-size histogram from
# a per-hop latency+bandwidth cost model, and overlaps the gradient
# exchange of micro-step i with micro-step i+1's compute inside the
# fused scan. Explicit quantized_comm.{algo,block,hierarchical} keys
# act as overrides.
#
# "comm_autotune": {
#   "enabled": false,
#   "overlap": "auto",          # true | false | "auto" (on when the
#                               # fused quantized exchange is active)
#   "calibrate": false,         # verify wire model vs compiled HLO at
#                               # init (best-effort probe)
#   "intra_size": 0,            # fast-wire extent of the data axis
#                               # (0 = infer: devices per process)
#   "intra_gbps": 75.0,         # fast (ICI) per-direction bandwidth
#   "inter_gbps": 12.5,         # slow (DCN/inter-slice) bandwidth
#   "intra_latency_us": 1.0,
#   "inter_latency_us": 10.0,
#   "block_candidates": [64, 128, 256]
# }
#############################################
COMM_AUTOTUNE = "comm_autotune"
COMM_AUTOTUNE_ENABLED = "enabled"
COMM_AUTOTUNE_ENABLED_DEFAULT = False
COMM_AUTOTUNE_OVERLAP = "overlap"
COMM_AUTOTUNE_OVERLAP_DEFAULT = "auto"
COMM_AUTOTUNE_CALIBRATE = "calibrate"
COMM_AUTOTUNE_CALIBRATE_DEFAULT = False
COMM_AUTOTUNE_INTRA_SIZE = "intra_size"
COMM_AUTOTUNE_INTRA_SIZE_DEFAULT = 0
COMM_AUTOTUNE_INTRA_GBPS = "intra_gbps"
COMM_AUTOTUNE_INTER_GBPS = "inter_gbps"
COMM_AUTOTUNE_INTRA_LATENCY_US = "intra_latency_us"
COMM_AUTOTUNE_INTER_LATENCY_US = "inter_latency_us"
COMM_AUTOTUNE_BLOCK_CANDIDATES = "block_candidates"

#############################################
# Profiler (TPU-native: jax.profiler trace capture; SURVEY.md §5 —
# the reference's wall_clock_breakdown/timers ladder, plus XLA traces)
#
# "profiler": {
#   "enabled": false,
#   "output_path": "/tmp/jax-trace",
#   "start_step": 2,        # skip compile steps
#   "num_steps": 3
# }
#############################################
PROFILER = "profiler"
PROFILER_ENABLED = "enabled"
PROFILER_ENABLED_DEFAULT = False
PROFILER_OUTPUT_PATH = "output_path"
PROFILER_OUTPUT_PATH_DEFAULT = "/tmp/deepspeed_tpu_trace"
PROFILER_START_STEP = "start_step"
PROFILER_START_STEP_DEFAULT = 2
PROFILER_NUM_STEPS = "num_steps"
PROFILER_NUM_STEPS_DEFAULT = 3

#############################################
# Unified observability (deepspeed_tpu/profiling/): FLOPs/MFU cost
# profiler, recompile tracking, HBM watermarks, trace spans, and the
# crash-safe JSONL event log that tools/obs_report.py renders. The
# legacy top-level "profiler" section above is aliased as
# observability.trace (its keys seed the defaults; explicit
# observability.trace keys win), mirroring the
# compressed_allreduce -> quantized_comm aliasing.
#
# "observability": {
#   "enabled": false,
#   "events_dir": "/tmp/deepspeed_tpu_obs",  # events.jsonl location
#   "flops_profiler": true,      # cost-analysis FLOPs/MFU record
#   "memory_watermarks": true,   # structured memory_stats() scalars
#   "recompile_warn_after": 1,   # warn on recompiles past this step
#   "chrome_trace_path": "",     # span timeline JSON ("" disables)
#   "trace": {                   # jax.profiler window (legacy "profiler")
#     "enabled": false, "output_path": "/tmp/deepspeed_tpu_trace",
#     "start_step": 2, "num_steps": 3
#   }
# }
#############################################
OBSERVABILITY = "observability"
OBS_ENABLED = "enabled"
OBS_ENABLED_DEFAULT = False
OBS_EVENTS_DIR = "events_dir"
OBS_EVENTS_DIR_DEFAULT = "/tmp/deepspeed_tpu_obs"
OBS_FLOPS_PROFILER = "flops_profiler"
OBS_FLOPS_PROFILER_DEFAULT = True
OBS_MEMORY_WATERMARKS = "memory_watermarks"
OBS_MEMORY_WATERMARKS_DEFAULT = True
OBS_RECOMPILE_WARN_AFTER = "recompile_warn_after"
OBS_RECOMPILE_WARN_AFTER_DEFAULT = 1
OBS_CHROME_TRACE_PATH = "chrome_trace_path"
OBS_CHROME_TRACE_PATH_DEFAULT = ""
# size-based events.jsonl rotation (0 = off): the live file atomically
# rolls to events.jsonl.<n> when it exceeds this many MiB, so a
# long-running (serving) job's event log is bounded per segment;
# tools/obs_report.py reads rotated segments back in order
OBS_EVENTS_MAX_MB = "events_max_mb"
OBS_EVENTS_MAX_MB_DEFAULT = 0
OBS_TRACE = "trace"
# request-granular serving observability (inference/tracing.py): the
# lifecycle event trail, latency-decomposition histograms, and the
# SLO/goodput split. Host-side and sync-free — on by default (the
# serving engine emits nothing anyway unless inference.events_dir or a
# monitor is wired).
OBS_SERVE = "serve"
OBS_SERVE_ENABLED = "enabled"
OBS_SERVE_ENABLED_DEFAULT = True
OBS_SERVE_SLO = "slo"
OBS_SERVE_SLO_TTFT_MS = "ttft_ms"
OBS_SERVE_SLO_TTFT_MS_DEFAULT = 2000.0    # time to first token budget
OBS_SERVE_SLO_TBT_MS = "tbt_ms"
OBS_SERVE_SLO_TBT_MS_DEFAULT = 200.0      # mean time-between-tokens budget
# serve_decode_window sampling: one window row per request every
# round(1/rate) tokens (deterministic stride, not RNG; 0 disables)
OBS_SERVE_SAMPLE_RATE = "sample_rate"
OBS_SERVE_SAMPLE_RATE_DEFAULT = 0.0625
# per-section override of the rotation cap for the SERVING events log
# (None = inherit the top-level observability.events_max_mb)
OBS_SERVE_EVENTS_MAX_MB = "events_max_mb"
OBS_SERVE_EVENTS_MAX_MB_DEFAULT = None
# fleet identity: which replica this engine serves as. Stamped onto
# every serve-tracer event row (``replica_id``) so the offline fleet
# merger (tools/obs_report.py --fleet) can attribute rows across
# process boundaries. None (the default) omits the field — a
# standalone engine's trail is unchanged.
OBS_SERVE_REPLICA_ID = "replica_id"
OBS_SERVE_REPLICA_ID_DEFAULT = None
# postmortem health plane (deepspeed_tpu/utils/health.py): flight
# recorder ring, stall watchdog, numeric anomaly detectors. Entirely
# host-side; enabling it is pinned to leave losses/params/outputs
# bitwise identical (tests/unit/test_health.py).
#
# "health": {
#   "enabled": false,
#   "ring_events": 256,        # flight-ring rows kept in memory
#   "stall_timeout_s": 0.0,    # 0 disables the watchdog thread
#   "on_stall": "warn",        # or "exit" (code 87, see health.py)
#   "flight_path": "",         # "" = <events_dir>/flight.json
#   "detectors": {
#     "enabled": true,
#     "nonfinite_streak": 3,        # NaN/inf losses in a row -> alert
#     "spike_zscore": 6.0,          # rolling z-score spike threshold
#     "spike_window": 32,           # rolling window (steps)
#     "grad_norm_max": 1.0e4,       # grad-norm explosion ceiling
#     "scale_collapse_below": 2.0,  # dynamic loss-scale floor
#     "recompile_storm_count": 3,   # compiles within ...
#     "recompile_storm_window": 16  # ... this many steps -> alert
#   }
# }
OBS_HEALTH = "health"
OBS_HEALTH_ENABLED = "enabled"
OBS_HEALTH_ENABLED_DEFAULT = False
OBS_HEALTH_RING_EVENTS = "ring_events"
OBS_HEALTH_RING_EVENTS_DEFAULT = 256
OBS_HEALTH_STALL_TIMEOUT_S = "stall_timeout_s"
OBS_HEALTH_STALL_TIMEOUT_S_DEFAULT = 0.0
OBS_HEALTH_ON_STALL = "on_stall"
OBS_HEALTH_ON_STALL_DEFAULT = "warn"
OBS_HEALTH_FLIGHT_PATH = "flight_path"
OBS_HEALTH_FLIGHT_PATH_DEFAULT = ""
OBS_HEALTH_DETECTORS = "detectors"
OBS_HEALTH_DET_ENABLED = "enabled"
OBS_HEALTH_DET_ENABLED_DEFAULT = True
OBS_HEALTH_DET_NONFINITE_STREAK = "nonfinite_streak"
OBS_HEALTH_DET_NONFINITE_STREAK_DEFAULT = 3
OBS_HEALTH_DET_SPIKE_ZSCORE = "spike_zscore"
OBS_HEALTH_DET_SPIKE_ZSCORE_DEFAULT = 6.0
OBS_HEALTH_DET_SPIKE_WINDOW = "spike_window"
OBS_HEALTH_DET_SPIKE_WINDOW_DEFAULT = 32
OBS_HEALTH_DET_GRAD_NORM_MAX = "grad_norm_max"
OBS_HEALTH_DET_GRAD_NORM_MAX_DEFAULT = 1.0e4
OBS_HEALTH_DET_SCALE_COLLAPSE_BELOW = "scale_collapse_below"
OBS_HEALTH_DET_SCALE_COLLAPSE_BELOW_DEFAULT = 2.0
OBS_HEALTH_DET_RECOMPILE_STORM_COUNT = "recompile_storm_count"
OBS_HEALTH_DET_RECOMPILE_STORM_COUNT_DEFAULT = 3
OBS_HEALTH_DET_RECOMPILE_STORM_WINDOW = "recompile_storm_window"
OBS_HEALTH_DET_RECOMPILE_STORM_WINDOW_DEFAULT = 16

#############################################
# Async step pipeline (TPU-native: the host must never sit between two
# device steps. One scan-fused compiled program per global batch, a
# background prefetch stage that overlaps H2D with compute, and
# deferred loss telemetry so steady-state steps enqueue work and
# return without a device round-trip; see docs/performance.md
# "Async step pipeline".)
#
# "async_pipeline": {
#   "fused_accumulation": true,   # lax.scan over the gas micro batches
#                                 # inside ONE jit (auto-falls back to
#                                 # the per-micro loop for offload/
#                                 # 1-bit/sparse-grad configs)
#   "prefetch_depth": 2,          # batches in flight in the background
#                                 # prefetch thread; 0 disables it
#   "sync_loss_every_step": false # true restores the old per-step
#                                 # float(loss) device sync
# }
#############################################
ASYNC_PIPELINE = "async_pipeline"
ASYNC_FUSED_ACCUMULATION = "fused_accumulation"
ASYNC_FUSED_ACCUMULATION_DEFAULT = True
ASYNC_PREFETCH_DEPTH = "prefetch_depth"
ASYNC_PREFETCH_DEPTH_DEFAULT = 2
ASYNC_SYNC_LOSS_EVERY_STEP = "sync_loss_every_step"
ASYNC_SYNC_LOSS_EVERY_STEP_DEFAULT = False

#############################################
# Persistent XLA compilation cache (TPU-native: the first jit of a
# large model costs a minute or more; caching the compiled executable
# on disk makes re-runs, benchmark runs, and resumed jobs start hot.
# No reference analog: CUDA kernels there are AOT-built at install time
# via DS_BUILD_* env flags, setup.py:47-68 — this knob is the JIT-world
# equivalent.)
#
# "compile_cache": {
#   "enabled": true,
#   "dir": null,               # null = <checkout>/.jax_cache
#                              # (utils/platform.py); the environment's
#                              # JAX_COMPILATION_CACHE_DIR wins over both
#   "min_compile_secs": 1.0    # don't cache trivial programs
# }
#############################################
COMPILE_CACHE = "compile_cache"
COMPILE_CACHE_ENABLED = "enabled"
COMPILE_CACHE_ENABLED_DEFAULT = True
COMPILE_CACHE_DIR = "dir"
COMPILE_CACHE_DIR_DEFAULT = None
COMPILE_CACHE_MIN_COMPILE_SECS = "min_compile_secs"
COMPILE_CACHE_MIN_COMPILE_SECS_DEFAULT = 1.0

#############################################
# Fault-tolerant checkpointing (TPU-native: preemption mid-save is the
# expected failure mode on TPU pods — every save is atomically
# committed, every load verified, recovery automatic; see
# runtime/checkpoint.py and docs/checkpointing.md)
#
# "checkpoint": {
#   "verify_checksums": true,   # CRC32-verify files against COMMITTED
#   "keep_n": 0,                # retention: 0 keeps all committed tags
#   "io_retries": 3,            # transient-OSError retries per file op
#   "io_retry_backoff": 0.05,   # base seconds, doubles per attempt
#   "async_save": false,        # snapshot at the boundary, commit in a
#                               # background writer (docs/checkpointing.md
#                               # "Async snapshot saves")
#   "drain_on_preemption": false, # SIGTERM/SIGINT -> finish window,
#                               # commit preempt tag, exit resumable (85)
#   "save_dir": null,           # where the preemption drain commits
#                               # (default: last save/load dir used)
#   "supervisor": {             # launcher relaunch-on-preemption policy
#     "max_restarts": 3,        # give up after this many resumable exits
#     "backoff": 1.0            # base seconds, doubles per restart
#   }
# }
#############################################
CHECKPOINT = "checkpoint"
CHECKPOINT_VERIFY_CHECKSUMS = "verify_checksums"
CHECKPOINT_VERIFY_CHECKSUMS_DEFAULT = True
CHECKPOINT_KEEP_N = "keep_n"
CHECKPOINT_KEEP_N_DEFAULT = 0
CHECKPOINT_IO_RETRIES = "io_retries"
CHECKPOINT_IO_RETRIES_DEFAULT = 3
CHECKPOINT_IO_RETRY_BACKOFF = "io_retry_backoff"
CHECKPOINT_IO_RETRY_BACKOFF_DEFAULT = 0.05
CHECKPOINT_ASYNC_SAVE = "async_save"
CHECKPOINT_ASYNC_SAVE_DEFAULT = False
CHECKPOINT_DRAIN_ON_PREEMPTION = "drain_on_preemption"
CHECKPOINT_DRAIN_ON_PREEMPTION_DEFAULT = False
CHECKPOINT_SAVE_DIR = "save_dir"
CHECKPOINT_SAVE_DIR_DEFAULT = None
CHECKPOINT_SUPERVISOR = "supervisor"
CHECKPOINT_SUPERVISOR_MAX_RESTARTS = "max_restarts"
CHECKPOINT_SUPERVISOR_MAX_RESTARTS_DEFAULT = 3
CHECKPOINT_SUPERVISOR_BACKOFF = "backoff"
CHECKPOINT_SUPERVISOR_BACKOFF_DEFAULT = 1.0

#############################################
# Inference serving engine (TPU-native extension: the reference
# snapshot is training-only. Bucketed prefill/decode over a
# preallocated donated KV cache + continuous-batching scheduler;
# see deepspeed_tpu/inference/ and docs/inference.md.)
#
# "inference": {
#   "max_batch_size": 8,          # concurrent decode slots
#   "prompt_buckets": [64, 256],  # prompt pad lengths (ascending)
#   "batch_buckets": [1, 8],      # prefill batch pad sizes (ascending)
#   "max_seq_len": 1024,          # KV-cache length (prompt + generated)
#   "max_new_tokens": 128,        # per-request default
#   "temperature": 0.0,           # 0 = greedy (per-request overridable)
#   "top_k": 0,                   # engine-global (compiled-in) filter
#   "eos_token_id": null,         # default stop token
#   "events_dir": "",             # serving events.jsonl ("" disables)
#   "quantize_weights": false,    # qwZ int8 block weight shipping:
#                                 # false | "bf16" (wire-only, eager
#                                 # dequant; true is an alias) | "int8"
#                                 # (int8-RESIDENT weights — compiled
#                                 # programs dequant per block at each
#                                 # matmul, ~2x less weight HBM)
#   "quantize_block": 256,        # qwZ block size
#   "admit_lookahead": 4,         # HOL fix: queue entries scanned for a
#                                 # head that fits (0 = strict FIFO)
#   "paged_kv": {                 # paged/block KV cache (default path;
#                                 # occupancy ~ tokens in flight, not
#                                 # slots x max_len)
#     "enabled": true,            # false = dense slot x max_len cache
#     "page_size": 16,            # tokens per page
#     "num_pages": 0,             # pool size incl. null page; 0 = auto
#                                 # (dense-equivalent worst case)
#     "prefix_cache": true,       # hash-dedup shared prompt prefixes
#     "attn_kernel": "pallas",    # decode attention: fused Pallas
#                                 # paged kernel (O(live tokens) pool
#                                 # reads) | "gather" (stripe oracle);
#                                 # unsupported geometries auto-fall
#                                 # back to gather with a one-line log
#     "decode_page_buckets": [],  # table-width buckets (pages) for the
#                                 # decode dispatch; [] = one program
#                                 # at full pages_per_seq width. More
#                                 # buckets = one decode program per
#                                 # width at warmup; gather fallback
#                                 # bandwidth then scales with the
#                                 # batch's LIVE pages, not max_len
#     "kv_dtype": null,           # pool payload dtype: null = the
#                                 # engine dtype; "int8" = quantized
#                                 # pool (per-token-row fp32 scales
#                                 # ride alongside, dequant in-kernel)
#     "kv_quant_block": 0         # int8 pool scale block over
#                                 # head_dim; 0 = one scale per token
#                                 # row (must divide head_dim)
#   },
#   "mesh": {                     # serving mesh (GSPMD NamedShardings)
#     "axes": {}                  # e.g. {"model": 4}: tensor-parallel
#                                 # prefill/decode over ICI
#   },
#   "chunked_prefill": {          # long-prompt chunked prefill
#     "enabled": false,           # requires paged_kv.enabled; prompts
#                                 # whose suffix exceeds the largest
#                                 # prompt bucket prefill chunk-by-
#                                 # chunk, interleaved with decode
#                                 # (at most one chunk dispatch/step)
#     "chunk_tokens": 256,        # tokens per chunk dispatch (one
#                                 # compiled chunk program per batch
#                                 # bucket — no prompt-bucket ladder)
#     "cp_threshold_tokens": 0    # prompts at least this long run
#                                 # their chunks context-parallel
#                                 # (ring attention over the serving
#                                 # mesh); 0 = off
#   },
#   "spec_decode": {              # speculative multi-token decoding
#     "enabled": false,           # requires paged_kv.enabled
#     "k": 4,                     # max draft tokens proposed/dispatch
#     "method": "ngram",          # "ngram" (prompt-lookup; host-side,
#                                 # no second model) | "callable"
#                                 # (engine-injected small draft model)
#     "ngram_min": 1,             # shortest suffix match tried
#     "ngram_max": 3,             # longest suffix match tried first
#     "verify_widths": []         # compiled verify seq widths;
#                                 # [] = one program at k + 1
#   },
#   "disagg": {                   # disaggregated prefill/decode workers
#     "enabled": false,           # requires paged_kv.enabled
#     "separate_pools": null,     # null = auto (true iff decode_mesh
#                                 # axes set); true forces a prefill
#                                 # pool + priced page handoff
#     "prefill_pages": 0,         # prefill pool size; 0 = auto
#     "decode_mesh": {            # decode worker's own mesh (else the
#       "axes": {}                # decode loop shares inference.mesh)
#     }
#   },
#   "fleet": {                    # multi-replica router (inference/
#                                 # fleet.py FleetRouter)
#     "replicas": 1,              # in-process engine replicas fronted
#     "routing": "least_loaded",  # | "prefix_affinity" (route to the
#                                 # replica whose prefix cache covers
#                                 # the most prompt tokens)
#     "slo_shed": {               # SLO-driven admission (goodput > raw
#                                 # throughput)
#       "enabled": false,
#       "ttft_budget_ms": null,   # p95 TTFT budget; null = the
#                                 # observability.serve.slo.ttft_ms SLO
#       "min_samples": 8,         # TTFTs before the ladder may engage
#       "shed_below_priority": 1, # rung 1: reject requests with
#                                 # priority < this while p95 breaches
#       "degrade_factor": 2.0,    # rung 2 at budget x factor: cap
#                                 # max_new + switch speculation off
#       "degrade_max_new": 32     # the rung-2 max_new cap (0 = no cap)
#     },
#     "swap": {                   # live weight swap (engine.swap_params)
#       "verify_integrity": true  # CRC-verify the tag before pushing
#     }
#   }
# }
#############################################
INFERENCE = "inference"
INF_MAX_BATCH_SIZE = "max_batch_size"
INF_MAX_BATCH_SIZE_DEFAULT = 8
INF_PROMPT_BUCKETS = "prompt_buckets"
INF_PROMPT_BUCKETS_DEFAULT = (64, 256)
INF_BATCH_BUCKETS = "batch_buckets"
INF_BATCH_BUCKETS_DEFAULT = (1, 8)
INF_MAX_SEQ_LEN = "max_seq_len"
INF_MAX_SEQ_LEN_DEFAULT = 1024
INF_MAX_NEW_TOKENS = "max_new_tokens"
INF_MAX_NEW_TOKENS_DEFAULT = 128
INF_TEMPERATURE = "temperature"
INF_TEMPERATURE_DEFAULT = 0.0
INF_TOP_K = "top_k"
INF_TOP_K_DEFAULT = 0
INF_EOS_TOKEN_ID = "eos_token_id"
INF_EOS_TOKEN_ID_DEFAULT = None
INF_EVENTS_DIR = "events_dir"
INF_EVENTS_DIR_DEFAULT = ""
INF_QUANTIZE_WEIGHTS = "quantize_weights"
INF_QUANTIZE_WEIGHTS_DEFAULT = False
INF_QUANTIZE_BLOCK = "quantize_block"
INF_QUANTIZE_BLOCK_DEFAULT = 256
INF_ADMIT_LOOKAHEAD = "admit_lookahead"
INF_ADMIT_LOOKAHEAD_DEFAULT = 4
INF_PAGED_KV = "paged_kv"
INF_PAGED_ENABLED = "enabled"
INF_PAGED_ENABLED_DEFAULT = True
INF_PAGED_PAGE_SIZE = "page_size"
INF_PAGED_PAGE_SIZE_DEFAULT = 16
INF_PAGED_NUM_PAGES = "num_pages"
INF_PAGED_NUM_PAGES_DEFAULT = 0     # 0 = auto (dense-equivalent pool)
INF_PAGED_PREFIX_CACHE = "prefix_cache"
INF_PAGED_PREFIX_CACHE_DEFAULT = True
INF_PAGED_ATTN_KERNEL = "attn_kernel"
INF_PAGED_ATTN_KERNEL_DEFAULT = "pallas"   # "gather" = stripe fallback
INF_PAGED_DECODE_PAGE_BUCKETS = "decode_page_buckets"
INF_PAGED_DECODE_PAGE_BUCKETS_DEFAULT = ()  # () = one full-width program
INF_PAGED_KV_DTYPE = "kv_dtype"
INF_PAGED_KV_DTYPE_DEFAULT = None   # None = follow the engine dtype
INF_PAGED_KV_QUANT_BLOCK = "kv_quant_block"
INF_PAGED_KV_QUANT_BLOCK_DEFAULT = 0  # 0 = one scale per token row
INF_MESH = "mesh"
INF_MESH_AXES = "axes"
# chunked prefill (long prompts): split prefill into fixed
# chunk_tokens-sized dispatches interleaved with decode steps — TBT
# stays bounded under long prompts, ONE compiled chunk program per
# batch bucket replaces the prompt-bucket ladder for chunked requests,
# and prompts past the largest bucket (up to max_seq_len) serve
# instead of rejecting. cp_threshold_tokens >= chunk-size routes
# chunks of prompts at least that long through the context-parallel
# (ring attention) prefill program over the serving mesh (0 = off).
INF_CHUNKED_PREFILL = "chunked_prefill"
INF_CHUNK_ENABLED = "enabled"
INF_CHUNK_ENABLED_DEFAULT = False
INF_CHUNK_TOKENS = "chunk_tokens"
INF_CHUNK_TOKENS_DEFAULT = 256
INF_CHUNK_CP_THRESHOLD = "cp_threshold_tokens"
INF_CHUNK_CP_THRESHOLD_DEFAULT = 0   # 0 = context-parallel off
INF_SPEC_DECODE = "spec_decode"
INF_SPEC_ENABLED = "enabled"
INF_SPEC_ENABLED_DEFAULT = False
INF_SPEC_K = "k"
INF_SPEC_K_DEFAULT = 4
INF_SPEC_METHOD = "method"
INF_SPEC_METHOD_DEFAULT = "ngram"
INF_SPEC_NGRAM_MIN = "ngram_min"
INF_SPEC_NGRAM_MIN_DEFAULT = 1
INF_SPEC_NGRAM_MAX = "ngram_max"
INF_SPEC_NGRAM_MAX_DEFAULT = 3
INF_SPEC_VERIFY_WIDTHS = "verify_widths"
INF_SPEC_VERIFY_WIDTHS_DEFAULT = ()  # () = one program at k + 1
INF_DISAGG = "disagg"
INF_DISAGG_ENABLED = "enabled"
INF_DISAGG_ENABLED_DEFAULT = False
INF_DISAGG_SEPARATE_POOLS = "separate_pools"
INF_DISAGG_SEPARATE_POOLS_DEFAULT = None  # auto: decode_mesh axes set
INF_DISAGG_PREFILL_PAGES = "prefill_pages"
INF_DISAGG_PREFILL_PAGES_DEFAULT = 0     # 0 = auto
INF_DISAGG_DECODE_MESH = "decode_mesh"
INF_FLEET = "fleet"
INF_FLEET_REPLICAS = "replicas"
INF_FLEET_REPLICAS_DEFAULT = 1
INF_FLEET_ROUTING = "routing"
INF_FLEET_ROUTING_DEFAULT = "least_loaded"
INF_FLEET_ROUTING_CHOICES = ("least_loaded", "prefix_affinity")
INF_FLEET_SLO_SHED = "slo_shed"
INF_FLEET_SHED_ENABLED = "enabled"
INF_FLEET_SHED_ENABLED_DEFAULT = False
INF_FLEET_SHED_TTFT_BUDGET_MS = "ttft_budget_ms"
INF_FLEET_SHED_TTFT_BUDGET_MS_DEFAULT = None  # None = serve SLO ttft_ms
INF_FLEET_SHED_MIN_SAMPLES = "min_samples"
INF_FLEET_SHED_MIN_SAMPLES_DEFAULT = 8
INF_FLEET_SHED_BELOW_PRIORITY = "shed_below_priority"
INF_FLEET_SHED_BELOW_PRIORITY_DEFAULT = 1
INF_FLEET_SHED_DEGRADE_FACTOR = "degrade_factor"
INF_FLEET_SHED_DEGRADE_FACTOR_DEFAULT = 2.0
INF_FLEET_SHED_DEGRADE_MAX_NEW = "degrade_max_new"
INF_FLEET_SHED_DEGRADE_MAX_NEW_DEFAULT = 32  # 0 = no cap
INF_FLEET_SWAP = "swap"
INF_FLEET_SWAP_VERIFY_INTEGRITY = "verify_integrity"
INF_FLEET_SWAP_VERIFY_INTEGRITY_DEFAULT = True
# process-isolated fleet (ISSUE 16): one engine per child process,
# fronted over the inference/rpc.py channel
INF_FLEET_PROCESS_MODE = "process_mode"
INF_FLEET_PM_ENABLED = "enabled"
INF_FLEET_PM_ENABLED_DEFAULT = False
INF_FLEET_PM_RPC_TIMEOUT_S = "rpc_timeout_s"
INF_FLEET_PM_RPC_TIMEOUT_S_DEFAULT = 120.0
INF_FLEET_PM_RPC_RETRIES = "rpc_retries"
INF_FLEET_PM_RPC_RETRIES_DEFAULT = 2
INF_FLEET_PM_RPC_BACKOFF_S = "rpc_backoff_s"
INF_FLEET_PM_RPC_BACKOFF_S_DEFAULT = 0.05
INF_FLEET_PM_MAX_RESTARTS = "max_restarts"
INF_FLEET_PM_MAX_RESTARTS_DEFAULT = 1
INF_FLEET_PM_RESTART_BACKOFF_S = "restart_backoff_s"
INF_FLEET_PM_RESTART_BACKOFF_S_DEFAULT = 0.5
INF_FLEET_PM_READY_TIMEOUT_S = "ready_timeout_s"
INF_FLEET_PM_READY_TIMEOUT_S_DEFAULT = 300.0
# goodput-driven autoscale (ISSUE 16): spawn on sustained rung-1
# shedding, retire (drain-via-migration) on sustained idleness
INF_FLEET_AUTOSCALE = "autoscale"
INF_FLEET_AS_ENABLED = "enabled"
INF_FLEET_AS_ENABLED_DEFAULT = False
INF_FLEET_AS_MIN_REPLICAS = "min_replicas"
INF_FLEET_AS_MIN_REPLICAS_DEFAULT = 1
INF_FLEET_AS_MAX_REPLICAS = "max_replicas"
INF_FLEET_AS_MAX_REPLICAS_DEFAULT = 4
INF_FLEET_AS_UP_PATIENCE = "scale_up_patience"
INF_FLEET_AS_UP_PATIENCE_DEFAULT = 4
INF_FLEET_AS_DOWN_PATIENCE = "scale_down_patience"
INF_FLEET_AS_DOWN_PATIENCE_DEFAULT = 64
INF_FLEET_AS_COOLDOWN_STEPS = "cooldown_steps"
INF_FLEET_AS_COOLDOWN_STEPS_DEFAULT = 16

TENSORBOARD = "tensorboard"
TENSORBOARD_ENABLED = "enabled"
TENSORBOARD_ENABLED_DEFAULT = False
TENSORBOARD_OUTPUT_PATH = "output_path"
TENSORBOARD_OUTPUT_PATH_DEFAULT = ""
TENSORBOARD_JOB_NAME = "job_name"
TENSORBOARD_JOB_NAME_DEFAULT = "DeepSpeedTPUJobName"

#############################################
# Sparse attention (reference config.py:156-317)
#############################################
SPARSE_ATTENTION = "sparse_attention"
SPARSE_DENSE_MODE = "dense"
SPARSE_FIXED_MODE = "fixed"
SPARSE_VARIABLE_MODE = "variable"
SPARSE_BIGBIRD_MODE = "bigbird"
SPARSE_BSLONGFORMER_MODE = "bslongformer"
SPARSE_MODE = "mode"
SPARSE_MODE_DEFAULT = SPARSE_FIXED_MODE
SPARSE_BLOCK = "block"
SPARSE_BLOCK_DEFAULT = 16
SPARSE_DIFFERENT_LAYOUT_PER_HEAD = "different_layout_per_head"
SPARSE_DIFFERENT_LAYOUT_PER_HEAD_DEFAULT = False
SPARSE_NUM_LOCAL_BLOCKS = "num_local_blocks"
SPARSE_NUM_LOCAL_BLOCKS_DEFAULT = 4
SPARSE_NUM_GLOBAL_BLOCKS = "num_global_blocks"
SPARSE_NUM_GLOBAL_BLOCKS_DEFAULT = 1
SPARSE_ATTENTION_TYPE = "attention"
SPARSE_ATTENTION_TYPE_DEFAULT = "bidirectional"
SPARSE_HORIZONTAL_GLOBAL_ATTENTION = "horizontal_global_attention"
SPARSE_HORIZONTAL_GLOBAL_ATTENTION_DEFAULT = False
SPARSE_NUM_DIFFERENT_GLOBAL_PATTERNS = "num_different_global_patterns"
SPARSE_NUM_DIFFERENT_GLOBAL_PATTERNS_DEFAULT = 1
SPARSE_NUM_RANDOM_BLOCKS = "num_random_blocks"
SPARSE_NUM_RANDOM_BLOCKS_DEFAULT = 0
SPARSE_LOCAL_WINDOW_BLOCKS = "local_window_blocks"
SPARSE_LOCAL_WINDOW_BLOCKS_DEFAULT = [4]
SPARSE_GLOBAL_BLOCK_INDICES = "global_block_indices"
SPARSE_GLOBAL_BLOCK_INDICES_DEFAULT = [0]
SPARSE_GLOBAL_BLOCK_END_INDICES = "global_block_end_indices"
SPARSE_GLOBAL_BLOCK_END_INDICES_DEFAULT = None
SPARSE_NUM_SLIDING_WINDOW_BLOCKS = "num_sliding_window_blocks"
SPARSE_NUM_SLIDING_WINDOW_BLOCKS_DEFAULT = 3

#############################################
# Pipeline (reference config.py:327)
#############################################
PIPELINE = "pipeline"
PIPELINE_STAGES = "stages"
PIPELINE_STAGES_DEFAULT = None
PIPELINE_PARTITION = "partition"
PIPELINE_PARTITION_DEFAULT = "best"
PIPELINE_SEED_LAYERS = "seed_layers"
PIPELINE_SEED_LAYERS_DEFAULT = False
PIPELINE_ACTIVATION_CHECKPOINT_INTERVAL = "activation_checkpoint_interval"
PIPELINE_ACTIVATION_CHECKPOINT_INTERVAL_DEFAULT = 0

#############################################
# Activation checkpointing (reference activation_checkpointing/config.py:59)
#############################################
ACTIVATION_CHECKPOINTING = "activation_checkpointing"
ACT_CKPT_PARTITION_ACTIVATIONS = "partition_activations"
ACT_CKPT_PARTITION_ACTIVATIONS_DEFAULT = False
ACT_CKPT_NUMBER_CHECKPOINTS = "number_checkpoints"
ACT_CKPT_NUMBER_CHECKPOINTS_DEFAULT = None
ACT_CKPT_CONTIGUOUS_MEMORY_OPTIMIZATION = "contiguous_memory_optimization"
ACT_CKPT_CONTIGUOUS_MEMORY_OPTIMIZATION_DEFAULT = False
ACT_CKPT_SYNCHRONIZE_CHECKPOINT_BOUNDARY = "synchronize_checkpoint_boundary"
ACT_CKPT_SYNCHRONIZE_CHECKPOINT_BOUNDARY_DEFAULT = False
ACT_CKPT_CPU_CHECKPOINTING = "cpu_checkpointing"
ACT_CKPT_CPU_CHECKPOINTING_DEFAULT = False
ACT_CKPT_PROFILE = "profile"
ACT_CKPT_PROFILE_DEFAULT = False

#############################################
# Mesh (TPU-native extension: named-axis device mesh)
#############################################
MESH = "mesh"
MESH_AXES = "axes"  # e.g. {"data": 8, "model": 1, "pipe": 1}
