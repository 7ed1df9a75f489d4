"""DeepSpeedEngine — the training runtime.

TPU-native analog of the reference's ``deepspeed/runtime/engine.py:96``.
Same facade (``forward`` :729 / ``backward`` :767 / ``step`` :903,
``save_checkpoint`` :1329 / ``load_checkpoint`` :1173, gradient-accumulation
boundary logic :843), completely different execution model:

- The reference is eager: backward hooks bucket per-param grads onto side
  CUDA streams (stage2.py:591), allreduce is hand-bucketed (engine.py:1013),
  overlap is hand-scheduled. Here one **compiled micro-step** holds forward,
  backward, gradient accumulation, and the (conditional) optimizer update;
  XLA schedules all collectives (psum/reduce-scatter/all-gather over the
  ``data`` mesh axis) with overlap.
- ZeRO stages are *sharding assignments* on the master/optimizer pytrees
  (see runtime/zero/sharding.py), not separate optimizer classes.
- fp16 dynamic loss scaling runs inside jit via ``lax.cond`` — no host
  round-trip per step (loss_scaler.py). bf16 is the TPU-native default.

Model contract: ``model`` is a pure loss function
``loss_fn(params, batch [, rng]) -> loss | (loss, aux)``; ``model_parameters``
is the initial fp32 pytree. (The reference wrapped an nn.Module; in JAX the
trainable object *is* (fn, params). ``deepspeed_tpu.flax_loss_fn`` adapts a
flax module + criterion to this contract.)
"""

import inspect
import time
import os
from contextlib import contextmanager
from functools import partial
from typing import Any, Callable, Dict, NamedTuple, Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import NamedSharding, PartitionSpec

from deepspeed_tpu import distributed as dist
from deepspeed_tpu.ops.optimizers import Optimizer, build_optimizer
from deepspeed_tpu.parallel.pallas_shard import pallas_kernel_mesh
from deepspeed_tpu.parallel.mesh import (axis_size, build_mesh,
                                         data_axis_names, data_axis_size,
                                         split_data_axis)
from deepspeed_tpu.parallel.topology import ParallelGrid
from deepspeed_tpu.profiling.recompile import setup_span
from deepspeed_tpu.profiling.spans import scope
from deepspeed_tpu.runtime import checkpoint as ckpt
from deepspeed_tpu.runtime import elastic
from deepspeed_tpu.runtime import fault
from deepspeed_tpu.runtime.config import DeepSpeedConfig
from deepspeed_tpu.runtime.dataloader import (
    DeepSpeedDataLoader, PrefetchLoader, RepeatingLoader,
    normalize_eval_input, stack_micro_batches)
from deepspeed_tpu.runtime.fp16.loss_scaler import (
    DynamicLossScaler, LossScaleState, StaticLossScaler, has_overflow)
from deepspeed_tpu.runtime.lr_schedules import build_lr_schedule
from deepspeed_tpu.runtime.zero.sharding import (
    replicated_shardings, zero_shardings)
from deepspeed_tpu.utils.logging import log_dist, logger
from deepspeed_tpu.utils.timer import SynchronizedWallClockTimer, ThroughputTimer

MEMORY_OPT_ALLREDUCE_SIZE = 500000000


class TrainState(NamedTuple):
    """All device-resident training state; a pure pytree so the whole step
    is functional (and shardable leaf-by-leaf)."""
    params: Any            # fp32 master params
    opt_state: Any
    accum_grads: Any       # () when gradient_accumulation_steps == 1
    loss_scale: LossScaleState
    global_step: jnp.ndarray    # optimizer steps taken
    micro_step: jnp.ndarray     # micro batches seen since last boundary
    skipped_steps: jnp.ndarray  # overflow-skipped optimizer steps
    rng: jnp.ndarray            # PRNG key threaded through the model


def _tree_cast(tree, dtype):
    if dtype is None:
        return tree
    return jax.tree_util.tree_map(
        lambda x: x.astype(dtype) if jnp.issubdtype(x.dtype, jnp.floating)
        else x, tree)


def _path_key(path) -> str:
    return "/".join(str(getattr(p, "key", getattr(p, "idx",
                                                  getattr(p, "name", p))))
                    for p in path)


_EMBEDDING_NAME_RE = None


def _detect_embedding_paths(params) -> set:
    """Leaf paths that look like lookup embeddings: 2-D float leaves whose
    name contains emb/embed/embedding/wte/word_embeddings (reference
    converts grads of ``nn.Embedding`` modules, engine.py:181-187)."""
    global _EMBEDDING_NAME_RE
    if _EMBEDDING_NAME_RE is None:
        import re
        _EMBEDDING_NAME_RE = re.compile(
            r"(^|[/_.])(emb|embed|embedding|embeddings|wte|word_embeddings)"
            r"($|[/_.])", re.IGNORECASE)
    out = set()
    for path, leaf in jax.tree_util.tree_flatten_with_path(params)[0]:
        key = _path_key(path)
        if (hasattr(leaf, "ndim") and leaf.ndim == 2
                and jnp.issubdtype(leaf.dtype, jnp.floating)
                and _EMBEDDING_NAME_RE.search(key)):
            out.add(key)
    return out


from deepspeed_tpu.runtime.utils import global_norm as _global_norm


class DeepSpeedEngine:

    @setup_span("setup/engine")
    def __init__(self,
                 args=None,
                 model: Callable = None,
                 optimizer: Optional[Optimizer] = None,
                 model_parameters: Any = None,
                 training_data=None,
                 lr_scheduler=None,
                 mpu: Optional[ParallelGrid] = None,
                 param_specs: Any = None,
                 collate_fn=None,
                 config: Any = None,
                 config_params: Any = None,
                 dont_change_device: bool = False,
                 seed: int = 0):
        assert model is not None, "deepspeed_tpu.initialize requires a model (loss fn)"
        assert model_parameters is not None, \
            "deepspeed_tpu.initialize requires model_parameters (init pytree)"

        dist.init_distributed()

        # -- config + mesh (mesh decides the dp world size for the batch
        #    triangle, so it is built first) --
        raw = config if config is not None else config_params
        if raw is None and args is not None and \
                getattr(args, "deepspeed_config", None):
            raw = args.deepspeed_config
        assert raw is not None, "a DeepSpeed config (dict or path) is required"
        if isinstance(raw, str):
            import json as _json
            with open(raw) as f:
                raw = _json.load(f)

        mesh_axes = raw.get("mesh", {}).get("axes") if isinstance(raw, dict) else None
        # hierarchical quantized comm (ZeRO++ 2D shapes) splits the data
        # axis into data_inter x data_intra BEFORE the mesh is built, so
        # every downstream sharding sees the 2D form
        _qc_hier = 0
        self._comm_plan = None
        if isinstance(raw, dict):
            from deepspeed_tpu.runtime.config import (
                get_comm_autotune_config, get_quantized_comm_config)
            _qc_raw = get_quantized_comm_config(raw)
            # the split is gated on enabled: a disabled quantized_comm
            # block must leave the mesh (and every 'data'-keyed path)
            # exactly as before
            if _qc_raw["enabled"]:
                _qc_hier = int(_qc_raw["hierarchical"])
                if get_comm_autotune_config(raw)["enabled"]:
                    # topology-aware autotuner: picks algo/block AND the
                    # hierarchy split, which must be known pre-mesh
                    self._comm_plan = self._plan_comm_autotune(
                        raw, _qc_raw, mesh_axes, model_parameters)
                if self._comm_plan is not None:
                    _qc_hier = self._comm_plan.hierarchical
                    if _qc_hier >= 2:
                        from deepspeed_tpu.parallel.mesh import \
                            resolve_axis_sizes
                        # the split below needs concrete sizes, not -1
                        mesh_axes = resolve_axis_sizes(
                            mesh_axes, len(jax.devices()))
        if _qc_hier >= 2:
            if mesh_axes is None:
                mesh_axes = {"data": len(jax.devices())}
            mesh_axes = split_data_axis(mesh_axes, _qc_hier)
        self.mesh = build_mesh(mesh_axes)
        # dp axes: ("data",), or ("data_inter", "data_intra") on a
        # hierarchical mesh; dp_world_size is their product
        self.dp_axes = data_axis_names(self.mesh) or ("data",)
        self._dp_hierarchical = len(self.dp_axes) > 1
        # the PartitionSpec dim entry that shards over the full dp degree
        self._dp_axis_entry = (self.dp_axes if self._dp_hierarchical
                               else self.dp_axes[0])
        self.dp_world_size = data_axis_size(self.mesh)
        self.mp_world_size = axis_size(self.mesh, "model")
        # make the mesh known to the activation-checkpointing subsystem so
        # partition_activations can shard the stash (the reference threads
        # mpu into deepspeed.checkpointing.configure; here the mesh is it)
        from deepspeed_tpu.runtime.activation_checkpointing import (
            checkpointing as _ds_ckpt)
        _ds_ckpt.set_mesh(self.mesh)

        self._config = DeepSpeedConfig(raw, world_size=self.dp_world_size)
        self.mpu = mpu

        # -- precision policy --
        self.fp16_enabled = self._config.fp16_enabled
        self.bf16_enabled = self._config.bf16_enabled
        if self.fp16_enabled:
            self.compute_dtype = jnp.float16
        elif self.bf16_enabled:
            self.compute_dtype = jnp.bfloat16
        else:
            self.compute_dtype = None  # fp32 end to end
        # Master-weight-free bf16 (TPU-native analog of the reference's
        # __STOCHASTIC_MODE__ kernels, setup.py:211-242 there): params are
        # held in bf16 end-to-end — no fp32 master copy, saving 4
        # bytes/param of HBM (and, at stage 3, halving the param
        # all-gather bytes) — and the optimizer casts its fp32 update
        # result back with stochastic rounding so sub-ulp steps
        # accumulate in expectation instead of RNE-truncating to zero.
        self.bf16_master_weights = self._config.bf16_master_weights
        self.bf16_stochastic_rounding = self._config.bf16_stochastic_rounding

        if self.fp16_enabled:
            if self._config.loss_scale == 0:
                ls_args = self._config.dynamic_loss_scale_args or {}
                self.loss_scaler = DynamicLossScaler(
                    init_scale=ls_args.get("init_scale",
                                           self._config.initial_dynamic_scale),
                    scale_window=ls_args.get("scale_window", 1000),
                    min_scale=ls_args.get("min_scale", 1.0),
                    delayed_shift=ls_args.get("delayed_shift", 1))
            else:
                self.loss_scaler = StaticLossScaler(self._config.loss_scale)
        else:
            self.loss_scaler = StaticLossScaler(1.0)

        # -- model / loss fn --
        self._loss_fn = model
        sig_params = None
        try:
            sig_params = len(inspect.signature(model).parameters)
        except (TypeError, ValueError):
            pass
        self._loss_takes_rng = (sig_params == 3)

        # -- optimizer --
        self.client_optimizer = optimizer
        # ZeRO-Offload (reference zero/stage2.py:334-350 cpu_offload path):
        # fp32 master + moments live on the host, updated by the native
        # C++ SIMD Adam (csrc/adam/cpu_adam.cpp); the device holds only
        # compute-dtype params and grads.
        self.zero_cpu_offload = bool(
            self._config.zero_config.stage >= 1 and
            self._config.zero_config.cpu_offload)
        # overlap_comm + cpu_offload: host Adam overlaps the next window's
        # device compute (one-window-delayed updates; reference overlaps
        # D2H/H2D on side streams, stage2.py:291-294)
        self._offload_overlap = bool(
            self.zero_cpu_offload and self._config.zero_config.overlap_comm)
        self._offload_pending = None
        self._offload_pool = None
        if self._offload_overlap:
            from concurrent.futures import ThreadPoolExecutor
            self._offload_pool = ThreadPoolExecutor(
                max_workers=1, thread_name_prefix="ds-offload")
        if self.zero_cpu_offload:
            assert optimizer is None, \
                "client optimizers are unsupported with cpu_offload"
            name = (self._config.optimizer_name or "adam").lower()
            assert "adam" in name and "onebit" not in name, \
                "ZeRO-Offload requires a plain Adam-family optimizer (the " \
                "reference drives DeepSpeedCPUAdam, stage2.py:1418); " \
                "OnebitAdam does not compose with ZeRO/offload"
            assert "8bit" not in name and "8_bit" not in name, \
                "Adam8bit does not compose with cpu_offload: offload " \
                "keeps fp32 moments in HOST memory (the native CPU Adam " \
                "owns them), so quantized device states would be " \
                "silently replaced — drop cpu_offload to use 8-bit " \
                "states, or keep offload with the host fp32 states"
            self.optimizer = None  # built below, once master params exist
        elif optimizer is not None:
            self.optimizer = optimizer
        else:
            self.optimizer = build_optimizer(self._config.optimizer_name,
                                             self._config.optimizer_params)
        self.base_lr = getattr(self.optimizer, "lr", 1e-3)

        # 1-bit Adam phase tracking (reference onebit_adam.py:369-372 flips
        # adam_freeze_key python-side; here the phase is a static compile
        # flag so XLA gets two clean programs). With dp > 1 the engine runs
        # the WHOLE grad+update under shard_map over 'data' so each rank
        # holds a local gradient and the compressed allreduce is the only
        # cross-rank traffic in the compression phase (the reference
        # disables dense backward allreduce at :369-372 for the same
        # reason).
        from deepspeed_tpu.runtime.fp16.onebit_adam import OnebitAdam
        self._onebit = isinstance(self.optimizer, OnebitAdam)
        self._onebit_compression = False
        self._onebit_dist = False

        # -- lr scheduler --
        if lr_scheduler is not None:
            self.lr_scheduler = lr_scheduler
        else:
            self.lr_scheduler = build_lr_schedule(self._config.scheduler_name,
                                                  self._config.scheduler_params)

        # -- zero stage / shardings --
        self.zero_stage = self._config.zero_optimization_stage
        if self._onebit:
            # reference parity: OnebitAdam is not a ZeRO-supported optimizer
            # (zero/utils.py is_zero_supported_optimizer lists only
            # Adam-family fused/CPU optimizers)
            assert self.zero_stage == 0, \
                "OneBitAdam does not compose with ZeRO (reference " \
                "zero/utils.py is_zero_supported_optimizer); use stage 0"
            if self.dp_world_size > 1:
                self._onebit_dist = True
                self.optimizer.axis_name = "data"
                self.optimizer.world_size = self.dp_world_size
        self.param_specs = param_specs  # tensor-parallel PartitionSpecs
        with setup_span("setup/engine/params"):
            master_params = _tree_cast(model_parameters, jnp.float32)
            if self.zero_stage >= 1:
                self._param_shardings = zero_shardings(
                    master_params, self.mesh, stage=self.zero_stage,
                    axis_name=self._dp_axis_entry, model_specs=param_specs)
            else:
                self._param_shardings = replicated_shardings(
                    master_params, self.mesh, model_specs=param_specs)

        if self.zero_cpu_offload:
            # (master_weights=false x cpu_offload is refused earlier, in
            # DeepSpeedConfig._do_error_check)
            from deepspeed_tpu.ops.adam import DeepSpeedCPUAdam
            p = dict(self._config.optimizer_params or {})
            self.optimizer = DeepSpeedCPUAdam(
                master_params,
                lr=p.get("lr", 1e-3),
                betas=tuple(p.get("betas", (0.9, 0.999))),
                eps=p.get("eps", 1e-8),
                weight_decay=p.get("weight_decay", 0.0),
                adamw_mode=p.get("adam_w_mode", True),
                bias_correction=p.get("bias_correction", True))
            self.base_lr = self.optimizer.lr
            # device params in compute dtype only — the HBM saving that IS
            # ZeRO-Offload; fp32 master stays host-side in the optimizer
            params = _tree_cast(master_params,
                                self.compute_dtype or jnp.float32)
            opt_state = ()
            self._opt_shardings = ()
        else:
            if self.bf16_enabled and not self.bf16_master_weights:
                assert not self._onebit, \
                    "bf16.master_weights=false does not compose with " \
                    "OnebitAdam (its error-feedback state assumes an " \
                    "fp32-precision param target)"
                try:
                    accepts_sr = "sr_key" in inspect.signature(
                        self.optimizer.update).parameters
                except (TypeError, ValueError):
                    accepts_sr = False
                assert accepts_sr, \
                    "bf16.master_weights=false needs an optimizer whose " \
                    "update() accepts sr_key (the built-in Adam/SGD/Lamb " \
                    "do); this one would silently RNE-truncate bf16 updates"
                # params live in bf16; moments stay fp32 (Optimizer.init
                # allocates them fp32 regardless of param dtype)
                params = _tree_cast(master_params, jnp.bfloat16)
            else:
                params = master_params
            with setup_span("setup/engine/state"):
                opt_state = self.optimizer.init(params)
            if self.zero_stage >= 1:
                self._opt_shardings = zero_shardings(
                    opt_state, self.mesh, stage=self.zero_stage,
                    axis_name=self._dp_axis_entry, model_specs=None)
            else:
                self._opt_shardings = replicated_shardings(opt_state,
                                                           self.mesh)
        if self._onebit_dist:
            # per-rank error-feedback state: leading (dp,) dim sharded over
            # 'data' — each shard owns its own worker/server error
            dp = self.dp_world_size
            data_shd = NamedSharding(self.mesh, PartitionSpec("data"))
            opt_state = opt_state._replace(
                worker_error=jax.tree_util.tree_map(
                    lambda e: jnp.zeros((dp,) + e.shape, e.dtype),
                    opt_state.worker_error),
                server_error=jax.tree_util.tree_map(
                    lambda e: jnp.zeros((dp,) + e.shape, e.dtype),
                    opt_state.server_error))
            self._opt_shardings = self._opt_shardings._replace(
                worker_error=jax.tree_util.tree_map(
                    lambda _: data_shd, opt_state.worker_error),
                server_error=jax.tree_util.tree_map(
                    lambda _: data_shd, opt_state.server_error))

        self.gradient_accumulation_steps = self._config.gradient_accumulation_steps
        # With real accumulation (ga>1) grads sum on device in fp32 and
        # apply at the boundary (offload: one D2H of the summed grads).
        # cpu_offload at ga=1 allocates NO accumulator at all: the grads
        # leave the micro step as a compute-dtype OUTPUT and the host
        # snapshots them right after the dispatch — the reference's
        # transfer-grads-as-produced design (zero/stage2.py cpu_offload
        # 16-bit grad buckets) without a params-sized staging buffer
        # resident in HBM (the saving that lets a 2.5B model fit v5e,
        # test_offload_memory.py).
        if self.gradient_accumulation_steps > 1:
            if self._onebit_dist:
                # stacked per-rank local-grad accumulators
                dp = self.dp_world_size
                accum = jax.tree_util.tree_map(
                    lambda p: jnp.zeros((dp,) + p.shape, jnp.float32), params)
                accum_shardings = jax.tree_util.tree_map(
                    lambda _: NamedSharding(self.mesh, PartitionSpec("data")),
                    accum)
            else:
                accum = jax.tree_util.tree_map(
                    lambda p: jnp.zeros(p.shape, jnp.float32), params)
                if self.zero_stage >= 2:
                    accum_shardings = zero_shardings(
                        accum, self.mesh, stage=self.zero_stage,
                        axis_name=self._dp_axis_entry,
                        model_specs=param_specs)
                else:
                    accum_shardings = replicated_shardings(accum, self.mesh)
        else:
            accum, accum_shardings = (), ()
        self._offload_grads_device = None   # offload ga=1 grad output

        state = TrainState(
            params=params,
            opt_state=opt_state,
            accum_grads=accum,
            loss_scale=self.loss_scaler.init(),
            global_step=jnp.zeros((), jnp.int32),
            micro_step=jnp.zeros((), jnp.int32),
            skipped_steps=jnp.zeros((), jnp.int32),
            rng=jax.random.PRNGKey(seed),
        )
        # Every leaf gets an explicit mesh placement (replicated unless a
        # ZeRO/TP rule shards it) so jit never sees mixed device sets.
        repl = NamedSharding(self.mesh, PartitionSpec())
        self._state_shardings = TrainState(
            params=self._param_shardings,
            opt_state=self._opt_shardings,
            accum_grads=accum_shardings,
            loss_scale=jax.tree_util.tree_map(lambda _: repl, state.loss_scale),
            global_step=repl, micro_step=repl, skipped_steps=repl, rng=repl,
        )
        with setup_span("setup/engine/params"):
            placed = jax.device_put(state, self._state_shardings)

            # device_put can alias the source buffers (same-device shards)
            # — but the compiled step DONATES the state, which would delete
            # the caller's model_parameters out from under them. One
            # explicit copy at init decouples the engine state from user
            # arrays.
            self.state = jax.tree_util.tree_map(
                lambda x: jnp.copy(x) if isinstance(x, jax.Array) else x,
                placed)

        self.gradient_clipping = self._config.gradient_clipping

        # -- data --
        self.training_dataloader = None
        if training_data is not None:
            self.training_dataloader = self.deepspeed_io(
                training_data, collate_fn=collate_fn)

        # -- misc bookkeeping --
        # tensorboard (reference engine.py:151-156; rank-0 only)
        from deepspeed_tpu.utils.monitor import TensorBoardMonitor
        self.monitor = TensorBoardMonitor(
            enabled=self._config.tensorboard_enabled,
            output_path=self._config.tensorboard_output_path,
            job_name=self._config.tensorboard_job_name,
            rank=jax.process_index())
        self.summary_writer = self.monitor.writer  # reference attr name

        self.timers = SynchronizedWallClockTimer()
        self.tput_timer = ThroughputTimer(
            batch_size=self.train_micro_batch_size_per_gpu() *
            self.gradient_accumulation_steps,
            num_workers=self.dp_world_size,
            steps_per_output=self._config.steps_per_print)
        self.wall_clock_breakdown_enabled = self._config.wall_clock_breakdown
        # jax.profiler trace window ('observability.trace', legacy
        # 'profiler' section aliased; the reference's analog is the
        # wall_clock_breakdown timer ladder — on TPU the XLA trace is
        # the actionable artifact, SURVEY.md §5)
        self._profiler_cfg = self._config.profiler_config
        self._profiler_active = False
        # unified profiling & telemetry ('observability' config section):
        # FLOPs/MFU cost profiler, recompile tracking, memory watermarks,
        # trace spans, JSONL event log (deepspeed_tpu/profiling/)
        from deepspeed_tpu.profiling import Observer
        self.observability = Observer(
            self._config.observability_config, monitor=self.monitor,
            rank=jax.process_index(), device=jax.local_devices()[0],
            num_devices=len(jax.devices()))
        self.observability.set_step_provider(
            lambda: self._host_global_step)
        # postmortem health plane ('observability.health' section):
        # flight-recorder ring tapping the monitor mirror, stall
        # watchdog fed heartbeats at dispatch boundaries, numeric
        # anomaly detectors over the deferred-telemetry flush values
        # (utils/health.py — host-side only, pinned zero-perturbation)
        from ..utils.health import HealthPlane
        self.health = HealthPlane(
            self._config.observability_config.get("health"),
            monitor=self.monitor, rank=jax.process_index(),
            component="train",
            events_dir=self._config.observability_config.get(
                "events_dir"))
        # fault-tolerant checkpointing knobs ('checkpoint' config section):
        # CRC verification on load, retention, transient-I/O retry policy
        self._ckpt_cfg = self._config.checkpoint_config
        ckpt.set_retry_policy(self._ckpt_cfg["io_retries"],
                              self._ckpt_cfg["io_retry_backoff"])
        # elastic resilience (runtime/elastic.py; docs/checkpointing.md
        # "Surviving TPU preemption"): env-armed fault injections so a
        # supervisor-relaunched child can be faulted, the async-save
        # writer slot, and the opt-in preemption guard. The guard only
        # FLAGS a signal; the drain runs at the next train_batch
        # boundary (_elastic_boundary) where the window has committed.
        fault.arm_from_env()
        self._ckpt_writer = None         # lazy AsyncCheckpointWriter
        self._last_ckpt_dir = None       # fallback preemption save_dir
        self._restart_count = elastic.restart_count()
        self._elastic = None
        if self._ckpt_cfg["drain_on_preemption"]:
            self._elastic = elastic.PreemptionGuard()
            if self._elastic.install():
                log_dist(
                    "elastic: draining on SIGTERM/SIGINT (resumable exit "
                    f"code {elastic.RESUMABLE_EXIT_CODE})", ranks=[0])
            else:
                logger.warning(
                    "elastic: drain_on_preemption set but signal handlers "
                    "are main-thread-only; software trigger still active")
        if self._restart_count:
            # a supervisor relaunch: make the restart count visible on
            # the same x-axis as everything else
            self.monitor.write_elastic_metrics(
                restarts=self._restart_count)
        cc = self._config.compile_cache_config
        if cc["enabled"]:
            from ..utils.platform import enable_compile_cache
            active = enable_compile_cache(cc["dir"], cc["min_compile_secs"])
            if cc["dir"] and os.path.expanduser(cc["dir"]) != active:
                logger.warning(
                    "compile_cache: dir %r not used — the cache already "
                    "lives at %r (the environment or an earlier engine "
                    "in this process chose it)", cc["dir"], active)
        self._last_step_time_ms = None

        # -- sparse (CSR) embedding gradients (reference engine.py:181-187
        # converts nn.Embedding grads; exchange at :1088-1139). With no
        # module types in the functional contract, embedding leaves are
        # detected by name (emb*/wte/word_embeddings) + 2-D shape. Active
        # only with dp > 1 (single shard has no exchange to compress) and
        # without 1-bit Adam (which owns its own grad path).
        self._sparse_grad_paths = set()
        if (self.sparse_gradients_enabled() and self.dp_world_size > 1
                and not self._onebit):
            explicit = getattr(self._config, "sparse_gradients_params",
                               None)
            if explicit:
                # explicit opt-in (safer than the name heuristic: a
                # tied-head "embedding" is NOT a pure lookup table and
                # must stay dense — the heuristic can only catch that at
                # runtime via the overflow flag)
                eligible = {
                    _path_key(p): leaf for p, leaf in
                    jax.tree_util.tree_flatten_with_path(params)[0]
                    if hasattr(leaf, "ndim") and leaf.ndim == 2
                    and jnp.issubdtype(leaf.dtype, jnp.floating)}
                resolved = set()
                for entry in explicit:
                    hits = {p for p in eligible
                            if p == entry or entry in p}
                    if not hits:
                        raise ValueError(
                            f"sparse_gradients_params entry {entry!r} "
                            f"matches no 2-D float leaf; eligible: "
                            f"{sorted(eligible)}")
                    resolved |= hits
                self._sparse_grad_paths = resolved
            else:
                self._sparse_grad_paths = _detect_embedding_paths(params)
            if self._sparse_grad_paths:
                log_dist("sparse_gradients: CSR allreduce for "
                         f"{sorted(self._sparse_grad_paths)}"
                         + ("" if explicit else " (name heuristic; set "
                            "sparse_gradients_params to pin)"), ranks=[0])
            else:
                logger.warning(
                    "sparse_gradients enabled but no embedding-named 2-D "
                    "leaves found; all grads exchanged dense")
        self._csr_overflow = None     # device flag from the last micro step
        self._csr_overflow_logged = False

        # Hierarchical quantized collectives (TPU-native extension; ZeRO++
        # qgZ/qwZ/hpZ shapes — runtime/quantized_collectives.py). The
        # gradient path is exclusive with the 1-bit and CSR manual paths.
        qc = self._config.quantized_comm_config
        self._quant_cfg = qc
        self._quant_allreduce = bool(
            qc["enabled"] and self.dp_world_size > 1
            and not self._onebit and not self._sparse_grad_paths)
        self._quant_block = int(qc["block"])
        self._quant_algo = qc["algo"]
        if qc["enabled"] and not self._quant_allreduce:
            logger.warning(
                "quantized_comm gradient exchange ignored (needs dp > 1 "
                "and no 1-bit/sparse gradient path)")
        if self._dp_hierarchical:
            assert not self._onebit and not self._sparse_grad_paths, \
                "quantized_comm.hierarchical does not compose with " \
                "OnebitAdam or sparse_gradients (their manual shard_map " \
                "paths are written against the flat 'data' axis)"
            assert self._quant_algo == "twohop", \
                "quantized_comm.hierarchical requires algo='twohop' " \
                "(the legacy allgather exchange has no 2D form)"
        # qwZ: int8 block-quantized ZeRO param all-gather. Only on the
        # GSPMD (non-shard_map) path where the gather exists, with a
        # compute-dtype cast to ride (stage 3 skips the up-front cast —
        # its per-use-site gathers are already the lean shape).
        # comm_autotune: the plan (computed pre-mesh) now overrides the
        # static algo/block; hierarchy already shaped the mesh above
        self._autotune_cfg = self._config.comm_autotune_config
        if self._comm_plan is not None and self._quant_allreduce:
            if self._comm_plan.world != self.dp_world_size:
                logger.warning(
                    "comm_autotune: planned against dp=%d but the mesh "
                    "built dp=%d — plan dropped, static quantized_comm "
                    "config in effect", self._comm_plan.world,
                    self.dp_world_size)
                self._comm_plan = None
            else:
                self._quant_algo = self._comm_plan.algo
                self._quant_block = int(self._comm_plan.block)
        if self._comm_plan is not None and self._quant_allreduce and \
                self._autotune_cfg["calibrate"]:
            # opt-in drift check of the wire model against the compiled
            # exchange — best-effort: too few devices for the probe mesh,
            # a device/runtime failure (RuntimeError, which XLA's own
            # errors subclass) or an unwritable calibration file (OSError)
            # must not fail init; anything else is a bug and propagates
            try:
                from deepspeed_tpu.runtime.comm_autotune import \
                    calibrate_wire_model
                cal = calibrate_wire_model(
                    world=self.dp_world_size, algo=self._quant_algo,
                    block=self._quant_block,
                    hierarchical=self._comm_plan.hierarchical, n=1 << 14)
                self._comm_plan = self._comm_plan._replace(calibration=cal)
                if abs(cal["drift"]) > 0.05:
                    logger.warning(
                        "comm_autotune: wire model drifts %.1f%% from "
                        "the compiled HLO byte accounting — the cost "
                        "model's inputs may have rotted",
                        cal["drift"] * 100.0)
                # on real hardware, also TIME the exchange and persist
                # the measured link constants: the next run's LinkModel
                # then plans against the fabric as measured, not the
                # nominal round numbers (explicit config keys still win).
                # KNOWN-uniform fabric only (unknown topology counts as
                # split): the flat probe's slowest hop on a split fabric
                # is the DCN, and persisting that as the INTRA constants
                # would collapse the planner's fast/slow-wire
                # distinction for every later run
                import jax as _jax
                from deepspeed_tpu.runtime.comm_autotune import \
                    uniform_fabric
                uniform = uniform_fabric(self._comm_plan.topo_intra,
                                         self.dp_world_size)
                if _jax.default_backend() == "tpu" and uniform:
                    from deepspeed_tpu.runtime.comm_autotune import (
                        measure_link_constants, save_wire_calibration)
                    measured = measure_link_constants(
                        world=self.dp_world_size, algo=self._quant_algo,
                        block=self._quant_block)
                    path = save_wire_calibration(measured)
                    logger.info(
                        "comm_autotune: measured link constants "
                        f"({measured['intra_gbps']:.1f} gbps, "
                        f"{measured['intra_latency_us']:.1f} us) saved "
                        f"to {path}")
            except (RuntimeError, OSError) as e:
                logger.warning(f"comm_autotune: calibration skipped "
                               f"({e!r})")
        self._qwz = bool(qc["enabled"] and qc["quantize_weights"]
                         and 1 <= self.zero_stage <= 2
                         and self.compute_dtype is not None
                         and self.dp_world_size > 1)
        if qc["quantize_weights"] and qc["enabled"] and not self._qwz:
            logger.warning(
                "quantized_comm.quantize_weights ignored (needs ZeRO "
                "stage 1-2, a compute dtype, and dp > 1)")
        # hpZ: keep the compute-dtype params sharded over the intra axis
        # only, so backward re-gathers never cross the slow inter axis
        self._hpz = bool(qc["enabled"] and qc["secondary_partition"]
                         and self._dp_hierarchical
                         and 1 <= self.zero_stage <= 2
                         and self.compute_dtype is not None)
        if qc["secondary_partition"] and qc["enabled"] and not self._hpz:
            logger.warning(
                "quantized_comm.secondary_partition ignored (needs "
                "hierarchical mode, ZeRO stage 1-2, and a compute dtype)")

        self._compiled_micro_step = None
        self._compiled_batch_step = None
        self._compiled_grad = None
        self._compiled_apply = None
        self._cached_grads = None
        self._cached_loss = None

        # Async step pipeline ('async_pipeline' config section,
        # docs/performance.md "Async step pipeline"): scan-fused
        # accumulation (one dispatch per train_batch), background
        # prefetch, and deferred loss telemetry so steady-state steps
        # never force a device round-trip.
        ap = self._config.async_pipeline_config
        self._async_cfg = ap
        self._sync_loss_every_step = bool(ap["sync_loss_every_step"])
        self._prefetch_depth = int(ap["prefetch_depth"])
        self._use_fused_batch = None     # decided once, at first train_batch
        self._use_overlap = None         # comm_autotune exchange overlap
        self._prefetcher = None
        self._train_iter = None
        self._stacked_shd = None
        self._micro_shd = None
        self._monitor_ring = []          # deferred loss/lr/scale records
        self._last_loss_device = None    # device scalar; last_loss() syncs
        # what the loss fn returned beside its loss in the last
        # train_batch: one pytree of device arrays a micro batch, outputs
        # of the step program that nothing has waited for; None where the
        # loss fn returns a bare loss
        self.last_aux = None
        self._host_sync_count = 0        # forced device syncs (telemetry)
        self._host_gap_ms = None         # per-step host time outside dispatch
        # only a dynamic fp16 scaler's per-step scale must be snapshot
        # into the ring; static scales are exact at flush time
        self._dynamic_scale_telemetry = bool(
            self.fp16_enabled and isinstance(self.loss_scaler,
                                             DynamicLossScaler))
        self._window_anchor = None       # flush-to-flush wall-clock base
        # scripts predating close() must not lose the tail of the ring
        # at process exit; registered AFTER the Observer's own atexit
        # hook so (LIFO) the flush still finds an open event log. The
        # hook holds only a WEAKREF — the registry must not pin the
        # engine (and its device state) for process life when the
        # caller simply drops it; close() unregisters explicitly.
        import atexit
        import weakref
        self_ref = weakref.ref(self)

        def _exit_flush(ref=self_ref):
            eng = ref()
            if eng is not None:
                eng._flush_monitor_atexit()

        self._atexit_flush_hook = _exit_flush
        atexit.register(_exit_flush)
        # Host mirrors of the device counters, used for boundary checks and
        # print gating WITHOUT a device->host sync per step (a sync per
        # step serializes the async dispatch pipeline). _host_micro_step
        # counts completed micro fwd/bwd/step
        # cycles (reference engine.py micro_steps); exact. _host_global_step
        # ignores overflow skips (the device value, via .global_steps, is
        # authoritative).
        self._host_micro_step = 0
        self._host_global_step = 0
        # the first train_batch builds the step program (_batch_span)
        self._first_batch = True

        # the one-line which-exchange log (mirrors the which-path-
        # compiled log of the async pipeline): chosen algo/block/
        # hierarchy and why — plus the comm_plan event obs_report shows
        if self._quant_allreduce:
            from deepspeed_tpu.runtime.comm_autotune import candidate_label
            hier = (axis_size(self.mesh, "data_intra")
                    if self._dp_hierarchical else 0)
            label = candidate_label(self._quant_algo, self._quant_block,
                                    hier)
            why = (self._comm_plan.reason if self._comm_plan is not None
                   else "static quantized_comm config")
            log_dist(f"quantized_comm exchange = {label} "
                     f"[{'autotuned' if self._comm_plan is not None else 'static'}] "
                     f"({why})", ranks=[0])
            if self._comm_plan is not None:
                p = self._comm_plan
                self.observability.record_comm_plan(
                    algo=p.algo, block=p.block,
                    hierarchical=p.hierarchical, world=p.world,
                    topo_intra=p.topo_intra, reason=p.reason,
                    overridden=p.overridden, modeled_us=p.modeled_us,
                    calibration=p.calibration)

        # per-step DP comm-bytes model (host math on leaf shapes; the
        # wire shape itself is pinned by the HLO audits) — written to the
        # monitor each step and logged once here
        self._comm_stats = self._estimate_step_comm_bytes()
        if self._comm_stats is not None:
            log_dist(
                "dp grad exchange: ~{:.2f} MB/step/rank ({}), dense fp32 "
                "ring would be ~{:.2f} MB (ratio {:.2f}x)".format(
                    self._comm_stats["bytes_per_step"] / 2**20,
                    self._comm_stats["mode"],
                    self._comm_stats["dense_bytes_per_step"] / 2**20,
                    self._comm_stats["compression_ratio"] or 1.0),
                ranks=[0])

        log_dist(
            f"DeepSpeedEngine initialized: mesh={dict(self.mesh.shape)} "
            f"zero_stage={self.zero_stage} dtype="
            f"{self.compute_dtype or jnp.float32} "
            f"grad_acc={self.gradient_accumulation_steps}", ranks=[0])

    # ------------------------------------------------------------------ #
    # config accessors (reference engine.py:255-370)
    # ------------------------------------------------------------------ #
    def train_batch_size(self):
        return self._config.train_batch_size

    def train_micro_batch_size_per_gpu(self):
        return self._config.train_micro_batch_size_per_gpu

    def steps_per_print(self):
        return self._config.steps_per_print

    def zero_optimization(self):
        return self.zero_stage > 0

    def sparse_gradients_enabled(self):
        """(reference engine.py:269) When enabled, embedding-style grads can
        be exchanged in CSR form — see runtime/csr_tensor.csr_allreduce for
        the shard_map collective; under plain GSPMD XLA already moves only
        live shards."""
        return self._config.sparse_gradients_enabled

    def loss_scale(self):
        return float(self.state.loss_scale.scale)

    def get_lr(self):
        return [float(self._lr_at(self.state.global_step))]

    def get_global_step(self):
        return int(self.state.global_step)

    @property
    def global_steps(self):
        return int(self.state.global_step)

    @property
    def skipped_steps(self):
        return int(self.state.skipped_steps)

    @property
    def module_params(self):
        """Current master params (host view on demand).

        With ``zero_optimization.overlap_comm`` offload, an update may
        still be in flight — reads here would see the previous window's
        params. Warn once rather than silently returning stale weights
        (call :meth:`synchronize` first, as save/eval do)."""
        if getattr(self, "_offload_pending", None) is not None and \
                not getattr(self, "_warned_stale_params", False):
            self._warned_stale_params = True
            logger.warning(
                "module_params read with an overlapped ZeRO-Offload "
                "update still in flight — values are one window stale; "
                "call engine.synchronize() first for settled weights")
        return self.state.params

    def is_gradient_accumulation_boundary(self):
        """True while processing the LAST micro batch of the accumulation
        window (reference engine.py:843: (micro_steps+1) % gas == 0)."""
        return ((self._host_micro_step + 1) %
                self.gradient_accumulation_steps == 0)

    # -- remaining config-accessor facade (reference engine.py:255-370;
    #    fp16_enabled/gradient_accumulation_steps/gradient_clipping/
    #    zero_cpu_offload exist as engine ATTRIBUTES here — a documented
    #    deviation, the values are identical) --
    def optimizer_name(self):
        return self._config.optimizer_name

    def optimizer_params(self):
        return self._config.optimizer_params

    def optimizer_legacy_fusion(self):
        return self._config.optimizer_legacy_fusion

    def scheduler_name(self):
        return self._config.scheduler_name

    def scheduler_params(self):
        return self._config.scheduler_params

    def dynamic_loss_scale(self):
        return self.fp16_enabled and self._config.loss_scale == 0

    def initial_dynamic_scale(self):
        return self._config.initial_dynamic_scale

    def dynamic_loss_scale_args(self):
        return self._config.dynamic_loss_scale_args

    def amp_enabled(self):
        return False                     # no apex/amp on TPU

    def amp_params(self):
        return None

    def postscale_gradients(self):
        return not self._config.prescale_gradients

    def gradient_predivide_factor(self):
        return self._config.gradient_predivide_factor

    def wall_clock_breakdown(self):
        return self._config.wall_clock_breakdown

    def memory_breakdown(self):
        return self._config.memory_breakdown

    def tensorboard_enabled(self):
        return self._config.tensorboard_enabled

    def tensorboard_output_path(self):
        return self._config.tensorboard_output_path

    def tensorboard_job_name(self):
        return self._config.tensorboard_job_name

    def get_summary_writer(self):
        mon = getattr(self, "monitor", None)
        return getattr(mon, "writer", None)

    def zero_optimization_stage(self):
        return self._config.zero_optimization_stage

    def zero_reduce_scatter(self):
        return self._config.zero_config.reduce_scatter

    def zero_overlap_comm(self):
        return self._config.zero_config.overlap_comm

    def zero_reduce_bucket_size(self):
        return self._config.zero_config.reduce_bucket_size

    def zero_allgather_partitions(self):
        return self._config.zero_config.allgather_partitions

    def zero_allgather_bucket_size(self):
        return self._config.zero_config.allgather_bucket_size

    def zero_contiguous_gradients(self):
        return self._config.zero_config.contiguous_gradients

    def zero_load_from_fp32_weights(self):
        return self._config.zero_config.load_from_fp32_weights

    def zero_optimization_partition_gradients(self):
        return self.zero_optimization_stage() >= 2

    def get_mom(self):
        """Current scheduled momentum, mirroring :meth:`get_lr`
        (reference engine.py get_mom)."""
        mom = self._mom_at(self.state.global_step)
        if mom is not None:
            return [float(mom)]
        betas = (self._config.optimizer_params or {}).get("betas")
        if betas:
            return [float(betas[0])]
        return [float((self._config.optimizer_params or {})
                      .get("momentum", 0.0))]

    def train(self, mode: bool = True):
        """Training-mode flag for API parity (reference calls
        module.train()); determinism here is owned by the loss fn's
        ``deterministic`` knob, so this only records intent."""
        self._train_mode = bool(mode)
        return self

    def eval(self):
        return self.train(False)

    def zero_grad(self):
        """Clear the gradient-accumulation buffer (the analog of zeroing
        module grads; reference engine.py zero_grad)."""
        zeros = jax.tree_util.tree_map(jnp.zeros_like,
                                       self.state.accum_grads)
        self.state = self.state._replace(
            accum_grads=zeros, micro_step=jnp.zeros((), jnp.int32))

    def allreduce_gradients(self, bucket_size=MEMORY_OPT_ALLREDUCE_SIZE):
        """No-op by design: gradient reduction happens INSIDE the
        compiled step (GSPMD psum/reduce-scatter over 'data'), not as a
        separate host-driven pass (reference engine.py:751). Kept so
        reference-style training scripts port unchanged."""
        del bucket_size

    def module_state_dict(self):
        """Host copy of the model params (reference engine.py:1370).

        Must be a REAL copy: np.asarray of a CPU-backed jax array is
        zero-copy, and the compiled step donates the old param buffer —
        a view would silently morph into the post-update values."""
        from deepspeed_tpu.runtime.checkpoint import _to_host_global
        return jax.tree_util.tree_map(
            lambda x: np.array(_to_host_global(x), copy=True),
            self.state.params)

    def load_module_state_dict(self, state_dict, strict: bool = True):
        """Replace model params from a host pytree (reference
        engine.py:1342); shapes must match the current params."""
        cur = self.state.params
        if strict:
            cur_leaves = jax.tree_util.tree_leaves(cur)
            new_leaves = jax.tree_util.tree_leaves(state_dict)
            assert len(cur_leaves) == len(new_leaves), \
                (len(cur_leaves), len(new_leaves))
            for a, b in zip(cur_leaves, new_leaves):
                assert a.shape == np.shape(b), (a.shape, np.shape(b))
        new = jax.tree_util.tree_map(
            lambda tmpl, v: jnp.asarray(v, tmpl.dtype), cur, state_dict)
        self.state = self.state._replace(params=jax.device_put(
            new, self._state_shardings.params))

    def dump_state(self):
        """Readable engine-state summary (reference engine.py dump_state
        prints its internals; ours is the compiled-step equivalent)."""
        lines = [
            f"world: dp={self.dp_world_size} mp={self.mp_world_size} "
            f"mesh={dict(self.mesh.shape)}",
            f"precision: fp16={self.fp16_enabled} "
            f"bf16={self.bf16_enabled} loss_scale={self.loss_scale()}",
            f"zero: stage={self.zero_optimization_stage()} "
            f"cpu_offload={self.zero_cpu_offload}",
            f"batch: micro={self.train_micro_batch_size_per_gpu()} "
            f"gas={self.gradient_accumulation_steps} "
            f"global={self.train_batch_size()}",
            f"progress: step={self.global_steps} "
            f"skipped={self.skipped_steps} lr={self.get_lr()[0]:.3e}",
        ]
        logger.info("engine state:\n  " + "\n  ".join(lines))
        return lines

    # ------------------------------------------------------------------ #
    # data
    # ------------------------------------------------------------------ #
    def deepspeed_io(self, dataset, batch_size=None, collate_fn=None,
                     data_sampler=None):
        """(reference engine.py:652) Build a sharded loader over the global
        micro batch (micro_batch_per_chip × dp_world)."""
        if batch_size is None:
            batch_size = (self.train_micro_batch_size_per_gpu() *
                          self.dp_world_size)
        return DeepSpeedDataLoader(dataset, batch_size=batch_size,
                                   mesh=self.mesh, collate_fn=collate_fn,
                                   data_sampler=data_sampler)

    # ------------------------------------------------------------------ #
    # compiled step construction
    # ------------------------------------------------------------------ #
    def _lr_at(self, step):
        if self.lr_scheduler is not None:
            return self.lr_scheduler.lr_at(step)
        return jnp.asarray(self.base_lr, jnp.float32)

    def _mom_at(self, step):
        """Scheduled momentum (OneCycle cycle_momentum, reference
        lr_schedules.py:518), or None when the schedule doesn't cycle it.
        Flows into the compiled optimizer update as a beta1/mu override,
        the same way _lr_at flows as the lr."""
        sch = self.lr_scheduler
        if (sch is not None and getattr(sch, "cycle_momentum", False)
                and hasattr(sch, "mom_at")):
            if getattr(self, "_onebit", False) or \
                    getattr(self, "_onebit_dist", False):
                # 1-bit Adam's error-feedback state is calibrated against
                # a FIXED beta1 during compression (its update does not
                # take a momentum override); cycling it silently would be
                # worse than not cycling — warn once and keep beta1 fixed
                if not getattr(self, "_warned_onebit_mom", False):
                    self._warned_onebit_mom = True
                    logger.warning(
                        "OneCycle cycle_momentum is ignored with "
                        "OnebitAdam: beta1 stays at its configured value "
                        "(set cycle_momentum=false to silence this)")
                return None
            return sch.mom_at(step)
        return None

    def _plan_comm_autotune(self, raw, qc, mesh_axes, model_parameters):
        """Run the topology-aware exchange autotuner
        (runtime/comm_autotune.py) BEFORE the mesh exists: the plan's
        hierarchy split shapes the mesh itself. Pure host math over the
        gradient-size histogram; returns a CommPlan or None (config the
        quantized exchange refuses, or nothing to tune). Called only
        from __init__ — must not touch engine attributes."""
        opt_name = ((raw.get("optimizer", {}) or {}).get("type") or "")
        if "onebit" in opt_name.lower().replace("_", ""):
            logger.warning("comm_autotune: skipped (OnebitAdam owns its "
                           "own compressed exchange)")
            return None
        if raw.get("sparse_gradients"):
            logger.warning("comm_autotune: skipped (sparse_gradients "
                           "owns the CSR exchange)")
            return None
        from deepspeed_tpu.parallel.mesh import (natural_intra_size,
                                                 resolve_axis_sizes)
        from deepspeed_tpu.runtime.comm_autotune import plan_comm
        try:
            axes = resolve_axis_sizes(mesh_axes, len(jax.devices()))
        except ValueError:
            return None          # build_mesh will raise the real error
        if all(a in axes for a in ("data_inter", "data_intra")):
            # an explicitly 2D mesh IS a topology statement: the split
            # is pinned, the autotuner still prices algo/block
            world = axes["data_inter"] * axes["data_intra"]
            qc = dict(qc, hierarchical=axes["data_intra"],
                      explicit=dict(qc["explicit"], hierarchical=True))
            intra_hint = axes["data_intra"]
        elif "data" in axes:
            world = axes["data"]
            # physical fallback hint (no comm_autotune.intra_size):
            # devices-per-process is the fast-wire island, but the data
            # axis only spans it at a stride of the MINOR axes' product
            # (model/seq/expert sit after 'data' in the canonical
            # device-mesh order) — a {'data': 4, 'model': 2} mesh on
            # 4-device hosts has data extent 2 per host, not 4.
            # Approximate (create_device_mesh may reorder devices for
            # ICI contiguity); comm_autotune.intra_size overrides.
            stride = 1
            past_data = False
            for name, size in axes.items():
                if name == "data":
                    past_data = True
                elif past_data:
                    stride *= size
            local = natural_intra_size()
            intra_hint = (local // stride
                          if local and local % stride == 0 else 0)
            if intra_hint < 2 or world % intra_hint:
                intra_hint = 0
        else:
            return None          # no data axis: no gradient exchange
        if world <= 1:
            return None
        sizes = [leaf.size for leaf in
                 jax.tree_util.tree_leaves(model_parameters)
                 if hasattr(leaf, "dtype")
                 and jnp.issubdtype(leaf.dtype, jnp.floating)]
        if not sizes:
            return None
        from deepspeed_tpu.runtime.config import get_comm_autotune_config
        try:
            return plan_comm(sizes, world, qc,
                             get_comm_autotune_config(raw),
                             intra_hint=intra_hint)
        except Exception as e:
            # planning runs BEFORE DeepSpeedConfig validation: an
            # invalid quantized_comm combo (pinned hierarchy with a
            # pinned non-twohop algo, typo'd algo, ...) must surface
            # the config layer's curated error a few lines later, not
            # a raw planner exception here
            logger.warning(f"comm_autotune: planning skipped ({e!r}); "
                           "static quantized_comm config in effect")
            return None

    def _cast_for_loss(self, params, constrain=True):
        """fp32 master -> compute dtype, unless the loss fn owns the cast
        (pipeline loss fns cast inside shard_map so grad psums stay fp32).

        ZeRO stage 3: no up-front cast at all — materializing the full
        compute-dtype copy would be the replicated-parameter transient
        stage 3 exists to eliminate. The data-sharded fp32 master flows in
        directly and each weight is gathered + cast AT ITS USE SITE (our
        model families cast per-weight: models/gpt2.py gpt2_block
        ``.astype(dtype)``), so GSPMD schedules per-layer all-gathers
        just-in-time and ``jax.checkpoint``ed blocks re-gather in backward
        — the reference stage-3 gather/partition lifecycle as a compiler
        schedule. Measured on the 8-dev mesh: ~34% lower XLA temp memory
        on a param-dominated GPT-2 vs the stage-2 pre-cast.
        """
        if getattr(self._loss_fn, "owns_cast", False):
            return params
        if self.zero_stage >= 3:
            return params
        if constrain and self._qwz:
            # qwZ: the ZeRO param all-gather moves int8 + per-slice fp32
            # scales instead of bf16 (ZeRO++ arXiv:2306.10209 §quantized
            # weights) — see _quantized_weight_cast
            return self._quantized_weight_cast(params)
        with scope("weight_cast"):
            cast = _tree_cast(params, self.compute_dtype)
        if constrain and self.compute_dtype is not None \
                and self.zero_stage >= 1:
            # Pin the compute-dtype copy to the MASTER's sharded layout so
            # the cast runs shard-local and the forward's param all-gather
            # moves compute-dtype (bf16) elements. Without this GSPMD may
            # gather the f32 masters and cast downstream — 2x wire traffic
            # on the per-micro gather (the docs/performance.md caveat,
            # now asserted in test_hlo_collectives.py).
            # hpZ (secondary_partition): constrain to the intra-sharded
            # secondary layout instead — the inter hop happens here once,
            # and every use-site (re-)gather stays on the fast intra axis.
            target = (self._secondary_shardings() if self._hpz
                      else self._param_shardings)
            cast = jax.lax.with_sharding_constraint(cast, target)
        return cast

    # -- qwZ / hpZ: quantized + secondary-sharded ZeRO weight gather ------
    def _leaf_dp_dim(self, spec) -> Optional[int]:
        """Index of the PartitionSpec dim sharded over the dp axes, or
        None (replicated / model-only leaf)."""
        dp = set(self.dp_axes)
        for i, entry in enumerate(spec):
            names = entry if isinstance(entry, tuple) else (entry,)
            if any(a in dp for a in names if a is not None):
                return i
        return None

    def _secondary_shardings(self):
        """hpZ target layout: each leaf's dp-sharded dim re-sharded over
        the intra sub-axis ONLY (replicated across data_inter) — the
        ZeRO++ secondary partition, as a sharding assignment."""
        def one(shd):
            spec = shd.spec
            k = self._leaf_dp_dim(spec)
            if k is None:
                return shd
            entries = list(spec)
            entries[k] = "data_intra"
            return NamedSharding(self.mesh, PartitionSpec(*entries))
        return jax.tree_util.tree_map(one, self._param_shardings)

    def _quantized_weight_cast(self, params):
        """qwZ (+ optional hpZ): per-leaf int8 block-quantized ZeRO param
        gather.

        For each dp-sharded leaf: symmetric int8 quantization per slice
        along the sharded dim (absmax over the other dims — shard-local
        math), both q and scales pinned to the master's sharded layout,
        then resharded to the gather target (replicated, or the
        intra-sharded secondary layout under hpZ) BEFORE dequantization —
        so the partitioner's all-gather moves int8 elements + fp32
        scales, ~2x less wire than the bf16 gather and ~4x less than a
        naive f32 one. Dequant + compute-dtype cast run on the gathered
        values (elementwise, negligible). Leaves with no dp sharding or
        tiny per-slice extents ship as plain compute-dtype casts.

        MUST be applied OUTSIDE autodiff (every caller pre-casts before
        value_and_grad / before entering shard_map): round() has a zero
        derivative and the int8 wire carries no cotangents, so
        differentiating through this cast would zero the master
        gradients.
        """
        mesh = self.mesh
        hpz = self._hpz
        dtype = self.compute_dtype

        def one(leaf, shd):
            spec = shd.spec
            k = self._leaf_dp_dim(spec)
            plain_ok = (k is None or leaf.ndim == 0
                        or not jnp.issubdtype(leaf.dtype, jnp.floating)
                        or leaf.size // leaf.shape[k] < 16)
            if plain_ok:
                cast = (leaf.astype(dtype)
                        if jnp.issubdtype(leaf.dtype, jnp.floating)
                        else leaf)
                if k is not None:
                    cast = jax.lax.with_sharding_constraint(cast, shd)
                return cast
            # per-slice symmetric int8: one fp32 scale per index along
            # the sharded dim (reduction is over unsharded dims only)
            other = tuple(i for i in range(leaf.ndim) if i != k)
            absmax = jnp.max(jnp.abs(leaf.astype(jnp.float32)),
                             axis=other, keepdims=True)
            s = jnp.where(absmax > 0, absmax / 127.0, 1.0)
            q = jnp.clip(jnp.round(leaf.astype(jnp.float32) / s),
                         -127, 127).astype(jnp.int8)
            # scales: same rank, size-1 dims except k -> only dim k's
            # entry of the leaf spec survives
            s_spec = PartitionSpec(*[spec[i] if i == k else None
                                     for i in range(leaf.ndim)])
            q = jax.lax.with_sharding_constraint(q, shd)
            s = jax.lax.with_sharding_constraint(
                s, NamedSharding(mesh, s_spec))
            # the gather: reshard the int8 payload (this is what crosses
            # the wire). hpZ keeps the intra shard; otherwise replicate.
            tgt_entry = "data_intra" if hpz else None
            t_spec = list(spec)
            t_spec[k] = tgt_entry
            q = jax.lax.with_sharding_constraint(
                q, NamedSharding(mesh, PartitionSpec(*t_spec)))
            ts_spec = [None] * leaf.ndim
            ts_spec[k] = tgt_entry
            s = jax.lax.with_sharding_constraint(
                s, NamedSharding(mesh, PartitionSpec(*ts_spec)))
            return (q.astype(jnp.float32) * s).astype(dtype)

        return jax.tree_util.tree_map(one, params, self._param_shardings)

    def _compute_loss_and_grads(self, params, batch, rng, scale,
                                constrain_cast=True):
        """value_and_grad of the (scaled) loss in the compute dtype.

        Pipelined models bypass autodiff: the 1F1B executor
        (runtime/pipe/spmd.py build_pipeline_grad_fn) returns explicit
        fp32 grads with the loss-scale folded in, attached as
        ``loss_fn.grad_fn``.

        ``constrain_cast=False`` is passed by the shard_map gradient
        paths (CSR / quantized / 1-bit): there 'data' is a manual axis,
        params are replicated per rank, and the cast's sharding
        constraint would be both illegal and meaningless."""
        explicit_grad = getattr(self._loss_fn, "grad_fn", None)
        if explicit_grad is not None:
            loss, grads = explicit_grad(
                params, batch, rng,
                scale / self.gradient_accumulation_steps)
            return loss, None, grads

        def scaled_loss_fn(p):
            cp = self._cast_for_loss(p, constrain=constrain_cast)
            # the model's Pallas kernels must be told the mesh: a
            # pallas_call cannot be auto-partitioned (inside the
            # shard_map gradient paths the context changes nothing)
            with pallas_kernel_mesh(self.mesh, batch_axes=self.dp_axes):
                if self._loss_takes_rng:
                    out = self._loss_fn(cp, batch, rng)
                else:
                    out = self._loss_fn(cp, batch)
            if isinstance(out, tuple):
                loss, aux = out[0], out[1]
            else:
                loss, aux = out, None
            scaled = (loss.astype(jnp.float32) * scale /
                      self.gradient_accumulation_steps)
            return scaled, (loss, aux)

        (_, (loss, aux)), grads = jax.value_and_grad(
            scaled_loss_fn, has_aux=True)(params)
        grads = _tree_cast(grads, jnp.float32)
        return loss, aux, grads

    # -- sparse (CSR) embedding-gradient path -----------------------------
    def _compute_sparse_grads(self, params, batch, rng, scale):
        """Grad exchange with CSR compression for embedding leaves
        (reference engine.py:1088-1139 csr_allreduce_no_retain).

        The whole backward runs under shard_map over 'data' so each rank
        holds a LOCAL gradient; embedding leaves are compacted to
        (capacity, dim+1) and exchanged via all_gather + local scatter-add
        (runtime/csr_tensor.csr_allreduce) — payload world x cap x (dim+1)
        instead of world x vocab x dim — while every other leaf takes a
        plain pmean. Returns an extra in-jit overflow flag: the capacity
        bound (tokens in the local batch) is provably safe for pure lookup
        embeddings but NOT for tied heads; a True flag means dropped rows
        and is surfaced loudly by the engine at the boundary.
        """
        from deepspeed_tpu.runtime.csr_tensor import (
            csr_allreduce, dense_to_csr)
        P = PartitionSpec
        repl = lambda tree: jax.tree_util.tree_map(lambda _: P(), tree)
        sparse_paths = self._sparse_grad_paths
        dp = self.dp_world_size

        def inner(p, b, r, s):
            r = jax.random.fold_in(r, jax.lax.axis_index("data"))
            loss, aux, g = self._compute_loss_and_grads(
                p, b, r, s, constrain_cast=False)
            loss = jax.lax.pmean(loss, "data")
            # capacity: one grad row per token index in the local batch
            tokens = sum(int(np.prod(x.shape))
                         for x in jax.tree_util.tree_leaves(b)
                         if jnp.issubdtype(x.dtype, jnp.integer))
            overflow = jnp.zeros((), bool)

            def exchange(path, grad):
                nonlocal overflow
                key = _path_key(path)
                if key in sparse_paths and tokens > 0 \
                        and tokens < grad.shape[0]:
                    idx, vals, ovf = dense_to_csr(grad, tokens,
                                                  with_overflow=True)
                    overflow = jnp.logical_or(
                        overflow, jax.lax.pmax(ovf, "data"))
                    return csr_allreduce(idx, vals, grad.shape[0],
                                         "data") / dp
                return jax.lax.pmean(grad, "data")

            g = jax.tree_util.tree_map_with_path(exchange, g)
            return loss, overflow, g

        loss, overflow, grads = jax.shard_map(
            inner, mesh=self.mesh,
            in_specs=(repl(params),
                      jax.tree_util.tree_map(lambda _: P("data"), batch),
                      P(), P()),
            out_specs=(P(), P(), repl(params)),
            check_vma=False)(params, batch, rng, scale)
        return loss, overflow, grads

    def _check_csr_overflow(self):
        """Surface a CSR capacity violation (dropped gradient rows) loudly,
        once; gated to boundary syncs so it costs nothing per-step."""
        if self._csr_overflow is None or self._csr_overflow_logged:
            return
        if bool(self._csr_overflow):
            self._csr_overflow_logged = True
            logger.error(
                "sparse_gradients: an embedding gradient had more nonzero "
                "rows than the token-count capacity — rows were DROPPED "
                "(gradient is wrong). This happens when a detected "
                "'embedding' leaf also receives dense gradients (e.g. a "
                "tied LM head). Disable sparse_gradients for this model.")

    # -- int8 quantized allreduce path ------------------------------------
    def _quant_exchange_parts(self):
        """``(detect_ovf, exchange_tree)`` closures over the engine's
        quantized-comm config — the ONE copy of the per-leaf exchange
        and the fp16 nonfinite sentinel, shared by the serial
        in-shard_map exchange and the overlapped deferred one
        (:meth:`_quant_exchange_stacked`), so the bitwise-parity
        contract between the two paths cannot drift across hand-kept
        copies. Both closures must run INSIDE shard_map over the data
        axes.

        ``detect_ovf``: fp16 overflow sentinel — quantization destroys
        inf/nan (the absmax scale goes inf -> q garbage), so nonfinite
        is detected BEFORE the exchange and ``exchange_tree`` re-poisons
        the result, keeping the engine's has_overflow skip-step
        machinery working. ``exchange_tree``: leaves smaller than one
        quantization block ship dense (pmean); the rest take the
        flat/hierarchical quantized mean."""
        from deepspeed_tpu.runtime.quantized_collectives import (
            hierarchical_quantized_allreduce_mean, quantized_allreduce_mean)
        block = self._quant_block
        algo = self._quant_algo
        dp_axes = self.dp_axes
        hierarchical = self._dp_hierarchical
        if hierarchical:
            inter_size = axis_size(self.mesh, "data_inter")
            intra_size = axis_size(self.mesh, "data_intra")
        world = self.dp_world_size
        fp16 = self.fp16_enabled

        def detect_ovf(g):
            ovf = jnp.zeros((), bool)
            if fp16:
                for leaf in jax.tree_util.tree_leaves(g):
                    ovf = jnp.logical_or(
                        ovf, jnp.any(~jnp.isfinite(leaf)))
                ovf = jax.lax.pmax(ovf.astype(jnp.int32),
                                   dp_axes).astype(bool)
            return ovf

        def exchange_tree(g, ovf):
            def exchange(grad):
                if grad.size < block:
                    return jax.lax.pmean(grad, dp_axes)
                if hierarchical:
                    out = hierarchical_quantized_allreduce_mean(
                        grad, "data_intra", "data_inter",
                        intra_size, inter_size, block)
                else:
                    out = quantized_allreduce_mean(
                        grad, dp_axes[0], block, algo=algo,
                        world_size=world)
                if fp16:
                    out = jnp.where(ovf, jnp.nan, out)
                return out

            return jax.tree_util.tree_map(exchange, g)

        return detect_ovf, exchange_tree

    def _compute_quantized_grads(self, params, batch, rng, scale):
        """Backward under shard_map over the data axes with the int8
        block-quantized gradient exchange
        (runtime/quantized_collectives.py).

        algo='twohop' (default) is the qgZ shape: per-rank wire ~2n int8
        bytes independent of dp degree. algo='allgather' is the legacy
        O(W*n) exchange (only sane at dp=2). With
        quantized_comm.hierarchical the bandwidth-heavy hops run over
        'data_intra' and only the reduced 1/W_intra chunk crosses
        'data_inter'."""
        P = PartitionSpec
        repl = lambda tree: jax.tree_util.tree_map(lambda _: P(), tree)
        # Gather + cast ONCE in GSPMD land before entering shard_map:
        # in_specs=repl would otherwise coerce the ZeRO-sharded fp32
        # masters to replicated — an f32 all-gather on the wire where a
        # compute-dtype (or, under qwZ, int8) gather would do. The cast
        # rides qwZ/hpZ when enabled; inside the shard_map the re-cast
        # is a no-op.
        params = self._cast_for_loss(params, constrain=True)
        dp_axes = self.dp_axes
        batch_entry = self._dp_axis_entry
        detect_ovf, exchange_tree = self._quant_exchange_parts()

        def inner(p, b, r, s):
            idx = jax.lax.axis_index(dp_axes[0])
            for ax in dp_axes[1:]:
                idx = idx * axis_size(self.mesh, ax) + \
                    jax.lax.axis_index(ax)
            r = jax.random.fold_in(r, idx)
            loss, _aux, g = self._compute_loss_and_grads(
                p, b, r, s, constrain_cast=False)
            loss = jax.lax.pmean(loss, dp_axes)
            g = exchange_tree(g, detect_ovf(g))
            return loss, g

        loss, grads = jax.shard_map(
            inner, mesh=self.mesh,
            in_specs=(repl(params),
                      jax.tree_util.tree_map(lambda _: P(batch_entry),
                                             batch),
                      P(), P()),
            out_specs=(P(), repl(params)),
            check_vma=False)(params, batch, rng, scale)
        return loss, None, grads

    # -- 1-bit Adam distributed path --------------------------------------
    def _compute_local_grads(self, params, batch, rng, scale):
        """Per-data-shard gradients, stacked on a leading (dp,) axis sharded
        over 'data'. Under shard_map XLA does NOT insert the dense grad
        allreduce — each rank keeps its local gradient, which is what the
        1-bit compressed momentum exchange needs (reference disables
        enable_backward_allreduce, onebit_adam.py:369-372)."""
        P = PartitionSpec
        repl = lambda tree: jax.tree_util.tree_map(lambda _: P(), tree)

        def inner(p, b, r, s):
            r = jax.random.fold_in(r, jax.lax.axis_index("data"))
            loss, _aux, g = self._compute_loss_and_grads(
                p, b, r, s, constrain_cast=False)
            loss = jax.lax.pmean(loss, "data")
            return loss, jax.tree_util.tree_map(lambda x: x[None], g)

        loss, grads = jax.shard_map(
            inner, mesh=self.mesh,
            in_specs=(repl(params),
                      jax.tree_util.tree_map(lambda _: P("data"), batch),
                      P(), P()),
            out_specs=(P(),
                       jax.tree_util.tree_map(lambda _: P("data"), params)),
            check_vma=False)(params, batch, rng, scale)
        return loss, None, grads

    def _onebit_shard_update(self, params, opt_state, grads_stacked, lr):
        """Run the OnebitAdam update inside shard_map over 'data': each rank
        updates momentum with its local grad, then the compressed allreduce
        (or warmup pmean) is the only cross-rank communication."""
        P = PartitionSpec
        repl = lambda tree: jax.tree_util.tree_map(lambda _: P(), tree)
        data = lambda tree: jax.tree_util.tree_map(lambda _: P("data"), tree)
        from deepspeed_tpu.runtime.fp16.onebit_adam import OnebitAdamState

        def upd(p, m, v, step, we, se, g, lr_):
            take0 = lambda tree: jax.tree_util.tree_map(
                lambda x: x[0], tree)
            st = OnebitAdamState(step=step, exp_avg=m, exp_avg_sq=v,
                                 worker_error=take0(we),
                                 server_error=take0(se))
            new_p, new_st = self.optimizer.update(
                take0(g), st, p, lr=lr_,
                compression=self._onebit_compression)
            lead = lambda tree: jax.tree_util.tree_map(
                lambda x: x[None], tree)
            return (new_p, new_st.exp_avg, new_st.exp_avg_sq, new_st.step,
                    lead(new_st.worker_error), lead(new_st.server_error))

        outs = jax.shard_map(
            upd, mesh=self.mesh,
            in_specs=(repl(params), repl(opt_state.exp_avg),
                      repl(opt_state.exp_avg_sq), P(),
                      data(opt_state.worker_error),
                      data(opt_state.server_error),
                      data(grads_stacked), P()),
            out_specs=(repl(params), repl(opt_state.exp_avg),
                       repl(opt_state.exp_avg_sq), P(),
                       data(opt_state.worker_error),
                       data(opt_state.server_error)),
            check_vma=False)(
            params, opt_state.exp_avg, opt_state.exp_avg_sq,
            opt_state.step, opt_state.worker_error,
            opt_state.server_error, grads_stacked, lr)
        new_params, m, v, step, we, se = outs
        return new_params, OnebitAdamState(
            step=step, exp_avg=m, exp_avg_sq=v,
            worker_error=we, server_error=se)

    def _apply_update(self, state: TrainState, grads) -> TrainState:
        """Optimizer boundary: unscale, clip, update, loss-scale bookkeeping.
        (reference stage2.py:1331 step / engine.py:865 _take_model_step)"""
        with scope("loss_scale"):
            inv_scale = 1.0 / state.loss_scale.scale
            grads = jax.tree_util.tree_map(lambda g: g * inv_scale, grads)

            if self.fp16_enabled:
                overflow = has_overflow(grads)
            else:
                overflow = jnp.zeros((), bool)

        if self.gradient_clipping > 0:
            with scope("grad_clip"):
                if self._onebit_dist:
                    # stacked local grads: clip by the norm of the
                    # averaged gradient (what the dense path would see)
                    norm = _global_norm(jax.tree_util.tree_map(
                        lambda g: g.mean(axis=0), grads))
                else:
                    norm = _global_norm(grads)
                clip = jnp.minimum(1.0, self.gradient_clipping /
                                   (norm + 1e-6))
                grads = jax.tree_util.tree_map(lambda g: g * clip, grads)

        lr = self._lr_at(state.global_step)
        mom = self._mom_at(state.global_step)
        # master-weight-free bf16: per-step PRNG key for the stochastic
        # rounding of the fp32 update result back into the bf16 params
        sr_key = None
        if self.bf16_enabled and not self.bf16_master_weights:
            sr_key = jax.random.fold_in(
                jax.random.PRNGKey(self._config.bf16_sr_seed),
                state.global_step)

        def do_update(operand):
            params, opt_state, g = operand
            if self._onebit_dist:
                return self._onebit_shard_update(params, opt_state, g, lr)
            if self._onebit:
                return self.optimizer.update(
                    g, opt_state, params, lr=lr,
                    compression=self._onebit_compression)
            kw = {} if sr_key is None else {"sr_key": sr_key}
            if mom is not None:
                return self.optimizer.update(g, opt_state, params, lr=lr,
                                             momentum=mom, **kw)
            return self.optimizer.update(g, opt_state, params, lr=lr, **kw)

        def skip_update(operand):
            params, opt_state, _ = operand
            return params, opt_state

        with scope("opt_update"):
            if self.fp16_enabled:
                new_params, new_opt = jax.lax.cond(
                    overflow, skip_update, do_update,
                    (state.params, state.opt_state, grads))
            else:
                # overflow is statically False (bf16/fp32): no cond —
                # keeps collectives (1-bit allreduce) out of conditional
                # branches
                new_params, new_opt = do_update(
                    (state.params, state.opt_state, grads))
            zero_accum = jax.tree_util.tree_map(jnp.zeros_like,
                                                state.accum_grads)

        with scope("loss_scale"):
            new_scale = self.loss_scaler.update(state.loss_scale, overflow)
        return state._replace(
            params=new_params,
            opt_state=new_opt,
            accum_grads=zero_accum,
            loss_scale=new_scale,
            global_step=state.global_step + (1 - overflow.astype(jnp.int32)),
            micro_step=jnp.zeros((), jnp.int32),
            skipped_steps=state.skipped_steps + overflow.astype(jnp.int32),
        )

    def _grads_for_micro(self, state: TrainState, batch, sub):
        """One micro batch's fwd+bwd, dispatched to the configured
        gradient-exchange path. Returns ``(loss, csr_overflow|None,
        grads, aux|None)`` — shared by the per-micro step, the facade
        ``forward()``, and the fused batch step's scan body. ``aux`` is
        what the loss fn returned beside its loss (the plain path only)."""
        scale = state.loss_scale.scale
        if self._onebit_dist:
            loss, _aux, grads = self._compute_local_grads(
                state.params, batch, sub, scale)
        elif self._sparse_grad_paths:
            return (*self._compute_sparse_grads(state.params, batch, sub,
                                                scale), None)
        elif self._quant_allreduce:
            loss, _aux, grads = self._compute_quantized_grads(
                state.params, batch, sub, scale)
        else:
            loss, aux, grads = self._compute_loss_and_grads(
                state.params, batch, sub, scale)
            return loss, None, grads, aux
        return loss, None, grads, None

    def _micro_step(self, state: TrainState, batch) -> Tuple[TrainState, Any]:
        """One fused micro-batch step: fwd + bwd + accumulate + maybe-apply.
        Returns ``(state, loss)`` — or ``(state, (loss, csr_overflow))``
        when the CSR sparse-gradient path is active, or ``(state, (loss,
        aux))`` when the loss fn returns an aux beside its loss: it
        leaves the program as an output and nothing waits for it
        (:attr:`last_aux`)."""
        rng, sub = jax.random.split(state.rng)
        loss, csr_ovf, grads, aux = self._grads_for_micro(state, batch, sub)

        out = loss if csr_ovf is None else (loss, csr_ovf)
        if aux is not None:
            out = (out, aux)
        if self.zero_cpu_offload and self.gradient_accumulation_steps == 1:
            # no accumulator: the compute-dtype grads are an OUTPUT of
            # the dispatch (half the D2H bytes of fp32 — the
            # reference's 16-bit grad transfer to the host optimizer);
            # train_batch/backward stash them for _host_grad_snapshot
            state = state._replace(rng=rng,
                                   micro_step=state.micro_step + 1)
            return state, (out, _tree_cast(grads, self.compute_dtype))
        if self.zero_cpu_offload or self.gradient_accumulation_steps > 1:
            accum = jax.tree_util.tree_map(jnp.add, state.accum_grads, grads)
            state = state._replace(accum_grads=accum, rng=rng,
                                   micro_step=state.micro_step + 1)
            if not self.zero_cpu_offload:
                # offload applies host-side in _host_apply_update instead
                boundary = (state.micro_step %
                            self.gradient_accumulation_steps == 0)
                state = jax.lax.cond(
                    boundary,
                    lambda s: self._apply_update(s, s.accum_grads),
                    lambda s: s,
                    state)
        else:
            state = state._replace(rng=rng,
                                   micro_step=state.micro_step + 1)
            state = self._apply_update(state, grads)
        return state, out

    def _get_compiled_micro_step(self):
        if self._compiled_micro_step is None:
            # wrap_jit is identity with observability off; on, it counts
            # compiles + wall time and flags steady-state recompiles
            self._compiled_micro_step = self.observability.wrap_jit(
                jax.jit(self._micro_step, donate_argnums=(0,)),
                "micro_step")
        return self._compiled_micro_step

    # ------------------------------------------------------------------ #
    # async step pipeline: scan-fused accumulation
    # ------------------------------------------------------------------ #
    def _batch_step(self, state: TrainState, stacked) -> Tuple[TrainState,
                                                               Any]:
        """The WHOLE accumulation window as ONE compiled program
        (``async_pipeline.fused_accumulation``): a ``lax.scan`` of the
        micro fwd+bwd+accumulate body over the stacked ``(gas, ...)``
        batch, then the boundary apply — same rng stream, same
        accumulation order, same loss-scale/overflow semantics as
        ``gas`` separate micro dispatches, so losses and updates are
        bit-identical to the per-micro loop
        (tests/unit/test_async_pipeline.py pins this). One dispatch per
        ``train_batch`` instead of ``gas``: the host never sits between
        two micro steps. State/accumulator shardings are the micro
        step's own (the ZeRO ``zero_shardings`` placements ride the
        donated carry); the quantized/hierarchical DP exchange runs
        unchanged inside the scan body."""
        gas = self.gradient_accumulation_steps
        # the scan body IS the micro step (same accumulate + boundary
        # cond + apply graph per iteration) — parity with the per-micro
        # loop is structural, not re-derived
        state, losses = jax.lax.scan(self._micro_step, state, stacked)
        aux = None
        if isinstance(losses, tuple):       # the loss fn gives an aux
            losses, aux = losses
        # left-fold mean in the loss dtype, matching the per-micro
        # loop's python-side accumulation
        total = losses[0]
        for i in range(1, gas):
            total = total + losses[i]
        if aux is None:
            return state, total / gas
        # one aux a micro batch, as the per-micro loop keeps them
        return state, (total / gas, [
            jax.tree_util.tree_map(lambda a, i=i: a[i], aux)
            for i in range(gas)])

    # -- comm_autotune: compute/comm overlap inside the fused window ------
    #
    # The serial scan body computes micro-step i's gradients AND
    # exchanges them in the same iteration — the exchange collectives
    # depend on that iteration's backward dots, so the ICI idles during
    # compute and the MXU idles during the exchange. The overlapped
    # shape double-buffers: iteration i carries micro-step i-1's LOCAL
    # (unexchanged) gradients and issues their exchange alongside
    # micro-step i's forward/backward — the exchange reads only the
    # loop carry, making it data-independent of the iteration's compute
    # (pinned structurally by the HLO operand-cone audit in
    # tests/unit/test_hlo_quantized_comm.py), so XLA's scheduler can
    # run the two concurrently. The last window's exchange flushes
    # after the scan, then the boundary apply runs. Exchange inputs,
    # math, and accumulation order are IDENTICAL to the serial path —
    # losses and updates are bitwise-equal (tier-1 pinned).

    def _quant_local_grads(self, params, batch, rng, scale):
        """One micro-step's loss + LOCAL (pre-exchange) gradients under
        shard_map over the data axes, stacked on a leading dp-sharded
        axis — the double-buffered carry of the overlapped scan.
        ``params`` are already cast/gathered by the caller (the qwZ
        weight gather is hoisted out of the scan: params are constant
        within the window, so one gather serves all ``gas`` micros)."""
        P = PartitionSpec
        repl = lambda tree: jax.tree_util.tree_map(lambda _: P(), tree)
        dp_axes = self.dp_axes
        batch_entry = self._dp_axis_entry
        stacked = lambda tree: jax.tree_util.tree_map(
            lambda _: P(batch_entry), tree)

        def inner(p, b, r, s):
            idx = jax.lax.axis_index(dp_axes[0])
            for ax in dp_axes[1:]:
                idx = idx * axis_size(self.mesh, ax) + \
                    jax.lax.axis_index(ax)
            r = jax.random.fold_in(r, idx)
            loss, _aux, g = self._compute_loss_and_grads(
                p, b, r, s, constrain_cast=False)
            loss = jax.lax.pmean(loss, dp_axes)
            return loss, jax.tree_util.tree_map(lambda x: x[None], g)

        loss, local = jax.shard_map(
            inner, mesh=self.mesh,
            in_specs=(repl(params),
                      jax.tree_util.tree_map(lambda _: P(batch_entry),
                                             batch),
                      P(), P()),
            out_specs=(P(), stacked(params)),
            check_vma=False)(params, batch, rng, scale)
        return loss, local

    def _quant_exchange_stacked(self, local):
        """The deferred half of the quantized exchange: stacked local
        gradients in, replicated fp32 mean out. Shares the per-leaf
        exchange (and fp16 nonfinite-poisoning) closures with the
        serial :meth:`_compute_quantized_grads` via
        :meth:`_quant_exchange_parts` — only the issue POINT moved, so
        the result is bitwise what the serial path produces for the
        same local gradients."""
        P = PartitionSpec
        repl = lambda tree: jax.tree_util.tree_map(lambda _: P(), tree)
        batch_entry = self._dp_axis_entry
        detect_ovf, exchange_tree = self._quant_exchange_parts()

        def inner(stacked):
            g = jax.tree_util.tree_map(lambda x: x[0], stacked)
            return exchange_tree(g, detect_ovf(g))

        return jax.shard_map(
            inner, mesh=self.mesh,
            in_specs=(jax.tree_util.tree_map(lambda _: P(batch_entry),
                                             local),),
            out_specs=repl(local),
            check_vma=False)(local)

    def _batch_step_overlapped(self, state: TrainState, stacked
                               ) -> Tuple[TrainState, Any]:
        """The fused window with the exchange double-buffered: micro 0
        computes outside the scan, each scan iteration exchanges the
        PREVIOUS micro's gradients while computing its own, the last
        exchange flushes after the scan, then the boundary apply runs.
        Same rng stream, same exchange math, same accumulation order as
        the serial :meth:`_batch_step` — bitwise-equal losses/params
        (tests/unit/test_comm_autotune.py pins this)."""
        gas = self.gradient_accumulation_steps
        # hoisted weight gather: params are constant within the window,
        # so the (qwZ/hpZ-riding) cast+gather runs once per window, not
        # once per micro — the prefetched next-step weights of the
        # ZeRO++ playbook, as a loop-invariant the partitioner can
        # schedule ahead of the first micro's compute
        cast = self._cast_for_loss(state.params, constrain=True)
        scale = state.loss_scale.scale
        rng, sub = jax.random.split(state.rng)
        micro0 = jax.tree_util.tree_map(lambda x: x[0], stacked)
        loss0, pending = self._quant_local_grads(cast, micro0, sub, scale)

        def body(carry, batch):
            rng, accum, pending = carry
            rng, sub = jax.random.split(rng)
            loss, local = self._quant_local_grads(cast, batch, sub, scale)
            exchanged = self._quant_exchange_stacked(pending)
            accum = jax.tree_util.tree_map(jnp.add, accum, exchanged)
            return (rng, accum, local), loss

        rest = jax.tree_util.tree_map(lambda x: x[1:], stacked)
        (rng, accum, pending), losses = jax.lax.scan(
            body, (rng, state.accum_grads, pending), rest)
        # flush: the last micro's exchange has no next compute to hide
        # under (the NEXT window's first micro would — across-dispatch
        # overlap is the async dispatch queue's job)
        exchanged = self._quant_exchange_stacked(pending)
        accum = jax.tree_util.tree_map(jnp.add, accum, exchanged)
        state = state._replace(rng=rng,
                               micro_step=state.micro_step + gas)
        state = self._apply_update(state, accum)
        total = loss0
        for i in range(gas - 1):
            total = total + losses[i]
        return state, total / gas

    def _select_overlap_path(self):
        """(overlap?, why) — the exchange-overlap analog of
        :meth:`_select_batch_path`; only consulted on the fused path."""
        ca = self._autotune_cfg
        if not ca["enabled"]:
            return False, "comm_autotune disabled"
        if ca["overlap"] is False:
            return False, "comm_autotune.overlap=false"
        if self.gradient_accumulation_steps < 2:
            return False, ("gas=1: no next micro-step to hide the "
                           "exchange under")
        if not self._quant_allreduce:
            return False, ("no explicit exchange to defer (dense GSPMD "
                           "/ CSR / 1-bit paths own their schedules)")
        return True, ("grad exchange of micro-step i issued alongside "
                      "micro-step i+1's compute (double-buffered carry, "
                      "post-scan flush)")

    def _overlap_path(self) -> bool:
        """Decide once which fused-step body compiles (overlapped or
        serial exchange), with its own one-line log."""
        if self._use_overlap is None:
            ov, why = self._select_overlap_path()
            self._use_overlap = ov
            if self._autotune_cfg["enabled"]:
                log_dist("comm_autotune: exchange overlap = "
                         + ("on" if ov else "off") + f" ({why})",
                         ranks=[0])
        return self._use_overlap

    def _select_batch_path(self):
        """(fused?, why) for this engine's configuration. The fused path
        covers the default configs (bf16/fp16/fp32 x ZeRO 0-2 x dense or
        quantized/hierarchical collectives); paths that genuinely need
        the host between micro steps keep the per-micro loop."""
        if not self._async_cfg["fused_accumulation"]:
            return False, "async_pipeline.fused_accumulation=false"
        if self.gradient_accumulation_steps == 1:
            return False, ("gas=1: the micro step already covers the "
                           "window in one dispatch")
        if self.zero_cpu_offload:
            return False, "ZeRO-Offload runs the host Adam at the boundary"
        if self._onebit or self._onebit_dist:
            return False, "1-bit Adam phase switching is host-driven"
        if self._sparse_grad_paths:
            return False, ("sparse (CSR) grads surface a per-micro "
                           "overflow flag")
        return True, (f"scan over gas={self.gradient_accumulation_steps} "
                      "micro batches, one dispatch per train_batch")

    def _batch_path(self) -> bool:
        """Decide once (at first train_batch) which path compiles, with
        the one-line log the acceptance criteria require."""
        if self._use_fused_batch is None:
            fused, why = self._select_batch_path()
            self._use_fused_batch = fused
            log_dist("async_pipeline: train_batch path = "
                     + ("fused batch_step" if fused else "per-micro loop")
                     + f" ({why})", ranks=[0])
        return self._use_fused_batch

    def _get_compiled_batch_step(self):
        if self._compiled_batch_step is None:
            body = (self._batch_step_overlapped if self._overlap_path()
                    else self._batch_step)
            self._compiled_batch_step = self.observability.wrap_jit(
                jax.jit(body, donate_argnums=(0,)),
                "batch_step")
        return self._compiled_batch_step

    def _stacked_batch_sharding(self):
        """Sharding for the fused path's ``(gas, batch, ...)`` input:
        micro axis replicated, batch dim split over the data axes
        (cached — the mesh is fixed at construction)."""
        if self._stacked_shd is None:
            from deepspeed_tpu.parallel.mesh import data_axis_names
            axes = data_axis_names(self.mesh)
            if axes:
                entry = axes if len(axes) > 1 else axes[0]
                spec = PartitionSpec(None, entry)
            else:
                spec = PartitionSpec()
            self._stacked_shd = NamedSharding(self.mesh, spec)
        return self._stacked_shd

    def _micro_batch_sharding(self):
        """Cached per-micro batch sharding (leading dim over data)."""
        if self._micro_shd is None:
            from deepspeed_tpu.parallel.mesh import data_sharding
            self._micro_shd = data_sharding(self.mesh)
        return self._micro_shd

    def _next_stacked_batch(self, data_iter):
        """One ``(gas, ...)`` stacked device batch for the fused step:
        consumed directly from a stacking :class:`PrefetchLoader`, else
        ``gas`` micro batches are pulled and stacked host-side
        (device-array micros pay a D2H — feed host batches, or let the
        engine's own prefetcher assemble them off-thread)."""
        if getattr(data_iter, "stacks_micro_batches", False):
            return next(data_iter)
        micros = [next(data_iter)
                  for _ in range(self.gradient_accumulation_steps)]
        # device-resident micros (a user loader that already device_put
        # them) stack on-device — np.stack would pull every micro D2H
        # and re-upload, a per-step round-trip the per-micro loop never
        # paid
        on_device = all(isinstance(x, jax.Array)
                        for x in jax.tree_util.tree_leaves(micros[0]))
        stacked = (jax.tree_util.tree_map(lambda *xs: jnp.stack(xs),
                                          *micros)
                   if on_device else stack_micro_batches(micros))
        return self._put_stacked_batch(stacked)

    def _put_guarded(self, batch, shd, batch_dim):
        """Sharded put with a replication fallback: leaves whose batch
        dim (``batch_dim``) doesn't divide the dp degree — or that lack
        it entirely (scalars) — stay replicated. The per-micro loop fed
        such host batches to jit unsharded and GSPMD partitions the
        compute either way, so the prefetch/stacking puts can never
        crash a config that runs without them."""
        if shd.spec == PartitionSpec():
            return jax.device_put(batch, shd)
        repl = NamedSharding(self.mesh, PartitionSpec())
        dp = self.dp_world_size

        def put(x):
            ok = (hasattr(x, "ndim") and x.ndim > batch_dim
                  and x.shape[batch_dim] % dp == 0)
            return jax.device_put(x, shd if ok else repl)

        return jax.tree_util.tree_map(put, batch)

    def _put_stacked_batch(self, stacked):
        """Guarded put for a ``(gas, batch, ...)`` window (also the
        stacking prefetch worker's put)."""
        return self._put_guarded(stacked, self._stacked_batch_sharding(),
                                 batch_dim=1)

    def _put_micro_batch(self, batch):
        """Guarded put for one un-stacked micro batch (the non-fused
        prefetch path)."""
        return self._put_guarded(batch, self._micro_batch_sharding(),
                                 batch_dim=0)

    def _ensure_train_iter(self):
        """``train_batch(data_iter=None)`` plumbing, shared with the
        pipe engine: lazily wrap ``training_data``'s loader in a
        RepeatingLoader plus (base engine) the async prefetch stage."""
        assert self.training_dataloader is not None, \
            "train_batch() without data_iter requires training_data"
        if getattr(self, "_train_iter", None) is None:
            self._train_iter = iter(self._wrap_train_iter(
                RepeatingLoader(self.training_dataloader)))
        return self._train_iter

    def _wrap_train_iter(self, it):
        """Insert the background prefetch stage (``async_pipeline
        .prefetch_depth`` > 0): a worker thread assembles and
        device_puts batches — stacked to ``(gas, ...)`` on the fused
        path — so H2D for batch N+1 overlaps compute of batch N."""
        fused = self._batch_path()
        if isinstance(self.training_dataloader, DeepSpeedDataLoader) and \
                (fused or self._prefetch_depth > 0):
            # the stacking put (or the prefetch worker) owns the H2D; a
            # loader-side device_put would force a D2H round-trip at
            # the host stacking stage
            self.training_dataloader.device_put_enabled = False
        if self._prefetch_depth <= 0:
            return it
        stack = self.gradient_accumulation_steps if fused else 1
        put_fn = (self._put_stacked_batch if stack > 1
                  else self._put_micro_batch)
        self._prefetcher = PrefetchLoader(it, put_fn=put_fn,
                                          depth=self._prefetch_depth,
                                          stack_micros=stack)
        return self._prefetcher

    def close(self):
        """Release engine-owned background resources: drain any
        in-flight overlapped offload update AND any pending async
        checkpoint saves (the close barrier of the async-save contract —
        a stored writer exception is re-raised at the end, after every
        resource is released), stop the prefetch thread, flush deferred
        telemetry, uninstall the preemption guard, seal the
        observability log."""
        self._offload_drain()
        save_error = None
        if self._ckpt_writer is not None:
            self._ckpt_writer.close()
            try:
                self._ckpt_writer.raise_pending_error()
            except Exception as e:   # surfaced below, not swallowed
                save_error = e
            self._ckpt_writer = None
        if self._elastic is not None:
            self._elastic.uninstall()
        if self._prefetcher is not None:
            self._prefetcher.close()
            self._prefetcher = None
        # drop the train iterator too: it wraps the closed prefetcher,
        # and a later train_batch() through it would silently restart a
        # worker thread the engine no longer tracks
        self._train_iter = None
        if self._monitor_ring:
            self._flush_monitor()
        import atexit
        try:
            atexit.unregister(self._atexit_flush_hook)
        except Exception:
            pass
        # health BEFORE observability: untapping the mirror restores
        # the Observer's own writer so its close-time identity check
        # (mirror is self._log) still clears it
        self.health.close()
        self.observability.close()
        if save_error is not None:
            raise save_error

    def _flush_monitor_atexit(self):
        """Interpreter-exit safety net for the deferred-telemetry ring
        (best-effort: the device may already be tearing down)."""
        try:
            if self._monitor_ring:
                self._flush_monitor()
        except Exception:
            pass

    # ------------------------------------------------------------------ #
    # reference-style facade: forward / backward / step
    # ------------------------------------------------------------------ #
    def forward(self, batch):
        """Compute loss for one micro batch (reference engine.py:729).

        NB: under XLA the backward pass is part of the same compiled graph,
        so ``forward`` runs value_and_grad and caches the grads;
        ``backward`` accumulates them; ``step`` applies at the boundary.
        Use ``train_batch`` for the single-dispatch fused path.
        """
        if self.wall_clock_breakdown_enabled:
            self.timers("forward").start()
        if self._compiled_grad is None:
            def fwd(state, batch):
                # same per-path dispatch as the micro/batch steps (incl.
                # the quantized exchange, which keeps the qwZ weight
                # quantization OUTSIDE autodiff — differentiating
                # through round() would zero the master gradients)
                rng, sub = jax.random.split(state.rng)
                loss, ovf, grads, _ = self._grads_for_micro(state, batch,
                                                            sub)
                if ovf is not None:
                    return loss, grads, rng, ovf
                return loss, grads, rng
            self._compiled_grad = self.observability.wrap_jit(
                jax.jit(fwd), "grad")
        with self.observability.span("forward"):
            out = self._compiled_grad(self.state, batch)
        if self._sparse_grad_paths and not self._onebit_dist:
            loss, grads, rng, self._csr_overflow = out
        else:
            loss, grads, rng = out
        self.state = self.state._replace(rng=rng)
        self._cached_grads = grads
        self._cached_loss = loss
        if self.wall_clock_breakdown_enabled:
            self.timers("forward").stop()
        return loss

    __call__ = forward

    def backward(self, loss=None, allreduce_gradients=True):
        """Accumulate the cached grads (reference engine.py:767). The DP
        allreduce happens implicitly: grads of replicated params over
        data-sharded batches are psum'd by GSPMD."""
        assert self._cached_grads is not None, \
            "backward() must follow forward() on the same micro batch"
        if self.wall_clock_breakdown_enabled:
            self.timers("backward").start()
        with self.observability.span("backward"):
            self._backward_inner()
        if self.wall_clock_breakdown_enabled:
            self.timers("backward").stop()
        return loss

    def _backward_inner(self):
        grads = self._cached_grads
        self._cached_grads = None
        if self.zero_cpu_offload and self.gradient_accumulation_steps == 1:
            # no device accumulator (micro-step parity): stash for the
            # boundary snapshot, cast to compute dtype like the fused
            # path so this API moves the same 16-bit D2H bytes
            self._offload_grads_device = _tree_cast(grads,
                                                    self.compute_dtype)
            self.state = self.state._replace(
                micro_step=self.state.micro_step + 1)
        elif self.gradient_accumulation_steps > 1 or self.zero_cpu_offload:
            accum = jax.tree_util.tree_map(jnp.add, self.state.accum_grads,
                                           grads)
            self.state = self.state._replace(
                accum_grads=accum, micro_step=self.state.micro_step + 1)
        else:
            self._pending_grads = grads
            self.state = self.state._replace(
                micro_step=self.state.micro_step + 1)

    # -- ZeRO-Offload boundary, split so the host Adam can overlap the
    # -- next window's device compute (reference overlaps D2H/H2D on side
    # -- streams, stage2.py:291-294 + async copy in csrc/adam/cpu_adam.cpp)
    def _host_grad_snapshot(self):
        """D2H of the summed, unscaled grads as host fp32. ga=1: the
        micro step emitted them as a compute-dtype output (no device
        accumulator to reset); ga>1: drain and zero the fp32
        accumulator so the next window can start immediately."""
        from deepspeed_tpu.runtime.checkpoint import _to_host_global
        scale = float(self.state.loss_scale.scale)
        inv = 1.0 / scale
        if self.gradient_accumulation_steps == 1:
            assert self._offload_grads_device is not None, \
                "offload boundary without a completed micro step"
            src, self._offload_grads_device = \
                self._offload_grads_device, None
            self.state = self.state._replace(
                micro_step=jnp.zeros((), jnp.int32))
            host = jax.tree_util.tree_map(_to_host_global, src)
            return jax.tree_util.tree_map(
                lambda g: np.asarray(g, np.float32) * inv, host)
        accum = jax.tree_util.tree_map(_to_host_global,
                                       self.state.accum_grads)
        grads = jax.tree_util.tree_map(
            lambda g: np.asarray(g, np.float32) * inv, accum)
        zero_accum = jax.tree_util.tree_map(
            lambda g: jnp.zeros(g.shape, g.dtype), self.state.accum_grads)
        self.state = self.state._replace(
            accum_grads=jax.device_put(
                zero_accum, self._state_shardings.accum_grads),
            micro_step=jnp.zeros((), jnp.int32))
        return grads

    def _host_optimize(self, grads, lr, mom=None):
        """Overflow check + clip + native C++ SIMD Adam on the host fp32
        master (reference stage2.py:1418-1431 DeepSpeedCPUAdam.step).
        Thread-safe w.r.t. device work: touches only host state."""
        overflow = any(not np.all(np.isfinite(g))
                       for g in jax.tree_util.tree_leaves(grads))
        if overflow:
            return None, True
        if self.gradient_clipping > 0:
            sq = sum(float(np.sum(g.astype(np.float64) ** 2))
                     for g in jax.tree_util.tree_leaves(grads))
            clip = min(1.0, self.gradient_clipping /
                       (np.sqrt(sq) + 1e-6))
            if clip < 1.0:
                grads = jax.tree_util.tree_map(
                    lambda g: g * np.float32(clip), grads)
        use_bf16 = self.compute_dtype == jnp.bfloat16
        new_params = self.optimizer.step(grads, lr=lr, bf16_out=use_bf16,
                                         beta1=mom)
        if not use_bf16:
            dtype = self.compute_dtype or jnp.float32
            new_params = jax.tree_util.tree_map(
                lambda p: p.astype(dtype), new_params)
        return new_params, False

    def _apply_host_result(self, new_params, overflow):
        """H2D of the updated compute-dtype params + counter/scale
        bookkeeping (reference's fp32->fp16 device copy)."""
        if overflow:
            device_params = self.state.params
        else:
            device_params = jax.device_put(new_params,
                                           self._param_shardings)
        new_scale = self.loss_scaler.update(
            self.state.loss_scale, jnp.asarray(overflow))
        inc = 0 if overflow else 1
        self.state = self.state._replace(
            params=device_params,
            loss_scale=new_scale,
            global_step=self.state.global_step + inc,
            skipped_steps=self.state.skipped_steps + (1 - inc),
        )

    def _host_apply_update(self):
        """Synchronous ZeRO-Offload boundary: snapshot -> Adam -> H2D."""
        grads = self._host_grad_snapshot()
        lr = float(self._lr_at(self.state.global_step))
        mom = self._mom_at(self.state.global_step)
        new_params, overflow = self._host_optimize(
            grads, lr, None if mom is None else float(mom))
        self._apply_host_result(new_params, overflow)

    def _host_apply_update_overlapped(self):
        """Overlapped boundary (zero_optimization.overlap_comm): apply the
        PREVIOUS window's pending update, snapshot this window's grads,
        and hand them to the worker thread — the host Adam then runs
        concurrently with the next window's device compute. Updates are
        one window delayed (window k+1 computes with params_{k-1}); call
        :meth:`synchronize` (or save/eval, which do) to drain."""
        self._offload_drain()
        grads = self._host_grad_snapshot()
        lr = float(self._lr_at(self.state.global_step))
        mom = self._mom_at(self.state.global_step)
        self._offload_pending = self._offload_pool.submit(
            self._host_optimize, grads, lr,
            None if mom is None else float(mom))

    def _offload_drain(self):
        if getattr(self, "_offload_pending", None) is not None:
            new_params, overflow = self._offload_pending.result()
            self._offload_pending = None
            self._apply_host_result(new_params, overflow)

    def synchronize(self):
        """Apply any in-flight overlapped offload update (no-op
        otherwise). Call before reading params outside the engine."""
        self._offload_drain()

    def _maybe_switch_onebit_phase(self):
        """Enter 1-bit compression once global_steps reaches freeze_step
        (reference onebit_adam.py:369-372). Recompiles the step functions —
        a one-time cost at the phase boundary."""
        if not self._onebit or self._onebit_compression:
            return  # phase is monotonic: once on, stay on (no per-step sync)
        # _host_global_step over-counts vs the device value by fp16
        # overflow skips (which DO happen in early fp16 training — the
        # initial dynamic scale of 2^32 typically overflows several steps).
        # The host mirror is only the cheap gate: at the boundary, confirm
        # with the authoritative device counter before flipping — the
        # one-time sync is amortized by the recompile that follows
        # (reference onebit_adam.py:369-372 gates on true optimizer steps).
        if self._host_global_step < self.optimizer.freeze_step:
            return
        phase = self.global_steps >= self.optimizer.freeze_step
        if phase != self._onebit_compression:
            self._onebit_compression = phase
            self._compiled_micro_step = None
            self._compiled_batch_step = None
            self._compiled_apply = None
            self._compiled_grad = None
            log_dist(f"OnebitAdam: compression phase = {phase} "
                     f"(step {self.global_steps})", ranks=[0])

    def step(self):
        """Apply the optimizer at the accumulation boundary
        (reference engine.py:903)."""
        self._maybe_switch_onebit_phase()
        if self.wall_clock_breakdown_enabled:
            self.timers("step").start()
        ga = self.gradient_accumulation_steps
        if self.zero_cpu_offload:
            if self.is_gradient_accumulation_boundary():
                if self._offload_overlap:
                    self._host_apply_update_overlapped()
                else:
                    self._host_apply_update()
                self._host_global_step += 1
                self._report_progress()
                self._write_monitor(self._cached_loss)
            self._host_micro_step += 1
            if self.wall_clock_breakdown_enabled:
                self.timers("step").stop()
            self._elastic_boundary()
            return
        if self._compiled_apply is None:
            if ga > 1:
                # grads live inside the (donated) state as accum_grads
                apply = jax.jit(
                    lambda s: self._apply_update(s, s.accum_grads),
                    donate_argnums=(0,))
            else:
                apply = jax.jit(self._apply_update, donate_argnums=(0,))
            self._compiled_apply = self.observability.wrap_jit(apply,
                                                               "apply")
        if ga > 1:
            if self.is_gradient_accumulation_boundary():
                with self.observability.span("step"):
                    self.state = self._compiled_apply(self.state)
                self._host_global_step += 1
                self._check_csr_overflow()
                self._report_progress()
                self._write_monitor(self._cached_loss)
        else:
            grads = getattr(self, "_pending_grads", None)
            assert grads is not None, "step() must follow backward()"
            self._pending_grads = None
            with self.observability.span("step"):
                self.state = self._compiled_apply(self.state, grads)
            self._host_global_step += 1
            self._check_csr_overflow()
            self._report_progress()
            self._write_monitor(self._cached_loss)
        self._host_micro_step += 1
        if self.wall_clock_breakdown_enabled:
            self.timers("step").stop()
            self.timers.log(["forward", "backward", "step"],
                            memory_breakdown=self._config.memory_breakdown)
        self._elastic_boundary()

    # ------------------------------------------------------------------ #
    # fused path
    # ------------------------------------------------------------------ #
    def _batch_span(self, name: str):
        """The host span around a ``train_batch``'s dispatches. The
        engine's first builds the step program (traced, lowered,
        compiled or loaded there), so it lies inside ``setup/program``
        and the program's row of the compile ledger names it
        (profiling/recompile.py). Opened HERE, in ``train_batch``'s own
        frame, and not by a wrapper around ``train_batch``: one more
        Python frame under the trace of GPT-2 345M's step made it 2.5 s
        longer on the benchmark's host (PERF.md §6, PR 53)."""
        if self._first_batch:
            self._first_batch = False
            return self._first_batch_span(name)
        return self.observability.span(name)

    @contextmanager
    def _first_batch_span(self, name: str):
        with setup_span("setup/program", cls=("train_batch",)):
            with self.observability.span(name):
                yield

    def train_batch(self, data_iter=None):
        """Process one *full* batch = grad_acc micro batches. On the
        scan-fused path (``async_pipeline.fused_accumulation``, the
        default for non-offload/1-bit/sparse configs) the whole window
        is ONE asynchronously-dispatched compiled program and the step
        returns without a device round-trip; otherwise the per-micro
        dispatch loop runs, one dispatch per micro batch. Mirrors
        PipelineEngine.train_batch (pipe/engine.py:229) semantics for
        the non-pipe engine.

        The returned loss is a device scalar (convert with ``float``,
        or read :meth:`last_loss` — both are explicit sync points)."""
        if data_iter is None:
            data_iter = self._ensure_train_iter()

        self._maybe_switch_onebit_phase()
        self._maybe_profile_step()
        # no-op unless a durability test armed it: deliver SIGTERM (or
        # the software preemption) here and the window below must still
        # run to completion before the boundary drain fires
        fault.fire("elastic.sigterm_mid_window", step=self._host_global_step)
        # health-plane liveness beat, then the armed-stall point: the
        # `stall` action wedges the loop HERE, past the beat, so the
        # watchdog observes a genuinely silent train_batch phase
        self.health.heartbeat("train_batch")
        fault.fire("health.stall", step=self._host_global_step)
        fused = self._batch_path()
        self.tput_timer.start()
        _t_step0 = time.perf_counter()
        if self._window_anchor is None:
            # telemetry window opens at the first dispatch after a
            # (re)anchor, so flush-time averages never include idle time
            self._window_anchor = _t_step0
        _t_dispatch = 0.0
        if fused:
            step_fn = self._get_compiled_batch_step()
            with self._batch_span("train_batch"):
                with self.observability.span("data"):
                    batch = self._next_stacked_batch(data_iter)
                _t0 = time.perf_counter()
                with self.observability.span("train/dispatch"):
                    self.state, mean_loss = step_fn(self.state, batch)
                _t_dispatch = time.perf_counter() - _t0
                if isinstance(mean_loss, tuple):
                    mean_loss, self.last_aux = mean_loss
        else:
            step_fn = self._get_compiled_micro_step()
            total, auxes = None, []
            offload_direct = (self.zero_cpu_offload and
                              self.gradient_accumulation_steps == 1)
            with self._batch_span("train_batch"):
                for _ in range(self.gradient_accumulation_steps):
                    with self.observability.span("data"):
                        batch = next(data_iter)
                    _t0 = time.perf_counter()
                    with self.observability.span("train/dispatch"):
                        self.state, out = step_fn(self.state, batch)
                    _t_dispatch += time.perf_counter() - _t0
                    if offload_direct:
                        out, self._offload_grads_device = out
                    if self._sparse_grad_paths and not self._onebit_dist:
                        loss, self._csr_overflow = out
                    elif isinstance(out, tuple):
                        loss, aux = out
                        auxes.append(aux)
                    else:
                        loss = out
                    total = loss if total is None else total + loss
                if self.zero_cpu_offload:
                    if self._offload_overlap:
                        self._host_apply_update_overlapped()
                    else:
                        self._host_apply_update()
            mean_loss = total / self.gradient_accumulation_steps
            if auxes:
                self.last_aux = auxes
        self.tput_timer.stop()
        self._last_step_time_ms = (time.perf_counter() - _t_step0) * 1e3
        # host time NOT spent inside a dispatch call: data wait + python
        # bookkeeping — the overhead the async pipeline exists to hide
        self._host_gap_ms = max(
            self._last_step_time_ms - _t_dispatch * 1e3, 0.0)
        self._host_micro_step += self.gradient_accumulation_steps
        self._host_global_step += 1
        # one-time FLOPs/MFU cost profile of the compiled step program —
        # OUTSIDE the timed window (it is an AOT re-compile); only the
        # last batch's shapes are read, never its (donated) buffers
        prog = "batch_step" if fused else "micro_step"
        if self.observability.wants_flops_profile(prog):
            self.observability.maybe_profile_flops(
                prog, step_fn, (self.state, batch),
                samples=self._host_global_step * self.train_batch_size())
        with self.observability.span("train/tail"):
            self._check_csr_overflow()
            self._report_progress()
            self._write_monitor(mean_loss)
            self._elastic_boundary()
        return mean_loss

    def last_loss(self):
        """Python float of the most recent ``train_batch`` mean loss —
        an explicit sync point that also flushes the deferred telemetry
        ring. ``None`` before the first step."""
        if self._last_loss_device is None:
            return None
        if self._monitor_ring:
            self._flush_monitor()
        else:
            self._host_sync_count += 1
        return float(self._last_loss_device)

    def eval_batch(self, batch):
        """Loss without grads/update. Accepts a single batch pytree OR
        an iterator of micro batches (the pipe engine's historical
        shape) — one eval API for both engines. An iterator is drained
        up to ``gradient_accumulation_steps`` micros (the engine's
        window, mirroring the pipe engine's ``micro_batches``) and the
        mean loss returned."""
        self._offload_drain()
        self._drain_saves()   # eval barrier: pending async saves land
        if self._monitor_ring:
            self._flush_monitor()   # eval is an explicit sync point
        it = normalize_eval_input(batch)
        micros = []
        for _ in range(self.gradient_accumulation_steps):
            try:
                micros.append(next(it))
            except StopIteration:
                break
        assert micros, "eval_batch: empty micro-batch iterator"
        if not hasattr(self, "_compiled_eval"):
            def ev(params, batch, rng):
                cp = self._cast_for_loss(params)
                with pallas_kernel_mesh(self.mesh,
                                        batch_axes=self.dp_axes):
                    out = (self._loss_fn(cp, batch, rng)
                           if self._loss_takes_rng
                           else self._loss_fn(cp, batch))
                return out[0] if isinstance(out, tuple) else out
            self._compiled_eval = self.observability.wrap_jit(
                jax.jit(ev), "eval")
        total = None
        with self.observability.span("eval"):
            for m in micros:
                loss = self._compiled_eval(self.state.params, m,
                                           self.state.rng)
                total = loss if total is None else total + loss
        return total / len(micros)

    def _maybe_profile_step(self):
        """Start/stop a jax.profiler trace window around the configured
        steps. The captured trace (tensorboard-viewable) is the TPU
        analog of the reference's per-phase CUDA timers."""
        if not self._profiler_cfg["enabled"]:
            return
        step = self._host_global_step
        start = self._profiler_cfg["start_step"]
        stop = start + self._profiler_cfg["num_steps"]
        if not self._profiler_active and step == start:
            jax.profiler.start_trace(self._profiler_cfg["output_path"])
            self._profiler_active = True
            log_dist(f"profiler: trace started at step {step} -> "
                     f"{self._profiler_cfg['output_path']}", ranks=[0])
        elif self._profiler_active and step >= stop:
            jax.profiler.stop_trace()
            self._profiler_active = False
            log_dist(f"profiler: trace stopped at step {step}", ranks=[0])

    def _estimate_step_comm_bytes(self):
        """Host-side model of the per-rank DP gradient-exchange bytes per
        optimizer step (the wire SHAPE is pinned by the HLO audits in
        tests/unit/test_hlo_quantized_comm.py; this is the byte-level
        telemetry of the same model, written per step to the monitor).
        None at dp=1 (no exchange)."""
        from deepspeed_tpu.runtime.quantized_collectives import wire_bytes
        from deepspeed_tpu.utils.hlo_audit import dense_allreduce_ring_bytes
        W = self.dp_world_size
        if W <= 1:
            return None
        gas = self.gradient_accumulation_steps
        hier = None
        if self._dp_hierarchical:
            hier = (axis_size(self.mesh, "data_inter"),
                    axis_size(self.mesh, "data_intra"))
        total_q = total_d = 0
        for leaf in jax.tree_util.tree_leaves(self.state.params):
            if not (hasattr(leaf, "dtype")
                    and jnp.issubdtype(leaf.dtype, jnp.floating)):
                continue
            n = leaf.size
            dense = dense_allreduce_ring_bytes(n, W, dtype_bytes=4)  # fp32
            total_d += dense
            if self._quant_allreduce and n >= self._quant_block:
                qb, _ = wire_bytes(n, W, self._quant_block,
                                   algo=self._quant_algo,
                                   hierarchical=hier)
                total_q += qb
            else:
                total_q += dense
        active = total_q if self._quant_allreduce else total_d
        if self._quant_allreduce:
            mode = ("hierarchical-" + self._quant_algo if hier
                    else self._quant_algo)
        else:
            mode = "dense"
        return {"bytes_per_step": active * gas,
                "dense_bytes_per_step": total_d * gas,
                "compression_ratio": (total_d / active) if active else None,
                "mode": mode}

    # steady-state bound on the deferred-telemetry ring: past this many
    # unflushed steps the ring syncs regardless of steps_per_print (the
    # records are tiny, but unbounded deferral would hold a device
    # scalar per step for the run's lifetime)
    _MONITOR_RING_CAP = 512

    def _write_monitor(self, loss=None):
        """reference engine.py:780-790/:922-936 scalars, x-axis =
        cumulative samples — but sync-free in steady state: host-side
        scalars (step time, throughput, comm bytes, MFU, memory,
        dispatch counters) are written immediately, while device-valued
        ones (loss, lr, loss_scale) are queued in a small ring and
        materialized only at sync points — every ``steps_per_print``,
        on :meth:`last_loss`/:meth:`eval_batch`/:meth:`close`, or at
        the ring cap. ``async_pipeline.sync_loss_every_step=true``
        restores the old per-step ``float(loss)`` sync. Deferred lr
        records are computed from the host step mirror (identical to
        the device counter except under fp16 overflow skips within a
        flush window)."""
        if loss is not None:
            self._last_loss_device = loss
        if not (self.monitor.enabled or self.observability.enabled):
            return
        samples = self._host_global_step * self.train_batch_size()
        if self._comm_stats is not None:
            self.monitor.write_comm_metrics(
                bytes_per_step=self._comm_stats["bytes_per_step"],
                compression_ratio=self._comm_stats["compression_ratio"],
                samples=samples,
                mode=(self._comm_stats["mode"]
                      + ("+overlap" if self._use_overlap else "")))
        # dynamic fp16 scaling: snapshot the per-step scale (jnp.copy —
        # the state leaf itself is donated to the next dispatch) so the
        # flushed scale trajectory attributes backoffs to the right
        # step; static scalers are constant and read at flush time
        scale = (jnp.copy(self.state.loss_scale.scale)
                 if self._dynamic_scale_telemetry else None)
        self._monitor_ring.append(
            {"samples": samples, "host_step": self._host_global_step,
             "loss": loss, "scale": scale,
             "raw_step_ms": self._last_step_time_ms})
        if (self._sync_loss_every_step
                or self._host_global_step % self._config.steps_per_print
                == 0
                or len(self._monitor_ring) >= self._MONITOR_RING_CAP):
            self._flush_monitor(at_step_boundary=True)
        # recompile + dispatch counters / memory / trace refresh — all
        # host-side probes, no device round-trip (the sync counter
        # reflects any flush this step just performed). Step time, MFU
        # and throughput are emitted at flush barriers instead: once
        # the host runs ahead of an async device, per-dispatch wall
        # clock measures host time, not device time.
        self.observability.on_step(
            samples=samples, step_time_ms=None,
            host_gap_ms=self._host_gap_ms,
            host_syncs=self._host_sync_count)

    def _flush_monitor(self, at_step_boundary: bool = False):
        """Materialize the deferred loss/lr/scale records — the ONE
        periodic device round-trip of the async pipeline — and emit the
        window's honest step-time/throughput/MFU.

        The ``block_until_ready`` on the newest loss is the explicit
        periodic barrier: a flush at a step boundary reports
        barrier-to-barrier wall time divided by the window's step
        count, which IS the device step time regardless of how far the
        host's async dispatches ran ahead (per-dispatch wall clock
        would measure only host time). Out-of-band flushes (eval /
        save / last_loss — arbitrary idle time may have passed) write
        loss/lr/scale but NO step-time/throughput/MFU records: honest
        by omission beats an idle-inflated or host-only number."""
        ring, self._monitor_ring = self._monitor_ring, []
        if not ring:
            return
        self._host_sync_count += 1
        newest = next((r["loss"] for r in reversed(ring)
                       if r["loss"] is not None), None)
        if newest is not None:
            jax.block_until_ready(newest)
        avg_ms = None
        comp_by_step = {}
        if at_step_boundary:
            now = time.perf_counter()
            if self._window_anchor is not None:
                window_ms = (now - self._window_anchor) * 1e3
                # jit compiles block the dispatching step — attribute
                # their wall time to THAT step's record instead of
                # smearing it across the window (keeps compile spikes
                # in the p95 tail, as the per-step scheme did). Compile
                # events record the pre-increment host step, hence +1.
                tracker = self.observability.compile_tracker
                steps_in = {rec["host_step"] for rec in ring}
                if tracker is not None:
                    for ev in tracker.events:
                        # only the train-step programs compile inside
                        # the timed window; eval/grad/apply compiles
                        # happen between train dispatches and must not
                        # be deducted from it
                        if ev.fn_name not in ("batch_step",
                                              "micro_step"):
                            continue
                        s = ev.step + 1
                        if s in steps_in:
                            comp_by_step[s] = (comp_by_step.get(s, 0.0)
                                               + ev.wall_ms)
                elif 1 in steps_in and len(ring) > 1 and \
                        ring[0]["raw_step_ms"]:
                    # no tracker (observability off): at least keep the
                    # first compile pinned to step 1 via its raw time
                    comp_by_step[ring[0]["host_step"]] = \
                        ring[0]["raw_step_ms"]
                avg_ms = max(window_ms - sum(comp_by_step.values()),
                             0.0) / len(ring)
            self._window_anchor = now
        else:
            self._window_anchor = None   # re-anchor at the next step
        scale = self.loss_scale()
        # the host step mirror over-counts the device optimizer step by
        # the cumulative fp16 overflow skips; re-anchor on the (now
        # settled) device counter so logged lr indices drift at most
        # within one flush window, never for the rest of the run
        skip_offset = self._host_global_step - int(self.state.global_step)
        for rec in ring:
            lr_step = max(rec["host_step"] - skip_offset, 0)
            loss_val = (float(rec["loss"]) if rec["loss"] is not None
                        else None)
            # armed-fault poison (health.nan_loss): corrupt THIS record's
            # telemetry value to NaN — params and the returned device
            # loss are untouched; the detector below must catch it
            try:
                fault.fire("health.nan_loss", step=rec["host_step"])
            except fault.InjectedCrash:
                if loss_val is not None:
                    loss_val = float("nan")
            scale_val = (float(rec["scale"])
                         if rec.get("scale") is not None else scale)
            # numeric health detectors read the SAME host floats the
            # monitor writes — this flush barrier already materialized
            # them, so the feed adds no device sync
            self.health.observe_loss(loss_val, rec["host_step"])
            # a collapse needs a DYNAMIC scale: fp32 / static-scale
            # runs hold a constant (often 1.0) that must not alert
            if self.dynamic_loss_scale():
                self.health.observe_loss_scale(scale_val,
                                               rec["host_step"])
            self.monitor.write_train_metrics(
                loss=loss_val,
                lr=float(self._lr_at(lr_step)),
                loss_scale=scale_val,
                samples=rec["samples"], flush=False)
            # step time only from boundary flushes: an out-of-band
            # flush (eval/save/last_loss — arbitrary idle or mere host
            # time may have passed) writes no step time rather than a
            # misleading one
            if avg_ms is not None:
                step_ms = avg_ms + comp_by_step.get(rec["host_step"],
                                                    0.0)
                self.monitor.write_timer_values(
                    {"step_time_ms": step_ms}, rec["samples"])
                if step_ms > 0:
                    self.monitor.write_scalar(
                        "Train/Samples/samples_per_sec",
                        self.train_batch_size() / (step_ms / 1e3),
                        rec["samples"])
        tracker = self.observability.compile_tracker
        if tracker is not None:
            self.health.observe_recompiles(tracker.total_compiles,
                                           self._host_global_step)
        self.observability.write_mfu(
            avg_ms, ring[-1]["samples"],
            micro_steps_per_step=(1 if self._use_fused_batch
                                  else self.gradient_accumulation_steps),
            program=("batch_step" if self._use_fused_batch
                     else "micro_step"))
        self.monitor.flush()

    def _report_progress(self):
        # gate on the host mirror: no device sync unless actually printing
        step = self._host_global_step
        if step > 0 and step % self._config.steps_per_print == 0:
            log_dist(
                f"step={self.global_steps} lr={self.get_lr()[0]:.3e} "
                f"loss_scale={self.loss_scale():.0f} "
                f"skipped={self.skipped_steps}", ranks=[0])

    # ------------------------------------------------------------------ #
    # checkpointing (reference engine.py:1329/:1173)
    # ------------------------------------------------------------------ #
    def save_checkpoint(self, save_dir: str, tag: Optional[str] = None,
                        client_state: Optional[Dict] = None,
                        async_: Optional[bool] = None,
                        preempted: bool = False):
        """Atomic-commit save: shards land in ``<tag>.tmp/``, process 0
        seals a ``COMMITTED`` marker (process_count + per-file sizes and
        CRC32s) after a multihost barrier, renames the directory to its
        final tag, then repoints ``latest`` atomically. A crash at any
        point leaves either the previous checkpoint fully intact or the
        new one fully committed — never a half-save that resume trusts.

        ``async_`` (default: ``checkpoint.async_save``) turns the call
        into a snapshot-and-return: a donation-safe device->host copy of
        the train state is taken at this step boundary (O(local shard)),
        then the whole stage/commit protocol above runs on a single
        background writer thread while the step loop keeps dispatching —
        the loop stalls only for the snapshot. A save submitted while
        one is still writing JOINS it (same tag) or SUPERSEDES the
        still-waiting one (newer tag); two saves never interleave their
        staging I/O. ``close()``, ``eval_batch()`` and ``load_checkpoint``
        drain pending saves; a writer exception surfaces on the next
        ``save_checkpoint``/``close``. Multi-process runs fall back to
        blocking saves (the commit barriers must run on every process's
        main thread).

        ``preempted`` marks the checkpoint as committed by the graceful
        preemption drain (``meta.preempted``); such tags are reported
        distinctly by ``tools/verify_checkpoint.py`` and — when newer
        than ``latest`` — are never garbage-collected.
        """
        self._raise_async_save_error()
        self._offload_drain()
        if self._monitor_ring:
            self._flush_monitor()   # a save is a natural sync point
        # the retry policy is process-global; re-assert this engine's so
        # its own saves run under its own config even with several
        # engines alive in one process
        ckpt.set_retry_policy(self._ckpt_cfg["io_retries"],
                              self._ckpt_cfg["io_retry_backoff"])
        if async_ is None:
            async_ = bool(self._ckpt_cfg["async_save"])
        if async_ and jax.process_count() > 1:
            log_dist("async_save: multi-process run — the commit barriers "
                     "must run on every process's main thread; falling "
                     "back to a blocking save", ranks=[0])
            async_ = False
        t0 = time.time()
        snap_model, snap_optim, cpu_arrays, meta = \
            self._snapshot_train_state(client_state, preempted,
                                       copy=async_)
        if tag is None:
            tag = f"global_step{meta['global_step']}"
        snapshot_ms = (time.time() - t0) * 1000.0
        final_dir = os.path.join(save_dir, tag)
        samples = self._host_global_step * self.train_batch_size()
        self._last_ckpt_dir = save_dir
        job = partial(self._write_checkpoint_job, save_dir, tag,
                      snap_model, snap_optim, cpu_arrays, meta, samples)
        if async_:
            writer = self._ensure_ckpt_writer()
            verdict = writer.submit(tag, job)
            self.monitor.write_elastic_metrics(
                snapshot_ms=snapshot_ms,
                pending_saves=writer.pending_saves(), samples=samples)
            log_dist(f"async checkpoint {final_dir}: snapshot in "
                     f"{snapshot_ms:.0f}ms ({verdict}); commit continues "
                     "in background", ranks=[0])
            return final_dir
        # a blocking save must not run its commit inline while the async
        # writer is still staging an earlier one — same never-interleave
        # invariant the writer enforces for its own jobs
        self._drain_saves()
        self.monitor.write_elastic_metrics(
            snapshot_ms=snapshot_ms, pending_saves=0, samples=samples,
            flush=False)
        job()
        return final_dir

    def _snapshot_train_state(self, client_state=None, preempted=False,
                              copy=True):
        """The state a checkpoint carries, captured at the step boundary.

        ``copy=True`` (async saves): replica-0 shard copies of the
        model and optimizer state (donation-safe — the fused step
        donates these buffers on the very next dispatch) plus a COPY of
        the ZeRO-Offload host master state (the host optimizer mutates
        its buffers in place between snapshot and background write).
        Nothing the writer touches afterwards is ever written by the
        step loop again.

        ``copy=False`` (blocking saves): the live trees pass straight
        through — ``save_tree_sharded`` streams their shards
        tree-by-tree exactly as the pre-async protocol did, so a
        blocking save's peak host memory stays max(tree), not
        sum(trees). The ``ckpt.snapshot`` kill point fires identically
        on both paths."""
        if copy:
            snap_model = ckpt.snapshot_tree(self.state.params)
            snap_optim = ckpt.snapshot_tree(
                {"opt_state": self.state.opt_state,
                 "loss_scale": self.state.loss_scale})
        else:
            fault.fire("ckpt.snapshot")
            snap_model = self.state.params
            snap_optim = {"opt_state": self.state.opt_state,
                          "loss_scale": self.state.loss_scale}
        cpu_arrays = None
        if self.zero_cpu_offload and jax.process_index() == 0:
            # host-resident fp32 master + moments (reference saves the
            # fp32 partitions in zero_pp_rank files, engine.py:1409)
            sd = self.optimizer.state_dict()
            cp = (lambda a: np.array(a, copy=True)) if copy else \
                (lambda a: a)
            cpu_arrays = {"step": cp(sd["step"])}
            cpu_arrays.update({f"mp_{i}": cp(a)
                               for i, a in enumerate(sd["master_params"])})
            cpu_arrays.update({f"m_{i}": cp(a)
                               for i, a in enumerate(sd["exp_avg"])})
            cpu_arrays.update({f"v_{i}": cp(a)
                               for i, a in enumerate(sd["exp_avg_sq"])})
        meta = {
            "global_step": int(self.state.global_step),
            "micro_step": int(self.state.micro_step),
            "skipped_steps": int(self.state.skipped_steps),
            "rng": np.asarray(self.state.rng).tolist(),
            "lr_scheduler": (self.lr_scheduler.state_dict()
                             if self.lr_scheduler is not None and
                             hasattr(self.lr_scheduler, "state_dict")
                             else None),
            "dp_world_size": self.dp_world_size,
            "zero_stage": self.zero_stage,
            "client_state": client_state or {},
        }
        if preempted:
            meta["preempted"] = True
        return snap_model, snap_optim, cpu_arrays, meta

    def _write_checkpoint_job(self, save_dir, tag, snap_model, snap_optim,
                              cpu_arrays, meta, samples):
        """The stage/commit protocol, run off host snapshots — inline by
        a blocking save, on the writer thread by an async one. The fault
        points are identical on both paths, so the tier-1
        kill-at-every-stage contract covers async saves for free."""
        import shutil
        t0 = time.time()
        final_dir = os.path.join(save_dir, tag)
        tmp_dir = final_dir + ckpt.TMP_SUFFIX
        if jax.process_index() == 0:
            if os.path.isdir(tmp_dir):  # stale staging from a crashed save
                shutil.rmtree(tmp_dir)
            os.makedirs(tmp_dir, exist_ok=True)
        if jax.process_count() > 1:
            from jax.experimental import multihost_utils
            multihost_utils.sync_global_devices("ckpt_tmp_ready")
        # sharded format: every process writes only its local device shards
        # (reference per-dp-rank zero_pp_rank_* files, engine.py:1153-1164)
        # — no host-0 gather, flat host RAM regardless of model size
        ckpt.save_tree_sharded(tmp_dir, "model_states", snap_model)
        fault.fire("ckpt.after_shard", name="model_states", dir=tmp_dir)
        ckpt.save_tree_sharded(tmp_dir, "optim_states", snap_optim)
        fault.fire("ckpt.after_shard", name="optim_states", dir=tmp_dir)
        if jax.process_count() > 1:
            # every process's shard files must be durable before process 0
            # seals the marker — the marker asserts completeness
            from jax.experimental import multihost_utils
            multihost_utils.sync_global_devices("ckpt_shards_written")
        if jax.process_index() == 0:
            if cpu_arrays is not None:
                ckpt._atomic_write_bytes(
                    os.path.join(tmp_dir, "cpu_optim_states.npz"),
                    ckpt._npz_bytes(cpu_arrays))
            self._save_checkpoint_extras(tmp_dir)
            ckpt.write_meta(tmp_dir, meta)
            fault.fire("ckpt.before_marker", dir=tmp_dir)
            ckpt.write_commit_marker(tmp_dir,
                                     process_count=jax.process_count())
            fault.fire("ckpt.before_rename", dir=tmp_dir)
            # re-saving an existing tag: rename the old committed copy
            # aside instead of deleting it — a crash between the two
            # renames leaves '<tag>.old', which list_tags still offers as
            # a fallback candidate, so no window ever has zero copies
            old_dir = final_dir + ckpt.OLD_SUFFIX
            if os.path.isdir(final_dir):
                if os.path.isdir(old_dir):
                    shutil.rmtree(old_dir)
                os.rename(final_dir, old_dir)
            os.replace(tmp_dir, final_dir)
            ckpt._fsync_dir(save_dir)
            if os.path.isdir(old_dir):
                shutil.rmtree(old_dir)
            ckpt.write_latest(save_dir, tag)
            keep_n = int(self._ckpt_cfg["keep_n"] or 0)
            if keep_n > 0:
                dropped = ckpt.gc_old_tags(save_dir, keep_n)
                if dropped:
                    log_dist(f"checkpoint retention (keep_n={keep_n}): "
                             f"removed {dropped}", ranks=[0])
        if jax.process_count() > 1:
            from jax.experimental import multihost_utils
            multihost_utils.sync_global_devices("ckpt_committed")
        write_ms = (time.time() - t0) * 1000.0
        # liveness beat from the commit tail (thread-safe: the watchdog
        # timestamp is a plain assignment, and this runs on the async
        # writer thread for async saves) — a long blocking save must
        # not read as a stalled train loop
        self.health.heartbeat("checkpoint_commit")
        pending = (max(0, self._ckpt_writer.pending_saves() - 1)
                   if self._ckpt_writer is not None else 0)
        self.monitor.write_elastic_metrics(
            write_ms=write_ms, pending_saves=pending, samples=samples,
            flush=False)
        self.monitor.write_checkpoint_event(
            action="save", ok=True, duration_ms=write_ms, samples=samples)
        log_dist(f"saved checkpoint {final_dir} "
                 f"(committed in {write_ms:.0f}ms)", ranks=[0])
        return final_dir

    # ---------------------------------------------- async-save plumbing
    def _ensure_ckpt_writer(self):
        if self._ckpt_writer is None:
            self._ckpt_writer = ckpt.AsyncCheckpointWriter()
        return self._ckpt_writer

    def _drain_saves(self):
        """Barrier: block until every pending async save is durable
        (``close()`` / ``eval_batch`` / ``load_checkpoint`` call it).
        Writer errors are NOT raised here — they surface on the next
        ``save_checkpoint``/``close`` via _raise_async_save_error."""
        if self._ckpt_writer is not None:
            self._ckpt_writer.drain()

    def _raise_async_save_error(self):
        if self._ckpt_writer is not None:
            self._ckpt_writer.raise_pending_error()

    def wait_pending_saves(self):
        """Public async-save barrier: block until every pending async
        checkpoint has committed, then surface any writer error. Call
        before handing a save_dir to another consumer (e.g.
        ``InferenceEngine.from_checkpoint``) mid-run; ``close()`` and
        ``eval_batch`` already drain implicitly."""
        self._drain_saves()
        self._raise_async_save_error()

    # ------------------------------------------------- preemption drain
    def _elastic_boundary(self):
        """Step-boundary preemption check — both engines call it at the
        end of ``train_batch`` (and the facade ``step()``), i.e. only
        once the in-flight accumulation window has fully dispatched, so
        'finish the window, then drain' holds by construction."""
        if self._elastic is None or not self._elastic.preempted:
            return
        if self.gradient_accumulation_steps > 1 and \
                self._host_micro_step % self.gradient_accumulation_steps:
            # facade forward/backward/step path, mid-window: accumulated
            # grads are not part of a checkpoint — wait for the boundary
            return
        self._handle_preemption()

    def _handle_preemption(self):
        """Graceful drain: pending async saves finish, a
        preemption-tagged checkpoint commits, a ``preemption`` event row
        lands, the engine closes, and :class:`elastic.Preempted`
        (``SystemExit`` with the resumable code) propagates so the
        supervisor relaunches us."""
        reason = self._elastic.reason or "signal"
        step = int(self.global_steps)   # boundary: device value is settled
        log_dist(f"preemption ({reason}): draining at step {step}",
                 ranks=[0])
        save_dir = self._ckpt_cfg["save_dir"] or self._last_ckpt_dir
        tag = None
        committed = False
        if save_dir:
            self._drain_saves()   # a new save never interleaves with one
            tag = f"preempt_step{step}"
            try:
                self.save_checkpoint(save_dir, tag=tag, async_=False,
                                     preempted=True)
                committed = True
            except fault.InjectedCrash:
                raise   # durability tests kill the drain's save too
            except Exception as e:
                logger.warning(
                    f"preemption drain: checkpoint failed ({e!r}); "
                    "exiting resumable anyway — resume falls back to the "
                    "newest committed tag")
        else:
            logger.warning(
                "preemption drain: no checkpoint.save_dir configured and "
                "no prior save/load dir — exiting without a preemption "
                "checkpoint")
        self.observability.event(
            "preemption", reason=reason, step=step, tag=tag,
            committed=committed, restarts=self._restart_count)
        # black-box dump before close tears the telemetry down: the
        # relaunched incarnation (or a human) reads flight.json to see
        # the final pre-drain ring
        self.health.dump("drain", reason=reason, step=step, tag=tag)
        try:
            self.close()
        except Exception as e:
            logger.warning(f"preemption drain: close() failed ({e!r})")
        raise elastic.Preempted(step=step, tag=tag, reason=reason)

    def _save_checkpoint_extras(self, ckpt_dir: str) -> None:
        """Subclass hook: extra files written here (process 0, staging
        dir) are sealed by the COMMITTED marker with the shards — they
        can never be missing from a visible checkpoint."""

    def load_checkpoint(self, load_dir: str, tag: Optional[str] = None,
                        load_optimizer_states: bool = True,
                        load_lr_scheduler_states: bool = True,
                        verify_integrity: Optional[bool] = None):
        """Verified load with automatic fallback.

        With an explicit ``tag`` the checkpoint must verify (marker +
        sizes + CRC32 unless ``verify_integrity=False``) or this raises.
        With ``tag=None`` the directory is scanned newest-first and the
        newest *committed and verified* checkpoint is restored — a torn
        ``latest`` pointer or a corrupt newest tag costs at most one
        checkpoint of progress, never the run.
        """
        self._offload_drain()
        # loading while an async save of THIS dir is mid-commit would
        # race the newest-first scan; the drain also orders save->load
        self._drain_saves()
        ckpt.set_retry_policy(self._ckpt_cfg["io_retries"],
                              self._ckpt_cfg["io_retry_backoff"])
        self._last_ckpt_dir = load_dir
        t0 = time.time()
        if verify_integrity is None:
            verify_integrity = bool(self._ckpt_cfg["verify_checksums"])
        samples = self._host_global_step * self.train_batch_size()

        if tag is not None:
            ckpt_dir = os.path.join(load_dir, tag)
            ok, problems = ckpt.verify_checkpoint_dir(
                ckpt_dir, check_crc=verify_integrity)
            if not ok:
                raise RuntimeError(
                    f"checkpoint {ckpt_dir} failed integrity verification: "
                    f"{'; '.join(problems)}")
            result = self._load_checkpoint_dir(
                ckpt_dir, load_optimizer_states, load_lr_scheduler_states)
            self.monitor.write_checkpoint_event(
                action="load", ok=True,
                duration_ms=(time.time() - t0) * 1000.0, samples=samples)
            self._record_resume(ckpt_dir)
            return result

        latest = ckpt.read_latest(load_dir)
        candidates = ckpt.candidate_tags(load_dir)
        if not candidates:
            logger.warning(f"no loadable checkpoint tags in {load_dir}; "
                           "nothing loaded")
            return None, {}
        for cand in candidates:
            cand_dir = os.path.join(load_dir, cand)
            ok, problems = ckpt.verify_checkpoint_dir(
                cand_dir, check_crc=verify_integrity)
            if not ok:
                logger.warning(
                    f"skipping checkpoint {cand_dir}: "
                    f"{'; '.join(problems)} — falling back to an older tag")
                self.monitor.write_checkpoint_event(
                    action="fallback", ok=False, samples=samples)
                continue
            try:
                result = self._load_checkpoint_dir(
                    cand_dir, load_optimizer_states,
                    load_lr_scheduler_states)
            except fault.InjectedCrash:
                raise
            except Exception as e:
                logger.warning(
                    f"failed to load checkpoint {cand_dir} ({e!r}); "
                    "falling back to an older tag")
                self.monitor.write_checkpoint_event(
                    action="fallback", ok=False, samples=samples)
                continue
            if latest is not None and cand != latest:
                logger.warning(
                    f"'latest' pointer named {latest!r} but the newest "
                    f"committed+verified checkpoint is {cand!r}; resumed "
                    "from it (torn pointer or interrupted save)")
            self.monitor.write_checkpoint_event(
                action="load", ok=True,
                duration_ms=(time.time() - t0) * 1000.0, samples=samples)
            self._record_resume(cand_dir)
            return result
        logger.warning(f"no committed+verified checkpoint in {load_dir}; "
                       "nothing loaded")
        return None, {}

    def _record_resume(self, ckpt_dir: str) -> None:
        """One ``resume`` event row + the restart-count scalar after a
        successful restore — together with the save side's
        ``preemption`` row, obs_report can reconstruct the full
        preempt -> relaunch -> resume chain of a supervised run."""
        samples = self._host_global_step * self.train_batch_size()
        self.observability.event(
            "resume", step=self._host_global_step,
            tag=os.path.basename(ckpt_dir),
            restarts=self._restart_count,
            preempted=ckpt.is_preemption_tag(ckpt_dir))
        self.monitor.write_elastic_metrics(
            restarts=self._restart_count, samples=samples)

    def _load_checkpoint_dir(self, ckpt_dir: str,
                             load_optimizer_states: bool = True,
                             load_lr_scheduler_states: bool = True):
        """Restore engine state from one verified checkpoint directory."""
        # read + validate meta BEFORE any engine mutation: if it is
        # semantically incomplete, this raises while the engine is still
        # pristine and the fallback loop can cleanly try an older tag
        # (no half-loaded optimizer/lr state left behind)
        meta = ckpt.read_meta(ckpt_dir)
        missing = [k for k in ("global_step", "micro_step",
                               "skipped_steps", "rng") if k not in meta]
        if missing:
            raise KeyError(f"meta.json in {ckpt_dir} missing {missing}")
        meta_rng = np.asarray(meta["rng"], dtype=np.uint32)
        sharded = ckpt.sharded_exists(ckpt_dir, "model_states")
        if sharded:
            params = ckpt.load_tree_sharded(
                ckpt_dir, "model_states", self.state.params,
                shardings=self._state_shardings.params)
        else:  # legacy single-file format
            params = ckpt.load_tree(
                os.path.join(ckpt_dir, "model_states.npz"),
                self.state.params,
                shardings=self._state_shardings.params)
        new_state = self.state._replace(params=params)
        if load_optimizer_states:
            opt_tmpl = {"opt_state": self.state.opt_state,
                        "loss_scale": self.state.loss_scale}
            opt_shd = {"opt_state": self._state_shardings.opt_state,
                       "loss_scale": self._state_shardings.loss_scale}
            if sharded:
                opt = ckpt.load_tree_sharded(ckpt_dir, "optim_states",
                                             opt_tmpl, shardings=opt_shd)
            else:
                opt = ckpt.load_tree(
                    os.path.join(ckpt_dir, "optim_states.npz"),
                    opt_tmpl, shardings=opt_shd)
            new_state = new_state._replace(opt_state=opt["opt_state"],
                                           loss_scale=opt["loss_scale"])
            if self.zero_cpu_offload:
                cpu_path = os.path.join(ckpt_dir, "cpu_optim_states.npz")
                if not os.path.exists(cpu_path):
                    # without the host master state the first offload step
                    # would overwrite the loaded weights with init-time
                    # params — fail loudly instead
                    raise FileNotFoundError(
                        f"{cpu_path} missing: checkpoint was not saved by "
                        "a cpu_offload run. Re-save with offload enabled, "
                        "or pass load_optimizer_states=False and accept a "
                        "fresh optimizer (master params will be re-seeded "
                        "from the loaded model weights).")
                z = np.load(cpu_path)
                n = len(self.optimizer.master_params)
                self.optimizer.load_state_dict({
                    "step": int(z["step"]),
                    "master_params": [z[f"mp_{i}"] for i in range(n)],
                    "exp_avg": [z[f"m_{i}"] for i in range(n)],
                    "exp_avg_sq": [z[f"v_{i}"] for i in range(n)]})
        elif self.zero_cpu_offload:
            # fresh optimizer requested: re-seed the host master copy from
            # the loaded weights so the next step starts from them
            from deepspeed_tpu.runtime.checkpoint import _to_host_global
            for dst, src in zip(self.optimizer.master_params,
                                jax.tree_util.tree_leaves(params)):
                np.copyto(dst, np.asarray(_to_host_global(src),
                                          np.float32).ravel())
        # topology sanity (warn, don't crash: elastic resume across dp
        # worlds / ZeRO stages is the supported path — but the operator
        # should know it happened)
        saved_dp = meta.get("dp_world_size")
        if saved_dp is not None and saved_dp != self.dp_world_size:
            logger.warning(
                f"checkpoint {ckpt_dir} was saved at dp_world_size="
                f"{saved_dp}, resuming at {self.dp_world_size} "
                "(elastic repartition)")
        saved_stage = meta.get("zero_stage")
        if saved_stage is not None and saved_stage != self.zero_stage:
            logger.warning(
                f"checkpoint {ckpt_dir} was saved at zero_stage="
                f"{saved_stage}, resuming at {self.zero_stage}")
        repl = self._state_shardings.global_step
        new_state = new_state._replace(
            global_step=jax.device_put(
                jnp.asarray(meta["global_step"], jnp.int32), repl),
            micro_step=jax.device_put(
                jnp.asarray(meta["micro_step"], jnp.int32), repl),
            skipped_steps=jax.device_put(
                jnp.asarray(meta["skipped_steps"], jnp.int32), repl),
            rng=jax.device_put(
                jnp.asarray(meta_rng), repl),
        )
        if load_lr_scheduler_states and self.lr_scheduler is not None and \
                meta.get("lr_scheduler") is not None:
            self.lr_scheduler.load_state_dict(meta["lr_scheduler"])
        self.state = new_state
        # host mirrors must track the restored device counters
        self._host_global_step = int(meta["global_step"])
        self._host_micro_step = (self._host_global_step *
                                 self.gradient_accumulation_steps +
                                 int(meta["micro_step"]))
        log_dist(f"loaded checkpoint {ckpt_dir} "
                 f"(step={int(meta['global_step'])} "
                 f"skipped_steps={int(meta['skipped_steps'])} "
                 f"loss_scale={self.loss_scale():.0f} "
                 f"saved at dp={meta.get('dp_world_size')}, now "
                 f"dp={self.dp_world_size})", ranks=[0])
        return ckpt_dir, meta.get("client_state", {})
