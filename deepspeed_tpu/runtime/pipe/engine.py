"""PipelineEngine — the training engine for pipeline-parallel models.

TPU-native analog of the reference's ``deepspeed/runtime/pipe/engine.py``
(PipelineEngine :45, train_batch :229, eval_batch :306). The reference
subclasses DeepSpeedEngine and *interprets* a PipeSchedule instruction
stream per rank with blocking p2p; here the subclass swaps the engine's
compiled micro-step for a compiled **pipelined batch step**
(runtime/pipe/spmd.py): one dispatch covers all micro-batches, every stage,
forward + backward + optimizer — the reference's
``_exec_schedule``/``_exec_*`` handlers (:1132-1145, :480-941) collapse
into the scan the compiler schedules.

What is inherited unchanged from DeepSpeedEngine: optimizer construction,
ZeRO shardings (over 'data', composing with the 'pipe'-stacked stage
params), fp16/bf16 policy + loss scaling, LR schedules, checkpointing,
timers/throughput. Reference parity notes:

- micro_batches per train_batch = gradient_accumulation_steps (the batch
  triangle, config.py:557 — same here);
- ``_aggregate_total_loss`` (ref :374) = the psum/pmean inside the compiled
  loss;
- tied-weight grad reduction (ref :203) is the automatic psum transpose of
  replicated tied params;
- PP×ZeRO-2 composes here (grad accumulation happens inside one compiled
  step, so the reference's conflict — engine.py:751-754 — does not exist).
"""

import os
from typing import Any, Callable, Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from deepspeed_tpu.parallel.mesh import axis_size
from deepspeed_tpu.runtime import fault
from deepspeed_tpu.runtime.dataloader import (DeepSpeedDataLoader,
                                              PrefetchLoader,
                                              normalize_eval_input,
                                              stack_micro_batches)
from deepspeed_tpu.runtime.engine import DeepSpeedEngine, TrainState
from deepspeed_tpu.runtime.pipe.module import PipelineModule
from deepspeed_tpu.runtime.pipe.spmd import (
    PipelineSpec, build_pipeline_grad_fn, build_pipeline_loss_fn,
    microbatch_sharding, module_pipeline_spec, pipeline_param_specs)
from deepspeed_tpu.utils.logging import log_dist


class PipelineEngine(DeepSpeedEngine):
    """Engine over a PipelineSpec (or homogeneous PipelineModule).

    ``train_batch(data_iter)`` consumes ``micro_batches`` micro-batches,
    stacks them on a leading axis, and runs ONE compiled pipelined step.
    """

    def __init__(self, model=None, config=None, config_params=None,
                 seed: int = 0, **kwargs):
        raw = config if config is not None else config_params
        if isinstance(raw, str):
            import json as _json
            with open(raw) as f:
                raw = _json.load(f)
        assert isinstance(raw, dict), "PipelineEngine needs a config dict/path"

        # resolve the batch triangle against the data-parallel world size
        # BEFORE super().__init__: micro_batches = grad-accum steps
        # (reference pipe/engine.py:79: micro_batches = gas)
        from deepspeed_tpu.parallel.mesh import build_mesh
        from deepspeed_tpu.runtime.config import DeepSpeedConfig
        mesh_axes = raw.get("mesh", {}).get("axes")
        probe_mesh = build_mesh(mesh_axes)
        if "pipe" not in probe_mesh.axis_names or \
                axis_size(probe_mesh, "pipe") < 1:
            raise ValueError("PipelineEngine requires a 'pipe' mesh axis in "
                             "config['mesh']['axes']")
        dp = axis_size(probe_mesh, "data")
        resolved = DeepSpeedConfig(raw, world_size=dp)
        if resolved.zero_optimization_stage >= 3:
            raise ValueError(
                "ZeRO stage 3 does not compose with pipeline parallelism "
                "(the pipeline executor owns its param lifecycle; stage "
                "<= 2 shards optimizer/gradient state over 'data')")
        self.micro_batches = resolved.gradient_accumulation_steps
        self._true_train_batch_size = resolved.train_batch_size

        # the pipelined step consumes the whole accumulation window in one
        # dispatch, so the base engine runs with gas=1 (no accum buffer)
        inner = dict(raw)
        inner["gradient_accumulation_steps"] = 1
        inner["train_batch_size"] = \
            resolved.train_micro_batch_size_per_gpu * dp
        inner["train_micro_batch_size_per_gpu"] = \
            resolved.train_micro_batch_size_per_gpu

        # interleaved virtual stages: each device hosts V chunks of 1/V
        # the layers, cutting the normalized fill/drain bubble from
        # 2(S-1) to ((V-1)S + 2(S-1))/V ticks (spmd.py module docstring)
        self.num_virtual = int(raw.get("pipeline", {})
                               .get("virtual_stages", 1))
        if self.num_virtual < 1:
            raise ValueError("pipeline.virtual_stages must be >= 1")
        num_stages = axis_size(probe_mesh, "pipe") * self.num_virtual
        if isinstance(model, PipelineModule):
            self.pipeline_spec = module_pipeline_spec(model, num_stages)
            self.module = model
        elif isinstance(model, PipelineSpec):
            self.pipeline_spec = model
            self.module = None
        else:
            raise TypeError(
                "PipelineEngine model must be a PipelineModule or "
                f"PipelineSpec, got {type(model)}")
        if self.pipeline_spec.num_stages != num_stages:
            raise ValueError(
                f"spec has {self.pipeline_spec.num_stages} stages; mesh "
                f"pipe axis x virtual_stages = {num_stages}")

        params = kwargs.pop("model_parameters", None)
        if params is None:
            params = self.pipeline_spec.init(jax.random.PRNGKey(seed))
        elif self.module is not None and not (
                isinstance(params, dict) and "stages" in params):
            # flat per-layer PipelineModule params -> stacked pipeline form
            params = {"pre": {}, "stages": self.module.stack_stage_params(
                params), "post": {}}
        if self.num_virtual > 1:
            # caller-facing layout is global-stage order; the executors
            # (and checkpoints) use the interleaved at-rest layout so the
            # contiguous 'pipe' sharding lands each device's cyclic chunks
            from deepspeed_tpu.runtime.pipe.spmd import interleave_stages
            params = dict(params)
            params["stages"] = interleave_stages(
                params["stages"], axis_size(probe_mesh, "pipe"),
                self.num_virtual)
        specs = pipeline_param_specs(self.pipeline_spec, params)

        if resolved.fp16_enabled:
            compute_dtype = jnp.float16
        elif resolved.bf16_enabled:
            compute_dtype = jnp.bfloat16
        else:
            compute_dtype = None
        loss_fn = build_pipeline_loss_fn(
            self.pipeline_spec, probe_mesh, num_micro=self.micro_batches,
            remat=raw.get("pipeline", {}).get("activation_checkpoint", True),
            compute_dtype=compute_dtype, num_virtual=self.num_virtual)
        # training runs the explicit 1F1B executor (O(S) activation memory,
        # grads computed in-schedule); the forward-only wavefront above
        # remains for eval_batch
        loss_fn.grad_fn = build_pipeline_grad_fn(
            self.pipeline_spec, probe_mesh, num_micro=self.micro_batches,
            compute_dtype=compute_dtype, num_virtual=self.num_virtual)

        super().__init__(model=loss_fn, model_parameters=params,
                         param_specs=specs, config=inner, seed=seed,
                         **kwargs)
        self.num_stages = num_stages
        # the inner config runs at gas=1, but each train_batch() consumes
        # the full accumulation window — retune the throughput timer so
        # samples/sec reflects micro_batches per tick
        self.tput_timer.batch_size = (
            self._true_train_batch_size // max(self.dp_world_size, 1))
        self._batch_sharding = microbatch_sharding(self.mesh)
        log_dist(
            f"PipelineEngine: stages={num_stages} "
            f"micro_batches={self.micro_batches} "
            f"global_batch={self._true_train_batch_size}", ranks=[0])

    # the externally visible batch size is the full accumulation window
    def train_batch_size(self):
        return self._true_train_batch_size

    def _wrap_train_iter(self, it):
        """The pipelined step stacks its own micro window; the async
        prefetch stage (when configured) assembles + device_puts the
        stacked (M, ...) batch off-thread with the pipe sharding."""
        if self._prefetch_depth <= 0:
            return it
        if isinstance(self.training_dataloader, DeepSpeedDataLoader):
            self.training_dataloader.device_put_enabled = False
        # stack_always: even an M=1 window needs the leading micro axis
        # the pipelined program (and self._batch_sharding) expect
        self._prefetcher = PrefetchLoader(
            it, put_fn=self._put_stacked_batch,
            depth=self._prefetch_depth, stack_micros=self.micro_batches,
            stack_always=True)
        return self._prefetcher

    def _stack_micro_batches(self, data_iter):
        """Pull micro_batches items and stack on a new leading axis (a
        stacking PrefetchLoader already yields the (M, ...) batch)."""
        if getattr(data_iter, "stacks_micro_batches", False):
            return next(data_iter)
        micros = [next(data_iter) for _ in range(self.micro_batches)]
        return jax.device_put(stack_micro_batches(micros),
                              self._batch_sharding)

    def train_batch(self, data_iter=None) -> jnp.ndarray:
        """One full pipelined optimizer step (reference pipe/engine.py:229).

        Accepts an iterator of micro-batches (engine-style) or of
        pre-stacked (M, ...) batches is NOT supported — always micro.
        """
        if data_iter is None:
            data_iter = self._ensure_train_iter()

        self._maybe_profile_step()
        # elastic passthrough: same window-then-drain contract as the
        # base engine (runtime/elastic.py; no-op unless armed)
        fault.fire("elastic.sigterm_mid_window",
                   step=self._host_global_step)
        # health passthrough: same beat-then-armed-stall order as the
        # base engine's train_batch
        self.health.heartbeat("train_batch")
        fault.fire("health.stall", step=self._host_global_step)
        with self.observability.span("pipe/stack_batch"):
            batch = self._stack_micro_batches(data_iter)
        step_fn = self._get_compiled_micro_step()
        self.tput_timer.start()
        import time as _time
        _t0 = _time.perf_counter()
        if self._window_anchor is None:
            self._window_anchor = _t0   # see base train_batch
        with self._batch_span("pipe/train_batch"):
            self.state, loss = step_fn(self.state, batch)
        self.tput_timer.stop()
        self._last_step_time_ms = (_time.perf_counter() - _t0) * 1e3
        self._host_micro_step += self.micro_batches
        self._host_global_step += 1
        # the pipelined program consumes the WHOLE accumulation window in
        # one dispatch, so its cost profile is already per optimizer step
        if self.observability.wants_flops_profile("micro_step"):
            self.observability.maybe_profile_flops(
                "micro_step", step_fn, (self.state, batch),
                samples=self._host_global_step * self.train_batch_size())
        self._report_progress()
        self._write_monitor(loss)  # tensorboard (reference pipe :283-292)
        self._elastic_boundary()
        return loss

    def eval_batch(self, data_iter) -> jnp.ndarray:
        """Pipelined forward-only loss (reference pipe/engine.py:306) —
        realizes InferenceSchedule's wavefront (the same scan, no grad).
        Accepts an iterator of micro batches or — like the base engine —
        a single batch pytree (repeated across the micro window; the
        mean loss over identical micros equals that batch's loss)."""
        self._drain_saves()   # eval barrier: pending async saves land
        if self._monitor_ring:
            self._flush_monitor()   # eval is an explicit sync point
        if not hasattr(self, "_compiled_pipe_eval"):
            def ev(params, batch, rng):
                return self._loss_fn(self._cast_for_loss(params), batch, rng)
            self._compiled_pipe_eval = self.observability.wrap_jit(
                jax.jit(ev), "pipe_eval")
        data_iter = normalize_eval_input(data_iter, self.micro_batches)
        batch = self._stack_micro_batches(data_iter)
        with self.observability.span("pipe/eval_batch"):
            return self._compiled_pipe_eval(self.state.params, batch,
                                            self.state.rng)

    # ---------------- checkpoint layout portability ----------------- #
    # stage weights are stored in the V-dependent interleaved layout
    # (spmd.py module docstring); a resume at a different pipe width or
    # virtual_stages must re-permute or every device silently runs the
    # wrong layers' weights. save records the layout; load converts.

    def _stage_order(self):
        from deepspeed_tpu.runtime.pipe.spmd import interleave_stage_order
        S = axis_size(self.mesh, "pipe")
        return interleave_stage_order(S, self.num_virtual)

    def _save_checkpoint_extras(self, ckpt_dir: str) -> None:
        # written into the staging dir and sealed by the COMMITTED marker
        # alongside the shards: a V>1 checkpoint can never become visible
        # without its layout file and be misread as V=1 (mis-permuted);
        # atomic+fsync'd like every other committed file so the marker's
        # recorded size/CRC can't outlive the bytes on power loss
        import json as _json
        from deepspeed_tpu.runtime import checkpoint as _ckpt
        _ckpt._atomic_write_bytes(
            os.path.join(ckpt_dir, "pipe_layout.json"),
            _json.dumps({"pipe_axis": axis_size(self.mesh, "pipe"),
                         "virtual_stages": self.num_virtual}).encode())

    def load_checkpoint(self, load_dir: str, tag=None, **kw):
        ret = super().load_checkpoint(load_dir, tag, **kw)
        if not ret or ret[0] is None:
            return ret
        ckpt_dir = ret[0]
        import json as _json
        from deepspeed_tpu.runtime.pipe.spmd import interleave_stage_order
        layout_path = os.path.join(ckpt_dir, "pipe_layout.json")
        if os.path.exists(layout_path):
            with open(layout_path) as f:
                saved = _json.load(f)
        else:
            # pre-layout checkpoints were only ever written at V=1
            # (identity order)
            saved = {"pipe_axis": self.pipeline_spec.num_stages,
                     "virtual_stages": 1}
        saved_order = interleave_stage_order(saved["pipe_axis"],
                                             saved["virtual_stages"])
        cur_order = self._stage_order()
        if saved_order != cur_order:
            from deepspeed_tpu.ops.optimizers import Adam8bitState
            if isinstance(self.state.opt_state, Adam8bitState):
                # the quantized moments are flattened (nblocks, block)
                # arrays — axis 0 is quantization blocks, NOT the stage
                # axis, so they cannot be re-permuted across layouts
                raise ValueError(
                    "pipeline layout changed (saved "
                    f"{saved['pipe_axis']}x{saved['virtual_stages']} vs "
                    f"current {self.pipeline_spec.num_stages}x"
                    f"{getattr(self, 'virtual_stages', 1)}) but Adam8bit "
                    "stores stage-stacked moments as flattened "
                    "quantization blocks and cannot re-permute them; "
                    "resume with the same layout, or use Adam for "
                    "layout-change resumes")
            # slot j currently holds global stage saved_order[j]; we need
            # it to hold cur_order[j]
            pos = {g: j for j, g in enumerate(saved_order)}
            perm = jnp.asarray([pos[g] for g in cur_order])

            def permute(tree, shd):
                if isinstance(tree, dict):
                    if "stages" in tree:
                        out = dict(tree)
                        out["stages"] = jax.tree_util.tree_map(
                            lambda x, s: jax.device_put(
                                jnp.take(x, perm, axis=0), s),
                            tree["stages"], shd["stages"])
                        return out
                    return {k: permute(v, shd[k]) for k, v in tree.items()}
                if hasattr(tree, "_fields"):
                    return type(tree)(*(
                        permute(getattr(tree, f), getattr(shd, f))
                        for f in tree._fields))
                if isinstance(tree, (list, tuple)):
                    return type(tree)(
                        permute(t, s) for t, s in zip(tree, shd))
                return tree

            shardings = self._state_shardings
            self.state = self.state._replace(
                params=permute(self.state.params, shardings.params),
                opt_state=permute(self.state.opt_state,
                                  shardings.opt_state))
            if getattr(self, "zero_cpu_offload", False):
                # the host-resident fp32 master + moments (ZeRO-Offload)
                # were restored in the saved layout too; left unpermuted,
                # the first host Adam step would push the wrong layers'
                # weights back to every device
                perm_np = np.asarray([pos[g] for g in cur_order])
                leaves = jax.tree_util.tree_flatten_with_path(
                    self.state.params)[0]
                for i, (path, leaf) in enumerate(leaves):
                    if not any(getattr(p, "key", None) == "stages"
                               for p in path):
                        continue
                    for arrs in (self.optimizer.master_params,
                                 self.optimizer.exp_avg,
                                 self.optimizer.exp_avg_sq):
                        a = arrs[i].reshape(leaf.shape)
                        arrs[i] = np.ascontiguousarray(
                            a[perm_np]).ravel()
            log_dist(
                f"pipe checkpoint re-permuted: saved layout "
                f"{saved['pipe_axis']}x{saved['virtual_stages']} -> "
                f"{axis_size(self.mesh, 'pipe')}x{self.num_virtual}",
                ranks=[0])
        return ret

    # forward/backward/step facade does not decompose for a pipelined
    # batch — the reference documents the same restriction
    # (pipe/engine.py:1078-1094 train_batch is the API)
    def forward(self, *a, **k):
        raise RuntimeError("PipelineEngine: use train_batch()/eval_batch() "
                           "(reference pipe/engine.py also forbids "
                           "forward()/backward() on pipelined models)")

    backward = forward
    step = forward
