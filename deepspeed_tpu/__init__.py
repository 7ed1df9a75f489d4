"""DeepSpeed-TPU: a TPU-native training framework.

Re-implements the capabilities of the reference DeepSpeed snapshot
(``deepspeed/__init__.py``; initialize at :52, add_config_arguments at :195)
on JAX/XLA/Pallas: ZeRO via GSPMD sharding, pipeline + 3D parallelism over a
named device mesh, fused transformer kernels in Pallas, bf16-first mixed
precision, block-sparse attention, and a multi-host launcher.
"""

# setup/import: stamped here and closed at the bottom of this file; the
# compile ledger's listeners are on from the first line below
import time as _time
_T_IMPORT = _time.perf_counter()
from deepspeed_tpu.profiling.recompile import setup_span as _setup_span  # noqa: E402
_IMPORT = _setup_span("setup/import", t0=_T_IMPORT)
_IMPORT.__enter__()

from deepspeed_tpu.runtime.engine import DeepSpeedEngine, TrainState  # noqa: E402
from deepspeed_tpu.runtime.config import DeepSpeedConfig
from deepspeed_tpu.runtime.pipe import (
    LayerSpec, PipelineModule, PipelineSpec, TiedLayerSpec)
from deepspeed_tpu.runtime.pipe.engine import PipelineEngine
from deepspeed_tpu.runtime.lr_schedules import (
    WarmupLR, OneCycle, LRRangeTest, add_tuning_arguments)
from deepspeed_tpu.utils.logging import log_dist
from deepspeed_tpu.runtime.dataloader import (
    DeepSpeedDataLoader, PrefetchLoader, RepeatingLoader)
from deepspeed_tpu.parallel.topology import (
    ProcessTopology, PipeDataParallelTopology, PipeModelDataParallelTopology,
    ParallelGrid)
from deepspeed_tpu.parallel.mesh import build_mesh
from deepspeed_tpu.ops.optimizers import (
    Adam, FusedAdam, Lamb, FusedLamb, SGD)
# reference exports the fused layer at top level (__init__.py:15)
from deepspeed_tpu.ops.transformer import (
    DeepSpeedTransformerLayer, DeepSpeedTransformerConfig)
# reference exports `deepspeed.checkpointing` (__init__.py:16)
from deepspeed_tpu.runtime.activation_checkpointing import checkpointing
# explicit multi-host bootstrap for user scripts (engine.py calls it
# automatically at initialize(); exported for the standalone-use parity
# of deepspeed.init_distributed)
from deepspeed_tpu.distributed import init_distributed
# serving (TPU-native extension: the reference snapshot is
# training-only; docs/inference.md)
from deepspeed_tpu.inference import InferenceEngine

__version__ = "0.1.0"


def _git_info():
    """Best-effort (hash, branch) — the reference bakes these at install
    (setup.py writes git_version_info consumed by basic_install_test.py);
    here they read from the working tree and fall back to 'unknown'."""
    import os
    head = os.path.join(os.path.dirname(os.path.dirname(
        os.path.abspath(__file__))), ".git", "HEAD")
    try:
        with open(head) as f:
            ref = f.read().strip()
        if ref.startswith("ref:"):
            refname = ref.split()[1]
            branch = refname.split("/")[-1]
            try:
                with open(os.path.join(os.path.dirname(head),
                                       *refname.split("/"))) as f:
                    return f.read().strip()[:9], branch
            except OSError:
                # after git gc/pack-refs the loose ref file is gone —
                # the hash lives in .git/packed-refs (ADVICE r3 #1)
                try:
                    with open(os.path.join(os.path.dirname(head),
                                           "packed-refs")) as f:
                        for line in f:
                            parts = line.strip().split(" ", 1)
                            if len(parts) == 2 and parts[1] == refname:
                                return parts[0][:9], branch
                except OSError:
                    pass
                return "unknown", branch
        return ref[:9], "detached"
    except OSError:
        return "unknown", "unknown"


__git_hash__, __git_branch__ = _git_info()


def initialize(args=None,
               model=None,
               optimizer=None,
               model_parameters=None,
               training_data=None,
               lr_scheduler=None,
               mpu=None,
               param_specs=None,
               dist_init_required=None,
               collate_fn=None,
               config=None,
               config_params=None):
    """Initialize the DeepSpeed-TPU engine (reference __init__.py:52).

    Returns the same 4-tuple as the reference:
    ``(engine, optimizer, training_dataloader, lr_scheduler)``.

    Model contract (TPU-native): ``model`` is a pure loss function
    ``loss_fn(params, batch[, rng]) -> loss | (loss, aux)`` and
    ``model_parameters`` is the initial parameter pytree. Use
    :func:`flax_loss_fn` to adapt a flax module + criterion.
    """
    if isinstance(model, (PipelineModule, PipelineSpec)):
        # (reference __init__.py:111-133 dispatches on PipelineModule)
        assert mpu is None, "mpu is owned by the PipelineModule's topology"
        assert param_specs is None, \
            "pipeline models carry their own shardings (PipelineSpec " \
            "pre/stage/post_specs); param_specs is not consumed here"
        engine = PipelineEngine(model=model,
                                args=args,
                                optimizer=optimizer,
                                model_parameters=model_parameters,
                                training_data=training_data,
                                lr_scheduler=lr_scheduler,
                                collate_fn=collate_fn,
                                config=config,
                                config_params=config_params)
    else:
        engine = DeepSpeedEngine(args=args,
                                 model=model,
                                 optimizer=optimizer,
                                 model_parameters=model_parameters,
                                 training_data=training_data,
                                 lr_scheduler=lr_scheduler,
                                 mpu=mpu,
                                 param_specs=param_specs,
                                 collate_fn=collate_fn,
                                 config=config,
                                 config_params=config_params)
    return (engine, engine.optimizer, engine.training_dataloader,
            engine.lr_scheduler)


def flax_loss_fn(module, criterion):
    """Adapt a flax linen Module + criterion to the engine's loss contract.

    ``criterion(outputs, batch) -> loss``; batches are pytrees whose
    structure the criterion understands (e.g. dicts with 'x'/'y').
    """
    def loss_fn(params, batch, rng):
        inputs = batch["x"] if isinstance(batch, dict) else batch[0]
        outputs = module.apply({"params": params}, inputs,
                               rngs={"dropout": rng})
        return criterion(outputs, batch)
    return loss_fn


def add_config_arguments(parser):
    """Add --deepspeed / --deepspeed_config CLI args
    (reference __init__.py:144-192)."""
    group = parser.add_argument_group("DeepSpeed-TPU",
                                      "DeepSpeed-TPU configurations")
    group.add_argument("--deepspeed", default=False, action="store_true",
                       help="Enable DeepSpeed-TPU (helper flag)")
    group.add_argument("--deepspeed_config", default=None, type=str,
                       help="DeepSpeed-TPU json configuration file")
    group.add_argument("--deepscale", default=False, action="store_true",
                       help="Deprecated alias of --deepspeed")
    group.add_argument("--deepscale_config", default=None, type=str,
                       help="Deprecated alias of --deepspeed_config")
    return parser


_IMPORT.__exit__(None, None, None)
