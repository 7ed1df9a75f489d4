"""Config system tests (mirrors reference tests/unit/test_config.py)."""

import json
import os

import pytest

from deepspeed_tpu.runtime.config import DeepSpeedConfig, DeepSpeedConfigError


def base_dict(**kwargs):
    d = {"train_batch_size": 32}
    d.update(kwargs)
    return d


class TestBatchTriangle:

    def test_all_three_consistent(self):
        cfg = DeepSpeedConfig(
            {
                "train_batch_size": 32,
                "train_micro_batch_size_per_gpu": 4,
                "gradient_accumulation_steps": 2,
            },
            world_size=4)
        assert cfg.train_batch_size == 32
        assert cfg.train_micro_batch_size_per_gpu == 4
        assert cfg.gradient_accumulation_steps == 2

    def test_all_three_inconsistent_raises(self):
        with pytest.raises(AssertionError):
            DeepSpeedConfig(
                {
                    "train_batch_size": 32,
                    "train_micro_batch_size_per_gpu": 4,
                    "gradient_accumulation_steps": 4,
                },
                world_size=4)

    def test_derive_grad_acc(self):
        cfg = DeepSpeedConfig(
            {"train_batch_size": 32, "train_micro_batch_size_per_gpu": 4},
            world_size=4)
        assert cfg.gradient_accumulation_steps == 2

    def test_derive_micro_batch(self):
        cfg = DeepSpeedConfig(
            {"train_batch_size": 32, "gradient_accumulation_steps": 2},
            world_size=4)
        assert cfg.train_micro_batch_size_per_gpu == 4

    def test_derive_train_batch(self):
        cfg = DeepSpeedConfig(
            {"train_micro_batch_size_per_gpu": 4, "gradient_accumulation_steps": 2},
            world_size=4)
        assert cfg.train_batch_size == 32

    def test_only_train_batch(self):
        cfg = DeepSpeedConfig({"train_batch_size": 32}, world_size=4)
        assert cfg.train_micro_batch_size_per_gpu == 8
        assert cfg.gradient_accumulation_steps == 1

    def test_only_micro_batch(self):
        cfg = DeepSpeedConfig({"train_micro_batch_size_per_gpu": 4}, world_size=4)
        assert cfg.train_batch_size == 16
        assert cfg.gradient_accumulation_steps == 1

    def test_none_raises(self):
        with pytest.raises(DeepSpeedConfigError):
            DeepSpeedConfig({"steps_per_print": 10}, world_size=4)

    def test_chip_spelling(self):
        cfg = DeepSpeedConfig({"train_micro_batch_size_per_chip": 4}, world_size=2)
        assert cfg.train_batch_size == 8


class TestFeatureConfigs:

    def test_defaults(self):
        cfg = DeepSpeedConfig(base_dict(), world_size=1)
        assert not cfg.fp16_enabled
        assert not cfg.bf16_enabled
        assert cfg.zero_optimization_stage == 0
        assert not cfg.zero_enabled
        assert cfg.gradient_clipping == 0.0
        assert cfg.steps_per_print == 10
        assert cfg.prescale_gradients is False
        assert cfg.optimizer_name is None
        assert cfg.scheduler_name is None

    def test_fp16(self):
        cfg = DeepSpeedConfig(
            base_dict(fp16={
                "enabled": True,
                "loss_scale": 0,
                "initial_scale_power": 16,
                "loss_scale_window": 500,
                "hysteresis": 2,
                "min_loss_scale": 1,
            }),
            world_size=1)
        assert cfg.fp16_enabled
        assert cfg.loss_scale == 0
        assert cfg.initial_dynamic_scale == 2**16
        assert cfg.dynamic_loss_scale_args["scale_window"] == 500

    def test_bf16(self):
        cfg = DeepSpeedConfig(base_dict(bf16={"enabled": True}), world_size=1)
        assert cfg.bf16_enabled

    def test_fp16_and_bf16_conflict(self):
        with pytest.raises(DeepSpeedConfigError):
            DeepSpeedConfig(
                base_dict(fp16={"enabled": True}, bf16={"enabled": True}),
                world_size=1)

    def test_zero_stage2(self):
        cfg = DeepSpeedConfig(
            base_dict(zero_optimization={
                "stage": 2,
                "cpu_offload": True,
                "overlap_comm": True,
            }),
            world_size=1)
        assert cfg.zero_enabled
        assert cfg.zero_optimization_stage == 2
        assert cfg.zero_config.cpu_offload
        assert cfg.zero_config.overlap_comm
        assert cfg.zero_config.reduce_scatter

    def test_zero_legacy_bool(self):
        cfg = DeepSpeedConfig(base_dict(zero_optimization=True), world_size=1)
        assert cfg.zero_optimization_stage == 1

    def test_optimizer_scheduler(self):
        cfg = DeepSpeedConfig(
            base_dict(
                optimizer={"type": "Adam", "params": {"lr": 1e-3}},
                scheduler={"type": "WarmupLR",
                           "params": {"warmup_num_steps": 10}},
            ),
            world_size=1)
        assert cfg.optimizer_name == "adam"
        assert cfg.optimizer_params["lr"] == 1e-3
        assert cfg.scheduler_name == "WarmupLR"
        assert cfg.scheduler_params["warmup_num_steps"] == 10

    def test_sparse_attention_fixed(self):
        cfg = DeepSpeedConfig(
            base_dict(sparse_attention={
                "mode": "fixed",
                "block": 16,
                "num_local_blocks": 4,
                "num_global_blocks": 1,
            }),
            world_size=1)
        sa = cfg.sparse_attention
        assert sa["mode"] == "fixed"
        assert sa["block"] == 16
        assert sa["num_local_blocks"] == 4

    def test_sparse_attention_bigbird(self):
        cfg = DeepSpeedConfig(
            base_dict(sparse_attention={"mode": "bigbird", "num_random_blocks": 2}),
            world_size=1)
        assert cfg.sparse_attention["num_random_blocks"] == 2

    def test_sparse_attention_bad_mode(self):
        with pytest.raises(NotImplementedError):
            DeepSpeedConfig(
                base_dict(sparse_attention={"mode": "nope"}), world_size=1)

    def test_activation_checkpointing(self):
        cfg = DeepSpeedConfig(
            base_dict(activation_checkpointing={
                "partition_activations": True,
                "cpu_checkpointing": True,
                "number_checkpoints": 4,
            }),
            world_size=1)
        acc = cfg.activation_checkpointing_config
        assert acc.partition_activations
        assert acc.cpu_checkpointing
        assert acc.number_checkpoints == 4

    def test_pipeline_config(self):
        cfg = DeepSpeedConfig(
            base_dict(pipeline={"stages": 4, "partition": "parameters"}),
            world_size=1)
        assert cfg.pipeline["stages"] == 4
        assert cfg.pipeline["partition"] == "parameters"
        assert cfg.pipeline["seed_layers"] is False

    def test_mesh_axes(self):
        cfg = DeepSpeedConfig(
            base_dict(mesh={"axes": {"data": 4, "model": 2}}), world_size=1)
        assert cfg.mesh_axes == {"data": 4, "model": 2}

    def test_json_file_and_duplicate_keys(self, tmp_path):
        p = tmp_path / "ds_config.json"
        p.write_text(json.dumps(base_dict()))
        cfg = DeepSpeedConfig(str(p), world_size=1)
        assert cfg.train_batch_size == 32

        bad = tmp_path / "dup.json"
        bad.write_text('{"train_batch_size": 8, "train_batch_size": 16}')
        with pytest.raises(ValueError):
            DeepSpeedConfig(str(bad), world_size=1)


class TestCompileCache:
    def test_defaults_and_override(self):
        cfg = DeepSpeedConfig(base_dict(), world_size=1)
        assert cfg.compile_cache_config["enabled"] is True
        # no dir in the JSON: the helper picks the in-checkout default
        assert cfg.compile_cache_config["dir"] is None
        cfg = DeepSpeedConfig(
            base_dict(compile_cache={"enabled": False, "dir": "/tmp/x",
                                     "min_compile_secs": 0.0}),
            world_size=1)
        assert cfg.compile_cache_config == {
            "enabled": False, "dir": "/tmp/x", "min_compile_secs": 0.0}

    @pytest.fixture
    def platform(self, monkeypatch):
        """utils/platform with no env override, no active dir, and
        jax's once-initialized cache object dropped; restored after."""
        import jax
        from jax.experimental.compilation_cache import compilation_cache
        from deepspeed_tpu.utils import platform as P
        monkeypatch.delenv(P.COMPILE_CACHE_ENV, raising=False)
        prev = jax.config.jax_compilation_cache_dir
        prev_secs = jax.config.jax_persistent_cache_min_compile_time_secs
        jax.config.update("jax_compilation_cache_dir", None)
        compilation_cache.reset_cache()
        yield P
        jax.config.update("jax_compilation_cache_dir", prev)
        jax.config.update("jax_persistent_cache_min_compile_time_secs",
                          prev_secs)
        compilation_cache.reset_cache()

    def test_enable_populates_cache_dir(self, tmp_path, platform):
        import jax
        import jax.numpy as jnp
        assert platform.enable_compile_cache(
            str(tmp_path), min_compile_secs=0.0) == str(tmp_path)
        # second call, different dir: the active one is reported
        # (jax's cache dir is global — first caller wins)
        assert platform.enable_compile_cache(
            str(tmp_path / "other")) == str(tmp_path)
        assert not (tmp_path / "other").exists()
        jax.jit(lambda x: jnp.sin(x) * 41.2512)(jnp.ones((8, 8)))
        assert os.listdir(str(tmp_path)), "no cache entry written"

    def test_default_dir_is_fixed_inside_the_checkout(self, platform):
        import deepspeed_tpu
        repo = os.path.dirname(os.path.dirname(
            os.path.abspath(deepspeed_tpu.__file__)))
        assert platform.DEFAULT_COMPILE_CACHE_DIR == \
            os.path.join(repo, ".jax_cache")
        assert platform.enable_compile_cache() == \
            platform.DEFAULT_COMPILE_CACHE_DIR

    def test_environment_wins_and_nothing_is_set_in_code(
            self, tmp_path, monkeypatch, platform):
        import jax
        secs = jax.config.jax_persistent_cache_min_compile_time_secs
        monkeypatch.setenv(platform.COMPILE_CACHE_ENV,
                           str(tmp_path / "env"))
        # a user's compile_cache.dir loses to the environment
        assert platform.enable_compile_cache(
            str(tmp_path / "json"), min_compile_secs=secs + 1) == \
            str(tmp_path / "env")
        assert jax.config.jax_compilation_cache_dir is None
        assert jax.config.jax_persistent_cache_min_compile_time_secs == secs
        assert not (tmp_path / "json").exists()
