"""Monitor/tensorboard tests: scalar writing (torch SummaryWriter or JSONL
fallback), engine integration writing loss/lr/scale per train_batch."""

import glob
import json
import os

import jax
import numpy as np
import pytest

from deepspeed_tpu.utils.monitor import TensorBoardMonitor, _JsonlWriter


def test_jsonl_writer(tmp_path):
    w = _JsonlWriter(str(tmp_path))
    w.add_scalar("Train/Samples/train_loss", 1.5, 10)
    w.add_scalar("Train/Samples/lr", 1e-3, 10)
    w.flush()
    lines = [json.loads(l) for l in
             open(os.path.join(tmp_path, "events.jsonl"))]
    assert lines[0] == {"tag": "Train/Samples/train_loss", "value": 1.5,
                        "step": 10}


def test_jsonl_schema_pinned(tmp_path):
    """tools/obs_report.py parses this log offline: the scalar row is
    exactly {"tag": str, "value": float, "step": int} (values coerced),
    and structured rows carry {"event": str, ...}."""
    w = _JsonlWriter(str(tmp_path))
    w.add_scalar("t", np.float32(1.5), np.int64(7))   # numpy in, json out
    w.add_event("compile", fn="micro_step", wall_ms=12.5)
    w.close()
    lines = [json.loads(l) for l in
             open(os.path.join(tmp_path, "events.jsonl"))]
    scalar, event = lines
    assert set(scalar) == {"tag", "value", "step"}
    assert type(scalar["tag"]) is str
    assert type(scalar["value"]) is float and scalar["value"] == 1.5
    assert type(scalar["step"]) is int and scalar["step"] == 7
    assert event["event"] == "compile" and event["wall_ms"] == 12.5


def test_jsonl_writer_crash_safe_line_buffering(tmp_path):
    """Rows must be on disk WITHOUT flush()/close(): a preempted run
    keeps its telemetry (the writer opens line-buffered)."""
    w = _JsonlWriter(str(tmp_path))
    w.add_scalar("a", 1.0, 1)
    # no flush, no close — read through a separate fd
    lines = open(os.path.join(tmp_path, "events.jsonl")).readlines()
    assert len(lines) == 1 and json.loads(lines[0])["tag"] == "a"
    w.close()


def test_jsonl_writer_context_manager_and_double_close(tmp_path):
    with _JsonlWriter(str(tmp_path)) as w:
        w.add_scalar("a", 1.0, 1)
    assert w._f is None
    w.close()                      # idempotent
    w.add_scalar("b", 2.0, 2)      # post-close writes are dropped, not a crash
    w.flush()
    lines = open(os.path.join(tmp_path, "events.jsonl")).readlines()
    assert len(lines) == 1


def test_jsonl_writer_del_closes_fd(tmp_path):
    w = _JsonlWriter(str(tmp_path))
    f = w._f
    del w
    import gc
    gc.collect()
    assert f.closed


def test_comm_metrics_flushed(tmp_path, monkeypatch):
    """write_comm_metrics was the only write_* method that never
    flushed — comm telemetry died with the process. Now it flushes like
    the rest."""
    import deepspeed_tpu.utils.monitor as mon

    class CountingWriter(_JsonlWriter):
        flushes = 0

        def flush(self):
            CountingWriter.flushes += 1
            super().flush()

    monkeypatch.setattr(mon, "_make_writer",
                        lambda log_dir: CountingWriter(log_dir))
    m = TensorBoardMonitor(enabled=True, output_path=str(tmp_path),
                           job_name="job")
    m.write_comm_metrics(bytes_per_step=1024.0, compression_ratio=2.0,
                         samples=8)
    assert CountingWriter.flushes >= 1
    m.close()
    lines = [json.loads(l) for l in
             open(os.path.join(tmp_path, "job", "events.jsonl"))]
    tags = {l["tag"]: l["value"] for l in lines}
    assert tags["Train/Samples/comm_bytes_per_step"] == 1024.0
    assert tags["Train/Samples/comm_compression_ratio"] == 2.0


def test_timer_values_flushed_and_gated(tmp_path, monkeypatch):
    """write_timer_values had BOTH halves of the write_* contract
    missing: no _writes() early-out (it crashed a disabled monitor on
    the f-string write path) and no trailing flush (timer telemetry
    buffered in the writer died with the process). Regression-pin
    both."""
    import deepspeed_tpu.utils.monitor as mon

    class CountingWriter(_JsonlWriter):
        flushes = 0

        def flush(self):
            CountingWriter.flushes += 1
            super().flush()

    CountingWriter.flushes = 0
    monkeypatch.setattr(mon, "_make_writer",
                        lambda log_dir: CountingWriter(log_dir))
    m = TensorBoardMonitor(enabled=True, output_path=str(tmp_path),
                           job_name="job")
    m.write_timer_values({"forward_microstep": 12.5, "backward": 30.0},
                         samples=64)
    assert CountingWriter.flushes >= 1
    m.close()
    lines = [json.loads(l) for l in
             open(os.path.join(tmp_path, "job", "events.jsonl"))]
    tags = {l["tag"]: (l["value"], l["step"]) for l in lines}
    assert tags["Train/Samples/forward_microstep"] == (12.5, 64)
    assert tags["Train/Samples/backward"] == (30.0, 64)
    # disabled monitor (no mirror): clean no-op, nothing written
    off = TensorBoardMonitor(enabled=False)
    off.write_timer_values({"forward": 1.0}, samples=1)
    off.close()


def test_monitor_mirror_receives_all_scalars(tmp_path):
    """The observability layer attaches a JSONL mirror: every monitor
    scalar (train metrics, checkpoint events, comm bytes) lands there
    even when tensorboard itself is disabled."""
    m = TensorBoardMonitor(enabled=False)
    assert m.writer is None
    mirror = _JsonlWriter(str(tmp_path))
    m.mirror = mirror
    m.write_train_metrics(loss=1.25, lr=1e-3, loss_scale=1.0, samples=4)
    m.write_checkpoint_event(action="save", ok=True, duration_ms=9.0,
                             samples=4)
    m.write_comm_metrics(bytes_per_step=77.0, samples=4)
    m.close()                      # must NOT close the (borrowed) mirror
    assert m.mirror is None and mirror._f is not None
    mirror.close()
    tags = {json.loads(l)["tag"] for l in
            open(os.path.join(tmp_path, "events.jsonl"))}
    assert {"Train/Samples/train_loss", "Train/Samples/lr",
            "Train/Samples/checkpoint_save_ms",
            "Train/Samples/checkpoint_save_ok",
            "Train/Samples/comm_bytes_per_step"} <= tags


def test_monitor_disabled_noops():
    m = TensorBoardMonitor(enabled=False)
    assert m.writer is None
    m.write_train_metrics(loss=1.0, lr=0.1, loss_scale=2.0, samples=1)
    m.flush(); m.close()  # all no-ops


def test_monitor_nonzero_rank_noops(tmp_path):
    m = TensorBoardMonitor(enabled=True, output_path=str(tmp_path), rank=3)
    assert m.writer is None


def test_monitor_checkpoint_events(tmp_path, monkeypatch):
    """Checkpoint durability telemetry: save/load durations and fallback
    events land as scalars (JSONL fallback path for determinism)."""
    import deepspeed_tpu.utils.monitor as mon
    monkeypatch.setattr(mon, "_make_writer",
                        lambda log_dir: _JsonlWriter(log_dir))
    m = TensorBoardMonitor(enabled=True, output_path=str(tmp_path),
                           job_name="job")
    m.write_checkpoint_event(action="save", ok=True, duration_ms=12.5,
                             samples=64)
    m.write_checkpoint_event(action="fallback", ok=False, samples=64)
    m.close()
    lines = [json.loads(l) for l in
             open(os.path.join(tmp_path, "job", "events.jsonl"))]
    tags = {l["tag"]: l["value"] for l in lines}
    assert tags["Train/Samples/checkpoint_save_ms"] == 12.5
    assert tags["Train/Samples/checkpoint_save_ok"] == 1.0
    assert tags["Train/Samples/checkpoint_fallback_ok"] == 0.0


@pytest.mark.slow
def test_monitor_writes_scalars(tmp_path):
    m = TensorBoardMonitor(enabled=True, output_path=str(tmp_path),
                           job_name="job")
    m.write_train_metrics(loss=2.0, lr=1e-4, loss_scale=8.0, samples=32)
    m.write_timer_values({"forward": 1.25, "backward": 2.5}, samples=32)
    m.close()
    files = glob.glob(str(tmp_path / "job" / "*"))
    assert files, "no event files written"


def test_engine_tensorboard_integration(tmp_path):
    import deepspeed_tpu as ds
    from tests.unit.simple_model import (init_simple_params, simple_loss_fn,
                                         random_batches)
    params = init_simple_params(jax.random.PRNGKey(0), hidden_dim=8)
    cfg = {
        "train_micro_batch_size_per_gpu": 4,
        "optimizer": {"type": "Adam", "params": {"lr": 1e-3}},
        "tensorboard": {"enabled": True,
                        "output_path": str(tmp_path),
                        "job_name": "unit_job"},
    }
    engine, *_ = ds.initialize(model=simple_loss_fn,
                               model_parameters=params, config=cfg)
    assert engine.monitor.enabled and engine.summary_writer is not None
    for b in random_batches(3, 4, 8):
        engine.train_batch(iter([b]))
    engine.monitor.close()
    files = glob.glob(str(tmp_path / "unit_job" / "*"))
    assert files, "engine wrote no tensorboard events"


def test_engine_unfused_path_writes(tmp_path):
    """forward/backward/step facade must also emit scalars (reference
    writes at step time, engine.py:922-936)."""
    import deepspeed_tpu as ds
    from tests.unit.simple_model import (init_simple_params, simple_loss_fn,
                                         random_batches)
    params = init_simple_params(jax.random.PRNGKey(0), hidden_dim=8)
    cfg = {
        "train_micro_batch_size_per_gpu": 4,
        "optimizer": {"type": "Adam", "params": {"lr": 1e-3}},
        "tensorboard": {"enabled": True, "output_path": str(tmp_path),
                        "job_name": "unfused"},
    }
    engine, *_ = ds.initialize(model=simple_loss_fn,
                               model_parameters=params, config=cfg)
    for b in random_batches(2, 4, 8):
        engine.forward(b)
        engine.backward()
        engine.step()
    engine.monitor.close()
    assert glob.glob(str(tmp_path / "unfused" / "*"))


def test_profiler_trace_window(tmp_path):
    """The configured jax.profiler window starts/stops around the given
    steps and leaves a trace on disk."""
    import os
    import deepspeed_tpu as ds
    from tests.unit.simple_model import (init_simple_params, simple_loss_fn,
                                         random_batches)
    out = str(tmp_path / "trace")
    params = init_simple_params(jax.random.PRNGKey(0), hidden_dim=8)
    engine, *_ = ds.initialize(
        model=simple_loss_fn, model_parameters=params,
        config={"train_micro_batch_size_per_gpu": 2,
                "optimizer": {"type": "Adam", "params": {"lr": 1e-3}},
                "profiler": {"enabled": True, "output_path": out,
                             "start_step": 1, "num_steps": 2}})
    batches = random_batches(5, 16, 8)
    for b in batches:
        engine.train_batch(iter([b]))
    assert not engine._profiler_active
    assert os.path.isdir(out) and any(os.scandir(out))


def test_step_time_scalar_written(tmp_path):
    import deepspeed_tpu as ds
    from tests.unit.simple_model import (init_simple_params, simple_loss_fn,
                                         random_batches)
    params = init_simple_params(jax.random.PRNGKey(0), hidden_dim=8)
    engine, *_ = ds.initialize(
        model=simple_loss_fn, model_parameters=params,
        config={"train_micro_batch_size_per_gpu": 2,
                "optimizer": {"type": "Adam", "params": {"lr": 1e-3}},
                "tensorboard": {"enabled": True,
                                "output_path": str(tmp_path)}})
    for b in random_batches(2, 16, 8):
        engine.train_batch(iter([b]))
    assert engine._last_step_time_ms is not None
    assert engine._last_step_time_ms > 0


@pytest.mark.parametrize("values", [
    [23.1] * 7,
    [0.0, 1e-4, 1e-3, 23.1, 23.1, 5.5, 23.1, 1e6],
    [float("nan"), 2.0, float("inf"), 2.0, float("-inf"), 3.0],
    [],
], ids=["one_value", "mixed", "not_finite", "none"])
def test_histogram_record_many_is_record_in_order(values):
    from deepspeed_tpu.utils.monitor import Histogram
    one, many = Histogram(), Histogram()
    for v in values:
        one.record(v)
    many.record_many(values)
    assert many._buckets == one._buckets
    assert (many.count, many.sum, many.min, many.max) == (
        one.count, one.sum, one.min, one.max)
