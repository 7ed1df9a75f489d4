"""Unified profiling & telemetry layer (deepspeed_tpu/profiling/).

Covers the ISSUE-3 acceptance bar: on CPU, a 3-step ``train_batch`` run
with ``observability.enabled`` produces a cost-analysis FLOPs/MFU
record, exactly the expected compile count (an injected shape change
bumps it by one), memory watermark scalars, and an ``obs_report``
summary with step-time percentiles, MFU, comm bytes, and recompile
count. Plus standalone-probe unit tests (flops registry, compile
tracker, memory snapshot, trace spans) and the run-report CLI smoke.
"""

import importlib.util
import json

import os
import subprocess
import sys

import jax
import jax.numpy as jnp
import numpy as np
import pytest

REPO = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))


def _load_obs_report():
    spec = importlib.util.spec_from_file_location(
        "obs_report", os.path.join(REPO, "tools", "obs_report.py"))
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def _events(path):
    rows = [json.loads(l) for l in open(path)]
    tags = {}
    for r in rows:
        if "tag" in r:
            tags.setdefault(r["tag"], []).append((r["step"], r["value"]))
    return rows, tags


# ------------------------------------------------------------ acceptance


def test_three_step_run_produces_full_observability_record(tmp_path):
    """The acceptance scenario, asserted end to end on the 8-device CPU
    mesh — tensorboard stays OFF so this also pins the event-log-only
    path (monitor mirror with no tensorboard writer)."""
    import deepspeed_tpu as ds
    from tests.unit.simple_model import (init_simple_params, simple_loss_fn,
                                         random_batches)
    params = init_simple_params(jax.random.PRNGKey(0), hidden_dim=8)
    engine, *_ = ds.initialize(
        model=simple_loss_fn, model_parameters=params,
        config={
            "train_micro_batch_size_per_gpu": 4,
            # per-step flush: this test reads the per-step records
            # mid-run; the async pipeline otherwise defers device-
            # valued scalars to steps_per_print boundaries
            "steps_per_print": 1,
            "optimizer": {"type": "Adam", "params": {"lr": 1e-3}},
            "observability": {
                "enabled": True, "events_dir": str(tmp_path),
                "chrome_trace_path": str(tmp_path / "trace.json")},
        })
    assert engine.observability.enabled
    for b in random_batches(3, 4, 8):
        engine.train_batch(iter([b]))

    rows, tags = _events(tmp_path / "events.jsonl")

    # (1) cost-analysis FLOPs/MFU record
    assert tags["Observability/flops_per_step"][0][1] > 0
    assert tags["Observability/bytes_accessed"][0][1] > 0
    mfus = [v for _, v in tags["Observability/mfu"]]
    assert len(mfus) == 3 and all(v > 0 for v in mfus)
    profs = [r for r in rows if r.get("event") == "flops_profile"]
    assert len(profs) == 1 and profs[0]["fn"] == "micro_step"
    assert profs[0]["num_devices"] == 8

    # (2) exactly the expected compile count: ONE micro_step compile
    # across all three same-shape steps
    assert tags["Observability/recompiles"][-1][1] == 1.0
    compiles = [r for r in rows if r.get("event") == "compile"]
    assert len(compiles) == 1 and compiles[0]["fn"] == "micro_step"
    assert compiles[0]["wall_ms"] > 0

    # ... and an injected shape change bumps it by exactly one
    bigger = random_batches(1, 8, 8)[0]
    engine.train_batch(iter([bigger]))
    rows, tags = _events(tmp_path / "events.jsonl")
    assert tags["Observability/recompiles"][-1][1] == 2.0

    # (3) memory watermark scalars, one per step, monotone peak
    peaks = [v for _, v in tags["Memory/peak_bytes_in_use"]]
    assert len(peaks) == 4 and all(v > 0 for v in peaks)
    assert peaks == sorted(peaks)
    assert len(tags["Memory/bytes_in_use"]) == 4
    assert len(tags["Memory/step_delta_bytes"]) == 4

    # per-step training scalars ride along without tensorboard
    assert len(tags["Train/Samples/step_time_ms"]) == 4
    assert all(v > 0 for _, v in tags["Train/Samples/samples_per_sec"])
    assert all(v > 0 for _, v in tags["Train/Samples/comm_bytes_per_step"])

    # chrome trace: spans on disk mid-run, no close() needed
    trace = json.load(open(tmp_path / "trace.json"))
    names = {e["name"] for e in trace["traceEvents"]}
    assert "train_batch" in names

    # (4) obs_report renders the summary from the same log
    obs_report = _load_obs_report()
    s = obs_report.summarize(str(tmp_path))
    assert s["steps"] == 4
    assert s["step_time_ms"]["p50"] > 0
    assert s["step_time_ms"]["p95"] >= s["step_time_ms"]["p50"]
    assert s["samples_per_sec"]["last"] > 0
    assert s["mfu"]["best"] > 0
    assert s["flops_per_step"] > 0
    assert s["comm"]["bytes_per_step"] > 0
    assert s["recompiles"]["count"] == 2
    assert s["recompiles"]["per_fn"]["micro_step"]["count"] == 2
    assert s["memory"]["peak_bytes_in_use"] > 0
    text = obs_report.render(s)
    for needle in ("step_time_ms", "mfu", "recompiles", "memory",
                   "samples_per_sec"):
        assert needle in text

    engine.observability.close()
    # close() is idempotent and seals a compile summary event
    engine.observability.close()
    rows, _ = _events(tmp_path / "events.jsonl")
    summaries = [r for r in rows if r.get("event") == "compile_summary"]
    assert len(summaries) == 1 and summaries[0]["total_compiles"] == 2


def test_observability_disabled_is_transparent(tmp_path):
    """Default-off: raw jit functions (the HLO audits call .lower() on
    them), no event files, no monitor coupling."""
    import deepspeed_tpu as ds
    from tests.unit.simple_model import (init_simple_params, simple_loss_fn,
                                         random_batches)
    params = init_simple_params(jax.random.PRNGKey(0), hidden_dim=8)
    engine, *_ = ds.initialize(
        model=simple_loss_fn, model_parameters=params,
        config={"train_micro_batch_size_per_gpu": 4,
                "optimizer": {"type": "Adam", "params": {"lr": 1e-3}}})
    assert not engine.observability.enabled
    step = engine._get_compiled_micro_step()
    from deepspeed_tpu.profiling import TrackedFunction
    assert not isinstance(step, TrackedFunction)
    assert hasattr(step, "lower")
    for b in random_batches(2, 4, 8):
        engine.train_batch(iter([b]))
    assert not os.path.exists(tmp_path / "events.jsonl")


def test_legacy_profiler_section_aliases_into_observability():
    from deepspeed_tpu.runtime.config import DeepSpeedConfig
    cfg = DeepSpeedConfig({
        "train_micro_batch_size_per_gpu": 1,
        "profiler": {"enabled": True, "output_path": "/tmp/x",
                     "start_step": 5},
    }, world_size=1)
    tr = cfg.observability_config["trace"]
    assert tr["enabled"] and tr["output_path"] == "/tmp/x"
    assert tr["start_step"] == 5 and tr["num_steps"] == 3
    # legacy attribute still points at the same dict
    assert cfg.profiler_config is tr
    # explicit observability.trace keys win over the legacy block
    cfg2 = DeepSpeedConfig({
        "train_micro_batch_size_per_gpu": 1,
        "profiler": {"enabled": True, "start_step": 5},
        "observability": {"trace": {"start_step": 9}},
    }, world_size=1)
    assert cfg2.observability_config["trace"]["start_step"] == 9
    assert cfg2.observability_config["trace"]["enabled"] is True


def test_observability_config_validation():
    from deepspeed_tpu.runtime.config import (DeepSpeedConfig,
                                              DeepSpeedConfigError)
    with pytest.raises(DeepSpeedConfigError):
        DeepSpeedConfig({"train_micro_batch_size_per_gpu": 1,
                         "observability": {"recompile_warn_after": -1}},
                        world_size=1)
    with pytest.raises(DeepSpeedConfigError):
        DeepSpeedConfig({"train_micro_batch_size_per_gpu": 1,
                         "observability": {"enabled": True,
                                           "events_dir": 7}},
                        world_size=1)


# ------------------------------------------------------------ probes


def test_flops_profiler_counts_matmul_flops():
    """cost_analysis of a pure matmul ≈ 2*m*k*n FLOPs — pins that the
    normalization reads the right keys."""
    from deepspeed_tpu.profiling.flops import profile_jit_fn
    m = k = n = 128
    f = jax.jit(lambda a, b: a @ b)
    a = jnp.ones((m, k), jnp.float32)
    b = jnp.ones((k, n), jnp.float32)
    prof = profile_jit_fn(f, (a, b), name="matmul")
    assert prof.flops == pytest.approx(2 * m * k * n, rel=0.01)
    assert prof.bytes_accessed >= 3 * m * n * 4
    assert prof.compile_ms > 0
    assert prof.arithmetic_intensity > 0


def test_peak_flops_registry():
    from deepspeed_tpu.profiling.flops import (CPU_NOMINAL_PEAK_FLOPS,
                                               peak_flops_per_device)

    class FakeDev:
        def __init__(self, kind, platform="tpu"):
            self.device_kind = kind
            self.platform = platform

    assert peak_flops_per_device(FakeDev("TPU v4"))[0] == 275e12
    assert peak_flops_per_device(FakeDev("TPU v5 lite"))[0] == 197e12
    assert peak_flops_per_device(FakeDev("TPU v5p"))[0] == 459e12
    peak, label = peak_flops_per_device(FakeDev("cpu", platform="cpu"))
    assert peak == CPU_NOMINAL_PEAK_FLOPS
    assert "nominal-peak" in label  # the CPU can't fake real MFU
    # an accelerator that is not in the table is an error, not 1e11
    with pytest.raises(ValueError, match="TPU v9"):
        peak_flops_per_device(FakeDev("TPU v9"))


def test_compute_mfu():
    from deepspeed_tpu.profiling.flops import compute_mfu
    assert compute_mfu(1e12, 1.0, 2e12) == pytest.approx(0.5)
    assert compute_mfu(1e12, 0.0, 2e12) == 0.0
    assert compute_mfu(1e12, 1.0, 0.0) == 0.0


def test_compile_tracker_counts_and_warns(monkeypatch):
    import deepspeed_tpu.profiling.recompile as rc
    warnings = []
    monkeypatch.setattr(rc.logger, "warning",
                        lambda msg, *a, **k: warnings.append(str(msg)))
    step = [0]
    tracker = rc.CompileTracker(step_provider=lambda: step[0], warn_after=1)
    f = tracker.wrap(jax.jit(lambda x: x * 2), "f")
    f(jnp.ones((4,)))
    f(jnp.ones((4,)))                     # cache hit: no new compile
    assert tracker.counts == {"f": 1}
    step[0] = 5
    f(jnp.ones((8,)))                     # steady-state recompile
    assert tracker.counts == {"f": 2}
    assert tracker.total_compiles == 2
    assert any("steady-state recompile" in w for w in warnings)
    assert tracker.total_compile_ms > 0
    assert [e.count for e in tracker.events] == [1, 2]
    assert tracker.events[1].step == 5
    s = tracker.summary()
    assert s["total_compiles"] == 2 and s["per_fn"]["f"]["count"] == 2


def test_compile_tracker_signature_fallback():
    """Without _cache_size (non-jit callables, exotic jax builds) the
    shape/dtype-signature detector still counts compiles exactly."""
    from deepspeed_tpu.profiling.recompile import CompileTracker
    tracker = CompileTracker()
    calls = []
    f = tracker.wrap(lambda x: calls.append(x.shape) or x, "g")
    f._has_cache_size = False
    x4, x8 = np.ones((4,)), np.ones((8,))
    f(x4); f(x4); f(x8); f(x4)
    assert tracker.counts == {"g": 2}


def test_tracked_function_passes_lower_through():
    from deepspeed_tpu.profiling.recompile import CompileTracker
    f = CompileTracker().wrap(jax.jit(lambda x: x + 1), "h")
    txt = f.lower(jnp.ones((4,))).compile().as_text()
    assert "HloModule" in txt or "ENTRY" in txt


def test_memory_snapshot_cpu_host_fallback():
    from deepspeed_tpu.profiling.memory import MemoryWatermark, memory_snapshot
    snap = memory_snapshot()
    assert snap is not None and snap["source"] in ("device", "host")
    assert snap["bytes_in_use"] > 0 and snap["peak_bytes_in_use"] > 0
    wm = MemoryWatermark()
    s1 = wm.sample("forward")
    s2 = wm.sample("step")
    assert s1["delta_bytes"] == 0 and isinstance(s2["delta_bytes"], int)
    assert wm.peak_bytes >= max(s1["bytes_in_use"], s2["bytes_in_use"])
    assert wm.samples_by_phase["forward"] is s1


def test_trace_span_records_chrome_events(tmp_path):
    import time
    from deepspeed_tpu.profiling.spans import (ChromeTraceRecorder,
                                               trace_span)
    rec = ChromeTraceRecorder()
    with trace_span("forward", recorder=rec):
        time.sleep(0.002)
    with trace_span("backward", recorder=rec, micro=3):
        pass
    assert [e["name"] for e in rec.events] == ["forward", "backward"]
    assert rec.events[0]["ph"] == "X"
    assert rec.events[0]["dur"] >= 1000          # µs
    assert rec.events[1]["args"] == {"micro": 3}
    out = rec.dump(str(tmp_path / "t" / "trace.json"))
    data = json.load(open(out))
    assert len(data["traceEvents"]) == 2


def test_trace_span_default_recorder_roundtrip():
    from deepspeed_tpu.profiling.spans import (ChromeTraceRecorder,
                                               get_default_recorder,
                                               set_default_recorder,
                                               trace_span)
    rec = ChromeTraceRecorder()
    set_default_recorder(rec)
    try:
        with trace_span("x"):
            pass
        assert get_default_recorder() is rec
        assert rec.events and rec.events[0]["name"] == "x"
    finally:
        set_default_recorder(None)
    with trace_span("y"):                        # no recorder: still fine
        pass
    assert len(rec.events) == 1


# ------------------------------------------------------- run-report CLI


def _synthetic_log(tmp_path):
    """events.jsonl with every record family the report consumes."""
    rows = []
    for i, ms in enumerate([120.0, 100.0, 105.0, 98.0, 300.0]):
        step = (i + 1) * 32
        rows += [
            {"tag": "Train/Samples/step_time_ms", "value": ms, "step": step},
            {"tag": "Train/Samples/samples_per_sec",
             "value": 32 / (ms / 1e3), "step": step},
            {"tag": "Train/Samples/train_loss", "value": 5.0 - i,
             "step": step},
            {"tag": "Observability/mfu", "value": 0.30 + 0.01 * i,
             "step": step},
            {"tag": "Observability/recompiles", "value": 1.0, "step": step},
            {"tag": "Memory/peak_bytes_in_use", "value": 1e9 + i,
             "step": step},
            {"tag": "Memory/bytes_in_use", "value": 9e8, "step": step},
            {"tag": "Train/Samples/comm_bytes_per_step", "value": 123456.0,
             "step": step},
            {"tag": "Train/Samples/comm_compression_ratio", "value": 3.4,
             "step": step},
        ]
    rows.append({"tag": "Observability/flops_per_step", "value": 2.5e12,
                 "step": 32})
    rows.append({"tag": "Train/Samples/checkpoint_save_ms", "value": 42.0,
                 "step": 160})
    rows.append({"tag": "Train/Samples/checkpoint_save_ok", "value": 1.0,
                 "step": 160})
    rows.append({"event": "compile", "fn": "micro_step", "count": 1,
                 "wall_ms": 1234.5, "step": 0})
    path = tmp_path / "events.jsonl"
    with open(path, "w") as f:
        for r in rows:
            f.write(json.dumps(r) + "\n")
        f.write("{torn line, never parsed\n")   # crash-torn tail tolerated
    return path


def test_obs_report_summarize_fields(tmp_path):
    _synthetic_log(tmp_path)
    obs_report = _load_obs_report()
    s = obs_report.summarize(str(tmp_path))     # dir resolution
    assert s["steps"] == 5
    assert s["step_time_ms"]["p50"] == pytest.approx(105.0)
    assert s["step_time_ms"]["p95"] == pytest.approx(264.0)
    assert s["samples_per_sec"]["best"] == pytest.approx(32 / 0.098, rel=1e-3)
    assert s["mfu"]["last"] == pytest.approx(0.34)
    assert s["flops_per_step"] == pytest.approx(2.5e12)
    assert s["comm"]["bytes_per_step"] == pytest.approx(123456.0)
    assert s["comm"]["compression_ratio"] == pytest.approx(3.4)
    assert s["recompiles"]["count"] == 1
    assert s["recompiles"]["per_fn"]["micro_step"]["wall_ms"] == \
        pytest.approx(1234.5)
    assert s["memory"]["peak_bytes_in_use"] == pytest.approx(1e9 + 4)
    assert s["checkpoints"]["saves"] == 1
    assert s["checkpoints"]["save_ms_mean"] == pytest.approx(42.0)
    assert s["loss"]["first"] == 5.0 and s["loss"]["last"] == 1.0


def test_obs_report_cli_smoke(tmp_path):
    """Tier-1 CI smoke: the CLI subprocess renders the summary (and the
    --json mode round-trips) against a synthetic log — stdlib only, no
    jax init in the child."""
    _synthetic_log(tmp_path)
    script = os.path.join(REPO, "tools", "obs_report.py")
    r = subprocess.run([sys.executable, script, str(tmp_path)],
                       capture_output=True, text=True, timeout=60)
    assert r.returncode == 0, r.stderr
    for needle in ("run report:", "step_time_ms", "p50=105.00",
                   "p95=264.00", "mfu", "recompiles        : 1",
                   "samples_per_sec"):
        assert needle in r.stdout, (needle, r.stdout)
    rj = subprocess.run([sys.executable, script, str(tmp_path), "--json"],
                        capture_output=True, text=True, timeout=60)
    assert rj.returncode == 0
    s = json.loads(rj.stdout)
    assert s["steps"] == 5 and s["recompiles"]["count"] == 1
    # missing log: explicit error, exit 2
    rerr = subprocess.run([sys.executable, script, str(tmp_path / "nope")],
                          capture_output=True, text=True, timeout=60)
    assert rerr.returncode == 2 and "error" in rerr.stderr


def test_cost_model_flops_track_the_analytic_count():
    """Cost-analysis FLOPs per token of the compiled GPT-2 micro-step
    (fwd + bwd + Adam, ZeRO-2 over the 8-device CPU mesh) against the
    PaLM-appendix 6N + 12LSH: a silent change in what the compiled
    program computes (lost fusion, duplicated backward, an optimizer
    graph regression) moves a checked number."""
    import deepspeed_tpu
    from jax.sharding import NamedSharding, PartitionSpec
    from deepspeed_tpu.models.gpt2 import (
        GPT2Config, count_params, gpt2_loss_fn, init_gpt2_params)
    from deepspeed_tpu.profiling.flops import profile_jit_fn

    cfg = GPT2Config(vocab_size=512, max_position_embeddings=128,
                     hidden_size=64, num_layers=2, num_heads=2)
    batch, seq = 8, 64
    n_dev = jax.device_count()
    params = init_gpt2_params(cfg, jax.random.PRNGKey(0))
    engine, *_ = deepspeed_tpu.initialize(
        model=gpt2_loss_fn(cfg, dtype=jnp.bfloat16, deterministic=True),
        model_parameters=params,
        config={"train_micro_batch_size_per_gpu": batch // n_dev,
                "gradient_accumulation_steps": 1,
                "bf16": {"enabled": True},
                "steps_per_print": 10**9,
                "zero_optimization": {"stage": 2},
                "optimizer": {"type": "Adam", "params": {"lr": 1e-4}}})
    ids = np.random.RandomState(0).randint(
        0, cfg.vocab_size, (batch, seq + 1)).astype(np.int32)
    b = {"input_ids": jax.device_put(
        ids, NamedSharding(engine.mesh, PartitionSpec("data")))}
    prof = profile_jit_fn(engine._get_compiled_micro_step(),
                          (engine.state, b), name="gpt2_micro_step")
    # cost_analysis FLOPs are per device for the partitioned program
    flops_per_token = prof.flops / (batch * seq / n_dev)
    analytic = 6 * count_params(params) \
        + 12 * cfg.num_layers * seq * cfg.hidden_size
    # same order of magnitude: the compiled program includes the
    # optimizer and the loss, the analytic count does not
    assert 0.2 < flops_per_token / analytic < 5.0
    engine.close()
