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


# ------------------------------------------------------ compile ledger
def _ledger():
    from deepspeed_tpu.profiling.spans import compile_ledger
    return compile_ledger()


def _rows_since(seq):
    return [r for r in _ledger().table()["programs"] if r["seq"] >= seq]


def test_a_plain_jit_outside_any_tracker_has_its_row_by_fun_name():
    """The ledger is process-wide: a ``jax.jit`` nobody wrapped makes a
    row by JAX's ``fun_name``, with the three durations, no wrap's name
    and no step, phase ``steady`` where no ``setup/*`` span is open."""
    ledger, x = _ledger(), jnp.ones((3,))
    seq = ledger.total

    def an_unwrapped_function(x):
        return jnp.where(x > 0, x, 0) * 2         # jitted jnp inside

    jax.jit(an_unwrapped_function)(x)
    rows = [r for r in _rows_since(seq)
            if r["fun_name"] == "jit(an_unwrapped_function)"]
    assert len(rows) == 1
    row = rows[0]
    assert row["name"] is None and row["step"] is None
    assert row["phase"] == "steady" and row["cls"] is None
    assert row["trace_s"] > 0 and row["lower_s"] > 0 and row["backend_s"] > 0
    assert row["call_s"] is None and row["changed"] is None
    # one interval on perf_counter that holds JAX's three spans
    assert row["t_end"] - row["t_begin"] >= \
        row["trace_s"] + row["lower_s"] + row["backend_s"] - 1e-6
    # the inner jits' traces were the outer's time, and made no row
    assert not [r for r in _rows_since(seq) if "where" in r["fun_name"]]


def test_a_rebuild_with_another_shape_is_a_steady_row_that_says_what_changed():
    import deepspeed_tpu.profiling.recompile as rc
    step = [0]
    tracker = rc.CompileTracker(step_provider=lambda: step[0], warn_after=1)
    f = tracker.wrap(jax.jit(lambda p, x: p["w"] * x), "scaled")
    seq = _ledger().total
    with rc.setup_span("setup/warmup"):
        with rc.setup_span("setup/program", cls=("prefill", 1, 4)):
            f({"w": jnp.ones((4,))}, np.ones((4,), np.float32))
    f({"w": jnp.ones((4,))}, np.ones((4,), np.float32))     # no build
    step[0] = 7
    f({"w": jnp.ones((4,))}, np.ones((2, 4), np.float32))   # steady build
    warm, steady = [r for r in _rows_since(seq) if r["name"] == "scaled"]
    assert warm["phase"] == "setup/program" and warm["changed"] is None
    assert warm["cls"] == ("prefill", 1, 4) and warm["step"] == 0
    assert steady["phase"] == "steady" and steady["step"] == 7
    assert steady["cls"] is None
    # the argument that changed, and only it
    assert steady["changed"] == [("args[1]", "float32[4]", "float32[2,4]")]
    # the tracked rows span the calls that built them
    assert steady["call_s"] == steady["t_end"] - steady["t_begin"] > 0
    assert steady["call_s"] >= (steady["trace_s"] + steady["lower_s"]
                                + steady["backend_s"])
    # and the tracker's own record is the same two builds
    assert tracker.counts == {"scaled": 2}
    assert [e.step for e in tracker.events] == [0, 7]
    assert tracker.events[1].wall_ms == pytest.approx(
        steady["call_s"] * 1e3)
    assert tracker.dispatch_counts == {"scaled": 3}


@pytest.fixture
def own_cache(tmp_path):
    """A persistent cache of this test's own that keeps every program,
    jax's once-initialized cache object dropped; restored after."""
    from jax.experimental.compilation_cache import compilation_cache
    keys = ("jax_compilation_cache_dir",
            "jax_persistent_cache_min_compile_time_secs",
            "jax_persistent_cache_min_entry_size_bytes",
            "jax_enable_compilation_cache")
    prev = {k: getattr(jax.config, k) for k in keys}
    jax.config.update(keys[0], str(tmp_path))
    jax.config.update(keys[1], 0.0)
    jax.config.update(keys[2], -1)
    compilation_cache.reset_cache()
    yield compilation_cache
    for k, v in prev.items():
        jax.config.update(k, v)
    compilation_cache.reset_cache()


def test_the_cache_column_reads_miss_then_hit_and_not_asked(own_cache):
    """The CPU backend of the pinned jax serves a persistent cache, so
    the events are JAX's own: a program built twice across
    ``jax.clear_caches()`` misses (and is written), then hits with what
    the load took and saved; with the cache off it is not asked (with
    no DIRECTORY set JAX still asks, and misses)."""
    def built(tag):
        def cached_or_not(x):
            return jnp.sin(x) * 41.25 + len(tag)
        seq = _ledger().total
        jax.jit(cached_or_not)(jnp.ones((5, 5)))
        return [r for r in _rows_since(seq)
                if r["fun_name"] == "jit(cached_or_not)"][-1]

    miss = built("aa")
    assert miss["cache"] == "miss" and miss["written"]
    assert miss["retrieval_s"] is None and miss["saved_s"] is None
    jax.clear_caches()
    hit = built("aa")
    assert hit["cache"] == "hit" and not hit["written"]
    assert hit["retrieval_s"] >= 0 and hit["saved_s"] is not None
    # a program under the threshold is asked for and never kept: it
    # misses in every process (utils/platform's min_compile_secs 1.0)
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 60.0)
    short = built("bbb")
    assert short["cache"] == "miss" and not short["written"]
    jax.config.update("jax_enable_compilation_cache", False)
    own_cache.reset_cache()
    off = built("cccc")
    assert off["cache"] == "not_asked" and not off["written"]


def test_a_trace_that_was_never_compiled_is_not_the_next_rows_time():
    """``lower()`` and ``eval_shape`` trace (and lower) without building:
    they leave no row, and the next program's row does not begin in
    them."""
    import time
    ledger, x, other = _ledger(), jnp.ones((6,)), jnp.ones((7,))
    seq = ledger.total
    f = jax.jit(lambda x: jnp.cos(x) * 3)
    f.lower(x)
    jax.eval_shape(f, other)
    assert ledger.total == seq
    mark = time.perf_counter()
    jax.jit(lambda x: jnp.cos(x) * 5)(x)
    (row,) = _rows_since(seq)
    assert row["t_begin"] >= mark


def test_the_ledger_is_a_ring_and_hand_fired_events_make_rows():
    """A ledger of its own, fed JAX's events by hand: a backend event a
    row, the overwritten ones counted, and a backend event INSIDE a
    trace (a program built while another is traced) a row with the
    backend's time alone."""
    import deepspeed_tpu.profiling.recompile as rc
    ledger = rc.CompileLedger(cap=2)
    for name in ("a", "b"):
        ledger._span_opens(rc.TRACE, 0.0, fun_name=name)
        ledger._span_closes(rc.TRACE, 0.25, fun_name=name)
        ledger._span_opens(rc.LOWER, 0.0, fun_name=name)
        ledger._span_closes(rc.LOWER, 0.5, fun_name=name)
        ledger._span_opens(rc.BACKEND, 0.0, fun_name=name)
        ledger._cache_says("/jax/compilation_cache/"
                           "compile_requests_use_cache")
        ledger._span_closes(rc.BACKEND, 1.0, fun_name=name)
    ledger._span_opens(rc.TRACE, 0.0, fun_name="outer")
    ledger._span_opens(rc.BACKEND, 0.0, fun_name="inner")
    ledger._span_closes(rc.BACKEND, 0.125, fun_name="inner")
    ledger._span_closes(rc.TRACE, 2.0, fun_name="outer")
    assert ledger.total == 3 and ledger.dropped == 1
    b, inner = ledger.table()["programs"]
    assert (b["fun_name"], b["seq"], b["cache"]) == ("b", 1, "miss")
    assert (b["trace_s"], b["lower_s"], b["backend_s"]) == (0.25, 0.5, 1.0)
    assert (inner["fun_name"], inner["seq"]) == ("inner", 2)
    assert (inner["trace_s"], inner["lower_s"], inner["backend_s"],
            inner["cache"]) == (0.0, 0.0, 0.125, "not_asked")


def test_a_program_that_begins_at_its_lowering_takes_no_older_trace():
    """pjit keeps a jaxpr: a program lowered again (other shardings,
    committed devices) begins at LOWER. A ``lower()`` before it, traced
    and lowered and never compiled, is not its trace nor its begin."""
    import time
    import deepspeed_tpu.profiling.recompile as rc
    ledger = rc.CompileLedger()
    ledger._span_opens(rc.TRACE, 0.0, fun_name="lowered_by_hand")
    ledger._span_closes(rc.TRACE, 0.25, fun_name="lowered_by_hand")
    ledger._span_opens(rc.LOWER, 0.0, fun_name="lowered_by_hand")
    ledger._span_closes(rc.LOWER, 0.5, fun_name="lowered_by_hand")
    mark = time.perf_counter()
    ledger._span_opens(rc.LOWER, 0.0, fun_name="again")
    ledger._span_closes(rc.LOWER, 0.0, fun_name="again")
    ledger._span_opens(rc.BACKEND, 0.0, fun_name="again")
    ledger._span_closes(rc.BACKEND, 0.0, fun_name="again")
    (row,) = ledger.table()["programs"]
    assert (row["fun_name"], row["trace_s"], row["lower_s"]) == (
        "again", 0.0, 0.0)
    assert row["t_begin"] >= mark
    # a trace and THEN this program's own lowering is one program still
    ledger._span_opens(rc.TRACE, 0.0, fun_name="whole")
    ledger._span_closes(rc.TRACE, 0.25, fun_name="whole")
    ledger._span_opens(rc.LOWER, 0.0, fun_name="whole")
    ledger._span_closes(rc.LOWER, 0.5, fun_name="whole")
    ledger._span_opens(rc.BACKEND, 0.0, fun_name="whole")
    ledger._span_closes(rc.BACKEND, 1.0, fun_name="whole")
    whole = ledger.table()["programs"][-1]
    assert (whole["trace_s"], whole["lower_s"], whole["backend_s"]) == (
        0.25, 0.5, 1.0)


def test_a_tracked_call_claims_its_own_rows_and_a_donated_argument_is_read():
    """The join needs no frame of the call: the rows a thread made since
    a tracked call began are the call's. An unwrapped build before it
    stays nobody's, and an argument the program DONATED (gone when the
    call returns) still says its shape and dtype."""
    import deepspeed_tpu.profiling.recompile as rc
    step = [3]
    tracker = rc.CompileTracker(step_provider=lambda: step[0])
    f = tracker.wrap(jax.jit(lambda s, x: s + x.sum(), donate_argnums=0),
                     "accumulate")
    x, small, gone = (jnp.asarray(np.ones(shape, np.float32))
                      for shape in ((2,), (4,), (4, 4)))
    seq = _ledger().total
    jax.jit(lambda x: x - 11)(x)                         # nobody's
    f(small, x)
    step[0] = 9
    f(gone, x)
    gone.delete()       # as the chip's donation leaves it, CPU or not
    assert rc._arg_signature((gone,), {}) == {"args[0]": "float32[4,4]"}
    nobody, first, again = _rows_since(seq)
    assert nobody["name"] is None and nobody["call_s"] is None
    assert (first["name"], first["step"], first["changed"]) == (
        "accumulate", 3, None)
    assert (again["name"], again["step"]) == ("accumulate", 9)
    assert again["changed"] == [("args[0]", "float32[4]", "float32[4,4]")]


def test_a_ledger_that_fails_loses_its_row_and_not_the_build(monkeypatch):
    """The listeners run inside JAX's compile, and a tracked call claims
    its rows inside an engine's step: what goes wrong in either is a
    warning (once) and a lost row, never a failed build or step."""
    import deepspeed_tpu.profiling.recompile as rc
    warnings = []
    monkeypatch.setattr(rc.logger, "warning",
                        lambda msg, *a, **k: warnings.append(str(msg)))
    monkeypatch.setattr(rc._LEDGER, "_warned", False)

    def broken(*args, **kwargs):
        raise RuntimeError("an argument no tree can hold")
    monkeypatch.setattr(rc, "_arg_signature", broken)    # the claim
    tracker = rc.CompileTracker()
    f = tracker.wrap(jax.jit(lambda x: x * 7), "sevenfold")
    seq = _ledger().total
    assert float(f(jnp.ones((3,)))[0]) == 7.0
    assert float(f(jnp.ones((2, 3)))[0, 0]) == 7.0
    assert tracker.counts == {"sevenfold": 2}
    assert not [r for r in _rows_since(seq) if r["name"] == "sevenfold"]
    monkeypatch.setattr(rc._LEDGER, "_built", broken)    # the listener
    seq = _ledger().total
    assert float(jax.jit(lambda x: x * 9)(jnp.ones((3,)))[0]) == 9.0
    assert _ledger().total == seq
    assert [w for w in warnings if "a row was lost" in w] == [
        "compile ledger: a row was lost"]


def test_a_tracked_function_does_not_pin_the_engine_that_owns_it():
    """An engine holds its tracked programs, and a program's jit holds
    the engine's bound method: a cycle the collector must see through.
    (jaxlib's bound ``_cache_size`` kept on the wrapper hid it, and
    every dropped engine kept its executables mapped: a test worker ran
    out of memory maps.)"""
    import gc
    import weakref
    from deepspeed_tpu.profiling.recompile import CompileTracker

    class Owner:
        def __init__(self):
            self.program = CompileTracker().wrap(jax.jit(self.double), "d")

        def double(self, x):
            return x * 2

    owner = Owner()
    assert float(owner.program(jnp.ones((3,)))[0]) == 2.0
    gone = weakref.ref(owner)
    del owner
    gc.collect()
    assert gone() is None


def test_setup_span_refuses_a_name_the_registry_does_not_hold():
    from deepspeed_tpu.profiling.recompile import setup_span
    with pytest.raises(ValueError, match="setup/teardown"):
        with setup_span("setup/teardown"):
            pass
    with pytest.raises(ValueError, match="serve/decode"):
        with setup_span("serve/decode"):          # registered, not set-up
            pass


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
