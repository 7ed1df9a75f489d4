"""Health plane (ISSUE 15): flight recorder, stall watchdog, numeric
anomaly detectors, and cross-run regression diffing.

Covers the acceptance bar: an injected ``health.stall`` in a real CPU
train step produces a ``stall_detected`` row naming the pinned phase
plus an atomic ``flight.json`` with the pre-stall ring and all-thread
stacks (and ``obs_report --health`` renders it); an injected NaN-loss
streak produces a ``health`` row with the pinned reason; the fully
enabled plane perturbs NOTHING (bitwise losses/params, identical
dispatch counts, zero steady-state recompiles); and ``--diff`` exits
nonzero naming the regressed metric on a deliberately slowed run while
two identical runs diff clean.
"""

import json
import os
import subprocess
import sys
import threading
import time

import numpy as np
import pytest

from deepspeed_tpu.runtime import fault
from deepspeed_tpu.utils.health import (HEALTH_PHASES, HEALTH_REASONS,
                                        STALL_EXIT_CODE, FlightRecorder,
                                        HealthPlane, NumericHealth,
                                        Watchdog)

REPO = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))


@pytest.fixture(autouse=True)
def _clean_faults():
    fault.reset()
    yield
    fault.reset()


def _load_obs_report():
    import importlib.util
    spec = importlib.util.spec_from_file_location(
        "obs_report", os.path.join(REPO, "tools", "obs_report.py"))
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def _events(path):
    rows = [json.loads(l) for l in open(path)]
    return rows


# ================================================================== #
# flight recorder units
# ================================================================== #


def test_flight_ring_is_bounded(tmp_path):
    rec = FlightRecorder(str(tmp_path / "flight.json"), ring_events=16)
    for i in range(100):
        rec.record({"tag": "x", "value": float(i), "step": i})
    assert len(rec.ring) == 16
    # oldest rows fell off; the LAST 16 survive
    assert [r["step"] for r in rec.ring] == list(range(84, 100))


def test_mirror_tap_is_transparent(tmp_path):
    """Install + remove the tap around a fake mirror: the inner writer
    sees the exact same calls, and untap restores the original object
    (the Observer's close-time identity check depends on it)."""

    class FakeMirror:
        def __init__(self):
            self.scalars, self.events, self.flushes = [], [], 0

        def add_scalar(self, tag, value, step):
            self.scalars.append((tag, value, step))

        def add_event(self, kind, **fields):
            self.events.append((kind, fields))

        def flush(self):
            self.flushes += 1

    class FakeMonitor:
        pass

    mon = FakeMonitor()
    inner = FakeMirror()
    mon.mirror = inner
    rec = FlightRecorder(str(tmp_path / "flight.json"), ring_events=8)
    rec.tap(mon)
    assert mon.mirror is not inner
    mon.mirror.add_scalar("Train/Samples/train_loss", 2.5, 32)
    mon.mirror.add_event("health", reason="nan_loss", step=32)
    mon.mirror.flush()
    # forwarded unchanged
    assert inner.scalars == [("Train/Samples/train_loss", 2.5, 32)]
    assert inner.events == [("health", {"reason": "nan_loss",
                                        "step": 32})]
    assert inner.flushes == 1
    # AND copied into the ring
    rows = list(rec.ring)
    assert rows[0]["tag"] == "Train/Samples/train_loss"
    assert rows[1]["event"] == "health"
    rec.untap()
    assert mon.mirror is inner


def test_flight_dump_atomic_roundtrip(tmp_path):
    path = str(tmp_path / "sub" / "flight.json")
    rec = FlightRecorder(path, ring_events=8)
    rec.record({"tag": "x", "value": 1.0, "step": 1})
    out = rec.dump("drain", extra={"reason": "test"}, stacks=True)
    assert out == path
    payload = json.load(open(path))
    assert payload["trigger"] == "drain"
    assert payload["reason"] == "test"
    assert payload["rows"] == [{"tag": "x", "value": 1.0, "step": 1}]
    assert payload["ring_events"] == 8
    # all-thread stacks name this (the main) thread
    assert any("MainThread" in k for k in payload["stacks"])
    # no torn tmp file left behind
    assert not os.path.exists(path + ".tmp")
    # best-effort: an unwritable path returns None instead of raising
    blocker = tmp_path / "blocker"
    blocker.write_text("not a directory")
    bad = FlightRecorder(str(blocker / "x" / "flight.json"))
    assert bad.dump("drain") is None


def test_excepthook_chains_and_dumps(tmp_path):
    path = str(tmp_path / "flight.json")
    rec = FlightRecorder(path, ring_events=8)
    rec.record({"tag": "x", "value": 1.0, "step": 1})
    seen = []
    prev_hook = sys.excepthook
    sys.excepthook = lambda t, e, tb: seen.append((t, str(e)))
    try:
        rec.install_excepthook()
        try:
            raise RuntimeError("boom at step 7")
        except RuntimeError:
            sys.excepthook(*sys.exc_info())
        payload = json.load(open(path))
        assert payload["trigger"] == "exception"
        assert payload["exception"]["type"] == "RuntimeError"
        assert "boom at step 7" in payload["exception"]["value"]
        assert payload["rows"]          # pre-crash ring rode along
        # the PREVIOUS hook still ran (chained, not replaced)
        assert seen == [(RuntimeError, "boom at step 7")]
        rec.uninstall_excepthook()
        assert sys.excepthook is not getattr(rec, "_hook", None)
    finally:
        sys.excepthook = prev_hook


# ================================================================== #
# watchdog units
# ================================================================== #


def test_watchdog_trips_in_warn_mode_and_rearms():
    trips = []
    wd = Watchdog(0.15, on_stall="warn",
                  on_trip=lambda **kw: trips.append(kw))
    wd.start()
    try:
        wd.beat("train_batch")
        deadline = time.monotonic() + 3.0
        while not trips and time.monotonic() < deadline:
            time.sleep(0.02)
        assert trips, "watchdog never tripped"
        t = trips[0]
        assert t["phase"] == "train_batch"
        assert t["silent_s"] >= 0.15
        assert any("MainThread" in k for k in t["stacks"])
        assert wd.trips >= 1
    finally:
        wd.stop()


def test_watchdog_trip_names_awaited_replica():
    """The router beats ``rpc_call`` with ``detail="replica N"``
    before every blocking wait — a trip during a hung RPC must carry
    that detail so the postmortem names WHICH replica was awaited."""
    trips = []
    wd = Watchdog(0.15, on_stall="warn",
                  on_trip=lambda **kw: trips.append(kw))
    wd.start()
    try:
        wd.beat("rpc_call", detail="replica 2")
        deadline = time.monotonic() + 3.0
        while not trips and time.monotonic() < deadline:
            time.sleep(0.02)
        assert trips, "watchdog never tripped"
        assert trips[0]["phase"] == "rpc_call"
        assert trips[0]["detail"] == "replica 2"
    finally:
        wd.stop()


def test_watchdog_heartbeats_prevent_trip():
    trips = []
    wd = Watchdog(0.25, on_stall="warn",
                  on_trip=lambda **kw: trips.append(kw))
    wd.start()
    try:
        t0 = time.monotonic()
        while time.monotonic() - t0 < 0.6:
            wd.beat("decode")
            time.sleep(0.03)
        assert trips == [] and wd.trips == 0
    finally:
        wd.stop()


def test_stall_exit_code_is_distinguishable():
    """87 must never collide with the elastic resumable code (85) or an
    uncaught SIGTERM (143) — supervisors dispatch on it."""
    from deepspeed_tpu.runtime.elastic import RESUMABLE_EXIT_CODE
    assert STALL_EXIT_CODE == 87
    assert STALL_EXIT_CODE not in (RESUMABLE_EXIT_CODE, 143, 0, 1, 2)


# ================================================================== #
# pinned vocabularies
# ================================================================== #


def test_heartbeat_phase_vocabulary_pinned(tmp_path):
    """The phase names ARE the stall-postmortem contract: renames break
    every consumer (obs_report, docs), so the set is
    pinned and unknown phases raise even on an ENABLED plane."""
    assert HEALTH_PHASES == (
        "train_batch", "prefill", "decode", "handoff_claim",
        "chunk_prefill", "checkpoint_commit", "fleet_step",
        "rpc_call")
    hp = HealthPlane({"enabled": True, "stall_timeout_s": 60.0},
                     events_dir=str(tmp_path))
    try:
        for phase in HEALTH_PHASES:
            hp.heartbeat(phase)           # every pinned phase accepted
        with pytest.raises(ValueError, match="unknown heartbeat phase"):
            hp.heartbeat("totally_new_phase")
    finally:
        hp.close()


def test_health_reason_vocabulary_pinned():
    assert HEALTH_REASONS == (
        "nan_loss", "loss_spike", "grad_norm_explosion",
        "loss_scale_collapse", "recompile_storm")
    det = NumericHealth({})
    with pytest.raises(AssertionError):
        det._alert("made_up_reason", 0)


# ================================================================== #
# numeric detectors (synthetic streams, pure host floats)
# ================================================================== #


def _collector():
    alerts = []
    return alerts, (lambda reason, step, detail:
                    alerts.append((reason, step, detail)))


def test_nonfinite_streak_alerts_once_per_episode():
    alerts, cb = _collector()
    det = NumericHealth({"nonfinite_streak": 3}, on_alert=cb)
    det.observe_loss(float("nan"), 1)
    det.observe_loss(float("nan"), 2)
    assert alerts == []                      # below the streak floor
    det.observe_loss(float("inf"), 3)        # inf counts as nonfinite
    assert [(r, s) for r, s, _ in alerts] == [("nan_loss", 3)]
    for step in range(4, 50):                # 46 MORE bad steps...
        det.observe_loss(float("nan"), step)
    assert len(alerts) == 1                  # ...one row, not 46
    det.observe_loss(2.0, 50)                # recovery resets the episode
    for step in range(51, 54):
        det.observe_loss(float("nan"), step)
    assert len(alerts) == 2                  # second episode = second row
    assert det.alerts_by_reason["nan_loss"] == 2


def test_loss_spike_zscore():
    alerts, cb = _collector()
    det = NumericHealth({"spike_zscore": 6.0, "spike_window": 32},
                        on_alert=cb)
    rng = np.random.RandomState(0)
    for step in range(20):                   # tight, healthy plateau
        det.observe_loss(2.0 + 0.01 * rng.randn(), step)
    assert alerts == []
    det.observe_loss(9.0, 20)                # z >> 6
    assert [(r, s) for r, s, _ in alerts] == [("loss_spike", 20)]
    assert alerts[0][2]["z"] > 6.0
    det.observe_loss(2.0, 21)                # back on the plateau: quiet
    det.observe_loss(2.0, 22)
    assert len(alerts) == 1


def test_grad_norm_and_scale_collapse_detectors():
    alerts, cb = _collector()
    det = NumericHealth({"grad_norm_max": 100.0,
                         "scale_collapse_below": 2.0}, on_alert=cb)
    det.observe_grad_norm(5.0, 1)
    det.observe_grad_norm(5000.0, 2)
    det.observe_grad_norm(7000.0, 3)         # still the same episode
    det.observe_loss_scale(65536.0, 3)
    det.observe_loss_scale(1.0, 4)           # ground into the floor
    assert [(r, s) for r, s, _ in alerts] == [
        ("grad_norm_explosion", 2), ("loss_scale_collapse", 4)]
    assert alerts[0][2]["ceiling"] == 100.0
    assert alerts[1][2]["loss_scale"] == 1.0
    # NaN grad norm is an explosion too
    det.observe_grad_norm(1.0, 5)            # episode reset
    det.observe_grad_norm(float("nan"), 6)
    assert alerts[-1][0] == "grad_norm_explosion"


def test_recompile_storm_from_cumulative_counter():
    alerts, cb = _collector()
    det = NumericHealth({"recompile_storm_count": 3,
                         "recompile_storm_window": 16}, on_alert=cb)
    det.observe_recompiles(1.0, 0)           # warmup baseline
    det.observe_recompiles(1.0, 10)          # steady state: no growth
    det.observe_recompiles(2.0, 20)          # one recompile — fine
    assert alerts == []
    det.observe_recompiles(3.0, 22)
    det.observe_recompiles(4.0, 24)          # 3 inside 16 steps: storm
    assert [(r, s) for r, s, _ in alerts] == [("recompile_storm", 24)]
    # marks outside the window age out — no second alert on quiet steps
    det.observe_recompiles(4.0, 100)
    assert len(alerts) == 1


# ================================================================== #
# config validation
# ================================================================== #


def test_health_config_defaults_and_validation():
    from deepspeed_tpu.runtime.config import (DeepSpeedConfig,
                                              DeepSpeedConfigError)
    cfg = DeepSpeedConfig({"train_micro_batch_size_per_gpu": 1},
                          world_size=1)
    hl = cfg.observability_config["health"]
    assert hl["enabled"] is False
    assert hl["ring_events"] == 256
    assert hl["stall_timeout_s"] == 0.0
    assert hl["on_stall"] == "warn"
    assert hl["detectors"]["nonfinite_streak"] == 3
    assert hl["detectors"]["spike_zscore"] == 6.0
    for bad in ({"on_stall": "panic"}, {"ring_events": 0},
                {"stall_timeout_s": -1},
                {"detectors": {"nonfinite_streak": 0}},
                {"detectors": {"spike_zscore": 0}}):
        with pytest.raises(DeepSpeedConfigError):
            DeepSpeedConfig({"train_micro_batch_size_per_gpu": 1,
                             "observability": {"health": bad}},
                            world_size=1)


def test_disabled_plane_is_inert(tmp_path):
    hp = HealthPlane({}, events_dir=str(tmp_path))
    assert not hp.enabled
    hp.heartbeat("train_batch")              # no watchdog: pure no-op
    with pytest.raises(ValueError):
        hp.heartbeat("nonsense")             # contract holds even off
    hp.observe_loss(float("nan"), 1)
    hp.observe_grad_norm(1e9, 1)
    assert hp.alerts_total == 0
    assert hp.dump("drain") is None
    hp.close()
    assert not list(tmp_path.iterdir())      # zero filesystem traffic


# ================================================================== #
# end-to-end: injected stall + NaN streak in a real CPU train loop
# ================================================================== #


def _train_engine(tmp_path, health):
    import jax
    import deepspeed_tpu as ds
    from tests.unit.simple_model import init_simple_params, simple_loss_fn
    params = init_simple_params(jax.random.PRNGKey(0), hidden_dim=8)
    engine, *_ = ds.initialize(
        model=simple_loss_fn, model_parameters=params,
        config={
            "train_micro_batch_size_per_gpu": 4,
            "steps_per_print": 1,
            "optimizer": {"type": "Adam", "params": {"lr": 1e-3}},
            "observability": {
                "enabled": True, "events_dir": str(tmp_path),
                "health": health},
        })
    return engine


def test_injected_stall_produces_postmortem(tmp_path):
    """The acceptance scenario: health.stall wedges a train step past
    its heartbeat; the watchdog (warn mode) trips mid-stall, dumps the
    black box, and emits a stall_detected row naming the pinned phase
    — and obs_report --health renders the whole postmortem."""
    from tests.unit.simple_model import random_batches
    engine = _train_engine(tmp_path, {
        "enabled": True, "stall_timeout_s": 0.25, "on_stall": "warn"})
    assert engine.health.enabled
    b0, b1 = random_batches(2, 4, 8)
    engine.train_batch(iter([b0]))           # healthy step feeds the ring
    fault.arm("health.stall", times=1,
              callback=lambda **ctx: time.sleep(1.0))
    engine.train_batch(iter([b1]))           # wedged past the beat
    assert engine.health.watchdog.trips >= 1

    rows = _events(tmp_path / "events.jsonl")
    stalls = [r for r in rows if r.get("event") == "stall_detected"]
    assert stalls, "no stall_detected row in events.jsonl"
    st = stalls[0]
    assert st["phase"] == "train_batch"      # the pinned phase name
    assert st["silent_s"] >= 0.25
    assert st["component"] == "train"

    # the black box: atomic flight.json with the pre-stall ring and
    # every thread's stack
    flight = st["flight"]
    assert flight and os.path.exists(flight)
    payload = json.load(open(flight))
    assert payload["trigger"] == "watchdog"
    assert payload["stall"]["phase"] == "train_batch"
    assert payload["rows"], "pre-stall telemetry missing from the ring"
    assert any("train_loss" in str(r.get("tag", ""))
               for r in payload["rows"])
    assert any("MainThread" in k for k in payload["stacks"])
    # the wedged main thread's stack shows WHERE it was stuck
    main_stack = "".join(v for k, s in payload["stacks"].items()
                         if "MainThread" in k for v in s)
    assert "time.sleep" in main_stack or "sleep" in main_stack

    # obs_report renders the postmortem from the same log
    obs_report = _load_obs_report()
    s = obs_report.summarize(str(tmp_path))
    assert s["health"]["stalls"] >= 1
    assert s["health"]["last_stall"]["phase"] == "train_batch"
    text = obs_report.render_health(s)
    assert "train_batch" in text and "flight" in text
    # the one-line pointer in the DEFAULT report too
    assert "--health" in obs_report.render(s)
    engine.close()


def test_injected_nan_streak_produces_health_row(tmp_path):
    """health.nan_loss poisons the TELEMETRY loss (values the engine
    already materialized host-side) for 5 steps: the streak detector
    fires one pinned-reason row plus the Health/alerts scalar."""
    from tests.unit.simple_model import random_batches
    engine = _train_engine(tmp_path, {"enabled": True})
    fault.arm("health.nan_loss", exc=fault.InjectedCrash("poison"),
              times=5)
    for b in random_batches(6, 4, 8):
        engine.train_batch(iter([b]))
    assert engine.health.alerts_total >= 1

    rows = _events(tmp_path / "events.jsonl")
    alerts = [r for r in rows if r.get("event") == "health"]
    assert len(alerts) == 1                  # once per episode
    assert alerts[0]["reason"] == "nan_loss"
    assert alerts[0]["component"] == "train"
    assert alerts[0]["streak"] == 3
    scalar = [r for r in rows if r.get("tag") == "Health/alerts"]
    assert scalar and scalar[-1]["value"] == 1.0

    obs_report = _load_obs_report()
    s = obs_report.summarize(str(tmp_path))
    assert s["health"]["alerts"] == 1
    assert s["health"]["by_reason"] == {"nan_loss": 1}
    assert "nan_loss" in obs_report.render_health(s)
    engine.close()


def test_preemption_drain_dumps_flight(tmp_path):
    """HealthPlane.dump on an explicit trigger: the flight_dump event
    row and the black box land together."""
    from tests.unit.simple_model import random_batches
    engine = _train_engine(tmp_path, {"enabled": True})
    engine.train_batch(iter([random_batches(1, 4, 8)[0]]))
    path = engine.health.dump("drain", reason="preempt-sim", step=1)
    assert path and os.path.exists(path)
    payload = json.load(open(path))
    assert payload["trigger"] == "drain"
    assert payload["reason"] == "preempt-sim"
    rows = _events(tmp_path / "events.jsonl")
    dumps = [r for r in rows if r.get("event") == "flight_dump"]
    assert dumps and dumps[0]["trigger"] == "drain"
    engine.close()


# ================================================================== #
# zero perturbation: the fully enabled plane changes NOTHING
# ================================================================== #


def test_health_plane_zero_perturbation(tmp_path):
    """Bitwise contract: health fully on (ring tap + armed watchdog +
    all detectors) vs off — identical per-step losses, identical final
    params, identical recompile counts. The plane reads what the engine
    already materialized; it must never add a device sync or change
    dispatch order."""
    import jax
    from tests.unit.simple_model import random_batches
    batches = random_batches(3, 4, 8)

    def run(health, sub):
        engine = _train_engine(tmp_path / sub, health)
        losses = [float(engine.train_batch(iter([b]))) for b in batches]
        params = jax.tree_util.tree_map(np.asarray, engine.state.params)
        recompiles = engine.observability.compile_tracker.total_compiles
        engine.close()
        return losses, params, recompiles

    l_off, p_off, rc_off = run({"enabled": False}, "off")
    l_on, p_on, rc_on = run(
        {"enabled": True, "stall_timeout_s": 60.0, "on_stall": "warn",
         "detectors": {"enabled": True}}, "on")
    assert l_on == l_off                     # bitwise, not approx
    flat_off, _ = jax.tree_util.tree_flatten(p_off)
    flat_on, _ = jax.tree_util.tree_flatten(p_on)
    for a, b in zip(flat_off, flat_on):
        np.testing.assert_array_equal(a, b)
    assert rc_on == rc_off
    # and the healthy run raised zero alerts
    events = _events(tmp_path / "on" / "events.jsonl")
    assert [r for r in events if r.get("event") == "health"] == []
    assert [r for r in events if r.get("event") == "stall_detected"] == []


def test_health_plane_adds_no_serving_dispatch(tmp_path):
    """The serving side of the same contract: ring tap + armed watchdog
    + detectors on an InferenceEngine change neither the warmup program
    set nor the number of dispatches a workload takes, nor a token."""
    import jax
    import jax.numpy as jnp
    from deepspeed_tpu.inference import InferenceEngine
    from deepspeed_tpu.models.gpt2 import GPT2Config, init_gpt2_params
    cfg = GPT2Config(vocab_size=61, max_position_embeddings=32,
                     hidden_size=32, num_layers=2, num_heads=4,
                     embd_dropout=0.0, attn_dropout=0.0,
                     resid_dropout=0.0)
    params = init_gpt2_params(cfg, jax.random.PRNGKey(3))
    prompts = [[1, 2, 3], [4, 5], [6, 7, 8, 9, 10], [11], [12, 13]]

    def run(on):
        eng = InferenceEngine(
            cfg, params,
            {"max_batch_size": 3, "prompt_buckets": [4, 8],
             "batch_buckets": [1, 2], "max_seq_len": 32,
             "max_new_tokens": 4,
             "events_dir": str(tmp_path / ("on" if on else "off"))},
            dtype=jnp.float32,
            observability_config={"health": {
                "enabled": on, "stall_timeout_s": 120.0,
                "on_stall": "warn"}})
        warm = eng.warmup()
        outs = eng.generate(prompts, max_new_tokens=4, temperature=0.0)
        stats = (warm, eng.compile_tracker.total_dispatches,
                 eng.steady_state_recompiles)
        alerts = eng.health.alerts_total
        eng.close()
        return outs, stats, alerts

    outs_off, stats_off, _ = run(False)
    outs_on, stats_on, alerts_on = run(True)
    assert stats_on == stats_off and stats_on[2] == 0
    assert outs_on == outs_off
    assert alerts_on == 0


# ================================================================== #
# cross-run regression diff (--diff RUN_A RUN_B)
# ================================================================== #


def _diff_log(tmp_path, name, step_ms, sps, recompiles=1, stalls=0):
    d = tmp_path / name
    d.mkdir()
    rows = []
    for i, ms in enumerate(step_ms):
        step = (i + 1) * 32
        rows.append({"tag": "Train/Samples/step_time_ms", "value": ms,
                     "step": step})
        rows.append({"tag": "Train/Samples/samples_per_sec",
                     "value": sps, "step": step})
        rows.append({"tag": "Observability/recompiles",
                     "value": float(recompiles), "step": step})
    for i in range(stalls):
        rows.append({"event": "stall_detected", "phase": "train_batch",
                     "silent_s": 1.0, "timeout_s": 0.5,
                     "component": "train", "flight": None})
    with open(d / "events.jsonl", "w") as f:
        for r in rows:
            f.write(json.dumps(r) + "\n")
    return str(d)


def test_diff_flags_regression_and_improvement(tmp_path):
    obs_report = _load_obs_report()
    a = _diff_log(tmp_path, "a", [100.0] * 8, 320.0)
    b = _diff_log(tmp_path, "b", [150.0] * 8, 210.0, recompiles=5,
                  stalls=1)
    d = obs_report.diff_runs(a, b)
    assert d["verdict"] == "REGRESSED"
    by = {m["metric"]: m for m in d["metrics"]}
    assert by["step_time_ms_p50"]["verdict"] == "REGRESSED"
    assert by["step_time_ms_p50"]["rel_change"] == pytest.approx(0.5)
    assert by["samples_per_sec_best"]["verdict"] == "REGRESSED"
    assert by["recompiles"]["verdict"] == "REGRESSED"
    assert by["stalls"]["verdict"] == "REGRESSED"
    assert set(d["regressed"]) >= {"step_time_ms_p50",
                                   "samples_per_sec_best",
                                   "recompiles", "stalls"}
    # absent-on-both metrics are N/A, never REGRESSED
    assert by["goodput_tokens_per_s"]["verdict"] == "N/A"
    # the reverse direction reads as IMPROVED
    rev = obs_report.diff_runs(b, a)
    assert rev["verdict"] == "OK"
    by_rev = {m["metric"]: m for m in rev["metrics"]}
    assert by_rev["step_time_ms_p50"]["verdict"] == "IMPROVED"
    # small noise inside the threshold: OK both ways
    c = _diff_log(tmp_path, "c", [104.0] * 8, 315.0)
    assert obs_report.diff_runs(a, c)["verdict"] == "OK"
    text = obs_report.render_diff(d)
    assert "verdict: REGRESSED" in text
    assert "step_time_ms_p50" in text


def test_diff_cli_exit_codes(tmp_path):
    """The regression gate: exit 1 naming the regressed metric, exit 0
    on identical runs, exit 2 on a missing log — scriptable in CI."""
    a = _diff_log(tmp_path, "a", [100.0] * 8, 320.0)
    b = _diff_log(tmp_path, "b", [150.0] * 8, 210.0)
    script = os.path.join(REPO, "tools", "obs_report.py")
    r = subprocess.run([sys.executable, script, "--diff", a, b],
                       capture_output=True, text=True, timeout=60)
    assert r.returncode == 1, (r.stdout, r.stderr)
    assert "REGRESSED" in r.stdout and "step_time_ms_p50" in r.stdout
    # identical runs: clean exit 0
    r0 = subprocess.run([sys.executable, script, "--diff", a, a],
                        capture_output=True, text=True, timeout=60)
    assert r0.returncode == 0 and "verdict: OK" in r0.stdout
    # JSON mode round-trips the same verdict
    rj = subprocess.run([sys.executable, script, "--diff", a, b,
                         "--json"],
                        capture_output=True, text=True, timeout=60)
    assert rj.returncode == 1
    dj = json.loads(rj.stdout)
    assert dj["verdict"] == "REGRESSED" and dj["schema"] == 3
    # missing log: explicit error, exit 2
    r2 = subprocess.run(
        [sys.executable, script, "--diff", a, str(tmp_path / "nope")],
        capture_output=True, text=True, timeout=60)
    assert r2.returncode == 2 and "error" in r2.stderr


def test_health_cli_smoke(tmp_path):
    a = _diff_log(tmp_path, "a", [100.0] * 4, 320.0, stalls=1)
    script = os.path.join(REPO, "tools", "obs_report.py")
    r = subprocess.run([sys.executable, script, a, "--health"],
                       capture_output=True, text=True, timeout=60)
    assert r.returncode == 0, r.stderr
    assert "health report:" in r.stdout
    assert "train_batch" in r.stdout
    # clean log renders the explicit no-events line, not an empty report
    c = _diff_log(tmp_path, "c", [100.0] * 4, 320.0)
    rc = subprocess.run([sys.executable, script, c, "--health"],
                        capture_output=True, text=True, timeout=60)
    assert "no health events" in rc.stdout


# ================================================================== #
# registry sync + schema
# ================================================================== #


def test_health_tag_registry_in_sync():
    """One tag, three homes: monitor (canonical), profiling registry
    (re-export), obs_report (mirrored string)."""
    from deepspeed_tpu import profiling as prof
    from deepspeed_tpu.utils import monitor as m
    obs_report = _load_obs_report()
    assert m.TAG_HEALTH_ALERTS == prof.TAG_HEALTH_ALERTS == \
        obs_report.T_HEALTH_ALERTS == "Health/alerts"


def test_obs_report_schema_v3_keeps_v2_keys(tmp_path):
    """Schema bump is ADDITIVE: every schema-2 consumer key survives
    unchanged next to the new health section."""
    obs_report = _load_obs_report()
    assert obs_report.SCHEMA_VERSION == 3
    a = _diff_log(tmp_path, "a", [100.0] * 4, 320.0)
    s = obs_report.summarize(a)
    assert s["schema"] == 3
    for key in ("steps", "step_time_ms", "samples_per_sec", "mfu",
                "flops_per_step", "comm", "recompiles", "memory",
                "checkpoints", "elastic", "loss", "host_overhead",
                "serving", "health"):
        assert key in s, key
    assert s["health"]["alerts"] == 0 and s["health"]["stalls"] == 0
