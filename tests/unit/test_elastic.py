"""Elastic-resilience tests (ISSUE 10): async snapshot checkpointing,
graceful preemption drain, the launcher supervisor, and — the pinned
tentpole contract — kill-the-save-at-every-commit-stage on a dp=2 CPU
mesh, then resume on dp=1 AND dp=4 meshes with loss/params matching the
uninterrupted run.

The contract is pinned in two exact halves:

- the RESTORE point: params loaded after a torn save are BITWISE equal
  to the reference run's params at the newest committed step, on every
  resume mesh (resharding is pure data movement);
- the CONTINUATION: training on from the torn-save resume is bitwise
  identical to training on from an uninterrupted checkpoint of the same
  step on the same mesh (same restored bytes + same program + same data
  -> f32-ulp/bitwise equality, with no cross-mesh reduction-order
  excuse available).
"""

import json
import os
import signal
import subprocess
import sys
import textwrap
import time

import jax
import numpy as np
import pytest

import deepspeed_tpu
from deepspeed_tpu.launcher import runner
from deepspeed_tpu.runtime import checkpoint as ckpt
from deepspeed_tpu.runtime import elastic, fault
from deepspeed_tpu.utils import health
from tests.unit.simple_model import (
    base_config, init_simple_params, random_batches, simple_loss_fn)

pytestmark = pytest.mark.faulty

HIDDEN = 16
SEED_A, SEED_B, SEED_C = 2, 3, 5     # steps 1-2 / 3-4 / continuation


@pytest.fixture(autouse=True)
def _reset_injector():
    fault.reset()
    yield
    fault.reset()


def make_engine(config=None, seed=0):
    params = init_simple_params(jax.random.PRNGKey(seed), HIDDEN)
    engine, *_ = deepspeed_tpu.initialize(
        model=simple_loss_fn, model_parameters=params,
        config=config or base_config())
    return engine


def dp_config(dp, **overrides):
    """Same GLOBAL batch (8) on any mesh, so dp=1/2/4 runs consume an
    identical data stream and the math is mesh-shape-independent."""
    cfg = {
        "train_batch_size": 8,
        "gradient_accumulation_steps": 1,
        "steps_per_print": 10**9,
        "optimizer": {"type": "Adam", "params": {"lr": 1e-2}},
        "zero_optimization": {"stage": 2},
        "mesh": {"axes": {"data": dp}},
    }
    cfg.update(overrides)
    return cfg


def run_steps(engine, n, seed):
    batches = iter(random_batches(n, 8, HIDDEN, seed=seed))
    return [float(engine.train_batch(batches)) for _ in range(n)]


def host_params(engine):
    from deepspeed_tpu.runtime.checkpoint import _to_host_global
    return [np.asarray(_to_host_global(x))
            for x in jax.tree_util.tree_leaves(engine.state.params)]


# ===================================================================== #
# tentpole: the pinned elastic contract
# ===================================================================== #

@pytest.fixture(scope="module")
def reference(tmp_path_factory):
    """The uninterrupted dp=2 run: clean committed checkpoints at steps
    2 and 4, host copies of the params at both."""
    d = str(tmp_path_factory.mktemp("elastic_ref"))
    e = make_engine(dp_config(2), seed=1)
    run_steps(e, 2, SEED_A)
    e.save_checkpoint(d)
    p2 = host_params(e)
    run_steps(e, 2, SEED_B)
    e.save_checkpoint(d)
    p4 = host_params(e)
    e.close()
    return {"dir": d, "params": {2: p2, 4: p4}}


@pytest.fixture(scope="module")
def clean_resume(reference):
    """Lazy cache of uninterrupted-resume trajectories: fresh dp=N
    engine loads the CLEAN checkpoint of `step` and trains 2 more steps
    — the ground truth every torn-save resume must match bitwise."""
    cache = {}

    def get(dp, step):
        if (dp, step) not in cache:
            e = make_engine(dp_config(dp), seed=7)
            path, _ = e.load_checkpoint(reference["dir"],
                                        tag=f"global_step{step}")
            assert path is not None
            losses = run_steps(e, 2, SEED_C)
            cache[(dp, step)] = {"losses": losses,
                                 "params": host_params(e)}
            e.close()
        return cache[(dp, step)]

    return get


# (fault point, arm kwargs, step the fallback must resume at). Every
# stage of the commit protocol dies once; only latest_tmp_written leaves
# step 4 committed (the save "finished", the pointer didn't).
CONTRACT_STAGES = [
    ("ckpt.snapshot", {}, 2),
    ("ckpt.after_shard",
     {"filter": lambda **c: c.get("name") == "model_states"}, 2),
    ("ckpt.before_marker", {}, 2),
    ("ckpt.before_rename", {}, 2),
    ("ckpt.latest_tmp_written", {}, 4),
]


@pytest.mark.parametrize("point,arm_kw,resume_step", CONTRACT_STAGES,
                         ids=[s[0] for s in CONTRACT_STAGES])
def test_kill_at_stage_resumes_on_any_mesh(tmp_path, reference,
                                           clean_resume, point, arm_kw,
                                           resume_step):
    # the to-be-killed dp=2 run retraces the reference data trajectory
    e = make_engine(dp_config(2), seed=1)
    run_steps(e, 2, SEED_A)
    e.save_checkpoint(str(tmp_path))          # committed baseline
    run_steps(e, 2, SEED_B)
    fault.arm(point, exc=fault.InjectedCrash(point), **arm_kw)
    with pytest.raises(fault.InjectedCrash):
        e.save_checkpoint(str(tmp_path))
    fault.reset()
    e.close()

    for dp in (1, 4):
        r = make_engine(dp_config(dp), seed=9)
        path, _ = r.load_checkpoint(str(tmp_path))
        assert path is not None, \
            f"{point}: fallback found nothing on dp={dp}"
        assert r.global_steps == resume_step, \
            f"{point}: resumed step {r.global_steps} != {resume_step}"
        # restore point: bitwise equal to the uninterrupted run's
        # params at that step, regardless of the resume mesh
        for a, b in zip(host_params(r),
                        reference["params"][resume_step]):
            np.testing.assert_array_equal(a, b)
        # continuation: bitwise identical to resuming an uninterrupted
        # checkpoint of the same step on the same mesh
        losses = run_steps(r, 2, SEED_C)
        want = clean_resume(dp, resume_step)
        np.testing.assert_allclose(losses, want["losses"],
                                   rtol=0, atol=0)
        for a, b in zip(host_params(r), want["params"]):
            np.testing.assert_array_equal(a, b)
        r.close()


def test_snapshot_kill_leaves_no_staging(tmp_path):
    """A save killed at the snapshot stage dies before ANY filesystem
    effect — not even a staging dir."""
    e = make_engine(seed=1)
    run_steps_simple(e, 1)
    fault.arm("ckpt.snapshot", exc=fault.InjectedCrash("snapshot"))
    with pytest.raises(fault.InjectedCrash):
        e.save_checkpoint(str(tmp_path))
    fault.reset()
    assert os.listdir(str(tmp_path)) == []
    e.close()


def run_steps_simple(engine, n, seed=0):
    batches = iter(random_batches(
        n * engine.gradient_accumulation_steps, 16, HIDDEN, seed=seed))
    return [float(engine.train_batch(batches)) for _ in range(n)]


# ===================================================================== #
# async snapshot checkpointing
# ===================================================================== #

class TestAsyncSave:
    def test_roundtrip_and_commit(self, tmp_path):
        e = make_engine(seed=1)
        run_steps_simple(e, 3, seed=2)
        want = host_params(e)
        d = e.save_checkpoint(str(tmp_path), async_=True)
        e.wait_pending_saves()
        ok, problems = ckpt.verify_checkpoint_dir(d)
        assert ok, problems
        assert ckpt.read_latest(str(tmp_path)) == "global_step3"
        e2 = make_engine(seed=9)
        path, _ = e2.load_checkpoint(str(tmp_path))
        assert path == d and e2.global_steps == 3
        for a, b in zip(host_params(e2), want):
            np.testing.assert_array_equal(a, b)
        e.close()
        e2.close()

    def test_config_default_async(self, tmp_path):
        """checkpoint.async_save makes plain save_checkpoint async."""
        e = make_engine(base_config(checkpoint={"async_save": True}),
                        seed=1)
        run_steps_simple(e, 1)
        fault.arm("ckpt.writer_crash", times=None,
                  callback=lambda **k: time.sleep(0.05))
        e.save_checkpoint(str(tmp_path))
        assert e._ckpt_writer is not None and \
            e._ckpt_writer.pending_saves() >= 1
        e.wait_pending_saves()
        assert ckpt.is_committed(str(tmp_path / "global_step1"))
        e.close()

    def test_snapshot_is_donation_safe(self, tmp_path):
        """The step loop keeps training (donating its state buffers)
        while the writer commits — the checkpoint must hold the
        snapshot-time values, not torn/freed memory."""
        e = make_engine(base_config(gradient_accumulation_steps=2),
                        seed=1)
        run_steps_simple(e, 2, seed=2)
        want_step = e.global_steps
        want = host_params(e)
        # slow the writer so training overlaps the write
        fault.arm("ckpt.writer_crash", times=None,
                  callback=lambda **k: time.sleep(0.1))
        e.save_checkpoint(str(tmp_path), async_=True)
        run_steps_simple(e, 3, seed=4)     # donates state repeatedly
        e.wait_pending_saves()
        fault.reset()
        e2 = make_engine(seed=9)
        path, _ = e2.load_checkpoint(str(tmp_path))
        assert e2.global_steps == want_step
        for a, b in zip(host_params(e2), want):
            np.testing.assert_array_equal(a, b)
        e.close()
        e2.close()

    def test_zero_extra_dispatches_and_syncs(self, tmp_path):
        """The dispatch-count pin: an async save adds no dispatches and
        no forced host syncs to the steady-state step loop."""
        import tempfile
        e = make_engine(base_config(
            gradient_accumulation_steps=4,
            observability={"enabled": True,
                           "events_dir": tempfile.mkdtemp(),
                           "flops_profiler": False,
                           "memory_watermarks": False}), seed=1)
        run_steps_simple(e, 1, seed=2)     # compile
        tracker = e.observability.compile_tracker
        d0 = tracker.total_dispatches
        s0 = e._host_sync_count
        run_steps_simple(e, 2, seed=3)
        assert e._host_sync_count == s0    # steady loop: sync-free
        e.save_checkpoint(str(tmp_path), async_=True)
        s1 = e._host_sync_count            # the save boundary itself may
        #                                    flush the telemetry ring
        run_steps_simple(e, 2, seed=4)
        assert tracker.total_dispatches - d0 == 4   # 1 per train_batch
        assert e._host_sync_count == s1    # post-save loop: still 0
        e.wait_pending_saves()
        assert ckpt.is_committed(str(tmp_path / "global_step3"))
        e.close()

    def test_collision_supersede_and_join(self, tmp_path):
        """A save submitted while one is writing joins (same tag) or
        supersedes (newer tag) the waiting one — never interleaves."""
        import threading
        e = make_engine(seed=1)
        run_steps_simple(e, 1)
        started = threading.Event()

        def slow_start(**_):
            started.set()
            time.sleep(0.2)

        fault.arm("ckpt.writer_crash", times=None, callback=slow_start)
        e.save_checkpoint(str(tmp_path), tag="s1", async_=True)  # runs
        assert started.wait(2)   # s1 is IN the writer before s2 lands
        e.save_checkpoint(str(tmp_path), tag="s2", async_=True)  # waits
        w = e._ckpt_writer
        assert w.submit("s2", lambda: None) == "joined"
        e.save_checkpoint(str(tmp_path), tag="s3", async_=True)  # wins
        assert w.superseded >= 1
        fault.reset()
        e.wait_pending_saves()
        assert ckpt.is_committed(str(tmp_path / "s1"))
        assert ckpt.is_committed(str(tmp_path / "s3"))
        assert not os.path.exists(str(tmp_path / "s2"))  # superseded
        e.close()

    def test_writer_error_surfaces_on_next_save(self, tmp_path):
        e = make_engine(seed=1)
        run_steps_simple(e, 1)
        fault.arm("ckpt.writer_crash",
                  exc=fault.InjectedCrash("writer died"))
        e.save_checkpoint(str(tmp_path), async_=True)
        e._drain_saves()
        with pytest.raises(RuntimeError, match="async checkpoint"):
            e.save_checkpoint(str(tmp_path))
        # error is popped once; the retried save goes through
        e.save_checkpoint(str(tmp_path))
        e.close()

    def test_writer_error_surfaces_on_close(self, tmp_path):
        e = make_engine(seed=1)
        run_steps_simple(e, 1)
        fault.arm("ckpt.writer_crash",
                  exc=fault.InjectedCrash("writer died"))
        e.save_checkpoint(str(tmp_path), async_=True)
        with pytest.raises(RuntimeError, match="async checkpoint"):
            e.close()

    def test_close_and_eval_drain(self, tmp_path):
        e = make_engine(seed=1)
        run_steps_simple(e, 1)
        fault.arm("ckpt.writer_crash", times=None,
                  callback=lambda **k: time.sleep(0.05))
        e.save_checkpoint(str(tmp_path), async_=True)
        batch = random_batches(1, 16, HIDDEN)[0]
        e.eval_batch(batch)                   # eval barrier drains
        assert e._ckpt_writer.pending_saves() == 0
        assert ckpt.is_committed(str(tmp_path / "global_step1"))
        fault.reset()
        e.save_checkpoint(str(tmp_path), async_=True)
        e.close()                             # close drains too
        assert ckpt.read_latest(str(tmp_path)) == "global_step1"

    def test_load_drains_pending_save(self, tmp_path):
        """save(async) -> load must see the committed save (ordering)."""
        e = make_engine(seed=1)
        run_steps_simple(e, 2, seed=2)
        fault.arm("ckpt.writer_crash", times=None,
                  callback=lambda **k: time.sleep(0.1))
        e.save_checkpoint(str(tmp_path), async_=True)
        path, _ = e.load_checkpoint(str(tmp_path))
        assert path is not None and path.endswith("global_step2")
        e.close()

    def test_writer_unit_semantics(self):
        """AsyncCheckpointWriter in isolation: queued/joined/superseded
        verdicts, drain, error pop-once."""
        w = ckpt.AsyncCheckpointWriter()
        import threading
        gate = threading.Event()
        done = []
        assert w.submit("a", lambda: (gate.wait(2), done.append("a"))) \
            == "queued"
        time.sleep(0.05)                      # let 'a' start
        assert w.submit("b", lambda: done.append("b")) == "queued"
        assert w.submit("b", lambda: done.append("b2")) == "joined"
        assert w.submit("c", lambda: done.append("c")) == "superseded"
        gate.set()
        assert w.drain(timeout=5)
        assert done == ["a", "c"]             # 'b' superseded, never ran
        assert w.superseded == 1

        def boom():
            raise ValueError("x")
        w.submit("d", boom)
        w.drain(timeout=5)
        with pytest.raises(RuntimeError, match="'d'"):
            w.raise_pending_error()
        w.raise_pending_error()               # popped: second call no-op
        w.close()
        with pytest.raises(RuntimeError):
            w.submit("e", lambda: None)


# ===================================================================== #
# graceful preemption drain
# ===================================================================== #

class TestPreemptionDrain:
    def _engine(self, tmp_path, **ckpt_over):
        cfg = base_config(checkpoint={"drain_on_preemption": True,
                                      "save_dir": str(tmp_path),
                                      **ckpt_over})
        return make_engine(cfg, seed=1)

    def test_sigterm_finishes_window_then_commits(self, tmp_path):
        """A real SIGTERM mid-window: the window completes, a
        preemption-tagged checkpoint commits, and Preempted (SystemExit
        with the resumable code) propagates."""
        e = self._engine(tmp_path)
        run_steps_simple(e, 1, seed=2)
        fault.arm("elastic.sigterm_mid_window",
                  callback=lambda **k: os.kill(os.getpid(),
                                               signal.SIGTERM))
        with pytest.raises(elastic.Preempted) as ei:
            run_steps_simple(e, 1, seed=3)
        assert ei.value.code == elastic.RESUMABLE_EXIT_CODE
        assert ei.value.reason == "SIGTERM"
        tag_dir = str(tmp_path / "preempt_step2")
        assert ckpt.is_committed(tag_dir)
        assert ckpt.is_preemption_tag(tag_dir)
        assert ckpt.read_latest(str(tmp_path)) == "preempt_step2"
        # the drain's close() uninstalled the signal handlers
        assert not e._elastic.installed
        # and a fresh run resumes from it
        e2 = make_engine(seed=9)
        path, _ = e2.load_checkpoint(str(tmp_path))
        assert path.endswith("preempt_step2") and e2.global_steps == 2
        e2.close()

    def test_software_trigger_drains(self, tmp_path):
        e = self._engine(tmp_path)
        run_steps_simple(e, 1, seed=2)
        e._elastic.trigger("pod-resize")
        with pytest.raises(elastic.Preempted) as ei:
            run_steps_simple(e, 1, seed=3)
        assert ei.value.reason == "pod-resize"
        assert ckpt.is_committed(str(tmp_path / "preempt_step2"))

    def test_drain_waits_for_pending_async_save(self, tmp_path):
        """A preemption with an async save in flight: the drain joins it
        before committing the preemption tag — never interleaves."""
        e = self._engine(tmp_path, async_save=True)
        run_steps_simple(e, 1, seed=2)
        fault.arm("ckpt.writer_crash", times=None,
                  callback=lambda **k: time.sleep(0.1))
        e.save_checkpoint(str(tmp_path))      # async per config
        e._elastic.trigger()
        with pytest.raises(elastic.Preempted):
            run_steps_simple(e, 1, seed=3)
        assert ckpt.is_committed(str(tmp_path / "global_step1"))
        assert ckpt.is_committed(str(tmp_path / "preempt_step2"))

    def test_no_save_dir_still_exits_resumable(self, tmp_path):
        cfg = base_config(checkpoint={"drain_on_preemption": True})
        e = make_engine(cfg, seed=1)
        run_steps_simple(e, 1, seed=2)
        e._elastic.trigger()
        with pytest.raises(elastic.Preempted) as ei:
            run_steps_simple(e, 1, seed=3)
        assert ei.value.tag is None
        assert ei.value.code == elastic.RESUMABLE_EXIT_CODE

    def test_offload_facade_step_drains(self, tmp_path):
        """Regression: the ZeRO-Offload facade forward/backward/step
        path returns early in step() — the boundary check must still
        run there, or an installed (flag-only) handler would swallow
        SIGTERM outright."""
        cfg = base_config(
            zero_optimization={"stage": 2, "cpu_offload": True},
            checkpoint={"drain_on_preemption": True,
                        "save_dir": str(tmp_path)})
        e = make_engine(cfg, seed=1)
        batches = random_batches(4, 16, HIDDEN, seed=2)
        e.forward(batches[0])
        e.backward()
        e.step()
        e._elastic.trigger("SIGTERM")
        e.forward(batches[1])
        e.backward()
        with pytest.raises(elastic.Preempted):
            e.step()
        assert ckpt.is_committed(str(tmp_path / "preempt_step2"))

    def test_preemption_event_row(self, tmp_path):
        import tempfile
        obs_dir = tempfile.mkdtemp()
        cfg = base_config(
            checkpoint={"drain_on_preemption": True,
                        "save_dir": str(tmp_path)},
            observability={"enabled": True, "events_dir": obs_dir,
                           "flops_profiler": False,
                           "memory_watermarks": False})
        e = make_engine(cfg, seed=1)
        run_steps_simple(e, 1, seed=2)
        e._elastic.trigger("SIGTERM")
        with pytest.raises(elastic.Preempted):
            run_steps_simple(e, 1, seed=3)
        rows = [json.loads(l) for l in
                open(os.path.join(obs_dir, "events.jsonl"))]
        pre = [r for r in rows if r.get("event") == "preemption"]
        assert len(pre) == 1
        assert pre[0]["tag"] == "preempt_step2"
        assert pre[0]["committed"] is True
        # snapshot/write telemetry rode along with the drain's save
        tags = {r.get("tag") for r in rows}
        assert "Checkpoint/snapshot_ms" in tags
        assert "Checkpoint/write_ms" in tags

    def test_resume_event_carries_restart_count(self, tmp_path,
                                                monkeypatch):
        import tempfile
        e = make_engine(seed=1)
        run_steps_simple(e, 2, seed=2)
        e.save_checkpoint(str(tmp_path))
        e.close()
        monkeypatch.setenv(elastic.RESTART_COUNT_ENV, "2")
        obs_dir = tempfile.mkdtemp()
        cfg = base_config(
            observability={"enabled": True, "events_dir": obs_dir,
                           "flops_profiler": False,
                           "memory_watermarks": False})
        e2 = make_engine(cfg, seed=9)
        assert e2._restart_count == 2
        e2.load_checkpoint(str(tmp_path))
        e2.close()
        rows = [json.loads(l) for l in
                open(os.path.join(obs_dir, "events.jsonl"))]
        res = [r for r in rows if r.get("event") == "resume"]
        assert len(res) == 1
        assert res[0]["restarts"] == 2
        assert res[0]["tag"] == "global_step2"
        assert any(r.get("tag") == "Checkpoint/restarts"
                   and r.get("value") == 2.0 for r in rows)


class TestPreemptionGuard:
    def test_trigger_and_clear(self):
        g = elastic.PreemptionGuard(signals=())
        assert not g.preempted
        g.trigger("x")
        assert g.preempted and g.reason == "x"
        g.trigger("y")                        # first reason wins
        assert g.reason == "x"
        g.clear()
        assert not g.preempted and g.reason is None

    def test_install_uninstall_restores_handlers(self):
        prev = signal.getsignal(signal.SIGTERM)
        g = elastic.PreemptionGuard(signals=(signal.SIGTERM,))
        assert g.install()
        assert signal.getsignal(signal.SIGTERM) == g._handler
        os.kill(os.getpid(), signal.SIGTERM)
        # deliver: a pure-python no-op forces the interpreter to run
        # pending signal handlers
        time.sleep(0.01)
        assert g.preempted and g.reason == "SIGTERM"
        g.uninstall()
        assert signal.getsignal(signal.SIGTERM) == prev

    def test_request_preemption_flags_installed_guards(self):
        with elastic.PreemptionGuard(signals=()) as g:
            n = elastic.request_preemption("env")
            assert n >= 1 and g.preempted and g.reason == "env"
        assert elastic.request_preemption("late") == 0 or not g.installed

    def test_restart_count_parse(self):
        assert elastic.restart_count({}) == 0
        assert elastic.restart_count(
            {elastic.RESTART_COUNT_ENV: "3"}) == 3
        assert elastic.restart_count(
            {elastic.RESTART_COUNT_ENV: "junk"}) == 0
        assert elastic.restart_count(
            {elastic.RESTART_COUNT_ENV: "-2"}) == 0


# ===================================================================== #
# env-armed fault injection (DSTPU_FAULT_ARM)
# ===================================================================== #

class TestEnvArm:
    def test_crash_action(self):
        armed = fault.arm_from_env({fault.ENV_ARM: "x.point:crash"})
        assert armed == ["x.point"]
        with pytest.raises(fault.InjectedCrash):
            fault.fire("x.point")
        fault.fire("x.point")                 # times=1: spent

    def test_times_and_multiple_specs(self):
        armed = fault.arm_from_env(
            {fault.ENV_ARM: "a:oserror:2, b:crash"})
        assert armed == ["a", "b"]
        with pytest.raises(OSError):
            fault.fire("a")
        with pytest.raises(OSError):
            fault.fire("a")
        fault.fire("a")                       # spent after 2
        with pytest.raises(fault.InjectedCrash):
            fault.fire("b")

    def test_once_file_consumed_across_incarnations(self, tmp_path):
        once = tmp_path / "armed"
        once.write_text("1")
        spec = {fault.ENV_ARM: f"p:crash@{once}"}
        assert fault.arm_from_env(spec) == ["p"]
        with pytest.raises(fault.InjectedCrash):
            fault.fire("p")
        assert not once.exists()              # consumed on first fire
        fault.reset()
        # the "relaunched process" arms from the same env: no-op now
        assert fault.arm_from_env(spec) == []
        fault.fire("p")

    def test_unset_and_malformed(self):
        assert fault.arm_from_env({}) == []
        with pytest.raises(ValueError):
            fault.arm_from_env({fault.ENV_ARM: "justapoint"})
        with pytest.raises(ValueError):
            fault.arm_from_env({fault.ENV_ARM: "p:frobnicate"})

    def test_engine_path_arms_once_per_process(self, monkeypatch):
        """Regression: a second engine's init must not re-arm (and
        reset the fired counter of) a `times:1` spec — env arming is
        per process, not per engine."""
        monkeypatch.setattr(fault, "_ENV_ARMED", False)
        monkeypatch.setenv(fault.ENV_ARM, "q.point:crash")
        assert fault.arm_from_env() == ["q.point"]
        with pytest.raises(fault.InjectedCrash):
            fault.fire("q.point")
        assert fault.arm_from_env() == []     # second engine init
        fault.fire("q.point")                 # still spent


# ===================================================================== #
# launcher supervisor
# ===================================================================== #

class TestSupervisor:
    def test_relaunches_on_resumable_exit_with_backoff(self):
        codes = iter([elastic.RESUMABLE_EXIT_CODE,
                      elastic.RESUMABLE_EXIT_CODE, 0])
        seen, sleeps = [], []
        rc = runner.supervise(
            lambda r: (seen.append(r), next(codes))[1],
            max_restarts=3, backoff=1.0, sleep=sleeps.append)
        assert rc == 0
        assert seen == [0, 1, 2]              # restart count exported
        assert sleeps == [1.0, 2.0]           # exponential backoff

    def test_gives_up_on_genuine_failure(self):
        codes = iter([elastic.RESUMABLE_EXIT_CODE, 17])
        rc = runner.supervise(lambda r: next(codes), max_restarts=5,
                              backoff=0.0, sleep=lambda s: None)
        assert rc == 17

    def test_gives_up_after_max_restarts(self):
        calls = []
        rc = runner.supervise(
            lambda r: (calls.append(r),
                       elastic.RESUMABLE_EXIT_CODE)[1],
            max_restarts=2, backoff=0.0, sleep=lambda s: None)
        assert rc == elastic.RESUMABLE_EXIT_CODE
        assert calls == [0, 1, 2]             # initial + 2 restarts

    def test_zero_exit_passes_through(self):
        assert runner.supervise(lambda r: 0, max_restarts=3,
                                backoff=0.0) == 0

    def test_restart_decision_matrix(self):
        """The restart classification is API: the preemption drain (85) and
        the hang watchdog's distinguished kill (87) are the ONLY exit
        codes worth another life — both certify a committed checkpoint
        chain. Everything else is a genuine failure."""
        assert runner.RESTARTABLE_EXIT_CODES == (85, 87)
        assert runner.RESTARTABLE_EXIT_CODES == (
            elastic.RESUMABLE_EXIT_CODE, health.STALL_EXIT_CODE)
        for rc, eligible in [(85, True), (87, True), (143, False),
                             (1, False), (0, False), (None, False)]:
            assert runner.restart_eligible(rc) is eligible, rc

    def test_watchdog_kill_is_restartable_end_to_end(self):
        # 87 then clean exit: one relaunch, one backoff sleep
        codes = iter([health.STALL_EXIT_CODE, 0])
        sleeps = []
        rc = runner.supervise(lambda r: next(codes), max_restarts=3,
                              backoff=1.0, sleep=sleeps.append)
        assert rc == 0
        assert sleeps == [1.0]
        # 87 then SIGTERM-ish 143: relaunched once, then give up
        codes = iter([health.STALL_EXIT_CODE, 143])
        rc = runner.supervise(lambda r: next(codes), max_restarts=3,
                              backoff=0.0, sleep=lambda s: None)
        assert rc == 143
        # constant genuine failure: returned immediately, no restarts
        calls = []
        rc = runner.supervise(lambda r: (calls.append(r), 1)[1],
                              max_restarts=3, backoff=0.0,
                              sleep=lambda s: None)
        assert rc == 1
        assert calls == [0]


CHILD_SCRIPT = textwrap.dedent("""
    import os, sys
    sys.path.insert(0, {repo!r})
    import jax
    import deepspeed_tpu
    from tests.unit.simple_model import (
        base_config, init_simple_params, random_batches, simple_loss_fn)

    save_dir, target = sys.argv[1], int(sys.argv[2])
    cfg = base_config(checkpoint={{"drain_on_preemption": True,
                                   "save_dir": save_dir}})
    e, *_ = deepspeed_tpu.initialize(
        model=simple_loss_fn,
        model_parameters=init_simple_params(jax.random.PRNGKey(0), 16),
        config=cfg)
    e.load_checkpoint(save_dir)
    start = e.global_steps
    batches = iter(random_batches(16, 16, 16, seed=start))
    while e.global_steps < target:
        e.train_batch(batches)
    e.save_checkpoint(save_dir)
    e.close()
    print("CHILD-DONE", e.global_steps, flush=True)
""")


def test_supervisor_restarts_preempted_child(tmp_path):
    """The full drill across a REAL process boundary: incarnation 1 is
    env-arm-SIGTERMed mid-window, drains, commits a preemption tag and
    exits with the resumable code; the supervisor relaunches; the
    one-shot arm file is consumed so incarnation 2 resumes from the
    preemption checkpoint, trains to the target and exits 0."""
    repo = os.path.dirname(os.path.dirname(
        os.path.dirname(os.path.abspath(__file__))))
    script = tmp_path / "child.py"
    script.write_text(CHILD_SCRIPT.format(repo=repo))
    save_dir = tmp_path / "ckpt"
    save_dir.mkdir()
    once = tmp_path / "armed_once"
    once.write_text("1")

    attempts = []

    def run_once(restarts):
        attempts.append(restarts)
        env = dict(os.environ)
        env["JAX_PLATFORMS"] = "cpu"
        env["XLA_FLAGS"] = "--xla_force_host_platform_device_count=1"
        env[fault.ENV_ARM] = f"elastic.sigterm_mid_window:sigterm@{once}"
        env[elastic.RESTART_COUNT_ENV] = str(restarts)
        proc = subprocess.run(
            [sys.executable, str(script), str(save_dir), "3"],
            env=env, cwd=repo, capture_output=True, text=True,
            timeout=240)
        return proc.returncode

    rc = runner.supervise(run_once, max_restarts=2, backoff=0.0,
                          sleep=lambda s: None)
    assert rc == 0
    assert attempts == [0, 1]                 # exactly one relaunch
    assert not once.exists()                  # arm consumed by life 1
    # life 1 left a committed preemption tag; life 2 finished at step 3
    tags = ckpt.list_tags(str(save_dir))
    assert any(t.startswith("preempt_step") for t in tags)
    assert ckpt.newest_committed_step(str(save_dir)) == 3


# ===================================================================== #
# retention safety (satellite)
# ===================================================================== #

def _commit_fake_tag(save_dir, tag, preempted=False):
    d = os.path.join(str(save_dir), tag)
    os.makedirs(d)
    meta = {"global_step": max(ckpt.tag_step(tag), 0)}
    if preempted:
        meta["preempted"] = True
    ckpt.write_meta(d, meta)
    ckpt.write_commit_marker(d)
    return d


class TestRetentionSafety:
    def test_gc_protects_preempt_tags_newer_than_latest(self, tmp_path):
        """keep_n=1 + stale pointer after a preemption drain: committed
        preemption tags newer than `latest` must survive GC — they are
        exactly what the relaunch resumes."""
        _commit_fake_tag(tmp_path, "global_step2")
        _commit_fake_tag(tmp_path, "preempt_step4", preempted=True)
        _commit_fake_tag(tmp_path, "preempt_step6", preempted=True)
        ckpt.write_latest(str(tmp_path), "global_step2")
        doomed = ckpt.gc_old_tags(str(tmp_path), keep_n=1)
        assert doomed == []
        for t in ("global_step2", "preempt_step4", "preempt_step6"):
            assert os.path.isdir(str(tmp_path / t)), t

    def test_gc_still_collects_old_preempt_tags(self, tmp_path):
        """A preemption tag OLDER than latest is ordinary history."""
        _commit_fake_tag(tmp_path, "preempt_step1", preempted=True)
        _commit_fake_tag(tmp_path, "global_step4")
        _commit_fake_tag(tmp_path, "global_step6")
        ckpt.write_latest(str(tmp_path), "global_step6")
        doomed = ckpt.gc_old_tags(str(tmp_path), keep_n=1)
        assert sorted(doomed) == ["global_step4", "preempt_step1"]

    def test_gc_keep_n1_fallback_race_regression(self, tmp_path):
        """keep_n=1 with a stale pointer (save committed, crash before
        the pointer update): BOTH the newest committed tag and latest's
        target survive, so the fallback loader always finds a copy."""
        _commit_fake_tag(tmp_path, "global_step2")
        _commit_fake_tag(tmp_path, "global_step4")
        ckpt.write_latest(str(tmp_path), "global_step2")
        doomed = ckpt.gc_old_tags(str(tmp_path), keep_n=1)
        assert doomed == []
        assert os.path.isdir(str(tmp_path / "global_step2"))
        assert os.path.isdir(str(tmp_path / "global_step4"))


# ===================================================================== #
# telemetry registry sync + obs_report (satellite)
# ===================================================================== #

def _load_tool(name):
    import importlib.util
    repo = os.path.dirname(os.path.dirname(
        os.path.dirname(os.path.abspath(__file__))))
    spec = importlib.util.spec_from_file_location(
        name, os.path.join(repo, "tools", f"{name}.py"))
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def test_elastic_tag_registry_in_sync():
    """One tag, three homes: monitor (canonical), profiling registry
    (re-export), obs_report (mirrored strings)."""
    from deepspeed_tpu import profiling as prof
    from deepspeed_tpu.utils import monitor as m
    obs_report = _load_tool("obs_report")
    assert m.TAG_CKPT_SNAPSHOT_MS == prof.TAG_CKPT_SNAPSHOT_MS == \
        obs_report.T_CKPT_SNAPSHOT
    assert m.TAG_CKPT_WRITE_MS == prof.TAG_CKPT_WRITE_MS == \
        obs_report.T_CKPT_WRITE
    assert m.TAG_CKPT_PENDING == prof.TAG_CKPT_PENDING == \
        obs_report.T_CKPT_PENDING
    assert m.TAG_CKPT_RESTARTS == prof.TAG_CKPT_RESTARTS == \
        obs_report.T_CKPT_RESTARTS


def test_obs_report_renders_elastic_section(tmp_path):
    obs_report = _load_tool("obs_report")
    rows = [
        {"tag": "Train/Samples/train_loss", "value": 1.0, "step": 8},
        {"tag": "Checkpoint/snapshot_ms", "value": 4.0, "step": 8},
        {"tag": "Checkpoint/snapshot_ms", "value": 6.0, "step": 16},
        {"tag": "Checkpoint/write_ms", "value": 50.0, "step": 16},
        {"tag": "Checkpoint/pending_saves", "value": 1.0, "step": 16},
        {"tag": "Checkpoint/restarts", "value": 2.0, "step": 16},
        {"event": "preemption", "reason": "SIGTERM", "step": 4,
         "tag": "preempt_step4", "committed": True, "restarts": 1},
        {"event": "resume", "step": 4, "tag": "preempt_step4",
         "restarts": 2, "preempted": True},
    ]
    p = tmp_path / "events.jsonl"
    with open(p, "w") as f:
        for r in rows:
            f.write(json.dumps(r) + "\n")
    s = obs_report.summarize(str(p))
    el = s["elastic"]
    assert el["snapshot_ms_mean"] == 5.0
    assert el["write_ms_mean"] == 50.0
    assert el["pending_saves_peak"] == 1.0
    assert el["restarts"] == 2.0
    assert el["preemptions"] == 1 and el["resumes"] == 1
    assert el["last_preemption"]["tag"] == "preempt_step4"
    text = obs_report.render(s)
    assert "elastic" in text and "restarts=2" in text
    assert "preempt_step4" in text
    assert obs_report.main([str(p)]) == 0
    assert obs_report.main([str(p), "--json"]) == 0


def test_monitor_write_elastic_metrics(tmp_path):
    from deepspeed_tpu.utils.monitor import TensorBoardMonitor, \
        _JsonlWriter
    mon = TensorBoardMonitor(enabled=False)
    mon.mirror = _JsonlWriter(str(tmp_path))
    mon.write_elastic_metrics(snapshot_ms=3.5, write_ms=40.0,
                              pending_saves=2, restarts=1, samples=64)
    mon.mirror.close()
    rows = [json.loads(l)
            for l in open(str(tmp_path / "events.jsonl"))]
    got = {r["tag"]: r["value"] for r in rows}
    assert got == {"Checkpoint/snapshot_ms": 3.5,
                   "Checkpoint/write_ms": 40.0,
                   "Checkpoint/pending_saves": 2.0,
                   "Checkpoint/restarts": 1.0}
    assert all(r["step"] == 64 for r in rows)


# ===================================================================== #
# verify_checkpoint CLI: preemption display + --expect-step (satellite)
# ===================================================================== #

class TestVerifyCLI:
    def test_expect_step_and_preempt_report(self, tmp_path, capsys):
        vc = _load_tool("verify_checkpoint")
        e = make_engine(seed=1)
        run_steps_simple(e, 2, seed=2)
        e.save_checkpoint(str(tmp_path))
        e._elastic = elastic.PreemptionGuard(signals=())
        e._ckpt_cfg["save_dir"] = str(tmp_path)
        e._restart_count = 0
        run_steps_simple(e, 1, seed=3)
        e._elastic.trigger("SIGTERM")
        with pytest.raises(elastic.Preempted):
            run_steps_simple(e, 1, seed=4)
        # newest committed is preempt_step4 -> expect-step 4 passes
        assert vc.main([str(tmp_path), "--expect-step", "4",
                        "--all"]) == 0
        out = capsys.readouterr().out
        assert "PREEMPTION checkpoint" in out
        assert "(preemption)" in out
        assert "expect-step OK" in out
        # demanding a newer step than exists fails nonzero
        assert vc.main([str(tmp_path), "--expect-step", "9"]) != 0

    def test_expect_step_on_tag_dir(self, tmp_path, capsys):
        vc = _load_tool("verify_checkpoint")
        e = make_engine(seed=1)
        run_steps_simple(e, 1, seed=2)
        e.save_checkpoint(str(tmp_path))
        e.close()
        tag_dir = str(tmp_path / "global_step1")
        assert vc.main([tag_dir, "--expect-step", "1"]) == 0
        assert vc.main([tag_dir, "--expect-step", "5"]) == 1


# ===================================================================== #
# config plumbing (satellite)
# ===================================================================== #

def test_checkpoint_config_parsing():
    from deepspeed_tpu.runtime.config import (DeepSpeedConfig,
                                              DeepSpeedConfigError)
    cfg = DeepSpeedConfig(base_config()).checkpoint_config
    assert cfg["async_save"] is False
    assert cfg["drain_on_preemption"] is False
    assert cfg["save_dir"] is None
    assert cfg["supervisor"] == {"max_restarts": 3, "backoff": 1.0}
    cfg = DeepSpeedConfig(base_config(checkpoint={
        "async_save": True, "drain_on_preemption": True,
        "save_dir": "/tmp/x",
        "supervisor": {"max_restarts": 7, "backoff": 0.5},
    })).checkpoint_config
    assert cfg["async_save"] and cfg["drain_on_preemption"]
    assert cfg["save_dir"] == "/tmp/x"
    assert cfg["supervisor"] == {"max_restarts": 7, "backoff": 0.5}
    with pytest.raises(DeepSpeedConfigError):
        DeepSpeedConfig(base_config(checkpoint={
            "supervisor": {"max_restarts": -1}}))
    with pytest.raises(DeepSpeedConfigError):
        DeepSpeedConfig(base_config(checkpoint={
            "supervisor": {"backoff": -0.1}}))
    with pytest.raises(DeepSpeedConfigError):
        DeepSpeedConfig(base_config(checkpoint={"save_dir": 3}))
