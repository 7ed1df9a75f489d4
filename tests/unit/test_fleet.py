"""Serving fleet (deepspeed_tpu/inference/fleet.py): multi-replica
router with SLO-driven load shedding, replica drain, and live weight
swap — serve through a preemption.

Tier-1 acceptance pins (ISSUE 14):
- a fixed mixed-length workload over 3 replicas reproduces the
  single-engine greedy outputs BITWISE — with a mid-run weight swap
  (same weights) AND with a replica drained mid-run (its queue
  redistributes to survivors);
- zero dropped responses in every scenario (exactly one
  FinishedRequest per submitted uid; a shed is a synthesized zero-token
  answer, never a missing one);
- ``steady_state_recompiles == 0`` on every replica across routing,
  drain, and swap;
- an injected mid-swap load failure (``serve.swap_load``) rolls the
  replica back to its old weights without killing it;
- the ``Serve/{shed_rate,fleet_queue_depth,weight_version}`` tags and
  the shed vocabulary stay in sync across their three homes.

The shed-ladder / routing-policy tests run on duck-typed fake engines:
fleet.py is jax-free (pinned by test_inference.py), so pure routing
policy is unit-testable in microseconds.
"""

import importlib.util
import json
import os

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from deepspeed_tpu.runtime import fault

REPO = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))


def _load_tool(name):
    spec = importlib.util.spec_from_file_location(
        name, os.path.join(REPO, "tools", f"{name}.py"))
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def tiny_gpt2():
    from deepspeed_tpu.models.gpt2 import GPT2Config, init_gpt2_params
    cfg = GPT2Config(vocab_size=61, max_position_embeddings=64,
                     hidden_size=32, num_layers=2, num_heads=4,
                     embd_dropout=0.0, attn_dropout=0.0,
                     resid_dropout=0.0)
    return cfg, init_gpt2_params(cfg, jax.random.PRNGKey(3))


INF = {"max_batch_size": 3, "prompt_buckets": [4, 8, 16, 24],
       "batch_buckets": [1, 2], "max_seq_len": 48,
       "max_new_tokens": 8}
NEW_TOKENS = 8

# the pinned mixed-length workload: enough requests that a drained
# replica still holds a non-empty queue (redistribution is exercised,
# not vacuously skipped)
_rng = np.random.RandomState(5)
WORKLOAD = [_rng.randint(1, 61, (l,)).tolist()
            for l in (5, 9, 3, 12, 4, 7, 15, 6, 8, 10, 5, 13)]


def _requests():
    from deepspeed_tpu.inference import Request
    return [Request(prompt=list(p), max_new_tokens=NEW_TOKENS,
                    temperature=0.0, seed=0) for p in WORKLOAD]


def _submit_all(target):
    reqs = _requests()
    return [target.submit(r) for r in reqs]


def _serve_single(cfg, params, events_dir=None):
    from deepspeed_tpu.inference import InferenceEngine
    ic = dict(INF)
    if events_dir is not None:
        ic["events_dir"] = events_dir
    eng = InferenceEngine(cfg, params, ic, dtype=jnp.float32)
    eng.warmup()
    uids = _submit_all(eng)
    by_uid = {f.uid: f.tokens for f in eng.run()}
    outs = [by_uid[u] for u in uids]
    rc = eng.steady_state_recompiles
    eng.close()
    return outs, rc


def _save_tag(ckptlib, root, tag, params, step):
    d = os.path.join(root, tag)
    os.makedirs(d, exist_ok=True)
    ckptlib.save_tree_sharded(d, "model_states", params)
    with open(os.path.join(d, "meta.json"), "w") as f:
        json.dump({"global_step": step}, f)
    ckptlib.write_commit_marker(d)
    ckptlib.write_latest(root, tag)
    return d


@pytest.fixture(scope="module")
def fleet_runs(tmp_path_factory):
    """All the expensive real-engine serving, once per module."""
    from deepspeed_tpu.inference import FleetRouter, InferenceEngine
    from deepspeed_tpu.runtime import checkpoint as ckptlib

    cfg, p1 = tiny_gpt2()
    from deepspeed_tpu.models.gpt2 import init_gpt2_params
    p2 = init_gpt2_params(cfg, jax.random.PRNGKey(7))

    ckroot = str(tmp_path_factory.mktemp("fleet_ckpt"))
    _save_tag(ckptlib, ckroot, "global_step1", p1, 1)
    _save_tag(ckptlib, ckroot, "global_step2", p2, 2)

    out = {"ckroot": ckroot}
    out["base"], out["base_rc"] = _serve_single(cfg, p1)
    out["p2_ref"], _ = _serve_single(cfg, p2)

    evdir = str(tmp_path_factory.mktemp("fleet_events"))

    def build_fleet(events=False):
        engines = []
        for i in range(3):
            ic = dict(INF)
            if events and i == 0:
                ic["events_dir"] = evdir
            eng = InferenceEngine(cfg, p1, ic, dtype=jnp.float32)
            eng.warmup()
            engines.append(eng)
        return engines, FleetRouter(engines, {"replicas": 3})

    try:
        # ---- fleet 1: routing parity + mid-run swap + push + rollback
        engines, router = build_fleet(events=True)
        uids = _submit_all(router)
        fins = []
        while len(fins) < 4:           # some answers land pre-swap...
            fins.extend(router.step())
        swap1 = router.swap_weights(ckroot, tag="global_step1")
        fins.extend(router.run())      # ...the rest after (same weights)
        by_uid = {f.uid: f for f in fins}
        out["swap_outs"] = [by_uid[u].tokens for u in uids]
        out["swap_fins"] = len(fins)
        out["swap_versions"] = {f.weight_version for f in fins}
        out["swap1"] = swap1

        # push genuinely NEW weights (auto-resolves newest committed)
        out["swap2"] = router.swap_weights(ckroot)
        uids2 = _submit_all(router)
        by_uid2 = {f.uid: f for f in router.run()}
        out["push_outs"] = [by_uid2[u].tokens for u in uids2]
        out["push_versions"] = {f.weight_version
                                for f in by_uid2.values()}

        # injected mid-swap failure on every replica: atomic-or-rollback
        fault.arm_from_env(
            env={fault.ENV_ARM: "serve.swap_load:oserror:3"})
        out["swap3"] = router.swap_weights(ckroot, tag="global_step1")
        fault.reset()
        uids3 = _submit_all(router)
        by_uid3 = {f.uid: f for f in router.run()}
        out["rollback_outs"] = [by_uid3[u].tokens for u in uids3]
        out["rollback_versions"] = {f.weight_version
                                    for f in by_uid3.values()}
        out["fleet1_rc"] = [e.steady_state_recompiles for e in engines]
        out["fleet1_state"] = router.debug_state()
        router.close()
        out["events_dir"] = evdir

        # ---- fleet 2: dispatch-fault reroute + preemption drain
        engines2, router2 = build_fleet()
        fault.arm("serve.dispatch", exc=OSError("injected flake"),
                  times=1)
        uids_d = _submit_all(router2)
        assert fault.get_injector().fired("serve.dispatch") == 1
        out["reroutes"] = router2.total_reroutes
        fins2 = router2.step()         # replicas get some work in flight
        fault.arm("serve.replica_preempt",
                  exc=fault.InjectedCrash("preempted"), times=1,
                  filter=lambda **ctx: ctx.get("replica") == 0)
        fins2.extend(router2.run())
        fault.reset()
        by_uid_d = {f.uid: f for f in fins2}
        out["drain_outs"] = [by_uid_d[u].tokens for u in uids_d]
        out["drain_fins"] = len(fins2)
        out["drain_reasons"] = {f.finish_reason for f in fins2}
        out["drain_state"] = router2.debug_state()
        out["redistributed"] = router2.total_redistributed
        out["fleet2_rc"] = [e.steady_state_recompiles for e in engines2]
        router2.close()
    finally:
        fault.reset()
    return out


class TestFleetContract:
    def test_baseline_sane(self, fleet_runs):
        assert len(fleet_runs["base"]) == len(WORKLOAD)
        assert all(len(t) == NEW_TOKENS for t in fleet_runs["base"])
        assert fleet_runs["base_rc"] == 0
        # the two weight sets genuinely disagree (else the swap pins
        # below would be vacuous)
        assert fleet_runs["p2_ref"] != fleet_runs["base"]

    def test_swap_parity_bitwise(self, fleet_runs):
        """Mid-run swap to the SAME weights: every request's greedy
        output bitwise equals the single-engine baseline."""
        assert fleet_runs["swap_outs"] == fleet_runs["base"]

    def test_swap_zero_dropped_and_versioned(self, fleet_runs):
        assert fleet_runs["swap_fins"] == len(WORKLOAD)
        # answers finished before the swap are stamped "initial",
        # after it the tag — the swap is attributable per response
        assert fleet_runs["swap_versions"] == {"initial",
                                               "global_step1"}
        assert fleet_runs["swap1"] == {0: "global_step1",
                                       1: "global_step1",
                                       2: "global_step1"}

    def test_push_new_weights_changes_outputs(self, fleet_runs):
        """Auto-resolved push of different weights: the fleet now
        reproduces a fresh engine built with those weights."""
        assert fleet_runs["swap2"] == {0: "global_step2",
                                       1: "global_step2",
                                       2: "global_step2"}
        assert fleet_runs["push_outs"] == fleet_runs["p2_ref"]
        assert fleet_runs["push_versions"] == {"global_step2"}

    def test_mid_swap_fault_rolls_back(self, fleet_runs):
        """serve.swap_load injection on every replica: each rolls back
        to (and keeps serving) its OLD weights — no replica dies, no
        output changes, no recompile."""
        assert fleet_runs["swap3"] == {0: None, 1: None, 2: None}
        assert fleet_runs["rollback_outs"] == fleet_runs["p2_ref"]
        assert fleet_runs["rollback_versions"] == {"global_step2"}

    def test_zero_steady_state_recompiles(self, fleet_runs):
        assert fleet_runs["fleet1_rc"] == [0, 0, 0]
        assert fleet_runs["fleet2_rc"] == [0, 0, 0]

    def test_dispatch_fault_reroutes(self, fleet_runs):
        """A transient serve.dispatch failure reroutes to the next-best
        replica — the request is never dropped."""
        assert fleet_runs["reroutes"] == 1
        st = fleet_runs["drain_state"]
        assert sum(r["dispatch_faults"] for r in st["replicas"]) == 1

    def test_drain_parity_bitwise(self, fleet_runs):
        """Replica 0 preempted mid-run (injected serve.replica_preempt):
        queued requests redistribute, in-flight finish in place, and
        every greedy output still bitwise equals the baseline."""
        assert fleet_runs["drain_outs"] == fleet_runs["base"]

    def test_drain_zero_dropped(self, fleet_runs):
        assert fleet_runs["drain_fins"] == len(WORKLOAD)
        assert fleet_runs["drain_reasons"] <= {"length", "eos"}

    def test_drain_redistributes_and_retires(self, fleet_runs):
        assert fleet_runs["redistributed"] >= 1
        st = fleet_runs["drain_state"]
        r0 = st["replicas"][0]
        assert r0["status"] == "retired"
        assert str(r0["drain_reason"]).startswith("fault:")
        assert {r["status"] for r in st["replicas"][1:]} == {"live"}

    def test_fleet_debug_state_shape(self, fleet_runs):
        st = fleet_runs["fleet1_state"]
        assert st["routing"] == "least_loaded"
        assert st["submitted"] == 3 * len(WORKLOAD)
        assert st["shed"]["total"] == 0 and st["shed"]["rate"] == 0.0
        assert st["fleet_queue_depth"] == 0
        assert {r["weight_version"] for r in st["replicas"]} == \
            {"global_step2"}
        assert all(r["weight_ordinal"] == 2 for r in st["replicas"])


class TestFleetObservability:
    def test_event_trail_and_obs_report(self, fleet_runs):
        ev = os.path.join(fleet_runs["events_dir"], "events.jsonl")
        rows = [json.loads(l) for l in open(ev) if l.strip()]
        kinds = {r.get("event") for r in rows if "event" in r}
        assert {"fleet_swap", "fleet_swap_push", "fleet_state"} <= kinds
        # replica 0 owns the event writer: its 2 applied swaps and 1
        # rolled-back swap land, each stamped with the serving version
        swaps = [r for r in rows if r.get("event") == "fleet_swap"]
        assert sum(1 for r in swaps if r["ok"]) == 2
        assert sum(1 for r in swaps if not r["ok"]) == 1
        assert all(not r["ok"] or r["weight_version"] for r in swaps)

        obs_report = _load_tool("obs_report")
        s = obs_report.summarize(fleet_runs["events_dir"])
        fl = s["serving"]["fleet"]
        assert fl is not None
        assert len(fl["replicas"]) == 3
        assert fl["routing"] == "least_loaded"
        assert fl["shed"]["total"] == 0
        assert [t for t in fl["timeline"] if t["kind"] == "swap"]
        text = obs_report.render_serve(s)
        assert "fleet" in text and "replica 0" in text
        assert obs_report.main([fleet_runs["events_dir"],
                                "--serve"]) == 0
        assert obs_report.main([fleet_runs["events_dir"],
                                "--json"]) == 0

    def test_fleet_tracing_adds_no_dispatch(self, tmp_path):
        """Fleet tracing on both sides of the router (trace-id stamping
        and ``fleet_dispatch`` rows in the router's log, the serve trail
        in each replica's own events.jsonl) is host-side Python: the
        replicas take exactly as many device dispatches, recompile
        nothing and answer with the same tokens as an untraced fleet.
        (The process fleet's ``dispatches`` state field reports this
        same CompileTracker count.)"""
        from deepspeed_tpu.inference import FleetRouter, InferenceEngine
        from deepspeed_tpu.utils.monitor import _JsonlWriter
        cfg, params = tiny_gpt2()

        def serve(traced):
            engines = []
            for i in range(2):
                ic = dict(INF)
                if traced:
                    ic["events_dir"] = str(tmp_path / f"r{i}")
                eng = InferenceEngine(
                    cfg, params, ic, dtype=jnp.float32,
                    observability_config={"serve": {"enabled": traced}})
                eng.warmup()
                engines.append(eng)
            writer = _JsonlWriter(str(tmp_path / "router")) \
                if traced else None
            router = FleetRouter(engines, {"replicas": 2}, writer=writer)
            d0 = [e.compile_tracker.total_dispatches for e in engines]
            uids = _submit_all(router)
            by_uid = {f.uid: f.tokens for f in router.run()}
            stats = ([e.compile_tracker.total_dispatches - d
                      for e, d in zip(engines, d0)],
                     [e.steady_state_recompiles for e in engines])
            router.close()
            if writer is not None:
                writer.close()
            return [by_uid[u] for u in uids], stats

        outs_off, stats_off = serve(False)
        outs_on, stats_on = serve(True)
        assert stats_on == stats_off and stats_on[1] == [0, 0]
        assert outs_on == outs_off
        rows = [json.loads(l) for l in
                open(tmp_path / "router" / "events.jsonl") if l.strip()]
        assert sum(r.get("event") == "fleet_dispatch" for r in rows) \
            == len(WORKLOAD)
        assert os.path.getsize(tmp_path / "r0" / "events.jsonl") > 0

    def test_serve_ready_preflight(self, fleet_runs, capsys):
        """tools/verify_checkpoint.py --serve-ready: the fleet swap
        preflight — the tag must verify AND carry model_states."""
        vc = _load_tool("verify_checkpoint")
        tag_dir = os.path.join(fleet_runs["ckroot"], "global_step2")
        assert vc.main([tag_dir, "--serve-ready"]) == 0
        assert "serve-ready OK" in capsys.readouterr().out
        assert vc.main([fleet_runs["ckroot"], "--serve-ready",
                        "--all"]) == 0
        # a tag with no model_states group can never be a swap target
        bad = os.path.join(fleet_runs["ckroot"], "optim_only")
        os.makedirs(bad, exist_ok=True)
        with open(os.path.join(bad, "meta.json"), "w") as f:
            json.dump({"global_step": 3}, f)
        from deepspeed_tpu.runtime import checkpoint as ckptlib
        ckptlib.write_commit_marker(bad)
        assert vc.main([bad, "--serve-ready"]) != 0


class TestCancelMidHandoff:
    @pytest.mark.parametrize("extra", [
        {"disagg": {"enabled": True}},
        {"disagg": {"enabled": True, "separate_pools": True}},
    ], ids=["shared_pool", "separate_pools"])
    def test_cancel_pops_handoff_record(self, extra):
        """A request cancelled while its completed prefill waits in the
        handoff queue must take its HandoffRecord with it — a phantom
        record would sit in the queue forever once the scheduler goes
        idle (or resurrect a freed slot at the next claim drain)."""
        from deepspeed_tpu.inference import InferenceEngine, Request
        cfg, params = tiny_gpt2()
        eng = InferenceEngine(cfg, params, dict(INF, **extra),
                              dtype=jnp.float32)
        eng.warmup()
        uids = [eng.submit(Request(prompt=list(p),
                                   max_new_tokens=NEW_TOKENS,
                                   temperature=0.0, seed=0))
                for p in WORKLOAD[:3]]
        eng.step()                      # prefill wave -> records queued
        q = eng._handoff_q
        assert len(q) > 0
        victim = q._q[0].uid
        depth = len(q)
        fin = eng.cancel(victim)
        assert fin is not None and fin.uid == victim
        assert len(q) == depth - 1      # record went with the request
        assert q.pop(victim) is None
        assert q.total_dropped == 1
        # the survivors still finish and the queue fully drains — no
        # phantom claim, no stuck reservation
        done = {}
        while not (eng.scheduler.idle() and len(q) == 0):
            for f in eng.step():
                done[f.uid] = f
        survivors = [u for u in uids if u != victim]
        assert set(done) == set(survivors)
        assert all(len(done[u].tokens) == NEW_TOKENS
                   for u in survivors)
        assert eng.debug_state()["disagg"]["queue"]["depth"] == 0
        assert eng.steady_state_recompiles == 0
        eng.close()


# --------------------------------------------------------------------- #
# shed ladder / routing policy on duck-typed fakes (fleet.py is
# jax-free: policy tests run in microseconds, no device state)
# --------------------------------------------------------------------- #
class _FakeSched:
    def __init__(self):
        self.queue = []
        self.total_tokens = 0
        self.occupancy = 0.0
        self.weight_version = "initial"

    @property
    def queue_depth(self):
        return len(self.queue)

    def active_slots(self):
        return []

    def idle(self):
        return not self.queue


class _FakeEngine:
    """The engine's host-side surface, minus the device."""

    def __init__(self, ttft_samples=(), prefix_hits=0):
        from deepspeed_tpu.utils.monitor import Histogram
        self.scheduler = _FakeSched()
        self.received = []
        self.spec_on = True
        self.monitor = None
        self._log = None
        self.steady_state_recompiles = 0
        tracer = type("T", (), {})()
        tracer.slo_ttft_ms = 100.0
        tracer.hist = {"ttft_ms": Histogram()}
        for v in ttft_samples:
            tracer.hist["ttft_ms"].record(v)
        self._tracer = tracer
        if prefix_hits:
            alloc = type("A", (), {})()
            alloc.match_prefix = lambda p, n=prefix_hits: ([], n)
            self.scheduler.admit_allocator = alloc

    def submit(self, req):
        self.scheduler.queue.append(req)
        self.received.append(req)
        return req.uid

    def step(self):
        from deepspeed_tpu.inference import FinishedRequest
        fins = [FinishedRequest(
            uid=r.uid, prompt=list(r.prompt),
            tokens=[1] * r.max_new_tokens, finish_reason="length",
            ttft_ms=1.0, latency_ms=1.0)
            for r in self.scheduler.queue]
        self.scheduler.queue = []
        self.scheduler.total_tokens += sum(len(f.tokens) for f in fins)
        return fins

    def cancel(self, uid, reason="evicted"):
        from deepspeed_tpu.inference import FinishedRequest
        for i, r in enumerate(self.scheduler.queue):
            if r.uid == uid:
                del self.scheduler.queue[i]
                return FinishedRequest(
                    uid=uid, prompt=list(r.prompt), tokens=[],
                    finish_reason=reason, ttft_ms=None, latency_ms=0.0)
        return None

    def set_speculation(self, on):
        self.spec_on = bool(on)
        return True


def _router(fakes, **slo):
    from deepspeed_tpu.inference import FleetRouter
    cfg = {"replicas": len(fakes)}
    if slo:
        cfg["slo_shed"] = slo
    return FleetRouter(fakes, cfg)


def _req(prompt=(1, 2, 3), priority=0, max_new=8):
    from deepspeed_tpu.inference import Request
    return Request(prompt=list(prompt), max_new_tokens=max_new,
                   temperature=0.0, priority=priority)


class TestShedLadder:
    def test_healthy_fleet_sheds_nothing(self):
        r = _router([_FakeEngine([1.0, 2.0]), _FakeEngine([1.0])],
                    enabled=True, ttft_budget_ms=1000.0, min_samples=1)
        assert r.shed_level() == 0
        uid = r.submit(_req(priority=0))
        fins = r.run()
        assert [f.uid for f in fins] == [uid]
        assert fins[0].finish_reason == "length"
        assert r.total_shed == 0 and r.shed_rate == 0.0

    def test_rung1_rejects_low_tier_only(self):
        fakes = [_FakeEngine([50.0, 60.0]), _FakeEngine([55.0])]
        r = _router(fakes, enabled=True, ttft_budget_ms=10.0,
                    min_samples=1, shed_below_priority=1,
                    degrade_factor=100.0)
        assert r.shed_level() == 1
        lo = r.submit(_req(priority=0))
        hi = r.submit(_req(priority=1))
        fins = {f.uid: f for f in r.run()}
        assert fins[lo].finish_reason == "shed_slo"
        assert fins[lo].tokens == []          # a zero-token ANSWER
        assert fins[hi].finish_reason == "length"
        assert r.shed_by_reason == {"shed_slo": 1}
        assert r.shed_by_priority == {0: 1}
        assert r.shed_rate == 0.5

    def test_rung2_caps_budget_and_disables_spec(self):
        fakes = [_FakeEngine([50.0, 60.0]), _FakeEngine([55.0])]
        r = _router(fakes, enabled=True, ttft_budget_ms=10.0,
                    min_samples=1, shed_below_priority=1,
                    degrade_factor=1.5, degrade_max_new=4)
        assert r.shed_level() == 2
        uid = r.submit(_req(priority=1, max_new=40))
        assert not any(f.spec_on for f in fakes)   # fleet-wide off
        got = [q for f in fakes for q in f.received]
        assert len(got) == 1 and got[0].uid == uid
        assert got[0].max_new_tokens == 4          # capped, same uid
        assert r.total_degraded == 1
        # recovery: budget satisfied again -> ladder disengages and
        # speculation comes back (the plain/spec programs are both
        # warm, so neither transition recompiles)
        r._budget_ms = 1e9
        r.submit(_req(priority=0))
        assert r.shed_level() == 0
        assert all(f.spec_on for f in fakes)
        r.run()

    def test_capacity_shed_when_no_live_replica(self):
        fakes = [_FakeEngine(), _FakeEngine()]
        r = _router(fakes)
        r.drain(0, reason="test")
        r.drain(1, reason="test")
        r.step()                       # both idle -> both retire
        st = r.debug_state()
        assert {x["status"] for x in st["replicas"]} == {"retired"}
        uid = r.submit(_req())
        fins = {f.uid: f for f in r.run()}
        assert fins[uid].finish_reason == "shed_capacity"
        assert fins[uid].tokens == []

    def test_least_loaded_routing(self):
        busy, idle = _FakeEngine(), _FakeEngine()
        busy.scheduler.queue = [_req(), _req()]
        r = _router([busy, idle])
        r.submit(_req())
        assert len(idle.received) == 1 and not busy.received

    def test_prefix_affinity_routing(self):
        from deepspeed_tpu.inference import FleetRouter
        cold, warm = _FakeEngine(), _FakeEngine(prefix_hits=16)
        r = FleetRouter([cold, warm],
                        {"replicas": 2, "routing": "prefix_affinity"})
        r.submit(_req(prompt=list(range(1, 20))))
        assert len(warm.received) == 1 and not cold.received

    def test_drain_redistributes_queued_fakes(self):
        a, b = _FakeEngine(), _FakeEngine()
        r = _router([a, b])
        # pin both requests onto a, then drain it
        b.scheduler.queue = [_req(), _req(), _req()]
        u1 = r.submit(_req())
        u2 = r.submit(_req())
        assert len(a.received) == 2
        b.scheduler.queue = []
        r.drain(0, reason="manual")
        fins = {f.uid: f for f in r.run()}
        assert r.total_redistributed == 2
        assert set(fins) >= {u1, u2}
        assert all(fins[u].finish_reason == "length" for u in (u1, u2))
        st = r.debug_state()
        assert st["replicas"][0]["status"] == "retired"
        assert st["replicas"][0]["drain_reason"] == "manual"


class TestFleetConfig:
    def _cfg(self, **fleet):
        from deepspeed_tpu.runtime.config import get_inference_config
        return get_inference_config({"inference": {"fleet": fleet}})

    def test_defaults(self):
        fl = self._cfg()["fleet"]
        assert fl["replicas"] == 1
        assert fl["routing"] == "least_loaded"
        assert fl["slo_shed"]["enabled"] is False
        assert fl["slo_shed"]["ttft_budget_ms"] is None
        assert fl["slo_shed"]["min_samples"] == 8
        assert fl["slo_shed"]["shed_below_priority"] == 1
        assert fl["slo_shed"]["degrade_factor"] == 2.0
        assert fl["slo_shed"]["degrade_max_new"] == 32
        assert fl["swap"]["verify_integrity"] is True

    def test_rejects_bad_values(self):
        from deepspeed_tpu.runtime.config import DeepSpeedConfigError
        with pytest.raises(DeepSpeedConfigError, match="replicas"):
            self._cfg(replicas=0)
        with pytest.raises(DeepSpeedConfigError, match="routing"):
            self._cfg(routing="round_robin")
        with pytest.raises(DeepSpeedConfigError,
                           match="ttft_budget_ms"):
            self._cfg(slo_shed={"ttft_budget_ms": -1})
        with pytest.raises(DeepSpeedConfigError,
                           match="degrade_factor"):
            self._cfg(slo_shed={"degrade_factor": 0.5})

    def test_router_rejects_empty_fleet(self):
        from deepspeed_tpu.inference import FleetRouter
        with pytest.raises(ValueError, match="at least one"):
            FleetRouter([])


class TestRegistrySync:
    def test_fleet_tags_three_homes(self):
        """One tag, three homes (extends the PR 9 pin to the fleet
        scalars): monitor (canonical), profiling (re-export),
        obs_report (stdlib mirror)."""
        from deepspeed_tpu import profiling as prof
        from deepspeed_tpu.utils import monitor as m
        obs_report = _load_tool("obs_report")
        assert m.TAG_SERVE_SHED_RATE == prof.TAG_SERVE_SHED_RATE == \
            obs_report.T_SHED_RATE == "Serve/shed_rate"
        assert m.TAG_SERVE_FLEET_QDEPTH == \
            prof.TAG_SERVE_FLEET_QDEPTH == \
            obs_report.T_FLEET_QDEPTH == "Serve/fleet_queue_depth"
        assert m.TAG_SERVE_WEIGHT_VERSION == \
            prof.TAG_SERVE_WEIGHT_VERSION == \
            obs_report.T_WEIGHT_VERSION == "Serve/weight_version"
        # ISSUE 16 process-fleet scalars ride the same registry
        assert m.TAG_SERVE_MIGRATIONS == \
            prof.TAG_SERVE_MIGRATIONS == \
            obs_report.T_MIGRATIONS == "Serve/migrations"
        assert m.TAG_SERVE_REPLICA_RESTARTS == \
            prof.TAG_SERVE_REPLICA_RESTARTS == \
            obs_report.T_REPLICA_RESTARTS == "Serve/replica_restarts"

    def test_shed_vocabulary_pinned(self):
        """Every shed decision lands in the trail with a reason from
        this exact vocabulary — dashboards group by these strings."""
        from deepspeed_tpu.inference.tracing import (DEFER_REASONS,
                                                     SHED_REASONS)
        assert SHED_REASONS == ("shed_slo", "shed_capacity",
                                "degrade_max_new", "degrade_spec_off",
                                "drain", "reject_too_long")
        # the serve-trail defer vocabulary is unchanged by the fleet
        assert isinstance(DEFER_REASONS, tuple) and DEFER_REASONS
        assert not set(SHED_REASONS) & set(DEFER_REASONS)


class TestQuantizedSwap:
    """ISSUE 17 satellite: a mid-run weight swap onto an int8-RESIDENT
    replica loads the full-precision checkpoint, re-quantizes, and
    re-places the tree with the warmup programs' exact avals — the
    fleet's zero-recompile live-swap guarantee survives quantized
    serving."""

    def test_swap_onto_int8_resident_replicas(self, tmp_path):
        from deepspeed_tpu.inference import FleetRouter, InferenceEngine
        from deepspeed_tpu.runtime import checkpoint as ckptlib
        from deepspeed_tpu.runtime.quantized_params import \
            is_quantized_tree

        cfg, p1 = tiny_gpt2()
        from deepspeed_tpu.models.gpt2 import init_gpt2_params
        p2 = init_gpt2_params(cfg, jax.random.PRNGKey(7))
        ckroot = str(tmp_path)
        _save_tag(ckptlib, ckroot, "global_step1", p1, 1)
        _save_tag(ckptlib, ckroot, "global_step2", p2, 2)

        qinf = dict(INF, quantize_weights="int8",
                    paged_kv={"kv_dtype": "int8"})

        def serve_once(params):
            eng = InferenceEngine(cfg, params, dict(qinf),
                                  dtype=jnp.float32)
            eng.warmup()
            uids = _submit_all(eng)
            by_uid = {f.uid: f.tokens for f in eng.run()}
            outs = [by_uid[u] for u in uids]
            rc = eng.steady_state_recompiles
            eng.close()
            return outs, rc

        base_q, base_rc = serve_once(p1)
        p2_q, _ = serve_once(p2)
        assert base_rc == 0 and base_q != p2_q

        engines = []
        for _ in range(2):
            eng = InferenceEngine(cfg, p1, dict(qinf),
                                  dtype=jnp.float32)
            eng.warmup()
            assert is_quantized_tree(eng.params)
            engines.append(eng)
        router = FleetRouter(engines, {"replicas": 2})
        try:
            uids = _submit_all(router)
            fins = router.step()
            while len(fins) < 4:            # some answers land pre-swap
                fins.extend(router.step())
            # same weights back: the swap itself must not perturb
            # outputs, and the tree must come back int8-resident
            swap = router.swap_weights(ckroot, tag="global_step1")
            assert swap == {0: "global_step1", 1: "global_step1"}
            fins.extend(router.run())
            by_uid = {f.uid: f.tokens for f in fins}
            assert [by_uid[u] for u in uids] == base_q
            assert len(fins) == len(WORKLOAD)
            for eng in engines:
                assert is_quantized_tree(eng.params)
                assert eng.steady_state_recompiles == 0

            # push genuinely new weights: outputs become the p2
            # quantized reference, still zero recompiles
            uids = _submit_all(router)
            assert router.swap_weights(ckroot) == \
                {0: "global_step2", 1: "global_step2"}
            by_uid = {f.uid: f.tokens for f in router.run()}
            assert [by_uid[u] for u in uids] == p2_q
            for eng in engines:
                assert is_quantized_tree(eng.params)
                assert eng.steady_state_recompiles == 0
        finally:
            router.close()
