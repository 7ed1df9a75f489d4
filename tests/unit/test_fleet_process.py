"""Process-isolated serving fleet (ISSUE 16): RPC replicas, live
KV-page migration, supervised restart, goodput-driven autoscale.

Tier-1 acceptance pins:
- killing a replica CHILD PROCESS mid-decode (env-armed
  ``serve.replica_kill``, fired only while a request holds a pending
  token) preserves every output BITWISE via live page migration — the
  dying child exports each in-flight request's live KV pages in its
  deathbed frame, a survivor imports them and resumes decode at the
  same cache_position, no re-prefill; zero dropped uids, zero
  steady-state recompiles on survivors, the dead child's flight
  recorder salvaged into the router's event trail, and the child
  relaunched under the launcher's 85/87 restart policy;
- the RPC framing / pinned error classification / bounded-backoff retry
  policy is testable jax-free over a socketpair in microseconds;
- ``FleetRouter.drain()`` is idempotent — a double drain is ONE
  episode, exactly one FinishedRequest per uid;
- death supervision honors ``restart_eligible`` (85/87 relaunch,
  anything else retires) and the ``max_restarts`` budget;
- autoscale: sustained shedding spawns a replica, sustained idleness
  drains one, hysteresis + cooldown, never below ``min_replicas``.
"""

import json
import os
import socket
import threading

import numpy as np
import pytest

from deepspeed_tpu.inference import rpc
from deepspeed_tpu.inference.disagg import MigrationRecord
from deepspeed_tpu.inference.rpc import (ReplicaDeadError, RpcClient,
                                         RpcRemoteError, RpcServer,
                                         RpcTimeoutError,
                                         RpcTransportError, ServerExit)
from deepspeed_tpu.runtime import fault

REPO = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))


def _load_tool(name):
    import importlib.util
    spec = importlib.util.spec_from_file_location(
        name, os.path.join(REPO, "tools", f"{name}.py"))
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def _mig_record(uid=7, pages=2, page_bytes=64):
    k = np.arange(2 * pages * 2 * 4 * 4, dtype=np.float32
                  ).reshape(2, pages, 2, 4, 4)
    return MigrationRecord(
        uid=uid, prompt=[1, 2, 3], max_new_tokens=8, temperature=0.5,
        seed=11, eos_id=None, priority=1, position=5, pending_tok=42,
        tokens=[42, 17], live_pages=pages, page_bytes=page_bytes,
        ttft_ms=1.5, queue_wait_ms=0.25, elapsed_ms=3.0,
        kslab=k, vslab=k + 1000.0)


# ===================================================================== #
# wire format (jax-free, socketpair)
# ===================================================================== #

class TestRpcWire:
    def test_frame_roundtrip_with_payload(self):
        a, b = socket.socketpair()
        try:
            rpc.send_frame(a, {"method": "x", "params": {"n": 3}},
                           b"\x00\x01slab")
            head, payload = rpc.recv_frame(b)
            assert head == {"method": "x", "params": {"n": 3}}
            assert payload == b"\x00\x01slab"
        finally:
            a.close()
            b.close()

    def test_eof_is_replica_dead(self):
        a, b = socket.socketpair()
        a.close()
        try:
            with pytest.raises(ReplicaDeadError):
                rpc.recv_frame(b)
        finally:
            b.close()

    def test_desynced_header_is_transport_error(self):
        a, b = socket.socketpair()
        try:
            # garbage bytes parse as an absurd length prefix
            a.sendall(b"\xff\xff\xff\xff\xff\xff\xff\xff")
            with pytest.raises(RpcTransportError):
                rpc.recv_frame(b)
        finally:
            a.close()
            b.close()

    def test_array_codec_roundtrip(self):
        arrays = [np.arange(12, dtype=np.float32).reshape(3, 4),
                  np.array([[1, 2], [3, 4]], dtype=np.int32)]
        metas, blob = rpc.encode_arrays(arrays)
        back = rpc.decode_arrays(metas, blob)
        for orig, got in zip(arrays, back):
            assert got.dtype == orig.dtype and got.shape == orig.shape
            np.testing.assert_array_equal(got, orig)

    def test_array_codec_bfloat16(self):
        # KV slabs ship in the serving dtype; bf16 resolves through
        # ml_dtypes without importing jax
        import ml_dtypes
        a = np.arange(8).astype(ml_dtypes.bfloat16).reshape(2, 4)
        metas, blob = rpc.encode_arrays([a])
        assert metas[0]["dtype"] == "bfloat16"
        (back,) = rpc.decode_arrays(metas, blob)
        np.testing.assert_array_equal(
            back.astype(np.float32), a.astype(np.float32))

    def test_request_wire_roundtrip_keeps_uid_and_seed(self):
        from deepspeed_tpu.inference import Request
        req = Request(prompt=[5, 6, 7], max_new_tokens=9,
                      temperature=0.3, seed=123, priority=2, uid=77)
        back = rpc.request_from_wire(rpc.request_to_wire(req))
        assert (back.uid, back.seed, back.priority) == (77, 123, 2)
        assert back.prompt == [5, 6, 7]
        assert back.max_new_tokens == 9
        assert back.temperature == pytest.approx(0.3)

    def test_migration_wire_roundtrip_bitwise(self):
        rec = _mig_record()
        head, payload = rpc.migration_to_wire(rec)
        back = rpc.migration_from_wire(head, payload)
        assert back.uid == rec.uid and back.position == rec.position
        assert back.pending_tok == rec.pending_tok
        assert back.tokens == rec.tokens
        assert back.live_pages == rec.live_pages
        np.testing.assert_array_equal(back.kslab, rec.kslab)
        np.testing.assert_array_equal(back.vslab, rec.vslab)
        assert back.nbytes == rec.nbytes

    def test_decode_migrations_unpacks_concatenated_deathbed(self):
        r1, r2 = _mig_record(uid=1, pages=1), _mig_record(uid=2,
                                                          pages=3)
        h1, p1 = rpc.migration_to_wire(r1)
        h2, p2 = rpc.migration_to_wire(r2)
        back = rpc.decode_migrations([h1, h2], p1 + p2)
        assert [b.uid for b in back] == [1, 2]
        np.testing.assert_array_equal(back[1].vslab, r2.vslab)


# ===================================================================== #
# client policy: timeout, retry/backoff, per-kind fault points
# ===================================================================== #

def _serve_in_thread(dispatch):
    """An RpcServer on one end of a socketpair, client on the other."""
    a, b = socket.socketpair()
    t = threading.Thread(target=lambda: RpcServer(b).serve(dispatch),
                         daemon=True)
    t.start()
    return a, b, t


class TestRpcClient:
    def test_call_roundtrip_and_payload(self):
        def dispatch(method, params, payload):
            return {"echo": method, "n": params["n"] + 1}, payload * 2
        a, b, t = _serve_in_thread(dispatch)
        try:
            c = RpcClient(a, timeout_s=10.0)
            res, payload = c.call("ping", {"n": 1}, b"xy")
            assert res == {"echo": "ping", "n": 2}
            assert payload == b"xyxy"
            assert c.calls == 1 and c.retried == 0
        finally:
            a.close()
            b.close()

    def test_remote_error_keeps_channel_alive(self):
        def dispatch(method, params, payload):
            if method == "bad":
                raise ValueError("handler exploded")
            return {"ok_method": method}, b""
        a, b, t = _serve_in_thread(dispatch)
        try:
            c = RpcClient(a, timeout_s=10.0)
            with pytest.raises(RpcRemoteError) as ei:
                c.call("bad")
            assert ei.value.kind == "remote"
            # the engine survived the handler failure — next call works
            res, _ = c.call("good")
            assert res == {"ok_method": "good"}
        finally:
            a.close()
            b.close()

    def test_server_exit_replies_then_stops(self):
        def dispatch(method, params, payload):
            raise ServerExit(result={"bye": True}, payload=b"last")
        a, b, t = _serve_in_thread(dispatch)
        try:
            c = RpcClient(a, timeout_s=10.0)
            res, payload = c.call("shutdown")
            assert res == {"bye": True} and payload == b"last"
            t.join(timeout=5.0)
            assert not t.is_alive()
        finally:
            a.close()
            b.close()

    def test_transport_fault_retried_with_exponential_backoff(self):
        def dispatch(method, params, payload):
            return {"served": True}, b""
        a, b, t = _serve_in_thread(dispatch)
        sleeps = []
        try:
            fault.arm("rpc.transport",
                      exc=OSError("injected flake"), times=2)
            c = RpcClient(a, timeout_s=10.0, retries=2, backoff_s=0.05,
                          sleep=sleeps.append)
            res, _ = c.call("step")
            assert res == {"served": True}
            assert c.retried == 2
            assert sleeps == [0.05, 0.1]      # backoff_s * 2**attempt
        finally:
            fault.reset()
            a.close()
            b.close()

    def test_transport_fault_exhausts_retries(self):
        a, b = socket.socketpair()
        try:
            fault.arm("rpc.transport", exc=OSError("flake"), times=99)
            c = RpcClient(a, timeout_s=10.0, retries=1, backoff_s=0.0,
                          sleep=lambda s: None)
            with pytest.raises(RpcTransportError):
                c.call("step")
            assert c.retried == 1
        finally:
            fault.reset()
            a.close()
            b.close()

    @pytest.mark.parametrize("point,err", [
        ("rpc.timeout", RpcTimeoutError),
        ("rpc.replica_dead", ReplicaDeadError),
    ])
    def test_timeout_and_death_are_never_retried(self, point, err):
        a, b = socket.socketpair()
        sleeps = []
        try:
            fault.arm(point, exc=fault.InjectedCrash(point), times=9)
            c = RpcClient(a, timeout_s=10.0, retries=5, backoff_s=0.1,
                          sleep=sleeps.append)
            with pytest.raises(err) as ei:
                c.call("step")
            assert ei.value.kind == point.split(".", 1)[1]
            assert ei.value.method == "step"
            assert sleeps == [] and c.retried == 0
            assert fault.get_injector().fired(point) == 1
        finally:
            fault.reset()
            a.close()
            b.close()

    def test_real_deadline_is_timeout_error(self):
        a, b = socket.socketpair()   # nobody ever replies
        try:
            c = RpcClient(a, timeout_s=0.05, retries=3,
                          sleep=lambda s: None)
            with pytest.raises(RpcTimeoutError):
                c.call("step")
            assert c.retried == 0    # timeouts are terminal, no retry
        finally:
            a.close()
            b.close()


# ===================================================================== #
# death supervision + autoscale on duck-typed fakes (fleet.py is
# jax-free: policy is unit-testable in microseconds)
# ===================================================================== #

class _Events:
    def __init__(self):
        self.rows = []

    def add_event(self, kind, **fields):
        self.rows.append({"event": kind, **fields})

    def kinds(self):
        return [r["event"] for r in self.rows]

    def of(self, kind):
        return [r for r in self.rows if r["event"] == kind]


class _FakeSched:
    def __init__(self):
        self.queue = []
        self.total_tokens = 0
        self.occupancy = 0.0

    @property
    def queue_depth(self):
        return len(self.queue)

    def active_slots(self):
        return []

    def idle(self):
        return not self.queue


class _FakeProcEngine:
    """The ReplicaProcess surface the router supervises: dies on
    command with a deathbed ReplicaDeadError, then supports
    poll_exit/orphans/relaunch."""

    def __init__(self, exit_code=85, relaunch_ok=True,
                 can_migrate=False):
        self.scheduler = _FakeSched()
        self.exit_code = exit_code
        self.relaunch_ok = relaunch_ok
        self.can_migrate = can_migrate
        self.die_next_step = False
        self.deathbed_exports = []
        self.relaunches = 0
        self.imported = []
        self.flight_path = None
        self.pid = 4242
        self.monitor = None
        self._log = None
        self.steady_state_recompiles = 0
        self.weight_version = "initial"
        self.weight_ordinal = 0

    def submit(self, req):
        self.scheduler.queue.append(req)
        return req.uid

    def step(self):
        from deepspeed_tpu.inference import FinishedRequest
        if self.die_next_step:
            self.die_next_step = False
            # mirror ReplicaProcess._call: deathbed-exported uids answer
            # through migration, never through orphans()
            gone = {r.uid for r in self.deathbed_exports}
            self.scheduler.queue = [r for r in self.scheduler.queue
                                    if r.uid not in gone]
            raise ReplicaDeadError(
                "fake child died", exports=list(self.deathbed_exports),
                reason="kill")
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
        return False

    def poll_exit(self, timeout_s=10.0):
        return self.exit_code

    def orphans(self):
        return list(self.scheduler.queue)

    def relaunch(self):
        if not self.relaunch_ok:
            raise OSError("spawn failed")
        self.relaunches += 1
        self.scheduler = _FakeSched()

    def import_request(self, rec):
        if not self.can_migrate:
            return None
        from deepspeed_tpu.inference import Request
        self.imported.append(rec)
        self.scheduler.queue.append(Request(
            prompt=list(rec.prompt),
            max_new_tokens=rec.max_new_tokens,
            temperature=rec.temperature, seed=rec.seed,
            eos_id=rec.eos_id, priority=rec.priority, uid=rec.uid))
        return len(self.imported) - 1


def _req(uid, prompt=(1, 2, 3), max_new=4):
    from deepspeed_tpu.inference import Request
    return Request(prompt=list(prompt), max_new_tokens=max_new,
                   temperature=0.0, seed=0, uid=uid)


def _router(engines, fleet_config=None, **kw):
    from deepspeed_tpu.inference import FleetRouter
    ev = _Events()
    return FleetRouter(engines, fleet_config or {}, writer=ev,
                       **kw), ev


class TestDeathSupervision:
    def test_exit_85_relaunches_and_redistributes(self):
        dying = _FakeProcEngine(exit_code=85)
        survivor = _FakeProcEngine()
        router, ev = _router([dying, survivor],
                             {"process_mode": {"max_restarts": 1,
                                               "restart_backoff_s": 0.5}},
                             sleep=lambda s: None)
        uids = [router.submit(_req(u)) for u in range(4)]
        dying.die_next_step = True
        fins = router.run()
        # zero dropped, exactly one answer per uid — the dead child's
        # queued requests moved to the survivor with the same uids
        assert sorted(f.uid for f in fins) == sorted(uids)
        r0 = router.replicas[0]
        assert r0.status == "live" and r0.restarts == 1
        assert r0.last_exit_code == 85
        assert dying.relaunches == 1
        assert router.total_restarts == 1
        death = ev.of("fleet_replica_death")
        assert death and death[0]["exit_code"] == 85
        restart = ev.of("fleet_replica_restart")
        assert restart[0]["decision"] == "restarted"
        assert restart[0]["backoff_s"] == pytest.approx(0.5)
        # relaunched replica serves again
        more = [router.submit(_req(u)) for u in (10, 11)]
        fins2 = router.run()
        assert sorted(f.uid for f in fins2) == sorted(more)

    @pytest.mark.parametrize("code", [87])
    def test_exit_87_is_restart_eligible(self, code):
        dying = _FakeProcEngine(exit_code=code)
        router, ev = _router([dying, _FakeProcEngine()],
                             {"process_mode": {"max_restarts": 1,
                                               "restart_backoff_s": 0.0}})
        router.submit(_req(0))
        dying.die_next_step = True
        router.run()
        assert router.replicas[0].status == "live"
        assert dying.relaunches == 1

    @pytest.mark.parametrize("code", [1, 143, None])
    def test_non_resumable_exit_gives_up(self, code):
        dying = _FakeProcEngine(exit_code=code)
        router, ev = _router([dying, _FakeProcEngine()],
                             {"process_mode": {"max_restarts": 3,
                                               "restart_backoff_s": 0.0}})
        uids = [router.submit(_req(u)) for u in range(2)]
        dying.die_next_step = True
        fins = router.run()
        assert sorted(f.uid for f in fins) == sorted(uids)  # no drops
        assert router.replicas[0].status == "retired"
        assert dying.relaunches == 0
        assert ev.of("fleet_replica_restart")[0]["decision"] == \
            "give_up"

    def test_restart_budget_exhausts(self):
        dying = _FakeProcEngine(exit_code=85)
        router, ev = _router([dying, _FakeProcEngine()],
                             {"process_mode": {"max_restarts": 0}})
        router.submit(_req(0))
        dying.die_next_step = True
        router.run()
        assert router.replicas[0].status == "retired"
        assert ev.of("fleet_replica_restart")[0]["decision"] == \
            "exhausted"

    def test_deathbed_exports_resume_on_survivor(self):
        rec = _mig_record(uid=5)
        dying = _FakeProcEngine(exit_code=85, relaunch_ok=False)
        dying.deathbed_exports = [rec]
        survivor = _FakeProcEngine(can_migrate=True)
        router, ev = _router(
            [dying, survivor],
            {"process_mode": {"max_restarts": 1,
                              "restart_backoff_s": 0.0}})
        router.submit(_req(5))
        dying.die_next_step = True
        fins = router.run()
        # the export landed on the survivor (no resubmit fallback)
        assert [r.uid for r in survivor.imported] == [5]
        assert router.total_migrated == 1
        assert router.migration_bytes == rec.nbytes
        assert [f.uid for f in fins] == [5]
        mig = ev.of("serve_migration")
        assert mig and mig[0]["uid"] == 5 and mig[0]["dst"] == 1
        # per-replica ledger feeds the fleet_replica_state rows
        assert router.replicas[0].migrations_out == 1
        assert router.replicas[1].migrations_in == 1
        # relaunch failed -> stays retired, event says so
        assert router.replicas[0].status == "retired"
        assert ev.of("fleet_replica_restart")[0]["decision"] == \
            "failed"

    def test_flight_recorder_salvaged(self, tmp_path):
        flight = tmp_path / "flight_serve.json"
        flight.write_text(json.dumps(
            {"trigger": "replica_death", "pid": 999,
             "reason": "kill", "rows": [{"kind": "heartbeat"}] * 3}))
        dying = _FakeProcEngine(exit_code=1)
        dying.flight_path = str(flight)
        router, ev = _router([dying, _FakeProcEngine()])
        router.submit(_req(0))
        dying.die_next_step = True
        router.run()
        assert router.total_salvaged == 1
        sal = ev.of("fleet_flight_salvage")
        assert sal[0]["replica"] == 0
        assert sal[0]["trigger"] == "replica_death"
        assert sal[0]["dead_pid"] == 999 and sal[0]["rows"] == 3

    def test_torn_flight_file_salvages_nothing(self, tmp_path):
        flight = tmp_path / "flight_serve.json"
        flight.write_text('{"trigger": "repl')   # torn write
        dying = _FakeProcEngine(exit_code=1)
        dying.flight_path = str(flight)
        router, ev = _router([dying, _FakeProcEngine()])
        router.submit(_req(0))
        dying.die_next_step = True
        router.run()
        assert router.total_salvaged == 0
        assert not ev.of("fleet_flight_salvage")


class TestDrainIdempotent:
    def test_double_drain_is_one_episode(self):
        """Bugfix pin: drain() called twice on the same replica must
        not restart the episode or redistribute twice — exactly one
        FinishedRequest per uid, one fleet_drain begin row."""
        fakes = [_FakeProcEngine(), _FakeProcEngine()]
        router, ev = _router(fakes)
        uids = [router.submit(_req(u)) for u in range(4)]
        router.drain(0, reason="manual")
        router.drain(0, reason="manual")        # idempotent: no-op
        fins = router.run()
        assert sorted(f.uid for f in fins) == sorted(uids)
        assert len(fins) == len(uids)           # EXACTLY one per uid
        begins = [r for r in ev.of("fleet_drain")
                  if r["phase"] == "begin"]
        assert len(begins) == 1
        assert router.replicas[0].status == "retired"
        # draining a retired replica is also a no-op
        router.drain(0)
        assert router.replicas[0].status == "retired"
        assert len([r for r in ev.of("fleet_drain")
                    if r["phase"] == "begin"]) == 1


class TestAutoscale:
    ASC = {"enabled": True, "min_replicas": 1, "max_replicas": 3,
           "scale_up_patience": 2, "scale_down_patience": 3,
           "cooldown_steps": 0}

    def test_sustained_shed_spawns_replica(self):
        spawned = []

        def factory(idx):
            e = _FakeProcEngine()
            spawned.append(idx)
            return e

        router, ev = _router([_FakeProcEngine()],
                             {"autoscale": dict(self.ASC)},
                             replica_factory=factory)
        router.shed_level = lambda: 1            # pin the ladder hot
        router.step()
        assert spawned == []                     # patience: not yet
        router.step()
        assert spawned == [1]                    # streak hit patience
        assert len(router.replicas) == 2
        up = ev.of("fleet_autoscale")
        assert up[0]["action"] == "up" and up[0]["replica"] == 1

    def test_scale_up_respects_max_replicas(self):
        router, ev = _router(
            [_FakeProcEngine() for _ in range(3)],
            {"autoscale": dict(self.ASC)},
            replica_factory=lambda i: _FakeProcEngine())
        router.shed_level = lambda: 2
        # pin one replica busy so the idle rung never competes
        router.replicas[0].engine.scheduler.active_slots = lambda: [1]
        for _ in range(8):
            router.step()
        assert len(router.replicas) == 3         # already at max
        assert not ev.of("fleet_autoscale")

    def test_sustained_idle_drains_one_never_below_min(self):
        router, ev = _router([_FakeProcEngine(), _FakeProcEngine()],
                             {"autoscale": dict(self.ASC)})
        for _ in range(10):
            router.step()
        live = [r for r in router.replicas if r.status == "live"]
        assert len(live) == 1                    # one drained away...
        downs = ev.of("fleet_autoscale")
        assert downs and downs[0]["action"] == "down"
        for _ in range(10):
            router.step()
        live = [r for r in router.replicas if r.status == "live"]
        assert len(live) == 1                    # ...but never below min

    def test_cooldown_spaces_actions(self):
        asc = dict(self.ASC, cooldown_steps=5, scale_up_patience=1,
                   max_replicas=4)
        router, ev = _router([_FakeProcEngine()],
                             {"autoscale": asc},
                             replica_factory=lambda i:
                             _FakeProcEngine())
        router.shed_level = lambda: 1
        for _ in range(6):
            router.step()
        # 6 steps, patience 1, cooldown 5: one spawn, not five
        assert len(ev.of("fleet_autoscale")) == 1

    def test_disabled_by_default(self):
        router, ev = _router([_FakeProcEngine(), _FakeProcEngine()])
        for _ in range(100):
            router.step()
        assert not ev.of("fleet_autoscale")
        assert all(r.status == "live" for r in router.replicas)


class TestProcessModeConfig:
    def test_defaults(self):
        from deepspeed_tpu.runtime.config import get_inference_config
        fl = get_inference_config({"inference": {}})["fleet"]
        pm = fl["process_mode"]
        assert pm["enabled"] is False
        assert pm["max_restarts"] == 1
        assert pm["rpc_retries"] == 2
        asc = fl["autoscale"]
        assert asc["enabled"] is False
        assert asc["min_replicas"] == 1
        assert asc["max_replicas"] == 4
        assert asc["scale_up_patience"] < asc["scale_down_patience"]

    @pytest.mark.parametrize("section,bad", [
        ("process_mode", {"rpc_timeout_s": 0}),
        ("process_mode", {"rpc_retries": -1}),
        ("process_mode", {"max_restarts": -2}),
        ("autoscale", {"min_replicas": 0}),
        ("autoscale", {"min_replicas": 3, "max_replicas": 2}),
        ("autoscale", {"scale_up_patience": 0}),
        ("autoscale", {"cooldown_steps": -1}),
    ])
    def test_rejects_bad_values(self, section, bad):
        from deepspeed_tpu.runtime.config import (DeepSpeedConfigError,
                                                  get_inference_config)
        with pytest.raises(DeepSpeedConfigError):
            get_inference_config(
                {"inference": {"fleet": {section: bad}}})


# ===================================================================== #
# the real thing: child processes, kill mid-decode, live migration
# ===================================================================== #

MCFG = {"vocab_size": 61, "max_position_embeddings": 64,
        "hidden_size": 32, "num_layers": 2, "num_heads": 4,
        "embd_dropout": 0.0, "attn_dropout": 0.0, "resid_dropout": 0.0}
ICFG = {"max_batch_size": 2, "prompt_buckets": [8, 16],
        "batch_buckets": [1, 2], "max_seq_len": 48}


def _mixed_requests(uids):
    """Half greedy, half seeded-sampled — migration must preserve both
    bitwise (sampling keys fold in the absolute position, so a resumed
    decode draws the same tokens)."""
    from deepspeed_tpu.inference import Request
    return [Request(prompt=[1 + u, 2, 3, 4, (5 + u) % 61],
                    max_new_tokens=8,
                    temperature=0.0 if i % 2 == 0 else 0.7,
                    seed=100 + u, uid=u)
            for i, u in enumerate(uids)]


@pytest.fixture(scope="module")
def proc_fleet_run(tmp_path_factory):
    """One expensive end-to-end run shared by the assertions below:
    3 replica children; child 0 armed to crash mid-decode (phase A),
    then a double-drain of child 1 mid-decode (phase B)."""
    import jax
    import jax.numpy as jnp

    from deepspeed_tpu.inference import InferenceEngine
    from deepspeed_tpu.inference.fleet import (FleetRouter,
                                               launch_replica_processes)
    from deepspeed_tpu.models.gpt2 import GPT2Config, init_gpt2_params
    from deepspeed_tpu.utils.monitor import _JsonlWriter

    cfg = GPT2Config(**MCFG)
    params = init_gpt2_params(cfg, jax.random.PRNGKey(3))

    # single-engine baseline, same uids/seeds/temps
    eng = InferenceEngine(cfg, params, ICFG, dtype=jnp.float32)
    eng.warmup()
    for r in _mixed_requests(range(4)):
        eng.submit(r)
    base_a = {f.uid: tuple(f.tokens) for f in eng.run()}
    for r in _mixed_requests(range(10, 14)):
        eng.submit(r)
    base_b = {f.uid: tuple(f.tokens) for f in eng.run()}
    eng.close()

    fdir = str(tmp_path_factory.mktemp("flights"))
    evdir = str(tmp_path_factory.mktemp("fleet_proc_events"))
    rbase = str(tmp_path_factory.mktemp("fleet_proc_replica_events"))
    hdir = str(tmp_path_factory.mktemp("fleet_proc_router_health"))
    # children must sample from the SAME prng stream as this process:
    # conftest.py flips jax_threefry_partitionable via jax.config (an
    # in-process setting a spawned child never sees), so mirror it as
    # an env var — XLA_FLAGS (8-device host platform) already inherits
    # through os.environ. Without this the baseline and the replicas
    # draw different tokens for every temperature>0 request.
    env = {"JAX_PLATFORMS": "cpu", "JAX_THREEFRY_PARTITIONABLE": "1"}
    kill_env = dict(env, DSTPU_FAULT_ARM="serve.replica_kill:crash:1")
    spec = {"family": "gpt2", "model_config": MCFG, "init_seed": 3,
            "dtype": "float32", "inference": ICFG}
    # fleet tracing fully ON (ISSUE 18): each child writes its own
    # serve trail (per-replica events.jsonl) stamped with replica_id;
    # the bitwise-parity assertions below double as the tracing-
    # enabled zero-perturbation pin
    obs = lambda i: {  # noqa: E731
        "observability": {
            "enabled": True, "serve": {"enabled": True},
            "health": {
                "enabled": True,
                "flight_path": os.path.join(fdir, f"flight_r{i}.json")}},
        "inference": dict(ICFG, events_dir=os.path.join(rbase,
                                                        f"r{i}"))}
    reps = launch_replica_processes(
        spec, 3, env_by_replica={0: kill_env, 1: env, 2: env},
        spec_by_replica={i: obs(i) for i in range(3)})
    writer = _JsonlWriter(evdir)
    # the router owns its own HealthPlane in process mode (children's
    # planes live across the process boundary) — its rpc_call beats
    # name which replica each blocking wait was on
    from deepspeed_tpu.utils.health import HealthPlane
    hp = HealthPlane({"enabled": True, "stall_timeout_s": 300.0},
                     events_dir=hdir)
    router = FleetRouter(
        reps, {"process_mode": {"enabled": True, "max_restarts": 1,
                                "restart_backoff_s": 0.0}},
        writer=writer, health=hp)
    out = {"evdir": evdir, "fdir": fdir, "base_a": base_a,
           "base_b": base_b,
           "rdirs": [os.path.join(rbase, f"r{i}") for i in range(3)]}
    try:
        out["pid0_before"] = reps[0].pid
        # the armed kill must fire exactly once: relaunch re-merges
        # _env into the child environment, so drop the arm now or the
        # phase-A replacement child re-arms and dies again in phase B
        reps[0]._env.pop("DSTPU_FAULT_ARM", None)
        # ---- phase A: armed child 0 crashes at its first mid-decode
        # step; deathbed exports migrate, child relaunches
        uids_a = [router.submit(r) for r in _mixed_requests(range(4))]
        fins_a = router.run()
        out["uids_a"] = uids_a
        out["fins_a"] = [(f.uid, tuple(f.tokens), f.finish_reason)
                         for f in fins_a]
        out["migrated_a"] = router.total_migrated
        out["restarts"] = router.total_restarts
        out["salvaged"] = router.total_salvaged
        out["r0"] = (router.replicas[0].status,
                     router.replicas[0].last_exit_code,
                     router.replicas[0].restarts)
        out["pid0_after"] = reps[0].pid
        # ---- phase B: drain replica 1 mid-decode, twice (idempotent);
        # its in-flight requests migrate over the RPC channel
        uids_b = [router.submit(r)
                  for r in _mixed_requests(range(10, 14))]
        fins_b = list(router.step())     # prefills land, decode starts
        router.drain(1, reason="manual")
        router.drain(1, reason="manual")          # must be a no-op
        fins_b += router.run()
        out["uids_b"] = uids_b
        out["fins_b"] = [(f.uid, tuple(f.tokens), f.finish_reason)
                         for f in fins_b]
        out["migrated_b"] = router.total_migrated
        out["migration_bytes"] = router.migration_bytes
        out["recompiles"] = [r.steady_state_recompiles for r in reps]
        out["statuses"] = [r.status for r in router.replicas]
        out["debug"] = router.debug_state()
    finally:
        router.close()
        writer.close()
        hp.close()
    rows = [json.loads(l) for l in
            open(os.path.join(evdir, "events.jsonl")) if l.strip()]
    out["events"] = rows
    return out


class TestProcessFleetKill:
    def test_child_really_died_and_relaunched(self, proc_fleet_run):
        status, exit_code, restarts = proc_fleet_run["r0"]
        assert exit_code == 85            # deathbed exit: resumable
        assert status == "live" and restarts == 1
        assert proc_fleet_run["restarts"] == 1
        # a NEW process, not a revived socket
        assert proc_fleet_run["pid0_after"] != \
            proc_fleet_run["pid0_before"]

    def test_kill_mid_decode_outputs_bitwise_zero_dropped(
            self, proc_fleet_run):
        got = {u: t for u, t, _ in proc_fleet_run["fins_a"]}
        assert sorted(got) == sorted(proc_fleet_run["uids_a"])
        assert len(proc_fleet_run["fins_a"]) == \
            len(proc_fleet_run["uids_a"])       # exactly one per uid
        assert got == proc_fleet_run["base_a"]  # BITWISE
        assert proc_fleet_run["migrated_a"] >= 1

    def test_double_drain_migrates_in_flight_bitwise(
            self, proc_fleet_run):
        got = {u: t for u, t, _ in proc_fleet_run["fins_b"]}
        assert sorted(got) == sorted(proc_fleet_run["uids_b"])
        assert len(proc_fleet_run["fins_b"]) == \
            len(proc_fleet_run["uids_b"])
        assert got == proc_fleet_run["base_b"]
        # drain moved live pages (phase B migrated on top of phase A)
        assert proc_fleet_run["migrated_b"] > \
            proc_fleet_run["migrated_a"]
        assert proc_fleet_run["statuses"][1] == "retired"
        begins = [r for r in proc_fleet_run["events"]
                  if r.get("event") == "fleet_drain"
                  and r.get("phase") == "begin"
                  and r.get("replica") == 1]
        assert len(begins) == 1           # double drain, ONE episode

    def test_zero_steady_state_recompiles(self, proc_fleet_run):
        # migration import/export ran from the warmed program set on
        # every replica — including the relaunched child
        assert proc_fleet_run["recompiles"] == [0, 0, 0]

    def test_flight_recorder_salvaged_into_router_trail(
            self, proc_fleet_run):
        assert proc_fleet_run["salvaged"] == 1
        sal = [r for r in proc_fleet_run["events"]
               if r.get("event") == "fleet_flight_salvage"]
        assert sal and sal[0]["replica"] == 0
        assert sal[0]["trigger"] == "replica_death"
        # the black box itself: written by the dying child
        flight = json.load(open(
            os.path.join(proc_fleet_run["fdir"], "flight_r0.json")))
        assert flight["trigger"] == "replica_death"
        assert flight["reason"].startswith("InjectedCrash")

    def test_event_trail_and_obs_report(self, proc_fleet_run):
        kinds = {r.get("event") for r in proc_fleet_run["events"]}
        assert {"fleet_replica_death", "fleet_replica_restart",
                "serve_migration", "fleet_replica_state",
                "fleet_state"} <= kinds
        mig = [r for r in proc_fleet_run["events"]
               if r.get("event") == "serve_migration"]
        assert all(r["nbytes"] > 0 and r["pages"] >= 1 for r in mig)
        obs_report = _load_tool("obs_report")
        s = obs_report.summarize(proc_fleet_run["evdir"])
        proc = s["serving"]["fleet"]["process"]
        assert proc is not None
        assert proc["migrations"]["count"] == \
            proc_fleet_run["migrated_b"]
        assert proc["migrations"]["bytes"] == \
            proc_fleet_run["migration_bytes"]
        assert proc["restarts"] == 1
        assert proc["deaths"] == 1 and proc["salvaged_flights"] == 1
        by_idx = {r["replica"]: r for r in proc["replicas"]}
        assert by_idx[0]["restarts"] == 1
        assert by_idx[0]["last_exit_code"] == 85
        assert by_idx[0]["pid"] is not None
        text = obs_report.render_serve(s)
        assert "process_fleet" in text and "migration" in text
        assert obs_report.main([proc_fleet_run["evdir"],
                                "--serve"]) == 0
        assert obs_report.main([proc_fleet_run["evdir"],
                                "--json"]) == 0

    def test_migration_ledger_in_debug_state(self, proc_fleet_run):
        dbg = proc_fleet_run["debug"]
        assert dbg["migrations"]["total"] == \
            proc_fleet_run["migrated_b"]
        assert dbg["migrations"]["bytes"] > 0
        assert dbg["restarts"] == 1
        assert dbg["salvaged_flights"] == 1


# ===================================================================== #
# fleet-wide distributed tracing (ISSUE 18)
# ===================================================================== #

class TestFleetTracing:
    def test_every_dispatch_carries_a_trace_id(self, proc_fleet_run):
        disp = [r for r in proc_fleet_run["events"]
                if r.get("event") == "fleet_dispatch"]
        assert disp
        assert all(r.get("trace_id") for r in disp)
        by_uid = {}
        for r in disp:
            by_uid.setdefault(r["uid"], set()).add(r["trace_id"])
        # one trace id per client request, however many reroutes
        assert all(len(ids) == 1 for ids in by_uid.values())

    def test_clock_sync_rows_cover_the_fleet(self, proc_fleet_run):
        cs = [r for r in proc_fleet_run["events"]
              if r.get("event") == "clock_sync"]
        # initial sync at launch covers every replica; the post-
        # relaunch re-sync adds more rows
        assert {r["replica"] for r in cs} == {0, 1, 2}
        assert all(r["rtt_ms"] > 0 and r["uncertainty_ms"] >= 0
                   and r["uncertainty_ms"] <= r["rtt_ms"]
                   for r in cs)
        # tiny-model CPU children share our wall clock: the estimated
        # offset must be bounded by the RTT (sanity, not precision)
        assert all(abs(r["offset_ms"]) <= r["rtt_ms"] + 50.0
                   for r in cs)

    def test_migration_rows_share_the_trace_id(self, proc_fleet_run):
        mig = [r for r in proc_fleet_run["events"]
               if r.get("event") == "serve_migration"]
        assert mig and all(r.get("trace_id") for r in mig)

    def test_end_to_end_lineage_single_timeline(self, proc_fleet_run):
        """The acceptance pin: the kill-mid-decode request's scattered
        rows (router log + dead child's log + survivor's log) merge
        into ONE timeline under ONE trace id — submit, prefill on the
        dead replica, migrate_out/migrate_in pair, decode on the
        survivor, finish — with the latency decomposition summing
        exactly."""
        obs_report = _load_tool("obs_report")
        s = obs_report.summarize_fleet(
            [proc_fleet_run["evdir"]] + proc_fleet_run["rdirs"])
        assert s["fleet_schema"] == 1
        # clock offsets were recorded for every replica
        assert set(s["clock_offsets"]) == {"0", "1", "2"}
        migrated = [r for r in s["requests"]
                    if r["migrations"]
                    and any("migrate_out" in h for h in r["hops"])]
        assert migrated, [r["path"] for r in s["requests"]]
        r = migrated[0]
        hops = r["hops"]
        # hop 0: submitted + prefilled on the replica that died
        assert hops[0]["hop"] == 0
        assert hops[0].get("t_submit") is not None
        assert "migrate_out" in hops[0]
        # final hop: resumed and finished on a DIFFERENT replica
        assert hops[-1]["hop"] >= 1
        assert "migrate_in" in hops[-1]
        assert "finish" in hops[-1]
        assert hops[-1]["replica"] != hops[0]["replica"]
        # the migration hop is priced (LinkModel) on the router spine
        assert r["migration_priced_ms"] >= 0.0
        assert r["migrations"][0]["nbytes"] > 0
        # decomposition sums exactly: queue_wait + prefill == ttft
        # (no disagg handoff here) up to the tracer's independent
        # 3-decimal rounding of each term, ttft + decode == latency
        assert r["decomp_exact"] is True
        assert abs(r["replica_queue_ms"] + r["prefill_ms"]
                   - r["ttft_ms"]) < 2e-3
        assert abs(r["ttft_ms"] + r["decode_ms"]
                   - r["latency_ms"]) < 1e-3
        assert r["flags"] == []
        assert s["missing_replica_logs"] == []

    def test_fleet_cli_and_merged_chrome_trace(self, proc_fleet_run,
                                               tmp_path):
        obs_report = _load_tool("obs_report")
        out = str(tmp_path / "fleet_trace.json")
        argv = ["--fleet", proc_fleet_run["evdir"],
                *proc_fleet_run["rdirs"], "--trace-out", out]
        assert obs_report.main(argv) == 0
        assert obs_report.main(argv[:-2] + ["--json"]) == 0
        trace = json.load(open(out))
        meta = [e for e in trace["traceEvents"] if e.get("ph") == "M"]
        names = {e["args"]["name"] for e in meta}
        assert "router" in names
        assert any(n.startswith("replica ") for n in names)
        # one process lane per replica: distinct pids
        pids = {e["pid"] for e in meta}
        assert len(pids) == len(meta)
        assert any(e.get("ph") == "X" for e in trace["traceEvents"])

    def test_rpc_call_beats_reached_the_router_health_plane(
            self, proc_fleet_run):
        # the watchdog never tripped (no stall rows), but the phase
        # vocabulary accepted rpc_call beats throughout the run —
        # a rename would have raised inside the fixture
        stalls = [r for r in proc_fleet_run["events"]
                  if r.get("event") == "stall_detected"]
        assert stalls == []
