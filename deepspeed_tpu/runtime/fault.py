"""Fault-injection harness for checkpoint durability testing.

On preemptible TPU pods a crash mid-save is the *expected* failure mode
(ISSUE: the reference treats checkpoints as the recovery backbone,
engine.py:1329/:1173). This module provides the monkeypatch-free shim the
checkpoint layer is instrumented with: production code calls
``fire("<point>")`` at named fault points (a no-op unless a test armed
that point), tests arm points to simulate torn writes, crash-after-shard,
transient ``OSError`` flakes, and bit-flips, then prove resume survives.

Fault points instrumented in the save path (see ``runtime/checkpoint.py``
and ``engine.save_checkpoint``):

- ``io_write``                 : inside every atomic file write, before any
                                 bytes hit disk (arm with ``OSError`` to
                                 simulate GCS/NFS flakes; retried)
- ``ckpt.snapshot``            : at the device->host snapshot that opens
                                 every save — kill here and NOTHING of the
                                 save exists on disk
- ``ckpt.after_shard``         : after one pytree's shard files are written
                                 (ctx: ``name``) — crash-after-shard-0
- ``ckpt.before_marker``       : all shards + meta written, COMMITTED not
- ``ckpt.before_rename``       : COMMITTED written, tmp dir not yet renamed
- ``ckpt.latest_tmp_written``  : ``latest.tmp`` durable, ``os.replace``
                                 not yet executed — torn-latest window
- ``ckpt.writer_crash``        : in the async checkpoint writer thread, at
                                 job start — a stored writer exception must
                                 surface on the next save/close, never die
                                 silently
- ``elastic.sigterm_mid_window``: at the top of every ``train_batch``
                                 window — arm a callback that delivers
                                 SIGTERM (or triggers the software
                                 preemption) to prove the in-flight window
                                 still finishes before the drain

Serve-plane points (ISSUE 14 — ``inference/engine.py`` and
``inference/fleet.py``; the fleet tests arm them through the same env
grammar):

- ``serve.swap_load``          : in ``engine.swap_params``, after the tag
                                 pre-flight and BEFORE the params load —
                                 arm ``oserror``/``crash`` to prove a
                                 failed mid-swap load leaves the replica
                                 serving the OLD weights (swap is
                                 atomic-or-rollback, never half-loaded)
- ``serve.replica_preempt``    : once per live replica per router step
                                 (ctx: ``replica``) — a raised injection
                                 preempts THAT replica (drain +
                                 redistribute); the ``preempt`` action
                                 instead flags every installed
                                 PreemptionGuard, same as a real SIGTERM
- ``serve.dispatch``           : in the router's dispatch of one request
                                 to its chosen replica (ctx: ``replica``,
                                 ``uid``) — a transient failure here must
                                 reroute the request to the next-best
                                 replica, never drop it

RPC-plane points (ISSUE 16 — ``inference/rpc.py`` client and the
``replica_worker`` child; one point per pinned error-classification kind so a
test targets exactly one failure mode):

- ``rpc.transport``            : at the top of every RPC call attempt
                                 (ctx: ``method``, ``name``) — raises
                                 surface as ``RpcTransportError``, the
                                 TRANSIENT kind the client retries with
                                 bounded exponential backoff
- ``rpc.timeout``              : same site — raises surface as
                                 ``RpcTimeoutError`` (per-call deadline
                                 exceeded; never retried, the call may
                                 have been applied)
- ``rpc.replica_dead``         : same site — raises surface as
                                 ``ReplicaDeadError`` (peer gone;
                                 terminal for the connection — the
                                 router salvages/migrates/relaunches)
- ``serve.replica_kill``       : in the replica worker's step handler,
                                 fired ONLY while a request is
                                 mid-decode (ctx: ``pid``) — the
                                 env-armed kill test's hook: ``crash``
                                 triggers the deathbed protocol (export
                                 live pages, dump flight.json, exit 85)
                                 at the worst possible moment

Health-plane points (ISSUE 15 — ``utils/health.py`` watchdog and
detectors; process-boundary-testable like the supervisor tests):

- ``health.stall``             : at the top of every ``train_batch``
                                 window, right after the heartbeat — arm
                                 the ``stall`` env action (or a sleeping
                                 callback) to wedge the step loop past
                                 ``stall_timeout_s`` and prove the
                                 watchdog dumps flight.json + stacks and
                                 emits ``stall_detected``
- ``health.nan_loss``          : at the monitor-flush barrier where each
                                 deferred loss is materialized host-side
                                 (ctx: ``step``) — arm ``crash`` and the
                                 engine poisons THAT loss value to NaN
                                 (telemetry only, params untouched) to
                                 prove the nonfinite-streak detector
                                 emits its pinned ``health`` row

``retry_io`` is the exponential-backoff wrapper used around all checkpoint
I/O; it retries ``OSError`` (transient filesystem flakes) but never
``InjectedCrash`` (a simulated process death must kill the save).

Env-armed injections (``DSTPU_FAULT_ARM``): a *relaunched* process — the
launcher supervisor's child, which no in-process test can reach — arms
itself at engine init from the environment. Grammar (comma-separated)::

    point:action[:times][@once_file]

with actions ``crash`` (raise InjectedCrash), ``oserror`` (raise OSError),
``sigterm`` (deliver a real SIGTERM to this process), ``preempt`` (flag
the installed PreemptionGuards via ``elastic.request_preemption``), and
``stall`` (sleep ``DSTPU_FAULT_STALL_S`` seconds — default 30 — inside
the fault point, wedging the caller past the health watchdog's timeout).
``@once_file`` makes the arm cross-process-one-shot: the spec only arms
while the file exists and the first fire deletes it, so a supervisor
relaunch with the *same* environment is not re-faulted forever.
"""

import os
import time
import zlib
from typing import Any, Callable, Dict, List, Optional

__all__ = [
    "InjectedCrash", "FaultInjector", "get_injector", "fire", "arm",
    "reset", "retry_io", "flip_byte", "truncate_file", "crc32_file",
    "arm_from_env", "ENV_ARM",
]

ENV_ARM = "DSTPU_FAULT_ARM"


class InjectedCrash(Exception):
    """Simulated process death at a named fault point.

    Deliberately NOT an ``OSError``: the retry wrapper must never swallow
    it — a preemption does not come back for attempt two.
    """


class FaultInjector:
    """Registry of armed fault points.

    ``arm(point, ...)`` installs an action; instrumented code calls
    ``fire(point, **ctx)`` which is a no-op unless that point is armed.
    An armed point fires at most ``times`` times (None = unlimited) and
    only when ``filter(**ctx)`` (if given) returns truthy.
    """

    def __init__(self):
        self._arms: Dict[str, Dict[str, Any]] = {}

    def arm(self, point: str, *, exc: Optional[BaseException] = None,
            times: Optional[int] = 1,
            callback: Optional[Callable[..., None]] = None,
            filter: Optional[Callable[..., bool]] = None) -> None:
        """Arm ``point`` to raise ``exc`` (class or instance) and/or run
        ``callback(**ctx)`` the next ``times`` matching fires."""
        if exc is None and callback is None:
            raise ValueError("arm() needs exc and/or callback")
        self._arms[point] = {"exc": exc, "times": times, "fired": 0,
                             "callback": callback, "filter": filter}

    def fire(self, point: str, **ctx) -> None:
        spec = self._arms.get(point)
        if spec is None:
            return
        if spec["times"] is not None and spec["fired"] >= spec["times"]:
            return
        if spec["filter"] is not None and not spec["filter"](**ctx):
            return
        spec["fired"] += 1
        if spec["callback"] is not None:
            spec["callback"](**ctx)
        exc = spec["exc"]
        if exc is not None:
            raise exc if isinstance(exc, BaseException) else exc()

    def fired(self, point: str) -> int:
        """How many times an armed point has actually fired."""
        spec = self._arms.get(point)
        return 0 if spec is None else spec["fired"]

    def reset(self) -> None:
        self._arms.clear()


_INJECTOR = FaultInjector()


def get_injector() -> FaultInjector:
    return _INJECTOR


def fire(point: str, **ctx) -> None:
    """Production-side hook: no-op unless a test armed ``point``."""
    _INJECTOR.fire(point, **ctx)


def arm(point: str, **kw) -> None:
    _INJECTOR.arm(point, **kw)


def reset() -> None:
    _INJECTOR.reset()


def retry_io(fn: Callable[[], Any], *, retries: int = 3,
             backoff: float = 0.05,
             sleep: Callable[[float], None] = time.sleep) -> Any:
    """Run ``fn`` retrying transient ``OSError`` with exponential backoff.

    ``retries`` is the number of *re*-attempts after the first failure.
    ``InjectedCrash`` (and any non-OSError) propagates immediately — a
    simulated preemption is not a flake.
    """
    attempt = 0
    while True:
        try:
            return fn()
        except InjectedCrash:
            raise
        except OSError:
            if attempt >= retries:
                raise
            sleep(backoff * (2 ** attempt))
            attempt += 1


# --------------------------------------------------------------------- #
# env-armed injections: fault a process you can only reach by env
# --------------------------------------------------------------------- #

def _env_action(name: str, point: str) -> Callable[..., None]:
    if name == "crash":
        def act(**ctx):
            raise InjectedCrash(point)
    elif name == "oserror":
        def act(**ctx):
            raise OSError(f"injected transient failure at {point}")
    elif name == "sigterm":
        def act(**ctx):
            import signal
            os.kill(os.getpid(), signal.SIGTERM)
    elif name == "preempt":
        def act(**ctx):
            from deepspeed_tpu.runtime import elastic
            elastic.request_preemption(f"env-armed fault at {point}")
    elif name == "stall":
        def act(**ctx):
            # wedge the CALLER (not a side thread): the health
            # watchdog must observe a genuinely silent step loop
            time.sleep(float(os.environ.get("DSTPU_FAULT_STALL_S",
                                            "30")))
    else:
        raise ValueError(
            f"{ENV_ARM}: unknown action {name!r} (want crash | oserror "
            f"| sigterm | preempt | stall)")
    return act


# process-global one-shot latch for the engine-init call: arming is
# per-PROCESS, not per-engine — re-arming on a second engine's init
# would reset the fired counter and turn a `times:1` spec into
# once-per-engine. Deliberately NOT cleared by reset().
_ENV_ARMED = False


def arm_from_env(env=None) -> List[str]:
    """Arm fault points from ``DSTPU_FAULT_ARM`` (see module docstring).

    Called at engine init so a supervisor-relaunched subprocess can be
    faulted without any in-process handle on it; with ``env=None`` (the
    engine path) it arms at most once per process. Returns the points
    armed (empty when the variable is unset or already armed). A
    malformed spec raises ``ValueError`` — a silently ignored fault arm
    would make a durability test pass vacuously.
    """
    global _ENV_ARMED
    if env is None:
        if _ENV_ARMED:
            return []
        _ENV_ARMED = True
    env = os.environ if env is None else env
    raw = env.get(ENV_ARM, "").strip()
    if not raw:
        return []
    armed: List[str] = []
    for spec in raw.split(","):
        spec = spec.strip()
        if not spec:
            continue
        once_file = None
        if "@" in spec:
            spec, once_file = spec.split("@", 1)
        parts = spec.split(":")
        if len(parts) < 2:
            raise ValueError(
                f"{ENV_ARM}: bad spec {spec!r} (want "
                "point:action[:times][@once_file])")
        point, action = parts[0], parts[1]
        times = int(parts[2]) if len(parts) > 2 else 1
        if once_file is not None and not os.path.exists(once_file):
            continue  # one-shot already consumed by a prior incarnation
        act = _env_action(action, point)

        def callback(_act=act, _once=once_file, **ctx):
            if _once is not None:
                try:
                    os.remove(_once)
                except OSError:
                    pass
            _act(**ctx)

        _INJECTOR.arm(point, callback=callback,
                      times=None if times <= 0 else times)
        armed.append(point)
    return armed


# --------------------------------------------------------------------- #
# corruption helpers for tests and the offline verifier
# --------------------------------------------------------------------- #

def crc32_file(path: str, chunk_bytes: int = 1 << 20) -> int:
    """Streaming CRC32 of a file's content (matches the COMMITTED
    marker's per-file checksum)."""
    crc = 0
    with open(path, "rb") as f:
        while True:
            block = f.read(chunk_bytes)
            if not block:
                break
            crc = zlib.crc32(block, crc)
    return crc & 0xFFFFFFFF


def flip_byte(path: str, offset: Optional[int] = None) -> int:
    """XOR one byte in-place (default: middle of the file) — simulates
    silent media corruption. Returns the offset flipped."""
    size = os.path.getsize(path)
    if size == 0:
        raise ValueError(f"cannot flip a byte of empty file {path}")
    if offset is None:
        offset = size // 2
    with open(path, "r+b") as f:
        f.seek(offset)
        b = f.read(1)
        f.seek(offset)
        f.write(bytes([b[0] ^ 0xFF]))
    return offset


def truncate_file(path: str, keep_bytes: Optional[int] = None) -> None:
    """Cut a file short (default: half) — simulates a torn write."""
    size = os.path.getsize(path)
    if keep_bytes is None:
        keep_bytes = size // 2
    with open(path, "r+b") as f:
        f.truncate(keep_bytes)
