"""Request-granular serving observability (ISSUE 9).

Tier-1 pins:
- the full request lifecycle event trail (serve_submit -> serve_defer*
  -> serve_prefix_hit? -> serve_admit -> serve_prefill ->
  serve_first_token -> serve_decode_window* -> serve_finish/serve_evict)
  with PINNED per-event required fields, per-uid ordering, and the
  defer-reason vocabulary, under a mixed-length continuous-batching
  workload;
- ``ttft_ms`` is null — never 0.0 — for requests evicted before their
  first token (engine + scheduler paths);
- SLO/goodput accounting: attainment and goodput are distinct from raw
  throughput and land as ``Serve/*`` scalars;
- events.jsonl size rotation: atomic segment rollover, obs_report reads
  segments back in order;
- ``engine.debug_state()`` live introspection (pool, prefix cache,
  slots, queue-by-bucket, per-program dispatches);
- tracing is free at the dispatch level: warmup program set, dispatch
  counts, and steady-state recompiles are IDENTICAL with tracing on;
- obs_report ``--serve`` CLI + the versioned ``--json`` schema.
"""

import importlib.util
import json
import os

import jax
import jax.numpy as jnp
import numpy as np
import pytest

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
    cfg = GPT2Config(vocab_size=61, max_position_embeddings=32,
                     hidden_size=32, num_layers=2, num_heads=4,
                     embd_dropout=0.0, attn_dropout=0.0,
                     resid_dropout=0.0)
    return cfg, init_gpt2_params(cfg, jax.random.PRNGKey(3))


TINY_INF = {"max_batch_size": 3, "prompt_buckets": [4, 8],
            "batch_buckets": [1, 2], "max_seq_len": 32,
            "max_new_tokens": 4}

# the pinned event schema: required fields per lifecycle event kind
# (docs/observability.md "Serving tracing & SLOs"); extra fields may be
# added, these may not be dropped or renamed
TRAIL_SCHEMA = {
    "serve_submit": {"uid", "prompt_tokens", "max_new_tokens"},
    "serve_defer": {"uid", "reason"},
    "serve_prefix_hit": {"uid", "tokens", "pages"},
    "serve_admit": {"uid", "slot", "queue_wait_ms", "prefix_tokens",
                    "prompt_bucket", "batch_bucket"},
    "serve_prefill": {"uid", "slot", "wall_ms", "prompt_bucket",
                      "batch_bucket", "rows"},
    "serve_first_token": {"uid", "ttft_ms", "prefill_ms"},
    "serve_handoff": {"uid", "mode", "queue_ms", "transfer_ms",
                      "handoff_ms", "pages", "bytes_moved"},
    "serve_spec_window": {"uid", "proposed", "accepted", "dispatches",
                          "accept_rate"},
    # chunked prefill (ISSUE 19): one row per chunk dispatch — chunk
    # ordinal, tokens scattered, wall and cumulative prefill ms
    "serve_prefill_chunk": {"uid", "slot", "chunk", "tokens",
                            "wall_ms", "cum_ms"},
    "serve_decode_window": {"uid", "tokens", "end_token", "window_ms",
                            "tbt_ms"},
    "serve_finish": {"uid", "reason", "new_tokens", "ttft_ms",
                     "latency_ms", "queue_wait_ms", "prefill_ms",
                     "tbt_ms", "tbt_ms_max", "slo_ok"},
    "serve_evict": {"uid", "reason", "new_tokens", "ttft_ms",
                    "latency_ms"},
    # fleet tracing (ISSUE 18): migration lineage rows — emitted by
    # the source at export and the destination at import, sharing the
    # request's trace id so the merged timeline survives replica death
    "serve_migrate_out": {"uid", "position", "pages", "nbytes",
                          "reason"},
    "serve_migrate_in": {"uid", "position", "pages", "nbytes",
                         "resumed_tokens"},
}
TRAIL_KINDS = set(TRAIL_SCHEMA)


def read_rows(tmp_path):
    rows = []
    obs_report = _load_tool("obs_report")
    for seg in obs_report.segment_files(
            os.path.join(str(tmp_path), "events.jsonl")):
        if os.path.exists(seg):
            rows += [json.loads(line) for line in open(seg)]
    return rows


def trail_of(rows, uid):
    """(index, row) of every lifecycle event for one request, in file
    order."""
    return [(i, r) for i, r in enumerate(rows)
            if r.get("event") in TRAIL_KINDS and r.get("uid") == uid]


# --------------------------------------------------------------------- #
# bounded histogram sink (utils/monitor.py)
# --------------------------------------------------------------------- #
class TestHistogram:
    def test_percentiles_and_exact_extremes(self):
        from deepspeed_tpu.utils.monitor import Histogram
        h = Histogram()
        for v in range(1, 101):
            h.record(float(v))
        assert h.count == 100 and h.min == 1.0 and h.max == 100.0
        assert h.percentile(0.0) == 1.0 and h.percentile(1.0) == 100.0
        # log-bucketed: one bucket width (~7.5%) of relative error
        assert abs(h.percentile(0.50) - 50) / 50 < 0.10
        assert abs(h.percentile(0.95) - 95) / 95 < 0.10
        assert abs(h.mean - 50.5) < 1e-9

    def test_bounded_buckets(self):
        from deepspeed_tpu.utils.monitor import Histogram
        h = Histogram()
        rng = np.random.RandomState(0)
        for v in rng.lognormal(3.0, 2.0, size=20_000):
            h.record(float(v))
        # millions of samples may land, bucket count stays O(range)
        assert len(h._buckets) < 400
        assert h.count == 20_000

    def test_snapshot_and_degenerate(self):
        from deepspeed_tpu.utils.monitor import Histogram
        h = Histogram()
        assert h.percentile(0.5) is None
        snap = h.snapshot()
        assert snap["count"] == 0 and snap["p99"] is None
        h.record(5.0)
        snap = h.snapshot()
        assert snap["count"] == 1
        assert snap["p50"] == snap["p99"] == 5.0
        h.record(float("nan"))           # non-finite samples are dropped
        assert h.count == 1


# --------------------------------------------------------------------- #
# events.jsonl size rotation (utils/monitor._JsonlWriter)
# --------------------------------------------------------------------- #
class TestEventLogRotation:
    def test_rotates_and_reads_back_in_order(self, tmp_path):
        from deepspeed_tpu.utils.monitor import _JsonlWriter
        w = _JsonlWriter(str(tmp_path), max_mb=0.001)       # ~1 KiB cap
        for step in range(200):
            w.add_scalar("T/x", float(step), step)
        w.close()
        segs = sorted(p for p in os.listdir(tmp_path)
                      if p.startswith("events.jsonl."))
        assert len(segs) >= 2, "cap of ~1 KiB must have rotated"
        for seg in segs:
            assert os.path.getsize(tmp_path / seg) >= 1024
        # obs_report folds segments + live file into ONE ordered stream
        obs_report = _load_tool("obs_report")
        scalars, _ = obs_report.load_events(
            str(tmp_path / "events.jsonl"))
        steps = [s for s, _ in scalars["T/x"]]
        assert steps == list(range(200))

    def test_reopen_resumes_sequence(self, tmp_path):
        from deepspeed_tpu.utils.monitor import _JsonlWriter
        w = _JsonlWriter(str(tmp_path), max_mb=0.001)
        for step in range(100):
            w.add_scalar("T/x", float(step), step)
        w.close()
        n1 = len([p for p in os.listdir(tmp_path)
                  if p.startswith("events.jsonl.")])
        # a restarted process must not overwrite existing segments
        w = _JsonlWriter(str(tmp_path), max_mb=0.001)
        for step in range(100, 200):
            w.add_scalar("T/x", float(step), step)
        w.close()
        n2 = len([p for p in os.listdir(tmp_path)
                  if p.startswith("events.jsonl.")])
        assert n2 > n1
        obs_report = _load_tool("obs_report")
        scalars, _ = obs_report.load_events(
            str(tmp_path / "events.jsonl"))
        assert [s for s, _ in scalars["T/x"]] == list(range(200))

    def test_rotation_off_by_default(self, tmp_path):
        from deepspeed_tpu.utils.monitor import _JsonlWriter
        w = _JsonlWriter(str(tmp_path))
        for step in range(200):
            w.add_scalar("T/x", float(step), step)
        w.close()
        assert [p for p in os.listdir(tmp_path)
                if p.startswith("events.jsonl.")] == []


# --------------------------------------------------------------------- #
# ServeTracer unit (jax-free, fake clock + captured writer)
# --------------------------------------------------------------------- #
class _CapWriter:
    def __init__(self):
        self.rows = []

    def add_event(self, kind, **fields):
        self.rows.append(dict(fields, event=kind))


class TestServeTracerUnit:
    def _tracer(self, **cfg):
        from deepspeed_tpu.inference.tracing import ServeTracer
        t = [0.0]
        base = {"enabled": True, "sample_rate": 0.5,
                "slo": {"ttft_ms": 100.0, "tbt_ms": 50.0}}
        base.update(cfg)
        w = _CapWriter()
        tr = ServeTracer(base, writer=w, clock=lambda: t[0])
        return tr, w, t

    def test_defer_dedupe_and_reset_on_admit(self):
        tr, w, _t = self._tracer()
        tr.on_submit(7, 4, 8)
        for _ in range(5):
            tr.on_defer(7, "pages")
        tr.on_defer(7, "bucket")
        assert [r["reason"] for r in w.rows
                if r["event"] == "serve_defer"] == ["pages", "bucket"]
        tr.on_admit(7, 0, 3.0, 0, 4, 2)
        tr.on_defer(7, "pages")          # a fresh cycle may defer again
        assert sum(1 for r in w.rows
                   if r["event"] == "serve_defer") == 3

    def test_decode_window_stride(self):
        tr, w, t = self._tracer(sample_rate=0.5)      # window = 2 tokens
        tr.on_submit(1, 4, 16)
        tr.on_admit(1, 0, 1.0, 0, 4, 1)
        tr.on_first_token(1, 5.0)
        for i in range(9):
            t[0] += 0.002
            tr.on_token(1)
        wins = [r for r in w.rows if r["event"] == "serve_decode_window"]
        # 10 tokens at stride 2 -> windows close at token 2,4,6,8,10
        assert len(wins) == 5
        assert wins[0]["tokens"] == 2 and wins[-1]["end_token"] == 10
        for r in wins:
            assert r["tbt_ms"] == pytest.approx(2.0, rel=0.25)

    def test_slo_classification_and_goodput(self):
        tr, w, t = self._tracer()
        from deepspeed_tpu.inference.scheduler import FinishedRequest

        def fin(uid, ttft, n=4):
            return FinishedRequest(uid=uid, prompt=[1], tokens=[0] * n,
                                   finish_reason="length", ttft_ms=ttft,
                                   latency_ms=50.0, queue_wait_ms=1.0)
        tr.on_submit(1, 1, 4)
        tr.on_admit(1, 0, 1.0, 0, 4, 1)
        tr.on_finish(fin(1, ttft=10.0))               # within SLO
        tr.on_submit(2, 1, 4)
        tr.on_admit(2, 0, 1.0, 0, 4, 1)
        tr.on_finish(fin(2, ttft=500.0))              # TTFT breach
        tr.on_submit(3, 1, 4)
        tr.on_finish(fin(3, ttft=None, n=0), evicted=True)
        assert tr.finished == 3 and tr.evicted == 1
        assert tr.finished_in_slo == 1
        assert tr.slo_attainment == pytest.approx(1 / 3)
        assert tr.good_tokens == 4 and tr.finished_tokens == 8
        oks = {r["uid"]: r["slo_ok"] for r in w.rows
               if r["event"] == "serve_finish"}
        assert oks == {1: True, 2: False}
        ev = [r for r in w.rows if r["event"] == "serve_evict"]
        assert len(ev) == 1 and ev[0]["ttft_ms"] is None

    def test_disabled_tracer_still_emits_legacy_finish(self):
        from deepspeed_tpu.inference.scheduler import FinishedRequest
        from deepspeed_tpu.inference.tracing import ServeTracer
        w = _CapWriter()
        tr = ServeTracer({"enabled": False}, writer=w)
        tr.on_submit(1, 4, 8)
        tr.on_admit(1, 0, 1.0, 0, 4, 1)
        tr.on_token(1)
        assert w.rows == []               # every non-terminal hook no-ops
        tr.on_finish(FinishedRequest(
            uid=1, prompt=[1], tokens=[], finish_reason="evicted",
            ttft_ms=None, latency_ms=3.0), evicted=True)
        assert len(w.rows) == 1
        row = w.rows[0]
        assert row["event"] == "serve_evict"
        assert row["ttft_ms"] is None            # null, never 0.0

    def test_on_tokens_is_on_token_at_one_reading_of_the_clock(self):
        """A step's decode tokens in ONE call: the rows and sums a call
        a token gives at the same clock; an unknown uid and a request
        with no first token yet are passed over; a repeated uid (a
        speculative run) counts each token."""
        def drive(batched):
            tr, w, t = self._tracer(sample_rate=0.5)
            for uid in (1, 2, 3):
                tr.on_submit(uid, 4, 16)
                tr.on_admit(uid, 0, 1.0, uid, 4, 1)
            tr.on_first_token(1, 5.0)
            t[0] += 0.001
            tr.on_first_token(2, 6.0)       # 3 has no first token yet
            for _ in range(4):
                t[0] += 0.002
                uids = (1, 9, 2, 3, 2)
                if batched:
                    tr.on_tokens(uids)
                else:
                    for uid in uids:
                        tr.on_token(uid)
            return tr, w
        (a, wa), (b, wb) = drive(True), drive(False)
        assert wa.rows == wb.rows
        assert [r["uid"] for r in wa.rows
                if r["event"] == "serve_decode_window"].count(2) == 4
        ha, hb = a.hist["tbt_ms"], b.hist["tbt_ms"]
        assert (ha.count, ha.sum, ha.min, ha.max, ha._buckets) == (
            hb.count, hb.sum, hb.min, hb.max, hb._buckets)
        assert ha.count == 12 and ha.min == 0.0
        assert a.drain_step_tbts() == b.drain_step_tbts()
        assert a._req[3].n_tokens == 0

    def test_snapshot_histograms(self):
        tr, _w, t = self._tracer()
        tr.on_submit(1, 4, 8)
        tr.on_admit(1, 0, 2.0, 0, 4, 1)
        tr.on_first_token(1, 6.0)
        t[0] += 0.004
        tr.on_token(1)
        snap = tr.snapshot()
        assert snap["slo"] == {"ttft_ms": 100.0, "tbt_ms": 50.0}
        assert snap["latency"]["queue_wait_ms"]["count"] == 1
        assert snap["latency"]["ttft_ms"]["p50"] == pytest.approx(
            6.0, rel=0.10)
        assert snap["latency"]["tbt_ms"]["count"] == 1
        assert snap["in_flight"] == 1


# --------------------------------------------------------------------- #
# scheduler-side decomposition + eviction
# --------------------------------------------------------------------- #
class TestSchedulerDecomposition:
    def _sched(self, clock, **kw):
        from deepspeed_tpu.inference.scheduler import Scheduler
        return Scheduler(3, (4, 8), (1, 2), 32, clock=clock, **kw)

    def test_queue_wait_measured_and_drained(self):
        from deepspeed_tpu.inference.scheduler import Request
        t = [0.0]
        s = self._sched(lambda: t[0])
        s.submit(Request(prompt=[1, 2], max_new_tokens=4))
        t[0] = 0.25                       # 250 ms in queue
        batches = s.admit()
        assert len(batches) == 1
        waits = s.drain_queue_waits()
        assert waits == [pytest.approx(250.0)]
        assert s.drain_queue_waits() == []
        t[0] = 0.30
        fins = s.record_tokens({batches[0].slot_ids[0]: 5})
        t[0] = 0.35
        for _ in range(3):
            fins += s.record_tokens({batches[0].slot_ids[0]: 5})
        assert fins and fins[0].finish_reason == "length"
        assert fins[0].queue_wait_ms == pytest.approx(250.0)
        assert fins[0].ttft_ms == pytest.approx(300.0)

    def test_evict_from_queue_has_null_ttft(self):
        from deepspeed_tpu.inference.scheduler import Request
        t = [0.0]
        s = self._sched(lambda: t[0])
        uid = s.submit(Request(prompt=[1, 2]))
        t[0] = 0.1
        fin = s.evict(uid)
        assert fin is not None
        assert fin.ttft_ms is None and fin.queue_wait_ms is None
        assert fin.finish_reason == "evicted" and fin.tokens == []
        assert fin.latency_ms == pytest.approx(100.0)
        assert s.idle()
        assert s.evict(uid) is None       # already gone

    def test_evict_in_flight_frees_slot_and_pages(self):
        from deepspeed_tpu.inference.paging import PageAllocator
        from deepspeed_tpu.inference.scheduler import Request
        t = [0.0]
        alloc = PageAllocator(9, 4)
        s = self._sched(lambda: t[0], allocator=alloc)
        uid = s.submit(Request(prompt=[1, 2, 3], max_new_tokens=4))
        batches = s.admit()
        s.record_tokens({batches[0].slot_ids[0]: 5})
        assert alloc.pages_in_use > 0
        fin = s.evict(uid)
        assert fin.finish_reason == "evicted"
        assert fin.ttft_ms is not None and len(fin.tokens) == 1
        assert alloc.pages_in_use == 0
        assert s.free_slots() == [0, 1, 2]

    def test_evict_admitted_before_first_token_is_null(self):
        """The FinishedRequest.ttft_ms-is-None path: admitted (slot
        held, queue_wait known) but evicted before any token."""
        from deepspeed_tpu.inference.scheduler import Request
        t = [0.0]
        s = self._sched(lambda: t[0])
        uid = s.submit(Request(prompt=[1, 2]))
        t[0] = 0.05
        s.admit()
        fin = s.evict(uid)
        assert fin.ttft_ms is None
        assert fin.queue_wait_ms == pytest.approx(50.0)

    def test_queue_by_bucket(self):
        from deepspeed_tpu.inference.scheduler import Request
        t = [0.0]
        s = self._sched(lambda: t[0])
        for plen in (2, 3, 7, 8, 4):
            s.submit(Request(prompt=list(range(1, plen + 1))))
        assert s.queue_by_bucket() == {4: 3, 8: 2}


# --------------------------------------------------------------------- #
# the pinned lifecycle trail (engine level, mixed-length workload)
# --------------------------------------------------------------------- #
class TestLifecycleTrail:
    @pytest.fixture(scope="class")
    def trail_run(self, tmp_path_factory):
        """One mixed-length continuous-batching run, paged engine with
        a page pool small enough to starve admission (forcing pages +
        lookahead defers), two prompt buckets (forcing bucket defers),
        prefix reuse, and per-token decode windows."""
        from deepspeed_tpu.inference import InferenceEngine
        tmp = tmp_path_factory.mktemp("trail")
        cfg, params = tiny_gpt2()
        icfg = dict(TINY_INF, events_dir=str(tmp), admit_lookahead=0,
                    max_new_tokens=3,
                    paged_kv={"page_size": 4, "num_pages": 5})
        eng = InferenceEngine(
            cfg, params, icfg, dtype=jnp.float32,
            observability_config={"serve": {"sample_rate": 1.0}})
        eng.warmup()
        # pool = 4 usable pages. First admit pass: head [1,2,3,4,16]
        # (2 pages); [1,2,3,4,17] shares its full first page ->
        # serve_prefix_hit + same-batch admit (1 shared + 1 fresh
        # page, 1-token suffix). Next pass: the len-7 head needs 3
        # pages but only 1 is free -> "pages", and with lookahead=0
        # whatever sits behind it isn't even scanned -> "lookahead";
        # once it does land (bucket 8), the short bucket-4 prompts
        # behind it defer "bucket" before getting their own batches.
        prompts = [[1, 2, 3, 4, 16], [1, 2, 3, 4, 17],
                   [4, 5, 6, 7, 8, 9, 10], [11, 12],
                   [13, 14, 15], [17, 18, 19]]
        uids = [eng.submit(__import__(
            "deepspeed_tpu.inference.scheduler",
            fromlist=["Request"]).Request(
                prompt=p, max_new_tokens=3, seed=i))
            for i, p in enumerate(prompts)]
        eng.run()
        state = eng.debug_state()
        eng.close()
        return read_rows(tmp), uids, prompts, state, str(tmp)

    def test_every_request_has_a_complete_ordered_trail(self, trail_run):
        rows, uids, prompts, _state, _d = trail_run
        for uid, prompt in zip(uids, prompts):
            trail = trail_of(rows, uid)
            kinds = [r["event"] for _, r in trail]
            assert kinds[0] == "serve_submit", kinds
            assert kinds[-1] == "serve_finish", kinds
            # strict per-request phase ordering by file position
            pos = {k: i for i, (_, r) in enumerate(trail)
                   for k in [r["event"]] if k != "serve_defer"}
            for a, b in [("serve_submit", "serve_admit"),
                         ("serve_admit", "serve_prefill"),
                         ("serve_prefill", "serve_first_token"),
                         ("serve_first_token", "serve_finish")]:
                assert pos[a] < pos[b], (uid, kinds)
            # defers (if any) happen strictly between submit and admit
            for i, (_, r) in enumerate(trail):
                if r["event"] == "serve_defer":
                    assert pos["serve_submit"] < i < pos["serve_admit"]
            # decode windows live between first token and finish
            for i, (_, r) in enumerate(trail):
                if r["event"] == "serve_decode_window":
                    assert pos["serve_first_token"] < i \
                        < pos["serve_finish"]

    def test_pinned_event_schema(self, trail_run):
        rows, _uids, _prompts, _state, _d = trail_run
        seen = set()
        for r in rows:
            kind = r.get("event")
            if kind in TRAIL_SCHEMA:
                seen.add(kind)
                missing = TRAIL_SCHEMA[kind] - set(r)
                assert not missing, (kind, missing)
        assert {"serve_submit", "serve_defer", "serve_admit",
                "serve_prefill", "serve_first_token",
                "serve_decode_window", "serve_finish"} <= seen

    def test_no_schema_drift_every_tracer_kind_is_renderable(self):
        """Structural version of the PR 13 ``serve_handoff`` near-miss:
        every event kind the tracer can emit must (a) have a pinned
        TRAIL_SCHEMA entry and (b) have a fold handler in the
        obs_report fleet merger — a new trail row that the merged
        report would silently drop fails here, not in production."""
        from deepspeed_tpu.inference.tracing import ServeTracer
        obs_report = _load_tool("obs_report")
        kinds = set(ServeTracer.EVENT_KINDS)
        assert kinds == set(TRAIL_SCHEMA), (
            "tracer kinds and TRAIL_SCHEMA diverged",
            kinds ^ set(TRAIL_SCHEMA))
        unrendered = kinds - set(obs_report.EVENT_HANDLERS)
        assert not unrendered, (
            "tracer kinds with no obs_report fleet handler",
            unrendered)

    def test_defer_reasons_pinned_and_exercised(self, trail_run):
        from deepspeed_tpu.inference.tracing import DEFER_REASONS
        rows, _uids, _prompts, _state, _d = trail_run
        reasons = {r["reason"] for r in rows
                   if r.get("event") == "serve_defer"}
        assert reasons <= set(DEFER_REASONS)
        # the starved pool forces page defers; lookahead=0 plus a
        # queue behind a stuck head forces lookahead defers
        assert "pages" in reasons
        assert "lookahead" in reasons

    def test_bucket_defer_under_mixed_buckets(self, tmp_path):
        """A ride-along candidate in a different prompt bucket defers
        with reason 'bucket' (and is admitted in the same admit pass
        as its own head)."""
        from deepspeed_tpu.inference import InferenceEngine, Request
        cfg, params = tiny_gpt2()
        eng = InferenceEngine(
            cfg, params, dict(TINY_INF, events_dir=str(tmp_path),
                              max_new_tokens=2),
            dtype=jnp.float32)
        eng.warmup()
        eng.submit(Request(prompt=[1, 2, 3], max_new_tokens=2))
        eng.submit(Request(prompt=[4, 5, 6, 7, 8, 9], max_new_tokens=2))
        eng.submit(Request(prompt=[7, 8], max_new_tokens=2))
        eng.run()
        eng.close()
        rows = read_rows(tmp_path)
        defers = [r for r in rows if r.get("event") == "serve_defer"]
        assert any(r["reason"] == "bucket" for r in defers)
        # ...and everything still finished
        assert sum(1 for r in rows
                   if r.get("event") == "serve_finish") == 3

    def test_prefix_hit_in_trail(self, trail_run):
        rows, uids, _prompts, _state, _d = trail_run
        hits = [r for r in rows if r.get("event") == "serve_prefix_hit"]
        assert hits, "page-aligned shared prefix must produce a hit row"
        assert all(r["tokens"] >= 1 and r["pages"] >= 1 for r in hits)
        assert any(r["uid"] == uids[1] for r in hits)

    def test_finish_decomposition_adds_up(self, trail_run):
        rows, _uids, _prompts, _state, _d = trail_run
        for r in rows:
            if r.get("event") != "serve_finish":
                continue
            assert r["ttft_ms"] is not None
            assert r["queue_wait_ms"] is not None
            # ttft = queue_wait + prefill (same clock, exact by
            # construction up to rounding)
            assert r["ttft_ms"] == pytest.approx(
                r["queue_wait_ms"] + r["prefill_ms"], abs=0.01)
            assert r["latency_ms"] >= r["ttft_ms"] - 0.01

    def test_debug_state_snapshot(self, trail_run):
        _rows, _uids, _prompts, state, _d = trail_run
        assert state["steady_state_recompiles"] == 0
        assert state["queue_depth"] == 0 and state["slots"] == []
        assert state["programs"]["prefill"]["dispatches"] >= 1
        assert state["programs"]["decode"]["dispatches"] >= 1
        pool = state["page_pool"]
        assert pool["pages_in_use"] == 0
        assert pool["pages_free"] == pool["num_pages"] - 1
        pc = pool["prefix_cache"]
        assert pc["hit_requests"] >= 1
        assert pc["evictions"] >= 1       # drained pool dropped entries
        slo = state["slo"]
        assert slo["finished"] == 6 and slo["evicted"] == 0
        assert slo["latency"]["ttft_ms"]["count"] == 6
        assert slo["attainment"] == 1.0   # default SLO is generous

    def test_serve_state_event_sealed_on_close(self, trail_run):
        rows, _uids, _prompts, _state, _d = trail_run
        states = [r for r in rows if r.get("event") == "serve_state"]
        assert states
        last = states[-1]
        assert last["page_pool"]["pages_in_use"] == 0
        assert last["slo"]["finished"] == 6


# --------------------------------------------------------------------- #
# eviction through the engine: null ttft in the JSON, pool reuse
# --------------------------------------------------------------------- #
class TestEngineEviction:
    def test_cancel_queued_and_inflight(self, tmp_path):
        from deepspeed_tpu.inference import InferenceEngine, Request
        cfg, params = tiny_gpt2()
        eng = InferenceEngine(
            cfg, params, dict(TINY_INF, events_dir=str(tmp_path),
                              max_new_tokens=6),
            dtype=jnp.float32)
        eng.warmup()
        uids = [eng.submit(Request(prompt=[i + 1, i + 2],
                                   max_new_tokens=6))
                for i in range(5)]
        eng.step()                         # admits up to 3, first tokens
        # in-flight cancel (has a first token) + queued cancel (none)
        fin_live = eng.cancel(uids[0])
        fin_queued = eng.cancel(uids[4])
        assert fin_live.ttft_ms is not None
        assert fin_queued.ttft_ms is None
        assert eng.cancel(99999) is None
        rest = eng.run()
        eng.close()
        assert {f.uid for f in rest} == {uids[1], uids[2], uids[3]}
        rows = read_rows(tmp_path)
        evicts = {r["uid"]: r for r in rows
                  if r.get("event") == "serve_evict"}
        assert set(evicts) == {uids[0], uids[4]}
        # the satellite fix: evicted-before-first-token is JSON null,
        # not 0.0
        assert evicts[uids[4]]["ttft_ms"] is None
        assert evicts[uids[0]]["ttft_ms"] is not None
        assert all(r.get("ttft_ms") != 0.0 for r in evicts.values())
        # evictions count in the SLO denominator, not the numerator
        assert rows[-1].get("event") == "serve_state" or True
        state = [r for r in rows if r.get("event") == "serve_state"][-1]
        assert state["slo"]["evicted"] == 2
        assert state["slo"]["finished"] == 5


# --------------------------------------------------------------------- #
# SLO / goodput scalars
# --------------------------------------------------------------------- #
class TestSLOGoodput:
    def _run(self, tmp_path, slo):
        from deepspeed_tpu.inference import InferenceEngine
        cfg, params = tiny_gpt2()
        eng = InferenceEngine(
            cfg, params, dict(TINY_INF, events_dir=str(tmp_path)),
            dtype=jnp.float32,
            observability_config={"serve": {"slo": slo}})
        eng.warmup()
        eng.generate([[1, 2, 3], [4, 5], [6, 7, 8]], max_new_tokens=4)
        state = eng.debug_state()
        eng.close()
        scalars = {}
        for r in read_rows(tmp_path):
            if "tag" in r:
                scalars.setdefault(r["tag"], []).append(r["value"])
        return scalars, state

    def test_goodput_equals_throughput_when_slo_met(self, tmp_path):
        scalars, state = self._run(
            tmp_path, {"ttft_ms": 1e9, "tbt_ms": 1e9})
        assert scalars["Serve/slo_attainment"][-1] == 1.0
        assert state["slo"]["attainment"] == 1.0
        assert scalars["Serve/goodput_tokens_per_s"][-1] == \
            pytest.approx(scalars["Serve/tokens_per_sec"][-1], rel=0.2)

    def test_goodput_zero_when_slo_impossible(self, tmp_path):
        scalars, state = self._run(
            tmp_path, {"ttft_ms": 1e-6, "tbt_ms": 1e-6})
        assert scalars["Serve/slo_attainment"][-1] == 0.0
        assert scalars["Serve/goodput_tokens_per_s"][-1] == 0.0
        assert scalars["Serve/tokens_per_sec"][-1] > 0
        assert state["slo"]["good_tokens"] == 0
        # throughput vs goodput are genuinely distinct numbers
        assert scalars["Serve/queue_wait_ms"], "queue waits must land"
        assert scalars["Serve/tbt_ms"], "per-dispatch TBT must land"


# --------------------------------------------------------------------- #
# tracing must not touch the compiled plane (ISSUE 9 acceptance)
# --------------------------------------------------------------------- #
class TestTracingDispatchInvariants:
    def test_program_set_dispatches_and_outputs_unchanged(self,
                                                          tmp_path):
        from deepspeed_tpu.inference import InferenceEngine
        cfg, params = tiny_gpt2()
        prompts = [[1, 2, 3], [4, 5], [6, 7, 8, 9, 10], [11],
                   [1, 2, 3], [12, 13]]

        def run(traced, events):
            icfg = dict(TINY_INF)
            if events:
                icfg["events_dir"] = os.path.join(
                    str(tmp_path), "on" if traced else "off")
            eng = InferenceEngine(
                cfg, params, icfg, dtype=jnp.float32,
                observability_config={
                    "serve": {"enabled": traced, "sample_rate": 1.0}})
            warm = eng.warmup()
            outs = eng.generate(prompts, max_new_tokens=4)
            stats = (warm, eng.compile_tracker.total_dispatches,
                     eng.steady_state_recompiles)
            eng.close()
            return outs, stats

        outs_off, (warm_off, disp_off, rc_off) = run(False, False)
        outs_on, (warm_on, disp_on, rc_on) = run(True, True)
        # tracing on: same warmup program set, same dispatch count,
        # zero steady-state recompiles, bitwise-equal greedy outputs
        assert warm_on == warm_off
        assert disp_on == disp_off
        assert rc_on == rc_off == 0
        assert outs_on == outs_off


# --------------------------------------------------------------------- #
# Chrome-trace request lanes
# --------------------------------------------------------------------- #
class TestChromeLanes:
    def test_recorder_add_lane(self):
        from deepspeed_tpu.profiling.spans import ChromeTraceRecorder
        rec = ChromeTraceRecorder()
        rec.add_lane(7, "req 7", "queue_wait", 0.0, 0.5)
        rec.add_lane(7, "req 7", "decode", 0.5, 1.0, tokens=3)
        metas = [e for e in rec.events if e.get("ph") == "M"]
        assert len(metas) == 1            # one thread_name per lane
        assert metas[0]["args"]["name"] == "req 7"
        xs = [e for e in rec.events if e.get("ph") == "X"]
        assert all(e["tid"] == 7 for e in xs)
        assert xs[1]["args"] == {"tokens": 3}

    def test_engine_emits_request_lanes(self, tmp_path):
        from deepspeed_tpu.inference import InferenceEngine
        cfg, params = tiny_gpt2()
        trace_path = str(tmp_path / "trace.json")
        eng = InferenceEngine(
            cfg, params, dict(TINY_INF), dtype=jnp.float32,
            observability_config={"chrome_trace_path": trace_path})
        eng.warmup()
        eng.generate([[1, 2, 3], [4, 5]], max_new_tokens=3)
        eng.close()
        trace = json.load(open(trace_path))
        names = {e["name"] for e in trace["traceEvents"]}
        # engine phase spans AND per-request lane phases in one trace
        assert {"serve/prefill", "serve/decode", "queue_wait",
                "prefill", "decode", "thread_name"} <= names
        lanes = {e["tid"] for e in trace["traceEvents"]
                 if e.get("ph") == "M"}
        assert len(lanes) == 2            # one lane per request


# --------------------------------------------------------------------- #
# obs_report: --serve, versioned schema, engine-driven rotation
# --------------------------------------------------------------------- #
class TestServeReport:
    @pytest.fixture(scope="class")
    def report_run(self, tmp_path_factory):
        from deepspeed_tpu.inference import InferenceEngine
        tmp = tmp_path_factory.mktemp("serve_report")
        cfg, params = tiny_gpt2()
        eng = InferenceEngine(
            cfg, params, dict(TINY_INF, events_dir=str(tmp)),
            dtype=jnp.float32,
            # a tiny rotation cap: the report must survive segments
            observability_config={"events_max_mb": 0.002})
        eng.warmup()
        eng.generate([[1, 2, 3], [4, 5], [6, 7, 8, 9], [10]],
                     max_new_tokens=4)
        eng.close()
        return str(tmp)

    def test_rotation_happened_and_summary_is_whole(self, report_run):
        segs = [p for p in os.listdir(report_run)
                if p.startswith("events.jsonl.")]
        assert segs, "0.002 MiB cap must rotate on this run"
        obs_report = _load_tool("obs_report")
        s = obs_report.summarize(report_run)
        assert s["schema"] == 3     # v3 (ISSUE 15) keeps every v2 key
        sv = s["serving"]
        # early rows (warmup, first admits) live in rotated segments;
        # losing them would undercount requests
        assert sv["requests"] == 4
        assert sv["queue_wait_ms"]["p99"] is not None
        assert sv["ttft_ms"]["p99"] >= sv["ttft_ms"]["p50"]
        assert sv["tbt_ms"]["p50"] is not None
        assert sv["slo"]["attainment"] == 1.0
        assert sv["slo"]["goodput_tokens_per_s"] > 0
        assert sv["pool"] is not None
        assert sv["pool"]["prefix_cache"]["entries"] == 0

    def test_render_serve_text(self, report_run):
        obs_report = _load_tool("obs_report")
        s = obs_report.summarize(report_run)
        text = obs_report.render_serve(s)
        for needle in ("queue_wait", "ttft", "tbt", "p50", "p95", "p99",
                       "slo_attainment", "goodput", "page_pool",
                       "prefix_cache"):
            assert needle in text, needle
        # the full report also carries the SLO line
        full = obs_report.render(s)
        assert "slo" in full and "goodput" in full

    def test_cli_serve_and_json_schema(self, report_run, capsys):
        obs_report = _load_tool("obs_report")
        assert obs_report.main([report_run, "--serve"]) == 0
        out = capsys.readouterr().out
        assert "serving report" in out and "goodput" in out
        assert obs_report.main([report_run, "--json"]) == 0
        payload = json.loads(capsys.readouterr().out)
        assert payload["schema"] == 3
        assert payload["serving"]["slo"]["attainment"] == 1.0


# --------------------------------------------------------------------- #
# observability.serve config section
# --------------------------------------------------------------------- #
class TestServeObsConfigSection:
    def test_defaults(self):
        from deepspeed_tpu.runtime.config import get_observability_config
        obs = get_observability_config({})
        assert obs["events_max_mb"] == 0
        srv = obs["serve"]
        assert srv["enabled"] is True
        assert srv["slo"] == {"ttft_ms": 2000.0, "tbt_ms": 200.0}
        assert srv["sample_rate"] == pytest.approx(0.0625)
        assert srv["events_max_mb"] == 0

    def test_serve_inherits_and_overrides_rotation_cap(self):
        from deepspeed_tpu.runtime.config import get_observability_config
        obs = get_observability_config(
            {"observability": {"events_max_mb": 64}})
        assert obs["serve"]["events_max_mb"] == 64
        obs = get_observability_config(
            {"observability": {"events_max_mb": 64,
                               "serve": {"events_max_mb": 8}}})
        assert obs["serve"]["events_max_mb"] == 8

    def test_validation(self):
        from deepspeed_tpu.runtime.config import (DeepSpeedConfigError,
                                                  get_observability_config)
        with pytest.raises(DeepSpeedConfigError, match="sample_rate"):
            get_observability_config(
                {"observability": {"serve": {"sample_rate": 2.0}}})
        with pytest.raises(DeepSpeedConfigError, match="slo"):
            get_observability_config(
                {"observability": {"serve": {"slo": {"ttft_ms": -1}}}})
        with pytest.raises(DeepSpeedConfigError, match="events_max_mb"):
            get_observability_config(
                {"observability": {"events_max_mb": -1}})

    def test_rides_deepspeed_config(self):
        from deepspeed_tpu.runtime.config import DeepSpeedConfig
        cfg = DeepSpeedConfig({
            "train_batch_size": 1,
            "observability": {"serve": {"slo": {"ttft_ms": 500}}}})
        assert cfg.observability_config["serve"]["slo"]["ttft_ms"] == 500.0
