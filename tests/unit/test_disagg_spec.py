"""Disaggregated prefill/decode serving + speculative decoding (ISSUE 13).

Tier-1 pins:
- host-side drafting (inference/draft.py): n-gram prompt-lookup
  semantics — longest suffix first, most recent occurrence wins — and
  the callable escape hatch; jax-free by construction;
- handoff bookkeeping (inference/disagg.py): FIFO queue with
  requeue-at-front (pool pressure backpressures the handoff, never the
  prefill loop), eviction-voided records, the dispatch-ordering trace
  ("no decode dispatch waits behind a prefill dispatch" as pure
  ordering), and LinkModel-priced wire cost;
- scheduler run semantics: a verify dispatch's (accepted + 1)-token run
  advances position per token, a mid-run stop DISCARDS the remainder,
  and rejected drafts exist only in the draft ledger — never in
  total_tokens/goodput;
- engine end-to-end: greedy outputs with speculation ON are bitwise
  identical to the plain engine (gpt2 AND llama, continuous batching +
  prefix reuse), the verify program set is fixed at warmup
  (steady_state_recompiles == 0), disaggregated serving (shared pool
  and separate pools) preserves outputs and drains both pools exactly,
  TTFT decomposes as queue + prefill + handoff in the trail, and
  eviction mid-flight with speculation keeps pool accounting exact.
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
    cfg = GPT2Config(vocab_size=61, max_position_embeddings=64,
                     hidden_size=32, num_layers=2, num_heads=4,
                     embd_dropout=0.0, attn_dropout=0.0,
                     resid_dropout=0.0)
    return cfg, init_gpt2_params(cfg, jax.random.PRNGKey(3))


def tiny_llama():
    from deepspeed_tpu.models.llama import LlamaConfig, init_llama_params
    cfg = LlamaConfig(vocab_size=61, hidden_size=32, num_layers=2,
                      num_heads=4, num_kv_heads=2,
                      max_position_embeddings=64)
    return cfg, init_llama_params(cfg, jax.random.PRNGKey(4))


TINY_INF = {"max_batch_size": 3, "prompt_buckets": [4, 8, 16, 24],
            "batch_buckets": [1, 2], "max_seq_len": 48,
            "max_new_tokens": 8}

# continuous batching + prefix reuse + draftable repetition: two
# requests share a full-page prefix (prefix-cache reuse under spec),
# two are periodic (the n-gram drafter's best case), the rest are
# arbitrary mixed lengths (draft stalls ride along)
SHARED = list(range(1, 17))                  # one full 16-token page
WORKLOAD = [SHARED + [20, 21], SHARED + [30, 31, 32],
            [5, 6, 7] * 4, [9, 10] * 5,
            [40, 41, 42], [50, 51, 52, 53, 54]]


def serve_all(eng, prompts, max_new=8):
    """submit/step driver returning (outputs in submit order, finished
    records by uid) — generate() hides the FinishedRequests."""
    from deepspeed_tpu.inference import Request
    uids = [eng.submit(Request(prompt=list(p), max_new_tokens=max_new,
                               temperature=0.0, seed=0))
            for p in prompts]
    fins = {f.uid: f for f in eng.run()}
    outs = [fins[u].prompt + fins[u].tokens for u in uids]
    return outs, [fins[u] for u in uids]


def read_trail(events_dir):
    obs_report = _load_tool("obs_report")
    rows = []
    for seg in obs_report.segment_files(
            os.path.join(str(events_dir), "events.jsonl")):
        if os.path.exists(seg):
            rows += [json.loads(line) for line in open(seg)]
    return rows


# --------------------------------------------------------------------- #
# drafting (inference/draft.py — jax-free, pure host)
# --------------------------------------------------------------------- #
class TestNGramDrafter:
    def _d(self, k=4, lo=1, hi=3):
        from deepspeed_tpu.inference.draft import NGramDrafter
        return NGramDrafter(k=k, ngram_min=lo, ngram_max=hi)

    def test_proposes_pattern_continuation(self):
        d = self._d()
        # history ends in [5, 6, 7]; the most recent earlier trigram
        # occurrence is one period back — its continuation (the rest of
        # the history after it) predicts the cycle
        h = [5, 6, 7] * 4
        assert d.propose(h, 4) == [5, 6, 7]

    def test_longest_suffix_wins(self):
        d = self._d()
        # suffix [2, 3] matches at one site, suffix [3] at two; the
        # bigram site's continuation (9) must win over the unigram's
        h = [1, 2, 3, 9, 8, 3, 7, 2, 3]
        assert d.propose(h, 1) == [9]

    def test_most_recent_occurrence_wins(self):
        d = self._d(lo=1, hi=1)
        # token 3 occurs twice; the LATER occurrence's continuation (7)
        # is the prediction, not the earlier one's (9)
        h = [3, 9, 8, 3, 7, 2, 3]
        assert d.propose(h, 1) == [7]

    def test_no_match_is_a_stall_not_an_error(self):
        d = self._d()
        assert d.propose([1, 2, 3, 4, 5], 4) == []
        assert d.propose([], 4) == []
        assert d.propose([1], 4) == []

    def test_k_caps_the_run(self):
        d = self._d(k=8)
        h = [5, 6, 7] * 4
        assert len(d.propose(h, 2)) <= 2
        assert d.propose(h, 2) == [5, 6]

    def test_make_drafter(self):
        from deepspeed_tpu.inference.draft import (CallableDrafter,
                                                   NGramDrafter,
                                                   make_drafter)
        base = {"enabled": True, "k": 4, "method": "ngram",
                "ngram_min": 1, "ngram_max": 3}
        assert isinstance(make_drafter(base, None), NGramDrafter)
        assert make_drafter(dict(base, enabled=False), None) is None
        fn = lambda hist, k: list(hist[-k:])
        d = make_drafter(dict(base, method="callable"), fn)
        assert isinstance(d, CallableDrafter)
        assert d.propose([1, 2, 3], 2) == [2, 3]
        with pytest.raises(ValueError, match="draft_fn"):
            make_drafter(dict(base, method="callable"), None)


# --------------------------------------------------------------------- #
# handoff bookkeeping (inference/disagg.py — jax-free, pure host)
# --------------------------------------------------------------------- #
def _rec(uid, t=0.0):
    from deepspeed_tpu.inference.disagg import HandoffRecord
    return HandoffRecord(uid=uid, slot=uid, first_token=1, live_pages=2,
                         prompt_tokens=20, t_ready=t)


class TestHandoffQueue:
    def _q(self, now):
        from deepspeed_tpu.inference.disagg import HandoffQueue
        return HandoffQueue(clock=lambda: now[0])

    def test_fifo_drain_and_claim_wait(self):
        now = [10.0]
        q = self._q(now)
        q.push(_rec(1, t=9.0))
        q.push(_rec(2, t=9.5))
        recs = q.drain()
        assert [r.uid for r in recs] == [1, 2]
        assert len(q) == 0
        assert q.claimed(recs[0]) == pytest.approx(1000.0)  # 1 s wait
        assert q.claimed(recs[1]) == pytest.approx(500.0)
        assert q.total_handoffs == 2

    def test_requeue_keeps_arrival_order(self):
        now = [0.0]
        q = self._q(now)
        a, b = _rec(1), _rec(2)
        q.push(a)
        q.push(b)
        recs = q.drain()
        q.requeue(recs[0])          # claim for uid 1 bounced
        q.push(_rec(3))             # newer handoff arrives
        assert [r.uid for r in q.drain()] == [1, 3]
        assert recs[0].attempts == 1
        assert q.total_requeues == 1

    def test_dropped_voids_evicted_records(self):
        now = [0.0]
        q = self._q(now)
        q.push(_rec(1))
        rec = q.drain()[0]
        q.dropped(rec)
        st = q.debug_state()
        assert st["dropped"] == 1 and st["handoffs"] == 0
        assert st["peak_depth"] == 1 and st["depth"] == 0


class TestDispatchTrace:
    def test_decode_first_holds(self):
        from deepspeed_tpu.inference.disagg import DispatchTrace
        t = DispatchTrace()
        for step in range(3):           # claims -> decode -> prefill
            t.record(step, "handoff")
            t.record(step, "verify")
            t.record(step, "prefill")
        assert t.decode_first_fraction() == 1.0

    def test_interleaved_step_is_a_violation(self):
        from deepspeed_tpu.inference.disagg import DispatchTrace
        t = DispatchTrace()
        t.record(0, "decode")
        t.record(0, "prefill")          # ok
        t.record(1, "prefill")
        t.record(1, "decode")           # decode waited behind prefill
        assert t.decode_first_fraction() == 0.5

    def test_unmixed_trace_measures_nothing(self):
        from deepspeed_tpu.inference.disagg import DispatchTrace
        t = DispatchTrace()
        t.record(0, "decode")
        t.record(1, "decode")
        assert t.decode_first_fraction() is None

    def test_ring_bound(self):
        from deepspeed_tpu.inference.disagg import DispatchTrace
        t = DispatchTrace(cap=8)
        for i in range(100):
            t.record(i, "decode")
        assert len(t.rows()) == 8 and t.total == 100


class TestPriceHandoff:
    class _Link:
        def bytes_per_us(self, axis):
            return 100.0 if axis == "intra" else 10.0

        def latency_us(self, axis):
            return 1.0 if axis == "intra" else 10.0

    def test_priced_per_hop_and_axis(self):
        from deepspeed_tpu.inference.disagg import price_handoff
        link = self._Link()
        # 2 pages x 1000 B over inter: 10 us latency + 2000/10 us
        assert price_handoff(2, 1000, link, axis="inter") == \
            pytest.approx(0.210)
        assert price_handoff(2, 1000, link, axis="intra") == \
            pytest.approx(0.021)
        assert price_handoff(2, 1000, link, axis="inter", hops=2) == \
            pytest.approx(0.420)

    def test_nothing_moved_costs_nothing(self):
        from deepspeed_tpu.inference.disagg import price_handoff
        assert price_handoff(0, 1000, self._Link()) == 0.0
        assert price_handoff(2, 1000, self._Link(), hops=0) == 0.0


# --------------------------------------------------------------------- #
# scheduler run semantics (jax-free)
# --------------------------------------------------------------------- #
class TestRecordTokenRuns:
    def _serve_one(self, max_new=8, eos=None):
        from deepspeed_tpu.inference.scheduler import Request, Scheduler
        t = [0.0]
        s = Scheduler(1, (4, 8), (1, 2), 32, clock=lambda: t[0])
        s.submit(Request(prompt=[1, 2, 3], max_new_tokens=max_new,
                         eos_id=eos))
        batches = s.admit()
        sid = batches[0].slot_ids[0]
        s.record_tokens({sid: 10})      # prefill's first token
        return s, sid, t

    def test_run_advances_position_per_token(self):
        s, sid, _ = self._serve_one()
        slot = s.slots[sid]
        p0 = slot.position
        done = s.record_token_runs({sid: [11, 12, 13]})
        assert done == []
        slot = s.slots[sid]
        assert slot.position == p0 + 3
        assert slot.tokens[-4:] == [10, 11, 12, 13]
        assert slot.pending_tok == 13   # last kept token is pending
        assert s.total_tokens == 4

    def test_mid_run_stop_discards_remainder(self):
        s, sid, _ = self._serve_one(max_new=8, eos=12)
        done = s.record_token_runs({sid: [11, 12, 13, 14]})
        assert len(done) == 1
        # tokens past the stop are never emitted or counted
        assert done[0].tokens == [10, 11, 12]
        assert done[0].finish_reason == "eos"
        assert s.total_tokens == 3
        assert s.slots[sid] is None     # slot freed for the next admit

    def test_max_new_mid_run(self):
        s, sid, _ = self._serve_one(max_new=3)
        done = s.record_token_runs({sid: [11, 12, 13, 14]})
        assert len(done) == 1
        assert done[0].tokens == [10, 11, 12]
        assert done[0].finish_reason == "length"

    def test_draft_ledger_and_tokens_per_s(self):
        s, sid, t = self._serve_one()
        t[0] += 0.5
        s.record_token_runs({sid: [11, 12, 13]}, {sid: (4, 2)})
        t[0] += 0.5
        done = s.record_token_runs({sid: [14, 15, 16, 17]},
                                   {sid: (3, 3)})
        assert len(done) == 1
        fin = done[0]
        # rejected drafts live ONLY in the ledger, never in the run
        assert fin.draft_proposed == 7 and fin.draft_accepted == 5
        assert fin.tokens_per_s is not None and fin.tokens_per_s > 0
        assert fin.tokens_per_s == pytest.approx(
            len(fin.tokens) / (fin.latency_ms / 1e3))

    def test_draft_proposals_respect_caps(self):
        from deepspeed_tpu.inference.draft import NGramDrafter
        from deepspeed_tpu.inference.scheduler import Request, Scheduler
        s = Scheduler(1, (4, 8), (1, 2), 32,
                      drafter=NGramDrafter(k=4, ngram_min=1,
                                           ngram_max=3), spec_k=4)
        s.submit(Request(prompt=[5, 6, 7, 5, 6, 7], max_new_tokens=3))
        sid = s.admit()[0].slot_ids[0]
        s.record_tokens({sid: 5})
        props = s.draft_proposals()
        # max_new 3, one token kept -> at most (3 - 1 - 1) = 1 proposal
        # even though the drafter could continue the cycle for 4
        assert 0 < len(props[sid]) <= 1
        assert s.draft_proposals(cap=0) == {}


# --------------------------------------------------------------------- #
# config surface (runtime/config.py)
# --------------------------------------------------------------------- #
class TestConfigValidation:
    def _cfg(self, **inf):
        from deepspeed_tpu.runtime.config import get_inference_config
        return get_inference_config({"inference": inf})

    @pytest.fixture(autouse=True)
    def _err(self):
        from deepspeed_tpu.runtime.config import DeepSpeedConfigError
        self.Err = DeepSpeedConfigError

    def test_defaults_off(self):
        cfg = self._cfg()
        assert cfg["spec_decode"]["enabled"] is False
        assert cfg["disagg"]["enabled"] is False
        assert cfg["spec_decode"]["k"] == 4
        assert cfg["disagg"]["separate_pools"] is None

    def test_spec_requires_paged(self):
        with pytest.raises(self.Err, match="paged_kv"):
            self._cfg(paged_kv={"enabled": False},
                      spec_decode={"enabled": True})

    def test_spec_k_bounds(self):
        with pytest.raises(self.Err, match="spec_decode.k"):
            self._cfg(spec_decode={"enabled": True, "k": 0})

    def test_spec_method_vocabulary(self):
        with pytest.raises(self.Err, match="method"):
            self._cfg(spec_decode={"enabled": True, "method": "oracle"})

    def test_ngram_ordering(self):
        with pytest.raises(self.Err, match="ngram"):
            self._cfg(spec_decode={"enabled": True, "ngram_min": 3,
                                   "ngram_max": 2})

    def test_verify_widths_floor(self):
        with pytest.raises(self.Err, match="verify_widths"):
            self._cfg(spec_decode={"enabled": True,
                                   "verify_widths": [1]})

    def test_disagg_prefill_pages(self):
        with pytest.raises(self.Err, match="prefill_pages"):
            self._cfg(disagg={"enabled": True, "prefill_pages": 1})

    def test_decode_mesh_needs_disagg(self):
        with pytest.raises(self.Err, match="disagg.enabled"):
            self._cfg(disagg={"enabled": False,
                              "decode_mesh": {"axes": {"model": 1}}})


# --------------------------------------------------------------------- #
# engine end-to-end (CPU backend; interpret-mode kernels)
# --------------------------------------------------------------------- #
@pytest.fixture(scope="module")
def runs(tmp_path_factory):
    """One baseline run + spec/disagg variants over the SAME workload —
    built once; every parity/telemetry test below reads this."""
    from deepspeed_tpu.inference import InferenceEngine
    cfg, params = tiny_gpt2()
    out = {}

    def build(name, extra, obs=False):
        ic = dict(TINY_INF, **extra)
        kw = {}
        if obs:
            tmp = tmp_path_factory.mktemp(name)
            ic["events_dir"] = str(tmp)
            # window row every 4 tokens so the short workload still
            # crosses the spec-window emission stride
            kw["observability_config"] = {
                "serve": {"enabled": True, "sample_rate": 0.25}}
            out[name + "_dir"] = tmp
        eng = InferenceEngine(cfg, params, ic, dtype=jnp.float32, **kw)
        warm = eng.warmup()
        outs, fins = serve_all(eng, WORKLOAD)
        out[name] = {"outs": outs, "fins": fins, "warm": warm,
                     "rc": eng.steady_state_recompiles,
                     "state": eng.debug_state(),
                     "total_tokens": eng.scheduler.total_tokens}
        eng.close()

    build("base", {})
    build("spec", {"spec_decode": {"enabled": True, "k": 4}}, obs=True)
    build("disagg", {"disagg": {"enabled": True}}, obs=True)
    build("sep", {"disagg": {"enabled": True, "separate_pools": True}})
    build("both", {"spec_decode": {"enabled": True, "k": 4},
                   "disagg": {"enabled": True, "separate_pools": True}})
    return out


class TestSpecEngine:
    def test_greedy_parity_gpt2(self, runs):
        assert runs["spec"]["outs"] == runs["base"]["outs"]

    def test_zero_recompiles_under_churn(self, runs):
        assert runs["base"]["rc"] == 0
        assert runs["spec"]["rc"] == 0

    def test_warmup_program_set_pinned(self, runs):
        # speculation adds exactly one verify program per verify width
        # (tables ride at full pps — never widths x page buckets), and
        # one shape of the token merge: a verify run's last kept tokens
        # are the host's to hand the device
        widths = runs["spec"]["state"]["spec_decode"]["verify_widths"]
        assert runs["spec"]["warm"] == \
            runs["base"]["warm"] + len(widths) + 1
        progs = runs["spec"]["state"]["programs"]
        assert "verify" in progs and progs["verify"]["dispatches"] > 0

    def test_speculation_actually_accepts(self, runs):
        spec = runs["spec"]["state"]["slo"]["spec"]
        assert spec["proposed"] > 0
        assert 0 < spec["accepted"] <= spec["proposed"]

    def test_verified_tokens_save_decode_dispatches(self, runs):
        """What speculation is for, as a dispatch count: the same tokens
        in fewer decode-phase device round-trips, so more kept tokens
        per verify-or-decode dispatch than without it."""
        def phase(name):
            progs = runs[name]["state"]["programs"]
            return sum(progs.get(k, {}).get("dispatches", 0)
                       for k in ("decode", "verify"))
        assert 0 < phase("spec") < phase("base")
        assert runs["spec"]["total_tokens"] / phase("spec") > \
            runs["base"]["total_tokens"] / phase("base")

    def test_goodput_counts_only_kept_tokens(self, runs):
        # rejected drafts must not inflate token accounting: the
        # scheduler's counter equals the tokens the requests got
        kept = sum(len(o) - len(p)
                   for o, p in zip(runs["spec"]["outs"], WORKLOAD))
        assert runs["spec"]["total_tokens"] == kept
        assert runs["spec"]["total_tokens"] == \
            runs["base"]["total_tokens"]

    def test_finished_requests_carry_the_ledger(self, runs):
        fins = runs["spec"]["fins"]
        assert all(f.tokens_per_s is not None and f.tokens_per_s > 0
                   for f in fins)
        assert all(f.draft_accepted <= f.draft_proposed for f in fins)
        assert sum(f.draft_accepted for f in fins) > 0
        # the baseline engine's requests carry an empty ledger
        assert all(f.draft_proposed == 0 for f in runs["base"]["fins"])

    def test_spec_trail_rows(self, runs):
        rows = read_trail(runs["spec_dir"])
        windows = [r for r in rows
                   if r.get("event") == "serve_spec_window"]
        assert windows, "no serve_spec_window rows in the trail"
        for r in windows:
            assert {"proposed", "accepted", "dispatches",
                    "accept_rate"} <= set(r)
        reasons = {r["reason"] for r in rows
                   if r.get("event") == "serve_defer"}
        from deepspeed_tpu.inference.tracing import DEFER_REASONS
        assert reasons <= set(DEFER_REASONS)

    def test_llama_greedy_parity(self):
        from deepspeed_tpu.inference import InferenceEngine
        cfg, params = tiny_llama()
        prompts = [WORKLOAD[0], WORKLOAD[2], WORKLOAD[4]]

        def go(extra):
            eng = InferenceEngine(cfg, params, dict(TINY_INF, **extra),
                                  dtype=jnp.float32)
            eng.warmup()
            outs, _ = serve_all(eng, prompts)
            rc = eng.steady_state_recompiles
            eng.close()
            return outs, rc

        base, rc_b = go({})
        spec, rc_s = go({"spec_decode": {"enabled": True, "k": 3}})
        assert spec == base
        assert rc_b == 0 and rc_s == 0

    def test_eviction_mid_flight_keeps_pool_exact(self):
        from deepspeed_tpu.inference import InferenceEngine, Request
        cfg, params = tiny_gpt2()
        eng = InferenceEngine(
            cfg, params,
            dict(TINY_INF, spec_decode={"enabled": True, "k": 4}),
            dtype=jnp.float32)
        eng.warmup()
        uids = [eng.submit(Request(prompt=list(p), max_new_tokens=8,
                                   temperature=0.0, seed=0))
                for p in WORKLOAD[:3]]
        eng.step()                  # prefill + first verify in flight
        fin = eng.cancel(uids[1])   # evict between steps, mid-decode
        assert fin is not None
        eng.run()
        alloc = eng.scheduler.allocator
        # exact accounting: every page came back, no double free, the
        # eviction freed the victim's pages despite pending speculation
        assert alloc.pages_in_use == 0
        assert alloc.free_pages == alloc.num_pages - 1
        assert eng.steady_state_recompiles == 0
        eng.close()


class TestDisaggEngine:
    def test_shared_pool_parity(self, runs):
        assert runs["disagg"]["outs"] == runs["base"]["outs"]
        assert runs["disagg"]["rc"] == 0

    def test_separate_pools_parity(self, runs):
        assert runs["sep"]["outs"] == runs["base"]["outs"]
        assert runs["sep"]["rc"] == 0

    def test_spec_plus_disagg_parity(self, runs):
        assert runs["both"]["outs"] == runs["base"]["outs"]
        assert runs["both"]["rc"] == 0

    def test_every_handoff_claimed(self, runs):
        for name in ("disagg", "sep", "both"):
            dg = runs[name]["state"]["disagg"]
            assert dg["queue"]["depth"] == 0
            assert dg["queue"]["handoffs"] == len(WORKLOAD)
            assert dg["queue"]["dropped"] == 0

    def test_pools_drain_exactly(self, runs):
        # decode pool empty after the run...
        pool = runs["sep"]["state"]["page_pool"]
        assert pool["pages_in_use"] == 0
        # ...and the prefill pool too (handoff claims re-homed every
        # slot; admission-side pages all came back)
        ppool = runs["sep"]["state"]["disagg"]["prefill_pool"]
        assert ppool["pages_in_use"] == 0

    def test_separate_pools_move_only_live_pages(self, runs):
        h = runs["sep"]["state"]["disagg"]["handoff"]
        from deepspeed_tpu.inference import pages_for
        live = sum(pages_for(len(p), 16) for p in WORKLOAD)
        assert h["pages_moved"] == live
        assert h["bytes_moved"] > 0

    def test_decode_never_waits_behind_prefill(self, runs):
        # the structural pin: in every traced step that ran both
        # phases, all decode-phase dispatches preceded all prefills
        for name in ("disagg", "sep", "both"):
            frac = runs[name]["state"]["disagg"]["decode_first_fraction"]
            assert frac is None or frac == 1.0
        assert any(
            runs[n]["state"]["disagg"]["decode_first_fraction"] == 1.0
            for n in ("disagg", "sep", "both")), \
            "no traced step ever mixed decode and prefill phases"

    def test_ttft_decomposes_with_handoff(self, runs):
        rows = read_trail(runs["disagg_dir"])
        handoffs = [r for r in rows if r.get("event") == "serve_handoff"]
        assert len(handoffs) == len(WORKLOAD)
        for r in handoffs:
            assert {"uid", "mode", "queue_ms", "transfer_ms",
                    "handoff_ms", "pages"} <= set(r)
            assert r["mode"] == "shared_pool"
            assert r["handoff_ms"] >= 0.0
        finishes = [r for r in rows if r.get("event") == "serve_finish"]
        assert finishes
        for r in finishes:
            # the PR 9 identity grows a handoff term: TTFT = queue wait
            # + prefill + handoff, per request, in the trail itself
            assert r["ttft_ms"] == pytest.approx(
                r["queue_wait_ms"] + r["prefill_ms"] + r["handoff_ms"],
                abs=0.05)
        # handoff must precede the first token's release in file order
        first_h = min(i for i, r in enumerate(rows)
                      if r.get("event") == "serve_handoff")
        first_t = min(i for i, r in enumerate(rows)
                      if r.get("event") == "serve_first_token")
        assert first_h < first_t

    def test_obs_report_serve_sections(self, runs):
        obs_report = _load_tool("obs_report")
        s = obs_report.summarize(str(runs["spec_dir"]))
        spec = s["serving"]["speculation"]
        assert spec["dispatches"] > 0 and spec["accepted"] > 0
        assert spec["accepted_per_dispatch"] > 0
        rendered = obs_report.render_serve(s)
        assert "speculation" in rendered
        s2 = obs_report.summarize(str(runs["disagg_dir"]))
        dg = s2["serving"]["disagg"]
        assert dg["handoffs"] == len(WORKLOAD)
        assert "disagg_handoff" in obs_report.render_serve(s2)
