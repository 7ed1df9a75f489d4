"""The dispatch ledger (`inference/disagg.py` `DispatchTrace`): one row a
device dispatch of every serving engine, with four stamps and the
scheduler's token count; the spans that carry its `seq`; the benchmark's
reader of it (`benchmarks/readers/dispatch_ledger.py`) on hand-made
ledgers; and the five metrics' declaration."""

import json
import os
import sys

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from deepspeed_tpu.inference import InferenceEngine
from deepspeed_tpu.inference.disagg import DispatchTrace
from deepspeed_tpu.inference.scheduler import Request
from deepspeed_tpu.models.gpt2 import GPT2Config, init_gpt2_params
from deepspeed_tpu.profiling import spans

REPO = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
BENCH = os.path.join(REPO, "benchmarks")
CELLS = ["gpt2-345m.serve-saturated",
         "solar-open2-250b.serve-rollout-saturated"]
METRICS = {"serve_stall_share.sat": "stall_share_pct",
           "serve_tokens_per_s_median_step.sat": "tokens_per_s_median_step",
           "serve_host_serial_ms.sat": "host_serial_ms",
           "serve_decode_wait_ms.sat": "decode_wait_ms",
           "serve_prefill_wait_ms.sat": "prefill_wait_ms"}


class _Clock:
    """A clock that moves only when told to."""

    def __init__(self):
        self.t = 100.0

    def __call__(self):
        return self.t


# ------------------------------------------------------ the ledger alone
def test_ring_wraps_and_counts_what_it_dropped():
    t = DispatchTrace(cap=8)
    for i in range(20):
        t.record(i, "decode", tokens_total=3 * (i + 1))
    table = t.table()
    assert t.total == 20 and t.dropped == 12
    assert table["seq"] == list(range(12, 20)) == table["step"]
    assert table["tokens"] == [3] * 8
    assert t.rows() == [(i, "decode") for i in range(12, 20)]
    assert DispatchTrace(cap=8).dropped == 0


def test_a_rows_stamps_are_ordered_and_the_rows_are_contiguous():
    clock = _Clock()
    t = DispatchTrace(clock=clock)
    for step in range(3):
        clock.t += 0.5                  # the caller, between two steps
        t.begin()
        clock.t += 0.001
        t.issued()
        clock.t += 0.010
        assert t.ready() == pytest.approx(11.0)     # ms since t_begin
        # up to its tokens' arrival the open row counts already
        assert t.serve_seconds() == pytest.approx(step * 0.013 + 0.011)
        clock.t += 0.002
        t.record(step, "prefill", 8, 128, tokens_total=4 * (step + 1))
    table = t.table()
    for i in range(3):
        stamps = [table[k][i] for k in ("t_begin", "t_issued", "t_ready",
                                        "t_done")]
        assert stamps == sorted(stamps)
        assert stamps[3] - stamps[0] == pytest.approx(0.013)
    assert table["tokens"] == [4] * 3
    assert t.serve_seconds() == pytest.approx(3 * 0.013)
    # a second dispatch of one phase begins where the first was done
    t.issued()
    t.ready()
    t.record(3, "prefill", 8, 128)
    assert t.table()["t_begin"][3] == table["t_done"][2]
    assert t.table()["tokens"][3] == 0          # no count handed in


def test_a_bare_record_is_a_whole_row():
    t = DispatchTrace()
    t.record(0, "decode")
    t.record(0, "prefill")
    table = t.table()
    for i in range(2):
        assert table["t_begin"][i] <= table["t_issued"][i] \
            <= table["t_ready"][i] <= table["t_done"][i]
    assert t.rows() == [(0, "decode"), (0, "prefill")]
    assert t.decode_first_fraction() == 1.0


def test_classes_are_keyed_by_the_program():
    t = DispatchTrace()
    for program in (("decode", 40), ("prefill", 8, 128), ("decode", 40),
                    ("prefill", 1, 512), ("verify", 4), ("decode", 24)):
        t.record(0, *program)
    table = t.table()
    assert t.classes == [("decode", 40), ("prefill", 8, 128),
                         ("prefill", 1, 512), ("verify", 4), ("decode", 24)]
    assert table["class_id"] == [0, 1, 0, 2, 3, 4]
    assert table["kind"] == ["decode", "prefill", "decode", "prefill",
                             "verify", "decode"]
    assert table["cls"][3] == ("prefill", 1, 512)


def test_a_row_costs_microseconds():
    """Not a measurement (PERF.md has the chip host's): a guard against
    a row that allocates or searches."""
    import time
    t = DispatchTrace()
    n = 20000
    t0 = time.perf_counter()
    for i in range(n):
        t.begin()
        t.issued()
        t.ready()
        t.record(i, "decode", 40, tokens_total=160 * i)
    assert (time.perf_counter() - t0) / n < 50e-6
    assert t.dropped == n - t.cap


# ------------------------------------------------- a small engine's run
CFG = GPT2Config(vocab_size=61, max_position_embeddings=32, hidden_size=32,
                 num_layers=2, num_heads=4, embd_dropout=0.0,
                 attn_dropout=0.0, resid_dropout=0.0)
INF = {"max_batch_size": 3, "prompt_buckets": [4, 8],
       "batch_buckets": [1, 2], "max_seq_len": 32, "max_new_tokens": 4,
       "paged_kv": {"attn_kernel": "gather"}}


@pytest.fixture(scope="module")
def served():
    """A paged engine run for 40 steps with a backlog that outlasts
    them, its spans in a ChromeTraceRecorder; closed."""
    recorder = spans.ChromeTraceRecorder()
    spans.set_default_recorder(recorder)
    try:
        engine = InferenceEngine(
            CFG, init_gpt2_params(CFG, jax.random.PRNGKey(3)), INF,
            dtype=jnp.float32)
        engine.warmup()
        assert engine.dispatch_ledger.total == 0    # warm-up is no row
        rs = np.random.RandomState(0)
        for i in range(60):
            engine.submit(Request(
                prompt=[int(x) for x in rs.randint(1, 60, rs.randint(2, 9))],
                max_new_tokens=int(rs.randint(2, 7)), temperature=0.0,
                seed=i, eos_id=None))
        for _ in range(40):
            engine.step()
        assert not engine.scheduler.idle()
        ledger = engine.dispatch_ledger
        steps = engine._steps
        # the step's last dispatch is read in the step after: close()
        # takes the one still waiting, and its tokens are counted then
        engine.close()
        total_tokens = engine.scheduler.total_tokens
    finally:
        spans.set_default_recorder(None)
    return {"ledger": ledger, "events": list(recorder.events),
            "total_tokens": total_tokens, "steps": steps}


def _dispatch_events(served):
    return [ev for ev in served["events"]
            if ev["name"] in ("serve/prefill", "serve/decode")]


def test_every_dispatch_is_one_row_with_a_dense_seq(served):
    table = served["ledger"].table()
    events = _dispatch_events(served)
    assert served["ledger"].dropped == 0
    assert table["seq"] == list(range(len(events)))
    # at most one decode a step (none where every slot waits for the
    # value of a last token already asked for), each in a step of its own
    decode_steps = [s for s, k in zip(table["step"], table["kind"])
                    if k == "decode"]
    assert decode_steps == sorted(set(decode_steps))
    assert 30 <= len(decode_steps) <= 40 == served["steps"]
    # the spans carry the row's seq and step (the step that ISSUED the
    # dispatch), in the order dispatched, which is the order read
    assert [ev["args"]["seq"] for ev in events] == table["seq"]
    assert [ev["args"]["step"] for ev in events] == table["step"]
    assert [ev["name"].split("/")[1] for ev in events] == table["kind"]
    for ev, cls in zip(events, table["cls"]):
        if cls[0] == "prefill":
            assert cls[1:] == (ev["args"]["batch"], ev["args"]["prompt"])
        else:
            assert cls == ("decode", ev["args"]["table_pages"])


def test_the_intervals_tile_the_run_and_the_tokens_add_up(served):
    table = served["ledger"].table()
    t = {k: np.asarray(table[k]) for k in ("t_begin", "t_issued", "t_ready",
                                           "t_done")}
    assert (t["t_begin"] <= t["t_issued"]).all()
    assert (t["t_issued"] <= t["t_ready"]).all()
    assert (t["t_ready"] <= t["t_done"]).all()
    # a row begins no earlier than the row before was done
    assert (t["t_begin"][1:] >= t["t_done"][:-1]).all()
    start = np.concatenate([t["t_begin"][:1], t["t_done"][:-1]])
    legs = (t["t_issued"] - start) + (t["t_ready"] - t["t_issued"]) \
        + (t["t_done"] - t["t_ready"])
    assert legs.sum() == pytest.approx(t["t_done"][-1] - t["t_begin"][0],
                                       rel=1e-9)
    assert sum(table["tokens"]) == served["total_tokens"] > 0
    assert served["ledger"].serve_seconds() == pytest.approx(
        (t["t_done"] - t["t_begin"]).sum())


def test_the_ledger_outlives_close(served):
    assert spans.last_dispatch_ledger() is served["ledger"]
    # the engine last built or closed
    first, second = (InferenceEngine(
        CFG, init_gpt2_params(CFG, jax.random.PRNGKey(3)), INF,
        dtype=jnp.float32) for _ in range(2))
    assert spans.last_dispatch_ledger() is second.dispatch_ledger
    first.close()
    assert spans.last_dispatch_ledger() is first.dispatch_ledger
    second.close()
    assert spans.last_dispatch_ledger() is second.dispatch_ledger


class _Ticking:
    """A clock that moves a millisecond every time it is read."""

    def __init__(self):
        self.t = 50.0

    def __call__(self):
        self.t += 0.001
        return self.t


def test_deferred_rows_legs_sum_to_their_intervals_on_a_fake_clock():
    """The engine's own stamping, on a clock that ticks a read: a row is
    recorded when its tokens ARRIVE (after the next dispatch was
    issued), in the order issued; `t_issued` is where the host starts to
    block for it, `t_ready` where it holds the tokens; before + wait +
    after of every row is the time since the row before was done, so the
    rows tile the host's clock with deferred rows as they did without."""
    recorder = spans.ChromeTraceRecorder()
    spans.set_default_recorder(recorder)
    try:
        engine = InferenceEngine(
            CFG, init_gpt2_params(CFG, jax.random.PRNGKey(3)), INF,
            dtype=jnp.float32)
        clock = _Ticking()
        engine._dispatch_trace = ledger = DispatchTrace(clock=clock)
        rs = np.random.RandomState(1)
        for i in range(12):
            engine.submit(Request(
                prompt=[int(x) for x in rs.randint(1, 60, rs.randint(2, 9))],
                max_new_tokens=int(rs.randint(3, 7)), temperature=0.0,
                seed=i, eos_id=None))
        issued_in = []          # the step each dispatch was issued in
        plain = engine._issue

        def issue(name, *args):
            issued_in.append((engine._steps, name.split("/")[1]))
            return plain(name, *args)

        engine._issue = issue
        while not engine.scheduler.idle():
            engine.step()
        total_tokens = engine.scheduler.total_tokens
        engine.close()
    finally:
        spans.set_default_recorder(None)
    table = ledger.table()
    # a row a dispatch, in the order issued, under the step that issued it
    assert list(zip(table["step"], table["kind"])) == issued_in
    t = {k: np.asarray(table[k]) for k in ("t_begin", "t_issued", "t_ready",
                                           "t_done")}
    assert (t["t_begin"] < t["t_issued"]).all()
    assert (t["t_issued"] < t["t_ready"]).all()
    assert (t["t_ready"] < t["t_done"]).all()
    assert (t["t_begin"][1:] >= t["t_done"][:-1]).all()
    start = np.concatenate([t["t_begin"][:1], t["t_done"][:-1]])
    before, wait, after = (t["t_issued"] - start, t["t_ready"] - t["t_issued"],
                           t["t_done"] - t["t_ready"])
    np.testing.assert_allclose(before + wait + after, t["t_done"] - start,
                               rtol=0, atol=1e-9)
    assert (before + wait + after).sum() == pytest.approx(
        t["t_done"][-1] - t["t_begin"][0], abs=1e-9)
    # the wait leg is the read alone: one tick of this clock
    assert wait == pytest.approx(0.001)
    assert sum(table["tokens"]) == total_tokens > 0
    # the spans carry the rows' seq, and most reads were deferred (the
    # next dispatch's build and call are in their BEFORE leg)
    events = [ev for ev in recorder.events
              if ev["name"] in ("serve/prefill", "serve/decode")]
    assert [ev["args"]["seq"] for ev in events] == table["seq"]
    deferred = np.asarray([ev["args"]["deferred"] for ev in events])
    assert 0.7 * len(events) < deferred.sum() < len(events)


# ------------------------------------------- the reader, on a hand ledger
@pytest.fixture(scope="module")
def reader():
    sys.path.insert(0, BENCH)
    try:
        from loader import load_module
        yield load_module("readers", "dispatch_ledger")
    finally:
        sys.path.remove(BENCH)


def _hand_ledger(stall_s=0.0, shift=1.0, steps=200):
    """`steps` engine steps on a clock of its own: a prefill of one of
    two buckets in two steps of three, then a decode of 160 tokens; the
    host's and the device's parts each `shift` times as long; one
    planted stall before step 100's decode is issued."""
    clock = _Clock()
    t = DispatchTrace(clock=clock)
    tokens = 0
    for step in range(steps):
        if step % 3:
            bucket = (8, 128) if step % 3 == 1 else (32, 64)
            clock.t += 0.0005 * shift
            t.begin()
            clock.t += 0.0015 * shift
            t.issued()
            clock.t += (0.020 if step % 3 == 1 else 0.030) * shift
            t.ready()
            clock.t += 0.001 * shift
            tokens += bucket[0]
            t.record(step, "prefill", *bucket, tokens_total=tokens)
        t.begin()
        clock.t += 0.002 * shift + (stall_s if step == 100 else 0.0)
        t.issued()
        clock.t += 0.010 * shift
        t.ready()
        clock.t += 0.002 * shift
        tokens += 160
        t.record(step, "decode", 40, tokens_total=tokens)
    return t, clock.t


MEDIANS_S = 200 * 0.014 + 67 * 0.023 + 66 * 0.033


def _summary(reader, **kw):
    ledger, t_end = _hand_ledger(**kw)
    rows = reader.window_rows(ledger.table(), 100.0, t_end)
    return reader.summarize(rows), rows


def test_reader_on_a_hand_made_ledger(reader):
    s, rows = _summary(reader)
    # 200 decodes of 14 ms, 67 prefills of 23 and 66 of 33 (the half
    # millisecond before a prefill's t_begin is in its before leg)
    assert s["dispatches"] == 333
    assert s["intervals_s"] == pytest.approx(MEDIANS_S)
    assert s["tokens"] == 200 * 160 + 67 * 8 + 66 * 32
    assert s["stall_share_pct"] == pytest.approx(0.0, abs=1e-9)
    assert s["tokens_per_s_median_step"] == pytest.approx(
        s["tokens"] / s["intervals_s"])
    assert s["host_serial_ms"] == pytest.approx(4.0)      # the decodes'
    assert s["decode_wait_ms"] == pytest.approx(10.0)
    assert s["prefill_wait_ms"] == pytest.approx((67 * 20 + 66 * 30) / 133)
    table = reader.class_table(rows)
    assert list(table)[0] == ("decode", 40)
    assert table[("prefill", 32, 64)]["wait_ms"][0] == pytest.approx(30.0)


@pytest.mark.parametrize("fault, moves", [
    ({"stall_s": 0.200}, "stall_share_pct"),
    ({"shift": 1.01}, "tokens_per_s_median_step"),
])
def test_a_stall_and_a_uniform_shift_move_one_number_each(reader, fault,
                                                          moves):
    base, _ = _summary(reader)
    s, rows = _summary(reader, **fault)
    if moves == "stall_share_pct":
        assert s["stall_share_pct"] == pytest.approx(
            100 * 0.2 / MEDIANS_S, rel=1e-6)
        assert s["tokens_per_s_median_step"] == pytest.approx(
            base["tokens_per_s_median_step"], rel=1e-9)
        seq, step, cls, ms, excess, leg, leg_ms = reader.longest(rows)[0]
        assert (step, cls, leg) == (100, ("decode", 40), "before")
        assert excess == pytest.approx(200.0) == pytest.approx(leg_ms)
    else:
        assert s["stall_share_pct"] == pytest.approx(0.0, abs=1e-9)
        assert s["tokens_per_s_median_step"] == pytest.approx(
            base["tokens_per_s_median_step"] / 1.01, rel=1e-9)
        assert s["host_serial_ms"] == pytest.approx(4.04)


def test_a_class_seen_fewer_than_eight_times_is_its_own_median(reader):
    clock = _Clock()
    t = DispatchTrace(clock=clock)
    for step, ms in enumerate([10, 10, 10, 500, 10, 10, 10]):
        t.begin()
        clock.t += ms / 1e3
        t.record(step, "prefill", 32, 512, tokens_total=32 * (step + 1))
    rows = reader.window_rows(t.table(), 100.0, clock.t)
    assert reader.summarize(rows)["stall_share_pct"] == pytest.approx(0.0)
    # the window keeps the rows finished inside it and clips the first
    rows = reader.window_rows(t.table(), 100.015, 100.535)
    assert rows["seq"].tolist() == [1, 2, 3]
    assert rows["interval"].tolist() == pytest.approx([0.005, 0.010, 0.500])
    assert reader.window_rows(t.table(), 200.0, 300.0) is None


def test_the_two_clocks_join_by_seq_and_idle_splits_at_the_legs(reader):
    """Five dispatches on a trace clock 5 s ahead of the ledger's; the
    device idles 1 ms in every leg of the second."""
    ledger, _ = _hand_ledger(steps=3)       # decode, (prefill, decode) x 2
    table = ledger.table()
    off = 5e9
    ns = {k: [v * 1e9 + off for v in table[k]]
          for k in ("t_begin", "t_issued", "t_ready", "t_done")}
    host = []
    for i, kind in enumerate(table["kind"]):
        name = "serve/" + kind
        host.append([name, ns["t_begin"][i] + 1e5,
                     ns["t_ready"][i] - ns["t_begin"][i],
                     {"seq": i, "step": table["step"][i]}])
        host.append([name + "/wait", ns["t_issued"][i] + 1e4,
                     ns["t_ready"][i] - ns["t_issued"][i] - 1e4 - 500.0 * i,
                     {}])
    host.sort(key=lambda ev: ev[1])
    program = {"host": host, "devices": []}
    # (a span of another engine, the same seq at another step, is no row)
    host.insert(0, ["serve/decode", ns["t_begin"][0], 1e6,
                    {"seq": 0, "step": 7}])
    offsets = reader.clock_offsets(program, table)
    assert sorted(off - offsets) == pytest.approx(
        [0.0, 500.0, 1000.0, 1500.0, 2000.0], abs=1.0)
    # the device is busy except 1 ms at the start of each leg of row 1
    lo, hi = ns["t_done"][0], ns["t_done"][1]
    holes = [[ns["t_done"][0], ns["t_done"][0] + 1e6],
             [ns["t_issued"][1], ns["t_issued"][1] + 1e6],
             [ns["t_ready"][1], ns["t_ready"][1] + 1e6]]
    busy = reader.tr.subtract([[lo, hi]], holes)
    program["devices"] = [{"ops": [["fusion", s, e - s, "", "fusion"]
                                   for s, e in busy]}]
    view = {"planes": [
        {"name": "/device:TPU:0", "lines": [
            {"name": reader.tr.OPS_LINE,
             "events": [["fusion", s, e - s] for s, e in busy]}]},
        {"name": "/host:CPU", "lines": [
            {"name": "main", "events": [["bench/trace_window", lo,
                                         hi - lo]]}]}]}
    idle = reader.idle_by_leg(view, program, table, off)
    assert [idle[k] for k in ("before", "wait", "after", "outside")] == \
        pytest.approx([1e6, 1e6, 1e6, 0.0], abs=1.0)


# --------------------------------------------------- what is declared
@pytest.mark.parametrize("name", sorted(METRICS))
def test_the_metric_is_declared_for_both_serving_cells(name):
    with open(os.path.join(REPO, "BENCHMARK.json")) as f:
        bench = json.load(f)
    entry = next(m for m in bench["per_layer"] if m["name"] == name)
    # PR 39's two cells first; a served cell added later joins the list
    assert entry["workloads"][:2] == CELLS and set(entry["workloads"]) <= {
        w["name"] for w in bench["workloads"] if ".serve-" in w["name"]}
    assert (entry["source"], entry["layer"], entry["moves"]) == \
        ("program_counter", "serving loop", "serve_tokens_per_s")
    # appended in PR 39 in this order, and still together: what later
    # PRs add comes after them
    names = [m["name"] for m in bench["per_layer"]]
    first = names.index("serve_stall_share.sat")
    assert names[first:first + 5] == [
        "serve_stall_share.sat", "serve_tokens_per_s_median_step.sat",
        "serve_host_serial_ms.sat", "serve_decode_wait_ms.sat",
        "serve_prefill_wait_ms.sat"]
    with open(os.path.join(BENCH, "metrics", name + ".json")) as f:
        spec = json.load(f)
    assert spec["reader"] == "dispatch_ledger"
    assert spec["params"] == {"value": METRICS[name]}
    assert (spec["unit"], spec["layer"], spec["moves"]) == \
        (entry["unit"], entry["layer"], entry["moves"])


def test_a_program_without_the_ledger_reads_nothing(reader, monkeypatch):
    """The parent commit under this benchmark: no such function."""
    monkeypatch.delattr(spans, "last_dispatch_ledger")

    class Ctx:
        setup_s = 1.0
        trace_dir = "/nonexistent"
        log = staticmethod(lambda msg: None)

    assert reader.ledger_of_process() is None
    assert reader.read(view=None, facts={"window_s": 1.0}, ctx=Ctx(),
                       value="stall_share_pct") is None
