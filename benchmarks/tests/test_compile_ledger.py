"""`readers/compile_ledger.py` on hand-made ledgers: the six numbers and
the partition of set-up. Run by hand:

    python -m pytest benchmarks/tests -q

No chip, no program code: a ledger here is the dict `CompileLedger.table()`
hands out.
"""

import os
import sys

import pytest

BENCH_DIR = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, BENCH_DIR)

from loader import load_module  # noqa: E402

cl = load_module("readers", "compile_ledger")


def program(t_begin, t_end, trace_s, lower_s, backend_s, phase,
            cache="miss", name=None, cls=None, **more):
    return dict({"seq": 0, "fun_name": "jit(f)", "name": name, "cls": cls,
                 "step": None, "phase": phase, "t_begin": t_begin,
                 "t_end": t_end, "trace_s": trace_s, "lower_s": lower_s,
                 "backend_s": backend_s, "cache": cache, "written": False,
                 "retrieval_s": None, "saved_s": None, "call_s": None,
                 "changed": None}, **more)


def span(name, t0, t1, parent=None, cls=None):
    return {"name": name, "t0": t0, "t1": t1, "parent": parent, "cls": cls}


# the process starts at 100 and its window opens at 160
T0, T_OPEN = 100.0, 160.0
LEDGER = {
    "programs": [
        # the harness's own jit, outside every span: 1.5 s
        program(106.0, 107.5, 0.2, 0.3, 0.9, "steady"),
        # a pool's zeros inside the engine's span: 0.5 s
        program(111.0, 111.5, 0.1, 0.1, 0.3, "setup/engine/state",
                cache="hit"),
        # two warmed programs: 6 s and 4 s of call, the second a hit
        program(121.0, 127.0, 1.0, 1.5, 3.0, "setup/program",
                name="prefill", cls=("prefill", 8, 128), written=True),
        program(128.0, 132.0, 0.5, 0.5, 2.0, "setup/program",
                cache="hit", name="decode", cls=("decode", 40)),
        # built inside the second's trace: its time is that row's
        program(128.1, 128.3, 0.0, 0.0, 0.2, "setup/program",
                cache="not_asked"),
        # the reference, compiled after the window opened
        program(210.0, 215.0, 1.0, 1.0, 3.0, "steady"),
    ],
    "spans": [
        span("setup/import", 102.0, 105.0),
        span("setup/engine/params", 110.0, 111.0, "setup/engine"),
        span("setup/engine/state", 111.0, 113.0, "setup/engine"),
        span("setup/engine", 110.0, 114.0),
        span("setup/program", 121.0, 127.0, "setup/warmup",
             ("prefill", 8, 128)),
        span("setup/program", 128.0, 132.0, "setup/warmup", ("decode", 40)),
        span("setup/warmup", 120.0, 133.0),
    ],
}
# the ramp: dispatches from 135 to 160, one after another
RAMP = [[135.0 + i, 136.0 + i] for i in range(25)]


def test_the_six_numbers_of_a_hand_made_ledger():
    s = cl.summarize(LEDGER, T0, T_OPEN, RAMP)
    # the reference's row ends after the opening: five rows count
    assert s["programs_built"] == 5
    assert s["trace_lower_s"] == pytest.approx(0.5 + 0.2 + 2.5 + 1.0)
    assert s["backend_s"] == pytest.approx(0.9 + 0.3 + 3.0 + 2.0 + 0.2)
    # two hits of five; the row the cache was not asked for counts against
    assert s["cache_hit_share_pct"] == pytest.approx(40.0)
    # the engine's 4 s less the 0.5 s of the program built inside it
    assert s["engine_s"] == pytest.approx(3.5)
    # before the import 2, the ramp 25, and what no span or row holds:
    # 105-106, 107.5-110, 114-120, 133-135
    assert s["outside_startup_s"] == pytest.approx(
        2.0 + 25.0 + 1.0 + 2.5 + 6.0 + 2.0)
    assert s["setup_s"] == pytest.approx(60.0)


def test_the_partition_counts_every_second_once():
    parts = cl.partition(LEDGER, T0, T_OPEN, RAMP)
    assert set(parts) == set(cl.PARTS)
    assert sum(parts.values()) == pytest.approx(T_OPEN - T0, abs=1e-9)
    assert parts["before_import"] == pytest.approx(2.0)
    assert parts["import"] == pytest.approx(3.0)
    assert parts["engine"] == pytest.approx(3.5)
    # inside the spans: 0.5 + 6 + 4 s of building calls; the program
    # built inside another's trace adds nothing of its own
    assert parts["programs_trace_lower"] == pytest.approx(0.2 + 2.5 + 1.0)
    assert parts["programs_backend"] == pytest.approx(0.3 + 3.0 + 2.0)
    assert parts["programs_rest"] == pytest.approx(
        10.5 - 3.7 - 5.3)
    # warm-up's 13 s less its two calls
    assert parts["warmup_rest"] == pytest.approx(3.0)
    assert parts["programs_outside_spans"] == pytest.approx(1.5)
    assert parts["ramp"] == pytest.approx(25.0)
    assert parts["remainder"] == pytest.approx(1.0 + 2.5 + 6.0 + 2.0)


def test_a_training_process_has_no_ramp_and_its_first_batch_is_warmup():
    ledger = {
        "programs": [program(12.0, 40.0, 3.0, 5.0, 19.0, "setup/program",
                             cls=("train_batch",))],
        "spans": [span("setup/import", 1.0, 4.0),
                  span("setup/engine", 5.0, 9.0),
                  span("setup/program", 10.0, 41.0, None,
                       ("train_batch",))]}
    parts = cl.partition(ledger, 0.0, 60.0)
    assert sum(parts.values()) == pytest.approx(60.0, abs=1e-9)
    assert parts["ramp"] == 0.0
    assert parts["warmup_rest"] == pytest.approx(3.0)      # 31 - 28
    assert parts["programs_rest"] == pytest.approx(1.0)    # 28 - 27
    # 0-1 before the import; 4-5, 9-10 and 41-60 under nothing
    assert parts["before_import"] == pytest.approx(1.0)
    assert parts["remainder"] == pytest.approx(1.0 + 1.0 + 19.0)


def test_spans_and_rows_that_end_after_the_opening_do_not_count():
    ledger = {"programs": [program(50.0, 70.0, 1.0, 1.0, 10.0, "steady")],
              "spans": [span("setup/engine", 55.0, 65.0)]}
    parts = cl.partition(ledger, 0.0, 60.0)
    assert parts["remainder"] == pytest.approx(60.0)
    assert cl.summarize(ledger, 0.0, 60.0)["programs_built"] == 0
    # and a process with no ledger rows at all gives nothing to read
    assert cl.summarize({"programs": [], "spans": []}, 0.0, 60.0) is None


class _Ctx:
    def __init__(self):
        self.lines, self.setup_s = [], T_OPEN - T0

    def log(self, msg):
        self.lines.append(msg)


def test_read_logs_the_table_and_the_partition_once(monkeypatch):
    class Ledger:
        total, dropped = 6, 0

        @staticmethod
        def table():
            return LEDGER
    monkeypatch.setattr(cl, "ledger_of_process", lambda: Ledger)
    monkeypatch.setattr(cl, "_dispatches", lambda facts, t_open: (
        RAMP, {("decode", 40): 900}))
    monkeypatch.setattr(sys.modules["__main__"], "T_PROCESS", T0,
                        raising=False)
    ctx, facts = _Ctx(), {"window_s": 45.0}
    assert cl.read(None, facts, ctx, "programs_built") == 5
    assert cl.read(None, facts, ctx, "engine_s") == pytest.approx(3.5)
    text = "\n".join(ctx.lines)
    assert text.count("set-up by part") == 1
    assert "sum 60.000 beside setup_s 60.000" in text
    assert "program prefill [prefill 8 128] in setup/program: trace 1.000 " \
        "lower 1.500 backend 3.000 rest 0.500 s, cache miss" in text
    assert "rest 0.500 s, cache miss, 0 dispatches" not in text
    assert "900 dispatches of its class in the window" in text
    # the harness's own jit is no steady-state build, and a miss that
    # was not written says so
    assert "program jit(f) in outside spans: trace 0.200 lower 0.300 " \
        "backend 0.900 rest 0.100 s, cache miss, not kept" in text
    assert " in steady" not in text
    # a program without the ledger (the parent commit): nothing to read
    monkeypatch.setattr(cl, "ledger_of_process", lambda: None)
    assert cl.read(None, {"window_s": 45.0}, _Ctx(), "engine_s") is None


@pytest.mark.parametrize("row, phase, cache", [
    (program(1.0, 2.0, 0.1, 0.1, 0.5, "steady"),
     "outside spans", "miss, not kept"),
    (program(1.0, 2.0, 0.1, 0.1, 0.5, "steady", name="decode",
             written=True), "steady", "miss"),
    (program(1.0, 2.0, 0.1, 0.1, 0.5, "setup/program", cache="hit",
             retrieval_s=0.25, saved_s=3.0),
     "setup/program", "hit (loaded in 0.250 s, saved 3.000)"),
    (program(1.0, 2.0, 0.1, 0.1, 0.5, "setup/engine/state",
             cache="not_asked"), "setup/engine/state", "not_asked"),
])
def test_the_logs_words_for_a_rows_phase_and_cache(row, phase, cache):
    """Before the window only a TRACKED row outside the spans is a
    steady-state build; the others are the harness's own jits. A miss
    that was not written will miss in the next process too."""
    assert (cl._phase(row), cl._cache(row)) == (phase, cache)


def test_the_log_groups_the_cheaper_programs_by_name_and_phase():
    rows = [program(float(i), i + 0.5, 0.1, 0.1, 0.2, "steady")
            for i in range(12)]
    ctx = _Ctx()
    cl._log_programs(ctx, rows, {}, top=10)
    assert len(ctx.lines) == 11
    assert ctx.lines[-1] == ("  and 2 x jit(f) in outside spans: 1.000 s "
                             "in all, 0 from the cache, 2 missed and not "
                             "kept")
