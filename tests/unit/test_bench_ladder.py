"""Bench ladder hardening (ISSUE 6 satellite; ROADMAP meta item).

r02–r05 produced zero hardware numbers because one hung device zeroed
each revision's perf record. The contracts pinned here, against the
importable ladder helpers in bench.py (no device, no child process
unless marked slow):

- probe-before-run: a dead device yields explicit ``device_unreachable``
  skip rows for every hardware metric — fast — instead of hanging
  per-metric; hardware-free rows still land.
- resume-from-partial: a rerun at the same source digest reuses the
  fsynced partial rows and only runs missing metrics; a different
  digest never resumes them as measurements (only as clearly-labeled
  stale context on error rows).
- row salvage: a child killed by the per-metric timeout AFTER its row
  streamed (teardown hang — the historical failure) keeps the
  measurement instead of discarding it.
"""

import json
import subprocess
import sys
import time

import pytest

sys.path.insert(0, __file__.rsplit("/tests/", 1)[0])
import bench  # noqa: E402


def _row(metric, value=1.0, unit="u"):
    return {"metric": metric, "value": value, "unit": unit,
            "vs_baseline": 1.0, "detail": {}}


# ------------------------------------------------------- resume-from-partial


def test_partial_roundtrip_resumes_same_head(monkeypatch, tmp_path):
    monkeypatch.setattr(bench, "PARTIAL_PATH", str(tmp_path / "p.jsonl"))
    row = _row("m1")
    fresh = bench._append_partial("src-AAAA", row, True)
    assert fresh is False                    # header written
    fresh = bench._append_partial("src-AAAA", _row("m2"), fresh)
    got = bench._load_partial("src-AAAA")
    assert set(got) == {"m1", "m2"} and got["m1"] == row


def test_partial_never_resumes_across_source_digests(monkeypatch,
                                                     tmp_path):
    monkeypatch.setattr(bench, "PARTIAL_PATH", str(tmp_path / "p.jsonl"))
    bench._append_partial("src-AAAA", _row("m1", 7.0), True)
    assert bench._load_partial("src-BBBB") == {}
    stale = bench._stale_partial("src-BBBB")
    assert stale["rows"]["m1"]["value"] == 7.0
    assert "NOT a current measurement" in stale["note"]
    assert bench._stale_partial("src-AAAA") is None   # same digest: resume


def test_partial_skips_error_rows_and_no_resume_knob(monkeypatch,
                                                     tmp_path):
    monkeypatch.setattr(bench, "PARTIAL_PATH", str(tmp_path / "p.jsonl"))
    fresh = bench._append_partial("src-AAAA", _row("good"), True)
    bench._append_partial(
        "src-AAAA", {"metric": "bad", "value": 0.0, "unit": "error",
                     "vs_baseline": 0.0, "detail": {"error": "x"}}, fresh)
    got = bench._load_partial("src-AAAA")
    assert "good" in got and "bad" not in got     # errors rerun
    monkeypatch.setenv("BENCH_NO_RESUME", "1")
    assert bench._load_partial("src-AAAA") == {}


# ------------------------------------------------------------- row salvage


def test_last_metric_row_takes_last_match():
    out = "\n".join(["garbage", json.dumps(_row("m", 1.0)),
                     json.dumps(_row("other", 9.0)),
                     json.dumps(_row("m", 2.0))])
    assert bench._last_metric_row(out, "m")["value"] == 2.0
    assert bench._last_metric_row("", "m") is None
    assert bench._last_metric_row("{not json", "m") is None


def test_watchdog_error_row_does_not_clobber_a_streamed_value_row(
        monkeypatch):
    """A child whose in-process stall watchdog fires during TEARDOWN —
    after the measurement row already streamed — appends a
    device_unreachable error row last and os._exit(2)s. The parent must
    keep the completed measurement (flagged), not discard it for the
    trailing error row."""
    value = _row("m", 4.2)
    err = {"metric": "m", "value": 0.0, "unit": "error",
           "vs_baseline": 0.0,
           "detail": {"error": "device_unreachable: no progress"}}
    out = json.dumps(value) + "\n" + json.dumps(err) + "\n"
    assert bench._last_metric_row(out, "m")["value"] == 4.2

    class R:
        stdout, stderr, returncode = out, "", 2

    monkeypatch.setattr(bench.subprocess, "run", lambda *a, **k: R())
    got, errmsg = bench._run_metric_subprocess("m")
    assert errmsg is None and got["value"] == 4.2
    assert "salvaged" in got["detail"]
    # error-only output still reports the error
    class R2:
        stdout, stderr, returncode = json.dumps(err) + "\n", "", 2

    monkeypatch.setattr(bench.subprocess, "run", lambda *a, **k: R2())
    got, errmsg = bench._run_metric_subprocess("m")
    assert got is None and "device_unreachable" in errmsg


def test_timed_out_child_with_streamed_row_is_salvaged(monkeypatch):
    row = _row("decode_throughput", 5.0, "tokens_per_s")

    def fake_run(cmd, **kw):
        raise subprocess.TimeoutExpired(
            cmd, kw.get("timeout") or 1,
            output=json.dumps(row) + "\n")

    monkeypatch.setattr(bench.subprocess, "run", fake_run)
    got, err = bench._run_metric_subprocess("decode_throughput")
    assert err is None and got["value"] == 5.0
    assert "salvaged" in got["detail"]


def test_timed_out_child_without_row_reports_timeout(monkeypatch):
    def fake_run(cmd, **kw):
        raise subprocess.TimeoutExpired(cmd, kw.get("timeout") or 1)

    monkeypatch.setattr(bench.subprocess, "run", fake_run)
    got, err = bench._run_metric_subprocess("decode_throughput")
    assert got is None and "exceeded" in err
    # a streamed ERROR row is not a measurement either
    err_row = {"metric": "decode_throughput", "value": 0.0,
               "unit": "error", "vs_baseline": 0.0,
               "detail": {"error": "device_unreachable: stalled"}}

    def fake_run2(cmd, **kw):
        raise subprocess.TimeoutExpired(cmd, kw.get("timeout") or 1,
                                        output=json.dumps(err_row))

    monkeypatch.setattr(bench.subprocess, "run", fake_run2)
    got, err = bench._run_metric_subprocess("decode_throughput")
    assert got is None


# ---------------------------------------------------------- probe-before-run


def test_dead_device_yields_explicit_skip_rows(monkeypatch, capsys,
                                               tmp_path):
    """End-to-end parent path with a dead device: hardware metrics
    become explicit device_unreachable error rows IMMEDIATELY (two
    probes, no per-metric timeout burn), the headline error row is
    last, and nothing hangs."""
    monkeypatch.setattr(bench, "PARTIAL_PATH", str(tmp_path / "p.jsonl"))
    monkeypatch.setattr(bench, "METRICS",
                        ["hw_a", "gpt2_train_mfu"])
    monkeypatch.setattr(bench, "HW_FREE", set())
    monkeypatch.setattr(bench, "HEADLINE", "gpt2_train_mfu")
    monkeypatch.setattr(bench, "_probe_device", lambda *a, **k: False)
    monkeypatch.setattr(bench, "_T_START", time.monotonic())
    monkeypatch.setattr(bench.time, "sleep", lambda s: None)
    monkeypatch.setattr(bench.sys, "argv", ["bench.py"])
    ran = []
    monkeypatch.setattr(bench, "_run_metric_subprocess",
                        lambda m: ran.append(m) or (None, "should not run"))
    bench.main()
    out = capsys.readouterr().out
    rows = [json.loads(l) for l in out.splitlines()
            if l.strip().startswith("{")]
    assert ran == []                       # no child burned a timeout
    assert rows and all(r["unit"] == "error" for r in rows)
    for r in rows:
        assert "device_unreachable" in r["detail"]["error"]
        assert r["detail"].get("skipped") is True
    assert rows[-1]["metric"] == "gpt2_train_mfu"   # headline last


def test_hw_free_rows_land_even_with_dead_device(monkeypatch, capsys,
                                                 tmp_path):
    """The hardware-free rows run in forced-CPU children and must land
    (and checkpoint) before any device probe happens."""
    monkeypatch.setattr(bench, "PARTIAL_PATH", str(tmp_path / "p.jsonl"))
    monkeypatch.setattr(bench, "METRICS", ["freebie", "gpt2_train_mfu"])
    monkeypatch.setattr(bench, "HW_FREE", {"freebie"})
    monkeypatch.setattr(bench, "HEADLINE", "gpt2_train_mfu")
    monkeypatch.setattr(bench, "_probe_device", lambda *a, **k: False)
    monkeypatch.setattr(bench, "_T_START", time.monotonic())
    monkeypatch.setattr(bench.time, "sleep", lambda s: None)
    monkeypatch.setattr(bench.sys, "argv", ["bench.py"])
    monkeypatch.setattr(
        bench, "_run_metric_subprocess",
        lambda m: (_row(m, 3.0), None) if m == "freebie"
        else (None, "nope"))
    monkeypatch.setattr(bench, "_git_head", lambda: "src-TEST")
    bench.main()
    out = capsys.readouterr().out
    rows = [json.loads(l) for l in out.splitlines()
            if l.strip().startswith("{")]
    by_metric = {r["metric"]: r for r in rows}     # last occurrence wins
    assert by_metric["freebie"]["value"] == 3.0
    assert by_metric["gpt2_train_mfu"]["unit"] == "error"
    # and the good row was checkpointed for resume
    assert "freebie" in bench._load_partial("src-TEST")


# ------------------------------------------------- stalled-child postmortem


def test_stalled_child_black_box_is_salvaged(monkeypatch, tmp_path):
    """ISSUE 15: a child whose stall watchdog fired dumps its flight
    ring before os._exit(2) and names the stall in its error row; the
    parent folds BOTH into _STALL_POSTMORTEMS keyed by metric."""
    flight = str(tmp_path / "flight.json")
    monkeypatch.setenv("BENCH_FLIGHT_PATH", flight)
    monkeypatch.setattr(bench, "_STALL_POSTMORTEMS", {})
    err = {"metric": "m", "value": 0.0, "unit": "error",
           "vs_baseline": 0.0,
           "detail": {"error": "device_unreachable: no benchmark "
                               "progress for 300s (device hung?)",
                      "skipped": True,
                      "stall_detected": {"phase": "bench_metric",
                                         "flight": flight}}}

    def fake_run(cmd, **kw):
        # the "child": dumps its black box, then streams the error row
        with open(flight, "w") as f:
            json.dump({"trigger": "bench_stall",
                       "rows": [{"event": "bench_start", "metric": "m"},
                                {"event": "bench_beat", "t_mono": 1.0}],
                       "stall": {"metric": "m", "phase": "bench_metric",
                                 "timeout_s": 300},
                       "stacks": {"MainThread (1)": ["wedged here"]}}, f)

        class R:
            stdout = json.dumps(err) + "\n"
            stderr, returncode = "", 2
        return R()

    monkeypatch.setattr(bench.subprocess, "run", fake_run)
    got, errmsg = bench._run_metric_subprocess("m")
    assert got is None and "device_unreachable" in errmsg
    post = bench._STALL_POSTMORTEMS["m"]
    assert post["stall_detected"]["phase"] == "bench_metric"
    assert post["flight"]["trigger"] == "bench_stall"
    assert post["flight"]["rows"] == 2       # pre-stall ring survived
    assert post["flight"]["stall"]["phase"] == "bench_metric"
    assert post["flight"]["threads"] == 1
    # a stale flight file is REMOVED before the next launch — it must
    # never masquerade as a fresh dump
    seen = []

    def fake_run2(cmd, **kw):
        seen.append(bench.os.path.exists(flight))

        class R:
            stdout = json.dumps(_row("m", 1.0)) + "\n"
            stderr, returncode = "", 0
        return R()

    monkeypatch.setattr(bench.subprocess, "run", fake_run2)
    got, errmsg = bench._run_metric_subprocess("m")
    assert got is not None and seen == [False]


def test_error_row_carries_stall_postmortem(monkeypatch, capsys,
                                            tmp_path):
    """main()'s explicit error row for a stalled metric includes the
    salvaged postmortem under detail.stalled."""
    monkeypatch.setattr(bench, "PARTIAL_PATH", str(tmp_path / "p.jsonl"))
    monkeypatch.setattr(bench, "METRICS", ["stuck"])
    monkeypatch.setattr(bench, "HW_FREE", {"stuck"})
    monkeypatch.setattr(bench, "HEADLINE", "stuck")
    monkeypatch.setattr(bench, "_T_START", time.monotonic())
    monkeypatch.setattr(bench.sys, "argv", ["bench.py"])
    monkeypatch.setattr(bench, "_git_head", lambda: "src-TEST")
    post = {"stall_detected": {"phase": "bench_metric", "flight": "/f"},
            "flight": {"path": "/f", "trigger": "bench_stall",
                       "rows": 7, "stall": None, "threads": 3}}
    monkeypatch.setattr(bench, "_STALL_POSTMORTEMS", {"stuck": post})
    monkeypatch.setattr(bench, "_run_metric_subprocess",
                        lambda m: (None, "metric subprocess exceeded "
                                         "300s (killed)"))
    bench.main()
    rows = [json.loads(l) for l in capsys.readouterr().out.splitlines()
            if l.strip().startswith("{")]
    row = rows[-1]
    assert row["metric"] == "stuck" and row["unit"] == "error"
    assert row["detail"]["stalled"] == post
    assert row["detail"]["stalled"]["flight"]["rows"] == 7


def test_health_overhead_is_in_the_ladder():
    assert "health_overhead" in bench.METRICS
    assert "health_overhead" in bench.HW_FREE
    # hardware-free: runs before the device probe, in canonical order
    assert (bench.METRICS.index("health_overhead")
            < bench.METRICS.index("bert_large_samples_per_s"))


# ------------------------------------------------------------- comm row


def test_comm_overlap_structure_is_in_the_ladder():
    assert "comm_overlap_structure" in bench.METRICS
    assert "comm_overlap_structure" in bench.HW_FREE
    # hardware-free rows run before the device probe, in canonical order
    assert (bench.METRICS.index("comm_overlap_structure")
            < bench.METRICS.index("bert_large_samples_per_s"))


@pytest.mark.slow
def test_bench_comm_overlap_structure_row():
    """The hardware-free row lands a real JSON row from a fresh child
    (same invocation the ladder parent uses): overlapped fraction 1.0,
    serial control 0.0, flush collectives outside the loop."""
    import os
    repo = __file__.rsplit("/tests/", 1)[0]
    env = dict(os.environ)
    env["JAX_PLATFORMS"] = "cpu"
    env["XLA_FLAGS"] = (env.get("XLA_FLAGS", "") +
                        " --xla_force_host_platform_device_count=8")
    r = subprocess.run(
        [sys.executable, os.path.join(repo, "bench.py"),
         "--metric", "comm_overlap_structure"],
        capture_output=True, text=True, timeout=420, env=env, cwd=repo)
    rows = [json.loads(l) for l in r.stdout.splitlines()
            if l.strip().startswith("{")]
    assert rows, (r.stdout[-2000:], r.stderr[-2000:])
    row = rows[-1]
    assert row["metric"] == "comm_overlap_structure"
    assert row["value"] == 1.0
    assert row["detail"]["serial_overlap_fraction"] == 0.0
    assert row["detail"]["flush_outside_loop"] >= 2
    assert 0 < row["vs_baseline"] <= 1.0
