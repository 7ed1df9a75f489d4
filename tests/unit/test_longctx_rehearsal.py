"""The CPU rehearsal of the benchmark cell that ISSUE 48 adds
(`kimi-linear-48b-a3b.serve-longctx-saturated`), as the driver's harness
would run it: `benchmarks/run.py --rehearse-cpu` in a process of its
own, at the cell's `tiny` sizes (chunks of 16) with the kernels in
interpret mode. It proves the cell's files are found by name, prompts
cross chunk boundaries through `submit` / `step`, and the served tokens
pass the reference's comparison; it prints no result line and measures
nothing. A file of its own, so that under `--dist loadfile` its minute
and a half falls to another worker than `test_kimi_linear.py`'s."""

import json
import os
import subprocess
import sys

REPO = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
CELL = "kimi-linear-48b-a3b.serve-longctx-saturated"


def test_the_long_context_cell_rehearses_on_the_cpu(tmp_path):
    env = dict(os.environ, JAX_PLATFORMS="cpu",
               JAX_COMPILATION_CACHE_DIR=str(tmp_path / "cache"))
    out = subprocess.run(
        [sys.executable, os.path.join(REPO, "benchmarks", "run.py"),
         "--workload", CELL, "--seed", "4000000007", "--seconds", "1",
         "--trace", "0", "--rehearse-cpu"],
        cwd=REPO, env=env, capture_output=True, text=True, timeout=900)
    assert out.returncode == 3, out.stdout[-2000:] + out.stderr[-2000:]
    last = [ln for ln in out.stdout.splitlines()
            if ln.startswith("[bench] rehearsal on cpu")]
    assert last, out.stdout[-2000:]
    line = json.loads(last[0].split("): ", 1)[1])
    assert line["correct"] and line["failed"] == 0 and line["attempted"] > 0
    assert set(line["metrics"]) == {"serve_tokens_per_s", "setup_s"}
    assert "chunked prefill 16" in out.stdout
    # 2 chunk buckets, the decode, and the token merge at its 3 shapes
    assert "6 programs warm" in out.stdout


def test_the_cell_is_declared_as_the_issue_names_it():
    with open(os.path.join(REPO, "BENCHMARK.json")) as f:
        bench = json.load(f)
    cell = next(w for w in bench["workloads"] if w["name"] == CELL)
    assert (cell["config"], cell["traffic"], cell["chips"]) == \
        ("kimi-linear-48b-a3b", "serve-longctx-saturated", 1)
    assert len(cell["why"]) <= 200
    with open(os.path.join(REPO, "benchmarks", "traffic",
                           "serve-longctx-saturated.json")) as f:
        traffic = json.load(f)
    assert traffic["kind"] == "serve_backlog"
    assert traffic["prompt"] == {"median": 8192, "sigma": 0.5, "low": 2048,
                                 "high": 16384}
    assert traffic["output"] == {"median": 512, "sigma": 0.5, "low": 128,
                                 "high": 2048}
    assert (traffic["backlog_requests"], traffic["epoch_requests"],
            traffic["order_seed"], traffic["check_requests"],
            traffic["check_pad_to"]) == (1024, 64, 48, 4, 18432)
    with open(os.path.join(REPO, "benchmarks", "configs",
                           "kimi-linear-48b-a3b.json")) as f:
        inference = json.load(f)["serve"]["inference"]
    assert inference["chunked_prefill"] == {"enabled": True,
                                            "chunk_tokens": 2048}
    assert (inference["max_batch_size"], inference["max_seq_len"],
            inference["batch_buckets"], inference["prompt_buckets"]) == \
        (64, 18432, [1, 2], [2048])
    served = next(m for m in bench["end_to_end"]
                  if m["name"] == "serve_tokens_per_s")
    assert CELL in served["workloads"]
    reported = {m["name"] for m in bench["per_layer"]
                if CELL in m.get("workloads", [])}
    assert {"prefill_mfu.longctx", "decode_hbm_roofline.longctx",
            "mla_prefix_roofline.longctx", "kda_scan_roofline.longctx",
            "chunk_carried_share.longctx", "serve_stall_share.sat",
            "prefill_scope_mla_prefix_ms.longctx"} <= reported
    for m in bench["per_layer"]:
        if m["name"].endswith(".longctx"):
            assert m["workloads"] == [CELL]
            assert m["moves"] == "serve_tokens_per_s"
            assert os.path.exists(os.path.join(
                REPO, "benchmarks", "metrics", m["name"] + ".json"))
