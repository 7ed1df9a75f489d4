"""The CPU rehearsal of the benchmark cell that ISSUE 32 adds
(`smallthinker-21b-a3b.train-8k`), as the driver's harness would run
it: `benchmarks/run.py --rehearse-cpu` in a process of its own, at the
cell's `tiny` sizes with the kernels in interpret mode. It proves the
cell's files are found by name, the family's model trains through
`initialize` / `train_batch`, the reference comparison and the counters
work; it prints no result line and measures nothing."""

import json
import os
import subprocess
import sys

REPO = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
CELL = "smallthinker-21b-a3b.train-8k"


def test_new_cell_rehearses_on_the_cpu(tmp_path):
    env = dict(os.environ, JAX_PLATFORMS="cpu",
               JAX_COMPILATION_CACHE_DIR=str(tmp_path / "cache"))
    out = subprocess.run(
        [sys.executable, os.path.join(REPO, "benchmarks", "run.py"),
         "--workload", CELL, "--seed", "4000000007", "--seconds", "1",
         "--trace", "0", "--rehearse-cpu"],
        cwd=REPO, env=env, capture_output=True, text=True, timeout=600)
    assert out.returncode == 3, out.stdout[-2000:] + out.stderr[-2000:]
    last = [ln for ln in out.stdout.splitlines()
            if ln.startswith("[bench] rehearsal on cpu")]
    assert last, out.stdout[-2000:]
    line = json.loads(last[0].split("): ", 1)[1])
    assert line["correct"] and line["failed"] == 0 and line["attempted"] > 0
    assert set(line["metrics"]) == {"train_tokens_per_s", "setup_s"}


def test_the_cell_is_declared_as_the_issue_names_it():
    with open(os.path.join(REPO, "BENCHMARK.json")) as f:
        bench = json.load(f)
    cell = next(w for w in bench["workloads"] if w["name"] == CELL)
    assert (cell["config"], cell["traffic"], cell["chips"]) == \
        ("smallthinker-21b-a3b", "train-8k", 1)
    with open(os.path.join(REPO, "benchmarks", "traffic",
                           "train-8k.json")) as f:
        traffic = json.load(f)
    assert (traffic["seq"], traffic["micro_batch_per_chip"],
            traffic["zipf_exponent"], traffic["successor_share"]) == \
        (8192, 2, 0.0, 0.5)
    with open(os.path.join(REPO, "benchmarks", "configs",
                           "smallthinker-21b-a3b.json")) as f:
        config = json.load(f)
    published = dict(hidden_size=2560, num_attention_heads=28,
                     num_key_value_heads=4, head_dim=128,
                     moe_ffn_hidden_size=768, moe_router_outputs=64,
                     moe_num_active_primary_experts=6,
                     sliding_window_size=4096, rope_theta=1500000)
    assert {k: config[k] for k in published} == published
    assert config["rope_layout"] == [0, 1, 1, 1] * 13 \
        == config["sliding_window_layout"]
    reported = {m["name"] for m in bench["per_layer"]
                if CELL in m.get("workloads", [])}
    assert {"moe_gmm_roofline.moe8k", "attn_kernel_roofline.moe8k",
            "train_mfu.moe8k", "expert_held_share.moe8k"} <= reported


def test_the_cells_controls_rehearse_on_the_cpu(tmp_path):
    """`benchmarks/tools/train_controls.py` at the cell's `tiny` sizes:
    the reference in the program's place goes through the family's own
    comparison; at bf16 it comes out correct and at float8 refused.
    (The planted faults move a layer of width 16 too little to be told
    here: the tool judges them at the published widths on the chip, and
    `test_smallthinker.py` at widths where the layers carry weight.)"""
    env = dict(os.environ, JAX_PLATFORMS="cpu",
               JAX_COMPILATION_CACHE_DIR=str(tmp_path / "cache"))
    out = subprocess.run(
        [sys.executable,
         os.path.join(REPO, "benchmarks", "tools", "train_controls.py"),
         "--workload", CELL, "--seed", "4000000007", "--rehearse-cpu"],
        cwd=REPO, env=env, capture_output=True, text=True, timeout=600)
    said = [ln for ln in out.stdout.splitlines()
            if ln.startswith("[bench] control") and "by the cell" in ln]
    assert any("bfloat16: correct" in ln for ln in said), out.stdout[-3000:]
    assert any("float8_e4m3fn: REFUSED" in ln for ln in said), \
        out.stdout[-3000:]
    assert len([ln for ln in out.stdout.splitlines()
                if ln.startswith("[bench] control") and ln.endswith(":")]
               ) == 6
