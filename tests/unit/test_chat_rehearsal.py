"""The CPU rehearsal of the benchmark cell that ISSUE 51 adds
(`lfm2-24b-a2b.serve-chat-saturated`), as the driver's harness would run
it: `benchmarks/run.py --rehearse-cpu` in a process of its own, at the
cell's `tiny` sizes with the kernels in interpret mode. It proves the
cell's files are found by name, prompts of unequal lengths go through
`submit` / `step` over pages and tails, and the served tokens pass the
reference's comparison; then the family's planted faults through the
cell's own controls, each of which has to move the comparison. It prints
no result line and measures nothing. A file of its own, so that under
`--dist loadfile` its minutes fall to another worker than
`test_lfm2.py`'s."""

import json
import os
import subprocess
import sys


REPO = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
CELL = "lfm2-24b-a2b.serve-chat-saturated"


def test_the_chat_cell_rehearses_on_the_cpu(tmp_path):
    env = dict(os.environ, JAX_PLATFORMS="cpu",
               JAX_COMPILATION_CACHE_DIR=str(tmp_path / "cache"))
    out = subprocess.run(
        [sys.executable, os.path.join(REPO, "benchmarks", "run.py"),
         "--workload", CELL, "--seed", "4000000007", "--seconds", "1",
         "--trace", "0", "--rehearse-cpu"],
        cwd=REPO, env=env, capture_output=True, text=True, timeout=800)
    assert out.returncode == 3, out.stdout[-2000:] + out.stderr[-2000:]
    last = [ln for ln in out.stdout.splitlines()
            if ln.startswith("[bench] rehearsal on cpu")]
    assert last, out.stdout[-2000:]
    line = json.loads(last[0].split("): ", 1)[1])
    assert line["correct"] and line["failed"] == 0 and line["attempted"] > 0
    assert set(line["metrics"]) == {"serve_tokens_per_s", "setup_s"}
    assert "convolution tails 5 rows over 5 per-slot layers" in out.stdout
    # 4 prefill buckets, the decode, and the token merge at its 3 shapes
    assert "8 programs warm" in out.stdout
    assert "order_seed 51" in out.stdout


def test_the_planted_faults_are_planted_at_the_tiny_size(tmp_path):
    """`tools/serve_faults.py --rehearse-cpu`: the family's PLANTED
    through the cell's comparison. At the tiny size a limit set on the
    chip refuses little; what is held here is that every fault is found
    by name, served once, and MOVES the logits (rms > 0) while the plain
    reference passes."""
    env = dict(os.environ, JAX_PLATFORMS="cpu",
               JAX_COMPILATION_CACHE_DIR=str(tmp_path / "cache"))
    out = subprocess.run(
        [sys.executable, os.path.join(REPO, "benchmarks", "tools",
                                      "serve_faults.py"),
         "--workload", CELL, "--seed", "7", "--requests", "2",
         "--rehearse-cpu"],
        cwd=REPO, env=env, capture_output=True, text=True, timeout=800)
    said = out.stdout + out.stderr
    for fault in ("conv_without_tail", "conv_gate_dropped", "qk_unnormed",
                  "rotation_at_position_0", "router_without_bias",
                  "router_weights_raw", "routed_sum_dropped",
                  "products_float8_e5m2"):
        assert fault in said, (fault, said[-3000:])
    assert "Traceback" not in said, said[-3000:]


def test_the_cell_is_declared_as_the_issue_names_it():
    with open(os.path.join(REPO, "BENCHMARK.json")) as f:
        bench = json.load(f)
    cell = next(w for w in bench["workloads"] if w["name"] == CELL)
    assert (cell["config"], cell["traffic"], cell["chips"]) == \
        ("lfm2-24b-a2b", "serve-chat-saturated", 1)
    assert len(cell["why"]) <= 200
    entry = next(c for c in bench["configs"] if c["name"] == "lfm2-24b-a2b")
    assert entry["reduced"] == ["num_hidden_layers"]
    with open(os.path.join(REPO, "benchmarks", "traffic",
                           "serve-chat-saturated.json")) as f:
        traffic = json.load(f)
    assert traffic["kind"] == "serve_backlog"
    assert traffic["prompt"] == {"median": 384, "sigma": 0.7, "low": 32,
                                 "high": 1024}
    assert traffic["output"] == {"median": 256, "sigma": 0.6, "low": 32,
                                 "high": 1024}
    assert (traffic["backlog_requests"], traffic["epoch_requests"],
            traffic["order_seed"], traffic["check_requests"],
            traffic["check_pad_to"], traffic["trace_s"]) == (
        8192, 256, 51, 4, 2048, 5)
    with open(os.path.join(REPO, entry["file"])) as f:
        config = json.load(f)
    inference = config["serve"]["inference"]
    assert (inference["max_batch_size"], inference["max_seq_len"],
            inference["batch_buckets"], inference["prompt_buckets"]) == \
        (256, 2048, [1, 4], [256, 512, 1024])
    assert inference["paged_kv"] == {"num_pages": 16385, "page_size": 16,
                                     "prefix_cache": False}
    # every published width and count as the catalog's row has it
    assert {k: config[k] for k in (
        "hidden_size", "intermediate_size", "moe_intermediate_size",
        "num_attention_heads", "num_key_value_heads", "conv_L_cache",
        "num_experts", "num_experts_per_tok", "num_dense_layers",
        "vocab_size", "max_position_embeddings", "norm_eps",
        "routed_scaling_factor")} == {
        "hidden_size": 2048, "intermediate_size": 11776,
        "moe_intermediate_size": 1536, "num_attention_heads": 32,
        "num_key_value_heads": 8, "conv_L_cache": 3, "num_experts": 64,
        "num_experts_per_tok": 4, "num_dense_layers": 2,
        "vocab_size": 65536, "max_position_embeddings": 128000,
        "norm_eps": 1e-05, "routed_scaling_factor": 1}
    assert config["published"] == {"num_hidden_layers": 40}
    assert config["num_hidden_layers"] == 10
    assert config["experts_held"] == [0, 64]
    assert config["vocab_held"] == [0, 65536]
    assert {"changed", "assumed", "deployment", "tiny"} <= set(config)
    served = next(m for m in bench["end_to_end"]
                  if m["name"] == "serve_tokens_per_s")
    assert CELL in served["workloads"]
    reported = {m["name"] for m in bench["per_layer"]
                if CELL in m.get("workloads", [])}
    assert {"decode_scope_moe_ms.chat", "decode_scope_conv_ms.chat",
            "decode_scope_attn_ms.chat", "prefill_scope_moe_ms.chat",
            "prefill_scope_conv_ms.chat", "prefill_scope_attn_ms.chat",
            "moe_experts_hbm_roofline.chat", "decode_hbm_roofline.chat",
            "prefill_mfu.chat", "moe_decode_rows_worked_share.chat",
            "expert_load_max_over_mean.chat", "expert_held_share.chat",
            "serve_stall_share.sat", "decode_step_device_ms.sat"} <= reported
    for m in bench["per_layer"]:
        if m["name"].endswith(".chat"):
            assert m["workloads"] == [CELL]
            assert m["moves"] == "serve_tokens_per_s"
            assert os.path.exists(os.path.join(
                REPO, "benchmarks", "metrics", m["name"] + ".json"))
