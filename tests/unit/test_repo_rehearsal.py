"""The CPU rehearsal of the benchmark cell that ISSUE 55 adds
(`keye-vl-2.0-30b-a3b.serve-repo-saturated`), as the driver's harness
would run it: `benchmarks/run.py --rehearse-cpu` in a process of its
own, at the cell's `tiny` sizes (chunks of 16, 12 tokens selected a
query) with the kernels in interpret mode. It proves the cell's files
are found by name, prompts cross chunk boundaries through `submit` /
`step` and select inside the paged cache, and the served tokens pass the
reference's comparison; it prints no result line and measures nothing.
A file of its own, so that under `--dist loadfile` its minute falls to
another worker than `test_keye_vl2.py`'s."""

import json
import os
import subprocess
import sys

REPO = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
CELL = "keye-vl-2.0-30b-a3b.serve-repo-saturated"


def test_the_repository_cell_rehearses_on_the_cpu(tmp_path):
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
    # 24 pages of 16 tokens, three layers of keys and values (2 x 32
    # lanes) and one indexer key (8 lanes), 2 B each; nothing a slot
    assert line["device"]["kv_pool_bytes"] == 24 * 16 * 3 * (64 + 8) * 2
    assert "chunked prefill 16" in out.stdout
    assert "12 tokens selected a query" in out.stdout
    # 2 chunk buckets, the decode, and the token merge at its 3 shapes
    assert "6 programs warm" in out.stdout


def test_the_cell_is_declared_as_the_issue_names_it():
    with open(os.path.join(REPO, "BENCHMARK.json")) as f:
        bench = json.load(f)
    cell = next(w for w in bench["workloads"] if w["name"] == CELL)
    assert (cell["config"], cell["traffic"], cell["chips"]) == \
        ("keye-vl-2.0-30b-a3b", "serve-repo-saturated", 1)
    assert len(cell["why"]) <= 200
    with open(os.path.join(REPO, "benchmarks", "traffic",
                           "serve-repo-saturated.json")) as f:
        traffic = json.load(f)
    assert traffic["kind"] == "serve_backlog"
    assert traffic["prompt"] == {"median": 32768, "sigma": 0.5,
                                 "low": 8192, "high": 65536}
    assert traffic["output"] == {"median": 1536, "sigma": 0.5, "low": 512,
                                 "high": 4096}
    assert (traffic["backlog_requests"], traffic["epoch_requests"],
            traffic["order_seed"], traffic["check_requests"],
            traffic["ramp_s"], traffic["trace_s"]) == (256, 64, 55, 4, 60, 5)
    with open(os.path.join(REPO, "benchmarks", "configs",
                           "keye-vl-2.0-30b-a3b.json")) as f:
        config = json.load(f)
    inference = config["serve"]["inference"]
    assert inference["chunked_prefill"] == {"enabled": True,
                                            "chunk_tokens": 2048}
    assert (inference["max_batch_size"], inference["max_seq_len"],
            inference["batch_buckets"], inference["prompt_buckets"]) == \
        (16, 69632, [1, 2], [2048])
    # every number of the catalog's config under its own key, but the
    # three that are cut
    published = {
        "attention_bias": False, "decoder_sparse_step": 1, "head_dim": 128,
        "hidden_act": "silu", "hidden_size": 2048,
        "intermediate_size": 6144, "max_position_embeddings": 262144,
        "max_window_layers": 48, "mlp_only_layers": [],
        "model_type": "KeyeVL2", "moe_intermediate_size": 768,
        "norm_topk_prob": True, "num_attention_heads": 32,
        "num_experts_per_tok": 8, "num_key_value_heads": 4,
        "num_local_experts": 128, "rms_norm_eps": 1e-06,
        "rope_scaling": {"mrope_section": [16, 24, 24],
                         "rope_type": "default", "type": "default"},
        "rope_theta": 10000000,
        "sa_config": {"indexer_head_dim": 64, "indexer_num_heads": 16,
                      "indexer_num_kv_heads": 1, "kv_chunk_size": 512,
                      "q_chunk_size": 512, "topk": 2048},
        "sliding_window": None, "tie_word_embeddings": False,
        "use_sliding_window": False}
    assert {k: config[k] for k in published} == published
    assert (config["num_hidden_layers"], config["num_experts"],
            config["vocab_size"]) == (6, 16, 18992)
    entry = next(c for c in bench["configs"]
                 if c["name"] == "keye-vl-2.0-30b-a3b")
    assert entry["reduced"] == config["reduced"] == [
        "num_hidden_layers", "num_experts", "vocab_size"]
    assert entry["source"] == config["source"]
    assert {"head_norm", "indexer", "indexer_inputs", "indexer_rotation",
            "indexer_keys_in_cache", "chunk_sizes", "weights"} <= set(
        config["assumed"])
    served = next(m for m in bench["end_to_end"]
                  if m["name"] == "serve_tokens_per_s")
    assert CELL in served["workloads"]
    reported = {m["name"] for m in bench["per_layer"]
                if CELL in m.get("workloads", [])}
    assert {"decode_hbm_roofline.repo", "serve_stall_share.sat",
            "decode_step_device_ms.sat", "slot_occupancy.sat"} <= reported
    # what reads `serve/prefill` finds nothing in a cell of chunks
    assert not reported & {"prefill_pad_share.sat",
                           "serve_prefill_wait_ms.sat"}
    # the readers' files are there for every `.repo` metric, listed or
    # waiting for room under `per_layer` (PERF.md section 7)
    metrics = os.path.join(REPO, "benchmarks", "metrics")
    waiting = [f for f in os.listdir(metrics) if f.endswith(".repo.json")]
    assert {"decode_hbm_roofline.repo.json", "selected_share.repo.json",
            "sparse_prefill_roofline.repo.json"} <= set(waiting)
    for name in waiting:
        with open(os.path.join(metrics, name)) as f:
            spec = json.load(f)
        assert spec["name"] + ".json" == name
        assert spec["moves"] == "serve_tokens_per_s"
        assert os.path.exists(os.path.join(
            REPO, "benchmarks", "readers", spec["reader"] + ".py"))
