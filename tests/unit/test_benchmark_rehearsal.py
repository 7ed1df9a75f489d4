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
    assert line["correct"] and line["failed"] == 0 and line["attempted"] > 0, \
        out.stdout[-2000:]
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


# --------------------------------------------------------------------- #
# ISSUE 37: solar-open2-250b.serve-rollout-saturated, a served cell found
# through families/solar_open2.py
# --------------------------------------------------------------------- #
SERVED = "solar-open2-250b.serve-rollout-saturated"
BENCH = os.path.join(REPO, "benchmarks")


def test_served_cell_rehearses_through_its_family(tmp_path):
    """`run.py --rehearse-cpu --trace 1`: the state pool, both prefill
    buckets and decode through the engine, the served tokens through the
    plain reference, the counters' metrics through their readers."""
    env = dict(os.environ, JAX_PLATFORMS="cpu",
               JAX_COMPILATION_CACHE_DIR=str(tmp_path / "cache"))
    out = subprocess.run(
        [sys.executable, os.path.join(BENCH, "run.py"), "--workload",
         SERVED, "--seed", "3000000019", "--seconds", "2", "--trace", "1",
         "--rehearse-cpu"],
        cwd=REPO, env=env, capture_output=True, text=True, timeout=900)
    assert out.returncode == 3, out.stdout[-2000:] + out.stderr[-2000:]
    last = [ln for ln in out.stdout.splitlines()
            if ln.startswith("[bench] rehearsal on cpu")]
    line = json.loads(last[0].split("): ", 1)[1])
    assert line["correct"] and line["failed"] == 0 and line["attempted"] > 0
    assert line["compared"]["served_logit_gap"]["value"] <= \
        line["compared"]["served_logit_gap"]["limit"]
    # a slot's state counts in what the pools hold
    assert line["device"]["kv_pool_bytes"] > 4 * 3 * 4 * 32 * 32 * 4
    metrics = line["metrics"]
    assert 0 < metrics["expert_held_share.roll"]["value"] <= 100
    assert metrics["expert_load_max_over_mean.roll"]["value"] >= 100
    assert metrics["slot_occupancy.sat"]["value"] > 0
    assert "state pool" in out.stdout


def test_the_served_cell_is_declared_as_the_issue_names_it():
    with open(os.path.join(REPO, "BENCHMARK.json")) as f:
        bench = json.load(f)
    cell = next(w for w in bench["workloads"] if w["name"] == SERVED)
    assert (cell["config"], cell["traffic"], cell["chips"]) == \
        ("solar-open2-250b", "serve-rollout-saturated", 1)
    assert len(cell["why"]) <= 200
    entry = next(c for c in bench["configs"]
                 if c["name"] == "solar-open2-250b")
    assert entry["reduced"] == ["num_hidden_layers", "n_routed_experts",
                                "vocab_size"]
    with open(os.path.join(BENCH, "traffic",
                           "serve-rollout-saturated.json")) as f:
        traffic = json.load(f)
    assert (traffic["kind"], traffic["backlog_requests"],
            traffic["epoch_requests"], traffic["order_seed"],
            traffic["ramp_s"]) == ("serve_backlog", 8192, 64, 37, 25)
    assert traffic["prompt"] == {"median": 192, "sigma": 0.8, "low": 32,
                                 "high": 1024}
    assert traffic["output"] == {"median": 192, "sigma": 0.5, "low": 64,
                                 "high": 512}
    with open(os.path.join(REPO, entry["file"])) as f:
        config = json.load(f)
    # every number of the catalog's row under its own key, but the three
    # that are reduced
    published = {
        "model_type": "solar_open2", "partial_rotary_factor": 1,
        "linear_attn_config": {"short_conv_kernel_size": 4, "head_dim": 128,
                               "num_heads": 64, "num_kv_heads": None},
        "hidden_size": 4096, "num_attention_heads": 64, "head_dim": 128,
        "num_key_value_heads": 8, "intermediate_size": 10240,
        "moe_intermediate_size": 1280, "rms_norm_eps": 1e-05,
        "rope_theta": 10000, "tie_word_embeddings": False,
        "max_position_embeddings": 1048576, "first_k_dense_replace": 0,
        "use_rope": False, "gqa_interval": 3,
        "gqa_layers": list(range(0, 48, 4)), "use_gqa_gate": True,
        "kda_use_full_proj": False, "kda_allow_neg_eigval": True,
        "n_shared_experts": 1, "norm_topk_prob": True,
        "routed_scaling_factor": 1, "num_experts_per_tok": 8}
    assert {k: config[k] for k in published} == published
    assert (config["num_hidden_layers"], config["n_routed_experts"],
            config["vocab_size"]) == (4, 40, 24576)
    assert config["published"] == {"num_hidden_layers": 48,
                                   "n_routed_experts": 320,
                                   "vocab_size": 196608}
    assert config["router_outputs"] == 320
    assert {"hidden_act", "router_score", "gqa_gate", "decay",
            "state_precision"} <= set(config["assumed"])
    inference = config["serve"]["inference"]
    assert inference["max_batch_size"] == 192
    assert inference["max_seq_len"] == 1536
    assert inference["paged_kv"]["prefix_cache"] is False
    assert SERVED in next(m for m in bench["end_to_end"]
                          if m["name"] == "serve_tokens_per_s")["workloads"]
    reported = {m["name"] for m in bench["per_layer"]
                if SERVED in m.get("workloads", [])}
    assert {"kda_state_hbm_roofline.roll", "kda_scan_roofline.roll",
            "moe_experts_hbm_roofline.roll", "decode_hbm_roofline.roll",
            "decode_scope_kda_ms.roll", "decode_scope_moe_ms.roll",
            "decode_scope_attn_ms.roll", "prefill_scope_kda_scan_ms.roll",
            "expert_held_share.roll", "expert_load_max_over_mean.roll",
            "decode_step_device_ms.sat", "prefill_device_ms.sat",
            "decode_unscoped_share.sat", "decode_inherited_share.sat",
            "serve_host_gap_ms.sat", "slot_occupancy.sat",
            "prefill_pad_share.sat",
            "serve_gap_unattributed_share.sat"} <= reported
    for name in reported:
        assert os.path.exists(os.path.join(BENCH, "metrics",
                                           name + ".json")), name


def _served_in_process(seed, monkeypatch, alter=None):
    """`kinds/serve_backlog.run` at the cell's tiny sizes in this
    process, past the harness's look for a chip; `alter(runs)` changes
    the tokens a decode dispatch hands the scheduler."""
    import jax
    from deepspeed_tpu.inference.scheduler import Scheduler
    for path in (REPO, BENCH):
        if path not in sys.path:
            sys.path.insert(0, path)
    import run as bench_run
    from core import device as dev
    from loader import load_module
    with open(os.path.join(REPO, "BENCHMARK.json")) as f:
        bench = json.load(f)
    cell, entry = bench_run.find_cell(bench, SERVED)
    with open(os.path.join(REPO, entry["file"])) as f:
        config = json.load(f)
    traffic = bench_run.load_json("traffic", cell["traffic"] + ".json")
    config = {**config, **config["tiny"]}
    # wider weights than 0.02, which at hidden 64 leaves every logit
    # within 0.01 of every other, so that no token is wrong
    config["initializer_range"] = 0.2
    traffic = {**traffic, **traffic["tiny"]}
    if alter is not None:
        plain = Scheduler.record_token_runs
        monkeypatch.setattr(
            Scheduler, "record_token_runs",
            lambda self, runs, *a, **kw: plain(self, alter(runs), *a, **kw))
    ctx = bench_run.Context(
        cell=cell, config=config, traffic=traffic, seed=seed, seconds=1.0,
        trace=False, devices=jax.devices()[:1], peaks=None,
        compiles=dev.CompileCounter(), log=lambda msg: None, setup_s=None)
    return load_module("kinds", traffic["kind"]).run(ctx)


def test_an_altered_served_token_of_the_served_cell_is_not_correct(
        monkeypatch):
    sound = _served_in_process(1000003, monkeypatch)
    assert sound["correct"], sound["why_not"]
    limit = sound["compared"]["served_logit_gap"]["limit"]
    assert sound["compared"]["served_logit_gap"]["value"] <= limit

    def every_token_one_up(runs):
        return {sid: [(int(t) + 1) % 128 for t in run]
                for sid, run in runs.items()}
    broken = _served_in_process(1000003, monkeypatch, every_token_one_up)
    assert not broken["correct"]
    assert any("below the reference's pick" in why
               for why in broken["why_not"]), broken["why_not"]
    assert broken["compared"]["served_logit_gap"]["value"] > 5 * limit


def test_the_served_cells_controls_rehearse_on_the_cpu(tmp_path):
    """`benchmarks/tools/serve_controls.py` at the tiny sizes: the
    reference, the nearest precision below the stated one (float8
    products) and the three that are shown only run through the cell's
    own comparison. (At hidden 64 the logits lie 0.16 apart in the mean,
    so nothing reaches the cell's limit here: the tool judges at the
    published widths on the chip, PERF.md has the readings. What shows
    here is the ORDER: float8 products move the logits at least ten
    times as far as bfloat16 ones, a bfloat16 state no farther than
    those.)"""
    env = dict(os.environ, JAX_PLATFORMS="cpu",
               JAX_COMPILATION_CACHE_DIR=str(tmp_path / "cache"))
    out = subprocess.run(
        [sys.executable, os.path.join(BENCH, "tools", "serve_controls.py"),
         "--workload", SERVED, "--seed", "5", "--requests", "4",
         "--rehearse-cpu"],
        cwd=REPO, env=env, capture_output=True, text=True, timeout=900)
    said = [ln for ln in out.stdout.splitlines()
            if ln.startswith("[bench] control")]
    assert len(said) == 5, out.stdout[-3000:] + out.stderr[-2000:]
    assert "control reference" in said[0] and \
        ": correct, has to be correct" in said[0]
    assert "has to be NOT correct" in said[1]
    assert all("shown only" in ln for ln in said[2:])
    readings = json.loads(out.stdout.splitlines()[-1])
    assert set(readings) == {"reference", "products_float8_e5m2",
                             "products_bfloat16", "state_bfloat16",
                             "recurrence_bfloat16"}
    moved = {k: v["rms_from_reference"] for k, v in readings.items()}
    assert moved["reference"] == 0.0
    assert moved["products_float8_e5m2"] > 10 * moved["products_bfloat16"]
    assert moved["state_bfloat16"] < 2 * moved["products_bfloat16"]
    assert readings["products_float8_e5m2"]["worst_gap"] > \
        10 * readings["reference"]["worst_gap"]


DOCS = "granite-4.0-h-small.serve-docs-saturated"


def test_the_docs_cell_is_declared_as_the_issue_names_it():
    """ISSUE 41's table letter for letter: the traffic's laws, the
    backlog, the ramp, the check; the configuration's published keys
    under their own names; the per-layer entries."""
    with open(os.path.join(REPO, "BENCHMARK.json")) as f:
        bench = json.load(f)
    cell = next(w for w in bench["workloads"] if w["name"] == DOCS)
    assert (cell["config"], cell["traffic"], cell["chips"]) == \
        ("granite-4.0-h-small", "serve-docs-saturated", 1)
    assert len(cell["why"]) <= 200
    entry = next(c for c in bench["configs"]
                 if c["name"] == "granite-4.0-h-small")
    assert entry["reduced"] == [
        "num_hidden_layers", "num_local_experts", "vocab_size"]
    with open(os.path.join(BENCH, "traffic",
                           "serve-docs-saturated.json")) as f:
        traffic = json.load(f)
    assert (traffic["kind"], traffic["backlog_requests"],
            traffic["epoch_requests"], traffic["order_seed"],
            traffic["ramp_s"], traffic["check_requests"],
            traffic["check_pad_to"], traffic["trace_s"]) == \
        ("serve_backlog", 2048, 64, 41, 25, 4, 4480, 5)
    assert traffic["prompt"] == {"median": 2048, "sigma": 0.6, "low": 512,
                                 "high": 4096}
    assert traffic["output"] == {"median": 128, "sigma": 0.5, "low": 32,
                                 "high": 384}
    with open(os.path.join(REPO, entry["file"])) as f:
        config = json.load(f)
    published = {
        "attention_bias": False, "attention_multiplier": 0.0078125,
        "embedding_multiplier": 12, "hidden_act": "silu",
        "hidden_size": 4096, "intermediate_size": 768,
        "logits_scaling": 16, "mamba_chunk_size": 256,
        "mamba_conv_bias": True, "mamba_d_conv": 4, "mamba_d_head": 64,
        "mamba_d_state": 128, "mamba_expand": 2, "mamba_n_groups": 1,
        "mamba_n_heads": 128, "mamba_proj_bias": False,
        "max_position_embeddings": 131072,
        "model_type": "granitemoehybrid",
        "normalization_function": "rmsnorm", "num_attention_heads": 32,
        "num_experts_per_tok": 10, "num_key_value_heads": 8,
        "position_embedding_type": "nope", "residual_multiplier": 0.22,
        "rms_norm_eps": 1e-05, "rope_scaling": None, "rope_theta": 10000,
        "shared_intermediate_size": 1536, "tie_word_embeddings": True}
    assert {k: config[k] for k in published} == published
    assert config["layer_types"] == [
        "attention" if l % 10 == 5 else "mamba" for l in range(40)]
    assert (config["num_hidden_layers"], config["num_local_experts"],
            config["vocab_size"]) == (10, 36, 50176)
    assert config["published"] == {"num_hidden_layers": 40,
                                   "num_local_experts": 72,
                                   "vocab_size": 100352}
    assert (config["router_outputs"], config["experts_held"],
            config["vocab_held"]) == (72, [0, 36], [0, 50176])
    assert {"mamba", "head_dim", "shared_expert", "router_score",
            "intermediate_size", "state_precision",
            "weights"} <= set(config["assumed"])
    assert "eight v5e chips" in config["deployment"]
    inference = config["serve"]["inference"]
    assert 48 <= inference["max_batch_size"] <= 64
    assert inference["max_seq_len"] == 4480
    assert inference["prompt_buckets"] == [1024, 2048, 4096]
    # at most seven programs: the buckets and one decode
    assert len(inference["batch_buckets"]) * 3 + 1 <= 7
    assert inference["paged_kv"]["prefix_cache"] is False
    assert DOCS in next(m for m in bench["end_to_end"]
                        if m["name"] == "serve_tokens_per_s")["workloads"]
    reported = {m["name"] for m in bench["per_layer"]
                if DOCS in m.get("workloads", [])}
    assert {"decode_scope_ssd_ms.docs", "decode_scope_moe_ms.docs",
            "decode_scope_attn_ms.docs", "prefill_scope_ssd_ms.docs",
            "prefill_scope_moe_ms.docs", "ssd_state_hbm_roofline.docs",
            "ssd_scan_roofline.docs", "prefill_mfu.docs",
            "moe_experts_hbm_roofline.docs", "decode_hbm_roofline.docs",
            "expert_held_share.docs", "expert_load_max_over_mean.docs",
            "decode_step_device_ms.sat", "prefill_device_ms.sat",
            "serve_host_gap_ms.sat", "slot_occupancy.sat",
            "prefill_pad_share.sat", "prefill_own_keys_share.sat",
            "serve_stall_share.sat"} <= reported
    for name in reported:
        assert os.path.exists(os.path.join(BENCH, "metrics",
                                           name + ".json")), name
    # every metric new with this cell is this cell's alone
    assert all(m["workloads"] == [DOCS] for m in bench["per_layer"]
               if m["name"].endswith(".docs"))


REASON = "ax-k1.serve-reason-saturated"


def test_the_reason_cell_is_declared_as_the_issue_names_it():
    """ISSUE 43's cell: the traffic's laws, the backlog, the ramp, the
    check; the configuration's published keys under their own names and
    the three cuts; the per-layer entries; appended, nothing before it
    moved."""
    with open(os.path.join(REPO, "BENCHMARK.json")) as f:
        bench = json.load(f)
    # the sixth cell and the fifth configuration: what later PRs add
    # comes after them
    cell = bench["workloads"][5]
    assert (cell["name"], cell["config"], cell["traffic"], cell["chips"]) \
        == (REASON, "ax-k1", "serve-reason-saturated", 1)
    assert len(cell["why"]) <= 200
    entry = bench["configs"][4]
    assert entry["name"] == "ax-k1" and entry["reduced"] == [
        "num_hidden_layers", "n_routed_experts", "vocab_size"]
    assert entry["source"] == \
        "https://huggingface.co/skt/A.X-K1/blob/main/config.json"
    with open(os.path.join(BENCH, "traffic",
                           "serve-reason-saturated.json")) as f:
        traffic = json.load(f)
    assert (traffic["kind"], traffic["backlog_requests"],
            traffic["epoch_requests"], traffic["order_seed"],
            traffic["ramp_s"], traffic["check_requests"],
            traffic["check_pad_to"]) == \
        ("serve_backlog", 2048, 64, 43, 30, 4, 6144)
    assert traffic["prompt"] == {"median": 2048, "sigma": 0.5, "low": 512,
                                 "high": 4096}
    assert traffic["output"] == {"median": 1024, "sigma": 0.5, "low": 256,
                                 "high": 2048}
    assert 0.2 < traffic["logit_tolerance"] < 0.9   # between its readings
    with open(os.path.join(REPO, entry["file"])) as f:
        config = json.load(f)
    assert config["family"] == "axk1"
    published = {
        "hidden_size": 7168, "intermediate_size": 18432,
        "moe_intermediate_size": 2048, "num_attention_heads": 64,
        "q_lora_rank": 1536, "kv_lora_rank": 512, "qk_nope_head_dim": 128,
        "qk_rope_head_dim": 64, "v_head_dim": 128, "n_group": 8,
        "topk_group": 4, "num_experts_per_tok": 8, "n_shared_experts": 1,
        "first_k_dense_replace": 1, "routed_scaling_factor": 2.5,
        "scoring_func": "sigmoid", "norm_topk_prob": True,
        "rms_norm_eps": 1e-06, "rope_theta": 10000,
        "max_position_embeddings": 131072, "model_type": "axk1"}
    assert {k: config[k] for k in published} == published
    assert config["rope_scaling"] == {
        "beta_fast": 32, "beta_slow": 1, "factor": 32, "mscale": 1,
        "mscale_all_dim": 1, "original_max_position_embeddings": 4096,
        "type": "yarn"}
    assert (config["num_hidden_layers"], config["n_routed_experts"],
            config["vocab_size"]) == (5, 12, 20480)
    assert config["published"] == {"num_hidden_layers": 61,
                                   "n_routed_experts": 192,
                                   "vocab_size": 163840}
    assert set(config["changed"]) == set(config["reduced"])
    assert (config["router_outputs"], config["experts_held"],
            config["vocab_held"]) == (192, [0, 12], [0, 20480])
    assert {"router", "rotary_layout", "weights", "bias",
            "cache_row"} <= set(config["assumed"])
    assert "sixteen v5e chips" in config["deployment"]
    inference = config["serve"]["inference"]
    assert (inference["max_batch_size"], inference["max_seq_len"]) == \
        (192, 6144)
    assert inference["prompt_buckets"] == [2048, 4096]   # five programs
    assert len(inference["batch_buckets"]) * len(
        inference["prompt_buckets"]) + 1 <= 9
    pool = inference["paged_kv"]
    assert pool["prefix_cache"] is False
    assert (pool["num_pages"] - 1, pool["page_size"]) == (45056, 16)
    for name in ("decode_read_live_share.sat",
                 "decode_stripe_live_share.sat"):     # the last page's tail
        assert next(m for m in bench["per_layer"] if m["name"] == name)[
            "workloads"][:2] == ["gpt2-345m.serve-saturated", REASON]
    assert REASON in next(m for m in bench["end_to_end"]
                          if m["name"] == "serve_tokens_per_s")["workloads"]
    reported = {m["name"] for m in bench["per_layer"]
                if REASON in m.get("workloads", [])}
    assert {"decode_scope_mla_ms.reason", "prefill_scope_mla_ms.reason",
            "decode_scope_moe_ms.reason", "prefill_scope_moe_ms.reason",
            "mla_decode_roofline.reason", "decode_hbm_roofline.reason",
            "moe_experts_hbm_roofline.reason", "prefill_mfu.reason",
            "expert_held_share.reason", "expert_load_max_over_mean.reason",
            "prefill_expert_rows_worked_share.sat"} <= reported
    # the 22 metrics that listed every served cell list this one too;
    # PR 44's `prefill_page_write_share.sat` and PR 45's
    # `serve_prefill_build_ms.sat` came with all four
    every = [m for m in bench["per_layer"] if DOCS in m.get(
        "workloads", []) and "gpt2-345m.serve-saturated" in m["workloads"]
        and "solar-open2-250b.serve-rollout-saturated" in m["workloads"]]
    # ... and PR 52's `decode_deferred_share.sat` and PR 54's
    # `decode_run_turn_share.sat` with all six
    assert len(every) == 26 and all(m["workloads"][3] == REASON
                                    for m in every)
    assert [m["name"] for m in every[-4:]] == [
        "prefill_page_write_share.sat", "serve_prefill_build_ms.sat",
        "decode_deferred_share.sat", "decode_run_turn_share.sat"]
    for name in reported:
        assert os.path.exists(os.path.join(BENCH, "metrics",
                                           name + ".json")), name
    # every metric new with this cell is this cell's alone
    new = [m for m in bench["per_layer"] if m["name"].endswith(".reason")]
    assert len(new) == 10 and all(m["workloads"] == [REASON] for m in new)
    # appended in PR 43 together: what later PRs add comes after them
    first = bench["per_layer"].index(new[0])
    assert bench["per_layer"][first:first + 10] == new
