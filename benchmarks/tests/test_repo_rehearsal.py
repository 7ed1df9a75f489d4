"""The CPU rehearsal of `keye-vl-2.0-30b-a3b.serve-repo-saturated`, and
its `correct` shown to fail: `benchmarks/run.py --rehearse-cpu` in a
process of its own at the cell's `tiny` sizes (chunks of 16, 12 tokens
selected a query), through `families/keye_vl2.py`; then the same kind
driven in this process with a served token altered where the scheduler
records it, which has to come out not correct (as
`test_longctx_rehearsal.py` does for kimi's cell). Run by hand:

    python -m pytest benchmarks/tests/test_repo_rehearsal.py -q

Measures nothing. Not part of tier-1 (it lives outside `tests/`;
`tests/unit/test_repo_rehearsal.py` keeps the first half there).
"""

import json
import os
import subprocess
import sys

BENCH_DIR = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
ROOT = os.path.dirname(BENCH_DIR)
for path in (ROOT, BENCH_DIR):
    if path not in sys.path:
        sys.path.insert(0, path)
CELL = "keye-vl-2.0-30b-a3b.serve-repo-saturated"


def rehearse(tmp_path, trace):
    env = dict(os.environ, JAX_PLATFORMS="cpu",
               JAX_COMPILATION_CACHE_DIR=str(tmp_path / "cache"))
    out = subprocess.run(
        [sys.executable, os.path.join(BENCH_DIR, "run.py"), "--workload",
         CELL, "--seed", "3000000019", "--seconds", "2", "--trace",
         str(trace), "--rehearse-cpu"],
        cwd=ROOT, env=env, capture_output=True, text=True, timeout=900)
    assert out.returncode == 3, out.stdout[-2000:] + out.stderr[-2000:]
    last = [ln for ln in out.stdout.splitlines()
            if ln.startswith("[bench] rehearsal on cpu")]
    return json.loads(last[0].split("): ", 1)[1]), out.stdout


def test_the_cell_rehearses_through_its_family(tmp_path):
    """A traced rehearsal: the cache tree with its third leaf, chunks
    that score and attend the prefix, decode through the engine, the
    served tokens through the plain reference, the counters' metrics
    through their readers; the device's metrics find nothing on a CPU
    and are left out."""
    line, said = rehearse(tmp_path, 1)
    assert line["correct"] and line["failed"] == 0 and line["attempted"] > 0
    assert line["compared"]["served_logit_gap"]["value"] <= \
        line["compared"]["served_logit_gap"]["limit"]
    assert line["device"]["kv_pool_bytes"] == 24 * 16 * 3 * (64 + 8) * 2
    metrics = line["metrics"]
    assert metrics["slot_occupancy.sat"]["value"] > 0
    assert "decode_hbm_roofline.repo" not in metrics
    assert "chunked prefill 16" in said and "indexer leaf" in said
    chunks = [ln for ln in said.splitlines() if "class chunk" in ln]
    assert chunks, said[-3000:]
    queued = next(ln for ln in said.splitlines()
                  if "requests queued at once" in ln)
    assert "order_seed 55" in queued and "prompts 17-60" in queued


def run_in_process(seed, monkeypatch, alter=None):
    """`kinds/serve_backlog.run` at the tiny sizes on whatever JAX
    finds; `alter(runs)` changes the tokens a dispatch hands the
    scheduler."""
    import jax
    from deepspeed_tpu.inference.scheduler import Scheduler
    import run as bench_run
    from core import device as dev
    from loader import load_module

    with open(os.path.join(ROOT, "BENCHMARK.json")) as f:
        bench = json.load(f)
    cell, entry = bench_run.find_cell(bench, CELL)
    with open(os.path.join(ROOT, entry["file"])) as f:
        config = json.load(f)
    traffic = bench_run.load_json("traffic", cell["traffic"] + ".json")
    config = {**config, **config["tiny"]}
    traffic = {**traffic, **traffic["tiny"]}
    # the head's spread gives logits an rms of sqrt(hidden) x 0.02: 0.9
    # at the published 2,048, a sixth of that at the tiny 64, so the
    # cell's limit (set on the chip) is held a sixth too
    traffic["logit_tolerance"] = traffic["logit_tolerance"] / 6
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


def test_a_served_token_altered_where_it_is_produced_is_not_correct(
        monkeypatch):
    sound = run_in_process(1000003, monkeypatch)
    assert sound["correct"], sound["why_not"]
    limit = sound["compared"]["served_logit_gap"]["limit"]
    assert sound["compared"]["served_logit_gap"]["value"] <= limit

    def every_token_one_up(runs):
        return {sid: [(int(t) + 1) % 128 for t in run]
                for sid, run in runs.items()}
    broken = run_in_process(1000003, monkeypatch, every_token_one_up)
    assert not broken["correct"]
    assert any("below the reference's pick" in why
               for why in broken["why_not"]), broken["why_not"]
    assert broken["compared"]["served_logit_gap"]["value"] > limit
