"""The benchmark's own arithmetic, by hand-worked cases. Run by hand:

    python -m pytest benchmarks/tests -q

Not part of tier-1 (it lives outside `tests/`). No chip, no program code.
"""

import json
import math
import os
import re
import statistics
import sys

import numpy as np
import pytest

BENCH_DIR = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
ROOT = os.path.dirname(BENCH_DIR)
sys.path.insert(0, BENCH_DIR)

from core import draws, flops, stats, trace  # noqa: E402


# ----------------------------------------------------------------- stats
def test_median_block_and_stall_share_beside_the_whole_window_rate():
    quiet = stats.median_block([2.0] * 15, 10, 8192, 1)
    stalled = stats.median_block([2.0] * 14 + [2.3], 10, 8192, 1)
    assert quiet["tokens_per_s_per_chip"] == pytest.approx(40960.0)
    # one host stall of 0.3 s: the rate does not move ...
    assert stalled["tokens_per_s_per_chip"] == quiet["tokens_per_s_per_chip"]
    # ... and what the median hid is reported beside it: 0.3 / 30 = 1%
    assert quiet["stall_share_pct"] == pytest.approx(0.0)
    assert stalled["stall_share_pct"] == pytest.approx(1.0)
    # the whole-window rate, which is the end-to-end metric, lost that 1%
    whole = 15 * 10 * 8192 / (14 * 2.0 + 2.3)
    assert whole == pytest.approx(40960.0 / 1.01)
    four = stats.median_block([2.0, 2.0], 2, 16384, 4)
    assert four["tokens_per_s_per_chip"] == pytest.approx(4096.0)
    assert four["step_ms"] == pytest.approx(1000.0)


def test_spread_is_the_contracts():
    vals = [100.0, 101.0, 102.0, 103.0, 104.0, 105.0]
    q1, _, q3 = statistics.quantiles(vals, n=4)
    assert stats.spread(vals) == pytest.approx((q3 - q1) / 102.5)


def test_driver_spread_leaves_out_the_one_farthest_run():
    # six runs, one far high: the range is 10 of a median 102.5; without
    # the run farthest from the median it is 4
    vals = [100.0, 101.0, 102.0, 103.0, 104.0, 110.0]
    assert stats.driver_spread(vals) == pytest.approx(4.0 / 102.5)
    # wider than the distance between quartiles, which the bound is
    # five times: q1 = 100.75, q3 = 105.5
    assert stats.spread(vals) == pytest.approx(4.75 / 102.5)
    # the far run may be the low one, and the order does not matter
    assert stats.driver_spread([103.0, 90.0, 101.0, 102.0, 100.0]) == \
        pytest.approx(3.0 / 101.0)
    # two far runs: one goes, the other stays in the spread
    assert stats.driver_spread([100.0, 100.0, 100.0, 100.0, 90.0, 112.0]) \
        == pytest.approx(10.0 / 100.0)
    # nothing is left out where that would narrow nothing
    assert stats.driver_spread([100.0, 100.0, 104.0, 104.0]) == \
        pytest.approx(4.0 / 102.0)
    assert stats.driver_spread([100.0, 101.0]) == pytest.approx(1.0 / 100.5)
    with pytest.raises(ValueError):
        stats.driver_spread([100.0])


# ----------------------------------------------------------------- draws
LAW = {"median": 96, "sigma": 0.8, "low": 16, "high": 512}
OUT = {"median": 48, "sigma": 0.6, "low": 8, "high": 128}


def test_two_seeds_queue_the_same_work_in_another_order():
    a = draws.backlog_lengths(200, 64, 1, LAW, OUT)
    b = draws.backlog_lengths(200, 64, 2 ** 31 + 11, LAW, OUT)
    assert len(a[0]) == len(a[1]) == 200
    for x, y in zip(a, b):
        # every whole epoch holds the same lengths under both seeds ...
        for k in range(3):
            e = slice(64 * k, 64 * (k + 1))
            assert sorted(x[e]) == sorted(y[e]) == sorted(x[:64])
        # ... in another order, and each epoch in an order of its own
        assert list(x) != list(y)
        assert list(x[:64]) != list(x[64:128])
    # which prompt meets which output differs too
    assert sorted(zip(*a))[:64] != sorted(zip(*b))[:64]
    # the same seed gives the same inputs
    c = draws.backlog_lengths(200, 64, 1, LAW, OUT)
    assert all((x == y).all() for x, y in zip(a, c))
    # an epoch is the stratified law: one request from each stratum
    assert sorted(a[0][:64]) == list(draws.lognormal_clipped(64, **LAW))
    assert sorted(a[1][:64]) == list(draws.lognormal_clipped(64, **OUT))


def traffic_file(name):
    with open(os.path.join(BENCH_DIR, "traffic", name + ".json")) as f:
        return json.load(f)


def test_serve_saturated_orders_its_backlog_from_the_traffic_file():
    """The order is the traffic file's `order_seed`, which the kind hands
    `backlog_lengths` whatever `--seed` is: two `--seed`s queue the same
    lengths in the same order and differ in every prompt's ids."""
    tr = traffic_file("serve-saturated")
    assert tr["backlog_requests"] == 12096
    with open(os.path.join(BENCH_DIR, "kinds", "serve_backlog.py")) as f:
        assert 'tr["order_seed"], tr["prompt"]' in f.read()
    n, epoch = 640, tr["epoch_requests"]
    plen, _ = draws.backlog_lengths(n, epoch, tr["order_seed"],
                                    tr["prompt"], tr["output"])
    ids_a = draws.prompt_tokens(plen, 50257, 1)
    ids_b = draws.prompt_tokens(plen, 50257, 2 ** 31 + 11)
    assert [len(x) for x in ids_a] == [len(x) for x in ids_b] == list(plen)
    assert not any((x == y).all() for x, y in zip(ids_a, ids_b))
    # each epoch still in an order of its own
    assert list(plen[:epoch]) != list(plen[epoch:2 * epoch])


def test_serve_saturated_backlog_outlasts_9000_tokens_per_s():
    """Ramp + window at the contract's run_seconds: the queued output
    tokens cover 9,000 tokens/s, three times what the cell serves."""
    tr = traffic_file("serve-saturated")
    with open(os.path.join(ROOT, "BENCHMARK.json")) as f:
        run_seconds = json.load(f)["run_seconds"]
    _, olen = draws.backlog_lengths(
        tr["backlog_requests"], tr["epoch_requests"], tr["order_seed"],
        tr["prompt"], tr["output"])
    assert int(olen.sum()) == 189 * 3542 == 669_438
    assert olen.sum() >= (tr["ramp_s"] + run_seconds) * 9000


def test_the_serving_kind_names_no_architecture():
    """`kinds/serve_backlog.py` finds its model through the
    configuration's `family`: it imports no model, initialiser or
    reference module and names no architecture."""
    with open(os.path.join(BENCH_DIR, "kinds", "serve_backlog.py")) as f:
        source = f.read()
    imports = [ln for ln in source.splitlines()
               if ln.startswith(("import ", "from "))]
    assert not any(re.search(r"models|reference|gpt2_model", ln)
                   for ln in imports), imports
    assert not re.search(r"gpt2|gpt-2|smallthinker|llama", source, re.I)
    assert 'load_module("families", cfg["family"])' in source
    family = os.path.join(BENCH_DIR, "families", "gpt2.py")
    assert os.path.isfile(family)
    with open(os.path.join(BENCH_DIR, "configs", "gpt2-345m.json")) as f:
        assert json.load(f)["family"] == "gpt2"
    with open(family) as f:
        text = f.read()
    for name in ("serve_model_of", "init_params", "reference_logits",
                 "cache_bytes", "param_count", "describe_served"):
        assert re.search(rf"^(def )?{name}\b", text, re.M), name


def test_the_law_is_the_stated_one():
    x = draws.lognormal_clipped(1001, **LAW)
    assert x.min() == 16 and x.max() == 512
    assert statistics.median(x) == 96
    # the 84th percentile of a log-normal is median * e^sigma
    assert np.percentile(x, 84.13) == pytest.approx(96 * math.e ** 0.8,
                                                    rel=0.02)


def test_token_stream_is_seeded_and_in_range():
    a = next(draws.TokenStream(5, 1000, 4, 64))["input_ids"]
    b = next(draws.TokenStream(5, 1000, 4, 64))["input_ids"]
    c = next(draws.TokenStream(6, 1000, 4, 64))["input_ids"]
    assert a.shape == (4, 65) and a.dtype == np.int32
    assert (a == b).all() and not (a == c).all()
    assert a.min() >= 0 and a.max() < 1000
    s = draws.TokenStream(5, 1000, 64, 256, successor_share=0.5)
    ids = next(s)["input_ids"]
    follows = (s.successor[ids[:, :-1]] == ids[:, 1:]).mean()
    assert 0.45 < follows < 0.56
    assert draws.seed32(2 ** 31 + 5) < 2 ** 32


# ----------------------------------------------------------------- flops
def test_gpt2_parameter_count_by_hand():
    # GPT-2 medium with the padded vocabulary: the 354.9M the engine logs
    n = flops.gpt2_param_count(50304, 1024, 1024, 24)
    per_layer = 12 * 1024 ** 2 + 13 * 1024
    assert n == 50304 * 1024 + 1024 * 1024 + 24 * per_layer + 2 * 1024
    assert round(n / 1e6, 1) == 354.9
    assert flops.gpt2_param_count(50257, 1024, 768, 12) == 124_439_808


def test_train_flops_and_mfu_by_hand():
    n = flops.gpt2_param_count(50304, 1024, 1024, 24)
    per_token = flops.train_flops_per_token(n, 24, 1024, 1024)
    assert per_token == 6 * n + 12 * 24 * 1024 * 1024
    assert per_token == 2_431_217_664
    # at the ledger's 42,228 tokens/s/chip (PR 22, which read 52.15 with
    # its own count of the parameters)
    assert 100 * per_token * 42228 / 197e12 == pytest.approx(52.114, abs=1e-3)


def test_attention_cost_and_roofline_by_hand():
    f, b = flops.flash_attention_train_cost(8, 16, 1024, 64, 24)
    assert f == 7 * 2 * 8 * 16 * 1024 * 1024 * 64 * 0.5 * 24
    assert b == 12 * (8 * 16 * 1024 * 64 * 2) * 24
    peak = {"flops_per_s": 197e12, "bytes_per_s": 819e9}
    t, bound = flops.roofline_seconds(f, b, peak)
    assert bound == "flops" and t == pytest.approx(7.326e-3, rel=1e-3)
    assert flops.roofline_seconds(1.0, 819e9, peak) == (1.0, "bytes")
    dense, _ = flops.flash_attention_train_cost(8, 16, 1024, 64, 24,
                                                causal=False)
    assert dense == 2 * f


def test_decode_bytes_by_hand():
    assert flops.kv_bytes_per_token(24, 1024) == 98304
    n = flops.gpt2_param_count(50257, 1024, 1024, 24)
    assert flops.decode_step_bytes(n, 1000, 24, 1024) == \
        2 * n + 1000 * 98304


# ----------------------------------------------------------------- trace
def test_interval_arithmetic():
    assert trace.union([[5, 7], [0, 2], [1, 3], [7, 8]]) == [[0, 3], [5, 8]]
    assert trace.length([[0, 3], [5, 8]]) == 6
    assert trace.clip([[0, 3], [5, 8]], 2, 6) == [[2, 3], [5, 6]]
    assert trace.subtract([[0, 10]], [[2, 3], [5, 6]]) == \
        [[0, 2], [3, 5], [6, 10]]
    assert trace.subtract([[0, 4], [6, 9]], [[3, 7]]) == [[0, 3], [7, 9]]
    assert trace.subtract([[0, 4]], []) == [[0, 4]]
    assert trace.base_name("%fusion.123 = bf16[8]{0} fusion(...)") == "fusion"
    assert trace.base_name("all-gather-start.5") == "all-gather-start"
    assert trace.op_name("%jvp__.3 = bf16[8] custom-call(%fusion.1)") == \
        "jvp__.3"


def synthetic():
    """Two chips, two steps of 100 ns with 10 ns between them; chip 0
    spends 30 ns of each step in an all-gather of which 10 ns run under a
    fusion (on a line of its own), so 20 are exposed."""
    def chip(offset):
        ops, mods = [], []
        for k in range(2):
            t = offset + k * 110
            mods.append([f"jit__micro_step({k})", t, 100])
            ops += [["%fusion.1 = f32[] fusion()", t, 40],
                    ["%all-gather.7 = f32[] all-gather()", t + 40, 30],
                    ["%jvp__.2 = bf16[] custom-call(%fusion.1)", t + 70, 30]]
        overlap = [["%fusion.9 = f32[] fusion()", offset + 60 + k * 110, 10]
                   for k in range(2)]
        return {"lines": [{"name": "XLA Modules", "events": mods},
                          {"name": "XLA Ops", "events": ops + overlap}]}
    host = {"name": "/host:CPU", "lines": [{"name": "python3", "events": [
        ["bench/trace_window", 0, 230], ["bench/train_batch", 95, 20],
        ["bench/block_sync", 210, 20]]}]}
    return {"planes": [dict(chip(0), name="/device:TPU:0"),
                       dict(chip(35), name="/device:TPU:1"), host]}


def test_reduction_on_a_worked_trace():
    view = synthetic()
    assert [p["name"] for p in trace.device_planes(view)] == \
        ["/device:TPU:0", "/device:TPU:1"]
    assert trace.window_of(view) == [0, 230]
    busy, window = trace.busy_seconds(view)
    # chip 0 is busy 200 of the 230 ns; chip 1 starts 35 ns later, so the
    # last 15 ns of its second step fall outside the window: 185
    assert window == pytest.approx(230e-9)
    assert busy == pytest.approx((200 + 185) / 2 * 1e-9)
    assert trace.idle_share(view) == pytest.approx(1 - 192.5 / 230)
    p0 = trace.device_planes(view)[0]
    assert trace.gaps_before(p0, "micro_step") == [10.0]
    assert len(trace.module_events(p0, "micro_step|batch_step")) == 2
    sums = trace.sum_by_name(trace.line_events(p0, trace.OPS_LINE))
    assert sums == {"fusion": 100.0, "all-gather": 60.0, "jvp__": 60.0}
    bd = trace.breakdown(view)
    assert bd["device_ops"][0] == ["fusion", pytest.approx(200e-9)]   # both chips
    # chip 0 idles 100-110 (under train_batch) and 210-230 (block_sync)
    assert dict(map(tuple, bd["idle_gaps"])) == {
        "block_sync": pytest.approx(20e-9),
        "train_batch": pytest.approx(10e-9)}


def test_readers_on_the_worked_trace():
    from loader import load_module

    class Ctx:
        peaks = {"flops_per_s": 197e12, "bytes_per_s": 819e9}
        log = staticmethod(lambda msg: None)
    view, facts = synthetic(), {}
    read = lambda name, **kw: load_module("readers", name).read(
        view=view, facts=facts, ctx=Ctx, **kw)
    assert read("module_device_ms", pattern="micro_step") == \
        pytest.approx(100e-6)
    assert read("module_gap_ms", pattern="micro_step") == pytest.approx(10e-6)
    assert read("op_ms_per_step", op_pattern="jvp_",
                step_pattern="micro_step") == pytest.approx(30e-6)
    # a fusion's text names its operands: only the op's own name counts
    assert read("op_ms_per_step", op_pattern="fusion",
                step_pattern="micro_step") == pytest.approx(50e-6)
    assert read("module_device_ms", pattern="no_such_program") is None
    assert read("fact", key="absent") is None
    facts.update(a={"b": 3.0}, c=4.0)
    assert read("fact", key="a.b", over="c", scale=100.0) == 75.0
    # MFU of the compiled step from its device time: 100 ns a step here
    assert read("train_mfu", pattern="micro_step") is None    # no facts
    facts.update(tokens_per_step=8192, chips=1, n_params=354871296,
                 model={"layers": 24, "hidden": 1024, "seq": 1024})
    per_step = (6 * 354871296 + 12 * 24 * 1024 * 1024) * 8192
    assert read("train_mfu", pattern="micro_step") == pytest.approx(
        100 * per_step / 100e-9 / 197e12)


def test_recorded_trace_from_the_chip():
    """A cut of a real v5e trace of `gpt2-345m.train-1k` (my chip run, PR
    23: every program event and host span, the first 300 operations): the
    planes, lines and names the reduction depends on are the ones a chip
    writes."""
    path = os.path.join(os.path.dirname(__file__), "recorded_trace.json")
    with open(path) as f:
        view = json.load(f)
    planes = trace.device_planes(view)
    assert planes and all(trace.line_events(p, trace.MODULES_LINE)
                          and trace.line_events(p, trace.OPS_LINE)
                          for p in planes)
    steps = [len(trace.module_events(p, "micro_step|batch_step"))
             for p in planes]
    assert steps == [20]                      # two blocks of ten steps
    ops = trace.line_events(planes[0], trace.OPS_LINE)
    assert len(trace.matching_ops(ops, "jvp_")) == 3      # by own name ...
    assert len(trace.matching(ops, "custom-call")) == 13  # ... not operands
    assert "bench/trace_window" in trace.host_spans(view)
    lo, hi = trace.window_of(view)
    for p in planes:
        busy = trace.length(trace.clip(trace.union(trace.spans(
            trace.line_events(p, trace.OPS_LINE))), lo, hi))
        assert 0 < busy <= hi - lo
        gaps = trace.gaps_before(p, "micro_step|batch_step")
        assert len(gaps) == steps[0] - 1 and min(gaps) >= 0
        # the host's gap between steps on this chip: 1-2 ms
        assert 0.5e6 < sum(gaps) / len(gaps) < 3e6


# ------------------------------------------------------------ BENCHMARK.json
NAME = re.compile(r"^[A-Za-z0-9_][A-Za-z0-9_.\-]{0,63}$")
UNIT = re.compile(r"^[A-Za-z0-9_/%.\-]{1,16}$")


def test_benchmark_json_keeps_the_contract():
    with open(os.path.join(ROOT, "BENCHMARK.json")) as f:
        raw = f.read()
    bench = json.loads(raw)
    assert len(raw) <= 64 * 1024
    assert set(bench) == {"command", "paths", "run_seconds", "configs",
                          "workloads", "end_to_end", "per_layer"}
    names = []
    for group in ("configs", "workloads", "end_to_end", "per_layer"):
        for entry in bench[group]:
            assert NAME.match(entry["name"]), entry["name"]
            names.append((group[:3] == "end" or group[:3] == "per",
                          group, entry["name"]))
    metric_names = [n for m, _, n in names if m]
    assert len(metric_names) == len(set(metric_names))
    cells = {w["name"]: w for w in bench["workloads"]}
    configs = {c["name"]: c for c in bench["configs"]}
    assert len(cells) == len(bench["workloads"])
    e2e = {m["name"]: m for m in bench["end_to_end"]}
    assert "setup_s" in e2e and e2e["setup_s"]["bound"] <= 0.1
    for w in bench["workloads"]:
        assert set(w) == {"name", "config", "traffic", "chips", "why"}
        assert w["config"] in configs and NAME.match(w["traffic"])
        assert w["chips"] in (1, 4) and 1 <= len(w["why"]) <= 200
        assert os.path.isfile(os.path.join(BENCH_DIR, "traffic",
                                           w["traffic"] + ".json"))
    assert sum(w["chips"] == 4 for w in bench["workloads"]) <= \
        max(1, len(cells) // 4)
    for c in bench["configs"]:
        assert set(c) == {"name", "source", "file", "reduced", "why"}
        assert c["file"].startswith("benchmarks/")
        assert os.path.isfile(os.path.join(ROOT, c["file"]))
        assert all(NAME.match(k) for k in c["reduced"])
        assert any(w["config"] == c["name"] for w in bench["workloads"])
    for m in bench["end_to_end"]:
        assert set(m) - {"workloads"} == {"name", "unit", "better", "bound",
                                          "source"}
        assert UNIT.match(m["unit"]) and m["better"] in ("lower", "higher")
        assert 0.01 <= m["bound"] <= 0.1
        assert m["source"] in ("host_clock", "device_trace")
    layers = set()
    for m in bench["per_layer"]:
        assert set(m) - {"workloads"} == {"name", "unit", "better", "source",
                                          "layer", "moves"}
        assert UNIT.match(m["unit"]) and m["better"] in ("lower", "higher")
        assert m["source"] in ("device_trace", "program_span",
                               "program_counter", "host_clock")
        layers.add(m["layer"])
        # `moves` is reported in every cell where this metric is
        where = m.get("workloads", list(cells))
        moved = e2e[m["moves"]]
        assert set(where) <= set(moved.get("workloads", list(cells)))
        assert all(w in cells for w in where)
        with open(os.path.join(BENCH_DIR, "metrics",
                               m["name"] + ".json")) as f:
            spec = json.load(f)
        assert os.path.isfile(os.path.join(BENCH_DIR, "readers",
                                           spec["reader"] + ".py"))
        assert spec["layer"] == m["layer"] and spec["moves"] == m["moves"]
    for name, cell in cells.items():
        have = [m for m in bench["end_to_end"]
                if name in m.get("workloads", [name])]
        assert len(have) >= 2                   # setup_s and one other
        assert any(name in m.get("workloads", [name])
                   for m in bench["per_layer"])
    # no metric file without an entry, no traffic file without a cell
    assert {f[:-5] for f in os.listdir(os.path.join(BENCH_DIR, "metrics"))} \
        == {m["name"] for m in bench["per_layer"]}
    assert {f[:-5] for f in os.listdir(os.path.join(BENCH_DIR, "traffic"))} \
        == {w["traffic"] for w in bench["workloads"]}
    rs = bench["run_seconds"]
    assert (2 + 14 * 24) * (rs + 60) + 24 * 180 + 1200 <= 43200
    for path in bench["paths"]:
        for folder, _, files in os.walk(os.path.join(ROOT, path)):
            if "__pycache__" in folder:
                continue
            for fn in files:
                assert re.match(r"^[A-Za-z0-9_.\-]+$", fn), fn
