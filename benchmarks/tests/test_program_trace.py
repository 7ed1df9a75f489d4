"""The readers of the program's own names, by hand-worked cases and on a
trace recorded from a chip run of each cell. Run by hand:

    python -m pytest benchmarks/tests -q

Not part of tier-1 (it lives outside `tests/`). No chip.
"""

import json
import os
import sys

import pytest

BENCH_DIR = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
ROOT = os.path.dirname(BENCH_DIR)
for path in (ROOT, BENCH_DIR):
    if path not in sys.path:
        sys.path.insert(0, path)

from core import program_trace as pt  # noqa: E402
from loader import load_module  # noqa: E402

SCOPES = ("attn_core", "kv_gather", "kv_write", "mlp", "loss_head")
SPANS = ("serve/decode", "serve/decode/build", "serve/decode/dispatch",
         "serve/prefill", "serve/record", "serve/metrics", "train_batch",
         "data")


class Ctx:
    trace_dir = "/nowhere"

    def __init__(self):
        self.lines = []

    def log(self, msg):
        self.lines.append(msg)


def reader(name, monkeypatch, view, scopes=SCOPES, spans=SPANS):
    """The reader module `name`, reading `view` in place of a file."""
    monkeypatch.setattr(pt, "load", lambda trace_dir: view)
    monkeypatch.setattr(pt, "registry", lambda: (scopes, spans))
    return load_module("readers", name)


# ---------------------------------------------------------- scope paths
def test_innermost_registered_scope_wins():
    f = lambda path: pt.scope_of(path, SCOPES)           # noqa: E731
    assert f("jit(step)/jvp(mlp)/dot_general") == "mlp"
    assert f("jit(step)/transpose(jvp(mlp))/dot_general") == "mlp"
    assert f("jit(decode)/attn_core/kv_gather/gather") == "kv_gather"
    assert f("jit(decode)/attn_core/kv_write/scatter") == "kv_write"
    assert f("jit(step)/jvp(loss_head)/while/body/closed_call/"
             "checkpoint/dot_general") == "loss_head"
    # a primitive that happens to share a scope's name is no scope
    assert f("jit(step)/mlp") is None
    assert f("jit(step)/jit(_threefry_split)/add") is None
    assert f("") is None
    assert pt.backward("jit(s)/transpose(jvp(mlp))/dot_general")
    assert not pt.backward("jit(s)/jvp(mlp)/dot_general")


def test_an_operation_with_no_name_is_charged_to_what_it_reads():
    gather = "jit(_decode)/attn_core/kv_gather/gather"
    md = {
        1: ("%fusion.4 = bf16[8]{0} fusion(bf16[9]{0} %copy.7), kind=kLoop, "
            "calls=%fused_computation.4", gather, 11),
        # made by the compiler: reads the gather's result
        2: ("%convert.3 = f32[8]{0} convert(bf16[8]{0} %fusion.4)", "", 11),
        # reads a parameter (no event): charged to the one that reads it
        3: ("%copy.7 = bf16[9]{0} copy(bf16[9]{0} %bitcast.1)", "", 11),
        # a chain: copy-done <- copy-start <- the convert above
        4: ("%copy-start.1 = (f32[8]{0}, f32[8]{0}, u32[]) copy-start("
            "f32[8]{0} %convert.3)", "", 11),
        5: ("%copy-done.1 = f32[8]{0} copy-done((f32[8]{0}, f32[8]{0}, u32[]) "
            "%copy-start.1)", "", 11),
        # nothing named near it: stays nameless
        6: ("%iota.1 = s32[8]{0} iota()", "", 11),
        # the same instruction name in ANOTHER program is another thing
        7: ("%convert.3 = f32[8]{0} convert(bf16[8]{0} %fusion.4)", "", 12),
    }
    got = pt.charge_nameless(md)
    assert got[1][1] == gather
    for k in (2, 3, 4, 5):
        assert got[k][1] == "~" + gather, k
    assert got[6][1] == "" and got[7][1] == ""
    assert pt.scope_of(got[5][1], SCOPES) == "kv_gather"


def test_a_while_keeps_only_its_own_time():
    ops = [["while.1", 0.0, 100.0, "jit(s)/jvp(loss_head)/while", "while"],
           ["fusion.1", 10.0, 30.0, "jit(s)/jvp(loss_head)/while/body/dot",
            "fusion"],
           ["fusion.2", 40.0, 50.0, "jit(s)/jvp(loss_head)/while/body/exp",
            "fusion"],
           ["fusion.3", 100.0, 20.0, "jit(s)/jvp(mlp)/dot_general",
            "fusion"]]
    assert pt.self_ns(ops) == [20.0, 30.0, 50.0, 20.0]


# ---- two decode runs of 100 ns and one prefill; in decode run 1 the
# operations tile the program, in run 2 they leave 10 ns empty
def serving_view():
    ops = [
        ["fusion.1", 0.0, 40.0, "jit(_decode)/attn_core/kv_gather/gather",
         "fusion"],
        ["convert.1", 40.0, 20.0,           # no name of its own: inherited
         "~jit(_decode)/attn_core/kv_gather/gather", "convert"],
        ["fusion.2", 60.0, 25.0, "jit(_decode)/mlp/dot_general", "fusion"],
        ["copy.1", 85.0, 15.0, "", "copy"],
        ["fusion.9", 200.0, 50.0, "jit(_prefill)/attn_core/kv_write/scatter",
         "fusion"],
        ["fusion.1", 300.0, 50.0, "jit(_decode)/attn_core/kv_gather/gather",
         "fusion"],
        ["convert.1", 350.0, 10.0,
         "~jit(_decode)/attn_core/kv_gather/gather", "convert"],
        ["fusion.2", 360.0, 25.0, "jit(_decode)/mlp/dot_general", "fusion"],
        ["copy.1", 385.0, 5.0, "", "copy"],
    ]
    return {"devices": [{"name": "/device:TPU:0", "ops": ops, "modules": [
        ["jit__decode_paged_impl(1)", 0.0, 100.0],
        ["jit__prefill_paged_impl(2)", 200.0, 50.0],
        ["jit__decode_paged_impl(1)", 300.0, 100.0]]}],
        "host": [
        ["serve/decode", -5.0, 110.0,
         {"rows": 4, "table_pages": 2, "page_size": 16, "live_tokens": 40}],
        ["serve/decode/build", -5.0, 4.0, {}],
        ["serve/decode/dispatch", -1.0, 2.0, {}],
        ["serve/record", 106.0, 6.0, {}],
        ["serve/metrics", 112.0, 8.0, {}],
        ["serve/prefill", 190.0, 70.0,
         {"batch": 2, "prompt": 8, "real_tokens": 10}],
        ["serve/decode", 290.0, 116.0,
         {"rows": 4, "table_pages": 2, "page_size": 16, "live_tokens": 88}],
        ["serve/decode/build", 290.0, 6.0, {}],
        ["serve/record", 406.0, 4.0, {}],
        ["serve/metrics", 410.0, 2.0, {}]]}


def test_scopes_plus_unscoped_equal_the_programs_operations(monkeypatch):
    view = serving_view()
    totals, module_ns, runs = pt.program_scopes(view, "decode", SCOPES)
    assert (module_ns, runs) == (100.0, 2)
    by = {}
    for (scope, _, _, _), ns in totals.items():
        by[scope] = by.get(scope, 0.0) + ns
    # per run: gather (40 + 20 + 50 + 10) / 2, mlp 25, no-name copy 10
    assert by == {"kv_gather": 60.0, "mlp": 25.0, pt.UNSCOPED: 10.0}
    # 95 of the program's 100: run 2 left 10 ns with no operation
    assert sum(by.values()) == 95.0
    # the prefill program's operations are not in the decode's table
    assert "kv_write" not in by

    ctx = Ctx()
    rd = reader("scope_ms_per_step", monkeypatch, view)
    read = lambda **kw: rd.read(None, {}, ctx, step_pattern="decode", **kw)  # noqa: E731,E501
    assert read(scopes=["kv_gather"]) == pytest.approx(60.0 / 1e6)
    assert read(scopes=["kv_gather", "mlp"]) == pytest.approx(85.0 / 1e6)
    assert read(scopes=["unscoped"], share=True) == pytest.approx(10.0)
    # `opcode` keeps one kind of operation: the stripe's cast alone
    assert read(scopes=["kv_gather"], opcode="convert") == \
        pytest.approx(15.0 / 1e6)
    assert read(scopes=["loss_head"]) == 0.0
    # what rests on the neighbour rule and what on a name of its own:
    # the two converts, 15 of the program's 100 a run
    assert read(scopes=None, share=True, inherited=True) == \
        pytest.approx(15.0)
    assert read(scopes=["kv_gather"], inherited=False) == \
        pytest.approx(45.0 / 1e6)
    assert read(scopes=None) == pytest.approx(95.0 / 1e6)
    assert rd.read(None, {}, ctx, scopes=["mlp"],
                   step_pattern="micro_step") is None
    # the table and the cross-check are logged once a program
    assert sum("add up to" in ln for ln in ctx.lines) == 1
    assert "(5.000% of it no operation runs)" in "".join(ctx.lines)


def test_a_gap_goes_to_the_innermost_span_over_its_middle(monkeypatch):
    view = serving_view()
    # the window, as run.py marks it: [-10, 420)
    old = {"planes": [
        {"name": "/device:TPU:0", "lines": [{"name": "XLA Ops", "events": [
            ev[:3] for ev in view["devices"][0]["ops"]]}]},
        {"name": "/host:CPU", "lines": [{"name": "python3", "events": [
            ["bench/trace_window", -10.0, 430.0]]}]}]}
    rd = reader("gap_by_span", monkeypatch, view)
    gaps = rd.by_span(old, view)
    # idle: [-10, 0) middle -5 lies in serve/decode AND its build: the
    # build is the innermost; [100, 200) middle 150 in no program span;
    # [250, 300) middle 275 in no span; [390, 420) middle 405 in the
    # second serve/decode (its record span starts at 406)
    assert gaps == {"serve/decode/build": 10.0, rd.NO_SPAN: 150.0,
                    "serve/decode": 30.0}
    ctx = Ctx()
    assert rd.read(old, {}, ctx) == pytest.approx(100 * 150.0 / 190.0)
    assert rd.read(old, {}, ctx, span="serve/decode") == \
        pytest.approx(100 * 30.0 / 190.0)
    # in ms per step: both serve/decode spans start inside the window
    assert rd.read(old, {}, ctx, span="serve/decode",
                   per="serve/decode") == pytest.approx(15.0 / 1e6)
    assert rd.read(old, {}, ctx, span="serve/decode", per="data") is None


def test_ratios_of_span_arguments_and_span_times(monkeypatch):
    view = serving_view()
    ctx = Ctx()
    rd = reader("span_args_share", monkeypatch, view)
    # live tokens over what the gather reads: (40 + 88) / (2 x 4 x 2 x 16)
    assert rd.read(None, {}, ctx, span="serve/decode", of=["live_tokens"],
                   over=["rows", "table_pages", "page_size"]) == \
        pytest.approx(50.0)
    # padding: 100 - 10 real tokens of a 2 x 8 bucket
    assert rd.read(None, {}, ctx, span="serve/prefill", of=["real_tokens"],
                   over=["batch", "prompt"], complement=True) == \
        pytest.approx(37.5)
    # a span that carries no such argument gives nothing, not a crash
    assert rd.read(None, {}, ctx, span="serve/decode", of=["width"],
                   over=["rows"]) is None
    ms = reader("span_ms", monkeypatch, view)
    assert ms.read(None, {}, ctx, spans=["serve/decode/build"],
                   per="serve/decode") == pytest.approx(5.0 / 1e6)
    assert ms.read(None, {}, ctx, spans=["serve/record", "serve/metrics"],
                   per="serve/decode") == pytest.approx(10.0 / 1e6)
    assert ms.read(None, {}, ctx, spans=["data"], per="train_batch") is None


@pytest.mark.parametrize("name,params", [
    ("scope_ms_per_step", {"scopes": ["mlp"], "step_pattern": "decode"}),
    ("span_ms", {"spans": ["data"], "per": "train_batch"}),
    ("span_args_share", {"span": "serve/decode", "of": ["live_tokens"],
                         "over": ["rows"]}),
    ("gap_by_span", {}),
    ("gap_by_span", {"span": "train/tail", "per": "train_batch"}),
    ("scope_ms_per_step", {"scopes": None, "step_pattern": "decode",
                           "share": True, "inherited": True}),
])
def test_a_program_without_the_names_gives_nothing(monkeypatch, name,
                                                   params):
    """The parent commit has no registry: `load` is None and every
    reader leaves its metric out."""
    rd = reader(name, monkeypatch, None)
    monkeypatch.setattr(pt, "registry", lambda: None)
    assert rd.read({"planes": []}, {}, Ctx(), **params) is None


# ------------------------------------------------------- the wire reader
def _varint(v):
    v &= (1 << 64) - 1
    out = bytearray()
    while True:
        out.append((v & 0x7F) | (0x80 if v > 0x7F else 0))
        v >>= 7
        if not v:
            return bytes(out)


def _msg(*fields):
    """Encode (number, value) pairs: ints as varints, bytes/str as
    length-delimited."""
    out = bytearray()
    for number, value in fields:
        if isinstance(value, int):
            out += _varint(number << 3) + _varint(value)
        else:
            data = value.encode() if isinstance(value, str) else value
            out += _varint(number << 3 | 2) + _varint(len(data)) + data
    return bytes(out)


def test_the_wire_reader_finds_names_times_and_tf_op(tmp_path):
    stat_md = lambda i, name: _msg((1, i), (2, _msg((1, i), (2, name))))  # noqa: E731,E501
    event_md = lambda i, text, *stats: _msg(                  # noqa: E731
        (1, i), (2, _msg((1, i), (2, text), *[(5, s) for s in stats])))
    plane = _msg(
        (2, "/device:TPU:0"),
        (5, stat_md(7, "hlo_category")), (5, stat_md(9, "tf_op")),
        (4, event_md(1, "jit__decode(11)")),
        (4, event_md(2, "%fusion.3 = bf16[8]{0} fusion(%x), kind=kLoop",
                     _msg((1, 7), (5, "loop fusion")),
                     _msg((1, 9), (5, "jit(_decode)/mlp/dot_general:")))),
        (4, event_md(3, "%copy.1 = bf16[8]{0} copy(%y)")),
        (3, _msg((2, "XLA Modules"), (3, 1000),
                 (4, _msg((1, 1), (2, 5000), (3, 90000))))),
        (3, _msg((2, "XLA Ops"), (3, 1000),
                 (4, _msg((1, 2), (2, 5000), (3, 60000))),
                 (4, _msg((1, 3), (2, 65000), (3, 30000))))),
        (3, _msg((2, "Steps"), (3, 1000), (4, _msg((1, 1), (2, 0))))))
    host = _msg((2, "/host:CPU"), (3, _msg((2, "python3"))))
    path = tmp_path / "t.xplane.pb"
    path.write_bytes(_msg((1, host), (1, plane)))
    planes = pt._raw_device_planes(str(path))
    assert planes == [{
        "name": "/device:TPU:0",
        "modules": [["jit__decode(11)", 1005.0, 90.0]],
        "ops": [["fusion.3", 1005.0, 60.0, "jit(_decode)/mlp/dot_general",
                 "fusion"],
                ["copy.1", 1065.0, 30.0, "", "copy"]]}]
    assert pt.opcode_of("%x.3 = (bf16[8]{0:T(8,128)(2,1)S(1)}, f32[8]{0}) "
                        "custom-call(bf16[8]{0} %y), custom_call_target="
                        '"tpu_custom_call"') == "custom-call"


# -------------------------------------------------- the recorded traces
RECORDED = os.path.join(os.path.dirname(os.path.abspath(__file__)),
                        "recorded_program_trace.json")


# metrics retired since the recording was made, as their files read: the
# recording is of the gather reader (before PR 30), and still holds the
# scope reader to what that chip run reported under `kv_gather`
RETIRED = {"decode_scope_kv_gather_ms.sat": {
    "reader": "scope_ms_per_step",
    "params": {"scopes": ["kv_gather"], "step_pattern": "decode"}}}


@pytest.fixture(scope="module")
def recorded():
    """The recording, with each operation's interned scope path put
    back in place (the file's `about` says how it was cut)."""
    with open(RECORDED) as f:
        data = json.load(f)
    for view in data.values():
        if isinstance(view, dict) and "paths" in view:
            for plane in view["devices"]:
                for ev in plane["ops"]:
                    ev[3] = view["paths"][ev[3]]
    return data


@pytest.mark.parametrize("cell,pattern,scopes", [
    ("gpt2-345m.train-1k", "micro_step",
     ["attn_core", "attn_proj", "mlp", "loss_head", "opt_update", "ln"]),
    ("gpt2-345m.serve-saturated", "decode",
     ["kv_gather", "kv_write", "attn_cached", "mlp", "lm_head", "sample"]),
])
def test_recorded_chip_trace_reads_as_on_the_chip(recorded, monkeypatch,
                                                  cell, pattern, scopes):
    from deepspeed_tpu.profiling.spans import DEVICE_SCOPES, HOST_SPANS
    view = recorded[cell]
    totals, module_ns, runs = pt.program_scopes(view, pattern,
                                                DEVICE_SCOPES)
    by = {}
    for (scope, _, _, _), ns in totals.items():
        by[scope] = by.get(scope, 0.0) + ns
    for scope in scopes:
        assert by.get(scope, 0.0) > 0.0, scope
    # scopes and unscoped add up to the program's device time but for
    # what no operation runs in
    assert sum(by.values()) == pytest.approx(module_ns, rel=0.005)
    assert by.get(pt.UNSCOPED, 0.0) < 0.10 * module_ns
    # what the recording's own chip run reported for it, through the
    # reader and the metric's own file
    rd = reader("scope_ms_per_step", monkeypatch, view, DEVICE_SCOPES,
                HOST_SPANS)
    checked = 0
    for name, value in recorded["reported"][cell].items():
        path = os.path.join(BENCH_DIR, "metrics", name + ".json")
        spec = json.load(open(path)) if os.path.isfile(path) \
            else RETIRED[name]
        if spec["reader"] != "scope_ms_per_step":
            continue
        # the cut holds the first run of each program, the metric's
        # value the mean of all the traced ones
        assert rd.read(None, {}, Ctx(), **spec["params"]) == \
            pytest.approx(value, rel=0.03), name
        checked += 1
    assert checked >= 7
