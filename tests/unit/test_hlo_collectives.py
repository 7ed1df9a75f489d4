"""Compiled-HLO collective audit (VERDICT r3 #4).

The only multi-chip PERF evidence this rig can produce: compile the
8-device ZeRO-2 data-parallel step and the 2x2x2 3D pipeline step on the
virtual CPU mesh, walk the partitioned HLO, and pin the communication
volume to theory. Reference scaling claims these de-risk:
/root/reference/docs/_tutorials/megatron.md:402-408 (ZeRO-2 superlinear
scaling — which requires grad traffic ~P and optimizer state NEVER on
the wire) and the ZeRO paper's 2P-per-step communication bound.

Counting rule: ELEMENTS, not bytes — the CPU backend upcasts bf16 dots
to f32, so the same program ships 2x the bytes it would on TPU while
element counts are invariant. all-reduce is counted 2x (ring cost =
reduce-scatter + all-gather); all-to-all / all-gather / reduce-scatter /
collective-permute count 1x their output.

What is asserted (robust to GSPMD strategy choice, fatal to real
regressions):
- ZeRO-2 micro step total wire traffic in [P, 2.6 P] elements: the
  theoretical shape is gather(P params) + reduce-scatter(P grads) ~ 2 P;
  an accidental duplicated grad all-reduce, a per-micro optimizer-state
  gather, or m/v (2 P fp32) crossing the wire all blow the bound.
- no single collective moves > 1.1 P elements (no monolithic state
  gather).
- with gradient accumulation, the per-micro (off-boundary) path ships
  gather(P) + grad-reduction(P) — the FSDP-style shape GSPMD derives
  from sharded fp32 masters — while the boundary branch's optimizer
  update is SHARD-LOCAL (<= 0.2 P): optimizer state and masters never
  cross the wire.
- 3D step: collective-permutes exist and each moves exactly one
  activation tile (mb_local x seq x hidden, possibly model-sharded);
  together with test_pipe.py's scan-weighted tick counts (2 ppermutes
  per tick) this bounds pipeline traffic = 2 ticks x tile.

Documented in docs/performance.md ("multi-chip communication audit").
"""

import re

import jax
import jax.numpy as jnp
import numpy as np
import pytest

import deepspeed_tpu as ds
# shared HLO collective accounting (also feeds test_hlo_quantized_comm.py)
from deepspeed_tpu.utils.hlo_audit import (
    collect_collectives, wire_elements,
    conditional_branch_comps as _conditional_branch_comps,
    hlo_computation_body as _hlo_computation_body)

pytestmark = pytest.mark.slow      # multi-minute 8-dev compiles


def _mlp_engine(gas=1):
    def loss_fn(params, batch, rngs=None):
        h = jnp.tanh(batch["x"] @ params["w1"])
        p = h @ params["w2"]
        return jnp.mean((p - batch["y"]) ** 2)

    key = jax.random.PRNGKey(0)
    params = {"w1": jax.random.normal(key, (256, 512)) * 0.1,
              "w2": jax.random.normal(key, (512, 128)) * 0.1}
    P = 256 * 512 + 512 * 128
    engine, *_ = ds.initialize(
        model=loss_fn, model_parameters=params,
        config={"train_micro_batch_size_per_gpu": 4,
                "gradient_accumulation_steps": gas,
                "bf16": {"enabled": True},
                "zero_optimization": {"stage": 2},
                "steps_per_print": 10**9,
                "optimizer": {"type": "Adam", "params": {"lr": 1e-3}}})
    from jax.sharding import NamedSharding, PartitionSpec
    shd = NamedSharding(engine.mesh, PartitionSpec("data"))
    rs = np.random.RandomState(0)
    batch = {
        "x": jax.device_put(rs.randn(32, 256).astype(np.float32), shd),
        "y": jax.device_put(rs.randn(32, 128).astype(np.float32), shd)}
    return engine, batch, P


def _micro_step_hlo(engine, batch):
    # the engine's OWN jit wrapper (test_zero3.py technique): the audit
    # must measure the production program, not a hand-copied jit config
    return (engine._get_compiled_micro_step()
            .lower(engine.state, batch).compile().as_text())


def test_zero2_step_wire_traffic_matches_theory():
    engine, batch, P = _mlp_engine()
    colls = collect_collectives(_micro_step_hlo(engine, batch))
    assert colls, "partitioned ZeRO-2 step has no collectives at all?"
    total = wire_elements(colls)
    # theory: all-gather(P params) + reduce-scatter(P grads) = 2 P (+
    # small activation-strategy and scalar terms). 2.6 P headroom covers
    # GSPMD picking activation-gather strategies for small dims; any
    # optimizer-state traffic (+2 P at minimum) or duplicated grad
    # all-reduce (+2 P) blows it.
    assert P <= total <= 2.6 * P, (total, P, [c[:2] for c in colls])
    # no monolithic gather: nothing bigger than one full param set
    biggest = max(c[1] for c in colls)
    assert biggest <= 1.1 * P, (biggest, P)


def test_zero2_grad_accumulation_boundary_split():
    """Per-micro (off-boundary) traffic is gather(P) + grad
    reduction(P): with sharded fp32 masters the forward re-gathers
    params each micro (the FSDP-style shape GSPMD produces from the
    sharding assignments) and ZeRO-2 reduces gradients every micro
    (reference IPG bucketing, zero/stage2.py:621 there). The OPTIMIZER
    UPDATE on the boundary lax.cond branch must be shard-local —
    optimizer state and masters never cross the wire."""
    engine, batch, P = _mlp_engine(gas=4)
    txt = _micro_step_hlo(engine, batch)
    colls = collect_collectives(txt)
    branch_comps = _conditional_branch_comps(txt)
    assert branch_comps, "gas=4 micro step compiled without the " \
                         "boundary conditional?"
    off_boundary = [c for c in colls if c[3] not in branch_comps]
    on_boundary = [c for c in colls if c[3] in branch_comps]
    per_micro = wire_elements(off_boundary)
    # gather(P) + reduce(P) + activation-strategy slack; optimizer
    # state (2 P fp32) appearing here would blow the bound
    assert P <= per_micro <= 2.4 * P, (per_micro, P,
                                       [c[:2] for c in off_boundary])
    # the update itself is shard-local: nothing param-scale on the
    # boundary branch (small resharding all-to-alls are tolerated)
    boundary = wire_elements(on_boundary)
    assert boundary <= 0.2 * P, (boundary, P,
                                 [c[:2] for c in on_boundary])


def test_zero2_param_gather_rides_compute_dtype_cast():
    """The compute-dtype cast sits AHEAD of the per-micro param
    all-gather — the bf16 value is what crosses the wire.

    With fp32 masters sharded ZeRO-style, GSPMD is in principle free to
    gather the f32 master values and cast downstream — 2x the wire
    bytes of a bf16 gather (the former docs/performance.md caveat).
    engine._cast_for_loss pins the compute-dtype cast to the master's
    sharded layout (with_sharding_constraint) so the cast runs
    shard-local. Two backend-invariant checks:

    1. StableHLO (pre-partitioning): every param leaf has an
       ``sdy.sharding_constraint`` on a BF16 tensor of its shape with a
       non-empty axis binding — the cast-then-constrain order is in the
       program, so the partitioner reshards the bf16 value.
    2. Partitioned HLO: no param-scale all-gather consumes a raw state
       parameter; each gather's operand chain contains the bf16
       rounding (the cast scheduled ahead of the wire).

    Byte-level dtype cannot be asserted on the CPU audit backend:
    FloatNormalization re-expands bf16 math to f32 (dots, tanh have no
    CPU bf16 kernels), so the gather result prints f32 here while the
    same program moves bf16 on TPU, where the constrained bf16 value
    feeds the MXU directly."""
    engine, batch, P = _mlp_engine(gas=4)
    lowered = (engine._get_compiled_micro_step()
               .lower(engine.state, batch))
    stable = lowered.as_text()
    for shape in ("256x512", "512x128"):
        # shardy partitioner (newer jax): sdy.sharding_constraint; GSPMD
        # (jax < 0.5): a @Sharding custom call with a non-replicated
        # mhlo.sharding — both prove the bf16 value is what gets resharded
        sdy = (r"sdy\.sharding_constraint[^\n]*<@mesh, \[\{\"data\"\}"
               r"[^\n]*tensor<" + shape + r"xbf16>")
        gspmd = (r"custom_call @Sharding[^\n]*devices=\[[^\n]*"
                 r"tensor<" + shape + r"xbf16>")
        assert re.search(sdy, stable) or re.search(gspmd, stable), \
            f"no sharded bf16 constraint for param {shape} in StableHLO"

    txt = lowered.compile().as_text()
    colls = collect_collectives(txt)
    param_gathers = [c for c in colls
                     if c[0] == "all-gather" and c[1] >= 0.2 * P]
    assert param_gathers, \
        "no param-scale all-gather in the compiled step?"
    defn = {m.group(1): line for line in txt.splitlines()
            for m in [re.match(r"^\s*(?:ROOT\s+)?%([\w.\-]+) = ",
                               line.strip())] if m}
    for op, e, line, _ in param_gathers:
        # collect_collectives returns sync `all-gather(` or async
        # `all-gather-done(` lines; for async, hop -done -> -start ->
        # the real data operand. The operand may carry a printed type
        # prefix (`all-gather(f32[...] %x)`) depending on jax version.
        m = re.search(r"all-gather(?:-done)?\((?:\S+(?:\{[\d,]*\})? )?"
                      r"%?([\w.\-]+)", line)
        assert m, line[:160]
        opd_line = defn.get(m.group(1), "")
        sm = re.search(r"all-gather-start\((?:\S+(?:\{[\d,]*\})? )?"
                       r"%?([\w.\-]+)", opd_line)
        if sm:
            opd_line = defn.get(sm.group(1), "")
        # a raw master crossing the wire would be parameter/gte directly
        assert (" parameter(" not in opd_line
                and "get-tuple-element(" not in opd_line), \
            (line[:120], opd_line[:120])
        cm = re.search(r"calls=%([\w.\-]+)", opd_line)
        body = (_hlo_computation_body(txt, cm.group(1))
                if cm else [opd_line])
        assert any("bf16[" in b for b in body), \
            ("gather operand has no bf16 rounding ahead of the wire",
             line[:120], opd_line[:120])


def _onebit_engine():
    """dp=8 OnebitAdam engine with a known param count P."""
    def loss_fn(params, batch, rngs=None):
        h = jnp.tanh(batch["x"] @ params["w1"])
        p = h @ params["w2"]
        return jnp.mean((p - batch["y"]) ** 2)

    key = jax.random.PRNGKey(0)
    params = {"w1": jax.random.normal(key, (256, 512)) * 0.1,
              "w2": jax.random.normal(key, (512, 128)) * 0.1}
    P = 256 * 512 + 512 * 128
    engine, *_ = ds.initialize(
        model=loss_fn, model_parameters=params,
        config={"train_micro_batch_size_per_gpu": 4,
                "steps_per_print": 10**9,
                "optimizer": {"type": "OneBitAdam",
                              "params": {"lr": 1e-3, "freeze_step": 4}}})
    from jax.sharding import NamedSharding, PartitionSpec
    shd = NamedSharding(engine.mesh, PartitionSpec("data"))
    rs = np.random.RandomState(0)
    batch = {
        "x": jax.device_put(rs.randn(32, 256).astype(np.float32), shd),
        "y": jax.device_put(rs.randn(32, 128).astype(np.float32), shd)}
    return engine, batch, P


def test_onebit_adam_compressed_wire_traffic():
    """The 1-bit Adam compression-phase exchange ships <= ~1/5 of the
    warmup (dense) exchange — the reference's headline claim
    (onebit-adam blog: 5x communication-volume reduction; BASELINE.md
    ladder item 5).

    Warmup phase: the momentum exchange is a dense pmean — all-reduce
    of P fp32 values = 2P ring wire elements. Compression phase: the
    packed sign bits ride an all-to-all (P/8 uint8 elements) plus the
    server-chunk all-gather (P/8) and per-rank fp32 scales — ~P/4
    total. In ELEMENTS (the backend-invariant unit, module docstring)
    that is an 8x reduction; in bytes on TPU it is 32x for the payload,
    so asserting elements-ratio >= 5 understates the wire saving."""
    engine, batch, P = _onebit_engine()
    assert engine._onebit_dist

    warm = _micro_step_hlo(engine, batch)
    warm_colls = collect_collectives(warm)
    warm_wire = wire_elements(warm_colls)
    # dense exchange present: pmean(P grads) ~ 2P (+ scalar terms)
    assert warm_wire >= 2 * P, (warm_wire, P,
                                [c[:2] for c in warm_colls])

    # flip to the compression phase exactly as the engine does at
    # freeze_step (recompile with the static phase flag)
    engine._onebit_compression = True
    engine._compiled_micro_step = None
    comp = _micro_step_hlo(engine, batch)
    comp_colls = collect_collectives(comp)
    comp_wire = wire_elements(comp_colls)
    assert comp_colls, "compression phase compiled without collectives?"
    # <= ~1/5 of the dense exchange (measured shape: ~P/4 vs 2P = 1/8)
    assert comp_wire * 5 <= warm_wire, \
        (comp_wire, warm_wire, P, [c[:2] for c in comp_colls])
    # and nothing dense-momentum-sized sneaks through per leaf: no
    # single collective moves more than the largest packed chunk
    # (P/8 elements) plus slack
    biggest = max(c[1] for c in comp_colls)
    assert biggest <= 0.2 * P, (biggest, P,
                                [c[:2] for c in comp_colls])


import functools


@functools.lru_cache(maxsize=1)
def _gpt2_3d_grad_hlo():
    from deepspeed_tpu.models.gpt2 import GPT2Config, gpt2_pipeline_spec
    from deepspeed_tpu.runtime.pipe.spmd import (build_pipeline_grad_fn,
                                                 interleave_stages)
    cfg = GPT2Config(vocab_size=128, max_position_embeddings=32,
                     hidden_size=64, num_layers=4, num_heads=4,
                     embd_dropout=0.0, attn_dropout=0.0,
                     resid_dropout=0.0)
    S, V, M, seq, mb = 2, 2, 4, 16, 4
    mesh = ds.build_mesh({"pipe": S, "data": 2, "model": 2})
    spec = gpt2_pipeline_spec(cfg, num_stages=S * V, dtype=jnp.float32)
    params = spec.init(jax.random.PRNGKey(0))
    params = dict(params)
    params["stages"] = interleave_stages(params["stages"], S, V)
    gf = build_pipeline_grad_fn(spec, mesh, num_micro=M, num_virtual=V)
    batch = {"input_ids": np.zeros((M, mb, seq + 1), np.int32)}
    rng = jax.random.PRNGKey(1)
    txt = (jax.jit(gf).lower(params, batch, rng, 1.0).compile().as_text())
    return txt, dict(S=S, V=V, M=M, seq=seq, mb=mb, hidden=cfg.hidden_size)


def test_3d_pipeline_permute_tile_sizes():
    """Every collective-permute in the compiled 2x2x2 step moves exactly
    one activation tile: mb_local x seq x hidden (or its model-sharded
    half) — never a params-sized or batch-replicated buffer. Combined
    with test_pipe.py::test_interleaved_bubble_tick_count (2 ppermutes
    per tick, scan-weighted) this pins total pipe traffic to
    2 x ticks x tile."""
    txt, d = _gpt2_3d_grad_hlo()
    colls = collect_collectives(txt)
    perms = [(e, line) for op, e, line, _ in colls
             if op == "collective-permute"]
    assert perms, "3D pipeline step compiled without collective-permute?"
    # per-device tile: batch dim sharded over data(2), hidden possibly
    # sharded over model(2) by GSPMD's choice
    tile = (d["mb"] // 2) * d["seq"] * d["hidden"]
    allowed = {tile, tile // 2}
    for e, line in perms:
        assert e in allowed, (e, sorted(allowed), line[:160])


def test_3d_pipeline_no_oversized_collectives():
    """No collective in the 3D step moves more than the largest single
    logical buffer (the stacked per-device stage params): catches a
    whole-model gather/reduce sneaking into the per-tick path."""
    txt, d = _gpt2_3d_grad_hlo()
    colls = collect_collectives(txt)
    # largest legitimate transfer: a full stage-stack grad reduction
    # over the data axis at batch end. hidden x 4*hidden QKV etc — bound
    # by total params per device ~ (L/S/V blocks) x 12 H^2 x V.
    h = d["hidden"]
    per_dev_params = 2 * 12 * h * h * 2 + 128 * h  # V x blocks + embed
    for op, e, line, _ in colls:
        assert e <= 1.5 * per_dev_params, (op, e, line[:160])
