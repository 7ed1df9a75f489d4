# Copyright The DeepSpeed-TPU authors. Licensed under Apache 2.0.
"""The decode programs of the served expert families that hold a SLIVER
of their experts, held to a fingerprint: the four benchmark cells that
run ``ops/moe.held_experts_every_row`` share ``models/served_trunk.py``
with every later family, and a change made for one of those (ISSUE 51:
a family with no shared expert, a tree with no state leaf) must leave
these four programs as they were.

Each configuration's ``tiny`` sizes (the benchmark's own, for its CPU
rehearsals) go through the engine the benchmark builds, and the decode
program's lowered text (no source locations) is hashed.
``served_decode_programs.json`` beside this file holds the hashes of the
tree BEFORE the change under review. A PR that means to change one of
these programs writes the file anew and says so:

    JAX_PLATFORMS=cpu python tests/unit/test_served_decode_programs.py --write

Run from another checkout's root (``git archive <commit>``, this file
copied in) it prints that commit's hashes: parent against change.
"""

import hashlib
import json
import os
import sys

import pytest

HERE = os.path.dirname(os.path.abspath(__file__))
FINGERPRINTS = os.path.join(HERE, "served_decode_programs.json")
CONFIGS = ("solar-open2-250b", "granite-4.0-h-small", "ax-k1",
           "kimi-linear-48b-a3b")


def decode_program_text(name, root):
    """The lowered decode program of configuration ``name`` at its
    ``tiny`` sizes, through ``root``'s benchmark family and engine."""
    bench = os.path.join(root, "benchmarks")
    for path in (root, bench):
        if path not in sys.path:
            sys.path.insert(0, path)
    import jax
    import jax.numpy as jnp
    from deepspeed_tpu.inference import InferenceEngine
    from loader import load_module
    with open(os.path.join(bench, "configs", name + ".json")) as f:
        cfg = json.load(f)
    cfg = {**cfg, **cfg["tiny"]}
    family = load_module("families", cfg["family"])
    model = family.serve_model_of(cfg)
    engine = InferenceEngine(
        model, family.init_params(model, jax.random.PRNGKey(0)),
        cfg["serve"]["inference"])
    rows, pps = engine._rows, engine.paged_spec.pages_per_seq
    i32 = lambda *shape: jnp.zeros(shape, jnp.int32)      # noqa: E731
    try:
        return jax.jit(engine._decode_paged_impl).lower(
            engine.params, engine._cache, i32(rows), i32(rows),
            i32(rows, pps), jnp.zeros((rows, 2), jnp.uint32),
            jnp.zeros((rows,), jnp.float32)).as_text()
    finally:
        engine.close()


def fingerprint(text):
    return {"lines": text.count("\n"),
            "sha256": hashlib.sha256(text.encode()).hexdigest()}


@pytest.mark.parametrize("name", CONFIGS)
def test_a_sliver_holding_cells_decode_program_is_what_it_was(name):
    root = os.path.dirname(os.path.dirname(HERE))
    with open(FINGERPRINTS) as f:
        want = json.load(f)["programs"][name]
    text = decode_program_text(name, root)
    assert "held_experts_every_row" not in text    # no source locations
    assert fingerprint(text) == want, (
        f"{name}'s decode program changed; if that is meant, see this "
        f"file's docstring")


if __name__ == "__main__":
    root = os.getcwd()
    out = {"of": "the lowered decode program (jax.jit(...).lower(...)"
                 ".as_text(), CPU) of each configuration's `tiny` sizes",
           "programs": {n: fingerprint(decode_program_text(n, root))
                        for n in CONFIGS}}
    print(json.dumps(out, indent=1))
    if "--write" in sys.argv:
        with open(FINGERPRINTS, "w") as f:
            json.dump(out, f, indent=1)
            f.write("\n")
