# Copyright The DeepSpeed-TPU authors. Licensed under Apache 2.0.
"""The package's layers import one way (ISSUE 46): ``ops/`` below
``models/`` below ``inference/``, and a model family takes nothing
private from another family. Read from the sources with ``ast``, so a
function-level import counts like one at the top of a file."""

import ast
import os

import pytest

PACKAGE = os.path.join(os.path.dirname(__file__), "..", "..",
                       "deepspeed_tpu")
# what the families share is not a family
SHARED_MODEL_MODULES = {"__init__", "served_trunk"}
# the reference's module-surgery API (PARITY.md), off every hot path:
# (file, module, name), the ONE import that points up
UPWARD = {("ops/sparse_attention/sparse_self_attention.py",
           "deepspeed_tpu.models.bert", "bert_encoder")}


def _imports(path, module):
    """(module imported from, name or None) of every import in a file,
    relative ones resolved against ``module``'s package."""
    with open(path) as f:
        tree = ast.parse(f.read())
    for node in ast.walk(tree):
        if isinstance(node, ast.Import):
            for alias in node.names:
                yield alias.name, None
        elif isinstance(node, ast.ImportFrom):
            base = node.module or ""
            if node.level:
                package = module.split(".")[:-node.level]
                base = ".".join(package + ([base] if base else []))
            for alias in node.names:
                yield base, alias.name


def _modules(subdir):
    """(path relative to the package, dotted name, file) under a
    directory of the package."""
    root = os.path.join(PACKAGE, subdir)
    for folder, _, files in os.walk(root):
        for name in sorted(files):
            if name.endswith(".py"):
                path = os.path.join(folder, name)
                rel = os.path.relpath(path, PACKAGE).replace(os.sep, "/")
                yield rel, "deepspeed_tpu." + rel[:-3].replace("/", "."), path


def _reaches(source, name, target):
    """``from source import name`` (or ``import source``) names
    something under the package ``target``."""
    full = source if name is None else f"{source}.{name}"
    return any(m == target or m.startswith(target + ".")
               for m in (source, full))


def _pointing_up(subdir, target):
    return sorted(
        (rel, source, name)
        for rel, module, path in _modules(subdir)
        for source, name in _imports(path, module)
        if _reaches(source, name, target)
        and (rel, source, name) not in UPWARD)


def _private_between_families():
    families = {module: rel for rel, module, _ in _modules("models")
                if module.rsplit(".", 1)[1] not in SHARED_MODEL_MODULES}
    return sorted(
        (rel, source, name)
        for rel, module, path in _modules("models") if module in families
        for source, name in _imports(path, module)
        if source in families and source != module
        and name is not None and name.startswith("_"))


RULES = {
    "ops_import_no_model": lambda: _pointing_up(
        "ops", "deepspeed_tpu.models"),
    "ops_import_no_serving_code": lambda: _pointing_up(
        "ops", "deepspeed_tpu.inference"),
    "models_import_no_serving_code": lambda: _pointing_up(
        "models", "deepspeed_tpu.inference"),
    "a_family_takes_nothing_private_from_another":
        _private_between_families,
}


@pytest.mark.parametrize("rule", sorted(RULES))
def test_the_layers_import_one_way(rule):
    assert RULES[rule]() == []


def test_the_one_upward_import_is_still_there():
    """The exception is a fact about the tree, not a standing licence:
    when the surgery helper goes, so does its line here."""
    rel, source, name = next(iter(UPWARD))
    path = os.path.join(PACKAGE, rel)
    module = "deepspeed_tpu." + rel[:-3].replace("/", ".")
    assert (source, name) in set(_imports(path, module))
