"""Modules of the benchmark found by the name a data file gives:
`benchmarks/<folder>/<name>.py` (`kinds/`, `readers/`)."""

import importlib.util
import os

BENCH_DIR = os.path.dirname(os.path.abspath(__file__))


def load_module(folder, name):
    path = os.path.join(BENCH_DIR, folder, f"{name}.py")
    if not os.path.isfile(path):
        raise SystemExit(f"benchmarks: no {folder}/{name}.py")
    spec = importlib.util.spec_from_file_location(
        f"bench_{folder}_{name}", path)
    module = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(module)
    return module
