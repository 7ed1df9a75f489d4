"""Test configuration: run everything on a virtual 8-device CPU mesh.

Mirrors the reference's single-node multi-process fixture strategy
(tests/unit/common.py:14 @distributed_test) but improves on it: instead of
forking NCCL processes we use XLA's host-platform device partitioning, so all
"distributed" logic (sharding, collectives, topology) runs in-process on CPU.

The tests never touch an accelerator: JAX_PLATFORMS=cpu is honoured by
JAX itself, and set here (before jax is imported) so that the
subprocess tests' children inherit it too.
"""

import os

os.environ["JAX_PLATFORMS"] = "cpu"
os.environ["XLA_FLAGS"] = (os.environ.get("XLA_FLAGS", "") +
                           " --xla_force_host_platform_device_count=8")
os.environ.setdefault("JAX_ENABLE_X64", "0")
# hermetic comm-autotune planning: a measured wire_model.json — whether
# in the user cache OR exported via DSTPU_WIRE_MODEL in the shell — must
# not skew the golden decision tables, so pin unconditionally (tests
# that WANT an artifact monkeypatch this to a tmp file)
os.environ["DSTPU_WIRE_MODEL"] = "/nonexistent/dstpu_wire_model.json"

import jax  # noqa: E402

# (also in code: a pytest plugin may have imported jax before the
# environment above was set)
jax.config.update("jax_platforms", "cpu")
jax.config.update("jax_threefry_partitionable", True)

assert jax.device_count() == 8, (
    f"tests expect an 8-device CPU mesh, got {jax.device_count()} "
    f"{jax.default_backend()} devices")
