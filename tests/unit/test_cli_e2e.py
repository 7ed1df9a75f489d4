"""End-to-end `bin/dstpu` CLI tests (VERDICT r4 #5).

The reference's model tests drive real training through the deepspeed
CLI (tests/model/Megatron_GPT2/run_func_test.py:20-36). These do the
same for `bin/dstpu`: a real subprocess of the installed entry point —
argv parsing, launcher selection, env propagation (DSTPU_* identity
vars, `.deepspeed_env` exports, DSTPU_WORLD_INFO), and exit-code
plumbing — none of which the in-process `runpy` example smokes
(test_examples.py) exercise.

Children run on the CPU backend: JAX_PLATFORMS=cpu in their environment,
which JAX honours itself and the launcher exports (``JAX_`` prefix).
"""

import base64
import json
import os
import subprocess
import sys
import textwrap

import pytest

pytestmark = pytest.mark.slow          # real subprocesses, fresh jax init

REPO = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
DSTPU = os.path.join(REPO, "bin", "dstpu")


def _env(extra=None):
    env = dict(os.environ)
    env["PYTHONPATH"] = REPO + os.pathsep + env.get("PYTHONPATH", "")
    env["JAX_PLATFORMS"] = "cpu"
    env["XLA_FLAGS"] = "--xla_force_host_platform_device_count=1"
    env.update(extra or {})
    return env


def _run(argv, cwd=None, extra_env=None, timeout=420):
    return subprocess.run(
        [sys.executable, DSTPU] + argv, cwd=cwd or REPO, env=_env(extra_env),
        capture_output=True, text=True, timeout=timeout)


def test_dstpu_local_launcher_trains():
    """`dstpu --launcher local <script>` must run real training end to
    end: the tiny megatron example takes steps and reports losses."""
    r = _run(["--launcher", "local",
              os.path.join(REPO, "examples", "megatron_gpt2", "train.py"),
              "--mode", "zero2", "--tiny", "--steps", "2"])
    assert r.returncode == 0, (r.stdout[-2000:], r.stderr[-2000:])
    assert "step 0: lm loss" in r.stdout, r.stdout[-2000:]
    assert "step 1: lm loss" in r.stdout, r.stdout[-2000:]


def test_dstpu_propagates_exit_code(tmp_path):
    """A failing user script's exit code must surface as dstpu's own
    (reference runner.py:356)."""
    script = tmp_path / "fail.py"
    script.write_text("import sys; sys.exit(3)\n")
    r = _run(["--launcher", "local", str(script)])
    assert r.returncode == 3, (r.returncode, r.stderr[-500:])


def test_dstpu_hostfile_env_propagation(tmp_path):
    """A localhost hostfile drives the ssh-runner command construction
    (env export line, DSTPU_* identity vars, world info, .deepspeed_env
    pickup) executed via the /bin/sh local shortcut — and the launched
    script trains a real step through deepspeed_tpu.initialize."""
    (tmp_path / "hostfile").write_text("localhost slots=1\n")
    (tmp_path / ".deepspeed_env").write_text("DSTPU_TEST_ENVVAR=42\n")
    script = tmp_path / "user.py"
    script.write_text(textwrap.dedent("""
        import base64, json, os
        assert os.environ["DSTPU_TEST_ENVVAR"] == "42"      # .deepspeed_env
        assert os.environ["DSTPU_NUM_PROCESSES"] == "1"
        assert os.environ["DSTPU_PROCESS_ID"] == "0"
        assert "DSTPU_COORDINATOR" in os.environ
        wi = json.loads(base64.urlsafe_b64decode(
            os.environ["DSTPU_WORLD_INFO"]))
        assert wi == {"localhost": [0]}, wi     # host -> slot indices
        import jax
        import jax.numpy as jnp
        import numpy as np
        import deepspeed_tpu as ds
        ds.init_distributed()          # 1 process: documented no-op
        def loss_fn(params, batch, rngs=None):
            p = jnp.tanh(batch["x"] @ params["w"])
            return jnp.mean((p - batch["y"]) ** 2)
        params = {"w": jax.random.normal(jax.random.PRNGKey(0), (8, 4))}
        engine, *_ = ds.initialize(
            model=loss_fn, model_parameters=params,
            config={"train_micro_batch_size_per_gpu": 4,
                    "optimizer": {"type": "Adam", "params": {"lr": 1e-2}}})
        rs = np.random.RandomState(0)
        b = {"x": rs.randn(4, 8).astype(np.float32),
             "y": rs.randn(4, 4).astype(np.float32)}
        loss = engine.train_batch(iter([b]))
        print("CLI_E2E_TRAIN_OK", float(loss))
    """))
    r = _run(["--hostfile", str(tmp_path / "hostfile"), str(script)],
             cwd=str(tmp_path))
    assert r.returncode == 0, (r.stdout[-2000:], r.stderr[-2000:])
    assert "CLI_E2E_TRAIN_OK" in r.stdout, r.stdout[-2000:]
