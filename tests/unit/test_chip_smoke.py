"""CPU rehearsal of ``chip_smoke.py`` (first rehearsal of the
on-chip-measurement guide): the same phase functions the chip run
calls, at a tiny size with the kernels in interpret mode, so a wrong
path, argument or check is found here and not on the chip. It says
nothing about the chip: the smoke itself refuses to run without one,
which the second test holds it to."""

import os
import sys

import pytest

REPO = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
if REPO not in sys.path:
    sys.path.insert(0, REPO)

import chip_smoke  # noqa: E402
from deepspeed_tpu.models.gpt2 import GPT2Config  # noqa: E402

# GPT2_TINY of examples/megatron_gpt2/train.py
GPT2_TINY = GPT2Config(vocab_size=512, max_position_embeddings=128,
                       hidden_size=64, num_layers=4, num_heads=4,
                       embd_dropout=0.0, attn_dropout=0.0,
                       resid_dropout=0.0)


def test_train_and_serve_phases_rehearse_on_the_cpu_mesh():
    config = chip_smoke.load_train_config()
    # 8 virtual devices x micro-batch 2; a tiny model needs a larger
    # rate than 345M's to move in eight steps
    config["train_micro_batch_size_per_gpu"] = 2
    config["optimizer"]["params"]["lr"] = 1e-2
    config["scheduler"]["params"]["warmup_max_lr"] = 1e-2
    losses = chip_smoke.phase_train(
        GPT2_TINY, config, seq=64, steps=8, seed=0, min_drop=0.05,
        require_kernel=False)       # interpret mode leaves no custom call
    assert len(losses) == 8

    outputs = chip_smoke.phase_serve(
        GPT2_TINY,
        {"max_batch_size": 4, "prompt_buckets": [8, 32],
         "batch_buckets": [1, 4], "max_seq_len": 64},
        prompt_lengths=(3, 7, 20), max_new_tokens=6, seed=0)
    assert [len(o) for o in outputs] == [9, 13, 26]


def test_the_delta_rule_check_rehearses_in_the_interpreter():
    """Two chunks of a ragged tail, three heads: the check's three cases
    run and report (o, state) differences far under its limit."""
    worst = chip_smoke.check_delta_rule_scan(0, shape=(1, 70, 3, 16))
    assert len(worst) == 2 and max(worst) < 2e-5


def test_device_phase_refuses_the_cpu(capsys):
    with pytest.raises(SystemExit) as failure:
        chip_smoke.phase_device(1)
    # a string code is printed to stderr and becomes exit status 1
    assert isinstance(failure.value.code, str)
    assert "not a TPU" in failure.value.code
    assert "'cpu'" in failure.value.code
    assert '"ok"' not in capsys.readouterr().out
