"""BERT fine-tune with 1-bit Adam (BASELINE.md ladder item 5): the
communication-compressed optimizer switches from dense warmup to 1-bit
compressed momentum exchange at freeze_step, cutting data-parallel traffic
~32x per phase-1 leg (recreates the reference's
DeepSpeedExamples/onebit_adam BingBertSQuAD workload shape).

    python examples/onebit_adam/train.py
"""

import argparse
import json
import os

import jax
import numpy as np

import deepspeed_tpu as ds
from deepspeed_tpu.models.bert import (BertConfig, bert_mlm_loss_fn,
                                       init_bert_params)


def main():
    parser = argparse.ArgumentParser()
    ds.add_config_arguments(parser)
    parser.add_argument("--seq", type=int, default=128)
    parser.add_argument("--steps", type=int, default=16)
    args = parser.parse_args()

    config = args.deepspeed_config or os.path.join(
        os.path.dirname(os.path.abspath(__file__)), "ds_config.json")
    with open(config) as f:
        config = json.load(f)

    cfg = BertConfig(vocab_size=8192, hidden_size=256, num_layers=4,
                     num_heads=4, intermediate_size=1024,
                     max_position_embeddings=512)
    params = init_bert_params(cfg, jax.random.PRNGKey(0))
    loss_fn = bert_mlm_loss_fn(cfg, deterministic=True)
    engine, opt, _, _ = ds.initialize(model=loss_fn,
                                      model_parameters=params,
                                      config=config)
    print(f"1-bit Adam: freeze_step={opt.freeze_step} "
          f"distributed={engine._onebit_dist}")

    rng = np.random.RandomState(0)
    bs = engine.train_batch_size()
    for step in range(args.steps):
        ids = rng.randint(0, cfg.vocab_size, (bs, args.seq))
        labels = np.full_like(ids, -100)
        m = rng.rand(*ids.shape) < 0.15
        labels[m] = ids[m]
        batch = {"input_ids": ids.astype(np.int32),
                 "labels": labels.astype(np.int32)}
        loss = engine.train_batch(iter([batch]))
        phase = "compressed" if engine._onebit_compression else "warmup"
        print(f"step {step} [{phase}]: loss {float(loss):.4f}")
    print("done")


if __name__ == "__main__":
    main()
