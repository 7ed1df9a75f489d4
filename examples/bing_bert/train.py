"""BERT pretraining workload — "bing_bert" (BASELINE.md ladder item 2;
recreates the reference's DeepSpeedExamples/bing_bert MLM pretraining with
the fused transformer-layer stack).

Synthetic MLM data by default (shape-realistic); swap in a real corpus by
feeding {"input_ids", "attention_mask", "mlm_labels"} batches.

    python examples/bing_bert/train.py --model base|large \
        [--deepspeed_config ds_config.json]
"""

import argparse
import json
import os

import jax
import numpy as np

import deepspeed_tpu as ds
from deepspeed_tpu.models.bert import (BERT_BASE, BERT_LARGE,
                                       bert_mlm_loss_fn, init_bert_params)


def synthetic_mlm_batches(cfg, n, batch_size, seq, mask_prob=0.15, seed=0):
    rng = np.random.RandomState(seed)
    for _ in range(n):
        ids = rng.randint(0, cfg.vocab_size, (batch_size, seq))
        labels = np.full((batch_size, seq), -100, np.int32)
        mask = rng.rand(batch_size, seq) < mask_prob
        labels[mask] = ids[mask]
        ids = ids.copy()
        ids[mask] = 103  # [MASK]
        yield {"input_ids": ids.astype(np.int32),
               "attention_mask": np.ones((batch_size, seq), np.int32),
               "labels": labels}


def main():
    parser = argparse.ArgumentParser()
    ds.add_config_arguments(parser)
    parser.add_argument("--model", choices=["tiny", "base", "large"],
                        default="base")
    parser.add_argument("--mode", choices=["dense", "sp", "sparse"],
                        default="dense",
                        help="sp: sequence-parallel over the 'seq' mesh "
                             "axis; sparse: block-sparse attention from "
                             "the config's sparse_attention section")
    parser.add_argument("--seq", type=int, default=128)
    parser.add_argument("--steps", type=int, default=20)
    args = parser.parse_args()

    if args.model == "large":
        cfg = BERT_LARGE
    elif args.model == "tiny":  # CPU smoke runs
        cfg = BERT_BASE._replace(vocab_size=2048, hidden_size=128,
                                 num_layers=2, num_heads=2,
                                 intermediate_size=256,
                                 max_position_embeddings=128)
    else:
        cfg = BERT_BASE
    config = args.deepspeed_config or os.path.join(
        os.path.dirname(os.path.abspath(__file__)), "ds_config.json")
    with open(config) as f:
        config = json.load(f)

    params = init_bert_params(cfg, jax.random.PRNGKey(0))
    if args.mode == "sp":
        from deepspeed_tpu.models.bert import bert_mlm_sp_loss_fn
        from deepspeed_tpu.parallel.mesh import build_mesh
        mesh = build_mesh(config["mesh"]["axes"])
        loss_fn = bert_mlm_sp_loss_fn(cfg, mesh)
    elif args.mode == "sparse":
        # block-sparse attention driven purely by the JSON config (the
        # reference's bing_bert + sparse_attention configuration; its
        # BERT sparse runs used `fixed` sparsity)
        from deepspeed_tpu.ops.sparse_attention import (
            sparsity_config_from_dict)
        from deepspeed_tpu.runtime.config import get_sparse_attention
        # parse first: the JSON schema's defaults (e.g. block=16) and
        # per-mode key filtering live in get_sparse_attention
        sa = get_sparse_attention(config)
        if sa is None:
            raise SystemExit("--mode sparse requires a sparse_attention "
                             "section in the deepspeed config")
        sc = sparsity_config_from_dict(sa, num_heads=cfg.num_heads)
        if args.seq % sc.block:
            raise SystemExit(f"--seq {args.seq} must be a multiple of the "
                             f"sparsity block ({sc.block}); see "
                             "SparseAttentionUtils.pad_to_block_size")
        loss_fn = bert_mlm_loss_fn(cfg, sparsity_config=sc)
    else:
        loss_fn = bert_mlm_loss_fn(cfg)
    engine, _, _, _ = ds.initialize(model=loss_fn, model_parameters=params,
                                    config=config)
    bs = engine.train_batch_size()
    ga = engine.gradient_accumulation_steps
    micro = bs // ga if ga else bs
    data = synthetic_mlm_batches(cfg, args.steps * ga, micro, args.seq)
    for step in range(args.steps):
        loss = engine.train_batch(data)
        if step % 5 == 0 or step == args.steps - 1:
            print(f"step {step}: mlm loss {float(loss):.4f}")
    print("done")


if __name__ == "__main__":
    main()
