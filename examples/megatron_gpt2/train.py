"""Megatron-GPT2 workload (BASELINE.md ladder items 3-4): GPT-2 345M with
ZeRO-2 data parallelism, or GPT-2 with 3D (pipe x data x model) parallelism
via the compiled SPMD pipeline. Recreates the reference's
tests/model/Megatron_GPT2 harness workloads as native examples.

    # 345M + ZeRO-2 (config ds_config_zero2.json)
    python examples/megatron_gpt2/train.py --mode zero2

    # 3D-parallel pipeline (config ds_config_3d.json; needs >=8 devices —
    # on CPU: XLA_FLAGS=--xla_force_host_platform_device_count=8)
    python examples/megatron_gpt2/train.py --mode 3d
"""

import argparse
import json
import os

import jax
import numpy as np

import deepspeed_tpu as ds
from deepspeed_tpu.models.gpt2 import (GPT2Config, count_params,
                                       gpt2_loss_fn, gpt2_pipeline_spec,
                                       gpt2_sp_loss_fn, init_gpt2_params)

GPT2_345M = dict(vocab_size=50304, max_position_embeddings=1024,
                 hidden_size=1024, num_layers=24, num_heads=16)
# GPT-2 XL (1.5B): the BASELINE ladder's 3D-parallel / ZeRO-Offload
# scale point (reference megatron tutorial's 1.5B config)
GPT2_XL = dict(vocab_size=50304, max_position_embeddings=1024,
               hidden_size=1600, num_layers=48, num_heads=25)
# 2.1B: the single-chip ZeRO-Offload flagship (reference ZeRO-Offload
# claim: 13B on one 32 GB V100, docs/_posts/2020-09-09-ZeRO-Offload.md
# :10). On a 16 GB v5e the offload recipe — bf16 params in HBM, grads
# as a direct compute-dtype output (no accumulator), fp32 master +
# Adam moments in host RAM, scan_layers + remat — fits 2.1B under the
# CONSERVATIVE compiler memory proof in tests/unit/test_offload_memory
# .py (no buffer-alias credit; with the alias XLA actually performs,
# ~2.5B fits). Heads of 128 (2048/16) keep flash on tuned block shapes.
GPT2_2B = dict(vocab_size=50304, max_position_embeddings=1024,
               hidden_size=2048, num_layers=40, num_heads=16)
GPT2_TINY = dict(vocab_size=512, max_position_embeddings=128,
                 hidden_size=64, num_layers=4, num_heads=4)


def main():
    parser = argparse.ArgumentParser()
    ds.add_config_arguments(parser)
    parser.add_argument("--mode",
                        choices=["zero2", "3d", "sp", "offload", "moe"],
                        default="zero2")
    parser.add_argument("--tiny", action="store_true",
                        help="Tiny model for smoke runs")
    parser.add_argument("--size", choices=["tiny", "345m", "xl", "2b"],
                        default=None,
                        help="model size (xl = GPT-2 1.5B; 2b = the "
                             "single-chip offload flagship; --tiny wins)")
    parser.add_argument("--seq", type=int, default=0)
    parser.add_argument("--steps", type=int, default=10)
    parser.add_argument("--save_dir", type=str, default=None,
                        help="save a checkpoint every --save_interval steps")
    parser.add_argument("--save_interval", type=int, default=0)
    parser.add_argument("--load_dir", type=str, default=None,
                        help="resume from the latest checkpoint here")
    parser.add_argument("--generate", type=int, default=0, metavar="N",
                        help="after training, sample N tokens from the "
                             "trained weights (dense zero2/offload modes)")
    args = parser.parse_args()

    here = os.path.dirname(os.path.abspath(__file__))
    config = args.deepspeed_config or os.path.join(
        here, f"ds_config_{args.mode}.json")
    with open(config) as f:
        config = json.load(f)

    sizes = {"tiny": GPT2_TINY, "345m": GPT2_345M, "xl": GPT2_XL,
             "2b": GPT2_2B}
    size = GPT2_TINY if args.tiny else sizes[args.size or "345m"]
    # billion-scale single-chip offload needs the memory recipe:
    # stacked-layer scan (one compiled block) + rematerialized blocks
    big_offload = args.mode == "offload" and \
        (args.size or "") in ("xl", "2b")
    cfg = GPT2Config(embd_dropout=0.0, attn_dropout=0.0, resid_dropout=0.0,
                     scan_layers=big_offload, **size)
    seq = args.seq or min(cfg.max_position_embeddings, 1024)

    rng = np.random.RandomState(0)
    if big_offload:
        # one micro per boundary: the engine then allocates no grad
        # accumulator at all — grads leave the step as a compute-dtype
        # output (test_offload_memory.py). Pinned BEFORE reading the
        # batch geometry below.
        config = dict(config, gradient_accumulation_steps=1,
                      train_micro_batch_size_per_gpu=1)
    micro = config["train_micro_batch_size_per_gpu"]
    ga = config.get("gradient_accumulation_steps", 1)

    if args.mode == "moe":
        # sparse-FFN scaling: every other block carries a MoE expert bank;
        # experts shard over the 'expert' mesh axis (docs/moe.md)
        from deepspeed_tpu.models.gpt2 import (gpt2_moe_loss_fn,
                                               init_gpt2_moe_params)
        from deepspeed_tpu.ops.moe import MoEConfig
        from deepspeed_tpu.parallel.mesh import build_mesh
        moe_cfg = MoEConfig(hidden_size=cfg.hidden_size,
                            intermediate_size=cfg.inter,
                            num_experts=8, top_k=2)
        params = init_gpt2_moe_params(cfg, moe_cfg, jax.random.PRNGKey(0))
        print(f"params: {count_params(params)/1e6:.0f}M (MoE)")
        mesh = build_mesh(config["mesh"]["axes"])  # == the engine's mesh
        loss_fn = gpt2_moe_loss_fn(cfg, moe_cfg, mesh=mesh,
                                   deterministic=True)
        engine, *_ = ds.initialize(model=loss_fn, model_parameters=params,
                                   config=config)
        bs = engine.train_batch_size() // ga

        def micro_batches():
            while True:
                yield {"input_ids": rng.randint(
                    0, cfg.vocab_size, (bs, seq + 1)).astype(np.int32)}
        it = micro_batches()
    elif args.mode == "sp":
        # sequence/context parallelism: ring attention over the 'seq'
        # mesh axis — each device holds a (B, S/P, H) activation shard
        from deepspeed_tpu.parallel.mesh import build_mesh
        mesh = build_mesh(config["mesh"]["axes"])
        params = init_gpt2_params(cfg, jax.random.PRNGKey(0))
        print(f"params: {count_params(params)/1e6:.0f}M")
        loss_fn = gpt2_sp_loss_fn(cfg, mesh, deterministic=True)
        engine, *_ = ds.initialize(model=loss_fn, model_parameters=params,
                                   config=config)
        bs = micro * config["mesh"]["axes"].get("data", 1)
        seq_par = config["mesh"]["axes"]["seq"]
        assert seq % seq_par == 0, (seq, seq_par)

        def micro_batches():
            while True:
                yield {"input_ids": rng.randint(
                    0, cfg.vocab_size, (bs, seq + 1)).astype(np.int32)}
        it = micro_batches()
    elif args.mode in ("zero2", "offload"):
        # offload: same data path; the config moves the fp32 master state
        # + Adam to host memory (reference ZeRO-Offload: 13B on one GPU —
        # here GPT-2 XL 1.5B trains on one v5e chip: bf16 params + grads
        # in HBM, fp32 master + moments in host RAM, AVX2 host Adam
        # overlapped under the next window's compute)
        params = init_gpt2_params(cfg, jax.random.PRNGKey(0))
        print(f"params: {count_params(params)/1e6:.0f}M")
        loss_fn = gpt2_loss_fn(cfg, deterministic=True, remat=big_offload)
        engine, *_ = ds.initialize(model=loss_fn, model_parameters=params,
                                   config=config)
        bs = engine.train_batch_size() // ga

        def micro_batches():
            while True:
                yield {"input_ids": rng.randint(
                    0, cfg.vocab_size, (bs, seq + 1)).astype(np.int32)}
        it = micro_batches()
    else:
        stages = (config["mesh"]["axes"]["pipe"]
                  * config.get("pipeline", {}).get("virtual_stages", 1))
        spec = gpt2_pipeline_spec(cfg, num_stages=stages)
        engine, *_ = ds.initialize(model=spec, config=config)
        data_par = config["mesh"]["axes"].get("data", 1)
        global_mb = micro * data_par

        def micro_batches():
            while True:
                yield {"input_ids": rng.randint(
                    0, cfg.vocab_size,
                    (global_mb, seq + 1)).astype(np.int32)}
        it = micro_batches()

    start_step = 0
    if args.load_dir:
        path, _ = engine.load_checkpoint(args.load_dir)
        if path is not None:
            start_step = engine.global_steps
            print(f"resumed from {path} at step {start_step}")
            # deterministic data stream: fast-forward past consumed micros
            per_step = getattr(engine, "micro_batches",
                               engine.gradient_accumulation_steps)
            for _ in range(start_step * per_step):
                next(it)

    for step in range(start_step, args.steps):
        loss = engine.train_batch(it)
        print(f"step {step}: lm loss {float(loss):.4f}")
        if args.save_dir and args.save_interval and \
                (step + 1) % args.save_interval == 0:
            engine.save_checkpoint(args.save_dir)

    if args.generate:
        if args.mode not in ("zero2", "offload"):
            print(f"--generate: not supported for --mode {args.mode} "
                  "(dense zero2/offload only); skipping")
        else:
            # sample from the just-trained weights (KV-cache decode);
            # drain any in-flight offloaded host-Adam update first
            engine.synchronize()
            from deepspeed_tpu.models.gpt2 import gpt2_generate
            prompt = rng.randint(0, cfg.vocab_size, (1, 4)).astype(np.int32)
            out = gpt2_generate(engine.module_params, cfg,
                                jax.numpy.asarray(prompt), args.generate,
                                rng=jax.random.PRNGKey(0), temperature=0.9,
                                top_k=40)
            print("sampled:", np.asarray(out)[0].tolist())
    print("done")


if __name__ == "__main__":
    main()
