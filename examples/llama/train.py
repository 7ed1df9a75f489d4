"""Llama-family workload (beyond the reference ladder): RoPE + RMSNorm +
SwiGLU decoder with grouped-query attention served natively by the flash
kernels, trained through the engine with ZeRO-2 or tensor parallelism.

    # ZeRO-2 data parallel (config ds_config_zero2.json)
    python examples/llama/train.py --mode zero2

    # data x model tensor parallel (config ds_config_tp.json)
    python examples/llama/train.py --mode tp

    # stacked-layer scan trunk (compiles the block once)
    python examples/llama/train.py --mode zero2 --scan-layers

    # sample from the trained weights (kv_heads-sized KV cache)
    python examples/llama/train.py --mode zero2 --generate 32
"""

import argparse
import json
import os

import jax
import numpy as np

import deepspeed_tpu as ds
from deepspeed_tpu.models.gpt2 import count_params
from deepspeed_tpu.models.llama import (LlamaConfig, init_llama_params,
                                        llama_generate, llama_loss_fn,
                                        llama_param_specs)

# ~1B-class config (llama-style ratios, GQA 4:1)
LLAMA_1B = dict(vocab_size=32128, hidden_size=2048, num_layers=16,
                num_heads=32, num_kv_heads=8,
                max_position_embeddings=2048)
LLAMA_TINY = dict(vocab_size=512, hidden_size=64, num_layers=4,
                  num_heads=4, num_kv_heads=2,
                  max_position_embeddings=128)


def main():
    parser = argparse.ArgumentParser()
    ds.add_config_arguments(parser)
    parser.add_argument("--mode", choices=["zero2", "tp"], default="zero2")
    parser.add_argument("--tiny", action="store_true")
    parser.add_argument("--scan-layers", action="store_true",
                        help="stacked layers + lax.scan trunk "
                             "(~num_layers x faster first compile)")
    parser.add_argument("--seq", type=int, default=0)
    parser.add_argument("--steps", type=int, default=10)
    parser.add_argument("--generate", type=int, default=0, metavar="N")
    args = parser.parse_args()

    here = os.path.dirname(os.path.abspath(__file__))
    config = args.deepspeed_config or os.path.join(
        here, f"ds_config_{args.mode}.json")
    with open(config) as f:
        config = json.load(f)

    size = LLAMA_TINY if args.tiny else LLAMA_1B
    cfg = LlamaConfig(scan_layers=args.scan_layers, **size)
    seq = args.seq or min(cfg.max_position_embeddings, 1024)

    params = init_llama_params(cfg, jax.random.PRNGKey(0))
    print(f"params: {count_params(params)/1e6:.0f}M "
          f"(GQA {cfg.num_heads}q:{cfg.kv_heads}kv)")
    loss_fn = llama_loss_fn(cfg)
    specs = llama_param_specs(cfg) if args.mode == "tp" else None
    engine, *_ = ds.initialize(model=loss_fn, model_parameters=params,
                               param_specs=specs, config=config)

    rng = np.random.RandomState(0)
    ga = config.get("gradient_accumulation_steps", 1)
    bs = engine.train_batch_size() // ga

    def micro_batches():
        while True:
            yield {"input_ids": rng.randint(
                0, cfg.vocab_size, (bs, seq + 1)).astype(np.int32)}

    it = micro_batches()
    for step in range(args.steps):
        loss = engine.train_batch(it)
        if step == 0 or (step + 1) % 5 == 0:
            print(f"step {step + 1}: loss {float(np.asarray(loss)):.4f}")
    print(f"final loss: {float(np.asarray(loss)):.4f}")

    if args.generate > 0:
        prompt = rng.randint(0, cfg.vocab_size, (1, 8)).astype(np.int32)
        out = llama_generate(engine.module_params, cfg,
                             jax.numpy.asarray(prompt), args.generate,
                             rng=jax.random.PRNGKey(7), temperature=0.8,
                             top_k=40)
        print("generated:", np.asarray(out)[0, 8:].tolist())


if __name__ == "__main__":
    main()
