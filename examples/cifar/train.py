"""CIFAR-style tiny-CNN smoke workload (BASELINE.md workload ladder item 1;
recreates the absent DeepSpeedExamples/cifar tutorial for this framework).

Runs on anything — CPU mesh, one TPU chip, or a pod — in seconds. Uses a
synthetic CIFAR-shaped dataset so no download is needed; swap in real data
by passing any iterable of {"x": (B,32,32,3), "y": (B,)} batches.

    python examples/cifar/train.py [--deepspeed_config ds_config.json]
"""

import argparse
import json
import os

import jax
import jax.numpy as jnp
import numpy as np

import deepspeed_tpu as ds


def init_params(key):
    k = jax.random.split(key, 4)
    glorot = jax.nn.initializers.glorot_normal()
    return {
        "conv1": {"w": glorot(k[0], (3, 3, 3, 32)),
                  "b": jnp.zeros((32,))},
        "conv2": {"w": glorot(k[1], (3, 3, 32, 64)),
                  "b": jnp.zeros((64,))},
        "fc1": {"w": glorot(k[2], (64 * 8 * 8, 256)),
                "b": jnp.zeros((256,))},
        "fc2": {"w": glorot(k[3], (256, 10)), "b": jnp.zeros((10,))},
    }


def _conv_block(p, x):
    x = jax.lax.conv_general_dilated(
        x, p["w"], (1, 1), "SAME",
        dimension_numbers=("NHWC", "HWIO", "NHWC")) + p["b"]
    x = jax.nn.relu(x)
    return jax.lax.reduce_window(
        x, -jnp.inf, jax.lax.max, (1, 2, 2, 1), (1, 2, 2, 1), "VALID")


def loss_fn(params, batch, rng):
    x = batch["x"].astype(jnp.float32)
    x = _conv_block(params["conv1"], x)
    x = _conv_block(params["conv2"], x)
    x = x.reshape(x.shape[0], -1)
    x = jax.nn.relu(x @ params["fc1"]["w"] + params["fc1"]["b"])
    logits = x @ params["fc2"]["w"] + params["fc2"]["b"]
    logp = jax.nn.log_softmax(logits)
    return -jnp.mean(jnp.take_along_axis(logp, batch["y"][:, None], 1))


def synthetic_batches(n, batch_size, seed=0):
    rng = np.random.RandomState(seed)
    protos = rng.randn(10, 32, 32, 3).astype(np.float32)
    for _ in range(n):
        y = rng.randint(0, 10, batch_size)
        x = protos[y] + 0.3 * rng.randn(batch_size, 32, 32, 3)
        yield {"x": x.astype(np.float32), "y": y.astype(np.int32)}


def main():
    parser = argparse.ArgumentParser()
    ds.add_config_arguments(parser)
    parser.add_argument("--steps", type=int, default=30)
    args = parser.parse_args()

    config = args.deepspeed_config or os.path.join(
        os.path.dirname(os.path.abspath(__file__)), "ds_config.json")
    with open(config) as f:
        config = json.load(f)

    params = init_params(jax.random.PRNGKey(0))
    engine, _, _, _ = ds.initialize(model=loss_fn, model_parameters=params,
                                    config=config)
    bs = engine.train_batch_size()
    for step, batch in enumerate(synthetic_batches(args.steps, bs)):
        loss = engine.train_batch(iter([batch]))
        if step % 10 == 0 or step == args.steps - 1:
            print(f"step {step}: loss {float(loss):.4f}")
    print("done")


if __name__ == "__main__":
    main()
