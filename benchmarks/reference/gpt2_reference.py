"""GPT-2 as published (Radford et al. 2019; the pre-LN block of
`openai/gpt-2` `model.py`), forward and next-token loss in plain
`jax.numpy` and float32: no kernel, no cache, no batching tricks, matmuls
at `highest` precision (on a TPU a float32 matmul otherwise runs in
bf16). Independent of `deepspeed_tpu/models/gpt2.py`; it reads only the
parameter tree's layout (`wte`, `wpe`, `h_<i>` with `ln_1`, `attn`
{`qkvw`, `qkvb`, `ow`, `ob`}, `ln_2`, `mlp` {`fc_w`, `fc_b`, `proj_w`,
`proj_b`}, `ln_f`), which is GPT-2's own. Departures from the published
model: none (tanh GELU as in the original code; the head is tied).
"""

import math

import jax
import jax.numpy as jnp


def _ln(x, p, eps):
    mu = x.mean(-1, keepdims=True)
    var = ((x - mu) ** 2).mean(-1, keepdims=True)
    return (x - mu) / jnp.sqrt(var + eps) * p["w"] + p["b"]


def _gelu(x):
    return 0.5 * x * (1.0 + jnp.tanh(
        math.sqrt(2.0 / math.pi) * (x + 0.044715 * x ** 3)))


def logits(params, ids, layers, heads, eps=1e-5):
    """(B, S) int tokens -> (B, S, vocab) float32 logits."""
    with jax.default_matmul_precision("highest"):
        f32 = lambda t: jax.tree_util.tree_map(
            lambda a: a.astype(jnp.float32), t)
        B, S = ids.shape
        wte = f32(params["wte"])
        x = wte[ids] + f32(params["wpe"])[jnp.arange(S)][None]
        h = x.shape[-1]
        hd = h // heads
        mask = jnp.tril(jnp.ones((S, S), bool))
        for i in range(layers):
            p = f32(params[f"h_{i}"])
            a = _ln(x, p["ln_1"], eps) @ p["attn"]["qkvw"] + p["attn"]["qkvb"]
            q, k, v = (t.reshape(B, S, heads, hd).transpose(0, 2, 1, 3)
                       for t in jnp.split(a, 3, axis=-1))
            s = q @ k.transpose(0, 1, 3, 2) / math.sqrt(hd)
            s = jnp.where(mask, s, -jnp.inf)
            ctx = jax.nn.softmax(s, axis=-1) @ v
            ctx = ctx.transpose(0, 2, 1, 3).reshape(B, S, h)
            x = x + ctx @ p["attn"]["ow"] + p["attn"]["ob"]
            m = _ln(x, p["ln_2"], eps)
            m = _gelu(m @ p["mlp"]["fc_w"] + p["mlp"]["fc_b"])
            x = x + m @ p["mlp"]["proj_w"] + p["mlp"]["proj_b"]
        x = _ln(x, f32(params["ln_f"]), eps)
        return x @ wte.T


def next_token_loss(params, ids, layers, heads):
    """Mean next-token cross entropy of (B, S + 1) tokens."""
    lg = logits(params, ids[:, :-1], layers, heads)
    logp = jax.nn.log_softmax(lg, axis=-1)
    picked = jnp.take_along_axis(logp, ids[:, 1:, None], axis=-1)[..., 0]
    return -picked.mean()
