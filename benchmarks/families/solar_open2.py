"""Family `solar_open2`, the serving half: what `kinds/serve_backlog.py`
needs to serve a configuration of this architecture (gated delta-rule
linear attention among gated softmax layers, routed SwiGLU experts with
a shared expert) and to decide `correct`: the program's model at the
configuration file's sizes, its initialiser (weights held in bfloat16),
the plain float32 reference behind the served-token check
(`reference/solar_open2_reference.py`), what a token and a SLOT hold in
the engine's pools, the parameter count and the sizes the counting
readers need (`core/hybrid_counts.py`).
"""

import numpy as np

from deepspeed_tpu.inference.kv_cache import (state_pool_bytes,
                                              state_pool_spec_for)
from deepspeed_tpu.models import solar_open2 as so

from reference import solar_open2_reference


def serve_model_of(config):
    """The program's config from the published keys, at the chip's
    share: `n_routed_experts` counts the experts HELD, the router keeps
    `router_outputs`; `vocab_size` is the slice's rows."""
    linear = config["linear_attn_config"]
    return so.SolarOpen2Config(
        vocab_size=config["vocab_size"],
        hidden_size=config["hidden_size"],
        num_layers=config["num_hidden_layers"],
        num_heads=config["num_attention_heads"],
        num_kv_heads=config["num_key_value_heads"],
        head_dim=config["head_dim"],
        gqa_layers=tuple(config["gqa_layers"]),
        kda_num_heads=linear["num_heads"],
        kda_head_dim=linear["head_dim"],
        kda_conv_width=linear["short_conv_kernel_size"],
        kda_gate_rank=config["kda_gate_rank"],
        moe_intermediate_size=config["moe_intermediate_size"],
        num_experts=config["router_outputs"],
        experts_per_token=config["num_experts_per_tok"],
        routed_scaling_factor=float(config["routed_scaling_factor"]),
        rms_norm_eps=config["rms_norm_eps"],
        max_position_embeddings=config["max_position_embeddings"],
        initializer_range=config.get("initializer_range", 0.02),
        experts_held=tuple(config["experts_held"]),
        vocab_held=tuple(config["vocab_held"]))


init_params = so.init_solar_open2_params


def reference_config(model):
    """The plain reference's own dict of the same sizes."""
    return {"num_layers": model.num_layers,
            "gqa_layers": tuple(model.gqa_layers),
            "num_heads": model.num_heads,
            "num_kv_heads": model.num_kv_heads,
            "head_dim": model.head_dim,
            "kda_num_heads": model.kda_num_heads,
            "kda_head_dim": model.kda_head_dim,
            "experts_per_token": model.experts_per_token,
            "routed_scaling_factor": model.routed_scaling_factor,
            "rms_norm_eps": model.rms_norm_eps,
            "experts_held": model.held}


def reference_logits(model, **lower):
    """`fn(params, ids)`: (1, S) tokens -> (1, S, rows) float32 logits
    of the plain forward, to be jitted by the caller. `lower`
    (`state_dtype`, `round_to`, `products`) is the reference at a lower
    precision, for the cell's controls (`tools/serve_controls.py`)."""
    cfg = reference_config(model)
    return lambda params, ids: solar_open2_reference.logits(
        params, ids, cfg, **lower)


def reference_state(model, **lower):
    """`fn(params, ids, lengths)`: (B, S) tokens and their (B,) true
    lengths -> (B, delta-rule layers, heads, dk, dv) float32, what the
    plain forward's recurrence holds after each row's true length: a
    slot's row of the engine's state pool is held against it
    (`kinds/serve_backlog_state.py`)."""
    cfg = reference_config(model)
    return lambda params, ids, lengths: solar_open2_reference.final_states(
        params, ids, lengths, cfg, **lower)


def cache_bytes(model, engine):
    """Bytes that live in the pools the engine built: `per_token` for
    every cached position (keys and values of the softmax layers, in the
    page pool's own type) and `per_slot` for what a slot holds whatever
    its length (the delta-rule layers' float32 state and the
    convolutions' tail)."""
    spec, state = engine.paged_spec, engine.state_spec
    width = np.dtype(spec.dtype).itemsize
    return {"per_token": 2 * spec.num_layers * spec.kv_heads
            * spec.head_dim * width,
            "per_slot": state_pool_bytes(state) // state.rows}


def param_count(model):
    kda, soft, around, expert, tables = so.solar_open2_param_count(model)
    return (len(model.recurrent_layers) * kda
            + len(model.softmax_layers) * soft
            + model.num_layers * (around + model.held[1] * expert)
            + tables)


def describe_served(model):
    """`facts["model"]`: the sizes the counting readers need."""
    row = state_pool_spec_for(model, 1)    # one slot's row, as built
    tail = int(np.prod(row.tail_shape[1:])) * np.dtype(
        row.tail_dtype).itemsize
    return {"layers": model.num_layers, "hidden": model.hidden_size,
            "heads": model.num_heads,
            "recurrent_layers": len(model.recurrent_layers),
            "softmax_layers": len(model.softmax_layers),
            "kda_heads": model.kda_num_heads,
            "kda_key_dim": model.kda_head_dim,
            "kda_value_dim": model.kda_head_dim,
            "kda_tail_bytes_per_layer": tail,
            "state_bytes_per_slot": state_pool_bytes(row),
            "kv_bytes_per_token": 2 * len(model.softmax_layers)
            * model.num_kv_heads * model.head_dim * 2,
            "experts_held": model.held[1], "ffn": model.moe_intermediate_size,
            "experts_per_token": model.experts_per_token,
            "weight_bytes": 2 * param_count(model)}
