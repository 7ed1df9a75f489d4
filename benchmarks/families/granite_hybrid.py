"""Family `granite_hybrid`, the serving half: what `kinds/serve_backlog.py`
needs to serve a configuration of this architecture (Mamba-2 state-space
mixers with one NoPE softmax layer in ten, routed SwiGLU experts with a
shared expert, a muP-scaled trunk over a tied table) and to decide
`correct`: the program's model at the configuration file's sizes, its
initialiser (weights held in bfloat16), the plain float32 reference
behind the served-token check (`reference/granite_hybrid_reference.py`),
what a token and a SLOT hold in the engine's pools, the parameter count
and the sizes the counting readers need (`core/hybrid_counts.py`,
`core/ssd_counts.py`).
"""

import numpy as np

from deepspeed_tpu.inference.kv_cache import (state_pool_bytes,
                                              state_pool_spec_for)
from deepspeed_tpu.models import granite_hybrid as gh

from reference import granite_hybrid_reference


def serve_model_of(config):
    """The program's config from the published keys, at the chip's
    share: `num_local_experts` counts the experts HELD, the router keeps
    `router_outputs`; `vocab_size` is the slice's rows."""
    if config["mamba_n_groups"] != 1 or config["mamba_expand"] \
            * config["hidden_size"] != config["mamba_n_heads"] \
            * config["mamba_d_head"]:
        raise ValueError("granite_hybrid: one group of B and C, and an "
                         "inner width of heads x head width, are all the "
                         "program has")
    return gh.GraniteHybridConfig(
        vocab_size=config["vocab_size"],
        hidden_size=config["hidden_size"],
        num_layers=config["num_hidden_layers"],
        layer_types=tuple(config["layer_types"]),
        num_heads=config["num_attention_heads"],
        num_kv_heads=config["num_key_value_heads"],
        head_dim=config["hidden_size"] // config["num_attention_heads"],
        attention_multiplier=float(config["attention_multiplier"]),
        embedding_multiplier=float(config["embedding_multiplier"]),
        residual_multiplier=float(config["residual_multiplier"]),
        logits_scaling=float(config["logits_scaling"]),
        mamba_n_heads=config["mamba_n_heads"],
        mamba_d_head=config["mamba_d_head"],
        mamba_d_state=config["mamba_d_state"],
        mamba_d_conv=config["mamba_d_conv"],
        mamba_chunk_size=config["mamba_chunk_size"],
        intermediate_size=config["intermediate_size"],
        shared_intermediate_size=config["shared_intermediate_size"],
        num_experts=config["router_outputs"],
        experts_per_token=config["num_experts_per_tok"],
        rms_norm_eps=config["rms_norm_eps"],
        max_position_embeddings=config["max_position_embeddings"],
        experts_held=tuple(config["experts_held"]),
        vocab_held=tuple(config["vocab_held"]))


init_params = gh.init_granite_hybrid_params


def reference_config(model):
    """The plain reference's own dict of the same sizes."""
    return {"num_layers": model.num_layers,
            "layer_types": tuple(model.layer_types),
            "num_heads": model.num_heads,
            "num_kv_heads": model.num_kv_heads,
            "head_dim": model.head_dim,
            "attention_multiplier": model.attention_multiplier,
            "embedding_multiplier": model.embedding_multiplier,
            "residual_multiplier": model.residual_multiplier,
            "logits_scaling": model.logits_scaling,
            "mamba_n_heads": model.mamba_n_heads,
            "mamba_d_head": model.mamba_d_head,
            "mamba_d_state": model.mamba_d_state,
            "experts_per_token": model.experts_per_token,
            "rms_norm_eps": model.rms_norm_eps,
            "experts_held": model.held}


def reference_logits(model, **lower):
    """`fn(params, ids)`: (1, S) tokens -> (1, S, rows) float32 logits
    of the plain forward, to be jitted by the caller. `lower`
    (`state_dtype`, `round_to`, `products`) is the reference at a lower
    precision, for the cell's controls (`tools/serve_controls.py`)."""
    cfg = reference_config(model)
    return lambda params, ids: granite_hybrid_reference.logits(
        params, ids, cfg, **lower)


def reference_state(model, **lower):
    """`fn(params, ids, lengths)`: (B, S) tokens and their (B,) true
    lengths -> (B, Mamba layers, heads, d_head, d_state) float32, what
    the plain forward's recurrence holds after each row's true length: a
    slot's row of the engine's state pool (`engine.slot_state`) is held
    against it."""
    cfg = reference_config(model)
    return lambda params, ids, lengths: \
        granite_hybrid_reference.final_states(params, ids, lengths, cfg,
                                              **lower)


def cache_bytes(model, engine):
    """Bytes that live in the pools the engine built: `per_token` for
    every cached position (keys and values of the attention layers, in
    the page pool's own type) and `per_slot` for what a slot holds
    whatever its length (the Mamba layers' float32 state and the
    convolutions' tail)."""
    spec, state = engine.paged_spec, engine.state_spec
    width = np.dtype(spec.dtype).itemsize
    return {"per_token": 2 * spec.num_layers * spec.kv_heads
            * spec.head_dim * width,
            "per_slot": state_pool_bytes(state) // state.rows}


def param_count(model):
    mamba, soft, around, expert, table = gh.granite_hybrid_param_count(model)
    return (len(model.recurrent_layers) * mamba
            + len(model.softmax_layers) * soft
            + model.num_layers * (around + model.held[1] * expert) + table)


def describe_served(model):
    """`facts["model"]`: the sizes the counting readers need
    (`readers/hybrid_roofline.py` for `moe_experts` and `decode_step`,
    `readers/ssd_roofline.py` for the rest)."""
    row = state_pool_spec_for(model, 1)    # one slot's row, as built
    tail = int(np.prod(row.tail_shape[1:])) * np.dtype(
        row.tail_dtype).itemsize
    mamba, soft, around, expert, table = gh.granite_hybrid_param_count(model)
    return {"layers": model.num_layers, "hidden": model.hidden_size,
            "heads": model.num_heads, "head_dim": model.head_dim,
            "recurrent_layers": len(model.recurrent_layers),
            "softmax_layers": len(model.softmax_layers),
            "ssd_heads": model.mamba_n_heads,
            "ssd_head_dim": model.mamba_d_head,
            "ssd_state_dim": model.mamba_d_state,
            "ssd_tail_bytes_per_layer": tail,
            "state_bytes_per_slot": state_pool_bytes(row),
            "kv_bytes_per_token": 2 * len(model.softmax_layers)
            * model.num_kv_heads * model.head_dim * 2,
            "experts_held": model.held[1], "ffn": model.intermediate_size,
            "experts_per_token": model.experts_per_token,
            "router_outputs": model.num_experts,
            # the parameters ONE token's products meet on this chip: the
            # mixers and what stands around the experts whole, of its
            # experts the share held here in the mean, the table once
            # (the head; the embedding is a lookup)
            "params_met_per_token": (
                len(model.recurrent_layers) * mamba
                + len(model.softmax_layers) * soft
                + model.num_layers * (
                    around + model.experts_per_token * model.held[1]
                    / model.num_experts * expert)),
            "head_params": table,
            "weight_bytes": 2 * param_count(model)}
