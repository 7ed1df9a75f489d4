"""Family `lfm2`, the serving half: what `kinds/serve_backlog.py` needs
to serve a configuration of this architecture (gated short convolutions
3 : 1 with grouped-query attention whose queries and keys are normed a
head and then rotated, leading dense SwiGLU layers, routed SwiGLU
experts behind a sigmoid router with a correction bias an expert, NO
shared expert, a tied table) and to decide `correct`: the program's
model at the configuration file's sizes, its initialiser (weights held
in bfloat16), the plain float32 reference behind the served-token check
(`reference/lfm2_reference.py`), what a token and a SLOT hold in the
engine's pools, the parameter count and the sizes the counting readers
need (`core/lfm2_counts.py`).
"""

import numpy as np

from deepspeed_tpu.models import lfm2

from reference import lfm2_reference

# the planted faults a served cell's tolerance must refuse, beside the
# float8 products every family's controls have
# (`tools/serve_faults.py`): the reference's `lower` arguments of each.
# The two of attention are refused because the configuration seeds a
# head's norm weights away from one (`qk_norm_spread`): under weights of
# one a seeded q or k is near unit rms before its norm and attention
# says too little for either to show (they read 0.10-0.14 and 0.23-0.24
# of 0.3 then): traffic/serve-chat-saturated.json `logit_tolerance_why`
PLANTED = {"conv_without_tail": {"fault": "conv_tail"},
           "conv_gate_dropped": {"fault": "gate_dropped"},
           "qk_unnormed": {"fault": "qk_unnormed"},
           "rotation_at_position_0": {"fault": "rope_zero"},
           "router_without_bias": {"fault": "router_bias"},
           "router_weights_raw": {"fault": "router_weights"},
           "routed_sum_dropped": {"fault": "routed_sum"}}


def serve_model_of(config):
    """The program's config from the published keys: the layers are the
    model's own first `num_hidden_layers` of `layer_types`; the family
    is TOLD what it holds (`experts_held`, `vocab_held`) like every
    other, and here that is all of both."""
    rope = config["rope_parameters"]
    if config["conv_bias"] or not config["norm_topk_prob"] \
            or not config["use_expert_bias"] \
            or rope["rope_type"] != "default" \
            or config["vocab_held"] != [0, config["vocab_size"]]:
        raise ValueError("lfm2: convolutions without a bias, a router "
                         "with a correction bias whose chosen scores are "
                         "renormalised, the default rotation and the "
                         "whole (tied) table are all the program has")
    return lfm2.LFM2Config(
        vocab_size=config["vocab_size"],
        hidden_size=config["hidden_size"],
        num_layers=config["num_hidden_layers"],
        layer_types=tuple(config["layer_types"]),
        num_heads=config["num_attention_heads"],
        num_kv_heads=config["num_key_value_heads"],
        conv_L_cache=config["conv_L_cache"],
        intermediate_size=config["intermediate_size"],
        moe_intermediate_size=config["moe_intermediate_size"],
        num_dense_layers=config["num_dense_layers"],
        num_experts=config["num_experts"],
        experts_per_token=config["num_experts_per_tok"],
        routed_scaling_factor=float(config["routed_scaling_factor"]),
        rms_norm_eps=config["norm_eps"],
        rope_theta=float(rope["rope_theta"]),
        max_position_embeddings=config["max_position_embeddings"],
        initializer_range=config.get("initializer_range", 0.02),
        routed_init_gain=config.get("routed_init_gain", 1.0),
        router_bias_std=config.get("router_bias_std", 0.0),
        qk_norm_spread=config.get("qk_norm_spread", 1.0),
        experts_held=tuple(config["experts_held"]),
        vocab_held=tuple(config["vocab_held"]))


init_params = lfm2.init_lfm2_params


def reference_config(model):
    """The plain reference's own dict of the same sizes."""
    return {"layer_types": tuple(model.kinds),
            "num_dense_layers": model.num_dense_layers,
            "num_heads": model.num_heads,
            "num_kv_heads": model.num_kv_heads,
            "head_dim": model.head_dim,
            "rope_theta": model.rope_theta,
            "moe_intermediate_size": model.moe_intermediate_size,
            "experts_per_token": model.experts_per_token,
            "routed_scaling_factor": model.routed_scaling_factor,
            "rms_norm_eps": model.rms_norm_eps,
            "experts_held": model.held}


def reference_logits(model, **lower):
    """`fn(params, ids)`: (1, S) tokens -> (1, S, rows) float32 logits
    of the plain forward, to be jitted by the caller. `lower` is the
    reference at a lower precision or with a planted fault, for the
    cell's controls (`tools/serve_controls.py`, `tools/serve_faults.py`):
    `products`, `state_dtype`, `round_to`; `fault`
    (`reference/lfm2_reference.FAULTS`)."""
    cfg = reference_config(model)
    return lambda params, ids: lfm2_reference.logits(
        params, ids, cfg, **lower)


def cache_bytes(model, engine):
    """Bytes that live in the pools the engine built: `per_token` for
    every cached position (keys and values of the attention layers, at
    the pool's own type) and `per_slot` for what a slot holds whatever
    its length (the convolutions' tails; there is no recurrent state)."""
    from deepspeed_tpu.inference.kv_cache import state_pool_bytes
    spec, tails = engine.paged_spec, engine.state_spec
    return {"per_token": 2 * spec.num_layers * spec.kv_heads
            * spec.head_dim * np.dtype(spec.dtype).itemsize,
            "per_slot": state_pool_bytes(tails) // tails.rows}


def _counts(model):
    conv, attn, dense, router, expert, tables = lfm2.lfm2_param_count(model)
    mixers = len(model.conv_layers) * conv + model.kv_cache_layers * attn
    return mixers, dense, router, expert, tables


def param_count(model):
    mixers, dense, router, expert, tables = _counts(model)
    return (mixers + model.num_dense_layers * dense
            + len(model.expert_layers) * (router + model.held[1] * expert)
            + tables)


def describe_served(model):
    """`facts["model"]`: the sizes the counting readers need
    (`readers/lfm2_roofline.py`, `core/lfm2_counts.py`)."""
    mixers, dense, router, expert, tables = _counts(model)
    experts = len(model.expert_layers)
    head = model.vocab_rows * model.hidden_size
    return {"family": "lfm2",
            "layers": model.num_layers, "expert_layers": experts,
            "hidden": model.hidden_size,
            "conv_layers": len(model.conv_layers),
            "conv_width": model.conv_L_cache,
            "attention_layers": model.kv_cache_layers,
            "heads": model.num_heads, "kv_heads": model.num_kv_heads,
            "head_dim": model.head_dim,
            "kv_bytes_per_token": 2 * model.kv_cache_layers
            * model.num_kv_heads * model.head_dim * 2,
            "tail_bytes_per_slot": len(model.conv_layers)
            * (model.conv_L_cache - 1) * model.hidden_size * 2,
            "experts_held": model.held[1],
            "ffn": model.moe_intermediate_size,
            "experts_per_token": model.experts_per_token,
            "router_outputs": model.num_experts,
            # the parameters ONE token's products meet on this chip: the
            # mixers, the dense layers and the routers whole, of its
            # experts the share held here in the mean; the head apart
            # (ONE position a row; the embedding is a lookup of the same
            # table)
            "params_met_per_token": (
                mixers + model.num_dense_layers * dense
                + experts * (router + model.experts_per_token
                             * model.held[1] / model.num_experts * expert)),
            "head_params": head,
            "weight_bytes": 2 * param_count(model)}
