"""Family `axk1`, the serving half: what `kinds/serve_backlog.py` needs
to serve a configuration of this architecture (multi-head latent
attention over one latent page pool, a leading dense SwiGLU layer,
routed SwiGLU experts behind a sigmoid router limited to the best
groups, a shared expert) and to decide `correct`: the program's model at
the configuration file's sizes, its initialiser (weights held in
bfloat16), the plain float32 reference behind the served-token check
(`reference/axk1_reference.py`), what a token holds in the engine's
pool, the parameter count and the sizes the counting readers need
(`core/mla_counts.py`).
"""

import numpy as np

from deepspeed_tpu.models import axk1 as ax

from reference import axk1_reference

# the planted faults a served cell's tolerance must refuse, beside the
# float8 products every family's controls have
# (`tools/serve_faults.py`): the reference's `lower` arguments of each
PLANTED = {"scale_without_mscale": {"fault": "scale"},
           "rotary_key_unrotated": {"fault": "rotary_key"},
           "latent_row_float8_e5m2": {"state_dtype": "float8_e5m2"},
           "router_weights_raw": {"fault": "router_weights"}}
# ... and two of the expert half's that it CANNOT refuse, shown and
# required of nothing: this chip holds 12 of 192 experts, half a pick a
# token lands on one, and a routed expert is seeded a quarter as loud as
# the other branches (`routed_init_gain`), so the whole routed sum is
# 3% of the stream where the tolerance is worth 6%. They read 0.21 and
# 0.38 of 0.6; with experts as loud as the rest 0.95 and 1.38, and the
# SOUND engine 0.63 (PERF.md section 6, PR 43). Tier-1 holds the router
# and the expert sum to the reference in float32, and
# `expert_held_share.reason` reads what the router did on the chip
SHOWN = {"no_group_limit": {"fault": "group_limit"},
         "routed_sum_dropped": {"fault": "routed_sum"}}


def serve_model_of(config):
    """The program's config from the published keys, at the chip's
    share: `n_routed_experts` counts the experts HELD, the router keeps
    `router_outputs`; `vocab_size` is the slice's rows."""
    if config["n_shared_experts"] != 1 or config["scoring_func"] \
            != "sigmoid" or config["rope_scaling"]["type"] != "yarn" \
            or not config["norm_topk_prob"]:
        raise ValueError("axk1: one shared expert, sigmoid scores "
                         "renormalised over the chosen, and YaRN rotary "
                         "are all the program has")
    rope = config["rope_scaling"]
    return ax.AXK1Config(
        vocab_size=config["vocab_size"],
        hidden_size=config["hidden_size"],
        num_layers=config["num_hidden_layers"],
        num_heads=config["num_attention_heads"],
        q_lora_rank=config["q_lora_rank"],
        kv_lora_rank=config["kv_lora_rank"],
        qk_nope_head_dim=config["qk_nope_head_dim"],
        qk_rope_head_dim=config["qk_rope_head_dim"],
        v_head_dim=config["v_head_dim"],
        intermediate_size=config["intermediate_size"],
        moe_intermediate_size=config["moe_intermediate_size"],
        first_k_dense=config["first_k_dense_replace"],
        num_experts=config["router_outputs"],
        experts_per_token=config["num_experts_per_tok"],
        n_group=config["n_group"], topk_group=config["topk_group"],
        routed_scaling_factor=float(config["routed_scaling_factor"]),
        rms_norm_eps=config["rms_norm_eps"],
        rope_theta=float(config["rope_theta"]),
        rope_factor=float(rope["factor"]),
        rope_beta_fast=float(rope["beta_fast"]),
        rope_beta_slow=float(rope["beta_slow"]),
        rope_mscale=float(rope["mscale"]),
        rope_mscale_all_dim=float(rope["mscale_all_dim"]),
        rope_original_max=rope["original_max_position_embeddings"],
        max_position_embeddings=config["max_position_embeddings"],
        initializer_range=config.get("initializer_range", 0.02),
        routed_init_gain=config.get("routed_init_gain", 1.0),
        experts_held=tuple(config["experts_held"]),
        vocab_held=tuple(config["vocab_held"]))


init_params = ax.init_axk1_params


def reference_config(model):
    """The plain reference's own dict of the same sizes."""
    return {"num_layers": model.num_layers,
            "first_k_dense": model.first_k_dense,
            "num_heads": model.num_heads,
            "kv_lora_rank": model.kv_lora_rank,
            "qk_nope_head_dim": model.qk_nope_head_dim,
            "qk_rope_head_dim": model.qk_rope_head_dim,
            "v_head_dim": model.v_head_dim,
            "moe_intermediate_size": model.moe_intermediate_size,
            "experts_per_token": model.experts_per_token,
            "n_group": model.n_group, "topk_group": model.topk_group,
            "routed_scaling_factor": model.routed_scaling_factor,
            "rms_norm_eps": model.rms_norm_eps,
            "rope_theta": model.rope_theta,
            "rope_factor": model.rope_factor,
            "rope_beta_fast": model.rope_beta_fast,
            "rope_beta_slow": model.rope_beta_slow,
            "rope_mscale_all_dim": model.rope_mscale_all_dim,
            "rope_original_max": model.rope_original_max,
            "experts_held": model.held}


def reference_logits(model, **lower):
    """`fn(params, ids)`: (1, S) tokens -> (1, S, rows) float32 logits
    of the plain forward, to be jitted by the caller. `lower` is the
    reference at a lower precision or with a planted fault, for the
    cell's controls (`tools/serve_controls.py`, `tools/serve_faults.py`):
    `products`; `state_dtype`, read as the dtype of the cached latent
    row; `fault` (`reference/axk1_reference.FAULTS`). `round_to` (a
    recurrence's operands: the family has none) changes nothing."""
    cfg = reference_config(model)
    lower.pop("round_to", None)
    return lambda params, ids: axk1_reference.logits(
        params, ids, cfg, **lower)


def cache_bytes(model, engine):
    """Bytes that live in the pool the engine built: `per_token` for
    every cached position, ONE latent row a layer at the pool's own
    lanes and type (the padded row: what the chip holds); nothing a
    slot."""
    spec = engine.paged_spec
    return {"per_token": spec.num_layers * spec.row_lanes
            * np.dtype(spec.dtype).itemsize,
            "per_slot": 0}


def param_count(model):
    mixer, dense, around, expert, table = ax.axk1_param_count(model)
    experts = len(model.expert_layers)
    return (model.num_layers * mixer + model.first_k_dense * dense
            + experts * (around + model.held[1] * expert) + table)


def describe_served(model):
    """`facts["model"]`: the sizes the counting readers need
    (`readers/mla_roofline.py`). `layers` are the layers with a latent
    row (all of them), `expert_layers` those with routed experts."""
    mixer, dense, around, expert, table = ax.axk1_param_count(model)
    experts = len(model.expert_layers)
    return {"layers": model.num_layers, "expert_layers": experts,
            "hidden": model.hidden_size, "heads": model.num_heads,
            "latent_width": model.kv_lora_rank,
            "rope_width": model.qk_rope_head_dim,
            "key_width": model.qk_nope_head_dim + model.qk_rope_head_dim,
            "value_width": model.v_head_dim,
            "experts_held": model.held[1],
            "ffn": model.moe_intermediate_size,
            "experts_per_token": model.experts_per_token,
            "router_outputs": model.num_experts,
            # the parameters ONE token's products meet on this chip: the
            # mixers, the dense layers, the router and the shared expert
            # whole, of its experts the share held here in the mean; the
            # table once (the head; the embedding is a lookup)
            "params_met_per_token": (
                model.num_layers * mixer + model.first_k_dense * dense
                + experts * (around + model.experts_per_token
                             * model.held[1] / model.num_experts * expert)),
            "head_params": (table - model.hidden_size) // 2,
            "weight_bytes": 2 * param_count(model)}
