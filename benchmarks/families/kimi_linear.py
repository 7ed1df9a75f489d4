"""Family `kimi_linear`, the serving half: what `kinds/serve_backlog.py`
needs to serve a configuration of this architecture (gated delta-rule
attention over a per-slot float32 state among latent attention layers
with no rotation over one latent page pool, a leading dense SwiGLU
layer, routed SwiGLU experts behind a sigmoid router with a correction
bias an expert, a shared expert; prompts served in CHUNKS that carry the
state and read the latent prefix) and to decide `correct`: the program's
model at the configuration file's sizes, its initialiser (weights held
in bfloat16), the plain float32 reference behind the served-token check
(`reference/kimi_linear_reference.py`), what a token and a slot hold in
the engine's pools, the parameter count and the sizes the counting
readers need (`core/kimi_counts.py`).
"""

import numpy as np

from deepspeed_tpu.models import kimi_linear as kl

from reference import kimi_linear_reference

# a served chunk's tokens in the cell (`serve.inference.chunked_prefill.
# chunk_tokens`); the rehearsal's tiny size has its own, and a fault
# planted at another chunk size is a fault all the same
_CHUNK = 2048
# the planted faults a served cell's tolerance must refuse, beside the
# float8 products every family's controls have
# (`tools/serve_faults.py`): the reference's `lower` arguments of each.
# The first three are what a CHUNK could get wrong (the convolution's
# tail forgotten touches three positions of 2,048, and the state carries
# them on: it read 3.21 where the limit is 0.3, my chip run, PR 48)
PLANTED = {"chunk_from_empty_state": {"fault": "chunk_state",
                                      "chunk": _CHUNK},
           "chunk_forgets_conv_tail": {"fault": "chunk_tail",
                                       "chunk": _CHUNK},
           "chunk_sees_own_rows_only": {"fault": "chunk_prefix",
                                        "chunk": _CHUNK},
           "router_without_bias": {"fault": "router_bias"},
           "router_weights_raw": {"fault": "router_weights"}}
# ... and what this share's limit stands too close to for a promise,
# shown with its number and required of nothing: the routed sum of 32
# held experts of 256 dropped (0.35 of 0.3; traffic/
# serve-longctx-saturated.json `logit_tolerance_why`)
SHOWN = {"routed_sum_dropped": {"fault": "routed_sum"}}


def serve_model_of(config):
    """The program's config from the published keys, at the chip's
    share: `num_experts` counts the experts HELD, the router keeps
    `router_outputs`; `vocab_size` is the slice's rows; the layers are
    the model's own first `num_hidden_layers`."""
    linear = config["linear_attn_config"]
    if config["num_shared_experts"] != 1 \
            or config["moe_router_activation_func"] != "sigmoid" \
            or not config["moe_renormalize"] or not config["mla_use_nope"] \
            or config["q_lora_rank"] is not None \
            or config["num_expert_group"] != 1 or config["topk_group"] != 1:
        raise ValueError("kimi_linear: one shared expert, sigmoid scores "
                         "renormalised over the chosen in ONE group, "
                         "latent attention with no rotation and no "
                         "queries' rank are all the program has")
    layers = config["num_hidden_layers"]
    # the config counts layers from 1
    latent = tuple(l - 1 for l in linear["full_attn_layers"] if l <= layers)
    kda = tuple(l - 1 for l in linear["kda_layers"] if l <= layers)
    if sorted(latent + kda) != list(range(layers)):
        raise ValueError("kimi_linear: every layer is a delta-rule or a "
                         "latent attention layer")
    return kl.KimiLinearConfig(
        vocab_size=config["vocab_size"],
        hidden_size=config["hidden_size"],
        num_layers=layers, latent_layers=latent,
        num_heads=config["num_attention_heads"],
        kv_lora_rank=config["kv_lora_rank"],
        qk_nope_head_dim=config["qk_nope_head_dim"],
        qk_rope_head_dim=config["qk_rope_head_dim"],
        v_head_dim=config["v_head_dim"],
        kda_num_heads=linear["num_heads"],
        kda_head_dim=linear["head_dim"],
        kda_conv_width=linear["short_conv_kernel_size"],
        kda_gate_rank=config["kda_gate_rank"],
        intermediate_size=config["intermediate_size"],
        moe_intermediate_size=config["moe_intermediate_size"],
        first_k_dense=config["first_k_dense_replace"],
        num_experts=config["router_outputs"],
        experts_per_token=config["num_experts_per_token"],
        routed_scaling_factor=float(config["routed_scaling_factor"]),
        rms_norm_eps=config["rms_norm_eps"],
        max_position_embeddings=config["model_max_length"],
        initializer_range=config.get("initializer_range", 0.02),
        routed_init_gain=config.get("routed_init_gain", 1.0),
        router_bias_std=config.get("router_bias_std", 0.0),
        experts_held=tuple(config["experts_held"]),
        vocab_held=tuple(config["vocab_held"]))


init_params = kl.init_kimi_linear_params


def reference_config(model):
    """The plain reference's own dict of the same sizes."""
    return {"num_layers": model.num_layers,
            "latent_layers": tuple(model.latent_layers),
            "first_k_dense": model.first_k_dense,
            "num_heads": model.num_heads,
            "kv_lora_rank": model.kv_lora_rank,
            "qk_nope_head_dim": model.qk_nope_head_dim,
            "qk_rope_head_dim": model.qk_rope_head_dim,
            "v_head_dim": model.v_head_dim,
            "kda_num_heads": model.kda_num_heads,
            "kda_head_dim": model.kda_head_dim,
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
    `products`, `state_dtype`, `round_to`; `fault` and `chunk`
    (`reference/kimi_linear_reference.FAULTS`)."""
    cfg = reference_config(model)
    return lambda params, ids: kimi_linear_reference.logits(
        params, ids, cfg, **lower)


def cache_bytes(model, engine):
    """Bytes that live in the pools the engine built: `per_token` for
    every cached position, ONE latent row a latent layer at the pool's
    own lanes and type (the padded row: what the chip holds), and
    `per_slot` for what a slot holds whatever its length (the delta-rule
    layers' float32 state and the convolutions' tail)."""
    from deepspeed_tpu.inference.kv_cache import state_pool_bytes
    spec, state = engine.paged_spec, engine.state_spec
    return {"per_token": spec.num_layers * spec.row_lanes
            * np.dtype(spec.dtype).itemsize,
            "per_slot": state_pool_bytes(state) // state.rows}


def _counts(model):
    kda, latent, dense, around, expert, tables = \
        kl.kimi_linear_param_count(model)
    n_kda, n_latent = len(model.recurrent_layers), model.kv_cache_layers
    mixers = n_kda * kda + n_latent * latent
    return mixers, dense, around, expert, tables


def param_count(model):
    mixers, dense, around, expert, tables = _counts(model)
    experts = len(model.expert_layers)
    return (mixers + model.first_k_dense * dense
            + experts * (around + model.held[1] * expert) + tables)


def describe_served(model):
    """`facts["model"]`: the sizes the counting readers need
    (`readers/kimi_roofline.py`, `core/kimi_counts.py`)."""
    from deepspeed_tpu.inference.kv_cache import (state_pool_bytes,
                                                  state_pool_spec_for)
    mixers, dense, around, expert, tables = _counts(model)
    experts = len(model.expert_layers)
    row = state_pool_spec_for(model, 1)    # one slot's row, as built
    tail = int(np.prod(row.tail_shape[1:])) * np.dtype(
        row.tail_dtype).itemsize
    head = model.vocab_rows * model.hidden_size
    return {"family": "kimi_linear",
            "layers": model.num_layers, "expert_layers": experts,
            "hidden": model.hidden_size,
            "latent_layers": model.kv_cache_layers,
            "heads": model.num_heads,
            "latent_width": model.kv_lora_rank,
            "shared_key_width": model.qk_rope_head_dim,
            "key_width": model.qk_nope_head_dim + model.qk_rope_head_dim,
            "value_width": model.v_head_dim,
            "kda_layers": len(model.recurrent_layers),
            "kda_heads": model.kda_num_heads,
            "kda_key_dim": model.kda_head_dim,
            "kda_value_dim": model.kda_head_dim,
            "kda_tail_bytes_per_layer": tail,
            "state_bytes_per_slot": state_pool_bytes(row),
            "experts_held": model.held[1],
            "ffn": model.moe_intermediate_size,
            "experts_per_token": model.experts_per_token,
            "router_outputs": model.num_experts,
            # the parameters ONE token's products meet on this chip: the
            # mixers, the dense layer, the router and the shared expert
            # whole, of its experts the share held here in the mean; the
            # head apart (ONE position a row; the embedding is a lookup)
            "params_met_per_token": (
                mixers + model.first_k_dense * dense
                + experts * (around + model.experts_per_token
                             * model.held[1] / model.num_experts * expert)),
            "head_params": head,
            "weight_bytes": 2 * param_count(model)}
