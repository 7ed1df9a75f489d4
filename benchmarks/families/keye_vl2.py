"""Family `keye_vl2`, the serving half: what `kinds/serve_backlog.py`
needs to serve a configuration of this architecture (grouped-query
attention normed a head and rotated, read over a learned selection of
2,048 tokens a query that an indexer picks inside the paged cache,
routed SwiGLU experts behind a softmax router, no shared expert;
prompts served in CHUNKS that score and attend the prefix) and to
decide `correct`: the program's model at the configuration file's
sizes, its initialiser (weights held in bfloat16), the plain float32
reference behind the served-token check
(`reference/keye_vl2_reference.py`), what a token holds in the engine's
pools, the parameter count and the sizes the counting readers need
(`core/dsa_counts.py`).
"""

import numpy as np

from deepspeed_tpu.models import keye_vl2 as kv2

from reference import keye_vl2_reference

# a served chunk's tokens in the cell (`serve.inference.chunked_prefill.
# chunk_tokens`), and the decode steps a stale indexer row is planted at
# (the cell's longest output: a decode that never wrote the third leaf,
# at its last step. The newest positions of a query's 37k hold about
# their share of its 2,048 chosen tokens, so at 512 steps, the shortest
# output, 28 tokens of a set change, which is what a bfloat16 engine's
# own rounding moves: traffic/serve-repo-saturated.json
# `logit_tolerance_why`): the rehearsal's tiny size has its own, and a
# fault planted at another size is a fault all the same
_CHUNK = 2048
_STALE = 4096
# the planted faults a served cell's tolerance must refuse, beside the
# float8 products every family's controls have
# (`tools/serve_faults.py`): the reference's `lower` arguments of each
PLANTED = {"no_selection": {"fault": "no_selection"},
           "newest_in_place_of_chosen": {"fault": "newest"},
           "indexer_without_w": {"fault": "indexer_weights"},
           "indexer_without_relu": {"fault": "indexer_relu"},
           "chunk_scores_own_rows_only": {"fault": "chunk_scores",
                                          "chunk": _CHUNK},
           "stale_indexer_rows": {"fault": "stale_index", "chunk": _STALE},
           "router_weights_raw": {"fault": "router_weights"}}


def serve_model_of(config):
    """The program's config from the published keys, at the chip's
    share: `num_experts` counts the experts HELD, the router keeps
    `router_outputs`; `vocab_size` is the slice's rows; the layers are
    the model's own first `num_hidden_layers`."""
    sa, rope = config["sa_config"], config["rope_scaling"]
    if config["attention_bias"] or not config["norm_topk_prob"] \
            or config["decoder_sparse_step"] != 1 \
            or config["mlp_only_layers"] or config["use_sliding_window"] \
            or config["tie_word_embeddings"] \
            or rope["rope_type"] != "default" \
            or sa["indexer_num_kv_heads"] != 1 \
            or config["router_outputs"] != config["num_local_experts"]:
        raise ValueError("keye_vl2: no attention bias, experts in every "
                         "layer behind a softmax router renormalised over "
                         "the chosen, no window, an untied head, the "
                         "default rotation and ONE indexer key head are "
                         "all the program has")
    return kv2.KeyeVL2Config(
        vocab_size=config["vocab_size"],
        hidden_size=config["hidden_size"],
        num_layers=config["num_hidden_layers"],
        num_heads=config["num_attention_heads"],
        num_kv_heads=config["num_key_value_heads"],
        head_dim=config["head_dim"],
        moe_intermediate_size=config["moe_intermediate_size"],
        num_experts=config["router_outputs"],
        experts_per_token=config["num_experts_per_tok"],
        rms_norm_eps=config["rms_norm_eps"],
        rope_theta=float(config["rope_theta"]),
        mrope_section=tuple(rope["mrope_section"]),
        indexer_num_heads=sa["indexer_num_heads"],
        indexer_head_dim=sa["indexer_head_dim"],
        indexer_topk=sa["topk"],
        max_position_embeddings=config["max_position_embeddings"],
        initializer_range=config.get("initializer_range", 0.02),
        embed_init_gain=config.get("embed_init_gain", 1.0),
        experts_held=tuple(config["experts_held"]),
        vocab_held=tuple(config["vocab_held"]))


init_params = kv2.init_keye_vl2_params


def reference_config(model):
    """The plain reference's own dict of the same sizes."""
    return {"num_layers": model.num_layers,
            "num_heads": model.num_heads,
            "num_kv_heads": model.num_kv_heads,
            "head_dim": model.head_dim,
            "rope_theta": model.rope_theta,
            "mrope_section": tuple(model.mrope_section),
            "indexer_num_heads": model.indexer_num_heads,
            "indexer_head_dim": model.indexer_head_dim,
            "indexer_topk": model.indexer_topk,
            "experts_per_token": model.experts_per_token,
            "rms_norm_eps": model.rms_norm_eps,
            "experts_held": model.held}


def reference_logits(model, **lower):
    """`fn(params, ids)`: (1, S) tokens -> (1, S, rows) float32 logits
    of the plain forward, to be jitted by the caller. `lower` is the
    reference at a lower precision or with a planted fault, for the
    cell's controls (`tools/serve_controls.py`, `tools/serve_faults.py`):
    `products`, `state_dtype`, `round_to`; `fault` and `chunk`
    (`reference/keye_vl2_reference.FAULTS`)."""
    cfg = reference_config(model)
    return lambda params, ids: keye_vl2_reference.logits(
        params, ids, cfg, **lower)


def cache_bytes(model, engine):
    """Bytes that live in the pools the engine built: `per_token` for
    every cached position, EVERY leaf of the tree (keys, values and the
    indexer's key, each at the pool's own type); nothing a slot."""
    spec = engine.paged_spec
    return {"per_token": spec.num_layers
            * (2 * spec.kv_heads * spec.head_dim + spec.index_width)
            * np.dtype(spec.dtype).itemsize,
            "per_slot": 0}


def _counts(model):
    attn, indexer, router, expert, tables = kv2.keye_vl2_param_count(model)
    return model.num_layers * (attn + indexer), router, expert, tables


def param_count(model):
    mixers, router, expert, tables = _counts(model)
    return (mixers + model.num_layers * (router + model.held[1] * expert)
            + tables)


def describe_served(model):
    """`facts["model"]`: the sizes the counting readers need
    (`readers/dsa_roofline.py`, `core/dsa_counts.py`)."""
    mixers, router, expert, tables = _counts(model)
    head = model.vocab_rows * model.hidden_size
    return {"family": "keye_vl2",
            "layers": model.num_layers, "expert_layers": model.num_layers,
            "hidden": model.hidden_size,
            "heads": model.num_heads, "kv_heads": model.num_kv_heads,
            "head_dim": model.head_dim,
            "indexer_heads": model.indexer_num_heads,
            "indexer_dim": model.indexer_head_dim,
            "topk": model.indexer_topk,
            "experts_held": model.held[1],
            "ffn": model.moe_intermediate_size,
            "experts_per_token": model.experts_per_token,
            "router_outputs": model.num_experts,
            # the parameters ONE token's products meet on this chip: the
            # mixers with their indexers and the routers whole, of its
            # experts the share held here in the mean; the head apart
            # (ONE position a row; the embedding is a lookup)
            "params_met_per_token": (
                mixers + model.num_layers
                * (router + model.experts_per_token * model.held[1]
                   / model.num_experts * expert)),
            "head_params": head,
            "weight_bytes": 2 * param_count(model)}
