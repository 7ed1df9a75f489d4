"""Family `gpt2`, the serving half: what `kinds/serve_backlog.py` needs
to serve a configuration of this architecture and to decide `correct`:
the program's model at the configuration file's sizes, its initialiser,
the plain float32 reference behind the served-token check
(`reference/gpt2_reference.py`), what a token and a slot hold in the
engine's pools, and the sizes the counting readers need.

What a family gives the serving kind (a later served architecture is
this file again, a reference, a configuration and a traffic file):
`serve_model_of(config)`, `init_params(model, key)`,
`reference_logits(model)`, `cache_bytes(model, engine)`,
`param_count(model)`, `describe_served(model)`.
"""

import numpy as np

from deepspeed_tpu.models.gpt2 import init_gpt2_params

from core import flops
from core.gpt2_model import model_of
from reference import gpt2_reference


def serve_model_of(config):
    """The program's config at the sizes served: the published rows of
    the vocabulary (training pads them, serving does not)."""
    return model_of(config, config["serve"]["vocab_size"])


init_params = init_gpt2_params


def reference_logits(model):
    """`fn(params, ids)`: (1, S) tokens -> (1, S, vocab) float32 logits
    of the plain forward, to be jitted by the caller."""
    return lambda params, ids: gpt2_reference.logits(
        params, ids, model.num_layers, model.num_heads)


def cache_bytes(model, engine):
    """Bytes that live in the pools the engine built: `per_token` for
    every cached position (keys and values over all layers, in the page
    pool's own type) and `per_slot` for what a slot holds whatever its
    length (a recurrent state; this architecture has none)."""
    width = np.dtype(engine.paged_spec.dtype).itemsize
    return {"per_token": flops.kv_bytes_per_token(
        model.num_layers, model.hidden_size, width), "per_slot": 0}


def param_count(model):
    return flops.gpt2_param_count(
        model.vocab_size, model.max_position_embeddings,
        model.hidden_size, model.num_layers, model.inter)


def describe_served(model):
    """`facts["model"]`: the sizes the counting readers need."""
    return {"layers": model.num_layers, "hidden": model.hidden_size,
            "heads": model.num_heads}
