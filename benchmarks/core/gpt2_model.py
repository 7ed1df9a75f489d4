"""The program's `GPT2Config` at a configuration file's sizes."""

from deepspeed_tpu.models.gpt2 import GPT2Config


def model_of(config, vocab, dropout=None):
    """`dropout` None keeps the published rates (serving never applies
    them); 0.0 turns them off, as the example trains."""
    rates = {} if dropout is None else dict(
        embd_dropout=dropout, attn_dropout=dropout, resid_dropout=dropout)
    return GPT2Config(
        vocab_size=vocab,
        max_position_embeddings=config["max_position_embeddings"],
        hidden_size=config["hidden_size"], num_layers=config["num_layers"],
        num_heads=config["num_heads"],
        intermediate_size=config["intermediate_size"],
        layer_norm_eps=config["layer_norm_eps"],
        initializer_range=config["initializer_range"], **rates)
