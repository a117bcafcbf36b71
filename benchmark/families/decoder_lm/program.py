"""The program under test for the dense decoder family: ``DecoderLM``
built as a user's training script builds it, through
``init_train_state`` and ``make_train_step``."""

from __future__ import annotations

from traceml_tpu.models import ModelConfig, init_train_state, make_train_step


def model_config(spec: dict) -> ModelConfig:
    """The program's ModelConfig for the cell, checked against the widths
    the configuration file states."""
    m = spec["model"]
    cfg = ModelConfig(
        vocab_size=m["vocab_size"], hidden=m["hidden_size"],
        n_layers=m["num_hidden_layers"], n_heads=m["num_attention_heads"],
        n_kv_heads=m["num_key_value_heads"], ffn_mult=m["program"]["ffn_mult"],
        max_seq_len=spec["load"]["seq"], rope_theta=float(m["rope_theta"]),
    )
    if (cfg.head_dim, cfg.ffn_hidden) != (m["head_dim"], m["intermediate_size"]):
        raise ValueError(
            f"ModelConfig gives head_dim {cfg.head_dim}, ffn {cfg.ffn_hidden}; "
            f"the configuration states {m['head_dim']}, {m['intermediate_size']}"
        )
    return cfg


def build(spec: dict, key, mesh):
    """The unjitted train step and its state, placed on ``mesh`` if given."""
    model, state, tx = init_train_state(
        model_config(spec), key,
        learning_rate=spec["model"]["optimizer"]["learning_rate"], mesh=mesh,
    )
    return make_train_step(model, tx, mesh=mesh), state
