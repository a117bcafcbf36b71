"""The program under test for the latent-attention, routed-expert
decoder family (DeepSeek-V3 / Moonlight): ``DecoderLM`` with
``ModelConfig.mla`` and ``.moe`` set, built as a user's training script
builds it, through ``init_train_state`` and ``make_train_step``."""

from __future__ import annotations

from traceml_tpu.models import ModelConfig, init_train_state, make_train_step
from traceml_tpu.models.transformer import ExpertConfig, MLAConfig

#: the published routing this family's program computes, key by key
_ROUTING = {
    "scoring_func": "sigmoid", "topk_method": "noaux_tc", "n_group": 1, "topk_group": 1,
    "norm_topk_prob": True, "moe_layer_freq": 1, "q_lora_rank": None, "hidden_act": "silu",
}


def model_config(spec: dict) -> ModelConfig:
    """The program's ModelConfig for the cell, checked against what the
    configuration file states."""
    m = spec["model"]
    other = {k: m.get(k) for k, v in _ROUTING.items() if m.get(k) != v}
    if other:
        raise ValueError(f"the program computes {_ROUTING}; the configuration states {other}")
    lo, hi = m["experts_held"]
    cfg = ModelConfig(
        vocab_size=m["vocab_size"], hidden=m["hidden_size"],
        n_layers=m["num_hidden_layers"], n_heads=m["num_attention_heads"],
        n_kv_heads=m["num_key_value_heads"],
        ffn_mult=m["intermediate_size"] / m["hidden_size"],
        max_seq_len=spec["load"]["seq"], rope_theta=float(m["rope_theta"]),
        rms_eps=m["rms_norm_eps"], remat=m["program"]["remat"],
        mla=MLAConfig(
            kv_lora_rank=m["kv_lora_rank"], qk_nope_head_dim=m["qk_nope_head_dim"],
            qk_rope_head_dim=m["qk_rope_head_dim"], v_head_dim=m["v_head_dim"],
        ),
        moe=ExpertConfig(
            n_experts=m["published"]["n_routed_experts"], top_k=m["num_experts_per_tok"],
            expert_ffn=m["moe_intermediate_size"], n_shared=m["n_shared_experts"],
            first_dense=m["first_k_dense_replace"], routed_scale=m["routed_scaling_factor"],
            expert_offset=lo, n_held=hi - lo, aux_alpha=m["seq_aux_alpha"],
        ),
    )
    if (cfg.ffn_hidden, cfg.moe.n_held) != (m["intermediate_size"], m["n_routed_experts"]):
        raise ValueError(
            f"ModelConfig gives ffn {cfg.ffn_hidden}, {cfg.moe.n_held} experts held; the "
            f"configuration states {m['intermediate_size']}, {m['n_routed_experts']}"
        )
    return cfg


def build(spec: dict, key, mesh):
    """The unjitted train step and its state, placed on ``mesh`` if given."""
    model, state, tx = init_train_state(
        model_config(spec), key,
        learning_rate=spec["model"]["optimizer"]["learning_rate"], mesh=mesh,
    )
    return make_train_step(model, tx, mesh=mesh), state
