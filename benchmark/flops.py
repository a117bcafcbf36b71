"""FLOPs of one DecoderLM training step, from shapes alone.

Counted: every matmul of the forward pass (q, k, v, o projections, the
SwiGLU gate, up and down projections, ``lm_head``) and attention's score
(QK^T) and value (PV) products, two FLOPs per multiply-add; the backward
pass as twice the forward. Left out: the embedding gather, norms,
softmax and other elementwise work, the optimizer, and any recomputation.

Causal convention: attention counts the query-key pairs a causal mask
keeps, L(L+1)/2 per head and sequence, not the L^2 the dense path
computes, because those are the operations the model requires.
"""

from __future__ import annotations


def forward_flops_per_sequence(cfg: dict, seq_len: int) -> float:
    """Forward FLOPs for one sequence of ``seq_len`` positions."""
    h = cfg["hidden_size"]
    hd = cfg["head_dim"]
    nq = cfg["num_attention_heads"]
    nkv = cfg["num_key_value_heads"]
    ffn = cfg["intermediate_size"]
    vocab = cfg["vocab_size"]
    per_token_layer = 2 * (
        h * nq * hd          # q
        + 2 * h * nkv * hd   # k, v
        + nq * hd * h        # o
        + 3 * h * ffn        # gate, up, down
    )
    pairs = seq_len * (seq_len + 1) // 2
    attn_layer = 2 * 2 * nq * hd * pairs  # QK^T and PV
    layers = cfg["num_hidden_layers"] * (per_token_layer * seq_len + attn_layer)
    head = 2 * h * vocab * seq_len
    return float(layers + head)


def train_step_flops(cfg: dict, batch: int, seq: int) -> float:
    """Forward plus backward FLOPs of one step on a ``batch x seq`` token
    array; the model sees ``seq - 1`` positions (inputs ``[:, :-1]``,
    targets ``[:, 1:]``)."""
    return 3.0 * batch * forward_flops_per_sequence(cfg, seq - 1)
