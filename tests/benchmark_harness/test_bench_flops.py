"""The step FLOP count against a count made by hand."""

import pytest

from benchmark.flops import forward_flops_per_sequence, train_step_flops

TINY = {
    "hidden_size": 8, "head_dim": 2, "num_attention_heads": 4,
    "num_key_value_heads": 2, "intermediate_size": 16, "vocab_size": 10,
    "num_hidden_layers": 3,
}


def test_forward_by_hand():
    L = 5
    # per token and layer: q 8x8, k and v 8x4 each, o 8x8, gate/up/down 8x16
    macs_token_layer = 64 + 32 + 32 + 64 + 3 * 128
    # causal pairs 5*6/2 = 15; QK^T and PV each 4 heads x 2 dims per pair
    macs_attn_layer = 2 * 4 * 2 * 15
    macs_head = 8 * 10 * L
    want = 2 * (3 * (macs_token_layer * L + macs_attn_layer) + macs_head)
    assert forward_flops_per_sequence(TINY, L) == want


@pytest.mark.parametrize("batch,seq", [(1, 6), (4, 6), (2, 33)])
def test_step_is_three_forwards_of_seq_minus_one(batch, seq):
    assert train_step_flops(TINY, batch, seq) == 3 * batch * forward_flops_per_sequence(TINY, seq - 1)


def test_ouro_cell_size():
    import json
    from pathlib import Path

    cfg = json.loads((Path(__file__).parents[2] / "benchmark/configs/ouro-2.6b.json").read_text())
    # 306.4M matmul weights: 2 x 306.4M x 511 x 3 = 0.939 TFLOP, attention 0.013
    assert train_step_flops(cfg, 1, 512) == pytest.approx(0.952e12, rel=2e-3)
