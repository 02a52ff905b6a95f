"""The benchmark's work counts against hand counts for a tiny configuration."""
import os
import sys

os.environ.setdefault("JAX_PLATFORMS", "cpu")
sys.path.insert(0, os.path.join(os.path.dirname(__file__), ".."))

import work  # noqa: E402
from traffic import Tenant  # noqa: E402

CFG = {"hidden_size": 8, "intermediate_size": 16, "num_hidden_layers": 2,
       "num_attention_heads": 2, "num_key_value_heads": 1, "head_dim": 4,
       "vocab_size": 32, "block": {"family": "dense", "mlp": "swiglu"}}


def tenant(method, rank, lengths, targets=("attn_q",)):
    return Tenant("t", method, rank, targets, tuple(lengths), 8, 1e-3, 16.0)


def test_matmul_params_by_hand():
    # q 8x8, k 8x4, v 8x4, o 8x8, gate/up 8x16, down 16x8 = 64+32+32+64+384
    per_layer = 576
    assert work.matmul_params(CFG) == 2 * per_layer + 8 * 32


def test_attention_pairs_by_hand():
    # causal pairs of lengths 3 and 1: 6 + 1; with 2 prefix rows: + 2*4
    assert work.attention_pairs([3, 1]) == 7
    assert work.attention_pairs([3, 1], prefix_rows=2) == 15


def test_train_flops_by_hand():
    t = tenant("lora", 2, [3, 1])
    mm = 4 * work.matmul_params(CFG) * 4             # 4 tokens
    attn = 3 * 4 * 4 * 2 * 2 * 7                     # 3x fwd, 4 dh H L, 7 pairs
    lora = 3 * 2 * 2 * (8 + 8) * 2 * 4               # 3x fwd, r (din+dout), L, tokens
    assert work.train_flops(CFG, [t]) == mm + attn + lora


def test_prefix_counts_its_rows_as_pairs():
    t = tenant("prefix", 2, [3])
    mm = 4 * work.matmul_params(CFG) * 3
    attn = 3 * 4 * 4 * 2 * 2 * (6 + 3 * 2)
    assert work.train_flops(CFG, [t]) == mm + attn

