"""The yardstick's arithmetic against hand counts."""
import pytest

from bench.lib import flops
from bench.lib import harness as H


def test_factor_update_counts():
    f, b = flops.factor_update(8192, 1536)
    assert f == 2 * 8192 * 1536 ** 2 + 3 * 1536 ** 2
    assert b == 4 * (8192 * 1536 + 2 * 1536 ** 2)
    f30, b30 = flops.factor_update(8192, 1536, stack=30)
    assert (f30, b30) == (30 * f, 30 * b)


def test_tile_rule():
    assert flops.tile_ok(8192, 1536) and flops.tile_ok(64)
    assert not flops.tile_ok(576) and not flops.tile_ok(192)
    assert not flops.tile_ok(785)


def test_llama_flops_by_hand():
    cfg = {"hidden_size": 8, "num_attention_heads": 2,
           "num_key_value_heads": 1, "intermediate_size": 16,
           "vocab_size": 32, "num_hidden_layers": 3}
    hd, kv = 4, 4
    layer = 8 * 8 + 2 * 8 * kv + 8 * 8 + 3 * 8 * 16
    mm = 3 * layer + 8 * 32
    attn = 3 * 2 * 2 * 1 * 2 * hd * 5 * 6 / 2
    assert flops.llama_train_flops(cfg, 1, 5) == 6 * mm * 5 + 3 * attn


def test_mlp_flops_by_hand():
    assert flops.mlp_train_flops([4, 3, 2], 10) == 6 * 10 * (5 * 3 + 4 * 2)


def test_seed_helpers_take_large_seeds():
    big = 2 ** 31 + 12345
    assert 0 <= H.seed31(big) < 2 ** 31
    assert H.seed31(big) != H.seed31(12345)
    k1, k2 = H.seed_key(big), H.seed_key(12345)
    assert (k1 != k2).any()
