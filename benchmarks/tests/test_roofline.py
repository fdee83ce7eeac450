"""Each operation and byte count against a hand count for one layer."""
from types import SimpleNamespace

from benchmarks.roofline import (common, paged_decode_multi, paged_prefill_batch,
                                 train_step)

# Mistral-7B widths, one layer, by hand:
#   wq 4096x4096, wk and wv 4096x1024, wo 4096x4096      = 41,943,040
#   gate, up, down 3 x 4096x14336                         = 176,160,768
MISTRAL_LAYER = 41_943_040 + 176_160_768
HEAD = 4096 * 32768


def cfg(layers=1):
    return SimpleNamespace(d_model=4096, head_dim=128, n_heads=32, n_kv_heads=8,
                           d_ff=14336, vocab_size=32768, n_layers=layers,
                           dtype="bfloat16")


def test_layer_and_head_parameters():
    assert common.layer_matmul_params(cfg()) == MISTRAL_LAYER == 218_103_808
    assert common.head_params(cfg()) == HEAD == 134_217_728


def test_decode_bytes_and_operations_one_layer():
    # 1000 live tokens: keys and values, 8 heads x 128 x 2 bytes each
    kv = 1000 * 2 * 8 * 128 * 2
    assert paged_decode_multi.bytes_per_step(cfg(), 1000) == (
        (MISTRAL_LAYER + HEAD) * 2 + kv) == 708_739_072
    # 16 slots: 2 operations a weight a slot; attention 2 x 2 x 32 heads x 128
    assert paged_decode_multi.flops_per_step(cfg(), 16, 1000) == (
        2 * 16 * (MISTRAL_LAYER + HEAD) + 4 * 1000 * 32 * 128)
    peaks = {"hbm_bytes_per_s": 819e9, "bf16_flops_per_s": 197e12}
    # bytes bound: 0.865 ms against 0.057 ms of operations
    assert abs(paged_decode_multi.least_seconds(cfg(), peaks, 16, 1000)
               - 708_739_072 / 819e9) < 1e-12


def test_prefill_operations_one_layer():
    # one prompt of 1000 tokens: matmuls 2 x 1000 x layer; causal attention
    # 2 matmuls x 2 operations x (1000 x 1000 / 2) pairs x 4096; head once
    want = 2 * 1000 * MISTRAL_LAYER + 2 * 1000 * 1000 * 4096 + 2 * HEAD
    assert paged_prefill_batch.flops(cfg(), [1000]) == want
    assert paged_prefill_batch.flops(cfg(), [1000, 1000]) == 2 * want


def test_train_operations_one_layer():
    # forward 2 and backward 4 a weight; attention 2*T*d forward, 4*T*d backward
    want = 6 * (MISTRAL_LAYER + HEAD) + 6 * 4096 * 4096
    assert train_step.flops_per_token(cfg(), 4096) == want
    three = train_step.flops_per_token(cfg(3), 4096)
    assert three == 6 * (3 * MISTRAL_LAYER + HEAD) + 3 * 6 * 4096 * 4096
