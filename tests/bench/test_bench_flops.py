"""The operation counts against hand counts, and the table of peaks."""
import pytest

from bench import flops, peaks

M = {"hidden_size": 4, "intermediate_size": 6, "num_hidden_layers": 2,
     "num_attention_heads": 2, "num_key_value_heads": 1, "head_dim": 2,
     "vocab_size": 10}


def test_layer_params_by_hand():
    # q 4x4, k 4x2, v 4x2, o 4x4, gate/up 4x6 each, down 6x4
    assert flops.layer_matmul_params(M) == 16 + 8 + 8 + 16 + 24 + 24 + 24


def test_prefill_flops_by_hand():
    n = 3
    dense = 2 * n * 2 * 120
    attn = 4 * 2 * 2 * 2 * (1 + 2 + 3)  # QK and PV over each causal prefix
    head = 2 * 4 * 10
    assert flops.prefill_flops(M, n) == dense + attn + head


def test_decode_flops_by_hand():
    ctx = 5
    assert flops.decode_flops(M, ctx) == 2 * 2 * 120 + 4 * 2 * 2 * 2 * ctx + 2 * 4 * 10


def test_unknown_device_kind_is_an_error():
    with pytest.raises(KeyError):
        peaks.peaks_for("cpu")


def test_scan_cost_by_hand():
    # 3 groups of 8 query rows over 100 valid rows in all, d 4, k 2, a
    # 16-slot float32 slab
    fl, by = flops.scan_cost(3, 2, 100, 8, 4, 16, 4)
    assert fl == 2 * 8 * 100 * 4
    assert by == 100 * 4 * 4 + 3 * 8 * 4 * 4 + 3 * 8 * 2 * (4 + 4) + 3 * 4 + 16 * 4
