"""Operation and byte counts against hand counts at small shapes."""
import os
import sys

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

from chipbench import flops  # noqa: E402

D = {"L": 2, "D": 8, "H": 4, "KV": 2, "hd": 2, "F": 16, "V": 10}


def test_decode_attention_counts_active_rows_to_their_length():
    # rows attend to 3 and 5 positions: per layer q.k and p.v over 8 positions,
    # 4 heads of 2: 2 * 2 * 8 * 4 * 2 = 256 operations; 2 layers
    w = flops.decode_attn(D, rows=2, sum_ctx=8)
    assert w["flops"] == 2 * 256
    # K and V: 8 positions x 2 kv heads x 2 dims x 2 tensors; q and out: 2 rows
    # x 4 heads x 2 dims x 2 tensors; 2 bytes each, 2 layers
    assert w["bytes"] == 2 * 2 * (8 * 2 * 2 * 2 + 2 * 4 * 2 * 2)


def test_flash_prefill_is_causal():
    # n = 3: 1 + 2 + 3 = 6 query-key pairs, 4 operations per pair per dim-head
    w = flops.flash_prefill(D, 3)
    assert w["flops"] == 2 * 4 * 4 * 2 * 6
    assert w["bytes"] == 2 * 2 * 3 * 2 * (2 * 4 + 2 * 2)


def test_model_counts():
    per_layer = 8 * (4 + 2 * 2) * 2 + 4 * 2 * 8 + 3 * 8 * 16
    assert flops.matmul_params(D) == per_layer
    head = 8 * 10
    assert flops.model_prefill(D, 3) == 2 * 3 * 2 * per_layer + 2 * head + 2 * 4 * 4 * 2 * 6
    assert flops.model_decode(D, 2, 8) == 2 * (2 * (2 * per_layer + head)) + 2 * 256


def test_least_time_takes_the_larger_bound():
    peaks = {"bf16_flops_per_s": 100.0, "hbm_bytes_per_s": 10.0}
    assert flops.least_time({"flops": 100.0, "bytes": 50.0}, peaks) == 5.0
    assert flops.least_time({"flops": 1000.0, "bytes": 5.0}, peaks) == 10.0
