"""Each kernel's cost function against a hand count."""

import os

from benchmark.spec import Spec

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))


def test_flash_decode_paged_against_a_hand_count():
    cost = Spec(os.path.join(ROOT, "BENCHMARK.json")).load_module(
        "kernel_costs", "flash_decode_paged.py").cost
    # One slot, 64 tokens of context, one head of 128, bf16: K and V are
    # 64 x 128 x 2 bytes each; the query row and the output row 128 x 2 each;
    # QK^T and PV are 2 x 128 x 64 operations each.
    one = cost(contexts=[64], q_rows=1, heads=1, kv_heads=1, head=128,
               dtype_bytes=2)
    assert one == {"bytes": 2 * 64 * 128 * 2 + 2 * 128 * 2,
                   "flops": 2 * (2 * 128 * 64)}
    # Yi-6B's shape: 32 query heads over 4 KV heads; two slots.
    two = cost(contexts=[1000, 3000], q_rows=1, heads=32, kv_heads=4,
               head=128, dtype_bytes=2)
    assert two["bytes"] == 2 * 4000 * 4 * 128 * 2 + 2 * (2 * 32 * 128 * 2)
    assert two["flops"] == 4 * 32 * 128 * 4000
    assert cost(contexts=[], q_rows=1, heads=32, kv_heads=4, head=128,
                dtype_bytes=2) == {"bytes": 0.0, "flops": 0.0}
