"""The EVA configuration's two cost functions against a hand count."""

import os

from benchmark.spec import Spec

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
SPEC = Spec(os.path.join(ROOT, "BENCHMARK.json"))

EVA = dict(heads=32, kv_heads=32, head=128, window=2048, chunk=16,
           dtype_bytes=2)


def test_eva_local_decode_against_a_hand_count():
    cost = SPEC.load_module("kernel_costs", "eva_local_decode.py").cost
    # A row at position 4,999 (its slot then holds 5,000) sees its own
    # aligned window [4096, 4999]: 904 rows, not the last 2,048; the first
    # row of a window (context 2,049: position 2,048) sees itself alone;
    # the last (context 2,048: position 2,047) the whole window.
    row = 32 * 128 * 2                       # one K or V row, every head
    ends = 32 * (2 * 128 * 2 + 4)            # q in, out and lse out, a slot
    for context, seen in ((5000, 904), (2049, 1), (2048, 2048), (100, 100)):
        c = cost(contexts=[context], q_rows=1, **EVA)
        assert c == {"bytes": 2 * seen * row + ends,
                     "flops": 4 * 32 * 128 * seen}, context
    two = cost(contexts=[5000, 2049], q_rows=1, **EVA)
    assert two["bytes"] == 2 * 905 * row + 2 * ends
    # A chunk of 256 rows that straddles a boundary keeps the old window:
    # rows 1,900..2,155 see from 0 on.
    assert cost(contexts=[2156], q_rows=256, **EVA)["bytes"] \
        == 2 * 2156 * row + 256 * ends
    assert cost(contexts=[], q_rows=1, **EVA) == {"bytes": 0.0, "flops": 0.0}


def test_eva_summary_decode_against_a_hand_count():
    cost = SPEC.load_module("kernel_costs", "eva_summary_decode.py").cost
    row = 32 * 128 * 2
    ends = 32 * (2 * 128 * 2 + 4)
    # Position 4,999 has two closed windows behind it: 2 x 128 summary
    # rows; position 2,047 (the last of the first window) has none, and
    # such a slot costs nothing, its queries neither.
    c = cost(contexts=[5000], q_rows=1, **EVA)
    assert c == {"bytes": 2 * 256 * row + ends, "flops": 4 * 32 * 128 * 256}
    assert cost(contexts=[2048, 100], q_rows=1, **EVA) \
        == {"bytes": 0.0, "flops": 0.0}
    assert cost(contexts=[2049], q_rows=1, **EVA)["bytes"] \
        == 2 * 128 * row + ends
    assert cost(contexts=[5000, 2048, 16384], q_rows=1, **EVA)["bytes"] \
        == 2 * (256 + 896) * row + 2 * ends
