"""The state configuration's two cost functions against a hand count, and its
adapter's answers for the kernels its cell's metrics read."""

import json
import os

from benchmark.spec import Spec

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
SPEC = Spec(os.path.join(ROOT, "BENCHMARK.json"))


def test_ssm_decode_update_against_a_hand_count():
    cost = SPEC.load_module("kernel_costs", "ssm_decode_update.py").cost
    # One live slot, 2 heads of 4 with a state of 8 in one group, float32:
    # the state 2 x 4 x 8 values read and written; x and y rows of 8, B and C
    # rows of 8, 2 step sizes; 6 operations a state element.
    one = cost(contexts=[700], q_rows=1, heads=2, head=4, state=8, groups=1,
               state_bytes=4)
    assert one == {"bytes": 2 * 64 * 4 + (2 * 8 + 2 * 8 + 2) * 4,
                   "flops": 6 * 64}
    # The published widths, 64 live slots: 2 x 4.19 MB a slot a layer.
    full = cost(contexts=[1] * 64, q_rows=1, heads=128, head=64, state=128,
                groups=8, state_bytes=4)
    assert full["bytes"] == 64 * (2 * 128 * 64 * 128 * 4
                                  + (2 * 8192 + 2 * 1024 + 128) * 4)
    assert cost(contexts=[], q_rows=1, heads=128, head=64, state=128,
                groups=8, state_bytes=4) == {"bytes": 0.0, "flops": 0.0}


def test_moe_ungated_matmul_counts_two_matrices_in_the_latent():
    cost = SPEC.load_module("kernel_costs", "moe_ungated_matmul.py").cost
    got = cost(latent=1024, width=2688, dtype_bytes=2, experts_held=128,
               experts_touched=10, pairs=30)
    assert got["bytes"] == 10 * 2 * 1024 * 2688 * 2 \
        + 30 * 2 * (1024 + 2688) * 2
    assert got["flops"] == 30 * 4 * 1024 * 2688
    # A gated expert on the residual's width would be 1.5 x 4 times that.
    gated = SPEC.load_module("kernel_costs", "moe_grouped_matmul.py").cost(
        hidden=1024, width=2688, dtype_bytes=2, experts_touched=10, pairs=30)
    assert gated["flops"] == 1.5 * got["flops"]
    assert cost(latent=1024, width=2688, dtype_bytes=2, contexts=[5],
                q_rows=1) == {"bytes": 0.0, "flops": 0.0}


def test_the_adapter_answers_for_the_cells_kernels():
    cell = SPEC.cell("nemotron3s_agentturn_sat")
    call = cell.adapter().kernel_call
    assert call(cell.config, "ssm_decode_update") == (
        {"heads": 128, "head": 64, "state": 128, "groups": 8,
         "state_bytes": 4}, 5)
    assert call(cell.config, "moe_ungated_matmul") == (
        {"latent": 1024, "width": 2688, "experts_held": 128,
         "dtype_bytes": 2}, 5)
    assert call(cell.config, "flash_decode_paged") == (
        {"heads": 32, "kv_heads": 2, "head": 128, "dtype_bytes": 2}, 1)
    assert call(cell.config, "moe_grouped_matmul")[0]["experts_held"] == 128
    assert call(cell.config, "mla_decode_paged") is None
    names = {m["name"] for m in cell.per_layer}
    assert {"ssm_update_ms_tick", "ssm_decode_update_roofline",
            "moe_ungated_ms_tick", "moe_ungated_matmul_roofline",
            "ssm_states_advanced_pct", "dec_conv_ms_tick"} <= names
    assert not {"moe_ffn_ms_tick", "moe_grouped_matmul_roofline",
                "mixer_rest_ms_tick"} & names
    with open(os.path.join(ROOT, "BENCHMARK.json")) as f:
        entry = [c for c in json.load(f)["configs"]
                 if c["name"] == cell.config["name"]][0]
    assert entry["reduced"] == cell.config["reduced"]
