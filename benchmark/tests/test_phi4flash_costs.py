"""The decoder-hybrid configuration's kernels at their shapes: the new cost
function (``ssm1_scan``) against a hand count, the paged kernels' at PAIRS of
KV heads and 8 calls a tick, and its adapter's answers for the kernels its
cell's metrics read."""

import json
import os

from benchmark.spec import Spec

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
SPEC = Spec(os.path.join(ROOT, "BENCHMARK.json"))
CELL = "phi4flash_reasoning_long_sat"


def test_ssm1_scan_against_a_hand_count():
    cost = SPEC.load_module("kernel_costs", "ssm1_scan.py").cost
    # One live slot, 8 channels with a state of 4, float32: the state 8 x 4
    # values read and written; x, dt and y rows of 8, B and C rows of 4; 6
    # operations a state element.
    one = cost(contexts=[700], q_rows=1, channels=8, state=4, state_bytes=4)
    assert one == {"bytes": 2 * 32 * 4 + (3 * 8 + 2 * 4) * 4,
                   "flops": 6 * 32}
    # The published widths, 48 live slots: 2 x 327,680 B a slot a layer.
    full = cost(contexts=[1] * 48, q_rows=1, channels=5120, state=16,
                state_bytes=4)
    assert full["bytes"] == 48 * (2 * 327680 + (3 * 5120 + 32) * 4)
    assert full["flops"] == 48 * 6 * 5120 * 16
    assert cost(contexts=[], q_rows=1, channels=5120, state=16,
                state_bytes=4) == {"bytes": 0.0, "flops": 0.0}


def test_flash_decode_paged_at_8_calls_over_pairs_of_heads():
    cell = SPEC.cell(CELL)
    of_model, calls = cell.adapter().kernel_call(cell.config,
                                                 "flash_decode_paged")
    assert (of_model, calls) == (
        {"heads": 40, "kv_heads": 10, "head": 128, "dtype_bytes": 2}, 8)
    cost = SPEC.load_module("kernel_costs", "flash_decode_paged.py").cost
    # A slot at 3,260 tokens: keys and values 2 x 3260 x 10 x 128 bf16
    # (5,120 B a token: ONE layer's rows), 40 packed query rows in and out;
    # the shared layer and the seven cross layers each stream them once.
    got = cost(contexts=[3260], q_rows=1, **of_model)
    assert got["bytes"] == 3260 * 5120 + 2 * 40 * 128 * 2
    assert calls * got["bytes"] == 8 * (3260 * 5120 + 20480)


def test_the_adapter_answers_for_the_cells_kernels():
    cell = SPEC.cell(CELL)
    call = cell.adapter().kernel_call
    assert call(cell.config, "window_decode_paged") == (
        {"heads": 40, "kv_heads": 10, "head": 128, "dtype_bytes": 2,
         "window": 512}, 8)
    assert call(cell.config, "ssm1_scan") == (
        {"channels": 5120, "state": 16, "state_bytes": 4}, 9)
    for other in ("mla_decode_paged", "moe_grouped_matmul",
                  "moe_ungated_matmul", "ssm_decode_update",
                  "eva_local_decode", "eva_summary_decode"):
        assert call(cell.config, other) is None
    names = {m["name"] for m in cell.per_layer}
    assert {"ssm1_scan_ms_tick", "ssm1_scan_roofline",
            "ssm1_states_advanced_pct", "rows_past_exit_pct",
            "attn_kernel_ms_tick", "flash_decode_paged_roofline",
            "window_attn_ms_tick", "window_decode_paged_roofline",
            "window_blocks_held_pct", "dec_conv_ms_tick",
            "dec_attn_ms_tick"} <= names
    assert not {"ssm_update_ms_tick", "ssm_decode_update_roofline",
                "ssm_states_advanced_pct", "moe_ffn_ms_tick",
                "dec_moe_ms_tick", "mixer_rest_ms_tick",
                "mla_decode_ms_tick", "eva_local_ms_tick"} & names
    assert not any(n.startswith("mix_") for n in names)
    for name in names:
        assert SPEC.load_module("layer_metrics", name + ".py").read
    with open(os.path.join(ROOT, "BENCHMARK.json")) as f:
        bench = json.load(f)
    entry = [c for c in bench["configs"]
             if c["name"] == cell.config["name"]][0]
    assert entry["reduced"] == cell.config["reduced"] == []
    assert cell.traffic == SPEC.cell("kexaone_reasoning_long_sat").traffic
    assert len(bench["workloads"]) == 11
    assert all(w["chips"] == 1 for w in bench["workloads"])
